"""The runner's profiler spans and run counters.

A traced run holds, inside each ``runner:run`` span, the runner's
``runner:gen`` / ``runner:pack`` / ``runner:eval`` / ``runner:drain``
spans and the ``dispatch:*`` enqueue spans; the uploads of a block's
arguments sit under ``runner:pack``, never under ``dispatch:*``; and the
``runner:run`` span carries the run's :class:`RunCounters` as metadata.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import topology
from repro.core.baselines import make_scheduler
from repro.core.runner import DecentralizedTrainer, RunCounters
from repro.core.straggler import StragglerModel
from repro.data.synthetic import ClassificationData

N = 16
DATA = ClassificationData(n_workers=N, d=16, n_classes=4,
                          samples_per_worker=64, seed=0)
CHILDREN = ("runner:gen", "runner:pack", "runner:eval", "runner:drain")
UPLOAD = "DevicePut"        # host events of a host-to-device upload


def loss_fn(params, batch):
    logp = jax.nn.log_softmax(batch["x"] @ params["w"])
    return -jnp.mean(jnp.take_along_axis(logp, batch["y"][:, None], axis=1))


def _trainer(alg, mode, sched_kw=None, **kw):
    g = topology.erdos_renyi(N, 0.4, seed=3)
    sm = StragglerModel(n=N, straggler_prob=0.2, slowdown=6.0, seed=0)
    return DecentralizedTrainer(
        make_scheduler(alg, g, sm, **(sched_kw or {})), loss_fn,
        lambda k: {"w": jax.random.normal(k, (16, 4)) * 0.1},
        lambda w, s: DATA.batch(w, s, batch_size=8), DATA.eval_batch(64),
        mode=mode, block_size=8, **kw)


def _traced_run(tmp_path, tr, **run_kw):
    """Host events of one traced ``run()``: [(name, start, end, stats)]."""
    from jax.profiler import ProfileData

    tr.warmup()
    tr.run(**run_kw)        # compiles everything the traced run uses
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        res = tr.run(**run_kw)
    finally:
        jax.profiler.stop_trace()
    (xp,) = tmp_path.rglob("*.xplane.pb")
    evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
            {k: v for k, v in ev.stats})
           for plane in ProfileData.from_file(str(xp)).planes
           if plane.name.startswith("/host:")
           for line in plane.lines for ev in line.events]
    return res, evs


def _inside(ev, outer):
    return outer[1] <= ev[1] and ev[2] <= outer[2]


MODES = [("dsgd_aau", "sparse_scan"), ("ad_psgd", "sparse_scan"),
         ("dsgd_aau", "scan")]


@pytest.mark.parametrize("alg, mode", MODES)
def test_spans_nest_inside_run(tmp_path, alg, mode):
    tr = _trainer(alg, mode)
    _, evs = _traced_run(tmp_path, tr, max_events=32, eval_every=16)
    (run,) = [e for e in evs if e[0] == "runner:run"]
    for name in CHILDREN + ("dispatch:",):
        spans = [e for e in evs if e[0].startswith(name)]
        assert spans, name
        assert all(_inside(e, run) for e in spans), name
    packs = [e for e in evs if e[0] == "runner:pack"]
    dispatches = [e for e in evs if e[0].startswith("dispatch:")]
    # every enqueue comes after a pack, and no pack is inside an enqueue
    assert len(dispatches) >= 1 and packs[0][1] < dispatches[0][1]
    assert not any(_inside(p, d) for p in packs for d in dispatches)


@pytest.mark.parametrize("alg, mode", MODES)
def test_uploads_are_packed_not_dispatched(tmp_path, alg, mode):
    tr = _trainer(alg, mode)
    _, evs = _traced_run(tmp_path, tr, max_events=32, eval_every=16)
    uploads = [e for e in evs if UPLOAD in e[0]]
    packs = [e for e in evs if e[0] == "runner:pack"]
    dispatches = [e for e in evs if e[0].startswith("dispatch:")]
    assert uploads
    assert not any(_inside(u, d) for u in uploads for d in dispatches)
    # the block's event arrays are uploaded while packing
    assert sum(any(_inside(u, p) for p in packs) for u in uploads) >= 4


@pytest.mark.parametrize("alg, mode", MODES)
def test_run_span_carries_the_run_counters(tmp_path, alg, mode):
    tr = _trainer(alg, mode)
    res, evs = _traced_run(tmp_path, tr, max_events=32, eval_every=16)
    (run,) = [e for e in evs if e[0] == "runner:run"]
    stats = run[3]
    assert stats["events"] == res.total_events == 32
    # the traced run is the second of two equal runs
    assert tr.counters.events == 2 * 32
    assert stats["blocks"] == len([e for e in evs
                                   if e[0].startswith("dispatch:")])
    assert stats["rows"] >= stats["blocks"]
    assert 0 < stats["grad"] <= stats["active"]


def test_counters_count_events_and_lanes():
    tr = _trainer("dsgd_aau", "sparse_scan", trace=True)
    res = tr.run(max_events=40, eval_every=20)
    c = tr.counters
    t = tr.last_trace
    assert c.events == res.total_events == t.n_events == 40
    assert c.active == t.n_lanes
    assert c.grad == int(t.lane_grad.sum())
    assert c.restarts == int(t.lane_restart.sum())
    assert c.blocks >= 1 and c.rows >= c.blocks


def test_warmup_counts_nothing():
    tr = _trainer("ad_psgd", "sparse_scan")
    tr.warmup()
    assert tr.counters == RunCounters()


def test_since_is_a_difference():
    a = RunCounters(events=3, blocks=1, rows=8, active=6, grad=3,
                    restarts=3, grad_evaluated=128)
    b = RunCounters(events=5, blocks=2, rows=16, active=10, grad=5,
                    restarts=4, grad_evaluated=256)
    assert b.since(a) == {"events": 2, "blocks": 1, "rows": 8, "active": 4,
                          "grad": 2, "restarts": 1, "grad_evaluated": 128}
    a.add(events=np.int64(2), blocks=1)
    assert (a.events, a.blocks) == (5, 2)


class _DispatchSink:
    """File-like run log keeping the ``block_dispatch`` records."""

    def __init__(self):
        self.records = []

    def write(self, line):
        if '"block_dispatch"' in line:
            self.records.append(json.loads(line))

    def flush(self):
        pass


@pytest.mark.parametrize("alg", ["dsgd_aau", "dsgd_sync"])
def test_dense_scan_evaluates_n_lanes_a_row(alg):
    sink = _DispatchSink()
    tr = _trainer(alg, "scan", run_log=sink)
    tr.warmup()
    sink.records.clear()
    before = dataclasses.replace(tr.counters)
    tr.run(max_events=20, eval_every=10)
    got = tr.counters.since(before)
    rows = sum(r["padded"] for r in sink.records)
    assert got["rows"] == rows
    assert got["grad_evaluated"] == N * rows
    assert got["grad"] <= got["grad_evaluated"]


@pytest.mark.parametrize("alg", ["dsgd_aau", "ad_psgd"])
def test_sparse_blocks_evaluate_rung_lanes_a_merged_step(alg):
    sink = _DispatchSink()
    # DSGD-AAU's default ladder at N=16 is one rung as wide as n, which
    # merges nothing: a narrower first rung does
    tr = _trainer(alg, "sparse_scan", run_log=sink,
                  sched_kw={"buckets": (4, N)} if alg == "dsgd_aau" else None)
    tr.warmup()
    sink.records.clear()
    before = dataclasses.replace(tr.counters)
    tr.run(max_events=40, eval_every=20)
    got = tr.counters.since(before)
    merged = [r for r in sink.records if r["merged"]]
    assert merged, "the stream merged no events"
    budgets = {A * tr._events_per_step(A)
               for A in tr.scheduler.active_buckets()
               if tr._events_per_step(A) > 1}
    for r in merged:
        # a merged step is K events of the rung's width A, packed together
        assert r["lanes"] in budgets
    # padded rows skip the update: only the block's real steps count
    assert got["grad_evaluated"] == sum(r["events"] * r["lanes"]
                                        for r in sink.records)
    assert got["grad"] <= got["grad_evaluated"] <= sum(
        r["padded"] * r["lanes"] for r in sink.records)


@pytest.mark.parametrize("n", [32, 128, 256])
def test_merge_budget_is_no_wider_than_n(n):
    g = topology.erdos_renyi(n, 0.15, seed=1)
    sm = StragglerModel(n=n, straggler_prob=0.2, slowdown=6.0, seed=0)
    tr = DecentralizedTrainer(
        make_scheduler("dsgd_aau", g, sm), loss_fn,
        lambda k: {"w": jnp.zeros((16, 4))},
        lambda w, s: DATA.batch(0, s, batch_size=8), DATA.eval_batch(64),
        mode="sparse_scan")
    for A in tr.scheduler.active_buckets() + (2,):     # AAU rungs, pairs
        K = tr._events_per_step(A)
        assert K * A <= max(A, n)
        if n >= 64:
            assert K == int(np.clip(64 // A, 1, 16))


def test_per_event_counts_its_lanes():
    tr = _trainer("dsgd_aau", "per_event")
    tr.run(max_events=6, eval_every=3)
    assert tr.counters.grad_evaluated == 6 * N
