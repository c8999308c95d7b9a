"""Virtual-time tracing (repro/obs/trace + critical_path): cross-mode
trace equality, Chrome-trace schema validity, the wait-blame oracle,
zero trajectory drift, and consistency with the telemetry counters.

The contract under test:

- the finalized :class:`Trace` is **bit-identical** across ``per_event``,
  ``scan`` and ``sparse_scan`` (incl. bucketed dispatch) of the same
  scheduler stream — all four host modes record the pre-merge, pre-pad
  identity stream the driving loop already holds;
- tracing is a pure observer: trajectories are bit-identical with it on
  or off;
- ``Σ blame + residual_wait == Σ wait`` exactly, and the blame pass's
  busy/wait vectors reproduce telemetry's ``busy_t``/``idle_t`` (f64 vs
  f32 tolerance) — the blame table is a lossless decomposition of the
  utilization numbers;
- the critical path tiles ``[0, t_end]``: ``compute_t + wait_t == t_end``
  and consecutive segments abut exactly;
- :func:`chrome_trace` emits a valid Chrome Trace Event Format document
  (JSON-serializable, complete spans, paired flow arrows).
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import topology
from repro.core.baselines import make_scheduler
from repro.core.runner import DecentralizedTrainer
from repro.core.straggler import StragglerModel
from repro.data.synthetic import ClassificationData
from repro.obs.critical_path import (attribute_wait, critical_path,
                                     straggler_tax)
from repro.obs.trace import Trace, chrome_trace

N = 16
DATA = ClassificationData(n_workers=N, d=16, n_classes=4,
                          samples_per_worker=64, seed=0)


def loss_fn(params, batch):
    logits = batch["x"] @ params["w"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, batch["y"][:, None], axis=1))


def init_fn(key):
    return {"w": jax.random.normal(key, (16, 4)) * 0.1}


def _sched(alg, seed=0, slowdown=6.0, **kw):
    g = topology.erdos_renyi(N, 0.4, seed=3)
    sm = StragglerModel(n=N, straggler_prob=0.2, slowdown=slowdown,
                        seed=seed)
    return make_scheduler(alg, g, sm, **kw)


def _trainer(alg, mode, seed=0, sched_kw=None, **kw):
    kw.setdefault("trace", True)
    return DecentralizedTrainer(
        _sched(alg, seed, **(sched_kw or {})), loss_fn, init_fn,
        lambda w, s: DATA.batch(w, s, batch_size=8),
        DATA.eval_batch(64), eta0=0.2, eta_decay=0.99, seed=seed,
        mode=mode, **kw)


_TRACE_FIELDS = ("times", "copies", "lane_ev", "lane_worker", "lane_fin",
                 "lane_grad", "lane_restart", "edge_ev", "edge_src",
                 "edge_dst")


def _assert_trace_equal(a: Trace, b: Trace, ctx=""):
    assert a.n == b.n, ctx
    for f in _TRACE_FIELDS:
        va, vb = getattr(a, f), getattr(b, f)
        if va.dtype == np.float64:  # compare clocks bitwise, not approx
            va, vb = va.view(np.uint64), vb.view(np.uint64)
        np.testing.assert_array_equal(va, vb,
                                      err_msg=f"{ctx}: Trace.{f} differs")


class TestCrossModeTraceEqual:
    """per_event / scan / sparse_scan record bit-identical traces."""

    EVENTS = 60

    @pytest.mark.parametrize("alg,sched_kw", [
        ("dsgd_aau", {"buckets": (4, 8, 16)}),   # forces bucketed dispatch
        ("ad_psgd", {}),
        ("agp", {}),
        ("prague", {}),
    ])
    def test_modes_bit_identical(self, alg, sched_kw):
        traces, summaries = {}, {}
        for mode in ("per_event", "scan", "sparse_scan"):
            tr = _trainer(alg, mode, sched_kw=sched_kw)
            res = tr.run(max_events=self.EVENTS, eval_every=20)
            traces[mode] = tr.last_trace
            summaries[mode] = res.trace
        _assert_trace_equal(traces["per_event"], traces["scan"],
                            f"{alg} per_event vs scan")
        _assert_trace_equal(traces["per_event"], traces["sparse_scan"],
                            f"{alg} per_event vs sparse_scan")
        # the blame summaries are pure functions of the trace, minus the
        # mode tag itself
        for mode in ("scan", "sparse_scan"):
            s, ref = dict(summaries[mode]), dict(summaries["per_event"])
            s.pop("mode"), ref.pop("mode")
            assert s == ref, f"{alg}: summary drift in {mode}"

    def test_sync_scan_matches_per_event(self):
        traces = {}
        for mode in ("per_event", "scan"):
            tr = _trainer("dsgd_sync", mode)
            tr.run(max_events=48, eval_every=16)
            traces[mode] = tr.last_trace
        _assert_trace_equal(traces["per_event"], traces["scan"],
                            "dsgd_sync per_event vs scan")

    def test_trace_is_well_formed(self):
        tr = _trainer("dsgd_aau", "sparse_scan")
        res = tr.run(max_events=self.EVENTS, eval_every=20)
        t = tr.last_trace
        assert t.n_events == res.total_events
        assert (np.diff(t.lane_ev) >= 0).all()        # stream order
        assert (np.diff(t.edge_ev) >= 0).all()
        assert (np.diff(t.times) >= 0).all()          # commit clocks sorted
        assert (t.lane_fin <= t.times[t.lane_ev] + 1e-6).all()
        assert int(t.copies.sum()) == res.total_comm_copies
        assert t.algorithm == "dsgd_aau" and t.mode == "sparse_scan"


class TestBlameOracle:
    """Hand-built 3-worker schedule with known attribution."""

    @staticmethod
    def _trace():
        # ev0 @ t=4.0: all three restart, fins (2, 4, 3)  → gate w1
        # ev1 @ t=7.5: w0, w1 restart,    fins (6, 7)     → gate w1,
        #              commit 0.5 after the gate fin → residual 2·0.5
        # ev2 @ t=9.0: w2 restarts alone, fin 9           → gate w2
        return Trace(
            n=3,
            times=np.array([4.0, 7.5, 9.0]),
            copies=np.array([4, 2, 0], dtype=np.int64),
            lane_ev=np.array([0, 0, 0, 1, 1, 2], dtype=np.int64),
            lane_worker=np.array([0, 1, 2, 0, 1, 2], dtype=np.int32),
            lane_fin=np.array([2.0, 4.0, 3.0, 6.0, 7.0, 9.0]),
            lane_grad=np.ones(6, dtype=bool),
            lane_restart=np.ones(6, dtype=bool),
            edge_ev=np.array([0, 0, 1], dtype=np.int64),
            edge_src=np.array([0, 1, 0], dtype=np.int32),
            edge_dst=np.array([1, 2, 1], dtype=np.int32),
            algorithm="oracle")

    def test_attribution_matches_hand_computation(self):
        attr = attribute_wait(self._trace())
        np.testing.assert_allclose(attr["blame"], [0.0, 4.0, 0.0])
        np.testing.assert_allclose(attr["busy"], [4.0, 7.0, 8.0])
        np.testing.assert_allclose(attr["wait"], [3.5, 0.5, 1.0])
        assert attr["residual_wait"] == pytest.approx(1.0)
        np.testing.assert_array_equal(attr["gate_worker"], [1, 1, 2])
        np.testing.assert_allclose(attr["gate_fin"], [4.0, 7.0, 9.0])
        # gate DAG edges: ev0's gate had no prior restart; ev1's gate (w1)
        # last restarted at ev0; ev2's gate (w2) likewise
        np.testing.assert_array_equal(attr["gate_prev_ev"], [-1, 0, 0])
        np.testing.assert_allclose(attr["gate_prev_t"], [0.0, 4.0, 4.0])

    def test_critical_path_walks_gates(self):
        cp = critical_path(self._trace())
        # backward from ev2 (gate w2, started at ev0's commit) to ev0
        assert [s["event"] for s in cp["segments"]] == [0, 2]
        assert [s["worker"] for s in cp["segments"]] == [1, 2]
        assert cp["compute_t"] == pytest.approx(9.0)
        assert cp["wait_t"] == pytest.approx(0.0)
        assert cp["t_end"] == pytest.approx(9.0)

    def test_summary(self):
        s = straggler_tax(self._trace())
        assert s["blame_total"] == pytest.approx(4.0)
        assert s["residual_wait"] == pytest.approx(1.0)
        # blame_total + residual ≡ total wait, tax = wait / (busy + wait)
        assert s["wait_t"] == pytest.approx(5.0)
        # summary fields round to 6 decimals (JSON friendliness)
        assert s["straggler_tax"] == pytest.approx(5.0 / 24.0, abs=1e-6)
        assert s["blame_top"][0] == {"worker": 1, "blame_t": 4.0,
                                     "share": 1.0}


class TestAttributionInvariants:
    """Blame ≡ wait decomposition; agreement with telemetry counters."""

    @pytest.mark.parametrize("alg,sched_kw", [
        ("dsgd_aau", {"buckets": (4, 8, 16)}),
        ("ad_psgd", {}),
        ("dsgd_sync", {}),
    ])
    def test_blame_plus_residual_is_total_wait(self, alg, sched_kw):
        tr = _trainer(alg, "scan" if alg == "dsgd_sync" else "sparse_scan",
                      sched_kw=sched_kw)
        tr.run(max_events=60, eval_every=20)
        attr = attribute_wait(tr.last_trace)
        total_wait = float(attr["wait"].sum())
        assert float(attr["blame"].sum()) + float(attr["residual_wait"]) \
            == pytest.approx(total_wait, rel=1e-9, abs=1e-9)
        if alg == "ad_psgd":
            # single-finisher gates: all wait is protocol (lock) residual
            assert float(attr["blame"].sum()) == 0.0

    def test_matches_telemetry_counters(self):
        tr = _trainer("dsgd_aau", "sparse_scan", telemetry=True)
        tr.run(max_events=60, eval_every=20)
        attr = attribute_wait(tr.last_trace)
        M = jax.device_get(tr._metrics)
        np.testing.assert_allclose(attr["busy"], np.asarray(M.busy_t),
                                   rtol=1e-5, atol=1e-4)
        np.testing.assert_allclose(attr["wait"], np.asarray(M.idle_t),
                                   rtol=1e-5, atol=1e-4)

    def test_critical_path_tiles_the_run(self):
        tr = _trainer("dsgd_aau", "sparse_scan")
        tr.run(max_events=60, eval_every=20)
        trace = tr.last_trace
        cp = critical_path(trace)
        assert cp["compute_t"] + cp["wait_t"] == pytest.approx(
            cp["t_end"], rel=1e-9)
        segs = cp["segments"]
        assert segs[0]["t_start"] == 0.0
        assert segs[-1]["t_commit"] == pytest.approx(float(trace.times[-1]))
        for a, b in zip(segs, segs[1:]):  # consecutive segments abut
            assert b["t_start"] == pytest.approx(a["t_commit"])


class TestZeroDrift:
    """Tracing is a pure observer: bit-identical state with it on/off."""

    @pytest.mark.parametrize("alg,mode", [
        ("dsgd_aau", "scan"),
        ("dsgd_aau", "sparse_scan"),
        ("dsgd_aau", "per_event"),
        ("ad_psgd", "sparse_scan"),
    ])
    def test_state_and_history_identical(self, alg, mode):
        results = {}
        for on in (False, True):
            tr = _trainer(alg, mode, trace=on)
            res = tr.run(max_events=48, eval_every=16)
            results[on] = (res, np.asarray(tr.y))
        r0, y0 = results[False]
        r1, y1 = results[True]
        np.testing.assert_array_equal(
            y0.view(np.uint32), y1.view(np.uint32),
            err_msg=f"{alg}/{mode}: consensus state drifts with trace")
        assert [(h.k, h.time, h.loss) for h in r0.history] \
            == [(h.k, h.time, h.loss) for h in r1.history]
        assert r0.total_comm_copies == r1.total_comm_copies
        assert r1.trace is not None and r0.trace is None


_SPAN_KEYS = {"name", "ph", "pid", "tid", "ts", "dur"}


def _validate_chrome(doc):
    """Chrome Trace Event Format (JSON Array/Object format) checks."""
    json.loads(json.dumps(doc))  # serializable, round-trips
    assert isinstance(doc["traceEvents"], list)
    flows = {}
    for e in doc["traceEvents"]:
        assert isinstance(e["name"], str) and e["name"]
        assert e["ph"] in ("X", "M", "s", "f", "i")
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        if e["ph"] == "X":
            assert _SPAN_KEYS <= set(e)
            assert e["ts"] >= 0 and e["dur"] >= 0
        elif e["ph"] in ("s", "f"):
            flows.setdefault(e["id"], []).append(e["ph"])
        elif e["ph"] == "i":
            assert e["s"] in ("t", "p", "g")
    for fid, phs in flows.items():
        assert sorted(phs) == ["f", "s"], f"unpaired flow id {fid}"


class TestChromeTraceExport:
    def test_virtual_track_schema(self):
        tr = _trainer("dsgd_aau", "sparse_scan")
        tr.run(max_events=60, eval_every=20)
        doc = chrome_trace(tr.last_trace)
        _validate_chrome(doc)
        evs = doc["traceEvents"]
        assert any(e["ph"] == "X" and e["name"] == "compute" for e in evs)
        assert any(e["ph"] == "X" and e["name"] == "wait" for e in evs)
        assert any(e["ph"] == "s" for e in evs)  # gossip flow arrows
        assert doc["otherData"]["algorithm"] == "dsgd_aau"
        # thread metadata names every worker
        names = {e["tid"] for e in evs
                 if e["ph"] == "M" and e["name"] == "thread_name"}
        assert names == set(range(N))
