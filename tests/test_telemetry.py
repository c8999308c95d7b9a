"""Device-resident telemetry (repro/obs): cross-mode bit-exactness,
the DSGD-AAU staleness-bound monitor, zero trajectory drift, comm-byte
accounting, and the structured run logger.

The contract under test (see repro/obs/metrics.py):

- the drained ``MetricsCarry`` is **bit-identical** across ``per_event``,
  ``scan`` and ``sparse_scan`` (incl. bucketed dispatch) of the same
  scheduler stream — every accumulator uses order-exact operations only;
- telemetry is a pure observer: trajectories are bit-identical with it on
  or off;
- ``stale_max`` obeys the 2N−4 event-staleness bound induced by
  Pathsearch's per-epoch commit bound B ≤ N−1 (the issue's "≤ N−1" is the
  per-epoch *edge* bound, which does not bound event staleness directly —
  the histogram empirically reaches beyond N−1 and up to exactly 2N−4).
"""
import io
import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import topology
from repro.core.baselines import make_scheduler
from repro.core.runner import DecentralizedTrainer
from repro.core.straggler import StragglerModel
from repro.data.synthetic import ClassificationData
from repro.obs import RunLogger, init_metrics
from repro.obs.metrics import (STALE_HIST_BINS, block_metrics_update,
                               sparse_metrics_update)

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
    kw.setdefault("telemetry", True)
    return DecentralizedTrainer(
        _sched(alg, seed, **(sched_kw or {})), loss_fn, init_fn,
        lambda w, s: DATA.batch(w, s, batch_size=8),
        DATA.eval_batch(64), eta0=0.2, eta_decay=0.99, seed=seed,
        mode=mode, **kw)


def _bits(M):
    """MetricsCarry → dict of integer views (f32 compared bitwise)."""
    host = jax.device_get(M)
    out = {}
    for f in host._fields:
        a = np.asarray(getattr(host, f))
        out[f] = a.view(np.uint32) if a.dtype == np.float32 else a
    return out


def _assert_carry_equal(Ma, Mb, ctx=""):
    a, b = _bits(Ma), _bits(Mb)
    for f in a:
        np.testing.assert_array_equal(
            a[f], b[f], err_msg=f"{ctx}: MetricsCarry.{f} differs")


class TestCrossModeBitExact:
    """per_event / scan / sparse_scan drain bit-identical counters."""

    EVENTS = 60

    @pytest.mark.parametrize("alg,sched_kw", [
        ("dsgd_aau", {"buckets": (4, 8, 16)}),   # forces bucketed dispatch
        ("ad_psgd", {}),
    ])
    def test_modes_bit_identical(self, alg, sched_kw):
        carries, summaries = {}, {}
        for mode in ("per_event", "scan", "sparse_scan"):
            tr = _trainer(alg, mode, sched_kw=sched_kw)
            res = tr.run(max_events=self.EVENTS, eval_every=20)
            carries[mode] = tr._metrics
            summaries[mode] = res.telemetry
        _assert_carry_equal(carries["per_event"], carries["scan"],
                            f"{alg} per_event vs scan")
        _assert_carry_equal(carries["per_event"], carries["sparse_scan"],
                            f"{alg} per_event vs sparse_scan")
        # the drained summaries (minus the sparse-only occupancy report)
        # must agree too — they are pure functions of the carry
        for mode in ("scan", "sparse_scan"):
            s = dict(summaries[mode])
            ref = dict(summaries["per_event"])
            s.pop("bucket_occupancy", None)
            ref.pop("bucket_occupancy", None)
            assert s == ref, f"{alg}: summary drift in {mode}"

    def test_sync_scan_matches_per_event(self):
        carries = {}
        for mode in ("per_event", "scan"):
            tr = _trainer("dsgd_sync", mode)
            tr.run(max_events=48, eval_every=16)
            carries[mode] = tr._metrics
        _assert_carry_equal(carries["per_event"], carries["scan"],
                            "dsgd_sync per_event vs scan")

    def test_counters_are_consistent(self):
        tr = _trainer("dsgd_aau", "sparse_scan")
        res = tr.run(max_events=self.EVENTS, eval_every=20)
        t = res.telemetry
        assert sum(t["stale_hist"]) == sum(t["grad_steps"])
        assert t["comm_copies"] == res.total_comm_copies
        assert len(t["grad_steps"]) == N
        assert len(t["stale_hist"]) == STALE_HIST_BINS
        assert all(0.0 <= u <= 1.0 for u in t["utilization"])
        # occupancy covers every event exactly once
        occ = t["bucket_occupancy"]
        assert sum(o["events"] for o in occ) == self.EVENTS


class TestTrajectoryUnchanged:
    """Telemetry is a pure observer: bit-identical state with it on/off."""

    @pytest.mark.parametrize("alg,mode", [
        ("dsgd_aau", "scan"),
        ("dsgd_aau", "sparse_scan"),
        ("dsgd_aau", "per_event"),
        ("ad_psgd", "sparse_scan"),
        ("dsgd_sync", "scan"),
    ])
    def test_state_and_history_identical(self, alg, mode):
        results = {}
        for tel in (False, True):
            tr = _trainer(alg, mode, telemetry=tel)
            res = tr.run(max_events=48, eval_every=16)
            results[tel] = (res, np.asarray(tr.y))
        r0, y0 = results[False]
        r1, y1 = results[True]
        np.testing.assert_array_equal(
            y0.view(np.uint32), y1.view(np.uint32),
            err_msg=f"{alg}/{mode}: consensus state drifts with telemetry")
        assert [(h.k, h.time, h.loss) for h in r0.history] \
            == [(h.k, h.time, h.loss) for h in r1.history]
        assert r0.total_comm_copies == r1.total_comm_copies
        assert r1.telemetry is not None and r0.telemetry is None


class TestStalenessBound:
    """DSGD-AAU's runtime monitor: stale_max ≤ 2N−4, and the bound is the
    *event*-staleness consequence of the per-epoch commit bound B ≤ N−1."""

    @pytest.mark.parametrize("seed,slowdown", [
        (0, 6.0), (1, 6.0), (2, 25.0), (3, 100.0),
    ])
    def test_bound_holds(self, seed, slowdown):
        tr = _trainer("dsgd_aau", "sparse_scan", seed=seed,
                      sched_kw={"slowdown": slowdown})
        res = tr.run(max_events=200, eval_every=100)
        b = res.telemetry["staleness_bound"]
        assert b["bound"] == 2 * N - 4
        assert b["edges_per_epoch_bound"] == N - 1
        assert b["observed_max"] == res.telemetry["stale_max"]
        assert b["ok"], (
            f"stale_max {b['observed_max']} exceeds 2N-4={b['bound']} "
            f"(seed={seed}, slowdown={slowdown})")

    def test_bound_is_reachable_beyond_n_minus_1(self):
        """Heavy straggling drives staleness past N−1 (so N−1 is NOT an
        event-staleness bound) while still respecting 2N−4."""
        worst = 0
        for seed in range(6):
            tr = _trainer("dsgd_aau", "sparse_scan", seed=seed,
                          sched_kw={"slowdown": 200.0})
            res = tr.run(max_events=300, eval_every=300)
            worst = max(worst, res.telemetry["stale_max"])
            assert res.telemetry["staleness_bound"]["ok"]
        assert worst > N - 1, (
            f"expected some stream to exceed N-1={N - 1} event staleness; "
            f"worst observed {worst}")

    def test_matches_host_replay(self):
        """The device staleness histogram equals a host replay of the
        event stream's restart bookkeeping."""
        import itertools
        sched = _sched("dsgd_aau")
        evs = list(itertools.islice(sched.events(), 120))
        last = np.full(N, -1, dtype=np.int64)
        hist = np.zeros(STALE_HIST_BINS, dtype=np.int64)
        smax, ssum = 0, 0
        for k, ev in enumerate(evs):
            for w in np.flatnonzero(ev.grad_workers):
                s = int(k - last[w] - 1)
                smax = max(smax, s)
                ssum += s
                hist[min(int(np.log2(s + 1)), STALE_HIST_BINS - 1)] += 1
            for w in np.flatnonzero(ev.restart_workers):
                last[w] = k
        tr = _trainer("dsgd_aau", "sparse_scan")
        res = tr.run(max_events=120, eval_every=120)
        t = res.telemetry
        assert t["stale_max"] == smax
        assert t["stale_hist"] == hist.tolist()
        assert sum(t["stale_hist"]) * t["stale_mean"] == pytest.approx(ssum)

    def test_non_aau_has_no_bound(self):
        tr = _trainer("ad_psgd", "sparse_scan")
        res = tr.run(max_events=40, eval_every=40)
        assert "staleness_bound" not in res.telemetry


class TestPairTelemetry:
    """The merged pair stream: deterministic, internally consistent;
    the block fold equals the sequential per-event fold on identical
    payloads."""

    def test_deterministic_and_consistent(self):
        summ = []
        for _ in range(2):
            tr = _trainer("ad_psgd", "sparse_scan")
            res = tr.run(max_events=96, eval_every=48)
            t = res.telemetry
            # one gradient lane (the finisher's) per pair event
            assert sum(t["grad_steps"]) == res.total_events
            assert t["comm_copies"] == res.total_comm_copies
            assert sum(t["stale_hist"]) == sum(t["grad_steps"])
            summ.append(t)
        assert summ[0] == summ[1], "pair telemetry not deterministic"

    def test_block_fold_matches_sequential_fold(self):
        """block_metrics_update ≡ event-by-event sparse_metrics_update on
        the same payload stream (integers exact, f32 to float tolerance),
        including the carry handoff between consecutive blocks."""
        rng = np.random.default_rng(7)
        n, A, E, k0 = 9, 2, 120, 13
        workers = np.full((E, A), -1, np.int32)
        gm = np.zeros((E, A), bool)
        cpl = np.zeros((E, A), bool)
        for e in range(E):
            if rng.random() < 0.8:
                i, j = rng.choice(n, 2, replace=False)
                workers[e] = [min(i, j), max(i, j)]
                gm[e, rng.integers(2)] = True
                cpl[e] = True
            else:
                workers[e, 0] = rng.integers(n)
                gm[e, 0] = True
        ts = np.cumsum(rng.random(E).astype(np.float32) * 0.1,
                       dtype=np.float32)
        fin = (ts[:, None]
               - rng.random((E, A)).astype(np.float32) * 0.05)
        ks = (k0 + np.arange(E)).astype(np.int32)
        copies = rng.integers(0, 3, E).astype(np.int32)

        M_seq = init_metrics(n)
        for e in range(E):
            P = (np.full((A, A), 0.5, np.float32) if cpl[e].all()
                 else np.eye(A, dtype=np.float32))
            M_seq = sparse_metrics_update(
                M_seq, jnp.asarray(workers[e]), jnp.asarray(P),
                jnp.asarray(gm[e]), jnp.asarray(gm[e]),
                jnp.full((A,), ts[e]), jnp.asarray(fin[e]),
                jnp.full((A,), ks[e], jnp.int32), jnp.int32(copies[e]))

        h = E // 2
        M_blk = init_metrics(n)
        for sl in (slice(None, h), slice(h, None)):
            M_blk = block_metrics_update(
                M_blk, jnp.asarray(workers[sl]), jnp.asarray(gm[sl]),
                jnp.asarray(gm[sl]), jnp.asarray(cpl[sl]),
                jnp.asarray(ts[sl]), jnp.asarray(fin[sl]),
                jnp.asarray(ks[sl]), jnp.asarray(copies[sl]))

        a, b = jax.device_get(M_seq), jax.device_get(M_blk)
        for f in a._fields:
            av, bv = np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
            if av.dtype == np.float32 and f in ("busy_t", "idle_t"):
                np.testing.assert_allclose(av, bv, rtol=1e-6, atol=1e-6,
                                           err_msg=f)
            else:
                np.testing.assert_array_equal(av, bv, err_msg=f)


class TestCommBytes:
    """RunResult.comm_bytes prices copies via the trainer's dtype policy."""

    def test_bf16_reports_bf16_bytes(self):
        tr = _trainer("dsgd_aau", "sparse_scan", telemetry=False,
                      dtype=jnp.bfloat16)
        res = tr.run(max_events=24, eval_every=24)
        assert res.bytes_per_scalar == 2
        assert res.comm_bytes() == \
            res.total_comm_copies * res.param_count * 2
        # explicit override still wins (the old fp32 pricing, on request)
        assert res.comm_bytes(4) == 2 * res.comm_bytes()

    def test_fp32_default(self):
        tr = _trainer("dsgd_aau", "scan", telemetry=False)
        res = tr.run(max_events=24, eval_every=24)
        assert res.bytes_per_scalar == 4
        assert res.comm_bytes() == \
            res.total_comm_copies * res.param_count * 4


class TestRunLogger:
    def test_jsonl_schema_and_warn_once(self):
        buf = io.StringIO()
        log = RunLogger(buf)
        log.log("run_start", n=4)
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            log.warn_once("pool_wrap", "pool wrapped")
            log.warn_once("pool_wrap", "pool wrapped")   # deduped
            log.warn_once("rng_order", "notice", warn=False)  # log-only
        lines = [json.loads(s) for s in buf.getvalue().splitlines()]
        assert [l["event"] for l in lines] \
            == ["run_start", "pool_wrap", "rng_order"]
        assert len(rec) == 1 and "pool wrapped" in str(rec[0].message)

    def test_disabled_logger_is_noop(self):
        log = RunLogger(None)
        assert not log.enabled
        log.log("anything", x=1)   # must not raise

    def test_trainer_emits_run_events(self):
        buf = io.StringIO()
        tr = _trainer("dsgd_aau", "sparse_scan", run_log=buf)
        tr.run(max_events=40, eval_every=20)
        events = [json.loads(s)["event"] for s in buf.getvalue().splitlines()]
        # the lane policy is logged once, when the trainer is built
        assert events[:2] == ["lane_policy", "run_start"]
        assert events[-1] == "run_end"
        assert "block_dispatch" in events
        assert "compile" in events

    def test_pool_wrap_routes_through_logger(self):
        """The batch-pool wrap notice lands in the JSONL log AND still
        warns on stderr (the pre-logger contract)."""
        buf = io.StringIO()
        tr = _trainer("dsgd_aau", "sparse_scan", telemetry=False,
                      run_log=buf, batch_pool=2)
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            tr.run(max_events=120, eval_every=60)
        wraps = [json.loads(s) for s in buf.getvalue().splitlines()
                 if json.loads(s)["event"] == "pool_wrap"]
        assert len(wraps) == 1, "pool_wrap must be logged exactly once"
        assert any("batch pool" in str(w.message) for w in rec)
