"""The packed event stream: native generation, merged dispatch, the mode
choice, and the exact pair stream's accounting.

- the array-native packed generators (``packed_stream(native=True)``) must
  be **bit-identical** to the object-path adapter, chunk by chunk, for every
  scheduler — same RNG consumption, same float casts, same k0 bookkeeping;
- ``merge_event_groups`` + the runner's event-blocked dispatch must be
  **bit-exact** re-executions of the one-event-per-step sparse scan (the
  trajectory equivalence lives in tests/test_sparse_event_stream.py; here
  the merged-vs-unmerged runner paths are pinned against each other);
- ``choose_mode`` picks the dense or the sparse scan from n, the lane
  ladder and the gradient's cost;
- the pair stream's event, restart and comm totals are exact, and
  ``warmup()`` leaves it where it was.
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import topology
from repro.core.baselines import make_scheduler
from repro.core.runner import (COMPUTE_BOUND_FLOPS_PER_BYTE,
                               DecentralizedTrainer, choose_mode)
from repro.core.scheduler import (BucketedSparseEventBatch, PackedEventStream,
                                  SparseEventBatch, merge_event_groups)
from repro.core.straggler import StragglerModel
from repro.data.synthetic import ClassificationData

N = 16
ALL_ALGS = ["dsgd_aau", "ad_psgd", "prague", "agp", "dsgd_sync"]
DATA = ClassificationData(n_workers=N, d=16, n_classes=4,
                          samples_per_worker=64, seed=0)


def loss_fn(params, batch):
    logits = batch["x"] @ params["w"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, batch["y"][:, None], axis=1))


def init_fn(key):
    return {"w": jax.random.normal(key, (16, 4)) * 0.1}


def _sched(alg, seed=0, straggler=None, **kw):
    g = topology.erdos_renyi(N, 0.4, seed=3)
    sm = straggler or StragglerModel(n=N, straggler_prob=0.2, slowdown=6.0,
                                     seed=seed)
    return make_scheduler(alg, g, sm, **kw)


def _trainer(alg, mode, seed=0, sched_kw=None, **kw):
    return DecentralizedTrainer(
        _sched(alg, seed, **(sched_kw or {})), loss_fn, init_fn,
        lambda w, s: DATA.batch(w, s, batch_size=8),
        DATA.eval_batch(64), eta0=0.2, eta_decay=0.99, seed=seed,
        mode=mode, **kw)


def _assert_sparse_equal(a: SparseEventBatch, b: SparseEventBatch):
    assert a.k0 == b.k0 and a.E == b.E and a.A == b.A
    np.testing.assert_array_equal(a.workers, b.workers)
    np.testing.assert_array_equal(a.n_workers, b.n_workers)
    np.testing.assert_array_equal(a.P_sub, b.P_sub)          # bit-exact
    np.testing.assert_array_equal(a.grad_workers, b.grad_workers)
    np.testing.assert_array_equal(a.restart_workers, b.restart_workers)
    np.testing.assert_array_equal(a.edges, b.edges)
    np.testing.assert_array_equal(a.n_edges, b.n_edges)
    np.testing.assert_array_equal(a.times, b.times)          # bit-exact
    np.testing.assert_array_equal(a.param_copies_sent, b.param_copies_sent)


def _assert_chunks_equal(a, b):
    assert type(a) is type(b)
    if isinstance(a, BucketedSparseEventBatch):
        assert a.k0 == b.k0 and a.buckets == b.buckets
        np.testing.assert_array_equal(a.event_bucket, b.event_bucket)
        np.testing.assert_array_equal(a.positions, b.positions)
        for sa, sb in zip(a.batches, b.batches):
            assert (sa is None) == (sb is None)
            if sa is not None:
                _assert_sparse_equal(sa, sb)
    else:
        _assert_sparse_equal(a, b)


class TestNativePackedGeneration:
    @pytest.mark.parametrize("alg", ALL_ALGS)
    def test_native_chunks_bit_identical_to_object_path(self, alg):
        native = _sched(alg).packed_stream(native=True)
        obj = _sched(alg).packed_stream(native=False)
        assert type(obj) is PackedEventStream
        for k in (7, 1, 12, 5):  # uneven chunk sizes exercise k0 bookkeeping
            _assert_chunks_equal(native.next_chunk(k), obj.next_chunk(k))

    @pytest.mark.parametrize("alg", ALL_ALGS)
    def test_native_stream_engaged(self, alg):
        # every built-in scheduler has an array-native generator
        assert _sched(alg)._native_packed_stream() is not None

    def test_horizon_scheduler_keeps_object_adapter(self):
        # the native pair stream replays the *exact* per-event RNG order;
        # horizon batching draws in a different order by construction
        sched = _sched("ad_psgd", sched_kw=None) if False else _sched(
            "ad_psgd", horizon=64)
        assert sched._native_packed_stream() is None
        assert type(sched.packed_stream(native=True)) is PackedEventStream


class TestMergeEventGroups:
    def _batch(self, alg="ad_psgd", events=24):
        sched = _sched(alg)
        evs = list(itertools.islice(sched.events(), events))
        return SparseEventBatch.from_events(
            evs, active_bound=sched.active_bound(),
            edge_bound=sched.edge_bound())

    def test_groups_are_conflict_free_and_order_preserving(self):
        batch = self._batch()
        merged, lane_off = merge_event_groups(batch, 4)
        assert merged.A == 4 * batch.A
        assert lane_off.shape == (merged.E, merged.A)
        assert merged.n_workers.sum() == batch.n_workers.sum()
        prev_last = -1
        for g in range(merged.E):
            valid = merged.workers[g] >= 0
            w = merged.workers[g][valid]
            # pairwise-disjoint worker sets within one scan step
            assert len(set(w.tolist())) == len(w)
            # offsets map each lane back to its source event, in stream order
            offs = lane_off[g][valid]
            assert (np.diff(offs) >= 0).all()
            assert offs[0] > prev_last  # groups partition the stream
            prev_last = int(offs[-1])
            for lane, off in zip(np.where(valid)[0], offs):
                assert merged.workers[g, lane] in batch.workers[off]
        assert prev_last == batch.E - 1

    def test_merged_payload_matches_sources(self):
        batch = self._batch()
        merged, lane_off = merge_event_groups(batch, 4)
        # group time is the last member's; copies are summed over members
        assert merged.param_copies_sent.sum() == batch.param_copies_sent.sum()
        e = 0
        for g in range(merged.E):
            members = np.unique(lane_off[g][merged.workers[g] >= 0])
            assert merged.times[g] == batch.times[int(members[-1])]
            e = int(members[-1]) + 1
        assert e == batch.E

    def test_k1_is_identity_with_arange_offsets(self):
        batch = self._batch()
        merged, lane_off = merge_event_groups(batch, 1)
        _assert_sparse_equal(merged, batch)
        np.testing.assert_array_equal(
            lane_off, np.broadcast_to(np.arange(batch.E)[:, None],
                                      (batch.E, batch.A)))

    @pytest.mark.parametrize("alg", ["ad_psgd", "dsgd_aau", "agp", "prague"])
    def test_merged_dispatch_bit_exact_vs_one_event_per_step(self, alg):
        one = _trainer(alg, "sparse_scan", block_size=8, batch_pool=48,
                       events_per_step=1)
        merged = _trainer(alg, "sparse_scan", block_size=8, batch_pool=48,
                          events_per_step=8)
        r1 = one.run(max_events=40, eval_every=10)
        r2 = merged.run(max_events=40, eval_every=10)
        np.testing.assert_array_equal(np.asarray(one.W["w"]),
                                      np.asarray(merged.W["w"]))
        np.testing.assert_array_equal(np.asarray(one.y), np.asarray(merged.y))
        assert r1.total_comm_copies == r2.total_comm_copies
        assert [p.loss for p in r1.history] == [p.loss for p in r2.history]


class TestChooseMode:
    def test_crossover_table(self):
        assert choose_mode(16, (2,)) == "scan"
        assert choose_mode(256, (2,)) == "sparse_scan"
        assert choose_mode(64, (16, 32, 64)) == "scan"
        assert choose_mode(256, (16, 64, 256)) == "sparse_scan"
        assert choose_mode(1024, (2,), global_events=True) == "scan"

    # F/R: the gradient's FLOPs a lane per byte of a worker's W and S rows
    # (the paper's 2-NN reads ≈17, ResNet-18 ≈1,040)
    @pytest.mark.parametrize("n, ladder, global_events, flops_per_byte, want", [
        (128, (16, 64, 128), False, 24.0, "sparse_scan"),
        (256, (16, 64, 256), False, 24.0, "sparse_scan"),
        (128, (2,), False, 24.0, "sparse_scan"),
        (32, (16, 32), False, 24.0, "scan"),
        (32, (16, 32), False, 1000.0, "sparse_scan"),
        (16, (2,), False, 1000.0, "sparse_scan"),
        (128, (128,), True, 24.0, "scan"),
        (32, (32,), True, 1000.0, "scan"),
    ])
    def test_payload_cost(self, n, ladder, global_events, flops_per_byte,
                          want):
        got = choose_mode(n, ladder, global_events, flops_per_byte)
        assert got == want
        if flops_per_byte < COMPUTE_BOUND_FLOPS_PER_BYTE or global_events:
            # below the ridge, and for barriers, the answer without the cost
            assert got == choose_mode(n, ladder, global_events)

    def test_auto_resolves_at_construction(self):
        tr = _trainer("ad_psgd", "auto", block_size=8, batch_pool=48)
        assert tr.mode == "scan"  # N=16 sits below every crossover
        tr.run(max_events=16, eval_every=8)  # and the resolved mode runs

    def test_fused_mode_is_refused(self):
        # the four modes left all replay the exact event stream
        with pytest.raises(ValueError, match="mode must be"):
            _trainer("ad_psgd", "fused")

    def test_auto_picks_sparse_at_scale(self):
        g = topology.erdos_renyi(128, 0.1, seed=1)
        sm = StragglerModel(n=128, straggler_prob=0.1, slowdown=10.0, seed=0)
        sched = make_scheduler("ad_psgd", g, sm)
        data = ClassificationData(n_workers=128, d=16, n_classes=4,
                                  samples_per_worker=4, seed=0)
        tr = DecentralizedTrainer(
            sched, loss_fn, init_fn,
            lambda w, s: data.batch(w, s, batch_size=4),
            data.eval_batch(32), mode="auto")
        assert tr.mode == "sparse_scan"


class TestExactPairStream:
    """AD-PSGD on the sparse scan: merged pairs, replayed exactly."""

    EVENTS = 96

    def _run(self, warmup=False):
        tr = _trainer("ad_psgd", "sparse_scan", block_size=16, batch_pool=96)
        if warmup:
            tr.warmup()
        res = tr.run(max_events=self.EVENTS, eval_every=24)
        return tr, res

    def test_warmup_does_not_shift_the_stream(self):
        t1, r1 = self._run(warmup=False)
        t2, r2 = self._run(warmup=True)
        np.testing.assert_array_equal(np.asarray(t1.W["w"]),
                                      np.asarray(t2.W["w"]))
        np.testing.assert_array_equal(np.asarray(t1.y), np.asarray(t2.y))
        assert r1.total_time == r2.total_time
        assert [p.loss for p in r1.history] == [p.loss for p in r2.history]

    def test_exact_event_accounting(self):
        # erdos_renyi(16, 0.4, seed=3) is connected: every event is a pair
        # exchange, so comm and restart totals are exact, not statistical.
        sched = _sched("ad_psgd")
        assert all(len(nb) for nb in sched.graph.neighbor_lists)
        copies_pair = next(sched.events()).param_copies_sent
        assert copies_pair > 0
        tr, res = self._run()
        assert res.total_events == self.EVENTS
        assert tr.counters.rows < self.EVENTS   # pairs merged per step
        assert res.total_comm_copies == self.EVENTS * copies_pair
        # one finisher restart per event
        assert int(np.asarray(tr._ptr).sum()) == self.EVENTS
        # pair events: finisher + neighbor active, the finisher's lane
        # alone takes a gradient (n_active counts gradient lanes)
        assert tr.counters.active == 2 * self.EVENTS
        assert tr.counters.grad == self.EVENTS
        assert res.history[-1].n_active_mean == 1.0
