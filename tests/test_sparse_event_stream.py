"""Sparse active-set event engine: SparseEventBatch packing, the
gather-compute-scatter scan (``mode="sparse_scan"``), and the
``sparse_gossip`` Pallas kernel.

The sparse path must be an *exact* re-execution of the dense compiled scan
(which is itself equivalence-tested against the per-event interpreter in
tests/test_event_stream.py): same scheduler seed ⇒ same ``(W, S, y)``
trajectory and the same recorded history, while touching only the workers
each event names.
"""
import dataclasses
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import topology
from repro.core.baselines import make_scheduler
from repro.core.consensus import metropolis_matrix
from repro.core.runner import DecentralizedTrainer
from repro.core.scheduler import EventBatch, SparseEventBatch
from repro.core.straggler import StragglerModel
from repro.data.synthetic import ClassificationData
from repro.kernels.sparse_gossip import (sparse_gossip_apply,
                                         sparse_gossip_apply_ref,
                                         sparse_gossip_ref,
                                         sparse_gossip_rows)

N = 16
DATA = ClassificationData(n_workers=N, d=16, n_classes=4,
                          samples_per_worker=64, seed=0)


def loss_fn(params, batch):
    logits = batch["x"] @ params["w"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, batch["y"][:, None], axis=1))


def init_fn(key):
    return {"w": jax.random.normal(key, (16, 4)) * 0.1}


def _sched(alg, seed=0, **kw):
    g = topology.erdos_renyi(N, 0.4, seed=3)
    sm = StragglerModel(n=N, straggler_prob=0.2, slowdown=6.0, seed=seed)
    return make_scheduler(alg, g, sm, **kw)


def _trainer(alg, mode, seed=0, **kw):
    return DecentralizedTrainer(
        _sched(alg, seed), loss_fn, init_fn,
        lambda w, s: DATA.batch(w, s, batch_size=8),
        DATA.eval_batch(64), eta0=0.2, eta_decay=0.99, seed=seed,
        mode=mode, **kw)


class TestSparseEventBatchPacking:
    @pytest.mark.parametrize("alg", ["dsgd_aau", "ad_psgd", "prague", "agp"])
    def test_round_trip_reconstructs_dense_events(self, alg):
        sched = _sched(alg)
        evs = list(itertools.islice(sched.events(), 12))
        batch = SparseEventBatch.from_events(
            evs, active_bound=sched.active_bound(),
            edge_bound=sched.edge_bound())
        assert batch.E == 12 and batch.A == sched.active_bound()
        for orig, back in zip(evs, batch.to_events(N)):
            assert back.k == orig.k
            assert back.time == pytest.approx(orig.time)
            np.testing.assert_array_equal(back.grad_workers, orig.grad_workers)
            np.testing.assert_array_equal(back.restart_workers,
                                          orig.restart_workers)
            np.testing.assert_allclose(back.P, orig.P)
            assert back.active_edges == orig.active_edges
            assert back.param_copies_sent == orig.param_copies_sent

    def test_single_edge_schedulers_carry_two_lanes(self):
        """AD-PSGD's sparse form is (E, 2) indices + (E, 2, 2) submatrices —
        the dense (E, n, n) stack is gone entirely."""
        sched = _sched("ad_psgd")
        stream = sched.packed_stream(native=False)
        batches = [stream.next_chunk(5) for _ in range(2)]
        assert [b.E for b in batches] == [5, 5]
        assert batches[1].k0 == 5
        assert batches[0].workers.shape == (5, 2)
        assert batches[0].P_sub.shape == (5, 2, 2)
        assert batches[0].edges.shape == (5, 1, 2)

    def test_sorted_active_sets_and_zero_padding(self):
        sched = _sched("dsgd_aau")
        batch = SparseEventBatch.from_events(
            list(itertools.islice(sched.events(), 8)),
            active_bound=sched.active_bound(), edge_bound=sched.edge_bound())
        for e in range(batch.E):
            m = int(batch.n_workers[e])
            lanes = batch.workers[e]
            assert (lanes[:m] >= 0).all() and (lanes[m:] == -1).all()
            assert list(lanes[:m]) == sorted(set(lanes[:m].tolist()))
            # padded lanes carry no mass in either direction and no masks
            assert np.all(batch.P_sub[e, m:, :] == 0.0)
            assert np.all(batch.P_sub[e, :, m:] == 0.0)
            assert not batch.grad_workers[e, m:].any()
            assert not batch.restart_workers[e, m:].any()

    def test_overflowing_active_bound_raises(self):
        sched = _sched("dsgd_aau")
        evs = list(itertools.islice(sched.events(), 10))
        widest = max(int(ev.grad_workers.sum()) for ev in evs)
        with pytest.raises(ValueError, match="active_bound"):
            SparseEventBatch.from_events(evs, active_bound=widest - 1)

    def test_pad_to_is_noop_events(self):
        sched = _sched("ad_psgd")
        evs = list(itertools.islice(sched.events(), 3))
        batch = SparseEventBatch.from_events(evs, active_bound=2).pad_to(8)
        assert batch.E == 8
        assert (batch.workers[3:] == -1).all()
        assert (batch.n_workers[3:] == 0).all()
        assert np.all(batch.P_sub[3:] == 0.0)
        assert not batch.grad_workers[3:].any()
        assert (batch.n_edges[3:] == 0).all()
        assert batch.param_copies_sent[3:].sum() == 0

    def test_padded_noop_block_leaves_state_bit_exact(self):
        tr = _trainer("ad_psgd", "sparse_scan")
        tr._ensure_sparse()
        W0 = jax.tree.map(lambda x: np.asarray(x).copy(), tr.W)
        ev = list(itertools.islice(_sched("ad_psgd").events(), 1))
        batch = SparseEventBatch.from_events(ev, active_bound=2, edge_bound=1)
        off = np.zeros_like(batch.grad_workers)
        noop = dataclasses.replace(
            batch, workers=np.full_like(batch.workers, -1),
            n_workers=np.zeros_like(batch.n_workers),
            P_sub=np.zeros_like(batch.P_sub),
            grad_workers=off, restart_workers=off)
        tr._launch_sparse([tr._pack_sparse_block(
            noop.pad_to(tr.block_size), 0, tr.block_size)])
        for a, b in zip(jax.tree.leaves(W0), jax.tree.leaves(tr.W)):
            np.testing.assert_array_equal(a, np.asarray(b))
        np.testing.assert_array_equal(np.asarray(tr._ptr), np.zeros(N))


class TestSparseScanEquivalence:
    """Same scheduler seed ⇒ sparse_scan ≡ scan ≡ per_event (fp32):
    parameters, snapshots, push-sum weights, and recorded history."""

    @pytest.mark.parametrize("alg", ["dsgd_aau", "ad_psgd", "agp", "prague"])
    def test_matches_dense_scan_and_per_event(self, alg):
        per_event = _trainer(alg, "per_event")
        res_pe = per_event.run(max_events=40, eval_every=10)
        dense = _trainer(alg, "scan", block_size=7, batch_pool=48)
        res_dense = dense.run(max_events=40, eval_every=10)
        # block_size deliberately not dividing eval_every: exercises the
        # eval-boundary snapping + no-op padding on the sparse path too
        sparse = _trainer(alg, "sparse_scan", block_size=7, batch_pool=48)
        res_sparse = sparse.run(max_events=40, eval_every=10)

        for other, res_other, tol in ((dense, res_dense, 0.0),
                                      (per_event, res_pe, 1e-6)):
            for name, a, b in (("W", other.W, sparse.W),
                               ("S", other.S, sparse.S)):
                for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
                    np.testing.assert_allclose(
                        np.asarray(la), np.asarray(lb), atol=tol,
                        err_msg=f"{name} vs {other.mode}")
            np.testing.assert_allclose(np.asarray(other.y),
                                       np.asarray(sparse.y), atol=tol)
            assert len(res_other.history) == len(res_sparse.history)
            for p_o, p_s in zip(res_other.history, res_sparse.history):
                assert p_s.k == p_o.k
                assert p_s.time == pytest.approx(p_o.time)
                assert p_s.loss == pytest.approx(p_o.loss, abs=1e-5)
                assert p_s.metric == pytest.approx(p_o.metric, abs=1e-5)
                assert p_s.comm_param_copies == p_o.comm_param_copies
                assert p_s.n_active_mean == pytest.approx(p_o.n_active_mean)
            assert res_sparse.total_events == res_other.total_events
            assert res_sparse.total_time == pytest.approx(
                res_other.total_time)

    def test_agp_pushsum_debias_survives_sparse_scan(self):
        sparse = _trainer("agp", "sparse_scan", block_size=8, batch_pool=48)
        sparse.run(max_events=30, eval_every=30)
        y = np.asarray(sparse.y)
        assert not np.allclose(y, 1.0)        # row-stochastic pushes moved mass
        assert y.sum() == pytest.approx(N, rel=1e-4)  # total mass conserved

    def test_kernel_path_matches_plain_sparse_scan(self):
        ref = _trainer("ad_psgd", "sparse_scan", block_size=4, batch_pool=24)
        res_ref = ref.run(max_events=12, eval_every=12)
        fused = _trainer("ad_psgd", "sparse_scan", block_size=4,
                         batch_pool=24, use_kernel=True)
        res_fused = fused.run(max_events=12, eval_every=12)
        for la, lb in zip(jax.tree.leaves(ref.W), jax.tree.leaves(fused.W)):
            np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                       atol=2e-5)
        assert res_fused.final_loss == pytest.approx(res_ref.final_loss,
                                                     abs=1e-4)

    def test_sync_scheduler_falls_back_to_dense_scan(self):
        """Global-barrier streams gain nothing from gathering: sparse_scan
        silently degrades to the dense scan and still runs correctly."""
        dense = _trainer("dsgd_sync", "scan", block_size=4, batch_pool=24)
        res_dense = dense.run(max_events=12, eval_every=6)
        sparse = _trainer("dsgd_sync", "sparse_scan", block_size=4,
                          batch_pool=24)
        assert sparse.mode == "scan"  # automatic fallback
        res_sparse = sparse.run(max_events=12, eval_every=6)
        assert res_sparse.final_loss == pytest.approx(res_dense.final_loss)

    def test_max_time_bound(self):
        ref = _trainer("ad_psgd", "scan", block_size=4).run(
            max_time=20.0, eval_every=10)
        sparse = _trainer("ad_psgd", "sparse_scan", block_size=4).run(
            max_time=20.0, eval_every=10)
        assert sparse.total_events == ref.total_events
        assert sparse.final_loss == pytest.approx(ref.final_loss, abs=1e-6)

    def test_warmup_leaves_state_unchanged(self):
        tr = _trainer("dsgd_aau", "sparse_scan")
        W0 = jax.tree.map(lambda x: np.asarray(x).copy(), tr.W)
        tr.warmup()
        for a, b in zip(jax.tree.leaves(W0), jax.tree.leaves(tr.W)):
            np.testing.assert_array_equal(a, np.asarray(b))


class TestSparseGossipKernel:
    def _problem(self, n, d, A, seed=0, pad=0):
        key = jax.random.PRNGKey(seed)
        W = jax.random.normal(key, (n, d), jnp.float32)
        G = jax.random.normal(jax.random.fold_in(key, 1), (A, d), jnp.float32)
        rng = np.random.default_rng(seed)
        w = np.full(A, -1, np.int32)
        m = A - pad
        w[:m] = np.sort(rng.choice(n, size=m, replace=False))
        P = np.zeros((A, A), np.float32)
        P[:m, :m] = metropolis_matrix(
            m, [(i, (i + 1) % m) for i in range(max(m - 1, 1))]) if m > 1 \
            else 1.0
        mask = np.zeros(A, np.float32)
        mask[:m] = 0.1 * rng.random(m)
        return W, G, jnp.asarray(P), jnp.asarray(mask), jnp.asarray(w)

    @pytest.mark.parametrize("n,d,A,pad", [
        (16, 256, 2, 0),     # AD-PSGD/AGP shape
        (16, 256, 2, 1),     # isolated-worker event: one padded lane
        (64, 640, 8, 3),     # AAU-style subset with padding, D % 512 != 0
        (256, 512, 16, 5),   # paper-scale row count
    ])
    def test_rows_match_ref(self, n, d, A, pad):
        W, G, P, mask, w = self._problem(n, d, A, seed=n + A, pad=pad)
        Q = mask[:, None] * P
        out = sparse_gossip_rows(W, G, P, mask, w, block_d=256)
        ref = sparse_gossip_ref(W, G, P, Q, w)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)
        # padded lanes produce exactly zero rows (the scatter drops them)
        if pad:
            assert np.all(np.asarray(out)[A - pad:] == 0.0)

    def test_apply_untouched_rows_bit_exact(self):
        """Scatter semantics: rows outside the active set are *identical*
        buffers-worth of data, and -1 lanes write nowhere."""
        W, G, P, mask, w = self._problem(32, 256, 4, seed=7, pad=2)
        out = np.asarray(sparse_gossip_apply(W, G, P, mask, w, block_d=256))
        ref = np.asarray(sparse_gossip_apply_ref(W, G, P, mask, w))
        np.testing.assert_allclose(out, ref, atol=2e-5)
        active = set(np.asarray(w)[np.asarray(w) >= 0].tolist())
        for i in range(32):
            if i not in active:
                np.testing.assert_array_equal(out[i], np.asarray(W)[i])

    def test_apply_matches_dense_masked_gossip(self):
        """The sparse kernel on the active set equals the dense fused kernel
        run with the full N×N matrix that is identity off the set."""
        from repro.kernels.gossip_mix import masked_gossip_ref
        n, d, A = 24, 384, 6
        W, Ga, P_sub, mask, w = self._problem(n, d, A, seed=3, pad=0)
        widx = np.asarray(w)
        P = np.eye(n, dtype=np.float32)
        P[np.ix_(widx, widx)] = np.asarray(P_sub)
        G = np.zeros((n, d), np.float32)
        G[widx] = np.asarray(Ga)
        scaled = np.zeros(n, np.float32)
        scaled[widx] = np.asarray(mask)
        dense = masked_gossip_ref(jnp.asarray(W), jnp.asarray(G),
                                  jnp.asarray(P), jnp.asarray(scaled))
        sparse = sparse_gossip_apply(W, Ga, P_sub, mask, w, block_d=384)
        np.testing.assert_allclose(np.asarray(sparse), np.asarray(dense),
                                   atol=2e-5)

    def test_all_padded_lanes_is_identity(self):
        W, G, P, mask, w = self._problem(16, 256, 4, seed=5, pad=0)
        w_all_pad = jnp.full_like(w, -1)
        out = sparse_gossip_apply(W, G, jnp.zeros_like(P),
                                  jnp.zeros_like(mask), w_all_pad,
                                  block_d=256)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(W))
