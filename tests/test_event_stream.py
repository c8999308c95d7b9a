"""Compiled event stream: EventBatch packing, scan/per-event equivalence,
and the batched/masked gossip kernels.

The block-compiled trainer (core/runner.py ``mode="scan"``) must be an
*exact* re-execution of the legacy per-event interpreter: same scheduler
seed ⇒ same ``(W, S, y)`` trajectory and the same recorded history.
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aau, topology
from repro.core.baselines import make_scheduler
from repro.core.consensus import metropolis_matrix
from repro.core.runner import DecentralizedTrainer
from repro.core.scheduler import EventBatch
from repro.core.straggler import StragglerModel
from repro.data.synthetic import ClassificationData
from repro.kernels.gossip_mix import (gossip_mix_batched,
                                      gossip_mix_batched_ref,
                                      masked_gossip_mix, masked_gossip_ref)

N = 8
DATA = ClassificationData(n_workers=N, d=16, n_classes=4,
                          samples_per_worker=64, seed=0)


def loss_fn(params, batch):
    logits = batch["x"] @ params["w"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, batch["y"][:, None], axis=1))


def init_fn(key):
    return {"w": jax.random.normal(key, (16, 4)) * 0.1}


def _sched(alg, seed=0):
    g = topology.erdos_renyi(N, 0.4, seed=3)
    sm = StragglerModel(n=N, straggler_prob=0.2, slowdown=6.0, seed=seed)
    return make_scheduler(alg, g, sm)


def _trainer(alg, mode, seed=0, **kw):
    return DecentralizedTrainer(
        _sched(alg, seed), loss_fn, init_fn,
        lambda w, s: DATA.batch(w, s, batch_size=8),
        DATA.eval_batch(64), eta0=0.2, eta_decay=0.99, seed=seed,
        mode=mode, **kw)


class TestEventBatchPacking:
    @pytest.mark.parametrize("alg", ["dsgd_aau", "ad_psgd", "prague", "agp"])
    def test_round_trip(self, alg):
        sched = _sched(alg)
        evs = list(itertools.islice(sched.events(), 12))
        batch = EventBatch.from_events(evs, edge_bound=sched.edge_bound())
        assert batch.E == 12 and batch.n == N
        assert batch.edges.shape[1] == sched.edge_bound()
        for orig, back in zip(evs, batch.to_events()):
            assert back.k == orig.k
            assert back.time == pytest.approx(orig.time)
            np.testing.assert_array_equal(back.grad_workers, orig.grad_workers)
            np.testing.assert_array_equal(back.restart_workers,
                                          orig.restart_workers)
            np.testing.assert_allclose(back.P, orig.P)
            assert back.active_edges == orig.active_edges
            assert back.param_copies_sent == orig.param_copies_sent

    def test_event_batches_api(self):
        sched = _sched("ad_psgd")
        stream = sched.packed_stream(native=False)
        batches = [stream.next_chunk(5) for _ in range(3)]
        assert [b.E for b in batches] == [5, 5, 5]
        assert batches[1].k0 == 5  # consecutive packing
        # AD-PSGD's compact-edge form is one edge per event, not O(n²)
        assert batches[0].edges.shape == (5, 1, 2)

    def test_pad_to_shapes(self):
        sched = _sched("dsgd_aau")
        evs = list(itertools.islice(sched.events(), 3))
        batch = EventBatch.from_events(evs).pad_to(8)
        assert batch.E == 8
        assert not batch.grad_workers[3:].any()
        assert not batch.restart_workers[3:].any()
        np.testing.assert_allclose(batch.P[4], np.eye(N))
        # padded events move no bytes
        assert batch.param_copies_sent[3:].sum() == 0
        assert (batch.n_edges[3:] == 0).all()

    def test_identity_padding_is_noop_on_device(self):
        """A block of pure no-op events leaves (W, S, y, ptr) bit-exact."""
        tr = _trainer("dsgd_aau", "scan")
        tr._ensure_scan()
        W0 = jax.tree.map(lambda x: np.asarray(x).copy(), tr.W)
        sched = _sched("dsgd_aau")
        ev = itertools.islice(sched.events(), 1)
        noop = EventBatch.from_events(list(ev), edge_bound=sched.edge_bound())
        off = np.zeros_like(noop.grad_workers)
        import dataclasses
        noop = dataclasses.replace(
            noop, grad_workers=off, restart_workers=off,
            P=np.eye(N, dtype=np.float32)[None],
            edges=np.full_like(noop.edges, -1),
            n_edges=np.zeros_like(noop.n_edges))
        tr._dispatch_block(noop.pad_to(tr.block_size), rounds=0)
        for a, b in zip(jax.tree.leaves(W0), jax.tree.leaves(tr.W)):
            np.testing.assert_array_equal(a, np.asarray(b))
        np.testing.assert_array_equal(np.asarray(tr._ptr), np.zeros(N))


class TestScanEquivalence:
    """Same scheduler seed ⇒ the compiled scan path replays the per-event
    trainer exactly (fp32): parameters, snapshots, push-sum weights, history."""

    @pytest.mark.parametrize("alg", ["dsgd_aau", "ad_psgd", "agp", "prague"])
    def test_matches_per_event(self, alg):
        ref = _trainer(alg, "per_event")
        res_ref = ref.run(max_events=40, eval_every=10)
        # block_size deliberately not dividing eval_every: exercises the
        # eval-boundary snapping + identity padding
        scan = _trainer(alg, "scan", block_size=7, batch_pool=48)
        res_scan = scan.run(max_events=40, eval_every=10)

        for name, a, b in (("W", ref.W, scan.W), ("S", ref.S, scan.S)):
            for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
                np.testing.assert_allclose(
                    np.asarray(la), np.asarray(lb), atol=1e-6, err_msg=name)
        np.testing.assert_allclose(np.asarray(ref.y), np.asarray(scan.y),
                                   atol=1e-6)  # push-sum weights (AGP ≠ 1)
        assert len(res_ref.history) == len(res_scan.history)
        for p_ref, p_scan in zip(res_ref.history, res_scan.history):
            assert p_scan.k == p_ref.k
            assert p_scan.time == pytest.approx(p_ref.time)
            assert p_scan.loss == pytest.approx(p_ref.loss, abs=1e-5)
            assert p_scan.comm_param_copies == p_ref.comm_param_copies
            assert p_scan.n_active_mean == pytest.approx(p_ref.n_active_mean)
        assert res_scan.total_events == res_ref.total_events
        assert res_scan.total_time == pytest.approx(res_ref.total_time)

    def test_agp_pushsum_debias_survives_scan(self):
        scan = _trainer("agp", "scan", block_size=8, batch_pool=48)
        scan.run(max_events=30, eval_every=30)
        y = np.asarray(scan.y)
        assert not np.allclose(y, 1.0)        # row-stochastic pushes moved mass
        assert y.sum() == pytest.approx(N, rel=1e-4)  # total mass conserved

    def test_max_time_bound(self):
        ref = _trainer("dsgd_aau", "per_event").run(max_time=20.0, eval_every=10)
        scan = _trainer("dsgd_aau", "scan", block_size=4).run(
            max_time=20.0, eval_every=10)
        assert scan.total_events == ref.total_events
        assert scan.final_loss == pytest.approx(ref.final_loss, abs=1e-5)

    def test_warmup_leaves_state_unchanged(self):
        tr = _trainer("dsgd_aau", "scan")
        W0 = jax.tree.map(lambda x: np.asarray(x).copy(), tr.W)
        tr.warmup()
        for a, b in zip(jax.tree.leaves(W0), jax.tree.leaves(tr.W)):
            np.testing.assert_array_equal(a, np.asarray(b))

    def test_eval_buffer_growth_matches_per_event(self):
        """A max_time-bounded run has no up-front eval count, so the scan
        modes start from a small device eval buffer (16 rows) and must grow
        it mid-run; the recorded history has to stay point-for-point equal
        to the per-event path across the growth boundary."""
        ref = _trainer("ad_psgd", "per_event")
        res_ref = ref.run(max_time=8.0, eval_every=1)
        scan = _trainer("ad_psgd", "scan", block_size=4)
        res_scan = scan.run(max_time=8.0, eval_every=1)
        assert len(res_ref.history) > 16  # the initial cap was outgrown
        assert len(res_scan.history) == len(res_ref.history)
        for p_ref, p_scan in zip(res_ref.history, res_scan.history):
            assert p_scan.k == p_ref.k
            assert p_scan.time == pytest.approx(p_ref.time)
            assert p_scan.loss == pytest.approx(p_ref.loss, abs=1e-5)
            assert p_scan.metric == pytest.approx(p_ref.metric, abs=1e-5)
            assert p_scan.comm_param_copies == p_ref.comm_param_copies
            assert p_scan.n_active_mean == pytest.approx(p_ref.n_active_mean)


class TestBatchedMaskedKernels:
    @pytest.mark.parametrize("n,d", [(8, 128), (13, 257), (16, 640)])
    def test_masked_matches_ref(self, n, d):
        k1, k2 = jax.random.split(jax.random.PRNGKey(n * d))
        W = jax.random.normal(k1, (n, d))
        G = jax.random.normal(k2, (n, d))
        P = jnp.asarray(metropolis_matrix(
            n, [(i, (i + 1) % n) for i in range(n - 1)]), jnp.float32)
        mask = (jnp.arange(n) % 2).astype(jnp.float32) * 0.1
        out = masked_gossip_mix(W, G, P, mask, block_d=256)
        ref = masked_gossip_ref(W, G, P, mask)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_masked_zero_mask_is_plain_mix(self):
        n, d = 8, 256
        W = jax.random.normal(jax.random.PRNGKey(0), (n, d))
        P = jnp.asarray(metropolis_matrix(
            n, [(i, (i + 1) % n) for i in range(n)]), jnp.float32)
        out = masked_gossip_mix(W, jnp.ones_like(W), P, jnp.zeros(n))
        ref = masked_gossip_ref(W, jnp.zeros_like(W), P, jnp.zeros(n))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    @pytest.mark.parametrize("E,n,d", [(3, 8, 256), (5, 12, 384)])
    def test_batched_matches_ref(self, E, n, d):
        W = jax.random.normal(jax.random.PRNGKey(E + n), (E, n, d))
        mats = [metropolis_matrix(
            n, [(i, (i + e) % n) for i in range(n - 1) if i != (i + e) % n])
            for e in range(1, E + 1)]
        P = jnp.asarray(np.stack(mats), jnp.float32)
        out = gossip_mix_batched(W, P, block_d=128)
        ref = gossip_mix_batched_ref(W, P)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_fused_step_matches_unfused(self):
        n, d = 16, 640
        key = jax.random.PRNGKey(7)
        W = {"w": jax.random.normal(key, (n, d))}
        G = {"w": jax.random.normal(jax.random.fold_in(key, 1), (n, d))}
        P = jnp.asarray(metropolis_matrix(
            n, [(i, (i + 1) % n) for i in range(n)]), jnp.float32)
        gm = jnp.arange(n) % 2 == 0
        y = jnp.ones(n)
        eta = jnp.float32(0.1)
        ref = aau.masked_gossip_step(W, W, y, G, P, gm, gm, eta,
                                     use_kernel=False)
        fused = aau.masked_gossip_step(W, W, y, G, P, gm, gm, eta,
                                       use_kernel=True)
        for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(fused)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-5)

    def test_scan_with_kernel_matches_plain_scan(self):
        ref = _trainer("dsgd_aau", "scan", block_size=4, batch_pool=24)
        res_ref = ref.run(max_events=12, eval_every=12)
        fused = _trainer("dsgd_aau", "scan", block_size=4, batch_pool=24,
                         use_kernel=True)
        res_fused = fused.run(max_events=12, eval_every=12)
        assert res_fused.final_loss == pytest.approx(res_ref.final_loss,
                                                     abs=1e-4)
