"""Runtime sanitizers: transfer guard, leak check, compile-count contract.

The N=64 regression pins **zero implicit device→host transfers per
compiled block** for the sparse_scan / bucketed / dense paths: the whole
driving loop runs under :func:`repro.check.runtime.sanitized`, whose
host-conversion guard raises on any ``float()``/``np.asarray()``/
``.item()`` applied to a jax value outside an explicit
``jax.device_get``.  The compile counter pins PR 6's one-compile-per-rung
contract: after warmup, a steady-state run adds zero jit-cache entries and
the sparse block holds exactly one program per bucket rung.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.check.runtime import (CompileCounter, host_conversion_guard,
                                 jit_cache_size, sanitize_enabled, sanitized)
from repro.core import topology
from repro.core.baselines import make_scheduler
from repro.core.runner import DecentralizedTrainer
from repro.core.straggler import StragglerModel
from repro.data.synthetic import ClassificationData

N = 64
DATA = ClassificationData(n_workers=N, d=16, n_classes=4,
                          samples_per_worker=32, seed=0)


def loss_fn(params, batch):
    logits = batch["x"] @ params["w"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, batch["y"][:, None], axis=1))


def init_fn(key):
    return {"w": jax.random.normal(key, (16, 4)) * 0.1}


def _trainer(alg, mode, sched_kw=None, **kw):
    g = topology.erdos_renyi(N, 0.15, seed=3)
    sm = StragglerModel(n=N, straggler_prob=0.2, slowdown=6.0, seed=0)
    return DecentralizedTrainer(
        make_scheduler(alg, g, sm, **(sched_kw or {})), loss_fn, init_fn,
        lambda w, s: DATA.batch(w, s, batch_size=8),
        DATA.eval_batch(64), eta0=0.2, seed=0, mode=mode, **kw)


class TestHostConversionGuard:
    def test_implicit_conversions_raise(self):
        x = jnp.ones(())
        # np.asarray via a lambda: the guard patches the numpy module
        # attribute, so the lookup must happen under the guard
        for convert in (float, int, bool, lambda v: np.asarray(v),
                        lambda v: v.item(), lambda v: v.tolist()):
            with sanitized(check_leaks=False):
                with pytest.raises(RuntimeError, match="implicit device"):
                    convert(x)

    def test_explicit_device_get_is_legal(self):
        with sanitized(check_leaks=False):
            v = jax.device_get(jnp.arange(4))
            assert isinstance(v, np.ndarray)
            # host data downstream of the fetch converts freely
            assert float(np.max(v)) == 3.0

    def test_guard_restores_on_exit(self):
        with sanitized(check_leaks=False):
            pass
        assert float(jnp.ones(())) == 1.0

    def test_audit_mode_records_instead_of_raising(self):
        with host_conversion_guard(raise_on_violation=False) as violations:
            float(jnp.ones(()))
            np.asarray(jnp.zeros((2, 3)))
            assert ("__float__", ()) in violations
            assert ("asarray", (2, 3)) in violations

    def test_env_flag(self):
        assert not sanitize_enabled("")
        assert not sanitize_enabled("0")
        assert sanitize_enabled("1")

    def test_leak_check_catches_tracer_escape(self):
        leaked = []

        @jax.jit
        def leaky(x):
            leaked.append(x)
            return x + 1

        with pytest.raises(Exception, match="[Ll]eak"):
            with sanitized(transfer_guard=None):
                leaky(jnp.ones(()))


class TestZeroImplicitTransfersN64:
    """sparse_scan / bucketed / dense scan at N=64 complete a full run
    with zero implicit device→host transfers.

    The runs wrap in the transfer guard alone (``check_leaks=False``):
    tracing the N=64 scan under ``jax.checking_leaks`` costs minutes, and
    leak coverage on a real run lives in the N=16 full-stack test below.
    """

    @pytest.mark.parametrize("alg,mode", [
        ("ad_psgd", "sparse_scan"),            # single-rung sparse
        ("dsgd_aau", "sparse_scan"),           # bucketed (16, 64)
        ("dsgd_sync", "scan"),                 # dense barrier scan
    ], ids=["sparse_scan", "bucketed", "dense"])
    def test_run_has_zero_implicit_transfers(self, alg, mode):
        tr = _trainer(alg, mode, block_size=16)
        with sanitized(check_leaks=False):
            result = tr.run(max_events=96, eval_every=32)
        assert np.isfinite(result.final_loss)
        assert result.total_events == 96

    def test_guarded_run_matches_unguarded(self):
        r0 = _trainer("ad_psgd", "sparse_scan", block_size=16).run(
            max_events=64, eval_every=32)
        with sanitized(check_leaks=False):
            r1 = _trainer("ad_psgd", "sparse_scan", block_size=16).run(
                max_events=64, eval_every=32)
        assert r0.final_loss == r1.final_loss  # sanitizers observe, never alter


class TestTrainerSanitizeFlag:
    def test_env_flag_reaches_trainer(self, monkeypatch):
        monkeypatch.setenv("REPRO_SANITIZE", "1")
        assert _trainer("ad_psgd", "sparse_scan", block_size=16).sanitize
        monkeypatch.setenv("REPRO_SANITIZE", "0")
        assert not _trainer("ad_psgd", "sparse_scan", block_size=16).sanitize

    def test_full_stack_sanitized_run_small(self):
        """leak check + transfer guard around a real (N=16) run, via the
        trainer's own ``sanitize=True`` path"""
        n = 16
        data = ClassificationData(n_workers=n, d=16, n_classes=4,
                                  samples_per_worker=32, seed=0)
        tr = DecentralizedTrainer(
            make_scheduler("ad_psgd", topology.erdos_renyi(n, 0.4, seed=3),
                           StragglerModel(n=n, straggler_prob=0.2,
                                          slowdown=6.0, seed=0)),
            loss_fn, init_fn, lambda w, s: data.batch(w, s, batch_size=8),
            data.eval_batch(64), eta0=0.2, seed=0, mode="sparse_scan",
            block_size=16, sanitize=True)
        result = tr.run(max_events=64, eval_every=32)
        assert np.isfinite(result.final_loss)


class TestCompileCountPerRung:
    def test_one_compile_per_rung_bucketed(self):
        # batch_pool pinned: the auto-sized pool would grow mid-run for
        # max_events=96 and re-trace each rung (see warmup's docstring)
        tr = _trainer("dsgd_aau", "sparse_scan", block_size=16,
                      batch_pool=128)
        buckets = tr.scheduler.active_buckets()
        assert len(buckets) > 1, "N=64 AAU ladder should be multi-rung"
        tr.warmup()
        counter = CompileCounter()
        counter.track("sparse", tr._sparse)
        counter.assert_equals("sparse", len(buckets))
        tr.run(max_events=96, eval_every=32)
        # steady state: the run dispatches into the warmed per-rung
        # programs and compiles nothing new
        counter.assert_steady_state("sparse")
        counter.assert_equals("sparse", len(buckets))

    def test_counter_raises_on_contract_violation(self):
        tr = _trainer("ad_psgd", "sparse_scan", block_size=16)
        tr.warmup()
        counter = CompileCounter()
        counter.track("sparse", tr._sparse)
        with pytest.raises(AssertionError, match="compile-count"):
            counter.assert_equals("sparse", 99)

    def test_cache_size_readable(self):
        tr = _trainer("ad_psgd", "sparse_scan", block_size=16)
        tr.warmup()
        assert jit_cache_size(tr._sparse) == 1
        assert jit_cache_size(object()) is None
