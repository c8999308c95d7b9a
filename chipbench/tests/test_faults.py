"""With the timed path broken underneath, a run comes out not correct.

Each fault is planted in the program's event update (core/aau.py) before
the trainer is built, and the rest of a run is driven as on the chip: a
block that returns its state unchanged, half of every batch left out (the
loss is the mean over the rest), and the exchange between workers left out
(every event mixes with the identity), and the push-sum weights y altered
where they are produced (scaled by 1 % at every block).  y is not a compared
number: it stays 1 to rounding under doubly-stochastic mixing in sound runs,
the control and the other faults alike, so nothing sets a limit for it; a
wrong y shows in the losses, evaluated at W / y, and in the gradients taken
there.  The cells run one chip and produce no tokens, so the faults of an
exchange between chips and of an altered token do not arise.
"""
import time

import jax.numpy as jnp
import pytest

from chipbench import run
from chipbench.tests.conftest import one_cell_per_mix, tiny_cell


def _unchanged(monkeypatch, aau):
    monkeypatch.setattr(aau, "sparse_gossip_scan",
                        lambda W, S, y, ptr, *a, **k: (W, S, y, ptr))
    monkeypatch.setattr(aau, "masked_gossip_scan",
                        lambda W, S, y, ptr, *a, **k: (W, S, y, ptr))


def _half_batch(monkeypatch, aau):
    def halve(fn):
        def sel(*a, **k):
            out = fn(*a, **k)
            return {key: v[:, : v.shape[1] // 2] for key, v in out.items()}
        return sel
    monkeypatch.setattr(aau, "select_pool_batch_at",
                        halve(aau.select_pool_batch_at))
    monkeypatch.setattr(aau, "select_pool_batch", halve(aau.select_pool_batch))


def _no_exchange(monkeypatch, aau):
    sparse, dense = aau.sparse_event_update, aau.masked_gossip_step

    def sparse_alone(W, S, y, ptr, pools, grad_fn, workers, P_sub, *a, **k):
        eye = jnp.eye(P_sub.shape[0], dtype=P_sub.dtype)
        return sparse(W, S, y, ptr, pools, grad_fn, workers,
                      eye * (workers >= 0)[:, None], *a, **k)

    def dense_alone(W, S, y, grads, P, *a, **k):
        return dense(W, S, y, grads, jnp.eye(P.shape[0], dtype=P.dtype),
                     *a, **k)

    monkeypatch.setattr(aau, "sparse_event_update", sparse_alone)
    monkeypatch.setattr(aau, "masked_gossip_step", dense_alone)


def _y_altered(monkeypatch, aau):
    for name in ("sparse_gossip_scan", "masked_gossip_scan"):
        def altered(*a, _scan=getattr(aau, name), **k):
            W, S, y, ptr = _scan(*a, **k)
            return W, S, y * 1.01, ptr
        monkeypatch.setattr(aau, name, altered)


FAULTS = {"unchanged": _unchanged, "half_batch": _half_batch,
          "no_exchange": _no_exchange, "y_altered": _y_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", one_cell_per_mix())
def test_fault_is_not_correct(name, fault, monkeypatch, peak):
    from repro.core import aau
    FAULTS[fault](monkeypatch, aau)
    res = run.run_cell(tiny_cell(name), 2 ** 31 + 11, 0.1, False, peak,
                       t_start=time.perf_counter())
    assert res["correct"] is False, res["checks"]
