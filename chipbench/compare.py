"""The numbers that decide ``correct``, each against its limit.

After each set-up run (one event, then three runs of the cell's size), a
reading of the state: for every worker and every parameter leaf, the norm
of its change from the common initial parameters, for W and for the
snapshots S, with the restart counters ptr, the push-sum weights y and the
run's history losses.  The program's readings and the reference's are
compared as the gap between the two norms, by the worst worker-leaf,
measured against the reference's norm of that leaf or the median moved
leaf's, whichever is larger.  A leaf the reference leaves unmoved (a worker
no event touched yet) is measured against the median, so the program has
to leave it unmoved too.  Plain SGD moves no leaf by round-off alone, so no
leaf is left out.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# Compared numbers, in the order they are printed.  ``ptr_mismatch`` is an
# exact comparison (limit 0); the others are shares.
NUMBERS = ("loss_gap", "event1_gap", "run1_gap", "run3_gap", "snap3_gap",
           "ptr_mismatch")


@jax.jit
def _change_norms(W, S, p0):
    def norms(T):
        return jnp.stack([
            jnp.sqrt(jnp.sum(jnp.square(t.astype(jnp.float32) - p[None]),
                             axis=tuple(range(1, t.ndim))))
            for t, p in zip(jax.tree.leaves(T), jax.tree.leaves(p0))])
    return norms(W), norms(S)


def reading(W, S, y, ptr, p0, losses):
    """What is kept of one run's end state: small host arrays."""
    dW, dS = jax.device_get(_change_norms(W, S, p0))
    return {"dW": np.asarray(dW, np.float64), "dS": np.asarray(dS, np.float64),
            "y": np.asarray(jax.device_get(y), np.float64),
            "ptr": np.asarray(jax.device_get(ptr), np.int64),
            "losses": [float(v) for v in losses]}


def leaf_gap(prog: np.ndarray, ref: np.ndarray) -> float:
    prog, ref = np.ravel(prog), np.ravel(ref)
    moved = ref[ref > 0]
    if not moved.size:
        return float("inf") if np.any(prog > 0) else 0.0
    den = np.maximum(ref, float(np.median(moved)))
    return float(np.max(np.abs(prog - ref) / den))


def numbers(prog, ref):
    """Compared numbers from the program's and the reference's readings
    (one per set-up run: the first event, then runs 1 to 3)."""
    lp = np.concatenate([r["losses"] for r in prog])
    lr = np.concatenate([r["losses"] for r in ref])
    if lp.shape != lr.shape:
        loss_gap = float("inf")
    else:
        loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    return {
        "loss_gap": loss_gap,
        "event1_gap": leaf_gap(prog[0]["dW"], ref[0]["dW"]),
        "run1_gap": leaf_gap(prog[1]["dW"], ref[1]["dW"]),
        "run3_gap": leaf_gap(prog[-1]["dW"], ref[-1]["dW"]),
        "snap3_gap": leaf_gap(prog[-1]["dS"], ref[-1]["dS"]),
        "ptr_mismatch": float(np.sum(prog[-1]["ptr"] != ref[-1]["ptr"])),
        # not compared: y stays 1 to rounding under doubly-stochastic mixing
        "y_gap": float(np.max(np.abs(prog[-1]["y"] - ref[-1]["y"]))),
    }


def verdict(nums, limits):
    """(correct, [(name, value, limit)]) — every compared number at or
    under its limit; a NaN or a missing limit is not correct."""
    rows = [(k, nums[k], limits.get(k)) for k in NUMBERS]
    ok = all(lim is not None and v == v and v <= lim for _, v, lim in rows)
    return ok, rows
