"""Fused device-resident event streaming for single-edge schedulers.

``DecentralizedTrainer(mode="fused")`` — the third stage of the
device-resident event pipeline.  The sparse scan path already consumes
events in compiled blocks, but the events themselves are still *produced*
by a Python heap loop and shipped through packed host arrays; for AD-PSGD
and AGP the event process is simple enough to move on device entirely.
Per event it is a pure recurrence over per-worker next-completion times
(the asynchronous-gossip clock model of Lian et al. 2018 / Assran &
Rabbat 2020):

    i   = argmin(times)                     # next finisher
    t   = lock-shift(times[i])              # AD-PSGD's atomic-average lock
    r   = neighbors[i][⌊pick·deg(i)⌋]       # uniform neighbor pick
    ... 2-lane sparse update on (W, S, y, ptr) ...
    times[i] = t + base[i] · factor         # next completion draw

so one ``lax.scan`` both *generates* the event (argmin "heap" carried in
the scan) and *consumes* it (``sparse_event_update`` — the identical
traced computation the sparse path's scan step runs).  The host's only
job per block is two vectorized RNG draws (completion-time factors and
neighbor picks, ``_SingleEdgeScheduler.fused_draws``); there is no
per-event host work, no packed-array transfer, and no ~100 µs/event
scan-step cost paid on host-visible shapes.

Like the event-horizon batcher (``horizon=K``), the fused stream is
**deterministic but a different RNG-order realization** than the exact
per-event path: factors are drawn as a flat block stream and assigned to
workers in device-decided event order, the clock runs in float32, and the
neighbor pick maps a uniform through ``⌊pick·deg⌋`` instead of
``integers(0, deg)``.  Equivalence is therefore tested distributionally
(event rates, per-worker activation counts) plus exact determinism per
(seed, block size) — see tests/test_fused_stream.py — and the mode is
gated on iid completion-time factors (``TimeModel.iid_horizon``): a
sampler whose factor law depends on the worker or the draw history
(diurnal scenario) cannot be pre-drawn flat.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aau import sparse_event_update

# An isolated worker's event: lane 0 keeps its row (purely local gradient
# step), lane 1 is padding.
_P_SELF2 = np.array([[1.0, 0.0], [0.0, 0.0]], dtype=np.float32)
_LANE_SELF2 = np.array([True, False])


def build_fused_pair_scan(loss_fn: Callable, spec: Dict[str, object],
                          use_kernel: bool = False, telemetry: bool = False):
    """Compile the fused generate-and-consume block for a pair scheduler.

    ``spec`` is ``_SingleEdgeScheduler.fused_spec()`` — the static device
    constants of the event process (padded neighbor table, degrees, base
    compute times, lock interval, the scheduler's frozen 2×2 payloads).

    Returns ``block(W, S, y, ptr, pools, times, lock_free, comm, factors,
    picks, etas) -> ((W, S, y, ptr, times, lock_free, comm), t_seq)``:
    one compiled call advances the worker state *and* the event process
    through ``len(factors)`` events; ``times`` is the (n,) f32 next-
    completion clock (the on-device replacement for the host heap),
    ``lock_free`` the scalar lock-release clock, ``comm`` the running
    int32 parameter-copy counter, and ``t_seq`` the per-event virtual
    clocks (the caller reads ``t_seq[-1]`` for history points).  The
    carry buffers are donated — thread the returned carry into the next
    block, never reuse the arguments.

    With ``telemetry`` the signature is unchanged; only the scan's
    per-event outputs widen from ``t_ev`` to ``(t_ev, i, p, t)`` — each
    event's lock-shifted clock, finisher, partner (−1 when isolated) and
    raw completion.  The runner buffers those outputs per block (device
    arrays, never synced) and consumes the whole run's stream once at
    drain time: folded into its
    :class:`~repro.obs.metrics.MetricsCarry` via
    :func:`~repro.obs.metrics.fused_metrics_fold`, and/or fetched with a
    single ``jax.device_get`` for the virtual-time trace
    (:func:`~repro.obs.trace.drain_fused_payload` — the runner passes
    ``telemetry=True`` here when *either* of its telemetry/trace flags is
    set, since both ride the same widened outputs).  The fused path thus
    stays free of per-event host work *and* of in-block observability
    arithmetic.  The state trajectory is unchanged.
    """
    grad_fn = jax.grad(loss_fn)
    deg = jnp.asarray(spec["deg"], dtype=jnp.int32)
    nbr_table = jnp.asarray(spec["nbr_table"], dtype=jnp.int32)
    base = jnp.asarray(spec["base"], dtype=jnp.float32)
    lock_dt = float(spec["lock_dt"])
    P1 = jnp.asarray(spec["P_first"], dtype=jnp.float32)
    P2 = jnp.asarray(spec["P_second"], dtype=jnp.float32)
    lane1 = jnp.asarray(spec["lane_first"])
    lane2 = jnp.asarray(spec["lane_second"])
    P_self = jnp.asarray(_P_SELF2)
    lane_self = jnp.asarray(_LANE_SELF2)
    copies_pair = int(spec["copies_pair"])

    def _event(W, S, y, ptr, pools, times, lock_free, factor, pick, eta):
        """One generated event: returns the updated state plus the event's
        identity ``(i, p, t, t_ev)`` — finisher, partner (−1 when
        isolated), raw and lock-shifted clocks — from which the callers
        derive comm/telemetry payloads (workers are the sorted pair, the
        finisher's lane is the grad/restart lane, a pair sends
        ``copies_pair`` copies)."""
        i = jnp.argmin(times).astype(jnp.int32)
        t = times[i]
        d = deg[i]
        has_nbr = d > 0
        if lock_dt:
            # serialized atomic averaging (isolated workers skip it)
            t_pair = jnp.maximum(t, lock_free) + jnp.float32(lock_dt)
            t_ev = jnp.where(has_nbr, t_pair, t)
            lock_free = jnp.where(has_nbr, t_ev, lock_free)
        else:
            t_ev = t
        # ⌊pick·deg⌋ clamped: pick ∈ [0, 1) but f32 rounding at huge
        # degree could land exactly on deg
        slot = jnp.minimum((pick * d.astype(jnp.float32))
                           .astype(jnp.int32),
                           jnp.maximum(d - 1, 0))
        r = nbr_table[i, slot]
        first = i < r
        pair = jnp.where(first, jnp.stack([i, r]), jnp.stack([r, i]))
        workers = jnp.where(has_nbr, pair,
                            jnp.stack([i, jnp.full((), -1, jnp.int32)]))
        P_sub = jnp.where(has_nbr, jnp.where(first, P1, P2), P_self)
        lanes = jnp.where(has_nbr,
                          jnp.where(first, lane1, lane2), lane_self)
        W, S, y, ptr = sparse_event_update(
            W, S, y, ptr, pools, grad_fn, workers, P_sub, lanes, lanes,
            eta, use_kernel=use_kernel)
        times = times.at[i].set(t_ev + base[i] * factor)
        p = jnp.where(has_nbr, r, jnp.int32(-1))
        return W, S, y, ptr, times, lock_free, i, p, t, t_ev

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3, 5, 6, 7))
    def block_fused(W, S, y, ptr, pools, times, lock_free, comm,
                    factors, picks, etas):
        def body(carry, xs):
            W, S, y, ptr, times, lock_free, comm = carry
            factor, pick, eta = xs
            (W, S, y, ptr, times, lock_free, i, p, t,
             t_ev) = _event(W, S, y, ptr, pools, times, lock_free,
                            factor, pick, eta)
            comm = comm + jnp.where(p >= 0, copies_pair,
                                    0).astype(comm.dtype)
            # With telemetry the scan additionally streams each event's
            # identity (finisher, partner, raw clock) — the runner buffers
            # these per block, device-resident, and folds them ONCE per run
            # via repro.obs.metrics.fused_metrics_fold; metrics work inside
            # the block (even a per-block fold) is a measurable slice of
            # the fused block's toy-scale runtime.
            ys = (t_ev, i, p, t) if telemetry else t_ev
            return (W, S, y, ptr, times, lock_free, comm), ys

        return jax.lax.scan(body, (W, S, y, ptr, times, lock_free, comm),
                            (factors, picks, etas))

    return block_fused
