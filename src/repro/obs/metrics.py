"""Device-resident telemetry accumulators for the event-stream simulators.

:class:`MetricsCarry` is a NamedTuple of small device arrays that rides the
``(W, S, y, ptr)`` carry of every execution mode's scan (dense, sparse,
bucketed) and of the per-event interpreter's jitted step.  Updates
are **order-exact across representations**: every accumulator uses only
operations whose result is independent of how the stream is chunked or
merged —

- integer adds / maxes and integer scatter-adds (exact, commutative);
- boolean participation tests derived from the consensus matrix itself
  (``P`` row/column off-diagonal support — identical floats in the dense
  stack and the active-set submatrix, see core/scheduler.py);
- per-worker float32 adds where non-participants contribute an exact
  ``+0.0`` (``x + 0.0 == x`` bitwise for the non-negative accumulators),
  at most one add per worker per scan step (merged rows have pairwise
  disjoint worker sets by construction — ``merge_event_groups``);
- a pure-integer log2 binning for the staleness histogram (no float
  ``log2`` whose rounding could differ between chunkings).

so the drained counters are **bit-identical** across ``per_event``,
``scan``, ``sparse_scan`` and bucketed dispatch of the same stream, which
tests/test_telemetry.py pins.

Staleness semantics: a worker's gradient is evaluated at the snapshot it
took at its previous restart, so when worker ``w`` fires a gradient at
event ``k`` its staleness is ``s = k − last_restart_k[w] − 1`` — 0 when it
participated in the immediately preceding event, and ``k`` on its first
participation (``last_restart_k`` initializes to −1: the initial snapshot
predates event 0).

For DSGD-AAU, Pathsearch's per-epoch commit bound B ≤ N−1 (paper Remark 4)
induces a hard event-staleness bound of **2N−4**: every event commits at
least one novel edge, an epoch holds at most N−1 of them, and no epoch can
*complete* until every worker has joined V (which requires participating).
So between worker w's consecutive participations at most N−2 events can
drain the current epoch's remaining unions, and at most N−2 more can merge
the other N−1 workers in the next epoch before any further union needs w
as an endpoint — the next event necessarily includes w.  The runtime
monitor checks the drained ``stale_max`` against 2N−4; heavy-tailed
straggler scenarios empirically *reach* it (the bound is tight), which is
what makes it a real invariant check rather than a slack one.
"""
from __future__ import annotations

from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

#: Histogram rungs: bin b counts gradient firings with staleness s where
#: ``floor(log2(s + 1)) == b`` — bin 0 is s = 0, the last bin absorbs
#: everything from 2^15 − 1 up.
STALE_HIST_BINS = 16


class MetricsCarry(NamedTuple):
    """Per-worker / scalar telemetry accumulators (all device arrays)."""

    grad_steps: jax.Array       # (n,) int32 — gradient firings per worker
    mix_count: jax.Array        # (n,) int32 — gossip participations (events
                                #   where the worker's P row/col mixed mass)
    last_restart_k: jax.Array   # (n,) int32 — event index of the last
                                #   restart; −1 before the first
    last_mix_t: jax.Array       # (n,) f32 — virtual clock of the last mix
    last_restart_t: jax.Array   # (n,) f32 — virtual clock of the last restart
    busy_t: jax.Array           # (n,) f32 — Σ local-computation time
    idle_t: jax.Array           # (n,) f32 — Σ wait time (finish → event)
    stale_max: jax.Array        # () int32 — max observed gradient staleness
    stale_sum: jax.Array        # () int32 — Σ staleness over gradient firings
    stale_hist: jax.Array       # (STALE_HIST_BINS,) int32 — log2-binned
    comm_copies: jax.Array      # () int32 — Σ parameter copies sent


def init_metrics(n: int) -> MetricsCarry:
    return MetricsCarry(
        grad_steps=jnp.zeros((n,), dtype=jnp.int32),
        mix_count=jnp.zeros((n,), dtype=jnp.int32),
        last_restart_k=jnp.full((n,), -1, dtype=jnp.int32),
        last_mix_t=jnp.zeros((n,), dtype=jnp.float32),
        last_restart_t=jnp.zeros((n,), dtype=jnp.float32),
        busy_t=jnp.zeros((n,), dtype=jnp.float32),
        idle_t=jnp.zeros((n,), dtype=jnp.float32),
        stale_max=jnp.int32(0),
        stale_sum=jnp.int32(0),
        stale_hist=jnp.zeros((STALE_HIST_BINS,), dtype=jnp.int32),
        comm_copies=jnp.int32(0),
    )


def _stale_bins(s: jax.Array) -> jax.Array:
    """``floor(log2(s + 1))`` via pure integer comparisons (exact).

    ``bin = Σ_j [s + 1 >= 2^j]`` for j = 1..STALE_HIST_BINS−1: no float
    log whose rounding could differ between the dense and sparse update
    shapes.  Negative ``s`` (masked-out lanes) maps to bin 0 — callers
    gate the histogram add on the gradient mask, so the value never lands.
    """
    thresholds = 2 ** jnp.arange(1, STALE_HIST_BINS, dtype=jnp.int32)
    return jnp.sum((s[..., None] + 1) >= thresholds, axis=-1).astype(jnp.int32)


def _staleness(M: MetricsCarry, last_k: jax.Array, gm: jax.Array,
               ks: jax.Array):
    """(stale_max', stale_sum', hist delta bins, per-slot counts)."""
    s = ks - last_k - 1
    stale_max = jnp.maximum(
        M.stale_max, jnp.max(jnp.where(gm, s, -1)).astype(jnp.int32))
    stale_sum = M.stale_sum + jnp.sum(jnp.where(gm, s, 0)).astype(jnp.int32)
    return stale_max, stale_sum, _stale_bins(s)


def dense_metrics_update(M: MetricsCarry, P: jax.Array, gm: jax.Array,
                         rm: jax.Array, t: jax.Array, fin: jax.Array,
                         k: jax.Array, copies: jax.Array) -> MetricsCarry:
    """One dense event's telemetry: the (n,)-shaped sibling of the sparse
    update below (``per_event`` and ``scan`` modes).

    P: (n, n) consensus matrix; gm/rm: (n,) bool masks; t: scalar f32
    event clock; fin: (n,) f32 raw completion clocks (only read where
    ``rm``); k: scalar int32 event index; copies: scalar int32.
    """
    n = P.shape[0]
    offdiag = P * (1.0 - jnp.eye(n, dtype=P.dtype))
    coupled = jnp.any(offdiag != 0, axis=1) | jnp.any(offdiag != 0, axis=0)
    gi = gm.astype(jnp.int32)
    stale_max, stale_sum, bins = _staleness(M, M.last_restart_k, gm, k)
    return MetricsCarry(
        grad_steps=M.grad_steps + gi,
        mix_count=M.mix_count + coupled.astype(jnp.int32),
        last_restart_k=jnp.where(rm, k, M.last_restart_k),
        last_mix_t=jnp.where(coupled, t, M.last_mix_t),
        last_restart_t=jnp.where(rm, t, M.last_restart_t),
        busy_t=M.busy_t + jnp.where(rm, fin - M.last_restart_t,
                                    jnp.float32(0.0)),
        idle_t=M.idle_t + jnp.where(rm, t - fin, jnp.float32(0.0)),
        stale_max=stale_max,
        stale_sum=stale_sum,
        stale_hist=M.stale_hist.at[bins].add(gi),
        comm_copies=M.comm_copies + copies,
    )


def sparse_metrics_update(M: MetricsCarry, workers: jax.Array,
                          P_sub: jax.Array, gm: jax.Array, rm: jax.Array,
                          ts: jax.Array, fin: jax.Array, ks: jax.Array,
                          copies: jax.Array) -> MetricsCarry:
    """One active-set scan step's telemetry (``sparse_scan`` / bucketed /
    merged rows).

    workers: (A,) int32, −1-padded; P_sub: (A, A); gm/rm: (A,) per-lane
    bools; ts/fin: (A,) f32 per-lane event / raw-completion clocks (merged
    rows carry each member event's own clock); ks: (A,) int32 per-lane
    event indices; copies: scalar int32 (a merged row carries the group
    sum — same total, exactly).

    A worker appears in at most one lane per step (events within a merged
    row have pairwise disjoint active sets), so every scatter touches each
    accumulator slot at most once — adds and sets land in stream order
    across steps, which is what makes the drained counters bit-identical
    to the dense per-event updates.
    """
    n = M.grad_steps.shape[0]
    A = workers.shape[0]
    valid = workers >= 0
    gidx = jnp.where(valid, workers, 0)
    sidx = jnp.where(valid, workers, n)         # OOB ⇒ scatter drops the lane
    gmv = gm & valid
    rmv = rm & valid
    offdiag = P_sub * (1.0 - jnp.eye(A, dtype=P_sub.dtype))
    coupled = (jnp.any(offdiag != 0, axis=1)
               | jnp.any(offdiag != 0, axis=0)) & valid
    gi = gmv.astype(jnp.int32)
    stale_max, stale_sum, bins = _staleness(
        M, M.last_restart_k[gidx], gmv, ks)
    return MetricsCarry(
        grad_steps=M.grad_steps.at[sidx].add(gi, mode="drop"),
        mix_count=M.mix_count.at[sidx].add(coupled.astype(jnp.int32),
                                           mode="drop"),
        last_restart_k=M.last_restart_k.at[
            jnp.where(rmv, workers, n)].set(ks, mode="drop"),
        last_mix_t=M.last_mix_t.at[
            jnp.where(coupled, workers, n)].set(ts, mode="drop"),
        last_restart_t=M.last_restart_t.at[
            jnp.where(rmv, workers, n)].set(ts, mode="drop"),
        busy_t=M.busy_t.at[sidx].add(
            jnp.where(rmv, fin - M.last_restart_t[gidx], jnp.float32(0.0)),
            mode="drop"),
        idle_t=M.idle_t.at[sidx].add(
            jnp.where(rmv, ts - fin, jnp.float32(0.0)), mode="drop"),
        stale_max=stale_max,
        stale_sum=stale_sum,
        # masked lanes add an exact integer 0 at their (garbage) bin
        stale_hist=M.stale_hist.at[bins].add(gi),
        comm_copies=M.comm_copies + copies,
    )


def block_metrics_update(M: MetricsCarry, workers: jax.Array,
                         gm: jax.Array, rm: jax.Array, coupled: jax.Array,
                         ts: jax.Array, fin: jax.Array, ks: jax.Array,
                         copies: jax.Array) -> MetricsCarry:
    """Fold a whole block of E events into the carry in one vectorized pass.

    The amortized sibling of :func:`sparse_metrics_update`: the only
    genuinely sequential state — each worker's last restart — is recovered
    with an exclusive ``lax.cummax`` prefix over the block, and every
    accumulator lands in a single flattened scatter per block, so
    telemetry cost is O(E·n) vectorized work amortized over E events.
    This is the *generic* block fold (arbitrary lane payloads), pinned
    against the sequential per-event updates by tests/test_telemetry.py.

    workers: (E, A) int32, −1-padded; gm/rm/coupled: (E, A) bools (the
    caller derives ``coupled`` from its payload structure); ts: (E,) f32
    per-event clocks; fin: (E, A) f32 raw completion clocks; ks: (E,)
    int32 **consecutive** event indices (``ks[0] + arange(E)`` — the
    prefix gather maps event index → block position by subtraction);
    copies: (E,) int32 per-event copy counts.

    Integer counters are bit-identical to the sequential fold; the f32
    busy/idle accumulators sum a block's contributions in scatter order
    before adding to the carry, so they are deterministic but not
    add-order-identical to the per-event fold.
    """
    n = M.grad_steps.shape[0]
    E, A = workers.shape
    valid = workers >= 0
    gmv = gm & valid
    rmv = rm & valid
    cpl = coupled & valid
    gidx = jnp.where(valid, workers, 0)
    k0 = ks[0]
    # (E, n) "worker w restarted at event e" → exclusive last-restart prefix
    hot_r = jnp.any((workers[:, :, None]
                     == jnp.arange(n, dtype=jnp.int32))
                    & rmv[:, :, None], axis=1)
    rk = jnp.where(hot_r, ks[:, None], jnp.int32(-1))
    cmax = jax.lax.cummax(rk, axis=0)
    prefix = jnp.concatenate(
        [jnp.full((1, n), -1, dtype=jnp.int32), cmax[:-1]])
    in_blk = prefix >= k0
    pos = jnp.clip(prefix - k0, 0, E - 1)
    eff_k = jnp.where(in_blk, prefix, M.last_restart_k[None, :])
    eff_t = jnp.where(in_blk, ts[pos], M.last_restart_t[None, :])
    lk = jnp.take_along_axis(eff_k, gidx, axis=1)       # (E, A)
    lt = jnp.take_along_axis(eff_t, gidx, axis=1)
    s = ks[:, None] - lk - 1
    stale_max = jnp.maximum(
        M.stale_max, jnp.max(jnp.where(gmv, s, -1)).astype(jnp.int32))
    stale_sum = M.stale_sum + jnp.sum(jnp.where(gmv, s, 0)).astype(jnp.int32)
    gi = gmv.astype(jnp.int32)
    sidx = jnp.where(valid, workers, n).ravel()         # OOB ⇒ dropped
    fin_k = cmax[-1]                                    # latest in-block restart
    fin_in = fin_k >= k0
    mix_k = jnp.max(jnp.where(
        jnp.any((workers[:, :, None] == jnp.arange(n, dtype=jnp.int32))
                & cpl[:, :, None], axis=1),
        ks[:, None], jnp.int32(-1)), axis=0)
    return MetricsCarry(
        grad_steps=M.grad_steps.at[sidx].add(gi.ravel(), mode="drop"),
        mix_count=M.mix_count.at[sidx].add(cpl.astype(jnp.int32).ravel(),
                                           mode="drop"),
        last_restart_k=jnp.where(fin_in, fin_k, M.last_restart_k),
        last_mix_t=jnp.where(mix_k >= k0,
                             ts[jnp.clip(mix_k - k0, 0, E - 1)],
                             M.last_mix_t),
        last_restart_t=jnp.where(fin_in,
                                 ts[jnp.clip(fin_k - k0, 0, E - 1)],
                                 M.last_restart_t),
        busy_t=M.busy_t.at[sidx].add(
            jnp.where(rmv, fin - lt, jnp.float32(0.0)).ravel(),
            mode="drop"),
        idle_t=M.idle_t.at[sidx].add(
            jnp.where(rmv, ts[:, None] - fin, jnp.float32(0.0)).ravel(),
            mode="drop"),
        stale_max=stale_max,
        stale_sum=stale_sum,
        stale_hist=M.stale_hist.at[_stale_bins(s).ravel()].add(gi.ravel()),
        comm_copies=M.comm_copies + jnp.sum(copies).astype(jnp.int32),
    )


def metrics_summary(M: MetricsCarry, t_end: float,
                    n_minus_1_bound: bool = False) -> Dict[str, object]:
    """Drain the carry to host (one fetch) and derive the report fields.

    Returns a JSON-friendly dict: per-worker arrays as lists plus derived
    scalars — mean utilization (busy / (busy + idle)), mean staleness,
    per-worker virtual age since the last mix.  With ``n_minus_1_bound``
    (DSGD-AAU) the dict carries a ``staleness_bound`` sub-dict checking
    ``stale_max ≤ 2N − 4`` — the event-staleness bound induced by the
    per-epoch commit bound B ≤ N−1 (see the module docstring).
    """
    host = jax.device_get(M)
    n = int(host.grad_steps.shape[0])
    busy = np.asarray(host.busy_t, dtype=np.float64)
    idle = np.asarray(host.idle_t, dtype=np.float64)
    span = busy + idle
    util = np.divide(busy, span, out=np.zeros_like(busy), where=span > 0)
    grads_total = int(host.grad_steps.sum())
    out: Dict[str, object] = {
        "grad_steps": [int(v) for v in host.grad_steps],
        "mix_count": [int(v) for v in host.mix_count],
        "busy_t": [round(float(v), 6) for v in busy],
        "idle_t": [round(float(v), 6) for v in idle],
        "utilization": [round(float(v), 6) for v in util],
        "utilization_mean": float(util.mean()) if n else 0.0,
        "mix_age": [round(float(t_end) - float(v), 6)
                    for v in host.last_mix_t],
        "stale_max": int(host.stale_max),
        "stale_mean": (float(host.stale_sum) / grads_total
                       if grads_total else 0.0),
        "stale_hist": [int(v) for v in host.stale_hist],
        "comm_copies": int(host.comm_copies),
    }
    if n_minus_1_bound:
        bound = max(0, 2 * n - 4)
        out["staleness_bound"] = {
            "bound": bound,
            "edges_per_epoch_bound": max(0, n - 1),
            "observed_max": int(host.stale_max),
            "ok": bool(int(host.stale_max) <= bound),
        }
    return out
