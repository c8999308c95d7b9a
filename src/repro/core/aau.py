"""DSGD-AAU parameter updates in JAX.

Three execution modes share the same math (eq. 5, ``W(k) = [W(k−1) − ηG] P(k)``):

1. **Per-event simulator** (`masked_gossip_step` / `build_event_step`): all N
   workers' parameters live in one pytree with a leading worker axis; one
   jitted dispatch advances one ScheduleEvent.  Kept as the reference path
   (the scan path is equivalence-tested against it).  The mixing contraction
   optionally runs through the Pallas ``gossip_mix`` kernels — with
   ``use_kernel`` the whole event (gradient step + mixing) is the single
   fused ``masked_gossip_mix`` kernel call.

2. **Block-compiled simulator** (`masked_gossip_scan` / `build_event_scan`):
   an entire :class:`~repro.core.scheduler.EventBatch` — stacked
   ``(E, n, n)`` consensus matrices, ``(E, n)`` masks, ``(E,)`` step sizes —
   advances ``(W, S, y)`` inside one ``jax.lax.scan``, i.e. one XLA dispatch
   per E events instead of E dispatches.  Per-worker batch refresh happens
   *on device*: each worker owns a pre-drawn sample pool (leading axes
   ``(n, pool)``) indexed by a restart counter ``ptr`` that the scan carries
   and bumps wherever ``restart_workers`` fires, eliminating the host
   round-trip the legacy runner paid per event.  ``ptr`` wraps modulo the
   pool size, so runs longer than the pool revisit samples cyclically —
   size the pool to the expected restart count for exact per-event
   equivalence.

2b. **Sparse active-set simulator** (`sparse_gossip_scan` /
   `build_sparse_event_scan`): the same block-compiled scan consuming
   :class:`~repro.core.scheduler.SparseEventBatch` arrays — per event it
   *gathers* the ≤A active workers' rows, snapshots, and pool batches,
   evaluates gradients only for those lanes, mixes with the A×A consensus
   submatrix (optionally via the Pallas ``sparse_gossip`` gather-fused
   kernel), and *scatters* the updated rows back.  O(A·D) gradient work and
   O(A²·D) mixing per event instead of O(n·D)/O(n²·D) — the active-set cut
   that makes single-edge schedulers (AD-PSGD/AGP, A=2) cheap at N=256.

3. **Sharded production gossip** (`ring_gossip`, `graph_gossip`): inside
   ``shard_map`` over the mesh ``data``/worker axis, neighbor exchange is one
   ``jax.lax.ppermute`` per edge-direction — the TPU-native analogue of the
   paper's MPI peer-to-peer sends, touching only ICI neighbor links instead of
   a global all-reduce.  Used by launch/train.py and the dry-run.
"""
from __future__ import annotations

import functools
from typing import Callable, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.obs.metrics import dense_metrics_update, sparse_metrics_update

Pytree = object


# ---------------------------------------------------------------------------
# Stacked-worker simulator updates
# ---------------------------------------------------------------------------

def gossip_mix_dense(W: Pytree, P: jax.Array, use_kernel: bool = False) -> Pytree:
    """out[j] = Σ_i P[i, j] · W[i]  for every leaf (leading axis = worker)."""
    if use_kernel:
        from repro.kernels.gossip_mix import ops as gossip_ops
        return jax.tree.map(lambda x: gossip_ops.gossip_mix(x, P.astype(x.dtype)), W)
    def mix(x):
        flat = x.reshape(x.shape[0], -1)
        out = jnp.einsum("nd,nj->jd", flat, P.astype(x.dtype),
                         precision=jax.lax.Precision.HIGHEST)
        return out.reshape(x.shape)
    return jax.tree.map(mix, W)


def masked_gossip_step(
    W: Pytree,
    S: Pytree,
    y: jax.Array,
    grads: Pytree,
    P: jax.Array,
    grad_mask: jax.Array,
    restart_mask: jax.Array,
    eta: jax.Array,
    use_kernel: bool = False,
) -> Tuple[Pytree, Pytree, jax.Array]:
    """One ScheduleEvent applied to stacked worker state.

    W: current parameters, leading axis N.
    S: snapshots at which in-flight gradients were evaluated.
    y: push-sum weights (stays all-ones for doubly-stochastic algorithms).
    grads: ∇F_j evaluated at S (all workers; masked here).
    Returns (W', S', y').
    """
    def expand(mask, leaf):
        return mask.reshape((-1,) + (1,) * (leaf.ndim - 1)).astype(leaf.dtype)

    gm = grad_mask
    # η is traced as float32; fold it into the 0/1 mask *before* casting to
    # each leaf's dtype (exact for fp32 — the product is η or 0) so a bf16
    # worker state stays bf16 through the update instead of being promoted
    # by the f32 scalar (a scan carry must keep its dtype).
    scaled = eta * gm.astype(jnp.float32)
    with jax.named_scope("mix"):
        if use_kernel:
            # Fused Pallas path: Pᵀ·(W − η·mask⊙G) in one kernel per leaf.
            from repro.kernels.gossip_mix import ops as gossip_ops
            Wn = jax.tree.map(
                lambda w, g: gossip_ops.masked_gossip_mix(
                    w, g, P.astype(w.dtype), scaled.astype(w.dtype)),
                W, grads)
        else:
            Wg = jax.tree.map(lambda w, g: w - expand(scaled, w) * g, W, grads)
            Wn = gossip_mix_dense(Wg, P, use_kernel=False)
        yn = jnp.einsum("n,nj->j", y, P.astype(y.dtype))
    rm = restart_mask
    with jax.named_scope("s_update"):
        Sn = jax.tree.map(lambda s, w: jnp.where(expand(rm, w) > 0, w, s), S, Wn)
    return Wn, Sn, yn


def debiased_average(W: Pytree, y: jax.Array) -> Pytree:
    """Network average of push-sum de-biased estimates: mean_j (W_j / y_j)."""
    def avg(x):
        yb = y.reshape((-1,) + (1,) * (x.ndim - 1)).astype(x.dtype)
        return jnp.mean(x / yb, axis=0)
    return jax.tree.map(avg, W)


# ---------------------------------------------------------------------------
# Sharded production gossip (shard_map over the worker axis)
# ---------------------------------------------------------------------------

def ring_gossip(x: jax.Array, axis_name: str, n: int,
                self_w: jax.Array, left_w: jax.Array, right_w: jax.Array) -> jax.Array:
    """Weighted ring gossip along a mesh axis: one ppermute per direction.

    ``out_j = self_w·x_j + left_w·x_{j−1} + right_w·x_{j+1}`` (indices mod n).
    With Metropolis ring weights (1/3, 1/3, 1/3) this is the doubly-stochastic
    mixing of a static ring; the weights may be masked per-step to express an
    AAU active-edge subset (a zero weight deactivates the edge — the permute
    still lowers, which is what the dry-run measures as worst-case traffic).
    """
    if n == 1:
        return x
    fwd = [(i, (i + 1) % n) for i in range(n)]
    bwd = [((i + 1) % n, i) for i in range(n)]
    from_left = jax.lax.ppermute(x, axis_name, fwd)    # j receives x_{j-1}
    from_right = jax.lax.ppermute(x, axis_name, bwd)   # j receives x_{j+1}
    return self_w * x + left_w * from_left + right_w * from_right


def tree_ring_gossip(params: Pytree, axis_name: str, n: int,
                     self_w, left_w, right_w) -> Pytree:
    return jax.tree.map(
        lambda p: ring_gossip(p, axis_name, n, self_w.astype(p.dtype),
                              left_w.astype(p.dtype), right_w.astype(p.dtype)),
        params)


def graph_gossip(x: jax.Array, axis_name: str,
                 perms: Sequence[Sequence[Tuple[int, int]]],
                 weights: jax.Array, self_weight: jax.Array) -> jax.Array:
    """General static-topology gossip: one ppermute per neighbor-offset class.

    ``perms[e]`` is a full permutation (list of (src, dst)) delivering each
    worker its e-th neighbor's shard; ``weights[e]`` scales that contribution.
    Used for torus / multipod topologies where each worker has the same number
    of neighbor classes.
    """
    out = self_weight.astype(x.dtype) * x
    for e, perm in enumerate(perms):
        out = out + weights[e].astype(x.dtype) * jax.lax.ppermute(x, axis_name, perm)
    return out


def tree_graph_gossip(params: Pytree, axis_name: str, perms, weights, self_weight):
    return jax.tree.map(
        lambda p: graph_gossip(p, axis_name, perms, weights, self_weight), params)


# ---------------------------------------------------------------------------
# Convenience: build a jitted event-step for a given loss function
# ---------------------------------------------------------------------------

def build_event_step(loss_fn: Callable, use_kernel: bool = False):
    """Returns jit(step)(W, S, y, batches, P, grad_mask, restart_mask, eta).

    ``loss_fn(params, batch) -> scalar``; batches carry a leading worker axis.
    Gradients are evaluated at the snapshots S (staleness-correct, see
    core/scheduler.py docstring).
    """
    grad_fn = jax.grad(loss_fn)

    @jax.jit
    def step(W, S, y, batches, P, grad_mask, restart_mask, eta):
        with jax.named_scope("grad"):
            grads = jax.vmap(grad_fn)(S, batches)
        return masked_gossip_step(
            W, S, y, grads, P, grad_mask, restart_mask, eta, use_kernel=use_kernel)

    return step


# ---------------------------------------------------------------------------
# Block-compiled path: one lax.scan over a whole EventBatch
# ---------------------------------------------------------------------------

def select_pool_batch(pools: Pytree, ptr: jax.Array) -> Pytree:
    """Each worker's current batch from its pre-drawn sample pool.

    ``pools`` leaves have shape (n, pool, ...); worker i's batch is
    ``pool[i, ptr[i] mod pool]`` — the on-device replacement for the legacy
    runner's host-side ``_refresh_batches``.
    """
    def sel(pool):
        idx = ptr % pool.shape[1]
        pick = jax.vmap(
            lambda row, p: jax.lax.dynamic_index_in_dim(
                row, p, axis=0, keepdims=False))
        return pick(pool, idx)
    return jax.tree.map(sel, pools)


def _dense_grads(grad_fn: Callable, S: Pytree, pools: Pytree,
                 ptr: jax.Array) -> Pytree:
    """Every worker's gradient at its snapshot on its current pool batch."""
    with jax.named_scope("pool_select"):
        batches = select_pool_batch(pools, ptr)
    with jax.named_scope("grad"):
        return jax.vmap(grad_fn)(S, batches)


def masked_gossip_scan(
    W: Pytree,
    S: Pytree,
    y: jax.Array,
    ptr: jax.Array,
    pools: Pytree,
    grad_fn: Callable,
    P_seq: jax.Array,
    grad_masks: jax.Array,
    restart_masks: jax.Array,
    etas: jax.Array,
    use_kernel: bool = False,
) -> Tuple[Pytree, Pytree, jax.Array, jax.Array]:
    """Advance (W, S, y) through a whole EventBatch in one ``lax.scan``.

    P_seq: (E, n, n); grad_masks/restart_masks: (E, n); etas: (E,).
    ptr: (n,) int32 restart counters indexing each worker's sample pool;
    incremented wherever ``restart_masks`` fires (a restarted worker starts
    its next local computation on a fresh batch).  Identity-padded no-op
    events (P=I, masks all-False — see EventBatch.pad_to) leave the carry
    bit-exact, so fixed-size blocks are safe.

    Returns the updated ``(W, S, y, ptr)``.
    """
    def body(carry, ev):
        W, S, y, ptr = carry
        P, gm, rm, eta = ev
        grads = _dense_grads(grad_fn, S, pools, ptr)
        W, S, y = masked_gossip_step(
            W, S, y, grads, P, gm, rm, eta, use_kernel=use_kernel)
        ptr = ptr + rm.astype(ptr.dtype)
        return (W, S, y, ptr), None

    carry, _ = jax.lax.scan(
        body, (W, S, y, ptr), (P_seq, grad_masks, restart_masks, etas))
    return carry


def build_event_scan(loss_fn: Callable, use_kernel: bool = False,
                     telemetry: bool = False):
    """Returns jit(block)(W, S, y, ptr, pools, P_seq, gm_seq, rm_seq, etas).

    One compiled call advances the stacked state through E events — the
    block-compiled execution model (module docstring, mode 2).  Block length
    and pool size are baked into the trace, so keep them fixed across calls
    (the runner pads truncated blocks with no-op events).

    With ``telemetry`` the block additionally threads a
    :class:`~repro.obs.metrics.MetricsCarry` ``M`` (inserted after ``ptr``)
    and consumes per-event telemetry xs — ``ts`` (E,) f32 event clocks,
    ``fin`` (E, n) f32 raw completion clocks, ``ks`` (E,) i32 event
    indices, ``copies`` (E,) i32 — updating ``M`` once per scan step on
    device.  The ``(W, S, y, ptr)`` trajectory is bit-identical either
    way: the metrics update reads the state but never writes it.
    """
    grad_fn = jax.grad(loss_fn)

    if not telemetry:
        @jax.jit
        def block_dense(W, S, y, ptr, pools, P_seq, grad_masks,
                        restart_masks, etas):
            return masked_gossip_scan(
                W, S, y, ptr, pools, grad_fn, P_seq, grad_masks,
                restart_masks, etas, use_kernel=use_kernel)

        return block_dense

    @jax.jit
    def block_dense_tel(W, S, y, ptr, M, pools, P_seq, grad_masks,
                        restart_masks, etas, ts, fin, ks, copies):
        def body(carry, ev):
            W, S, y, ptr, M = carry
            P, gm, rm, eta, t, f, k, cp = ev
            grads = _dense_grads(grad_fn, S, pools, ptr)
            W, S, y = masked_gossip_step(
                W, S, y, grads, P, gm, rm, eta, use_kernel=use_kernel)
            ptr = ptr + rm.astype(ptr.dtype)
            with jax.named_scope("metrics_update"):
                M = dense_metrics_update(M, P, gm, rm, t, f, k, cp)
            return (W, S, y, ptr, M), None

        carry, _ = jax.lax.scan(
            body, (W, S, y, ptr, M),
            (P_seq, grad_masks, restart_masks, etas, ts, fin, ks, copies))
        return carry

    return block_dense_tel


# ---------------------------------------------------------------------------
# Sparse active-set path: gather → compute → scatter per event
# ---------------------------------------------------------------------------

def select_pool_batch_at(pools: Pytree, widx: jax.Array,
                         ptra: jax.Array) -> Pytree:
    """Active-set batches: lane a gets ``pool[widx[a], ptra[a] mod pool]``.

    The sparse sibling of :func:`select_pool_batch`: instead of every
    worker's current batch it gathers only the A active lanes' batches —
    pools stay untouched for the other n − A workers.

    Each floating batch is multiplied by :func:`pool_lane_factor`, which is
    exactly 1 on every lane, so the batch is the gathered values bit for
    bit.  Without it the TPU compiler, whose DEFAULT-precision matmuls read
    the batch as bf16, rewrites ``convert(gather(pool))`` as
    ``gather(convert(pool))`` and hoists the convert of the loop-invariant
    pool out of the scan: every block call would convert the whole
    ``(n, pool, B, d)`` pool to bf16 while its steps read only a few A-lane
    batches.  A factor the compiler cannot prove constant keeps the gather
    on the pool as stored and the cast inside each step's own matmuls
    (``tests/test_tpu_compile.py`` checks the compiled program).  The dense
    :func:`select_pool_batch` does without it: every dense step reads all n
    workers' batches, so one hoisted convert a call is cheaper than one of
    n batches a step.
    """
    def sel(pool):
        idx = ptra % pool.shape[1]
        batch = pool[widx, idx]
        if not jnp.issubdtype(batch.dtype, jnp.floating):
            return batch
        # × 1: without it the TPU compiler converts the whole pool to bf16
        # on every block call
        one = pool_lane_factor(idx).astype(batch.dtype)
        return batch * one.reshape((-1,) + (1,) * (batch.ndim - 1))
    return jax.tree.map(sel, pools)


def pool_lane_factor(idx: jax.Array) -> jax.Array:
    """``idx >= 0`` as a bool per lane: 1 for every pool index ``ptr mod pool``.

    jnp's ``%`` rounds towards −∞, so a positive pool length leaves every
    int32 ``ptr`` (negative ones included) an index in ``[0, pool)``; the
    compiler does not fold the comparison, which is all the factor is for
    (:func:`select_pool_batch_at`).
    """
    return idx >= 0


def sparse_gossip_scan(
    W: Pytree,
    S: Pytree,
    y: jax.Array,
    ptr: jax.Array,
    pools: Pytree,
    grad_fn: Callable,
    workers_seq: jax.Array,
    P_sub_seq: jax.Array,
    grad_masks: jax.Array,
    restart_masks: jax.Array,
    etas: jax.Array,
    use_kernel: bool = False,
) -> Tuple[Pytree, Pytree, jax.Array, jax.Array]:
    """Advance (W, S, y) through a :class:`SparseEventBatch` in one scan.

    The active-set execution of eq. (5): each scan step *gathers* the A
    active workers' snapshots, pool batches, and parameter rows, evaluates
    gradients **only for those lanes** (the ~n× vmap-grad cut for
    single-edge schedulers), mixes with the A×A consensus submatrix, and
    *scatters* the A updated rows back — every other worker's ``(W, S, y,
    ptr)`` row is never touched, read-modify-written only by the scatter's
    identity complement.

    workers_seq: (E, A) int32, ``-1``-padded (SparseEventBatch lanes);
    P_sub_seq: (E, A, A); grad_masks/restart_masks: (E, A) per-lane bools;
    etas: (E,) — one step size per event — or (E, A) per *lane* (merged
    block-diagonal rows, :func:`~repro.core.scheduler.merge_event_groups`,
    where one scan step replays K source events whose η-schedule positions
    differ).  Padded lanes carry zero P_sub rows/columns, so they gather
    row 0 harmlessly, contribute no mass, and their scatter index is mapped
    out of bounds (dropped).  Returns the updated ``(W, S, y, ptr)``.
    """
    if etas.ndim == 1:
        # broadcast to per-lane: the body's `eta * mask` product is then
        # elementwise either way, and one trace serves both calling forms
        etas = jnp.broadcast_to(etas[:, None], grad_masks.shape)

    def body(carry, ev):
        workers, P_sub, gm, rm, eta = ev

        def step(c):
            W, S, y, ptr = c
            return sparse_event_update(W, S, y, ptr, pools, grad_fn,
                                       workers, P_sub, gm, rm, eta,
                                       use_kernel=use_kernel)

        # Fixed-shape blocks arrive tail-padded with no-op rows (pad_to:
        # every lane -1; real rows always carry lane 0 — packing is
        # valid-first).  The whole gather-compute-scatter for a no-op row
        # is the identity, so skip it: the O(A²·D) mix of a padded step
        # would otherwise cost the same as a real event's, and short
        # same-bucket segments are mostly padding.
        return jax.lax.cond(workers[0] >= 0, step, lambda c: c, carry), None

    carry, _ = jax.lax.scan(
        body, (W, S, y, ptr),
        (workers_seq, P_sub_seq, grad_masks, restart_masks, etas))
    return carry


def sparse_event_update(
    W: Pytree,
    S: Pytree,
    y: jax.Array,
    ptr: jax.Array,
    pools: Pytree,
    grad_fn: Callable,
    workers: jax.Array,
    P_sub: jax.Array,
    gm: jax.Array,
    rm: jax.Array,
    eta: jax.Array,
    use_kernel: bool = False,
) -> Tuple[Pytree, Pytree, jax.Array, jax.Array]:
    """One active-set event against the stacked carry — the single scan step
    of :func:`sparse_gossip_scan`, factored out so the fused
    generate-and-consume scan (core/fused.py) applies the *identical*
    traced computation to events it materializes on device.

    workers: (A,) int32 ``-1``-padded; P_sub: (A, A); gm/rm: (A,) bools;
    eta: scalar or (A,) per-lane.  Returns the updated ``(W, S, y, ptr)``.
    """
    n = y.shape[0]

    def expand(mask, leaf):
        return mask.reshape((-1,) + (1,) * (leaf.ndim - 1)).astype(leaf.dtype)

    valid = workers >= 0
    gidx = jnp.where(valid, workers, 0)      # clamped gather index
    sidx = jnp.where(valid, workers, n)      # OOB ⇒ scatter drops the lane
    # -- gather ------------------------------------------------------
    with jax.named_scope("sparse_gather"):
        Sa = jax.tree.map(lambda s: s[gidx], S)
        ptra = ptr[gidx]
        ya = y[gidx]
        # the kernel gathers its W rows itself
        Wa = None if use_kernel else jax.tree.map(lambda w: w[gidx], W)
    with jax.named_scope("pool_select"):
        batches = select_pool_batch_at(pools, gidx, ptra)
    with jax.named_scope("grad"):
        grads = jax.vmap(grad_fn)(Sa, batches)   # A gradient lanes, not n
    scaled = eta * (gm & valid).astype(jnp.float32)
    # -- compute: P_subᵀ·(W_a − η·mask⊙G) ----------------------------
    with jax.named_scope("mix"):
        if use_kernel:
            from repro.kernels.sparse_gossip import ops as sparse_ops
            Wn = jax.tree.map(
                lambda w, g: sparse_ops.sparse_gossip_rows(
                    w, g, P_sub.astype(w.dtype), scaled.astype(w.dtype),
                    gidx),
                W, grads)
        else:
            vf = valid.astype(jnp.float32)
            Pm = P_sub * vf[:, None] * vf[None, :]

            def mix(wa, g):
                stepped = (wa - expand(scaled, wa) * g).reshape(
                    wa.shape[0], -1)
                out = jnp.einsum("ad,ab->bd", stepped, Pm.astype(wa.dtype),
                                 precision=jax.lax.Precision.HIGHEST)
                return out.reshape(wa.shape)

            Wn = jax.tree.map(mix, Wa, grads)
        ya = jnp.einsum("a,ab->b", ya, P_sub.astype(y.dtype))
    with jax.named_scope("s_update"):
        Sn = jax.tree.map(lambda s, w: jnp.where(expand(rm, w) > 0, w, s),
                          Sa, Wn)
    # -- scatter -----------------------------------------------------
    with jax.named_scope("sparse_scatter"):
        if use_kernel:
            # kernel scatter-into-carry: the (n, ...) parameter leaves are
            # updated through input/output aliasing (only the A active
            # windows are written) instead of XLA's fresh-buffer scatter;
            # the O(n) vector leaves (y, ptr) stay on the cheap XLA path.
            W = jax.tree.map(
                lambda w, rows: sparse_ops.sparse_scatter_rows(
                    w, rows.astype(w.dtype), workers),
                W, Wn)
            S = jax.tree.map(
                lambda s, rows: sparse_ops.sparse_scatter_rows(
                    s, rows.astype(s.dtype), workers),
                S, Sn)
        else:
            W = jax.tree.map(
                lambda w, rows: w.at[sidx].set(rows.astype(w.dtype),
                                               mode="drop"),
                W, Wn)
            S = jax.tree.map(
                lambda s, rows: s.at[sidx].set(rows.astype(s.dtype),
                                               mode="drop"),
                S, Sn)
        y = y.at[sidx].set(ya.astype(y.dtype), mode="drop")
        ptr = ptr.at[sidx].set(ptra + rm.astype(ptr.dtype), mode="drop")
    return W, S, y, ptr


def build_sparse_event_scan(loss_fn: Callable, use_kernel: bool = False,
                            telemetry: bool = False):
    """Returns jit(block)(W, S, y, ptr, pools, workers, P_sub, gm, rm, etas).

    One compiled call advances the stacked state through E active-set
    events (``SparseEventBatch`` arrays).  The lane width A and block length
    E are baked into the trace — fixed per scheduler *bucket*, so a handful
    of compiled programs (one per (A, E) shape the dispatcher emits) serves
    the whole stream.

    The ``(W, S, y, ptr)`` carry buffers are **donated**: the caller always
    threads the returned carry into the next block and never reuses the
    arguments (the runner's contract), so XLA reuses their n-row buffers
    in place instead of allocating a fresh copy per block — at N=1024 the
    W+S stack is ~0.7 GB of float32, twice per block without donation.

    With ``telemetry`` the block signature gains a
    :class:`~repro.obs.metrics.MetricsCarry` ``M`` after ``ptr`` (donated
    with the rest of the carry) and per-event xs — ``ts``/``fin`` (E, A)
    f32 per-lane event / raw-completion clocks, ``ks`` (E, A) i32 per-lane
    event indices (merged rows carry each member event's own clock and
    index), ``copies`` (E,) i32.  The state trajectory is bit-identical
    to the non-telemetry block's; padded no-op rows skip the metrics
    update along with the state update (same ``lax.cond``).
    """
    grad_fn = jax.grad(loss_fn)

    if not telemetry:
        @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
        def block_sparse(W, S, y, ptr, pools, workers_seq, P_sub_seq,
                         grad_masks, restart_masks, etas):
            return sparse_gossip_scan(
                W, S, y, ptr, pools, grad_fn, workers_seq, P_sub_seq,
                grad_masks, restart_masks, etas, use_kernel=use_kernel)

        return block_sparse

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4))
    def block_sparse_tel(W, S, y, ptr, M, pools, workers_seq, P_sub_seq,
                         grad_masks, restart_masks, etas, ts, fin, ks,
                         copies):
        if etas.ndim == 1:
            etas_seq = jnp.broadcast_to(etas[:, None], grad_masks.shape)
        else:
            etas_seq = etas

        def body(carry, ev):
            workers, P_sub, gm, rm, eta, t, f, k, cp = ev

            def step(c):
                W, S, y, ptr, M = c
                W, S, y, ptr = sparse_event_update(
                    W, S, y, ptr, pools, grad_fn, workers, P_sub, gm, rm,
                    eta, use_kernel=use_kernel)
                with jax.named_scope("metrics_update"):
                    M = sparse_metrics_update(M, workers, P_sub, gm, rm,
                                              t, f, k, cp)
                return W, S, y, ptr, M

            return jax.lax.cond(workers[0] >= 0, step, lambda c: c,
                                carry), None

        carry, _ = jax.lax.scan(
            body, (W, S, y, ptr, M),
            (workers_seq, P_sub_seq, grad_masks, restart_masks, etas_seq,
             ts, fin, ks, copies))
        return carry

    return block_sparse_tel
