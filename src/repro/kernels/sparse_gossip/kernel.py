"""Pallas TPU kernel: active-set gossip mixing via gather → mix → (scatter).

The sparse counterpart of ``gossip_mix``: an asynchronous event touches only
the ``A`` workers named by its active-edge list (AD-PSGD/AGP touch 2 of N;
DSGD-AAU a finished subset), and every consensus matrix the schedulers emit
is identity outside that set.  Mixing therefore only needs the A×A submatrix
``P_sub`` and the A gathered worker rows — O(A²·D) work instead of the dense
kernel's O(N²·D), the factor that makes paper-scale N=256 streams cheap.

``sparse_gossip_pallas`` computes the *compact* mixed rows

    out[b] = Σ_a P_sub[a, b] · W[workers[a]]  −  Σ_a Q_sub[a, b] · G[a]

with the gather fused into the kernel: ``workers`` is a scalar-prefetch
operand (``pltpu.PrefetchScalarGridSpec``), so the BlockSpec index map DMAs
exactly the A active rows of W out of HBM — inactive rows are never read.
As with the dense ``masked_gossip`` kernel, Q = diag(η·grad_mask)·P_sub
folds the gradient step into the same pass: out = P_subᵀ·(W_a − η·mask⊙G).

Grid layout: ``(D // block_d, A)`` with the active-row axis innermost.  The
(A, block_d) output tile has a constant index over the inner axis, so it
stays VMEM-resident while each step accumulates one gathered row's
rank-1 contribution (P_sub[a, :] ⊗ W[workers[a]] tile).  P_sub/Q_sub stay
resident across the whole grid.

The *scatter* half of the gather-compute-scatter contract deliberately stays
outside the kernel (ops.py ``sparse_gossip_apply``): writing updated rows
back into a W-aliased output would race the gather reads of later grid steps
(every output row is also an input row of the mix), so ops scatters the
compact result with a deterministic ``.at[workers].set(..., mode="drop")``.

Padding contract (ops.py enforces it): padded lanes carry ``workers`` index 0
(any valid row — its contribution is annihilated) and all-zero P_sub/Q_sub
rows *and* columns, so they neither contribute to nor receive mass; their
compact output rows are exactly zero and the scatter drops them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _sparse_gossip_kernel(workers_ref, pt_ref, qt_ref, w_ref, g_ref, o_ref):
    # workers_ref: (A,) scalar-prefetch (consumed by the index maps);
    # pt_ref/qt_ref: (A, A) resident P_subᵀ/Q_subᵀ; w_ref: (1, Dt) gathered
    # row W[workers[a]]; g_ref: (1, Dt) compact gradient row a; o_ref:
    # (A, Dt) resident tile.
    del workers_ref
    a = pl.program_id(1)

    @pl.when(a == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # Row a of P_sub as an (A, 1) column: column a of P_subᵀ, picked by a
    # lane mask (a one-term sum, so exact).  A dynamic row index into the
    # ref would need a sublane offset the compiler cannot prove aligned.
    lane = jax.lax.broadcasted_iota(jnp.int32, pt_ref.shape, 1)

    def column(ref):
        return jnp.sum(jnp.where(lane == a, ref[...], 0), axis=1,
                       keepdims=True)

    contrib = column(pt_ref) * w_ref[...] - column(qt_ref) * g_ref[...]
    o_ref[...] += contrib.astype(o_ref.dtype)


def sparse_gossip_pallas(W: jax.Array, G: jax.Array, P_sub: jax.Array,
                         Q_sub: jax.Array, workers: jax.Array, *,
                         block_d: int = 512,
                         interpret: bool = False) -> jax.Array:
    """Compact active-set mix: out = P_subᵀ·W[workers] − Q_subᵀ·G.

    W: (N, D) full worker-stacked state (only ``workers`` rows are read);
    G: (A, D) active-set gradients; P_sub/Q_sub: (A, A); workers: (A,) int32
    row indices in [0, N).  Returns the (A, D) mixed active rows.
    """
    N, D = W.shape
    A = workers.shape[0]
    assert G.shape == (A, D), (G.shape, (A, D))
    assert P_sub.shape == (A, A) and Q_sub.shape == (A, A), (
        P_sub.shape, Q_sub.shape)
    assert D % block_d == 0, (D, block_d)
    grid = (D // block_d, A)
    # Rows are gathered through (rows, 1, D) views whose row axis is squeezed
    # out of the block: a TPU block must tile its last two dims by (8, 128)
    # or span them, and a (1, block_d) window of the (N, D) stack does
    # neither, while a (1, block_d) window of (N, 1, D) spans its dim 1.
    row_block = (pl.squeezed, 1, block_d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((A, A), lambda d, a, workers: (0, 0)),  # Pᵀ resident
            pl.BlockSpec((A, A), lambda d, a, workers: (0, 0)),  # Qᵀ resident
            # the gather: row a of the active set comes from W[workers[a]]
            pl.BlockSpec(row_block, lambda d, a, workers: (workers[a], 0, d)),
            pl.BlockSpec(row_block, lambda d, a, workers: (a, 0, d)),
        ],
        out_specs=pl.BlockSpec((A, block_d), lambda d, a, workers: (0, d)),
    )
    return pl.pallas_call(
        _sparse_gossip_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((A, D), W.dtype),
        interpret=interpret,
    )(workers, P_sub.T, Q_sub.T, W.reshape(N, 1, D), G.reshape(A, 1, D))


def _scatter_rows_kernel(workers_ref, owner0_ref, rows_ref, x_ref, o_ref):
    # workers_ref: (A,) scalar-prefetch; owner0_ref: (1,) scalar-prefetch,
    # the lane that carries worker 0 or -1 when none does; x_ref / o_ref:
    # the same (1, Dt) window of the aliased carry at row max(workers[a], 0);
    # rows_ref: the compact row of lane a for valid lanes, of *worker 0's
    # lane* for padded lanes (see the index map).  A valid lane replaces its
    # window with its compact row.  A padded lane (workers[a] < 0, clamped
    # to row 0) must write row 0's *final* content back: that is the owning
    # lane's compact row when some valid lane carries worker 0 — wherever
    # that lane sits (merged block-diagonal rows interleave pads, so it
    # need not be lane 0) — else the gathered window.  Deciding from the
    # workers array rather than re-reading the carry keeps the kernel
    # correct whether the x gather observes the aliased buffer's updates
    # (TPU read-through) or a stale pre-kernel copy (interpret mode).  Both
    # decisions read scalars only: TPU kernels load SMEM one scalar at a time.
    a = pl.program_id(1)
    keep_rows = (workers_ref[a] >= 0) | (owner0_ref[0] >= 0)

    @pl.when(keep_rows)
    def _write_rows():
        o_ref[...] = rows_ref[...].astype(o_ref.dtype)

    @pl.when(jnp.logical_not(keep_rows))
    def _write_back():
        o_ref[...] = x_ref[...].astype(o_ref.dtype)


def scatter_rows_pallas(X: jax.Array, rows: jax.Array, workers: jax.Array, *,
                        block_d: int = 512,
                        interpret: bool = False) -> jax.Array:
    """Scatter compact active-set rows into the carry, in place.

    The scatter half of the gather-compute-scatter contract, moved into the
    kernel: ``X`` (N, D) is **aliased to the output** (donated by the
    caller), so only the A windows named by ``workers`` are ever written —
    the other N−A rows are never touched, never copied, never DMA'd.  That
    replaces the XLA ``.at[workers].set``, whose lowering materializes a
    fresh (N, D) buffer per event — O(N·D) carry traffic for an O(A·D)
    logical update, the term that grows linearly with n and capped the
    sparse path's scaling (see BENCH_event_stream.json N≥128).

    Race-freedom: valid active-set indices are unique per event (disjoint
    across the blocks of a merged row), so the only repeated output window
    is the padded lanes' row-0 writes — and the kernel makes each of those
    re-write row 0's final content (see ``_scatter_rows_kernel``), so
    repetition is idempotent regardless of where pads sit in the lane axis
    (``merge_event_groups`` interleaves them between blocks).

    rows: (A, D) compact rows; workers: (A,) int32 with ``-1`` padding in
    any position.  Returns the updated (N, D) carry (the same buffer when
    donation applies).
    """
    N, D = X.shape
    A = workers.shape[0]
    assert rows.shape == (A, D), (rows.shape, (A, D))
    assert D % block_d == 0, (D, block_d)
    # worker 0's lane (or -1), worked out here so the kernel and its index
    # maps read one scalar instead of a vector reduction over the prefetch
    is0 = workers == 0
    owner0 = jnp.where(jnp.any(is0), jnp.argmax(is0), -1).astype(
        jnp.int32).reshape(1)
    # (rows, 1, D) views with a squeezed row axis: see sparse_gossip_pallas
    X3 = X.reshape(N, 1, D)
    row_block = (pl.squeezed, 1, block_d)
    grid = (D // block_d, A)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            # padded lanes read worker 0's owning lane (the row-0 writeback
            # candidate; lane 0 when no lane carries worker 0, and the
            # kernel then keeps the gathered window instead)
            pl.BlockSpec(row_block,
                         lambda d, a, workers, owner0: (jnp.where(
                             workers[a] >= 0, a,
                             jnp.maximum(owner0[0], 0)), 0, d)),
            pl.BlockSpec(row_block,
                         lambda d, a, workers, owner0: (
                             jnp.maximum(workers[a], 0), 0, d)),
        ],
        out_specs=pl.BlockSpec(
            row_block,
            lambda d, a, workers, owner0: (jnp.maximum(workers[a], 0), 0, d)),
    )
    out = pl.pallas_call(
        _scatter_rows_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(X3.shape, X3.dtype),
        # operand indices count the scalar-prefetch args:
        # (workers, owner0, rows, X)
        input_output_aliases={3: 0},
        interpret=interpret,
    )(workers, owner0, rows.reshape(A, 1, D), X3)
    return out.reshape(N, D)
