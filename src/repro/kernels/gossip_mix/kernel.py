"""Pallas TPU kernels: consensus gossip mixing  out = Pᵀ · W and variants.

The hot step of eq. (5): every worker's new parameters are a P-weighted
combination of all workers' parameters.  W is (N, D) with N = #workers (small,
≤ 128) and D = flattened parameter dimension (huge).  The kernel tiles D into
VMEM-resident blocks; the (N, N) consensus matrix stays resident across the
whole grid.  Each grid step issues one (N×N)·(N×Dt) MXU matmul — N is padded
to the 8-sublane boundary and Dt is a multiple of 128 lanes (ops.py pads).

VMEM budget per step: double-buffered (N×N) matrices and (N×Dt) tiles plus
the f32 matmul results — e.g. N=128, Dt=512 → about 1.5 MB, but N=1024 needs
about 32 MB, over the default scoped-VMEM limit; ops.py sizes the limit
(``vmem_limit_bytes``) from N and Dt for every entry point.

Three entry points share that tiling scheme:

- ``gossip_mix_pallas``:        out = Pᵀ·W                  (plain mixing)
- ``masked_gossip_pallas``:     out = Pᵀ·W − Qᵀ·G           (fused event step)
- ``gossip_mix_batched_pallas``: out[e] = P[e]ᵀ·W[e]        (stacked problems)

The masked form is the whole gradient-then-mix event update in one pass:
with Q = diag(η·grad_mask)·P it equals Pᵀ·(W − η·mask⊙G) without ever
materializing the masked-gradient intermediate — this is what the
``masked_gossip_scan`` block trainer (core/aau.py) runs per scan step.  The
batched form adds a leading grid axis over E independent (P, W) problems;
both preserve the resident-P / D-tiled MXU layout above.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Pᵀ·W contraction of every kernel below.
_CONTRACT = (((0,), (0,)), ((), ()))


def _precision(dtype):
    """HIGHEST for f32 operands: f32 mixing stays f32 on the MXU, as in the
    XLA reference einsums, so the weights are not rounded to bf16 and P's
    column sums stay 1.  bf16 operands take Mosaic's only bf16 precision."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _gossip_kernel(p_ref, w_ref, o_ref):
    # p_ref: (N, N) consensus matrix; w_ref: (N, Dt) tile; o_ref: (N, Dt)
    p = p_ref[...]
    w = w_ref[...]
    o_ref[...] = jax.lax.dot_general(
        p, w, dimension_numbers=_CONTRACT, precision=_precision(p.dtype),
        preferred_element_type=jnp.float32,
    ).astype(o_ref.dtype)


def gossip_mix_pallas(W: jax.Array, P: jax.Array, *, block_d: int = 512,
                      vmem_limit_bytes: int,
                      interpret: bool = False) -> jax.Array:
    """W: (N, D) worker-stacked parameters; P: (N, N). D % block_d == 0."""
    N, D = W.shape
    assert P.shape == (N, N), (P.shape, N)
    assert D % block_d == 0, (D, block_d)
    grid = (D // block_d,)
    return pl.pallas_call(
        _gossip_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((N, N), lambda d: (0, 0)),        # P resident
            pl.BlockSpec((N, block_d), lambda d: (0, d)),  # W tile
        ],
        out_specs=pl.BlockSpec((N, block_d), lambda d: (0, d)),
        out_shape=jax.ShapeDtypeStruct((N, D), W.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
    )(P, W)


def _masked_gossip_kernel(p_ref, q_ref, w_ref, g_ref, o_ref):
    # p_ref/q_ref: (N, N) resident; w_ref/g_ref: (N, Dt) tiles.
    # out = Pᵀ·W − Qᵀ·G, two MXU matmuls per tile.
    precision = _precision(p_ref.dtype)
    mix = jax.lax.dot_general(p_ref[...], w_ref[...],
                              dimension_numbers=_CONTRACT, precision=precision,
                              preferred_element_type=jnp.float32)
    step = jax.lax.dot_general(q_ref[...], g_ref[...],
                               dimension_numbers=_CONTRACT, precision=precision,
                               preferred_element_type=jnp.float32)
    o_ref[...] = (mix - step).astype(o_ref.dtype)


def masked_gossip_pallas(W: jax.Array, G: jax.Array, P: jax.Array,
                         Q: jax.Array, *, block_d: int = 512,
                         vmem_limit_bytes: int,
                         interpret: bool = False) -> jax.Array:
    """Fused event step: Pᵀ·W − Qᵀ·G with Q = diag(η·mask)·P (see ops.py)."""
    N, D = W.shape
    assert G.shape == (N, D), (G.shape, W.shape)
    assert P.shape == (N, N) and Q.shape == (N, N), (P.shape, Q.shape)
    assert D % block_d == 0, (D, block_d)
    grid = (D // block_d,)
    return pl.pallas_call(
        _masked_gossip_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((N, N), lambda d: (0, 0)),        # P resident
            pl.BlockSpec((N, N), lambda d: (0, 0)),        # Q resident
            pl.BlockSpec((N, block_d), lambda d: (0, d)),  # W tile
            pl.BlockSpec((N, block_d), lambda d: (0, d)),  # G tile
        ],
        out_specs=pl.BlockSpec((N, block_d), lambda d: (0, d)),
        out_shape=jax.ShapeDtypeStruct((N, D), W.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
    )(P, Q, W, G)


def _gossip_batched_kernel(p_ref, w_ref, o_ref):
    # p_ref: (1, N, N); w_ref: (1, N, Dt) — one event's problem per grid row.
    o_ref[0] = jax.lax.dot_general(
        p_ref[0], w_ref[0], dimension_numbers=_CONTRACT,
        precision=_precision(p_ref.dtype),
        preferred_element_type=jnp.float32,
    ).astype(o_ref.dtype)


def gossip_mix_batched_pallas(W: jax.Array, P: jax.Array, *, block_d: int = 512,
                              vmem_limit_bytes: int,
                              interpret: bool = False) -> jax.Array:
    """W: (E, N, D) stacked problems; P: (E, N, N).  out[e] = P[e]ᵀ·W[e]."""
    E, N, D = W.shape
    assert P.shape == (E, N, N), (P.shape, W.shape)
    assert D % block_d == 0, (D, block_d)
    grid = (E, D // block_d)
    return pl.pallas_call(
        _gossip_batched_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, N, N), lambda e, d: (e, 0, 0)),
            pl.BlockSpec((1, N, block_d), lambda e, d: (e, 0, d)),
        ],
        out_specs=pl.BlockSpec((1, N, block_d), lambda e, d: (e, 0, d)),
        out_shape=jax.ShapeDtypeStruct((E, N, D), W.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
    )(P, W)
