"""Jitted wrappers for the gossip_mix kernels: shape guards, padding, CPU
interpret fallback.

Handles arbitrary leaf shapes by flattening to (N, D), padding D up to the
lane-aligned tile and N up to the sublane boundary (padding P with identity
rows so padded workers mix with nobody).  ``masked_gossip_mix`` additionally
folds the per-event learning-rate/gradient mask into a second resident matrix
Q = diag(η·mask)·P so the scan body's whole event update is one kernel call.
Every wrapper sizes the kernel's scoped VMEM from N and the tile width
(``_vmem_limit``): the resident (N, N) matrices outgrow the TPU's default
limit near N=1024.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.gossip_mix.kernel import (gossip_mix_batched_pallas,
                                             gossip_mix_pallas,
                                             masked_gossip_pallas)

_SUBLANE = 8
# v5e's default scoped-VMEM limit, and the most a kernel here may ask for of
# the 128 MiB of VMEM a v5e TensorCore has.
_VMEM_DEFAULT = 16 << 20
_VMEM_CAP = 100 << 20


def _vmem_limit(Np: int, block_d: int, itemsize: int, n_square: int,
                n_tiles: int) -> int:
    """Scoped-VMEM bytes for a gossip kernel over (Np, block_d) tiles.

    Pallas double-buffers every block: ``n_square`` resident (Np, Np)
    matrices and ``n_tiles`` (Np, block_d) input/output tiles, plus one f32
    matmul result per resident matrix.  A quarter of headroom on top; never
    below the default limit, never above the cap.
    """
    need = (2 * itemsize * (n_square * Np * Np + n_tiles * Np * block_d)
            + 4 * n_square * Np * block_d)
    return min(max(_VMEM_DEFAULT, need + need // 4), _VMEM_CAP)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _pad_P_identity(P: jax.Array, N: int, Np: int) -> jax.Array:
    """Pad P to (Np, Np) with identity rows: padded workers mix with nobody."""
    P = jnp.pad(P, ((0, Np - N), (0, Np - N)))
    return P.at[jnp.arange(N, Np), jnp.arange(N, Np)].set(1.0)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def gossip_mix(W: jax.Array, P: jax.Array, *, block_d: int = 512,
               interpret: bool | None = None) -> jax.Array:
    """Mix worker-stacked parameters: out = Pᵀ·W for any W of shape (N, ...)."""
    if interpret is None:
        interpret = not _on_tpu()
    N = W.shape[0]
    orig_shape = W.shape
    flat = W.reshape(N, -1)
    D = flat.shape[1]
    Dp = _pad_up(D, block_d)
    Np = _pad_up(N, _SUBLANE)
    if Dp != D:
        flat = jnp.pad(flat, ((0, 0), (0, Dp - D)))
    if Np != N:
        flat = jnp.pad(flat, ((0, Np - N), (0, 0)))
        P = _pad_P_identity(P, N, Np)
    with jax.named_scope("gossip_mix"):
        out = gossip_mix_pallas(
            flat, P.astype(flat.dtype), block_d=block_d,
            vmem_limit_bytes=_vmem_limit(Np, block_d, flat.dtype.itemsize,
                                         n_square=1, n_tiles=2),
            interpret=interpret)
    return out[:N, :D].reshape(orig_shape)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def masked_gossip_mix(W: jax.Array, G: jax.Array, P: jax.Array,
                      scaled_mask: jax.Array, *, block_d: int = 512,
                      interpret: bool | None = None) -> jax.Array:
    """Fused event update: out = Pᵀ·(W − diag(scaled_mask)·G), any (N, ...) W.

    ``scaled_mask`` is η·grad_mask (length N); padded workers get zero mask
    and identity mixing, so padding never leaks into real rows.
    """
    if interpret is None:
        interpret = not _on_tpu()
    N = W.shape[0]
    orig_shape = W.shape
    flat_w = W.reshape(N, -1)
    flat_g = G.reshape(N, -1).astype(flat_w.dtype)
    D = flat_w.shape[1]
    Dp = _pad_up(D, block_d)
    Np = _pad_up(N, _SUBLANE)
    if Dp != D:
        flat_w = jnp.pad(flat_w, ((0, 0), (0, Dp - D)))
        flat_g = jnp.pad(flat_g, ((0, 0), (0, Dp - D)))
    if Np != N:
        flat_w = jnp.pad(flat_w, ((0, Np - N), (0, 0)))
        flat_g = jnp.pad(flat_g, ((0, Np - N), (0, 0)))
        P = _pad_P_identity(P, N, Np)
        scaled_mask = jnp.pad(scaled_mask, (0, Np - N))
    P = P.astype(flat_w.dtype)
    Q = scaled_mask.astype(flat_w.dtype)[:, None] * P
    with jax.named_scope("masked_gossip_mix"):
        out = masked_gossip_pallas(
            flat_w, flat_g, P, Q, block_d=block_d,
            vmem_limit_bytes=_vmem_limit(Np, block_d, flat_w.dtype.itemsize,
                                         n_square=2, n_tiles=3),
            interpret=interpret)
    return out[:N, :D].reshape(orig_shape)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def gossip_mix_batched(W: jax.Array, P: jax.Array, *, block_d: int = 512,
                       interpret: bool | None = None) -> jax.Array:
    """Stacked mixing problems: out[e] = P[e]ᵀ·W[e] for W of shape (E, N, ...)."""
    if interpret is None:
        interpret = not _on_tpu()
    E, N = W.shape[:2]
    orig_shape = W.shape
    flat = W.reshape(E, N, -1)
    D = flat.shape[2]
    Dp = _pad_up(D, block_d)
    Np = _pad_up(N, _SUBLANE)
    if Dp != D:
        flat = jnp.pad(flat, ((0, 0), (0, 0), (0, Dp - D)))
    if Np != N:
        flat = jnp.pad(flat, ((0, 0), (0, Np - N), (0, 0)))
        P = jnp.pad(P, ((0, 0), (0, Np - N), (0, Np - N)))
        P = P.at[:, jnp.arange(N, Np), jnp.arange(N, Np)].set(1.0)
    with jax.named_scope("gossip_mix_batched"):
        out = gossip_mix_batched_pallas(
            flat, P.astype(flat.dtype), block_d=block_d,
            vmem_limit_bytes=_vmem_limit(Np, block_d, flat.dtype.itemsize,
                                         n_square=1, n_tiles=2),
            interpret=interpret)
    return out[:, :N, :D].reshape(orig_shape)
