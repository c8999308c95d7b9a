"""Distributed step functions: decentralized train_step, serve_step, prefill_step.

``build_train_step`` produces the production DSGD-AAU update:

  1. per-worker forward/backward (remat-scanned layers, chunked CE) — workers
     stacked on the leading axis, vmapped; each worker sees its own non-iid
     batch shard (in_shardings place one worker per ``worker`` mesh slice);
  2. masked local SGD  W ← W − η·g  (paper eq. 4, plain SGD per worker);
  3. gossip mixing along the worker axis via ``lax.ppermute`` ring (+ an
     inter-pod edge on the multi-pod mesh) with step-dependent Metropolis
     weights streamed from the host scheduler — the paper's time-varying
     P(k) restricted to the physical ring/bridge topology.

Gossip weights are traced scalars, so the *same compiled step* serves every
AAU iteration: a zero weight deactivates an edge (the collective still moves
bytes — the dry-run therefore reports worst-case gossip traffic).
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.launch.mesh import TrainAxes
from repro.models.transformer import decode_step as _decode
from repro.models.transformer import init_model, lm_loss
from repro.models.transformer import prefill as _prefill


def stacked_init(cfg: ModelConfig, n_workers: int):
    """init fn for worker-stacked parameters (same init across workers)."""
    def init(key):
        p = init_model(key, cfg)
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n_workers,) + x.shape), p)
    return init


def gossip_weights_spec():
    """Abstract gossip weights: (self, left, right, pod_gamma) f32 scalars."""
    s = jax.ShapeDtypeStruct((), jnp.float32)
    return {"self": s, "left": s, "right": s, "pod": s}


def default_gossip_weights(n_workers_per_pod: int, multi_pod: bool):
    if n_workers_per_pod >= 3:
        w = {"self": 1 / 3, "left": 1 / 3, "right": 1 / 3}
    elif n_workers_per_pod == 2:
        w = {"self": 0.5, "left": 0.25, "right": 0.25}
    else:
        w = {"self": 1.0, "left": 0.0, "right": 0.0}
    w["pod"] = 0.25 if multi_pod else 0.0
    return {k: jnp.float32(v) for k, v in w.items()}


def _tree_gossip(W, axes: TrainAxes, w_per_pod: int, weights):
    """Ring gossip over the worker axis + optional inter-pod edge.

    Runs under shard_map: leaves are local blocks with worker-axis size
    w_per_pod / mesh_size (=1 when fully sharded); ppermute moves whole
    blocks.  Mixing is linear and elementwise over parameters, so it commutes
    with the fsdp/model shardings of the replica (DESIGN.md §4).
    """
    n = w_per_pod
    fwd = [(i, (i + 1) % n) for i in range(n)]
    bwd = [((i + 1) % n, i) for i in range(n)]

    # Doubly stochastic composition: out = (1−γ)·ring_mix + γ·other_pod_same_idx
    def mix2(x):
        dt = x.dtype
        ring = weights["self"].astype(dt) * x
        if n > 1:
            ring = ring + weights["left"].astype(dt) * jax.lax.ppermute(
                x, axes.worker, fwd)
            ring = ring + weights["right"].astype(dt) * jax.lax.ppermute(
                x, axes.worker, bwd)
        if axes.pod is not None:
            other = jax.lax.ppermute(x, axes.pod, [(0, 1), (1, 0)])
            g = weights["pod"].astype(dt)
            ring = (1 - g) * ring + g * other
        return ring

    return jax.tree.map(mix2, W)


def build_train_step(cfg: ModelConfig, n_workers: int, axes: TrainAxes,
                     mesh, param_specs, *, microbatch: int = 1,
                     logit_chunk: int = 512, remat: bool = True) -> Callable:
    """Returns train_step(W, batch, eta, gossip_weights) -> (W, loss)."""
    w_per_pod = n_workers // (2 if axes.pod else 1)

    def worker_loss(params, tokens, prefix):
        b = {"tokens": tokens}
        if prefix is not None:
            b["prefix"] = prefix
        return lm_loss(params, cfg, b, remat=remat, logit_chunk=logit_chunk)

    def worker_grad(params, tokens, prefix):
        if microbatch > 1:
            tb = tokens.reshape(microbatch, -1, tokens.shape[-1])
            pb = (prefix.reshape((microbatch, -1) + prefix.shape[1:])
                  if prefix is not None else None)

            def mb_body(carry, i):
                tot, acc = carry
                pf = pb[i] if pb is not None else None
                l, g = jax.value_and_grad(worker_loss)(params, tb[i], pf)
                acc = jax.tree.map(
                    lambda a, gi: a + gi.astype(jnp.float32), acc, g)
                return (tot + l, acc), None

            acc0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (tot, acc), _ = jax.lax.scan(
                mb_body, (jnp.float32(0), acc0), jnp.arange(microbatch))
            g = jax.tree.map(lambda a, p: (a / microbatch).astype(p.dtype),
                             acc, params)
            return tot / microbatch, g
        l, g = jax.value_and_grad(worker_loss)(params, tokens, prefix)
        return l, g

    gossip_sm = jax.shard_map(
        lambda W, wt: _tree_gossip(W, axes, w_per_pod, wt),
        mesh=mesh, in_specs=(param_specs, P()), out_specs=param_specs,
        check_vma=False)

    def train_step(W, batch, eta, gossip_w):
        tokens = batch["tokens"]
        prefix = batch.get("prefix")
        if prefix is not None:
            losses, grads = jax.vmap(worker_grad)(W, tokens, prefix)
        else:
            losses, grads = jax.vmap(
                lambda p, t: worker_grad(p, t, None))(W, tokens)
        W = jax.tree.map(
            lambda w, g: (w - eta.astype(jnp.float32)
                          * g.astype(jnp.float32)).astype(w.dtype), W, grads)
        W = gossip_sm(W, gossip_w)
        return W, jnp.mean(losses)

    return train_step


def build_serve_step(cfg: ModelConfig) -> Callable:
    """serve_step(params, token, state, pos) -> (logits, new_state)."""
    def serve_step(params, token, state, pos):
        return _decode(params, cfg, token, state, pos)
    return serve_step


def build_prefill_step(cfg: ModelConfig, cache_len: int) -> Callable:
    """prefill_step(params, batch) -> (last logits, decode state)."""
    def prefill_step(params, batch):
        return _prefill(params, cfg, batch["tokens"], cache_len,
                        prefix_embeds=batch.get("prefix"))
    return prefill_step
