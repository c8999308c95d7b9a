"""Seeded label-sharded Gaussian-mixture data, made on the device in one call.

The vectorised form of ``repro.data.ClassificationData``'s label-shard
protocol: ``n_classes`` Gaussian prototypes, each worker holding
``samples_per_worker`` samples of ``classes_per_worker`` of the classes, and
a held-out evaluation batch from the global mixture.  What the trainer needs
is each worker's pool of pre-drawn batches: ``pool_x[w, s]`` is the ``s``-th
batch worker ``w`` draws, sampled with replacement from its own samples.
The same key gives the same arrays, so the reference rebuilds them exactly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=(
    "n", "d", "n_classes", "classes_per_worker", "samples_per_worker",
    "pool", "batch", "n_eval", "noise"))
def _generate(key, *, n, d, n_classes, classes_per_worker, samples_per_worker,
              pool, batch, n_eval, noise):
    kp, kc, kl, kx, ki, kel, kex = jax.random.split(key, 7)
    protos = jax.random.normal(kp, (n_classes, d), jnp.float32)
    # each worker's classes: the first few of a random permutation of all
    classes = jnp.argsort(jax.random.uniform(kc, (n, n_classes)),
                          axis=1)[:, :classes_per_worker]
    pick = jax.random.randint(kl, (n, samples_per_worker), 0,
                              classes_per_worker)
    labels = jnp.take_along_axis(classes, pick, axis=1)
    x = protos[labels] + noise * jax.random.normal(
        kx, (n, samples_per_worker, d), jnp.float32)
    idx = jax.random.randint(ki, (n, pool, batch), 0, samples_per_worker)
    pool_x = jax.vmap(lambda xs, ii: xs[ii])(x, idx)
    pool_y = jax.vmap(lambda ys, ii: ys[ii])(labels, idx).astype(jnp.int32)
    ey = jax.random.randint(kel, (n_eval,), 0, n_classes)
    ex = protos[ey] + noise * jax.random.normal(kex, (n_eval, d), jnp.float32)
    return pool_x, pool_y, ex, ey.astype(jnp.int32)


def make_data(config, seed: int):
    """``(pool_x, pool_y, eval_batch)`` for a configuration and a data seed.

    pool_x: (n, batch_pool, batch_size, d_in) f32; pool_y: (n, batch_pool,
    batch_size) int32; eval_batch: {"x": (eval_batch, d_in), "y": ...}.
    """
    if config["partition"] != "label_shard":
        raise ValueError(f"unknown partition {config['partition']!r}")
    pool_x, pool_y, ex, ey = _generate(
        jax.random.PRNGKey(seed), n=config["n_workers"], d=config["d_in"],
        n_classes=config["n_classes"],
        classes_per_worker=config["classes_per_worker"],
        samples_per_worker=config["samples_per_worker"],
        pool=config["batch_pool"], batch=config["batch_size"],
        n_eval=config["eval_batch"], noise=float(config["noise"]))
    return pool_x, pool_y, {"x": ex, "y": ey}
