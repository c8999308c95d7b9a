"""The paper's 2-NN (arXiv 2306.06559, Table 3): d_in -> 256 -> 256 -> 10.

Init, loss and eval adapters that the trainer is given, and the model's
operation counts worked out from its shapes.  The same functions serve the
plain reference, which evaluates them under HIGHEST matmul precision.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _check(config):
    if config["n_hidden_layers"] != 2:
        raise ValueError("mlp2nn is the 2-NN: n_hidden_layers must be 2")


def make_init(config):
    """``init(key) -> params`` with 1/sqrt(fan_in) normal weights, zero biases."""
    _check(config)
    d_in, d_h, n_cls = config["d_in"], config["d_hidden"], config["n_classes"]

    def init(key):
        ks = jax.random.split(key, 3)

        def w(k, a, b):
            return jax.random.normal(k, (a, b)) / np.sqrt(a)

        return {"w1": w(ks[0], d_in, d_h), "b1": jnp.zeros(d_h),
                "w2": w(ks[1], d_h, d_h), "b2": jnp.zeros(d_h),
                "w3": w(ks[2], d_h, n_cls), "b3": jnp.zeros(n_cls)}

    return init


def _logits(params, x):
    h = jax.nn.relu(x @ params["w1"] + params["b1"])
    h = jax.nn.relu(h @ params["w2"] + params["b2"])
    return h @ params["w3"] + params["b3"]


def loss(params, batch):
    """Mean cross-entropy of the batch."""
    logp = jax.nn.log_softmax(_logits(params, batch["x"]))
    return -jnp.mean(jnp.take_along_axis(logp, batch["y"][:, None], axis=1))


def evaluate(params, batch):
    """(loss, accuracy) on a held-out batch."""
    acc = jnp.mean((jnp.argmax(_logits(params, batch["x"]), -1)
                    == batch["y"]).astype(jnp.float32))
    return loss(params, batch), acc


def param_count(config) -> int:
    d_in, d_h, n_cls = config["d_in"], config["d_hidden"], config["n_classes"]
    return d_in * d_h + d_h + d_h * d_h + d_h + d_h * n_cls + n_cls


def grad_flops(config) -> float:
    """Matmul FLOPs of one gradient lane: forward and backward of one batch.

    Forward: 2·B·(d_in·h + h·h + h·c).  Backward: the weight gradients of
    all three layers (the same again) and the input gradients of layers 2
    and 3 (2·B·(h·h + h·c)); layer 1's input is data and needs none.
    Elementwise work (bias, relu, softmax) is left out.
    """
    d_in, h, c = config["d_in"], config["d_hidden"], config["n_classes"]
    B = config["batch_size"]
    fwd = 2 * B * (d_in * h + h * h + h * c)
    return float(2 * fwd + 2 * B * (h * h + h * c))


def batch_bytes(config, itemsize: int) -> float:
    """Bytes of one pool batch: B rows of d_in features and one int32 label."""
    return float(config["batch_size"] * (config["d_in"] * itemsize + 4))
