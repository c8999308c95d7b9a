"""ResNet-18 in its CIFAR-10 form (``ResNet18`` of github.com/kuangliu/
pytorch-cifar, ``models/resnet.py``, at the widths of He et al., arXiv
1512.03385; trained by DSGD-AAU in arXiv 2306.06559 §6).

A 3×3 stem of stride 1 with no max-pool, then stages of BasicBlocks (two
3×3 convolutions, each followed by a norm, with the input added back
before the second ReLU) at ``widths``; every stage after the first opens
with a stride-2 block whose shortcut is a 1×1 projection with its own norm.
Global average pooling and a dense layer to ``n_classes`` close it.  At
widths 64/128/256/512 and two blocks a stage that is 11,173,962 parameters
in 62 leaves.  NHWC, float32, ``lax.conv_general_dilated``; the trainer
and the plain reference (under HIGHEST precision) call the same functions.

Departure from the published model: the norm is BatchNorm over the batch's
own statistics, per gradient lane, with no running averages, because the
trainer's state is the parameter tree alone; ``evaluate`` normalizes with
the evaluation batch's statistics.

Operation counts follow XLA's ``cost_analysis``: a convolution counts
2·B·C_in·C_out for each pair of output position and kernel tap that reads
the image, so the zero taps of the padding are left out.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-5
_DIMS = ("NHWC", "HWIO", "NHWC")


def _layout(config):
    """[(stage, block, c_in, c_out, stride)] of the BasicBlocks."""
    h, w, c = config["image"]
    if config["d_in"] != h * w * c:
        raise ValueError(f"resnet18: d_in {config['d_in']} is not the "
                         f"product of image {config['image']}")
    widths, blocks = config["widths"], config["blocks_per_stage"]
    if len(widths) != len(blocks):
        raise ValueError("resnet18: widths and blocks_per_stage differ in "
                         "length")
    out, c_in = [], widths[0]
    for s, (width, n_blocks) in enumerate(zip(widths, blocks)):
        for b in range(n_blocks):
            out.append((s, b, c_in, width, 2 if s and not b else 1))
            c_in = width
    return out


def _norm_params(c):
    return {"scale": jnp.ones(c), "bias": jnp.zeros(c)}


def make_init(config):
    """``init(key) -> params``: He-normal convolutions with no bias, norm
    scale 1 and bias 0, the dense layer at 1/sqrt(fan_in) with zero bias."""
    layout = _layout(config)
    c_img, width0 = config["image"][2], config["widths"][0]
    c_last, n_cls = config["widths"][-1], config["n_classes"]

    def init(key):
        keys = iter(jax.random.split(key, 2 + 3 * len(layout)))

        def conv(k, c_in, c_out):
            shape = (k, k, c_in, c_out)
            return (jax.random.normal(next(keys), shape)
                    * np.sqrt(2.0 / (k * k * c_in)))

        stages = [[] for _ in config["widths"]]
        for s, _, c_in, c_out, stride in layout:
            block = {"conv1": conv(3, c_in, c_out), "norm1": _norm_params(c_out),
                     "conv2": conv(3, c_out, c_out),
                     "norm2": _norm_params(c_out)}
            if stride != 1 or c_in != c_out:
                block["proj"] = conv(1, c_in, c_out)
                block["proj_norm"] = _norm_params(c_out)
            stages[s].append(block)
        return {"stem": {"conv": conv(3, c_img, width0),
                         "norm": _norm_params(width0)},
                "stages": stages,
                "fc": {"w": jax.random.normal(next(keys), (c_last, n_cls))
                       / np.sqrt(c_last),
                       "b": jnp.zeros(n_cls)}}

    return init


def _conv(x, w, stride):
    pad = (w.shape[0] - 1) // 2
    dtype = jnp.result_type(x, w)       # as ``x @ w`` promotes
    return jax.lax.conv_general_dilated(
        x.astype(dtype), w.astype(dtype), (stride, stride),
        ((pad, pad), (pad, pad)), dimension_numbers=_DIMS)


def _norm(x, p):
    """BatchNorm over the batch's own statistics (module docstring)."""
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * jax.lax.rsqrt(var + EPS) * p["scale"] + p["bias"]


def _block(x, p):
    # the stride-2 blocks are the ones with a projection shortcut
    stride = 2 if "proj" in p else 1
    h = jax.nn.relu(_norm(_conv(x, p["conv1"], stride), p["norm1"]))
    h = _norm(_conv(h, p["conv2"], 1), p["norm2"])
    short = _norm(_conv(x, p["proj"], stride), p["proj_norm"]) \
        if "proj" in p else x
    return jax.nn.relu(h + short)


def _logits(params, x, image):
    h = x.reshape((x.shape[0], *image))
    h = jax.nn.relu(_norm(_conv(h, params["stem"]["conv"], 1),
                          params["stem"]["norm"]))
    for stage in params["stages"]:
        for block in stage:
            h = _block(h, block)
    h = jnp.mean(h, axis=(1, 2))
    return h @ params["fc"]["w"] + params["fc"]["b"]


def _image(params, x):
    """(H, W, C) of a flat batch: square, with the stem's input channels."""
    c = params["stem"]["conv"].shape[2]
    side = math.isqrt(x.shape[1] // c)
    return side, side, c


def loss(params, batch):
    """Mean cross-entropy of the batch."""
    x = batch["x"]
    logp = jax.nn.log_softmax(_logits(params, x, _image(params, x)))
    return -jnp.mean(jnp.take_along_axis(logp, batch["y"][:, None], axis=1))


def evaluate(params, batch):
    """(loss, accuracy) on a held-out batch, normalized by its statistics."""
    x = batch["x"]
    acc = jnp.mean((jnp.argmax(_logits(params, x, _image(params, x)), -1)
                    == batch["y"]).astype(jnp.float32))
    return loss(params, batch), acc


# -- counts --------------------------------------------------------------------

def _convs(config):
    """[(kernel, c_in, c_out, stride, input side)] of every convolution in
    forward order, the stem first."""
    side = config["image"][0]
    out = [(3, config["image"][2], config["widths"][0], 1, side)]
    for _, _, c_in, c_out, stride in _layout(config):
        out.append((3, c_in, c_out, stride, side))
        if stride != 1 or c_in != c_out:
            out.append((1, c_in, c_out, stride, side))
        side = (side - 1) // stride + 1
        out.append((3, c_out, c_out, 1, side))
    return out


def _taps(side, k, stride):
    """Pairs of output position and kernel tap that read the image, along
    one spatial axis (padding (k - 1) // 2 on each side)."""
    pad = (k - 1) // 2
    n_out = (side + 2 * pad - k) // stride + 1
    pos = np.arange(n_out)[:, None] * stride - pad + np.arange(k)[None, :]
    return int(np.sum((pos >= 0) & (pos < side)))


def param_count(config) -> int:
    convs = _convs(config)
    n = sum(k * k * c_in * c_out for k, c_in, c_out, *_ in convs)
    norms = 2 * sum(c_out for _, _, c_out, *_ in convs)
    c_last = config["widths"][-1]
    return n + norms + c_last * config["n_classes"] + config["n_classes"]


def grad_flops(config) -> float:
    """FLOPs of one gradient lane: forward and backward of one batch.

    Forward: each convolution's 2·B·C_in·C_out per reading tap, and the
    dense layer's 2·B·512·10.  Backward: every weight gradient costs its
    forward again, and every input gradient too but the stem's, whose
    input is data.  Elementwise work (norms, ReLUs, the pooling, the
    softmax) is left out: about 1.7 % of XLA's count at the published
    widths.
    """
    B = config["batch_size"]
    convs = [2 * B * c_in * c_out * _taps(side, k, stride) ** 2
             for k, c_in, c_out, stride, side in _convs(config)]
    fc = 2 * B * config["widths"][-1] * config["n_classes"]
    return float(3 * (sum(convs) + fc) - convs[0])


def batch_bytes(config, itemsize: int) -> float:
    """Bytes of one pool batch: B images of d_in values and one int32 label."""
    return float(config["batch_size"] * (config["d_in"] * itemsize + 4))
