"""A compute-bound payload under ``mode="auto"``: the bucketed sparse scan.

A convolutional gradient costs far more FLOPs than the bytes of the worker
rows it reads, so ``mode="auto"`` sends DSGD-AAU through the sparse scan
even at N=32, where a row-bound payload takes the dense scan, and merges
narrow events into steps no wider than n lanes.  The run must replay the
same trajectory as the ``per_event`` interpreter and the dense scan.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import topology
from repro.core.baselines import make_scheduler
from repro.core.runner import (COMPUTE_BOUND_FLOPS_PER_BYTE,
                               DecentralizedTrainer)
from repro.data.synthetic import ClassificationData
from repro.scenarios import get_scenario

N = 32
IMAGE = (8, 8, 3)
CLASSES = 4
DATA = ClassificationData(n_workers=N, d=int(np.prod(IMAGE)),
                          n_classes=CLASSES, samples_per_worker=64, seed=0)


def conv_loss(params, batch):
    x = batch["x"].reshape((-1,) + IMAGE)
    h = jax.lax.conv_general_dilated(x, params["conv"], (1, 1), "SAME",
                                     dimension_numbers=("NHWC", "HWIO",
                                                        "NHWC"))
    h = jnp.mean(jax.nn.relu(h), axis=(1, 2))
    logp = jax.nn.log_softmax(h @ params["dense"])
    return -jnp.mean(jnp.take_along_axis(logp, batch["y"][:, None], axis=1))


def init_fn(key):
    k1, k2 = jax.random.split(key)
    return {"conv": jax.random.normal(k1, (3, 3, IMAGE[2], 16)) * 0.2,
            "dense": jax.random.normal(k2, (16, CLASSES)) * 0.2}


class _Sink:
    """File-like run log keeping every record."""

    def __init__(self):
        self.records = []

    def write(self, line):
        self.records.append(json.loads(line))

    def flush(self):
        pass


def _trainer(mode, run_log=None):
    # ER(0.15) with the paper's default stragglers: the first 64 events
    # hold cliques of more than 16 workers (the rung as wide as n)
    sched = make_scheduler("dsgd_aau", topology.erdos_renyi(N, 0.15, seed=1),
                           get_scenario("paper_default", n=N, seed=0))
    return DecentralizedTrainer(
        sched, conv_loss, init_fn,
        lambda w, s: DATA.batch(w, s, batch_size=16), DATA.eval_batch(64),
        eta0=0.1, mode=mode, block_size=32, batch_pool=64, run_log=run_log)


def test_auto_takes_the_sparse_scan_and_replays_the_stream():
    sink = _Sink()
    auto = _trainer("auto", run_log=sink)
    F, R = auto.grad_cost
    assert F / R > COMPUTE_BOUND_FLOPS_PER_BYTE
    assert auto.mode == "sparse_scan"
    (policy,) = [r for r in sink.records if r["event"] == "lane_policy"]
    assert policy["mode"] == "sparse_scan"
    assert policy["lanes_per_step"] == [[16, 32], [32, 32]]

    before = dataclasses.replace(auto.counters)
    res = auto.run(max_events=64, eval_every=32)
    got = auto.counters.since(before)
    blocks = [r for r in sink.records if r["event"] == "block_dispatch"]
    assert all(r["mode"] == "sparse_scan" and r["lanes"] == N
               for r in blocks)
    assert any(r["merged"] for r in blocks), "no merged step"
    assert any(not r["merged"] for r in blocks), "no rung-32 event"
    assert got["grad_evaluated"] == sum(r["events"] * r["lanes"]
                                        for r in blocks)
    assert got["grad"] < got["grad_evaluated"] < 64 * N

    for mode in ("per_event", "scan"):
        other = _trainer(mode)
        ref = other.run(max_events=64, eval_every=32)
        for a, b in zip(jax.tree.leaves((auto.W, auto.S, auto.y)),
                        jax.tree.leaves((other.W, other.S, other.y))):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=0, atol=1e-6, err_msg=mode)
        assert [p.loss for p in res.history] == pytest.approx(
            [p.loss for p in ref.history], abs=1e-6)
        assert res.total_comm_copies == ref.total_comm_copies
