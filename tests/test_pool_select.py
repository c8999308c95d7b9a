"""The sparse step's pool selection is a plain gather, bit for bit.

``select_pool_batch_at`` multiplies each gathered floating batch by a
per-lane factor (``pool_lane_factor``) so that the TPU compiler cannot hoist
a convert of the whole sample pool out of the scan
(tests/test_tpu_compile.py checks the compiled program).  The factor is 1
on every lane, so every sparse program — the sparse scan, its telemetry
variant and the fused pair scan — must produce the same ``(W, S, y, ptr)``
as with the plain ``pool[widx, ptr mod pool]`` gather: padded (-1) lanes,
restart counters past the pool length (the wrap), f32 and bf16 state.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aau, topology
from repro.core.baselines import make_scheduler
from repro.core.runner import DecentralizedTrainer
from repro.core.straggler import StragglerModel
from repro.data.synthetic import ClassificationData

N, D, H, C = 16, 16, 8, 4
POOL = 3            # short, so the runs below wrap it many times
DATA = ClassificationData(n_workers=N, d=D, n_classes=C,
                          samples_per_worker=64, seed=0)
INT32 = np.iinfo(np.int32)


def loss_fn(params, batch):
    h = jax.nn.relu(batch["x"] @ params["w1"])
    logp = jax.nn.log_softmax(h @ params["w2"])
    return -jnp.mean(jnp.take_along_axis(logp, batch["y"][:, None], axis=1))


def init_fn(key):
    k1, k2 = jax.random.split(key)
    return {"w1": jax.random.normal(k1, (D, H)) * 0.3,
            "w2": jax.random.normal(k2, (H, C)) * 0.3}


def plain_select(pools, widx, ptra):
    return jax.tree.map(lambda pool: pool[widx, ptra % pool.shape[1]], pools)


def _bits(x):
    x = np.asarray(x)
    return x.view(np.dtype(f"u{x.dtype.itemsize}")) if x.dtype.kind == "f" \
        else x


def _assert_bit_identical(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(_bits(x), _bits(y))


@pytest.mark.parametrize("pool_len", [1, 3, 16, 2048])
def test_factor_is_one_on_every_lane(pool_len):
    ptr = np.concatenate([
        np.arange(-3 * pool_len - 5, 3 * pool_len + 5),
        [INT32.min, INT32.min + 1, INT32.max - 1, INT32.max]]).astype(np.int32)
    factor = jax.jit(lambda p: aau.pool_lane_factor(p % pool_len))(ptr)
    assert factor.dtype == jnp.bool_
    assert bool(jnp.all(factor))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_select_matches_plain_gather(dtype):
    rng = np.random.default_rng(1)
    pools = {"x": jnp.asarray(rng.normal(size=(N, POOL, 8, D)), dtype),
             "y": jnp.asarray(rng.integers(0, C, size=(N, POOL, 8)),
                              jnp.int32)}
    widx = jnp.asarray(rng.integers(0, N, size=64), jnp.int32)
    ptra = jnp.asarray(np.concatenate([
        rng.integers(0, 10 * POOL, size=60),
        [INT32.min, -1, INT32.max, POOL]]), jnp.int32)
    got = jax.jit(aau.select_pool_batch_at)(pools, widx, ptra)
    want = jax.jit(plain_select)(pools, widx, ptra)
    assert got["x"].dtype == dtype and got["y"].dtype == jnp.int32
    _assert_bit_identical(got, want)


def _event_args(dtype, seed):
    rng = np.random.default_rng(seed)
    params = jax.vmap(init_fn)(jax.random.split(jax.random.key(seed), N))
    W = jax.tree.map(lambda w: w.astype(dtype), params)
    S = jax.tree.map(lambda w: (w + 0.05).astype(dtype), params)
    y = jnp.asarray(1.0 + 0.1 * rng.random(N), jnp.float32)
    # restart counters well past the pool length: the selection wraps, and
    # the active workers 2, 5, 9, 11 read every pool slot
    ptr = rng.integers(0, 7 * POOL, size=N)
    ptr[[2, 5, 9, 11]] = [0, POOL + 1, 5 * POOL + 2, 6 * POOL]
    ptr = jnp.asarray(ptr, jnp.int32)
    pools = {"x": jnp.asarray(rng.normal(size=(N, POOL, 8, D)), dtype),
             "y": jnp.asarray(rng.integers(0, C, size=(N, POOL, 8)),
                              jnp.int32)}
    workers = jnp.asarray([2, 5, 9, 11, -1, -1], jnp.int32)  # padded lanes
    A = workers.shape[0]
    valid = np.asarray(workers) >= 0
    P = rng.random((A, A)) * valid[:, None] * valid[None, :]
    P_sub = jnp.asarray(P / np.maximum(P.sum(0, keepdims=True), 1e-9),
                        jnp.float32)
    gm = jnp.asarray(valid & (np.arange(A) != 3))
    rm = jnp.asarray(valid & (np.arange(A) % 2 == 0))
    eta = jnp.float32(0.2)
    return W, S, y, ptr, pools, workers, P_sub, gm, rm, eta


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sparse_event_update_matches_plain_gather(dtype, monkeypatch):
    W, S, y, ptr, pools, workers, P_sub, gm, rm, eta = _event_args(dtype, 3)
    grad_fn = jax.grad(loss_fn)

    def update():   # a new function each call: jit traces it afresh
        return jax.jit(lambda *a: aau.sparse_event_update(
            *a[:5], grad_fn, *a[5:]))(W, S, y, ptr, pools, workers, P_sub,
                                     gm, rm, eta)

    got = update()
    monkeypatch.setattr(aau, "select_pool_batch_at", plain_select)
    want = update()
    _assert_bit_identical(got, want)
    # the event moved the active rows and left the others alone
    moved = np.asarray(got[0]["w1"] != W["w1"]).any(axis=(1, 2))
    assert moved[[2, 5, 9, 11]].all() and moved.sum() == 4


def _trainer(alg, mode, dtype, telemetry):
    g = topology.erdos_renyi(N, 0.4, seed=3)
    sm = StragglerModel(n=N, straggler_prob=0.2, slowdown=6.0, seed=0)
    return DecentralizedTrainer(
        make_scheduler(alg, g, sm), loss_fn, init_fn,
        lambda w, s: DATA.batch(w, s, batch_size=8),
        DATA.eval_batch(64), eta0=0.2, eta_decay=0.99, seed=0, mode=mode,
        block_size=8, batch_pool=POOL, dtype=dtype, telemetry=telemetry)


# (scheduler, mode, telemetry): the three sparse programs that run
# sparse_event_update — the sparse scan, its telemetry variant and the
# fused pair scan
SCANS = {"sparse": ("dsgd_aau", "sparse_scan", False),
         "telemetry": ("dsgd_aau", "sparse_scan", True),
         "fused": ("ad_psgd", "fused", False)}


@pytest.mark.filterwarnings("ignore:batch pool of")   # the wrap is wanted
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("scan", sorted(SCANS))
def test_scans_match_plain_gather(scan, dtype, monkeypatch):
    alg, mode, telemetry = SCANS[scan]

    def final_state():
        tr = _trainer(alg, mode, dtype, telemetry)
        tr.run(max_events=48, eval_every=48)
        assert tr.mode == mode
        return tr.W, tr.S, tr.y, tr._ptr

    got = final_state()
    monkeypatch.setattr(aau, "select_pool_batch_at", plain_select)
    want = final_state()
    _assert_bit_identical(got, want)
    assert int(np.max(np.asarray(got[3]))) > POOL   # the pool wrapped
