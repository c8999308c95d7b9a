"""ResNet-18 as the trainer's payload: its shapes and counts at the published
widths (from shapes alone), and the program against the plain reference at
a tiny size on the CPU (widths 4/8/8/16, 8×8×3 images, N=32)."""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, run

CELL = "aau64.resnet18-er32"
SEED = 2 ** 31 + 15


@pytest.fixture(scope="module")
def cell():
    return harness.load_cell(harness.load_benchmark(), CELL)


@pytest.fixture(scope="module")
def tiny(cell):
    """The cell at widths 4/8/8/16 on 8×8×3 images, on its own mode, mix
    and limits, in runs of 4 events.

    Runs are short because this payload amplifies any difference in
    rounding: the reference against itself, padded to 32 lanes instead of
    a power of two, reads 1.5e-6 after the first event and 0.93 after the
    next 64 at this size on the CPU (0.0004 and 0.12 to 0.15 at the
    published widths on a v5e).  The program sums in the dense scan's
    order, so over 64-event runs a 1e-5 bound would read that
    amplification, not the program; after 13 events it is still rounding.
    """
    return dataclasses.replace(
        cell, config=dict(cell.config, image=[8, 8, 3], d_in=192,
                          widths=[4, 8, 8, 16]),
        traffic=dict(cell.traffic, events_per_run=4, eval_every=2))


def _shapes(cell):
    return jax.eval_shape(cell.model.make_init(cell.config),
                          jax.random.PRNGKey(0))


def test_published_widths_give_the_published_parameter_count(cell):
    leaves = jax.tree.leaves(_shapes(cell))
    assert len(leaves) == 62
    assert sum(int(np.prod(x.shape)) for x in leaves) == 11_173_962
    assert cell.model.param_count(cell.config) == 11_173_962
    # 3×3 convolutions, 1×1 projections, norms, the dense layer
    assert {x.ndim for x in leaves} == {1, 2, 4}


def test_gradient_flops_are_xlas_count(cell):
    model, config = cell.model, cell.config
    B = config["batch_size"]
    batch = {"x": jax.ShapeDtypeStruct((B, config["d_in"]), jnp.float32),
             "y": jax.ShapeDtypeStruct((B,), jnp.int32)}
    lane = jax.jit(jax.grad(model.loss)).lower(_shapes(cell), batch).compile()
    xla = lane.cost_analysis()["flops"]
    # the rest of XLA's count is elementwise work, which grad_flops leaves out
    assert model.grad_flops(config) <= xla
    assert model.grad_flops(config) == pytest.approx(xla, rel=0.02)
    assert model.grad_flops(config) == pytest.approx(9.403e10, rel=0.02)


def test_counts_follow_the_shapes(cell):
    model, config = cell.model, cell.config
    # the stem alone at one image: 32·32 outputs, 30·30 of them read all 9
    # taps; the padding's zero taps are not counted
    assert model._taps(32, 3, 1) ** 2 == (3 * 32 - 2) ** 2
    assert model._taps(32, 3, 2) == 3 * 16 - 1
    assert model._taps(32, 1, 2) == 16
    assert model.batch_bytes(config, 4) == 32 * (3072 * 4 + 4)
    with pytest.raises(ValueError, match="d_in"):
        model.make_init(dict(config, d_in=3000))


def test_program_matches_the_reference(tiny, peak):
    res = run.run_cell(tiny, SEED, 0.1, False, peak,
                       t_start=time.perf_counter())
    checks = {k: v["value"] for k, v in res["checks"].items()}
    # on the CPU both compute in float32: every number reads 0 to rounding
    assert checks.pop("ptr_mismatch") == 0
    assert all(v <= 1e-5 for v in checks.values()), checks
    assert res["correct"] is True


def test_control_is_not_correct(tiny, monkeypatch, peak):
    """The control: the program with its bfloat16 state policy."""
    real = harness.build_trainer
    monkeypatch.setattr(harness, "build_trainer",
                        lambda *a, **kw: real(*a, **{**kw, "dtype": "bfloat16"}))
    res = run.run_cell(tiny, SEED, 0.1, False, peak,
                       t_start=time.perf_counter())
    assert res["correct"] is False, res["checks"]


# -- the two readers of ``grad_evaluated`` -------------------------------------

BODY = "jit(block_dense)/while/body/closed_call"
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def _window(counters):
    """A window of one block program: 1 s of gradient, 0.5 s of mixing."""
    return {"window": [0.0, 3e9], "devices": 1,
            "ops": [["%w = (f32[4]) while((f32[4]) %t)", 0.0, 2e9],
                    ["%g = f32[4]{0} convolution(f32[4]{0} %p)", 0.0, 1e9],
                    ["%m = f32[4]{0} fusion(f32[4]{0} %p)", 1e9, 0.5e9]],
            "op_scopes": ["jit(block_dense)/while",
                          f"{BODY}/grad/vmap(jvp())/conv_general_dilated",
                          f"{BODY}/mix/dot_general"],
            "modules": [["jit_block_dense(1)", 0.0, 2e9]],
            "host": [], "counters": [["runner:run", 0.0, 1e9, c]
                                     for c in counters]}


def _rec(cell, trace):
    class Rec:
        events, window_s, config, model, peak, itemsize = (
            64, 3.0, cell.config, cell.model, PEAK, 4)
    Rec.trace = trace
    return Rec


@pytest.fixture
def no_traces(tmp_path, monkeypatch):
    from chipbench import program_trace
    monkeypatch.setattr(program_trace, "TRACE_ROOT", tmp_path)


def test_grad_lane_fill_reads_the_counters(cell):
    fill = harness.load_module("metrics", "grad_lane_fill")
    rec = _rec(cell, _window([{"grad": 150, "grad_evaluated": 1024},
                              {"grad": 10, "grad_evaluated": 64}]))
    assert fill.reduce(rec) == pytest.approx(100 * 160 / 1088)


def test_grad_roofline_is_the_least_time_over_the_grad_phase(cell):
    roof = harness.load_module("metrics", "grad_roofline")
    rec = _rec(cell, _window([{"grad": 150, "grad_evaluated": 1024}]))
    D = 11_173_962
    flop_s = 1024 * cell.model.grad_flops(cell.config) / 197e12
    byte_s = 1024 * (D * 4 + 32 * (3072 * 4 + 4)) / 819e9
    assert flop_s > byte_s          # ResNet-18's lanes are bound by FLOPs
    # the grad phase took 1 s of the window's device time
    assert roof.reduce(rec) == pytest.approx(100 * flop_s / 1.0)
    mlp = harness.load_module("models", "mlp2nn")
    two_nn = harness.load_json("configs", "2nn-er128")
    rec.model, rec.config = mlp, two_nn
    # the lane reads its snapshot row and its batch
    assert roof.lane_bytes(rec) == 855_050 * 4 + 32 * (3072 * 4 + 4)


def test_readers_find_nothing_without_the_counter(cell, no_traces):
    readers = [harness.load_module("metrics", k)
               for k in ("grad_lane_fill", "grad_roofline")]
    parent = _window([{"events": 64, "grad": 150}])     # no grad_evaluated
    no_scopes = {k: v for k, v in parent.items() if k != "op_scopes"}
    for trace in (None, parent, no_scopes):
        for reader in readers:
            assert reader.reduce(_rec(cell, trace)) is None
    # a window without the phase scopes has no grad phase to divide by
    bare = _window([{"grad": 150, "grad_evaluated": 1024}])
    bare["op_scopes"] = [""] * 3
    assert readers[1].reduce(_rec(cell, bare)) is None
    assert readers[0].reduce(_rec(cell, bare)) == pytest.approx(
        100 * 150 / 1024)
