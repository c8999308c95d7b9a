"""The plain reference against DecentralizedTrainer.run, and the whole run
path at a tiny size on the CPU (N=32, d_in=64), for each mix's cell."""
import time

import numpy as np
import pytest

from chipbench import compare, harness, run
from chipbench.tests.conftest import one_cell_per_mix, tiny_cell

SEED = 2 ** 31 + 7


@pytest.mark.parametrize("name", one_cell_per_mix())
def test_reference_matches_the_program(name):
    cell = tiny_cell(name)
    seeds = harness.derive_seeds(SEED, cell.traffic)
    trainer, _, p0 = harness.build_trainer(cell, seeds)
    assert trainer.mode == cell.traffic["mode"]
    prog = harness.first_steps(trainer, cell.traffic, p0)
    ref = harness.reference_readings(cell, seeds, mode=trainer.mode)
    nums = compare.numbers(prog, ref)
    # on the CPU both compute in float32: every number reads 0 to rounding
    assert nums["ptr_mismatch"] == 0
    for k in ("loss_gap", "event1_gap", "run1_gap", "run3_gap", "snap3_gap"):
        assert nums[k] <= 1e-5, (k, nums[k])
    # the runs moved the state and restarted workers
    assert np.all(ref[-1]["ptr"] > 0)
    assert float(np.median(ref[-1]["dW"])) > 0


@pytest.mark.parametrize("name", one_cell_per_mix())
def test_sound_run_is_correct(name, peak):
    res = run.run_cell(tiny_cell(name), SEED, 0.3, False, peak,
                       t_start=time.perf_counter())
    assert res["correct"] is True
    assert list(res)[-1] == "checks"
    assert res["metrics"]["events_per_s"]["value"] > 0
    assert res["failed"] == 0 and res["attempted"] > 0


@pytest.mark.parametrize("name", one_cell_per_mix())
def test_control_fails(name, monkeypatch, peak):
    """The control: the program with its bfloat16 state policy."""
    real = harness.build_trainer
    monkeypatch.setattr(harness, "build_trainer",
                        lambda *a, **kw: real(*a, **{**kw, "dtype": "bfloat16"}))
    res = run.run_cell(tiny_cell(name), SEED, 0.1, False, peak,
                       t_start=time.perf_counter())
    assert res["correct"] is False


def test_traced_run_counts_on_the_host(tmp_path, peak):
    """A traced run on the CPU: host counts and spans are read, and no
    device metric is reported (the CPU trace has no device plane)."""
    name = next(n for n in one_cell_per_mix() if n.startswith("aau."))
    res = run.run_cell(tiny_cell(name), SEED, 0.3, True, peak,
                       t_start=time.perf_counter(), trace_dir=tmp_path)
    m = res["metrics"]
    assert 0 < m["lane_fill"]["value"] <= 100
    assert m["gen_us_per_event"]["value"] > 0
    assert m["dispatch_us_per_event"]["value"] > 0
    for device_metric in ("block_device_us_per_event", "event_roofline",
                          "device_idle_share"):
        assert device_metric not in m
    assert res["correct"] is True
