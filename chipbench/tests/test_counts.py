"""Operation and byte counts of the 2-NN and of an event, worked by hand."""
import numpy as np
import pytest

from chipbench import harness

CONFIG = harness.load_json("configs", "2nn-er128")
MODEL = harness.load_module("models", "mlp2nn")
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_parameter_count():
    # 3072·256 + 256 + 256·256 + 256 + 256·10 + 10
    assert MODEL.param_count(CONFIG) == 855_050


def test_gradient_flops():
    # forward 2·32·(786,432 + 65,536 + 2,560) = 54,689,792; backward twice
    # that for the weights plus 2·32·(65,536 + 2,560) = 4,358,144 for the
    # inputs of layers 2 and 3
    assert MODEL.grad_flops(CONFIG) == 2 * 54_689_792 + 4_358_144


def test_batch_bytes():
    assert MODEL.batch_bytes(CONFIG, 4) == 32 * (3072 * 4 + 4)


def test_event_least_time():
    roof = harness.load_module("metrics", "event_roofline")

    class Rec:
        counts = np.array([[2, 1, 1], [128, 128, 128]])
        config, model, itemsize, peak = CONFIG, MODEL, 4, PEAK
    tf, tb = roof.per_event(Rec)
    D = 855_050
    # a pair event: one gradient lane, two rows mixed
    assert tf[0] == pytest.approx((113_737_728 + 2 * 4 * D) / 197e12)
    assert tb[0] == pytest.approx(((1 + 4 + 1) * D * 4 + 393_344) / 819e9)
    # a barrier of 128: 128 lanes and the dense 128-way mix
    assert tf[1] == pytest.approx(
        (128 * 113_737_728 + 2 * 128 ** 2 * D) / 197e12)
    assert tb[1] == pytest.approx(
        (4 * 128 * D * 4 + 128 * 393_344) / 819e9)


def test_mfu_counts_gradient_lanes_only():
    mfu = harness.load_module("metrics", "mfu")

    class Rec:
        counts = np.array([[5, 5, 5], [2, 1, 1]])
        config, model, peak, window_s = CONFIG, MODEL, PEAK, 2.0
    assert mfu.reduce(Rec) == pytest.approx(
        100 * 6 * 113_737_728 / (2.0 * 197e12))


def test_lane_fill_uses_dispatched_slots():
    fill = harness.load_module("metrics", "lane_fill")

    class Rec:
        counts = np.array([[5, 5, 5], [3, 3, 3], [16, 16, 16]])
        config = CONFIG
        dispatches = [{"mode": "sparse_scan", "padded": 8, "lanes": 64},
                      {"mode": "scan", "padded": 1}]
    assert fill.reduce(Rec) == pytest.approx(100 * 24 / (8 * 64 + 128))
