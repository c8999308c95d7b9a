"""The reduction from a profiler trace to the per-layer numbers: interval
arithmetic worked by hand, and a 40 ms window recorded on a TPU v5 lite
(``testdata/window_aau128.json.gz``, cell aau.2nn-er128, taken with
``chipbench.trace.compact`` from a ``--trace 1`` run)."""
from pathlib import Path

import numpy as np
import pytest

from chipbench import harness
from chipbench import trace as tr

RECORDED = Path(__file__).resolve().parents[1] / "testdata" / "window_aau128.json.gz"

# window 0..100 ns; device busy 10-30 (two overlapping ops) and 50-60;
# host: a run 0-100 holding a gen span 30-45 and a dispatch span 60-90
HAND = {
    "window": [0.0, 100.0], "devices": 1,
    "ops": [["%a = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop", 10.0, 15.0],
            ["%b = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop", 20.0, 10.0],
            ["%w = (s32[], f32[4]{0}) while((s32[], f32[4]{0}) %t)", 50.0, 10.0]],
    "modules": [["jit_block(1)", 10.0, 20.0], ["jit_eval_row(2)", 50.0, 10.0]],
    "host": [["chipbench:run", 0.0, 100.0], ["chipbench:gen", 30.0, 15.0],
             ["dispatch:sparse_scan", 60.0, 30.0]],
}


def test_intervals_by_hand():
    assert tr.busy(HAND) == [(10.0, 30.0), (50.0, 60.0)]
    assert tr.busy_seconds(HAND) == pytest.approx(30e-9)
    assert tr.window_seconds(HAND) == pytest.approx(100e-9)
    assert tr.idle_gaps(HAND) == [(0.0, 10.0), (30.0, 50.0), (60.0, 100.0)]
    assert tr.module_seconds(HAND, tr.BLOCK_MODULES) == pytest.approx(20e-9)
    assert tr.span_seconds(HAND, "dispatch:") == pytest.approx(30e-9)
    assert tr.host_segments(HAND) == [
        (0.0, 30.0, "chipbench:run"), (30.0, 45.0, "chipbench:gen"),
        (45.0, 60.0, "chipbench:run"), (60.0, 90.0, "dispatch:sparse_scan"),
        (90.0, 100.0, "chipbench:run")]


def test_breakdown_by_hand():
    b = tr.breakdown(HAND)
    # the while loop is a container: its body's ops are listed instead
    assert b["device_ops"] == [["%a fusion f32[4]", pytest.approx(15e-9)],
                               ["%b fusion f32[4]", pytest.approx(10e-9)]]
    # idle 0-10 and 45-50 under the run, 30-45 in gen, 60-90 in dispatch
    # and 90-100 under the run again
    assert dict(b["idle_gaps"]) == {
        "dispatch:sparse_scan x1": pytest.approx(30e-9),
        "chipbench:gen x1": pytest.approx(15e-9),
        "chipbench:run x3": pytest.approx(25e-9)}


def test_op_label():
    assert tr.op_label("%fusion.23 = f32[128,3072,256]{2,1,0:T(8,128)} "
                       "fusion(f32[1]{0} %x), kind=kLoop") == (
        "%fusion.23 fusion f32[128,3072,256]", "fusion")
    assert tr.op_label("%cond.3 = (f32[2]{0}, s32[]) conditional(s32[] %p)")[1] \
        == "conditional"


def test_recorded_window():
    t = tr.load(RECORDED)
    assert t["devices"] == 1
    assert tr.window_seconds(t) == pytest.approx(0.04)
    assert tr.busy_seconds(t) == pytest.approx(0.025578153, rel=1e-9)
    assert tr.module_seconds(t, tr.BLOCK_MODULES) == pytest.approx(
        0.024332752, rel=1e-9)
    assert tr.span_seconds(t, "dispatch:") == pytest.approx(
        0.000961851, rel=1e-9)
    assert tr.span_seconds(t, "chipbench:gen") == pytest.approx(
        0.014793401, rel=1e-9)
    b = tr.breakdown(t)
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 5
    idle = sum(v for _, v in b["idle_gaps"])
    assert idle == pytest.approx(0.04 - 0.025578153, rel=1e-6)
    assert [k for k, _ in b["idle_gaps"]] == [
        "chipbench:gen x2", "chipbench:run x88", "chipbench:eval x16",
        "chipbench:drain x3", "outside run x1"]


def test_recorded_window_through_the_readers():
    t = tr.load(RECORDED)
    config = harness.load_json("configs", "2nn-er128")

    class Rec:
        events, window_s, trace = 40, 0.04, t
        counts = np.tile([[8, 8, 8]], (40, 1))
        model = harness.load_module("models", "mlp2nn")
        peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
        itemsize = 4
    Rec.config = config
    read = {k: harness.load_module("metrics", k).reduce(Rec) for k in (
        "device_idle_share", "block_device_us_per_event",
        "dispatch_us_per_event", "event_roofline")}
    assert read["device_idle_share"] == pytest.approx(
        100 * (1 - 0.025578153 / 0.04))
    assert read["block_device_us_per_event"] == pytest.approx(
        0.024332752 / 40 * 1e6)
    assert read["dispatch_us_per_event"] == pytest.approx(
        0.000961851 / 40 * 1e6)
    # 8 active lanes: (8 + 16 + 8)·D·4 + 8 batches of bytes an event
    least = 40 * (32 * 855_050 * 4 + 8 * 393_344) / 819e9
    assert read["event_roofline"] == pytest.approx(100 * least / 0.024332752)


def test_readers_find_nothing_in_an_empty_trace():
    empty = {"window": [0.0, 1e9], "devices": 0, "ops": [], "modules": [],
             "host": []}

    class Rec:
        events, window_s, trace = 10, 1.0, empty
        counts = np.zeros((0, 3), np.int64)
        dispatches = []
    for k in ("device_idle_share", "block_device_us_per_event",
              "dispatch_us_per_event", "event_roofline", "lane_fill", "mfu"):
        assert harness.load_module("metrics", k).reduce(Rec) is None, k
