"""Recorded v5e windows through every reader.

``window_aau128.json.gz`` was recorded from a program without the runner's
spans and the blocks' phase scopes: every reader the benchmark had reads it
as it did, and the readers of those spans and scopes find nothing in it.
``window_aau128_scopes.json.gz`` holds 30 ms of a ``--trace 1`` run of
``aau.2nn-er128`` with them (``program_trace.cut`` of the extended window,
across the end of one run and the start of the next).
"""
from pathlib import Path

import numpy as np
import pytest

from chipbench import harness, program_trace
from chipbench import trace as tr

DATA = Path(__file__).resolve().parents[1] / "testdata"
OLD_READERS = {"gen_us_per_event": 307.5,
               "dispatch_us_per_event": 24.046275,
               "lane_fill": 25.0,
               "block_device_us_per_event": 608.3188,
               "event_roofline": 22.599396248870832,
               "mfu": 0.46187909847715736,
               "device_idle_share": 36.0546175}
NEW_READERS = ("runner_gen_us_per_event", "pack_us_per_event",
               "run_self_us_per_event", "blocks_per_event",
               "grad_device_us_per_event", "mix_device_us_per_event",
               "rows_device_us_per_event", "snapshot_device_us_per_event",
               "other_device_us_per_event")


def _rec(window):
    class Rec:
        events, window_s, gen_s = 40, 0.04, 0.0123
        counts = np.tile([[8, 8, 8]], (40, 1))
        dispatches = [{"mode": "sparse_scan", "padded": 8, "lanes": 16}] * 10
        model = harness.load_module("models", "mlp2nn")
        peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
        itemsize = 4
        config = harness.load_json("configs", "2nn-er128")
    Rec.trace = window
    return Rec


@pytest.fixture
def no_traces(tmp_path, monkeypatch):
    monkeypatch.setattr(program_trace, "TRACE_ROOT", tmp_path)


def test_every_earlier_reader_reads_the_recorded_window_as_before():
    rec = _rec(tr.load(DATA / "window_aau128.json.gz"))
    for name, value in OLD_READERS.items():
        assert harness.load_module("metrics", name).reduce(rec) == \
            pytest.approx(value, rel=1e-12), name
    assert tr.BLOCK_MODULES == ("jit_block",)


def test_the_new_readers_find_nothing_in_a_window_without_them(no_traces):
    rec = _rec(tr.load(DATA / "window_aau128.json.gz"))
    for name in NEW_READERS:
        assert harness.load_module("metrics", name).reduce(rec) is None, name


NEW_VALUES = {"runner_gen_us_per_event": 484.71853846153846,
              "pack_us_per_event": 175.02069230769234,
              "run_self_us_per_event": 99.45307692307694,
              "blocks_per_event": (42 + 34) / (256 + 256),
              "grad_device_us_per_event": 62.10046153846152,
              "mix_device_us_per_event": 68.62446153846153,
              "rows_device_us_per_event": 546.0140769230768,
              "snapshot_device_us_per_event": 183.5075384615385,
              "other_device_us_per_event": 236.06207692307711}


def test_every_new_reader_reads_the_window_with_scopes():
    window = tr.load(DATA / "window_aau128_scopes.json.gz")
    rec = _rec(window)
    rec.events = 13
    read = {name: harness.load_module("metrics", name).reduce(rec)
            for name in NEW_READERS}
    assert read == {k: pytest.approx(v, rel=1e-9)
                    for k, v in NEW_VALUES.items()}
    # the five phases partition the blocks' leaf-op time, which covers all
    # but 38 us of their 14.29 ms of module time
    block, bare = program_trace.uncovered(window)
    assert block == pytest.approx(0.014290041, rel=1e-9)
    assert bare == pytest.approx(3.8029e-05, rel=1e-6)
    phases = sum(v for k, v in read.items() if k.endswith("device_us_per_event"))
    assert phases * 13e-6 == pytest.approx(block - bare, rel=1e-9)
    # the window crosses a run boundary: idle time under every program span
    idle = program_trace.idle_by_program_span(window)
    assert set(idle) == {"runner:run", "runner:gen", "runner:pack",
                         "runner:eval", "runner:drain", "outside run"}
    # the readers the benchmark had read the new window too
    assert harness.load_module("metrics", "block_device_us_per_event") \
        .reduce(rec) == pytest.approx(block / 13 * 1e6, rel=1e-9)
