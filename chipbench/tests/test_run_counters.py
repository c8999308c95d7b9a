"""The runner's own counters and spans against what the benchmark counts
from outside the program (``harness.Taps``, the run log), at a tiny size
on the CPU."""
import dataclasses

import jax
import pytest

from chipbench import harness, program_trace, trace
from chipbench.tests.conftest import one_cell_per_mix, tiny_cell

SEED = 2 ** 31 + 77


def _tapped(name):
    cell = tiny_cell(name)
    seeds = harness.derive_seeds(SEED, cell.traffic)
    sink = harness.DispatchLog()
    trainer, _, _ = harness.build_trainer(cell, seeds, run_log=sink)
    taps = harness.Taps(trainer)
    sink.lines.clear()          # warmup()'s no-op blocks
    return cell, trainer, taps, sink


@pytest.mark.parametrize("name", one_cell_per_mix())
def test_run_counters_equal_the_taps(name):
    cell, trainer, taps, sink = _tapped(name)
    before = dataclasses.replace(trainer.counters)
    for _ in range(2):
        trainer.run(max_events=cell.traffic["events_per_run"],
                    eval_every=cell.traffic["eval_every"])
    got = trainer.counters.since(before)
    counts = taps.window_counts()
    records = sink.records()
    assert got["events"] == len(counts) == 2 * 64
    assert [got[k] for k in ("active", "grad", "restarts")] \
        == counts.sum(axis=0).tolist()
    assert got["blocks"] == len(records)
    assert got["rows"] == sum(r["padded"] for r in records)


def test_the_readers_find_the_programs_spans_in_a_cpu_trace(tmp_path,
                                                             monkeypatch):
    cell, trainer, taps, sink = _tapped("aau.2nn-er128")
    before = dataclasses.replace(trainer.counters)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    root = tmp_path / "trace"
    jax.profiler.start_trace(str(root / cell.name), profiler_options=opts)
    try:
        win = harness.measure_window(trainer, cell.traffic, 0.0)
    finally:
        jax.profiler.stop_trace()
    compact = trace.compact(trace.find_xplane(root / cell.name))
    monkeypatch.setattr(program_trace, "TRACE_ROOT", root)

    class Rec:
        events, window_s = win.events, win.wall
    Rec.trace = compact
    ext = program_trace.of(Rec)
    assert ext is not None and ext["window"] == compact["window"]
    names = {h[0] for h in ext["host"]}
    assert {"runner:run", "runner:gen", "runner:pack", "runner:eval",
            "runner:drain", "dispatch:sparse_scan"} <= names
    assert program_trace.counter_totals(ext) == trainer.counters.since(
        before)
    read = {k: harness.load_module("metrics", k).reduce(Rec) for k in (
        "runner_gen_us_per_event", "pack_us_per_event",
        "run_self_us_per_event", "blocks_per_event",
        "grad_device_us_per_event", "other_device_us_per_event")}
    assert read["runner_gen_us_per_event"] > 0
    assert read["pack_us_per_event"] > 0
    assert read["run_self_us_per_event"] > 0
    assert read["blocks_per_event"] == pytest.approx(
        len(sink.records()) / win.events)
    # the CPU trace holds no device plane: nothing to split
    assert read["grad_device_us_per_event"] is None
    assert read["other_device_us_per_event"] is None
    # but its metadata plane holds the HLO of the process's block
    # programs, each instruction with its scope path
    tables = program_trace.op_names(trace.find_xplane(root / cell.name))
    sparse = [t for k, t in tables.items()
              if k.startswith("jit_block_sparse")]
    assert sparse
    for table in sparse:
        phases = {program_trace.phase_of(s) for s in table.values()}
        assert set(program_trace.PHASES) <= phases
    # a record of another window finds nothing
    Rec.trace = dict(compact, window=[0.0, 1.0])
    assert program_trace.of(Rec) is None
