"""One benchmark run of a cell on the chip.

  python -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the program's ``DecentralizedTrainer`` for the cell from the seed,
warms it up (``warmup()``, a one-event run and three runs of the cell's
size, which the correctness check reads), then measures consecutive
``run(max_events=events_per_run, eval_every=...)`` calls for ``--seconds``.
After the window it replays those set-up runs with the plain reference
(``chipbench/reference.py``) and compares.  The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (and with ``--trace 1`` ``breakdown``), then
``checks``, each compared number beside its limit.  Without a TPU, or with
fewer chips than the cell asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import List, Optional  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "src"))

# JAX's persistent compile cache, at a fixed path inside the checkout: the
# program's enable_compile_cache() takes this variable when it is set.
CACHE_DIR = REPO / ".jax_cache"
TRACE_DIR = HERE / "out" / "trace"


@dataclasses.dataclass
class WindowRecord:
    """What the per-layer metric readers see of a traced window."""
    events: int
    window_s: float
    gen_s: float
    counts: np.ndarray          # (events, 3): active, gradient, restarted lanes
    dispatches: List[dict]      # the run log's block_dispatch records
    trace: Optional[dict]       # chipbench.trace.compact() of the window
    config: dict
    model: object
    peak: dict
    itemsize: int


def _device_info(jax):
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _peak_bytes(jax) -> Optional[int]:
    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness
    from chipbench.harness import log

    bench = harness.load_benchmark()
    cell = harness.load_cell(bench, args.workload)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax

    dev = _device_info(jax)
    if dev["platform"] != "tpu" or dev["count"] < cell.chips:
        log(f"chipbench: cell {cell.name} needs {cell.chips} TPU chip(s); "
            f"JAX found {dev['count']} {dev['platform']} device(s)")
        return 2
    peaks = json.loads((HERE / "peaks.json").read_text())
    if dev["kind"] not in peaks:
        log(f"chipbench: no peaks for device kind {dev['kind']!r} in "
            "peaks.json")
        return 2
    peak = peaks[dev["kind"]]

    from repro.utils.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), peak)
    print(json.dumps(result), flush=True)
    return 0


def run_cell(cell, seed: int, seconds: float, traced: bool, peak: dict,
             t_start: float = T0, trace_dir: Path = TRACE_DIR) -> dict:
    """Set-up, window and correctness check of one run; the result object.

    Makes no check for a chip: ``main`` does that before it calls this.
    """
    import jax

    from chipbench import compare, harness
    from chipbench.harness import log

    dev = _device_info(jax)
    seeds = harness.derive_seeds(seed, cell.traffic)
    sink = harness.DispatchLog() if traced else None
    with harness.CompileCounter() as setup_compiles:
        trainer, _, p0 = harness.build_trainer(cell, seeds, run_log=sink)
        taps = harness.Taps(trainer) if traced else None
        prog = harness.first_steps(trainer, cell.traffic, p0)
    mode = trainer.mode
    log(f"cell {cell.name}: mode={mode} n={cell.config['n_workers']} "
        f"seeds={seeds} setup compiles={setup_compiles.count} "
        f"({setup_compiles.seconds:.3f} s)")

    xplane_dir = trace_dir / cell.name
    if traced:
        taps.reset()
        sink.lines.clear()
        shutil.rmtree(xplane_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(xplane_dir), profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    with harness.CompileCounter() as window_compiles, \
            harness.GcPauses() as gc_pauses:
        win = harness.measure_window(trainer, cell.traffic, seconds)
    if traced:
        jax.profiler.stop_trace()
    events, wall = win.events, win.wall
    slowest = int(np.argmax(win.run_s))
    log(f"window: {win.runs} runs, {events} events, {wall:.6f} s, "
        f"compiles in window={window_compiles.count}")
    log(f"window runs: median {float(np.median(win.run_s)):.6f} s, slowest "
        f"{win.run_s[slowest]:.6f} s (run {slowest}); gc pauses "
        f"{gc_pauses.count}, {gc_pauses.total:.6f} s in all, longest "
        f"{gc_pauses.longest:.6f} s")
    memory_peak = _peak_bytes(jax)
    log(f"memory_peak_bytes={memory_peak}")

    metrics, breakdown, device = {}, None, dict(dev)
    device["memory_peak_bytes"] = memory_peak
    if traced:
        from chipbench import trace as tr
        t_reduce = time.perf_counter()
        xp = tr.find_xplane(xplane_dir)
        compact = tr.compact(xp) if xp is not None else None
        if compact is not None:
            tr.save(compact, xplane_dir / "window.json.gz")
            device["busy_s"] = tr.busy_seconds(compact)
            device["window_s"] = tr.window_seconds(compact)
            breakdown = tr.breakdown(compact)
        rec = WindowRecord(
            events=events, window_s=wall, gen_s=taps.gen_s,
            counts=taps.window_counts(), dispatches=sink.records(),
            trace=compact, config=cell.config, model=cell.model, peak=peak,
            itemsize=np.dtype(cell.config["state_dtype"]).itemsize)
        for name, entry in cell.metrics.items():
            reader = cell.readers[name]
            value = reader.reduce(rec)
            if value is not None:
                metrics[name] = {"value": value, "unit": entry["unit"]}
            if hasattr(reader, "describe"):
                log(f"{name}: {reader.describe(rec)}")
        log(f"trace reduced in {time.perf_counter() - t_reduce:.3f} s")
    else:
        rates = {"events_per_s": events / wall, "setup_s": setup_s}
        for name, entry in cell.end_to_end.items():
            metrics[name] = {"value": rates[name], "unit": entry["unit"]}

    # the program's state goes before the reference takes the chip's memory
    del trainer, taps
    gc.collect()
    t_ref = time.perf_counter()
    ref = harness.reference_readings(cell, seeds, mode=mode)
    log(f"reference: {time.perf_counter() - t_ref:.3f} s")
    nums = compare.numbers(prog, ref)
    correct, rows = compare.verdict(nums, cell.limits)
    if not cell.limits:
        log(f"chipbench: no limits file for {cell.name}: not correct")
    log(f"not compared: y_gap={nums['y_gap']!r}")
    for name, value, limit in rows:
        log(f"check {name}={value!r} limit={limit!r}")
    result = {"correct": bool(correct), "attempted": events + win.shortfall,
              "failed": win.shortfall, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {name: {"value": value, "limit": limit}
                        for name, value, limit in rows}
    return result


if __name__ == "__main__":
    raise SystemExit(main())
