"""Readings that the correctness limits are set from, for one cell on the chip.

  python -m chipbench.control --workload <cell> --seeds 11,12,... \\
      [--control-seeds 3] [--out <file.jsonl>]

In one process: for every seed, the program's sound run (its first three
runs, as the benchmark's set-up drives them) against the reference, which
gives the lower readings; on the first ``--control-seeds`` seeds the
control, the program with its bfloat16 state policy (the nearest precision
below the configuration's float32), and two faults planted in the reference
put in the program's place: the exchange between workers left out, and
half of each batch left out with the mean over the rest.  A state left
unchanged reads 1 by construction and needs no run.  Prints one JSON line
per reading, then the largest sound reading and the smallest control and
fault readings of every compared number and of ``y_gap``, which is read but
not compared.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(REPO / ".jax_cache")
    import jax

    from chipbench import compare, harness
    from repro.utils.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("chipbench.control: needs a TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.load_cell(harness.load_benchmark(), args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    out = open(args.out, "a", encoding="utf-8") if args.out else None
    table = {}

    def emit(kind, seed, nums, secs):
        rec = {"cell": cell.name, "kind": kind, "seed": seed,
               "seconds": secs, **nums}
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
        for k in (*compare.NUMBERS, "y_gap"):
            table.setdefault(kind, {}).setdefault(k, []).append(nums[k])

    for i, seed in enumerate(seeds):
        s = harness.derive_seeds(seed, cell.traffic)
        t = time.perf_counter()
        trainer, _, p0 = harness.build_trainer(cell, s)
        mode = trainer.mode
        prog = harness.first_steps(trainer, cell.traffic, p0)
        del trainer
        gc.collect()
        t_prog = time.perf_counter() - t
        t = time.perf_counter()
        ref = harness.reference_readings(cell, s, mode=mode)
        emit("sound", seed, compare.numbers(prog, ref),
             [t_prog, time.perf_counter() - t])
        if i >= args.control_seeds:
            continue
        t = time.perf_counter()
        trainer, _, p0 = harness.build_trainer(cell, s, dtype="bfloat16")
        ctl = harness.first_steps(trainer, cell.traffic, p0)
        del trainer
        gc.collect()
        emit("control_bf16", seed, compare.numbers(ctl, ref),
             [time.perf_counter() - t])
        for kind, kw in (("fault_no_mix", {"mix": False}),
                         ("fault_half_batch", {"half_batch": True})):
            t = time.perf_counter()
            bad = harness.reference_readings(cell, s, mode=mode, **kw)
            emit(kind, seed, compare.numbers(bad, ref),
                 [time.perf_counter() - t])
    for kind, cols in table.items():
        agg = max if kind == "sound" else min
        print(json.dumps({"cell": cell.name, "summary": kind,
                          "agg": agg.__name__,
                          **{k: agg(v) for k, v in cols.items()}}), flush=True)
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
