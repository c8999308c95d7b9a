"""CLI for the experiment harness.

  python -m repro.xp [--preset paper_figures] [--out BENCH_paper_figures.json]
  python -m repro.xp --smoke            # CI dry-run tier (N=8, all scenarios)

Prints ``name,us_per_call,derived`` CSV rows (the benchmark-harness
contract) and writes the JSON artifact only when ``--out`` is given, so a
smoke run can never clobber recorded results.  Render tables from a
recorded artifact with ``python experiments/render_tables.py paper_figures``.
"""
from __future__ import annotations

import argparse
import sys

from repro.xp.artifacts import artifact_payload, csv_rows, write_artifact
from repro.xp.presets import PRESETS, get_preset
from repro.xp.sweep import run_spec


def _csv_tuple(s, conv=str):
    return tuple(conv(x) for x in s.split(",") if x)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m repro.xp")
    ap.add_argument("--preset", default="paper_figures",
                    choices=sorted(PRESETS))
    ap.add_argument("--smoke", action="store_true",
                    help="shortcut for --preset smoke (CI dry-run tier)")
    ap.add_argument("--out", default=None,
                    help="write the JSON artifact here (omit: print only)")
    ap.add_argument("--scales", default=None,
                    help="override worker counts, e.g. 32,64")
    ap.add_argument("--seeds", default=None, help="override seeds, e.g. 0,1")
    ap.add_argument("--scenarios", default=None,
                    help="override scenario names, e.g. paper_default,churn")
    ap.add_argument("--dtype", default=None,
                    help="worker-state dtype policy: float32 | bfloat16")
    ap.add_argument("--max-time", type=float, default=None,
                    help="override the async virtual-time budget")
    ap.add_argument("--telemetry", action="store_true",
                    help="record device-resident per-worker telemetry "
                         "(repro.obs) into the artifact's telemetry section")
    ap.add_argument("--trace", action="store_true",
                    help="record event-identity traces and the wait-blame / "
                         "straggler-tax summary (repro.obs.trace) into the "
                         "artifact's trace section")
    ap.add_argument("--run-log", default=None,
                    help="append structured JSONL run events here")
    args = ap.parse_args(argv)

    spec = get_preset("smoke" if args.smoke else args.preset)
    over = {}
    if args.scales:
        over["scales"] = _csv_tuple(args.scales, int)
    if args.seeds:
        over["seeds"] = _csv_tuple(args.seeds, int)
    if args.scenarios:
        over["scenarios"] = _csv_tuple(args.scenarios)
    if args.dtype:
        over["dtype"] = args.dtype
    if args.max_time is not None:
        # an explicit time budget must actually bind: drop any event bound
        # the preset carries (event bounds take precedence in the sweep)
        over["max_time"] = args.max_time
        over["max_events"] = None
    if args.telemetry:
        over["telemetry"] = True
    if args.trace:
        over["trace"] = True
    if args.run_log:
        over["run_log"] = args.run_log
    if over:
        spec = spec.replace(**over)

    sweep = run_spec(spec, log=lambda s: print(s, file=sys.stderr))
    payload = artifact_payload(sweep)
    print("name,us_per_call,derived")
    for row in csv_rows(payload):
        print(row)
    if args.out:
        write_artifact(args.out, payload)
        print(f"# wrote {args.out}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
