"""Benchmark harness: one module per paper table/figure.

  python -m benchmarks.run [--paper-scale] [--xl] [--smoke]
      [--only convergence,roofline] [--profile]
  python -m benchmarks.run --compare OLD.json NEW.json

Prints ``name,us_per_call,derived`` CSV rows (the harness contract).
Default scale finishes on CPU in minutes; --paper-scale reproduces the
paper's N∈{128, 256} settings (slow); --xl adds N∈{512, 1024} to the
benches that support it (sparse path only); --smoke runs every bench at
N=16 for a few blocks — a fast importable-and-runnable check to pair with
the tier-1 pytest suite (it never overwrites recorded BENCH_*.json
results).

--profile wraps each selected bench in ``jax.profiler.trace`` and prints
the per-bench trace directory (open with TensorBoard or Perfetto).  Pair
it with ``--only`` and ``--smoke`` to keep traces small: a full bench
traces every dispatch, and the trace grows with wall time.

--compare is the trend gate: a per-row delta report between two recorded
``BENCH_*.json`` files of the same bench (rows matched on their identity
fields, metrics on shared numeric keys; higher is better for ``*_eps`` /
``*_speedup`` throughputs, lower for ``*_overhead`` ratios).  It is a
*soft* CI gate — timing on shared runners drifts — warning at a >= 10%
regression on any metric and failing (exit 1) only at >= 30% on the
pinned throughput metrics.  Readers are tolerant of legacy files: a
``null``, a legacy ``"unsupported"`` string, or a missing key simply
drops that metric from the comparison.
"""
import argparse
import contextlib
import inspect
import json
import os
import sys
import tempfile
import time

MODULES = ("convergence", "walltime", "speedup", "communication",
           "ablation", "kernels", "roofline", "event_stream")

# Hard-gate metrics: the recorded throughputs each PR's perf story rests
# on.  Everything else (overheads, speedup ratios, occupancy) only warns.
PINNED_METRICS = ("gen_eps", "sparse_eps", "e2e_eps", "scan_eps",
                  "per_event_eps")
WARN_AT, FAIL_AT = 0.10, 0.30

# Row-identity fields, in display order; whatever subset a row carries
# forms its key (the event-stream bench uses n/alg, roofline-style tables
# arch/shape).
_ID_FIELDS = ("n", "alg", "algorithm", "arch", "shape", "scenario", "name")
# run configuration, not measurements — a delta here means the benches
# aren't comparable, not that performance moved
_CONFIG_FIELDS = ("events", "block_size", "buckets", "occupancy")


def _load_rows(path):
    with open(path) as f:
        data = json.load(f)
    rows = data.get("results") if isinstance(data, dict) else data
    if not isinstance(rows, list):
        raise SystemExit(f"{path}: expected a bench artifact with a "
                         "'results' list (or a bare row list)")
    keyed = {}
    for r in rows:
        if not isinstance(r, dict):
            continue
        key = tuple((f, r[f]) for f in _ID_FIELDS if f in r)
        keyed[key] = r
    return keyed


def _regression(metric, old, new):
    """Signed regression fraction: positive = worse, negative = better."""
    if metric.endswith("_overhead"):
        return new / old - 1.0   # ratios: lower is better
    return 1.0 - new / old       # throughputs/speedups: higher is better


def compare(old_path: str, new_path: str) -> int:
    from benchmarks.common import as_metric
    old_rows, new_rows = _load_rows(old_path), _load_rows(new_path)
    warns = fails = 0
    for key in old_rows:
        if key not in new_rows:
            print(f"# {_fmt_key(key)}: only in {old_path}", file=sys.stderr)
    for key, new in new_rows.items():
        old = old_rows.get(key)
        if old is None:
            print(f"# {_fmt_key(key)}: only in {new_path}", file=sys.stderr)
            continue
        for metric in sorted(set(old) & set(new)):
            if metric in _CONFIG_FIELDS or any(f == metric for f, _ in key):
                if as_metric(old[metric]) != as_metric(new[metric]):
                    print(f"# {_fmt_key(key)}: config field {metric} "
                          f"differs ({old[metric]!r} -> {new[metric]!r})",
                          file=sys.stderr)
                continue
            ov, nv = as_metric(old[metric]), as_metric(new[metric])
            if ov is None or nv is None or ov == 0:
                continue  # null / legacy "unsupported" / non-numeric
            reg = _regression(metric, ov, nv)
            flag = ""
            if reg >= FAIL_AT and metric in PINNED_METRICS:
                flag, fails = " FAIL", fails + 1
            elif reg >= WARN_AT:
                flag, warns = " WARN", warns + 1
            print(f"{_fmt_key(key)} {metric}: {ov:g} -> {nv:g} "
                  f"({0.0 - 100 * reg:+.1f}%){flag}")
    print(f"# compare: {fails} fail(s), {warns} warning(s) "
          f"(warn >= {WARN_AT:.0%}, fail >= {FAIL_AT:.0%} on pinned rows)",
          file=sys.stderr)
    return 1 if fails else 0


def _fmt_key(key):
    return "/".join(f"{f}={v}" for f, v in key) or "(row)"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--paper-scale", action="store_true")
    ap.add_argument("--xl", action="store_true",
                    help="add N∈{512, 1024} where a bench supports it")
    ap.add_argument("--smoke", action="store_true",
                    help="N=16, a few blocks per bench: fast CI check")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: " + ",".join(MODULES))
    ap.add_argument("--profile", action="store_true",
                    help="wrap each bench in jax.profiler.trace and print "
                         "the trace directory")
    ap.add_argument("--compare", nargs=2, metavar=("OLD.json", "NEW.json"),
                    help="trend gate: per-row metric deltas between two "
                         "recorded bench artifacts (warn >= 10%% "
                         "regression, exit 1 at >= 30%% on pinned rows)")
    args = ap.parse_args()
    if args.compare:
        return compare(*args.compare)
    chosen = args.only.split(",") if args.only else list(MODULES)
    from repro.utils.compile_cache import enable_compile_cache
    print(f"# compile cache: {enable_compile_cache()}", file=sys.stderr)

    trace_root = None
    if args.profile:
        # repo-local (and git-ignored) so traces survive the run and are
        # easy to find; one fresh subdir per invocation
        os.makedirs("bench-traces", exist_ok=True)
        trace_root = tempfile.mkdtemp(prefix="run-", dir="bench-traces")

    print("name,us_per_call,derived")
    failures = 0
    for name in chosen:
        mod = __import__(f"benchmarks.bench_{name}", fromlist=["run"])
        params = inspect.signature(mod.run).parameters
        kw = {"paper_scale": args.paper_scale}
        if "smoke" in params:
            kw["smoke"] = args.smoke
        elif args.smoke:
            print(f"# bench_{name} has no smoke mode; running at default "
                  "scale", file=sys.stderr)
        if "xl" in params:
            kw["xl"] = args.xl
        elif args.xl:
            print(f"# bench_{name} has no xl scale; running at default "
                  "scale", file=sys.stderr)
        profiling = contextlib.nullcontext()
        if trace_root is not None:
            import jax  # deferred: keep --help / arg errors jax-free
            trace_dir = os.path.join(trace_root, name)
            profiling = jax.profiler.trace(trace_dir)
            print(f"# profiling bench_{name} -> {trace_dir}",
                  file=sys.stderr)
        t0 = time.time()
        try:
            with profiling:
                for row in mod.run(**kw):
                    print(row)
        except Exception as e:  # a failing table is a bug, not a skip
            failures += 1
            print(f"{name},0.0,ERROR={e!r}")
        print(f"# bench_{name} done in {time.time()-t0:.1f}s", file=sys.stderr)
    if trace_root is not None:
        print(f"# traces under {trace_root}", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
