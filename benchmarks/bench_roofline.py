"""§Roofline: per (arch × shape × mesh) terms from the dry-run artifacts.

Reads ``experiments/dryrun_{single,multi}.json`` written by
``python -m repro.launch.dryrun --all [--multipod] --out experiments`` and
emits one CSV row per pair.  A missing artifact is a failure that names the
command producing it: the dry-run compiles for 512 placeholder devices in a
process of its own, and this bench runs inside the harness's JAX process,
which must not start another.
"""
import json
import os

from benchmarks.common import csv_row

ART = os.path.join(os.path.dirname(__file__), "..", "experiments")


def _artifact(tag: str) -> str:
    path = os.path.join(ART, f"dryrun_{tag}.json")
    if not os.path.exists(path):
        cmd = ("PYTHONPATH=src python -m repro.launch.dryrun --all "
               "--out experiments" + (" --multipod" if tag == "multi" else ""))
        raise FileNotFoundError(
            f"dry-run artifact {path} is missing; produce it with: {cmd}")
    return path


def run(paper_scale: bool = False):
    rows = []
    for tag in ("single", "multi"):
        with open(_artifact(tag)) as f:
            data = json.load(f)
        for r in data:
            if "error" in r:
                rows.append(csv_row(
                    f"roofline/{tag}/{r['arch']}/{r['shape']}", 0.0,
                    f"ERROR={r['error'][:60]}"))
                continue
            bound = max(r["compute_s"], r["memory_s"], r["collective_s"])
            rows.append(csv_row(
                f"roofline/{tag}/{r['arch']}/{r['shape']}",
                1e6 * bound,  # roofline-bound step latency
                f"dom={r['dominant']};comp_ms={r['compute_s']*1e3:.2f};"
                f"mem_ms={r['memory_s']*1e3:.2f};"
                f"coll_ms={r['collective_s']*1e3:.2f};"
                f"useful={r['useful_flops_ratio']:.3f};"
                f"peak_GiB={r['peak_bytes_per_device']/2**30:.2f}"))
    return rows
