"""From the profiler's trace to the few series the per-layer metrics read.

``compact(xplane_path)`` keeps, inside the ``chipbench:window`` span:

- ``ops``: the device's XLA operations, ``[name, start_ns, dur_ns]``;
- ``modules``: the device's XLA module (program) executions, likewise;
- ``host``: the host spans of the benchmark (``chipbench:*``) and of the
  runner (``dispatch:*``);
- ``window``: ``[start_ns, end_ns]`` of the measured window.

Device and host events share the profiler's clock.  The rest of this
module is interval arithmetic over those lists.
"""
from __future__ import annotations

import gzip
import json
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW = "chipbench:window"
HOST_PREFIXES = ("chipbench:", "dispatch:")
# XLA module names of the trainer's compiled event blocks: the jitted
# ``block`` of core/aau.py's dense and sparse scans.
BLOCK_MODULES = ("jit_block",)
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:TPU:") and "SparseCore" not in plane_name


def compact(xplane_path) -> dict:
    """Read an ``.xplane.pb`` into the compact form (see module docstring)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(xplane_path))
    host: List[list] = []
    ops: List[list] = []
    modules: List[list] = []
    devices = set()
    for plane in pd.planes:
        if _is_device(plane.name):
            devices.add(plane.name)
            for line in plane.lines:
                dst = {OPS_LINE: ops, MODULES_LINE: modules}.get(line.name)
                if dst is None:
                    continue
                for ev in line.events:
                    dst.append([ev.name, float(ev.start_ns),
                                float(ev.duration_ns)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIXES):
                        host.append([ev.name, float(ev.start_ns),
                                     float(ev.duration_ns)])
    wins = [h for h in host if h[0] == WINDOW]
    if not wins:
        raise ValueError(f"{xplane_path}: no {WINDOW} span in the trace")
    lo, hi = wins[0][1], wins[0][1] + wins[0][2]

    def inside(evs):
        return sorted((e for e in evs if e[1] + e[2] > lo and e[1] < hi),
                      key=lambda e: e[1])

    return {"window": [lo, hi], "devices": len(devices),
            "ops": inside(ops), "modules": inside(modules),
            "host": inside(h for h in host if h[0] != WINDOW)}


def save(trace: dict, path) -> None:
    with gzip.open(path, "wt", encoding="utf-8") as fh:
        json.dump(trace, fh)


def load(path) -> dict:
    with gzip.open(path, "rt", encoding="utf-8") as fh:
        return json.load(fh)


def find_xplane(log_dir) -> Optional[Path]:
    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    return found[-1] if found else None


# -- interval arithmetic -----------------------------------------------------------

def merged(intervals: Iterable[Tuple[float, float]], lo: float,
           hi: float) -> List[Tuple[float, float]]:
    """Union of [start, end) intervals, clipped to [lo, hi], in order."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def busy(trace: dict) -> List[Tuple[float, float]]:
    """Intervals in which some operation ran on the device."""
    lo, hi = trace["window"]
    evs = trace["ops"] or trace["modules"]
    return merged(((e[1], e[1] + e[2]) for e in evs), lo, hi)


def busy_seconds(trace: dict) -> float:
    return sum(e - s for s, e in busy(trace)) * 1e-9


def window_seconds(trace: dict) -> float:
    lo, hi = trace["window"]
    return (hi - lo) * 1e-9


def idle_gaps(trace: dict) -> List[Tuple[float, float]]:
    lo, hi = trace["window"]
    out, t = [], lo
    for s, e in busy(trace):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def host_segments(trace: dict) -> List[Tuple[float, float, str]]:
    """The window cut where host spans open or close, each piece labelled
    with the innermost span open in it (the one opened last), or
    ``outside run`` where none is."""
    lo, hi = trace["window"]
    marks = []
    for i, (name, s, d) in enumerate(trace["host"]):
        marks.append((max(s, lo), 0, i))
        marks.append((min(s + d, hi), 1, i))
    marks.sort()
    out: List[Tuple[float, float, str]] = []
    open_spans: Dict[int, float] = {}
    t = lo
    for when, kind, i in marks + [(hi, 2, -1)]:
        if when > t:
            label = (trace["host"][max(open_spans, key=open_spans.get)][0]
                     if open_spans else "outside run")
            out.append((t, when, label))
            t = when
        if kind == 0:
            open_spans[i] = when
        elif kind == 1:
            open_spans.pop(i, None)
    return out


def idle_by_host(trace: dict) -> Dict[str, list]:
    """Device idle time split by what the host was doing: label ->
    [nanoseconds, pieces of idle gaps]."""
    segs = host_segments(trace)
    out: Dict[str, list] = {}
    j = 0
    for gs, ge in idle_gaps(trace):
        while j < len(segs) and segs[j][1] <= gs:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < ge:
            s, e, label = segs[k]
            piece = min(e, ge) - max(s, gs)
            if piece > 0:
                acc = out.setdefault(label, [0.0, 0])
                acc[0] += piece
                acc[1] += 1
            k += 1
    return out


def span_seconds(trace: dict, prefix: str) -> float:
    """Host seconds inside spans whose name starts with ``prefix`` (nested
    spans of one prefix counted once)."""
    lo, hi = trace["window"]
    return sum(e - s for s, e in merged(
        ((h[1], h[1] + h[2]) for h in trace["host"] if h[0].startswith(prefix)),
        lo, hi)) * 1e-9


def module_seconds(trace: dict, names: Iterable[str]) -> float:
    """Device seconds of the XLA modules whose name starts with one of
    ``names`` (the jitted function's name, ``jit_<name>``)."""
    prefixes = tuple(names)
    lo, hi = trace["window"]
    return sum(min(e[1] + e[2], hi) - max(e[1], lo) for e in trace["modules"]
               if e[0].startswith(prefixes)) * 1e-9


CONTAINERS = ("while", "conditional", "call")


def op_label(text: str) -> Tuple[str, str]:
    """(label, opcode) of an XLA op event named by its HLO text: the
    instruction's name, opcode and (for a single result) result shape."""
    if " = " not in text:
        return text[:80], ""
    name, rest = text.split(" = ", 1)
    shape = ""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    elif " " in rest:
        shape, rest = rest.split(" ", 1)
        shape = shape.split("{", 1)[0]
    opcode = rest.strip().split("(", 1)[0]
    return " ".join(x for x in (name, opcode, shape) if x), opcode


def breakdown(trace: dict, top: int = 10) -> Dict[str, list]:
    """The device operations that took most time (control-flow containers,
    whose bodies are listed themselves, left out) and the device's idle time
    by what the host was doing, each as [[name, seconds], ...]."""
    lo, hi = trace["window"]
    per_op: Dict[str, float] = {}
    for text, s, d in trace["ops"] or trace["modules"]:
        name, opcode = op_label(text)
        if opcode in CONTAINERS:
            continue
        per_op[name] = per_op.get(name, 0.0) + (min(s + d, hi) - max(s, lo))
    per_label = idle_by_host(trace)
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(per_label.items(), key=lambda kv: -kv[1][0])[:top]
    return {"device_ops": [[k, v * 1e-9] for k, v in ops],
            "idle_gaps": [[f"{k} x{v[1]}", v[0] * 1e-9] for k, v in idle]}
