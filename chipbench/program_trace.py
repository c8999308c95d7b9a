"""The program's own spans, counters and phase scopes in a traced window.

The runner (``src/repro/core/runner.py``) marks its host phases with
profiler spans: ``runner:run`` around each ``run()``, and inside it
``runner:gen`` (the scheduler's work: each run's new event process and
each pull from it), ``runner:pack`` (host work that
makes a block's arguments, uploads included), ``runner:eval`` and
``runner:drain`` (host waits on the device), beside the ``dispatch:*``
spans around each enqueue.  It attaches each run's ``RunCounters`` to the
run's ``runner:run`` span as span metadata.  The compiled blocks name their
phases with ``jax.named_scope`` (``PHASES``).

``chipbench.trace.compact`` keeps none of this, so :func:`of` reads the
window's ``.xplane.pb`` once more into the same form with three additions:

- ``host`` also holds the ``runner:*`` spans;
- ``op_scopes``: parallel to ``ops``, each device op's scope path: the
  ``op_name`` of its HLO instruction in the module that ran it, "" where
  there is none.  The v5e's op events carry no scope (their stats are
  times alone), so it is looked up by instruction name in the HLO the
  profiler stores in the trace's ``/host:metadata`` plane (:func:`op_names`);
- ``counters``: ``[name, start_ns, dur_ns, metadata]`` of each
  ``runner:run`` span in the window, the metadata being the run's
  ``RunCounters``.

A program without these spans, counters or scopes (one older than them)
leaves the readers nothing to read: they return None.
"""
from __future__ import annotations

from pathlib import Path
from typing import Dict, Iterator, List, Optional, Tuple

from chipbench import trace

HERE = Path(__file__).resolve().parent
TRACE_ROOT = HERE / "out" / "trace"     # where ``chipbench.run`` traces
PROGRAM_PREFIXES = ("runner:", "dispatch:")
HOST_PREFIXES = trace.HOST_PREFIXES + ("runner:",)
RUN_SPAN = "runner:run"
# the compiled blocks' phase scopes, and the metric each one's device time
# goes to: rows are the gathers, the pool-batch select and the scatter
PHASES = {"grad": "grad", "mix": "mix", "sparse_gather": "rows",
          "pool_select": "rows", "sparse_scatter": "rows",
          "s_update": "snapshot"}
GROUPS = ("grad", "mix", "rows", "snapshot", "other")
# scopes only a program with the phase vocabulary has: the parent's
# ``sparse_gather``/``sparse_scatter`` alone do not make a partition
VOCABULARY = ("grad", "mix", "s_update")
HLO_STAT = b"Hlo Proto"        # the metadata plane's stat holding a module

_CACHE: Dict[tuple, dict] = {}


def read(xplane_path) -> dict:
    """The extended window (module docstring) of an ``.xplane.pb``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(xplane_path))
    host: List[list] = []
    counters: List[list] = []
    ops: List[list] = []
    modules: List[list] = []
    devices = set()
    for plane in pd.planes:
        if trace._is_device(plane.name):
            devices.add(plane.name)
            for line in plane.lines:
                if line.name == trace.MODULES_LINE:
                    modules.extend([ev.name, float(ev.start_ns),
                                    float(ev.duration_ns)]
                                   for ev in line.events)
                elif line.name == trace.OPS_LINE:
                    ops.extend([ev.name, float(ev.start_ns),
                                float(ev.duration_ns)]
                               for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if not ev.name.startswith(HOST_PREFIXES):
                        continue
                    host.append([ev.name, float(ev.start_ns),
                                 float(ev.duration_ns)])
                    if ev.name == RUN_SPAN:
                        counters.append([ev.name, float(ev.start_ns),
                                         float(ev.duration_ns),
                                         {k: v for k, v in ev.stats
                                          if isinstance(v, int)}])
    wins = [h for h in host if h[0] == trace.WINDOW]
    if not wins:
        raise ValueError(f"{xplane_path}: no {trace.WINDOW} span in the trace")
    lo, hi = wins[0][1], wins[0][1] + wins[0][2]

    def inside(evs):
        return sorted((e for e in evs if e[1] + e[2] > lo and e[1] < hi),
                      key=lambda e: e[1])

    ops, modules = inside(ops), inside(modules)
    return {"window": [lo, hi], "devices": len(devices), "ops": ops,
            "op_scopes": _scopes(ops, modules, op_names(xplane_path)),
            "modules": modules,
            "host": inside(h for h in host if h[0] != trace.WINDOW),
            "counters": inside(counters)}


def _scopes(ops, modules, names) -> List[str]:
    """Each op's ``op_name`` in the module it ran in (both lists sorted by
    start; an op runs inside its module's interval)."""
    out, j = [], 0
    for text, s, _ in ops:
        while j < len(modules) and modules[j][1] + modules[j][2] <= s:
            j += 1
        inside = j < len(modules) and modules[j][1] <= s
        table = names.get(modules[j][0], {}) if inside else {}
        out.append(table.get(text.split(" = ", 1)[0].lstrip("%"), ""))
    return out


def op_names(xplane_path) -> Dict[str, Dict[str, str]]:
    """{module event name: {HLO instruction name: op_name}} of the block
    programs (``BLOCK_MODULES``), from the HLO protos in the trace's
    ``/host:metadata`` plane.  Read from the protobuf wire format: XSpace
    planes (1); XPlane name (2), event metadata (4), stat metadata (5);
    XEventMetadata name (2), stats (5); XStat metadata id (1), bytes (6);
    HloProto module (1); HloModuleProto computations (3);
    HloComputationProto instructions (2); HloInstructionProto name (1),
    metadata (7); OpMetadata op_name (2)."""
    out: Dict[str, Dict[str, str]] = {}
    space = memoryview(Path(xplane_path).read_bytes())
    for _, plane in _fields(space, 1):
        if bytes(_one(plane, 2, b"")) != b"/host:metadata":
            continue
        hlo_ids = {_one(entry, 1) for _, entry in _fields(plane, 5)
                   if bytes(_one(_one(entry, 2), 2, b"")) == HLO_STAT}
        for _, entry in _fields(plane, 4):
            meta = _one(entry, 2)
            name = bytes(_one(meta, 2, b"")).decode()
            if not name.startswith(trace.BLOCK_MODULES):
                continue
            for _, stat in _fields(meta, 5):
                if _one(stat, 1) in hlo_ids:
                    out[name] = _instruction_op_names(_one(stat, 6, b""))
    return out


def _instruction_op_names(hlo_proto) -> Dict[str, str]:
    names = {}
    for _, module in _fields(hlo_proto, 1):
        for _, comp in _fields(module, 3):
            for _, inst in _fields(comp, 2):
                meta = _one(inst, 7)
                op_name = bytes(_one(meta, 2, b"")) if meta is not None \
                    else b""
                names[bytes(_one(inst, 1, b"")).decode()] = op_name.decode()
    return names


def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return value, i


def _fields(buf, only: Optional[int] = None):
    """(field number, value) of a protobuf message: an int for a varint,
    a memoryview for everything else."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")
        if only is None or field == only:
            yield field, value


def _one(buf, field: int, default=None):
    for _, value in _fields(buf, field):
        return value
    return default


def of(rec) -> Optional[dict]:
    """The extended window of a run record: its trace itself where that is
    already extended (a recorded window), else the newest ``.xplane.pb``
    under ``TRACE_ROOT`` whose window is the record's; None without one."""
    t = getattr(rec, "trace", None)
    if t is None:
        return None
    if "op_scopes" in t:
        return t
    found = sorted(TRACE_ROOT.rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        return None
    newest = found[-1]
    key = (str(newest), newest.stat().st_mtime_ns)
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = read(newest)
    ext = _CACHE[key]
    return ext if ext["window"] == t["window"] else None


def cut(t: dict, lo: float, hi: float) -> dict:
    """The part of an extended window inside [lo, hi), for recording a
    short window as test data (``trace.save``)."""
    def inside(evs):
        return [e for e in evs if e[1] + e[2] > lo and e[1] < hi]

    keep = [i for i, e in enumerate(t["ops"]) if e[1] + e[2] > lo and e[1] < hi]
    return {"window": [lo, hi], "devices": t["devices"],
            "ops": [t["ops"][i] for i in keep],
            "op_scopes": [t["op_scopes"][i] for i in keep],
            "modules": inside(t["modules"]), "host": inside(t["host"]),
            "counters": inside(t["counters"])}


# -- host spans and counters ----------------------------------------------------

def has_program_spans(t: dict) -> bool:
    return any(h[0] == RUN_SPAN for h in t["host"])


def run_self_seconds(t: dict) -> float:
    """Host seconds inside ``runner:run`` and inside none of its child
    program spans (``runner:*``, ``dispatch:*``)."""
    lo, hi = t["window"]
    runs = trace.merged(((h[1], h[1] + h[2]) for h in t["host"]
                         if h[0] == RUN_SPAN), lo, hi)
    kids = trace.merged(((h[1], h[1] + h[2]) for h in t["host"]
                         if h[0].startswith(PROGRAM_PREFIXES)
                         and h[0] != RUN_SPAN), lo, hi)
    covered = sum(max(0.0, min(e, re) - max(s, rs))
                  for rs, re in runs for s, e in kids)
    return (sum(e - s for s, e in runs) - covered) * 1e-9


def counter_totals(t: dict) -> Dict[str, int]:
    """The ``RunCounters`` of the window's runs, summed."""
    out: Dict[str, int] = {}
    for *_, c in t.get("counters", ()):
        for k, v in c.items():
            out[k] = out.get(k, 0) + v
    return out


def idle_by_program_span(t: dict) -> Dict[str, list]:
    """Device idle time by the innermost program span open (``runner:*``,
    ``dispatch:*``; ``outside run`` where none is)."""
    prog = dict(t, host=[h for h in t["host"]
                         if h[0].startswith(PROGRAM_PREFIXES)])
    return trace.idle_by_host(prog)


# -- device phases ------------------------------------------------------------

def phase_of(scope: str) -> Optional[str]:
    """The innermost phase scope of an op's scope path, or None.  A fused
    op's path may join several paths with ';': the first that names a
    phase decides."""
    for path in scope.split(";"):
        hits = [c for c in path.split("/") if c in PHASES]
        if hits:
            return hits[-1]
    return None


def block_leaf_ops(t: dict) -> Iterator[Tuple[str, float, str]]:
    """(op text, seconds in the window, phase or None) of each leaf op (no
    control-flow container) run inside a block program (``BLOCK_MODULES``)."""
    lo, hi = t["window"]
    mods = sorted((m[1], m[1] + m[2]) for m in t["modules"]
                  if m[0].startswith(trace.BLOCK_MODULES))
    j = 0
    for (text, s, d), scope in zip(t["ops"], t["op_scopes"]):
        while j < len(mods) and mods[j][1] <= s:
            j += 1
        if j == len(mods) or mods[j][0] > s:
            continue
        if trace.op_label(text)[1] in trace.CONTAINERS:
            continue
        yield text, (min(s + d, hi) - max(s, lo)) * 1e-9, phase_of(scope)


def phase_seconds(t: dict) -> Optional[Dict[str, float]]:
    """Block leaf-op seconds by metric group (``GROUPS``): an op counts
    under its innermost phase scope, ``other`` under none.  None where the
    blocks carry no phase scopes."""
    if "op_scopes" not in t:
        return None
    out = dict.fromkeys(GROUPS, 0.0)
    seen = set()
    for _, sec, phase in block_leaf_ops(t):
        out[PHASES[phase] if phase else "other"] += sec
        seen.add(phase)
    return out if seen.intersection(VOCABULARY) else None


def per_event(rec, group: str) -> Optional[float]:
    """A phase group's device microseconds per event of the window."""
    t = of(rec)
    split = phase_seconds(t) if t is not None else None
    if split is None or not rec.events:
        return None
    return split[group] / rec.events * 1e6


def uncovered(t: dict) -> Tuple[float, float]:
    """(block module seconds, of them covered by no leaf op)."""
    block = trace.module_seconds(t, trace.BLOCK_MODULES)
    leaf = sum(sec for _, sec, _ in block_leaf_ops(t))
    return block, block - leaf


def unscoped_ops(t: dict, top: int = 5) -> List[Tuple[str, float]]:
    """The block's leaf ops under no phase scope that took most time."""
    per: Dict[str, float] = {}
    for text, sec, phase in block_leaf_ops(t):
        if phase is None:
            label = trace.op_label(text)[0]
            per[label] = per.get(label, 0.0) + sec
    return sorted(per.items(), key=lambda kv: -kv[1])[:top]
