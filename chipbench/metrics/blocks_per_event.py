"""Runner layer (core/runner.py): block programs dispatched per event
consumed, from the runner's ``RunCounters`` as each ``runner:run`` span
carries them (``chipbench/program_trace.py``)."""
from chipbench import program_trace


def reduce(rec):
    t = program_trace.of(rec)
    if t is None:
        return None
    c = program_trace.counter_totals(t)
    if not c.get("events"):
        return None
    return c["blocks"] / c["events"]


def describe(rec):
    t = program_trace.of(rec)
    if t is None:
        return "no counters"
    return f"run counters of the window: {program_trace.counter_totals(t)}"
