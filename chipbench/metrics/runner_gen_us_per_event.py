"""Scheduler layer (core/scheduler.py, core/pathsearch.py, core/baselines.py):
host microseconds inside the runner's own ``runner:gen`` spans (each run's
new event process and each pull from it), per event of the window, from
the profiler trace (``chipbench/program_trace.py``)."""
from chipbench import program_trace, trace


def reduce(rec):
    t = program_trace.of(rec)
    if t is None or not rec.events:
        return None
    s = trace.span_seconds(t, "runner:gen")
    return s / rec.events * 1e6 if s > 0 else None
