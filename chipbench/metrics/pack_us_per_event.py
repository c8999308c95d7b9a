"""Runner layer (core/runner.py): host microseconds inside the runner's
``runner:pack`` spans (merging, slicing and padding a chunk, its step
sizes, and every upload of a block's arguments), per event of the window,
from the profiler trace (``chipbench/program_trace.py``).  The
``dispatch:*`` spans beside them cover the enqueue alone."""
from chipbench import program_trace, trace


def reduce(rec):
    t = program_trace.of(rec)
    if t is None or not rec.events:
        return None
    s = trace.span_seconds(t, "runner:pack")
    return s / rec.events * 1e6 if s > 0 else None
