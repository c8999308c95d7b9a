"""Runner layer (core/runner.py): host microseconds inside the runner's
``dispatch:*`` spans (packing arrays to the device and enqueuing a block),
per event of the window, from the profiler trace."""
from chipbench import trace


def reduce(rec):
    if rec.trace is None or not rec.events:
        return None
    s = trace.span_seconds(rec.trace, "dispatch:")
    return s / rec.events * 1e6 if s > 0 else None
