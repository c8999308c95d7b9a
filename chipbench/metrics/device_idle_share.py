"""Device: share of the traced window in which no operation ran on the chip,
from the profiler trace (1 minus the union of device-op intervals over the
window)."""
from chipbench import trace


def reduce(rec):
    if rec.trace is None:
        return None
    win = trace.window_seconds(rec.trace)
    busy = trace.busy_seconds(rec.trace)
    if win <= 0 or busy <= 0:
        return None
    return 100.0 * (1.0 - busy / win)
