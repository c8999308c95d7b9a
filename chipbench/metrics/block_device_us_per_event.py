"""Compiled block (core/aau.py scans): device microseconds of the trainer's
event-block programs (``trace.BLOCK_MODULES``), per event of the window."""
from chipbench import trace


def reduce(rec):
    if rec.trace is None or not rec.events:
        return None
    s = trace.module_seconds(rec.trace, trace.BLOCK_MODULES)
    return s / rec.events * 1e6 if s > 0 else None
