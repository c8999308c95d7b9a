"""Scheduler layer (core/scheduler.py, core/pathsearch.py, core/baselines.py):
host microseconds spent generating events, per event of the window.

Read from the benchmark's own timer around ``packed_stream().next_chunk``
and ``events()`` (the ``chipbench:gen`` span).
"""


def reduce(rec):
    if not rec.events:
        return None
    return rec.gen_s / rec.events * 1e6
