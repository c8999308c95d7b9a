"""Compiled block (core/aau.py scans): device microseconds of the mixing
(the ``mix`` scope: the ``-η·mask⊙G`` step, the W mix and the y mix), per
event of the window.

Counts the block programs' leaf ops (no ``while``/``conditional``/
``call``) whose innermost phase scope is ``mix``
(``chipbench/program_trace.py``)."""
from chipbench import program_trace


def reduce(rec):
    return program_trace.per_event(rec, "mix")
