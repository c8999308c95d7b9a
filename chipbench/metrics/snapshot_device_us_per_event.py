"""Compiled block (core/aau.py scans): device microseconds of the snapshot
update (the ``s_update`` scope: ``where(rm, W, S)``), per event of the
window.

Counts the block programs' leaf ops (no ``while``/``conditional``/
``call``) whose innermost phase scope is ``s_update``
(``chipbench/program_trace.py``)."""
from chipbench import program_trace


def reduce(rec):
    return program_trace.per_event(rec, "snapshot")
