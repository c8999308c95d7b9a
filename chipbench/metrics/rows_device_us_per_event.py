"""Compiled block (core/aau.py scans): device microseconds of row movement
(the ``sparse_gather``, ``pool_select`` and ``sparse_scatter`` scopes:
row and pool-batch gathers, writes back into the carry), per event of the
window.

Counts the block programs' leaf ops (no ``while``/``conditional``/
``call``) whose innermost phase scope is one of those
(``chipbench/program_trace.py``)."""
from chipbench import program_trace


def reduce(rec):
    return program_trace.per_event(rec, "rows")
