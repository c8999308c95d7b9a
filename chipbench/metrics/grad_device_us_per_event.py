"""Compiled block (core/aau.py scans): device microseconds of the gradient
lanes (``jax.vmap(grad_fn)``, the ``grad`` scope), per event of the window.

Counts the block programs' leaf ops (no ``while``/``conditional``/
``call``) whose innermost phase scope is ``grad``
(``chipbench/program_trace.py``)."""
from chipbench import program_trace


def reduce(rec):
    return program_trace.per_event(rec, "grad")
