"""Compiled block (core/aau.py scans): device microseconds of the block
programs' leaf ops (no ``while``/``conditional``/``call``) under none of
the phase scopes (carry copies, converts, the plumbing of the scan and its
``cond``), per event of the window (``chipbench/program_trace.py``).
With the four phase metrics it partitions the blocks' leaf-op time;
``describe`` logs the module time no leaf op covers and the costliest
unscoped ops."""
from chipbench import program_trace


def reduce(rec):
    return program_trace.per_event(rec, "other")


def describe(rec):
    t = program_trace.of(rec)
    if t is None or program_trace.phase_seconds(t) is None:
        return "no phase scopes"
    block, bare = program_trace.uncovered(t)
    top = ", ".join(f"{k} {v:.6f} s"
                    for k, v in program_trace.unscoped_ops(t))
    return (f"block modules {block:.6f} s, {bare:.6f} s of it under no leaf "
            f"op; costliest unscoped ops: {top}")
