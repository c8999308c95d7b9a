"""Compiled block (core/aau.py scans): the gradient lanes' share of their
roofline.  The least time the chip needs for every lane the block programs
evaluated (``RunCounters.grad_evaluated``, ``chipbench/program_trace.py``),
as a share of the device time of the ``grad`` phase scope.

Per evaluated lane: the model's forward and backward FLOPs
(``grad_flops``), and the bytes the gradient has to read, the lane's
snapshot row and its pool batch: D·s + ``batch_bytes``.  The gradient's
write is not charged: the compiler may fuse it into the update, whose ops
lie under the ``mix`` scope, and a charge for it lifts the 2-NN's sparse
cells past 100 % (``adpsgd.2nn-er128``: 4.75 lanes an event, 2·D·s a lane
at HBM peak is 42 µs, against 27–31 µs of ``grad`` on a v5e).  Its least time is
the larger of FLOPs over the bf16 peak and bytes over the HBM bandwidth
(``peaks.json``).
"""
from chipbench import program_trace


def lane_bytes(rec) -> float:
    s = rec.itemsize
    return (float(rec.model.param_count(rec.config)) * s
            + rec.model.batch_bytes(rec.config, s))


def least_seconds(rec, lanes: int):
    """(FLOP seconds, byte seconds) of ``lanes`` gradient lanes."""
    flops = lanes * rec.model.grad_flops(rec.config)
    return (flops / rec.peak["bf16_flops"],
            lanes * lane_bytes(rec) / rec.peak["hbm_bytes_per_s"])


def reduce(rec):
    t = program_trace.of(rec)
    if t is None:
        return None
    lanes = program_trace.counter_totals(t).get("grad_evaluated")
    split = program_trace.phase_seconds(t)
    if not lanes or split is None or split["grad"] <= 0:
        return None
    return 100.0 * max(least_seconds(rec, lanes)) / split["grad"]


def describe(rec):
    t = program_trace.of(rec)
    lanes = program_trace.counter_totals(t).get("grad_evaluated") \
        if t is not None else None
    if not lanes:
        return "no grad_evaluated counter"
    tf, tb = least_seconds(rec, lanes)
    return (f"{lanes} gradient lanes evaluated: FLOPs {tf:.6f} s, bytes "
            f"{tb:.6f} s at the peaks")
