"""Compiled block (core/aau.py scans): the least time the chip needs for the
window's events, as a share of the block programs' device time.

Per event with A active workers, g gradient lanes and r restarts, at
parameter count D and state itemsize s, the work required whatever
implements it:

- bytes: the g rows of S read for the gradients, the A rows of W read for
  the mix, the g pool batches, the A rows of W written and the r restarted
  rows of S written: (g + A + A + r)·D·s + g·batch_bytes;
- FLOPs: g gradient lanes (the model's forward and backward matmuls) and
  2·A²·D for the mix (A = n for a barrier event).

Its least time is the larger of FLOPs over the bf16 peak and bytes over
the HBM bandwidth (``peaks.json``).
"""
import numpy as np

from chipbench import trace


def per_event(rec):
    """(FLOP seconds, byte seconds) per event of the window."""
    a, g, r = (rec.counts[:, i].astype(np.float64) for i in range(3))
    D = float(rec.model.param_count(rec.config))
    s = rec.itemsize
    flops = g * rec.model.grad_flops(rec.config) + 2.0 * a * a * D
    nbytes = (g + 2.0 * a + r) * D * s + g * rec.model.batch_bytes(
        rec.config, s)
    return flops / rec.peak["bf16_flops"], nbytes / rec.peak["hbm_bytes_per_s"]


def reduce(rec):
    if rec.trace is None or not len(rec.counts):
        return None
    block = trace.module_seconds(rec.trace, trace.BLOCK_MODULES)
    if block <= 0:
        return None
    tf, tb = per_event(rec)
    return 100.0 * float(np.maximum(tf, tb).sum()) / block


def describe(rec):
    if not len(rec.counts):
        return "no events"
    tf, tb = per_event(rec)
    return (f"least time {np.maximum(tf, tb).sum():.6f} s: bytes bind "
            f"{int(np.sum(tb >= tf))} of {len(tb)} events "
            f"(bytes {tb.sum():.6f} s, FLOPs {tf.sum():.6f} s)")
