"""Whole step: the model's forward and backward FLOPs for every gradient lane
the window applied, over the window's seconds times the chip's bf16 peak.
The mixing is simulated communication and is left out (it counts in
``event_roofline``)."""


def reduce(rec):
    if not len(rec.counts) or rec.window_s <= 0:
        return None
    flops = float(rec.counts[:, 1].sum()) * rec.model.grad_flops(rec.config)
    return 100.0 * flops / (rec.window_s * rec.peak["bf16_flops"])
