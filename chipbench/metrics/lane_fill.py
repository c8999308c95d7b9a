"""Runner layer (core/runner.py): share of the lane slots dispatched that
carry an active worker.

Slots: the run log's ``block_dispatch`` records, ``padded`` rows times
``lanes`` (the dense scan's rows are all n workers wide).  Useful lanes:
the active workers of each event the window consumed, counted from the
packed chunks and events the scheduler handed over.
"""


def reduce(rec):
    slots = sum(d["padded"] * d.get("lanes", rec.config["n_workers"])
                for d in rec.dispatches)
    if not slots or not len(rec.counts):
        return None
    return 100.0 * float(rec.counts[:, 0].sum()) / slots
