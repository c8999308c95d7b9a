"""Runner layer (core/runner.py): share of the gradient lanes the block
programs evaluate that an event needs: ``RunCounters.grad`` ÷
``RunCounters.grad_evaluated``, from the metadata of each ``runner:run``
span (``chipbench/program_trace.py``).  The dense scan evaluates all n
lanes every row, padded rows included; a sparse block its rung's lanes
for each step it runs."""
from chipbench import program_trace


def reduce(rec):
    t = program_trace.of(rec)
    if t is None:
        return None
    c = program_trace.counter_totals(t)
    if not c.get("grad_evaluated"):
        return None
    return 100.0 * c["grad"] / c["grad_evaluated"]
