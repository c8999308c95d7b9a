"""Runner layer (core/runner.py): host microseconds inside ``runner:run``
and inside none of its child spans (``runner:gen``/``pack``/``eval``/
``drain``, ``dispatch:*``), per event of the window: the runner's own
bookkeeping between them.  ``describe`` logs the device's idle time by the
innermost program span open (``chipbench/program_trace.py``)."""
from chipbench import program_trace


def reduce(rec):
    t = program_trace.of(rec)
    if t is None or not rec.events or not program_trace.has_program_spans(t):
        return None
    return program_trace.run_self_seconds(t) / rec.events * 1e6


def describe(rec):
    t = program_trace.of(rec)
    if t is None:
        return "no program spans"
    idle = sorted(program_trace.idle_by_program_span(t).items(),
                  key=lambda kv: -kv[1][0])
    return "device idle by innermost program span: " + ", ".join(
        f"{k} x{n} {ns * 1e-9:.6f} s" for k, (ns, n) in idle)
