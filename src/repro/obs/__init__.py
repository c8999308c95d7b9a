"""Observability: device-resident telemetry, tracing + structured logging.

``repro.obs`` is the measurement layer the paper's argument needs at
runtime — per-worker staleness (Pathsearch's B ≤ N−1 bound, Remark 4),
gossip participation, busy/idle virtual time, and dtype-aware
communication accounting — implemented as a :class:`MetricsCarry` of
device accumulator arrays that rides the ``(W, S, y, ptr)`` scan carries
of every execution mode and is drained to host once per run (never per
event: a per-event host sync would reintroduce the dispatch overhead the
block-compiled scans removed).

On top of the aggregate counters, the tracing layer
(:mod:`repro.obs.trace` + :mod:`repro.obs.critical_path`) buffers the
full event-identity stream under the same drain-once discipline and
reconstructs per-worker virtual-time timelines (Chrome Trace Event
Format, loadable in Perfetto), the event dependency DAG's critical path,
and a per-worker wait-blame decomposition — the "straggler tax" table
that quantifies what DSGD-AAU's adaptive neighbor count saves.

Around the device core, :class:`RunLogger` writes structured JSONL run
logs (block dispatches, compile events, pool-wrap warnings) replacing
bare ``warnings.warn``.  Wall-clock time lives in the JAX profiler's
trace: the runner's ``runner:*`` / ``dispatch:*`` host spans and the
compiled blocks' phase scopes (``grad``, ``mix``, ``sparse_gather``,
``pool_select``, ``sparse_scatter``, ``s_update``).
"""
from repro.obs.critical_path import (attribute_wait, critical_path,
                                     straggler_tax)
from repro.obs.metrics import (MetricsCarry, block_metrics_update,
                               dense_metrics_update, init_metrics,
                               metrics_summary, sparse_metrics_update)
from repro.obs.runlog import RunLogger
from repro.obs.trace import Trace, TraceRecorder, chrome_trace

__all__ = [
    "MetricsCarry", "RunLogger", "Trace", "TraceRecorder",
    "attribute_wait", "block_metrics_update", "chrome_trace",
    "critical_path", "dense_metrics_update", "init_metrics",
    "metrics_summary", "sparse_metrics_update", "straggler_tax",
]
