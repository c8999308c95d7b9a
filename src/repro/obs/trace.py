"""Virtual-time tracing: per-worker timelines from the drained event stream.

Every execution mode of :class:`~repro.core.runner.DecentralizedTrainer`
already materializes, per event, the event's identity tuple
— *(event clock, participating workers, per-lane raw completion clocks,
grad/restart lanes, gossip edges, copies sent)*.  :class:`TraceRecorder`
buffers exactly that identity stream and normalizes it into a
:class:`Trace`: flat numpy arrays in stream order, from which per-worker
span timelines, the event dependency DAG and the wait-blame attribution
(:mod:`repro.obs.critical_path`) are all pure host-side derivations.

Recording cost follows the drain-once discipline of the telemetry layer
(PR 8):

- ``per_event`` / ``scan`` / ``sparse_scan`` / bucketed dispatch generate
  their streams host-side (``ScheduleEvent`` objects or packed
  ``SparseEventBatch`` arrays), so recording is **zero extra device work
  and zero host drains** — the recorder slices arrays that already exist.
  All four modes record the *pre-merge, pre-pad* stream, so their traces
  are bit-identical to the per-event reference (tests/test_trace.py).

The Chrome Trace Event Format exporter (:func:`chrome_trace`) renders the
virtual-time track, loadable in Perfetto / ``chrome://tracing``: one
thread per worker; ``compute`` spans (previous restart → raw completion),
``wait`` spans (completion → event commit, i.e. straggler/lock wait), and
gossip edges as ``s``/``f`` flow arrows between the coupled workers at the
commit instant.  Wall-clock time is not this module's: the runner's
``runner:*`` / ``dispatch:*`` profiler spans and the compiled block's
phase scopes put it in the JAX profiler's trace (docs/observability.md).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence

import numpy as np

__all__ = [
    "Trace",
    "TraceRecorder",
    "chrome_trace",
]


@dataclasses.dataclass
class Trace:
    """A run's normalized event-identity stream (host numpy, stream order).

    Events are indexed ``0..E-1`` in commit order.  Lanes are the ragged
    per-event participant records, flattened with ``lane_ev`` ascending
    (lanes of one event keep the event's worker order — ascending worker
    id for every generator in this repo).  Edges are the gossip pairs the
    event mixed over, as global worker-id endpoints.
    """

    n: int
    times: np.ndarray          # (E,) f64 event commit clocks
    copies: np.ndarray         # (E,) i64 param copies sent
    lane_ev: np.ndarray        # (L,) i64 owning event index, ascending
    lane_worker: np.ndarray    # (L,) i32 global worker id
    lane_fin: np.ndarray       # (L,) f64 raw completion clock (≤ commit)
    lane_grad: np.ndarray      # (L,) bool lane fires a gradient
    lane_restart: np.ndarray   # (L,) bool lane restarts its computation
    edge_ev: np.ndarray        # (M,) i64 owning event index, ascending
    edge_src: np.ndarray       # (M,) i32
    edge_dst: np.ndarray       # (M,) i32
    algorithm: str = ""
    mode: str = ""

    @property
    def n_events(self) -> int:
        return int(self.times.shape[0])

    @property
    def n_lanes(self) -> int:
        return int(self.lane_ev.shape[0])

    def event_bounds(self) -> np.ndarray:
        """(E+1,) lane-array offsets: event k's lanes are
        ``[bounds[k], bounds[k+1])``."""
        return np.searchsorted(self.lane_ev,
                               np.arange(self.n_events + 1, dtype=np.int64))


_EMPTY_CHUNK_KEYS = (
    "times", "copies", "lane_ev", "lane_worker", "lane_fin",
    "lane_grad", "lane_restart", "edge_ev", "edge_src", "edge_dst",
)


class TraceRecorder:
    """Accumulates identity chunks; :meth:`finalize` concatenates once.

    The record methods mirror the runner's per-mode stream forms and are
    all pure host work over arrays the driving loop already holds: the
    trace path makes no device transfer at all.
    """

    def __init__(self, n: int):
        self.n = int(n)
        self._chunks: List[Dict[str, np.ndarray]] = []
        self._k = 0  # events recorded so far (global stream index base)

    # -- per-mode recording ------------------------------------------------
    def record_event(self, ev) -> None:
        """One ``ScheduleEvent`` (``per_event`` mode)."""
        m = len(ev.workers)
        fin = (np.asarray(ev.finish_lanes, dtype=np.float64)
               if ev.finish_lanes is not None
               else np.full(m, ev.time, dtype=np.float64))
        e = len(ev.edges)
        self._chunks.append({
            "times": np.array([ev.time], dtype=np.float64),
            "copies": np.array([ev.param_copies_sent], dtype=np.int64),
            "lane_ev": np.full(m, self._k, dtype=np.int64),
            "lane_worker": np.asarray(ev.workers, dtype=np.int32),
            "lane_fin": fin,
            "lane_grad": np.asarray(ev.grad_lanes, dtype=bool),
            "lane_restart": np.asarray(ev.restart_lanes, dtype=bool),
            "edge_ev": np.full(e, self._k, dtype=np.int64),
            "edge_src": np.asarray(ev.edges[:, 0], dtype=np.int32)
            if e else np.zeros(0, dtype=np.int32),
            "edge_dst": np.asarray(ev.edges[:, 1], dtype=np.int32)
            if e else np.zeros(0, dtype=np.int32),
        })
        self._k += 1

    def record_events(self, events: Sequence) -> None:
        """A buffered block of ``ScheduleEvent``s (``scan`` mode), recorded
        *before* padding — the trace never sees no-op filler events."""
        for ev in events:
            self.record_event(ev)

    def record_sparse(self, batch) -> None:
        """One packed ``SparseEventBatch`` (sparse path), pre-merge/pad."""
        workers = batch.workers
        E, _A = workers.shape
        valid = workers >= 0
        rows, cols = np.nonzero(valid)
        fin = (batch.finish[rows, cols].astype(np.float64)
               if batch.finish is not None
               else batch.times[rows].astype(np.float64))
        emask = (np.arange(batch.edges.shape[1])[None, :]
                 < batch.n_edges[:, None])
        erows, ecols = np.nonzero(emask)
        self._chunks.append({
            "times": np.asarray(batch.times, dtype=np.float64),
            "copies": np.asarray(batch.param_copies_sent, dtype=np.int64),
            "lane_ev": self._k + rows.astype(np.int64),
            "lane_worker": workers[rows, cols].astype(np.int32),
            "lane_fin": fin,
            "lane_grad": batch.grad_workers[rows, cols].astype(bool),
            "lane_restart": batch.restart_workers[rows, cols].astype(bool),
            "edge_ev": self._k + erows.astype(np.int64),
            "edge_src": batch.edges[erows, ecols, 0].astype(np.int32),
            "edge_dst": batch.edges[erows, ecols, 1].astype(np.int32),
        })
        self._k += E

    def record_chunk(self, chunk) -> None:
        """A sparse-path stream chunk: plain or bucketed.

        A bucketed chunk is recorded segment-by-segment in stream order
        (``segment_batches`` yields the maximal same-bucket runs exactly
        as the dispatcher replays them), so event indices stay the global
        stream indices.
        """
        if hasattr(chunk, "segment_batches"):
            for _b, _off, seg in chunk.segment_batches():
                self.record_sparse(seg)
        else:
            self.record_sparse(chunk)

    # -- drain -------------------------------------------------------------
    def finalize(self, algorithm: str = "", mode: str = "") -> Trace:
        cat: Dict[str, np.ndarray] = {}
        for key in _EMPTY_CHUNK_KEYS:
            parts = [c[key] for c in self._chunks]
            cat[key] = (np.concatenate(parts) if parts
                        else _empty_like_key(key))
        return Trace(n=self.n, algorithm=algorithm, mode=mode, **cat)


def _empty_like_key(key: str) -> np.ndarray:
    if key in ("times", "lane_fin"):
        return np.zeros(0, dtype=np.float64)
    if key in ("copies", "lane_ev", "edge_ev"):
        return np.zeros(0, dtype=np.int64)
    if key in ("lane_grad", "lane_restart"):
        return np.zeros(0, dtype=bool)
    return np.zeros(0, dtype=np.int32)


# -- Chrome Trace Event Format export ---------------------------------------

#: 1 unit of virtual time renders as 1 s (Chrome trace ``ts`` is in µs).
_VIRT_US = 1e6


def chrome_trace(trace: Trace) -> Dict:
    """A Chrome Trace Event Format document (JSON-serializable) of a run's
    virtual-time track: one thread per worker."""
    return {"traceEvents": _virtual_track(trace), "displayTimeUnit": "ms",
            "otherData": {"algorithm": trace.algorithm, "mode": trace.mode,
                          "n": trace.n, "events": trace.n_events}}


def _virtual_track(trace: Trace, pid: int = 0) -> List[Dict]:
    out: List[Dict] = [{
        "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
        "args": {"name": f"virtual time · {trace.algorithm or 'run'}"
                 + (f" ({trace.mode})" if trace.mode else "")},
    }]
    for w in range(trace.n):
        out.append({"name": "thread_name", "ph": "M", "pid": pid,
                    "tid": w, "args": {"name": f"worker {w}"}})
    last_restart = np.zeros(trace.n, dtype=np.float64)
    ev = trace.lane_ev
    for j in range(trace.n_lanes):
        if not trace.lane_restart[j]:
            continue
        k = int(ev[j])
        w = int(trace.lane_worker[j])
        fin = float(trace.lane_fin[j])
        t = float(trace.times[k])
        start = float(last_restart[w])
        out.append({
            "name": "compute", "cat": "compute", "ph": "X", "pid": pid,
            "tid": w, "ts": start * _VIRT_US,
            "dur": max(fin - start, 0.0) * _VIRT_US,
            "args": {"event": k},
        })
        if t > fin:
            out.append({
                "name": "wait", "cat": "wait", "ph": "X", "pid": pid,
                "tid": w, "ts": fin * _VIRT_US,
                "dur": (t - fin) * _VIRT_US,
                "args": {"event": k},
            })
        last_restart[w] = t
    for j in range(trace.edge_ev.shape[0]):
        k = int(trace.edge_ev[j])
        ts = float(trace.times[k]) * _VIRT_US
        fid = int(j) + 1
        a, b = int(trace.edge_src[j]), int(trace.edge_dst[j])
        out.append({"name": "gossip", "cat": "gossip", "ph": "s",
                    "pid": pid, "tid": a, "ts": ts, "id": fid,
                    "args": {"event": k}})
        out.append({"name": "gossip", "cat": "gossip", "ph": "f",
                    "bp": "e", "pid": pid, "tid": b, "ts": ts, "id": fid,
                    "args": {"event": k}})
    return out
