"""Decentralized training driver: any scheduler × any model × any data.

Consumes a scheduler's event stream and advances the stacked worker state
with the updates from core/aau.py.  Records loss / accuracy versus both the
iteration counter and the *virtual wall-clock*, plus cumulative
communication, reproducing the paper's Figures 3–5 measurement protocol.

Execution model — block-compiled, mode chosen automatically by default
(``mode="auto"`` resolves to the dense ``scan`` or the active-set
``sparse_scan`` via :func:`choose_mode`, from n, the scheduler's lane
ladder and the gradient's FLOPs per byte of a worker's rows):

- The event stream is packed ``block_size`` events at a time into
  :class:`~repro.core.scheduler.EventBatch` stacked arrays and replayed on
  device through one compiled ``lax.scan`` call per block
  (``masked_gossip_scan``) — one XLA dispatch and zero host round-trips per
  E events, instead of the legacy one-dispatch-per-event interpreter.
- ``mode="sparse_scan"`` replays the same stream in active-set form
  (:class:`~repro.core.scheduler.SparseEventBatch` + ``sparse_gossip_scan``):
  each event gathers only the workers it touches, evaluates gradients for
  those lanes alone, mixes with the A×A consensus submatrix, and scatters
  back — O(A·D) per event instead of O(n²·D), the representation that makes
  paper-scale N≥256 streams affordable.  The lane width A follows the
  scheduler's ``active_buckets()`` ladder: single-bucket schedulers
  (AD-PSGD/AGP at A=2, Prague at the group size) compile one block program,
  while schedulers whose event sizes are a *distribution* (DSGD-AAU's
  finished cliques) are packed per bucket and dispatched segment-by-segment
  in stream order (``BucketedSparseEventBatch`` — see
  ``_dispatch_bucketed``), so the typical small event stops paying the
  worst-case event's padding.  Schedulers whose events are global barriers
  (sync DSGD, ``Scheduler.global_events``) automatically fall back to the
  dense scan.  The sparse block donates its carry buffers — the n-row state
  is updated in place across blocks rather than copied per dispatch.
- Per-worker batches come from a pre-drawn on-device sample pool indexed by
  a restart counter the scan carries.  By default the pool is sized from the
  first run's bound — ``max_events`` directly, or a ``max_time`` bound via a
  restarts-per-worker estimate (``2·max_time / min base time``), both capped
  at 1024 — which guarantees exact per-event sampling semantics; pass
  ``batch_pool`` to fix the size explicitly.  The pointer wraps modulo the
  pool, so runs with more restarts per worker than the pool revisit samples
  cyclically — a warning is issued once if that happens.
- Evaluation fires every ``eval_every`` events; block boundaries are snapped
  to the eval grid and truncated blocks are padded with no-op events, so a
  single compiled program serves the whole run and the recorded history
  matches the per-event path point-for-point.  Eval scalars accumulate in a
  device buffer (one ``.at[i].set`` dispatch per eval, no host sync) and are
  fetched once when the run ends.

The legacy interpreter is kept behind ``mode="per_event"`` for equivalence
testing (tests/test_event_stream.py) and as the reference semantics.  All
four modes (``auto``, ``scan``, ``sparse_scan``, ``per_event``) consume the
scheduler's exact event stream, so their runs match ``per_event``
event for event.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Callable, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aau import (build_event_scan, build_event_step,
                            build_sparse_event_scan, debiased_average)
from repro.core.scheduler import (BucketedSparseEventBatch, EventBatch,
                                  Scheduler, SparseEventBatch,
                                  merge_event_groups)
from repro.obs import RunLogger, init_metrics, metrics_summary
from repro.obs.critical_path import straggler_tax
from repro.obs.metrics import dense_metrics_update
from repro.obs.trace import TraceRecorder
from repro.utils.tree import tree_size, tree_stack


# Gradient FLOPs a lane per byte of the lane's W and S rows above which the
# gradient, not the row traffic, bounds a lane: v5e's ridge, 197 TFLOP/s ÷
# 819 GB/s ≈ 240.  The paper's 2-NN reads ≈17 (row-bound: a masked lane of
# the dense scan costs little beside the gathers it saves); ResNet-18 ≈1,040
# (compute-bound: every masked or padded lane costs a whole gradient).
COMPUTE_BOUND_FLOPS_PER_BYTE = 240.0


def choose_mode(n: int, buckets: Tuple[int, ...],
                global_events: bool = False,
                flops_per_byte: Optional[float] = None) -> str:
    """``mode="auto"``'s dispatch decision: dense ``scan`` vs ``sparse_scan``.

    - Barrier streams (``global_events``) take the dense scan: every event
      touches all n workers, so gathering them is pure overhead.
    - A compute-bound gradient (``flops_per_byte``, the gradient's FLOPs a
      lane over the bytes of one worker's W and S rows, above
      ``COMPUTE_BOUND_FLOPS_PER_BYTE``) takes the sparse scan at any n: the
      dense scan evaluates all n lanes every event, the sparse one only the
      lanes of its rung.
    - Otherwise a lane costs about its rows, and the dense scan's single
      fixed-shape block wins until n outgrows the narrowest rung: the
      sparse scan is taken above ``max(16, 4 · buckets[0])`` workers.
    """
    if global_events:
        return "scan"
    if flops_per_byte is not None \
            and flops_per_byte > COMPUTE_BOUND_FLOPS_PER_BYTE:
        return "sparse_scan"
    if n <= max(16, 4 * buckets[0]):
        return "scan"
    return "sparse_scan"


def grad_cost(loss_fn: Callable, row, batch) -> Tuple[float, int]:
    """``(F, R)`` of one gradient lane: F the FLOPs of ``jax.grad(loss_fn)``
    at one worker's parameter row and batch (pytrees of arrays or shapes),
    as XLA's cost analysis counts them; R the bytes of that worker's W row
    plus its S row.  A backend that hosts XLA itself analyses the lowered
    module; a plugin backend (the TPU) analyses compiled modules only."""
    lowered = jax.jit(jax.grad(loss_fn)).lower(row, batch)
    cost = lowered.cost_analysis()
    if cost is None:
        cost = lowered.compile().cost_analysis()
    row_bytes = sum(x.size * jnp.dtype(x.dtype).itemsize
                    for x in jax.tree.leaves(row))
    return float(cost["flops"]), 2 * int(row_bytes)


@dataclasses.dataclass
class HistoryPoint:
    k: int
    time: float
    loss: float
    metric: float
    comm_param_copies: int
    n_active_mean: float


@dataclasses.dataclass
class RunResult:
    algorithm: str
    history: List[HistoryPoint]
    final_loss: float
    final_metric: float
    total_events: int
    total_time: float
    total_comm_copies: int
    param_count: int
    # Scalar width of the trainer's dtype policy (bf16 runs send 2-byte
    # scalars, not the old hardcoded 4) and, when the trainer ran with
    # telemetry=True, the drained device-counter summary
    # (repro.obs.metrics.metrics_summary).
    bytes_per_scalar: int = 4
    telemetry: Optional[Dict] = None
    # With trace=True, the wait-blame / critical-path summary
    # (repro.obs.critical_path.straggler_tax) of the run's recorded
    # event-identity stream; the full Trace stays on the trainer as
    # ``trainer.last_trace`` (export it with repro.obs.chrome_trace).
    trace: Optional[Dict] = None

    def comm_bytes(self, bytes_per_scalar: Optional[int] = None) -> int:
        bps = self.bytes_per_scalar if bytes_per_scalar is None else bytes_per_scalar
        return self.total_comm_copies * self.param_count * bps

    def time_to_loss(self, target: float) -> Optional[float]:
        for p in self.history:
            if p.loss <= target:
                return p.time
        return None

    def iters_to_loss(self, target: float) -> Optional[int]:
        for p in self.history:
            if p.loss <= target:
                return p.k
        return None


@dataclasses.dataclass
class RunCounters:
    """The runner's work since the trainer was built, always on.

    Summed once per chunk or per block with numpy sums, never per lane.
    ``run()`` attaches each run's share (:meth:`since`) to its
    ``runner:run`` profiler span as span metadata, so a profiler trace
    carries the counts beside the spans.  ``per_event`` mode dispatches no
    block programs (``blocks``/``rows`` stay 0).

    ``grad_evaluated`` counts the gradient lanes the programs evaluate,
    masked and padding lanes included: n a dense scan row (padded rows
    too) or ``per_event`` event, lanes × steps of each sparse block (its
    padded rows skip the update).
    """
    events: int = 0       # events consumed
    blocks: int = 0       # block programs dispatched
    rows: int = 0         # scan rows dispatched, padding included
    active: int = 0       # active lanes of the consumed events
    grad: int = 0         # gradient lanes of the consumed events
    restarts: int = 0     # restarted lanes of the consumed events
    grad_evaluated: int = 0   # gradient lanes the programs evaluate

    def add(self, **counts: int) -> None:
        for k, v in counts.items():
            setattr(self, k, getattr(self, k) + int(v))

    def since(self, before: "RunCounters") -> Dict[str, int]:
        return {f.name: getattr(self, f.name) - getattr(before, f.name)
                for f in dataclasses.fields(self)}


def _lane_counts(chunk) -> Dict[str, int]:
    """Active, gradient and restarted lanes of a packed sparse chunk."""
    parts = (chunk.batches if isinstance(chunk, BucketedSparseEventBatch)
             else (chunk,))
    parts = [b for b in parts if b is not None]
    return {"active": sum(int(b.n_workers.sum()) for b in parts),
            "grad": sum(int(b.grad_workers.sum()) for b in parts),
            "restarts": sum(int(b.restart_workers.sum()) for b in parts)}


_span = jax.profiler.TraceAnnotation


class DecentralizedTrainer:
    """Runs one algorithm on one model/dataset under one straggler model."""

    def __init__(
        self,
        scheduler: Scheduler,
        loss_fn: Callable,                  # loss_fn(params, batch) -> scalar
        init_params_fn: Callable,           # init_params_fn(rng) -> pytree
        worker_batch_fn: Callable,          # worker_batch_fn(worker, step) -> batch pytree
        eval_batch,                         # held-out batch for the global model
        eval_fn: Optional[Callable] = None, # eval_fn(params, batch) -> (loss, metric)
        eta0: float = 0.1,
        eta_decay: float = 1.0,             # paper uses η(k) = η₀ · δᵏ with δ=0.95 per *round*
        eta_decay_every: int = 1,
        seed: int = 0,
        use_kernel: bool = False,
        same_init: bool = True,
        mode: str = "auto",                 # "auto" (choose_mode picks scan
                                            # vs sparse_scan from n, the
                                            # scheduler's lane ladder and
                                            # the gradient's cost) |
                                            # "scan" | "sparse_scan" |
                                            # "per_event"
        block_size: int = 32,               # events per compiled scan call
        batch_pool: Optional[int] = None,   # pre-drawn samples per worker
                                            # (scan mode; None = auto from the
                                            # first run's max_events, cap 1024)
        dtype: str = "float32",             # worker-state dtype policy:
                                            # "float32" | "bfloat16" — applied
                                            # to stacked params, snapshots and
                                            # sample pools (float leaves only)
        events_per_step: Optional[int] = None,
                                            # sparse path: merge up to K
                                            # conflict-free events per scan
                                            # step (None = auto per bucket,
                                            # min(64, n) lanes/step; 1
                                            # disables)
        telemetry: bool = False,            # device-resident per-worker
                                            # counters (repro.obs): drained
                                            # once per run into
                                            # RunResult.telemetry
        trace: bool = False,                # record the event-identity
                                            # stream (repro.obs.trace):
                                            # wait-blame summary in
                                            # RunResult.trace, full Trace
                                            # in trainer.last_trace —
                                            # host-side recording
        run_log: Optional[Union[str, object]] = None,
                                            # JSONL structured run log: a
                                            # path, a file-like object, or
                                            # None (disabled)
        sanitize: Optional[bool] = None,    # wrap runs in repro.check.runtime
                                            # .sanitized() — leak checking +
                                            # d2h transfer guard (None = the
                                            # REPRO_SANITIZE env flag)
    ):
        if mode not in ("scan", "sparse_scan", "per_event", "auto"):
            raise ValueError(
                "mode must be 'scan', 'sparse_scan', 'per_event' or 'auto', "
                f"got {mode!r}")
        self.dtype = jnp.dtype(dtype)
        if not jnp.issubdtype(self.dtype, jnp.floating):
            raise ValueError(f"dtype policy must be a float dtype, got {dtype!r}")
        if mode == "sparse_scan" and scheduler.global_events:
            # Barrier streams (sync DSGD) touch all n workers every event:
            # the gather-compute-scatter path would gather everything anyway,
            # so fall back to the dense scan automatically.
            mode = "scan"
        self.scheduler = scheduler
        self.n = scheduler.n
        self.loss_fn = loss_fn
        self.eval_fn = eval_fn or (lambda p, b: (loss_fn(p, b), 0.0))
        self.worker_batch_fn = worker_batch_fn
        self.eval_batch = eval_batch
        self.eta0, self.eta_decay, self.eta_decay_every = eta0, eta_decay, eta_decay_every
        self.use_kernel = use_kernel
        self.block_size = max(1, block_size)
        self.batch_pool = batch_pool if batch_pool is None else max(1, batch_pool)
        self.events_per_step = events_per_step
        self.telemetry = bool(telemetry)
        self.trace = bool(trace)
        if sanitize is None:
            from repro.check.runtime import sanitize_enabled
            sanitize = sanitize_enabled()
        self.sanitize = bool(sanitize)
        self._log = RunLogger(run_log)
        rng = jax.random.PRNGKey(seed)
        if same_init:
            p0 = init_params_fn(rng)
            params = [p0] * self.n
        else:
            params = [init_params_fn(k) for k in jax.random.split(rng, self.n)]
        # The dtype policy casts the stacked worker state (and, below, the
        # on-device sample pools): the gossip kernels and the scan updates
        # already accept bf16 leaves, so bf16 halves simulator memory and
        # doubles effective MXU throughput at paper scale.  Push-sum weights
        # y stay float32 — they are n scalars and de-biasing divides by them.
        self.W = self._cast(tree_stack(params))
        self.S = self.W
        self.y = jnp.ones((self.n,), dtype=jnp.float32)
        self.param_count = tree_size(params[0])
        # (F, R) of one gradient lane (grad_cost), read under mode="auto"
        # for streams whose path it can decide
        self.grad_cost = None
        if mode == "auto":
            if not scheduler.global_events:
                self.grad_cost = grad_cost(
                    loss_fn, jax.eval_shape(self._cast, params[0]),
                    jax.eval_shape(self._cast, worker_batch_fn(0, 0)))
            mode = choose_mode(
                self.n, scheduler.active_buckets(), scheduler.global_events,
                None if self.grad_cost is None
                else self.grad_cost[0] / self.grad_cost[1])
        self.mode = mode
        self._log_lane_policy()
        self._eval = jax.jit(self.eval_fn)
        # Per-mode state built lazily on first use (avoids tracing both paths).
        self._step = None           # per-event jitted update
        self._batches = None        # per-event current batch stack
        self._draw_count = np.zeros(self.n, dtype=np.int64)
        self._scan = None           # block-compiled jitted update (dense)
        self._sparse = None         # block-compiled jitted update (active-set)
        self._pools = None          # (n, batch_pool, ...) on-device sample pools
        self._ptr = None            # (n,) int32 restart counters
        self._eval_accum = None     # jitted eval → device-buffer accumulator
        self._metrics = None        # MetricsCarry device accumulators
        self._metrics_step = None   # per-event jitted dense metrics update
        self._bucket_occ = None     # host per-rung occupancy aggregation
        self._trace = None          # TraceRecorder (host-side buffers)
        self.last_trace = None      # finalized Trace of the latest run
        self.counters = RunCounters()

    def _log_lane_policy(self) -> None:
        """One ``lane_policy`` line on the run log: the resolved mode, the
        gradient's cost that chose it, and the gradient lanes a block step
        evaluates at each rung (n for the dense scan, K·A for a sparse rung
        that merges K events a step)."""
        F, R = self.grad_cost or (None, None)
        if self.mode == "sparse_scan":
            budget = [[A, A * self._events_per_step(A)]
                      for A in self.scheduler.active_buckets()]
        elif self.mode == "scan":
            budget = [[self.n, self.n]]
        else:
            budget = None
        self._log.log("lane_policy", mode=self.mode, grad_flops=F,
                      row_bytes=R, flops_per_byte=None if F is None else F / R,
                      lanes_per_step=budget)

    def _cast(self, tree):
        """Apply the worker-state dtype policy to a pytree's float leaves."""
        dt = self.dtype
        return jax.tree.map(
            lambda x: x.astype(dt)
            if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating) else x,
            tree)

    # one compiled call per reset: plain init_metrics is 11 separate device
    # puts, a measurable per-run fixed cost on the overhead-asserted paths
    _init_metrics = staticmethod(jax.jit(init_metrics, static_argnums=0))

    def _ensure_metrics(self):
        if self.telemetry and self._metrics is None:
            self._metrics = self._init_metrics(self.n)
            if self._bucket_occ is None:
                self._bucket_occ = {}

    # -- legacy per-event state -------------------------------------------
    def _ensure_per_event(self):
        if self._step is None:
            self._log.log("compile", key="per_event")
            self._step = build_event_step(self.loss_fn, use_kernel=self.use_kernel)
            self._batches = self._cast(
                tree_stack([self._draw(i) for i in range(self.n)]))
            if self.telemetry:
                self._metrics_step = jax.jit(dense_metrics_update)
        self._ensure_metrics()

    def _draw(self, worker: int):
        b = self.worker_batch_fn(worker, int(self._draw_count[worker]))
        self._draw_count[worker] += 1
        return b

    def _refresh_batches(self, idx: np.ndarray) -> None:
        """Redraw the batches of the workers in ``idx`` (restarted lanes)."""
        if len(idx) == 0:
            return
        new = {int(i): self._draw(int(i)) for i in idx}

        def upd(leaf_batches, getter):
            arr = np.array(leaf_batches)  # host copy (jax buffers are read-only)
            for i, b in new.items():
                arr[i] = np.asarray(getter(b))
            return jnp.asarray(arr)

        leaves, treedef = jax.tree.flatten(self._batches)
        new_leaves = []
        for li, leaf in enumerate(leaves):
            new_leaves.append(upd(leaf, lambda b, li=li: jax.tree.leaves(b)[li]))
        self._batches = jax.tree.unflatten(treedef, new_leaves)

    # -- scan-mode state ---------------------------------------------------
    def _estimate_restarts(self, max_time: float) -> int:
        """Upper-bound restarts/worker for a ``max_time``-bounded run.

        A worker restarts at most once per completed local computation, and
        the fastest worker's completions take at least its base time shrunk
        by jitter's low tail — 2× headroom covers both that tail and any
        scheduler that restarts on someone else's clock.  Without this a
        long ``max_time`` run fell back to a 64-draw pool and silently
        revisited samples (the wrap warning below remains the backstop).
        """
        base = np.min(self.scheduler.sampler.base)
        return int(np.ceil(2.0 * max_time / max(float(base), 1e-9)))

    def _ensure_pools(self, max_events: Optional[int] = None,
                      max_time: Optional[float] = None):
        # Restarts per worker are bounded by total events, so a pool of
        # max_events draws never wraps; a max_time bound is converted into
        # a restart estimate; explicit batch_pool overrides both.
        if self.batch_pool is not None:
            pool_len = self.batch_pool
        elif max_events:
            pool_len = min(max_events, 1024)
        elif max_time is not None:
            pool_len = max(64, min(self._estimate_restarts(max_time), 1024))
        else:
            pool_len = 64
        if self._pools is not None and self._pool_len >= pool_len:
            return
        # pool[i, s] = the s-th batch worker i would draw — identical to
        # the legacy path's draw sequence, moved on-device ahead of time.
        # Growing an auto-sized pool (e.g. warmup() built 64, a later
        # run(max_events=...) needs more) is safe mid-stream: the draw at
        # (w, s) is a pure function of its arguments, so a larger pool keeps
        # the prefix already consumed and the carried ``ptr`` stays valid
        # (the block jit re-traces once for the new pool shape).
        self._pool_len = pool_len
        self._pools = self._cast(tree_stack([
            tree_stack([self.worker_batch_fn(w, s)
                        for s in range(pool_len)])
            for w in range(self.n)]))
        if self._ptr is None:
            self._ptr = jnp.zeros((self.n,), dtype=jnp.int32)

    def _ensure_scan(self, max_events: Optional[int] = None,
                     max_time: Optional[float] = None):
        if self._scan is None:
            self._log.log("compile", key="scan", telemetry=self.telemetry)
            self._scan = build_event_scan(self.loss_fn,
                                          use_kernel=self.use_kernel,
                                          telemetry=self.telemetry)
        self._ensure_metrics()
        self._ensure_pools(max_events, max_time)

    def _ensure_sparse(self, max_events: Optional[int] = None,
                       max_time: Optional[float] = None):
        if self._sparse is None:
            self._log.log("compile", key="sparse_scan", telemetry=self.telemetry)
            self._sparse = build_sparse_event_scan(
                self.loss_fn, use_kernel=self.use_kernel,
                telemetry=self.telemetry)
            # The sparse block donates its (W, S, y, ptr) carry arguments.
            # With same_init the snapshot stack S still *is* W (one shared
            # buffer) until the first update — donating that buffer through
            # two arguments is an XLA error, so break the alias once here.
            if any(w is s for w, s in zip(jax.tree.leaves(self.W),
                                          jax.tree.leaves(self.S))):
                self.S = jax.tree.map(jnp.array, self.S)
        self._ensure_metrics()
        self._ensure_pools(max_events, max_time)

    def _etas_for(self, batch_E: int, valid_E: int, rounds: int) -> np.ndarray:
        etas = self.eta0 * self.eta_decay ** (
            (rounds + np.arange(batch_E)) // self.eta_decay_every)
        if valid_E < batch_E:
            etas[valid_E:] = 0.0  # padded no-op events (masks all-False)
        return etas

    def _dispatch_block(self, batch: EventBatch, rounds: int,
                        target: Optional[int] = None) -> int:
        """One compiled call: pad to the block shape, advance (W, S, y, ptr).

        The host work that makes the call's arguments runs under the
        ``runner:pack`` span, the enqueue alone under ``dispatch:scan``.
        Returns the scan rows dispatched, padding included.
        """
        with _span("runner:pack"):
            xs, rows = self._pack_block(batch, rounds, target)
        self._log.log("block_dispatch", mode="scan", events=batch.E,
                      padded=rows, rounds=rounds)
        with _span("dispatch:scan"):
            if self.telemetry:
                (self.W, self.S, self.y, self._ptr, self._metrics) = \
                    self._scan(self.W, self.S, self.y, self._ptr,
                               self._metrics, self._pools, *xs)
            else:
                self.W, self.S, self.y, self._ptr = self._scan(
                    self.W, self.S, self.y, self._ptr, self._pools, *xs)
        return rows

    def _pack_block(self, batch: EventBatch, rounds: int,
                    target: Optional[int]) -> Tuple[tuple, int]:
        """The dense block's event arrays on the device, and its rows."""
        E = batch.E
        if target is None:
            target = self.block_size
        if E < target:
            batch = batch.pad_to(target)
        etas = self._etas_for(batch.E, E, rounds)
        xs = (jnp.asarray(batch.P, dtype=jnp.float32),
              jnp.asarray(batch.grad_workers),
              jnp.asarray(batch.restart_workers),
              jnp.asarray(etas, dtype=jnp.float32))
        if not self.telemetry:
            return xs, batch.E
        Ep = batch.E
        fin = batch.finish if batch.finish is not None \
            else np.broadcast_to(batch.times[:, None], (Ep, self.n))
        # casts happen host-side: a cross-dtype jnp.asarray would pay a
        # per-block convert_element_type dispatch
        return xs + (
            jnp.asarray(np.asarray(batch.times, dtype=np.float32)),
            jnp.asarray(np.asarray(fin, dtype=np.float32)),
            jnp.asarray(np.arange(rounds, rounds + Ep, dtype=np.int32)),
            jnp.asarray(np.asarray(batch.param_copies_sent,
                                   dtype=np.int32)),
        ), Ep

    def _pack_sparse_block(self, batch: SparseEventBatch, rounds: int,
                           target: int,
                           lane_off: Optional[np.ndarray] = None,
                           lane_ts: Optional[np.ndarray] = None) -> tuple:
        """One sparse block's event arrays on the device: O(A·D) per event.

        ``lane_off`` marks ``batch`` as the output of ``merge_event_groups``:
        a (E, A) int array of absolute source-event offsets per lane, from
        which per-*lane* step sizes are built (each merged lane keeps the η
        its source event would have used — the decay schedule is indexed by
        event, not by scan step, so merging stays bit-exact).  ``lane_ts``
        (telemetry, merged path only) carries the matching per-lane source
        event clocks, gathered the same way.  Returns ``(xs, log fields)``.
        """
        E = batch.E
        if E < target:
            batch = batch.pad_to(target)
        if lane_off is None:
            etas = self._etas_for(batch.E, E, rounds)
        else:
            etas = np.zeros((batch.E, batch.A))
            etas[:E] = self.eta0 * self.eta_decay ** (
                (rounds + lane_off) // self.eta_decay_every)
        xs = (jnp.asarray(batch.workers),
              jnp.asarray(batch.P_sub, dtype=jnp.float32),
              jnp.asarray(batch.grad_workers),
              jnp.asarray(batch.restart_workers),
              jnp.asarray(etas, dtype=jnp.float32))
        log = dict(events=E, padded=batch.E, lanes=batch.A, rounds=rounds,
                   merged=lane_off is not None)
        if not self.telemetry:
            return xs, log
        Ep, A = batch.E, batch.A
        # Per-lane event indices and clocks: every lane of an unmerged row
        # shares the row's event; a merged row's lanes keep their source
        # event's index/clock so staleness and mix ages stay bit-exact
        # against the unmerged replay.  Padded rows are skipped wholesale
        # by the scan body's cond (workers[0] < 0), so their values are
        # never read.
        if lane_off is None:
            ks = np.broadcast_to(
                np.arange(rounds, rounds + Ep, dtype=np.int32)[:, None],
                (Ep, A))
            ts = np.broadcast_to(batch.times[:, None], (Ep, A))
        else:
            ks = np.zeros((Ep, A), dtype=np.int32)
            ks[:E] = rounds + lane_off
            ts = np.zeros((Ep, A))
            ts[:E] = lane_ts
        fin = batch.finish if batch.finish is not None else ts
        # casts happen host-side: a cross-dtype jnp.asarray would pay a
        # per-block convert_element_type dispatch
        return xs + (
            jnp.asarray(np.asarray(ts, dtype=np.float32)),
            jnp.asarray(np.asarray(fin, dtype=np.float32)),
            jnp.asarray(ks),
            jnp.asarray(np.asarray(batch.param_copies_sent,
                                   dtype=np.int32)),
        ), log

    def _launch_sparse(self, blocks: List[tuple]) -> Tuple[int, int, int]:
        """Enqueue packed sparse blocks in order, each alone under the
        ``dispatch:sparse_scan`` span; returns (blocks, scan rows, gradient
        lanes evaluated: lanes × unpadded rows)."""
        rows = lanes = 0
        for xs, log in blocks:
            self._log.log("block_dispatch", mode="sparse_scan", **log)
            rows += log["padded"]
            lanes += log["events"] * log["lanes"]
            with _span("dispatch:sparse_scan"):
                if self.telemetry:
                    (self.W, self.S, self.y, self._ptr,
                     self._metrics) = self._sparse(
                        self.W, self.S, self.y, self._ptr, self._metrics,
                        self._pools, *xs)
                else:
                    self.W, self.S, self.y, self._ptr = self._sparse(
                        self.W, self.S, self.y, self._ptr, self._pools,
                        *xs)
        return len(blocks), rows, lanes

    def _events_per_step(self, A: int) -> int:
        """Events merged per scan step at lane width ``A`` (the blocking K).

        K·A is the step's *lane budget*: ``merge_event_groups`` packs
        conflict-free events compactly into it, and every lane of it is a
        gradient the step evaluates, real or padding.  The budget is
        min(64, n) lanes: enough to amortize a scan step's fixed cost over
        several narrow events, and never wider than the n workers a step
        could touch, so a rung as wide as n stays unmerged.  K is clipped
        to [1, 16]; ``events_per_step`` overrides it.
        """
        if self.events_per_step is not None:
            return max(1, int(self.events_per_step))
        return int(np.clip(min(64, self.n) // max(A, 1), 1, 16))

    def _dispatch_sparse_chunk(self, batch: SparseEventBatch, rounds: int,
                               cap: int) -> Tuple[int, int, int]:
        """Advance the carry through one same-bucket packed chunk.

        All of the chunk's blocks are packed under one ``runner:pack``
        span, then enqueued in order.  Returns ``_launch_sparse``'s counts.
        """
        with _span("runner:pack"):
            blocks = self._pack_sparse_chunk(batch, rounds, cap)
        return self._launch_sparse(blocks)

    def _pack_sparse_chunk(self, batch: SparseEventBatch, rounds: int,
                           cap: int) -> List[tuple]:
        """The packed blocks of one same-bucket chunk, in stream order.

        With K > 1 the chunk is first folded by ``merge_event_groups`` —
        runs of ≤K consecutive events with pairwise-disjoint worker sets
        become single block-diagonal scan steps — then chopped into
        fixed-length ``cap // K`` dispatches (the merged path compiles its
        own (E, K·A) block shape, distinct from the unmerged one).
        """
        K = self._events_per_step(batch.A)
        if K <= 1:
            return [self._pack_sparse_block(batch.slice(start, stop),
                                            rounds + start, cap)
                    for start, stop in _cuts(batch.E, cap)]
        merged, lane_off = merge_event_groups(batch, K)
        g_cap = max(1, cap // K)
        # telemetry: lane-level source-event clocks, gathered once per chunk
        lane_ts = batch.times[lane_off] if self.telemetry else None
        # lane_off carries *absolute* source offsets within ``batch``, so
        # ``rounds`` stays the chunk base across slices.
        return [self._pack_sparse_block(
                    merged.slice(start, stop), rounds, g_cap,
                    lane_off=lane_off[start:stop],
                    lane_ts=None if lane_ts is None else lane_ts[start:stop])
                for start, stop in _cuts(merged.E, g_cap)]

    # Base chunk length for the narrowest bucket of a multi-bucket ladder.
    # Chunks must be short: a DSGD-AAU stream switches buckets every ~4
    # events at N=256, so a chunk longer than the typical same-bucket
    # segment just pads with no-op events.  They must also be *one fixed
    # shape per bucket*: each distinct (A, E) pair compiles its own block
    # program, and with segment-length-sized shapes the tracing cost (tens
    # of XLA compiles) swamped the event stream it was meant to speed up.
    _CHUNK_QUANTUM = 32

    @staticmethod
    def _bucket_cap(buckets: Tuple[int, ...], b: int, target: int) -> int:
        """Fixed chunk length for bucket ``b`` of the ladder.

        Scaled inversely to the *square* of the lane-width ratio —
        ``quantum · (buckets[0] / buckets[b])²`` — which tracks both costs
        that grow with lane width: the O(A²·D) mix per event and, more
        importantly on a fragmented stream, the no-op padding.  Measured
        DSGD-AAU streams at N=256 spend ~93% of events in the first rung in
        ~15-event runs, but the wide rungs fire in 1–2-event bursts — a
        linear cap (quantum·b0/A) padded those bursts 4–8× with wide-lane
        no-ops and cost more than the dense fallback it replaced; the
        quadratic cap pins wide-bucket chunks at 1–2 events (≈ their true
        burst length) and lifted bucketed throughput from ~3× to ~5–6× the
        static-bound path.
        """
        quantum = min(target, DecentralizedTrainer._CHUNK_QUANTUM)
        return max(1, (quantum * buckets[0] * buckets[0])
                   // (buckets[b] * buckets[b]))

    def _dispatch_bucketed(self, bucketed: BucketedSparseEventBatch,
                           rounds: int, target: int) -> Tuple[int, int, int]:
        """Advance the carry through a bucketed block, in stream order.

        State updates are sequential, so buckets are *not* replayed whole:
        the stream's maximal same-bucket runs (``segment_batches`` — each
        contiguous both in the stream and in its bucket's packed arrays)
        are dispatched in order, every segment chopped into fixed-length
        chunks at its bucket's lane width (short chunks padded with no-op
        events — ``SparseEventBatch.pad_to`` — to keep one compiled shape
        per bucket).  Events therefore execute in exactly the per-event
        order — the bucketed path's results are bit-exact against the dense
        scan — while a typical DSGD-AAU event pays for ~16 lanes instead
        of n.  Every segment is packed under one ``runner:pack`` span before
        the first block is enqueued.  Returns ``_launch_sparse``'s counts.
        """
        with _span("runner:pack"):
            blocks = [
                blk for b, off, seg in bucketed.segment_batches()
                for blk in self._pack_sparse_chunk(
                    seg, rounds + off,
                    self._bucket_cap(bucketed.buckets, b, target))]
        return self._launch_sparse(blocks)

    def _accum_occupancy(self, rows: List[Dict[str, float]]) -> None:
        """Fold one chunk's per-rung packing stats into the run aggregate."""
        if self._bucket_occ is None:
            self._bucket_occ = {}
        for r in rows:
            if not r["events"]:
                continue
            acc = self._bucket_occ.setdefault(int(r["A"]),
                                              {"events": 0, "lanes": 0.0})
            acc["events"] += int(r["events"])
            acc["lanes"] += float(r["lane_fill"]) * r["events"] * r["A"]

    def _telemetry_summary(self, t_end: float) -> Optional[Dict]:
        """Drain the device counters once (logged before ``run_end``)."""
        if not self.telemetry or self._metrics is None:
            return None
        with _span("runner:drain"):
            summary = metrics_summary(
                self._metrics, t_end,
                n_minus_1_bound=self.scheduler.name == "dsgd_aau")
        summary["comm_bytes_per_copy"] = self.param_count * self.dtype.itemsize
        if self._bucket_occ:
            summary["bucket_occupancy"] = [
                {"A": A, "events": acc["events"],
                 "lane_fill": acc["lanes"] / (acc["events"] * A)}
                for A, acc in sorted(self._bucket_occ.items())]
        bound = summary.get("staleness_bound")
        if bound is not None:
            self._log.log("staleness_bound", **bound)
            if not bound["ok"]:
                self._log.warn_once(
                    "staleness_bound",
                    f"DSGD-AAU staleness monitor: observed max staleness "
                    f"{bound['observed_max']} exceeds the 2N-4 bound "
                    f"({bound['bound']}) induced by the B <= N-1 per-epoch "
                    "commit bound — the scheduler violated the paper's "
                    "bounded-staleness guarantee.")
        return summary

    def _trace_summary(self) -> Optional[Dict]:
        """Finalize the identity stream, recorded host-side as it ran."""
        if not self.trace or self._trace is None:
            return None
        tr = self._trace.finalize(algorithm=self.scheduler.name,
                                  mode=self.mode)
        self.last_trace = tr
        return straggler_tax(tr)

    def warmup(self) -> None:
        """Compile this trainer's update and eval with no-op dispatches.

        State is left exactly unchanged (identity P, all-False masks — η is
        traced data, so its warmup values don't matter), letting benchmarks
        separate compile time from steady-state throughput.  In the scan
        modes the compiled block shape is ``block_size``; a subsequent run
        whose ``eval_every`` is smaller re-traces once at the smaller
        shape, and an auto-sized batch pool built here at the 64-draw
        default grows (one more re-trace) if the run's ``max_events``
        needs more — pass ``batch_pool`` explicitly to pin both.
        """
        n = self.n
        if self.mode == "sparse_scan":
            self._ensure_sparse()
            buckets = self.scheduler.active_buckets()
            ebound = self.scheduler.edge_bound()
            if len(buckets) > 1:
                # one compiled block program per bucket, at the chunk cap
                # (and merge width) its full segments will dispatch with
                for b, A in enumerate(buckets):
                    cap = self._bucket_cap(buckets, b, self.block_size)
                    noop = SparseEventBatch.from_events(
                        [_identity_event(n)], active_bound=A,
                        edge_bound=min(ebound, max(1, A * (A - 1) // 2)))
                    self._dispatch_sparse_chunk(noop, 0, cap)
            else:
                noop = SparseEventBatch.from_events(
                    [_identity_event(n)],
                    active_bound=self.scheduler.active_bound(),
                    edge_bound=ebound)
                self._dispatch_sparse_chunk(noop, 0, self.block_size)
            self.y.block_until_ready()
            self._warm_eval()
            return
        noop = EventBatch.from_events(
            [_identity_event(n)], edge_bound=1).pad_to(
                self.block_size if self.mode == "scan" else 1)
        if self.mode == "scan":
            self._ensure_scan()
            self._dispatch_block(noop, rounds=0)
            self.y.block_until_ready()
            self._warm_eval()
            return
        self._ensure_per_event()
        ev = noop.to_events()[0]
        self.W, self.S, self.y = self._step(
            self.W, self.S, self.y, self._batches,
            jnp.asarray(ev.P, dtype=jnp.float32),
            jnp.asarray(ev.grad_workers), jnp.asarray(ev.restart_workers),
            jnp.float32(0.0),
        )
        self.y.block_until_ready()
        self._eval_now()

    def _warm_eval(self) -> None:
        """Compile the scan modes' history eval (state left untouched)."""
        self._ensure_eval_accum()
        self._eval_accum(self.W, self.y, self.eval_batch).block_until_ready()

    # -- driving loop ------------------------------------------------------
    def run(
        self,
        max_events: Optional[int] = None,
        max_time: Optional[float] = None,
        eval_every: int = 10,
    ) -> RunResult:
        assert max_events or max_time, "bound the run by events or virtual time"
        with _span("runner:run") as span:
            if self.telemetry:
                # fresh counters per run: event indices (the staleness clock)
                # restart at 0 every run, so carried-over restart marks from a
                # previous run would alias as negative staleness
                self._metrics = self._init_metrics(self.n)
                self._bucket_occ = {}
            if self.trace:
                self._trace = TraceRecorder(self.n)
            self._log.log("run_start", algorithm=self.scheduler.name, n=self.n,
                          mode=self.mode, max_events=max_events,
                          max_time=max_time, eval_every=eval_every,
                          dtype=str(self.dtype), telemetry=self.telemetry,
                          trace=self.trace)
            if getattr(self.scheduler, "horizon", None):
                self._log.warn_once(
                    "rng_order",
                    "event stream is a different-but-deterministic RNG-order "
                    "realization (horizon batching): distributionally "
                    "identical to the exact per-event stream, not "
                    "bit-identical to it.", warn=False)
            drive = {"sparse_scan": self._run_sparse_stream,
                     "scan": self._run_scan}.get(self.mode, self._run_per_event)
            before = dataclasses.replace(self.counters)
            with self._maybe_sanitized():
                res = drive(max_events, max_time, eval_every)
            span.set_metadata(**self.counters.since(before))
        return res

    def _maybe_sanitized(self):
        """The runtime sanitizer context when enabled, else a no-op.

        Wraps the whole driving loop: every trace runs under
        ``jax.checking_leaks`` and every implicit device→host transfer
        (the ~100 µs/event sync class) raises instead of silently blocking
        — the runner's explicit per-drain ``jax.device_get`` stays legal.
        """
        if not self.sanitize:
            return contextlib.nullcontext()
        from repro.check.runtime import sanitized
        self._log.log("sanitize", check_leaks=True, transfer_guard="disallow")
        return sanitized()

    def _run_per_event(self, max_events, max_time, eval_every) -> RunResult:
        self._ensure_per_event()
        history: List[HistoryPoint] = []
        comm = 0
        active_sizes: List[int] = []
        t = 0.0
        k = -1
        rounds = 0
        with _span("runner:gen"):    # a new event process per run
            stream = self.scheduler.events()
        while True:
            with _span("runner:gen"):
                ev = next(stream, None)
            if ev is None:
                break
            if max_events is not None and ev.k >= max_events:
                break
            if max_time is not None and ev.time > max_time:
                break
            k, t = ev.k, ev.time
            self.counters.add(events=1, active=len(ev.workers),
                              grad=ev.grad_lanes.sum(),
                              restarts=ev.restart_lanes.sum(),
                              grad_evaluated=self.n)
            comm += ev.param_copies_sent
            active_sizes.append(ev.n_active)
            if self.trace:
                self._trace.record_event(ev)
            eta = jnp.float32(
                self.eta0 * (self.eta_decay ** (rounds // self.eta_decay_every)))
            P_dev = jnp.asarray(ev.P, dtype=jnp.float32)
            gm_dev = jnp.asarray(ev.grad_workers)
            rm_dev = jnp.asarray(ev.restart_workers)
            self.W, self.S, self.y = self._step(
                self.W, self.S, self.y, self._batches,
                P_dev, gm_dev, rm_dev, eta,
            )
            if self.telemetry:
                # same per-event quantities the scan paths pack: per-lane
                # raw completion clocks scattered over the event-time base
                fin = np.full(self.n, ev.time)
                if ev.finish_lanes is not None and len(ev.workers):
                    fin[ev.workers] = ev.finish_lanes
                self._metrics = self._metrics_step(
                    self._metrics, P_dev, gm_dev, rm_dev,
                    jnp.float32(ev.time),
                    jnp.asarray(fin, dtype=jnp.float32),
                    jnp.int32(rounds),
                    jnp.int32(ev.param_copies_sent))
            self._refresh_batches(ev.workers[ev.restart_lanes])
            rounds += 1
            if rounds % eval_every == 0:
                loss, metric = self._eval_now()
                history.append(HistoryPoint(
                    k=k, time=t, loss=loss, metric=metric,
                    comm_param_copies=comm,
                    n_active_mean=float(np.mean(active_sizes[-eval_every:])),
                ))
        return self._finish(history, k, t, comm, rounds, active_sizes)

    def _run_scan(self, max_events, max_time, eval_every) -> RunResult:
        self._ensure_scan(max_events, max_time)
        self._ensure_eval_accum()
        bound = self.scheduler.edge_bound()
        # With eval_every < block_size every chunk is exactly eval_every
        # events, so padding to this target (not block_size) wastes nothing
        # while still compiling a single block shape for the whole run.
        target = min(self.block_size, eval_every)
        # Eval scalars accumulate in a device buffer (one .at[i].set dispatch
        # per eval, zero host syncs); meta carries the host-side fields and
        # everything is fetched once in _finish_scan.
        cap = max(2, (max_events // eval_every + 2) if max_events else 16)
        eval_buf = jnp.zeros((cap, 2), dtype=jnp.float32)
        meta: List[Tuple[int, float, int, float]] = []  # (k, t, comm, a_mean)
        comm = 0
        active_sizes: List[int] = []
        t = 0.0
        k = -1
        rounds = 0
        buf = []
        with _span("runner:gen"):    # a new event process per run
            stream = self.scheduler.events()
        exhausted = False
        while not exhausted:
            with _span("runner:gen"):
                # None: a finite custom stream ended; flush what we have
                ev = next(stream, None)
            if (ev is None
                    or (max_events is not None and ev.k >= max_events)
                    or (max_time is not None and ev.time > max_time)):
                exhausted = True
            else:
                buf.append(ev)
                k, t = ev.k, ev.time
                comm += ev.param_copies_sent
                active_sizes.append(ev.n_active)
            # Snap block boundaries to the eval grid so the history matches
            # the per-event path point-for-point.
            until_eval = eval_every - rounds % eval_every
            flush = len(buf) >= min(target, until_eval) or (
                exhausted and buf)
            if not flush:
                continue
            if self.trace:
                # recorded pre-pack, pre-pad: the same object events the
                # per-event reference replays, so the traces bit-match
                self._trace.record_events(buf)
            with _span("runner:pack"):
                batch = EventBatch.from_events(buf, edge_bound=bound)
            rows = self._dispatch_block(batch, rounds, target)
            self.counters.add(
                events=batch.E, blocks=1, rows=rows,
                active=sum(len(ev.workers) for ev in buf),
                grad=batch.grad_workers.sum(),
                restarts=batch.restart_workers.sum(),
                grad_evaluated=rows * self.n)
            rounds += len(buf)
            buf = []
            if rounds % eval_every == 0:
                eval_buf = self._record_eval(eval_buf, len(meta))
                meta.append((k, t, comm,
                             float(np.mean(active_sizes[-eval_every:]))))
        self._warn_pool_wrap(rounds)
        return self._finish_scan(eval_buf, meta, k, t, comm, rounds,
                                 active_sizes)

    def _warn_pool_wrap(self, rounds: int) -> None:
        # host-side max: keeps this off the compile cache (a jnp.max here
        # would be the run's only reduce op — one more first-run compile)
        with _span("runner:drain"):
            max_ptr = int(np.max(jax.device_get(self._ptr))) if rounds else 0
        if max_ptr > self._pool_len:
            self._log.warn_once(
                "pool_wrap",
                f"batch pool of {self._pool_len} draws/worker wrapped "
                f"(max restarts {max_ptr}): samples were "
                "revisited cyclically; raise batch_pool (or bound the run "
                "by max_events) for exact per-event sampling semantics.")

    def _run_sparse_stream(self, max_events, max_time, eval_every) -> RunResult:
        """The sparse path's driving loop, over *packed chunks*.

        Replaces the object-event buffered loop for ``mode="sparse_scan"``:
        the stream arrives ``next_chunk``-at-a-time already in
        ``SparseEventBatch`` / ``BucketedSparseEventBatch`` array form
        (array-natively generated where the scheduler supports it), and the
        per-chunk metadata — virtual clocks, copy counts, active sizes —
        is read from the packed arrays in vectorized form.  Event order,
        eval-grid snapping and recorded history are identical to the
        object path's (pinned by tests/test_sparse_event_stream.py).
        """
        self._ensure_sparse(max_events, max_time)
        self._ensure_eval_accum()
        target = min(self.block_size, eval_every)
        cap = max(2, (max_events // eval_every + 2) if max_events else 16)
        eval_buf = jnp.zeros((cap, 2), dtype=jnp.float32)
        meta: List[Tuple[int, float, int, float]] = []  # (k, t, comm, a_mean)
        comm = 0
        active_sizes: List[int] = []
        t = 0.0
        k = -1
        rounds = 0
        with _span("runner:gen"):    # a new event process per run
            stream = self.scheduler.packed_stream()
        exhausted = False
        while not exhausted:
            until_eval = eval_every - rounds % eval_every
            want = min(target, until_eval)
            if max_events is not None:
                want = min(want, max_events - rounds)
            if want <= 0:
                break
            with _span("runner:gen"):
                chunk = stream.next_chunk(want)
            if chunk is None:
                break
            if chunk.E < want:  # finite custom stream ended mid-chunk
                exhausted = True
            tms = chunk.stream_times()
            if max_time is not None and tms[-1] > max_time:
                exhausted = True
                j = int(np.argmax(tms > max_time))
                if j == 0:
                    break
                chunk = chunk.head(j)
                tms = tms[:j]
            comm += int(chunk.stream_copies().sum())
            active_sizes.extend(chunk.stream_n_active().tolist())
            t = float(tms[-1])
            k = rounds + chunk.E - 1
            if self.trace:
                # pre-merge, pre-pad packed arrays (bucketed chunks are
                # walked segment-by-segment in stream order)
                self._trace.record_chunk(chunk)
            if isinstance(chunk, BucketedSparseEventBatch):
                if self.telemetry:
                    self._accum_occupancy(chunk.occupancy())
                blocks, rows, lanes = self._dispatch_bucketed(
                    chunk, rounds, target)
            else:
                if self.telemetry:
                    self._accum_occupancy([{
                        "A": int(chunk.A), "events": int(chunk.E),
                        "lane_fill": float(chunk.n_workers.sum())
                        / max(chunk.E * chunk.A, 1)}])
                blocks, rows, lanes = self._dispatch_sparse_chunk(
                    chunk, rounds, target)
            self.counters.add(events=chunk.E, blocks=blocks, rows=rows,
                              grad_evaluated=lanes, **_lane_counts(chunk))
            rounds += chunk.E
            if rounds % eval_every == 0:
                eval_buf = self._record_eval(eval_buf, len(meta))
                meta.append((k, t, comm,
                             float(np.mean(active_sizes[-eval_every:]))))
        self._warn_pool_wrap(rounds)
        return self._finish_scan(eval_buf, meta, k, t, comm, rounds,
                                 active_sizes)

    # -- on-device eval history -------------------------------------------
    def _ensure_eval_accum(self):
        if self._eval_accum is not None:
            return
        eval_fn = self.eval_fn

        @jax.jit
        def eval_row(W, y, batch):
            loss, metric = eval_fn(debiased_average(W, y), batch)
            return jnp.stack([jnp.asarray(loss, dtype=jnp.float32),
                              jnp.asarray(metric, dtype=jnp.float32)])

        self._eval_accum = eval_row

    def _record_eval(self, eval_buf: jax.Array, i: int) -> jax.Array:
        # The jitted part (eval at the de-biased average) has run-independent
        # shapes — warmup() precompiles it; the scatter into the history
        # buffer is a tiny eager device op (dynamic index: one executable
        # regardless of i or buffer growth).  No host sync anywhere.
        with _span("runner:eval"):
            row = self._eval_accum(self.W, self.y, self.eval_batch)
            if i == eval_buf.shape[0]:  # a max_time run outgrew the buffer
                eval_buf = jnp.concatenate([eval_buf,
                                            jnp.zeros_like(eval_buf)])
            return eval_buf.at[jnp.asarray(i)].set(row)

    def _finish_scan(self, eval_buf, meta, k, t, comm, rounds,
                     active_sizes) -> RunResult:
        eval_buf = self._record_eval(eval_buf, len(meta))
        meta.append((k, t, comm,
                     float(np.mean(active_sizes)) if active_sizes else 0.0))
        with _span("runner:drain"):
            vals = np.asarray(jax.device_get(eval_buf[:len(meta)]))  # one fetch
        history = [
            HistoryPoint(k=mk, time=mt, loss=float(vals[i, 0]),
                         metric=float(vals[i, 1]), comm_param_copies=mc,
                         n_active_mean=ma)
            for i, (mk, mt, mc, ma) in enumerate(meta)]
        trc = self._trace_summary()
        tel = self._telemetry_summary(t)
        self._log.log("run_end", rounds=rounds, t=t, comm=comm)
        return RunResult(
            algorithm=self.scheduler.name, history=history,
            final_loss=history[-1].loss, final_metric=history[-1].metric,
            total_events=rounds, total_time=t, total_comm_copies=comm,
            param_count=self.param_count,
            bytes_per_scalar=self.dtype.itemsize,
            telemetry=tel, trace=trc,
        )

    def _finish(self, history, k, t, comm, rounds, active_sizes) -> RunResult:
        loss, metric = self._eval_now()
        history.append(HistoryPoint(
            k=k, time=t, loss=loss, metric=metric, comm_param_copies=comm,
            n_active_mean=float(np.mean(active_sizes)) if active_sizes else 0.0))
        trc = self._trace_summary()
        tel = self._telemetry_summary(t)
        self._log.log("run_end", rounds=rounds, t=t, comm=comm)
        return RunResult(
            algorithm=self.scheduler.name, history=history,
            final_loss=loss, final_metric=metric,
            total_events=rounds, total_time=t, total_comm_copies=comm,
            param_count=self.param_count,
            bytes_per_scalar=self.dtype.itemsize,
            telemetry=tel, trace=trc,
        )

    def _eval_now(self):
        with _span("runner:eval"):
            avg = debiased_average(self.W, self.y)
            # explicit fetch: float() on the device scalars would be an
            # implicit d2h sync (the runtime sanitizer's transfer guard
            # rejects those)
            loss, metric = jax.device_get(self._eval(avg, self.eval_batch))
        return float(loss), float(metric)


def _cuts(total: int, size: int) -> List[Tuple[int, int]]:
    """``[start, stop)`` pieces of ``range(total)``, ``size`` long but the
    last."""
    return [(start, min(total, start + size))
            for start in range(0, total, size)]


def _identity_event(n: int):
    from repro.core.scheduler import ScheduleEvent
    return ScheduleEvent(
        k=0, time=0.0, n=n,
        workers=np.zeros(0, dtype=np.int32),
        P_sub=np.zeros((0, 0), dtype=np.float32),
        grad_lanes=np.zeros(0, dtype=bool),
        restart_lanes=np.zeros(0, dtype=bool),
        edges=np.zeros((0, 2), dtype=np.int32), param_copies_sent=0)


def run_algorithms(
    algorithms: Dict[str, Scheduler],
    make_trainer: Callable[[Scheduler], DecentralizedTrainer],
    **run_kw,
) -> Dict[str, RunResult]:
    """Run several algorithms under identical model/data settings."""
    out = {}
    for name, sched in algorithms.items():
        trainer = make_trainer(sched)
        out[name] = trainer.run(**run_kw)
    return out
