"""Events/sec of the event-stream execution modes, at paper worker counts.

Three consumers share one scheduler stream, for each of the paper's async
algorithms with distinct active-set shapes (AD-PSGD: constant A=2 pairs;
DSGD-AAU: heavy-tailed finished cliques, the bucketed-ladder stress case;
Prague: constant group-size cliques):

- ``per_event``: one XLA dispatch + host batch refresh per event (legacy);
- ``scan``: block-compiled dense scan — one dispatch per ``block_size``
  events, but every event still pays the O(n²·D) dense mix and O(n·D)
  gradients;
- ``sparse_scan``: the active-set gather-compute-scatter scan — O(A²·D)
  mix and O(A·D) gradients at the scheduler's lane-width ladder.  For
  DSGD-AAU that ladder is multi-rung (``Scheduler.active_buckets``), and
  the row records the static single-bucket throughput next to the
  bucketed one so the ladder's win is in the artifact, not just the docs.

Each row also records the measured per-bucket occupancy of the stream
(``BucketedSparseEventBatch.occupancy``): events per rung and lane fill —
the padding-waste numbers that motivated bucketing (a DSGD-AAU stream at
N=256 packed to the static bound sits under 4% lane fill).

Event *generation* (host-side numpy) is timed separately: it bounds every
consumer from above.  The opt-in event-horizon batcher is timed for the
single-edge schedulers only (the others don't accept ``horizon=``; their
rows record ``gen_horizon_eps: null`` — the number-or-null metric schema
enforced by ``common.write_bench_json``, which also normalizes the legacy
``"unsupported"`` string older recordings carried).

``e2e_eps`` times the sparse path at its *defaults* — array-native
packed generation plus the event-blocked scan (K conflict-free events
merged per ``lax.scan`` step) — generation and consumption together.
``sparse_eps`` stays measured with ``events_per_step=1`` (one event per
scan step).

Observability overhead, measured on the sparse stream of the AD-PSGD pair
scheduler and of DSGD-AAU (the bucketed ladder, worst case for extra
carries): ``e2e_tel_eps`` / ``e2e_tel_overhead`` re-time it with
``telemetry=True`` (``repro.obs``: device-resident counters drained once
per run), ``e2e_trace_eps`` / ``trace_overhead`` with ``trace=True``
(``repro.obs.trace``: host-side event-identity recording per block plus
the end-of-run wait-blame attribution).  ``--smoke`` asserts the trace
ratio stays under 1.10 on both streams: tracing must never sync mid-run.
The telemetry ratio is recorded, not asserted: the sparse block folds its
counters every scan step, which at this bench's 16-wide payload costs
about a fifth of a step on the CPU.

  python -m benchmarks.bench_event_stream [--paper-scale] [--xl] [--smoke]
      # writes BENCH_event_stream.json

All trainers are warmed up first (``DecentralizedTrainer.warmup`` compiles
via a no-op dispatch), so the numbers compare steady-state throughput, not
compile time.  ``per_event`` is skipped above N=64 and the dense scan above
N=256 (each would dominate the wall clock without adding information —
above those scales the sparse path is the only contender, which is the
point of the bench).
"""
from __future__ import annotations

import argparse
import itertools
import os
import time

import jax
import jax.numpy as jnp

from benchmarks.common import bench_sizes, csv_row, write_bench_json
from repro.core import topology
from repro.core.baselines import make_scheduler
from repro.core.runner import DecentralizedTrainer
from repro.core.scheduler import BucketedSparseEventBatch
from repro.core.straggler import StragglerModel
from repro.data.synthetic import ClassificationData

ALGS = ("ad_psgd", "dsgd_aau", "prague")
BLOCK_SIZE = 128
D_IN, D_H, BATCH = 16, 16, 4
PER_EVENT_MAX_N = 64     # legacy interpreter is noise above this scale
SCAN_MAX_N = 256         # dense O(n²·D) mix: wall-clock filler above this
HORIZON_ALGS = ("ad_psgd", "agp")   # single-edge scheds accept horizon=
OVERHEAD_ALGS = ("ad_psgd", "dsgd_aau")  # observability overhead rows

_JSON_PATH = os.path.join(os.path.dirname(__file__), "..",
                          "BENCH_event_stream.json")


def _loss(params, batch):
    h = jax.nn.relu(batch["x"] @ params["w1"])
    logp = jax.nn.log_softmax(h @ params["w2"])
    return -jnp.mean(jnp.take_along_axis(logp, batch["y"][:, None], axis=1))


def _init(key):
    k1, k2 = jax.random.split(key)
    return {"w1": jax.random.normal(k1, (D_IN, D_H)) * 0.1,
            "w2": jax.random.normal(k2, (D_H, 10)) * 0.1}


def _events_for(n: int, smoke: bool) -> int:
    if smoke:
        return 64  # a few blocks: proves the paths run, not their speed
    return {128: 384, 256: 256, 512: 192, 1024: 128}.get(n, 1024)


def _make_sched(alg: str, n: int, **kw):
    g = topology.erdos_renyi(n, max(0.15, 4.0 / n), seed=1)
    sm = StragglerModel(n=n, straggler_prob=0.1, slowdown=10.0, seed=0)
    return make_scheduler(alg, g, sm, **kw)


def _make_trainer(alg: str, mode: str, n: int, block_size: int,
                  trainer_kw=None, **sched_kw) -> DecentralizedTrainer:
    data = ClassificationData(n_workers=n, d=D_IN, samples_per_worker=64,
                              seed=0)
    # warmup() builds the pool before run() can size it, so pass an explicit
    # pool covering the observed worst-case restarts/worker of the event
    # bounds used here (~81 at N=16); bigger pools measurably slow the
    # per-step gather on CPU, which would pollute the dispatch comparison.
    kw = ({"block_size": block_size, "batch_pool": 96}
          if mode in ("scan", "sparse_scan") else {})
    kw.update(trainer_kw or {})
    return DecentralizedTrainer(
        _make_sched(alg, n, **sched_kw), _loss, _init,
        lambda w, s: data.batch(w, s, batch_size=BATCH),
        data.eval_batch(256), eta0=0.2, seed=0, mode=mode, **kw)


def _events_per_sec(alg: str, mode: str, n: int, events: int,
                    block_size: int, trainer_kw=None, repeats: int = 1,
                    **sched_kw) -> float:
    tr = _make_trainer(alg, mode, n, block_size, trainer_kw, **sched_kw)
    tr.warmup()
    best = 0.0
    for _ in range(repeats):
        t0 = time.perf_counter()
        res = tr.run(max_events=events, eval_every=10 ** 9)
        jax.block_until_ready(tr.y)
        wall = time.perf_counter() - t0
        best = max(best, res.total_events / wall)
    return best


def _flag_overhead_pair(alg: str, mode: str, n: int, events: int,
                        block_size: int, flag: str = "telemetry",
                        repeats: int = 3, **sched_kw):
    """(base_eps, flag_on_eps) for ``mode``, measured interleaved.

    ``flag`` names the trainer observability switch under test
    (``telemetry`` — the MetricsCarry of device accumulators — or
    ``trace`` — event-identity recording plus the end-of-run wait-blame
    attribution).  The with/without timings alternate run-by-run (best-of
    ``repeats`` each) so background load drift hits both sides equally —
    a sequential pair can fake a ±20% "overhead" on a busy host.
    """
    trs = {on: _make_trainer(alg, mode, n, block_size,
                             {flag: on}, **sched_kw)
           for on in (False, True)}
    for tr in trs.values():
        tr.warmup()
        tr.run(max_events=block_size, eval_every=10 ** 9)  # steady state
    best = {False: 0.0, True: 0.0}
    for _ in range(repeats):
        for tel, tr in trs.items():
            t0 = time.perf_counter()
            res = tr.run(max_events=events, eval_every=10 ** 9)
            jax.block_until_ready(tr.y)
            wall = time.perf_counter() - t0
            best[tel] = max(best[tel], res.total_events / wall)
    return best[False], best[True]


def _generation_events_per_sec(alg: str, n: int, events: int,
                               horizon=None) -> float:
    """Host-side scheduler throughput alone: the event loop + event build."""
    sched = _make_sched(alg, n, **({"horizon": horizon} if horizon else {}))
    stream = sched.events()
    next(stream)  # exclude generator setup / first-draw warmup
    t0 = time.perf_counter()
    for _ in itertools.islice(stream, events):
        pass
    return events / (time.perf_counter() - t0)


def _bucket_occupancy(alg: str, n: int, events: int):
    """Measured lane-width ladder + per-rung packing stats of the stream."""
    sched = _make_sched(alg, n)
    buckets = sched.active_buckets()
    evs = list(itertools.islice(sched.events(), events))
    occ = BucketedSparseEventBatch.from_events(evs, buckets=buckets,
                                               edge_bound=sched.edge_bound())
    return list(map(int, buckets)), occ.occupancy()


def _overhead_rows(alg: str, n: int, events: int, smoke: bool):
    """Telemetry and trace cost on the sparse stream, each measured
    interleaved against its own base (a separately-timed pair under host
    generation noise can fake a large ratio).  The telemetry ratio is
    recorded; the trace ratio is asserted < 1.10 under ``--smoke`` (tracing
    must never sync mid-run) on a longer run — a 64-event run is all fixed
    cost and would fake any ratio."""
    base, tel = _flag_overhead_pair(alg, "sparse_scan", n, events,
                                    min(BLOCK_SIZE, events),
                                    repeats=2 if smoke else 3)
    trace_events = max(events, 2048) if smoke else events
    trace_base, trace = _flag_overhead_pair(
        alg, "sparse_scan", n, trace_events, min(BLOCK_SIZE, trace_events),
        flag="trace", repeats=3)
    row = {"e2e_tel_eps": tel, "e2e_tel_overhead": base / tel,
           "e2e_trace_eps": trace, "trace_overhead": trace_base / trace}
    if smoke:
        assert row["trace_overhead"] < 1.10, (
            f"virtual-time tracing cost {row['trace_overhead']:.3f}x "
            f"on the streaming path (contract: < 1.10x)")
    return row


def run(paper_scale: bool = False, smoke: bool = False, xl: bool = False):
    sizes = bench_sizes(paper_scale, smoke, xl)
    results = []
    for n, alg in itertools.product(sizes, ALGS):
        events = _events_for(n, smoke)
        block = min(BLOCK_SIZE, events)
        gen = _generation_events_per_sec(alg, n, events)
        buckets, occupancy = _bucket_occupancy(alg, n, events)
        # one event per scan step: the unmerged sparse path
        sparse = _events_per_sec(
            alg, "sparse_scan", n, events, block,
            trainer_kw=dict(events_per_step=1))
        # The streaming defaults: native packed generation + event-blocked
        # scan, generation and consumption timed together.
        e2e = _events_per_sec(alg, "sparse_scan", n, events, block)
        row = {
            "n": n, "alg": alg, "events": events, "block_size": block,
            "gen_eps": gen, "sparse_eps": sparse, "e2e_eps": e2e,
            "buckets": buckets, "occupancy": occupancy,
        }
        yield csv_row(f"event_stream_gen_{alg}_n{n}", 1e6 / gen,
                      f"{gen:.0f} events/s generation")
        if alg in HORIZON_ALGS:
            gen_h = _generation_events_per_sec(alg, n, events, horizon=256)
            row["gen_horizon_eps"] = gen_h
            yield csv_row(f"event_stream_gen_horizon_{alg}_n{n}",
                          1e6 / gen_h, f"{gen_h:.0f} events/s horizon gen")
        else:
            # multi-worker restart sets consume the RNG in event order —
            # the horizon batcher's flat pre-draw doesn't apply (null, per
            # the number-or-null metric schema; see common.write_bench_json)
            row["gen_horizon_eps"] = None
        if n <= PER_EVENT_MAX_N:
            per_event = _events_per_sec(alg, "per_event", n, events, block)
            row["per_event_eps"] = per_event
            yield csv_row(f"event_stream_per_event_{alg}_n{n}",
                          1e6 / per_event, f"{per_event:.0f} events/s")
        if n <= SCAN_MAX_N:
            scan = _events_per_sec(alg, "scan", n, events, block)
            row["scan_eps"] = scan
            row["sparse_speedup"] = sparse / scan
            yield csv_row(f"event_stream_scan_{alg}_n{n}", 1e6 / scan,
                          f"{scan:.0f} events/s")
        if len(buckets) > 1 and n <= SCAN_MAX_N:
            # the pre-ladder sparse path: every event padded to A=n.  Kept
            # in the artifact so the bucketing win is a recorded number
            # (measured at the same settings as sparse_eps).
            static = _events_per_sec(
                alg, "sparse_scan", n, events, block,
                trainer_kw=dict(events_per_step=1),
                buckets=(n,))
            row["sparse_static_eps"] = static
            row["bucket_speedup"] = sparse / static
            yield csv_row(
                f"event_stream_sparse_static_{alg}_n{n}", 1e6 / static,
                f"{static:.0f} events/s (single-bucket A={n} padding)")
        vs = (f" ({row['sparse_speedup']:.1f}x vs dense scan)"
              if "sparse_speedup" in row else "")
        yield csv_row(f"event_stream_sparse_{alg}_n{n}", 1e6 / sparse,
                      f"{sparse:.0f} events/s{vs}")
        yield csv_row(f"event_stream_e2e_{alg}_n{n}", 1e6 / e2e,
                      f"{e2e:.0f} events/s streaming defaults "
                      f"({e2e / sparse:.1f}x vs one-event-per-step)")
        if alg in OVERHEAD_ALGS:
            row.update(_overhead_rows(alg, n, events, smoke))
            yield csv_row(f"event_stream_e2e_tel_{alg}_n{n}",
                          1e6 / row["e2e_tel_eps"],
                          f"{row['e2e_tel_eps']:.0f} events/s streaming with "
                          f"telemetry ({row['e2e_tel_overhead']:.3f}x overhead)")
            yield csv_row(f"event_stream_e2e_trace_{alg}_n{n}",
                          1e6 / row["e2e_trace_eps"],
                          f"{row['e2e_trace_eps']:.0f} events/s streaming "
                          f"with trace ({row['trace_overhead']:.3f}x overhead)")
        results.append(row)
    payload = {
        "bench": "event_stream",
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "results": results,
    }
    if not smoke:  # smoke checks runnability; don't clobber measured rows
        write_bench_json(os.path.abspath(_JSON_PATH), payload)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--paper-scale", action="store_true")
    ap.add_argument("--xl", action="store_true",
                    help="add N∈{512, 1024} (sparse path only)")
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    for row in run(paper_scale=args.paper_scale, smoke=args.smoke,
                   xl=args.xl):
        print(row)
    if not args.smoke:
        print(f"# wrote {os.path.abspath(_JSON_PATH)}")


if __name__ == "__main__":
    main()
