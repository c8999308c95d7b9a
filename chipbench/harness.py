"""Pieces of a benchmark run: finding a cell's files by name, building the
program's trainer from them, driving it, and reading it.

Nothing here is specific to a configuration, a traffic mix or a metric: a
cell is ``BENCHMARK.json``'s entry, its configuration is
``configs/<config>.json``, its mix ``traffic/<mix>.json``, its model
``models/<model>.py``, each per-layer metric ``metrics/<metric>.py`` and its
correctness limits ``limits/<cell>.json``.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

import jax
import numpy as np

from chipbench import compare
from chipbench.data import make_data

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
GEN_SPAN = "chipbench:gen"
RUN_SPAN = "chipbench:run"
EVAL_SPAN = "chipbench:eval"
DRAIN_SPAN = "chipbench:drain"
WINDOW_SPAN = "chipbench:window"
RUN_SEEDS = ("init", "data")             # from --seed
ARRIVAL_SEEDS = ("straggler", "sched")   # from the mix's arrival_seed


# -- finding things by name ----------------------------------------------------

def load_json(kind: str, name: str, base: Path = HERE) -> dict:
    path = base / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1] if kind.endswith('s') else kind}"
                                f" named {name!r}: {path} is missing")
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def load_module(kind: str, name: str, base: Path = HERE):
    path = base / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_benchmark(path: Path = REPO / "BENCHMARK.json") -> dict:
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    model: object
    metrics: Dict[str, dict]        # per-layer metric entry of BENCHMARK.json
    readers: Dict[str, object]      # per-layer metric name -> reader module
    end_to_end: Dict[str, dict]
    limits: dict


def load_cell(bench: dict, name: str, base: Path = HERE) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    config = load_json("configs", w["config"], base)
    traffic = load_json("traffic", w["traffic"], base)
    model = load_module("models", config["model"], base)

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    per_layer = {m["name"]: m for m in bench["per_layer"] if applies(m)}
    readers = {k: load_module("metrics", k, base) for k in per_layer}
    e2e = {m["name"]: m for m in bench["end_to_end"] if applies(m)}
    limits_path = base / "limits" / f"{name}.json"
    limits = load_json("limits", name, base) if limits_path.is_file() else {}
    return Cell(name, int(w["chips"]), config, traffic, model, per_layer,
                readers, e2e, limits)


def _split(seed: int, keys) -> Dict[str, int]:
    state = np.random.SeedSequence(int(seed) % 2 ** 64).generate_state(
        len(keys))
    return {k: int(v & 0x7FFFFFFF) for k, v in zip(keys, state)}


def derive_seeds(seed: int, traffic: dict) -> Dict[str, int]:
    """Independent 31-bit seeds for each consumer.

    The weights and the data come from the run's seed, any whole number.
    The arrivals (the time model's draws and the pair picks) come from the
    mix's own ``arrival_seed``, so every run of a cell replays the same
    event stream: which events come, and so the work, does not change with
    the run's seed, only the data and weights do.
    """
    return {**_split(seed, RUN_SEEDS),
            **_split(traffic["arrival_seed"], ARRIVAL_SEEDS)}


# -- the program under test -----------------------------------------------------

class DispatchLog:
    """File-like sink for the trainer's run log; keeps ``block_dispatch``."""

    def __init__(self):
        self.lines: List[str] = []

    def write(self, s: str) -> None:
        if '"block_dispatch"' in s:
            self.lines.append(s)

    def flush(self) -> None:
        pass

    def records(self) -> List[dict]:
        return [json.loads(s) for s in self.lines]


def build_trainer(cell: Cell, seeds: Dict[str, int], *,
                  dtype: Optional[str] = None, run_log=None):
    """The program's trainer for a cell, warmed up: (trainer, eval_batch, p0)."""
    from repro.core.baselines import make_scheduler
    from repro.core.runner import DecentralizedTrainer
    from repro.scenarios import get_scenario
    from repro.xp.builders import build_graph

    config, traffic = cell.config, cell.traffic
    n = config["n_workers"]
    graph = build_graph(config["topology"], n, p=config["edge_prob"],
                        seed=config["graph_seed"])
    scenario = get_scenario(traffic["scenario"], n=n, seed=seeds["straggler"],
                            **traffic.get("scenario_kw", {}))
    sched_kw = dict(traffic.get("scheduler_kw", {}))
    if traffic["algorithm"] in ("ad_psgd", "agp", "prague"):
        sched_kw["seed"] = seeds["sched"]
    sched = make_scheduler(traffic["algorithm"], graph, scenario, **sched_kw)
    pool_x, pool_y, eval_batch = make_data(config, seeds["data"])
    held = {"x": pool_x, "y": pool_y}

    def batch_fn(w, s):
        return {"x": held["x"][w, s], "y": held["y"][w, s]}

    init = cell.model.make_init(config)
    trainer = DecentralizedTrainer(
        sched, cell.model.loss, init, batch_fn, eval_batch,
        eval_fn=cell.model.evaluate, eta0=config["eta0"],
        seed=seeds["init"], same_init=config["same_init"],
        mode=traffic["mode"], block_size=config["block_size"],
        batch_pool=config["batch_pool"],
        dtype=dtype or config["state_dtype"], run_log=run_log)
    trainer.warmup()
    held.clear()        # the trainer holds its own pools from here on
    p0 = init(jax.random.PRNGKey(seeds["init"]))
    return trainer, eval_batch, p0


def step_sizes(traffic) -> List[int]:
    """Events of each set-up run the correctness check reads: one event
    (the first optimizer step), then three runs of the cell's size."""
    return [1] + [traffic["events_per_run"]] * 3


def first_steps(trainer, traffic, p0) -> List[dict]:
    """The set-up runs (``step_sizes``), each read for the correctness
    check.  They go through the window's own call, ``run()``; the one-event
    run pads its block to the window's block shape."""
    out = []
    for size in step_sizes(traffic):
        res = trainer.run(max_events=size, eval_every=traffic["eval_every"])
        out.append(compare.reading(trainer.W, trainer.S, trainer.y,
                                   trainer._ptr, p0,
                                   [h.loss for h in res.history]))
    return out


def reference_readings(cell: Cell, seeds: Dict[str, int], *, mode: str,
                       mix: bool = True,
                       half_batch: bool = False) -> List[dict]:
    """The plain reference's readings after each set-up run
    (``step_sizes``).  ``mode`` is the trainer's resolved mode: the dense
    ``scan`` loop pulls one event past each run's bound."""
    from chipbench import reference

    config, traffic = cell.config, cell.traffic
    pool_x, pool_y, eval_batch = make_data(config, seeds["data"])
    if half_batch:
        half = config["batch_size"] // 2
        pool_x, pool_y = pool_x[:, :, :half], pool_y[:, :, :half]
    p0 = cell.model.make_init(config)(jax.random.PRNGKey(seeds["init"]))
    out: List[dict] = []

    def observe(r, W, S, y, ptr, losses):
        out.append(compare.reading(W, S, y, ptr, p0, losses))

    reference.replay(config, traffic, seeds, cell.model,
                     sizes=step_sizes(traffic),
                     extra_pull=1 if mode == "scan" else 0,
                     pools=(pool_x, pool_y), eval_batch=eval_batch,
                     observe=observe, mix=mix)
    return out


# -- instrumentation of the traced run -----------------------------------------

def chunk_counts(chunk) -> np.ndarray:
    """(E, 3) per event: active lanes, gradient lanes, restarted lanes."""
    if hasattr(chunk, "batches"):           # bucketed by lane width
        rows = np.zeros((chunk.E, 3), np.int64)
        for b, batch in enumerate(chunk.batches):
            if batch is None:
                continue
            sel = chunk.event_bucket == b
            pos = chunk.positions[sel]
            rows[sel] = np.stack([batch.n_workers[pos],
                                  batch.grad_workers[pos].sum(1),
                                  batch.restart_workers[pos].sum(1)], 1)
        return rows
    return np.stack([chunk.n_workers, chunk.grad_workers.sum(1),
                     chunk.restart_workers.sum(1)], 1).astype(np.int64)


class Taps:
    """Host spans and counts around the program's calls, from outside it.

    Times the scheduler's event generation (``packed_stream().next_chunk``
    and ``events()``) under the ``chipbench:gen`` span, counts every
    consumed event's lanes, and marks each ``run()``, its history evals
    and its drain with spans of their own.
    """

    def __init__(self, trainer):
        self.gen_s = 0.0
        self.counts: List[np.ndarray] = []
        self._pulled: List[np.ndarray] = []
        sched = trainer.scheduler
        orig_packed, orig_events = sched.packed_stream, sched.events
        ann = jax.profiler.TraceAnnotation

        def packed_stream(*a, **kw):
            stream = orig_packed(*a, **kw)
            inner = stream.next_chunk

            def next_chunk(k):
                with ann(GEN_SPAN):
                    t0 = time.perf_counter()
                    chunk = inner(k)
                    self.gen_s += time.perf_counter() - t0
                if chunk is not None:
                    self._pulled.append(chunk_counts(chunk))
                return chunk

            stream.next_chunk = next_chunk
            return stream

        def events():
            it = orig_events()
            while True:
                with ann(GEN_SPAN):
                    t0 = time.perf_counter()
                    try:
                        ev = next(it)
                    except StopIteration:
                        return
                    self.gen_s += time.perf_counter() - t0
                self._pulled.append(np.array(
                    [[len(ev.workers), int(np.sum(ev.grad_lanes)),
                      int(np.sum(ev.restart_lanes))]], np.int64))
                yield ev

        sched.packed_stream = packed_stream
        sched.events = events
        orig_run = trainer.run

        def run(*a, **kw):
            self._pulled = []
            with ann(RUN_SPAN):
                res = orig_run(*a, **kw)
            if self._pulled:
                self.counts.append(
                    np.concatenate(self._pulled)[:res.total_events])
            return res

        trainer.run = run
        for attr, span in (("_record_eval", EVAL_SPAN),
                           ("_finish_scan", DRAIN_SPAN)):
            fn = getattr(trainer, attr, None)
            if fn is not None:
                setattr(trainer, attr, _spanned(fn, span))

    def reset(self) -> None:
        self.gen_s = 0.0
        self.counts = []

    def window_counts(self) -> np.ndarray:
        if not self.counts:
            return np.zeros((0, 3), np.int64)
        return np.concatenate(self.counts)


def _spanned(fn, span):
    def call(*a, **kw):
        with jax.profiler.TraceAnnotation(span):
            return fn(*a, **kw)
    return call


class CompileCounter:
    """Backend compiles (a persistent-cache load counts too) while active."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self.seconds = 0.0

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)


class GcPauses:
    """Python's garbage-collector pauses while active: how many, their
    total and the longest, in seconds."""

    def __init__(self):
        self.count = 0
        self.total = 0.0
        self.longest = 0.0
        self._t = None

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            pause = time.perf_counter() - self._t
            self.count += 1
            self.total += pause
            self.longest = max(self.longest, pause)
            self._t = None

    def __enter__(self):
        gc.callbacks.append(self._on)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on)


@dataclasses.dataclass
class Window:
    events: int
    runs: int
    wall: float         # seconds, from the first run's call to the drained end
    shortfall: int      # events asked for and not delivered
    run_s: List[float]  # wall seconds of each run() call


def measure_window(trainer, traffic, seconds: float) -> Window:
    """Consecutive runs for ``seconds``, each drained by ``run()`` itself."""
    events = shortfall = 0
    run_s: List[float] = []
    want = traffic["events_per_run"]
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        t0 = t = time.perf_counter()
        while True:
            res = trainer.run(max_events=want,
                              eval_every=traffic["eval_every"])
            events += res.total_events
            shortfall += want - res.total_events
            now = time.perf_counter()
            run_s.append(now - t)
            t = now
            if now - t0 >= seconds:
                break
        jax.block_until_ready((trainer.W, trainer.S, trainer.y))
        wall = time.perf_counter() - t0
    return Window(events, len(run_s), wall, shortfall, run_s)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
