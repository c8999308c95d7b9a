"""Compile each cell's programs at full size for a described TPU v5e, without
a chip, and print what the compiler says of their memory.

  JAX_PLATFORMS=cpu python -m chipbench.rehearse [cell ...]

For every cell (default: all of ``BENCHMARK.json``) it compiles, from shapes
alone, the trainer's event-block programs at the shapes the runner
dispatches, the data generator and the reference's dense step, and prints
``memory_analysis()`` of each.  The block shapes are the program's own: its
``DecentralizedTrainer.warmup()``, which dispatches one block of every shape
a run uses, is driven on a trainer that holds no state, and the event
arrays of each dispatch are recorded instead of run.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import harness  # noqa: E402

GiB = 2 ** 30


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype), sharding=sharding)


def _report(label, compiled):
    m = compiled.memory_analysis()
    print(f"  {label}: args {m.argument_size_in_bytes / GiB:.3f} GiB, "
          f"out {m.output_size_in_bytes / GiB:.3f} GiB, "
          f"temp {m.temp_size_in_bytes / GiB:.3f} GiB, "
          f"alias {m.alias_size_in_bytes / GiB:.3f} GiB", flush=True)


def dispatched_shapes(sched, mode, block_size):
    """[(shape, dtype) of each event array] of every block that the runner's
    ``warmup()`` dispatches, on a trainer that holds no state: the block
    programs are replaced by a recorder that returns the carry unchanged."""
    from repro.core.runner import DecentralizedTrainer
    from repro.obs.runlog import RunLogger

    calls = []

    def record(*args):          # (W, S, y, ptr, pools, *event arrays)
        calls.append([(a.shape, a.dtype) for a in args[5:]])
        return args[:4]

    probe = DecentralizedTrainer.__new__(DecentralizedTrainer)
    carry = jnp.zeros((sched.n,), jnp.float32)
    probe.__dict__.update(
        scheduler=sched, n=sched.n, mode=mode, block_size=block_size,
        events_per_step=None, telemetry=False, eta0=0.0, eta_decay=1.0,
        eta_decay_every=1, _log=RunLogger(None), W=carry, S=carry, y=carry,
        _ptr=carry, _pools=None, _scan=record, _sparse=record,
        _ensure_scan=lambda *a, **k: None,
        _ensure_sparse=lambda *a, **k: None, _warm_eval=lambda: None)
    probe.warmup()
    return calls


def rehearse(cell, one_chip):
    from repro.core.aau import build_event_scan, build_sparse_event_scan
    from repro.core.baselines import make_scheduler
    from repro.core.runner import choose_mode
    from repro.scenarios import get_scenario
    from repro.xp.builders import build_graph

    from chipbench import data, reference

    cfg, trf, model = cell.config, cell.traffic, cell.model
    n, B, pool = cfg["n_workers"], cfg["batch_size"], cfg["batch_pool"]
    sched = make_scheduler(
        trf["algorithm"], build_graph(cfg["topology"], n, p=cfg["edge_prob"],
                                      seed=cfg["graph_seed"]),
        get_scenario(trf["scenario"], n=n, **trf.get("scenario_kw", {})),
        **trf.get("scheduler_kw", {}))
    mode = trf["mode"]
    if mode == "auto":
        mode = choose_mode(n, sched.active_buckets(), sched.global_events)
    print(f"{cell.name}: mode {mode}, ladder {sched.active_buckets()}", flush=True)
    p0 = jax.eval_shape(model.make_init(cfg), jax.random.PRNGKey(0))
    f32 = cfg["state_dtype"]
    W = jax.tree.map(lambda x: _sds((n,) + x.shape, f32, one_chip), p0)
    y = _sds((n,), jnp.float32, one_chip)
    ptr = _sds((n,), jnp.int32, one_chip)
    pools = {"x": _sds((n, pool, B, cfg["d_in"]), f32, one_chip),
             "y": _sds((n, pool, B), jnp.int32, one_chip)}
    sparse = mode == "sparse_scan"
    # shapes only: no buffer exists, so no W/S alias to break
    blk = (build_sparse_event_scan if sparse  # repro: disable=missing-alias-break
           else build_event_scan)(model.loss)
    for events in dispatched_shapes(sched, mode, cfg["block_size"]):
        args = (W, W, y, ptr, pools,
                *(_sds(shape, dtype, one_chip) for shape, dtype in events))
        _report(f"block {' '.join(str(sh) for sh, _ in events)}",
                blk.lower(*args).compile())
    gen = data._generate.lower(
        _sds((2,), jnp.uint32, one_chip), n=n, d=cfg["d_in"],
        n_classes=cfg["n_classes"], classes_per_worker=cfg["classes_per_worker"],
        samples_per_worker=cfg["samples_per_worker"], pool=pool, batch=B,
        n_eval=cfg["eval_batch"], noise=float(cfg["noise"])).compile()
    _report("data generator", gen)
    with jax.default_matmul_precision("highest"):
        step, _ = reference._compiled(model.loss, model.evaluate)
        ref = step.lower(W, W, y, ptr, pools["x"], pools["y"],
                         _sds((n,), jnp.int32, one_chip),
                         _sds((n, n), jnp.float32, one_chip),
                         _sds((n,), jnp.float32, one_chip),
                         _sds((n,), jnp.int32, one_chip),
                         _sds((), jnp.float32, one_chip)).compile()
    _report(f"reference step, {n} lanes", ref)


def main(argv=None) -> int:
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    names = list(argv if argv is not None else sys.argv[1:])
    bench = harness.load_benchmark()
    names = names or [w["name"] for w in bench["workloads"]]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    for name in names:
        rehearse(harness.load_cell(bench, name), one_chip)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
