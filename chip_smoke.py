"""Run DSGD-AAU's main path on a TPU and check it against the XLA reference.

  python chip_smoke.py                # one chip: phases A, B and C
  python chip_smoke.py --four-chips   # four chips: the mesh paths only

Each one-chip phase trains the paper's 2-NN at its published width
(3072→256→256→10, random weights from a seed, synthetic label-sharded data)
through ``DecentralizedTrainer`` twice from the same seed: once with the
Pallas kernels (``use_kernel=True``) and once on the XLA reference path.
It checks that the compiled block calls the kernels (``tpu_custom_call``),
that the two runs agree within ``BOUND``, and that the loss is finite and
below the initial loss.

  A  dsgd_aau   mode="sparse_scan"  bucketed ladder, sparse_gossip kernels
  B  dsgd_sync  mode="scan"         dense masked_gossip_mix kernel
  C  ad_psgd    mode="sparse_scan"  merged single-rung pairs, sparse_gossip

``--four-chips`` runs the ``shard_map`` ring gossip with one worker per chip
against the dense mixing on one device, then a few steps of
``repro.launch.train``'s mesh step on the 2×2 mesh.  One process drives all
the chips.

Wall seconds printed here are not a benchmark.  Without a TPU the script
exits 2 and prints no result.  On success its last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.baselines import make_scheduler  # noqa: E402
from repro.core.runner import DecentralizedTrainer  # noqa: E402
from repro.data import ClassificationData  # noqa: E402
from repro.scenarios import get_scenario  # noqa: E402
from repro.xp.builders import (build_graph, mlp2nn_eval,  # noqa: E402
                               mlp2nn_init, mlp2nn_loss)

# Largest |kernel − XLA| allowed in W, y and the final loss after a phase.
# Not zero: the kernels sum in another order than the XLA einsums (both at
# f32), and the runs compound those last-bit differences over every event.
BOUND = 1e-3
# Largest |ring gossip − dense mixing| of the four-chip check: one mix of
# three f32 terms, against a HIGHEST-precision einsum.
RING_BOUND = 1e-5

PHASES = {"A": ("dsgd_aau", "sparse_scan"),
          "B": ("dsgd_sync", "scan"),
          "C": ("ad_psgd", "sparse_scan")}
# The trainer's compiled block for each mode (the HLO check compiles it
# again from the argument shapes of its first call).
_BLOCK_ATTR = {"sparse_scan": "_sparse", "scan": "_scan"}
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class _CompileClock:
    """Backend compile seconds (a cache hit counts its fetch) and cache hits."""

    def __init__(self):
        self.seconds = 0.0
        self.hits = 0

    def on_duration(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            self.seconds += duration

    def on_event(self, event, **_):
        if event == _CACHE_HIT_EVENT:
            self.hits += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self.on_duration)
        jax.monitoring.unregister_event_listener(self.on_event)


def _peak_bytes():
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def _max_abs_diff(a, b) -> float:
    return max(float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                     - y.astype(jnp.float32))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _spy_first_call(trainer):
    """Wrap the trainer's block so its first call's argument shapes are kept."""
    attr = _BLOCK_ATTR[trainer.mode]
    block = getattr(trainer, attr)
    seen = []

    def spy(*args):
        if not seen:
            seen.append([jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x))
                         for x in jax.tree.leaves(args)])
            seen.append(jax.tree.structure(args))
        return block(*args)

    setattr(trainer, attr, spy)
    return block, seen


def _train_once(alg, mode, data, *, n, d_in, batch, batch_pool, block_size,
                events, use_kernel, seed):
    """One trainer, warmed up and run for ``events``; returns (record, W, y)."""
    trainer = DecentralizedTrainer(
        make_scheduler(alg, build_graph("erdos_renyi", n),
                       get_scenario("paper_default", n=n, seed=seed)),
        mlp2nn_loss, mlp2nn_init(d_in=d_in),
        lambda w, s: data.batch(w, s, batch_size=batch),
        data.eval_batch(), eval_fn=mlp2nn_eval, seed=seed,
        use_kernel=use_kernel, mode=mode, batch_pool=batch_pool,
        block_size=block_size)
    p0 = jax.tree.map(lambda x: x[0], trainer.W)   # same_init: every worker
    loss0 = float(jax.device_get(jax.jit(mlp2nn_loss)(p0, trainer.eval_batch)))
    with _CompileClock() as clock:
        t0 = time.perf_counter()
        trainer.warmup()
        warmup_s = time.perf_counter() - t0
        block, seen = _spy_first_call(trainer)
        t0 = time.perf_counter()
        # eval_every = block_size: the run reuses the warmed block shapes
        result = trainer.run(max_events=events, eval_every=trainer.block_size)
        jax.block_until_ready((trainer.W, trainer.y))
        wall_s = time.perf_counter() - t0
        custom_call = None
        if use_kernel:
            args = jax.tree.unflatten(seen[1], seen[0])
            hlo = block.lower(*args).compile().as_text()
            custom_call = "tpu_custom_call" in hlo
    record = {"events": result.total_events, "compile_s": clock.seconds,
              "cache_hits": clock.hits, "warmup_s": warmup_s,
              "wall_s": wall_s, "loss0": loss0, "loss": result.final_loss,
              "tpu_custom_call": custom_call, "peak_bytes": _peak_bytes()}
    return record, trainer.W, trainer.y


def run_phase(phase, *, n=128, d_in=3072, batch=32, batch_pool=16,
              block_size=32, events=256, seed=0):
    """Phase ``phase`` (a key of PHASES): kernel and XLA runs and their diffs."""
    alg, mode = PHASES[phase]
    data = ClassificationData(n_workers=n, d=d_in, partition="label_shard",
                              seed=seed)
    kw = dict(n=n, d_in=d_in, batch=batch, batch_pool=batch_pool,
              block_size=block_size, events=events, seed=seed)
    kernel, W_k, y_k = _train_once(alg, mode, data, use_kernel=True, **kw)
    xla, W_x, y_x = _train_once(alg, mode, data, use_kernel=False, **kw)
    diff = {"W": _max_abs_diff(W_k, W_x), "y": _max_abs_diff(y_k, y_x),
            "loss": abs(kernel["loss"] - xla["loss"])}
    return {"phase": phase, "alg": alg, "mode": mode, "n": n, "d_in": d_in,
            "events": events, "kernel": kernel, "xla": xla, "diff": diff}


def phase_failures(res, *, need_custom_call):
    """Every check ``res`` fails, as messages (empty when it passed)."""
    out = []
    for side in ("kernel", "xla"):
        r = res[side]
        if r["events"] != res["events"]:
            out.append(f"{side}: ran {r['events']} of {res['events']} events")
        if not (math.isfinite(r["loss"]) and r["loss"] < r["loss0"]):
            out.append(f"{side}: final loss {r['loss']} is not finite and "
                       f"below the initial {r['loss0']}")
    for key, v in res["diff"].items():
        if not v <= BOUND:
            out.append(f"|kernel - xla| of {key} is {v}, over {BOUND}")
    if need_custom_call and not res["kernel"]["tpu_custom_call"]:
        out.append("the kernel run's compiled block has no tpu_custom_call")
    return out


def ring_gossip_check(*, d_in=3072, seed=0):
    """shard_map ring gossip, one worker per device (at least 3), against
    the dense mixing with the same ring Metropolis matrix on one device."""
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

    from repro.core.aau import gossip_mix_dense, tree_ring_gossip
    from repro.core.consensus import metropolis_matrix
    from repro.utils.tree import tree_stack

    devices = jax.devices()
    n = len(devices)
    init = mlp2nn_init(d_in=d_in)
    W = tree_stack([init(k) for k in jax.random.split(jax.random.PRNGKey(seed),
                                                       n)])
    Pm = metropolis_matrix(n, [(i, (i + 1) % n) for i in range(n)])
    self_w, left_w, right_w = (jnp.float32(Pm[0, 0]), jnp.float32(Pm[-1, 0]),
                               jnp.float32(Pm[1 % n, 0]))
    mesh = jax.make_mesh((n,), ("worker",), axis_types=(AxisType.Auto,),
                         devices=devices)
    spec = jax.tree.map(lambda _: P("worker"), W)
    ring = jax.jit(jax.shard_map(
        lambda W: tree_ring_gossip(W, "worker", n, self_w, left_w, right_w),
        mesh=mesh, in_specs=(spec,), out_specs=spec))
    out = ring(jax.device_put(W, NamedSharding(mesh, P("worker"))))
    ref = gossip_mix_dense(jax.device_put(W, devices[0]),
                           jnp.asarray(Pm, jnp.float32))
    placed = set().union(*(x.sharding.device_set
                           for x in jax.tree.leaves(out)))
    diff = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
               for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)))
    return {"workers": n, "diff": diff, "out_devices": len(placed)}


def mesh_train_check(*, steps=3):
    """A few steps of repro.launch.train's mesh step at --demo."""
    from repro.launch.train import run_training

    W, mesh, losses = run_training(["--arch", "paper-char-lm", "--demo",
                                    "--steps", str(steps)])
    placed = set().union(*(x.sharding.device_set for x in jax.tree.leaves(W)))
    return {"mesh": dict(mesh.shape), "losses": losses,
            "param_devices": len(placed)}


def four_chip_failures(ring, train):
    n = len(jax.devices())
    out = []
    if not ring["diff"] <= RING_BOUND:
        out.append(f"ring gossip differs from dense mixing by {ring['diff']}")
    if ring["out_devices"] != n:
        out.append(f"ring gossip output on {ring['out_devices']} of {n} devices")
    if not all(math.isfinite(x) for x in train["losses"]):
        out.append(f"mesh train losses not finite: {train['losses']}")
    if train["param_devices"] != n:
        out.append(f"mesh train parameters on {train['param_devices']} of "
                   f"{n} devices")
    return out


def _print_phase(res):
    head = f"phase {res['phase']} {res['alg']} mode={res['mode']}"
    for side in ("kernel", "xla"):
        r = res[side]
        print(f"{head} {side}: events={r['events']} "
              f"compile_s={r['compile_s']} cache_hits={r['cache_hits']} "
              f"warmup_s={r['warmup_s']} wall_s={r['wall_s']} "
              "(not a benchmark) "
              f"loss0={r['loss0']} loss={r['loss']} "
              f"tpu_custom_call={r['tpu_custom_call']} "
              f"peak_bytes_in_use={r['peak_bytes']}", flush=True)
    d = res["diff"]
    print(f"{head} |kernel - xla|: W={d['W']} y={d['y']} loss={d['loss']} "
          f"(bound {BOUND})", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the multi-chip paths (needs 4 chips)")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    from repro.utils.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    print(f"device: {dev.device_kind} x{len(jax.devices())}", flush=True)
    failures = []
    if args.four_chips:
        if len(jax.devices()) != 4:
            print(f"chip_smoke: --four-chips needs 4 chips, found "
                  f"{len(jax.devices())}", file=sys.stderr)
            return 2
        ring = ring_gossip_check()
        print(f"ring gossip over {ring['workers']} chips: |shard_map - dense|"
              f"={ring['diff']} (bound {RING_BOUND}) "
              f"output on {ring['out_devices']} devices", flush=True)
        train = mesh_train_check()
        print(f"mesh train {train['mesh']}: losses={train['losses']} "
              f"params on {train['param_devices']} devices", flush=True)
        failures += four_chip_failures(ring, train)
    else:
        for phase in PHASES:
            res = run_phase(phase)
            _print_phase(res)
            failures += [f"phase {phase}: {m}" for m in
                         phase_failures(res, need_custom_call=True)]
    for msg in failures:
        print(f"FAILED {msg}", file=sys.stderr)
    if failures:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
