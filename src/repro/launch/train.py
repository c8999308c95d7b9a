"""Production training launcher: decentralized DSGD-AAU on a device mesh.

Runs the pjit/shard_map train_step from launch/steps.py in a loop with the
host-side AAU scheduler streaming gossip weights, the token data pipeline,
and periodic checkpointing.  ``--demo`` shrinks everything (reduced config,
tiny mesh) so the same driver runs end-to-end on CPU; on a TPU pod the same
code paths run the production mesh.

  python -m repro.launch.train --arch qwen3-8b --demo --steps 20
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P


def run_training(argv=None):
    """Parse ``argv`` as the CLI does and train.

    Returns ``(W, mesh, losses)``: the worker-stacked parameters as the
    last step left them (sharded over ``mesh``) and the per-step mean loss.
    """
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--global-batch", type=int, default=None)
    ap.add_argument("--eta", type=float, default=0.05)
    ap.add_argument("--workers", type=int, default=None)
    ap.add_argument("--demo", action="store_true",
                    help="reduced config on a small CPU mesh")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--straggler-prob", type=float, default=0.1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.configs import get_config
    from repro.core.straggler import StragglerModel
    from repro.data.pipeline import TokenStream, TokenStreamConfig
    from repro.launch import sharding as S
    from repro.launch import shapes as SH
    from repro.launch import steps as ST
    from repro.launch.mesh import (MICROBATCH, TrainAxes, hierarchical_view,
                                   make_production_mesh, train_view)

    cfg = get_config(args.arch)
    if args.demo:
        cfg = cfg.reduced()
        n_dev = jax.device_count()
        model_par = 2 if n_dev % 2 == 0 and n_dev > 1 else 1
        data_par = max(1, n_dev // model_par)
        base = jax.make_mesh((data_par, model_par), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        workers = args.workers or data_par
        fsdp = data_par // workers
        mesh, axes = hierarchical_view(base, workers, max(1, fsdp))
        n_workers = workers
        seq = args.seq or 64
        gb = args.global_batch or max(n_workers * 2, 4)
        microbatch = 1
    else:
        mesh, axes, n_workers = train_view(args.arch, multi_pod=args.multipod)
        seq = args.seq or 4096
        gb = args.global_batch or 256
        microbatch = MICROBATCH.get(args.arch, 1)

    shape = SH.InputShape("train_cli", "train", seq, gb)
    params_init = ST.stacked_init(cfg, n_workers)
    params_sds = jax.eval_shape(params_init, jax.random.PRNGKey(0))
    pspecs = S.param_pspecs(params_sds, mesh, fsdp=axes.fsdp, model=axes.model,
                            worker_axes=axes.worker_axes)
    batch_sds, batch_specs = SH.train_input_specs(cfg, shape, n_workers, axes)
    ns = lambda spec: jax.tree.map(lambda s: NamedSharding(mesh, s), spec,
                                   is_leaf=lambda x: isinstance(x, P))
    step = ST.build_train_step(cfg, n_workers, axes, mesh, pspecs,
                               microbatch=microbatch,
                               logit_chunk=min(512, max(seq // 4, 16)))
    gw0 = ST.default_gossip_weights(n_workers // (2 if axes.pod else 1),
                                    axes.pod is not None)
    jitted = jax.jit(
        step,
        in_shardings=(ns(pspecs), ns(batch_specs), NamedSharding(mesh, P()),
                      jax.tree.map(lambda _: NamedSharding(mesh, P()), gw0)),
        out_shardings=(ns(pspecs), NamedSharding(mesh, P())),
        donate_argnums=(0,),
    )

    stream = TokenStream(TokenStreamConfig(
        vocab_size=cfg.vocab_size, seq_len=seq, global_batch=gb,
        n_workers=n_workers))
    rng = np.random.default_rng(0)

    losses = []
    with mesh:
        W = jax.jit(params_init, out_shardings=ns(pspecs))(jax.random.PRNGKey(0))
        ckpt = None
        if args.ckpt_dir:
            from repro.checkpoint import Checkpointer
            ckpt = Checkpointer(args.ckpt_dir)
        for k in range(args.steps):
            # AAU adaptivity: edges whose endpoint straggles this round carry
            # zero weight (the worker keeps computing; its mass stays put).
            gw = dict(gw0)
            if rng.random() < args.straggler_prob:
                gw = {**gw0, "left": jnp.float32(0.0),
                      "right": jnp.float32(0.0),
                      "self": jnp.float32(1.0)}
            toks = np.stack([
                np.asarray(stream.worker_batch(w)["tokens"])
                for w in range(n_workers)])
            batch = {"tokens": jax.device_put(jnp.asarray(toks),
                                              ns(batch_specs)["tokens"])}
            if cfg.frontend:
                pf = jnp.zeros((n_workers, gb // n_workers,
                                cfg.n_prefix_tokens, cfg.d_model), cfg.cdtype)
                batch["prefix"] = jax.device_put(pf, ns(batch_specs)["prefix"])
            t0 = time.time()
            W, loss = jitted(W, batch, jnp.float32(args.eta), gw)
            loss = float(loss)
            losses.append(loss)
            print(f"step {k:4d} loss {loss:.4f}  ({time.time()-t0:.2f}s)")
            if ckpt and args.ckpt_every and (k + 1) % args.ckpt_every == 0:
                ckpt.save(k + 1, jax.device_get(W),
                          extra={"stream": {"cursor": stream.state_dict()["cursor"].tolist()}})
    return W, mesh, losses


def main(argv=None):
    """CLI entry: exit status 1 when any step's loss is not finite."""
    _, _, losses = run_training(argv)
    print("done")
    return 0 if np.all(np.isfinite(losses)) else 1


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
