"""Multi-pod dry-run: lower + compile every (arch × input shape × mesh).

Proves the distribution config is coherent without hardware: 512 placeholder
host devices build the production meshes; every step function is lowered with
ShapeDtypeStruct inputs (no allocation), compiled, and its memory_analysis /
cost_analysis / collective schedule recorded for EXPERIMENTS.md §Dry-run and
§Roofline.

Usage:
  python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k [--multipod]
  python -m repro.launch.dryrun --all [--multipod] --out experiments/dryrun
"""
import argparse
import json
import os
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


def _mesh_contexts(arch: str, multi_pod: bool):
    from repro.launch import mesh as M
    view, axes, n_workers = M.train_view(arch, multi_pod=multi_pod)
    serve_mesh = M.make_production_mesh(multi_pod=multi_pod)
    return view, axes, n_workers, serve_mesh


def _make_attn_hint(mesh, batch_axis="data", head_axis="model"):
    """with_sharding_constraint hook for attention internals (layers._hint).

    batch_axis=None → leave the batch dim unconstrained (train views whose
    per-worker batch is not sharded within the worker)."""

    def _size(ax):
        axes = ax if isinstance(ax, tuple) else (ax,)
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        return n

    def hint(x, dims):
        spec = [None] * len(dims)
        # prefer sharding heads over the model axis; when the head count
        # doesn't divide (e.g. minicpm's 36 heads on a 16-wide axis) fall
        # back to sharding the q/sequence-chunk dim over the same axis --
        # attention and CE rows are independent per q position.
        placed = False
        for i, ch in enumerate(dims):
            if ch == "h" and x.shape[i] % _size(head_axis) == 0:
                spec[i] = head_axis
                placed = True
                break
        if not placed:
            for i, ch in enumerate(dims):
                if ch == "q" and x.shape[i] % _size(head_axis) == 0:
                    spec[i] = head_axis
                    break
        for i, ch in enumerate(dims):
            if (ch == "b" and batch_axis is not None
                    and x.shape[i] % _size(batch_axis) == 0):
                spec[i] = batch_axis
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(*spec)))

    return hint


def run_one(arch: str, shape_name: str, multi_pod: bool = False,
            seq_shard: bool = True, attn_hint: bool = True,
            embed_vocab_shard: bool = False,
            verbose: bool = True) -> dict:
    from repro.configs import get_config
    from repro.launch import hlo_analysis as H
    from repro.launch import sharding as S
    from repro.launch import shapes as SH
    from repro.launch import steps as ST
    from repro.launch.mesh import MICROBATCH
    from repro.models import layers as L
    from repro.models.transformer import active_param_count

    shape = SH.SHAPES[shape_name]
    cfg = SH.shape_config(get_config(arch), shape)
    t0 = time.time()
    view, axes, n_workers, serve_mesh = _mesh_contexts(arch, multi_pod)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "n_devices": 512 if multi_pod else 256}

    if shape.kind == "train":
        mesh = view
        params_sds = jax.eval_shape(ST.stacked_init(cfg, n_workers),
                                    jax.random.PRNGKey(0))
        pspecs = S.param_pspecs(params_sds, mesh, fsdp=axes.fsdp,
                                model=axes.model, worker_axes=axes.worker_axes,
                                embed_vocab_shard=embed_vocab_shard)
        batch_sds, batch_specs = SH.train_input_specs(
            cfg, shape, n_workers, axes, seq_shard=seq_shard)
        mb = MICROBATCH.get(arch, 1)
        # CE-chunk sized so one chunk's fp32 logits stay under ~0.5 GiB per
        # worker (the live-buffer peak is a few chunks deep in backward)
        bw = shape.global_batch // n_workers // mb
        budget = int(0.5e9 / max(bw * cfg.vocab_size * 4, 1))
        logit_chunk = max(32, min(512, 1 << max(budget, 1).bit_length() - 1))
        step = ST.build_train_step(cfg, n_workers, axes, mesh, pspecs,
                                   microbatch=mb, logit_chunk=logit_chunk)
        ns = lambda spec: jax.tree.map(lambda s: NamedSharding(mesh, s), spec,
                                       is_leaf=lambda x: isinstance(x, P))
        gw = ST.gossip_weights_spec()
        jitted = jax.jit(
            step,
            in_shardings=(ns(pspecs), ns(batch_specs),
                          NamedSharding(mesh, P()),
                          jax.tree.map(lambda _: NamedSharding(mesh, P()), gw)),
            out_shardings=(ns(pspecs), NamedSharding(mesh, P())),
            donate_argnums=(0,),
        )
        if attn_hint:
            L.set_attention_shard_hint(
                _make_attn_hint(mesh, batch_axis=axes.fsdp, head_axis=axes.model))
        try:
            with mesh:
                lowered = jitted.lower(params_sds, batch_sds,
                                       jax.ShapeDtypeStruct((), jnp.float32), gw)
                compiled = lowered.compile()
        finally:
            L.set_attention_shard_hint(None)
        tokens_per_step = shape.global_batch * shape.seq_len
        # MODEL_FLOPS: 6·N_active·D tokens per *worker step*; all workers step.
        model_flops = 6.0 * active_param_count(cfg) * tokens_per_step
    elif shape.kind == "prefill":
        mesh = serve_mesh
        from repro.models.transformer import init_model
        params_sds = jax.eval_shape(
            lambda k: init_model(k, cfg), jax.random.PRNGKey(0))
        da = ("pod", "data") if multi_pod else "data"
        pspecs = S.param_pspecs(params_sds, mesh, fsdp=da, model="model")
        batch_sds, batch_specs = SH.prefill_input_specs(cfg, shape, mesh)
        step = ST.build_prefill_step(cfg, cache_len=shape.seq_len)
        ns = lambda spec: jax.tree.map(lambda s: NamedSharding(mesh, s), spec,
                                       is_leaf=lambda x: isinstance(x, P))
        jitted = jax.jit(step, in_shardings=(ns(pspecs), ns(batch_specs)))
        if attn_hint:
            L.set_attention_shard_hint(_make_attn_hint(mesh, batch_axis=da))
        try:
            with mesh:
                lowered = jitted.lower(params_sds, batch_sds)
                compiled = lowered.compile()
        finally:
            L.set_attention_shard_hint(None)
        model_flops = (2.0 * active_param_count(cfg)
                       * shape.global_batch * shape.seq_len)
    else:  # decode
        mesh = serve_mesh
        from repro.models.transformer import init_model
        params_sds = jax.eval_shape(
            lambda k: init_model(k, cfg), jax.random.PRNGKey(0))
        da = ("pod", "data") if multi_pod else "data"
        pspecs = S.param_pspecs(params_sds, mesh, fsdp=da, model="model")
        inp, specs = SH.decode_input_specs(cfg, shape, mesh)
        step = ST.build_serve_step(cfg)
        ns = lambda spec: jax.tree.map(
            lambda s: NamedSharding(mesh, s), spec,
            is_leaf=lambda x: isinstance(x, P))
        jitted = jax.jit(step, in_shardings=(ns(pspecs), ns(specs["token"]),
                                             ns(specs["state"]),
                                             NamedSharding(mesh, P())))
        with mesh:
            lowered = jitted.lower(params_sds, inp["token"], inp["state"],
                                   inp["pos"])
            compiled = lowered.compile()
        model_flops = 2.0 * active_param_count(cfg) * shape.global_batch

    mem = compiled.memory_analysis()
    rl, coll = H.analyze(compiled, rec["n_devices"], model_flops)
    rec.update(
        compile_s=round(time.time() - t0, 1),
        argument_bytes_per_device=getattr(mem, "argument_size_in_bytes", None),
        output_bytes_per_device=getattr(mem, "output_size_in_bytes", None),
        temp_bytes_per_device=getattr(mem, "temp_size_in_bytes", None),
        peak_bytes_per_device=(
            (getattr(mem, "argument_size_in_bytes", 0) or 0)
            + (getattr(mem, "temp_size_in_bytes", 0) or 0)),
        flops=rl.flops, hbm_bytes=rl.hbm_bytes, coll_bytes=rl.coll_bytes,
        model_flops=model_flops,
        compute_s=rl.compute_s, memory_s=rl.memory_s,
        collective_s=rl.collective_s, dominant=rl.dominant,
        useful_flops_ratio=rl.useful_flops_ratio,
        coll_bytes_by_kind=coll.bytes_by_kind,
        coll_count_by_kind=coll.count_by_kind,
    )
    if verbose:
        print(json.dumps(rec, indent=None, default=str))
    return rec


def main():
    # 512 placeholder host devices; read when the backend first starts, so
    # this must run before anything touches a device
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--no-seq-shard", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from repro.configs import ASSIGNED
    from repro.launch.shapes import SHAPES

    pairs = ([(args.arch, args.shape)] if not args.all else
             [(a, s) for a in ASSIGNED for s in SHAPES])
    results = []
    for arch, shape in pairs:
        try:
            results.append(run_one(arch, shape, multi_pod=args.multipod,
                                   seq_shard=not args.no_seq_shard))
        except Exception as e:  # record the failure — it is a bug to fix
            traceback.print_exc()
            results.append({"arch": arch, "shape": shape,
                            "mesh": "2x16x16" if args.multipod else "16x16",
                            "error": repr(e)})
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        tag = "multi" if args.multipod else "single"
        path = os.path.join(args.out, f"dryrun_{tag}.json")
        with open(path, "w") as f:
            json.dump(results, f, indent=1, default=str)
        print("wrote", path)
    ok = sum(1 for r in results if "error" not in r)
    print(f"dry-run: {ok}/{len(results)} pairs compiled")
    return 0 if ok == len(results) else 1


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
