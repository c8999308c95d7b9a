"""Distributed-layer tests: sharding policy, mesh views, gossip equivalence,
and a scaled-down dry-run — all in subprocesses so the main test process keeps
its single CPU device (XLA fixes the device count at first use)."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    # CPU only: a child that reached for the TPU library would contend with
    # the test worker that may hold it
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


class TestShardingPolicy:
    def test_param_specs_cover_all_leaves(self):
        import jax
        from jax.sharding import PartitionSpec as P
        from repro.configs import get_config
        from repro.launch.sharding import param_pspecs
        from repro.models.transformer import init_model

        class FakeMesh:
            shape = {"fsdp": 4, "model": 16, "data": 16}

        for name in ("qwen3-8b", "grok-1-314b", "rwkv6-1.6b",
                     "recurrentgemma-2b"):
            cfg = get_config(name)
            shapes = jax.eval_shape(lambda k: init_model(k, cfg),
                                    jax.random.PRNGKey(0))
            specs = param_pspecs(shapes, FakeMesh(), fsdp="fsdp",
                                 model="model")
            flat_shapes = jax.tree.leaves(shapes)
            flat_specs = jax.tree.leaves(
                specs, is_leaf=lambda x: isinstance(x, P))
            assert len(flat_shapes) == len(flat_specs)
            for sh, sp in zip(flat_shapes, flat_specs):
                assert len(sp) <= len(sh.shape)
                # every named axis divides its dim
                for dim, axis in zip(sh.shape, tuple(sp)):
                    if axis is None:
                        continue
                    axes = axis if isinstance(axis, tuple) else (axis,)
                    size = int(np.prod([FakeMesh.shape[a] for a in axes]))
                    assert dim % size == 0, (name, sh.shape, tuple(sp))

    def test_expert_parallel_when_divisible(self):
        import jax
        from repro.configs import get_config
        from repro.launch.sharding import param_pspecs
        from repro.models.transformer import init_model

        class FakeMesh:
            shape = {"fsdp": 4, "model": 16}

        cfg = get_config("arctic-480b")  # 128 experts % 16 == 0
        shapes = jax.eval_shape(lambda k: init_model(k, cfg),
                                jax.random.PRNGKey(0))
        specs = param_pspecs(shapes, FakeMesh(), fsdp="fsdp", model="model")
        spec = specs["layers"]["ffn"]["w_gate"]
        assert tuple(spec)[1] == "model"  # E axis expert-parallel


class TestMeshViews:
    def test_hierarchical_view_shapes(self):
        out = run_py("""
            import jax
            from repro.launch.mesh import hierarchical_view
            from jax.sharding import AxisType
            base = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
            v, axes = hierarchical_view(base, 2, 2)
            print(v.axis_names, v.shape["worker"], v.shape["fsdp"])
            v1, axes1 = hierarchical_view(base, 4, 1)
            print(v1.axis_names, axes1.fsdp)
        """, devices=8)
        assert "('worker', 'fsdp', 'model') 2 2" in out
        assert "('worker', 'model') None" in out

    def test_production_mesh_axes(self):
        out = run_py("""
            import jax
            from repro.launch.mesh import make_production_mesh
            # 512 host devices: both meshes must build
            m1 = make_production_mesh()
            m2 = make_production_mesh(multi_pod=True)
            print(m1.axis_names, m1.devices.size)
            print(m2.axis_names, m2.devices.size)
        """, devices=512)
        assert "('data', 'model') 256" in out
        assert "('pod', 'data', 'model') 512" in out


class TestGossipEquivalence:
    def test_shardmap_ring_matches_dense_mixing(self):
        """ppermute ring gossip == dense Pᵀ·W with ring Metropolis weights."""
        out = run_py("""
            import jax, jax.numpy as jnp, numpy as np
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
            from repro.launch.mesh import TrainAxes
            from repro.launch.steps import _tree_gossip, default_gossip_weights
            from repro.core.consensus import metropolis_matrix
            from jax.sharding import AxisType

            n = 4
            mesh = jax.make_mesh((n,), ("worker",), axis_types=(AxisType.Auto,))
            axes = TrainAxes(pod=None, worker="worker", fsdp=None, model="model")
            W = {"w": jnp.arange(n * 6, dtype=jnp.float32).reshape(n, 6)}
            spec = {"w": P("worker", None)}
            gw = default_gossip_weights(n, False)
            f = jax.shard_map(lambda W: _tree_gossip(W, axes, n, gw),
                          mesh=mesh, in_specs=(spec,), out_specs=spec)
            out = f(W)
            Pm = metropolis_matrix(n, [(i, (i + 1) % n) for i in range(n)])
            ref = Pm.T @ np.asarray(W["w"])
            err = float(np.abs(np.asarray(out["w"]) - ref).max())
            print("ERR", err)
        """, devices=4)
        assert float(out.strip().split()[-1]) < 1e-5

    def test_multipod_gossip_doubly_stochastic(self):
        """Pod-edge mixing preserves the mean (doubly stochastic check)."""
        out = run_py("""
            import jax, jax.numpy as jnp, numpy as np
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.launch.mesh import TrainAxes
            from repro.launch.steps import _tree_gossip, default_gossip_weights
            from jax.sharding import AxisType
            mesh = jax.make_mesh((2, 2), ("pod", "worker"),
                             axis_types=(AxisType.Auto,) * 2)
            axes = TrainAxes(pod="pod", worker="worker", fsdp=None, model="model")
            W = {"w": jax.random.normal(jax.random.PRNGKey(0), (4, 5))}
            spec = {"w": P(("pod", "worker"), None)}
            gw = default_gossip_weights(2, True)
            f = jax.shard_map(lambda W: _tree_gossip(W, axes, 2, gw),
                          mesh=mesh, in_specs=(spec,), out_specs=spec)
            out = f(W)
            print("MEAN_ERR",
                  float(np.abs(np.asarray(out["w"]).mean(0)
                               - np.asarray(W["w"]).mean(0)).max()))
        """, devices=4)
        assert float(out.strip().split()[-1]) < 1e-5


class TestDryRunSmall:
    """Scaled-down dry-run through the exact dryrun code path."""

    def test_train_and_decode_lower_on_small_mesh(self):
        out = run_py("""
            import jax, jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.configs import get_config
            from repro.launch import sharding as S, shapes as SH, steps as ST
            from repro.launch.mesh import hierarchical_view
            from repro.models.transformer import init_model
            from jax.sharding import AxisType

            base = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
            view, axes = hierarchical_view(base, 2, 2)
            cfg = get_config("qwen3-8b").reduced()
            nw = 2
            params_sds = jax.eval_shape(ST.stacked_init(cfg, nw),
                                        jax.random.PRNGKey(0))
            pspecs = S.param_pspecs(params_sds, view, fsdp=axes.fsdp,
                                    model=axes.model,
                                    worker_axes=axes.worker_axes)
            shape = SH.InputShape("t", "train", 64, 8)
            batch_sds, bspecs = SH.train_input_specs(cfg, shape, nw, axes)
            step = ST.build_train_step(cfg, nw, axes, view, pspecs,
                                       logit_chunk=16)
            ns = lambda s: jax.tree.map(lambda x: NamedSharding(view, x), s,
                                        is_leaf=lambda x: isinstance(x, P))
            gw = ST.gossip_weights_spec()
            j = jax.jit(step, in_shardings=(
                ns(pspecs), ns(bspecs), NamedSharding(view, P()),
                jax.tree.map(lambda _: NamedSharding(view, P()), gw)))
            with view:
                c = j.lower(params_sds, batch_sds,
                            jax.ShapeDtypeStruct((), jnp.float32), gw).compile()
            assert c.memory_analysis() is not None
            print("TRAIN_OK")

            mesh = base
            cfg2 = SH.shape_config(get_config("rwkv6-1.6b").reduced(),
                                   SH.SHAPES["long_500k"])
            shape2 = SH.InputShape("d", "decode", 256, 4)
            p_sds = jax.eval_shape(lambda k: init_model(k, cfg2),
                                   jax.random.PRNGKey(0))
            psp = S.param_pspecs(p_sds, mesh, fsdp="data", model="model")
            inp, specs = SH.decode_input_specs(cfg2, shape2, mesh)
            sstep = ST.build_serve_step(cfg2)
            nsm = lambda s: jax.tree.map(lambda x: NamedSharding(mesh, x), s,
                                         is_leaf=lambda x: isinstance(x, P))
            j2 = jax.jit(sstep, in_shardings=(
                nsm(psp), nsm(specs["token"]), nsm(specs["state"]),
                NamedSharding(mesh, P())))
            with mesh:
                c2 = j2.lower(p_sds, inp["token"], inp["state"],
                              inp["pos"]).compile()
            print("DECODE_OK")
        """, devices=8)
        assert "TRAIN_OK" in out and "DECODE_OK" in out


class TestHloAnalysis:
    def test_trip_count_corrected_flops(self):
        """Custom HLO cost model multiplies while bodies by trip count."""
        out = run_py("""
            import jax, jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.launch.hlo_analysis import analyze_hlo_text
            from jax.sharding import AxisType
            mesh = jax.make_mesh((2, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
            def f(w, x):
                def body(c, wi):
                    return jnp.tanh(c @ wi), ()
                return jax.lax.scan(body, x, w)[0].sum()
            w = jax.ShapeDtypeStruct((5, 64, 64), jnp.float32)
            x = jax.ShapeDtypeStruct((8, 64), jnp.float32)
            j = jax.jit(f, in_shardings=(
                NamedSharding(mesh, P(None, None, "model")),
                NamedSharding(mesh, P("data", None))))
            with mesh:
                c = j.lower(w, x).compile()
            cost = analyze_hlo_text(c.as_text())
            print("FLOPS", cost.flops)
            print("AG", cost.collectives.bytes_by_kind["all-gather"])
        """, devices=4)
        lines = dict(l.split() for l in out.strip().splitlines())
        assert float(lines["FLOPS"]) == pytest.approx(5 * 2 * 4 * 32 * 64, rel=0.05)
        assert float(lines["AG"]) == pytest.approx(5 * 4 * 32 * 4, rel=0.05)

    def test_parser_on_synthetic_hlo(self):
        from repro.launch.hlo_analysis import analyze_hlo_text
        hlo = """
HloModule test, num_partitions=2

%body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,8]{1,0} get-tuple-element(%p), index=1
  %one = s32[] constant(1)
  %i2 = s32[] add(%i, %one)
  %d = f32[8,8]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,8]{1,0} all-reduce(%d), replica_groups={}
  ROOT %t = (s32[], f32[8,8]{1,0}) tuple(%i2, %ar)
}

%cond (p: (s32[], f32[8,8])) -> pred[] {
  %p = (s32[], f32[8,8]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(7)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8]{1,0} parameter(0)
  %z = s32[] constant(0)
  %t0 = (s32[], f32[8,8]{1,0}) tuple(%z, %a)
  %w = (s32[], f32[8,8]{1,0}) while(%t0), condition=%cond, body=%body
  ROOT %r = f32[8,8]{1,0} get-tuple-element(%w), index=1
}
"""
        cost = analyze_hlo_text(hlo)
        assert cost.flops == pytest.approx(7 * 2 * 8 * 8 * 8)
        assert cost.collectives.bytes_by_kind["all-reduce"] == pytest.approx(
            7 * 8 * 8 * 4)
        assert cost.collectives.count_by_kind["all-reduce"] == 7
