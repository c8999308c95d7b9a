"""Compile the main-path kernels for a described TPU v5e, with no chip attached.

The TPU compiler refuses what interpret mode accepts: blocks not tiled by
(8, 128), vector loads from scalar memory, more scoped VMEM than the limit.
Each case compiles one kernel wrapper at the 2-NN's ``w1`` width
(3072·256 features, N=128 workers) with ``interpret=False`` and checks the
compiled program calls the kernel (``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and test workers import every
test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.gossip_mix.ops import masked_gossip_mix
from repro.kernels.sparse_gossip.ops import (sparse_gossip_rows,
                                             sparse_scatter_rows)

N = 128
D_W1 = 3072 * 256   # the 2-NN's first weight matrix, flattened per worker


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


def _compiled_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("A,dtype", [(16, jnp.float32), (128, jnp.float32),
                                     (16, jnp.bfloat16)])
def test_sparse_gossip_rows_compiles(one_chip, A, dtype):
    text = _compiled_text(
        lambda W, G, P, m, w: sparse_gossip_rows(W, G, P, m, w,
                                                 interpret=False),
        one_chip, ((N, D_W1), dtype), ((A, D_W1), dtype), ((A, A), dtype),
        ((A,), dtype), ((A,), jnp.int32))
    assert "tpu_custom_call" in text


# A=13 is padded to 16 lanes with -1 workers inside the wrapper.
@pytest.mark.parametrize("A,dtype", [(16, jnp.float32), (128, jnp.float32),
                                     (13, jnp.float32), (16, jnp.bfloat16)])
def test_sparse_scatter_rows_compiles(one_chip, A, dtype):
    text = _compiled_text(
        # compiled for the TPU, never run here
        lambda X, rows, w: sparse_scatter_rows(X, rows, w, interpret=False),  # repro: disable=kernel-gate
        one_chip, ((N, D_W1), dtype), ((A, D_W1), dtype), ((A,), jnp.int32))
    assert "tpu_custom_call" in text


# N=1024 holds two (N, N) matrices resident: over the default scoped VMEM.
@pytest.mark.parametrize("n,dtype", [(128, jnp.float32), (1024, jnp.float32),
                                     (128, jnp.bfloat16)])
def test_masked_gossip_mix_compiles(one_chip, n, dtype):
    text = _compiled_text(
        lambda W, G, P, m: masked_gossip_mix(W, G, P, m, interpret=False),
        one_chip, ((n, D_W1), dtype), ((n, D_W1), dtype), ((n, n), dtype),
        ((n,), dtype))
    assert "tpu_custom_call" in text
