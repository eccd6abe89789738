"""The Pallas kernels of the training path compile for a TPU v5e at
llama3.2-1b widths (d_model 2048, d_ff 8192, 8 KV heads of 64).

Nothing runs: the TPU compiler installed with jax compiles for a
described ``v5e:2x2`` topology, and refuses what the chip would refuse
(a block over the scoped VMEM, a slice off the tiling).  Interpret-mode
tests cannot see either.  The topology is described inside a fixture,
never at import: only one process may load the TPU library, and every
test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import fused_collectives as fc

D_MODEL, D_FF, KV_WIDTH = 2048, 8192, 8 * 64
TOKENS, SHARDS = 2048, 4


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("k,n", [
    (D_MODEL, D_MODEL),       # attention q / o projections
    (D_MODEL, KV_WIDTH),      # attention k / v projections
    (D_MODEL, D_FF),          # MLP gate / up
    (D_FF, D_MODEL),          # MLP down
])
def test_all_gather_matmul_compiles(one_chip, k, n):
    x = _sds((TOKENS, k), one_chip)
    w = _sds((SHARDS, k // SHARDS, n), one_chip)
    _assert_kernel(fc.all_gather_matmul.lower(
        x, w, interpret=False).compile())


def test_reduce_scatter_rmsnorm_compiles(one_chip):
    shards = _sds((SHARDS, TOKENS, D_MODEL), one_chip)
    scale = _sds((D_MODEL,), one_chip)
    _assert_kernel(fc.reduce_scatter_rmsnorm.lower(
        shards, scale, interpret=False).compile())


def test_reduce_scatter_adamw_compiles(one_chip):
    length = D_MODEL * D_FF
    seg = _sds((length,), one_chip)
    hyper = _sds((), one_chip)
    _assert_kernel(fc.reduce_scatter_adamw.lower(
        _sds((SHARDS, length), one_chip), seg, seg, seg, hyper, hyper,
        hyper, interpret=False).compile())
