"""Fused collective+compute Pallas kernels vs their unfused jnp
oracles (kernels.fused_collectives / kernels.ref), plus the
differentiable ``fused_dense`` wrapper and the launcher-side
``--xla-overlap`` preset.

Tolerance rationale: all three kernels differ from the references only
in f32 summation/association order (the shard reduction and matmul
partials), so fp32 inputs get a 1-2 ulp allclose band, never a loose
one; bf16 inputs get the usual half-precision band.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.fused_collectives import COL_TILE, ROW_TILE, SEG_TILE

RNG = np.random.default_rng(0)


def _shards(n, t, d, dtype=jnp.float32):
    return jnp.asarray(RNG.normal(size=(n, t, d)), jnp.float32) \
        .astype(dtype)


# -- reduce_scatter + rmsnorm --------------------------------------------- #

@pytest.mark.parametrize("n,t,d", [
    (2, ROW_TILE, 64),        # exactly one row tile
    (4, 2 * ROW_TILE, 32),    # multi-tile
    (3, 37, 48),              # odd rows: padded grid, ragged shard count
    (8, 1, 16),               # single row
])
def test_rs_rmsnorm_matches_ref_fp32(n, t, d):
    shards = _shards(n, t, d)
    scale = jnp.asarray(RNG.normal(size=(d,)), jnp.float32)
    got = ops.reduce_scatter_rmsnorm(shards, scale)
    want = ref.reduce_scatter_rmsnorm_ref(shards, scale)
    assert got.shape == (t, d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-7)


def test_rs_rmsnorm_bf16():
    shards = _shards(4, 96, 64, jnp.bfloat16)
    scale = jnp.asarray(RNG.normal(size=(64,)), jnp.bfloat16)
    got = ops.reduce_scatter_rmsnorm(shards, scale)
    want = ref.reduce_scatter_rmsnorm_ref(shards, scale)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=1e-2)


# -- reduce_scatter + AdamW ----------------------------------------------- #

def _adamw_inputs(n, length, dtype=jnp.float32):
    g = jnp.asarray(RNG.normal(size=(n, length)), jnp.float32)
    p = jnp.asarray(RNG.normal(size=(length,)), jnp.float32) \
        .astype(dtype)
    m = jnp.asarray(RNG.normal(size=(length,)) * 0.1, jnp.float32)
    v = jnp.asarray(RNG.random(size=(length,)) * 0.01, jnp.float32)
    return g, p, m, v


@pytest.mark.parametrize("n,length,wd", [
    (2, SEG_TILE, 0.0),           # one tile
    (4, 3 * SEG_TILE, 0.1),       # multi-tile + weight decay
    (3, 1000, 0.0),               # odd length: padded grid
    (6, 7, 0.01),                 # shorter than any tile
])
def test_rs_adamw_matches_ref(n, length, wd):
    g, p, m, v = _adamw_inputs(n, length)
    args = dict(lr=3e-3, bc1=1.0 - 0.9 ** 3, bc2=1.0 - 0.95 ** 3)
    got_p, got_m, got_v = ops.reduce_scatter_adamw(
        g, p, m, v, args["lr"], args["bc1"], args["bc2"],
        weight_decay=wd)
    want_p, want_m, want_v = ref.reduce_scatter_adamw_ref(
        g, p, m, v, args["lr"], args["bc1"], args["bc2"],
        weight_decay=wd)
    # same f32 math, shard sum may associate differently: 1-2 ulp
    for got, want in ((got_m, want_m), (got_v, want_v),
                      (got_p, want_p)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-7)


def test_rs_adamw_padding_leaves_tail_untouched():
    """The padded grid cells must not leak into the returned segment:
    moments past ``length`` would corrupt the next step if sliced
    wrong."""
    g, p, m, v = _adamw_inputs(2, SEG_TILE + 17)
    got_p, got_m, got_v = ops.reduce_scatter_adamw(
        g, p, m, v, 1e-3, 0.1, 0.05)
    assert got_p.shape == got_m.shape == got_v.shape \
        == (SEG_TILE + 17,)


# -- all_gather + matmul -------------------------------------------------- #

@pytest.mark.parametrize("n,t,ks,nout", [
    (2, ROW_TILE, 32, 48),    # one row tile
    (4, 200, 16, 64),         # odd rows: padded grid
    (8, 64, 8, 128),          # many shards
    (4, 64, 16, 2 * COL_TILE),        # two output column tiles
    (4, 64, 16, 2 * COL_TILE + 76),   # ragged width: padded last tile
])
def test_ag_matmul_matches_ref(n, t, ks, nout):
    x = jnp.asarray(RNG.normal(size=(t, n * ks)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(n, ks, nout)), jnp.float32)
    got = ops.all_gather_matmul(x, w)
    want = ref.all_gather_matmul_ref(x, w)
    assert got.shape == (t, nout)
    # same f32 accumulation, different summation order (per-shard
    # partials vs one dot): tight allclose, not bitwise
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_ag_matmul_rejects_contraction_mismatch():
    x = jnp.zeros((8, 48), jnp.float32)
    w = jnp.zeros((4, 16, 8), jnp.float32)    # 4*16 != 48
    with pytest.raises(ValueError, match="contraction mismatch"):
        ops.all_gather_matmul(x, w)


def test_fused_dense_forward_and_grads():
    """``fused_dense`` must match the reference matmul in value and in
    both gradients (its VJP is the plain-jnp transpose), including
    collapsed leading batch dims."""
    n, ks, nout = 4, 16, 24
    x = jnp.asarray(RNG.normal(size=(2, 5, n * ks)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(n, ks, nout)), jnp.float32)

    def fused(x, w):
        return jnp.sum(jnp.sin(ops.fused_dense(x, w)))

    def unfused(x, w):
        return jnp.sum(jnp.sin(x @ w.reshape(n * ks, nout)))

    np.testing.assert_allclose(float(fused(x, w)),
                               float(unfused(x, w)), rtol=1e-5)
    gx_f, gw_f = jax.grad(fused, argnums=(0, 1))(x, w)
    gx_u, gw_u = jax.grad(unfused, argnums=(0, 1))(x, w)
    assert gx_f.shape == x.shape and gw_f.shape == w.shape
    np.testing.assert_allclose(np.asarray(gx_f), np.asarray(gx_u),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gw_f),
                               np.asarray(gw_u).reshape(n, ks, nout),
                               rtol=1e-4, atol=1e-5)


def test_dense_helper_dispatches_on_stacked_shards():
    """``models.layers.dense`` routes StackedShards through the fused
    kernel and plain arrays through ``@`` - same numbers either way."""
    from repro.core.overlap import StackedShards
    from repro.models.layers import dense
    n, ks, nout = 2, 8, 12
    x = jnp.asarray(RNG.normal(size=(3, n * ks)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(n, ks, nout)), jnp.float32)
    flat = w.reshape(n * ks, nout)
    got = dense(x, StackedShards(w))
    np.testing.assert_allclose(np.asarray(got), np.asarray(x @ flat),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(dense(x, flat)),
                                  np.asarray(x @ flat))


def test_stacked_shards_is_a_pytree():
    from repro.core.overlap import StackedShards
    s = StackedShards(jnp.ones((2, 3, 4)))
    leaves = jax.tree.leaves(s)
    assert len(leaves) == 1 and leaves[0].shape == (2, 3, 4)
    mapped = jax.tree.map(lambda a: a * 2, s)
    assert isinstance(mapped, StackedShards)
    np.testing.assert_array_equal(np.asarray(mapped.shards), 2.0)


# -- ledger fused split --------------------------------------------------- #

def test_ledger_fused_context_and_fallback_audit():
    from repro.core import ledger
    ledger.reset()
    ledger.record("all_gather", 1000.0)
    with ledger.fused():
        ledger.record("all_gather", 500.0)
    ledger.record("reduce_scatter", 300.0, fused=True)
    ledger.record_fallback("all_to_all", level="node", fabric="cxl")
    snap = ledger.snapshot()
    assert snap["fused_bytes"] == {"all_gather": 500.0,
                                   "reduce_scatter": 300.0}
    assert snap["total_fused_bytes"] == 800.0
    assert snap["wire_bytes"]["all_gather"] == 1500.0
    fb = snap["fallbacks"]
    assert len(fb) == 1 and fb[0]["primitive"] == "all_to_all"
    assert fb[0]["reason"] == "flat_on_ragged"
    ledger.reset()
    assert ledger.snapshot()["fallbacks"] == []
    assert ledger.snapshot()["total_fused_bytes"] == 0.0


# -- launcher --xla-overlap preset ---------------------------------------- #

def test_xla_overlap_preset(monkeypatch):
    from repro.launch import xla
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    # absent flag: no-op
    assert not xla.apply_overlap_preset([])
    assert "XLA_FLAGS" not in __import__("os").environ
    # applied (forced past the CUDA-jaxlib gate): all flags land
    assert xla.apply_overlap_preset(["--xla-overlap"], force=True)
    flags = __import__("os").environ["XLA_FLAGS"].split()
    assert all(f in flags for f in xla.OVERLAP_FLAGS)
    # an env-pinned flag wins over the preset, with a warning
    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_gpu_enable_latency_hiding_scheduler=false")
    with pytest.warns(UserWarning, match="keeping it"):
        xla.apply_overlap_preset(["--xla-overlap"], force=True)
    flags = __import__("os").environ["XLA_FLAGS"].split()
    assert "--xla_gpu_enable_latency_hiding_scheduler=false" in flags
    assert "--xla_gpu_enable_latency_hiding_scheduler=true" not in flags


def test_xla_overlap_preset_skips_without_cuda(monkeypatch):
    from repro.launch import xla
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    monkeypatch.setattr(xla, "_gpu_jaxlib", lambda: False)
    with pytest.warns(UserWarning, match="no CUDA jaxlib"):
        assert not xla.apply_overlap_preset(["--xla-overlap"])
    assert "XLA_FLAGS" not in __import__("os").environ
