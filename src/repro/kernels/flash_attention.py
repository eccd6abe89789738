"""Pallas TPU kernel: blocked causal attention (FlashAttention forward).

Canonical TPU formulation: 3-D grid (batch*heads, q_blocks, kv_blocks)
with the kv dimension innermost (sequential on TPU), online-softmax state
(m, l, acc) carried across kv steps in VMEM scratch, initialized at
jk == 0 and written out at the last kv block.  Block shapes are
MXU-aligned (q_block x head_dim and head_dim x kv_block matmuls).

Layout: q/k/v (BH, L, D) - the wrapper folds batch and (already
GQA-expanded) heads.  Supports causal masking and a sliding window.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
BLOCK_Q = 128
BLOCK_K = 128


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window, nk: int, block_q: int,
            block_k: int):
    iq = pl.program_id(1)
    jk = pl.program_id(2)

    @pl.when(jk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]                                  # (bq, d)
    k = k_ref[0]                                  # (bk, d)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # (bq, bk)

    qpos = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = jk * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones_like(s, dtype=jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    acc_scr[...] = acc_scr[...] * corr + pv
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(jk == nk - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-20)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_k", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, window=None,
                    block_q: int = BLOCK_Q, block_k: int = BLOCK_K,
                    *, interpret: bool) -> jnp.ndarray:
    """q/k/v: (BH, L, D) with L divisible by the block sizes."""
    bh, lq, d = q.shape
    lk = k.shape[1]
    if lq % block_q or lk % block_k:
        raise ValueError("sequence must divide the block size")
    nq, nk = lq // block_q, lk // block_k
    scale = 1.0 / math.sqrt(d)
    kern = functools.partial(_kernel, scale=scale, causal=causal,
                             window=window, nk=nk, block_q=block_q,
                             block_k=block_k)
    return pl.pallas_call(
        kern,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, lq, d), q.dtype),
        scratch_shapes=[
            # (bq, 1) running max / normalizer and (bq, d) accumulator
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
