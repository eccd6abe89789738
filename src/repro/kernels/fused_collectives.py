"""Pallas TPU kernels fusing collective edges into adjacent compute.

The collective layer and the kernel layer used to meet only through
HBM: a ReduceScatter lands its bytes, then rmsnorm (or the optimizer)
reads the very same bytes right back; an FSDP AllGather materializes a
full weight only for the next matmul to stream it in again.  The three
kernels here close that gap (ROADMAP item 4):

* ``reduce_scatter_rmsnorm`` - the consumer-side final accumulation of
  a ReduceScatter (a rank holds the n_src peers' partials of its own
  segment, ``chunked_reduce`` style) with the rmsnorm epilogue applied
  in-register before writeback: one HBM write instead of a write + a
  full read + another write.
* ``reduce_scatter_adamw`` - the same accumulation with the AdamW
  update as the epilogue: the summed gradient segment never exists in
  HBM; the kernel emits updated param + moments directly (the FSDP
  grad-sync -> optimizer hot path).
* ``all_gather_matmul`` - a matmul whose contraction streams the
  gathered operand shard-by-shard: the grid's innermost axis walks the
  rank-major shard stack, so Pallas's pipelined block fetch brings
  shard k+1 into VMEM while shard k is on the MXU (the
  ``flash_attention`` kv-innermost pattern).  The output columns are
  tiled too, so a weight block is one shard's rows by ``COL_TILE``
  columns whatever the layer's width.  ``fused_dense`` wraps it
  with a custom VJP so it can sit on the differentiated FSDP path
  (``models.layers.dense``); the backward pass is plain-jnp reference
  math.

Pure-jnp oracles live in ``kernels.ref``; ``kernels.ops`` carries the
public wrappers and decides ``interpret`` for the backend.  Accumulation
is f32 throughout, matching the unfused reference composition
op-for-op so fp32 inputs reproduce it bitwise where the schedule
permits (the elementwise epilogues; the matmul differs only in f32
summation order).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROW_TILE = 128          # token rows per grid step (rs+rmsnorm, matmul)
SEG_TILE = 2048         # flat elements per grid step (rs+adamw)
# output columns per grid step (matmul): a (Ks, COL_TILE) f32 weight
# block, double-buffered, stays inside the default scoped VMEM for the
# widest llama3.2-1b shard (Ks = 8192 / 4) - 2 x 4 MiB
COL_TILE = 512


# --------------------------------------------------------------------- #
# reduce_scatter + rmsnorm epilogue
# --------------------------------------------------------------------- #

def _rs_rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps: float):
    # x_ref: (n_src, rows, D) VMEM block - the peers' partials of this
    # rank's segment.  Accumulate f32, normalize in-register, write once.
    acc = jnp.sum(x_ref[...].astype(jnp.float32), axis=0)   # (rows, D)
    var = jnp.mean(jnp.square(acc), axis=-1, keepdims=True)
    y = acc * jax.lax.rsqrt(var + eps) * s_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "rows", "interpret"))
def reduce_scatter_rmsnorm(shards: jnp.ndarray, scale: jnp.ndarray,
                           eps: float = 1e-5, rows: int = ROW_TILE, *,
                           interpret: bool) -> jnp.ndarray:
    """``shards``: (n_src, T, D) peer partials -> (T, D) normalized sum."""
    n_src, t, d = shards.shape
    r = min(rows, t)
    pad = (-t) % r
    if pad:
        shards = jnp.pad(shards, ((0, 0), (0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_rs_rmsnorm_kernel, eps=eps),
        grid=((t + pad) // r,),
        in_specs=[pl.BlockSpec((n_src, r, d), lambda i: (0, i, 0)),
                  pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=pl.BlockSpec((r, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((t + pad, d), shards.dtype),
        interpret=interpret,
    )(shards, scale)
    return out[:t]


# --------------------------------------------------------------------- #
# reduce_scatter + AdamW epilogue
# --------------------------------------------------------------------- #

def _rs_adamw_kernel(g_ref, p_ref, m_ref, v_ref, h_ref,
                     po_ref, mo_ref, vo_ref, *, b1: float, b2: float,
                     eps: float, weight_decay: float):
    # g_ref: (n_src, tile) grad partials; h_ref: (3,) = [lr, bc1, bc2]
    # (traced scalars - lr comes from a schedule).  The summed gradient
    # lives only in VMEM; updated param + f32 moments write out.
    g = jnp.sum(g_ref[...].astype(jnp.float32), axis=0)
    lr, bc1, bc2 = h_ref[0], h_ref[1], h_ref[2]
    m = b1 * m_ref[...] + (1.0 - b1) * g
    v = b2 * v_ref[...] + (1.0 - b2) * jnp.square(g)
    mhat = m / bc1
    vhat = v / bc2
    delta = mhat / (jnp.sqrt(vhat) + eps)
    p32 = p_ref[...].astype(jnp.float32)
    if weight_decay:
        delta = delta + weight_decay * p32
    po_ref[...] = (p32 - lr * delta).astype(po_ref.dtype)
    mo_ref[...] = m
    vo_ref[...] = v


@functools.partial(jax.jit, static_argnames=(
    "b1", "b2", "eps", "weight_decay", "tile", "interpret"))
def reduce_scatter_adamw(shards: jnp.ndarray, p: jnp.ndarray,
                         m: jnp.ndarray, v: jnp.ndarray, lr, bc1, bc2,
                         b1: float = 0.9, b2: float = 0.95,
                         eps: float = 1e-8, weight_decay: float = 0.0,
                         tile: int = SEG_TILE, *,
                         interpret: bool) -> tuple:
    """``shards``: (n_src, L) grad partials; ``p``/``m``/``v``: (L,)
    param and f32 moments; ``lr``/``bc1``/``bc2`` traced scalars (the
    schedule LR and bias corrections ``1 - b^step``).  Returns
    (new_p, new_m, new_v) - the AdamW math of ``optim.adamw_update``
    applied to the in-register sum of the partials."""
    n_src, length = shards.shape
    hyper = jnp.stack([jnp.float32(lr), jnp.float32(bc1),
                       jnp.float32(bc2)])
    t = min(tile, length)
    pad = (-length) % t
    if pad:
        shards = jnp.pad(shards, ((0, 0), (0, pad)))
        p = jnp.pad(p, (0, pad))
        m = jnp.pad(m, (0, pad))
        v = jnp.pad(v, (0, pad))
    padded = length + pad
    new_p, new_m, new_v = pl.pallas_call(
        functools.partial(_rs_adamw_kernel, b1=b1, b2=b2, eps=eps,
                          weight_decay=weight_decay),
        grid=(padded // t,),
        in_specs=[pl.BlockSpec((n_src, t), lambda i: (0, i)),
                  pl.BlockSpec((t,), lambda i: (i,)),
                  pl.BlockSpec((t,), lambda i: (i,)),
                  pl.BlockSpec((t,), lambda i: (i,)),
                  pl.BlockSpec((3,), lambda i: (0,))],
        out_specs=[pl.BlockSpec((t,), lambda i: (i,)),
                   pl.BlockSpec((t,), lambda i: (i,)),
                   pl.BlockSpec((t,), lambda i: (i,))],
        out_shape=[jax.ShapeDtypeStruct((padded,), p.dtype),
                   jax.ShapeDtypeStruct((padded,), jnp.float32),
                   jax.ShapeDtypeStruct((padded,), jnp.float32)],
        interpret=interpret,
    )(shards, p, m, v, hyper)
    return new_p[:length], new_m[:length], new_v[:length]


# --------------------------------------------------------------------- #
# all_gather fused into the consuming matmul's prologue
# --------------------------------------------------------------------- #

def _ag_matmul_kernel(x_ref, w_ref, o_ref, acc_scr, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # shard k multiplies while the pipeline fetches shard k+1 (the
    # innermost grid axis is sequential on TPU; Pallas double-buffers
    # the HBM->VMEM block copies).
    acc_scr[...] += jax.lax.dot_general(
        x_ref[...], w_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _finalize():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rows", "interpret"))
def all_gather_matmul(x: jnp.ndarray, w_shards: jnp.ndarray,
                      rows: int = ROW_TILE, *,
                      interpret: bool) -> jnp.ndarray:
    """``x``: (T, n*Ks) activations; ``w_shards``: (n, Ks, N) rank-major
    gathered weight shards.  Returns ``x @ concat(w_shards)`` without
    ever materializing the concatenated weight: the contraction streams
    the shard stack through VMEM, one shard per (sequential) grid step,
    for each (``rows``, ``COL_TILE``) output tile.
    """
    n, ks, nout = w_shards.shape
    t, kdim = x.shape
    if kdim != n * ks:
        raise ValueError(
            f"contraction mismatch: x has {kdim} columns, shards give "
            f"{n}x{ks}")
    r = min(rows, t)
    c = min(COL_TILE, nout)
    pad_t, pad_n = (-t) % r, (-nout) % c
    if pad_t:
        x = jnp.pad(x, ((0, pad_t), (0, 0)))
    if pad_n:
        w_shards = jnp.pad(w_shards, ((0, 0), (0, 0), (0, pad_n)))
    out = pl.pallas_call(
        functools.partial(_ag_matmul_kernel, nk=n),
        grid=((t + pad_t) // r, (nout + pad_n) // c, n),
        in_specs=[pl.BlockSpec((r, ks), lambda i, j, k: (i, k)),
                  pl.BlockSpec((1, ks, c), lambda i, j, k: (k, 0, j))],
        out_specs=pl.BlockSpec((r, c), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((t + pad_t, nout + pad_n),
                                       x.dtype),
        scratch_shapes=[pltpu.VMEM((r, c), jnp.float32)],
        interpret=interpret,
    )(x, w_shards)
    return out[:t, :nout]


# --------------------------------------------------------------------- #
# differentiable wrapper for the training path
# --------------------------------------------------------------------- #

@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def fused_dense(x: jnp.ndarray, w_shards: jnp.ndarray,
                interpret: bool) -> jnp.ndarray:
    """``x @ concat(w_shards)`` over the last dim of ``x`` (leading dims
    are batch), forward via :func:`all_gather_matmul`.  Differentiable:
    the VJP is the plain-jnp reference matmul transpose (the fusion win
    is a forward-bandwidth property; the backward pass keeps the
    unfused reference numerics)."""
    return _fused_dense_fwd(x, w_shards, interpret)[0]


def _fused_dense_fwd(x, w_shards, interpret):
    n, ks, nout = w_shards.shape
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    y = all_gather_matmul(x2, w_shards, interpret=interpret)
    return y.reshape(lead + (nout,)), (x2, w_shards, lead)


def _fused_dense_bwd(interpret, res, g):
    x2, w_shards, lead = res
    n, ks, nout = w_shards.shape
    g2 = g.reshape(-1, nout)
    w = w_shards.reshape(n * ks, nout)
    dx = jax.lax.dot_general(
        g2, w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32).astype(x2.dtype)
    dw = jax.lax.dot_general(
        x2, g2, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(w_shards.dtype)
    return dx.reshape(lead + (n * ks,)), dw.reshape(n, ks, nout)


fused_dense.defvjp(_fused_dense_fwd, _fused_dense_bwd)
