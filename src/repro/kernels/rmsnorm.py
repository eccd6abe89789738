"""Pallas TPU kernel: fused RMSNorm.

Every decoder row begins with an RMSNorm over d_model; unfused it costs
three HBM passes (square-mean, rsqrt-scale, multiply).  The kernel fuses
them into one read + one write per tile with the f32 variance reduction
in VMEM.  Rows (tokens) tile the grid; d_model stays resident per tile.

x: (T, D), scale: (D,) -> (T, D).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROW_TILE = 256


def _kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)              # (rows, D)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps) * s_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "rows", "interpret"))
def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-5,
             rows: int = ROW_TILE, *, interpret: bool) -> jnp.ndarray:
    t, d = x.shape
    r = min(rows, t)
    pad = (-t) % r
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=((t + pad) // r,),
        in_specs=[pl.BlockSpec((r, d), lambda i: (i, 0)),
                  pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=pl.BlockSpec((r, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((t + pad, d), x.dtype),
        interpret=interpret,
    )(x, scale)
    return out[:t]
