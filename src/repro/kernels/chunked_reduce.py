"""Pallas TPU kernel: chunked multi-source reduction.

This is the consumer-side hot spot of the paper's AllReduce / Reduce /
ReduceScatter: after the retrieve phase a rank holds N peers' chunks and
reduces them locally ("each rank must perform its own full reduction",
Sec. 5.2).  On TPU the chunks arrive via the ppermute schedule; this
kernel fuses the N-way add over VMEM-resident tiles with f32
accumulation, one grid step per output tile - the tile size is the
paper's slicing-factor chunk mapped to VMEM.

x: (n_src, length) -> out: (length,) = sum over sources.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_TILE = 2048


def _kernel(x_ref, o_ref):
    # x_ref: (n_src, tile) VMEM block; accumulate in f32 on the VPU.
    acc = jnp.sum(x_ref[...].astype(jnp.float32), axis=0)
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def chunked_reduce(x: jnp.ndarray, tile: int = DEFAULT_TILE,
                   *, interpret: bool) -> jnp.ndarray:
    """Sum ``x`` (n_src, length) over sources, tiled along length."""
    n_src, length = x.shape
    pad = (-length) % tile
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    padded = length + pad
    grid = (padded // tile,)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((n_src, tile), lambda i: (0, i))],
        out_specs=pl.BlockSpec((tile,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((padded,), x.dtype),
        interpret=interpret,
    )(x)
    return out[:length]
