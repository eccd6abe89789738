"""Pallas TPU kernel: selective-state-space scan (Mamba-1 core).

Computes, per batch and channel block:

    h_t = exp(dt_t * A) * h_{t-1} + (dt_t * x_t) * B_t
    y_t = <h_t, C_t> + D * x_t

TPU adaptation (DESIGN.md): the CUDA kernel's warp-level scan does not
map to the MXU/VPU, so the kernel keeps the recurrent state
(block_d, N) resident in VMEM scratch and walks the sequence dimension
as the innermost (sequential) grid axis, processing ``block_l`` steps
per invocation with a ``fori_loop`` of rank-2 VPU ops.  Channels are the
vectorized dim (block_d lanes), so throughput is bound by dt*A exps and
the (block_d, N) FMAs - exactly the arithmetic the paper's GPU kernel
does per thread, re-vectorized for the VPU.

Layouts: x/dt (B, L, D), A (D, N), Bs/Cs (B, L, N), D_res (D,)
-> y (B, L, D).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_D = 256
BLOCK_L = 128


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, dres_ref, y_ref, h_scr,
            *, block_l: int):
    jl = pl.program_id(2)

    @pl.when(jl == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    a = a_ref[...].astype(jnp.float32)            # (bd, N)
    dres = dres_ref[...].astype(jnp.float32)      # (bd,)

    def step(t, h):
        xt = x_ref[0, t].astype(jnp.float32)      # (bd,)
        dtt = dt_ref[0, t].astype(jnp.float32)    # (bd,)
        bt = b_ref[0, t].astype(jnp.float32)      # (N,)
        ct = c_ref[0, t].astype(jnp.float32)      # (N,)
        decay = jnp.exp(dtt[:, None] * a)         # (bd, N)
        h = decay * h + (dtt * xt)[:, None] * bt[None, :]
        y = jnp.sum(h * ct[None, :], axis=-1) + dres * xt
        y_ref[0, t] = y.astype(y_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, block_l, step, h_scr[...])


@functools.partial(jax.jit, static_argnames=(
    "block_d", "block_l", "interpret"))
def ssm_scan(x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray,
             bs: jnp.ndarray, cs: jnp.ndarray, d_res: jnp.ndarray,
             block_d: int = BLOCK_D, block_l: int = BLOCK_L,
             *, interpret: bool) -> jnp.ndarray:
    """See module docstring for shapes."""
    b, l, d = x.shape
    n = a.shape[1]
    bd = min(block_d, d)
    bl = min(block_l, l)
    if d % bd or l % bl:
        raise ValueError("d / l must divide the block sizes")
    grid = (b, d // bd, l // bl)   # seq innermost: sequential carry
    return pl.pallas_call(
        functools.partial(_kernel, block_l=bl),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bl, bd), lambda ib, idd, il: (ib, il, idd)),
            pl.BlockSpec((1, bl, bd), lambda ib, idd, il: (ib, il, idd)),
            pl.BlockSpec((bd, n), lambda ib, idd, il: (idd, 0)),
            pl.BlockSpec((1, bl, n), lambda ib, idd, il: (ib, il, 0)),
            pl.BlockSpec((1, bl, n), lambda ib, idd, il: (ib, il, 0)),
            pl.BlockSpec((bd,), lambda ib, idd, il: (idd,)),
        ],
        out_specs=pl.BlockSpec((1, bl, bd),
                               lambda ib, idd, il: (ib, il, idd)),
        out_shape=jax.ShapeDtypeStruct((b, l, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((bd, n), jnp.float32)],
        interpret=interpret,
    )(x, dt, a, bs, cs, d_res)
