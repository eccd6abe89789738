"""Jit'd public wrappers for the Pallas kernels.

``_interpret`` is the one place that decides how a kernel runs: compiled
on a TPU, through the Pallas interpreter on the CPU (the test backend:
the kernel body runs in Python), and not at all on any other backend.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import chunked_reduce as _cr
from repro.kernels import flash_attention as _fa
from repro.kernels import fused_collectives as _fc
from repro.kernels import rmsnorm as _rn
from repro.kernels import ssm_scan as _ss


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas kernels are written for TPU (interpreted on CPU); "
        f"the {backend!r} backend runs neither")


def chunked_reduce(x: jnp.ndarray, tile: int = _cr.DEFAULT_TILE
                   ) -> jnp.ndarray:
    return _cr.chunked_reduce(x, tile=tile, interpret=_interpret())


def flash_attention(q, k, v, causal: bool = True, window=None,
                    block_q: int = _fa.BLOCK_Q,
                    block_k: int = _fa.BLOCK_K):
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=_interpret())


def ssm_scan(x, dt, a, bs, cs, d_res, block_d: int = _ss.BLOCK_D,
             block_l: int = _ss.BLOCK_L):
    return _ss.ssm_scan(x, dt, a, bs, cs, d_res, block_d=block_d,
                        block_l=block_l, interpret=_interpret())


def rms_norm(x, scale, eps: float = 1e-5, rows: int = _rn.ROW_TILE):
    return _rn.rms_norm(x, scale, eps=eps, rows=rows,
                        interpret=_interpret())


def reduce_scatter_rmsnorm(shards, scale, eps: float = 1e-5,
                           rows: int = _fc.ROW_TILE):
    return _fc.reduce_scatter_rmsnorm(shards, scale, eps=eps, rows=rows,
                                      interpret=_interpret())


def reduce_scatter_adamw(shards, p, m, v, lr, bc1, bc2,
                         b1: float = 0.9, b2: float = 0.95,
                         eps: float = 1e-8, weight_decay: float = 0.0,
                         tile: int = _fc.SEG_TILE):
    return _fc.reduce_scatter_adamw(shards, p, m, v, lr, bc1, bc2,
                                    b1=b1, b2=b2, eps=eps,
                                    weight_decay=weight_decay,
                                    tile=tile, interpret=_interpret())


def all_gather_matmul(x, w_shards, rows: int = _fc.ROW_TILE):
    return _fc.all_gather_matmul(x, w_shards, rows=rows,
                                 interpret=_interpret())


def fused_dense(x, w_shards):
    """Differentiable fused AllGather-consuming matmul (the FSDP path's
    gather+matmul replacement; see ``fused_collectives.fused_dense``)."""
    return _fc.fused_dense(x, w_shards, _interpret())
