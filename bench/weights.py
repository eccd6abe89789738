"""Random weights from the seed, made by the benchmark and not by the
program.

Every leaf is a function of (seed, leaf, layer) alone, so the program's
whole parameter tree comes from one jitted call, and
the reference makes the same values again one layer at a time.
Matrices are normals at 1/sqrt(fan_in), the embedding at 0.02, norm
scales are ones.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.sizes import Sizes

_LEAF_IDS = {"tok": 0, "head": 1, "wq": 10, "wk": 11, "wv": 12, "wo": 13,
             "wg": 14, "wu": 15, "wd": 16}


def base_key(seed: int) -> jax.Array:
    """A JAX key from any whole seed (the harness's seeds pass 2**31)."""
    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.key(int(word) & 0x7FFFFFFF)


def _normal(key, name: str, layer: int, shape, scale: float, dtype):
    k = jax.random.fold_in(jax.random.fold_in(key, _LEAF_IDS[name]), layer)
    return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)


def layer_shapes(s: Sizes) -> dict:
    hd = s.head_dim
    return {"wq": (s.d_model, s.n_heads * hd),
            "wk": (s.d_model, s.n_kv_heads * hd),
            "wv": (s.d_model, s.n_kv_heads * hd),
            "wo": (s.n_heads * hd, s.d_model),
            "wg": (s.d_model, s.d_ff), "wu": (s.d_model, s.d_ff),
            "wd": (s.d_ff, s.d_model)}


def layer(key, s: Sizes, i, dtype=jnp.float32) -> dict:
    """Layer ``i``'s matrices (``i`` may be traced)."""
    return {n: _normal(key, n, i, shp, 1.0 / math.sqrt(shp[0]), dtype)
            for n, shp in layer_shapes(s).items()}


def embed(key, s: Sizes, dtype=jnp.float32) -> dict:
    return {"tok": _normal(key, "tok", 0, (s.vocab, s.d_model), 0.02,
                           dtype),
            "head": _normal(key, "head", 0, (s.d_model, s.vocab),
                            1.0 / math.sqrt(s.d_model), dtype)}


def program_tree(key, s: Sizes, dtype=jnp.float32) -> dict:
    """The tree ``repro.models.model.init_params`` builds for a dense
    model with tp=1: embed, final_norm and one stacked group ``g0``."""
    stacked = jax.vmap(lambda i: layer(key, s, i, dtype))(
        jnp.arange(s.n_layers))
    ones = jnp.ones((s.n_layers, s.d_model), jnp.float32)
    return {"embed": embed(key, s, dtype),
            "final_norm": jnp.ones((s.d_model,), jnp.float32),
            "g0": {"norm1": ones, "norm2": ones,
                   "attn": {n: stacked[n] for n in ("wq", "wk", "wv", "wo")},
                   "ffn": {n: stacked[n] for n in ("wg", "wu", "wd")}}}


def make_program_params(seed: int, s: Sizes, dtype=jnp.float32):
    """The program's parameters on the device, in one jitted call."""
    return jax.jit(lambda k: program_tree(k, s, dtype))(base_key(seed))
