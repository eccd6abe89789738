"""The plain reference: a dense llama-architecture decoder in
``jax.numpy``, float32 at the highest matmul precision.

It imports nothing of the program.  Its weights come from
``bench.weights`` by the seed, one layer at a time, so it fits on the
chip once the program's state is freed.  What it follows:

* RMSNorm with a learned scale, pre-norm residual blocks;
* rotary embeddings on the two halves of each head (the ``rotate_half``
  convention of the published llama code), no rope scaling;
* grouped-query attention, query head ``h`` reading key/value head
  ``h // (n_heads / n_kv_heads)``, causal, scaled by 1/sqrt(head_dim);
* SwiGLU: ``(silu(x Wg) * (x Wu)) Wd``;
* an untied LM head.

The weights are the served ones: made in the configuration's dtype and
read here exactly (``weight_dtype``).  ``dtype``/``precision`` select
the computation: float32 at ``HIGHEST`` is the reference.  The control
is one precision step below a bfloat16 configuration, int8 products
(W8A8): every weight matrix rounded to int8 per output channel and
every activation entering a weight product to int8 per token (both
symmetric, scale max|x|/127), the rest in bfloat16 at the default
precision.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench import weights
from bench.sizes import Sizes

HIGHEST = lax.Precision.HIGHEST
DEFAULT = lax.Precision.DEFAULT
Q_BLOCK = 512          # query rows per attention block


def int8_rows(x, axis=-1):
    """``x`` rounded to int8 with one scale per slice along ``axis``."""
    x32 = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(x32), axis=axis, keepdims=True) / 127.0
    q = jnp.clip(jnp.round(x32 / jnp.maximum(scale, 1e-30)), -127, 127)
    return (q * scale).astype(x.dtype)


def _mm(eq, a, b, precision):
    return jnp.einsum(eq, a, b, precision=precision,
                      preferred_element_type=jnp.float32).astype(a.dtype)


def _wmm(eq, x, w, precision, int8):
    """A product with a weight matrix; W8A8 activations when ``int8``."""
    return _mm(eq, int8_rows(x) if int8 else x, w, precision)


def rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def rope(x, positions, theta):
    """x: (B, T, H, hd); positions: (T,)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


def attention(q, k, v, precision):
    """Causal GQA attention, computed in blocks of query rows.
    q: (B, T, H, hd); k, v: (B, T, KV, hd)."""
    b, t, h, hd = q.shape
    g = h // k.shape[2]
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    outs = []
    for q0 in range(0, t, Q_BLOCK):
        qb = q[:, q0:q0 + Q_BLOCK]
        n = qb.shape[1]
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k, precision=precision,
                       preferred_element_type=jnp.float32) / math.sqrt(hd)
        mask = jnp.arange(t)[None, :] <= (q0 + jnp.arange(n))[:, None]
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        outs.append(_mm("bhqk,bkhd->bqhd", p, v, precision))
    return jnp.concatenate(outs, axis=1)


def block(w, norms, h, s: Sizes, precision, int8: bool = False):
    """One pre-norm layer.  w: the layer's matrices; norms: (n1, n2)."""
    b, t, _ = h.shape
    hd = s.head_dim
    pos = jnp.arange(t)
    mm = functools.partial(_wmm, precision=precision, int8=int8)
    x = rms_norm(h, norms[0], s.norm_eps)
    q = mm("btd,de->bte", x, w["wq"]).reshape(b, t, -1, hd)
    k = mm("btd,de->bte", x, w["wk"]).reshape(b, t, -1, hd)
    v = mm("btd,de->bte", x, w["wv"]).reshape(b, t, -1, hd)
    q, k = rope(q, pos, s.rope_theta), rope(k, pos, s.rope_theta)
    o = attention(q, k, v, precision).reshape(b, t, -1)
    h = h + mm("bte,ed->btd", o, w["wo"])
    x = rms_norm(h, norms[1], s.norm_eps)
    a = jax.nn.silu(mm("btd,df->btf", x, w["wg"])) \
        * mm("btd,df->btf", x, w["wu"])
    return h + mm("btf,fd->btd", a, w["wd"])


# --------------------------------------------------------------------- #
# serving: logits at chosen positions, one layer at a time
# --------------------------------------------------------------------- #

def int8_per_channel(w):
    """``w`` rounded to int8 per output column, as float32."""
    return int8_rows(w.astype(jnp.float32), axis=0)


def _as(w, dtype, int8: bool):
    w = int8_per_channel(w) if int8 else w
    return w.astype(dtype)


@functools.partial(jax.jit, static_argnums=(1, 3, 4, 5))
def _gen_layer(key, s, i, weight_dtype, dtype, int8):
    w = weights.layer(key, s, i, weight_dtype)
    return {k: _as(v, dtype, int8) for k, v in w.items()}


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _block_jit(w, h, ones, s, precision, int8):
    return block(w, (ones, ones), h, s, precision, int8)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _head_jit(head, h, ones, s, precision, int8):
    x = rms_norm(h, ones, s.norm_eps)
    x = int8_rows(x) if int8 else x
    return jnp.einsum("nd,dv->nv", x, head, precision=precision,
                      preferred_element_type=jnp.float32)


def logits_at(seed: int, s: Sizes, seqs: list, want: list, pad_to: int,
              weight_dtype=jnp.float32, dtype=jnp.float32,
              precision=HIGHEST, int8: bool = False) -> list:
    """Logits (float32, ``(len(want[i]), vocab)``) at positions
    ``want[i]`` of each token sequence ``seqs[i]``, from the
    ``weight_dtype`` weights made from ``seed`` with unit norm scales
    (as served), computed in ``dtype`` at ``precision`` (``int8``: the
    control's int8 products).  Every sequence is padded at its
    end to ``pad_to`` tokens: causal attention leaves the earlier
    positions alone, and one shape compiles once."""
    key = weights.base_key(seed)
    emb = {k: _as(v, dtype, int8 and k == "head")
           for k, v in weights.embed(key, s, weight_dtype).items()}
    ones = jnp.ones((s.d_model,), dtype)
    hs = []
    for toks in seqs:
        ids = np.zeros((pad_to,), np.int32)
        ids[:len(toks)] = toks
        hs.append(emb["tok"][jnp.asarray(ids)][None])
    head = emb["head"]
    del emb
    for i in range(s.n_layers):
        w = _gen_layer(key, s, i, jnp.dtype(weight_dtype), jnp.dtype(dtype),
                       int8)
        hs = [_block_jit(w, h, ones, s, precision, int8) for h in hs]
        del w
    out = []
    for h, pos in zip(hs, want):
        rows = h[0][jnp.asarray(np.asarray(pos, np.int32))]
        out.append(np.asarray(_head_jit(head, rows, ones, s, precision,
                                        int8)))
    return out
