"""Decoder rows and scan-group assembly.

A "row" is one entry of ``cfg.layer_pattern``:

* ``a`` - pre-norm attention + pre-norm SwiGLU FFN (plus cross-attention
  when the model has an encoder);
* ``e`` - pre-norm attention + pre-norm MoE FFN;
* ``1``/``2`` - pre-norm Mamba block.

Consecutive rows of the same kind are stacked (params get a leading layer
axis) and executed with ``lax.scan`` so the compiled HLO contains one body
per kind regardless of depth - essential to keep 512-device dry-run
compiles tractable.  Rows marked shared (Zamba2's shared attention block)
hold a single param set applied at every occurrence.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import layers, moe, ssm
from repro.models.config import ModelConfig
from repro.models.pcontext import ParallelContext

Params = dict


@dataclasses.dataclass(frozen=True)
class Group:
    kind: str          # 'a' | 'e' | '1' | '2'
    count: int
    shared: bool = False   # single param set reused `count` times


def scan_groups(cfg: ModelConfig) -> list[Group]:
    """Groups in pattern order; consecutive same-kind rows merge into one
    scanned group.  Shared-attention rows (Zamba2) become ``shared=True``
    groups which all reference the single ``shared_a`` param set."""
    groups: list[Group] = []
    for ch in cfg.layer_pattern:
        shared = ch == "a" and cfg.shared_attention
        if groups and groups[-1].kind == ch \
                and groups[-1].shared == shared:
            groups[-1] = Group(ch, groups[-1].count + 1, shared)
        else:
            groups.append(Group(ch, 1, shared))
    return groups


# ----------------------------------------------------------------------- #
# row init / forward
# ----------------------------------------------------------------------- #

def init_row(key, kind: str, cfg: ModelConfig, tp: int, dtype,
             cross: bool = False) -> Params:
    ks = jax.random.split(key, 4)
    if kind == "a":
        p = {"norm1": jnp.ones((cfg.d_model,), jnp.float32),
             "attn": layers.init_attention(ks[0], cfg, tp, dtype),
             "norm2": jnp.ones((cfg.d_model,), jnp.float32),
             "ffn": layers.init_ffn(ks[1], cfg.d_model, cfg.d_ff, dtype)}
        if cross:
            p["norm_x"] = jnp.ones((cfg.d_model,), jnp.float32)
            p["xattn"] = layers.init_attention(ks[2], cfg, tp, dtype,
                                               cross=True)
        return p
    if kind == "e":
        return {"norm1": jnp.ones((cfg.d_model,), jnp.float32),
                "attn": layers.init_attention(ks[0], cfg, tp, dtype),
                "norm2": jnp.ones((cfg.d_model,), jnp.float32),
                "moe": moe.init_moe(ks[1], cfg, tp, dtype)}
    if kind == "1":
        return {"norm1": jnp.ones((cfg.d_model,), jnp.float32),
                "mamba": ssm.init_mamba1(ks[0], cfg, tp, dtype)}
    if kind == "2":
        return {"norm1": jnp.ones((cfg.d_model,), jnp.float32),
                "mamba": ssm.init_mamba2(ks[0], cfg, tp, dtype)}
    raise ValueError(kind)


def row_forward(p: Params, h: jnp.ndarray, kind: str, cfg: ModelConfig,
                pc: ParallelContext, positions: jnp.ndarray,
                encoder_out: Optional[jnp.ndarray] = None,
                causal: bool = True,
                window: Optional[int] = None):
    """Full-sequence forward for one row.  Returns (h, aux_loss)."""
    aux = jnp.float32(0.0)
    if kind in ("a", "e"):
        attn_in = layers.rms_norm(h, p["norm1"], cfg.norm_eps)
        h = h + layers.attention_forward(p["attn"], attn_in, cfg, pc,
                                         positions, causal=causal,
                                         window=window)
        if "xattn" in p and encoder_out is not None:
            x_in = layers.rms_norm(h, p["norm_x"], cfg.norm_eps)
            h = h + layers.attention_forward(p["xattn"], x_in, cfg, pc,
                                             positions, causal=False,
                                             kv_source=encoder_out)
        ff_in = layers.rms_norm(h, p["norm2"], cfg.norm_eps)
        if kind == "a":
            h = h + layers.ffn_forward(p["ffn"], ff_in, pc)
        else:
            out, aux = moe.moe_forward(p["moe"], ff_in, cfg, pc)
            h = h + out
    else:
        m_in = layers.rms_norm(h, p["norm1"], cfg.norm_eps)
        fwd = ssm.mamba1_forward if kind == "1" else ssm.mamba2_forward
        h = h + fwd(p["mamba"], m_in, cfg, pc)
    return h, aux


def row_prefill(p: Params, h: jnp.ndarray, kind: str, cfg: ModelConfig,
                pc: ParallelContext, positions: jnp.ndarray,
                max_seq: int, cache_dtype,
                encoder_out: Optional[jnp.ndarray] = None,
                window: Optional[int] = None):
    """Full-sequence forward that also emits this row's decode cache.
    Returns (h, aux, cache)."""
    aux = jnp.float32(0.0)
    if kind in ("a", "e"):
        attn_in = layers.rms_norm(h, p["norm1"], cfg.norm_eps)
        out, (k, v) = layers.attention_forward(
            p["attn"], attn_in, cfg, pc, positions, causal=True,
            window=window, return_kv=True)
        h = h + out
        cache = _kv_to_cache(k, v, cfg, pc, max_seq, cache_dtype)
        if "xattn" in p and encoder_out is not None:
            x_in = layers.rms_norm(h, p["norm_x"], cfg.norm_eps)
            xout, (ck, cv) = layers.attention_forward(
                p["xattn"], x_in, cfg, pc, positions, causal=False,
                kv_source=encoder_out, return_kv=True)
            h = h + xout
            cache["ck"] = ck.astype(cache_dtype)
            cache["cv"] = cv.astype(cache_dtype)
        ff_in = layers.rms_norm(h, p["norm2"], cfg.norm_eps)
        if kind == "a":
            h = h + layers.ffn_forward(p["ffn"], ff_in, pc)
        else:
            out, aux = moe.moe_forward(p["moe"], ff_in, cfg, pc)
            h = h + out
        return h, aux, cache
    m_in = layers.rms_norm(h, p["norm1"], cfg.norm_eps)
    if kind == "1":
        out, (conv, st) = ssm.mamba1_forward(p["mamba"], m_in, cfg, pc,
                                             return_state=True)
        return h + out, aux, {"conv": conv, "ssm": st}
    out, (cx, cbc, st) = ssm.mamba2_forward(p["mamba"], m_in, cfg, pc,
                                            return_state=True)
    return h + out, aux, {"conv": cx, "conv_bc": cbc, "ssm": st}


def _kv_to_cache(k: jnp.ndarray, v: jnp.ndarray, cfg: ModelConfig,
                 pc: ParallelContext, max_seq: int, cache_dtype) -> dict:
    """(B, L, n_kv_local, hd) head-layout -> flash-decoding cache layout:
    (B, S_local, n_kv_full, hd), sequence sharded over tp."""
    d = layers.attn_dims(cfg, pc.tp)
    b, l = k.shape[0], k.shape[1]
    if pc.tp > 1 and d.kv_sharded:
        k = layers._gather_heads(k, pc)
        v = layers._gather_heads(v, pc)
    if pc.tp > 1:
        s_local = max_seq // pc.tp
        start = pc.tp_index() * s_local
        # my sequence slice (prefill length L == global cache len for the
        # assigned shapes; shorter prefills zero-pad)
        k = jax.lax.dynamic_slice_in_dim(k, start, s_local, axis=1)
        v = jax.lax.dynamic_slice_in_dim(v, start, s_local, axis=1)
    else:
        s_local = max_seq
        if l < max_seq:
            pad = max_seq - l
            k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return {"k": k.astype(cache_dtype), "v": v.astype(cache_dtype)}


def row_decode(p: Params, h: jnp.ndarray, kind: str, cache: dict,
               at: tuple, pos: jnp.ndarray, active: jnp.ndarray,
               cfg: ModelConfig, pc: ParallelContext,
               window: Optional[int] = None):
    """Single-token decode for one row.  ``cache`` holds the whole
    leaves the caller carries and ``at`` indexes this row in them
    (``()`` for an unstacked row); layouts: attention {'k','v'}
    (+{'ck','cv'} cross KV, read only); mamba {'conv','ssm'}.  ``pos``
    and ``active`` are (B,): slots that are not ``active`` keep their
    state bit for bit.  Returns (h, new_cache)."""
    if kind in ("a", "e"):
        attn_in = layers.rms_norm(h, p["norm1"], cfg.norm_eps)
        out, ck, cv = layers.decode_attention(
            p["attn"], attn_in, cache["k"], cache["v"], at, pos, active,
            cfg, pc, window=window)
        h = h + out
        new_cache = dict(cache, k=ck, v=cv)
        if "xattn" in p and "ck" in cache:
            x_in = layers.rms_norm(h, p["norm_x"], cfg.norm_eps)
            h = h + _cross_decode(p["xattn"], x_in, cache["ck"][at],
                                  cache["cv"][at], cfg, pc)
        ff_in = layers.rms_norm(h, p["norm2"], cfg.norm_eps)
        if kind == "a":
            h = h + layers.ffn_forward(p["ffn"], ff_in, pc)
        else:
            # decode is drop-free: worst case every assignment lands on
            # one expert, so capacity = tokens * top_k (tiny at decode)
            cap = ff_in.shape[0] * ff_in.shape[1] * cfg.moe.top_k
            out, _ = moe.moe_forward(p["moe"], ff_in, cfg, pc,
                                     capacity=cap)
            h = h + out
        return h, new_cache
    # Recurrent state is small and rewritten whole every step: select
    # per slot, then write this row back.
    state = {k: v[at] for k, v in cache.items()}
    m_in = layers.rms_norm(h, p["norm1"], cfg.norm_eps)
    if kind == "1":
        out, (conv, st) = ssm.mamba1_decode(
            p["mamba"], m_in, (state["conv"], state["ssm"]), cfg, pc)
        new = dict(conv=conv, ssm=st)
    else:
        out, (cx, cbc, st) = ssm.mamba2_decode(
            p["mamba"], m_in,
            (state["conv"], state["conv_bc"], state["ssm"]), cfg, pc)
        new = dict(conv=cx, conv_bc=cbc, ssm=st)

    def put(leaf, old, nv):
        keep = active.reshape((-1,) + (1,) * (nv.ndim - 1))
        return leaf.at[at].set(jnp.where(keep, nv.astype(leaf.dtype), old))
    return h + out, {k: put(cache[k], state[k], new[k]) for k in cache}


def _cross_decode(p: Params, x: jnp.ndarray, ck: jnp.ndarray,
                  cv: jnp.ndarray, cfg: ModelConfig,
                  pc: ParallelContext) -> jnp.ndarray:
    """Cross-attention against precomputed encoder KV (B, S_enc, n_kv, hd)
    - local kv heads, full encoder sequence (encoder KV is small)."""
    d = layers.attn_dims(cfg, pc.tp)
    b = x.shape[0]
    q = (x @ p["wq"]).reshape(b, 1, d.n_q, d.head_dim)
    kk, vv = layers.select_kv(ck, cv, d, cfg, pc)
    out = layers.attention_scores(q, kk, vv, causal=False)
    out = out.reshape(b, 1, d.n_q * d.head_dim) @ p["wo"]
    return pc.tp_all_reduce(out)


def row_cache_init(kind: str, cfg: ModelConfig, pc: ParallelContext,
                   batch: int, max_seq: int, dtype,
                   cross_len: int = 0) -> dict:
    """Zero-initialized decode cache for one row.  The KV cache sequence
    dim is sharded over tp (flash-decoding layout)."""
    if kind in ("a", "e"):
        d = layers.attn_dims(cfg, pc.tp)
        n_kv_full = d.n_kv * pc.tp if d.kv_sharded else d.n_kv
        s_local = max_seq // max(pc.tp, 1) if pc.tp > 1 else max_seq
        c = {"k": jnp.zeros((batch, s_local, n_kv_full, d.head_dim),
                            dtype),
             "v": jnp.zeros((batch, s_local, n_kv_full, d.head_dim),
                            dtype)}
        if cross_len:
            c["ck"] = jnp.zeros((batch, cross_len, d.n_kv, d.head_dim),
                                dtype)
            c["cv"] = jnp.zeros((batch, cross_len, d.n_kv, d.head_dim),
                                dtype)
        return c
    if kind == "1":
        conv_s, ssm_s = ssm.mamba_state_shapes(cfg, max(pc.tp, 1), batch,
                                               1)
        return {"conv": jnp.zeros(conv_s, dtype),
                "ssm": jnp.zeros(ssm_s, jnp.float32)}
    cx_s, cbc_s, ssm_s = ssm.mamba_state_shapes(cfg, max(pc.tp, 1),
                                                batch, 2)
    return {"conv": jnp.zeros(cx_s, dtype),
            "conv_bc": jnp.zeros(cbc_s, jnp.float32),
            "ssm": jnp.zeros(ssm_s, jnp.float32)}
