"""Core layers: RMSNorm, RoPE, GQA attention (train/prefill/decode),
SwiGLU FFN, embeddings, sharded-vocab cross-entropy.

Tensor-parallel convention (Megatron-style, inside shard_map):

* column-parallel weights hold their *local* out-features slice; the
  matmul needs no collective;
* row-parallel weights hold their local in-features slice; the partial
  product is summed with ``pc.tp_all_reduce`` (CXL-CCL AllReduce);
* Q heads are padded to a multiple of tp (zero weights, numerically
  inert); KV heads are sharded when divisible by tp, else replicated
  (GQA KV is small).

Decode attention is flash-decoding style: the KV cache is sharded over the
tp axis on the *sequence* dim; each shard computes a partial softmax
(m, l, o) and the combine is two tp AllReduces.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.overlap import StackedShards
from repro.models.config import ModelConfig
from repro.models.pcontext import ParallelContext

Params = dict


def dense(x: jnp.ndarray, w) -> jnp.ndarray:
    """``x @ w`` where ``w`` may be a rank-major ``StackedShards`` stack
    from the fused FSDP gather path (``TrainConfig.fuse_kernels``): the
    stack streams through the fused all_gather+matmul kernel
    (``kernels.ops.fused_dense`` - shard k+1 prefetched while shard k
    multiplies) instead of being concatenated first.  Plain arrays take
    the ordinary matmul, so the unfused/serving paths are unchanged."""
    if isinstance(w, StackedShards):
        from repro.kernels import ops
        return ops.fused_dense(x, w.shards)
    return x @ w


# ---------------------------------------------------------------------- #
# initialization helpers
# ---------------------------------------------------------------------- #

def _dense_init(key, shape, in_dim, dtype):
    scale = 1.0 / math.sqrt(in_dim)
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray,
             eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * scale).astype(dt)


# ---------------------------------------------------------------------- #
# rotary position embeddings
# ---------------------------------------------------------------------- #

def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                       dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float) -> jnp.ndarray:
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)                    # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs
    cos = jnp.cos(angles)[..., None, :]                    # (..., s, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------- #
# attention
# ---------------------------------------------------------------------- #

@dataclasses.dataclass(frozen=True)
class AttnDims:
    """Local (per-tp-shard) attention dimensions."""
    n_q: int          # local query heads (after padding / tp split)
    n_kv: int         # local kv heads (sharded) or full kv heads (repl.)
    head_dim: int
    kv_sharded: bool


def attn_dims(cfg: ModelConfig, tp: int) -> AttnDims:
    if cfg.kv_sharded(tp) and cfg.padded_heads(tp) != cfg.n_heads:
        # sharded kv + padded q would misalign shard-local GQA grouping
        raise ValueError(
            f"{cfg.name}: q-head padding with sharded kv unsupported")
    return AttnDims(n_q=cfg.padded_heads(tp) // tp,
                    n_kv=(cfg.n_kv_heads // tp if cfg.kv_sharded(tp)
                          else cfg.n_kv_heads),
                    head_dim=cfg.head_dim,
                    kv_sharded=cfg.kv_sharded(tp))


def init_attention(key, cfg: ModelConfig, tp: int, dtype,
                   cross: bool = False) -> Params:
    """GLOBAL param shapes (shard_map splits them per param_specs).
    Q heads padded to a multiple of tp; padded head weights zeroed so the
    padding is numerically inert under any tp."""
    dm = cfg.d_model
    hd = cfg.head_dim
    hq_pad = cfg.padded_heads(tp)
    n_kv = cfg.n_kv_heads
    real = cfg.n_heads * hd
    ks = jax.random.split(key, 4)
    wq = _dense_init(ks[0], (dm, hq_pad * hd), dm, dtype)
    wo = _dense_init(ks[3], (hq_pad * hd, dm), cfg.n_heads * hd, dtype)
    if hq_pad != cfg.n_heads:
        wq = wq.at[:, real:].set(0.0)
        wo = wo.at[real:, :].set(0.0)
    return {
        "wq": wq,
        "wk": _dense_init(ks[1], (dm, n_kv * hd), dm, dtype),
        "wv": _dense_init(ks[2], (dm, n_kv * hd), dm, dtype),
        "wo": wo,
    }


def _repeat_kv(k: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    if n_rep == 1:
        return k
    return jnp.repeat(k, n_rep, axis=2)


def select_kv(k: jnp.ndarray, v: jnp.ndarray, d: "AttnDims",
              cfg: ModelConfig, pc: ParallelContext):
    """Map each *local* query head to its GQA kv head.

    With sharded kv heads, shard-local grouping is aligned (guarded in
    attn_dims).  With replicated kv the mapping must use the GLOBAL query
    index and the *unpadded* group size - padded q heads clip to the last
    kv head (they are numerically inert via zero wo rows)."""
    if d.kv_sharded:
        rep = d.n_q // d.n_kv
        return _repeat_kv(k, rep), _repeat_kv(v, rep)
    g = max(1, cfg.n_heads // max(cfg.n_kv_heads, 1))
    q_glob = pc.tp_index() * d.n_q + jnp.arange(d.n_q)
    kv_idx = jnp.clip(q_glob // g, 0, d.n_kv - 1)
    return jnp.take(k, kv_idx, axis=2), jnp.take(v, kv_idx, axis=2)


FLASH_THRESHOLD = 1024  # sequences this long use blocked attention


def attention_scores(q, k, v, causal: bool, window: Optional[int] = None,
                     q_offset: int = 0):
    """Attention.  q: (B,Lq,H,hd), k/v: (B,Lk,H,hd).  Long sequences
    dispatch to the blocked flash path (O(L) memory fwd+bwd)."""
    if q.shape[1] >= FLASH_THRESHOLD and k.shape[1] >= FLASH_THRESHOLD:
        from repro.models.flash import flash_attention
        return flash_attention(q, k, v, causal, window, q_offset)
    hd = q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    logits = logits / math.sqrt(hd)
    lq, lk = q.shape[1], k.shape[1]
    if causal:
        qpos = jnp.arange(lq)[:, None] + q_offset
        kpos = jnp.arange(lk)[None, :]
        mask = kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)


def attention_forward(params: Params, x: jnp.ndarray, cfg: ModelConfig,
                      pc: ParallelContext, positions: jnp.ndarray,
                      causal: bool = True,
                      window: Optional[int] = None,
                      kv_source: Optional[jnp.ndarray] = None,
                      return_kv: bool = False):
    """Full-sequence attention (training / prefill / encoder).

    ``kv_source`` switches to cross-attention (keys/values from encoder
    output, no causal mask, no rope on kv positions beyond arange).
    The output is row-parallel-reduced over tp.
    """
    d = attn_dims(cfg, pc.tp)
    b, l, _ = x.shape
    q = dense(x, params["wq"]).reshape(b, l, d.n_q, d.head_dim)
    src = x if kv_source is None else kv_source
    lk = src.shape[1]
    k = dense(src, params["wk"]).reshape(b, lk, d.n_kv, d.head_dim)
    v = dense(src, params["wv"]).reshape(b, lk, d.n_kv, d.head_dim)
    if kv_source is None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions[..., :lk] if positions.shape[-1] >= lk
                       else jnp.arange(lk), cfg.rope_theta)
    kk, vv = select_kv(k, v, d, cfg, pc)
    out = attention_scores(q, kk, vv, causal=causal and kv_source is None,
                           window=window)
    out = dense(out.reshape(b, l, d.n_q * d.head_dim), params["wo"])
    out = pc.tp_all_reduce(out)
    if return_kv:
        return out, (k, v)
    return out


# -- decode path --------------------------------------------------------- #

def decode_attention(params: Params, x: jnp.ndarray, cache_k, cache_v,
                     at: tuple, pos: jnp.ndarray, active: jnp.ndarray,
                     cfg: ModelConfig, pc: ParallelContext,
                     window: Optional[int] = None):
    """One-token decode with a sequence-sharded KV cache, written in
    place.

    x: (B, 1, d_model).  cache_{k,v}: (..., B, S_local, n_kv, hd) - the
    whole cache leaf the caller carries (a scan group's stack of
    layers); ``at`` indexes this row's (B, S_local, n_kv, hd) slice,
    ``()`` when the leaf is one row.  S_local is the local slice of a
    cache whose *global* sequence length is S_local * tp (tp sharded)
    or S_local (unsharded).  ``pos``: (B,) int32, the global position
    each slot writes; ``active``: (B,) bool.  A ring-buffer window
    cache writes at pos % window.

    Each slot's new K/V row is scattered at (``at``, b, pos[b]); an
    inactive slot, or a shard that does not own the position, gets an
    out-of-range index that the scatter drops, so the rest of the
    cache is never rewritten.  The row is then read once for
    attention, query heads grouped onto their KV head as
    (B, n_kv, heads per KV head, hd).

    Returns (attn_out (B,1,d_model), new_cache_k, new_cache_v).
    """
    d = attn_dims(cfg, pc.tp)
    b = x.shape[0]
    s_local = cache_k.shape[-3]
    tp_idx = pc.tp_index()
    rope_pos = pos.reshape(b, 1)

    q = dense(x, params["wq"]).reshape(b, 1, d.n_q, d.head_dim)
    q = apply_rope(q, rope_pos, cfg.rope_theta)
    # KV for the new token: computed on every shard (redundant but tiny),
    # using the *full* kv-head projection when kv is replicated; when kv
    # is head-sharded we gather the heads so the seq-sharded cache holds
    # all kv heads.
    k_new = dense(x, params["wk"]).reshape(b, 1, d.n_kv, d.head_dim)
    v_new = dense(x, params["wv"]).reshape(b, 1, d.n_kv, d.head_dim)
    k_new = apply_rope(k_new, rope_pos, cfg.rope_theta)
    if d.kv_sharded and pc.tp > 1:
        # (B,1,n_kv_local,hd) -> all heads: gather over tp along head dim
        k_new = _gather_heads(k_new, pc)
        v_new = _gather_heads(v_new, pc)

    write = pos % window if window is not None else pos
    keep = active
    if pc.tp > 1:
        keep = keep & (write // s_local == tp_idx)   # owning shard only
    row = jnp.where(keep, write % s_local, s_local)  # s_local: dropped
    idx = (*at, jnp.arange(b), row)
    cache_k = cache_k.at[idx].set(k_new[:, 0].astype(cache_k.dtype),
                                  mode="drop")
    cache_v = cache_v.at[idx].set(v_new[:, 0].astype(cache_v.dtype),
                                  mode="drop")
    ck, cv = cache_k[at], cache_v[at]                # (B,S_local,n_kv,hd)

    # Partial attention over the local sequence slice, all q heads,
    # each group of g query heads against its KV head as it lies in the
    # cache.  Padded q heads (hq_full > n_heads) are cut here and come
    # back as zeros below: their wo rows are zero.
    q_full = _gather_heads(q, pc) if pc.tp > 1 else q   # (B,1,Hq_full,hd)
    hq_full = q_full.shape[2]
    n_kv = ck.shape[2]
    g = cfg.n_heads // n_kv
    qg = q_full[:, 0, :cfg.n_heads].reshape(b, n_kv, g, d.head_dim)
    logits = jnp.einsum("bkgd,bskd->bkgs", qg, ck,
                        preferred_element_type=jnp.float32)
    logits = logits / math.sqrt(d.head_dim)
    # mask invalid cache slots: global slot index of local slot j
    base = tp_idx * s_local if pc.tp > 1 else 0
    sp = (base + jnp.arange(s_local))[None, None, None, :]
    pv = pos.reshape(b, 1, 1, 1)
    if window is not None:
        # ring buffer: before the buffer wraps (pos < window) only slots
        # <= pos hold data; afterwards every slot is live.
        valid = (sp <= pv) | (pv >= window)
    else:
        valid = sp <= pv
    logits = jnp.where(valid, logits, -jnp.inf)
    m = jnp.max(logits, axis=-1)                          # (B,K,G)
    m_glob = pc.tp_psum_max(m)
    p = jnp.exp(logits - m_glob[..., None])
    p = jnp.where(valid, p, 0.0)
    l_part = jnp.sum(p, axis=-1)                          # (B,K,G)
    o_part = jnp.einsum("bkgs,bskd->bkgd", p.astype(cv.dtype), cv)
    l_glob = pc.tp_all_reduce(l_part)
    o_glob = pc.tp_all_reduce(o_part.astype(jnp.float32))
    out_full = (o_glob / jnp.maximum(l_glob, 1e-20)[..., None]).reshape(
        b, 1, cfg.n_heads, d.head_dim)
    if hq_full > cfg.n_heads:
        out_full = jnp.pad(out_full, ((0, 0), (0, 0),
                                      (0, hq_full - cfg.n_heads), (0, 0)))
    # Row-parallel output projection: my shard's q-head slice only.
    if pc.tp > 1:
        my = pc.tp_index()
        out_local = lax.dynamic_slice_in_dim(out_full, my * d.n_q, d.n_q,
                                             axis=2)
    else:
        out_local = out_full
    out = dense(out_local.astype(x.dtype).reshape(b, 1,
                                                  d.n_q * d.head_dim),
                params["wo"])
    out = pc.tp_all_reduce(out)
    return out, cache_k, cache_v


def _gather_heads(x: jnp.ndarray, pc: ParallelContext) -> jnp.ndarray:
    """(B, L, h_local, hd) -> (B, L, h_local*tp, hd) via tp all-gather."""
    if pc.tp_axis is None or pc.tp == 1:
        return x
    moved = jnp.moveaxis(x, 2, 0)          # (h, B, L, hd)
    gathered = pc.comm.all_gather(moved, pc.tp_axis)
    return jnp.moveaxis(gathered, 0, 2)


# ---------------------------------------------------------------------- #
# SwiGLU FFN
# ---------------------------------------------------------------------- #

def init_ffn(key, d_model: int, d_ff_local: int, dtype) -> Params:
    ks = jax.random.split(key, 3)
    return {
        "wg": _dense_init(ks[0], (d_model, d_ff_local), d_model, dtype),
        "wu": _dense_init(ks[1], (d_model, d_ff_local), d_model, dtype),
        "wd": _dense_init(ks[2], (d_ff_local, d_model), d_ff_local, dtype),
    }


def ffn_forward(params: Params, x: jnp.ndarray,
                pc: ParallelContext) -> jnp.ndarray:
    h = jax.nn.silu(dense(x, params["wg"])) * dense(x, params["wu"])
    out = dense(h, params["wd"])
    return pc.tp_all_reduce(out)


# ---------------------------------------------------------------------- #
# embeddings + sharded-vocab cross entropy
# ---------------------------------------------------------------------- #

def init_embedding(key, cfg: ModelConfig, tp: int, dtype) -> Params:
    """GLOBAL shapes; vocab padded to a multiple of tp (padded ids are
    masked out of the softmax in sharded_xent)."""
    v_pad = cfg.padded_vocab(tp)
    k1, k2 = jax.random.split(key)
    p = {"tok": (jax.random.normal(k1, (v_pad, cfg.d_model)) *
                 0.02).astype(dtype)}
    if not cfg.tie_embeddings:
        p["head"] = _dense_init(k2, (cfg.d_model, v_pad), cfg.d_model,
                                dtype)
    return p


def embed_tokens(params: Params, tokens: jnp.ndarray, cfg: ModelConfig,
                 pc: ParallelContext) -> jnp.ndarray:
    """Vocab-sharded embedding lookup: each shard contributes its slice,
    summed over tp (one AllReduce)."""
    v_local = params["tok"].shape[0]
    if pc.tp > 1:
        start = pc.tp_index() * v_local
        local_ids = tokens - start
        in_range = (local_ids >= 0) & (local_ids < v_local)
        local_ids = jnp.clip(local_ids, 0, v_local - 1)
        emb = params["tok"][local_ids]
        emb = jnp.where(in_range[..., None], emb, 0.0)
        return pc.tp_all_reduce(emb)
    return params["tok"][tokens]


def lm_logits(params: Params, h: jnp.ndarray, cfg: ModelConfig,
              pc: ParallelContext) -> jnp.ndarray:
    """Returns *local* vocab-slice logits (B, L, V/tp)."""
    w = params.get("head")
    if w is None:
        w = params["tok"].T
    return h @ w


def sharded_xent(logits_local: jnp.ndarray, labels: jnp.ndarray,
                 pc: ParallelContext,
                 mask: Optional[jnp.ndarray] = None,
                 vocab_size: Optional[int] = None) -> jnp.ndarray:
    """Cross-entropy over a vocab-sharded logits tensor.

    logits_local: (B, L, V_local); labels: (B, L) global ids.
    Three tp collectives: max, sum-exp, label-logit.  ``vocab_size``
    excludes padded vocabulary ids from the softmax.
    """
    v_local = logits_local.shape[-1]
    logits_local = logits_local.astype(jnp.float32)
    if vocab_size is not None:
        gid = pc.tp_index() * v_local + jnp.arange(v_local)
        logits_local = jnp.where(gid[None, None, :] < vocab_size,
                                 logits_local, -jnp.inf)
    # stop_gradient: the max is a constant offset of logsumexp, so
    # gradients are exact without it (and pmax has no AD rule - tp_max
    # is the gather-based differentiable-path variant).
    m = jax.lax.stop_gradient(
        pc.tp_max(jnp.max(logits_local, axis=-1)))
    z = jnp.sum(jnp.exp(logits_local - m[..., None]), axis=-1)
    z = pc.tp_all_reduce(z)
    start = pc.tp_index() * v_local
    local_ids = labels - start
    in_range = (local_ids >= 0) & (local_ids < v_local)
    safe = jnp.clip(local_ids, 0, v_local - 1)
    lab = jnp.take_along_axis(logits_local, safe[..., None],
                              axis=-1)[..., 0]
    lab = jnp.where(in_range, lab, 0.0)
    lab = pc.tp_all_reduce(lab)
    nll = jnp.log(z) + m - lab
    if mask is not None:
        nll = nll * mask
        return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
