"""Top-level language model: init, train forward, prefill, decode.

Handles all six architecture families through ``cfg.layer_pattern`` +
frontend switches:

* decoder-only text (dense / MoE / SSM / hybrid);
* decoder-only with a stub modality frontend (VLM: projected patch
  embeddings prepended to the token sequence);
* encoder-decoder (audio: stub frame embeddings -> bidirectional encoder,
  causal decoder with cross-attention).

Layer rows are executed per scan group with ``lax.scan`` over stacked
params (one compiled body per kind).  ``remat=True`` wraps each row in
``jax.checkpoint`` for training-memory control.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core import ledger
from repro.models import blocks, layers
from repro.models.config import ModelConfig
from repro.models.pcontext import ParallelContext

Params = dict


# ----------------------------------------------------------------------- #
# init
# ----------------------------------------------------------------------- #

def init_params(key, cfg: ModelConfig, tp: int = 1,
                dtype=jnp.float32) -> Params:
    keys = jax.random.split(key, 8)
    params: Params = {
        "embed": layers.init_embedding(keys[0], cfg, tp, dtype),
        "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
    }
    groups = blocks.scan_groups(cfg)
    cross = cfg.encoder is not None
    gkeys = jax.random.split(keys[1], len(groups))
    shared_done = False
    for gi, g in enumerate(groups):
        if g.shared:
            if not shared_done:
                params["shared_a"] = blocks.init_row(
                    gkeys[gi], "a", cfg, tp, dtype, cross=cross)
                shared_done = True
            continue
        # groups are ALWAYS layer-stacked (count-1 groups get a leading
        # dim of 1) so the 'g<i>' key uniformly means "stacked"
        rk = jax.random.split(gkeys[gi], g.count)
        params[f"g{gi}"] = jax.vmap(
            lambda k: blocks.init_row(k, g.kind, cfg, tp, dtype,
                                      cross=cross))(rk)
    if cfg.encoder is not None:
        ek = jax.random.split(keys[2], cfg.encoder.n_layers)
        params["encoder"] = jax.vmap(
            lambda k: blocks.init_row(k, "a", cfg, tp, dtype))(ek)
        params["enc_norm"] = jnp.ones((cfg.d_model,), jnp.float32)
        fd = cfg.frontend_dim or cfg.d_model
        params["enc_proj"] = layers._dense_init(
            keys[3], (fd, cfg.d_model), fd, dtype)
    elif cfg.frontend != "text":
        fd = cfg.frontend_dim or cfg.d_model
        params["front_proj"] = layers._dense_init(
            keys[3], (fd, cfg.d_model), fd, dtype)
    return params


def abstract_params(cfg: ModelConfig, tp: int = 1, dtype=jnp.float32):
    """ShapeDtypeStruct pytree with the same structure as init_params -
    used by the dry-run (no allocation)."""
    return jax.eval_shape(
        lambda k: init_params(k, cfg, tp, dtype),
        jax.random.key(0))


# ----------------------------------------------------------------------- #
# shared plumbing
# ----------------------------------------------------------------------- #

def _encode(params: Params, source: jnp.ndarray, cfg: ModelConfig,
            pc: ParallelContext) -> jnp.ndarray:
    """Stub-frontend frames -> encoder stack (bidirectional)."""
    h = source @ params["enc_proj"]
    positions = jnp.arange(h.shape[1])
    def body(carry, p):
        out, _ = blocks.row_forward(p, carry, "a", cfg, pc, positions,
                                    causal=False)
        return out, None
    with ledger.scale(cfg.encoder.n_layers):
        h, _ = jax.lax.scan(body, h, params["encoder"])
    return layers.rms_norm(h, params["enc_norm"], cfg.norm_eps)


def _embed_inputs(params: Params, batch: dict, cfg: ModelConfig,
                  pc: ParallelContext):
    """Returns (h, n_prefix, encoder_out)."""
    tokens = batch["tokens"]
    h = layers.embed_tokens(params["embed"], tokens, cfg, pc)
    encoder_out = None
    n_prefix = 0
    if cfg.encoder is not None:
        encoder_out = _encode(params, batch["source"], cfg, pc)
    elif cfg.frontend != "text":
        front = batch["frontend"] @ params["front_proj"]
        h = jnp.concatenate([front.astype(h.dtype), h], axis=1)
        n_prefix = front.shape[1]
    return h, n_prefix, encoder_out


def _run_groups(params: Params, h, cfg, pc, positions, encoder_out,
                remat: bool, window=None, gather_fn=None,
                prefetch: int = 0):
    """``prefetch >= 1`` enables the double-buffered FSDP prefetch: each
    scan body issues layer ``l+1``'s param AllGather (carried explicitly)
    alongside layer ``l``'s compute, so XLA schedules the gather as an
    async collective hidden behind the matmuls.  The prefetched gathers
    run under ``ledger.hidden()`` (the prologue gather stays exposed)."""
    groups = blocks.scan_groups(cfg)
    aux_total = jnp.float32(0.0)
    prefetching = gather_fn is not None and prefetch >= 1

    def make_body(kind, group_key):
        def body(carry, p):
            if gather_fn is not None:
                p = gather_fn(group_key, p)   # FSDP: gather row params
            out, aux = blocks.row_forward(p, carry, kind, cfg, pc,
                                          positions,
                                          encoder_out=encoder_out,
                                          window=window)
            return out, aux
        return jax.checkpoint(body) if remat else body

    def make_prefetch_body(kind, group_key):
        """carry = (h, gathered params of the layer to compute now);
        xs = raw (sharded) params of the NEXT layer."""
        def body(carry, p_next):
            hh, p_cur = carry
            with ledger.hidden():
                p_pre = gather_fn(group_key, p_next)
            out, aux = blocks.row_forward(p_cur, hh, kind, cfg, pc,
                                          positions,
                                          encoder_out=encoder_out,
                                          window=window)
            return (out, p_pre), aux
        return jax.checkpoint(body) if remat else body

    def make_consume(kind):
        """Epilogue: compute one row from already-gathered params."""
        def body(carry, p):
            out, aux = blocks.row_forward(p, carry, kind, cfg, pc,
                                          positions,
                                          encoder_out=encoder_out,
                                          window=window)
            return out, aux
        return jax.checkpoint(body) if remat else body

    for gi, g in enumerate(groups):
        if g.shared:
            if prefetching:
                # one param set reused count x: gather it ONCE instead of
                # per occurrence (count x fewer AllGathers; the AD
                # transpose fuses the count ReduceScatters into one)
                sp = gather_fn("shared_a", params["shared_a"])
                body = make_consume("a")
                for _ in range(g.count):
                    h, aux = body(h, sp)
                    aux_total += aux
            else:
                body = make_body("a", "shared_a")
                for _ in range(g.count):
                    h, aux = body(h, params["shared_a"])
                    aux_total += aux
        elif prefetching:
            stacked = params[f"g{gi}"]
            first = jax.tree.map(lambda x: x[0], stacked)
            gathered = gather_fn(f"g{gi}", first)    # exposed prologue
            if g.count > 1:
                rest = jax.tree.map(lambda x: x[1:], stacked)
                # trace-time ledger: the prefetch body runs count-1 x;
                # the prologue gather and epilogue row run once each, so
                # totals match the non-prefetched schedule exactly.
                with ledger.scale(g.count - 1):
                    (h, gathered), auxs = jax.lax.scan(
                        make_prefetch_body(g.kind, f"g{gi}"),
                        (h, gathered), rest)
                aux_total += jnp.sum(auxs)
            h, aux_last = make_consume(g.kind)(h, gathered)
            aux_total += aux_last
        else:
            # trace-time collective ledger: the scan body runs count x
            with ledger.scale(g.count):
                h, auxs = jax.lax.scan(make_body(g.kind, f"g{gi}"), h,
                                       params[f"g{gi}"])
            aux_total += jnp.sum(auxs)
    return h, aux_total


# ----------------------------------------------------------------------- #
# training forward
# ----------------------------------------------------------------------- #

def loss_fn(params: Params, batch: dict, cfg: ModelConfig,
            pc: ParallelContext, remat: bool = True,
            window: Optional[int] = None, gather_fn=None,
            prefetch: int = 0):
    """batch: tokens (B, L_text), labels (B, L_text), optional
    frontend/source.  ``gather_fn(group_key, row_params)`` is the FSDP
    hook (sharding.fsdp_gather_fn / core.overlap.make_gather_fn);
    ``prefetch >= 1`` double-buffers it (see _run_groups).  Returns
    (loss, aux_dict)."""
    if gather_fn is not None:
        # embed is used at both ends of the step: gather once up front.
        params = dict(params, embed=gather_fn("embed", params["embed"]))
    h, n_prefix, encoder_out = _embed_inputs(params, batch, cfg, pc)
    positions = jnp.arange(h.shape[1])
    h, aux = _run_groups(params, h, cfg, pc, positions, encoder_out,
                         remat=remat, window=window, gather_fn=gather_fn,
                         prefetch=prefetch)
    h = layers.rms_norm(h, params["final_norm"], cfg.norm_eps)
    if n_prefix:
        h = h[:, n_prefix:]
    logits = layers.lm_logits(params["embed"], h, cfg, pc)
    xent = layers.sharded_xent(logits, batch["labels"], pc,
                               mask=batch.get("loss_mask"),
                               vocab_size=cfg.vocab_size)
    return xent + aux, {"xent": xent, "aux": aux}


# ----------------------------------------------------------------------- #
# serving: prefill + decode
# ----------------------------------------------------------------------- #

def prefill(params: Params, batch: dict, cfg: ModelConfig,
            pc: ParallelContext, max_seq: int,
            cache_dtype=jnp.bfloat16, window: Optional[int] = None):
    """Full-sequence forward producing last-position logits + decode
    cache (a list aligned with scan groups)."""
    h, n_prefix, encoder_out = _embed_inputs(params, batch, cfg, pc)
    positions = jnp.arange(h.shape[1])
    groups = blocks.scan_groups(cfg)
    caches: list = []

    def make_body(kind):
        def body(carry, p):
            out, aux, cache = blocks.row_prefill(
                p, carry, kind, cfg, pc, positions, max_seq, cache_dtype,
                encoder_out=encoder_out, window=window)
            return out, cache
        return body

    for gi, g in enumerate(groups):
        if g.shared:
            body = make_body("a")
            gc = []
            for _ in range(g.count):
                h, cache = body(h, params["shared_a"])
                gc.append(cache)
            caches.append(gc)
        else:
            with ledger.scale(g.count):
                h, cache = jax.lax.scan(make_body(g.kind), h,
                                        params[f"g{gi}"])
            caches.append(cache)
    h = layers.rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = layers.lm_logits(params["embed"], h[:, -1:], cfg, pc)
    return logits, caches


def init_cache(cfg: ModelConfig, pc: ParallelContext, batch: int,
               max_seq: int, cache_dtype=jnp.bfloat16,
               window: Optional[int] = None):
    """Zero cache for decode-from-scratch (the dry-run decode shapes)."""
    eff_seq = min(max_seq, window) if window else max_seq
    cross_len = cfg.encoder.source_len if cfg.encoder else 0
    groups = blocks.scan_groups(cfg)
    caches = []
    for g in groups:
        one = lambda: blocks.row_cache_init(g.kind, cfg, pc, batch,
                                            eff_seq, cache_dtype,
                                            cross_len=cross_len)
        if g.shared:
            caches.append([one() for _ in range(g.count)])
        else:
            caches.append(jax.tree.map(
                lambda *xs: jnp.stack(xs), *[one() for _ in
                                             range(g.count)]))
    return caches


def decode_step(params: Params, caches: list, tokens: jnp.ndarray,
                pos: jnp.ndarray, cfg: ModelConfig, pc: ParallelContext,
                window: Optional[int] = None,
                active: Optional[jnp.ndarray] = None):
    """One decode step.  tokens: (B, 1) int32; pos: the global position
    being decoded - scalar int32 (whole batch in lockstep) or (B,)
    int32 (per-slot positions for the continuous-batching engine).
    ``active``: (B,) bool, default all; an inactive slot's cache is
    left bit for bit as it was.  Each scan group carries its cache
    leaves through the layer loop and every row writes only its new
    positions into them (``layers.decode_attention``), so a caller
    that donates ``caches`` gets the step's cache in the same buffers.
    Returns (logits (B, 1, V_padded), new_caches)."""
    b = tokens.shape[0]
    pos = jnp.broadcast_to(pos, (b,)).astype(jnp.int32)
    if active is None:
        active = jnp.ones((b,), bool)
    h = layers.embed_tokens(params["embed"], tokens, cfg, pc)
    groups = blocks.scan_groups(cfg)
    new_caches = []
    for gi, g in enumerate(groups):
        def row(h, p, cache, at, kind=g.kind):
            return blocks.row_decode(p, h, kind, cache, at, pos, active,
                                     cfg, pc, window=window)
        if g.shared:
            gc = []
            for cache in caches[gi]:
                h, nc = row(h, params["shared_a"], cache, ())
                gc.append(nc)
            new_caches.append(gc)
        else:
            def body(carry, xs):
                h, cache = carry
                p, i = xs
                return row(h, p, cache, (i,)), None
            with ledger.scale(g.count):
                (h, nc), _ = jax.lax.scan(
                    body, (h, caches[gi]),
                    (params[f"g{gi}"], jnp.arange(g.count)))
            new_caches.append(nc)
    h = layers.rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits_local = layers.lm_logits(params["embed"], h, cfg, pc)
    if pc.tp > 1:
        moved = jnp.moveaxis(logits_local, -1, 0)
        logits = jnp.moveaxis(pc.comm.all_gather(moved, pc.tp_axis), 0, -1)
    else:
        logits = logits_local
    return logits[..., :cfg.vocab_size], new_caches
