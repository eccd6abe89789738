"""Prefill -> decode continuation must equal full-sequence forward, per
architecture (exercises KV caches, ring buffers, SSM states, cross-attn
caches and the MoE drop-free decode path)."""
import dataclasses
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.configs import ALL_IDS, get_config
from repro.models import layers, model
from repro.models.pcontext import UNSHARDED

KEY = jax.random.key(0)
RNG = np.random.default_rng(0)
B, L = 2, 12


@pytest.mark.parametrize("arch", ALL_IDS)
def test_prefill_decode_consistency(arch):
    cfg = get_config(arch, smoke=True)
    if cfg.moe:  # drop-free routing for exactness
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=8.0))
    params = model.init_params(KEY, cfg, tp=1, dtype=jnp.float32)
    n_prefix = cfg.frontend_tokens if (cfg.frontend != "text"
                                       and cfg.encoder is None) else 0
    max_seq = n_prefix + L + 8
    toks = RNG.integers(0, cfg.vocab_size, (B, L + 1))
    extra = {}
    if cfg.frontend == "vision_stub" and cfg.encoder is None:
        extra["frontend"] = jnp.asarray(RNG.standard_normal(
            (B, cfg.frontend_tokens, cfg.frontend_dim)), jnp.float32)
    if cfg.encoder is not None:
        extra["source"] = jnp.asarray(RNG.standard_normal(
            (B, cfg.encoder.source_len, cfg.frontend_dim)), jnp.float32)

    ref_logits, _ = jax.jit(lambda p: model.prefill(
        p, {"tokens": jnp.asarray(toks)} | extra, cfg, UNSHARDED,
        max_seq=max_seq, cache_dtype=jnp.float32))(params)
    _, caches = jax.jit(lambda p: model.prefill(
        p, {"tokens": jnp.asarray(toks[:, :L])} | extra, cfg, UNSHARDED,
        max_seq=max_seq, cache_dtype=jnp.float32))(params)
    logits_d, _ = jax.jit(lambda p, c: model.decode_step(
        p, c, jnp.asarray(toks[:, L:L + 1]), jnp.int32(L + n_prefix),
        cfg, UNSHARDED))(params, caches)
    err = np.max(np.abs(np.asarray(ref_logits)[..., :cfg.vocab_size]
                        - np.asarray(logits_d)))
    assert err < 2e-3, f"{arch}: {err}"


def test_windowed_equals_full_within_window():
    """Sliding-window decode == full decode while pos < window."""
    cfg = get_config("llama3.2-1b", smoke=True)
    params = model.init_params(KEY, cfg, tp=1, dtype=jnp.float32)
    toks = RNG.integers(0, cfg.vocab_size, (B, 6))
    full = model.init_cache(cfg, UNSHARDED, B, 32,
                            cache_dtype=jnp.float32)
    win = model.init_cache(cfg, UNSHARDED, B, 1 << 20,
                           cache_dtype=jnp.float32, window=32)
    lf = lw = None
    for i in range(6):
        t = jnp.asarray(toks[:, i:i + 1])
        lf, full = model.decode_step(params, full, t, jnp.int32(i), cfg,
                                     UNSHARDED)
        lw, win = model.decode_step(params, win, t, jnp.int32(i), cfg,
                                    UNSHARDED, window=32)
    np.testing.assert_allclose(np.asarray(lf), np.asarray(lw),
                               rtol=1e-4, atol=1e-5)


# -- the in-place slot write against the formulation it replaced --------- #

def _oracle_attention(a, x, ck, cv, pos, cfg, window):
    """Decode attention as it was before the cache was written in place:
    a one-hot select over every position (per-slot ``pos``) or a
    dynamic_update_slice (scalar ``pos``) rewrites the whole row, and
    every KV head is expanded to its query heads with ``jnp.take``."""
    b, s = x.shape[0], ck.shape[1]
    n_q, n_kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    vec = jnp.ndim(pos) > 0
    rope_pos = pos.reshape(b, 1) if vec else pos.reshape(1)
    q = layers.apply_rope((x @ a["wq"]).reshape(b, 1, n_q, hd), rope_pos,
                          cfg.rope_theta)
    k = layers.apply_rope((x @ a["wk"]).reshape(b, 1, n_kv, hd),
                          rope_pos, cfg.rope_theta)
    v = (x @ a["wv"]).reshape(b, 1, n_kv, hd)
    write = pos % window if window else pos
    if vec:
        hit = (jnp.arange(s)[None] == write[:, None])[..., None, None]
        ck = jnp.where(hit, k.astype(ck.dtype), ck)
        cv = jnp.where(hit, v.astype(cv.dtype), cv)
    else:
        ck = lax.dynamic_update_slice(ck, k.astype(ck.dtype),
                                      (0, write, 0, 0))
        cv = lax.dynamic_update_slice(cv, v.astype(cv.dtype),
                                      (0, write, 0, 0))
    kv_idx = jnp.arange(n_q) // (n_q // n_kv)
    kk, vv = jnp.take(ck, kv_idx, axis=2), jnp.take(cv, kv_idx, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kk,
                        preferred_element_type=jnp.float32) / math.sqrt(hd)
    sp = jnp.arange(s)[None, None, None, :]
    pv = pos.reshape(b, 1, 1, 1) if vec else pos
    valid = ((sp <= pv) | (pv >= window)) if window else sp <= pv
    logits = jnp.where(valid, logits, -jnp.inf)
    p = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
    p = jnp.where(valid, p, 0.0)
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(vv.dtype), vv)
    o = o.astype(jnp.float32) / jnp.sum(p, -1).transpose(0, 2, 1)[..., None]
    return o.astype(x.dtype).reshape(b, 1, n_q * hd) @ a["wo"], ck, cv


def _oracle_decode_step(params, caches, tokens, pos, active, cfg, window):
    """The old decode step of a dense model: every row's cache comes back
    whole from the layer scan, then a per-slot select of every leaf keeps
    the inactive slots' old values."""
    h = layers.embed_tokens(params["embed"], tokens, cfg, UNSHARDED)

    def body(h, xs):
        p, c = xs
        out, ck, cv = _oracle_attention(
            p["attn"], layers.rms_norm(h, p["norm1"], cfg.norm_eps),
            c["k"], c["v"], pos, cfg, window)
        h = h + out
        h = h + layers.ffn_forward(
            p["ffn"], layers.rms_norm(h, p["norm2"], cfg.norm_eps),
            UNSHARDED)
        return h, dict(c, k=ck, v=cv)
    h, new = lax.scan(body, h, (params["g0"], caches[0]))
    keep = active.reshape(1, -1, 1, 1, 1)
    new = jax.tree.map(lambda n, o: jnp.where(keep, n, o), new, caches[0])
    h = layers.rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = layers.lm_logits(params["embed"], h, cfg, UNSHARDED)
    return logits[..., :cfg.vocab_size], [new]


_SMOKE = get_config("llama3.2-1b", smoke=True)
_GQA7 = dataclasses.replace(_SMOKE, d_model=224, n_heads=14, n_kv_heads=2)
_MHA = dataclasses.replace(_SMOKE, n_kv_heads=8)


@pytest.mark.parametrize("case", ["gqa7", "mha", "window", "scalar_pos"])
def test_in_place_decode_matches_the_whole_cache_oracle(case):
    """The in-place, grouped-query decode step against the one-hot write,
    head expansion and per-slot select it replaced: logits to bf16
    tolerance, every cache leaf bit for bit, inactive slots untouched
    (slot 1 idles at position 0 over live data, as the engine feeds it)."""
    cfg = _MHA if case == "mha" else _GQA7
    window = 8 if case == "window" else None
    b, max_seq = 4, 24
    rng = np.random.default_rng(14)
    params = model.init_params(KEY, cfg, tp=1, dtype=jnp.bfloat16)
    caches = model.init_cache(cfg, UNSHARDED, b, max_seq,
                              cache_dtype=jnp.bfloat16, window=window)
    caches = jax.tree.map(lambda c: jnp.asarray(
        rng.standard_normal(c.shape), c.dtype), caches)
    if case == "scalar_pos":
        pos, active = jnp.int32(6), np.ones(b, bool)
    else:
        pos = np.array([5, 0, 11, 19 if window else 2], np.int32)
        active = np.array([True, False, True, True])
    new_step = jax.jit(lambda p, c, t, q, a: model.decode_step(
        p, c, t, q, cfg, UNSHARDED, window=window,
        active=None if case == "scalar_pos" else a))
    old_step = jax.jit(lambda p, c, t, q, a: _oracle_decode_step(
        p, c, t, q, a, cfg, window))
    new = old = caches
    for _ in range(3):
        tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, 1)))
        ln, new = new_step(params, new, tok, pos, active)
        lo, old = old_step(params, old, tok, pos, active)
        ln, lo = np.asarray(ln, np.float32), np.asarray(lo, np.float32)
        np.testing.assert_allclose(ln[active], lo[active], rtol=0,
                                   atol=2e-2 * np.abs(lo[active]).max())
        for a, o, c in zip(jax.tree.leaves(new), jax.tree.leaves(old),
                           jax.tree.leaves(caches)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(o))
            np.testing.assert_array_equal(np.asarray(a)[:, ~active],
                                          np.asarray(c)[:, ~active])
        pos = pos + 1 if case == "scalar_pos" else np.where(
            active, pos + 1, pos)


def test_sequence_sharded_decode_matches_unsharded():
    """tp=2 (KV heads sharded) and tp=4 (q heads padded, KV replicated)
    on forced host devices, in a subprocess so the device count never
    leaks into this process (``_decode_tp_runner.py``)."""
    here = os.path.dirname(__file__)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(here), "src") + \
        os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.join(here, "_decode_tp_runner.py")],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "DECODE TP: ALL OK" in proc.stdout
