"""Operations and bytes that a step of the model requires, from its
shapes and the actual sequence lengths: not from what an implementation
happens to compute or read, so a faster implementation cannot push a
share of the peak past 100%."""
from __future__ import annotations

from bench.sizes import Sizes


def attn_pair_flops(s: Sizes) -> int:
    """Multiply-adds (x2) of one query attending to one key in one
    layer: q.k and p.v over every query head."""
    return 4 * s.n_heads * s.head_dim


def kv_bytes_per_token(s: Sizes, cache_bytes: int) -> int:
    """One position's keys and values over all layers."""
    return 2 * s.n_layers * s.n_kv_heads * s.head_dim * cache_bytes


def decode_step(s: Sizes, lengths, weight_bytes: int,
                cache_bytes: int) -> tuple:
    """(flops, bytes) of one batched decode step.  ``lengths`` holds,
    for every active slot, the number of positions it attends to (the
    cached ones and the new token).  Bytes: every weight once, the
    embedding rows of the fed tokens, the live cache positions read,
    the new entries written and the float32 logits written."""
    n = len(lengths)
    flops = n * 2 * s.matmul_params \
        + sum(lengths) * s.n_layers * attn_pair_flops(s)
    weights = (s.matmul_params + (2 * s.n_layers + 1) * s.d_model) \
        * weight_bytes + n * s.d_model * weight_bytes
    kv = (sum(lengths) - n) * kv_bytes_per_token(s, cache_bytes)
    written = n * kv_bytes_per_token(s, cache_bytes) + n * s.vocab * 4
    return flops, weights + kv + written


def prefill(s: Sizes, t: int) -> int:
    """Forward flops of a causal prefill of ``t`` tokens (LM head on the
    last position only)."""
    return 2 * t * s.n_layers * s.layer_matmul_params \
        + 2 * s.d_model * s.vocab \
        + s.n_layers * attn_pair_flops(s) * t * (t + 1) // 2

