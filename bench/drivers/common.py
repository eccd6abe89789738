"""What the drivers share: the program's model configuration built from
a configuration file, and the check of one compared number."""
from __future__ import annotations

import math

from bench.sizes import Sizes


def program_config(config: dict, s: Sizes):
    """The program's ``ModelConfig`` for a dense configuration file."""
    from repro.models.config import ModelConfig, dense_pattern
    return ModelConfig(
        name=config.get("name", "bench"), family="dense",
        n_layers=s.n_layers, d_model=s.d_model, n_heads=s.n_heads,
        n_kv_heads=s.n_kv_heads, d_ff=s.d_ff, vocab_size=s.vocab,
        layer_pattern=dense_pattern(s.n_layers), rope_theta=s.rope_theta,
        norm_eps=s.norm_eps, tie_embeddings=config["tie_word_embeddings"],
        source=config["source"])


def check(name: str, value: float, limit: float) -> dict:
    """One number compared with its limit; a missing or non-finite
    number fails."""
    ok = value is not None and math.isfinite(value) and value <= limit
    return {"name": name, "value": value, "limit": limit, "ok": bool(ok)}
