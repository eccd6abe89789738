"""Small stand-ins for the benchmark's configurations and mixes, so the
harness's code paths run on the CPU in seconds."""
from __future__ import annotations

import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402

TINY = dict(hidden_size=256, intermediate_size=512, num_attention_heads=8,
            num_key_value_heads=2, vocab_size=512, num_hidden_layers=2)

MIX = {"kind": "open_loop", "rate_per_s": 20.0,
       "prompt_tokens": {"lognormal_median": 64, "lognormal_sigma": 0.6,
                         "min": 32, "max": 160, "round_up_to": 32},
       "output_tokens": {"lognormal_median": 8, "lognormal_sigma": 0.6,
                         "min": 4, "max": 24}}


def serve_config() -> dict:
    c = harness.load_json(
        ROOT / "bench/configs/deepseek-coder-33b.serve1.json")
    c.update(TINY)
    c["serve"] = dict(c["serve"], max_seq=256, decode_slots=4)
    return c


def context(config: dict, mix: dict, seed: int = 2**31 + 12345,
            seconds: float = 1.5, n_devices: int = 1):
    import jax
    return harness.Context(
        name="tiny", config=config, traffic=mix, seed=seed,
        seconds=seconds, trace_dir=None, devices=jax.devices()[:n_devices],
        peaks=None, t_start=time.perf_counter(),
        clock=harness.compile_clock(), log=lambda m: None)
