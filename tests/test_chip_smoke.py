"""``chip_smoke.py`` at a tiny size on the CPU.

The script itself runs its phases at llama3.2-1b's published widths and
refuses any backend but a TPU; these tests call the same phase
functions with the smoke config, so its control flow and checks are
exercised on every run without a chip.  The four-chip phase runs on
forced host devices in ``_mesh_runner.py``.
"""
import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.configs import get_config  # noqa: E402


@pytest.fixture(scope="module")
def cfg():
    return get_config("llama3.2-1b", smoke=True)


def test_train_phase_tiny(cfg):
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         devices=jax.devices()[:1])
    out = chip_smoke.train_phase(cfg, mesh, "ring", batch=2, seq=16,
                                 steps=5)
    assert len(out["losses"]) == 5
    assert out["losses"][-1] < out["losses"][0]


def test_serve_phase_tiny(cfg):
    out = chip_smoke.serve_phase(cfg, n_requests=3, prompt_len=8,
                                 new_tokens=4, slots=2)
    assert sorted(out["tokens"]) == ["req0", "req1", "req2"]
    assert all(len(t) == 4 for t in out["tokens"].values())
    # fp32 on the CPU: decode and the full forward agree far inside the
    # chip's bf16-pass bound
    assert out["logit_err"] < 1e-3


def test_train_cut_keeps_published_widths():
    cut = chip_smoke.train_cut()
    full = chip_smoke.CONFIG
    assert (cut.n_layers, len(cut.layer_pattern)) == (4, 4)
    assert (cut.d_model, cut.n_heads, cut.n_kv_heads, cut.d_ff,
            cut.vocab_size) == (full.d_model, full.n_heads,
                                full.n_kv_heads, full.d_ff,
                                full.vocab_size)


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert '"ok"' not in proc.stdout
