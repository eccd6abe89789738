"""A run whose timed path is broken underneath comes out not correct.

The serving driver runs at a small size on the CPU (the look for a chip
is skipped), under the cell's own limits, once sound and once for each
fault a serving cell can have: a decode step that returns its cache
unchanged, half of the batch left out of the step, and a token altered
where it is sampled.  (The serving cell runs on one chip: it has no
exchange between chips to leave out.)"""
from __future__ import annotations

import bench_tiny
import jax.numpy as jnp
import pytest

from bench.drivers import serve


@pytest.fixture
def serve_ctx():
    return bench_tiny.context(bench_tiny.serve_config(), bench_tiny.MIX)


def _wrap_decode(monkeypatch, fault):
    from repro.serving import engine as eng_mod
    init = eng_mod.ServeEngine.__init__

    def patched(self, *a, **k):
        init(self, *a, **k)
        decode = self._decode
        self._decode = lambda p, c, tok, pos, act: fault(decode, p, c, tok,
                                                         pos, act)
    monkeypatch.setattr(eng_mod.ServeEngine, "__init__", patched)


def test_sound_serving_run_is_correct(serve_ctx):
    out = serve.run(serve_ctx)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 10


def test_decode_returning_its_cache_unchanged_is_caught(serve_ctx,
                                                        monkeypatch):
    def fault(decode, p, c, tok, pos, act):
        logits, _ = decode(p, c, tok, pos, act)
        return logits, c
    _wrap_decode(monkeypatch, fault)
    assert not serve.run(serve_ctx)["correct"]


def test_half_the_batch_left_out_is_caught(serve_ctx, monkeypatch):
    def fault(decode, p, c, tok, pos, act):
        half = jnp.arange(act.shape[0]) < act.shape[0] // 2
        return decode(p, c, tok, pos, act & half)
    _wrap_decode(monkeypatch, fault)
    assert not serve.run(serve_ctx)["correct"]


def test_a_token_altered_where_it_is_sampled_is_caught(serve_ctx,
                                                       monkeypatch):
    from repro.serving import engine as eng_mod
    sample = eng_mod.ServeEngine._sample_one

    def altered(self, row, sp, index):
        tok = sample(self, row, sp, index)
        return (tok + 1) % self.cfg.vocab_size if index == 3 else tok
    monkeypatch.setattr(eng_mod.ServeEngine, "_sample_one", altered)
    assert not serve.run(serve_ctx)["correct"]

