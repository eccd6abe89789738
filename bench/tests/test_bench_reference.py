"""The plain reference against the program's model at a small size on
the CPU: prefill and decode logits.  Both use the benchmark's seeded
weights."""
from __future__ import annotations

import bench_tiny
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, weights
from bench.drivers.common import program_config
from bench.sizes import from_config

CFG = bench_tiny.serve_config()
S = from_config(CFG)
SEED = 2**31 + 17


@pytest.fixture(scope="module")
def program():
    from repro.models import model
    from repro.models.pcontext import UNSHARDED
    cfg = program_config(CFG, S)
    params = weights.make_program_params(SEED, S)
    return model, UNSHARDED, cfg, params


def test_weights_have_the_programs_tree(program):
    model, _, cfg, params = program
    want = jax.eval_shape(lambda k: model.init_params(k, cfg),
                          jax.random.key(0))
    assert jax.tree.structure(params) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype


def test_weights_of_one_layer_match_the_stacked_tree(program):
    params = program[3]
    one = weights.layer(weights.base_key(SEED), S, 1)
    np.testing.assert_array_equal(one["wg"], params["g0"]["ffn"]["wg"][1])
    np.testing.assert_array_equal(one["wk"], params["g0"]["attn"]["wk"][1])


def test_prefill_and_decode_logits_match_the_reference(program):
    model, pc, cfg, params = program
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, S.vocab, 40).astype(np.int32)
    max_seq = 64
    logits, caches = model.prefill(params, {"tokens": jnp.asarray(
        prompt[None])}, cfg, pc, max_seq, cache_dtype=jnp.float32)
    rows = [np.asarray(logits)[0, -1]]
    toks = list(rng.integers(0, S.vocab, 5))
    for i, t in enumerate(toks[:-1]):
        out, caches = model.decode_step(
            params, caches, jnp.asarray([[t]], jnp.int32),
            jnp.int32(len(prompt) + i), cfg, pc)
        rows.append(np.asarray(out)[0, 0])
    seq = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
    want = np.arange(len(prompt) - 1, len(seq))
    ref = reference.logits_at(SEED, S, [seq], [want], pad_to=64)[0]
    np.testing.assert_allclose(np.stack(rows), ref, rtol=0,
                               atol=2e-4 * np.abs(ref).max())

