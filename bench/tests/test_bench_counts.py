"""Flop and byte counts against hand counts, the traffic generator's
determinism, clips and rounding."""
from __future__ import annotations

import types

import bench_tiny  # noqa: F401  (puts the repo on sys.path)
import numpy as np
import pytest

from bench import counts, traffic
from bench.harness import ROOT, _module, load_json
from bench.sizes import from_config

DSC = from_config(load_json(
    ROOT / "bench/configs/deepseek-coder-33b.serve1.json"))


def test_sizes_are_the_published_widths():
    assert (DSC.d_model, DSC.n_heads, DSC.n_kv_heads, DSC.d_ff, DSC.vocab,
            DSC.head_dim) == (7168, 56, 8, 19200, 32256, 128)


def test_deepseek_coder_decode_step_by_hand():
    layer = (2 * 7168 * 7168          # wq, wo: 56 heads of 128
             + 2 * 7168 * 8 * 128     # wk, wv: 8 kv heads
             + 3 * 7168 * 19200)      # SwiGLU
    assert DSC.layer_matmul_params == 530_317_312 == layer
    matmul = 10 * layer + 7168 * 32256
    assert DSC.matmul_params == matmul
    flops, nbytes = counts.decode_step(DSC, [100, 200], 2, 2)
    # two tokens through every matmul, and 300 query-key pairs in each
    # of 10 layers at 4 flops per pair per head dim over 56 heads
    assert flops == 2 * 2 * matmul + 300 * 10 * 4 * 56 * 128
    kv_token = 2 * 10 * 8 * 128 * 2   # k and v, 10 layers, bf16
    # bf16 matrices and 21 norm vectors, and two embedding rows
    weights = (matmul + 21 * 7168) * 2 + 2 * 7168 * 2
    assert nbytes == weights + (300 - 2) * kv_token \
        + 2 * kv_token + 2 * 32256 * 4


def test_prefill_flops_by_hand():
    t = 512
    assert counts.prefill(DSC, t) == (
        2 * t * 10 * DSC.layer_matmul_params + 2 * 7168 * 32256
        + 10 * 4 * 56 * 128 * t * (t + 1) // 2)


MIX = load_json(ROOT / "bench/traffic/code.json")


def test_traffic_repeats_exactly_for_a_seed():
    a = traffic.requests(MIX, 2**31 + 99, 45.0, DSC.vocab)
    b = traffic.requests(MIX, 2**31 + 99, 45.0, DSC.vocab)
    assert [(r.due_s, r.max_new_tokens) for r in a] == \
        [(r.due_s, r.max_new_tokens) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_seeds_share_the_sizes_in_another_order():
    a = traffic.requests(MIX, 1, 45.0, DSC.vocab)
    b = traffic.requests(MIX, 2, 45.0, DSC.vocab)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    # the same work, up to the requests the window cuts off
    la = sorted(len(r.prompt) for r in a)
    lb = sorted(len(r.prompt) for r in b)
    n = min(len(la), len(lb))
    assert abs(len(la) - len(lb)) <= 3
    assert abs(sum(la[:n]) - sum(lb[:n])) <= 3 * MIX["prompt_tokens"]["max"]


def test_traffic_follows_its_clips_and_rounding():
    reqs = traffic.requests(MIX, 7, 45.0, DSC.vocab)
    p = np.array([len(r.prompt) for r in reqs])
    o = np.array([r.max_new_tokens for r in reqs])
    assert p.min() >= 512 and p.max() <= 3584 and np.all(p % 512 == 0)
    assert o.min() >= 2 and o.max() <= 128
    assert set(p) == {512, 1024, 1536, 2048, 2560, 3072, 3584}
    due = np.array([r.due_s for r in reqs])
    assert due[0] == 0.0 and np.all(np.diff(due) > 0) and due[-1] < 45.0
    assert 0.9 * MIX["rate_per_s"] * 45 <= len(reqs) <= \
        MIX["rate_per_s"] * 45 + 1
    assert all(r.prompt.max() < DSC.vocab for r in reqs)


def test_lengths_are_quantiles_of_the_lognormal():
    spec = {"lognormal_median": 100, "lognormal_sigma": 1.0, "min": 1,
            "max": 10**9}
    x = traffic.lengths(spec, 1001)
    assert x[500] == 100 and np.all(np.diff(x) >= 0)

