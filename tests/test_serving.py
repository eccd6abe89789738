"""Serving engine: the request-level API (submit/step/poll),
continuous-batching lifecycle (preempt / evict-to-pool / restore /
replay), paged block accounting with shared prefixes, the pooled
prefix cache across engines, and the ``generate()`` compat wrapper's
bitwise equivalence to the old batch API."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import ledger
from repro.models import model
from repro.models.pcontext import UNSHARDED
from repro.serving import (BlockManager, PooledKVStore, Request,
                           SamplingParams, Scheduler, ServeConfig,
                           ServeEngine, chain_hashes)

KEY = jax.random.key(0)
RNG = np.random.default_rng(0)


def _engine(arch="llama3.2-1b", **kw):
    cfg = get_config(arch, smoke=True)
    params = model.init_params(KEY, cfg, tp=1, dtype=jnp.float32)
    return cfg, ServeEngine(cfg, params,
                            ServeConfig(max_seq=64, **kw))


def test_greedy_generation_deterministic():
    cfg, eng = _engine()
    prompts = {"tokens": jnp.asarray(
        RNG.integers(0, cfg.vocab_size, (3, 8)))}
    a = eng.generate(prompts, max_new_tokens=6)
    b = eng.generate(prompts, max_new_tokens=6)
    assert a.shape == (3, 6)
    np.testing.assert_array_equal(a, b)
    assert a.max() < cfg.vocab_size


def test_sampled_generation_valid():
    cfg, eng = _engine(temperature=0.8)
    prompts = {"tokens": jnp.asarray(
        RNG.integers(0, cfg.vocab_size, (2, 8)))}
    out = eng.generate(prompts, max_new_tokens=5, seed=3)
    assert out.shape == (2, 5)
    assert out.max() < cfg.vocab_size


def test_ssm_engine_generates():
    cfg, eng = _engine("falcon-mamba-7b")
    prompts = {"tokens": jnp.asarray(
        RNG.integers(0, cfg.vocab_size, (2, 8)))}
    out = eng.generate(prompts, max_new_tokens=4)
    assert out.shape == (2, 4)


def test_windowed_engine_matches_full_early():
    """While the context fits the window, the windowed engine must make
    the same greedy choices as the full-cache engine."""
    cfg, full = _engine()
    _, win = _engine(window=64)
    prompts = {"tokens": jnp.asarray(
        RNG.integers(0, cfg.vocab_size, (2, 8)))}
    np.testing.assert_array_equal(full.generate(prompts, 6),
                                  win.generate(prompts, 6))


# -- scheduler / block-manager policy (no model, no jit) -------------------


def test_block_manager_shared_prefix_refcounts():
    bm = BlockManager(8, 4)
    h = chain_hashes(tuple(range(8)), 4)     # two complete blocks
    a = bm.alloc("a", 8, h)
    b = bm.alloc("b", 8, h)
    assert a == b                            # hash-shared prompt blocks
    assert bm.used_blocks == 2
    assert bm.shared_block_hits == 2
    assert all(bm.refcount(blk) == 2 for blk in a)
    # growth past the hashed prefix is private
    bm.append("b", 1)
    assert bm.used_blocks == 3
    assert bm.refcount(bm.table("b")[-1]) == 1
    bm.free("a")
    assert bm.used_blocks == 3               # b still holds the prefix
    bm.free("b")
    assert bm.used_blocks == 0


def test_scheduler_continuous_policy():
    s = Scheduler(2, BlockManager(100, 4))
    r = [s.submit(Request(id=f"r{i}", tokens=(1, 2, 3)))
         for i in range(3)]
    assert [a.state.req.id
            for a in s.admissions(lambda st: True)] == ["r0", "r1"]
    # newest running request is the eviction victim, and a preempted
    # request resumes before fresh waiting work
    assert s.pick_victim().req.id == "r1"
    assert s.pick_victim(exclude=(r[1],)).req.id == "r0"
    s.preempt(r[1])
    assert r[1].status == "preempted" and r[1].preemptions == 1
    assert [a.state.req.id
            for a in s.admissions(lambda st: True)] == ["r1"]
    s.finish(r[0])
    assert [a.state.req.id
            for a in s.admissions(lambda st: True)] == ["r2"]
    s.finish(r[1])
    s.finish(r[2])
    assert s.idle


def test_scheduler_transactional_reserve():
    """A failing reserve leaves the candidate queued (no slot leak)."""
    s = Scheduler(2, BlockManager(100, 4))
    s.submit(Request(id="r0", tokens=(1,)))
    assert s.admissions(lambda st: False) == []
    assert len(s.waiting) == 1 and len(s._free_slots) == 2
    assert [a.state.req.id
            for a in s.admissions(lambda st: True)] == ["r0"]


def test_scheduler_static_gates_admission():
    s = Scheduler(2, BlockManager(100, 4), mode="static")
    for i in range(4):
        s.submit(Request(id=f"r{i}", tokens=(1,)))
    batch = s.admissions(lambda st: True)
    assert len(batch) == 2
    assert s.admissions(lambda st: True) == []   # not drained yet
    s.finish(batch[0].state)
    assert s.admissions(lambda st: True) == []   # still one running
    s.finish(batch[1].state)
    assert len(s.admissions(lambda st: True)) == 2


# -- request-level API -----------------------------------------------------


def test_request_api_streaming():
    cfg, eng = _engine()
    toks = RNG.integers(0, cfg.vocab_size, 8)
    rid = eng.submit(Request(id="s0", tokens=toks, max_new_tokens=5))
    status, fresh = eng.poll(rid)
    assert status == "waiting" and fresh == []
    with pytest.raises(ValueError):
        eng.submit(Request(id="s0", tokens=toks))   # duplicate id
    seen = []
    busy = True
    while busy:
        busy = eng.step()
        status, fresh = eng.poll(rid)
        seen += fresh
    assert status == "finished" and len(seen) == 5
    assert max(seen) < cfg.vocab_size
    with pytest.raises(KeyError):
        eng.poll(rid)            # drained requests drop out of poll


def test_generate_is_thin_wrapper_greedy():
    cfg, eng = _engine()
    _, ref = _engine()
    toks = RNG.integers(0, cfg.vocab_size, (3, 8))
    out = ref.generate({"tokens": jnp.asarray(toks)}, max_new_tokens=6)
    sp = SamplingParams(temperature=0.0, seed=0)
    for b in range(3):
        eng.submit(Request(id=f"m{b}", tokens=toks[b], sampling=sp,
                           max_new_tokens=6))
    while eng.step():
        pass
    rows = [eng.poll(f"m{b}")[1] for b in range(3)]
    np.testing.assert_array_equal(out, np.asarray(rows))


def test_generate_is_thin_wrapper_sampled():
    cfg, eng = _engine(temperature=0.8)
    _, ref = _engine(temperature=0.8)
    toks = RNG.integers(0, cfg.vocab_size, (2, 8))
    out = ref.generate({"tokens": jnp.asarray(toks)},
                       max_new_tokens=5, seed=3)
    sp = SamplingParams(temperature=0.8, seed=3)
    for b in range(2):
        eng.submit(Request(id=f"m{b}", tokens=toks[b], sampling=sp,
                           max_new_tokens=5))
    while eng.step():
        pass
    rows = [eng.poll(f"m{b}")[1] for b in range(2)]
    np.testing.assert_array_equal(out, np.asarray(rows))


# -- KV tiering: preemption-by-eviction ------------------------------------

_TIGHT = dict(decode_slots=2, kv_block_tokens=4, hbm_budget_blocks=6)


def test_eviction_to_pool_restores_bitwise():
    cfg, eng = _engine(kv_placement="pool", **_TIGHT)
    toks = RNG.integers(0, cfg.vocab_size, (3, 8))
    out = eng.generate({"tokens": jnp.asarray(toks)}, 6)
    assert eng.counters["evictions"] > 0
    assert eng.counters["restores"] > 0
    assert eng.counters["replays"] == 0
    _, ref = _engine(decode_slots=2, kv_block_tokens=4)  # roomy HBM
    exp = ref.generate({"tokens": jnp.asarray(toks)}, 6)
    assert ref.counters["evictions"] == 0
    np.testing.assert_array_equal(out, exp)


def test_eviction_recompute_replays_bitwise():
    cfg, eng = _engine(kv_placement="recompute", **_TIGHT)
    toks = RNG.integers(0, cfg.vocab_size, (3, 8))
    out = eng.generate({"tokens": jnp.asarray(toks)}, 6)
    assert eng.counters["evictions"] > 0
    assert eng.counters["replays"] > 0
    assert eng.counters["restores"] == 0
    _, ref = _engine(decode_slots=2, kv_block_tokens=4)
    exp = ref.generate({"tokens": jnp.asarray(toks)}, 6)
    np.testing.assert_array_equal(out, exp)


def test_ssm_whole_image_eviction_bitwise():
    """SSM state has no seq axis: eviction serializes the whole image
    and must still restore bitwise."""
    cfg, eng = _engine("falcon-mamba-7b", kv_placement="pool", **_TIGHT)
    toks = RNG.integers(0, cfg.vocab_size, (3, 8))
    out = eng.generate({"tokens": jnp.asarray(toks)}, 6)
    assert eng.counters["evictions"] > 0
    _, ref = _engine("falcon-mamba-7b", decode_slots=2,
                     kv_block_tokens=4)
    exp = ref.generate({"tokens": jnp.asarray(toks)}, 6)
    np.testing.assert_array_equal(out, exp)


def test_static_scheduler_matches_continuous():
    cfg, eng = _engine(scheduler="static", decode_slots=2)
    _, ref = _engine(decode_slots=2)
    toks = {"tokens": jnp.asarray(
        RNG.integers(0, cfg.vocab_size, (3, 8)))}
    np.testing.assert_array_equal(eng.generate(toks, 5),
                                  ref.generate(toks, 5))


def test_budget_too_small_raises():
    cfg, eng = _engine(decode_slots=2, kv_block_tokens=4,
                       hbm_budget_blocks=1)
    eng.submit(Request(id="big",
                       tokens=RNG.integers(0, cfg.vocab_size, 8)))
    with pytest.raises(MemoryError):
        eng.step()


def test_kv_block_plan_cell_overrides_oracle(tmp_path):
    """A kv_block cell written by ``tune --kv-block-bytes`` must win
    over the live oracle (the plan->serve contract)."""
    from repro.tuner import save_plan
    from repro.tuner.plan import Choice, Plan, hardware_fingerprint
    plan = Plan(fingerprint=hardware_fingerprint())
    plan.add("kv_block", 1 << 16, 1,
             Choice(backend="recompute", slicing_factor=1,
                    allreduce_mode="kv_tier"))
    path = str(tmp_path / "plan.json")
    save_plan(plan, path)
    cfg, eng = _engine(plan_path=path, **_TIGHT)
    toks = RNG.integers(0, cfg.vocab_size, (3, 8))
    ledger.reset()
    eng.generate({"tokens": jnp.asarray(toks)}, 6)
    assert eng.counters["evictions"] > 0
    assert eng.counters["replays"] > 0      # plan forced recompute
    assert eng.counters["restores"] == 0
    cells = [c for c in ledger.snapshot()["auto_choices"]
             if c["primitive"] == "kv_block"]
    assert cells and all(c["backend"] == "recompute" for c in cells)


# -- pooled prefix sharing -------------------------------------------------


def test_pooled_prefix_sharing_across_engines():
    """Engine A publishes its prompt's blocks; engine B (sharing the
    pool) restores them instead of prefilling, bit-identically."""
    cfg = get_config("llama3.2-1b", smoke=True)
    params = model.init_params(KEY, cfg, tp=1, dtype=jnp.float32)
    scfg = ServeConfig(max_seq=64, decode_slots=2, kv_block_tokens=8,
                       prefix_sharing=True)
    a = ServeEngine(cfg, params, scfg)
    toks = RNG.integers(0, cfg.vocab_size, (1, 32))
    exp = a.generate({"tokens": jnp.asarray(toks)}, 4)
    assert a.counters["prefix_publishes"] == 4   # 32 tok / 8-tok blocks
    assert a.counters["prefix_hits"] == 0
    b = ServeEngine(cfg, params, scfg, pool=a.pool)
    ledger.reset()
    got = b.generate({"tokens": jnp.asarray(toks)}, 4)
    # restore is capped at 3 blocks: >= 1 prompt token must be
    # teacher-forced to produce the logits the first sample needs
    assert b.counters["prefix_hits"] == 1
    assert b.counters["prefix_hit_tokens"] == 24
    assert b.counters["prefills"] == 0
    np.testing.assert_array_equal(got, exp)
    cells = [c for c in ledger.snapshot()["auto_choices"]
             if c["primitive"] == "kv_prefix"]
    assert len(cells) == 1 and cells[0]["backend"] == "pool"


def test_prefix_store_doorbell_and_refcount_protocol():
    """put commits via the doorbell; pinned entries survive reclaim."""
    pool = PooledKVStore(4 << 16, block_bytes=1 << 16, max_entries=4)
    assert pool.put("a", bytes(1 << 16))
    assert pool.put("b", bytes(1 << 16))
    pool.acquire("a")
    # filling the budget reclaims LRU *unpinned* entries only
    assert pool.put("c", bytes(1 << 16))
    assert pool.put("d", bytes(1 << 16))
    assert pool.put("e", bytes(1 << 16))
    assert "a" in pool and pool.get("a") == bytes(1 << 16)
    assert "b" not in pool                   # LRU, unpinned: reclaimed
    with pytest.raises(ValueError):
        pool.remove("a")                     # still referenced
    pool.release("a")
    pool.remove("a")
    assert "a" not in pool


# -- engine spans on the profiler's clock ----------------------------------


def _within(outer, spans, name=None):
    return [s for s in spans if s is not outer and (name is None
            or s[0] == name) and outer[1] <= s[1] and s[2] <= outer[2]]


def test_engine_spans_nest_under_the_profiler(profiled):
    cfg, eng = _engine(decode_slots=2)
    toks = RNG.integers(0, cfg.vocab_size, (3, 8))
    for b in range(3):
        eng.submit(Request(id=f"p{b}", tokens=toks[b], max_new_tokens=4))

    def drain():
        while eng.step():
            pass
    spans = profiled(drain)
    steps = [s for s in spans if s[0] == "serve.step"]
    decoded = 0
    for st in steps:
        dec = _within(st, spans, "serve.decode")
        assert len(dec) <= 1
        decoded += len(dec)
    assert decoded == eng.counters["decode_steps"] > 0
    admits = [s for s in spans if s[0] == "serve.admit"]
    assert sorted(a[3]["req"] for a in admits) == ["p0", "p1", "p2"]
    for a in admits:
        assert a[3]["tokens"] == 8
        assert {s[0] for s in _within(a, spans)} == {
            "serve.prefill", "serve.kv.extract", "serve.kv.insert",
            "serve.sample"}
        assert any(_within(st, [a]) for st in steps)


@pytest.mark.parametrize("placement,inner", [
    ("pool", {"serve.kv.insert"}),
    ("recompute", {"serve.prefill", "serve.kv.extract", "serve.kv.insert"})])
def test_eviction_spans(profiled, placement, inner):
    cfg, eng = _engine(kv_placement=placement, **_TIGHT)
    toks = RNG.integers(0, cfg.vocab_size, (3, 8))
    spans = profiled(lambda: eng.generate({"tokens": jnp.asarray(toks)},
                                          6))
    admits = [s for s in spans if s[0] == "serve.admit"]
    # a preempted request's second admission restores or replays it
    again = [a for i, a in enumerate(admits)
             if a[3]["req"] in {b[3]["req"] for b in admits[:i]}]
    assert again and all({s[0] for s in _within(a, spans)} == inner
                         for a in again)
    # an eviction's extract happens in a step, outside any admission
    evicted = [s for s in spans if s[0] == "serve.kv.extract"
               and not any(_within(a, [s]) for a in admits)]
    assert bool(evicted) == (placement == "pool")
    steps = [s for s in spans if s[0] == "serve.step"]
    for s in evicted:
        assert any(_within(st, [s]) for st in steps)
