"""Request-level serving engine: continuous batching over a pooled,
paged KV cache.

The engine's numeric state is one dense slot-major cache pytree
(``ServeConfig.decode_slots`` batch lanes) driven by a single jitted
step whose position argument is a per-slot vector
(``model.decode_step`` with ``pos: (B,)``), so slots at different
depths decode together.  Around it:

* admission / preemption / slot packing live in
  ``serving.scheduler.Scheduler`` (``continuous`` or the
  batch-synchronous ``static`` baseline);
* HBM is accounted in fixed token blocks
  (``serving.kvcache.BlockManager``), and when a growing sequence
  cannot get a block the newest running request is *evicted to the
  pool*: its slot's cache image is serialized through
  ``core.pool.PoolBlockAllocator`` (doorbell-committed) and restored
  bitwise-exactly when a slot frees up - or, when the placement
  oracle prices recompute cheaper than the pool round-trip, dropped
  and re-prefixed by teacher-forcing (the ``kv_block`` plan cell
  decides, audited in the ledger like any collective);
* with ``prefix_sharing`` on, complete prompt blocks are published to
  a hash-addressed :class:`~repro.serving.kvcache.PooledKVStore`; a
  later request (this engine or any engine *sharing the store*)
  restores the longest pooled prefix instead of prefilling it, and
  teacher-forces only the remainder.

API: ``submit(Request) -> id``, ``step() -> bool`` (one scheduler
round + one decode step), ``poll() -> finished-token streaming``.
``generate()`` remains as a thin compat wrapper (submit-all +
step-until-drained) over the same machinery.  Sampling is
per-request: the key is ``fold_in(key(seed), token_index)``, so a
request's token stream is invariant to how it was scheduled,
preempted, or restored.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ledger
from repro.models import model
from repro.models.config import ModelConfig
from repro.models.pcontext import ParallelContext, UNSHARDED
from repro.obs.trace import span, step as trace_step
from repro.serving import kvcache
from repro.serving.scheduler import (FINISHED, RUNNING, Request,
                                     RequestState, SamplingParams,
                                     Scheduler)
from repro.tuner.costmodel import roofline_compute_time

_ENGINE_IDS = itertools.count()


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Engine-level serving configuration.

    Per-request knobs (temperature, seed) moved to
    ``serving.scheduler.SamplingParams`` - the ``temperature`` field
    here survives only as the default the ``generate()`` compat
    wrapper folds into its requests' ``SamplingParams`` (see
    docs/API.md for the migration).
    """

    max_seq: int = 2048
    window: Optional[int] = None          # sliding-window cache size
    temperature: float = 0.0              # compat default for generate()
    cache_dtype: str = "float32"
    # Autotuning plan (repro.launch.tune output).  When set, the engine's
    # Communicator switches to backend='auto' driven by this plan, and
    # kv_block cache-placement cells in it override the live oracle.
    plan_path: Optional[str] = None
    # KV tiering (PR 9): decode lanes, HBM block budget, pool budget.
    decode_slots: int = 4
    kv_block_tokens: int = 16
    hbm_budget_blocks: Optional[int] = None   # None: slots*ceil(seq/bt)
    pool_budget_bytes: int = 64 << 20
    pool_block_bytes: int = 1 << 16
    scheduler: str = "continuous"             # or 'static' (baseline)
    # Eviction placement: 'auto' prices pool-round-trip vs recompute
    # through the kv_block plan cell / live oracle; 'pool' and
    # 'recompute' force one arm (tests, A/B benchmarks).
    kv_placement: str = "auto"
    # Cross-request pooled-prefix sharing.  Off by default: a pooled
    # prefix is restored bitwise, but the *suffix* is then teacher-
    # forced through the decode path, whose float reduction order can
    # differ from prefill's - repeated identical prompts would no
    # longer be bit-identical to the first.  The Poisson benchmark and
    # ``serve --prompt-reuse`` turn it on.
    prefix_sharing: bool = False


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, scfg: ServeConfig,
                 pc: ParallelContext = UNSHARDED, *,
                 pool: Optional[kvcache.PooledKVStore] = None,
                 obs=None):
        self.cfg = cfg
        self.params = params
        self.scfg = scfg
        self.obs = obs
        self._kv_plan = None
        if scfg.plan_path is not None:
            from repro.core.hw import CXL_POOL, INFINIBAND
            from repro.tuner import load_plan
            plan = load_plan(scfg.plan_path, pool=CXL_POOL,
                             ib=INFINIBAND)
            self._kv_plan = plan
            pc = dataclasses.replace(
                pc, comm=dataclasses.replace(pc.comm, backend="auto",
                                             plan=plan))
            if pc.tp_axis is None or pc.tp == 1:
                self._diag("plan loaded but the engine is unsharded "
                           "(tp=1): no collectives to autotune")
        self.pc = pc
        self._uid = f"eng{next(_ENGINE_IDS)}"
        cd = jnp.dtype(scfg.cache_dtype)
        self._cd = cd
        self._n_prefix = cfg.frontend_tokens if (
            cfg.frontend != "text" and cfg.encoder is None) else 0

        # Dense slot cache + its structural layout.
        self.layout = kvcache.CacheLayout(
            cfg, pc, scfg.decode_slots, scfg.max_seq, cd,
            window=scfg.window)
        self.caches = model.init_cache(cfg, pc, scfg.decode_slots,
                                       scfg.max_seq, cache_dtype=cd,
                                       window=scfg.window)

        # Paged HBM accounting + scheduler + pool tier.
        bt = scfg.kv_block_tokens
        n_hbm = scfg.hbm_budget_blocks
        if n_hbm is None:
            n_hbm = scfg.decode_slots * (-(-scfg.max_seq // bt))
        self.blocks = kvcache.BlockManager(n_hbm, bt)
        self.sched = Scheduler(scfg.decode_slots, self.blocks,
                               mode=scfg.scheduler)
        self.pool = pool if pool is not None else kvcache.PooledKVStore(
            scfg.pool_budget_bytes, block_bytes=scfg.pool_block_bytes)
        self._share = bool(scfg.prefix_sharing
                           and self.layout.block_sharable)

        self._states: dict = {}          # request id -> RequestState
        self._sample_after: dict = {}    # id -> sample when forced drains
        self._gen = itertools.count()
        # Serving counters (exported through obs, read by stats()).
        self.counters = {"finished": 0, "evictions": 0, "restores": 0,
                         "replays": 0, "prefix_hits": 0,
                         "prefix_hit_tokens": 0, "prefix_publishes": 0,
                         "decode_steps": 0, "prefills": 0}
        # request id -> host copy of its logits from the latest decode
        # step that advanced it (for checks against a reference)
        self.last_logits: dict = {}

        def prefill_impl(p, b):
            return model.prefill(p, b, cfg, pc, scfg.max_seq,
                                 cache_dtype=cd, window=scfg.window)

        self._prefill = jax.jit(prefill_impl)

        def step_impl(p, c, tok, pos, active):
            logits, nc = model.decode_step(p, c, tok, pos, cfg, pc,
                                           window=scfg.window)
            return logits, self.layout.where_slots(active, nc, c)

        self._decode = jax.jit(step_impl)

    # -- diagnostics / metrics --------------------------------------------

    def _diag(self, msg: str) -> None:
        if self.obs is not None:
            self.obs.diag("serve", msg)
        else:
            print(f"[serve] {msg}")

    def stats(self) -> dict:
        return {"inflight": self.sched.inflight,
                "running": len(self.sched.running),
                "waiting": len(self.sched.waiting),
                "preempted_queued": len(self.sched.preempted),
                "hbm_blocks_used": self.blocks.used_blocks,
                "hbm_shared_hits": self.blocks.shared_block_hits,
                "pool": self.pool.stats, **self.counters}

    def _export_metrics(self) -> None:
        if self.obs is None or not self.obs.enabled:
            return
        g = self.obs.registry.gauge
        g("repro_serve_inflight",
          "requests in flight").set(self.sched.inflight)
        g("repro_serve_hbm_blocks_used",
          "HBM KV blocks held").set(self.blocks.used_blocks)
        g("repro_serve_pool_blocks_used",
          "pool KV blocks held").set(self.pool.alloc.used_blocks)
        for k in ("finished", "evictions", "restores", "replays",
                  "prefix_hits", "prefix_publishes"):
            g(f"repro_serve_{k}_total", f"serving {k}").set(
                self.counters[k])
        g("repro_serve_pool_hits_total",
          "pooled KV store hits").set(self.pool.hits)

    # -- request API -------------------------------------------------------

    def submit(self, req: Request) -> str:
        """Queue a request; returns its id (``poll`` key)."""
        if req.id in self._states:
            raise ValueError(f"request id {req.id!r} already submitted")
        self._states[req.id] = self.sched.submit(req)
        return req.id

    def poll(self, req_id: Optional[str] = None):
        """Finished-token streaming.  ``poll(id)`` returns
        ``(status, new_tokens)`` - the tokens generated since the last
        poll.  ``poll()`` returns ``{id: (status, new_tokens)}`` for
        every tracked request and drops fully-delivered finished
        requests from tracking."""
        if req_id is not None:
            st = self._states[req_id]
            fresh = [int(t) for t in st.generated[st.delivered:]]
            st.delivered = len(st.generated)
            if st.status == FINISHED and st.delivered == len(
                    st.generated):
                del self._states[req_id]
            return st.status, fresh
        out = {}
        for rid in list(self._states):
            out[rid] = self.poll(rid)
        return out

    def step(self) -> bool:
        """One engine round: admit what fits, secure block capacity
        (evicting to the pool when HBM runs out), run one jitted
        decode step over every running slot, sample/advance each
        request.  Returns True while work remains."""
        with trace_step("serve.step", self.counters["decode_steps"]):
            self._do_step()
        self._export_metrics()
        return not self.sched.idle

    # -- compat wrapper ----------------------------------------------------

    def generate(self, batch: dict, max_new_tokens: int,
                 seed: int = 0) -> np.ndarray:
        """Pre-PR-9 batch API, now a thin wrapper: one request per
        batch row (temperature from ``ServeConfig``), stepped until
        drained.  Returns (B, max_new_tokens)."""
        toks = np.asarray(batch["tokens"])
        sp = SamplingParams(temperature=self.scfg.temperature,
                            seed=seed)
        ids = []
        for b in range(toks.shape[0]):
            extras = {k: np.asarray(v)[b] for k, v in batch.items()
                      if k != "tokens"} or None
            ids.append(self.submit(Request(
                id=f"gen{next(self._gen)}", tokens=toks[b],
                sampling=sp, max_new_tokens=max_new_tokens,
                extras=extras)))
        while self.step():
            pass
        rows = []
        for rid in ids:
            _status, fresh = self.poll(rid)
            rows.append(fresh)
        return np.asarray(rows, np.int32)

    # -- internals ---------------------------------------------------------

    def _prompt_ntok(self, st: RequestState) -> int:
        return self._n_prefix + len(st.req.tokens)

    def _hashes(self, st: RequestState) -> list:
        """Chain hashes of the prompt's complete blocks (content
        addressing is text-only: conditioned requests don't share)."""
        if st.req.extras is not None or self._n_prefix:
            return []
        return kvcache.chain_hashes(st.req.tokens,
                                    self.blocks.block_tokens)

    def _reserve(self, st: RequestState) -> bool:
        """Transactionally claim the blocks an admission needs (the
        scheduler's ``reserve`` callback)."""
        ntok = st.pos if st.preemptions else self._prompt_ntok(st)
        try:
            self.blocks.alloc(st.req.id, max(ntok, 1),
                              self._hashes(st))
            return True
        except MemoryError:
            return False

    def _replay_flops(self, ntok: int) -> float:
        """Roofline FLOPs of recomputing ``ntok`` tokens of cache
        (~2 * active params per token, forward only)."""
        return 2.0 * self.cfg.active_param_count() * max(1, ntok)

    def _evict(self, st: RequestState) -> None:
        """Preemption-by-eviction: spill ``st``'s slot to the pool (or
        drop it for recompute when the oracle prices that cheaper)."""
        nbytes = self.layout.bytes_for(st.pos)
        if self.scfg.kv_placement == "auto":
            choice = kvcache.resolve_kv_choice(
                "kv_block", nbytes, self._replay_flops(st.pos),
                plan=self._kv_plan,
                block_bytes=self.pool.alloc.block_bytes)
            backend = choice.backend
        else:
            backend = self.scfg.kv_placement
            ledger.record_choice("kv_block", max(1, nbytes), 1,
                                 backend, 1, "kv_tier")
        slot = st.slot
        if backend == "pool":
            with span("serve.kv.extract"):
                img = self.layout.extract_slot(self.caches, slot, st.pos)
            if not self.pool.put(("evict", self._uid, st.req.id), img):
                self._diag(f"pool budget full: eviction of "
                           f"{st.req.id!r} falls back to recompute")
        self.blocks.free(st.req.id)
        self.sched.preempt(st)
        self.counters["evictions"] += 1

    def _ensure_capacity(self, st: RequestState) -> bool:
        """Secure the next token's HBM block, evicting newer requests
        as needed.  False when ``st`` itself got preempted."""
        while True:
            try:
                self.blocks.append(st.req.id, 1)
                return True
            except MemoryError:
                victim = self.sched.pick_victim(exclude=(st,))
                if victim is None:
                    raise MemoryError(
                        f"hbm_budget_blocks={self.blocks.num_blocks} "
                        f"cannot hold request {st.req.id!r} alone "
                        f"({self.blocks.used_blocks} blocks at "
                        f"{st.pos} tokens)")
                self._evict(victim)

    def _sample_one(self, row, sp: SamplingParams, index: int) -> int:
        row = row[:self.cfg.vocab_size]
        if sp.temperature == 0.0:
            return int(jnp.argmax(row))
        key = jax.random.fold_in(jax.random.key(sp.seed), index)
        return int(jax.random.categorical(key, row / sp.temperature))

    def _finish(self, st: RequestState) -> None:
        self.blocks.free(st.req.id)
        self.sched.finish(st)
        self.counters["finished"] += 1

    def _prefill_into_slot(self, st: RequestState):
        """Prefill ``st``'s prompt at batch 1 and copy its cache into
        the slot through the canonical byte image; returns the logits
        of the last prompt position."""
        with span("serve.prefill"):
            b = {"tokens": jnp.asarray(
                np.asarray(st.req.tokens, np.int32)[None])}
            if st.req.extras is not None:
                for k, v in st.req.extras.items():
                    b[k] = jnp.asarray(np.asarray(v)[None])
            logits, c1 = jax.block_until_ready(
                self._prefill(self.params, b))
        self.counters["prefills"] += 1
        st.n_prefix = self._n_prefix
        st.pos = self._prompt_ntok(st)
        with span("serve.kv.extract"):
            img = self._lay1.extract_slot(c1, 0, st.pos)
        with span("serve.kv.insert"):
            self.caches = self.layout.insert_slot(self.caches, st.slot,
                                                  st.pos, img)
        return logits

    def _prefill_request(self, st: RequestState) -> None:
        """Materialize a fresh prompt: full prefill into the slot, then
        sample the first token."""
        logits = self._prefill_into_slot(st)
        if self._share:
            self._publish_prefix(st)
        with span("serve.sample"):
            tok = self._sample_one(np.asarray(logits)[0, -1],
                                   st.req.sampling, 0)
        st.generated.append(tok)
        st.last_token = tok

    @property
    def _lay1(self) -> kvcache.CacheLayout:
        """Layout of a batch-1 prefill cache (same leaves, one slot)."""
        if not hasattr(self, "_lay1_cached"):
            self._lay1_cached = kvcache.CacheLayout(
                self.cfg, self.pc, 1, self.scfg.max_seq, self._cd,
                window=self.scfg.window)
        return self._lay1_cached

    def _publish_prefix(self, st: RequestState) -> None:
        """Push the prompt's complete blocks to the pooled prefix
        store (hash-addressed; write -> refcount -> doorbell ring)."""
        hashes = self._hashes(st)
        bt = self.blocks.block_tokens
        for i, h in enumerate(hashes):
            key = ("kvblk", h)
            if key in self.pool:
                continue
            with span("serve.kv.extract"):
                img = self.layout.extract_token_range(
                    self.caches, st.slot, i * bt, (i + 1) * bt)
            if not self.pool.put(key, img):
                break               # pool full of pinned entries
            self.counters["prefix_publishes"] += 1

    def _try_prefix_restore(self, st: RequestState) -> bool:
        """Restore the longest pooled prefix and queue the rest of the
        prompt for teacher-forcing.  False on miss (caller prefills)."""
        if not self._share:
            return False
        hashes = self._hashes(st)
        bt = self.blocks.block_tokens
        prompt_len = len(st.req.tokens)
        # Cap so at least one prompt token is teacher-forced: its
        # decode step yields the logits the first sample needs.
        usable = min(len(hashes), (prompt_len - 1) // bt)
        run = 0
        while run < usable and ("kvblk", hashes[run]) in self.pool:
            run += 1
        if run == 0:
            return False
        imgs = []
        keys = [("kvblk", h) for h in hashes[:run]]
        for key in keys:
            self.pool.acquire(key)      # pin against reclaim mid-read
        try:
            for key in keys:
                img = self.pool.get(key)
                if img is None:         # lost a race with reclaim
                    return False
                imgs.append(img)
        finally:
            for key in keys:
                self.pool.release(key)
        for i, img in enumerate(imgs):
            with span("serve.kv.insert"):
                self.caches = self.layout.insert_token_range(
                    self.caches, st.slot, i * bt, (i + 1) * bt, img)
        prefix = run * bt
        st.pos = prefix
        st.forced = tuple(st.req.tokens[prefix:])
        self._sample_after[st.req.id] = True
        st.prefix_hit_tokens = prefix
        self.counters["prefix_hits"] += 1
        self.counters["prefix_hit_tokens"] += prefix
        # Audit: pooled prefix replaced prefill compute over `prefix`
        # tokens - a kv_prefix cell, recorded like any collective.
        nbytes = self.layout.bytes_for_range(0, prefix)
        ledger.record_choice(
            "kv_prefix", max(1, nbytes), 1, "pool", 1, "kv_tier",
            predicted_time=self.pool.predict_get_s(nbytes),
            baseline_time=roofline_compute_time(
                self._replay_flops(prefix)))
        return True

    def _admit(self, st: RequestState, slot: int) -> None:
        with span("serve.admit", req=st.req.id, tokens=len(st.req.tokens)):
            if st.preemptions:
                key = ("evict", self._uid, st.req.id)
                img = self.pool.get(key)
                if img is not None:
                    # Bitwise restore of the evicted image (blocks were
                    # reserved at admission).
                    with span("serve.kv.insert"):
                        self.caches = self.layout.insert_slot(
                            self.caches, slot, st.pos, img)
                    self.pool.remove(key)
                    self.counters["restores"] += 1
                    return
                self._replay(st)
                return
            if self._try_prefix_restore(st):
                return
            self._prefill_request(st)
            if st.done:
                self._finish(st)

    def _replay(self, st: RequestState) -> None:
        """Recompute path: re-prefill the prompt, then teacher-force the
        tokens already sampled (minus the last, which is the next
        step's input).  The sample stream is index-keyed, so the
        continuation is unchanged."""
        # Re-size the admission reservation (made at the preempted
        # pos) down to the prompt; forced steps grow it back.
        self.blocks.free(st.req.id)
        self.blocks.alloc(st.req.id, self._prompt_ntok(st),
                          self._hashes(st))
        self._prefill_into_slot(st)
        # Sampling must not rerun when the forced queue drains.
        st.forced = tuple(st.generated[:-1])
        self._sample_after[st.req.id] = False
        st.last_token = st.generated[-1]
        self.counters["replays"] += 1

    def _do_step(self) -> None:
        for adm in self.sched.admissions(self._reserve):
            self._admit(adm.state, adm.slot)
        # Secure one token of growth per running request; evictions
        # here shrink `running` for this round.
        stepping = []
        for st in list(self.sched.running.values()):
            if st.status == RUNNING and self._ensure_capacity(st):
                stepping.append(st)
        # An eviction later in the loop may have preempted an earlier
        # entrant; only still-running slots step.
        stepping = [st for st in stepping if st.status == RUNNING]
        if not stepping:
            if self.sched.inflight and not self.sched.running:
                head = (self.sched.preempted or self.sched.waiting)[0]
                raise MemoryError(
                    f"engine cannot make progress: request "
                    f"{head.req.id!r} does not fit an empty "
                    f"hbm_budget_blocks={self.blocks.num_blocks}")
            return
        n = self.scfg.decode_slots
        tok = np.zeros((n, 1), np.int32)
        pos = np.zeros((n,), np.int32)
        active = np.zeros((n,), bool)
        for st in stepping:
            feed = st.forced[0] if st.forced else st.last_token
            tok[st.slot, 0] = feed
            pos[st.slot] = st.pos
            active[st.slot] = True
        with span("serve.decode"):
            logits, self.caches = self._decode(
                self.params, self.caches, jnp.asarray(tok),
                jnp.asarray(pos), jnp.asarray(active))
            rows = np.asarray(logits)[:, 0]
        self.counters["decode_steps"] += 1
        self.last_logits = {st.req.id: rows[st.slot] for st in stepping}
        with span("serve.sample"):
            for st in stepping:
                st.pos += 1
                if st.forced:
                    st.forced = st.forced[1:]
                    if st.forced:
                        continue
                    if not self._sample_after.pop(st.req.id, True):
                        continue    # replay rejoin: last_token is set
                tokv = self._sample_one(rows[st.slot], st.req.sampling,
                                        len(st.generated))
                st.generated.append(tokv)
                st.last_token = tokv
                if st.done:
                    self._finish(st)
