"""Serving launcher: request-level generation against any zoo arch.

Two modes:

* default - the pre-PR-9 fixed-batch path (one ``generate`` call per
  round, reported as tok/s); still the --online-retune vehicle.
* ``--trace poisson`` - an open-loop request trace: ``--requests``
  arrivals drawn from a Poisson process (``--arrival-rate`` requests
  per decode step) are submitted against the continuous-batching
  engine and reported as req/s + latency percentiles.
  ``--prompt-reuse`` draws that fraction of prompts from a shared
  prefix, exercising the CXL-pooled prefix cache (prefix sharing is
  auto-enabled when reuse > 0).

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
      --batch 4 --new-tokens 16 [--window 64]
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
      --trace poisson --requests 24 --arrival-rate 0.5 \
      --prompt-reuse 0.6 --decode-slots 4
"""
from __future__ import annotations

import argparse
import time

from repro.launch import xla
xla.apply_overlap_preset()   # --xla-overlap: must precede the jax import

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model
from repro.serving import (Request, SamplingParams, ServeConfig,
                           ServeEngine)


def _pct(sorted_vals: list, q: float) -> float:
    if not sorted_vals:
        return 0.0
    i = min(len(sorted_vals) - 1, int(q * (len(sorted_vals) - 1)))
    return sorted_vals[i]


def run_trace(eng: ServeEngine, cfg, args, obs_sess) -> None:
    """Open-loop Poisson request trace against the live engine."""
    rng = np.random.default_rng(args.seed)
    bt = args.kv_block_tokens
    prefix_len = args.prefix_len
    if prefix_len is None:
        # longest block-aligned prefix that still leaves a suffix
        prefix_len = max(bt, (args.prompt_len - 1) // bt * bt)
    prefix_len = min(prefix_len, args.prompt_len - 1)
    shared = rng.integers(0, cfg.vocab_size, prefix_len)
    arrivals = np.cumsum(rng.exponential(
        1.0 / args.arrival_rate, args.requests))   # in decode steps
    reqs = []
    for i in range(args.requests):
        if rng.random() < args.prompt_reuse:
            toks = np.concatenate([shared, rng.integers(
                0, cfg.vocab_size, args.prompt_len - prefix_len)])
        else:
            toks = rng.integers(0, cfg.vocab_size, args.prompt_len)
        reqs.append(Request(
            id=f"req{i}", tokens=toks,
            sampling=SamplingParams(temperature=args.temperature,
                                    seed=args.seed + i),
            max_new_tokens=args.new_tokens))
    t0 = time.time()
    born, done = {}, {}
    step, nxt = 0, 0
    while nxt < len(reqs) or not eng.sched.idle:
        if (eng.sched.idle and nxt < len(reqs)
                and arrivals[nxt] > step):
            step = int(np.ceil(arrivals[nxt]))   # skip the idle gap
        while nxt < len(reqs) and arrivals[nxt] <= step:
            eng.submit(reqs[nxt])
            born[reqs[nxt].id] = time.time()
            nxt += 1
        ts = time.time()
        eng.step()
        dt = time.time() - ts
        step += 1
        for rid, (status, _fresh) in eng.poll().items():
            if status == "finished" and rid not in done:
                done[rid] = time.time()
        if obs_sess is not None:
            obs_sess.on_step(step, dt, extra={
                "inflight": eng.sched.inflight})
    wall = time.time() - t0
    lats = sorted(done[r] - born[r] for r in done)
    toks = len(done) * args.new_tokens
    c = eng.counters
    print(f"{cfg.name}: trace poisson  {len(done)} requests in "
          f"{wall:.2f}s ({len(done) / wall:.2f} req/s, "
          f"{toks / wall:.1f} tok/s)")
    print(f"  latency p50 {_pct(lats, 0.5):.3f}s  "
          f"p99 {_pct(lats, 0.99):.3f}s  "
          f"decode steps {c['decode_steps']}  "
          f"prefills {c['prefills']}")
    print(f"  prefix hits {c['prefix_hits']} "
          f"({c['prefix_hit_tokens']} tokens pooled)  "
          f"evictions {c['evictions']}  restores {c['restores']}  "
          f"replays {c['replays']}  "
          f"preemptions {eng.sched.preemption_count}")
    if obs_sess is not None:
        from repro.core import ledger as _ledger
        obs_sess.finalize(snapshot=_ledger.snapshot(), extra={
            "requests": len(done), "wall_s": wall,
            "req_per_s": len(done) / wall,
            "latency_p50_s": _pct(lats, 0.5),
            "latency_p99_s": _pct(lats, 0.99), **eng.stats()})


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--window", type=int, default=None)
    ap.add_argument("--trace", choices=["poisson"], default=None,
                    help="request-trace mode: submit --requests "
                         "Poisson arrivals through submit/step/poll "
                         "and report req/s + latency percentiles "
                         "instead of the fixed-batch rounds")
    ap.add_argument("--requests", type=int, default=16,
                    help="trace mode: number of requests")
    ap.add_argument("--arrival-rate", type=float, default=0.5,
                    help="trace mode: mean arrivals per decode step")
    ap.add_argument("--prompt-reuse", type=float, default=0.0,
                    help="trace mode: fraction of prompts sharing a "
                         "common prefix (> 0 auto-enables "
                         "--prefix-sharing)")
    ap.add_argument("--prefix-len", type=int, default=None,
                    help="shared-prefix tokens for --prompt-reuse "
                         "(default: longest block-aligned prefix)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--decode-slots", type=int, default=4,
                    help="dense decode lanes (engine batch)")
    ap.add_argument("--kv-block-tokens", type=int, default=16,
                    help="tokens per paged HBM KV block")
    ap.add_argument("--hbm-budget-blocks", type=int, default=None,
                    help="HBM KV block budget (default: enough for "
                         "every slot at max_seq; lower it to force "
                         "eviction to the pool)")
    ap.add_argument("--pool-budget-mib", type=int, default=64,
                    help="CXL pool budget for evictions + pooled "
                         "prefixes (MiB)")
    ap.add_argument("--scheduler", choices=["continuous", "static"],
                    default="continuous",
                    help="'static' is the batch-synchronous baseline "
                         "(admits only when the engine drained)")
    ap.add_argument("--kv-placement",
                    choices=["auto", "pool", "recompute"],
                    default="auto",
                    help="eviction placement: 'auto' prices the pool "
                         "round-trip vs recompute (kv_block plan "
                         "cell / live oracle)")
    ap.add_argument("--prefix-sharing", action="store_true",
                    help="publish complete prompt blocks to the "
                         "pooled prefix store and restore them for "
                         "later matching prompts")
    ap.add_argument("--plan", default=None,
                    help="autotuning plan JSON (repro.launch.tune); "
                         "switches the engine's Communicator to "
                         "backend='auto' (takes effect when serving "
                         "sharded, i.e. with a tp>1 ParallelContext)")
    ap.add_argument("--online-retune", action="store_true",
                    help="treat every generate round as a step: fold "
                         "its measured wall time back into the plan "
                         "and hot-swap at --retune-interval round "
                         "boundaries; requires --plan (and, like "
                         "--plan itself, only folds measurements when "
                         "serving sharded: an unsharded tp=1 engine "
                         "issues no collectives to measure)")
    ap.add_argument("--retune-interval", type=int, default=4,
                    help="generate rounds between plan refresh + "
                         "hot-swap under --online-retune")
    ap.add_argument("--rounds", type=int, default=None,
                    help="number of generate rounds (default 1; "
                         "2 x retune-interval under --online-retune)")
    ap.add_argument("--plan-out", default=None,
                    help="persist the measurement-refined plan "
                         "(format v4) here at the end of the run")
    ap.add_argument("--topology", default=None,
                    help="'axis:fabric[:shape],...' spec or topology "
                         "JSON file to activate for this process")
    ap.add_argument("--placement", default=None,
                    help="'auto' or a saved placement JSON: rank the "
                         "mesh-axis -> fabric-level assignments for "
                         "this arch (tuner.placement), print the "
                         "report, and activate the placed topology + "
                         "axis aliases (takes effect when serving "
                         "sharded); needs a topology")
    ap.add_argument("--placement-axes", default="data=2,model=4",
                    help="logical axis degrees for --placement, "
                         "'name=size,...'")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-step", type=int, default=None)
    ap.add_argument("--metrics-out", default=None,
                    help="write per-round/retune events + the final "
                         "metric registry as JSON-lines here (and a "
                         "Prometheus rendering to <base>.prom); see "
                         "repro.obs")
    xla.add_argument(ap)
    args = ap.parse_args()
    enable_compile_cache()
    if args.online_retune and not args.plan:
        ap.error("--online-retune requires --plan")
    if args.trace and args.online_retune:
        ap.error("--trace and --online-retune are mutually exclusive "
                 "(retune is driven by fixed-batch rounds)")

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.topology:
        from repro.core.topology import (parse_topology,
                                         set_active_topology)
        set_active_topology(parse_topology(args.topology))
    if args.placement:
        from repro import tuner
        from repro.core.topology import (get_active_topology,
                                         set_active_topology)
        from repro.models import sharding
        topo = get_active_topology()
        if topo is None:
            ap.error("--placement requires --topology")
        axes = {k: int(v) for k, v in
                (p.split("=") for p in args.placement_axes.split(","))}
        mix = tuner.CollectiveMix.for_model(cfg, axes,
                                            seq=args.prompt_len
                                            + args.new_tokens)
        pplan = tuner.plan_placement(mix, topo) \
            if args.placement == "auto" \
            else tuner.load_placement(args.placement)
        chosen = pplan.best_with_unsplit(("model",))
        print(tuner.format_report(pplan, chosen=chosen))
        _, _, aliases = tuner.mesh_spec(chosen, mix, topo)
        sharding.set_axis_aliases(aliases)
        set_active_topology(tuner.placed_topology(chosen, topo))
    params = model.init_params(jax.random.key(0), cfg, tp=1,
                               dtype=jnp.float32)
    if args.ckpt:
        from repro.training import checkpoint
        step = args.ckpt_step or checkpoint.latest_step(args.ckpt)
        params = checkpoint.restore(args.ckpt, step,
                                    {"params": params})["params"]
        print(f"restored {args.ckpt} step {step}")
    obs_sess = None
    if args.metrics_out:
        from repro.obs import ObsSession
        obs_sess = ObsSession(metrics_out=args.metrics_out)
    scfg = ServeConfig(
        max_seq=args.prompt_len + args.new_tokens + 8,
        window=args.window, temperature=args.temperature,
        plan_path=args.plan, decode_slots=args.decode_slots,
        kv_block_tokens=args.kv_block_tokens,
        hbm_budget_blocks=args.hbm_budget_blocks,
        pool_budget_bytes=args.pool_budget_mib << 20,
        scheduler=args.scheduler, kv_placement=args.kv_placement,
        prefix_sharing=(args.prefix_sharing
                        or args.prompt_reuse > 0.0))
    eng = ServeEngine(cfg, params, scfg, obs=obs_sess)
    if args.trace:
        run_trace(eng, cfg, args, obs_sess)
        return
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(
        0, cfg.vocab_size, (args.batch, args.prompt_len)))}
    if cfg.frontend == "vision_stub" and cfg.encoder is None:
        batch["frontend"] = jnp.asarray(rng.standard_normal(
            (args.batch, cfg.frontend_tokens, cfg.frontend_dim)),
            jnp.float32)
    if cfg.encoder is not None:
        batch["source"] = jnp.asarray(rng.standard_normal(
            (args.batch, cfg.encoder.source_len, cfg.frontend_dim)),
            jnp.float32)
    online = None
    if args.online_retune:
        import dataclasses as _dc

        from repro import tuner
        from repro.core import ledger
        from repro.core.hw import CXL_POOL, INFINIBAND
        online = tuner.OnlineTuner(
            tuner.load_plan(args.plan, pool=CXL_POOL, ib=INFINIBAND),
            retune_interval=args.retune_interval)
        # the refreshed plan lives in a file so rebuilt engines load it
        live_path = args.plan_out or (args.plan + ".refined.json")
    rounds = args.rounds if args.rounds is not None else (
        2 * args.retune_interval if args.online_retune else 1)
    out = None
    if online is not None:
        ledger.reset()
    profile = None   # trace-time auto_choices of the compiled engine
    for r in range(rounds):
        t0 = time.time()
        out = eng.generate(batch, max_new_tokens=args.new_tokens)
        dt = time.time() - t0
        print(f"{cfg.name}: {out.shape} in {dt:.2f}s "
              f"({args.batch * args.new_tokens / dt:.1f} tok/s)")
        if obs_sess is not None:
            obs_sess.on_step(r, dt, extra={
                "tok_per_s": args.batch * args.new_tokens / dt})
        if online is None:
            continue
        if profile is None:
            # the engine traced during this round: its audit is the
            # per-round collective profile cached rounds rerun (the
            # round's wall time includes compilation, so skip it)
            profile = ledger.snapshot()["auto_choices"]
            if not profile:
                msg = ("--online-retune: the engine issued no auto "
                       "collectives (unsharded tp=1 engines have "
                       "nothing to measure) - rounds will run but "
                       "the plan cannot change")
                if obs_sess is not None:
                    obs_sess.diag("serve", msg)
                else:
                    print(f"[serve] {msg}")
        else:
            online.observe_step(dt, profile)
        prev = online.plan
        refreshed = online.maybe_retune(r)
        if refreshed is not None:
            tuner.save_plan(refreshed, live_path)
            if obs_sess is not None:
                obs_sess.on_retune(
                    epoch=tuner.plan_epoch(),
                    swapped=tuner.choices_changed(prev, refreshed),
                    regret_s=online.measured_regret())
            if tuner.choices_changed(prev, refreshed):
                # hot-swap between rounds: rebuild the engine against
                # the refreshed plan (its jitted prefill/decode must
                # re-trace to pick up the new resolution)
                eng = ServeEngine(cfg, params, _dc.replace(
                    scfg, plan_path=live_path), obs=obs_sess)
                ledger.reset()
                profile = None
                print(f"round {r}: plan hot-swap -> {live_path}")
    if online is not None and args.plan_out:
        refined = online.refresh()
        from repro.tuner import save_plan
        save_plan(refined, args.plan_out)
        print(f"saved refined plan (v4) -> {args.plan_out}")
    if obs_sess is not None:
        from repro.core import ledger as _ledger
        obs_sess.finalize(snapshot=_ledger.snapshot(),
                          extra={"rounds": rounds})
    print(out[: min(2, args.batch)].tolist())


if __name__ == "__main__":
    main()
