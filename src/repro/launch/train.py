"""Production training launcher.

On a real TPU cluster this process runs per host (jax.distributed); on
this CPU container it drives the same code over forced host devices.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
      --steps 100 --backend cxl [--multi-pod] [--smoke]
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m repro.launch.train --arch yi-6b --smoke \
      --mesh 2x4 --steps 20
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m repro.launch.train --arch yi-6b --smoke \
      --pp-stages 2 --microbatches 4 --pp-schedule 1f1b --batch 16
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
      --smoke --backend auto --plan plan.json --online-retune \
      --retune-interval 10 --plan-out refined.json
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
      --smoke --backend auto --online-retune --timing-source emulator \
      --metrics-out run.jsonl --trace-out run.trace.json

Observability (repro.obs): ``--metrics-out`` streams step/retune/health
events as JSON-lines and dumps the final metric registry (+ a
Prometheus rendering next to it); ``--trace-out`` keeps a flight
recorder of the last ``--trace-steps`` steps and writes a Chrome trace
openable in Perfetto.  ``--timing-source`` picks where measured
per-collective times come from: ``step`` (apportion the step wall time
over the trace-time profile - the pre-obs behavior), ``emulator`` (the
device-free oracle-driven ``obs.StepEmulator``; ``--emu-degrade``
injects link slowdowns), or ``profiler`` (parse ``jax.profiler``
traces; falls back to ``step`` if the build emits none).  With
``--online-retune``, emulator/profiler sources feed the tuner
*candidate-level* measurements instead of step-time apportioning.

Resilience (repro.resilience): ``--fault-plan`` injects a seeded fault
schedule (rank deaths / link degrades / pool-error windows) through
the emulator degrade hooks and the pool fault shim; ``--resilience``
runs the closed detect -> re-plan -> resume loop around it —
heartbeat/health monitoring each step, an automatic survivor or
failover re-plan hot-swapped on confirmation, and a warm rollback to
the newest pool-resident snapshot (``--pool-ckpt-interval``).
``--ewma-decay``/``--explore-eps`` let the online tuner walk back to
calibrated oracle predictions after a fault heals (see
docs/RESILIENCE.md).
"""
from __future__ import annotations

import argparse
import contextlib
import time

from repro.launch import xla
xla.apply_overlap_preset()   # --xla-overlap: must precede the jax import

import jax

from repro.configs import ARCH_IDS, get_config
from repro.data.pipeline import SyntheticTokens
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import dp_axes, make_production_mesh
from repro.training import checkpoint
from repro.training.train_loop import (TrainConfig, init_sharded_state,
                                       make_sharded_train_step,
                                       named_shardings)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--backend", choices=["ring", "cxl", "auto"],
                    default="ring")
    ap.add_argument("--plan", default=None,
                    help="autotuning plan JSON (see repro.launch.tune); "
                         "used by --backend auto; a topology plan also "
                         "activates hierarchical decomposition")
    ap.add_argument("--topology", default=None,
                    help="'axis:fabric,...' spec or topology JSON file: "
                         "tuple-axis collectives decompose per level "
                         "(default: the plan's embedded topology, if "
                         "any)")
    ap.add_argument("--online-retune", action="store_true",
                    help="feed measured step times back into the plan "
                         "(per-cell EWMA, tuner.online) and hot-swap "
                         "the refreshed plan between steps; requires "
                         "--backend auto")
    ap.add_argument("--retune-interval", type=int, default=10,
                    help="steps between plan refresh + hot-swap "
                         "under --online-retune")
    ap.add_argument("--plan-out", default=None,
                    help="persist the measurement-refined plan "
                         "(format v4) here at the end of the run")
    ap.add_argument("--slicing-factor", type=int, default=4)
    ap.add_argument("--allreduce-mode", default="two_phase",
                    choices=["two_phase", "faithful"])
    ap.add_argument("--microbatches", type=int, default=1,
                    help="gradient-accumulation splits; with "
                         "--pp-stages > 1 this is the pipeline "
                         "microbatch count M (bubble fraction "
                         "(S-1)/(M+S-1) under 1F1B)")
    ap.add_argument("--pp-stages", type=int, default=1,
                    help="pipeline stages: > 1 trains on a "
                         "(stage, data) mesh with the microbatch "
                         "pipeline (training.pipeline); activation/"
                         "grad handoffs ride the tuned p2p plan cells "
                         "(cxl pool write + doorbell commit vs direct "
                         "IB hop)")
    ap.add_argument("--pp-schedule", default="1f1b",
                    choices=["1f1b", "interleaved"],
                    help="pipeline schedule driving bubble accounting "
                         "and realizability validation (interleaved "
                         "needs microbatches %% stages == 0)")
    ap.add_argument("--pp-chunks", type=int, default=2,
                    help="model chunks per stage under --pp-schedule "
                         "interleaved")
    ap.add_argument("--bucket-mb", type=float, default=25.0,
                    help="grad-sync AllReduce bucket cap in MiB; any "
                         "value > 0 also row-fuses the FSDP gathers "
                         "(0 = per-leaf collectives)")
    ap.add_argument("--prefetch", type=int, default=1, choices=[0, 1],
                    help="FSDP AllGather prefetch depth "
                         "(0 = serialized gather-then-compute)")
    ap.add_argument("--fuse-kernels", action="store_true",
                    help="fuse the FSDP AllGather into the consuming "
                         "matmuls (kernels.fused_collectives); needs "
                         "the bucketed gather path (--bucket-mb > 0)")
    xla.add_argument(ap)
    ap.add_argument("--mesh", default=None,
                    help="DPxTP, e.g. 2x4; default: production mesh")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--placement", default=None,
                    help="'auto' (plan the mesh-axis -> fabric-level "
                         "assignment from the model's analytic "
                         "collective mix, tuner.placement) or a saved "
                         "placement JSON; needs an active topology "
                         "(--topology or a topology plan) and --mesh "
                         "for the DP/TP degrees.  Applies the best "
                         "assignment that keeps the TP axis unsplit")
    ap.add_argument("--placement-from-dryrun", default=None,
                    help="dry-run JSON record (launch.dryrun --backend "
                         "auto): build the placement CollectiveMix "
                         "from its recorded auto_choices audit "
                         "(CollectiveMix.from_dryrun) instead of the "
                         "analytic per-model mix; needs --placement")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--metrics-out", default=None,
                    help="write step/retune/health events + final "
                         "metric registry as JSON-lines here (and a "
                         "Prometheus text rendering to <base>.prom)")
    ap.add_argument("--trace-out", default=None,
                    help="flight-recorder Chrome trace JSON (last "
                         "--trace-steps steps; open in Perfetto)")
    ap.add_argument("--trace-steps", type=int, default=32,
                    help="flight-recorder ring capacity in steps")
    ap.add_argument("--timing-source", default="step",
                    choices=["step", "emulator", "profiler"],
                    help="measured-time source: 'step' apportions step "
                         "wall time over the profile; 'emulator' / "
                         "'profiler' produce per-collective samples "
                         "(requires --backend auto)")
    ap.add_argument("--emu-degrade", default=None,
                    help="'key=factor,...' slowdowns for the emulator "
                         "timing source; keys are level axes ('node'), "
                         "fabric kinds ('cxl'), backend-qualified "
                         "'node@cxl', or '*'")
    ap.add_argument("--resilience", action="store_true",
                    help="run the detect -> re-plan -> resume loop "
                         "(repro.resilience): heartbeat + link-health "
                         "monitoring each step; on a confirmed rank "
                         "death or persistent cxl degrade, hot-swap a "
                         "survivor/failover re-plan and roll back to "
                         "the newest pool snapshot")
    ap.add_argument("--fault-plan", default=None,
                    help="seeded fault schedule, e.g. "
                         "'rank_death@12:rank=5;link_degrade@10-18:"
                         "link=node@cxl,factor=4;pool_error@5-7:"
                         "rate=0.5' (repro.resilience.FaultPlan)")
    ap.add_argument("--pool-ckpt-interval", type=int, default=0,
                    help="steps between pool-resident snapshots "
                         "(training.checkpoint.PoolCheckpointStore); "
                         "0 disables; the resume half of --resilience "
                         "rolls back to the newest committed snapshot")
    ap.add_argument("--ewma-decay", type=float, default=0.0,
                    help="per-refresh decay of the online tuner's "
                         "measured EWMAs (and calibration) toward the "
                         "oracle, so post-fault costs un-learn "
                         "(requires --online-retune)")
    ap.add_argument("--explore-eps", type=float, default=0.0,
                    help="epsilon-greedy re-exploration of measured "
                         "plan cells at refresh (requires "
                         "--online-retune)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.online_retune and args.backend != "auto":
        ap.error("--online-retune requires --backend auto")
    if (args.ewma_decay or args.explore_eps) and not args.online_retune:
        ap.error("--ewma-decay/--explore-eps tune the online tuner; "
                 "add --online-retune")
    if args.timing_source != "step" and args.backend != "auto":
        ap.error("--timing-source emulator/profiler needs the "
                 "--backend auto audit to key samples to plan cells")
    if args.placement_from_dryrun and not args.placement:
        ap.error("--placement-from-dryrun feeds the placement "
                 "planner; add --placement auto")
    if args.pp_stages > 1:
        for on, flag in ((args.online_retune, "--online-retune"),
                         (args.resilience, "--resilience"),
                         (args.fault_plan, "--fault-plan"),
                         (args.placement, "--placement"),
                         (args.timing_source != "step",
                          "--timing-source emulator/profiler")):
            if on:
                ap.error(f"{flag} is not supported with "
                         f"--pp-stages > 1 (plain pipeline training "
                         f"path only)")

    from repro.core.topology import (get_active_topology, parse_topology,
                                     set_active_topology, warn_uncovered)
    if args.topology:
        set_active_topology(parse_topology(args.topology))
    if args.plan:
        # one shared activation path with dryrun: fingerprint-checks the
        # plan, activates it process-wide, and activates (or warns about
        # a mismatch with) its embedded topology
        from repro.core.hw import CXL_POOL, INFINIBAND
        from repro.tuner import activate_plan_file
        activate_plan_file(args.plan, pool=CXL_POOL, ib=INFINIBAND)
    cfg = get_config(args.arch, smoke=args.smoke)
    if args.pp_stages > 1:
        ndev = jax.device_count()
        pp = args.pp_stages
        if ndev % pp:
            ap.error(f"--pp-stages {pp} does not divide "
                     f"{ndev} devices")
        dpsz = ndev // pp
        if args.batch % (dpsz * args.microbatches):
            ap.error(f"--batch {args.batch} must split over "
                     f"{dpsz} data ranks x {args.microbatches} "
                     f"microbatches")
        mesh = jax.make_mesh((pp, dpsz), ("stage", "data"))
    elif args.placement:
        from repro import tuner
        from repro.launch.mesh import make_placed_mesh
        topo = get_active_topology()
        if topo is None:
            ap.error("--placement requires an active topology "
                     "(--topology or a topology plan)")
        if not args.mesh:
            ap.error("--placement requires --mesh DPxTP for the "
                     "logical axis degrees")
        dp, tp = (int(x) for x in args.mesh.split("x"))
        if args.placement_from_dryrun:
            import json
            with open(args.placement_from_dryrun) as f:
                record = json.load(f)
            mix = tuner.CollectiveMix.from_dryrun(
                record, {"data": dp, "model": tp})
        else:
            mix = tuner.CollectiveMix.for_model(
                cfg, {"data": dp, "model": tp}, seq=args.seq,
                batch_per_rank=max(1, args.batch // max(1, dp)))
        pplan = tuner.plan_placement(mix, topo) \
            if args.placement == "auto" \
            else tuner.load_placement(args.placement)
        chosen = pplan.best_with_unsplit(("model",))
        print(tuner.format_report(pplan, chosen=chosen))
        mesh = make_placed_mesh(chosen, mix, topo)
    elif args.mesh:
        dp, tp = (int(x) for x in args.mesh.split("x"))
        mesh = jax.make_mesh((dp, tp), ("data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
    if get_active_topology() is not None:
        warn_uncovered(get_active_topology(), mesh)
    tcfg = TrainConfig(lr=args.lr, warmup=min(20, args.steps // 5),
                       total_steps=args.steps, backend=args.backend,
                       slicing_factor=args.slicing_factor,
                       allreduce_mode=args.allreduce_mode,
                       microbatches=args.microbatches, clip_norm=None,
                       # plan already activated process-wide above;
                       # backend='auto' resolves it via the registry
                       plan_path=None, bucket_mb=args.bucket_mb,
                       prefetch=args.prefetch,
                       fuse_kernels=args.fuse_kernels)
    from repro.core import ledger
    ledger.reset()
    if args.pp_stages > 1:
        from repro.training.pipeline import (bubble_fraction,
                                             make_sharded_pipeline_step)
        step, pspecs, bspecs, pc = make_sharded_pipeline_step(
            cfg, tcfg, mesh, n_microbatches=args.microbatches,
            schedule=args.pp_schedule, n_chunks=args.pp_chunks)
        tp = 1
        bub = bubble_fraction(args.pp_stages, args.microbatches,
                              args.pp_schedule, args.pp_chunks)
        print(f"pipeline: {args.pp_stages} stages x "
              f"{dict(mesh.shape)['data']} dp, "
              f"{args.microbatches} microbatches, "
              f"schedule {args.pp_schedule}, "
              f"bubble fraction {bub:.3f}")
    else:
        step, pspecs, bspecs, pc = make_sharded_train_step(
            cfg, tcfg, mesh, dp_axis=dp_axes(mesh))
        tp = mesh.shape["model"]
    params, opt = init_sharded_state(cfg, mesh, pspecs,
                                     jax.random.key(0), tp=tp)
    batch_sh = named_shardings(mesh, bspecs)
    data = iter(SyntheticTokens(cfg, batch=args.batch, seq=args.seq))

    online = None
    if args.online_retune:
        from repro import tuner
        base = tuner.ensure_default_plan(
            topology=get_active_topology())
        online = tuner.OnlineTuner(
            base, retune_interval=args.retune_interval,
            decay=args.ewma_decay, explore_eps=args.explore_eps)
        print(f"online re-tuning: interval {args.retune_interval} "
              f"steps, plan epoch {tuner.plan_epoch()}")

    obs_sess = None
    if args.metrics_out or args.trace_out:
        from repro.obs import ObsSession
        obs_sess = ObsSession(metrics_out=args.metrics_out,
                              trace_out=args.trace_out,
                              trace_steps=args.trace_steps)
    emu = None
    if args.timing_source == "emulator":
        from repro.obs import StepEmulator
        degrade = {}
        for part in (args.emu_degrade or "").split(","):
            if part.strip():
                k, _, v = part.partition("=")
                degrade[k.strip()] = float(v)
        emu = StepEmulator(topology=get_active_topology(),
                           noise_std=0.02, seed=0, degrade=degrade)
    prof_dir, prof_failures = None, 0
    if args.timing_source == "profiler":
        import tempfile
        prof_dir = tempfile.mkdtemp(prefix="repro-prof-")
    # profile/emulator/profiler sources all need the trace-time audit
    want_profile = (online is not None
                    or args.timing_source != "step"
                    or (obs_sess is not None
                        and args.backend == "auto"))

    fault_plan = None
    if args.fault_plan:
        from repro.resilience import FaultPlan
        fault_plan = FaultPlan.parse(args.fault_plan)
        fault_plan.install()        # pool fault hook: deaths + errors
        print(f"fault plan: {fault_plan.describe()}")
    resil = None
    if args.resilience:
        from repro.resilience import (FailureMonitor,
                                      ResilienceController)
        monitor = FailureMonitor(int(mesh.devices.size))
        resil = ResilienceController(monitor)
        print(f"resilience: monitoring {monitor.nranks} ranks "
              f"(heartbeat timeout {monitor.heartbeat_timeout}, "
              f"patience {monitor.patience})")
    pool_store = None
    if args.pool_ckpt_interval > 0:
        import numpy as np
        from repro.training.checkpoint import PoolCheckpointStore
        state_bytes = sum(
            np.asarray(l).nbytes
            for l in jax.tree.leaves({"params": params, "opt": opt}))
        # two slots, each big enough for image + header slack
        pool_store = PoolCheckpointStore(
            capacity_bytes=2 * (state_bytes + (1 << 20)) + 4096)
        print(f"pool checkpoints: every {args.pool_ckpt_interval} "
              f"steps, {pool_store.slot_bytes} B/slot")

    print(f"training {cfg.name} on mesh {dict(mesh.shape)} "
          f"backend={args.backend}")
    t0 = time.time()
    profile = None       # trace-time auto_choices of the compiled step
    for i, batch in zip(range(args.steps), data):
        if fault_plan is not None:
            for ev in fault_plan.begin_step(i, emulator=emu):
                print(f"step {i:5d} fault injected: {ev.describe()}")
        batch = jax.device_put(batch, {k: batch_sh[k] for k in batch})
        ts = time.perf_counter()
        step_timings = None
        with (obs_sess.step_span(i) if obs_sess is not None
              else contextlib.nullcontext()):
            prof_cm = contextlib.nullcontext()
            if prof_dir is not None and profile is not None \
                    and prof_failures < 2:
                prof_cm = jax.profiler.trace(prof_dir)
            with prof_cm:
                params, opt, metrics = step(params, opt, batch)
                if want_profile or obs_sess is not None:
                    jax.block_until_ready(metrics["loss"])
            dt = time.perf_counter() - ts
            compiled_this_step = False
            if want_profile and profile is None:
                # the step traced during this call: its audit is the
                # per-step collective profile every later step reruns
                profile = ledger.snapshot()["auto_choices"]
                compiled_this_step = True
            if profile is not None and not compiled_this_step:
                if emu is not None:
                    # books each sample into the ledger, which feeds
                    # the flight recorder via the timing hook
                    step_timings = emu.step_timings(profile)
                elif prof_dir is not None:
                    from repro.obs import profiled_timings
                    step_timings = profiled_timings(prof_dir, profile,
                                                    book=True)
                    if not step_timings:
                        prof_failures += 1
                        if prof_failures == 2:
                            print("warning: no parseable profiler "
                                  "traces; falling back to step-time "
                                  "apportioning")
            if online is not None and profile is not None \
                    and not compiled_this_step:
                if step_timings:
                    # candidate-level feedback: every sample carries
                    # its own plan-cell identity + executed knobs
                    online.observe_timings(step_timings)
                else:
                    # skip the compile step's wall time; every cached
                    # step apportions its measured time over the
                    # profile
                    online.observe_step(dt, profile)
            if online is not None:
                prev = online.plan
                refreshed = online.maybe_retune(i)
                if refreshed is not None:
                    swapped = tuner.choices_changed(prev, refreshed)
                    if obs_sess is not None:
                        obs_sess.on_retune(
                            epoch=tuner.plan_epoch(), swapped=swapped,
                            regret_s=online.measured_regret(),
                            measured_cells=sum(
                                st.samples > 0
                                for st in online.stats.values()))
                    if online.calibration:
                        from repro.obs import calibration_drift
                        for d in calibration_drift(
                                online.calibration_export()):
                            print(f"step {i:5d} calibration drift: "
                                  f"{d['backend']}@{d['level']} "
                                  f"measures {d['scale']}x the oracle "
                                  f"- {d['recommendation']}")
                    if swapped:
                        # hot-swap: the registry already serves the
                        # refreshed plan (epoch bumped); re-trace the
                        # step so auto resolution picks it up at the
                        # next step boundary
                        ledger.reset()
                        profile = None
                        step, pspecs, bspecs, pc = \
                            make_sharded_train_step(
                                cfg, tcfg, mesh, dp_axis=dp_axes(mesh))
                        print(f"step {i:5d} plan hot-swap -> epoch "
                              f"{tuner.plan_epoch()} (choices changed)")
        if obs_sess is not None:
            obs_sess.on_step(i, time.perf_counter() - ts,
                             timings=step_timings)
        if pool_store is not None \
                and i % args.pool_ckpt_interval == 0:
            from repro.core.pool import PoolAccessError
            try:
                rep = pool_store.snapshot(
                    i, {"params": params, "opt": opt})
                if rep["retries"]:
                    print(f"step {i:5d} pool snapshot committed "
                          f"after {rep['retries']} retried faults")
            except PoolAccessError as e:
                # persists past the retry budget: the previous
                # committed snapshot stays restorable
                if resil is not None:
                    resil.monitor.record_pool_error(i)
                print(f"step {i:5d} pool snapshot failed: {e}")
        if resil is not None:
            rp = resil.step(i, timings=step_timings)
            if rp is not None:
                # resume: roll the survivors back to the newest
                # committed pool snapshot (warm rejoin) and re-trace
                # the step so auto resolution sees the new plan and
                # topology.  The forced-host mesh keeps its devices;
                # a true mesh shrink is exercised in
                # tests/_mesh_runner.py.
                snap = pool_store.latest() \
                    if pool_store is not None else None
                if snap is not None:
                    like = {"params": params, "opt": opt}
                    restored, _ = pool_store.restore(like)
                    params, opt = restored["params"], restored["opt"]
                    print(f"step {i:5d} resume: rolled back to pool "
                          f"snapshot step {snap} "
                          f"({i - snap} steps of rollback)")
                ledger.reset()
                profile = None
                step, pspecs, bspecs, pc = make_sharded_train_step(
                    cfg, tcfg, mesh, dp_axis=dp_axes(mesh))
                if online is not None:
                    # restart measured feedback from the recovery plan
                    from repro import tuner
                    online = tuner.OnlineTuner(
                        rp.plan, retune_interval=args.retune_interval,
                        decay=args.ewma_decay,
                        explore_eps=args.explore_eps)
        ledger.clear_timings()    # folded; keep the list O(one step)
        if i % 10 == 0 or i == args.steps - 1:
            print(f"step {i:5d} loss {float(metrics['loss']):.4f} "
                  f"({time.time() - t0:.1f}s)")
    if online is not None and args.plan_out:
        from repro.tuner import save_plan
        refined = online.refresh()
        save_plan(refined, args.plan_out)
        measured = sum(st.samples > 0 for st in online.stats.values())
        print(f"saved refined plan (v4, {len(refined.entries)} cells, "
              f"{measured} measured candidates) -> {args.plan_out}")
    if obs_sess is not None:
        obs_sess.finalize(snapshot=ledger.snapshot(),
                          extra={"steps": int(args.steps),
                                 "wall_s": time.time() - t0,
                                 "timing_source": args.timing_source})
    if prof_dir is not None:
        import shutil
        shutil.rmtree(prof_dir, ignore_errors=True)
    if fault_plan is not None:
        fault_plan.uninstall()
        print(f"faults injected: {len(fault_plan.injected)}")
    if resil is not None:
        rep = resil.report()
        print(f"resilience: {rep['replans']} re-plan(s), "
              f"dead ranks {rep['monitor']['dead_ranks']}, "
              f"degraded links {rep['monitor']['degraded_links']}")
    if args.ckpt:
        checkpoint.save(args.ckpt, args.steps, {"params": params})
        print(f"saved {args.ckpt}/step_{args.steps:08d}")


if __name__ == "__main__":
    main()
