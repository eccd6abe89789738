"""Hierarchical-topology audit: level-decomposed collectives vs the flat
per-level recursion, on a 3-level (2 pods x 2 nodes x 2 gpus) cluster
with distinct per-level fabrics (pod: IB, node: CXL pool, gpu: ICI).

The whole tune -> plan -> auto path runs for real: a per-level plan is
generated against each level's own fabric config (and written to
``bench-topology-plan.json`` as a CI artifact), then AllReduce and
Broadcast are traced through ``Communicator(backend='auto')`` on an
abstract 2x2x2 mesh - no devices needed, the trace-time ledger records
the wire bytes each level's fabric actually carries.

The headline claim: under hierarchical decomposition each byte crosses
the slow pod-spanning fabric once (at 1/prod(inner) of the payload),
so cross-pool wire bytes drop by ~prod(inner sizes) = 4x vs recursing
the flat algorithm per level.  ``topology_*_crosspool_ratio`` must be
> 1 for AllReduce and Broadcast; the audit also sums the plan's
predicted per-level times for both schedules.
"""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro import tuner
from repro.core import ledger
from repro.core.api import Communicator
from repro.core.hw import (MiB, CXLPoolConfig, ICIConfig,
                           InfiniBandConfig)
from repro.core.topology import Level, Topology

AXES = ("pod", "node", "gpu")
PLAN_ARTIFACT = os.environ.get("BENCH_TOPO_PLAN",
                               "bench-topology-plan.json")

TOPOLOGY = Topology(levels=(
    Level("pod", "ib", ib=InfiniBandConfig(link_bw=12.5e9)),
    Level("node", "cxl", pool=CXLPoolConfig(device_bw=18e9)),
    Level("gpu", "ici", ici=ICIConfig(link_bw=45e9)),
))


def _trace(mesh, fn, nbytes: int) -> dict:
    """Trace one collective program and return the ledger snapshot."""
    ledger.reset()
    x = jax.ShapeDtypeStruct((nbytes // 4, 1), jnp.float32)
    jax.eval_shape(jax.shard_map(fn, mesh=mesh, in_specs=P(AXES),
                                 out_specs=P(AXES), check_vma=False), x)
    return ledger.snapshot()


def _crosspool(snap: dict) -> float:
    lvl = snap.get("level_wire_bytes") or {}
    return float(sum((lvl.get("pod/ib") or {}).values()))


def _predicted_s(snap: dict) -> float:
    return float(sum(c["predicted_time"]
                     for c in snap.get("auto_choices") or []))


def run(emit, smoke: bool = False) -> None:
    grid = tuner.TuneGrid(
        sizes=tuple(m * MiB for m in (1, 16, 64)),
        nranks=(2,), slicing_factors=(1, 4))
    plan = tuner.generate_plan(grid, topology=TOPOLOGY)
    tuner.save_plan(plan, PLAN_ARTIFACT)
    emit("topology_plan_cells", len(plan.entries),
         f"3-level plan -> {PLAN_ARTIFACT} (CI artifact)")
    for lv in TOPOLOGY.levels:
        lkey = TOPOLOGY.level_key(lv.axis)
        cells = [c for k, c in plan.entries.items() if k[3] == lkey]
        frac = sum(c.backend == "cxl" for c in cells) / len(cells)
        emit(f"topology_level_{lv.axis}_cxl_fraction", frac,
             f"{lv.fabric} fabric, fp {lv.fingerprint()}")

    mesh = AbstractMesh((2, 2, 2), AXES)   # no devices needed to trace
    comm = Communicator(backend="auto", plan=plan, topology=TOPOLOGY)
    size = (16 if smoke else 64) * MiB

    # hierarchical vs flat per-level recursion, real traces
    hier_ar = _trace(mesh, lambda a: comm.all_reduce(a, AXES), size)

    def flat_ar(a):
        for ax in AXES:      # the legacy schedule: full payload per level
            a = comm.all_reduce(a, ax)
        return a
    flat_ar_snap = _trace(mesh, flat_ar, size)

    hier_bc = _trace(mesh, lambda a: comm.broadcast(a, AXES, root=0),
                     size)

    def flat_bc(a):
        for ax in AXES:      # per-level root chain, full payload
            a = comm.broadcast(a, ax, root=0)
        return a
    flat_bc_snap = _trace(mesh, flat_bc, size)

    for prim, hier, flat in (("all_reduce", hier_ar, flat_ar_snap),
                             ("broadcast", hier_bc, flat_bc_snap)):
        xh, xf = _crosspool(hier), _crosspool(flat)
        ratio = xf / xh if xh else float("inf")
        emit(f"topology_{prim}_crosspool_bytes_hier", xh,
             "pod/ib wire bytes per rank, hierarchical")
        emit(f"topology_{prim}_crosspool_bytes_flat", xf,
             "pod/ib wire bytes per rank, flat per-level recursion")
        emit(f"topology_{prim}_crosspool_ratio", ratio,
             "flat/hier; each byte crosses the pool fabric once")
        assert ratio > 1.0 + 1e-9, (
            f"hierarchical {prim} does not reduce cross-pool bytes: "
            f"{xh} vs {xf}")
        th, tf = _predicted_s(hier), _predicted_s(flat)
        if th > 0:
            emit(f"topology_{prim}_predicted_speedup", tf / th,
                 "sum of per-level plan-predicted times, flat/hier")

    # every traced byte is attributed to a level/fabric
    tagged = sum(sum(v.values())
                 for v in hier_ar["level_wire_bytes"].values())
    emit("topology_ledger_level_coverage",
         tagged / hier_ar["total_wire_bytes"],
         "fraction of hierarchical-AR bytes attributed per level")

    if os.path.exists(PLAN_ARTIFACT):
        with open(PLAN_ARTIFACT) as f:
            doc = json.load(f)
        assert doc["version"] == 6 and doc["meta"].get("topology")
