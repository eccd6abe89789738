"""Hierarchical-topology workflow: define a multi-fabric cluster, tune
a per-level plan, and watch the Communicator decompose collectives
against it - all offline (abstract mesh, no devices).

Run:
  PYTHONPATH=src python examples/topology_workflow.py
"""
import json
import tempfile

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import tuner
from repro.core import ledger
from repro.core.api import Communicator
from repro.core.hw import MiB, CXLPoolConfig, InfiniBandConfig
from repro.core.topology import Level, Topology


def main() -> None:
    # 2 pods x 2 nodes x 2 gpus: IB across pods, a rack-scale CXL pool
    # within a pod, the chip ring within a node.
    topo = Topology(levels=(
        Level("pod", "ib", ib=InfiniBandConfig(link_bw=12.5e9)),
        Level("node", "cxl", pool=CXLPoolConfig(device_bw=18e9)),
        Level("gpu", "ici"),
    ))
    print("topology fingerprint:", topo.fingerprint())

    # offline: tune every level against its own fabric oracle
    grid = tuner.TuneGrid(sizes=tuple(m * MiB for m in (1, 16, 64)),
                          nranks=(2,), slicing_factors=(1, 4))
    plan = tuner.generate_plan(grid, topology=topo)
    path = tempfile.mktemp(suffix=".json")
    tuner.save_plan(plan, path)
    print(f"tuned {len(plan.entries)} level-keyed cells -> {path}")

    # online: one flag's worth of setup - the plan carries the topology
    plan = tuner.load_plan(path, topology=topo)
    comm = Communicator(backend="auto", plan=plan)
    axes = ("pod", "node", "gpu")
    mesh = jax.sharding.AbstractMesh((2, 2, 2), axes)

    ledger.reset()
    jax.eval_shape(jax.shard_map(
        lambda g: comm.all_reduce(g, axes), mesh=mesh,
        in_specs=P(axes), out_specs=P(axes), check_vma=False),
        jax.ShapeDtypeStruct((16 * MiB // 4, 1), jnp.float32))
    snap = ledger.snapshot()
    print("per-level wire bytes (hierarchical AllReduce, 16 MiB):")
    print(json.dumps({k: sum(v.values())
                      for k, v in snap["level_wire_bytes"].items()},
                     indent=1))
    print("per-level choices:")
    for ch in snap["auto_choices"]:
        print(f"  {ch['primitive']:<15} level={ch['level']:<5} "
              f"fabric={ch['fabric']:<4} -> {ch['backend']}")


if __name__ == "__main__":
    main()
