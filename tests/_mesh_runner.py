"""Standalone multi-device validation, run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (tests must not leak
the forced device count into other test processes).

Validates, for ring and cxl backends:
  1. every Communicator collective vs its jax.lax oracle (single axis);
  2. hierarchical (pod, data)-style axes;
  3. TP+FSDP sharded loss == unsharded loss;
  4. one sharded AdamW train step produces the SAME updated params as
     the unsharded step (grads + replicated-grad sync + optimizer) -
     through the bucketed gather + prefetch production path;
  5. bucketed sync_grads / fused FSDP gather numerics vs the per-leaf
     reference across TP x FSDP mesh shapes (bitwise for fp32 ring,
     allclose for cxl and bf16), including sub-FSDP_MIN_SIZE leaves;
  6. obs metrics export reconciles exactly with ledger.snapshot();
  7. elastic reconfiguration: a rank death mid-run -> confirmed by the
     heartbeat monitor -> ragged survivor re-plan + mesh rebuild +
     pool-snapshot rollback, allclose vs a flat 7-rank reference;
  8. fused collective+compute kernels: the padding-free ragged
     reduce_scatter vs the flat reference (no fallback events), and
     ``fuse_kernels`` train steps vs the unfused bucketed path on
     regular and ragged (4+2) dp meshes, with the ledger's fused-byte
     split flipping on and off with the flag;
  9. flat-fallback audit: all_to_all / scatter on a grouped (4+2)
     level book one explicit flat-on-ragged event per call while
     still matching the flat-schedule numerics;
 10. pipeline parallelism: a 2-stage x 4-dp pipelined train step
     (1F1B microbatch loop, Communicator.send stage handoff over the
     tuned p2p plan cells) matches the FSDP-only 8-rank step, with
     the p2p wire bytes attributed to the stage level;
 11. ``chip_smoke.py --chips 4`` at a tiny size: cxl and ring train
     steps agree on a 2x2 mesh of 4 devices, params sharded over all.
"""
import os

assert os.environ.get("XLA_FLAGS", "").endswith("device_count=8"), \
    "run with XLA_FLAGS=--xla_force_host_platform_device_count=8"

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.core import overlap
from repro.core.api import Communicator
from repro.models import model, sharding
from repro.models.pcontext import ParallelContext, UNSHARDED
from repro.optim import adamw_init
from repro.training.train_loop import TrainConfig, make_train_step

RNG = np.random.default_rng(0)
KEY = jax.random.key(0)


def check_collectives(backend: str, rng=None) -> None:
    rng = RNG if rng is None else rng
    mesh = jax.make_mesh((8,), ("x",))
    comm = Communicator(backend=backend, slicing_factor=4)
    x = rng.standard_normal((8 * 16, 4)).astype(np.float32)
    y = rng.standard_normal((8, 32, 4)).astype(np.float32)

    def smap(f, ins, outs):
        return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=ins,
                                     out_specs=outs, check_vma=False))

    out = smap(lambda a: comm.all_gather(a, "x"), P("x"), P())(x)
    np.testing.assert_allclose(out, x, rtol=1e-6)
    out = smap(lambda a: comm.reduce_scatter(a, "x"), P("x"),
               P("x"))(y.reshape(256, 4))
    np.testing.assert_allclose(np.asarray(out), y.sum(0), rtol=1e-4,
                               atol=1e-5)
    for mode in ("faithful", "two_phase"):
        c = Communicator(backend=backend, allreduce_mode=mode)
        out = smap(lambda a: c.all_reduce(a, "x"), P("x"),
                   P("x"))(y.reshape(256, 4))
        np.testing.assert_allclose(np.asarray(out).reshape(8, 32, 4),
                                   np.tile(y.sum(0), (8, 1, 1)),
                                   rtol=1e-4, atol=1e-5)
    z = rng.standard_normal((8, 16, 3)).astype(np.float32)
    out = smap(lambda a: comm.all_to_all(a, "x"), P("x"),
               P("x"))(z.reshape(128, 3))
    np.testing.assert_allclose(
        np.asarray(out).reshape(8, 8, 2, 3),
        z.reshape(8, 8, 2, 3).transpose(1, 0, 2, 3), rtol=1e-6)
    out = smap(lambda a: comm.broadcast(a, "x", root=3), P("x"),
               P("x"))(x)
    np.testing.assert_allclose(
        np.asarray(out).reshape(8, 16, 4),
        np.tile(x.reshape(8, 16, 4)[3], (8, 1, 1)), rtol=1e-6)
    out = smap(lambda a: comm.reduce(a, "x", root=2), P("x"),
               P("x"))(y.reshape(256, 4))
    o = np.asarray(out).reshape(8, 32, 4)
    np.testing.assert_allclose(o[2], y.sum(0), rtol=1e-4, atol=1e-5)
    assert np.allclose(o[3], 0)
    out = smap(lambda a: comm.gather(a, "x", root=1), P("x"),
               P("x"))(x)
    np.testing.assert_allclose(np.asarray(out).reshape(8, 128, 4)[1], x,
                               rtol=1e-6)
    out = smap(lambda a: comm.scatter(a, "x", root=0), P("x"),
               P("x"))(x)
    np.testing.assert_allclose(np.asarray(out).reshape(8, 2, 4),
                               x.reshape(8, 16, 4)[0].reshape(8, 2, 4),
                               rtol=1e-6)
    print(f"  collectives[{backend}] ok")


def check_flat_lane_rows() -> None:
    """Flat buffers of whole (8, 128) tiles per rank (the FSDP buckets)
    run the cxl schedules as lane rows: all_gather, reduce_scatter,
    two-phase all_reduce and the all_gather's AD transpose must still
    match the ``jax.lax`` collectives."""
    mesh = jax.make_mesh((8,), ("x",))
    comm = Communicator(backend="cxl", slicing_factor=4)
    x = np.random.default_rng(5).standard_normal(
        8 * 8 * 1024).astype(np.float32)

    def smap(f, outs=P("x")):
        return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("x"),
                                     out_specs=outs, check_vma=False))

    for mine, ref in (
            (lambda a: comm.all_gather(a, "x"),
             lambda a: jax.lax.all_gather(a, "x", tiled=True)),
            (lambda a: comm.reduce_scatter(
                jnp.concatenate([a] * 8), "x"),
             lambda a: jax.lax.psum_scatter(
                 jnp.concatenate([a] * 8), "x", tiled=True)),
            (lambda a: comm.all_reduce(a, "x"),
             lambda a: jax.lax.psum(a, "x")),
            (jax.grad(lambda a: jnp.sum(
                jnp.sin(comm.all_gather(a, "x")))),
             jax.grad(lambda a: jnp.sum(jnp.sin(
                 jax.lax.all_gather(a, "x", tiled=True)))))):
        np.testing.assert_allclose(np.asarray(smap(mine)(x)),
                                   np.asarray(smap(ref)(x)),
                                   rtol=1e-5, atol=1e-5)
    print("  flat-lane-rows[cxl] ok")


def check_hierarchical(backend: str, rng=None) -> None:
    rng = RNG if rng is None else rng
    mesh = jax.make_mesh((2, 4), ("p", "d"))
    comm = Communicator(backend=backend)
    w = rng.standard_normal((48, 5)).astype(np.float32)
    f = jax.jit(jax.shard_map(
        lambda a: comm.all_gather(a, ("p", "d")), mesh=mesh,
        in_specs=P(("p", "d")), out_specs=P(), check_vma=False))
    np.testing.assert_allclose(f(w), w, rtol=1e-6)
    v = rng.standard_normal((8, 16, 5)).astype(np.float32)
    g = jax.jit(jax.shard_map(
        lambda a: comm.all_gather(comm.reduce_scatter(a, ("p", "d")),
                                  ("p", "d")), mesh=mesh,
        in_specs=P(("p", "d")), out_specs=P(("p", "d")),
        check_vma=False))
    np.testing.assert_allclose(
        np.asarray(g(v.reshape(128, 5))).reshape(8, 16, 5),
        np.tile(v.sum(0), (8, 1, 1)), rtol=1e-4, atol=1e-5)
    print(f"  hierarchical[{backend}] ok")


def check_rank_major_layout(backend: str, rng=None) -> None:
    """Tuple-axis (outer, inner) all_gather / reduce_scatter must produce
    exactly the layout of the same collective over one flat axis whose
    rank order is outer-major (rank = p * |d| + d)."""
    rng = RNG if rng is None else rng
    mesh2 = jax.make_mesh((2, 4), ("p", "d"))
    mesh1 = jax.make_mesh((8,), ("x",))
    comm = Communicator(backend=backend)
    x = rng.standard_normal((8 * 8, 5)).astype(np.float32)

    def run(mesh, spec, f):
        return np.asarray(jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=P(spec), out_specs=P(spec),
            check_vma=False))(x))

    ag2 = run(mesh2, ("p", "d"), lambda a: comm.all_gather(a, ("p", "d")))
    ag1 = run(mesh1, "x", lambda a: comm.all_gather(a, "x"))
    np.testing.assert_allclose(ag2, ag1, rtol=1e-6)
    # oracle: every rank holds the full rank-major array
    np.testing.assert_allclose(ag2.reshape(8, 64, 5),
                               np.tile(x, (8, 1, 1)), rtol=1e-6)

    rs2 = run(mesh2, ("p", "d"),
              lambda a: comm.reduce_scatter(a, ("p", "d")))
    rs1 = run(mesh1, "x", lambda a: comm.reduce_scatter(a, "x"))
    np.testing.assert_allclose(rs2, rs1, rtol=1e-4, atol=1e-5)
    # oracle: assembled output is the cross-rank sum of the shards
    np.testing.assert_allclose(rs2, x.reshape(8, 8, 5).sum(0),
                               rtol=1e-4, atol=1e-5)
    print(f"  rank-major-layout[{backend}] ok")


def check_bucketed_sync_grads(backend: str) -> None:
    """Bucketed sync_grads vs the per-leaf reference across TP x FSDP
    mesh shapes: bitwise-equal for fp32 under ring (same per-element
    rank-summation order), allclose for cxl and for bf16.  The tree
    mixes a big FSDP leaf, a sub-FSDP_MIN_SIZE replicated leaf, a
    TP-sharded leaf and a norm vector, so every sync group (missing tp,
    missing dp, missing both) is exercised."""
    rng = np.random.default_rng(99)
    for dp, tp in ((2, 4), (4, 2)):
        mesh = jax.make_mesh((dp, tp), ("data", "model"))
        sharding.set_mesh_sizes({"data": dp, "model": tp})
        comm = Communicator(backend=backend)
        pc = ParallelContext(tp_axis="model", dp_axis="data", tp=tp,
                             comm=comm)
        params = {
            "big": jnp.zeros((256, 512), jnp.float32),   # FSDP-sharded
            "small": jnp.zeros((64, 32), jnp.float32),   # < FSDP_MIN_SIZE
            "wq": jnp.zeros((128, 8 * 16), jnp.float32),  # TP-sharded
            "norm1": jnp.zeros((128,), jnp.float32),
        }

        class _Cfg:  # minimal stand-in for spec construction
            @staticmethod
            def kv_sharded(tp):
                return True
        pspecs = sharding.param_specs(params, _Cfg, dp_axis="data",
                                      fsdp=True)
        assert sharding._has_axis(pspecs["big"], "data") is not None
        assert sharding._has_axis(pspecs["small"], "data") is None

        for dtype, tol in ((jnp.float32, 0.0), (jnp.bfloat16, 2e-2)):
            grads = {k: jnp.asarray(
                rng.standard_normal(v.shape), jnp.float32).astype(dtype)
                for k, v in params.items()}

            def run(fn):
                f = jax.jit(jax.shard_map(
                    fn, mesh=mesh, in_specs=(pspecs,), out_specs=pspecs,
                    check_vma=False))
                return jax.tree.map(np.asarray, f(grads))

            ref = run(lambda g: sharding.sync_grads(g, pspecs, pc,
                                                    "data"))
            for cap in (None, 3000):   # fully fused + multi-bucket
                got = run(lambda g: overlap.bucketed_sync_grads(
                    g, pspecs, pc, "data", bucket_bytes=cap))
                for k in params:
                    if backend == "ring" and dtype == jnp.float32:
                        assert np.array_equal(ref[k], got[k]), \
                            (dp, tp, k, cap)
                    else:
                        np.testing.assert_allclose(
                            np.asarray(ref[k], np.float32),
                            np.asarray(got[k], np.float32),
                            rtol=tol or 1e-5, atol=tol or 1e-6,
                            err_msg=f"{dp}x{tp} {k} cap={cap}")
    print(f"  bucketed-sync[{backend}] ok")


def check_bucketed_gather(backend: str) -> None:
    """Fused (bucketed) FSDP AllGather vs the per-leaf gather over a
    hierarchical (pod, data) axis: pure data movement, so the result
    must be bitwise identical - including dtype-split buckets and
    pass-through of sub-threshold leaves."""
    rng = np.random.default_rng(7)
    mesh = jax.make_mesh((2, 4), ("pod", "data"))
    comm = Communicator(backend=backend)
    pc = ParallelContext(tp_axis=None, dp_axis=("pod", "data"), tp=1,
                         comm=comm)
    row = {
        "w1": jnp.asarray(rng.standard_normal((64, 48)), jnp.float32),
        "w2": jnp.asarray(rng.standard_normal((32, 64)), jnp.float32),
        "wb": jnp.asarray(rng.standard_normal((64, 16)),
                          jnp.float32).astype(jnp.bfloat16),
        "tiny": jnp.asarray(rng.standard_normal((8,)), jnp.float32),
    }
    specs = {"w1": P(("pod", "data"), None),
             "w2": P(None, ("pod", "data")),
             "wb": P(("pod", "data"), None),
             "tiny": P(None)}
    in_specs = (specs,)
    out_specs = {k: P() for k in row}

    def run(fn):
        f = jax.jit(jax.shard_map(
            lambda p: fn("row", p), mesh=mesh, in_specs=in_specs,
            out_specs=out_specs, check_vma=False))
        return jax.tree.map(np.asarray, f(row))

    ref = run(sharding.fsdp_gather_fn({"row": specs}, pc,
                                      ("pod", "data")))
    for cap in (None, 8192):
        got = run(overlap.make_gather_fn({"row": specs}, pc,
                                         ("pod", "data"),
                                         bucket_bytes=cap))
        for k in row:
            assert got[k].dtype == ref[k].dtype, k
            assert np.array_equal(ref[k], got[k]), (k, cap)
    # oracle: gathered leaves reproduce the full (unsharded) array
    np.testing.assert_array_equal(ref["w1"], np.asarray(row["w1"]))
    np.testing.assert_array_equal(ref["tiny"], np.asarray(row["tiny"]))
    print(f"  bucketed-gather[{backend}] ok")


def check_train_equivalence(backend: str, arch: str) -> None:
    mesh = jax.make_mesh((2, 4), ("data", "model"))
    cfg = get_config(arch, smoke=True)
    if cfg.moe:
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=16.0, router_aux_weight=0.0))
    params = model.init_params(KEY, cfg, tp=4, dtype=jnp.float32)
    B, L = 4, 16
    batch = {"tokens": jnp.asarray(RNG.integers(0, cfg.vocab_size,
                                                (B, L))),
             "labels": jnp.asarray(RNG.integers(0, cfg.vocab_size,
                                                (B, L)))}
    bspecs = {"tokens": P("data"), "labels": P("data")}
    if cfg.frontend == "vision_stub" and cfg.encoder is None:
        batch["frontend"] = jnp.asarray(RNG.standard_normal(
            (B, cfg.frontend_tokens, cfg.frontend_dim)), jnp.float32)
        bspecs["frontend"] = P("data")
    if cfg.encoder is not None:
        batch["source"] = jnp.asarray(RNG.standard_normal(
            (B, cfg.encoder.source_len, cfg.frontend_dim)), jnp.float32)
        bspecs["source"] = P("data")

    tcfg = TrainConfig(lr=1e-3, warmup=0, clip_norm=None, remat=False)
    ref_step = jax.jit(make_train_step(cfg, tcfg))
    p_ref, _, m_ref = ref_step(params, adamw_init(params), batch)

    sharding.set_mesh_sizes({"model": 4, "data": 2})
    comm = Communicator(backend=backend)
    pc = ParallelContext(tp_axis="model", dp_axis="data", tp=4, comm=comm)
    pspecs = sharding.param_specs(params, cfg, dp_axis="data", fsdp=True)
    rspecs = sharding.row_specs(pspecs)
    # production path: row-fused FSDP gathers + bucketed grad sync +
    # double-buffered prefetch (TrainConfig defaults)
    gather = overlap.make_gather_fn(rspecs, pc, "data", bucket_bytes=None)
    inner = make_train_step(cfg, tcfg, pc, gather_fn=gather,
                            param_spec_tree=pspecs, dp_axis="data")
    from repro.optim import AdamWState
    ospecs = AdamWState(step=P(), mu=pspecs, nu=pspecs)
    mspecs = {"loss": P(), "lr": P(), "grad_norm": P(), "xent": P(),
              "aux": P()}
    step = jax.jit(jax.shard_map(
        inner, mesh=mesh, in_specs=(pspecs, ospecs, bspecs),
        out_specs=(pspecs, ospecs, mspecs), check_vma=False))
    p_sh, _, m_sh = step(params, adamw_init(params), batch)

    # zamba2 stacks 38 recurrent (exp-decay) layers: the row-parallel
    # psum reassociation amplifies chaotically, so it gets a wider band
    # (observed deltas up to ~5e-2 on CPU jax 0.4.x).
    tol = 8e-2 if arch.startswith("zamba2") else 5e-3
    assert abs(float(m_sh["loss"]) - float(m_ref["loss"])) < tol, \
        (arch, float(m_sh["loss"]), float(m_ref["loss"]))
    errs = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                           - b.astype(jnp.float32)))),
        p_ref, p_sh)
    worst = max(jax.tree.leaves(errs))
    assert worst < tol, f"{arch} {backend}: param delta {worst}"
    print(f"  train-equiv[{backend}/{arch}] ok "
          f"(loss {float(m_sh['loss']):.4f}, worst dp {worst:.1e})")


def check_topology_hierarchical() -> None:
    """Acceptance: a 3-level ("pod", "node", "gpu") topology with
    distinct per-level fabric configs round-trips through
    tune -> save -> load -> Communicator(backend='auto'), the plan cells
    carry (level, fabric fingerprint) keys, the ledger splits wire bytes
    per level/fabric, and the hierarchical decomposition matches the
    flat single-axis reference: bitwise for fp32 (integer-valued data,
    so cross-order summation is exact) under ring, allclose for cxl and
    bf16.  Uneven level sizes (2x4, 4x2) are covered too."""
    import tempfile

    from repro import tuner
    from repro.core import ledger
    from repro.core.hw import CXLPoolConfig, ICIConfig, InfiniBandConfig
    from repro.core.topology import Level, Topology

    rng = np.random.default_rng(42)
    topo = Topology(levels=(
        Level("pod", "ib", ib=InfiniBandConfig(link_bw=12.5e9)),
        Level("node", "cxl", pool=CXLPoolConfig(device_bw=18e9)),
        Level("gpu", "ici", ici=ICIConfig(link_bw=45e9)),
    ))
    grid = tuner.TuneGrid(sizes=(256, 4096, 65536), nranks=(2, 4, 8),
                          slicing_factors=(1, 4))
    plan = tuner.generate_plan(grid, topology=topo)
    # round-trip through disk, exactly as tune -> train would
    with tempfile.TemporaryDirectory() as td:
        path = td + "/topo_plan.json"
        tuner.save_plan(plan, path)
        plan = tuner.load_plan(path, topology=topo)
    assert plan.topology().fingerprint() == topo.fingerprint()
    lkeys = plan.levels()
    assert len(lkeys) == 3, lkeys
    for i, lv in enumerate(topo.levels):
        assert topo.level_key(lv.axis) in lkeys, (lv.axis, lkeys)
        assert topo.level_key(lv.axis).startswith(f"{i}:")
    # distinct fabrics -> distinct fingerprints
    assert len({k.split(":")[1] for k in lkeys}) == 3

    mesh3 = jax.make_mesh((2, 2, 2), ("pod", "node", "gpu"))
    mesh1 = jax.make_mesh((8,), ("x",))
    axes3 = ("pod", "node", "gpu")
    xi = rng.integers(-8, 8, (64, 5)).astype(np.float32)

    def run(mesh, spec, comm_fn, x):
        return np.asarray(jax.jit(jax.shard_map(
            comm_fn, mesh=mesh, in_specs=P(spec), out_specs=P(spec),
            check_vma=False))(x))

    for backend in ("ring", "cxl", "auto"):
        comm = Communicator(backend=backend, plan=plan, topology=topo)
        flat = Communicator(backend=backend, plan=plan)
        ledger.reset()
        ar3 = run(mesh3, axes3, lambda a: comm.all_reduce(a, axes3), xi)
        snap = ledger.snapshot()
        # hierarchical AR decomposes into per-level RS/AR/AG and the
        # ledger attributes every byte to its level/fabric; the outer
        # (pod-spanning) fabric carries 1/prod(inner) of the payload
        lvl = {k: sum(v.values())
               for k, v in snap["level_wire_bytes"].items()}
        assert set(lvl) == {"pod/ib", "node/cxl", "gpu/ici"}, lvl
        assert lvl["pod/ib"] < lvl["gpu/ici"], lvl
        if backend == "auto":
            audit = snap["auto_choices"]
            assert {a["level"] for a in audit} == set(axes3)
            assert {a["fabric"] for a in audit} == {"ib", "cxl", "ici"}
            # the pool schedule only exists on the cxl level
            for a in audit:
                if a["fabric"] != "cxl":
                    assert a["backend"] == "ring", a
        ar1 = run(mesh1, "x", lambda a: flat.all_reduce(a, "x"), xi)
        assert np.array_equal(ar3, ar1), backend
        ag3 = run(mesh3, axes3, lambda a: comm.all_gather(a, axes3), xi)
        ag1 = run(mesh1, "x", lambda a: flat.all_gather(a, "x"), xi)
        assert np.array_equal(ag3, ag1), backend
        bc3 = run(mesh3, axes3,
                  lambda a: comm.broadcast(a, axes3, root=5), xi)
        bc1 = run(mesh1, "x",
                  lambda a: flat.broadcast(a, "x", root=5), xi)
        assert np.array_equal(bc3, bc1), backend
        rs3 = run(mesh3, axes3,
                  lambda a: comm.reduce_scatter(a, axes3), xi)
        rs1 = run(mesh1, "x", lambda a: flat.reduce_scatter(a, "x"), xi)
        assert np.array_equal(rs3, rs1), backend
        # bf16: same decomposition, allclose band
        xb = jnp.asarray(xi + 0.25 * rng.standard_normal(xi.shape),
                         jnp.bfloat16)
        arb3 = run(mesh3, axes3, lambda a: comm.all_reduce(a, axes3), xb)
        arb1 = run(mesh1, "x", lambda a: flat.all_reduce(a, "x"), xb)
        np.testing.assert_allclose(
            np.asarray(arb3, np.float32), np.asarray(arb1, np.float32),
            rtol=3e-2, atol=3e-1, err_msg=backend)
    # uneven level sizes: 2x4 and 4x2 two-level topologies
    topo_pn = Topology(levels=topo.levels[:2])
    for shape in ((2, 4), (4, 2)):
        mesh2 = jax.make_mesh(shape, ("pod", "node"))
        for backend in ("ring", "cxl"):
            comm = Communicator(backend=backend, topology=topo_pn)
            flat = Communicator(backend=backend)
            a2 = run(mesh2, ("pod", "node"),
                     lambda a: comm.all_reduce(a, ("pod", "node")), xi)
            a1 = run(mesh1, "x", lambda a: flat.all_reduce(a, "x"), xi)
            assert np.array_equal(a2, a1), (shape, backend)
            b2 = run(mesh2, ("pod", "node"),
                     lambda a: comm.broadcast(a, ("pod", "node"),
                                              root=5), xi)
            b1 = run(mesh1, "x",
                     lambda a: flat.broadcast(a, "x", root=5), xi)
            assert np.array_equal(b2, b1), (shape, backend)
    print("  topology-hierarchical ok")


def check_irregular_ragged() -> None:
    """Irregular (4+2) hierarchical collectives vs the flat single-axis
    reference: a topology level with a mixed-fan-out shape vector lives
    on one flat 6-rank axis, decomposes into within-pod rings + an IB
    sub-root exchange, and must stay allclose to the flat result (the
    grouped decomposition changes the summation order).  The ledger
    must attribute the cross-group bytes to the parent (pod) fabric."""
    from repro import tuner
    from repro.core import ledger
    from repro.core.hw import CXLPoolConfig, InfiniBandConfig
    from repro.core.topology import Level, Topology

    rng = np.random.default_rng(11)
    topo = Topology(levels=(
        Level("pod", "ib", ib=InfiniBandConfig(link_bw=2.5e9)),
        Level("node", "cxl", pool=CXLPoolConfig(device_bw=18e9),
              shape=(4, 2)),
    ))
    plan = tuner.generate_plan(
        tuner.TuneGrid(sizes=(4096, 65536), nranks=(2, 4),
                       slicing_factors=(1, 4)), topology=topo)
    mesh6 = jax.sharding.Mesh(np.asarray(jax.devices()[:6]), ("node",))
    mesh1 = jax.sharding.Mesh(np.asarray(jax.devices()[:6]), ("x",))
    x = rng.standard_normal((6 * 8, 5)).astype(np.float32)

    def run(mesh, spec, f, arr, out_spec=None):
        return np.asarray(jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=P(spec),
            out_specs=P(out_spec if out_spec is not None else spec),
            check_vma=False))(arr))

    for backend in ("ring", "cxl", "auto"):
        comm = Communicator(backend=backend, plan=plan, topology=topo)
        flat = Communicator(backend=backend, plan=plan)
        ledger.reset()
        ar6 = run(mesh6, "node", lambda a: comm.all_reduce(a, "node"), x)
        snap = ledger.snapshot()
        lvl = {k: sum(v.values())
               for k, v in snap["level_wire_bytes"].items()}
        assert set(lvl) == {"node/cxl", "pod/ib"}, lvl
        assert lvl["pod/ib"] < lvl["node/cxl"], lvl
        ar1 = run(mesh1, "x", lambda a: flat.all_reduce(a, "x"), x)
        np.testing.assert_allclose(ar6, ar1, rtol=1e-4, atol=1e-5,
                                   err_msg=backend)
        ag6 = run(mesh6, "node", lambda a: comm.all_gather(a, "node"),
                  x, out_spec=())
        np.testing.assert_allclose(ag6, x, rtol=1e-6, err_msg=backend)
        g6 = run(mesh6, "node",
                 lambda a: comm.gather(a, "node", root=4), x)
        g6 = g6.reshape(6, 48, 5)
        np.testing.assert_allclose(g6[4], x, rtol=1e-6, err_msg=backend)
        assert np.allclose(g6[0], 0.0), backend
        if backend == "auto":
            audit = snap["auto_choices"]
            assert {a["level"] for a in audit} == {"node", "pod"}
            # the sub-root exchange runs at the group count on the
            # parent level, the within-pod schedule at the max group
            ns = {(a["level"], a["nranks"]) for a in audit}
            assert ("pod", 2) in ns and ("node", 4) in ns, ns
    print("  irregular-ragged ok (4+2 vs flat, per-level ledger)")


def check_survivor_reconfig() -> None:
    """Elastic reconfiguration on real devices: an 8-rank
    ``node:cxl:4+4`` data-parallel loop loses rank 5 mid-run.  The
    heartbeat monitor confirms the death, ``resilience.replan``
    produces the ragged ``4+3`` survivor topology (hot-swapped through
    the registry), the mesh is rebuilt over the 7 surviving devices,
    and state rolls back to the newest pool-resident snapshot.  The
    continued (ragged, hierarchical) run must stay allclose to a fresh
    flat single-axis 7-rank run from the same restored state, and the
    post-failure ledger must attribute bytes to the survivor
    topology's levels (within-group cxl + cross-group ib sub-root)."""
    from repro import tuner
    from repro.core import ledger
    from repro.core.hw import CXLPoolConfig, InfiniBandConfig
    from repro.core.topology import (Level, Topology,
                                     set_active_topology)
    from repro.resilience import (FailureMonitor, FaultPlan,
                                  ResilienceController)
    from repro.training.checkpoint import PoolCheckpointStore
    from repro.tuner import runtime as tuner_runtime

    # detached stream: the chaotic train-equivalence checks depend on
    # the module RNG's draw order
    rng = np.random.default_rng(31)
    base_plan = tuner.get_active_plan()
    topo8 = Topology(levels=(
        Level("pod", "ib", ib=InfiniBandConfig(link_bw=2.5e9)),
        Level("node", "cxl", pool=CXLPoolConfig(device_bw=18e9),
              shape=(4, 4)),
    ))

    def make_step(mesh, axis, comm):
        def step(p, x):
            g = comm.all_reduce(x * p, axis)
            piece = comm.reduce_scatter(g, axis)
            return p - 0.1 * comm.all_gather(piece, axis)
        return jax.jit(jax.shard_map(step, mesh=mesh,
                                     in_specs=(P(), P(axis)),
                                     out_specs=P(), check_vma=False))

    mesh8 = jax.sharding.Mesh(np.asarray(jax.devices()[:8]), ("node",))
    comm8 = Communicator(backend="cxl", topology=topo8)
    step8 = make_step(mesh8, "node", comm8)
    p = jnp.asarray(rng.standard_normal((56, 4)).astype(np.float32)
                    * 1e-3)
    store = PoolCheckpointStore(capacity_bytes=1 << 20)
    mon = FailureMonitor(8)
    ctrl = ResilienceController(mon, topology=topo8,
                                log=lambda *_: None)
    fp = FaultPlan.parse("rank_death@6:rank=5")
    confirm_step = rp = None
    with fp:
        for i in range(12):
            fp.begin_step(i)
            x = rng.standard_normal((8 * 56, 4)).astype(np.float32)
            p = step8(p, x)
            if i % 2 == 0:
                store.snapshot(i, {"p": p})
            got = ctrl.step(i)
            if got is not None:
                confirm_step, rp = i, got
                break
    assert rp is not None, "rank death never confirmed"
    lv = rp.topology.level_for("node")
    assert lv.shape == (4, 3), lv.shape
    snap = store.latest()
    lost = ctrl.steps_lost(6, confirm_step, snap)
    assert lost <= 8, (confirm_step, snap, lost)

    # resume: survivors restore the snapshot and continue on a 7-rank
    # mesh under the re-planned ragged topology (registry-resolved)
    restored, _ = store.restore({"p": p})
    p7 = jnp.asarray(restored["p"])
    mesh7 = jax.sharding.Mesh(np.asarray(jax.devices()[:7]), ("node",))
    comm7 = Communicator(backend="auto")    # recovery plan + topology
    step7 = make_step(mesh7, "node", comm7)
    ledger.reset()
    xs = [rng.standard_normal((7 * 56, 4)).astype(np.float32)
          for _ in range(3)]
    p_ragged = p7
    for x in xs:
        p_ragged = step7(p_ragged, x)
    snap7 = ledger.snapshot()
    lvl = {k: sum(v.values())
           for k, v in snap7["level_wire_bytes"].items()}
    assert set(lvl) == {"node/cxl", "pod/ib"}, lvl
    assert lvl["pod/ib"] < lvl["node/cxl"], lvl
    audit = snap7["auto_choices"]
    ns = {(a["level"], a["nranks"]) for a in audit}
    # ragged 4+3: within-group schedules at the max group (4), the
    # cross-group sub-root exchange at the group count (2)
    assert ("node", 4) in ns and ("pod", 2) in ns, ns

    # reference: fresh flat single-axis 7-rank run, same state + data
    mesh7f = jax.sharding.Mesh(np.asarray(jax.devices()[:7]), ("x",))
    flat = Communicator(backend="cxl")
    stepf = make_step(mesh7f, "x", flat)
    p_flat = p7
    for x in xs:
        p_flat = stepf(p_flat, x)
    np.testing.assert_allclose(np.asarray(p_ragged),
                               np.asarray(p_flat),
                               rtol=1e-4, atol=1e-6)

    # restore process-wide state for the checks that follow
    tuner.set_active_plan(base_plan)
    set_active_topology(None)
    tuner_runtime.clear_rank_liveness()
    print(f"  survivor-reconfig ok (confirm@{confirm_step}, "
          f"rollback to {snap}, {lost} steps lost, ragged 4+3 "
          f"allclose vs flat 7-rank)")


def check_online_retune_hotswap() -> None:
    """Hot-swapping a measurement-refreshed plan mid-run must keep the
    numerics bitwise-identical to running the whole loop under the
    fixed plan.  Two swap flavors are exercised mid-loop:

    1. a refresh whose measurements *confirm* the oracle (EWMA-only
       update: the workload's resolved choices cannot move);
    2. a refresh whose measurements flip a cell the workload never
       touches (choices_changed is True, the step re-traces against
       the bumped registry epoch).

    Either way the collectives the step actually runs are identical,
    so the retraced program must produce bit-identical parameters.
    """
    from repro import tuner
    from repro.core import ledger

    mesh = jax.make_mesh((8,), ("x",))
    base = tuner.get_active_plan()
    assert base is not None

    def make_step():
        comm = Communicator(backend="auto")  # registry resolution
        def step(p, x):
            g = comm.all_reduce(x * p, "x")
            piece = comm.reduce_scatter(g, "x")
            return p - 0.1 * comm.all_gather(piece, "x")
        return jax.jit(jax.shard_map(step, mesh=mesh,
                                     in_specs=(P(), P("x")),
                                     out_specs=P(), check_vma=False))

    rng = np.random.default_rng(7)
    p0 = rng.standard_normal((16, 4)).astype(np.float32)
    xs = [rng.standard_normal((128, 4)).astype(np.float32)
          for _ in range(6)]

    # reference: 6 steps under the fixed base plan
    tuner.set_active_plan(base)
    ledger.reset()
    step = make_step()
    p_ref = jnp.asarray(p0)
    for x in xs:
        p_ref = step(p_ref, x)
    profile = ledger.snapshot()["auto_choices"]
    assert profile, "auto resolution recorded no choices"

    # hot-swap run: swap at step 3 with oracle-confirming measurements,
    # then at step 5 with a flip in an untouched broadcast cell
    tuner.set_active_plan(base)
    step = make_step()
    p_hot = jnp.asarray(p0)
    ot = tuner.OnlineTuner(base, min_samples=2)
    for i, x in enumerate(xs):
        if i == 3:
            for c in profile:
                # only feed cells the plan already holds: a sample at
                # an untuned bucket would legitimately grow an
                # exact-bucket cell and re-resolve it at its own size
                key = (c["primitive"],
                       tuner.size_bucket(c["msg_bytes"]), c["nranks"])
                if key not in base.entries:
                    continue
                for _ in range(2):   # measured == predicted: confirm
                    ot.observe(c["primitive"], c["msg_bytes"],
                               c["nranks"], c["backend"],
                               c["predicted_time"],
                               slicing_factor=c["slicing_factor"],
                               allreduce_mode=c["allreduce_mode"])
            refreshed = ot.refresh_and_activate()
            for c in profile:   # workload cells resolve identically
                want = base.lookup(c["primitive"], c["msg_bytes"],
                                   c["nranks"])
                got = refreshed.lookup(c["primitive"], c["msg_bytes"],
                                       c["nranks"])
                assert (got.backend, got.slicing_factor,
                        got.allreduce_mode) == \
                    (want.backend, want.slicing_factor,
                     want.allreduce_mode), (c, want, got)
            step = make_step()   # re-trace against the new epoch
        if i == 5:
            # flip an untouched broadcast cell: its *chosen* candidate
            # measures terribly, so the argmin must move off it
            bch = base.lookup("broadcast", 4096, 4)
            for _ in range(2):
                ot.observe("broadcast", 4096, 4, bch.backend, 10.0,
                           slicing_factor=bch.slicing_factor,
                           allreduce_mode=bch.allreduce_mode)
            refreshed = ot.refresh_and_activate()
            assert tuner.choices_changed(base, refreshed)
            step = make_step()
        p_hot = step(p_hot, x)

    assert np.array_equal(np.asarray(p_ref), np.asarray(p_hot)), \
        "hot-swap perturbed the numerics"
    tuner.set_active_plan(base)
    print("  online-retune-hotswap ok (bitwise vs fixed plan)")


def check_obs_metrics() -> None:
    """Every gauge ``obs.from_ledger`` exports must reconcile exactly
    with the ``ledger.snapshot()`` it was built from - per collective
    kind, per (level, fabric) attribution, and in total - and survive a
    JSON-lines round trip.  Run against a real 2-level hierarchical
    AllReduce so the snapshot carries multi-fabric attribution."""
    from repro.core import ledger
    from repro.core.hw import CXLPoolConfig, InfiniBandConfig
    from repro.core.topology import Level, Topology
    from repro.obs import MetricsRegistry, from_ledger

    topo = Topology(levels=(
        Level("pod", "ib", ib=InfiniBandConfig(link_bw=12.5e9)),
        Level("node", "cxl", pool=CXLPoolConfig(device_bw=18e9)),
    ))
    mesh = jax.make_mesh((2, 4), ("pod", "node"))
    comm = Communicator(backend="cxl", topology=topo)
    # detached stream: the chaotic train-equivalence checks depend on
    # the module RNG's draw order
    x = np.random.default_rng(23).standard_normal(
        (64, 5)).astype(np.float32)
    ledger.reset()
    jax.jit(jax.shard_map(
        lambda a: comm.all_gather(comm.all_reduce(a, ("pod", "node")),
                                  ("pod", "node")),
        mesh=mesh, in_specs=P(("pod", "node")), out_specs=P(),
        check_vma=False)).lower(x)
    snap = ledger.snapshot()
    assert snap["wire_bytes"] and snap["level_wire_bytes"], snap

    reg = MetricsRegistry()
    from_ledger(reg, snap)
    for kind, b in snap["wire_bytes"].items():
        assert reg.value("repro_wire_bytes", kind=kind) == b, kind
    for kind, c in snap["collective_calls"].items():
        assert reg.value("repro_collective_launches",
                         kind=kind) == c, kind
    for lk, kinds in snap["level_wire_bytes"].items():
        level, _, fabric = lk.partition("/")
        for kind, b in kinds.items():
            assert reg.value("repro_level_wire_bytes", level=level,
                             fabric=fabric, kind=kind) == b, (lk, kind)
    # per-level attribution partitions the wire total
    lvl_total = sum(b for kinds in snap["level_wire_bytes"].values()
                    for b in kinds.values())
    assert abs(lvl_total - snap["total_wire_bytes"]) < 1e-6, \
        (lvl_total, snap["total_wire_bytes"])
    # the JSON-lines artifact round-trips to the same values
    import json as _json
    seen = {}
    for line in reg.to_jsonl().splitlines():
        rec = _json.loads(line)
        seen[(rec["name"], tuple(sorted(rec["labels"].items())))] = \
            rec["value"]
    for kind, b in snap["wire_bytes"].items():
        assert seen[("repro_wire_bytes", (("kind", kind),))] == b
    print(f"  obs-metrics ok ({len(seen)} samples reconcile with the "
          f"ledger)")


def check_ledger_vs_hlo():
    """For an unscanned program the trace-time ledger and the compiled-HLO
    parse must agree on collective wire bytes (the scan undercount is the
    only reason the two differ - see EXPERIMENTS.md §Dry-run)."""
    from repro.core import ledger
    from repro.launch.dryrun import parse_collectives
    mesh = jax.make_mesh((8,), ("x",))
    comm = Communicator()

    def f(a):
        return comm.all_reduce(comm.all_gather(a, "x"), "x")

    ledger.reset()
    lowered = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=P("x"), out_specs=P("x"),
        check_vma=False)).lower(
        jax.ShapeDtypeStruct((64, 128), jnp.float32))
    led = ledger.snapshot()["total_wire_bytes"]
    hlo = parse_collectives(lowered.compile().as_text())
    parsed = hlo["total_wire_bytes"]
    ratio = parsed / led if led else 0.0
    # XLA may fuse/convert ops (e.g. AR -> AG or RS+AG) so allow 2x band
    assert 0.4 < ratio < 2.5, (led, parsed, hlo)
    print(f"  ledger-vs-hlo ok (ledger {led/1e3:.1f}KB, "
          f"hlo {parsed/1e3:.1f}KB)")


def check_ragged_reduce_scatter() -> None:
    """Padding-free ragged reduce_scatter: a 4+2 grouped level on one
    flat 6-rank axis must return the same rank-major segments as the
    flat single-axis schedule (allclose - the grouped decomposition
    reassociates the sum), attribute the within-group bytes to the cxl
    level and the sub-root exchange to the parent ib fabric, and record
    NO flat-on-ragged fallback event: the ragged schedule is the real
    path, not a padded or flattened escape hatch."""
    from repro import tuner
    from repro.core import ledger
    from repro.core.hw import CXLPoolConfig, InfiniBandConfig
    from repro.core.topology import Level, Topology

    rng = np.random.default_rng(23)
    topo = Topology(levels=(
        Level("pod", "ib", ib=InfiniBandConfig(link_bw=2.5e9)),
        Level("node", "cxl", pool=CXLPoolConfig(device_bw=18e9),
              shape=(4, 2)),
    ))
    plan = tuner.generate_plan(
        tuner.TuneGrid(sizes=(4096, 65536), nranks=(2, 4),
                       slicing_factors=(1, 4)), topology=topo)
    mesh6 = jax.sharding.Mesh(np.asarray(jax.devices()[:6]), ("node",))
    mesh1 = jax.sharding.Mesh(np.asarray(jax.devices()[:6]), ("x",))
    # per-rank lead 12 divides the 6-rank axis; seg = 2 rows
    x = rng.standard_normal((6 * 12, 5)).astype(np.float32)

    def run(mesh, spec, f, arr):
        return np.asarray(jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=P(spec), out_specs=P(spec),
            check_vma=False))(arr))

    for backend in ("ring", "cxl", "auto"):
        comm = Communicator(backend=backend, plan=plan, topology=topo)
        flat = Communicator(backend=backend, plan=plan)
        ledger.reset()
        rs6 = run(mesh6, "node",
                  lambda a: comm.reduce_scatter(a, "node"), x)
        snap = ledger.snapshot()
        assert snap["fallbacks"] == [], (backend, snap["fallbacks"])
        lvl = {k: sum(v.values())
               for k, v in snap["level_wire_bytes"].items()}
        assert set(lvl) == {"node/cxl", "pod/ib"}, lvl
        assert lvl["pod/ib"] < lvl["node/cxl"], lvl
        rs1 = run(mesh1, "x", lambda a: flat.reduce_scatter(a, "x"), x)
        np.testing.assert_allclose(rs6, rs1, rtol=1e-5, atol=1e-6,
                                   err_msg=backend)
        if backend == "auto":
            ns = {(a["level"], a["nranks"])
                  for a in snap["auto_choices"]
                  if a["primitive"] == "reduce_scatter"}
            # within-group rings at the max group, sub-root exchange
            # at the group count on the parent level
            assert ("node", 4) in ns and ("pod", 2) in ns, ns
    print("  ragged-reduce-scatter ok (4+2 vs flat, no fallback)")


def check_fused_train(ragged: bool) -> None:
    """``TrainConfig.fuse_kernels`` routes the FSDP AllGather into the
    consuming matmuls (kernels.fused_collectives via StackedShards) -
    one sharded AdamW step must match the unfused bucketed path on the
    same mesh, and the ledger must book the gathered weight bytes into
    the fused split (and book nothing there when the flag is off).
    ``ragged=True`` re-runs the comparison on a 6-rank 4+2 grouped dp
    axis, where the gather's AD transpose lowers to the padding-free
    ragged reduce_scatter - no fallback events allowed."""
    from repro.core import ledger
    from repro.models.config import ModelConfig, dense_pattern
    from repro.optim import AdamWState
    from repro.training.train_loop import make_gather_fn as mk_gather

    rng = np.random.default_rng(77)
    if ragged:
        from repro.core.hw import CXLPoolConfig, InfiniBandConfig
        from repro.core.topology import Level, Topology
        topo = Topology(levels=(
            Level("pod", "ib", ib=InfiniBandConfig(link_bw=2.5e9)),
            Level("data", "cxl", pool=CXLPoolConfig(device_bw=18e9),
                  shape=(4, 2)),
        ))
        mesh = jax.sharding.Mesh(
            np.asarray(jax.devices()[:6]).reshape(6, 1),
            ("data", "model"))
        # d_model divisible by the ragged dp=6 and past FSDP_MIN_SIZE
        # (384*384 elements), so the matmul weights actually shard
        cfg = ModelConfig(name="tiny-fsdp6", family="dense",
                          n_layers=2, d_model=384, n_heads=6,
                          n_kv_heads=2, d_ff=768, vocab_size=512,
                          layer_pattern=dense_pattern(2))
        dp, tp = 6, 1
        comm = Communicator(backend="cxl", topology=topo)
    else:
        mesh = jax.make_mesh((2, 2), ("data", "model"))
        cfg = get_config("llama3-8b", smoke=True)
        dp, tp = 2, 2
        comm = Communicator(backend="ring")
    params = model.init_params(jax.random.key(7), cfg, tp=tp,
                               dtype=jnp.float32)
    B, L = dp, 16
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size,
                                                (B, L))),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab_size,
                                                (B, L)))}
    bspecs = {"tokens": P("data"), "labels": P("data")}

    sharding.set_mesh_sizes({"model": tp, "data": dp})
    pc = ParallelContext(tp_axis="model", dp_axis="data", tp=tp,
                         comm=comm)
    pspecs = sharding.param_specs(params, cfg, dp_axis="data",
                                  fsdp=True)
    rspecs = sharding.row_specs(pspecs)
    ospecs = AdamWState(step=P(), mu=pspecs, nu=pspecs)
    mspecs = {"loss": P(), "lr": P(), "grad_norm": P(), "xent": P(),
              "aux": P()}

    out = {}
    for fuse in (False, True):
        tcfg = TrainConfig(lr=1e-3, warmup=0, clip_norm=None,
                           remat=False, fuse_kernels=fuse)
        gather = mk_gather(tcfg, rspecs, pc, "data")
        inner = make_train_step(cfg, tcfg, pc, gather_fn=gather,
                                param_spec_tree=pspecs, dp_axis="data")
        step = jax.jit(jax.shard_map(
            inner, mesh=mesh, in_specs=(pspecs, ospecs, bspecs),
            out_specs=(pspecs, ospecs, mspecs), check_vma=False))
        ledger.reset()
        p2, o2, m2 = step(params, adamw_init(params), batch)
        out[fuse] = (p2, o2, m2, ledger.snapshot())
    (p_u, o_u, m_u, snap_u), (p_f, o_f, m_f, snap_f) = \
        out[False], out[True]

    # the flag alone flips the fused split on and off
    assert snap_u["total_fused_bytes"] == 0.0, snap_u["fused_bytes"]
    assert snap_f["fused_bytes"].get("all_gather", 0.0) > 0.0, \
        snap_f["fused_bytes"]
    if ragged:
        assert snap_f["fallbacks"] == [], snap_f["fallbacks"]
        assert snap_u["fallbacks"] == [], snap_u["fallbacks"]
    assert abs(float(m_f["loss"]) - float(m_u["loss"])) < 1e-5, \
        (float(m_f["loss"]), float(m_u["loss"]))
    # The kernels differ from the unfused path only in f32 matmul
    # summation order, so the gradients (read back from the first
    # moment, mu = (1 - b1) * g after one step) must agree to a few
    # ulp of each leaf's largest element.  AdamW's first step then
    # moves every element by lr * g / (|g| + eps) plus a decay term
    # both paths share: where |g| >> eps that is +-lr whatever the
    # ulp noise, but a near-zero g whose sign the noise flips moves by
    # up to 2 * lr (observed 1.4e-3 at lr=1e-3 on the ragged mesh under
    # jax 0.9's CPU dot).  So params are held tight where the update
    # is saturated and to the 2 * lr ceiling elsewhere.
    from repro.optim.optimizer import AdamWConfig
    acfg = AdamWConfig()
    lr = float(m_u["lr"])
    worst = 0.0
    for pu, pf, mu_u, mu_f in zip(*(jax.tree.leaves(t) for t in (
            p_u, p_f, o_u.mu, o_f.mu))):
        pu, pf, mu_u, mu_f = (np.asarray(a) for a in (pu, pf, mu_u,
                                                      mu_f))
        g_scale = float(np.max(np.abs(mu_u))) / (1 - acfg.b1)
        g_delta = float(np.max(np.abs(mu_u - mu_f))) / (1 - acfg.b1)
        assert g_delta <= 1e-5 * g_scale, (g_delta, g_scale)
        dp = np.abs(pu - pf)
        saturated = np.abs(mu_u) / (1 - acfg.b1) > 1e3 * acfg.eps
        dp_sat = float(np.max(dp[saturated], initial=0.0))
        assert dp_sat < 1e-6, \
            f"fused-vs-unfused saturated param delta {dp_sat}"
        worst = max(worst, float(dp.max()))
    assert worst <= 2 * lr * (1 + 1e-5), \
        f"fused-vs-unfused param delta {worst}"
    print(f"  fused-train[{'ragged 4+2' if ragged else '2x2'}] ok "
          f"(loss {float(m_f['loss']):.4f}, worst delta {worst:.1e}, "
          f"fused AG {snap_f['fused_bytes']['all_gather']/1e6:.2f}MB)")


def check_fallback_audit() -> None:
    """all_to_all / scatter have no grouped schedule: on a grouped
    (4+2) level they run the flat single-axis program and must book one
    explicit flat-on-ragged fallback event per call - with the level
    and fabric they degraded on - while still computing the flat
    schedule's exact answer.  The inverse of check_ragged_reduce_scatter
    (which asserts the ragged path books NO events)."""
    from repro.core import ledger
    from repro.core.hw import CXLPoolConfig, InfiniBandConfig
    from repro.core.topology import Level, Topology

    rng = np.random.default_rng(31)
    topo = Topology(levels=(
        Level("pod", "ib", ib=InfiniBandConfig(link_bw=2.5e9)),
        Level("node", "cxl", pool=CXLPoolConfig(device_bw=18e9),
              shape=(4, 2)),
    ))
    mesh6 = jax.sharding.Mesh(np.asarray(jax.devices()[:6]), ("node",))
    # per-rank lead 12 divides the 6-rank axis; a2a block / scatter
    # segment = 2 rows
    x = rng.standard_normal((6 * 12, 5)).astype(np.float32)

    def run(f, arr):
        return np.asarray(jax.jit(jax.shard_map(
            f, mesh=mesh6, in_specs=P("node"), out_specs=P("node"),
            check_vma=False))(arr))

    for backend in ("ring", "cxl"):
        comm = Communicator(backend=backend, topology=topo,
                            slicing_factor=4)
        ledger.reset()
        a2a = run(lambda a: comm.all_to_all(a, "node"), x)
        sc = run(lambda a: comm.scatter(a, "node", root=1), x)
        snap = ledger.snapshot()
        prims = sorted(e["primitive"] for e in snap["fallbacks"])
        assert prims == ["all_to_all", "scatter"], \
            (backend, snap["fallbacks"])
        for e in snap["fallbacks"]:
            assert (e["level"], e["fabric"], e["reason"]) == \
                ("node", "cxl", "flat_on_ragged"), e
            assert e["calls"] == 1.0, e
        # the degraded calls still attribute wire bytes to the level
        lvl = snap["level_wire_bytes"]["node/cxl"]
        assert lvl.get("all_to_all", 0.0) > 0.0, lvl
        assert lvl.get("scatter", 0.0) > 0.0, lvl
        # numerics vs the flat oracle
        z = x.reshape(6, 12, 5)
        np.testing.assert_allclose(
            a2a.reshape(6, 6, 2, 5),
            z.reshape(6, 6, 2, 5).transpose(1, 0, 2, 3), rtol=1e-6,
            err_msg=backend)
        np.testing.assert_allclose(
            sc.reshape(6, 2, 5), z[1].reshape(6, 2, 5), rtol=1e-6,
            err_msg=backend)
    print("  fallback-audit ok (all_to_all/scatter on 4+2 book "
          "flat_on_ragged)")


def check_pipeline_train() -> None:
    """Pipeline parallelism end to end on real devices: a 2-stage x
    4-dp pipelined AdamW step (1F1B microbatch loop, stage handoff via
    ``Communicator.send`` resolved from the plan's tuned p2p cells)
    must produce the same loss and updated params as the FSDP-only
    8-rank step on the same global batch, and the ledger must attribute
    the activation/cotangent handoff bytes to the stage level's fabric
    as ``p2p`` - not to any collective kind."""
    from repro import tuner
    from repro.core import ledger
    from repro.core.hw import CXLPoolConfig, InfiniBandConfig
    from repro.core.topology import Level, Topology, set_active_topology
    from repro.models.config import ModelConfig, dense_pattern
    from repro.training.pipeline import (bubble_fraction,
                                         make_sharded_pipeline_step)
    from repro.training.train_loop import make_sharded_train_step

    rng = np.random.default_rng(7)
    cfg = ModelConfig(name="tiny-pp", family="dense", n_layers=4,
                      d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                      vocab_size=96, layer_pattern=dense_pattern(4))
    B, L, M = 16, 16, 4
    batch = {"tokens": jnp.asarray(
                 rng.integers(0, cfg.vocab_size, (B, L))),
             "labels": jnp.asarray(
                 rng.integers(0, cfg.vocab_size, (B, L)))}
    params = model.init_params(jax.random.key(1), cfg, tp=1,
                               dtype=jnp.float32)
    tcfg = TrainConfig(lr=1e-3, warmup=0, clip_norm=None, remat=False,
                       backend="ring")

    # FSDP-only reference: the same 8 devices as one data axis
    mesh_ref = jax.make_mesh((8, 1), ("data", "model"))
    sharding.set_mesh_sizes({"data": 8, "model": 1})
    step_ref, _, _, _ = make_sharded_train_step(cfg, tcfg, mesh_ref)
    p_ref, _, m_ref = step_ref(params, adamw_init(params), batch)

    # pipelined run: IB between stages, the CXL pool under the data
    # axis - the plan's per-level p2p cells steer the stage handoff
    base_plan = tuner.get_active_plan()
    topo = Topology(levels=(
        Level("stage", "ib", ib=InfiniBandConfig(link_bw=2.5e9)),
        Level("data", "cxl", pool=CXLPoolConfig(device_bw=18e9),
              shape=(4,)),
    ))
    plan = tuner.generate_plan(
        tuner.TuneGrid(sizes=(256, 4096, 65536), nranks=(2, 4, 8),
                       slicing_factors=(1, 4)), topology=topo)
    tuner.set_active_plan(plan)
    set_active_topology(topo)
    try:
        mesh = jax.make_mesh((2, 4), ("stage", "data"))
        tcfg_pp = dataclasses.replace(tcfg, backend="auto")
        step_pp, _, _, _ = make_sharded_pipeline_step(
            cfg, tcfg_pp, mesh, n_microbatches=M)
        ledger.reset()
        p_pp, _, m_pp = step_pp(params, adamw_init(params), batch)
        snap = ledger.snapshot()
    finally:
        tuner.set_active_plan(base_plan)
        set_active_topology(None)

    assert abs(float(m_pp["loss"]) - float(m_ref["loss"])) < 1e-5, \
        (float(m_pp["loss"]), float(m_ref["loss"]))
    # host-side: the two steps' outputs live on different meshes
    errs = jax.tree.map(
        lambda a, b: float(np.max(np.abs(np.asarray(a) - np.asarray(b)))),
        p_ref, p_pp)
    worst = max(jax.tree.leaves(errs))
    # same AdamW-first-step amplification band as check_fused_train:
    # the two paths differ only in f32 reduction order
    assert worst < 5e-4, f"pipeline-vs-fsdp param delta {worst}"
    lvl = snap["level_wire_bytes"]
    assert lvl.get("stage/ib", {}).get("p2p", 0.0) > 0.0, lvl
    assert "p2p" not in lvl.get("data/cxl", {}), lvl
    assert lvl.get("data/cxl", {}).get("all_reduce", 0.0) > 0.0, lvl
    p2p_audit = [a for a in snap["auto_choices"]
                 if a["primitive"] == "p2p"]
    assert p2p_audit and \
        all(a["level"] == "stage" for a in p2p_audit), p2p_audit
    assert abs(float(m_pp["bubble_fraction"])
               - bubble_fraction(2, M)) < 1e-6
    print(f"  pipeline-train ok (loss {float(m_pp['loss']):.4f} vs "
          f"fsdp {float(m_ref['loss']):.4f}, worst delta {worst:.1e}, "
          f"p2p {lvl['stage/ib']['p2p']/1e3:.1f}KB on stage/ib)")


def check_chip_smoke_four() -> None:
    """``chip_smoke.py --chips 4`` at a tiny size: the cxl-vs-ring
    train phase on a (data=2, model=2) mesh of 4 of the forced host
    devices (its own checks raise on disagreement or unsharded
    params)."""
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import chip_smoke
    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         devices=jax.devices()[:4])
    out = chip_smoke.cxl_vs_ring_phase(get_config("llama3.2-1b",
                                                  smoke=True),
                                       mesh, batch=4, seq=32, steps=3)
    assert len(out["per_device"]) == 4, out["per_device"]
    print(f"  chip-smoke-four ok (dloss {out['dloss']:.1e}, "
          f"dparam {out['dparam']:.1e})")


if __name__ == "__main__":
    # backend='auto' resolves from the process-wide plan: tune a tiny
    # grid spanning the message sizes/axis sizes these checks use.
    from repro import tuner
    tuner.set_active_plan(tuner.generate_plan(tuner.TuneGrid(
        sizes=(256, 4096, 65536), nranks=(2, 4, 8),
        slicing_factors=(1, 4))))

    check_ledger_vs_hlo()
    check_obs_metrics()
    check_online_retune_hotswap()
    check_topology_hierarchical()
    check_irregular_ragged()
    check_ragged_reduce_scatter()
    check_survivor_reconfig()
    check_fused_train(ragged=False)
    check_fused_train(ragged=True)
    check_fallback_audit()
    check_pipeline_train()
    check_chip_smoke_four()
    check_flat_lane_rows()
    # ring/cxl draw from the module RNG in the original order (the
    # chaotic train-equivalence checks below are sensitive to the global
    # draw sequence); the added checks use a detached stream.
    for backend in ("ring", "cxl"):
        check_collectives(backend)
        check_hierarchical(backend)
    aux = np.random.default_rng(1234)
    for backend in ("ring", "cxl", "auto"):
        check_rank_major_layout(backend, rng=aux)
    check_collectives("auto", rng=aux)
    check_hierarchical("auto", rng=aux)
    for backend in ("ring", "cxl", "auto"):
        check_bucketed_sync_grads(backend)
        check_bucketed_gather(backend)
    for backend in ("ring", "cxl"):
        for arch in ("llama3-8b", "arctic-480b", "falcon-mamba-7b",
                     "zamba2-1.2b"):
            check_train_equivalence(backend, arch)
    print("MESH RUNNER: ALL OK")
