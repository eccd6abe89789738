"""core.topology + the per-level tuner path: level fingerprints, spec
parsing, topology-keyed plans (format v3), per-level cost oracles, the
plan version compat chain, and the dry-run helpers (plan report,
roofline-derived overlap windows)."""
import dataclasses
import json

import pytest

from repro import tuner
from repro.core.hw import (CXL_POOL, ICI, INFINIBAND, MiB, CXLPoolConfig,
                           ICIConfig, InfiniBandConfig)
from repro.core.topology import (Level, Topology, clear_active_topology,
                                 default_topology, get_active_topology,
                                 parse_topology, save_topology,
                                 set_active_topology)

TOPO = Topology(levels=(
    Level("pod", "ib", ib=InfiniBandConfig(link_bw=12.5e9)),
    Level("node", "cxl", pool=CXLPoolConfig(device_bw=18e9)),
    Level("gpu", "ici", ici=ICIConfig(link_bw=45e9)),
))

TINY = tuner.TuneGrid(
    primitives=("all_reduce", "all_gather", "broadcast"),
    sizes=(1 * MiB, 16 * MiB), nranks=(2, 4), slicing_factors=(1, 4))


@pytest.fixture(scope="module")
def topo_plan():
    return tuner.generate_plan(TINY, topology=TOPO)


# -- topology mechanics ---------------------------------------------------

def test_level_validation_and_defaults():
    with pytest.raises(ValueError):
        Level("pod", "nvlink")
    lv = Level("node")
    assert lv.fabric == "cxl"
    assert lv.pool_cfg is CXL_POOL and lv.ib_cfg is INFINIBAND
    assert Level("gpu", "ici").ici_cfg is ICI
    assert Level("node", "cxl").backends() == ("ring", "cxl")
    assert Level("pod", "ib").backends() == ("ring",)


def test_topology_validation():
    with pytest.raises(ValueError):
        Topology(levels=())
    with pytest.raises(ValueError):
        Topology(levels=(Level("a"), Level("a")))
    assert TOPO.axes == ("pod", "node", "gpu")
    assert TOPO.level_for("node").fabric == "cxl"
    assert TOPO.level_for("nope") is None
    assert TOPO.covers(("pod", "gpu")) and not TOPO.covers(("pod", "x"))
    assert TOPO.index_of("gpu") == 2


def test_fingerprints_track_fabric_config():
    base = Level("node", "cxl")
    tweaked = Level("node", "cxl",
                    pool=dataclasses.replace(CXL_POOL, device_bw=1e9))
    assert base.fingerprint() != tweaked.fingerprint()
    # same config, different position -> different level key
    t = Topology(levels=(Level("a", "ib"), Level("b", "ib")))
    ka, kb = t.level_key("a"), t.level_key("b")
    assert ka.split(":")[1] == kb.split(":")[1]   # same fabric fp
    assert ka != kb                               # different index
    assert TOPO.fingerprint() != Topology(
        levels=TOPO.levels[:2]).fingerprint()


def test_parse_and_roundtrip(tmp_path):
    t = parse_topology("pod:ib, node:cxl, gpu:ici")
    assert t.axes == ("pod", "node", "gpu")
    assert [lv.fabric for lv in t.levels] == ["ib", "cxl", "ici"]
    # JSON file round-trip preserves per-level config overrides
    path = str(tmp_path / "topo.json")
    save_topology(TOPO, path)
    t2 = parse_topology(path)
    assert t2 == TOPO
    assert t2.fingerprint() == TOPO.fingerprint()
    assert t2.level_for("node").pool.device_bw == 18e9


def test_default_topology():
    t3 = default_topology(("pod", "data", "model"))
    assert [lv.fabric for lv in t3.levels] == ["ib", "cxl", "ici"]
    t2 = default_topology(("data", "model"))
    assert [lv.fabric for lv in t2.levels] == ["cxl", "ici"]
    assert default_topology(("x",)).levels[0].fabric == "cxl"


def test_active_topology_registry():
    clear_active_topology()
    assert get_active_topology() is None
    set_active_topology(TOPO)
    try:
        assert get_active_topology() is TOPO
    finally:
        clear_active_topology()


# -- per-level cost oracle ------------------------------------------------

def test_predict_level_time_prices_each_fabric():
    size, n = 64 * MiB, 4
    t_ib = tuner.predict_level_time(TOPO.levels[0], "all_gather", n, size)
    t_ici = tuner.predict_level_time(TOPO.levels[2], "all_gather", n,
                                     size)
    # the 12.5 GB/s pod IB must be slower than the 45 GB/s ICI ring
    assert t_ib > t_ici > 0
    # cxl level: ring prices the IB alternative, cxl runs the simulator
    lv = TOPO.levels[1]
    t_ring = tuner.predict_level_time(lv, "all_gather", n, size)
    t_cxl = tuner.predict_level_time(lv, "all_gather", n, size,
                                     backend="cxl")
    assert t_ring > 0 and t_cxl > 0 and t_ring != t_cxl
    # the pool schedule does not exist off the pool
    import math
    assert math.isinf(tuner.predict_level_time(
        TOPO.levels[0], "all_gather", n, size, backend="cxl"))
    assert tuner.predict_level_time(lv, "all_gather", 1, size) == 0.0
    with pytest.raises(ValueError):
        tuner.predict_level_time(lv, "all_gather", n, size,
                                 backend="nccl")


# -- topology plans -------------------------------------------------------

def test_topology_plan_cells_are_level_keyed(topo_plan):
    assert topo_plan.fingerprint == TOPO.fingerprint()
    assert topo_plan.topology() == TOPO
    lkeys = topo_plan.levels()
    assert set(lkeys) == {TOPO.level_key(a) for a in TOPO.axes}
    # every cell is level-keyed; only the cxl level may pick 'cxl'
    for k, c in topo_plan.entries.items():
        assert len(k) == 4
        if k[3] != TOPO.level_key("node"):
            assert c.backend == "ring", k
    node_backends = {c.backend for k, c in topo_plan.entries.items()
                     if k[3] == TOPO.level_key("node")}
    assert "cxl" in node_backends


def test_topology_plan_lookup_levels(topo_plan):
    node = topo_plan.lookup("all_reduce", 1 * MiB, 4,
                            level=TOPO.level_key("node"))
    pod = topo_plan.lookup("all_reduce", 1 * MiB, 4,
                           level=TOPO.level_key("pod"))
    assert node is not None and pod is not None and node != pod
    # unknown level with no flat cells -> None (Communicator rings)
    assert topo_plan.lookup("all_reduce", 1 * MiB, 4,
                            level="9:deadbeef") is None
    # flat plans ignore the level arg via the level-agnostic fallback
    flat = tuner.generate_plan(TINY)
    assert flat.lookup("all_reduce", 1 * MiB, 4,
                       level=TOPO.level_key("node")) is not None


def test_topology_plan_roundtrip_and_fingerprint_check(topo_plan,
                                                       tmp_path):
    path = str(tmp_path / "plan.json")
    tuner.save_plan(topo_plan, path)
    loaded = tuner.load_plan(path, topology=TOPO)
    assert loaded.entries == topo_plan.entries
    # the flat pool/ib fingerprint check must not reject topology plans
    loaded2 = tuner.load_plan(path, pool=CXL_POOL, ib=INFINIBAND)
    assert loaded2.fingerprint == TOPO.fingerprint()
    with pytest.raises(ValueError):
        tuner.load_plan(path, topology=Topology(levels=TOPO.levels[:2]))


def test_activate_plan_file_activates_topology(topo_plan, tmp_path):
    path = str(tmp_path / "plan.json")
    tuner.save_plan(topo_plan, path)
    clear_active_topology()
    tuner.clear_active_plan()
    try:
        plan = tuner.activate_plan_file(path)
        assert tuner.get_active_plan() is plan
        assert get_active_topology() == TOPO
    finally:
        tuner.clear_active_plan()
        clear_active_topology()


def test_activate_plan_file_keeps_explicit_topology(topo_plan,
                                                    tmp_path):
    """An explicitly activated topology wins over the plan's embedded
    one; a fingerprint mismatch warns instead of silently ringing."""
    path = str(tmp_path / "plan.json")
    tuner.save_plan(topo_plan, path)
    other = Topology(levels=TOPO.levels[:2])
    tuner.clear_active_plan()
    set_active_topology(other)
    try:
        with pytest.warns(UserWarning, match="topology conflict") as rec:
            tuner.activate_plan_file(path)
        # the warning must name BOTH fingerprints - with only one in
        # the logs a conflict cannot be attributed to either side
        msg = str(rec[0].message)
        assert other.fingerprint() in msg
        assert TOPO.fingerprint() in msg
        assert get_active_topology() is other
    finally:
        tuner.clear_active_plan()
        clear_active_topology()


def test_warn_uncovered_mesh_axes():
    """Topology axis names that don't match the mesh must be surfaced,
    not silently fall back to the untuned flat path."""
    import jax

    from repro.core.topology import warn_uncovered
    mesh = jax.sharding.AbstractMesh((2, 4), ("data", "model"))
    wrong = parse_topology("node:cxl,gpu:ici")
    with pytest.warns(UserWarning, match="data.*model"):
        assert warn_uncovered(wrong, mesh) == ("data", "model")
    right = parse_topology("data:cxl,model:ici")
    assert warn_uncovered(right, mesh) == ()
    # size-1 axes need no level (nothing to communicate over)
    mesh1 = jax.sharding.AbstractMesh((1, 2), ("pod", "data"))
    assert warn_uncovered(parse_topology("data:cxl"), mesh1) == ()


def test_never_slower_than_fixed_per_level(topo_plan):
    """The regret guarantee holds per level against that level's own
    fabric oracle."""
    for (prim, bucket, n, lkey), ch in topo_plan.entries.items():
        level = TOPO.levels[int(lkey.split(":")[0])]
        size = 1 << bucket
        if prim == "p2p":
            # the stage handoff's ring baseline is one direct hop
            t_ring = tuner.predict_level_p2p_time(level, size)
        else:
            t_ring = tuner.predict_level_time(level, prim, n, size)
        assert ch.predicted_time <= t_ring * (1 + 1e-9), (prim, lkey, ch)


# -- plan format versioning (satellite) -----------------------------------

def test_unknown_version_raises_plan_version_error(tmp_path):
    doc = {"version": 99, "fingerprint": "x", "entries": []}
    path = tmp_path / "plan.json"
    path.write_text(json.dumps(doc))
    with pytest.raises(tuner.PlanVersionError) as ei:
        tuner.load_plan(str(path))
    msg = str(ei.value)
    assert "99" in msg and "(1, 2, 3, 4, 5, 6)" in msg
    # PlanVersionError is a ValueError: existing catch sites still work
    assert isinstance(ei.value, ValueError)
    with pytest.raises(tuner.PlanVersionError):
        tuner.Plan.from_json({"entries": []})   # missing version


def test_plan_version_compat_chain(tmp_path):
    """v1 -> v2 -> v3 load compatibility: the same entries doc loads
    under every readable version, with the fields each version lacks
    defaulting (v1: no overlap fields; v1/v2: no level keys)."""
    base_entry = {"primitive": "all_gather", "bucket": 20, "nranks": 3,
                  "backend": "cxl", "slicing_factor": 4,
                  "allreduce_mode": "two_phase",
                  "predicted_time": 1e-3, "baseline_time": 2e-3}
    v1 = {"version": 1, "fingerprint": "f", "meta": {},
          "entries": [dict(base_entry)]}
    p1 = tuner.Plan.from_json(v1)
    ch = p1.entries[("all_gather", 20, 3)]
    assert ch.overlap is False and ch.hidden_time == 0.0
    v2 = {"version": 2, "fingerprint": "f", "meta": {},
          "entries": [dict(base_entry, overlap=True, hidden_time=5e-4)]}
    p2 = tuner.Plan.from_json(v2)
    assert p2.entries[("all_gather", 20, 3)].overlap is True
    v3 = {"version": 3, "fingerprint": "f", "meta": {},
          "entries": [dict(base_entry, overlap=True, hidden_time=5e-4,
                           level="1:abc")]}
    p3 = tuner.Plan.from_json(v3)
    assert ("all_gather", 20, 3, "1:abc") in p3.entries
    # a v3 plan saved today re-loads identically (self round-trip)
    for p in (p1, p2, p3):
        again = tuner.Plan.from_json(p.to_json())
        assert again.entries == p.entries


# -- roofline-derived overlap windows (satellite) -------------------------

def _fake_record(flops, wire, calls):
    return {"status": "ok", "cost": {"flops": flops,
                                     "bytes accessed": 0.0},
            "ledger": {"wire_bytes": wire, "collective_calls": calls}}


def test_overlap_windows_from_dryrun():
    rec = _fake_record(
        flops=197e12,  # exactly 1 s of roofline compute on TPU_V5E
        wire={"all_gather": 3e9, "all_reduce": 1e9},
        calls={"all_gather": 30.0, "all_reduce": 5.0})
    win = tuner.overlap_windows_from_dryrun([rec])
    # compute apportioned by byte share / per-primitive launch count
    assert win("all_gather", 1, 2) == pytest.approx(0.75 / 30)
    assert win("all_reduce", 1, 2) == pytest.approx(0.25 / 5)
    assert win("broadcast", 1, 2) == 0.0     # unseen primitive
    # failed / empty records are skipped
    win2 = tuner.overlap_windows_from_dryrun(
        [{"status": "error"}, _fake_record(0.0, {}, {})])
    assert win2("all_gather", 1, 2) == 0.0


def test_generate_plan_with_derived_windows_marks_overlap():
    rec = _fake_record(flops=197e12, wire={"all_gather": 1e9},
                       calls={"all_gather": 2.0})
    win = tuner.overlap_windows_from_dryrun([rec])
    plan = tuner.generate_plan(
        tuner.TuneGrid(primitives=("all_gather", "broadcast"),
                       sizes=(1 * MiB,), nranks=(3,),
                       slicing_factors=(4,)),
        overlap_compute=win)
    ag = plan.lookup("all_gather", 1 * MiB, 3)
    bc = plan.lookup("broadcast", 1 * MiB, 3)
    assert ag.overlap and ag.hidden_time > 0.0
    assert not bc.overlap                    # zero window for broadcast
    assert plan.meta["overlap_compute_s"] == "per-cell"


# -- Communicator topology resolution -------------------------------------

def test_communicator_topology_resolution(topo_plan):
    from repro.core.api import Communicator
    c = Communicator(backend="cxl", topology=TOPO)
    assert c._topo() is TOPO
    clear_active_topology()
    try:
        assert Communicator(backend="cxl")._topo() is None
        set_active_topology(TOPO)
        assert Communicator(backend="cxl")._topo() is TOPO
        clear_active_topology()
        # auto + topology plan: topology rides in via the plan meta
        c2 = Communicator(backend="auto", plan=topo_plan)
        assert c2._topo() == TOPO
    finally:
        clear_active_topology()


def test_communicator_choice_is_level_aware(topo_plan):
    from repro.core import ledger
    from repro.core.api import Communicator
    comm = Communicator(backend="auto", plan=topo_plan, topology=TOPO)
    ledger.reset()
    # the cxl pool level may resolve to the pool schedule; the ib pod
    # level must ring
    comm._choice("all_reduce", 16 * MiB, 4, TOPO, "node")
    comm._choice("all_reduce", 16 * MiB, 4, TOPO, "pod")
    audit = ledger.snapshot()["auto_choices"]
    assert [a["level"] for a in audit] == ["node", "pod"]
    assert [a["fabric"] for a in audit] == ["cxl", "ib"]
    assert audit[1]["backend"] == "ring"
    want = topo_plan.lookup("all_reduce", 16 * MiB, 4,
                            level=TOPO.level_key("node"))
    assert audit[0]["backend"] == want.backend
    assert audit[0]["predicted_time"] == want.predicted_time
    ledger.reset()


def test_flat_fallback_never_drives_non_pool_fabric():
    """A flat (level-agnostic) plan cell reached through the lookup
    fallback must not drive an ib/ici level with the pool schedule:
    the Communicator coerces it to ring."""
    from repro.core import ledger
    from repro.core.api import Communicator
    flat = tuner.Plan(fingerprint="x")
    flat.add("all_gather", 16 * MiB, 4,
             tuner.Choice(backend="cxl", slicing_factor=8))
    comm = Communicator(backend="auto", plan=flat, topology=TOPO)
    ledger.reset()
    be_pod, _, _, _, _ = comm._choice("all_gather", 16 * MiB, 4, TOPO,
                                      "pod")
    be_gpu, _, _, _, _ = comm._choice("all_gather", 16 * MiB, 4, TOPO,
                                      "gpu")
    be_node, _, _, _, _ = comm._choice("all_gather", 16 * MiB, 4, TOPO,
                                       "node")
    assert (be_pod, be_gpu) == ("ring", "ring")
    assert be_node == "cxl"           # the pool level may keep it
    audit = ledger.snapshot()["auto_choices"]
    assert [a["backend"] for a in audit] == ["ring", "ring", "cxl"]
    ledger.reset()


def test_ledger_level_split():
    from repro.core import ledger
    ledger.reset()
    ledger.record("all_gather", 100.0, level="node", fabric="cxl")
    ledger.record("all_gather", 10.0, level="pod", fabric="ib")
    ledger.record("all_gather", 1.0)   # untagged: flat total only
    snap = ledger.snapshot()
    assert snap["level_wire_bytes"] == {
        "node/cxl": {"all_gather": 100.0},
        "pod/ib": {"all_gather": 10.0}}
    assert snap["total_wire_bytes"] == 111.0
    ledger.reset()
    assert ledger.snapshot()["level_wire_bytes"] == {}
