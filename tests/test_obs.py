"""Observability subsystem: tracer ring buffer + span nesting, metrics
registry export (JSON-lines / Prometheus), profiler-trace matching and
the device-free step emulator, link-health EWMA detection + recovery,
tuner calibration learn/persist/warm-start, ObsSession end-to-end
artifacts, and the report CLI summary."""
import gzip
import json
import os

import numpy as np
import pytest

from repro import tuner
from repro.core import ledger
from repro.core.hw import MiB
from repro.core.topology import parse_topology
from repro.launch import report
from repro.obs import (HealthMonitor, MetricsRegistry, ObsSession,
                       StepEmulator, calibration_drift, disable_tracing,
                       enable_tracing, from_ledger, profiled_timings,
                       trace_timings)
from repro.obs import metrics as obs_metrics
from repro.obs import profile as obs_profile
from repro.obs import trace as obs_trace
from repro.obs.trace import Tracer
from repro.tuner import costmodel, runtime

TOPO = parse_topology("pod:ib,node:cxl")


@pytest.fixture(autouse=True)
def _clean_obs_state():
    """Tracing hooks, the ledger, and the link-health registry are
    process-global: every test starts and ends detached/empty."""
    disable_tracing()
    ledger.reset()
    runtime.clear_link_health()
    yield
    disable_tracing()
    ledger.reset()
    runtime.clear_link_health()


def _book(seconds=1e-3, *, primitive="all_gather", backend="cxl",
          level="node", fabric="cxl", calls=1.0):
    ledger.record_timing(primitive, 1 * MiB, 4, backend, seconds,
                         slicing_factor=4, allreduce_mode="two_phase",
                         level=level, fabric=fabric, calls=calls)


def _sample(seconds, *, primitive="all_gather", backend="cxl",
            level="node", fabric="cxl", calls=1.0, msg_bytes=1 * MiB,
            nranks=4):
    return {"primitive": primitive, "msg_bytes": msg_bytes,
            "nranks": nranks, "backend": backend, "slicing_factor": 4,
            "allreduce_mode": "two_phase", "level": level,
            "fabric": fabric, "seconds": float(seconds),
            "calls": float(calls)}


# -- tracer / flight recorder ---------------------------------------------

def test_tracer_ring_buffer_keeps_last_steps():
    tr = Tracer(capacity_steps=4)
    tr.enabled = True
    for i in range(10):
        with tr.step(i):
            tr.instant("tick")
    assert tr.steps_retained() == [6, 7, 8, 9]
    doc = tr.dump()
    steps = [e for e in doc["traceEvents"]
             if e.get("cat") == "step"]
    assert [e["args"]["step"] for e in steps] == [6, 7, 8, 9]
    assert doc["metadata"]["capacity_steps"] == 4
    assert doc["metadata"]["steps_retained"] == [6, 7, 8, 9]


def test_tracer_span_nesting_and_containment():
    tr = Tracer()
    tr.enabled = True
    with tr.step(0):
        with tr.span("gather", phase="fwd"):
            with tr.span("inner"):
                pass
    doc = tr.dump()
    by_name = {e["name"]: e for e in doc["traceEvents"]
               if e.get("ph") == "X"}
    step, outer, inner = (by_name["step 0"], by_name["gather"],
                          by_name["inner"])
    assert outer["args"] == {"phase": "fwd"}
    # timestamp containment: step spans the phases, phases nest
    for parent, child in ((step, outer), (outer, inner)):
        assert parent["ts"] <= child["ts"]
        assert (child["ts"] + child["dur"]
                <= parent["ts"] + parent["dur"] + 1e-6)


def _serve_a_little(new_tokens: int = 3):
    """Two requests through a tiny serving engine, to completion."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models import model
    from repro.serving import Request, ServeConfig, ServeEngine
    cfg = get_config("llama3.2-1b", smoke=True)
    params = model.init_params(jax.random.key(0), cfg, tp=1,
                               dtype=jnp.float32)
    eng = ServeEngine(cfg, params, ServeConfig(max_seq=32,
                                               decode_slots=2))
    toks = np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 8))
    for b in range(2):
        eng.submit(Request(id=f"q{b}", tokens=toks[b],
                           max_new_tokens=new_tokens))
    while eng.step():
        pass


def test_span_reaches_profiler_and_flight_recorder(profiled):
    tr = enable_tracing(capacity_steps=4)

    def run():
        with obs_trace.span("serve.x", req="r1", ntok=5):
            _serve_a_little()
    spans = profiled(run)
    assert spans[0][0] == "serve.x"
    assert spans[0][3] == {"req": "r1", "ntok": 5}
    recorded = [e for e in tr.dump()["traceEvents"] if e.get("ph") == "X"]
    assert recorded[-1]["name"] == "serve.x"
    assert recorded[-1]["args"] == {"req": "r1", "ntok": 5}
    assert sorted(e["name"] for e in recorded) == sorted(
        s[0] for s in spans)


def test_engine_steps_keep_the_flight_recorder_bounded():
    tr = enable_tracing(capacity_steps=3)
    _serve_a_little(new_tokens=12)
    disable_tracing()
    # every engine span lies inside a ``serve.step``, the ring's unit,
    # so the last 3 of about 12 steps are all that is kept
    assert len(tr.steps_retained()) == 3
    assert not tr._events
    kept = [e["name"] for e in tr.dump()["traceEvents"]
            if e.get("ph") == "X"]
    assert kept.count("serve.step") == 3
    assert set(kept) == {"serve.step", "serve.decode", "serve.sample"}


def test_spans_with_tracing_off_leave_the_recorder_empty(profiled):
    tr = enable_tracing()
    disable_tracing()
    spans = profiled(_serve_a_little)
    assert {"serve.step", "serve.admit", "serve.decode"} <= {
        s[0] for s in spans}
    assert not tr.dump()["traceEvents"][3:]
    assert tr.steps_retained() == []


def test_tracer_ledger_hook_bridges_collectives(tmp_path):
    tr = enable_tracing(capacity_steps=8)
    with tr.step(3):
        _book(2e-3, calls=2.0)
    doc = tr.dump()
    coll = [e for e in doc["traceEvents"]
            if e.get("cat") == "collective"]
    assert len(coll) == 1
    ev = coll[0]
    assert ev["name"] == "all_gather@cxl [node]"
    assert ev["tid"] == 1 and ev["dur"] == pytest.approx(2e3)
    assert ev["args"]["calls"] == 2.0
    assert ev["args"]["step"] == 3
    # disabled tracer stops receiving (hook detached)
    disable_tracing()
    _book()
    assert sum(1 for e in tr.dump()["traceEvents"]
               if e.get("cat") == "collective") == 1


def test_enable_tracing_twice_does_not_duplicate_hook():
    enable_tracing()
    tr2 = enable_tracing()          # replaces, must unhook the first
    with tr2.step(0):
        _book()
    coll = [e for e in tr2.dump()["traceEvents"]
            if e.get("cat") == "collective"]
    assert len(coll) == 1


def test_tracer_trigger_dumps_anomaly(tmp_path):
    tr = enable_tracing(capacity_steps=4)
    with tr.step(0):
        pass
    out = str(tmp_path / "flight.json")
    tr.trigger("link node/cxl degraded", out)
    assert tr.dumps == 1
    doc = json.load(open(out))
    assert doc["metadata"]["anomalies"][0]["reason"] == \
        "link node/cxl degraded"
    marks = [e for e in doc["traceEvents"] if e.get("cat") == "anomaly"]
    assert marks and marks[0]["ph"] == "i"


# -- metrics registry ------------------------------------------------------

def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("repro_steps_total", "steps")
    c.inc()
    c.inc(2.0, phase="fwd")
    assert reg.value("repro_steps_total") == 1.0
    assert reg.value("repro_steps_total", phase="fwd") == 2.0
    with pytest.raises(ValueError):
        c.inc(-1.0)
    g = reg.gauge("repro_plan_epoch")
    g.set(3)
    g.add(2)
    assert reg.value("repro_plan_epoch") == 5.0
    h = reg.histogram("repro_step_seconds", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    samples = dict(((n, k), v) for n, k, v in h.samples())
    assert samples[("repro_step_seconds_bucket",
                    (("le", "0.1"),))] == 1
    assert samples[("repro_step_seconds_bucket",
                    (("le", "1"),))] == 2          # cumulative
    assert samples[("repro_step_seconds_bucket",
                    (("le", "+Inf"),))] == 3
    assert samples[("repro_step_seconds_count", ())] == 3
    assert samples[("repro_step_seconds_sum", ())] == \
        pytest.approx(5.55)
    # same name, different type: refuse
    with pytest.raises(TypeError):
        reg.gauge("repro_steps_total")
    # idempotent re-registration returns the same family
    assert reg.counter("repro_steps_total") is c


def test_prometheus_and_jsonl_export():
    reg = MetricsRegistry()
    reg.counter("repro_x_total", "help text").inc(3, kind="ag")
    reg.histogram("repro_t_seconds", buckets=(1.0,)).observe(0.5)
    text = reg.to_prometheus()
    assert "# HELP repro_x_total help text" in text
    assert "# TYPE repro_x_total counter" in text
    assert 'repro_x_total{kind="ag"} 3' in text
    assert 'repro_t_seconds_bucket{le="+Inf"} 1' in text
    assert "repro_t_seconds_sum 0.5" in text
    lines = [json.loads(ln) for ln in reg.to_jsonl().splitlines()]
    assert {"name": "repro_x_total", "type": "counter",
            "labels": {"kind": "ag"}, "value": 3.0} in lines


def test_from_ledger_reconciles_with_snapshot():
    snap = {
        "wire_bytes": {"all_gather": 1024.0, "all_reduce": 2048.0},
        "exposed_bytes": {"all_gather": 256.0},
        "hidden_bytes": {"all_gather": 768.0},
        "collective_calls": {"all_gather": 4.0},
        "level_wire_bytes": {"node/cxl": {"all_gather": 1024.0}},
    }
    reg = MetricsRegistry()
    from_ledger(reg, snap)
    assert reg.value("repro_wire_bytes", kind="all_gather") == 1024.0
    assert reg.value("repro_wire_bytes", kind="all_reduce") == 2048.0
    assert reg.value("repro_exposed_bytes", kind="all_gather") == 256.0
    assert reg.value("repro_hidden_bytes", kind="all_gather") == 768.0
    assert reg.value("repro_collective_launches",
                     kind="all_gather") == 4.0
    assert reg.value("repro_level_wire_bytes", level="node",
                     fabric="cxl", kind="all_gather") == 1024.0
    # re-export after a re-trace overwrites (gauges, not counters)
    from_ledger(reg, snap)
    assert reg.value("repro_wire_bytes", kind="all_gather") == 1024.0


def test_observe_timings_histogram_and_busy_counter():
    reg = MetricsRegistry()
    n = obs_metrics.observe_timings(reg, [
        _sample(1e-3, calls=2.0),
        _sample(2e-3, primitive="all_reduce", backend="ring",
                level="pod", fabric="ib"),
    ])
    assert n == 2
    assert reg.value("repro_level_busy_seconds_total", level="node",
                     fabric="cxl") == pytest.approx(2e-3)   # 1e-3 x 2
    assert reg.value("repro_level_busy_seconds_total", level="pod",
                     fabric="ib") == pytest.approx(2e-3)
    hist = reg.histogram("repro_collective_seconds")
    counts = {k: v for name, k, v in hist.samples()
              if name.endswith("_count")}
    key = (("backend", "cxl"), ("level", "node"),
           ("primitive", "all_gather"))
    assert counts[key] == 1


# -- profiler-trace parsing + emulator -------------------------------------

def test_classify_hlo_names():
    assert obs_profile.classify("all-reduce.3") == (True, "all_reduce")
    assert obs_profile.classify("AllGather_7") == (True, "all_gather")
    assert obs_profile.classify("reduce-scatter.0") == \
        (True, "reduce_scatter")
    assert obs_profile.classify("all-to-all.1") == (True, "all_to_all")
    # one cxl collective is a chain of permutes: collective, unmatchable
    assert obs_profile.classify("collective-permute.5") == (True, None)
    assert obs_profile.classify("fusion.12") == (False, None)


def _choices():
    return [
        {"primitive": "all_gather", "msg_bytes": 4 * MiB, "nranks": 4,
         "backend": "cxl", "slicing_factor": 4,
         "allreduce_mode": "two_phase", "level": "node",
         "fabric": "cxl", "calls": 2.0},
        {"primitive": "all_gather", "msg_bytes": 1 * MiB, "nranks": 2,
         "backend": "ring", "slicing_factor": 1,
         "allreduce_mode": "two_phase", "level": "pod", "fabric": "ib",
         "calls": 1.0},
        {"primitive": "all_reduce", "msg_bytes": 1 * MiB, "nranks": 4,
         "backend": "cxl", "slicing_factor": 4,
         "allreduce_mode": "two_phase", "level": "node",
         "fabric": "cxl", "calls": 1.0},
    ]


def test_match_events_walks_expanded_schedule():
    # 3 all_gather launches expected per step: cxl, cxl, ring (calls
    # 2+1); 4 events = one step + cyclic wrap back to the first slot
    events = [{"name": f"all-gather.{i}", "primitive": "all_gather",
               "ts_us": 10.0 * i, "dur_us": 5.0 + i}
              for i in range(4)]
    events.append({"name": "all-reduce.0", "primitive": "all_reduce",
                   "ts_us": 100.0, "dur_us": 7.0})
    events.append({"name": "collective-permute.0", "primitive": None,
                   "ts_us": 200.0, "dur_us": 9.0})
    out = obs_profile.match_events(events, _choices())
    assert len(out) == 5                      # permute chain skipped
    ag = [t for t in out if t["primitive"] == "all_gather"]
    assert [t["msg_bytes"] for t in ag] == \
        [4 * MiB, 4 * MiB, 1 * MiB, 4 * MiB]
    assert [t["backend"] for t in ag] == ["cxl", "cxl", "ring", "cxl"]
    assert all(t["calls"] == 1.0 for t in out)  # one launch per event
    assert ag[0]["seconds"] == pytest.approx(5e-6)
    ar = [t for t in out if t["primitive"] == "all_reduce"]
    assert ar[0]["level"] == "node" and ar[0]["fabric"] == "cxl"


def test_trace_timings_from_gzipped_chrome_trace(tmp_path):
    doc = {"traceEvents": [
        {"ph": "X", "name": "all-reduce.1", "ts": 3.0, "dur": 11.0},
        {"ph": "X", "name": "fusion.2", "ts": 1.0, "dur": 50.0},
        {"ph": "M", "name": "process_name"},
        {"ph": "X", "name": "all-gather.0", "ts": 0.5, "dur": 2.0},
    ]}
    path = str(tmp_path / "t.trace.json.gz")
    with gzip.open(path, "wt") as f:
        json.dump(doc, f)
    out = trace_timings(path, _choices())
    # sorted by ts: the all_gather event lands on the first cxl slot
    assert [t["primitive"] for t in out] == ["all_gather", "all_reduce"]
    assert out[0]["backend"] == "cxl"
    assert out[1]["seconds"] == pytest.approx(11e-6)


def test_profiled_timings_picks_newest_and_books(tmp_path):
    logdir = tmp_path / "prof"
    nested = logdir / "plugins" / "profile" / "run1"
    nested.mkdir(parents=True)
    with open(nested / "host.trace.json", "w") as f:
        json.dump({"traceEvents": [
            {"ph": "X", "name": "all-reduce.0", "ts": 0.0, "dur": 4.0},
        ]}, f)
    out = profiled_timings(str(logdir), _choices(), book=True)
    assert len(out) == 1
    booked = ledger.snapshot()["timings"]
    assert len(booked) == 1
    assert booked[0]["primitive"] == "all_reduce"
    assert booked[0]["seconds"] == pytest.approx(4e-6)
    # empty logdir -> [] (caller falls back to step apportioning)
    assert profiled_timings(str(tmp_path / "nope"), _choices()) == []


def test_step_emulator_prices_with_level_oracle():
    emu = StepEmulator(topology=TOPO, noise_std=0.0, seed=0)
    c = _choices()[0]
    want = costmodel.predict_level_time(
        TOPO.level_for("node"), "all_gather", 4, 4 * MiB,
        backend="cxl", slicing_factor=4, allreduce_mode="two_phase")
    assert emu.time_choice(c) == pytest.approx(want)
    # degrade factors multiply: level axis x fabric kind x wildcard
    emu.set_degrade("node", 4.0)
    emu.set_degrade("cxl", 2.0)
    emu.set_degrade("*", 0.5)
    assert emu.time_choice(c) == pytest.approx(want * 4.0)
    emu.set_degrade("node", 1.0)          # factor 1.0 clears the key
    assert "node" not in emu.degrade
    samples = emu.step_timings(_choices())        # books by default
    assert [t["calls"] for t in samples] == [2.0, 1.0, 1.0]
    assert len(ledger.snapshot()["timings"]) == 3


def test_step_emulator_noise_is_seeded():
    a = StepEmulator(topology=TOPO, noise_std=0.1, seed=7)
    b = StepEmulator(topology=TOPO, noise_std=0.1, seed=7)
    ta = [a.time_choice(c) for c in _choices()]
    tb = [b.time_choice(c) for c in _choices()]
    assert ta == tb
    base = StepEmulator(topology=TOPO).time_choice(_choices()[0])
    assert ta[0] != pytest.approx(base)


# -- link health -----------------------------------------------------------

def test_health_monitor_flags_and_recovers():
    mon = HealthMonitor(threshold=2.0, patience=2, warmup_steps=2,
                        publish=False)
    events = []
    for step in range(20):
        slow = 8 <= step < 12
        t = [_sample(4e-3 if slow else 1e-3),
             _sample(1e-3, primitive="all_reduce", backend="ring",
                     level="pod", fabric="ib")]
        events += mon.observe_step(t, step)
    kinds = [(e["event"], e["link"], e["step"]) for e in events]
    assert ("degraded", "node/cxl", 9) in kinds     # patience=2 -> step 9
    assert any(e[0] == "recovered" and e[1] == "node/cxl"
               for e in kinds)
    assert all(e[1] == "node/cxl" for e in kinds)   # ib never flagged
    deg = next(e for e in events if e["event"] == "degraded")
    assert deg["since_step"] == 8
    assert deg["slowdown"] > 2.0
    assert mon.degraded_links() == []               # recovered by end
    assert mon.report()["node/cxl"]["degraded"] is False


def test_health_baseline_frozen_while_outlying():
    """A persistent slowdown must not launder itself into the baseline:
    with the degradation never lifted, the link stays flagged."""
    mon = HealthMonitor(threshold=2.0, patience=2, warmup_steps=2,
                        publish=False)
    for step in range(30):
        mon.observe_step([_sample(1e-3 if step < 5 else 5e-3)], step)
    assert mon.degraded_links() == ["node/cxl"]
    assert mon.report()["node/cxl"]["slowdown"] > 2.0


def test_health_exports_gauges_and_registry():
    reg = MetricsRegistry()
    mon = HealthMonitor(threshold=2.0, patience=1, warmup_steps=1,
                        registry=reg)
    for step in range(6):
        mon.observe_step([_sample(1e-3 if step < 4 else 9e-3)], step)
    assert reg.value("repro_link_health", level="node",
                     fabric="cxl") == 0.0
    assert reg.value("repro_link_slowdown_ratio", level="node",
                     fabric="cxl") > 2.0
    # published into the plan registry for planners / dry-run reports
    assert runtime.degraded_links() == ["node/cxl"]
    assert runtime.get_link_health("node/cxl")["degraded"] is True


def test_health_ignores_idle_links_and_warmup():
    mon = HealthMonitor(threshold=2.0, patience=1, warmup_steps=3,
                        publish=False)
    # huge jump inside warmup: never flagged
    ev = mon.observe_step([_sample(1e-3)], 0)
    ev += mon.observe_step([_sample(50e-3)], 1)
    assert ev == []
    assert mon.observe_step([], 2) == []            # idle step is a no-op


def test_calibration_drift_flags_both_directions():
    cal = {"levels": [
        {"backend": "cxl", "level": "1:abc", "scale": 4.0,
         "samples": 12.0},
        {"backend": "ring", "level": "0:def", "scale": 1.1,
         "samples": 9.0},
        {"backend": "ring", "level": None, "scale": 0.5,
         "samples": 4.0},
    ]}
    hits = calibration_drift(cal, threshold=1.5)
    assert [(h["backend"], h["scale"]) for h in hits] == \
        [("cxl", 4.0), ("ring", 0.5)]
    assert all("placement" in h["recommendation"] for h in hits)
    assert calibration_drift({}, threshold=1.5) == []
    with pytest.raises(ValueError):
        calibration_drift(cal, threshold=1.0)


# -- tuner calibration: learn -> persist -> warm-start ---------------------

def test_calibration_learns_persists_and_warm_starts():
    plan = tuner.generate_plan(tuner.TuneGrid(
        primitives=("all_gather",), sizes=(1 * MiB,), nranks=(4,),
        slicing_factors=(4,), allreduce_modes=("two_phase",)))
    ch = plan.lookup("all_gather", 1 * MiB, 4)
    oracle = costmodel.predict_time(
        ch.backend, "all_gather", 4, 1 * MiB,
        slicing_factor=ch.slicing_factor,
        allreduce_mode=ch.allreduce_mode)
    ot = tuner.OnlineTuner(plan, min_samples=2)
    ot.observe("all_gather", 1 * MiB, 4, ch.backend, 4.0 * oracle,
               slicing_factor=ch.slicing_factor,
               allreduce_mode=ch.allreduce_mode)
    # below cal_min_samples the scale stays neutral
    assert ot.cal_scale(ch.backend, None, "all_gather") == 1.0
    ot.observe("all_gather", 1 * MiB, 4, ch.backend, 4.0 * oracle,
               slicing_factor=ch.slicing_factor,
               allreduce_mode=ch.allreduce_mode)
    assert ot.cal_scale(ch.backend, None, "all_gather") == \
        pytest.approx(4.0, rel=1e-6)
    exp = ot.calibration_export()
    assert exp["scales"][0]["scale"] == pytest.approx(4.0, rel=1e-6)
    assert exp["levels"][0]["backend"] == ch.backend
    refreshed = ot.refresh()
    assert refreshed.meta["calibration"]["scales"]
    # a fresh tuner over the refreshed plan starts corrected
    ot2 = tuner.OnlineTuner(refreshed, min_samples=2)
    assert ot2.cal_scale(ch.backend, None, "all_gather") == \
        pytest.approx(4.0, rel=1e-6)


# -- ObsSession end-to-end -------------------------------------------------

def test_obs_session_end_to_end(tmp_path):
    metrics_out = str(tmp_path / "run.jsonl")
    trace_out = str(tmp_path / "run.trace.json")
    sess = ObsSession(metrics_out=metrics_out, trace_out=trace_out,
                      trace_steps=8, threshold=2.0, patience=1,
                      warmup_steps=2, log=lambda *_: None)
    for step in range(8):
        slow = step >= 6
        with sess.step_span(step):
            with sess.span("sync", phase="bwd"):
                _book(8e-3 if slow else 1e-3)
        timings = ledger.snapshot()["timings"]
        sess.on_step(step, 0.01, timings=timings,
                     extra={"loss": 2.5})
        ledger.clear_timings()
    sess.on_retune(epoch=2, swapped=True, regret_s=1.5e-4,
                   measured_cells=3)
    summary = sess.finalize(snapshot=ledger.snapshot(),
                            extra={"steps": 8})
    assert summary["degraded_links"] == ["node/cxl"]
    assert summary["steps"] == 8
    assert sess.finalize() == {}                    # idempotent

    events = report.load_events(metrics_out)
    kinds = {e["kind"] for e in events}
    assert {"step", "retune", "health", "metric", "summary"} <= kinds
    steps = [e for e in events if e["kind"] == "step"]
    assert len(steps) == 8 and steps[0]["loss"] == 2.5
    assert steps[0]["timing_samples"] == 1
    health = [e for e in events if e["kind"] == "health"]
    assert health[0]["link"] == "node/cxl"
    assert health[0]["event"] == "degraded"
    retune = next(e for e in events if e["kind"] == "retune")
    assert retune == {"kind": "retune", "epoch": 2, "swapped": True,
                      "regret_s": 1.5e-4, "measured_cells": 3}
    metric = {(e["name"], tuple(sorted(e["labels"].items())))
              : e["value"] for e in events if e["kind"] == "metric"}
    assert metric[("repro_steps_total", ())] == 8.0
    assert metric[("repro_retune_swaps_total", ())] == 1.0
    assert metric[("repro_plan_epoch", ())] == 2.0

    # Prometheus rendering lands next to the jsonl
    prom = open(str(tmp_path / "run.prom")).read()
    assert "repro_steps_total 8" in prom
    assert "# TYPE repro_step_seconds histogram" in prom

    # the degradation triggered an immediate flight-recorder dump, and
    # finalize wrote the final trace
    doc = json.load(open(trace_out))
    assert doc["metadata"]["anomalies"]
    assert "degraded" in doc["metadata"]["anomalies"][0]["reason"]
    assert any(e.get("cat") == "collective"
               for e in doc["traceEvents"])


def test_obs_session_disabled_is_inert(tmp_path):
    sess = ObsSession(log=lambda *_: None)
    assert not sess.enabled
    with sess.step_span(0):
        with sess.span("x"):
            pass
    assert sess.on_step(0, 0.1, timings=[_sample(1.0)]) == []
    sess.on_retune(epoch=1, swapped=False)
    assert sess.finalize() == {}
    assert list(tmp_path.iterdir()) == []


# -- report CLI ------------------------------------------------------------

def test_report_summarize(tmp_path):
    metrics_out = str(tmp_path / "run.jsonl")
    sess = ObsSession(metrics_out=metrics_out, threshold=2.0,
                      patience=1, warmup_steps=2, log=lambda *_: None)
    for step in range(6):
        t = [_sample(6e-3 if step >= 4 else 1e-3, calls=2.0)]
        sess.on_step(step, 0.5 if step == 0 else 0.01, timings=t)
    sess.finalize(snapshot={"wire_bytes": {"all_gather": 4096.0}})
    text = report.summarize(report.load_events(metrics_out))
    assert "steps: 6" in text
    assert "(first step 0.50s, incl. compile)" in text
    assert "all_gather@cxl [node]" in text
    assert "node/cxl" in text
    assert "health: link node/cxl degraded" in text
    assert "degraded links at exit: ['node/cxl']" in text
    assert "trace-time wire bytes/step" in text


def test_obs_session_diag_routes_to_report(tmp_path):
    """A launcher diagnostic routed through ``diag`` is counted,
    persisted as a kind=diag event, and surfaced by the report."""
    metrics_out = str(tmp_path / "run.jsonl")
    logged = []
    sess = ObsSession(metrics_out=metrics_out, health=False,
                      log=logged.append)
    sess.diag("serve", "plan loaded but the engine is unsharded")
    sess.finalize()
    assert logged[0].startswith("[serve] plan loaded")
    events = report.load_events(metrics_out)
    diag = next(e for e in events if e.get("kind") == "diag")
    assert diag["source"] == "serve"
    counted = next(e for e in events
                   if e.get("kind") == "metric"
                   and e["name"] == "repro_diag_total")
    assert counted["value"] == 1
    text = report.summarize(events)
    assert "diagnostics: 1" in text
    assert "[serve] plan loaded but the engine is unsharded" in text


def test_report_summarize_trace(tmp_path):
    tr = enable_tracing(capacity_steps=4)
    with tr.step(0):
        _book()
    tr.trigger("test anomaly")
    path = str(tmp_path / "t.json")
    tr.dump(path)
    text = report.summarize_trace(path)
    assert "steps retained [0]" in text
    assert "1 collective slices" in text
    assert "test anomaly" in text
