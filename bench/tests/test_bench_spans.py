"""The readers of the serving engine's spans, on a synthetic trace whose
answers are worked out by hand, and on one traced run of a tiny serving
cell on the CPU."""
from __future__ import annotations

import json
import shutil
import types

import bench_tiny
import pytest

from bench import harness, spans
from bench import trace_reduce as tr
from bench.harness import ROOT, _module

NEW = ("admit_prefill_ms", "admit_codec_ms", "admit_idle_ms",
       "decode_sample_ms", "decode_idle_ms")
MS = 1_000_000           # the synthetic times below are in ms


def _s(name, t0, t1, **stats):
    return spans.Span(name, t0 * MS, t1 * MS, stats)


# The window is 0-1000 ms.  Step A admits (prefill, codec and its first
# token) and decodes; B and D only decode; C admits with no codec and no
# decode.  The last two steps lie partly or wholly outside the window.
SPANS = [
    _s("serve.step", 10, 300),
    _s("serve.admit", 20, 250, req="r1", tokens=64),
    _s("serve.prefill", 25, 100),
    _s("serve.kv.extract", 100, 140),
    _s("serve.kv.insert", 140, 180),
    _s("serve.sample", 185, 195),
    _s("serve.decode", 260, 290),
    _s("serve.sample", 291, 295),
    _s("serve.step", 400, 500),
    _s("serve.decode", 405, 480),
    _s("serve.sample", 482, 490),
    _s("serve.step", 600, 700),
    _s("serve.admit", 610, 630, req="r2", tokens=64),
    _s("serve.step", 800, 900),
    _s("serve.decode", 805, 870),
    _s("serve.sample", 872, 880),
    _s("serve.step", 990, 1050),
    _s("serve.decode", 995, 1040),
    _s("serve.step", 1100, 1200),
    _s("serve.prefill", 1120, 1130),
]
OPS = {"/device:TPU:0": [("a", 0, 30 * MS), ("b", 30 * MS, 100 * MS),
                         ("c", 150 * MS, 200 * MS), ("d", 265 * MS, 288 * MS),
                         ("e", 405 * MS, 470 * MS), ("f", 805 * MS, 860 * MS),
                         ("g", 995 * MS, 1040 * MS)],
       "/device:TPU:1": [("a", 0, 1000 * MS)]}
# The prefill program ran 30-100 ms, inside the window's one prefill
# span, and 1121-1129 ms, inside the one outside the window.
MODULES = {"/device:TPU:0": [("jit_prefill_impl(3)", 30 * MS, 100 * MS),
                             ("jit_step_impl(4)", 265 * MS, 288 * MS),
                             ("jit_prefill_impl(3)", 1121 * MS, 1129 * MS)],
           "/device:TPU:1": [("jit_prefill_impl(3)", 0, 1000 * MS)]}


def _run(tmp_path, monkeypatch, ops=OPS, modules=MODULES):
    (tmp_path / "host.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(spans, "load", lambda path: SPANS)
    trace = tr.Trace(ops=ops, modules=modules,
                     host=[("bench.window", 0, 1000 * MS)])
    return types.SimpleNamespace(trace=trace, ctx=types.SimpleNamespace(
        trace_dir=str(tmp_path)))


def metric(name):
    return _module(ROOT / "bench" / "metrics" / f"{name}.py")


def test_readers_by_hand(tmp_path, monkeypatch):
    run = _run(tmp_path, monkeypatch)
    got = {n: metric(n).value(run) for n in NEW}
    assert got == pytest.approx({
        # the prefill program's device time in the window's one prefill
        "admit_prefill_ms": 70.0,
        # admissions' codec time: 40 + 40 and 0
        "admit_codec_ms": 40.0,
        # idle inside the admissions: 230 - (10 + 70 + 50) and 20
        "admit_idle_ms": 60.0,
        # the three decode steps' samples, not the first token's
        "decode_sample_ms": 8.0,
        # steps B and D: 100 - 65 and 100 - 55; A and C admitted
        "decode_idle_ms": 40.0})


def test_no_tpu_plane_no_device_time_and_no_spans_nothing(tmp_path,
                                                          monkeypatch):
    run = _run(tmp_path, monkeypatch, ops={}, modules={})
    for n in ("admit_prefill_ms", "admit_idle_ms", "decode_idle_ms"):
        assert metric(n).value(run) is None
    assert metric("admit_codec_ms").value(run) == pytest.approx(40.0)
    monkeypatch.setattr(spans, "load", lambda path: [])
    assert all(metric(n).value(run) is None for n in NEW)
    untraced = types.SimpleNamespace(trace=None, ctx=run.ctx)
    assert all(metric(n).value(untraced) is None for n in NEW)


def test_idle_inside_a_span():
    span = spans.Span("serve.step", 10, 20, {})
    assert spans.idle_ns(span, [(0, 100)]) == 0
    assert spans.idle_ns(span, [(0, 5), (30, 40)]) == 10
    assert spans.idle_ns(span, [(0, 12), (15, 17), (18, 30)]) == 4


def test_traced_tiny_serving_run_reads_the_engine_spans(tmp_path,
                                                         monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    root = tmp_path / "checkout"
    root.mkdir()
    bench = harness.benchmark()
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    (root / "bench/configs/tiny.serve.json").write_text(
        json.dumps(bench_tiny.serve_config()))
    (root / "bench/traffic/tiny-mix.json").write_text(
        json.dumps(bench_tiny.MIX))
    bench["configs"].append({"name": "tiny.serve", "source": "test",
                             "file": "bench/configs/tiny.serve.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.cell", "config": "tiny.serve",
                               "traffic": "tiny-mix", "chips": 1,
                               "why": "test"})
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            m["workloads"].append("tiny.cell")
    line = harness.run_cell(bench, "tiny.cell", seed=2**31 + 77,
                            seconds=1.5, trace=True, t_start=0.0,
                            require_chip=False, root=root)
    assert line["correct"] is True
    got = line["metrics"]
    for n in ("admit_codec_ms", "decode_sample_ms"):
        assert got[n]["value"] > 0 and got[n]["unit"] == "ms"
    # the CPU trace has no TPU plane: no device time, no idle time
    for n in ("admit_prefill_ms", "admit_idle_ms", "decode_idle_ms"):
        assert n not in got
