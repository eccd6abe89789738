"""The reduction from trace intervals and run records to metrics, on
small synthetic traces whose answers are known by hand."""
from __future__ import annotations

import types

import bench_tiny  # noqa: F401  (puts the repo on sys.path)
import numpy as np
import pytest

from bench import trace_reduce as tr
from bench.harness import _module, ROOT


def metric(name):
    return _module(ROOT / "bench" / "metrics" / f"{name}.py")


def test_union_merges_overlaps_and_touching():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)]) == \
        [(0, 4), (5, 7)]
    assert tr.length([(0, 10), (2, 3), (8, 12)]) == 12


def test_subtract_leaves_uncovered_parts():
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert tr.subtract([(0, 10)], [(0, 10)]) == []


def _trace(ops_by_dev, host=(), window=(0, 100)):
    host = [("bench.window",) + tuple(window)] + list(host)
    return tr.Trace(ops=ops_by_dev, modules={}, host=host)


def test_idle_share_is_one_minus_busy_union_averaged_over_chips():
    t = _trace({"/device:TPU:0": [("fusion.1", 0, 40), ("fusion.2", 30, 50)],
                "/device:TPU:1": [("fusion.1", 10, 20)]})
    # chip 0 busy 50 of 100, chip 1 busy 10 of 100
    assert tr.idle_share(t) == pytest.approx((0.5 + 0.9) / 2)
    run = types.SimpleNamespace(trace=t)
    assert metric("idle_share.serve").value(run) == pytest.approx(70.0)


def test_ops_outside_the_window_do_not_count():
    t = _trace({"/device:TPU:0": [("a", -50, 10), ("b", 90, 200)]})
    assert tr.busy_ns(t.ops["/device:TPU:0"], 0, 100) == 20


def test_idle_gaps_are_named_by_the_host_span_over_them():
    t = _trace({"/device:TPU:0": [("a", 0, 10), ("b", 40, 50),
                                  ("c", 55, 100)]},
               host=[("bench.step.admit", 8, 45), ("bench.poll", 50, 54)])
    gaps = tr.idle_gaps(t, "/device:TPU:0")
    assert [g[0] for g in gaps] == ["bench.step.admit", "bench.poll"]
    assert gaps[0][1] == pytest.approx(30e-9)


def test_ops_are_named_by_their_instruction():
    text = ("%all-gather-start.3 = (f32[8]) all-gather-start(f32[2] "
            "%fusion.1), channel_id=2")
    assert tr.op_name(text) == "all-gather-start.3"


def test_self_time_leaves_out_nested_ops():
    evs = [("%while.1 = (s32[]) while()", 0, 100), ("%fusion.1 = f32[]", 10, 30),
           ("%fusion.2 = f32[]", 40, 50), ("%copy.1 = f32[]", 100, 110)]
    assert dict(tr.self_times(evs)) == {"%while.1 = (s32[]) while()": 70,
                                        "%fusion.1 = f32[]": 20,
                                        "%fusion.2 = f32[]": 10,
                                        "%copy.1 = f32[]": 10}
    t = _trace({"/device:TPU:0": evs}, window=(0, 200))
    top = dict(tr.top_ops(t, "/device:TPU:0"))
    assert top == pytest.approx({"while": 70e-9, "fusion": 30e-9,
                                 "copy": 10e-9})


def test_top_ops_group_instances():
    t = _trace({"/device:TPU:0": [("fusion.1", 0, 10), ("fusion.2", 10, 30),
                                  ("copy.1", 30, 35)]})
    top = tr.top_ops(t, "/device:TPU:0")
    assert [n for n, _ in top] == ["fusion", "copy"]
    assert [v for _, v in top] == pytest.approx([30e-9, 5e-9])


def _serve_run(gaps_per_request, window_s=100.0):
    reqs = {}
    for i, gaps in enumerate(gaps_per_request):
        t = list(np.cumsum([0.5] + list(gaps)))
        reqs[f"r{i}"] = {"due": 0.0, "t": t, "tokens": [0] * len(t),
                         "finished": t[-1], "submit": 0.0, "prompt": 8}
    return types.SimpleNamespace(records={"requests": reqs,
                                          "window_s": window_s})


def test_median_gap_is_the_decode_pace_and_skips_late_tokens():
    # 40 requests of 16 gaps at 30 ms; every request has one 400 ms
    # admission stall: 6.25% of all gaps
    gaps = [[0.03] * 15 + [0.4] for _ in range(40)]
    assert metric("itl_p50_ms").value(_serve_run(gaps)) == \
        pytest.approx(30.0)
    # tokens after the window's close are not counted: with the window
    # closing before the last gap of each request, the stalls drop out
    slow = [[0.05] * 3 + [0.03] * 13 for _ in range(40)]
    assert metric("itl_p50_ms").value(_serve_run(slow)) == \
        pytest.approx(30.0)
    assert metric("itl_p50_ms").value(_serve_run(
        slow, window_s=0.5 + 0.15 + 1e-9)) == pytest.approx(50.0)
