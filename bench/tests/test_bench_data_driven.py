"""The harness finds a cell's files by name, so a new cell is new files
and a ``workloads`` entry; and it refuses to report without a chip."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import bench_tiny
import pytest

from bench import harness

ROOT = harness.ROOT
BENCH = harness.benchmark()
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
@pytest.mark.parametrize("trace", [False, True])
def test_every_workload_resolves_its_files(w, trace):
    cell = harness.resolve(BENCH, w["name"], trace)
    assert cell.config["driver"] == "serve"
    assert hasattr(cell.driver, "run")
    kind = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in BENCH[kind] if harness.applies(m, w["name"])}
    assert {m["name"] for m, _ in cell.metrics} == want
    assert all(hasattr(mod, "value") for _, mod in cell.metrics)
    if not trace:
        assert "setup_s" in want and len(want) >= 2
    else:
        assert want


def test_benchmark_json_keeps_to_its_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.fullmatch(n) for n in names)
    configs = {c["name"] for c in BENCH["configs"]}
    used = {w["config"] for w in BENCH["workloads"]}
    assert configs == used
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and len(c["why"]) <= 200
        assert c["reduced"] == harness.load_json(ROOT / c["file"])["reduced"]
    cells = {w["name"] for w in BENCH["workloads"]}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.fullmatch(m["unit"]) and m["better"] in ("lower",
                                                             "higher")
        assert set(m.get("workloads", [])) <= cells
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def _tmp_checkout(tmp_path):
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    return root


def test_a_new_cell_is_new_files_and_a_workloads_entry(tmp_path,
                                                       monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    root = _tmp_checkout(tmp_path)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    cfg = bench_tiny.serve_config()
    (root / "bench/configs/tiny.serve.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/tiny-mix.json").write_text(
        json.dumps(bench_tiny.MIX))
    (root / "bench/metrics/tiny_requests_done.py").write_text(
        "def value(run):\n"
        "    return sum(r['finished'] is not None\n"
        "               for r in run.records['requests'].values())\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny.serve", "source": "test",
                             "file": "bench/configs/tiny.serve.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.cell", "config": "tiny.serve",
                               "traffic": "tiny-mix", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({"name": "tiny_requests_done", "unit": "1",
                                "better": "higher", "bound": 0.25,
                                "source": "host_clock",
                                "workloads": ["tiny.cell"]})
    line = harness.run_cell(bench, "tiny.cell", seed=2**31 + 3, seconds=1.5,
                            trace=False, t_start=0.0, require_chip=False,
                            root=root)
    assert line["correct"] is True
    assert line["metrics"]["tiny_requests_done"]["value"] > 0
    assert {"setup_s", "tiny_requests_done"} <= set(line["metrics"])
    assert list(line)[-1] == "checks"
    after = {p: p.read_bytes() for p in before}
    assert after == before


def _run_py(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", str(2**31 + 1),
         "--seconds", "1", "--trace", "0"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_no_chip_means_no_result():
    p = _run_py(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    root = _tmp_checkout(tmp_path)
    p = _run_py(root)
    assert p.returncode != 0 and p.stdout.strip() == ""
