"""Runs one cell of ``BENCHMARK.json`` once and prints its result line.

Everything that belongs to one cell is found by name:

* ``bench/configs/<config>.json``: sizes, precision, ``driver``;
* ``bench/traffic/<traffic>.json``: the generator's parameters;
* ``bench/drivers/<driver>.py``: the loop for that kind of cell, a
  ``run(ctx) -> dict``;
* ``bench/metrics/<metric>.py``: a ``value(run) -> float | None``;
* ``bench/peaks.json``: the chip's peaks, by ``device_kind``.

So a new cell, configuration, mix or metric is new files and a
``workloads`` entry.  ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from a traced run.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell needs."""


# --------------------------------------------------------------------- #
# finding a cell's files
# --------------------------------------------------------------------- #

def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def _module(path: Path):
    name = "bench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    workload: dict
    config: dict           # the configuration file's contents
    traffic: dict          # the traffic file's contents
    driver: object         # the driver module
    metrics: list          # [(entry, module)] for this cell and --trace


def applies(metric: dict, workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


def resolve(bench: dict, name: str, trace: bool,
            root: Path = ROOT) -> Cell:
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(has {sorted(work)})")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = load_json(root / conf["file"])
    bdir = root / "bench"
    traffic = load_json(bdir / "traffic" / f"{w['traffic']}.json")
    driver = _module(bdir / "drivers" / f"{config['driver']}.py")
    kind = "per_layer" if trace else "end_to_end"
    metrics = [(m, _module(bdir / "metrics" / f"{m['name']}.py"))
               for m in bench[kind] if applies(m, name)]
    return Cell(w, config, traffic, driver, metrics)


# --------------------------------------------------------------------- #
# what a run measures besides its metrics
# --------------------------------------------------------------------- #

class CompileClock:
    """Seconds JAX spends in XLA compiles (a persistent-cache load is
    timed in their place), their number and the cache hits, since the
    last ``take``.  One per process: listeners cannot be removed."""

    def __init__(self):
        import jax
        self.seconds, self.compiles, self.hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def take(self) -> tuple:
        out = (self.seconds, self.compiles, self.hits)
        self.seconds, self.compiles, self.hits = 0.0, 0, 0
        return out


_CLOCK = None


def compile_clock() -> CompileClock:
    global _CLOCK
    if _CLOCK is None:
        _CLOCK = CompileClock()
    return _CLOCK


def peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
               for d in devices)


def chips(n: int, require: bool = True) -> list:
    """The first ``n`` accelerator devices; ``NoChip`` without them."""
    import jax
    devs = jax.devices()
    if require and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX sees {len(devs)} {devs[0].platform} "
                     f"device(s)")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX sees {len(devs)}")
    return devs[:n]


@dataclasses.dataclass
class Context:
    """What a driver gets."""
    name: str
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace_dir: str | None     # where to write the traced window, or None
    devices: list
    peaks: dict | None        # this chip's row of peaks.json
    t_start: float            # perf_counter at process start
    clock: CompileClock
    log: object = print


@dataclasses.dataclass
class Run:
    """What a metric reads: the driver's records and the trace."""
    ctx: Context
    out: dict                 # the driver's result
    trace: object = None      # trace_reduce.Trace of the traced window

    @property
    def records(self) -> dict:
        return self.out["records"]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def result_line(ctx: Context, cell: Cell, out: dict, trace) -> dict:
    """The contract's JSON object."""
    import jax
    run = Run(ctx, out, trace)
    metrics = {}
    for m, mod in cell.metrics:
        v = mod.value(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    dev = ctx.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": device}
    if trace is not None and trace.ops:
        from bench import trace_reduce as tr
        lo, hi = trace.window()
        busy = [tr.busy_ns(ops, lo, hi) for ops in trace.ops.values()]
        device["busy_s"] = sum(busy) / len(busy) * 1e-9
        device["window_s"] = (hi - lo) * 1e-9
        first = sorted(trace.ops)[0]
        line["breakdown"] = {
            "device_ops": tr.top_ops(trace, first),
            "idle_gaps": [list(g) for g in tr.idle_gaps(trace, first)]}
    line["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                      for c in out["checks"]}
    return line


def run_cell(bench: dict, name: str, seed: int, seconds: float,
             trace: bool, t_start: float, require_chip: bool = True,
             root: Path = ROOT) -> dict:
    """One run of one cell; returns the result line."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    cell = resolve(bench, name, trace, root)
    devices = chips(cell.workload["chips"], require_chip)
    cache = enable_compile_cache()
    # cache every program, however fast it compiles: the window's eager
    # ops then load in set-up instead of compiling there
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    peaks = load_json(root / "bench" / "peaks.json")
    kind = devices[0].device_kind
    if require_chip and kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    trace_dir = None
    if trace:
        trace_dir = str(root / "bench" / "out" / "trace" / name)
        shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = Context(name=name, config=cell.config, traffic=cell.traffic,
                  seed=int(seed), seconds=float(seconds),
                  trace_dir=trace_dir, devices=devices,
                  peaks=peaks.get(kind), t_start=t_start,
                  clock=compile_clock(), log=log)
    log(f"{name}: {kind} x{len(devices)}, seed {seed}, {seconds} s, "
        f"trace {int(trace)}, compile cache {cache}")
    out = cell.driver.run(ctx)
    tr = None
    if trace:
        from bench import trace_reduce
        tr = trace_reduce.load(trace_dir)
    line = result_line(ctx, cell, out, tr)
    for c in out["checks"]:
        log(f"check {c['name']}: {c['value']!r} limit {c['limit']!r}")
    return line


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        line = run_cell(benchmark(), args.workload, args.seed,
                        args.seconds, bool(args.trace), t_start)
    except NoChip as e:
        log(f"bench: {e}; nothing was run")
        return 3
    print(json.dumps(line), flush=True)
    return 0
