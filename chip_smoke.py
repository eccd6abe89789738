#!/usr/bin/env python3
"""Bring-up check: llama3.2-1b trains and serves on a TPU.

    python chip_smoke.py             # one chip: phases train, serve
    python chip_smoke.py --chips 4   # four chips: phase cxl_vs_ring only

* ``train`` - llama3.2-1b at its published widths and whole vocabulary,
  cut to 4 of its 16 layers, through ``make_sharded_train_step`` on a
  1x1 (data, model) mesh with the launcher's defaults (fp32 params,
  AdamW, 25 MiB buckets, prefetch 1): 5 steps on one fixed seeded batch
  of 4 x 1024 tokens.  Every loss must be finite and the last below
  the first.
* ``serve`` - the whole 16-layer model behind ``ServeEngine``
  (``submit``/``step``/``poll``): 8 greedy requests of 128 prompt and 16
  new tokens over 4 decode slots.  Every request must finish with 16
  tokens, and the engine's last decode logits of one request must match
  a plain full forward pass over the same tokens.
* ``cxl_vs_ring`` (``--chips 4``) - the 4-layer cut on a (data=2,
  model=2) mesh: 3 steps with ``backend=cxl`` (the paper's chunked
  ppermute schedules), then 3 with ``backend=ring`` (the ``jax.lax``
  collectives), from the same seed and batch.  Losses and parameters
  must agree, and every parameter must be sharded over all 4 devices.

Earlier lines report the device, compile seconds per phase (XLA
compiles and persistent-cache loads), step and request wall times taken
after ``block_until_ready``, losses and peak device memory.  The last
line is the JSON result.  A failed check raises: no result line, and a
non-zero exit.  Without a TPU the script exits non-zero before it runs
anything.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.configs.llama3p2_1b import CONFIG  # noqa: E402
from repro.data.pipeline import batch_for  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import model  # noqa: E402
from repro.models.config import dense_pattern  # noqa: E402
from repro.models.pcontext import UNSHARDED  # noqa: E402
from repro.serving import (Request, SamplingParams, ServeConfig,  # noqa: E402
                           ServeEngine)
from repro.serving.scheduler import FINISHED  # noqa: E402
from repro.training.train_loop import (TrainConfig,  # noqa: E402
                                       init_sharded_state,
                                       make_sharded_train_step,
                                       named_shardings)

# fp32 params plus two fp32 AdamW moments of all 16 layers need ~19.8 GB,
# more than the 16 GB of one v5e chip; 4 layers keep every width and
# the whole vocabulary (5.65 GiB of state; the compiler plans 13.4 GiB
# for a step of 4 x 1024 tokens, and a v5e peaks at 12.2e9 bytes).
TRAIN_LAYERS = 4
SEED = 0

# Engine decode vs a full forward of the same tokens.  Both run fp32
# weights through the TPU's default matmul precision: one bf16 pass, so
# every operand is rounded to 8 mantissa bits (2^-9 relative).  The two
# paths round differently scaled values (decode takes one softmax over
# the whole cache, the forward an online softmax per key block), so
# their rounding errors are independent; after 16 layers the last
# logits differ by 8.1e-3 of the largest logit on a TPU v5e.  The bound
# is 3x that.  A wrong input moves them by O(1) of their range: the
# check also feeds the forward a different last token and requires the
# bound to reject that.
LOGIT_RTOL = 2.5e-2

# cxl vs ring: the same math with collectives summed in another order
# (f32).  Three AdamW steps at lr <= 3e-4 move an element by at most
# ~lr each, so even an element whose update the reordering flips (a
# near-zero gradient changing sign) differs by < 3 * 2 * 3e-4 = 1.8e-3;
# 5e-3 is the band tests/_mesh_runner.py holds the sharded train step to.
CXL_RING_TOL = 5e-3


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {msg}")


class _CompileClock:
    """Seconds JAX spends in XLA compiles (a persistent-cache load is
    timed in their place) and the number of cache hits, since the last
    ``take``."""

    def __init__(self):
        self.seconds, self.hits = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def take(self) -> tuple:
        out = (self.seconds, self.hits)
        self.seconds, self.hits = 0.0, 0
        return out


_CLOCK = None   # one listener per process: jax.monitoring cannot drop one


def _clock() -> _CompileClock:
    global _CLOCK
    if _CLOCK is None:
        _CLOCK = _CompileClock()
    return _CLOCK


def _peak_bytes(device) -> int:
    return int((device.memory_stats() or {}).get("peak_bytes_in_use", -1))


def train_cut(layers: int = TRAIN_LAYERS):
    """llama3.2-1b at its published widths, cut to ``layers`` layers."""
    return dataclasses.replace(CONFIG, n_layers=layers,
                               layer_pattern=dense_pattern(layers))


def train_phase(cfg, mesh, backend: str, batch: int, seq: int,
                steps: int, seed: int = SEED, name: str = "train") -> dict:
    """Run ``steps`` sharded train steps on one fixed seeded batch."""
    clock = _clock()
    clock.take()
    # the launcher's TrainConfig for ``--steps steps --backend backend``
    tcfg = TrainConfig(lr=3e-4, warmup=min(20, steps // 5),
                       total_steps=steps, backend=backend,
                       clip_norm=None, bucket_mb=25.0, prefetch=1)
    step, pspecs, bspecs, _ = make_sharded_train_step(
        cfg, tcfg, mesh, dp_axis=("data",))
    params, opt = init_sharded_state(cfg, mesh, pspecs,
                                     jax.random.key(seed),
                                     tp=mesh.shape["model"])
    toks = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (batch, seq + 1))
    data = jax.device_put(batch_for(cfg, toks),
                          named_shardings(mesh, bspecs))
    compiled = step.lower(params, opt, data).compile()
    compile_s, hits = clock.take()
    log(f"{name}: compile_s {compile_s:.2f} (persistent cache hits "
        f"{hits})")
    losses, step_s = [], []
    for i in range(steps):
        ts = time.perf_counter()
        params, opt, metrics = compiled(params, opt, data)
        jax.block_until_ready((params, opt, metrics))
        step_s.append(time.perf_counter() - ts)
        losses.append(float(metrics["loss"]))
        log(f"{name}: step {i} loss {losses[-1]:.6f} "
            f"wall_s {step_s[-1]:.4f}")
    check(all(math.isfinite(x) for x in losses),
          f"{name}: non-finite loss in {losses}")
    return {"losses": losses, "step_s": step_s, "compile_s": compile_s,
            "params": params}


def serve_phase(cfg, n_requests: int, prompt_len: int, new_tokens: int,
                slots: int, seed: int = SEED) -> dict:
    """Serve ``n_requests`` greedy requests through the engine and
    check one request's decode logits against a full forward pass."""
    clock = _clock()
    clock.take()
    params = jax.jit(lambda k: model.init_params(
        k, cfg, tp=1, dtype=jnp.float32))(jax.random.key(seed))
    scfg = ServeConfig(max_seq=prompt_len + new_tokens + 8,
                       decode_slots=slots)
    eng = ServeEngine(cfg, params, scfg)
    prompts = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (n_requests, prompt_len))
    ids = [eng.submit(Request(
        id=f"req{i}", tokens=p,
        sampling=SamplingParams(temperature=0.0, seed=seed + i),
        max_new_tokens=new_tokens)) for i, p in enumerate(prompts)]
    probe = ids[0]
    tokens = {rid: [] for rid in ids}
    finished_s, step_s, probe_row = {}, [], None
    t0 = time.perf_counter()
    while True:
        ts = time.perf_counter()
        more = eng.step()
        jax.block_until_ready(eng.caches)
        step_s.append(time.perf_counter() - ts)
        if probe in eng.last_logits:
            probe_row = np.array(eng.last_logits[probe])
        for rid, (status, fresh) in eng.poll().items():
            tokens[rid] += fresh
            if status == FINISHED:
                finished_s[rid] = time.perf_counter() - t0
        if not more:
            break
    compile_s, hits = clock.take()
    # the first step prefills and compiles; the rest decode
    log(f"serve: compile_s {compile_s:.2f} (persistent cache hits "
        f"{hits}); {len(step_s)} engine steps, first "
        f"{step_s[0]:.4f} s, median of the rest "
        f"{float(np.median(step_s[1:])):.4f} s")
    for rid in ids:
        log(f"serve: {rid} finished after {finished_s.get(rid, -1):.4f} s "
            f"with {len(tokens[rid])} tokens")
    check(set(finished_s) == set(ids),
          f"unfinished requests {set(ids) - set(finished_s)}")
    check(all(len(t) == new_tokens for t in tokens.values()),
          f"token counts {[len(t) for t in tokens.values()]}")

    # the probe's last decode step fed its 15th generated token and
    # produced the logits of its 16th: a full forward over the prompt
    # and the first 15 generated tokens must give the same last row
    full = np.concatenate([prompts[0], tokens[probe][:-1]])[None]
    wrong = full.copy()
    wrong[0, -1] = (wrong[0, -1] + 1) % cfg.vocab_size
    forward = jax.jit(lambda p, t: model.prefill(
        p, {"tokens": t}, cfg, UNSHARDED, max_seq=scfg.max_seq,
        cache_dtype=jnp.float32)[0][0, -1, :cfg.vocab_size])
    ref, ref_wrong = (np.asarray(forward(params, jnp.asarray(t, jnp.int32)))
                      for t in (full, wrong))
    err = float(np.max(np.abs(ref - probe_row)))
    err_wrong = float(np.max(np.abs(ref_wrong - probe_row)))
    bound = LOGIT_RTOL * float(np.max(np.abs(ref)))
    log(f"serve: {probe} decode vs full forward: max |dlogit| {err:.3e}, "
        f"bound {bound:.3e} ({LOGIT_RTOL} x max |logit|); with a wrong "
        f"last token {err_wrong:.3e}; argmax agrees: "
        f"{int(np.argmax(ref)) == tokens[probe][-1]}")
    check(err <= bound, f"decode logits off the full forward by {err} "
                        f"(> {bound})")
    check(err_wrong > bound, f"a wrong last token moves the logits by "
                             f"{err_wrong} only, inside the bound {bound}")
    return {"tokens": tokens, "finished_s": finished_s, "step_s": step_s,
            "compile_s": compile_s, "logit_err": err}


def cxl_vs_ring_phase(cfg, mesh, batch: int, seq: int, steps: int,
                      seed: int = SEED) -> dict:
    """Train with ``backend=cxl`` and ``backend=ring`` from one seed and
    batch; the two must agree, with every parameter on every device."""
    runs = {b: train_phase(cfg, mesh, b, batch, seq, steps, seed,
                           name=f"cxl_vs_ring[{b}]")
            for b in ("cxl", "ring")}
    cxl, ring = runs["cxl"], runs["ring"]
    dloss = max(abs(a - b) for a, b in zip(cxl["losses"], ring["losses"]))
    dparam = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
                 for a, b in zip(jax.tree.leaves(cxl["params"]),
                                 jax.tree.leaves(ring["params"])))
    log(f"cxl_vs_ring: max |dloss| {dloss:.3e}, max |dparam| "
        f"{dparam:.3e} (bound {CXL_RING_TOL})")
    check(dloss <= CXL_RING_TOL, f"cxl vs ring loss delta {dloss}")
    check(dparam <= CXL_RING_TOL, f"cxl vs ring param delta {dparam}")
    devices = set(mesh.devices.flat)
    leaves = jax.tree.leaves(cxl["params"])
    for leaf in leaves:
        check({s.device for s in leaf.addressable_shards} == devices,
              f"a {leaf.shape} parameter is not on all {len(devices)} "
              f"devices")
    per_dev = {}
    for leaf in leaves:
        for s in leaf.addressable_shards:
            per_dev[s.device] = per_dev.get(s.device, 0) + s.data.nbytes
    total = sum(leaf.nbytes for leaf in leaves)
    log(f"cxl_vs_ring: params {total} B in all, per device "
        f"{sorted(per_dev.values())} B")
    check(max(per_dev.values()) < total, "parameters are not sharded")
    return {"dloss": dloss, "dparam": dparam, "per_device": per_dev}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the cxl-vs-ring phase on a 2x2 "
                         "mesh of four chips")
    args = ap.parse_args(argv)

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {len(devices)} "
              f"{platform} device(s)); nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 1
    dev = devices[0]
    log(f"device_kind {dev.device_kind} platform {platform} count "
        f"{len(devices)}")
    log(f"compile cache: {enable_compile_cache()}")
    _clock()

    cut = train_cut()
    log(f"model: {CONFIG.name} (d_model {cut.d_model}, heads "
        f"{cut.n_heads}/{cut.n_kv_heads}, d_ff {cut.d_ff}, vocab "
        f"{cut.vocab_size}); train cut to {TRAIN_LAYERS} of "
        f"{CONFIG.n_layers} layers")
    if args.chips == 4:
        mesh = jax.make_mesh((2, 2), ("data", "model"),
                             devices=devices[:4])
        cxl_vs_ring_phase(cut, mesh, batch=4, seq=1024, steps=3)
        log(f"cxl_vs_ring: peak_bytes_in_use {_peak_bytes(dev)} "
            f"(device 0)")
    else:
        mesh = jax.make_mesh((1, 1), ("data", "model"),
                             devices=devices[:1])
        out = train_phase(cut, mesh, "ring", batch=4, seq=1024, steps=5)
        check(out["losses"][-1] < out["losses"][0],
              f"train: loss did not fall: {out['losses']}")
        del out
        log(f"train: peak_bytes_in_use {_peak_bytes(dev)}")
        log(f"serve: {CONFIG.name}, all {CONFIG.n_layers} layers, 8 "
            f"requests x (128 prompt + 16 new) tokens, 4 decode slots")
        serve_phase(CONFIG, n_requests=8, prompt_len=128, new_tokens=16,
                    slots=4)
        log(f"serve: peak_bytes_in_use {_peak_bytes(dev)} (process "
            f"peak, train included)")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
