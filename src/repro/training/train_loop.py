"""Training step + loop.

The sharded step is a pure ``shard_map`` over the full production mesh
(Megatron-style manual sharding): every collective - FSDP param
AllGather, grad ReduceScatter (via AD transpose), TP AllReduce, MoE
AllToAll, vocab-sharded softmax reductions - goes through the CXL-CCL
``Communicator``, so ``--backend ring|cxl`` swaps the entire
communication layer of the framework.  This is the paper's Sec. 5.5 FSDP
case study generalized to every architecture in the zoo.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import ledger, overlap
from repro.core.api import Communicator
from repro.models import model, sharding
from repro.models.config import ModelConfig
from repro.models.pcontext import ParallelContext, UNSHARDED
from repro.optim import (AdamWState, adamw_init, adamw_update,
                         clip_by_global_norm, linear_warmup_cosine)


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    lr: float = 3e-4
    warmup: int = 100
    total_steps: int = 1000
    clip_norm: Optional[float] = 1.0     # unsharded path only
    remat: bool = True
    microbatches: int = 1                # gradient accumulation splits
    backend: str = "ring"                # 'ring' | 'cxl' | 'auto'
    slicing_factor: int = 4
    allreduce_mode: str = "two_phase"
    plan_path: Optional[str] = None      # autotuning plan for 'auto'
    # communication/compute overlap (core.overlap): NCCL-style cap on
    # the fused grad-sync AllReduce buffers; > 0 also switches the FSDP
    # gathers to row-fused buckets, <= 0 restores the per-leaf baseline.
    # prefetch=1 double-buffers the FSDP AllGather (0 restores the
    # serialized gather-then-compute schedule).
    bucket_mb: float = 25.0
    prefetch: int = 1
    # Fused collective+compute kernels (kernels.fused_collectives): the
    # FSDP gathers return the matmul weights as rank-major shard stacks
    # and the consuming matmuls stream them through the fused
    # all_gather+matmul kernel (models.layers.dense).  Requires the
    # bucketed gather path (bucket_mb > 0); the per-leaf reference
    # gather ignores the flag.
    fuse_kernels: bool = False

    @property
    def bucket_bytes(self) -> int:
        return int(self.bucket_mb * 1024 * 1024)


def make_gather_fn(tcfg: TrainConfig, rspecs: dict, pc: ParallelContext,
                   dp_axis):
    """FSDP gather hook for the configured overlap mode: row-fused
    buckets (``core.overlap``; the gather cap is intentionally None -
    a row is one FlatParameter regardless of ``bucket_mb``, which only
    caps the grad-sync buffers) or the per-leaf reference when
    ``bucket_mb <= 0``.  Shared by the trainer and the dry-run so the
    two always lower the same schedule.  ``tcfg.fuse_kernels`` rides
    through to the bucketed path: matmul weights come back as shard
    stacks for the fused all_gather+matmul kernel."""
    if tcfg.bucket_bytes > 0:
        return overlap.make_gather_fn(rspecs, pc, dp_axis,
                                      bucket_bytes=None,
                                      fuse=tcfg.fuse_kernels)
    return sharding.fsdp_gather_fn(rspecs, pc, dp_axis)


def make_train_step(cfg: ModelConfig, tcfg: TrainConfig,
                    pc: ParallelContext = UNSHARDED,
                    gather_fn=None, param_spec_tree=None,
                    dp_axis=None) -> Callable:
    """Unsharded (or inside-shard_map) train step:
    (params, opt_state, batch) -> (params, opt_state, metrics).

    With ``microbatches > 1`` the local batch is split and gradients are
    accumulated with ``lax.scan`` (bounding activation memory).
    ``param_spec_tree`` enables the replicated-grad AllReduce sync."""
    lr_fn = linear_warmup_cosine(tcfg.lr, tcfg.warmup, tcfg.total_steps)

    def lf(p, b):
        loss, aux = model.loss_fn(p, b, cfg, pc, remat=tcfg.remat,
                                  gather_fn=gather_fn,
                                  prefetch=tcfg.prefetch)
        if pc.dp_axis is not None:
            loss = pc.dp_all_reduce_mean(loss)
        return loss, aux

    def step(params, opt_state: AdamWState, batch):
        mb = tcfg.microbatches
        if mb > 1:
            split = jax.tree.map(
                lambda x: x.reshape((mb, x.shape[0] // mb) + x.shape[1:]),
                batch)

            def acc_step(acc, b):
                acc_g, acc_loss, acc_aux = acc
                # ledger: AD transposes double every collective's wire
                # bytes (AG<->RS, psum<->psum); remat inside the rows
                # already adds its replay factor in _run_groups' bodies.
                with ledger.scale(2 if not tcfg.remat else 3):
                    (loss, aux), grads = jax.value_and_grad(
                        lf, has_aux=True)(params, b)
                acc_g = jax.tree.map(jnp.add, acc_g, grads)
                return (acc_g, acc_loss + loss,
                        jax.tree.map(jnp.add, acc_aux, aux)), None

            zeros_g = jax.tree.map(
                lambda x: jnp.zeros(x.shape, jnp.float32), params)
            zero_aux = {"xent": jnp.float32(0), "aux": jnp.float32(0)}
            with ledger.scale(mb):
                (grads, loss, aux), _ = jax.lax.scan(
                    acc_step, (zeros_g, jnp.float32(0), zero_aux), split)
            grads = jax.tree.map(lambda g: g / mb, grads)
            loss = loss / mb
            aux = jax.tree.map(lambda a: a / mb, aux)
        else:
            with ledger.scale(2 if not tcfg.remat else 3):
                (loss, aux), grads = jax.value_and_grad(
                    lf, has_aux=True)(params, batch)
        if param_spec_tree is not None:
            if tcfg.bucket_bytes > 0:
                # fused (bucketed) replicated-grad sync: a handful of
                # large AllReduces instead of one per leaf
                grads = overlap.bucketed_sync_grads(
                    grads, param_spec_tree, pc, dp_axis,
                    bucket_bytes=tcfg.bucket_bytes)
            else:
                grads = sharding.sync_grads(grads, param_spec_tree, pc,
                                            dp_axis)
        gnorm = jnp.float32(0.0)
        if tcfg.clip_norm is not None and pc.tp_axis is None:
            grads, gnorm = clip_by_global_norm(grads, tcfg.clip_norm)
        lr = lr_fn(opt_state.step)
        params, opt_state = adamw_update(params, grads, opt_state, lr)
        return params, opt_state, {"loss": loss, "lr": lr,
                                   "grad_norm": gnorm, **aux}
    return step


def make_sharded_train_step(cfg: ModelConfig, tcfg: TrainConfig, mesh,
                            tp_axis: str = "model",
                            dp_axis=("data",)) -> tuple:
    """Builds the shard_map'ed train step for a production mesh.

    Returns (step_fn, param_specs, batch_specs, pc).  ``step_fn`` takes
    (params, opt_state, batch) with params/opt_state sharded per
    param_specs and the batch sharded over dp.
    """
    from repro.data.pipeline import make_batch_specs

    dp = dp_axis if isinstance(dp_axis, (tuple, list)) else (dp_axis,)
    dp = tuple(a for a in dp if mesh.shape[a] > 1) or (dp[0],)
    dp_spec = dp if len(dp) > 1 else dp[0]
    tp = mesh.shape[tp_axis]

    plan = None
    if tcfg.plan_path is not None:
        from repro.core.hw import CXL_POOL, INFINIBAND
        from repro.tuner import load_plan
        # fingerprint-checked: refuse a plan tuned for other hardware
        plan = load_plan(tcfg.plan_path, pool=CXL_POOL, ib=INFINIBAND)
    comm = Communicator(backend=tcfg.backend,
                        slicing_factor=tcfg.slicing_factor,
                        allreduce_mode=tcfg.allreduce_mode, plan=plan)
    pc = ParallelContext(tp_axis=tp_axis if tp > 1 else None,
                         dp_axis=dp_spec, tp=tp, comm=comm)

    sharding.set_mesh_sizes({a: mesh.shape[a] for a in mesh.axis_names})
    abstract = model.abstract_params(cfg, tp=tp)
    pspecs = sharding.param_specs(abstract, cfg, model_axis=tp_axis,
                                  dp_axis=dp_spec, fsdp=True)
    rspecs = sharding.row_specs(pspecs)
    gather = make_gather_fn(tcfg, rspecs, pc, dp_spec)
    bspecs = make_batch_specs(cfg, dp_spec)
    inner = make_train_step(cfg, tcfg, pc, gather_fn=gather,
                            param_spec_tree=pspecs, dp_axis=dp_spec)

    # optimizer state mirrors the param sharding
    ospecs = AdamWState(step=P(), mu=pspecs, nu=pspecs)
    mspecs = {"loss": P(), "lr": P(), "grad_norm": P(), "xent": P(),
              "aux": P()}

    step_fn = jax.jit(jax.shard_map(
        inner, mesh=mesh, in_specs=(pspecs, ospecs, bspecs),
        out_specs=(pspecs, ospecs, mspecs), check_vma=False))
    return step_fn, pspecs, bspecs, pc


def named_shardings(mesh, specs):
    """PartitionSpec pytree -> the matching NamedSharding pytree."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda s: isinstance(s, P))


def init_sharded_state(cfg: ModelConfig, mesh, pspecs, key,
                       tp: int = 1) -> tuple:
    """fp32 params and AdamW state for the sharded step, built by one
    jitted init whose ``out_shardings`` are the step's own specs: every
    device materializes only its shards, so no device ever holds the
    whole model (an eager init would stage it all on device 0 and leave
    the first step to reshard it)."""
    p_sh = named_shardings(mesh, pspecs)
    o_sh = AdamWState(step=NamedSharding(mesh, P()), mu=p_sh, nu=p_sh)

    def init(k):
        params = model.init_params(k, cfg, tp=tp, dtype=jnp.float32)
        return params, adamw_init(params)
    return jax.jit(init, out_shardings=(p_sh, o_sh))(key)


def train(cfg: ModelConfig, tcfg: TrainConfig, data_iter, steps: int,
          params=None, key=None, log_every: int = 10,
          log_fn=print) -> tuple:
    """Single-host training loop (CPU smoke / quickstart example)."""
    key = key if key is not None else jax.random.key(0)
    if params is None:
        params = model.init_params(key, cfg, tp=1, dtype=jnp.float32)
    opt_state = adamw_init(params)
    step = jax.jit(make_train_step(cfg, tcfg))
    t0 = time.time()
    metrics = {}
    for i, batch in zip(range(steps), data_iter):
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        params, opt_state, metrics = step(params, opt_state, batch)
        if i % log_every == 0 or i == steps - 1:
            log_fn(f"step {i:5d} loss {float(metrics['loss']):.4f} "
                   f"lr {float(metrics['lr']):.2e} "
                   f"({(time.time()-t0):.1f}s)")
    return params, opt_state, metrics
