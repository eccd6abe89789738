"""Sequence-sharded decode (tp > 1) against the unsharded decode step.

Run on forced host devices (``XLA_FLAGS=
--xla_force_host_platform_device_count=4``); ``test_consistency.py``
starts it in a subprocess.  tp=2 shards the KV heads; tp=4 pads 14
query heads to 16 and replicates the 2 KV heads.  Each shard owns a
quarter (half) of every slot's positions and writes only those; the
logits and the written cache must match the tp=1 step, and the
inactive slot's cache must come back bit for bit.  Ends with
``DECODE TP: ALL OK``."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import get_config
from repro.core.api import Communicator
from repro.launch.dryrun import cache_specs
from repro.models import model, sharding
from repro.models.pcontext import UNSHARDED, ParallelContext

CFG = dataclasses.replace(get_config("llama3.2-1b", smoke=True),
                          d_model=224, n_heads=14, n_kv_heads=2)
B, MAX_SEQ = 4, 16


def unpadded(params, tp):
    """The tp-padded global params with the zero q heads cut off."""
    real = CFG.n_heads * CFG.head_dim
    g = dict(params["g0"], attn=dict(
        params["g0"]["attn"], wq=params["g0"]["attn"]["wq"][..., :real],
        wo=params["g0"]["attn"]["wo"][:, :real]))
    return dict(params, g0=g)


def check(tp: int) -> None:
    rng = np.random.default_rng(tp)
    params = model.init_params(jax.random.key(3), CFG, tp=tp,
                               dtype=jnp.float32)
    caches = model.init_cache(CFG, UNSHARDED, B, MAX_SEQ,
                              cache_dtype=jnp.float32)
    caches = jax.tree.map(lambda c: jnp.asarray(
        rng.standard_normal(c.shape), c.dtype), caches)
    tok = jnp.asarray(rng.integers(0, CFG.vocab_size, (B, 1)), jnp.int32)
    # positions in every shard's range; slot 1 idles at 0 over live data
    pos = jnp.asarray([3, 0, 9, 14], jnp.int32)
    active = jnp.asarray([True, False, True, True])

    mesh = Mesh(np.array(jax.devices()[:tp]), ("model",))
    sharding.set_mesh_sizes({"model": tp})
    pc = ParallelContext(tp_axis="model", tp=tp,
                         comm=Communicator(backend="ring"))
    pspecs = sharding.param_specs(params, CFG, dp_axis=None, fsdp=False)
    cspecs = cache_specs(CFG, caches, None, batch_sharded=False, tp=tp)
    sharded = jax.jit(jax.shard_map(
        lambda p, c, t, q, a: model.decode_step(p, c, t, q, CFG, pc,
                                                active=a),
        mesh=mesh, in_specs=(pspecs, cspecs, P(), P(), P()),
        out_specs=(P(), cspecs), check_vma=False))
    lt, ct = sharded(params, caches, tok, pos, active)
    l1, c1 = jax.jit(lambda p, c: model.decode_step(
        p, c, tok, pos, CFG, UNSHARDED, active=active))(
            unpadded(params, tp), caches)

    lt, l1 = np.asarray(lt), np.asarray(l1)
    act = np.asarray(active)
    np.testing.assert_allclose(lt[act], l1[act], rtol=0,
                               atol=1e-4 * np.abs(l1).max())
    for a, b, c in zip(jax.tree.leaves(ct), jax.tree.leaves(c1),
                       jax.tree.leaves(caches)):
        a, b, c = np.asarray(a), np.asarray(b), np.asarray(c)
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-5)
        np.testing.assert_array_equal(a[:, ~act], c[:, ~act])
        # exactly one position per active slot and layer was written
        changed = np.any(a != c, axis=(3, 4))            # (L, B, S)
        assert (changed.sum(axis=2)[:, act] == 1).all(), changed.sum(2)
    print(f"tp={tp}: ok")


if __name__ == "__main__":
    for tp in (2, 4):
        check(tp)
    print("DECODE TP: ALL OK")
