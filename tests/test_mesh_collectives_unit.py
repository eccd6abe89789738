"""Single-device unit behaviour of the mesh-collective helpers (the
multi-device semantics are covered by tests/_mesh_runner.py)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import mesh_collectives as mc
from repro.core.api import Communicator, make_communicator


def test_split_chunks_divisible():
    x = jnp.arange(12.0).reshape(12, 1)
    chunks = mc._split_chunks(x, 4)
    assert len(chunks) == 4
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(c) for c in chunks]), np.asarray(x))


def test_split_chunks_non_divisible_falls_back():
    x = jnp.arange(10.0)
    assert len(mc._split_chunks(x, 4)) == 1   # 10 % 4 != 0


def test_split_chunks_scalar_and_single():
    assert len(mc._split_chunks(jnp.float32(1.0), 4)) == 1
    assert len(mc._split_chunks(jnp.arange(8.0), 1)) == 1


def test_lane_rows_only_for_whole_tiles():
    """Flat buffers run the schedules as (rows, 128) when every rank's
    share is whole (8, 128) tiles; anything else stays as it is."""
    x = jnp.arange(2 * 1024.0)
    rows = mc._lane_rows(x, 2)
    assert rows.shape == (16, 128)
    np.testing.assert_array_equal(np.asarray(rows).reshape(-1),
                                  np.asarray(x))
    assert mc._lane_rows(x, 4) is None            # 512 per rank
    assert mc._lane_rows(jnp.arange(1000.0)) is None
    assert mc._lane_rows(x.reshape(16, 128)) is None   # already 2-D


def test_ring_perm():
    assert mc._ring_perm(4) == [(0, 1), (1, 2), (2, 3), (3, 0)]
    assert mc._ring_perm(4, shift=2) == [(0, 2), (1, 3), (2, 0), (3, 1)]


def test_communicator_validation():
    with pytest.raises(ValueError):
        Communicator(backend="nccl")
    with pytest.raises(ValueError):
        Communicator(allreduce_mode="ring")
    c = make_communicator("cxl", slicing_factor=8,
                          allreduce_mode="faithful")
    assert c.backend == "cxl" and c.slicing_factor == 8


def test_axis_size_one_is_identity():
    """All collectives must be exact no-ops over a size-1 axis (the
    single-pod 'pod' dimension)."""
    import jax
    from jax.sharding import PartitionSpec as P
    mesh = jax.make_mesh((1,), ("solo",))
    comm = Communicator(backend="cxl")
    x = jnp.arange(16.0).reshape(8, 2)
    for fn in (lambda a: comm.all_reduce(a, "solo"),
               lambda a: comm.all_gather(a, "solo"),
               lambda a: comm.reduce_scatter(a, "solo"),
               lambda a: comm.all_to_all(a, "solo"),
               lambda a: comm.broadcast(a, "solo")):
        out = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P(),
                                    out_specs=P(),
                                    check_vma=False))(x)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(x))
