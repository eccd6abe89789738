"""TPU-mesh realizations of the CXL-CCL collective schedules.

On a TPU pod there is no shared memory pool; the paper's insight maps onto
ICI as follows (DESIGN.md, "hardware adaptation"):

* Eq. 4's disjoint-device ownership ≙ each rank's shard living in its own
  HBM; the read rotation "start from (rank_id+1) % nranks" (Fig. 6) is
  exactly a ring schedule - at every step all ranks pull a *different*
  peer's chunk, so every ICI link carries traffic every step.  We realize
  it with unrolled ``lax.ppermute`` rounds.
* The slicing-factor chunking of Sec. 4.4 becomes per-chunk ppermute
  rounds: communication of chunk k+1 overlaps the consumer-side compute
  (reduction) of chunk k.  XLA schedules these as async collectives.
* Doorbells are unnecessary: SSA data dependence of the ppermute chain
  enforces the producer->consumer (RAW) ordering the doorbell protects.

Everything here must be called inside ``shard_map`` with the named axis.

The paper-faithful AllReduce reads *all* peers' data and reduces locally
(no partial-result reuse - Sec. 5.2 explains why theirs only reaches 1.05x
on large messages).  ``all_reduce(..., mode='faithful')`` reproduces that;
``mode='two_phase'`` is the beyond-paper reduce_scatter + all_gather
composition (wire bytes 2S(n-1)/n instead of S(n-1) per rank).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

DEFAULT_CHUNKS = 4


def _ring_perm(n: int, shift: int = 1) -> list[tuple[int, int]]:
    return [(i, (i + shift) % n) for i in range(n)]


# --------------------------------------------------------------------- #
# hierarchical decomposition scaffolds (core.topology)
#
# Tuple axes used to recurse the same flat algorithm per level, so the
# outer (pool-spanning, slow) fabric carried the full payload at every
# level.  These scaffolds implement the level-decomposed schedules -
# the per-level single-axis collectives are injected as callables so the
# Communicator can pick a different backend per fabric level.
# --------------------------------------------------------------------- #

def hierarchical_all_reduce(x: jnp.ndarray, axes, *, rs_fn, ar_fn,
                            ag_fn) -> jnp.ndarray:
    """Level-decomposed AllReduce over ``axes`` (outer level first):

        ReduceScatter innermost..axes[1]  ->  AllReduce over axes[0]
        on the 1/prod(inner) shard        ->  AllGather back out.

    Each byte crosses the outermost (pool-spanning) fabric once at
    1/prod(inner) of the payload, instead of the full payload crossing
    at every level as the flat per-level recursion did.  ``rs_fn`` /
    ``ar_fn`` / ``ag_fn`` are ``(array, axis_name) -> array`` single-
    axis collectives (the Communicator's per-level dispatch).
    """
    axes = tuple(axes)
    inner = axes[1:]
    prod_inner = 1
    for ax in inner:
        prod_inner *= lax.axis_size(ax)
    orig_shape = x.shape
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % max(1, prod_inner)
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    seg = flat
    for ax in reversed(inner):      # innermost level first
        seg = rs_fn(seg, ax)
    seg = ar_fn(seg, axes[0])       # the only cross-outer traffic
    for ax in inner:                # inverse order back out
        seg = ag_fn(seg, ax)
    if pad:
        seg = seg[:-pad]
    return seg.reshape(orig_shape)


# --------------------------------------------------------------------- #
# grouped / ragged schedules (irregular topologies, core.topology)
#
# An irregular level (mixed per-pod fan-out, e.g. one pod of 4 nodes and
# one of 2) cannot be a regular mesh axis of its own: it lives on ONE
# flat mesh axis of sum(shape) ranks, partitioned into contiguous rank
# groups.  SPMD forbids per-rank shapes, so the ragged decomposition
# never produces uneven shards; instead it composes uniform-shape
# grouped schedules:
#
# * within-group ops are masked ring rounds - every group forms its own
#   ppermute ring, rounds run to max(shape)-1 and each rank masks the
#   rounds beyond its own group size;
# * cross-group traffic moves between per-group sub-roots (the first
#   rank of each group) over the *parent* level's fabric;
# * gathers concatenate padding-free: each rank scatters its shard into
#   a full-size buffer at its global offset, and summing the sub-roots'
#   disjoint-offset buffers IS the concatenation (no padded segments).
# --------------------------------------------------------------------- #

def _group_tables(group_shape) -> tuple:
    """Static per-rank tables for contiguous rank groups: returns
    (n, roots, group size per rank, position-in-group per rank,
    group root per rank)."""
    shape = tuple(int(g) for g in group_shape)
    if not shape or any(g < 1 for g in shape):
        raise ValueError(f"bad group shape {group_shape!r}")
    gsize, gpos, groot, roots = [], [], [], []
    start = 0
    for g in shape:
        roots.append(start)
        for p in range(g):
            gsize.append(g)
            gpos.append(p)
            groot.append(start)
        start += g
    return start, tuple(roots), gsize, gpos, groot


def _group_ring_perm(group_shape) -> list:
    """One ppermute whose cycles are the per-group rings."""
    n, _, gsize, gpos, groot = _group_tables(group_shape)
    return [(r, groot[r] + (gpos[r] + 1) % gsize[r]) for r in range(n)]


def _check_axis(axis_name: str, group_shape) -> int:
    n = lax.axis_size(axis_name)
    want = sum(int(g) for g in group_shape)
    if n != want:
        raise ValueError(
            f"group shape {tuple(group_shape)} spans {want} ranks but "
            f"axis {axis_name!r} has {n}")
    return n


def grouped_all_reduce(x: jnp.ndarray, axis_name: str, group_shape,
                       n_chunks: int = DEFAULT_CHUNKS) -> jnp.ndarray:
    """AllReduce *within* each contiguous rank group of a flat axis.

    Groups may have different sizes (``group_shape=(4, 2)``): rounds
    run to ``max(group_shape) - 1`` on the merged per-group rings and
    each rank stops accumulating after its own group's ``g - 1``
    rounds, so no padding ranks or uneven shards appear.  Every rank
    returns its group's sum.
    """
    _check_axis(axis_name, group_shape)
    shape = tuple(int(g) for g in group_shape)
    if max(shape) == 1:
        return x
    _, _, gsize, _, _ = _group_tables(shape)
    idx = lax.axis_index(axis_name)
    my_g = jnp.asarray(gsize)[idx]
    perm = _group_ring_perm(shape)
    out_chunks = []
    for c in _split_chunks(x, n_chunks):
        acc = c
        cur = c
        for t in range(1, max(shape)):
            cur = lax.ppermute(cur, axis_name, perm)
            acc = acc + jnp.where(t < my_g, cur, jnp.zeros_like(cur))
        out_chunks.append(acc)
    return jnp.concatenate(out_chunks, axis=0) if len(out_chunks) > 1 \
        else out_chunks[0]


def subroot_all_reduce(x: jnp.ndarray, axis_name: str, group_shape,
                       n_chunks: int = DEFAULT_CHUNKS) -> jnp.ndarray:
    """AllReduce *across* the per-group sub-roots (first rank of each
    group); every other rank passes through unchanged.  This is the
    only cross-group traffic of the ragged decomposition - the hop
    that rides the parent level's fabric."""
    n = _check_axis(axis_name, group_shape)
    _, roots, _, _, _ = _group_tables(group_shape)
    n_g = len(roots)
    if n_g == 1:
        return x
    nxt = {roots[i]: roots[(i + 1) % n_g] for i in range(n_g)}
    perm = [(r, nxt.get(r, r)) for r in range(n)]
    idx = lax.axis_index(axis_name)
    is_root = jnp.any(idx == jnp.asarray(roots))
    out_chunks = []
    for c in _split_chunks(x, n_chunks):
        acc = c
        cur = c
        for _ in range(1, n_g):
            cur = lax.ppermute(cur, axis_name, perm)
            acc = acc + jnp.where(is_root, cur, jnp.zeros_like(cur))
        out_chunks.append(acc)
    return jnp.concatenate(out_chunks, axis=0) if len(out_chunks) > 1 \
        else out_chunks[0]


def grouped_broadcast(x: jnp.ndarray, axis_name: str, group_shape,
                      n_chunks: int = DEFAULT_CHUNKS) -> jnp.ndarray:
    """Every rank receives its group sub-root's value (pipelined ring
    forward within each group, like ``broadcast`` with the distance
    measured from the group root)."""
    _check_axis(axis_name, group_shape)
    shape = tuple(int(g) for g in group_shape)
    if max(shape) == 1:
        return x
    _, _, _, gpos, _ = _group_tables(shape)
    idx = lax.axis_index(axis_name)
    dist = jnp.asarray(gpos)[idx]
    perm = _group_ring_perm(shape)
    out_chunks = []
    for c in _split_chunks(x, n_chunks):
        cur = c
        out = jnp.where(dist == 0, c, jnp.zeros_like(c))
        for step in range(1, max(shape)):
            cur = lax.ppermute(cur, axis_name, perm)
            out = jnp.where(dist == step, cur, out)
            cur = jnp.where(dist == step, out, cur)  # forward my copy
        out_chunks.append(out)
    return jnp.concatenate(out_chunks, axis=0) if len(out_chunks) > 1 \
        else out_chunks[0]


def ragged_all_reduce(x: jnp.ndarray, axis_name: str, group_shape,
                      n_chunks: int = DEFAULT_CHUNKS) -> jnp.ndarray:
    """Hierarchical AllReduce over a flat axis with ragged groups:
    within-group AllReduce, sub-root exchange across groups, grouped
    broadcast back out.  Numerically a sum over the whole axis (same
    result as the flat single-axis AllReduce up to summation order)."""
    y = grouped_all_reduce(x, axis_name, group_shape, n_chunks=n_chunks)
    z = subroot_all_reduce(y, axis_name, group_shape, n_chunks=n_chunks)
    return grouped_broadcast(z, axis_name, group_shape, n_chunks=n_chunks)


def ragged_all_gather(x: jnp.ndarray, axis_name: str, group_shape,
                      n_chunks: int = DEFAULT_CHUNKS,
                      cross_chunks: "int | None" = None) -> jnp.ndarray:
    """Padding-free hierarchical all-gather over ragged groups.

    Phase 1 rotates shards within each group, every rank writing each
    received shard into a full-size output buffer at the *global*
    rank-major offset - so after ``g - 1`` rounds each rank holds its
    whole group's block, at the right place, with no padded segments.
    Phase 2 sums the sub-roots' buffers across groups: the blocks sit
    at disjoint offsets, so the sum IS the concatenation.  Phase 3
    fans the assembled buffer back out within each group.  The result
    matches the flat single-axis ``all_gather`` exactly (rank-major
    order along axis 0).  ``cross_chunks`` is the slicing factor of
    the cross-group (sub-root) phase - the hop a per-level plan may
    tune separately; defaults to ``n_chunks``.
    """
    n = _check_axis(axis_name, group_shape)
    shape = tuple(int(g) for g in group_shape)
    if n == 1:
        return x
    if x.ndim == 0:
        raise ValueError("ragged_all_gather needs at least 1-d input")
    _, _, gsize, gpos, groot = _group_tables(shape)
    idx = lax.axis_index(axis_name)
    my_g = jnp.asarray(gsize)[idx]
    my_pos = jnp.asarray(gpos)[idx]
    my_root = jnp.asarray(groot)[idx]
    perm = _group_ring_perm(shape)
    lead = x.shape[0]
    buf = jnp.zeros((n * lead,) + x.shape[1:], x.dtype)
    buf = lax.dynamic_update_slice_in_dim(buf, x, idx * lead, axis=0)
    cur = x
    for t in range(1, max(shape)):
        # after t hops my copy originated t ranks behind me in my group
        cur = lax.ppermute(cur, axis_name, perm)
        src = my_root + jnp.mod(my_pos - t, my_g)
        upd = lax.dynamic_update_slice_in_dim(buf, cur, src * lead,
                                              axis=0)
        buf = jnp.where(t < my_g, upd, buf)
    buf = subroot_all_reduce(buf, axis_name, shape,
                             n_chunks=cross_chunks if cross_chunks
                             is not None else n_chunks)
    return grouped_broadcast(buf, axis_name, shape, n_chunks=n_chunks)


def ragged_reduce_scatter(x: jnp.ndarray, axis_name: str, group_shape,
                          n_chunks: int = DEFAULT_CHUNKS,
                          cross_chunks: "int | None" = None
                          ) -> jnp.ndarray:
    """Padding-free hierarchical reduce-scatter over ragged groups:
    rank r returns ``sum_ranks(x)[r*seg:(r+1)*seg]`` with
    ``seg = lead / sum(shape)`` - exactly the flat single-axis
    ``reduce_scatter`` semantics, decomposed so the cross-group hop
    rides the parent level's fabric.

    Phase 1 reduces within each group (masked rings to
    ``max(shape) - 1`` rounds, so no padding ranks appear); phase 2
    exchanges the group partials across the per-group sub-roots - the
    disjoint-offset complement of ``ragged_all_gather``'s assembly:
    each sub-root's buffer carries its group's partial of *every*
    global segment, and summing them completes every segment at once;
    phase 3 fans the completed buffer back out within each group and
    every rank slices its own rank-major segment (a traced offset -
    uniform shapes, so SPMD never sees an uneven shard).
    ``cross_chunks`` tunes the sub-root hop's slicing factor
    separately; defaults to ``n_chunks``.
    """
    n = _check_axis(axis_name, group_shape)
    shape = tuple(int(g) for g in group_shape)
    if n == 1:
        return x
    if x.shape[0] % n:
        raise ValueError(f"leading dim {x.shape[0]} must divide axis {n}")
    seg = x.shape[0] // n
    idx = lax.axis_index(axis_name)
    part = grouped_all_reduce(x, axis_name, shape, n_chunks=n_chunks)
    full = subroot_all_reduce(part, axis_name, shape,
                              n_chunks=cross_chunks if cross_chunks
                              is not None else n_chunks)
    full = grouped_broadcast(full, axis_name, shape, n_chunks=n_chunks)
    return lax.dynamic_slice_in_dim(full, idx * seg, seg, axis=0)


def ragged_gather(x: jnp.ndarray, axis_name: str, group_shape,
                  root: int = 0,
                  n_chunks: int = DEFAULT_CHUNKS,
                  cross_chunks: "int | None" = None) -> jnp.ndarray:
    """Gather-to-root over ragged groups (rank-major concatenation,
    non-root ranks return zeros), via the padding-free assembly of
    ``ragged_all_gather``."""
    full = ragged_all_gather(x, axis_name, group_shape,
                             n_chunks=n_chunks,
                             cross_chunks=cross_chunks)
    idx = lax.axis_index(axis_name)
    return jnp.where(idx == root, full, jnp.zeros_like(full))


_LANES = 128


def _lane_rows(x: jnp.ndarray, n: int = 1):
    """A flat buffer (the FSDP buckets) as (rows, 128), or None where
    its size is not a whole number of (8, 128) tiles per rank.  The TPU
    lays both shapes out in the same tiles, so the reshape is free, and
    the schedules' per-rank buffers then keep the lane dim minor: kept
    1-D, every (n, chunk) buffer puts the rank count in the sublane dim
    and forces relayouts that the TPU compiler emits as code growing
    with the buffer (compiling for a 2x2 v5e, gathering one llama3.2-1b
    embedding shard took 99 s and 100 MB of code)."""
    if x.ndim == 1 and x.shape[0] % (8 * _LANES * n) == 0:
        return x.reshape(-1, _LANES)
    return None


def _split_chunks(x: jnp.ndarray, n_chunks: int) -> list[jnp.ndarray]:
    """Split along axis 0 (the paper's slicing factor).  Falls back to a
    single chunk when the leading dim does not divide."""
    lead = x.shape[0] if x.ndim else 1
    if n_chunks <= 1 or x.ndim == 0 or lead % n_chunks:
        return [x]
    return list(jnp.split(x, n_chunks, axis=0))


def p2p_shift(x: jnp.ndarray, axis_name: str, shift: int = 1,
              n_chunks: int = DEFAULT_CHUNKS) -> jnp.ndarray:
    """Point-to-point ring shift: every rank sends ``x`` to the rank
    ``shift`` ahead on the axis and returns the payload received from
    the rank ``shift`` behind (cyclic).  This is the pipeline-parallel
    activation/grad handoff primitive: the whole payload moves exactly
    one hop, so wire bytes are S per rank per call.

    On the pool this is a write + doorbell commit + consumer read; on
    the TPU mesh both backends lower to per-chunk ``ppermute`` (SSA
    data dependence replaces the doorbell, exactly as for the
    collectives above), with the slicing factor pipelining the
    producer write against the consumer read."""
    n = lax.axis_size(axis_name)
    if n == 1 or shift % n == 0:
        return x
    perm = _ring_perm(n, shift % n)
    moved = [lax.ppermute(c, axis_name, perm)
             for c in _split_chunks(x, n_chunks)]
    return jnp.concatenate(moved, axis=0) if len(moved) > 1 else moved[0]


def all_gather(x: jnp.ndarray, axis_name: str,
               n_chunks: int = DEFAULT_CHUNKS) -> jnp.ndarray:
    """Chunked ring all-gather; returns shards concatenated along axis 0 in
    rank order (``tiled=True`` semantics)."""
    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    rows = _lane_rows(x)
    if rows is not None:
        return all_gather(rows, axis_name, n_chunks).reshape(-1)
    idx = lax.axis_index(axis_name)
    perm = _ring_perm(n)
    chunks = _split_chunks(x, n_chunks)
    gathered = []
    for c in chunks:
        out = jnp.zeros((n,) + c.shape, c.dtype)
        out = lax.dynamic_update_index_in_dim(out, c, idx, 0)
        cur = c
        for step in range(1, n):
            # After `step` hops my copy of `cur` originated at idx - step.
            cur = lax.ppermute(cur, axis_name, perm)
            src = (idx - step) % n
            out = lax.dynamic_update_index_in_dim(out, cur, src, 0)
        gathered.append(out)
    # Re-interleave chunk rows back into rank-major order: stack to
    # (chunks, n, lead/chunks, ...), swap to rank-major and flatten -
    # one transpose instead of O(n * chunks) concatenates.
    stacked = jnp.stack(gathered, axis=0)
    lead = x.shape[0] if x.ndim else 1
    return jnp.swapaxes(stacked, 0, 1).reshape((n * lead,)
                                               + x.shape[1:])


def reduce_scatter(x: jnp.ndarray, axis_name: str,
                   n_chunks: int = DEFAULT_CHUNKS) -> jnp.ndarray:
    """Chunked ring reduce-scatter over axis 0 (``scatter_dimension=0``):
    rank r returns ``sum_ranks(x)[r*seg:(r+1)*seg]``."""
    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    rows = _lane_rows(x, n)
    if rows is not None:
        return reduce_scatter(rows, axis_name, n_chunks).reshape(-1)
    if x.shape[0] % n:
        raise ValueError(f"leading dim {x.shape[0]} must divide axis {n}")
    idx = lax.axis_index(axis_name)
    perm = _ring_perm(n)
    segs = jnp.reshape(x, (n, x.shape[0] // n) + x.shape[1:])

    # Partial for segment s starts at rank s+1; after t hops it sits at
    # rank r = s + 1 + t and absorbs that rank's segment s = r - t - 1.
    acc = lax.dynamic_index_in_dim(segs, (idx - 1) % n, 0, keepdims=False)
    acc_chunks = _split_chunks(acc, n_chunks)
    for t in range(1, n):
        local = lax.dynamic_index_in_dim(segs, (idx - t - 1) % n, 0,
                                         keepdims=False)
        local_chunks = _split_chunks(local, n_chunks)
        acc_chunks = [lax.ppermute(a, axis_name, perm) + l
                      for a, l in zip(acc_chunks, local_chunks)]
    return jnp.concatenate(acc_chunks, axis=0) if len(acc_chunks) > 1 \
        else acc_chunks[0]


def all_reduce(x: jnp.ndarray, axis_name: str, *, mode: str = "two_phase",
               n_chunks: int = DEFAULT_CHUNKS) -> jnp.ndarray:
    """AllReduce over the named axis.

    ``faithful``  - the paper's algorithm: gather every peer's full buffer
                    (ring) and reduce locally; wire bytes S(n-1) per rank.
    ``two_phase`` - reduce_scatter + all_gather; wire bytes 2S(n-1)/n.
    """
    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    if mode == "faithful":
        perm = _ring_perm(n)
        chunks = _split_chunks(x, n_chunks)
        out_chunks = []
        for c in chunks:
            acc = c
            cur = c
            for _ in range(1, n):
                cur = lax.ppermute(cur, axis_name, perm)
                acc = acc + cur
            out_chunks.append(acc)
        return jnp.concatenate(out_chunks, axis=0) if len(out_chunks) > 1 \
            else out_chunks[0]
    if mode == "two_phase":
        orig_shape = x.shape
        flat = x.reshape(-1)
        pad = (-flat.shape[0]) % n
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
        seg = reduce_scatter(flat, axis_name, n_chunks=n_chunks)
        full = all_gather(seg, axis_name, n_chunks=n_chunks)
        if pad:
            full = full[:-pad]
        return full.reshape(orig_shape)
    raise ValueError(f"unknown all_reduce mode {mode!r}")


def all_to_all(x: jnp.ndarray, axis_name: str,
               n_chunks: int = DEFAULT_CHUNKS) -> jnp.ndarray:
    """Rotation-scheduled all-to-all over axis 0: segment p of the result
    is rank p's segment ``my_rank``.  Mirrors the paper's AllToAll where
    rank r publishes segment ``dest`` starting from ``(r+1) % nranks``:
    rotation ``s`` exchanges data between ranks at ring distance ``s``."""
    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    if x.shape[0] % n:
        raise ValueError(f"leading dim {x.shape[0]} must divide axis {n}")
    idx = lax.axis_index(axis_name)
    segs = jnp.reshape(x, (n, x.shape[0] // n) + x.shape[1:])
    out = jnp.zeros_like(segs)
    own = lax.dynamic_index_in_dim(segs, idx, 0, keepdims=False)
    out = lax.dynamic_update_index_in_dim(out, own, idx, 0)
    for s in range(1, n):
        perm = _ring_perm(n, shift=s)
        # I send my segment for rank (idx+s); I receive from rank (idx-s)
        # its segment destined to me.
        send = lax.dynamic_index_in_dim(segs, (idx + s) % n, 0,
                                        keepdims=False)
        recv_chunks = [lax.ppermute(c, axis_name, perm)
                       for c in _split_chunks(send, n_chunks)]
        recv = jnp.concatenate(recv_chunks, axis=0) \
            if len(recv_chunks) > 1 else recv_chunks[0]
        out = lax.dynamic_update_index_in_dim(out, recv, (idx - s) % n, 0)
    return out.reshape(x.shape)


def broadcast(x: jnp.ndarray, axis_name: str, root: int = 0,
              n_chunks: int = DEFAULT_CHUNKS) -> jnp.ndarray:
    """Pipelined ring broadcast from ``root``; chunks stream hop-by-hop so
    link utilization matches the pool version's chunk overlap."""
    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    idx = lax.axis_index(axis_name)
    dist = (idx - root) % n
    perm = _ring_perm(n)
    out_chunks = []
    for c in _split_chunks(x, n_chunks):
        cur = c
        out = jnp.where(dist == 0, c, jnp.zeros_like(c))
        for step in range(1, n):
            cur = lax.ppermute(cur, axis_name, perm)
            out = jnp.where(dist == step, cur, out)
            cur = jnp.where(dist == step, out, cur)  # forward my copy
        out_chunks.append(out)
    return jnp.concatenate(out_chunks, axis=0) if len(out_chunks) > 1 \
        else out_chunks[0]


def reduce(x: jnp.ndarray, axis_name: str, root: int = 0,
           n_chunks: int = DEFAULT_CHUNKS) -> jnp.ndarray:
    """Ring reduce-to-root; non-root ranks return zeros."""
    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    idx = lax.axis_index(axis_name)
    total = all_reduce(x, axis_name, mode="two_phase", n_chunks=n_chunks)
    return jnp.where(idx == root, total, jnp.zeros_like(total))


def gather(x: jnp.ndarray, axis_name: str, root: int = 0,
           n_chunks: int = DEFAULT_CHUNKS) -> jnp.ndarray:
    """Gather-to-root (rank order along axis 0); non-root ranks zeros."""
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    full = all_gather(x, axis_name, n_chunks=n_chunks)
    return jnp.where(idx == root, full, jnp.zeros_like(full))


def scatter(x: jnp.ndarray, axis_name: str, root: int = 0,
            n_chunks: int = DEFAULT_CHUNKS) -> jnp.ndarray:
    """Scatter from root: rank r receives segment r of root's axis-0."""
    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    if x.shape[0] % n:
        raise ValueError(f"leading dim {x.shape[0]} must divide axis {n}")
    idx = lax.axis_index(axis_name)
    rooted = broadcast(x, axis_name, root=root, n_chunks=n_chunks)
    segs = jnp.reshape(rooted, (n, x.shape[0] // n) + x.shape[1:])
    return lax.dynamic_index_in_dim(segs, idx, 0, keepdims=False)
