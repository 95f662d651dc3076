"""Distributed partitioned equi-join: all_to_all repartition + local join.

The shard_map bodies here are 32-bit only (keys are single u32 dictionary-ID
columns; row identity uses multi-operand ``lax.sort``) so they run without
the x64 scope that the packed host-facing kernels in
:mod:`kolibrie_tpu.ops.device_join` need.

Replaces the reference's rayon par_chunks hash joins
(``shared/src/join_algorithm.rs:19-131,499-570``) with the classic
distributed-DB plan: hash-partition both sides on the join key (one
``all_to_all`` per repartitioned side, riding ICI), then sort-merge join
locally per chip.

Invalid-row sentinels: dictionary IDs occupy bits 0..30 (bit 31 marks quoted
triples — ``shared/src/dictionary.rs:36-40``), so 0xFFFFFFFE / 0xFFFFFFFF
never collide with real IDs.
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kolibrie_tpu.ops.prefix import prefix_count

_LPAD32 = np.uint32(0xFFFFFFFE)  # np scalar: a trace-time LITERAL, never a lifted const buffer
_RPAD32 = np.uint32(0xFFFFFFFF)


def mix32(x: jnp.ndarray) -> jnp.ndarray:
    """Device twin of ``sharded_store._mix32`` — MUST stay bit-identical."""
    x = x.astype(jnp.uint32)
    c = np.uint32(0x45D9F3B)
    x = (x ^ (x >> 16)) * c
    x = (x ^ (x >> 16)) * c
    return x ^ (x >> 16)


def shard_of_dev(key: jnp.ndarray, n_shards: int) -> jnp.ndarray:
    return (mix32(key) % np.uint32(n_shards)).astype(jnp.int32)


def dist_pallas_enabled() -> bool:
    """Route the distributed rounds' shard-local joins through the Pallas
    tile kernel.  Governed by the unified ``KOLIBRIE_PALLAS`` mode:
    ``force`` turns it on, ``off``/``auto`` keep it off — this path keeps
    its historical default-off even under ``auto`` on TPU until
    shard_map+Pallas composition is validated on real hardware (see
    COVERAGE.md "remaining gaps").  EXPERIMENTAL — read at TRACE time, so
    the mode must be set before the first round program of a process is
    built (the compiled-program caches do not key on it)."""
    from kolibrie_tpu.ops.pallas_kernels import pallas_mode

    return pallas_mode() == "force"


def _dist_check_vma() -> bool:
    """shard_map's varying-mesh-axes checking (jax>=0.9 default) rejects
    ``pallas_call`` bodies (``dynamic_slice`` vma mismatch raised from the
    kernel's internal machinery, with jax's own error message suggesting
    ``check_vma=False``) — disable it exactly when the experimental dist
    Pallas route is on; all XLA-only programs keep the check."""
    return not dist_pallas_enabled()


def local_join_u32(
    lkey: jnp.ndarray,
    rkey: jnp.ndarray,
    cap: int,
    lvalid: jnp.ndarray,
    rvalid: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """32-bit static-shape equi-join (see device_join.join_indices)."""
    if dist_pallas_enabled():
        return _local_join_u32_pallas(lkey, rkey, cap, lvalid, rvalid)
    lkey = jnp.where(lvalid, lkey.astype(jnp.uint32), _LPAD32)
    rkey = jnp.where(rvalid, rkey.astype(jnp.uint32), _RPAD32)
    ln, rn = lkey.shape[0], rkey.shape[0]
    if ln == 0 or rn == 0:
        z = jnp.zeros(cap, dtype=jnp.int32)
        return z, z, jnp.zeros(cap, dtype=bool), jnp.int32(0)
    order = jnp.argsort(rkey)
    rsorted = rkey[order]
    lo = jnp.searchsorted(rsorted, lkey, side="left")
    hi = jnp.searchsorted(rsorted, lkey, side="right")
    counts = (hi - lo).astype(jnp.int32)
    cum = jnp.cumsum(counts)
    total = cum[-1]
    idx = jnp.arange(cap, dtype=jnp.int32)
    row = jnp.searchsorted(cum, idx, side="right")
    row_c = jnp.clip(row, 0, ln - 1)
    start = cum[row_c] - counts[row_c]
    pos = lo[row_c] + (idx - start)
    valid = idx < total
    li = jnp.where(valid, row_c, 0).astype(jnp.int32)
    ri = jnp.where(valid, order[jnp.clip(pos, 0, rn - 1)], 0).astype(jnp.int32)
    return li, ri, valid, total


def _local_join_u32_pallas(
    lkey: jnp.ndarray,
    rkey: jnp.ndarray,
    cap: int,
    lvalid: jnp.ndarray,
    rvalid: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """:func:`local_join_u32` via the Pallas tile kernel: sort the right
    keys once, run the merge-join kernel, map ``ri`` back through the sort
    permutation.  Same ``(li, ri, valid, total)`` contract; u32 keys need
    no dense-rank prepass."""
    from kolibrie_tpu.ops.pallas_kernels import merge_join_indices

    lk = jnp.where(lvalid, lkey.astype(jnp.uint32), _LPAD32)
    rk = jnp.where(rvalid, rkey.astype(jnp.uint32), _RPAD32)
    if lk.shape[0] == 0 or rk.shape[0] == 0:
        z = jnp.zeros(cap, dtype=jnp.int32)
        return z, z, jnp.zeros(cap, dtype=bool), jnp.int32(0)
    rorder = jnp.argsort(rk)
    li, rpos, valid, total = merge_join_indices(lk, rk[rorder], cap)
    li, rpos, valid = li[:cap], rpos[:cap], valid[:cap]
    ri = jnp.where(valid, rorder[rpos], 0).astype(jnp.int32)
    return li, ri, valid, total.astype(jnp.int32)


def bucketize(
    cols: Sequence[jnp.ndarray],
    valid: jnp.ndarray,
    dest: jnp.ndarray,
    n_shards: int,
    bucket_cap: int,
) -> Tuple[Tuple[jnp.ndarray, ...], jnp.ndarray, jnp.ndarray]:
    """Scatter local rows into per-destination buckets ``[n*bucket_cap]``.

    Rows beyond a destination's capacity are DROPPED and counted so the host
    can grow ``bucket_cap`` and retry (static-shape overflow protocol).
    """
    L = dest.shape[0]
    dmask = jnp.where(valid, dest, n_shards)
    # a row's place in its bucket is how many rows before it go the same
    # way: one prefix count a destination (linear in the mesh, which is 4 or
    # 8 wide) and no sort of the table.  The places are those a stable sort
    # by destination gives; at a table of 131,072 rows that sort was 20 s of
    # a mesh program's 23 s compile for a described v5e (PR 50)
    rank = jnp.zeros(L, dtype=jnp.int32)
    for d in range(n_shards):
        goes = dmask == d
        rank = jnp.where(goes, prefix_count(goes) - 1, rank)
    ok = valid & (rank < bucket_cap)
    slot = jnp.where(ok, dmask * bucket_cap + rank, n_shards * bucket_cap)
    bufs = []
    for c in cols:
        buf = jnp.zeros(n_shards * bucket_cap, dtype=c.dtype)
        bufs.append(buf.at[slot].set(c, mode="drop"))
    bvalid = (
        jnp.zeros(n_shards * bucket_cap, dtype=bool).at[slot].set(ok, mode="drop")
    )
    dropped = jnp.sum(valid) - jnp.sum(ok)
    return tuple(bufs), bvalid, dropped


def compact(
    cols: Sequence[jnp.ndarray],
    valid: jnp.ndarray,
    cap: int,
) -> Tuple[Tuple[jnp.ndarray, ...], jnp.ndarray, jnp.ndarray]:
    """Gather the valid rows, in their order, into the first slots of a
    table of ``cap`` rows.

    For a scan that selects a few rows of a wide block: what runs at the
    block's width is one prefix count of the mask; the positions are ``cap``
    searches into it and the columns ``cap``-row gathers, so nothing
    downstream sorts, searches or scatters at the block's width.  Rows
    beyond ``cap`` are DROPPED and counted, as :func:`bucketize` counts
    them, for the host's grow-and-retry."""
    cum = prefix_count(valid)
    total = cum[-1]
    idx = jnp.arange(cap, dtype=jnp.int32)
    pos = jnp.searchsorted(cum, idx + 1, side="left")
    ok = idx < total
    pos = jnp.where(ok, pos, 0)
    out = tuple(jnp.where(ok, c[pos], 0) for c in cols)
    return out, ok, jnp.maximum(total - cap, 0)


def exchange(
    cols: Sequence[jnp.ndarray],
    valid: jnp.ndarray,
    dest: jnp.ndarray,
    n_shards: int,
    axis: str,
    bucket_cap: int,
) -> Tuple[Tuple[jnp.ndarray, ...], jnp.ndarray, jnp.ndarray]:
    """Route rows to their destination shard: bucketize + one all_to_all.

    Returns local received rows ``[n*bucket_cap]`` + valid mask + the
    GLOBAL dropped-row count (psum) for overflow detection.
    """
    bufs, bvalid, dropped = bucketize(cols, valid, dest, n_shards, bucket_cap)
    a2a = lambda b: lax.all_to_all(  # noqa: E731
        b.reshape(n_shards, bucket_cap), axis, 0, 0, tiled=True
    ).reshape(n_shards * bucket_cap)
    out = tuple(a2a(b) for b in bufs)
    out_valid = a2a(bvalid)
    return out, out_valid, lax.psum(dropped, axis)


def _dist_join_body(
    lcols, lvalid, rcols, rvalid, *, lkey_i, rkey_i, n, axis, bucket_cap, out_cap
):
    """Per-shard body: repartition both sides by key hash, join locally."""
    lcols = tuple(c[0] for c in lcols)  # strip leading shard dim of size 1
    rcols = tuple(c[0] for c in rcols)
    lvalid, rvalid = lvalid[0], rvalid[0]
    ld = shard_of_dev(lcols[lkey_i], n)
    rd = shard_of_dev(rcols[rkey_i], n)
    lr, lrv, ldrop = exchange(lcols, lvalid, ld, n, axis, bucket_cap)
    rr, rrv, rdrop = exchange(rcols, rvalid, rd, n, axis, bucket_cap)
    li, ri, jvalid, total = local_join_u32(
        lr[lkey_i], rr[rkey_i], out_cap, lrv, rrv
    )
    # a shard whose local match count exceeds out_cap truncates its output —
    # count the overrun so the caller's dropped>0 retry protocol catches it
    out_ovf = lax.psum(jnp.maximum(total - out_cap, 0).astype(jnp.int32), axis)
    louts = tuple(jnp.where(jvalid, c[li], 0)[None] for c in lr)
    routs = tuple(jnp.where(jvalid, c[ri], 0)[None] for c in rr)
    return (
        louts,
        routs,
        jvalid[None],
        lax.psum(total, axis)[None],
        (ldrop + rdrop + out_ovf)[None],
    )


@lru_cache(maxsize=64)
def _equi_join_fn(mesh, nl, nr, lkey_i, rkey_i, bucket_cap, out_cap):
    """Compiled-program cache: repeated joins with the same mesh/arity/caps
    reuse one jitted shard_map program instead of retracing per call."""
    axis = mesh.axis_names[0]
    n = mesh.devices.size
    spec_cols = P(axis, None)
    body = partial(
        _dist_join_body,
        lkey_i=lkey_i,
        rkey_i=rkey_i,
        n=n,
        axis=axis,
        bucket_cap=bucket_cap,
        out_cap=out_cap,
    )
    return jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            check_vma=_dist_check_vma(),
            in_specs=(
                (spec_cols,) * nl,
                spec_cols,
                (spec_cols,) * nr,
                spec_cols,
            ),
            out_specs=(
                (spec_cols,) * nl,
                (spec_cols,) * nr,
                spec_cols,
                P(axis),
                P(axis),
            ),
        )
    )


def dist_equi_join(
    mesh: Mesh,
    left_cols: Sequence[np.ndarray],
    left_valid: np.ndarray,
    right_cols: Sequence[np.ndarray],
    right_valid: np.ndarray,
    lkey_i: int,
    rkey_i: int,
    bucket_cap: int = 1024,
    out_cap: int = 4096,
):
    """Distributed equi-join of two sharded row sets on one u32 key column.

    Inputs are global ``[n_shards, L]`` arrays (host numpy or device).
    Returns ``(left_out, right_out, valid, global_total, dropped)`` with
    per-shard static capacity ``out_cap``; ``dropped > 0`` means rows were
    lost to exchange-bucket OR join-output capacity — retry with larger
    ``bucket_cap`` / ``out_cap``.
    """
    nl, nr = len(left_cols), len(right_cols)
    fn = _equi_join_fn(mesh, nl, nr, lkey_i, rkey_i, bucket_cap, out_cap)
    sh = NamedSharding(mesh, P(mesh.axis_names[0], None))
    put = lambda a: jax.device_put(jnp.asarray(a), sh)  # noqa: E731
    lo, ro, v, tot, drop = fn(
        tuple(put(c) for c in left_cols),
        put(left_valid),
        tuple(put(c) for c in right_cols),
        put(right_valid),
    )
    return lo, ro, v, int(tot[0]), int(drop[0])


def dist_bgp_join_count(store, p1: int, p2: int) -> int:
    """COUNT of the 2-pattern BGP join ``(?x p1 ?y) . (?y p2 ?z)``.

    Exploits the dual partitioning of :class:`ShardedTripleStore`: the left
    side (keyed by object) lives object-hashed, the right (keyed by subject)
    subject-hashed — matching keys are ALREADY co-located, so the join runs
    with zero exchange and one scalar psum.  This is the headline
    BGP-join benchmark path (BASELINE.md config 1/5).
    """
    # host readback, not a device gather: the count array is i64 (the
    # device path runs under enable_x64) and an eager [0] outside that
    # scope lowers with an i32 result type against the i64 operand
    return int(jax.device_get(dist_bgp_join_count_device(store, p1, p2))[0])


def dist_bgp_join_count_device(store, p1: int, p2: int):
    """As :func:`dist_bgp_join_count` but returns the un-read device array.

    Benchmarks dispatch-and-time BEFORE any host readback; this variant
    lets callers defer the read."""
    store.ensure_subj_index()
    fn = _bgp_count_fn(store.mesh)
    with jax.enable_x64(True):
        return fn(
            np.uint32(p1),
            np.uint32(p2),
            store.by_obj[1],
            store.by_obj[2],
            store.by_obj_valid,
            *store.subj_index_parts,
        )


@lru_cache(maxsize=8)
def _bgp_count_fn(mesh):
    axis = mesh.axis_names[0]

    def body(p1, p2, op, oo, ov, subj_base, subj_tombs, subj_delta):
        op, oo, ov = op[0], oo[0], ov[0]
        # PRE-SORTED (pred<<32|subj) packs — no sort here.  Two-tier probe
        # (sharded_store.refresh_subj_index): a key's live multiplicity is
        # count(base) - count(tombstones) + count(delta adds); monolithic
        # indexes arrive with all-sentinel tomb/delta packs (counts 0).
        parts = (subj_base[0], subj_tombs[0], subj_delta[0])
        lv = ov & (op == p1)
        p2_hi = p2.astype(jnp.uint64) << np.uint64(32)
        # Invalid left rows get a probe key beyond every real packed key.
        # This relies on dictionary IDs never reaching 0xFFFFFFFF (IDs use
        # bits 0..30 + quoted bit 31, asserted in core.dictionary): a real
        # (pred, subj) = (0xFFFFFFFF, 0xFFFFFFFF) row would be
        # indistinguishable from the all-ones padding in the sorted packs
        # and a probe for it would overcount against padding entries.
        lkey = jnp.where(
            lv, p2_hi | oo.astype(jnp.uint64), np.uint64(0xFFFFFFFFFFFFFFFF)
        )

        def count(packed):
            lo = jnp.searchsorted(packed, lkey, side="left")
            hi = jnp.searchsorted(packed, lkey, side="right")
            return jnp.sum(jnp.where(lv, hi - lo, 0).astype(jnp.int32))

        total = count(parts[0]) - count(parts[1]) + count(parts[2])
        return lax.psum(total, axis)[None]

    spec = P(axis, None)
    return jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P(), P()) + (spec,) * 6,
            out_specs=P(axis),
        )
    )
