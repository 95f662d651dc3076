"""Device-side (JAX/XLA) join, dedup, and scan kernels with STATIC shapes.

The host joins in :mod:`kolibrie_tpu.ops.join` are numpy with dynamic output
sizes.  Under ``jit`` every shape must be static, so the device variants here
take an explicit output ``cap`` (capacity) and return validity masks.  The
caller picks / doubles the capacity on overflow (host-side recompile
fallback, SURVEY.md §7 "hard parts").

Replaces (TPU-natively — not a translation) the reference's hot loops:

- ``shared/src/join_algorithm.rs:19-131`` sorted-merge join → ``join_indices``
  (argsort + two ``searchsorted`` + static-size materialization).
- ``shared/src/index_manager.rs:253-340`` point/prefix index query →
  ``prefix_range`` over sorted columns.
- dedup ``compact_results`` (``join_algorithm.rs:446``) → ``sort_unique_rows``
  (``lax.sort`` multi-operand + first-occurrence scatter compaction).

All functions are pure and jittable; the per-shard bodies of the distributed
joins in :mod:`kolibrie_tpu.parallel` reuse them inside ``shard_map``.
"""

from __future__ import annotations

from functools import partial, wraps
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# Sentinel keys for masked (invalid) rows.  Left and right invalid rows get
# DIFFERENT sentinels so padding never joins with padding.  (Plain ints —
# u64 jnp scalars can only be constructed under the x64 scope below.)
_LPAD = 0xFFFFFFFFFFFFFFFE
_RPAD = 0xFFFFFFFFFFFFFFFF
_U32PAD = np.uint32(0xFFFFFFFF)


def _x64(fn):
    """Run (trace) ``fn`` with 64-bit types enabled, WITHOUT flipping the
    global JAX default: u64 packed join keys need real 64-bit ints, while the
    rest of the framework (ML stack) stays on the 32-bit defaults."""

    @wraps(fn)
    def wrapper(*args, **kwargs):
        with jax.enable_x64(True):
            return fn(*args, **kwargs)

    return wrapper


def pack2(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Pack two u32 columns into one u64 key (device mirror of host pack)."""
    return (a.astype(jnp.uint64) << np.uint64(32)) | b.astype(jnp.uint64)


def pack_key_multi(lcols, rcols, lvalid, rvalid, lpad=_LPAD, rpad=_RPAD):
    """Exact u64 keys for 3+ shared join columns: iterated dense-rank
    composition over the UNION of both sides, so equal column tuples get
    equal keys across sides (a per-side rank would not).  Each round is one
    sort + two searchsorteds over (nl + nr) rows — the same cost class as
    the join itself.  Invalid rows are sentinel-masked at the end (their
    garbage intermediate ranks never surface).  Device twin of the host
    ``ops/join.py::_pack_shared_keys`` 3+-column branch; shared by the
    device query engine and the device fixpoint's premise joins."""
    lk = lcols[0].astype(jnp.uint64)
    rk = rcols[0].astype(jnp.uint64)
    for lc, rc in zip(lcols[1:], rcols[1:]):
        union = jnp.sort(jnp.concatenate([lk, rk]))
        lr = jnp.searchsorted(union, lk).astype(jnp.uint64)
        rr = jnp.searchsorted(union, rk).astype(jnp.uint64)
        lk = (lr << jnp.uint64(32)) | lc.astype(jnp.uint64)
        rk = (rr << jnp.uint64(32)) | rc.astype(jnp.uint64)
    lk = jnp.where(lvalid, lk, jnp.uint64(lpad))
    rk = jnp.where(rvalid, rk, jnp.uint64(rpad))
    return lk, rk


@_x64
@partial(jax.jit, static_argnames="cap")
def join_indices(
    lkey: jnp.ndarray,
    rkey: jnp.ndarray,
    cap: int,
    lvalid: jnp.ndarray | None = None,
    rvalid: jnp.ndarray | None = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Equi-join: all (li, ri) with ``lkey[li] == rkey[ri]``.

    Returns ``(li, ri, valid, total)`` where the first three have static
    length ``cap`` and ``total`` is the true (unclipped) match count — if
    ``total > cap`` the caller must re-run with a larger capacity.
    """
    lkey = lkey.astype(jnp.uint64)
    rkey = rkey.astype(jnp.uint64)
    if lvalid is not None:
        lkey = jnp.where(lvalid, lkey, np.uint64(_LPAD))
    if rvalid is not None:
        rkey = jnp.where(rvalid, rkey, np.uint64(_RPAD))
    ln, rn = lkey.shape[0], rkey.shape[0]
    if ln == 0 or rn == 0:
        z = jnp.zeros(cap, dtype=jnp.int32)
        return z, z, jnp.zeros(cap, dtype=bool), jnp.int64(0)
    order = jnp.argsort(rkey)
    rsorted = rkey[order]
    # int32 positions/cumsum (i64 cumsum lowers to a VMEM-heavy
    # reduce-window on TPU); the TRUE match count is an i64 reduction so a
    # >2^31 blow-up is still detected by the caller's overflow check — the
    # wrapped i32 cum only affects rows invalid in that case anyway
    lo = jnp.searchsorted(rsorted, lkey, side="left").astype(jnp.int32)
    hi = jnp.searchsorted(rsorted, lkey, side="right").astype(jnp.int32)
    counts = hi - lo
    # left padding rows can never match right rows (distinct sentinels)
    cum = jnp.cumsum(counts)
    total = jnp.sum(counts.astype(jnp.int64)) if ln else jnp.int64(0)
    idx = jnp.arange(cap, dtype=jnp.int32)
    row = jnp.searchsorted(cum, idx, side="right").astype(jnp.int32)
    row_c = jnp.clip(row, 0, max(ln - 1, 0))
    start = cum[row_c] - counts[row_c]
    pos = lo[row_c] + (idx - start)
    valid = idx < total
    li = jnp.where(valid, row_c, 0)
    ri = jnp.where(valid, order[jnp.clip(pos, 0, max(rn - 1, 0))], 0).astype(
        jnp.int32
    )
    return li, ri, valid, total


@_x64
@partial(jax.jit, static_argnames="cap")
def join_indices_presorted(
    lkey: jnp.ndarray,
    rkey_sorted: jnp.ndarray,
    cap: int,
    lvalid: jnp.ndarray | None = None,
    rvalid_prefix: jnp.ndarray | None = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """:func:`join_indices` for a right side that is ALREADY sorted — skips
    the argsort, which dominates the join's device time.  The engine feeds
    this from store scans whose sort order makes the key column pre-sorted
    (the reference's PSO-index-driven merge join, join_algorithm.rs:19-131).

    ``rvalid_prefix`` must be a PREFIX mask (all valid rows first), as
    produced by a bare range scan: masked tail rows become the max sentinel,
    which keeps the array sorted.
    """
    lkey = lkey.astype(jnp.uint64)
    rkey = rkey_sorted.astype(jnp.uint64)
    if lvalid is not None:
        lkey = jnp.where(lvalid, lkey, np.uint64(_LPAD))
    if rvalid_prefix is not None:
        rkey = jnp.where(rvalid_prefix, rkey, np.uint64(_RPAD))
    ln, rn = lkey.shape[0], rkey.shape[0]
    if ln == 0 or rn == 0:
        z = jnp.zeros(cap, dtype=jnp.int32)
        return z, z, jnp.zeros(cap, dtype=bool), jnp.int32(0)
    # int32 positions/cumsum: i64 cumsum lowers to a VMEM-heavy
    # reduce-window on TPU and capacities are < 2^31 by construction.  The
    # TRUE match count is reported in i64 (a plain reduction) so a >2^31
    # blow-up is still detected by the caller's overflow check; the wrapped
    # i32 cum only affects rows that are invalid in that case anyway.
    lo = jnp.searchsorted(rkey, lkey, side="left").astype(jnp.int32)
    hi = jnp.searchsorted(rkey, lkey, side="right").astype(jnp.int32)
    counts = hi - lo
    cum = jnp.cumsum(counts)
    total = jnp.sum(counts.astype(jnp.int64))
    idx = jnp.arange(cap, dtype=jnp.int32)
    row = jnp.searchsorted(cum, idx, side="right").astype(jnp.int32)
    row_c = jnp.clip(row, 0, max(ln - 1, 0))
    start = cum[row_c] - counts[row_c]
    pos = lo[row_c] + (idx - start)
    valid = idx < total
    li = jnp.where(valid, row_c, 0)
    ri = jnp.where(valid, jnp.clip(pos, 0, max(rn - 1, 0)), 0)
    return li, ri, valid, total


@_x64
@jax.jit
def semi_join_mask(
    lkey: jnp.ndarray, rkey: jnp.ndarray, rvalid: jnp.ndarray | None = None
) -> jnp.ndarray:
    """Mask over left rows with >=1 match on the right (EXISTS)."""
    lkey = lkey.astype(jnp.uint64)
    rkey = rkey.astype(jnp.uint64)
    if rkey.shape[0] == 0:
        return jnp.zeros(lkey.shape[0], dtype=bool)
    if rvalid is not None:
        rkey = jnp.where(rvalid, rkey, np.uint64(_RPAD))
    rsorted = jnp.sort(rkey)
    idx = jnp.clip(jnp.searchsorted(rsorted, lkey), 0, rkey.shape[0] - 1)
    return rsorted[idx] == lkey


def _first_occurrence(cols_sorted: Sequence[jnp.ndarray]) -> jnp.ndarray:
    isnew = jnp.zeros(cols_sorted[0].shape[0], dtype=bool).at[0].set(True)
    for c in cols_sorted:
        isnew = isnew | jnp.concatenate([jnp.ones(1, bool), c[1:] != c[:-1]])
    return isnew


@_x64
@partial(jax.jit, static_argnames="cap")
def sort_unique_rows(
    cols: Sequence[jnp.ndarray],
    valid: jnp.ndarray,
    cap: int,
) -> Tuple[Tuple[jnp.ndarray, ...], jnp.ndarray, jnp.ndarray]:
    """Deduplicate rows given as parallel u32 columns (e.g. (s, p, o)).

    Multi-operand ``lax.sort`` orders rows lexicographically (invalid rows
    forced to the u32-max sentinel so they sink to the end and collapse);
    first-occurrence rows are compacted to the front by masked scatter.
    Returns ``(unique_cols, out_valid, n_unique)`` with static length ``cap``.
    """
    cols = [c.astype(jnp.uint32) for c in cols]
    cols = [jnp.where(valid, c, _U32PAD) for c in cols]
    sorted_ops = lax.sort(tuple(cols), num_keys=len(cols))
    isnew = _first_occurrence(sorted_ops)
    # the (all-sentinel) padding block contributes exactly one "new" row if
    # any padding exists; drop it by re-checking validity of the row itself
    row_valid = jnp.ones_like(isnew)
    for c in sorted_ops:
        row_valid = row_valid & (c != _U32PAD)
    # a real row may legitimately contain u32-max?  Dictionary IDs are
    # restricted to bits 0..30 (+bit 31 for quoted triples) so 0xFFFFFFFF is
    # never a real ID (reference: shared/src/dictionary.rs:36-40).
    isnew = isnew & row_valid
    dest = jnp.cumsum(isnew) - 1
    dest = jnp.where(isnew, dest, cap)  # dropped by scatter mode="drop"
    n_unique = jnp.sum(isnew)
    outs = []
    for c in sorted_ops:
        out = jnp.zeros(cap, dtype=jnp.uint32)
        outs.append(out.at[dest].set(c, mode="drop"))
    out_valid = jnp.arange(cap) < n_unique
    return tuple(outs), out_valid, n_unique


@_x64
@partial(jax.jit, static_argnames="cap")
def set_difference_rows(
    cols: Sequence[jnp.ndarray],
    valid: jnp.ndarray,
    other_cols: Sequence[jnp.ndarray],
    other_valid: jnp.ndarray,
    cap: int,
) -> Tuple[Tuple[jnp.ndarray, ...], jnp.ndarray, jnp.ndarray]:
    """Rows of ``cols`` not present in ``other_cols`` (both (s,p,o)-style).

    The semi-naive "subtract already-known facts" step; also ISTREAM/DSTREAM
    window deltas (reference: rsp/r2s.rs:37-58).  Membership is an exact
    progressive pairwise pack (see :func:`_row_membership`).
    """
    ours = [jnp.where(valid, c.astype(jnp.uint32), np.uint32(0xFFFFFFFE)) for c in cols]
    theirs = [
        jnp.where(other_valid, c.astype(jnp.uint32), _U32PAD) for c in other_cols
    ]
    member = _row_membership(ours, theirs)
    keep = valid & ~member
    # compact surviving rows to the front
    dest = jnp.cumsum(keep) - 1
    dest = jnp.where(keep, dest, cap)
    n_out = jnp.sum(keep)
    outs = []
    for c in cols:
        out = jnp.zeros(cap, dtype=jnp.uint32)
        outs.append(out.at[dest].set(c.astype(jnp.uint32), mode="drop"))
    out_valid = jnp.arange(cap) < n_out
    return tuple(outs), out_valid, n_out


def _row_membership(
    ours: Sequence[jnp.ndarray], theirs: Sequence[jnp.ndarray]
) -> jnp.ndarray:
    """For each row of ``ours``: does an equal row exist in ``theirs``?

    Progressive pairwise packing keeps keys exact: (a,b,c) → (pack2(a,b)
    ranked densely against theirs, then packed with c).  For u32 triple
    columns two levels suffice.
    """
    if len(ours) == 1:
        return semi_join_mask(ours[0].astype(jnp.uint64), theirs[0].astype(jnp.uint64))
    if len(ours) == 2:
        return semi_join_mask(pack2(ours[0], ours[1]), pack2(theirs[0], theirs[1]))
    # 3 columns: dense-rank the (s,p) pair over the union, then pack with o
    osp = pack2(ours[0], ours[1])
    tsp = pack2(theirs[0], theirs[1])
    union = jnp.concatenate([osp, tsp])
    sorted_u = jnp.sort(union)
    rank_o = jnp.searchsorted(sorted_u, osp).astype(jnp.uint32)
    rank_t = jnp.searchsorted(sorted_u, tsp).astype(jnp.uint32)
    return semi_join_mask(
        pack2(rank_o, ours[2]), pack2(rank_t, theirs[2])
    )


@_x64
@partial(jax.jit, static_argnames="cap")
def prefix_range_scan(
    sorted_key: jnp.ndarray,
    payload: Sequence[jnp.ndarray],
    key_lo: jnp.ndarray,
    key_hi: jnp.ndarray,
    cap: int,
) -> Tuple[Tuple[jnp.ndarray, ...], jnp.ndarray, jnp.ndarray]:
    """Gather rows whose sorted u64 key lies in [key_lo, key_hi).

    The device analogue of the reference's six-permutation index ``query()``
    dispatch (``shared/src/index_manager.rs:253-340``): a (S,P,?) scan is a
    ``pack2(s,p)``-prefixed range over the SPO order, etc.
    """
    lo = jnp.searchsorted(sorted_key, key_lo, side="left")
    hi = jnp.searchsorted(sorted_key, key_hi, side="left")
    n = hi - lo
    idx = jnp.arange(cap, dtype=jnp.int64)
    src = jnp.clip(lo + idx, 0, max(sorted_key.shape[0] - 1, 0))
    valid = idx < n
    outs = tuple(
        jnp.where(valid, c[src], 0).astype(c.dtype) for c in payload
    )
    return outs, valid, n


@_x64
@jax.jit
def compare_filter(
    col: jnp.ndarray, op_code: jnp.ndarray, rhs: jnp.ndarray
) -> jnp.ndarray:
    """Vectorized numeric-ID comparison — the VPU replacement for the SSE2/
    NEON filter paths (``sparql_database.rs:1497-1785``).  ``op_code``:
    0 '=', 1 '!=', 2 '>', 3 '<', 4 '>=', 5 '<='.
    """
    c = col.astype(jnp.int64)
    r = rhs.astype(jnp.int64)
    return lax.switch(
        op_code,
        [
            lambda: c == r,
            lambda: c != r,
            lambda: c > r,
            lambda: c < r,
            lambda: c >= r,
            lambda: c <= r,
        ],
    )
