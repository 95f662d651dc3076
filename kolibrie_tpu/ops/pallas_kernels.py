"""Pallas TPU kernels for the hot physical operators.

BASELINE.json's north star: the Volcano physical operators — BGP
triple-pattern scan, hash-join, SIMD filter/aggregate — become Pallas
kernels.  This module provides the TPU-native kernel path:

- :func:`merge_join` — sorted merge-join materialization as a tiled Pallas
  kernel.  Replaces (TPU-natively) the reference's PSO-index-driven sorted
  merge join ``shared/src/join_algorithm.rs:19-131``.  The classic expansion
  (cumsum + searchsorted + gather) is re-formulated gather-free: a
  merge-path partition assigns each 128-wide output tile a provably bounded
  window of left rows, and all per-output row lookups happen inside VMEM as
  one-hot masked reductions on the VPU.
- :func:`lex_probe_select` / :func:`lex_probe_validate` — the WCOJ
  level's per-slot lex-probe expansion fused on the VPU: base/delta
  merge-by-rank value select, first-of-run dedup, smallest-accessor
  choice, tombstone-aware live-existence and the base-representative
  tie-break run as int32 boolean algebra in VMEM instead of a dozen
  separate XLA ops round-tripping every per-slot intermediate through
  HBM.  The lex ``searchsorted`` range computation itself stays an XLA
  pre-pass (:func:`kolibrie_tpu.ops.wcoj.range_search` — Mosaic has no
  vector gather, so a binary search over HBM-resident columns cannot
  live in the kernel); row oracle: ``ops/wcoj.py::host_lex_probe``.
- :func:`filter_mask` — fused pattern/constant compare over dictionary-ID
  columns (the VPU equivalent of the reference's SSE2/NEON
  ``apply_filters_simd``, ``kolibrie/src/sparql_database.rs:1497-1785``).
- :func:`tag_combine` — vectorized semiring ⊕/⊗ on f32 tag columns
  (MinMax / AddMult / Expiration semirings of
  ``shared/src/provenance.rs:69-146,460-479``).

All entry points fall back to the Pallas interpreter off-TPU, so the same
code paths are exercised by the CPU test suite.

Merge-path window bound
-----------------------
After compacting the left side to rows with at least one match, every left
row in a tile contributes ≥ 1 output, so the rows feeding outputs
``[t*T, (t+1)*T)`` span at most ``T`` consecutive compacted rows starting at
``row_start[t] = searchsorted(cum, t*T, 'right')``.

What the pre-pass pays for
--------------------------
The kernel needs each left key's run in the sorted right keys
(``low``, ``high``): two binary searches whose cost is left keys x trips
(7-8 ns each on a v5e), which at a scan's template capacity was nearly all
of a keyed lookup (PERF.md section 6, PR 39: 65,536 slots searched to place
6-34 rows).  Where the caller passes a validity mask the searches run over
the live extent of the left keys instead, in blocks of ``_SEARCH_BLOCK``
under a traced trip count (:func:`_run_bounds`): a join pays for the keys it
has, not for the slots its left side was compiled with, and the executable
stays one a template.  Without a mask (``merge_join``,
``ranked_merge_join_indices``) the extent is the static width: the same
searches, every slot.  The compaction of the matched rows runs in such
blocks too (:func:`_matched_first`, up to the last matched row; no sort: a
sort is what the TPU compiler spends longest on).  What is still paid at
the compiled width: the cumsums, the gathers by the compaction's order and
the packed row table.

Mosaic block constraints (and how the kernel scales past VMEM)
--------------------------------------------------------------
Mosaic requires output blocks with sublane dim a multiple of 8 — so each
kernel invocation produces a ``(G=8, T)`` block, an unrolled loop over 8
sub-tiles.  It also rejects DMA windows at arbitrary sublane offsets, so
the per-row arrays cannot be manually DMA'd from ``row_start[t]``.
Instead each array is passed TWICE as a block-quantized ``(BW, 1)`` input
(lane dim 1 equals the full array — legal) whose index map reads the
prefetched row starts: blocks ``rstart//BW`` and ``rstart//BW + 1``
together always cover the group's row window; the kernel concatenates the
two resident blocks and dynamic-slices each sub-tile's ``W = T + 8`` row
window from VMEM.  Per-group residency is ``10 * BW * 4`` bytes —
independent of the left side's total length, so there is no whole-array
VMEM cliff.
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

TILE = 128  # output tile width = one lane row
G = 8  # sub-tiles per kernel invocation (Mosaic sublane granularity)
_WPAD = 8  # sublane alignment padding for the left-row window
W = TILE + _WPAD  # per-sub-tile row window
BW = 2048  # block-quantized row-window granule (two consecutive blocks
#            always cover a group's G*TILE + W row span: G*TILE + W +
#            (BW - 1) <= 2 * BW)
# Verified-safe SINGLE-LAUNCH kernel range on the current Mosaic toolchain
# (see merge_join docstring); larger left sides use the chunk-level driver
# (_pallas_join_core_chunked), which keeps every launch inside this range.
_PALLAS_MAX_LEFT_ROWS = 393216
# Outputs per chunked-driver launch: 1024 tiles / 128 groups per launch;
# local row windows are bounded by _CHUNK_OUT + 1 rows — an order of
# magnitude under the fault boundary.
_CHUNK_OUT = 131072
_CHUNK_ROWS = 256  # grid chunk height for elementwise kernels (128KB/col)
# Left keys a trip of the prepass's run-bound searches covers where the
# caller knows how far its left rows reach (_run_bounds).  Chosen by the gate
# of PERF.md §6, PR 39, on a v5e: a block's fixed cost is small, so 1,024
# ties 4,096 and 16,384 at 10,000 live rows and wins where 6-34 are live.
_SEARCH_BLOCK = 1024


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pallas_call(*args, **kwargs):
    """``pl.pallas_call`` with x64 promotion OFF at trace time (kernel body
    and index maps alike).  Callers (the device engine, the fixpoint) trace
    whole plans under ``jax.enable_x64``, where ``jnp.sum`` accumulates i32
    in i64 — and Mosaic's i64→i32 convert lowering recurses without
    terminating.  Operands are concretely i32/f32, so only Python-literal
    promotion changes.  Every kernel in this module must launch through
    this wrapper."""
    inner = pl.pallas_call(*args, **kwargs)

    def launch(*operands):
        with jax.enable_x64(False):
            return inner(*operands)

    return launch


_PALLAS_MODES = ("off", "auto", "force")


def pallas_mode() -> str:
    """The engine-wide Pallas routing mode: ``off`` | ``auto`` | ``force``.

    ``KOLIBRIE_PALLAS`` is THE switch for every Pallas kernel path (the
    merge-join tile kernel, the WCOJ ``lex_probe_*`` kernels, the
    distributed shard-local join):

    - ``off`` (also ``0``/``false``): XLA formulations everywhere;
    - ``auto`` (default): kernels on real TPU, XLA off-TPU (interpreted
      Pallas is far slower than XLA on CPU, so the test suite keeps the
      XLA path unless it opts in);
    - ``force`` (also ``1``): kernels everywhere — off-TPU they run under
      the Pallas interpreter, which is how the CPU tier-1 suite exercises
      the exact kernel code paths.

    The mode participates in the template fingerprint and the executor's
    ``env_sig`` exactly like ``KOLIBRIE_WCOJ`` / ``KOLIBRIE_PLAN_INTERP``:
    a mode flip lands in a fresh plan slot, never a stale replay.  An
    unrecognized value falls back to ``auto``.
    """
    import os

    v = os.environ.get("KOLIBRIE_PALLAS", "auto").strip().lower()
    if v in _PALLAS_MODES:
        return v
    if v in ("0", "false"):
        return "off"
    if v in ("1", "true"):
        return "force"
    return "auto"


def pallas_enabled() -> bool:
    """Resolve :func:`pallas_mode` against the backend: should eligible
    operators route through the Pallas kernels right now?"""
    mode = pallas_mode()
    if mode == "force":
        return True
    if mode == "off":
        return False
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# merge join
# ---------------------------------------------------------------------------


_NCOLS = 5  # packed per-row columns: lkey, lval, low, cum, cumprev


def _merge_join_kernel(
    row_start_ref,  # scalar-prefetch: (n_tiles + 1,) int32; last slot = total
    rows_a_ref,  # (1, BW, 5) block at rstart//BW: packed per-row columns
    rows_b_ref,  # (1, BW, 5) block at rstart//BW + 1
    key_out_ref,  # (G, T) block: joined key
    lval_out_ref,  # (G, T) block: left payload
    pos_out_ref,  # (G, T) block: right row index (caller gathers payload)
    valid_out_ref,  # (G, T) block: int32 0/1 mask
    rows_s,  # VMEM scratch (2*BW, 5): the two resident blocks, contiguous
):
    g = pl.program_id(0)
    # first resident row; lax.div (trunc == floor: row starts are
    # non-negative) with a concrete i32 divisor — under a caller's
    # jax.enable_x64 the weak literal `// BW` lowers as an i64 constant
    # whose floor_divide helper call collides with the i32 instantiation
    base = lax.div(row_start_ref[g * G], jnp.int32(BW)) * BW
    total = row_start_ref[pl.num_programs(0) * G]
    # Global index of this launch's first output: 0 for the whole-join
    # launch; chunk_index * chunk_out for the chunked driver, whose row
    # table, row starts and tile ids are all launch-local while cum/low
    # stay global (see _pallas_join_core_chunked).
    kbase = row_start_ref[pl.num_programs(0) * G + 1]

    # Two consecutive BW-row blocks of the packed per-row table are
    # VMEM-resident (block-quantized index maps driven by the prefetched
    # row starts); together they cover this group's row span.  Stitch them
    # into one contiguous scratch so sub-tile windows can dynamic-slice
    # across the block boundary (ref reads support dynamic sublane
    # offsets; value dynamic_slice does not lower).
    rows_s[0:BW, :] = rows_a_ref[0]
    rows_s[BW : 2 * BW, :] = rows_b_ref[0]

    for r in range(G):
        t = g * G + r
        # Window start within the residency.  Clamped: tiles past the last
        # match carry row_start == n_rows, which can lie far outside this
        # group's two resident blocks — their outputs are zeroed by the
        # valid mask below, so any in-bounds window serves; without the
        # clamp the reads are undefined behavior.  Legitimate windows are
        # bounded by (BW-1) + G*TILE < 2*BW - W and are never clamped.
        off = jnp.minimum(row_start_ref[t] - base, 2 * BW - W)

        win = rows_s[pl.ds(off, W), :]  # (W, 5)
        lkey_w = win[:, 0:1]  # (W, 1)
        lval_w = win[:, 1:2]
        low_w = win[:, 2:3]
        cum_w = win[:, 3:4]
        cumprev0 = rows_s[off, 4]  # off already clamped in-bounds above

        k = kbase + t * TILE + jax.lax.broadcasted_iota(
            jnp.int32, (1, TILE), 1
        )  # (1, T)

        # M[j, k] = does output k lie past row j's last output?  Kept as
        # int32 masks throughout — Mosaic has no i1-vector select.
        m = (cum_w <= k).astype(jnp.int32)  # (W, T) broadcast
        row_local = jnp.sum(m, axis=0, keepdims=True)  # (1, T)

        # Row attributes via one-hot masked reduction (gather-free).
        onehot = (
            jax.lax.broadcasted_iota(jnp.int32, (W, TILE), 0) == row_local
        ).astype(jnp.int32)  # (W, T)
        key_k = jnp.sum(onehot * lkey_w, axis=0, keepdims=True)
        lval_k = jnp.sum(onehot * lval_w, axis=0, keepdims=True)
        low_k = jnp.sum(onehot * low_w, axis=0, keepdims=True)

        # Outputs already emitted before row(k): the largest qualifying
        # cum, or the window's exclusive prefix when row_local == 0.
        cum_ex = jnp.maximum(
            jnp.max(m * cum_w, axis=0, keepdims=True), cumprev0
        )

        valid = (k < total).astype(jnp.int32)
        pos = low_k + (k - cum_ex)
        key_out_ref[r, :] = (valid * key_k)[0, :]
        lval_out_ref[r, :] = (valid * lval_k)[0, :]
        pos_out_ref[r, :] = (valid * pos)[0, :]
        valid_out_ref[r, :] = valid[0, :]


def _run_bounds(lkey_u, rkey_u, extent):
    """``(low, high)``: each left key's run in the sorted right keys.  With
    an ``extent`` (a traced int32, one past the last left row that can
    match) the searches cover the left keys in blocks of ``_SEARCH_BLOCK``
    under a traced trip count and stop at the block that holds the extent:
    rows past it keep ``low == high`` (no match), which is what their
    sentinel key finds.  Without one the trip count is the static width:
    one search over every slot."""

    def search(keys):
        return tuple(
            jnp.searchsorted(rkey_u, keys, side=side).astype(jnp.int32)
            for side in ("left", "right")
        )

    n = lkey_u.shape[0]
    if extent is None:
        return search(lkey_u)
    block = min(_SEARCH_BLOCK, n)

    def trip(i, bounds):
        # a last block that would run off the end is clamped back by slice
        # and update alike: its overlap is searched twice, to equal results
        start = (i * jnp.int32(block),)
        found = search(lax.dynamic_slice(lkey_u, start, (block,)))
        return tuple(
            lax.dynamic_update_slice(buf, x, start)
            for buf, x in zip(bounds, found)
        )

    none = jnp.zeros(n, jnp.int32)
    trips = lax.div(extent + jnp.int32(block - 1), jnp.int32(block))
    return lax.fori_loop(jnp.int32(0), trips, trip, (none, none))


def _matched_first(counts):
    """``(order, n_matched)``: the rows with at least one match, in their
    order, as the first ``n_matched`` entries of ``order`` (some row's index
    in each later entry, for the caller to mask).  A stream compaction in
    blocks of ``_SEARCH_BLOCK`` rows under a traced trip count, up to the
    block of the last matched row: a block's matched rows take the slots of
    their running number among them (a one-hot sum over the block, no gather
    and no scatter) and the block is written where the matched rows before
    it end, over the unmatched tail of the block before.  It took the place
    of a stable ``argsort(counts == 0)``, which gave the same first
    ``n_matched`` entries but sorted every slot, and a sort is what the TPU
    compiler spends longest on (PERF.md section 6, PR 40, the gate on a
    v5e: 16.8 s to compile it at 65,536 slots, 28.6 s and 20.7 ms a call at
    8,388,608, where this form takes 3.0 ms for 40,000 matched rows)."""
    n = counts.shape[0]
    if n == 0:
        return jnp.zeros(0, jnp.int32), jnp.int32(0)
    block = min(_SEARCH_BLOCK, n)
    n_p = -(-n // block) * block  # whole blocks
    hit = jnp.pad(counts > 0, (0, n_p - n))
    matched = jnp.cumsum(hit.astype(jnp.int32))
    n_matched = matched[-1]
    slots = jnp.arange(block, dtype=jnp.int32)

    def trip(i, order):
        start = i * jnp.int32(block)
        hit_b = lax.dynamic_slice(hit, (start,), (block,))
        upto = lax.dynamic_slice(matched, (start,), (block,))
        before = upto[0] - hit_b[0].astype(jnp.int32)
        slot_of = upto - before - 1  # of a matched row, among its block's
        mine = hit_b[None, :] & (slot_of[None, :] == slots[:, None])
        rows = jnp.sum(
            jnp.where(mine, start + slots[None, :], 0), axis=1, dtype=jnp.int32
        )
        return lax.dynamic_update_slice(order, rows, (before,))

    last = jnp.max(jnp.where(hit, jnp.arange(1, n_p + 1, dtype=jnp.int32), 0))
    trips = lax.div(last + jnp.int32(block - 1), jnp.int32(block))
    # one block of room past the end: a block written at ``before`` fits
    order = lax.fori_loop(
        jnp.int32(0), trips, trip, jnp.zeros(n_p + block, jnp.int32)
    )
    return order[:n], n_matched


def searched_keys(width: int, rows: Optional[int] = None) -> int:
    """Left keys the run-bound searches of one prepass cover: the whole
    ``width`` where the call gives no extent, else the blocks of
    :func:`_run_bounds` up to the one that holds row ``rows``."""
    if rows is None or not width:
        return width
    block = min(_SEARCH_BLOCK, width)
    return -(-min(rows, width) // block) * block


def _join_prepass(lkey_u, lval, rkey_u, extent=None):
    """Shared XLA pre-pass of both kernel drivers: searchsorted run bounds
    (over the left keys up to ``extent``, see :func:`_run_bounds`), stable
    compaction of matched rows to the front, cumsum.  Returns
    ``(lkey_c, lval_c, low_c, cum, cumprev, total, total64)`` — the packed
    per-row columns (bitcast i32), the global output-offset prefix, the i32
    device total and the exact i64 match count."""

    def _bc(x):
        return lax.bitcast_convert_type(x.astype(jnp.uint32), jnp.int32)

    low, high = _run_bounds(lkey_u, rkey_u, extent)
    counts = high - low
    with jax.enable_x64(True):
        total64 = jnp.sum(counts.astype(jnp.int64))
    # Compact to rows with ≥1 match, in their order; the slots after them
    # count nothing.
    order, n_matched = _matched_first(counts)
    lkey_c = _bc(lkey_u)[order]
    lval_c = _bc(lval)[order]
    low_c = low[order]
    live = jnp.arange(counts.shape[0], dtype=jnp.int32) < n_matched
    counts_c = jnp.where(live, counts[order], 0)
    cum = jnp.cumsum(counts_c).astype(jnp.int32)
    total = cum[-1] if cum.shape[0] else jnp.int32(0)
    cumprev = jnp.concatenate([jnp.zeros(1, jnp.int32), cum[:-1]])
    return lkey_c, lval_c, low_c, cum, cumprev, total, total64


def _pallas_join_core(
    lkey_u: jnp.ndarray,
    lval: jnp.ndarray,
    rkey_u: jnp.ndarray,
    cap: int,
    extent: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Shared Pallas pipeline: returns ``(key, lval, pos, valid, total)``
    where ``pos`` is the matching RIGHT row index (int32) and outputs have
    static length ``cap`` rounded up to whole (G, TILE) blocks.  ``rkey_u``
    must be sorted ascending; ``lkey_u`` may be in any order (the merge-path
    partition runs over the cumsum of per-left-row match counts, which is
    monotone regardless of left key order).  ``total`` is an exact i64
    match count.  ``extent`` bounds the prepass's searches
    (:func:`_run_bounds`).
    """
    n_groups = max(1, -(-cap // (G * TILE)))
    n_tiles = n_groups * G
    cap = n_tiles * TILE

    lkey_c, lval_c, low_c, cum, cumprev, total, total64 = _join_prepass(
        lkey_u, lval, rkey_u, extent
    )

    # Merge-path partition: first compacted row feeding each output tile.
    tile_starts = jnp.arange(n_tiles, dtype=jnp.int32) * TILE
    row_start = jnp.searchsorted(cum, tile_starts, side="right").astype(
        jnp.int32
    )
    row_start = jnp.concatenate(
        [row_start, total[None], jnp.zeros(1, jnp.int32)]
    )

    # Pack the five per-row columns into one (N, 5) table (linear in HBM;
    # ONE lane-padded VMEM block instead of five), padded to whole BW
    # blocks PLUS one spare block (the second resident block's index is
    # always rstart//BW + 1).  Padded rows carry cum == max so they never
    # match.
    n_rows = lkey_c.shape[0]
    pad_to = (-(-(n_rows + W) // BW) + 1) * BW
    big = jnp.int32(np.iinfo(np.int32).max)
    rows_p = jnp.stack([lkey_c, lval_c, low_c, cum, cumprev], axis=1)
    pad_row = jnp.array([[0, 0, 0, big, big]], jnp.int32)
    rows_p = jnp.concatenate(
        [rows_p, jnp.broadcast_to(pad_row, (pad_to - n_rows, _NCOLS))]
    )
    # Leading block dimension: the resident-block index must ride a plain
    # array dimension — HBM sublane offsets saturate a ~2^19 descriptor
    # field, which faults for left sides past ~500K rows.
    rows_p = rows_p.reshape(pad_to // BW, BW, _NCOLS)

    out_block = pl.BlockSpec((G, TILE), lambda g, *_: (g, 0))

    nb = pad_to // BW

    def blk_a(g, rs):
        # clamp: the pipeline evaluates index maps one step past the grid,
        # where rs[g*G] is the TOTAL (a match count, not a row index).
        # lax.div (trunc == floor: row starts are non-negative) with a
        # concrete i32 divisor — index maps lower under the CALLER's x64
        # config, and `// BW` there emits a floor_divide helper call whose
        # i64 operand collides with the kernel body's i32 instantiation.
        return (jnp.minimum(lax.div(rs[g * G], jnp.int32(BW)), nb - 2), 0, 0)

    def blk_b(g, rs):
        return (jnp.minimum(lax.div(rs[g * G], jnp.int32(BW)) + 1, nb - 1), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_groups,),
        # the packed table rides as TWO consecutive block-quantized
        # (1, BW, 5) residents (see module docstring)
        in_specs=[
            pl.BlockSpec((1, BW, _NCOLS), blk_a),
            pl.BlockSpec((1, BW, _NCOLS), blk_b),
        ],
        out_specs=[out_block] * 4,
        scratch_shapes=[pltpu.VMEM((2 * BW, _NCOLS), jnp.int32)],
    )
    # Inside a shard_map body with vma checking ON, the kernel's outputs
    # must declare how they vary across mesh axes; propagate the operand's
    # varying-mesh-axes set (empty outside shard_map).  NOTE: the dist
    # callers currently run with check_vma=False (jax's checker still
    # rejects the kernel's internal dynamic_slice), making this branch
    # dormant — it exists so the escape hatch can be dropped the moment
    # jax accepts pallas_call under vma checking.
    vma = getattr(jax.typeof(lkey_u), "vma", None)
    kwargs = {"vma": vma} if vma else {}
    out_shape = [
        jax.ShapeDtypeStruct((n_tiles, TILE), jnp.int32, **kwargs)
        for _ in range(4)
    ]
    key_o, lval_o, pos_o, valid_o = _pallas_call(
        _merge_join_kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=_interpret(),
    )(row_start, rows_p, rows_p)

    key_o = lax.bitcast_convert_type(key_o.reshape(cap), jnp.uint32)
    lval_o = lax.bitcast_convert_type(lval_o.reshape(cap), jnp.uint32)
    pos_o = pos_o.reshape(cap)
    valid_o = valid_o.reshape(cap).astype(bool)
    return key_o, lval_o, pos_o, valid_o, total64


def _pallas_join_core_chunked(
    lkey_u: jnp.ndarray,
    lval: jnp.ndarray,
    rkey_u: jnp.ndarray,
    cap: int,
    chunk_out: int,
    extent: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Chunk-level merge-path driver: same tile kernel, bounded local windows.

    Lifts the ``_PALLAS_MAX_LEFT_ROWS`` limit by hoisting the merge-path
    partition one level up: the output space is cut into ``chunk_out``-wide
    ranges, and because every compacted left row emits >= 1 output, the rows
    feeding outputs ``[a, b)`` span at most ``b - a + 1`` compacted rows.
    Each launch therefore dynamic-slices a bounded local window of the
    packed row table and passes LOCAL row starts — offsets never approach
    the empirical 2^19 Mosaic fault boundary regardless of total left size,
    and each launch's grid is a fixed ``chunk_out / 1024`` groups (vs the
    multi-thousand-tile grids of the faulting regime).  ``cum``/``low``
    columns stay GLOBAL; the kernel offsets its output ids by the launch's
    ``kbase`` prefetch slot, so the concatenation of chunk outputs is
    bit-identical to the unchunked kernel's output.  Total grid work across
    chunks equals the unchunked kernel's; ``lax.scan`` reuses ONE compiled
    kernel across chunks.  Same return contract as
    :func:`_pallas_join_core` with outputs of length
    ``n_chunks * chunk_out >= cap``.
    """
    if chunk_out % (G * TILE):
        raise ValueError("chunk_out must be a multiple of G * TILE")
    n_chunks = max(1, -(-cap // chunk_out))
    t_c = chunk_out // TILE  # tiles per chunk
    nb_loc = -(-(chunk_out + W) // BW) + 1  # resident-quantized local blocks
    l_win = nb_loc * BW  # local row window (covers chunk_out + 1 + W rows)

    lkey_c, lval_c, low_c, cum, cumprev, total, total64 = _join_prepass(
        lkey_u, lval, rkey_u, extent
    )

    # Packed table stays FLAT (the local slice is reshaped per chunk);
    # l_win rows of padding guarantee every slice is in-bounds unclamped
    # (slice starts are row indices <= n_rows).
    big = jnp.int32(np.iinfo(np.int32).max)
    rows_p = jnp.stack([lkey_c, lval_c, low_c, cum, cumprev], axis=1)
    pad_row = jnp.array([[0, 0, 0, big, big]], jnp.int32)
    rows_p = jnp.concatenate(
        [rows_p, jnp.broadcast_to(pad_row, (l_win, _NCOLS))]
    )

    tile_starts = jnp.arange(n_chunks * t_c, dtype=jnp.int32) * TILE
    row_start_g = jnp.searchsorted(cum, tile_starts, side="right").astype(
        jnp.int32
    )

    out_block = pl.BlockSpec((G, TILE), lambda g, *_: (g, 0))

    def blk_a(g, rs):
        # lax.div + i32 divisor: see the unchunked blk_a on x64 lowering
        return (jnp.minimum(lax.div(rs[g * G], jnp.int32(BW)), nb_loc - 2), 0, 0)

    def blk_b(g, rs):
        return (
            jnp.minimum(lax.div(rs[g * G], jnp.int32(BW)) + 1, nb_loc - 1),
            0,
            0,
        )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(t_c // G,),
        in_specs=[
            pl.BlockSpec((1, BW, _NCOLS), blk_a),
            pl.BlockSpec((1, BW, _NCOLS), blk_b),
        ],
        out_specs=[out_block] * 4,
        scratch_shapes=[pltpu.VMEM((2 * BW, _NCOLS), jnp.int32)],
    )
    vma = getattr(jax.typeof(lkey_u), "vma", None)
    kwargs = {"vma": vma} if vma else {}
    out_shape = [
        jax.ShapeDtypeStruct((t_c, TILE), jnp.int32, **kwargs)
        for _ in range(4)
    ]

    def chunk_body(_, c):
        row_base = row_start_g[c * t_c]
        rs_local = (
            lax.dynamic_slice(row_start_g, (c * t_c,), (t_c,)) - row_base
        )
        # Tiles past the last match carry row_start == n_rows; clamp their
        # LOCAL starts to the window (their outputs are masked by the
        # valid bit).  Legitimate local starts are <= chunk_out + 1 and
        # are never clamped.
        rs_local = jnp.minimum(rs_local, jnp.int32(chunk_out + W))
        pref = jnp.concatenate(
            [rs_local, total[None], (c * chunk_out)[None].astype(jnp.int32)]
        )
        # Both slice indices must share a dtype: a bare Python 0 promotes
        # to i64 under the callers' jax.enable_x64 traces and fails.
        rows_loc = lax.dynamic_slice(
            rows_p, (row_base, jnp.int32(0)), (l_win, _NCOLS)
        ).reshape(nb_loc, BW, _NCOLS)
        outs = _pallas_call(
            _merge_join_kernel,
            grid_spec=grid_spec,
            out_shape=out_shape,
            interpret=_interpret(),
        )(pref, rows_loc, rows_loc)
        return None, outs

    _, (key_s, lval_s, pos_s, valid_s) = lax.scan(
        chunk_body, None, jnp.arange(n_chunks, dtype=jnp.int32)
    )
    n_out = n_chunks * chunk_out
    key_o = lax.bitcast_convert_type(key_s.reshape(n_out), jnp.uint32)
    lval_o = lax.bitcast_convert_type(lval_s.reshape(n_out), jnp.uint32)
    pos_o = pos_s.reshape(n_out)
    valid_o = valid_s.reshape(n_out).astype(bool)
    return key_o, lval_o, pos_o, valid_o, total64


def pallas_chunked_enabled() -> bool:
    """Route left sides past ``_PALLAS_MAX_LEFT_ROWS`` through the chunked
    kernel driver (default) instead of the pure-XLA formulation.
    ``KOLIBRIE_PALLAS_CHUNKED=0`` restores the XLA fallback (checked at
    trace time — set it before first use)."""
    import os

    return os.environ.get("KOLIBRIE_PALLAS_CHUNKED") != "0"


@partial(jax.jit, static_argnames=("cap", "chunk_out"))
def merge_join(
    lkey: jnp.ndarray,
    lval: jnp.ndarray,
    rkey: jnp.ndarray,
    rval: jnp.ndarray,
    cap: int,
    chunk_out: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Equi-join of two runs (right sorted), Pallas-tiled materialization.

    ``rkey`` must be sorted ascending (``lkey`` may be in any order).
    Returns ``(key, lval, rval, valid, total)`` of static length ``cap``
    rounded up to whole tiles (``total`` is the true match count; if
    ``total > cap`` the caller re-runs with a larger capacity — the standard
    static-shape contract of :mod:`kolibrie_tpu.ops.device_join`).

    Pipeline: XLA pre-pass (searchsorted run bounds, nonzero-row compaction,
    cumsum, per-tile merge-path partition) → Pallas tile kernel (gather-free
    one-hot materialization) → one XLA row gather for the right payload.

    Keys/payloads are treated as u32; inside the kernel they ride as
    bitcast int32 (pure passthrough, exact for the full u32 range — the
    sorted-order-sensitive searchsorted runs on the u32 originals).

    Inputs past ``_PALLAS_MAX_LEFT_ROWS`` route to the chunk-level driver
    (:func:`_pallas_join_core_chunked`): the current Mosaic toolchain
    raises a device fault once row-start offsets cross 2^19 under
    multi-thousand-tile grids (verified empirically on v5e; block-index,
    pipeline-lookahead and SMEM-size causes ruled out), so the
    single-launch kernel is gated to the proven range and larger inputs
    run the same kernel per bounded output chunk.  ``chunk_out`` (a
    multiple of 1024) forces the chunked driver with that chunk width —
    used by tests; production picks ``_CHUNK_OUT`` automatically.
    ``KOLIBRIE_PALLAS_CHUNKED=0`` restores the pure-XLA fallback (the
    same algorithm — searchsorted + cumsum expansion — gather-based).
    """
    lkey_u = lkey.astype(jnp.uint32)
    rkey_u = rkey.astype(jnp.uint32)
    n_groups = max(1, -(-cap // (G * TILE)))
    cap = n_groups * G * TILE
    if lkey.shape[0] == 0 or rkey.shape[0] == 0:
        z = jnp.zeros(cap, jnp.uint32)
        return z, z, z, jnp.zeros(cap, bool), jnp.int32(0)
    if chunk_out is not None or lkey.shape[0] > _PALLAS_MAX_LEFT_ROWS:
        if chunk_out is None and not pallas_chunked_enabled():
            return _xla_merge_join(lkey_u, lval, rkey_u, rval, cap)
        key_o, lval_o, pos_o, valid_o, total = _pallas_join_core_chunked(
            lkey_u, lval, rkey_u, cap, chunk_out or _CHUNK_OUT
        )
        key_o, lval_o = key_o[:cap], lval_o[:cap]
        pos_o, valid_o = pos_o[:cap], valid_o[:cap]
    else:
        key_o, lval_o, pos_o, valid_o, total = _pallas_join_core(
            lkey_u, lval, rkey_u, cap
        )
    rval_o = jnp.where(
        valid_o,
        rval.astype(jnp.uint32)[jnp.clip(pos_o, 0, max(rval.shape[0] - 1, 0))],
        jnp.uint32(0),
    )
    return key_o, lval_o, rval_o, valid_o, total


@partial(jax.jit, static_argnames=("cap", "chunk_out"))
def merge_join_indices(
    lkey: jnp.ndarray,
    rkey_sorted: jnp.ndarray,
    cap: int,
    lvalid: Optional[jnp.ndarray] = None,
    rvalid_prefix: Optional[jnp.ndarray] = None,
    chunk_out: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Index-returning Pallas merge join: the drop-in kernel twin of
    :func:`kolibrie_tpu.ops.device_join.join_indices_presorted` for
    single-u32-key joins (the device query engine's ``rsorted`` join node).

    Returns ``(li, ri, valid, total)``: int32 row indices into the ORIGINAL
    left/right inputs, static length ``cap`` rounded up to whole tiles.
    The left payload slot of the shared tile kernel carries the left row
    index through compaction, so the engine can gather arbitrarily many
    binding columns afterwards.  ``rvalid_prefix`` must be a prefix mask
    (range-scan validity), which keeps the sentinel-masked right keys
    sorted; ``lvalid`` may have holes (left order is irrelevant — see
    :func:`_pallas_join_core`).  With ``lvalid`` the run-bound searches
    stop at the block of the last live left row (:func:`_run_bounds`);
    without it they cover every slot.
    """
    lkey_u = lkey.astype(jnp.uint32)
    rkey_u = rkey_sorted.astype(jnp.uint32)
    if lvalid is not None:
        lkey_u = jnp.where(lvalid, lkey_u, np.uint32(0xFFFFFFFE))
    if rvalid_prefix is not None:
        rkey_u = jnp.where(rvalid_prefix, rkey_u, np.uint32(0xFFFFFFFF))
    n_groups = max(1, -(-cap // (G * TILE)))
    cap_r = n_groups * G * TILE
    ln, rn = lkey_u.shape[0], rkey_u.shape[0]
    if ln == 0 or rn == 0:
        z = jnp.zeros(cap_r, jnp.int32)
        return z, z, jnp.zeros(cap_r, bool), jnp.int32(0)
    # how far the left rows reach, where the caller says which are live: a
    # scan's and a join's output are prefixes, so this is their row count
    extent = None
    if lvalid is not None:
        extent = jnp.max(
            jnp.where(lvalid, jnp.arange(1, ln + 1, dtype=jnp.int32), 0)
        )
    if chunk_out is not None or ln > _PALLAS_MAX_LEFT_ROWS:
        if chunk_out is None and not pallas_chunked_enabled():
            from kolibrie_tpu.ops.device_join import join_indices_presorted

            li, ri, valid, total = join_indices_presorted(
                lkey_u, rkey_u, cap_r
            )
            return li, ri.astype(jnp.int32), valid, total
        _, li_o, pos_o, valid_o, total = _pallas_join_core_chunked(
            lkey_u,
            jnp.arange(ln, dtype=jnp.uint32),
            rkey_u,
            cap_r,
            chunk_out or _CHUNK_OUT,
            extent,
        )
        li_o, pos_o = li_o[:cap_r], pos_o[:cap_r]
        valid_o = valid_o[:cap_r]
    else:
        _, li_o, pos_o, valid_o, total = _pallas_join_core(
            lkey_u, jnp.arange(ln, dtype=jnp.uint32), rkey_u, cap_r, extent
        )
    li = lax.bitcast_convert_type(li_o, jnp.int32)
    li = jnp.where(valid_o, jnp.clip(li, 0, ln - 1), 0)
    ri = jnp.where(valid_o, jnp.clip(pos_o, 0, rn - 1), 0)
    return li, ri, valid_o, total


@partial(jax.jit, static_argnames=("cap",))
def ranked_merge_join_indices(
    lkey: jnp.ndarray, rkey: jnp.ndarray, cap: int
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Pallas merge join for ARBITRARY (u64-packed, unsorted) key columns:
    dense-rank both sides over their sorted union into u32 (equal keys ⇔
    equal ranks; distinct sentinels stay distinct), sort the right ranks,
    run the tile kernel, and map ``ri`` back through the sort permutation.
    Same ``(li, ri, valid, total)`` contract as
    :func:`kolibrie_tpu.ops.device_join.join_indices`, with outputs sliced
    to exactly ``cap``.  Shared by the device query engine's non-presorted
    joins and the device fixpoint's premise joins."""
    union_sorted = jnp.sort(jnp.concatenate([lkey, rkey]))
    lrank = jnp.searchsorted(union_sorted, lkey).astype(jnp.uint32)
    rrank = jnp.searchsorted(union_sorted, rkey).astype(jnp.uint32)
    rorder = jnp.argsort(rrank)
    li, rpos, valid, total = merge_join_indices(lrank, rrank[rorder], cap)
    li, rpos, valid = li[:cap], rpos[:cap], valid[:cap]
    ri = jnp.where(valid, rorder[rpos], 0)
    return li, ri, valid, total


def _xla_merge_join(lkey, lval, rkey, rval, cap):
    """Pure-XLA fallback for inputs too large for whole-array VMEM residency
    (same contract as :func:`merge_join`)."""
    low = jnp.searchsorted(rkey, lkey, side="left").astype(jnp.int32)
    high = jnp.searchsorted(rkey, lkey, side="right").astype(jnp.int32)
    counts = high - low
    cum = jnp.cumsum(counts)
    total = cum[-1].astype(jnp.int32)
    idx = jnp.arange(cap, dtype=jnp.int32)
    row = jnp.clip(
        jnp.searchsorted(cum, idx, side="right"), 0, lkey.shape[0] - 1
    )
    pos = low[row] + (idx - (cum[row] - counts[row]))
    valid = idx < total
    z = jnp.uint32(0)
    return (
        jnp.where(valid, lkey[row], z),
        jnp.where(valid, lval.astype(jnp.uint32)[row], z),
        jnp.where(
            valid,
            rval.astype(jnp.uint32)[jnp.clip(pos, 0, rkey.shape[0] - 1)],
            z,
        ),
        valid,
        total,
    )


# ---------------------------------------------------------------------------
# fused WCOJ lex-probe expansion
# ---------------------------------------------------------------------------
#
# One WCOJ level expands ``cap`` candidate slots from the chosen accessor's
# base+delta ranges and validates each against every accessor.  The range
# computation (lexicographic binary search) and the per-slot gathers must
# stay XLA — Mosaic has no vector gather — but everything elementwise
# BETWEEN the gathers used to be ~15 separate XLA ops per accessor, each
# round-tripping a cap-sized vector through HBM.  Two kernels fuse them:
#
#   lex_probe_select   (gathers →) merge-by-rank value, first-of-run
#                      dedup, accessor choice → val / ok / is_base
#   lex_probe_validate (existence ranges →) tombstone-adjusted liveness,
#                      key-sentinel kill, base-representative tie-break
#
# split at the existence probe, which needs ``val`` back in XLA.  All
# comparisons are integer (equality on u32 bit patterns carried in i32;
# ordered compares only on small non-negative counts), so kernel outputs
# are bit-identical to the XLA formulation — the engine asserts this on
# the full WCOJ test surface under KOLIBRIE_PALLAS=force.


def _probe_grid(p: int) -> Tuple[int, int, int]:
    """Elementwise launch geometry for ``p`` slots: ``(n_chunks,
    chunk_rows, rows)`` with ``chunk_rows`` a multiple of the sublane
    granule ``G`` and small caps served by a single sub-``_CHUNK_ROWS``
    launch instead of a full 32K-element block."""
    rows = max(1, -(-p // TILE))
    rows = -(-rows // G) * G
    if rows <= _CHUNK_ROWS:
        return 1, rows, rows
    n_chunks = -(-rows // _CHUNK_ROWS)
    return n_chunks, _CHUNK_ROWS, n_chunks * _CHUNK_ROWS


def _probe2d(x: jnp.ndarray, rows: int) -> jnp.ndarray:
    """Pad a ``(p,)`` vector to ``rows * TILE`` and reshape to the
    ``(rows, TILE)`` block layout, carrying u32/bool bit patterns as
    bitcast i32 (the kernels run pure integer algebra)."""
    p = x.shape[0]
    x = lax.bitcast_convert_type(x.astype(jnp.uint32), jnp.int32)
    x = jnp.concatenate([x, jnp.zeros(rows * TILE - p, jnp.int32)])
    return x.reshape(rows, TILE)


@lru_cache(maxsize=None)
def _lex_probe_select_kernel(a_count: int):
    """Kernel factory closed over the STATIC accessor count: inputs are
    ``kk, ch, in_range`` then ``a_count`` groups of ``(nb, bval, dval,
    bprev, dprev)``; outputs ``val, ok, is_base`` (i32).  Int32 masks and
    0/1 arithmetic select throughout — Mosaic has no i1-vector select,
    and exactly one accessor matches ``ch`` so masked sums ARE selects."""

    def kernel(*refs):
        kk = refs[0][...]
        ch = refs[1][...]
        inr = refs[2][...]
        val = kk * 0
        first = kk * 0
        isb_sel = kk * 0
        for a in range(a_count):
            base = 3 + 5 * a
            nb = refs[base][...]
            bval = refs[base + 1][...]
            dval = refs[base + 2][...]
            bprev = refs[base + 3][...]
            dprev = refs[base + 4][...]
            isb = (kk < nb).astype(jnp.int32)
            first_a = isb * ((kk == 0) | (bprev != bval)).astype(
                jnp.int32
            ) + (1 - isb) * ((kk == nb) | (dprev != dval)).astype(jnp.int32)
            val_a = isb * bval + (1 - isb) * dval
            pick = (ch == a).astype(jnp.int32)
            val += pick * val_a
            first += pick * first_a
            isb_sel += pick * isb
        # SENTINEL (0xFFFFFFFF) bitcast i32 is -1
        ok = ((inr != 0) & (val != -1) & (first != 0)).astype(jnp.int32)
        refs[3 + 5 * a_count][...] = val
        refs[3 + 5 * a_count + 1][...] = ok
        refs[3 + 5 * a_count + 2][...] = isb_sel

    return kernel


@lru_cache(maxsize=None)
def _lex_probe_validate_kernel(a_count: int):
    """Kernel factory for the validation half: inputs ``ok, is_base, ch``
    then ``a_count`` groups of ``(fl, fh, tl, th, dl2, dh2, sent)``;
    output the final validity mask (i32)."""

    def kernel(*refs):
        ok = refs[0][...]
        isb = refs[1][...]
        ch = refs[2][...]
        v = ok != 0
        braw = ch * 0
        for a in range(a_count):
            base = 3 + 7 * a
            fl = refs[base][...]
            fh = refs[base + 1][...]
            tl = refs[base + 2][...]
            th = refs[base + 3][...]
            dl2 = refs[base + 4][...]
            dh2 = refs[base + 5][...]
            sent = refs[base + 6][...]
            # live copies = raw base range minus tombstoned + delta range
            blive = (fh - fl) - (th - tl)
            live = (blive + (dh2 - dl2)) > 0
            v &= live & (sent == 0)
            braw += (ch == a).astype(jnp.int32) * ((fh - fl) > 0).astype(
                jnp.int32
            )
        # a delta-enumerated value whose base also has raw copies defers
        # to the base slot as the unique representative
        v &= (isb != 0) | (braw == 0)
        refs[3 + 7 * a_count][...] = v.astype(jnp.int32)

    return kernel


def _lex_probe_call(kernel, ops, p: int, n_out: int):
    """Shared elementwise launcher: pad/bitcast the slot vectors, launch
    over the :func:`_probe_grid` geometry, slice outputs back to ``p``."""
    n_chunks, chunk_rows, rows = _probe_grid(p)
    ops2d = [_probe2d(o, rows) for o in ops]
    block = pl.BlockSpec((chunk_rows, TILE), lambda i: (i, 0))
    vma = getattr(jax.typeof(ops[0]), "vma", None)
    kwargs = {"vma": vma} if vma else {}
    out_shape = [
        jax.ShapeDtypeStruct((rows, TILE), jnp.int32, **kwargs)
        for _ in range(n_out)
    ]
    outs = _pallas_call(
        kernel,
        grid=(n_chunks,),
        in_specs=[block] * len(ops2d),
        out_specs=[block] * n_out,
        out_shape=out_shape,
        interpret=_interpret(),
    )(*ops2d)
    return tuple(o.reshape(-1)[:p] for o in outs)


def lex_probe_select(kk, ch, in_range, accessors):
    """Fused per-slot candidate materialization for one WCOJ level.

    ``kk``/``ch`` int32/int slot vectors (rank within the chosen range,
    chosen accessor), ``in_range`` bool; ``accessors`` a sequence of
    ``(nb, bval, dval, bprev, dprev)`` tuples — the XLA-gathered range
    width and value/predecessor columns of each accessor at every slot.
    Returns ``(val u32, ok bool, is_base bool)``: the merged candidate
    value, the in-range ∧ non-sentinel ∧ first-of-run mask, and whether
    the chosen slot came from the base segment.  Traced inline in the
    jitted plan body (launch through :func:`_pallas_call`)."""
    ops = [kk, ch, in_range]
    for t in accessors:
        ops.extend(t)
    with jax.named_scope("lex_probe_select"):
        val, ok, isb = _lex_probe_call(
            _lex_probe_select_kernel(len(accessors)), ops, kk.shape[0], 3
        )
        val = lax.bitcast_convert_type(val, jnp.uint32)
        return val, ok != 0, isb != 0


def lex_probe_validate(ok, is_base, ch, accessors):
    """Fused per-slot validation for one WCOJ level: existence-range
    liveness (tombstone-adjusted), key-sentinel kill and the
    base-representative tie-break.  ``accessors`` is a sequence of
    ``(fl, fh, tl, th, dl2, dh2, sent)`` tuples from the XLA existence
    pre-pass.  Returns the final bool validity mask."""
    ops = [ok, is_base, ch]
    for t in accessors:
        ops.extend(t)
    with jax.named_scope("lex_probe_validate"):
        (v,) = _lex_probe_call(
            _lex_probe_validate_kernel(len(accessors)), ops, ok.shape[0], 1
        )
        return v != 0


# ---------------------------------------------------------------------------
# fused filter
# ---------------------------------------------------------------------------

_OPS = {"eq": 0, "ne": 1, "lt": 2, "le": 3, "gt": 4, "ge": 5}


_I32_MIN = -(1 << 31)


def _filter_kernel(consts_ref, s_ref, p_ref, o_ref, mask_ref):
    # consts layout: [s_val, s_active, p_val, p_active, o_val, o_active,
    #                 o_op, o_cmp]; values are u32 bit patterns carried in
    # i32.  Equality is bit-exact either way; ordered comparisons flip the
    # sign bit (x ^ i32min) so i32 compare == unsigned u32 compare — IDs
    # with bit 31 set (quoted triples) order correctly.
    s_c, s_on = consts_ref[0], consts_ref[1]
    p_c, p_on = consts_ref[2], consts_ref[3]
    o_c, o_on = consts_ref[4], consts_ref[5]
    o_op, o_cmp = consts_ref[6], consts_ref[7]
    # Boolean algebra only (Mosaic has no i1-vector select): an inactive
    # clause is vacuously true via scalar broadcast.
    m = (s_ref[...] == s_c) | (s_on == 0)
    m &= (p_ref[...] == p_c) | (p_on == 0)
    m &= (o_ref[...] == o_c) | (o_on == 0)
    o = o_ref[...]
    ob = o ^ _I32_MIN
    cb = o_cmp ^ _I32_MIN
    m &= (o == o_cmp) | (o_op != 0)
    m &= (o != o_cmp) | (o_op != 1)
    m &= (ob < cb) | (o_op != 2)
    m &= (ob <= cb) | (o_op != 3)
    m &= (ob > cb) | (o_op != 4)
    m &= (ob >= cb) | (o_op != 5)
    mask_ref[...] = m


def filter_mask(
    s: jnp.ndarray,
    p: jnp.ndarray,
    o: jnp.ndarray,
    s_const: int = -1,
    p_const: int = -1,
    o_const: int = -1,
    o_op: int = -1,
    o_cmp: int = 0,
) -> jnp.ndarray:
    """Fused triple-pattern + comparison filter over ID columns.

    ``-1`` constants are wildcards.  ``o_op`` indexes ``_OPS`` for an extra
    comparison on the object column (numeric filters compare encoded IDs the
    caller has mapped to an order-preserving key, as the reference's SIMD
    path compares raw epoch/ID words).  One pass over HBM, mask out.

    Constants and comparands cover the FULL u32 range (quoted-triple IDs
    have bit 31 set): values ride as u32 bit patterns in i32 with a
    sign-bit flip for the ordered comparisons inside the kernel.  The
    constants travel in the scalar-prefetch operand (traced, not static),
    so every constant combination shares ONE compiled executable.
    """

    def bits(v) -> int:
        return int(np.uint32(v).view(np.int32))

    consts = np.array(
        [
            bits(s_const) if s_const >= 0 else 0,
            1 if s_const >= 0 else 0,
            bits(p_const) if p_const >= 0 else 0,
            1 if p_const >= 0 else 0,
            bits(o_const) if o_const >= 0 else 0,
            1 if o_const >= 0 else 0,
            int(o_op),
            bits(o_cmp),
        ],
        np.int32,
    )
    return _filter_mask_jit(consts, s, p, o)


@jax.jit
def _filter_mask_jit(consts, s, p, o) -> jnp.ndarray:
    n = s.shape[0]
    n_chunks = max(1, -(-n // (_CHUNK_ROWS * TILE)))
    rows = n_chunks * _CHUNK_ROWS
    pad = rows * TILE - n

    def shape2d(x):
        x = jnp.concatenate(
            [
                lax.bitcast_convert_type(x.astype(jnp.uint32), jnp.int32),
                jnp.zeros(pad, jnp.int32),
            ]
        )
        return x.reshape(rows, TILE)

    block = pl.BlockSpec((_CHUNK_ROWS, TILE), lambda i, *_: (i, 0))
    with jax.named_scope("filter"):
        mask2d = _pallas_call(
            _filter_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(n_chunks,),
                in_specs=[block] * 3,
                out_specs=block,
            ),
            out_shape=jax.ShapeDtypeStruct((rows, TILE), jnp.bool_),
            interpret=_interpret(),
        )(consts, shape2d(s), shape2d(p), shape2d(o))
    return mask2d.reshape(-1)[:n]


# ---------------------------------------------------------------------------
# semiring tag combine
# ---------------------------------------------------------------------------

_TAG_OPS = ("min", "max", "mul", "noisy_or")


def _tag_kernel_factory(op: str):
    def kernel(a_ref, b_ref, o_ref):
        a, b = a_ref[...], b_ref[...]
        if op == "min":
            o_ref[...] = jnp.minimum(a, b)
        elif op == "max":
            o_ref[...] = jnp.maximum(a, b)
        elif op == "mul":
            o_ref[...] = a * b
        else:  # noisy_or: a ⊕ b = 1 - (1-a)(1-b)
            o_ref[...] = 1.0 - (1.0 - a) * (1.0 - b)

    return kernel


@partial(jax.jit, static_argnames=("op",))
def tag_combine(a: jnp.ndarray, b: jnp.ndarray, op: str) -> jnp.ndarray:
    """Vectorized semiring ⊕/⊗ on f32 tag columns.

    ``min``/``max`` serve MinMaxProbability ⊗/⊕ and ExpirationProvenance;
    ``mul``/``noisy_or`` serve AddMultProbability ⊗/⊕
    (``shared/src/provenance.rs:69-146``).
    """
    if op not in _TAG_OPS:
        raise ValueError(f"unknown tag op {op!r}")
    n = a.shape[0]
    n_chunks = max(1, -(-n // (_CHUNK_ROWS * TILE)))
    rows = n_chunks * _CHUNK_ROWS
    pad = rows * TILE - n

    def shape2d(x):
        x = jnp.concatenate(
            [x.astype(jnp.float32), jnp.zeros(pad, jnp.float32)]
        )
        return x.reshape(rows, TILE)

    block = pl.BlockSpec((_CHUNK_ROWS, TILE), lambda i: (i, 0))
    out = _pallas_call(
        _tag_kernel_factory(op),
        grid=(n_chunks,),
        out_shape=jax.ShapeDtypeStruct((rows, TILE), jnp.float32),
        in_specs=[block] * 2,
        out_specs=block,
        interpret=_interpret(),
    )(shape2d(a), shape2d(b))
    return out.reshape(-1)[:n]
