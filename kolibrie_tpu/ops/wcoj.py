"""Worst-case-optimal (leapfrog-triejoin-style) multiway join primitives.

The Volcano binary joins in :mod:`kolibrie_tpu.ops.device_join` materialize
every pairwise intermediate, which on cyclic basic graph patterns
(triangles, LUBM q2/q9 shapes) is quadratic in the input even when the
final result is tiny.  A worst-case-optimal join instead eliminates ONE
VARIABLE AT A TIME: at each level the candidate values for the variable
are enumerated from the accessor (pattern) with the smallest sorted-range
count and validated by existence probes against every other accessor —
so the intermediate row count is bounded by the output of each prefix
join (the AGM bound), never by a pairwise product.

The store already maintains all six sorted permutations on device as
two-tier base + delta segments with tombstone positions
(:meth:`ColumnarTripleStore.device_segment`), which makes the trie
navigation a batch of lexicographic ``searchsorted`` probes — a pure
XLA formulation with static shapes, so it composes with the
parameterized-template ABI (zero recompiles across constant variants).

This module holds the shared primitives:

- :func:`lex_searchsorted` — batched lexicographic binary search over up
  to three sorted u32 columns (device, traced inline by the plan body);
- :func:`lex_range` — BOTH insertion points of each probe tuple in one
  fixed-trip loop (half the gathers and a quarter of the loop overhead of
  four separate ``lex_searchsorted`` calls; bit-identical results);
- :func:`lex_range_sorted` — the same ``(lo, hi)``, bit for bit, from one
  variadic ``lax.sort`` of the base rows and two tagged copies of every probe
  tuple instead of a gather loop: the loop pays per probe and trip (a trip
  gathers 2 x ncols x P elements: 40-100 ns a probe and trip on a v5e,
  whatever the table's size), the sort per element (4-7 ns over N + 2P);
- :func:`range_search_form` / :func:`range_search` — the rule that picks
  the form of one call from its static shapes ``(N, P, ncols)``, and the
  call a WCOJ level makes (``optimizer/device_engine.py`` ``eval_level``);
- :func:`key_window` — the rows of the order that a search's constant
  leading keys select, as one sorted slice: an accessor that names a
  predicate searches those W rows (its hottest key's, a template property:
  ``WcojAccessor.window``), so N above is W for it, and the sort's
  elements W + 2P, not the padded order's N + 2P;
- :func:`slot_rows` — which probe row each output slot of a level expands
  (``searchsorted(cumsum(cnt), arange(cap), "right")``) by one scatter and
  one prefix count, P + C elements where the search makes C (log P + 1)
  gathers;
- :func:`host_lex_range` — the numpy twin returning ``[lo, hi)`` ranges,
  exact for 3-key probes via a dense-rank packing (u64 cannot hold three
  u32 keys directly);
- :func:`host_lex_probe` — the numpy row oracle for one WCOJ level's
  fused probe expansion (range → merge-by-rank → first-of-run dedup →
  tombstone-aware existence), mirroring the device math slot for slot.
  The Pallas ``lex_probe_*`` kernels (:mod:`kolibrie_tpu.ops.
  pallas_kernels`) and the XLA formulation are both fuzzed against it.

The level evaluation itself lives in the device plan interpreter
(``optimizer/device_engine.py`` ``WcojSpec``) because it threads the
plan's capacity/counts protocol; its math is documented there.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import numpy as np

__all__ = [
    "lex_searchsorted",
    "lex_range",
    "lex_range_sorted",
    "range_search_form",
    "key_window",
    "range_search",
    "slot_rows",
    "host_lex_range",
    "host_lex_probe",
]

# never a real dictionary ID (IDs use bits 0..30 + bit 31 for quoted;
# dictionary.rs:36-40) — doubles as the device padding fill, so probes for
# it locate the start of a segment's padding block
SENTINEL = 0xFFFFFFFF


def lex_searchsorted(cols, keys, side: str = "left"):
    """Batched lexicographic ``searchsorted`` over parallel sorted columns.

    ``cols``: tuple of 1..3 u32 arrays (length N) sorted lexicographically
    as a column-major tuple; ``keys``: tuple of equally many u32 arrays
    (length P) — one probe tuple per row.  Returns int32 positions (P,).

    A fixed-trip binary search (``fori_loop`` with a static step count)
    instead of packing: three u32 keys do not fit one u64 word, and the
    dense-rank repacking the binary joins use would cost a sort per probe
    batch.  Intended to be traced INLINE inside the jitted plan body — it
    is deliberately not jitted itself.
    """
    import jax.numpy as jnp
    from jax import lax

    n = int(cols[0].shape[0])
    p = keys[0].shape[0]
    if n == 0:
        return jnp.zeros(p, dtype=jnp.int32)
    right = side == "right"

    def body(_i, lh):
        lo, hi = lh
        active = lo < hi
        mid = jnp.clip((lo + hi) >> 1, 0, n - 1)
        lt = jnp.zeros(p, dtype=bool)
        eq = jnp.ones(p, dtype=bool)
        for c, k in zip(cols, keys):
            v = c[mid]
            lt = lt | (eq & (v < k))
            eq = eq & (v == k)
        go_right = (lt | eq) if right else lt
        lo = jnp.where(active & go_right, mid + 1, lo)
        hi = jnp.where(active & ~go_right, mid, hi)
        return lo, hi

    lo0 = jnp.zeros(p, dtype=jnp.int32)
    hi0 = jnp.full(p, n, dtype=jnp.int32)
    # the search interval [lo, hi] starts at width n and halves every step
    lo, _hi = lax.fori_loop(0, n.bit_length() + 1, body, (lo0, hi0))
    return lo


def lex_range(cols, keys):
    """Both lexicographic insertion points of each probe tuple: returns
    ``(lo, hi)`` int32 arrays, bit-identical to
    ``(lex_searchsorted(cols, keys, "left"),
    lex_searchsorted(cols, keys, "right"))``.

    The two binary searches share ONE ``fori_loop``: each carries its own
    ``[lo, hi]`` interval (the searches diverge, so the midpoints differ),
    but the column gathers per trip drop from four (two calls × left +
    right of the WCOJ probe pair) to two, and the loop overhead from four
    ``fori_loop`` launches per segment pair to one.  Like
    :func:`lex_searchsorted` it is deliberately not jitted — it is traced
    inline inside the jitted plan body.
    """
    import jax.numpy as jnp
    from jax import lax

    n = int(cols[0].shape[0])
    p = keys[0].shape[0]
    if n == 0:
        z = jnp.zeros(p, dtype=jnp.int32)
        return z, z

    def probe(mid):
        # (lt, eq) of the column tuple at ``mid`` vs the probe tuples
        lt = jnp.zeros(p, dtype=bool)
        eq = jnp.ones(p, dtype=bool)
        for c, k in zip(cols, keys):
            v = c[mid]
            lt = lt | (eq & (v < k))
            eq = eq & (v == k)
        return lt, eq

    def body(_i, state):
        llo, lhi, rlo, rhi = state
        # left-side search: descend right while strictly less
        lact = llo < lhi
        lmid = jnp.clip((llo + lhi) >> 1, 0, n - 1)
        lt, _eq = probe(lmid)
        llo = jnp.where(lact & lt, lmid + 1, llo)
        lhi = jnp.where(lact & ~lt, lmid, lhi)
        # right-side search: descend right while less-or-equal
        ract = rlo < rhi
        rmid = jnp.clip((rlo + rhi) >> 1, 0, n - 1)
        rlt, req = probe(rmid)
        go = rlt | req
        rlo = jnp.where(ract & go, rmid + 1, rlo)
        rhi = jnp.where(ract & ~go, rmid, rhi)
        return llo, lhi, rlo, rhi

    z = jnp.zeros(p, dtype=jnp.int32)
    f = jnp.full(p, n, dtype=jnp.int32)
    lo, _lh, hi, _rh = lax.fori_loop(
        0, n.bit_length() + 1, body, (z, f, z.copy(), f.copy())
    )
    return lo, hi


def lex_range_sorted(cols, keys):
    """:func:`lex_range` by sorting instead of gathering: same arguments,
    the same ``(lo, hi)`` int32 arrays, bit for bit.

    Every probe tuple goes into ONE variadic ``lax.sort`` twice, beside the
    N base rows, under a last key that is tag and way back at once: the
    left copy of probe ``i`` carries ``i`` (below every base row's ``P``),
    the right copy ``P + 1 + i`` (above it).  Sorted by (key columns...,
    that code), a left copy lands before the base rows equal to it and a
    right copy after them, so a running count of base rows reads ``lo`` at
    the left copy and ``hi`` at the right one.  A second, two-operand sort
    by the code alone brings the counts back to probe order: the left
    copies' first, the base rows' ``N`` in the middle, the right copies'
    last.  Ties (equal probes, duplicate base rows) need no stable sort:
    tied elements of one kind read the same count.  The comparisons are
    the loop's own (unsigned, lexicographic), so sentinel probes, sentinel
    padding and an all-padding base read as they do there.

    N + 2P elements through two sorts, whatever ``N.bit_length() + 1``
    trips would have gathered; :func:`range_search_form` says when that is
    the cheaper form.  Traced inline, like :func:`lex_range`."""
    import jax.numpy as jnp
    from jax import lax

    n = int(cols[0].shape[0])
    p = int(keys[0].shape[0])
    if n == 0:
        z = jnp.zeros(p, dtype=jnp.int32)
        return z, z
    code = jnp.concatenate(
        [
            jnp.arange(p, dtype=jnp.uint32),
            jnp.full(n, p, dtype=jnp.uint32),
            jnp.arange(p + 1, 2 * p + 1, dtype=jnp.uint32),
        ]
    )
    merged = [jnp.concatenate([k, c, k]) for c, k in zip(cols, keys)]
    scode = lax.sort(
        (*merged, code), num_keys=len(merged) + 1, is_stable=False
    )[-1]
    below = jnp.cumsum((scode == p).astype(jnp.int32), dtype=jnp.int32)
    _code, back = lax.sort((scode, below), num_keys=1, is_stable=False)
    return back[:p], back[n + p :]


# The rule's constants, from PR 35's gate (PERF.md section 6: one v5e,
# 2026-09-29, jit call until ready, median of 15, ms; loop / sorted):
#   (2^23, 1,048,576, 3)  2,569.5 / 69.2    (2^20, 65,536, 3)  77.6 / 6.1
#   (2^23,   262,144, 2)    429.6 / 45.5    (2^20, 16,384, 2)  13.8 / 4.7
#   (2^23,    65,536, 3)     88.1 / 55.6    (2^20,  8,192, 3)  10.1 / 5.9
#   (2^23,    16,384, 2)     15.8 / 44.1    (2^20,  1,024, 3)   2.1 / 5.8
#   (2^23,     4,096, 3)      8.6 / 59.6    (1,024, 65,536, 3) 28.6 / 1.4
# A sort costs by element (4-7 ns, whatever P), the loop by probe and trip
# (40-100 ns); they cross near N = 190 P.  The sort is taken from N = 64 P
# down, where it won 2.9-37 times: at N = 128 P it would win 1.6 times, and
# a sorted search costs the TPU compiler two more sort instructions (27-67 s
# a search alone, where a loop compiles in under a second).  8,192 is the
# fewest probes the gate saw a sort win at.
# Since PR 48 N is the rows searched: the window of the order that the
# accessor's constant keys select (key_window), where it has one, so a
# search sits further inside the rule than it did (LUBM(50)'s sorted
# searches at N = 0.125-8 P where they were at 8-32 P; LUBM(5)'s Q2 takes
# its third level's three, 8,192 probes over windows of 16,384-65,536 rows,
# to the sort).  The constants stand as PR 35's gate set them: PR 48's
# gate at window-sized shapes is in PERF.md section 6 and docs/JOINS.md.
_SORT_MIN_PROBES = 8192
_SORT_ROWS_PER_PROBE = 64


def range_search_form(n: int, p: int, ncols: int) -> str:
    """Which form one range search takes: ``"sorted"`` or ``"loop"``, a pure
    function of the call's static shapes (N rows searched: the accessor's
    window where it has one, else the padded segment; P probe tuples;
    ``ncols`` key columns), so one plan may take both and a template still
    has one executable a capacity set.

    The loop pays ``N.bit_length() + 1`` trips of 2 x ncols x P gathers;
    the sort pays for N + 2P elements.  So the sort wins once the probes
    are many and not too few beside the rows; the gate's two- and
    three-column shapes cross at the same N / P, so ``ncols`` moves
    nothing yet."""
    if p >= _SORT_MIN_PROBES and n <= _SORT_ROWS_PER_PROBE * p:
        return "sorted"
    return "loop"


def key_window(cols, consts, rows: int):
    """Where the rows that lead with ``consts`` lie in the sorted columns:
    ``(start, window)``, ``window`` the ``rows``-long slice of every column
    that begins at ``start``, one ``dynamic_slice`` each, so still sorted.

    ``consts`` are scalars, one for each of the first ``len(consts)``
    columns; the rows equal to them are contiguous, and ``start`` is where
    they begin (the count of rows below the constants: one pass over the
    leading columns, no loop), pulled back to ``N - rows`` where the slice
    would run past the padded end.  Rows of the slice before or after the
    constants' own are the neighbouring keys', which sort before and after
    every tuple that leads with the constants; so a search of the slice for
    such a tuple, plus ``start``, is the search of the whole columns, bit
    for bit, as long as the constants' rows are no more than ``rows``: the
    caller's business (``device_engine`` sizes ``rows`` by the hottest key
    of the frozen base the columns were cut from)."""
    import jax.numpy as jnp
    from jax import lax

    n = int(cols[0].shape[0])
    below = jnp.zeros(n, dtype=bool)
    eq = jnp.ones(n, dtype=bool)
    for c, k in zip(cols, consts):
        below = below | (eq & (c < k))
        eq = eq & (c == k)
    start = jnp.minimum(jnp.sum(below, dtype=jnp.int32), jnp.int32(n - rows))
    return start, tuple(lax.dynamic_slice(c, (start,), (rows,)) for c in cols)


def range_search(cols, keys, lead=(), rows: int = 0):
    """``(lo, hi)`` of each probe tuple in the sorted columns, in the form
    :func:`range_search_form` picks for this call's shapes.

    Where the first ``len(lead)`` keys of every probe tuple are the scalars
    ``lead`` and ``rows`` is given and under the columns' length, the search
    runs over the :func:`key_window` of that many rows and its positions
    are moved back by the window's start: the same arrays, from ``rows``
    base rows where the whole columns have N."""
    n = int(cols[0].shape[0])
    p = int(keys[0].shape[0])
    if lead and 0 < rows < n:
        start, cols = key_window(cols, lead, rows)
        lo, hi = range_search(cols, keys)
        return lo + start, hi + start
    if range_search_form(n, p, len(cols)) == "sorted":
        return lex_range_sorted(cols, keys)
    return lex_range(cols, keys)


def slot_rows(cum, cap: int):
    """The probe row each of ``cap`` output slots expands:
    ``searchsorted(cum, arange(cap), side="right")`` as int32, for the
    running total ``cum`` (int32, non-decreasing) of a level's counts.

    ``row[s]`` is the number of rows whose total is at or under ``s``: a
    histogram of ``cum`` summed from the left.  One P-wide scatter-add and
    one ``cap``-wide prefix count in blocks (``ops/prefix.py``), where
    ``jnp.searchsorted`` runs ``P.bit_length() + 1`` trips of a ``cap``-wide
    gather, 7.2-7.5 ns a slot and trip on a v5e (PR 50's ledger lines).

    A total past ``cap`` adds nothing to a slot under ``cap`` and lands in
    the histogram's spare last entry, as does one that wrapped negative
    (such a dispatch is thrown away by the capacity protocol; here it must
    only not fault), so every index is in bounds.

    One form at every shape.  PR 51's gate (one v5e, 2026-10-05, median of
    15 calls, ms, every result equal to ``np.searchsorted``'s; PERF.md
    section 6 and docs/JOINS.md have the whole table):

    ========  ==========  ===============  ==========================
    P rows    cap slots   search a slot    scatter + blocked prefix
    ========  ==========  ===============  ==========================
    524,288   1,048,576   151.24           5.62
    4,096     1,048,576   98.66            0.96
    262,144   524,288     72.30            3.16
    32,768    65,536      8.33             0.94
    4,096     8,192       1.41             0.64
    1         262,144     0.75             0.78
    ========  ==========  ===============  ==========================

    A promise of sorted or in-bounds indices to the scatter moved none of
    these by more than 0.1 ms, so none is made."""
    import jax.numpy as jnp

    from kolibrie_tpu.ops.prefix import prefix_count

    at = jnp.where((cum < 0) | (cum > cap), cap, cum)
    hist = jnp.zeros(cap + 1, dtype=jnp.int32).at[at].add(1)
    return prefix_count(hist)[:cap]


def _pack2(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    return (a.astype(np.uint64) << np.uint64(32)) | b.astype(np.uint64)


def host_lex_range(
    cols: Sequence[np.ndarray], keys: Sequence[np.ndarray]
) -> Tuple[np.ndarray, np.ndarray]:
    """Numpy twin of two :func:`lex_searchsorted` calls: ``[lo, hi)`` row
    ranges of each probe tuple in lexicographically sorted columns.

    1/2-key probes pack into u64 words; 3-key probes ride a dense rank of
    the leading pair (run-change cumsum), replacing the pair with its rank
    so ``(rank << 32) | c2`` stays exact — an absent leading pair keeps
    the plain pair insertion point (left == right there, so the range is
    empty at the correct position).
    """
    n = len(cols[0]) if cols else 0
    k = len(keys)
    p = len(keys[0]) if k else 0
    if n == 0 or k == 0:
        z = np.zeros(p, dtype=np.int64)
        return z, z.copy()
    if k == 1:
        packed, kp = cols[0], np.asarray(keys[0])
    elif k == 2:
        packed = _pack2(cols[0], cols[1])
        kp = _pack2(np.asarray(keys[0]), np.asarray(keys[1]))
    else:
        p01 = _pack2(cols[0], cols[1])
        change = np.empty(n, dtype=bool)
        change[0] = True
        change[1:] = p01[1:] != p01[:-1]
        rank01 = np.cumsum(change) - 1
        packed = (rank01.astype(np.uint64) << np.uint64(32)) | cols[2].astype(
            np.uint64
        )
        kp01 = _pack2(np.asarray(keys[0]), np.asarray(keys[1]))
        i = np.searchsorted(p01, kp01, side="left")
        ic = np.minimum(i, n - 1)
        present = p01[ic] == kp01
        kp = (rank01[ic].astype(np.uint64) << np.uint64(32)) | np.asarray(
            keys[2]
        ).astype(np.uint64)
        lo = np.where(present, np.searchsorted(packed, kp, side="left"), i)
        hi = np.where(present, np.searchsorted(packed, kp, side="right"), i)
        return lo.astype(np.int64), hi.astype(np.int64)
    lo = np.searchsorted(packed, kp, side="left")
    hi = np.searchsorted(packed, kp, side="right")
    return lo.astype(np.int64), hi.astype(np.int64)


def host_lex_probe(accessors, wvalid: np.ndarray, cap: int) -> dict:
    """Numpy row oracle for ONE WCOJ level's fused probe expansion.

    Mirrors the device math of ``WcojSpec`` evaluation
    (``optimizer/device_engine.py``) slot for slot — range probe,
    smallest-accessor choice, capacity expansion, base/delta
    merge-by-rank, first-of-run dedup, tombstone-aware live-existence
    probes and the base-representative tie-break — so both the XLA
    formulation and the Pallas ``lex_probe_*`` kernels can be fuzzed
    against it.

    ``accessors``: sequence of dicts with keys

    - ``bkeys`` / ``dkeys``: tuple of sorted base / delta key columns
      (the accessor's bound prefix in perm order; ``()`` when unbound);
    - ``bval`` / ``dval``: the candidate value column of each segment
      (sentinel-padded, never empty — as ``device_segment`` guarantees);
    - ``del_pos``: sorted u32 base-row tombstone positions
      (sentinel-padded);
    - ``keys``: tuple of per-probe key arrays, shape ``(pcap,)`` each
      (``()`` for an unbound accessor).

    ``wvalid``: the level's incoming validity mask, shape ``(pcap,)``.
    Returns a dict with ``val``, ``valid``, ``row`` (the source slot of
    each output), ``choice`` and ``total`` (raw candidate count — the
    convergence protocol's capacity signal).
    """
    SENT = np.uint32(0xFFFFFFFF)
    wvalid = np.asarray(wvalid, dtype=bool)
    pcap = wvalid.shape[0]
    probes = []
    for acc in accessors:
        keys = tuple(np.asarray(k, dtype=np.uint32) for k in acc["keys"])
        sent = np.zeros(pcap, dtype=bool)
        for k in keys:
            sent |= k == SENT
        if keys:
            bl, bh = host_lex_range(acc["bkeys"], keys)
            dl, dh = host_lex_range(acc["dkeys"], keys)
        else:
            bl = np.zeros(pcap, dtype=np.int64)
            dl = np.zeros(pcap, dtype=np.int64)
            nb0 = np.searchsorted(
                np.asarray(acc["bval"], np.uint32), SENT, side="left"
            )
            nd0 = np.searchsorted(
                np.asarray(acc["dval"], np.uint32), SENT, side="left"
            )
            bh = np.full(pcap, nb0, dtype=np.int64)
            dh = np.full(pcap, nd0, dtype=np.int64)
        probes.append((keys, sent, bl, bh, dl, dh))
    cntm = np.stack(
        [
            np.where(sent, 0, (bh - bl) + (dh - dl))
            for (_k, sent, bl, bh, dl, dh) in probes
        ]
    )
    choice = np.argmin(cntm, axis=0)
    cnt = np.where(wvalid, cntm.min(axis=0), 0)
    total = int(cnt.sum())
    cum = np.cumsum(cnt)
    slot = np.arange(cap, dtype=np.int64)
    row = np.searchsorted(cum, slot, side="right")
    row_c = np.clip(row, 0, pcap - 1)
    kk = slot - (cum[row_c] - cnt[row_c])
    in_range = slot < total
    vals_l, first_l, isb_l = [], [], []
    for acc, (keys, sent, bl, bh, dl, dh) in zip(accessors, probes):
        bv = np.asarray(acc["bval"], dtype=np.uint32)
        dv = np.asarray(acc["dval"], dtype=np.uint32)
        nb = bh[row_c] - bl[row_c]
        isb = kk < nb
        bidx = np.clip(bl[row_c] + kk, 0, bv.shape[0] - 1)
        didx = np.clip(dl[row_c] + (kk - nb), 0, dv.shape[0] - 1)
        bval, dval = bv[bidx], dv[didx]
        bprev = bv[np.clip(bidx - 1, 0, bv.shape[0] - 1)]
        dprev = dv[np.clip(didx - 1, 0, dv.shape[0] - 1)]
        vals_l.append(np.where(isb, bval, dval))
        first_l.append(
            np.where(
                isb,
                (kk == 0) | (bprev != bval),
                (kk == nb) | (dprev != dval),
            )
        )
        isb_l.append(isb)
    ch = choice[row_c]
    val = np.stack(vals_l)[ch, slot]
    first = np.stack(first_l)[ch, slot]
    is_base = np.stack(isb_l)[ch, slot]
    new_valid = in_range & (val != SENT) & first
    n_dedup = int(new_valid.sum())  # pre-liveness: the :dedup stats stage
    braw_l = []
    for acc, (keys, sent, *_r) in zip(accessors, probes):
        fkeys = tuple(k[row_c] for k in keys) + (val,)
        bsf = tuple(acc["bkeys"]) + (np.asarray(acc["bval"], np.uint32),)
        dsf = tuple(acc["dkeys"]) + (np.asarray(acc["dval"], np.uint32),)
        fl, fh = host_lex_range(bsf, fkeys)
        dl2, dh2 = host_lex_range(dsf, fkeys)
        del_pos = np.asarray(acc["del_pos"], dtype=np.uint32)
        tl = np.searchsorted(del_pos, fl.astype(np.uint32))
        th = np.searchsorted(del_pos, fh.astype(np.uint32))
        blive = (fh - fl) - (th - tl)
        live = (blive + (dh2 - dl2)) > 0
        new_valid = new_valid & live & ~sent[row_c]
        braw_l.append((fh - fl) > 0)
    braw = np.stack(braw_l)[ch, slot]
    new_valid = new_valid & (is_base | ~braw)
    return {
        "val": np.where(new_valid, val, 0).astype(np.uint32),
        "valid": new_valid,
        "row": row_c,
        "choice": ch,
        "total": total,
        "dedup": n_dedup,
        "live": int(new_valid.sum()),
    }
