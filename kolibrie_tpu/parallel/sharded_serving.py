"""Sharded serving: the HTTP front door's mesh execution layer.

ROADMAP item 1 closes here: the serving path (http_server -> TemplateBatcher
-> executor) gains a :class:`ShardedDatabase` that keeps the two-tier store
(frozen base + delta segment + tombstones, ``core/store.py``) hash-partitioned
across the device mesh and device-RESIDENT, so a same-template query group —
of one member or of many — becomes ONE ``shard_map`` dispatch instead of B
single-device programs.

Three design rules, inherited from the systems this reproduces (MapSQ's
partition-match-merge split, arXiv:1702.03484; GPU Datalog's resident
relations + delta-only transfer, arXiv:2311.02206):

1. **Partition once, mutate by delta.**  The frozen base partitions by
   ``mix32(key) % n`` into per-shard ``[n, base_cap]`` blocks — uploaded once
   per ``base_version``.  Mutation batches under ``delta_threshold`` re-upload
   only the O(delta) add blocks and tombstone positions; the combined view is
   reassembled on device (:func:`_assemble`) and each mirror's rows sorted
   by its join key (:func:`_get_sort_fn`: the right side of every join step
   of every template, so no serving program sorts a mirror), so shapes — and
   therefore every compiled serving program — survive sustained
   insert/delete traffic with ZERO recompiles.  Nothing partitions for a
   write: the mirrors go stale and the first read that needs them brings
   them up to date, once (:meth:`ShardedDatabase.refresh`), so a bulk load
   of *k* chunks re-partitions the base once.
2. **One dispatch per template group, one executable per template.**
   Same-template queries differ only in constants (``query/template.py``);
   the batched program moves those constants into a traced
   ``[slots, n_slots]`` parameter matrix and takes the number of live
   members beside it as a traced scalar, replicated over the mesh.  A loop
   INSIDE one ``shard_map`` body runs the live members only — per member:
   shard-local seed scan compacted to ``join_cap`` rows, fixed-cap
   ``all_to_all`` binding-table exchange, local joins against the sorted
   mirrors, replicated filter masks, the final rows compacted to
   ``out_cap`` — so a dispatch costs what its live members cost, and a group
   of one is a group like any other: under
   an attached mesh the executor sends every request of a supported shape
   here (no device holds the whole store in the deployment this stands
   for).  ``slots`` is a class (a power of two, not below 8), not the
   group size, so a template has one executable per capacity set for
   every group up to the class.  A member's plan — the seed premise, the
   step order it gives and the three capacities — is counted once on the
   host from the store's sorted orders, on the template's first sight,
   as the rows THIS body will count (``DistQueryExecutor._counted_plan``
   with ``batched``), and pinned with the template: the members of every
   later group are lowered with it.  The host merge brings a dispatch's
   answers down in one transfer, decodes the rows the program counted and
   is deterministic and identical to the solo path
   (``_finish_select_table``).
3. **Cross-cutting layers ride the shard hop.**  Deadlines are checked before
   dispatch (``shard.dispatch`` is also a fault-injection site), per-template
   breakers gate the group in the executor, per-shard span children and
   ``kolibrie_shard_*`` counters make imbalance and exchange pressure
   observable, and recovery (WAL replay / snapshot restore) rebuilds the
   mirrors through the same :meth:`ShardedDatabase.refresh` staleness check.

Plan-cache interaction: the executor's per-template state key carries
:attr:`ShardedDatabase.signature` (the mesh signature), so attaching or
detaching the mesh can never replay a plan lowered for the other topology.
"""

from __future__ import annotations

import threading
import time
import weakref
from concurrent.futures import ThreadPoolExecutor
from functools import lru_cache, partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from kolibrie_tpu.obs import analyze as _analyze
from kolibrie_tpu.obs import metrics as _m
from kolibrie_tpu.obs.spans import span
from kolibrie_tpu.ops import slot_class as _slot_class
from kolibrie_tpu.parallel.dist_general import _exchange_table
from kolibrie_tpu.parallel.dist_join import (
    _LPAD32 as _JLPAD,
    _RPAD32 as _JRPAD,
    _dist_check_vma,
    compact,
    prefix_count,
)
from kolibrie_tpu.parallel.mesh import make_mesh
from kolibrie_tpu.parallel.sharded_store import ShardedTripleStore, shard_of
from kolibrie_tpu.query import compile_cache as _cc
from kolibrie_tpu.optimizer.caps import fit_join_caps
from kolibrie_tpu.query.template import fingerprint_query, note_cap_retry
from kolibrie_tpu.resilience.deadline import check_deadline
from kolibrie_tpu.resilience.faultinject import fault_point
from kolibrie_tpu.reasoner.device_fixpoint import Unsupported

__all__ = [
    "ShardedDatabase",
    "attach_sharded",
    "detach_sharded",
    "active_sharded",
    "sharded_compile_stats",
    "Unsupported",
]

# ------------------------------------------------------------------ metrics
_SHARD_DISPATCH = _m.counter(
    "kolibrie_shard_dispatch_total",
    "Mesh serving dispatches by path (batched: a group of several; lone: "
    "a group of one)",
    labels=("path",),
)
_SHARD_QUERIES = _m.counter(
    "kolibrie_shard_queries_total", "Queries served through the mesh path"
)
_SHARD_MEMBER_SLOTS = _m.counter(
    "kolibrie_shard_member_slots_total",
    "Member slots the dispatched mesh executables were compiled for "
    "(queries_total over this is the member loop's occupancy)",
)
_SHARD_ROWS = _m.counter(
    "kolibrie_shard_rows_scanned_total",
    "Resident rows visited by shard-local premise scans (static bound)",
)
_SHARD_XBYTES = _m.counter(
    "kolibrie_shard_exchanged_bytes_total",
    "Bytes moved by fixed-cap all-to-all binding-table exchanges "
    "(static buffer size - what actually rides the interconnect)",
)
_SHARD_H2D = _m.counter(
    "kolibrie_shard_h2d_bytes_total",
    "Host->device mirror upload bytes by segment",
    labels=("segment",),
)
_SHARD_IMBALANCE = _m.gauge(
    "kolibrie_shard_imbalance",
    "max/mean per-shard row occupancy (1.0 = perfectly balanced)",
)
_SHARD_OCCUPANCY = _m.gauge(
    "kolibrie_shard_rows", "Live rows resident per shard", labels=("shard",)
)
_SHARD_CAP_HITS = _m.counter(
    "kolibrie_shard_exchange_cap_hits_total",
    "Dispatches that overflowed a join/exchange capacity and retried doubled",
)
_SHARD_PLANS = _m.counter(
    "kolibrie_shard_plan_total",
    "Templates planned for the mesh on their first sight, by where the "
    "seed came from (counted: the host count of the chains; constants: "
    "the most-constants rule, nothing could be counted)",
    labels=("source",),
)
_SHARD_CAP_SLOTS = _m.counter(
    "kolibrie_shard_cap_slots_total",
    "Join and exchange slots the dispatched mesh executables were compiled "
    "for: live members x shards x (join steps x join_cap + exchanges x "
    "shards x bucket_cap)",
)
_SHARD_SEED_SLOTS = _m.counter(
    "kolibrie_shard_seed_slots_total",
    "Slots of the compacted seed tables the dispatched mesh executables "
    "were compiled for: live members x shards x join_cap",
)
_SHARD_SEED_ROWS = _m.counter(
    "kolibrie_shard_seed_rows_total",
    "Rows the mesh programs' seed scans counted, per live member and shard "
    "(over seed_slots_total: the seed tables' occupancy)",
)
_SHARD_JOIN_ROWS = _m.counter(
    "kolibrie_shard_join_rows_total",
    "Rows the mesh programs counted into those slots: per live member and "
    "shard the key matches of each join step and the rows each exchange "
    "delivered (over cap_slots_total: the mesh path's occupancy)",
)
_SHARD_XROWS = _m.counter(
    "kolibrie_shard_exchange_rows_total",
    "Rows the mesh programs' all_to_all exchanges delivered, per live "
    "member and shard (the exchange<k> columns of the shard_stats block, "
    "which join_rows_total lumps with the key matches)",
)
_SHARD_XSLOTS = _m.counter(
    "kolibrie_shard_exchange_slots_total",
    "Slots the exchanges' receive buffers were compiled for: live members "
    "x exchanges x shards x shards x bucket_cap (exchange_rows_total over "
    "this is what an exchange really carries)",
)
_SHARD_MERGED_ROWS = _m.counter(
    "kolibrie_shard_merged_rows_total",
    "Live rows the host merge decoded: the live members' final rows, "
    "summed over the shards",
)
_SHARD_MERGED_BYTES = _m.counter(
    "kolibrie_shard_merged_bytes_total",
    "Bytes the host merge's transfers brought down, padding included "
    "(merged_rows_total x a row's width x 4 over this is its occupancy)",
)
_SHARD_BASE_REBUILDS = _m.counter(
    "kolibrie_shard_base_rebuilds_total",
    "Re-partitions of the frozen base into the two hash mirrors (one a "
    "bulk load, whatever its chunk count; one a compaction)",
)
_SHARD_PARTITION_SECONDS = _m.counter(
    "kolibrie_shard_partition_seconds_total",
    "Seconds ShardedDatabase.refresh spent bringing the mirrors up to "
    "date: base re-partitions, delta blocks, the sorted sides",
)
_SHARD_FALLBACKS = _m.counter(
    "kolibrie_shard_fallback_total",
    "Template groups the mesh path declined",
    labels=("reason",),
)
_SHARD_DISPATCH_LAT = _m.histogram(
    "kolibrie_shard_dispatch_seconds", "Mesh dispatch latency (one group)"
)

# a template's running sums in ``ShardedDatabase.stats()``: its dispatches,
# their live members, the seconds of ``shard.build`` / ``.wait`` / ``.merge``
_TOOK = ("dispatches", "members", "build_s", "wait_s", "merge_s")

# ------------------------------------------------- compile-surface tracking
# One entry per distinct batched program / assemble shape ever built — the
# no-recompile regression asserts these stay flat across mutation batches.
_compile_stats = {"batched_programs": 0, "assemble_shapes": 0}
_ASSEMBLE_SHAPES: set = set()


def sharded_compile_stats() -> Dict[str, int]:
    """Counters of distinct compiled surfaces on the sharded serving path
    (monotonic; flat across mutation batches under ``delta_threshold``)."""
    return dict(_compile_stats)


# ------------------------------------------------------------ device pieces


@jax.jit
def _assemble(base_cols, base_valid, add_cols, add_valid, del_pos):
    """Combine the resident base blocks with the O(delta) add blocks and
    tombstones into the view the mesh programs scan: tombstoned base rows
    flip invalid (scatter at intra-shard positions; the ``base_cap``
    sentinel lands out of bounds and drops), then base and delta concat
    along the row axis.  Shapes are a function of ``(n, base_cap,
    delta_cap)`` only — mutation batches reuse the same executable."""
    bv = jax.vmap(lambda v, p: v.at[p].set(False, mode="drop"))(
        base_valid, del_pos
    )
    cols = tuple(
        jnp.concatenate([b, a], axis=1) for b, a in zip(base_cols, add_cols)
    )
    return cols, jnp.concatenate([bv, add_valid], axis=1)


def _strmask_verdict(col, masks, f):
    from kolibrie_tpu.parallel.dist_query import _strmask_verdict as _sv

    return _sv(col, masks, f)


def _join_presorted(lkey, lvalid, rsorted, order, cap):
    """:func:`dist_join.local_join_u32` against a PRE-sorted right side:
    identical ``(li, ri, valid, total)`` contract, minus the per-call
    ``argsort`` — the batched body joins every live member of the loop
    against the same resident mirror, sorted once a refresh of the mirrors
    (:func:`_get_sort_fn`).  ``total`` counts UNFILTERED key matches
    (the side premise's constant filters apply post-join): that is the
    number the host count sizes ``join_cap`` from
    (``DistQueryExecutor._count_chain`` with ``batched``) and the overflow
    retry doubles against."""
    ln, rn = lkey.shape[0], rsorted.shape[0]
    lk = jnp.where(lvalid, lkey.astype(jnp.uint32), _JLPAD)
    lo = jnp.searchsorted(rsorted, lk, side="left")
    hi = jnp.searchsorted(rsorted, lk, side="right")
    counts = (hi - lo).astype(jnp.int32)
    cum = prefix_count(counts)
    total = cum[-1]
    idx = jnp.arange(cap, dtype=jnp.int32)
    row = jnp.searchsorted(cum, idx, side="right")
    row_c = jnp.clip(row, 0, ln - 1)
    start = cum[row_c] - counts[row_c]
    pos = lo[row_c] + (idx - start)
    valid = idx < total
    li = jnp.where(valid, row_c, 0).astype(jnp.int32)
    ri = jnp.where(
        valid, order[jnp.clip(pos, 0, rn - 1)], 0
    ).astype(jnp.int32)
    return li, ri, valid, total


def _batched_body(
    state,
    masks,
    params,
    live,
    *,
    premises,
    seed,
    steps,
    filters,
    out_vars,
    n,
    axis,
    join_cap,
    bucket_cap,
    out_cap,
):
    """One template group in one mesh program: a loop over the first
    ``live`` rows of the ``[slots, n_slots]`` constant matrix, each member
    running the shard-local scan -> routed-join -> filter pipeline of
    ``dist_query._query_body`` and writing its outputs into row ``i`` of
    preallocated ``[slots, ...]`` buffers.  Unlike that body, a member
    compacts its seed scan to ``join_cap`` rows (:func:`dist_join.compact`)
    before the first step, so its binding table is never wider than
    ``max(join_cap, n * bucket_cap)``: the seed's mask and one prefix count
    of it are all a member does at the shard's width.  A member's final
    rows are compacted once more, into the first of ``out_cap`` slots a
    shard, so what the host merge brings down is ``out_cap`` wide and not
    ``join_cap``, and it decodes the counted rows alone.  ``live`` is a
    traced scalar,
    replicated over the mesh: every shard runs the same trips, so the
    ``all_to_all`` / ``psum`` inside the loop stay matched, and a group of
    any size up to ``slots`` shares this one executable at the cost of its
    live members.  Rows past ``live`` stay invalid (zero) and are never
    read.  Premise ``consts`` here hold SLOT INDICES into the parameter
    vector (the template's constant-free twin), so every constant-variant
    of the template shares the executable too."""
    from kolibrie_tpu.parallel.dist_query import exchanged_steps

    fs, fp, fo, fv, gs, gp, go, gv, fsorted, forder, gsorted, gorder = (
        a[0] for a in state
    )
    masks = tuple(masks)
    fcols = (fs, fp, fo)
    # Exchange elision (a trace-time decision; the program cache key
    # covers seed/steps): a step whose join key the rows are already
    # partitioned by is co-located and skips its all-to-all.
    # Subject-keyed star joins — the dominant serving templates —
    # exchange nothing.
    exchanged = exchanged_steps(premises, seed, steps, n)

    # Every member of every dispatch joins against the same resident
    # mirror, so its sort by the join key is not the program's: the state
    # brings each mirror's sorted keys and their rows (``_get_sort_fn``,
    # once a refresh).  The side premise's constant filters (which DO vary
    # per member) apply post-join at the matched rows instead of
    # pre-masking the sort input.
    sides = [
        (fcols, forder, fsorted) if kpos == 0 else ((gs, gp, go), gorder, gsorted)
        for (_j, _kv, kpos, _extra) in steps
    ]

    def scan_param(prem, cols, valid, prm):
        m = valid
        for c, col in zip(prem.consts, cols):
            if c is not None:
                m = m & (col == prm[c])
        for a, b in prem.eq_pairs:
            m = m & (cols[a] == cols[b])
        table = {v: cols[pos] for v, pos in prem.vars}
        return table, m

    def one(prm):
        with jax.named_scope("seed"):
            table, valid = scan_param(premises[seed], fcols, fv, prm)
        # The seed's few rows leave the shard's width here: everything
        # below runs at ``join_cap`` or ``n * bucket_cap``, and rows past
        # ``join_cap`` on a shard overflow like any join's.
        with jax.named_scope("compact"):
            names = sorted(table)
            cols, valid, dropped = compact(
                tuple(table[v] for v in names), valid, join_cap
            )
            table = dict(zip(names, cols))
            ov = lax.psum(dropped, axis)
        # Per-operator stats, SHARD-LOCAL (no psum: the host sees the
        # [slots, n, n_stats] block and can read imbalance per shard or sum
        # across shards).  Layout: [seed rows, (exchange rows, key
        # matches, join rows) per step, final rows] — exchange slot stays
        # 0 when the step's all-to-all is elided by co-partitioning; the
        # seed rows and the key matches are what ``join_cap`` has to hold,
        # the join rows what the side premise's constants leave of them.
        svec = [jnp.sum(valid).astype(jnp.int32) + dropped]
        for k, ((j, kv, kpos, extra), routed, (side_cols, order, rsorted)) in (
            enumerate(zip(steps, exchanged, sides))
        ):
            prem = premises[j]
            if routed:
                with jax.named_scope(f"exchange{k}"):
                    table, valid, dropped = _exchange_table(
                        table, valid, kv, n, axis, bucket_cap
                    )
                    ov = ov + dropped.astype(jnp.int32)
                    svec.append(jnp.sum(valid).astype(jnp.int32))
            else:
                svec.append(jnp.int32(0))
            with jax.named_scope(f"join{k}"):
                li, ri, jvalid, total = _join_presorted(
                    table[kv], valid, rsorted, order, join_cap
                )
                ov = ov + lax.psum(
                    jnp.maximum(total - join_cap, 0).astype(jnp.int32), axis
                )
                svec.append(total.astype(jnp.int32))
                # side premise filters, post-join at the matched rows
                for c, col in zip(prem.consts, side_cols):
                    if c is not None:
                        jvalid = jvalid & (col[ri] == prm[c])
                for a, b in prem.eq_pairs:
                    jvalid = jvalid & (side_cols[a][ri] == side_cols[b][ri])
                ptable = {v: side_cols[pos] for v, pos in prem.vars}
                new_table = {v: c[li] for v, c in table.items()}
                for v, c in ptable.items():
                    if v not in new_table:
                        new_table[v] = c[ri]
                    elif v in extra:
                        jvalid = jvalid & (new_table[v] == c[ri])
                table, valid = new_table, jvalid
                svec.append(jnp.sum(valid).astype(jnp.int32))
        with jax.named_scope("filters"):
            for f in filters:
                col = table[f.var]
                if f.kind == "eq":
                    valid = valid & (col == jnp.uint32(f.const_id))
                elif f.kind == "ne":
                    valid = valid & (col != jnp.uint32(f.const_id))
                elif f.kind == "strmask":
                    valid = valid & _strmask_verdict(col, masks, f)
                else:
                    m = masks[f.mask_idx]
                    valid = valid & m[jnp.minimum(col, m.shape[0] - 1)]
            svec.append(jnp.sum(valid).astype(jnp.int32))
        with jax.named_scope("emit"):
            outs, _ok, dropped = compact(
                tuple(table[v] for v in out_vars), valid, out_cap
            )
            ov = ov + lax.psum(dropped.astype(jnp.int32), axis)
        return outs, ov, jnp.stack(svec)

    # The live-member loop.  The carry is typed from one member's outputs
    # (shapes, dtypes and which of them vary over the mesh axis), so the
    # zero buffers enter the loop as what the body writes back.
    def buffer(aval):
        buf = jnp.zeros((params.shape[0], *aval.shape), aval.dtype)
        vary = tuple(getattr(aval, "vma", ()) or ())
        return lax.pcast(buf, vary, to="varying") if vary else buf

    def member(i, bufs):
        return jax.tree.map(
            lambda buf, x: lax.dynamic_update_index_in_dim(buf, x, i, 0),
            bufs,
            one(lax.dynamic_index_in_dim(params, i, 0, keepdims=False)),
        )

    outs, ovs, svecs = lax.fori_loop(
        0, live, member, jax.tree.map(buffer, jax.eval_shape(one, params[0]))
    )
    overflow = jnp.sum(ovs)  # each member's ov is already a global psum
    return (
        tuple(o[:, None] for o in outs),
        overflow[None],
        svecs[:, None, :],
    )


# Memoized program factory (the sanctioned jit-factory pattern) — the key
# is the template's constant-free shape, its capacities and the slot
# class, so constant-variants, mutation epochs and every group size up to
# ``slots`` share one executable (the class is ``ops.slot_class``, the
# one-chip batch's rule too).

@lru_cache(maxsize=64)
def _get_batched_fn(
    mesh, premises, seed, steps, filters, out_vars, n_masks, join_cap,
    bucket_cap, out_cap, slots,
):
    _compile_stats["batched_programs"] += 1
    axis = mesh.axis_names[0]
    n = mesh.devices.size
    body = partial(
        _batched_body,
        premises=premises,
        seed=seed,
        steps=steps,
        filters=filters,
        out_vars=out_vars,
        n=n,
        axis=axis,
        join_cap=join_cap,
        bucket_cap=bucket_cap,
        out_cap=out_cap,
    )
    spec = P(axis, None)
    bspec = P(None, axis, None)
    return jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            check_vma=_dist_check_vma(),
            in_specs=((spec,) * 12, (P(),) * n_masks, P(), P()),
            out_specs=((bspec,) * len(out_vars), P(axis), bspec),
        )
    )


@lru_cache(maxsize=8)
def _get_sort_fn(mesh):
    """A mirror's rows by their join key, shard by shard: ``(sorted keys,
    the rows they came from)``, invalid rows last.  Every join step of
    every template searches one of the two mirrors by its key, and a mirror
    moves only with the store, so it is sorted once a refresh and not once
    a dispatch (until PR 50 each program sorted for itself: 19 s of a
    program's 20 s compile at 2 M rows a shard, and 4 % of
    ``lubm5.mesh4``'s busy time).  One executable a mirror width."""
    axis = mesh.axis_names[0]

    def body(key, valid):
        rk = jnp.where(valid[0], key[0].astype(jnp.uint32), _JRPAD)
        # lax.sort carries the rows through the sort instead of
        # argsort-then-gather: XLA:CPU fuses an ``rk[order]`` gather into
        # a consuming searchsorted incorrectly under shard_map (observed
        # as phantom join matches), and the fused form is also slower.
        iota = jnp.arange(rk.shape[0], dtype=jnp.int32)
        rsorted, order = lax.sort((rk, iota), num_keys=1)
        return rsorted[None], order[None]

    spec = P(axis, None)
    return jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            check_vma=_dist_check_vma(),
            in_specs=(spec, spec),
            out_specs=(spec, spec),
        )
    )


def _pad_pow2_mask(m: np.ndarray) -> np.ndarray:
    """Pad a per-ID boolean mask to a power of two with False — mask SHAPES
    then move only when the dictionary doubles, not on every intern, so
    mutation batches keep the batched executable."""
    n = len(m)
    cap = max(8, 1 << max(n - 1, 1).bit_length())
    if cap == n:
        return m
    out = np.zeros(cap, dtype=bool)
    out[:n] = m
    return out


# --------------------------------------------------------------- partitioning


class _HashMirror:
    """One hash-partitioned two-tier mirror (key = subject or object column).

    Holds the device-resident base blocks plus the host row->shard map
    (``base_dest``/``base_intra``) that translates the store's global
    tombstone positions into per-shard scatter positions in O(delta)."""

    def __init__(self, key_pos: int):
        self.key_pos = key_pos
        self.base_cols = None  # device [n, base_cap] x3
        self.base_valid = None  # device [n, base_cap], PRE-tombstone
        self.base_dest = None  # host [N] shard of each base row
        self.base_intra = None  # host [N] position within its shard block
        self.base_counts = None  # host [n]
        self.add_cols = None  # device [n, delta_cap] x3
        self.add_valid = None
        self.del_pos = None  # device [n, delta_cap] int32, sentinel=base_cap
        self.add_counts = None  # host [n]
        self.del_counts = None  # host [n]

    def rebuild_base(self, cols, n: int, base_cap: int, sharding) -> None:
        key = cols[self.key_pos]
        dest = shard_of(key, n)
        counts = np.bincount(dest, minlength=n)
        order = np.argsort(dest, kind="stable")
        offs = np.concatenate([[0], np.cumsum(counts)])
        intra = np.empty(len(key), dtype=np.int64)
        blocks = [np.zeros((n, base_cap), dtype=np.uint32) for _ in range(3)]
        valid = np.zeros((n, base_cap), dtype=bool)
        for sh in range(n):
            rows = order[offs[sh] : offs[sh + 1]]
            intra[rows] = np.arange(len(rows))
            for blk, col in zip(blocks, cols):
                blk[sh, : len(rows)] = col[rows]
            valid[sh, : len(rows)] = True
        put = lambda a: jax.device_put(a, sharding)  # noqa: E731
        self.base_cols = tuple(put(b) for b in blocks)
        self.base_valid = put(valid)
        self.base_dest = dest
        self.base_intra = intra
        self.base_counts = counts
        _SHARD_H2D.labels("base").inc(n * base_cap * (3 * 4 + 1))

    def refresh_delta(
        self, add_cols, del_global_pos, n: int, base_cap: int,
        delta_cap: int, sharding,
    ) -> None:
        key = add_cols[self.key_pos]
        dest = shard_of(key, n)
        counts = np.bincount(dest, minlength=n)
        if counts.max(initial=0) > delta_cap:
            raise OverflowError("delta shard load exceeds delta_device_cap")
        order = np.argsort(dest, kind="stable")
        offs = np.concatenate([[0], np.cumsum(counts)])
        blocks = [np.zeros((n, delta_cap), dtype=np.uint32) for _ in range(3)]
        valid = np.zeros((n, delta_cap), dtype=bool)
        for sh in range(n):
            rows = order[offs[sh] : offs[sh + 1]]
            for blk, col in zip(blocks, add_cols):
                blk[sh, : len(rows)] = col[rows]
            valid[sh, : len(rows)] = True
        # tombstones: global base positions -> (shard, intra) via the maps
        # recorded at base partition time; sentinel base_cap drops in the
        # _assemble scatter
        dpos = np.full((n, delta_cap), base_cap, dtype=np.int32)
        dd = self.base_dest[del_global_pos]
        di = self.base_intra[del_global_pos]
        dcounts = np.bincount(dd, minlength=n)
        if dcounts.max(initial=0) > delta_cap:
            raise OverflowError("tombstone shard load exceeds delta_device_cap")
        dorder = np.argsort(dd, kind="stable")
        doffs = np.concatenate([[0], np.cumsum(dcounts)])
        for sh in range(n):
            rows = dorder[doffs[sh] : doffs[sh + 1]]
            dpos[sh, : len(rows)] = di[rows]
        put = lambda a: jax.device_put(a, sharding)  # noqa: E731
        self.add_cols = tuple(put(b) for b in blocks)
        self.add_valid = put(valid)
        self.del_pos = put(dpos)
        self.add_counts = counts
        self.del_counts = dcounts
        _SHARD_H2D.labels("delta").inc(n * delta_cap * (3 * 4 + 1 + 4))

    def assemble(self):
        shape = (
            self.base_valid.shape[0],
            self.base_valid.shape[1],
            self.add_valid.shape[1],
        )
        if shape not in _ASSEMBLE_SHAPES:
            _ASSEMBLE_SHAPES.add(shape)
            _compile_stats["assemble_shapes"] += 1
        return _assemble(
            self.base_cols,
            self.base_valid,
            self.add_cols,
            self.add_valid,
            self.del_pos,
        )

    def occupancy(self) -> np.ndarray:
        return self.base_counts + self.add_counts - self.del_counts


# -------------------------------------------------------------- the database


class ShardedDatabase:
    """Mesh-resident serving twin of one :class:`SparqlDatabase`.

    Owns the two hash mirrors (subject- and object-partitioned), the
    combined :class:`ShardedTripleStore` view the distributed executors
    scan, per-template pinned plans (seed and capacities), and the batched
    dispatch path.
    All mutating entry points hold :attr:`lock`; the executor calls them
    under the HTTP batcher's ``dispatch_lock`` as well."""

    def __init__(self, db, mesh=None):
        if mesh is None:
            mesh = make_mesh()
        self.db = db
        self.mesh = mesh
        self.n = mesh.devices.size
        self.axis = mesh.axis_names[0]
        self.lock = threading.RLock()
        self._subj = _HashMirror(0)
        self._obj = _HashMirror(2)
        # (sorted keys, their rows) of each mirror's view, as of the last
        # refresh: the right side of every join step
        self._subj_sorted = None  # guarded by: lock
        self._obj_sorted = None  # guarded by: lock
        self.view: Optional[ShardedTripleStore] = None  # guarded by: lock
        self._sig = None  # guarded by: lock
        self._base_ref = None  # guarded by: lock
        self._base_cap_s = 0
        self._base_cap_o = 0
        self._delta_cap = 0
        # (fingerprint, base version) -> (seed, join_cap, bucket_cap,
        # out_cap): the plan a template got on its first sight, and the
        # capacities that held since
        self._plans: Dict[tuple, Tuple[int, int, int, int]] = {}  # guarded by: lock
        # fingerprint -> what its dispatches took so far, by ``_TOOK``
        self._by_template: Dict[str, dict] = {}  # guarded by: lock
        self.stats_counters = {
            "base_rebuilds": 0,
            "delta_refreshes": 0,
            "dispatches": 0,
            "batched_queries": 0,
            "fallbacks": 0,
            "cap_hits": 0,
            "last_cap_hit": None,
        }  # guarded by: lock

    @property
    def signature(self) -> tuple:
        """Hashable mesh identity for plan-cache state keys: attaching,
        detaching, or resizing the mesh must never replay a plan lowered
        for another topology."""
        return ("shards", self.n, self.axis)

    # ------------------------------------------------------------- mirrors

    def refresh(self, force: bool = False) -> bool:
        """Sync the device mirrors to the store's live two-tier state.
        Base blocks re-partition only when ``base_version`` moved (or the
        base arrays were swapped by ``restore()``); otherwise only the
        O(delta) add/tombstone blocks re-upload.  Returns True when any
        device state moved.

        Nothing calls this for a write: the mirrors are stale from the
        moment the store moves until the first read that needs them, and
        every read starts here (:meth:`execute_batch`), so a read sees
        every triple the store holds, and a bulk load of *k* chunks, each
        of which folds into the base, re-partitions the base once and not
        *k* times.  Recovery and a follower's bootstrap call it before
        they serve."""
        with self.lock:
            t0 = time.perf_counter()
            st = self.db.store
            sig = st.segment_signature()
            anchor = st.base_rows("spo")[0]
            base_same = (
                self._base_ref is not None and self._base_ref() is anchor
            )
            if not force and sig == self._sig and base_same:
                return False
            base_changed = force or not base_same
            sharding = NamedSharding(self.mesh, P(self.axis, None))
            if base_changed:
                bs, bp, bo = st.base_rows("spo")
                # independent caps per mirror: the object partitioning is
                # skew-prone (rdf:type objects pile onto one shard) and
                # must not inflate the subject mirror's scan range — every
                # serving program scans the subject mirror at least twice
                def _cap_for(col):
                    need = (
                        np.bincount(
                            shard_of(col, self.n), minlength=self.n
                        ).max()
                        if len(col)
                        else 0
                    )
                    return max(8, 1 << max(int(need) - 1, 1).bit_length())

                self._base_cap_s = _cap_for(bs)
                self._base_cap_o = _cap_for(bo)
                self._delta_cap = int(st.delta_device_cap)
                with span(
                    "shard.partition_base",
                    rows=len(bs),
                    cap_subj=self._base_cap_s,
                    cap_obj=self._base_cap_o,
                ):
                    self._subj.rebuild_base(
                        (bs, bp, bo), self.n, self._base_cap_s, sharding
                    )
                    self._obj.rebuild_base(
                        (bs, bp, bo), self.n, self._base_cap_o, sharding
                    )
                # the one place a rebuild is counted: ``stats()`` and
                # ``/metrics`` read the same event
                self.stats_counters["base_rebuilds"] += 1
                _SHARD_BASE_REBUILDS.inc()
            adds = st.delta_rows("spo")
            dels = st.delta_del_positions("spo")
            for mirror, bcap in (
                (self._subj, self._base_cap_s),
                (self._obj, self._base_cap_o),
            ):
                mirror.refresh_delta(
                    adds, dels, self.n, bcap, self._delta_cap, sharding
                )
            if self.view is None or base_changed:
                cap = self._base_cap_s + self._delta_cap
                view = ShardedTripleStore.__new__(ShardedTripleStore)
                view.mesh = self.mesh
                view.axis = self.axis
                view.n_shards = self.n
                view.cap = cap
                view.sharding = sharding
                view.subj_packed_sorted = None
                view._subj_index_src = None
                view.subj_index_parts = None
                view._subj_base_packed = None
                view._subj_base_end = None
                view.subj_index_base_builds = 0
                view.subj_index_delta_builds = 0
                self.view = view
            self.view.by_subj, self.view.by_subj_valid = self._subj.assemble()
            self.view.by_obj, self.view.by_obj_valid = self._obj.assemble()
            # each mirror's rows by its join key, the two beside one
            # another: two widths are two executables, and on a machine's
            # first sight of them the sorts compile at once, not one after
            # the other (compiling releases the interpreter lock)
            sort = _get_sort_fn(self.mesh)
            with ThreadPoolExecutor(2) as pool:
                self._subj_sorted, self._obj_sorted = pool.map(
                    lambda side: sort(*side),
                    (
                        (self.view.by_subj[0], self.view.by_subj_valid),
                        (self.view.by_obj[2], self.view.by_obj_valid),
                    ),
                )
            if self.view.subj_index_parts is not None:
                # a consumer of the probe index has asked for one on this
                # view (``dist_join.dist_bgp_join_count``): keep it two-tier,
                # the base pack surviving delta refreshes.  No served
                # template reads it, so a view nobody asked builds none
                # (``ensure_subj_index`` builds on demand)
                self.view.refresh_subj_index(
                    base_end=self._base_cap_s,
                    base_valid=self._subj.base_valid,
                    del_pos=self._subj.del_pos,
                    base_unchanged=not base_changed,
                )
            self._sig = sig
            self._base_ref = weakref.ref(anchor)
            self.stats_counters["delta_refreshes"] += 1
            occ = self._subj.occupancy()
            mean = float(occ.mean()) if len(occ) else 0.0
            imb = float(occ.max()) / mean if mean > 0 else 1.0
            _SHARD_IMBALANCE.set(imb)
            for sh in range(self.n):
                _SHARD_OCCUPANCY.labels(str(sh)).set(int(occ[sh]))
            _SHARD_PARTITION_SECONDS.inc(time.perf_counter() - t0)
            return True

    # ------------------------------------------------------------ execution

    def _pinned_plan(self, fp: str) -> Optional[Tuple[int, int, int]]:  # kolint: holds[lock]
        bv = self._sig[0] if self._sig else None
        for k in [k for k in self._plans if k[1] != bv]:
            self._plans.pop(k)
        return self._plans.get((fp, bv))

    def warm(self, sparql: str) -> bool:
        """Pre-compile the mesh program for one template off the request
        path (the background warmer's entry point).  The query is
        dispatched as a group of one through :meth:`execute_batch`, so it
        lowers and jits the very executable that every later request of
        the template runs, alone or in a group (one per template and
        capacity set), and the capacities settle here too — with the
        persistent compilation cache enabled the XLA work is a disk load
        on every process after the first.  Returns False (instead of
        raising) for templates the mesh lowering declines: the warmer
        treats that as "this template serves single-device" and moves
        on."""
        from kolibrie_tpu.query.parser import parse_combined_query

        fp, _ = fingerprint_query(
            parse_combined_query(sparql, self.db.prefixes)
        )
        try:
            self.execute_batch(fp, [(0, sparql)])
        except Unsupported:
            return False
        with self.lock:
            self.stats_counters["prewarmed"] = (
                self.stats_counters.get("prewarmed", 0) + 1
            )
        return True

    def execute_batch(
        self, fp: str, items: List[Tuple[int, str]]
    ) -> Dict[int, List[List[str]]]:
        """One template group -> one mesh dispatch.  ``items`` is
        ``[(caller_index, sparql), ...]`` of same-fingerprint plain
        SELECTs, one member or many; returns ``{caller_index: rows}``
        with rows identical to the solo host path.  The group is
        dispatched in the slot class of its size (:func:`_slot_class`) and
        costs what its live members cost.  Raises :class:`Unsupported`
        when the group cannot ride the parameterized program (the caller
        falls through to the single-device paths), and lets device faults
        / deadline misses propagate for the breaker protocol."""
        with self.lock:
            self.refresh()
            check_deadline("shard.dispatch")
            live = len(items)
            t0 = time.perf_counter()
            with span(
                "shard.dispatch", shards=self.n, batch=live, template=fp
            ):
                with span("shard.build"):
                    group = self._build_group(fp, items)
                t1 = time.perf_counter()
                fault_point("shard.dispatch")
                with span(
                    "shard.wait", slots=group["params"].shape[0], live=live
                ) as sp:
                    device_out = self._run_group(fp, group, sp)
                t2 = time.perf_counter()
                with span("shard.merge"):
                    results = self._merge_group(fp, items, group, device_out)
            t3 = time.perf_counter()
            _SHARD_DISPATCH_LAT.observe(t3 - t0)
            self._count_dispatch(fp, group)
            # the three spans' seconds by template, for ``stats()``: a
            # dispatch of several templates is one trace, and the spans'
            # ring keeps a window's last requests only
            took = self._by_template.setdefault(fp, dict.fromkeys(_TOOK, 0))
            for key, more in zip(_TOOK, (1, live, t1 - t0, t2 - t1, t3 - t2)):
                took[key] += more
            return results

    def _decline(self, reason: str) -> None:  # kolint: holds[lock]
        self.stats_counters["fallbacks"] += 1
        _SHARD_FALLBACKS.labels(reason).inc()

    def _build_group(self, fp: str, items) -> dict:  # kolint: holds[lock]
        """Host side of a dispatch before the program: the members'
        lowerings, their structural agreement, the ``[slots, n_slots]``
        parameter matrix and the replicated filter masks."""
        from kolibrie_tpu.parallel.dist_query import (
            DistQueryExecutor,
            _materialize_masks,
        )
        from kolibrie_tpu.reasoner.device_fixpoint import LoweredPremise

        # The plan is the template's, not the member's: on a template's
        # first sight (or after a base-version bump dropped the pin) the
        # first member's constants are counted on the host — the seed, its
        # step order and the capacities, for the joins ``_batched_body``
        # runs and the rows it emits — and every member of this and every
        # later group is lowered with that plan, so the group shares one
        # shape and the template one executable a capacity set.
        plan = self._pinned_plan(fp)
        kw = (
            {"seed": plan[0], "join_cap": plan[1], "bucket_cap": plan[2]}
            if plan
            else {}
        )
        try:
            exemplar = DistQueryExecutor(
                self.mesh,
                self.db,
                items[0][1],
                store=self.view,
                batched=True,
                **kw,
            )
        except Unsupported:
            self._decline("unsupported")
            raise
        if plan is None:
            _SHARD_PLANS.labels(exemplar.plan_source).inc()
            # a member's final rows sit in a table of ``join_cap`` slots a
            # shard; what comes to the host is the counted rows' capacity
            # by the one rule, never wider than that table
            out_cap = exemplar.join_cap
            if exemplar.final_rows is not None:
                (out_cap,) = fit_join_caps(
                    [out_cap], [exemplar.final_rows], [exemplar.final_is_ceiling]
                )
        else:
            out_cap = plan[3]
        if (
            exemplar.agg_items
            or exemplar.query.group_by
            or exemplar.binds
            or exemplar.union_specs
            or exemplar.optional_specs
            or exemplar.anti
            or exemplar.values_var is not None
            or exemplar.query.order_by
        ):
            # _batchable_select should have filtered these; belt and
            # braces for direct callers
            self._decline("shape")
            raise Unsupported("clause shape stays on the vmap path")
        execs = [exemplar]
        for _idx, text in items[1:]:
            execs.append(
                DistQueryExecutor(
                    self.mesh,
                    self.db,
                    text,
                    store=self.view,
                    seed=exemplar.seed,
                    join_cap=exemplar.join_cap,
                    bucket_cap=exemplar.bucket_cap,
                    batched=True,
                )
            )
        # structural agreement: the group shares one constant-free
        # shape; filter constants must MATCH (the single-device vmap
        # path parameterizes those — this path parameterizes pattern
        # constants, by far the common serving variation)
        def shape_of(ex):
            return (
                tuple(
                    (
                        tuple(c is not None for c in pr.consts),
                        pr.vars,
                        pr.eq_pairs,
                    )
                    for pr in ex.premises
                ),
                ex.seed,
                ex.steps,
                ex.filters,
                ex.mask_exprs,
                ex.out_vars,
            )

        shape0 = shape_of(exemplar)
        if any(shape_of(ex) != shape0 for ex in execs[1:]):
            self._decline("divergent")
            raise Unsupported("group members diverge beyond pattern constants")
        # constant slots -> parameter matrix [slots, n_slots]: the live
        # members fill the first rows, the rest of the slot class stays
        # zero and is never run
        consts = [
            (i, pos)
            for i, pr in enumerate(exemplar.premises)
            for pos in range(3)
            if pr.consts[pos] is not None
        ]
        const_idx = {ip: k for k, ip in enumerate(consts)}
        param_premises = tuple(
            LoweredPremise(
                tuple(
                    const_idx[(i, pos)] if c is not None else None
                    for pos, c in enumerate(pr.consts)
                ),
                pr.vars,
                pr.eq_pairs,
            )
            for i, pr in enumerate(exemplar.premises)
        )
        params = np.zeros(
            (_slot_class(len(execs)), max(len(consts), 1)), dtype=np.uint32
        )
        for r, ex in enumerate(execs):
            for k, (i, pos) in enumerate(consts):
                params[r, k] = np.uint32(ex.premises[i].consts[pos])
        masks = tuple(
            jnp.asarray(_pad_pow2_mask(np.asarray(m)))
            for m in _materialize_masks(self.db, exemplar.mask_exprs)
        )
        return {
            "execs": execs,
            "premises": param_premises,
            "params": params,
            "masks": masks,
            "caps": (exemplar.join_cap, exemplar.bucket_cap, out_cap),
        }

    def _run_group(self, fp: str, group: dict, sp):  # kolint: holds[lock]
        """The program, until its outputs are ready: one jit call, and
        one more at doubled capacities for each overflow (a ``retry<k>``
        attribute on the ``shard.wait`` span ``sp``).  Leaves the
        capacities that held in ``group["caps"]``."""

        exemplar = group["execs"][0]
        state = (
            *self.view.by_subj,
            self.view.by_subj_valid,
            *self.view.by_obj,
            self.view.by_obj_valid,
            *self._subj_sorted,
            *self._obj_sorted,
        )
        live = np.int32(len(group["execs"]))
        join_cap, bucket_cap, out_cap = group["caps"]
        for attempt in range(8):
            key = (
                group["premises"],
                exemplar.seed,
                exemplar.steps,
                exemplar.filters,
                exemplar.out_vars,
                len(group["masks"]),
                join_cap,
                bucket_cap,
                out_cap,
                group["params"].shape[0],
            )
            fn = _get_batched_fn(self.mesh, *key)
            with jax.enable_x64(True):
                # the program's key and the mesh's size are what this layer
                # holds to define the executable (a first sight's identity)
                outs, overflow, shard_stats = _cc.call(
                    fn, state, group["masks"], group["params"], live,
                    declared=("mesh", (self.mesh.devices.size, key)),
                )
            if int(np.asarray(overflow)[0]) == 0:
                break
            if sp is not None:
                sp.attrs[f"retry{attempt}"] = [join_cap, bucket_cap, out_cap]
            join_cap *= 2
            bucket_cap *= 2
            out_cap = min(2 * out_cap, join_cap)
            self.stats_counters["cap_hits"] += 1
            self.stats_counters["last_cap_hit"] = time.time()
            _SHARD_CAP_HITS.inc()
            note_cap_retry("sharded")
        else:
            raise RuntimeError("sharded batch capacities failed to converge")
        group["caps"] = (join_cap, bucket_cap, out_cap)
        return jax.block_until_ready((outs, shard_stats))

    def _merge_group(self, fp: str, items, group: dict, device_out):  # kolint: holds[lock]
        """Host side of a dispatch after the program: one transfer of the
        dispatch's answers (``out_cap`` slots a member and shard, with the
        per-operator counts beside them), the analyze records, per live
        member the rows its shards counted and the post-pass of the solo
        path, then the per-shard span children."""
        from kolibrie_tpu.query.executor import _finish_select_table

        execs = group["execs"]
        exemplar, live = execs[0], len(execs)
        # the per-operator counts ride the result transfer (about 1 KB a
        # dispatch): the occupancy counters read them on every dispatch,
        # an analyze capture records them per member
        outs, stats_all = jax.device_get(device_out)
        stats_np = group["stats"] = stats_all[:live]
        cap_rec = _analyze.active()
        if cap_rec is not None:
            stat_names = ["seed"]
            for k in range(len(exemplar.steps)):
                stat_names += [f"exchange{k}", f"matches{k}", f"join{k}"]
            stat_names.append("final")
            for r in range(live):
                cap_rec.record(
                    "sharded",
                    member=r,
                    template=fp,
                    shards=self.n,
                    seed=exemplar.seed,
                    steps=[(j, kv) for (j, kv, _kp, _ex) in exemplar.steps],
                    stat_names=stat_names,
                    per_shard=stats_np[r].T.tolist(),
                    operators={
                        name: int(stats_np[r, :, i].sum())
                        for i, name in enumerate(stat_names)
                    },
                    caps=list(group["caps"]),
                )
        # host merge: per member its shards' counted rows (the program
        # compacted them to the front of their ``out_cap`` slots, in the
        # table's order), then the post-pass of the solo path
        final = stats_np[:, :, -1]
        results: Dict[int, List[List[str]]] = {}
        for r, ((idx, _text), ex) in enumerate(zip(items, execs)):
            table = {
                var: np.concatenate(
                    [outs[k][r, sh, : final[r, sh]] for sh in range(self.n)]
                )
                for k, var in enumerate(exemplar.out_vars)
            }
            results[idx] = _finish_select_table(self.db, ex.query, table)
        _SHARD_MERGED_ROWS.inc(int(final.sum()))
        _SHARD_MERGED_BYTES.inc(sum(o.nbytes for o in outs) + stats_all.nbytes)
        # per-shard span children: surviving rows per shard across the
        # group (observable imbalance of THIS dispatch)
        per_shard = final.sum(axis=0)
        for sh in range(self.n):
            with span("shard.partition", shard=sh, rows=int(per_shard[sh])):
                pass
        return results

    def _count_dispatch(self, fp: str, group: dict) -> None:  # kolint: holds[lock]
        """Pin the plan with the capacities that held and count the
        dispatch: live members beside the member slots it was compiled
        for (their ratio is the loop's occupancy), the rows the program
        counted beside its seed, join and exchange slots (the capacities'
        occupancy), rows scanned, static exchange bytes."""
        from kolibrie_tpu.parallel.dist_query import exchanged_steps

        exemplar, live = group["execs"][0], len(group["execs"])
        join_cap, bucket_cap, out_cap = group["caps"]
        bv = self._sig[0]
        self._plans[(fp, bv)] = (exemplar.seed, join_cap, bucket_cap, out_cap)
        occ_total = int(self._subj.occupancy().sum())
        n_scans = 1 + len(exemplar.steps)
        _SHARD_ROWS.inc(occ_total * n_scans * live)
        width = len({v for v, _ in exemplar.premises[exemplar.seed].vars})
        xbytes = 0
        # co-partitioned steps move no bytes
        exchanged = exchanged_steps(
            exemplar.premises, exemplar.seed, exemplar.steps, self.n
        )
        for (j, _kv, _kpos, _extra), routed in zip(exemplar.steps, exchanged):
            if routed:
                xbytes += width * self.n * self.n * bucket_cap * 4
            width += len({v for v, _ in exemplar.premises[j].vars})
        _SHARD_XBYTES.inc(xbytes * live)
        _SHARD_CAP_SLOTS.inc(
            live
            * self.n
            * (
                len(exemplar.steps) * join_cap
                + sum(exchanged) * self.n * bucket_cap
            )
        )
        # stats layout: [seed, (exchange, matches, join) a step, final]
        _SHARD_SEED_SLOTS.inc(live * self.n * join_cap)
        _SHARD_SEED_ROWS.inc(int(group["stats"][:, :, 0].sum()))
        counted = group["stats"][:, :, 1:-1].reshape(live, self.n, -1, 3)
        _SHARD_JOIN_ROWS.inc(int(counted[..., :2].sum()))
        _SHARD_XROWS.inc(int(counted[..., 0].sum()))
        _SHARD_XSLOTS.inc(live * sum(exchanged) * self.n * self.n * bucket_cap)
        _SHARD_DISPATCH.labels("lone" if live == 1 else "batched").inc()
        _SHARD_QUERIES.inc(live)
        _SHARD_MEMBER_SLOTS.inc(group["params"].shape[0])
        self.stats_counters["dispatches"] += 1
        self.stats_counters["batched_queries"] += live

    # -------------------------------------------------------------- health

    def stats(self) -> dict:
        """Shard-level health for ``/stats`` (and the ``/metrics`` gauges):
        shard count, per-shard row occupancy, imbalance, last exchange cap
        hit, rebuild/dispatch counters."""
        with self.lock:
            out = {
                "shards": self.n,
                "signature": list(self.signature),
                "base_cap": {
                    "subj": self._base_cap_s,
                    "obj": self._base_cap_o,
                },
                "delta_cap": self._delta_cap,
            }
            out.update(self.stats_counters)
            # each pinned template's seed premise and capacities, and what
            # its dispatches took, span by span
            out["plans"] = {
                fp: list(plan) for (fp, _bv), plan in self._plans.items()
            }
            out["by_template"] = {
                fp: dict(took) for fp, took in self._by_template.items()
            }
            if self._subj.base_counts is not None:
                occ = self._subj.occupancy()
                mean = float(occ.mean()) if len(occ) else 0.0
                out["occupancy"] = [int(x) for x in occ]
                out["imbalance"] = (
                    float(occ.max()) / mean if mean > 0 else 1.0
                )
            out["compile_surfaces"] = sharded_compile_stats()
            return out


# ----------------------------------------------------------------- attaching


def attach_sharded(db, mesh=None) -> Optional[ShardedDatabase]:
    """Create (or return) the :class:`ShardedDatabase` riding ``db``.
    Requires a multi-device runtime; returns None on a single device so
    callers can attach unconditionally.  The executor and the obs/stats
    exporters discover it via ``db.__dict__['_sharded_serving']``."""
    existing = db.__dict__.get("_sharded_serving")
    if existing is not None:
        return existing
    if mesh is None:
        if len(jax.devices()) < 2:
            return None
        mesh = make_mesh()
    sh = ShardedDatabase(db, mesh)
    db.__dict__["_sharded_serving"] = sh
    return sh


def detach_sharded(db) -> None:
    db.__dict__.pop("_sharded_serving", None)


def active_sharded(db) -> Optional[ShardedDatabase]:
    return db.__dict__.get("_sharded_serving")
