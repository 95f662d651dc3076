"""Distributed execution of full SPARQL query plans over the device mesh.

BASELINE config 5 ("pod-sharded BGP join on LUBM-1000"): a SELECT's basic
graph pattern + filters + projection is exactly a datalog rule body, so the
distributed lowering reuses the mesh fixpoint machinery — shard-local
pattern scans over the :class:`~kolibrie_tpu.parallel.sharded_store.
ShardedTripleStore`'s subject-owned blocks, ``all_to_all`` repartitioning of
the binding table between join stages (riding ICI), local sort-merge joins
against the subject-owned facts or the object-hash mirror, replicated
numeric filter masks, and a final projection gathered to host.  One compiled
``shard_map`` program per (query shape, capacities).

This is a SINGLE-ROUND specialization of
:func:`kolibrie_tpu.parallel.dist_general._general_round`: same routed join
steps, no conclusion instantiation / dedup / fixpoint loop — the joined
binding table IS the result (SPARQL bag semantics: no dedup unless
``DISTINCT``).

Scope: BGP patterns (constants anywhere but joins keyed at subject/object
position), numeric + term-equality + constant-pattern string FILTERs
(AND-composed; string predicates as replicated per-ID verdict masks),
projection,
DISTINCT (mesh-side: projection tuples hash to an owner shard, shard-local
sort-unique is globally exact), ORDER BY + LIMIT (mesh-side per-shard
top-k, O(k·n) readback, host re-orders the union; a non-numeric sort value
ANYWHERE flips the run to global per-ID string ranks — the single-chip
engine's rank tables, replicated — and re-runs the SAME mesh top-k, so
string keys never fall back to full-result readback; for rows tied at the
k boundary the kept representative may differ from the host executor's
stable order — both are valid SPARQL answers), and BIND (the
mesh gathers all pattern variables; binds + bind-reading filters apply
host-side to the small result table — the single-chip device split).
VALUES in its constraining form (one BGP-bound variable, distinct bound
cells) lowers to a replicated membership mask inside the mesh program.
Plain sub-SELECTs (no aggregation/modifiers) fold into the BGP before
lowering (:mod:`kolibrie_tpu.query.subquery_inline` — the same rewrite
the single-chip paths apply), so nested selects distribute too.
UNION, OPTIONAL, MINUS and NOT clauses with BGP(+filter) branches run
as mesh programs: each branch evaluates through the same shard-local
pipeline, equal shared-key tuples co-locate by hash routing, then a
local join (UNION, over the branch concat with UNBOUND fill), a
left-outer join (OPTIONAL — matches plus unmatched main rows with
UNBOUND branch-only columns) or a membership test (MINUS/NOT) applies,
in the host post-pass order.
Everything else (general VALUES, non-inlinable subqueries, non-BGP
clause branches, clauses sharing no variable with the group, windows;
BIND mixed with aggregates) raises :class:`Unsupported` — callers fall
back to the single-chip engine, mirroring the device engine's own
fallback contract.

Parity: the reference has NO distributed execution (SURVEY §2.6) — this is
the TPU-native axis it lacks.  Row agreement with the host volcano executor
is tested on the virtual 8-device CPU mesh (``tests/test_dist_query.py``).
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kolibrie_tpu.ops import round_cap
from kolibrie_tpu.parallel.dist_general import _exchange_table, _plan_rule_dist
from kolibrie_tpu.parallel.dist_join import _dist_check_vma, local_join_u32
from kolibrie_tpu.parallel.sharded_store import (
    ShardedTripleStore,
    _mix32,
    shard_of,
)
from kolibrie_tpu.query import ast as A
from kolibrie_tpu.reasoner.device_fixpoint import (
    LoweredFilter,
    LoweredPremise,
    Unsupported,
    _scan_premise,
)

__all__ = ["DistQueryExecutor", "execute_query_distributed", "Unsupported"]

# Unknown-constant sentinel: dictionary IDs occupy bits 0..30 (+ bit 31 for
# quoted triples) but never all-ones, so a scan against it matches nothing.
_NO_MATCH = 0xFFFFFFFF


# ---------------------------------------------------------------------------
# Lowering: SelectQuery -> premises + filters + projection
# ---------------------------------------------------------------------------


def _lower_query_pattern(resolved) -> LoweredPremise:
    """Resolved :class:`PatternTriple` (kinds 'var'/'id') → LoweredPremise."""
    consts: List[Optional[int]] = []
    out_vars: List[tuple] = []
    eq_pairs: List[tuple] = []
    seen: Dict[str, int] = {}
    for pos, t in enumerate((resolved.subject, resolved.predicate, resolved.object)):
        if t.kind == "id":
            consts.append(_NO_MATCH if t.value is None else int(t.value))
        elif t.kind == "var":
            consts.append(None)
            name = t.value
            if name in seen:
                eq_pairs.append((seen[name], pos))
            else:
                seen[name] = pos
                out_vars.append((name, pos))
        else:
            raise Unsupported(f"pattern term kind {t.kind!r}")
    return LoweredPremise(tuple(consts), tuple(out_vars), tuple(eq_pairs))


def _most_constants(premises) -> int:
    """The seed rule that needs no count: the premise with the most constant
    positions, ties to the lowest index."""
    return max(
        range(len(premises)),
        key=lambda i: (sum(c is not None for c in premises[i].consts), -i),
    )


def exchanged_steps(premises, seed, steps, n) -> Tuple[bool, ...]:
    """Per join step, whether ``sharded_serving._batched_body`` exchanges
    the binding table before it.  The seed scans the subject-partitioned
    mirror, so rows start partitioned by the seed's subject variable, and
    every step leaves them partitioned by its join key: a step keyed by
    the variable the rows are already partitioned by is co-located and its
    ``all_to_all`` would be an identity.  One definition for the program,
    the host count and the dispatch counters."""
    part = next((v for v, pos in premises[seed].vars if pos == 0), None)
    out = []
    for _j, kv, _kpos, _extra in steps:
        out.append(n > 1 and kv != part)
        part = kv
    return tuple(out)


def _largest(step_rows, buckets) -> Tuple[int, int]:
    """What the two capacities have to hold of a counted chain: the largest
    per-shard join step and the largest exchange bucket."""
    return (
        max((int(s.max()) for s in step_rows), default=0),
        max(buckets, default=0),
    )


# the column of a freed walk's table that holds each row's key (times the
# mesh size: the base of its (key, shard) pairs); no variable is named so
_FREED_KEY = "\x00key"


class _Uncounted(Exception):
    """A host chain walk given up: over the best plan so far, or past
    ``_CALIBRATE_ROW_LIMIT``."""


def _mirror(op: str) -> str:
    return {"<": ">", "<=": ">=", ">": "<", ">=": "<="}.get(op, op)


def _lower_query_filters(
    filters, db, bound: set, mask_offset: int = 0
) -> Tuple[Tuple[LoweredFilter, ...], Tuple[tuple, ...]]:
    """Query FILTER expressions → LoweredFilters + numeric mask exprs.

    Numeric comparisons (including ``=``/``!=`` — value semantics, matching
    the host engine's NumCmp) become per-ID mask gathers; term equality
    against IRIs/strings becomes an ID compare.  AND composes; anything
    else is Unsupported.  ``mask_offset``: starting index the returned
    mask exprs will occupy in the caller's combined mask bank (MINUS/NOT
    branch filters share the main query's bank).
    """
    lowered: List[LoweredFilter] = []
    exprs: List[tuple] = []
    keys: Dict[tuple, int] = {}

    def mask_key(k: tuple) -> int:
        if k not in keys:
            keys[k] = mask_offset + len(exprs)
            exprs.append(k)
        return keys[k]

    def mask_idx(op: str, const: float) -> int:
        return mask_key((op, const))

    def walk(f) -> None:
        if isinstance(f, A.LogicalAnd):
            walk(f.left)
            walk(f.right)
            return
        if isinstance(f, A.FunctionCall):
            # constant-pattern string predicates: per-ID verdict masks
            # (dict + quoted), the single-chip StrMaskRef scheme with the
            # quoted index riding const_id
            name = f.name.upper()
            args = f.args
            if (
                name in ("REGEX", "CONTAINS", "STRSTARTS", "STRENDS")
                and len(args) == 2
                and isinstance(args[0], A.Var)
                and args[0].name in bound
                and isinstance(args[1], A.StringLit)
            ):
                lex = args[1].value
                pattern = (
                    lex[1:].split('"')[0] if lex.startswith('"') else lex
                )
                didx = mask_key(("str", name, pattern, "dict"))
                qidx = mask_key(("str", name, pattern, "quoted"))
                lowered.append(
                    LoweredFilter(
                        "strmask", args[0].name, mask_idx=didx, const_id=qidx
                    )
                )
                return
            raise Unsupported(f"filter function {f.name}")
        if not isinstance(f, A.Comparison):
            raise Unsupported(f"filter {type(f).__name__}")
        left, op, right = f.left, f.op, f.right
        if isinstance(right, A.Var) and not isinstance(left, A.Var):
            left, right, op = right, left, _mirror(op)
        if not isinstance(left, A.Var) or left.name not in bound:
            raise Unsupported("filter variable unbound in patterns")
        var = left.name
        if isinstance(right, A.NumberLit):
            lowered.append(
                LoweredFilter("mask", var, mask_idx=mask_idx(op, float(right.value)))
            )
            return
        if isinstance(right, (A.IriRef, A.StringLit)) and op in ("=", "!="):
            term = (
                db.expand_term(right.iri)
                if isinstance(right, A.IriRef)
                else right.value
            )
            tid = db.dictionary.lookup(term)
            if tid is None:
                tid = _NO_MATCH  # '=' never matches; '!=' always passes
            kind = "eq" if op == "=" else "ne"
            lowered.append(LoweredFilter(kind, var, const_id=int(tid)))
            return
        raise Unsupported(f"filter comparison against {type(right).__name__}")

    for f in filters:
        walk(f)
    return tuple(lowered), tuple(exprs)


def _materialize_masks(db, exprs: Tuple[tuple, ...]) -> List[np.ndarray]:
    """Per-ID boolean masks — the SAME builders as the single-chip engine
    (numeric-literal comparisons and constant-pattern string predicates,
    one shared definition each)."""
    if not exprs:
        return []
    from kolibrie_tpu.optimizer.device_engine import (
        numeric_filter_mask,
        string_filter_mask,
    )

    vals = db.numeric_values()
    out = []
    for key in exprs:
        if key[0] == "str":
            out.append(string_filter_mask(db, key[1], key[2], key[3]))
        else:
            out.append(numeric_filter_mask(vals, key[0], key[1]))
    return out


def _strmask_verdict(col, masks, f):
    """Two-level string-predicate gather: dictionary IDs from masks[f.mask_idx],
    quoted IDs (bit 31) from masks[f.const_id] (single-chip StrMaskRef twin)."""
    from kolibrie_tpu.core.dictionary import QUOTED_BIT

    dm = masks[f.mask_idx]
    qm = masks[f.const_id]
    isq = (col & jnp.uint32(QUOTED_BIT)) != 0
    dv = dm[jnp.minimum(col, dm.shape[0] - 1)]
    qv = qm[jnp.minimum(col & jnp.uint32(~QUOTED_BIT & 0xFFFFFFFF), qm.shape[0] - 1)]
    return jnp.where(isq, qv, dv)


# ---------------------------------------------------------------------------
# The shard_map body (single round: scan -> routed joins -> filter -> project)
# ---------------------------------------------------------------------------


def _query_body(
    state,
    masks,
    numf,
    vals,
    dranks,
    qranks,
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
    distinct=False,
    topk=None,
    values_var=None,
    anti=(),
    unions=(),
    optionals=(),
):
    fs, fp, fo, fv, gs, gp, go, gv = (a[0] for a in state)
    masks = tuple(masks)
    fcols = (fs, fp, fo)
    overflow = jnp.int32(0)

    def eval_bgp(premises, seed, steps, filters):
        """Seed scan → routed join steps → filters: the shard-local BGP
        pipeline, shared by the main pattern and MINUS/NOT branches.
        Accumulates into the enclosing ``overflow`` via its return."""
        ov = jnp.int32(0)
        table, valid = _scan_premise(premises[seed], fcols, fv)
        for (j, kv, kpos, extra) in steps:
            prem = premises[j]
            if n > 1:
                table, valid, dropped = _exchange_table(
                    table, valid, kv, n, axis, bucket_cap
                )
                ov = ov + dropped.astype(jnp.int32)
            # n == 1 (single-chip mesh): every key hashes to shard 0 — the
            # exchange is an identity that would still pay a full
            # bucketize sort per join step; skip it
            if kpos == 0:
                side_cols, side_valid, side_key = fcols, fv, fs
            else:
                side_cols, side_valid, side_key = (gs, gp, go), gv, go
            ptable, pmask = _scan_premise(prem, side_cols, side_valid)
            li, ri, jvalid, total = local_join_u32(
                table[kv], side_key, join_cap, valid, pmask
            )
            ov = ov + lax.psum(
                jnp.maximum(total - join_cap, 0).astype(jnp.int32), axis
            )
            new_table = {v: c[li] for v, c in table.items()}
            for v, c in ptable.items():
                if v not in new_table:
                    new_table[v] = c[ri]
                elif v in extra:
                    jvalid = jvalid & (new_table[v] == c[ri])
            table, valid = new_table, jvalid
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
        return table, valid, ov

    table, valid, ov = eval_bgp(premises, seed, steps, filters)
    overflow = overflow + ov

    if values_var is not None:
        # replicated VALUES membership: sorted array + searchsorted per row
        col = table[values_var]
        vpos = jnp.clip(jnp.searchsorted(vals, col), 0, vals.shape[0] - 1)
        valid = valid & (vals[vpos] == col)

    # UNION / OPTIONAL / MINUS / NOT branches, in the host post-pass
    # order: each branch evaluates through the same shard-local BGP
    # pipeline, equal shared-key tuples co-locate by hash routing, then a
    # local join (union), left-outer join (optional) or membership test
    # (anti) applies — the mesh twins of the device engine's UnionSpec /
    # LeftOuterSpec / AntiJoinSpec.
    from kolibrie_tpu.parallel.dist_join import exchange as _exchange
    from kolibrie_tpu.parallel.dist_join import mix32

    def _dest(cols_k):
        h = cols_k[0]
        for c in cols_k[1:]:
            h = mix32(h) ^ c
        return (mix32(h) % jnp.uint32(n)).astype(jnp.int32)

    def _route_sides(table, valid, btable, bvalid, bkeys, bextra):
        """Co-locate main rows and branch rows by shared-key hash.
        ``bextra``: branch columns beyond the keys to carry through."""
        nonlocal overflow
        if n <= 1:
            return table, valid, btable, bvalid
        names = sorted(table)
        routed, valid, dropped = _exchange(
            tuple(table[v] for v in names),
            valid,
            _dest([table[v] for v in bkeys]),
            n,
            axis,
            bucket_cap,
        )
        overflow = overflow + dropped.astype(jnp.int32)
        table = dict(zip(names, routed))
        bnames = list(bkeys) + [v for v in bextra if v not in bkeys]
        brouted, bvalid, bdropped = _exchange(
            tuple(btable[v] for v in bnames),
            bvalid,
            _dest([btable[v] for v in bkeys]),
            n,
            axis,
            bucket_cap,
        )
        overflow = overflow + bdropped.astype(jnp.int32)
        return table, valid, dict(zip(bnames, brouted)), bvalid

    def _pack_pair(table, valid, btable, bvalid, bkeys):
        """Shared-key tuples → comparable u64 keys.  Equal tuples are
        co-located after routing, so a LOCAL rank pack over the
        concatenated columns is exact for any key arity."""
        lcols_k = [table[v] for v in bkeys]
        rcols_k = [btable[v] for v in bkeys]
        lk = lcols_k[0].astype(jnp.uint64)
        rk = rcols_k[0].astype(jnp.uint64)
        for lc, rc in zip(lcols_k[1:], rcols_k[1:]):
            union = jnp.sort(jnp.concatenate([lk, rk]))
            lr = jnp.searchsorted(union, lk).astype(jnp.uint64)
            rr = jnp.searchsorted(union, rk).astype(jnp.uint64)
            lk = (lr << jnp.uint64(32)) | lc.astype(jnp.uint64)
            rk = (rr << jnp.uint64(32)) | rc.astype(jnp.uint64)
        lk = jnp.where(valid, lk, jnp.uint64(0xFFFFFFFFFFFFFFFE))
        rk = jnp.where(bvalid, rk, jnp.uint64(0xFFFFFFFFFFFFFFFF))
        return lk, rk

    for (branches, gvars, gkeys) in unions:
        parts = []
        for (bprem, bseed, bsteps, bfilters) in branches:
            bt, bv, ov = eval_bgp(bprem, bseed, bsteps, bfilters)
            overflow = overflow + ov
            parts.append((bt, bv))
        ucols = {}
        for v in gvars:
            segs = [
                bt[v]
                if v in bt
                else jnp.zeros(bv.shape[0], dtype=jnp.uint32)
                for bt, bv in parts
            ]
            ucols[v] = jnp.concatenate(segs)
        uvalid = jnp.concatenate([bv for _bt, bv in parts])
        table, valid, ucols, uvalid = _route_sides(
            table, valid, ucols, uvalid, gkeys, gvars
        )
        lk, rk = _pack_pair(table, valid, ucols, uvalid, gkeys)
        from kolibrie_tpu.ops.device_join import join_indices as _dj

        li, ri, jvalid, total = _dj(lk, rk, join_cap)
        overflow = overflow + lax.psum(
            jnp.maximum(total - join_cap, 0).astype(jnp.int32), axis
        )
        new_table = {v: jnp.where(jvalid, c[li], 0) for v, c in table.items()}
        for v in gvars:
            if v not in new_table:
                new_table[v] = jnp.where(jvalid, ucols[v][ri], 0)
        table, valid = new_table, jvalid

    for (oprem, oseed, osteps, ofilters, ovars, okeys) in optionals:
        bt, bv, ov = eval_bgp(oprem, oseed, osteps, ofilters)
        overflow = overflow + ov
        table, valid, bt, bv = _route_sides(table, valid, bt, bv, okeys, ovars)
        lk, rk = _pack_pair(table, valid, bt, bv, okeys)
        from kolibrie_tpu.ops.device_join import join_indices as _dj

        li, ri, jvalid, total = _dj(lk, rk, join_cap)
        overflow = overflow + lax.psum(
            jnp.maximum(total - join_cap, 0).astype(jnp.int32), axis
        )
        rs = jnp.sort(rk)
        pos = jnp.clip(jnp.searchsorted(rs, lk), 0, rs.shape[0] - 1)
        keep = valid & (rs[pos] != lk)  # unmatched main rows
        new_table = {}
        for v, c in table.items():
            new_table[v] = jnp.concatenate([jnp.where(jvalid, c[li], 0), c])
        for v in ovars:
            if v not in table:
                new_table[v] = jnp.concatenate(
                    [
                        jnp.where(jvalid, bt[v][ri], 0),
                        jnp.zeros(valid.shape[0], dtype=jnp.uint32),
                    ]
                )
        table, valid = new_table, jnp.concatenate([jvalid, keep])

    for (bprem, bseed, bsteps, bfilters, bkeys) in anti:
        btable, bvalid, ov = eval_bgp(bprem, bseed, bsteps, bfilters)
        overflow = overflow + ov
        table, valid, btable, bvalid = _route_sides(
            table, valid, btable, bvalid, bkeys, ()
        )
        lk, rk = _pack_pair(table, valid, btable, bvalid, bkeys)
        rs = jnp.sort(rk)
        pos = jnp.clip(jnp.searchsorted(rs, lk), 0, rs.shape[0] - 1)
        valid = valid & (rs[pos] != lk)

    if distinct and out_vars:
        # mesh-side DISTINCT: equal projection tuples hash to the same
        # owner shard, so a shard-local sort + first-occurrence mask is a
        # GLOBALLY exact dedup (readback carries only distinct rows)
        from kolibrie_tpu.parallel.dist_join import mix32
        from kolibrie_tpu.parallel.dist_join import exchange as _exchange

        ocols = [table[v].astype(jnp.uint32) for v in out_vars]
        if n > 1:
            h = ocols[0]
            for c in ocols[1:]:
                h = mix32(h) ^ c
            dest = (mix32(h) % jnp.uint32(n)).astype(jnp.int32)
            routed, valid, dropped = _exchange(
                tuple(ocols), valid, dest, n, axis, bucket_cap
            )
            overflow = overflow + dropped.astype(jnp.int32)
            ocols = list(routed)
        sent = jnp.uint32(0xFFFFFFFF)  # never a real dictionary ID
        keyed = tuple(jnp.where(valid, c, sent) for c in ocols)
        scols = (
            lax.sort(keyed, num_keys=len(keyed))
            if len(keyed) > 1
            else (jnp.sort(keyed[0]),)
        )
        neq = jnp.zeros(scols[0].shape[0] - 1, dtype=bool)
        for c in scols:
            neq = neq | (c[1:] != c[:-1])
        first = jnp.concatenate([jnp.ones(1, dtype=bool), neq])
        valid = first & (scols[0] != sent)
        table = dict(zip(out_vars, scols))

    nan_seen = jnp.zeros((), dtype=bool)
    if topk is not None:
        # mesh-side ORDER BY + LIMIT: per-shard top-k through the device
        # engine's `_order_limit` (one definition of the lexsort
        # composition) — the union of per-shard top-k contains the global
        # top-k, so readback is O(k·n), and the host re-orders those k·n
        # rows for the final slice.  The numeric-vs-string decision per
        # key column must be GLOBAL (host rule: one non-numeric value
        # anywhere switches the whole column), so each key's flag is
        # psum'd before the sort.  Phase 1 runs with placeholder ranks;
        # a truthy flag makes the driver build the real ranks and re-run.
        from kolibrie_tpu.optimizer.device_engine import _order_limit

        k, opos, descs = topk
        cols_t = tuple(table[v] for v in out_vars)
        overrides = []
        for pos in opos:
            vals_k = numf[jnp.minimum(cols_t[pos], numf.shape[0] - 1)]
            overrides.append(
                lax.psum(
                    jnp.any(jnp.isnan(vals_k) & valid).astype(jnp.int32),
                    axis,
                )
                > 0
            )
        top_cols, valid, _n_valid, nan_seen = _order_limit(
            cols_t,
            valid,
            numf,
            opos,
            descs,
            k,
            dranks,
            qranks,
            tuple(overrides),
        )
        table = dict(zip(out_vars, top_cols))

    outs = tuple(jnp.where(valid, table[v], 0)[None] for v in out_vars)
    total_rows = lax.psum(jnp.sum(valid).astype(jnp.int32), axis)
    nan_any = lax.psum(nan_seen.astype(jnp.int32), axis)
    return outs, valid[None], total_rows[None], overflow[None], nan_any[None]


@lru_cache(maxsize=64)
def _query_fn(
    mesh,
    premises,
    seed,
    steps,
    filters,
    out_vars,
    n_masks,
    join_cap,
    bucket_cap,
    distinct=False,
    topk=None,
    values_var=None,
    anti=(),
    unions=(),
    optionals=(),
):
    axis = mesh.axis_names[0]
    n = mesh.devices.size
    body = partial(
        _query_body,
        premises=premises,
        seed=seed,
        steps=steps,
        filters=filters,
        out_vars=out_vars,
        n=n,
        axis=axis,
        join_cap=join_cap,
        bucket_cap=bucket_cap,
        distinct=distinct,
        topk=topk,
        values_var=values_var,
        anti=anti,
        unions=unions,
        optionals=optionals,
    )
    spec = P(axis, None)
    return jax.jit(
        jax.shard_map(
            lambda state, masks, numf, vals, dranks, qranks: body(
                state, masks, numf, vals, dranks, qranks
            ),
            mesh=mesh,
            check_vma=_dist_check_vma(),
            in_specs=((spec,) * 8, (P(),) * n_masks, P(), P(), P(), P()),
            out_specs=(
                (spec,) * len(out_vars),
                spec,
                P(axis),
                P(axis),
                P(axis),
            ),
        )
    )


# ---------------------------------------------------------------------------
# Host driver
# ---------------------------------------------------------------------------


class DistQueryExecutor:
    """Lower one SELECT for the mesh and execute it over sharded triples.

    ``store`` may be a prebuilt :class:`ShardedTripleStore` (reused across
    queries — the benchmark path); otherwise one is partitioned from the
    database's columns on first :meth:`run`.

    The plan — the seed premise, with the step order ``_plan_rule_dist``
    gives that seed, and the two capacities — comes from one host count
    of what the program will count (:meth:`_counted_plan`); a caller that
    holds a template's plan passes ``seed``, ``join_cap`` and
    ``bucket_cap`` and nothing is counted.  ``batched`` says which body
    the plan is for: ``sharded_serving._batched_body`` joins against the
    whole mirror block, :func:`_query_body` against the side premise's
    own scan.
    """

    def __init__(
        self,
        mesh: Mesh,
        db,
        sparql: str,
        store: Optional[ShardedTripleStore] = None,
        join_cap: Optional[int] = None,
        bucket_cap: Optional[int] = None,
        seed: Optional[int] = None,
        batched: bool = False,
    ):
        from kolibrie_tpu.optimizer.engine import resolve_pattern
        from kolibrie_tpu.query.parser import parse_combined_query

        self.mesh = mesh
        self.db = db
        self.n = mesh.devices.size
        db.register_prefixes_from_query(sparql)
        cq = parse_combined_query(sparql, db.prefixes)
        q = cq.select
        if q is None or cq.rules or cq.insert or cq.delete or cq.ml_predict:
            raise Unsupported("distributed path executes plain SELECT only")
        from kolibrie_tpu.query.subquery_inline import inline_subqueries

        # plain sub-SELECTs fold into the BGP (same rewrite the single-chip
        # paths apply), so nested selects distribute too
        w = inline_subqueries(q.where)
        if w.subqueries or w.window_blocks:
            raise Unsupported("non-BGP clause in WHERE")
        if not w.patterns:
            raise Unsupported("empty BGP")
        resolved = [resolve_pattern(db, p) for p in w.patterns]
        self.premises = tuple(_lower_query_pattern(p) for p in resolved)
        bound = {v for pr in self.premises for v, _ in pr.vars}

        # UNION groups / OPTIONAL branches: structural lowering NOW so the
        # clause variables join the projection/aggregation variable space;
        # branch filters lower later into the shared mask bank.  Join keys
        # accumulate left-to-right, matching the host post-pass order
        # (group N may key on group N-1's variables).
        def _branch_bgp(bw, kind):
            bw = inline_subqueries(bw)
            if (
                not bw.patterns
                or bw.binds
                or bw.values is not None
                or bw.subqueries
                or bw.not_blocks
                or bw.window_blocks
                or bw.optionals
                or bw.unions
                or bw.minus
            ):
                raise Unsupported(f"non-BGP {kind} branch stays single-chip")
            bres = [resolve_pattern(db, p) for p in bw.patterns]
            bprem = tuple(_lower_query_pattern(p) for p in bres)
            bbound = {v for pr in bprem for v, _ in pr.vars}
            return bprem, bbound, bw

        cur_vars = set(bound)
        union_pre = []
        for groups in w.unions:
            gpre = [_branch_bgp(bw_u, "UNION") for bw_u in groups]
            gvars: set = set()
            for _bp, bb, _bw in gpre:
                gvars |= bb
            keys = tuple(sorted(gvars & cur_vars))
            if not keys:
                raise Unsupported(
                    "UNION with no shared variables stays single-chip"
                )
            union_pre.append((gpre, tuple(sorted(gvars)), keys))
            cur_vars |= gvars
        opt_pre = []
        for ow in w.optionals:
            oprem, obound, ow_i = _branch_bgp(ow, "OPTIONAL")
            keys = tuple(sorted(obound & cur_vars))
            if not keys:
                raise Unsupported(
                    "OPTIONAL with no shared variables stays single-chip"
                )
            opt_pre.append((oprem, obound, ow_i, keys))
            cur_vars |= obound
        full_bound = cur_vars
        # VALUES in its constraining form — ONE variable that the BGP
        # binds, all cells bound and distinct — lowers to a replicated
        # membership mask inside the mesh program (a sorted array +
        # searchsorted per row).  General VALUES (multi-var, UNBOUND
        # wildcards, duplicate rows => bag multiplicity) stays single-chip.
        self.values_var: Optional[str] = None
        self.values_ids: Optional[np.ndarray] = None
        if w.values is not None:
            if len(w.values.variables) != 1:
                raise Unsupported("multi-variable VALUES stays single-chip")
            vvar = w.values.variables[0]
            if vvar not in bound:
                raise Unsupported("VALUES variable unbound in patterns")
            ids = []
            for row in w.values.rows:
                term = row[0] if row else None
                if term is None:
                    raise Unsupported("UNBOUND VALUES cell stays single-chip")
                ids.append(db.dictionary.encode(db.expand_term(term)))
            if len(set(ids)) != len(ids):
                # duplicate cells change bag multiplicity, not membership
                raise Unsupported("duplicate VALUES cells stay single-chip")
            self.values_var = vvar
            self.values_ids = np.sort(np.asarray(ids, dtype=np.uint32))
        # BINDs: the mesh program computes the BGP; binds (and any filter
        # that reads a bind output) apply HOST-side to the gathered table —
        # the single-chip device split (results are small next to the
        # store).  Bind inputs must be pattern variables (or earlier bind
        # outputs, applied in order).
        self.binds = list(w.binds)
        bind_vars = {b.var for b in self.binds}
        if self.binds and (
            q.group_by or any(i.kind == "agg" for i in q.select)
        ):
            raise Unsupported("BIND with aggregates stays single-chip")
        from kolibrie_tpu.query.executor import _filter_vars

        plan_filters = [
            f
            for f in w.filters
            if not (set(_filter_vars(f)) & bind_vars)
        ]
        self.post_bind_filters = [
            f for f in w.filters if set(_filter_vars(f)) & bind_vars
        ]
        # GROUP BY + aggregates (BASELINE config 2 distributed): the plan's
        # out columns stay mesh-resident and flow into the single-chip
        # segment aggregator (XLA all-gathers the post-join/post-filter
        # rows — the aggregation input, not the base data); host reads one
        # row per group.  GROUP_CONCAT / DISTINCT-on-non-COUNT mirror the
        # single-chip engine's fallback contract.
        self.agg_items = [i for i in q.select if i.kind == "agg"]
        if self.agg_items or q.group_by:
            for item in self.agg_items:
                a = item.agg
                if a.func not in ("COUNT", "SUM", "AVG", "MIN", "MAX", "SAMPLE"):
                    raise Unsupported(f"aggregate {a.func}")
                if a.distinct and a.func != "COUNT":
                    raise Unsupported("DISTINCT on non-COUNT aggregate")
                if a.var is not None and a.var not in full_bound:
                    raise Unsupported(f"aggregate variable unbound: {a.var}")
            if any(i.kind == "expr" for i in q.select):
                raise Unsupported("expressions in aggregate SELECT")
            missing = set(q.group_by) - full_bound
            if missing:
                raise Unsupported(f"group variables unbound: {missing}")
            # out columns = group vars + every aggregated var
            need = list(q.group_by) + [
                i.agg.var
                for i in self.agg_items
                if i.agg.var is not None
            ]
            self.out_vars = tuple(dict.fromkeys(need)) or tuple(sorted(full_bound))[:1]
        elif not q.select_all() and any(i.kind != "var" for i in q.select):
            raise Unsupported("expressions in SELECT")
        elif q.select_all():
            # internal variables (subquery-inline renames, "__sq*") are
            # never user-visible: keeping them here would make the
            # mesh-side DISTINCT dedup over hidden columns and disagree
            # with the host engine (which drops them before dedup)
            visible = [v for v in sorted(full_bound) if not v.startswith("__")]
            self.out_vars = tuple(visible) or tuple(sorted(full_bound))[:1]
        elif self.binds:
            # binds may reference any pattern variable: gather them ALL,
            # apply binds host-side, project afterwards (run())
            sel = tuple(item.var for item in q.select)
            missing = set(sel) - full_bound - bind_vars
            if missing:
                raise Unsupported(f"projected variables unbound: {missing}")
            self.out_vars = tuple(sorted(full_bound))
        else:
            self.out_vars = tuple(item.var for item in q.select)
            missing = set(self.out_vars) - full_bound
            if missing:
                raise Unsupported(f"projected variables unbound: {missing}")
        self.filters, self.mask_exprs = _lower_query_filters(
            plan_filters, db, bound
        )
        # Clause branches (UNION / OPTIONAL structurally lowered above,
        # MINUS / NOT here): each lowers to its own premise pipeline (same
        # machinery as the main BGP).  Branch filters share the main mask
        # bank via offsets.
        mask_exprs = list(self.mask_exprs)

        def _branch_pipeline(bprem, bfilter_src, bbound):
            bfilters, bexprs = _lower_query_filters(
                list(bfilter_src), db, bbound, mask_offset=len(mask_exprs)
            )
            mask_exprs.extend(bexprs)
            bplans = dict(_plan_rule_dist(bprem))
            bseed = _most_constants(bprem)
            return bprem, bseed, bplans[bseed], bfilters

        unions_l = []
        for gpre, gvars, keys in union_pre:
            branches = tuple(
                _branch_pipeline(bprem, bw_u.filters, bbound)
                for bprem, bbound, bw_u in gpre
            )
            unions_l.append((branches, gvars, keys))
        self.union_specs = tuple(unions_l)
        opts_l = []
        for oprem, obound, ow_i, keys in opt_pre:
            opts_l.append(
                _branch_pipeline(oprem, ow_i.filters, obound)
                + (tuple(sorted(obound)), keys)
            )
        self.optional_specs = tuple(opts_l)
        anti = []
        for bw in list(w.minus) + [
            A.WhereClause(patterns=nb.patterns) for nb in w.not_blocks
        ]:
            bprem, bbound, bw = _branch_bgp(bw, "MINUS/NOT")
            bkeys = tuple(sorted(bbound & full_bound))
            if not bkeys:
                continue  # disjoint domains: MINUS removes nothing
            anti.append(
                _branch_pipeline(bprem, bw.filters, bbound) + (bkeys,)
            )
        self.anti = tuple(anti)
        self.mask_exprs = tuple(mask_exprs)
        plans = dict(_plan_rule_dist(self.premises))
        self.query = q
        self.store = store
        self.batched = batched
        self.plan_source = "pinned"  # where the seed came from
        # the most rows of the main chain's answer on one shard, where this
        # lowering counted them (the mesh serving layer sizes what it
        # brings to the host from it)
        self.final_rows: Optional[int] = None
        self.final_is_ceiling = False  # counted over every key of the seed
        if seed is None or join_cap is None or bucket_cap is None:
            counted = self._counted_plan_cached(plans, seed)
            self.final_rows, self.final_is_ceiling = counted[4:6]
            if seed is None:
                seed, self.plan_source = counted[:2]
            if join_cap is None:
                join_cap = counted[2]
            if bucket_cap is None:
                bucket_cap = counted[3]
        self.seed = seed
        self.steps = plans[seed]
        self.join_cap = join_cap
        self.bucket_cap = bucket_cap

    # A host count bails to the store-size heuristic (and the seed to the
    # most-constants rule) past this many intermediate rows: materializing
    # bigger host joins just to plan and size the device buffers would cost
    # the host memory the static-capacity design exists to avoid.
    _CALIBRATE_ROW_LIMIT = 8_000_000

    def _counted_plan_cached(self, plans, pinned) -> tuple:
        """Per-database memo of :meth:`_counted_plan` keyed on (query
        shape, the body it sizes, mesh size), valid for ONE store version:
        one-shot ``execute_query_distributed`` calls of a repeated query
        must not pay the host chain pass every time.  A store mutation
        drops the whole memo (stale-version entries must not accumulate
        for the life of a long-running database)."""
        version = self.db.store.version
        cache = self.db.__dict__.get("_dist_plan_cache")
        if cache is None or cache["version"] != version:
            cache = {"version": version, "plans": {}}
            self.db.__dict__["_dist_plan_cache"] = cache
        key = (
            self.premises,
            self.anti,
            self.union_specs,
            self.optional_specs,
            bool(self.query.distinct),
            self.batched,
            pinned,
            self.n,
        )
        plan = cache["plans"].get(key)
        if plan is None:
            plan = self._counted_plan(plans, pinned)
            cache["plans"][key] = plan
        return plan

    def _counted_plan(self, plans, pinned) -> tuple:
        """``(seed, source, join_cap, bucket_cap, final rows, whether they
        are a ceiling)`` from ONE host count of what the mesh program will
        count; the final rows are the main chain's answer on its fullest
        shard, ``None`` where nothing could be counted.  The candidate seeds (the
        pinned one alone where a template has one) are walked in the
        order of their constant scans' sizes — range counts on the
        store's sorted orders — each through the step order
        ``_plan_rule_dist`` gives it, and the seed whose chain's largest
        per-shard join step is smallest is kept (``source`` "counted"); a
        walk is abandoned at the first step that exceeds the best so far,
        so a plan that would join millions of rows is never materialised.
        The two capacities follow the single-device rule
        (``caps.fit_join_caps``), each from its own count: the
        largest per-shard join step (for the batched body the seed's rows
        a shard among them: it compacts them into ``join_cap`` slots) and
        the largest (source, destination) exchange bucket, never above the
        store-size
        heuristic; for the batched body the counts are those of the seed
        premise's hottest key and take no headroom
        (:meth:`_hottest_key_counts`), and the overflow/retry protocol
        backstops what no count saw.  Where nothing can be counted
        (every walk past ``_CALIBRATE_ROW_LIMIT``) the most-constants
        seed and the heuristic stand (``source`` "constants")."""
        from kolibrie_tpu.optimizer.caps import fit_join_caps

        heuristic = round_cap(
            4 * max(1, -(-len(self.db.store) // self.n)), 256
        )
        if pinned is not None:
            candidates = [pinned]
        else:
            scan = {
                i: self.db.store.count(*pr.consts)
                for i, pr in enumerate(self.premises)
            }
            first = _most_constants(self.premises)
            candidates = sorted(plans, key=lambda i: (scan[i], i != first, i))
        best = None
        for i in candidates:
            try:
                steps, buckets, table, shard = self._count_chain(
                    self.premises,
                    i,
                    plans[i],
                    limit=None if best is None else best[0],
                )
            except _Uncounted:
                continue
            size = _largest(steps, buckets)
            if best is None or size < best[:2]:
                best = (*size, i, table, shard)
        if best is not None:
            step, bucket, seed, table, shard = best
            try:
                cstep, cbucket = self._count_clauses(table, shard)
            except _Uncounted:
                best = None
        if best is None:
            fallback = pinned if pinned is not None else _most_constants(
                self.premises
            )
            return fallback, "constants", heuristic, heuristic, None, False
        final = int(np.bincount(shard, minlength=1).max()) if len(shard) else 0
        counts = [max(step, cstep), max(bucket, cbucket)]
        # The batched body serves every instance of a template with the
        # capacities of its first sight, so they are counted for the
        # template's HOTTEST instance and not for the one that came first:
        # one more walk of the chosen chain with the seed premise's key
        # freed, each count the most any one key gives on any one shard.
        # No instance of the seed's key passes it, so it takes no headroom
        # (``fit_join_caps``' ceiling), and a template's capacities no
        # longer move with the seed of the data (Q8's ``join_cap`` read
        # 131,072 or 262,144, and a cycle 2.5 or 5.1 s, by which
        # university came first: PERF.md section 6, PR 50).  Another
        # parameter of the text than the seed's key keeps the overflow
        # protocol behind it, as a hot walk past the row limit keeps the
        # first instance's counts and their headroom.
        hot = self._hottest_key_counts(seed, plans[seed]) if self.batched else None
        if hot is not None:
            counts = [max(a, b) for a, b in zip(counts, hot[:2])]
            final = max(final, hot[2])
        join_cap, bucket_cap = fit_join_caps(
            [heuristic, heuristic], counts, [hot is not None] * 2
        )
        return seed, "counted", join_cap, bucket_cap, final, hot is not None

    def _hottest_key_counts(self, seed, steps) -> Optional[Tuple[int, int, int]]:
        """``(largest join step, largest bucket, most final rows)`` of the
        main chain over every key of the seed premise, each on its fullest
        shard: the chain walked once with that key freed
        (:meth:`_count_chain` with ``free``).  ``None`` where the seed
        premise is not a keyed scan (a predicate with a subject or an
        object), where clauses follow the chain, or where the walk would
        pass ``_CALIBRATE_ROW_LIMIT``."""
        consts = self.premises[seed].consts
        keyed = [pos for pos in (0, 2) if consts[pos] is not None]
        if (
            consts[1] is None
            or len(keyed) != 1
            or self.union_specs
            or self.optional_specs
            or self.anti
        ):
            return None
        try:
            step_rows, buckets, _table, per_key_shard = self._count_chain(
                self.premises, seed, steps, free=keyed[0]
            )
        except _Uncounted:
            return None
        final = int(np.bincount(per_key_shard, minlength=1).max()) if len(
            per_key_shard
        ) else 0
        return (*_largest(step_rows, buckets), final)

    def _count_chain(self, premises, seed, steps, limit=None, free=None):
        """Host twin of one premise chain as the mesh program runs it:
        ``(each join step's rows per shard, each exchange's largest
        bucket, final table, final rows' shards)`` — the same walk for
        the main BGP and every clause branch, told by ``self.batched``
        which body it sizes.  Rows start on the shard that owns their triple's subject
        (the seed scans the subject mirror) and move to the owner of each
        step's key.  The batched body compacts its seed scan into a table
        of ``join_cap`` rows, so for it the seed's rows per shard stand in
        front of the join steps'.  A join step's size is what the program's
        join counts
        before any mask, on the largest shard: for the solo
        ``_query_body`` the left rows' matches in the side premise's
        scan (its constants pre-mask the side), for the batched body
        their matches in the WHOLE mirror block that owns the key, by
        subject or by object (the side sort is hoisted out of the member
        loop, so the side premise's constants apply after the join).  A
        bucket's size is the largest (source, destination) pair of an
        exchange; the batched body elides the exchange of a step whose
        key the rows are already partitioned by.  Counted before anything
        is materialised: a step over ``limit`` on some shard or a join
        past ``_CALIBRATE_ROW_LIMIT`` raises :class:`_Uncounted`.

        With ``free`` (the position, subject 0 or object 2, of the seed
        premise's key) the seed scans every key of its predicate and each
        row carries its key along: "a shard" then reads "a shard of one
        key" throughout, so each count is the most ANY instance of that
        key gives, and the last value returned numbers the final rows'
        (key, shard) pairs."""
        st = self.db.store
        n = self.n

        def table_of(prem):
            scan = st.match(*prem.consts)
            m = np.ones(len(scan[0]), dtype=bool)
            for a, b in prem.eq_pairs:
                m &= scan[a] == scan[b]
            return {v: scan[pos][m] for v, pos in prem.vars}, scan[0][m]

        if free is None:
            table, subj = table_of(premises[seed])
            shard = shard_of(subj, n)
        else:
            prem = premises[seed]
            scan = st.match(
                *(None if pos == free else c for pos, c in enumerate(prem.consts))
            )
            m = np.ones(len(scan[0]), dtype=bool)
            for a, b in prem.eq_pairs:
                m &= scan[a] == scan[b]
            table = {v: scan[pos][m] for v, pos in prem.vars}
            _keys, key_of_row = np.unique(scan[free][m], return_inverse=True)
            # the key rides with the rows through every join; a row's
            # "shard" is its place among the (key, shard) pairs
            table[_FREED_KEY] = key_of_row.astype(np.int64) * n
            shard = shard_of(scan[0][m], n)
        exchanged = (
            exchanged_steps(premises, seed, steps, n)
            if self.batched
            else (n > 1,) * len(steps)
        )
        step_rows, buckets = [], []

        def place():
            """Each row's shard, or with ``free`` its (key, shard) pair."""
            return shard if free is None else table[_FREED_KEY] + shard

        if self.batched:
            seed_rows = np.bincount(place(), minlength=n).astype(np.int64)
            if limit is not None and seed_rows.max() > limit:
                raise _Uncounted
            step_rows.append(seed_rows)
        for (j, kv, kpos, extra), routed in zip(steps, exchanged):
            ptab, _ = table_of(premises[j])
            lk, rk = table[kv], ptab[kv]
            if routed:
                dest = shard_of(lk, n)
                buckets.append(
                    int(np.bincount(place() * n + dest, minlength=1).max())
                )
                shard = dest
            order = np.argsort(rk, kind="stable")
            rs = rk[order]
            lo = np.searchsorted(rs, lk, side="left")
            counts = np.searchsorted(rs, lk, side="right") - lo
            if self.batched:
                block = st.order("spo" if kpos == 0 else "osp").c0
                matched = np.searchsorted(
                    block, lk, side="right"
                ) - np.searchsorted(block, lk, side="left")
            else:
                matched = counts
            per_shard = np.bincount(
                place(), weights=matched, minlength=n
            ).astype(np.int64)
            total = int(counts.sum())
            if (
                limit is not None and per_shard.max() > limit
            ) or total > self._CALIBRATE_ROW_LIMIT:
                raise _Uncounted
            step_rows.append(per_shard)
            # expand (li, ri) straight from the bounds already in hand
            li = np.repeat(np.arange(len(lk)), counts)
            offs = np.concatenate(([0], np.cumsum(counts[:-1]))) if len(
                counts
            ) else np.zeros(0, dtype=np.int64)
            pos = np.arange(total) - np.repeat(offs, counts) + np.repeat(
                lo, counts
            )
            ri = order[pos]
            new_table = {v: c[li] for v, c in table.items()}
            keep = np.ones(total, dtype=bool)
            for v, c in ptab.items():
                if v not in new_table:
                    new_table[v] = c[ri]
                elif v in extra:
                    keep &= new_table[v] == c[ri]
            table = {v: c[keep] for v, c in new_table.items()}
            shard = shard[li][keep]
        return step_rows, buckets, table, place()

    def _count_clauses(self, table, shard) -> Tuple[int, int]:
        """The clause stages' share of the two counts, from the main
        chain's final ``table``: they run through the SAME static
        buffers, so their chain intermediates, their clause-join totals
        and the grown post-OPTIONAL tables all have to fit, or the first
        dispatch overflows and pays recompiles at doubled caps.  A clause
        join and its routes are counted as a shard's even share of the
        rows (they hash on shared-key tuples); the DISTINCT exchange by
        its largest (source, destination) pair."""
        n = self.n
        max_step = max_bucket = 0

        def rows(t):
            return len(next(iter(t.values()))) if t else 0

        def share(total):
            return -(-total // n)

        def count_and_join(table, btable, keys):
            """Clause join on the mesh program's shared-key route:
            (pre-mask join total, joined table restricted to the host
            emulation's needs) — sizes the ``join_cap`` the ``_dj`` of
            this clause must hold."""
            from kolibrie_tpu.ops.join import _pack_shared_keys, join_indices

            ln, rn = rows(table), rows(btable)
            if ln == 0 or rn == 0:
                return 0, {
                    v: np.empty(0, dtype=np.uint32)
                    for v in set(table) | set(btable)
                }
            lk, rk = _pack_shared_keys(table, btable, list(keys), ln)
            li, ri = join_indices(lk, rk)
            total = len(li)
            if total > self._CALIBRATE_ROW_LIMIT:
                raise _Uncounted
            out = {v: c[li] for v, c in table.items()}
            for v, c in btable.items():
                if v not in out:
                    out[v] = c[ri]
            return total, out

        def branch(bprem, bseed, bsteps):
            nonlocal max_step, max_bucket
            bsteps_rows, bbuckets, btab, _ = self._count_chain(
                bprem, bseed, bsteps
            )
            bstep, bbucket = _largest(bsteps_rows, bbuckets)
            max_step = max(max_step, bstep)
            max_bucket = max(max_bucket, bbucket)
            return btab

        def routed(*tables):
            nonlocal max_bucket
            if n > 1:
                max_bucket = max(
                    [max_bucket] + [share(rows(t)) for t in tables]
                )

        for branches, gvars, gkeys in self.union_specs:
            parts = [
                branch(bprem, bseed, bsteps)
                for bprem, bseed, bsteps, _bf in branches
            ]
            ucols = {
                v: np.concatenate(
                    [
                        t[v] if v in t else np.zeros(rows(t), dtype=np.uint32)
                        for t in parts
                    ]
                )
                for v in gvars
            }
            routed(table, ucols)
            total, table = count_and_join(table, ucols, gkeys)
            max_step = max(max_step, share(total))
        for oprem, oseed, osteps, _of, ovars, okeys in self.optional_specs:
            btab = branch(oprem, oseed, osteps)
            routed(table, btab)
            total, joined = count_and_join(table, btab, okeys)
            # OPTIONAL output = matches + every left row (mesh concat)
            n_l = rows(table)
            if total + n_l > self._CALIBRATE_ROW_LIMIT:
                raise _Uncounted
            max_step = max(max_step, share(total + n_l))
            table = {
                v: np.concatenate(
                    [
                        joined.get(v, np.zeros(total, dtype=np.uint32)),
                        table.get(v, np.zeros(n_l, dtype=np.uint32)),
                    ]
                )
                for v in set(table) | set(joined)
            }
        for bprem, bseed, bsteps, _bf, _bkeys in self.anti:
            routed(table, branch(bprem, bseed, bsteps))  # anti only shrinks
        if self.query.distinct and n > 1 and rows(table):
            if self.union_specs or self.optional_specs:
                routed(table)  # a clause re-routed the rows
            else:
                h = table[self.out_vars[0]]
                for v in self.out_vars[1:]:
                    h = _mix32(h) ^ table[v]
                max_bucket = max(
                    max_bucket,
                    int(np.bincount(shard * n + shard_of(h, n)).max()),
                )
        return max_step, max_bucket

    def _ensure_store(self) -> ShardedTripleStore:
        if self.store is None:
            s, p, o = self.db.store.columns()
            self.store = ShardedTripleStore.from_columns(self.mesh, s, p, o)
        return self.store

    def run_device(
        self, max_attempts: int = 8, distinct=False, topk=None, with_ranks=False
    ):
        """Dispatch the compiled program; returns the UN-read device arrays
        ``(out_cols, valid, total, nan_flag)`` at the first capacity that
        does not overflow (benchmarks time this, then read back).
        ``distinct``/``topk`` enable the mesh-side DISTINCT and per-shard
        ORDER BY+LIMIT stages (see :func:`_query_body`)."""
        from kolibrie_tpu.optimizer.device_engine import device_numf

        store = self._ensure_store()
        state = (
            *store.by_subj,
            store.by_subj_valid,
            *store.by_obj,
            store.by_obj_valid,
        )
        masks = tuple(jnp.asarray(m) for m in _materialize_masks(self.db, self.mask_exprs))
        numf = (
            device_numf(self.db)
            if topk is not None
            else np.zeros(1, dtype=np.float64)
        )
        if topk is not None and with_ranks:
            from kolibrie_tpu.optimizer.device_engine import (
                device_string_ranks,
            )

            dranks, qranks = device_string_ranks(self.db)
        else:
            # phase-1 placeholders: unused unless a psum'd per-key flag
            # fires, in which case the driver re-runs with real ranks
            dranks = np.zeros(1, dtype=np.float64)
            qranks = np.zeros(1, dtype=np.float64)
        vals = (
            self.values_ids
            if self.values_var is not None
            else np.zeros(1, dtype=np.uint32)
        )
        for _attempt in range(max_attempts):
            fn = _query_fn(
                self.mesh,
                self.premises,
                self.seed,
                self.steps,
                self.filters,
                self.out_vars,
                len(masks),
                self.join_cap,
                self.bucket_cap,
                distinct,
                topk,
                self.values_var,
                self.anti,
                self.union_specs,
                self.optional_specs,
            )
            with jax.enable_x64(True):
                outs, valid, total, overflow, nan_flag = fn(
                    state, masks, numf, vals, dranks, qranks
                )
            if int(overflow[0]) == 0:
                return outs, valid, total, nan_flag
            self.join_cap *= 2
            self.bucket_cap *= 2
        raise RuntimeError("distributed query capacities failed to converge")

    def _run_aggregated(self) -> List[List[str]]:
        """GROUP BY/aggregate tail: the mesh-resident result columns flow
        into the single-chip device segment aggregator (same program the
        engine uses — one definition of aggregate semantics); readback is
        one row per group."""
        from kolibrie_tpu.optimizer.device_engine import (
            aggregate_stage,
            aggregate_table,
        )
        from kolibrie_tpu.query.executor import (
            _apply_limit_offset,
            _order_table,
            format_results,
        )

        q = self.query
        outs, valid, _total, _nan = self.run_device()
        flat_cols = tuple(jnp.reshape(c, (-1,)) for c in outs)
        flat_valid = jnp.reshape(valid, (-1,))
        # the shapes __init__ let through are the ones the stage takes; the
        # group capacity is kept a shape of query (its output columns and
        # aggregates), as the single-chip engine keeps it a template
        stage = aggregate_stage(self.out_vars, q)
        table, _rows, _cap = aggregate_table(
            self.db, flat_cols, flat_valid, stage, ("dist", self.out_vars)
        )
        table = _order_table(self.db, table, q.order_by)
        rows = format_results(self.db, table, q, sort_rows=not q.order_by)
        return _apply_limit_offset(rows, q)

    def _run_with_binds(self) -> List[List[str]]:
        """BIND tail: the mesh program gathers ALL pattern variables, then
        binds, post-bind filters, DISTINCT, ordering and the final
        projection run host-side on the (small) result table — the same
        split the single-chip device path uses.  Mesh DISTINCT/top-k
        stages are disabled here: they would act on pre-bind tuples."""
        from kolibrie_tpu.optimizer.engine import ExecutionEngine
        from kolibrie_tpu.ops.unique import unique_table
        from kolibrie_tpu.query.executor import (
            _apply_limit_offset,
            _order_table,
            format_results,
        )

        q = self.query
        outs, valid, _total, _nan = self.run_device()
        v = np.asarray(valid).reshape(-1)
        table = {
            var: np.asarray(col).reshape(-1)[v].astype(np.uint32)
            for var, col in zip(self.out_vars, outs)
        }
        engine = ExecutionEngine(self.db)
        for b in self.binds:
            col = engine.eval_arith_to_ids(b.expr, table)
            table = dict(table)
            table[b.var] = col
        for f in self.post_bind_filters:
            mask = engine.eval_filter(f, table)
            table = {k: c[mask] for k, c in table.items()}
        if not q.select_all():
            sel = [item.var for item in q.select]
            table = {k: table[k] for k in sel if k in table}
        if q.distinct and table:
            table = unique_table(table)
        table = _order_table(self.db, table, q.order_by)
        rows = format_results(self.db, table, q, sort_rows=not q.order_by)
        return _apply_limit_offset(rows, q)

    def run(self) -> List[List[str]]:
        """Execute and return decoded rows identical to the host volcano
        executor (same formatting, ordering, DISTINCT, LIMIT post-passes)."""
        from kolibrie_tpu.query.executor import (
            _apply_limit_offset,
            _order_table,
            format_results,
        )

        if self.agg_items or self.query.group_by:
            return self._run_aggregated()
        q = self.query
        if self.binds:
            return self._run_with_binds()
        # mesh-side ORDER BY + LIMIT: per-shard numeric top-k when every
        # sort key is a projected variable (host re-orders the k·n rows)
        topk = None
        if q.limit is not None and q.order_by:
            opos, descs = [], []
            for cond in q.order_by:
                if (
                    isinstance(cond.expr, A.Var)
                    and cond.expr.name in self.out_vars
                ):
                    opos.append(self.out_vars.index(cond.expr.name))
                    descs.append(bool(cond.descending))
                else:
                    opos = None
                    break
            if opos is not None:
                k = round_cap((q.offset or 0) + q.limit, 8)
                topk = (k, tuple(opos), tuple(descs))
        outs, valid, _total, nan_flag = self.run_device(
            distinct=bool(q.distinct), topk=topk
        )
        if topk is not None and int(nan_flag[0]) > 0:
            # a non-numeric sort key somewhere on the mesh: build the
            # global string ranks and re-run the SAME top-k with them
            outs, valid, _total, _nan = self.run_device(
                distinct=bool(q.distinct), topk=topk, with_ranks=True
            )
        v = np.asarray(valid).reshape(-1)
        table = {
            var: np.asarray(col).reshape(-1)[v].astype(np.uint32)
            for var, col in zip(self.out_vars, outs)
        }
        # DISTINCT already happened on the mesh (owner-shard dedup)
        table = _order_table(self.db, table, self.query.order_by)
        rows = format_results(
            self.db, table, self.query, sort_rows=not self.query.order_by
        )
        return _apply_limit_offset(rows, self.query)


def execute_query_distributed(sparql: str, db, mesh: Mesh, **caps) -> List[List[str]]:
    """One-shot distributed SELECT (see :class:`DistQueryExecutor`)."""
    return DistQueryExecutor(mesh, db, sparql, **caps).run()
