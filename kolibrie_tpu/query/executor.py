"""Query driver: parse → (updates | plan → execute) → post-process → format.

Parity: ``kolibrie/src/execute_query.rs`` — the Volcano path
``execute_query_rayon_parallel2_volcano`` (:356): TRAIN decls, DELETE (re-issue
SELECT + substitute + delete), INSERT, logical plan build, memoized
``Streamertail::find_best_plan``, execution, then the post-pass (subqueries,
GROUP BY/aggregate, ORDER BY, LIMIT, formatting :607-650).  The legacy
sequential join path ``execute_query`` (:156) is kept as the naive reference
implementation for agreement testing (the reference's own most valuable test
pattern, SURVEY §4).
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from kolibrie_tpu.core.dictionary import QUOTED_BIT, display_form
from kolibrie_tpu.core.triple import Triple
from kolibrie_tpu.optimizer.engine import UNBOUND, ExecutionEngine, resolve_pattern
from kolibrie_tpu.optimizer.planner import Streamertail, build_logical_plan
from kolibrie_tpu.ops.join import (
    BindingTable,
    anti_join_tables,
    concat_tables,
    equi_join_tables,
    left_outer_join_tables,
    table_len,
)
from kolibrie_tpu.ops.unique import unique_rows, unique_table
from kolibrie_tpu.query.ast import (
    Aggregate,
    CombinedQuery,
    DeleteClause,
    InsertClause,
    OrderCondition,
    PatternTerm,
    PatternTriple,
    SelectItem,
    SelectQuery,
    SubQuery,
    Var,
    WhereClause,
)
from kolibrie_tpu.obs import metrics as obs_metrics
from kolibrie_tpu.obs.spans import set_baggage, span
from kolibrie_tpu.optimizer.stats_advisor import (
    set_current_fp as _sa_set_current_fp,
)
from kolibrie_tpu.query.parser import parse_combined_query
from kolibrie_tpu.query.template import note_aggregate_tier
from kolibrie_tpu.resilience.breaker import breaker_board
from kolibrie_tpu.resilience.deadline import check_deadline
from kolibrie_tpu.resilience.errors import DeadlineExceeded, is_device_fault

Rows = List[List[str]]

_PARSE_LAT = obs_metrics.histogram(
    "kolibrie_query_parse_seconds", "SPARQL parse + template fingerprint time"
)
_PLAN_LAT = obs_metrics.histogram(
    "kolibrie_query_plan_seconds",
    "Streamertail planning time (plan-cache misses only)",
)
_QUERY_LAT = obs_metrics.histogram(
    "kolibrie_query_seconds",
    "end-to-end executor time by path (device/host/degraded)",
    labels=("path",),
)
_PLAN_CACHE_EVENTS = obs_metrics.counter(
    "kolibrie_plan_cache_events_total",
    "plan cache events (hit/miss/param_rebind/eviction)",
    labels=("event",),
)
_BATCHED_QUERIES = obs_metrics.counter(
    "kolibrie_query_batched_total",
    "queries served in a template group (one chip's batch or the mesh)",
)
# fixed-label children hoisted out of the per-query hot path
_QUERY_LAT_DEVICE = _QUERY_LAT.labels("device")
_QUERY_LAT_HOST = _QUERY_LAT.labels("host")
_QUERY_LAT_DEGRADED = _QUERY_LAT.labels("degraded")
_PLAN_CACHE_HIT = _PLAN_CACHE_EVENTS.labels("hit")
_PLAN_CACHE_MISS = _PLAN_CACHE_EVENTS.labels("miss")
_PLAN_CACHE_REBIND = _PLAN_CACHE_EVENTS.labels("param_rebind")
_PLAN_CACHE_EVICTION = _PLAN_CACHE_EVENTS.labels("eviction")

# "auto" execution mode switches to the device engine at this store size;
# db.execution_mode = "device" / "host" forces either path.
_DEVICE_AUTO_MIN = 100_000


# --------------------------------------------------------------------------
# WHERE evaluation (shared by volcano executor, rules, RSP, ML input queries)
# --------------------------------------------------------------------------


def _interp_mode() -> str:
    """Current ``KOLIBRIE_PLAN_INTERP`` routing mode (lazy import: the
    interpreter module pulls in the device engine)."""
    from kolibrie_tpu.optimizer.plan_interp import plan_interp_mode

    return plan_interp_mode()


def _device_routed(db) -> bool:
    """THE routing rule for "does this query run on the device engine":
    explicit ``execution_mode == "device"``, or auto mode over a store big
    enough that device dispatch beats the host numpy engine."""
    mode = getattr(db, "execution_mode", "auto")
    return mode == "device" or (
        mode == "auto" and len(db.store) >= _DEVICE_AUTO_MIN
    )


def eval_where(
    db,
    where: WhereClause,
    use_optimizer: bool = True,
    prebuilt_plan=None,
    prebuilt_lowered=None,
    capture=None,
) -> BindingTable:
    """Evaluate a group graph pattern to a binding table (IDs).

    ``prebuilt_plan``: physical plan already produced for this WHERE (the
    device-aggregation attempt plans first; on fallback the plan is reused
    here instead of running the optimizer twice).  ``prebuilt_lowered``:
    the matching device-lowered plan — an object to execute directly,
    ``False`` if lowering already failed (skip the device path), None if
    no lowering was attempted yet.  ``capture``: plan-cache entry dict —
    the plan and the lowered program (or ``False`` for a failed lowering)
    are recorded into it for reuse by the next identical query."""
    from kolibrie_tpu.query.subquery_inline import inline_subqueries

    # Fold plain sub-SELECTs into the group before planning: one plan (and
    # on TPU one device program) instead of materialize-then-join-on-host.
    # Non-inlinable subqueries stay in where.subqueries for the post-pass.
    where = inline_subqueries(where)
    engine = ExecutionEngine(db, subquery_eval=lambda sq: eval_select_to_table(db, sq.query))
    resolved = [resolve_pattern(db, p) for p in where.patterns]
    # filters referencing BIND outputs can only run after the binds
    bind_vars = {b.var for b in where.binds}
    plan_filters = [
        f for f in where.filters if not (set(_filter_vars(f)) & bind_vars)
    ]
    post_bind_filters = [
        f for f in where.filters if set(_filter_vars(f)) & bind_vars
    ]
    fused_clauses = False
    if use_optimizer:
        planner = Streamertail(db.get_or_build_stats())
        if prebuilt_plan is not None:
            plan = prebuilt_plan
        else:
            logical = build_logical_plan(resolved, plan_filters, [], where.values)
            with span("query.plan"):
                t0 = time.perf_counter()
                plan = planner.find_best_plan(logical)
                _PLAN_LAT.observe(time.perf_counter() - t0)
        if capture is not None:
            capture["plan"] = plan
        table = None
        if prebuilt_lowered is not None and prebuilt_lowered is not False:
            table = prebuilt_lowered.execute()
            fused_clauses = getattr(prebuilt_lowered, "fused_clauses", False)
        elif prebuilt_lowered is None and _device_routed(db):
            from kolibrie_tpu.optimizer.device_engine import try_device_execute

            # UNION / OPTIONAL / MINUS / NOT clauses fuse into the device
            # program (union concat, left-outer join, anti-join) in the
            # same order the host post-passes apply them.  All-or-nothing:
            # a single non-BGP branch keeps everything on the post-pass
            # path so clause ordering semantics never split across engines.
            union_groups: List[tuple] = []
            optional_plans: List[object] = []
            anti_plans: List[object] = []
            fusable = not where.subqueries and (
                where.minus
                or where.not_blocks
                or where.unions
                or where.optionals
            )
            if fusable:
                for groups in where.unions:
                    g = [_branch_plan(db, planner, bw) for bw in groups]
                    if any(bp is None for bp in g):
                        fusable = False
                        break
                    union_groups.append(tuple(g))
                for ow in where.optionals if fusable else ():
                    bp = _branch_plan(db, planner, ow)
                    if bp is None:
                        fusable = False
                        break
                    optional_plans.append(bp)
                branches = list(where.minus) + [
                    WhereClause(patterns=nb.patterns)
                    for nb in where.not_blocks
                ]
                for bw in branches if fusable else ():
                    bp = _branch_plan(db, planner, bw)
                    if bp is None:
                        fusable = False
                        break
                    anti_plans.append(bp)
            if fusable:
                main_plan = plan
                if not where.patterns and where.values is None:
                    # clause-only group: the first union/optional stands
                    # alone (plan=None).  Filters attached to an empty
                    # plan never see clause columns on the host path, so
                    # only a filter-free group keeps exact parity.
                    if where.filters or not (union_groups or optional_plans):
                        main_plan = False  # shape host handles better
                    else:
                        main_plan = None
                if main_plan is not False:
                    table = try_device_execute(
                        db,
                        main_plan,
                        tuple(anti_plans),
                        tuple(union_groups),
                        tuple(optional_plans),
                        capture=capture,
                    )
                    fused_clauses = table is not None
            if table is None:
                table = try_device_execute(db, plan, capture=capture)
        if table is None and not _device_routed(db):
            # host-routed stores (RSP window stores live far below the
            # device-routing floor) reach the MQO layer here: the shared
            # prefix evaluates through the numpy twin and only the filter
            # suffix runs per query (optimizer/mqo.py, docs/MQO.md)
            from kolibrie_tpu.optimizer import mqo as _mqo

            table = _mqo.try_shared_host(db, plan)
        if table is None:
            from kolibrie_tpu.obs import analyze as _obs_analyze

            cap_rec = _obs_analyze.active()
            if cap_rec is not None:
                # EXPLAIN ANALYZE honesty: say WHICH engine ran when the
                # query never reached a device program
                cap_rec.record(
                    "host",
                    reason=(
                        "device lowering unavailable"
                        if _device_routed(db)
                        else "host-routed store"
                    ),
                )
            table = engine.execute_with_ids(plan)
    else:
        table = _naive_eval(engine, resolved, where, plan_filters)
    # subqueries join in
    for sq in where.subqueries:
        sub = eval_select_to_table(db, sq.query)
        table = equi_join_tables(table, sub)
    # UNION groups
    for groups in () if fused_clauses else where.unions:
        parts = [eval_where(db, g, use_optimizer) for g in groups]
        keys = set()
        for t in parts:
            keys |= set(t)
        norm = []
        for t in parts:
            nt = dict(t)
            n = table_len(t)
            for k in keys:
                if k not in nt:
                    nt[k] = np.full(n, UNBOUND, dtype=np.uint32)
            norm.append(nt)
        union_table = concat_tables(norm) if norm else {}
        table = equi_join_tables(table, union_table) if table_len(table) or where.patterns else union_table
    # OPTIONAL — over the unit table (no preceding clauses produced columns)
    # join(unit, optional) keeps the optional's solutions
    for opt in () if fused_clauses else where.optionals:
        opt_table = eval_where(db, opt, use_optimizer)
        if (
            not table
            and not where.patterns
            and where.values is None
            and not where.subqueries
            and not where.unions
        ):
            table = opt_table
        else:
            table = left_outer_join_tables(table, opt_table)
    # MINUS
    if not fused_clauses:
        for m in where.minus:
            table = anti_join_tables(table, eval_where(db, m, use_optimizer))
        # NOT blocks (NAF)
        for nb in where.not_blocks:
            neg_where = WhereClause(patterns=nb.patterns)
            table = anti_join_tables(
                table, eval_where(db, neg_where, use_optimizer)
            )
    # BINDs after joins (may reference any bound variable)
    for b in where.binds:
        col = engine.eval_arith_to_ids(b.expr, table)
        table = dict(table)
        table[b.var] = col
    # filters that reference BIND outputs run now
    for f in post_bind_filters:
        mask = engine.eval_filter(f, table)
        table = {k: v[mask] for k, v in table.items()}
    return table


def _branch_plan(db, planner, bw: WhereClause):
    """Physical plan for a clause branch (UNION / OPTIONAL / MINUS / NOT
    block) eligible to fuse into the device program; ``None`` when the
    branch needs the host post-pass (non-BGP content)."""
    from kolibrie_tpu.query.subquery_inline import inline_subqueries

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
        return None
    bres = [resolve_pattern(db, p) for p in bw.patterns]
    blogical = build_logical_plan(bres, list(bw.filters), [], None)
    return planner.find_best_plan(blogical)


def _filter_vars(expr) -> List[str]:
    from kolibrie_tpu.query import ast as A

    out: List[str] = []

    def walk(e):
        if isinstance(e, A.Var):
            out.append(e.name)
        elif isinstance(e, A.Comparison):
            walk(e.left)
            walk(e.right)
        elif isinstance(e, (A.LogicalAnd, A.LogicalOr)):
            walk(e.left)
            walk(e.right)
        elif isinstance(e, A.LogicalNot):
            walk(e.inner)
        elif isinstance(e, (A.FunctionCall, A.FuncExpr)):
            for a in e.args:
                walk(a)
        elif isinstance(e, A.ArithOp):
            walk(e.left)
            walk(e.right)

    walk(expr)
    return out


def _naive_eval(
    engine: ExecutionEngine, patterns, where: WhereClause, filters
) -> BindingTable:
    """Legacy sequential join path (execute_query.rs:156): patterns joined in
    textual order, filters applied at the end."""
    table: Optional[BindingTable] = None
    for pat in patterns:
        t = engine._scan(pat)
        table = t if table is None else equi_join_tables(table, t)
    if table is None:
        table = {}
        if where.values is not None:
            table = engine._values_table(where.values)
    elif where.values is not None:
        table = equi_join_tables(table, engine._values_table(where.values))
    for f in filters:
        mask = engine.eval_filter(f, table)
        table = {k: v[mask] for k, v in table.items()}
    return table


# --------------------------------------------------------------------------
# SELECT execution
# --------------------------------------------------------------------------


def eval_select_to_table(
    db, q: SelectQuery, use_optimizer: bool = True, cache_entry=None
) -> BindingTable:
    """Run a SELECT down to a binding table projected to its variables
    (aggregates resolved).  Used for subqueries and ML input queries.

    ``cache_entry``: automatic plan-cache slot (see ``_plan_cache_entry``)
    — a populated entry's plan/lowered program short-circuit the planner
    and device lowering; a fresh one captures them for the next call."""
    prebuilt_plan = None
    prebuilt_lowered = None
    if q.group_by or any(i.kind == "agg" for i in q.select):
        table, prebuilt_plan, prebuilt_lowered = _try_device_aggregate(
            db, q, use_optimizer, cache_entry=cache_entry
        )
        if table is not None:
            note_aggregate_tier("device")
            if q.distinct:
                table = unique_table(table)
            return table
        cache_entry = None  # aggregate fallback: prebuilts already in hand
    if cache_entry is not None:
        if cache_entry["plan"] is not None:
            prebuilt_plan = cache_entry["plan"]
        if cache_entry["lowered"] is not None:
            prebuilt_lowered = cache_entry["lowered"]
    table = eval_where(
        db,
        q.where,
        use_optimizer,
        prebuilt_plan=prebuilt_plan,
        prebuilt_lowered=prebuilt_lowered,
        capture=cache_entry,
    )
    if q.group_by or any(i.kind == "agg" for i in q.select):
        # the plan's rows came to the host (from the device where the store
        # is served there: query.execute's path says so, not this counter)
        note_aggregate_tier("host")
        table = _group_and_aggregate_table(db, table, q)
    else:
        if not q.select_all():
            keep = [i.var for i in q.select if i.kind == "var" and i.var in table]
            engine = ExecutionEngine(db)
            out: BindingTable = {v: table[v] for v in keep}
            for item in q.select:
                if item.kind == "expr":
                    out[item.alias] = engine.eval_arith_to_ids(item.expr, table)
            table = out
        elif any(k.startswith("__") for k in table):
            # internal columns (e.g. inlined subqueries' scoped variables)
            # are not part of ``*`` — drop them BEFORE DISTINCT so dedup
            # runs over the visible projection only
            table = {k: v for k, v in table.items() if not k.startswith("__")}
    if q.distinct:
        table = unique_table(table)
    return table


def _try_device_aggregate(
    db, q: SelectQuery, use_optimizer: bool, cache_entry=None
) -> Tuple[Optional[BindingTable], Optional[object], Optional[object]]:
    """Aggregate query fused ON DEVICE (plan + GROUP BY segment-reduce in
    one device pipeline; readback is one row per group).  Returns
    ``(table, plan, lowered)``: table None → the normal eval_where + host
    aggregation path, which reuses the returned plan AND device-lowered
    plan when present (neither the optimizer nor plan lowering runs
    twice on fallback; lowered False = lowering failed, don't retry).

    ``cache_entry``: plan-cache slot — a populated slot replays the
    cached plan + lowered program (repeat aggregate queries skip the
    optimizer and lowering entirely); a fresh one captures them."""
    if not use_optimizer or not _device_routed(db):
        return None, None, None
    from kolibrie_tpu.query.subquery_inline import inline_subqueries

    w = inline_subqueries(q.where)  # same fold eval_where applies (it is
    #                                 deterministic, so the plan built here
    #                                 matches the where eval_where sees)
    if w.subqueries or w.binds or w.window_blocks or not w.patterns:
        return None, None, None
    from kolibrie_tpu.optimizer.device_engine import (
        Unsupported,
        clause_replayable,
        lower_plan,
        try_device_execute_aggregated,
    )

    if cache_entry is not None and cache_entry["lowered"] is False:
        # lowering known-failed for this template+state.  The sentinel is
        # sticky across parameter rebinds (the slot's plan is dropped when
        # the constants change, but lowerability is a property of the
        # template, so the False survives and no retry happens here).
        return None, cache_entry["plan"], False
    if cache_entry is not None and cache_entry["plan"] is not None:
        cplan, clow = cache_entry["plan"], cache_entry["lowered"]
        if clow is not None:
            if not clause_replayable(clow, w):
                # plain-BGP lowering for a clause-carrying WHERE: its
                # UNION/OPTIONAL/MINUS/NOT ran as host post-passes on the
                # first call — hand it back as prebuilts so eval_where
                # replays exactly that route (device BGP + host clauses +
                # host aggregation), never the fused aggregate pipeline
                return None, cplan, clow
            table = try_device_execute_aggregated(db, cplan, q, lowered=clow)
            # table None here means the AGGREGATE stage declined (shape);
            # the caller's host fallback still reuses plan+lowered
            return table, cplan, clow

    resolved = [resolve_pattern(db, p) for p in w.patterns]
    logical = build_logical_plan(resolved, list(w.filters), [], w.values)
    planner = Streamertail(db.get_or_build_stats())
    plan = planner.find_best_plan(logical)
    # UNION/OPTIONAL/MINUS/NOT fuse under the aggregation exactly as on
    # the plain path (all-or-nothing; ineligible branch → host post-pass,
    # which also means host aggregation over the post-passed table)
    union_groups, optional_plans, anti_plans = [], [], []
    fusable = True
    for groups in w.unions:
        g = [_branch_plan(db, planner, bw) for bw in groups]
        if any(bp is None for bp in g):
            fusable = False
            break
        union_groups.append(tuple(g))
    for ow in w.optionals if fusable else ():
        bp = _branch_plan(db, planner, ow)
        if bp is None:
            fusable = False
            break
        optional_plans.append(bp)
    for bw in (
        list(w.minus) + [WhereClause(patterns=nb.patterns) for nb in w.not_blocks]
        if fusable
        else ()
    ):
        bp = _branch_plan(db, planner, bw)
        if bp is None:
            fusable = False
            break
        anti_plans.append(bp)
    def _capture(p, low):
        if cache_entry is not None:
            cache_entry["plan"] = p
            cache_entry["lowered"] = low

    if not fusable and (w.unions or w.optionals or w.minus or w.not_blocks):
        # branches un-fusable: eval_where will run the plain device BGP
        # with host clause post-passes + host aggregation — lower and
        # cache that program HERE so repeats (and this call's fallback)
        # skip the second optimizer pass and the re-lowering
        try:
            plain = lower_plan(db, plan)
            _capture(plan, plain)
            return None, plan, plain
        except Unsupported:
            _capture(plan, False)
            return None, plan, False

    try:
        lowered = lower_plan(
            db, plan, tuple(anti_plans), tuple(union_groups), tuple(optional_plans)
        )
    except Unsupported:
        if anti_plans or union_groups or optional_plans:
            try:  # the plain BGP may still lower even if a branch cannot
                plain = lower_plan(db, plan)
                _capture(plan, plain)
                return None, plan, plain
            except Unsupported:
                pass
        _capture(plan, False)
        return None, plan, False
    _capture(plan, lowered)
    return (
        try_device_execute_aggregated(db, plan, q, lowered=lowered),
        plan,
        lowered,
    )


def _group_key_cols(table: BindingTable, group_by: List[str]):
    cols = [table[g] for g in group_by if g in table]
    return cols


def _group_and_aggregate_table(db, table: BindingTable, q: SelectQuery) -> BindingTable:
    """GROUP BY + aggregates via np.unique segment ids (segment-reduce —
    device-friendly).  Parity: ``group_and_aggregate_results`` in
    execute_query.rs."""
    n = table_len(table)
    group_by = [g for g in q.group_by if g in table]
    if group_by:
        cols = _group_key_cols(table, group_by)
        stacked = np.stack(cols, axis=1) if cols else np.zeros((n, 0), dtype=np.uint32)
        uniq, inverse = np.unique(stacked, axis=0, return_inverse=True)
        n_groups = len(uniq)
    else:
        # aggregate without GROUP BY: exactly one group (SPARQL semantics)
        uniq = None
        inverse = np.zeros(n, dtype=np.int64)
        n_groups = 1
    out: BindingTable = {}
    for j, g in enumerate(group_by):
        out[g] = uniq[:, j].astype(np.uint32) if uniq is not None else np.empty(0, dtype=np.uint32)
    numeric = db.numeric_values()
    enc = db.dictionary.encode
    for item in q.select:
        if item.kind != "agg":
            continue
        agg = item.agg
        vals_col: Optional[np.ndarray] = None
        if agg.var is not None and agg.var in table:
            vals_col = table[agg.var]
        if agg.func == "COUNT":
            if vals_col is None:
                counts = np.bincount(inverse, minlength=n_groups) if n else np.zeros(n_groups, dtype=np.int64)
            elif agg.distinct:
                counts = np.zeros(n_groups, dtype=np.int64)
                for g in range(n_groups):
                    seg = vals_col[inverse == g]
                    counts[g] = len(np.unique(seg[seg != UNBOUND]))
            else:
                counts = np.bincount(inverse, weights=(vals_col != UNBOUND).astype(float), minlength=n_groups).astype(np.int64) if n else np.zeros(n_groups, dtype=np.int64)
            out[agg.alias] = _encode_numbers(enc, counts.astype(np.float64))
            continue
        if vals_col is None:
            out[agg.alias] = np.full(n_groups, UNBOUND, dtype=np.uint32)
            continue
        nums = numeric[np.minimum(vals_col, len(numeric) - 1)] if n else np.empty(0)
        if agg.func in ("SUM", "AVG", "MIN", "MAX"):
            res = np.zeros(n_groups, dtype=np.float64)
            for g in range(n_groups):
                seg = nums[inverse == g]
                seg = seg[~np.isnan(seg)]
                if len(seg) == 0:
                    res[g] = np.nan
                elif agg.func == "SUM":
                    res[g] = seg.sum()
                elif agg.func == "AVG":
                    res[g] = seg.mean()
                elif agg.func == "MIN":
                    res[g] = seg.min()
                else:
                    res[g] = seg.max()
            out[agg.alias] = _encode_numbers(enc, res)
        elif agg.func == "SAMPLE":
            res_ids = np.zeros(n_groups, dtype=np.uint32)
            for g in range(n_groups):
                seg = vals_col[inverse == g]
                res_ids[g] = seg[0] if len(seg) else UNBOUND
            out[agg.alias] = res_ids
        elif agg.func == "GROUP_CONCAT":
            dec = db.decode_term
            res_ids = np.zeros(n_groups, dtype=np.uint32)
            for g in range(n_groups):
                seg = vals_col[inverse == g]
                parts = [_format_value(dec(int(i))) for i in seg]
                res_ids[g] = enc('"' + ", ".join(x or "" for x in parts) + '"')
            out[agg.alias] = res_ids
        else:
            raise ValueError(f"unsupported aggregate {agg.func}")
    return out


def _encode_numbers(enc, values: np.ndarray) -> np.ndarray:
    out = np.empty(len(values), dtype=np.uint32)
    for i, v in enumerate(values):
        if np.isnan(v):
            out[i] = UNBOUND
        else:
            # non-finite stays float-formatted ("inf"/"-inf"); int(inf) raises
            isint = np.isfinite(v) and float(v) == int(v)
            sv = str(int(v)) if isint else f"{v:g}"
            out[i] = enc(f'"{sv}"')
    return out


# --------------------------------------------------------------------------
# Ordering / formatting
# --------------------------------------------------------------------------


def _order_table(db, table: BindingTable, order_by: List[OrderCondition]) -> BindingTable:
    n = table_len(table)
    if n == 0 or not order_by:
        return table
    numeric = db.numeric_values()
    keys = []
    for cond in reversed(order_by):
        if isinstance(cond.expr, Var) and cond.expr.name in table:
            col = table[cond.expr.name]
            nums = numeric[np.minimum(col, len(numeric) - 1)]
            if np.isnan(nums).any():
                # non-numeric: rank the decoded strings so DESC can negate
                dec = db.decode_term
                strs = np.array([dec(int(i)) or "" for i in col])
                _, order_key = np.unique(strs, return_inverse=True)
                order_key = order_key.astype(np.float64)
            else:
                order_key = nums
        else:
            engine = ExecutionEngine(db)
            nums = engine._try_numeric(cond.expr, table)
            order_key = nums if nums is not None else np.zeros(n)
        if cond.descending:
            order_key = -order_key
        keys.append(order_key)
    # stable lexsort over keys (last key = primary)
    idx = np.lexsort(tuple(keys))
    return {k: v[idx] for k, v in table.items()}


def _format_value(term: Optional[str]) -> str:
    """Human-facing form: strip literal quotes and datatype suffix.

    THE display rule — delegates to :func:`core.dictionary.display_form`,
    which the dictionary also applies incrementally at intern time, so the
    per-ID display cache and this per-term path can never diverge."""
    return display_form(term)


def table_header(table: BindingTable, q: SelectQuery) -> List[str]:
    """Output column names for a SELECT over a binding table (internal
    ``__``-prefixed columns excluded)."""
    if q.select_all():
        return sorted(k for k in table.keys() if not k.startswith("__"))
    header = []
    for item in q.select:
        if item.kind == "var":
            header.append(item.var)
        elif item.kind == "agg":
            header.append(item.agg.alias)
        else:
            header.append(item.alias)
    return header


_GLOBAL_RANK_MAX = 1 << 19  # dict sizes past this use per-column ranks


def _display_array(db):
    """(dict_len, display): ``display[id]`` is the human-facing form of
    every plain dictionary term (object array; ``display[0] == ""`` for
    UNBOUND).  Maintained INCREMENTALLY: the dictionary appends display
    forms at intern time, and growth here is one ``np.concatenate`` of the
    new tail — no full rebuild.  This converts the per-query decode of
    :func:`format_results` into one fancy index — the decode analogue of
    the reference's deferred final rayon pass (engine.rs:34-50)."""
    d = db.dictionary
    n = d._next_id
    cache = db.__dict__.get("_display_cache")
    if cache is not None and cache[0] == n:
        return cache
    forms = d.display_forms()
    if cache is not None and cache[0] < n:
        disp = np.concatenate(
            [cache[1], np.array(forms[cache[0]:], dtype=object)]
        )
    else:
        disp = np.array(forms, dtype=object)
    cache = (n, disp)
    db.__dict__["_display_cache"] = cache
    return cache


def _display_ranks(db, disp, result_rows: int = 1 << 62):
    """``ranks[id]`` = dense rank of ``display[id]`` in lexicographic
    order, or None when a dictionary-wide sort would not amortize (callers
    rank per column instead).  Built only when a canonical row sort
    actually needs it, once per dictionary size.

    Under mutation the dictionary grows every batch; rebuilding the global
    ranks then costs O(dict log dict) per batch no matter how small the
    result.  A stale cache is therefore only refreshed when the result is
    large enough for the rebuild to amortize — small results on a grown
    dictionary take the per-column path, which scales with the result."""
    n = len(disp)
    if n > _GLOBAL_RANK_MAX:
        return None
    cached = db.__dict__.get("_display_ranks")
    if (cached is None or cached[0] != n) and result_rows * 8 < n:
        return None
    cache = db.__dict__.get("_display_ranks")
    if cache is not None and cache[0] == n:
        return cache[1]
    if n:
        _, ranks = np.unique(disp, return_inverse=True)
        ranks = ranks.astype(np.uint32)
    else:
        ranks = np.empty(0, dtype=np.uint32)
    db.__dict__["_display_ranks"] = (n, ranks)
    return ranks


def format_results(
    db, table: BindingTable, q: SelectQuery, sort_rows: bool = False
) -> Rows:
    """:func:`_decode_rows` under the span ``query.decode``."""
    with span("query.decode"):
        return _decode_rows(db, table, q, sort_rows)


def _decode_rows(
    db, table: BindingTable, q: SelectQuery, sort_rows: bool = False
) -> Rows:
    """Final ID→string decode (engine.rs:34-50 parity).

    Plain-term columns decode by fancy-indexing the db-level display cache;
    ``sort_rows=True`` additionally applies the engine's canonical
    no-ORDER-BY row order (lexicographic by display string) via
    ``np.lexsort`` over per-ID display ranks — exactly ``rows.sort()``,
    without materializing rows first.  Columns containing quoted-triple IDs
    (RDF-star) take the per-unique decode path instead."""
    header = table_header(table, q)
    n = table_len(table)
    if n == 0 or not header:
        return []
    id_cols = []
    any_quoted = False
    for h in header:
        col = table.get(h)
        if col is None:
            id_cols.append(None)
            continue
        ids = np.asarray(col)
        if (ids & QUOTED_BIT).any():
            any_quoted = True
        id_cols.append(ids)
    if any_quoted:
        # rare path: per-unique recursive decode (<< s p o >> rendering)
        dec = db.decode_term
        cols = []
        for ids in id_cols:
            if ids is None:
                cols.append([""] * n)
                continue
            uniq, inv = np.unique(ids, return_inverse=True)
            decoded = [
                _format_value(dec(int(i))) if i != UNBOUND else ""
                for i in uniq
            ]
            cols.append([decoded[j] for j in inv.tolist()])
        rows = [list(row) for row in zip(*cols)]
        if sort_rows:
            rows.sort()
        return rows
    dict_len, disp = _display_array(db)
    safe_cols = [
        None if ids is None else np.where(ids < dict_len, ids, 0)
        for ids in id_cols
    ]
    if sort_rows:
        ranks = _display_ranks(db, disp, result_rows=n)
        keys = []
        for ids in safe_cols:
            if ids is None:
                keys.append(np.zeros(n, dtype=np.uint32))
            elif ranks is not None:
                keys.append(ranks[ids])
            else:
                # dictionary too large for global ranks: dense ranks over
                # just this column's distinct display strings
                u_ids, inv = np.unique(ids, return_inverse=True)
                _, u_rank = np.unique(disp[u_ids], return_inverse=True)
                keys.append(u_rank.astype(np.uint32)[inv])
        idx = np.lexsort(tuple(reversed(keys)))
        safe_cols = [None if c is None else c[idx] for c in safe_cols]
    out = np.empty((n, len(header)), dtype=object)
    for j, ids in enumerate(safe_cols):
        out[:, j] = "" if ids is None else disp[ids]
    return out.tolist()


# --------------------------------------------------------------------------
# Top-level entry points
# --------------------------------------------------------------------------


def _apply_limit_offset(rows: Rows, q: SelectQuery) -> Rows:
    start = q.offset or 0
    end = start + q.limit if q.limit is not None else None
    return rows[start:end]


def execute_select(
    db, q: SelectQuery, use_optimizer: bool = True, cache_entry=None
) -> Rows:
    if (
        use_optimizer
        and q.order_by
        and q.limit is not None
        and not (cache_entry is not None and cache_entry.get("ordered_failed"))
    ):
        # ORDER BY + LIMIT fused on device: top-k sort, O(limit) readback.
        # ``ordered_failed`` is the sticky per-template negative: once the
        # fused lowering raised Unsupported for this template+state, repeat
        # calls (any constants) skip the doomed plan+lower attempt.
        from kolibrie_tpu.optimizer.device_engine import (
            try_device_execute_ordered,
        )

        rows = try_device_execute_ordered(db, q, cache_entry=cache_entry)
        if rows is not None:
            return rows
    table = eval_select_to_table(db, q, use_optimizer, cache_entry=cache_entry)
    table = _order_table(db, table, q.order_by)
    rows = format_results(db, table, q, sort_rows=not q.order_by)
    return _apply_limit_offset(rows, q)


def process_insert_clause(db, insert: InsertClause) -> int:
    count = 0
    for pat in insert.triples:
        ids = []
        for t in (pat.subject, pat.predicate, pat.object):
            if t.is_var:
                raise ValueError("INSERT DATA cannot contain variables")
            ids.append(_encode_pattern_term(db, t))
        db.add_triple(Triple(*ids))
        count += 1
    return count


def _encode_pattern_term(db, t: PatternTerm) -> int:
    if t.kind == "quoted":
        s, p, o = t.value
        return db.quoted.intern(
            _encode_pattern_term(db, s),
            _encode_pattern_term(db, p),
            _encode_pattern_term(db, o),
        )
    return db.dictionary.encode(db.expand_term(t.value))


def process_delete_clause(db, delete: DeleteClause) -> int:
    """DELETE [WHERE]: bind variables from WHERE, substitute into the delete
    templates, remove (execute_query.rs:395-468)."""
    count = 0
    if delete.where is None:
        for pat in delete.triples:
            ids = [_encode_pattern_term(db, t) for t in (pat.subject, pat.predicate, pat.object)]
            db.delete_triple(Triple(*ids))
            count += 1
        return count
    table = eval_where(db, delete.where)
    n = table_len(table)
    for pat in delete.triples:
        cols = []
        for t in (pat.subject, pat.predicate, pat.object):
            if t.is_var:
                col = table.get(t.value)
                if col is None:
                    col = np.full(n, UNBOUND, dtype=np.uint32)
                cols.append(col)
            else:
                cols.append(np.full(n, _encode_pattern_term(db, t), dtype=np.uint32))
        for i in range(n):
            db.delete_triple(Triple(int(cols[0][i]), int(cols[1][i]), int(cols[2][i])))
            count += 1
    return count


_PLAN_CACHE_MAX = 128  # parsed-AST entries (query text → template key)


_TEMPLATE_CACHE_MAX = 64  # plan templates (fingerprint → per-state slots)


_PLAN_STATES_MAX = 4  # per-template (store version, udfs, mode) slots kept


def _plan_caches(db):
    """The two cache levels + counters, lazily attached to the database."""
    from collections import OrderedDict

    parse = db.__dict__.get("_plan_cache")
    if parse is None:
        parse = OrderedDict()
        db.__dict__["_plan_cache"] = parse
    templates = db.__dict__.get("_template_cache")
    if templates is None:
        templates = OrderedDict()
        db.__dict__["_template_cache"] = templates
    stats = db.__dict__.get("_plan_cache_stats")
    if stats is None:
        stats = {
            "hits": 0,
            "misses": 0,
            "param_rebinds": 0,
            "evictions": 0,
            "batched": 0,
            "batch_groups": 0,
            "solo_tail": 0,
        }
        db.__dict__["_plan_cache_stats"] = stats
    return parse, templates, stats


def _unresolved_params(db, params) -> tuple:
    """The string constants among ``params`` with no dictionary id yet.
    A plan built while any of these were unknown embeds a can-never-match
    sentinel for them, so it must be rebuilt (host-side; the device
    executable is keyed on the constant-free spec and is NOT recompiled)
    once the term gets interned — mutation batches under the delta
    threshold no longer move ``base_version``, so the slot key alone
    can't notice."""
    dic = db.dictionary
    return tuple(
        p
        for p in params
        if isinstance(p, str) and dic.lookup(db.expand_term(p)) is None
    )


def _plan_cache_entry(db, sparql: str):
    """Automatic plan cache on the database.  Three granularities:

    - the parsed AST is keyed by (query text, prefix map) — it survives
      store mutations, so INSERT/SELECT workloads never re-parse; parsing
      also canonicalizes the query into a constant-free *template*
      fingerprint plus its parameter tuple
      (:func:`kolibrie_tpu.query.template.fingerprint_query`);
    - plan slots are keyed by the TEMPLATE fingerprint, not the query
      text: the thousand constant-variants of one query shape share a
      single cache entry (and, downstream, a single jit executable —
      the lowered program carries its constants in a traced parameter
      vector).  What a template shares is every constant but the
      predicates its scanned patterns name: a scan is compiled for the
      rows under its predicate, so those are structure in the
      fingerprint (and parameters too), and a text that names other
      predicates is another template with capacities of its own;
    - within a template, the physical plan + device-lowered program live
      in per-state slots keyed by (store BASE version, UDF registry,
      execution mode), so e.g. host/device alternation keeps BOTH
      compiled programs warm instead of evicting on every flip — and
      because mutation batches under the store's delta threshold advance
      only ``delta_epoch`` (never ``base_version``), prepared plans
      survive sustained insert/delete traffic; per-execution scan ranges
      and the small device delta segment carry the fresh state.

    A slot replays its plan/lowered program only when the stored
    parameter binding matches the incoming one; on mismatch the plan is
    rebuilt (host-side, cheap) while the device executable — keyed on
    the constant-free ``PlanSpec`` — is reused without recompiling.
    Known-failure sentinels (``lowered is False``, ``ordered_failed``)
    are properties of the template and survive parameter rebinds.

    Both levels are LRU-bounded (``_PLAN_CACHE_MAX`` parse entries,
    ``_TEMPLATE_CACHE_MAX`` templates); ``plan_cache_info`` reports
    occupancy and hit/miss/eviction counters.  Returns ``(entry, slot)``;
    ``entry`` carries the parsed ``cq``, ``slot`` has the
    ``plan``/``lowered`` keys ``eval_select_to_table`` consumes."""
    from kolibrie_tpu.optimizer.mqo import mqo_mode
    from kolibrie_tpu.optimizer.planner import wcoj_mode
    from kolibrie_tpu.optimizer.stats_advisor import (
        stats_advisor,
        stats_advisor_mode,
    )
    from kolibrie_tpu.ops.pallas_kernels import pallas_mode
    from kolibrie_tpu.query.compile_cache import record_template
    from kolibrie_tpu.query.template import fingerprint_query

    parse, templates, stats = _plan_caches(db)
    prefix_sig = tuple(sorted(db.prefixes.items()))
    # the join-strategy, interpreter-routing, Pallas kernel, MQO sharing
    # and stats-advisor modes are part of the template fingerprint; a
    # mode flip after parse must refingerprint (not replay the old-mode
    # plan)
    env_sig = (
        wcoj_mode(),
        _interp_mode(),
        pallas_mode(),
        mqo_mode(),
        stats_advisor_mode(),
    )
    ent = parse.get(sparql)
    if ent is None or ent["prefix_sig"] != prefix_sig or ent["env_sig"] != env_sig:
        ent = {
            "prefix_sig": prefix_sig,
            "env_sig": env_sig,
            "cq": None,
            "fp": None,
            "params": (),
        }
        parse[sparql] = ent
    parse.move_to_end(sparql)
    while len(parse) > _PLAN_CACHE_MAX:
        parse.popitem(last=False)
    if ent["cq"] is None:
        with span("query.parse"):
            t0 = time.perf_counter()
            ent["cq"] = parse_combined_query(sparql, db.prefixes)
            ent["fp"], ent["params"] = fingerprint_query(ent["cq"])
            _PARSE_LAT.observe(time.perf_counter() - t0)
    fp, params = ent["fp"], ent["params"]
    # feed the pre-warm manifest: per-template popularity + one
    # representative query text the warmer can replay after a restart
    record_template(fp, sparql)
    tent = templates.get(fp)
    if tent is None:
        tent = {"by_state": {}, "hits": 0, "misses": 0}
        templates[fp] = tent
    templates.move_to_end(fp)
    while len(templates) > _TEMPLATE_CACHE_MAX:
        templates.popitem(last=False)
        stats["evictions"] += 1
        _PLAN_CACHE_EVICTION.inc()
    version = db.store.base_version
    # the mesh signature joins the state key: attaching/detaching the
    # sharded serving layer (or resizing its mesh) must never replay a
    # plan lowered for the other topology (docs/SHARDING.md)
    _sh = db.__dict__.get("_sharded_serving")
    state = (
        version,
        db.__dict__.get("_udf_version", 0),
        db.execution_mode,
        None if _sh is None else _sh.signature,
    )
    slot = tent["by_state"].get(state)
    if slot is not None and slot["lowered"] is False:
        # sticky-failure expiry: a ``False`` sentinel from a TRANSIENT
        # device fault should not outlive the fault.  The template's
        # circuit breaker bumps ``close_epoch`` on every open→closed
        # recovery; when the epoch has advanced past the one captured
        # with the sentinel, the fault demonstrably healed — clear the
        # sentinel so the next execution retries device lowering.
        # Shape-level failures (Unsupported) stay sticky: their host
        # fallback records success on an always-closed breaker, which
        # never bumps the epoch.
        epoch = breaker_board(db).close_epoch(fp)
        if slot.get("breaker_epoch") is None:
            slot["breaker_epoch"] = epoch
        elif slot["breaker_epoch"] != epoch:
            slot["plan"] = None
            slot["lowered"] = None
            slot["ordered_failed"] = False
            slot["breaker_epoch"] = epoch
            stats["sentinel_expiries"] = stats.get("sentinel_expiries", 0) + 1
    if slot is None:
        # stale-base-version slots pin device-resident copies of OLD store
        # orders (a LoweredPlan holds full sorted-store copies): drop
        # them, keeping only the live base's udf/mode variants (same
        # policy as dist_query's _dist_plan_cache)
        for k in [k for k in tent["by_state"] if k[0] != version]:
            tent["by_state"].pop(k)
        slot = {
            "plan": None,
            "lowered": None,
            "params": params,
            "ordered_failed": False,
            "unresolved": _unresolved_params(db, params),
            "quoted_n": len(db.quoted),
        }
        tent["by_state"][state] = slot
        while len(tent["by_state"]) > _PLAN_STATES_MAX:
            # dicts iterate in insertion order: drop the oldest state
            tent["by_state"].pop(next(iter(tent["by_state"])))
        stats["misses"] += 1
        tent["misses"] += 1
        _PLAN_CACHE_MISS.inc()
    elif slot["params"] != params:
        # same template, new constants: the cached plan/lowered program
        # embed the OLD parameter binding, so they cannot replay — drop
        # them and rebind.  The jit executable is keyed on the
        # constant-free PlanSpec, so the re-lowering triggered downstream
        # rebinds the parameter vector WITHOUT a device recompile.  The
        # known-failure sentinels stay: lowerability is decided by the
        # template's shape, never by the constant values.
        failed = slot["lowered"] is False
        slot["plan"] = None
        slot["lowered"] = False if failed else None
        slot["params"] = params
        slot["unresolved"] = _unresolved_params(db, params)
        slot["quoted_n"] = len(db.quoted)
        stats["param_rebinds"] += 1
        tent["misses"] += 1
        _PLAN_CACHE_REBIND.inc()
    else:
        # same binding — but a constant that was UNKNOWN when the slot's
        # plan was built may have been interned by an insert since (only
        # delta_epoch moved, so the state key didn't): the embedded
        # can-never-match sentinel is now wrong.  Rebind exactly like a
        # parameter change: host-side rebuild, no device recompile.
        rebind = False
        unres = slot.get("unresolved", ())
        if unres:
            still = _unresolved_params(db, unres)
            if len(still) != len(unres):
                slot["unresolved"] = still
                rebind = True
        if not rebind and slot.get("quoted_n") != len(db.quoted):
            # unknown quoted-triple ids resolve through db.quoted, not the
            # dictionary; only plans that actually embed one need a rebuild
            low = slot["lowered"]
            if low is not None and low is not False:
                checks = getattr(low, "const_checks", ()) or ()
                scans = getattr(low, "scan_descs", ()) or ()
                if any(t is None for cc in checks for t in cc) or any(
                    c is not None and c < 0 for _n, cs in scans for c in cs
                ):
                    rebind = True
            slot["quoted_n"] = len(db.quoted)
        if rebind:
            failed = slot["lowered"] is False
            slot["plan"] = None
            slot["lowered"] = False if failed else None
            stats["param_rebinds"] += 1
            tent["misses"] += 1
            _PLAN_CACHE_REBIND.inc()
        else:
            stats["hits"] += 1
            tent["hits"] += 1
            _PLAN_CACHE_HIT.inc()
    # drift-triggered replan: the stats advisor bumps a template's plan
    # generation when observed cardinalities drift past the estimates the
    # cached plan was built from (mutation churn moving selectivities, or
    # the cold→learned transition).  A stale stamp drops the plan AND the
    # lowered program — the rebuild replans with the tuned stats; the jit
    # executable for an unchanged plan shape replays from its spec-keyed
    # cache without recompiling.  Same slot-expiry discipline as the
    # breaker epoch above; the MODE itself already rode in via env_sig.
    gen = stats_advisor.plan_gen(fp)
    if slot.get("advisor_gen") is None:
        slot["advisor_gen"] = gen
    elif slot["advisor_gen"] != gen:
        slot["plan"] = None
        slot["lowered"] = None
        slot["ordered_failed"] = False
        slot["advisor_gen"] = gen
        stats["advisor_replans"] = stats.get("advisor_replans", 0) + 1
        stats_advisor.note_replan(fp)
    return ent, slot


def plan_cache_info(db) -> dict:
    """Inspection snapshot of the two-level plan cache: occupancy,
    hit/miss/eviction/rebind counters, sticky-failure counts, and a
    per-template breakdown (keyed by fingerprint)."""
    parse, templates, stats = _plan_caches(db)
    per = {}
    sticky = 0
    for fp, tent in templates.items():
        failed = sum(
            1 for s in tent["by_state"].values() if s["lowered"] is False
        )
        sticky += failed
        # where the template's most recent device dispatch came from:
        # "interp" (bytecode interpreter), "compiled" (real XLA compile
        # or warm jit replay), "disk" (persistent-cache hit) — None when
        # nothing device-lowered has run yet
        source = None
        for s in tent["by_state"].values():
            low = s.get("lowered")
            if low is not None and low is not False:
                source = getattr(low, "last_source", None) or source
        per[fp] = {
            "states": len(tent["by_state"]),
            "hits": tent["hits"],
            "misses": tent["misses"],
            "failed_states": failed,
            "source": source,
        }
    return {
        "parse_entries": len(parse),
        "templates": len(templates),
        "hits": stats["hits"],
        "misses": stats["misses"],
        "param_rebinds": stats["param_rebinds"],
        "evictions": stats["evictions"],
        "batched": stats["batched"],
        "batch_groups": stats["batch_groups"],
        "solo_tail": stats["solo_tail"],
        "sticky_failures": sticky,
        "sentinel_expiries": stats.get("sentinel_expiries", 0),
        "advisor_replans": stats.get("advisor_replans", 0),
        "per_template": per,
        "limits": {
            "parse": _PLAN_CACHE_MAX,
            "templates": _TEMPLATE_CACHE_MAX,
            "states": _PLAN_STATES_MAX,
        },
    }


def _execute_degraded(db, sparql: str) -> Rows:
    """Degraded mode: run on the CPU interpreter path by forcing host
    execution for this call.  The plan-cache state key includes
    ``execution_mode``, so the host plan gets (and keeps) its own warm
    slot — repeat degraded queries don't re-plan.

    The mode flip is a plain attribute swap: callers that share a
    database across threads (the serving layer's TemplateBatcher) already
    serialize all database access on ``dispatch_lock``."""
    check_deadline("executor.degraded")
    prev = db.execution_mode
    db.execution_mode = "host"
    t0 = time.perf_counter()
    try:
        with span("query.degraded"):
            ent, slot = _plan_cache_entry(db, sparql)
            rows = execute_combined(db, ent["cq"], cache_entry=slot)
        _QUERY_LAT_DEGRADED.observe(time.perf_counter() - t0)
        return rows
    finally:
        db.execution_mode = prev


def execute_query_volcano(sparql: str, db) -> Rows:
    """The main query path (execute_query.rs:356 parity).

    Device-routed queries run behind the template's circuit breaker
    (:mod:`kolibrie_tpu.resilience.breaker`): transient device faults
    (injected or real compile failures, device OOM) and deadline blowups
    count against the breaker; a device fault degrades THIS call to the
    CPU interpreter path and, once the breaker trips, the whole template
    is served degraded until a half-open probe succeeds.  ``Unsupported``
    is not a fault — the sticky lowering sentinel already handles it."""
    check_deadline("executor.enter")
    db.register_prefixes_from_query(sparql)
    ent, slot = _plan_cache_entry(db, sparql)
    fp = ent["fp"]
    # baggage lets device_engine label its lower/dispatch timings with
    # the template fingerprint without threading it through eval_where
    set_baggage("template", fp)
    # the stats advisor's own channel: planning (Streamertail) and the
    # observation hooks key learned cardinalities on the fingerprint —
    # routing state must not ride the observability baggage, which dies
    # with the obs kill switch
    _sa_set_current_fp(fp)
    if not _device_routed(db):
        t0 = time.perf_counter()
        with span("query.execute", template=fp, path="host"):
            rows = execute_combined(db, ent["cq"], cache_entry=slot)
        _QUERY_LAT_HOST.observe(time.perf_counter() - t0)
        return rows
    board = breaker_board(db)
    if not board.allow(fp):
        return _execute_degraded(db, sparql)
    t0 = time.perf_counter()
    try:
        with span("query.execute", template=fp, path="device"):
            rows = execute_combined(db, ent["cq"], cache_entry=slot)
    except DeadlineExceeded:
        # still shed (the client's budget is gone either way), but a
        # template that repeatedly blows deadlines on the device trips
        # its breaker and future calls go straight to the host path
        board.record_failure(fp)
        raise
    except Exception as e:
        if not is_device_fault(e):
            raise
        board.record_failure(fp)
        return _execute_degraded(db, sparql)
    board.record_success(fp)
    _QUERY_LAT_DEVICE.observe(time.perf_counter() - t0)
    return rows


def _batchable_select(db, cq):
    """Return ``(q, folded_where)`` when the query is a plain SELECT the
    batched device dispatch can run — single BGP + filters, projection of
    variables only, all post-processing (DISTINCT, LIMIT/OFFSET,
    formatting) host-side per member.  ``None`` → run it solo."""
    from kolibrie_tpu.query.subquery_inline import inline_subqueries

    if (
        cq.select is None
        or cq.register is not None
        or cq.rules
        or cq.insert is not None
        or cq.delete is not None
        or cq.models
        or cq.neural_relations
        or cq.train_decls
        or cq.ml_predict is not None
        or cq.retrieve is not None
    ):
        return None
    if db.neural_relations:
        return None
    q = cq.select
    if q.group_by or q.order_by or any(i.kind != "var" for i in q.select):
        return None
    w = inline_subqueries(q.where)
    if (
        w.subqueries
        or w.binds
        or w.window_blocks
        or w.unions
        or w.optionals
        or w.minus
        or w.not_blocks
        or w.values is not None
        or not w.patterns
    ):
        return None
    return q, w


def _finish_select_table(db, q: SelectQuery, table: BindingTable) -> Rows:
    """The host tail of a plain SELECT (projection → DISTINCT → format →
    LIMIT/OFFSET), mirroring eval_select_to_table + execute_select."""
    if not q.select_all():
        keep = [i.var for i in q.select if i.kind == "var" and i.var in table]
        table = {v: table[v] for v in keep}
    elif any(k.startswith("__") for k in table):
        table = {k: v for k, v in table.items() if not k.startswith("__")}
    if q.distinct:
        table = unique_table(table)
    rows = format_results(db, table, q, sort_rows=True)
    return _apply_limit_offset(rows, q)


def _serve_group_on_one_chip(db, fp: str, group, board):
    """One one-chip template group, under the span ``executor.batch``
    (attrs ``template``, ``batch`` = live members, ``slots``): its members'
    lowerings, one ``execute_plan_batch`` dispatch in the slot class of
    its live members, each member's finished rows.  ``group``: ``(index,
    entry, slot, query, where)`` a member.  ``None`` where the group
    cannot ride one dispatch (a shape the lowering declines, a divergence
    inside the group, a device fault the breaker has counted): its
    members then run solo."""
    from kolibrie_tpu.optimizer.device_engine import (
        Unsupported,
        execute_plan_batch,
        lower_plan,
    )
    from kolibrie_tpu.ops import slot_class

    with span("executor.batch", template=fp, batch=len(group)) as sp:
        try:
            lowereds = []
            for _i, _ent, _slot, _q, w in group:
                resolved = [resolve_pattern(db, p) for p in w.patterns]
                logical = build_logical_plan(resolved, list(w.filters), [], None)
                planner = Streamertail(db.get_or_build_stats())
                plan = planner.find_best_plan(logical)
                lowereds.append((plan, lower_plan(db, plan)))
            if sp is not None:  # what only the lowerings tell
                live = sum(low.const_ok() for _, low in lowereds)
                sp.attrs.update(batch=live, slots=slot_class(live) if live else 0)
            tables = execute_plan_batch([low for _, low in lowereds])
        except Unsupported:
            return None  # shape/plan divergence inside the group: solo path
        except DeadlineExceeded:
            board.record_failure(fp)
            raise
        except Exception as e:
            if not is_device_fault(e):
                raise
            # transient compile or device fault: count it, hand the whole
            # group to the solo path (which degrades per the breaker)
            board.record_failure(fp)
            return None
        board.record_success(fp)
        out = []
        for (i, ent, slot, q, _w), (plan, lowered), table in zip(
            group, lowereds, tables
        ):
            if slot["params"] == ent["params"] and slot["lowered"] is None:
                slot["plan"], slot["lowered"] = plan, lowered
            out.append((i, _finish_select_table(db, q, table)))
        return out


def execute_queries_batched(db, queries: List[str]) -> List[Rows]:
    """Execute a batch of queries, dispatching same-template plain SELECTs
    as ONE device program (``execute_plan_batch``): the device runs every
    member of a template group in a single jit call, in the slot class of
    the group's size, instead of one dispatch per query.  Everything else
    — singleton templates, aggregates, ordered queries, updates — falls
    back to ``execute_query_volcano`` per query.  With a mesh attached
    (``db._sharded_serving``) every template group, singletons too, goes
    to ``ShardedDatabase.execute_batch`` first, and the single-device
    paths serve only what the mesh lowering declines.  Results come back
    in input order; per-query host post-processing (DISTINCT,
    LIMIT/OFFSET, formatting) is identical to the solo path."""
    check_deadline("executor.batch")
    results: List[Optional[Rows]] = [None] * len(queries)
    for text in queries:
        db.register_prefixes_from_query(text)
    groups: Dict[str, List[int]] = {}
    members: List[Optional[tuple]] = [None] * len(queries)
    board = breaker_board(db)
    sharded = db.__dict__.get("_sharded_serving")
    if _device_routed(db) or sharded is not None:
        for i, text in enumerate(queries):
            ent, slot = _plan_cache_entry(db, text)
            if slot["lowered"] is False:
                continue  # template known un-lowerable: solo (host) path
            eligible = _batchable_select(db, ent["cq"])
            if eligible is None:
                continue
            q, w = eligible
            members[i] = (ent, slot, q, w)
            groups.setdefault(ent["fp"], []).append(i)
    _, _, stats = _plan_caches(db)
    for fp, idxs in groups.items():
        if len(idxs) < 2 and sharded is None:
            continue  # solo dispatch is already optimal for singletons
        if not board.allow(fp):
            continue  # breaker open: members fall to the solo degraded path
        if _interp_mode() == "force":
            # forced interpreter routing: the mesh shard_map program and
            # the one-chip group jit are exactly the per-template compiles
            # the mode exists to avoid — members run solo through the
            # single-device interpreter instead (docs/COMPILE_CACHE.md)
            continue
        set_baggage("template", fp)
        _sa_set_current_fp(fp)
        if sharded is not None:
            # mesh-first: the whole template group, a group of one like
            # any other, rides one shard_map dispatch
            # (parallel/sharded_serving.py): under an attached mesh no
            # device holds the whole store in the deployment this stands
            # for.  On Unsupported or a device fault the group degrades
            # to the single-device paths below, with the breaker counting
            # mesh trips
            from kolibrie_tpu.parallel.sharded_serving import (
                Unsupported as _MeshUnsupported,
            )

            try:
                with span("executor.sharded", template=fp, batch=len(idxs)):
                    got = sharded.execute_batch(
                        fp, [(i, queries[i]) for i in idxs]
                    )
            except _MeshUnsupported:
                pass  # group shape stays single-device: fall through
            except DeadlineExceeded:
                board.record_failure(fp)
                raise
            except Exception as e:
                if not is_device_fault(e):
                    raise
                board.record_failure(fp)
            else:
                board.record_success(fp)
                stats["batched"] += len(idxs)
                stats["batch_groups"] += 1
                _BATCHED_QUERIES.inc(len(idxs))
                for i in idxs:
                    results[i] = got[i]
                continue
        if len(idxs) < 2 or not _device_routed(db):
            # mesh declined: a singleton runs solo, and so does a group
            # without single-device jit routing
            continue
        got = _serve_group_on_one_chip(
            db, fp, [(i, *members[i]) for i in idxs], board
        )
        if got is None:
            continue  # the members fall to the solo path
        stats["batched"] += len(idxs)
        stats["batch_groups"] += 1
        _BATCHED_QUERIES.inc(len(idxs))
        for i, rows in got:
            results[i] = rows
    # multi-query sharing for the solo tail: register every still-pending
    # member's prefix fingerprint as a transient beneficiary, so the MQO
    # layer sees the dispatch's full fan-out before the first member runs
    # (optimizer/mqo.py; fingerprints memoize per store version)
    from kolibrie_tpu.optimizer import mqo as _mqo

    transient_fps: List[str] = []
    pending = [i for i in range(len(queries)) if results[i] is None]
    if len(pending) >= 2 and _mqo.mqo_mode() != "off":
        for i in pending:
            fp = _solo_prefix_fp(db, queries[i])
            if fp is not None:
                transient_fps.append(fp)

    def solo_tail():
        for i in pending:
            results[i] = execute_query_volcano(queries[i], db)

    with _mqo.transient_scope(db, transient_fps):
        if pending and len(queries) > 1:
            # one span around the singletons that run behind other members
            # of the same dispatch (its groups, or one another): what a mixed
            # dispatch adds to a request that would have left alone.  A lone
            # request opens none
            with span(
                "executor.solo_tail",
                members=len(pending),
                grouped=len(queries) - len(pending),
            ):
                solo_tail()
        else:
            solo_tail()
    stats["solo_tail"] += len(pending)
    return results


def dispatch_programs(db) -> Tuple[int, int]:
    """What ``execute_queries_batched`` has run on ``db`` so far: ``(group
    programs, solo programs)``: one group program a template group served
    as one (on one chip two or more members, on the mesh one or more), one
    solo program a query that went through :func:`execute_query_volcano`, a
    lone request's too.  The micro-batcher reads it around a dispatch, under
    the lock that serializes the store."""
    _, _, stats = _plan_caches(db)
    return stats["batch_groups"], stats["solo_tail"]


def _solo_prefix_fp(db, text: str) -> Optional[str]:
    """MQO prefix fingerprint for one batch member, or None when the
    query is outside the batchable/shareable shape.  Never raises: a
    member that fails here simply isn't registered as a beneficiary, and
    the solo loop reports its real error in input order."""
    from kolibrie_tpu.optimizer import mqo as _mqo
    from kolibrie_tpu.optimizer.device_engine import Unsupported, lower_plan

    try:
        ent, _slot = _plan_cache_entry(db, text)
        eligible = _batchable_select(db, ent["cq"])
        if eligible is None:
            return None
        _q, w = eligible

        def _lower():
            try:
                resolved = [resolve_pattern(db, p) for p in w.patterns]
                logical = build_logical_plan(
                    resolved, list(w.filters), [], None
                )
                planner = Streamertail(db.get_or_build_stats())
                return lower_plan(db, planner.find_best_plan(logical))
            except Unsupported:
                return None

        return _mqo.prefix_fp_for(db, ent["fp"], _lower)
    except Exception:
        # registration is best-effort routing state; the member's actual
        # evaluation surfaces any real error — but the miss is counted so
        # a systematically failing registration path stays visible
        _mqo._DECLINED.labels("fp_error").inc()
        return None


def collect_all_patterns(where: WhereClause) -> List[PatternTriple]:
    """Every triple pattern reachable from a group pattern — including
    OPTIONAL/UNION/MINUS branches, NOT blocks, subqueries, and WINDOW
    blocks (used for neural-relation materialization coverage)."""
    out: List[PatternTriple] = list(where.patterns)
    for nb in where.not_blocks:
        out.extend(nb.patterns)
    for wb in where.window_blocks:
        out.extend(wb.patterns)
    for opt in where.optionals:
        out.extend(collect_all_patterns(opt))
    for groups in where.unions:
        for g in groups:
            out.extend(collect_all_patterns(g))
    for m in where.minus:
        out.extend(collect_all_patterns(m))
    for sq in where.subqueries:
        out.extend(collect_all_patterns(sq.query.where))
    return out


def _materialize_neural_for_select(db, select: SelectQuery) -> None:
    if not db.neural_relations:
        return
    from kolibrie_tpu.ml import runtime as ml_runtime

    ml_runtime.materialize_neural_relations_for_patterns(
        db, collect_all_patterns(select.where)
    )


def execute_combined(db, cq: CombinedQuery, cache_entry=None) -> Rows:
    db.prefixes.update(cq.prefixes)
    if cache_entry is not None and (
        cq.register is not None
        or cq.rules
        or cq.insert is not None
        or cq.delete is not None
        or cq.models
        or cq.neural_relations
        or cq.train_decls
        or cq.ml_predict is not None
    ):
        # updates / declarations mutate the database (or registries the
        # cache state key doesn't cover): only plain SELECTs reuse plans
        cache_entry = None
    if cache_entry is not None and db.neural_relations:
        # neural-predicate materialization inserts triples MID-execution,
        # so the slot's store-version key would not describe the program
        # captured under it
        cache_entry = None
    # neural/train declarations
    if cq.models or cq.neural_relations or cq.train_decls or cq.ml_predict:
        from kolibrie_tpu.ml import runtime as ml_runtime

        ml_runtime.register_declarations(db, cq)
        for train in cq.train_decls:
            ml_runtime.execute_train_decl(db, train)
        if cq.ml_predict is not None:
            ml_runtime.execute_ml_predict(db, cq.ml_predict)
    for rule in cq.rules:
        from kolibrie_tpu.reasoner import rule_runtime

        rule_runtime.process_combined_rule(db, rule)
    if cq.delete is not None:
        process_delete_clause(db, cq.delete)
    if cq.insert is not None:
        process_insert_clause(db, cq.insert)
    if cq.select is not None:
        # neural predicates referenced anywhere in the query materialize as
        # ordinary triples first (neural_relations.rs parity)
        _materialize_neural_for_select(db, cq.select)
        return execute_select(db, cq.select, cache_entry=cache_entry)
    return []


def execute_query(sparql: str, db) -> Rows:
    """Legacy sequential path (execute_query.rs:156 parity): same semantics,
    naive join order, no cost-based planning.  Kept for agreement tests."""
    db.register_prefixes_from_query(sparql)
    cq = parse_combined_query(sparql, db.prefixes)
    if cq.select is None:
        return execute_combined(db, cq)
    # same pre-pass as the volcano path, so both agree on neural queries
    _materialize_neural_for_select(db, cq.select)
    return execute_select(db, cq.select, use_optimizer=False)
