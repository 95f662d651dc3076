"""Query → (template fingerprint, parameter tuple) canonicalization.

A *template* is the parsed AST with every constant leaf (IRIs, string and
numeric literals, pattern-position terms, VALUES cells) replaced by a typed
placeholder.  Two queries that differ only in those constants share one
fingerprint, and therefore one plan-cache entry and — because the lowered
plan carries the constants in a traced parameter vector
(:mod:`kolibrie_tpu.optimizer.device_engine`) — one device executable.

The constants themselves come back as an ordered tuple of ``params``; the
order is the deterministic AST traversal order, which is also the order the
lowering pass consumes them in, so equal fingerprints imply positionally
comparable parameter tuples.

Structure-relevant scalars stay in the fingerprint:

* variable / alias names, operators, DISTINCT, GROUP BY keys;
* the predicate a scanned triple pattern names (it is in ``params`` as
  well): a scan is compiled for the rows under its predicate, so the
  capacities and the compiled group that a fingerprint keys belong to one
  set of predicates;
* whether a string literal parses as a number (the lowering pass branches
  on that when it sits on one side of a comparison);
* for ordered+limited queries, the power-of-two bucket of
  ``offset + limit`` (the top-k ``k`` is a static jit argument, quantized
  exactly like :func:`try_device_execute_ordered` quantizes it);
* the VALUES row/column shape and its UNDEF mask (the device VALUES table
  is shape-static; only the cell contents are parameters).
"""

from __future__ import annotations

import dataclasses
import enum
import hashlib
from typing import Any, List, Tuple

from kolibrie_tpu.obs import metrics
from kolibrie_tpu.query.ast import (
    CombinedQuery,
    DeleteClause,
    InsertClause,
    IriRef,
    NumberLit,
    PatternTerm,
    PatternTriple,
    SelectQuery,
    StringLit,
    ValuesClause,
)

__all__ = [
    "fingerprint_query",
    "template_key",
    "note_cap_occupancy",
    "note_cap_retry",
    "note_scan_occupancy",
    "note_aggregate",
    "note_aggregate_tier",
    "note_scan_tiers",
    "note_range_searches",
    "note_range_search_rows",
    "occupancy_pct",
]


def occupancy_pct(rows: int, cap: int) -> float:
    """How full a template-cap slot ran: ``rows / cap`` as a percentage.
    The EXPLAIN ANALYZE renderer and the capacity telemetry share
    this so 'occupancy' means one thing everywhere.  A non-positive cap
    (degenerate/elided slot) reads as 0 rather than dividing by zero."""
    if cap <= 0:
        return 0.0
    return 100.0 * rows / cap


def _as_number(text: str) -> bool:
    try:
        float(text.strip('"'))
        return True
    except (ValueError, AttributeError):
        return False


def _k_bucket(n: int, lo: int = 8) -> int:
    c = lo
    while c < n:
        c <<= 1
    return c


def _ser_terms(triple: PatternTriple, params: List[Any]) -> tuple:
    return tuple(
        _ser(t, params) for t in (triple.subject, triple.predicate, triple.object)
    )


def _ser(node: Any, params: List[Any]) -> Any:
    """Serialize ``node`` into a hashable structure, appending constant
    leaves to ``params`` and emitting typed placeholders in their place."""
    if isinstance(node, NumberLit):
        params.append(node.value)
        return ("#num",)
    if isinstance(node, StringLit):
        params.append(node.value)
        # lowering treats numeric-looking strings as numeric comparands
        return ("#str", _as_number(node.value))
    if isinstance(node, IriRef):
        params.append(node.iri)
        return ("#iri",)
    if isinstance(node, PatternTerm):
        if node.kind == "var":
            return ("pv", node.value)
        if node.kind == "quoted":
            s, p, o = node.value  # type: ignore[misc]
            return ("pq", _ser(s, params), _ser(p, params), _ser(o, params))
        params.append(node.value)
        return ("#pt",)
    if isinstance(node, PatternTriple):
        # the predicate a pattern names stays a parameter and is structure
        # too: its scan is compiled for the rows under that predicate
        # (device_engine.template_scan_cap), so two texts of one shape that
        # name different predicates are two templates
        named = node.predicate.value if node.predicate.kind == "term" else None
        return ("triple", _ser_terms(node, params), named)
    if isinstance(node, (InsertClause, DeleteClause)):
        # triples written or removed, never scanned: their predicates size
        # nothing and every term stays a parameter
        return (
            type(node).__name__,
            tuple(_ser_terms(tr, params) for tr in node.triples),
            tuple(
                (f.name, _ser(getattr(node, f.name), params))
                for f in dataclasses.fields(node)
                if f.name != "triples"
            ),
        )
    if isinstance(node, ValuesClause):
        rows = tuple(
            tuple("U" if c is None else "#vc" for c in row) for row in node.rows
        )
        for row in node.rows:
            for c in row:
                if c is not None:
                    params.append(c)
        return ("values", tuple(node.variables), rows)
    if isinstance(node, SelectQuery):
        body = tuple(
            (f.name, _ser(getattr(node, f.name), params))
            for f in dataclasses.fields(node)
            if f.name not in ("prefixes", "limit", "offset")
        )
        if node.order_by and node.limit is not None:
            # static top-k bucket: same quantization as the ordered device path
            lim = ("kbucket", _k_bucket((node.offset or 0) + node.limit))
        else:
            lim = ("lim", node.limit is None, node.offset is None)
        params.append(node.limit)
        params.append(node.offset)
        return ("SelectQuery", body, lim)
    if dataclasses.is_dataclass(node) and not isinstance(node, type):
        return (
            type(node).__name__,
            tuple(
                (f.name, _ser(getattr(node, f.name), params))
                for f in dataclasses.fields(node)
                if f.name != "prefixes"
            ),
        )
    if isinstance(node, enum.Enum):
        return ("enum", type(node).__name__, node.value)
    if isinstance(node, dict):
        return (
            "dict",
            tuple(sorted((str(k), _ser(v, params)) for k, v in node.items())),
        )
    if isinstance(node, (list, tuple)):
        return tuple(_ser(x, params) for x in node)
    if node is None or isinstance(node, (bool, int, float, str)):
        return node
    return ("repr", repr(node))  # unknown node kinds stay fully structural


def template_key(cq: CombinedQuery) -> Tuple[Any, Tuple[Any, ...]]:
    """Return ``(structure, params)`` for a parsed query: the hashable
    template skeleton and the ordered tuple of extracted constants.

    The join-strategy routing mode (``KOLIBRIE_WCOJ``) is folded into the
    skeleton: strategy selection happens at PLAN time, so a plan cached
    under one mode must never replay after the mode flips — distinct
    fingerprints give each strategy its own slot (and device executable).
    ``KOLIBRIE_PLAN_INTERP`` joins it for the same reason: the interpreter
    routing decision is sticky per cached slot (its source state, its
    learned caps), so a mode flip must land in a fresh fingerprint.
    ``KOLIBRIE_PALLAS`` is the third member: the kernel-vs-XLA routing is
    a static argument of the compiled plan body — a mode flip must replan
    in a fresh slot, never replay a stale one.  ``KOLIBRIE_MQO``
    is the fourth: shared-prefix routing changes which engine produces a
    template's rows, so a mode flip must land in a fresh fingerprint
    (``off`` reproduces pre-MQO behavior bit-for-bit, docs/MQO.md).
    ``KOLIBRIE_STATS_ADVISOR`` is the fifth: the feedback optimizer keys
    its learned cardinalities (and its plan-generation counter) on the
    fingerprint, so a mode flip must replan in a fresh slot where ``off``
    is bitwise-inert and ``auto`` re-learns from scratch
    (docs/OPTIMIZER.md)."""
    from kolibrie_tpu.optimizer.planner import wcoj_mode  # lazy: avoids cycle
    from kolibrie_tpu.optimizer.mqo import mqo_mode
    from kolibrie_tpu.optimizer.plan_interp import plan_interp_mode
    from kolibrie_tpu.optimizer.stats_advisor import stats_advisor_mode
    from kolibrie_tpu.ops.pallas_kernels import pallas_mode

    params: List[Any] = []
    structure = (
        "stats",
        stats_advisor_mode(),
        (
            "mqo",
            mqo_mode(),
            (
                "interp",
                plan_interp_mode(),
                (
                    "pallas",
                    pallas_mode(),
                    ("wcoj", wcoj_mode(), _ser(cq, params)),
                ),
            ),
        ),
    )
    return structure, tuple(params)


def fingerprint_query(cq: CombinedQuery) -> Tuple[str, Tuple[Any, ...]]:
    """Return ``(fingerprint, params)``: a stable hex digest of the query's
    template skeleton plus the constants stripped from it."""
    structure, params = template_key(cq)
    digest = hashlib.sha1(repr(structure).encode("utf-8")).hexdigest()
    return digest, params


# ---------------------------------------------------------------------------
# capacity protocol counters (optimizer/caps.py holds the protocol itself)
# ---------------------------------------------------------------------------

_CAP_RETRIES = metrics.counter(
    "kolibrie_cap_retries_total",
    "doubled-capacity retried dispatches (overflow → re-run); a template's "
    "capacities are remembered on its store, so this stays zero in steady "
    "state",
    labels=("engine",),
)
# pre-create both engine series so a zero-retry steady state is visible
# in /metrics as an explicit 0, not an absent family
_CAP_RETRIES.labels("device")
_CAP_RETRIES.labels("sharded")


def note_cap_retry(engine: str) -> None:
    """One dispatch of ``engine`` overflowed a capacity and runs again."""
    _CAP_RETRIES.labels(engine).inc()


# what those re-runs cost: build-to-counts wall time of each re-dispatch,
# incremented by the overflow loop itself (optimizer/caps.py run_until_fits)
cap_retry_seconds = metrics.counter(
    "kolibrie_cap_retry_seconds_total",
    "wall seconds spent in doubled-capacity re-runs (build to count "
    "readback), by engine",
    labels=("engine",),
)
cap_retry_seconds.labels("device")  # the mesh path counts retries, not yet their seconds
# how the capacity rule engages: per dispatch, the join and WCOJ-level
# slots its executable was compiled for and the rows the counts read back
# (rows / slots is the occupancy; optimizer/caps.py fit_join_caps)
_CAP_SLOTS = metrics.counter(
    "kolibrie_device_cap_slots_total",
    "join and WCOJ-level slots the dispatched executables were compiled "
    "for, summed over dispatches, by engine",
    labels=("engine",),
)
_JOIN_ROWS = metrics.counter(
    "kolibrie_device_join_rows_total",
    "rows the join and WCOJ-level counts read back, summed over "
    "dispatches, by engine",
    labels=("engine",),
)
_CAP_SLOTS.labels("device")
_JOIN_ROWS.labels("device")
# what a template's calibrated start costs: wall seconds of the numpy twin
# on a template's first sight, by whether it counted this variant, counted
# it with one scan at its predicate's hottest key (hot_key), or gave up at
# the row limit (then the first device run's counts calibrate)
cap_calibrate_seconds = metrics.counter(
    "kolibrie_cap_calibrate_seconds_total",
    "wall seconds of host calibration passes (a template's first sight "
    "on a db), by outcome",
    labels=("outcome",),
)
cap_calibrate_seconds.labels("counted")
cap_calibrate_seconds.labels("hot_key")
cap_calibrate_seconds.labels("too_large")

# which arm of the capacity rule a template's first sight took, join by join
# (group tables under the same two kinds): "ceiling" where the calibration
# counted the most rows any instance of the text can give and the capacity
# is that count's, "headroom" where the count is the instances' it saw and
# the capacity is H times it (optimizer/device_engine.py fit_join_caps)
_CALIBRATED_JOINS = metrics.counter(
    "kolibrie_cap_calibrated_joins_total",
    "joins, WCOJ levels and group tables the host calibration sized on a "
    "template's first sight, by engine and by the rule's arm",
    labels=("engine", "kind"),
)
_CALIBRATED_JOINS.labels("device", "ceiling")
_CALIBRATED_JOINS.labels("device", "headroom")


def note_calibrated_caps(engine: str, ceilings: int, headroom: int) -> None:
    """A first sight sized ``ceilings`` capacities at counts no instance can
    pass and ``headroom`` at counts with room over them."""
    _CALIBRATED_JOINS.labels(engine, "ceiling").inc(ceilings)
    _CALIBRATED_JOINS.labels(engine, "headroom").inc(headroom)


def note_cap_occupancy(engine: str, slots: int, rows: int) -> None:
    """One dispatch ran ``slots`` join slots and counted ``rows`` rows."""
    _CAP_SLOTS.labels(engine).inc(slots)
    _JOIN_ROWS.labels(engine).inc(rows)


# what a template-wide scan capacity costs a variant: per dispatch, the slots
# its scans were compiled for (ScanSpec.cap: the largest key-group of the
# order's bound prefix among the rows under the predicate the scan names,
# whichever other constants the text carries) and the rows their ranges
# held (host values, read where the counts are read back)
_SCAN_SLOTS = metrics.counter(
    "kolibrie_device_scan_slots_total",
    "slots the dispatched executables' scans were compiled for (each scan "
    "as wide as the hottest key under the predicate it names), summed "
    "over dispatches, by engine",
    labels=("engine",),
)
_SCAN_ROWS = metrics.counter(
    "kolibrie_device_scan_rows_total",
    "rows the dispatched scans' ranges held (base and delta), summed over "
    "dispatches, by engine",
    labels=("engine",),
)
_SCAN_SLOTS.labels("device")
_SCAN_ROWS.labels("device")


def note_scan_occupancy(engine: str, slots: int, rows: int) -> None:
    """One dispatch's scans were ``slots`` wide and held ``rows`` rows."""
    _SCAN_SLOTS.labels(engine).inc(slots)
    _SCAN_ROWS.labels(engine).inc(rows)


# what a GROUP BY on the device costs and holds: per run of the segment
# aggregation (optimizer/device_engine.py aggregate_table), the width of the
# plan's table it sorts and the rows of it that are valid, the group capacity
# it was compiled for and the groups it returned; a run whose groups passed
# the capacity is run again at a larger one and counted as a retry
_AGG_SLOTS = metrics.counter(
    "kolibrie_device_aggregate_slots_total",
    "compiled width of the tables the device aggregations sorted, summed "
    "over their runs",
)
_AGG_ROWS = metrics.counter(
    "kolibrie_device_aggregate_rows_total",
    "valid rows of the tables the device aggregations sorted, summed over "
    "their runs",
)
_GROUP_SLOTS = metrics.counter(
    "kolibrie_device_group_slots_total",
    "group capacity the device aggregations were compiled for, summed over "
    "their runs",
)
_GROUPS = metrics.counter(
    "kolibrie_device_groups_total",
    "groups the device aggregations returned, summed over their runs",
)
_AGG_CAP_RETRIES = metrics.counter(
    "kolibrie_aggregate_cap_retries_total",
    "device aggregations run again because the groups passed the group "
    "capacity (a template's group capacity is counted on its first sight, "
    "so this stays 0 in steady state)",
)
# where a request's GROUP BY and aggregates ran: fused behind the plan on the
# device, or over the plan's rows on the host (a shape the device declines,
# a store served from the host); query.execute's path says where the request
# was routed, not this
_AGG_TIER = metrics.counter(
    "kolibrie_aggregate_total",
    "SELECTs with GROUP BY or an aggregate, by where the aggregation ran",
    labels=("tier",),
)
_AGG_TIER.labels("device")
_AGG_TIER.labels("host")


def note_aggregate(slots: int, rows: int, group_slots: int, groups: int) -> None:
    """One run of the device aggregation sorted ``slots`` slots holding
    ``rows`` rows into ``groups`` groups of ``group_slots`` compiled."""
    _AGG_SLOTS.inc(slots)
    _AGG_ROWS.inc(rows)
    _GROUP_SLOTS.inc(group_slots)
    _GROUPS.inc(groups)


def note_aggregate_retry() -> None:
    _AGG_CAP_RETRIES.inc()


def note_aggregate_tier(tier: str) -> None:
    """One SELECT's GROUP BY ran on ``tier`` (``device`` or ``host``)."""
    _AGG_TIER.labels(tier).inc()


# how often an empty delta tier is not searched: per dispatch, one a scan
# and one a WCOJ accessor, by the branch the plan body takes for its order
# (optimizer/device_engine.py _plan_body: the same host entries it uploads)
_SCAN_TIER = metrics.counter(
    "kolibrie_device_scan_tier_total",
    "scans and WCOJ accessors dispatched, by whether their order's delta "
    "tier held nothing (base_only: the base is read alone) or a row or a "
    "tombstone (two_tier: base and delta are merged)",
    labels=("tier",),
)
_SCAN_TIER.labels("base_only")
_SCAN_TIER.labels("two_tier")


def note_scan_tiers(base_only: int, two_tier: int) -> None:
    """One dispatch ran ``base_only`` + ``two_tier`` scans and accessors."""
    _SCAN_TIER.labels("base_only").inc(base_only)
    _SCAN_TIER.labels("two_tier").inc(two_tier)


# how a dispatch's WCOJ range searches were made: static per template and
# capacity set (ops/wcoj.py range_search_form reads shapes alone), counted on
# the host per dispatch, so a run says how many of its searches took which
# form at no device traffic
_RANGE_SEARCH = metrics.counter(
    "kolibrie_wcoj_range_search_total",
    "range searches of WCOJ levels dispatched, by the form their shapes "
    "select: one sort of base rows and probe tuples (sorted) or a "
    "binary-search loop of column gathers (loop)",
    labels=("form",),
)
_RANGE_SEARCH.labels("sorted")
_RANGE_SEARCH.labels("loop")


def note_range_searches(sorted_: int, loop: int) -> None:
    """One dispatch made ``sorted_`` + ``loop`` WCOJ range searches."""
    _RANGE_SEARCH.labels("sorted").inc(sorted_)
    _RANGE_SEARCH.labels("loop").inc(loop)


# the base rows those searches ran over: an accessor whose leading keys are
# constants of the text searches the window of its order that they select
# (device_engine WcojAccessor.window), any other the whole padded order
_RANGE_SEARCH_ROWS = metrics.counter(
    "kolibrie_wcoj_range_search_rows_total",
    "base rows the range searches of WCOJ levels ran over, summed over "
    "dispatches, by whether the search took the window of the order that "
    "the accessor's leading constants select (window) or the whole padded "
    "order (order)",
    labels=("extent",),
)
_RANGE_SEARCH_ROWS.labels("window")
_RANGE_SEARCH_ROWS.labels("order")


def note_range_search_rows(window: int, order: int) -> None:
    """One dispatch's WCOJ range searches ran over ``window`` rows of
    windows and ``order`` rows of whole padded orders."""
    _RANGE_SEARCH_ROWS.labels("window").inc(window)
    _RANGE_SEARCH_ROWS.labels("order").inc(order)


# what a merge join's run-bound searches were spared: per dispatch and join
# that runs the Pallas prepass, its left side's compiled width and the keys
# its blocks covered (ops/pallas_kernels.py _run_bounds), both from row
# counts the host already holds
_JOIN_SEARCH_KEYS = metrics.counter(
    "kolibrie_join_search_keys_total",
    "left keys of the merge joins' run-bound searches, summed over "
    "dispatches: the slots the left sides were compiled with (slots) and "
    "the keys the searches' blocks covered (searched)",
    labels=("what",),
)
_JOIN_SEARCH_KEYS.labels("slots")
_JOIN_SEARCH_KEYS.labels("searched")


def note_join_search_keys(slots: int, searched: int) -> None:
    """One dispatch's prepass joins were ``slots`` left slots wide and
    searched ``searched`` of their keys."""
    _JOIN_SEARCH_KEYS.labels("slots").inc(slots)
    _JOIN_SEARCH_KEYS.labels("searched").inc(searched)
