"""Device (TPU) execution mode for physical plans.

This is the path that puts the TPU *inside* the query engine: a physical
plan from :mod:`kolibrie_tpu.optimizer.planner` is lowered to a hashable
``PlanSpec`` and interpreted as ONE jitted XLA program — scans are
``dynamic_slice`` windows over the store's device-resident sorted orders,
held as a two-tier base + delta segment pair
(:meth:`ColumnarTripleStore.device_segment`) merged inside the compiled
plan so mutation batches under the delta threshold re-upload only the
small delta segment and never change shapes, joins are the static-capacity
sort-join of :func:`kolibrie_tpu.ops.device_join.join_indices`, numeric
filters are gathers over host-precomputed per-ID masks, and strings are
decoded only after the final readback.

Parity: the reference's ID-space interpreter
``streamertail_optimizer/execution/engine.rs:27-1018`` and its shared join
kernels ``shared/src/join_algorithm.rs:19-131`` — redesigned for XLA: the
whole operator tree compiles to a single device program with static shapes
(padded buffers + validity masks, capacity doubling on overflow — SURVEY §7
"hard parts"), instead of a tuple/thread-parallel interpreter.

Fully-constant patterns lower to host membership guards (zero device ops);
3+-variable join keys ride a union dense-rank composition; quoted patterns
with inner variables scan their position as a synthetic qid column and
expand it against the device-resident quoted table (a searchsorted gather
— each qid names exactly one quoted row); constant-pattern string
predicates (REGEX/CONTAINS/STRSTARTS/STRENDS) become per-ID verdict-mask
gathers, BOUND/ISTRIPLE become ID tests.  The remaining unsupported
constructs (UDFs, variable string patterns, cartesian joins,
doubly-nested quoted patterns) raise :class:`Unsupported` at lowering
time and the
caller falls back to the host numpy engine — agreement between the two
paths is tested in ``tests/test_device_engine.py``.  (BINDs never reach
the device plan: the executor applies them host-side to the readback
table, which is the right split — results are small next to the store.)

Capacity / readback protocol: join
capacities are estimated, validated by reading the true match counts once,
and cached per plan shape on the database.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from kolibrie_tpu.optimizer import plan as P
from kolibrie_tpu.ops.join import BindingTable
from kolibrie_tpu.query.ast import (
    Comparison,
    FunctionCall,
    IriRef,
    LogicalAnd,
    LogicalNot,
    LogicalOr,
    NumberLit,
    PatternTriple,
    StringLit,
    Var,
)

__all__ = [
    "Unsupported",
    "lower_plan",
    "try_device_execute",
    "execute_plan_batch",
    "device_compile_stats",
    "template_scan_cap",
]

import threading as _threading
import time as _time

from kolibrie_tpu.obs import analyze as _analyze
from kolibrie_tpu.obs import metrics as _obs_metrics
from kolibrie_tpu.obs.spans import get_baggage as _get_baggage
from kolibrie_tpu.optimizer import caps as _caps
from kolibrie_tpu.optimizer import stats_advisor as _sa
from kolibrie_tpu.optimizer.caps import CAP_FLOOR as _CAP_FLOOR
from kolibrie_tpu.optimizer.caps import fit_join_caps, group_cap_ceiling
from kolibrie_tpu.optimizer.stats import hottest_key_rows
from kolibrie_tpu.obs.spans import span as _obs_span
from kolibrie_tpu.ops import round_cap as _round_cap
from kolibrie_tpu.query import compile_cache as _cc
from kolibrie_tpu.resilience.deadline import check_deadline
from kolibrie_tpu.resilience.faultinject import fault_point


def _pad_pow2(arr: np.ndarray, fill, lo: int = 128) -> np.ndarray:
    """Pad a 1-D per-ID table to a power-of-two length with a semantically
    neutral fill value.  Per-ID operands (numeric table, filter masks,
    string ranks, quoted table) grow with the dictionary; padding keeps
    their device SHAPES stable across small mutation batches so cached
    compiled plans are reused instead of retraced."""
    cap = _round_cap(len(arr), lo)
    if cap == len(arr):
        return arr
    out = np.full(cap, fill, dtype=arr.dtype)
    out[: len(arr)] = arr
    return out

# Per-template device phase timings.  The template label is the plan
# template fingerprint carried in trace baggage by the executor —
# bounded upstream by the template cache, so cardinality is safe.
_LOWER_LAT = _obs_metrics.histogram(
    "kolibrie_device_lower_seconds",
    "plan lowering (trace + spec assembly) time by template",
    labels=("template",),
)
_DISPATCH_LAT = _obs_metrics.histogram(
    "kolibrie_device_dispatch_seconds",
    "device dispatch + convergence time by template (first observation "
    "per shape includes the XLA compile)",
    labels=("template",),
)
_COLLECT_LAT = _obs_metrics.histogram(
    "kolibrie_device_collect_seconds",
    "device→host result materialization time",
)
_AGGREGATE_LAT = _obs_metrics.histogram(
    "kolibrie_device_aggregate_seconds",
    "device GROUP BY time: sort, segment reduction and the groups' readback",
)
_DEVICE_BATCH_SIZE = _obs_metrics.histogram(
    "kolibrie_device_batch_size",
    "members per one-chip template group dispatch",
    buckets=_obs_metrics.DEFAULT_COUNT_BUCKETS,
)
# One-chip template groups (``execute_plan_batch``), once a group served:
# members over slots is the slot class's occupancy, members over dispatches
# the mean group.
_BATCH_DISPATCHES = _obs_metrics.counter(
    "kolibrie_device_batch_dispatch_total",
    "one-chip template groups served by the batch executable",
)
_BATCH_MEMBERS = _obs_metrics.counter(
    "kolibrie_device_batch_members_total",
    "live members of the one-chip template groups served",
)
_BATCH_MEMBER_SLOTS = _obs_metrics.counter(
    "kolibrie_device_batch_member_slots_total",
    "member slots the batch executables of the groups served were compiled "
    "for (the slot class of each group's live members)",
)
# Worst-case-optimal join instrumentation (emitted once per converged
# execution, from the host-read counts — no extra device traffic)
_WCOJ_LEVEL_ROWS = _obs_metrics.histogram(
    "kolibrie_wcoj_level_rows",
    "intermediate rows per WCOJ elimination level (exact, post-converge)",
    buckets=_obs_metrics.DEFAULT_COUNT_BUCKETS,
)
_WCOJ_CAP_OCCUPANCY = _obs_metrics.histogram(
    "kolibrie_wcoj_cap_occupancy",
    "rows/capacity ratio per WCOJ level (cap headroom health)",
    buckets=(0.01, 0.05, 0.1, 0.25, 0.5, 0.75, 0.9, 1.0),
)
_WCOJ_PROBES = _obs_metrics.counter(
    "kolibrie_wcoj_probes_total",
    "candidate existence probes issued by WCOJ levels (cap x accessors)",
)


class Unsupported(Exception):
    """Plan construct the device path cannot express (host fallback)."""


# The numpy twin gives up past this many rows in one scan, join or WCOJ
# level (the guard of dist_query's calibration): materializing more on the
# host just to size device buffers costs the memory static capacities exist
# to avoid.  The first device run's counts calibrate instead.
_CALIBRATE_ROW_LIMIT = 8_000_000


# ---------------------------------------------------------------------------
# Frozen spec nodes (jit static argument — must be hashable)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ScanSpec:
    order_idx: int  # into PlanSpec.orders
    scan_idx: int  # into the (n_scans, 4) [lo_b, n_b, lo_d, n_d] scalars
    out_vars: tuple  # ((var, pos), ...) pos: 0=s 1=p 2=o canonical
    eq_pairs: tuple  # ((pos_a, pos_b), ...) repeated-variable constraints
    cap: int
    # canonical positions of the two order columns packed as the base/delta
    # merge key — the first unbound perm column (and its successor), so the
    # merged stream stays sorted exactly where the rsorted joins require it
    key_pos: tuple = (0, 1)


@dataclass(frozen=True)
class QuotedExpandSpec:
    """Expand a column of quoted-triple IDs against the device-resident
    quoted table (qid-sorted): bind inner variables, enforce inner
    constants / repeats / collisions with already-bound variables.  Each
    qid maps to exactly one quoted row, so the expansion is a searchsorted
    gather, not a join (host twin: ``optimizer/engine.py::_join_quoted``,
    ref ``execution/engine.rs:1159``)."""

    child: object
    qvar: str  # synthetic column of qids produced by the scan
    out_vars: tuple  # ((var, inner_pos 0..2), ...) fresh inner bindings
    const_checks: tuple  # ((inner_pos, const_id), ...)
    eq_checks: tuple  # ((inner_pos, bound_var), ...) incl. repeats


@dataclass(frozen=True)
class ValuesSpec:
    values_idx: int
    vars: tuple
    n: int


@dataclass(frozen=True)
class JoinSpec:
    left: object
    right: object
    key_vars: tuple  # 1 or 2 variable names
    join_idx: int  # into the capacity table / counts output
    cap: int
    rsorted: bool = False  # right key column pre-sorted by its scan order


@dataclass(frozen=True)
class WcojAccessor:
    """One pattern's sorted-order view at a WCOJ level: the order whose
    perm prefix is exactly the pattern's bound positions (constants +
    already-eliminated variables) followed by the level variable, so the
    candidate column comes out sorted and range-probeable.

    ``key_srcs`` supply the bound-prefix key values in PERM order —
    ``('u', param_idx)`` reads the traced uint32 parameter vector (query
    constants, incl. the never-an-ID sentinel for unknown terms),
    ``('v', var)`` reads an already-eliminated variable's column.
    ``key_pos``/``val_pos`` are canonical column positions (0=s 1=p 2=o).

    ``window``: the base rows a range search of this accessor runs over,
    where its leading keys are constants (:attr:`lead`): the hottest group
    of those keys in the frozen base, on the capacity ladder
    (``LoweredPlan._template_window_caps``; a template property, as
    ``ScanSpec.cap`` is).  0: no constant leads, or its rows are the order's
    padded length anyway, and the searches run over the whole order."""

    order_idx: int
    key_srcs: tuple
    key_pos: tuple
    val_pos: int
    window: int = 0

    @property
    def lead(self) -> int:
        """How many of the keys, from the first, are constants of the text:
        the same value for every probe tuple."""
        n = 0
        for src in self.key_srcs:
            if src[0] != "u":
                break
            n += 1
        return n

    @property
    def lead_predicate(self) -> Optional[int]:
        """The parameter index of the predicate among the leading constants
        (the key at canonical position 1), ``None`` where none names one:
        what the window's width is read by."""
        for src, pos in zip(self.key_srcs[: self.lead], self.key_pos):
            if pos == 1:
                return src[1]
        return None


@dataclass(frozen=True)
class WcojLevel:
    """Eliminate one variable: candidates come from the accessor with the
    smallest raw sorted-range count (leapfrog's "smallest iterator leads"),
    deduplicated to first-of-run, validated by live-existence probes
    against EVERY accessor.  Shares the join capacity/counts protocol —
    ``join_idx`` indexes the counts tuple and the convergence cap table."""

    var: str
    join_idx: int
    cap: int
    accessors: tuple


@dataclass(frozen=True)
class WcojSpec:
    """Worst-case-optimal multiway join over a whole basic graph pattern:
    one :class:`WcojLevel` per variable, in elimination order.  Intermediate
    row counts are bounded by each prefix join's OUTPUT (AGM-style), never
    by a pairwise product — the point of routing cyclic BGPs here."""

    levels: tuple


@dataclass(frozen=True)
class UnionSpec:
    """UNION group: concatenation of branch tables over the union of their
    variables, a branch's missing columns filled with the UNBOUND (0)
    sentinel (host twin: the executor's branch-normalize + concat).
    Capacity = sum of branch capacities; joins into the main tree like any
    other table node."""

    children: Tuple[object, ...]
    vars: Tuple[str, ...]


@dataclass(frozen=True)
class LeftOuterSpec:
    """OPTIONAL: matches of left⋈right plus unmatched left rows with
    UNBOUND right-only columns (host twin ``ops/join.py::
    left_outer_join_tables``).  Carries a join capacity for the matching
    part (validated by the shared convergence protocol); output capacity =
    join cap + left capacity."""

    left: object
    right: object
    key_vars: Tuple[str, ...]
    join_idx: int
    cap: int


@dataclass(frozen=True)
class AntiJoinSpec:
    """MINUS / query-NAF: keep ``left`` rows with NO ``right`` match on the
    shared variables (host twin ``ops/join.py::anti_join_tables``).  Output
    columns/capacity are the left child's; the membership test is one sort
    + searchsorted over the right keys — validity only shrinks, so no
    capacity of its own to converge."""

    left: object
    right: object
    key_vars: Tuple[str, ...]


@dataclass(frozen=True)
class FilterSpec:
    child: object
    expr: object


@dataclass(frozen=True)
class MaskRef:
    """Per-ID boolean mask gather (host-precomputed numeric/string filter)."""

    mask_idx: int
    var: str


@dataclass(frozen=True)
class StrMaskRef:
    """String-predicate verdict gathers (REGEX/CONTAINS/STRSTARTS/STRENDS
    against a constant pattern): dictionary IDs read one host-precomputed
    mask, quoted IDs (bit 31) a second one built over the quoted store —
    matching the host's decode-then-test semantics for every reachable
    ID."""

    dict_idx: int
    quoted_idx: int
    var: str


@dataclass(frozen=True)
class QuotedCheck:
    """ISTRIPLE(?v): bit-31 test on the ID column."""

    var: str


@dataclass(frozen=True)
class IdCmp:
    """ID equality against a runtime parameter: the constant lives in the
    uint32 parameter vector (``uparams[param_idx]``), NOT in the spec —
    ``?v = <iri>`` and ``?v = <other-iri>`` share one compiled program."""

    op: str  # '=' | '!='
    var: str
    param_idx: int


@dataclass(frozen=True)
class NumConstCmp:
    """Numeric compare of a variable's value against a runtime parameter
    (``fparams[param_idx]``, f64).  Replaces the host-precomputed per-ID
    :class:`MaskRef` masks for constant numeric filters: same semantics as
    :func:`numeric_filter_mask` (NaN = non-numeric, always excluded) but
    the constant is a traced operand, so ``?age > 30`` and ``?age > 40``
    are ONE executable — and the O(dictionary) host mask build per
    constant disappears."""

    op: str
    var: str
    param_idx: int


@dataclass(frozen=True)
class NumCmp:
    """Numeric compare between two variables' values (f64 gather)."""

    op: str
    lvar: str
    rvar: str


@dataclass(frozen=True)
class BoolNode:
    kind: str  # 'and' | 'or' | 'not'
    args: tuple


@dataclass(frozen=True)
class PlanSpec:
    root: object
    out_vars: tuple
    orders: tuple  # order names aligned with the order_arrays input
    tag: int = 0  # calibration marker: distinct value → distinct executable


# ---------------------------------------------------------------------------
# Jitted interpreter
# ---------------------------------------------------------------------------


# Device→host readback audit: every place the engine forces a transfer
# calls _note_fetch, so the analyze regression test can pin the exact
# per-execute fetch count and assert instrumentation adds none on the
# hot path (and exactly one under an active analyze capture).
_FETCHES: Dict[str, int] = {}


def _note_fetch(site: str) -> None:
    _FETCHES[site] = _FETCHES.get(site, 0) + 1


def fetch_counters() -> Dict[str, int]:
    return dict(_FETCHES)


# Host→device upload audit, the twin of the one above: every device array a
# build makes is made by :func:`_upload` (an explicit transfer) or
# :func:`_device_zeros` (a device computation), so a test can pin what a
# warmed dispatch sends up before its program: nothing.  What a request does
# change (scan ranges, tiers, the two parameter vectors) travels with the jit
# call as numpy arguments and is no array of the build's.
_BUILD_PUTS = _obs_metrics.counter(
    "kolibrie_device_build_puts_total",
    "device arrays the builds of device dispatches made before their "
    "programs: explicit host-to-device transfers (transfer: an order's, a "
    "table's or a mask's upload) and device computations (compute)",
    labels=("what",),
)
_PUTS: Dict[str, int] = {"transfer": 0, "compute": 0}
for _what in _PUTS:
    _BUILD_PUTS.labels(_what)


def _note_put(what: str) -> None:
    _PUTS[what] += 1
    _BUILD_PUTS.labels(what).inc()


_note_transfer = partial(_note_put, "transfer")


def build_put_counters() -> Dict[str, int]:
    return dict(_PUTS)


def _upload(host, dtype=None):
    """One explicit host→device transfer of ``host``, counted."""
    import jax.numpy as jnp

    _note_transfer()
    return jnp.asarray(host, dtype=dtype)


# the placeholders a template that reads no number and names no quoted
# triple is called with, by numpy dtype: constants, made at first use and kept
_DEVICE_ZEROS: Dict[type, object] = {}


def _device_zeros(dtype):
    """The process's one ``zeros(1, dtype)`` on the device."""
    import jax.numpy as jnp

    arr = _DEVICE_ZEROS.get(dtype)
    if arr is None:
        _note_put("compute")
        with jax.enable_x64(True):
            arr = _DEVICE_ZEROS[dtype] = jnp.zeros(1, dtype=dtype)
    return arr


# The children of ``device.dispatch``, shared by the solo and the group
# path so both split the same way (docs/OBSERVABILITY.md "Span taxonomy").


def _build_traced(lowered, tag: int, operands: bool = True):
    """``lowered.build(tag, operands)`` under ``device.build``; ``h2d_bytes``
    where the store uploaded a segment (an order's first use after a base
    merge)."""
    from kolibrie_tpu.core.store import h2d_bytes_total

    with _obs_span("device.build") as sp:
        before = h2d_bytes_total() if sp is not None else 0
        built = lowered.build(tag, operands)
        uploaded = h2d_bytes_total() - before if sp is not None else 0
        if uploaded:
            sp.attrs["h2d_bytes"] = int(uploaded)
    return built


def _enqueue_traced(entry, *args):
    """Call a jit entry point under ``device.enqueue``: Python dispatch and
    argument handling until the call returns (asynchronously), plus the trace
    and the compile or cache load where the shape is new (``compiled=1``: the
    call left a first-sight record, ``compile.*`` children beside it)."""
    with _obs_span("device.enqueue") as sp:
        out = _cc.call(entry, *args)
        if sp is not None:
            sp.attrs["compiled"] = int(_cc.last_sight() is not None)
    return out


def _read_counts(out, counts, attempt: int, to_host):
    """Wait for the program, then read the join counts back.  The wait is its
    own span so device time and the readback round trips (one a join) read
    apart; one executable's outputs become ready together, so it adds no
    synchronization the readback did not already imply."""
    with _obs_span("device.wait", attempt=attempt):
        jax.block_until_ready(out)
    with _obs_span("device.counts", attempt=attempt, n=len(counts)):
        return [to_host(c) for c in counts]


def _pack_key(cols: List, valid, pad_sentinel):
    import jax.numpy as jnp

    if len(cols) == 1:
        key = cols[0].astype(jnp.uint64)
    else:
        key = (cols[0].astype(jnp.uint64) << jnp.uint64(32)) | cols[1].astype(
            jnp.uint64
        )
    return jnp.where(valid, key, jnp.uint64(pad_sentinel))




def _base_window(col, lo, cap: int):
    """Rows ``lo .. lo + cap`` of a base column as one ``dynamic_slice`` (a
    copy; gathering the same rows by index took 68 of the 184 ms of device
    time a LUBM lookups cycle had left on a v5e: PERF.md section 6, PR 31).
    ``dynamic_slice`` clamps its start so the window fits the column; a
    window that would run past the padded end is rotated back into place,
    and what it wraps around to lies beyond the scan's rows, which the
    caller masks."""
    import jax.numpy as jnp
    from jax import lax

    n = col.shape[0]
    if cap > n:
        col, n = jnp.pad(col, (0, cap - n)), cap
    start = jnp.minimum(lo, n - cap)
    return jnp.roll(lax.dynamic_slice(col, (start,), (cap,)), start - lo)


def _plan_body(
    spec: PlanSpec,
    order_arrays,
    scalars,
    tiers,
    masks,
    values,
    numf,
    quoted,
    params,
    use_pallas=False,
):
    import jax.numpy as jnp
    from jax import lax

    from kolibrie_tpu.ops.device_join import _LPAD, _RPAD, join_indices

    uparams, fparams = params
    counts: List = []

    def delta_holds_nothing(order_idx):
        """An empty delta tier is not searched: where an order's delta
        segment holds no row and no tombstone, its scans and WCOJ probes
        read the base alone (``lax.cond`` on this predicate; both sites
        give what the two-tier branch gives for an empty delta, bit for
        bit).  ``tiers[i]`` is order i's delta rows + tombstones as the
        host assembled them with the segments (``LoweredPlan._assemble``),
        a traced operand and neither a shape nor a static argument:

        - one executable a template, as before: a static flag or a
          zero-length delta shape would double the executables and put a
          compile into the first request after the first write; here that
          write flips a scalar;
        - a template group shares it: ``_run_plan_batch`` loops over the
          members' ``scalars`` and ``params`` and hands every member the
          one store, its segments and this entry with them;
        - no option: it is one algorithm whose second input is empty, and
          the code sees that in its input.
        """
        return tiers[order_idx] == 0

    def delta_or_zeros(order_idx, like, probe):
        """``probe()``, the ranges a WCOJ accessor finds in its order's delta
        rows and tombstones, shaped like the base's ranges ``like``: all
        zero, and not searched, where the tier holds nothing."""
        zeros = jax.tree.map(jnp.zeros_like, like)
        return lax.cond(delta_holds_nothing(order_idx), lambda: zeros, probe)

    # EXPLAIN ANALYZE operator stats: key -> device scalar, computed from
    # sums the operators already materialize, so the vector rides the
    # result transfer for free.  Keys are stable across the device walk,
    # the numpy twin in host_execute, and the describe() renderer:
    # indexed nodes use their plan index (scan3, join0, optional1,
    # values0, wcoj2:cand/:dedup/:live); index-less nodes (filter, anti,
    # union, quoted) use a PRE-ORDER occurrence counter assigned at node
    # entry, before children are walked — all three walks must agree.
    stats: Dict = {}
    seq = {"filter": 0, "anti": 0, "union": 0, "quoted": 0}

    def eval_expr(expr, cols, valid):
        if isinstance(expr, MaskRef):
            m = masks[expr.mask_idx]
            ids = cols[expr.var]
            return m[jnp.minimum(ids, m.shape[0] - 1)]
        if isinstance(expr, StrMaskRef):
            from kolibrie_tpu.core.dictionary import QUOTED_BIT

            ids = cols[expr.var]
            dm = masks[expr.dict_idx]
            qm = masks[expr.quoted_idx]
            isq = (ids & jnp.uint32(QUOTED_BIT)) != 0
            dv = dm[jnp.minimum(ids, dm.shape[0] - 1)]
            qidx = ids & jnp.uint32(~QUOTED_BIT & 0xFFFFFFFF)
            qv = qm[jnp.minimum(qidx, qm.shape[0] - 1)]
            return jnp.where(isq, qv, dv)
        if isinstance(expr, QuotedCheck):
            from kolibrie_tpu.core.dictionary import QUOTED_BIT

            return (cols[expr.var] & jnp.uint32(QUOTED_BIT)) != 0
        if isinstance(expr, IdCmp):
            eq = cols[expr.var] == uparams[expr.param_idx]
            return eq if expr.op == "=" else ~eq
        if isinstance(expr, NumConstCmp):
            vals = numf[jnp.minimum(cols[expr.var], numf.shape[0] - 1)]
            c = fparams[expr.param_idx]
            op = expr.op
            if op == "=":
                res = vals == c
            elif op == "!=":
                res = vals != c
            elif op == "<":
                res = vals < c
            elif op == "<=":
                res = vals <= c
            elif op == ">":
                res = vals > c
            else:
                res = vals >= c
            return res & ~jnp.isnan(vals)
        if isinstance(expr, NumCmp):
            a = numf[jnp.minimum(cols[expr.lvar], numf.shape[0] - 1)]
            b = numf[jnp.minimum(cols[expr.rvar], numf.shape[0] - 1)]
            ok = ~(jnp.isnan(a) | jnp.isnan(b))
            op = expr.op
            if op == "=":
                res = a == b
            elif op == "!=":
                res = a != b
            elif op == "<":
                res = a < b
            elif op == "<=":
                res = a <= b
            elif op == ">":
                res = a > b
            else:
                res = a >= b
            if op in ("=", "!="):
                ideq = cols[expr.lvar] == cols[expr.rvar]
                idres = ideq if op == "=" else ~ideq
                return jnp.where(ok, res, idres)
            return res & ok
        if isinstance(expr, BoolNode):
            if expr.kind == "not":
                return ~eval_expr(expr.args[0], cols, valid)
            m = eval_expr(expr.args[0], cols, valid)
            for a in expr.args[1:]:
                m2 = eval_expr(a, cols, valid)
                m = (m & m2) if expr.kind == "and" else (m | m2)
            return m
        raise TypeError(f"unknown filter spec {expr!r}")

    def node_key(node) -> str:
        """The node's EXPLAIN ANALYZE key, assigned at node entry."""
        if isinstance(node, ScanSpec):
            return f"scan{node.scan_idx}"
        if isinstance(node, ValuesSpec):
            return f"values{node.values_idx}"
        if isinstance(node, JoinSpec):
            return f"join{node.join_idx}"
        if isinstance(node, LeftOuterSpec):
            return f"optional{node.join_idx}"
        if isinstance(node, WcojSpec):
            return f"wcoj{node.levels[0].join_idx}"
        for kind, cls in (
            ("filter", FilterSpec),
            ("anti", AntiJoinSpec),
            ("union", UnionSpec),
            ("quoted", QuotedExpandSpec),
        ):
            if isinstance(node, cls):
                seq[kind] += 1
                return f"{kind}{seq[kind] - 1}"
        raise TypeError(f"unknown plan spec node {node!r}")

    def eval_node(node):
        # the key names the node's ops in a device profile too: the scope
        # path is each op's ``tf_op`` stat (metadata only, the HLO is the same)
        skey = node_key(node)
        with jax.named_scope(skey):
            return eval_op(node, skey)

    def eval_op(node, skey):
        if isinstance(node, ScanSpec):
            # Two-segment scan: a window over the FROZEN base order (with
            # tombstoned rows masked out) merged with a window over the
            # small delta order, entirely inside the compiled plan.  Shapes
            # depend only on (base cap, delta cap), so mutation batches
            # under the delta threshold re-upload the delta operand without
            # recompiling.  Each live row's output slot is its rank in the
            # two-way merge (base before delta on key ties), which keeps
            # the merge-key column sorted with prefix validity — the exact
            # contract the rsorted merge joins rely on.
            bcols, dcols, del_pos = order_arrays[node.order_idx]
            lo_b = scalars[node.scan_idx, 0]
            n_b = scalars[node.scan_idx, 1]
            lo_d = scalars[node.scan_idx, 2]
            n_d = scalars[node.scan_idx, 3]
            cap = node.cap
            dcap = del_pos.shape[0]
            ar = jnp.arange(cap, dtype=jnp.int32)
            inb = ar < n_b
            need = sorted(
                {pos for _, pos in node.out_vars}
                | {pos for pair in node.eq_pairs for pos in pair}
            )

            def base_only():
                # the merge's answer for an empty delta: rows lo_b ..
                # lo_b + n_b of the base, in place, zeros beyond them
                return (
                    tuple(
                        jnp.where(inb, _base_window(bcols[pos], lo_b, cap), 0)
                        for pos in need
                    ),
                    n_b,
                )

            def two_tier():
                # Every output slot finds its source row (a gather); the
                # delta rows and the tombstones, of which there are few,
                # are the tables searched.  Scattering the window's rows to
                # their ranks instead cost a sort a column at a wide scan
                # (what the TPU compiler makes of a scatter past a million
                # slots): three quarters of a template's compile time and
                # of its executable's bytes, in a branch that a store
                # without writes never takes (PERF.md section 6, PR 40).
                ard = jnp.arange(dcap, dtype=jnp.int32)
                src_d = jnp.clip(lo_d + ard, 0, dcap - 1)
                ind = ard < n_d
                k0, k1 = node.key_pos
                sent = jnp.uint64(0xFFFFFFFFFFFFFFFF)

                def packed(c0, c1, live):
                    key = (c0.astype(jnp.uint64) << jnp.uint64(32)) | c1.astype(
                        jnp.uint64
                    )
                    return jnp.where(live, key, sent)

                # deleted rows KEEP their real key (the window stays
                # sorted); only rows beyond the window go sentinel
                bkey = packed(
                    _base_window(bcols[k0], lo_b, cap),
                    _base_window(bcols[k1], lo_b, cap),
                    inb,
                )
                dkey = packed(dcols[k0][src_d], dcols[k1][src_d], ind)

                def dead_before(pos):
                    # tombstones (sorted base ROW POSITIONS, one u32 word)
                    # at positions under ``pos``
                    return jnp.searchsorted(
                        del_pos, pos.astype(jnp.uint32), side="left"
                    ).astype(jnp.int32)

                t0 = dead_before(lo_b)
                n_del = dead_before(lo_b + jnp.minimum(n_b, cap)) - t0
                # a delta row's slot: the delta rows before it and the live
                # base rows whose key is at most its own (base before delta
                # on key ties)
                ib = jnp.searchsorted(bkey, dkey, side="right").astype(jnp.int32)
                far = jnp.int32(np.iinfo(np.int32).max)
                pos_d = jnp.where(
                    ind, ard + ib - (dead_before(lo_b + ib) - t0), far
                )
                # a slot's source: the delta row whose slot it is, else the
                # live base row of rank ``slot - delta rows before it``
                k = jnp.searchsorted(pos_d, ar, side="right").astype(jnp.int32)
                kd = jnp.clip(k - 1, 0, dcap - 1)
                from_delta = (k > 0) & (pos_d[kd] == ar)
                rank = ar - k
                # live rows of the window before its t-th tombstone; the row
                # of rank m lies past the tombstones that have at most m
                in_win = (ard >= t0) & (ard < t0 + n_del)
                live_before = jnp.where(
                    ard < t0,
                    -1,
                    jnp.where(
                        in_win,
                        (del_pos - lo_b.astype(jnp.uint32)).astype(jnp.int32)
                        - (ard - t0),
                        far,
                    ),
                )
                dead = (
                    jnp.searchsorted(live_before, rank, side="right").astype(
                        jnp.int32
                    )
                    - t0
                )
                src_b = jnp.clip(lo_b + rank + dead, 0, bcols[0].shape[0] - 1)
                row_d = jnp.clip(lo_d + kd, 0, dcap - 1)
                n_out = (n_b - n_del) + n_d
                live = ar < n_out
                return (
                    tuple(
                        jnp.where(
                            live,
                            jnp.where(
                                from_delta, dcols[pos][row_d], bcols[pos][src_b]
                            ),
                            0,
                        )
                        for pos in need
                    ),
                    n_out,
                )

            merged, n_live = lax.cond(
                delta_holds_nothing(node.order_idx), base_only, two_tier
            )
            raw = dict(zip(need, merged))
            valid = ar < n_live
            for a, b in node.eq_pairs:
                valid = valid & (raw[a] == raw[b])
            cols = {var: raw[pos] for var, pos in node.out_vars}
            n = jnp.sum(valid)
            stats[skey] = n
            return cols, valid, n
        if isinstance(node, QuotedExpandSpec):
            from kolibrie_tpu.core.dictionary import QUOTED_BIT

            cols, valid, _ = eval_node(node.child)
            qid_sorted, qs, qp, qo = quoted
            qcol = cols.pop(node.qvar)
            pos = jnp.searchsorted(qid_sorted, qcol)
            posc = jnp.clip(pos, 0, qid_sorted.shape[0] - 1)
            valid = (
                valid
                & (qid_sorted[posc] == qcol)
                & ((qcol & jnp.uint32(QUOTED_BIT)) != 0)
            )
            inner = (qs[posc], qp[posc], qo[posc])
            for ipos, pidx in node.const_checks:
                valid = valid & (inner[ipos] == uparams[pidx])
            for var, ipos in node.out_vars:
                cols[var] = inner[ipos]
            for ipos, var in node.eq_checks:
                valid = valid & (inner[ipos] == cols[var])
            n = jnp.sum(valid)
            stats[skey] = n
            return cols, valid, n
        if isinstance(node, ValuesSpec):
            cols = {v: values[node.values_idx][i] for i, v in enumerate(node.vars)}
            valid = jnp.ones(node.n, dtype=bool)
            stats[skey] = jnp.int32(node.n)
            return cols, valid, jnp.int32(node.n)
        if isinstance(node, JoinSpec):
            from kolibrie_tpu.ops.device_join import join_indices_presorted

            lcols, lvalid, _ = eval_node(node.left)
            rcols, rvalid, _ = eval_node(node.right)
            if node.rsorted and use_pallas:
                # right child is a bare range scan whose order presents the
                # single u32 key column sorted with prefix validity — the
                # exact contract of the Pallas merge-join tile kernel
                # (ops/pallas_kernels.py), which is the engine's production
                # join on TPU (BASELINE north star: physical operators as
                # Pallas kernels).
                from kolibrie_tpu.ops.pallas_kernels import merge_join_indices

                kv = node.key_vars[0]
                li, ri, valid, total = merge_join_indices(
                    lcols[kv], rcols[kv], node.cap, lvalid, rvalid
                )
                # kernel outputs are padded to whole tiles; matches are a
                # prefix, so slicing restores the node's static capacity
                li, ri, valid = li[: node.cap], ri[: node.cap], valid[: node.cap]
            elif node.rsorted:
                # same join, pure-XLA formulation (searchsorted + cumsum
                # expansion) — used off-TPU where interpreted Pallas would
                # be slow, and overridable via KOLIBRIE_PALLAS
                lkey = _pack_key([lcols[v] for v in node.key_vars], lvalid, _LPAD)
                rkey = _pack_key([rcols[v] for v in node.key_vars], rvalid, _RPAD)
                li, ri, valid, total = join_indices_presorted(
                    lkey, rkey, node.cap
                )
            else:
                lc = [lcols[v] for v in node.key_vars]
                rc = [rcols[v] for v in node.key_vars]
                if len(node.key_vars) > 2:
                    # 3+ shared variables: union dense-rank composition
                    from kolibrie_tpu.ops.device_join import pack_key_multi

                    lkey, rkey = pack_key_multi(lc, rc, lvalid, rvalid)
                else:
                    lkey = _pack_key(lc, lvalid, _LPAD)
                    rkey = _pack_key(rc, rvalid, _RPAD)
                if use_pallas:
                    # unsorted keys still ride the tile kernel via the
                    # dense-rank prepass (see ranked_merge_join_indices)
                    from kolibrie_tpu.ops.pallas_kernels import (
                        ranked_merge_join_indices,
                    )

                    li, ri, valid, total = ranked_merge_join_indices(
                        lkey, rkey, node.cap
                    )
                else:
                    li, ri, valid, total = join_indices(lkey, rkey, node.cap)
            counts.append(total)
            stats[skey] = jnp.sum(valid)
            out = {}
            for v, c in lcols.items():
                out[v] = jnp.where(valid, c[li], 0)
            for v, c in rcols.items():
                if v not in out:
                    out[v] = jnp.where(valid, c[ri], 0)
            return out, valid, total
        if isinstance(node, FilterSpec):
            cols, valid, _ = eval_node(node.child)
            mask = eval_expr(node.expr, cols, valid)
            valid = valid & mask
            n = jnp.sum(valid)
            stats[skey] = n
            return cols, valid, n
        if isinstance(node, AntiJoinSpec):
            lcols, lvalid, _ = eval_node(node.left)
            rcols, rvalid, _ = eval_node(node.right)
            lc = [lcols[v] for v in node.key_vars]
            rc = [rcols[v] for v in node.key_vars]
            if len(node.key_vars) > 2:
                from kolibrie_tpu.ops.device_join import pack_key_multi

                lkey, rkey = pack_key_multi(lc, rc, lvalid, rvalid)
            else:
                lkey = _pack_key(lc, lvalid, _LPAD)
                rkey = _pack_key(rc, rvalid, _RPAD)
            rs = jnp.sort(rkey)
            pos = jnp.clip(jnp.searchsorted(rs, lkey), 0, rs.shape[0] - 1)
            valid = lvalid & (rs[pos] != lkey)
            n = jnp.sum(valid)
            stats[skey] = n
            return lcols, valid, n
        if isinstance(node, UnionSpec):
            parts = [eval_node(ch) for ch in node.children]
            cols = {}
            for v in node.vars:
                segs = []
                for ccols, cvalid, _ in parts:
                    if v in ccols:
                        segs.append(ccols[v])
                    else:  # branch doesn't bind v: UNBOUND (0) fill
                        segs.append(
                            jnp.zeros(cvalid.shape[0], dtype=jnp.uint32)
                        )
                cols[v] = jnp.concatenate(segs)
            valid = jnp.concatenate([p[1] for p in parts])
            n = jnp.sum(valid)
            stats[skey] = n
            return cols, valid, n
        if isinstance(node, LeftOuterSpec):
            lcols, lvalid, _ = eval_node(node.left)
            rcols, rvalid, _ = eval_node(node.right)
            lc = [lcols[v] for v in node.key_vars]
            rc = [rcols[v] for v in node.key_vars]
            if len(node.key_vars) > 2:
                from kolibrie_tpu.ops.device_join import pack_key_multi

                lkey, rkey = pack_key_multi(lc, rc, lvalid, rvalid)
            else:
                lkey = _pack_key(lc, lvalid, _LPAD)
                rkey = _pack_key(rc, rvalid, _RPAD)
            li, ri, mvalid, total = join_indices(lkey, rkey, node.cap)
            counts.append(total)
            rs = jnp.sort(rkey)
            pos = jnp.clip(jnp.searchsorted(rs, lkey), 0, rs.shape[0] - 1)
            keep = lvalid & (rs[pos] != lkey)  # unmatched left rows
            out = {}
            for v, c in lcols.items():
                out[v] = jnp.concatenate([jnp.where(mvalid, c[li], 0), c])
            for v, c in rcols.items():
                if v not in out:  # right-only: UNBOUND on the kept side
                    out[v] = jnp.concatenate(
                        [
                            jnp.where(mvalid, c[ri], 0),
                            jnp.zeros(lvalid.shape[0], dtype=jnp.uint32),
                        ]
                    )
            valid = jnp.concatenate([mvalid, keep])
            n = jnp.sum(valid)
            stats[skey] = n
            return out, valid, n
        if isinstance(node, WcojSpec):
            # Variable-at-a-time leapfrog over the two-tier sorted orders.
            # Counts are RAW range sizes (tombstoned/duplicate rows
            # included): a sound capacity bound whose total is identical in
            # the numpy twin, so calibration and convergence share the one
            # protocol.  Liveness and dedup ride per-slot probes:
            #   valid = in_range & real & first_of_run(chosen segment)
            #         & AND_r(live_exists_r) & (base_slot | no_base_raw)
            # where the last term keeps a value enumerated from the chosen
            # accessor's delta from double-counting when its base also has
            # raw (possibly all-tombstoned) copies — the base slot is the
            # unique representative, made live by the delta via the
            # existence probe.
            from kolibrie_tpu.ops.prefix import prefix_count
            from kolibrie_tpu.ops.wcoj import range_search, slot_rows

            SENT = jnp.uint32(0xFFFFFFFF)
            wcols: Dict = {}
            wvalid = jnp.ones(1, dtype=bool)
            def lead_of(a):
                # the constants that lead the accessor's keys: what its
                # window of the order is found by (ops/wcoj.py key_window)
                return tuple(uparams[src[1]] for src in a.key_srcs[: a.lead])

            def eval_level(lv, wcols, wvalid):
                with jax.named_scope("probe"):
                    pcap = wvalid.shape[0]
                    segs = [order_arrays[a.order_idx] for a in lv.accessors]
                    probes = []
                    for a, (bcols, dcols, del_pos) in zip(lv.accessors, segs):
                        keys = []
                        sent = jnp.zeros(pcap, dtype=bool)
                        for src in a.key_srcs:
                            if src[0] == "u":
                                k = jnp.broadcast_to(uparams[src[1]], (pcap,))
                            else:
                                k = wcols[src[1]]
                            sent = sent | (k == SENT)
                            keys.append(k)
                        if keys:
                            kt = tuple(keys)
                            bsort = tuple(bcols[p] for p in a.key_pos)
                            dsort = tuple(dcols[p] for p in a.key_pos)
                            # lo and hi of every probe tuple in one search,
                            # by a gather loop or by one sort as the shapes
                            # say (ops/wcoj.py range_search_form; the same
                            # int32 arrays either way, shared by the XLA and
                            # Pallas paths), over the rows its constant keys
                            # select where the accessor has a window
                            bl, bh = range_search(bsort, kt, lead_of(a), a.window)
                            dl, dh = delta_or_zeros(
                                a.order_idx,
                                (bl, bh),
                                lambda dsort=dsort, kt=kt: range_search(dsort, kt),
                            )
                        else:
                            # unbound accessor: the whole live prefix (padding
                            # is all-sentinel and sorts last; the order was
                            # picked so the level variable IS the first column)
                            bl = jnp.zeros(pcap, dtype=jnp.int32)
                            dl = jnp.zeros(pcap, dtype=jnp.int32)
                            nb0 = jnp.searchsorted(
                                bcols[a.val_pos], SENT, side="left"
                            ).astype(jnp.int32)
                            nd0 = delta_or_zeros(
                                a.order_idx,
                                nb0,
                                lambda dv=dcols[a.val_pos]: jnp.searchsorted(
                                    dv, SENT, side="left"
                                ).astype(jnp.int32),
                            )
                            bh = jnp.broadcast_to(nb0, (pcap,))
                            dh = jnp.broadcast_to(nd0, (pcap,))
                        probes.append((keys, sent, bl, bh, dl, dh))
                    cntm = jnp.stack(
                        [
                            jnp.where(sent, 0, (bh - bl) + (dh - dl))
                            for (_k, sent, bl, bh, dl, dh) in probes
                        ]
                    )
                    choice = jnp.argmin(cntm, axis=0)
                    cnt = jnp.where(wvalid, jnp.min(cntm, axis=0), 0)
                    total = jnp.sum(cnt.astype(jnp.int64))
                    counts.append(total)
                    stats[f"wcoj{lv.join_idx}:cand"] = total
                with jax.named_scope("expand"):
                    cap = lv.cap
                    cum = prefix_count(cnt)
                    slot = jnp.arange(cap, dtype=jnp.int32)
                    # a scatter and a prefix count, no search (ops/wcoj.py)
                    row = slot_rows(cum, cap)
                    row_c = jnp.clip(row, 0, pcap - 1)
                    kk = slot - (cum - cnt)[row_c]
                    in_range = slot.astype(jnp.int64) < total
                    ch = choice[row_c]
                    # per-accessor slot operands (XLA gathers — shared by both
                    # formulations below)
                    sel = []
                    for a, (bcols, dcols, _dp), (keys, sent, bl, bh, dl, dh) in zip(
                        lv.accessors, segs, probes
                    ):
                        bv, dv = bcols[a.val_pos], dcols[a.val_pos]
                        nb = bh[row_c] - bl[row_c]
                        bidx = jnp.clip(bl[row_c] + kk, 0, bv.shape[0] - 1)
                        didx = jnp.clip(dl[row_c] + (kk - nb), 0, dv.shape[0] - 1)
                        bval, dval = bv[bidx], dv[didx]
                        bprev = bv[jnp.clip(bidx - 1, 0, bv.shape[0] - 1)]
                        dprev = dv[jnp.clip(didx - 1, 0, dv.shape[0] - 1)]
                        sel.append((nb, bval, dval, bprev, dprev))
                with jax.named_scope("dedup"):
                    if use_pallas:
                        # fused VPU expansion: merge-by-rank select, dedup and
                        # accessor choice in one VMEM-resident kernel (bit-
                        # identical to the XLA branch — see ops/pallas_kernels)
                        from kolibrie_tpu.ops.pallas_kernels import (
                            lex_probe_select,
                            lex_probe_validate,
                        )

                        val, new_valid, is_base = lex_probe_select(
                            kk.astype(jnp.int32),
                            ch.astype(jnp.int32),
                            in_range,
                            [
                                (nb.astype(jnp.int32), bval, dval, bprev, dprev)
                                for nb, bval, dval, bprev, dprev in sel
                            ],
                        )
                    else:
                        vals_l, first_l, isb_l = [], [], []
                        for nb, bval, dval, bprev, dprev in sel:
                            isb = kk < nb
                            vals_l.append(jnp.where(isb, bval, dval))
                            first_l.append(
                                jnp.where(
                                    isb,
                                    (kk == 0) | (bprev != bval),
                                    (kk == nb) | (dprev != dval),
                                )
                            )
                            isb_l.append(isb)
                        val = jnp.stack(vals_l)[ch, slot]
                        first = jnp.stack(first_l)[ch, slot]
                        is_base = jnp.stack(isb_l)[ch, slot]
                        new_valid = in_range & (val != SENT) & first
                    # dedup count: distinct candidate values BEFORE the
                    # liveness/base-representative probes (both formulations
                    # agree at this point — lex_probe_select's new_valid is
                    # the same pre-liveness predicate)
                    stats[f"wcoj{lv.join_idx}:dedup"] = jnp.sum(new_valid)
                with jax.named_scope("live"):
                    ex = []
                    for a, (bcols, dcols, del_pos), (keys, sent, *_r) in zip(
                        lv.accessors, segs, probes
                    ):
                        fkeys = tuple(k[row_c] for k in keys) + (val,)
                        bsf = tuple(bcols[p] for p in a.key_pos) + (
                            bcols[a.val_pos],
                        )
                        dsf = tuple(dcols[p] for p in a.key_pos) + (
                            dcols[a.val_pos],
                        )
                        fl, fh = range_search(bsf, fkeys, lead_of(a), a.window)

                        def delta_live(
                            dsf=dsf, fkeys=fkeys, del_pos=del_pos, fl=fl, fh=fh
                        ):
                            dl2, dh2 = range_search(dsf, fkeys)
                            # tombstoned copies inside [fl, fh): del_pos holds
                            # sorted base-row positions (sentinel-padded)
                            tl = jnp.searchsorted(del_pos, fl.astype(jnp.uint32))
                            th = jnp.searchsorted(del_pos, fh.astype(jnp.uint32))
                            return (
                                tl.astype(jnp.int32),
                                th.astype(jnp.int32),
                                dl2,
                                dh2,
                            )

                        tl, th, dl2, dh2 = delta_or_zeros(
                            a.order_idx, (fl, fh, fl, fh), delta_live
                        )
                        ex.append((fl, fh, tl, th, dl2, dh2, sent[row_c]))
                    if use_pallas:
                        new_valid = lex_probe_validate(
                            new_valid,
                            is_base,
                            ch.astype(jnp.int32),
                            [
                                (
                                    fl,
                                    fh,
                                    tl,
                                    th,
                                    dl2,
                                    dh2,
                                    sent_r,
                                )
                                for fl, fh, tl, th, dl2, dh2, sent_r in ex
                            ],
                        )
                    else:
                        braw_l = []
                        for fl, fh, tl, th, dl2, dh2, sent_r in ex:
                            blive = (fh - fl) - (th - tl).astype(jnp.int32)
                            live = (blive + (dh2 - dl2)) > 0
                            new_valid = new_valid & live & ~sent_r
                            braw_l.append((fh - fl) > 0)
                        braw = jnp.stack(braw_l)[ch, slot]
                        new_valid = new_valid & (is_base | ~braw)
                    stats[f"wcoj{lv.join_idx}:live"] = jnp.sum(new_valid)
                wcols = {
                    v: jnp.where(new_valid, c[row_c], 0)
                    for v, c in wcols.items()
                }
                wcols[lv.var] = jnp.where(new_valid, val, 0)
                return wcols, new_valid

            for level, lv in enumerate(node.levels):
                with jax.named_scope(f"{skey}.L{level}"):
                    wcols, wvalid = eval_level(lv, wcols, wvalid)
            return wcols, wvalid, jnp.sum(wvalid)
        raise TypeError(f"unknown plan spec node {node!r}")

    cols, valid, _ = eval_node(spec.root)
    out = tuple(cols[v] for v in spec.out_vars)
    return out, valid, tuple(counts), stats


@partial(jax.jit, static_argnames=("spec", "use_pallas"))
def _run_plan(
    spec: PlanSpec,
    use_pallas: bool,
    order_arrays,
    scalars,
    tiers,
    masks,
    values,
    numf,
    quoted,
    params,
):
    return _plan_body(
        spec, order_arrays, scalars, tiers, masks, values, numf, quoted, params,
        use_pallas,
    )


@partial(jax.jit, static_argnames=("spec", "use_pallas"))
def _run_plan_batch(
    spec: PlanSpec,
    use_pallas: bool,
    order_arrays,
    scalars_b,
    live,
    tiers,
    masks,
    values,
    numf,
    quoted,
    params_b,
):
    """One template group on one chip: ONE executable a template, capacity
    set and slot class (``scalars_b`` and ``params_b`` have a row a slot,
    ``ops.slot_class`` of the group's size), whatever the group's size.
    ``live`` is traced: the loop runs the first ``live`` rows through the
    solo body (:func:`_plan_body`, Pallas kernels and the delta tier's
    conditionals as a lone request has them) and writes each member's
    outputs at its slot, so a padded slot joins nothing and reads as it
    was made: no row valid, every count 0.  The serving layer's
    micro-batcher lands here.

    Returns ``(rows, counts, stats)``: ``rows`` a tuple with one
    ``[len(out_vars) + 1, cap]`` uint32 block a slot (the output columns,
    then the valid mask), separate buffers so that the host reads the live
    members' and no other; ``counts`` and ``stats`` as the solo body gives
    them, each leaf ``[slots]``."""
    import jax.numpy as jnp
    from jax import lax

    def one(scalars, params):
        out, valid, counts, stats = _plan_body(
            spec, order_arrays, scalars, tiers, masks, values, numf, quoted,
            params, use_pallas,
        )
        block = jnp.stack(
            [*(c.astype(jnp.uint32) for c in out), valid.astype(jnp.uint32)]
        )
        return block, counts, stats

    def row(i):
        return jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
            (scalars_b, params_b),
        )

    def member(i, bufs):
        return jax.tree.map(
            lambda buf, x: lax.dynamic_update_index_in_dim(buf, x, i, 0),
            bufs,
            one(*row(i)),
        )

    slots = scalars_b.shape[0]
    blocks, counts, stats = lax.fori_loop(
        0,
        live,
        member,
        jax.tree.map(
            lambda a: jnp.zeros((slots, *a.shape), a.dtype),
            jax.eval_shape(one, *row(0)),
        ),
    )
    return tuple(blocks[b] for b in range(slots)), counts, stats


def _jit_entries(fn) -> int:
    """Size of one jit entry point's cache (a compile or a cache load on a
    shape's first sight adds an entry)."""
    try:
        return int(fn._cache_size())
    # kolint: ignore[KL601] jax version probe; -1 is the sentinel the stats endpoint documents for "cache API absent"
    except Exception:
        return -1


def device_compile_stats() -> Dict[str, int]:
    """Per-entry-point jit cache sizes — the compile counter the template
    tests and ``compiles_in_window`` read (a recompile ⇒ a new cache
    entry)."""
    out = {
        "run_plan": _jit_entries(_run_plan),
        "run_plan_batch": _jit_entries(_run_plan_batch),
        "segment_aggregate": _jit_entries(_segment_aggregate),
    }
    from kolibrie_tpu.optimizer.plan_interp import interp_compile_stats

    out["run_interp"] = interp_compile_stats()
    return out


# ---------------------------------------------------------------------------
# Lowering: physical plan -> IR (+ host-side prep)
# ---------------------------------------------------------------------------


def _spec_nodes(node, kind):
    """The nodes of class ``kind`` in a plan spec, in plan order (children
    before the node that joins them)."""
    if isinstance(node, (JoinSpec, AntiJoinSpec, LeftOuterSpec)):
        yield from _spec_nodes(node.left, kind)
        yield from _spec_nodes(node.right, kind)
    elif isinstance(node, (FilterSpec, QuotedExpandSpec)):
        yield from _spec_nodes(node.child, kind)
    elif isinstance(node, UnionSpec):
        for ch in node.children:
            yield from _spec_nodes(ch, kind)
    if isinstance(node, kind):
        yield node


class LoweredPlan:
    """A physical plan lowered for device execution.

    Holds the structural IR plus the host-side preparation products (scan
    range descriptors, filter mask arrays, values tables).  ``execute()``
    assembles the frozen :class:`PlanSpec`, runs the jitted interpreter,
    validates join capacities against the true match counts, and returns a
    host :data:`BindingTable` identical to the numpy engine's output.
    """

    def __init__(self, db, plan, anti_plans=(), union_groups=(), optional_plans=()):
        self.db = db
        self.scan_descs: List[tuple] = []  # (order_name, (cs, cp, co)) per scan
        # stats-advisor bookkeeping: canonical pattern sig per scan_idx,
        # and per-WCOJ-group (level keys, covered-sig multiset) — recorded
        # at lowering so observed counts can be keyed plan-shape-
        # independently (optimizer/stats_advisor.py)
        self.scan_sigs: List[str] = []
        self.wcoj_level_keys: List[tuple] = []  # (advisor_key, join_idx)
        self.wcoj_sig_groups: List[tuple] = []  # (sig tuple, last join_idx)
        self.mask_arrays: List[np.ndarray] = []
        self.mask_exprs: List[tuple] = []  # (op, const) per mask
        self._mask_keys: Dict[tuple, int] = {}
        self._mask_dict_len: tuple = (0, 0)
        self.values_tables: List[tuple] = []
        self.order_names: List[str] = []
        self._order_idx: Dict[str, int] = {}
        self.join_count = 0
        self.need_numf = False
        self.need_quoted = False
        # packed runtime parameter vectors: query constants live HERE (one
        # slot per syntactic constant site, traversal order — never
        # deduplicated by value, so the slot layout is a template property)
        self.u_params: List[int] = []  # uint32 term-id constants
        self.f_params: List[float] = []  # f64 numeric comparands
        self.quoted_specs: List[str] = []  # synthetic qid column names
        # fully-constant patterns: hoisted out of the join tree as host
        # membership guards — a failed guard empties the whole result
        # (engine.rs:144-260 evaluates them as 0/1-row scans; here they
        # never cost a device op)
        self.const_checks: List[tuple] = []
        if plan is None:
            # clause-only group (UNION/OPTIONAL with no main BGP): the
            # first clause becomes the root (host twin: the executor's
            # standalone union/optional special cases)
            self.root, vars_ = None, set()
        else:
            self.root, vars_ = self._lower(plan)
            if self.root is None:
                raise Unsupported("constant-only query")

        def _lower_branch(bplan, kind):
            n_checks = len(self.const_checks)
            broot, bvars = self._lower(bplan)
            if len(self.const_checks) != n_checks or broot is None:
                # a branch-local constant guard gates only the BRANCH, not
                # the query; const_ok() can't express that — fall back
                raise Unsupported(f"constant pattern in {kind} branch")
            return broot, bvars

        def _phys_vars(op) -> set:
            """Variable set a physical branch plan WOULD bind — used for
            statically-empty UNION branches, which are dropped from the
            fused tree but whose variables the host post-pass still
            synthesizes as UNBOUND-filled columns (executor.py union
            normalize): the device union must carry them too, or SELECT *
            arity diverges between the engines."""
            if isinstance(op, (P.PhysIndexScan, P.PhysTableScan)):
                # pattern.variables() recurses into quoted (RDF-star)
                # terms, whose inner variables the host also synthesizes
                return set(op.pattern.variables())
            if isinstance(
                op,
                (
                    P.PhysHashJoin,
                    P.PhysMergeJoin,
                    P.PhysParallelJoin,
                    P.PhysNestedLoopJoin,
                ),
            ):
                return _phys_vars(op.left) | _phys_vars(op.right)
            if isinstance(op, (P.PhysStarJoin, P.WcojNode)):
                out: set = set()
                for s in op.scans:
                    out |= _phys_vars(s)
                return out
            if isinstance(op, (P.PhysFilter, P.PhysProjection)):
                return _phys_vars(op.child)
            if isinstance(op, P.PhysValues):
                return set(op.values.variables)
            return set()

        def _statically_empty(op) -> bool:
            """A branch whose plan scans an UNKNOWN constant can never
            match (the term isn't in the dictionary) — its table is empty
            for the lifetime of this lowering's store version."""
            if isinstance(op, (P.PhysIndexScan, P.PhysTableScan)):
                pat = op.pattern
                return any(
                    t.kind == "id" and t.value is None
                    for t in (pat.subject, pat.predicate, pat.object)
                )
            if isinstance(
                op,
                (
                    P.PhysHashJoin,
                    P.PhysMergeJoin,
                    P.PhysParallelJoin,
                    P.PhysNestedLoopJoin,
                ),
            ):
                return _statically_empty(op.left) or _statically_empty(op.right)
            if isinstance(op, (P.PhysStarJoin, P.WcojNode)):
                return any(_statically_empty(s) for s in op.scans)
            if isinstance(op, (P.PhysFilter, P.PhysProjection)):
                return _statically_empty(op.child)
            return False

        # post-pass clauses compose over the main tree in the executor's
        # order — UNION joins, then OPTIONAL left-outers, then MINUS/NOT
        # anti-joins — so the whole group pattern is ONE device program
        for group in union_groups:
            live = [b for b in group if not _statically_empty(b)]
            if not live:
                # every branch scans an unknown constant: the union table
                # is empty, and joining an empty table empties the result
                # (host equi_join semantics) — a never-true guard says so
                self.const_checks.append((None, None, None))
                continue
            children, all_vars = [], set()
            for bplan in live:
                broot, bvars = _lower_branch(bplan, "UNION")
                children.append(broot)
                all_vars |= bvars
            # dropped (statically-empty) branches contribute no rows but
            # DO contribute columns: UNBOUND(0)-filled, like the host
            for bplan in group:
                if not any(bplan is lv for lv in live):
                    all_vars |= _phys_vars(bplan)
            uspec = UnionSpec(tuple(children), tuple(sorted(all_vars)))
            self.root, vars_ = self._make_join(
                self.root, vars_, uspec, all_vars
            )
        for bplan in optional_plans:
            if _statically_empty(bplan):
                # host keeps every left row and fills the branch-only
                # columns with UNBOUND; synthesizing those columns without
                # a branch tree isn't worth the spec — host fallback
                raise Unsupported("OPTIONAL branch with unknown constant")
            broot, bvars = _lower_branch(bplan, "OPTIONAL")
            if self.root is None:
                # leading OPTIONAL with no group: stands alone (host twin)
                self.root, vars_ = broot, set(bvars)
                continue
            shared = tuple(sorted(bvars & vars_))
            if not shared:
                raise Unsupported("OPTIONAL with no shared variables")
            self.root = LeftOuterSpec(
                self.root, broot, shared, self.join_count, 0
            )
            self.join_count += 1
            vars_ = vars_ | bvars
        # MINUS / query-NAF branches compose as anti-joins over the main
        # tree (host post-pass twin: executor's anti_join_tables loop)
        for bplan in anti_plans:
            if self.root is None:
                raise Unsupported("MINUS without a group")
            if _statically_empty(bplan):
                continue  # empty branch: MINUS/NOT removes nothing
            broot, bvars = _lower_branch(bplan, "MINUS/NOT")
            shared = tuple(sorted(bvars & vars_))
            if not shared:
                continue  # disjoint domains: MINUS removes nothing
            self.root = AntiJoinSpec(self.root, broot, shared)
        if self.root is None:
            raise Unsupported("constant-only query")
        # consumers that receive this object prebuilt need to know whether
        # the union/optional/minus host post-passes are already inside it
        self.fused_clauses = bool(anti_plans or union_groups or optional_plans)
        self.out_vars = tuple(sorted(vars_))
        if not self.out_vars:
            raise Unsupported("no output variables")
        self._compact_orders()
        # stable key for the db-level capacity caches.  TEMPLATE-level on
        # purpose: constants live in the parameter vectors (the spec tree
        # only carries param indices), and the scan descriptors contribute
        # their (order, bound-position) shape and the predicate they name,
        # which a scan's capacity is read from (template_scan_cap) — so
        # every constant variant of one text shares capacities, which is
        # what keeps the assembled PlanSpec (a static jit argument)
        # bit-identical across variants: ONE compile per template; and two
        # texts of one shape that name different predicates, whose scans
        # are compiled for different widths, keep their join capacities and
        # their calibration apart.
        self.cap_key = (
            self.root,
            self.out_vars,
            tuple(
                (name, tuple(c is not None for c in consts), consts[1])
                for name, consts in self.scan_descs
            ),
        )
        named = tuple(
            self.u_params[a.lead_predicate]
            for node in _spec_nodes(self.root, WcojSpec)
            for lv in node.levels
            for a in lv.accessors
            if a.lead_predicate is not None
        )
        if named:
            # the predicates that lead a WCOJ accessor's keys size its
            # window (_template_window_caps) as a scan's sizes the scan
            self.cap_key += (named,)
        # pre-actuals worthiness signal for the MQO layer: the planner's
        # leaf-scan cardinality bound (optimizer/mqo.py, docs/MQO.md)
        from kolibrie_tpu.optimizer.planner import estimated_prefix_rows

        self.est_prefix_rows = estimated_prefix_rows(plan)

    def _compact_orders(self) -> None:
        """Drop sort orders no longer referenced after join-driven order
        re-picking (each order is a full device-resident copy of the store —
        uploading unused ones would be a real cost at scale)."""
        used: List[int] = []
        sites: List[int] = []  # the order of each scan and WCOJ accessor

        def collect(node):
            if isinstance(node, ScanSpec):
                sites.append(node.order_idx)
                if node.order_idx not in used:
                    used.append(node.order_idx)
            elif isinstance(node, (JoinSpec, AntiJoinSpec, LeftOuterSpec)):
                collect(node.left)
                collect(node.right)
            elif isinstance(node, (FilterSpec, QuotedExpandSpec)):
                collect(node.child)
            elif isinstance(node, UnionSpec):
                for ch in node.children:
                    collect(ch)
            elif isinstance(node, WcojSpec):
                for lv in node.levels:
                    for a in lv.accessors:
                        sites.append(a.order_idx)
                        if a.order_idx not in used:
                            used.append(a.order_idx)

        collect(self.root)
        remap = {old: new for new, old in enumerate(sorted(used))}
        self._tier_sites = tuple(remap[o] for o in sites)
        if len(remap) == len(self.order_names) and all(
            o == n for o, n in remap.items()
        ):
            return
        self.order_names = [self.order_names[o] for o in sorted(used)]
        self._order_idx = {n: i for i, n in enumerate(self.order_names)}

        def rebuild(node):
            if isinstance(node, ScanSpec):
                return ScanSpec(
                    remap[node.order_idx],
                    node.scan_idx,
                    node.out_vars,
                    node.eq_pairs,
                    node.cap,
                    node.key_pos,
                )
            if isinstance(node, JoinSpec):
                return JoinSpec(
                    rebuild(node.left),
                    rebuild(node.right),
                    node.key_vars,
                    node.join_idx,
                    node.cap,
                    node.rsorted,
                )
            if isinstance(node, FilterSpec):
                return FilterSpec(rebuild(node.child), node.expr)
            if isinstance(node, QuotedExpandSpec):
                return QuotedExpandSpec(
                    rebuild(node.child),
                    node.qvar,
                    node.out_vars,
                    node.const_checks,
                    node.eq_checks,
                )
            if isinstance(node, AntiJoinSpec):
                return AntiJoinSpec(
                    rebuild(node.left), rebuild(node.right), node.key_vars
                )
            if isinstance(node, LeftOuterSpec):
                return LeftOuterSpec(
                    rebuild(node.left),
                    rebuild(node.right),
                    node.key_vars,
                    node.join_idx,
                    node.cap,
                )
            if isinstance(node, UnionSpec):
                return UnionSpec(
                    tuple(rebuild(ch) for ch in node.children), node.vars
                )
            if isinstance(node, WcojSpec):
                return WcojSpec(
                    tuple(
                        WcojLevel(
                            lv.var,
                            lv.join_idx,
                            lv.cap,
                            tuple(
                                replace(a, order_idx=remap[a.order_idx])
                                for a in lv.accessors
                            ),
                        )
                        for lv in node.levels
                    )
                )
            return node

        self.root = rebuild(self.root)

    # ------------------------------------------------------------- lowering

    def _order(self, name: str) -> int:
        idx = self._order_idx.get(name)
        if idx is None:
            idx = len(self.order_names)
            self.order_names.append(name)
            self._order_idx[name] = idx
        return idx

    def _lower(self, op):
        if isinstance(op, (P.PhysIndexScan, P.PhysTableScan)):
            pat = op.pattern
            terms = [pat.subject, pat.predicate, pat.object]
            if all(t.kind == "id" for t in terms):
                # hoist as a host membership guard (an unknown constant can
                # never match -> the guard is permanently false)
                self.const_checks.append(
                    tuple(
                        None if t.value is None else int(t.value)
                        for t in terms
                    )
                )
                return None, set()
            return self._lower_scan(pat)
        if isinstance(
            op,
            (P.PhysHashJoin, P.PhysMergeJoin, P.PhysParallelJoin, P.PhysNestedLoopJoin),
        ):
            left, lv = self._lower(op.left)
            right, rv = self._lower(op.right)
            return self._make_join(left, lv, right, rv)
        if isinstance(op, P.PhysStarJoin):
            node = None
            vars_: set = set()
            for scan in op.scans:
                n, v = self._lower(scan)
                if node is None:
                    node, vars_ = n, v
                else:
                    node, vars_ = self._make_join(node, vars_, n, v)
            if node is None:
                raise Unsupported("empty star join")
            return node, vars_
        if isinstance(op, P.PhysFilter):
            child, cv = self._lower(op.child)
            if child is None:
                raise Unsupported("filter over constant-only group")
            expr = self._lower_filter(op.expr, cv)
            return FilterSpec(child, expr), cv
        if isinstance(op, P.PhysValues):
            return self._lower_values(op.values)
        if isinstance(op, P.PhysProjection):
            # projection to fewer columns happens after readback (free)
            return self._lower(op.child)
        if isinstance(op, P.WcojNode):
            return self._lower_wcoj(op)
        raise Unsupported(f"operator {type(op).__name__}")

    _DEFAULT_ORDER = {
        # bound canonical positions -> default order (mirrors store.match)
        frozenset(): "spo",
        frozenset({0}): "spo",
        frozenset({1}): "pos",
        frozenset({2}): "osp",
        frozenset({0, 1}): "spo",
        frozenset({1, 2}): "pos",
        frozenset({0, 2}): "osp",
    }

    @staticmethod
    def _orders_for(bound: frozenset, sorted_pos: int) -> List[str]:
        """Sort orders whose prefix matches the bound positions AND whose next
        column is ``sorted_pos`` — i.e. a range scan from one presents that
        column sorted (enabling the sort-free merge join) — in the store's
        order of orders."""
        from kolibrie_tpu.core.store import ColumnarTripleStore

        pos_of = {"s": 0, "p": 1, "o": 2}
        k = len(bound)
        return [
            name
            for name, perm in ColumnarTripleStore._ORDER_PERMS.items()
            if frozenset(pos_of[c] for c in perm[:k]) == bound
            and pos_of[perm[k]] == sorted_pos
        ]

    @classmethod
    def _order_for(cls, bound: frozenset, sorted_pos: int) -> Optional[str]:
        """The first of :meth:`_orders_for`, ``None`` where there is none."""
        return next(iter(cls._orders_for(bound, sorted_pos)), None)

    @staticmethod
    def _merge_key_pos(order_name: str, n_bound: int) -> tuple:
        """Canonical positions of the two order columns the two-segment
        scan packs as its base/delta merge key: the first UNBOUND perm
        column and its successor.  Rows inside a scanned range are sorted
        by exactly that pair, so merging on it preserves the order the
        rsorted joins require (fully-constant patterns never reach a scan —
        they hoist to const_checks — hence ``n_bound <= 2``)."""
        from kolibrie_tpu.core.store import ColumnarTripleStore

        pos_of = {"s": 0, "p": 1, "o": 2}
        perm = ColumnarTripleStore._ORDER_PERMS[order_name]
        k = min(n_bound, 2)
        return (pos_of[perm[k]], pos_of[perm[min(k + 1, 2)]])

    def _lower_scan(self, pattern: PatternTriple):
        terms = [pattern.subject, pattern.predicate, pattern.object]
        consts: List[Optional[int]] = []
        quoted_at: List[tuple] = []  # (outer_pos, synthetic var, inner terms)
        for pos, t in enumerate(terms):
            if t.kind == "id":
                # a constant not in the dictionary can never match: keep the
                # scan (template shape is a structural property, not a
                # property of this variant's constants) and mark the slot so
                # _scan_ranges emits an empty (lo, 0) range
                consts.append(-1 if t.value is None else int(t.value))
            elif t.kind == "var":
                consts.append(None)
            else:
                # quoted term with inner variables (ground quoted terms were
                # folded to their qid by resolve_pattern); scan the position
                # as a synthetic qid variable, then expand it against the
                # device quoted table
                qvar = f"__qt{len(self.quoted_specs)}{len(quoted_at)}"
                quoted_at.append((pos, qvar, t.value))
                consts.append(None)
        bound = frozenset(i for i, c in enumerate(consts) if c is not None)
        # fully-constant patterns never reach here: _lower hoists them into
        # const_checks before calling _lower_scan
        order_name = self._DEFAULT_ORDER[bound]
        order_idx = self._order(order_name)
        scan_idx = len(self.scan_descs)
        self.scan_descs.append((order_name, tuple(consts)))
        self.scan_sigs.append(_sa.pattern_sig(pattern))
        out_vars: List[tuple] = []
        eq_pairs: List[tuple] = []
        seen: Dict[str, int] = {}
        for pos, t in enumerate(terms):
            if t.kind == "var":
                name = t.value
            elif t.kind == "quoted":
                name = next(q for p, q, _ in quoted_at if p == pos)
            else:
                continue
            if name in seen:
                eq_pairs.append((seen[name], pos))
            else:
                seen[name] = pos
                out_vars.append((name, pos))
        if not out_vars:
            raise Unsupported("pattern binds no variables")
        node: object = ScanSpec(
            order_idx,
            scan_idx,
            tuple(out_vars),
            tuple(eq_pairs),
            0,
            self._merge_key_pos(order_name, len(bound)),
        )
        bound_vars = {v for v in seen if not v.startswith("__qt")}
        for _pos, qvar, inner in quoted_at:
            node, bound_vars = self._wrap_quoted(node, qvar, inner, bound_vars)
        return node, bound_vars

    def _lower_wcoj(self, op):
        """Lower a :class:`WcojNode` to a :class:`WcojSpec`: one level per
        elimination variable; at each level, every pattern containing the
        variable contributes an accessor over the order whose perm prefix
        is exactly its bound positions.  Constants go through the uint32
        parameter vector (unknown ones as the never-an-ID sentinel, which
        zeroes the accessor's ranges at run time), so the spec tree — and
        hence the compiled executable — is a template property."""
        srcs: List[tuple] = []
        for scan in op.scans:
            if not isinstance(scan, (P.PhysIndexScan, P.PhysTableScan)):
                raise Unsupported("non-scan input to WCOJ")
            row: List[tuple] = []
            for t in (scan.pattern.subject, scan.pattern.predicate, scan.pattern.object):
                if t.kind == "var":
                    row.append(("v", t.value))
                elif t.kind == "id":
                    cid = 0xFFFFFFFF if t.value is None else int(t.value)
                    row.append(("u", self._uparam(cid)))
                else:
                    raise Unsupported("quoted term in WCOJ pattern")
            srcs.append(tuple(row))
        pos_of = {"s": 0, "p": 1, "o": 2}
        from kolibrie_tpu.core.store import ColumnarTripleStore

        eliminated: set = set()
        levels: List[WcojLevel] = []
        for var in op.elim_order:
            accessors: List[WcojAccessor] = []
            for row in srcs:
                positions = [i for i, s in enumerate(row) if s == ("v", var)]
                if not positions:
                    continue
                if len(positions) > 1:
                    raise Unsupported("repeated variable in WCOJ pattern")
                val_pos = positions[0]
                bound = frozenset(
                    i
                    for i, s in enumerate(row)
                    if s[0] == "u" or (s[0] == "v" and s[1] in eliminated)
                )
                covering = self._orders_for(bound, val_pos)
                if not covering:  # can't happen for |bound| <= 2
                    raise Unsupported("no covering order for WCOJ accessor")

                def constants_first(name):
                    # the text's constants before the eliminated variables:
                    # the rows a search can match are then one window of
                    # the order (WcojAccessor.window)
                    perm = ColumnarTripleStore._ORDER_PERMS[name]
                    return [row[pos_of[c]][0] != "u" for c in perm[: len(bound)]]

                order_name = min(covering, key=constants_first)
                perm = ColumnarTripleStore._ORDER_PERMS[order_name]
                key_pos = tuple(pos_of[c] for c in perm[: len(bound)])
                accessors.append(
                    WcojAccessor(
                        self._order(order_name),
                        tuple(row[p] for p in key_pos),
                        key_pos,
                        val_pos,
                    )
                )
            if not accessors:
                raise Unsupported("WCOJ variable not covered by any pattern")
            levels.append(
                WcojLevel(var, self.join_count, 0, tuple(accessors))
            )
            self.wcoj_level_keys.append((f"wcoj:?{var}", self.join_count))
            self.join_count += 1
            eliminated.add(var)
        # the last level's live count IS the output of joining exactly
        # this pattern group — the same quantity any Volcano tree over
        # the group would produce, hence the shared subset key
        self.wcoj_sig_groups.append(
            (
                tuple(_sa.pattern_sig(s.pattern) for s in op.scans),
                levels[-1].join_idx,
            )
        )
        return WcojSpec(tuple(levels)), set(op.elim_order)

    def _wrap_quoted(self, node, qvar: str, inner, bound_vars: set):
        """Wrap ``node`` with one :class:`QuotedExpandSpec` for the quoted
        term ``inner`` scanned into synthetic column ``qvar``."""
        q_out: List[tuple] = []
        q_const: List[tuple] = []
        q_eq: List[tuple] = []
        newly: set = set()
        for ipos, it in enumerate(inner):
            if it.kind == "id":
                # unknown inner constant: parameterize with the never-an-ID
                # sentinel (dictionary.rs:36-40) — the check can never pass
                cid = 0xFFFFFFFF if it.value is None else int(it.value)
                q_const.append((ipos, self._uparam(cid)))
            elif it.kind == "var":
                name = it.value
                if name in bound_vars or name in newly:
                    q_eq.append((ipos, name))  # collision or repeat
                else:
                    q_out.append((name, ipos))
                    newly.add(name)
            else:
                # host engine has the same limit (_join_quoted raises)
                raise Unsupported("doubly-nested quoted pattern")
        self.quoted_specs.append(qvar)
        self.need_quoted = True
        return (
            QuotedExpandSpec(
                node, qvar, tuple(q_out), tuple(q_const), tuple(q_eq)
            ),
            bound_vars | newly,
        )

    def _try_presort_scan(self, node, key_var: str) -> Optional[ScanSpec]:
        """If ``node`` is a bare scan (prefix validity) re-pick its order so
        ``key_var``'s column comes out sorted; None if not possible."""
        if not isinstance(node, ScanSpec) or node.eq_pairs:
            return None
        pos = dict(node.out_vars).get(key_var)
        if pos is None:
            return None
        consts = self.scan_descs[node.scan_idx][1]
        bound = frozenset(i for i, c in enumerate(consts) if c is not None)
        order_name = self._order_for(bound, pos)
        if order_name is None:
            return None
        self.scan_descs[node.scan_idx] = (order_name, consts)
        return ScanSpec(
            self._order(order_name),
            node.scan_idx,
            node.out_vars,
            node.eq_pairs,
            node.cap,
            self._merge_key_pos(order_name, len(bound)),
        )

    def _lower_values(self, values):
        if not values.variables or not values.rows:
            raise Unsupported("empty VALUES")
        from kolibrie_tpu.ops.join import UNBOUND

        n = len(values.rows)
        cols = []
        for j, _var in enumerate(values.variables):
            col = np.empty(n, dtype=np.uint32)
            for i, row in enumerate(values.rows):
                term = row[j] if j < len(row) else None
                if term is None:
                    col[i] = UNBOUND
                else:
                    col[i] = self.db.dictionary.encode(self.db.expand_term(term))
            cols.append(col)
        idx = len(self.values_tables)
        self.values_tables.append(tuple(cols))
        spec = ValuesSpec(idx, tuple(values.variables), n)
        return spec, set(values.variables)

    def _make_join(self, left, lv: set, right, rv: set):
        # a constant-pattern child lowered to a host guard joins as identity
        if left is None:
            return right, rv
        if right is None:
            return left, lv
        shared = tuple(sorted(lv & rv))
        if not shared:
            raise Unsupported("cartesian join")
        rsorted = False
        if len(shared) == 1:
            presorted = self._try_presort_scan(right, shared[0])
            if presorted is not None:
                right, rsorted = presorted, True
            else:
                presorted = self._try_presort_scan(left, shared[0])
                if presorted is not None:  # swap sides: inner join commutes
                    left, right, rsorted = right, presorted, True
        spec = JoinSpec(left, right, shared, self.join_count, 0, rsorted)
        self.join_count += 1
        return spec, lv | rv

    # ---------------------------------------------------------- filter lowering

    def _uparam(self, value: int) -> int:
        """Allocate the next uint32 parameter slot; returns its index."""
        self.u_params.append(int(value) & 0xFFFFFFFF)
        return len(self.u_params) - 1

    def _fparam(self, value: float) -> int:
        """Allocate the next f64 parameter slot; returns its index."""
        self.f_params.append(float(value))
        return len(self.f_params) - 1

    def _compute_mask(self, key: tuple) -> np.ndarray:
        if key[0] == "str":
            _tag, name, pattern, which = key
            return string_filter_mask(self.db, name, pattern, which)
        op, const = key
        return numeric_filter_mask(self.db.numeric_values(), op, const)

    def _mask_index(self, key: tuple) -> int:
        idx = self._mask_keys.get(key)
        if idx is None:
            idx = len(self.mask_arrays)
            self.mask_arrays.append(self._compute_mask(key))
            self.mask_exprs.append(key)
            self._mask_keys[key] = idx
            self._mask_dict_len = self._store_sizes()
        return idx

    def _store_sizes(self) -> tuple:
        return (len(self.db.dictionary.id_to_str), len(self.db.quoted))

    def _refresh_masks(self) -> None:
        """Rebuild per-ID filter masks if the dictionary (or quoted store —
        string masks cover it) grew since lowering: new IDs would otherwise
        clamp onto the last old ID's verdict."""
        sizes = self._store_sizes()
        if self.mask_arrays and sizes != self._mask_dict_len:
            self.mask_arrays = [
                self._compute_mask(k) for k in self.mask_exprs
            ]
            self._mask_dict_len = sizes

    def _lower_filter(self, expr, vars_: set):
        if isinstance(expr, LogicalAnd):
            return BoolNode(
                "and",
                (self._lower_filter(expr.left, vars_), self._lower_filter(expr.right, vars_)),
            )
        if isinstance(expr, LogicalOr):
            return BoolNode(
                "or",
                (self._lower_filter(expr.left, vars_), self._lower_filter(expr.right, vars_)),
            )
        if isinstance(expr, LogicalNot):
            return BoolNode("not", (self._lower_filter(expr.inner, vars_),))
        if isinstance(expr, Comparison):
            return self._lower_comparison(expr, vars_)
        if isinstance(expr, FunctionCall):
            return self._lower_function(expr, vars_)
        raise Unsupported(f"filter expression {type(expr).__name__}")

    _STR_FUNCS = ("REGEX", "CONTAINS", "STRSTARTS", "STRENDS")

    def _lower_function(self, expr, vars_: set):
        """Builtin boolean functions: BOUND/ISTRIPLE as ID tests; the
        constant-pattern string predicates as per-ID verdict masks (one
        over dictionary IDs, one over quoted IDs).  UDFs and variable
        patterns stay host-side."""
        name = expr.name.upper()
        args = expr.args
        if (
            name in ("BOUND", "ISTRIPLE")
            and len(args) == 1
            and isinstance(args[0], Var)
            and args[0].name in vars_
        ):
            if name == "BOUND":
                from kolibrie_tpu.ops.join import UNBOUND

                return IdCmp("!=", args[0].name, self._uparam(int(UNBOUND)))
            return QuotedCheck(args[0].name)
        if (
            name in self._STR_FUNCS
            and len(args) == 2
            and isinstance(args[0], Var)
            and args[0].name in vars_
            and isinstance(args[1], StringLit)
        ):
            lex = args[1].value
            pattern = lex[1:].split('"')[0] if lex.startswith('"') else lex
            didx = self._mask_index(("str", name, pattern, "dict"))
            qidx = self._mask_index(("str", name, pattern, "quoted"))
            return StrMaskRef(didx, qidx, args[0].name)
        raise Unsupported(f"filter function {expr.name}")

    @staticmethod
    def _as_number(e) -> Optional[float]:
        if isinstance(e, NumberLit):
            return float(e.value)
        if isinstance(e, StringLit):
            try:
                return float(e.value.strip('"').split('"')[0])
            except ValueError:
                return None
        return None

    def _lower_comparison(self, cmp: Comparison, vars_: set):
        lhs, rhs, op = cmp.left, cmp.right, cmp.op
        # const op var  ->  var flipped-op const
        if isinstance(rhs, Var) and not isinstance(lhs, Var):
            lhs, rhs = rhs, lhs
            flip = True
        else:
            flip = False
        if not isinstance(lhs, Var) or lhs.name not in vars_:
            raise Unsupported("filter lhs not a bound variable")
        if isinstance(rhs, Var):
            if rhs.name not in vars_:
                raise Unsupported("filter rhs variable unbound")
            self.need_numf = True
            return NumCmp(op, lhs.name, rhs.name)
        num = self._as_number(rhs)
        if num is not None:
            if flip:
                op = {
                    "<": ">", "<=": ">=", ">": "<", ">=": "<=",
                    "=": "=", "!=": "!=",
                }[op]
            self.need_numf = True
            return NumConstCmp(op, lhs.name, self._fparam(num))
        if op not in ("=", "!="):
            raise Unsupported("ordered comparison with non-numeric constant")
        if isinstance(rhs, IriRef):
            tid = self.db.dictionary.lookup(self.db.expand_term(rhs.iri))
        elif isinstance(rhs, StringLit):
            tid = self.db.dictionary.lookup(rhs.value)
        else:
            raise Unsupported(f"filter rhs {type(rhs).__name__}")
        return IdCmp(
            op, lhs.name, self._uparam(0xFFFFFFFF if tid is None else int(tid))
        )

    # ------------------------------------------------------------- assembly

    def _scan_ranges(self) -> np.ndarray:
        """Host searchsorted over the (host) base + delta sorted orders →
        ``(lo_base, n_base, lo_delta, n_delta)`` rows.  The compiled plan
        merges the two windows and masks base tombstones on device; the
        base window intentionally INCLUDES deleted rows (the tombstone
        positions handle them), keeping the range math identical on both
        segments."""
        store = self.db.store
        pos_of = {"s": 0, "p": 1, "o": 2}
        out = np.zeros((max(len(self.scan_descs), 1), 4), dtype=np.int32)
        for i, (order_name, consts) in enumerate(self.scan_descs):
            segments = (
                store.base_order(order_name),
                store.delta_order(order_name),
            )
            for j, order in enumerate(segments):
                keys = [
                    consts[pos_of[c]]
                    for c in order.perm
                    if consts[pos_of[c]] is not None
                ]
                if any(k < 0 for k in keys):
                    continue  # unknown constant: (0, 0) — matches nothing
                if not keys:
                    lo, hi = 0, len(order)
                elif len(keys) == 1:
                    lo, hi = order.range0(keys[0])
                else:
                    lo, hi = order.range01(keys[0], keys[1])
                out[i, 2 * j] = lo
                out[i, 2 * j + 1] = hi - lo
        return out

    def _host_scan_ranges(self) -> np.ndarray:
        """``(lo, n)`` rows over the LIVE sorted orders — the
        host-evaluation twin of :meth:`_scan_ranges` (host consumers never
        see the base/delta split)."""
        store = self.db.store
        pos_of = {"s": 0, "p": 1, "o": 2}
        out = np.zeros((max(len(self.scan_descs), 1), 2), dtype=np.int32)
        for i, (order_name, consts) in enumerate(self.scan_descs):
            order = store.order(order_name)
            keys = [
                consts[pos_of[c]]
                for c in order.perm
                if consts[pos_of[c]] is not None
            ]
            if any(k < 0 for k in keys):
                continue  # unknown constant: (0, 0) — matches nothing
            if not keys:
                lo, hi = 0, len(order)
            elif len(keys) == 1:
                lo, hi = order.range0(keys[0])
            else:
                lo, hi = order.range01(keys[0], keys[1])
            out[i] = (lo, hi - lo)
        return out

    def _with_caps(self, node, scan_caps: Dict[int, int], join_caps: List[int]):
        if isinstance(node, ScanSpec):
            return ScanSpec(
                node.order_idx,
                node.scan_idx,
                node.out_vars,
                node.eq_pairs,
                scan_caps[node.scan_idx],
                node.key_pos,
            )
        if isinstance(node, JoinSpec):
            return JoinSpec(
                self._with_caps(node.left, scan_caps, join_caps),
                self._with_caps(node.right, scan_caps, join_caps),
                node.key_vars,
                node.join_idx,
                join_caps[node.join_idx],
                node.rsorted,
            )
        if isinstance(node, FilterSpec):
            return FilterSpec(
                self._with_caps(node.child, scan_caps, join_caps), node.expr
            )
        if isinstance(node, QuotedExpandSpec):
            return QuotedExpandSpec(
                self._with_caps(node.child, scan_caps, join_caps),
                node.qvar,
                node.out_vars,
                node.const_checks,
                node.eq_checks,
            )
        if isinstance(node, AntiJoinSpec):
            return AntiJoinSpec(
                self._with_caps(node.left, scan_caps, join_caps),
                self._with_caps(node.right, scan_caps, join_caps),
                node.key_vars,
            )
        if isinstance(node, LeftOuterSpec):
            return LeftOuterSpec(
                self._with_caps(node.left, scan_caps, join_caps),
                self._with_caps(node.right, scan_caps, join_caps),
                node.key_vars,
                node.join_idx,
                join_caps[node.join_idx],
            )
        if isinstance(node, UnionSpec):
            return UnionSpec(
                tuple(
                    self._with_caps(ch, scan_caps, join_caps)
                    for ch in node.children
                ),
                node.vars,
            )
        if isinstance(node, WcojSpec):
            return WcojSpec(
                tuple(
                    WcojLevel(
                        lv.var,
                        lv.join_idx,
                        join_caps[lv.join_idx],
                        tuple(
                            replace(a, window=self._window_caps[lv.join_idx, i])
                            for i, a in enumerate(lv.accessors)
                        ),
                    )
                    for lv in node.levels
                )
            )
        return node

    def _node_cap(self, node, scan_caps, join_caps) -> int:
        if isinstance(node, ScanSpec):
            return scan_caps[node.scan_idx]
        if isinstance(node, JoinSpec):
            return join_caps[node.join_idx]
        if isinstance(node, (FilterSpec, QuotedExpandSpec)):
            return self._node_cap(node.child, scan_caps, join_caps)
        if isinstance(node, AntiJoinSpec):
            return self._node_cap(node.left, scan_caps, join_caps)
        if isinstance(node, LeftOuterSpec):
            return join_caps[node.join_idx] + self._node_cap(
                node.left, scan_caps, join_caps
            )
        if isinstance(node, UnionSpec):
            return sum(
                self._node_cap(ch, scan_caps, join_caps)
                for ch in node.children
            )
        if isinstance(node, ValuesSpec):
            return node.n
        if isinstance(node, WcojSpec):
            return join_caps[node.levels[-1].join_idx]
        raise TypeError(node)

    def _heuristic_join_caps(self, scan_caps) -> List[int]:
        """Capacities from the inputs' capacities alone: the ceiling of
        :func:`fit_join_caps` and the start where nothing has been counted
        yet.  Not a bound (a join can produce left x right); the overflow
        protocol in :meth:`converge` is what keeps answers exact."""
        caps: List[int] = [0] * self.join_count

        def walk(node) -> int:
            if isinstance(node, JoinSpec):
                ln = walk(node.left)
                rn = walk(node.right)
                cap = _round_cap(2 * max(ln, rn))
                caps[node.join_idx] = cap
                return cap
            if isinstance(node, AntiJoinSpec):
                ln = walk(node.left)
                walk(node.right)  # fills the branch's own join caps
                return ln
            if isinstance(node, LeftOuterSpec):
                ln = walk(node.left)
                rn = walk(node.right)
                cap = _round_cap(2 * max(ln, rn))
                caps[node.join_idx] = cap
                return cap + ln
            if isinstance(node, UnionSpec):
                return sum(walk(ch) for ch in node.children)
            if isinstance(node, (FilterSpec, QuotedExpandSpec)):
                return walk(node.child)  # fill caps of joins under wrappers
            if isinstance(node, WcojSpec):
                # each level no larger than its tightest accessor's largest
                # key-group (template property) or the previous level,
                # whichever wins; totals are exact even when a level
                # overflows, so each retry fixes a level for good
                prev = 1
                for lv in node.levels:
                    group = min(
                        template_scan_cap(
                            self.db,
                            self.order_names[a.order_idx],
                            len(a.key_srcs),
                        )
                        for a in lv.accessors
                    )
                    prev = _round_cap(max(prev, group))
                    caps[lv.join_idx] = prev
                return prev
            return self._node_cap(node, scan_caps, caps)

        walk(self.root)
        return caps

    def _initial_join_caps(self, scan_caps) -> List[int]:
        """Join and WCOJ-level capacities for this dispatch: what the
        template already holds on this db (every variant shares it: one
        executable a template); else, its first sight on this store, a
        calibrated start (:func:`fit_join_caps`), counted by the numpy twin
        before the first executable is chosen: the most rows any instance
        of the text gives a join where the twin could count that (a
        ceiling: :meth:`_calibration_counts`), the rows it saw with
        headroom elsewhere."""
        store = _caps.of(self.db)
        held = store.joins.get(self.cap_key, self.join_count)
        if held is not None:
            return list(held)
        store.templates.setdefault(self.cap_key, _get_baggage("template", "unknown"))
        heuristic = self._heuristic_join_caps(scan_caps)
        if max(heuristic, default=0) <= _CAP_FLOOR:
            return heuristic  # nothing the rule could tighten
        from kolibrie_tpu.query.template import note_calibrated_caps

        counted = self._calibration_counts()
        if counted is None:
            # the host pass would be too large: run once at the heuristic
            # and let the run that fits tighten from the counts it reads
            store.joins.start(self.cap_key, heuristic, provisional=True)
            return heuristic
        counts, ceilings = counted
        caps = fit_join_caps(heuristic, counts, ceilings)
        note_calibrated_caps("device", sum(ceilings), len(ceilings) - sum(ceilings))
        store.joins.start(self.cap_key, caps)
        return caps

    def _calibration_counts(self) -> Optional[Tuple[List[int], List[bool]]]:
        """Exact per-join counts from the numpy twin (no device I/O): those
        of this variant and, for each scan that binds its predicate and a
        subject or an object, the most rows any one key of that scan gives
        each join with the other constants as they are
        (:meth:`host_execute`'s ``free_scan``), then, where several scans
        are keyed, the most rows any one combination of their keys gives
        (a text with two placeholders has an instance hot in both, which no
        pass that frees one of them counts), the larger of them join by
        join.  So the template starts where its hottest instance
        takes it, whichever instance came first.  ``None`` where
        an intermediate of this variant would pass ``_CALIBRATE_ROW_LIMIT``
        rows; a hot pass that would counts the join at which it would without
        materializing it (:func:`_freed_join_rows`) and ends there, or is
        left out where it cannot (the overflow protocol keeps what it exceeds
        exact).

        Beside each count, whether it is a CEILING, the most rows any
        instance of the text gives that join on the store as it stands:
        every parameter read beneath the join is the key of a keyed scan
        (:meth:`_freed_beneath`), and a pass that freed all of those counted
        the join, by its largest group (the variant's own pass where nothing
        beneath reads a parameter).  The joins a cut or left-out pass did
        not count, and whatever sits over a parameter no pass frees, are one
        instance's and say so.

        Where the dispatch ends in an aggregation (``_stage``) every pass
        counts its groups too, the same way: ``_calibrated_groups`` is the
        most groups of this variant or of any one key (combination of keys)
        of a pass, ``None`` where the variant's own pass gave up;
        ``_groups_are_ceiling`` where every pass ran to its end and nothing
        under the aggregation reads a parameter they do not free."""
        from kolibrie_tpu.ops.join import RowLimitExceeded
        from kolibrie_tpu.query.template import cap_calibrate_seconds

        stage = self._stage
        groups = 0
        finished: List[Tuple[tuple, Sequence[int]]] = []  # (freed, joins counted)
        complete = True

        def timed(outcome, free_scan=()):
            nonlocal groups, complete
            t0 = _time.perf_counter()
            try:
                table, counts = self.host_execute(_CALIBRATE_ROW_LIMIT, free_scan)
                if stage is not None and stage.group_by:
                    found = _most_groups(
                        _freed_columns(table),
                        [table[g] for g in stage.group_by],
                    )
                    groups = max(groups, found)
                finished.append((free_scan, range(self.join_count)))
                return counts
            except _HotPassCut as cut:
                # counted up to the join it could not materialize; a topmost
                # join's rows bound its groups
                if stage is not None and cut.rows is not None:
                    groups = max(groups, cut.rows)
                finished.append((free_scan, cut.reached))
                complete = False
                return cut.counts
            except RowLimitExceeded:
                outcome = "too_large"
                complete = False
                return None
            finally:
                cap_calibrate_seconds.labels(outcome).inc(
                    _time.perf_counter() - t0
                )

        with _obs_span("device.calibrate") as sp:
            counts = timed("counted")
            keyed = self._keyed_scans() if counts is not None else []
            passes = [(i,) for i in keyed]
            if len(keyed) > 1:
                passes.append(tuple(keyed))
            if passes:
                own_stats = self.last_host_stats  # EXPLAIN's, not a pass's
                for free in passes:
                    hot = timed("hot_key", free)
                    if hot is not None:
                        counts = [max(a, b) for a, b in zip(counts, hot)]
                self.last_host_stats = own_stats
            if sp is not None:
                sp.attrs["hot_passes"] = len(passes)
            if counts is None:
                return None
            beneath, under_root = self._freed_beneath(keyed)
            ceilings = [
                beneath.get(j) is not None
                and any(
                    beneath[j] <= set(free) and j in counted
                    for free, counted in finished
                )
                for j in range(self.join_count)
            ]
            if sp is not None:
                sp.attrs["ceilings"] = sum(ceilings)
        if stage is not None:
            # SPARQL: without GROUP BY one group, of no rows too
            self._calibrated_groups = max(groups, 1)
            self._groups_are_ceiling = complete and under_root is not None
        return counts, ceilings

    def _freed_beneath(self, keyed: Sequence[int]):
        """What the hot passes have to free for a count to hold for every
        instance of the text: by join index, and for the root, the scans of
        ``keyed`` beneath it, or ``None`` where something beneath reads a
        parameter that no pass frees: a scan that binds a subject or an
        object without being keyed, a FILTER that compares with a constant
        (the parameter vectors' or a mask's), a VALUES table, a quoted
        pattern's inner constant, a WCOJ.  The predicate a scan names is no
        parameter: it is structure in ``cap_key``.  A branch that only takes
        rows away or fills them in (MINUS, OPTIONAL, UNION) counts whole
        passes, not groups of a key: with a parameter in it nothing above is
        a ceiling, and an OPTIONAL's own count is one only over no
        parameter at all.  A join index that is missing (a WCOJ level's) is
        no ceiling either."""
        beneath: Dict[int, Optional[frozenset]] = {}
        nothing: frozenset = frozenset()

        def both(a, b):
            return None if a is None or b is None else a | b

        def walk(node) -> Optional[frozenset]:
            if isinstance(node, ScanSpec):
                if node.scan_idx in keyed:
                    return frozenset((node.scan_idx,))
                consts = self.scan_descs[node.scan_idx][1]
                return nothing if consts[0] is None and consts[2] is None else None
            if isinstance(node, JoinSpec):
                beneath[node.join_idx] = both(walk(node.left), walk(node.right))
                return beneath[node.join_idx]
            if isinstance(node, FilterSpec):
                below = walk(node.child)
                return None if _reads_a_constant(node.expr) else below
            if isinstance(node, QuotedExpandSpec):
                below = walk(node.child)
                return None if node.const_checks else below
            if isinstance(node, (AntiJoinSpec, LeftOuterSpec)):
                left, branch = walk(node.left), walk(node.right)
                if isinstance(node, LeftOuterSpec):
                    whole = both(left, branch)
                    beneath[node.join_idx] = whole if whole == nothing else None
                return left if branch == nothing else None
            if isinstance(node, UnionSpec):
                parts = [walk(ch) for ch in node.children]
                return nothing if all(p == nothing for p in parts) else None
            if isinstance(node, (ValuesSpec, WcojSpec)):
                return None
            raise TypeError(node)

        return beneath, walk(self.root)

    def _calibrate_group_cap(self) -> None:
        """Where the dispatch ends in an aggregation and this db holds no
        group capacity for the template yet, publish the one it starts from
        (:func:`aggregate_table` reads it): the groups the numpy twin
        counted with headroom (beside the joins, in
        :meth:`_calibration_counts`, where :meth:`build` just ran it; in a
        pass of its own where the joins' capacities were held already), by
        the one rule (:func:`fit_join_caps`) under the table's width.  Where
        the twin gave up nothing is published: the aggregation starts at the
        floor and its retry sizes the template."""
        stage = self._stage
        if stage is None:
            return
        groups = _caps.of(self.db).groups
        key = (self.cap_key, stage.key)
        if groups.get(key) is not None:
            return
        from kolibrie_tpu.query.template import note_calibrated_caps

        slots = self._node_cap(self.root, self._scan_caps, self._join_caps)
        ceiling = group_cap_ceiling(slots)
        if ceiling <= _CAP_FLOOR:
            cap = ceiling  # nothing the rule could tighten
        else:
            if self._calibrated_groups is None:
                self._calibration_counts()
            if self._calibrated_groups is None:
                return
            top = self._groups_are_ceiling
            (cap,) = fit_join_caps([ceiling], [self._calibrated_groups], [top])
            note_calibrated_caps("device", int(top), int(not top))
        groups.start(key, [cap])
        # the template's first sight compiles two executables, the plan's and
        # the aggregation's: the second beside the first, not after it
        compile_aggregation_ahead(
            self.db, slots, len(self.out_vars), stage, cap
        )

    def _keyed_scans(self) -> List[int]:
        """The scans that bind their predicate and one of subject and
        object, both known to the dictionary: where a template's instances
        differ in rows by the key."""
        return [
            i
            for i, (_order, consts) in enumerate(self.scan_descs)
            if consts[1] is not None
            and sum(c is not None for c in consts) == 2
            and min(c for c in consts if c is not None) >= 0
        ]

    def _template_scan_caps(self) -> Dict[int, int]:
        """Scan capacities are a TEMPLATE property: the largest key-group
        of the order's bound-column prefix, among the rows under the
        predicate the scan names where it names one, bounds the live range
        for ANY other constant, so every variant of a text assembles the
        same ScanSpec.cap (the variant's true range rides in the traced
        scalars)."""
        return {
            i: _round_cap(
                template_scan_cap(
                    self.db,
                    name,
                    sum(c is not None for c in consts),
                    consts[1],
                )
            )
            for i, (name, consts) in enumerate(self.scan_descs)
        }

    def _template_window_caps(self) -> Dict[Tuple[int, int], int]:
        """By ``(level's join_idx, accessor's place in it)``, the base rows
        that accessor's range searches run over (``WcojAccessor.window``): a
        TEMPLATE property like a scan's capacity and read from the same
        table, the most rows any instance of the text has under the
        accessor's leading constants in the frozen base
        (:func:`base_key_group_rows`), on the capacity ladder.  0 where no
        constant leads or the ladder's step is the order's padded length:
        the whole order is searched.  Computed each build, so it follows
        ``base_version`` as ``ScanSpec.cap`` does."""
        store = self.db.store
        out: Dict[Tuple[int, int], int] = {}
        for node in _spec_nodes(self.root, WcojSpec):
            for lv in node.levels:
                for i, a in enumerate(lv.accessors):
                    out[lv.join_idx, i] = 0
                    if not a.lead:
                        continue
                    name = self.order_names[a.order_idx]
                    named = a.lead_predicate
                    rows = _round_cap(
                        base_key_group_rows(
                            self.db,
                            name,
                            a.lead,
                            None if named is None else self.u_params[named],
                        )
                    )
                    if rows < _round_cap(len(store.base_order(name))):
                        out[lv.join_idx, i] = rows
        return out

    def build(self, tag: int = 0, operands: bool = True) -> Tuple[PlanSpec, tuple]:
        """Assemble (spec, array_args) for the current store/capacities;
        without ``operands`` the spec alone, ``(spec, None)``, and nothing
        goes to the device: what a batch asks of its members after the
        first, whose store operands it shares."""
        self._refresh_masks()
        scan_ranges = self._scan_ranges()
        scan_caps = self._template_scan_caps()
        self._window_caps = self._template_window_caps()
        self._calibrated_groups = None
        join_caps = self._initial_join_caps(scan_caps)
        self._scan_ranges_np = scan_ranges
        self._scan_caps = scan_caps
        self._join_caps = join_caps
        self._calibrate_group_cap()
        return self._assemble(tag, operands)

    def _assemble(self, tag: int, operands: bool = True):
        store = self.db.store
        root = self._with_caps(self.root, self._scan_caps, self._join_caps)
        spec = PlanSpec(root, self.out_vars, tuple(self.order_names), tag)
        if not operands:
            return spec, None
        order_arrays = tuple(
            store.device_segment(name, _note_transfer)
            for name in self.order_names
        )
        # (base, delta) padded rows an order: with the capacities, all that
        # the forms of this dispatch's WCOJ range searches depend on
        self._seg_rows = tuple(
            (int(bcols[0].shape[0]), int(dcols[0].shape[0]))
            for bcols, dcols, _del_pos in order_arrays
        )
        # per-ID masks grow with the dictionary; pad each to a power-of-two
        # capacity (False = "no match", the clamp-gather's existing
        # out-of-range verdict) so small mutation batches that mint new
        # dictionary IDs re-upload without changing operand shapes
        masks = tuple(_upload(_pad_pow2(m, False)) for m in self.mask_arrays)
        values = tuple(
            tuple(_upload(c) for c in cols) for cols in self.values_tables
        )
        numf = self._device_numf() if self.need_numf else _device_zeros(np.float32)
        # what each order's delta tier holds right now (rows + tombstones),
        # read with the segments above so both are one delta epoch's: the
        # plan body reads the base alone where an entry is 0
        self._tiers_np = np.asarray(
            [
                len(store.delta_order(name)) + len(store.delta_del_positions(name))
                for name in self.order_names
            ],
            dtype=np.int32,
        )
        quoted = (
            device_quoted(self.db)
            if self.need_quoted
            else (_device_zeros(np.uint32),) * 4
        )
        # the scan ranges, the tiers and the parameter vectors are the numpy
        # arrays the build holds: the jit call transfers its host arguments
        # itself, so the build issues no transfer for them
        return spec, (
            order_arrays,
            self._scan_ranges_np,
            self._tiers_np,
            masks,
            values,
            numf,
            quoted,
            self.host_params(),
        )

    def host_params(self):
        """Pack the query constants as the (uparams, fparams) traced
        operands — the parameter-vector ABI: one uint32 slot per term-id
        constant site and one f64 slot per numeric comparand site, in
        lowering traversal order (padded to length >= 1 so empty templates
        keep a stable operand shape).  Numpy vectors: they go up with the
        jit call, which must run under ``enable_x64`` for ``f`` to stay
        float64."""
        return (
            np.asarray(self.u_params or [0], dtype=np.uint32),
            np.asarray(self.f_params or [0.0], dtype=np.float64),
        )

    def _device_numf(self):
        return device_numf(self.db)

    # ------------------------------------------------------- host evaluation

    def host_execute(
        self,
        row_limit: Optional[int] = None,
        free_scan: Sequence[int] = (),
    ) -> Tuple[BindingTable, List[int]]:
        """Evaluate the lowered IR with numpy — the executable-free reference
        semantics.  Returns (table, exact join counts).  Used to calibrate
        join capacities without any device readback and as the oracle in
        spec-semantics tests.
        With ``row_limit`` a scan, join or WCOJ level of more rows raises
        :class:`kolibrie_tpu.ops.join.RowLimitExceeded` before it is
        materialized.
        ``free_scan``: the calibration's hot-key pass, the indices of one
        scan or of several.  Such a scan (one of :meth:`_keyed_scans`) reads every row
        under its predicate, its bound subject or object riding along as a
        column of its own, and a join above it counts the largest group of
        the freed columns it holds: the most rows any one key (any one
        combination of keys) gives the join.  The table is then no answer to
        anything."""
        from kolibrie_tpu.ops.join import RowLimitExceeded
        from kolibrie_tpu.ops.join import join_indices as host_join_indices

        if not self.const_ok():
            self.last_host_stats = {}
            return self.empty_table(), [0] * self.join_count
        self._refresh_masks()
        scan_ranges = self._host_scan_ranges()
        numf = self.db.numeric_values() if self.need_numf else None
        counts: List[int] = [0] * self.join_count
        reached: List[int] = []  # the joins counted so far (a cut pass's)
        # numpy twin of _plan_body's analyze stats: same keys, same
        # pre-order sequence numbering for index-less nodes — the
        # EXPLAIN ANALYZE oracle tests assert exact agreement
        hstats: Dict[str, int] = {}
        hseq = {"filter": 0, "anti": 0, "union": 0, "quoted": 0}

        def check_rows(n: int) -> None:
            if row_limit is not None and n > row_limit:
                raise RowLimitExceeded(n)

        def eval_expr(expr, cols) -> np.ndarray:
            if isinstance(expr, MaskRef):
                m = self.mask_arrays[expr.mask_idx]
                ids = np.minimum(cols[expr.var], len(m) - 1)
                return m[ids]
            if isinstance(expr, StrMaskRef):
                from kolibrie_tpu.core.dictionary import QUOTED_BIT

                ids = cols[expr.var]
                dm = self.mask_arrays[expr.dict_idx]
                qm = self.mask_arrays[expr.quoted_idx]
                isq = (ids & np.uint32(QUOTED_BIT)) != 0
                dv = dm[np.minimum(ids, len(dm) - 1)]
                qidx = ids & np.uint32(~QUOTED_BIT & 0xFFFFFFFF)
                qv = qm[np.minimum(qidx, len(qm) - 1)]
                return np.where(isq, qv, dv)
            if isinstance(expr, QuotedCheck):
                from kolibrie_tpu.core.dictionary import QUOTED_BIT

                return (cols[expr.var] & np.uint32(QUOTED_BIT)) != 0
            if isinstance(expr, IdCmp):
                eq = cols[expr.var] == np.uint32(self.u_params[expr.param_idx])
                return eq if expr.op == "=" else ~eq
            if isinstance(expr, NumConstCmp):
                vals = numf[np.minimum(cols[expr.var], len(numf) - 1)]
                const = self.f_params[expr.param_idx]
                ops = {
                    "=": np.equal,
                    "!=": np.not_equal,
                    "<": np.less,
                    "<=": np.less_equal,
                    ">": np.greater,
                    ">=": np.greater_equal,
                }
                with np.errstate(invalid="ignore"):
                    res = ops[expr.op](vals, const)
                return res & ~np.isnan(vals)
            if isinstance(expr, NumCmp):
                a = numf[np.minimum(cols[expr.lvar], len(numf) - 1)]
                b = numf[np.minimum(cols[expr.rvar], len(numf) - 1)]
                ok = ~(np.isnan(a) | np.isnan(b))
                ops = {
                    "=": np.equal,
                    "!=": np.not_equal,
                    "<": np.less,
                    "<=": np.less_equal,
                    ">": np.greater,
                    ">=": np.greater_equal,
                }
                with np.errstate(invalid="ignore"):
                    res = ops[expr.op](a, b)
                if expr.op in ("=", "!="):
                    ideq = cols[expr.lvar] == cols[expr.rvar]
                    idres = ideq if expr.op == "=" else ~ideq
                    return np.where(ok, res, idres)
                return res & ok
            if isinstance(expr, BoolNode):
                if expr.kind == "not":
                    return ~eval_expr(expr.args[0], cols)
                m = eval_expr(expr.args[0], cols)
                for a in expr.args[1:]:
                    m2 = eval_expr(a, cols)
                    m = (m & m2) if expr.kind == "and" else (m | m2)
                return m
            raise TypeError(expr)

        def eval_node(node) -> Dict[str, np.ndarray]:
            if isinstance(node, ScanSpec):
                order_name, consts = self.scan_descs[node.scan_idx]
                order = self.db.store.order(order_name)
                if node.scan_idx in free_scan:
                    canon = predicate_rows(order, consts[1])
                    n = len(canon["p"])
                else:
                    lo, n = (int(x) for x in scan_ranges[node.scan_idx])
                    canon = order.slice_rows(lo, lo + n)  # views: nothing read yet
                check_rows(n)
                raw = {0: canon["s"], 1: canon["p"], 2: canon["o"]}
                mask = None
                for a, b in node.eq_pairs:
                    m = raw[a] == raw[b]
                    mask = m if mask is None else (mask & m)
                cols = {var: raw[pos] for var, pos in node.out_vars}
                if node.scan_idx in free_scan:
                    cols[f"{_FREE_KEY}{node.scan_idx}"] = raw[
                        0 if consts[0] is not None else 2
                    ]
                if mask is not None:
                    cols = {k: v[mask] for k, v in cols.items()}
                hstats[f"scan{node.scan_idx}"] = (
                    int(mask.sum()) if mask is not None else n
                )
                return cols
            if isinstance(node, ValuesSpec):
                hstats[f"values{node.values_idx}"] = node.n
                return {
                    v: self.values_tables[node.values_idx][i]
                    for i, v in enumerate(node.vars)
                }
            if isinstance(node, JoinSpec):
                from kolibrie_tpu.ops.join import _pack_shared_keys

                lcols = eval_node(node.left)
                rcols = eval_node(node.right)
                lkey, rkey = _pack_shared_keys(
                    lcols,
                    rcols,
                    list(node.key_vars),
                    len(next(iter(lcols.values()))),
                )
                try:
                    li, ri = host_join_indices(lkey, rkey, max_rows=row_limit)
                except RowLimitExceeded:
                    # a hot pass too large to materialize still counts this
                    # join, and ends there
                    most = _freed_join_rows(lcols, rcols, lkey, rkey)
                    if most is None:
                        raise
                    counts[node.join_idx] = most
                    raise _HotPassCut(
                        counts,
                        reached + [node.join_idx],
                        most if node is _top_join(self.root) else None,
                    ) from None
                hstats[f"join{node.join_idx}"] = len(li)
                out = {v: c[li] for v, c in lcols.items()}
                for v, c in rcols.items():
                    if v not in out:
                        out[v] = c[ri]
                freed = _freed_columns(out)
                counts[node.join_idx] = (
                    _largest_group(*freed) if freed else len(li)
                )
                reached.append(node.join_idx)
                return out
            if isinstance(node, FilterSpec):
                skey = f"filter{hseq['filter']}"
                hseq["filter"] += 1
                cols = eval_node(node.child)
                mask = eval_expr(node.expr, cols)
                hstats[skey] = int(mask.sum())
                return {k: v[mask] for k, v in cols.items()}
            if isinstance(node, QuotedExpandSpec):
                from kolibrie_tpu.core.dictionary import QUOTED_BIT

                skey = f"quoted{hseq['quoted']}"
                hseq["quoted"] += 1
                cols = eval_node(node.child)
                qcol = cols.pop(node.qvar)
                qid, qs_, qp_, qo_ = host_quoted_table(self.db)
                pos = np.searchsorted(qid, qcol)
                posc = np.minimum(pos, len(qid) - 1)
                mask = (qid[posc] == qcol) & ((qcol & QUOTED_BIT) != 0)
                inner = [qs_[posc], qp_[posc], qo_[posc]]
                for ipos, pidx in node.const_checks:
                    mask = mask & (inner[ipos] == np.uint32(self.u_params[pidx]))
                for var, ipos in node.out_vars:
                    cols[var] = inner[ipos]
                for ipos, var in node.eq_checks:
                    mask = mask & (inner[ipos] == cols[var])
                hstats[skey] = int(mask.sum())
                return {k: v[mask] for k, v in cols.items()}
            if isinstance(node, AntiJoinSpec):
                from kolibrie_tpu.ops.join import anti_join_tables

                skey = f"anti{hseq['anti']}"
                hseq["anti"] += 1
                lcols = eval_node(node.left)
                rcols = eval_node(node.right)
                out = anti_join_tables(lcols, rcols)
                hstats[skey] = len(next(iter(out.values()), ()))
                return out
            if isinstance(node, UnionSpec):
                skey = f"union{hseq['union']}"
                hseq["union"] += 1
                parts = [eval_node(ch) for ch in node.children]
                out = {}
                for v in node.vars:
                    segs = []
                    for ccols in parts:
                        if v in ccols:
                            segs.append(ccols[v])
                        else:
                            n = len(next(iter(ccols.values()), np.empty(0)))
                            segs.append(np.zeros(n, dtype=np.uint32))
                    out[v] = np.concatenate(segs) if segs else np.empty(0, np.uint32)
                hstats[skey] = len(next(iter(out.values()), ()))
                return out
            if isinstance(node, LeftOuterSpec):
                from kolibrie_tpu.ops.join import _pack_shared_keys

                lcols = eval_node(node.left)
                rcols = eval_node(node.right)
                ln = len(next(iter(lcols.values())))
                rn = len(next(iter(rcols.values())))
                reached.append(node.join_idx)
                if ln == 0 or rn == 0:
                    counts[node.join_idx] = 0
                    hstats[f"optional{node.join_idx}"] = ln
                    out = {k: v.copy() for k, v in lcols.items()}
                    for k in rcols:
                        if k not in out:
                            out[k] = np.zeros(ln, dtype=np.uint32)
                    return out
                lkey, rkey = _pack_shared_keys(
                    lcols, rcols, list(node.key_vars), ln
                )
                li, ri = host_join_indices(lkey, rkey, max_rows=row_limit)
                counts[node.join_idx] = len(li)
                matched = np.zeros(ln, dtype=bool)
                matched[li] = True
                unmatched = np.nonzero(~matched)[0]
                hstats[f"optional{node.join_idx}"] = len(li) + len(unmatched)
                out = {}
                for k, col in lcols.items():
                    out[k] = np.concatenate([col[li], col[unmatched]])
                for k, col in rcols.items():
                    if k not in out:
                        out[k] = np.concatenate(
                            [
                                col[ri],
                                np.zeros(len(unmatched), dtype=np.uint32),
                            ]
                        )
                return out
            if isinstance(node, WcojSpec):
                return eval_wcoj(node)
            raise TypeError(node)

        def eval_wcoj(node) -> Dict[str, np.ndarray]:
            """Numpy twin of the device WCOJ levels.  Mirrors the RAW-count
            math bit for bit (tombstoned and duplicate rows included in the
            candidate counts) so ``counts`` calibrates device capacities
            exactly; rows are compressed to the valid set after each level
            instead of padded to a cap."""
            from kolibrie_tpu.ops.wcoj import host_lex_range

            store = self.db.store
            SENT = np.uint32(0xFFFFFFFF)
            pos_of = {"s": 0, "p": 1, "o": 2}
            seg_cache: Dict[int, tuple] = {}

            def seg(order_idx):
                cached = seg_cache.get(order_idx)
                if cached is None:
                    name = self.order_names[order_idx]
                    bo = store.base_order(name)
                    do = store.delta_order(name)
                    bperm = [pos_of[c] for c in bo.perm]
                    bcanon = [None, None, None]
                    dcanon = [None, None, None]
                    for j, p in enumerate(bperm):
                        bcanon[p] = (bo.c0, bo.c1, bo.c2)[j]
                        dcanon[p] = (do.c0, do.c1, do.c2)[j]
                    cached = (
                        bcanon,
                        dcanon,
                        store.delta_del_positions(name),
                    )
                    seg_cache[order_idx] = cached
                return cached

            cols: Dict[str, np.ndarray] = {}
            nrows = 1
            for lv in node.levels:
                per = []
                for a in lv.accessors:
                    bcanon, dcanon, dp = seg(a.order_idx)
                    keys = []
                    sent = np.zeros(nrows, dtype=bool)
                    for src in a.key_srcs:
                        if src[0] == "u":
                            k = np.full(
                                nrows, self.u_params[src[1]], dtype=np.uint32
                            )
                        else:
                            k = cols[src[1]]
                        sent |= k == SENT
                        keys.append(k)
                    if keys:
                        bl, bh = host_lex_range(
                            [bcanon[p] for p in a.key_pos], keys
                        )
                        dl, dh = host_lex_range(
                            [dcanon[p] for p in a.key_pos], keys
                        )
                    else:
                        bl = np.zeros(nrows, dtype=np.int64)
                        dl = np.zeros(nrows, dtype=np.int64)
                        bh = np.full(
                            nrows, len(bcanon[a.val_pos]), dtype=np.int64
                        )
                        dh = np.full(
                            nrows, len(dcanon[a.val_pos]), dtype=np.int64
                        )
                    cnt = np.where(sent, 0, (bh - bl) + (dh - dl))
                    per.append(
                        (a, bcanon, dcanon, dp, keys, sent, bl, bh, dl, cnt)
                    )
                cntm = np.stack([p[-1] for p in per])
                choice = np.argmin(cntm, axis=0)
                cnt = np.min(cntm, axis=0)
                total = int(cnt.sum())
                check_rows(total)
                counts[lv.join_idx] = total
                hstats[f"wcoj{lv.join_idx}:cand"] = total
                rows = np.repeat(np.arange(nrows), cnt)
                kk = np.arange(total, dtype=np.int64) - np.repeat(
                    np.cumsum(cnt) - cnt, cnt
                )
                ch = choice[rows]
                val = np.zeros(total, dtype=np.uint32)
                first = np.zeros(total, dtype=bool)
                is_base = np.zeros(total, dtype=bool)
                for ai, (a, bcanon, dcanon, dp, keys, sent, bl, bh, dl, _c) in enumerate(per):
                    m = ch == ai
                    if not m.any():
                        continue
                    bv = bcanon[a.val_pos]
                    dv = dcanon[a.val_pos]
                    rm, km = rows[m], kk[m]
                    nb = bh[rm] - bl[rm]
                    isb = km < nb
                    if len(bv):
                        bidx = np.clip(bl[rm] + km, 0, len(bv) - 1)
                        bval = bv[bidx]
                        bprev = bv[np.clip(bidx - 1, 0, len(bv) - 1)]
                    else:
                        bval = bprev = np.zeros(len(km), dtype=np.uint32)
                    if len(dv):
                        didx = np.clip(dl[rm] + (km - nb), 0, len(dv) - 1)
                        dval = dv[didx]
                        dprev = dv[np.clip(didx - 1, 0, len(dv) - 1)]
                    else:
                        dval = dprev = np.zeros(len(km), dtype=np.uint32)
                    val[m] = np.where(isb, bval, dval)
                    first[m] = np.where(
                        isb,
                        (km == 0) | (bprev != bval),
                        (km == nb) | (dprev != dval),
                    )
                    is_base[m] = isb
                vvalid = first
                # device dedup = in_range & (val != SENT) & first; host
                # rows are exact-length (no padding in range) so val is
                # never the sentinel and first alone is the same count
                hstats[f"wcoj{lv.join_idx}:dedup"] = int(first.sum())
                braw_ch = np.zeros(total, dtype=bool)
                for ai, (a, bcanon, dcanon, dp, keys, sent, *_r) in enumerate(per):
                    fkeys = [k[rows] for k in keys] + [val]
                    fl, fh = host_lex_range(
                        [bcanon[p] for p in a.key_pos]
                        + [bcanon[a.val_pos]],
                        fkeys,
                    )
                    dl2, dh2 = host_lex_range(
                        [dcanon[p] for p in a.key_pos]
                        + [dcanon[a.val_pos]],
                        fkeys,
                    )
                    tl = np.searchsorted(dp, fl.astype(np.uint32))
                    th = np.searchsorted(dp, fh.astype(np.uint32))
                    live = ((fh - fl) - (th - tl) + (dh2 - dl2)) > 0
                    vvalid = vvalid & live & ~sent[rows]
                    braw_ch = np.where(ch == ai, (fh - fl) > 0, braw_ch)
                vvalid = vvalid & (is_base | ~braw_ch)
                cols = {v: c[rows][vvalid] for v, c in cols.items()}
                cols[lv.var] = val[vvalid]
                nrows = int(vvalid.sum())
                hstats[f"wcoj{lv.join_idx}:live"] = nrows
            return cols

        table = eval_node(self.root)
        self.last_host_stats = hstats
        return table, counts

    def calibrate_host(self) -> List[int]:
        """Size the join capacities from a host evaluation (no device I/O)
        by the one rule, publish them (max-merge: an earlier, larger
        variant's caps stay); returns the exact per-join match counts
        (EXPLAIN annotates with them)."""
        self._scan_ranges_np = self._scan_ranges()
        _table, counts = self.host_execute()
        self._join_caps = fit_join_caps(
            self._heuristic_join_caps(self._template_scan_caps()), counts
        )
        self._store_caps()
        # calibration counts are EXACT per-join match counts: feed the
        # stats advisor before the first dispatch so a misrouted cold
        # template can already replan on its second execution
        self._advise(counts)
        return counts

    # ------------------------------------------------------------ execution

    def run(self, tag: int = 0):
        """One dispatch (no readback).  Returns (out_cols, valid, counts,
        stats) — all device-resident."""
        from kolibrie_tpu.ops.pallas_kernels import pallas_enabled

        spec, args = _build_traced(self, tag)
        with jax.enable_x64(True):
            return _enqueue_traced(_run_plan, spec, pallas_enabled(), *args)

    def _note_scan_tiers(self, members: int = 1) -> None:
        """Count the dispatch just assembled: its scans and WCOJ accessors by
        the branch their order's entry of ``tiers`` selects in the plan body
        (``members``: the live members a group's dispatch runs them for)."""
        from kolibrie_tpu.query.template import note_scan_tiers

        base_only = sum(1 for o in self._tier_sites if self._tiers_np[o] == 0)
        note_scan_tiers(
            members * base_only, members * (len(self._tier_sites) - base_only)
        )

    def _range_searches(self):
        """The WCOJ range searches of the dispatch just assembled, as the
        plan body traced them: ``(rows, probes, ncols, extent)`` each.  An
        accessor searches its order's base once in ``probe`` (the level
        before's capacity wide, where it has keys) and once in ``live``
        (this level's), over its window where it has one
        (``WcojAccessor.window``: extent ``"window"``, else ``"order"``),
        and its delta (``"delta"``), whole, as often where the tier holds
        something."""
        for node in _spec_nodes(self.root, WcojSpec):
            pcap = 1
            for lv in node.levels:
                cap = self._join_caps[lv.join_idx]
                for i, a in enumerate(lv.accessors):
                    nkeys = len(a.key_srcs)
                    base, delta = self._seg_rows[a.order_idx]
                    window = self._window_caps[lv.join_idx, i]
                    tiers = [(window or base, "window" if window else "order")]
                    if self._tiers_np[a.order_idx]:
                        tiers.append((delta, "delta"))
                    for n, extent in tiers:
                        if nkeys:
                            yield n, pcap, nkeys, extent
                        yield n, cap, nkeys + 1, extent
                pcap = cap

    def _note_range_searches(self, members: int = 1) -> None:
        """Count :meth:`_range_searches` by the form the rule gives each
        (:func:`range_search_form` reads the rows searched: the window's),
        and the base rows they ran over by whether a window was taken (the
        delta's searches have one width, the tier's, and are left out)."""
        from kolibrie_tpu.ops.wcoj import range_search_form
        from kolibrie_tpu.query.template import (
            note_range_search_rows,
            note_range_searches,
        )

        forms = {"sorted": 0, "loop": 0}
        rows = {"window": 0, "order": 0, "delta": 0}
        for n, p, ncols, extent in self._range_searches():
            forms[range_search_form(n, p, ncols)] += 1
            rows[extent] += n
        note_range_searches(members * forms["sorted"], members * forms["loop"])
        note_range_search_rows(members * rows["window"], members * rows["order"])

    def _note_join_searches(self, members) -> None:
        """Count the run-bound searches of the dispatch just read back: for
        each join that ran the Pallas prepass its left side's width, and the
        keys its searches' blocks covered for the rows that side held: a
        bare scan's range, a join's counted rows; the whole width where the
        left child is neither or the join passes no validity.  ``members``:
        one ``(scan_ranges, counts)`` a live member, numbers the host holds."""
        from kolibrie_tpu.ops.pallas_kernels import (
            pallas_enabled,
            searched_keys,
        )
        from kolibrie_tpu.query.template import note_join_search_keys

        if not pallas_enabled():
            return
        slots = searched = 0
        for node in _spec_nodes(self.root, JoinSpec):
            left = node.left
            width = self._node_cap(left, self._scan_caps, self._join_caps)
            slots += len(members) * width
            for scan_ranges, counts in members:
                rows = None
                if not node.rsorted:
                    pass  # ranked keys, no validity: every slot is searched
                elif isinstance(left, ScanSpec):
                    rows = int(scan_ranges[left.scan_idx, 1::2].sum())
                elif isinstance(left, JoinSpec):
                    rows = int(counts[left.join_idx])
                searched += searched_keys(width, rows)
        note_join_search_keys(slots, searched)

    def _note_scan_occupancy(self, members) -> None:
        """Count the scans of the dispatch just read back: the slots they
        were compiled for (the template's scan capacities, each as wide as
        the predicate its scan names) and the rows
        their ranges held, base and delta.  ``members``: one
        ``_scan_ranges_np`` a live member, numbers the host holds."""
        from kolibrie_tpu.query.template import note_scan_occupancy

        scans = [node.scan_idx for node in _spec_nodes(self.root, ScanSpec)]
        note_scan_occupancy(
            "device",
            len(members) * sum(self._scan_caps[i] for i in scans),
            sum(int(ranges[scans, 1::2].sum()) for ranges in members),
        )

    def _store_caps(self) -> None:
        """Publish this dispatch's join capacities to the db's template
        memory (a monotonic merge) and run with what it then holds."""
        self._join_caps = list(
            _caps.of(self.db).joins.merge(self.cap_key, self._join_caps)
        )

    def converge(self, out):
        """Validate join counts against the capacities ``out`` ran with;
        re-run with grown capacities until everything fits (the one overflow
        loop, :func:`caps.run_until_fits`).  Returns ``(out_cols, valid)``
        — readback of the counts happens here."""
        from kolibrie_tpu.query.template import (
            cap_retry_seconds,
            note_cap_occupancy,
            note_cap_retry,
        )

        def run(attempt):
            ran = out if attempt == 0 else self.run()
            self._last_stats = ran[3]  # device-resident; fetched only on analyze
            counts_h = _read_counts(ran, ran[2], attempt, int)
            _note_fetch("converge.counts")
            return ran, self._join_caps, counts_h

        def tally(counts_h, caps):
            note_cap_occupancy("device", sum(caps), sum(counts_h))
            self._note_scan_tiers()
            self._note_range_searches()
            self._note_join_searches([(self._scan_ranges_np, counts_h)])
            self._note_scan_occupancy([self._scan_ranges_np])
            return counts_h

        (out_cols, valid, _counts, _stats), _ran_with, counts_h = _caps.run_until_fits(
            _caps.of(self.db).joins,
            self.cap_key,
            run,
            tally,
            retried=lambda: note_cap_retry("device"),
            rerun_seconds=cap_retry_seconds.labels("device").inc,
        )
        self._last_counts = counts_h
        self._emit_wcoj_obs(counts_h)
        self._advise(counts_h)
        return out_cols, valid

    def _emit_wcoj_obs(self, counts_h: List[int]) -> None:
        """Per-level WCOJ instrumentation from the converged host-read
        counts: intermediate rows, cap occupancy, probe volume."""

        for node in _spec_nodes(self.root, WcojSpec):
            for lv in node.levels:
                if lv.join_idx >= len(counts_h):
                    continue
                rows = counts_h[lv.join_idx]
                cap = self._join_caps[lv.join_idx]
                _WCOJ_LEVEL_ROWS.observe(rows)
                if cap > 0:
                    _WCOJ_CAP_OCCUPANCY.observe(rows / cap)
                _WCOJ_PROBES.inc(cap * len(lv.accessors))

    def _advisor_sites(self) -> List[tuple]:
        """Observable operator sites for the stats advisor: a list of
        ``(source, idx, advisor_key, describe_key)`` where ``source`` is
        ``"scan"`` (rows read from :meth:`_host_scan_ranges` row ``idx``)
        or ``"count"`` (rows read from the converged counts at ``idx``).
        Advisor keys are plan-shape-independent (pattern-sig based); the
        describe keys match :meth:`describe`/``fetch_stats`` naming so
        EXPLAIN can annotate nodes with their learned est/actual pair."""
        cached = getattr(self, "_advisor_sites_cache", None)
        if cached is not None:
            return cached
        sites: List[tuple] = []

        def sigs(node) -> Optional[List[str]]:
            if isinstance(node, ScanSpec):
                sig = self.scan_sigs[node.scan_idx]
                sites.append(
                    ("scan", node.scan_idx, "scan:" + sig,
                     f"scan{node.scan_idx}")
                )
                return [sig]
            if isinstance(node, JoinSpec):
                left, right = sigs(node.left), sigs(node.right)
                if left is None or right is None:
                    return None
                got = left + right
                sites.append(
                    ("count", node.join_idx, _sa.subset_key(got),
                     f"join{node.join_idx}")
                )
                return got
            if isinstance(node, (FilterSpec, QuotedExpandSpec)):
                # template-fixed transforms: the covered pattern group is
                # the child's (the subset key names the group, and any
                # filters a template applies to it apply identically
                # under every candidate join tree)
                return sigs(node.child)
            if isinstance(node, LeftOuterSpec):
                left, right = sigs(node.left), sigs(node.right)
                if left is not None and right is not None:
                    # the MATCHED part of a left-outer join is exactly the
                    # inner join of the covered groups
                    sites.append(
                        ("count", node.join_idx,
                         _sa.subset_key(left + right),
                         f"optional{node.join_idx}")
                    )
                return None  # outer output != inner join of the leaves
            if isinstance(node, AntiJoinSpec):
                sigs(node.left)
                sigs(node.right)
                return None
            if isinstance(node, UnionSpec):
                for ch in node.children:
                    sigs(ch)
                return None
            return None  # VALUES / WCOJ (levels handled below)

        if self.root is not None:
            sigs(self.root)
        for akey, join_idx in self.wcoj_level_keys:
            sites.append(("count", join_idx, akey, f"wcoj{join_idx}:live"))
        for group, join_idx in self.wcoj_sig_groups:
            sites.append(
                ("count", join_idx, _sa.subset_key(list(group)),
                 f"wcoj{join_idx}:live")
            )
        self._advisor_sites_cache = sites
        return sites

    def advisor_actuals(self, counts_h: List[int]) -> Dict[str, float]:
        """Per-operator actual rows from one converged execution, keyed
        plan-shape-independently.  Every input is already host-resident
        (``converge`` read the counts; scan ranges are host binary
        searches) — feeding the advisor adds ZERO device I/O."""
        actuals: Dict[str, float] = {}
        scan_rows = self._host_scan_ranges()
        for source, idx, akey, _dkey in self._advisor_sites():
            if source == "scan":
                if idx < len(scan_rows):
                    actuals[akey] = float(scan_rows[idx][1])
            elif idx < len(counts_h):
                actuals[akey] = float(counts_h[idx])
        return actuals

    def _advise(
        self, counts_h: Optional[List[int]], rows: Optional[int] = None
    ) -> None:
        """Feed the stats advisor (KOLIBRIE_STATS_ADVISOR=auto) from one
        execution's host-resident numbers; no-op when the advisor is off
        or no template fingerprint is in flight."""
        if _sa.stats_advisor_mode() == "off":
            return
        fp = _sa.current_fp()
        if fp is None:
            fp = _get_baggage("template", "unknown")
            if fp == "unknown":
                return
        actuals = self.advisor_actuals(counts_h) if counts_h else {}
        if rows is not None:
            actuals["result"] = float(rows)
        if actuals:
            _sa.stats_advisor.observe(
                fp, actuals, version=self.db.store.version_key()
            )

    def _aggregate(self, stage, out_cols, valid):
        """The dispatch's last stage under ``device.aggregate``: the plan's
        device-resident columns through :func:`aggregate_table` at the
        template's group capacity.  Returns ``(table, rows)``: one row a
        group, and the rows the plan produced."""
        _note_fetch("aggregate")
        with _obs_span("device.aggregate") as sp:
            table, rows, cap = aggregate_table(
                self.db, out_cols, valid, stage, self.cap_key
            )
            if sp is not None:
                groups = len(next(iter(table.values()))) if table else 0
                sp.attrs.update(groups=groups, cap=cap, rows=rows)
        return table, rows

    def to_table(self, out_cols, valid) -> BindingTable:
        _note_fetch("to_table")
        valid_h = np.asarray(valid)
        return {
            var: np.asarray(col)[valid_h].astype(np.uint32)
            for var, col in zip(self.out_vars, out_cols)
        }

    def fetch_stats(self) -> Dict[str, int]:
        """Host-read the per-operator stats of the last converged run.
        ONE extra device→host sync, paid only by EXPLAIN ANALYZE — the
        hot path never calls this."""
        stats = getattr(self, "_last_stats", None)
        if not stats:
            return {}
        _note_fetch("analyze.stats")
        fetched = jax.device_get(stats)
        return {k: int(v) for k, v in fetched.items()}

    def describe(self, counts: Optional[List[int]] = None,
                 analyze: Optional[Dict] = None,
                 drift: Optional[Dict] = None) -> str:
        """Readable physical-plan tree for EXPLAIN surfaces: scans with
        their sorted order + bound constants + live range size, joins with
        key variables, capacities and (when provided) exact match counts,
        filters, and quoted expansions.  ``counts`` is the per-join exact
        count list from :meth:`host_execute`/calibration.

        ``analyze`` is a capture record from an actual dispatch (see
        :mod:`kolibrie_tpu.obs.analyze`): its ``operators`` map annotates
        every node with ``actual=`` rows (estimated-vs-actual side by
        side) and joins/WCOJ levels with cap ``occ=`` percentages.

        ``drift`` is a stats-advisor report's ``ops`` map (advisor
        operator key -> (est, actual)); matching nodes gain an
        ``est=/actual=/x-off=`` drift column."""
        scan_ranges = self._host_scan_ranges()
        lines: List[str] = []
        ops = (analyze or {}).get("operators", {}) or {}
        acounts = (analyze or {}).get("counts", []) or []
        dseq = {"filter": 0, "anti": 0, "union": 0, "quoted": 0}
        dmap: Dict[str, tuple] = {}
        if drift:
            for _src, _idx, akey, dkey in self._advisor_sites():
                pair = drift.get(akey)
                if pair is not None:
                    dmap[dkey] = pair

        def term(c):
            return "?" if c is None else str(c)

        def drift_col(dkey):
            pair = dmap.get(dkey)
            if pair is None:
                return ""
            est, act = pair
            if est is None or act is None:
                return ""
            xoff = max(est, act) / max(min(est, act), 1.0)
            return f" est={est:.0f} actual={act:.0f} x-off={xoff:.1f}"

        def actual(key):
            base = f" actual={ops[key]}" if key in ops else ""
            return base + drift_col(key)

        def occ(join_idx, cap):
            from kolibrie_tpu.query.template import occupancy_pct

            if join_idx < len(acounts) and isinstance(cap, int) and cap > 0:
                return f" occ={occupancy_pct(acounts[join_idx], cap):.1f}%"
            return ""

        def walk(node, depth):
            pad = "  " * depth
            if isinstance(node, ScanSpec):
                order_name, consts = self.scan_descs[node.scan_idx]
                lo, n = (int(x) for x in scan_ranges[node.scan_idx])
                vars_ = " ".join(f"?{v}@{p}" for v, p in node.out_vars)
                lines.append(
                    f"{pad}scan[{order_name}] ({term(consts[0])} "
                    f"{term(consts[1])} {term(consts[2])}) rows={n}"
                    f"{actual(f'scan{node.scan_idx}')} binds {vars_}"
                )
            elif isinstance(node, JoinSpec):
                cnt = (
                    f" matched={counts[node.join_idx]}"
                    if counts is not None and node.join_idx < len(counts)
                    else ""
                )
                jcaps = getattr(self, "_join_caps", None)
                cap = jcaps[node.join_idx] if jcaps else "?"
                kind = "merge(rsorted)" if node.rsorted else "sort"
                lines.append(
                    f"{pad}{kind}-join on ({', '.join(node.key_vars)})"
                    f" cap={cap}{cnt}{actual(f'join{node.join_idx}')}"
                    f"{occ(node.join_idx, cap)}"
                )
                walk(node.left, depth + 1)
                walk(node.right, depth + 1)
            elif isinstance(node, AntiJoinSpec):
                key = f"anti{dseq['anti']}"
                dseq["anti"] += 1
                lines.append(
                    f"{pad}anti-join (MINUS/NOT) on"
                    f" ({', '.join(node.key_vars)}){actual(key)}"
                )
                walk(node.left, depth + 1)
                walk(node.right, depth + 1)
            elif isinstance(node, LeftOuterSpec):
                cnt = (
                    f" matched={counts[node.join_idx]}"
                    if counts is not None and node.join_idx < len(counts)
                    else ""
                )
                lines.append(
                    f"{pad}left-outer-join (OPTIONAL) on"
                    f" ({', '.join(node.key_vars)}){cnt}"
                    f"{actual(f'optional{node.join_idx}')}"
                )
                walk(node.left, depth + 1)
                walk(node.right, depth + 1)
            elif isinstance(node, UnionSpec):
                key = f"union{dseq['union']}"
                dseq["union"] += 1
                lines.append(
                    f"{pad}union -> ({', '.join(node.vars)}){actual(key)}"
                )
                for ch in node.children:
                    walk(ch, depth + 1)
            elif isinstance(node, FilterSpec):
                key = f"filter{dseq['filter']}"
                dseq["filter"] += 1
                lines.append(f"{pad}filter {node.expr}{actual(key)}")
                walk(node.child, depth + 1)
            elif isinstance(node, QuotedExpandSpec):
                key = f"quoted{dseq['quoted']}"
                dseq["quoted"] += 1
                vars_ = " ".join(f"?{v}@{p}" for v, p in node.out_vars)
                lines.append(
                    f"{pad}quoted-expand {node.qvar} -> "
                    f"{vars_ or '(checks only)'}{actual(key)}"
                )
                walk(node.child, depth + 1)
            elif isinstance(node, WcojSpec):
                jcaps = getattr(self, "_join_caps", None)
                lines.append(
                    f"{pad}wcoj elim=["
                    + " ".join(f"?{lv.var}" for lv in node.levels)
                    + "]"
                )
                for lv in node.levels:
                    cnt = (
                        f" rows={counts[lv.join_idx]}"
                        if counts is not None and lv.join_idx < len(counts)
                        else ""
                    )
                    cap = jcaps[lv.join_idx] if jcaps else "?"
                    wcaps = getattr(self, "_window_caps", {})
                    accs = ", ".join(
                        f"{self.order_names[a.order_idx]}"
                        f"/k{len(a.key_srcs)}"
                        # the base rows its searches run over, where a
                        # constant selects a window of the order
                        + (f"/w{w}" if (w := wcaps.get((lv.join_idx, i))) else "")
                        for i, a in enumerate(lv.accessors)
                    )
                    act = ""
                    ck = f"wcoj{lv.join_idx}:cand"
                    if ck in ops:
                        act = (
                            f" cand={ops[ck]}"
                            f" dedup={ops.get(f'wcoj{lv.join_idx}:dedup', '?')}"
                            f" live={ops.get(f'wcoj{lv.join_idx}:live', '?')}"
                        )
                    lines.append(
                        f"{pad}  level ?{lv.var} cap={cap}{cnt}{act}"
                        f"{drift_col(f'wcoj{lv.join_idx}:live')}"
                        f"{occ(lv.join_idx, cap)} [{accs}]"
                    )
            elif isinstance(node, ValuesSpec):
                lines.append(f"{pad}values({', '.join(node.vars)}) rows={node.n}")
            else:
                lines.append(f"{pad}{type(node).__name__}")

        walk(self.root, 0)
        for s, p, o in self.const_checks:
            lines.append(f"const-guard ({s} {p} {o})")
        if self.u_params or self.f_params:
            lines.append(
                f"params u32={list(self.u_params)} f64={list(self.f_params)}"
            )
        lines.append(f"project -> {' '.join('?' + v for v in self.out_vars)}")
        return "\n".join(lines)

    def const_ok(self) -> bool:
        """Evaluate the hoisted fully-constant pattern guards against the
        CURRENT store (host binary searches; no device op).  False ⇒ the
        query's result is empty regardless of the plan tree."""
        if not self.const_checks:
            return True
        order = self.db.store.order("spo")
        for s, p, o in self.const_checks:
            if s is None or p is None or o is None:
                return False  # unknown constant can never match
            lo, hi = order.range012(s, p, o)
            if lo >= hi:
                return False
        return True

    def empty_table(self) -> BindingTable:
        return {v: np.empty(0, dtype=np.uint32) for v in self.out_vars}

    # how the last execute() produced its rows: "mqo" (shared-prefix
    # fan-out), "interp" (plan-bytecode interpreter), "compiled"
    # (specialized jit, compiled or warm), or "disk" (specialized jit
    # whose executable loaded from the persistent compilation cache).
    # Plan-cache slots surface this as `source`.
    last_source: Optional[str] = None
    # the aggregation the dispatch in flight ends in (execute's argument),
    # and the groups a calibration of this build counted for it
    _stage: Optional["AggregateStage"] = None
    _calibrated_groups: Optional[int] = None
    _groups_are_ceiling = False

    def execute(self, stage: Optional["AggregateStage"] = None) -> BindingTable:
        """Run to completion with capacity validation; returns a host table:
        the plan's rows or, with ``stage``, one row a group -- the rows then
        stay on the device and the aggregation (``device.aggregate``: sort,
        segment reduction, the groups' readback) takes the place of the
        readback (``device.collect``)."""
        self._stage = stage
        # deadline check BEFORE the dispatch (don't start device work the
        # client stopped waiting for) and a fault point that can inject
        # kernel latency / simulated device OOM for the chaos tests
        check_deadline("device.execute")
        fault_point("device.execute")
        if not self.const_ok():
            return self.empty_table()
        tpl = _get_baggage("template", "unknown")
        # multi-query sharing: when KOLIBRIE_MQO routes this template to a
        # shared scan/join prefix, the prefix table comes from the
        # version-keyed cache (or one interpreter dispatch) and only the
        # filter suffix runs per member (optimizer/mqo.py, docs/MQO.md)
        from kolibrie_tpu.optimizer import mqo as _mqo

        # the shared-prefix and interpreter routes hand back host rows: an
        # aggregation takes the specialized executable's device columns
        if stage is None and _mqo.mqo_mode() != "off":
            t0 = _time.perf_counter()
            table = _mqo.try_shared_execute(self)
            if table is not None:
                self.last_source = "mqo"
                _DISPATCH_LAT.labels(tpl).observe(_time.perf_counter() - t0)
                check_deadline("device.execute.done")
                return table
        # zero-compile cold path: KOLIBRIE_PLAN_INTERP routes eligible
        # templates through the plan-bytecode interpreter until the
        # specialized executable exists (docs/COMPILE_CACHE.md); a shape
        # the interpreter declines falls through to the specialized path
        from kolibrie_tpu.optimizer import plan_interp

        if stage is None and plan_interp.should_interp(self):
            t0 = _time.perf_counter()
            table = plan_interp.interp_execute(self)
            if table is not None:
                self.last_source = "interp"
                _DISPATCH_LAT.labels(tpl).observe(_time.perf_counter() - t0)
                check_deadline("device.execute.done")
                return table
        t0 = _time.perf_counter()
        with _obs_span("device.dispatch", template=tpl):
            parts = self.converge(self.run())
        _DISPATCH_LAT.labels(tpl).observe(_time.perf_counter() - t0)
        plan_interp.mark_compiled(self)
        sight = _cc.last_sight()  # of the run whose rows these are
        self.last_source = (
            "disk" if sight is not None and sight["outcome"] == "hit" else "compiled"
        )
        t1 = _time.perf_counter()
        if stage is None:
            with _obs_span("device.collect"):
                table = self.to_table(*parts)
            _COLLECT_LAT.observe(_time.perf_counter() - t1)
            nrows = len(next(iter(table.values()))) if table else 0
        else:
            table, nrows = self._aggregate(stage, *parts)
            _AGGREGATE_LAT.observe(_time.perf_counter() - t1)
        self._advise(None, rows=nrows)
        cap = _analyze.active()
        if cap is not None:
            cap.record(
                "device",
                source=self.last_source,
                operators=self.fetch_stats(),
                counts=list(getattr(self, "_last_counts", [])),
                caps=list(self._join_caps),
                rows=nrows,
            )
        check_deadline("device.execute.done")
        return table


def string_filter_mask(db, name: str, pattern: str, which: str) -> np.ndarray:
    """Per-ID verdicts for a constant-pattern string predicate: ``which`` =
    'dict' evaluates over every dictionary term, 'quoted' over every quoted
    ID's decoded RDF-star form (so quoted-valued variables keep host
    semantics).  One sentinel False entry keeps empty stores shaped."""
    from kolibrie_tpu.core.dictionary import QUOTED_BIT

    from kolibrie_tpu.optimizer.engine import strip_literal

    if which == "dict":
        strs = [strip_literal(s) for s in db.dictionary.id_to_str]
    else:
        strs = [
            strip_literal(db.decode_term(QUOTED_BIT | i))
            for i in range(len(db.quoted))
        ]
    if not strs:
        strs = [None]
    if name == "REGEX":
        import re

        rx = re.compile(pattern or "")
        return np.array([bool(rx.search(s or "")) for s in strs], dtype=bool)
    if name == "CONTAINS":
        return np.array(
            [(s or "").find(pattern or "") >= 0 for s in strs], dtype=bool
        )
    if name == "STRSTARTS":
        return np.array(
            [(s or "").startswith(pattern or "") for s in strs], dtype=bool
        )
    return np.array(
        [(s or "").endswith(pattern or "") for s in strs], dtype=bool
    )


def numeric_filter_mask(vals: np.ndarray, op: str, const: float) -> np.ndarray:
    """Per-ID boolean mask for ``term op const`` over the database's
    numeric-literal table (NaN = non-numeric, always excluded).  The ONE
    definition of numeric-filter semantics shared by the single-chip plan
    lowering and the distributed query executor."""
    with np.errstate(invalid="ignore"):
        if op == "=":
            m = vals == const
        elif op == "!=":
            m = vals != const
        elif op == "<":
            m = vals < const
        elif op == "<=":
            m = vals <= const
        elif op == ">":
            m = vals > const
        else:
            m = vals >= const
    return m & ~np.isnan(vals)


def base_key_group_rows(
    db, order_name: str, n_bound: int, predicate: Optional[int] = None
) -> int:
    """The most rows any one key of ``order_name``'s first ``n_bound``
    columns holds in the FROZEN base segment: what bounds a scan's base
    range (:func:`template_scan_cap`) and a WCOJ accessor's window
    (``LoweredPlan._template_window_caps``) for ANY constant variant.
    Where ``predicate`` is named (a dictionary id; one the dictionary does
    not know holds 0 rows) beside at most one of subject and object, the
    group is the largest AMONG THE ROWS UNDER THAT PREDICATE
    (:func:`stats.hottest_key_rows`, the table the planner orders keyed
    scans by): the predicate's own base rows, or those of its hottest
    subject or object.  Without one, the largest group over the whole
    store: O(base) to compute, cached per ``base_version`` on the
    database."""
    store = db.store
    base = store.base_order(order_name)
    nb = len(base)
    if nb == 0 or n_bound <= 0:
        return nb
    if predicate is not None:
        other = [c for c in base.perm[:n_bound] if c != "p"]
        return hottest_key_rows(db, predicate, other[0] if other else "p")

    def count() -> int:
        rows = base.slice_rows(0, nb)
        change = np.zeros(nb, dtype=bool)
        change[0] = True
        for c in base.perm[:n_bound]:
            col = rows[c]
            change[1:] |= col[1:] != col[:-1]
        bounds = np.append(np.flatnonzero(change), nb)
        return int(np.max(np.diff(bounds)))

    return _caps.of(db).largest_key_group(
        order_name, n_bound, store.base_version, count
    )


def template_scan_cap(
    db, order_name: str, n_bound: int, predicate: Optional[int] = None
) -> int:
    """Upper bound on ANY constant-variant's merged (base + delta) range
    for a scan whose ``order_name`` prefix binds ``n_bound`` columns: the
    largest key-group of that prefix in the FROZEN base segment
    (:func:`base_key_group_rows`: among the rows under the ``predicate``
    the scan names, where it names one) plus the fixed delta device
    capacity.  Every instance of a text names the same predicates, so
    ``ScanSpec.cap`` stays a property of the TEMPLATE rather than of one
    variant's other constants (shape-stable compilation), and a scan is as
    wide as the predicate it names, not as the store's largest.  Because
    the base is frozen at ``base_version`` and the delta tier holds at most
    ``delta_device_cap`` rows in all, the bound survives every incremental
    mutation batch."""
    return (
        base_key_group_rows(db, order_name, n_bound, predicate)
        + db.store.delta_device_cap
    )


# the column a freed scan's subject or object rides in through the twin's
# joins (LoweredPlan.host_execute, free_scan): no variable can have this name
_FREE_KEY = "\0key"


def predicate_rows(order, predicate: int) -> Dict[str, np.ndarray]:
    """Every row of sorted ``order`` under ``predicate``, as ``slice_rows``
    gives a range: a slice where the order leads with the predicate, a
    gather where the predicate is its second column (``spo``, ``ops``)."""
    at = order.perm.index("p")
    if at == 0:
        p = order.c0.dtype.type(predicate)
        return order.slice_rows(
            int(np.searchsorted(order.c0, p, side="left")),
            int(np.searchsorted(order.c0, p, side="right")),
        )
    col = (order.c0, order.c1, order.c2)[at]
    rows = np.flatnonzero(col == col.dtype.type(predicate))
    return {
        name: c[rows] for name, c in zip(order.perm, (order.c0, order.c1, order.c2))
    }


class _HotPassCut(Exception):
    """A hot pass of the twin met a join too large to materialize and
    counted it without (:func:`_freed_join_rows`).  ``counts``: the pass's
    join counts up to and with that join, 0 above it.  ``reached``: the
    indices of the joins it counted, that one last.  ``rows``: that
    count where the join is the plan's topmost (only filters above it, so
    no group of the freed keys holds more rows, nor more groups), else
    ``None``."""

    def __init__(self, counts: List[int], reached: List[int], rows: Optional[int]):
        super().__init__(rows)
        self.counts, self.reached, self.rows = counts, reached, rows


def _reads_a_constant(expr) -> bool:
    """Whether a FILTER's expression compares with a constant of the text:
    one that rides in the parameter vectors, or one a mask was computed
    from.  Neither is part of ``cap_key``."""
    if isinstance(expr, BoolNode):
        return any(_reads_a_constant(a) for a in expr.args)
    return isinstance(expr, (IdCmp, NumConstCmp, MaskRef, StrMaskRef))


def _top_join(node):
    """The join whose rows, filtered, are the plan's; ``None`` where the
    root is no chain of filters over a join."""
    while isinstance(node, FilterSpec):
        node = node.child
    return node if isinstance(node, JoinSpec) else None


def _freed_join_rows(lcols, rcols, lkey, rkey) -> Optional[int]:
    """The most rows one combination of freed keys gives a join, counted
    without materializing it: each row of the side that carries the freed
    columns matches as many rows as the other side holds under its key, and
    the matches are summed by freed tuple.  ``None`` where neither side or
    both sides carry freed columns (the pass then gives up, as before)."""
    freed_l, freed_r = _freed_columns(lcols), _freed_columns(rcols)
    if bool(freed_l) == bool(freed_r):
        return None
    freed = freed_l or freed_r
    own, other = (lkey, rkey) if freed_l else (rkey, lkey)
    other = np.sort(other)
    matches = np.searchsorted(other, own, "right") - np.searchsorted(other, own, "left")
    order = np.lexsort(freed[::-1])
    starts = np.flatnonzero(_tuple_starts(freed, order))
    return int(np.add.reduceat(matches[order], starts).max()) if len(own) else 0


def _freed_columns(table) -> List[np.ndarray]:
    """The columns of a twin's table that freed scans' keys ride in."""
    return [col for name, col in table.items() if name.startswith(_FREE_KEY)]


def _tuple_starts(cols, order: np.ndarray) -> np.ndarray:
    """Mask, over rows taken in ``order`` (sorted by ``cols``), of the rows
    whose tuple of ``cols`` differs from the row before: each run's first."""
    starts = np.zeros(len(order), dtype=bool)
    starts[:1] = True
    for col in cols:
        col = col[order]
        starts[1:] |= col[1:] != col[:-1]
    return starts


def _largest_group(*keys: np.ndarray) -> int:
    """Rows of the value that ``keys`` holds most often (of the tuple of
    values that several columns hold most often together); 0 of no rows."""
    n = len(keys[0])
    if not n:
        return 0
    if len(keys) == 1:
        return int(np.unique(keys[0], return_counts=True)[1].max())
    starts = _tuple_starts(keys, np.lexsort(keys[::-1]))
    return int(np.diff(np.append(np.flatnonzero(starts), n)).max())


def _most_groups(freed, keys) -> int:
    """The most distinct tuples of the columns ``keys`` that the rows of one
    tuple of the columns ``freed`` hold (of the whole table where no column
    is freed); 0 of no rows."""
    cols = list(freed) + list(keys)
    if not cols or not len(cols[0]):
        return 0
    order = np.lexsort(cols[::-1])
    new_freed = _tuple_starts(freed, order)
    new_key = new_freed | _tuple_starts(keys, order)
    if not freed:
        return int(new_key.sum())
    return int(np.bincount(np.cumsum(new_freed)[new_key] - 1).max())


def lower_plan(db, plan, anti_plans=(), union_groups=(), optional_plans=()) -> LoweredPlan:
    # resilience hooks: an injected compile fault raises DeviceFault (NOT
    # Unsupported — transient, counted by the circuit breaker, never
    # recorded as a sticky lowering sentinel); an expired deadline sheds
    # the request before lowering work starts
    check_deadline("device.lower")
    fault_point("device.lower")
    tpl = _get_baggage("template", "unknown")
    t0 = _time.perf_counter()
    with _obs_span("device.lower", template=tpl):
        lowered = LoweredPlan(db, plan, anti_plans, union_groups, optional_plans)
    _LOWER_LAT.labels(tpl).observe(_time.perf_counter() - t0)
    return lowered


def execute_plan_batch(lowereds: List[LoweredPlan]) -> List[BindingTable]:
    """Run MANY constant-variants of ONE plan template as a single device
    dispatch (:func:`_run_plan_batch`): the members' scan ranges and packed
    parameter vectors are the first rows of matrices as long as the slot
    class of their number (``ops.slot_class``), the store operands are the
    first member's, and the program runs the live rows only.  Returns one
    host table per input, each identical to that plan's own ``execute()``.

    Every member must have lowered to the same template: equal assembled
    spec, which members of one fingerprint have, the predicates their scans
    are sized by being part of it (two texts of one shape that name
    different predicates are two fingerprints, and a group whose specs
    differ is refused here as ``Unsupported``); members with string
    masks must carry identical patterns, and VALUES templates are not
    batchable (their rows are per-variant constants outside the parameter
    ABI).  Capacities, occupancy and the readback see the live members
    only: a padded slot counts nothing and is not read.

    Spans and timings as a lone ``execute()`` has them: ``device.dispatch``
    (build to the counts read back, capacity re-runs in it), then
    ``device.collect``."""
    if not lowereds:
        return []
    check_deadline("device.batch")
    fault_point("device.batch")
    base = lowereds[0]
    for lp in lowereds[1:]:
        if lp.mask_exprs != base.mask_exprs:
            raise Unsupported("batch members differ in string-mask patterns")
        if lp.values_tables or base.values_tables:
            raise Unsupported("VALUES templates are not batchable")
    tpl = _get_baggage("template", "unknown")
    _DEVICE_BATCH_SIZE.observe(len(lowereds))
    results: List[Optional[BindingTable]] = [None] * len(lowereds)
    live = []
    for i, lp in enumerate(lowereds):
        if lp.const_ok():
            live.append(i)
        else:
            results[i] = lp.empty_table()
    if not live:
        return results
    members = [lowereds[i] for i in live]
    t0 = _time.perf_counter()
    with _obs_span("device.dispatch", template=tpl, batch=len(members)):
        blocks, bstats = _converge_plan_batch(members)
    _DISPATCH_LAT.labels(tpl).observe(_time.perf_counter() - t0)
    cap = _analyze.active()
    if cap is not None:
        # batched stats leaves are [slots]: one fetch, sliced per member
        bstats_h = {k: np.asarray(v) for k, v in jax.device_get(bstats).items()}
        _note_fetch("analyze.batch_stats")
        for b, i in enumerate(live):
            cap.record(
                "device_batch",
                member=i,
                operators={k: int(v[b]) for k, v in bstats_h.items()},
                caps=list(members[0]._join_caps),
            )
    t1 = _time.perf_counter()
    with _obs_span("device.collect", members=len(members)):
        _note_fetch("batch.rows")
        blocks_h = jax.device_get(list(blocks))
    _COLLECT_LAT.observe(_time.perf_counter() - t1)
    for i, lp, block in zip(live, members, blocks_h):
        v = block[-1] != 0
        results[i] = {var: col[v] for var, col in zip(lp.out_vars, block)}
    return results


def _converge_plan_batch(members: List[LoweredPlan]):
    """Dispatch the group in the slot class of its size until every live
    member's join counts fit the template's capacities: one overflow grows
    the shared capacity for everyone and re-runs the group (one executable
    a capacity set, whatever the group).  Returns the live members' row
    blocks and the ``[slots]`` stats, device-resident."""

    from kolibrie_tpu.ops import slot_class
    from kolibrie_tpu.ops.pallas_kernels import pallas_enabled
    from kolibrie_tpu.query.template import (
        cap_retry_seconds,
        note_cap_occupancy,
        note_cap_retry,
    )

    lp0 = members[0]
    n, slots = len(members), slot_class(len(members))

    def rows(of, dtype):
        """The members' ``of`` as the first rows of a ``[slots, ...]`` matrix."""
        live_rows = np.asarray([of(lp) for lp in members], dtype=dtype)
        mat = np.zeros((slots, *live_rows.shape[1:]), dtype=dtype)
        mat[:n] = live_rows
        return mat

    def run(attempt):
        spec0, base_args = _build_traced(lp0, 0)
        for lp in members[1:]:
            spec, _ = _build_traced(lp, 0, operands=False)
            if spec != spec0:
                raise Unsupported(
                    "batch members lowered to different templates"
                )
        # the first member's own scan ranges and parameters are numpy arrays
        # its build held anyway; the group's three matrices go up with the
        # call as the solo path's vectors do
        order_arrays, _sc, tiers, masks, values, numf, quoted, _pp = base_args
        with jax.enable_x64(True):
            out = _enqueue_traced(
                _run_plan_batch,
                spec0,
                pallas_enabled(),
                order_arrays,
                rows(lambda lp: lp._scan_ranges_np, np.int32),
                np.int32(n),
                tiers,
                masks,
                values,
                numf,
                quoted,
                (
                    rows(lambda lp: lp.u_params or [0], np.uint32),
                    rows(lambda lp: lp.f_params or [0.0], np.float64),
                ),
            )
        return out, lp0._join_caps, _read_counts(out, out[1], attempt, np.asarray)

    def tally(counts_b, caps):
        note_cap_occupancy(
            "device", n * sum(caps), sum(int(np.sum(c)) for c in counts_b)
        )
        lp0._note_scan_tiers(n)
        lp0._note_range_searches(n)
        lp0._note_join_searches(
            [
                (lp._scan_ranges_np, [c[m] for c in counts_b])
                for m, lp in enumerate(members)
            ]
        )
        lp0._note_scan_occupancy([lp._scan_ranges_np for lp in members])
        return [int(np.max(c)) for c in counts_b]

    (blocks, _counts, bstats), _ran_with, _most = _caps.run_until_fits(
        _caps.of(lp0.db).joins,
        lp0.cap_key,
        run,
        tally,
        retried=lambda: note_cap_retry("device"),
        rerun_seconds=cap_retry_seconds.labels("device").inc,
    )
    _BATCH_DISPATCHES.inc()
    _BATCH_MEMBERS.inc(n)
    _BATCH_MEMBER_SLOTS.inc(slots)
    return blocks[:n], bstats


def try_device_execute(
    db, plan, anti_plans=(), union_groups=(), optional_plans=(), capture=None
) -> Optional[BindingTable]:
    """Device path if the plan is expressible, else ``None`` (host fallback).

    ``anti_plans``: physical plans of MINUS / NOT-block branches (device
    anti-joins); ``union_groups``: per-UNION-group tuples of branch plans
    (device concat + join); ``optional_plans``: OPTIONAL branch plans
    (device left-outer joins).  All compose over the main tree in the host
    post-pass order, so the whole group pattern is one device program.
    ``capture``: plan-cache entry — records the lowered program (``False``
    when this plan cannot lower) so the next identical query skips
    lowering/compilation entirely."""
    try:
        lowered = lower_plan(db, plan, anti_plans, union_groups, optional_plans)
    except Unsupported:
        if capture is not None:
            capture["lowered"] = False
        return None
    if capture is not None:
        capture["lowered"] = lowered
    return lowered.execute()


# ---------------------------------------------------------------------------
# Device GROUP BY / aggregation (BASELINE config 2 on device)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("gpos", "funcs", "apos", "distincts", "cap"))
def _segment_aggregate(cols, valid, numf, gpos, funcs, apos, distincts, cap):
    """Segment-reduce the final plan table ON DEVICE: stable multi-operand
    sort by the group key columns, first-occurrence segment ids,
    scatter-reduce per aggregate.

    ``gpos``: positions of the group columns in ``cols`` (ANY count — the
    key rides as parallel sort operands, not a packed word); ``funcs``:
    aggregate names (COUNT/SUM/AVG/MIN/MAX/SAMPLE); ``apos``: per-aggregate
    value column position (or -1 for COUNT(*)); ``distincts``: per-aggregate
    DISTINCT flag (honored for COUNT — host parity: other funcs ignore it).
    Returns (group id cols, f64-or-id agg arrays, n_groups, n_rows) with
    static length ``cap`` — readback is O(group capacity), not O(rows);
    ``n_rows`` counts the valid rows the sort carried among its slots."""
    import jax.numpy as jnp
    from jax import lax

    n = valid.shape[0]
    sent = np.uint32(0xFFFFFFFF)  # never a real ID (dictionary.rs:36-40)
    if gpos:
        keys = [jnp.where(valid, cols[g], sent) for g in gpos]
    else:
        # aggregate without GROUP BY: one group holding every valid row
        keys = [jnp.where(valid, jnp.uint32(0), sent)]
    iota = jnp.arange(n, dtype=jnp.int32)
    sorted_ops = lax.sort(
        (*keys, iota), num_keys=len(keys), is_stable=True
    )
    order = sorted_ops[-1]
    ks = sorted_ops[:-1]
    rowok = ks[0] != sent  # invalid rows carry the sentinel in EVERY key
    isnew = jnp.zeros(n, bool).at[0].set(True)
    for k in ks:
        isnew = isnew | jnp.concatenate([jnp.ones(1, bool), k[1:] != k[:-1]])
    isnew = isnew & rowok
    if not gpos:
        # SPARQL: an empty input still yields ONE group (COUNT()=0)
        isnew = isnew.at[0].set(True)
    seg = jnp.cumsum(isnew) - 1
    n_groups = jnp.sum(isnew)
    segc = jnp.where(rowok, seg, cap)

    group_cols = []
    gdest = jnp.where(isnew, seg, cap)
    for k in ks[: len(gpos)]:
        group_cols.append(
            jnp.zeros(cap, jnp.uint32).at[gdest].set(k, mode="drop")
        )

    def _distinct_first(vcol):
        """Mask (in ORIGINAL row order) of the first occurrence of each
        (group key, value) pair — one extra sort per DISTINCT aggregate."""
        ops = lax.sort((*keys, jnp.where(valid, vcol, sent), iota),
                       num_keys=len(keys) + 1)
        vs, it2 = ops[-2], ops[-1]
        firstp = jnp.zeros(n, bool).at[0].set(True)
        for k in ops[: len(keys)]:
            firstp = firstp | jnp.concatenate(
                [jnp.ones(1, bool), k[1:] != k[:-1]]
            )
        firstp = firstp | jnp.concatenate([jnp.ones(1, bool), vs[1:] != vs[:-1]])
        # back to original row order
        return jnp.zeros(n, bool).at[it2].set(firstp)

    agg_out = []
    for func, ap, dst_flag in zip(funcs, apos, distincts):
        if func == "COUNT" and ap < 0:
            counts = (
                jnp.zeros(cap, jnp.float64)
                .at[segc]
                .add(jnp.ones(n, jnp.float64), mode="drop")
            )
            agg_out.append(counts)
            continue
        col = cols[ap][order]
        if func == "SAMPLE":
            # stable sort ⇒ the segment's first row is the FIRST row of the
            # group in plan-output order (host parity: seg[0]); value is a
            # term id, not a number.  The forced group of a no-GROUP-BY
            # aggregate can be EMPTY — its gdest points at an invalid row,
            # so guard with the per-group row count (host: UNBOUND=0).
            cnt0 = (
                jnp.zeros(cap, jnp.float64)
                .at[segc]
                .add(jnp.ones(n, jnp.float64), mode="drop")
            )
            ids = jnp.zeros(cap, jnp.uint32).at[gdest].set(col, mode="drop")
            agg_out.append(jnp.where(cnt0 == 0, jnp.uint32(0), ids))
            continue
        if func == "COUNT":
            ok = segc < cap
            bound = ok & (col != np.uint32(0))  # 0 = UNBOUND sentinel
            if dst_flag:
                bound = bound & _distinct_first(cols[ap])[order]
            agg_out.append(
                jnp.zeros(cap, jnp.float64)
                .at[jnp.where(bound, segc, cap)]
                .add(jnp.ones(n, jnp.float64), mode="drop")
            )
            continue
        vals = numf[jnp.minimum(col, numf.shape[0] - 1)]
        ok = (segc < cap) & ~jnp.isnan(vals)
        dst = jnp.where(ok, segc, cap)
        v0 = jnp.where(ok, vals, 0.0)
        # one numeric-value count per segment, shared by every func below:
        # emptiness (→ NaN → UNBOUND) is decided by COUNT, never by the
        # reduction's identity value — a genuine ±inf literal must survive
        cnt = (
            jnp.zeros(cap, jnp.float64)
            .at[dst]
            .add(jnp.ones(n, jnp.float64), mode="drop")
        )
        if func in ("SUM", "AVG"):
            sums = (
                jnp.zeros(cap, jnp.float64).at[dst].add(v0, mode="drop")
            )
            res = sums / jnp.where(cnt == 0, 1.0, cnt) if func == "AVG" else sums
            agg_out.append(jnp.where(cnt == 0, jnp.nan, res))
        elif func == "MIN":
            mins = (
                jnp.full(cap, jnp.inf, jnp.float64)
                .at[dst]
                .min(jnp.where(ok, vals, jnp.inf), mode="drop")
            )
            agg_out.append(jnp.where(cnt == 0, jnp.nan, mins))
        else:  # MAX
            maxs = (
                jnp.full(cap, -jnp.inf, jnp.float64)
                .at[dst]
                .max(jnp.where(ok, vals, -jnp.inf), mode="drop")
            )
            agg_out.append(jnp.where(cnt == 0, jnp.nan, maxs))

    return tuple(group_cols), tuple(agg_out), n_groups, jnp.sum(valid)


_DEVICE_AGG_FUNCS = ("COUNT", "SUM", "AVG", "MIN", "MAX", "SAMPLE")


@dataclass(frozen=True)
class AggregateStage:
    """A SELECT's GROUP BY and aggregates over a plan's output columns, as
    the last stage of the plan's dispatch (:meth:`LoweredPlan.execute`) and
    of the distributed executor's: which columns key the groups, which
    aggregate reads which column.  ``key`` is what the segment aggregation
    is compiled for beside the table's width and the group capacity."""

    group_by: tuple  # the group variables' names
    aliases: tuple  # each aggregate's result name
    gpos: tuple  # the group columns' positions among the plan's out_vars
    funcs: tuple  # COUNT / SUM / AVG / MIN / MAX / SAMPLE
    apos: tuple  # the aggregated column's position, -1 for COUNT(*)
    distincts: tuple

    @property
    def key(self) -> tuple:
        return (self.gpos, self.funcs, self.apos, self.distincts)

    @property
    def reads_numbers(self) -> bool:
        """Whether an aggregate reads its column's numeric value (COUNT and
        SAMPLE read term ids alone)."""
        return any(
            f in ("SUM", "AVG", "MIN", "MAX") for f in self.funcs
        )


def aggregate_stage(out_vars, q) -> Optional[AggregateStage]:
    """The aggregation of SELECT ``q`` over a table of ``out_vars``, or
    ``None`` where the device declines the shape (GROUP_CONCAT, DISTINCT on
    an aggregate other than COUNT, an expression in the SELECT list, a
    group key or an aggregated variable the plan does not bind): the host
    then aggregates the plan's rows."""
    agg_items = [i for i in q.select if i.kind == "agg"]
    if not agg_items and not q.group_by:
        return None
    if any(i.kind == "expr" for i in q.select):
        return None  # host semantics drop exprs in agg queries; stay exact
    out_vars = tuple(out_vars)
    gpos, funcs, apos = [], [], []
    for g in q.group_by:
        if g not in out_vars:
            return None
        gpos.append(out_vars.index(g))
    for item in agg_items:
        a = item.agg
        if a.func not in _DEVICE_AGG_FUNCS:
            return None
        if a.distinct and a.func != "COUNT":
            # host parity: DISTINCT only changes COUNT semantics there
            return None
        if a.var is None:
            apos.append(-1)
        elif a.var in out_vars:
            apos.append(out_vars.index(a.var))
        else:
            return None
        funcs.append(a.func)
    return AggregateStage(
        tuple(q.group_by),
        tuple(i.agg.alias for i in agg_items),
        tuple(gpos),
        tuple(funcs),
        tuple(apos),
        tuple(bool(i.agg.distinct) for i in agg_items),
    )


def try_device_execute_aggregated(
    db, plan, q, lowered: Optional[LoweredPlan] = None
) -> Optional[BindingTable]:
    """Plan execution + GROUP BY/aggregation entirely on device; readback is
    one row per GROUP.  The aggregation is the last stage of the plan's own
    dispatch: :meth:`LoweredPlan.execute` with the query's
    :class:`AggregateStage`, so an aggregate request leaves the spans,
    histograms, counters and advisor observations every dispatch leaves,
    and ``device.aggregate`` in place of ``device.collect``.  ``None`` →
    host fallback (:func:`aggregate_stage` declined the shape, the plan
    does not lower, or a constant guard empties the result: the host path
    aggregates nothing).  ``lowered``: caller-supplied device lowering of
    ``plan`` (avoids lowering the same plan twice when the caller also owns
    the fallback path)."""
    if lowered is None:
        try:
            lowered = lower_plan(db, plan)
        except Unsupported:
            return None
    stage = aggregate_stage(lowered.out_vars, q)
    if stage is None or not lowered.const_ok():
        return None
    return lowered.execute(stage)


def host_quoted_table(db):
    """Per-database qid-sorted quoted table as numpy ``(qid, s, p, o)``,
    cached until the quoted store grows.  One sentinel row (all-ones qid —
    never a real ID) keeps shapes non-empty and unmatched when the store
    has no quoted triples.  Shared by the device upload
    (:func:`device_quoted`) and ``host_execute``'s oracle twin."""
    cache = db.__dict__.get("_host_qt_cache")
    n = len(db.quoted)
    if cache is not None and cache[0] == n:
        return cache[1]
    qid = np.full(n + 1, 0xFFFFFFFF, dtype=np.uint32)
    qs = np.zeros(n + 1, dtype=np.uint32)
    qp = np.zeros(n + 1, dtype=np.uint32)
    qo = np.zeros(n + 1, dtype=np.uint32)
    for i, (q, (s, p, o)) in enumerate(db.quoted.items()):
        qid[i], qs[i], qp[i], qo[i] = q, s, p, o
    order = np.argsort(qid, kind="stable")
    arrs = tuple(a[order] for a in (qid, qs, qp, qo))
    db.__dict__["_host_qt_cache"] = (n, arrs)
    return arrs


def device_quoted(db):
    """Device copy of :func:`host_quoted_table`, cached alongside it.
    Padded to a power-of-two row count with extra sentinel rows (all-ones
    qid stays sorted-last and never matches) for shape stability under
    mutation."""

    cache = db.__dict__.get("_operand_qt_cache")
    n = len(db.quoted)
    if cache is not None and cache[0] == n:
        return cache[1]
    qid, qs, qp, qo = host_quoted_table(db)
    arrs = (
        _upload(_pad_pow2(qid, 0xFFFFFFFF)),
        _upload(_pad_pow2(qs, 0)),
        _upload(_pad_pow2(qp, 0)),
        _upload(_pad_pow2(qo, 0)),
    )
    db.__dict__["_operand_qt_cache"] = (n, arrs)
    return arrs


def device_string_ranks(db):
    """Per-ID global string ranks (f64) for device ORDER BY over
    non-numeric keys: every dictionary ID and quoted ID ranked by its RAW
    decoded term (host ``_order_table`` ranks the result subset the same
    way — subset ranks are order-isomorphic to these global ones).
    Returns ``(dict_ranks, quoted_ranks)`` (quoted padded to >= 1), cached
    until either store grows."""
    import jax.numpy as jnp

    from kolibrie_tpu.core.dictionary import QUOTED_BIT

    n_d = len(db.dictionary.id_to_str)
    n_q = len(db.quoted)
    cache = db.__dict__.get("_operand_strrank_cache")
    if cache is not None and cache[0] == (n_d, n_q):
        return cache[1]
    dec = db.decode_term
    strs = [dec(i) or "" for i in range(n_d)] + [
        dec(QUOTED_BIT | i) or "" for i in range(n_q)
    ]
    _, inv = np.unique(np.array(strs), return_inverse=True)
    ranks = inv.astype(np.float64)
    with jax.enable_x64(True):
        # power-of-two padding (real IDs never index the pad slots) keeps
        # operand shapes stable while the dictionary grows
        arrs = (
            jnp.asarray(_pad_pow2(ranks[:n_d], 0.0)),
            jnp.asarray(
                _pad_pow2(
                    ranks[n_d:] if n_q else np.zeros(1, dtype=np.float64),
                    0.0,
                )
            ),
        )
    db.__dict__["_operand_strrank_cache"] = ((n_d, n_q), arrs)
    return arrs


def device_numf(db):
    """Per-database device copy of the numeric-literal table (f64), cached
    until the dictionary grows — the one cache both the single-chip plan
    lowering and the distributed aggregate tail read/populate.

    Padded to a power-of-two capacity with NaN (NaN already means
    "non-numeric": every comparison over it is False) so dictionary growth
    re-uploads the table without changing the operand SHAPE — small
    mutation batches keep riding the compiled plan instead of retracing.
    """

    cache = db.__dict__.get("_operand_numf_cache")
    vals = db.numeric_values()
    n = len(vals)
    if cache is not None and cache[0] == n:
        return cache[1]
    padded = np.full(_round_cap(n, 1024), np.nan)
    padded[:n] = vals
    with jax.enable_x64(True):
        arr = _upload(padded, np.float64)
    db.__dict__["_operand_numf_cache"] = (n, arr)
    return arr


# aggregations being compiled ahead of their first call, by what they are
# compiled for; ``None`` once the call has waited for one
_AHEAD: Dict[tuple, Optional[_threading.Thread]] = {}


def _aggregation_shapes(db, slots: int, ncols: int, stage: "AggregateStage"):
    """The operands of :func:`_segment_aggregate` for a table ``slots`` wide,
    as shapes: what :func:`aggregate_table` will call it with."""
    import jax.numpy as jnp

    numf = _round_cap(len(db.numeric_values()), 1024) if stage.reads_numbers else 1
    return (
        (jax.ShapeDtypeStruct((slots,), jnp.uint32),) * ncols,
        jax.ShapeDtypeStruct((slots,), jnp.bool_),
        jax.ShapeDtypeStruct((numf,), jnp.float64),
    )


def compile_aggregation_ahead(db, slots, ncols, stage, cap) -> None:
    """Start compiling the aggregation that a template's first dispatch will
    end in, on a thread of its own, while the caller compiles and runs the
    plan: the two executables of an aggregate template's first sight cost
    the longer of their compiles, not the sum (each a third to a half of a
    minute at a million slots: ``PERF.md`` section 6, PR 42).
    :func:`aggregate_table` waits for the thread and its call then finds the
    executable: JAX keeps what a lowering compiled, and the persistent
    compilation cache holds the entry besides, so without a cache directory
    nothing is started (a process that caches nothing is not one whose
    first answers are waited for)."""
    sig = (slots, ncols, stage.key, cap)
    if _cc.enabled_dir() is None or sig in _AHEAD:
        return

    def ahead(cols, valid, numf):
        return _segment_aggregate.lower(
            cols, valid, numf, stage.gpos, stage.funcs, stage.apos,
            stage.distincts, cap,
        ).compile()

    # the first-sight record is the entry point's, whichever thread compiled
    ahead.__name__ = _segment_aggregate.__name__
    shapes = _aggregation_shapes(db, slots, ncols, stage)

    def work():
        with jax.enable_x64(True):
            _cc.call(ahead, *shapes)

    thread = _threading.Thread(
        target=work, name="kolibrie-aggregate-compile", daemon=True
    )
    _AHEAD[sig] = thread
    thread.start()


def aggregate_table(
    db, cols, valid, stage: AggregateStage, cap_key
) -> Tuple[BindingTable, int, int]:
    """Shared aggregate tail: run :func:`_segment_aggregate` at the
    template's group capacity and decode the per-group results into a host
    table.  The ONE definition of aggregate readback semantics — used by
    the single-chip engine and the distributed query executor.

    The group capacity is a static argument of the compiled aggregation, so
    it is the template's and not the request's: the one this db holds under
    ``(cap_key, stage.key)`` (what an earlier request fitted in, or what
    :meth:`LoweredPlan.build` counted on the template's first sight), else
    the floor.  Groups beyond it run the aggregation again at
    a capacity that holds them (a counted retry), and what a run fitted in
    is remembered, so the next request of the template starts there.
    Returns ``(table, rows, capacity)``: the valid rows the aggregation
    sorted and the capacity it converged at."""
    from kolibrie_tpu.query.executor import _encode_numbers
    from kolibrie_tpu.query.template import note_aggregate, note_aggregate_retry

    slots = int(valid.shape[0])
    ceiling = group_cap_ceiling(slots)
    key = (cap_key, stage.key)

    def run(_attempt):
        cap = min(_caps.of(db).group_cap(key) or _CAP_FLOOR, ceiling)
        sig = (slots, len(cols), stage.key, cap)
        if _AHEAD.get(sig) is not None:  # being compiled ahead: wait
            _AHEAD[sig].join()
            _AHEAD[sig] = None
        out = _cc.call(
            _segment_aggregate,
            tuple(cols),
            valid,
            numf_dev,
            stage.gpos,
            stage.funcs,
            stage.apos,
            stage.distincts,
            cap,
        )
        return out, (cap,), (int(out[2]), int(out[3]))

    def tally(read, caps):
        note_aggregate(slots, read[1], caps[0], min(read[0], caps[0]))
        return read[:1]

    with jax.enable_x64(True):
        numf_dev = (
            device_numf(db) if stage.reads_numbers else _device_zeros(np.float64)
        )
        (gcols, aggs, _ng, n_rows), (cap,), (ng,) = _caps.run_until_fits(
            _caps.of(db).groups,
            key,
            run,
            tally,
            retried=note_aggregate_retry,
            ceiling=ceiling,
        )
    table: BindingTable = {}
    for g, col in zip(stage.group_by, gcols):
        table[g] = np.asarray(col)[:ng].astype(np.uint32)
    enc = db.dictionary.encode
    for func, alias, arr in zip(stage.funcs, stage.aliases, aggs):
        if func == "SAMPLE":
            # the aggregate IS a term id, not a numeric result
            table[alias] = np.asarray(arr)[:ng].astype(np.uint32)
        else:
            table[alias] = _encode_numbers(enc, np.asarray(arr)[:ng])
    return table, int(n_rows), cap


# ---------------------------------------------------------------------------
# Device ORDER BY + LIMIT (top-k readback)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("opos", "descs", "k"))
def _order_limit(
    cols,
    valid,
    numf,
    opos,
    descs,
    k,
    dranks=None,
    qranks=None,
    nan_overrides=None,
):
    """ORDER BY + LIMIT on device: sort keys gathered from the per-ID
    numeric table — or, when a key column holds ANY non-numeric value
    (the host ``_order_table`` per-column rule), from the global string
    RANKS (``device_string_ranks``; two-level for quoted IDs) — composed
    as lexsort-stable argsorts, first-``k`` slice.  Readback is O(k), not
    O(rows).  Returns ``(sliced cols, sliced valid, n_valid, nan_seen)``.
    Callers run WITHOUT ranks first (numeric ordering pays no host rank
    build); a truthy ``nan_seen`` means re-run with ranks.  Under
    ``shard_map`` the per-key decision must be GLOBAL — pass psum'd
    ``nan_overrides`` (one traced bool per key), or a shard could sort
    numerically while another holds the non-numeric value that switches
    the whole column to string ranks."""
    import jax.numpy as jnp

    n = valid.shape[0]
    perm = jnp.arange(n, dtype=jnp.int32)
    nan_seen = jnp.zeros((), bool)
    keys = []
    for i, (pos, desc) in enumerate(zip(opos, descs)):
        col = cols[pos]
        vals = numf[jnp.minimum(col, numf.shape[0] - 1)]
        if nan_overrides is not None:
            col_nan = nan_overrides[i]
        else:
            col_nan = jnp.any(jnp.isnan(vals) & valid)
        nan_seen = nan_seen | col_nan
        if dranks is not None:
            from kolibrie_tpu.core.dictionary import QUOTED_BIT

            isq = (col & jnp.uint32(QUOTED_BIT)) != 0
            dr = dranks[jnp.minimum(col, dranks.shape[0] - 1)]
            qi = col & jnp.uint32(~QUOTED_BIT & 0xFFFFFFFF)
            qr = qranks[jnp.minimum(qi, qranks.shape[0] - 1)]
            srank = jnp.where(isq, qr, dr)
            # host rule: a single non-numeric value switches the WHOLE
            # column to string-rank ordering
            vals = jnp.where(col_nan, srank, vals)
        keys.append(-vals if desc else vals)
    # lexsort composition: secondary keys first, primary key last, then
    # validity as the outermost key so invalid rows sink to the end
    for key in reversed(keys):
        perm = perm[jnp.argsort(key[perm], stable=True)]
    vkey = jnp.where(valid, 0, 1)
    perm = perm[jnp.argsort(vkey[perm], stable=True)]
    top = perm[:k]
    out = tuple(c[top] for c in cols)
    return out, valid[top], jnp.sum(valid), nan_seen


def clause_replayable(lowered, w) -> bool:
    """True when a cached lowered program may be replayed WITHOUT the host
    clause post-passes: it either fused the WHERE's
    UNION/OPTIONAL/MINUS/NOT branches itself, or the WHERE has none.  A
    plain-BGP lowering for a clause-carrying WHERE must instead replay
    through ``eval_where`` (device BGP + host post-passes) — THE shared
    eligibility rule for every cache-replay site."""
    return getattr(lowered, "fused_clauses", False) or not (
        w.unions or w.optionals or w.minus or w.not_blocks
    )


def try_device_execute_ordered(db, q, cache_entry=None) -> Optional[List[List[str]]]:
    """ORDER BY + LIMIT entirely on device: plan execution, numeric-key
    top-k sort, O(limit) readback (SURVEY §7 step 3 "ORDER BY (device
    sort)").  ``None`` → host fallback (shape not expressible, or a sort
    key is non-numeric — host orders those by decoded-string rank).
    ``cache_entry``: plan-cache slot — repeat ordered queries reuse the
    lowered program instead of re-planning/lowering."""
    from kolibrie_tpu.query.ast import Var
    from kolibrie_tpu.query.executor import (
        _device_routed,
        format_results,
    )

    if not _device_routed(db):
        return None
    if q.limit is None or not q.order_by or q.distinct or q.group_by:
        return None
    if any(i.kind != "var" for i in q.select) and not q.select_all():
        return None
    from kolibrie_tpu.query.subquery_inline import inline_subqueries

    w = inline_subqueries(q.where)
    if w.subqueries or w.binds or w.window_blocks or not w.patterns:
        return None
    # cheap shape checks BEFORE any planning (a rejected query would
    # otherwise pay the optimizer + lowering twice: here and again on the
    # host fallback).  Host parity: eval_select_to_table projects to the
    # SELECT variables BEFORE ordering, so a sort key outside the
    # projection is a no-op there — leave those to the host path.
    pattern_vars = {
        t.value
        for p in w.patterns
        for t in (p.subject, p.predicate, p.object)
        if t.kind == "var"
    }
    sel_vars = (
        pattern_vars
        if q.select_all()
        else {i.var for i in q.select if i.kind == "var"}
    )
    for cond in q.order_by:
        if (
            not isinstance(cond.expr, Var)
            or cond.expr.name not in pattern_vars
            or cond.expr.name not in sel_vars
        ):
            return None

    from kolibrie_tpu.optimizer.engine import resolve_pattern
    from kolibrie_tpu.optimizer.planner import Streamertail, build_logical_plan

    lowered = None
    if cache_entry is not None and cache_entry["lowered"] not in (None, False):
        clow = cache_entry["lowered"]
        if clause_replayable(clow, w):
            lowered = clow  # repeat query: skip plan + lower
        else:
            # a plain-BGP lowering in the slot for a clause-carrying WHERE
            # proves the fused attempt FAILED at this state — re-planning
            # here would fail identically, so memoize the negative and let
            # eval_where replay the cached program with host post-passes
            return None
    if lowered is None:
        resolved = [resolve_pattern(db, p) for p in w.patterns]
        try:
            logical = build_logical_plan(
                resolved, list(w.filters), [], w.values
            )
            planner = Streamertail(db.get_or_build_stats())
            plan = planner.find_best_plan(logical)
            # UNION/OPTIONAL/MINUS/NOT fuse exactly as on the unordered path
            from kolibrie_tpu.query.ast import WhereClause as _WC
            from kolibrie_tpu.query.executor import _branch_plan

            union_groups, optional_plans, anti_plans = [], [], []
            for groups in w.unions:
                g = [_branch_plan(db, planner, bw) for bw in groups]
                if any(bp is None for bp in g):
                    return None
                union_groups.append(tuple(g))
            for ow in w.optionals:
                bp = _branch_plan(db, planner, ow)
                if bp is None:
                    return None
                optional_plans.append(bp)
            for bw in list(w.minus) + [
                _WC(patterns=nb.patterns) for nb in w.not_blocks
            ]:
                bp = _branch_plan(db, planner, bw)
                if bp is None:
                    return None
                anti_plans.append(bp)
            lowered = lower_plan(
                db,
                plan,
                tuple(anti_plans),
                tuple(union_groups),
                tuple(optional_plans),
            )
        except Unsupported:
            if cache_entry is not None:
                # sticky negative: re-planning this template at this store
                # state would fail identically on every call — memoize so
                # repeat queries skip the plan+lower attempt entirely
                cache_entry["ordered_failed"] = True
            return None
        if cache_entry is not None:
            cache_entry["plan"] = plan
            cache_entry["lowered"] = lowered
    if not lowered.const_ok():
        return []  # a failed constant guard empties the result
    out_vars = lowered.out_vars
    if q.select_all():
        # ``*`` covers branch-bound vars too; internal (renamed) vars stay
        # hidden, matching table_header's convention
        sel_vars = {v for v in out_vars if not v.startswith("__")}
    opos, descs = [], []
    for cond in q.order_by:
        if cond.expr.name not in out_vars:
            return None
        opos.append(out_vars.index(cond.expr.name))
        descs.append(bool(cond.descending))
    k = _round_cap((q.offset or 0) + q.limit, 8)
    with jax.enable_x64(True):
        numf_dev = lowered._device_numf()
        out_cols, valid = lowered.converge(lowered.run())
        # phase 1: numeric keys only — no host rank build
        top_cols, top_valid, _n_valid, nan_seen = _order_limit(
            tuple(out_cols),
            valid,
            numf_dev,
            tuple(opos),
            tuple(descs),
            k,
        )
        if bool(nan_seen):
            # phase 2: a key column holds non-numeric values — build the
            # global string ranks once (cached per store version) and
            # re-sort the already-device-resident columns
            dranks, qranks = device_string_ranks(db)
            top_cols, top_valid, _n_valid, _nan = _order_limit(
                tuple(out_cols),
                valid,
                numf_dev,
                tuple(opos),
                tuple(descs),
                k,
                dranks,
                qranks,
            )
    tv = np.asarray(top_valid)
    table: BindingTable = {
        v: np.asarray(c)[tv].astype(np.uint32)
        for v, c in zip(out_vars, top_cols)
        if v in sel_vars
    }
    rows = format_results(db, table, q)
    start = q.offset or 0
    return rows[start : start + q.limit]
