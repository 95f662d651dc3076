"""Plan-bytecode interpreter: execute ANY eligible template with ZERO
per-template compiles.

The specialized engine jits ``_run_plan`` with the constant-free
``PlanSpec`` as a static argument — optimal steady-state code, but every
*new template shape* pays a full XLA compile (the serving tail this PR
kills).  This module pushes the parameter-vector ABI one level further:
the plan TREE itself becomes data.  ``compile_bytecode`` flattens the
spec into a dense int32 op-code/operand table; ``_run_interp`` is ONE
jitted ``fori_loop`` whose body ``lax.switch``-es on the opcode, so any
template that fits a *size class* executes through an executable that
already exists.  The design follows the iterated-RA machines of
"Optimizing Datalog for the GPU" (2311.02206) and the fixed
column-kernel repertoire of "Column-Oriented Datalog on the GPU"
(2501.13051) — our ScanSpec/JoinSpec/FilterSpec lowering is exactly such
a repertoire.

**Machine model.**  A register file of full-width binding tables:
``regs[i]`` is the ``[cap, n_slots]`` uint32 output of op ``i`` (slot
``c`` = the template's ``out_vars[c]``), with a ``[cap]`` validity row.
Ops:

====  ============  =====================================================
  0   NOP           padding up to the size-class op count
  1   SCAN          two-segment base+delta merge with tombstone masking —
                    the same rank arithmetic as the specialized ScanSpec,
                    but order index / scan row / merge-key positions /
                    output-slot routing are all traced operands
  2   JOIN          generic sort-based equi-join (``join_indices``) on 1
                    or 2 key slots; per-slot left/right source selectors
  3   FILTER_ID     ``?v =|!= uparams[k]``
  4   FILTER_NUMC   numeric compare against ``fparams[k]``
  5   FILTER_NUMV   numeric compare between two slots (with the =/!=
                    id-equality fallback the specialized path applies)
====  ============  =====================================================

**Size classes.**  The jit key is (op-count bucket, unified capacity,
slot-count bucket) plus the operand shapes (store segment sizes, scalar
rows, parameter-vector buckets).  Capacities ride the EXISTING
template-cap protocol — ``cap_key``-bucketed, monotonic, shared with the
specialized path — so warming a template through the interpreter also
calibrates its eventual specialized compile.

**Eligibility.**  Plain BGP shapes: scans (no repeated-variable
patterns), 1–2-key joins, Id/NumConst/NumCmp filters and AND-chains of
them.  Everything else (string masks, VALUES, UNION/OPTIONAL/MINUS,
quoted expansion, WCOJ) declines with :class:`InterpUnsupported` and
runs the specialized path — routing, not failure.

Routing is ``KOLIBRIE_PLAN_INTERP=auto|off|force`` (default ``off``;
``auto`` serves cold templates through the interpreter until the
background warmer has compiled the specialized executable).  The mode
participates in the template fingerprint exactly like ``KOLIBRIE_WCOJ``.
"""

from __future__ import annotations

import os
import threading
import time as _time
from functools import partial
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax
from jax import lax
import jax.numpy as jnp

from kolibrie_tpu.obs import analyze as _analyze
from kolibrie_tpu.obs import metrics as _metrics
from kolibrie_tpu.obs.spans import span as _obs_span
from kolibrie_tpu.query import compile_cache as _cc

__all__ = [
    "plan_interp_mode",
    "override_mode",
    "InterpUnsupported",
    "compile_bytecode",
    "interp_execute",
    "should_interp",
    "mark_compiled",
    "interp_compile_stats",
]

_INTERP_DISPATCH = _metrics.counter(
    "kolibrie_interp_dispatch_total",
    "queries executed through the plan-bytecode interpreter",
)
_INTERP_DECLINED = _metrics.counter(
    "kolibrie_interp_declined_total",
    "templates the interpreter declined (shape outside the op repertoire)",
)
_INTERP_LAT = _metrics.histogram(
    "kolibrie_interp_dispatch_seconds",
    "plan-bytecode interpreter dispatch wall time",
)

# opcodes
NOP, SCAN, JOIN, FILTER_ID, FILTER_NUMC, FILTER_NUMV = range(6)
_W = 12  # operand columns per op row

_MODES = ("auto", "off", "force")
_tl = threading.local()


def plan_interp_mode() -> str:
    """Routing mode, thread-local override first (the warmer suppresses
    the interpreter for its own compile-the-specialized-path calls).
    Default ``off``: the interpreter is an opt-in serving feature; the
    bare library keeps the one-compile-per-template behavior."""
    ov = getattr(_tl, "mode", None)
    if ov is not None:
        return ov
    mode = os.environ.get("KOLIBRIE_PLAN_INTERP", "off").strip().lower()
    return mode if mode in _MODES else "off"


class override_mode:
    """``with override_mode("off"): ...`` — scoped, per-thread."""

    def __init__(self, mode: str):
        self.mode = mode

    def __enter__(self):
        self.prev = getattr(_tl, "mode", None)
        _tl.mode = self.mode
        return self

    def __exit__(self, *exc):
        _tl.mode = self.prev
        return False


class InterpUnsupported(Exception):
    """Template shape outside the interpreter's op repertoire."""


def _bucket(n: int, lo: int) -> int:
    c = lo
    while c < n:
        c <<= 1
    return c


# register-file memory guard: n_ops * cap * n_slots u32 cells
_MAX_CELLS = int(os.environ.get("KOLIBRIE_INTERP_MAX_CELLS", str(2**22)))
_MAX_OPS = 64
_MAX_SLOTS = 16


class InterpProgram:
    """Host-side compiled bytecode for one lowered plan."""

    __slots__ = (
        "code",
        "n_ops",
        "cap",
        "n_slots",
        "var_slots",
        "out_reg",
        "join_count",
        "n_real",
        "stat_keys",
    )

    def __init__(self, code, n_ops, cap, n_slots, var_slots, out_reg,
                 join_count, n_real=0, stat_keys=()):
        self.code = code  # np.int32 [n_ops, _W]
        self.n_ops = n_ops  # size-class bucket (rows incl. NOP padding)
        self.cap = cap
        self.n_slots = n_slots
        self.var_slots = var_slots  # var name -> slot index
        self.out_reg = out_reg
        self.join_count = join_count
        self.n_real = n_real  # real rows before NOP padding
        # per-row EXPLAIN ANALYZE key (shared with _plan_body's stats
        # scheme); None for intermediate AND-chain filter rows
        self.stat_keys = stat_keys


def _emit_rows(lowered):
    """Flatten ``lowered.root`` into raw op rows WITHOUT touching
    capacities or the device — safe to call before ``lowered.build()``.
    Returns ``(rows, bound, stat_keys, slots, out_reg)``; the MQO layer
    uses this for prefix splitting/fingerprinting on host-routed stores.
    Raises :class:`InterpUnsupported` for shapes outside the repertoire."""
    from kolibrie_tpu.optimizer.device_engine import (
        BoolNode,
        FilterSpec,
        IdCmp,
        JoinSpec,
        NumCmp,
        NumConstCmp,
        ScanSpec,
    )

    if lowered.mask_exprs or lowered.values_tables:
        raise InterpUnsupported("string masks / VALUES")
    if getattr(lowered, "need_quoted", False):
        raise InterpUnsupported("quoted expansion")
    slots = {v: i for i, v in enumerate(lowered.out_vars)}
    if len(slots) > _MAX_SLOTS:
        raise InterpUnsupported(f"{len(slots)} variables > {_MAX_SLOTS}")
    rows: List[List[int]] = []
    bound: List[set] = []  # vars bound by each register
    stat_keys: List[Optional[str]] = []  # analyze key per row (None = sub-step)
    fseq = [0]  # pre-order FilterSpec counter (matches _plan_body's seq)

    def emit(row, vars_, key=None) -> int:
        rows.append(row + [0] * (_W - len(row)))
        bound.append(vars_)
        stat_keys.append(key)
        return len(rows) - 1

    def flatten_and(expr, out):
        if isinstance(expr, BoolNode):
            if expr.kind != "and":
                raise InterpUnsupported(f"boolean {expr.kind}")
            for a in expr.args:
                flatten_and(a, out)
        else:
            out.append(expr)

    def walk(node) -> int:
        if isinstance(node, ScanSpec):
            if node.eq_pairs:
                raise InterpUnsupported("repeated-variable pattern")
            tgt = [-1, -1, -1]
            vars_ = set()
            for var, pos in node.out_vars:
                tgt[pos] = slots[var]
                vars_.add(var)
            k0, k1 = node.key_pos
            return emit(
                [SCAN, node.order_idx, node.scan_idx, k0, k1] + tgt,
                vars_,
                key=f"scan{node.scan_idx}",
            )
        if isinstance(node, JoinSpec):
            if len(node.key_vars) > 2:
                raise InterpUnsupported("3+ key join")
            lr = walk(node.left)
            rr = walk(node.right)
            lv, rv = bound[lr], bound[rr]
            ks = [slots[v] for v in node.key_vars]
            k0 = ks[0]
            k1 = ks[1] if len(ks) > 1 else 0
            from_right = 0
            bmask = 0
            for v in lv | rv:
                bmask |= 1 << slots[v]
                if v not in lv:
                    from_right |= 1 << slots[v]
            return emit(
                [JOIN, lr, rr, len(ks), k0, k1, node.join_idx, from_right, bmask],
                lv | rv,
                key=f"join{node.join_idx}",
            )
        if isinstance(node, FilterSpec):
            # pre-order key, assigned BEFORE the child walk (same scheme
            # as the specialized path); it lands on the LAST row of the
            # AND-chain — the row whose validity is the node's output
            fkey = f"filter{fseq[0]}"
            fseq[0] += 1
            src = walk(node.child)
            exprs: List[object] = []
            flatten_and(node.expr, exprs)
            for e in exprs:
                if isinstance(e, IdCmp):
                    src = emit(
                        [
                            FILTER_ID,
                            src,
                            slots[e.var],
                            0 if e.op == "=" else 1,
                            e.param_idx,
                        ],
                        bound[src],
                    )
                elif isinstance(e, NumConstCmp):
                    src = emit(
                        [
                            FILTER_NUMC,
                            src,
                            slots[e.var],
                            _NUM_OPS.index(e.op),
                            e.param_idx,
                        ],
                        bound[src],
                    )
                elif isinstance(e, NumCmp):
                    src = emit(
                        [
                            FILTER_NUMV,
                            src,
                            slots[e.lvar],
                            _NUM_OPS.index(e.op),
                            slots[e.rvar],
                        ],
                        bound[src],
                    )
                else:
                    raise InterpUnsupported(type(e).__name__)
            stat_keys[src] = fkey
            return src
        raise InterpUnsupported(type(node).__name__)

    out_reg = walk(lowered.root)
    if len(rows) > _MAX_OPS:
        raise InterpUnsupported(f"{len(rows)} ops > {_MAX_OPS}")
    return rows, bound, stat_keys, slots, out_reg


def compile_bytecode(lowered) -> InterpProgram:
    """Flatten ``lowered.root`` into the op table.  Requires
    ``lowered.build()`` to have run (capacities populated).  Raises
    :class:`InterpUnsupported` for shapes outside the repertoire."""
    rows, bound, stat_keys, slots, out_reg = _emit_rows(lowered)
    n_real = len(rows)
    caps = list(lowered._scan_caps.values()) + list(lowered._join_caps)
    cap = _bucket(max(caps) if caps else 1, 8)
    n_ops = _bucket(n_real, 4)
    n_slots = _bucket(len(slots), 4)
    if n_ops * cap * n_slots > _MAX_CELLS:
        raise InterpUnsupported(
            f"register file {n_ops}x{cap}x{n_slots} exceeds cell budget"
        )
    code = np.zeros((n_ops, _W), dtype=np.int32)
    for i, row in enumerate(rows):
        code[i] = row
    return InterpProgram(
        code, n_ops, cap, n_slots, slots, out_reg, lowered.join_count,
        n_real=n_real, stat_keys=tuple(stat_keys),
    )


_NUM_OPS = ("=", "!=", "<", "<=", ">", ">=")


# ---------------------------------------------------------------------------
# The one jitted interpreter per size class
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("n_ops", "cap", "n_slots"))
def _run_interp(
    n_ops: int,
    cap: int,
    n_slots: int,
    code,  # [n_ops, _W] i32
    out_reg,  # scalar i32
    B,  # [n_orders, 3, n_base] u32   base segments, canonical s/p/o rows
    D,  # [n_orders, 3, dcap] u32     delta segments
    DEL,  # [n_orders, dcap] u32      sorted tombstone positions
    scalars,  # [S, 4] i32             per-scan (lo_b, n_b, lo_d, n_d)
    numf,  # [NF] f32                  per-id numeric values (NaN padded)
    numf_len,  # scalar i32            live prefix of numf (clamp bound)
    uparams,  # [U] u32
    fparams,  # [F] f64
):
    from kolibrie_tpu.ops.device_join import _LPAD, _RPAD, join_indices

    nbase = B.shape[2]
    dcap = D.shape[2]
    ar = jnp.arange(cap, dtype=jnp.int32)
    ard = jnp.arange(dcap, dtype=jnp.int32)
    slot_ids = jnp.arange(n_slots, dtype=jnp.int32)
    sent64 = jnp.uint64(0xFFFFFFFFFFFFFFFF)
    zero_cols = jnp.zeros((cap, n_slots), dtype=jnp.uint32)
    zero_valid = jnp.zeros((cap,), dtype=bool)
    scratch = jnp.int32(n_ops)  # counts slot for non-join ops

    def op_nop(op, regs, rvalid):
        return zero_cols, zero_valid, jnp.int64(0), scratch

    def op_scan(op, regs, rvalid):
        # twin of the specialized ScanSpec merge (device_engine._plan_body):
        # identical rank arithmetic, but order/scan/key/output routing are
        # traced operands instead of static spec fields
        bcols = B[op[1]]  # [3, n_base]
        dcols = D[op[1]]  # [3, dcap]
        del_pos = DEL[op[1]]  # [dcap]
        lo_b, n_b = scalars[op[2], 0], scalars[op[2], 1]
        lo_d, n_d = scalars[op[2], 2], scalars[op[2], 3]
        src_b = jnp.clip(lo_b + ar, 0, nbase - 1)
        src_d = jnp.clip(lo_d + ard, 0, dcap - 1)
        inb = ar < n_b
        ind = ard < n_d
        sbu = src_b.astype(jnp.uint32)
        jd = jnp.clip(jnp.searchsorted(del_pos, sbu), 0, dcap - 1)
        is_del = (del_pos[jd] == sbu) & inb
        bvalid = inb & ~is_del
        bk = (bcols[op[3]][src_b].astype(jnp.uint64) << jnp.uint64(32)) | (
            bcols[op[4]][src_b].astype(jnp.uint64)
        )
        bk = jnp.where(inb, bk, sent64)
        dk = (dcols[op[3]][src_d].astype(jnp.uint64) << jnp.uint64(32)) | (
            dcols[op[4]][src_d].astype(jnp.uint64)
        )
        dk = jnp.where(ind, dk, sent64)
        pos_b = (jnp.cumsum(bvalid.astype(jnp.int32)) - 1) + (
            jnp.searchsorted(dk, bk, side="left").astype(jnp.int32)
        )
        cdel = jnp.concatenate(
            [jnp.zeros(1, jnp.int32), jnp.cumsum(is_del.astype(jnp.int32))]
        )
        ib = jnp.searchsorted(bk, dk, side="right").astype(jnp.int32)
        pos_d = ard + ib - cdel[ib]
        n_live = (n_b - cdel[-1]) + n_d
        valid = ar < n_live
        dst_b = jnp.where(bvalid, pos_b, cap)
        dst_d = jnp.where(ind, pos_d, cap)
        cols = zero_cols
        for p in range(3):  # canonical s/p/o — static unroll
            tgt = op[5 + p]
            merged = (
                jnp.zeros(cap, dtype=jnp.uint32)
                .at[dst_b]
                .set(bcols[p][src_b], mode="drop")
                .at[dst_d]
                .set(dcols[p][src_d], mode="drop")
            )
            cols = jnp.where(slot_ids[None, :] == tgt, merged[:, None], cols)
        return cols, valid, jnp.int64(0), scratch

    def op_join(op, regs, rvalid):
        lcols, lval = regs[op[1]], rvalid[op[1]]
        rcols, rval = regs[op[2]], rvalid[op[2]]
        two = op[3] > 1
        lk1 = jnp.where(two, jnp.take(lcols, op[5], axis=1), 0)
        rk1 = jnp.where(two, jnp.take(rcols, op[5], axis=1), 0)
        lkey = (jnp.take(lcols, op[4], axis=1).astype(jnp.uint64) << 32) | (
            lk1.astype(jnp.uint64)
        )
        rkey = (jnp.take(rcols, op[4], axis=1).astype(jnp.uint64) << 32) | (
            rk1.astype(jnp.uint64)
        )
        lkey = jnp.where(lval, lkey, jnp.uint64(_LPAD))
        rkey = jnp.where(rval, rkey, jnp.uint64(_RPAD))
        li, ri, valid, total = join_indices(lkey, rkey, cap)
        lg = jnp.take(lcols, li, axis=0)
        rg = jnp.take(rcols, ri, axis=0)
        from_right = ((op[7] >> slot_ids) & 1).astype(bool)[None, :]
        bmask = ((op[8] >> slot_ids) & 1).astype(bool)[None, :]
        out = jnp.where(from_right, rg, lg)
        out = jnp.where(valid[:, None] & bmask, out, 0)
        return out, valid, total.astype(jnp.int64), op[6]

    def op_filter_id(op, regs, rvalid):
        cols = regs[op[1]]
        col = jnp.take(cols, op[2], axis=1)
        u = uparams[jnp.clip(op[4], 0, uparams.shape[0] - 1)]
        eq = col == u
        mask = jnp.where(op[3] == 0, eq, ~eq)
        return cols, rvalid[op[1]] & mask, jnp.int64(0), scratch

    def _numv(col):
        return numf[jnp.clip(col, 0, numf_len - 1).astype(jnp.int32)]

    def op_filter_numc(op, regs, rvalid):
        cols = regs[op[1]]
        vals = _numv(jnp.take(cols, op[2], axis=1))
        c = fparams[jnp.clip(op[4], 0, fparams.shape[0] - 1)]
        res = jnp.stack(
            [vals == c, vals != c, vals < c, vals <= c, vals > c, vals >= c]
        )[op[3]]
        mask = res & ~jnp.isnan(vals)
        return cols, rvalid[op[1]] & mask, jnp.int64(0), scratch

    def op_filter_numv(op, regs, rvalid):
        cols = regs[op[1]]
        lcol = jnp.take(cols, op[2], axis=1)
        rcol = jnp.take(cols, op[4], axis=1)
        a, b = _numv(lcol), _numv(rcol)
        ok = ~(jnp.isnan(a) | jnp.isnan(b))
        res = jnp.stack([a == b, a != b, a < b, a <= b, a > b, a >= b])[op[3]]
        # =/!= fall back to id equality for non-numeric pairs (host twin)
        ideq = lcol == rcol
        idres = jnp.where(op[3] == 0, ideq, ~ideq)
        mask = jnp.where(op[3] <= 1, jnp.where(ok, res, idres), res & ok)
        return cols, rvalid[op[1]] & mask, jnp.int64(0), scratch

    branches = (
        op_nop,
        op_scan,
        op_join,
        op_filter_id,
        op_filter_numc,
        op_filter_numv,
    )

    def body(i, state):
        regs, rvalid, counts, oprows = state
        op = code[i]
        cols, valid, cnt, cidx = lax.switch(op[0], branches, op, regs, rvalid)
        return (
            regs.at[i].set(cols),
            rvalid.at[i].set(valid),
            counts.at[cidx].set(cnt),
            # per-op rows-out for EXPLAIN ANALYZE: one reduction over a
            # mask the op computed anyway, carried with the result so the
            # host fetches it only under an active analyze capture
            oprows.at[i].set(jnp.sum(valid).astype(jnp.int64)),
        )

    regs0 = jnp.zeros((n_ops, cap, n_slots), dtype=jnp.uint32)
    rvalid0 = jnp.zeros((n_ops, cap), dtype=bool)
    counts0 = jnp.zeros((n_ops + 1,), dtype=jnp.int64)
    oprows0 = jnp.zeros((n_ops,), dtype=jnp.int64)
    regs, rvalid, counts, oprows = lax.fori_loop(
        0, n_ops, body, (regs0, rvalid0, counts0, oprows0)
    )
    return regs[out_reg], rvalid[out_reg], counts[:n_ops], oprows


def interp_compile_stats() -> int:
    """Interpreter jit-cache size (one entry per live size class)."""
    try:
        return int(_run_interp._cache_size())
    # kolint: ignore[KL601] same jax cache-API probe as device_compile_stats
    except Exception:
        return -1


# ---------------------------------------------------------------------------
# Host driver
# ---------------------------------------------------------------------------


def _stacked_segments(lowered):
    """[n_orders, 3, n] stacks of the plan's order segments, cached on the
    db: the base stack per (orders, base_version), the delta/tombstone
    stacks per (orders, base_version, delta_epoch).  The stacks are device
    copies OVER the per-order segments device_segment already caches —
    the price of dynamic order indexing inside one executable."""
    db = lowered.db
    store = db.store
    names = tuple(lowered.order_names)
    cache = db.__dict__.setdefault("_interp_segment_cache", {})
    bkey = ("base", names, store.base_version)
    dkey = ("delta", names, store.base_version, store.delta_epoch)
    B = cache.get(bkey)
    D_DEL = cache.get(dkey)
    if B is None or D_DEL is None:
        segs = [store.device_segment(n) for n in names]
        if B is None:
            B = jnp.stack([jnp.stack(bcols) for bcols, _d, _p in segs])
            for k in [k for k in cache if k[0] == "base" and k != bkey]:
                cache.pop(k)
            cache[bkey] = B
        if D_DEL is None:
            D = jnp.stack([jnp.stack(dcols) for _b, dcols, _p in segs])
            DEL = jnp.stack([dp for _b, _d, dp in segs])
            for k in [k for k in cache if k[0] == "delta" and k != dkey]:
                cache.pop(k)
            D_DEL = cache[dkey] = (D, DEL)
    return B, D_DEL[0], D_DEL[1]


def _dispatch(lowered, prog: InterpProgram, args):
    _order_arrays, scalars, _tiers, _masks, _values, numf, _quoted, params = args
    B, D, DEL = _stacked_segments(lowered)
    sc = np.zeros((_bucket(scalars.shape[0], 4), 4), dtype=np.int32)
    sc[: scalars.shape[0]] = np.asarray(scalars, dtype=np.int32)
    nf_len = int(numf.shape[0])
    nfb = _bucket(nf_len, 8)
    code = jnp.asarray(prog.code)
    with jax.enable_x64(True):
        numf_p = jnp.concatenate(
            [numf, jnp.full((nfb - nf_len,), jnp.nan, dtype=numf.dtype)]
        )
        u, f = params
        ub = _bucket(u.shape[0], 8)
        fb = _bucket(f.shape[0], 8)
        u = jnp.concatenate([u, jnp.zeros(ub - u.shape[0], dtype=u.dtype)])
        f = jnp.concatenate([f, jnp.zeros(fb - f.shape[0], dtype=f.dtype)])
        return _cc.call(
            _run_interp,
            prog.n_ops,
            prog.cap,
            prog.n_slots,
            code,
            jnp.int32(prog.out_reg),
            B,
            D,
            DEL,
            jnp.asarray(sc),
            numf_p,
            jnp.int32(nf_len),
            u,
            f,
        )


def interp_execute(lowered, max_attempts: int = 12):
    """Execute ``lowered`` through the bytecode interpreter.  Returns a
    host binding table, or ``None`` when the shape declines (caller falls
    through to the specialized path).  Shares the capacity protocol:
    overflow doubles the template's join caps via ``_store_caps`` — caps
    learned here pre-calibrate the eventual specialized compile."""
    from kolibrie_tpu.optimizer.caps import grown_cap
    from kolibrie_tpu.optimizer.device_engine import _note_fetch

    if not lowered.const_ok():
        return lowered.empty_table()
    t0 = _time.perf_counter()
    for _attempt in range(max_attempts):
        args = lowered.build(tag=0)[1]
        try:
            prog = compile_bytecode(lowered)
        except InterpUnsupported:
            _INTERP_DECLINED.inc()
            return None
        sz = f"{prog.n_ops}x{prog.cap}x{prog.n_slots}"
        with _obs_span("interp.dispatch", size_class=sz):
            out_cols, out_valid, counts, oprows = _dispatch(
                lowered, prog, args
            )
        _note_fetch("interp.counts")
        counts_h = [int(c) for c in np.asarray(counts)[: prog.join_count]]
        overflow = [
            i
            for i, c in enumerate(counts_h)
            if c > lowered._join_caps[i]
        ]
        if not overflow:
            lowered._store_caps()
            _note_fetch("interp.collect")
            valid_h = np.asarray(out_valid)
            lowered._advise(counts_h, rows=int(valid_h.sum()))
            cols_h = np.asarray(out_cols)
            table = {
                var: cols_h[valid_h, prog.var_slots[var]].astype(np.uint32)
                for var in lowered.out_vars
            }
            _INTERP_DISPATCH.inc()
            _INTERP_LAT.observe(_time.perf_counter() - t0)
            cap = _analyze.active()
            if cap is not None:
                _note_fetch("analyze.oprows")
                rows_h = np.asarray(oprows)
                operators = {
                    key: int(rows_h[i])
                    for i, key in enumerate(prog.stat_keys)
                    if key is not None
                }
                names = ("NOP", "SCAN", "JOIN", "FILTER_ID",
                         "FILTER_NUMC", "FILTER_NUMV")
                opcodes = {n: 0 for n in names}
                for oc in prog.code[: prog.n_real, 0]:
                    opcodes[names[int(oc)]] += 1
                opcodes["NOP"] += prog.n_ops - prog.n_real
                cap.record(
                    "interp",
                    size_class=sz,
                    operators=operators,
                    opcodes=opcodes,
                    counts=counts_h,
                    caps=list(lowered._join_caps),
                    rows=int(valid_h.sum()),
                )
            return table
        for i in overflow:
            lowered._join_caps[i] = grown_cap(counts_h[i])
        lowered._store_caps()
    raise RuntimeError("interpreter plan capacities failed to converge")


# ---------------------------------------------------------------------------
# Routing
# ---------------------------------------------------------------------------


def _compiled_keys(db) -> set:
    keys = db.__dict__.get("_compiled_cap_keys")
    if keys is None:
        keys = db.__dict__["_compiled_cap_keys"] = set()
    return keys


def should_interp(lowered) -> bool:
    """Route this execution through the interpreter?  ``force`` always
    (eligibility still declines downstream); ``auto`` only while the
    specialized executable for this template is not known-compiled in
    this process — the warmer (or any foreground specialized run) flips
    a template to the fast path by executing it once."""
    mode = plan_interp_mode()
    if mode == "off":
        return False
    if mode == "force":
        return True
    if lowered.cap_key in _compiled_keys(lowered.db):
        return False
    # measured admission: when the stats advisor has seen this template
    # produce intermediates past the interpreter's economical cell
    # budget (cap rides every op row in the dense register file), the
    # interpreter would either decline after compiling or pay a
    # pathological dispatch — go straight to the specialized path
    from kolibrie_tpu.optimizer import stats_advisor as _sa

    peak = _sa.stats_advisor.peak_rows(_sa.current_fp())
    if peak is not None and peak > _MAX_CELLS // (_MAX_OPS * 4):
        return False
    return True


def mark_compiled(lowered) -> None:
    """Record that the specialized executable for this template now
    exists in-process (auto mode stops interpreting it)."""
    _compiled_keys(lowered.db).add(lowered.cap_key)
