"""Single-chip device semi-naive Datalog fixpoint.

The host strategies (:mod:`kolibrie_tpu.reasoner.strategies`) evaluate rule
bodies with numpy joins round by round.  Here the ENTIRE fixpoint runs as a
single XLA dispatch: a ``lax.while_loop`` whose body is one semi-naive round
— delta-seeded premise joins (static-capacity sort joins), filter masks,
NAF anti-joins, conclusion instantiation, sort-unique dedup, set-difference
against known facts, fact append — with the loop condition fusing
"no new facts?" into the program (SURVEY §7.4: fixpoint termination without
per-round host sync).

Parity (TPU-native redesign, not a translation):
``datalog/src/reasoning/materialisation/semi_naive_parallel.rs:11-177`` —
the rayon delta fan-out becomes whole-column joins;
``semi_naive.rs:22-59`` — delta seeding per premise position.

Static-shape protocol: every buffer has a power-of-two capacity.  A round
that would overflow any capacity does NOT commit (the loop exits with the
pre-round state and an overflow code); the host driver doubles the failing
capacity and re-enters the loop from the preserved state.  Readback happens
once per ``while_loop`` exit, not per round.

GROUND quoted (RDF-star) terms lower to their qid constants — premises
against never-interned triples become never-match scans, quoted
conclusions intern eagerly at lowering.  Rules whose shapes the device
path cannot express (quoted terms with INNER VARIABLES, non-numeric
filters, cartesian premise joins) raise :class:`Unsupported`; callers
fall back to the host strategies.  3-variable
join keys ride the union dense-rank composition
(``ops/device_join.py::pack_key_multi``).  Agreement between both paths is
tested in ``tests/test_device_fixpoint.py``.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from kolibrie_tpu.core.rule import FilterCondition, Rule

__all__ = ["Unsupported", "DeviceFixpoint", "infer_semi_naive_device"]


class Unsupported(Exception):
    """Rule set the device fixpoint cannot express (host fallback)."""


from kolibrie_tpu.obs import metrics as _obs_metrics
from kolibrie_tpu.obs import runtime as _obs_runtime
from kolibrie_tpu.obs.spans import span as _obs_span
from kolibrie_tpu.ops import round_cap as _round_cap

_FIXPOINT_ROUNDS = _obs_metrics.histogram(
    "kolibrie_fixpoint_rounds",
    "semi-naive rounds per fixpoint run (chunked path: productive rounds)",
    buckets=_obs_metrics.DEFAULT_COUNT_BUCKETS,
)
_FIXPOINT_DERIVED = _obs_metrics.histogram(
    "kolibrie_fixpoint_derived_facts",
    "facts derived per fixpoint run",
    buckets=_obs_metrics.DEFAULT_COUNT_BUCKETS,
)
_FIXPOINT_DELTA = _obs_metrics.histogram(
    "kolibrie_fixpoint_delta_facts",
    "delta size fed to each chunked fixpoint round",
    buckets=_obs_metrics.DEFAULT_COUNT_BUCKETS,
)


# ---------------------------------------------------------------------------
# Rule lowering (host) — frozen, hashable: part of the jit static key
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class LoweredPremise:
    consts: tuple  # (Optional[int], Optional[int], Optional[int])
    vars: tuple  # ((var, pos) first occurrence ...)
    eq_pairs: tuple  # ((pos, pos) ...) repeated variables


@dataclass(frozen=True)
class LoweredFilter:
    kind: str  # 'mask' (per-ID bool gather) | 'eq' | 'ne' (ID compare)
    var: str
    mask_idx: int = -1
    const_id: int = 0


@dataclass(frozen=True)
class LoweredRule:
    premises: tuple  # (LoweredPremise, ...)
    negs: tuple  # (LoweredPremise, ...)
    filters: tuple  # (LoweredFilter, ...)
    concls: tuple  # ((term, term, term), ...); term = ('var', name) | ('const', id)
    # per seed position: premise evaluation order (seed first) and the join
    # key variables for each subsequent step
    plans: tuple  # ((order: tuple[int], keys: tuple[tuple[str,...]]), ...)
    # fully-ground GUARD premises dropped from the join plan after static
    # satisfaction (see lower_rules: non-derivable + present in the initial
    # facts — facts never retract, so the gate holds for the whole closure).
    # Kept for the tagged drivers, whose ⊗ would need the guard's tag.
    guards: tuple = ()


def _ground_quoted_id(term, quoted) -> Optional[int]:
    """qid of a GROUND quoted term (recursively constant inner triple), or
    None when the triple is not interned — a premise against it can never
    match.  Raises Unsupported for quoted terms with inner variables (the
    host unification path covers those)."""
    inner = term.value.terms()
    ids = []
    for t in inner:
        if t.is_quoted:
            qid = _ground_quoted_id(t, quoted)
            if qid is None:
                return None
            ids.append(qid)
        elif t.is_constant:
            ids.append(int(t.value))
        else:
            raise Unsupported("quoted-triple pattern with inner variables")
    if quoted is None:
        raise Unsupported("quoted-triple pattern without a quoted store")
    return quoted.lookup(*ids)


# never a dictionary ID (bits 0..30 + quoted bit 31, not all-ones): a scan
# constant that matches nothing — the lowering of a ground quoted premise
# whose triple was never interned
_NEVER_MATCH = 0xFFFFFFFF


def _lower_pattern(pattern, dictionary, quoted=None) -> LoweredPremise:
    consts: List[Optional[int]] = []
    out_vars: List[tuple] = []
    eq_pairs: List[tuple] = []
    seen: Dict[str, int] = {}
    for pos, t in enumerate(pattern.terms()):
        if t.is_quoted:
            # ground quoted term → its qid constant (absent ⇒ never match);
            # inner variables stay host-side (Unsupported from the helper)
            qid = _ground_quoted_id(t, quoted)
            consts.append(_NEVER_MATCH if qid is None else int(qid))
            continue
        if t.is_constant:
            consts.append(int(t.value))
        else:
            consts.append(None)
            if t.value in seen:
                eq_pairs.append((seen[t.value], pos))
            else:
                seen[t.value] = pos
                out_vars.append((t.value, pos))
    return LoweredPremise(tuple(consts), tuple(out_vars), tuple(eq_pairs))


def _plan_rule(premises: List[LoweredPremise]) -> tuple:
    """For each seed position: greedy connected join order + key vars."""
    plans = []
    for i in range(len(premises)):
        order = [i]
        bound = {v for v, _ in premises[i].vars}
        remaining = [j for j in range(len(premises)) if j != i]
        keys: List[tuple] = []
        while remaining:
            scored = []
            for j in remaining:
                jvars = {v for v, _ in premises[j].vars}
                scored.append((len(jvars & bound), -len(jvars), j))
            scored.sort(reverse=True)
            n_shared, _, best = scored[0]
            if n_shared == 0:
                raise Unsupported("cartesian premise join")
            jvars = {v for v, _ in premises[best].vars}
            shared = tuple(sorted(jvars & bound))
            # 1-2 keys pack exactly into u64; 3 keys (a premise has only
            # three positions) ride the union dense-rank composition
            keys.append(shared)
            order.append(best)
            bound |= jvars
            remaining.remove(best)
        plans.append((tuple(order), tuple(keys)))
    return tuple(plans)


class _MaskBank:
    """Per-ID boolean masks for numeric rule filters (host-precomputed)."""

    def __init__(self, reasoner):
        self.reasoner = reasoner
        self.exprs: List[tuple] = []  # (op, float const)
        self._keys: Dict[tuple, int] = {}

    def index_for(self, op: str, const: float) -> int:
        key = (op, const)
        idx = self._keys.get(key)
        if idx is None:
            idx = len(self.exprs)
            self.exprs.append(key)
            self._keys[key] = idx
        return idx

    def materialize(self) -> List[np.ndarray]:
        if not self.exprs:
            return []
        d = self.reasoner.dictionary
        n = len(d.id_to_str)
        cached = getattr(self, "_mask_cache", None)
        if cached is not None and cached[0] == n:
            return cached[1]
        vals = np.full(n, np.nan)
        for i in range(1, n):
            v = self.reasoner.numeric_value(i)
            if v is not None:
                vals[i] = v
        out = []
        with np.errstate(invalid="ignore"):
            for op, const in self.exprs:
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
                out.append(m & ~np.isnan(vals))
        self._mask_cache = (n, out)
        return out


def _guard_derivable(guard: LoweredPremise, rules: List[Rule]) -> bool:
    """Could any rule's conclusion unify with this fully-ground premise?
    Conservative syntactic test (variables unify with anything; quoted
    conclusion terms count as wildcards)."""
    for r in rules:
        for c in r.conclusion:
            if all(
                (not t.is_constant) or int(t.value) == g
                for t, g in zip(c.terms(), guard.consts)
            ):
                return True
    return False


def lower_rules(reasoner, rules: List[Rule]) -> Tuple[tuple, _MaskBank]:
    bank = _MaskBank(reasoner)
    lowered: List[LoweredRule] = []
    for rule in rules:
        quoted = getattr(reasoner, "quoted", None)
        prems = [
            _lower_pattern(p, reasoner.dictionary, quoted)
            for p in rule.premise
        ]
        if not prems:
            raise Unsupported("rule without positive premises")
        # fully-ground GUARD premises (the RDF-star annotation-gate shape):
        # facts never retract, so a non-derivable guard's truth is CONSTANT
        # through any one closure — it drops out of the JOIN PLAN and is
        # evaluated as a whole-rule membership gate at RUN time (the same
        # lowered rules must stay correct for callers like DeviceR2R that
        # lower once and supply different fact columns per window).  A
        # derivable guard can flip mid-closure, which the delta-seeded
        # plans over the remaining premises would miss — host fallback.
        guards = [p for p in prems if not p.vars]
        if guards:
            for g in guards:
                if _guard_derivable(g, rules):
                    raise Unsupported("derivable ground guard premise")
            prems = [p for p in prems if p.vars]
            if not prems:
                raise Unsupported("fully ground rule")
        bound = {v for pr in prems for v, _ in pr.vars}
        negs = [
            _lower_pattern(p, reasoner.dictionary, quoted)
            for p in rule.negative_premise
        ]
        for neg in negs:
            # the host path anti-joins on the SHARED variables only; a
            # negated variable outside the positive premises needs that
            # looser semantics — fall back rather than trace a KeyError
            if any(v not in bound for v, _ in neg.vars):
                raise Unsupported("negated variable unbound in positive premises")
        filters: List[LoweredFilter] = []
        for f in rule.filters:
            if f.variable not in bound:
                raise Unsupported("filter variable unbound in positive premises")
            filters.append(_lower_filter(f, bank))
        concls = []
        for c in rule.conclusion:
            terms = []
            for t in c.terms():
                if t.is_quoted:
                    # a GROUND quoted conclusion is a constant qid; intern
                    # eagerly (host interns on first derivation — the only
                    # observable difference is the quoted-store entry
                    # existing before the rule fires).  Inner variables
                    # (constructing new quoted terms per binding) stay
                    # host-side.
                    inner = t.value.terms()
                    if any(not it.is_constant for it in inner):
                        raise Unsupported(
                            "quoted-triple conclusion with inner variables"
                        )
                    if quoted is None:
                        raise Unsupported("quoted conclusion without a store")
                    qid = quoted.intern(*(int(it.value) for it in inner))
                    terms.append(("const", int(qid)))
                    continue
                if t.is_constant:
                    terms.append(("const", int(t.value)))
                else:
                    if t.value not in bound:
                        raise Unsupported("head variable unbound in premises")
                    terms.append(("var", t.value))
            concls.append(tuple(terms))
        lowered.append(
            LoweredRule(
                tuple(prems),
                tuple(negs),
                tuple(filters),
                tuple(concls),
                _plan_rule(prems),
                tuple(guards),
            )
        )
    return tuple(lowered), bank


def _lower_filter(f: FilterCondition, bank: _MaskBank) -> LoweredFilter:
    if isinstance(f.value, bool):
        raise Unsupported("boolean filter value")
    if isinstance(f.value, int):
        if f.operator == "=":
            return LoweredFilter("eq", f.variable, const_id=int(f.value))
        if f.operator == "!=":
            return LoweredFilter("ne", f.variable, const_id=int(f.value))
        # ordered comparison against an ID-valued constant is numeric on the
        # DECODED literal in the host path — same here via the mask bank
        raise Unsupported("ordered comparison against term id")
    try:
        const = float(f.value)  # type: ignore[arg-type]
    except (TypeError, ValueError):
        raise Unsupported(f"non-numeric filter value {f.value!r}")
    return LoweredFilter("mask", f.variable, mask_idx=bank.index_for(f.operator, const))


# ---------------------------------------------------------------------------
# Jitted fixpoint
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class _Caps:
    fact: int
    delta: int
    join: int  # one shared capacity for all intermediate joins


def _scan_premise(prem: LoweredPremise, cols, valid):
    """Premise match against a (cols, valid) buffer → (var table, mask)."""
    import jax.numpy as jnp

    m = valid
    for c, col in zip(prem.consts, cols):
        if c is not None:
            m = m & (col == np.uint32(c))
    for a, b in prem.eq_pairs:
        m = m & (cols[a] == cols[b])
    table = {v: cols[pos] for v, pos in prem.vars}
    return table, m


def _pack(cols: List, valid, sentinel):
    import jax.numpy as jnp

    if len(cols) == 1:
        key = cols[0].astype(jnp.uint64)
    else:
        key = (cols[0].astype(jnp.uint64) << np.uint64(32)) | cols[1].astype(
            jnp.uint64
        )
    return jnp.where(valid, key, np.uint64(sentinel))


def _eval_filters(rule, table, valid, masks):
    import jax.numpy as jnp

    for f in rule.filters:
        col = table[f.var]
        if f.kind == "eq":
            valid = valid & (col == np.uint32(f.const_id))
        elif f.kind == "ne":
            valid = valid & (col != np.uint32(f.const_id))
        else:
            m = masks[f.mask_idx]
            valid = valid & m[jnp.minimum(col, m.shape[0] - 1)]
    return valid


def _eval_negs(rule, table, valid, facts):
    import jax.numpy as jnp

    from kolibrie_tpu.ops.device_join import (
        _LPAD,
        _RPAD,
        _row_membership,
        semi_join_mask,
    )

    fsx, fpx, fox, fvx = facts
    fcols = (fsx, fpx, fox)
    for neg in rule.negs:
        nm = fvx
        for c, col in zip(neg.consts, fcols):
            if c is not None:
                nm = nm & (col == np.uint32(c))
        for a, b in neg.eq_pairs:
            nm = nm & (fcols[a] == fcols[b])
        key_cols = [table[v] for v, _ in neg.vars]
        fact_cols = [fcols[pos] for _, pos in neg.vars]
        if not key_cols:
            # fully-constant negated premise: existence kills every row
            valid = valid & ~jnp.any(nm)
            continue
        if len(key_cols) <= 2:
            member = semi_join_mask(
                _pack(key_cols, valid, _LPAD), _pack(fact_cols, nm, _RPAD)
            )
        else:
            ours = [jnp.where(valid, c, np.uint32(0xFFFFFFFE)) for c in key_cols]
            theirs = [
                jnp.where(nm, c, np.uint32(0xFFFFFFFF)) for c in fact_cols
            ]
            member = _row_membership(ours, theirs)
        valid = valid & ~member
    return valid


def _gen_candidates(
    rules, fcols, fvalid, dcols, dvalid, masks, J, use_pallas=False
):
    """Candidate conclusions of one semi-naive round: delta-seeded premise
    joins + filters + NAF over a FROZEN fact snapshot, as static-cap column
    blocks.  Shared by the one-dispatch fixpoint (inside its ``while_loop``)
    and the per-round chunk program (:func:`_device_round_chunk`).

    ``use_pallas``: premise joins ride the Pallas tile kernel through the
    dense-rank prepass (the engine's production join on TPU) instead of
    the XLA searchsorted expansion.
    """
    import jax.numpy as jnp

    from kolibrie_tpu.ops.device_join import _LPAD, _RPAD, join_indices

    if use_pallas:
        from kolibrie_tpu.ops.pallas_kernels import ranked_merge_join_indices

    facts = (*fcols, fvalid)
    overflow = np.int32(0)
    cand_parts: List[tuple] = []  # (s, p, o, valid) static-cap blocks

    for rule in rules:
        # ground-guard gate: a whole-rule membership test against the fact
        # snapshot (non-derivable by the lowering gate, so its value is
        # constant through the closure — per-window callers like DeviceR2R
        # get the right value for THEIR facts)
        guard_ok = None
        for g in rule.guards:
            _t, gm = _scan_premise(g, fcols, fvalid)
            hit = jnp.any(gm)
            guard_ok = hit if guard_ok is None else (guard_ok & hit)
        for order, keys in rule.plans:
            seed = order[0]
            table, m = _scan_premise(rule.premises[seed], dcols, dvalid)
            valid = m if guard_ok is None else (m & guard_ok)
            for step, j in enumerate(order[1:]):
                ptable, pm = _scan_premise(rule.premises[j], fcols, fvalid)
                kv = keys[step]
                if len(kv) > 2:
                    from kolibrie_tpu.ops.device_join import pack_key_multi

                    lkey, rkey = pack_key_multi(
                        [table[v] for v in kv],
                        [ptable[v] for v in kv],
                        valid,
                        pm,
                    )
                else:
                    lkey = _pack([table[v] for v in kv], valid, _LPAD)
                    rkey = _pack([ptable[v] for v in kv], pm, _RPAD)
                if use_pallas:
                    li, ri, jvalid, total = ranked_merge_join_indices(
                        lkey, rkey, J
                    )
                else:
                    li, ri, jvalid, total = join_indices(lkey, rkey, J)
                overflow = overflow | jnp.where(total > J, np.int32(1), 0)
                new_table = {}
                for v, c in table.items():
                    new_table[v] = c[li]
                for v, c in ptable.items():
                    if v not in new_table:
                        new_table[v] = c[ri]
                table, valid = new_table, jvalid
            valid = _eval_filters(rule, table, valid, masks)
            valid = _eval_negs(rule, table, valid, facts)
            n = valid.shape[0]
            for concl in rule.concls:
                out = []
                for kind, v in concl:
                    if kind == "var":
                        out.append(table[v])
                    else:
                        out.append(jnp.full(n, v, dtype=jnp.uint32))
                cand_parts.append((out[0], out[1], out[2], valid))

    cs = jnp.concatenate([p[0] for p in cand_parts])
    cp = jnp.concatenate([p[1] for p in cand_parts])
    co = jnp.concatenate([p[2] for p in cand_parts])
    cv = jnp.concatenate([p[3] for p in cand_parts])
    return cs, cp, co, cv, overflow


@partial(jax.jit, static_argnames=("rules", "caps", "use_pallas"))
def _device_fixpoint(
    rules: tuple,
    caps: _Caps,
    fs,
    fp,
    fo,
    n_facts,
    masks,
    use_pallas: bool = False,
):
    """Run semi-naive rounds to fixpoint (or capacity overflow) on device.

    ``fs/fp/fo`` must be padded to ``caps.fact`` by the caller (keeps the
    jit cache keyed on capacities, not exact fact counts).  Returns
    (fs, fp, fo, n_facts, rounds, overflow_code) where overflow_code:
    a bitmask: 0 ok, bit0 join cap, bit1 delta cap, bit2 fact cap.
    """
    import jax.numpy as jnp
    from jax import lax

    from kolibrie_tpu.ops.device_join import _row_membership

    F, D, J = caps.fact, caps.delta, caps.join

    def pad_to(x, cap, fill=0):
        return jnp.concatenate(
            [x, jnp.full(cap - x.shape[0], fill, dtype=x.dtype)]
        )

    fvalid = jnp.arange(F, dtype=jnp.int32) < n_facts

    # round 0: delta = all facts
    ds = fs[:D] if D <= F else pad_to(fs, D)
    dp = fp[:D] if D <= F else pad_to(fp, D)
    do = fo[:D] if D <= F else pad_to(fo, D)
    dvalid = jnp.arange(D, dtype=jnp.int32) < jnp.minimum(n_facts, D)
    init_overflow = jnp.where(n_facts > D, np.int32(2), np.int32(0))  # bit1: delta

    def round_body(carry):
        fs, fp, fo, fvalid, n_facts, ds, dp, do, dvalid, n_new, rounds, _ovf = carry

        cs, cp, co, cv, overflow = _gen_candidates(
            rules, (fs, fp, fo), fvalid, (ds, dp, do), dvalid, masks, J,
            use_pallas,
        )

        # dedup + subtract known facts (fused membership: rank (s,p), pack o)
        ours = [
            jnp.where(cv, cs, np.uint32(0xFFFFFFFE)),
            jnp.where(cv, cp, np.uint32(0xFFFFFFFE)),
            jnp.where(cv, co, np.uint32(0xFFFFFFFE)),
        ]
        theirs = [
            jnp.where(fvalid, fs, np.uint32(0xFFFFFFFF)),
            jnp.where(fvalid, fp, np.uint32(0xFFFFFFFF)),
            jnp.where(fvalid, fo, np.uint32(0xFFFFFFFF)),
        ]
        known = _row_membership(ours, theirs)
        cv = cv & ~known

        from kolibrie_tpu.parallel.dist_fixpoint import _sort_unique3

        (us, up, uo), uvalid, n_uniq = _sort_unique3((cs, cp, co), cv, D)
        overflow = overflow | jnp.where(n_uniq > D, np.int32(2), 0)
        n_new_next = jnp.minimum(n_uniq, D).astype(jnp.int32)

        # append new facts
        dest = jnp.where(uvalid, n_facts + jnp.cumsum(uvalid) - 1, F)
        nfs = fs.at[dest].set(us, mode="drop")
        nfp = fp.at[dest].set(up, mode="drop")
        nfo = fo.at[dest].set(uo, mode="drop")
        n_facts_next = n_facts + n_new_next
        overflow = overflow | jnp.where(n_facts_next > F, np.int32(4), 0)
        nfvalid = jnp.arange(F, dtype=jnp.int32) < n_facts_next

        # commit only on success: an overflowing round must not corrupt state
        ok = overflow == 0

        def sel(new, old):
            return jnp.where(ok, new, old)

        return (
            sel(nfs, fs),
            sel(nfp, fp),
            sel(nfo, fo),
            sel(nfvalid, fvalid),
            sel(n_facts_next, n_facts),
            sel(us, ds),
            sel(up, dp),
            sel(uo, do),
            sel(uvalid, dvalid),
            sel(n_new_next, n_new),
            rounds + jnp.where(ok, 1, 0),
            overflow,
        )

    ROUND_LIMIT = 10_000  # runaway-rule backstop, far above any real closure

    def cond(carry):
        n_new, rounds, overflow = carry[9], carry[10], carry[11]
        return (n_new > 0) & (overflow == 0) & (rounds < ROUND_LIMIT)

    init = (
        fs,
        fp,
        fo,
        fvalid,
        n_facts.astype(jnp.int32),
        ds,
        dp,
        do,
        dvalid,
        jnp.minimum(n_facts, np.int32(1)).astype(jnp.int32),
        np.int32(0),
        init_overflow,
    )
    out = lax.while_loop(cond, round_body, init)
    # bit3: round limit hit with work remaining — an incomplete closure must
    # never be reported as success
    code = out[11] | jnp.where(
        (out[10] >= ROUND_LIMIT) & (out[9] > 0), np.int32(8), np.int32(0)
    )
    return out[0], out[1], out[2], out[4], out[10], code


@partial(jax.jit, static_argnames=("rules", "caps", "use_pallas"))
def _device_round_chunk(
    rules: tuple,
    caps: _Caps,
    fs,
    fp,
    fo,
    n_facts,
    ds,
    dp,
    do,
    n_delta,
    accs,
    accp,
    acco,
    n_acc,
    masks,
    use_pallas: bool = False,
):
    """One delta CHUNK of one semi-naive round as its own XLA program.

    The facts are FROZEN for the whole round — NAF and known-fact
    subtraction see the same snapshot in every chunk, so K chunked
    dispatches produce exactly the round the one-dispatch program's
    ``round_body`` would.  New facts accumulate (deduplicated) in the
    ``acc*`` buffer; the host driver merges it into the fact columns at
    round end and feeds it back as the next round's delta.

    The point of the split: each program's join capacity stays below the
    toolchain bound that faults the composed one-dispatch fixpoint
    (``SAFE_JOIN_CAP``), which is what lets LUBM-1000-scale closures run
    on-chip.  Returns ``(accs, accp, acco, n_acc, overflow)``; an
    overflowing chunk does NOT commit (bit0 join cap, bit1 accumulator
    cap), so the caller can double the failing capacity and re-run it.
    """
    import jax.numpy as jnp

    from kolibrie_tpu.ops.device_join import _row_membership

    F, D, J = caps.fact, caps.delta, caps.join
    fvalid = jnp.arange(F, dtype=jnp.int32) < n_facts
    dvalid = jnp.arange(ds.shape[0], dtype=jnp.int32) < n_delta

    cs, cp, co, cv, overflow = _gen_candidates(
        rules, (fs, fp, fo), fvalid, (ds, dp, do), dvalid, masks, J,
        use_pallas,
    )

    # subtract known facts AND rows already accumulated by earlier chunks
    ours = [jnp.where(cv, c, np.uint32(0xFFFFFFFE)) for c in (cs, cp, co)]
    known = _row_membership(
        ours,
        [jnp.where(fvalid, c, np.uint32(0xFFFFFFFF)) for c in (fs, fp, fo)],
    )
    accv = jnp.arange(D, dtype=jnp.int32) < n_acc
    in_acc = _row_membership(
        ours,
        [jnp.where(accv, c, np.uint32(0xFFFFFFFF)) for c in (accs, accp, acco)],
    )
    cv = cv & ~known & ~in_acc

    from kolibrie_tpu.parallel.dist_fixpoint import _sort_unique3

    (us, up, uo), uvalid, n_uniq = _sort_unique3((cs, cp, co), cv, D)
    n_u = jnp.minimum(n_uniq, D).astype(jnp.int32)
    overflow = overflow | jnp.where(
        (n_uniq > D) | (n_acc + n_u > D), np.int32(2), 0
    )

    dest = jnp.where(uvalid, n_acc + jnp.cumsum(uvalid) - 1, D)
    nas = accs.at[dest].set(us, mode="drop")
    nap = accp.at[dest].set(up, mode="drop")
    nao = acco.at[dest].set(uo, mode="drop")

    ok = overflow == 0

    def sel(new, old):
        return jnp.where(ok, new, old)

    return (
        sel(nas, accs),
        sel(nap, accp),
        sel(nao, acco),
        sel(n_acc + n_u, n_acc),
        overflow,
    )


# ---------------------------------------------------------------------------
# Host driver
# ---------------------------------------------------------------------------


class DeviceFixpoint:
    """Host driver: lowers the reasoner's rules, sizes capacities, runs the
    on-device fixpoint with overflow-driven capacity doubling, and writes
    derived facts back into ``reasoner.facts``."""

    def __init__(self, reasoner):
        self.reasoner = reasoner
        self.rules, self.bank = lower_rules(reasoner, reasoner.rules)
        # rounds taken by the most recent successful infer/infer_padded —
        # previously computed on device and discarded at readback
        self.last_rounds = 0

    def _caps(self, n_facts: int):
        return _Caps(
            fact=_round_cap(8 * n_facts, 2048),
            delta=_round_cap(max(2 * n_facts, 1024)),
            join=_round_cap(4 * n_facts, 1024),
        )

    def infer_padded(
        self,
        fs,
        fp,
        fo,
        n_facts,
        caps: _Caps,
        max_attempts: int = 12,
    ):
        """Capacity-retry fixpoint over device-resident fact columns.

        ``fs/fp/fo`` are u32 device columns holding ``n_facts`` valid rows
        (any padding beyond is ignored; columns shorter than ``caps.fact``
        are re-padded).  Returns ``(ofs, ofp, ofo, n_out, caps)`` — the raw
        padded output columns (input rows first, derived appended), the int
        fact count, and the converged capacities — WITHOUT touching
        ``reasoner.facts``.  This is the entry the device-resident RSP
        driver reuses every window firing: no host round-trip of the fact
        columns, one compiled program per capacity configuration.
        """
        import jax.numpy as jnp

        if not self.rules:
            return fs, fp, fo, int(n_facts), caps

        masks = tuple(jnp.asarray(m) for m in self.bank.materialize()) or (
            jnp.zeros(1, dtype=bool),
        )
        from kolibrie_tpu.ops.pallas_kernels import pallas_enabled

        use_pallas = pallas_enabled()
        for _attempt in range(max_attempts):

            def pad(x):
                if x.shape[0] < caps.fact:
                    return jnp.concatenate(
                        [
                            x.astype(jnp.uint32),
                            jnp.zeros(caps.fact - x.shape[0], dtype=jnp.uint32),
                        ]
                    )
                # longer columns (an oversized resident mirror) are sliced:
                # caps.fact >= 8 * n_facts, so only invalid padding drops
                return x[: caps.fact].astype(jnp.uint32)

            fs, fp, fo = pad(fs), pad(fp), pad(fo)
            with jax.enable_x64(True):
                ofs, ofp, ofo, on, rounds, code = _device_fixpoint(
                    self.rules, caps, fs, fp, fo, n_facts, masks, use_pallas
                )
            code = int(code)
            if code == 0:
                if _obs_runtime.enabled():
                    # one extra scalar readback, gated: the same sync the
                    # int(code) above already paid for covers its latency
                    self.last_rounds = int(rounds)
                    _FIXPOINT_ROUNDS.observe(self.last_rounds)
                return ofs, ofp, ofo, int(on), caps
            if code & 8:
                raise RuntimeError(
                    "device fixpoint hit the round limit before convergence"
                )
            # preserve progress: restart from the (committed) returned state,
            # doubling every capacity that overflowed (code is a bitmask)
            fs, fp, fo, n_facts = ofs, ofp, ofo, on
            caps = _Caps(
                caps.fact * (2 if code & 4 else 1),
                caps.delta * (2 if code & 2 else 1),
                caps.join * (2 if code & 1 else 1),
            )
            if (
                jax.default_backend() == "tpu"
                and caps.join > SAFE_JOIN_CAP
            ):
                # the doubled program would hit the toolchain fault the
                # entry gate exists to avoid — bail to the host path
                raise JoinCapExceeded(caps.join)
        raise RuntimeError("device fixpoint capacities failed to converge")

    def infer(self, max_attempts: int = 12, initial_caps: Optional[_Caps] = None) -> int:
        import jax.numpy as jnp

        r = self.reasoner
        s, p, o = r.facts.columns()
        n0 = len(s)
        if n0 == 0 or not self.rules:
            # every rule was statically dead (unsatisfiable ground guards)
            return 0
        caps = initial_caps if initial_caps is not None else self._caps(n0)
        with _obs_span("reasoner.fixpoint", facts=n0):
            ofs, ofp, ofo, n_out, caps = self.infer_padded(
                jnp.asarray(s),
                jnp.asarray(p),
                jnp.asarray(o),
                jnp.int32(n0),
                caps,
                max_attempts,
            )
        self.converged_caps = caps
        if n_out > n0:
            s_h = np.asarray(ofs[:n_out])
            p_h = np.asarray(ofp[:n_out])
            o_h = np.asarray(ofo[:n_out])
            r.facts.add_batch(s_h[n0:], p_h[n0:], o_h[n0:])
        _FIXPOINT_DERIVED.observe(n_out - n0)
        return n_out - n0


    def infer_chunked(
        self,
        chunk_rows: Optional[int] = None,
        join_cap: Optional[int] = None,
        delta_cap: Optional[int] = None,
        max_attempts: int = 64,
    ) -> int:
        """Host-driven per-round fixpoint for inputs past the one-dispatch
        program's toolchain-safe join capacity.

        Each ROUND runs as one chunk program (:func:`_device_round_chunk`)
        per ``chunk_rows``-row slice of the delta, with the fact columns
        frozen for the round; the host merges the round's accumulator into
        the facts and feeds it back as the next delta.  More dispatches
        than the ``lax.while_loop`` path, but every program stays below
        ``SAFE_JOIN_CAP`` — this is the path that puts LUBM-1000-scale
        closures on the chip.  Agreement with the host reasoner is tested
        in ``tests/test_device_fixpoint.py``.
        """
        import jax.numpy as jnp
        from jax import lax

        r = self.reasoner
        s, p, o = r.facts.columns()
        n0 = len(s)
        if n0 == 0 or not self.rules:
            return 0
        masks = tuple(jnp.asarray(m) for m in self.bank.materialize()) or (
            jnp.zeros(1, dtype=bool),
        )
        def chunk_call(caps, *dyn):
            # NOTE: every scalar constant in the traced body must be a
            # numpy scalar (literal), not a jnp array — a concrete jnp
            # scalar created at trace time is lifted to a hoisted-constant
            # parameter on warm retraces, which the dispatch fast path
            # fails to feed once two capacity keys coexist (observed on
            # jax 0.9: "Executable expected parameter 0 of size 4...").
            from kolibrie_tpu.ops.pallas_kernels import pallas_enabled

            return _device_round_chunk(
                self.rules, caps, *dyn, use_pallas=pallas_enabled()
            )

        on_tpu = jax.default_backend() == "tpu"
        # all powers of two (user values rounded up), so chunk offsets stay
        # aligned across buffers: dynamic_slice never clamps a start index,
        # which would silently re-read earlier rows and skip tail rows
        Dc = _round_cap(chunk_rows, 8) if chunk_rows else min(
            _round_cap(n0, 1024), 1 << 19
        )
        J = join_cap or (
            SAFE_JOIN_CAP if on_tpu else _round_cap(4 * max(Dc, 1024), 1024)
        )
        D = _round_cap(
            max(delta_cap, Dc) if delta_cap else max(2 * Dc, 2048), Dc
        )
        F = _round_cap(n0 + D, 2048)
        attempts = 0

        with jax.enable_x64(True):

            def pad(x, cap):
                x = jnp.asarray(x, dtype=jnp.uint32)
                return jnp.concatenate(
                    [x, jnp.zeros(cap - x.shape[0], dtype=jnp.uint32)]
                )

            def grow(cols, old, new):
                return tuple(
                    jnp.concatenate([c, jnp.zeros(new - old, dtype=jnp.uint32)])
                    for c in cols
                )

            fs, fp, fo = pad(s, F), pad(p, F), pad(o, F)
            n_facts = n0
            # round-0 delta = all facts, in a chunk-aligned buffer
            dlen = _round_cap(n0, Dc)
            dels, delp, delo = pad(s, dlen), pad(p, dlen), pad(o, dlen)
            n_delta = n0

            for _round in range(10_000):
                _FIXPOINT_DELTA.observe(n_delta)
                # Readback discipline: chunks chain through DEVICE scalars
                # (n_acc, OR-ed overflow code) and the host syncs ONCE per
                # round attempt — per-round is the true minimum a
                # host-driven loop needs (termination + chunk count).
                while True:
                    accs = jnp.zeros(D, dtype=jnp.uint32)
                    accp = jnp.zeros(D, dtype=jnp.uint32)
                    acco = jnp.zeros(D, dtype=jnp.uint32)
                    n_acc_dev = jnp.int32(0)
                    code_dev = jnp.int32(0)
                    for off in range(0, n_delta, Dc):
                        m = min(Dc, n_delta - off)
                        ds = lax.dynamic_slice(dels, (off,), (Dc,))
                        dpp = lax.dynamic_slice(delp, (off,), (Dc,))
                        doo = lax.dynamic_slice(delo, (off,), (Dc,))
                        accs, accp, acco, n_acc_dev, ovf = chunk_call(
                            _Caps(F, D, J),
                            fs,
                            fp,
                            fo,
                            jnp.int32(n_facts),
                            ds,
                            dpp,
                            doo,
                            jnp.int32(m),
                            accs,
                            accp,
                            acco,
                            n_acc_dev,
                            masks,
                        )
                        code_dev = code_dev | ovf
                    code = int(code_dev)  # the one sync point
                    n_acc = int(n_acc_dev)
                    if code == 0:
                        break
                    # overflow: retry the WHOLE round (facts are frozen per
                    # round, so a round restart is exact) with the failing
                    # capacities adjusted
                    attempts += 1
                    if attempts > max_attempts:
                        raise RuntimeError(
                            "chunked device fixpoint: capacities failed "
                            "to converge"
                        )
                    if code & 1:
                        if on_tpu and 2 * J > SAFE_JOIN_CAP:
                            # doubling J would enter the faulting regime the
                            # chunked path exists to avoid — shrink the
                            # chunk instead (fewer delta seeds per program
                            # → smaller join output at the same J)
                            if Dc <= 1024:
                                raise JoinCapExceeded(2 * J)
                            Dc //= 2
                        else:
                            J *= 2
                    if code & 2:
                        D *= 2
                if n_acc == 0:
                    break
                # merge the round's accumulator into the fact columns; the
                # accumulator's zero tail lands past n_facts+n_acc where
                # fvalid masks it (and later rounds overwrite it)
                if n_facts + D > F:
                    newF = _round_cap(n_facts + D, 2048)
                    fs, fp, fo = grow((fs, fp, fo), F, newF)
                    F = newF
                fs = lax.dynamic_update_slice(fs, accs, (n_facts,))
                fp = lax.dynamic_update_slice(fp, accp, (n_facts,))
                fo = lax.dynamic_update_slice(fo, acco, (n_facts,))
                n_facts += n_acc
                # next round's delta = this round's accumulator (D is a
                # power of two >= Dc, so it stays chunk-aligned)
                dels, delp, delo, n_delta = accs, accp, acco, n_acc
            else:
                raise RuntimeError(
                    "device fixpoint hit the round limit before convergence"
                )

            self.last_rounds = _round  # productive rounds (final is empty)
            _FIXPOINT_ROUNDS.observe(_round)
            self.converged_caps = _Caps(F, D, J)
            if n_facts > n0:
                s_h = np.asarray(fs[:n_facts])
                p_h = np.asarray(fp[:n_facts])
                o_h = np.asarray(fo[:n_facts])
                r.facts.add_batch(s_h[n0:], p_h[n0:], o_h[n0:])
            return n_facts - n0


# Largest join capacity verified stable on the Mosaic toolchain it was
# last measured with (ROADMAP C6 re-runs the repro on this chip): composed
# fixpoint programs with join buffers past 2^21 rows
# raise a TPU device fault at dispatch (the same ops standalone — sorts to
# 16M rows, join_indices at 4M cap, gathers — all pass, so this is a
# composition-specific toolchain issue, not a memory or algorithm bound).
# Past it the reasoner transparently uses the host semi-naive path.
SAFE_JOIN_CAP = 2_097_152


class JoinCapExceeded(RuntimeError):
    """Raised when capacity doubling would cross SAFE_JOIN_CAP on TPU."""


def infer_semi_naive_device(reasoner) -> Optional[int]:
    """Device fixpoint if the rule set lowers; ``None`` → host fallback.

    Small inputs take the one-dispatch ``lax.while_loop`` program; inputs
    whose capacities would cross the toolchain-safe join bound take the
    host-driven chunked per-round driver (``infer_chunked``), whose
    programs all stay below the bound — the device handles both regimes.
    """
    try:
        fx = DeviceFixpoint(reasoner)
    except Unsupported:
        return None
    import jax

    try:
        if (
            jax.default_backend() == "tpu"
            and fx._caps(len(reasoner.facts)).join > SAFE_JOIN_CAP
        ):
            # one-dispatch program would cross the toolchain bound — run
            # the round-per-dispatch chunked driver instead
            return fx.infer_chunked()
        try:
            return fx.infer()
        except JoinCapExceeded:
            return fx.infer_chunked()  # doubling crossed the bound mid-run
    except JoinCapExceeded:
        # even minimum-size chunk programs would need a join buffer past
        # the toolchain bound (pathological fan-out) — host fallback
        return None
