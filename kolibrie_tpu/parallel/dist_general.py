"""General distributed semi-naive fixpoint: arbitrary rule shapes.

:mod:`kolibrie_tpu.parallel.dist_fixpoint` lowers only two rule shapes
(unary renaming, binary chains).  This module runs ARBITRARY positive rules
— any premise count, constants in any position, shared/repeated variables,
numeric filters, stratum-free NAF — across the device mesh, reusing the
single-chip lowering IR (:mod:`kolibrie_tpu.reasoner.device_fixpoint`).

Per round (one compiled shard_map program per shard):

1. seed a binding table from the shard-local delta for every (rule, seed
   premise) pair,
2. for each further premise, route binding rows to the shard owning the
   join key (``all_to_all``), then join locally against the subject-owned
   facts (key at subject) or the object-hashed mirror (key at object);
   extra shared variables beyond the routed key become post-join equality
   masks,
3. numeric filters gather replicated per-ID masks; NAF premises route rows
   to the owner of the instantiated negated subject and anti-check
   membership there,
4. conclusions are instantiated, routed to their subject owner, deduped
   (sort-unique), subtracted against known facts, appended to the facts and
   the object mirror; the global new-fact count is the ``psum`` the host
   loop terminates on.

Static-shape overflow protocol as everywhere else: overflowing rounds
report a global drop/overflow count; the host doubles capacities and
retries the round (facts state is only advanced by successful rounds
because overflowing appends raise before the store is updated).

Parity: ``datalog/src/reasoning/materialisation/semi_naive_parallel.rs:28-161``
(arbitrary premises over rayon) — redesigned as mesh-partitioned columnar
joins with ICI all-to-all instead of a shared-memory thread pool.
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kolibrie_tpu.core.rule import Rule
from kolibrie_tpu.ops import round_cap
from kolibrie_tpu.parallel.dist_fixpoint import _append_rows, _member3, _sort_unique3
from kolibrie_tpu.parallel.dist_join import (
    _dist_check_vma,
    _LPAD32,
    exchange,
    local_join_u32,
    shard_of_dev,
)
from kolibrie_tpu.parallel.sharded_store import ShardedTripleStore
from kolibrie_tpu.reasoner.device_fixpoint import (
    LoweredPremise,
    LoweredRule,
    Unsupported,
    _MaskBank,
    _scan_premise,
    lower_rules,
)

__all__ = ["DistGeneralReasoner", "distributed_seminaive_general", "Unsupported"]


# ---------------------------------------------------------------------------
# Distributed planning: single routed key per step, rest as equality masks
# ---------------------------------------------------------------------------


def _pos_of_var(prem: LoweredPremise) -> Dict[str, int]:
    return {v: pos for v, pos in prem.vars}


def _plan_rule_dist(premises: Tuple[LoweredPremise, ...]) -> tuple:
    """Per seed position: join order, and per step (key_var, key_pos,
    extra_eq_vars).  ``key_pos`` must be 0 (subject-owned facts) or
    2 (object mirror) — predicates are not a partition axis."""
    plans = []
    for i in range(len(premises)):
        order = [i]
        bound = {v for v, _ in premises[i].vars}
        remaining = [j for j in range(len(premises)) if j != i]
        steps: List[tuple] = []
        while remaining:
            best = None
            for j in remaining:
                pv = _pos_of_var(premises[j])
                shared = set(pv) & bound
                if not shared:
                    continue
                # prefer a subject-position key, then object
                key = None
                for v in sorted(shared):
                    if pv[v] == 0:
                        key = (v, 0)
                        break
                if key is None:
                    for v in sorted(shared):
                        if pv[v] == 2:
                            key = (v, 2)
                            break
                if key is None:
                    continue  # only predicate-position sharing: try later
                cand = (len(shared), j, key, tuple(sorted(shared - {key[0]})))
                if best is None or cand[0] > best[0]:
                    best = cand
            if best is None:
                raise Unsupported(
                    "premise join key not at subject/object position"
                )
            _, j, (kv, kpos), extra = best
            steps.append((j, kv, kpos, extra))
            bound |= {v for v, _ in premises[j].vars}
            remaining.remove(j)
        plans.append((i, tuple(steps)))
    return tuple(plans)


def lower_rules_dist(reasoner, rules: List[Rule]) -> Tuple[tuple, _MaskBank]:
    """Single-chip lowering + distributed join plans."""
    lowered, bank = lower_rules(reasoner, rules)
    out = []
    for lr in lowered:
        out.append((lr, _plan_rule_dist(lr.premises)))
    return tuple(out), bank


# ---------------------------------------------------------------------------
# Round body (runs under shard_map, one instance per shard)
# ---------------------------------------------------------------------------


def _exchange_table(table, valid, key_var, n, axis, bucket_cap):
    """Route a binding table to ``hash(table[key_var])`` owners; returns the
    routed table, validity, and the global dropped count."""
    names = sorted(table)
    cols = tuple(table[v] for v in names)
    routed, rvalid, dropped = exchange(
        cols, valid, shard_of_dev(table[key_var], n), n, axis, bucket_cap
    )
    out = dict(zip(names, routed))
    return out, rvalid, dropped


def _pos2var(prem: LoweredPremise) -> Dict[int, str]:
    m = {pos: v for v, pos in prem.vars}
    for a, b in prem.eq_pairs:
        m[b] = m[a]
    return m


def _instantiate(term_map, consts, table, length):
    cols = []
    for pos in range(3):
        if consts[pos] is not None:
            cols.append(jnp.full(length, consts[pos], dtype=jnp.uint32))
        else:
            cols.append(table[term_map[pos]])
    return cols


def _general_round(
    state,
    masks,
    *,
    rules,
    n,
    axis,
    fact_cap,
    delta_cap,
    join_cap,
    bucket_cap,
):
    (fs, fp, fo, fv, gs, gp, go, gv, ds, dp_, do_, dv) = (a[0] for a in state)
    masks = tuple(m for m in masks)  # replicated, no shard dim

    fcols = (fs, fp, fo)
    overflow = jnp.int32(0)
    parts: List[tuple] = []

    for lr, plans in rules:
        # ground-guard gate: shard-local membership in the subject-owned
        # block, psum'd — non-derivable (lowering gate), so constant
        # through the closure
        guard_ok = None
        for g in lr.guards:
            _t, gm = _scan_premise(g, fcols, fv)
            hit = lax.psum(jnp.any(gm).astype(jnp.int32), axis) > 0
            guard_ok = hit if guard_ok is None else (guard_ok & hit)
        for seed, steps in plans:
            table, valid = _scan_premise(lr.premises[seed], (ds, dp_, do_), dv)
            if guard_ok is not None:
                valid = valid & guard_ok
            for (j, kv, kpos, extra) in steps:
                prem = lr.premises[j]
                # route bindings to the shard owning the join key
                table, valid, dropped = _exchange_table(
                    table, valid, kv, n, axis, bucket_cap
                )
                overflow = overflow + dropped.astype(jnp.int32)
                if kpos == 0:
                    side_cols, side_valid, side_key = fcols, fv, fs
                else:
                    side_cols, side_valid, side_key = (gs, gp, go), gv, go
                ptable, pmask = _scan_premise(prem, side_cols, side_valid)
                li, ri, jvalid, total = local_join_u32(
                    table[kv], side_key, join_cap, valid, pmask
                )
                overflow = overflow + lax.psum(
                    jnp.maximum(total - join_cap, 0).astype(jnp.int32), axis
                )
                new_table = {v: c[li] for v, c in table.items()}
                for v, c in ptable.items():
                    if v not in new_table:
                        new_table[v] = c[ri]
                    elif v in extra:
                        # shared var beyond the routed key: equality mask
                        jvalid = jvalid & (new_table[v] == c[ri])
                table, valid = new_table, jvalid
            # filters (replicated per-ID masks)
            for f in lr.filters:
                col = table[f.var]
                if f.kind == "eq":
                    valid = valid & (col == jnp.uint32(f.const_id))
                elif f.kind == "ne":
                    valid = valid & (col != jnp.uint32(f.const_id))
                else:
                    m = masks[f.mask_idx]
                    valid = valid & m[jnp.minimum(col, m.shape[0] - 1)]
            # NAF: route to the owner of the instantiated negated subject,
            # anti-check membership in the subject-owned facts there
            for neg in lr.negs:
                p2v = _pos2var(neg)
                L = valid.shape[0]
                n_s, n_p, n_o = _instantiate(p2v, neg.consts, table, L)
                names = sorted(table)
                cols = tuple(table[v] for v in names) + (n_s, n_p, n_o)
                routed, rvalid, dropped = exchange(
                    cols, valid, shard_of_dev(n_s, n), n, axis, bucket_cap
                )
                overflow = overflow + dropped.astype(jnp.int32)
                table = dict(zip(names, routed[:-3]))
                member = _member3(routed[-3:], rvalid, fcols, fv)
                valid = rvalid & ~member
            # conclusions
            L = valid.shape[0]
            for concl in lr.concls:
                cols = []
                for kind, v in concl:
                    if kind == "const":
                        cols.append(jnp.full(L, v, dtype=jnp.uint32))
                    else:
                        cols.append(table[v])
                parts.append((cols[0], cols[1], cols[2], valid))

    cs = jnp.concatenate([p[0] for p in parts])
    cp = jnp.concatenate([p[1] for p in parts])
    co = jnp.concatenate([p[2] for p in parts])
    cv = jnp.concatenate([p[3] for p in parts])

    # route candidates to their subject owner, dedup, subtract known facts
    (rs_, rp_, ro_), rv_, drop1 = exchange(
        (cs, cp, co), cv, shard_of_dev(cs, n), n, axis, bucket_cap
    )
    (us, up, uo), uv, n_uniq = _sort_unique3((rs_, rp_, ro_), rv_, delta_cap)
    overflow = overflow + lax.psum(
        jnp.maximum(n_uniq.astype(jnp.int32) - delta_cap, 0), axis
    ) + drop1.astype(jnp.int32)
    known = _member3((us, up, uo), uv, fcols, fv)
    nv = uv & ~known
    rank = jnp.cumsum(nv).astype(jnp.int32) - 1
    dst = jnp.where(nv, rank, delta_cap)
    nds = jnp.zeros(delta_cap, jnp.uint32).at[dst].set(us, mode="drop")
    ndp = jnp.zeros(delta_cap, jnp.uint32).at[dst].set(up, mode="drop")
    ndo = jnp.zeros(delta_cap, jnp.uint32).at[dst].set(uo, mode="drop")
    n_new = jnp.sum(nv)
    ndv = jnp.arange(delta_cap) < n_new

    (fs, fp, fo), fv, ovf1 = _append_rows(
        (fs, fp, fo), fv, (nds, ndp, ndo), ndv, fact_cap
    )
    (ms_, mp_, mo_), mv, drop2 = exchange(
        (nds, ndp, ndo), ndv, shard_of_dev(ndo, n), n, axis, bucket_cap
    )
    (gs, gp, go), gv, ovf2 = _append_rows(
        (gs, gp, go), gv, (ms_, mp_, mo_), mv, fact_cap
    )

    new_count = lax.psum(n_new.astype(jnp.int32), axis)
    overflow = (
        overflow
        + lax.psum((ovf1 + ovf2).astype(jnp.int32), axis)
        + drop2.astype(jnp.int32)
    )
    out_state = tuple(
        a[None] for a in (fs, fp, fo, fv, gs, gp, go, gv, nds, ndp, ndo, ndv)
    )
    return out_state, new_count[None], overflow[None]


# ---------------------------------------------------------------------------
# Host driver
# ---------------------------------------------------------------------------


class DistGeneralReasoner:
    """Host driver for the general distributed fixpoint (see module doc)."""

    def __init__(
        self,
        mesh: Mesh,
        reasoner,
        fact_cap: Optional[int] = None,
        delta_cap: Optional[int] = None,
        join_cap: Optional[int] = None,
        bucket_cap: Optional[int] = None,
    ):
        self.mesh = mesh
        self.axis = mesh.axis_names[0]
        self.n = mesh.devices.size
        self.reasoner = reasoner
        self.rules, self.bank = lower_rules_dist(reasoner, reasoner.rules)
        n_local = max(1, -(-len(reasoner.facts) // self.n))
        self.fact_cap = fact_cap or round_cap(8 * n_local, 512)
        self.delta_cap = delta_cap or round_cap(4 * n_local, 256)
        self.join_cap = join_cap or round_cap(4 * n_local, 256)
        self.bucket_cap = bucket_cap or round_cap(4 * n_local, 256)

    def _round_fn(self):
        return self._round_fn_for(
            self.fact_cap, self.delta_cap, self.join_cap, self.bucket_cap
        )

    @lru_cache(maxsize=8)  # one entry per capacity attempt (infer doubles)
    def _round_fn_for(self, fact_cap, delta_cap, join_cap, bucket_cap):
        body = partial(
            _general_round,
            rules=self.rules,
            n=self.n,
            axis=self.axis,
            fact_cap=fact_cap,
            delta_cap=delta_cap,
            join_cap=join_cap,
            bucket_cap=bucket_cap,
        )
        spec = P(self.axis, None)
        rep = P()
        n_masks = len(self.bank.exprs)
        return jax.jit(
            jax.shard_map(
                lambda state, masks: body(state, masks),
                mesh=self.mesh,
                check_vma=_dist_check_vma(),
                in_specs=((spec,) * 12, (rep,) * n_masks),
                out_specs=((spec,) * 12, P(self.axis), P(self.axis)),
            )
        )

    def infer(self, max_rounds: int = 256, max_attempts: int = 8) -> int:
        """Run to fixpoint over a :class:`ShardedTripleStore` built from the
        reasoner's facts; derived facts are written back into
        ``reasoner.facts``.  Returns the number of derived facts."""
        r = self.reasoner
        s, p, o = r.facts.columns()
        n0 = len(s)
        if n0 == 0 or not self.rules:
            return 0
        for _attempt in range(max_attempts):
            derived = self._try_infer(s, p, o, max_rounds)
            if derived is not None:
                if derived:
                    arr = np.asarray(sorted(derived), dtype=np.uint32)
                    r.facts.add_batch(arr[:, 0], arr[:, 1], arr[:, 2])
                return len(derived)
            self.fact_cap *= 2
            self.delta_cap *= 2
            self.join_cap *= 2
            self.bucket_cap *= 2
        raise RuntimeError("distributed fixpoint capacities failed to converge")

    def _try_infer(self, s, p, o, max_rounds: int = 256):
        """One capacity attempt; None on overflow (caller doubles caps)."""
        store = ShardedTripleStore.from_columns(
            self.mesh, s, p, o, cap_per_shard=self.fact_cap
        )
        masks = tuple(jnp.asarray(m) for m in self.bank.materialize())
        round_fn = self._round_fn()
        sh = NamedSharding(self.mesh, P(self.axis, None))

        def fit(a, fill, dtype):
            out = np.full((self.n, self.delta_cap), fill, dtype=dtype)
            src = np.asarray(a)
            w = min(self.delta_cap, src.shape[1])
            out[:, :w] = src[:, :w]
            return jax.device_put(out, sh)

        per_shard = np.asarray(store.by_subj_valid).sum(axis=1)
        if int(per_shard.max(initial=0)) > self.delta_cap:
            return None  # initial delta does not fit: grow delta_cap
        state = (
            *store.by_subj,
            store.by_subj_valid,
            *store.by_obj,
            store.by_obj_valid,
            fit(store.by_subj[0], 0, np.uint32),
            fit(store.by_subj[1], 0, np.uint32),
            fit(store.by_subj[2], 0, np.uint32),
            fit(store.by_subj_valid, False, bool),
        )
        converged = False
        for _ in range(max_rounds):
            state, count, overflow = round_fn(state, masks)
            if int(overflow[0]) > 0:
                return None
            if int(count[0]) == 0:
                converged = True
                break
        if not converged:
            raise RuntimeError(
                "distributed fixpoint hit the round limit before convergence"
            )
        # collect facts back: every valid subject-owned row across shards
        fs = np.asarray(state[0]).reshape(-1)
        fp = np.asarray(state[1]).reshape(-1)
        fo = np.asarray(state[2]).reshape(-1)
        fv = np.asarray(state[3]).reshape(-1)
        all_facts = set(
            zip(fs[fv].tolist(), fp[fv].tolist(), fo[fv].tolist())
        )
        base = set(zip(s.tolist(), p.tolist(), o.tolist()))
        return all_facts - base


def distributed_seminaive_general(mesh: Mesh, reasoner, **caps) -> int:
    """Lower the reasoner's rules for the mesh and run the general
    distributed fixpoint; raises :class:`Unsupported` for rule shapes even
    this path can't express (quoted patterns, predicate-position joins) —
    callers then fall back to the host reasoner."""
    return DistGeneralReasoner(mesh, reasoner, **caps).infer()
