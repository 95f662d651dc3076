"""Distributed semi-naive Datalog fixpoint over a device mesh.

The reference's parallel semi-naive (``datalog/src/reasoning/materialisation/
semi_naive_parallel.rs:11-177``) fans the per-round delta over a rayon thread
pool on one node.  Here the fact base itself is hash-partitioned across chips
(subject-owned, with an object-hashed mirror — see
:class:`~kolibrie_tpu.parallel.sharded_store.ShardedTripleStore`), and each
round is ONE compiled XLA program per shard:

  1. join the round's delta against the full fact base for every rule, in
     both premise positions (delta-as-p1 needs one ``all_to_all`` to move
     delta rows to the shard owning their join key; delta-as-p2 is local by
     construction),
  2. route derived triples to their subject-owner shard (``all_to_all``),
  3. sort-unique + set-difference against known facts → the next delta,
  4. ``psum`` the global new-fact count — the host loop stops at zero.

Supported rule shapes (the distributed fast path; everything else falls back
to the host reasoner, :mod:`kolibrie_tpu.reasoner`):

- unary:  ``head(X,Y) :- p(X,Y)``            (predicate renaming / RDFS sub*)
- binary: ``head(X,Z) :- p1(X,Y), p2(Y,Z)``  (transitivity / chains)
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kolibrie_tpu.core.rule import Rule
from kolibrie_tpu.core.terms import Term
from kolibrie_tpu.parallel.dist_join import (
    _dist_check_vma,
    exchange,
    local_join_u32,
    shard_of_dev,
    _LPAD32,
    _RPAD32,
)
from kolibrie_tpu.parallel.sharded_store import ShardedTripleStore


@dataclass
class DistRuleSet:
    """Rules lowered to u32 predicate IDs for the device fixpoint."""

    unary: List[Tuple[int, int]] = field(default_factory=list)  # (p, head)
    binary: List[Tuple[int, int, int]] = field(default_factory=list)  # (p1, p2, head)

    @classmethod
    def from_rules(cls, rules: List[Rule]) -> Optional["DistRuleSet"]:
        """Lower :class:`Rule` objects; ``None`` if any rule is unsupported."""
        rs = cls()
        for r in rules:
            if r.negative_premise or r.filters or len(r.conclusion) != 1:
                return None
            (hs, hp, ho) = _pat(r.conclusion[0])
            if len(r.premise) == 1:
                (s1, p1, o1) = _pat(r.premise[0])
                if (
                    isinstance(p1, int)
                    and isinstance(hp, int)
                    and s1 == hs
                    and o1 == ho
                    and isinstance(s1, str)
                    and isinstance(o1, str)
                    and s1 != o1
                ):
                    rs.unary.append((p1, hp))
                    continue
                return None
            if len(r.premise) == 2:
                (s1, p1, o1) = _pat(r.premise[0])
                (s2, p2, o2) = _pat(r.premise[1])
                ok = (
                    isinstance(p1, int)
                    and isinstance(p2, int)
                    and isinstance(hp, int)
                    and isinstance(s1, str)
                    and isinstance(o1, str)
                    and isinstance(o2, str)
                    and o1 == s2  # chain variable
                    and hs == s1
                    and ho == o2
                    and len({s1, o1, o2}) == 3
                )
                if ok:
                    rs.binary.append((p1, p2, hp))
                    continue
                return None
            return None
        return rs


def _pat(pattern):
    out = []
    for t in pattern:
        if isinstance(t, Term):
            out.append(t.value if t.is_variable else int(t.value))
        else:
            out.append(t)
    return tuple(out)


def _append_rows(cols, valid, new_cols, new_valid, cap):
    """Append new rows after the current valid block (static shapes)."""
    count = jnp.sum(valid).astype(jnp.int32)
    rank = jnp.cumsum(new_valid).astype(jnp.int32) - 1
    dest = jnp.where(new_valid, count + rank, cap)
    outs = tuple(
        c.at[dest].set(nc, mode="drop") for c, nc in zip(cols, new_cols)
    )
    out_valid = valid.at[dest].set(new_valid, mode="drop")
    overflow = jnp.maximum(count + jnp.sum(new_valid) - cap, 0)
    return outs, out_valid, overflow


def _sort_unique3(cols, valid, cap):
    """u32 (s,p,o) sort-unique with compaction (32-bit twin of
    device_join.sort_unique_rows)."""
    cs = [jnp.where(valid, c.astype(jnp.uint32), _RPAD32) for c in cols]
    sorted_ops = lax.sort(tuple(cs), num_keys=3)
    isnew = jnp.concatenate(
        [
            jnp.ones(1, bool),
            (sorted_ops[0][1:] != sorted_ops[0][:-1])
            | (sorted_ops[1][1:] != sorted_ops[1][:-1])
            | (sorted_ops[2][1:] != sorted_ops[2][:-1]),
        ]
    )
    row_valid = sorted_ops[0] != _RPAD32
    isnew = isnew & row_valid
    dest = jnp.where(isnew, jnp.cumsum(isnew) - 1, cap)
    n = jnp.sum(isnew)
    outs = tuple(
        jnp.zeros(cap, dtype=jnp.uint32).at[dest].set(c, mode="drop")
        for c in sorted_ops
    )
    return outs, jnp.arange(cap) < n, n


def _member3(ours, ours_valid, theirs, theirs_valid):
    """For each u32 (s,p,o) row of ``ours``: does it occur in ``theirs``?

    ``theirs`` is sorted lexicographically once (multi-operand ``lax.sort``);
    each probe then narrows [lo, hi) per key level with a vectorized
    fixed-step binary search.  The right bound of an integer key v is the
    left bound of v+1 (padding rows are excluded before the +1 can wrap).
    """
    ts, tp, to = (
        jnp.where(theirs_valid, c.astype(jnp.uint32), _RPAD32) for c in theirs
    )
    ts, tp, to = lax.sort((ts, tp, to), num_keys=3)
    n = ts.shape[0]
    s = jnp.where(ours_valid, ours[0].astype(jnp.uint32), _LPAD32)
    pcol = ours[1].astype(jnp.uint32)
    o = ours[2].astype(jnp.uint32)
    zero = jnp.zeros_like(s, dtype=jnp.int32)
    full = jnp.full_like(zero, n)
    lo1 = _bsearch(ts, zero, full, s)
    hi1 = _bsearch(ts, zero, full, s + 1)
    lo2 = _bsearch(tp, lo1, hi1, pcol)
    hi2 = _bsearch(tp, lo1, hi1, pcol + 1)
    lo3 = _bsearch(to, lo2, hi2, o)
    idx = jnp.clip(lo3, 0, n - 1)
    return ours_valid & (lo3 < hi2) & (to[idx] == o)


def _bsearch(arr, lo, hi, v):
    """Leftmost position in the per-row slice ``arr[lo:hi)`` with
    ``arr[pos] >= v`` — vectorized fixed-iteration binary search."""
    n = arr.shape[0]
    lo_ = lo.astype(jnp.int32)
    hi_ = hi.astype(jnp.int32)
    steps = max(int(np.ceil(np.log2(max(n, 2)))) + 2, 2)
    for _ in range(steps):
        active = lo_ < hi_
        mid = (lo_ + hi_) // 2
        mv = arr[jnp.clip(mid, 0, n - 1)]
        go = active & (mv < v)
        lo_ = jnp.where(go, mid + 1, lo_)
        hi_ = jnp.where(active & ~go, mid, hi_)
    return lo_


def _round_body(
    state,
    *,
    unary,
    binary,
    n,
    axis,
    fact_cap,
    delta_cap,
    join_cap,
    bucket_cap,
):
    """One semi-naive round on one shard (runs under shard_map)."""
    (fs, fp, fo, fv, gs, gp, go, gv, ds, dp_, do_, dv) = (a[0] for a in state)

    derived: List[Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]] = []
    drops = np.int32(0)
    local_ovf = np.int32(0)  # per-shard join/dedup capacity overruns

    for (pb, ph) in unary:
        m = dv & (dp_ == np.uint32(pb))
        derived.append((ds, jnp.full_like(dp_, ph), do_, m))

    for (p1, p2, ph) in binary:
        # Δ as premise1: key Y = Δ.o → shard hash(o); facts p2 subject-owned
        m1 = dv & (dp_ == np.uint32(p1))
        (es, ep, eo), ev, drop0 = exchange(
            (ds, dp_, do_),
            m1,
            shard_of_dev(do_, n),
            n,
            axis,
            bucket_cap,
        )
        drops = drops + drop0.astype(jnp.int32)
        rv = fv & (fp == np.uint32(p2))
        li, ri, jv, jtot = local_join_u32(eo, fs, join_cap, ev, rv)
        local_ovf = local_ovf + jnp.maximum(jtot - join_cap, 0)
        derived.append(
            (
                jnp.where(jv, es[li], 0),
                jnp.full(join_cap, ph, dtype=jnp.uint32),
                jnp.where(jv, fo[ri], 0),
                jv,
            )
        )
        # Δ as premise2: key Y = Δ.s (already owner-local); probe the
        # object-hashed mirror for p1 facts with fact.o == Δ.s
        m2 = dv & (dp_ == np.uint32(p2))
        lv2 = gv & (gp == np.uint32(p1))
        li2, ri2, jv2, jtot2 = local_join_u32(go, ds, join_cap, lv2, m2)
        local_ovf = local_ovf + jnp.maximum(jtot2 - join_cap, 0)
        derived.append(
            (
                jnp.where(jv2, gs[li2], 0),
                jnp.full(join_cap, ph, dtype=jnp.uint32),
                jnp.where(jv2, do_[ri2], 0),
                jv2,
            )
        )

    if derived:
        cs = jnp.concatenate([d[0] for d in derived])
        cp = jnp.concatenate([d[1] for d in derived])
        co = jnp.concatenate([d[2] for d in derived])
        cv = jnp.concatenate([d[3] for d in derived])
    else:
        cs = cp = co = jnp.zeros(1, dtype=jnp.uint32)
        cv = jnp.zeros(1, dtype=bool)

    # route derived to subject-owner, dedup, subtract known facts
    (rs_, rp_, ro_), rv_, drop1 = exchange(
        (cs, cp, co), cv, shard_of_dev(cs, n), n, axis, bucket_cap
    )
    (us, up, uo), uv, n_uniq = _sort_unique3((rs_, rp_, ro_), rv_, delta_cap)
    local_ovf = local_ovf + jnp.maximum(n_uniq.astype(jnp.int32) - delta_cap, 0)
    known = _member3((us, up, uo), uv, (fs, fp, fo), fv)
    nv = uv & ~known
    # compact the new delta to the front
    rank = jnp.cumsum(nv).astype(jnp.int32) - 1
    dst = jnp.where(nv, rank, delta_cap)
    nds = jnp.zeros(delta_cap, jnp.uint32).at[dst].set(us, mode="drop")
    ndp = jnp.zeros(delta_cap, jnp.uint32).at[dst].set(up, mode="drop")
    ndo = jnp.zeros(delta_cap, jnp.uint32).at[dst].set(uo, mode="drop")
    n_new = jnp.sum(nv)
    ndv = jnp.arange(delta_cap) < n_new

    # append new facts to the subject-owned copy
    (fs, fp, fo), fv, ovf1 = _append_rows(
        (fs, fp, fo), fv, (nds, ndp, ndo), ndv, fact_cap
    )
    # route new facts to object-owners and append to the mirror
    (ms, mp, mo), mv, drop2 = exchange(
        (nds, ndp, ndo), ndv, shard_of_dev(ndo, n), n, axis, bucket_cap
    )
    (gs, gp, go), gv, ovf2 = _append_rows((gs, gp, go), gv, (ms, mp, mo), mv, fact_cap)

    new_count = lax.psum(n_new.astype(jnp.int32), axis)
    overflow = (
        lax.psum((ovf1 + ovf2 + local_ovf).astype(jnp.int32), axis)
        + drop1.astype(jnp.int32)
        + drop2.astype(jnp.int32)
        + drops
    )
    out_state = tuple(
        a[None]
        for a in (fs, fp, fo, fv, gs, gp, go, gv, nds, ndp, ndo, ndv)
    )
    return out_state, new_count[None], overflow[None]


class DistributedReasoner:
    """Host driver for the device fixpoint.

    ``infer()`` runs semi-naive rounds until the global new-fact count is
    zero (one ``psum`` read per round — the only host sync).
    """

    def __init__(
        self,
        mesh: Mesh,
        ruleset: DistRuleSet,
        fact_cap: int = 4096,
        delta_cap: int = 2048,
        join_cap: int = 4096,
        bucket_cap: int = 1024,
    ):
        self.mesh = mesh
        self.axis = mesh.axis_names[0]
        self.n = mesh.devices.size
        self.ruleset = ruleset
        self.fact_cap = fact_cap
        self.delta_cap = delta_cap
        self.join_cap = join_cap
        self.bucket_cap = bucket_cap
        spec = P(self.axis, None)
        body = partial(
            _round_body,
            unary=tuple(ruleset.unary),
            binary=tuple(ruleset.binary),
            n=self.n,
            axis=self.axis,
            fact_cap=fact_cap,
            delta_cap=delta_cap,
            join_cap=join_cap,
            bucket_cap=bucket_cap,
        )
        self._round = jax.jit(
            jax.shard_map(
                lambda *state: body(state),
                mesh=mesh,
                check_vma=_dist_check_vma(),
                in_specs=(spec,) * 12,
                out_specs=((spec,) * 12, P(self.axis), P(self.axis)),
            )
        )

    def infer(self, store: ShardedTripleStore, max_rounds: int = 64) -> int:
        """Run to fixpoint; facts accumulate inside ``store``.  Returns the
        number of rounds executed (excluding the final empty round)."""
        if store.cap != self.fact_cap:
            raise ValueError("store capacity must match reasoner fact_cap")
        sh = NamedSharding(self.mesh, P(self.axis, None))
        # initial delta = all facts (round-0 semantics of semi-naive with
        # empty previous state — reference semi_naive.rs:57-59)
        ds = jax.device_put(np.asarray(store.by_subj[0]), sh)
        dp_ = jax.device_put(np.asarray(store.by_subj[1]), sh)
        do_ = jax.device_put(np.asarray(store.by_subj[2]), sh)
        dv = jax.device_put(np.asarray(store.by_subj_valid), sh)
        if self.delta_cap != store.cap:
            # re-fit the initial delta to delta_cap.  Valid rows sit in a
            # contiguous front block per shard, so losing any means a shard
            # holds more seed facts than delta_cap — refuse rather than
            # silently run an incomplete fixpoint.
            per_shard = np.asarray(store.by_subj_valid).sum(axis=1)
            if int(per_shard.max(initial=0)) > self.delta_cap:
                raise OverflowError(
                    f"initial delta ({int(per_shard.max())} facts on one "
                    f"shard) exceeds delta_cap={self.delta_cap}"
                )

            def fit(a, fill):
                out = np.full((self.n, self.delta_cap), fill, dtype=a.dtype)
                w = min(self.delta_cap, a.shape[1])
                out[:, :w] = np.asarray(a)[:, :w]
                return jax.device_put(out, sh)

            ds, dp_, do_ = (fit(np.asarray(x), 0) for x in (ds, dp_, do_))
            dv = fit(np.asarray(dv), False)
        state = (
            *store.by_subj,
            store.by_subj_valid,
            *store.by_obj,
            store.by_obj_valid,
            ds,
            dp_,
            do_,
            dv,
        )
        rounds = 0
        for _ in range(max_rounds):
            state, count, overflow = self._round(*state)
            if int(overflow[0]) > 0:
                raise OverflowError(
                    "distributed fixpoint buffer overflow — grow "
                    "fact_cap/delta_cap/join_cap/bucket_cap"
                )
            if int(count[0]) == 0:
                break
            rounds += 1
        store.by_subj = tuple(state[0:3])
        store.by_subj_valid = state[3]
        store.by_obj = tuple(state[4:7])
        store.by_obj_valid = state[7]
        # probe index rebuilds lazily on next ensure_subj_index()
        return rounds


def distributed_seminaive(
    mesh: Mesh,
    store: ShardedTripleStore,
    rules: List[Rule],
    **caps,
) -> int:
    """Convenience: lower rules and run the fixpoint.  Raises on rules the
    distributed fast path can't express (caller should fall back to the host
    :class:`~kolibrie_tpu.reasoner.reasoner.Reasoner`)."""
    rs = DistRuleSet.from_rules(rules)
    if rs is None:
        raise NotImplementedError(
            "rule set not expressible on the distributed fast path"
        )
    caps.setdefault("fact_cap", store.cap)
    dr = DistributedReasoner(mesh, rs, **caps)
    return dr.infer(store)
