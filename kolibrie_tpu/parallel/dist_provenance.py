"""Distributed provenance semi-naive fixpoint: tag columns over the mesh.

Extends the general distributed fixpoint
(:mod:`kolibrie_tpu.parallel.dist_general`) with f64 semiring tag columns
for the idempotent scalar semirings (minmax / boolean / expiration — the
same family the single-chip device path accelerates,
:mod:`kolibrie_tpu.reasoner.device_provenance`): ⊗ = ``min`` carried
through the routed join chain, ⊕ = ``max`` via group-max dedup on the
conclusion owner shard, in-place tag improvement on the owner, and
improved facts re-entering the delta.  Tags ride the same ``all_to_all``
exchanges as the binding columns (``bucketize`` is dtype-generic), and the
fixpoint terminates on ``psum(new + improved) == 0``.

TagStore parity follows the single-chip device path exactly: NaN in a tag
column means "no explicit TagStore entry" — premise reads see ``one()``,
but a fact's first derivation OVERWRITES (``update_disjunction`` inserts),
later derivations ⊕-merge.

The subject-owned fact block is authoritative for tags; the object-hash
mirror's tag column is refreshed for new AND improved facts (routed to the
object owner and scattered by exact (s,p,o) index lookup) so object-keyed
premise reads stay consistent.

The non-idempotent AddMult semiring also runs distributed (``kind=
"addmult"``): the round adds exactly-once accounting — OLD (facts \\ delta)
views of both fact blocks for premise positions before the seed, and ⊕ as
a shard-local segment noisy-OR in log space (every derivation of a fact
lands on its subject owner, so the local reduction is globally exact) —
mirroring the single-chip :func:`_prov_round_addmult`.  Rule sets whose
accumulation is evaluation-order-dependent (a rule's conclusions feed a
later rule's premises) are refused, exactly like the single-chip path.
Stratified NAF runs distributed for the idempotent family: after the
positive stratum quiesces, a :func:`_naf_pass` mesh program evaluates each
NAF rule's body over the full fact block and resolves negated premises
with a two-hop exchange (ground keys to their subject owner, negated tags
back), then the pass's delta re-enters the positive stratum — the same
stratified alternation as the single-chip driver.  Cross-blocking NAF
rule sets (a conclusion unifying another rule's negated premise) dispatch
ONE rule per mesh program in host rule order, with the pass delta
recovered from the per-shard appended rows at pass end (round 5; same
semantics as the single-chip sequential driver).  NAF over addmult and
rules whose conclusion unifies their OWN negated premise stay host-side
(`Unsupported`), as do the structural semirings.

Parity: ``datalog/.../provenance_semi_naive.rs:26-34,134-197`` over
``semi_naive_parallel.rs``'s partitioning — redesigned as mesh-partitioned
tagged columnar joins with ICI all-to-all.  Agreement with the host
provenance loop is tested in ``tests/test_dist_provenance.py`` on the
virtual CPU mesh.
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
from kolibrie_tpu.parallel.dist_fixpoint import _bsearch, _member3
from kolibrie_tpu.parallel.dist_join import (
    _dist_check_vma,
    _LPAD32,
    _RPAD32,
    exchange,
    local_join_u32,
    mix32,
    shard_of_dev,
)
from kolibrie_tpu.parallel.dist_general import (
    _instantiate,
    _pos2var,
    lower_rules_dist,
)
from kolibrie_tpu.parallel.sharded_store import partition_rows, shard_of
from kolibrie_tpu.reasoner.device_fixpoint import Unsupported, _scan_premise
from kolibrie_tpu.reasoner.device_provenance import (
    _ADDMULT_TAG_EQ,
    _addmult_order_sensitive,
    _decode_tags,
    _guard_tag_array,
    _naf_cross_blocking,
    _naf_self_blocking,
    _naf_premise_drift,
    _seed_tag_arrays,
    supports_idempotent,
)

__all__ = ["DistProvenanceReasoner", "Unsupported"]


def _index3(ours, ours_valid, theirs, theirs_valid, miss):
    """Exact (s,p,o) → row index into ``theirs`` (``miss`` when absent).

    Same 3-level narrowing as ``_member3`` but sorts an index operand along
    so the matched SORTED position maps back to the original row."""
    n = theirs[0].shape[0]
    ts, tp, to = (
        jnp.where(theirs_valid, c.astype(jnp.uint32), _RPAD32) for c in theirs
    )
    perm0 = jnp.arange(n, dtype=jnp.int32)
    ts, tp, to, perm = lax.sort((ts, tp, to, perm0), num_keys=3)
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
    found = ours_valid & (lo3 < hi2) & (to[idx] == o)
    return jnp.where(found, perm[idx], miss), found


def _exchange_tagged(table, tag, valid, key_col, n, axis, bucket_cap):
    """Route a binding table + its tag column to ``hash(key_col)`` owners."""
    names = sorted(table)
    cols = tuple(table[v] for v in names) + (tag,)
    routed, rvalid, dropped = exchange(
        cols, valid, shard_of_dev(key_col, n), n, axis, bucket_cap
    )
    return dict(zip(names, routed[:-1])), routed[-1], rvalid, dropped


def _tagged_round(
    state,
    masks,
    one_enc,
    gtags,
    *,
    rules,
    n,
    axis,
    fact_cap,
    delta_cap,
    join_cap,
    bucket_cap,
    kind="idem",
):
    (
        fs,
        fp,
        fo,
        ftag,
        fv,
        gs,
        gp,
        go,
        gtag,
        gv,
        ds,
        dp_,
        do_,
        dtag,
        dv,
    ) = (a[0] for a in state)
    masks = tuple(m for m in masks)
    one_enc = one_enc[0]

    fcols = (fs, fp, fo)
    overflow = jnp.int32(0)
    parts: List[tuple] = []

    if kind == "addmult":
        # exactly-once decomposition needs OLD (= facts \ delta) views of
        # both fact blocks.  The delta is subject-partitioned like the
        # subject-owned block (local lookup); the object mirror's mask
        # needs one routing of the delta to object owners.
        didx_f, dfound_f = _index3((ds, dp_, do_), dv, fcols, fv, fact_cap)
        in_f = (
            jnp.zeros(fact_cap, bool)
            .at[jnp.where(dfound_f, didx_f, fact_cap)]
            .set(True, mode="drop")
        )
        old_fv = fv & ~in_f
        (rds, rdp, rdo), rdv, dropd = exchange(
            (ds, dp_, do_), dv, shard_of_dev(do_, n), n, axis, bucket_cap
        )
        overflow = overflow + dropd.astype(jnp.int32)
        didx_g, dfound_g = _index3(
            (rds, rdp, rdo), rdv, (gs, gp, go), gv, fact_cap
        )
        in_g = (
            jnp.zeros(fact_cap, bool)
            .at[jnp.where(dfound_g, didx_g, fact_cap)]
            .set(True, mode="drop")
        )
        old_gv = gv & ~in_g
    else:
        old_fv, old_gv = fv, gv  # idempotent ⊕: duplicates are harmless

    for r_idx, (lr, plans) in enumerate(rules):
        for seed, steps in plans:
            table, valid = _scan_premise(lr.premises[seed], (ds, dp_, do_), dv)
            # delta tags are EFFECTIVE values (never NaN); statically-
            # satisfied ground guards fold their closure-constant tags in
            if kind == "addmult":
                tag = dtag * gtags[r_idx]
            else:
                tag = jnp.minimum(dtag, gtags[r_idx])
            for (j, kv, kpos, extra) in steps:
                prem = lr.premises[j]
                table, tag, valid, dropped = _exchange_tagged(
                    table, tag, valid, table[kv], n, axis, bucket_cap
                )
                overflow = overflow + dropped.astype(jnp.int32)
                if kpos == 0:
                    side_cols, side_key, side_tag = fcols, fs, ftag
                    side_valid = old_fv if j < seed else fv
                else:
                    side_cols, side_key, side_tag = (gs, gp, go), go, gtag
                    side_valid = old_gv if j < seed else gv
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
                        jvalid = jvalid & (new_table[v] == c[ri])
                # ⊗ (min for the idempotent family, product for addmult);
                # absent (NaN) premise entries read as one()
                ptag = side_tag[ri]
                ptag = jnp.where(jnp.isnan(ptag), one_enc, ptag)
                if kind == "addmult":
                    tag = tag[li] * ptag
                else:
                    tag = jnp.minimum(tag[li], ptag)
                table, valid = new_table, jvalid
            for f in lr.filters:
                col = table[f.var]
                if f.kind == "eq":
                    valid = valid & (col == np.uint32(f.const_id))
                elif f.kind == "ne":
                    valid = valid & (col != np.uint32(f.const_id))
                else:
                    m = masks[f.mask_idx]
                    valid = valid & m[jnp.minimum(col, m.shape[0] - 1)]
            # zero-tag pruning
            valid = valid & (tag > 0.0)
            L = valid.shape[0]
            for concl in lr.concls:
                cols = []
                for tkind, v in concl:
                    if tkind == "const":
                        cols.append(jnp.full(L, v, dtype=jnp.uint32))
                    else:
                        cols.append(table[v])
                parts.append((cols[0], cols[1], cols[2], tag, valid))

    return _commit_candidates(
        parts,
        overflow,
        fs,
        fp,
        fo,
        ftag,
        fv,
        gs,
        gp,
        go,
        gtag,
        gv,
        kind=kind,
        n=n,
        axis=axis,
        fact_cap=fact_cap,
        delta_cap=delta_cap,
        bucket_cap=bucket_cap,
    )


def _commit_candidates(
    parts,
    overflow,
    fs,
    fp,
    fo,
    ftag,
    fv,
    gs,
    gp,
    go,
    gtag,
    gv,
    *,
    kind,
    n,
    axis,
    fact_cap,
    delta_cap,
    bucket_cap,
    fresh_delta_only=False,
):
    """Shared commit tail of the distributed tagged round programs: route
    candidate conclusions to their subject owner, segment-⊕ per (s,p,o)
    group, merge into the subject-owned fact block, refresh the object-hash
    mirror, and emit the next delta (new ∪ changed — or new ONLY under
    ``fresh_delta_only``, the NAF-pass/host-``naf_new`` contract)."""
    fcols = (fs, fp, fo)

    cs = jnp.concatenate([p[0] for p in parts])
    cp = jnp.concatenate([p[1] for p in parts])
    co = jnp.concatenate([p[2] for p in parts])
    ct = jnp.concatenate([p[3] for p in parts])
    cv = jnp.concatenate([p[4] for p in parts])

    # route candidates (with tags) to their subject owner
    (rs_, rp_, ro_, rt_), rv_, drop1 = exchange(
        (cs, cp, co, ct), cv, shard_of_dev(cs, n), n, axis, bucket_cap
    )
    overflow = overflow + drop1.astype(jnp.int32)

    # group the candidates per (s,p,o) — every derivation of a fact lands
    # on its subject owner, so a shard-local segment ⊕ is globally exact
    sent = _RPAD32
    ss = jnp.where(rv_, rs_, sent)
    sp = jnp.where(rv_, rp_, sent)
    so = jnp.where(rv_, ro_, sent)
    if kind == "addmult":
        # ⊕ = noisy-OR over the group, folded as a segment reduction in
        # log space: 1 - ∏(1-pᵢ) = -expm1(Σ log1p(-pᵢ))
        st = jnp.where(rv_, jnp.clip(rt_, 0.0, 1.0), 0.0)
        ss, sp, so, st = lax.sort((ss, sp, so, st), num_keys=3)
    else:
        # idempotent ⊕ = max: 4-key sort with -tag tiebreak, first row per
        # group carries the max
        st = jnp.where(rv_, rt_, 0.0)
        ss, sp, so, negtag = lax.sort((ss, sp, so, -st), num_keys=4)
        st = -negtag
    isnew = jnp.concatenate(
        [
            jnp.ones(1, bool),
            (ss[1:] != ss[:-1]) | (sp[1:] != sp[:-1]) | (so[1:] != so[:-1]),
        ]
    )
    isnew = isnew & (ss != sent)
    n_uniq = jnp.sum(isnew)
    overflow = overflow + lax.psum(
        jnp.maximum(n_uniq.astype(jnp.int32) - delta_cap, 0), axis
    )
    dest = jnp.where(isnew, jnp.cumsum(isnew) - 1, delta_cap)
    us = jnp.zeros(delta_cap, jnp.uint32).at[dest].set(ss, mode="drop")
    up = jnp.zeros(delta_cap, jnp.uint32).at[dest].set(sp, mode="drop")
    uo = jnp.zeros(delta_cap, jnp.uint32).at[dest].set(so, mode="drop")
    if kind == "addmult":
        seg = jnp.cumsum(isnew) - 1
        segdst = jnp.where(ss != sent, seg, delta_cap)
        logsum = (
            jnp.zeros(delta_cap, jnp.float64)
            .at[segdst]
            .add(jnp.log1p(-st), mode="drop")
        )
        ut = -jnp.expm1(logsum)
    else:
        ut = jnp.zeros(delta_cap, jnp.float64).at[dest].set(st, mode="drop")
    uv = jnp.arange(delta_cap) < n_uniq

    # owner-local exact lookup: index into the subject-owned fact block
    fidx, found = _index3((us, up, uo), uv, fcols, fv, fact_cap)
    old_tag = ftag[jnp.clip(fidx, 0, fact_cap - 1)]
    absent = found & jnp.isnan(old_tag)
    if kind == "addmult":
        # update_disjunction parity: saturated (≥1) short-circuits; else
        # new = old ⊕ g with the 1e-12 tag_eq change cutoff
        saturated = found & (old_tag >= 1.0)  # NaN compares False
        merged = old_tag + ut - old_tag * ut
        improved = (
            found
            & ~absent
            & ~saturated
            & (jnp.abs(merged - old_tag) >= _ADDMULT_TAG_EQ)
        )
        ut = jnp.where(improved, merged, ut)  # stored/delta value
    else:
        improved = found & (ut > old_tag)  # NaN compares False
    changed = absent | improved
    fresh = uv & ~found

    # append new facts (with tags) to the subject-owned block
    n_fact_local = jnp.sum(fv)
    n_new = jnp.sum(fresh)
    overflow = overflow + lax.psum(
        jnp.maximum(
            (n_fact_local + n_new).astype(jnp.int32) - fact_cap, 0
        ),
        axis,
    )
    adest = jnp.where(fresh, n_fact_local + jnp.cumsum(fresh) - 1, fact_cap)
    fs = fs.at[adest].set(us, mode="drop")
    fp = fp.at[adest].set(up, mode="drop")
    fo = fo.at[adest].set(uo, mode="drop")
    ftag = ftag.at[adest].set(ut, mode="drop")
    fv = fv.at[adest].set(jnp.ones(delta_cap, bool), mode="drop")
    # in-place store for changed facts (overwrite-or-grown-max = ut)
    ftag = ftag.at[jnp.where(changed, fidx, fact_cap)].set(ut, mode="drop")

    # next delta = new ∪ changed (subject-owned rows with final tags)
    dmask = fresh | changed
    n_dnext = jnp.sum(dmask)
    ddest = jnp.where(dmask, jnp.cumsum(dmask) - 1, delta_cap)
    nds = jnp.zeros(delta_cap, jnp.uint32).at[ddest].set(us, mode="drop")
    ndp = jnp.zeros(delta_cap, jnp.uint32).at[ddest].set(up, mode="drop")
    ndo = jnp.zeros(delta_cap, jnp.uint32).at[ddest].set(uo, mode="drop")
    ndt = jnp.zeros(delta_cap, jnp.float64).at[ddest].set(ut, mode="drop")
    ndv = jnp.arange(delta_cap) < n_dnext

    # refresh the object-hash mirror for new AND changed rows: route to the
    # object owner, append the fresh ones, scatter tags for the rest
    mflag = _compact(fresh, dmask, ddest, delta_cap)
    (ms_, mp_, mo_, mt_, mfresh), mv, drop2 = exchange(
        (nds, ndp, ndo, ndt, mflag),
        ndv,
        shard_of_dev(ndo, n),
        n,
        axis,
        bucket_cap,
    )
    overflow = overflow + drop2.astype(jnp.int32)
    mfresh_b = mv & (mfresh > 0)
    mold_b = mv & (mfresh == 0)
    n_g_local = jnp.sum(gv)
    n_gnew = jnp.sum(mfresh_b)
    overflow = overflow + lax.psum(
        jnp.maximum((n_g_local + n_gnew).astype(jnp.int32) - fact_cap, 0),
        axis,
    )
    gdest = jnp.where(mfresh_b, n_g_local + jnp.cumsum(mfresh_b) - 1, fact_cap)
    gs = gs.at[gdest].set(ms_, mode="drop")
    gp = gp.at[gdest].set(mp_, mode="drop")
    go = go.at[gdest].set(mo_, mode="drop")
    gtag = gtag.at[gdest].set(mt_, mode="drop")
    gv = gv.at[gdest].set(jnp.ones_like(mfresh_b), mode="drop")
    gidx, gfound = _index3(
        (ms_, mp_, mo_), mold_b, (gs, gp, go), gv, fact_cap
    )
    gtag = gtag.at[jnp.where(gfound, gidx, fact_cap)].set(mt_, mode="drop")

    if fresh_delta_only:
        # returned delta = NEW facts only (host naf_new parity); the
        # mirror refresh above still covered tag-improved rows
        n_dnext = jnp.sum(fresh)
        fdest = jnp.where(fresh, jnp.cumsum(fresh) - 1, delta_cap)
        nds = jnp.zeros(delta_cap, jnp.uint32).at[fdest].set(us, mode="drop")
        ndp = jnp.zeros(delta_cap, jnp.uint32).at[fdest].set(up, mode="drop")
        ndo = jnp.zeros(delta_cap, jnp.uint32).at[fdest].set(uo, mode="drop")
        ndt = jnp.zeros(delta_cap, jnp.float64).at[fdest].set(ut, mode="drop")
        ndv = jnp.arange(delta_cap) < n_dnext

    new_count = lax.psum(n_dnext.astype(jnp.int32), axis)
    out_state = tuple(
        a[None]
        for a in (
            fs,
            fp,
            fo,
            ftag,
            fv,
            gs,
            gp,
            go,
            gtag,
            gv,
            nds,
            ndp,
            ndo,
            ndt,
            ndv,
        )
    )
    return out_state, new_count[None], overflow[None]


def _naf_body(
    lr,
    plans,
    fcols,
    fv,
    gside,
    eff_f,
    eff_g,
    start_tag,
    combine,
    masks,
    n,
    axis,
    join_cap,
    bucket_cap,
):
    """Shared NAF-rule body evaluation over ALL facts: seed scan, routed
    joins with the per-row tag folded by ``combine`` (⊗ = min for the
    idempotent family, product for addmult), extra-var equality, filters.
    Returns ``(table, tag, valid, overflow)`` — the negated premises and
    commit differ per pass and stay with the callers."""
    gs, gp, go, gv = gside
    fs = fcols[0]
    overflow = jnp.int32(0)
    seed, steps = plans[0]
    table, valid = _scan_premise(lr.premises[seed], fcols, fv)
    tag = start_tag
    for (j, kv, kpos, extra) in steps:
        prem = lr.premises[j]
        table, tag, valid, dropped = _exchange_tagged(
            table, tag, valid, table[kv], n, axis, bucket_cap
        )
        overflow = overflow + dropped.astype(jnp.int32)
        if kpos == 0:
            side_cols, side_key, side_eff, side_valid = fcols, fs, eff_f, fv
        else:
            side_cols, side_key, side_eff, side_valid = (
                (gs, gp, go),
                go,
                eff_g,
                gv,
            )
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
                jvalid = jvalid & (new_table[v] == c[ri])
        tag = combine(tag[li], side_eff[ri])
        table, valid = new_table, jvalid
    for f in lr.filters:
        col = table[f.var]
        if f.kind == "eq":
            valid = valid & (col == np.uint32(f.const_id))
        elif f.kind == "ne":
            valid = valid & (col != np.uint32(f.const_id))
        else:
            m = masks[f.mask_idx]
            valid = valid & m[jnp.minimum(col, m.shape[0] - 1)]
    return table, tag, valid, overflow


def _naf_pass(
    state,
    masks,
    one_enc,
    gtags,
    *,
    rules,
    neg_kind,
    n,
    axis,
    fact_cap,
    delta_cap,
    join_cap,
    bucket_cap,
):
    """One stratified NAF pass over the quiesced positive fixpoint, as a
    mesh program (single-chip :func:`device_provenance._prov_naf_pass`
    twin).  Each NAF rule's positive body is evaluated against the FULL
    subject-owned fact block (idempotent ⊕ — re-derivation is harmless);
    every negated premise is resolved with a two-hop exchange: ground
    (s,p,o) keys ride to their hash(subject) owner for an exact lookup,
    and the negated tag (absent ⇒ one(), present ⇒ ⊖tag) rides back to
    the origin shard's row.  Commit tail shared with the round program.
    """
    from kolibrie_tpu.reasoner.device_provenance import _negate_enc

    (
        fs,
        fp,
        fo,
        ftag,
        fv,
        gs,
        gp,
        go,
        gtag,
        gv,
        ds,
        dp_,
        do_,
        dtag,
        dv,
    ) = (a[0] for a in state)
    masks = tuple(m for m in masks)
    one_enc = one_enc[0]

    fcols = (fs, fp, fo)
    eff_f = jnp.where(jnp.isnan(ftag), one_enc, ftag)
    eff_g = jnp.where(jnp.isnan(gtag), one_enc, gtag)
    overflow = jnp.int32(0)
    parts: List[tuple] = []

    for r_idx, (lr, plans) in enumerate(rules):
        table, tag, valid, ovf_b = _naf_body(
            lr,
            plans,
            fcols,
            fv,
            (gs, gp, go, gv),
            eff_f,
            eff_g,
            jnp.minimum(eff_f, gtags[r_idx]),
            jnp.minimum,
            masks,
            n,
            axis,
            join_cap,
            bucket_cap,
        )
        overflow = overflow + ovf_b
        L = valid.shape[0]
        me = lax.axis_index(axis).astype(jnp.int32)
        for neg in lr.negs:
            term_map = _pos2var(neg)
            qs, qp, qo = _instantiate(term_map, neg.consts, table, L)
            rowid = jnp.arange(L, dtype=jnp.int32)
            origin = jnp.full(L, 0, jnp.int32) + me
            (rqs, rqp, rqo, rrow, rorig), rqv, d1 = exchange(
                (qs, qp, qo, rowid, origin),
                valid,
                shard_of_dev(qs, n),
                n,
                axis,
                bucket_cap,
            )
            overflow = overflow + d1.astype(jnp.int32)
            idx, found = _index3(
                (rqs, rqp, rqo), rqv, fcols, fv, fact_cap
            )
            t = eff_f[jnp.clip(idx, 0, fact_cap - 1)]
            ntag = jnp.where(
                found, _negate_enc(t, neg_kind, one_enc), one_enc
            )
            (brow, bnt), bv, d2 = exchange(
                (rrow, ntag), rqv, rorig, n, axis, bucket_cap
            )
            overflow = overflow + d2.astype(jnp.int32)
            ntag_buf = (
                jnp.full(L, one_enc, jnp.float64)
                .at[jnp.where(bv, brow, L)]
                .set(bnt, mode="drop")
            )
            tag = jnp.minimum(tag, ntag_buf)
        # zero-tag pruning
        valid = valid & (tag > 0.0)
        for concl in lr.concls:
            cols = []
            for tkind, v in concl:
                if tkind == "const":
                    cols.append(jnp.full(L, v, dtype=jnp.uint32))
                else:
                    cols.append(table[v])
            parts.append((cols[0], cols[1], cols[2], tag, valid))

    return _commit_candidates(
        parts,
        overflow,
        fs,
        fp,
        fo,
        ftag,
        fv,
        gs,
        gp,
        go,
        gtag,
        gv,
        kind="idem",
        n=n,
        axis=axis,
        fact_cap=fact_cap,
        delta_cap=delta_cap,
        bucket_cap=bucket_cap,
        fresh_delta_only=True,
    )


def _naf_pass_addmult(
    state,
    seen,
    n_seen,
    masks,
    one_enc,
    gtag,
    *,
    rule,
    n,
    axis,
    fact_cap,
    delta_cap,
    join_cap,
    bucket_cap,
    seen_cap,
):
    """ONE NAF rule's stratified pass for the addmult semiring, as a mesh
    program (single-chip :func:`device_provenance._prov_naf_pass_addmult`
    twin).  The driver dispatches rules sequentially in host order.

    Exactly-once accounting on the mesh: candidate derivation rows route
    by a hash of their FULL variable binding to a binding-owner shard, so
    the owner-local [seen ∥ candidates] multi-operand sort (dedup +
    membership + next-seen in one sort, exactly the single-chip trick) is
    globally exact — the same binding always lands on the same owner.
    ``seen`` is one sorted u32 column per rule variable, sharded
    ``(n, seen_cap)``; ``n_seen`` is the per-shard count.

    Negated premises resolve from the binding owner with the same two-hop
    exchange as the idempotent pass (⊖ = 1 − t); conclusions instantiate
    from the owned binding columns and flow into the shared commit with
    ``kind="addmult"`` (segment noisy-OR at the subject owner) and
    ``fresh_delta_only`` (host ``naf_new`` parity).
    """
    lr, plans = rule
    (
        fs,
        fp,
        fo,
        ftag,
        fv,
        gs,
        gp,
        go,
        gtag_blk,
        gv,
        _ds,
        _dp,
        _do,
        _dt,
        _dv,
    ) = (a[0] for a in state)
    seen = tuple(a[0] for a in seen)
    n_seen = n_seen[0][0]
    masks = tuple(m for m in masks)
    # one_enc rides only for signature symmetry with the idempotent pass
    # (addmult's ⊗/⊕ identities are the literals 1.0 / 0.0 below)
    g_scalar = gtag[0]

    fcols = (fs, fp, fo)
    eff_f = jnp.where(jnp.isnan(ftag), 1.0, ftag)
    eff_g = jnp.where(jnp.isnan(gtag_blk), 1.0, gtag_blk)

    # ---- body over ALL facts, ⊗ = product --------------------------------
    table, tag, valid, overflow = _naf_body(
        lr,
        plans,
        fcols,
        fv,
        (gs, gp, go, gv),
        eff_f,
        eff_g,
        eff_f * g_scalar,
        lambda a, b: a * b,
        masks,
        n,
        axis,
        join_cap,
        bucket_cap,
    )

    # ---- route candidates to their binding owner -------------------------
    var_names = tuple(sorted(table))
    bhash = jnp.zeros(valid.shape[0], dtype=jnp.uint32)
    for v in var_names:
        bhash = mix32(bhash ^ table[v])
    routed, rvalid, d_route = exchange(
        tuple(table[v] for v in var_names) + (tag,),
        valid,
        (bhash % np.uint32(n)).astype(jnp.int32),
        n,
        axis,
        bucket_cap,
    )
    overflow = overflow + d_route.astype(jnp.int32)
    bind_in = routed[: len(var_names)]
    tag_in = routed[len(var_names)]
    n_cand = rvalid.shape[0]

    # ---- owner-local seen/dedup: one multi-operand sort ------------------
    sent = _RPAD32
    seen_valid = jnp.arange(seen_cap, dtype=jnp.int32) < n_seen
    ops = []
    for k in range(len(var_names)):
        cand = jnp.where(rvalid, bind_in[k], sent)
        sc = jnp.where(seen_valid, seen[k], sent)
        ops.append(jnp.concatenate([sc, cand]))
    flag = jnp.concatenate(
        [
            jnp.zeros(seen_cap, dtype=jnp.uint32),
            jnp.ones(n_cand, dtype=jnp.uint32),
        ]
    )
    payload_tag = jnp.concatenate([jnp.zeros(seen_cap, jnp.float64), tag_in])
    sorted_all = lax.sort(
        (*ops, flag, payload_tag), num_keys=len(var_names) + 1
    )
    scols = sorted_all[: len(var_names)]
    sflag = sorted_all[len(var_names)]
    stag = sorted_all[len(var_names) + 1]
    live = scols[0] != sent
    head = jnp.concatenate(
        [
            jnp.ones(1, bool),
            jnp.any(jnp.stack([c[1:] != c[:-1] for c in scols]), axis=0),
        ]
    )
    fire = live & head & (sflag == 1)
    keep = live & head
    n_seen_next = jnp.sum(keep)
    overflow = overflow + lax.psum(
        jnp.maximum(n_seen_next.astype(jnp.int32) - seen_cap, 0), axis
    )
    kdest = jnp.where(keep, jnp.cumsum(keep) - 1, seen_cap)
    seen_next = tuple(
        jnp.full(seen_cap, sent, dtype=jnp.uint32)
        .at[kdest]
        .set(c, mode="drop")
        for c in scols
    )
    bind = {v: scols[k] for k, v in enumerate(var_names)}
    L = seen_cap + n_cand
    tag2 = stag

    # ---- negated premises from the binding owner (two-hop) ---------------
    me = lax.axis_index(axis).astype(jnp.int32)
    for neg in lr.negs:
        term_map = _pos2var(neg)
        qs, qp, qo = _instantiate(term_map, neg.consts, bind, L)
        rowid = jnp.arange(L, dtype=jnp.int32)
        origin = jnp.full(L, 0, jnp.int32) + me
        (rqs, rqp, rqo, rrow, rorig), rqv, d1 = exchange(
            (qs, qp, qo, rowid, origin),
            fire,
            shard_of_dev(qs, n),
            n,
            axis,
            bucket_cap,
        )
        overflow = overflow + d1.astype(jnp.int32)
        idx, found = _index3((rqs, rqp, rqo), rqv, fcols, fv, fact_cap)
        t = eff_f[jnp.clip(idx, 0, fact_cap - 1)]
        ntag = jnp.where(found, 1.0 - t, 1.0)  # addmult ⊖ = 1 − t
        (brow, bnt), bv, d2 = exchange(
            (rrow, ntag), rqv, rorig, n, axis, bucket_cap
        )
        overflow = overflow + d2.astype(jnp.int32)
        ntag_buf = (
            jnp.full(L, 1.0, jnp.float64)
            .at[jnp.where(bv, brow, L)]
            .set(bnt, mode="drop")
        )
        tag2 = tag2 * ntag_buf
    fire = fire & (tag2 > 0.0)  # zero-tag pruning

    parts = []
    for concl in lr.concls:
        cols = []
        for tkind, v in concl:
            if tkind == "const":
                cols.append(jnp.full(L, v, dtype=jnp.uint32))
            else:
                cols.append(bind[v])
        parts.append((cols[0], cols[1], cols[2], tag2, fire))

    out_state, new_count, ovf = _commit_candidates(
        parts,
        overflow,
        fs,
        fp,
        fo,
        ftag,
        fv,
        gs,
        gp,
        go,
        gtag_blk,
        gv,
        kind="addmult",
        n=n,
        axis=axis,
        fact_cap=fact_cap,
        delta_cap=delta_cap,
        bucket_cap=bucket_cap,
        fresh_delta_only=True,
    )
    return (
        out_state,
        new_count,
        ovf,
        tuple(s[None] for s in seen_next),
        n_seen_next.astype(jnp.int32)[None, None],
    )


def _compact(flags, mask, dest, cap):
    """Compact ``flags`` (u32 0/1) through the same scatter that built the
    next-delta columns, so row i of the delta carries its fresh/changed
    provenance."""
    return (
        jnp.zeros(cap, jnp.uint32)
        .at[dest]
        .set(jnp.where(mask, flags.astype(jnp.uint32), 0), mode="drop")
    )


# ---------------------------------------------------------------------------
# Host driver
# ---------------------------------------------------------------------------


class DistProvenanceReasoner:
    """Host driver for the distributed tagged fixpoint (see module doc).

    ``infer()`` runs the closure for an idempotent scalar semiring over the
    mesh, writes derived facts into ``reasoner.facts`` and final tags into
    ``tag_store`` (host-TagStore parity), and returns the derived count.
    Raises :class:`Unsupported` for NAF rules, unsupported semirings, or
    rule shapes the distributed planner cannot route.
    """

    def __init__(
        self,
        mesh: Mesh,
        reasoner,
        provenance,
        tag_store,
        fact_cap: Optional[int] = None,
        delta_cap: Optional[int] = None,
        join_cap: Optional[int] = None,
        bucket_cap: Optional[int] = None,
    ):
        if supports_idempotent(provenance):
            self.kind = "idem"
        elif getattr(provenance, "name", None) == "addmult":
            if _addmult_order_sensitive(
                [r for r in reasoner.rules if not r.negative_premise]
            ):
                # POSITIVE rules only: NAF rules never run inside the
                # round program (they dispatch sequentially in host order),
                # and NAF→premise feedback is gated by _naf_premise_drift
                raise Unsupported(
                    "addmult accumulation is rule-evaluation-order-dependent"
                    " for this rule set (a rule's conclusions feed a later"
                    " rule's premises): host semantics win"
                )
            self.kind = "addmult"
        else:
            raise Unsupported(
                f"semiring {provenance.name!r} has no distributed tag algebra"
            )
        # (round 5: stratified NAF over addmult runs on the mesh — per-rule
        # sequential dispatch with a binding-owner-routed seen relation
        # reproducing the host's exactly-once naf_seen accounting)
        self.mesh = mesh
        self.axis = mesh.axis_names[0]
        self.n = mesh.devices.size
        self.reasoner = reasoner
        self.provenance = provenance
        self.tag_store = tag_store
        self.rules, self.bank = lower_rules_dist(reasoner, reasoner.rules)
        # ground-guard satisfaction at driver time (facts are real here;
        # guards are non-derivable, so absence is final for this closure)
        self.rules = tuple(
            (lr, pl)
            for lr, pl in self.rules
            if all(reasoner.facts.contains(*g.consts) for g in lr.guards)
        )
        self.pos_rules = tuple(
            (lr, pl) for lr, pl in self.rules if not lr.negs
        )
        self.naf_rules = tuple((lr, pl) for lr, pl in self.rules if lr.negs)
        if self.naf_rules and _naf_self_blocking(
            [lr for lr, _ in self.naf_rules]
        ):
            raise Unsupported(
                "a NAF conclusion unifies with the SAME rule's negated"
                " premise: the host's per-row sequential commits are"
                " load-bearing"
            )
        # CROSS-rule blocking runs SEQUENTIALLY (one rule per mesh
        # dispatch, host rule order) instead of gating — round-5 parity
        # with the single-chip driver; addmult NAF is ALWAYS sequential
        # (its per-rule seen relations need the partition anyway)
        self.naf_sequential = bool(self.naf_rules) and (
            self.kind == "addmult"
            or _naf_cross_blocking([lr for lr, _ in self.naf_rules])
        )
        if self.naf_rules and _naf_premise_drift(
            [lr for lr, _ in self.rules], [lr for lr, _ in self.naf_rules]
        ):
            raise Unsupported(
                "a NAF body reads derived predicates: the host's"
                " exactly-once naf_seen tag freezing is load-bearing"
            )
        self.neg_kind = (
            "expiration"
            if getattr(provenance, "name", None) == "expiration"
            else "complement"
        )
        n_local = max(1, -(-len(reasoner.facts) // self.n))
        self.fact_cap = fact_cap or round_cap(8 * n_local, 512)
        self.delta_cap = delta_cap or round_cap(4 * n_local, 256)
        self.join_cap = join_cap or round_cap(4 * n_local, 256)
        self.bucket_cap = bucket_cap or round_cap(4 * n_local, 256)
        # per-rule NAF seen-relation capacity (addmult exactly-once)
        self.seen_cap = round_cap(4 * n_local, 256)

    def _round_fn(self):
        return self._pass_fn_for(
            "round",
            None,
            self.fact_cap,
            self.delta_cap,
            self.join_cap,
            self.bucket_cap,
        )

    def _naf_fn(self, rule_idx=None):
        """NAF pass program; ``rule_idx`` selects one rule (sequential
        cross-blocking dispatch), None compiles all NAF rules into one."""
        return self._pass_fn_for(
            "naf",
            rule_idx,
            self.fact_cap,
            self.delta_cap,
            self.join_cap,
            self.bucket_cap,
        )

    @lru_cache(maxsize=32)  # keyed per capacity attempt and per NAF rule
    def _pass_fn_for(self, tag, rule_idx, fact_cap, delta_cap, join_cap, bucket_cap):
        if tag == "round":
            body = partial(
                _tagged_round,
                rules=self.pos_rules,
                n=self.n,
                axis=self.axis,
                fact_cap=fact_cap,
                delta_cap=delta_cap,
                join_cap=join_cap,
                bucket_cap=bucket_cap,
                kind=self.kind,
            )
        else:
            body = partial(
                _naf_pass,
                rules=(
                    self.naf_rules
                    if rule_idx is None
                    else (self.naf_rules[rule_idx],)
                ),
                neg_kind=self.neg_kind,
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
                lambda state, masks, one, gtags: body(
                    state, masks, one, gtags
                ),
                mesh=self.mesh,
                check_vma=_dist_check_vma(),
                in_specs=((spec,) * 15, (rep,) * n_masks, P(self.axis), rep),
                out_specs=((spec,) * 15, P(self.axis), P(self.axis)),
            )
        )

    @staticmethod
    def _rule_vars(lr) -> int:
        return len({v for prem in lr.premises for v, _pos in prem.vars})

    def _naf_addmult_fn(self, rule_idx):
        return self._naf_addmult_fn_for(
            rule_idx,
            self.fact_cap,
            self.delta_cap,
            self.join_cap,
            self.bucket_cap,
            self.seen_cap,
        )

    @lru_cache(maxsize=32)  # keyed per capacity attempt and per NAF rule
    def _naf_addmult_fn_for(
        self, rule_idx, fact_cap, delta_cap, join_cap, bucket_cap, seen_cap
    ):
        """Wrap :func:`_naf_pass_addmult` for one rule: the state specs
        plus this rule's seen-relation columns (one per rule variable)."""
        rule = self.naf_rules[rule_idx]
        k = self._rule_vars(rule[0])
        spec = P(self.axis, None)
        rep = P()
        n_masks = len(self.bank.exprs)
        body = partial(
            _naf_pass_addmult,
            rule=rule,
            n=self.n,
            axis=self.axis,
            fact_cap=fact_cap,
            delta_cap=delta_cap,
            join_cap=join_cap,
            bucket_cap=bucket_cap,
            seen_cap=seen_cap,
        )
        return jax.jit(
            jax.shard_map(
                lambda state, seen, n_seen, masks, one, gtag: body(
                    state, seen, n_seen, masks, one, gtag
                ),
                mesh=self.mesh,
                check_vma=_dist_check_vma(),
                in_specs=(
                    (spec,) * 15,
                    (spec,) * k,
                    spec,
                    (rep,) * n_masks,
                    P(self.axis),
                    rep,
                ),
                out_specs=(
                    (spec,) * 15,
                    P(self.axis),
                    P(self.axis),
                    (spec,) * k,
                    spec,
                ),
            )
        )

    def infer(self, max_rounds: int = 256, max_attempts: int = 8) -> int:
        r = self.reasoner
        s, p, o = r.facts.columns()
        n0 = len(s)
        if n0 == 0 or not self.rules:
            return 0
        tags0, one_enc = _seed_tag_arrays(
            self.provenance,
            self.tag_store,
            list(zip(s.tolist(), p.tolist(), o.tolist())),
        )
        for _attempt in range(max_attempts):
            result = self._try_infer(s, p, o, tags0, one_enc, max_rounds)
            if result is not None:
                return self._write_back(s, p, o, tags0, *result)
            self.fact_cap *= 2
            self.seen_cap *= 2
            self.delta_cap *= 2
            self.join_cap *= 2
            self.bucket_cap *= 2
        raise RuntimeError(
            "distributed tagged fixpoint capacities failed to converge"
        )

    def _try_infer(self, s, p, o, tags0, one_enc, max_rounds):
        n = self.n
        sh = NamedSharding(self.mesh, P(self.axis, None))
        with jax.enable_x64(True):
            try:
                (ss, sp, so, stg), sv = partition_rows(
                    (s, p, o, tags0), s, n, self.fact_cap
                )
                (os_, op, oo, otg), ov = partition_rows(
                    (s, p, o, tags0), o, n, self.fact_cap
                )
            except ValueError:
                # a shard's initial load exceeds fact_cap: let infer()'s
                # doubling protocol retry, like every other capacity
                return None
            # delta = all facts (subject-partitioned), EFFECTIVE tags
            eff = np.where(np.isnan(stg), one_enc, stg)
            if self.delta_cap < self.fact_cap:
                per_shard = sv.sum(axis=1)
                if int(per_shard.max(initial=0)) > self.delta_cap:
                    return None
                dsl = np.zeros((n, self.delta_cap), np.uint32)
                dpl = np.zeros((n, self.delta_cap), np.uint32)
                dol = np.zeros((n, self.delta_cap), np.uint32)
                dtl = np.zeros((n, self.delta_cap), np.float64)
                dvl = np.zeros((n, self.delta_cap), bool)
                w = self.delta_cap
                dsl[:, :w] = ss[:, :w]
                dpl[:, :w] = sp[:, :w]
                dol[:, :w] = so[:, :w]
                dtl[:, :w] = eff[:, :w]
                dvl[:, :w] = sv[:, :w]
            else:
                pad = self.delta_cap - self.fact_cap
                padw = lambda a, fill, dt: np.concatenate(  # noqa: E731
                    [a, np.full((n, pad), fill, dt)], axis=1
                )
                dsl = padw(ss, 0, np.uint32)
                dpl = padw(sp, 0, np.uint32)
                dol = padw(so, 0, np.uint32)
                dtl = padw(eff, 0.0, np.float64)
                dvl = padw(sv, False, bool)

            put = lambda a: jax.device_put(a, sh)  # noqa: E731
            state = tuple(
                put(a)
                for a in (
                    ss,
                    sp,
                    so,
                    stg,
                    sv,
                    os_,
                    op,
                    oo,
                    otg,
                    ov,
                    dsl,
                    dpl,
                    dol,
                    dtl,
                    dvl,
                )
            )
            masks = tuple(jnp.asarray(m) for m in self.bank.materialize())
            one_arr = put(np.full((n, 1), one_enc, np.float64))
            round_fn = self._round_fn() if self.pos_rules else None
            if not self.naf_rules:
                naf_fns = None
            elif self.kind == "addmult":
                # one mesh program per rule, each threading its own seen
                # relation (exactly-once accounting across passes)
                naf_fns = [
                    self._naf_addmult_fn(i)
                    for i in range(len(self.naf_rules))
                ]
            elif self.naf_sequential:
                # cross-blocking: one mesh program per rule, dispatched in
                # host rule order so earlier rules' commits are visible
                naf_fns = [
                    self._naf_fn(rule_idx=i)
                    for i in range(len(self.naf_rules))
                ]
            else:
                naf_fns = [self._naf_fn()]
            if self.kind == "addmult" and self.naf_rules:
                seen_state = [
                    (
                        tuple(
                            put(
                                np.full(
                                    (n, self.seen_cap),
                                    0xFFFFFFFF,
                                    np.uint32,
                                )
                            )
                            for _ in range(self._rule_vars(lr))
                        ),
                        put(np.zeros((n, 1), np.int32)),
                    )
                    for lr, _pl in self.naf_rules
                ]
            gt_pos = jnp.asarray(
                _guard_tag_array(
                    [lr for lr, _ in self.pos_rules],
                    self.provenance,
                    self.tag_store,
                )
            )
            gt_naf = jnp.asarray(
                _guard_tag_array(
                    [lr for lr, _ in self.naf_rules],
                    self.provenance,
                    self.tag_store,
                )
            )

            def extract(state):
                fs = np.asarray(state[0]).reshape(-1)
                fp = np.asarray(state[1]).reshape(-1)
                fo = np.asarray(state[2]).reshape(-1)
                ft = np.asarray(state[3]).reshape(-1)
                fv = np.asarray(state[4]).reshape(-1)
                return fs[fv], fp[fv], fo[fv], ft[fv]

            quiesced = round_fn is None  # no positive stratum to drain
            for _ in range(max_rounds):
                if not quiesced:
                    state, count, overflow = round_fn(
                        state, masks, one_arr, gt_pos
                    )
                    if int(overflow[0]) > 0:
                        return None
                    if int(count[0]) > 0:
                        continue
                    quiesced = True
                # positive stratum drained: fire one NAF pass (host
                # stratified-loop parity); its delta re-enters the
                # positive stratum
                if naf_fns is None:
                    return extract(state)
                if not self.naf_sequential:
                    state, count, overflow = naf_fns[0](
                        state, masks, one_arr, gt_naf
                    )
                    if int(overflow[0]) > 0:
                        return None
                    if int(count[0]) == 0:
                        return extract(state)
                else:
                    # sequential pass: per-shard fact counts BEFORE, one
                    # dispatch per rule, then the pass delta = exactly the
                    # rows each shard appended during the pass, read back
                    # WITH their final tags (a later rule may have
                    # ⊕-improved an earlier rule's fresh fact — the host
                    # reads the tag store live, and so must the re-run).
                    # The readback is O(fact block) per PASS, not per rule
                    # — passes are few (stratified quiescence) and the
                    # sync-per-dispatch driver already reads counts; a
                    # device-side slice extraction would save bandwidth if
                    # NAF-heavy workloads ever show up in profiles
                    n_before = np.asarray(state[4]).sum(axis=1)
                    for i, fn in enumerate(naf_fns):
                        if self.kind == "addmult":
                            cols, cnt = seen_state[i]
                            (
                                state,
                                count,
                                overflow,
                                cols2,
                                cnt2,
                            ) = fn(
                                state,
                                cols,
                                cnt,
                                masks,
                                one_arr,
                                gt_naf[i : i + 1],
                            )
                            seen_state[i] = (cols2, cnt2)
                        else:
                            state, count, overflow = fn(
                                state, masks, one_arr, gt_naf[i : i + 1]
                            )
                        if int(overflow[0]) > 0:
                            return None
                    fs_h = np.asarray(state[0])
                    fp_h = np.asarray(state[1])
                    fo_h = np.asarray(state[2])
                    ft_h = np.asarray(state[3])
                    n_after = np.asarray(state[4]).sum(axis=1)
                    per_shard = (n_after - n_before).astype(np.int64)
                    if int(per_shard.sum()) == 0:
                        return extract(state)
                    if int(per_shard.max()) > self.delta_cap:
                        return None  # retry at doubled caps
                    dsl = np.zeros((n, self.delta_cap), np.uint32)
                    dpl = np.zeros((n, self.delta_cap), np.uint32)
                    dol = np.zeros((n, self.delta_cap), np.uint32)
                    dtl = np.zeros((n, self.delta_cap), np.float64)
                    dvl = np.zeros((n, self.delta_cap), bool)
                    for si in range(n):
                        b, a = int(n_before[si]), int(n_after[si])
                        m = a - b
                        if m == 0:
                            continue
                        dsl[si, :m] = fs_h[si, b:a]
                        dpl[si, :m] = fp_h[si, b:a]
                        dol[si, :m] = fo_h[si, b:a]
                        t = ft_h[si, b:a]
                        dtl[si, :m] = np.where(np.isnan(t), one_enc, t)
                        dvl[si, :m] = True
                    state = (
                        *state[:10],
                        put(dsl),
                        put(dpl),
                        put(dol),
                        put(dtl),
                        put(dvl),
                    )
                quiesced = round_fn is None
            raise RuntimeError(
                "distributed tagged fixpoint hit the round limit"
            )

    def _write_back(self, s, p, o, tags0, fs, fp, fo, ft):
        """Append derived facts; store changed-or-new tag entries
        (vectorized, host-TagStore parity)."""
        prov = self.provenance
        base = dict(
            zip(
                zip(s.tolist(), p.tolist(), o.tolist()),
                tags0.tolist(),
            )
        )
        keys = list(zip(fs.tolist(), fp.tolist(), fo.tolist()))
        new_rows = []
        entries = {}
        for k, v in zip(keys, ft.tolist()):
            v0 = base.get(k)
            if v0 is None:
                new_rows.append(k)
                if not np.isnan(v):
                    entries[k] = v
            else:
                if not np.isnan(v) and not (v == v0 or (np.isnan(v0) and np.isnan(v))):
                    entries[k] = v
        if new_rows:
            arr = np.asarray(sorted(new_rows), dtype=np.uint32)
            self.reasoner.facts.add_batch(arr[:, 0], arr[:, 1], arr[:, 2])
        if entries:
            ks = list(entries)
            decoded = _decode_tags(
                prov, np.asarray([entries[k] for k in ks])
            )
            self.tag_store.tags.update(zip(ks, decoded))
        return len(new_rows)
