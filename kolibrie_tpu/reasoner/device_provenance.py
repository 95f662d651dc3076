"""Device (TPU) provenance semi-naive fixpoint for the scalar semirings.

The host provenance loop (:mod:`kolibrie_tpu.reasoner.provenance_seminaive`)
runs per-derivation tag algebra in Python.  For the three IDEMPOTENT scalar
semirings — MinMax (fuzzy), Boolean, Expiration (the cross-window SDS+
workhorse) — the whole algebra collapses onto one device form: tags are an
f64 column, ⊗ (conjunction over a derivation's premises) is ``min`` and
⊕ (disjunction over derivations of the same fact) is ``max``:

- minmax:     tags in [0,1] verbatim,     zero 0.0, one 1.0
- boolean:    False/True → 0.0/1.0,       zero 0.0, one 1.0
- expiration: expiry timestamps → f64 (exact below 2^53; FOREVER → +inf),
              zero 0.0 (expired), one +inf (static)

Because ⊕ is idempotent, duplicate discoveries of the same derivation are
harmless — the per-seed delta expansion (every premise position seeded from
the delta, remaining positions joined against ALL facts) needs no old/delta
store split.

The NON-idempotent AddMult semiring (⊕ = noisy-OR a+b−ab, ⊗ = product)
runs a separate round program (:func:`_prov_round_addmult`) with
exactly-once derivation accounting: old/delta premise decomposition, the
delta carried as fact-row indices, and per-group ⊕ as a segment noisy-OR
in log space.  Only the structural semirings (SDD/TopK/DNF), whose tags
are pointer-shaped proof objects, stay host-side.

A round is one XLA program: delta-seeded premise joins with tag ``min``
carried through the join chain, filter masks, conclusion instantiation,
4-key sort so each (s,p,o) group's first row carries its ``max`` tag,
match-against-facts index lookup, fact append + in-place tag improvement,
and the next delta = new facts ∪ tag-improved facts.  The host drives
rounds (one scalar sync per round) and doubles capacities on overflow, the
same protocol as :meth:`DeviceFixpoint.infer_chunked`.

Parity: ``datalog/.../provenance_semi_naive.rs:26-34,134-197`` (delta
re-inclusion of improved tags, per-derivation ⊗, ⊕ merge, zero-pruning) —
redesigned as whole-column device programs.  Agreement with the host path
is tested in ``tests/test_device_provenance.py``.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional, Set, Tuple

import jax
import numpy as np

from kolibrie_tpu.ops import round_cap as _round_cap
from kolibrie_tpu.reasoner.device_fixpoint import (
    Unsupported,
    _Caps,
    _eval_filters,
    _pack,
    _scan_premise,
    lower_rules,
)
__all__ = ["supports", "infer_provenance_device", "AUTO_MIN_FACTS"]

# below this many facts the host loop wins (device dispatch + compile cost)
AUTO_MIN_FACTS = 20_000

_IDEMPOTENT = ("minmax", "boolean", "expiration")

# addmult (noisy-OR/product) is NON-idempotent: it runs a separate round
# program with exactly-once derivation accounting (see _prov_round_addmult)
_DEVICE_SEMIRINGS = _IDEMPOTENT + ("addmult",)

_EXP_FOREVER = 0xFFFF_FFFF_FFFF_FFFF

# host TagStore parity: AddMultProbability.tag_eq treats |Δ| < 1e-12 as
# "unchanged", which is also what terminates cyclic noisy-OR fixpoints
_ADDMULT_TAG_EQ = 1e-12


def supports(provenance) -> bool:
    return getattr(provenance, "name", None) in _DEVICE_SEMIRINGS


def supports_idempotent(provenance) -> bool:
    """True only for the scalar-IDEMPOTENT semirings (min/max tag algebra).
    The distributed tagged round hardwires ⊗=min/⊕=max with no exactly-once
    accounting, so it must gate on THIS predicate, not :func:`supports`."""
    return getattr(provenance, "name", None) in _IDEMPOTENT


def _addmult_order_sensitive(rules) -> bool:
    """True when within-round tag updates could be VISIBLE to a later rule,
    making the non-idempotent fixpoint depend on rule evaluation order.

    The host loop (reference parity: ``provenance_semi_naive.rs:163-193``
    reads ``tag_store.get_tag`` live) lets rule j read a tag that rule i<j
    improved in the same round; the device round reads a round-start
    snapshot.  For idempotent ⊕ both converge to the same fixpoint; for
    addmult the accumulated noisy-OR values genuinely differ.  The device
    path therefore only takes rule sets where rule i's conclusion predicates
    never feed rule j>i's premises — then no mid-round improvement can be
    observed and snapshot ≡ live.  (A rule's OWN conclusions are safe: the
    host pre-aggregates per rule and writes after it.)  Variable predicates
    count as wildcards."""

    def preds(terms):
        out = set()
        for t in terms:
            p = t.predicate
            out.add(None if p.is_variable else int(p.value))
        return out

    for i, ri in enumerate(rules):
        concl = preds(ri.conclusion)
        for rj in rules[i + 1:]:
            prem = preds(rj.premise)
            if None in concl or None in prem or (concl & prem):
                return True
    return False


def _encode_tags(provenance, tags) -> np.ndarray:
    name = provenance.name
    if name == "boolean":
        return np.asarray([1.0 if t else 0.0 for t in tags], dtype=np.float64)
    if name == "expiration":
        return np.asarray(
            [np.inf if t >= _EXP_FOREVER else float(t) for t in tags],
            dtype=np.float64,
        )
    return np.asarray(tags, dtype=np.float64)


def _decode_tags(provenance, vals: np.ndarray) -> list:
    """Vectorized inverse of :func:`_encode_tags` (shared by the single-chip
    and distributed write-backs)."""
    name = provenance.name
    if name == "boolean":
        return (vals > 0.5).tolist()
    if name == "expiration":
        return [
            _EXP_FOREVER if np.isinf(v) else int(round(v))
            for v in vals.tolist()
        ]
    return vals.tolist()


def _seed_tag_arrays(provenance, tag_store, keys) -> Tuple[np.ndarray, float]:
    """(tags0, one_enc) for a fact-key list: NaN = "no explicit TagStore
    entry" (premise reads see one(); the first derivation overwrites —
    update_disjunction parity).  Shared by both device drivers."""
    tget = tag_store.tags.get  # keys are plain (s, p, o) tuples
    host_tags = [tget(k) for k in keys]
    one = provenance.one()
    tags0 = np.where(
        [t is None for t in host_tags],
        np.nan,
        _encode_tags(
            provenance, [one if t is None else t for t in host_tags]
        ),
    )
    return tags0, float(_encode_tags(provenance, [one])[0])


def _guard_tag_array(rules, provenance, tag_store) -> np.ndarray:
    """Per-rule encoded ⊗ of the rule's ground-guard tags (one() when the
    rule has no guards).  Guards are non-derivable by construction
    (lower_rules), so these values are CONSTANT through the closure —
    one dynamic operand, no recompile per tag value."""
    out = []
    for r in rules:
        t = provenance.one()
        for g in r.guards:
            gt = tag_store.tags.get(tuple(g.consts))
            if gt is not None:
                t = provenance.conjunction(t, gt)
        out.append(t)
    return _encode_tags(provenance, out) if out else np.zeros(0, np.float64)


# ---------------------------------------------------------------------------
# Jitted round
# ---------------------------------------------------------------------------


def _join_keys(table, ptable, kv, valid, pm):
    """Packed u64 join keys for a premise-join step: 1-2 shared variables
    pack exactly; 3+ ride the union dense-rank composition (the same
    ``pack_key_multi`` path as the untagged fixpoint — a plain ``_pack``
    would silently drop the third key column)."""
    from kolibrie_tpu.ops.device_join import _LPAD, _RPAD, pack_key_multi

    if len(kv) > 2:
        return pack_key_multi(
            [table[v] for v in kv], [ptable[v] for v in kv], valid, pm
        )
    return (
        _pack([table[v] for v in kv], valid, _LPAD),
        _pack([ptable[v] for v in kv], pm, _RPAD),
    )


@partial(jax.jit, static_argnames=("rules", "caps"))
def _prov_round(
    rules: tuple,
    caps: _Caps,
    fs,
    fp,
    fo,
    ftag,
    n_facts,
    ds,
    dp,
    do,
    dtag,
    n_delta,
    one_enc,
    masks,
    gtags,
):
    """One tagged semi-naive round.  Returns the updated fact columns/tags,
    the next delta (new ∪ changed facts, with their stored tags), the count
    of delta entries, and an overflow bitmask (bit0 join, bit1 delta cap,
    bit2 fact cap).  An overflowing round does not commit.

    Tag-store parity: ``ftag`` mirrors the host TagStore exactly — NaN
    means "no explicit entry" (premise reads see ``one_enc``), and a fact's
    FIRST derivation overwrites (``update_disjunction`` inserts the new tag
    when no entry exists, tag_store.py:47-49) while later derivations
    ⊕-merge with ``max``.  Delta tags (``dtag``) are effective values,
    never NaN."""
    import jax.numpy as jnp
    from jax import lax

    from kolibrie_tpu.ops.device_join import _LPAD, _RPAD, join_indices, pack2

    F, D, J = caps.fact, caps.delta, caps.join
    fvalid = jnp.arange(F, dtype=jnp.int32) < n_facts
    dvalid = jnp.arange(ds.shape[0], dtype=jnp.int32) < n_delta
    fcols = (fs, fp, fo)
    dcols = (ds, dp, do)

    overflow = np.int32(0)
    parts: List[tuple] = []  # (s, p, o, tag, valid) static-cap blocks
    for r_idx, rule in enumerate(rules):
        for order, keys in rule.plans:
            seed = order[0]
            table, m = _scan_premise(rule.premises[seed], dcols, dvalid)
            valid = m
            # statically-satisfied ground guards contribute their (closure-
            # constant) tags to every derivation's ⊗ — one() when no guards
            tag = jnp.minimum(dtag, gtags[r_idx])
            for step, j in enumerate(order[1:]):
                ptable, pm = _scan_premise(rule.premises[j], fcols, fvalid)
                kv = keys[step]
                lkey, rkey = _join_keys(table, ptable, kv, valid, pm)
                li, ri, jvalid, total = join_indices(lkey, rkey, J)
                overflow = overflow | jnp.where(total > J, np.int32(1), 0)
                new_table = {}
                for v, c in table.items():
                    new_table[v] = c[li]
                for v, c in ptable.items():
                    if v not in new_table:
                        new_table[v] = c[ri]
                # ⊗ = min: a derivation is as strong as its weakest premise;
                # an absent (NaN) entry reads as one() for premises
                ptag = ftag[ri]
                ptag = jnp.where(jnp.isnan(ptag), one_enc, ptag)
                tag = jnp.minimum(tag[li], ptag)
                table, valid = new_table, jvalid
            valid = _eval_filters(rule, table, valid, masks)
            # zero-tag pruning (provenance_semi_naive.rs:171)
            valid = valid & (tag > 0.0)
            n = valid.shape[0]
            for concl in rule.concls:
                out = []
                for kind, v in concl:
                    if kind == "var":
                        out.append(table[v])
                    else:
                        out.append(jnp.full(n, v, dtype=jnp.uint32))
                parts.append((out[0], out[1], out[2], tag, valid))

    return _commit_parts(
        parts, caps, fs, fp, fo, ftag, n_facts, ds, dp, do, dtag, overflow
    )


def _fact_lookup(qs, qp, qo, qvalid, fs, fp, fo, fvalid, F):
    """Exact ground (s,p,o) → fact-row lookup: dense-rank the (s,p) pair
    over the union, pack with o, binary-search the sorted fact keys.
    Returns ``(found, fidx)`` with ``fidx == F`` for misses.  Relies on
    dictionary IDs never reaching 0xFFFFFFFF (bits 0..30 + quoted bit 31,
    asserted in core.dictionary)."""
    import jax.numpy as jnp

    from kolibrie_tpu.ops.device_join import pack2

    sent = np.uint32(0xFFFFFFFF)
    fsp = pack2(jnp.where(fvalid, fs, sent), jnp.where(fvalid, fp, sent))
    usp = pack2(jnp.where(qvalid, qs, sent), jnp.where(qvalid, qp, sent))
    union = jnp.sort(jnp.concatenate([fsp, usp]))
    rank_f = jnp.searchsorted(union, fsp).astype(jnp.uint32)
    rank_u = jnp.searchsorted(union, usp).astype(jnp.uint32)
    fkey = pack2(rank_f, jnp.where(fvalid, fo, sent))
    ukey = pack2(rank_u, jnp.where(qvalid, qo, sent))
    forder = jnp.argsort(fkey)
    fsorted = fkey[forder]
    pos = jnp.clip(jnp.searchsorted(fsorted, ukey), 0, F - 1)
    found = qvalid & (fsorted[pos] == ukey)
    fidx = jnp.where(found, forder[pos], F)
    return found, fidx


def _commit_parts(
    parts,
    caps,
    fs,
    fp,
    fo,
    ftag,
    n_facts,
    ds,
    dp,
    do,
    dtag,
    overflow,
    fresh_delta_only=False,
):
    """Shared commit tail of the idempotent round programs: dedup candidate
    conclusions by (s,p,o) keeping each group's ⊕-max tag, look them up
    against the fact columns, append new facts / improve tags in place, and
    emit the next delta (new ∪ changed facts — or new ONLY under
    ``fresh_delta_only``, the NAF-pass contract: the host stratified loop
    feeds just ``naf_new`` KEYS back into the positive stratum, so a
    tag-improved existing fact must NOT re-fire it)."""
    import jax.numpy as jnp
    from jax import lax

    F, D = caps.fact, caps.delta
    fvalid = jnp.arange(F, dtype=jnp.int32) < n_facts

    cs = jnp.concatenate([p[0] for p in parts])
    cp = jnp.concatenate([p[1] for p in parts])
    co = jnp.concatenate([p[2] for p in parts])
    ctag = jnp.concatenate([p[3] for p in parts])
    cv = jnp.concatenate([p[4] for p in parts])

    # group candidates by (s,p,o), each group's FIRST row carrying its max
    # tag: 4-key sort with -tag as the tie-breaking key (⊕ = max)
    sent = np.uint32(0xFFFFFFFF)
    ss = jnp.where(cv, cs, sent)
    sp = jnp.where(cv, cp, sent)
    so = jnp.where(cv, co, sent)
    stag = jnp.where(cv, ctag, 0.0)
    ss, sp, so, negtag = lax.sort((ss, sp, so, -stag), num_keys=4)
    utag = -negtag
    isnew = jnp.concatenate(
        [
            jnp.ones(1, bool),
            (ss[1:] != ss[:-1]) | (sp[1:] != sp[:-1]) | (so[1:] != so[:-1]),
        ]
    )
    isnew = isnew & (ss != sent)
    n_uniq = jnp.sum(isnew)
    overflow = overflow | jnp.where(n_uniq > D, np.int32(2), 0)
    dest = jnp.where(isnew, jnp.cumsum(isnew) - 1, D)
    us = jnp.zeros(D, jnp.uint32).at[dest].set(ss, mode="drop")
    up = jnp.zeros(D, jnp.uint32).at[dest].set(sp, mode="drop")
    uo = jnp.zeros(D, jnp.uint32).at[dest].set(so, mode="drop")
    ut = jnp.zeros(D, jnp.float64).at[dest].set(utag, mode="drop")
    uvalid = jnp.arange(D) < n_uniq

    found, fidx = _fact_lookup(us, up, uo, uvalid, fs, fp, fo, fvalid, F)

    old_tag = ftag[jnp.clip(fidx, 0, F - 1)]
    # update_disjunction parity: no entry (NaN) → first derivation
    # OVERWRITES; an existing entry ⊕-merges (max), changed iff it grew
    absent = found & jnp.isnan(old_tag)
    improved = found & (ut > old_tag)  # NaN compares False
    changed = absent | improved
    fresh = uvalid & ~found

    # append new facts (tags included)
    n_new = jnp.sum(fresh)
    n_facts_next = n_facts + n_new
    overflow = overflow | jnp.where(n_facts_next > F, np.int32(4), 0)
    adest = jnp.where(fresh, n_facts + jnp.cumsum(fresh) - 1, F)
    nfs = fs.at[adest].set(us, mode="drop")
    nfp = fp.at[adest].set(up, mode="drop")
    nfo = fo.at[adest].set(uo, mode="drop")
    nftag = ftag.at[adest].set(ut, mode="drop")
    # in-place store for changed facts: overwrite when absent, else the
    # grown max (ut > old ⇒ max(old, ut) = ut in both cases)
    nftag = nftag.at[jnp.where(changed, fidx, F)].set(ut, mode="drop")

    # next delta = new ∪ changed facts, with their stored tags (NAF pass:
    # new facts only — host `naf_new` parity)
    dmask = fresh if fresh_delta_only else (fresh | changed)
    n_dnext = jnp.sum(dmask)
    ddest = jnp.where(dmask, jnp.cumsum(dmask) - 1, D)
    nds = jnp.zeros(D, jnp.uint32).at[ddest].set(us, mode="drop")
    ndp = jnp.zeros(D, jnp.uint32).at[ddest].set(up, mode="drop")
    ndo = jnp.zeros(D, jnp.uint32).at[ddest].set(uo, mode="drop")
    ndt = jnp.zeros(D, jnp.float64).at[ddest].set(ut, mode="drop")

    ok = overflow == 0

    def sel(new, old):
        return jnp.where(ok, new, old)

    # delta buffers are driver-padded to exactly D, so shapes line up
    return (
        sel(nfs, fs),
        sel(nfp, fp),
        sel(nfo, fo),
        sel(nftag, ftag),
        sel(n_facts_next, n_facts),
        sel(nds, ds),
        sel(ndp, dp),
        sel(ndo, do),
        sel(ndt, dtag),
        sel(n_dnext.astype(jnp.int32), np.int32(0)),
        overflow,
    )


# ---------------------------------------------------------------------------
# Stratified NAF pass (idempotent semirings only)
# ---------------------------------------------------------------------------


def _concl_unifies_neg(concl, neg) -> bool:
    """Conservative syntactic unification of a conclusion pattern with a
    negated premise — variables unify with anything."""
    return all(
        kind != "const" or c is None or c == v
        for (kind, v), c in zip(concl, neg.consts)
    )


def _naf_cross_blocking(naf_rules) -> bool:
    """True when some NAF rule's conclusion pattern could unify with some
    NAF rule's NEGATED premise (including its own): within one negative
    pass the host's sequential fact commits make the outcome order-
    dependent.  Since round 5 this routes to the SEQUENTIAL per-rule
    driver (host rule order reproduced dispatch-by-dispatch) instead of
    gating — only the within-rule case (:func:`_naf_self_blocking`)
    still falls back to host."""
    for ra in naf_rules:
        for concl in ra.concls:
            for rb in naf_rules:
                for neg in rb.negs:
                    if _concl_unifies_neg(concl, neg):
                        return True
    return False


def _naf_self_blocking(naf_rules) -> bool:
    """True when a NAF rule's conclusion unifies a negated premise OF THE
    SAME rule: the host commits that rule's derivations row by row, so an
    earlier row's conclusion can block a later row of the same evaluation
    — an order no snapshot pass or per-rule sequencing reproduces."""
    for r in naf_rules:
        for concl in r.concls:
            for neg in r.negs:
                if _concl_unifies_neg(concl, neg):
                    return True
    return False


def _naf_premise_drift(all_rules, naf_rules) -> bool:
    """True when a NAF pass's output can REACH a NAF rule's positive
    premise through the rule graph.  Then a premise tag read by a NAF body
    can improve BETWEEN passes, and the host's exactly-once ``naf_seen``
    skip (which freezes each derivation's first-read tags) becomes
    load-bearing — a snapshot recomputation would ⊕-merge the improved
    value.  NAF bodies over predicates that are derived but FINAL before
    the first pass (no feedback from NAF conclusions) are safe.

    Predicate-level reachability, conservative: variable predicates are
    wildcards; guard premises are excluded (non-derivable by
    construction)."""
    reach: Set[int] = set()  # predicate ids reachable from NAF conclusions
    wild = False  # a variable-predicate conclusion reaches everything

    def add_concls(r) -> bool:
        nonlocal wild
        changed = False
        for c in r.concls:
            kind, v = c[1]
            if kind == "const":
                if v not in reach:
                    reach.add(v)
                    changed = True
            elif not wild:
                wild = True
                changed = True
        return changed

    for nr in naf_rules:
        add_concls(nr)
    changed = True
    while changed:
        changed = False
        for r in all_rules:
            prem_preds = [p.consts[1] for p in r.premises]
            fires = wild or any(
                (pp is None and reach) or (pp in reach) for pp in prem_preds
            )
            if fires and add_concls(r):
                changed = True
    for nr in naf_rules:
        for p in nr.premises:
            pp = p.consts[1]
            if wild or (pp is None and reach) or pp in reach:
                return True
    return False


def _negate_enc(t, neg_kind, one_enc):
    """⊖ on the f64 tag encoding.  ``complement``: 1 − t (minmax fuzzy
    complement; boolean 0/1 flip).  ``expiration``: an expired premise
    (NEVER → 0.0) negates to FOREVER (+inf) and any live one to NEVER
    (provenance.rs negate parity)."""
    import jax.numpy as jnp

    if neg_kind == "expiration":
        return jnp.where(t == 0.0, jnp.float64(np.inf), jnp.float64(0.0))
    return 1.0 - t


@partial(jax.jit, static_argnames=("rules", "caps", "neg_kind"))
def _prov_naf_pass(
    rules: tuple,
    caps: _Caps,
    fs,
    fp,
    fo,
    ftag,
    n_facts,
    ds,
    dp,
    do,
    dtag,
    one_enc,
    masks,
    neg_kind,
    gtags,
):
    """One stratified NAF pass over the QUIESCED positive fixpoint: each
    NAF rule's positive body is evaluated against ALL facts (no delta
    decomposition — ⊕ is idempotent, so re-derivation is harmless), the
    per-row tag is the ⊗-chain of premise tags, and every negative premise
    contributes ``one()`` when its ground instantiation is absent from the
    facts and ``⊖tag`` when present (provenance_semi_naive.rs:235-389).
    Same state contract / return tuple as :func:`_prov_round`; the ``ds``
    inputs are the (drained) delta buffers, passed for the non-commit
    fallback and output shapes.

    Host-parity note: the host pass processes each derivation signature at
    most once across passes (``naf_seen``); this pass recomputes all
    derivations and ⊕-merges, which agrees because ⊕ is idempotent and a
    stratified program's premise tags are final when the stratum fires.
    Programs where one NAF rule's conclusion unifies with a NAF rule's
    negated premise are rejected at the driver (:func:`_naf_cross_blocking`)
    — there the host's sequential within-pass commits are load-bearing.
    """
    import jax.numpy as jnp

    from kolibrie_tpu.ops.device_join import join_indices

    F, D, J = caps.fact, caps.delta, caps.join
    fvalid = jnp.arange(F, dtype=jnp.int32) < n_facts
    fcols = (fs, fp, fo)
    eff = jnp.where(jnp.isnan(ftag), one_enc, ftag)

    overflow = np.int32(0)
    parts: List[tuple] = []
    for r_idx, rule in enumerate(rules):
        # one plan suffices: the body runs against the full fact store
        order, keys = rule.plans[0]
        table, valid = _scan_premise(rule.premises[order[0]], fcols, fvalid)
        tag = jnp.minimum(eff, gtags[r_idx])
        for step, j in enumerate(order[1:]):
            ptable, pm = _scan_premise(rule.premises[j], fcols, fvalid)
            kv = keys[step]
            lkey, rkey = _join_keys(table, ptable, kv, valid, pm)
            li, ri, jvalid, total = join_indices(lkey, rkey, J)
            overflow = overflow | jnp.where(total > J, np.int32(1), 0)
            new_table = {}
            for v, c in table.items():
                new_table[v] = c[li]
            for v, c in ptable.items():
                if v not in new_table:
                    new_table[v] = c[ri]
            tag = jnp.minimum(tag[li], eff[ri])
            table, valid = new_table, jvalid
        valid = _eval_filters(rule, table, valid, masks)
        n = valid.shape[0]
        for neg in rule.negs:
            # ground the negated pattern per derivation row: constants,
            # bound variables (lowering guarantees binding), repeats
            qcol: list = [None, None, None]
            for pos_i, c in enumerate(neg.consts):
                if c is not None:
                    qcol[pos_i] = jnp.full(n, c, dtype=jnp.uint32)
            for v, pos_i in neg.vars:
                qcol[pos_i] = table[v]
            for a, b in neg.eq_pairs:
                qcol[b] = qcol[a]
            found, fidx = _fact_lookup(
                qcol[0], qcol[1], qcol[2], valid, fs, fp, fo, fvalid, F
            )
            ntag = _negate_enc(
                eff[jnp.clip(fidx, 0, F - 1)], neg_kind, one_enc
            )
            tag = jnp.minimum(tag, jnp.where(found, ntag, one_enc))
        # zero-tag pruning (a certainly-blocked derivation adds nothing)
        valid = valid & (tag > 0.0)
        for concl in rule.concls:
            out = []
            for kind, v in concl:
                if kind == "var":
                    out.append(table[v])
                else:
                    out.append(jnp.full(n, v, dtype=jnp.uint32))
            parts.append((out[0], out[1], out[2], tag, valid))

    return _commit_parts(
        parts,
        caps,
        fs,
        fp,
        fo,
        ftag,
        n_facts,
        ds,
        dp,
        do,
        dtag,
        overflow,
        fresh_delta_only=True,
    )


# ---------------------------------------------------------------------------
# Non-idempotent round: AddMult (noisy-OR ⊕, product ⊗)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("rules", "caps"))
def _prov_round_addmult(
    rules: tuple,
    caps: _Caps,
    fs,
    fp,
    fo,
    ftag,
    n_facts,
    didx,
    n_delta,
    masks,
    gtags,
):
    """One EXACTLY-ONCE tagged semi-naive round for the addmult semiring.

    Non-idempotent ⊕ (a+b-ab) must see every derivation exactly once, so
    the round differs from the idempotent program in three ways:

    - **Decomposition** (host parity: ``eval_rule_body``'s old/delta split,
      ``provenance_semi_naive.rs:26-34``): for the plan seeded at premise
      position k, premise j < k scans OLD facts (facts minus delta), j > k
      scans ALL facts, so a derivation touching several delta facts is
      counted at exactly one seed position.
    - **Delta as fact-row indices** (``didx``): the delta is always a set of
      committed fact rows, so membership ("old" mask) is one scatter, and
      delta columns/tags are gathers — no separate delta buffers to keep
      consistent.
    - **⊕ within the round** is a segment noisy-OR in log space:
      group tag = 1 - ∏(1-pᵢ) = -expm1(Σ log1p(-pᵢ)) over the group's
      derivations (exactly ⊕ folded over the group, in any order).

    Merge with the stored tag matches ``TagStore.update_disjunction``:
    absent (NaN) → the group tag is inserted verbatim; saturated (≥ 1.0)
    short-circuits; otherwise new = old + g - old·g, and the fact re-enters
    the delta iff |new - old| ≥ 1e-12 (``AddMultProbability.tag_eq``) —
    the same cutoff that makes cyclic noisy-OR fixpoints terminate on the
    host.  Returns the same (state..., overflow) protocol as
    :func:`_prov_round`; an overflowing round does not commit.
    """
    import jax.numpy as jnp

    from kolibrie_tpu.ops.device_join import join_indices

    F, D, J = caps.fact, caps.delta, caps.join
    fvalid = jnp.arange(F, dtype=jnp.int32) < n_facts
    dvalid = jnp.arange(D, dtype=jnp.int32) < n_delta
    fcols = (fs, fp, fo)
    didx_c = jnp.clip(didx, 0, F - 1)
    dcols = tuple(c[didx_c] for c in fcols)
    dtag_eff = ftag[didx_c]
    dtag_eff = jnp.where(jnp.isnan(dtag_eff), 1.0, dtag_eff)  # one() = 1.0
    in_delta = (
        jnp.zeros(F, bool)
        .at[jnp.where(dvalid, didx_c, F)]
        .set(True, mode="drop")
    )
    old_valid = fvalid & ~in_delta

    overflow = np.int32(0)
    parts: List[tuple] = []  # (s, p, o, tag, valid) static-cap blocks
    for r_idx, rule in enumerate(rules):
        for order, keys in rule.plans:
            seed = order[0]
            table, m = _scan_premise(rule.premises[seed], dcols, dvalid)
            valid = m
            # statically-satisfied ground guards contribute their (closure-
            # constant) tags to every derivation's ⊗ — one() when no guards
            tag = dtag_eff * gtags[r_idx]
            for step, j in enumerate(order[1:]):
                pvalid = old_valid if j < seed else fvalid
                ptable, pm = _scan_premise(rule.premises[j], fcols, pvalid)
                kv = keys[step]
                lkey, rkey = _join_keys(table, ptable, kv, valid, pm)
                li, ri, jvalid, total = join_indices(lkey, rkey, J)
                overflow = overflow | jnp.where(total > J, np.int32(1), 0)
                new_table = {}
                for v, c in table.items():
                    new_table[v] = c[li]
                for v, c in ptable.items():
                    if v not in new_table:
                        new_table[v] = c[ri]
                # ⊗ = product; absent (NaN) entries read as one()
                ptag = ftag[ri]
                ptag = jnp.where(jnp.isnan(ptag), 1.0, ptag)
                tag = tag[li] * ptag
                table, valid = new_table, jvalid
            valid = _eval_filters(rule, table, valid, masks)
            # zero-tag pruning (provenance_semi_naive.rs:171)
            valid = valid & (tag > 0.0)
            n = valid.shape[0]
            for concl in rule.concls:
                out = []
                for kind, v in concl:
                    if kind == "var":
                        out.append(table[v])
                    else:
                        out.append(jnp.full(n, v, dtype=jnp.uint32))
                parts.append((out[0], out[1], out[2], tag, valid))

    (
        nfs,
        nfp,
        nfo,
        nftag,
        n_facts_next,
        ndidx,
        n_dnext,
        overflow,
    ) = _addmult_commit(parts, caps, fs, fp, fo, ftag, n_facts, overflow)
    ok = overflow == 0

    def sel(new, old):
        return jnp.where(ok, new, old)

    return (
        sel(nfs, fs),
        sel(nfp, fp),
        sel(nfo, fo),
        sel(nftag, ftag),
        sel(n_facts_next, n_facts),
        sel(ndidx, didx),
        sel(n_dnext.astype(jnp.int32), np.int32(0)),
        overflow,
    )


def _addmult_commit(
    parts, caps, fs, fp, fo, ftag, n_facts, overflow, fresh_delta_only=False
):
    """Shared commit tail of the addmult round AND NAF pass: group the
    candidate (s,p,o,tag,valid) blocks, ⊕ per group as a segment noisy-OR
    in log space (order-free — exactly ⊕ folded over the group), merge with
    stored tags (``TagStore.update_disjunction`` semantics incl. the 1e-12
    change cutoff), append fresh facts, and emit the next delta as fact-row
    indices.  ``fresh_delta_only`` (the NAF pass): the delta carries ONLY
    newly-appended facts — host parity with ``_negative_pass``, whose
    ``naf_new`` returns newly ADDED keys, so an improved pre-existing
    conclusion must NOT re-enter the positive stratum.  Traced inside the
    callers' jit."""
    import jax.numpy as jnp
    from jax import lax

    from kolibrie_tpu.ops.device_join import pack2

    F, D = caps.fact, caps.delta
    fvalid = jnp.arange(F, dtype=jnp.int32) < n_facts

    cs = jnp.concatenate([p[0] for p in parts])
    cp = jnp.concatenate([p[1] for p in parts])
    co = jnp.concatenate([p[2] for p in parts])
    ctag = jnp.concatenate([p[3] for p in parts])
    cv = jnp.concatenate([p[4] for p in parts])

    # group candidates by (s,p,o); ⊕ over each group = segment noisy-OR in
    # log space (order-free, unlike the idempotent max-tag sort trick)
    sent = np.uint32(0xFFFFFFFF)
    ss = jnp.where(cv, cs, sent)
    sp = jnp.where(cv, cp, sent)
    so = jnp.where(cv, co, sent)
    stag = jnp.where(cv, jnp.clip(ctag, 0.0, 1.0), 0.0)
    ss, sp, so, stag = lax.sort((ss, sp, so, stag), num_keys=3)
    isnew = jnp.concatenate(
        [
            jnp.ones(1, bool),
            (ss[1:] != ss[:-1]) | (sp[1:] != sp[:-1]) | (so[1:] != so[:-1]),
        ]
    )
    isnew = isnew & (ss != sent)
    n_uniq = jnp.sum(isnew)
    overflow = overflow | jnp.where(n_uniq > D, np.int32(2), 0)
    seg = jnp.cumsum(isnew) - 1
    segdst = jnp.where(ss != sent, seg, D)
    # log1p(-p): p=1 → -inf → group tag exactly 1.0; p∈[0,1) stays finite
    logsum = (
        jnp.zeros(D, jnp.float64)
        .at[segdst]
        .add(jnp.log1p(-stag), mode="drop")
    )
    gtag = -jnp.expm1(logsum)  # 1 - ∏(1-pᵢ)
    dest = jnp.where(isnew, seg, D)
    us = jnp.zeros(D, jnp.uint32).at[dest].set(ss, mode="drop")
    up = jnp.zeros(D, jnp.uint32).at[dest].set(sp, mode="drop")
    uo = jnp.zeros(D, jnp.uint32).at[dest].set(so, mode="drop")
    uvalid = jnp.arange(D) < n_uniq

    # exact (s,p,o) → fact-index lookup (same machinery as _prov_round)
    fsp = pack2(jnp.where(fvalid, fs, sent), jnp.where(fvalid, fp, sent))
    usp = pack2(jnp.where(uvalid, us, sent), jnp.where(uvalid, up, sent))
    union = jnp.sort(jnp.concatenate([fsp, usp]))
    rank_f = jnp.searchsorted(union, fsp).astype(jnp.uint32)
    rank_u = jnp.searchsorted(union, usp).astype(jnp.uint32)
    fkey = pack2(rank_f, jnp.where(fvalid, fo, sent))
    ukey = pack2(rank_u, jnp.where(uvalid, uo, sent))
    forder = jnp.argsort(fkey)
    fsorted = fkey[forder]
    pos = jnp.clip(jnp.searchsorted(fsorted, ukey), 0, F - 1)
    found = uvalid & (fsorted[pos] == ukey)
    fidx = jnp.where(found, forder[pos], F)

    old_tag = ftag[jnp.clip(fidx, 0, F - 1)]
    absent = found & jnp.isnan(old_tag)
    saturated = found & (old_tag >= 1.0)  # NaN compares False
    new_tag = old_tag + gtag - old_tag * gtag
    improved = (
        found
        & ~absent
        & ~saturated
        & (jnp.abs(new_tag - old_tag) >= _ADDMULT_TAG_EQ)
    )
    changed = absent | improved
    merged = jnp.where(absent, gtag, new_tag)
    fresh = uvalid & ~found

    # append new facts (tags included)
    n_new = jnp.sum(fresh)
    n_facts_next = n_facts + n_new
    overflow = overflow | jnp.where(n_facts_next > F, np.int32(4), 0)
    adest = jnp.where(fresh, n_facts + jnp.cumsum(fresh) - 1, F)
    nfs = fs.at[adest].set(us, mode="drop")
    nfp = fp.at[adest].set(up, mode="drop")
    nfo = fo.at[adest].set(uo, mode="drop")
    nftag = ftag.at[adest].set(gtag, mode="drop")
    nftag = nftag.at[jnp.where(changed, fidx, F)].set(merged, mode="drop")

    # next delta = indices of new (∪ changed, unless fresh_delta_only) rows
    dmask = fresh if fresh_delta_only else (fresh | changed)
    row_idx = jnp.where(fresh, adest, fidx).astype(jnp.int32)
    n_dnext = jnp.sum(dmask)
    ddest = jnp.where(dmask, jnp.cumsum(dmask) - 1, D)
    ndidx = jnp.zeros(D, jnp.int32).at[ddest].set(row_idx, mode="drop")
    return nfs, nfp, nfo, nftag, n_facts_next, ndidx, n_dnext, overflow


# ---------------------------------------------------------------------------
# Non-idempotent stratified NAF pass: exactly-once via a device seen-set
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("rule", "caps", "scap"))
def _prov_naf_pass_addmult(
    rule,
    caps: _Caps,
    scap: int,
    fs,
    fp,
    fo,
    ftag,
    n_facts,
    seen_cols,
    n_seen,
    masks,
    gtag,
):
    """One NAF rule's stratified pass for the NON-idempotent addmult
    semiring, with the host's exactly-once derivation accounting
    (``naf_seen``, provenance_seminaive.py::_negative_pass) ON DEVICE.

    The host processes each derivation signature — (rule, variable
    bindings) — at most once across passes, because noisy-OR ⊕ would
    double-count re-derivations.  Here the signature set is a device-
    resident SEEN relation: ``seen_cols`` is one sorted u32 column per
    rule variable (lexicographic, capacity ``scap``), partitioned per rule
    by the driver.  The pass sorts [seen rows ∥ this pass's candidate
    rows] on the binding columns with a seen-first tie-break; a candidate
    fires iff it HEADS its equal-binding group (neither a seen row nor an
    earlier duplicate candidate precedes it), and the sorted union of
    distinct bindings is the next seen relation — dedup, membership, and
    maintenance in ONE multi-operand sort.

    One rule per dispatch: the driver sequences rules in host order, so a
    rule's committed facts are visible to later rules' body joins and
    negated-premise checks exactly like the host's within-pass sequential
    commits (this also serves the idempotent cross-blocking case).
    Self-interaction (a rule's conclusion unifying its OWN negated
    premise, or reaching its own positive premises) stays host-gated —
    there the host's per-ROW commit order is load-bearing.

    Same didx delta / overflow protocol as :func:`_prov_round_addmult`;
    overflow bit 8 = seen-set capacity.
    """
    import jax.numpy as jnp
    from jax import lax

    from kolibrie_tpu.ops.device_join import join_indices

    F, D, J = caps.fact, caps.delta, caps.join
    fvalid = jnp.arange(F, dtype=jnp.int32) < n_facts
    fcols = (fs, fp, fo)
    eff = jnp.where(jnp.isnan(ftag), 1.0, ftag)

    overflow = np.int32(0)
    # body vs ALL facts (host: eval_rule_body with delta=None)
    order, keys = rule.plans[0]
    table, valid = _scan_premise(rule.premises[order[0]], fcols, fvalid)
    tag = eff * gtag
    for step, j in enumerate(order[1:]):
        ptable, pm = _scan_premise(rule.premises[j], fcols, fvalid)
        kv = keys[step]
        lkey, rkey = _join_keys(table, ptable, kv, valid, pm)
        li, ri, jvalid, total = join_indices(lkey, rkey, J)
        overflow = overflow | jnp.where(total > J, np.int32(1), 0)
        new_table = {}
        for v, c in table.items():
            new_table[v] = c[li]
        for v, c in ptable.items():
            if v not in new_table:
                new_table[v] = c[ri]
        ptag = eff[ri]
        tag = tag[li] * ptag
        table, valid = new_table, jvalid
    valid = _eval_filters(rule, table, valid, masks)

    # ---- seen-set: dedup + membership + maintenance in one sort ----------
    var_names = tuple(sorted(table))  # host sig order: sorted(row.items())
    n_cand = valid.shape[0]
    sent = np.uint32(0xFFFFFFFF)
    seen_valid = jnp.arange(scap, dtype=jnp.int32) < n_seen
    ops = []
    for k, v in enumerate(var_names):
        cand = jnp.where(valid, table[v], sent)
        seen = jnp.where(seen_valid, seen_cols[k], sent)
        ops.append(jnp.concatenate([seen, cand]))
    # flag sorts seen (0) before equal-binding candidates (1)
    flag = jnp.concatenate(
        [
            jnp.zeros(scap, dtype=jnp.uint32),
            jnp.ones(n_cand, dtype=jnp.uint32),
        ]
    )
    payload_tag = jnp.concatenate([jnp.zeros(scap, jnp.float64), tag])
    sorted_all = lax.sort(
        (*ops, flag, payload_tag), num_keys=len(var_names) + 1
    )
    scols = sorted_all[: len(var_names)]
    sflag = sorted_all[len(var_names)]
    stag = sorted_all[len(var_names) + 1]
    live = scols[0] != sent  # all-sentinel rows (invalid) sort last
    head = jnp.concatenate(
        [
            jnp.ones(1, bool),
            jnp.any(
                jnp.stack([c[1:] != c[:-1] for c in scols]), axis=0
            ),
        ]
    )
    # a candidate FIRES iff it heads its equal-binding group: no seen row
    # (flag 0 sorts first) and no duplicate candidate precedes it
    fire = live & head & (sflag == 1)
    # next seen relation = the distinct bindings of the union
    keep = live & head
    n_seen_next = jnp.sum(keep)
    overflow = overflow | jnp.where(n_seen_next > scap, np.int32(8), 0)
    kdest = jnp.where(keep, jnp.cumsum(keep) - 1, scap)
    seen_next = tuple(
        jnp.full(scap, sent, dtype=jnp.uint32).at[kdest].set(c, mode="drop")
        for c in scols
    )

    # ---- negated premises over the firing rows ---------------------------
    bind = {v: scols[k] for k, v in enumerate(var_names)}
    n_all = scap + n_cand
    tag2 = stag
    for neg in rule.negs:
        qcol: list = [None, None, None]
        for pos_i, c in enumerate(neg.consts):
            if c is not None:
                qcol[pos_i] = jnp.full(n_all, c, dtype=jnp.uint32)
        for v, pos_i in neg.vars:
            qcol[pos_i] = bind[v]
        for a, b in neg.eq_pairs:
            qcol[b] = qcol[a]
        found, fidx = _fact_lookup(
            qcol[0], qcol[1], qcol[2], fire, fs, fp, fo, fvalid, F
        )
        ntag = 1.0 - eff[jnp.clip(fidx, 0, F - 1)]  # addmult ⊖ = 1 − t
        tag2 = tag2 * jnp.where(found, ntag, 1.0)
    fire = fire & (tag2 > 0.0)  # zero-tag pruning

    parts = []
    for concl in rule.concls:
        out = []
        for kind, v in concl:
            if kind == "var":
                out.append(bind[v])
            else:
                out.append(jnp.full(n_all, v, dtype=jnp.uint32))
        parts.append((out[0], out[1], out[2], tag2, fire))

    (
        nfs,
        nfp,
        nfo,
        nftag,
        n_facts_next,
        ndidx,
        n_dnext,
        overflow,
    ) = _addmult_commit(
        parts, caps, fs, fp, fo, ftag, n_facts, overflow,
        fresh_delta_only=True,
    )
    ok = overflow == 0

    def sel(new, old):
        return jnp.where(ok, new, old)

    return (
        sel(nfs, fs),
        sel(nfp, fp),
        sel(nfo, fo),
        sel(nftag, ftag),
        sel(n_facts_next, n_facts),
        ndidx,
        sel(n_dnext.astype(jnp.int32), np.int32(0)),
        tuple(sel(ns, os_) for ns, os_ in zip(seen_next, seen_cols)),
        sel(n_seen_next.astype(jnp.int32), n_seen),
        overflow,
    )


# ---------------------------------------------------------------------------
# Host driver + integration
# ---------------------------------------------------------------------------


def infer_provenance_device(
    reasoner,
    provenance,
    tag_store,
    initial_delta: Optional[Set[Tuple[int, int, int]]] = None,
    max_attempts: int = 32,
) -> Optional[Dict[Tuple[int, int, int], float]]:
    """Run the tagged fixpoint on device; returns None for host fallback.

    On success the derived facts are appended to ``reasoner.facts`` and
    ``tag_store`` holds the final tags (exactly like the host path).
    """
    if not supports(provenance):
        return None
    if provenance.name == "addmult" and _addmult_order_sensitive(
        [r for r in reasoner.rules if not r.negative_premise]
    ):
        # order-dependent accumulation WITHIN the positive round program:
        # host semantics win.  NAF rules are excluded — the stratified
        # driver dispatches them one at a time in host order, so cross-rule
        # visibility matches the host pass by construction.
        return None
    try:
        rules, bank = lower_rules(reasoner, reasoner.rules)
    except Unsupported:
        return None
    if not rules:
        return None
    # ground-guard satisfaction at DRIVER time (this driver always lowers
    # against the real facts, unlike DeviceR2R's per-window reuse — the
    # untagged rounds evaluate guards at run time instead): facts never
    # retract and guards are non-derivable, so an absent guard makes its
    # rule dead for this whole closure
    rules = tuple(
        r
        for r in rules
        if all(reasoner.facts.contains(*g.consts) for g in r.guards)
    )
    if not rules:
        return {}  # every rule statically dead: nothing to derive
    pos_rules = tuple(r for r in rules if not r.negs)
    naf_rules = tuple(r for r in rules if r.negs)
    if naf_rules and _naf_self_blocking(naf_rules):
        # a rule whose conclusion unifies its OWN negated premise: the
        # host's per-ROW sequential commits within that rule's evaluation
        # are load-bearing (row k can block row k+1 of the same rule) —
        # no snapshot or per-rule sequencing reproduces that order
        return None
    if naf_rules and _naf_premise_drift(rules, naf_rules):
        # a NAF body reading DERIVED predicates can see its premise tags
        # improve between passes; host freezes each derivation's first
        # read (naf_seen) — keep those programs host-side
        return None
    # CROSS-rule blocking (rule A's conclusion unifying rule B's negated
    # premise) no longer gates: the drivers dispatch NAF rules one at a
    # time in host order, so each rule's commits are visible to later
    # rules' body joins and negated-premise checks exactly like the host
    # pass's sequential commits (round 5; addmult is ALWAYS sequential —
    # its per-rule seen-sets need the partition anyway)
    naf_sequential = bool(naf_rules) and (
        provenance.name == "addmult" or _naf_cross_blocking(naf_rules)
    )

    import jax.numpy as jnp

    s, p, o = reasoner.facts.columns()
    n0 = len(s)
    if n0 == 0:
        return None
    facts_keys = list(zip(s.tolist(), p.tolist(), o.tolist()))
    tags0, one_enc = _seed_tag_arrays(provenance, tag_store, facts_keys)

    masks = tuple(jnp.asarray(m) for m in bank.materialize()) or (
        jnp.zeros(1, dtype=bool),
    )

    # delta tags are EFFECTIVE values (absent resolves to one())
    eff0 = np.where(np.isnan(tags0), one_enc, tags0)
    if initial_delta is not None:
        key_to_idx = {k: i for i, k in enumerate(facts_keys)}
        didx = np.asarray(
            sorted(key_to_idx[k] for k in initial_delta if k in key_to_idx),
            dtype=np.int32,
        )
        if didx.size == 0:
            return {}
    else:
        didx = np.arange(n0, dtype=np.int32)

    if provenance.name == "addmult":
        return _drive_addmult(
            reasoner,
            provenance,
            tag_store,
            pos_rules,
            naf_rules,
            masks,
            s,
            p,
            o,
            tags0,
            didx,
            n0,
            max_attempts,
        )

    d_s = s[didx]
    d_p = p[didx]
    d_o = o[didx]
    d_t = eff0[didx]
    nd0 = len(d_s)

    with jax.enable_x64(True):
        st = {
            "fs": _pad_u32(s, 0),
            "fp": _pad_u32(p, 0),
            "fo": _pad_u32(o, 0),
            "ftag": _pad_f64(tags0, 0),
            "n_facts": n0,
            "ds": _pad_u32(d_s, 0),
            "dp": _pad_u32(d_p, 0),
            "do": _pad_u32(d_o, 0),
            "dt": _pad_f64(d_t, 0),
            "n_delta": nd0,
        }

        gtags_pos = jnp.asarray(
            _guard_tag_array(pos_rules, provenance, tag_store)
        )

        def round_fn(caps, st):
            out = _prov_round(
                pos_rules,
                caps,
                st["fs"],
                st["fp"],
                st["fo"],
                st["ftag"],
                jnp.int32(st["n_facts"]),
                st["ds"],
                st["dp"],
                st["do"],
                st["dt"],
                jnp.int32(st["n_delta"]),
                jnp.float64(one_enc),
                masks,
                gtags_pos,
            )
            code = int(out[10])  # one sync per round
            if code != 0:
                return None, code
            return {
                "fs": out[0],
                "fp": out[1],
                "fo": out[2],
                "ftag": out[3],
                "n_facts": int(out[4]),
                "ds": out[5],
                "dp": out[6],
                "do": out[7],
                "dt": out[8],
                "n_delta": int(out[9]),
            }, 0

        def pad_delta(st, D):
            for k in ("ds", "dp", "do"):
                st[k] = _pad_u32(st[k], D)
            st["dt"] = _pad_f64(st["dt"], D)
            return st

        if pos_rules:
            st = _run_overflow_protocol(
                round_fn, st, n0, nd0, pad_delta, max_attempts
            )
        else:
            # no positive stratum: pad buffers (the protocol's job) and
            # treat the initial delta as drained — NAF evaluates vs ALL facts
            F = _round_cap(4 * n0, 2048)
            D = _round_cap(max(2 * nd0, n0 // 2, 1024))
            for k in ("fs", "fp", "fo"):
                st[k] = _pad_u32(st[k], F)
            st["ftag"] = _pad_f64(st["ftag"], F)
            st = pad_delta(st, D)
            st["n_delta"] = 0
        if st is not None and naf_rules:
            st = _drive_naf(
                naf_rules,
                st,
                round_fn if pos_rules else None,
                pad_delta,
                provenance,
                one_enc,
                masks,
                jnp.asarray(_guard_tag_array(naf_rules, provenance, tag_store)),
                n0,
                nd0,
                max_attempts,
                sequential=naf_sequential,
            )
        if st is None:
            return None  # graceful host fallback (reasoner state untouched)
        _write_back(
            reasoner,
            provenance,
            tag_store,
            st["fs"],
            st["fp"],
            st["fo"],
            st["ftag"],
            st["n_facts"],
            n0,
            tags0,
        )
    return {}


def _pad_u32(x, cap):
    import jax.numpy as jnp

    x = jnp.asarray(x, dtype=jnp.uint32)
    pad = max(cap - x.shape[0], 0)
    return jnp.concatenate([x, jnp.zeros(pad, dtype=jnp.uint32)])


def _pad_f64(x, cap):
    import jax.numpy as jnp

    x = jnp.asarray(x, dtype=jnp.float64)
    pad = max(cap - x.shape[0], 0)
    return jnp.concatenate([x, jnp.zeros(pad, dtype=jnp.float64)])


def _pad_i32(x, cap):
    import jax.numpy as jnp

    x = jnp.asarray(x, dtype=jnp.int32)
    pad = max(cap - x.shape[0], 0)
    return jnp.concatenate([x, jnp.zeros(pad, dtype=jnp.int32)])


def _run_overflow_protocol(round_fn, st, n0, nd0, pad_delta, max_attempts):
    """THE shared static-capacity fixpoint protocol (both round programs):
    run rounds until the delta drains; an overflowing round does NOT commit
    — the failing capacity doubles (bit0 join, bit1 delta, bit2 fact) and
    the round retries from the preserved state.

    ``round_fn(caps, st) -> (next_st | None, code)``; ``st`` holds fact
    buffers under keys fs/fp/fo/ftag (+ counts n_facts/n_delta), with the
    delta representation private to the caller (re-padded by ``pad_delta``).
    Returns the final state, or None after ``max_attempts`` overflows or
    10k rounds (graceful host fallback).
    """
    # never shrink below already-padded buffers: the stratified-NAF driver
    # re-enters this protocol after a pass that may have doubled capacities
    F = max(_round_cap(4 * n0, 2048), st["fs"].shape[0])
    D = _round_cap(max(2 * nd0, n0 // 2, 1024))
    # the delta representation is caller-private: idempotent rounds carry
    # value columns ("ds"), addmult carries fact-row indices ("didx")
    _dbuf = st.get("ds", st.get("didx"))
    if _dbuf is not None:
        D = max(D, _dbuf.shape[0])
    # start TIGHT: the candidate sort scales with J × plans, and the
    # overflow protocol doubles J cheaply when a round actually needs it
    J = _round_cap(max(nd0, 1024), 1024)
    for k in ("fs", "fp", "fo"):
        st[k] = _pad_u32(st[k], F)
    st["ftag"] = _pad_f64(st["ftag"], F)
    st = pad_delta(st, D)

    attempts = 0
    for _round in range(10_000):
        new_st, code = round_fn(_Caps(F, D, J), st)
        if code != 0:
            attempts += 1
            if attempts > max_attempts:
                return None
            if code & 1:
                J *= 2
            if code & 2:
                D *= 2
                st = pad_delta(st, D)
            if code & 4:
                F *= 2
                for k in ("fs", "fp", "fo"):
                    st[k] = _pad_u32(st[k], F)
                st["ftag"] = _pad_f64(st["ftag"], F)
            continue  # retry the round (it did not commit)
        st = new_st
        if st["n_delta"] == 0:
            return st
    return None  # round limit


def _drive_naf(
    naf_rules,
    st,
    round_fn,
    pad_delta,
    provenance,
    one_enc,
    masks,
    gtags,
    n0,
    nd0,
    max_attempts,
    sequential: bool = False,
):
    """Stratified-NAF driver (host loop parity, provenance_seminaive.py):
    alternate one device NAF pass with a positive fixpoint re-run seeded by
    the pass's delta, until a pass derives nothing new.  Shares the
    doubling overflow protocol; ``round_fn is None`` means the program has
    no positive stratum.

    ``sequential`` (cross-blocking rule sets): dispatch ONE rule at a
    time in host rule order — a rule's committed facts are then visible
    to later rules' negated-premise checks and body joins within the same
    pass, exactly like the host's sequential commits; the pass delta is
    the union of the per-rule deltas."""
    import jax.numpy as jnp

    neg_kind = "expiration" if provenance.name == "expiration" else "complement"
    F = st["fs"].shape[0]
    D = st["ds"].shape[0]
    # NAF bodies join over ALL facts, not a delta — start J at fact scale
    J = _round_cap(max(st["n_facts"], 1024), 1024)
    attempts = 0
    rule_groups = (
        [((r,), gtags[i : i + 1]) for i, r in enumerate(naf_rules)]
        if sequential
        else [(naf_rules, gtags)]
    )
    for _pass in range(10_000):
        pass_start = st["n_facts"]
        committed = [False] * len(rule_groups)
        while True:  # per-pass retry loop: only NOT-yet-committed groups
            failed = False
            for gi, (grules, ggtags) in enumerate(rule_groups):
                if committed[gi]:
                    # a group that committed before an overflow keeps its
                    # commit — its appended facts are recovered from the
                    # fact buffers at pass end, so nothing is lost
                    continue
                out = _prov_naf_pass(
                    grules,
                    _Caps(F, D, J),
                    st["fs"],
                    st["fp"],
                    st["fo"],
                    st["ftag"],
                    jnp.int32(st["n_facts"]),
                    st["ds"],
                    st["dp"],
                    st["do"],
                    st["dt"],
                    jnp.float64(one_enc),
                    masks,
                    neg_kind,
                    ggtags,
                )
                code = int(out[10])  # one sync per dispatch
                if code != 0:
                    attempts += 1
                    if attempts > max_attempts:
                        return None
                    if code & 1:
                        J *= 2
                    if code & 2:
                        D *= 2
                        st = pad_delta(st, D)
                    if code & 4:
                        F *= 2
                        for k in ("fs", "fp", "fo"):
                            st[k] = _pad_u32(st[k], F)
                        st["ftag"] = _pad_f64(st["ftag"], F)
                    failed = True
                    break  # retry the remaining groups at bigger caps
                st = {
                    "fs": out[0],
                    "fp": out[1],
                    "fo": out[2],
                    "ftag": out[3],
                    "n_facts": int(out[4]),
                    "ds": out[5],
                    "dp": out[6],
                    "do": out[7],
                    "dt": out[8],
                    "n_delta": int(out[9]),
                }
                if sequential:
                    committed[gi] = True
            if not failed:
                break
        if sequential:
            # the pass delta = EXACTLY the facts appended during the pass
            # (host naf_new), read back from the fact buffers WITH their
            # current tags — a later rule may have ⊕-improved an earlier
            # rule's fresh fact, and the positive re-run must see the
            # merged value (the host reads the tag store live)
            nd = st["n_facts"] - pass_start
            if nd > D:
                D = _round_cap(nd)
            if nd:
                sl = slice(pass_start, st["n_facts"])
                dt = np.asarray(st["ftag"][sl])
                st["ds"] = _pad_u32(np.asarray(st["fs"][sl]), D)
                st["dp"] = _pad_u32(np.asarray(st["fp"][sl]), D)
                st["do"] = _pad_u32(np.asarray(st["fo"][sl]), D)
                st["dt"] = _pad_f64(
                    np.where(np.isnan(dt), one_enc, dt), D
                )
            st["n_delta"] = int(nd)
        if st["n_delta"] == 0:
            return st
        # NAF-derived facts feed back into the positive stratum
        if round_fn is not None:
            st = _run_overflow_protocol(
                round_fn, st, n0, nd0, pad_delta, max_attempts
            )
            if st is None:
                return None
        else:
            st["n_delta"] = 0
        F = st["fs"].shape[0]
        D = st["ds"].shape[0]
    return None  # pass limit


def _drive_naf_addmult(
    naf_rules,
    st,
    round_fn,
    pad_delta,
    provenance,
    tag_store,
    masks,
    n0,
    max_attempts,
):
    """Stratified-NAF driver for the NON-idempotent addmult semiring:
    one rule per dispatch in host order (sequential commits visible to
    later rules), each rule carrying its own device-resident seen-set
    (exactly-once across passes), the pass's union delta re-seeding the
    positive protocol until a pass derives nothing new."""
    import jax.numpy as jnp

    F = st["fs"].shape[0]
    D = st["didx"].shape[0]
    # NAF bodies join over ALL facts, not a delta — start J at fact scale
    J = _round_cap(max(st["n_facts"], 1024), 1024)
    gtags = np.asarray(_guard_tag_array(naf_rules, provenance, tag_store))
    scaps = [
        _round_cap(max(2 * st["n_facts"], 1024)) for _ in naf_rules
    ]
    seen: List[Optional[tuple]] = [None] * len(naf_rules)
    attempts = 0
    for _pass in range(10_000):
        pass_start = st["n_facts"]
        committed = [False] * len(naf_rules)
        while True:  # per-pass retry loop: only NOT-yet-committed rules
            failed = False
            for gi, rule in enumerate(naf_rules):
                if committed[gi]:
                    continue
                nvars = len(
                    {v for prem in rule.premises for v, _pos in prem.vars}
                )
                if seen[gi] is None:
                    cols = tuple(
                        jnp.full(scaps[gi], 0xFFFFFFFF, dtype=jnp.uint32)
                        for _ in range(nvars)
                    )
                    ns = 0
                else:
                    cols, ns = seen[gi]
                if cols and cols[0].shape[0] != scaps[gi]:
                    cols = tuple(_pad_u32(c, scaps[gi]) for c in cols)
                out = _prov_naf_pass_addmult(
                    rule,
                    _Caps(F, D, J),
                    scaps[gi],
                    st["fs"],
                    st["fp"],
                    st["fo"],
                    st["ftag"],
                    jnp.int32(st["n_facts"]),
                    cols,
                    jnp.int32(ns),
                    masks,
                    jnp.float64(gtags[gi]),
                )
                code = int(out[9])  # one sync per dispatch
                if code != 0:
                    attempts += 1
                    if attempts > max_attempts:
                        return None
                    if code & 1:
                        J *= 2
                    if code & 2:
                        D *= 2
                        st = pad_delta(st, D)
                    if code & 4:
                        F *= 2
                        for k in ("fs", "fp", "fo"):
                            st[k] = _pad_u32(st[k], F)
                        st["ftag"] = _pad_f64(st["ftag"], F)
                    if code & 8:
                        scaps[gi] *= 2
                    failed = True
                    break  # retry the remaining rules at bigger caps
                st = {
                    "fs": out[0],
                    "fp": out[1],
                    "fo": out[2],
                    "ftag": out[3],
                    "n_facts": int(out[4]),
                    "didx": out[5],
                    "n_delta": int(out[6]),
                }
                seen[gi] = (out[7], int(out[8]))
                committed[gi] = True
            if not failed:
                break
        # the pass delta = EXACTLY the facts appended during the pass
        # (host naf_new: newly ADDED keys only — an improved pre-existing
        # conclusion must not re-enter the positive stratum), as fact-row
        # indices; their tags are read from the live buffers by the round
        if st["n_facts"] == pass_start:
            return st
        didx = np.arange(pass_start, st["n_facts"], dtype=np.int32)
        if didx.size > D:
            D = _round_cap(didx.size)
        st["didx"] = _pad_i32(didx, D)
        st["n_delta"] = int(didx.size)
        if round_fn is not None:
            st = _run_overflow_protocol(
                round_fn, st, n0, st["n_delta"], pad_delta, max_attempts
            )
            if st is None:
                return None
            F = st["fs"].shape[0]
            D = st["didx"].shape[0]
        else:
            st["n_delta"] = 0
    return None  # pass limit


def _write_back(
    reasoner, provenance, tag_store, fs, fp, fo, ftag, n_facts, n0, tags0
) -> None:
    """Write back: new facts into the store; every changed-or-new tag entry
    into the tag store (vectorized — no per-fact Python loop).  Host parity:
    each derived fact gets an explicit entry (update_disjunction inserts on
    first derivation); NaN still means "no entry"."""
    fs_h = np.asarray(fs[:n_facts])
    fp_h = np.asarray(fp[:n_facts])
    fo_h = np.asarray(fo[:n_facts])
    ft_h = np.asarray(ftag[:n_facts])
    if n_facts > n0:
        reasoner.facts.add_batch(fs_h[n0:], fp_h[n0:], fo_h[n0:])
    has_entry = ~np.isnan(ft_h)
    unchanged = np.zeros(n_facts, dtype=bool)
    unchanged[:n0] = ~np.isnan(tags0) & (ft_h[:n0] == tags0)
    sel = np.flatnonzero(has_entry & ~unchanged)
    if sel.size:
        decoded = _decode_tags(provenance, ft_h[sel])
        keys = zip(
            fs_h[sel].tolist(), fp_h[sel].tolist(), fo_h[sel].tolist()
        )
        tag_store.tags.update(zip(keys, decoded))


def _drive_addmult(
    reasoner,
    provenance,
    tag_store,
    pos_rules,
    naf_rules,
    masks,
    s,
    p,
    o,
    tags0,
    didx0: np.ndarray,
    n0: int,
    max_attempts: int,
) -> Optional[Dict[Tuple[int, int, int], float]]:
    """Host driver for the exactly-once addmult rounds: the shared overflow
    protocol with the delta carried as fact-row INDICES.  NAF rules run as
    the stratified loop — positive protocol to quiescence, then ONE rule
    per dispatch in host order (:func:`_prov_naf_pass_addmult`, each rule
    carrying its own device-resident seen-set), the pass's union delta
    feeding the positive stratum again until a pass derives nothing."""
    import jax.numpy as jnp

    nd0 = int(didx0.size)

    with jax.enable_x64(True):
        st = {
            "fs": _pad_u32(s, 0),
            "fp": _pad_u32(p, 0),
            "fo": _pad_u32(o, 0),
            "ftag": _pad_f64(tags0, 0),
            "n_facts": n0,
            "didx": _pad_i32(didx0, 0),
            "n_delta": nd0,
        }
        gtags = jnp.asarray(
            _guard_tag_array(pos_rules, provenance, tag_store)
        )

        def round_fn(caps, st):
            out = _prov_round_addmult(
                pos_rules,
                caps,
                st["fs"],
                st["fp"],
                st["fo"],
                st["ftag"],
                jnp.int32(st["n_facts"]),
                st["didx"],
                jnp.int32(st["n_delta"]),
                masks,
                gtags,
            )
            code = int(out[7])  # one sync per round
            if code != 0:
                return None, code
            return {
                "fs": out[0],
                "fp": out[1],
                "fo": out[2],
                "ftag": out[3],
                "n_facts": int(out[4]),
                "didx": out[5],
                "n_delta": int(out[6]),
            }, 0

        def pad_delta(st, D):
            st["didx"] = _pad_i32(st["didx"], D)
            return st

        if pos_rules:
            st = _run_overflow_protocol(
                round_fn, st, n0, nd0, pad_delta, max_attempts
            )
        else:
            F = max(_round_cap(4 * n0, 2048), st["fs"].shape[0])
            D = _round_cap(max(2 * nd0, n0 // 2, 1024))
            for k in ("fs", "fp", "fo"):
                st[k] = _pad_u32(st[k], F)
            st["ftag"] = _pad_f64(st["ftag"], F)
            st = pad_delta(st, D)
            st["n_delta"] = 0
        if st is not None and naf_rules:
            st = _drive_naf_addmult(
                naf_rules,
                st,
                round_fn if pos_rules else None,
                pad_delta,
                provenance,
                tag_store,
                masks,
                n0,
                max_attempts,
            )
        if st is None:
            return None  # graceful host fallback (reasoner state untouched)
        _write_back(
            reasoner,
            provenance,
            tag_store,
            st["fs"],
            st["fp"],
            st["fo"],
            st["ftag"],
            st["n_facts"],
            n0,
            tags0,
        )
    return {}
