"""Pallas lex-probe kernels + cap advisor (ISSUE 11).

Three layers of assurance for the fused WCOJ probe path:

1. op level — ``lex_range`` against ``host_lex_range`` and the
   ``lex_searchsorted`` pair it fused, and the full level expansion
   (XLA pre-pass + ``lex_probe_select``/``lex_probe_validate`` kernels,
   interpret mode on CPU) against the ``host_lex_probe`` numpy twin over
   randomized base/delta/tombstone/reinsert structures;
2. engine level — ``KOLIBRIE_PALLAS=force`` must return rows
   byte-identical to the XLA chain (``off``) and the host oracle on
   randomized cyclic BGPs, across mutations, with no recompiles across
   constant variants and a replan on every mode flip;
3. protocol level — the capacity advisor holds doubled-cap retried
   dispatches at zero once warm (fresh dbs, mutation churn), and its
   state surfaces in ``/stats``.
"""

import numpy as np
import pytest

from kolibrie_tpu.ops.pallas_kernels import (
    lex_probe_select,
    lex_probe_validate,
    pallas_mode,
)
from kolibrie_tpu.ops.wcoj import (
    host_lex_probe,
    host_lex_range,
    lex_range,
    lex_searchsorted,
)
from kolibrie_tpu.query.executor import execute_query_volcano
from kolibrie_tpu.query.sparql_database import SparqlDatabase

import jax.numpy as jnp

SENT = np.uint32(0xFFFFFFFF)
PREFIX = "PREFIX ex: <http://example.org/>\n"


# ---------------------------------------------------------------- helpers


def _sorted_cols(rng, n_cols, n_rows, cap, alphabet=8):
    """``n_cols`` lexicographically co-sorted u32 columns with duplicate
    runs (small alphabet), sentinel-padded to ``cap`` rows."""
    raw = rng.integers(0, alphabet, size=(n_cols, n_rows)).astype(np.uint32)
    order = np.lexsort(raw[::-1]) if n_rows else np.arange(0)
    cols = []
    for c in range(n_cols):
        col = np.full(cap, SENT, dtype=np.uint32)
        col[:n_rows] = raw[c][order]
        cols.append(col)
    return tuple(cols)


def _graph_db(rng, n_nodes, n_edges):
    lines = []
    for _ in range(n_edges):
        p = ("p1", "p2", "p3")[int(rng.integers(0, 3))]
        a, b = rng.integers(0, n_nodes, 2)
        lines.append(
            f"<http://example.org/n{a}> <http://example.org/{p}> "
            f"<http://example.org/n{b}> ."
        )
    db = SparqlDatabase()
    db.parse_ntriples("\n".join(lines))
    db.execution_mode = "device"
    return db, lines


TRI_Q = PREFIX + (
    "SELECT ?x ?y ?z WHERE { ?x ex:p1 ?y . ?y ex:p2 ?z . ?z ex:p3 ?x }"
)
SQUARE_Q = PREFIX + (
    "SELECT ?x ?y ?z ?w WHERE "
    "{ ?x ex:p1 ?y . ?y ex:p2 ?z . ?z ex:p3 ?w . ?w ex:p1 ?x }"
)


def _rows(db, query, mode):
    prev = db.execution_mode
    db.execution_mode = mode
    try:
        return sorted(map(tuple, execute_query_volcano(query, db)))
    finally:
        db.execution_mode = prev


# ------------------------------------------------------------- mode flag


def test_pallas_mode_parsing(monkeypatch):
    monkeypatch.delenv("KOLIBRIE_PALLAS", raising=False)
    assert pallas_mode() == "auto"
    for val, want in (
        ("off", "off"), ("0", "off"), ("false", "off"),
        ("auto", "auto"), ("bogus", "auto"),
        ("force", "force"), ("1", "force"), ("true", "force"),
    ):
        monkeypatch.setenv("KOLIBRIE_PALLAS", val)
        assert pallas_mode() == want, val


@pytest.mark.parametrize("suffix", ["_JOIN", "_DIST"])
@pytest.mark.parametrize("value", ["0", "1"])
def test_pallas_legacy_flags_have_no_effect(monkeypatch, suffix, value):
    """The per-subsystem names that predate ``KOLIBRIE_PALLAS`` were shims
    until PR 30: set, they now change neither the mode nor the mesh's
    shard-local join route."""
    from kolibrie_tpu.parallel.dist_join import dist_pallas_enabled

    monkeypatch.delenv("KOLIBRIE_PALLAS", raising=False)
    monkeypatch.setenv("KOLIBRIE_PALLAS" + suffix, value)
    assert pallas_mode() == "auto"
    assert dist_pallas_enabled() is False
    monkeypatch.setenv("KOLIBRIE_PALLAS", "force")
    assert pallas_mode() == "force"
    assert dist_pallas_enabled() is True


# ------------------------------------------------------ lex_range fuzz


def test_lex_range_matches_searchsorted_pair_fuzz():
    """The fused lo+hi search must be bit-identical to the left/right
    ``lex_searchsorted`` pair and the numpy twin — 1-3 key columns,
    empty relations, empty ranges and sentinel probes included."""
    rng = np.random.default_rng(11)
    for trial in range(12):
        n_cols = int(rng.integers(1, 4))
        n_rows = int(rng.integers(0, 40))
        cap = 1 << int(np.int64(max(1, n_rows)).item() - 1).bit_length()
        cols = _sorted_cols(rng, n_cols, n_rows, cap)
        p = int(rng.integers(1, 30))
        keys = tuple(
            np.where(
                rng.random(p) < 0.1,
                SENT,
                rng.integers(0, 10, p).astype(np.uint32),
            ).astype(np.uint32)
            for _ in range(n_cols)
        )
        jcols = tuple(jnp.asarray(c) for c in cols)
        jkeys = tuple(jnp.asarray(k) for k in keys)
        lo, hi = lex_range(jcols, jkeys)
        lo_ref = lex_searchsorted(jcols, jkeys, side="left")
        hi_ref = lex_searchsorted(jcols, jkeys, side="right")
        np.testing.assert_array_equal(np.asarray(lo), np.asarray(lo_ref))
        np.testing.assert_array_equal(np.asarray(hi), np.asarray(hi_ref))
        hlo, hhi = host_lex_range(cols, keys)
        np.testing.assert_array_equal(np.asarray(lo), hlo)
        np.testing.assert_array_equal(np.asarray(hi), hhi)


# ------------------------------------------- fused probe vs numpy twin


def _random_accessor(rng, n_keys, pcap, reinsert):
    """One accessor: sorted base/delta segments over (keys..., val),
    random tombstones, optional reinsertion of tombstoned base rows into
    the delta, and probe keys mixing hits, misses and sentinels."""
    nb = int(rng.integers(0, 30))
    nd = int(rng.integers(0, 20))
    bcap = 1 << int(np.int64(max(1, nb)).item() - 1).bit_length()
    dcap = 1 << int(np.int64(max(1, nd)).item() - 1).bit_length()
    bcols = _sorted_cols(rng, n_keys + 1, nb, bcap)
    dcols = list(_sorted_cols(rng, n_keys + 1, nd, dcap))
    # tombstone a random subset of live base rows
    n_del = int(rng.integers(0, nb + 1))
    dels = np.sort(
        rng.choice(nb, size=n_del, replace=False).astype(np.uint32)
        if n_del
        else np.zeros(0, np.uint32)
    )
    if reinsert and n_del and nd < dcap:
        # reinsert one tombstoned base row into the delta (mutation
        # churn: delete + re-add lands the copy in the delta segment)
        pos = int(dels[int(rng.integers(0, n_del))])
        row = [bcols[c][pos] for c in range(n_keys + 1)]
        stacked = np.stack([np.asarray(c).copy() for c in dcols])
        stacked[:, nd] = row
        order = np.lexsort(stacked[::-1])
        dcols = [stacked[c][order] for c in range(n_keys + 1)]
    del_cap = 1 << int(np.int64(max(1, n_del)).item() - 1).bit_length()
    del_pos = np.full(del_cap, SENT, dtype=np.uint32)
    del_pos[:n_del] = dels
    keys = tuple(
        np.where(
            rng.random(pcap) < 0.12,
            SENT,
            rng.integers(0, 8, pcap).astype(np.uint32),
        ).astype(np.uint32)
        for _ in range(n_keys)
    )
    return {
        "bkeys": bcols[:n_keys],
        "dkeys": tuple(dcols[:n_keys]),
        "bval": bcols[n_keys],
        "dval": dcols[n_keys],
        "del_pos": del_pos,
        "keys": keys,
    }


def _device_probe(accessors, wvalid, cap, use_pallas):
    """The test-side mirror of one WCOJ level expansion in
    ``optimizer/device_engine.py`` — XLA pre-pass (ranges, slot math,
    gathers, existence) around the two fused kernels, or the equivalent
    straight-line XLA chain."""
    JSENT = jnp.uint32(0xFFFFFFFF)
    wvalid = jnp.asarray(wvalid)
    pcap = wvalid.shape[0]
    probes = []
    for acc in accessors:
        keys = [jnp.asarray(k) for k in acc["keys"]]
        sent = jnp.zeros(pcap, dtype=bool)
        for k in keys:
            sent = sent | (k == JSENT)
        if keys:
            bl, bh = lex_range(
                tuple(jnp.asarray(c) for c in acc["bkeys"]), tuple(keys)
            )
            dl, dh = lex_range(
                tuple(jnp.asarray(c) for c in acc["dkeys"]), tuple(keys)
            )
        else:
            bl = jnp.zeros(pcap, dtype=jnp.int32)
            dl = jnp.zeros(pcap, dtype=jnp.int32)
            nb0 = jnp.searchsorted(
                jnp.asarray(acc["bval"]), JSENT, side="left"
            ).astype(jnp.int32)
            nd0 = jnp.searchsorted(
                jnp.asarray(acc["dval"]), JSENT, side="left"
            ).astype(jnp.int32)
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
    cum = jnp.cumsum(cnt)
    slot = jnp.arange(cap, dtype=jnp.int32)
    row = jnp.searchsorted(cum, slot, side="right").astype(jnp.int32)
    row_c = jnp.clip(row, 0, pcap - 1)
    kk = slot - (cum[row_c] - cnt[row_c])
    in_range = slot.astype(jnp.int64) < total
    ch = choice[row_c]
    sel = []
    for acc, (keys, sent, bl, bh, dl, dh) in zip(accessors, probes):
        bv, dv = jnp.asarray(acc["bval"]), jnp.asarray(acc["dval"])
        nb = bh[row_c] - bl[row_c]
        bidx = jnp.clip(bl[row_c] + kk, 0, bv.shape[0] - 1)
        didx = jnp.clip(dl[row_c] + (kk - nb), 0, dv.shape[0] - 1)
        bval, dval = bv[bidx], dv[didx]
        bprev = bv[jnp.clip(bidx - 1, 0, bv.shape[0] - 1)]
        dprev = dv[jnp.clip(didx - 1, 0, dv.shape[0] - 1)]
        sel.append((nb, bval, dval, bprev, dprev))
    if use_pallas:
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
        new_valid = in_range & (val != JSENT) & first
    ex = []
    for acc, (keys, sent, *_r) in zip(accessors, probes):
        fkeys = tuple(k[row_c] for k in keys) + (val,)
        bsf = tuple(jnp.asarray(c) for c in acc["bkeys"]) + (
            jnp.asarray(acc["bval"]),
        )
        dsf = tuple(jnp.asarray(c) for c in acc["dkeys"]) + (
            jnp.asarray(acc["dval"]),
        )
        fl, fh = lex_range(bsf, fkeys)
        dl2, dh2 = lex_range(dsf, fkeys)
        del_pos = jnp.asarray(acc["del_pos"])
        tl = jnp.searchsorted(del_pos, fl.astype(jnp.uint32))
        th = jnp.searchsorted(del_pos, fh.astype(jnp.uint32))
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
                    tl.astype(jnp.int32),
                    th.astype(jnp.int32),
                    dl2,
                    dh2,
                    sent_r,
                )
                for fl, fh, tl, th, dl2, dh2, sent_r in ex
            ],
        )
    else:
        for fl, fh, tl, th, dl2, dh2, sent_r in ex:
            blive = (fh - fl) - (th - tl)
            live = (blive + (dh2 - dl2)) > 0
            new_valid = new_valid & live & ~sent_r
        braw = jnp.stack([(fh - fl) > 0 for fl, fh, *_x in ex])[ch, slot]
        new_valid = new_valid & (is_base | ~braw)
    return {
        "val": np.asarray(jnp.where(new_valid, val, 0)),
        "valid": np.asarray(new_valid),
        "row": np.asarray(row_c),
        "choice": np.asarray(ch),
        "total": int(total),
    }


@pytest.mark.parametrize("use_pallas", [True, False])
def test_lex_probe_matches_host_twin_fuzz(use_pallas):
    """Randomized level expansions — 1/2/3 key columns (plus unbound
    accessors), base/delta/tombstone/reinsert structures, empty ranges,
    sentinel probes, caps above AND below the candidate total — must be
    bit-identical between the numpy twin and both device formulations
    (the Pallas kernels run interpret-mode on CPU)."""
    rng = np.random.default_rng(29)
    for trial in range(6):
        n_acc = int(rng.integers(1, 4))
        pcap = int(rng.integers(4, 48))
        accessors = []
        for a in range(n_acc):
            # first accessor of a level may be unbound (no key columns)
            n_keys = (
                0
                if a == 0 and rng.random() < 0.25
                else int(rng.integers(1, 4))
            )
            accessors.append(
                _random_accessor(rng, n_keys, pcap, rng.random() < 0.5)
            )
        wvalid = rng.random(pcap) < 0.8
        host = host_lex_probe(accessors, wvalid, cap=1)
        # one cap above the total, one strictly below (truncation edge)
        caps = {max(8, 1 << int(host["total"]).bit_length())}
        if host["total"] > 1:
            caps.add(max(1, host["total"] // 2))
        for cap in sorted(caps):
            href = host_lex_probe(accessors, wvalid, cap=cap)
            dev = _device_probe(accessors, wvalid, cap, use_pallas)
            assert dev["total"] == href["total"], (trial, cap)
            np.testing.assert_array_equal(
                dev["valid"], href["valid"], err_msg=f"trial {trial} cap {cap}"
            )
            np.testing.assert_array_equal(
                dev["val"], href["val"], err_msg=f"trial {trial} cap {cap}"
            )
            np.testing.assert_array_equal(dev["row"], href["row"])
            np.testing.assert_array_equal(dev["choice"], href["choice"])


# ------------------------------------------------- engine byte-identity


def test_engine_force_matches_off_and_host_fuzz(monkeypatch):
    """KOLIBRIE_PALLAS=force (fused kernels, interpret mode on CPU) must
    return rows byte-identical to off (the XLA chain) and to the host
    oracle on randomized cyclic BGPs, including after mutation churn
    (deletes + reinserts → tombstones and delta copies)."""
    monkeypatch.setenv("KOLIBRIE_WCOJ", "auto")
    rng = np.random.default_rng(3)
    for seed in range(1):
        db, lines = _graph_db(rng, 25, 260)
        for q in (TRI_Q, SQUARE_Q):
            monkeypatch.setenv("KOLIBRIE_PALLAS", "off")
            off = _rows(db, q, "device")
            monkeypatch.setenv("KOLIBRIE_PALLAS", "force")
            force = _rows(db, q, "device")
            host = _rows(db, q, "host")
            assert off == force == host, (seed, q)
        # mutation churn: delete a slice (tombstones), re-add it (delta
        # copies of tombstoned base rows) plus fresh edges
        victims = lines[:30]
        for ln in victims:
            s, p, o = ln.rstrip(" .").split(" ")
            db.delete_triple(db.add_triple_parts(s, p, o))
        db.parse_ntriples("\n".join(victims))
        db.parse_ntriples(
            "\n".join(
                f"<http://example.org/n{int(rng.integers(0, 25))}> "
                f"<http://example.org/p1> "
                f"<http://example.org/n{int(rng.integers(0, 25))}> ."
                for _ in range(10)
            )
        )
        monkeypatch.setenv("KOLIBRIE_PALLAS", "off")
        off = _rows(db, TRI_Q, "device")
        monkeypatch.setenv("KOLIBRIE_PALLAS", "force")
        force = _rows(db, TRI_Q, "device")
        host = _rows(db, TRI_Q, "host")
        assert off == force == host, f"post-mutation divergence seed {seed}"


def test_no_recompile_across_constant_variants_under_force(monkeypatch):
    """Constant variants of one cyclic template must share a single
    device executable with the fused kernels on — the Pallas routing is a
    static jit argument and part of the fingerprint, never a per-variant
    recompile trigger."""
    monkeypatch.setenv("KOLIBRIE_WCOJ", "force")
    monkeypatch.setenv("KOLIBRIE_PALLAS", "force")
    from kolibrie_tpu.optimizer.device_engine import device_compile_stats

    lines = []
    for h in range(8):
        for i in range(3):
            a, b, hub = 100 + 10 * h + i, 200 + 10 * h + i, 1000 + h
            lines.append(
                f"<http://example.org/n{hub}> <http://example.org/p1> "
                f"<http://example.org/n{a}> ."
            )
            lines.append(
                f"<http://example.org/n{a}> <http://example.org/p2> "
                f"<http://example.org/n{b}> ."
            )
            lines.append(
                f"<http://example.org/n{b}> <http://example.org/p3> "
                f"<http://example.org/n{hub}> ."
            )
    db = SparqlDatabase()
    db.parse_ntriples("\n".join(lines))
    db.execution_mode = "device"

    def variant(h):
        return PREFIX + (
            "SELECT ?y ?z WHERE { "
            f"ex:n{1000 + h} ex:p1 ?y . ?y ex:p2 ?z . ?z ex:p3 ex:n{1000 + h}"
            " }"
        )

    for h in range(8):  # warmup: one compile, converged caps
        assert len(_rows(db, variant(h), "device")) == 3
    base = dict(device_compile_stats())
    for h in range(8):
        assert _rows(db, variant(h), "device") == _rows(
            db, variant(h), "host"
        )
    assert dict(device_compile_stats()) == base, "recompile across variants"


def test_pallas_mode_flip_replans(monkeypatch):
    """Flipping KOLIBRIE_PALLAS must land on a fresh fingerprint (replan +
    recompile), never replay the other mode's cached executable."""
    monkeypatch.setenv("KOLIBRIE_WCOJ", "auto")
    from kolibrie_tpu.optimizer.device_engine import device_compile_stats

    rng = np.random.default_rng(17)
    db, _ = _graph_db(rng, 20, 200)
    monkeypatch.setenv("KOLIBRIE_PALLAS", "off")
    rows_off = _rows(db, TRI_Q, "device")
    base = dict(device_compile_stats())
    monkeypatch.setenv("KOLIBRIE_PALLAS", "force")
    rows_force = _rows(db, TRI_Q, "device")
    after = dict(device_compile_stats())
    assert rows_off == rows_force
    assert after != base, "mode flip replayed the cached executable"


# ------------------------------------------- capacities, warm and in /stats


def _device_retries() -> float:
    from kolibrie_tpu.obs import export as obs_export

    name = 'kolibrie_cap_retries_total{engine="device"}'
    for line in obs_export.render_prometheus().splitlines():
        if line.startswith(name):
            return float(line.rpartition(" ")[2])
    raise KeyError(name)


def test_a_warm_database_redispatches_with_zero_retries(monkeypatch):
    """A dense cyclic workload whose per-level candidate totals exceed the
    optimistic heuristic start walks the double-and-retry ladder once
    (cold); what it converged to is remembered on the store, so EVERY
    re-dispatch, store mutations (base-version bumps) between them or not,
    starts there and ``kolibrie_cap_retries_total{engine="device"}`` stays
    where the first dispatch left it."""
    monkeypatch.setenv("KOLIBRIE_WCOJ", "auto")
    rng = np.random.default_rng(5)
    db, _lines = _graph_db(rng, 40, 1500)
    cold0 = _device_retries()
    rows = _rows(db, TRI_Q, "device")
    warm = _device_retries()
    assert warm > cold0, "workload must actually exercise the retry ladder"
    assert _rows(db, TRI_Q, "device") == rows
    assert _device_retries() == warm, "a warm store must not retry"
    # mutation churn on the live db: re-dispatch must stay retry-free
    db.parse_ntriples(
        "\n".join(
            f"<http://example.org/n{i}> <http://example.org/p2> "
            f"<http://example.org/n{(i + 1) % 40}> ."
            for i in range(20)
        )
    )
    _rows(db, TRI_Q, "device")
    assert _device_retries() == warm


def test_capacities_stats_surface(monkeypatch):
    """The /stats payload carries, store by store, the capacities block fed
    from that database's one capacity store, and /metrics carries the
    retry counter family (pre-created engine series)."""
    from kolibrie_tpu.frontends.http_server import TemplateBatcher
    from kolibrie_tpu.obs import export as obs_export
    from kolibrie_tpu.optimizer import caps

    monkeypatch.setenv("KOLIBRIE_WCOJ", "auto")
    rng = np.random.default_rng(5)
    db, _lines = _graph_db(rng, 40, 1500)
    assert TemplateBatcher(db).stats()["capacities"] == {"templates": []}
    _rows(db, TRI_Q, "device")
    block = TemplateBatcher(db).stats()["capacities"]
    assert block == caps.of(db).stats()
    (rec,) = block["templates"]
    ((_key, held),) = caps.of(db).joins.items()
    assert rec["caps"] == list(held) and len(held) == 3  # a WCOJ level a variable
    assert rec["provisional"] is False and rec["group_caps"] == []
    assert len(rec["template"]) == 40  # the fingerprint it was dispatched under
    assert "enabled" not in block
    # a second store's block is its own
    assert TemplateBatcher(SparqlDatabase()).stats()["capacities"] == {"templates": []}
    # monotonic elementwise-max merge
    key = _key
    caps.of(db).joins.merge(key, [1, 1 << 30, 1])
    assert caps.of(db).joins.get(key) == (held[0], 1 << 30, held[2])
    prom = obs_export.render_prometheus()
    assert 'kolibrie_cap_retries_total{engine="device"}' in prom
    assert 'kolibrie_cap_retries_total{engine="sharded"}' in prom


# ------------------------------------------- the sorted form of lex_range


def _range_case(rng, n_cols, kind):
    """``(cols, keys)`` of one range-search case: a base with runs of
    duplicates and sentinel padding (``kind`` says how much of each) and
    probes absent, present, repeated and holding the sentinel."""
    cap, n_rows, p = {
        "runs_and_padding": (64, 45, 40),
        "no_padding": (32, 32, 17),
        "all_padding": (16, 0, 9),
        "one_probe": (64, 50, 1),
        "probes_outnumber_rows": (8, 6, 200),
        "one_row": (1, 1, 5),
    }[kind]
    cols = _sorted_cols(rng, n_cols, n_rows, cap, alphabet=5)
    present = rng.integers(0, max(n_rows, 1), p)
    keys = []
    for c in range(n_cols):
        k = cols[c][present].copy()  # a row of the base, padding included
        absent = rng.random(p) < 0.3
        k = np.where(absent, rng.integers(0, 7, p).astype(np.uint32), k)
        keys.append(np.where(rng.random(p) < 0.1, SENT, k).astype(np.uint32))
    if p > 3:
        for k in keys:
            k[1] = k[0]  # equal probes
            k[2] = SENT  # a probe that is the padding's own tuple
    return cols, tuple(keys)


@pytest.mark.parametrize(
    "kind",
    ["runs_and_padding", "no_padding", "all_padding", "one_probe",
     "probes_outnumber_rows", "one_row"],
)
@pytest.mark.parametrize("n_cols", [1, 2, 3])
def test_lex_range_sorted_is_lex_range_bit_for_bit(n_cols, kind):
    """One sort of base rows and tagged probe copies gives the gather
    loop's ``(lo, hi)``, and the numpy twin's: same values, same dtype."""
    import jax

    from kolibrie_tpu.ops.wcoj import lex_range_sorted

    rng = np.random.default_rng(35 + 7 * n_cols + len(kind))
    for _trial in range(4):
        cols, keys = _range_case(rng, n_cols, kind)
        jcols = tuple(jnp.asarray(c) for c in cols)
        jkeys = tuple(jnp.asarray(k) for k in keys)
        lo, hi = lex_range(jcols, jkeys)
        for x64 in (False, True):  # the plan body is traced under x64
            with jax.enable_x64(x64):
                slo, shi = jax.jit(lex_range_sorted)(jcols, jkeys)
            assert slo.dtype == lo.dtype == jnp.int32 and shi.dtype == jnp.int32
            np.testing.assert_array_equal(np.asarray(slo), np.asarray(lo))
            np.testing.assert_array_equal(np.asarray(shi), np.asarray(hi))
        hlo, hhi = host_lex_range(cols, keys)
        np.testing.assert_array_equal(np.asarray(slo), hlo)
        np.testing.assert_array_equal(np.asarray(shi), hhi)


def test_lex_range_sorted_of_no_rows_is_zeros():
    from kolibrie_tpu.ops.wcoj import lex_range_sorted

    empty = (jnp.zeros(0, dtype=jnp.uint32),) * 2
    keys = (jnp.arange(5, dtype=jnp.uint32),) * 2
    for got, want in zip(lex_range_sorted(empty, keys), lex_range(empty, keys)):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
