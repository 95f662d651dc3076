"""Worst-case-optimal join (ISSUE 6): WCOJ vs Volcano agreement.

The WCOJ device kernel enumerates one variable per level from sorted-order
range probes, so its correctness surface is the interaction of candidate
choice (argmin over accessor counts), first-of-run dedup, live-existence
validation against base−tombstones+delta, and the shape-stable cap
protocol.  These tests fuzz that surface against the Volcano binary-join
path, which has its own independently tested host semantics.
"""

from __future__ import annotations

import numpy as np
import pytest

from kolibrie_tpu.core.store import Triple
from kolibrie_tpu.query.executor import execute_query_volcano
from kolibrie_tpu.query.sparql_database import SparqlDatabase

PREFIX = "PREFIX ex: <http://example.org/>\n"


def _edge(store_lines, a, p, b):
    store_lines.append(
        f"<http://example.org/n{a}> <http://example.org/{p}> "
        f"<http://example.org/n{b}> ."
    )


def _graph_db(rng, n_nodes, n_edges, preds=("p1", "p2", "p3")):
    lines = []
    for _ in range(n_edges):
        p = preds[int(rng.integers(0, len(preds)))]
        a, b = rng.integers(0, n_nodes, 2)
        _edge(lines, a, p, b)
    db = SparqlDatabase()
    db.parse_ntriples("\n".join(lines))
    return db, lines


def _rows(db, query, mode):
    prev = db.execution_mode
    db.execution_mode = mode
    try:
        return sorted(map(tuple, execute_query_volcano(query, db)))
    finally:
        db.execution_mode = prev


def _check_modes_agree(db, query, tag=""):
    host = _rows(db, query, "host")
    dev = _rows(db, query, "device")
    assert host == dev, f"device/host divergence {tag}: {len(host)} vs {len(dev)}"
    return host


def _strategy_counts():
    from kolibrie_tpu.obs import export as obs_export

    out = {"wcoj": 0.0, "volcano": 0.0, "star": 0.0}
    for line in obs_export.render_prometheus().splitlines():
        if "kolibrie_planner_join_strategy_total{" in line:
            key = line.split('strategy="')[1].split('"')[0]
            out[key] = float(line.rsplit(" ", 1)[1])
    return out


# ------------------------------------------------------------------ routing


def test_planner_routes_cyclic_to_wcoj(monkeypatch):
    """Auto mode: a triangle BGP plans WCOJ, an acyclic chain stays on the
    Volcano binary-join path."""
    monkeypatch.setenv("KOLIBRIE_WCOJ", "auto")
    rng = np.random.default_rng(7)
    db, _ = _graph_db(rng, 25, 260)
    db.execution_mode = "device"

    tri = PREFIX + (
        "SELECT ?x ?y ?z WHERE "
        "{ ?x ex:p1 ?y . ?y ex:p2 ?z . ?z ex:p3 ?x }"
    )
    chain = PREFIX + (
        "SELECT ?x ?y ?z ?w WHERE "
        "{ ?x ex:p1 ?y . ?y ex:p2 ?z . ?z ex:p3 ?w }"
    )

    before = _strategy_counts()
    _check_modes_agree(db, tri, "triangle")
    mid = _strategy_counts()
    assert mid["wcoj"] > before["wcoj"], "triangle did not plan WCOJ"

    _check_modes_agree(db, chain, "chain")
    after = _strategy_counts()
    assert after["volcano"] > mid["volcano"], "chain did not plan Volcano"
    assert after["wcoj"] == mid["wcoj"], "acyclic chain planned WCOJ"


def test_mode_off_matches_auto(monkeypatch):
    """KOLIBRIE_WCOJ=off must replan (not replay the cached WCOJ plan) and
    produce identical rows."""
    rng = np.random.default_rng(8)
    db, _ = _graph_db(rng, 20, 200)
    db.execution_mode = "device"
    tri = PREFIX + (
        "SELECT ?x ?y ?z WHERE "
        "{ ?x ex:p1 ?y . ?y ex:p2 ?z . ?z ex:p3 ?x }"
    )
    monkeypatch.setenv("KOLIBRIE_WCOJ", "auto")
    rows_auto = _rows(db, tri, "device")
    monkeypatch.setenv("KOLIBRIE_WCOJ", "off")
    before = _strategy_counts()
    rows_off = _rows(db, tri, "device")
    after = _strategy_counts()
    assert rows_auto == rows_off
    assert after["volcano"] > before["volcano"], "mode flip did not replan"


# --------------------------------------------------------------------- fuzz


def _random_connected_bgp(rng):
    """A connected multi-pattern BGP over 2-4 variables; every pattern has
    two DISTINCT variables (the WCOJ eligibility shape), predicates drawn
    from p1-p3, and a fresh variable is attached to the connected core at
    each step."""
    n_vars = int(rng.integers(2, 5))
    variables = [f"v{i}" for i in range(n_vars)]
    n_patterns = int(rng.integers(2, 6))
    patterns = []
    connected = [variables[0]]
    for _ in range(n_patterns):
        a = connected[int(rng.integers(0, len(connected)))]
        rest = [v for v in variables if v != a]
        b = rest[int(rng.integers(0, len(rest)))]
        if b not in connected:
            connected.append(b)
        p = f"p{int(rng.integers(1, 4))}"
        if rng.integers(0, 2):
            a, b = b, a
        patterns.append(f"?{a} ex:{p} ?{b}")
    used = sorted({v for pat in patterns for v in pat.split() if v.startswith("?")})
    return (
        PREFIX
        + "SELECT "
        + " ".join(used)
        + " WHERE { "
        + " . ".join(patterns)
        + " }"
    )


def test_wcoj_matches_volcano_fuzz(monkeypatch):
    """Force mode on randomized connected BGPs (cyclic AND acyclic): the
    WCOJ device path must agree with the Volcano host path row-for-row."""
    monkeypatch.setenv("KOLIBRIE_WCOJ", "force")
    rng = np.random.default_rng(11)
    db, _ = _graph_db(rng, 18, 190)
    before = _strategy_counts()
    for i in range(6):
        q = _random_connected_bgp(rng)
        _check_modes_agree(db, q, f"fuzz[{i}] {q}")
    after = _strategy_counts()
    assert after["wcoj"] > before["wcoj"], "force mode never planned WCOJ"


def test_wcoj_delta_and_tombstone_states(monkeypatch):
    """The two-tier probe math: base-only, populated delta segment,
    tombstoned base rows, delta deletions, and tombstone+re-insert (a base
    row that is dead while an identical delta row is live)."""
    monkeypatch.setenv("KOLIBRIE_WCOJ", "force")
    rng = np.random.default_rng(13)
    db, lines = _graph_db(rng, 22, 210)
    db.store.delta_threshold = 4096  # keep mutations in the delta segment
    tri = PREFIX + (
        "SELECT ?x ?y ?z WHERE "
        "{ ?x ex:p1 ?y . ?y ex:p2 ?z . ?z ex:p3 ?x }"
    )
    _check_modes_agree(db, tri, "base-only")

    def enc(term):
        return db.encode_term_str(term)

    # small compacted batches take the incremental path -> delta segment
    for _batch in range(8):
        for _ in range(4):
            a, b = rng.integers(0, 22, 2)
            for s, p, o in ((a, "p1", b), (b, "p2", a), (a, "p3", a)):
                db.add_triple(
                    Triple(
                        enc(f"<http://example.org/n{s}>"),
                        enc(f"<http://example.org/{p}>"),
                        enc(f"<http://example.org/n{o}>"),
                    )
                )
        db.store.compact()
    assert len(db.store.delta_order("spo").c0) > 0, "delta segment empty"
    _check_modes_agree(db, tri, "delta-populated")

    # tombstone every 7th original base row
    first_del = None
    for ln in lines[:140:7]:
        s, p, o = ln.split()[:3]
        t = Triple(enc(s), enc(p), enc(o))
        first_del = first_del or t
        db.delete_triple(t)
    db.store.compact()
    assert len(db.store.delta_del_positions("spo")) > 0, "no tombstones"
    _check_modes_agree(db, tri, "delta+tombstones")

    # re-insert a tombstoned base row: base copy stays dead, delta copy is
    # live -- exactly-once enumeration must not double-count it
    db.add_triple(first_del)
    db.store.compact()
    _check_modes_agree(db, tri, "tombstone+reinsert")


# ------------------------------------------------------------- no-recompile


def test_no_recompile_across_16_triangle_variants(monkeypatch):
    """16 constant variants of one cyclic template share a single device
    executable: constants ride the traced parameter vector and caps are a
    template property, so the jit cache must not grow after warmup.

    The data is symmetric (every hub constant has identical degree), so
    per-variant statistics — and with them the elimination order and the
    converged caps — are identical across variants.

    Force mode: with the hub constant bound, the residual join graph
    {y}-{y,z}-{z} is GYO-acyclic, so auto would (correctly) route it to
    Volcano; forcing keeps the test on the WCOJ executable."""
    monkeypatch.setenv("KOLIBRIE_WCOJ", "force")
    from kolibrie_tpu.optimizer.device_engine import device_compile_stats

    lines = []
    for h in range(16):
        # per-hub triangle fan: hub -p1-> a_i -p2-> b_i -p3-> hub, 3 each
        for i in range(3):
            _edge(lines, 1000 + h, "p1", 100 + 10 * h + i)
            _edge(lines, 100 + 10 * h + i, "p2", 200 + 10 * h + i)
            _edge(lines, 200 + 10 * h + i, "p3", 1000 + h)
    db = SparqlDatabase()
    db.parse_ntriples("\n".join(lines))
    db.execution_mode = "device"

    def variant(h):
        return PREFIX + (
            "SELECT ?y ?z WHERE { "
            f"ex:n{1000 + h} ex:p1 ?y . ?y ex:p2 ?z . ?z ex:p3 ex:n{1000 + h}"
            " }"
        )

    # warmup pass: compiles once, converges the template caps
    for h in range(16):
        rows = _rows(db, variant(h), "device")
        assert len(rows) == 3, f"hub {h}: expected 3 triangles, got {len(rows)}"
    base = dict(device_compile_stats())
    for h in range(16):
        _check_modes_agree(db, variant(h), f"variant {h}")
    after = dict(device_compile_stats())
    assert after == base, f"recompile across variants: {base} -> {after}"


def test_host_fallback_joins_in_connected_order(monkeypatch):
    """The host engine's WcojNode fallback must not join in textual order:
    LUBM Q2's first two patterns (?x a GraduateStudent / ?y a University)
    share no variable, and their cross product is what killed a
    3.8M-triple host run.  No intermediate may outgrow the largest scan."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "examples"))
    import lubm

    from kolibrie_tpu.ops.join import table_len
    from kolibrie_tpu.optimizer import engine as host_engine

    db = SparqlDatabase()
    s, p, o = lubm.generate_fast(2, db.dictionary)
    db.store.add_batch(s, p, o)
    db.execution_mode = "host"
    sizes = []
    real = host_engine.equi_join_tables

    def spy(left, right):
        out = real(left, right)
        sizes.append(table_len(out))
        return out

    monkeypatch.setattr(host_engine, "equi_join_tables", spy)
    rows = execute_query_volcano(lubm.LUBM_Q2, db)
    members = 2 * lubm.DEPTS_PER_UNIV * lubm.STUDENTS_PER_DEPT  # memberOf scan
    assert rows and sizes and max(sizes) <= members, sizes
