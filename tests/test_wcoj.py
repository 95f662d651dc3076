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


# ------------------------------------------- range searches by shape (ISSUE 35)
#
# A level's range searches take one of two forms by their static shapes
# (``ops/wcoj.py`` ``range_search_form``): the tests run the same requests at
# capacities on both sides of the rule and hold both to the numpy twin.

RDF_TYPE = "<http://www.w3.org/1999/02/22-rdf-syntax-ns#type>"
EX = "http://example.org/"
# the two cyclic LUBM queries' shapes: three typed variables and a triangle
# of properties, Q9's closing edge from ?x to ?z, Q2's from ?x to ?y
TYPED = "SELECT ?x ?y ?z WHERE { ?x a ex:@A@ . ?y a ex:B . ?z a ex:C . %s }"
SHAPES = {
    "q9": TYPED % "?x ex:p1 ?y . ?y ex:p2 ?z . ?x ex:p3 ?z",
    "q2": TYPED % "?x ex:p1 ?z . ?z ex:p2 ?y . ?x ex:p3 ?y",
}
LOOP_CAP, SORTED_CAP = 1024, 16384
SIDE_CAP = {"loop": LOOP_CAP, "sorted": SORTED_CAP}


def _typed_db(seed=35, n_nodes=60, n_edges=900):
    rng = np.random.default_rng(seed)
    lines = set()
    for k in range(n_nodes):
        for cls in ("A", "B", "C"):
            if rng.random() < 0.6:
                lines.add(f"<{EX}n{k}> {RDF_TYPE} <{EX}{cls}> .")
                if cls == "A":  # A2: as many members, other nodes: one plan
                    lines.add(f"<{EX}n{(k + 1) % n_nodes}> {RDF_TYPE} <{EX}A2> .")
    while len(lines) < n_edges:
        a, b = rng.integers(0, n_nodes, 2)
        p = ("p1", "p2", "p3")[int(rng.integers(0, 3))]
        lines.add(f"<{EX}n{a}> <{EX}{p}> <{EX}n{b}> .")
    db = SparqlDatabase()
    db.store.delta_threshold = 4096  # keep the writes below in the delta
    db.parse_ntriples("\n".join(sorted(lines)))
    db.execution_mode = "device"
    # a live delta and tombstones: every 9th base row deleted, and inserts
    # few enough (under a sixteenth of the store) to stay in the delta
    s, p, o = (c.copy() for c in db.store.columns())
    for i in range(0, len(s), 9):
        db.delete_triple(Triple(int(s[i]), int(p[i]), int(o[i])))
    db.parse_ntriples("\n".join(
        f"<{EX}n{a}> <{EX}{pp}> <{EX}n{(a * 7 + 3) % n_nodes}> ."
        for a in range(0, n_nodes, 6) for pp in ("p1", "p2", "p3")))
    db.store.compact()
    assert len(db.store.delta_order("spo")) > 0
    assert len(db.store.delta_del_positions("spo")) > 0
    return db


def _lower(db, sparql):
    from kolibrie_tpu.optimizer import device_engine as de
    from kolibrie_tpu.optimizer.engine import resolve_pattern
    from kolibrie_tpu.optimizer.planner import Streamertail, build_logical_plan
    from kolibrie_tpu.query.parser import parse_sparql_query

    db.register_prefixes_from_query(sparql)
    w = parse_sparql_query(sparql, db.prefixes).where
    resolved = [resolve_pattern(db, p) for p in w.patterns]
    logical = build_logical_plan(resolved, list(w.filters), [], w.values)
    plan = Streamertail(db.get_or_build_stats()).find_best_plan(logical)
    low = de.lower_plan(db, plan)
    assert isinstance(low.root, de.WcojSpec), low.root
    return low


def _at_capacity(db, sparql, cap):
    """The request's lowering with every level ``cap`` wide."""
    low = _lower(db, sparql)
    from kolibrie_tpu.optimizer import caps

    caps.of(db).joins.start(low.cap_key, [cap] * low.join_count)
    return _lower(db, sparql)


def _searches():
    from kolibrie_tpu.query.template import _RANGE_SEARCH

    return {f: _RANGE_SEARCH.labels(f).value for f in ("sorted", "loop")}


def _spec_searches(low, every_tier=False):
    """The range searches one dispatch of ``low`` makes, by form, counted
    from its levels as ``eval_level`` makes them: per accessor and tier one
    in ``probe`` where it has keys, one in ``live``."""
    from kolibrie_tpu.ops.wcoj import range_search_form

    out = {"sorted": 0, "loop": 0}
    pcap = 1
    for lv in low.root.levels:
        cap = low._join_caps[lv.join_idx]
        for a in lv.accessors:
            base, delta = low._seg_rows[a.order_idx]
            live_delta = every_tier or low._tiers_np[a.order_idx] > 0
            for n in (base, delta) if live_delta else (base,):
                if a.key_srcs:
                    out[range_search_form(n, pcap, len(a.key_srcs))] += 1
                out[range_search_form(n, cap, len(a.key_srcs) + 1)] += 1
        pcap = cap
    return out


def _id_rows(table):
    names = sorted(table)
    return sorted(zip(*(table[v].tolist() for v in names)))


@pytest.mark.parametrize(
    "n,p,ncols,form",
    [
        # PR 35's gate, one v5e (PERF.md section 6): the shapes it timed
        (2**20, 1024, 3, "loop"),
        (2**20, 16384, 2, "sorted"),
        (2**20, 65536, 3, "sorted"),
        (2**23, 4096, 3, "loop"),
        (2**23, 262144, 2, "sorted"),
        (2**23, 1048576, 3, "sorted"),
        (1024, 65536, 3, "sorted"),
        # the gate's three further points: the sort won 1.6-1.7 times at
        # N = 128 P and stays out (its two sort instructions cost the TPU
        # compiler 27-67 s); it lost at N = 512 P
        (2**23, 65536, 3, "loop"),
        (2**20, 8192, 3, "loop"),
        (2**23, 16384, 2, "loop"),
        # a level's first probe, and the floor capacity over a small store
        (2**23, 1, 2, "loop"),
        (1024, 1024, 3, "loop"),
    ],
)
def test_the_rule_picks_the_form_by_shape(n, p, ncols, form):
    from kolibrie_tpu.ops.wcoj import range_search_form

    assert range_search_form(n, p, ncols) == form


@pytest.mark.parametrize("ncols", [1, 2, 3])
def test_the_rule_is_monotone_in_the_probes(ncols):
    """More probes never turn a sort back into a loop: the loop's cost
    grows with P, the sort's hardly."""
    from kolibrie_tpu.ops.wcoj import range_search_form

    for n in (1, 1024, 2**17, 2**20, 2**23, 2**26):
        forms = [range_search_form(n, 2**k, ncols) for k in range(0, 24)]
        first = forms.index("sorted") if "sorted" in forms else len(forms)
        assert forms == ["loop"] * first + ["sorted"] * (len(forms) - first)
        assert forms[0] == "loop"  # a level's first probe is one tuple wide


@pytest.mark.parametrize("side", ["loop", "sorted"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_typed_triangles_on_both_sides_of_the_rule(shape, side, monkeypatch):
    """Q9- and Q2-shaped triangles over a store with a live delta and
    tombstones: at 1,024-wide levels every search loops, at 16,384-wide
    ones the levels' searches sort, and the rows are the numpy twin's and
    the host path's either way; the counter grows by the spec's count."""
    monkeypatch.setenv("KOLIBRIE_WCOJ", "force")
    db = _typed_db()
    sparql = PREFIX + SHAPES[shape].replace("@A@", "A")
    low = _at_capacity(db, sparql, SIDE_CAP[side])
    before = _searches()
    got = _id_rows(low.execute())
    grew = {f: v - before[f] for f, v in _searches().items()}
    assert set(low._join_caps) == {SIDE_CAP[side]}
    want = _spec_searches(low)
    assert grew == want
    accessors = sum(len(lv.accessors) for lv in low.root.levels)
    assert sum(want.values()) >= 2 * accessors  # base and delta, every tier live
    if side == "loop":
        assert want["sorted"] == 0
    else:
        # all but the first level's one-tuple probes
        first_probes = 2 * sum(bool(a.key_srcs) for a in low.root.levels[0].accessors)
        assert want == {"sorted": sum(want.values()) - first_probes,
                        "loop": first_probes}
    assert got == _id_rows(low.host_execute()[0])
    assert got and len(got) == len(_rows(db, sparql, "host"))


def _sort_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "sort":
            yield eqn
        for sub in eqn.params.values():
            for item in sub if isinstance(sub, (list, tuple)) else (sub,):
                inner = getattr(item, "jaxpr", item)
                if hasattr(inner, "eqns"):
                    yield from _sort_eqns(inner)


@pytest.mark.parametrize("side", ["loop", "sorted"])
def test_the_traced_program_sorts_where_the_counter_says_so(side, monkeypatch):
    """One variadic sort a sorted search (keys: its columns and the tag),
    one two-operand sort back; a loop search traces none."""
    import jax

    from kolibrie_tpu.optimizer import device_engine as de

    monkeypatch.setenv("KOLIBRIE_WCOJ", "force")
    db = _typed_db()
    low = _at_capacity(
        db, PREFIX + SHAPES["q9"].replace("@A@", "A"), SIDE_CAP[side])
    spec, args = low.build()
    with jax.enable_x64(True):
        jaxpr = jax.make_jaxpr(lambda *a: de._run_plan(spec, False, *a))(*args)
    sorts = list(_sort_eqns(jaxpr.jaxpr))
    merged = [e for e in sorts if e.params["num_keys"] >= 2]
    back = [e for e in sorts if e.params["num_keys"] == 1]
    want = _spec_searches(low, every_tier=True)  # both branches are traced
    assert len(merged) == len(back) == want["sorted"]
    assert (want["sorted"] == 0) == (side == "loop")
    for e in merged:
        assert len(e.invars) == e.params["num_keys"]  # columns..., tag: all keys


def test_a_group_of_two_wcoj_members_builds_one_executable(monkeypatch):
    """Two constants of one typed triangle in one dispatch: one batch
    executable, each member's rows its own twin's, and the searches counted
    once a live member."""
    from kolibrie_tpu.optimizer import device_engine as de

    monkeypatch.setenv("KOLIBRIE_WCOJ", "force")
    db = _typed_db()
    texts = [PREFIX + SHAPES["q9"].replace("@A@", a) for a in ("A", "A2")]
    lows = [_at_capacity(db, t, SORTED_CAP) for t in texts]
    assert lows[0].cap_key == lows[1].cap_key
    programs0 = de.device_compile_stats()["run_plan_batch"]
    before = _searches()
    tables = de.execute_plan_batch(lows)
    grew = {f: v - before[f] for f, v in _searches().items()}
    assert de.device_compile_stats()["run_plan_batch"] - programs0 == 1
    want = _spec_searches(lows[0])
    assert want["sorted"] > 0
    assert grew == {f: 2 * v for f, v in want.items()}
    rows = [_id_rows(t) for t in tables]
    for text, got in zip(texts, rows):
        assert got == _id_rows(_lower(db, text).host_execute()[0])
    assert rows[0] != rows[1] and all(rows)
    # the same group again: nothing compiles
    de.execute_plan_batch([_lower(db, t) for t in texts])
    assert de.device_compile_stats()["run_plan_batch"] - programs0 == 1


def test_a_wcoj_plan_adds_nothing_to_the_join_search_counter(monkeypatch):
    """ISSUE 39: ``kolibrie_join_search_keys_total`` counts the merge joins
    that run the Pallas prepass; a plan that is one ``WcojSpec`` has none,
    with the kernels on as well."""
    from kolibrie_tpu.optimizer import device_engine as de
    from kolibrie_tpu.query.template import _JOIN_SEARCH_KEYS

    monkeypatch.setenv("KOLIBRIE_WCOJ", "force")
    monkeypatch.setenv("KOLIBRIE_PALLAS", "force")
    db = _typed_db()
    low = _at_capacity(db, PREFIX + SHAPES["q9"].replace("@A@", "A"), LOOP_CAP)
    assert not list(de._spec_nodes(low.root, de.JoinSpec))
    before = [_JOIN_SEARCH_KEYS.labels(w).value for w in ("slots", "searched")]
    searches0 = _searches()
    assert _id_rows(low.execute()) == _id_rows(low.host_execute()[0])
    assert _searches() != searches0  # the dispatch was counted
    assert [_JOIN_SEARCH_KEYS.labels(w).value
            for w in ("slots", "searched")] == before
