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
    in ``probe`` where it has keys, one in ``live``; the base's over the
    accessor's window where the assembled spec gives it one."""
    from kolibrie_tpu.ops.wcoj import range_search_form

    out = {"sorted": 0, "loop": 0}
    pcap = 1
    for lv in low.build()[0].root.levels:
        cap = low._join_caps[lv.join_idx]
        for a in lv.accessors:
            base, delta = low._seg_rows[a.order_idx]
            live_delta = every_tier or low._tiers_np[a.order_idx] > 0
            for n in (a.window or base, delta) if live_delta else (a.window or base,):
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


def _scoped_eqns(jaxpr, scope, inside=False):
    """Every equation traced under the named scope ``scope``, sub-jaxprs
    (a jitted helper's body, a loop's, a branch's) included."""
    for eqn in jaxpr.eqns:
        here = inside or scope in str(eqn.source_info.name_stack).split("/")
        if here:
            yield eqn
        for sub in eqn.params.values():
            for item in sub if isinstance(sub, (list, tuple)) else (sub,):
                inner = getattr(item, "jaxpr", item)
                if hasattr(inner, "eqns"):
                    yield from _scoped_eqns(inner, scope, here)


def _sort_eqns(jaxpr):
    return (e for e in _scoped_eqns(jaxpr, "", inside=True)
            if e.primitive.name == "sort")


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


# ------------------------------------- a search over its key's window (ISSUE 48)
#
# Every probe tuple of an accessor leads with the text's constants, so the
# rows it can match are one window of the order.  ``range_search`` with
# ``lead`` and ``rows`` searches that window alone and must give the whole
# order's ``(lo, hi)``, bit for bit, in both forms.

SENT32 = 0xFFFFFFFF
WINDOW_ROWS = 1024


def _window_columns(pad):
    """Three sorted columns, ``pad`` sentinel rows at their end.  Under the
    first column: 3 opens the order (500 rows), 10 lies in the middle (700
    rows, 300 of them under a second key of 5), 15 is not there, 20 is the
    last real key (500 rows, so with little padding a 1,024-row window that
    began at its first row would run past the end).  Every group begins and
    ends with a row written three times."""
    rng = np.random.default_rng(48)
    groups = [(3, 500), (7, 900), (10, 700), (12, 396), (20, 500)]
    c0 = np.concatenate([np.full(n, k) for k, n in groups])
    c1 = rng.integers(1, 9, len(c0))
    c1[(c0 == 10).nonzero()[0][:300]] = 5
    c2 = rng.integers(1, 5000, len(c0))
    rows = np.stack([c0, c1, c2], axis=1)
    rows = rows[np.lexsort((c2, c1, c0))]
    for k, _n in groups:
        at = np.flatnonzero(rows[:, 0] == k)
        rows[at[:3]] = rows[at[0]]
        rows[at[-3:]] = rows[at[-1]]
    cols = [np.concatenate([rows[:, j], np.full(pad, SENT32)]).astype(np.uint32)
            for j in range(3)]
    return cols


def _window_probes(cols, lead, p, ncols):
    """``p`` tuples that lead with ``lead``: rows that are there (each
    group's first and last, written three times, among them), values
    between rows, below every row, above every row, and the sentinel."""
    rng = np.random.default_rng(len(lead) * 1000 + p)
    keys = [np.full(p, k, dtype=np.uint32) for k in lead]
    match = np.ones(len(cols[0]), dtype=bool)
    for c, k in zip(cols, lead):
        match &= c == np.uint32(k)
    there = np.flatnonzero(match)
    for j in range(len(lead), ncols):
        col = rng.integers(0, 5002, p).astype(np.uint32)
        col[:4] = (0, 1, SENT32 - 1, SENT32)
        keys.append(col)
    if len(there):
        take = np.concatenate([there[:3], there[-3:], rng.choice(there, p // 2)])
        for j in range(len(lead), ncols):
            keys[j][4:4 + len(take)] = cols[j][take]
    return keys


@pytest.mark.parametrize("form", ["loop", "sorted"])
@pytest.mark.parametrize("ncols", [2, 3])
@pytest.mark.parametrize(
    "case,pad,lead",
    [
        ("at the column's start", 1100, (3,)),
        ("in the middle", 1100, (10,)),
        ("ending at the padding", 1100, (20,)),
        ("clamped: the window would pass the end", 100, (20,)),
        ("no padding at all", 0, (20,)),
        ("a constant with no rows", 1100, (15,)),
        ("a constant below every row", 1100, (1,)),
        ("a sentinel constant: the padding block", 1100, (SENT32,)),
        ("a sentinel constant, no padding", 0, (SENT32,)),
        ("two leading constants", 1100, (10, 5)),
        ("two leading constants, the second not there", 1100, (10, 99)),
        ("two leading constants, clamped", 100, (20, 8)),
    ],
)
def test_a_search_of_the_window_is_the_search_of_the_order(case, pad, lead, ncols, form):
    import jax
    import jax.numpy as jnp

    from kolibrie_tpu.ops import wcoj

    if len(lead) >= ncols and lead[-1] == 99:
        pytest.skip("nothing left to search under both constants")
    p = 8192 if form == "sorted" else 64
    cols = _window_columns(pad)[:ncols]
    n = len(cols[0])
    assert wcoj.range_search_form(WINDOW_ROWS, p, ncols) == form
    in_window = np.ones(n, dtype=bool)
    for c, k in zip(cols, lead):
        in_window &= c == np.uint32(k)
    # the padding is no key's rows: a sentinel constant's window may be cut
    # short of it (1,100 rows of padding), and only a tuple of sentinels
    # alone, which ``sent`` masks in every level, could tell
    sentinel = lead[0] == SENT32
    assert sentinel or in_window.sum() <= WINDOW_ROWS
    assert WINDOW_ROWS < n
    keys = _window_probes(cols, lead, p, ncols)
    dcols = tuple(jnp.asarray(c) for c in cols)
    dkeys = tuple(jnp.asarray(k) for k in keys)
    consts = tuple(jnp.uint32(k) for k in lead)
    windowed = jax.jit(
        lambda c, k, ld: wcoj.range_search(c, k, ld, WINDOW_ROWS))(dcols, dkeys, consts)
    whole = jax.jit(wcoj.lex_range)(dcols, dkeys)
    host = wcoj.host_lex_range(cols, keys)
    told = np.ones(p, dtype=bool)
    if sentinel:
        told = ~np.all([k == np.uint32(SENT32) for k in keys], axis=0)
        assert 0 < told.sum() < p
    for got, want, twin in zip(windowed, whole, host):
        assert got.dtype == want.dtype == jnp.int32
        assert np.array_equal(np.asarray(got)[told], np.asarray(want)[told]), case
        assert np.array_equal(np.asarray(got)[told], twin[told]), case
    # the window really was cut: its start is where the constants' rows begin,
    # pulled back where the slice would pass the padded end
    start, window = jax.jit(
        lambda c, ld: wcoj.key_window(c, ld, WINDOW_ROWS))(dcols, consts)
    below = np.zeros(n, dtype=bool)
    eq = np.ones(n, dtype=bool)
    for c, k in zip(cols, lead):
        below |= eq & (c < np.uint32(k))
        eq &= c == np.uint32(k)
    assert int(start) == min(int(below.sum()), n - WINDOW_ROWS)
    for w, c in zip(window, cols):
        assert np.array_equal(np.asarray(w), c[int(start):int(start) + WINDOW_ROWS])
    if "clamped" in case:
        assert int(below.sum()) + WINDOW_ROWS > n


def test_a_search_without_a_window_is_the_one_it_was():
    """No constant leads, no rows given, or as many as the columns hold:
    ``range_search`` is the plain search, and traces no slice."""
    import jax
    import jax.numpy as jnp

    from kolibrie_tpu.ops import wcoj

    cols = tuple(jnp.asarray(c) for c in _window_columns(1100))
    keys = tuple(jnp.asarray(k) for k in _window_probes(_window_columns(1100), (10,), 64, 3))
    n = int(cols[0].shape[0])
    plain = str(jax.make_jaxpr(wcoj.range_search)(cols, keys))
    for lead, rows in (((), WINDOW_ROWS), ((jnp.uint32(10),), 0), ((jnp.uint32(10),), n)):
        text = str(jax.make_jaxpr(
            lambda c, k, ld, rows=rows: wcoj.range_search(c, k, ld, rows))(cols, keys, lead))
        assert "dynamic_slice" not in text
        assert text.count("while") == plain.count("while")
    cut = str(jax.make_jaxpr(
        lambda c, k, ld: wcoj.range_search(c, k, ld, WINDOW_ROWS))(
            cols, keys, (jnp.uint32(10),)))
    assert cut.count("dynamic_slice") >= 3


def _search_rows():
    from kolibrie_tpu.query.template import _RANGE_SEARCH_ROWS

    return {e: _RANGE_SEARCH_ROWS.labels(e).value for e in ("window", "order")}


@pytest.mark.parametrize("side", ["loop", "sorted"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_typed_triangles_search_their_windows(shape, side, monkeypatch):
    """Q9- and Q2-shaped triangles over a store with a live delta and every
    ninth base row tombstoned, so inside every window: each accessor names a
    predicate, so each carries a window and reads ``pos`` or ``pso`` (the
    constants lead); the rows and the level counts are the numpy twin's, which
    knows no window; the rows counter grows by the windows' rows and by no
    whole order's."""
    from kolibrie_tpu.optimizer import device_engine as de

    monkeypatch.setenv("KOLIBRIE_WCOJ", "force")
    db = _typed_db()
    sparql = PREFIX + SHAPES[shape].replace("@A@", "A")
    low = _at_capacity(db, sparql, SIDE_CAP[side])
    before = _search_rows()
    got = _id_rows(low.execute())
    grew = {e: v - before[e] for e, v in _search_rows().items()}
    table, counts = low.host_execute()
    assert got == _id_rows(table) and got
    assert low._last_counts == counts
    assert set(low.order_names) <= {"pos", "pso"}
    spec, _args = low.build()
    accessors = [a for lv in spec.root.levels for a in lv.accessors]
    base_rows = low._seg_rows[0][0]
    for a in accessors:
        assert a.lead >= 1 and a.key_srcs[0][0] == "u"
        assert 0 < a.window < base_rows
        assert a.window == de._round_cap(a.window)
    searched = [(n, extent) for n, _p, _k, extent in low._range_searches()]
    assert {extent for _n, extent in searched} == {"window", "delta"}
    assert grew == {
        "window": sum(n for n, extent in searched if extent == "window"),
        "order": 0,
    }
    # the lowered tree carries no width: the template's, as ScanSpec.cap is
    assert all(a.window == 0 for lv in low.root.levels for a in lv.accessors)


def test_a_window_is_as_wide_as_its_hottest_key(monkeypatch):
    """``(?x a ex:A)`` leads with predicate and class: its window holds the
    hottest class's rows, whichever class the instance names, so two
    instances share one assembled spec; ``(?x ex:p1 ?y)`` leads with the
    predicate alone: its window holds every row under it."""
    from kolibrie_tpu.optimizer import device_engine as de
    from kolibrie_tpu.optimizer.stats import hottest_key_rows

    monkeypatch.setenv("KOLIBRIE_WCOJ", "force")
    db = _typed_db()
    lows = [_at_capacity(db, PREFIX + SHAPES["q9"].replace("@A@", a), LOOP_CAP)
            for a in ("A", "A2")]
    specs = [low.build()[0] for low in lows]
    assert specs[0] == specs[1] and lows[0].u_params != lows[1].u_params
    rdf_type = db.dictionary.lookup(RDF_TYPE.strip("<>"))
    p1 = db.dictionary.lookup(EX + "p1")
    wide = {}
    for lv in specs[0].root.levels:
        for a in lv.accessors:
            named = lows[0].u_params[a.lead_predicate]
            wide.setdefault((named, a.lead), set()).add(a.window)
    assert wide[rdf_type, 2] == {de._round_cap(hottest_key_rows(db, rdf_type, "o"))}
    assert wide[p1, 1] == {de._round_cap(hottest_key_rows(db, p1, "p"))}


def test_an_unknown_constant_selects_the_padding_and_no_row(monkeypatch):
    """A class the dictionary does not know rides as the sentinel: the
    accessor's window starts at the padding block, ``sent`` zeroes its
    counts, and the answer is the twin's: empty, every level 0."""
    monkeypatch.setenv("KOLIBRIE_WCOJ", "force")
    db = _typed_db()
    low = _at_capacity(
        db, PREFIX + SHAPES["q2"].replace("@A@", "NoSuchClass"), LOOP_CAP)
    assert SENT32 in low.u_params
    assert low._template_window_caps()  # the windows are there all the same
    assert _id_rows(low.execute()) == []
    assert low._last_counts == low.host_execute()[1]


def test_a_variable_predicate_keeps_the_whole_order(monkeypatch):
    """A triangle that names no predicate has no constant to lead with: no
    accessor carries a window, the searches run over the orders as they did
    and the rows counter says so."""
    monkeypatch.setenv("KOLIBRIE_WCOJ", "force")
    db = _typed_db()
    low = _lower(db, PREFIX + "SELECT ?x ?y ?z WHERE "
                 "{ ?x ?p ?y . ?y ?p ?z . ?z ?p ?x }")
    before = _search_rows()
    assert _id_rows(low.execute()) == _id_rows(low.host_execute()[0])
    grew = {e: v - before[e] for e, v in _search_rows().items()}
    assert set(low._window_caps.values()) == {0}
    assert grew["window"] == 0 and grew["order"] > 0
    assert low.cap_key[2:] == ((),)  # and nothing of it in the capacities' key


def test_a_base_that_outgrows_a_window_is_compiled_for_the_new_one(monkeypatch):
    """The overflow path.  A window is computed at every build from the
    frozen base it will search (``_template_window_caps`` beside
    ``_template_scan_caps``), so a compaction that puts more rows under a
    predicate than the window the plan last ran with moves ``base_version``
    and the next dispatch of the SAME lowered plan assembles a wider window:
    one more executable, never a search of a window cut short."""
    from kolibrie_tpu.optimizer import device_engine as de

    monkeypatch.setenv("KOLIBRIE_WCOJ", "force")
    db = _typed_db()
    sparql = PREFIX + SHAPES["q9"].replace("@A@", "A")
    low = _at_capacity(db, sparql, LOOP_CAP)
    assert _id_rows(low.execute()) == _id_rows(low.host_execute()[0])
    was = dict(low._window_caps)
    version = db.store.base_version
    programs = de.device_compile_stats()["run_plan"]
    # 700 more ex:p1 rows, among them triangles' closing edges, folded into
    # the base: p1's rows no longer fit the 512 its window had
    db.store.delta_threshold = 64
    db.parse_ntriples("\n".join(
        f"<{EX}n{a % 60}> <{EX}p1> <{EX}n{(a * 11 + a // 60) % 60}> ."
        for a in range(700)))
    db.store.compact()
    assert db.store.base_version != version
    assert len(db.store.delta_order("spo")) == 0
    got = _id_rows(low.execute())
    grown = [k for k, w in low._window_caps.items() if w > was[k]]
    assert grown  # (a window follows its base: the tombstones' went with them)
    p1 = db.dictionary.lookup(EX + "p1")
    under_p1 = int((db.store.base_order("pso").c0 == p1).sum())
    assert under_p1 > max(was.values())
    assert max(low._window_caps.values()) >= under_p1
    assert de.device_compile_stats()["run_plan"] == programs + 1
    assert got == _id_rows(low.host_execute()[0])
    assert len(got) == len(_rows(db, sparql, "host")) and got


# --------------------------- a level's slot -> row map without a search (ISSUE 51)
#
# ``expand`` asks, for each of a level's ``cap`` output slots, which probe row
# it expands: ``searchsorted(cumsum(cnt), arange(cap), "right")``.
# ``ops/wcoj.py`` ``slot_rows`` answers with one scatter-add and one prefix
# count; the numpy twin keeps ``np.searchsorted`` and is the reference here.

INT32_MAX = 2**31 - 1


def _counts(case):
    """``(cnt, cap)`` of one case: int64 counts, each within int32."""
    rng = np.random.default_rng(51)
    if case == "all_zero":
        return np.zeros(37, np.int64), 64
    if case == "one_row_holds_everything":
        c = np.zeros(29, np.int64)
        c[11] = 50
        return c, 64
    if case == "zero_rows_at_the_start_in_runs_and_at_the_end":
        return np.array([0, 0, 0, 4, 0, 0, 1, 1, 0, 9, 0, 0, 0, 2, 0, 0]), 32
    if case == "total_is_cap":
        return np.array([3, 0, 5, 8, 0, 16]), 32
    if case == "total_past_cap":
        return np.array([3, 0, 5, 8, 0, 16, 40, 0, 7]), 32
    if case == "total_past_cap_in_the_first_row":
        return np.array([100, 1, 0, 2]), 32
    if case == "cum_wraps_once":
        # int32 totals: 5, 7, negative from the third row on
        return np.array([5, 2, INT32_MAX, 3, 0, 11]), 64
    if case == "p_is_one":
        return np.array([19]), 48
    if case == "p_is_one_and_empty":
        return np.array([0]), 48
    if case == "more_rows_than_slots":
        c = (rng.random(700) < 0.05).astype(np.int64) * rng.integers(1, 4, 700)
        return c, 96
    if case == "fewer_rows_than_slots":
        return rng.integers(0, 40, 50), 2048
    if case == "summed_in_blocks":  # cap + 1 and P past four blocks of 1,024
        c = rng.geometric(0.4, 5000).astype(np.int64)
        c[rng.random(5000) < 0.4] = 0
        c[[17, 4000]] = 2500
        return c, 16384
    if case == "summed_in_blocks_total_past_cap":
        return rng.integers(0, 9, 6000), 8192
    raise AssertionError(case)


SLOT_CASES = [
    "all_zero", "one_row_holds_everything",
    "zero_rows_at_the_start_in_runs_and_at_the_end", "total_is_cap",
    "total_past_cap", "total_past_cap_in_the_first_row", "cum_wraps_once",
    "p_is_one", "p_is_one_and_empty", "more_rows_than_slots",
    "fewer_rows_than_slots", "summed_in_blocks", "summed_in_blocks_total_past_cap",
]


@pytest.mark.parametrize("x64", [False, True], ids=["x32", "x64"])
@pytest.mark.parametrize("case", SLOT_CASES)
def test_slot_rows_is_the_search_of_the_running_total(case, x64):
    """Every slot under ``cap`` names the row ``np.searchsorted`` names over
    the true (unwrapped) running total, whatever lies past ``cap``; an
    int32 total that wrapped faults nothing."""
    import jax
    import jax.numpy as jnp

    from kolibrie_tpu.ops.wcoj import slot_rows

    cnt, cap = _counts(case)
    assert cnt.max(initial=0) <= INT32_MAX
    true_cum = np.cumsum(cnt.astype(np.int64))
    want = np.searchsorted(true_cum, np.arange(cap), side="right")
    with jax.enable_x64(x64):
        cum = jnp.cumsum(jnp.asarray(cnt.astype(np.int32)))
        assert cum.dtype == jnp.int32
        if case == "cum_wraps_once":
            assert int(cum[2]) < 0 and int(true_cum[-1]) > cap
        got = jax.jit(slot_rows, static_argnums=1)(cum, cap)
        assert got.dtype == jnp.int32 and got.shape == (cap,)
        if true_cum[-1] <= INT32_MAX:  # the parent's own expression agrees
            loop = jnp.searchsorted(cum, jnp.arange(cap, dtype=jnp.int32), side="right")
            assert np.array_equal(np.asarray(loop), want)
    assert np.array_equal(np.asarray(got), want)
    # what ``expand`` reads next: slots under the total lie inside their row
    total = min(int(true_cum[-1]), cap)
    rows = np.asarray(got)[:total]
    assert (cnt[rows] > 0).all()
    assert (np.arange(total) < true_cum[rows]).all()
    assert (np.arange(total) >= true_cum[rows] - cnt[rows]).all()


@pytest.mark.parametrize("width", [1, 600, 4096, 9000])
def test_the_running_total_in_blocks_wraps_as_the_flat_one(width):
    """``expand`` takes ``cum`` from the blocked prefix count (a flat
    ``cumsum`` of 524,288 counts is 7 s of the TPU compiler's time, the
    blocked one 0.4): int32 addition wraps the same in any grouping, so
    the array is the flat one's bit for bit, past int32 too."""
    import jax
    import jax.numpy as jnp

    from kolibrie_tpu.ops.prefix import prefix_count

    rng = np.random.default_rng(width)
    cnt = rng.integers(0, 2**22, width).astype(np.int32)
    with jax.enable_x64(True):
        got = jax.jit(prefix_count)(jnp.asarray(cnt))
        flat = jnp.cumsum(jnp.asarray(cnt))
        assert got.dtype == flat.dtype == jnp.int32
    assert np.array_equal(np.asarray(got), np.asarray(flat))
    assert np.array_equal(np.asarray(got),
                          np.cumsum(cnt.astype(np.int64)).astype(np.int32))
    assert width < 4096 or int(np.cumsum(cnt.astype(np.int64))[-1]) > INT32_MAX


def _plan_jaxpr(low):
    import jax

    from kolibrie_tpu.optimizer import device_engine as de

    spec, args = low.build()
    run = de._run_plan.__wrapped__  # the jit keeps its first trace of a spec
    with jax.enable_x64(True):
        return jax.make_jaxpr(lambda *a: run(spec, False, *a))(*args).jaxpr


def _searching_slot_rows(cum, cap):
    """The parent's form of ``slot_rows``: a binary search a slot."""
    import jax.numpy as jnp

    slot = jnp.arange(cap, dtype=jnp.int32)
    return jnp.searchsorted(cum, slot, side="right").astype(jnp.int32)


@pytest.mark.parametrize("side", ["loop", "sorted"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_expand_holds_no_loop_and_no_sort(shape, side, monkeypatch):
    """The traced ``expand`` scope of every level is loop-free and
    sort-free, and the plan holds one ``while`` a level fewer than with the
    search a slot in its place: the other scopes are as they were."""
    from kolibrie_tpu.ops import wcoj

    monkeypatch.setenv("KOLIBRIE_WCOJ", "force")
    db = _typed_db()
    low = _at_capacity(db, PREFIX + SHAPES[shape].replace("@A@", "A"), SIDE_CAP[side])
    levels = len(low.root.levels)
    assert levels == 3

    def prims(jaxpr, scope):
        # a loop of known trips is traced as ``scan`` and lowered as ``while``
        return ["while" if e.primitive.name == "scan" else e.primitive.name
                for e in _scoped_eqns(jaxpr, scope)]

    now = _plan_jaxpr(low)
    in_expand = prims(now, "expand")
    assert "scatter-add" in in_expand and "gather" in in_expand
    assert not {"while", "sort"} & set(in_expand)
    monkeypatch.setattr(wcoj, "slot_rows", _searching_slot_rows)
    before = _plan_jaxpr(low)
    assert prims(before, "expand").count("while") == levels  # the walk sees one
    whole = "wcoj0"
    assert (prims(before, whole).count("while") - prims(now, whole).count("while")
            == levels)
    for scope in ("probe", "live", "dedup"):
        assert prims(before, scope) == prims(now, scope)
    assert prims(before, whole).count("sort") == prims(now, whole).count("sort")
