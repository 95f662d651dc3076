"""An empty delta tier is not searched (ISSUE 31): where an order's delta
segment holds no row and no tombstone, the plan body's scans and WCOJ probes
read the base alone, on a traced operand (``tiers``), so

- the rows are the numpy twin's with an empty delta, with a live one and
  after the delta folded back into the base;
- ``kolibrie_device_scan_tier_total`` says which branch each scan and
  accessor took;
- a write flips a scalar and compiles nothing: one executable a template;
- the group dispatch keeps a conditional: its member loop runs the solo
  body, and the predicate is a store operand.
"""

import numpy as np
import pytest

from kolibrie_tpu.core.triple import Triple
from kolibrie_tpu.optimizer import device_engine as de
from kolibrie_tpu.query.sparql_database import SparqlDatabase
from kolibrie_tpu.query.template import _SCAN_TIER

EX = "http://example.org/"
PREFIX = f"PREFIX ex: <{EX}>\n"
THRESHOLD = 48

# name -> (query, what the lowered tree must hold for the case to mean
# what its name says)
TEMPLATES = {
    "join2": (
        "SELECT ?a ?c WHERE { ?a ex:p1 ?b . ?b ex:p2 ?c }",
        lambda n: _count(n, de.ScanSpec) == 2,
    ),
    "join3": (
        "SELECT ?a ?d WHERE { ?a ex:p1 ?b . ?b ex:p2 ?c . ?c ex:p3 ?d }",
        lambda n: _count(n, de.ScanSpec) == 3,
    ),
    "eq_pairs": (
        "SELECT ?a ?c WHERE { ?a ex:p1 ?a . ?a ex:p2 ?c }",
        lambda n: any(s.eq_pairs for s in _nodes(n, de.ScanSpec)),
    ),
    "rsorted": (
        "SELECT ?b ?c WHERE { ex:n1 ex:p1 ?b . ?b ex:p2 ?c }",
        lambda n: any(j.rsorted for j in _nodes(n, de.JoinSpec)),
    ),
    "triangle": (
        "SELECT ?x ?y ?z WHERE { ?x ex:p1 ?y . ?y ex:p2 ?z . ?z ex:p3 ?x }",
        lambda n: _count(n, de.WcojSpec) == 1,
    ),
}


def _nodes(node, cls):
    out = [node] if isinstance(node, cls) else []
    for attr in ("left", "right", "child"):
        if hasattr(node, attr):
            out += _nodes(getattr(node, attr), cls)
    for ch in getattr(node, "children", ()):
        out += _nodes(ch, cls)
    return out


def _count(node, cls) -> int:
    return len(_nodes(node, cls))


def _edge(a, p, b) -> str:
    return f"<{EX}n{a}> <{EX}{p}> <{EX}n{b}> ."


def _graph_db(seed=11, n_nodes=14, n_edges=420) -> SparqlDatabase:
    rng = np.random.default_rng(seed)
    lines = {_edge(k, "p1", k) for k in range(0, n_nodes, 3)}  # ?a p1 ?a rows
    while len(lines) < n_edges:
        a, b = rng.integers(0, n_nodes, 2)
        lines.add(_edge(a, ("p1", "p2", "p3")[int(rng.integers(0, 3))], b))
    db = SparqlDatabase()
    db.store.delta_threshold = THRESHOLD
    db.parse_ntriples("\n".join(sorted(lines)))
    db.execution_mode = "device"
    return db


def _lower(db, sparql):
    from kolibrie_tpu.optimizer.engine import resolve_pattern
    from kolibrie_tpu.optimizer.planner import Streamertail, build_logical_plan
    from kolibrie_tpu.query.parser import parse_sparql_query

    db.register_prefixes_from_query(sparql)
    w = parse_sparql_query(sparql, db.prefixes).where
    resolved = [resolve_pattern(db, p) for p in w.patterns]
    logical = build_logical_plan(resolved, list(w.filters), [], w.values)
    plan = Streamertail(db.get_or_build_stats()).find_best_plan(logical)
    return de.lower_plan(db, plan)


def _rows(table) -> list:
    names = sorted(table)
    return sorted(zip(*(table[v].tolist() for v in names)))


def _tiers() -> tuple:
    return (
        _SCAN_TIER.labels("base_only").value,
        _SCAN_TIER.labels("two_tier").value,
    )


def _run(db, sparql, check=None):
    """Device rows, checked against the numpy twin's; the counter's growth
    ``(base_only, two_tier)`` a dispatch (a capacity retry is a dispatch
    more); the sites of the plan."""
    low = _lower(db, sparql)
    if check is not None:
        assert check(low.root), low.root
    t0 = _tiers()
    got = _rows(low.execute())
    t1 = _tiers()
    want = _rows(low.host_execute()[0])
    assert got == want
    sites = len(low._tier_sites)
    dispatches, rest = divmod(t1[0] - t0[0] + t1[1] - t0[1], sites)
    assert dispatches >= 1 and rest == 0
    grew = ((t1[0] - t0[0]) / dispatches, (t1[1] - t0[1]) / dispatches)
    return got, grew, sites


def _write_a_little(db) -> None:
    """One insert and one delete of a base row: an incremental compaction,
    so the delta tier holds a row and a tombstone under the threshold."""
    bv = db.store.base_version
    s, p, o = db.store.columns()
    victim = int(np.flatnonzero(p == db.encode_term_str(f"<{EX}p2>"))[0])
    db.delete_triple(Triple(int(s[victim]), int(p[victim]), int(o[victim])))
    db.parse_ntriples("\n".join([_edge(2, "p1", 5), _edge(5, "p2", 900),
                                 _edge(900, "p3", 2), _edge(6, "p1", 6)]))
    assert db.store.base_version == bv
    assert len(db.store.delta_order("spo")) > 0
    assert len(db.store.delta_del_positions("spo")) == 1


@pytest.mark.parametrize("name", sorted(TEMPLATES))
def test_tiers_follow_the_delta_and_compile_nothing(name, monkeypatch):
    monkeypatch.setenv("KOLIBRIE_WCOJ", "force" if name == "triangle" else "off")
    sparql = PREFIX + TEMPLATES[name][0]
    db = _graph_db()
    # (a) nothing in the delta: every site reads the base alone
    rows_a, grew, sites = _run(db, sparql, TEMPLATES[name][1])
    assert rows_a and sites >= 2
    assert grew == (sites, 0)
    compiled = dict(de.device_compile_stats())
    # (b) a live delta under the threshold: the same executable merges
    _write_a_little(db)
    rows_b, grew, sites_b = _run(db, sparql)
    assert sites_b == sites and grew == (0, sites)
    assert rows_b != rows_a
    assert dict(de.device_compile_stats()) == compiled
    # (c) past the threshold the delta folds into the base: base alone again
    bv = db.store.base_version
    for k in range(THRESHOLD // 8 + 1):
        if db.store.base_version != bv:
            break
        db.parse_ntriples("\n".join(
            _edge(1000 + 8 * k + i, "p1", 2000 + 8 * k + i) for i in range(8)))
    assert db.store.base_version != bv
    assert len(db.store.delta_order("spo")) == 0
    _rows_c, grew, sites_c = _run(db, sparql)
    assert grew == (sites_c, 0)


@pytest.mark.parametrize(
    "n, cap, lo",
    [
        (1024, 256, 0),     # the window at the column's head
        (1024, 256, 300),   # inside it
        (1024, 256, 768),   # ending at the padded end
        (1024, 256, 900),   # running past it: the slice is clamped and rotated
        (1024, 1024, 512),  # as wide as the column
        (128, 512, 40),     # wider than the column (a small store)
    ],
)
def test_base_window_is_the_clipped_gather(n, cap, lo):
    """The base-only scan's ``dynamic_slice`` gives the rows the two-tier
    branch gathers, wherever the window lies; what lies past the column's
    end is the caller's to mask."""
    import jax.numpy as jnp

    col = jnp.arange(1, n + 1, dtype=jnp.uint32)
    got = np.asarray(de._base_window(col, jnp.int32(lo), cap))
    inside = min(cap, n - lo)
    assert got.shape == (cap,)
    assert np.array_equal(got[:inside], np.arange(lo + 1, lo + 1 + inside))


def _stacked(db, variants):
    lows = [_lower(db, q) for q in variants]
    built = [lp.build() for lp in lows]
    spec, (orders, _sc, tiers, masks, values, numf, quoted, _pp) = built[0]
    assert all(s == spec for s, _ in built)
    scal = np.stack([np.asarray(lp._scan_ranges_np) for lp in lows])
    params = (
        np.stack([np.asarray(lp.u_params or [0], np.uint32) for lp in lows]),
        np.stack([np.asarray(lp.f_params or [0.0], np.float64) for lp in lows]),
    )
    live = np.int32(len(lows))
    return lows, spec, (
        orders, scal, live, tiers, masks, values, numf, quoted, params
    )


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in eqn.params.values():
            for item in sub if isinstance(sub, (list, tuple)) else (sub,):
                inner = getattr(item, "jaxpr", item)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


@pytest.mark.parametrize("delta", ["empty", "live"])
def test_the_batch_keeps_a_conditional(delta):
    """The group's program is a loop over its live members whose body is
    the solo plan body: the scan's ``cond`` stays a ``cond`` on a scalar
    store operand, one member wide, and the batch's rows are the single
    dispatches'."""
    import jax

    db = _graph_db()
    if delta == "live":
        _write_a_little(db)
    variants = [
        PREFIX + f"SELECT ?b ?c WHERE {{ ex:n{k} ex:p1 ?b . ?b ex:p2 ?c }}"
        for k in (1, 2)
    ]
    lows, spec, args = _stacked(db, variants)
    with jax.enable_x64(True):
        jaxpr = jax.make_jaxpr(
            lambda *a: de._run_plan_batch(spec, False, *a)
        )(*args)
    eqns = list(_eqns(jaxpr.jaxpr))
    assert [e for e in eqns if e.primitive.name == "while"]
    conds = [e for e in eqns if e.primitive.name == "cond"]
    n_scans = _count(lows[0].root, de.ScanSpec)
    assert n_scans == 2 and len(conds) == n_scans
    for e in conds:
        # a scalar predicate, and each branch one member wide: nothing in
        # the loop's body carries the group's axis
        assert e.invars[0].aval.shape == ()
        assert all(v.aval.ndim <= 1 for v in e.outvars)
    cap = max(s.cap for s in _nodes(lows[0].root, de.ScanSpec))
    assert not [
        e for e in eqns
        if e.primitive.name == "select_n"
        and e.invars[0].aval.shape == ()
        and e.outvars[0].aval.shape[-1:] == (cap,)
    ]
    t0 = _tiers()
    tables = de.execute_plan_batch([_lower(db, q) for q in variants])
    t1 = _tiers()
    grew = (t1[0] - t0[0], t1[1] - t0[1])
    sites = len(variants) * n_scans
    assert grew == ((sites, 0) if delta == "empty" else (0, sites))
    for q, table in zip(variants, tables):
        single = _lower(db, q)
        assert _rows(table) == _rows(single.execute()) == _rows(
            single.host_execute()[0])


@pytest.mark.parametrize("seed", [3, 5, 8, 13])
def test_a_scan_merges_many_writes_in_key_order(seed):
    """A live delta of many rows and tombstones, some at the window's edges:
    a scan's slots hold the live rows of base and delta in the order's key
    order with the live ones a prefix (what a merge join to its right relies
    on), each slot finding its source row (ISSUE 40: a gather, no scatter)."""
    rng = np.random.default_rng(seed)
    db = _graph_db(seed=seed, n_nodes=11, n_edges=300)
    db.store.delta_threshold = 60  # the same 64 delta slots, room for 41 writes
    bv = db.store.base_version
    s, p, o = (c.copy() for c in db.store.columns())
    p1 = db.encode_term_str(f"<{EX}p1>")
    mine = np.flatnonzero(p == p1)
    # the first and the last row of p1's run, and some between
    victims = {int(mine[0]), int(mine[-1]), *rng.choice(mine, 9).tolist()}
    for v in sorted(victims):
        db.delete_triple(Triple(int(s[v]), int(p[v]), int(o[v])))
    for _batch in range(2):  # each under a sixteenth of the store: incremental
        db.parse_ntriples("\n".join(
            _edge(int(a), pred, int(b))
            for a, b, pred in zip(rng.integers(0, 40, 15), rng.integers(0, 40, 15),
                                  rng.choice(["p1", "p2"], 15))))
        db.store.compact()
    assert db.store.base_version == bv
    assert len(db.store.delta_del_positions("spo")) >= 9
    for sparql in ("SELECT ?a ?b WHERE { ?a ex:p1 ?b }",
                   "SELECT ?b WHERE { ex:n3 ex:p1 ?b }",
                   "SELECT ?a WHERE { ?a ex:p1 ex:n4 }"):
        low = _lower(db, PREFIX + sparql)
        assert isinstance(low.root, de.ScanSpec)
        cols, valid, _counts, _stats = low.run()
        valid = np.asarray(valid)
        n = int(valid.sum())
        assert valid[:n].all()
        got = [np.asarray(c)[:n].tolist() for c in cols]
        want = low.host_execute()[0]
        names = list(low.out_vars)
        assert sorted(zip(*got)) == sorted(zip(*(want[v].tolist() for v in names)))
        (k0, k1) = low.root.key_pos
        key = [(row[0],) + tuple(row[1:]) for row in zip(*got)]
        by_pos = {pos: i for i, (_v, pos) in enumerate(low.root.out_vars)}
        if k0 in by_pos:  # the merge key's first column is an output: sorted
            first = [row[by_pos[k0]] for row in key]
            assert first == sorted(first)
    _run(db, PREFIX + "SELECT ?a ?c WHERE { ?a ex:p1 ?b . ?b ex:p2 ?c }")
    _run(db, PREFIX + "SELECT ?b ?c WHERE { ex:n1 ex:p1 ?b . ?b ex:p2 ?c }")
