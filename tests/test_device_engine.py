"""Agreement tests: device (jitted XLA) query path vs host numpy engine.

The reference's most valuable test pattern is agreement between a naive and
an optimized path (SURVEY §4); here the host ID-space engine
(``optimizer/engine.py``) is the oracle for the device plan interpreter
(``optimizer/device_engine.py``).
"""

import numpy as np
import pytest

from kolibrie_tpu.optimizer.device_engine import (
    Unsupported,
    lower_plan,
    try_device_execute,
)
from kolibrie_tpu.query.executor import execute_query_volcano, execute_select
from kolibrie_tpu.query.parser import parse_sparql_query
from kolibrie_tpu.query.sparql_database import SparqlDatabase

PREFIXES = """PREFIX ex: <http://example.org/>
PREFIX foaf: <http://xmlns.com/foaf/0.1/>
"""


def employee_db(n=500) -> SparqlDatabase:
    db = SparqlDatabase()
    lines = []
    for i in range(n):
        e = f"<http://example.org/e{i}>"
        lines.append(
            f"{e} <http://xmlns.com/foaf/0.1/workplaceHomepage> "
            f"<http://company{i % 7}.example/> ."
        )
        lines.append(
            f'{e} <http://example.org/salary> "{30000 + (i % 50) * 1000}" .'
        )
        lines.append(f'{e} <http://example.org/dept> "dept{i % 5}" .')
        if i % 3 == 0:
            lines.append(
                f"{e} <http://example.org/knows> <http://example.org/e{(i + 1) % n}> ."
            )
    db.parse_ntriples("\n".join(lines))
    db.execution_mode = "device"
    return db


def run_both(db, query):
    dev_rows = execute_query_volcano(query, db)
    db.execution_mode = "host"
    host_rows = execute_query_volcano(query, db)
    db.execution_mode = "device"
    return dev_rows, host_rows


def test_two_pattern_join_agreement():
    db = employee_db()
    q = PREFIXES + """
    SELECT ?e ?w ?s WHERE {
        ?e foaf:workplaceHomepage ?w .
        ?e ex:salary ?s
    }"""
    dev, host = run_both(db, q)
    assert len(dev) == 500
    assert sorted(dev) == sorted(host)


def test_star_join_agreement():
    db = employee_db()
    q = PREFIXES + """
    SELECT ?e ?w ?s ?d WHERE {
        ?e foaf:workplaceHomepage ?w .
        ?e ex:salary ?s .
        ?e ex:dept ?d
    }"""
    dev, host = run_both(db, q)
    assert sorted(dev) == sorted(host)
    assert len(dev) == 500


def test_numeric_filter_agreement():
    db = employee_db()
    q = PREFIXES + """
    SELECT ?e ?s WHERE {
        ?e ex:salary ?s .
        FILTER(?s > 60000)
    }"""
    dev, host = run_both(db, q)
    assert sorted(dev) == sorted(host)
    assert 0 < len(dev) < 500


def test_compound_filter_agreement():
    db = employee_db()
    q = PREFIXES + """
    SELECT ?e ?s ?d WHERE {
        ?e ex:salary ?s .
        ?e ex:dept ?d .
        FILTER(?s >= 40000 && (?s < 70000 || ?d = "dept1"))
    }"""
    dev, host = run_both(db, q)
    assert sorted(dev) == sorted(host)


def test_iri_equality_filter():
    db = employee_db()
    q = PREFIXES + """
    SELECT ?e ?w WHERE {
        ?e foaf:workplaceHomepage ?w .
        FILTER(?w = <http://company3.example/>)
    }"""
    dev, host = run_both(db, q)
    assert sorted(dev) == sorted(host)
    assert len(dev) > 0


def test_two_var_join_key():
    # second join shares two variables with the accumulated table
    db = employee_db()
    q = PREFIXES + """
    SELECT ?a ?b ?w WHERE {
        ?a ex:knows ?b .
        ?a foaf:workplaceHomepage ?w .
        ?b foaf:workplaceHomepage ?w
    }"""
    dev, host = run_both(db, q)
    assert sorted(dev) == sorted(host)


def test_values_clause():
    db = employee_db()
    q = PREFIXES + """
    SELECT ?e ?d WHERE {
        ?e ex:dept ?d .
        VALUES ?d { "dept1" "dept3" }
    }"""
    dev, host = run_both(db, q)
    assert sorted(dev) == sorted(host)
    assert len(dev) == 200


def test_repeated_variable_pattern():
    db = SparqlDatabase()
    db.parse_ntriples(
        "\n".join(
            [
                "<http://e/a> <http://e/p> <http://e/a> .",
                "<http://e/a> <http://e/p> <http://e/b> .",
                "<http://e/b> <http://e/p> <http://e/b> .",
                "<http://e/c> <http://e/q> <http://e/c> .",
            ]
        )
    )
    db.execution_mode = "device"
    q = "SELECT ?x WHERE { ?x <http://e/p> ?x }"
    dev, host = run_both(db, q)
    assert sorted(dev) == sorted(host)
    assert len(dev) == 2


def test_unsupported_falls_back(monkeypatch):
    """BIND in the plan → device lowering refuses → host path answers."""
    db = employee_db()
    q = PREFIXES + """
    SELECT ?e ?s ?double WHERE {
        ?e ex:salary ?s .
        BIND((?s + ?s) AS ?double)
    }"""
    dev, host = run_both(db, q)
    assert sorted(dev) == sorted(host)


def test_group_by_over_device_table():
    db = employee_db()
    q = PREFIXES + """
    SELECT ?d (COUNT(?e) AS ?n) (AVG(?s) AS ?avg) WHERE {
        ?e ex:dept ?d .
        ?e ex:salary ?s
    } GROUP BY ?d"""
    dev, host = run_both(db, q)
    assert sorted(dev) == sorted(host)
    assert len(dev) == 5


def test_capacity_doubling_converges():
    """Start with a deliberately tiny capacity estimate and confirm the
    overflow/retry protocol still yields exact results."""
    db = employee_db()
    q = parse_sparql_query(
        PREFIXES
        + """
    SELECT ?e ?w ?s WHERE {
        ?e foaf:workplaceHomepage ?w .
        ?e ex:salary ?s
    }"""
    )
    from kolibrie_tpu.optimizer.engine import resolve_pattern
    from kolibrie_tpu.optimizer.planner import Streamertail, build_logical_plan

    resolved = [resolve_pattern(db, p) for p in q.where.patterns]
    plan = Streamertail(db.get_or_build_stats()).find_best_plan(
        build_logical_plan(resolved, [], [], None)
    )
    lowered = lower_plan(db, plan)
    lowered.build()
    # sabotage the cap cache with a too-small value
    from kolibrie_tpu.optimizer import caps

    caps.of(db).joins.start(lowered.cap_key, [128] * lowered.join_count)
    lowered2 = lower_plan(db, plan)
    table = lowered2.execute()
    assert len(next(iter(table.values()))) == 500


def _request_lowering(db, sparql):
    """The LoweredPlan a request for ``sparql`` runs: the one the executor's
    plan cache keeps for the template on this store state."""
    from kolibrie_tpu.query.executor import _plan_cache_entry

    _ent, slot = _plan_cache_entry(db, sparql)
    return slot["lowered"]


def _rows(db, low, sparql):
    """One dispatch of ``low`` read back as a request's rows are: counts
    validated, table pulled, ids decoded."""
    from kolibrie_tpu.query.executor import format_results

    table = low.to_table(*low.converge(low.run()))
    q = parse_sparql_query(sparql)
    return format_results(db, table, q, sort_rows=True)


def test_lowered_plan_run_converge_roundtrip():
    db = employee_db()
    q = PREFIXES + """
    SELECT ?e ?w ?s WHERE {
        ?e foaf:workplaceHomepage ?w .
        ?e ex:salary ?s .
        FILTER(?s > 50000)
    }"""
    dev, host = run_both(db, q)
    assert sorted(dev) == sorted(host)
    assert _rows(db, _request_lowering(db, q), q) == sorted(host)


def test_lowered_plan_mask_refresh_after_dict_growth():
    """New dictionary IDs after lowering must not clamp onto old mask entries
    — and join-capacity overflow after store growth must re-run, not
    truncate.  The template's plan slot survives a mutation under the delta
    threshold, so both requests run ONE lowered program."""
    db = employee_db()
    q = PREFIXES + "SELECT ?e ?s WHERE { ?e ex:salary ?s . FILTER(?s > 50000) }"
    rows1 = execute_query_volcano(q, db)
    low = _request_lowering(db, q)
    # a brand-new literal (new ID beyond the old mask) that passes the filter
    db.parse_ntriples(
        '<http://example.org/new> <http://example.org/salary> "123456" .'
    )
    rows2 = execute_query_volcano(q, db)
    assert _request_lowering(db, q) is low
    db.execution_mode = "host"
    host = execute_query_volcano(q, db)
    db.execution_mode = "device"
    assert sorted(rows2) == sorted(host)
    assert len(rows2) == len(rows1) + 1
    assert _rows(db, low, q) == sorted(host)


def test_store_mutation_between_executions():
    db = employee_db()
    q = PREFIXES + "SELECT ?e ?s WHERE { ?e ex:salary ?s . FILTER(?s > 75000) }"
    dev1, host1 = run_both(db, q)
    assert sorted(dev1) == sorted(host1)
    db.parse_ntriples(
        '<http://example.org/new> <http://example.org/salary> "99000" .'
    )
    dev2, host2 = run_both(db, q)
    assert sorted(dev2) == sorted(host2)
    assert len(dev2) == len(dev1) + 1


def test_device_aggregation_shapes():
    """The fused device GROUP BY path must agree with the host aggregation
    for every supported aggregate shape."""
    db = employee_db()
    queries = [
        # single group var, multiple aggregates
        PREFIXES + """
        SELECT ?d (COUNT(?e) AS ?n) (SUM(?s) AS ?sum) (MIN(?s) AS ?lo)
               (MAX(?s) AS ?hi) WHERE {
            ?e ex:dept ?d . ?e ex:salary ?s
        } GROUP BY ?d""",
        # two group vars
        PREFIXES + """
        SELECT ?d ?w (COUNT(?e) AS ?n) WHERE {
            ?e ex:dept ?d . ?e foaf:workplaceHomepage ?w
        } GROUP BY ?d ?w""",
        # aggregate with no GROUP BY (single group)
        PREFIXES + """
        SELECT (COUNT(?e) AS ?n) (AVG(?s) AS ?avg) WHERE {
            ?e ex:salary ?s
        }""",
        # COUNT(*) via bare COUNT
        PREFIXES + """
        SELECT ?d (COUNT(?e) AS ?n) WHERE { ?e ex:dept ?d } GROUP BY ?d""",
        # aggregation over a filtered join
        PREFIXES + """
        SELECT ?d (COUNT(?e) AS ?n) WHERE {
            ?e ex:dept ?d . ?e ex:salary ?s . FILTER(?s > 50000)
        } GROUP BY ?d""",
    ]
    for q in queries:
        dev, host = run_both(db, q)
        assert sorted(dev) == sorted(host), q


def test_device_aggregation_fused_path_used(monkeypatch):
    """Above the auto threshold the fused path must actually run (guard
    against silent fallback)."""
    import kolibrie_tpu.optimizer.device_engine as de

    db = employee_db()
    called = []
    orig = de.try_device_execute_aggregated

    def spy(db_, plan, q, lowered=None):
        out = orig(db_, plan, q, lowered=lowered)
        called.append(out is not None)
        return out

    monkeypatch.setattr(de, "try_device_execute_aggregated", spy)
    q = PREFIXES + """
    SELECT ?d (COUNT(?e) AS ?n) WHERE { ?e ex:dept ?d } GROUP BY ?d"""
    execute_query_volcano(q, db)
    assert called and called[0], "fused device aggregation did not run"


def test_device_aggregation_count_distinct():
    """COUNT(DISTINCT ?v) runs on device (per-(group,value) first-occurrence
    mask via a second key sort) and must match the host path exactly."""
    db = employee_db()
    q = PREFIXES + """
    SELECT ?d (COUNT(DISTINCT ?w) AS ?n) WHERE {
        ?e ex:dept ?d . ?e foaf:workplaceHomepage ?w
    } GROUP BY ?d"""
    dev, host = run_both(db, q)
    assert sorted(dev) == sorted(host)


def test_device_aggregation_three_group_vars():
    """>2 group variables ride as parallel sort operands (no packed-u64
    limit); agreement with the host path."""
    db = employee_db()
    q = PREFIXES + """
    SELECT ?d ?w ?s (COUNT(?e) AS ?n) WHERE {
        ?e ex:dept ?d . ?e foaf:workplaceHomepage ?w . ?e ex:salary ?s
    } GROUP BY ?d ?w ?s"""
    dev, host = run_both(db, q)
    assert len(dev) > 10
    assert sorted(dev) == sorted(host)


def test_device_aggregation_sample():
    """SAMPLE returns the group's first value in plan order on both paths;
    agreement is on the (group, decoded term) pairs being a valid sample
    (host picks its own first row, so compare against group membership)."""
    db = employee_db()
    q = PREFIXES + """
    SELECT ?d (SAMPLE(?w) AS ?any) WHERE {
        ?e ex:dept ?d . ?e foaf:workplaceHomepage ?w
    } GROUP BY ?d"""
    dev, host = run_both(db, q)
    assert len(dev) == len(host) == 5
    # membership check: each sampled value must belong to the group
    members = {}
    for row in execute_query_volcano(
        PREFIXES
        + "SELECT ?d ?w WHERE { ?e ex:dept ?d . ?e foaf:workplaceHomepage ?w }",
        db,
    ):
        members.setdefault(row[0], set()).add(row[1])
    for d, w in dev:
        assert w in members[d], (d, w)


def test_device_aggregation_infinite_literal():
    """A genuinely infinite numeric literal ("1e999" parses to +inf) must
    survive MIN/MAX on both paths — the empty-segment identity (±inf) is
    distinguished from real infinities by COUNT, not by value."""
    db = employee_db()
    db.parse_ntriples(
        '<http://example.org/e0> <http://example.org/salary> "1e999" .'
    )
    q = PREFIXES + """
    SELECT ?d (MAX(?s) AS ?m) WHERE {
        ?e ex:dept ?d . ?e ex:salary ?s
    } GROUP BY ?d"""
    dev, host = run_both(db, q)
    assert sorted(dev) == sorted(host)
    assert any("inf" in row[1] for row in dev), dev
    # MIN is unaffected by +inf but must agree too
    qmin = q.replace("MAX", "MIN")
    dev, host = run_both(db, qmin)
    assert sorted(dev) == sorted(host)


def test_pallas_join_path_agreement(monkeypatch):
    """Forced Pallas merge-join tile kernel (interpret mode off-TPU) must
    agree with the host engine AND with the XLA join formulation on the
    identical plan — the engine's production join on real TPU hardware."""
    monkeypatch.setenv("KOLIBRIE_PALLAS", "force")
    db = employee_db(200)
    q = PREFIXES + """
    SELECT ?e ?w ?s WHERE {
        ?e foaf:workplaceHomepage ?w .
        ?e ex:salary ?s
    }"""
    dev, host = run_both(db, q)
    assert len(dev) == 200
    assert sorted(dev) == sorted(host)
    # filtered variant: left side arrives with validity holes
    qf = PREFIXES + """
    SELECT ?e ?w ?s WHERE {
        ?e foaf:workplaceHomepage ?w .
        ?e ex:salary ?s .
        FILTER(?s > 45000)
    }"""
    dev, host = run_both(db, qf)
    assert sorted(dev) == sorted(host)
    monkeypatch.setenv("KOLIBRIE_PALLAS", "off")
    xla_rows = execute_query_volcano(qf, db)
    assert sorted(xla_rows) == sorted(dev)


def test_device_order_by_limit():
    """ORDER BY numeric key + LIMIT runs the device top-k path (O(limit)
    readback); rows agree with the host sort.  Unique keys make the
    ordering total, so agreement is exact row-for-row."""
    db = employee_db(97)
    # unique salaries: i * 1000
    db2 = SparqlDatabase()
    lines = []
    for i in range(97):
        e = f"<http://example.org/e{i}>"
        lines.append(f'{e} <http://example.org/salary> "{1000 * i}" .')
        lines.append(f'{e} <http://example.org/dept> "dept{i % 5}" .')
    db2.parse_ntriples("\n".join(lines))
    db2.execution_mode = "device"
    q = PREFIXES + """
    SELECT ?e ?s WHERE {
        ?e ex:salary ?s . ?e ex:dept ?d
    } ORDER BY DESC(?s) LIMIT 7"""
    dev, host = run_both(db2, q)
    assert len(dev) == 7
    assert dev == host
    # ascending + offset
    q2 = PREFIXES + """
    SELECT ?e ?s WHERE {
        ?e ex:salary ?s . ?e ex:dept ?d
    } ORDER BY ?s LIMIT 5 OFFSET 3"""
    dev2, host2 = run_both(db2, q2)
    assert dev2 == host2
    assert len(dev2) == 5


def test_device_order_by_string_key_falls_back():
    """A non-numeric sort key must take the host string-rank path and stay
    exact."""
    db = employee_db(60)
    q = PREFIXES + """
    SELECT ?e ?d WHERE {
        ?e ex:dept ?d . ?e ex:salary ?s
    } ORDER BY ?d ?e LIMIT 9"""
    dev, host = run_both(db, q)
    assert dev == host


def test_pallas_join_two_var_key_agreement(monkeypatch):
    """Two-variable join keys ride the Pallas kernel via a dense-rank
    prepass (u64 pack -> union rank -> u32 kernel); rows must equal the
    host engine and the XLA formulation.  The data makes the triangle
    genuinely match (same-org knows edges) AND contain non-matches
    (cross-org edges) so the agreement is non-vacuous both ways."""
    monkeypatch.setenv("KOLIBRIE_PALLAS", "force")
    db = SparqlDatabase()
    lines = []
    for i in range(150):
        e = f"<http://e/p{i}>"
        # same-org edge (orgs repeat every 9): matches unless the mod-150
        # wrap crosses an org boundary
        lines.append(f"{e} <http://e/knows> <http://e/p{(i + 9) % 150}> .")
        lines.append(f"{e} <http://e/org> <http://e/org{i % 9}> .")
        if i % 5 == 0:  # cross-org edge: must be filtered by the join
            lines.append(f"{e} <http://e/knows> <http://e/p{(i + 1) % 150}> .")
    db.parse_ntriples("\n".join(lines))
    db.execution_mode = "device"
    q = (
        "SELECT ?a ?b ?w WHERE { ?a <http://e/knows> ?b . "
        "?a <http://e/org> ?w . ?b <http://e/org> ?w }"
    )
    dev, host = run_both(db, q)
    assert len(dev) == 141  # 150 same-org edges minus 9 org-crossing wraps
    assert sorted(dev) == sorted(host)
    monkeypatch.setenv("KOLIBRIE_PALLAS", "off")
    assert sorted(execute_query_volcano(q, db)) == sorted(dev)


def test_device_query_fuzz():
    """Randomized BGP+FILTER queries over random data: the device engine
    (auto-routing, fallbacks included) must agree with the host engine on
    every query.  Seeded for reproducibility."""
    import random

    rng = random.Random(20260731)
    db = SparqlDatabase()
    lines = []
    preds = [f"<http://f.e/p{k}>" for k in range(5)]
    for i in range(400):
        s = f"<http://f.e/s{rng.randrange(80)}>"
        pr = rng.choice(preds)
        if rng.random() < 0.5:
            o = f"<http://f.e/s{rng.randrange(80)}>"
        else:
            o = f'"{rng.randrange(0, 5000)}"'
        lines.append(f"{s} {pr} {o} .")
    db.parse_ntriples("\n".join(lines))
    db.execution_mode = "device"

    vars_pool = ["?a", "?b", "?c", "?d"]
    for trial in range(30):
        n_pat = rng.randrange(1, 4)
        used = []
        pats = []
        for _ in range(n_pat):
            s = rng.choice(used) if used and rng.random() < 0.8 else rng.choice(vars_pool)
            o = rng.choice(vars_pool + [f"<http://f.e/s{rng.randrange(80)}>"])
            pr = rng.choice(preds)
            pats.append(f"{s} {pr} {o} .")
            for t in (s, o):
                if t.startswith("?") and t not in used:
                    used.append(t)
        filt = ""
        numeric_vars = [v for v in used]
        if used and rng.random() < 0.5:
            v = rng.choice(numeric_vars)
            op = rng.choice([">", "<", ">=", "<=", "=", "!="])
            filt = f"FILTER({v} {op} {rng.randrange(0, 5000)})"
        sel = " ".join(used) if used else "*"
        q = f"SELECT {sel} WHERE {{ {' '.join(pats)} {filt} }}"
        try:
            dev, host = run_both(db, q)
        except Exception as e:
            raise AssertionError(f"trial {trial}: {q!r} raised {e}") from e
        assert sorted(dev) == sorted(host), (trial, q, len(dev), len(host))


def test_fully_constant_pattern_present():
    """A fully-constant pattern that exists is a no-op guard — the rest of
    the BGP runs on device (round 4: hoisted host membership check, no
    fallback)."""
    db = employee_db()
    q = PREFIXES + """
    SELECT ?e ?s WHERE {
        ?e ex:salary ?s .
        <http://example.org/e0> ex:dept "dept0" .
    }"""
    dev, host = run_both(db, q)
    assert sorted(dev) == sorted(host)
    assert len(dev) == 500


def test_fully_constant_pattern_absent_empties_result():
    db = employee_db()
    q = PREFIXES + """
    SELECT ?e ?s WHERE {
        ?e ex:salary ?s .
        <http://example.org/e0> ex:dept "no-such-dept" .
    }"""
    dev, host = run_both(db, q)
    assert dev == host == []


def test_constant_pattern_lowers_without_fallback():
    from kolibrie_tpu.optimizer.device_engine import lower_plan
    from kolibrie_tpu.optimizer.engine import resolve_pattern
    from kolibrie_tpu.optimizer.planner import Streamertail, build_logical_plan
    from kolibrie_tpu.query.parser import parse_combined_query

    db = employee_db()
    q = PREFIXES + """
    SELECT ?e ?s WHERE {
        ?e ex:salary ?s .
        <http://example.org/e0> ex:dept "dept0" .
    }"""
    db.register_prefixes_from_query(q)
    cq = parse_combined_query(q, db.prefixes)
    resolved = [resolve_pattern(db, p) for p in cq.select.where.patterns]
    logical = build_logical_plan(resolved, [], [], None)
    plan = Streamertail(db.get_or_build_stats()).find_best_plan(logical)
    lowered = lower_plan(db, plan)  # must NOT raise Unsupported
    assert len(lowered.const_checks) == 1
    assert lowered.const_ok()
    table = lowered.execute()
    assert len(next(iter(table.values()))) == 500


def test_three_var_join_key_agreement():
    """{?s ?p ?o . ?o ?p ?s} shares THREE variables — the union dense-rank
    composition (round 4) runs it on device; host twin must agree."""
    db = SparqlDatabase()
    lines = []
    # 40 symmetric pairs + 120 asymmetric edges + noise predicates
    for i in range(40):
        lines.append(f"<http://g/a{i}> <http://g/sym> <http://g/b{i}> .")
        lines.append(f"<http://g/b{i}> <http://g/sym> <http://g/a{i}> .")
    for i in range(120):
        lines.append(f"<http://g/a{i}> <http://g/asym> <http://g/c{i}> .")
    db.parse_ntriples("\n".join(lines))
    db.execution_mode = "device"
    q = "SELECT ?s ?p ?o WHERE { ?s ?p ?o . ?o ?p ?s }"
    dev, host = run_both(db, q)
    assert sorted(dev) == sorted(host)
    assert len(dev) == 80  # both orientations of each symmetric pair


def test_three_var_join_pallas_agreement(monkeypatch):
    monkeypatch.setenv("KOLIBRIE_PALLAS", "force")
    db = SparqlDatabase()
    lines = []
    for i in range(12):
        lines.append(f"<http://g/a{i}> <http://g/sym> <http://g/b{i}> .")
        lines.append(f"<http://g/b{i}> <http://g/sym> <http://g/a{i}> .")
    db.parse_ntriples("\n".join(lines))
    db.execution_mode = "device"
    q = "SELECT ?s ?p ?o WHERE { ?s ?p ?o . ?o ?p ?s }"
    dev, host = run_both(db, q)
    assert sorted(dev) == sorted(host)
    assert len(dev) == 24


def test_constant_pattern_absent_with_order_limit():
    """The ORDER BY + LIMIT device path must honor a failed constant guard
    (review finding: it bypassed execute()'s guard and returned rows)."""
    db = employee_db()
    q = PREFIXES + """
    SELECT ?e ?s WHERE {
        ?e ex:salary ?s .
        <http://example.org/e0> ex:dept "no-such-dept" .
    } ORDER BY ?s LIMIT 5"""
    dev, host = run_both(db, q)
    assert dev == host == []


def _rdf_star_db() -> SparqlDatabase:
    db = SparqlDatabase()
    db.parse_turtle(
        """
    @prefix ex: <http://example.org/> .
    << ex:alice ex:age 30 >> ex:certainty "0.9" .
    << ex:bob ex:age 41 >> ex:certainty "0.5" .
    << ex:carol ex:likes ex:dave >> ex:certainty "0.8" .
    << ex:eve ex:likes ex:eve >> ex:certainty "0.7" .
    ex:alice ex:knows ex:bob .
    ex:dave ex:knows ex:carol .
    """
    )
    db.execution_mode = "device"
    return db


def test_quoted_pattern_scan_device_agreement():
    """Quoted patterns with inner variables lower to the synthetic-qid
    expansion (round 4): the quoted table gather must reproduce the host
    engine exactly."""
    db = _rdf_star_db()
    q = """PREFIX ex: <http://example.org/>
    SELECT ?s ?v ?c WHERE { << ?s ex:age ?v >> ex:certainty ?c }"""
    dev, host = run_both(db, q)
    assert len(host) == 2 and sorted(dev) == sorted(host)
    # inner constant at a different position
    q2 = """PREFIX ex: <http://example.org/>
    SELECT ?p ?c WHERE { << ex:alice ?p 30 >> ex:certainty ?c }"""
    dev2, host2 = run_both(db, q2)
    assert len(host2) == 1 and sorted(dev2) == sorted(host2)


def test_quoted_pattern_join_and_collision_agreement():
    """Inner variables join with outer patterns; a repeated inner variable
    (<< ?x likes ?x >>) becomes an equality check."""
    db = _rdf_star_db()
    q = """PREFIX ex: <http://example.org/>
    SELECT ?s ?o ?c WHERE {
        ?s ex:knows ?o . << ?s ex:age ?v >> ex:certainty ?c }"""
    dev, host = run_both(db, q)
    assert len(host) == 1 and sorted(dev) == sorted(host)
    q2 = """PREFIX ex: <http://example.org/>
    SELECT ?x ?c WHERE { << ?x ex:likes ?x >> ex:certainty ?c }"""
    dev2, host2 = run_both(db, q2)
    assert len(host2) == 1 and sorted(dev2) == sorted(host2)


def test_quoted_lowering_accepts_and_marks():
    from kolibrie_tpu.optimizer.engine import resolve_pattern
    from kolibrie_tpu.optimizer.planner import (
        Streamertail,
        build_logical_plan,
    )

    db = _rdf_star_db()
    sel = parse_sparql_query(
        """PREFIX ex: <http://example.org/>
        SELECT ?s ?v ?c WHERE { << ?s ex:age ?v >> ex:certainty ?c }"""
    )
    resolved = [resolve_pattern(db, p) for p in sel.where.patterns]
    logical = build_logical_plan(resolved, [], [], sel.where.values)
    plan = Streamertail(db.get_or_build_stats()).find_best_plan(logical)
    lowered = lower_plan(db, plan)
    assert lowered.need_quoted
    # host-oracle evaluation of the same IR agrees with the device run
    table, _counts = lowered.host_execute()
    out_cols, valid = lowered.converge(lowered.run())
    dev_table = lowered.to_table(out_cols, valid)
    for v in lowered.out_vars:
        assert sorted(table[v].tolist()) == sorted(dev_table[v].tolist())


def test_quoted_query_fuzz():
    """Randomized RDF-star queries: quoted annotation patterns (inner
    variables, inner constants, joins with plain patterns) through the
    auto-routing engine must agree with the host on every query."""
    import random

    rng = random.Random(20260804)
    db = SparqlDatabase()
    lines = ["@prefix f: <http://f.e/> ."]
    n_subj, n_pred = 30, 3
    for i in range(120):
        s = f"f:s{rng.randrange(n_subj)}"
        p = f"f:p{rng.randrange(n_pred)}"
        o = f"f:s{rng.randrange(n_subj)}"
        ann = rng.choice(["f:certainty", "f:saidBy"])
        val = (
            f'"{rng.randrange(1, 100) / 100}"'
            if ann == "f:certainty"
            else f"f:src{rng.randrange(4)}"
        )
        lines.append(f"<< {s} {p} {o} >> {ann} {val} .")
        if rng.random() < 0.5:
            lines.append(f"{s} f:knows {o} .")
    db.parse_turtle("\n".join(lines))
    db.execution_mode = "device"

    for trial in range(20):
        p = f"f:p{rng.randrange(n_pred)}"
        shape = rng.randrange(4)
        if shape == 0:
            body = f"<< ?x {p} ?y >> f:certainty ?c ."
            sel = "?x ?y ?c"
        elif shape == 1:
            s_const = f"f:s{rng.randrange(n_subj)}"
            body = f"<< {s_const} ?p ?y >> f:saidBy ?w ."
            sel = "?p ?y ?w"
        elif shape == 2:
            body = (
                f"<< ?x {p} ?y >> f:certainty ?c . ?x f:knows ?y ."
            )
            sel = "?x ?y ?c"
        else:
            body = f"<< ?x {p} ?x >> f:certainty ?c ."
            sel = "?x ?c"
        q = (
            "PREFIX f: <http://f.e/> "
            f"SELECT {sel} WHERE {{ {body} }}"
        )
        try:
            dev, host = run_both(db, q)
        except Exception as e:
            raise AssertionError(f"trial {trial}: {q!r} raised {e}") from e
        assert sorted(dev) == sorted(host), (trial, q, len(dev), len(host))


def test_string_function_filters_device():
    """REGEX/CONTAINS/STRSTARTS/STRENDS with constant patterns lower to
    per-ID verdict masks (round 4); ISTRIPLE is a bit test; BOUND an ID
    compare. Host agreement on every shape, including quoted-ID columns."""
    db = SparqlDatabase()
    db.parse_turtle(
        """
    @prefix ex: <http://example.org/> .
    ex:alice ex:name "Alice Smith" . ex:alice ex:dept "engineering" .
    ex:bob ex:name "Bob Stone" .     ex:bob ex:dept "marketing" .
    ex:carol ex:name "Carol Quinn" . ex:carol ex:dept "engineering" .
    << ex:alice ex:age 30 >> ex:note "approximate estimate" .
    """
    )
    db.execution_mode = "device"
    for q, n in (
        ('SELECT ?e ?n WHERE { ?e ex:name ?n . FILTER(CONTAINS(?n, "o")) }', 2),
        ('SELECT ?e WHERE { ?e ex:name ?n . FILTER(STRSTARTS(?n, "Car")) }', 1),
        ('SELECT ?e WHERE { ?e ex:dept ?d . FILTER(REGEX(?d, "eng.*ing")) }', 2),
        (
            'SELECT ?e WHERE { ?e ex:name ?n . '
            'FILTER(STRENDS(?n, "ne") && CONTAINS(?n, "B")) }',
            1,
        ),
        ("SELECT ?t WHERE { ?t ex:note ?x . FILTER(ISTRIPLE(?t)) }", 1),
        (
            'SELECT ?e WHERE { ?e ex:name ?n . FILTER(!CONTAINS(?n, "o")) }',
            1,
        ),
    ):
        full = "PREFIX ex: <http://example.org/> " + q
        dev, host = run_both(db, full)
        assert sorted(dev) == sorted(host), q
        assert len(host) == n, (q, host)


def test_string_mask_refreshes_after_growth():
    """A prepared string-filter plan must rebuild its masks when the
    dictionary (or quoted store) grows — new IDs would otherwise clamp."""
    db = SparqlDatabase()
    db.parse_ntriples(
        '<http://e/a> <http://e/name> "anchor match" .'
    )
    db.execution_mode = "device"
    q = (
        'SELECT ?s WHERE { ?s <http://e/name> ?n . '
        'FILTER(CONTAINS(?n, "match")) }'
    )
    first = execute_query_volcano(q, db)
    assert len(first) == 1
    db.parse_ntriples(
        '<http://e/b> <http://e/name> "late match arrival" .\n'
        '<http://e/c> <http://e/name> "no hit" .'
    )
    db.execution_mode = "host"
    host = execute_query_volcano(q, db)
    db.execution_mode = "device"
    dev = execute_query_volcano(q, db)
    assert sorted(dev) == sorted(host)
    assert len(dev) == 2


def test_string_order_by_device_topk():
    """Non-numeric ORDER BY keys ride the global per-ID string ranks
    (round 4) — the device top-k no longer falls back to host ordering;
    exact host agreement with unique keys, mixed key directions."""
    from kolibrie_tpu.optimizer.device_engine import (
        try_device_execute_ordered,
    )

    db = SparqlDatabase()
    lines = []
    for i in range(150):
        lines.append(f'<http://e/p{i}> <http://e/name> "person {i:03d}" .')
        lines.append(f'<http://e/p{i}> <http://e/dept> "d{i % 7}" .')
        lines.append(f'<http://e/p{i}> <http://e/salary> "{1000 + i * 3}" .')
    db.parse_ntriples("\n".join(lines))
    for q in (
        "SELECT ?p ?n WHERE { ?p <http://e/name> ?n . ?p <http://e/dept> ?d }"
        " ORDER BY DESC(?n) LIMIT 9",
        "SELECT ?p ?n ?s WHERE { ?p <http://e/name> ?n . "
        "?p <http://e/salary> ?s } ORDER BY ?n LIMIT 6",
        # string primary + numeric secondary
        "SELECT ?p ?d ?s WHERE { ?p <http://e/dept> ?d . "
        "?p <http://e/salary> ?s } ORDER BY ?d DESC(?s) LIMIT 8",
    ):
        db.execution_mode = "host"
        host = execute_query_volcano(q, db)
        db.execution_mode = "device"
        dev = try_device_execute_ordered(db, parse_sparql_query(q))
        assert dev is not None, q
        assert dev == host, q


# ---------------------------------------------------------------------------
# MINUS / NOT blocks fused as device anti-joins (round 4)
# ---------------------------------------------------------------------------


def _lowers_with_anti(db, query):
    """The fused lowering must succeed for these shapes (proves the device
    path, not the host post-pass, serves the query)."""
    from kolibrie_tpu.optimizer.engine import resolve_pattern
    from kolibrie_tpu.optimizer.planner import Streamertail, build_logical_plan
    from kolibrie_tpu.query.executor import _branch_plan
    from kolibrie_tpu.query.parser import parse_combined_query
    from kolibrie_tpu.query.ast import WhereClause

    db.register_prefixes_from_query(query)
    w = parse_combined_query(query, db.prefixes).select.where
    planner = Streamertail(db.get_or_build_stats())
    resolved = [resolve_pattern(db, p) for p in w.patterns]
    logical = build_logical_plan(resolved, list(w.filters), [], w.values)
    plan = planner.find_best_plan(logical)
    branches = list(w.minus) + [
        WhereClause(patterns=nb.patterns) for nb in w.not_blocks
    ]
    anti = [_branch_plan(db, planner, b) for b in branches]
    assert all(a is not None for a in anti)
    return lower_plan(db, plan, tuple(anti))


def test_minus_agreement():
    db = employee_db()
    q = PREFIXES + """
    SELECT ?e ?s WHERE {
        ?e ex:salary ?s
        MINUS { ?e ex:dept "dept0" }
    }"""
    dev, host = run_both(db, q)
    assert len(host) == 400
    assert sorted(dev) == sorted(host)
    lowered = _lowers_with_anti(db, q)
    assert "anti-join" in lowered.describe()


def test_minus_with_branch_filter_agreement():
    db = employee_db()
    q = PREFIXES + """
    SELECT ?e ?w WHERE {
        ?e foaf:workplaceHomepage ?w
        MINUS { ?e ex:salary ?s . FILTER(?s > 60000) }
    }"""
    dev, host = run_both(db, q)
    assert 0 < len(host) < 500
    assert sorted(dev) == sorted(host)
    _lowers_with_anti(db, q)


def test_not_block_agreement():
    db = employee_db()
    q = PREFIXES + """
    SELECT ?e ?s WHERE {
        ?e ex:salary ?s .
        NOT { ?e ex:knows ?y }
    }"""
    dev, host = run_both(db, q)
    assert 0 < len(host) < 500
    assert sorted(dev) == sorted(host)
    _lowers_with_anti(db, q)


def test_minus_disjoint_domains_removes_nothing():
    db = employee_db()
    q = PREFIXES + """
    SELECT ?e ?s WHERE {
        ?e ex:salary ?s
        MINUS { ?a ex:dept "dept0" }
    }"""
    dev, host = run_both(db, q)
    assert len(dev) == 500
    assert sorted(dev) == sorted(host)


def test_minus_and_not_stack():
    db = employee_db()
    q = PREFIXES + """
    SELECT ?e ?s WHERE {
        ?e ex:salary ?s
        MINUS { ?e ex:dept "dept1" }
        NOT { ?e ex:knows ?y }
    }"""
    dev, host = run_both(db, q)
    assert 0 < len(host) < 500
    assert sorted(dev) == sorted(host)
    _lowers_with_anti(db, q)


def test_minus_fuzz_agreement():
    """Random BGP + random MINUS/NOT branches: device vs host."""
    import random

    rng = random.Random(20260732)
    db = SparqlDatabase()
    lines = []
    preds = [f"<http://f.e/p{k}>" for k in range(4)]
    for i in range(400):
        s = f"<http://f.e/s{rng.randrange(60)}>"
        pr = rng.choice(preds)
        if rng.random() < 0.5:
            o = f"<http://f.e/s{rng.randrange(60)}>"
        else:
            o = f'"{rng.randrange(0, 3000)}"'
        lines.append(f"{s} {pr} {o} .")
    db.parse_ntriples("\n".join(lines))
    db.execution_mode = "device"

    vars_pool = ["?a", "?b", "?c"]
    for trial in range(20):
        n_pat = rng.randrange(1, 3)
        pats, used = [], []
        for _ in range(n_pat):
            s = (
                rng.choice(used)
                if used and rng.random() < 0.8
                else rng.choice(vars_pool)
            )
            o = rng.choice(vars_pool + [f"<http://f.e/s{rng.randrange(60)}>"])
            pats.append(f"{s} {rng.choice(preds)} {o} .")
            for t in (s, o):
                if t.startswith("?") and t not in used:
                    used.append(t)
        bs = rng.choice(used) if rng.random() < 0.9 else "?z"
        bo = rng.choice(vars_pool + [f"<http://f.e/s{rng.randrange(60)}>"])
        bfilt = ""
        if rng.random() < 0.4 and bo.startswith("?"):
            bfilt = f"FILTER({bo} > {rng.randrange(0, 3000)})"
        kw = "MINUS" if rng.random() < 0.5 else "NOT"
        branch = f"{kw} {{ {bs} {rng.choice(preds)} {bo} . {bfilt} }}"
        if kw == "NOT" and bfilt:
            branch = f"NOT {{ {bs} {rng.choice(preds)} {bo} }}"
        sel = " ".join(used)
        q = f"SELECT {sel} WHERE {{ {' '.join(pats)} {branch} }}"
        try:
            dev, host = run_both(db, q)
        except Exception as e:
            raise AssertionError(f"trial {trial}: {q!r} raised {e}") from e
        assert sorted(dev) == sorted(host), (trial, q, len(dev), len(host))


# ---------------------------------------------------------------------------
# UNION / OPTIONAL fused into the device program (round 4)
# ---------------------------------------------------------------------------


def test_union_agreement():
    db = employee_db()
    q = PREFIXES + """
    SELECT ?e ?x WHERE {
        ?e ex:salary ?x
        { ?e ex:dept "dept0" } UNION { ?e ex:dept "dept1" }
    }"""
    dev, host = run_both(db, q)
    assert len(host) == 200
    assert sorted(dev) == sorted(host)


def test_union_unbound_fill_agreement():
    # branches bind DIFFERENT variables: the union table carries UNBOUND
    # fills; join happens on the one genuinely shared var
    db = employee_db()
    q = PREFIXES + """
    SELECT ?e ?s WHERE {
        ?e ex:salary ?s
        { ?e ex:dept "dept2" } UNION { ?e ex:knows ?y }
    }"""
    dev, host = run_both(db, q)
    assert len(host) > 0
    assert sorted(dev) == sorted(host)


def test_optional_agreement():
    db = employee_db()
    q = PREFIXES + """
    SELECT ?e ?s ?y WHERE {
        ?e ex:salary ?s .
        OPTIONAL { ?e ex:knows ?y }
    }"""
    dev, host = run_both(db, q)
    # every employee kept; knows-targets only where present
    assert len(host) == 500
    assert sorted(dev) == sorted(host)
    blanks = [r for r in host if r[2] == ""]
    assert 0 < len(blanks) < 500


def test_optional_with_filter_branch_agreement():
    db = employee_db()
    q = PREFIXES + """
    SELECT ?e ?w ?s WHERE {
        ?e foaf:workplaceHomepage ?w .
        OPTIONAL { ?e ex:salary ?s . FILTER(?s > 70000) }
    }"""
    dev, host = run_both(db, q)
    assert len(host) == 500
    assert sorted(dev) == sorted(host)


def test_union_optional_minus_compose():
    db = employee_db()
    q = PREFIXES + """
    SELECT ?e ?s ?y WHERE {
        ?e ex:salary ?s
        { ?e ex:dept "dept0" } UNION { ?e ex:dept "dept3" }
        OPTIONAL { ?e ex:knows ?y }
        MINUS { ?e foaf:workplaceHomepage <http://company0.example/> }
    }"""
    dev, host = run_both(db, q)
    assert len(host) > 0
    assert sorted(dev) == sorted(host)


def test_union_optional_fuzz_agreement():
    """Random BGP + union/optional/minus tails: device vs host."""
    import random

    rng = random.Random(20260733)
    db = SparqlDatabase()
    lines = []
    preds = [f"<http://f.e/p{k}>" for k in range(4)]
    for i in range(400):
        s = f"<http://f.e/s{rng.randrange(60)}>"
        pr = rng.choice(preds)
        if rng.random() < 0.5:
            o = f"<http://f.e/s{rng.randrange(60)}>"
        else:
            o = f'"{rng.randrange(0, 3000)}"'
        lines.append(f"{s} {pr} {o} .")
    db.parse_ntriples("\n".join(lines))
    db.execution_mode = "device"

    vars_pool = ["?a", "?b", "?c"]
    for trial in range(25):
        pats, used = [], []
        for _ in range(rng.randrange(1, 3)):
            s = (
                rng.choice(used)
                if used and rng.random() < 0.8
                else rng.choice(vars_pool)
            )
            o = rng.choice(vars_pool + [f"<http://f.e/s{rng.randrange(60)}>"])
            pats.append(f"{s} {rng.choice(preds)} {o} .")
            for t in (s, o):
                if t.startswith("?") and t not in used:
                    used.append(t)
        share = rng.choice(used)
        clauses = []
        kind = rng.randrange(3)
        if kind == 0:
            b1 = f"{{ {share} {rng.choice(preds)} <http://f.e/s{rng.randrange(60)}> }}"
            b2 = f"{{ {share} {rng.choice(preds)} ?u }}"
            clauses.append(f"{b1} UNION {b2}")
        elif kind == 1:
            clauses.append(
                f"OPTIONAL {{ {share} {rng.choice(preds)} ?v }}"
            )
        else:
            clauses.append(
                f"OPTIONAL {{ {share} {rng.choice(preds)} ?v }}"
            )
            clauses.append(
                f"MINUS {{ {share} {rng.choice(preds)} "
                f"<http://f.e/s{rng.randrange(60)}> }}"
            )
        sel = " ".join(used)
        q = f"SELECT {sel} WHERE {{ {' '.join(pats)} {' '.join(clauses)} }}"
        try:
            dev, host = run_both(db, q)
        except Exception as e:
            raise AssertionError(f"trial {trial}: {q!r} raised {e}") from e
        assert sorted(dev) == sorted(host), (trial, q, len(dev), len(host))


def test_ordered_with_minus_and_optional():
    """ORDER BY + LIMIT fast path fuses the round-4 clauses too."""
    from kolibrie_tpu.optimizer.device_engine import try_device_execute_ordered
    from kolibrie_tpu.query.parser import parse_sparql_query

    db = employee_db()
    q = PREFIXES + """
    SELECT ?e ?s WHERE {
        ?e ex:salary ?s .
        OPTIONAL { ?e ex:knows ?y }
        MINUS { ?e ex:dept "dept4" }
    } ORDER BY DESC(?s) LIMIT 7"""
    dev, host = run_both(db, q)
    assert len(host) == 7
    assert dev == host  # ordered: exact row order must match
    db.register_prefixes_from_query(q)
    parsed = parse_sparql_query(q, db.prefixes)
    rows = try_device_execute_ordered(db, parsed)
    assert rows is not None  # proves the fast path served it
    assert rows == host


def test_ordered_with_subquery():
    from kolibrie_tpu.optimizer.device_engine import try_device_execute_ordered
    from kolibrie_tpu.query.parser import parse_sparql_query

    db = employee_db()
    q = PREFIXES + """
    SELECT ?e ?s WHERE {
        ?e ex:salary ?s .
        { SELECT ?e WHERE { ?e ex:dept "dept2" } }
    } ORDER BY ?s LIMIT 5"""
    dev, host = run_both(db, q)
    assert len(host) == 5
    assert dev == host
    db.register_prefixes_from_query(q)
    rows = try_device_execute_ordered(db, parse_sparql_query(q, db.prefixes))
    assert rows is not None
    assert rows == host


def test_aggregate_over_union_minus_optional():
    """GROUP BY aggregation fuses over the round-4 clauses (device segment
    reduce over the fused table)."""
    from kolibrie_tpu.query.executor import _try_device_aggregate
    from kolibrie_tpu.query.parser import parse_sparql_query

    db = employee_db()
    cases = [
        PREFIXES + """
        SELECT ?d (COUNT(?e) AS ?c) WHERE {
            ?e ex:dept ?d
            { ?e ex:salary ?s } UNION { ?e ex:knows ?y }
        } GROUP BY ?d""",
        PREFIXES + """
        SELECT ?d (COUNT(?y) AS ?c) WHERE {
            ?e ex:dept ?d .
            OPTIONAL { ?e ex:knows ?y }
        } GROUP BY ?d""",
        PREFIXES + """
        SELECT ?d (COUNT(?e) AS ?c) WHERE {
            ?e ex:dept ?d
            MINUS { ?e ex:knows ?y }
        } GROUP BY ?d""",
    ]
    for q in cases:
        dev, host = run_both(db, q)
        assert len(host) > 0, q
        assert sorted(dev) == sorted(host), q
        db.register_prefixes_from_query(q)
        parsed = parse_sparql_query(q, db.prefixes)
        table, _p, _l = _try_device_aggregate(db, parsed, True)
        assert table is not None, q  # proves the device aggregate served it


def test_union_only_query_on_device():
    """A WHERE that is just a UNION (the executor's standalone-union case)
    lowers with plan=None — the union IS the program."""
    db = employee_db()
    q = PREFIXES + """
    SELECT ?e WHERE {
        { ?e ex:dept "dept0" } UNION { ?e ex:dept "dept1" }
    }"""
    dev, host = run_both(db, q)
    assert len(host) == 200
    assert sorted(dev) == sorted(host)
    lowered = lower_plan(
        db,
        None,
        (),
        (_union_branch_plans(db, q),),
        (),
    )
    assert "union" in lowered.describe()
    assert len(lowered.execute()["e"]) == 200


def _union_branch_plans(db, q):
    from kolibrie_tpu.optimizer.planner import Streamertail
    from kolibrie_tpu.query.executor import _branch_plan
    from kolibrie_tpu.query.parser import parse_sparql_query

    db.register_prefixes_from_query(q)
    w = parse_sparql_query(q, db.prefixes).where
    planner = Streamertail(db.get_or_build_stats())
    return tuple(_branch_plan(db, planner, bw) for bw in w.unions[0])


def test_optional_only_query_on_device():
    db = employee_db()
    q = PREFIXES + """
    SELECT ?e ?y WHERE {
        OPTIONAL { ?e ex:knows ?y }
    }"""
    dev, host = run_both(db, q)
    assert len(host) > 0
    assert sorted(dev) == sorted(host)


def test_union_then_optional_clause_only():
    db = employee_db()
    q = PREFIXES + """
    SELECT ?e ?y WHERE {
        { ?e ex:dept "dept0" } UNION { ?e ex:dept "dept2" }
        OPTIONAL { ?e ex:knows ?y }
    }"""
    dev, host = run_both(db, q)
    assert len(host) == 200
    assert sorted(dev) == sorted(host)


def test_request_with_clauses_is_one_fused_program():
    """UNION, OPTIONAL and MINUS branches ride in the request's one device
    program: the lowering the plan cache keeps is the fused one, a second
    request replays it without a compile, and a bare dispatch of it reads
    back the host engine's rows."""
    from kolibrie_tpu.optimizer.device_engine import device_compile_stats

    db = employee_db()
    q = PREFIXES + """
    SELECT ?e ?s ?y WHERE {
        ?e ex:salary ?s
        { ?e ex:dept "dept0" } UNION { ?e ex:dept "dept1" }
        OPTIONAL { ?e ex:knows ?y }
        MINUS { ?e foaf:workplaceHomepage <http://company3.example/> }
    }"""
    dev, host = run_both(db, q)
    assert len(host) > 0
    assert sorted(dev) == sorted(host)
    low = _request_lowering(db, q)
    assert low.fused_clauses
    before = device_compile_stats()
    assert sorted(execute_query_volcano(q, db)) == sorted(host)
    assert _rows(db, low, q) == sorted(host)
    assert device_compile_stats() == before


def test_group_concat_over_minus_uses_fused_prebuilt():
    """GROUP_CONCAT can't aggregate on device, but the WHERE (with MINUS)
    still executes as the fused device program; the prebuilt-lowered
    handoff must not re-apply the MINUS post-pass (fused_clauses flag)."""
    db = employee_db()
    q = PREFIXES + """
    SELECT ?d (GROUP_CONCAT(?e) AS ?c) WHERE {
        ?e ex:dept ?d
        MINUS { ?e ex:knows ?y }
    } GROUP BY ?d"""
    dev, host = run_both(db, q)
    assert len(host) == 5
    assert sorted(dev) == sorted(host)


def test_empty_branch_clauses():
    """Branches scanning UNKNOWN constants (absent from the dictionary):
    MINUS/NOT remove nothing, an all-empty UNION empties the result, a
    some-empty UNION uses the live branches — all still on device."""
    db = employee_db()
    q1 = PREFIXES + """
    SELECT ?e ?s WHERE {
        ?e ex:salary ?s
        MINUS { ?e ex:no_such_predicate ?y }
    }"""
    dev, host = run_both(db, q1)
    assert len(dev) == 500
    assert sorted(dev) == sorted(host)

    q2 = PREFIXES + """
    SELECT ?e ?s WHERE {
        ?e ex:salary ?s
        { ?e ex:no_such_a "x" } UNION { ?e ex:no_such_b "y" }
    }"""
    dev, host = run_both(db, q2)
    assert dev == host == []

    q3 = PREFIXES + """
    SELECT ?e ?s WHERE {
        ?e ex:salary ?s
        { ?e ex:no_such_a "x" } UNION { ?e ex:dept "dept0" }
    }"""
    dev, host = run_both(db, q3)
    assert len(dev) == 100
    assert sorted(dev) == sorted(host)

    # ADVICE r4 (medium): SELECT * with a some-empty UNION — the dropped
    # branch's variables must still surface as UNBOUND-filled columns so
    # the device arity matches the host post-pass (4 columns, not 3)
    q3b = PREFIXES + """
    SELECT * WHERE {
        ?e ex:salary ?s
        { ?e ex:dept ?d } UNION { ?e ex:no_such_c ?z }
    }"""
    dev, host = run_both(db, q3b)
    assert len(host) > 0
    assert len(host[0]) == 4  # e, s, d, z (z all-UNBOUND)
    assert sorted(dev) == sorted(host)

    # ... and a dropped branch whose QUOTED term carries inner variables
    # (?x ?y) must surface those too (PatternTriple.variables recursion)
    q3c = PREFIXES + """
    SELECT * WHERE {
        ?e ex:salary ?s
        { ?e ex:dept ?d } UNION { << ?x ex:no_such_r ?y >> ex:no_such_p ?c }
    }"""
    dev, host = run_both(db, q3c)
    assert len(host) > 0
    assert len(host[0]) == 6  # e, s, d, c, x, y (c/x/y all-UNBOUND)
    assert sorted(dev) == sorted(host)

    # OPTIONAL over an unknown predicate: host semantics (left kept,
    # UNBOUND fill) via fallback — rows must still agree
    q4 = PREFIXES + """
    SELECT ?e ?s ?y WHERE {
        ?e ex:salary ?s
        OPTIONAL { ?e ex:no_such ?y }
    }"""
    dev, host = run_both(db, q4)
    assert len(dev) == 500
    assert sorted(dev) == sorted(host)
