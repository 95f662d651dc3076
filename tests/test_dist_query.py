"""Agreement tests: distributed full-plan SPARQL execution vs the host
volcano executor, on the virtual 8-device CPU mesh (conftest.py).

BASELINE config 5: the LUBM Q2/Q9 triangles (3+ patterns, shared variables
beyond the routed key) plus filters and DISTINCT run over the sharded store
with all-to-all repartitioning between join stages, and must return exactly
the host engine's rows.
"""

import numpy as np
import pytest

import jax

from kolibrie_tpu.parallel import make_mesh
from kolibrie_tpu.parallel.dist_query import (
    DistQueryExecutor,
    Unsupported,
    _largest,
    execute_query_distributed,
)
from kolibrie_tpu.query.executor import execute_query_volcano
from kolibrie_tpu.query.sparql_database import SparqlDatabase

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "examples"))
import lubm  # noqa: E402


@pytest.fixture(scope="module")
def mesh():
    assert jax.device_count() >= 8, "conftest must provide 8 virtual devices"
    return make_mesh(8)


@pytest.fixture(scope="module")
def lubm_db():
    db = SparqlDatabase()
    s, p, o = lubm.generate_fast(3, db.dictionary)
    db.store.add_batch(s, p, o)
    db.execution_mode = "host"
    return db


def test_lubm_q2_agreement(mesh, lubm_db):
    host = execute_query_volcano(lubm.LUBM_Q2, lubm_db)
    dist = execute_query_distributed(lubm.LUBM_Q2, lubm_db, mesh)
    assert len(host) > 0
    assert dist == host


def test_lubm_q9_agreement(mesh, lubm_db):
    host = execute_query_volcano(lubm.LUBM_Q9, lubm_db)
    dist = execute_query_distributed(lubm.LUBM_Q9, lubm_db, mesh)
    assert len(host) > 0
    assert dist == host


Q7_SHAPED = """PREFIX rdf: <http://www.w3.org/1999/02/22-rdf-syntax-ns#>
PREFIX ub: <http://swat.cse.lehigh.edu/onto/univ-bench.owl#>
SELECT ?x ?y WHERE {
    ?x rdf:type ub:UndergraduateStudent .
    ?y rdf:type ub:Course .
    ?x ub:takesCourse ?y .
    <http://www.Department0.University0.edu/FullProfessor0> ub:teacherOf ?y
}"""


@pytest.mark.parametrize("seed", [None, 0, 3])
def test_the_seed_is_counted_and_any_seed_answers_alike(mesh, lubm_db, seed):
    host = execute_query_volcano(Q7_SHAPED, lubm_db)
    assert len(host) > 0
    ex = DistQueryExecutor(mesh, lubm_db, Q7_SHAPED, seed=seed)
    if seed is None:
        # one professor's course, not every undergraduate: the premise
        # the host count makes cheapest, where most-constants takes 0
        assert (ex.seed, ex.plan_source) == (3, "counted")
        step, bucket = _largest(
            *ex._count_chain(ex.premises, ex.seed, ex.steps)[:2]
        )
        assert 0 < 4 * step <= ex.join_cap == 1024
        assert 0 < 4 * bucket <= ex.bucket_cap == 1024
    else:
        assert ex.seed == seed  # a caller's pin stands; its caps are counted
    assert ex.run() == host


def test_filter_and_distinct_agreement(mesh):
    db = SparqlDatabase()
    lines = []
    for i in range(300):
        e = f"<http://example.org/e{i}>"
        lines.append(
            f"{e} <http://example.org/worksAt> <http://example.org/org{i % 9}> ."
        )
        lines.append(
            f'{e} <http://example.org/salary> "{30000 + (i % 40) * 1000}" .'
        )
    db.parse_ntriples("\n".join(lines))
    db.execution_mode = "host"
    q = """PREFIX ex: <http://example.org/>
    SELECT DISTINCT ?o WHERE {
        ?e ex:worksAt ?o .
        ?e ex:salary ?s .
        FILTER(?s > 55000)
    }"""
    host = execute_query_volcano(q, db)
    dist = execute_query_distributed(q, db, mesh)
    assert len(host) > 0
    assert dist == host
    # term-equality filter + projection of both vars
    q2 = """PREFIX ex: <http://example.org/>
    SELECT ?e ?s WHERE {
        ?e ex:worksAt ?o .
        ?e ex:salary ?s .
        FILTER(?o = ex:org3)
    }"""
    host2 = execute_query_volcano(q2, db)
    dist2 = execute_query_distributed(q2, db, mesh)
    assert len(host2) > 0
    assert dist2 == host2


def test_constant_subject_and_limit(mesh):
    db = SparqlDatabase()
    lines = []
    for i in range(64):
        lines.append(
            f"<http://example.org/hub> <http://example.org/links> "
            f"<http://example.org/n{i}> ."
        )
        lines.append(
            f"<http://example.org/n{i}> <http://example.org/tag> "
            f'"t{i % 4}" .'
        )
    db.parse_ntriples("\n".join(lines))
    db.execution_mode = "host"
    q = """PREFIX ex: <http://example.org/>
    SELECT ?n ?t WHERE {
        ex:hub ex:links ?n .
        ?n ex:tag ?t
    } LIMIT 10"""
    host = execute_query_volcano(q, db)
    dist = execute_query_distributed(q, db, mesh)
    assert dist == host
    assert len(dist) == 10


def test_unsupported_shapes_raise(mesh, lubm_db):
    with pytest.raises(Unsupported):
        # OPTIONAL now distributes, but only with a plain BGP(+filter)
        # branch — a nested OPTIONAL inside the branch stays single-chip
        DistQueryExecutor(
            mesh,
            lubm_db,
            "SELECT ?x WHERE { ?x ?p ?y . "
            "OPTIONAL { ?y ?q ?z OPTIONAL { ?z ?q ?w } } }",
        )
    with pytest.raises(Unsupported):
        # an OPTIONAL sharing no variable with the group has cross-join
        # semantics on the host — stays single-chip
        DistQueryExecutor(
            mesh,
            lubm_db,
            "SELECT ?x WHERE { ?x ?p ?y . OPTIONAL { ?a ?q ?b } }",
        )
    with pytest.raises(Unsupported):
        # GROUP_CONCAT stays host-side (same contract as the single-chip
        # device engine); plain COUNT/SUM/AVG/MIN/MAX are supported
        DistQueryExecutor(
            mesh,
            lubm_db,
            "SELECT (GROUP_CONCAT(?x) AS ?c) WHERE { ?x ?p ?y }",
        )


def test_executor_reuse_and_store_reuse(mesh, lubm_db):
    """One sharded store serves multiple prepared queries (the benchmark
    path); capacity state persists across runs."""
    ex2 = DistQueryExecutor(mesh, lubm_db, lubm.LUBM_Q2)
    r1 = ex2.run()
    ex9 = DistQueryExecutor(mesh, lubm_db, lubm.LUBM_Q9, store=ex2.store)
    r9 = ex9.run()
    assert r1 == execute_query_volcano(lubm.LUBM_Q2, lubm_db)
    assert r9 == execute_query_volcano(lubm.LUBM_Q9, lubm_db)


def test_group_by_aggregates_agreement(mesh):
    """Distributed GROUP BY + aggregates: mesh-resident result columns feed
    the single-chip segment aggregator; rows equal the host engine."""
    db = SparqlDatabase()
    lines = []
    for i in range(240):
        e = f"<http://example.org/e{i}>"
        lines.append(
            f"{e} <http://example.org/dept> <http://example.org/d{i % 6}> ."
        )
        lines.append(
            f'{e} <http://example.org/salary> "{30000 + (i % 40) * 500}" .'
        )
    db.parse_ntriples("\n".join(lines))
    db.execution_mode = "host"
    q = """PREFIX ex: <http://example.org/>
    SELECT ?d (COUNT(?e) AS ?n) (AVG(?s) AS ?avg) (MAX(?s) AS ?mx) WHERE {
        ?e ex:dept ?d . ?e ex:salary ?s
    } GROUP BY ?d"""
    host = execute_query_volcano(q, db)
    dist = execute_query_distributed(q, db, mesh)
    assert len(host) == 6
    assert dist == host
    # COUNT(DISTINCT) + filter
    q2 = """PREFIX ex: <http://example.org/>
    SELECT ?d (COUNT(DISTINCT ?s) AS ?k) WHERE {
        ?e ex:dept ?d . ?e ex:salary ?s . FILTER(?s > 40000)
    } GROUP BY ?d"""
    assert execute_query_distributed(q2, db, mesh) == execute_query_volcano(q2, db)
    # aggregate without GROUP BY: exactly one row
    q3 = """PREFIX ex: <http://example.org/>
    SELECT (COUNT(?e) AS ?n) WHERE { ?e ex:salary ?s }"""
    assert execute_query_distributed(q3, db, mesh) == execute_query_volcano(q3, db)


def test_repeated_variable_and_single_pattern(mesh):
    """Edge shapes: a pattern with a repeated variable (?x p ?x) and a
    single-pattern query (seed scan only, no join steps)."""
    db = SparqlDatabase()
    db.parse_ntriples(
        "\n".join(
            [
                "<http://e/a> <http://e/p> <http://e/a> .",
                "<http://e/a> <http://e/p> <http://e/b> .",
                "<http://e/b> <http://e/p> <http://e/b> .",
                "<http://e/c> <http://e/q> <http://e/c> .",
                "<http://e/a> <http://e/q> <http://e/b> .",
            ]
        )
    )
    db.execution_mode = "host"
    q_rep = "SELECT ?x WHERE { ?x <http://e/p> ?x }"
    assert execute_query_distributed(q_rep, db, mesh) == execute_query_volcano(
        q_rep, db
    ) != []
    q_one = "SELECT ?s ?o WHERE { ?s <http://e/q> ?o }"
    assert execute_query_distributed(q_one, db, mesh) == execute_query_volcano(
        q_one, db
    ) != []


def test_order_by_limit_topk_agreement(mesh):
    """Mesh-side per-shard numeric top-k: union of shard top-k re-ordered
    on host must equal the host executor's full ordering (keys unique so
    ties cannot make both answers differ)."""
    db = SparqlDatabase()
    lines = []
    for i in range(200):
        e = f"<http://example.org/e{i}>"
        lines.append(
            f"{e} <http://example.org/worksAt> <http://example.org/org{i % 7}> ."
        )
        lines.append(
            f'{e} <http://example.org/salary> "{30000 + i * 13}" .'
        )
    db.parse_ntriples("\n".join(lines))
    db.execution_mode = "host"
    for order in ("ASC(?s)", "DESC(?s)"):
        q = f"""PREFIX ex: <http://example.org/>
        SELECT ?e ?s WHERE {{
            ?e ex:worksAt ?o .
            ?e ex:salary ?s .
        }} ORDER BY {order} LIMIT 7"""
        host = execute_query_volcano(q, db)
        dist = execute_query_distributed(q, db, mesh)
        assert len(host) == 7
        assert dist == host


def test_order_by_offset_and_distinct_topk(mesh):
    """DISTINCT + ORDER BY + LIMIT/OFFSET compose: mesh dedup feeds the
    per-shard top-k, host applies the final offset slice."""
    db = SparqlDatabase()
    lines = []
    for i in range(120):
        e = f"<http://example.org/e{i}>"
        # many employees per org -> DISTINCT ?o ?b collapses duplicates
        lines.append(
            f"{e} <http://example.org/worksAt> <http://example.org/org{i % 10}> ."
        )
        lines.append(
            f"<http://example.org/org{i % 10}> "
            f'<http://example.org/budget> "{(i % 10) * 1000 + 500}" .'
        )
    db.parse_ntriples("\n".join(lines))
    db.execution_mode = "host"
    q = """PREFIX ex: <http://example.org/>
    SELECT DISTINCT ?o ?b WHERE {
        ?e ex:worksAt ?o .
        ?o ex:budget ?b .
    } ORDER BY DESC(?b) LIMIT 4 OFFSET 2"""
    host = execute_query_volcano(q, db)
    dist = execute_query_distributed(q, db, mesh)
    assert len(host) == 4
    assert dist == host


def test_order_by_string_key_mesh_ranked(mesh):
    """Non-numeric sort keys ride the global per-ID string ranks inside
    the mesh top-k (round 4) — no host re-run, exact agreement."""
    db = SparqlDatabase()
    lines = []
    for i in range(40):
        e = f"<http://example.org/e{i}>"
        lines.append(
            f"{e} <http://example.org/worksAt> <http://example.org/org{i % 5}> ."
        )
        lines.append(f'{e} <http://example.org/name> "name{i:03d}" .')
    db.parse_ntriples("\n".join(lines))
    db.execution_mode = "host"
    q = """PREFIX ex: <http://example.org/>
    SELECT ?e ?nm WHERE {
        ?e ex:worksAt ?o .
        ?e ex:name ?nm .
    } ORDER BY DESC(?nm) LIMIT 5"""
    host = execute_query_volcano(q, db)
    dist = execute_query_distributed(q, db, mesh)
    assert len(host) == 5
    assert dist == host


def test_bind_host_tail_agreement(mesh):
    """BINDs apply host-side to the gathered table (single-chip split):
    arithmetic bind, a filter reading the bind output, DISTINCT and
    ORDER BY over the bind column all agree with the host executor."""
    db = SparqlDatabase()
    lines = []
    for i in range(150):
        e = f"<http://example.org/e{i}>"
        lines.append(
            f"{e} <http://example.org/worksAt> <http://example.org/org{i % 6}> ."
        )
        lines.append(
            f'{e} <http://example.org/salary> "{30000 + (i % 25) * 1000}" .'
        )
    db.parse_ntriples("\n".join(lines))
    db.execution_mode = "host"
    q = """PREFIX ex: <http://example.org/>
    SELECT ?e ?total WHERE {
        ?e ex:worksAt ?o .
        ?e ex:salary ?s .
        BIND(?s * 1.1 AS ?total)
        FILTER(?total > 40000)
    }"""
    host = execute_query_volcano(q, db)
    dist = execute_query_distributed(q, db, mesh)
    assert len(host) > 0
    assert dist == host
    q2 = """PREFIX ex: <http://example.org/>
    SELECT DISTINCT ?o ?bonus WHERE {
        ?e ex:worksAt ?o .
        ?e ex:salary ?s .
        BIND(?s + 500 AS ?bonus)
    } ORDER BY DESC(?bonus) LIMIT 6"""
    host2 = execute_query_volcano(q2, db)
    dist2 = execute_query_distributed(q2, db, mesh)
    assert len(host2) == 6
    assert dist2 == host2


def test_values_membership_agreement(mesh):
    """Constraining VALUES lowers to a replicated membership mask in the
    mesh program; general shapes still raise."""
    db = SparqlDatabase()
    lines = []
    for i in range(90):
        e = f"<http://example.org/e{i}>"
        lines.append(
            f"{e} <http://example.org/worksAt> <http://example.org/org{i % 9}> ."
        )
        lines.append(f'{e} <http://example.org/grade> "g{i % 4}" .')
    db.parse_ntriples("\n".join(lines))
    db.execution_mode = "host"
    q = """PREFIX ex: <http://example.org/>
    SELECT ?e ?o WHERE {
        ?e ex:worksAt ?o .
        ?e ex:grade ?g .
        VALUES ?g { "g1" "g3" }
    }"""
    host = execute_query_volcano(q, db)
    dist = execute_query_distributed(q, db, mesh)
    assert len(host) > 0
    assert dist == host
    with pytest.raises(Unsupported):
        # duplicate cells change bag multiplicity -> single-chip
        DistQueryExecutor(
            mesh,
            db,
            """PREFIX ex: <http://example.org/>
            SELECT ?e WHERE { ?e ex:grade ?g . VALUES ?g { "g1" "g1" } }""",
        )


def test_distinct_bucket_overflow_retry(mesh):
    """Tiny bucket capacity forces the DISTINCT stage's exchange to drop
    rows; the driver's doubling protocol must converge to the exact
    distinct set."""
    db = SparqlDatabase()
    lines = []
    for i in range(400):
        e = f"<http://example.org/e{i}>"
        # only 5 distinct orgs, heavily duplicated -> hash concentration
        lines.append(
            f"{e} <http://example.org/worksAt> <http://example.org/org{i % 5}> ."
        )
    db.parse_ntriples("\n".join(lines))
    db.execution_mode = "host"
    q = """PREFIX ex: <http://example.org/>
    SELECT DISTINCT ?o WHERE { ?e ex:worksAt ?o }"""
    host = execute_query_volcano(q, db)
    ex = DistQueryExecutor(mesh, db, q, join_cap=512, bucket_cap=8)
    dist = ex.run()
    assert sorted(dist) == sorted(host)
    assert len(dist) == 5


def test_string_function_filter_agreement(mesh):
    """Constant-pattern string predicates lower to replicated verdict
    masks in the mesh program (single-chip StrMaskRef twin)."""
    db = SparqlDatabase()
    lines = []
    names = ["Alice Smith", "Bob Stone", "Carol Quinn", "Dan Smithers"]
    for i in range(120):
        e = f"<http://example.org/e{i}>"
        lines.append(
            f"{e} <http://example.org/worksAt> <http://example.org/org{i % 4}> ."
        )
        lines.append(f'{e} <http://example.org/name> "{names[i % 4]} {i}" .')
    db.parse_ntriples("\n".join(lines))
    db.execution_mode = "host"
    for flt in (
        'CONTAINS(?n, "Smith")',
        'STRSTARTS(?n, "Bob")',
        'REGEX(?n, "S(mith|tone)")',
        'STRENDS(?n, "7") && CONTAINS(?n, "o")',
    ):
        q = f"""PREFIX ex: <http://example.org/>
        SELECT ?e ?n WHERE {{
            ?e ex:worksAt ?o . ?e ex:name ?n . FILTER({flt})
        }}"""
        host = execute_query_volcano(q, db)
        dist = execute_query_distributed(q, db, mesh)
        assert len(host) > 0, flt
        assert dist == host, flt


def test_order_by_mixed_key_types_global_decision(mesh):
    """One non-numeric value ANYWHERE switches the whole sort column to
    string ranks (host rule) — the mesh top-k must psum the per-key
    decision, or shards holding only numeric values would sort numerically
    and drop rows from the global top-k."""
    db = SparqlDatabase()
    lines = []
    for i in range(1, 51):
        e = f"<http://example.org/e{i}>"
        lines.append(f"{e} <http://example.org/worksAt> <http://example.org/org> .")
        lines.append(f'{e} <http://example.org/v> "{i}" .')
    # the single non-numeric value: most shards never see it
    lines.append(
        "<http://example.org/odd> <http://example.org/worksAt> <http://example.org/org> ."
    )
    lines.append('<http://example.org/odd> <http://example.org/v> "apple" .')
    db.parse_ntriples("\n".join(lines))
    db.execution_mode = "host"
    q = """PREFIX ex: <http://example.org/>
    SELECT ?e ?v WHERE {
        ?e ex:worksAt ?o . ?e ex:v ?v .
    } ORDER BY ?v LIMIT 8"""
    host = execute_query_volcano(q, db)
    dist = execute_query_distributed(q, db, mesh)
    assert len(host) == 8
    assert dist == host


def test_order_by_pure_string_keys_mesh_topk(mesh):
    """Non-numeric ORDER BY + LIMIT stays a MESH top-k over global string
    ranks (readback k rows/shard) — not a full-result host re-order."""
    import numpy as np

    db = SparqlDatabase()
    words = ["apple", "banana", "cherry", "date", "elder",
             "fig", "grape", "kiwi", "lemon", "mango"]
    lines = []
    for i in range(200):
        e = f"<http://x.e/e{i}>"
        lines.append(f"{e} <http://x.e/works> <http://x.e/o{i % 5}> .")
        lines.append(f'{e} <http://x.e/tag> "{words[i % 10]}_{i:03d}" .')
    db.parse_ntriples("\n".join(lines))
    db.execution_mode = "host"
    q = """SELECT ?e ?t WHERE {
        ?e <http://x.e/works> ?o . ?e <http://x.e/tag> ?t
    } ORDER BY ?t LIMIT 7"""
    ex = DistQueryExecutor(mesh, db, q)
    dist = ex.run()
    host = execute_query_volcano(q, db)
    assert len(host) == 7
    assert dist == host
    # the rank-aware mesh program's readback is k rows per shard, not the
    # 200-row result: the top-k stage really ran on device
    outs, valid, _t, _nan = ex.run_device(
        topk=(8, (1,), (False,)), with_ranks=True
    )
    assert np.asarray(outs[0]).shape == (8, 8)


# ---------------------------------------------------------------------------
# MINUS / NOT as mesh anti-joins (round 4)
# ---------------------------------------------------------------------------


def _anti_db(n=300):
    db = SparqlDatabase()
    lines = []
    for i in range(n):
        e = f"<http://example.org/e{i}>"
        lines.append(
            f"{e} <http://example.org/worksAt> <http://example.org/org{i % 9}> ."
        )
        lines.append(
            f'{e} <http://example.org/salary> "{30000 + (i % 40) * 1000}" .'
        )
        if i % 3 == 0:
            lines.append(
                f"{e} <http://example.org/knows> <http://example.org/e{(i + 1) % n}> ."
            )
    db.parse_ntriples("\n".join(lines))
    db.execution_mode = "host"
    return db


def test_minus_agreement_dist(mesh):
    db = _anti_db()
    q = """PREFIX ex: <http://example.org/>
    SELECT ?e ?s WHERE {
        ?e ex:salary ?s
        MINUS { ?e ex:knows ?y }
    }"""
    host = execute_query_volcano(q, db)
    dist = execute_query_distributed(q, db, mesh)
    assert 0 < len(host) < 300
    assert dist == host


def test_minus_with_filter_branch_dist(mesh):
    db = _anti_db()
    q = """PREFIX ex: <http://example.org/>
    SELECT ?e ?o WHERE {
        ?e ex:worksAt ?o
        MINUS { ?e ex:salary ?s . FILTER(?s > 50000) }
    }"""
    host = execute_query_volcano(q, db)
    dist = execute_query_distributed(q, db, mesh)
    assert 0 < len(host) < 300
    assert dist == host


def test_not_block_dist(mesh):
    db = _anti_db()
    q = """PREFIX ex: <http://example.org/>
    SELECT ?e ?o WHERE {
        ?e ex:worksAt ?o .
        NOT { ?e ex:knows ?y }
    }"""
    host = execute_query_volcano(q, db)
    dist = execute_query_distributed(q, db, mesh)
    assert 0 < len(host) < 300
    assert dist == host


def test_minus_multikey_branch_dist(mesh):
    # branch shares TWO variables with the outer pattern
    db = _anti_db()
    q = """PREFIX ex: <http://example.org/>
    SELECT ?e ?y WHERE {
        ?e ex:knows ?y
        MINUS { ?e ex:worksAt ?o . ?y ex:worksAt ?o }
    }"""
    host = execute_query_volcano(q, db)
    dist = execute_query_distributed(q, db, mesh)
    assert len(host) > 0
    assert dist == host


def test_minus_disjoint_branch_dist(mesh):
    db = _anti_db()
    q = """PREFIX ex: <http://example.org/>
    SELECT ?e ?s WHERE {
        ?e ex:salary ?s
        MINUS { ?a ex:knows ?b }
    }"""
    host = execute_query_volcano(q, db)
    dist = execute_query_distributed(q, db, mesh)
    assert len(host) == 300
    assert dist == host


def test_minus_composes_with_distinct_dist(mesh):
    db = _anti_db()
    q = """PREFIX ex: <http://example.org/>
    SELECT DISTINCT ?o WHERE {
        ?e ex:worksAt ?o
        MINUS { ?e ex:knows ?y }
    }"""
    host = execute_query_volcano(q, db)
    dist = execute_query_distributed(q, db, mesh)
    assert len(host) > 0
    assert dist == host


# ---------------------------------------------------------------------------
# UNION / OPTIONAL as mesh programs (round 4)
# ---------------------------------------------------------------------------


def test_union_agreement_dist(mesh):
    db = _anti_db()
    q = """PREFIX ex: <http://example.org/>
    SELECT ?e ?s WHERE {
        ?e ex:salary ?s
        { ?e ex:worksAt <http://example.org/org0> }
        UNION { ?e ex:worksAt <http://example.org/org1> }
    }"""
    host = execute_query_volcano(q, db)
    dist = execute_query_distributed(q, db, mesh)
    assert 0 < len(host) < 300
    assert dist == host


def test_union_unbound_fill_dist(mesh):
    # branches bind different variable sets: UNBOUND fill rides the mesh
    db = _anti_db()
    q = """PREFIX ex: <http://example.org/>
    SELECT ?e ?s ?y WHERE {
        ?e ex:salary ?s
        { ?e ex:worksAt <http://example.org/org2> } UNION { ?e ex:knows ?y }
    }"""
    host = execute_query_volcano(q, db)
    dist = execute_query_distributed(q, db, mesh)
    assert len(host) > 0
    assert dist == host


def test_optional_agreement_dist(mesh):
    db = _anti_db()
    q = """PREFIX ex: <http://example.org/>
    SELECT ?e ?s ?y WHERE {
        ?e ex:salary ?s .
        OPTIONAL { ?e ex:knows ?y }
    }"""
    host = execute_query_volcano(q, db)
    dist = execute_query_distributed(q, db, mesh)
    assert len(host) == 300
    assert dist == host
    assert any(r[2] == "" for r in dist)  # UNBOUND survives the mesh


def test_optional_filter_branch_dist(mesh):
    db = _anti_db()
    q = """PREFIX ex: <http://example.org/>
    SELECT ?e ?o ?s WHERE {
        ?e ex:worksAt ?o .
        OPTIONAL { ?e ex:salary ?s . FILTER(?s > 60000) }
    }"""
    host = execute_query_volcano(q, db)
    dist = execute_query_distributed(q, db, mesh)
    assert len(host) == 300
    assert dist == host


def test_union_optional_minus_compose_dist(mesh):
    db = _anti_db()
    q = """PREFIX ex: <http://example.org/>
    SELECT ?e ?s ?y WHERE {
        ?e ex:salary ?s
        { ?e ex:worksAt <http://example.org/org0> }
        UNION { ?e ex:worksAt <http://example.org/org3> }
        OPTIONAL { ?e ex:knows ?y }
        MINUS { ?e ex:worksAt <http://example.org/org3> }
    }"""
    host = execute_query_volcano(q, db)
    dist = execute_query_distributed(q, db, mesh)
    assert len(host) > 0
    assert dist == host


@pytest.mark.slow
def test_dist_clause_fuzz(mesh):
    """Random BGP + subquery/union/optional/minus tails: distributed vs
    host, exercising clause composition over the mesh."""
    import random

    rng = random.Random(20260735)
    db = SparqlDatabase()
    lines = []
    preds = [f"<http://d.e/p{k}>" for k in range(4)]
    for i in range(400):
        s = f"<http://d.e/s{rng.randrange(50)}>"
        pr = rng.choice(preds)
        if rng.random() < 0.5:
            o = f"<http://d.e/s{rng.randrange(50)}>"
        else:
            o = f'"{rng.randrange(0, 3000)}"'
        lines.append(f"{s} {pr} {o} .")
    db.parse_ntriples("\n".join(lines))
    db.execution_mode = "host"

    vars_pool = ["?a", "?b", "?c"]
    skipped = 0
    for trial in range(18):
        pats, used = [], []
        for _ in range(rng.randrange(1, 3)):
            s = (
                rng.choice(used)
                if used and rng.random() < 0.8
                else rng.choice(vars_pool)
            )
            o = rng.choice(vars_pool + [f"<http://d.e/s{rng.randrange(50)}>"])
            pats.append(f"{s} {rng.choice(preds)} {o} .")
            for t in (s, o):
                if t.startswith("?") and t not in used:
                    used.append(t)
        share = rng.choice(used)
        clauses = []
        bound_out = set(used)
        kind = rng.randrange(4)
        if kind == 0:
            clauses.append(
                f"{{ SELECT {share} WHERE {{ {share} {rng.choice(preds)} ?u . "
                f"FILTER(?u > {rng.randrange(0, 3000)}) }} }}"
            )
        elif kind == 1:
            clauses.append(
                f"{{ {share} {rng.choice(preds)} "
                f"<http://d.e/s{rng.randrange(50)}> }} UNION "
                f"{{ {share} {rng.choice(preds)} ?u }}"
            )
            bound_out.add("?u")
        elif kind == 2:
            clauses.append(f"OPTIONAL {{ {share} {rng.choice(preds)} ?v }}")
            bound_out.add("?v")
        else:
            clauses.append(
                f"MINUS {{ {share} {rng.choice(preds)} "
                f"<http://d.e/s{rng.randrange(50)}> }}"
            )
        sel = " ".join(sorted(bound_out))
        q = f"SELECT {sel} WHERE {{ {' '.join(pats)} {' '.join(clauses)} }}"
        host = execute_query_volcano(q, db)
        try:
            dist = execute_query_distributed(q, db, mesh)
        except Unsupported:
            skipped += 1  # e.g. predicate-position-only join keys
            continue
        assert dist == host, (trial, q, len(dist), len(host))
    assert skipped < 12  # the mesh path must serve most shapes


def test_topk_on_optional_var_dist(mesh):
    # ORDER BY a variable that is UNBOUND on some rows (bound only in the
    # OPTIONAL branch): the mesh top-k must agree with the host ordering
    db = _anti_db()
    q = """PREFIX ex: <http://example.org/>
    SELECT ?e ?s WHERE {
        ?e ex:worksAt ?o .
        OPTIONAL { ?e ex:salary ?s . FILTER(?s > 64000) }
    } ORDER BY DESC(?s) LIMIT 9"""
    host = execute_query_volcano(q, db)
    dist = execute_query_distributed(q, db, mesh)
    assert len(host) == 9
    # documented top-k contract: the key SEQUENCE matches the host order;
    # rows tied at the boundary may keep a different (valid) representative
    assert [r[1] for r in dist] == [r[1] for r in host]
    full = {
        tuple(r)
        for r in execute_query_volcano(q.split(" LIMIT")[0], db)
    }
    assert all(tuple(r) in full for r in dist)


def test_aggregate_over_clauses_dist(mesh):
    db = _anti_db()
    q = """PREFIX ex: <http://example.org/>
    SELECT ?o (COUNT(?y) AS ?c) WHERE {
        ?e ex:worksAt ?o .
        OPTIONAL { ?e ex:knows ?y }
        MINUS { ?e ex:salary ?s . FILTER(?s > 66000) }
    } GROUP BY ?o"""
    host = execute_query_volcano(q, db)
    dist = execute_query_distributed(q, db, mesh)
    assert len(host) == 9
    assert dist == host


def test_calibration_covers_branch_pipelines(mesh):
    """ADVICE r4 (low): _calibrate_caps must size the static buffers from
    the clause-branch pipelines too, not just the main premise chain —
    a branch-heavy query would otherwise overflow on first dispatch and
    pay recompiles at doubled caps."""
    db = SparqlDatabase()
    lines = []
    for i in range(100):
        e = f"<http://example.org/e{i}>"
        lines.append(f"{e} <http://example.org/p1> <http://example.org/a{i}> .")
        for j in range(100):  # OPTIONAL branch: 100x the main chain
            lines.append(
                f"{e} <http://example.org/p2> <http://example.org/b{j}> ."
            )
    db.parse_ntriples("\n".join(lines))
    db.execution_mode = "host"
    q = """PREFIX ex: <http://example.org/>
    SELECT ?e ?a ?b WHERE {
        ?e ex:p1 ?a .
        OPTIONAL { ?e ex:p2 ?b }
    }"""
    ex = DistQueryExecutor(mesh, db, q)
    # branch table = 10_000 rows; OPTIONAL output grows to matches + left.
    # Main-chain-only calibration would give the 4*100/8-row floor (256).
    assert ex.join_cap >= 4 * 10_000 // 8
    dist = ex.run()
    host = execute_query_volcano(q, db)
    assert dist == host
