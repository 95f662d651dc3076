"""End-to-end query execution tests: BGP joins, filters, aggregates, BIND,
VALUES, subqueries, INSERT/DELETE, RDF-star, optional/union/minus.

Parity targets: kolibrie/tests/integration_test.rs + rdf_star_test.rs and the
legacy-vs-volcano agreement pattern (SURVEY §4).
"""

import pytest

from kolibrie_tpu.query.executor import execute_query, execute_query_volcano
from kolibrie_tpu.query.sparql_database import SparqlDatabase

EX = "http://example.org/"

EMPLOYEE_TTL = """
@prefix ex: <http://example.org/> .
ex:alice a ex:Employee ; ex:name "Alice" ; ex:age 30 ; ex:dept ex:Sales ; ex:salary 50000 .
ex:bob a ex:Employee ; ex:name "Bob" ; ex:age 25 ; ex:dept ex:Sales ; ex:salary 40000 .
ex:carol a ex:Employee ; ex:name "Carol" ; ex:age 35 ; ex:dept ex:Engineering ; ex:salary 70000 .
ex:dave a ex:Employee ; ex:name "Dave" ; ex:age 28 ; ex:dept ex:Engineering ; ex:salary 60000 .
ex:eve a ex:Manager ; ex:name "Eve" ; ex:age 45 ; ex:dept ex:Engineering ; ex:salary 90000 .
ex:Sales ex:label "Sales Department" .
ex:Engineering ex:label "Engineering Department" .
"""


@pytest.fixture
def db():
    d = SparqlDatabase()
    d.parse_turtle(EMPLOYEE_TTL)
    return d


class TestBasicSelect:
    def test_single_pattern(self, db):
        rows = execute_query_volcano(
            "PREFIX ex: <http://example.org/> SELECT ?n WHERE { ?x ex:name ?n }", db
        )
        assert sorted(r[0] for r in rows) == ["Alice", "Bob", "Carol", "Dave", "Eve"]

    def test_bgp_join(self, db):
        rows = execute_query_volcano(
            """PREFIX ex: <http://example.org/>
            SELECT ?n ?d WHERE { ?x ex:name ?n . ?x ex:dept ?d }""",
            db,
        )
        assert ["Carol", EX + "Engineering"] in rows
        assert len(rows) == 5

    def test_filter_numeric(self, db):
        rows = execute_query_volcano(
            """PREFIX ex: <http://example.org/>
            SELECT ?n WHERE { ?x ex:name ?n . ?x ex:age ?a . FILTER (?a > 28) }""",
            db,
        )
        assert sorted(r[0] for r in rows) == ["Alice", "Carol", "Eve"]

    def test_filter_logical(self, db):
        rows = execute_query_volcano(
            """PREFIX ex: <http://example.org/>
            SELECT ?n WHERE { ?x ex:name ?n . ?x ex:age ?a .
              FILTER (?a > 28 && ?a < 40) }""",
            db,
        )
        assert sorted(r[0] for r in rows) == ["Alice", "Carol"]

    def test_filter_equality_on_terms(self, db):
        rows = execute_query_volcano(
            """PREFIX ex: <http://example.org/>
            SELECT ?n WHERE { ?x ex:name ?n . ?x ex:dept ?d . FILTER (?d = ex:Sales) }""",
            db,
        )
        assert sorted(r[0] for r in rows) == ["Alice", "Bob"]

    def test_three_pattern_join_type(self, db):
        rows = execute_query_volcano(
            """PREFIX ex: <http://example.org/>
            SELECT ?n WHERE {
              ?x a ex:Employee . ?x ex:name ?n . ?x ex:dept ex:Engineering }""",
            db,
        )
        assert sorted(r[0] for r in rows) == ["Carol", "Dave"]

    def test_limit_offset(self, db):
        rows = execute_query_volcano(
            """PREFIX ex: <http://example.org/>
            SELECT ?n WHERE { ?x ex:name ?n } ORDER BY ?n LIMIT 2 OFFSET 1""",
            db,
        )
        assert [r[0] for r in rows] == ["Bob", "Carol"]

    def test_select_star(self, db):
        rows = execute_query_volcano(
            "PREFIX ex: <http://example.org/> SELECT * WHERE { ?x ex:dept ?d }", db
        )
        assert len(rows) == 5 and len(rows[0]) == 2

    def test_distinct(self, db):
        rows = execute_query_volcano(
            "PREFIX ex: <http://example.org/> SELECT DISTINCT ?d WHERE { ?x ex:dept ?d }",
            db,
        )
        assert len(rows) == 2


class TestAggregates:
    def test_count_group_by(self, db):
        rows = execute_query_volcano(
            """PREFIX ex: <http://example.org/>
            SELECT ?d (COUNT(?x) AS ?n) WHERE { ?x ex:dept ?d } GROUP BY ?d""",
            db,
        )
        res = {r[0]: r[1] for r in rows}
        assert res[EX + "Engineering"] == "3"
        assert res[EX + "Sales"] == "2"

    def test_avg_sum_min_max(self, db):
        rows = execute_query_volcano(
            """PREFIX ex: <http://example.org/>
            SELECT ?d (AVG(?s) AS ?avg) (SUM(?s) AS ?sum) (MIN(?s) AS ?min) (MAX(?s) AS ?max)
            WHERE { ?x ex:dept ?d . ?x ex:salary ?s } GROUP BY ?d""",
            db,
        )
        res = {r[0]: r[1:] for r in rows}
        assert res[EX + "Sales"] == ["45000", "90000", "40000", "50000"]

    def test_count_no_group(self, db):
        rows = execute_query_volcano(
            "PREFIX ex: <http://example.org/> SELECT (COUNT(?x) AS ?n) WHERE { ?x a ex:Employee }",
            db,
        )
        assert rows == [["4"]]

    def test_order_by_aggregate(self, db):
        rows = execute_query_volcano(
            """PREFIX ex: <http://example.org/>
            SELECT ?d (COUNT(?x) AS ?n) WHERE { ?x ex:dept ?d }
            GROUP BY ?d ORDER BY DESC(?n)""",
            db,
        )
        assert rows[0][0] == EX + "Engineering"


class TestBindValues:
    def test_bind_arithmetic(self, db):
        rows = execute_query_volcano(
            """PREFIX ex: <http://example.org/>
            SELECT ?n ?a2 WHERE { ?x ex:name ?n . ?x ex:age ?a . BIND(?a * 2 AS ?a2) }""",
            db,
        )
        res = {r[0]: r[1] for r in rows}
        assert res["Alice"] == "60"

    def test_bind_concat(self, db):
        rows = execute_query_volcano(
            """PREFIX ex: <http://example.org/>
            SELECT ?greeting WHERE { ?x ex:name ?n . BIND(CONCAT("Hello, ", ?n) AS ?greeting) }""",
            db,
        )
        assert "Hello, Alice" in [r[0] for r in rows]

    def test_values(self, db):
        rows = execute_query_volcano(
            """PREFIX ex: <http://example.org/>
            SELECT ?n WHERE { VALUES ?x { ex:alice ex:bob } ?x ex:name ?n }""",
            db,
        )
        assert sorted(r[0] for r in rows) == ["Alice", "Bob"]

    def test_udf(self, db):
        db.register_udf("SHOUT", lambda s: (s or "").upper() + "!")
        rows = execute_query_volcano(
            """PREFIX ex: <http://example.org/>
            SELECT ?y WHERE { ?x ex:name ?n . BIND(SHOUT(?n) AS ?y) }""",
            db,
        )
        assert "ALICE!" in [r[0] for r in rows]


class TestSubqueryOptionalUnionMinus:
    def test_subquery(self, db):
        rows = execute_query_volcano(
            """PREFIX ex: <http://example.org/>
            SELECT ?n WHERE {
              ?x ex:name ?n .
              { SELECT ?x WHERE { ?x ex:dept ex:Sales } }
            }""",
            db,
        )
        assert sorted(r[0] for r in rows) == ["Alice", "Bob"]

    def test_optional(self, db):
        db.parse_turtle("@prefix ex: <http://example.org/> . ex:frank ex:name \"Frank\" .")
        rows = execute_query_volcano(
            """PREFIX ex: <http://example.org/>
            SELECT ?n ?d WHERE { ?x ex:name ?n OPTIONAL { ?x ex:dept ?d } }""",
            db,
        )
        res = {r[0]: r[1] for r in rows}
        assert res["Frank"] == ""
        assert res["Alice"] == EX + "Sales"

    def test_union(self, db):
        rows = execute_query_volcano(
            """PREFIX ex: <http://example.org/>
            SELECT ?x WHERE { { ?x a ex:Manager } UNION { ?x ex:dept ex:Sales } }""",
            db,
        )
        assert sorted(r[0] for r in rows) == [EX + "alice", EX + "bob", EX + "eve"]

    def test_minus(self, db):
        rows = execute_query_volcano(
            """PREFIX ex: <http://example.org/>
            SELECT ?x WHERE { ?x a ex:Employee MINUS { ?x ex:dept ex:Sales } }""",
            db,
        )
        assert sorted(r[0] for r in rows) == [EX + "carol", EX + "dave"]


class TestUpdates:
    def test_insert(self, db):
        execute_query_volcano(
            'PREFIX ex: <http://example.org/> INSERT DATA { ex:frank ex:name "Frank" . }',
            db,
        )
        rows = execute_query_volcano(
            "PREFIX ex: <http://example.org/> SELECT ?n WHERE { ex:frank ex:name ?n }", db
        )
        assert rows == [["Frank"]]

    def test_delete_data(self, db):
        execute_query_volcano(
            "PREFIX ex: <http://example.org/> DELETE DATA { ex:alice ex:dept ex:Sales . }",
            db,
        )
        rows = execute_query_volcano(
            "PREFIX ex: <http://example.org/> SELECT ?x WHERE { ?x ex:dept ex:Sales }", db
        )
        assert [r[0] for r in rows] == [EX + "bob"]

    def test_delete_where(self, db):
        execute_query_volcano(
            """PREFIX ex: <http://example.org/>
            DELETE { ?x ex:salary ?s } WHERE { ?x ex:salary ?s . FILTER(?s > 55000) }""",
            db,
        )
        rows = execute_query_volcano(
            "PREFIX ex: <http://example.org/> SELECT ?s WHERE { ?x ex:salary ?s }", db
        )
        assert sorted(r[0] for r in rows) == ["40000", "50000"]


class TestRdfStar:
    def test_quoted_pattern_query(self, db):
        db.parse_turtle(
            """@prefix ex: <http://example.org/> .
            << ex:alice ex:knows ex:bob >> ex:certainty "0.9" .
            << ex:bob ex:knows ex:carol >> ex:certainty "0.5" ."""
        )
        rows = execute_query_volcano(
            """PREFIX ex: <http://example.org/>
            SELECT ?s ?c WHERE { << ?s ex:knows ?o >> ex:certainty ?c . FILTER (?c > 0.7) }""",
            db,
        )
        assert rows == [[EX + "alice", "0.9"]]

    def test_triple_builtin(self, db):
        db.parse_turtle(
            """@prefix ex: <http://example.org/> .
            << ex:alice ex:knows ex:bob >> ex:certainty "0.9" ."""
        )
        rows = execute_query_volcano(
            """PREFIX ex: <http://example.org/>
            SELECT ?sub WHERE {
              << ?s ex:knows ?o >> ex:certainty ?c .
              BIND(TRIPLE(?s, ex:knows, ?o) AS ?t) .
              BIND(SUBJECT(?t) AS ?sub)
            }""",
            db,
        )
        assert rows == [[EX + "alice"]]

    def test_istriple_filter(self, db):
        db.parse_turtle(
            """@prefix ex: <http://example.org/> .
            << ex:a ex:b ex:c >> ex:p ex:o .
            ex:plain ex:p ex:o ."""
        )
        rows = execute_query_volcano(
            """PREFIX ex: <http://example.org/>
            SELECT ?s WHERE { ?s ex:p ex:o . FILTER (isTRIPLE(?s)) }""",
            db,
        )
        assert rows == [["<< " + EX + "a " + EX + "b " + EX + "c >>"]]


class TestAgreement:
    """Legacy naive path vs Volcano path must agree (SURVEY §4 pattern)."""

    QUERIES = [
        "PREFIX ex: <http://example.org/> SELECT ?n WHERE { ?x ex:name ?n }",
        """PREFIX ex: <http://example.org/>
           SELECT ?n ?d WHERE { ?x ex:name ?n . ?x ex:dept ?d . ?x ex:age ?a . FILTER(?a < 40) }""",
        """PREFIX ex: <http://example.org/>
           SELECT ?d (COUNT(?x) AS ?n) WHERE { ?x ex:dept ?d } GROUP BY ?d""",
    ]

    def test_agreement(self, db):
        for q in self.QUERIES:
            naive = execute_query(q, db)
            volcano = execute_query_volcano(q, db)
            assert sorted(map(tuple, naive)) == sorted(map(tuple, volcano)), q


class TestDatabaseStats:
    """Sampled stats + per-predicate join-selectivity cache
    (database_stats.rs:43-193 parity)."""

    def test_sampling_scales_counts(self):
        import numpy as np

        from kolibrie_tpu.optimizer.stats import SAMPLE_CAP, DatabaseStats
        from kolibrie_tpu.query.sparql_database import SparqlDatabase

        db = SparqlDatabase()
        n = SAMPLE_CAP * 2  # force the sampling path
        s = np.arange(n, dtype=np.uint32) % 1000
        p = np.full(n, 7, dtype=np.uint32)
        o = np.arange(n, dtype=np.uint32)
        db.store.add_batch(s, p, o)
        st = DatabaseStats.gather_stats_fast(db)
        assert st.total_triples == n
        # scaled-up predicate count lands near the true total
        assert abs(st.predicate_counts[7] - n) / n < 0.01

    @pytest.mark.parametrize("k", [2, 3, 4])
    def test_predicates_are_counted_over_every_row(self, k):
        """ISSUE 40: every subject carries the same k predicates, k a divisor
        of the sampling step, so a step sample of the subject-sorted rows
        meets one of them every time and the others never (WatDiv's purchases:
        ``purchaseDate`` 1 row, ``purchaseFor`` twice its own): predicates
        are counted over all rows; subjects and objects stay sampled."""
        import numpy as np

        from kolibrie_tpu.optimizer.stats import SAMPLE_CAP, DatabaseStats
        from kolibrie_tpu.query.sparql_database import SparqlDatabase

        n_subjects = SAMPLE_CAP * 12 // k
        s = np.repeat(np.arange(n_subjects, dtype=np.uint32), k)
        p = np.tile(np.arange(100, 100 + k, dtype=np.uint32), n_subjects)
        o = np.arange(len(s), dtype=np.uint32)
        assert (len(s) // SAMPLE_CAP) % k == 0  # the step the sample takes
        db = SparqlDatabase()
        db.store.add_batch(s, p, o)
        st = DatabaseStats.gather_stats_fast(db)
        assert st.distinct_predicates == k
        assert st.predicate_counts == {100 + i: float(n_subjects) for i in range(k)}
        assert len(st.subject_counts) <= SAMPLE_CAP + 1  # still a sample

    def test_join_selectivity_cached_per_predicate(self):
        from kolibrie_tpu.optimizer.stats import DatabaseStats
        from kolibrie_tpu.query.sparql_database import SparqlDatabase

        db = SparqlDatabase()
        for i in range(80):
            db.store.add(i, 1, i + 1000)
        for i in range(20):
            db.store.add(i, 2, i + 2000)
        st = DatabaseStats.gather_stats_fast(db)
        assert st.get_join_selectivity(1) == 0.8
        assert st.get_join_selectivity(2) == 0.2
        assert st.join_selectivity_cache == {1: 0.8, 2: 0.2}
        # unseen predicate -> 0 matches sampled
        assert st.get_join_selectivity(999) == 0.0

    def test_incremental_update_remove(self):
        from kolibrie_tpu.optimizer.stats import DatabaseStats
        from kolibrie_tpu.query.sparql_database import SparqlDatabase

        db = SparqlDatabase()
        db.store.add(1, 2, 3)
        st = DatabaseStats.gather_stats_fast(db)
        st.get_join_selectivity(2)
        assert st.distinct_subjects == 1 and st.distinct_objects == 1
        st.update_stats(5, 2, 6)
        assert st.join_selectivity_cache == {}  # cache cleared
        assert st.total_triples == 2 and st.predicate_counts[2] == 2.0
        # distinct counts maintained too (the independence fallback uses them)
        assert st.distinct_subjects == 2 and st.distinct_objects == 2
        assert st.distinct_predicates == 1
        st.remove_stats(5, 2, 6)
        assert st.total_triples == 1 and st.predicate_counts[2] == 1.0
        assert st.distinct_subjects == 1 and st.distinct_objects == 1


class TestPlanCache:
    """Automatic plan cache on SparqlDatabase (round 5): repeat queries
    through the plain public API skip parse + Streamertail plan + device
    lowering; any store/prefix/UDF/mode change invalidates."""

    def _db(self, n=200):
        db = SparqlDatabase()
        lines = []
        for i in range(n):
            e = f"<http://e.x/e{i}>"
            lines.append(f"{e} <http://e.x/works> <http://e.x/c{i % 7}> .")
            lines.append(f'{e} <http://e.x/sal> "{1000 + i}" .')
        db.parse_ntriples("\n".join(lines))
        return db

    Q = (
        "SELECT ?e ?w ?s WHERE { ?e <http://e.x/works> ?w . "
        "?e <http://e.x/sal> ?s }"
    )

    @staticmethod
    def _slots(db, q):
        """Round-6 layout: parse entries carry the template fingerprint;
        the per-state plan/lowered slots live under the template cache."""
        fp = db.__dict__["_plan_cache"][q]["fp"]
        return db.__dict__["_template_cache"][fp]["by_state"]

    def test_repeat_query_reuses_plan_and_lowered(self):
        db = self._db()
        db.execution_mode = "device"
        r1 = execute_query_volcano(self.Q, db)
        ent = db.__dict__["_plan_cache"][self.Q]
        assert ent["cq"] is not None
        (slot,) = self._slots(db, self.Q).values()
        assert slot["plan"] is not None
        assert slot["lowered"] not in (None, False)
        lowered_obj = slot["lowered"]
        r2 = execute_query_volcano(self.Q, db)
        assert r2 == r1 and len(r1) == 200
        # same object still cached — the second run reused it
        (slot2,) = self._slots(db, self.Q).values()
        assert slot2["lowered"] is lowered_obj

    def test_aggregate_query_reuses_lowered(self):
        db = self._db()
        db.execution_mode = "device"
        q = (
            "SELECT ?w (COUNT(?e) AS ?n) WHERE "
            "{ ?e <http://e.x/works> ?w } GROUP BY ?w ORDER BY ?w"
        )
        r1 = execute_query_volcano(q, db)
        (slot,) = self._slots(db, q).values()
        assert slot["lowered"] not in (None, False)
        lowered_obj = slot["lowered"]
        r2 = execute_query_volcano(q, db)
        assert r2 == r1 and len(r1) == 7
        (slot2,) = self._slots(db, q).values()
        assert slot2["lowered"] is lowered_obj
        # mutation invalidates the slot but the answer stays correct
        db.parse_ntriples(
            "<http://e.x/zz> <http://e.x/works> <http://e.x/c0> ."
        )
        r3 = execute_query_volcano(q, db)
        assert r3 != r1 and len(r3) == 7

    def test_ordered_limit_query_reuses_lowered(self):
        db = self._db()
        db.execution_mode = "device"
        q = (
            "SELECT ?e ?s WHERE { ?e <http://e.x/sal> ?s } "
            "ORDER BY DESC(?s) LIMIT 5"
        )
        r1 = execute_query_volcano(q, db)
        (slot,) = self._slots(db, q).values()
        assert slot["lowered"] not in (None, False)
        lowered_obj = slot["lowered"]
        r2 = execute_query_volcano(q, db)
        assert r2 == r1 and len(r1) == 5
        assert r1[0][1] == "1199"  # top salary of the 200-employee db
        (slot2,) = self._slots(db, q).values()
        assert slot2["lowered"] is lowered_obj

    def test_ordered_replay_keeps_host_clause_postpasses(self):
        """Code-review r5: the ordered path must NOT replay a plain-BGP
        lowering (captured by the host fallback) for a clause-carrying
        WHERE — run 2 would silently drop the MINUS."""
        db = SparqlDatabase()
        lines = []
        for i in range(10):
            e = f"<http://e.x/e{i}>"
            lines.append(f'{e} <http://e.x/sal> "{1000 + i}" .')
            if i % 2 == 0:
                lines.append(f"{e} <http://e.x/flag> <http://e.x/y> .")
        db.parse_ntriples("\n".join(lines))
        db.execution_mode = "device"
        # the OPTIONAL inside MINUS keeps the branch un-fusable, so the
        # device path lowers only the plain BGP and the MINUS runs host-side
        q = (
            "SELECT ?e ?s WHERE { ?e <http://e.x/sal> ?s "
            "MINUS { ?e <http://e.x/flag> ?f "
            "OPTIONAL { ?f <http://e.x/nothing> ?z } } } "
            "ORDER BY DESC(?s) LIMIT 3"
        )
        r1 = execute_query_volcano(q, db)
        r2 = execute_query_volcano(q, db)
        assert r1 == r2
        assert [r[0] for r in r1] == [
            "http://e.x/e9",
            "http://e.x/e7",
            "http://e.x/e5",
        ]

    def test_aggregate_replay_keeps_host_clause_postpasses(self):
        """Code-review r5: the aggregate path must NOT replay a plain-BGP
        lowering through the fused aggregate pipeline when the WHERE
        carries clauses the first call applied host-side."""
        db = SparqlDatabase()
        db.parse_ntriples(
            "<http://e.x/a> <http://e.x/works> <http://e.x/c1> .\n"
            "<http://e.x/b> <http://e.x/works> <http://e.x/c1> .\n"
            "<http://e.x/c> <http://e.x/works> <http://e.x/c2> .\n"
            "<http://e.x/t1> <http://e.x/tag> <http://e.x/v> .\n"
            "<http://e.x/t2> <http://e.x/tag> <http://e.x/v> .\n"
        )
        db.execution_mode = "device"
        # OPTIONAL sharing no variable with the BGP: un-fusable → host
        # post-pass cross-product doubles every count
        q = (
            "SELECT ?w (COUNT(?e) AS ?n) WHERE { "
            "?e <http://e.x/works> ?w "
            "OPTIONAL { ?x <http://e.x/tag> ?t } } GROUP BY ?w ORDER BY ?w"
        )
        r1 = execute_query_volcano(q, db)
        r2 = execute_query_volcano(q, db)
        assert r1 == r2
        assert r1 == [["http://e.x/c1", "4"], ["http://e.x/c2", "2"]]

    def test_mode_flip_keeps_both_lowered_states(self):
        db = self._db()
        db.execution_mode = "device"
        dev1 = execute_query_volcano(self.Q, db)
        db.execution_mode = "host"
        execute_query_volcano(self.Q, db)
        db.execution_mode = "device"
        states = self._slots(db, self.Q)
        assert len(states) == 2  # device + host slots coexist
        dev_slot = next(
            s for (v, u, m, _sh), s in states.items() if m == "device"
        )
        lowered_obj = dev_slot["lowered"]
        assert lowered_obj not in (None, False)
        assert execute_query_volcano(self.Q, db) == dev1
        dev_slot2 = next(
            s
            for (v, u, m, _sh), s in self._slots(db, self.Q).items()
            if m == "device"
        )
        assert dev_slot2["lowered"] is lowered_obj  # flip did not evict

    def test_insert_keeps_parsed_ast(self):
        db = self._db()
        db.execution_mode = "host"
        execute_query_volcano(self.Q, db)
        cq = db.__dict__["_plan_cache"][self.Q]["cq"]
        db.parse_ntriples(
            "<http://e.x/eY> <http://e.x/works> <http://e.x/c2> .\n"
            '<http://e.x/eY> <http://e.x/sal> "5" .'
        )
        r = execute_query_volcano(self.Q, db)
        assert len(r) == 201
        # the store bump invalidated the plan slot but NOT the parse
        assert db.__dict__["_plan_cache"][self.Q]["cq"] is cq

    def test_store_mutation_invalidates(self):
        db = self._db()
        db.execution_mode = "host"
        r1 = execute_query_volcano(self.Q, db)
        db.parse_ntriples(
            "<http://e.x/eX> <http://e.x/works> <http://e.x/c0> .\n"
            '<http://e.x/eX> <http://e.x/sal> "99" .'
        )
        r2 = execute_query_volcano(self.Q, db)
        assert len(r2) == len(r1) + 1

    def test_update_queries_not_cached(self):
        db = self._db()
        ins = (
            'INSERT DATA { <http://e.x/n1> <http://e.x/works> '
            "<http://e.x/c1> }"
        )
        execute_query_volcano(ins, db)
        execute_query_volcano(ins, db)  # runs again, not replayed from cache
        rows = execute_query_volcano(
            "SELECT ?e WHERE { ?e <http://e.x/works> <http://e.x/c1> }", db
        )
        assert any(r == ["http://e.x/n1"] for r in rows)

    def test_mode_split(self):
        db = self._db()
        db.execution_mode = "host"
        host = execute_query_volcano(self.Q, db)
        db.execution_mode = "device"
        dev = execute_query_volcano(self.Q, db)
        assert dev == host

    def test_udf_reregistration_invalidates(self):
        db = self._db(5)
        db.register_udf("TAG", lambda s: f"v1:{s}")
        q = (
            "SELECT ?y WHERE { ?e <http://e.x/sal> ?s . "
            "BIND(TAG(?s) AS ?y) }"
        )
        r1 = execute_query_volcano(q, db)
        assert all(r[0].startswith("v1:") for r in r1)
        db.register_udf("TAG", lambda s: f"v2:{s}")
        r2 = execute_query_volcano(q, db)
        assert all(r[0].startswith("v2:") for r in r2)


class TestFormatDisplayCache:
    def test_sorted_rows_match_python_sort(self):
        import random

        import numpy as np

        from kolibrie_tpu.query.executor import (
            eval_select_to_table,
            format_results,
        )
        from kolibrie_tpu.query.parser import parse_sparql_query

        db = SparqlDatabase()
        rng = random.Random(7)
        lines = []
        for i in range(300):
            s = f"<http://z.x/s{rng.randrange(40)}>"
            o = (
                f'"{rng.randrange(50)}"'
                if rng.random() < 0.5
                else f"<http://z.x/o{rng.randrange(30)}>"
            )
            lines.append(f"{s} <http://z.x/p> {o} .")
        db.parse_ntriples("\n".join(lines))
        q = parse_sparql_query(
            "SELECT ?a ?b WHERE { ?a <http://z.x/p> ?b }", db.prefixes
        )
        table = eval_select_to_table(db, q)
        fast = format_results(db, table, q, sort_rows=True)
        slow = format_results(db, table, q)
        slow.sort()
        assert fast == slow

    def test_quoted_ids_take_recursive_path(self):
        from kolibrie_tpu.query.executor import execute_query_volcano as run

        db = SparqlDatabase()
        db.parse_ntriples(
            "<< <http://z.x/a> <http://z.x/p> <http://z.x/b> >> "
            "<http://z.x/saidBy> <http://z.x/carol> ."
        )
        rows = run(
            "SELECT ?t ?w WHERE { ?t <http://z.x/saidBy> ?w }", db
        )
        assert rows == [
            ["<< http://z.x/a http://z.x/p http://z.x/b >>", "http://z.x/carol"]
        ]

    def test_display_survives_checkpoint_restore(self, tmp_path):
        db = SparqlDatabase()
        db.parse_ntriples(
            '<http://z.x/a> <http://z.x/p> "hello" .'
        )
        path = str(tmp_path / "snap.npz")
        db.checkpoint(path)
        db2 = SparqlDatabase.from_checkpoint(path)
        rows = execute_query_volcano(
            "SELECT ?o WHERE { <http://z.x/a> <http://z.x/p> ?o }", db2
        )
        assert rows == [["hello"]]
        # regression (code-review r5): interning NEW terms after a restore
        # must not shift the restored IDs' display forms — the display
        # list is position-aligned and must be rebuilt at restore time
        db2.parse_ntriples(
            "<http://z.x/new> <http://z.x/p> <http://z.x/also_new> ."
        )
        rows = execute_query_volcano(
            "SELECT ?s ?o WHERE { ?s <http://z.x/p> ?o }", db2
        )
        assert rows == [
            ["http://z.x/a", "hello"],
            ["http://z.x/new", "http://z.x/also_new"],
        ]


def test_plan_cache_interleave_fuzz():
    """Randomized INSERT / SELECT / mode-flip / UDF interleavings: the
    cached-plan path must always return exactly what a cache-free database
    returns for the same history.  Exercises slot invalidation (store
    version bumps), per-mode slots, AST retention across mutations, and
    eviction (cache capped), with device mode in the mix."""
    import random

    from kolibrie_tpu.query import executor as ex

    rng = random.Random(20260733)
    queries = [
        "SELECT ?e ?w WHERE { ?e <http://f.z/works> ?w }",
        "SELECT ?e ?s WHERE { ?e <http://f.z/works> ?w . "
        "?e <http://f.z/sal> ?s }",
        "SELECT DISTINCT ?w WHERE { ?e <http://f.z/works> ?w } ORDER BY ?w",
        "SELECT ?w (COUNT(?e) AS ?n) WHERE { ?e <http://f.z/works> ?w } "
        "GROUP BY ?w ORDER BY ?w",
        "SELECT ?e ?s WHERE { ?e <http://f.z/sal> ?s FILTER(?s > 1050) }",
        "SELECT ?y WHERE { ?e <http://f.z/sal> ?s . BIND(TAG(?s) AS ?y) }",
        # clause shapes: fusable MINUS, un-fusable MINUS (nested OPTIONAL),
        # and an un-fusable OPTIONAL under aggregation — the cache must
        # never replay a plain-BGP lowering past host clause post-passes
        "SELECT ?e ?s WHERE { ?e <http://f.z/sal> ?s "
        "MINUS { ?e <http://f.z/works> <http://f.z/c0> } } ORDER BY ?s "
        "LIMIT 4",
        "SELECT ?e ?s WHERE { ?e <http://f.z/sal> ?s "
        "MINUS { ?e <http://f.z/works> ?w "
        "OPTIONAL { ?w <http://f.z/none> ?z } } } ORDER BY DESC(?s) LIMIT 3",
        "SELECT ?w (COUNT(?e) AS ?n) WHERE { ?e <http://f.z/works> ?w "
        "OPTIONAL { ?x <http://f.z/sal> ?t } } GROUP BY ?w ORDER BY ?w",
    ]

    def apply(db, kind, payload, outs):
        if kind == "insert":
            db.parse_ntriples(payload)
        elif kind == "mode":
            db.execution_mode = payload
        elif kind == "udf":
            db.register_udf("TAG", lambda s, v=payload: f"v{v}:{s}")
        else:
            outs.append(execute_query_volcano(payload, db))

    def fresh(history):
        """Replay a history on a brand-new db with the cache DISABLED
        (entry lookups bypassed by clearing after every call)."""
        db = SparqlDatabase()
        db.register_udf("TAG", lambda s: f"v0:{s}")
        outs: list = []
        for kind, payload in history:
            apply(db, kind, payload, outs)
            db.__dict__.pop("_plan_cache", None)  # never reuse
        return outs

    # cap the cache at 3 entries so the 6-query rotation also exercises
    # LRU eviction, not just hits
    cap0 = ex._PLAN_CACHE_MAX
    ex._PLAN_CACHE_MAX = 3
    try:
        for trial in range(6):
            history = []
            n_tr = 0
            n_udf = 0
            db = SparqlDatabase()
            db.register_udf("TAG", lambda s: f"v0:{s}")
            cached_outs: list = []
            for step in range(rng.randrange(10, 18)):
                r = rng.random()
                if r < 0.22:
                    lines = []
                    for _ in range(rng.randrange(1, 5)):
                        e = f"<http://f.z/e{n_tr}>"
                        lines.append(
                            f"{e} <http://f.z/works> <http://f.z/c{n_tr % 3}> ."
                        )
                        lines.append(
                            f'{e} <http://f.z/sal> "{1000 + n_tr}" .'
                        )
                        n_tr += 1
                    step_ = ("insert", "\n".join(lines))
                elif r < 0.34:
                    step_ = ("mode", rng.choice(["host", "device"]))
                elif r < 0.42:
                    # re-register the UDF with new semantics: cached plans
                    # whose filters/binds bound v(n) must not serve v(n+1)
                    n_udf += 1
                    step_ = ("udf", n_udf)
                else:
                    step_ = ("query", rng.choice(queries))
                history.append(step_)
                apply(db, *step_, cached_outs)
            assert cached_outs == fresh(history), (trial, history)
    finally:
        ex._PLAN_CACHE_MAX = cap0
