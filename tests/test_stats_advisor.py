"""StatsAdvisor: the feedback-driven optimizer (ISSUE 19).

Covers the acceptance surface end to end:

- mode gating: default ``off`` is bitwise-inert; the mode participates
  in the template fingerprint so an env flip never replays the other
  mode's plan;
- row identity: advisor-on and advisor-off return identical rows on the
  host, device, interpreter, WCOJ and sharded paths, across mutation
  churn;
- the drift loop: the cold→learned contradiction bumps the plan
  generation, the executor replans exactly once, and repeated warm runs
  do NOT ping-pong;
- the q9 routing flip: WCOJ's AGM-routed plan loses to the measured
  binary-join alternative once the advisor has observed the template,
  and the flip survives a restart through the prewarm manifest;
- manifest durability: round-trip, plus corrupted/truncated advisor
  sections degrading to the static AGM model instead of raising.
"""

import json
import os
import sys
from pathlib import Path

import pytest

from kolibrie_tpu.optimizer import stats_advisor as sa
from kolibrie_tpu.optimizer.stats_advisor import (
    stats_advisor,
    stats_advisor_mode,
    subset_key,
)
from kolibrie_tpu.query import compile_cache
from kolibrie_tpu.query.engine import QueryEngine
from kolibrie_tpu.query.executor import (
    execute_queries_batched,
    execute_query_volcano,
    plan_cache_info,
)
from kolibrie_tpu.query.parser import parse_combined_query
from kolibrie_tpu.query.sparql_database import SparqlDatabase
from kolibrie_tpu.query.template import fingerprint_query

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "examples"))
import lubm  # noqa: E402

PREFIX = "PREFIX ub: <http://swat.cse.lehigh.edu/onto/univ-bench.owl#>\n"
UB = "http://swat.cse.lehigh.edu/onto/univ-bench.owl#"
JOIN_Q = (
    PREFIX
    + "SELECT ?x ?c WHERE { ?x ub:worksFor ?d . ?x ub:teacherOf ?c . }"
)
DEPTS_Q = PREFIX + "SELECT DISTINCT ?d WHERE { ?x ub:worksFor ?d . }"
TEMPLATE = (
    PREFIX
    + "SELECT ?x ?c WHERE {{ ?x ub:worksFor <{dept}> . ?x ub:teacherOf ?c . }}"
)


@pytest.fixture(autouse=True)
def _fresh_advisor():
    stats_advisor.reset()
    yield
    stats_advisor.reset()


def _db(n_univ=1):
    db = SparqlDatabase()
    s, p, o = lubm.generate_fast(n_univ, db.dictionary)
    db.store.add_batch(s, p, o)
    db.store.compact()
    return db


def _rows(q, db):
    return sorted(map(tuple, execute_query_volcano(q, db)))


def _churn(db, i):
    """One meaningful mutation batch: a fresh professor who worksFor an
    existing department and teaches a fresh course — grows JOIN_Q's
    result on the next run."""
    dept = execute_query_volcano(DEPTS_Q, db)[0][0]
    prof = f"http://churn.example/prof{i}"
    db.add_triple_parts(f"<{prof}>", f"<{UB}worksFor>", f"<{dept}>")
    db.add_triple_parts(
        f"<{prof}>", f"<{UB}teacherOf>", f"<http://churn.example/course{i}>"
    )


# ------------------------------------------------------------ mode gating


def test_mode_default_off(monkeypatch):
    monkeypatch.delenv("KOLIBRIE_STATS_ADVISOR", raising=False)
    assert stats_advisor_mode() == "off"
    monkeypatch.setenv("KOLIBRIE_STATS_ADVISOR", "auto")
    assert stats_advisor_mode() == "auto"
    monkeypatch.setenv("KOLIBRIE_STATS_ADVISOR", "bogus")
    assert stats_advisor_mode() == "off"
    with sa.override_mode("off"):
        monkeypatch.setenv("KOLIBRIE_STATS_ADVISOR", "auto")
        assert stats_advisor_mode() == "off"  # thread-local wins


def test_off_mode_is_inert():
    with sa.override_mode("off"):
        stats_advisor.observe("fp", {"result": 1000.0}, version=(1, 0))
        stats_advisor.record_estimates("fp", {"result": 10.0}, source="agm")
    with sa.override_mode("auto"):
        # nothing was stored while off — no entry, no gen, no view
        assert stats_advisor.view("fp") is None
        assert stats_advisor.plan_gen("fp") == 0


def test_mode_participates_in_template_fingerprint():
    db = SparqlDatabase()
    cq = parse_combined_query(JOIN_Q, db.prefixes)
    with sa.override_mode("off"):
        fp_off, _ = fingerprint_query(cq)
    with sa.override_mode("auto"):
        fp_auto, _ = fingerprint_query(cq)
    assert fp_off != fp_auto


def test_subset_key_is_order_insensitive():
    assert subset_key(["b|#|c", "a|#|b"]) == subset_key(["a|#|b", "b|#|c"])


# ---------------------------------------------------------- drift machine


def test_cold_to_learned_drift_bumps_generation_once():
    with sa.override_mode("auto"):
        fp = "t-drift"
        stats_advisor.record_estimates(fp, {"result": 10.0}, source="agm")
        stats_advisor.observe(fp, {"result": 1000.0}, version=(1, 0))
        g1 = stats_advisor.plan_gen(fp)
        assert g1 == 1  # cold→learned contradiction evaluates immediately
        # the executor has not replanned yet (est_gen behind gen): more
        # observations at any version must NOT bump again
        stats_advisor.observe(fp, {"result": 1000.0}, version=(2, 0))
        assert stats_advisor.plan_gen(fp) == g1
        # replan re-records estimates at the new generation from the
        # learned values — the loop converges
        stats_advisor.record_estimates(
            fp, {"result": 1000.0}, source="learned"
        )
        stats_advisor.observe(fp, {"result": 1000.0}, version=(3, 0))
        assert stats_advisor.plan_gen(fp) == g1
        assert stats_advisor.report(fp)["drift"] == "stable"


def test_drift_needs_min_rows_and_xoff():
    with sa.override_mode("auto"):
        fp = "t-small"
        # 4x off but under the 64-row floor: planning noise, not drift
        stats_advisor.record_estimates(fp, {"result": 2.0}, source="agm")
        stats_advisor.observe(fp, {"result": 32.0}, version=(1, 0))
        assert stats_advisor.plan_gen(fp) == 0
        fp2 = "t-close"
        # big but within 4x: stable
        stats_advisor.record_estimates(fp2, {"result": 600.0}, source="agm")
        stats_advisor.observe(fp2, {"result": 1000.0}, version=(1, 0))
        assert stats_advisor.plan_gen(fp2) == 0
        assert stats_advisor.report(fp2)["drift"] == "stable"


def test_learned_drift_only_reevaluates_on_version_boundary():
    with sa.override_mode("auto"):
        fp = "t-boundary"
        stats_advisor.record_estimates(fp, {"result": 100.0}, source="agm")
        stats_advisor.observe(fp, {"result": 100.0}, version=(1, 0))
        assert stats_advisor.report(fp)["drift"] == "stable"
        # same store version: a 10x swing is buffered until churn lands
        stats_advisor.observe(fp, {"result": 1000.0}, version=(1, 0))
        assert stats_advisor.plan_gen(fp) == 0
        # the version boundary re-evaluates and catches it
        stats_advisor.observe(fp, {"result": 1000.0}, version=(1, 1))
        assert stats_advisor.plan_gen(fp) == 1


# ------------------------------------------- row identity across paths


@pytest.mark.parametrize("path", ["host", "device", "interp"])
def test_row_identity_under_churn(path):
    db = _db(1)
    db.execution_mode = "host" if path == "host" else "device"
    from contextlib import nullcontext

    from kolibrie_tpu.optimizer.plan_interp import (
        override_mode as interp_override,
    )

    interp_ctx = (
        interp_override("force") if path == "interp" else nullcontext()
    )
    queries = [JOIN_Q] if path == "interp" else [JOIN_Q, lubm.LUBM_Q2]
    with interp_ctx:
        baseline = len(_rows(JOIN_Q, db))
        for rnd in range(3):
            for q in queries:
                with sa.override_mode("off"):
                    off = _rows(q, db)
                with sa.override_mode("auto"):
                    on = _rows(q, db)
                    # and again: the advisor may have replanned between
                    # these two runs — rows must not move
                    on2 = _rows(q, db)
                assert on == off, f"{path} round {rnd}: {q[:60]}"
                assert on2 == off
            _churn(db, rnd)
        # churn actually did something: the result set grew
        assert len(_rows(JOIN_Q, db)) > baseline


def test_row_identity_wcoj_path(monkeypatch):
    monkeypatch.setenv("KOLIBRIE_WCOJ", "auto")
    db = _db(1)
    db.execution_mode = "device"
    for rnd in range(2):
        with sa.override_mode("off"):
            off = _rows(lubm.LUBM_Q9, db)
        with sa.override_mode("auto"):
            assert _rows(lubm.LUBM_Q9, db) == off
            assert _rows(lubm.LUBM_Q9, db) == off  # post-replan
        _churn(db, 100 + rnd)


def test_row_identity_sharded(mesh8):
    from kolibrie_tpu.parallel.sharded_serving import attach_sharded

    db = _db(2)
    db.execution_mode = "host"
    sh = attach_sharded(db, mesh8)
    sh.refresh()
    deps = execute_query_volcano(DEPTS_Q, db)
    texts = [TEMPLATE.format(dept=d[0]) for d in deps[:4]]
    with sa.override_mode("off"):
        off = execute_queries_batched(db, texts)
    with sa.override_mode("auto"):
        on = execute_queries_batched(db, texts)
    assert on == off


# ------------------------------------------------- the q9 routing flip


def test_q9_drift_replan_fires_and_converges():
    db = _db(4)
    db.execution_mode = "device"
    with sa.override_mode("auto"):
        r1 = _rows(lubm.LUBM_Q9, db)
        r2 = _rows(lubm.LUBM_Q9, db)  # generation bump lands here
        assert r2 == r1
        info = plan_cache_info(db)
        assert info["advisor_replans"] >= 1
        replans = stats_advisor.stats()["replans_total"]
        # converged: repeated warm runs keep the plan and the rows
        for _ in range(4):
            assert _rows(lubm.LUBM_Q9, db) == r1
        assert stats_advisor.stats()["replans_total"] == replans
        # ... and the replanned route is the measured binary join, not
        # the AGM-routed WCOJ
        exp = QueryEngine(db).explain_device(lubm.LUBM_Q9)
        assert "wcoj elim=" not in exp
    with sa.override_mode("off"):
        # advisor off: same store, untouched static routing
        exp_off = QueryEngine(db).explain_device(lubm.LUBM_Q9)
        assert "wcoj elim=" in exp_off
        assert _rows(lubm.LUBM_Q9, db) == r1


def test_restart_with_manifest_routes_q9_on_first_plan(tmp_path):
    root = str(tmp_path)
    db = _db(4)
    db.execution_mode = "device"
    with sa.override_mode("auto"):
        execute_query_volcano(lubm.LUBM_Q9, db)
        execute_query_volcano(lubm.LUBM_Q9, db)
        assert "wcoj elim=" not in QueryEngine(db).explain_device(
            lubm.LUBM_Q9
        )
        compile_cache.save_manifest(root)

        # cold process without the manifest: first plan is AGM → WCOJ
        stats_advisor.reset()
        db_cold = _db(4)
        db_cold.execution_mode = "device"
        assert "wcoj elim=" in QueryEngine(db_cold).explain_device(
            lubm.LUBM_Q9, exact_counts=False
        )

        # restarted process WITH the manifest: tuned routing on the
        # very first plan — no relearning execution needed
        stats_advisor.reset()
        assert compile_cache.load_advisor_state(root) >= 1
        db_warm = _db(4)
        db_warm.execution_mode = "device"
        assert "wcoj elim=" not in QueryEngine(db_warm).explain_device(
            lubm.LUBM_Q9, exact_counts=False
        )


# -------------------------------------------------- manifest durability


def test_manifest_roundtrip(tmp_path):
    root = str(tmp_path)
    with sa.override_mode("auto"):
        stats_advisor.record_estimates(
            "fp-rt", {"result": 10.0}, source="agm"
        )
        stats_advisor.observe(
            "fp-rt",
            {"result": 640.0, "scan:?x|#|?y": 640.0},
            version=(1, 0),
        )
        assert compile_cache.save_manifest(root) is not None
        stats_advisor.reset()
        assert stats_advisor.view("fp-rt") is None
        assert compile_cache.load_advisor_state(root) == 1
        view = stats_advisor.view("fp-rt")
        assert view == {"result": 640.0, "scan:?x|#|?y": 640.0}
        # imported estimates are dropped — the restarted process replans
        # from actuals and records its own
        rep = stats_advisor.report("fp-rt")
        assert rep["ops"]["result"][0] is None
        assert rep["drift"] == "stable"


def test_manifest_corrupt_advisor_section_degrades_to_agm(tmp_path):
    root = str(tmp_path)
    path = os.path.join(root, "prewarm_manifest.json")

    def reload_with(section):
        stats_advisor.reset()
        with open(path, "w") as f:
            json.dump(
                {"version": 1, "templates": [], "stats_advisor": section}, f
            )
        return compile_cache.load_advisor_state(root)

    with sa.override_mode("auto"):
        # section entirely the wrong type
        assert reload_with("garbage") == 0
        assert reload_with([1, 2, 3]) == 0
        # entry-level garbage is skipped, valid siblings still import
        n = reload_with(
            {
                "version": 1,
                "templates": {
                    "fp-bad": "not-a-dict",
                    "fp-noops": {"ops": 7},
                    "fp-badrec": {"ops": {"result": {"actual": "NaNish"}}},
                    "fp-ok": {"ops": {"result": {"actual": 99.0, "n": 3}}},
                },
            }
        )
        assert n == 1
        assert stats_advisor.view("fp-ok") == {"result": 99.0}
        assert stats_advisor.view("fp-bad") is None

        # truncated file: JSON parse fails, loader returns 0, no raise
        stats_advisor.reset()
        payload = json.dumps(
            {"version": 1, "templates": [], "stats_advisor": {}}
        )
        with open(path, "w") as f:
            f.write(payload[: len(payload) // 2])
        assert compile_cache.load_advisor_state(root) == 0
        assert compile_cache.load_manifest(root) == []


# ------------------------------------------------------- stats surface


def test_stats_block_shape():
    with sa.override_mode("auto"):
        stats_advisor.record_estimates(
            "fp-s", {"result": 10.0}, source="agm"
        )
        stats_advisor.observe("fp-s", {"result": 1000.0}, version=(1, 0))
        s = stats_advisor.stats()
    assert s["observations"] == 1
    assert s["drift_detections"] == 1
    ent = s["templates"]["fp-s"]
    assert ent["keys"] == 1
    assert ent["gen"] == 1
    assert ent["drift"] == "drifted"
    assert ent["source"] == "agm"


def test_explain_analyze_drift_column_and_advisor_line():
    db = _db(1)
    db.execution_mode = "device"
    eng = QueryEngine(db)
    with sa.override_mode("off"):
        out = eng.explain_device(JOIN_Q, analyze=True)
        assert "advisor: off" in out
        assert "x-off=" not in out
    with sa.override_mode("auto"):
        first = eng.explain_device(JOIN_Q, analyze=True)
        assert "advisor: source=" in first
        # the first analyze feeds the advisor; the second renders the
        # per-operator drift column against it
        second = eng.explain_device(JOIN_Q, analyze=True)
        assert "est=" in second and "x-off=" in second
        assert "advisor: source=learned" in second
