"""Plan-bytecode interpreter: row agreement with the host oracle, the
zero-per-template-compile property, size-class executable sharing,
mode fingerprinting, eligibility fallthrough, and the breaker-epoch
expiry of sticky failure sentinels (the satellite to KOLIBRIE_PLAN_INTERP
routing).

The load-bearing property: under ``KOLIBRIE_PLAN_INTERP=force`` a stream
of NEW template shapes must grow only the interpreter's jit cache (one
entry per size class), never ``_run_plan``'s (one entry per template).
"""

import numpy as np
import pytest

import kolibrie_tpu.optimizer.device_engine as de
import kolibrie_tpu.optimizer.plan_interp as pi
from kolibrie_tpu.query.executor import (
    execute_query_volcano,
    plan_cache_info,
)
from kolibrie_tpu.query.sparql_database import SparqlDatabase

PREFIXES = "PREFIX ex: <http://example.org/>\n"


def people_db(n=240) -> SparqlDatabase:
    db = SparqlDatabase()
    lines = []
    for i in range(n):
        e = f"<http://example.org/e{i}>"
        lines.append(f'{e} <http://example.org/dept> "dept{i % 5}" .')
        lines.append(f'{e} <http://example.org/salary> "{20 + (i % 50)}" .')
        lines.append(f'{e} <http://example.org/grade> "{i % 9}" .')
        lines.append(
            f"{e} <http://example.org/site> <http://site{i % 7}.example/> ."
        )
    db.parse_ntriples("\n".join(lines))
    db.execution_mode = "device"
    return db


def host_rows(db, q):
    mode = db.execution_mode
    db.execution_mode = "host"
    try:
        return execute_query_volcano(q, db)
    finally:
        db.execution_mode = mode


def assert_rows_match(db, q):
    got = execute_query_volcano(q, db)
    want = host_rows(db, q)
    assert sorted(map(tuple, got)) == sorted(map(tuple, want)), q


SHAPES = [
    # scan only
    'SELECT ?e WHERE { ?e ex:dept "dept3" }',
    # one join, projected both sides
    'SELECT ?e ?s WHERE { ?e ex:dept "dept2" . ?e ex:salary ?s }',
    # join + numeric-const filter
    'SELECT ?e ?s WHERE { ?e ex:dept "dept2" . ?e ex:salary ?s . '
    "FILTER(?s > 30) }",
    # AND-chain of numeric filters
    "SELECT ?e ?s WHERE { ?e ex:salary ?s . "
    "FILTER(?s >= 25 && ?s < 40) }",
    # three-pattern chain with var-var numeric compare
    "SELECT ?e ?s ?g WHERE { ?e ex:salary ?s . ?e ex:grade ?g . "
    "FILTER(?g < ?s) }",
    # IRI-object scan + join
    "SELECT ?e ?s WHERE { ?e ex:site <http://site3.example/> . "
    "?e ex:salary ?s }",
]


@pytest.mark.parametrize("shape", SHAPES)
def test_force_rows_match_host(monkeypatch, shape):
    monkeypatch.setenv("KOLIBRIE_PLAN_INTERP", "force")
    db = people_db()
    assert_rows_match(db, PREFIXES + shape)
    assert plan_cache_info(db)["per_template"]
    (per,) = [
        v for v in plan_cache_info(db)["per_template"].values()
        if v["source"] is not None
    ]
    assert per["source"] == "interp"


def test_force_never_compiles_specialized(monkeypatch):
    """The headline property: new template shapes, zero _run_plan
    entries — the interpreter executable serves them all."""
    monkeypatch.setenv("KOLIBRIE_PLAN_INTERP", "force")
    db = people_db()
    before = de.device_compile_stats()
    for shape in SHAPES:
        execute_query_volcano(PREFIXES + shape, db)
    after = de.device_compile_stats()
    assert after["run_plan"] == before["run_plan"]
    assert after["run_plan_batch"] == before["run_plan_batch"]
    assert after["run_interp"] >= before["run_interp"]


def test_constant_variants_share_interp_executable(monkeypatch):
    """Same template, different constants: zero new interpreter entries
    after the first — constants ride the parameter vector here too."""
    monkeypatch.setenv("KOLIBRIE_PLAN_INTERP", "force")
    db = people_db()
    q = PREFIXES + (
        'SELECT ?e ?s WHERE { ?e ex:dept "dept0" . ?e ex:salary ?s . '
        "FILTER(?s > 21) }"
    )
    assert_rows_match(db, q)
    base = de.device_compile_stats()["run_interp"]
    for dept, sal in [("dept1", 25), ("dept2", 33), ("dept4", 60)]:
        v = PREFIXES + (
            f'SELECT ?e ?s WHERE {{ ?e ex:dept "{dept}" . '
            f"?e ex:salary ?s . FILTER(?s > {sal}) }}"
        )
        assert_rows_match(db, v)
    assert de.device_compile_stats()["run_interp"] == base


def test_mutations_visible_through_interp(monkeypatch):
    """Delta inserts and tombstoned deletes flow through the interpreter's
    two-segment merge exactly as through the specialized scan."""
    monkeypatch.setenv("KOLIBRIE_PLAN_INTERP", "force")
    db = people_db(60)
    q = PREFIXES + 'SELECT ?e ?s WHERE { ?e ex:dept "dept1" . ?e ex:salary ?s }'
    assert_rows_match(db, q)
    db.parse_ntriples(
        '<http://example.org/new1> <http://example.org/dept> "dept1" .\n'
        '<http://example.org/new1> <http://example.org/salary> "99" .'
    )
    assert_rows_match(db, q)
    t = db.add_triple_parts(
        "<http://example.org/e1>", "<http://example.org/dept>", '"dept1"'
    )
    db.delete_triple(t)
    assert_rows_match(db, q)


def test_mode_participates_in_fingerprint():
    from kolibrie_tpu.query.parser import parse_combined_query
    from kolibrie_tpu.query.template import fingerprint_query

    cq = parse_combined_query(
        PREFIXES + "SELECT ?s WHERE { ?s ex:p ?o }", {}
    )
    with pi.override_mode("off"):
        fp_off, _ = fingerprint_query(cq)
    with pi.override_mode("force"):
        fp_force, _ = fingerprint_query(cq)
    assert fp_off != fp_force


def test_ineligible_shape_falls_through(monkeypatch):
    """OPTIONAL is outside the op repertoire: force mode must decline and
    serve through the specialized path with identical rows."""
    monkeypatch.setenv("KOLIBRIE_PLAN_INTERP", "force")
    db = people_db(60)
    q = PREFIXES + (
        "SELECT ?e ?s ?g WHERE { ?e ex:salary ?s . "
        "OPTIONAL { ?e ex:grade ?g } }"
    )
    got = execute_query_volcano(q, db)
    want = host_rows(db, q)
    assert sorted(map(tuple, got)) == sorted(map(tuple, want))


def test_cell_budget_declines(monkeypatch):
    """A register file over the memory guard declines to the specialized
    path instead of allocating it."""
    monkeypatch.setenv("KOLIBRIE_PLAN_INTERP", "force")
    monkeypatch.setattr(pi, "_MAX_CELLS", 1)
    db = people_db(60)
    q = PREFIXES + 'SELECT ?e ?s WHERE { ?e ex:dept "dept1" . ?e ex:salary ?s }'
    assert_rows_match(db, q)
    per = [
        v for v in plan_cache_info(db)["per_template"].values()
        if v["source"] is not None
    ]
    assert per and all(v["source"] != "interp" for v in per)


def test_auto_switches_to_specialized_after_warm(monkeypatch):
    """auto: a cold template serves through the interpreter; once the
    specialized executable exists (any specialized run — here a forced-
    off warm), routing flips and last_source becomes compiled."""
    monkeypatch.setenv("KOLIBRIE_PLAN_INTERP", "auto")
    db = people_db(60)
    q = PREFIXES + 'SELECT ?e ?s WHERE { ?e ex:dept "dept1" . ?e ex:salary ?s }'
    execute_query_volcano(q, db)
    info = [
        v for v in plan_cache_info(db)["per_template"].values()
        if v["source"] is not None
    ]
    assert info and info[0]["source"] == "interp"
    from kolibrie_tpu.query.prewarm import warm_one

    res = warm_one(db, q)
    assert res["source"] in ("compiled", "disk")
    execute_query_volcano(q, db)
    # the auto-mode slot now reports the specialized source
    srcs = {
        v["source"]
        for v in plan_cache_info(db)["per_template"].values()
        if v["source"] is not None
    }
    assert "compiled" in srcs or "disk" in srcs


def test_breaker_close_epoch_expires_sentinel():
    """Satellite: a sticky ``lowered is False`` sentinel is dropped when
    the template's breaker closes again (transient fault healed), but
    stays sticky while the breaker never trips (the Unsupported case)."""
    from kolibrie_tpu.query.executor import _plan_cache_entry
    from kolibrie_tpu.resilience.breaker import breaker_board

    db = people_db(30)
    q = PREFIXES + 'SELECT ?e WHERE { ?e ex:dept "dept0" }'
    ent, slot = _plan_cache_entry(db, q)
    fp = ent["fp"]
    # simulate a transient-fault sentinel
    slot["lowered"] = False
    slot["plan"] = None
    _, slot2 = _plan_cache_entry(db, q)
    assert slot2 is slot and slot2["lowered"] is False  # sticky (epoch 0)
    board = breaker_board(db)
    # an always-closed breaker (Unsupported host fallback) never expires it
    board.record_success(fp)
    _, slot3 = _plan_cache_entry(db, q)
    assert slot3["lowered"] is False
    # trip then recover: close_epoch advances, sentinel expires
    for _ in range(10):
        board.record_failure(fp)
    board.get(fp).retry_at = 0.0  # make the half-open probe immediate
    assert board.allow(fp)
    board.record_success(fp)
    assert board.close_epoch(fp) == 1
    _, slot4 = _plan_cache_entry(db, q)
    assert slot4["lowered"] is None  # cleared: device lowering retries
    assert plan_cache_info(db)["sentinel_expiries"] == 1
