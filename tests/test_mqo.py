"""Multi-query optimization (shared-prefix evaluation, docs/MQO.md).

The load-bearing properties:

* **Row identity** — shared-prefix evaluation returns exactly the rows
  independent evaluation returns, across host / device / interp /
  batched paths and under mutation churn (fuzzed).
* **Zero new specialized compiles** — the prefix rides the interpreter
  executable (truncated op table, same size class) and the suffix is a
  host filter twin; ``_run_plan`` never grows.
* **Off is inert** — ``KOLIBRIE_MQO=off`` (the default) reproduces
  pre-MQO behavior: no registry state, no routing change.
* **Mode participates in the fingerprint** — off↔auto flips land in a
  fresh plan-cache slot, never replay a stale one.
* **Fleet sharing** — N standing RSP windows over one stream evaluate
  the shared prefix once per fire round; rows match the off twin.
"""

import random

import pytest

import kolibrie_tpu.optimizer.device_engine as de
from kolibrie_tpu.optimizer import mqo
from kolibrie_tpu.query.executor import execute_query_volcano
from kolibrie_tpu.query.parser import parse_sparql_query
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
    db.parse_ntriples("\n".join(lines))
    return db


def q_filter(th: int, dept: int = 2) -> str:
    """Same scan/join prefix for every ``th``; only the filter differs."""
    return PREFIXES + (
        f'SELECT ?e ?s WHERE {{ ?e ex:dept "dept{dept}" . '
        f"?e ex:salary ?s . FILTER(?s > {th}) }}"
    )


def rows_off(db, q, monkeypatch):
    monkeypatch.setenv("KOLIBRIE_MQO", "off")
    try:
        return execute_query_volcano(q, db)
    finally:
        monkeypatch.setenv("KOLIBRIE_MQO", "force")


# ------------------------------------------------------------ prefix fp


def _lowered(db, q):
    from kolibrie_tpu.optimizer.device_engine import lower_plan
    from kolibrie_tpu.optimizer.engine import resolve_pattern
    from kolibrie_tpu.optimizer.planner import (
        Streamertail,
        build_logical_plan,
    )

    sel = parse_sparql_query(q, db.prefixes)
    resolved = [resolve_pattern(db, p) for p in sel.where.patterns]
    logical = build_logical_plan(
        resolved, list(sel.where.filters), [], None
    )
    planner = Streamertail(db.get_or_build_stats())
    return lower_plan(db, planner.find_best_plan(logical))


def test_same_prefix_same_fp():
    db = people_db()
    db.register_prefixes_from_query(PREFIXES)
    p1 = mqo._plan_prefix(_lowered(db, q_filter(30)))
    p2 = mqo._plan_prefix(_lowered(db, q_filter(55)))
    assert p1 is not None and p2 is not None
    assert p1.fp == p2.fp
    assert p1.k >= 1


def test_different_prefix_different_fp():
    db = people_db()
    db.register_prefixes_from_query(PREFIXES)
    p1 = mqo._plan_prefix(_lowered(db, q_filter(30, dept=1)))
    p2 = mqo._plan_prefix(_lowered(db, q_filter(30, dept=2)))
    assert p1 is not None and p2 is not None
    # different scan constants → different prefixes: sharing them would
    # fan the WRONG binding table out to a suffix
    assert p1.fp != p2.fp


def test_filterless_query_has_no_suffix_but_valid_prefix():
    db = people_db()
    db.register_prefixes_from_query(PREFIXES)
    q = PREFIXES + (
        'SELECT ?e ?s WHERE { ?e ex:dept "dept2" . ?e ex:salary ?s }'
    )
    p = mqo._plan_prefix(_lowered(db, q))
    assert p is not None
    assert p.k == p.n_real  # whole plan IS the prefix


# ------------------------------------------------------------- off inert


def test_off_is_inert(monkeypatch):
    monkeypatch.setenv("KOLIBRIE_MQO", "off")
    db = people_db()
    mqo.register_standing(db, "w1")
    with mqo.standing_scope(db, "w1"):
        rows = execute_query_volcano(q_filter(30), db)
    assert rows
    st = mqo.stats(db)
    assert st["mode"] == "off"
    assert st["cache_entries"] == 0
    assert st["prefixes"] == {}


def test_mode_participates_in_fingerprint(monkeypatch):
    from kolibrie_tpu.query.parser import parse_combined_query
    from kolibrie_tpu.query.template import fingerprint_query

    db = people_db()
    cq = parse_combined_query(q_filter(30), db.prefixes)
    monkeypatch.setenv("KOLIBRIE_MQO", "off")
    fp_off, _ = fingerprint_query(cq)
    monkeypatch.setenv("KOLIBRIE_MQO", "auto")
    fp_auto, _ = fingerprint_query(cq)
    assert fp_off != fp_auto


def test_off_auto_replan_rows_agree(monkeypatch):
    """Flipping off↔auto mid-session lands in a fresh plan-cache slot
    and both slots return identical rows."""
    db = people_db()
    monkeypatch.setenv("KOLIBRIE_MQO", "off")
    r_off = execute_query_volcano(q_filter(30), db)
    monkeypatch.setenv("KOLIBRIE_MQO", "auto")
    r_auto = execute_query_volcano(q_filter(30), db)
    monkeypatch.setenv("KOLIBRIE_MQO", "off")
    r_back = execute_query_volcano(q_filter(30), db)
    assert sorted(map(tuple, r_off)) == sorted(map(tuple, r_auto))
    assert sorted(map(tuple, r_off)) == sorted(map(tuple, r_back))


# --------------------------------------------------------- shared = solo


def test_force_host_rows_match_and_cache_populates(monkeypatch):
    monkeypatch.setenv("KOLIBRIE_MQO", "force")
    db = people_db()
    mqo.register_standing(db, "w1")
    mqo.register_standing(db, "w2")
    with mqo.standing_scope(db, "w1"):
        r1 = execute_query_volcano(q_filter(30), db)
    with mqo.standing_scope(db, "w2"):
        r2 = execute_query_volcano(q_filter(55), db)
    assert sorted(map(tuple, r1)) == sorted(
        map(tuple, rows_off(db, q_filter(30), monkeypatch))
    )
    assert sorted(map(tuple, r2)) == sorted(
        map(tuple, rows_off(db, q_filter(55), monkeypatch))
    )
    st = mqo.stats(db)
    assert st["standing"] == 2
    (pfx,) = st["prefixes"].values()
    assert pfx["shared_evals"] == 1
    assert pfx["cache_hits"] >= 1
    assert pfx["beneficiaries"] == 2


def test_force_device_rows_match(monkeypatch):
    monkeypatch.setenv("KOLIBRIE_MQO", "force")
    db = people_db()
    db.execution_mode = "device"
    mqo.register_standing(db, "w1")
    with mqo.standing_scope(db, "w1"):
        r1 = execute_query_volcano(q_filter(30), db)
        r2 = execute_query_volcano(q_filter(55), db)
    db.execution_mode = "host"
    monkeypatch.setenv("KOLIBRIE_MQO", "off")
    assert sorted(map(tuple, r1)) == sorted(
        map(tuple, execute_query_volcano(q_filter(30), db))
    )
    assert sorted(map(tuple, r2)) == sorted(
        map(tuple, execute_query_volcano(q_filter(55), db))
    )
    st = mqo.stats(db)
    assert st["prefixes"], "device path should populate the registry"


def test_mutation_invalidates_prefix_cache(monkeypatch):
    monkeypatch.setenv("KOLIBRIE_MQO", "force")
    db = people_db()
    mqo.register_standing(db, "w1")
    with mqo.standing_scope(db, "w1"):
        r1 = execute_query_volcano(q_filter(30), db)
        db.parse_ntriples(
            "<http://example.org/e999> <http://example.org/dept> "
            '"dept2" .\n<http://example.org/e999> '
            '<http://example.org/salary> "45" .'
        )
        r2 = execute_query_volcano(q_filter(30), db)
    assert len(r2) == len(r1) + 1
    assert sorted(map(tuple, r2)) == sorted(
        map(tuple, rows_off(db, q_filter(30), monkeypatch))
    )


# ------------------------------------------------------ zero new compiles


def test_no_new_specialized_compiles(monkeypatch):
    """Mixed same-prefix templates under force: the specialized per-
    template executable caches must not grow — the prefix rides the
    interpreter entry and the suffix is host numpy."""
    monkeypatch.setenv("KOLIBRIE_MQO", "force")
    db = people_db()
    db.execution_mode = "device"
    mqo.register_standing(db, "w1")
    # warm the prefix once so only steady-state dispatches are measured
    with mqo.standing_scope(db, "w1"):
        execute_query_volcano(q_filter(25), db)
    before = de.device_compile_stats()
    with mqo.standing_scope(db, "w1"):
        for th in (30, 35, 40, 45, 55):
            execute_query_volcano(q_filter(th), db)
    after = de.device_compile_stats()
    assert after["run_plan"] == before["run_plan"]
    assert after["run_plan_batch"] == before["run_plan_batch"]
    assert after["run_interp"] == before["run_interp"]
    st = mqo.stats(db)
    (pfx,) = st["prefixes"].values()
    assert pfx["cache_hits"] >= 5


# ------------------------------------------------------------------ fuzz


@pytest.mark.parametrize("path", ["host", "device", "interp", "batched"])
def test_fuzz_shared_rows_identical(monkeypatch, path):
    """Randomized template sets × mutation churn: force-mode rows must
    equal off-mode rows on every path, every round."""
    rng = random.Random(20160806 + hash(path) % 1000)
    db = people_db()
    if path in ("device", "interp"):
        db.execution_mode = "device"
    if path == "interp":
        monkeypatch.setenv("KOLIBRIE_PLAN_INTERP", "force")
    for w in ("w1", "w2", "w3"):
        mqo.register_standing(db, w)

    def run_all(texts):
        if path == "batched":
            from kolibrie_tpu.query.executor import execute_queries_batched

            return execute_queries_batched(db, texts)
        out = []
        for i, t in enumerate(texts):
            with mqo.standing_scope(db, f"w{i % 3 + 1}"):
                out.append(execute_query_volcano(t, db))
        return out

    for round_no in range(3):
        texts = [
            q_filter(rng.randrange(20, 70), dept=rng.randrange(0, 3))
            for _ in range(5)
        ]
        monkeypatch.setenv("KOLIBRIE_MQO", "force")
        got = run_all(texts)
        monkeypatch.setenv("KOLIBRIE_MQO", "off")
        want = [execute_query_volcano(t, db) for t in texts]
        for g, w, t in zip(got, want, texts):
            assert sorted(map(tuple, g)) == sorted(map(tuple, w)), (
                round_no,
                t,
            )
        # mutation churn between rounds: new entities join the scanned
        # predicate space, so a stale prefix table would be visible
        i = 1000 + round_no
        db.parse_ntriples(
            f"<http://example.org/e{i}> <http://example.org/dept> "
            f'"dept{i % 3}" .\n<http://example.org/e{i}> '
            f'<http://example.org/salary> "{20 + i % 50}" .'
        )


# ------------------------------------------------------------- RSP fleet


def _fleet_engine(thresholds, consumer):
    from kolibrie_tpu.rsp.engine import RSPEngine, RSPWindowConfig
    from kolibrie_tpu.rsp.s2r import ReportStrategy, Tick

    configs = []
    for i, th in enumerate(thresholds):
        q = parse_sparql_query(
            "SELECT ?s ?o WHERE { ?s <http://e/val> ?o . "
            f"FILTER(?o > {th}) }}",
            {},
        )
        configs.append(
            RSPWindowConfig(
                window_iri=f"http://e/w{i}",
                stream_iri="http://e/stream",
                width=10,
                slide=2,
                report=ReportStrategy.ON_WINDOW_CLOSE,
                tick=Tick.TIME_DRIVEN,
                query=q,
            )
        )
    return RSPEngine(configs, consumer=consumer)


def _drive(engine):
    from kolibrie_tpu.rsp.s2r import WindowTriple

    for i, ts in enumerate([1, 1, 2, 3, 4], start=1):
        engine.add_to_stream(
            "http://e/stream",
            WindowTriple(f"<http://e/s{i}>", "<http://e/val>", f'"{i}"'),
            ts,
        )
    engine.process_single_thread_window_results()


def test_rsp_fleet_shares_prefix(monkeypatch):
    thresholds = [0, 1, 2, 3]
    monkeypatch.setenv("KOLIBRIE_MQO", "force")
    got, want = [], []
    e1 = _fleet_engine(thresholds, lambda row: got.append(tuple(row)))
    _drive(e1)
    st = e1.mqo_stats()
    assert st["standing"] == len(thresholds)
    assert st["prefixes"], "fire rounds should register shared prefixes"
    total_evals = sum(p["shared_evals"] for p in st["prefixes"].values())
    total_hits = sum(p["cache_hits"] for p in st["prefixes"].values())
    # the fleet property: windows 2..N of a same-content round hit the
    # prefix cache instead of re-evaluating
    assert total_hits >= total_evals
    e1.stop()
    # off twin: bit-for-bit the same emitted rows
    monkeypatch.setenv("KOLIBRIE_MQO", "off")
    e2 = _fleet_engine(thresholds, lambda row: want.append(tuple(row)))
    _drive(e2)
    assert st_rows(got) == st_rows(want)
    assert e2.mqo_stats()["prefixes"] == {}
    e2.stop()


def st_rows(rows):
    return sorted(map(str, rows))


def test_rsp_stop_unregisters_standing(monkeypatch):
    monkeypatch.setenv("KOLIBRIE_MQO", "force")
    e = _fleet_engine([0, 1], lambda row: None)
    assert e.mqo_stats()["standing"] == 2
    db = e.r2r.db
    e.stop()
    assert mqo.stats(db)["standing"] == 0
