"""Join and WCOJ-level capacities follow the rows a template produces.

The rule (``device_engine.fit_join_caps``): a join is compiled for
``min(heuristic, round_cap(max(H x count, FLOOR)))`` slots, the counts
coming from the numpy twin on the template's first sight on a db.  After
that the caps only grow (the overflow protocol, max-merged), so a template
keeps one executable across its constants.  What must hold: answers stay
exact whatever the caps, a template's variants neither retry nor recompile
once its first request is through, and the counters that say how full the
slots ran add up.
"""

import pytest

import kolibrie_tpu.optimizer.device_engine as de
from benchmark.harness import data as bench_files
from kolibrie_tpu.obs import analyze as obs_analyze
from kolibrie_tpu.obs import export as obs_export
from kolibrie_tpu.query.executor import execute_query_volcano
from kolibrie_tpu.query.sparql_database import SparqlDatabase
from kolibrie_tpu.query.template import cap_advisor

PREFIX = "PREFIX ex: <http://example.org/>\n"


def counter(name: str) -> float:
    for line in obs_export.render_prometheus().splitlines():
        if line.startswith(name):
            return float(line.rpartition(" ")[2])
    raise KeyError(name)


def retries() -> float:
    return counter('kolibrie_cap_retries_total{engine="device"}')


def host_rows(db, q):
    db.execution_mode = "host"
    try:
        return sorted(map(tuple, execute_query_volcano(q, db)))
    finally:
        db.execution_mode = "device"


def device_rows(db, q):
    return sorted(map(tuple, execute_query_volcano(q, db)))


# ------------------------------------------------- (a) LUBM(1), every constant


@pytest.fixture(scope="module")
def lubm1():
    """LUBM(1, seed 1) in UBA's shape, as the benchmark generates it: large
    enough that the heuristic caps pass FLOOR and the rule engages."""
    cap_advisor.reset()
    config = bench_files.read_json("configs", "lubm-5.json")
    data = bench_files.load_module("generators", config["generator"]).generate(
        config, 1, 1
    )
    db = SparqlDatabase()
    for text in bench_files.ntriples_chunks(data):
        db.parse_ntriples(text)
    db.execution_mode = "device"
    return db, data["domains"]


LUBM_TEMPLATES = [
    ("lubm_q1", "department"),
    ("lubm_q2", None),
    ("lubm_q3", "department"),
    ("lubm_q4", "department"),
    ("lubm_q7", "department"),
    ("lubm_q8", "university"),
    ("lubm_q9", None),
]


@pytest.mark.parametrize("template,domain", LUBM_TEMPLATES)
def test_lubm_variants_exact_one_executable_no_retry(lubm1, template, domain):
    db, domains = lubm1
    text = bench_files.template_text(template)
    constants = domains[domain] if domain else [None]
    queries = [
        text if c is None else text.replace(f"@{domain}@", c) for c in constants
    ]
    compiled0 = de.device_compile_stats()["run_plan"]
    after_first = None
    for q in queries:
        assert device_rows(db, q) == host_rows(db, q), q
        if after_first is None:
            after_first = (retries(), de.device_compile_stats()["run_plan"])
    assert (retries(), de.device_compile_stats()["run_plan"]) == after_first
    # Q1 and Q3 assemble the same spec, so the second of them adds none
    assert after_first[1] - compiled0 <= 1


def test_lubm_caps_follow_the_counts_not_the_scans(lubm1):
    db, domains = lubm1
    q4 = bench_files.template_text("lubm_q4").replace(
        "@department@", domains["department"][0]
    )
    with obs_analyze.capture() as cap:
        execute_query_volcano(q4, db)
    rec = cap.last("device")
    # a department has a few dozen professors: every join at the floor,
    # where the inputs' capacities alone asked for 2^15 and more
    assert rec["caps"] == [de._CAP_FLOOR] * len(rec["caps"]), rec
    assert max(rec["counts"]) < de._CAP_FLOOR // de._CAP_HEADROOM


# ------------------------------------- (b) overflow once, then monotonic


def skewed_db(big=6000, small=10) -> SparqlDatabase:
    """One small and one large department: caps fitted to the small one
    cannot hold the large one."""
    lines = []
    for i in range(big + small):
        dept = "big" if i < big else "small"
        e = f"<http://example.org/e{i}>"
        lines.append(f'{e} <http://example.org/dept> "{dept}" .')
        lines.append(f'{e} <http://example.org/salary> "{i % 97}" .')
    db = SparqlDatabase()
    db.parse_ntriples("\n".join(lines))
    db.execution_mode = "device"
    return db


def dept_query(dept: str) -> str:
    return PREFIX + (
        f'SELECT ?e ?s WHERE {{ ?e ex:dept "{dept}" . ?e ex:salary ?s }}'
    )


def cached_caps(db):
    (caps,) = db.__dict__["_device_cap_cache"].values()
    return caps


def test_larger_variant_overflows_once_and_caps_never_shrink():
    cap_advisor.reset()
    db = skewed_db()
    compiled0 = de.device_compile_stats()["run_plan"]
    retries0 = retries()
    assert device_rows(db, dept_query("small")) == host_rows(db, dept_query("small"))
    assert cached_caps(db) == (de._CAP_FLOOR,)
    assert retries() == retries0
    # 6000 rows > H x 10: the overflow protocol re-runs once, doubled
    big = device_rows(db, dept_query("big"))
    assert len(big) == 6000 and big == host_rows(db, dept_query("big"))
    assert retries() == retries0 + 1
    raised = cached_caps(db)
    assert raised == (16384,)
    # later, smaller variants keep the raised caps and the second executable
    compiled = de.device_compile_stats()["run_plan"]
    assert compiled - compiled0 == 2
    for dept in ("small", "big", "small"):
        assert device_rows(db, dept_query(dept)) == host_rows(db, dept_query(dept))
    assert cached_caps(db) == raised
    assert retries() == retries0 + 1
    assert de.device_compile_stats()["run_plan"] == compiled


def test_host_pass_too_large_tightens_once_from_the_first_run(monkeypatch):
    """The fallback: where the numpy twin gives up at the row limit the
    first dispatch runs at the heuristic, its counts tighten the caps once,
    and from then on they only grow."""
    cap_advisor.reset()
    monkeypatch.setattr(de, "_CALIBRATE_ROW_LIMIT", 5)
    db = skewed_db()
    q = dept_query("small")
    with obs_analyze.capture() as cap:
        assert device_rows(db, q) == host_rows(db, q)
    heuristic = cap.last("device")["caps"]
    assert heuristic[0] > de._CAP_FLOOR
    assert cached_caps(db) == (de._CAP_FLOOR,)
    assert not db.__dict__["_device_cap_provisional"]
    retries0 = retries()
    big = dept_query("big")
    assert device_rows(db, big) == host_rows(db, big)
    assert retries() == retries0 + 1
    assert cached_caps(db) == (16384,)
    assert device_rows(db, q) == host_rows(db, q)
    assert cached_caps(db) == (16384,)


def test_advice_replaces_the_heuristic_on_a_fresh_db():
    """A second db of the same template starts from what the first
    converged to, not from the larger of that and the heuristic."""
    cap_advisor.reset()
    q = dept_query("small")
    first = skewed_db()
    device_rows(first, q)
    fresh = skewed_db()
    with obs_analyze.capture() as cap:
        assert device_rows(fresh, q) == host_rows(fresh, q)
    assert cap.last("device")["caps"] == [de._CAP_FLOOR]


def test_explain_calibration_publishes_rule_caps():
    """``calibrate_host`` (EXPLAIN, the planner's exploration) sizes by the
    same rule: headroom and floor, never the bare counts."""
    from kolibrie_tpu.optimizer.engine import resolve_pattern
    from kolibrie_tpu.optimizer.planner import Streamertail, build_logical_plan
    from kolibrie_tpu.query.parser import parse_sparql_query

    db = skewed_db()
    q = parse_sparql_query(dept_query("big"))
    resolved = [resolve_pattern(db, p) for p in q.where.patterns]
    plan = Streamertail(db.get_or_build_stats()).find_best_plan(
        build_logical_plan(resolved, [], [], None)
    )
    lowered = de.lower_plan(db, plan)
    counts = lowered.calibrate_host()
    assert counts == [6000]
    heuristic = lowered._heuristic_join_caps(lowered._template_scan_caps())
    assert lowered._join_caps == de.fit_join_caps(heuristic, counts)
    assert lowered._join_caps[0] >= 6000


# ------------------------------------------------------------ (c) the rule


def is_pow2(n: int) -> bool:
    return n > 0 and n & (n - 1) == 0


@pytest.mark.parametrize(
    "heuristic,count,expected",
    [
        (2_097_152, 28, 1024),  # LUBM Q4's last join
        (2_097_152, 12_065, 65_536),  # LUBM Q8's
        (65_536, 100, 1024),  # LUBM Q2, level z
        (65_536, 1_548, 8_192),  # LUBM Q2, level x
        (65_536, 25_000, 65_536),  # the employee join: the heuristic stays
        (65_536, 6_164, 32_768),  # the nested SELECT
        (65_536, 0, 1024),  # nothing counted: the floor
        (65_536, 256, 1024),  # H x count exactly at the floor
        (65_536, 257, 2048),  # one row over
        (65_536, 10**9, 65_536),  # never above the heuristic
        (256, 3, 256),  # a small store: the heuristic is under the floor
        (128, 10**6, 128),
    ],
)
def test_capacity_rule(heuristic, count, expected):
    (cap,) = de.fit_join_caps([heuristic], [count])
    assert cap == expected
    assert cap <= heuristic
    assert cap >= min(de._CAP_FLOOR, heuristic)
    assert is_pow2(cap)
    # headroom wherever the heuristic leaves room for it
    assert cap >= min(heuristic, de._CAP_HEADROOM * count)


def test_capacity_rule_is_elementwise():
    caps = de.fit_join_caps([131_072, 524_288, 1_048_576], [4, 51, 12_065])
    assert caps == [1024, 1024, 65_536]


# ------------------------------------------------------- (d) the counters


def test_occupancy_counters_are_rows_over_slots():
    cap_advisor.reset()
    db = skewed_db(big=3000, small=50)
    slots0 = counter('kolibrie_device_cap_slots_total{engine="device"}')
    rows0 = counter('kolibrie_device_join_rows_total{engine="device"}')
    n = 3
    for _ in range(n):
        with obs_analyze.capture() as cap:
            execute_query_volcano(dept_query("small"), db)
    rec = cap.last("device")
    assert rec["counts"] == [50] and rec["caps"] == [de._CAP_FLOOR]
    slots = counter('kolibrie_device_cap_slots_total{engine="device"}') - slots0
    rows = counter('kolibrie_device_join_rows_total{engine="device"}') - rows0
    assert slots == n * sum(rec["caps"])
    assert rows == n * sum(rec["counts"])
    assert rows / slots == 50 / de._CAP_FLOOR


def test_calibration_seconds_are_counted_once_a_template():
    cap_advisor.reset()
    family = 'kolibrie_cap_calibrate_seconds_total{outcome="counted"}'
    db = skewed_db(big=3000, small=50)
    before = counter(family)
    execute_query_volcano(dept_query("small"), db)
    first = counter(family)
    assert first > before
    execute_query_volcano(dept_query("big"), db)
    assert counter(family) == first
