"""Join and WCOJ-level capacities follow the rows a template produces.

The rule (``device_engine.fit_join_caps``): a join is compiled for
``min(heuristic, round_cap(max(H x count, FLOOR)))`` slots, the counts
coming from the numpy twin on the template's first sight on a db: this
variant's, and the variant's with each scan that binds a subject or an
object at its predicate's hottest key (ISSUE 40), so that the first instance
does not decide them.  Where such a count is a ceiling, the most rows any
instance of the text can give the join on the store as it stands, the rule
leaves the ``H`` out (ISSUE 44, section (f)): a count is one where every
parameter beneath the join is a keyed scan's and a pass that freed them all
counted it.  After that the caps only grow (the overflow protocol,
max-merged: what a store that grew past a ceiling, or an instance past the
headroom, still exceeds), so a template keeps one executable across its
constants.  What must hold: answers stay
exact whatever the caps, a template's variants neither retry nor recompile
once its first request is through, and the counters that say how full the
slots ran add up.

A scan's capacity follows the predicate it names (ISSUE 41,
``device_engine.template_scan_cap``): the largest key-group of its order's
bound prefix among the rows under that predicate, over the whole store only
where the predicate is a variable; section (e).
"""

import numpy as np
import pytest

import kolibrie_tpu.optimizer.device_engine as de
from kolibrie_tpu.optimizer import caps as capacities
from benchmark.harness import data as bench_files
from kolibrie_tpu.obs import analyze as obs_analyze
from kolibrie_tpu.obs import export as obs_export
from kolibrie_tpu.optimizer.stats import hottest_key_rows
from kolibrie_tpu.query.executor import execute_query_volcano
from kolibrie_tpu.query.sparql_database import SparqlDatabase

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


def lowered(db, q):
    """The lowering ``eval_where`` and ``_try_device_aggregate`` give a text,
    with the aggregation its dispatch would end in."""
    from kolibrie_tpu.optimizer.engine import resolve_pattern
    from kolibrie_tpu.optimizer.planner import Streamertail, build_logical_plan
    from kolibrie_tpu.query.parser import parse_sparql_query
    from kolibrie_tpu.query.subquery_inline import inline_subqueries

    query = parse_sparql_query(q)
    w = inline_subqueries(query.where)
    resolved = [resolve_pattern(db, p) for p in w.patterns]
    plan = Streamertail(db.get_or_build_stats()).find_best_plan(
        build_logical_plan(resolved, list(w.filters), [], w.values)
    )
    low = de.lower_plan(db, plan)
    if query.group_by or any(i.kind == "agg" for i in query.select):
        low._stage = de.aggregate_stage(low.out_vars, query)
    return low


# ------------------------------------------------- (a) LUBM(1), every constant


@pytest.fixture(scope="module")
def lubm1():
    """LUBM(1, seed 1) in UBA's shape, as the benchmark generates it: large
    enough that the heuristic caps pass FLOOR and the rule engages."""
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
    assert max(rec["counts"]) < de._CAP_FLOOR // capacities.CAP_HEADROOM


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


def uniform_db(depts=600, members=10) -> SparqlDatabase:
    """``depts`` departments of ``members`` each, "small" and "big" among
    them: no key is hotter than another, and the store is large enough that
    the heuristic passes the floor."""
    lines = []
    names = ["small", "big"] + [f"d{k}" for k in range(depts - 2)]
    for i in range(depts * members):
        e = f"<http://example.org/e{i}>"
        lines.append(f'{e} <http://example.org/dept> "{names[i % depts]}" .')
        lines.append(f'{e} <http://example.org/salary> "{i % 97}" .')
    db = SparqlDatabase()
    db.parse_ntriples("\n".join(lines))
    db.execution_mode = "device"
    return db


def tagged_db() -> SparqlDatabase:
    """60 items share 50 tags, 2 share 3 others; every item has a name."""
    lines = []
    for a in range(62):
        for k in range(50 if a < 60 else 3):
            tag = f"big{k}" if a < 60 else f"small{k}"
            lines.append(f"<http://example.org/a{a}> <http://example.org/tag> "
                         f"<http://example.org/{tag}> .")
        lines.append(f'<http://example.org/a{a}> <http://example.org/name> "n{a}" .')
    db = SparqlDatabase()
    db.parse_ntriples("\n".join(lines))
    db.execution_mode = "device"
    return db


def half_salaried_db() -> SparqlDatabase:
    """6,000 members of "big" and 10 of "small"; the second half of "big" and
    "small" have a salary."""
    lines = []
    for i in range(6010):
        e = f"<http://example.org/e{i}>"
        lines.append(f'{e} <http://example.org/dept> "{"big" if i < 6000 else "small"}" .')
        if i >= 3000:
            lines.append(f'{e} <http://example.org/salary> "{i % 97}" .')
    db = SparqlDatabase()
    db.parse_ntriples("\n".join(lines))
    db.execution_mode = "device"
    return db


@pytest.fixture
def first_sight_alone(monkeypatch):
    """The calibration's hot-key passes left out, as where they pass the row
    limit: a template is then sized by its first instance, and a larger one
    meets the overflow protocol."""
    monkeypatch.setattr(de.LoweredPlan, "_keyed_scans", lambda self: [])


def dept_query(dept: str) -> str:
    return PREFIX + (
        f'SELECT ?e ?s WHERE {{ ?e ex:dept "{dept}" . ?e ex:salary ?s }}'
    )


def cached_caps(db):
    ((_key, caps),) = capacities.of(db).joins.items()
    return caps


def test_larger_variant_overflows_once_and_caps_never_shrink(first_sight_alone):
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


def test_host_pass_too_large_tightens_once_from_the_first_run(
        monkeypatch, first_sight_alone):
    """The fallback: where the numpy twin gives up at the row limit the
    first dispatch runs at the heuristic, its counts tighten the caps once,
    and from then on they only grow."""
    monkeypatch.setattr(de, "_CALIBRATE_ROW_LIMIT", 5)
    db = skewed_db()
    q = dept_query("small")
    with obs_analyze.capture() as cap:
        assert device_rows(db, q) == host_rows(db, q)
    heuristic = cap.last("device")["caps"]
    assert heuristic[0] > de._CAP_FLOOR
    assert cached_caps(db) == (de._CAP_FLOOR,)
    assert capacities.of(db).stats()["templates"][0]["provisional"] is False
    retries0 = retries()
    big = dept_query("big")
    assert device_rows(db, big) == host_rows(db, big)
    assert retries() == retries0 + 1
    assert cached_caps(db) == (16384,)
    assert device_rows(db, q) == host_rows(db, q)
    assert cached_caps(db) == (16384,)


def test_a_second_store_in_the_process_is_sized_from_its_own_counts():
    """ISSUE 46 (b): capacities are remembered on the store they were counted
    on.  A second database in the same process, a tenth of the first's rows
    under the same template, calibrates for itself: its own hottest key, a
    ceiling, and not the first store's 8,192."""
    q = dept_query("small")
    first = skewed_db()
    device_rows(first, q)
    assert cached_caps(first) == (8192,)
    tenth = skewed_db(big=600, small=1)
    calibrated0 = counter(
        'kolibrie_cap_calibrated_joins_total{engine="device",kind="ceiling"}')
    with obs_analyze.capture() as cap:
        assert device_rows(tenth, q) == host_rows(tenth, q)
    assert cap.last("device")["caps"] == [de._CAP_FLOOR]
    assert cached_caps(tenth) == (de._CAP_FLOOR,) and cached_caps(first) == (8192,)
    assert counter(
        'kolibrie_cap_calibrated_joins_total{engine="device",kind="ceiling"}'
    ) == calibrated0 + 1
    assert len(device_rows(tenth, dept_query("big"))) == 600


def test_explain_calibration_publishes_rule_caps():
    """``calibrate_host`` (EXPLAIN, the planner's exploration) sizes by the
    same rule: headroom and floor, never the bare counts."""
    low = lowered(skewed_db(), dept_query("big"))
    counts = low.calibrate_host()
    assert counts == [6000]
    heuristic = low._heuristic_join_caps(low._template_scan_caps())
    assert low._join_caps == de.fit_join_caps(heuristic, counts)
    assert low._join_caps[0] >= 6000


def test_the_hot_keys_capacity_is_there_from_the_first_instance():
    """ISSUE 40: the small department comes first and the template is sized
    for the large one all the same: no overflow, one executable."""
    db = skewed_db()
    compiled0 = de.device_compile_stats()["run_plan"]
    retries0 = retries()
    hot0 = counter('kolibrie_cap_calibrate_seconds_total{outcome="hot_key"}')
    assert device_rows(db, dept_query("small")) == host_rows(db, dept_query("small"))
    assert cached_caps(db) == (8192,)  # the large one's 6,000 rows, a ceiling: no H
    compiled = de.device_compile_stats()["run_plan"]
    assert compiled - compiled0 <= 1  # (another test may have built it)
    assert counter('kolibrie_cap_calibrate_seconds_total{outcome="hot_key"}') > hot0
    for dept in ("big", "small", "big"):
        rows = device_rows(db, dept_query(dept))
        assert len(rows) == (6000 if dept == "big" else 10)
        assert rows == host_rows(db, dept_query(dept))
    assert cached_caps(db) == (8192,) and retries() == retries0
    assert de.device_compile_stats()["run_plan"] == compiled
    # the large department first: the same capacities, the same executable
    other = skewed_db()
    assert len(device_rows(other, dept_query("big"))) == 6000
    assert cached_caps(other) == (8192,)
    assert de.device_compile_stats()["run_plan"] == compiled


def test_a_fan_out_that_no_scans_rows_show_is_counted_at_the_join():
    """Two departments of ten members: neither key holds more rows under
    ``ex:dept``, but the members of one have 600 salaries each.  The pass
    counts the join's largest group, so the first instance of the other
    department sizes the template for this one."""
    lines = []
    for i in range(20):
        e = f"<http://example.org/e{i}>"
        lines.append(f'{e} <http://example.org/dept> "{"big" if i < 10 else "small"}" .')
        for k in range(600 if i < 10 else 1):
            lines.append(f'{e} <http://example.org/salary> "{i}-{k}" .')
    db = SparqlDatabase()
    db.parse_ntriples("\n".join(lines))
    db.execution_mode = "device"
    retries0 = retries()
    assert len(device_rows(db, dept_query("small"))) == 10
    (cap,) = cached_caps(db)
    assert cap == de._round_cap(6000)  # the ten members' 600 salaries each, and no more
    assert device_rows(db, dept_query("big")) == host_rows(db, dept_query("big"))
    assert cached_caps(db) == (cap,) and retries() == retries0


def test_a_product_of_two_hot_keys_is_counted_by_the_pass_that_frees_both():
    """Two placeholders in one text are freed one at a time, and then both at
    once (ISSUE 42): the pair of hot keys, which neither single pass counts,
    is the largest group of the combination of the two freed columns.  The
    template starts where its hottest pair takes it: no re-run, one capacity
    set whichever pair came first."""
    lines = []
    for a in range(62):  # 60 members of "big", 2 of "small"
        dept = "big" if a < 60 else "small"
        lines.append(f'<http://example.org/a{a}> <http://example.org/dept> "{dept}" .')
        for k in range(40 if a < 60 else 3):
            b = f"<http://example.org/b{a}-{k}>"
            lines.append(f"<http://example.org/a{a}> <http://example.org/knows> {b} .")
            lines.append(f'{b} <http://example.org/team> "{dept}" .')
    db = SparqlDatabase()
    db.parse_ntriples("\n".join(lines))
    db.execution_mode = "device"

    def q(dept, team):
        return PREFIX + (
            f'SELECT ?a ?b WHERE {{ ?a ex:dept "{dept}" . ?a ex:knows ?b . '
            f'?b ex:team "{team}" }}'
        )

    retries0 = retries()
    hot0 = counter('kolibrie_cap_calibrate_seconds_total{outcome="hot_key"}')
    assert len(device_rows(db, q("small", "small"))) == 6
    assert counter('kolibrie_cap_calibrate_seconds_total{outcome="hot_key"}') > hot0
    assert retries() == retries0
    caps = cached_caps(db)
    # a department freed with the team as it is counts 6 rows at the last
    # join, a team freed with the department as it is 6; both freed, the pair
    # of "big" and "big" counts 2,400: no pair of this store has more
    assert caps[-1] == de._round_cap(2400)
    both = device_rows(db, q("big", "big"))
    assert len(both) == 2400 and both == host_rows(db, q("big", "big"))
    for dept, team in (("big", "big"), ("small", "small"), ("big", "small")):
        assert device_rows(db, q(dept, team)) == host_rows(db, q(dept, team))
    assert cached_caps(db) == caps and retries() == retries0


@pytest.mark.parametrize("grouped", [False, True])
def test_a_hot_pass_whose_join_passes_the_row_limit_counts_it_unmaterialized(
        monkeypatch, grouped):
    """The freed scan is small, the join above it is not (BSBM's BI Q2: every
    pair of products that share a feature): the pass counts that join from
    its sides' keys, the matches of each freed row summed by freed key, and
    ends there.  The template starts at its hottest key; where the join is
    the plan's topmost its rows bound the groups of an aggregation too."""
    db = tagged_db()
    # a scan reads 3,006 rows, the instance's own pass joins 6, the freed one
    # 50 x 60 x 60 + 3 x 2 x 2
    monkeypatch.setattr(de, "_CALIBRATE_ROW_LIMIT", 4000)

    def q(item):
        head = "?o (COUNT(?t) AS ?n)" if grouped else "?o ?t"
        tail = " GROUP BY ?o" if grouped else ""
        return PREFIX + (
            f"SELECT {head} WHERE {{ ex:{item} ex:tag ?t . ?o ex:tag ?t }}{tail}")

    retries0 = retries()
    agg0 = counter("kolibrie_aggregate_cap_retries_total")
    large0 = counter('kolibrie_cap_calibrate_seconds_total{outcome="too_large"}')
    assert device_rows(db, q("a61")) == host_rows(db, q("a61"))
    assert counter('kolibrie_cap_calibrate_seconds_total{outcome="too_large"}') == large0
    # an item of the 60 joins 50 tags x 60 items, counted exactly though never
    # materialized: a ceiling, under the heuristic's twice the wider scan (the
    # instance's own 6 rows alone leave the floor)
    cap = de._round_cap(3000)
    assert de._CAP_FLOOR < cap < 2 * de._round_cap(3006 + db.store.delta_device_cap)
    assert cached_caps(db) == (cap,)
    big = device_rows(db, q("a7"))
    assert len(big) == (60 if grouped else 3000) and big == host_rows(db, q("a7"))
    assert cached_caps(db) == (cap,) and retries() == retries0
    assert counter("kolibrie_aggregate_cap_retries_total") == agg0
    if grouped:  # no group of the freed key holds more groups than rows: a
        # bound from a pass that was cut, so with headroom, under the table's width
        ((_key, (group_cap,)),) = capacities.of(db).groups.items()
        assert group_cap == cap


def test_the_twin_counts_groups_and_the_largest_group_of_several_columns():
    """What the calibration reads off a pass's table: the most rows one
    combination of freed keys holds, and the most distinct group keys."""
    a = np.array([1, 1, 1, 2, 2, 3], dtype=np.uint32)
    b = np.array([7, 7, 8, 7, 7, 7], dtype=np.uint32)
    g = np.array([5, 6, 6, 5, 5, 9], dtype=np.uint32)
    assert de._largest_group(a) == 3
    assert de._largest_group(a, b) == 2  # (1, 7) and (2, 7)
    assert de._largest_group(a[:0]) == 0 and de._largest_group(a[:0], b[:0]) == 0
    assert de._most_groups([], [g]) == 3  # 5, 6, 9 over the whole table
    assert de._most_groups([], [a, g]) == 4
    assert de._most_groups([a], [g]) == 2  # key 1 holds groups 5 and 6
    assert de._most_groups([a, b], [g]) == 2  # (1, 7) holds 5 and 6
    assert de._most_groups([b], [a, g]) == 4
    assert de._most_groups([], []) == 0 and de._most_groups([a[:0]], [g[:0]]) == 0
    assert de._freed_columns({"x": a, de._FREE_KEY + "0": b}) == [b]


def test_a_hot_pass_over_the_row_limit_is_left_out_and_the_first_instance_sizes(
        monkeypatch):
    """The freed scan reads every row under its predicate; where that passes
    the row limit the pass is dropped, counted as ``too_large``, and the
    template is its first instance's: the large department then overflows
    once and is answered exactly."""
    db = half_salaried_db()
    # the instance's own pass reads 10 and 3,010 rows, the freed scan 6,010
    monkeypatch.setattr(de, "_CALIBRATE_ROW_LIMIT", 4000)
    retries0 = retries()
    large0 = counter('kolibrie_cap_calibrate_seconds_total{outcome="too_large"}')
    hot0 = counter('kolibrie_cap_calibrate_seconds_total{outcome="hot_key"}')
    assert device_rows(db, dept_query("small")) == host_rows(db, dept_query("small"))
    assert counter('kolibrie_cap_calibrate_seconds_total{outcome="too_large"}') > large0
    assert counter('kolibrie_cap_calibrate_seconds_total{outcome="hot_key"}') == hot0
    assert cached_caps(db) == (de._CAP_FLOOR,) and retries() == retries0
    big = device_rows(db, dept_query("big"))
    assert len(big) == 3000 and big == host_rows(db, dept_query("big"))
    assert retries() == retries0 + 1
    assert cached_caps(db)[0] >= 3000


def test_a_keyed_scan_is_ordered_by_its_predicates_hottest_key():
    """The planner runs per constant binding; a text has one executable.  A
    scan that binds its predicate and a subject or an object is ordered by
    the rows of the hottest key under the predicate, so the small and the
    large department plan one order: the large one's."""
    from kolibrie_tpu.optimizer.engine import resolve_pattern
    from kolibrie_tpu.optimizer.planner import Streamertail, build_logical_plan
    from kolibrie_tpu.optimizer.stats import hottest_key_rows
    from kolibrie_tpu.query.parser import parse_sparql_query

    lines = []
    for i in range(6010):
        dept = "big" if i < 6000 else "small"
        lines.append(f'<http://example.org/a{i}> <http://example.org/dept> "{dept}" .')
    for i in range(5950, 6010):
        lines.append(f"<http://example.org/a{i}> <http://example.org/knows> "
                     f"<http://example.org/b{i}> .")
        lines.append(f'<http://example.org/b{i}> <http://example.org/team> "t{i % 7}" .')
    db = SparqlDatabase()
    db.parse_ntriples("\n".join(lines))

    def leaves(node):
        if hasattr(node, "left"):
            return leaves(node.left) + leaves(node.right)
        return [db.dictionary.decode(node.pattern.predicate.value).rpartition("/")[2]]

    def order(dept, cost=None):
        q = parse_sparql_query(PREFIX + (
            f'SELECT ?a WHERE {{ ?a ex:dept "{dept}" . ?a ex:knows ?b . ?b ex:team ?t }}'))
        resolved = [resolve_pattern(db, p) for p in q.where.patterns]
        planner = Streamertail(db.get_or_build_stats())
        if cost is not None:
            planner.estimator.ordering_cost = cost(planner.estimator)
        return leaves(planner.find_best_plan(build_logical_plan(resolved, [], [], None)))

    def by_the_constant(estimator):  # what the ordering read before
        return estimator.estimate_cost

    assert order("small", by_the_constant) == ["dept", "knows", "team"]
    assert order("big", by_the_constant) == ["knows", "team", "dept"]
    assert order("small") == order("big") == ["knows", "team", "dept"]
    dept, team = (resolve_pattern(db, p).predicate.value for p in (
        parse_sparql_query(PREFIX + "SELECT ?a WHERE { ?a ex:dept ?d . ?a ex:team ?t }")
        .where.patterns))
    assert hottest_key_rows(db, dept, "o") == 6000
    assert hottest_key_rows(db, team, "o") == 9 and hottest_key_rows(db, team, "s") == 1
    assert hottest_key_rows(db, 10**9, "o") == 0  # no such predicate


# ------------------------------------------------------------ (c) the rule


def is_pow2(n: int) -> bool:
    return n > 0 and n & (n - 1) == 0


HEADROOM_CASES = [
    (2_097_152, 28, 1024),  # LUBM Q4's last join
    (2_097_152, 12_065, 65_536),  # LUBM Q8's
    (65_536, 100, 1024),  # LUBM Q2, level z
    (65_536, 1_548, 8_192),  # LUBM Q2, level x
    (65_536, 25_000, 65_536),  # one instance's 25,000: the heuristic stays
    (65_536, 6_164, 32_768),  # the nested SELECT
    (65_536, 0, 1024),  # nothing counted: the floor
    (65_536, 256, 1024),  # H x count exactly at the floor
    (65_536, 257, 2048),  # one row over
    (65_536, 10**9, 65_536),  # never above the heuristic
    (256, 3, 256),  # a small store: the heuristic is under the floor
    (128, 10**6, 128),
]
CEILING_CASES = [
    (2_097_152, 284_800, 524_288),  # BI Q5's joins: every review
    (1_048_576, 12_202, 16_384),  # LUBM(5) Q8's
    (65_536, 25_000, 32_768),  # the employee join: no constant at all
    (65_536, 4096, 4096),  # at a power of two: that power
    (65_536, 4095, 4096),  # just under
    (65_536, 4097, 8192),  # just over
    (65_536, 28, 1024),  # under the floor: the floor
    (65_536, 0, 1024),
    (65_536, 1024, 1024),  # the headroom's arm leaves the floor at 257 rows
    (16_384, 20_000, 16_384),  # clipped by the heuristic (the protocol's then)
    (256, 3, 256),
]


@pytest.mark.parametrize(
    "heuristic,count,expected,ceiling",
    [case + (False,) for case in HEADROOM_CASES]
    + [case + (True,) for case in CEILING_CASES],
)
def test_capacity_rule(heuristic, count, expected, ceiling):
    (cap,) = de.fit_join_caps([heuristic], [count], [ceiling])
    assert cap == expected
    assert cap <= heuristic
    assert cap >= min(de._CAP_FLOOR, heuristic)
    assert is_pow2(cap)
    headroom = de.fit_join_caps([heuristic], [count])
    if ceiling:
        assert cap >= min(heuristic, count)  # the ceiling fits
        assert cap < 2 * max(count, de._CAP_FLOOR)  # nothing beyond the rounding
        assert cap <= headroom[0]
    else:
        assert headroom == [cap]  # no flags: the headroom's arm
        # headroom wherever the heuristic leaves room for it
        assert cap >= min(heuristic, capacities.CAP_HEADROOM * count)


def test_capacity_rule_is_elementwise():
    caps = de.fit_join_caps([131_072, 524_288, 1_048_576], [4, 51, 12_065])
    assert caps == [1024, 1024, 65_536]
    mixed = de.fit_join_caps(
        [131_072, 524_288, 1_048_576], [4, 12_065, 12_065], [True, True, False])
    assert mixed == [1024, 16_384, 65_536]


# ------------------------------------------------------- (d) the counters


def test_occupancy_counters_are_rows_over_slots():
    db = uniform_db(depts=60, members=50)
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
    family = 'kolibrie_cap_calibrate_seconds_total{outcome="counted"}'
    db = skewed_db(big=3000, small=50)
    before = counter(family)
    execute_query_volcano(dept_query("small"), db)
    first = counter(family)
    assert first > before
    execute_query_volcano(dept_query("big"), db)
    assert counter(family) == first


# ------------------- (e) a scan is as wide as the predicate it names


BIG, SMALL = 16000, 60  # rows under ex:big and ex:small: over a hundredfold apart


def two_predicates_db() -> SparqlDatabase:
    """Every subject holds a row under ``ex:big``, the first 60 one under
    ``ex:small`` and one under ``ex:other`` too; half of each predicate's
    rows share the object "hot"."""
    lines = []
    for i in range(BIG):
        e = f"<http://example.org/e{i}>"
        lines.append(f'{e} <http://example.org/big> "{"hot" if i % 2 else f"g{i % 30}"}" .')
        if i < SMALL:
            lines.append(f'{e} <http://example.org/small> "{"hot" if i % 2 else f"s{i % 10}"}" .')
            lines.append(f'{e} <http://example.org/other> "o{i}" .')
    db = SparqlDatabase()
    db.parse_ntriples("\n".join(lines))
    db.execution_mode = "device"
    return db


def scan_caps(db, q) -> dict:
    """``ScanSpec.cap`` of the assembled spec, by the name of the predicate
    each scan binds (``None``: a variable, "?": not in the dictionary)."""
    low = lowered(db, q)
    spec, _ = low.build(operands=False)
    out = {}
    for node in de._spec_nodes(spec.root, de.ScanSpec):
        pid = low.scan_descs[node.scan_idx][1][1]
        name = pid if pid is None else (
            "?" if pid < 0 else db.dictionary.decode(pid).rpartition("/")[2]
        )
        out[name] = node.cap
    return out


def pid(db, name) -> int:
    return db.dictionary.lookup(f"http://example.org/{name}")


def wide(db, rows) -> int:
    return de._round_cap(rows + db.store.delta_device_cap)


def case_predicate_alone(db):
    caps = scan_caps(db, PREFIX + "SELECT ?e ?a ?b WHERE { ?e ex:big ?a . ?e ex:small ?b }")
    assert caps == {"big": wide(db, BIG), "small": wide(db, SMALL)}
    assert caps["big"] >= 4 * caps["small"]
    for name, rows in (("big", BIG), ("small", SMALL)):
        assert hottest_key_rows(db, pid(db, name), "p") == rows
        assert caps[name] >= rows + db.store.delta_device_cap


def case_predicate_and_a_key(db):
    by_object = PREFIX + 'SELECT ?e WHERE {{ ?e ex:big "{}" . ?e ex:small "{}" }}'
    want = {"big": wide(db, BIG // 2), "small": wide(db, SMALL // 2)}
    assert hottest_key_rows(db, pid(db, "big"), "o") == BIG // 2
    assert hottest_key_rows(db, pid(db, "small"), "o") == SMALL // 2
    # the hottest object under each predicate, whichever object the text names
    for a, b in (("hot", "hot"), ("g4", "s2"), ("no such", "s4")):
        assert scan_caps(db, by_object.format(a, b)) == want
    by_subject = PREFIX + "SELECT ?a WHERE { ex:e7 ex:big ?a . ex:e9 ex:small ?a }"
    assert hottest_key_rows(db, pid(db, "big"), "s") == 1
    assert scan_caps(db, by_subject) == {"big": wide(db, 1), "small": wide(db, 1)}


def case_a_variable_predicate_keeps_the_stores_group(db):
    # nothing names a predicate: the largest subject, the largest object
    for q, order, rows in (
        ("SELECT ?p ?a WHERE { ex:e7 ?p ?a }", "spo", 3),
        ('SELECT ?e ?p WHERE { ?e ?p "hot" }', "osp", BIG // 2 + SMALL // 2),
    ):
        caps = scan_caps(db, PREFIX + q)
        assert caps == {None: de._round_cap(de.template_scan_cap(db, order, 1))}
        assert caps[None] == wide(db, rows)


def case_an_unknown_predicate_reads_the_empty_table(db):
    q = PREFIX + "SELECT ?e ?a ?b WHERE { ?e ex:nosuch ?a . ?e ex:small ?b }"
    assert hottest_key_rows(db, -1, "p") == hottest_key_rows(db, 10**9, "s") == 0
    assert scan_caps(db, q) == {"?": wide(db, 0), "small": wide(db, SMALL)}
    assert device_rows(db, q) == host_rows(db, q) == []


def case_delta_rows_under_the_small_predicate_fit_the_same_executable(db):
    q = PREFIX + "SELECT ?e ?a ?b WHERE { ?e ex:big ?a . ?e ex:small ?b }"
    assert len(device_rows(db, q)) == SMALL
    compiled, tried, version = (
        de.device_compile_stats()["run_plan"], retries(), db.store.base_version)
    extra = 900  # fifteen times the predicate's base rows, under the delta's capacity
    assert SMALL + extra <= db.store.delta_device_cap < wide(db, SMALL)
    db.parse_ntriples("\n".join(
        f'<http://example.org/e{i}> <http://example.org/small> "late" .'
        for i in range(SMALL, SMALL + extra)))
    rows = device_rows(db, q)
    assert len(rows) == SMALL + extra and rows == host_rows(db, q)
    assert db.store.base_version == version
    assert (de.device_compile_stats()["run_plan"], retries()) == (compiled, tried)


def case_two_texts_of_one_shape_are_two_templates(db):
    from kolibrie_tpu.query.executor import _plan_cache_entry, execute_queries_batched

    text = PREFIX + 'SELECT ?e ?n WHERE {{ ?e ex:{} "{}" . ?e ex:other ?n }}'
    qa, qa2, qb = text.format("big", "hot"), text.format("big", "g4"), text.format("small", "hot")
    fps = [_plan_cache_entry(db, q)[0]["fp"] for q in (qa, qa2, qb)]
    assert fps[0] == fps[1] != fps[2]
    lows = [lowered(db, q) for q in (qa, qa2, qb)]
    assert lows[0].cap_key == lows[1].cap_key != lows[2].cap_key
    batches = 'kolibrie_device_batch_dispatch_total'
    before = counter(batches)
    want = [host_rows(db, q) for q in (qa, qb)]
    got = execute_queries_batched(db, [qa, qb])
    assert [sorted(map(tuple, rows)) for rows in got] == want
    assert counter(batches) == before  # no group: each rode a dispatch of its own
    assert len(capacities.of(db).joins) == 2  # a record a predicate set
    assert len(capacities.of(db).stats()["templates"]) == 2
    with pytest.raises(de.Unsupported):  # and a group made by hand is refused
        de.execute_plan_batch([lows[0], lows[2]])
    # two instances of one text do ride one dispatch, as before
    got = execute_queries_batched(db, [qa, qa2])
    assert [sorted(map(tuple, rows)) for rows in got] == [want[0], host_rows(db, qa2)]
    assert counter(batches) == before + 1


@pytest.mark.parametrize("case", [
    case_predicate_alone,
    case_predicate_and_a_key,
    case_a_variable_predicate_keeps_the_stores_group,
    case_an_unknown_predicate_reads_the_empty_table,
    case_delta_rows_under_the_small_predicate_fit_the_same_executable,
    case_two_texts_of_one_shape_are_two_templates,
], ids=lambda case: case.__name__[5:])
def test_a_scan_is_as_wide_as_the_predicate_it_names(case):
    case(two_predicates_db())


# ------------------- (f) a count that no instance can pass needs no headroom


KINDS = ("ceiling", "headroom")


def calibrated_kinds():
    return tuple(
        counter(f'kolibrie_cap_calibrated_joins_total{{engine="device",kind="{kind}"}}')
        for kind in KINDS)


def kinds_since(before):
    return tuple(int(now - then) for now, then in zip(calibrated_kinds(), before))


def staffed_db(sizes=(3000, 400, 10, 1)) -> SparqlDatabase:
    """Departments "d0", "d1", ... of the given sizes; every member has a
    salary and a boss, and carries one tag the others of its department share."""
    lines, i = [], 0
    for d, size in enumerate(sizes):
        for _ in range(size):
            e = f"<http://example.org/e{i}>"
            lines.append(f'{e} <http://example.org/dept> "d{d}" .')
            lines.append(f'{e} <http://example.org/salary> "{i % 97}" .')
            lines.append(f"{e} <http://example.org/boss> <http://example.org/e{i // 10}> .")
            i += 1
    db = SparqlDatabase()
    db.parse_ntriples("\n".join(lines))
    db.execution_mode = "device"
    return db


def staff_query(dept: str, tail: str = "") -> str:
    return PREFIX + (
        f'SELECT ?e ?s ?b WHERE {{ ?e ex:dept "{dept}" . ?e ex:salary ?s . '
        f"?e ex:boss ?b {tail}}}")


def test_every_instance_runs_at_the_ceiling_with_no_retry_and_one_executable():
    """The smallest department comes first; the template is compiled for the
    largest one's rows as they are, and every department of the store runs
    there: no instance is left for a headroom to wait for."""
    sizes = (3000, 400, 10, 1)
    db = staffed_db(sizes)
    kinds0, retries0 = calibrated_kinds(), retries()
    compiled0 = de.device_compile_stats()["run_plan"]
    for d in reversed(range(len(sizes))):
        rows = device_rows(db, staff_query(f"d{d}"))
        assert len(rows) == sizes[d] and rows == host_rows(db, staff_query(f"d{d}"))
        assert cached_caps(db) == (de._round_cap(3000),) * 2
    assert device_rows(db, staff_query("no such")) == []
    assert kinds_since(kinds0) == (2, 0)  # both joins, on the first sight alone
    assert retries() == retries0
    assert de.device_compile_stats()["run_plan"] - compiled0 == 1
    low = lowered(db, staff_query("d3"))
    assert low._calibration_counts() == ([3000, 3000], [True, True])
    # the headroom's arm would have asked for four times the slots
    assert de.fit_join_caps([10**9] * 2, [3000, 3000]) == [16384, 16384]


def case_a_parameterised_filter(monkeypatch):
    """The planner leaves a FILTER over the joins, where it takes nothing
    from their counts; beneath a join (a branch's, pushed here by hand) the
    rows that reach the join depend on its constant, which no pass frees."""
    import dataclasses

    low = lowered(staffed_db(), staff_query("d2", ". FILTER(?s > 50) "))
    top = low.root.child
    assert isinstance(low.root, de.FilterSpec) and isinstance(top.left, de.JoinSpec)
    assert low._calibration_counts() == ([3000, 3000], [True, True])
    low.root = dataclasses.replace(top, left=de.FilterSpec(top.left, low.root.expr))
    counts, ceilings = low._calibration_counts()
    assert ceilings[top.left.join_idx] and not ceilings[top.join_idx]
    for tail in (". FILTER(?e != ex:e3) ", '. FILTER(REGEX(?s, "^4")) '):
        low = lowered(staffed_db(sizes=(40, 2)), staff_query("d1", tail))
        top = low.root.child
        low.root = dataclasses.replace(top, left=de.FilterSpec(top.left, low.root.expr))
        assert not low._calibration_counts()[1][top.join_idx], tail
    # a comparison of two variables reads no constant
    assert not de._reads_a_constant(de.NumCmp("<", "a", "b"))
    assert de._reads_a_constant(de.BoolNode("or", (
        de.NumCmp("<", "a", "b"), de.BoolNode("not", (de.IdCmp("=", "a", 0),)))))


def case_a_cut_hot_pass(monkeypatch):
    """The pass that frees the item is cut at the first join, which it counts
    without materializing it, exactly: a ceiling.  The join above it the pass
    never reached: the instance's own 6 rows, with headroom."""
    db = tagged_db()
    q = PREFIX + "SELECT ?o ?t ?n WHERE {{ ex:{} ex:tag ?t . ?o ex:tag ?t . ?o ex:name ?n }}"
    assert lowered(db, q.format("a61"))._calibration_counts() == (
        [3000, 3000], [True, True])
    monkeypatch.setattr(de, "_CALIBRATE_ROW_LIMIT", 4000)
    assert lowered(db, q.format("a61"))._calibration_counts() == ([3000, 6], [True, False])
    kinds0, retries0 = calibrated_kinds(), retries()
    assert device_rows(db, q.format("a61")) == host_rows(db, q.format("a61"))
    assert kinds_since(kinds0) == (1, 1)
    assert cached_caps(db) == (de._round_cap(3000), de._CAP_FLOOR)
    # what the headroom could not hold the protocol does, once
    big = device_rows(db, q.format("a7"))
    assert len(big) == 3000 and big == host_rows(db, q.format("a7"))
    assert retries() == retries0 + 1


def case_a_left_out_hot_pass(monkeypatch):
    """The freed scan alone passes the row limit: no pass counted anything
    for the other departments, and the count is this instance's."""
    db = half_salaried_db()
    # the instance's own pass reads 10 and 3,010 rows, the freed scan 6,010
    monkeypatch.setattr(de, "_CALIBRATE_ROW_LIMIT", 4000)
    low = lowered(db, dept_query("small"))
    assert low._keyed_scans() == [0]
    assert low._calibration_counts() == ([10], [False])
    monkeypatch.setattr(de, "_CALIBRATE_ROW_LIMIT", 8_000_000)
    assert low._calibration_counts() == ([3000], [True])


def case_a_scan_bound_but_not_keyed(monkeypatch):
    """A subject with no predicate beside it, and a constant the dictionary
    does not know: neither is a keyed scan's, so no pass frees it."""
    db = staffed_db()
    q = PREFIX + "SELECT ?p ?s WHERE {{ ex:{} ?p ?o . ?x ex:boss ?o . ?x ex:salary ?s }}"
    low = lowered(db, q.format("e7"))
    assert low._keyed_scans() == []
    counts, ceilings = low._calibration_counts()
    assert ceilings == [False, False] and max(counts) == 10
    low = lowered(db, staff_query("no such"))
    assert low._keyed_scans() == []
    assert low._calibration_counts() == ([0, 0], [False, False])
    # the predicate alone is structure: nothing to free, a ceiling by its own count
    low = lowered(db, PREFIX + "SELECT ?e ?s ?b WHERE { ?e ex:salary ?s . ?e ex:boss ?b }")
    assert low._calibration_counts() == ([3411], [True])


def case_a_wcoj_level(monkeypatch):
    """A WCOJ level's constants ride in the parameter vector and its count
    follows the accessor that leads: the levels keep the rule they had."""
    db = staffed_db()
    low = lowered(db, PREFIX + (
        "SELECT ?a ?b ?c WHERE { ?a ex:boss ?b . ?b ex:boss ?c . ?c ex:boss ?a }"))
    assert isinstance(low.root, de.WcojSpec)
    counts, ceilings = low._calibration_counts()
    assert len(counts) == 3 and ceilings == [False] * 3
    kinds0 = calibrated_kinds()
    q = PREFIX + "SELECT ?a ?b ?c WHERE { ?a ex:boss ?b . ?b ex:boss ?c . ?c ex:boss ?a }"
    assert device_rows(db, q) == host_rows(db, q)
    assert kinds_since(kinds0) == (0, 3)


def case_a_branch_with_a_parameter(monkeypatch):
    """OPTIONAL, MINUS and UNION count whole passes, not groups of a key: an
    OPTIONAL's own count is a ceiling only over no parameter at all, and a
    branch that reads one leaves nothing above it a ceiling."""
    db = staffed_db()
    kinds0 = calibrated_kinds()
    for body, kinds in (
        ("?e ex:dept ?d . OPTIONAL { ?e ex:boss ?b }", (1, 0)),
        ('?e ex:dept "d2" . OPTIONAL { ?e ex:boss ?b }', (0, 1)),
        ('?e ex:dept "d2" . ?e ex:salary ?b . MINUS { ?e ex:boss ex:e301 }', (1, 0)),
        ('{ ?e ex:dept "d2" } UNION { ?e ex:dept "d3" } . ?e ex:boss ?b', (0, 1)),
        ("{ ?e ex:salary ?b } UNION { ?e ex:boss ?b } . ?e ex:dept ?d", (1, 0)),
    ):
        q = PREFIX + f"SELECT ?e ?b WHERE {{ {body} }}"
        assert device_rows(db, q) == host_rows(db, q), body
        assert kinds_since(kinds0) == kinds, body
        kinds0 = calibrated_kinds()
    # and above a MINUS whose branch names a constant, by hand: the planner
    # composes MINUS over the whole group, so no join of its own sits there
    low = lowered(db, staff_query("d2"))
    inner = low.root.left
    branch = de.ScanSpec(0, 0, (("e", 0),), (), 0)
    low.root = de.JoinSpec(
        de.AntiJoinSpec(inner, branch, ("e",)), low.root.right, ("e",), 1, 0)
    assert low._calibration_counts()[1] == [True, False]


@pytest.mark.parametrize("case", [
    case_a_parameterised_filter,
    case_a_cut_hot_pass,
    case_a_left_out_hot_pass,
    case_a_scan_bound_but_not_keyed,
    case_a_wcoj_level,
    case_a_branch_with_a_parameter,
], ids=lambda case: case.__name__[5:])
def test_a_count_is_no_ceiling_above(case, monkeypatch):
    case(monkeypatch)


def test_a_group_table_keeps_its_headroom_under_a_parameterised_filter():
    """BI Q2's shape: the join under the FILTER is a ceiling, the groups over
    it depend on the FILTER's constant, which no pass frees."""
    db = skewed_db()
    text = PREFIX + (
        'SELECT ?s (COUNT(?e) AS ?n) WHERE {{ ?e ex:dept "{}" . ?e ex:salary ?s {}}} '
        "GROUP BY ?s")
    retries0, agg0 = retries(), counter("kolibrie_aggregate_cap_retries_total")
    for tail, kinds in (("", (2, 0)), (". FILTER(?e != ex:e3) ", (1, 1))):
        kinds0 = calibrated_kinds()
        for dept, groups in (("small", 10), ("big", 97)):
            q = text.format(dept, tail)
            rows = device_rows(db, q)
            assert len(rows) == groups and rows == host_rows(db, q)
        assert kinds_since(kinds0) == kinds, tail  # the join, then the group table
    assert {c for _k, c in capacities.of(db).groups.items()} == {(de._CAP_FLOOR,)}
    assert retries() == retries0
    assert counter("kolibrie_aggregate_cap_retries_total") == agg0


def test_a_write_that_passes_a_ceiling_costs_one_retry():
    """A ceiling is the store's at first sight, and the store's capacities are
    not dropped on a write: the department that grows past it meets the
    overflow protocol once, is answered exactly, and runs at the doubled
    capacity from then on, as an instance past the headroom always did."""
    db = skewed_db(big=1500, small=10)
    assert device_rows(db, dept_query("small")) == host_rows(db, dept_query("small"))
    assert cached_caps(db) == (2048,)  # 1,500 rows, no headroom
    retries0 = retries()
    db.parse_ntriples("\n".join(
        f'<http://example.org/e{i}> <http://example.org/dept> "big" .\n'
        f'<http://example.org/e{i}> <http://example.org/salary> "{i % 97}" .'
        for i in range(20_000, 20_600)))
    big = device_rows(db, dept_query("big"))
    assert len(big) == 2100 and big == host_rows(db, dept_query("big"))
    assert retries() == retries0 + 1
    assert cached_caps(db) == (de._round_cap(2 * 2100),)
    for dept in ("small", "big"):
        assert device_rows(db, dept_query(dept)) == host_rows(db, dept_query(dept))
    assert retries() == retries0 + 1


@pytest.mark.slow
@pytest.mark.parametrize("config,traffic,sample", [
    ("bsbm-10m", "bi_counts", 2000),  # 584 types, 100 pairs, 2,000 of 28,480 products
    ("watdiv-100", "stars_snowflakes", 2000),
    ("lubm-5", "lookups", 0),
    ("lubm-50", "lookups", 0),
    ("employee-100k", "upstream", 0),
])
def test_no_instance_passes_a_ceiling_at_full_scale(config, traffic, sample):
    """That a ceiling is one, on the CPU, twin alone (minutes and gigabytes: not
    tier-1): every instance of every domain of a cell's texts, at the
    configuration's own scale, counts no join and no group table over the
    ceiling its template's first sight calibrated.  ``CHANGES.md``, PR 44, has
    the numbers."""
    seed = 2**31 + 7
    spec = bench_files.read_json("configs", config + ".json")
    data = bench_files.load_module("generators", spec["generator"]).generate(spec, seed, None)
    db = SparqlDatabase()
    for text in bench_files.ntriples_chunks(data):
        db.parse_ntriples(text)
    db.execution_mode = "device"
    for step in bench_files.read_json("traffic", traffic + ".json")["cycle"]:
        text = bench_files.template_text(step["template"])
        domains = [(name, data["domains"][how["draw"]])
                   for name, how in step.get("constants", {}).items()]
        n = len(domains[0][1]) if domains else 1
        picks = range(n) if not sample or n <= sample else sorted(
            np.random.default_rng(seed).choice(n, sample, replace=False).tolist())
        sights = {}  # a template's first sight, by cap_key
        for k in picks:
            instance = text
            for name, values in domains:
                instance = instance.replace(f"@{name}@", values[k])
            low = lowered(db, instance)
            if low.cap_key not in sights:
                counts, ceilings = low._calibration_counts()
                sights[low.cap_key] = (
                    counts, ceilings, low._calibrated_groups, low._groups_are_ceiling)
            counts, ceilings, groups, groups_top = sights[low.cap_key]
            table, own = low.host_execute()
            over = [j for j, top in enumerate(ceilings) if top and own[j] > counts[j]]
            assert not over, (step["template"], k, over, own, counts)
            if low._stage is not None and groups_top and low._stage.group_by:
                assert de._most_groups(
                    [], [table[g] for g in low._stage.group_by]) <= groups
        assert len(sights) == 1, step["template"]  # one template a text


# ------------------------- (g) one module: the memory and the loop (ISSUE 46)


def test_an_aggregate_template_then_a_store_wide_scan_and_a_wcoj_query_on_one_store():
    """A GROUP BY's capacity and the largest key-group of an order's prefix
    are two tables of the store's memory, each under keys of its own: the
    first sight of a scan with a variable predicate, or of a WCOJ accessor,
    prunes the second by ``base_version`` and never reads the first.  (They
    shared one dict once, and the pruning indexed the aggregate's
    two-element key: ``IndexError``.)"""
    db = staffed_db((300, 40))
    agg = PREFIX + (
        'SELECT ?s (COUNT(?e) AS ?n) WHERE { ?e ex:dept "d0" . ?e ex:salary ?s } '
        "GROUP BY ?s")
    assert device_rows(db, agg) == host_rows(db, agg)
    memory = capacities.of(db)
    assert len(memory.groups) == 1
    by_subject = PREFIX + "SELECT ?p ?a WHERE { ex:e7 ?p ?a }"
    assert scan_caps(db, by_subject) == {None: wide(db, 3)}
    assert device_rows(db, by_subject) == host_rows(db, by_subject)
    triangle = PREFIX + (
        "SELECT ?a ?b ?c WHERE { ?a ex:boss ?b . ?b ex:boss ?c . ?c ex:boss ?a }")
    assert isinstance(lowered(db, triangle).root, de.WcojSpec)
    assert device_rows(db, triangle) == host_rows(db, triangle)
    assert len(memory.groups) == 1 and device_rows(db, agg) == host_rows(db, agg)
    # a write that rebuilds the base drops the key-groups and nothing else
    held = dict(memory.joins.items())
    version = db.store.base_version
    assert memory.largest_key_group("spo", 1, version, count=None) == 3
    assert memory.largest_key_group("spo", 1, version + 1, count=lambda: 7) == 7
    assert dict(memory.joins.items()) == held and len(memory.groups) == 1


def _one_join(dept: str) -> str:
    return PREFIX + f'SELECT ?e ?s WHERE {{ ?e ex:dept "{dept}" . ?e ex:salary ?s }}'


def _lone_plan(db):
    q = _one_join("d0")
    return (lambda: device_rows(db, q)), [host_rows(db, q)], "joins"


def _group_of_three(db):
    from kolibrie_tpu.query.executor import execute_queries_batched

    qs = [_one_join(d) for d in ("d0", "d1", "d2")]
    return (
        lambda: [sorted(map(tuple, rows)) for rows in execute_queries_batched(db, qs)],
        [[host_rows(db, q) for q in qs]],
        "joins",
    )


def _aggregation(db):
    q = PREFIX + (
        'SELECT ?e (COUNT(?s) AS ?n) WHERE { ?e ex:dept "d0" . ?e ex:salary ?s } '
        "GROUP BY ?e")
    return (lambda: device_rows(db, q)), [host_rows(db, q)], "groups"


@pytest.mark.parametrize("caller", [_lone_plan, _group_of_three, _aggregation],
                         ids=lambda caller: caller.__name__[1:])
def test_the_one_overflow_loop_serves_its_three_callers_alike(caller, monkeypatch):
    """``caps.run_until_fits`` under ``LoweredPlan.converge``, the group's
    dispatch and ``aggregate_table``: each starts from a capacity that the
    3,000 rows (the most of the group's members; the 3,000 groups) overflow.
    One counted retry, the same grown capacity, stored once."""
    db = staffed_db((3000, 400, 10))
    run, (want,), table = caller(db)
    assert run() == want  # the first sight: calibrated, compiled, no retry
    memory = capacities.of(db)
    ((join_key, _held),) = memory.joins.items()
    grown = capacities.grown_cap(3000)
    if table == "groups":  # a table as wide as the other callers' joins grow to
        memory.joins.start(join_key, [grown])
        ((key, _held),) = memory.groups.items()
        memory.groups.start(key, [de._CAP_FLOOR])
    else:
        key = join_key
        memory.joins.start(key, [de._CAP_FLOOR])
    stored, merge = [], capacities.Remembered.merge

    def spy(self, k, caps):
        before = self.get(k)
        after = merge(self, k, caps)
        if after != before:
            stored.append((k, after))
        return after

    monkeypatch.setattr(capacities.Remembered, "merge", spy)
    before = retries(), counter("kolibrie_aggregate_cap_retries_total")
    assert run() == want
    after = retries(), counter("kolibrie_aggregate_cap_retries_total")
    counted = (0, 1) if table == "groups" else (1, 0)
    assert tuple(b - a for a, b in zip(before, after)) == counted
    assert stored == [(key, (grown,))]
    assert getattr(memory, table).get(key) == (grown,)
    assert run() == want and after == (
        retries(), counter("kolibrie_aggregate_cap_retries_total"))
    assert stored == [(key, (grown,))]
