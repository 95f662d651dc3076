"""EXPLAIN ANALYZE (ISSUE 14): device-resident per-operator stats.

The tentpole contract, fuzzed: the stats vector the device program
returns piggybacked on the result transfer must match a host-oracle
replay EXACTLY — per operator, on the specialized path, the interpreter
path, and the WCOJ path — while adding ZERO device→host transfers to
the hot path (guarded by the fetch-site audit counters).  Plus the
timeline ring's delta/quantile math and the bench gate's comparator.
"""

from __future__ import annotations

import time

import numpy as np
import pytest

from kolibrie_tpu.obs import analyze as obs_analyze
from kolibrie_tpu.obs import metrics as obs_metrics
from kolibrie_tpu.obs.timeseries import (
    Sampler,
    TimeSeriesRing,
    bucket_quantile,
)
from kolibrie_tpu.query.executor import execute_query_volcano
from kolibrie_tpu.query.sparql_database import SparqlDatabase

PREFIX = "PREFIX ex: <http://example.org/>\n"


def _graph_db(rng, n_nodes, n_edges, preds=("p1", "p2", "p3")):
    lines = []
    for _ in range(n_edges):
        p = preds[int(rng.integers(0, len(preds)))]
        a, b = rng.integers(0, n_nodes, 2)
        lines.append(
            f"<http://example.org/n{a}> <http://example.org/{p}> "
            f"<http://example.org/n{b}> ."
        )
    db = SparqlDatabase()
    db.parse_ntriples("\n".join(lines))
    db.execution_mode = "device"
    return db


def _lower(db, sparql):
    """Mirror engine.explain_device's lowering for the plain-BGP subset
    the fuzz uses: parse → Streamertail plan → device IR."""
    from kolibrie_tpu.optimizer.device_engine import lower_plan
    from kolibrie_tpu.optimizer.engine import resolve_pattern
    from kolibrie_tpu.optimizer.planner import (
        Streamertail,
        build_logical_plan,
    )
    from kolibrie_tpu.query.parser import parse_sparql_query
    from kolibrie_tpu.query.subquery_inline import inline_subqueries

    db.register_prefixes_from_query(sparql)
    q = parse_sparql_query(sparql, db.prefixes)
    w = inline_subqueries(q.where)
    resolved = [resolve_pattern(db, p) for p in w.patterns]
    logical = build_logical_plan(resolved, list(w.filters), [], w.values)
    planner = Streamertail(db.get_or_build_stats())
    plan = planner.find_best_plan(logical)
    return lower_plan(db, plan)


# One pool of device-expressible query shapes: chains, stars, filters.
QUERY_SHAPES = [
    PREFIX + "SELECT ?a ?b WHERE { ?a ex:p1 ?b }",
    PREFIX + "SELECT ?a ?c WHERE { ?a ex:p1 ?b . ?b ex:p2 ?c }",
    PREFIX + "SELECT ?a ?b ?c WHERE { ?a ex:p1 ?b . ?a ex:p2 ?c }",
    PREFIX
    + "SELECT ?a ?d WHERE { ?a ex:p1 ?b . ?b ex:p2 ?c . ?c ex:p3 ?d }",
    PREFIX + "SELECT ?a ?b WHERE { ?a ex:p1 ?b . "
    "FILTER(?b != <http://example.org/n0>) }",
    PREFIX + "SELECT ?a ?c WHERE { ?a ex:p1 ?b . ?b ex:p2 ?c . "
    "FILTER(?a != ?c) }",
]


# ------------------------------------------------- specialized-path oracle


@pytest.mark.parametrize("seed", range(4))
def test_device_stats_match_host_oracle_fuzz(seed):
    from kolibrie_tpu.optimizer.device_engine import Unsupported

    rng = np.random.default_rng(seed)
    db = _graph_db(rng, int(rng.integers(8, 24)), int(rng.integers(40, 160)))
    compared = 0
    for q in QUERY_SHAPES:
        try:
            lowered = _lower(db, q)
        except Unsupported:
            continue
        lowered.calibrate_host()
        host_stats = dict(lowered.last_host_stats)
        with obs_analyze.capture() as cap:
            lowered.execute()
        rec = cap.last("device")
        assert rec is not None, q
        if not host_stats:
            continue  # constant-scan early-out: no per-node host replay
        assert rec["operators"] == host_stats, q
        compared += 1
    assert compared >= 3


def test_wcoj_stats_match_host_oracle(monkeypatch):
    monkeypatch.setenv("KOLIBRIE_WCOJ", "force")
    rng = np.random.default_rng(7)
    db = _graph_db(rng, 20, 200)
    tri = PREFIX + (
        "SELECT ?x ?y ?z WHERE "
        "{ ?x ex:p1 ?y . ?y ex:p2 ?z . ?z ex:p3 ?x }"
    )
    lowered = _lower(db, tri)
    lowered.calibrate_host()
    host_stats = dict(lowered.last_host_stats)
    wcoj_keys = [k for k in host_stats if k.startswith("wcoj")]
    assert wcoj_keys, "triangle did not plan WCOJ"
    assert any(k.endswith(":dedup") for k in wcoj_keys)
    with obs_analyze.capture() as cap:
        lowered.execute()
    rec = cap.last("device")
    assert rec["operators"] == host_stats


def test_interp_stats_match_host_oracle(monkeypatch):
    monkeypatch.setenv("KOLIBRIE_PLAN_INTERP", "force")
    rng = np.random.default_rng(11)
    db = _graph_db(rng, 16, 120)
    q = PREFIX + (
        "SELECT ?a ?c WHERE { ?a ex:p1 ?b . ?b ex:p2 ?c . "
        "FILTER(?a != ?c) }"
    )
    lowered = _lower(db, q)
    lowered.calibrate_host()
    host_stats = dict(lowered.last_host_stats)
    with obs_analyze.capture() as cap:
        lowered.execute()
    rec = cap.last("interp")
    assert rec is not None, "interp route did not run under force"
    # the interpreter attributes rows to the same key scheme; every key it
    # reports must agree with the oracle exactly
    assert rec["operators"], rec
    for k, v in rec["operators"].items():
        assert host_stats.get(k) == v, (k, v, host_stats)
    # opcode histogram covers the program
    assert rec["opcodes"]["SCAN"] == 2
    assert rec["opcodes"]["JOIN"] == 1
    assert sum(rec["opcodes"].values()) >= 3


def test_interp_and_device_paths_agree(monkeypatch):
    rng = np.random.default_rng(13)
    db = _graph_db(rng, 16, 120)
    q = QUERY_SHAPES[1]
    lowered = _lower(db, q)
    lowered.calibrate_host()
    with obs_analyze.capture() as cap:
        lowered.execute()
    dev_ops = cap.last("device")["operators"]
    monkeypatch.setenv("KOLIBRIE_PLAN_INTERP", "force")
    lowered2 = _lower(db, q)
    lowered2.calibrate_host()
    with obs_analyze.capture() as cap:
        lowered2.execute()
    rec = cap.last("interp")
    for k, v in rec["operators"].items():
        assert dev_ops.get(k) == v, (k, rec["operators"], dev_ops)


# --------------------------------------------- transfer-count regression


def test_hot_path_adds_no_transfers():
    """THE acceptance guard: per warm execute, the device engine performs
    exactly its two historical fetches (counts check + result collect).
    The stats vector must ride those — any new fetch site is a bug."""
    from kolibrie_tpu.optimizer.device_engine import fetch_counters

    rng = np.random.default_rng(3)
    db = _graph_db(rng, 16, 120)
    lowered = _lower(db, QUERY_SHAPES[1])
    lowered.calibrate_host()
    lowered.execute()  # warm: compile + converge caps
    lowered.execute()
    f0 = fetch_counters()
    lowered.execute()
    f1 = fetch_counters()
    delta = {k: f1.get(k, 0) - f0.get(k, 0) for k in f1}
    assert {k: v for k, v in delta.items() if v} == {
        "converge.counts": 1,
        "to_table": 1,
    }


def test_analyze_capture_costs_exactly_one_fetch():
    from kolibrie_tpu.optimizer.device_engine import fetch_counters

    rng = np.random.default_rng(5)
    db = _graph_db(rng, 16, 120)
    lowered = _lower(db, QUERY_SHAPES[2])
    lowered.calibrate_host()
    lowered.execute()
    f0 = fetch_counters()
    with obs_analyze.capture():
        lowered.execute()
    f1 = fetch_counters()
    delta = {k: f1.get(k, 0) - f0.get(k, 0) for k in f1}
    assert {k: v for k, v in delta.items() if v} == {
        "converge.counts": 1,
        "to_table": 1,
        "analyze.stats": 1,
    }


# --------------------------------------------------------- capture plumbing


def test_capture_nesting_and_isolation():
    assert obs_analyze.active() is None
    with obs_analyze.capture() as outer:
        obs_analyze.record("device", x=1)
        with obs_analyze.capture() as inner:
            obs_analyze.record("interp", y=2)
        # inner scope restored the outer capture
        assert obs_analyze.active() is outer
        obs_analyze.record("device", x=3)
    assert obs_analyze.active() is None
    assert [r["kind"] for r in outer.records] == ["device", "device"]
    assert inner.last("interp")["y"] == 2
    assert outer.last("device")["x"] == 3


def test_host_fallback_is_recorded():
    db = SparqlDatabase()
    db.parse_ntriples('<http://e/a> <http://e/p> "1" .')
    db.execution_mode = "host"
    with obs_analyze.capture() as cap:
        execute_query_volcano("SELECT ?s WHERE { ?s <http://e/p> ?o }", db)
    rec = cap.last("host")
    assert rec is not None and rec["reason"] == "host-routed store"


def test_explain_analyze_renders_actuals():
    from kolibrie_tpu.query.engine import QueryEngine

    rng = np.random.default_rng(9)
    db = _graph_db(rng, 16, 120)
    text = QueryEngine(db).explain_device(QUERY_SHAPES[5], analyze=True)
    assert "actual=" in text
    assert "occ=" in text
    assert "source:" in text
    assert "device time:" in text
    # estimated (matched=) and actual sit side by side on the join line
    join_line = next(l for l in text.splitlines() if "join on" in l)
    assert "matched=" in join_line and "actual=" in join_line


# ------------------------------------------------------------ timeline ring


def test_ring_counter_deltas_and_restart_clamp():
    reg = obs_metrics.Registry()
    c = reg.counter("t_total")
    ring = TimeSeriesRing(capacity=8, registry=reg)
    c.inc(10)
    ring.record(now=1.0)
    c.inc(5)
    ring.record(now=2.0)
    c._default.value = 3.0  # simulated process restart: counter reset
    ring.record(now=3.0)
    series = ring.series()
    s = series["metrics"]["t_total"]["series"][""]
    assert s["deltas"] == [5.0, 3.0]  # restart clamps to new absolute


def test_ring_gauge_and_histogram_series():
    reg = obs_metrics.Registry()
    g = reg.gauge("t_gauge")
    h = reg.histogram("t_lat", buckets=(1.0, 2.0, 4.0))
    ring = TimeSeriesRing(capacity=8, registry=reg)
    g.set(1.5)
    h.observe(0.5)
    ring.record(now=1.0)
    g.set(2.5)
    for v in (0.5, 1.5, 3.0):
        h.observe(v)
    ring.record(now=2.0)
    out = ring.series(quantiles=(0.5,))
    assert out["metrics"]["t_gauge"]["series"][""]["values"] == [1.5, 2.5]
    hs = out["metrics"]["t_lat"]["series"][""]
    assert hs["count_deltas"] == [3]
    assert hs["sum_deltas"] == [5.0]
    assert len(hs["quantiles"]["p50"]) == 2
    assert hs["quantiles"]["p50"][1] is not None


def test_ring_eviction_keeps_sequence():
    ring = TimeSeriesRing(capacity=3, registry=obs_metrics.Registry())
    for i in range(7):
        ring.record(now=float(i))
    assert len(ring) == 3
    w = ring.window()
    assert [s["seq"] for s in w] == [4, 5, 6]
    assert ring.series()["first_seq"] == 4


def test_ring_metric_filter_and_window():
    reg = obs_metrics.Registry()
    reg.counter("a_total")
    reg.counter("b_total")
    ring = TimeSeriesRing(capacity=8, registry=reg)
    ring.record(now=1.0)
    ring.record(now=2.0)
    out = ring.series(metric="a_total")
    assert list(out["metrics"]) == ["a_total"]
    assert ring.series(n=1)["samples"] == 1


def test_bucket_quantile_interpolation():
    cum = [(1.0, 5), (2.0, 10), (float("inf"), 10)]
    assert bucket_quantile(cum, 0.5) == pytest.approx(1.0)
    assert bucket_quantile(cum, 0.99) == pytest.approx(1.98)
    # +Inf landing degrades to the largest finite bound
    assert bucket_quantile([(1.0, 5), (float("inf"), 10)], 0.9) == 1.0
    # empty / all-inf shapes degrade to None, never raise
    assert bucket_quantile([], 0.5) is None
    assert bucket_quantile([(float("inf"), 10)], 0.5) is None
    assert bucket_quantile([(1.0, 0), (float("inf"), 0)], 0.5) is None


def test_sampler_records_and_stops():
    ring = TimeSeriesRing(capacity=8, registry=obs_metrics.Registry())
    s = Sampler(ring, interval_s=0.01)
    s.start()
    deadline = time.time() + 2.0
    while len(ring) < 2 and time.time() < deadline:
        time.sleep(0.01)
    s.stop()
    assert len(ring) >= 2
    n = len(ring)
    time.sleep(0.05)
    assert len(ring) == n  # stopped means stopped


def test_registry_snapshot_shape():
    reg = obs_metrics.Registry()
    reg.counter("c_total", labels=("k",)).labels("a").inc(2)
    reg.histogram("h_lat", buckets=(1.0,)).observe(0.5)
    snap = reg.snapshot()
    assert snap["c_total"]["kind"] == "counter"
    assert snap["c_total"]["children"][("a",)] == 2.0
    hchild = snap["h_lat"]["children"][()]
    assert hchild["count"] == 1 and hchild["sum"] == 0.5
    assert hchild["cumulative"][-1][1] == 1
