"""Observability tests: metrics registry semantics, span tracing and
context propagation, Prometheus exposition, and the instrumented HTTP
serving path (ISSUE 3)."""

import json
import re
import threading
import urllib.error
import urllib.request

import pytest

from kolibrie_tpu.frontends.http_server import make_server
from kolibrie_tpu.obs import export as obs_export
from kolibrie_tpu.obs import metrics as obs_metrics
from kolibrie_tpu.obs import runtime as obs_runtime
from kolibrie_tpu.obs import spans as obs_spans
from kolibrie_tpu.optimizer import caps

# ------------------------------------------------------------------ helpers


@pytest.fixture(scope="module")
def server():
    httpd = make_server("127.0.0.1", 0, quiet=True)
    port = httpd.server_address[1]
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    yield f"http://127.0.0.1:{port}"
    httpd.shutdown()


def post(base, path, payload, headers=None):
    req = urllib.request.Request(
        base + path,
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
        method="POST",
    )
    with urllib.request.urlopen(req) as resp:
        return dict(resp.headers), json.loads(resp.read())


def get(base, path):
    with urllib.request.urlopen(base + path) as resp:
        return dict(resp.headers), resp.read().decode()


def trace_spans(base, trace_id):
    """One trace from ``/debug/traces``.  The reply is on the wire before the
    server leaves ``http.request``, so wait for that span to land."""
    import time

    deadline = time.monotonic() + 5.0
    while True:
        _, body = get(base, f"/debug/traces?trace_id={trace_id}")
        spans = [json.loads(l) for l in body.splitlines() if l]
        if any(s["name"] == "http.request" for s in spans) or (
            time.monotonic() > deadline
        ):
            return spans
        time.sleep(0.01)


NT = "\n".join(f'<http://e/{i}> <http://e/p> "{i}" .' for i in range(64))
QUERY = "SELECT ?s ?o WHERE { ?s <http://e/p> ?o }"


# ------------------------------------------------------------ metrics core


def test_histogram_bucket_boundaries():
    reg = obs_metrics.Registry()
    h = reg.histogram("t_hist", "test", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.1, 0.5, 1.0, 5.0, 10.0, 99.0):
        h.observe(v)
    cum = h._default.cumulative()
    # boundary values land in their own bucket (le is inclusive)
    assert cum == [(0.1, 2), (1.0, 4), (10.0, 6), (float("inf"), 7)]
    assert h._default.count == 7
    assert h._default.sum == pytest.approx(sum((0.05, 0.1, 0.5, 1.0, 5.0, 10.0, 99.0)))


def test_counter_concurrent_increments():
    reg = obs_metrics.Registry()
    c = reg.counter("t_conc", "test")
    per_thread, n_threads = 1000, 8

    def work():
        for _ in range(per_thread):
            c.inc()

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c._default.value == per_thread * n_threads


def test_labeled_children_are_independent():
    reg = obs_metrics.Registry()
    c = reg.counter("t_lbl", "test", labels=("kind",))
    c.labels("a").inc(3)
    c.labels("b").inc()
    assert c.labels("a").value == 3
    assert c.labels("b").value == 1
    with pytest.raises(ValueError):
        c.labels("a", "extra")


def test_registry_rejects_kind_conflicts():
    reg = obs_metrics.Registry()
    reg.counter("t_kind", "test")
    with pytest.raises(ValueError):
        reg.gauge("t_kind", "test")


def test_disabled_runtime_skips_recording():
    reg = obs_metrics.Registry()
    c = reg.counter("t_off", "test")
    h = reg.histogram("t_off_h", "test")
    obs_runtime.set_enabled(False)
    try:
        c.inc()
        h.observe(1.0)
        with obs_spans.span("t.off"):
            pass
    finally:
        obs_runtime.set_enabled(True)
    assert c._default.value == 0
    assert h._default.count == 0
    assert not obs_spans.spans_snapshot()[-1:] or (
        obs_spans.spans_snapshot()[-1]["name"] != "t.off"
    )


# ------------------------------------------------------------------- spans


def test_span_nesting_and_ring():
    obs_spans.clear()
    with obs_spans.trace_scope("trace-nest") as tid:
        assert tid == "trace-nest"
        with obs_spans.span("outer"):
            with obs_spans.span("inner"):
                pass
    recorded = obs_spans.spans_snapshot("trace-nest")
    by_name = {s["name"]: s for s in recorded}
    assert set(by_name) == {"outer", "inner"}
    assert by_name["outer"]["parent_id"] is None
    assert by_name["inner"]["parent_id"] == by_name["outer"]["span_id"]
    # JSONL export round-trips
    lines = obs_spans.export_jsonl("trace-nest").splitlines()
    assert len(lines) == 2 and all(json.loads(l)["trace_id"] == "trace-nest" for l in lines)


def test_span_ring_eviction():
    obs_spans.set_ring_capacity(8)
    try:
        obs_spans.clear()
        with obs_spans.trace_scope("trace-evict"):
            for i in range(20):
                with obs_spans.span(f"s{i}"):
                    pass
        kept = obs_spans.spans_snapshot("trace-evict")
        assert len(kept) == 8
        # oldest evicted, newest retained
        assert [s["name"] for s in kept] == [f"s{i}" for i in range(12, 20)]
    finally:
        obs_spans.set_ring_capacity(obs_spans.DEFAULT_RING_CAPACITY)


def test_span_records_errors():
    obs_spans.clear()
    with obs_spans.trace_scope("trace-err"):
        with pytest.raises(RuntimeError):
            with obs_spans.span("boom"):
                raise RuntimeError("kaboom")
    (sp,) = obs_spans.spans_snapshot("trace-err")
    assert "kaboom" in sp["error"]


def test_baggage_scoped_to_trace():
    with obs_spans.trace_scope("trace-bag"):
        obs_spans.set_baggage("template", "fp123")
        assert obs_spans.get_baggage("template") == "fp123"
        with obs_spans.trace_scope("trace-bag-2"):
            assert obs_spans.get_baggage("template") is None
        assert obs_spans.get_baggage("template") == "fp123"


# ------------------------------------------------------------- exposition


def test_prometheus_exposition_parses():
    text = obs_export.render_prometheus()
    sample_re = re.compile(
        r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? -?[0-9.e+Inf-]+$"
    )
    seen_types = []
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP"):
            continue
        if line.startswith("# TYPE"):
            parts = line.split()
            assert parts[3] in ("counter", "gauge", "histogram")
            seen_types.append(parts[2])
            continue
        assert sample_re.match(line), f"unparseable sample line: {line!r}"
    # one TYPE per metric family, no duplicates
    assert len(seen_types) == len(set(seen_types))
    # the catalog's core families are present
    for name in (
        "kolibrie_http_request_seconds",
        "kolibrie_plan_cache_events_total",
        "kolibrie_device_dispatch_seconds",
        "kolibrie_admission_inflight",
        "kolibrie_breaker_trips_total",
        "kolibrie_rsp_dead_letters_total",
    ):
        assert f"# TYPE {name} " in text, name


def test_histogram_exposition_shape():
    reg = obs_metrics.Registry()
    h = reg.histogram("t_expo", "test", buckets=(1.0, 2.0))
    h.observe(0.5)
    h.observe(1.5)
    text = obs_export.render_prometheus(reg)
    assert 't_expo_bucket{le="1"} 1' in text
    assert 't_expo_bucket{le="2"} 2' in text
    assert 't_expo_bucket{le="+Inf"} 2' in text
    assert "t_expo_sum 2" in text
    assert "t_expo_count 2" in text


def test_label_value_escaping():
    reg = obs_metrics.Registry()
    c = reg.counter("t_esc", "test", labels=("v",))
    c.labels('quo"te\nnl').inc()
    text = obs_export.render_prometheus(reg)
    assert 't_esc{v="quo\\"te\\nnl"} 1' in text


# ------------------------------------------------- HTTP serving path (e2e)


def test_trace_propagation_http_to_executor(server):
    obs_spans.clear()
    post(server, "/store/load",
         {"store_id": "obs1", "rdf": NT, "format": "ntriples", "mode": "device"})
    headers, out = post(
        server, "/store/query", {"store_id": "obs1", "sparql": QUERY},
        headers={"X-Kolibrie-Trace-Id": "trace-e2e-1"},
    )
    assert headers.get("X-Kolibrie-Trace-Id") == "trace-e2e-1"
    assert len(out["data"]) == 64
    spans = trace_spans(server, "trace-e2e-1")
    assert spans and all(s["trace_id"] == "trace-e2e-1" for s in spans)
    names = {s["name"] for s in spans}
    # the full serving chain under ONE trace id: HTTP → batcher → executor
    # → device phases (parse/plan/lower/dispatch/collect)
    assert {
        "http.request", "batcher.submit", "batcher.dispatch",
        "query.execute", "query.parse", "query.plan",
        "device.lower", "device.dispatch", "device.collect",
    } <= names
    # parent links resolve within the trace
    ids = {s["span_id"] for s in spans}
    for s in spans:
        if s["parent_id"] is not None:
            assert s["parent_id"] in ids


def test_generated_trace_id_echoed(server):
    headers, _ = post(server, "/query", {"sparql": "SELECT ?s WHERE { ?s ?p ?o }",
                                         "rdf": "", "format": "ntriples"})
    assert re.fullmatch(r"[0-9a-f]{32}", headers.get("X-Kolibrie-Trace-Id", ""))


def test_error_payload_carries_trace_id(server):
    req = urllib.request.Request(
        server + "/store/query",
        data=json.dumps({"store_id": "missing", "sparql": QUERY}).encode(),
        headers={"Content-Type": "application/json",
                 "X-Kolibrie-Trace-Id": "trace-err-404"},
        method="POST",
    )
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(req)
    body = json.loads(ei.value.read())
    assert ei.value.code == 404
    assert body["trace_id"] == "trace-err-404"


def test_metrics_endpoint_scrapes(server):
    post(server, "/store/load",
         {"store_id": "obs2", "rdf": NT, "format": "ntriples"})
    post(server, "/store/query", {"store_id": "obs2", "sparql": QUERY})
    headers, text = get(server, "/metrics")
    assert headers["Content-Type"].startswith("text/plain")
    assert "# TYPE kolibrie_http_requests_total counter" in text
    assert 'kolibrie_batcher_queue_depth{store="obs2"}' in text
    assert "kolibrie_device_compile_cache_entries" in text
    # counters visibly moved
    m = re.search(
        r'kolibrie_http_requests_total\{route="/store/query",code="200"\} (\d+)',
        text,
    )
    assert m and int(m.group(1)) >= 1


def test_stats_single_source_of_truth(server):
    post(server, "/store/load",
         {"store_id": "obs3", "rdf": NT, "format": "ntriples"})
    post(server, "/store/query", {"store_id": "obs3", "sparql": QUERY})
    _, text = get(server, "/stats")
    stats = json.loads(text)
    block = stats["stores"]["obs3"]
    # legacy shape preserved (asserted by test_plan_template/test_chaos too)
    for key in ("requests", "dispatches", "dedup_hits", "max_batch",
                "shed_queue_full", "shed_deadline", "per_template",
                "triples", "plan_cache", "breakers", "device_compiles"):
        assert key in block, key
    assert block["requests"] >= 1
    # both renderers ARE the same function: TemplateBatcher.stats()
    # delegates to the obs.export builder the /stats handler uses
    from kolibrie_tpu.frontends.http_server import TemplateBatcher
    from kolibrie_tpu.query.sparql_database import SparqlDatabase

    b = TemplateBatcher(SparqlDatabase())
    assert b.stats() == obs_export.store_stats(b)


def test_debug_profile_noops_on_cpu(server):
    _, out = post(server, "/debug/profile?seconds=0.01", {})
    assert out["profiled"] is False
    assert out["backend"] == "cpu"
    assert "KOLIBRIE_PROFILE_FORCE" in out["reason"]


def test_debug_profile_forced_on_cpu(server, monkeypatch):
    # env is read per request, so the module-scoped server honors it
    monkeypatch.setenv("KOLIBRIE_PROFILE_FORCE", "1")
    _, out = post(server, "/debug/profile?seconds=0.01", {})
    assert out["profiled"] is True
    assert out["forced"] is True
    assert out["backend"] == "cpu"
    assert isinstance(out["trace_files"], int) and out["trace_files"] >= 1
    assert out["trace_dir"]


def test_label_escaping_round_trips():
    # backslash, newline and double-quote through the exposition format
    # and back: unescaping the rendered line recovers the original value
    raw = 'a\\b"c\nd'
    reg = obs_metrics.Registry()
    reg.counter("t_rt", "test", labels=("v",)).labels(raw).inc()
    text = obs_export.render_prometheus(reg)
    m = re.search(r't_rt\{v="((?:[^"\\]|\\.)*)"\} 1', text)
    assert m, text
    unescaped = (
        m.group(1)
        .replace("\\\\", "\x00")
        .replace("\\n", "\n")
        .replace('\\"', '"')
        .replace("\x00", "\\")
    )
    assert unescaped == raw


# ----------------------------------------------- EXPLAIN ANALYZE (ISSUE 14)


def test_store_query_explain_analyze(server):
    post(server, "/store/load",
         {"store_id": "obs_an", "rdf": NT, "format": "ntriples",
          "mode": "device"})
    _, out = post(server, "/store/query?explain=analyze",
                  {"store_id": "obs_an", "sparql": QUERY})
    assert len(out["data"]) == 64
    recs = out["explain"]
    assert isinstance(recs, list) and recs
    ops = next(r["operators"] for r in recs
               if r["kind"] in ("device", "interp"))
    assert ops["scan0"] == 64


def test_store_query_rejects_unknown_explain_mode(server):
    with pytest.raises(urllib.error.HTTPError) as ei:
        post(server, "/store/query?explain=verbose",
             {"store_id": "obs_an", "sparql": QUERY})
    assert ei.value.code == 400


def test_debug_explain_endpoint(server):
    # inline dataset: per-operator actuals annotated onto the plan tree
    _, out = post(server, "/debug/explain",
                  {"rdf": NT, "format": "ntriples", "sparql": QUERY})
    assert "actual=" in out["plan"]
    assert "device time:" in out["plan"]
    # registered store: same renderer, batcher's db under its lock
    _, out = post(server, "/debug/explain",
                  {"store_id": "obs_an", "sparql": QUERY})
    assert "actual=" in out["plan"]
    assert "source:" in out["plan"]


def test_debug_timeline_endpoint(server):
    from kolibrie_tpu.obs import timeseries

    ring = timeseries.default_ring()
    ring.record()
    post(server, "/store/query", {"store_id": "obs_an", "sparql": QUERY})
    ring.record()
    _, text = get(server, "/debug/timeline")
    body = json.loads(text)
    assert body["samples"] >= 2
    assert body["interval_s"] == timeseries.DEFAULT_INTERVAL_S
    assert body["capacity"] == ring.capacity
    # the serving counters the queries above moved are in the ring
    assert "kolibrie_http_requests_total" in body["metrics"]
    # ?metric= narrows, ?n= windows
    _, text = get(server,
                  "/debug/timeline?metric=kolibrie_http_requests_total&n=2")
    narrowed = json.loads(text)
    assert list(narrowed["metrics"]) == ["kolibrie_http_requests_total"]
    assert narrowed["samples"] == 2
    with pytest.raises(urllib.error.HTTPError) as ei:
        get(server, "/debug/timeline?n=bogus")
    assert ei.value.code == 400


def test_trace_id_reaches_interpreter_spans(server, monkeypatch):
    # satellite: the client trace id must survive into the PR-9
    # plan-interpreter route's spans
    monkeypatch.setenv("KOLIBRIE_PLAN_INTERP", "force")
    obs_spans.clear()
    post(server, "/store/load",
         {"store_id": "obs_int", "rdf": NT, "format": "ntriples",
          "mode": "device"})
    post(server, "/store/query", {"store_id": "obs_int", "sparql": QUERY},
         headers={"X-Kolibrie-Trace-Id": "trace-interp-1"})
    _, body = get(server, "/debug/traces?trace_id=trace-interp-1")
    spans = [json.loads(l) for l in body.splitlines() if l]
    names = {s["name"] for s in spans}
    assert "interp.dispatch" in names, names
    assert all(s["trace_id"] == "trace-interp-1" for s in spans)


# ------------------------------------- inside device.dispatch (ISSUE 25)

GRAPH_NT = "\n".join(
    line
    for i in range(40)
    for line in (
        f"<http://g/n{i}> <http://g/knows> <http://g/n{(i + 1) % 40}> .",
        f"<http://g/n{i}> <http://g/likes> <http://g/n{(i + 2) % 40}> .",
        f"<http://g/n{(i + 1) % 40}> <http://g/likes> <http://g/n{(i + 2) % 40}> .",
        f'<http://g/n{i}> <http://g/name> "n{i}" .',
    )
)
JOIN_Q = "SELECT ?a ?n WHERE { ?a <http://g/knows> ?b . ?a <http://g/name> ?n }"
TRIANGLE_Q = (
    "SELECT ?a ?b ?c WHERE { ?a <http://g/knows> ?b . "
    "?b <http://g/likes> ?c . ?a <http://g/likes> ?c }"
)
DISPATCH_CHILDREN = ("device.build", "device.enqueue", "device.wait", "device.counts")


def graph_db():
    from kolibrie_tpu import SparqlDatabase

    db = SparqlDatabase()
    db.execution_mode = "device"
    db.parse_ntriples(GRAPH_NT)
    return db


def children_of(spans, parent_name):
    parents = [s for s in spans if s["name"] == parent_name]
    return parents, {
        p["span_id"]: [s for s in spans if s["parent_id"] == p["span_id"]]
        for p in parents
    }


def counter_values(prefix):
    """Every sample of the families whose name starts with ``prefix``."""
    out = {}
    for line in obs_export.render_prometheus().splitlines():
        if line.startswith(prefix):
            key, _, value = line.rpartition(" ")
            out[key] = float(value)
    return out


def test_device_dispatch_child_spans():
    from kolibrie_tpu import execute_query_volcano

    db = graph_db()
    obs_spans.clear()
    assert len(execute_query_volcano(JOIN_Q, db)) == 40
    spans = obs_spans.spans_snapshot()
    (dispatch,), kids = children_of(spans, "device.dispatch")
    mine = kids[dispatch["span_id"]]
    assert [s["name"] for s in mine] == list(DISPATCH_CHILDREN)
    assert sum(s["dur_ms"] for s in mine) <= dispatch["dur_ms"]
    by_name = {s["name"]: s for s in mine}
    assert by_name["device.build"]["attrs"]["h2d_bytes"] > 0  # first use
    assert by_name["device.enqueue"]["attrs"]["compiled"] in (0, 1)
    assert by_name["device.wait"]["attrs"] == {"attempt": 0}
    assert by_name["device.counts"]["attrs"] == {"attempt": 0, "n": 1}
    # the executor's decode is a child of its own request span
    (execute,), kids = children_of(spans, "query.execute")
    assert "query.decode" in {s["name"] for s in kids[execute["span_id"]]}


def test_cap_overflow_repeats_children_and_counts_seconds():
    from kolibrie_tpu import execute_query_volcano

    family = 'kolibrie_cap_retry_seconds_total{engine="device"}'
    db = graph_db()
    rows = execute_query_volcano(JOIN_Q, db)
    # forget what the first run learned: the next starts from a capacity
    # its 40 matches overflow, and has to re-run with a doubled one
    joins = caps.of(db).joins
    for key, held in joins.items():
        joins.start(key, [8] * len(held))
    obs_spans.clear()
    before = counter_values("kolibrie_cap_retry_seconds_total")[family]
    assert execute_query_volcano(JOIN_Q, db) == rows
    (dispatch,), kids = children_of(obs_spans.spans_snapshot(), "device.dispatch")
    mine = kids[dispatch["span_id"]]
    assert [s["name"] for s in mine] == list(DISPATCH_CHILDREN) * 2
    assert [s["attrs"]["attempt"] for s in mine if s["name"] == "device.wait"] == [0, 1]
    assert sum(s["dur_ms"] for s in mine) <= dispatch["dur_ms"]
    grew = counter_values("kolibrie_cap_retry_seconds_total")[family] - before
    rerun_ms = sum(s["dur_ms"] for s in mine[4:])
    assert rerun_ms / 1000.0 <= grew <= dispatch["dur_ms"] / 1000.0


def test_dispatch_children_off_when_disabled():
    from kolibrie_tpu import execute_query_volcano

    db = graph_db()
    names = ("kolibrie_cap_retry_seconds", "kolibrie_store_", "kolibrie_device_compile_seconds")
    obs_spans.clear()
    before = {n: counter_values(n) for n in names}
    obs_runtime.set_enabled(False)
    try:
        assert len(execute_query_volcano(TRIANGLE_Q, db)) == 40
    finally:
        obs_runtime.set_enabled(True)
    assert obs_spans.spans_snapshot() == []
    assert {n: counter_values(n) for n in names} == before


SETUP_COUNTERS = (
    ['kolibrie_store_load_seconds_total{phase="%s"}' % p for p in ("parse", "compact")]
    + ['kolibrie_store_order_build_seconds_total{order="%s"}' % o
       for o in ("spo", "pos", "osp", "pso", "ops", "sop")]
    + ['kolibrie_store_h2d_seconds_total{segment="%s"}' % s for s in ("base", "delta")]
    + ['kolibrie_device_compile_seconds_total{source="%s"}' % s
       for s in ("compile", "disk")]
    + ['kolibrie_cap_retry_seconds_total{engine="device"}']
)


def setup_counters():
    values = counter_values("kolibrie_")
    return {k: values[k] for k in SETUP_COUNTERS}  # every child exists, at 0


def test_load_then_query_grows_setup_counters(server):
    before = setup_counters()
    obs_spans.clear()
    post(server, "/store/load",
         {"store_id": "obs_setup", "rdf": GRAPH_NT, "format": "ntriples",
          "mode": "device"})
    # a shape no other test of this module compiles
    headers, out = post(
        server, "/store/query",
        {"store_id": "obs_setup", "sparql":
         "SELECT ?a ?c ?n WHERE { ?a <http://g/knows> ?b . "
         "?b <http://g/knows> ?c . ?c <http://g/name> ?n }"},
        headers={"X-Kolibrie-Trace-Id": "trace-setup-1"})
    assert len(out["data"]) == 40
    grew = {k: v - before[k] for k, v in setup_counters().items() if v > before[k]}
    assert 'kolibrie_store_load_seconds_total{phase="parse"}' in grew
    assert 'kolibrie_store_load_seconds_total{phase="compact"}' in grew
    assert 'kolibrie_store_h2d_seconds_total{segment="base"}' in grew
    assert 'kolibrie_store_h2d_seconds_total{segment="delta"}' in grew
    assert any(k.startswith("kolibrie_store_order_build_seconds_total")
               and "spo" not in k for k in grew), grew
    # XLA compiled the new shape, or the persistent cache held it
    assert any(k.startswith("kolibrie_device_compile_seconds_total") for k in grew)
    # the front door's two halves, under the request's span
    spans = trace_spans(server, "trace-setup-1")
    (request,), kids = children_of(spans, "http.request")
    names = [s["name"] for s in kids[request["span_id"]]]
    assert names[0] == "http.read_body" and names[-1] == "http.respond"


@pytest.mark.parametrize(
    "sparql, use_pallas, scopes",
    [
        (JOIN_Q, False, ("scan0", "scan1", "join0")),
        (TRIANGLE_Q, False,
         ("wcoj0/wcoj0.L0/probe", "wcoj0/wcoj0.L1/expand", "wcoj0/wcoj0.L1/dedup",
          "wcoj0/wcoj0.L2/live")),
        (TRIANGLE_Q, True,
         ("wcoj0.L1/dedup/lex_probe_select", "wcoj0.L1/live/lex_probe_validate")),
    ],
)
def test_lowered_plan_names_its_operators(sparql, use_pallas, scopes):
    """The EXPLAIN ANALYZE keys are the scope path of each operator's ops --
    what a device profile shows as an op's ``tf_op``."""
    import jax
    from test_chip_compile import _lower_bgp

    from kolibrie_tpu.optimizer import device_engine as de

    spec, args = _lower_bgp(graph_db(), sparql).build()
    with jax.enable_x64(True):
        text = de._run_plan.lower(spec, use_pallas, *args).as_text(debug_info=True)
    paths = set(re.findall(r'"(jit\(_run_plan\)/[^"]*)"', text))
    for scope in scopes:
        assert any(f"/{scope}/" in p for p in paths), (scope, sorted(paths)[:40])
