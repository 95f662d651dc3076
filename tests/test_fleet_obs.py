"""Fleet observability (ISSUE 18): structured logging, the crash
flight recorder, Prometheus fleet merge, cross-process trace
propagation through the router (including the retry hop), and the
router's /fleet/metrics + /fleet/status aggregation endpoints."""

import json
import os
import socket
import threading
import time
import urllib.error
import urllib.request

import pytest

from kolibrie_tpu.obs import flightrec
from kolibrie_tpu.obs import log as obslog
from kolibrie_tpu.obs import promtext
from kolibrie_tpu.obs.spans import (
    clear as spans_clear,
    new_trace_id,
    spans_snapshot,
    trace_scope,
)
from kolibrie_tpu.replication.router import make_router, template_affinity_key

# ------------------------------------------------------------------ helpers


@pytest.fixture(autouse=True)
def _quiet_logs():
    """Silence the stderr sink and isolate the tail ring per test; the
    module state is process-wide."""
    obslog.set_quiet(True)
    obslog.clear()
    yield
    obslog.set_quiet(False)
    obslog.clear()


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _get(base, path, headers=None, timeout=30):
    req = urllib.request.Request(base + path, headers=headers or {})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, resp.read(), dict(resp.headers)


def _wait_ready(base, timeout_s=60.0):
    import time as _time

    deadline = _time.monotonic() + timeout_s
    last = None
    while _time.monotonic() < deadline:
        try:
            st, body, _ = _get(base, "/healthz", timeout=5)
            last = json.loads(body)
            if st == 200 and last.get("status") == "ready":
                return last
        except (urllib.error.URLError, OSError):
            pass
        _time.sleep(0.05)
    raise AssertionError(f"{base} never became ready: {last}")


def _wait_follower_applied(base, segment, timeout_s=30.0):
    import time as _time

    deadline = _time.monotonic() + timeout_s
    while _time.monotonic() < deadline:
        st, body, _ = _get(base, "/healthz")
        repl = json.loads(body).get("replication") or {}
        if (repl.get("watermark") or {}).get("applied_segment", 0) >= segment:
            return
        _time.sleep(0.05)
    raise AssertionError(f"{base} never applied segment {segment}")


def _post(base, path, payload, headers=None, timeout=30):
    h = {"Content-Type": "application/json"}
    h.update(headers or {})
    req = urllib.request.Request(
        base + path, data=json.dumps(payload).encode(), headers=h,
        method="POST",
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read()), dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


# ------------------------------------------------------- structured logging


def test_log_record_shape_and_tail_ring():
    lg = obslog.get_logger("unit")
    lg.info("hello", key=7)
    recs = obslog.tail(component="unit")
    assert recs, "tail ring recorded nothing"
    rec = recs[-1]
    assert rec["component"] == "unit"
    assert rec["msg"] == "hello"
    assert rec["key"] == 7
    assert rec["level"] == "info"
    assert isinstance(rec["ts"], float)
    # no span context live -> no trace_id key at all
    assert "trace_id" not in rec


def test_log_trace_id_auto_injected_from_span_context():
    lg = obslog.get_logger("unit")
    with trace_scope(None) as tid:
        lg.warn("inside")
    assert obslog.tail(component="unit")[-1]["trace_id"] == tid


def test_log_level_floor_and_filters():
    lg = obslog.get_logger("unit")
    obslog.set_min_level("warn")
    try:
        lg.info("dropped")
        lg.error("kept")
    finally:
        obslog.set_min_level("info")
    msgs = [r["msg"] for r in obslog.tail(component="unit")]
    assert msgs == ["kept"]
    assert obslog.tail(level="error", component="unit")[-1]["msg"] == "kept"


def test_log_export_jsonl_parses():
    lg = obslog.get_logger("unit")
    lg.info("a")
    lg.info("b")
    lines = obslog.export_jsonl().splitlines()
    parsed = [json.loads(ln) for ln in lines if ln.strip()]
    assert [p["msg"] for p in parsed if p["component"] == "unit"] == ["a", "b"]


def test_logger_handles_are_cached():
    assert obslog.get_logger("same") is obslog.get_logger("same")


# -------------------------------------------------------- flight recorder


def test_flightrec_dump_and_read_bundle_roundtrip(tmp_path):
    obslog.get_logger("unit").info("pre-crash narrative")
    with trace_scope(None):
        pass
    path = flightrec.dump(
        str(tmp_path), "manual", stats_fn=lambda: {"stores": {}}
    )
    assert os.path.basename(os.path.dirname(path)) == "postmortem"
    bundle = flightrec.read_bundle(path)
    assert bundle["manifest"]["reason"] == "manual"
    assert bundle["manifest"]["pid"] == os.getpid()
    assert sorted(bundle["manifest"]["artifacts"]) == [
        "config.json", "log_tail.jsonl", "spans.jsonl",
        "stats.json", "timeline.json",
    ]
    assert bundle["stats"] == {"stores": {}}
    assert any(
        r.get("msg") == "pre-crash narrative" for r in bundle["log_tail"]
    )
    assert isinstance(bundle["config"]["argv"], list)
    # no partial debris left behind
    assert not [
        n
        for n in os.listdir(flightrec.postmortem_dir(str(tmp_path)))
        if n.startswith(".")
    ]


def test_flightrec_stats_failure_degrades_not_fails(tmp_path):
    def broken():
        raise RuntimeError("stats surface is on fire")

    path = flightrec.dump(str(tmp_path), "manual", stats_fn=broken)
    bundle = flightrec.read_bundle(path)
    assert "RuntimeError" in bundle["stats"]["error"]


def test_flightrec_try_dump_never_raises(tmp_path):
    # a FILE where the data dir should be: makedirs fails
    blocker = tmp_path / "not-a-dir"
    blocker.write_text("x")
    assert flightrec.try_dump(str(blocker / "sub"), "manual") is None
    errs = obslog.tail(level="error", component="flightrec")
    assert errs and errs[-1]["msg"] == "postmortem dump failed"


def test_flightrec_blackbox_checkpoint_and_listing(tmp_path):
    rec = flightrec.FlightRecorder(str(tmp_path), interval_s=3600.0)
    box = rec.checkpoint()
    assert box == rec.blackbox_path
    bundle = flightrec.read_bundle(box)
    assert bundle["manifest"]["reason"] == "checkpoint"
    # refresh in place: same dir, manifest stays parseable
    rec.checkpoint()
    assert rec.checkpoints == 2
    assert flightrec.read_bundle(box)["manifest"]["reason"] == "checkpoint"
    terminal = flightrec.dump(str(tmp_path), "sigterm")
    paths = flightrec.list_bundles(str(tmp_path))
    assert paths[-1] == box, "blackbox sorts last"
    assert terminal in paths


def test_flightrec_recorder_thread_rolls_checkpoints(tmp_path):
    rec = flightrec.FlightRecorder(str(tmp_path), interval_s=0.05)
    rec.start()
    try:
        deadline = 50
        while rec.checkpoints < 2 and deadline:
            deadline -= 1
            threading.Event().wait(0.05)
    finally:
        rec.stop()
    assert rec.checkpoints >= 2
    assert flightrec.read_bundle(rec.blackbox_path)["manifest"]["pid"] == (
        os.getpid()
    )


# ------------------------------------------------------------ fleet merge


def test_merge_prometheus_overlapping_families_disjoint_labels():
    node_a = "\n".join([
        "# HELP reqs_total requests",
        "# TYPE reqs_total counter",
        'reqs_total{route="/query"} 5',
        "# HELP up node liveness",
        "# TYPE up gauge",
        "up 1",
    ]) + "\n"
    node_b = "\n".join([
        "# HELP reqs_total requests (other wording)",
        "# TYPE reqs_total counter",
        'reqs_total{shard="0",zone="z1"} 9',   # disjoint label set
        "# HELP only_b unique family",
        "# TYPE only_b gauge",
        "only_b 3",
    ]) + "\n"
    merged = promtext.merge_prometheus({"a": node_a, "b": node_b})
    lines = merged.splitlines()
    # one HELP/TYPE per family even when both nodes expose it
    assert lines.count("# TYPE reqs_total counter") == 1
    assert sum(ln.startswith("# HELP reqs_total") for ln in lines) == 1
    # the node label is stamped first, original labels kept
    assert 'reqs_total{node="a",route="/query"} 5' in lines
    assert 'reqs_total{node="b",shard="0",zone="z1"} 9' in lines
    # label-less samples gain a braces block
    assert 'up{node="a"} 1' in lines
    assert 'only_b{node="b"} 3' in lines
    # family grouping: both reqs_total samples sit under the one header
    i = lines.index("# TYPE reqs_total counter")
    block = lines[i + 1:i + 3]
    assert all(ln.startswith("reqs_total{") for ln in block)


def test_merge_prometheus_histograms_keep_suffixed_series_together():
    node = "\n".join([
        "# HELP lat_seconds latency",
        "# TYPE lat_seconds histogram",
        'lat_seconds_bucket{le="0.1"} 2',
        'lat_seconds_bucket{le="+Inf"} 3',
        "lat_seconds_sum 0.4",
        "lat_seconds_count 3",
    ]) + "\n"
    merged = promtext.merge_prometheus({"n1": node})
    lines = merged.splitlines()
    assert lines.count("# TYPE lat_seconds histogram") == 1
    assert 'lat_seconds_bucket{node="n1",le="0.1"} 2' in lines
    assert 'lat_seconds_sum{node="n1"} 0.4' in lines
    assert 'lat_seconds_count{node="n1"} 3' in lines
    # _bucket/_sum/_count all grouped under the family header
    assert lines.index('lat_seconds_count{node="n1"} 3') > lines.index(
        "# TYPE lat_seconds histogram"
    )


def test_merge_prometheus_drops_garbage_lines():
    merged = promtext.merge_prometheus(
        {"n": "!!! not exposition\nok_total 1\n"}
    )
    assert 'ok_total{node="n"} 1' in merged
    assert "!!!" not in merged


# ----------------------------------------- live fleet (in-process servers)


@pytest.fixture
def fleet(tmp_path):
    """A real primary shipping WAL to a real follower, fronted by the
    router — all in-process (threads), all on ephemeral ports."""
    from kolibrie_tpu.frontends import http_server as hs

    repl_port = _free_port()
    prim = hs.make_server(
        "127.0.0.1", 0, quiet=True,
        data_dir=str(tmp_path / "prim"), recover_async=False,
        repl_port=repl_port,
    )
    fol = hs.make_server(
        "127.0.0.1", 0, quiet=True,
        data_dir=str(tmp_path / "fol"), recover_async=False,
        repl_source=f"127.0.0.1:{repl_port}",
    )
    threads = []
    for httpd in (prim, fol):
        t = threading.Thread(target=httpd.serve_forever, daemon=True)
        t.start()
        threads.append(t)
    prim_base = f"http://127.0.0.1:{prim.server_address[1]}"
    fol_base = f"http://127.0.0.1:{fol.server_address[1]}"
    # a third rung that refuses connections: the retry-hop fault
    ghost_base = f"http://127.0.0.1:{_free_port()}"
    router, core = make_router(
        [("prim", prim_base), ("fol", fol_base), ("ghost", ghost_base)],
        probe_interval_s=3600.0,  # probes only when the test asks
        auto_promote=False,
    )
    rt = threading.Thread(target=router.serve_forever, daemon=True)
    rt.start()
    router_base = f"http://127.0.0.1:{router.server_address[1]}"
    try:
        _wait_ready(prim_base)
        _wait_ready(fol_base)  # follower gates ready on first bootstrap
        core.probe_once()
        yield {
            "core": core,
            "router": router_base,
            "prim": prim_base,
            "fol": fol_base,
            "prim_httpd": prim,
            "fol_httpd": fol,
        }
    finally:
        core.stop()
        router.shutdown()
        for httpd in (prim, fol):
            hs.shutdown_gracefully(httpd, timeout_s=5.0)
            httpd.shutdown()


def _traces_for(base, tid):
    # a node's reply is on the wire before it leaves ``http.request``: wait
    # for that span to land in its ring
    deadline = time.monotonic() + 5.0
    while True:
        st, body, _ = _get(base, f"/debug/traces?trace_id={tid}")
        assert st == 200
        recs = [
            json.loads(ln) for ln in body.decode().splitlines() if ln.strip()
        ]
        if any(r["name"] == "http.request" for r in recs) or (
            time.monotonic() > deadline
        ):
            return recs
        time.sleep(0.01)


def _sparql_with_home(core, home, fallback):
    """A query whose rendezvous home is ``home`` and whose retry rung is
    ``fallback`` — deterministically found, not hoped for.  After the
    home fails it drops from the recomputed order, and attempt 1 indexes
    the SECOND remaining rung, so the full order must be
    [home, other, fallback]."""
    for i in range(400):
        # the affinity key masks IRIs/literals/numbers — vary the
        # VARIABLE names so each candidate is a distinct template
        q = f"SELECT ?s{i} WHERE {{ ?s{i} <http://e/p> ?o }}"
        order = [r.name for r in core.read_order(template_affinity_key(q))]
        if order[0] == home and order[2] == fallback:
            return q
    raise AssertionError(f"no template homed on {home} then {fallback}")


def test_e2e_trace_propagation_router_replica_primary(fleet):
    core = fleet["core"]
    core.probe_once()
    assert core.primary() is not None
    spans_clear()

    tid = new_trace_id()
    hdr = {"X-Kolibrie-Trace-Id": tid}

    # hop 1: a mutation through the router lands on the PRIMARY
    st, out, headers = _post(
        fleet["router"], "/store/load",
        {"rdf": '<http://e/a> <http://e/p> "1" .', "format": "ntriples"},
        headers=hdr,
    )
    assert st == 200, out
    assert headers["X-Kolibrie-Replica"] == "prim"
    # the read below may land on the follower: wait until it holds the store
    _wait_follower_applied(fleet["fol"], out["watermark"]["segment"])

    # hop 2 (with retry): force the ghost as the rendezvous home so the
    # first forward dies on a refused connect and the ladder retries to
    # the follower — same trace id on every rung
    with core.lock:
        ghost = core.replicas["ghost"]
        ghost.healthy = True
        ghost.role = "follower"
        ghost.evicted = False  # probes during boot already evicted it
        ghost.consecutive_failures = 0
    q = _sparql_with_home(core, "ghost", "fol")
    st, out, headers = _post(
        fleet["router"], "/store/query",
        {"store_id": out["store_id"], "sparql": q}, headers=hdr,
    )
    assert st == 200, out
    assert headers["X-Kolibrie-Replica"] == "fol"
    assert headers["X-Kolibrie-Trace-Id"] == tid

    # the router's own ring: request span + one forward span per rung.
    # The router writes the reply before it leaves ``router.request``, so
    # the second request's span may still be open: wait for it to close
    deadline = time.monotonic() + 5.0
    while True:
        router_spans = spans_snapshot(tid)
        names = [s["name"] for s in router_spans]
        if names.count("router.request") >= 2 or time.monotonic() > deadline:
            break
        time.sleep(0.01)
    assert names.count("router.request") == 2
    forwards = [s for s in router_spans if s["name"] == "router.forward"]
    by_attempt = {
        (s["attrs"]["replica"], s["attrs"]["attempt"]) for s in forwards
    }
    assert ("ghost", 0) in by_attempt, by_attempt  # the failed rung
    assert ("fol", 1) in by_attempt, by_attempt    # the retry hop
    assert ("prim", 0) in by_attempt, by_attempt   # the mutation

    # one trace id stitches router -> primary -> follower: each node's
    # span ring holds http.request spans under the SAME id
    for base in (fleet["prim"], fleet["fol"]):
        recs = _traces_for(base, tid)
        assert any(r["name"] == "http.request" for r in recs), base
        assert {r["trace_id"] for r in recs} == {tid}


def test_fleet_metrics_merges_all_nodes(fleet):
    core = fleet["core"]
    core.probe_once()
    # traffic so replica registries hold interesting families
    st, out, _ = _post(
        fleet["router"], "/store/load",
        {"rdf": '<http://e/a> <http://e/p> "1" .', "format": "ntriples"},
    )
    assert st == 200, out
    st, body, headers = _get(fleet["router"], "/fleet/metrics")
    assert st == 200
    assert headers["Content-Type"].startswith("text/plain")
    text = body.decode()
    node_label = f'node="{core.node_id}"'
    assert node_label in text  # the router's own registry rides along
    assert 'node="prim"' in text
    assert 'node="fol"' in text
    # replication SLO families surface with node attribution
    assert "kolibrie_repl_lag_segments" in text
    assert "kolibrie_repl_applied_records" in text
    # merged exposition keeps one TYPE header per family
    lines = text.splitlines()
    type_lines = [ln for ln in lines if ln.startswith("# TYPE ")]
    assert len(type_lines) == len(set(type_lines))
    # TTL cache: an immediate re-scrape returns the identical payload
    st2, body2, _ = _get(fleet["router"], "/fleet/metrics")
    assert st2 == 200 and body2 == body


def test_fleet_status_reports_watermarks_and_lag(fleet):
    core = fleet["core"]
    core.probe_once()
    st, out, _ = _post(
        fleet["router"], "/store/load",
        {"rdf": '<http://e/a> <http://e/p> "1" .', "format": "ntriples"},
    )
    assert st == 200, out
    core.fleet_cache_ttl_s = 0.0  # fresh view per call for the test
    core.probe_once()
    st, body, _ = _get(fleet["router"], "/fleet/status")
    assert st == 200
    status = json.loads(body)
    nodes = status["nodes"]
    assert nodes["prim"]["role"] == "primary"
    assert nodes["fol"]["role"] == "follower"
    assert nodes["prim"]["healthy"] and nodes["fol"]["healthy"]
    assert not nodes["ghost"]["healthy"]
    assert status["head_segment"] >= 1
    for name in ("prim", "fol"):
        n = nodes[name]
        assert n["applied_lag_segments"] >= 0
        assert n["applied_lag_segments"] == (
            status["head_segment"] - n["applied_segment"]
        )
        assert n["probe_age_s"] is not None and n["probe_age_s"] >= 0.0
    assert status["promotions"] == 0
    assert "last_failover_ms" in status


def test_debug_bundle_endpoint_writes_a_bundle(fleet, tmp_path):
    st, out, _ = _post(fleet["prim"], "/debug/bundle", {})
    assert st == 200, out
    bundle = flightrec.read_bundle(out["path"])
    assert bundle["manifest"]["reason"] == "manual"
    assert str(tmp_path / "prim") in out["path"]
    # the live /stats surface made it into the bundle
    assert "stores" in bundle["stats"]


def test_reads_shed_catching_up_is_counted(fleet):
    core = fleet["core"]
    core.probe_once()
    st, out, _ = _post(
        fleet["prim"], "/store/load",
        {"rdf": '<http://e/a> <http://e/p> "1" .', "format": "ntriples"},
    )
    assert st == 200, out
    from kolibrie_tpu.obs import metrics as obs_metrics

    fam = obs_metrics.REGISTRY.get("kolibrie_reads_shed_catching_up_total")
    child = fam.children()[0][1]
    # the store must exist on the follower before the watermark gate is
    # even consulted — wait for the load's segment to apply
    _wait_follower_applied(fleet["fol"], out["watermark"]["segment"])
    before = child.value
    st, out, _ = _post(
        fleet["fol"], "/store/query",
        {"store_id": out["store_id"],
         "sparql": "SELECT ?s ?p ?o WHERE { ?s ?p ?o }",
         "min_watermark": {"segment": 10_000}},
    )
    assert st == 503 and out["phase"] == "catching_up", out
    assert child.value == before + 1
