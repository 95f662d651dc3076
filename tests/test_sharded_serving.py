"""Sharded serving (parallel/sharded_serving.py) on the virtual 8-device
CPU mesh: mirror/store agreement, batched template groups vs the
single-device oracle, zero-recompile mutation batches, recovery rebuilds,
resilience degradation, and the HTTP front door end to end.

Every result-bearing test uses the host volcano executor as the oracle —
the mesh path must return identical rows (ISSUE 8 acceptance).
"""

import json
import sys
import threading
import urllib.request
from pathlib import Path

import numpy as np
import pytest

from kolibrie_tpu.parallel.sharded_serving import (
    attach_sharded,
    detach_sharded,
    sharded_compile_stats,
)
from kolibrie_tpu.obs import export as obs_export
from kolibrie_tpu.query.executor import (
    _plan_cache_entry,
    _plan_caches,
    execute_queries_batched,
    execute_query_volcano,
)
from kolibrie_tpu.query.sparql_database import SparqlDatabase

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "examples"))
import lubm  # noqa: E402

PREFIX = "PREFIX ub: <http://swat.cse.lehigh.edu/onto/univ-bench.owl#>\n"
# one template, varied only by the department constant — the serving
# pattern the parameterized mesh program targets
TEMPLATE = (
    PREFIX
    + "SELECT ?x ?c WHERE {{ ?x ub:worksFor <{dept}> . ?x ub:teacherOf ?c . }}"
)
# a plain SELECT the batcher admits and the mesh lowering declines (a
# filter that compares two variables): it stays on the single-device copy
DECLINED = (
    PREFIX
    + "SELECT ?x ?c WHERE {{ ?x ub:worksFor <{dept}> . ?x ub:teacherOf ?c . "
    "FILTER(?x != ?c) }}"
)
DEPTS_Q = PREFIX + "SELECT DISTINCT ?d WHERE { ?x ub:worksFor ?d . }"
WORKS_Q = PREFIX + "SELECT ?x ?d WHERE { ?x ub:worksFor ?d . }"


def _lubm_db(n_univ=2):
    db = SparqlDatabase()
    s, p, o = lubm.generate_fast(n_univ, db.dictionary)
    db.store.add_batch(s, p, o)
    db.execution_mode = "host"
    return db


def _template_group(db, k=4):
    deps = execute_query_volcano(DEPTS_Q, db)
    assert len(deps) >= k
    return [TEMPLATE.format(dept=d[0]) for d in deps[:k]]


def _lone(sh, text):
    """``text`` served by the mesh as a group of one: what a request that
    meets no other in the batcher gets."""
    return sh.execute_batch(_fp(sh.db, text), [(0, text)])[0]


def _metric(prefix, text=None):
    """Sum of the ``/metrics`` samples whose name and labels start with
    ``prefix`` (a labelled counter has no line until it first grows)."""
    if text is None:
        text = obs_export.render_prometheus()
    return sum(
        float(line.rpartition(" ")[2])
        for line in text.splitlines()
        if line.startswith(prefix)
    )


_ROUTES = {
    "mesh_queries": "kolibrie_shard_queries_total",
    "lone": 'kolibrie_shard_dispatch_total{path="lone"}',
    "batched": "kolibrie_query_batched_total",
    "single_device": "kolibrie_query_seconds_count",
    "fallbacks": "kolibrie_shard_fallback_total",
}


def _routes(text=None):
    return {k: _metric(name, text) for k, name in _ROUTES.items()}


def _grew(before, after):
    return {k: after[k] - before[k] for k in before}


@pytest.fixture(scope="module")
def sharded_db(mesh8):
    db = _lubm_db()
    sh = attach_sharded(db, mesh8)
    sh.refresh()
    return db, sh


# ------------------------------------------------------------------ mirrors


def test_mirror_matches_store(sharded_db):
    db, sh = sharded_db
    st = db.store
    bs, bp, bo = st.base_rows("spo")
    keep = np.ones(len(bs), dtype=bool)
    keep[st.delta_del_positions("spo")] = False
    ds, dp, do = st.delta_rows("spo")
    expect = set(zip(bs[keep].tolist(), bp[keep].tolist(), bo[keep].tolist()))
    expect |= set(zip(ds.tolist(), dp.tolist(), do.tolist()))
    s, p, o = sh.view.gather_host()
    assert set(zip(s.tolist(), p.tolist(), o.tolist())) == expect


def test_refresh_is_idempotent(sharded_db):
    db, sh = sharded_db
    rebuilds = sh.stats_counters["base_rebuilds"]
    assert sh.refresh() is False  # nothing moved: no device traffic
    assert sh.stats_counters["base_rebuilds"] == rebuilds


def test_occupancy_and_signature(sharded_db):
    db, sh = sharded_db
    stats = sh.stats()
    assert stats["shards"] == 8
    assert len(stats["occupancy"]) == 8
    assert sum(stats["occupancy"]) == len(db.store)
    assert stats["imbalance"] >= 1.0
    assert sh.signature == ("shards", 8, sh.axis)


# ------------------------------------------------------- batched execution


def test_batched_group_matches_oracle(sharded_db):
    db, sh = sharded_db
    texts = _template_group(db, 4)
    oracle = [execute_query_volcano(t, db) for t in texts]
    assert all(len(r) > 0 for r in oracle)
    got = execute_queries_batched(db, texts)
    assert got == oracle
    assert sh.stats_counters["batched_queries"] >= 4


@pytest.mark.parametrize("size", [1, 2, 3, 5, 8])
def test_group_runs_its_live_members_in_one_executable(sharded_db, size):
    db, sh = sharded_db
    texts = _template_group(db, 8)
    fp = _plan_cache_entry(db, texts[0])[0]["fp"]
    # a group of one settles the template's capacities ...
    sh.execute_batch(fp, [(0, texts[0])])
    programs = sharded_compile_stats()["batched_programs"]
    items = list(enumerate(texts[:size]))
    got = sh.execute_batch(fp, items)
    assert [got[i] for i, _ in items] == [
        execute_query_volcano(t, db) for t in texts[:size]
    ]
    assert all(len(got[i]) > 0 for i, _ in items)
    # ... and no further size builds another program
    assert sharded_compile_stats()["batched_programs"] == programs
    # the program itself: rows past the live count were never written
    with sh.lock:
        group = sh._build_group(fp, items)
        outs, stats = sh._run_group(fp, group, None)
    assert group["params"].shape[0] == 8
    final = np.asarray(stats)[:, :, -1]  # a member's rows, shard by shard
    assert (final[:size].sum(axis=1) > 0).all()
    assert not final[size:].any()
    assert all(not np.asarray(o)[size:].any() for o in outs)
    # a member's rows stand at the front of its slots on every shard
    for o in map(np.asarray, outs):
        for r in range(size):
            for s in range(sh.n):
                assert o[r, s, : final[r, s]].all()
                assert not o[r, s, final[r, s]:].any()
    assert sharded_compile_stats()["batched_programs"] == programs


def test_larger_group_takes_the_next_slot_class(sharded_db):
    from kolibrie_tpu.parallel.sharded_serving import _slot_class

    assert [_slot_class(b) for b in (1, 2, 7, 8, 9, 16, 17)] == [
        8, 8, 8, 8, 16, 16, 32,
    ]
    db, sh = sharded_db
    texts = _template_group(db, 9)
    fp = _plan_cache_entry(db, texts[0])[0]["fp"]
    slots = _metric("kolibrie_shard_member_slots_total")
    got = sh.execute_batch(fp, list(enumerate(texts)))
    assert [got[i] for i in range(9)] == [
        execute_query_volcano(t, db) for t in texts
    ]
    assert _metric("kolibrie_shard_member_slots_total") - slots == 16


def test_lone_request_is_served_by_the_mesh(sharded_db):
    db, sh = sharded_db
    text = _template_group(db, 6)[5]
    before = _routes()
    assert execute_queries_batched(db, [text]) == [
        execute_query_volcano(text, db)
    ]
    # the oracle call above is the one single-device execution
    assert _grew(before, _routes()) == {
        "mesh_queries": 1,
        "lone": 1,
        "batched": 1,
        "single_device": 1,
        "fallbacks": 0,
    }


def test_declined_shape_answers_from_the_single_device_copy(sharded_db):
    db, sh = sharded_db
    dept = execute_query_volcano(DEPTS_Q, db)[0][0]
    text = DECLINED.format(dept=dept)
    oracle = execute_query_volcano(text, db)
    assert len(oracle) > 0
    before = _routes()
    assert execute_queries_batched(db, [text]) == [oracle]
    assert _grew(before, _routes()) == {
        "mesh_queries": 0,
        "lone": 0,
        "batched": 0,
        "single_device": 1,
        "fallbacks": 1,
    }


def test_the_program_has_what_the_cell_lubm5_mesh4_requires(monkeypatch):
    """``benchmark/requires/lubm5.mesh4.json``: ``run.py`` refuses a program
    without it before anything starts (the parent of PR 28 is one)."""
    import os

    from benchmark import harness

    monkeypatch.setenv("KOLIBRIE_SHARDED", "")  # the cell's env is undone after
    harness._requires("lubm5.mesh4")
    assert os.environ["KOLIBRIE_SHARDED"] == "1"


def test_warm_builds_the_executable_requests_run(mesh8):
    db = _lubm_db(1)
    sh = attach_sharded(db, mesh8)
    sh.refresh()
    texts = _template_group(db, 4)
    assert sh.warm(texts[0]) is True
    assert sh.stats_counters["prewarmed"] == 1
    programs = sharded_compile_stats()["batched_programs"]
    hits = sh.stats_counters["cap_hits"]
    assert execute_queries_batched(db, texts[1:]) == [
        execute_query_volcano(t, db) for t in texts[1:]
    ]
    assert sharded_compile_stats()["batched_programs"] == programs
    assert sh.stats_counters["cap_hits"] == hits
    # a template the mesh lowering declines serves single-device
    assert sh.warm(DECLINED.format(dept="http://nowhere/d")) is False


def test_plan_cache_state_key_carries_mesh_signature(sharded_db):
    db, sh = sharded_db
    execute_queries_batched(db, _template_group(db, 2))
    _, templates, _ = _plan_caches(db)
    keys = [k for t in templates.values() for k in t["by_state"]]
    assert keys and all(k[-1] == sh.signature for k in keys)


def test_divergent_members_fall_back_to_oracle(mesh8):
    # members differing beyond pattern constants must NOT ride the
    # parameterized program — and must still return oracle rows
    db = _lubm_db(1)
    attach_sharded(db, mesh8).refresh()
    deps = execute_query_volcano(DEPTS_Q, db)
    texts = [
        PREFIX + "SELECT ?x ?c WHERE { ?x ub:worksFor <%s> . "
        "?x ub:teacherOf ?c . FILTER(?x != <%s>) }" % (d[0], d[0])
        for d in deps[:2]
    ]
    oracle = [execute_query_volcano(t, db) for t in texts]
    assert execute_queries_batched(db, texts) == oracle


# ------------------------------------------------ mutation: O(delta), fuzz


def test_interleaved_mutation_fuzz_vs_oracle(mesh8):
    db = _lubm_db(1)
    sh = attach_sharded(db, mesh8)
    sh.refresh()
    texts = _template_group(db, 3)
    rng = np.random.default_rng(8)
    d = db.dictionary
    pred = np.uint32(d.encode("http://fuzz/p"))
    works = np.uint32(d.encode(
        "http://swat.cse.lehigh.edu/onto/univ-bench.owl#worksFor"
    ))
    churn = []  # live fuzz triples, each unique (never re-added)
    uid = 0
    for rnd in range(6):
        n_add = int(rng.integers(1, 30))
        s = np.array(
            [d.encode(f"http://fuzz/s{uid + k}") for k in range(n_add)],
            dtype=np.uint32,
        )
        o = np.array(
            [d.encode(f"http://fuzz/o{uid + k}") for k in range(n_add)],
            dtype=np.uint32,
        )
        uid += n_add
        db.store.add_batch(s, np.full(n_add, pred, dtype=np.uint32), o)
        churn.extend(zip(s.tolist(), o.tolist()))
        for _ in range(min(len(churn), int(rng.integers(0, 8)))):
            ts, to = churn.pop(int(rng.integers(0, len(churn))))
            db.store.remove(ts, int(pred), to)
        # also delete a LIVE LUBM edge so the oracle answer itself moves
        rows = execute_query_volcano(WORKS_Q, db)
        vx, vd = rows[int(rng.integers(0, len(rows)))]
        db.store.remove(d.encode(vx), int(works), d.encode(vd))
        got = execute_queries_batched(db, texts)
        assert got == [execute_query_volcano(t, db) for t in texts], rnd
        # the mirror tracks the live store exactly after each round
        s_, p_, o_ = sh.view.gather_host()
        assert len(s_) == len(db.store)


def test_mutation_batches_cause_zero_recompiles(mesh8):
    db = _lubm_db(1)
    sh = attach_sharded(db, mesh8)
    sh.refresh()
    texts = _template_group(db, 3)
    execute_queries_batched(db, texts)  # prime: compile once
    before = sharded_compile_stats()
    # no served template reads the probe index, so a view builds none until
    # a consumer asks (``dist_join.dist_bgp_join_count``); from then on the
    # refreshes keep it, two-tier
    assert sh.view.subj_index_parts is None
    sh.view.ensure_subj_index()
    d = db.dictionary
    for r in range(5):
        s = np.array(
            [d.encode(f"http://zr/{r}-{k}") for k in range(6)], dtype=np.uint32
        )
        p = np.full(6, d.encode("http://zr/p"), dtype=np.uint32)
        o = np.array(
            [d.encode(f"http://zr/o{r}-{k}") for k in range(6)],
            dtype=np.uint32,
        )
        db.store.add_batch(s, p, o)
        execute_queries_batched(db, texts)
        if r == 0:  # the first refresh after it was asked packs the base
            base_builds = sh.view.subj_index_base_builds
    assert sharded_compile_stats() == before
    # satellite: the per-shard probe index must NOT full-repack per batch
    assert sh.view.subj_index_base_builds == base_builds
    assert sh.view.subj_index_delta_builds >= 4


# --------------------------------------------------- durability / recovery


def test_recovery_rebuilds_sharded_mirrors(mesh8, tmp_path):
    from kolibrie_tpu.durability.manager import DurabilityManager

    data = str(tmp_path / "data")
    m = DurabilityManager(data, fsync_policy="always")
    m.start()
    db = SparqlDatabase()
    db.execution_mode = "host"
    m.attach("s1", db)
    db.parse_ntriples(
        "\n".join(
            f"<http://r/e{i}> <http://r/p> <http://r/o{i % 7}> ."
            for i in range(60)
        )
    )
    m.snapshot({"s1": db})
    # post-snapshot mutations ride the WAL only
    db.parse_ntriples("<http://r/extra> <http://r/p> <http://r/o1> .")
    q = "SELECT ?s WHERE { ?s <http://r/p> <http://r/o1> . }"
    oracle = execute_query_volcano(q, db)
    m.close()

    m2 = DurabilityManager(data, fsync_policy="always")
    rebuilt = {}

    def hook(sid, rdb):
        sh = attach_sharded(rdb, mesh8)
        sh.refresh()
        rebuilt[sid] = sh

    m2.on_store_recovered = hook
    res = m2.recover()
    m2.close()
    assert "s1" in rebuilt  # snapshot restore + WAL replay reached the hook
    rdb = res.stores["s1"]
    assert len(rdb.store) == len(db.store)
    s, p, o = rebuilt["s1"].view.gather_host()
    assert len(s) == len(rdb.store)
    assert sorted(_lone(rebuilt["s1"], q)) == sorted(oracle)


def test_checkpoint_restore_then_refresh(mesh8, tmp_path):
    # restore swaps every base array: refresh must rebuild the mirrors for
    # the new arrays even when the shape signature looks unchanged
    db = _lubm_db(1)
    sh = attach_sharded(db, mesh8)
    sh.refresh()
    path = str(tmp_path / "ck.bin")
    db.checkpoint(path)
    db2 = SparqlDatabase.from_checkpoint(path)
    db2.execution_mode = "host"
    sh2 = attach_sharded(db2, mesh8)
    sh2.refresh()
    q = _template_group(db, 1)[0]
    assert _lone(sh2, q) == execute_query_volcano(q, db)


# ------------------------------------------------------------- resilience


def test_mesh_fault_degrades_to_single_device(mesh8):
    from kolibrie_tpu.resilience.breaker import breaker_board
    from kolibrie_tpu.resilience.faultinject import (
        FaultPlan,
        InjectedDeviceOOM,
    )

    db = _lubm_db(1)
    attach_sharded(db, mesh8).refresh()
    texts = _template_group(db, 3)
    oracle = [execute_query_volcano(t, db) for t in texts]
    plan = FaultPlan(seed=3)
    plan.add("shard.dispatch", error=InjectedDeviceOOM, rate=1.0)
    with plan.installed():
        got = execute_queries_batched(db, texts)
    assert got == oracle  # degraded single-device path, same rows
    snap = breaker_board(db).snapshot()
    assert any(rec["total_failures"] >= 1 for rec in snap.values())


def test_mesh_deadline_propagates(mesh8):
    from kolibrie_tpu.resilience.deadline import Deadline, deadline_scope
    from kolibrie_tpu.resilience.errors import DeadlineExceeded

    db = _lubm_db(1)
    sh = attach_sharded(db, mesh8)
    sh.refresh()
    with pytest.raises(DeadlineExceeded):
        with deadline_scope(Deadline(0.0)):
            _lone(sh, _template_group(db, 1)[0])


def test_detach_restores_single_device_key(mesh8):
    db = _lubm_db(1)
    sh = attach_sharded(db, mesh8)
    sh.refresh()
    texts = _template_group(db, 2)
    execute_queries_batched(db, texts)
    detach_sharded(db)
    assert execute_queries_batched(db, texts) == [
        execute_query_volcano(t, db) for t in texts
    ]
    _, templates, _ = _plan_caches(db)
    keys = [k for t in templates.values() for k in t["by_state"]]
    assert any(k[-1] is None for k in keys)
    assert any(k[-1] == sh.signature for k in keys)


# ------------------------------------------------------- obs / trace spans


def test_dispatch_emits_shard_spans(sharded_db):
    from kolibrie_tpu.obs.spans import spans_snapshot, trace_scope

    db, sh = sharded_db
    texts = _template_group(db, 3)
    with trace_scope("trace-shard") as tid:
        execute_queries_batched(db, texts)
    spans = spans_snapshot(tid)
    names = [s["name"] for s in spans]
    assert "executor.sharded" in names
    assert "shard.dispatch" in names
    # build, wait and merge are the dispatch's children and cover it
    dispatch = next(s for s in spans if s["name"] == "shard.dispatch")
    parts = {
        s["name"]: s for s in spans if s["parent_id"] == dispatch["span_id"]
    }
    assert sorted(parts) == ["shard.build", "shard.merge", "shard.wait"]
    assert parts["shard.wait"]["attrs"] == {"slots": 8, "live": 3}
    assert sum(p["dur_ms"] for p in parts.values()) <= dispatch["dur_ms"]
    kids = [s for s in spans if s["name"] == "shard.partition"]
    assert len(kids) == 8  # one child per shard, occupancy attached
    assert all("rows" in k["attrs"] for k in kids)
    assert all(
        k["parent_id"] == parts["shard.merge"]["span_id"] for k in kids
    )


# ----------------------------------------------------------- HTTP serving


@pytest.fixture()
def sharded_server(mesh8, monkeypatch):
    from kolibrie_tpu.frontends import http_server

    monkeypatch.setattr(http_server, "SHARDED_SERVING", True)
    httpd = http_server.make_server("127.0.0.1", 0, quiet=True)
    port = httpd.server_address[1]
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    yield f"http://127.0.0.1:{port}"
    httpd.shutdown()


def _post(base, path, payload, headers=None):
    req = urllib.request.Request(
        base + path,
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=60) as resp:
        return json.loads(resp.read())


def test_http_sharded_store_end_to_end(sharded_server):
    base = sharded_server
    db = _lubm_db(1)
    out = _post(
        base,
        "/store/load",
        {"rdf": db.to_ntriples(), "format": "ntriples", "mode": "host"},
    )
    sid = out["store_id"]
    assert out["triples"] == len(db.store)
    # the LUBM suite through the HTTP path: identical to the oracle
    for q in (lubm.LUBM_Q2, DEPTS_Q, *_template_group(db, 2)):
        got = _post(base, "/store/query", {"store_id": sid, "sparql": q})
        oracle = execute_query_volcano(q, db)
        assert sorted(map(tuple, got["data"])) == sorted(map(tuple, oracle))
    # shard-level health is exported in /stats ...
    with urllib.request.urlopen(base + "/stats", timeout=60) as resp:
        stats = json.loads(resp.read())
    sharding = stats["stores"][sid]["sharding"]
    assert sharding["shards"] == 8
    assert len(sharding["occupancy"]) == 8
    assert "last_cap_hit" in sharding
    # ... and the kolibrie_shard_* series in /metrics
    with urllib.request.urlopen(base + "/metrics", timeout=60) as resp:
        metrics = resp.read().decode()
    assert "kolibrie_shard_rows_scanned_total" in metrics
    assert "kolibrie_store_shards" in metrics


def test_http_lone_request_is_served_by_the_mesh(sharded_server):
    base = sharded_server
    db = _lubm_db(1)
    sid = _post(
        base,
        "/store/load",
        {"rdf": db.to_ntriples(), "format": "ntriples", "mode": "host"},
    )["store_id"]

    def metrics():
        with urllib.request.urlopen(base + "/metrics", timeout=60) as resp:
            return _routes(resp.read().decode())

    def ask(text):
        got = _post(base, "/store/query", {"store_id": sid, "sparql": text})
        return sorted(map(tuple, got["data"]))

    lone, declined = (
        t.format(dept=execute_query_volcano(DEPTS_Q, db)[0][0])
        for t in (TEMPLATE, DECLINED)
    )
    oracle = [
        sorted(map(tuple, execute_query_volcano(t, db)))
        for t in (lone, declined)
    ]
    before = metrics()
    assert ask(lone) == oracle[0]
    assert _grew(before, metrics()) == {
        "mesh_queries": 1,
        "lone": 1,
        "batched": 1,
        "single_device": 0,
        "fallbacks": 0,
    }
    before = metrics()
    assert ask(declined) == oracle[1]
    assert _grew(before, metrics()) == {
        "mesh_queries": 0,
        "lone": 0,
        "batched": 0,
        "single_device": 1,
        "fallbacks": 1,
    }


# ------------------------------- a member's plan from counted rows (PR 29)

Q7 = (Path(__file__).resolve().parent.parent
      / "benchmark" / "templates" / "lubm_q7.rq").read_text()
# a template whose cheapest seed depends on its constants: the advisees of
# a professor against the takers of a course
TWO_KEYS = (
    PREFIX
    + "SELECT ?x WHERE {{ ?x ub:advisor <{prof}> . ?x ub:takesCourse <{course}> . }}"
)
ADVISED_Q = (
    PREFIX
    + "SELECT ?x ?p ?c WHERE { ?x ub:advisor ?p . ?x ub:takesCourse ?c . }"
)


@pytest.fixture(scope="module")
def uba(mesh8):
    """LUBM(1) in UBA's distribution (the benchmark's generator, as the
    cell ``lubm5.mesh4`` loads it) on the 8-device mesh, and Q7's text for
    each of its departments."""
    from benchmark.harness import data as files

    config = files.read_json("configs", "lubm-5-mesh4.json")
    data = files.load_module("generators", config["generator"]).generate(
        config, 7, 1
    )
    db = SparqlDatabase()
    ids = np.fromiter(
        (
            db.dictionary.encode(t[1:-1] if t.startswith("<") else t)
            for t in data["terms"]
        ),
        dtype=np.uint32,
        count=len(data["terms"]),
    )
    db.store.add_batch(ids[data["s"]], ids[data["p"]], ids[data["o"]])
    db.execution_mode = "host"
    sh = attach_sharded(db, mesh8)
    sh.refresh()
    texts = [
        Q7.replace("@department@", d) for d in data["domains"]["department"]
    ]
    return db, sh, texts


def _fp(db, text):
    return _plan_cache_entry(db, text)[0]["fp"]


def _plan_of(sh, fp):
    with sh.lock:
        return sh._pinned_plan(fp)


def _slots(sh, ex, live, join_cap, bucket_cap):
    """What ``kolibrie_shard_cap_slots_total`` counts for one dispatch."""
    from kolibrie_tpu.parallel.dist_query import exchanged_steps

    exchanges = sum(exchanged_steps(ex.premises, ex.seed, ex.steps, sh.n))
    return live * sh.n * (
        len(ex.steps) * join_cap + exchanges * sh.n * bucket_cap
    )


def test_q7_starts_from_the_professor_and_settles_in_one_program(uba):
    db, sh, texts = uba
    fp = _fp(db, texts[0])
    programs = sharded_compile_stats()["batched_programs"]
    hits = sh.stats_counters["cap_hits"]
    counted = _metric('kolibrie_shard_plan_total{source="counted"}')
    got = sh.execute_batch(fp, [(0, texts[0])])
    oracle = execute_query_volcano(texts[0], db)
    assert got[0] == oracle and len(oracle) > 0
    seed, join_cap, bucket_cap, out_cap = _plan_of(sh, fp)
    with sh.lock:
        exemplar = sh._build_group(fp, [(0, texts[0])])["execs"][0]
    # the premise with the professor as its subject: 2-4 rows, where the
    # most-constants rule starts from every student
    assert exemplar.premises[seed].consts[0] is not None
    assert [j for j, *_ in exemplar.steps] == [1, 2, 0]
    assert (join_cap, bucket_cap, out_cap) == (1024, 1024, 1024)
    assert sharded_compile_stats()["batched_programs"] == programs + 1
    assert sh.stats_counters["cap_hits"] == hits
    assert _metric('kolibrie_shard_plan_total{source="counted"}') == counted + 1
    # every other department runs the plan and the executable of the first
    items = list(enumerate(texts[1:9]))
    got = sh.execute_batch(fp, items)
    assert [got[i] for i, _ in items] == [
        execute_query_volcano(t, db) for _, t in items
    ]
    assert _plan_of(sh, fp) == (seed, 1024, 1024, 1024)
    assert sharded_compile_stats()["batched_programs"] == programs + 1
    assert sh.stats_counters["cap_hits"] == hits
    assert _metric('kolibrie_shard_plan_total{source="counted"}') == counted + 1


@pytest.mark.parametrize(
    "body, seed", [("batched", None), ("batched", 0), ("solo", None)]
)
def test_the_host_counts_what_the_program_counts(uba, body, seed):
    """Capacities set to the host's two counts exactly hold the program's
    rows, and one slot fewer in either overflows: the largest per-shard
    join step (unfiltered key matches for the batched body, the side
    premise's own rows for the solo one) and the largest (source,
    destination) bucket."""
    from kolibrie_tpu.parallel.dist_query import DistQueryExecutor
    from kolibrie_tpu.parallel.sharded_serving import _get_batched_fn
    from kolibrie_tpu.parallel.sharded_store import shard_of
    import jax

    db, sh, texts = uba
    text = texts[3]
    ex = DistQueryExecutor(
        sh.mesh, db, text, store=sh.view, seed=seed, batched=body == "batched"
    )
    from kolibrie_tpu.parallel.dist_query import _largest

    step_rows, buckets, _table, _shard = ex._count_chain(
        ex.premises, ex.seed, ex.steps
    )
    step, bucket = _largest(step_rows, buckets)
    assert step > 1 and bucket > 1
    if body == "batched":
        # the batched body compacts its seed into ``join_cap`` slots, so
        # the seed's rows a shard are among what the plan's largest step
        # has to cover
        scan = db.store.match(*ex.premises[ex.seed].consts)
        seed_rows = np.bincount(shard_of(scan[0], sh.n), minlength=sh.n)
        assert len(step_rows) == 1 + len(ex.steps)
        assert step_rows[0].tolist() == seed_rows.tolist()
        assert step >= seed_rows.max() > 0
    if seed == 0:
        # from every student the last step meets every triple that has a
        # course as its object: three orders of magnitude over the answer
        assert step > 1000 * len(execute_query_volcano(text, db))
        assert seed_rows.max() > 1000

    def overflows(join_cap, bucket_cap):
        if body == "solo":
            ex.join_cap, ex.bucket_cap = join_cap, bucket_cap
            try:
                ex.run_device(max_attempts=1)
            except RuntimeError:
                return True, None
            return False, None
        with sh.lock:
            group = sh._build_group(_fp(db, text), [(0, text)])
        state = (
            *sh.view.by_subj, sh.view.by_subj_valid,
            *sh.view.by_obj, sh.view.by_obj_valid,
            *sh._subj_sorted, *sh._obj_sorted,
        )
        fn = _get_batched_fn(
            sh.mesh, group["premises"], ex.seed, ex.steps, ex.filters,
            ex.out_vars, len(group["masks"]), join_cap, bucket_cap,
            join_cap, 8,
        )
        with jax.enable_x64(True):
            _outs, overflow, stats = fn(
                state, group["masks"], group["params"], np.int32(1)
            )
        return int(np.asarray(overflow)[0]) > 0, np.asarray(stats)[0]

    over, stats = overflows(step, bucket)
    assert not over
    if stats is not None:
        # [seed, (exchange, matches, join) a step, final], per shard
        assert stats[:, 0].tolist() == step_rows[0].tolist()
        for k, per_shard in enumerate(step_rows[1:]):
            assert stats[:, 2 + 3 * k].tolist() == per_shard.tolist()
    assert overflows(step - 1, bucket)[0]
    assert overflows(step, bucket - 1)[0]


@pytest.mark.parametrize(
    "width, cap, rows",
    [
        (64, 8, 0), (64, 8, 1), (64, 8, 7), (64, 8, 8), (64, 8, 9),
        (64, 8, 64), (4, 8, 3),
    ],
)
def test_compact_keeps_the_first_cap_valid_rows_in_order(width, cap, rows):
    import jax

    from kolibrie_tpu.parallel.dist_join import compact

    rng = np.random.default_rng(rows)
    valid = np.zeros(width, dtype=bool)
    valid[rng.choice(width, rows, replace=False)] = True
    cols = (
        np.arange(1, width + 1, dtype=np.uint32) * 3,
        rng.integers(1, 1 << 31, width).astype(np.uint32),
    )
    out, ok, dropped = jax.jit(compact, static_argnums=2)(cols, valid, cap)
    pos = np.flatnonzero(valid)[:cap]
    assert np.asarray(ok).tolist() == [True] * len(pos) + [False] * (
        cap - len(pos)
    )
    for col, got in zip(cols, out):
        assert np.asarray(got).tolist() == col[pos].tolist() + [0] * (
            cap - len(pos)
        )
    assert int(dropped) == max(rows - cap, 0)


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for j in v if isinstance(v, (tuple, list)) else (v,):
            j = getattr(j, "jaxpr", j)
            if hasattr(j, "eqns"):
                yield j


def _eqns(jaxpr, inside=()):
    """Every equation under ``jaxpr`` with the primitives it is nested in."""
    for eqn in jaxpr.eqns:
        yield eqn, inside
        for sub in _sub_jaxprs(eqn):
            yield from _eqns(sub, inside + (eqn.primitive.name,))


@pytest.mark.parametrize("width", [1, 4095, 4096, 5000, 66560])
def test_prefix_count_in_blocks_is_the_flat_cumsum(width):
    """``dist_join.prefix_count`` sums a wide mask (or counts) in blocks of
    1,024 with the blocks' totals beneath them: the numbers of a flat
    ``cumsum`` at every width, a multiple of the block or not."""
    import jax

    from kolibrie_tpu.parallel.dist_join import prefix_count

    rng = np.random.default_rng(width)
    mask = rng.random(width) < 0.3
    counts = rng.integers(0, 7, width).astype(np.int32)
    for x in (mask, counts):
        got = np.asarray(jax.jit(prefix_count)(x))
        assert got.dtype == np.int32
        assert got.tolist() == np.cumsum(x.astype(np.int64)).tolist()


def test_nothing_in_the_member_loop_indexes_at_the_shards_width(uba):
    """Inside the live-member loop of Q7's mesh program no gather or
    scatter takes indices, and no search (a nested ``while``) carries
    keys, of a mirror block's width, no sort runs there at all (the
    mirrors come sorted with the state) and one prefix sum over as many
    elements as a block has: the seed's compaction, in blocks.  A member's
    cost follows its capacities, not the shard."""
    import jax

    from kolibrie_tpu.parallel.sharded_serving import _get_batched_fn

    db, sh, texts = uba
    fp = _fp(db, texts[0])
    with sh.lock:
        group = sh._build_group(fp, [(0, texts[0])])
    ex = group["execs"][0]
    join_cap, bucket_cap, out_cap = group["caps"]
    wide = {sh._base_cap_s + sh._delta_cap, sh._base_cap_o + sh._delta_cap}
    assert not wide & {join_cap, bucket_cap, sh.n * bucket_cap}
    assert min(wide) > sh.n * bucket_cap
    state = (
        *sh.view.by_subj, sh.view.by_subj_valid,
        *sh.view.by_obj, sh.view.by_obj_valid,
        *sh._subj_sorted, *sh._obj_sorted,
    )
    assert {a.shape[1] for a in state} == wide
    fn = _get_batched_fn(
        sh.mesh, group["premises"], ex.seed, ex.steps, ex.filters,
        ex.out_vars, len(group["masks"]), join_cap, bucket_cap, out_cap, 8,
    )
    with jax.enable_x64(True):
        program = jax.make_jaxpr(fn)(
            state, group["masks"], group["params"], np.int32(1)
        )

    def is_wide(var):
        return bool(wide & set(getattr(var.aval, "shape", ())))

    def holds_a_block(var):
        return int(np.prod(getattr(var.aval, "shape", ()))) >= min(wide)

    loops = [
        eqn
        for eqn, inside in _eqns(program.jaxpr)
        if eqn.primitive.name == "while"
        and "while" not in inside
        and any(
            e.primitive.name == "all_to_all"
            for e, _ in _eqns(eqn.params["body_jaxpr"].jaxpr)
        )
    ]
    assert len(loops) == 1  # the member loop: Q7 exchanges once a member
    seen = {"gather": 0, "search": 0, "wide_order": 0}
    for eqn, _ in _eqns(loops[0].params["body_jaxpr"].jaxpr):
        name = eqn.primitive.name
        if name == "gather" or name.startswith("scatter"):
            seen["gather"] += 1
            assert not is_wide(eqn.invars[1]), eqn
        elif name in ("while", "scan"):
            # a ``searchsorted``: its carry (the bounds) is as wide as its
            # keys, the sorted side it searches is a constant of the loop
            seen["search"] += 1
            consts = (
                eqn.params["num_consts"]
                if name == "scan"
                else eqn.params["cond_nconsts"] + eqn.params["body_nconsts"]
            )
            assert not any(is_wide(v) for v in eqn.invars[consts:]), eqn
        elif name.startswith(("cum", "reduce_window")):
            seen["wide_order"] += any(holds_a_block(v) for v in eqn.invars)
        if name == "sort":  # an exchange's buckets, at a table's width
            assert not any(holds_a_block(v) for v in eqn.invars), eqn
    # the walk met the member's gathers and searches, and the one
    # shard-wide prefix count is the compaction's
    assert seen["gather"] > 0 and seen["search"] > 0
    assert seen["wide_order"] == 1
    assert not any(
        eqn.primitive.name == "sort" and any(map(holds_a_block, eqn.invars))
        for eqn, _ in _eqns(program.jaxpr)
    )


def test_members_that_alone_would_seed_differently_share_one_plan(uba):
    from kolibrie_tpu.parallel.dist_query import DistQueryExecutor

    db, sh, _ = uba
    rows = execute_query_volcano(ADVISED_Q, db)
    texts, alone = [], []
    for _x, prof, course in rows[:: max(1, len(rows) // 60)]:
        text = TWO_KEYS.format(prof=prof, course=course)
        if text not in texts:
            texts.append(text)
            alone.append(
                DistQueryExecutor(
                    sh.mesh, db, text, store=sh.view, batched=True
                ).seed
            )
    first = texts[0]
    other = texts[[s != alone[0] for s in alone].index(True)]
    rest = [t for t in texts if t not in (first, other)][:4]
    fp = _fp(db, first)
    programs = sharded_compile_stats()["batched_programs"]
    before = _routes()
    got = sh.execute_batch(fp, [(0, first), (1, other)])
    assert [got[0], got[1]] == [
        execute_query_volcano(t, db) for t in (first, other)
    ]
    assert len(got[0]) > 0 and len(got[1]) > 0
    # one executable, the first member's seed, nothing declined
    assert _plan_of(sh, fp)[0] == alone[0]
    assert sharded_compile_stats()["batched_programs"] == programs + 1
    assert _grew(before, _routes())["fallbacks"] == 0
    # a later group, other constants: the pinned plan, no program
    got = sh.execute_batch(fp, list(enumerate(rest)))
    assert [got[i] for i in range(len(rest))] == [
        execute_query_volcano(t, db) for t in rest
    ]
    assert sharded_compile_stats()["batched_programs"] == programs + 1
    assert _grew(before, _routes())["fallbacks"] == 0


@pytest.mark.parametrize("hot", ["big", "mid"])
def test_a_first_sight_counts_the_hottest_key_and_a_write_past_it_retries_doubled(
    mesh8, hot
):
    # <small> comes first, and the template's capacities are those of its
    # hottest key all the same (PR 50: one more walk of the chain with the
    # seed's key freed, no headroom over it): <big> has 4,000 seed rows on
    # its subject's shard and four times as many answers, of <mid> only the
    # seed is large (1,500 rows on one shard, 10 of which join anything).
    # The overflow retry stands behind a store that moves: a write under
    # the delta threshold carries <big> past its ceiling
    db = SparqlDatabase()
    ex = "http://example.org/"
    lines = [f"<{ex}small> <{ex}p1> <{ex}y0> ."]
    for i in range(4000):
        lines.append(f"<{ex}big> <{ex}p1> <{ex}y{i}> .")
        for j in range(4):
            lines.append(f"<{ex}y{i}> <{ex}p2> <{ex}z{i}_{j}> .")
    for i in range(1500):
        lines.append(f"<{ex}mid> <{ex}p1> <{ex}m{i}> .")
    for i in range(10):
        lines.append(f"<{ex}m{i}> <{ex}p2> <{ex}w{i}> .")
    db.parse_ntriples("\n".join(lines))
    db.execution_mode = "host"
    sh = attach_sharded(db, mesh8)
    sh.refresh()
    text = "SELECT ?y ?z WHERE {{ <%s{}> <%sp1> ?y . ?y <%sp2> ?z . }}" % (
        ex, ex, ex
    )
    small, other = text.format("small"), text.format(hot)
    fp = _fp(db, small)
    assert sh.execute_batch(fp, [(0, small)])[0] == execute_query_volcano(
        small, db
    )
    seed, join_cap, bucket_cap, out_cap = _plan_of(sh, fp)
    # <big>'s 4,000 seed rows on one shard and its 16,000 answers over
    # eight; <small> alone would have read the floor, 1,024 each
    assert (join_cap, out_cap) == (4096, 4096) and bucket_cap >= 1024
    hits = sh.stats_counters["cap_hits"]
    programs = sharded_compile_stats()["batched_programs"]
    got = sh.execute_batch(fp, [(0, other)])[0]
    assert got == execute_query_volcano(other, db)
    assert len(got) == (16000 if hot == "big" else 10)
    # no retry, no program: the first sight had counted this key
    assert sh.stats_counters["cap_hits"] == hits
    assert sharded_compile_stats()["batched_programs"] == programs
    assert _plan_of(sh, fp) == (seed, join_cap, bucket_cap, out_cap)
    # a write under the delta threshold (the base and the pin stand) puts
    # 150 more rows under <big>: 4,150 seed rows overflow 4,096 slots
    d = db.dictionary
    more = np.array(
        [d.encode(f"{ex}late{i}") for i in range(150)], dtype=np.uint32
    )
    db.store.add_batch(
        np.full(150, d.encode(f"{ex}big"), dtype=np.uint32),
        np.full(150, d.encode(f"{ex}p1"), dtype=np.uint32),
        more,
    )
    big = text.format("big")
    got = sh.execute_batch(fp, [(0, big)])[0]
    assert got == execute_query_volcano(big, db) and len(got) == 16000
    # one retry, every capacity doubled (the answer's no wider than the join's)
    assert sh.stats_counters["cap_hits"] == hits + 1
    grown = _plan_of(sh, fp)
    assert grown == (seed, 2 * join_cap, 2 * bucket_cap, 2 * out_cap)
    # the capacities that held stay: the small constant builds no program
    programs = sharded_compile_stats()["batched_programs"]
    assert sh.execute_batch(fp, [(0, small)])[0] == execute_query_volcano(
        small, db
    )
    assert _plan_of(sh, fp) == grown
    assert sharded_compile_stats()["batched_programs"] == programs


@pytest.mark.parametrize("source", ["counted", "constants"])
def test_plan_and_occupancy_counters(uba, source, monkeypatch):
    from kolibrie_tpu.obs import analyze as obs_analyze
    from kolibrie_tpu.parallel.dist_query import DistQueryExecutor

    db, sh, texts = uba
    if source == "constants":
        # nothing can be counted: the most-constants seed, the heuristic
        monkeypatch.setattr(DistQueryExecutor, "_CALIBRATE_ROW_LIMIT", 0)
    with sh.lock:
        sh._plans.clear()
        db.__dict__.pop("_dist_plan_cache", None)
    items = list(enumerate(texts[10:13]))
    fp = _fp(db, items[0][1])
    names = {
        "plans": 'kolibrie_shard_plan_total{source="%s"}' % source,
        "all_plans": "kolibrie_shard_plan_total",
        "slots": "kolibrie_shard_cap_slots_total",
        "rows": "kolibrie_shard_join_rows_total",
        "seed_slots": "kolibrie_shard_seed_slots_total",
        "seed_rows": "kolibrie_shard_seed_rows_total",
    }
    before = {k: _metric(v) for k, v in names.items()}
    with obs_analyze.capture() as cap:
        got = sh.execute_batch(fp, items)
    grew = {k: _metric(v) - before[k] for k, v in names.items()}
    assert [got[i] for i, _ in items] == [
        execute_query_volcano(t, db) for _, t in items
    ]
    seed, join_cap, bucket_cap, out_cap = _plan_of(sh, fp)
    with sh.lock:
        exemplar = sh._build_group(fp, items[:1])["execs"][0]
    assert exemplar.seed == seed
    assert (seed == 0) == (source == "constants")
    # what comes to the host: the counted answer's capacity, and the whole
    # table's where nothing could be counted
    assert out_cap == (1024 if source == "counted" else join_cap)
    assert grew["plans"] == grew["all_plans"] == 1
    assert grew["slots"] == _slots(sh, exemplar, 3, join_cap, bucket_cap)
    recs = [r for r in cap.records if r["kind"] == "sharded"]
    assert [r["seed"] for r in recs] == [seed] * 3
    assert grew["rows"] == sum(
        count
        for r in recs
        for name, count in r["operators"].items()
        if name.startswith(("exchange", "matches"))
    ) > 0
    # the occupancy the two counters give: a few per cent at the floor,
    # and what the most-constants plan honestly fills its slots with
    assert 0 < grew["rows"] <= grew["slots"]
    # the compacted seed tables: ``join_cap`` slots a member and shard
    # beside the rows the seed scans counted (every student of the store
    # under the most-constants seed, 2-4 courses under the professor's)
    assert grew["seed_slots"] == 3 * sh.n * join_cap
    assert grew["seed_rows"] == sum(r["operators"]["seed"] for r in recs)
    assert 0 < grew["seed_rows"] <= grew["seed_slots"]
    assert (grew["seed_rows"] > 3000) == (source == "constants")
    with sh.lock:
        sh._plans.clear()  # the next test plans for itself


# ------------------------------------------------ EXPLAIN ANALYZE (ISSUE 14)


def test_batched_analyze_matches_oracle(sharded_db):
    # the shard-local stats vector rides the batched result transfer;
    # summed across the mesh it must equal the oracle's row counts, and
    # capturing it must not perturb results
    from kolibrie_tpu.obs import analyze as obs_analyze

    db, sh = sharded_db
    texts = _template_group(db, 4)
    oracle = [execute_query_volcano(t, db) for t in texts]
    with obs_analyze.capture() as cap:
        got = execute_queries_batched(db, texts)
    assert got == oracle
    recs = [r for r in cap.records if r["kind"] == "sharded"]
    assert len(recs) == len(texts)
    for rec in recs:
        assert rec["shards"] == 8
        assert rec["operators"]["final"] == len(oracle[rec["member"]])
        # per-shard breakdowns sum to the cross-mesh operator totals
        for i, name in enumerate(rec["stat_names"]):
            assert len(rec["per_shard"][i]) == 8
            assert sum(rec["per_shard"][i]) == rec["operators"][name]
        # the subject-keyed star join is co-partitioned: exchange elided,
        # its stats slot honestly reads zero
        assert rec["operators"]["exchange0"] == 0
        assert len(rec["caps"]) == 3  # join, bucket, out


def test_trace_id_reaches_shard_spans(sharded_server):
    # satellite: a client trace id must survive the HTTP front door into
    # the PR-8 shard_map dispatch's per-shard span children.  One request
    # is enough: the mesh serves a group of one like any other (before
    # PR 28 it took two members meeting in the batcher's 5 ms window).
    base = sharded_server
    db = _lubm_db(1)
    out = _post(
        base,
        "/store/load",
        {"rdf": db.to_ntriples(), "format": "ntriples", "mode": "host"},
    )
    sid = out["store_id"]
    tid = "trace-shard-http"
    _post(
        base,
        "/store/query",
        {"store_id": sid, "sparql": _template_group(db, 1)[0]},
        headers={"X-Kolibrie-Trace-Id": tid},
    )
    with urllib.request.urlopen(
        base + f"/debug/traces?trace_id={tid}", timeout=60
    ) as resp:
        spans = [json.loads(l) for l in resp.read().decode().splitlines() if l]
    assert spans and all(s["trace_id"] == tid for s in spans)
    names = {s["name"] for s in spans}
    assert "executor.sharded" in names, names
    assert "shard.dispatch" in names, names
    kids = [s for s in spans if s["name"] == "shard.partition"]
    assert len(kids) == 8
    ids = {s["span_id"] for s in spans}
    assert all(k["parent_id"] in ids for k in kids)


# ------------------------------------------------------------------ kolint


def test_shard_map_reachable_code_is_kl101_clean():
    # CI guard (ISSUE 8 satellite): the mesh serving path must stay free
    # of host syncs inside shard_map-reachable code — one .item() in the
    # batched body would serialize all eight shards on every dispatch
    from kolibrie_tpu.analysis import core

    pkg = Path(__file__).resolve().parent.parent / "kolibrie_tpu" / "parallel"
    res = core.run([str(pkg)], use_baseline=False, rules=["KL101"])
    assert res.findings == [], [
        f"{f.path}:{f.line} {f.message}" for f in res.findings
    ]
