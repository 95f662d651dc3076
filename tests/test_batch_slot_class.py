"""One chip, several clients (ISSUE 32): a template group of 2 or more is
one dispatch in the slot class of its size (8, 16, ...: ``ops.slot_class``,
the mesh's rule), the number of live members a traced value, so

- every member's rows are its own solo ``execute()``'s and the plain
  reference's (``benchmark/reference/sparql_subset.py``), whatever the group;
- a template has one ``_run_plan_batch`` executable a capacity set and class:
  sizes 2-8 build one, 9-16 one more;
- the work follows the live members: a padded slot joins nothing, counts 0
  towards capacities and occupancy, triggers no retry and is not read back;
  an overflow in one live member re-runs the group at the doubled capacity
  with one more executable, not one a size;
- the delta tier's two-tier branch holds inside the loop;
- through the server, 8 free-running clients compile nothing after the ramp
  1, 2, 4, 8, and the span ``executor.batch`` and the three
  ``kolibrie_device_batch_*`` counters say what was grouped.

Data: LUBM(1, seed) of the cell's own generator and configuration
(``lubm-5-clients8``), query Q7 by department, as ``lubm5.batch8`` sends it.
"""

import json
import os
import sys
import threading
import urllib.request

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.harness import data as bench_files  # noqa: E402
from benchmark.reference.sparql_subset import Reference  # noqa: E402
from kolibrie_tpu.core.triple import Triple  # noqa: E402
from kolibrie_tpu.obs import export as obs_export  # noqa: E402
from kolibrie_tpu.obs import spans as prog_spans  # noqa: E402
from kolibrie_tpu.ops import slot_class  # noqa: E402
from kolibrie_tpu.optimizer import device_engine as de  # noqa: E402
from kolibrie_tpu.query import executor  # noqa: E402
from kolibrie_tpu.query.sparql_database import SparqlDatabase  # noqa: E402

SEED = 2**31 + 32
Q7 = bench_files.template_text("lubm_q7")


def _metric(prefix, text=None):
    if text is None:
        text = obs_export.render_prometheus()
    return sum(float(line.rpartition(" ")[2]) for line in text.splitlines()
               if line.startswith(prefix))


def _batch_counters(text=None):
    return {k: _metric(f"kolibrie_device_batch_{k}_total", text)
            for k in ("dispatch", "members", "member_slots")}


def _programs():
    return de.device_compile_stats()["run_plan_batch"]


def _multiset(rows):
    return sorted(map(tuple, rows))


def _id_rows(table):
    names = sorted(table)
    return sorted(zip(*(table[v].tolist() for v in names)))


@pytest.fixture(scope="module")
def generated():
    config = bench_files.read_json("configs", "lubm-5-clients8.json")
    assert config["chips"] == 1 and config["universities"] == 5
    return bench_files.load_module("generators", config["generator"]).generate(
        config, SEED, 1)


def _db_of(data):
    db = SparqlDatabase()
    ids = np.array([db.dictionary.encode(t[1:-1] if t.startswith("<") else t)
                    for t in data["terms"]], dtype=np.uint32)
    db.store.add_batch(ids[data["s"]], ids[data["p"]], ids[data["o"]])
    db.execution_mode = "device"
    return db, ids


@pytest.fixture(scope="module")
def lubm(generated):
    """The store, the reference over the same triples, the Q7 texts (one a
    department), and the programs and size classes this module has met."""
    db, _ids = _db_of(generated)
    ref = Reference(generated["terms"], generated["s"], generated["p"],
                    generated["o"])
    texts = [Q7.replace("@department@", d)
             for d in generated["domains"]["department"]]
    assert len(texts) >= 15
    return {"db": db, "ref": ref, "texts": texts, "programs0": _programs(),
            "classes": set()}


def _lowered(db, text):
    """``(select query, lowered plan)`` of one member, as
    ``execute_queries_batched`` lowers it."""
    from kolibrie_tpu.optimizer.engine import resolve_pattern
    from kolibrie_tpu.optimizer.planner import Streamertail, build_logical_plan

    db.register_prefixes_from_query(text)
    ent, _slot = executor._plan_cache_entry(db, text)
    q, w = executor._batchable_select(db, ent["cq"])
    resolved = [resolve_pattern(db, p) for p in w.patterns]
    logical = build_logical_plan(resolved, list(w.filters), [], None)
    plan = Streamertail(db.get_or_build_stats()).find_best_plan(logical)
    return q, de.lower_plan(db, plan)


# ------------------------------------------------- (a) every size, one program


@pytest.mark.parametrize("size", list(range(2, 17)))
def test_a_group_of_any_size_answers_as_its_members_alone(lubm, size):
    db, ref = lubm["db"], lubm["ref"]
    # another stretch of the departments a size, so a size is not a rerun
    texts = [lubm["texts"][(size + k) % len(lubm["texts"])] for k in range(size)]
    assert len(set(texts)) == size
    members = [_lowered(db, t) for t in texts]
    before = _batch_counters()
    tables = de.execute_plan_batch([low for _, low in members])
    grew = {k: v - before[k] for k, v in _batch_counters().items()}
    assert grew == {"dispatch": 1, "members": size,
                    "member_slots": 8 if size <= 8 else 16}
    nonempty = 0
    for text, (q, low), table in zip(texts, members, tables):
        solo = _lowered(db, text)[1].execute()
        assert _id_rows(table) == _id_rows(solo)
        rows = executor._finish_select_table(db, q, table)
        assert _multiset(rows) == _multiset(ref.query(text))
        nonempty += bool(rows)
    assert nonempty == size  # Q7 has rows in every department
    # one executable a class, whatever the sizes met so far and their order
    lubm["classes"].add(slot_class(size))
    assert _programs() - lubm["programs0"] == len(lubm["classes"])


def test_sizes_2_to_8_share_a_program_and_9_to_16_one_more(lubm):
    db = lubm["db"]
    for size in (2, 8, 9, 16):  # both classes, wherever this test runs
        de.execute_plan_batch(
            [_lowered(db, t)[1] for t in lubm["texts"][:size]])
    lubm["classes"] |= {8, 16}
    programs = _programs()
    assert programs - lubm["programs0"] == 2
    for size in (3, 5, 7, 11, 13):
        de.execute_plan_batch(
            [_lowered(db, t)[1] for t in lubm["texts"][:size]])
    assert _programs() == programs
    assert [slot_class(n) for n in (1, 2, 8, 9, 16, 17)] == [8, 8, 8, 16, 16, 32]


def test_the_executor_serves_a_group_under_its_own_span(lubm):
    db, ref = lubm["db"], lubm["ref"]
    texts = lubm["texts"][:5]
    prog_spans.clear()
    got = executor.execute_queries_batched(db, texts)
    for text, rows in zip(texts, got):
        assert _multiset(rows) == _multiset(ref.query(text))
    spans = prog_spans.spans_snapshot()
    (batch,) = [s for s in spans if s["name"] == "executor.batch"]
    assert batch["attrs"]["batch"] == 5 and batch["attrs"]["slots"] == 8
    # the dispatch and the readback lie inside it
    inside = {s["name"] for s in spans
              if s["start_s"] >= batch["start_s"]
              and s["start_s"] + s["dur_ms"] / 1e3
              <= batch["start_s"] + batch["dur_ms"] / 1e3 + 1e-6}
    assert {"device.dispatch", "device.build", "device.enqueue", "device.wait",
            "device.counts", "device.collect"} <= inside
    # a group of one is not a group: it goes solo, as before
    before = _batch_counters()
    assert _multiset(executor.execute_queries_batched(db, texts[:1])[0]) == (
        _multiset(ref.query(texts[0])))
    assert _batch_counters() == before


# ----------------------------------------- (b) the work follows the live members


def _program_outputs(lows, slots):
    """The batch program's outputs for ``lows`` in a class of ``slots``."""
    import jax

    built = [lp.build() for lp in lows]
    spec, (orders, _sc, tiers, masks, values, numf, quoted, _pp) = built[0]
    assert all(s == spec for s, _ in built)

    def rows(of, dtype):
        live = np.asarray([of(lp) for lp in lows], dtype=dtype)
        mat = np.zeros((slots, *live.shape[1:]), dtype=dtype)
        mat[: len(lows)] = live
        return mat

    with jax.enable_x64(True):
        return de._run_plan_batch(
            spec, False, orders, rows(lambda lp: lp._scan_ranges_np, np.int32),
            np.int32(len(lows)), tiers, masks, values, numf, quoted,
            (rows(lambda lp: lp.u_params or [0], np.uint32),
             rows(lambda lp: lp.f_params or [0.0], np.float64)))


def test_a_padded_slot_joins_nothing_and_is_not_read_back(lubm):
    db = lubm["db"]
    texts = lubm["texts"][:3]
    lows = [_lowered(db, t)[1] for t in texts]
    blocks, counts, _stats = _program_outputs(lows, 8)
    assert len(blocks) == 8
    for b, block in enumerate(blocks):
        block = np.asarray(block)
        assert block.shape[0] == len(lows[0].out_vars) + 1
        assert bool(block[-1].any()) == (b < 3)
        if b >= 3:
            assert not block.any()
    for c in counts:
        c = np.asarray(c)
        assert c.shape == (8,) and (c[:3] > 0).all() and (c[3:] == 0).all()
    # the host side: occupancy counts the live members' slots, the readback
    # holds their blocks and no other, and nothing re-runs
    slots0 = _metric('kolibrie_device_cap_slots_total{engine="device"}')
    retries0 = _metric('kolibrie_cap_retries_total{engine="device"}')
    fetches0 = de.fetch_counters().get("batch.rows", 0)
    prog_spans.clear()
    lows = [_lowered(db, t)[1] for t in texts]
    live_blocks, _ = de._converge_plan_batch(lows)
    assert len(live_blocks) == 3
    tables = de.execute_plan_batch([_lowered(db, t)[1] for t in texts])
    assert [len(next(iter(t.values()))) > 0 for t in tables] == [True] * 3
    caps = sum(lows[0]._join_caps)
    assert _metric('kolibrie_device_cap_slots_total{engine="device"}') - slots0 == (
        2 * 3 * caps)
    assert _metric('kolibrie_cap_retries_total{engine="device"}') == retries0
    assert de.fetch_counters()["batch.rows"] - fetches0 == 1
    spans = prog_spans.spans_snapshot()
    assert [s["attrs"]["attempt"] for s in spans if s["name"] == "device.wait"] == [0, 0]
    (collect,) = [s for s in spans if s["name"] == "device.collect"]
    assert collect["attrs"]["members"] == 3


def _join_search_keys():
    from kolibrie_tpu.query.template import _JOIN_SEARCH_KEYS

    return {w: _JOIN_SEARCH_KEYS.labels(w).value for w in ("slots", "searched")}


def test_three_members_through_the_blocked_prepass_answer_as_the_twin(
        lubm, monkeypatch):
    """ISSUE 39: under the Pallas join (interpreted here) a member's merge
    joins search their live left keys in blocks under a traced trip count,
    inside the live-member loop's traced trip count; every member's rows
    are the numpy twin's and the reference's, and the dispatch counts each
    member's prepass joins: their left widths, and the blocks their rows
    reach (one block each: Q7's lefts hold 2-60 rows)."""
    from kolibrie_tpu.ops.pallas_kernels import _SEARCH_BLOCK as B
    from kolibrie_tpu.ops.pallas_kernels import pallas_enabled

    monkeypatch.setenv("KOLIBRIE_PALLAS", "force")
    assert pallas_enabled()
    db, ref = lubm["db"], lubm["ref"]
    texts = lubm["texts"][4:7]
    members = [_lowered(db, t) for t in texts]
    before = _join_search_keys()
    tables = de.execute_plan_batch([low for _, low in members])
    grew = {w: v - before[w] for w, v in _join_search_keys().items()}
    low0 = members[0][1]
    joins = list(de._spec_nodes(low0.root, de.JoinSpec))
    assert len(joins) == 3 and all(j.rsorted for j in joins)
    widths = [low0._node_cap(j.left, low0._scan_caps, low0._join_caps)
              for j in joins]
    # the scan of a department's professors holds a few rows: one block; the
    # two join outputs are as wide as a block or narrower
    assert widths[0] >= B >= widths[1] == widths[2]
    assert grew == {"slots": 3 * sum(widths),
                    "searched": 3 * (B + widths[1] + widths[2])}
    for text, (q, low), table in zip(texts, members, tables):
        twin = _lowered(db, text)[1].host_execute()[0]
        assert _id_rows(table) == _id_rows(twin)
        rows = executor._finish_select_table(db, q, table)
        assert rows and _multiset(rows) == _multiset(ref.query(text))


EX = "http://example.org/"


def _hub_db(spokes=3000):
    """Nodes 1-6 have two ``p1`` edges each, node 0 has ``spokes``; every
    target has one ``p2`` edge: a template whose join counts 2 or 3,000 rows
    by the constant."""
    lines = []
    for k in range(spokes):
        lines.append(f"<{EX}n0> <{EX}p1> <{EX}m{k}> .")
        lines.append(f"<{EX}m{k}> <{EX}p2> <{EX}t{k}> .")
    for a in range(1, 7):
        for j in range(2):
            lines.append(f"<{EX}n{a}> <{EX}p1> <{EX}m{10 * a + j}> .")
    db = SparqlDatabase()
    db.parse_ntriples("\n".join(lines))
    db.execution_mode = "device"
    return db


def test_a_dispatch_counts_its_prepass_joins_slots_and_searched_keys(monkeypatch):
    """ISSUE 39: ``kolibrie_join_search_keys_total``: a join whose left scan
    holds 2 rows in 8,192 slots searches one block of its keys; the hub's
    6,000 rows take the blocks that hold them, in the overflowing dispatch
    and in its re-run; with the XLA join (no prepass) a dispatch adds nothing."""
    from kolibrie_tpu.ops.pallas_kernels import _SEARCH_BLOCK as B

    assert 8192 % B == 0  # the left scan is 8,192 slots wide
    db = _hub_db(6000)
    # the calibration's hot-key passes left out (ISSUE 40: they would size
    # the template for the hub at its first sight), so that the hub overflows
    monkeypatch.setattr(de.LoweredPlan, "_keyed_scans", lambda self: [])

    def grown(k):
        low = _lowered(db, (
            f"PREFIX ex: <{EX}>\n"
            f"SELECT ?b ?c WHERE {{ ex:n{k} ex:p1 ?b . ?b ex:p2 ?c }}"))[1]
        before = _join_search_keys()
        table = low.execute()
        assert _id_rows(table) == _id_rows(low.host_execute()[0])
        return len(table["b"]), {
            w: v - before[w] for w, v in _join_search_keys().items()}

    assert grown(1) == (2, {"slots": 0, "searched": 0})
    monkeypatch.setenv("KOLIBRIE_PALLAS", "force")
    assert grown(1) == (2, {"slots": 8192, "searched": B})
    assert grown(0) == (6000, {"slots": 2 * 8192,
                               "searched": 2 * -(-6000 // B) * B})


def test_an_overflow_in_one_member_reruns_the_group_once_for_every_size(monkeypatch):
    db = _hub_db()
    # the first sight is the small variants' alone, as where a fan-out that
    # no scan's hottest key shows exceeds them (ISSUE 40's hot-key passes
    # would size the template for the hub at once)
    monkeypatch.setattr(de.LoweredPlan, "_keyed_scans", lambda self: [])

    def text(k):
        return (f"PREFIX ex: <{EX}>\n"
                f"SELECT ?b ?c WHERE {{ ex:n{k} ex:p1 ?b . ?b ex:p2 ?c }}")

    def group(ks):
        lows = [_lowered(db, text(k))[1] for k in ks]
        tables = de.execute_plan_batch(lows)
        for k, table in zip(ks, tables):
            single = _lowered(db, text(k))[1]
            assert _id_rows(table) == _id_rows(single.host_execute()[0])
            assert len(table["b"]) == (3000 if k == 0 else 2)
        return lows[0]

    # a request in flight with its template, as the executor sets it:
    # retries are counted under the template's fingerprint
    with prog_spans.trace_scope("test-batch-hub"):
        prog_spans.set_baggage("template", "test-batch-hub")
        programs0 = _programs()
        retries0 = _metric('kolibrie_cap_retries_total{engine="device"}')
        first = group([1, 2])  # the template's first sight: the small variants
        assert max(first._join_caps) == 1024 and _programs() - programs0 == 1
        # the hub among them: one live member overflows, the group runs again
        # at the doubled capacity, every member's rows are right
        again = group([3, 0, 4])
        assert max(again._join_caps) == 8192
        assert _programs() - programs0 == 2
        assert _metric('kolibrie_cap_retries_total{engine="device"}') - retries0 == 1
        # one more executable, not one a size: other sizes run the two there are
        group([0, 1, 2, 3, 4])
        group([5, 6])
        group([0, 1, 2, 3, 4, 5, 6])
        assert _programs() - programs0 == 2
        assert _metric('kolibrie_cap_retries_total{engine="device"}') - retries0 == 1


# ------------------------------------------------ (c) a live delta tier inside


def test_a_group_over_a_live_delta_tier_and_a_tombstone_equals_the_reference(
        generated):
    s, p, o = (np.asarray(generated[c]) for c in "spo")
    terms = list(generated["terms"])
    at = {t: i for i, t in enumerate(terms)}
    takes = at[f"<{Q7.split('ub: <')[1].split('>')[0]}takesCourse>"]
    depts = generated["domains"]["department"][:4]
    texts = [Q7.replace("@department@", d) for d in depts]
    before = Reference(terms, s, p, o)
    # a course of the first department's professor loses one student (a
    # tombstone on a base row) and gains another department's (a delta row)
    row = before.query(texts[0])[0]
    student, course = at[f"<{row[0]}>"], at[f"<{row[1]}>"]
    victim = int(np.flatnonzero((s == student) & (p == takes) & (o == course))[0])
    newcomer = at[f"<{before.query(texts[1])[0][0]}>"]
    assert not ((s == newcomer) & (p == takes) & (o == course)).any()
    db, ids = _db_of(generated)
    db.store.delta_threshold = 1 << 20
    _ = db.store.order("spo")  # the base is built before the write
    bv = db.store.base_version
    db.delete_triple(Triple(int(ids[student]), int(ids[takes]), int(ids[course])))
    db.add_triple(Triple(int(ids[newcomer]), int(ids[takes]), int(ids[course])))
    keep = np.ones(len(s), bool)
    keep[victim] = False
    ref = Reference(terms, np.r_[s[keep], newcomer], np.r_[p[keep], takes],
                    np.r_[o[keep], course])
    assert _multiset(ref.query(texts[0])) != _multiset(before.query(texts[0]))
    members = [_lowered(db, t) for t in texts]
    tables = de.execute_plan_batch([low for _, low in members])
    assert db.store.base_version == bv
    assert len(db.store.delta_order("spo")) == 1
    assert len(db.store.delta_del_positions("spo")) == 1
    assert members[0][1]._tiers_np.min() > 0  # every order took the two-tier branch
    for text, (q, low), table in zip(texts, members, tables):
        rows = executor._finish_select_table(db, q, table)
        assert _multiset(rows) == _multiset(ref.query(text))
        assert _id_rows(table) == _id_rows(_lowered(db, text)[1].execute())


# ------------------------------------------- (d) eight free clients, the server


def _post(base, path, payload, trace_id=""):
    headers = {"Content-Type": "application/json"}
    if trace_id:
        headers["X-Kolibrie-Trace-Id"] = trace_id
    req = urllib.request.Request(base + path, data=json.dumps(payload).encode(),
                                 headers=headers, method="POST")
    with urllib.request.urlopen(req, timeout=600) as resp:
        return json.loads(resp.read())


def _get(base, path):
    with urllib.request.urlopen(base + path, timeout=60) as resp:
        return resp.read().decode()


def test_eight_free_clients_compile_nothing_after_the_ramp(generated, lubm):
    from kolibrie_tpu.frontends import http_server

    ref = lubm["ref"]
    depts = generated["domains"]["department"]
    httpd = http_server.make_server("127.0.0.1", 0, quiet=True, data_dir=None)
    server = threading.Thread(target=httpd.serve_forever, daemon=True)
    server.start()
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    try:
        terms, (s, p, o) = generated["terms"], (generated[c] for c in "spo")
        nt = "\n".join(f"{terms[a]} {terms[b]} {terms[c]} ."
                       for a, b, c in zip(s.tolist(), p.tolist(), o.tolist()))
        sid = _post(base, "/store/load", {"rdf": nt, "format": "ntriples",
                                          "mode": "device"})["store_id"]
        answers, lock = [], threading.Lock()

        def client(c, n_clients, cycles, tag):
            # client c walks the departments at place c modulo the clients
            own = depts[c::n_clients]
            for k in range(cycles):
                text = Q7.replace("@department@", own[k % len(own)])
                got = _post(base, "/store/query",
                            {"store_id": sid, "sparql": text,
                             "deadline_ms": 600_000}, f"{tag}-{k}-{c}")
                with lock:
                    answers.append((text, got["data"]))

        def run(n_clients, cycles, tag):
            threads = [threading.Thread(target=client,
                                        args=(c, n_clients, cycles, tag))
                       for c in range(n_clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()

        for n in (1, 2, 4, 8):  # the cell's warm-up ramp, a cycle each
            run(n, 1, f"ramp{n}")
        m0 = _get(base, "/metrics")
        compiled = dict(de.device_compile_stats())
        prog_spans.clear()
        n_ramp = len(answers)
        run(8, 2, "window")  # each client for itself: no two start a cycle together
        m1 = _get(base, "/metrics")
    finally:
        httpd.shutdown()
        httpd.server_close()
        server.join(timeout=30)
    assert len(answers) == 15 + 16
    for text, rows in answers:
        assert _multiset(rows) == _multiset(ref.query(text))
    # whatever splits the clients fell into, no program was built for them
    assert dict(de.device_compile_stats()) == compiled
    grew = {k: v - _batch_counters(m0)[k] for k, v in _batch_counters(m1).items()}
    window = len(answers) - n_ramp
    solo = (_metric('kolibrie_query_seconds_count{path="device"', m1)
            - _metric('kolibrie_query_seconds_count{path="device"', m0))
    batched = (_metric("kolibrie_query_batched_total", m1)
               - _metric("kolibrie_query_batched_total", m0))
    assert solo + batched == window and grew["members"] == batched
    assert grew["members"] <= grew["member_slots"] == 8 * grew["dispatch"]
    spans = prog_spans.spans_snapshot()
    groups = [s for s in spans if s["name"] == "executor.batch"]
    assert len(groups) == grew["dispatch"] >= 1
    assert sum(s["attrs"]["batch"] for s in groups) == grew["members"]
    # in the leader's trace, which is one of the window's requests
    assert all(s["trace_id"].startswith("window-") for s in groups)
    dispatches = {s["trace_id"] for s in spans if s["name"] == "batcher.dispatch"}
    assert {s["trace_id"] for s in groups} <= dispatches
