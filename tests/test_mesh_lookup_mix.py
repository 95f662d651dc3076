"""The five-query lookup mix across four chips (ISSUE 50, ``lubm50.mesh4``):
the store hash-partitioned by subject and by object over a mesh of four
(virtual) devices, asked Q1, Q3, Q4, Q7 and Q8 as ``lookups_clients8`` sends
them.

- Every template is planned ``counted`` and served by the mesh, alone and in
  groups of 3 and 8, and a dispatch that holds three templates at once is
  three mesh groups: every answer equals the plain reference's as a multiset,
  ``kolibrie_shard_fallback_total`` grows by 0 and
  ``kolibrie_shard_queries_total`` by every member.
- The four shards' parts of a Q8 answer add up to the uncut answer, no row
  twice, and stand at the front of their ``out_cap`` slots: what the merge
  brings to the host in one transfer, and decodes by the program's counts.
- The four counters of what a merge decoded and an exchange carried equal
  counts taken by hand from the program's own per-operator block.
- A load of 6 chunks, each of which folds into the base, ends in ONE base
  re-partition, paid by the first read (span ``shard.partition_base`` in that
  read's trace); a read after each acknowledged chunk sees it.
- A failed attach is counted with its reason.

Data: LUBM(2, seed) of the cell's own generator and configuration
(``lubm-50-mesh4``): two universities, so that a Q8 group holds two texts.
"""

import os
import sys
import threading
from collections import Counter

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.harness import compare  # noqa: E402
from benchmark.harness import data as bench_files  # noqa: E402
from benchmark.harness.client import Client  # noqa: E402
from benchmark.reference.sparql_subset import Reference  # noqa: E402
from kolibrie_tpu.frontends import http_server  # noqa: E402
from kolibrie_tpu.obs import export as obs_export  # noqa: E402
from kolibrie_tpu.obs import spans as prog_spans  # noqa: E402
from kolibrie_tpu.obs.spans import trace_scope  # noqa: E402
from kolibrie_tpu.parallel import make_mesh  # noqa: E402
from kolibrie_tpu.parallel import sharded_serving as ss  # noqa: E402
from kolibrie_tpu.query import executor  # noqa: E402
from kolibrie_tpu.query.sparql_database import SparqlDatabase  # noqa: E402

SEED = 2**31 + 50
TEMPLATES = {"lubm_q1": "department", "lubm_q3": "department",
             "lubm_q4": "department", "lubm_q7": "department",
             "lubm_q8": "university"}
COUNTERS = {
    "queries": "kolibrie_shard_queries_total",
    "dispatches": "kolibrie_shard_dispatch_total",
    "fallbacks": "kolibrie_shard_fallback_total",
    "counted": 'kolibrie_shard_plan_total{source="counted"}',
    "plans": "kolibrie_shard_plan_total",
    "merged_rows": "kolibrie_shard_merged_rows_total",
    "merged_bytes": "kolibrie_shard_merged_bytes_total",
    "exchange_rows": "kolibrie_shard_exchange_rows_total",
    "exchange_slots": "kolibrie_shard_exchange_slots_total",
    "rebuilds": "kolibrie_shard_base_rebuilds_total",
    "partition_s": "kolibrie_shard_partition_seconds_total",
    "attach_errors": "kolibrie_shard_attach_errors_total",
}


def _counters():
    text = obs_export.render_prometheus()
    return {k: sum(float(line.rpartition(" ")[2]) for line in text.splitlines()
                   if line.startswith(name))
            for k, name in COUNTERS.items()}


def _grew(before):
    return {k: v - before[k] for k, v in _counters().items()}


@pytest.fixture(scope="module")
def mesh4():
    import jax

    if jax.device_count() < 4:
        pytest.skip("needs 4 devices (XLA_FLAGS came too late to force them)")
    return make_mesh(4)


@pytest.fixture(scope="module")
def generated():
    config = bench_files.read_json("configs", "lubm-50-mesh4.json")
    assert (config["chips"], config["universities"]) == (4, 50)
    return bench_files.load_module("generators", config["generator"]).generate(
        config, SEED, 2)


@pytest.fixture(scope="module")
def reference(generated):
    return Reference(generated["terms"], generated["s"], generated["p"],
                     generated["o"])


@pytest.fixture(scope="module")
def store(generated, mesh4):
    db = SparqlDatabase()
    ids = np.array([db.dictionary.encode(t[1:-1] if t.startswith("<") else t)
                    for t in generated["terms"]], dtype=np.uint32)
    db.store.add_batch(ids[generated["s"]], ids[generated["p"]],
                       ids[generated["o"]])
    db.execution_mode = "host"
    sh = ss.attach_sharded(db, mesh4)
    return db, sh


def _texts(template, generated, size, first=0):
    """``size`` instances of ``template``, constants in the domain's order
    from ``first`` (two universities: a Q8 group of three holds one twice)."""
    domain = generated["domains"][TEMPLATES[template]]
    return [bench_files.template_text(template).replace(
        "@%s@" % TEMPLATES[template], domain[(first + i) % len(domain)])
        for i in range(size)]


# ---- five templates, served by the mesh in every group

@pytest.mark.parametrize("size", [1, 3, 8])
@pytest.mark.parametrize("template", sorted(TEMPLATES))
def test_a_template_group_is_served_by_the_mesh_and_equals_the_reference(
        store, generated, reference, template, size):
    db, sh = store
    texts = _texts(template, generated, size, first=size)
    before = _counters()
    programs = ss.sharded_compile_stats()["batched_programs"]
    got = executor.execute_queries_batched(db, texts)
    grew = _grew(before)
    for text, rows in zip(texts, got):
        want = compare.multiset(reference.query(text))
        assert sum(want.values()) > 0
        assert compare.multiset(rows) == want
    assert grew["fallbacks"] == 0
    assert (grew["queries"], grew["dispatches"]) == (size, 1)
    # slot class 8 for every group of 1-8: a template's first sight builds
    # its one program (Q3 runs Q1's: the same shape), no later size another
    assert ss.sharded_compile_stats()["batched_programs"] - programs <= (size == 1)
    assert grew["plans"] == grew["counted"] <= 1
    fp = executor._plan_cache_entry(db, texts[0])[0]["fp"]
    with sh.lock:
        seed, join_cap, bucket_cap, out_cap = sh._pinned_plan(fp)
    assert out_cap <= join_cap and sh.stats()["plans"][fp] == [
        seed, join_cap, bucket_cap, out_cap]


def test_every_template_was_planned_from_a_host_count(store, generated):
    """After the cases above: five pinned plans, each ``counted`` (the
    counter above grew by each one and by no ``constants`` plan), and Q8's
    capacities are a university's: thousands of rows to the host, in slots a
    fraction of the join's."""
    db, sh = store
    plans = {}
    for template in TEMPLATES:
        text = _texts(template, generated, 1)[0]
        fp = executor._plan_cache_entry(db, text)[0]["fp"]
        with sh.lock:
            plans[template] = sh._pinned_plan(fp)
            ex = sh._build_group(fp, [(0, text)])["execs"][0]
        assert ex.plan_source == "pinned" and plans[template] is not None
    assert len({executor._plan_cache_entry(db, _texts(t, generated, 1)[0])[0]["fp"]
                for t in TEMPLATES}) == 5
    _seed, join_cap, _bucket_cap, out_cap = plans["lubm_q8"]
    assert 4096 <= out_cap < join_cap
    assert all(plans[t][3] == 1024 for t in TEMPLATES if t != "lubm_q8")


def test_a_dispatch_of_three_templates_is_three_mesh_groups(
        store, generated, reference):
    db, _sh = store
    texts = (_texts("lubm_q1", generated, 3) + _texts("lubm_q7", generated, 2)
             + _texts("lubm_q8", generated, 2) + _texts("lubm_q1", generated, 1, 5))
    order = [0, 3, 5, 1, 6, 4, 2, 7]  # as they might meet in the batcher
    texts = [texts[i] for i in order]
    before = _counters()
    groups0 = executor.dispatch_programs(db)
    got = executor.execute_queries_batched(db, texts)
    grew = _grew(before)
    for text, rows in zip(texts, got):
        assert compare.multiset(rows) == compare.multiset(reference.query(text))
    assert (grew["queries"], grew["dispatches"], grew["fallbacks"]) == (8, 3, 0)
    groups1 = executor.dispatch_programs(db)
    assert (groups1[0] - groups0[0], groups1[1] - groups0[1]) == (3, 0)


def test_stats_give_a_templates_dispatches_span_by_span(store, generated):
    """``/stats``' ``sharding`` block (the harness prints it in its ``mesh``
    line): per template its dispatches, members and the seconds of
    ``shard.build`` / ``.wait`` / ``.merge``, which the spans carry by the
    ``template`` attribute of ``shard.dispatch`` in whichever trace led."""
    db, sh = store
    text = _texts("lubm_q4", generated, 1)[0]
    fp = executor._plan_cache_entry(db, text)[0]["fp"]
    before = dict(sh.stats()["by_template"][fp])
    prog_spans.clear()
    with trace_scope("by-template"):
        sh.execute_batch(fp, [(0, text), (1, _texts("lubm_q4", generated, 1, 3)[0])])
    after = sh.stats()["by_template"][fp]
    assert (after["dispatches"] - before["dispatches"],
            after["members"] - before["members"]) == (1, 2)
    spans = {s["name"]: s for s in prog_spans.spans_snapshot()
             if s["trace_id"] == "by-template"}
    assert spans["shard.dispatch"]["attrs"]["template"] == fp
    for part in ("build", "wait", "merge"):
        took = (after[part + "_s"] - before[part + "_s"]) * 1000.0
        assert took == pytest.approx(spans["shard." + part]["dur_ms"], rel=0.5, abs=2.0)
    assert set(sh.stats()["plans"]) == set(sh.stats()["by_template"])


# ---- what comes to the host

@pytest.fixture(scope="module")
def q8_dispatch(store, generated):
    """One Q8 dispatch of two universities, taken apart by hand: the
    program's outputs as they stand on the device, and the counters around
    the whole ``execute_batch``."""
    import jax

    db, sh = store
    texts = _texts("lubm_q8", generated, 2)
    fp = executor._plan_cache_entry(db, texts[0])[0]["fp"]
    items = list(enumerate(texts))
    sh.execute_batch(fp, items)  # the template's first sight, if it is
    before = _counters()
    got = sh.execute_batch(fp, items)
    grew = _grew(before)
    with sh.lock:
        group = sh._build_group(fp, items)
        outs, stats = jax.device_get(sh._run_group(fp, group, None))
    return {"texts": texts, "got": got, "grew": grew, "group": group,
            "outs": outs, "stats": stats, "sh": sh, "db": db}


def test_the_shards_parts_of_a_q8_answer_add_up_to_the_uncut_answer(
        q8_dispatch, reference):
    d = q8_dispatch
    final = d["stats"][:2, :, -1]
    out_cap = d["group"]["caps"][2]
    assert d["outs"][0].shape == (8, 4, out_cap) and len(d["outs"]) == 3
    for r, text in enumerate(d["texts"]):
        want = reference.query(text)
        assert len(want) > 1000
        parts = [np.stack([o[r, s, : final[r, s]] for o in d["outs"]], axis=1)
                 for s in range(4)]
        assert all(len(p) > 0 for p in parts)  # every shard holds a part
        rows = np.concatenate(parts)
        assert len(rows) == len(want) == len({tuple(x) for x in rows.tolist()})
        # the rest of a shard's slots is empty: nothing to decode there
        assert not any(o[r, s, final[r, s]:].any()
                       for o in d["outs"] for s in range(4))
        assert compare.multiset(d["got"][r]) == compare.multiset(want)
    assert not d["stats"][2:].any() and not any(o[2:].any() for o in d["outs"])


@pytest.mark.parametrize("counter", ["merged_rows", "merged_bytes",
                                     "exchange_rows", "exchange_slots"])
def test_a_new_counter_equals_the_count_taken_by_hand(q8_dispatch, counter):
    from kolibrie_tpu.parallel.dist_query import exchanged_steps

    d = q8_dispatch
    ex = d["group"]["execs"][0]
    _join_cap, bucket_cap, _out_cap = d["group"]["caps"]
    routed = exchanged_steps(ex.premises, ex.seed, ex.steps, 4)
    assert 0 < sum(routed) < len(routed)  # Q8 exchanges, and elides one
    live = d["stats"][:2]
    # the block's layout: [seed, (exchange, matches, join) a step, final]
    by_hand = {
        "merged_rows": int(live[:, :, -1].sum()),
        "merged_bytes": sum(o.nbytes for o in d["outs"]) + d["stats"].nbytes,
        "exchange_rows": int(sum(live[:, :, 1 + 3 * k].sum()
                                 for k, r in enumerate(routed) if r)),
        "exchange_slots": 2 * sum(routed) * 4 * 4 * bucket_cap,
    }
    assert by_hand["merged_rows"] == sum(len(rows) for rows in d["got"].values())
    assert not any(live[:, :, 1 + 3 * k].any()
                   for k, r in enumerate(routed) if not r)
    assert d["grew"][counter] == by_hand[counter] > 0
    # a row is three terms of four bytes: the merge's occupancy
    if counter == "merged_bytes":
        assert 0 < by_hand["merged_rows"] * 3 * 4 <= by_hand["merged_bytes"]
    if counter == "exchange_slots":
        assert by_hand["exchange_rows"] <= by_hand["exchange_slots"]


# ---- the partition follows the load

@pytest.fixture()
def served(mesh4, monkeypatch):
    monkeypatch.setattr(http_server, "SHARDED_SERVING", True)
    monkeypatch.setattr(ss, "make_mesh", lambda: mesh4)
    httpd = http_server.make_server("127.0.0.1", 0, quiet=True, data_dir=None)
    server = threading.Thread(target=httpd.serve_forever, daemon=True)
    server.start()
    try:
        yield httpd, Client(httpd.server_address[1], 600_000)
    finally:
        httpd.shutdown()
        httpd.server_close()
        server.join(timeout=30)


def _six_chunks(generated):
    terms = np.array(generated["terms"], dtype=object)
    cuts = np.linspace(0, len(generated["s"]), 7).astype(int)
    return [
        "".join(terms[generated["s"][a:b]] + " " + terms[generated["p"][a:b]]
                + " " + terms[generated["o"][a:b]] + " .\n")
        for a, b in zip(cuts[:-1], cuts[1:])]


def test_a_load_of_six_chunks_partitions_the_base_once(
        served, generated, reference):
    httpd, client = served
    before = _counters()
    for chunk in _six_chunks(generated):
        body = client.post("/store/load", {
            "store_id": "six", "rdf": chunk, "format": "ntriples"})
    assert body["triples"] == len(generated["s"])
    db = httpd.RequestHandlerClass.state.stores["six"].db
    sh = ss.active_sharded(db)
    # attached by the first chunk, and nothing partitioned yet: every chunk
    # folded into the base, none touched the mirrors
    assert sh is not None and sh.view is None
    assert _grew(before)["rebuilds"] == 0 == sh.stats()["base_rebuilds"]
    text = _texts("lubm_q8", generated, 1)[0]
    prog_spans.clear()
    status, reply, _ms = client.query("six", text, "first-read")
    assert status == 200
    rows = compare.rows_of(reply)
    assert compare.multiset(rows) == compare.multiset(reference.query(text))
    grew = _grew(before)
    assert grew["rebuilds"] == 1 == sh.stats()["base_rebuilds"]
    assert grew["partition_s"] > 0 and grew["fallbacks"] == 0
    # the request that needed the mirrors paid for them, in its own trace
    (part,) = [s for s in prog_spans.spans_snapshot()
               if s["name"] == "shard.partition_base"]
    assert part["trace_id"] == "first-read"
    assert part["attrs"] == {"rows": len(generated["s"]),
                             "cap_subj": sh._base_cap_s,
                             "cap_obj": sh._base_cap_o}
    # a second read partitions nothing
    status, _reply, _ms = client.query("six", text, "second-read")
    assert status == 200 and _grew(before)["rebuilds"] == 1


def test_a_read_after_each_acknowledged_chunk_sees_it(served, generated):
    """The guarantee across the deferred partition: whichever chunk a triple
    came in, the first read after its acknowledgement answers with it."""
    httpd, client = served
    terms = np.array(generated["terms"], dtype=object)
    text = ("SELECT ?s ?o WHERE { ?s <http://swat.cse.lehigh.edu/onto/"
            "univ-bench.owl#subOrganizationOf> ?o }")
    pred = generated["terms"].index(
        "<http://swat.cse.lehigh.edu/onto/univ-bench.owl#subOrganizationOf>")
    cuts = np.linspace(0, len(generated["s"]), 7).astype(int)
    seen, before = [], _counters()
    for chunk, upto in zip(_six_chunks(generated), cuts[1:]):
        client.post("/store/load", {
            "store_id": "each", "rdf": chunk, "format": "ntriples"})
        status, reply, _ms = client.query("each", text, f"after-{upto}")
        assert status == 200
        here = generated["p"][:upto] == pred
        want = Counter(zip(terms[generated["s"][:upto][here]].tolist(),
                           terms[generated["o"][:upto][here]].tolist()))
        got = Counter((s, o) for s, o in compare.rows_of(reply))
        assert {(s.strip("<>"), o.strip("<>")): n for (s, o), n in want.items()} == {
            (s.strip("<>"), o.strip("<>")): n for (s, o), n in got.items()}
        seen.append(sum(got.values()))
    assert seen == sorted(seen) and seen[-1] > seen[0] > 0
    grew = _grew(before)
    # six reads, each after a chunk that folded into the base
    assert grew["rebuilds"] == 6 and grew["fallbacks"] == 0
    assert grew["queries"] == 6


def test_a_failed_attach_is_counted_with_its_reason(monkeypatch):
    from kolibrie_tpu.parallel import sharded_serving

    def broken(db):
        raise MemoryError("no room for the mirrors")

    monkeypatch.setattr(http_server, "SHARDED_SERVING", True)
    monkeypatch.setattr(sharded_serving, "attach_sharded", broken)
    before = _counters()
    db = SparqlDatabase()
    http_server._maybe_attach_sharded(db, refresh=False)  # never raises
    assert _grew(before)["attach_errors"] == 1
    assert 'kolibrie_shard_attach_errors_total{reason="MemoryError"}' in (
        obs_export.render_prometheus())
    assert ss.active_sharded(db) is None
