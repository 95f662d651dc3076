"""Eight workers running a mix (ISSUE 47, ``lubm50.mix8``): several templates
meet in ``TemplateBatcher``, and one dispatch, under one hold of
``dispatch_lock``, runs a group program a template that has two or more
members and then its singletons, one after another.

- A dispatch built by hand (two templates with two members each, one
  singleton of a third, one text twice): every request's rows are its own
  text's solo answer and the plain reference's, and the dispatch's
  composition is counted: ``kolibrie_batcher_dispatch_templates_total``,
  ``kolibrie_batcher_dispatch_programs_total{kind}``, the attributes of
  ``batcher.dispatch`` and the span ``executor.solo_tail``.
- Through the real server, the harness's own eight clients
  (``benchmark/harness/loadgen.py``) send ``lookups_clients8``: every answer
  of the ramp and of the window equals the reference's.
- After the ramp 1, 2, 4, 8 no later cycle builds a program.  The program has
  no rule of its own for that and needs none: the clients of a warm-up cycle
  start together, the first to arrive takes the idle lock alone and the
  others queue behind it and ride the hand-off as ONE group of their step's
  template, so every template that can form a group has formed one by the
  first cycle at 4 clients, and a group of any size runs the one executable
  of its slot class.

Data: LUBM(1, seed) of the cell's own generator and configuration
(``lubm-50-clients8``).  One university, so every client draws the same
university for Q8: those requests fold into one execution and Q8 never forms
a group here (on the chip, at 50 universities, no two clients share one).
"""

import os
import sys
import threading
from collections import Counter

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.harness import compare, loadgen  # noqa: E402
from benchmark.harness import data as bench_files  # noqa: E402
from benchmark.harness.client import Client  # noqa: E402
from benchmark.harness.traffic import Traffic  # noqa: E402
from benchmark.reference.sparql_subset import Reference  # noqa: E402
from kolibrie_tpu.frontends import http_server  # noqa: E402
from kolibrie_tpu.obs import export as obs_export  # noqa: E402
from kolibrie_tpu.obs import spans as prog_spans  # noqa: E402
from kolibrie_tpu.obs.spans import trace_scope  # noqa: E402
from kolibrie_tpu.optimizer import device_engine as de  # noqa: E402
from kolibrie_tpu.query import executor  # noqa: E402
from kolibrie_tpu.query.sparql_database import SparqlDatabase  # noqa: E402

SEED = 2**31 + 47
COUNTERS = {
    "dispatches": "kolibrie_batcher_dispatches_total",
    "templates": "kolibrie_batcher_dispatch_templates_total",
    "group": 'kolibrie_batcher_dispatch_programs_total{kind="group"}',
    "solo": 'kolibrie_batcher_dispatch_programs_total{kind="solo"}',
}


def _metric(prefix, text=None):
    if text is None:
        text = obs_export.render_prometheus()
    return sum(float(line.rpartition(" ")[2]) for line in text.splitlines()
               if line.startswith(prefix))


def _counters(text=None):
    return {k: _metric(name, text) for k, name in COUNTERS.items()}


@pytest.fixture(scope="module")
def generated():
    config = bench_files.read_json("configs", "lubm-50-clients8.json")
    assert (config["chips"], config["universities"]) == (1, 50)
    return bench_files.load_module("generators", config["generator"]).generate(
        config, SEED, 1)


@pytest.fixture(scope="module")
def reference(generated):
    return Reference(generated["terms"], generated["s"], generated["p"],
                     generated["o"])


# ---- a dispatch built by hand

def _text(template, generated, k):
    return bench_files.template_text(template).replace(
        "@department@", generated["domains"]["department"][k])


@pytest.fixture(scope="module")
def dispatch(generated):
    """One dispatch of six requests, run as the leader of a hand-off runs it:
    Q1 for two departments, Q7 for two, Q4 for one, and the first Q1 again."""
    db = SparqlDatabase()
    ids = np.array([db.dictionary.encode(t[1:-1] if t.startswith("<") else t)
                    for t in generated["terms"]], dtype=np.uint32)
    db.store.add_batch(ids[generated["s"]], ids[generated["p"]],
                       ids[generated["o"]])
    db.execution_mode = "device"
    texts = [_text("lubm_q1", generated, 0), _text("lubm_q7", generated, 1),
             _text("lubm_q4", generated, 2), _text("lubm_q1", generated, 3),
             _text("lubm_q7", generated, 4), _text("lubm_q1", generated, 0)]
    batcher = http_server.TemplateBatcher(db)
    batch = [http_server._BatchRequest(t, trace_id=f"hand-{i}")
             for i, t in enumerate(texts)]
    before = _counters()
    prog_spans.clear()
    with trace_scope("hand-leader"), batcher.dispatch_lock:
        batcher._run_batch(batch)
    spans = prog_spans.spans_snapshot()
    grew = {k: v - before[k] for k, v in _counters().items()}
    # each text alone, afterwards, on the same store
    solo = [executor.execute_query_volcano(t, db) for t in texts]
    return {"db": db, "texts": texts, "batch": batch, "grew": grew,
            "spans": spans, "solo": solo, "batcher": batcher}


@pytest.mark.parametrize("member", range(6))
def test_a_request_of_a_mixed_dispatch_gets_its_own_texts_answer(
        dispatch, reference, member):
    req = dispatch["batch"][member]
    assert req.done.is_set() and req.error is None
    want = compare.multiset(reference.query(req.text))
    assert sum(want.values()) > 0
    assert compare.multiset(req.result) == want
    assert compare.multiset(dispatch["solo"][member]) == want
    if member == 5:  # the text sent twice: one execution, one answer, shared
        assert req.result is dispatch["batch"][0].result


@pytest.mark.parametrize("counter, grew", [
    ("dispatches", 1), ("templates", 3), ("group", 2), ("solo", 1)])
def test_a_mixed_dispatch_counts_its_templates_and_its_programs(
        dispatch, counter, grew):
    assert dispatch["grew"][counter] == grew
    # the executor's own per-store counts, which the batcher read them off
    assert executor.dispatch_programs(dispatch["db"])[0] == 2
    assert dispatch["batcher"].distinct_per_dispatch == [3]


def test_a_mixed_dispatch_is_groups_first_and_singletons_after_in_one_trace(
        dispatch):
    spans = [s for s in dispatch["spans"] if s["trace_id"] == "hand-leader"]
    assert len(spans) == len(dispatch["spans"])  # all in the leader's trace
    (whole,) = [s for s in spans if s["name"] == "batcher.dispatch"]
    assert whole["attrs"] == {"batch": 6, "uniq": 5, "templates": 3,
                              "programs": (2, 1)}
    groups = [s for s in spans if s["name"] == "executor.batch"]
    (tail,) = [s for s in spans if s["name"] == "executor.solo_tail"]
    assert [g["attrs"]["batch"] for g in groups] == [2, 2]
    assert tail["attrs"] == {"members": 1, "grouped": 4}
    assert all(g["parent_id"] == whole["span_id"] for g in groups + [tail])
    # the groups ran before the tail, and the singleton ran inside it
    assert max(g["start_s"] for g in groups) <= tail["start_s"]
    solos = [s for s in spans if s["name"] == "query.execute"]
    assert [s["parent_id"] for s in solos] == [tail["span_id"]]


def test_a_lone_request_opens_no_solo_tail(dispatch):
    db, batcher = dispatch["db"], dispatch["batcher"]
    before = _counters()
    prog_spans.clear()
    req = http_server._BatchRequest(dispatch["texts"][2], trace_id="hand-lone")
    with trace_scope("hand-lone"), batcher.dispatch_lock:
        batcher._run_batch([req])
    names = [s["name"] for s in prog_spans.spans_snapshot()]
    assert "executor.solo_tail" not in names and "query.execute" in names
    grew = {k: v - before[k] for k, v in _counters().items()}
    assert grew == {"dispatches": 1, "templates": 1, "group": 0, "solo": 1}
    assert compare.multiset(req.result) == compare.multiset(dispatch["solo"][2])
    assert executor.dispatch_programs(db) == (2, 2)  # the store's own counts


# ---- through the server: the harness's own clients

@pytest.fixture(scope="module")
def served(generated):
    """The ramp's eight warm-up cycles and a short window of eight free
    clients against the real server, as ``runner.run_cell`` drives them."""
    traffic = Traffic("lookups_clients8", generated["domains"], SEED)
    httpd = http_server.make_server("127.0.0.1", 0, quiet=True, data_dir=None)
    server = threading.Thread(target=httpd.serve_forever, daemon=True)
    server.start()
    try:
        port = httpd.server_address[1]
        clients = [Client(port, traffic.deadline_ms) for _ in range(traffic.clients)]
        for text in bench_files.ntriples_chunks(generated):
            body = clients[0].post("/store/load", {
                "store_id": loadgen.STORE_ID, "rdf": text, "format": "ntriples"})
        ramp = []
        for k, n in enumerate(traffic.warmup_counts()):
            ramp.extend(loadgen.send_cycle(traffic, clients, k, "warmup", n)[2])
        compiled = dict(de.device_compile_stats())
        m0 = obs_export.render_prometheus()
        window = [r for _, _, records in loadgen.free_run(
            traffic, clients, "window", 2.0) for r in records]
        m1 = obs_export.render_prometheus()
        statuses = sum((cl.statuses for cl in clients), Counter())
    finally:
        httpd.shutdown()
        httpd.server_close()
        server.join(timeout=30)
    return {"traffic": traffic, "acknowledged": body["triples"], "ramp": ramp,
            "window": window, "compiled": compiled, "m0": m0, "m1": m1,
            "compiled_after": dict(de.device_compile_stats()),
            "statuses": dict(statuses)}


def test_eight_clients_of_the_mix_get_the_references_answers(
        served, generated, reference):
    traffic = served["traffic"]
    assert (traffic.clients, traffic.warmup_ramp, traffic.warmup_cycles) == (
        8, [1, 2, 4, 8], 2)
    assert served["acknowledged"] == len(generated["s"])
    assert len(served["ramp"]) == 2 * (1 + 2 + 4 + 8) * 5
    # every client finished at least its first cycle of five
    assert len(served["window"]) >= 8 * 5
    assert {r["client"] for r in served["window"]} == set(range(8))
    for sent in (served["ramp"], served["window"]):
        bad, want = compare.wrong_answers(sent, reference.query)
        assert bad == []
        assert {r["template"] for r in sent} == {
            "lubm_q1", "lubm_q3", "lubm_q4", "lubm_q7", "lubm_q8"}
        assert all(want[r["text"]] for r in sent)  # no empty answer
    assert set(served["statuses"]) == {200}
    # the window's requests: each counted on the device path, alone or in a
    # group, but for those folded into an equal text's execution
    def grew(name):
        return _metric(name, served["m1"]) - _metric(name, served["m0"])

    served_by = (grew('kolibrie_query_seconds_count{path="device"')
                 + grew("kolibrie_query_batched_total")
                 + grew("kolibrie_batcher_dedup_hits_total"))
    assert served_by == len(served["window"])
    assert grew('kolibrie_query_seconds_count{path="degraded"') == 0
    assert grew('kolibrie_query_seconds_count{path="host"') == 0
    # the composition of its dispatches is counted
    dispatches = grew(COUNTERS["dispatches"])
    assert grew(COUNTERS["templates"]) >= dispatches >= 1
    assert grew(COUNTERS["group"]) >= 1
    assert (grew(COUNTERS["group"]) + grew(COUNTERS["solo"])
            >= grew(COUNTERS["templates"]))


def test_after_the_ramp_no_cycle_builds_a_group_program(served):
    """See the module's text for why the ramp meets every template's group:
    the window, whatever splits its clients fell into, built nothing."""
    assert served["compiled"]["run_plan_batch"] >= 3  # Q1, Q3, Q4, Q7 grouped
    assert served["compiled_after"] == served["compiled"]
