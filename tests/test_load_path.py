"""The load path of ISSUE 34: RDF text reaches a tokenizer that skips ``#``
comments itself (no per-character strip in front of it), a load in many
chunks leaves the store a single load leaves, the phases of a load are
counted and traced where the work happens, and ``lubm-50``'s deployment,
rehearsed at one university, answers as the plain reference does.

- (a) {native tokenizer, Python parser} x {``ntriples``, ``turtle``}: text
  with a comment line, a comment after the final ``.``, ``#`` inside an IRI
  and inside a literal loads the triples of the same text with the comments
  taken out by hand, through ``_load_rdf_into`` and through ``/rsp/push``;
- (b) ``/store/load`` in 24 and more chunks, the later ones folded by
  ``_compact_incremental`` (its bulk branch and its per-row one), with rows
  sent twice: the exact deduplicated count in every reply, the six orders
  and the rows of one load;
- (c) ``lubm-50`` at scale 1 through the served path: Q2, Q9 and the five
  ``lookups`` templates against ``benchmark/reference/sparql_subset.py``;
- (e) ``tokenize`` + ``intern`` stay inside ``parse``, the span ``store.load``
  has the phases as children, and the gauges read the padded slots of a
  known small store.
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
from benchmark.harness.traffic import Traffic  # noqa: E402
from benchmark.reference.sparql_subset import Reference  # noqa: E402
from kolibrie_tpu import native  # noqa: E402
from kolibrie_tpu.core.store import ColumnarTripleStore  # noqa: E402
from kolibrie_tpu.frontends import http_server  # noqa: E402
from kolibrie_tpu.obs import export as obs_export  # noqa: E402
from kolibrie_tpu.obs import spans as prog_spans  # noqa: E402
from kolibrie_tpu.ops import round_cap  # noqa: E402
from kolibrie_tpu.query.sparql_database import SparqlDatabase  # noqa: E402

SEED = 2**31 + 34
ORDERS = ("spo", "pos", "osp", "pso", "ops", "sop")


@pytest.fixture(scope="module")
def server():
    httpd = http_server.make_server("127.0.0.1", 0, quiet=True, data_dir=None)
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    yield httpd, f"http://127.0.0.1:{httpd.server_address[1]}"
    httpd.shutdown()
    httpd.server_close()
    thread.join(timeout=30)


def _post(base, path, payload, trace_id=""):
    headers = {"Content-Type": "application/json"}
    if trace_id:
        headers["X-Kolibrie-Trace-Id"] = trace_id
    req = urllib.request.Request(base + path, data=json.dumps(payload).encode(),
                                 headers=headers, method="POST")
    with urllib.request.urlopen(req, timeout=600) as resp:
        return json.loads(resp.read())


def _metric(prefix):
    return sum(float(line.rpartition(" ")[2])
               for line in obs_export.render_prometheus().splitlines()
               if line.startswith(prefix))


def _load_seconds(phase):
    if phase in ("tokenize", "intern"):  # the steps of parse, a family of their own
        return _metric(f'kolibrie_store_parse_seconds_total{{step="{phase}"}}')
    return _metric(f'kolibrie_store_load_seconds_total{{phase="{phase}"}}')


# ------------------------------------------------ (a) comments are the tokenizers'

COMMENTED = {
    "ntriples": (
        "# a comment line, with a <http://e/not#a> \"triple\" . in it\n"
        "<http://e/univ-bench.owl#Student> <http://e/p#q> \"a # in a literal\" . # after the dot\n"
        "   # an indented comment\n"
        "<http://e/s> <http://e/p#q> <http://e/univ-bench.owl#Student> .# no space\n"
        "<http://e/s> <http://e/name> \"quote \\\" then # still inside\"@en .\n"
        "# the last line is a comment without a newline"),
    "turtle": (
        "# a comment line\n"
        "@prefix ub: <http://e/univ-bench.owl#> . # after a directive\n"
        "@prefix e: <http://e/> .\n"
        "e:s a ub:Student ; # between a predicate list's parts\n"
        "    e:name \"a # in a literal\" , \"b\" . # after the dot\n"
        "<http://e/univ-bench.owl#Student> e:p <http://e/o#frag> .\n"
        "# the last line is a comment without a newline"),
}
BY_HAND = {
    "ntriples": (
        "<http://e/univ-bench.owl#Student> <http://e/p#q> \"a # in a literal\" .\n"
        "<http://e/s> <http://e/p#q> <http://e/univ-bench.owl#Student> .\n"
        "<http://e/s> <http://e/name> \"quote \\\" then # still inside\"@en .\n"),
    "turtle": (
        "@prefix ub: <http://e/univ-bench.owl#> .\n"
        "@prefix e: <http://e/> .\n"
        "e:s a ub:Student ;\n"
        "    e:name \"a # in a literal\" , \"b\" .\n"
        "<http://e/univ-bench.owl#Student> e:p <http://e/o#frag> .\n"),
}
N_TRIPLES = {"ntriples": 3, "turtle": 4}
RSP_QUERY = (
    "REGISTER RSTREAM <out> AS SELECT * "
    "FROM NAMED WINDOW <w> ON <stream1> [RANGE 10 STEP 2] "
    "WHERE { WINDOW <w> { ?s ?p ?o } }")


def _decoded(db):
    s, p, o = db.store.columns()
    return sorted((db.decode_term(int(a)), db.decode_term(int(b)), db.decode_term(int(c)))
                  for a, b, c in zip(s, p, o))


class _Recorder:
    """What ``_push_event`` asks of an RSP engine, kept."""

    def __init__(self):
        self.pushed = []

    def add_to_stream(self, stream, triple, timestamp):
        self.pushed.append((stream, timestamp, triple.s, triple.p, triple.o))

    def process_single_thread_window_results(self):
        pass


@pytest.mark.parametrize("fmt", ["ntriples", "turtle"])
@pytest.mark.parametrize("parser", ["native", "python"])
def test_text_with_comments_loads_the_triples_of_the_text_without(
        server, monkeypatch, parser, fmt):
    ran_native = []
    for name in ("_parse_ntriples_native", "_parse_turtle_native"):
        inner = getattr(SparqlDatabase, name)
        if parser == "python":
            monkeypatch.setattr(SparqlDatabase, name, lambda self, data: None)
        else:
            def spy(self, data, _inner=inner):
                got = _inner(self, data)
                ran_native.append(got is not None)
                return got
            monkeypatch.setattr(SparqlDatabase, name, spy)
    loaded = {}
    for what, text in (("commented", COMMENTED[fmt]), ("by hand", BY_HAND[fmt])):
        db = SparqlDatabase()
        assert http_server._load_rdf_into(db, text, fmt) == N_TRIPLES[fmt]
        loaded[what] = _decoded(db)
    assert loaded["commented"] == loaded["by hand"]
    assert len(loaded["by hand"]) == N_TRIPLES[fmt]
    terms = {t for row in loaded["by hand"] for t in row}
    assert "http://e/univ-bench.owl#Student" in terms and '"a # in a literal"' in terms
    if parser == "native" and native.available():
        assert ran_native == [True, True]  # the tokenizer under test did the work
    if parser == "python":
        assert ran_native == []

    # the stream's route: one parser, Python's, whatever the store's is
    pushed = {}
    for what, text in (("commented", COMMENTED[fmt]), ("by hand", BY_HAND[fmt])):
        engine = _Recorder()
        assert http_server._push_event(engine, "stream1", 7, text) == N_TRIPLES[fmt]
        pushed[what] = sorted(engine.pushed)
    assert pushed["commented"] == pushed["by hand"]
    _httpd, base = server
    sid = _post(base, "/rsp/register", {"query": RSP_QUERY})["session_id"]
    for ts, text in ((1, COMMENTED[fmt]), (2, BY_HAND[fmt]), (3, "# nothing but a comment")):
        got = _post(base, "/rsp/push", {"session_id": sid, "stream": "stream1",
                                        "timestamp": ts, "ntriples": text})
        assert got["ok"] and got["triples"] == (N_TRIPLES[fmt] if ts < 3 else 0)


# ------------------------------------------------ (b) many chunks, one store


@pytest.fixture(scope="module")
def generated():
    config = bench_files.read_json("configs", "lubm-50.json")
    assert config["universities"] == 50 and config["reduced"] == {}
    return bench_files.load_module("generators", config["generator"]).generate(
        config, SEED, 1)


def _text(data, rows):
    terms = data["terms"]
    s, p, o = (data[c][rows].tolist() for c in "spo")
    return "".join(f"{terms[a]} {terms[b]} {terms[c]} .\n" for a, b, c in zip(s, p, o))


def _store_of(httpd, sid):
    return httpd.RequestHandlerClass.state.stores[sid].db.store


def test_a_store_loaded_in_many_chunks_is_the_store_of_one_load(server, generated):
    httpd, base = server
    n = len(generated["s"])
    # 24 even chunks, each with the 300 rows before it sent again, then three
    # of 300 rows: the first 17 rebuild (a chunk is a sixteenth of the store
    # or more), the later even ones take _compact_incremental's bulk branch
    # (more rows than the delta's threshold), the small ones its per-row one
    small, even = 3 * 300, 24
    edges = np.linspace(0, n - small, even + 1).astype(int).tolist()
    edges += [n - small + 300 * k for k in (1, 2, 3)]
    one = _post(base, "/store/load", {"rdf": _text(generated, np.arange(n)),
                                      "format": "ntriples"})
    assert one["loaded"] == one["triples"] == n  # the generator emits no row twice
    whole = _store_of(httpd, one["store_id"])
    merges0 = _metric("kolibrie_store_delta_merges_total")
    rebuilds0 = _metric("kolibrie_store_order_rebuilds_total")
    sid, rebuilt = None, 0
    for k, (lo, hi) in enumerate(zip(edges, edges[1:])):
        again = max(lo - 300, 0)
        rebuilt += (hi - again) * 16 >= lo  # store.py's rule for a full rebuild
        body = {"rdf": _text(generated, np.arange(again, hi)), "format": "ntriples"}
        if sid:
            body["store_id"] = sid
        got = _post(base, "/store/load", body)
        sid = got["store_id"]
        assert (got["loaded"], got["triples"]) == (hi - again, hi), k
        if k == 19:
            # from here on every order is built and has to be maintained
            for name in ORDERS:
                _store_of(httpd, sid).order(name)
    chunked = _store_of(httpd, sid)
    assert 16 <= rebuilt <= 18
    assert _metric("kolibrie_store_order_rebuilds_total") - rebuilds0 == rebuilt
    # every later even chunk folded into the base, no small one did
    assert _metric("kolibrie_store_delta_merges_total") - merges0 == even - rebuilt
    assert chunked._delta_epoch == even - rebuilt + 3
    assert len(chunked._delta_add_set) == small  # the small chunks are a delta
    for a, b in zip(chunked.columns(), whole.columns()):
        assert np.array_equal(a, b)
    for name in ORDERS:
        got, want = chunked.order(name), whole.order(name)
        for col in ("c0", "c1", "c2", "key01"):
            assert np.array_equal(getattr(got, col), getattr(want, col)), (name, col)
    # and a read sees every acknowledged triple, the small chunks' too
    text = ("SELECT ?s ?p ?o WHERE { ?s ?p ?o . ?s <http://www.w3.org/1999/02/"
            "22-rdf-syntax-ns#type> <http://swat.cse.lehigh.edu/onto/univ-bench.owl#University> }")
    rows = [_post(base, "/store/query", {"store_id": s_, "sparql": text,
                                         "deadline_ms": 600_000})["data"]
            for s_ in (sid, one["store_id"])]
    assert sorted(map(tuple, rows[0])) == sorted(map(tuple, rows[1])) and rows[0]


def test_a_bulk_append_over_tombstones_and_a_delta_equals_the_full_rebuild():
    """The bulk branch of ``_compact_incremental`` beside the oracle
    (``incremental = False``), started from a store whose delta tier holds
    adds and tombstones, the batch re-adding a tombstoned base row."""
    rng = np.random.default_rng(34)
    base = rng.integers(1, 5000, size=(60_000, 3)).astype(np.uint32)
    batch = rng.integers(1, 5000, size=(3_000, 3)).astype(np.uint32)
    stores = []
    for incremental in (True, False):
        st = ColumnarTripleStore()
        st.incremental = incremental
        st.add_batch(*base.T)
        for name in ORDERS:
            st.order(name)
        for row in base[:5]:
            st.remove(*row.tolist())
        st.add(7001, 7002, 7003)
        assert len(st) == len(np.unique(base, axis=0)) - len(np.unique(base[:5], axis=0)) + 1
        st.add_batch(*np.vstack([batch, base[:2], batch[:10]]).T)
        stores.append(st)
    inc, full = stores
    assert len(inc) == len(full)
    assert inc._delta_epoch == 2 and not inc._delta_add_set and not inc._delta_del_set
    assert inc.base_version == inc.version  # the bulk append folded into the base
    for name in ORDERS:
        for col in ("c0", "c1", "c2", "key01"):
            assert np.array_equal(getattr(inc.order(name), col),
                                  getattr(full.order(name), col)), (name, col)
    assert inc.triples_set() == full.triples_set()


# ------------------------------------------------ (c) lubm-50, rehearsed


def test_lubm_50_at_one_university_answers_as_the_reference(server, generated):
    httpd, base = server
    ref = Reference(generated["terms"], generated["s"], generated["p"], generated["o"])
    sid = None
    for text in bench_files.ntriples_chunks(generated):  # the harness's chunks
        body = {"rdf": text, "format": "ntriples", "mode": "device"}
        if sid:
            body["store_id"] = sid
        got = _post(base, "/store/load", body)
        sid = got["store_id"]
    assert got["triples"] == len(generated["s"])
    # growth, not the value: a worker runs many files in one process, and a
    # file before this one may have degraded requests on purpose
    on_device0 = _metric('kolibrie_query_seconds_count{path="device"}')
    degraded0 = _metric('kolibrie_query_seconds_count{path="degraded"}')
    sent = 0
    for traffic_name in ("triangles", "lookups"):
        traffic = Traffic(traffic_name, generated["domains"], SEED)
        for name, text in traffic.cycle(0):
            rows = _post(base, "/store/query", {"store_id": sid, "sparql": text,
                                                "deadline_ms": 900_000})["data"]
            want = ref.query(text)
            assert want, name
            assert sorted(map(tuple, rows)) == sorted(map(tuple, want)), name
            sent += 1
    assert sent == 7
    assert _metric('kolibrie_query_seconds_count{path="device"}') - on_device0 == sent
    assert _metric('kolibrie_query_seconds_count{path="degraded"}') == degraded0
    # what the cell's new metrics read: the base segments this store holds
    store = _store_of(httpd, sid)
    slots = round_cap(len(generated["s"]))
    assert _metric('kolibrie_store_base_rows{of="slots"}') == slots
    assert _metric('kolibrie_store_base_rows{of="rows"}') == len(generated["s"])
    assert _metric("kolibrie_store_device_bytes") == (
        len(store._device_segments) * 3 * 4 * slots) > 0
    assert _metric("kolibrie_wcoj_probes_total") > 0


# ------------------------------------------------ (e) phases, span, gauges


def test_the_phases_of_a_load_stay_inside_parse_and_the_span_holds_them(server):
    _httpd, base = server
    before = {ph: _load_seconds(ph) for ph in ("parse", "tokenize", "intern", "compact")}
    nt = "".join(f"<http://e/s{k}> <http://e/p{k % 7}> \"v{k} # {k}\" . # c\n"
                 for k in range(3000))
    prog_spans.clear()
    got = _post(base, "/store/load", {"rdf": nt, "format": "ntriples"}, "load-phases")
    assert got["triples"] == 3000
    grew = {ph: _load_seconds(ph) - before[ph] for ph in before}
    assert all(v > 0 for v in grew.values()), grew
    assert grew["tokenize"] + grew["intern"] <= grew["parse"]
    assert (grew["tokenize"] + grew["intern"] + grew["compact"]
            <= grew["parse"] + grew["compact"])
    # what setup_parse_s has always read, every phase of the family summed,
    # is parse + compact still: the steps are counted apart
    family = _metric("kolibrie_store_load_seconds_total")
    assert family == pytest.approx(_load_seconds("parse") + _load_seconds("compact"))
    spans = {s["name"]: s for s in prog_spans.spans_snapshot("load-phases")}
    load = spans["store.load"]
    assert load["parent_id"] == spans["http.request"]["span_id"]
    assert load["attrs"] == {"format": "ntriples", "loaded": 3000, "triples": 3000}
    for phase in ("tokenize", "intern", "compact"):
        child = spans["store." + phase]
        assert child["parent_id"] == load["span_id"]
    assert sum(spans["store." + ph]["dur_ms"] for ph in ("tokenize", "intern", "compact")
               ) <= load["dur_ms"]
    # outside a trace a phase is counted and opens no span
    prog_spans.clear()
    db = SparqlDatabase()
    assert db.parse_ntriples(nt) == 3000 and len(db.store) == 3000
    assert prog_spans.spans_snapshot() == []
    assert _load_seconds("compact") > before["compact"] + grew["compact"]


@pytest.mark.parametrize("rows", [1, 128, 129, 5000])
def test_the_gauges_read_the_padded_slots_of_a_known_store(rows):
    st = ColumnarTripleStore()
    k = np.arange(1, rows + 1, dtype=np.uint32)
    st.add_batch(k, k % 5 + 1, k[::-1])
    slots = round_cap(rows)
    for held, name in enumerate(("spo", "pos", "osp"), start=1):
        st.device_segment(name)
        assert _metric('kolibrie_store_base_rows{of="rows"}') == rows
        assert _metric('kolibrie_store_base_rows{of="slots"}') == slots
        assert _metric("kolibrie_store_device_bytes") == held * 3 * 4 * slots
    st.device_segment("spo")  # held already: nothing uploads, nothing moves
    assert _metric("kolibrie_store_device_bytes") == 3 * 3 * 4 * slots
