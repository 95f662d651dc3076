"""BSBM's Business Intelligence use case (ISSUE 42): the deployment
``bsbm-10m`` rehearsed at small product counts on the CPU.

- (a) the generator: the same seed gives the same arrays and another seed
  other data; the classes hold the numbers the stated ratios give; every
  instance is typed, every triple and term is there once, 38 predicates; the
  type tree follows the stated formula; countries follow the weighted list;
- (b) the three templates through ``/store/load`` and ``/store/query``
  against ``benchmark/reference/sparql_subset.py`` on two seeds, every
  request on the device path and its GROUP BY on the device tier; the
  control (the reference on a stale store) answers wrongly;
- (c) a template's group capacity is the template's: coldest instance first
  against hottest first gives one capacity set, one plan executable and one
  aggregation executable, after which no instance re-runs the aggregation;
  a pair of hot countries, which no pass that frees one of them counts, is
  counted by the pass that frees both; every type, pair of countries and
  product runs from the capacities the first request calibrated, joins and
  group tables at the counts no instance can pass (ISSUE 44);
- (d) an aggregate request leaves ``device.dispatch`` and
  ``device.aggregate``; a shape the device declines grows ``tier="host"``;
  the counters read the slots, rows, group slots and groups of one request;
  a first sight compiles the aggregation on a thread beside the plan.
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

from benchmark.generators import bsbm  # noqa: E402
from benchmark.harness import data as bench_files  # noqa: E402
from benchmark.harness import loadgen  # noqa: E402
from benchmark.harness.traffic import Traffic  # noqa: E402
from benchmark.reference.sparql_subset import Reference  # noqa: E402
from kolibrie_tpu.frontends import http_server  # noqa: E402
from kolibrie_tpu.obs import export as obs_export  # noqa: E402
from kolibrie_tpu.obs import spans as obs_spans  # noqa: E402
from kolibrie_tpu.optimizer import caps as capacities  # noqa: E402
from kolibrie_tpu.optimizer import device_engine as de  # noqa: E402

SEED = 2**31 + 42
CONFIG = bench_files.read_json("configs", "bsbm-10m.json")
TEMPLATES = {"bsbm_bi_q1": ("country1", "country2"), "bsbm_bi_q2": ("product",),
             "bsbm_bi_q5": ("producttype",)}
BSBM = bsbm.NAMESPACES["bsbm"]


def _pid(data, prefixed):
    return data["terms"].index(f"<{bsbm.iri(prefixed)}>")


def _subjects_of_class(data, iri):
    cls = data["terms"].index(f"<{iri}>")
    return data["s"][(data["p"] == _pid(data, "rdf:type")) & (data["o"] == cls)]


# ------------------------------------------------------------ (a) the generator


@pytest.fixture(scope="module")
def generated():
    assert CONFIG["products"] == 28480 and sorted(CONFIG["reduced"]) == ["queries", "top_k"]
    return bsbm.generate(CONFIG, SEED, 3)


def test_the_same_seed_gives_the_same_data_and_another_seed_other_data(generated):
    again = bsbm.generate(CONFIG, SEED, 3)
    assert again["terms"] == generated["terms"]
    for col in "spo":
        assert np.array_equal(again[col], generated[col])
    assert again["domains"] == generated["domains"]
    other = bsbm.generate(CONFIG, SEED + 1, 3)
    assert len(other["s"]) != len(generated["s"]) or not np.array_equal(
        other["o"], generated["o"])
    # the countries' pairs and the type tree are the scale's, not the seed's
    for domain in ("country1", "country2", "producttype"):
        assert other["domains"][domain] == generated["domains"][domain]


def test_the_classes_hold_what_the_stated_ratios_give(generated):
    s, p, o, terms = (generated[k] for k in ("s", "p", "o", "terms"))
    n = 300
    count = {cls: len(_subjects_of_class(generated, BSBM + cls)) for cls in bsbm.CLASSES}
    count["Person"] = len(_subjects_of_class(generated, bsbm.iri("foaf:Person")))
    ratios = bsbm.RATIOS
    assert count["Product"] == n == len(generated["domains"]["product"])
    assert count["Offer"] == n * ratios["offers_a_product"] == 6000
    assert count["Review"] == n * ratios["reviews_a_product"] == 3000
    assert count["Producer"] == round(n / ratios["products_a_producer"]) == 6
    assert count["Vendor"] == round(n / ratios["products_a_vendor"]) == 3
    assert count["Person"] == round(3000 / ratios["reviews_a_reviewer"]) == 154
    # 300 products: depth 1 // 2 + 1 ... round(log10 300) = 2: depth 2, the
    # root 4-fold, below it 8-fold
    parent, level = bsbm.type_tree(n)
    assert count["ProductType"] == len(parent) == 1 + 4 + 32
    assert len(generated["domains"]["producttype"]) == len(parent) - 1
    assert np.bincount(level).tolist() == [1, 4, 32]
    lo, hi = ratios["features_a_type"]
    assert lo * 36 <= count["ProductFeature"] <= hi * 36
    # the 10 M data set's tree, by the same formula
    assert np.bincount(bsbm.type_tree(28480)[1]).tolist() == [1, 8, 64, 512]
    # every subject is typed; a product carries its leaf type and each ancestor
    typed = set(s[p == _pid(generated, "rdf:type")].tolist())
    assert set(s.tolist()) <= typed
    product = generated["terms"].index(f"<{generated['domains']['product'][7]}>")
    types = o[(s == product) & (p == _pid(generated, "rdf:type"))]
    assert len(types) == 1 + 3  # bsbm:Product, the leaf, its parent, the root
    used = {terms[i] for i in np.unique(p)}
    assert used == {f"<{bsbm.iri(name)}>" for name in bsbm.PREDICATES}
    assert len(used) == CONFIG["shapes"]["predicates"] == 38
    assert len(set(terms)) == len(terms)
    assert len(np.unique((s << 42) ^ (p << 21) ^ o)) == len(s)  # every triple once
    assert 330 * n <= len(s) <= 380 * n  # about 350 triples a product
    assert all(t[0] in '<"' for t in terms)
    assert not any(t.startswith('"') and not t.endswith('"') for t in terms)  # plain
    # one producer, 10 reviews and 20 offers a product on average, ratings absent in part
    assert (p == _pid(generated, "bsbm:producer")).sum() == n
    rated = (p == _pid(generated, "bsbm:rating1")).sum()
    assert 0.6 * 3000 < rated < 0.8 * 3000
    features = (p == _pid(generated, "bsbm:productFeature")).sum() / n
    assert 8 < features < 20  # two levels of types below the root here, three at 10 M


def test_countries_follow_the_weighted_list_and_pair_place_for_place():
    data = bsbm.generate(CONFIG, SEED, 30)  # 1,500 reviewers
    assert dict(bsbm.COUNTRIES) == CONFIG["shapes"]["countries_percent"]
    assert sum(w for _, w in bsbm.COUNTRIES) == 100
    rows = data["p"] == _pid(data, "bsbm:country")
    people = np.isin(data["s"], _subjects_of_class(data, bsbm.iri("foaf:Person")))
    ids, counts = np.unique(data["o"][rows & people], return_counts=True)
    share = {data["terms"][i][1:-1].rpartition("#")[2]: c / counts.sum()
             for i, c in zip(ids, counts)}
    assert 0.35 < share["US"] < 0.45 and all(
        0.02 < share[c] < 0.14 for c, _ in bsbm.COUNTRIES[1:])
    one, two = data["domains"]["country1"], data["domains"]["country2"]
    assert len(one) == len(two) == 100 == len(set(zip(one, two)))
    assert one[0] == two[0] == bsbm.COUNTRY_NS + "US"
    # one step's placeholders walk together: the i-th pair, never one country twice
    traffic = Traffic("bi_counts", data["domains"], SEED)
    pairs = set()
    for k in range(100):
        text = traffic.cycle(k)[0][1]
        pair = tuple(c for c in (text.split("bsbm:country <")[1].split(">")[0],
                                 text.split("bsbm:country <")[2].split(">")[0]))
        pairs.add(pair)
    assert pairs == set(zip(one, two))  # a whole epoch walks every ordered pair


def test_no_block_of_the_harness_chunks_passes_the_servers_request_limit():
    """``harness/data.py`` cuts the N-Triples at blocks of 100,000 triples and
    sends a block whole whatever its size; the server takes 64 MiB a request.
    Written entity by entity no block comes near it (by predicate, a block of
    review texts is twice the limit: my chip run A, PR 42, a broken pipe)."""
    data = bsbm.generate(CONFIG, SEED, 30)  # 1.05 M triples, 30,000 reviews
    assert (np.diff(data["s"]) != 0).sum() + 1 == len(np.unique(data["s"]))  # by subject
    lengths = np.array([len(t.encode()) for t in data["terms"]])
    sizes = lengths[data["s"]] + lengths[data["p"]] + lengths[data["o"]] + 5
    blocks = np.add.reduceat(sizes, np.arange(0, len(sizes), 100_000))
    assert len(blocks) >= 10 and blocks.max() < bench_files.LOAD_CHUNK_BYTES
    chunks = bench_files.ntriples_chunks(data)
    assert max(len(c) for c in chunks) <= bench_files.LOAD_CHUNK_BYTES
    assert sum(c.count("\n") for c in chunks) == len(data["s"])


def test_the_traffic_file_cycles_the_three_counting_cores():
    spec = bench_files.read_json("traffic", "bi_counts.json")
    assert (spec["loop"], spec["clients"], spec["deadline_ms"], spec["warmup_cycles"],
            spec["warmup_ramp"], spec["trace_min_seconds"]) == (
        "closed", 1, 900000, 5, [1], 3)
    assert [step["template"] for step in spec["cycle"]] == list(TEMPLATES)
    for step in spec["cycle"]:
        text = bench_files.template_text(step["template"])
        assert step["constants"] == {d: {"draw": d} for d in TEMPLATES[step["template"]]}
        assert all(f"<@{d}@>" in text for d in TEMPLATES[step["template"]])
        assert "COUNT(" in text and "GROUP BY" in text and " a " not in text
        assert "ORDER BY" not in text and "LIMIT" not in text  # reduced: top_k
    assert "FILTER(?otherProduct != <@product@>)" in bench_files.template_text("bsbm_bi_q2")
    assert sorted(CONFIG["shapes"]["templates"]) == sorted(TEMPLATES) + ["written"]


# --------------------------------------------- (b) the templates, served


@pytest.fixture(scope="module")
def server():
    httpd = http_server.make_server("127.0.0.1", 0, quiet=True, data_dir=None)
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    yield httpd, f"http://127.0.0.1:{httpd.server_address[1]}"
    httpd.shutdown()
    httpd.server_close()
    thread.join(timeout=30)


def _post(base, path, payload, trace_id=None):
    headers = {"Content-Type": "application/json"}
    if trace_id:
        headers["X-Kolibrie-Trace-Id"] = trace_id
    req = urllib.request.Request(base + path, data=json.dumps(payload).encode(),
                                 headers=headers, method="POST")
    with urllib.request.urlopen(req, timeout=900) as resp:
        return resp.read()


def _metric(prefix):
    return sum(float(line.rpartition(" ")[2])
               for line in obs_export.render_prometheus().splitlines()
               if line.startswith(prefix))


AGGREGATE_COUNTERS = (
    "kolibrie_device_aggregate_slots_total", "kolibrie_device_aggregate_rows_total",
    "kolibrie_device_group_slots_total", "kolibrie_device_groups_total",
    "kolibrie_aggregate_cap_retries_total", 'kolibrie_aggregate_total{tier="device"}',
    'kolibrie_aggregate_total{tier="host"}', 'kolibrie_cap_retries_total{engine="device"}',
    'kolibrie_query_seconds_count{path="device"}',
    'kolibrie_query_seconds_count{path="host"}',
    'kolibrie_query_seconds_count{path="degraded"}')


def _counters():
    return {name: _metric(name) for name in AGGREGATE_COUNTERS}


def _grew(before):
    return {name: value - before[name] for name, value in _counters().items()
            if value != before[name]}


def _load(base, data):
    sid = None
    for text in bench_files.ntriples_chunks(data):  # the harness's chunks
        body = {"rdf": text, "format": "ntriples", "mode": "device"}
        if sid:
            body["store_id"] = sid
        got = json.loads(_post(base, "/store/load", body))
        sid = got["store_id"]
    assert got["triples"] == len(data["s"])
    return sid


def _ask_body(base, sid, text, trace_id=None):
    return _post(base, "/store/query",
                 {"store_id": sid, "sparql": text, "deadline_ms": 900_000}, trace_id)


def _ask(base, sid, text, trace_id=None):
    return json.loads(_ask_body(base, sid, text, trace_id))["data"]


def _capacities(base, sid):
    """The store's block of ``/stats``: a record a template it has seen."""
    with urllib.request.urlopen(base + "/stats", timeout=60) as resp:
        return json.load(resp)["stores"][sid]["capacities"]["templates"]


def _instance(data, template, index):
    text = bench_files.template_text(template)
    for domain in TEMPLATES[template]:
        text = text.replace(f"@{domain}@", data["domains"][domain][index])
    return text


@pytest.mark.parametrize("seed", [SEED, 7])
def test_the_cycle_answers_as_the_reference_and_the_control_does_not(server, seed):
    _httpd, base = server
    data = bsbm.generate(CONFIG, seed, 3)
    sid = _load(base, data)
    traffic = Traffic("bi_counts", data["domains"], seed)
    before, requests = _counters(), []
    for k in range(4):
        for name, text in traffic.cycle(k):
            requests.append({"template": name, "text": text, "status": 200,
                             "body": _ask_body(base, sid, text)})
    grew = _grew(before)
    assert grew.pop("kolibrie_device_aggregate_slots_total") > 0
    assert grew.pop("kolibrie_device_aggregate_rows_total") > 0
    assert grew.pop("kolibrie_device_group_slots_total") >= grew.pop(
        "kolibrie_device_groups_total") > 0
    # every request on the device path, its GROUP BY on the device tier, no
    # aggregation run twice, no join capacity passed
    assert grew == {'kolibrie_aggregate_total{tier="device"}': 12,
                    'kolibrie_query_seconds_count{path="device"}': 12}
    # the harness's own comparison, the control beside it (a stale store
    # changes counts)
    out = loadgen._compare(CONFIG, data, seed, requests, control=True)
    assert out["wrong"] == [] and out["empty"] == [] and out["distinct_texts"] == 12
    assert set(out["rows_by_template"]) == set(TEMPLATES)
    assert out["control"]["stale_share"] == 0.01
    assert out["control"]["texts_answered_wrongly"] >= 6
    assert out["control"]["control_correct"] is False


# ---------------- (c) the first instance does not decide the group capacity


@pytest.fixture(scope="module")
def skewed():
    """2,000 products: a type under the root groups more reviews than the
    1,024-group floor holds, a leaf a fifth of it."""
    return bsbm.generate(CONFIG, SEED, 20)


@pytest.fixture(scope="module")
def two_stores(server, skewed):
    _httpd, base = server
    ref = Reference(skewed["terms"], skewed["s"], skewed["p"], skewed["o"])
    return {"cold_first": _load(base, skewed), "hot_first": _load(base, skewed)}, ref


@pytest.mark.parametrize("template, past_the_floor", [
    ("bsbm_bi_q5", True), ("bsbm_bi_q1", False), ("bsbm_bi_q2", False)])
def test_the_first_instance_does_not_decide_a_templates_group_capacity(
        server, skewed, two_stores, template, past_the_floor):
    _httpd, base = server
    stores, ref = two_stores
    n = len(skewed["domains"][TEMPLATES[template][0]])
    picks = range(n) if n <= 100 else np.random.default_rng(SEED).integers(0, n, 24)
    groups = {int(k): len(ref.query(_instance(skewed, template, int(k)))) for k in picks}
    hot = max(groups, key=groups.get)
    cold = min((k for k in groups if groups[k]), key=groups.get)
    if past_the_floor:  # beyond the floor, and beyond the cold instance's headroom
        assert groups[hot] > 1024 > 4 * groups[cold] > 0
    caps, compiled0 = {}, de.device_compile_stats()
    before = _counters()
    for order, first in (("cold_first", cold), ("hot_first", hot)):
        sid = stores[order]
        known = _capacities(base, sid)  # the templates before this one
        assert len(_ask(base, sid, _instance(skewed, template, first))) == groups[first]
        (entry,) = [e for e in _capacities(base, sid) if e not in known]
        assert entry["provisional"] is False
        caps[order] = (entry["caps"], entry["group_caps"])
        for k in groups:  # then every instance picked
            assert len(_ask(base, sid, _instance(skewed, template, k))) == groups[k]
        (entry,) = [e for e in _capacities(base, sid) if e not in known]
        assert (entry["caps"], entry["group_caps"]) == caps[order]
    # one capacity set whichever came first: one plan and one aggregation compiled
    assert caps["cold_first"] == caps["hot_first"]
    assert caps["cold_first"][1][0] >= groups[hot]
    compiled = de.device_compile_stats()
    assert compiled["run_plan"] - compiled0["run_plan"] <= 1
    assert compiled["segment_aggregate"] - compiled0["segment_aggregate"] <= 1
    grew = _grew(before)
    assert "kolibrie_aggregate_cap_retries_total" not in grew
    assert 'kolibrie_cap_retries_total{engine="device"}' not in grew
    assert 'kolibrie_aggregate_total{tier="host"}' not in grew


def test_a_pair_of_hot_countries_is_counted_by_the_pass_that_frees_both(
        server, skewed, two_stores):
    """BI Q1 has two placeholders: a pass that frees one country with the
    other as the instance has it never sees US-US (16 % of the pairs of
    producer and reviewer), 64 times the rows of a pair of 5 % countries."""
    _httpd, base = server
    stores, ref = two_stores
    domains = skewed["domains"]
    pair = list(zip(domains["country1"], domains["country2"]))
    us = pair.index((bsbm.COUNTRY_NS + "US",) * 2)
    rows = []
    for k in (us, len(pair) - 1):  # US-US and AT-AT
        text = _instance(skewed, "bsbm_bi_q1", k)
        rows.append(sum(int(r[1]) for r in ref.query(text)))
    assert rows[0] > 16 * max(rows[1], 1)
    before = _counters()
    at_at = _instance(skewed, "bsbm_bi_q1", len(pair) - 1)
    assert sorted(map(tuple, _ask(base, stores["cold_first"], at_at))) == sorted(
        map(tuple, ref.query(at_at)))
    from kolibrie_tpu.query.executor import _plan_cache_entry

    httpd, _base = server
    db = httpd.RequestHandlerClass.state.stores[stores["cold_first"]].db
    fp = _plan_cache_entry(db, at_at)[0]["fp"]
    (entry,) = [e for e in _capacities(base, stores["cold_first"]) if e["template"] == fp]
    assert max(entry["caps"]) >= rows[0]  # the hot pair's rows fit what AT-AT compiled
    us_us = _instance(skewed, "bsbm_bi_q1", us)
    assert sorted(map(tuple, _ask(base, stores["cold_first"], us_us))) == sorted(
        map(tuple, ref.query(us_us)))
    assert 'kolibrie_cap_retries_total{engine="device"}' not in _grew(before)


KINDS = tuple(f'kolibrie_cap_calibrated_joins_total{{engine="device",kind="{kind}"}}'
              for kind in ("ceiling", "headroom"))


def _kinds():
    return tuple(_metric(name) for name in KINDS)


def _kinds_since(before):
    return tuple(int(now - then) for now, then in zip(_kinds(), before))


def test_every_instance_of_every_domain_runs_at_the_ceilings(server, generated, skewed):
    """ISSUE 44: a count that no instance can pass is compiled for as it is.
    At 300 products every type, every pair of countries and every product
    gives the reference's rows from the capacities the first request
    calibrated: no join capacity passed, no aggregation run twice.  BI Q5's
    three joins and its group table and BI Q1's six joins are ceilings (two
    of Q5's joins read no placeholder at all, the others are counted by the
    passes that free the placeholders); BI Q2's join is one, its group table
    sits over ``FILTER(?otherProduct != <product>)``, which no pass frees."""
    _httpd, base = server
    sid = _load(base, generated)
    ref = Reference(generated["terms"], generated["s"], generated["p"], generated["o"])
    before, sized = _counters(), {}
    for template, domains in TEMPLATES.items():
        kinds0 = _kinds()
        n = len(generated["domains"][domains[0]])
        assert n == {"bsbm_bi_q1": 100, "bsbm_bi_q2": 300, "bsbm_bi_q5": 36}[template]
        for k in range(n):
            text = _instance(generated, template, k)
            assert sorted(map(tuple, _ask(base, sid, text))) == sorted(
                map(tuple, ref.query(text))), (template, k)
            if k == 0:
                sized[template] = _kinds_since(kinds0)
        assert _kinds_since(kinds0) == sized[template]  # the first sight's alone
    grew = _grew(before)
    assert grew['kolibrie_aggregate_total{tier="device"}'] == 436
    assert "kolibrie_aggregate_cap_retries_total" not in grew
    assert 'kolibrie_cap_retries_total{engine="device"}' not in grew
    assert 'kolibrie_aggregate_total{tier="host"}' not in grew
    # joins and then the group table, where it is wider than the floor: Q2's
    # join holds under 1,024 rows here, and a table of 1,024 slots has no
    # more groups whatever the rule says
    assert sized == {"bsbm_bi_q1": (7, 0), "bsbm_bi_q2": (1, 0), "bsbm_bi_q5": (4, 0)}
    # at 2,000 products Q2's join passes the floor and the rule sizes its
    # group table: with headroom, the join beneath it without
    sid = _load(base, skewed)
    kinds0 = _kinds()
    text = _instance(skewed, "bsbm_bi_q2", 0)
    assert len(_ask(base, sid, text)) > 0
    assert _kinds_since(kinds0) == (1, 1)


# ------------------------------------- (d) spans, tiers and the counters


def test_an_aggregate_request_leaves_dispatch_and_aggregate_spans(server, generated):
    httpd, base = server
    sid = _load(base, generated)
    text = _instance(generated, "bsbm_bi_q5", 0)  # a type under the root
    _ask(base, sid, text)  # the first sight: calibration, compiles
    before = _counters()
    rows = _ask(base, sid, text, trace_id="bsbm-q5-warm")
    grew = _grew(before)
    spans = {sp["name"]: sp for sp in obs_spans.spans_snapshot()
             if sp["trace_id"] == "bsbm-q5-warm"}
    assert {"query.execute", "device.dispatch", "device.build", "device.enqueue",
            "device.wait", "device.counts", "device.aggregate"} <= set(spans)
    assert "device.collect" not in spans and "device.calibrate" not in spans
    attrs = spans["device.aggregate"]["attrs"]
    assert attrs["groups"] == len(rows) > 0
    assert attrs["rows"] == sum(int(r[2]) for r in rows)
    assert attrs["cap"] >= attrs["groups"]
    assert spans["device.aggregate"]["parent_id"] == spans["device.dispatch"]["parent_id"]
    # the counters are that request's: the table the aggregation sorted (the
    # last join's capacity), its valid rows, the group capacity, the groups
    store = httpd.RequestHandlerClass.state.stores[sid]
    ((_key, join_caps),) = capacities.of(store.db).joins.items()
    assert grew == {
        "kolibrie_device_aggregate_slots_total": join_caps[-1],
        "kolibrie_device_aggregate_rows_total": attrs["rows"],
        "kolibrie_device_group_slots_total": attrs["cap"],
        "kolibrie_device_groups_total": attrs["groups"],
        'kolibrie_aggregate_total{tier="device"}': 1,
        'kolibrie_query_seconds_count{path="device"}': 1}
    with open(os.path.join(REPO, "docs", "OBSERVABILITY.md"), encoding="utf-8") as f:
        catalog = f.read()
    for family in AGGREGATE_COUNTERS[:5] + ("kolibrie_aggregate_total",
                                            "kolibrie_device_aggregate_seconds"):
        assert f"`{family.partition('{')[0]}`" in catalog, family
    assert "`device.aggregate`" in catalog


def test_a_first_sight_compiles_the_aggregation_beside_the_plan(
        server, generated, monkeypatch):
    """Where the process keeps a compile cache, the aggregation a template's
    first dispatch ends in is compiled on a thread of its own from the moment
    its capacity is known; the dispatch waits for it and compiles nothing
    twice: one first-sight record of the entry point, one jit entry."""
    from kolibrie_tpu.query import compile_cache

    _httpd, base = server
    sid = _load(base, generated)
    monkeypatch.setattr(compile_cache, "enabled_dir", lambda: "/a/cache/directory")
    text = (f"PREFIX bsbm: <{BSBM}> SELECT ?vendor (COUNT(?offer) AS ?offers) "
            "WHERE { ?offer bsbm:vendor ?vendor } GROUP BY ?vendor")
    ahead0 = dict(de._AHEAD)
    entries0 = de.device_compile_stats()["segment_aggregate"]
    records0 = compile_cache.records()  # the ring holds the newest 256: by identity
    rows = _ask(base, sid, text)
    assert sorted(int(r[1]) for r in rows) == sorted(
        np.unique(generated["o"][generated["p"] == _pid(generated, "bsbm:vendor")],
                  return_counts=True)[1].tolist())
    (sig,) = set(de._AHEAD) - set(ahead0)
    assert de._AHEAD[sig] is None  # started, and waited for
    assert sig[1:] == (2, ((1,), ("COUNT",), (0,), (False,)), 1024)
    assert de.device_compile_stats()["segment_aggregate"] == entries0 + 1
    mine = [r for r in compile_cache.records() if r["fun"] == "_segment_aggregate"
            and not any(r is old for old in records0)]
    assert len(mine) == 1 and mine[0]["entry"] == "segment_aggregate"
    _ask(base, sid, text)  # and a second request starts nothing
    assert set(de._AHEAD) - set(ahead0) == {sig}


def test_a_shape_the_device_declines_is_counted_on_the_host_tier(server, generated):
    _httpd, base = server
    sid = _load(base, generated)
    text = (f"PREFIX bsbm: <{BSBM}> SELECT ?producer (GROUP_CONCAT(?product) AS ?all) "
            "WHERE { ?product bsbm:producer ?producer } GROUP BY ?producer")
    before = _counters()
    rows = _ask(base, sid, text)
    assert len(rows) == 6
    # routed to the device, its plan run there, its GROUP BY on the host:
    # query.execute's path alone would not tell
    assert _grew(before) == {'kolibrie_aggregate_total{tier="host"}': 1,
                             'kolibrie_query_seconds_count{path="device"}': 1}
