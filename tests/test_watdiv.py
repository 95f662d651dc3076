"""WatDiv (ISSUE 40): the deployment ``watdiv-100`` rehearsed at small scale
factors on the CPU.

- (a) the generator at scale factor 1: the same seed gives the same arrays
  and another seed other data; the classes hold the model's numbers of
  instances; 86 predicates, every triple and every term once; the largest
  group of ``gr:offers`` by retailer (at scale factor 10: 120 retailers),
  ``og:tag`` by topic, ``wsdbm:hasGenre`` by sub-genre and ``wsdbm:likes`` by
  product holds at least 20 times the rows of the median one: the property
  the cell exists for;
- (b) each of the twenty templates through ``/store/load`` and
  ``/store/query`` against ``benchmark/reference/sparql_subset.py`` on three
  instances (the placeholder's hottest instance and two drawn ones), every
  request on the device path;
- (c) the warm-up's order does not decide a template's executable: coldest
  instance first against hottest first gives one capacity set, after which no
  instance of the whole domain overflows a capacity or compiles a plan of
  another join order;
- (d) the two scan counters against the slots and rows of one plan, counted
  by hand from the generator's columns: a scan is as wide as the predicate it
  names (ISSUE 41), and over the cell's cycle every template keeps one spec.
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

from benchmark.generators import watdiv  # noqa: E402
from benchmark.harness import data as bench_files  # noqa: E402
from benchmark.reference.sparql_subset import Reference  # noqa: E402
from kolibrie_tpu.frontends import http_server  # noqa: E402
from kolibrie_tpu.obs import export as obs_export  # noqa: E402
from kolibrie_tpu.ops import round_cap  # noqa: E402
from benchmark.harness.traffic import Traffic  # noqa: E402
from kolibrie_tpu.optimizer.device_engine import (  # noqa: E402
    ScanSpec,
    _spec_nodes,
    device_compile_stats,
    predicate_rows,
)

SEED = 2**31 + 40
CONFIG = bench_files.read_json("configs", "watdiv-100.json")
TEMPLATES = {  # id -> the domain its placeholder draws from (None: no placeholder)
    "S1": "retailer", "S2": "country", "S3": "category", "S4": "agegroup",
    "S5": "category", "S6": "subgenre", "S7": "user",
    "F1": "topic", "F2": "subgenre", "F3": "subgenre", "F4": "topic", "F5": "retailer",
    "L1": "website", "L2": "city", "L3": "website", "L4": "topic", "L5": "city",
    "C1": None, "C2": None, "C3": None}


def _pid(data, prefixed):
    return data["terms"].index(f"<{watdiv.iri(prefixed)}>")


# ------------------------------------------------------------ (a) the generator


@pytest.fixture(scope="module")
def generated():
    assert CONFIG["scale_factor"] == 100 and CONFIG["reduced"] == {}
    return watdiv.generate(CONFIG, SEED, 1)


def test_the_same_seed_gives_the_same_data_and_another_seed_other_data(generated):
    again = watdiv.generate(CONFIG, SEED, 1)
    assert again["terms"] == generated["terms"]
    for col in "spo":
        assert np.array_equal(again[col], generated[col])
    assert again["domains"] == generated["domains"]
    other = watdiv.generate(CONFIG, SEED + 1, 1)
    assert len(other["s"]) != len(generated["s"]) or not np.array_equal(
        other["o"], generated["o"])
    assert other["domains"] == generated["domains"]  # every instance of a class


def test_the_classes_hold_the_models_numbers_and_the_predicates_are_86(generated):
    s, p, o = generated["s"], generated["p"], generated["o"]
    terms = generated["terms"]
    want = {"retailer": 12, "country": 25, "category": 15, "agegroup": 9,
            "subgenre": 145, "user": 1000, "topic": 250, "website": 50, "city": 240}
    assert {d: len(v) for d, v in generated["domains"].items()} == want
    assert set(want) == set(CONFIG["domains"])
    for domain, cls in CONFIG["domains"].items():
        assert generated["domains"][domain][-1] == (
            f"{watdiv.NAMESPACES['wsdbm']}{cls}{want[domain] - 1}")
    for cls, n in {**watdiv.SCALABLE, **watdiv.FIXED}.items():
        assert f"<{watdiv.NAMESPACES['wsdbm']}{cls}{n - 1}>" in terms
        assert f"<{watdiv.NAMESPACES['wsdbm']}{cls}{n}>" not in terms
    assert CONFIG["shapes"]["scalable_classes_at_scale_factor_1"] == watdiv.SCALABLE
    assert CONFIG["shapes"]["fixed_classes"] == watdiv.FIXED
    used = {terms[i] for i in np.unique(p)}
    assert used == {f"<{watdiv.iri(name)}>" for name in watdiv.PREDICATES}
    assert len(used) == CONFIG["shapes"]["predicates"] == 86
    assert len(set(terms)) == len(terms)
    assert len(np.unique((s << 40) | (p << 32) | o)) == len(s)  # every triple once
    assert 100_000 <= len(s) <= 115_000  # about 109,000 a scale factor
    counts = np.bincount(p)
    assert counts.argmax() == _pid(generated, "wsdbm:friendOf")  # the largest predicate
    assert all(t[0] in '<"' for t in terms)
    assert not any(t.startswith('"') and not t.endswith('"') for t in terms)  # plain


@pytest.mark.parametrize("predicate, by, scale", [
    # twelve retailers cannot differ twentyfold under a Zipfian of exponent 1
    # (the first against the sixth): read at 120 retailers
    ("gr:offers", "s", 10),
    ("og:tag", "o", 1), ("wsdbm:hasGenre", "o", 1), ("wsdbm:likes", "o", 1)])
def test_the_hottest_key_holds_twenty_times_the_median_keys_rows(
        generated, predicate, by, scale):
    data = generated if scale == 1 else watdiv.generate(CONFIG, SEED, scale)
    rows = data["p"] == _pid(data, predicate)
    sizes = np.unique(data[by][rows], return_counts=True)[1]
    assert sizes.max() >= 20 * np.median(sizes), (sizes.max(), np.median(sizes))


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


def _post(base, path, payload):
    req = urllib.request.Request(base + path, data=json.dumps(payload).encode(),
                                 headers={"Content-Type": "application/json"},
                                 method="POST")
    with urllib.request.urlopen(req, timeout=900) as resp:
        return json.loads(resp.read())


def _metric(prefix):
    return sum(float(line.rpartition(" ")[2])
               for line in obs_export.render_prometheus().splitlines()
               if line.startswith(prefix))


def _load(base, data):
    sid = None
    for text in bench_files.ntriples_chunks(data):  # the harness's chunks
        body = {"rdf": text, "format": "ntriples", "mode": "device"}
        if sid:
            body["store_id"] = sid
        got = _post(base, "/store/load", body)
        sid = got["store_id"]
    assert got["triples"] == len(data["s"])
    return sid


def _ask(base, sid, text):
    return _post(base, "/store/query", {"store_id": sid, "sparql": text,
                                        "deadline_ms": 900_000})["data"]


def _capacities(base, sid):
    """The store's block of ``/stats``: a record a template it has seen."""
    with urllib.request.urlopen(base + "/stats", timeout=60) as resp:
        return json.load(resp)["stores"][sid]["capacities"]["templates"]


def _instance(data, template, index):
    text = bench_files.template_text("watdiv_" + template)
    domain = TEMPLATES[template]
    if domain is None:
        return text
    return text.replace(f"@{domain}@", data["domains"][domain][index])


@pytest.fixture(scope="module")
def served(server, generated):
    _httpd, base = server
    return _load(base, generated), Reference(
        generated["terms"], generated["s"], generated["p"], generated["o"])


def test_the_traffic_file_cycles_the_twelve_stars_and_snowflakes():
    spec = bench_files.read_json("traffic", "stars_snowflakes.json")
    assert (spec["loop"], spec["clients"], spec["deadline_ms"], spec["warmup_cycles"],
            spec["warmup_ramp"], spec["trace_min_seconds"]) == (
        "closed", 1, 900000, 5, [1], 3)
    ids = [t for t in TEMPLATES if t[0] in "SF"]
    assert [step["template"] for step in spec["cycle"]] == ["watdiv_" + t for t in ids]
    for step, t in zip(spec["cycle"], ids):
        assert step["constants"] == {TEMPLATES[t]: {"draw": TEMPLATES[t]}}
        assert f"<@{TEMPLATES[t]}@>" in bench_files.template_text(step["template"])
    patterns = sum(bench_files.template_text("watdiv_" + t).count("\n    ") for t in ids)
    assert patterns == 66  # the scans of one cycle


@pytest.mark.parametrize("template", sorted(TEMPLATES))
def test_a_template_answers_as_the_reference_on_the_device_path(
        server, generated, served, template):
    _httpd, base = server
    sid, ref = served
    domain = TEMPLATES[template]
    picks = [0]
    if domain is not None:
        rng = np.random.default_rng([SEED, sorted(TEMPLATES).index(template)])
        picks += rng.integers(1, len(generated["domains"][domain]), 2).tolist()
    before = {path: _metric('kolibrie_query_seconds_count{path="%s"}' % path)
              for path in ("device", "degraded", "host")}
    for index in picks:
        text = _instance(generated, template, index)
        assert "@" not in text.split("WHERE")[1]
        rows = _ask(base, sid, text)
        want = ref.query(text)
        assert sorted(map(tuple, rows)) == sorted(map(tuple, want)), (template, index)
    grew = {path: _metric('kolibrie_query_seconds_count{path="%s"}' % path) - n
            for path, n in before.items()}
    assert grew == {"device": len(picks), "degraded": 0, "host": 0}


# ----------------------- (c) the warm-up's order does not decide the executable


@pytest.fixture(scope="module")
def skewed():
    """Scale factor 10: the hottest retailer and sub-genre answer S1 and F3
    with more rows than the 1,024-slot floor holds, the coldest with under a
    quarter of it: a template calibrated on a cold instance alone overflows
    at the hot one."""
    return watdiv.generate(CONFIG, SEED, 10)


@pytest.fixture(scope="module")
def two_stores(server, skewed):
    _httpd, base = server
    ref = Reference(skewed["terms"], skewed["s"], skewed["p"], skewed["o"])
    return {"cold_first": _load(base, skewed), "hot_first": _load(base, skewed)}, ref


@pytest.mark.parametrize("template, overflows", [
    ("S1", True), ("F5", True),
    # F3 and F2 would start from another scan at the hottest sub-genre (its
    # rows pass the sorg:contentRating or sorg:url scan's) if the planner
    # ordered by the constant's rows: it orders a keyed scan by its
    # predicate's hottest key, so every instance plans that one order
    ("F3", True), ("F2", False),
    # F1's og:tag has two kinds of subjects: a topic's rows under it (its
    # products) say nothing of the sub-genres it tags, whose products the
    # first join counts: the pass asks the join, not the scan
    ("F1", False)])
def test_the_first_instance_does_not_decide_a_templates_capacities(
        server, skewed, two_stores, template, overflows):
    _httpd, base = server
    stores, ref = two_stores
    domain = skewed["domains"][TEMPLATES[template]]
    rows = [len(ref.query(_instance(skewed, template, k))) for k in range(len(domain))]
    hot = int(np.argmax(rows))
    cold = min((k for k in range(len(domain)) if rows[k]), key=lambda k: rows[k])
    if overflows:  # beyond the floor, and beyond the cold instance's headroom
        assert rows[hot] > 1024 >= 4 * rows[cold] > 0
    caps = {}
    compiled0 = device_compile_stats()["run_plan"]
    retries0 = _metric('kolibrie_cap_retries_total{engine="device"}')
    for order, first in (("cold_first", cold), ("hot_first", hot)):
        sid = stores[order]
        known = _capacities(base, sid)  # the templates before this one
        assert len(_ask(base, sid, _instance(skewed, template, first))) == rows[first]
        (entry,) = [e for e in _capacities(base, sid) if e not in known]
        caps[order] = entry["caps"]
        for k in range(len(domain)):  # then every instance of the class
            assert len(_ask(base, sid, _instance(skewed, template, k))) == rows[k]
        (entry,) = [e for e in _capacities(base, sid) if e not in known]
        assert entry["caps"] == caps[order], order
    assert _metric('kolibrie_cap_retries_total{engine="device"}') == retries0
    # one capacity set and one join order whichever came first: one executable
    assert caps["cold_first"] == caps["hot_first"]
    assert device_compile_stats()["run_plan"] - compiled0 <= 1
    assert max(caps["cold_first"]) >= rows[hot]


def test_a_predicates_rows_are_found_in_either_order(server, skewed, two_stores):
    """What a hot-key pass of the calibration reads in place of one key's
    range: every row under the scan's predicate, whichever column leads."""
    httpd, _base = server
    db = httpd.RequestHandlerClass.state.stores[two_stores[0]["hot_first"]].db
    for predicate in ("gr:offers", "wsdbm:hasGenre", "og:tag"):
        pid = db.dictionary.lookup(watdiv.iri(predicate))
        want = int((skewed["p"] == _pid(skewed, predicate)).sum())
        found = []
        for name in ("pos", "ops", "pso", "spo"):
            got = predicate_rows(db.store.order(name), pid)
            assert len(got["s"]) == want and (got["p"] == pid).all(), (predicate, name)
            found.append(sorted(zip(got["s"].tolist(), got["o"].tolist())))
        assert all(rows == found[0] for rows in found)
    assert len(predicate_rows(db.store.order("pos"), 2**31 - 1)["s"]) == 0
    assert len(predicate_rows(db.store.order("spo"), 2**31 - 1)["s"]) == 0


def _lower(db, text):
    from kolibrie_tpu.optimizer.device_engine import lower_plan
    from kolibrie_tpu.optimizer.engine import resolve_pattern
    from kolibrie_tpu.optimizer.planner import Streamertail, build_logical_plan
    from kolibrie_tpu.query.parser import parse_sparql_query

    db.register_prefixes_from_query(text)
    w = parse_sparql_query(text, db.prefixes).where
    resolved = [resolve_pattern(db, p) for p in w.patterns]
    plan = Streamertail(db.get_or_build_stats()).find_best_plan(
        build_logical_plan(resolved, list(w.filters), [], None))
    return lower_plan(db, plan)


def test_a_template_assembles_one_spec_and_its_scans_run_a_quarter_full(
        server, skewed, two_stores):
    """ISSUE 41: a scan is compiled for the rows under the predicate it names,
    so over eight cycles of the cell's traffic each of the twelve templates
    still assembles one ``PlanSpec`` whatever the instance, no scan's range
    and no join's count (the numpy twin's) passes its capacity, and the
    cycle's scans hold over a quarter of their slots (under 2 % where every
    predicate-only scan is as wide as ``wsdbm:friendOf``)."""
    httpd, _base = server
    db = httpd.RequestHandlerClass.state.stores[two_stores[0]["cold_first"]].db
    traffic = Traffic("stars_snowflakes", skewed["domains"], SEED)
    specs, slots, rows = {}, 0, 0
    for k in range(8):
        for name, text in traffic.cycle(k):
            low = _lower(db, text)
            spec, _ = low.build(operands=False)
            specs.setdefault(name, set()).add(spec)
            counts = low.host_execute()[1]
            assert all(c <= cap for c, cap in zip(counts, low._join_caps)), text
            scans = [node.scan_idx for node in _spec_nodes(spec.root, ScanSpec)]
            held = low._scan_ranges_np[scans, 1::2].sum(axis=1)
            caps = np.array([low._scan_caps[i] for i in scans])
            assert (held <= caps).all(), text
            slots += int(caps.sum())
            rows += int(held.sum())
    assert {name: len(seen) for name, seen in specs.items()} == {
        "watdiv_" + t: 1 for t in TEMPLATES if t[0] in "SF"}
    friend_of = round_cap(int(np.bincount(skewed["p"]).max()) + db.store.delta_device_cap)
    # the 49 scans of a cycle that bind their predicate alone, at that width
    assert rows / slots > 0.25 and rows / (8 * 49 * friend_of) < 0.02, (rows, slots)


# ------------------------------------------------- (d) the two scan counters


def test_the_scan_counters_read_the_slots_and_rows_of_one_plan(server, generated, served):
    """S2: ``dc:Location`` and ``wsdbm:gender`` bind the predicate alone,
    ``sorg:nationality`` and ``rdf:type`` the object too."""
    httpd, base = server
    sid, _ref = served
    p, o = generated["p"], generated["o"]
    store = httpd.RequestHandlerClass.state.stores[sid].db.store
    dcap = store.delta_device_cap
    # a scan is compiled for the largest key-group of its order's bound
    # prefix among the rows under the predicate it names (ISSUE 41): the
    # predicate's rows, or those of its hottest object
    slots = sum(round_cap(int((p == _pid(generated, name)).sum()) + dcap)
                for name in ("dc:Location", "wsdbm:gender"))
    slots += sum(round_cap(int(np.unique(o[p == _pid(generated, name)],
                                         return_counts=True)[1].max()) + dcap)
                 for name in ("sorg:nationality", "rdf:type"))
    widest_p = np.bincount(p).max()  # wsdbm:friendOf, which S2 does not name
    assert slots < round_cap(widest_p + dcap)
    country = generated["domains"]["country"][3]
    cid = generated["terms"].index(f"<{country}>")
    role = generated["terms"].index(f"<{watdiv.NAMESPACES['wsdbm']}Role2>")
    rows = (int((p == _pid(generated, "dc:Location")).sum())
            + int((p == _pid(generated, "wsdbm:gender")).sum())
            + int(((p == _pid(generated, "sorg:nationality")) & (o == cid)).sum())
            + int(((p == _pid(generated, "rdf:type")) & (o == role)).sum()))
    assert 0 < rows < slots
    before = [_metric('kolibrie_device_scan_slots_total{engine="device"}'),
              _metric('kolibrie_device_scan_rows_total{engine="device"}')]
    _ask(base, sid, _instance(generated, "S2", 3))
    assert _metric('kolibrie_device_scan_slots_total{engine="device"}') - before[0] == slots
    assert _metric('kolibrie_device_scan_rows_total{engine="device"}') - before[1] == rows
    with open(os.path.join(REPO, "docs", "OBSERVABILITY.md"), encoding="utf-8") as f:
        catalog = f.read()
    assert "`kolibrie_device_scan_slots_total`" in catalog
    assert "`kolibrie_device_scan_rows_total`" in catalog
    assert "`device.calibrate`" in catalog
