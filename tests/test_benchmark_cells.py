"""The benchmark's cells as ``BENCHMARK.json`` lists them, checked without
JAX: the texts one client sends are what they were before there were clients,
the clients of ``mesh_q7`` never share a constant (in ``lubm5.mesh4`` and in
``lubm5.batch8``, which sends the same traffic to one chip), the cell
``lubm5.mesh4`` is in with the entries PR 27 wrote for it, ``lubm5.batch8``
as ISSUE 32 states it and ``lubm50.triangles`` (``lubm-50``, nothing cut) as
ISSUE 34 does, ISSUE 35's three range-search metrics are data files for the
two triangles cells, ``lubm50.lookups`` (``lookups`` against ``lubm-50``) and
the two join-search metrics are in as ISSUE 39 states them, ``watdiv-100``,
``watdiv100.stars_snowflakes`` and the two scan metrics as ISSUE 40 does,
``bsbm-10m``, ``bsbm10m.bi_counts`` and the seven aggregate metrics as ISSUE
42 does, ISSUE 45's three counts of the micro-batcher are data files for
every cell, ``lubm-50-clients8``, ``lubm50.mix8`` and the four metrics of a
dispatch's composition as ISSUE 47 does, ``lubm-50-mesh4``, ``lubm50.mesh4``,
its ``requires`` row and the six metrics of the mesh's merge, exchanges and
partition as ISSUE 50 does (eleven cells of nine configurations, two of four
chips; every cell and metric is found by its name, so that the
next one appended breaks none of these), every file a cell or a
per-layer metric names is there, a program that lacks what a cell
requires of it (``benchmark/requires``) is refused before anything starts,
and ``run.py`` itself, started off the chip, prints no result and exits 3
(a child process: this one stays off JAX).

Imports ``benchmark.harness`` (``traffic``, ``data``; numpy; the generators
are found by name through ``data.load_module``) and, of the program,
``kolibrie_tpu.obs.metrics`` and the modules that register the families it
reads (``core.store``, ``query.template``: neither imports JAX).
"""

import hashlib
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.harness import data as files  # noqa: E402
from benchmark.harness.traffic import Traffic  # noqa: E402

BENCH = files.read_json(os.pardir, "BENCHMARK.json")
CELLS = {w["name"]: w for w in BENCH["workloads"]}
# the scale the digests were recorded at: 1 university, 25,000 employees
ONE_CLIENT = {"lubm5.triangles": 1, "lubm5.lookups": 1, "employee100k.upstream": 25000}
DIGESTS = files.read_json("data", "traffic_digests.json")["digests"]
MESH4 = files.read_json("data", "lubm5.mesh4.entries.json")
CONFIGS = {c["name"]: c for c in BENCH["configs"]}
CELL_ORDER = [w["name"] for w in BENCH["workloads"]]
# appended after every cell it lists and born with them in its list (ISSUE
# 49): no standing list was edited to take a cell in
BUILD_PUTS = "build_puts_in_window"
CONFIG_ORDER = [c["name"] for c in BENCH["configs"]]


def per_layer_run(names):
    """The ``per_layer`` entries of ``names``, found by the first one's name:
    they stand together, in the order they were appended."""
    listed = [m["name"] for m in BENCH["per_layer"]]
    at = listed.index(list(names)[0])
    added = BENCH["per_layer"][at:at + len(names)]
    assert [m["name"] for m in added] == list(names)
    return added


def generated(workload, seed, scale):
    config = files.read_json("configs", CELLS[workload]["config"] + ".json")
    generator = files.load_module("generators", config["generator"])
    return generator.generate(config, seed, scale)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("workload", sorted(ONE_CLIENT))
def test_one_client_sends_the_texts_it_always_sent(workload, seed):
    data = generated(workload, seed, ONE_CLIENT[workload])
    traffic = Traffic(CELLS[workload]["traffic"], data["domains"], seed)
    assert traffic.clients == 1 and traffic.warmup_ramp == [1]
    h = hashlib.sha256()
    for stream, n in (("warmup", len(traffic.warmup_counts())), ("window", 8)):
        for k in range(n):
            for name, text in traffic.cycle(k, stream):
                h.update(f"{stream}\0{k}\0{name}\0{text}\0".encode())
    assert h.hexdigest() == DIGESTS[f"{workload}:{seed}"]


MESH_Q7_CELLS = ["lubm5.mesh4", "lubm5.batch8"]


@pytest.fixture(scope="module", params=MESH_Q7_CELLS)
def departments(request):
    assert CELLS[request.param]["traffic"] == "mesh_q7"
    return generated(request.param, 2**31 + 5, 1)["domains"]


@pytest.mark.parametrize("clients", [1, 2, 8])
def test_no_two_clients_of_mesh_q7_ever_draw_one_constant(
        departments, clients, monkeypatch):
    spec = files.read_json("traffic", "mesh_q7.json")
    assert spec["clients"] == 8 and spec["warmup_ramp"] == [1, 2, 4, 8]
    monkeypatch.setattr(
        files, "read_json",
        lambda *parts: dict(spec, clients=clients, warmup_ramp=[clients]))
    traffic = Traffic("mesh_q7", departments, 2**31 + 5)
    assert traffic.clients == clients
    domain = departments["department"]
    assert len(domain) >= 15  # a LUBM university has 15 to 25 departments
    for stream in ("window", "warmup"):
        # however far the clients drift apart: any cycle of one against any
        # cycle of another, over more cycles than a client has values
        own = [{traffic.cycle(k, stream, c)[0][1] for k in range(2 * len(domain))}
               for c in range(clients)]
        assert sum(len(o) for o in own) == len(set().union(*own)) == len(domain)


def test_benchmark_json_has_the_mesh_cell_as_its_entries_file_states_it():
    for key in ("configs", "workloads", "per_layer"):
        listed = {e["name"]: e for e in BENCH[key]}
        for entry in MESH4[key]:
            assert listed[entry["name"]] == entry, (key, entry["name"])
    cell = CELLS["lubm5.mesh4"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lubm-5-mesh4", "mesh_q7", 4)
    config = files.read_json("configs", "lubm-5-mesh4.json")
    assert config["chips"] == 4 and config["layout"]["partitions"] == 4
    assert files.read_json("workloads", "lubm5.mesh4.json") == {
        "env": {"KOLIBRIE_SHARDED": "1"}}
    # what PR 28 adds to the mesh's layer reads this cell and moves cycle_ms
    added = {m["name"]: m for m in BENCH["per_layer"]
             if m["name"] in ("shard_build_ms", "shard_wait_ms", "shard_merge_ms",
                              "shard_lone_in_window",
                              "shard_member_slots_in_window")}
    assert len(added) == 5
    for m in added.values():
        assert (m["layer"], m["moves"], m["workloads"]) == (
            "mesh serving", "cycle_ms", ["lubm5.mesh4"])


BATCH8_METRICS = {
    "executor_batch_ms": ("span_total", "one-chip batch"),
    "batch_dispatch_ms": ("span_total", "device dispatch"),
    "batch_device_wait_ms": ("span_total", "device dispatch"),
    "batch_dispatches_in_window": ("counter_delta", "one-chip batch"),
    "batch_members_in_window": ("counter_delta", "one-chip batch"),
    "batch_member_slots_in_window": ("counter_delta", "one-chip batch"),
    "batch_programs_in_window": ("counter_delta", "one-chip batch"),
}


def test_benchmark_json_has_the_one_chip_cell_of_eight_clients():
    cell = CELLS["lubm5.batch8"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lubm-5-clients8", "mesh_q7", 1)
    # appended, nothing before it moved
    assert CELL_ORDER[:CELL_ORDER.index(cell["name"])] == [
        "lubm5.triangles", "lubm5.lookups", "employee100k.upstream", "lubm5.mesh4"]
    config = files.read_json("configs", "lubm-5-clients8.json")
    one_client = files.read_json("configs", "lubm-5.json")
    assert (config["chips"], config["universities"], config["store_mode"]) == (
        1, 5, "device")
    assert "layout" not in config  # one chip holds the whole store
    # lubm-5's deployment asked by 8 clients: the same data, guarantees and
    # cut, and one guarantee more, which a group must not break
    for key in ("generator", "universities", "control", "reduced"):
        assert config[key] == one_client[key], key
    assert config["guarantees"].items() >= one_client["guarantees"].items()
    assert "whatever group" in config["guarantees"]["served_in_a_group"]
    assert config["assumed"][:-1] == one_client["assumed"]
    assert files.read_json("workloads", "lubm5.batch8.json") == {"env": {}}
    # its per-layer metrics: data files with readers that exist, listing
    # this cell alone, all moving cycle_ms
    added = {m["name"]: m for m in BENCH["per_layer"] if m["name"] in BATCH8_METRICS}
    assert sorted(added) == sorted(BATCH8_METRICS)
    for name, (kind, layer) in BATCH8_METRICS.items():
        m = added[name]
        assert (m["layer"], m["moves"], m["workloads"]) == (
            layer, "cycle_ms", ["lubm5.batch8"])
        assert files.read_json("layer_metrics", name + ".json")["reader"][
            "kind"] == kind
    # no standing metric's list was edited to take the cell in (ISSUE 39's
    # two came later, with the cell in the list they were born with)
    for m in BENCH["per_layer"]:
        if m["name"] not in {*BATCH8_METRICS, *JOIN_SEARCH_METRICS, BUILD_PUTS}:
            assert "lubm5.batch8" not in m.get("workloads", [])


LUBM50_METRICS = {
    # name: (reader kind, layer, the end-to-end metric it moves)
    "setup_tokenize_s": ("counter_at_open", "store and ingest", "setup_s"),
    "setup_intern_s": ("counter_at_open", "store and ingest", "setup_s"),
    "setup_compact_s": ("counter_at_open", "store and ingest", "setup_s"),
    "setup_h2d_mb": ("counter_at_open", "store and ingest", "setup_s"),
    "store_device_mb": ("counter_at_open", "store and ingest", "setup_s"),
    "wcoj_probes_in_window": ("counter_delta", "device dispatch", "cycle_ms"),
}


def test_benchmark_json_has_lubm_50_uncut_and_its_cell():
    cell = CELLS["lubm50.triangles"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lubm-50", "triangles", 1)
    # appended, nothing before it moved
    assert CELL_ORDER[CELL_ORDER.index(cell["name"]) - 1] == "lubm5.batch8"
    entry = CONFIGS["lubm-50"]
    assert CONFIG_ORDER[CONFIG_ORDER.index("lubm-50") - 1] == "lubm-5-clients8"
    assert (entry["name"], entry["file"], entry["reduced"]) == (
        "lubm-50", "benchmark/configs/lubm-50.json", [])
    assert "LUBM(50, seed), the largest the paper reports" in entry["source"]
    assert len(entry["source"]) <= 200 and len(cell["why"]) <= 200
    config = files.read_json("configs", "lubm-50.json")
    small = files.read_json("configs", "lubm-5.json")
    # the paper's own scale: nothing is cut, and everything else is lubm-5's
    assert (config["universities"], config["reduced"], config["chips"]) == (50, {}, 1)
    assert config["source"] == entry["source"]
    for key in ("generator", "store_mode", "guarantees", "control"):
        assert config[key] == small[key], key
    assert set(config) == set(small)
    assert len(config["assumed"]) == len(small["assumed"])
    assert config["assumed"][1] == small["assumed"][1]
    assert config["assumed"][3] == small["assumed"][3]
    assert "LUBM(50,0): 6,890,933" in config["assumed"][0]
    assert files.read_json("workloads", "lubm50.triangles.json") == {"env": {}}
    # the traffic is lubm5.triangles', as it stands
    assert CELLS["lubm5.triangles"]["traffic"] == cell["traffic"]
    # its per-layer metrics: data files of readers that exist, listing this
    # cell alone, in the order PR 34 appended them
    added = [m for m in BENCH["per_layer"] if m["name"] in LUBM50_METRICS]
    assert [m["name"] for m in added] == list(LUBM50_METRICS)
    for m in added:
        kind, layer, moves = LUBM50_METRICS[m["name"]]
        assert (m["layer"], m["moves"], m["workloads"], m["source"]) == (
            layer, moves, ["lubm50.triangles"], "program_counter")
        reader = files.read_json("layer_metrics", m["name"] + ".json")["reader"]
        assert reader["kind"] == kind
        assert os.path.exists(files.path("readers", kind + ".py"))
    # it is added to no list that was there; it reports what has no list
    for m in BENCH["per_layer"] + BENCH["end_to_end"]:
        if m["name"] not in {*LUBM50_METRICS, *RANGE_SEARCH_METRICS, *SEARCH_ROWS_METRICS,
                             BUILD_PUTS}:
            assert "lubm50.triangles" not in m.get("workloads", [])
        if m["name"] not in {*JOIN_SEARCH_METRICS, *SCAN_METRICS, BUILD_PUTS}:
            assert "lubm50.lookups" not in m.get("workloads", [])
    reported = {m["name"] for m in BENCH["end_to_end"] if "workloads" not in m}
    assert reported == {"cycle_ms", "setup_s"}


@pytest.mark.parametrize("name", sorted(LUBM50_METRICS))
def test_a_new_metric_reads_its_family_and_nothing_of_a_program_without_it(name):
    """The readers run on the parent's checkout too: where the program has no
    such counter or gauge the metric is left out and nothing raises."""
    from kolibrie_tpu.core import store  # noqa: F401  (registers its families)
    from kolibrie_tpu.obs import metrics

    args = dict(files.read_json("layer_metrics", name + ".json")["reader"])
    reader = files.load_module("readers", args.pop("kind"))
    sample = args.get("prefix") or args["prefixes"][0]
    family = sample[len("metrics."):].partition("{")[0]
    with open(os.path.join(REPO, "docs", "OBSERVABILITY.md"), encoding="utf-8") as f:
        assert f"`{family}`" in f.read()
    if family.startswith("kolibrie_store_"):
        assert metrics.REGISTRY.get(family) is not None
    there = {"counters0": {sample: 5e6, "metrics.kolibrie_other_total": 1.0},
             "counters1": {sample: 7e6, "metrics.kolibrie_other_total": 3.0}}
    want = 2e6 if name == "wcoj_probes_in_window" else 5e6 * args.get("scale", 1.0)
    assert reader.read(there, **args) == pytest.approx(want)
    lacking = {key: {"metrics.kolibrie_other_total": 1.0} for key in there}
    assert reader.read(lacking, **args) is None


RANGE_SEARCH_METRICS = {
    # name: (reader kind, layer, source, the reader's arguments)
    "sort_pct": ("trace_op_share", "kernels and XLA ops", "device_trace",
                 {"match": "^sort", "level": "top"}),
    "wcoj_sorted_searches_in_window": (
        "counter_delta", "device dispatch", "program_counter",
        {"prefix": 'metrics.kolibrie_wcoj_range_search_total{form="sorted"}',
         "beside": "metrics.kolibrie_wcoj_range_search_total"}),
    "wcoj_loop_searches_in_window": (
        "counter_delta", "device dispatch", "program_counter",
        {"prefix": 'metrics.kolibrie_wcoj_range_search_total{form="loop"}',
         "beside": "metrics.kolibrie_wcoj_range_search_total"}),
}
TRIANGLES_CELLS = ["lubm5.triangles", "lubm50.triangles"]


def test_the_range_search_metrics_stand_together_and_are_data_alone():
    """ISSUE 35: three per-layer entries, appended, for the two triangles
    cells; each a data file of a reader that was there.  (ISSUE 38 appended
    its seven behind them: tests/test_compile_first_sight.py.)"""
    added = per_layer_run(RANGE_SEARCH_METRICS)
    # behind ISSUE 34's last: nothing before them moved
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[names.index("sort_pct") - 1] == "wcoj_probes_in_window"
    for m in added:
        kind, layer, source, args = RANGE_SEARCH_METRICS[m["name"]]
        assert (m["layer"], m["moves"], m["workloads"], m["source"], m["unit"]) == (
            layer, "cycle_ms", TRIANGLES_CELLS, source,
            "%" if m["name"] == "sort_pct" else "count")
        reader = files.read_json("layer_metrics", m["name"] + ".json")["reader"]
        assert reader == {"kind": kind, **args}
        assert os.path.exists(files.path("readers", kind + ".py"))
    # no cell came with them, and no other list took a triangles cell in
    assert CELL_ORDER[CELL_ORDER.index("lubm50.triangles") - 1] == "lubm5.batch8"
    added_files = {name + ".json" for name in RANGE_SEARCH_METRICS}
    assert added_files <= set(os.listdir(files.path("layer_metrics")))


@pytest.mark.parametrize("name", sorted(RANGE_SEARCH_METRICS))
def test_a_range_search_metric_reads_its_source_and_nothing_of_a_program_without_it(name):
    """The readers run on the parent's checkout too: a program without the
    counter reports neither count and nothing raises; a label that has not
    grown yet reads 0 beside the one that has; a trace without a sort reads
    a share of 0, and no trace reads nothing."""
    from kolibrie_tpu.obs import metrics
    from kolibrie_tpu.query import template  # noqa: F401  (registers the family)

    kind, _layer, _source, args = RANGE_SEARCH_METRICS[name]
    reader = files.load_module("readers", kind)
    if kind == "trace_op_share":
        trace = {"busy_s": 4.0, "top": {"sort.12": 1.0, "while.3": 2.0, "resort.1": 0.5}}
        assert reader.read({"trace": trace}, **args) == pytest.approx(25.0)
        trace["top"] = {"while.3": 4.0}
        assert reader.read({"trace": trace}, **args) == 0.0
        assert reader.read({}, **args) is None
        return
    family = args["beside"][len("metrics."):]
    assert metrics.REGISTRY.get(family) is not None
    with open(os.path.join(REPO, "docs", "OBSERVABILITY.md"), encoding="utf-8") as f:
        assert f"`{family}`" in f.read()
    other = args["beside"] + ('{form="loop"}' if "sorted" in name else '{form="sorted"}')
    there = {"counters0": {args["prefix"]: 18.0, other: 4.0},
             "counters1": {args["prefix"]: 54.0, other: 4.0}}
    assert reader.read(there, **args) == pytest.approx(36.0)
    never_grew = {key: {other: 4.0} for key in there}
    assert reader.read(never_grew, **args) == 0.0
    lacking = {key: {"metrics.kolibrie_wcoj_probes_total": 1.0} for key in there}
    assert reader.read(lacking, **args) is None


JOIN_SEARCH_METRICS = {
    # name: the label of kolibrie_join_search_keys_total it reads
    "join_search_slots_in_window": "slots",
    "join_search_keys_in_window": "searched",
}
JOIN_SEARCH_CELLS = ["lubm5.lookups", "employee100k.upstream", "lubm5.batch8",
                     "lubm50.lookups"]


def test_benchmark_json_has_the_lookups_against_lubm_50_behind_its_triangles():
    """ISSUE 39: ``lookups`` as it stands against ``lubm-50`` as it stands,
    one chip, a data file beside the others; two per-layer entries appended,
    each a data file of a reader that was there; no standing list took the
    cell in, so it reports ``cycle_ms``, ``setup_s`` and what has no list."""
    cell = CELLS["lubm50.lookups"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lubm-50", "lookups", 1)
    assert CELL_ORDER[CELL_ORDER.index(cell["name"]) - 1] == "lubm50.triangles"
    assert len(cell["why"]) <= 200
    assert files.read_json("workloads", "lubm50.lookups.json") == {"env": {}}
    assert CELLS["lubm5.lookups"]["traffic"] == cell["traffic"]
    assert CELLS["lubm50.triangles"]["config"] == cell["config"]
    # no configuration came with it: lubm-50's next is ISSUE 40's
    assert CONFIG_ORDER[CONFIG_ORDER.index("lubm-50") + 1] == "watdiv-100"
    traffic = files.read_json("traffic", "lookups.json")
    assert (traffic["loop"], traffic["clients"], traffic["warmup_cycles"],
            traffic["deadline_ms"]) == ("closed", 1, 5, 900000)
    assert [step["template"] for step in traffic["cycle"]] == [
        "lubm_q1", "lubm_q3", "lubm_q4", "lubm_q7", "lubm_q8"]
    added = per_layer_run(JOIN_SEARCH_METRICS)
    for m in added:
        assert m == {"name": m["name"], "unit": "count", "better": "lower",
                     "source": "program_counter", "layer": "kernels and XLA ops",
                     "moves": "cycle_ms", "workloads": JOIN_SEARCH_CELLS}
        reader = files.read_json("layer_metrics", m["name"] + ".json")["reader"]
        assert reader == {
            "kind": "counter_delta",
            "prefix": 'metrics.kolibrie_join_search_keys_total{what="%s"}'
                      % JOIN_SEARCH_METRICS[m["name"]],
            "beside": "metrics.kolibrie_join_search_keys_total"}
        assert os.path.exists(files.path("readers", "counter_delta.py"))
    for m in BENCH["per_layer"] + BENCH["end_to_end"]:
        if m["name"] not in {*JOIN_SEARCH_METRICS, *SCAN_METRICS, BUILD_PUTS}:
            assert "lubm50.lookups" not in m.get("workloads", [])


@pytest.mark.parametrize("name", sorted(JOIN_SEARCH_METRICS))
def test_a_join_search_metric_reads_its_label_and_nothing_of_a_program_without_it(name):
    """The readers run on the parent's checkout too: a program without the
    family reports neither count and nothing raises; the program registers
    both labels at import, so each has a line from the start."""
    from kolibrie_tpu.obs import export, metrics
    from kolibrie_tpu.query import template  # noqa: F401  (registers the family)

    args = dict(files.read_json("layer_metrics", name + ".json")["reader"])
    reader = files.load_module("readers", args.pop("kind"))
    family = args["beside"][len("metrics."):]
    assert metrics.REGISTRY.get(family) is not None
    assert args["prefix"][len("metrics."):] + " " in export.render_prometheus()
    with open(os.path.join(REPO, "docs", "OBSERVABILITY.md"), encoding="utf-8") as f:
        assert f"`{family}`" in f.read()
    other = args["beside"] + ('{what="searched"}' if "slots" in name else '{what="slots"}')
    there = {"counters0": {args["prefix"]: 65536.0, other: 7.0},
             "counters1": {args["prefix"]: 196608.0, other: 9.0}}
    assert reader.read(there, **args) == pytest.approx(131072.0)
    lacking = {key: {"metrics.kolibrie_device_join_rows_total": 1.0} for key in there}
    assert reader.read(lacking, **args) is None


SCAN_METRICS = {
    # name: (the family it reads, better)
    "scan_slots_in_window": ("kolibrie_device_scan_slots_total", "lower"),
    "scan_rows_in_window": ("kolibrie_device_scan_rows_total", "higher"),
}
SCAN_CELLS = ["watdiv100.stars_snowflakes", "lubm50.lookups", "lubm5.lookups"]
# sha256 over the texts of the warm-up's 5 cycles and the window's first 8,
# at scale factor 1, by seed: what one client of ``stars_snowflakes`` sends
WATDIV_DIGESTS = {
    0: "d7ac5664482398b203829819ac784adc2415a67d418addcb426649b52db0a102",
    1: "6c492285a33f193be92b755e4f3d2ef10acaa383adc449c5cd12ac66eca630c2",
    2: "b1241029da2de97e1f4b0379f9316f463f36a5ae71131b2e00ab7ddc1be23418",
}


def test_benchmark_json_has_watdiv_100_uncut_and_its_cell():
    """ISSUE 40: one configuration, one cell of one chip, two per-layer
    entries, all appended; every file new; no standing list took the cell
    in, so it reports ``cycle_ms``, ``setup_s`` and what has no list.
    (ISSUE 42 appended its own behind them.)"""
    entry, cell = CONFIGS["watdiv-100"], CELLS["watdiv100.stars_snowflakes"]
    assert CELL_ORDER[CELL_ORDER.index(cell["name"]) - 1] == "lubm50.lookups"
    assert (entry["name"], entry["file"], entry["reduced"]) == (
        "watdiv-100", "benchmark/configs/watdiv-100.json", [])
    assert cell == {**cell, "name": "watdiv100.stars_snowflakes", "config": "watdiv-100",
                    "traffic": "stars_snowflakes", "chips": 1}
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert max(len(entry["source"]), len(entry["why"]), len(cell["why"])) <= 200
    config = files.read_json("configs", "watdiv-100.json")
    lubm = files.read_json("configs", "lubm-50.json")
    assert config["source"] == entry["source"] and "scale factor 100" in entry["source"]
    assert (config["scale_factor"], config["reduced"], config["chips"],
            config["store_mode"], config["generator"]) == (100, {}, 1, "device", "watdiv")
    for key in ("guarantees", "control"):  # lubm-50's, letter for letter
        assert config[key] == lubm[key], key
    assert sorted(config["domains"]) == [
        "agegroup", "category", "city", "country", "retailer", "subgenre", "topic",
        "user", "website"]
    assert len(config["assumed"]) == 14 and all(config["assumed"])
    assert files.read_json("workloads", cell["name"] + ".json") == {"env": {}}
    templates = sorted(f for f in os.listdir(files.path("templates"))
                       if f.startswith("watdiv_"))
    assert templates == sorted(
        f"watdiv_{kind}{k}.rq" for kind, n in (("L", 5), ("S", 7), ("F", 5), ("C", 3))
        for k in range(1, n + 1))
    need = files.read_json("requires", cell["name"] + ".json")
    assert (need["module"], need["registers"]) == (
        "kolibrie_tpu.query.template", "kolibrie_device_scan_slots_total")
    added = per_layer_run(SCAN_METRICS)
    for m in added:
        family, better = SCAN_METRICS[m["name"]]
        assert m == {"name": m["name"], "unit": "count", "better": better,
                     "source": "program_counter", "layer": "device dispatch",
                     "moves": "cycle_ms", "workloads": SCAN_CELLS}
        assert files.read_json("layer_metrics", m["name"] + ".json")["reader"] == {
            "kind": "counter_delta",
            "prefix": 'metrics.%s{engine="device"}' % family}
    for m in BENCH["per_layer"] + BENCH["end_to_end"]:
        if m["name"] not in {*SCAN_METRICS, BUILD_PUTS}:
            assert cell["name"] not in m.get("workloads", [])


@pytest.mark.parametrize("name", sorted(SCAN_METRICS))
def test_a_scan_metric_reads_its_family_and_nothing_of_a_program_without_it(name):
    """The readers run on the parent's checkout too: a program without the
    family reports neither count and nothing raises; the program registers
    the ``device`` engine's line at import."""
    from kolibrie_tpu.obs import export, metrics
    from kolibrie_tpu.query import template  # noqa: F401  (registers the family)

    args = dict(files.read_json("layer_metrics", name + ".json")["reader"])
    reader = files.load_module("readers", args.pop("kind"))
    assert metrics.REGISTRY.get(SCAN_METRICS[name][0]) is not None
    assert args["prefix"][len("metrics."):] + " " in export.render_prometheus()
    there = {"counters0": {args["prefix"]: 8388608.0, "metrics.kolibrie_other_total": 1.0},
             "counters1": {args["prefix"]: 25165824.0, "metrics.kolibrie_other_total": 3.0}}
    assert reader.read(there, **args) == pytest.approx(16777216.0)
    lacking = {key: {"metrics.kolibrie_device_cap_slots_total": 1.0} for key in there}
    assert reader.read(lacking, **args) is None


@pytest.mark.parametrize("seed", sorted(WATDIV_DIGESTS))
def test_one_client_of_stars_and_snowflakes_sends_these_texts(seed):
    data = generated("watdiv100.stars_snowflakes", seed, 1)
    traffic = Traffic("stars_snowflakes", data["domains"], seed)
    assert traffic.clients == 1 and traffic.warmup_ramp == [1]
    assert len(traffic.warmup_counts()) == 5
    h = hashlib.sha256()
    for stream, n in (("warmup", 5), ("window", 8)):
        for k in range(n):
            cycle = traffic.cycle(k, stream)
            assert len(cycle) == 12 and len({text for _, text in cycle}) == 12
            for name, text in cycle:
                h.update(f"{stream}\0{k}\0{name}\0{text}\0".encode())
    assert h.hexdigest() == WATDIV_DIGESTS[seed]


AGGREGATE_METRICS = {
    # name: (reader kind, reader arguments, unit, better, source, layer)
    "aggregate_ms": (
        "span_total", {"spans": ["device.aggregate"]},
        "ms", "lower", "program_span", "device dispatch"),
    "aggregate_slots_in_window": (
        "counter_delta", {"prefix": "metrics.kolibrie_device_aggregate_slots_total"},
        "count", "lower", "program_counter", "device dispatch"),
    "aggregate_rows_in_window": (
        "counter_delta", {"prefix": "metrics.kolibrie_device_aggregate_rows_total"},
        "count", "higher", "program_counter", "device dispatch"),
    "group_slots_in_window": (
        "counter_delta", {"prefix": "metrics.kolibrie_device_group_slots_total"},
        "count", "lower", "program_counter", "device dispatch"),
    "groups_in_window": (
        "counter_delta", {"prefix": "metrics.kolibrie_device_groups_total"},
        "count", "higher", "program_counter", "device dispatch"),
    "aggregate_retries_in_window": (
        "counter_delta", {"prefix": "metrics.kolibrie_aggregate_cap_retries_total"},
        "count", "lower", "program_counter", "device dispatch"),
    "host_aggregates_in_window": (
        "counter_delta", {"prefix": 'metrics.kolibrie_aggregate_total{tier="host"}',
                          "beside": "metrics.kolibrie_aggregate_total"},
        "count", "lower", "program_counter", "executor: decode and format"),
}
# sha256 over the texts of the warm-up's 5 cycles and the window's first 8,
# at 200 products, by seed: what one client of ``bi_counts`` sends
BSBM_DIGESTS = {
    0: "a5432a265084d9e188a557cb93ef71e83af47334b73cdc9350cd3789ca6b22b9",
    1: "bf105825b9eb4d552c0ac09b1376861d7d863b88ee451c0324d217c45681a04a",
    2: "fcc45400243f45bad3721007fb447e896a1e6206cd4847f81f729bb711be3634",
}


def test_benchmark_json_has_bsbm_10m_and_its_cell():
    """ISSUE 42: one configuration, one cell of one chip, seven per-layer
    entries that list this cell alone, all appended; every file new; no
    standing list took the cell in, so it reports ``cycle_ms``, ``setup_s``
    and what has no list."""
    entry, cell = CONFIGS["bsbm-10m"], CELLS["bsbm10m.bi_counts"]
    assert (entry["name"], entry["file"], entry["reduced"]) == (
        "bsbm-10m", "benchmark/configs/bsbm-10m.json", ["queries", "top_k"])
    assert cell == {**cell, "name": "bsbm10m.bi_counts", "config": "bsbm-10m",
                    "traffic": "bi_counts", "chips": 1}
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert max(len(entry["source"]), len(entry["why"]), len(cell["why"])) <= 200
    config = files.read_json("configs", "bsbm-10m.json")
    lubm = files.read_json("configs", "lubm-50.json")
    assert config["source"] == entry["source"]
    for words in ("BSBM V3.1", "Business Intelligence use case", "BI Q1, Q2, Q5"):
        assert words in entry["source"]
    assert (config["products"], config["chips"], config["store_mode"],
            config["generator"]) == (28480, 1, "device", "bsbm")
    assert sorted(config["reduced"]) == ["queries", "top_k"] and all(
        len(why) > 100 for why in config["reduced"].values())
    for key in ("guarantees", "control"):  # lubm-50's, letter for letter
        assert config[key] == lubm[key], key
    assert sorted(config["domains"]) == ["country1", "country2", "product", "producttype"]
    assert len(config["assumed"]) == 11 and all(config["assumed"])
    assert files.read_json("workloads", cell["name"] + ".json") == {"env": {}}
    templates = sorted(f for f in os.listdir(files.path("templates"))
                       if f.startswith("bsbm_"))
    assert templates == ["bsbm_bi_q1.rq", "bsbm_bi_q2.rq", "bsbm_bi_q5.rq"]
    need = files.read_json("requires", cell["name"] + ".json")
    assert (need["module"], need["registers"]) == (
        "kolibrie_tpu.query.template", "kolibrie_device_aggregate_slots_total")
    added = per_layer_run(AGGREGATE_METRICS)
    for m in added:
        kind, args, unit, better, source, layer = AGGREGATE_METRICS[m["name"]]
        assert m == {"name": m["name"], "unit": unit, "better": better,
                     "source": source, "layer": layer, "moves": "cycle_ms",
                     "workloads": [cell["name"]]}
        assert files.read_json("layer_metrics", m["name"] + ".json")["reader"] == {
            "kind": kind, **args}
        assert os.path.exists(files.path("readers", kind + ".py"))
    for m in BENCH["per_layer"] + BENCH["end_to_end"]:
        if m["name"] not in {*AGGREGATE_METRICS, BUILD_PUTS}:
            assert cell["name"] not in m.get("workloads", [])
    # the ninth cell came behind the eight, which stand as they stood
    assert CELL_ORDER[:CELL_ORDER.index(cell["name"])] == [
        "lubm5.triangles", "lubm5.lookups", "employee100k.upstream", "lubm5.mesh4",
        "lubm5.batch8", "lubm50.triangles", "lubm50.lookups",
        "watdiv100.stars_snowflakes"]


@pytest.mark.parametrize("name", sorted(AGGREGATE_METRICS))
def test_an_aggregate_metric_reads_its_source_and_nothing_of_a_program_without_it(name):
    """The readers run on the parent's checkout too: a program without the
    family (or the span) reports nothing and nothing raises; the tier's
    ``host`` line is registered at import, and reads 0 beside the family
    where it has not grown."""
    from kolibrie_tpu.obs import export, metrics
    from kolibrie_tpu.query import template  # noqa: F401  (registers the families)

    kind, args = AGGREGATE_METRICS[name][:2]
    reader = files.load_module("readers", kind)
    if kind == "span_total":
        span = {"name": "device.aggregate", "dur_ms": 7.5, "span_id": "a", "parent_id": ""}
        other = {"name": "device.collect", "dur_ms": 2.0, "span_id": "b", "parent_id": ""}
        ctx = {"cycles": [{"trace_ids": ["t0", "t1"]}],
               "spans_by_trace": {"t0": [span, other], "t1": [span]}}
        assert reader.read(ctx, **args) == pytest.approx(15.0)
        ctx["spans_by_trace"] = {"t0": [other], "t1": [other]}
        assert reader.read(ctx, **args) is None  # the parent opens no such span
        return
    family = args["prefix"][len("metrics."):].partition("{")[0]
    assert metrics.REGISTRY.get(family) is not None
    assert args["prefix"][len("metrics."):] + " " in export.render_prometheus()
    there = {"counters0": {args["prefix"]: 1048576.0, "metrics.kolibrie_other_total": 1.0},
             "counters1": {args["prefix"]: 3145728.0, "metrics.kolibrie_other_total": 3.0}}
    assert reader.read(there, **args) == pytest.approx(2097152.0)
    lacking = {key: {"metrics.kolibrie_device_scan_slots_total": 1.0} for key in there}
    assert reader.read(lacking, **args) is None
    if "beside" in args:
        device = 'metrics.kolibrie_aggregate_total{tier="device"}'
        never_grew = {key: {device: 4.0} for key in there}
        assert reader.read(never_grew, **args) == 0.0


@pytest.mark.parametrize("seed", sorted(BSBM_DIGESTS))
def test_one_client_of_bi_counts_sends_these_texts(seed):
    data = generated("bsbm10m.bi_counts", seed, 2)
    traffic = Traffic("bi_counts", data["domains"], seed)
    assert traffic.clients == 1 and traffic.warmup_ramp == [1]
    assert len(traffic.warmup_counts()) == 5
    h = hashlib.sha256()
    for stream, n in (("warmup", 5), ("window", 8)):
        for k in range(n):
            cycle = traffic.cycle(k, stream)
            assert [name for name, _ in cycle] == [
                "bsbm_bi_q1", "bsbm_bi_q2", "bsbm_bi_q5"]
            assert not any("@" in text.split("WHERE")[1] for _, text in cycle)
            for name, text in cycle:
                h.update(f"{stream}\0{k}\0{name}\0{text}\0".encode())
    assert h.hexdigest() == BSBM_DIGESTS[seed]


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_every_file_a_cell_names_is_there(workload):
    cell = CELLS[workload]
    config_entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert os.path.exists(os.path.join(REPO, config_entry["file"]))
    config = files.read_json("configs", cell["config"] + ".json")
    assert config.get("chips", 1) == cell["chips"]
    assert set(config_entry["reduced"]) == set(config["reduced"])
    files.load_module("generators", config["generator"])
    assert "env" in files.read_json("workloads", workload + ".json")
    traffic = files.read_json("traffic", cell["traffic"] + ".json")
    for step in traffic["cycle"]:
        assert files.template_text(step["template"])


def test_every_per_layer_metric_has_its_file_and_its_reader():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert len(names) == len(set(names))
    end_to_end = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        reader = files.read_json("layer_metrics", m["name"] + ".json")["reader"]
        assert os.path.exists(files.path("readers", reader["kind"] + ".py")), m
        assert m["moves"] in end_to_end
        assert set(m.get("workloads", CELLS)) <= set(CELLS)


def test_at_most_half_the_cells_take_four_chips():
    four = [w["name"] for w in BENCH["workloads"] if w["chips"] == 4]
    assert four == ["lubm5.mesh4", "lubm50.mesh4"] and len(CELLS) == 11
    assert len(BENCH["configs"]) == 9
    assert len(four) <= max(1, len(CELLS) // 2)
    assert json.dumps(BENCH).count('"chips": 4') == 2


# ---- what a cell requires of the program (``benchmark/requires``)

def test_every_requirement_belongs_to_a_cell_and_names_a_documented_metric():
    with open(os.path.join(REPO, "docs", "OBSERVABILITY.md"), encoding="utf-8") as f:
        catalog = f.read()
    found = sorted(os.listdir(files.path("requires")))
    assert found == ["bsbm10m.bi_counts.json", "lubm5.batch8.json",
                     "lubm5.mesh4.json", "lubm50.mesh4.json",
                     "watdiv100.stars_snowflakes.json"]
    for name in found:
        assert name[:-len(".json")] in CELLS
        need = files.read_json("requires", name)
        assert set(need) == {"module", "registers", "why"}
        assert os.path.exists(
            os.path.join(REPO, *need["module"].split(".")) + ".py")
        assert f"`{need['registers']}`" in catalog


@pytest.mark.parametrize("registered", [True, False])
@pytest.mark.parametrize(
    "family", ["kolibrie_test_required_total"] + [
        files.read_json("requires", cell + ".json")["registers"]
        for cell in ("lubm5.batch8", "lubm5.mesh4", "watdiv100.stars_snowflakes",
                     "bsbm10m.bi_counts", "lubm50.mesh4")])
def test_a_program_without_the_required_metric_is_refused_at_once(
        tmp_path, monkeypatch, registered, family):
    """Each cell's own family too, asked of a program whose registry is
    empty or holds it (the module is the registry's, so this stays off JAX)."""
    from benchmark import harness
    from kolibrie_tpu.obs import metrics

    for folder, body in (
            ("requires", {"module": "kolibrie_tpu.obs.metrics",
                          "registers": family,
                          "why": "a test"}),
            ("workloads", {"env": {"KOLIBRIE_TEST_REQUIRES": "1"}})):
        (tmp_path / folder).mkdir()
        (tmp_path / folder / "a.cell.json").write_text(json.dumps(body))
    monkeypatch.setenv("KOLIBRIE_TEST_REQUIRES", "0")
    monkeypatch.setattr(metrics, "REGISTRY", metrics.Registry())
    if registered:
        metrics.REGISTRY.counter(family, "a test")
        harness._requires("a.cell", str(tmp_path))
        assert os.environ["KOLIBRIE_TEST_REQUIRES"] == "1"
    else:
        with pytest.raises(SystemExit, match="cannot run cell a.cell"):
            harness._requires("a.cell", str(tmp_path))
    harness._requires("a.cell.without.the.file", str(tmp_path))  # requires nothing


@pytest.mark.parametrize("workload", ["employee100k.upstream", "lubm5.batch8",
                                      "watdiv100.stars_snowflakes",
                                      "bsbm10m.bi_counts", "lubm50.mix8"])
def test_off_the_chip_run_py_prints_no_result_and_exits_3(tmp_path, workload):
    """A number from a CPU run is never written as a result: without a TPU
    ``benchmark/run.py`` says on standard error what it found, prints
    nothing on standard output and exits 3 (``NO_CHIP_EXIT``).  For
    ``lubm5.batch8`` that also says this program passed what the cell
    requires of it (``benchmark/requires``): a program that does not is
    refused with exit 1 before it looks for a chip."""
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    env.pop("KOLIBRIE_BENCH_REHEARSAL_SCALE", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", workload, "--seconds", "1"],
        cwd=str(tmp_path), env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 3, proc.stderr[-2000:]
    assert proc.stdout.strip() == ""
    assert "needs 1 TPU chip(s)" in proc.stderr


BATCHER_COUNTS = {
    # name: (reader arguments, better)
    "batcher_arrival_starts_in_window": (
        {"prefix": 'metrics.kolibrie_batcher_dispatch_start_total{at="arrival"}',
         "beside": "metrics.kolibrie_batcher_dispatch_start_total"}, "higher"),
    "batcher_dispatches_in_window": (
        {"prefix": "metrics.kolibrie_batcher_dispatches_total"}, "lower"),
    "batcher_requests_in_window": (
        {"prefix": "metrics.kolibrie_batcher_requests_total"}, "higher"),
}


@pytest.mark.parametrize("name", sorted(BATCHER_COUNTS))
def test_a_batcher_count_is_a_data_file_that_every_cell_reports(name):
    """ISSUE 45: three entries appended behind ISSUE 42's seven, no list of
    cells (every cell has a batcher), each a file of ``counter_delta``; a
    program without the family (the parent has no ``dispatch_start``) reports
    nothing and nothing raises, one whose dispatches all left by hand-off
    reads 0 arrivals.  ``tests/test_batcher_dispatch.py`` reads them off the
    program's own registry."""
    args, better = BATCHER_COUNTS[name]
    added = per_layer_run(BATCHER_COUNTS)
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[names.index(added[0]["name"]) - 1] == "host_aggregates_in_window"
    assert added[sorted(BATCHER_COUNTS).index(name)] == {
        "name": name, "unit": "count", "better": better, "source": "program_counter",
        "layer": "micro-batcher", "moves": "cycle_ms"}
    assert files.read_json("layer_metrics", name + ".json") == {
        "reader": {"kind": "counter_delta", **args}}
    reader = files.load_module("readers", "counter_delta")
    there = {"counters0": {args["prefix"]: 7.0}, "counters1": {args["prefix"]: 107.0}}
    assert reader.read(there, **args) == pytest.approx(100.0)
    lacking = {key: {"metrics.kolibrie_batcher_shed_total": 1.0} for key in there}
    assert reader.read(lacking, **args) is None
    if "beside" in args:
        handoff = 'metrics.kolibrie_batcher_dispatch_start_total{at="handoff"}'
        assert reader.read({key: {handoff: 4.0} for key in there}, **args) == 0.0


# ---- ISSUE 47: LUBM(50) asked by eight workers running the lookup mix

MIX8_METRICS = {
    # name: (reader arguments, layer, source, unit, the cells it lists)
    "batcher_templates_in_window": (
        {"kind": "counter_delta",
         "prefix": "metrics.kolibrie_batcher_dispatch_templates_total"},
        "micro-batcher", "program_counter", "count", None),
    "batcher_group_programs_in_window": (
        {"kind": "counter_delta",
         "prefix": 'metrics.kolibrie_batcher_dispatch_programs_total{kind="group"}',
         "beside": "metrics.kolibrie_batcher_dispatch_programs_total"},
        "micro-batcher", "program_counter", "count", None),
    "batcher_solo_programs_in_window": (
        {"kind": "counter_delta",
         "prefix": 'metrics.kolibrie_batcher_dispatch_programs_total{kind="solo"}',
         "beside": "metrics.kolibrie_batcher_dispatch_programs_total"},
        "micro-batcher", "program_counter", "count", None),
    "solo_tail_ms": (
        {"kind": "span_total", "spans": ["executor.solo_tail"]},
        "one-chip batch", "program_span", "ms", ["lubm50.mix8"]),
}


def test_benchmark_json_has_lubm_50_asked_by_eight_workers_and_its_cell():
    """One configuration, one traffic mix, one cell of one chip and four
    per-layer entries, all appended; every file new; no standing list took
    the cell in, so it reports ``cycle_ms``, ``setup_s`` and what has no
    list, and ``solo_tail_ms``, which was born with it."""
    entry, cell = CONFIGS["lubm-50-clients8"], CELLS["lubm50.mix8"]
    assert CONFIG_ORDER[CONFIG_ORDER.index(entry["name"]) - 1] == "bsbm-10m"
    assert CELL_ORDER[CELL_ORDER.index(cell["name"]) - 1] == "bsbm10m.bi_counts"
    assert (entry["file"], entry["reduced"]) == (
        "benchmark/configs/lubm-50-clients8.json", [])
    assert cell == {**cell, "config": "lubm-50-clients8",
                    "traffic": "lookups_clients8", "chips": 1}
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert max(len(entry["source"]), len(entry["why"]), len(cell["why"])) <= 200
    for words in ("LUBM(50", "Q1, Q3, Q4, Q7, Q8", "BSBM", "4, 8, 64"):
        assert words in entry["source"]
    assert len({c["source"] for c in BENCH["configs"]}) == len(BENCH["configs"])
    config = files.read_json("configs", "lubm-50-clients8.json")
    one_client = files.read_json("configs", "lubm-50.json")
    small = files.read_json("configs", "lubm-5-clients8.json")
    assert config["source"] == entry["source"]
    assert (config["universities"], config["reduced"], config["chips"],
            config["store_mode"]) == (50, {}, 1, "device")
    assert "layout" not in config  # one chip holds the whole store
    # lubm-50's deployment asked by 8 clients: the same data, control and
    # assumptions, its guarantees and the one a dispatch must not break
    for key in ("generator", "universities", "control", "reduced"):
        assert config[key] == one_client[key], key
    assert config["guarantees"].items() >= one_client["guarantees"].items()
    assert set(config["guarantees"]) == set(small["guarantees"])
    served = config["guarantees"]["served_in_a_group"]
    assert "its own text" in served and "other templates' groups" in served
    assert config["assumed"][:-1] == one_client["assumed"]
    assert "BSBM's" in config["assumed"][-1]
    assert files.read_json("workloads", "lubm50.mix8.json") == {"env": {}}
    assert not os.path.exists(files.path("requires", "lubm50.mix8.json"))
    # the traffic: lookups' cycle, letter for letter, sent by eight
    traffic = files.read_json("traffic", "lookups_clients8.json")
    assert traffic["cycle"] == files.read_json("traffic", "lookups.json")["cycle"]
    assert {k: v for k, v in traffic.items() if k != "cycle"} == {
        "loop": "closed", "clients": 8, "deadline_ms": 900000,
        "warmup_ramp": [1, 2, 4, 8], "warmup_cycles": 2, "trace_min_seconds": 10}
    # its per-layer entries: data files of readers that were there
    added = per_layer_run(MIX8_METRICS)
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[names.index(added[0]["name"]) - 1] == "batcher_requests_in_window"
    for m in added:
        args, layer, source, unit, cells = MIX8_METRICS[m["name"]]
        want = {"name": m["name"], "unit": unit, "better": "lower",
                "source": source, "layer": layer, "moves": "cycle_ms"}
        assert m == (want if cells is None else {**want, "workloads": cells})
        assert files.read_json("layer_metrics", m["name"] + ".json") == {
            "reader": args}
        assert os.path.exists(files.path("readers", args["kind"] + ".py"))
    for m in BENCH["per_layer"] + BENCH["end_to_end"]:
        if m["name"] not in ("solo_tail_ms", BUILD_PUTS):
            assert cell["name"] not in m.get("workloads", [])


@pytest.mark.parametrize("clients", [1, 2, 8])
def test_no_two_clients_of_the_mix_ever_draw_one_constant(clients, monkeypatch):
    """At eight universities, the fewest that give each of eight clients a
    university of its own (the cell has 50): no two clients ever send one
    text, in any step, however far they drift apart."""
    config = files.read_json("configs", "lubm-50-clients8.json")
    domains = files.load_module("generators", config["generator"]).generate(
        config, 2**31 + 47, 8)["domains"]
    assert len(domains["university"]) == 8 and len(domains["department"]) >= 8 * 15
    spec = files.read_json("traffic", "lookups_clients8.json")
    monkeypatch.setattr(
        files, "read_json",
        lambda *parts: dict(spec, clients=clients, warmup_ramp=[clients]))
    traffic = Traffic("lookups_clients8", domains, 2**31 + 47)
    assert traffic.clients == clients
    for stream in ("window", "warmup"):
        for step, (_template, _text, constants) in enumerate(traffic.steps):
            (rule,) = constants.values()
            domain = domains[rule["draw"]]
            own = [{traffic.cycle(k, stream, c)[step][1]
                    for k in range(2 * len(domain) // clients + 2)}
                   for c in range(clients)]
            assert sum(len(o) for o in own) == len(set().union(*own)) == len(domain)


@pytest.mark.parametrize("name", sorted(MIX8_METRICS))
def test_a_dispatch_composition_metric_reads_its_family_and_nothing_of_a_program_without_it(name):
    """The readers run on the parent's checkout too: a program without the
    families (or the span) reports nothing and nothing raises; both kinds of
    program are registered at import, so each has a line from the start and
    one that never grew reads 0."""
    from kolibrie_tpu.frontends import http_server  # noqa: F401  (registers them)
    from kolibrie_tpu.obs import export, metrics

    args = dict(MIX8_METRICS[name][0])
    reader = files.load_module("readers", args.pop("kind"))
    with open(os.path.join(REPO, "docs", "OBSERVABILITY.md"), encoding="utf-8") as f:
        catalog = f.read()
    if "spans" in args:
        assert "`executor.solo_tail`" in catalog
        span = {"name": "executor.solo_tail", "dur_ms": 30.5, "span_id": "a",
                "parent_id": ""}
        other = {"name": "executor.batch", "dur_ms": 2.0, "span_id": "b",
                 "parent_id": ""}
        ctx = {"cycles": [{"trace_ids": ["t0", "t1"]}],
               "spans_by_trace": {"t0": [span, other], "t1": [span]}}
        assert reader.read(ctx, **args) == pytest.approx(61.0)
        ctx["spans_by_trace"] = {"t0": [other], "t1": [other]}
        assert reader.read(ctx, **args) is None  # the parent opens no such span
        return
    family = args["prefix"][len("metrics."):].partition("{")[0]
    assert metrics.REGISTRY.get(family) is not None and f"`{family}`" in catalog
    assert args["prefix"][len("metrics."):] + " " in export.render_prometheus()
    there = {"counters0": {args["prefix"]: 40.0, "metrics.kolibrie_other_total": 1.0},
             "counters1": {args["prefix"]: 100.0, "metrics.kolibrie_other_total": 3.0}}
    assert reader.read(there, **args) == pytest.approx(60.0)
    # the parent has the batcher's older counters and neither new family
    lacking = {key: {"metrics.kolibrie_batcher_dispatches_total": 9.0,
                     'metrics.kolibrie_batcher_dispatch_start_total{at="arrival"}': 9.0}
               for key in there}
    assert reader.read(lacking, **args) is None
    if "beside" in args:
        other_kind = args["beside"] + (
            '{kind="solo"}' if "group" in name else '{kind="group"}')
        assert reader.read({key: {other_kind: 4.0} for key in there}, **args) == 0.0


SEARCH_ROWS_FAMILY = "metrics.kolibrie_wcoj_range_search_rows_total"
SEARCH_ROWS_METRICS = {
    # name: the reader's arguments (ISSUE 48)
    "wcoj_search_rows_in_window": {"prefix": SEARCH_ROWS_FAMILY},
    "wcoj_order_wide_rows_in_window": {
        "prefix": SEARCH_ROWS_FAMILY + '{extent="order"}', "beside": SEARCH_ROWS_FAMILY},
}


def test_the_search_rows_metrics_are_data_alone_for_the_triangles_cells():
    """ISSUE 48: two per-layer entries, appended behind ISSUE 47's last, for
    the two triangles cells; each a data file of ``counter_delta``; no cell,
    no configuration and no reader came with them."""
    added = per_layer_run(SEARCH_ROWS_METRICS)
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[names.index(added[0]["name"]) - 1] == "solo_tail_ms"
    for m in added:
        assert m == {"name": m["name"], "unit": "rows", "better": "lower",
                     "source": "program_counter", "layer": "device dispatch",
                     "moves": "cycle_ms", "workloads": TRIANGLES_CELLS}
        reader = files.read_json("layer_metrics", m["name"] + ".json")["reader"]
        assert reader == {"kind": "counter_delta", **SEARCH_ROWS_METRICS[m["name"]]}
    assert CELL_ORDER[-2] == "lubm50.mix8" and CONFIG_ORDER[-2] == "lubm-50-clients8"


@pytest.mark.parametrize("name", sorted(SEARCH_ROWS_METRICS))
def test_a_search_rows_metric_reads_its_extents_and_nothing_of_a_program_without_them(name):
    """The readers run on the parent's checkout too, which has the searches'
    counter by form and none of their rows: it reports neither metric and
    nothing raises.  ``wcoj_search_rows_in_window`` sums both extents;
    ``wcoj_order_wide_rows_in_window`` reads the whole orders' alone, 0 where
    only windows were searched (a label without growth has no line)."""
    from kolibrie_tpu.obs import metrics
    from kolibrie_tpu.query import template  # noqa: F401  (registers the family)

    args = SEARCH_ROWS_METRICS[name]
    reader = files.load_module("readers", "counter_delta")
    family = SEARCH_ROWS_FAMILY[len("metrics."):]
    assert metrics.REGISTRY.get(family) is not None
    with open(os.path.join(REPO, "docs", "OBSERVABILITY.md"), encoding="utf-8") as f:
        assert f"`{family}`" in f.read()
    window, order = (SEARCH_ROWS_FAMILY + '{extent="%s"}' % e for e in ("window", "order"))
    both = {"counters0": {window: 100.0, order: 7.0},
            "counters1": {window: 2_476_100.0, order: 1_048_583.0}}
    assert reader.read(both, **args) == pytest.approx(
        1_048_576.0 if "order" in name else 3_524_576.0)
    windows_only = {"counters0": {window: 100.0}, "counters1": {window: 2_476_100.0}}
    assert reader.read(windows_only, **args) == pytest.approx(
        0.0 if "order" in name else 2_476_000.0)
    parent = {key: {'metrics.kolibrie_wcoj_range_search_total{form="sorted"}': 15.0}
              for key in both}
    assert reader.read(parent, **args) is None


BUILD_PUTS_FAMILY = "metrics.kolibrie_device_build_puts_total"
ONE_CHIP_CELLS = [w["name"] for w in BENCH["workloads"] if w["chips"] == 1]


def test_the_build_puts_metric_is_data_alone_for_the_one_chip_cells():
    """ISSUE 49: one per-layer entry, appended behind ISSUE 48's two, for the
    nine cells of one chip (the mesh's groups make no ``LoweredPlan``); a data
    file of ``counter_delta`` on the family, both kinds summed; no cell, no
    configuration and no reader came with it."""
    (m,) = per_layer_run([BUILD_PUTS])
    at = BENCH["per_layer"].index(m)
    assert BENCH["per_layer"][at - 1]["name"] == "wcoj_order_wide_rows_in_window"
    assert BENCH["per_layer"][at + 1]["name"] == "shard_merged_rows_in_window"
    assert len(ONE_CHIP_CELLS) == 9 and "lubm5.mesh4" not in ONE_CHIP_CELLS
    assert m == {"name": BUILD_PUTS, "unit": "count", "better": "lower",
                 "source": "program_counter", "layer": "device dispatch",
                 "moves": "cycle_ms", "workloads": ONE_CHIP_CELLS}
    assert files.read_json("layer_metrics", BUILD_PUTS + ".json") == {
        "reader": {"kind": "counter_delta", "prefix": BUILD_PUTS_FAMILY}}
    assert CELL_ORDER[-2] == "lubm50.mix8" and CONFIG_ORDER[-2] == "lubm-50-clients8"


@pytest.mark.parametrize("program", ["change", "first_uploads", "parent"])
def test_the_build_puts_metric_reads_both_kinds_and_nothing_of_a_program_without_them(
        program):
    """The reader runs on the parent's checkout too, which has no such
    family: it reports nothing there and nothing raises.  Both label
    children exist from import, so a window in which no build made a device
    array reads 0, not nothing."""
    from kolibrie_tpu.obs import metrics
    from kolibrie_tpu.optimizer import device_engine  # noqa: F401  (registers the family)

    reader = files.load_module("readers", "counter_delta")
    family = BUILD_PUTS_FAMILY[len("metrics."):]
    assert metrics.REGISTRY.get(family) is not None
    with open(os.path.join(REPO, "docs", "OBSERVABILITY.md"), encoding="utf-8") as f:
        assert f"`{family}`" in f.read()
    transfer, compute = (BUILD_PUTS_FAMILY + '{what="%s"}' % w
                         for w in ("transfer", "compute"))
    ctx, want = {
        "change": ({"counters0": {transfer: 12.0, compute: 3.0},
                    "counters1": {transfer: 12.0, compute: 3.0}}, 0.0),
        "first_uploads": ({"counters0": {transfer: 12.0, compute: 3.0},
                           "counters1": {transfer: 18.0, compute: 4.0}}, 7.0),
        "parent": ({key: {"metrics.kolibrie_batcher_requests_total": 500.0}
                    for key in ("counters0", "counters1")}, None),
    }[program]
    got = reader.read(ctx, prefix=BUILD_PUTS_FAMILY)
    assert got is None if want is None else got == pytest.approx(want)


# ---- ISSUE 50: LUBM(50) over four chips, the five-query lookup mix

MESH_CELLS = ["lubm5.mesh4", "lubm50.mesh4"]
MESH_MIX_METRICS = {
    "shard_merged_rows_in_window": (
        "rows", "higher", "cycle_ms",
        {"kind": "counter_delta",
         "prefix": "metrics.kolibrie_shard_merged_rows_total"}),
    "shard_merged_mb_in_window": (
        "MB", "lower", "cycle_ms",
        {"kind": "counter_delta",
         "prefix": "metrics.kolibrie_shard_merged_bytes_total", "scale": 1e-06}),
    "shard_exchange_rows_in_window": (
        "rows", "higher", "cycle_ms",
        {"kind": "counter_delta",
         "prefix": "metrics.kolibrie_shard_exchange_rows_total"}),
    "shard_exchange_slots_in_window": (
        "slots", "lower", "cycle_ms",
        {"kind": "counter_delta",
         "prefix": "metrics.kolibrie_shard_exchange_slots_total"}),
    "mesh_base_rebuilds_in_setup": (
        "count", "lower", "setup_s",
        {"kind": "counter_at_open",
         "prefixes": ["metrics.kolibrie_shard_base_rebuilds_total"]}),
    "setup_mesh_partition_s": (
        "s", "lower", "setup_s",
        {"kind": "counter_at_open",
         "prefixes": ["metrics.kolibrie_shard_partition_seconds_total"]}),
}


def test_benchmark_json_has_lubm_50_over_four_chips_and_its_cell():
    """One configuration and one cell of four chips, appended last; the
    traffic is ``lubm50.mix8``'s file, letter for letter; the configuration
    is ``lubm-50-clients8``'s deployment laid out as ``lubm-5-mesh4``'s, with
    the mesh's share an equality; no standing list took the cell in."""
    entry, cell = CONFIGS["lubm-50-mesh4"], CELLS["lubm50.mesh4"]
    assert CONFIG_ORDER[-1] == entry["name"] and CELL_ORDER[-1] == cell["name"]
    assert (entry["file"], entry["reduced"]) == (
        "benchmark/configs/lubm-50-mesh4.json", ["universities", "pod"])
    assert cell == {**cell, "config": "lubm-50-mesh4",
                    "traffic": "lookups_clients8", "chips": 4}
    assert CELLS["lubm50.mix8"]["traffic"] == cell["traffic"]
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert max(len(entry["source"]), len(entry["why"]), len(cell["why"])) <= 200
    for words in ("LUBM(50, seed)", "Q1, Q3, Q4, Q7, Q8", "BSBM", "8 ",
                  "BASELINE.md configuration 5", "4 chips"):
        assert words in entry["source"], words
    for words in ("8 free clients", "Q1, Q3, Q4, Q7, Q8", "1.98 M rows a shard",
                  "10,000-row", "host merge", "lubm50.mix8", "lubm5.mesh4"):
        assert words in cell["why"], words
    assert len({c["source"] for c in BENCH["configs"]}) == len(BENCH["configs"])
    config = files.read_json("configs", "lubm-50-mesh4.json")
    one_chip = files.read_json("configs", "lubm-50-clients8.json")
    small = files.read_json("configs", "lubm-5-mesh4.json")
    assert config["source"] == entry["source"]
    assert (config["universities"], config["chips"], config["store_mode"]) == (
        50, 4, "device")
    for key in ("generator", "universities", "control", "assumed", "store_mode"):
        assert config[key] == one_chip[key], key
    assert config["guarantees"].items() >= one_chip["guarantees"].items()
    assert "served_in_a_group" in config["guarantees"]
    assert sorted(config["reduced"]) == sorted(entry["reduced"])
    assert "1,000 -> 50" in config["reduced"]["universities"]
    assert "not by memory" in config["reduced"]["universities"]
    assert "eighth" in config["reduced"]["universities"]
    assert "v5e-8" in config["reduced"]["pod"] and "4 chips" in config["reduced"]["pod"]
    # the layout is lubm-5-mesh4's, brought up to what the program does
    assert set(config["layout"]) >= set(small["layout"])
    assert config["layout"]["partitions"] == small["layout"]["partitions"] == 4
    assert config["layout"]["exchange"] == small["layout"]["exchange"]
    assert config["layout"]["served_by_the_mesh_at_least"] == 1.0
    assert "serves nothing in a correct run" in config["layout"]["full_copy"]
    assert "once" in config["layout"]["partitioned_when"]
    assert files.read_json("workloads", "lubm50.mesh4.json") == {
        "env": {"KOLIBRIE_SHARDED": "1"}} == files.read_json(
            "workloads", "lubm5.mesh4.json")
    # no standing list took the cell in: it reports what has no list and
    # the six metrics born with it
    listed = [m["name"] for m in BENCH["per_layer"]
              if "lubm50.mesh4" in m.get("workloads", [])]
    assert listed == list(MESH_MIX_METRICS)
    assert "lubm50.mesh4" not in next(
        m for m in BENCH["end_to_end"] if m["name"] == "latency_p95_ms")["workloads"]


def test_the_cell_requires_the_merge_counters_of_the_program():
    from kolibrie_tpu.obs import metrics
    from kolibrie_tpu.parallel import sharded_serving  # noqa: F401

    need = files.read_json("requires", "lubm50.mesh4.json")
    assert (need["module"], need["registers"]) == (
        "kolibrie_tpu.parallel.sharded_serving",
        "kolibrie_shard_merged_rows_total")
    assert metrics.REGISTRY.get(need["registers"]) is not None
    for words in ("39", "re-partition", "before 7.9 M triples are generated"):
        assert words in need["why"], words


def test_the_six_mesh_metrics_are_data_alone_for_the_two_mesh_cells():
    added = per_layer_run(MESH_MIX_METRICS)
    assert BENCH["per_layer"][-len(added):] == added
    with open(os.path.join(REPO, "docs", "OBSERVABILITY.md"), encoding="utf-8") as f:
        catalog = f.read()
    for m in added:
        unit, better, moves, reader = MESH_MIX_METRICS[m["name"]]
        assert m == {"name": m["name"], "unit": unit, "better": better,
                     "source": "program_counter", "layer": "mesh serving",
                     "moves": moves, "workloads": MESH_CELLS}
        assert files.read_json("layer_metrics", m["name"] + ".json") == {
            "reader": reader}
        family = (reader.get("prefix") or reader["prefixes"][0])[len("metrics."):]
        assert f"`{family}`" in catalog


@pytest.mark.parametrize("name", sorted(MESH_MIX_METRICS))
def test_a_mesh_mix_metric_reads_its_counter_and_nothing_of_a_program_without_it(
        name):
    """The readers run on the parent's checkout too, which registers none of
    the six families: it reports none of the metrics and nothing raises."""
    reader_args = dict(MESH_MIX_METRICS[name][3])
    reader = files.load_module("readers", reader_args.pop("kind"))
    key = reader_args.get("prefix") or reader_args["prefixes"][0]
    scale = reader_args.get("scale", 1.0)
    change = {"counters0": {key: 3.0}, "counters1": {key: 10.0}}
    want = 3.0 if "at_open" in MESH_MIX_METRICS[name][3]["kind"] else 7.0
    assert reader.read(change, **reader_args) == pytest.approx(want * scale)
    parent = {"counters0": {"metrics.kolibrie_shard_queries_total": 3.0},
              "counters1": {"metrics.kolibrie_shard_queries_total": 9.0}}
    assert reader.read(parent, **reader_args) is None


def test_the_selftest_counts_two_of_eleven_cells_on_four_chips(capsys):
    from benchmark.harness import selftest

    problems = []
    selftest.check_files(problems)
    assert problems == []
    four = [w["name"] for w in BENCH["workloads"] if w["chips"] == 4]
    assert (len(four), len(BENCH["workloads"])) == (2, 11)
    assert len(four) <= max(1, len(BENCH["workloads"]) // 2) == 5
