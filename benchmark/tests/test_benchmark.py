"""Tests of the benchmark itself, at a size a test run can hold (CPU).

Run with ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``.  They are
not part of the repo's tier-1 suite (``tests/``).

* the plain reference agrees with the program's host engine;
* the control -- the reference answering from a store that lacks 1 % of the
  acknowledged triples -- comes out not correct, on several seeds;
* a sound run, with the look for a chip waived, is ``correct``; the same run
  with the timed path's answer altered where the client receives it is not;
* off the chip, the command prints no result and exits non-zero;
* the generator keeps UBA's shape, the traffic walks each domain in a
  shuffled order, and ``cycle_ms`` holds the time between cycles.
"""

import os
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness import compare, e2e, loadgen, runner  # noqa: E402
from benchmark.harness import data as files  # noqa: E402
from benchmark.harness.traffic import Traffic  # noqa: E402
from benchmark.reference.sparql_subset import Reference  # noqa: E402
from benchmark.tests import mesh4_entries  # noqa: E402

ONE_CHIP = {"lubm5.triangles": 1, "lubm5.lookups": 1, "employee100k.upstream": 25000}
# the four-chip cell is not in BENCHMARK.json yet (mesh4_entries.py); its run
# is rehearsed in a process of its own (test_traffic_and_mesh.py), its
# reference and control are held here
CELLS = dict(ONE_CHIP, **{"lubm5.mesh4": 1})
CHIP_LOOK = frozenset({"platform_is_tpu", "pallas_enabled_not_interpreted",
                       "scale_as_configured"})


def _cell(workload, seed, scale):
    bench = mesh4_entries.bench()
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    config = files.read_json("configs", cell["config"] + ".json")
    data = files.load_module("generators", config["generator"]).generate(
        config, seed, scale)
    return config, data, Traffic(cell["traffic"], data["domains"], seed)


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_reference_agrees_with_the_host_engine(workload):
    from kolibrie_tpu.query.executor import execute_query_volcano
    from kolibrie_tpu.query.sparql_database import SparqlDatabase

    _, data, traffic = _cell(workload, 11, CELLS[workload])
    db = SparqlDatabase()
    for text in files.ntriples_chunks(data):
        db.parse_ntriples(text)
    db.execution_mode = "host"
    ref = Reference(data["terms"], data["s"], data["p"], data["o"])
    for _, text in traffic.cycle(0) + traffic.cycle(1, client=traffic.clients - 1):
        want = compare.multiset(execute_query_volcano(text, db))
        assert sum(want.values()) > 0
        assert compare.multiset(ref.query(text)) == want


@pytest.mark.parametrize("workload", sorted(CELLS))
@pytest.mark.parametrize("seed", [3, 2**31 + 5, 77])
def test_control_comes_out_not_correct(workload, seed):
    config, data, traffic = _cell(workload, seed, CELLS[workload])
    requests = [{"template": name, "text": text, "status": 200, "body": b""}
                for k in range(20)
                for name, text in traffic.cycle(k, client=k % traffic.clients)]
    out = loadgen._compare(config, data, seed, requests, control=True)
    assert len(out["wrong"]) == len(requests)  # empty bodies: all wrong
    assert out["control"]["texts_answered_wrongly"] > 0
    assert not out["control"]["control_correct"]


def test_lubm_generator_keeps_ubas_shape():
    """Counts per department inside UBA's ranges, data changing with the
    seed, about 10^5 asserted triples a university."""
    _, data, _ = _cell("lubm5.triangles", 2**31 + 1, 1)
    terms = np.array(data["terms"], object)
    ub = "<http://swat.cse.lehigh.edu/onto/univ-bench.owl#%s>"
    p_type = data["terms"].index(
        "<http://www.w3.org/1999/02/22-rdf-syntax-ns#type>")

    def count(cls):
        return int(((data["p"] == p_type)
                    & (data["o"] == data["terms"].index(ub % cls))).sum())

    depts, faculty = count("Department"), count("Faculty")
    assert 15 <= depts <= 25
    assert 30 * depts <= faculty <= 42 * depts
    assert 8 * faculty <= count("UndergraduateStudent") <= 14 * faculty
    assert 3 * faculty <= count("GraduateStudent") <= 4 * faculty
    assert count("Student") == count("UndergraduateStudent") + count("GraduateStudent")
    closure = sum(count(c) for c in ("Professor", "Faculty", "Student", "Person"))
    closure += count("GraduateCourse")
    assert 5500 * depts <= len(data["s"]) - closure <= 8500 * depts
    assert len(set(zip(data["s"], data["p"], data["o"]))) == len(data["s"])
    _, other, _ = _cell("lubm5.triangles", 2**31 + 2, 1)
    assert len(other["s"]) != len(data["s"]) or (other["o"] != data["o"]).any()
    assert terms[data["s"]].tolist()  # every id has a term


def test_traffic_walks_each_domain_in_a_shuffled_order():
    domains = {"department": [f"d{i}" for i in range(7)],
               "university": [f"u{i}" for i in range(5)]}
    a, b = Traffic("lookups", domains, 2**31 + 3), Traffic("lookups", domains, 4)
    for stream in ("warmup", "window"):
        for step, domain in ((0, "department"), (4, "university")):
            n = len(domains[domain])
            texts = [a.cycle(k, stream)[step][1] for k in range(2 * n)]
            # every value once before any comes twice, in both halves
            assert len(set(texts[:n])) == n and len(set(texts[n:])) == n
    assert a.cycle(3) == Traffic("lookups", domains, 2**31 + 3).cycle(3)
    assert [a.cycle(k) for k in range(5)] != [b.cycle(k) for k in range(5)]
    assert a.cycle(0, "warmup") != a.cycle(0, "window") or a.cycle(1, "warmup") != a.cycle(1)


def test_cycle_ms_holds_the_time_between_cycles():
    cycles = [{"t0": 10.0, "t1": 10.4, "ms": 400.0}, {"t0": 10.6, "t1": 11.0, "ms": 400.0}]
    assert e2e.cycle_ms({"cycles": cycles}) == pytest.approx(500.0)
    assert e2e.cycle_ms({"cycles": []}) is None


@pytest.mark.parametrize("workload", sorted(ONE_CHIP))
def test_sound_run_is_correct_and_broken_answers_are_not(workload):
    def run(tamper=None):
        result, code = runner.run_cell(
            workload, 2**31 + 9, 1.0, False, time.perf_counter(),
            scale=ONE_CHIP[workload], waive=CHIP_LOOK, tamper=tamper)
        return result, code

    result, code = run()
    assert result["correct"] and code == 0 and result["failed"] == 0
    assert result["metrics"]["cycle_ms"]["value"] > 0

    for tamper in ((1, "alter_value"), (0, "drop_row")):
        result, code = run(tamper)
        assert not result["correct"] and code == 1 and result["failed"] == 1


def test_off_the_chip_no_result_and_non_zero_exit():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("KOLIBRIE_BENCH_REHEARSAL_SCALE", None)
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"), "--workload",
         "employee100k.join", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
