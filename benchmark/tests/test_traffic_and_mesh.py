"""Tests of the traffic with more than one client and of the four-chip cell.

Run with ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``.

* with one client the harness sends the texts it sent before there were
  clients (a digest recorded from the parent commit's ``Traffic``);
* with eight clients no two clients ever send the same constant, however far
  they drift apart, cycle *k* is the same whatever the run's length, two
  seeds differ, and the warm-up goes through the ramp;
* clients that run free wait for no one, all send the same number of cycles,
  and ``cycle_ms`` and ``latency_p95_ms`` over their recorded run;
* a rehearsal of ``lubm5.mesh4`` (not a cell of ``BENCHMARK.json`` yet:
  ``mesh4_entries.py``) on four virtual CPU devices answers as the reference
  does and passes the mesh's proof; with the cell's environment emptied the
  proof fails; with one answer altered the comparison fails.
"""

import hashlib
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness import data as files  # noqa: E402
from benchmark.harness import e2e, loadgen  # noqa: E402
from benchmark.harness.traffic import Traffic  # noqa: E402
from benchmark.tests import mesh4_entries  # noqa: E402

ACCEPTED = {"lubm5.triangles": 1, "lubm5.lookups": 1, "employee100k.upstream": 25000}
DIGESTS = files.read_json("data", "traffic_digests.json")["digests"]
DEPARTMENTS = {"department": [f"<http://d{i}>" for i in range(21)]}


def one_client_digest(workload, seed, scale):
    bench = files.read_json(os.pardir, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    config = files.read_json("configs", cell["config"] + ".json")
    data = files.load_module("generators", config["generator"]).generate(
        config, seed, scale)
    traffic = Traffic(cell["traffic"], data["domains"], seed)
    assert traffic.clients == 1 and traffic.warmup_ramp == [1]
    h = hashlib.sha256()
    for stream, n in (("warmup", len(traffic.warmup_counts())), ("window", 8)):
        for k in range(n):
            for name, text in traffic.cycle(k, stream):
                h.update(f"{stream}\0{k}\0{name}\0{text}\0".encode())
    return h.hexdigest()


@pytest.mark.parametrize("workload", sorted(ACCEPTED))
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_one_client_sends_the_texts_it_sent_before_there_were_clients(workload, seed):
    assert one_client_digest(workload, seed, ACCEPTED[workload]) == DIGESTS[
        f"{workload}:{seed}"]


def constants(traffic, k, stream="window", n=None):
    return [traffic.cycle(k, stream, c)[0][1] for c in range(n or traffic.clients)]


def test_no_two_of_eight_clients_ever_send_the_same_constant():
    a = Traffic("mesh_q7", DEPARTMENTS, 2**31 + 3)
    assert a.clients == 8 and a.warmup_ramp == [1, 2, 4, 8]
    assert a.warmup_counts() == [1, 2, 4, 8]
    for stream in ("window", "warmup"):
        # 21 departments: five clients own three of them and three own two,
        # whatever the cycle and however often an order is reshuffled
        own = [{a.cycle(k, stream, c)[0][1] for k in range(40)} for c in range(8)]
        assert sorted(len(o) for o in own) == [2, 2, 2, 3, 3, 3, 3, 3]
        assert len(set().union(*own)) == sum(len(o) for o in own) == 21
        # a client has walked all of its own before it sends one again
        for c in range(8):
            for start in range(0, 36, len(own[c])):
                walk = [a.cycle(k, stream, c)[0][1]
                        for k in range(start, start + len(own[c]))]
                assert set(walk) == own[c]
    # cycle k is the same texts whatever else was asked for before it
    fresh = Traffic("mesh_q7", DEPARTMENTS, 2**31 + 3)
    assert constants(fresh, 17) == constants(a, 17)
    # a warm-up cycle of fewer clients sends the first of the full cycle's
    assert constants(a, 3, "warmup", 2) == constants(a, 3, "warmup")[:2]
    b = Traffic("mesh_q7", DEPARTMENTS, 4)
    assert [constants(a, k) for k in range(4)] != [constants(b, k) for k in range(4)]
    assert constants(a, 0, "warmup") != constants(a, 0)


def test_a_domain_shorter_than_the_clients_is_walked_whole():
    few = Traffic("mesh_q7", {"department": ["<http://a>", "<http://b>", "<http://c>"]}, 1)
    assert len({t for k in range(3) for t in constants(few, k)}) == 3


def test_a_ramp_that_does_not_end_in_clients_is_refused(monkeypatch):
    spec = files.read_json("traffic", "mesh_q7.json")
    monkeypatch.setattr(files, "read_json",
                        lambda *parts: dict(spec, warmup_ramp=[1, 2, 4]))
    with pytest.raises(ValueError, match="warmup_ramp"):
        Traffic("mesh_q7", DEPARTMENTS, 1)
    monkeypatch.setattr(files, "read_json", lambda *parts: dict(spec, loop="open"))
    with pytest.raises(NotImplementedError):
        Traffic("mesh_q7", DEPARTMENTS, 1)


def test_selftest_names_a_bad_ramp_and_a_four_chip_cell_too_many(monkeypatch):
    from benchmark.harness import selftest

    problems = []
    monkeypatch.setattr(files, "read_json", mesh4_entries.read_json)
    selftest.check_files(problems)
    assert problems == []

    def altered(*parts):
        got = mesh4_entries.read_json(*parts)
        if parts[-1] == "mesh_q7.json":
            return dict(got, warmup_ramp=[1, 2, 4])
        if parts[-1] == "BENCHMARK.json":
            cells = [dict(w, chips=4 if w["name"] == "lubm5.lookups" else w["chips"])
                     for w in got["workloads"] if w["name"] != "employee100k.upstream"]
            return dict(got, workloads=cells)
        return got

    monkeypatch.setattr(files, "read_json", altered)
    selftest.check_files(problems)
    text = "\n".join(problems)
    assert "mesh_q7: warmup_ramp [1, 2, 4] does not end in clients 8" in text
    assert "2 of 3 cells take four chips" in text
    assert "lubm5.lookups: chips 4, but its configuration states 1" in text


class RecordedClient:
    """Answers after the recorded time."""

    def __init__(self, waits_ms):
        self.waits_ms = list(waits_ms)

    def query(self, store_id, text, trace_id):
        ms = self.waits_ms.pop(0)
        time.sleep(ms / 1000.0)
        return 200, b'{"data": []}', ms


def three_clients(monkeypatch, recorded):
    spec = dict(files.read_json("traffic", "mesh_q7.json"), clients=3, warmup_ramp=[3])
    spec["cycle"] = spec["cycle"] * 2  # two steps a client
    monkeypatch.setattr(files, "read_json", lambda *parts: spec)
    return (Traffic("mesh_q7", DEPARTMENTS, 5),
            [RecordedClient(recorded[c]) for c in range(3)])


def test_clients_that_run_free_wait_for_no_one_and_a_cycle_is_n_client_cycles(
        monkeypatch):
    # (ms of step 0, step 1) a cycle: client 0 takes 50 ms a cycle, client 1
    # 100, client 2 250
    recorded = {0: [25, 25] * 20, 1: [50, 50] * 20, 2: [100, 150] * 20}
    traffic, clients = three_clients(monkeypatch, recorded)
    cycles = loadgen.free_run(traffic, clients, "window", 0.78)
    sent = [r for _, _, records in cycles for r in records]
    by_client = {c: [r for r in sent if r["client"] == c] for c in range(3)}
    # each client stops for itself: the last cycle it starts is the last
    # that would end inside 780 ms: 15, 7 and 3 cycles where a sleep is
    # exact, a few less where it is not
    n_cycles = [len(by_client[c]) // 2 for c in range(3)]
    assert 10 <= n_cycles[0] <= 15 and 5 <= n_cycles[1] <= 7 and 2 <= n_cycles[2] <= 3
    texts = [{r["text"] for r in by_client[c]} for c in range(3)]
    assert not (texts[0] & texts[1] or texts[0] & texts[2] or texts[1] & texts[2])
    for c in range(3):
        assert [r["trace_id"] for r in by_client[c]] == [
            f"bench-window-{k}-{i}-{c}" for k in range(n_cycles[c]) for i in range(2)]
    # no barrier: client 0 has ended four cycles (200 ms) before client 2 has
    # ended its first (250 ms), so the first whole cycle is none of client 2's
    assert {r["client"] for r in cycles[0][2]} == {0, 1}
    # a whole cycle is 3 client-cycles in the order they ended; what is left
    # over comes last and is no cycle
    total = sum(n_cycles)
    left = total % 3
    assert len(cycles) == total // 3 + bool(left)
    assert all(len(records) == 6 and t1 for _, t1, records in cycles[:total // 3])
    if left:
        assert cycles[-1][1] is None and len(cycles[-1][2]) == 2 * left
    whole = [{"k": k, "t0": t0, "t1": t1, "ms": (t1 - t0) * 1000.0}
             for k, (t0, t1, _) in enumerate(cycles[:total // 3])]
    assert all(a["t1"] <= b["t1"] for a, b in zip(whole, whole[1:]))
    run = {"cycles": whole, "requests": sent}
    # all the whole cycles over all their time: where every sleep is exact, 8
    # in 750 ms; it is the mean of no client's cycle (50, 100 and 250 ms)
    assert e2e.cycle_ms(run) == pytest.approx(
        (whole[-1]["t1"] - whole[0]["t0"]) * 1000.0 / len(whole))
    assert 85 < e2e.cycle_ms(run) < 130
    every = [ms for c in recorded for ms in recorded[c][:len(by_client[c])]]
    assert e2e.latency_p95_ms(run) == pytest.approx(float(np.percentile(every, 95)))


def test_the_clients_of_a_warm_up_cycle_start_together_and_it_ends_with_the_last(
        monkeypatch):
    traffic, clients = three_clients(
        monkeypatch, {0: [20, 30], 1: [40, 10], 2: [60, 50]})
    t0, t1, sent = loadgen.send_cycle(traffic, clients, 0, "warmup", 3)
    assert (t1 - t0) * 1000.0 == pytest.approx(110, abs=15)
    assert [(r["client"], r["trace_id"]) for r in sent] == [
        (c, f"bench-warmup-0-{i}-{c}") for c in range(3) for i in range(2)]
    assert max(r["wall"] for r in sent if r["trace_id"].split("-")[3] == "0") - min(
        r["wall"] for r in sent) < 0.015
    # one client is the calling thread itself
    t0, t1, sent = loadgen.send_cycle(traffic, [RecordedClient([5, 5])], 1, "warmup", 1)
    assert [r["trace_id"] for r in sent] == ["bench-warmup-1-0-0", "bench-warmup-1-1-0"]


def rehearse(mode, seed, seconds=5):
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "tests", "mesh_rehearsal.py"),
         "lubm5.mesh4", str(seed), str(seconds), mode],
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(line) for line in p.stdout.splitlines() if line.startswith("{")]
    checks = {c["check"]: c for c in lines if "check" in c}
    return lines[-1], checks


MESH_ATTACHED = ("store_attached_to_a_mesh_over_the_chips", "shard_attach_errors",
                 "mirror_arrays_on_distinct_devices", "shard_fallbacks_in_window")
MESH_SHARE = "window_requests_served_by_the_mesh"


def test_mesh_rehearsal_answers_as_the_reference_and_the_mesh_serves_what_meets():
    result, checks = rehearse("sound", 2**31 + 11)
    assert result["failed"] == 0 and checks["wrong_or_failed_answers"]["ok"]
    assert result["attempted"] >= 16
    assert result["device"]["count"] == 4
    assert result["metrics"]["cycle_ms"]["value"] > 0
    assert "latency_p95_ms" not in result["metrics"]
    for name in MESH_ATTACHED:
        assert checks[name]["ok"], checks[name]
    # the clients run free, so which requests meet in the batcher is the
    # program's doing: a request that meets no other is served alone from
    # device 0, and where more than a tenth are the run is not correct
    # (7 + 1 a cycle, 14 of 16, in the rehearsals of PR 27)
    served, limit = checks[MESH_SHARE]["value"], result["attempted"] * 0.9
    assert checks[MESH_SHARE]["limit"] == f">={limit:g}"
    assert served >= result["attempted"] / 2
    assert checks[MESH_SHARE]["ok"] == (served >= limit)
    assert result["correct"] == checks[MESH_SHARE]["ok"]
    assert result["exit_code"] == (0 if result["correct"] else 1)


def test_mesh_rehearsal_without_the_cells_environment_fails_the_meshs_proof():
    result, checks = rehearse("no_env", 7)
    assert not result["correct"] and result["exit_code"] == 1
    # one chip answered, and answered rightly: only the proof says so
    assert result["failed"] == 0 and checks["wrong_or_failed_answers"]["ok"]
    assert not checks["store_attached_to_a_mesh_over_the_chips"]["ok"]
    assert not checks["mirror_arrays_on_distinct_devices"]["ok"]
    assert not checks[MESH_SHARE]["ok"] and checks[MESH_SHARE]["value"] == 0


def test_mesh_rehearsal_with_an_answer_altered_is_not_correct():
    result, checks = rehearse("tamper", 8)
    assert not result["correct"] and result["failed"] == 1
    assert not checks["wrong_or_failed_answers"]["ok"]
    for name in MESH_ATTACHED:
        assert checks[name]["ok"], checks[name]
