"""``run.py --selftest``: the harness checks itself, on the CPU, in seconds.

1. The answer comparison passes the reference's own answer and fails on a
   dropped row, an altered value and a non-200.
2. The trace reduction gives the known busy, window and per-op totals on the
   small recorded trace kept under ``benchmark/data``.
3. Every file ``BENCHMARK.json`` names exists and every name and unit uses
   only the allowed characters; a traffic file's ``clients`` and
   ``warmup_ramp`` agree; a cell's ``chips`` is 1 or 4 and its
   configuration's, and at most half the cells (one always) take four.
"""

import json
import os
import re

from . import compare, xplane
from . import data as files
from .traffic import ramp_of

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def check_comparison(problems):
    from benchmark.reference.sparql_subset import Reference

    config = files.read_json("configs", "lubm-5.json")
    data = files.load_module("generators", config["generator"]).generate(
        config, seed=5, scale=1)
    ref = Reference(data["terms"], data["s"], data["p"], data["o"])
    text = files.template_text("lubm_q9")
    rows = ref.query(text)

    def verdict(rows, status=200):
        body = json.dumps({"data": rows}).encode()
        bad, _ = compare.wrong_answers(
            [{"text": text, "status": status, "body": body}], ref.query)
        return not bad

    altered = [list(r) for r in rows]
    altered[len(rows) // 2][1] += "x"
    cases = {"the reference's own answer": (verdict(rows), True),
             "a dropped row": (verdict(rows[:-1]), False),
             "an altered value": (verdict(altered), False),
             "a doubled row": (verdict(rows + rows[:1]), False),
             "HTTP 503": (verdict(rows, 503), False)}
    if len(rows) < 100:
        problems.append(f"comparison: reference answer has only {len(rows)} rows")
    for what, (got, want) in cases.items():
        if got != want:
            problems.append(f"comparison: {what} came out {'equal' if got else 'wrong'}")


def check_trace_reduction(problems):
    want = files.read_json("data", "fixture_expected.json")
    got = xplane.reduce(xplane.load(files.path("data", "fixture.xplane.pb")))
    for key in ("devices", "busy_s", "window_s"):
        if abs(got[key] - want[key]) > 1e-9:
            problems.append(f"trace reduction: {key} {got[key]!r} != {want[key]!r}")
    for level in ("top", "any", "self"):
        for name, seconds in want[level].items():
            if abs(got[level].get(name, 0.0) - seconds) > 1e-9:
                problems.append(f"trace reduction: {level}[{name}] "
                                f"{got[level].get(name)!r} != {seconds!r}")
    if abs(sum(got["self"].values()) - got["busy_s"]) > 1e-9:
        problems.append("trace reduction: self times do not sum to busy")
    gaps = sum(d for _, d in got["gaps"]) / 1e9
    if abs(gaps + got["busy_s"] - got["window_s"]) > 1e-9:
        problems.append("trace reduction: busy + gaps != window")


def check_files(problems):
    bench = files.read_json(os.pardir, "BENCHMARK.json")

    def need(*parts):
        if not os.path.exists(files.path(*parts)):
            problems.append("missing file benchmark/" + "/".join(parts))
            return False
        return True

    def name_ok(kind, value, rx=NAME):
        if not rx.match(value):
            problems.append(f"{kind} {value!r} has characters outside the contract's")

    for c in bench["configs"]:
        name_ok("config", c["name"])
        for key in c["reduced"]:
            name_ok("reduced key", key)
        if need("configs", c["name"] + ".json"):
            conf = files.read_json("configs", c["name"] + ".json")
            need("generators", conf["generator"] + ".py")
    cells = set()
    for w in bench["workloads"]:
        cells.add(w["name"])
        for key in ("name", "config", "traffic"):
            name_ok("workload " + key, w[key])
        need("workloads", w["name"] + ".json")
        if need("traffic", w["traffic"] + ".json"):
            spec = files.read_json("traffic", w["traffic"] + ".json")
            for step in spec["cycle"]:
                need("templates", step["template"] + ".rq")
            try:
                ramp_of(w["traffic"], spec)
            except ValueError as e:
                problems.append(str(e))
        if w["chips"] not in (1, 4):
            problems.append(f"cell {w['name']}: chips {w['chips']} is not 1 or 4")
        if os.path.exists(files.path("configs", w["config"] + ".json")):
            stated = files.read_json("configs", w["config"] + ".json").get("chips")
            if stated != w["chips"]:
                problems.append(f"cell {w['name']}: chips {w['chips']}, but its "
                                f"configuration states {stated}")
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    if len(four) > max(1, len(bench["workloads"]) // 2):
        problems.append(f"{len(four)} of {len(bench['workloads'])} cells take four "
                        f"chips, more than half: {four}")
    e2e_names = {m["name"] for m in bench["end_to_end"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        name_ok("metric", m["name"])
        name_ok("unit", m["unit"], UNIT)
        for cell in m.get("workloads", []):
            if cell not in cells:
                problems.append(f"metric {m['name']}: unknown cell {cell}")
    from .e2e import METRICS

    for m in bench["end_to_end"]:
        if m["name"] not in METRICS:
            problems.append(f"end-to-end metric {m['name']} has no code in e2e.py")
    for m in bench["per_layer"]:
        if m["moves"] not in e2e_names:
            problems.append(f"metric {m['name']}: moves unknown {m['moves']}")
        if need("layer_metrics", m["name"] + ".json"):
            decl = files.read_json("layer_metrics", m["name"] + ".json")
            need("readers", decl["reader"]["kind"] + ".py")
    peaks = files.read_json("data", "peaks.json")
    if not peaks["peaks"] or not peaks["source"]:
        problems.append("peaks table is empty or names no source")


def main() -> int:
    problems = []
    for part in (check_comparison, check_trace_reduction, check_files):
        before = len(problems)
        part(problems)
        print(json.dumps({"selftest": part.__name__,
                          "ok": len(problems) == before}), flush=True)
    for p in problems:
        print(json.dumps({"problem": p}), flush=True)
    print(json.dumps({"selftest": "all", "ok": not problems}), flush=True)
    return 1 if problems else 0
