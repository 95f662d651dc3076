"""The load generator: the client's side, in a process of its own.

A client in the server's process shares its interpreter lock: after a large
response the server thread's clean-up delayed the client's wake-up by a
switch interval, and the full join's latency spread 9.5 % (my chip run 3, PR
24).  A deployment's clients are other processes, so this
one is too.  It never imports JAX or the program: it generates the data from
the seed, loads it through ``POST /store/load``, sends cycles on command,
keeps the window's response bodies, and after the window compares them with
the plain reference.  Commands arrive on a pipe from ``runner.run_cell``.
"""

import json
import time

import numpy as np

from . import compare
from . import data as files
from .client import Client
from .traffic import Traffic

STORE_ID = "bench"


def _tampered(body: bytes, kind: str) -> bytes:
    """Tests only: a response altered where the client receives it."""
    rows = json.loads(body)["data"]
    if kind == "drop_row":
        rows = rows[1:]
    elif kind == "alter_value":
        rows[0][0] += "x"
    else:
        raise ValueError(kind)
    return json.dumps({"data": rows}).encode()


def main(conn, config, traffic_name, seed, scale, tamper):
    """``tamper``: ``None`` or ``(request index in the window, kind)``."""
    requests, data, traffic, cl = [], None, None, None
    try:
        while True:
            cmd, *args = conn.recv()
            if cmd == "quit":
                return
            if cmd == "generate":
                t0 = time.perf_counter()
                data = files.load_module(
                    "generators", config["generator"]).generate(config, seed, scale)
                traffic = Traffic(traffic_name, data["domains"], seed)
                conn.send({"triples": len(data["s"]),
                           "seconds": time.perf_counter() - t0,
                           "warmup_cycles": traffic.warmup_cycles,
                           "trace_min_seconds": traffic.trace_min_seconds})
            elif cmd == "load":
                cl = Client(args[0], traffic.deadline_ms)
                t0 = time.perf_counter()
                for text in files.ntriples_chunks(data):
                    body = cl.post("/store/load", {"store_id": STORE_ID,
                                                   "rdf": text, "format": "ntriples"})
                conn.send({"acknowledged": body["triples"],
                           "seconds": time.perf_counter() - t0})
            elif cmd == "cycle":
                k, stream = args
                sent, t0 = [], time.perf_counter()
                for i, (template, text) in enumerate(traffic.cycle(k, stream)):
                    trace_id = f"bench-{stream}-{k}-{i}"
                    wall = time.time()
                    status, body, ms = cl.query(STORE_ID, text, trace_id)
                    meta = {"cycle": k, "template": template, "status": status,
                            "ms": ms, "wall": wall, "trace_id": trace_id}
                    sent.append(meta)
                    if stream == "window":
                        if tamper and tamper[0] == len(requests):
                            body = _tampered(body, tamper[1])
                        requests.append(dict(meta, text=text, body=body))
                t1 = time.perf_counter()
                conn.send({"ms": (t1 - t0) * 1000.0, "t0": t0, "t1": t1,
                           "requests": sent})
            elif cmd == "compare":
                conn.send(_compare(config, data, seed, requests, control=args[0]))
            elif cmd == "statuses":
                conn.send(dict(cl.statuses))
            else:
                raise ValueError(cmd)
    except EOFError:
        return


def _compare(config, data, seed, requests, control):
    """Outside the window and outside set-up: the plain reference."""
    from benchmark.reference.sparql_subset import Reference

    t0 = time.perf_counter()
    ref = Reference(data["terms"], data["s"], data["p"], data["o"])
    bad, want = compare.wrong_answers(requests, ref.query)
    out = {
        "wrong": bad,
        "distinct_texts": len(want),
        "rows_by_template": {r["template"]: sum(want[r["text"]].values())
                             for r in requests if r["text"] in want},
        # an empty answer is an answer; a template that never finds a row
        # in a whole window is not exercising anything
        "empty": sorted({r["template"] for r in requests}
                        - {r["template"] for r in requests if want.get(r["text"])}),
        "reference_s": time.perf_counter() - t0,
    }
    if control:
        # the control: the reference, put in the program's place, with one
        # stated guarantee broken -- it answers from a store that lacks a
        # share of the acknowledged triples (a stale read)
        share = config["control"]["stale_share"]
        keep = np.random.default_rng([int(seed), 7]).random(len(data["s"])) >= share
        stale = Reference(data["terms"], data["s"][keep], data["p"][keep],
                          data["o"][keep])
        wrong = sum(1 for text, rows in want.items()
                    if compare.multiset(stale.query(text)) != rows)
        out["control"] = {
            "guarantee": "a read sees every acknowledged triple",
            "stale_share": share, "triples_missing": int((~keep).sum()),
            "distinct_texts": len(want), "texts_answered_wrongly": wrong,
            "limit": 0, "control_correct": wrong == 0}
    return out
