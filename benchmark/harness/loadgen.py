"""The load generator: the client's side, in a process of its own.

A client in the server's process shares its interpreter lock: after a large
response the server thread's clean-up delayed the client's wake-up by a
switch interval, and the full join's latency spread 9.5 % (my chip run 3, PR
24).  A deployment's clients are other processes, so this
one is too.  It never imports JAX or the program: it generates the data from
the seed, loads it through ``POST /store/load``, sends cycles on command,
keeps the window's response bodies, and after the window compares them with
the plain reference.  Commands arrive on a pipe from ``runner.run_cell``.

Several clients are threads of this process, each a ``Client`` of its own,
as the one client is: a client spends its time waiting for a reply, so eight
of them share one interpreter lock without holding one another up.  In the
window they run free (``free_run``); only the clients of a warm-up cycle
start together.
"""

import json
import threading
import time
from collections import Counter

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


def _client_cycle(traffic, client, k, stream, c):
    """Client ``c``'s cycle ``k``: the cycle's steps in order, each sent when
    the reply to the last has been read.  ``(t0, t1, request records)``."""
    sent, t0 = [], time.perf_counter()
    for i, (template, text) in enumerate(traffic.cycle(k, stream, c)):
        trace_id = f"bench-{stream}-{k}-{i}-{c}"
        wall = time.time()
        status, body, ms = client.query(STORE_ID, text, trace_id)
        sent.append({"cycle": k, "client": c, "template": template,
                     "status": status, "ms": ms, "wall": wall,
                     "trace_id": trace_id, "text": text, "body": body})
    return t0, time.perf_counter(), sent


def _in_threads(n, target):
    threads = [threading.Thread(target=target, args=(c,), daemon=True)
               for c in range(n)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()


def _round(cycles):
    """Several client-cycles as one record: from the first one's first send
    to the last one's last reply."""
    return (min(t0 for t0, _, _ in cycles), max(t1 for _, t1, _ in cycles),
            [r for _, _, sent in cycles for r in sent])


def send_cycle(traffic, clients, k, stream, n):
    """One cycle of ``n`` clients that start together (one client is this
    thread itself); it ends when the last has read its last reply."""
    if n == 1:
        return _client_cycle(traffic, clients[0], k, stream, 0)
    cycles = [None] * n

    def run(c):
        cycles[c] = _client_cycle(traffic, clients[c], k, stream, c)

    _in_threads(n, run)
    return _round(cycles)


def free_run(traffic, clients, stream, seconds):
    """The window of several clients: each a closed loop of its own, none
    waiting for another.  A client always sends its first cycle, and a
    further one only if the longest it has seen would still end inside
    ``seconds``: the rule of the one client's window, each client for itself.
    With *n* clients a whole cycle is *n* client-cycles, counted in the order
    they end, whichever clients sent them: clients that keep pace give a
    cycle each, and one that is served faster than the rest gives more.
    Returns ``[(t0, t1, request records)]``, a whole cycle each; what is left
    over, fewer than *n* client-cycles, comes last with ``t1`` ``None``."""
    n = traffic.clients
    done = [[] for _ in range(n)]
    t_open = time.perf_counter()

    def run(c):
        k, longest = 0, 0.0
        while k == 0 or time.perf_counter() - t_open + longest < seconds:
            done[c].append(_client_cycle(traffic, clients[c], k, stream, c))
            t0, t1, _ = done[c][-1]
            k, longest = k + 1, max(longest, t1 - t0)

    _in_threads(n, run)
    ended = sorted((cyc for one in done for cyc in one), key=lambda cyc: cyc[1])
    whole = len(ended) - len(ended) % n
    out = [_round(ended[i:i + n]) for i in range(0, whole, n)]
    if ended[whole:]:
        t0, _, records = _round(ended[whole:])
        out.append((t0, None, records))
    return out


def main(conn, config, traffic_name, seed, scale, tamper):
    """``tamper``: ``None`` or ``(request index in the window, kind)``."""
    requests, data, traffic, clients = [], None, None, []
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
                           "clients": traffic.clients,
                           "warmup_counts": traffic.warmup_counts(),
                           "trace_min_seconds": traffic.trace_min_seconds})
            elif cmd == "load":
                # a client a thread; the first also loads
                clients = [Client(args[0], traffic.deadline_ms)
                           for _ in range(max(traffic.warmup_ramp))]
                t0 = time.perf_counter()
                for text in files.ntriples_chunks(data):
                    body = clients[0].post("/store/load", {
                        "store_id": STORE_ID, "rdf": text, "format": "ntriples"})
                conn.send({"acknowledged": body["triples"],
                           "seconds": time.perf_counter() - t0})
            elif cmd in ("cycle", "free"):
                if cmd == "cycle":
                    k, stream, n = args
                    sent = [send_cycle(traffic, clients, k, stream, n)]
                else:
                    stream, seconds = args
                    sent = free_run(traffic, clients, stream, seconds)
                replies = []
                for t0, t1, records in sent:
                    if stream == "window":
                        for r in records:
                            if tamper and tamper[0] == len(requests):
                                r["body"] = _tampered(r["body"], tamper[1])
                            requests.append(r)
                    replies.append({
                        "ms": t1 and (t1 - t0) * 1000.0, "t0": t0, "t1": t1,
                        "requests": [{key: v for key, v in r.items()
                                      if key not in ("text", "body")}
                                     for r in records]})
                conn.send(replies[0] if cmd == "cycle" else replies)
            elif cmd == "compare":
                conn.send(_compare(config, data, seed, requests, control=args[0]))
            elif cmd == "statuses":
                conn.send(dict(sum((cl.statuses for cl in clients), Counter())))
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
