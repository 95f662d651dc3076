"""Per-firing R2R latency vs window size: full recompute vs incremental.

VERDICT r4 (round-3 item 5) evidence: the delta-incremental R2R
(``rsp/r2r.py::IncrementalR2R`` — expiration-provenance closure carried
across firings, delta-seeded per firing) against the host full-recompute
path (``SimpleR2R``) on identical sliding-window streams with a FIXED
per-firing delta (50 events) and growing window size.  Agreement of the
derived sets is asserted at every firing of every size.

Prints one JSON line per window size.  CityBench-style workload: sparse
knows-graph, 2-hop reach rule.
"""
import json
import random
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from kolibrie_tpu.rsp.r2r import IncrementalR2R, SimpleR2R  # noqa: E402
from kolibrie_tpu.rsp.s2r import WindowTriple  # noqa: E402

RULES = """@prefix s: <http://c/> .
{ ?a s:knows ?b . ?b s:knows ?c . } => { ?a s:reach ?c . } .
"""
STEP = 50
FIRINGS = 12
WARMUP = 3


def _decode_set(r, triples):
    dec = r.db.dictionary.decode
    return sorted(
        (dec(t.subject), dec(t.predicate), dec(t.object)) for t in triples
    )


def bench_size(win_size: int) -> dict:
    rng = random.Random(3)

    def mk():
        return WindowTriple(
            f"<http://c/p{rng.randrange(win_size)}>",
            "<http://c/knows>",
            f"<http://c/p{rng.randrange(win_size)}>",
        )

    win0 = [(mk(), i) for i in range(win_size)]
    deltas = [[(mk(), 0) for _ in range(STEP)] for _ in range(FIRINGS)]

    host, inc = SimpleR2R(), IncrementalR2R()
    host.load_rules(RULES)
    inc.load_rules(RULES)

    times = {"host": [], "incremental": []}
    wl_h = list(win0)
    wl_i = list(win0)
    now = win_size
    prev = []
    for f in range(FIRINGS):
        fresh = [(it, now + j) for j, (it, _) in enumerate(deltas[f])]
        now += STEP

        wl_h = wl_h[STEP:] + fresh
        t0 = time.perf_counter()
        for t in prev:
            host.remove(t)
        prev = [it for it, _ in wl_h]
        for it in prev:
            host.add(it)
        dh = host.materialize()
        times["host"].append(time.perf_counter() - t0)

        wl_i = wl_i[STEP:] + fresh
        t0 = time.perf_counter()
        inc.feed_window("w", win_size, iter(wl_i))
        di = inc.materialize_incremental()
        times["incremental"].append(time.perf_counter() - t0)

        assert _decode_set(host, dh) == _decode_set(inc, di), (
            f"derived mismatch at win={win_size} firing={f}"
        )
    h = sum(times["host"][WARMUP:]) / (FIRINGS - WARMUP)
    i = sum(times["incremental"][WARMUP:]) / (FIRINGS - WARMUP)
    return {
        "metric": "r2r_per_firing_latency",
        "window": win_size,
        "delta_per_firing": STEP,
        "host_ms": round(h * 1000, 2),
        "incremental_ms": round(i * 1000, 2),
        "speedup": round(h / i, 2),
        "agreement": "asserted every firing",
    }


def main():
    for n in (500, 1000, 2000, 4000, 8000, 16000):
        print(json.dumps(bench_size(n)))


if __name__ == "__main__":
    main()
