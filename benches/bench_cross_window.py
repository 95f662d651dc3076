"""Cross-window SDS+ naive vs incremental, traffic/parking rule.

Mirrors the reference's ``kolibrie/benches/cross_window_benchmark.rs:22-80``
and the CityBench-style sweep of
``citybench_cross_window_compare.rs:29-62``: a two-window join rule
(traffic avgSpeed x parking nearRoad/occupancy → congested) over a
Streaming Dataset, sweeping size x update-ratio; incremental maintenance
re-derives only from facts whose expiry improved.

Prints one JSON line per (size, ratio) with naive/incremental wall-clock
and their agreement check.
"""

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

# The cross-window SDS+ engines are host-only (numpy) — pin the CPU
# backend so the sweep never claims a chip.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from kolibrie_tpu.core.dictionary import Dictionary  # noqa: E402
from kolibrie_tpu.reasoner.cross_window import (  # noqa: E402
    Sds,
    WindowData,
    WindowedTriple,
    incremental_sds_plus,
    naive_sds_plus,
    sds_with_expiry_to_external,
)
from kolibrie_tpu.reasoner.n3_parser import parse_n3_rules_for_sds  # noqa: E402

TRAFFIC = "http://traffic/"
PARKING = "http://parking/"
RESULT = "http://result/"
CURRENT_TIME = 60

RULE_N3 = """
@prefix wt: <http://traffic/> .
@prefix wp: <http://parking/> .
@prefix wr: <http://result/> .
{ ?road wt:avgSpeed ?s . ?lot wp:nearRoad ?road . ?lot wp:occupancy ?occ } => { ?road wr:congested <true> }
"""


def make_sds(n: int, update_ratio_percent: int) -> Sds:
    """Same generator shape as cross_window_benchmark.rs:42-100."""
    sds = Sds()
    sds.output_iris.add(RESULT)

    update_count = n * update_ratio_percent // 100
    traffic = [
        WindowedTriple(
            subject=f"road_{i}",
            predicate="avgSpeed",
            object=str(20 + i % 80),
            event_time=(CURRENT_TIME + i % 10) if i < update_count else 1 + i % 59,
        )
        for i in range(n)
    ]
    sds.windows[TRAFFIC] = WindowData(alpha=60, triples=traffic)

    lots = max(n // 4, 1)
    p_update = lots * update_ratio_percent // 100
    parking = []
    for j in range(lots):
        et = (CURRENT_TIME + j % 10) if j < p_update else 1 + j % 119
        parking.append(
            WindowedTriple(f"lot_{j}", "nearRoad", f"road_{(j * 4) % max(n, 1)}", et)
        )
        parking.append(
            WindowedTriple(f"lot_{j}", "occupancy", str(50 + j % 50), et)
        )
    sds.windows[PARKING] = WindowData(alpha=120, triples=parking)
    return sds


def run_sweep(
    sizes=(100, 500, 1_000, 5_000, 10_000, 50_000),
    ratios=(1, 10, 50, 100),
):
    """Full reference grid (citybench_cross_window_compare.rs:29-30):
    sizes {100, 500, 1k, 5k, 10k, 50k} x update ratios {1, 10, 50, 100}%.
    Pass KOLIBRIE_CITYBENCH_QUICK=1 for the reduced smoke grid."""
    import os

    if os.environ.get("KOLIBRIE_CITYBENCH_QUICK"):
        sizes = (100, 1000, 5000)
    records = []
    for n in sizes:
        for ratio in ratios:
            dictionary = Dictionary()
            rules, _ctx = parse_n3_rules_for_sds(
                RULE_N3, dictionary, [TRAFFIC, PARKING]
            )
            sds = make_sds(n, ratio)

            t_naive = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                naive_out = naive_sds_plus(rules, sds, dictionary, CURRENT_TIME)
                t_naive = min(t_naive, time.perf_counter() - t0)

            # Incremental: prior state = the ratio-0 SDS maintained at time
            # 0 (all pre-update facts alive), exactly the reference bench's
            # prior construction (cross_window_benchmark.rs:121-127)
            prior = incremental_sds_plus(
                rules, make_sds(n, 0), {}, dictionary, 0
            )
            t_inc = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                inc_out = incremental_sds_plus(
                    rules, sds, prior, dictionary, CURRENT_TIME
                )
                t_inc = min(t_inc, time.perf_counter() - t0)

            ext = sds_with_expiry_to_external(
                inc_out, dictionary, [TRAFFIC, PARKING, RESULT]
            )
            naive_results = {tuple(t) for t in naive_out.get(RESULT, [])}
            inc_results = {tuple(t) for t in ext.get(RESULT, [])}
            rec = {
                "metric": "cross_window_sds_plus",
                "size": n,
                "update_ratio_pct": ratio,
                "naive_ms": round(1000 * t_naive, 2),
                "incremental_ms": round(1000 * t_inc, 2),
                "speedup": round(t_naive / max(t_inc, 1e-9), 2),
                "agree": naive_results == inc_results,
                "derived": len(naive_results),
            }
            records.append(rec)
            print(json.dumps(rec), flush=True)
    return records


if __name__ == "__main__":
    recs = run_sweep()
    # checked-in sweep artifact (VERDICT r4 item 9): the full grid's rows
    out = Path(__file__).resolve().parent.parent / "CITYBENCH_SWEEP.json"
    out.write_text(json.dumps({"grid": recs}, indent=1))
    print(f"wrote {out} ({len(recs)} grid points)")
