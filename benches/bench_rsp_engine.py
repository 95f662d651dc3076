"""End-to-end RSP engine: S2R windows + cross-window rules over a
generated event stream.

Mirrors ``kolibrie/benches/rsp_citybench_cross_window.rs:13-45`` (CityBench
style: traffic + parking streams, RANGE/STEP windows, cross-window join
rule), comparing NAIVE vs INCREMENTAL cross-window reasoning modes on
identical streams.

Prints one JSON line per mode with events/sec through the whole engine
(scope → window assignment → coordinator → SDS+ → R2S → consumer).
"""

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from kolibrie_tpu.rsp.builder import RSPBuilder  # noqa: E402
from kolibrie_tpu.rsp.engine import CrossWindowReasoningMode  # noqa: E402
from kolibrie_tpu.rsp.s2r import WindowTriple  # noqa: E402

QUERY = """PREFIX ex: <http://city/>
REGISTER RSTREAM <http://out/congestion> AS
SELECT ?road ?speed
FROM NAMED WINDOW <http://city/wT/> ON <http://city/traffic> [RANGE 120 STEP 60]
FROM NAMED WINDOW <http://city/wP/> ON <http://city/parking> [RANGE 180 STEP 60]
WHERE {
  WINDOW <http://city/wT/> { ?road <congested> ?speed }
  WINDOW <http://city/wP/> { ?lot <nearRoad> ?road }
}"""

RULES = """@prefix t: <http://city/wT/> .
@prefix p: <http://city/wP/> .
{ ?road t:avgSpeed ?s . ?lot p:nearRoad ?road . } => { ?road t:congested ?s . } .
"""

# Coprime with the 4-events-per-tick cycle so every road sees both traffic
# and parking events (a multiple of 4 would partition them disjointly).
N_ROADS = 41
N_EVENTS = 2_000


def run_mode(mode: str) -> dict:
    results = []
    engine = (
        RSPBuilder(QUERY)
        .set_cross_window_rules(RULES)
        .set_cross_window_reasoning_mode(mode)
        .with_consumer(lambda row: results.append(row))
        .build()
    )
    t0 = time.perf_counter()
    last_ts = -1
    for i in range(N_EVENTS):
        ts = i // 4  # four events per tick
        if ts != last_ts:
            engine.process_single_thread_window_results()
            last_ts = ts
        road = f"road_{i % N_ROADS}"
        if i % 4 < 3:
            engine.add_to_stream(
                "http://city/traffic",
                WindowTriple(road, "avgSpeed", f'"{20 + i % 60}"'),
                ts,
            )
        else:
            engine.add_to_stream(
                "http://city/parking",
                WindowTriple(f"lot_{i % 11}", "nearRoad", road),
                ts,
            )
    engine.process_single_thread_window_results()
    engine.stop()
    elapsed = time.perf_counter() - t0
    return {
        "metric": "rsp_engine_cross_window_e2e",
        "mode": mode,
        "events": N_EVENTS,
        "seconds": round(elapsed, 3),
        "events_per_sec": round(N_EVENTS / elapsed, 1),
        "result_rows": len(results),
    }


R2R_QUERY = """PREFIX ex: <http://city/>
REGISTER RSTREAM <http://out/reach> AS
SELECT ?a ?c
FROM NAMED WINDOW <http://city/w/> ON <http://city/social> [RANGE 120 STEP 60]
WHERE { WINDOW <http://city/w/> { ?a ex:reach ?c } }"""

R2R_RULES = """@prefix s: <http://city/> .
{ ?a s:knows ?b . ?b s:knows ?c . } => { ?a s:reach ?c . } .
"""


def run_r2r_mode(mode: str) -> dict:
    """Single window + per-window rules: the SimpleR2R/DeviceR2R
    materialize path (no cross-window coordinator), host vs
    device-resident (VERDICT r3 item 4 done-criterion)."""
    results = []
    engine = (
        RSPBuilder(R2R_QUERY)
        .add_rules(R2R_RULES)
        .set_r2r_mode(mode)
        .with_consumer(lambda row: results.append(row))
        .build()
    )
    t0 = time.perf_counter()
    last_ts = -1
    for i in range(N_EVENTS):
        ts = i // 4
        if ts != last_ts:
            engine.process_single_thread_window_results()
            last_ts = ts
        engine.add_to_stream(
            "http://city/social",
            WindowTriple(
                f"<http://city/p{i % N_ROADS}>",
                "<http://city/knows>",
                f"<http://city/p{(i * 7 + 1) % N_ROADS}>",
            ),
            ts,
        )
    engine.process_single_thread_window_results()
    engine.stop()
    elapsed = time.perf_counter() - t0
    return {
        "metric": "rsp_engine_r2r_materialize_e2e",
        "mode": mode,
        "events": N_EVENTS,
        "seconds": round(elapsed, 3),
        "events_per_sec": round(N_EVENTS / elapsed, 1),
        "result_rows": len(results),
    }


def main():
    out_naive = run_mode(CrossWindowReasoningMode.NAIVE)
    out_inc = run_mode(CrossWindowReasoningMode.INCREMENTAL)
    # Same stream, same windows: both modes must derive the same number of
    # rows, and the workload must actually produce some.
    assert out_naive["result_rows"] == out_inc["result_rows"] > 0, (
        out_naive["result_rows"],
        out_inc["result_rows"],
    )
    print(json.dumps(out_naive))
    print(json.dumps(out_inc))
    out_host = run_r2r_mode("host")
    out_inc2 = run_r2r_mode("incremental")
    out_dev = run_r2r_mode("device")
    assert (
        out_host["result_rows"]
        == out_inc2["result_rows"]
        == out_dev["result_rows"]
        > 0
    ), (
        out_host["result_rows"],
        out_inc2["result_rows"],
        out_dev["result_rows"],
    )
    print(json.dumps(out_host))
    print(json.dumps(out_inc2))
    print(json.dumps(out_dev))


if __name__ == "__main__":
    main()
