"""Group-pattern clause fusion: the fused device program vs the host
post-pass pipeline.

Round 4 compiled UNION / OPTIONAL / MINUS (plus inlined sub-SELECTs)
into the single device program (`AntiJoinSpec`/`UnionSpec`/
`LeftOuterSpec` over the plan tree).  The host engine evaluates the same
query as four passes over materialized numpy tables.  This bench runs a
query using all three clause kinds over 100K employee triples through
``PreparedQuery`` (amortized dispatch, no readback in the loop) and
reports throughput + the ratio to the host pipeline.

Prints ONE JSON line.
"""

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

N_EMPLOYEES = 25_000
N_DISPATCH = 12
SCAN_K = 16
GAP_S = 0.15

QUERY = """PREFIX foaf: <http://xmlns.com/foaf/0.1/>
PREFIX ds: <https://data.example/ontology#>
SELECT ?e ?s ?m WHERE {
    ?e ds:annual_salary ?s
    { ?e foaf:title "Developer" } UNION { ?e foaf:title "Engineer" }
    OPTIONAL { ?e ds:mentors ?m }
    MINUS { ?e ds:flagged "yes" }
}
"""


def build_db():
    from kolibrie_tpu.query.sparql_database import SparqlDatabase

    db = SparqlDatabase()
    lines = []
    titles = ["Developer", "Engineer", "Analyst", "Manager"]
    for i in range(N_EMPLOYEES):
        e = f"<https://data.example/employee/{i}>"
        lines.append(
            f'{e} <http://xmlns.com/foaf/0.1/title> "{titles[i % 4]}" .'
        )
        lines.append(
            f'{e} <https://data.example/ontology#annual_salary> '
            f'"{30000 + (i % 50) * 1000}" .'
        )
        if i % 5 == 0:
            lines.append(
                f"{e} <https://data.example/ontology#mentors> "
                f"<https://data.example/employee/{(i + 1) % N_EMPLOYEES}> ."
            )
        if i % 9 == 0:
            lines.append(
                f'{e} <https://data.example/ontology#flagged> "yes" .'
            )
    db.parse_ntriples("\n".join(lines))
    return db


def main():
    import jax

    from kolibrie_tpu.optimizer.device_engine import PreparedQuery
    from kolibrie_tpu.query.executor import execute_query_volcano

    db = build_db()
    platform = jax.devices()[0].platform
    n_triples = len(db.store)
    n_dispatch, scan_k, gap = (
        (N_DISPATCH, SCAN_K, GAP_S) if platform == "tpu" else (4, 4, 0.0)
    )

    db.execution_mode = "host"
    host_e2e = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        host_rows = execute_query_volcano(QUERY, db)
        host_e2e = min(host_e2e, time.perf_counter() - t0)

    prep = PreparedQuery(db, QUERY)
    prep.calibrate()
    host_exec = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        prep.lowered.host_execute()
        host_exec = min(host_exec, time.perf_counter() - t0)

    out = prep.run()
    jax.block_until_ready(out)
    ok = prep.run_amortized(scan_k)
    jax.block_until_ready(ok)
    ts = []
    for _ in range(n_dispatch):
        t0 = time.perf_counter()
        ok = prep.run_amortized(scan_k)
        jax.block_until_ready(ok)
        ts.append(time.perf_counter() - t0)
        time.sleep(gap)
    dev_tk = min(ts) / scan_k

    rows = prep.fetch(prep.run())
    assert rows == sorted(host_rows), (len(rows), len(host_rows))

    print(
        json.dumps(
            {
                "metric": f"clause_fusion_union_optional_minus_{platform}",
                "value": round(n_triples / dev_tk, 1),
                "unit": "triples/sec/chip",
                "vs_baseline": round(host_exec / dev_tk, 3),
                "secondary": {
                    "plan_exec_amortized_ms": round(1000 * dev_tk, 4),
                    "host_pipeline_exec_ms": round(1000 * host_exec, 3),
                    "host_e2e_ms": round(1000 * host_e2e, 2),
                    "rows": len(rows),
                    "note": "UNION+OPTIONAL+MINUS fused into ONE device "
                    "program (PreparedQuery amortized dispatch) vs the "
                    "host engine's four-pass pipeline over the same data; "
                    "rows verified equal",
                },
            }
        )
    )


if __name__ == "__main__":
    main()
