"""Parameterized plan templates: compile count + dispatch latency across
constant-variants of one query shape.

Before this optimization every constant-variant baked its constants into
the static ``PlanSpec``, so 64 variants meant 64 XLA compiles.  Now the
constants travel in a traced parameter vector and the template cache
re-keys the plan cache on the constant-free fingerprint: 64 variants, ONE
compile.  This bench measures

- the jit cache growth across ``N_VARIANTS`` variants (expected: 1),
- the cold first-variant latency (pays the single compile) vs the warm
  per-variant p50/p95 (pays parse + plan + parameter rebind only),
- the batched path: all variants stacked into one vmap dispatch.

Prints ONE JSON line.
"""

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

N_EMPLOYEES = 25_000
N_VARIANTS = 64


def build_db():
    from kolibrie_tpu.query.sparql_database import SparqlDatabase

    db = SparqlDatabase()
    lines = []
    for i in range(N_EMPLOYEES):
        e = f"<https://data.example/employee/{i}>"
        lines.append(
            f'{e} <https://data.example/ontology#dept> "dept{i % 16}" .'
        )
        lines.append(
            f'{e} <https://data.example/ontology#annual_salary> '
            f'"{30000 + (i % 50) * 1000}" .'
        )
    db.parse_ntriples("\n".join(lines))
    db.execution_mode = "device"
    return db


def variant(i: int) -> str:
    return (
        "PREFIX ds: <https://data.example/ontology#> "
        f'SELECT ?e ?s WHERE {{ ?e ds:dept "dept{i % 16}" . '
        f"?e ds:annual_salary ?s . FILTER(?s > {30000 + (i * 700) % 35000}) }}"
    )


def _pct(samples, q):
    s = sorted(samples)
    return s[min(len(s) - 1, int(round(q * (len(s) - 1))))]


def main():
    import jax

    from kolibrie_tpu.optimizer.device_engine import device_compile_stats
    from kolibrie_tpu.query.executor import (
        execute_queries_batched,
        execute_query_volcano,
        plan_cache_info,
    )

    db = build_db()
    platform = jax.devices()[0].platform
    queries = [variant(i) for i in range(N_VARIANTS)]

    base = device_compile_stats()
    t0 = time.perf_counter()
    rows0 = execute_query_volcano(queries[0], db)
    cold_ms = (time.perf_counter() - t0) * 1000.0
    after_first = device_compile_stats()

    lat = []
    for q in queries[1:]:
        t0 = time.perf_counter()
        execute_query_volcano(q, db)
        lat.append((time.perf_counter() - t0) * 1000.0)
    after_all = device_compile_stats()
    compiles_first = after_first["run_plan"] - base["run_plan"]
    compiles_rest = after_all["run_plan"] - after_first["run_plan"]

    # batched: every variant in ONE stacked vmap dispatch (plus its compile)
    t0 = time.perf_counter()
    batch_rows = execute_queries_batched(db, queries)
    batch_ms = (time.perf_counter() - t0) * 1000.0
    t0 = time.perf_counter()
    batch_rows = execute_queries_batched(db, queries)
    batch_warm_ms = (time.perf_counter() - t0) * 1000.0

    # correctness: batched results equal the solo path's
    assert sorted(map(tuple, batch_rows[0])) == sorted(map(tuple, rows0))

    info = plan_cache_info(db)
    p50 = _pct(lat, 0.50)
    print(
        json.dumps(
            {
                "metric": f"plan_template_warm_variant_dispatch_{platform}",
                "value": round(p50, 3),
                "unit": "ms/variant",
                "vs_baseline": round(cold_ms / p50, 1),
                "secondary": {
                    "n_variants": N_VARIANTS,
                    "compiles_first_variant": compiles_first,
                    "compiles_remaining_63": compiles_rest,
                    "cold_first_variant_ms": round(cold_ms, 2),
                    "warm_variant_ms_p50": round(p50, 3),
                    "warm_variant_ms_p95": round(_pct(lat, 0.95), 3),
                    "batched_all64_ms": round(batch_warm_ms, 2),
                    "batched_all64_cold_ms": round(batch_ms, 2),
                    "batched_per_query_ms": round(
                        batch_warm_ms / N_VARIANTS, 3
                    ),
                    "templates_cached": info["templates"],
                    "param_rebinds": info["param_rebinds"],
                    "note": "64 constant-variants of one BGP+filter "
                    "template through the public API; constants ride a "
                    "traced parameter vector so the jit cache grows by "
                    "exactly compiles_first_variant (expected 1, formerly "
                    "64); vs_baseline = cold(compile)/warm ratio; batched = "
                    "all 64 stacked into one vmap program",
                },
            }
        )
    )


if __name__ == "__main__":
    main()
