"""Full-engine query benchmarks over employee-100K.

Mirrors ``kolibrie/benches/my_benchmark.rs:19-113``: (a) the 2-pattern BGP
join SELECT and (b) the nested-subquery SELECT, each through the complete
path — SPARQL parse → Volcano plan search → ID-space execution → string
decode.  Also reports the optimizer-less path (``use_optimizer=False``) as
the reference's "legacy join path" analogue, and checks both agree.

Prints one JSON line per variant.
"""

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from kolibrie_tpu.query.executor import (  # noqa: E402
    execute_query_volcano,
    execute_select,
)
from kolibrie_tpu.query.parser import parse_sparql_query  # noqa: E402
from kolibrie_tpu.query.sparql_database import SparqlDatabase  # noqa: E402

N_EMPLOYEES = 25_000

PREFIXES = """PREFIX ds: <https://data.example/ontology#>
PREFIX foaf: <http://xmlns.com/foaf/0.1/>
"""

JOIN_QUERY = PREFIXES + """
SELECT ?employee ?workplaceHomepage ?salary WHERE {
    ?employee foaf:workplaceHomepage ?workplaceHomepage .
    ?employee ds:annual_salary ?salary
}
"""

SUBQUERY_QUERY = PREFIXES + """
SELECT ?employee ?salary WHERE {
    ?employee ds:annual_salary ?salary .
    {
        SELECT ?employee WHERE {
            ?employee foaf:workplaceHomepage ?workplaceHomepage
        }
    }
}
"""


def build_db() -> SparqlDatabase:
    """Same shape as synthetic_data_employee_100K.rdf: four predicates per
    employee, 100K triples."""
    db = SparqlDatabase()
    lines = []
    for i in range(N_EMPLOYEES):
        e = f"<https://data.example/employee/{i}>"
        lines.append(f'{e} <http://xmlns.com/foaf/0.1/name> "Employee {i}" .')
        lines.append(
            f'{e} <https://data.example/ontology#title> "Engineer" .'
        )
        lines.append(
            f"{e} <http://xmlns.com/foaf/0.1/workplaceHomepage> "
            f"<https://company{i % 500}.example/> ."
        )
        lines.append(
            f'{e} <https://data.example/ontology#annual_salary> '
            f'"{30000 + (i % 50) * 1000}" .'
        )
    db.parse_ntriples("\n".join(lines))
    return db


def timed(fn, reps=3):
    best = float("inf")
    out = None
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    return best, out


def main():
    t0 = time.perf_counter()
    db = build_db()
    t_load = time.perf_counter() - t0
    n = len(db)
    # Host engine only: this bench measures repeated e2e query calls, each
    # of which reads results back.  bench.py + bench_lubm.py measure the
    # device path with the no-readback discipline.
    db.execution_mode = "host"
    print(
        json.dumps(
            {
                "metric": "ntriples_bulk_load",
                "triples": n,
                "seconds": round(t_load, 3),
                "triples_per_sec": round(n / t_load, 1),
            }
        )
    )

    t_join, rows = timed(lambda: execute_query_volcano(JOIN_QUERY, db))
    q = parse_sparql_query(JOIN_QUERY)
    t_legacy, rows_legacy = timed(
        lambda: execute_select(db, q, use_optimizer=False)
    )
    assert sorted(rows) == sorted(rows_legacy), "paths disagree"
    print(
        json.dumps(
            {
                "metric": "bgp_join_query_e2e",
                "rows": len(rows),
                "volcano_ms": round(1000 * t_join, 2),
                "legacy_ms": round(1000 * t_legacy, 2),
                "triples_per_sec": round(4 * N_EMPLOYEES / t_join, 1),
            }
        )
    )

    t_sub, rows_sub = timed(lambda: execute_query_volcano(SUBQUERY_QUERY, db))
    print(
        json.dumps(
            {
                "metric": "nested_subquery_e2e",
                "rows": len(rows_sub),
                "volcano_ms": round(1000 * t_sub, 2),
            }
        )
    )


if __name__ == "__main__":
    main()
