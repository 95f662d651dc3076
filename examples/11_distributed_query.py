"""Distributed full SPARQL plans over a device mesh (BASELINE config 5).

A SELECT's basic graph pattern is lowered onto the mesh as a chain of
routed joins: sharded scans over the subject-/object-hash triple shards,
``all_to_all`` repartitioning of the binding table between join stages,
local sort-merge joins, replicated filter masks, and a projection gathered
to host — rows are exactly the host engine's.

Run with a virtual 8-device CPU mesh:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python examples/11_distributed_query.py
"""

import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

import lubm  # noqa: E402

from kolibrie_tpu.parallel import make_mesh  # noqa: E402
from kolibrie_tpu.parallel.dist_query import DistQueryExecutor  # noqa: E402
from kolibrie_tpu.query.executor import execute_query_volcano  # noqa: E402
from kolibrie_tpu.query.sparql_database import SparqlDatabase  # noqa: E402


def main() -> None:
    db = SparqlDatabase()
    s, p, o = lubm.generate_fast(5, db.dictionary)
    db.store.add_batch(s, p, o)
    db.execution_mode = "host"
    print(f"LUBM-5: {len(db.store):,} triples")

    mesh = make_mesh(len(jax.devices()))
    print(f"mesh: {mesh.devices.size} x {jax.devices()[0].platform}")

    # Q2: the triangle GraduateStudent -memberOf-> Department
    #     -subOrganizationOf-> University <-undergraduateDegreeFrom- (same
    #     student) — six patterns, shared variables beyond the routed key.
    ex = DistQueryExecutor(mesh, db, lubm.LUBM_Q2)
    print(
        f"Q2 calibrated caps: join={ex.join_cap}, bucket={ex.bucket_cap} "
        "(host chain pass, memoized per store version)"
    )
    rows = ex.run()
    host_rows = execute_query_volcano(lubm.LUBM_Q2, db)
    assert rows == host_rows
    print(f"Q2: {len(rows)} rows — distributed == host ✓")

    # The sharded store is reusable across prepared queries.
    ex9 = DistQueryExecutor(mesh, db, lubm.LUBM_Q9, store=ex.store)
    rows9 = ex9.run()
    assert rows9 == execute_query_volcano(lubm.LUBM_Q9, db)
    print(f"Q9: {len(rows9)} rows — distributed == host ✓ (store reused)")


if __name__ == "__main__":
    main()
