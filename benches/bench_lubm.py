"""LUBM Q2/Q9 wall-clock + rule-closure + pod-sharded join (BASELINE
configs 3 and 5).

- Q2/Q9 run through the full engine twice: host path (parse → Volcano →
  numpy ID-space execute → decode) and device path (same parse/plan, the
  plan compiled to one XLA program via ``PreparedQuery``).
- The closure bench materializes transitive subOrganizationOf and
  member-propagation rules with the host semi-naive reasoner AND the
  single-dispatch device fixpoint (whole closure = one ``lax.while_loop``
  program).
- The sharded join runs the distributed BGP join (all-to-all partitioned)
  over a device mesh: the real chip when only one device is visible, or an
  8-device virtual CPU mesh under
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu.

Each section runs in its OWN subprocess, one at a time, so a section's
result verification never shares a process with the next section's timing
loop.  The parent never imports JAX: one process holds the chip at a time.

Prints one JSON line per metric.
"""

import json
import subprocess
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import os  # noqa: E402

import numpy as np  # noqa: E402

from lubm import LUBM_Q2, LUBM_Q9, UB, generate_fast, predicate_ids  # noqa: E402

# LUBM scale knob: LUBM_UNIVERSITIES=1000 runs the BASELINE.md LUBM-1000
# configuration (~3.79M triples, generated vectorized in ~1s)
N_UNIVERSITIES = int(os.environ.get("LUBM_UNIVERSITIES", "40"))
SECTIONS = (
    "load",
    "queries_host",
    "queries_device",
    "closure",
    "sharded",
    "dist_query",
    "load10m",
)


def build_db():
    from kolibrie_tpu.query.sparql_database import SparqlDatabase

    db = SparqlDatabase()
    t0 = time.perf_counter()
    s, p, o = generate_fast(N_UNIVERSITIES, db.dictionary)
    db.store.add_batch(s, p, o)
    db.store.compact()
    t_gen = time.perf_counter() - t0
    return db, (s, p, o), t_gen


def section_load():
    db, _cols, t_gen = build_db()
    print(
        json.dumps(
            {
                "metric": "lubm_generate_load",
                "universities": N_UNIVERSITIES,
                "triples": len(db.store),
                "seconds": round(t_gen, 3),
            }
        )
    )


def section_queries_host():
    from kolibrie_tpu.query.executor import execute_query_volcano

    db, _cols, _ = build_db()
    db.execution_mode = "host"
    n = len(db.store)
    for name, query in (("lubm_q2", LUBM_Q2), ("lubm_q9", LUBM_Q9)):
        best, rows = float("inf"), []
        for _ in range(3):
            t0 = time.perf_counter()
            rows = execute_query_volcano(query, db)
            best = min(best, time.perf_counter() - t0)
        print(
            json.dumps(
                {
                    "metric": f"{name}_host_wall_clock",
                    "rows": len(rows),
                    "ms": round(1000 * best, 2),
                    "triples_per_sec": round(n / best, 1),
                }
            )
        )


def section_queries_device():
    import jax

    from kolibrie_tpu.optimizer.device_engine import PreparedQuery
    from kolibrie_tpu.query.executor import execute_query_volcano

    db, _cols, _ = build_db()
    n = len(db.store)
    preps = {}
    for name, query in (("lubm_q2", LUBM_Q2), ("lubm_q9", LUBM_Q9)):
        prep = PreparedQuery(db, query)
        prep.calibrate()  # host-side exact capacities, no device I/O
        preps[name] = (prep, query)
    # ALL timed dispatches before ANY readback
    results = {}
    for name, (prep, _q) in preps.items():
        out = prep.run()
        jax.block_until_ready(out)
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            out = prep.run()
            jax.block_until_ready(out)
            best = min(best, time.perf_counter() - t0)
        results[name] = (best, out)
    # verification readbacks
    db.execution_mode = "host"
    for name, (prep, query) in preps.items():
        best, out = results[name]
        rows = prep.fetch(out)
        host_rows = sorted(execute_query_volcano(query, db))
        assert rows == host_rows, f"{name}: device/host mismatch"
        print(
            json.dumps(
                {
                    "metric": f"{name}_device_wall_clock",
                    "rows": len(rows),
                    "ms": round(1000 * best, 3),
                    "triples_per_sec": round(n / best, 1),
                }
            )
        )


def _closure_reasoner(db, cols):
    from kolibrie_tpu.reasoner.reasoner import Reasoner

    s, p, o = cols
    r = Reasoner(db.dictionary)
    r.facts.add_batch(s, p, o)
    sub = UB + "subOrganizationOf"
    mem = UB + "memberOf"
    r.add_rule(
        r.rule_from_strings(
            [("?a", sub, "?b"), ("?b", sub, "?c")], [("?a", sub, "?c")]
        )
    )
    r.add_rule(
        r.rule_from_strings(
            [("?x", mem, "?d"), ("?d", sub, "?u")], [("?x", mem, "?u")]
        )
    )
    return r


def section_closure():
    import jax

    from kolibrie_tpu.reasoner.device_fixpoint import (
        DeviceFixpoint,
        _Caps,
        _round_cap,
    )

    db, cols, _ = build_db()
    r = _closure_reasoner(db, cols)
    before = len(r.facts)
    t0 = time.perf_counter()
    r.infer_new_facts_semi_naive()
    t_closure = time.perf_counter() - t0
    derived = len(r.facts) - before
    print(
        json.dumps(
            {
                "metric": "lubm_rule_closure",
                "base_triples": before,
                "derived": derived,
                "ms": round(1000 * t_closure, 2),
                "derived_per_sec": round(derived / max(t_closure, 1e-9), 1),
            }
        )
    )

    # whole closure = ONE device dispatch; timed before any readback
    from kolibrie_tpu.reasoner.device_fixpoint import SAFE_JOIN_CAP

    r_dev = _closure_reasoner(db, cols)
    fx = DeviceFixpoint(r_dev)
    caps = _Caps(
        fact=_round_cap(2 * (before + derived)),
        delta=_round_cap(before),
        join=_round_cap(4 * before, 1024),
    )
    if jax.default_backend() == "tpu" and caps.join > SAFE_JOIN_CAP:
        # past the one-dispatch program's toolchain-safe join bound: run the
        # host-driven chunked per-round driver (every program stays below
        # the bound).  Wall-clock includes its one scalar sync per round —
        # that IS the algorithm's host cost, so it is timed honestly.
        best = float("inf")
        derived_dev = 0
        for i in range(3):
            r_i = _closure_reasoner(db, cols)
            fx_i = DeviceFixpoint(r_i)
            t0 = time.perf_counter()
            derived_dev = fx_i.infer_chunked(writeback=False)
            dt = time.perf_counter() - t0
            if i > 0:  # first call pays compiles
                best = min(best, dt)
            t_first = dt if i == 0 else t_first  # noqa: F821
        assert derived_dev == derived, (derived_dev, derived)
        # bulk device→host transfer + set verification AFTER timing
        fx_i.materialize_to_host()
        assert r_i.facts.triples_set() == r.facts.triples_set()
        print(
            json.dumps(
                {
                    "metric": "lubm_rule_closure_device",
                    "mode": "chunked_rounds",
                    "derived": derived_dev,
                    "compile_s": round(t_first, 1),
                    "ms": round(1000 * best, 3),
                    "derived_per_sec": round(derived_dev / max(best, 1e-9), 1),
                    "note": "per-round chunk programs under SAFE_JOIN_CAP; "
                    "facts set verified equal to host closure",
                }
            )
        )
        return
    t0 = time.perf_counter()
    out = fx.run_raw(caps)  # compile + warm
    jax.block_until_ready(out)
    t_first = time.perf_counter() - t0
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        out = fx.run_raw(caps)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    # readback + verification AFTER timing
    code = int(out[5])
    assert code == 0, f"fixpoint overflow code {code} — raise bench caps"
    n_out = int(out[3])
    assert n_out - before == derived, (n_out - before, derived)
    dev_set = set(
        zip(
            np.asarray(out[0][:n_out]).tolist(),
            np.asarray(out[1][:n_out]).tolist(),
            np.asarray(out[2][:n_out]).tolist(),
        )
    )
    assert dev_set == r.facts.triples_set()
    print(
        json.dumps(
            {
                "metric": "lubm_rule_closure_device",
                "derived": derived,
                "rounds": int(out[4]),
                "compile_s": round(t_first, 1),
                "ms": round(1000 * best, 3),
                "derived_per_sec": round(derived / max(best, 1e-9), 1),
            }
        )
    )


def section_sharded():
    import jax

    from kolibrie_tpu.parallel.dist_join import dist_bgp_join_count_device
    from kolibrie_tpu.parallel.mesh import make_mesh
    from kolibrie_tpu.parallel.sharded_store import ShardedTripleStore

    db, (s, p, o), _ = build_db()
    n = len(db.store)
    n_dev = len(jax.devices())
    mesh = make_mesh(n_dev)
    preds = predicate_ids(db.dictionary)
    store = ShardedTripleStore.from_columns(mesh, s, p, o)
    p1, p2 = preds["advisor"], preds["teacherOf"]
    # Timing discipline: no host readback until all dispatches are timed.
    out = dist_bgp_join_count_device(store, p1, p2)  # compile + warm
    jax.block_until_ready(out)
    t_join = float("inf")
    for _ in range(10):
        t0 = time.perf_counter()
        out = dist_bgp_join_count_device(store, p1, p2)
        jax.block_until_ready(out)
        t_join = min(t_join, time.perf_counter() - t0)
    count = int(out[0])
    lv, lc = np.unique(o[p == p1], return_counts=True)
    rv, rc = np.unique(s[p == p2], return_counts=True)
    _, li, ri = np.intersect1d(lv, rv, return_indices=True)
    host = int((lc[li] * rc[ri]).sum())
    assert count == host, (count, host)
    print(
        json.dumps(
            {
                "metric": "lubm_sharded_bgp_join",
                "devices": n_dev,
                "platform": jax.devices()[0].platform,
                "matches": int(count),
                "ms": round(1000 * t_join, 2),
                "triples_per_sec_per_chip": round(n / t_join / max(n_dev, 1), 1),
            }
        )
    )


def section_dist_query():
    """FULL distributed SPARQL plans (BASELINE config 5): Q2/Q9 lowered
    onto the mesh — sharded scans, all_to_all repartition between join
    stages, local joins, filters, projection — timed as the un-read device
    dispatch; rows verified equal to the host engine afterwards."""
    import jax

    from kolibrie_tpu.parallel.dist_query import DistQueryExecutor
    from kolibrie_tpu.parallel.mesh import make_mesh
    from kolibrie_tpu.query.executor import execute_query_volcano

    db, _cols, _ = build_db()
    n = len(db.store)
    n_dev = len(jax.devices())
    mesh = make_mesh(n_dev)
    execs = {}
    for name, query in (("lubm_q2", LUBM_Q2), ("lubm_q9", LUBM_Q9)):
        ex = DistQueryExecutor(mesh, db, query)
        outs = ex.run_device()  # builds store, converges capacities
        jax.block_until_ready(outs[0])
        execs[name] = (ex, query, outs)
    results = {}
    for name, (ex, _q, outs) in execs.items():
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            outs = ex.run_device()
            jax.block_until_ready(outs[0])
            best = min(best, time.perf_counter() - t0)
        results[name] = (best, outs)
    # verification AFTER all timing (readback discipline)
    db.execution_mode = "host"
    for name, (ex, query, _outs) in execs.items():
        best, _ = results[name]
        rows = ex.run()
        host_rows = execute_query_volcano(query, db)
        assert rows == host_rows, f"{name}: dist/host row mismatch"
        print(
            json.dumps(
                {
                    "metric": f"{name}_dist_plan_wall_clock",
                    "devices": n_dev,
                    "platform": jax.devices()[0].platform,
                    "rows": len(rows),
                    "ms": round(1000 * best, 3),
                    "triples_per_sec_per_chip": round(
                        n / best / max(n_dev, 1), 1
                    ),
                }
            )
        )


def section_load10m():
    """10M-triple N-Triples bulk load through the public parser (native
    C++ tokenizer fast path) — the reference's ``n_triple_10M.rs`` example,
    fed in 1M-line chunks the way a file stream would arrive."""
    from kolibrie_tpu.query.sparql_database import SparqlDatabase

    n_total = int(os.environ.get("LUBM_BULK_TRIPLES", "10000000"))
    n_subjects = n_total // 4
    db = SparqlDatabase()
    chunk = 250_000  # subjects per chunk -> 1M triples
    loaded = 0
    t_parse = 0.0
    for start in range(0, n_subjects, chunk):
        end = min(start + chunk, n_subjects)
        lines = []
        for i in range(start, end):
            e = f"<https://data.example/employee/{i}>"
            lines.append(f'{e} <http://xmlns.com/foaf/0.1/name> "Employee {i}" .')
            lines.append(
                f"{e} <https://data.example/ontology#dept> "
                f"<https://data.example/dept/{i % 500}> ."
            )
            lines.append(
                f"{e} <http://xmlns.com/foaf/0.1/workplaceHomepage> "
                f"<https://company{i % 997}.example/> ."
            )
            lines.append(
                f'{e} <https://data.example/ontology#annual_salary> '
                f'"{30000 + (i % 50) * 1000}" .'
            )
        text = "\n".join(lines)
        t0 = time.perf_counter()
        loaded += db.parse_ntriples(text)
        t_parse += time.perf_counter() - t0
    n_stored = len(db.store)
    print(
        json.dumps(
            {
                "metric": "bulk_load_10m_ntriples",
                "triples_parsed": loaded,
                "triples_stored": n_stored,
                "seconds": round(t_parse, 2),
                "triples_per_sec": round(loaded / t_parse, 1),
            }
        )
    )


def main():
    if len(sys.argv) > 1 and sys.argv[1].startswith("--section"):
        name = sys.argv[1].split("=", 1)[1] if "=" in sys.argv[1] else sys.argv[2]
        globals()[f"section_{name}"]()
        return
    here = str(Path(__file__).resolve())
    for name in SECTIONS:
        proc = subprocess.run(
            [sys.executable, here, f"--section={name}"],
            capture_output=True,
            text=True,
        )
        sys.stdout.write(proc.stdout)
        sys.stdout.flush()
        if proc.returncode != 0:
            sys.stderr.write(proc.stderr[-2000:])

if __name__ == "__main__":
    main()
