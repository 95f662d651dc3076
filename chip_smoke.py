#!/usr/bin/env python3
"""Chip smoke: serve LUBM queries from a device-resident store on one TPU.

One process — the only one that touches JAX.  It starts the real HTTP
server in a thread, loads generated LUBM through ``POST /store/load``
(default ``"device"`` mode), sends queries through ``POST /store/query``,
compares every answer with the host (numpy) engine as a multiset, and then
PROVES the device served them: platform, Pallas not interpreted, every
query counted on ``path="device"`` (or the batched dispatch), none
degraded, no sticky lowering failure, no open breaker, jit entry points
grew.  Off-TPU every phase still runs (rehearsal), the last line says
``"ok": false`` and the exit code is 1 — no option turns that into a pass.

``--mesh`` (four chips, run by hand) runs ONLY the sharded-serving path and
what it is compared with: every request, alone or in a group, has to be
served from the partitioned store.

Every output line is one JSON object.  Times are smoke observations taken
on the host clock around whole HTTP requests, not benchmark numbers.  The
last line is ``{"ok": ..., "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import resource
import sys
import threading
import time
import urllib.error
import urllib.request
from collections import Counter

HERE = os.path.dirname(os.path.abspath(__file__))
UB = "http://swat.cse.lehigh.edu/onto/univ-bench.owl#"
PREFIXES = (
    "PREFIX rdf: <http://www.w3.org/1999/02/22-rdf-syntax-ns#>\n"
    f"PREFIX ub: <{UB}>\n"
)
# explicit per-request budget: a cold whole-plan compile may outlast the
# server's 30 s default, and a shed request would trip the template's
# breaker and let the HOST serve the warm repeat
DEADLINE_MS = 900_000
LOAD_CHUNK_BYTES = 48 * 1024 * 1024  # under the 64 MiB request limit


def say(**obj) -> None:
    if "phase" in obj or "query" in obj:
        # host memory high-water mark, MiB (ru_maxrss is KiB on Linux)
        obj["host_peak_rss_mib"] = (
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss // 1024
        )
    print(json.dumps(obj, sort_keys=True), flush=True)


# ------------------------------------------------------------------ http


class Client:
    """urllib client of the in-process server; remembers every status."""

    def __init__(self, port: int):
        self.base = f"http://127.0.0.1:{port}"
        self.statuses: Counter = Counter()
        self._lock = threading.Lock()

    def _open(self, req):
        try:
            with urllib.request.urlopen(req, timeout=DEADLINE_MS / 1000) as r:
                status, body = r.status, r.read()
        except urllib.error.HTTPError as e:
            status, body = e.code, e.read()
        with self._lock:
            self.statuses[status] += 1
        if status != 200:
            raise RuntimeError(
                f"{req.full_url} -> HTTP {status}: {body[:400]!r}"
            )
        return body

    def post(self, path: str, payload: dict, trace_id: str = "") -> dict:
        headers = {"Content-Type": "application/json"}
        if trace_id:
            headers["X-Kolibrie-Trace-Id"] = trace_id
        req = urllib.request.Request(
            self.base + path, data=json.dumps(payload).encode(),
            headers=headers,
        )
        return json.loads(self._open(req))

    def get_json(self, path: str) -> dict:
        return json.loads(self._open(urllib.request.Request(self.base + path)))

    def get_text(self, path: str) -> str:
        return self._open(urllib.request.Request(self.base + path)).decode()

    def query(self, store_id: str, sparql: str, trace_id: str = ""):
        """(rows, wall ms) of one ``/store/query`` round trip."""
        t0 = time.perf_counter()
        body = self.post(
            "/store/query",
            {"store_id": store_id, "sparql": sparql, "deadline_ms": DEADLINE_MS},
            trace_id,
        )
        return body["data"], (time.perf_counter() - t0) * 1000.0

    def span_ms(self, trace_id: str) -> dict:
        """The server's own spans of one traced request: name -> total ms."""
        out: dict = {}
        for line in self.get_text(f"/debug/traces?trace_id={trace_id}").splitlines():
            sp = json.loads(line)
            out[sp["name"]] = round(out.get(sp["name"], 0.0) + sp["dur_ms"], 3)
        return out


def metric(text: str, name: str, labels: str = "") -> float:
    """One sample of the Prometheus exposition (0 when absent)."""
    key = name + labels
    for line in text.splitlines():
        if line.startswith(key + " "):
            return float(line.split()[-1])
    return 0.0


# ------------------------------------------------------------------ data


def lubm_ntriples(n_universities: int):
    """LUBM from the repo's generator, as N-Triples chunks each under the
    server's request limit.  The generator is a pure function of the
    university count (its only pseudo-random choice is a fixed hash), so
    ``--seed`` selects query constants, not data."""
    import numpy as np

    from benches import lubm
    from kolibrie_tpu.core.dictionary import Dictionary

    d = Dictionary()
    s, p, o = lubm.generate_fast(n_universities, d)
    terms = np.array(
        ["" if t is None else f"<{t}>" for t in d.id_to_str], dtype=object
    )
    chunks, step = [], 100_000
    cur, cur_bytes = [], 0
    for i in range(0, len(s), step):
        j = slice(i, i + step)
        text = "".join(
            terms[s[j]] + " " + terms[p[j]] + " " + terms[o[j]] + " .\n"
        )
        if cur and cur_bytes + len(text) > LOAD_CHUNK_BYTES:
            chunks.append("".join(cur))
            cur, cur_bytes = [], 0
        cur.append(text)
        cur_bytes += len(text)
    if cur:
        chunks.append("".join(cur))
    return chunks, len(s)


def smoke_queries(n_universities: int, seed: int, mesh: bool):
    """(name, sparql) solo queries and the 8 constant-variants of one
    template.  Constants are drawn from ``seed``."""
    import numpy as np

    from benches import lubm

    rng = np.random.default_rng(seed)
    n_depts = n_universities * lubm.DEPTS_PER_UNIV
    picks = rng.choice(n_depts, size=8, replace=n_depts < 8)
    depts = [
        f"http://www.Department{int(k) % lubm.DEPTS_PER_UNIV}"
        f".University{int(k) // lubm.DEPTS_PER_UNIV}.edu"
        for k in picks
    ]
    univ = f"http://www.University{int(rng.integers(n_universities))}.edu"
    solo = [("lubm_q2", lubm.LUBM_Q2), ("lubm_q9", lubm.LUBM_Q9)]
    if not mesh:
        solo += [
            (
                "join_iri_filter",
                PREFIXES
                + "SELECT ?x ?z WHERE { ?x ub:worksFor ?z . "
                "?z ub:subOrganizationOf ?y . "
                f"FILTER(?y = <{univ}>) }}",
            ),
            (
                "group_count",
                PREFIXES
                + "SELECT ?z (COUNT(?x) AS ?n) WHERE { ?x ub:worksFor ?z . "
                f"?z ub:subOrganizationOf <{univ}> }} GROUP BY ?z",
            ),
        ]
    variants = [
        PREFIXES
        + "SELECT ?x ?y ?c WHERE { "
        f"?x ub:memberOf <{d}> . ?x ub:advisor ?y . ?y ub:teacherOf ?c }}"
        for d in depts
    ]
    return solo, variants


def multiset(rows) -> Counter:
    return Counter(tuple(r) for r in rows)


# ------------------------------------------------------------------ main


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--universities", type=int, default=None,
                    help="LUBM scale (default 1000; 200 with --mesh)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", action="store_true",
                    help="four chips: run only the sharded-serving path")
    args = ap.parse_args()
    n_univ = args.universities or (200 if args.mesh else 1000)
    if args.mesh:
        # the way a deployment switches it on: before http_server imports
        os.environ["KOLIBRIE_SHARDED"] = "1"

    import jax

    from kolibrie_tpu import native
    from kolibrie_tpu.frontends import http_server
    from kolibrie_tpu.ops import pallas_kernels
    from kolibrie_tpu.query import compile_cache

    # the persistent cache goes on before the first lowering; where
    # JAX_COMPILATION_CACHE_DIR is set, enable() records it and sets nothing
    cache_dir = compile_cache.enable(
        explicit_dir=os.path.join(HERE, ".jax_cache")
    )
    dev0 = jax.devices()[0]
    device = {
        "platform": dev0.platform,
        "kind": dev0.device_kind,
        "count": len(jax.devices()),
    }
    say(phase="start", device=device, jax=jax.__version__, mesh=args.mesh,
        universities=n_univ, seed=args.seed, compile_cache_dir=cache_dir,
        native_tokenizer=native.available(),
        pallas_enabled=pallas_kernels.pallas_enabled(),
        pallas_interpret=pallas_kernels._interpret())
    failures = []

    def check(name: str, cond: bool, **detail) -> None:
        if not cond:
            failures.append(name)
        say(check=name, ok=bool(cond), **detail)

    httpd = http_server.make_server("127.0.0.1", 0, quiet=True, data_dir=None)
    server = threading.Thread(target=httpd.serve_forever, daemon=True)
    server.start()
    try:
        run(args, n_univ, device, httpd, check)
    finally:
        httpd.shutdown()
        httpd.server_close()
        server.join(timeout=30)
    if failures:
        say(phase="failed_checks", checks=failures)
    print(json.dumps({"ok": not failures, "device": device}), flush=True)
    return 1 if failures else 0


def run(args, n_univ, device, httpd, check) -> None:
    import jax

    from kolibrie_tpu.ops import pallas_kernels
    from kolibrie_tpu.optimizer.device_engine import device_compile_stats
    from kolibrie_tpu.query import compile_cache
    from kolibrie_tpu.query.executor import execute_query_volcano
    from kolibrie_tpu.query.sparql_database import SparqlDatabase
    from kolibrie_tpu.frontends.rules import strip_hash_comments

    cl = Client(httpd.server_address[1])

    # ---- load: generated LUBM through /store/load, default (device) mode
    t0 = time.perf_counter()
    chunks, n_triples = lubm_ntriples(n_univ)
    gen_s = time.perf_counter() - t0
    sid, t0 = "lubm", time.perf_counter()
    for text in chunks:
        body = cl.post(
            "/store/load",
            {"store_id": sid, "rdf": text, "format": "ntriples"},
        )
    load_s = time.perf_counter() - t0
    batcher = httpd.RequestHandlerClass.state.stores[sid]
    db = batcher.db
    say(phase="load", triples=body["triples"], generated=n_triples,
        chunks=len(chunks), generate_seconds_smoke=round(gen_s, 3),
        load_seconds_smoke=round(load_s, 3), execution_mode=db.execution_mode)
    check("loaded_all_triples", body["triples"] == n_triples,
          triples=body["triples"], expected=n_triples)
    check("store_is_device_mode", db.execution_mode == "device")

    # ---- plain reference: the numpy engine on a second database
    host_db = SparqlDatabase()
    for text in chunks:
        host_db.parse_ntriples(text)
    host_db.execution_mode = "host"
    del chunks

    solo, variants = smoke_queries(n_univ, args.seed, args.mesh)
    compiles0 = device_compile_stats()
    metrics0 = cl.get_text("/metrics")
    n_sent = 0

    def compare(name, sparql, rows_by_pass, ms_by_pass, **extra) -> None:
        want = multiset(execute_query_volcano(sparql, host_db))
        equal = all(multiset(rows) == want for rows in rows_by_pass)
        say(query=name, rows=sum(want.values()), rows_equal_host=equal,
            cold_wall_ms_smoke=round(ms_by_pass[0], 3),
            warm_wall_ms_smoke=round(ms_by_pass[1], 3), **extra)
        check(f"rows_equal_host:{name}", equal)
        check(f"rows_nonempty:{name}", sum(want.values()) > 0)

    # ---- solo queries, each sent twice (cold, warm)
    for name, sparql in solo:
        got = [cl.query(sid, sparql, f"smoke-{name}-{tag}")
               for tag in ("cold", "warm")]
        n_sent += 2
        compare(name, sparql, [g[0] for g in got], [g[1] for g in got],
                warm_span_ms_smoke=cl.span_ms(f"smoke-{name}-warm"))

    # ---- 8 constant-variants of one template, concurrently, twice: the
    # batcher stacks them into one vmap (or, with --mesh, shard_map) dispatch
    passes = []
    for _ in range(2):
        out = [None] * len(variants)
        gate = threading.Barrier(len(variants))

        def one(i, out=out, gate=gate):
            gate.wait()
            out[i] = cl.query(sid, variants[i])

        ts = [threading.Thread(target=one, args=(i,))
              for i in range(len(variants))]
        t0 = time.perf_counter()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        passes.append((out, (time.perf_counter() - t0) * 1000.0))
        n_sent += len(variants)
    check("variants_all_answered",
          all(o is not None for out, _ in passes for o in out))
    for i, sparql in enumerate(variants):
        compare(f"variant{i}", sparql,
                [out[i][0] for out, _ in passes],
                [out[i][1] for out, _ in passes])
    say(phase="variant_batch", members=len(variants),
        cold_wall_ms_smoke=round(passes[0][1], 3),
        warm_wall_ms_smoke=round(passes[1][1], 3))

    # ---- prove the device served it
    stats = cl.get_json("/stats")["stores"][sid]
    metrics1 = cl.get_text("/metrics")
    compiles1 = device_compile_stats()

    def delta(name, labels=""):
        return metric(metrics1, name, labels) - metric(metrics0, name, labels)

    on_device = delta("kolibrie_query_seconds_count", '{path="device"}')
    degraded = delta("kolibrie_query_seconds_count", '{path="degraded"}')
    batched = delta("kolibrie_query_batched_total")
    mem = jax.devices()[0].memory_stats() or {}
    say(phase="observations", sent=n_sent, path_device=on_device,
        path_degraded=degraded, batched=batched,
        device_compile_stats=compiles1,
        compile_cache=compile_cache.counters(),
        compile_cache_dir=compile_cache.enabled_dir(),
        device_bytes_in_use=mem.get("bytes_in_use"),
        device_peak_bytes_in_use=mem.get("peak_bytes_in_use"),
        batcher={k: stats[k] for k in
                 ("requests", "dispatches", "max_batch", "dedup_hits")},
        http_statuses={str(k): v for k, v in sorted(cl.statuses.items())})
    check("platform_is_tpu", device["platform"] == "tpu",
          platform=device["platform"])
    check("pallas_enabled_not_interpreted",
          pallas_kernels.pallas_enabled() and not pallas_kernels._interpret())
    check("every_query_on_device_path", on_device + batched == n_sent,
          path_device=on_device, batched=batched, sent=n_sent)
    check("none_degraded", degraded == 0, path_degraded=degraded)
    check("batch_dispatch_ran", batched >= 2, batched=batched)
    check("no_sticky_lowering_failure",
          stats["plan_cache"]["sticky_failures"] == 0,
          sticky_failures=stats["plan_cache"]["sticky_failures"])
    # which engine produced each template's last solo dispatch: "compiled"
    # / "disk" = the specialized jit (real compile / persistent-cache hit);
    # the variant template is None when its last dispatch was the batch
    names = {}
    for name, text in solo + [("variants", v) for v in variants]:
        ent = db.__dict__["_plan_cache"].get(strip_hash_comments(text))
        names.setdefault((ent or {}).get("fp"), name)
    sources = {names.get(fp, fp): t["source"]
               for fp, t in stats["plan_cache"]["per_template"].items()}
    # (the fused on-device GROUP BY dispatches run()/converge() itself and
    # leaves no source: None is its device signature, a host fallback
    # would have left a sticky failure or a degraded count above)
    # (under an attached mesh every request of a supported shape is served
    # from the partitioned store, alone or in a group: no single-device
    # plan runs, so none is asked for; check_mesh counts them instead)
    if not args.mesh:
        check("solo_templates_served_by_compiled_plans",
              all(sources.get(name) in ("compiled", "disk")
                  or (name == "group_count" and sources.get(name) is None)
                  for name, _ in solo),
              sources=sources)
    bad = {fp: b for fp, b in stats["breakers"].items()
           if b["state"] != "closed" or b["total_failures"]}
    check("no_breaker_open_or_failed", not bad, breakers=bad)
    check("only_http_200", set(cl.statuses) == {200},
          statuses={str(k): v for k, v in cl.statuses.items()})
    if args.mesh:
        check_mesh(db, stats, metrics1, check, n_sent,
                   delta("kolibrie_shard_queries_total"))
    else:
        check("run_plan_compiled",
              compiles1["run_plan"] > compiles0["run_plan"],
              before=compiles0["run_plan"], after=compiles1["run_plan"])
        check("run_plan_batch_compiled",
              compiles1["run_plan_batch"] > compiles0["run_plan_batch"],
              before=compiles0["run_plan_batch"],
              after=compiles1["run_plan_batch"])
        check_q9_lowering(db, dict(solo)["lubm_q9"], check)


def check_q9_lowering(db, q9: str, check) -> None:
    """The Q9 plan, lowered exactly as ``LoweredPlan.run`` dispatches it,
    must carry the Mosaic kernel (``tpu_custom_call``)."""
    import jax

    from kolibrie_tpu.ops import pallas_kernels
    from kolibrie_tpu.optimizer import device_engine as de
    from kolibrie_tpu.query.executor import _plan_cache_entry

    _ent, slot = _plan_cache_entry(db, q9)
    lowered = slot.get("lowered")
    if not lowered:
        check("q9_plan_has_tpu_custom_call", False, reason="no lowered plan")
        return
    spec, plan_args = lowered.build()
    with jax.enable_x64(True):
        text = de._run_plan.lower(
            spec, pallas_kernels.pallas_enabled(), *plan_args
        ).as_text()
    check("q9_plan_has_tpu_custom_call", "tpu_custom_call" in text,
          lowered_chars=len(text))


def check_mesh(db, stats, metrics_text, check, n_sent, served) -> None:
    import jax

    check("every_query_served_by_the_mesh", served == n_sent,
          served=served, sent=n_sent)
    sh = db.__dict__.get("_sharded_serving")
    check("four_devices", jax.device_count() == 4, count=jax.device_count())
    check("store_has_sharded_attachment", sh is not None)
    errs = metric(metrics_text, "kolibrie_shard_attach_errors_total")
    check("no_sharded_attach_errors", errs == 0, errors=errs)
    if sh is None:
        return
    check("mesh_spans_four_devices", sh.mesh.devices.size == 4,
          mesh=str(dict(sh.mesh.shape)))
    block = stats.get("sharding", {})
    check("dispatches_recorded_sharded",
          block.get("dispatches", 0) >= 1
          and block.get("batched_queries", 0) >= 2
          and block.get("fallbacks", 0) == 0,
          **{k: block.get(k) for k in
             ("shards", "dispatches", "batched_queries", "fallbacks",
              "cap_hits", "occupancy", "imbalance")})
    view = sh.view
    arrays = [*view.by_subj, view.by_subj_valid, *view.by_obj,
              view.by_obj_valid]
    spans = [len({s.device.id for s in a.addressable_shards
                  if s.data.size}) for a in arrays]
    check("shard_arrays_span_four_devices", min(spans) == 4,
          arrays=len(spans), min_devices=min(spans))


if __name__ == "__main__":
    sys.exit(main())
