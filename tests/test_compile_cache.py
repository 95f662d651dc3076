"""Persistent compilation cache + pre-warm manifest + restart guarantee.

The acceptance property (satellite to the compile-tail PR): a restarted
process pointed at a populated cache directory, after replaying the
pre-warm manifest, serves its first query with ZERO new XLA compiles —
``device_compile_stats()`` delta 0 and persistent-cache miss delta 0 —
and byte-identical rows to the process that populated the cache.
"""

import json
import os
import subprocess
import sys
import threading
import urllib.request

import pytest

from kolibrie_tpu.query import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _own_cache_dirs(monkeypatch):
    """These cases place cache directories of their own: an externally
    placed cache (``JAX_COMPILATION_CACHE_DIR``) would win over them."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)


# --------------------------------------------------------------- unit layer


def test_namespace_is_version_and_backend_scoped():
    import jax

    ns = compile_cache.cache_namespace()
    assert jax.__version__ in ns
    assert ns.endswith(jax.default_backend())


def test_enable_resolution_and_idempotence(tmp_path, monkeypatch):
    monkeypatch.delenv("KOLIBRIE_COMPILE_CACHE_DIR", raising=False)
    assert compile_cache.enable() is None  # no location configured
    d1 = compile_cache.enable(data_dir=str(tmp_path / "data"))
    assert d1 is not None and os.path.isdir(d1)
    assert compile_cache.cache_namespace() in d1
    assert compile_cache.enable(data_dir=str(tmp_path / "data")) == d1
    assert compile_cache.enabled_dir() == d1
    st = compile_cache.stats()
    assert st["enabled"] and st["dir"] == d1
    # explicit env var wins over data_dir
    monkeypatch.setenv("KOLIBRIE_COMPILE_CACHE_DIR", str(tmp_path / "env"))
    d2 = compile_cache.enable(data_dir=str(tmp_path / "data"))
    assert str(tmp_path / "env") in d2


def test_env_placed_cache_is_recorded_not_set(tmp_path, monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set, JAX reads the directory itself:
    enable() records it as active and never sets one in code — whatever
    arguments it was given."""
    import jax

    monkeypatch.setattr(compile_cache, "_active_dir", None)
    monkeypatch.setattr(compile_cache, "_active_root", None)
    placed = str(tmp_path / "placed")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    before = jax.config.jax_compilation_cache_dir
    got = compile_cache.enable(
        data_dir=str(tmp_path / "data"), explicit_dir=str(tmp_path / "arg")
    )
    assert got == placed == compile_cache.enabled_dir()
    assert jax.config.jax_compilation_cache_dir == before
    assert not os.path.exists(tmp_path / "data")
    assert not os.path.exists(tmp_path / "arg")
    # the manifest sits beside the entries, no namespace segment between
    assert compile_cache.manifest_path() == os.path.join(
        placed, "prewarm_manifest.json"
    )


def test_manifest_roundtrip(tmp_path, monkeypatch):
    # isolate the process-global tally: earlier suite tests run real
    # queries and their templates would outrank the synthetic ones
    monkeypatch.setattr(compile_cache, "_templates", {})
    root = str(tmp_path / "cc")
    for i in range(5):
        for _ in range(i + 1):
            compile_cache.record_template(f"fp{i}", f"SELECT {i}")
    with compile_cache.suppress_recording():
        compile_cache.record_template("suppressed", "NOPE")
    snap = compile_cache.manifest_snapshot()
    assert snap[0]["fp"] == "fp4" and snap[0]["hits"] == 5
    assert all(e["fp"] != "suppressed" for e in snap)
    path = compile_cache.save_manifest(root)
    assert path and os.path.isfile(path)
    loaded = compile_cache.load_manifest(root)
    assert loaded[0] == {"fp": "fp4", "query": "SELECT 4", "hits": 5}
    # merge keeps the on-disk maximum
    compile_cache.save_manifest(root)
    assert compile_cache.load_manifest(root)[0]["hits"] == 5


def test_manifest_tolerates_corruption(tmp_path):
    root = str(tmp_path / "cc")
    os.makedirs(root)
    with open(os.path.join(root, "prewarm_manifest.json"), "w") as f:
        f.write('{"version": 1, "templates": [{"q"')  # torn write
    assert compile_cache.load_manifest(root) == []


# ------------------------------------------------- restart regression test

_PROC = r"""
import json, os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, {repo!r})
from kolibrie_tpu.query import compile_cache
from kolibrie_tpu.query.prewarm import replay_manifest
import kolibrie_tpu.optimizer.device_engine as de
from kolibrie_tpu.query.executor import execute_query_volcano
from kolibrie_tpu.query.sparql_database import SparqlDatabase

ROOT = {root!r}
PHASE = {phase!r}
compile_cache.enable(explicit_dir=ROOT)

db = SparqlDatabase()
lines = []
for i in range(200):
    e = f"<http://example.org/e{{i}}>"
    lines.append(f'{{e}} <http://example.org/dept> "dept{{i % 5}}" .')
    lines.append(f'{{e}} <http://example.org/salary> "{{20 + (i % 50)}}" .')
db.parse_ntriples("\n".join(lines))
db.execution_mode = "device"

QUERIES = [
    'PREFIX ex: <http://example.org/>\n'
    'SELECT ?e ?s WHERE {{ ?e ex:dept "dept2" . ?e ex:salary ?s . '
    'FILTER(?s > 30) }}',
    'PREFIX ex: <http://example.org/>\n'
    'SELECT ?e WHERE {{ ?e ex:dept "dept1" }}',
]

if PHASE == "seed":
    rows = [execute_query_volcano(q, db) for q in QUERIES]
    compile_cache.save_manifest(ROOT)
    print(json.dumps({{
        "rows": rows,
        "misses": compile_cache.counters()["misses"],
    }}))
else:
    warmed = replay_manifest(db, root=ROOT)
    jit_before = de.device_compile_stats()
    cc_before = compile_cache.counters()
    rows = [execute_query_volcano(q, db) for q in QUERIES]
    print(json.dumps({{
        "rows": rows,
        "warmed": len(warmed),
        "jit_delta": {{k: v - jit_before[k]
                      for k, v in de.device_compile_stats().items()}},
        "miss_delta": compile_cache.counters()["misses"] - cc_before["misses"],
        "replay_hits": cc_before["hits"],
    }}))
"""


def _run_proc(root: str, phase: str) -> dict:
    env = dict(os.environ)
    env.pop("KOLIBRIE_PLAN_INTERP", None)
    env.pop("KOLIBRIE_COMPILE_CACHE_DIR", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", _PROC.format(repo=REPO, root=root, phase=phase)],
        capture_output=True,
        text=True,
        timeout=240,
        env=env,
        cwd=REPO,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.splitlines()[-1])


def test_restart_serves_first_query_with_zero_compiles(tmp_path):
    """Process A compiles and populates the cache + manifest; process B
    replays the manifest at startup, then serves the same queries with
    zero new jit entries, zero persistent-cache misses, and identical
    rows."""
    root = str(tmp_path / "cc")
    a = _run_proc(root, "seed")
    assert a["misses"] > 0  # A really compiled (and wrote) the entries
    assert compile_cache.load_manifest(root), "A persisted the manifest"
    b = _run_proc(root, "serve")
    assert b["warmed"] == 2
    assert b["rows"] == a["rows"]  # byte-identical result payloads
    assert all(v == 0 for v in b["jit_delta"].values()), b["jit_delta"]
    assert b["miss_delta"] == 0
    assert b["replay_hits"] > 0  # the warm-up itself was served from disk


# ----------------------------------------------------- /debug/prewarm route


@pytest.fixture()
def durable_server(tmp_path, monkeypatch):
    from kolibrie_tpu.frontends.http_server import (
        make_server,
        shutdown_gracefully,
    )

    # isolate the process-wide manifest accumulator: entries recorded by
    # other tests in this module must not leak into the warm sweep
    monkeypatch.setattr(compile_cache, "_templates", {})

    httpd = make_server(
        "127.0.0.1", 0, quiet=True,
        data_dir=str(tmp_path / "data"), recover_async=False,
    )
    port = httpd.server_address[1]
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    yield f"http://127.0.0.1:{port}", httpd
    shutdown_gracefully(httpd, timeout_s=5)


def _post(base, path, payload=None):
    req = urllib.request.Request(
        base + path,
        data=json.dumps(payload or {}).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    with urllib.request.urlopen(req) as resp:
        return json.loads(resp.read())


def test_debug_prewarm_endpoint(durable_server, tmp_path):
    base, _httpd = durable_server
    r = _post(base, "/store/load", {
        "rdf": "<http://a> <http://p> <http://b> .\n"
               "<http://b> <http://p> <http://c> .",
        "format": "ntriples",
        "mode": "device",
    })
    sid = r["store_id"]
    q = "SELECT ?s ?o WHERE { ?s <http://p> ?o }"
    rows = _post(base, "/store/query", {"store_id": sid, "sparql": q})
    assert rows["data"]
    warm = _post(base, "/debug/prewarm")
    assert warm["compile_cache"]["enabled"]
    assert warm["manifest"]
    (entry,) = [e for e in warm["warmed"] if e["targets"]]
    res = entry["targets"][sid]
    assert res["ms"] >= 0 and res["source"] in ("compiled", "disk")
    # /stats carries the compile-tail block
    with urllib.request.urlopen(base + "/stats") as resp:
        stats = json.loads(resp.read())
    assert stats["compile_tail"]["cache"]["enabled"]
    assert "prewarm" in stats["compile_tail"]
