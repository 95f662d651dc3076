"""First-sight records (ISSUE 38): one record for every executable the
process builds or loads, the compile journal that carries them to the next
process, and why a miss was a miss.

The persistent cache belongs to a process (JAX opens its directory once, and
the suite's own cache keeps small entries out), so the cases that need a
cache directory of their own run in child processes, as
``test_restart_serves_first_query_with_zero_compiles`` does: ``seed`` first,
then ``serve`` on the directory ``seed`` left.
"""

import json
import os
import subprocess
import sys
import threading
import urllib.request

import pytest

from kolibrie_tpu.obs import spans
from kolibrie_tpu.query import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.harness import data as files  # noqa: E402

_PROC = r"""
import json, os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, {repo!r})
from kolibrie_tpu.obs import spans
from kolibrie_tpu.query import compile_cache as cc

ROOT, PHASE, WIDE_KEY = {root!r}, {phase!r}, {wide_key!r}
cc.enable(explicit_dir=ROOT)
inherited = cc.load_journal()

import jax, jax.numpy as jnp
from jax._src import monitoring
import kolibrie_tpu.optimizer.device_engine as de
from kolibrie_tpu.query.executor import execute_query_volcano
from kolibrie_tpu.query.sparql_database import SparqlDatabase

backend_steps = [0]
def count_steps(event, duration, **kw):
    backend_steps[0] += event == cc._BACKEND_EVENT
monitoring.register_event_duration_secs_listener(count_steps)

out = {{"inherited_pids": sorted({{r["pid"] for r in inherited}}), "pid": os.getpid()}}

# ---- a LoweredPlan dispatch: last_source, `compiled` and the record
sources = []
plain_execute = de.LoweredPlan.execute
def execute(self):
    table = plain_execute(self)
    sources.append(self.last_source)
    return table
de.LoweredPlan.execute = execute

db = SparqlDatabase()
db.parse_ntriples("\n".join(
    f'<http://example.org/e{{i}}> <http://example.org/dept> "dept{{i % 5}}" .\n'
    f'<http://example.org/e{{i}}> <http://example.org/salary> "{{20 + i % 50}}" .'
    for i in range(200)))
db.execution_mode = "device"
QUERY = ('PREFIX ex: <http://example.org/>\n'
         'SELECT ?e ?s WHERE {{ ?e ex:dept "dept2" . ?e ex:salary ?s }}')
dispatches = []
for _ in range(2):
    spans.clear()
    seen = len(cc.records())
    with spans.trace_scope():
        execute_query_volcano(QUERY, db)
    enqueue = [s for s in spans.spans_snapshot() if s["name"] == "device.enqueue"]
    dispatches.append({{
        "source": sources[-1],
        "compiled": [s["attrs"]["compiled"] for s in enqueue],
        "records": [r for r in cc.records()[seen:] if r["entry"] == "run_plan"],
    }})
out["dispatches"] = dispatches

# ---- a small jit under the name of an entry point
@jax.jit
def _run_plan(x):
    return jnp.sin(x) * 2 + 1

def sight(n):
    x = jnp.ones(n)
    cc.call(_run_plan, x)
    return cc.last_sight()

def size_of(key):
    path = cc._entry_file(key)
    return os.path.getsize(path) if os.path.exists(path) else None

if PHASE == "seed":
    first = sight(8)
    out["first"], out["first_file_bytes"] = first, size_of(first["key"])
    out["warm"] = sight(8)
    jax.clear_caches()
    out["again"] = sight(8)
    os.remove(cc._entry_file(first["key"]))
    jax.clear_caches()
    out["lost"] = sight(8)
    out["wide"] = sight(16)
    out["backend_steps"], out["records"] = backend_steps[0], len(cc.records())
    out["journal_lines"] = len(cc.load_journal())
    # another root, whose journal has the identity of a shape this process
    # has not met under a key that is not JAX's
    moved_root = ROOT + "-moved"
    os.makedirs(moved_root)
    unmet = cc._identity("run_plan", "_run_plan", None, (jnp.ones(32),))
    with open(cc.journal_path(moved_root), "w") as f:
        f.write(json.dumps(dict(first, identity=unmet, key="jit__run_plan-0000")) + "\n")
    cc.enable(explicit_dir=moved_root)
    from jax._src import compilation_cache as jcc
    jcc.reset_cache()  # JAX opens its directory once a process
    jax.clear_caches()
    out["moved"] = sight(32)
    out["other_directory"] = sight(8)
else:
    out["hit"] = sight(8)
    os.remove(cc._entry_file(WIDE_KEY))
    out["lost_since_seed"] = sight(16)
print(json.dumps(out))
"""


def _run_script(script: str, **env_more) -> dict:
    """The last line a child process prints, as JSON."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_more)
    for name in ("KOLIBRIE_PLAN_INTERP", "KOLIBRIE_COMPILE_CACHE_DIR",
                 "JAX_COMPILATION_CACHE_DIR", "KOLIBRIE_MQO"):
        env.pop(name, None)
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=240, env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.splitlines()[-1])


def _run_proc(root: str, phase: str, wide_key: str = "") -> dict:
    return _run_script(
        _PROC.format(repo=REPO, root=root, phase=phase, wide_key=wide_key))


@pytest.fixture(scope="module")
def seed_and_serve(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("first_sight") / "cc")
    seed = _run_proc(root, "seed")
    serve = _run_proc(root, "serve", wide_key=seed["wide"]["key"])
    return root, seed, serve


# ------------------------------------------------ (a) miss_new, then a hit


def test_a_first_sight_is_miss_new_and_after_the_jit_cache_is_cleared_a_hit(
        seed_and_serve):
    _root, seed, _serve = seed_and_serve
    first, again = seed["first"], seed["again"]
    assert (first["outcome"], again["outcome"]) == ("miss_new", "hit")
    assert (first["fun"], first["entry"]) == ("_run_plan", "run_plan")
    assert first["key"] == again["key"] and first["key"].startswith("jit__run_plan-")
    assert first["identity"] == again["identity"] and len(first["identity"]) == 32
    for rec in (first, again):
        assert rec["trace_s"] > 0 and rec["lower_s"] > 0 and rec["backend_s"] > 0
    assert first["write"] == "ok" and "write" not in again
    assert first["bytes"] == seed["first_file_bytes"] > 0
    assert seed["warm"] is None  # the second call was warm: no record


def test_every_backend_step_jax_reports_has_exactly_one_record(seed_and_serve):
    _root, seed, _serve = seed_and_serve
    # the jits nobody wraps (jnp.ones, the store's own) have theirs too
    assert seed["backend_steps"] == seed["records"] == seed["journal_lines"] > 5


# ------------------------------------------------------ (b) miss_entry_lost


def test_an_entry_deleted_between_two_calls_is_miss_entry_lost(seed_and_serve):
    _root, seed, _serve = seed_and_serve
    lost = seed["lost"]
    assert lost["outcome"] == "miss_entry_lost"
    assert (lost["key"], lost["identity"]) == (
        seed["first"]["key"], seed["first"]["identity"])
    assert lost["write"] == "ok" and lost["bytes"] > 0  # and it is back


# ------------------------------------------------------- (c) miss_key_moved


def test_an_identity_the_journal_has_under_another_key_is_miss_key_moved(
        seed_and_serve):
    _root, seed, _serve = seed_and_serve
    moved = seed["moved"]
    assert moved["outcome"] == "miss_key_moved"
    assert moved["was"] == "jit__run_plan-0000" != moved["key"]
    assert moved["identity"] not in (seed["first"]["identity"], seed["wide"]["identity"])
    # the other shape has another identity: no earlier record, whatever its key
    assert seed["wide"]["outcome"] == "miss_new"
    assert seed["wide"]["identity"] != seed["first"]["identity"]


def test_the_cache_directorys_path_is_part_of_jaxs_key(seed_and_serve):
    """What the record is for: the same executable under another cache
    directory has another key (jax 0.9.0 hands XLA an autotune directory under
    the cache's path and hashes it), and the record says so.  Should JAX stop
    hashing the path, the entry is simply not there: lost."""
    _root, seed, _serve = seed_and_serve
    first, other = seed["first"], seed["other_directory"]
    assert other["identity"] == first["identity"]
    if other["key"] != first["key"]:
        assert (other["outcome"], other["was"]) == ("miss_key_moved", first["key"])
    else:
        assert other["outcome"] == "miss_entry_lost"


# ------------------------------------- (d) the next process reads the journal


def test_a_second_process_reads_what_the_first_wrote(seed_and_serve):
    root, seed, serve = seed_and_serve
    assert serve["inherited_pids"] == [seed["pid"]] and serve["pid"] != seed["pid"]
    hit = serve["hit"]
    assert hit["outcome"] == "hit"
    # the identity is the program's: it is the same in another process
    assert (hit["key"], hit["identity"]) == (
        seed["first"]["key"], seed["first"]["identity"])
    # only the journal knows the entry of the other shape was ever written
    lost = serve["lost_since_seed"]
    assert lost["outcome"] == "miss_entry_lost" and lost["key"] == seed["wide"]["key"]
    pids = {r["pid"] for r in compile_cache.load_journal(root)}
    assert pids == {seed["pid"], serve["pid"]}


# --------------------------------------- (e) a corrupt or a missing journal


def _fresh_journal_state(monkeypatch, root):
    """The module as a process that has just made ``root`` active and has
    indexed and recorded nothing yet."""
    for name, fresh in (("_landed", set()), ("_key_of", {}), ("_journal_lines", 0),
                        ("_records", type(compile_cache._records)()),
                        ("_active_root", root)):
        monkeypatch.setattr(compile_cache, name, fresh)


def test_a_corrupt_or_missing_journal_is_tolerated(tmp_path, monkeypatch):
    root = str(tmp_path / "cc")
    assert compile_cache.load_journal(root) == []  # no directory at all
    os.makedirs(root)
    good = {"fun": "f", "entry": "run_plan", "identity": "i" * 32, "key": "k-1",
            "outcome": "miss_new", "write": "ok"}
    with open(compile_cache.journal_path(root), "wb") as f:
        f.write(b'{"fun": "torn", "key"\n\xff\xfe not utf-8\n[1, 2]\n{"no": "fun"}\n')
        f.write(json.dumps(good).encode() + b"\n")
        f.write(b'{"fun": "cut off in the middle of a wri')
    assert compile_cache.load_journal(root) == [good]
    # adopted, it indexes the line that parsed and leaves the file alone
    _fresh_journal_state(monkeypatch, root)
    compile_cache._adopt_journal()
    assert "k-1" in compile_cache._landed
    assert compile_cache._key_of["i" * 32] == "k-1"
    assert compile_cache._journal_lines == 1


def test_the_journal_keeps_its_newest_lines(tmp_path, monkeypatch):
    root = str(tmp_path / "cc")
    os.makedirs(root)
    bound, slack = compile_cache._JOURNAL_MAX, compile_cache._JOURNAL_SLACK
    with open(compile_cache.journal_path(root), "w") as f:
        for i in range(bound + 10):
            f.write(json.dumps({"fun": "f", "n": i}) + "\n")
    _fresh_journal_state(monkeypatch, root)
    compile_cache._adopt_journal()  # trims what an earlier process left
    kept = compile_cache.load_journal(root)
    assert len(kept) == bound and kept[0]["n"] == 10
    for i in range(slack):
        compile_cache._journal_append({"fun": "g", "n": i})
    kept = compile_cache.load_journal(root)
    assert len(kept) == bound and kept[-1] == {"fun": "g", "n": slack - 1}
    with open(compile_cache.journal_path(root)) as f:
        assert sum(1 for _ in f) == bound  # the file itself was cut back


# ------------------------------------------- (f) a warm dispatch pays nothing


QUERY = ('PREFIX ex: <http://example.org/>\n'
         'SELECT ?e ?b ?d WHERE { ?e ex:boss ?b . ?b ex:dept ?d . ?e ex:dept ex:d%d }')


@pytest.fixture(scope="module")
def device_db():
    from kolibrie_tpu.query.sparql_database import SparqlDatabase

    db = SparqlDatabase()
    db.parse_ntriples("\n".join(
        f"<http://example.org/e{i}> <http://example.org/dept> <http://example.org/d{i % 5}> .\n"
        f"<http://example.org/e{i}> <http://example.org/boss> <http://example.org/e{i * 7 % 150}> ."
        for i in range(150)))
    db.execution_mode = "device"
    return db


def test_a_warm_dispatch_appends_no_record_and_hashes_nothing(
        device_db, monkeypatch):
    from kolibrie_tpu.query.executor import execute_query_volcano

    hashed = []
    plain = compile_cache._identity

    def spy(entry, fun, static, args):
        hashed.append((entry, fun))
        return plain(entry, fun, static, args)

    monkeypatch.setattr(compile_cache, "_identity", spy)
    rows = execute_query_volcano(QUERY % 1, device_db)  # its first sight
    assert ("run_plan", "_run_plan") in hashed
    del hashed[:]
    before = len(compile_cache.records())
    sights = []
    for dept in (1, 2, 3):  # other constants: the same template and executable
        execute_query_volcano(QUERY % dept, device_db)
        sights.append(compile_cache.last_sight())
    assert hashed == [] and sights == [None] * 3
    assert len(compile_cache.records()) == before
    assert rows == execute_query_volcano(QUERY % 1, device_db)
    # and the thread keeps nothing of the call's arguments alive
    assert compile_cache._tls.sight.args is None


def test_a_declared_call_records_its_own_jit_and_not_one_compiled_inside_it():
    """A program built at run time declares ``(entry, static)``, as the mesh
    does; a jit that its trace evaluates at compile time compiles first,
    inside the call, and is nobody's: the record waits for the function that
    was called."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def program():
        @jax.jit
        def _met_while_tracing(x):
            return x + 1

        @jax.jit
        def _program(x):
            with jax.ensure_compile_time_eval():  # a constant: compiled now
                _met_while_tracing(np.ones(3))
            return x * 2

        return _program

    sights = []
    for key in (("k", 4), ("k", 8)):
        compile_cache.call(program(), jnp.ones(5), declared=("mesh", key))
        sights.append(compile_cache.last_sight())
    assert [(r["fun"], r["entry"]) for r in sights] == [("_program", "mesh")] * 2
    # the static part is the program's own: another key, another identity
    assert sights[0]["identity"] != sights[1]["identity"]
    inner = [r for r in compile_cache.records() if r["fun"] == "_met_while_tracing"]
    assert inner and all(
        (r["entry"], r["identity"]) == ("other", "_met_while_tracing") for r in inner)


def test_the_mesh_program_declares_itself(mesh8):
    """``_run_group`` calls through ``compile_cache.call`` with the program's
    key and the mesh's size: the mesh executable's record has entry ``mesh``,
    a group of another size class has another identity, a warm group none."""
    from kolibrie_tpu.parallel.sharded_serving import attach_sharded
    from kolibrie_tpu.query.executor import execute_queries_batched
    from kolibrie_tpu.query.sparql_database import SparqlDatabase

    db = SparqlDatabase()
    db.parse_ntriples("\n".join(
        f"<http://example.org/e{i}> <http://example.org/dept> <http://example.org/d{i % 12}> .\n"
        f"<http://example.org/e{i}> <http://example.org/boss> <http://example.org/e{i * 7 % 96}> ."
        for i in range(96)))
    db.execution_mode = "host"
    attach_sharded(db, mesh8).refresh()
    query = ('PREFIX ex: <http://example.org/>\n'
             'SELECT ?e ?b WHERE { ?e ex:dept ex:d%d . ?e ex:boss ?b }')

    def group(depts):
        seen = compile_cache.records()  # the ring holds the newest 256: by identity
        rows = execute_queries_batched(db, [query % d for d in depts])
        assert all(rows)
        return [r for r in compile_cache.records()
                if r["entry"] != "other" and not any(r is old for old in seen)]

    (pair,) = group([1, 2])
    assert (pair["fun"], pair["entry"]) == ("_batched_body", "mesh")
    assert group([3, 4]) == []  # other constants, the same executable: warm
    (wider,) = group(range(12))  # past a slot class of 8: another program
    assert (wider["fun"], wider["entry"]) == ("_batched_body", "mesh")
    assert wider["identity"] != pair["identity"]


# ------------------------------------------------------ (g) compile.* spans


def test_compile_spans_are_children_of_device_enqueue_on_a_first_sight_only(
        device_db):
    from kolibrie_tpu.query.executor import execute_query_volcano

    query = ('PREFIX ex: <http://example.org/>\n'
             'SELECT ?e ?c WHERE { ?e ex:boss ?b . ?b ex:boss ?c . ?c ex:dept ex:d%d }')
    traces = []
    for dept in (1, 2):
        with spans.trace_scope() as trace_id:
            execute_query_volcano(query % dept, device_db)
        traces.append(spans.spans_snapshot(trace_id))
    first, second = traces
    by_id = {s["span_id"]: s for s in first}
    steps = [s for s in first if s["name"].startswith("compile.")]
    assert [s["name"] for s in steps] == [
        "compile.trace", "compile.lower", "compile.backend"]
    record = [r for r in compile_cache.records() if r["entry"] == "run_plan"][-1]
    for s in steps:
        parent = by_id[s["parent_id"]]
        assert parent["name"] == "device.enqueue" and parent["attrs"]["compiled"] == 1
        # on the wall clock every span has, inside its parent
        assert parent["start_s"] <= s["start_s"]
        assert s["start_s"] + s["dur_ms"] / 1e3 <= (
            parent["start_s"] + parent["dur_ms"] / 1e3 + 0.05)
        assert s["attrs"] == {
            "entry": "run_plan", "fun": "_run_plan", "outcome": record["outcome"],
            "key": (record["key"] or "").rsplit("-", 1)[-1][:16],
            "bytes": record["bytes"]}
    assert steps[0]["dur_ms"] == pytest.approx(record["trace_s"] * 1e3, abs=0.01)
    assert steps[2]["dur_ms"] == pytest.approx(record["backend_s"] * 1e3, abs=0.01)
    assert [s["name"] for s in second if s["name"].startswith("compile.")] == []
    (enqueue,) = [s for s in second if s["name"] == "device.enqueue"]
    assert enqueue["attrs"]["compiled"] == 0


# ------------------------- (h) one classifier: last_source, compiled, record


def test_last_source_and_compiled_agree_with_the_record(seed_and_serve):
    _root, seed, serve = seed_and_serve
    compile_, warm = seed["dispatches"]
    (disk, warm_again) = serve["dispatches"]
    (rec,) = compile_["records"]
    assert rec["outcome"] == "miss_new" and rec["fun"] == "_run_plan"
    # the entry point's own trace, not one of the small jits lowering traces after it
    assert rec["trace_s"] > 0.01 and rec["lower_s"] > 0 and rec["backend_s"] > 0
    assert (compile_["source"], compile_["compiled"]) == ("compiled", [1])
    assert (warm["source"], warm["compiled"], warm["records"]) == ("compiled", [0], [])
    (rec_disk,) = disk["records"]
    assert rec_disk["outcome"] == "hit"
    assert (rec_disk["key"], rec_disk["identity"]) == (rec["key"], rec["identity"])
    assert (disk["source"], disk["compiled"]) == ("disk", [1])
    assert (warm_again["source"], warm_again["compiled"], warm_again["records"]) == (
        "compiled", [0], [])


# ---------------------------------------------- (i) the seven per-layer metrics


FIRST_SIGHT_METRICS = {
    # name: (unit, better, the reader's arguments)
    "setup_trace_s": ("s", "lower", {
        "prefixes": ["metrics.kolibrie_device_trace_seconds_total"]}),
    "setup_lower_s": ("s", "lower", {
        "prefixes": ["metrics.kolibrie_device_lower_seconds_total"]}),
    "setup_first_sights": ("count", "lower", {
        "prefixes": ["metrics.kolibrie_compile_first_sight_total"]}),
    "setup_cache_entries_lost": ("count", "lower", {
        "prefixes": ['metrics.kolibrie_compile_first_sight_total{outcome="miss_entry_lost"}']}),
    "setup_cache_keys_moved": ("count", "lower", {
        "prefixes": ['metrics.kolibrie_compile_first_sight_total{outcome="miss_key_moved"}']}),
    "setup_cache_written_mb": ("MB", "lower", {
        "prefixes": ["metrics.kolibrie_compile_cache_written_bytes_total"],
        "scale": 1e-06}),
    "setup_cache_found_mb": ("MB", "higher", {
        "prefixes": ["metrics.kolibrie_compile_cache_found_bytes"], "scale": 1e-06}),
}


def test_the_first_sight_metrics_stand_where_they_were_appended_and_are_data_alone():
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    # the last entries when ISSUE 38 appended them (ISSUE 39's two follow)
    added = bench["per_layer"][67:67 + len(FIRST_SIGHT_METRICS)]
    assert [m["name"] for m in added] == list(FIRST_SIGHT_METRICS)
    for m in added:
        unit, better, args = FIRST_SIGHT_METRICS[m["name"]]
        # every cell has a set-up: no list of workloads
        assert m == {"name": m["name"], "unit": unit, "better": better,
                     "source": "program_counter", "layer": "device dispatch",
                     "moves": "setup_s"}
        reader = files.read_json("layer_metrics", m["name"] + ".json")["reader"]
        assert reader == {"kind": "counter_at_open", **args}
    # no cell or configuration came with them
    assert [w["name"] for w in bench["workloads"]][5] == "lubm50.triangles"
    assert [c["name"] for c in bench["configs"]][:5] == [
        "lubm-5", "employee-100k", "lubm-5-mesh4", "lubm-5-clients8", "lubm-50"]


@pytest.fixture(scope="module")
def scrape(tmp_path_factory):
    """``counters0`` as the harness builds it, of a server that has loaded a
    store and answered a query."""
    from kolibrie_tpu.frontends.http_server import make_server, shutdown_gracefully

    httpd = make_server("127.0.0.1", 0, quiet=True, recover_async=False,
                        data_dir=str(tmp_path_factory.mktemp("served") / "data"))
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    threading.Thread(target=httpd.serve_forever, daemon=True).start()

    def post(path, payload):
        req = urllib.request.Request(
            base + path, data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        with urllib.request.urlopen(req) as resp:
            return json.loads(resp.read())

    try:
        sid = post("/store/load", {
            "rdf": "<http://a> <http://p> <http://b> .\n<http://b> <http://q> <http://c> .",
            "format": "ntriples", "mode": "device"})["store_id"]
        post("/store/query", {"store_id": sid, "deadline_ms": 120000, "sparql":
             "SELECT ?s ?o WHERE { ?s <http://p> ?m . ?m <http://q> ?o }"})
        with urllib.request.urlopen(base + "/metrics") as resp:
            text = resp.read().decode()
        with urllib.request.urlopen(base + "/stats") as resp:
            stats = json.loads(resp.read())
    finally:
        shutdown_gracefully(httpd, timeout_s=5)
    counters0 = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            key, _, value = line.rpartition(" ")
            counters0["metrics." + key] = float(value)
    return counters0, stats


@pytest.mark.parametrize("name", sorted(FIRST_SIGHT_METRICS))
def test_a_first_sight_metric_reads_a_served_stores_scrape(name, scrape):
    counters0, _stats = scrape
    args = dict(files.read_json("layer_metrics", name + ".json")["reader"])
    reader = files.load_module("readers", args.pop("kind"))
    value = reader.read({"counters0": counters0}, **args)
    # every label is registered at import: what counted nothing reads 0
    assert value is not None and value >= 0
    if name in ("setup_trace_s", "setup_lower_s", "setup_first_sights"):
        assert value > 0  # the query's executable, at the least
    family = args["prefixes"][0][len("metrics."):].partition("{")[0]
    for doc in ("OBSERVABILITY.md", "COMPILE_CACHE.md"):
        with open(os.path.join(REPO, "docs", doc), encoding="utf-8") as f:
            assert f"`{family}`" in f.read(), (family, doc)
    # a program without the family (the parent) reports nothing and raises nothing
    lacking = {"counters0": {"metrics.kolibrie_other_total": 1.0}}
    assert reader.read(lacking, **args) is None


def test_the_compile_seconds_family_keeps_its_one_label(scrape):
    counters0, _stats = scrape
    family = "metrics.kolibrie_device_compile_seconds_total"
    assert {k for k in counters0 if k.startswith(family)} == {
        family + '{source="compile"}', family + '{source="disk"}'}
    assert sum(counters0[k] for k in counters0 if k.startswith(family)) > 0
    entries = {k for k in counters0
               if k.startswith("metrics.kolibrie_device_trace_seconds_total")}
    assert len(entries) == len(compile_cache.ENTRIES)
    outcomes = {k for k in counters0
                if k.startswith("metrics.kolibrie_compile_first_sight_total")}
    assert len(outcomes) == len(compile_cache.OUTCOMES)


def test_stats_compile_tail_carries_the_records_and_the_journal(scrape):
    _counters0, stats = scrape
    cache = stats["compile_tail"]["cache"]
    assert cache["enabled"] and cache["journal"].endswith("compile_journal.jsonl")
    assert os.path.dirname(cache["journal"]) == os.path.dirname(
        compile_cache.manifest_path())
    assert "max_size" in cache
    served = [r for r in cache["records"] if r["entry"] == "run_plan"]
    assert served and set(served[-1]) >= {
        "fun", "entry", "identity", "key", "outcome", "trace_s", "lower_s",
        "backend_s", "bytes", "pid", "t"}


# --------------------------------- (j) the journal is the cache's, not the ring's


_PROC_DISABLED = r"""
import json, os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, {repo!r})
from kolibrie_tpu.obs import metrics, spans
from kolibrie_tpu.query import compile_cache as cc
cc.enable(explicit_dir={root!r})
import jax, jax.numpy as jnp

@jax.jit
def _run_plan(x):
    return x * 3

x = jnp.arange(4)
with spans.trace_scope():
    with spans.span("device.enqueue"):
        cc.call(_run_plan, x)
family = metrics.REGISTRY.get("kolibrie_compile_first_sight_total")
print(json.dumps({{
    "spans": spans.spans_snapshot(),
    "record": cc.last_sight(),
    "journal": cc.load_journal(),
    "counted": sum(child.value for _labels, child in family.children()),
}}))
"""


def test_with_obs_disabled_no_span_is_opened_and_the_journal_is_still_written(
        tmp_path):
    got = _run_script(_PROC_DISABLED.format(repo=REPO, root=str(tmp_path / "cc")),
                      KOLIBRIE_OBS_DISABLED="1")
    assert got["spans"] == [] and got["counted"] == 0
    assert got["record"]["outcome"] == "miss_new"
    assert got["record"] in got["journal"]
    assert {r["fun"] for r in got["journal"]} >= {"_run_plan"}
