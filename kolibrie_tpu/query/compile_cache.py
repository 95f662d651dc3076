"""Persistent XLA compilation cache management — kill the restart tail.

Every new template shape pays one XLA compile (PERF_r06: 567 ms cold vs
3.8 ms warm on CPU; far worse on real chips).  Within a process the jit
entry points (``_run_plan`` & friends) memoize by ``PlanSpec``, but a
restarted replica — or a fresh member of a replica fleet sharing a data
volume — used to recompile every template from scratch.  This module
turns on JAX's persistent compilation cache and scopes it so the disk
artifacts are shared exactly as widely as they are valid:

- **Location**: where ``$JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
  itself and this module sets NO directory — it only records that one as
  active, so whoever runs the process places the cache.  Otherwise
  ``$KOLIBRIE_COMPILE_CACHE_DIR``, else ``<data_dir>/compile_cache``
  where ``data_dir`` is the durability root (``$KOLIBRIE_DATA_DIR`` for
  the HTTP server).  No directory → cache stays off (library embedders
  opt in explicitly).
- **Keying**: entries this module places are namespaced under
  ``<root>/<jax-version>-<backend>/`` so a jax upgrade or a backend
  switch (cpu ↔ tpu) never replays a stale binary.  *Within* the
  namespace the key is XLA's own hash of the lowered HLO — and because
  the engine's jit entry points take the constant-free ``PlanSpec`` as
  their static argument (the parameter-vector ABI), that HLO is a pure
  function of (template fingerprint, mesh signature, store shape
  buckets).  Two replicas that ever lower the same template shape hash
  to the same entry; constants never leak into the key.
- **Thresholds**: min-compile-time and min-entry-size are zeroed — the
  serving tail this kills is made of exactly the small-but-many
  template compiles the defaults would skip.

Hit/miss traffic is observed through jax's monitoring events and
re-exported as ``kolibrie_compile_cache_{hits,misses}_total`` so /stats
and the bench can attribute a cold query to "disk hit" vs "real
compile".

Every executable the process builds or loads also leaves one **first-sight
record** (:func:`_first_sight`): which function and entry point, seconds of
tracing, lowering and backend compile or cache load, JAX's cache key, the
entry's bytes on disk, and why a miss was a miss.  The records go to the
counters below, to ``compile.*`` child spans, to ``/stats`` and to a bounded
journal beside the pre-warm manifest that the next process on the same
directory reads (docs/COMPILE_CACHE.md "First-sight records").

The module also owns the **pre-warm manifest**: a small JSON file next
to the cache recording, per template fingerprint, one representative
query text and its cumulative hit count.  On startup the warmer
(:mod:`kolibrie_tpu.query.prewarm`) replays the top-N entries so the
first *foreground* query finds both the in-process jit cache and the
disk cache hot — zero compiles, zero disk misses.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import threading
from collections import deque
from typing import Dict, List, Optional

from kolibrie_tpu.obs import metrics as _metrics
from kolibrie_tpu.obs import spans as _spans

__all__ = [
    "enable",
    "enabled_dir",
    "cache_namespace",
    "stats",
    "counters",
    "call",
    "last_sight",
    "records",
    "journal_path",
    "load_journal",
    "manifest_path",
    "load_manifest",
    "save_manifest",
    "record_template",
    "manifest_snapshot",
    "suppress_recording",
]

_HITS = _metrics.counter(
    "kolibrie_compile_cache_hits_total",
    "persistent compilation cache hits (executable loaded from disk)",
)
_MISSES = _metrics.counter(
    "kolibrie_compile_cache_misses_total",
    "persistent compilation cache misses (real XLA compile + write)",
)

# seconds inside jax's compile-or-load step, by where the executable came
# from: XLA compiled it, or the persistent cache held it on disk
_COMPILE_SECONDS = _metrics.counter(
    "kolibrie_device_compile_seconds_total",
    "wall seconds in the backend compile step (source=compile: XLA compiled "
    "the executable; source=disk: the persistent cache held it)",
    labels=("source",),
)
_COMPILE_SECONDS.labels("compile")
_COMPILE_SECONDS.labels("disk")
# The closed sets behind the first-sight families' labels.  Every value is
# registered here, as ``source``'s two are, so a family that counted nothing
# reads 0 and its metric prints.
ENTRIES = (
    "run_plan", "run_plan_batch", "run_interp", "segment_aggregate", "mesh",
    "other",
)
OUTCOMES = ("hit", "miss_new", "miss_entry_lost", "miss_key_moved", "uncached")

_TRACE_SECONDS = _metrics.counter(
    "kolibrie_device_trace_seconds_total",
    "wall seconds tracing a jit's Python into a jaxpr on a shape's first "
    "sight, by the entry point that declared the call",
    labels=("entry",),
)
_LOWER_SECONDS = _metrics.counter(
    "kolibrie_device_lower_seconds_total",
    "wall seconds lowering a first sight's jaxpr to an MLIR module, by the "
    "entry point that declared the call",
    labels=("entry",),
)
_FIRST_SIGHT = _metrics.counter(
    "kolibrie_compile_first_sight_total",
    "executables built or loaded, by outcome: hit, miss_new, miss_entry_lost "
    "(the journal has this key as written or hit), miss_key_moved (the "
    "journal has this identity under another key), uncached (no key)",
    labels=("outcome",),
)
_WRITTEN_BYTES = _metrics.counter(
    "kolibrie_compile_cache_written_bytes_total",
    "bytes of the persistent-cache entries found on disk after a miss's write",
)
_WRITE_FAILURES = _metrics.counter(
    "kolibrie_compile_cache_write_failures_total",
    "misses whose entry was not on disk after JAX wrote it (an entry over "
    "jax_compilation_cache_max_size, an I/O error)",
)
_FOUND_BYTES = _metrics.gauge(
    "kolibrie_compile_cache_found_bytes",
    "bytes of entries the cache directory held when this process enabled it",
)
_FOUND_ENTRIES = _metrics.gauge(
    "kolibrie_compile_cache_found_entries",
    "entries the cache directory held when this process enabled it",
)
for _entry in ENTRIES:
    _TRACE_SECONDS.labels(_entry)
    _LOWER_SECONDS.labels(_entry)
for _outcome in OUTCOMES:
    _FIRST_SIGHT.labels(_outcome)

# What the compiling thread has seen of the executable in flight: .sight (the
# call a jit entry point declared), .traces and .lower (JAX's finished steps),
# .key / .hit / .writing (the persistent cache's verdict).
_tls = threading.local()

_lock = threading.Lock()
_active_dir: Optional[str] = None
# where the pre-warm manifest and the compile journal live: the configured
# root (the cache directory itself when the environment placed it)
_active_root: Optional[str] = None
_listener_installed = False
# raw event tallies, independent of the obs registry being enabled —
# the restart regression test asserts on these
_event_counts = {"hits": 0, "misses": 0}

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_SUFFIX = "-cache"  # jax._src.lru_cache names an entry <key>-cache


def cache_namespace() -> str:
    """Version/backend namespace segment: artifacts are valid exactly as
    long as (jax version, backend kind) both match."""
    import jax

    return f"jax{jax.__version__}-{jax.default_backend()}"


def _on_event(event: str, **kwargs) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _event_counts["hits"] += 1
        _HITS.inc()
    elif event == "/jax/compilation_cache/cache_misses":
        _event_counts["misses"] += 1
        _MISSES.inc()


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    # jax records a hit's retrieval time inside the backend-compile step, on
    # the compiling thread
    if event == "/jax/compilation_cache/cache_retrieval_time_sec":
        _tls.hit = True


def _on_time_span(
    event: str, start_time: float, end_time: float, fun_name: str = "", **kwargs
) -> None:
    # jax reports each step when it ends, on the compiling thread, on the wall
    # clock: traces, lower, then the backend step that closes the record
    if event == _TRACE_EVENT:
        # by name, the newest: lowering traces hundreds of small jits (`add`,
        # `less`) after the entry point's own trace has ended
        _tls.__dict__.setdefault("traces", {})[fun_name] = (start_time, end_time)
    elif event == _LOWER_EVENT:
        _tls.lower = (start_time, end_time, fun_name)
    elif event == _BACKEND_EVENT:
        try:
            _first_sight(fun_name, start_time, end_time)
        # kolint: ignore[KL601] the listener runs inside JAX's compile step: a record that cannot be made must not fail the query it describes
        except Exception:
            pass


class _KeyFilter(logging.Filter):
    """Reads the persistent cache's key and verdict off JAX's own log lines
    (``jax._src.compiler``, ``jax._src.compilation_cache``: debug level,
    emitted on the compiling thread), which no monitoring event carries.  The
    loggers are opened to DEBUG for it; the filter lets through only what they
    would have emitted before, so nobody's log grows."""

    def filter(self, record: logging.LogRecord) -> bool:
        msg, args = record.msg, record.args
        if isinstance(msg, str) and args:
            if msg.startswith(
                ("Persistent compilation cache hit", "PERSISTENT COMPILATION CACHE MISS")
            ):
                _tls.key = str(args[-1])
            elif msg.startswith("Writing ") and "persistent compilation cache" in msg:
                _tls.writing = True
        logger = logging.getLogger(record.name)
        return bool(logger.handlers) or (
            record.levelno >= logger.parent.getEffectiveLevel()
        )


def _install_listener() -> None:
    global _listener_installed
    with _lock:
        if _listener_installed:
            return
        try:
            from jax._src import monitoring

            monitoring.register_event_listener(_on_event)
            monitoring.register_event_duration_secs_listener(_on_duration)
            monitoring.register_event_time_span_listener(_on_time_span)
            for name in ("jax._src.compiler", "jax._src.compilation_cache"):
                logger = logging.getLogger(name)
                logger.addFilter(_KeyFilter())
                logger.setLevel(logging.DEBUG)
            _listener_installed = True
        # kolint: ignore[KL601] private-API drift: cache still works, only the counters go dark
        except Exception:
            pass


# ---------------------------------------------------------------------------
# First-sight records: one per executable built or loaded
# ---------------------------------------------------------------------------


class FirstSight:
    """What a jit entry point hands the listener around its call, by
    reference: the jit function's name, what it declared and the call's
    arguments.  ``record`` is the first-sight record if the call built or
    loaded an executable."""

    __slots__ = ("fun", "declared", "args", "record")

    def __init__(self, fun, declared, args):
        self.fun = fun
        self.declared = declared
        self.args = args
        self.record: Optional[dict] = None


def call(fn, *args, declared=None):
    """``fn(*args)`` for a jit entry point, so that an executable first seen
    inside knows whose it is: the jit function's own name is the entry
    (``_run_plan`` is ``run_plan``), or ``declared`` is ``(entry, static)``
    for a program built at run time.  A warm dispatch pays the thread-local
    assignment and nothing else: nothing is hashed unless a compile event
    fires."""
    # kolint: ignore[KL312] double-checked: the lock-free read is the warm dispatch's; _install_listener re-checks under the lock
    if not _listener_installed:
        _install_listener()
    sight = _tls.sight = FirstSight(getattr(fn, "__name__", ""), declared, args)
    try:
        return fn(*args)
    finally:
        sight.args = None  # the thread must not pin the call's device arrays


def last_sight() -> Optional[dict]:
    """The first-sight record of this thread's last :func:`call`, or ``None``
    where that call was warm."""
    sight = getattr(_tls, "sight", None)
    return sight.record if sight is not None else None


def _identity(entry: str, fun: str, static, args) -> str:
    """Hash of what THE PROGRAM holds to define an executable: the entry
    point, the function, its static arguments (a ``PlanSpec``, a mesh
    program's key: by ``repr``) and the other arguments' shapes and dtypes.
    What it leaves out on purpose is everything only JAX's key holds, so that
    an identity met under a new key says something outside the program's
    notion moved (docs/COMPILE_CACHE.md)."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(args)
    sig = [
        (tuple(x.shape), str(x.dtype))
        if hasattr(x, "shape") and hasattr(x, "dtype")
        else repr(x)
        for x in leaves
    ]
    text = repr((entry, fun, static, str(treedef), sig))
    return hashlib.sha256(text.encode()).hexdigest()[:32]


def _declared_by(sight: Optional[FirstSight], fun: str):
    """``(entry, static)`` where ``sight`` is the call of the jit ``fun`` and
    has no record yet, else ``None`` (a jit of someone else's inside the
    declared call, a compile after it returned)."""
    if sight is None or sight.record is not None or sight.args is None:
        return None
    if sight.fun != fun:
        return None
    entry, static = sight.declared or (fun.lstrip("_"), None)
    return (entry if entry in ENTRIES else "other"), static


def _entry_file(key: str) -> Optional[str]:
    try:
        import jax
        from jax._src import compilation_cache as jcc

        root = getattr(jcc._cache, "_path", None) or jax.config.jax_compilation_cache_dir
    # kolint: ignore[KL601] private-API drift: the record goes without the entry's bytes
    except Exception:
        return None
    return os.path.join(str(root), key + _CACHE_SUFFIX) if root else None


def _first_sight(module: str, start: float, end: float) -> None:
    """Close the record of the executable whose backend step just ended on
    this thread (``module`` is JAX's ``jit(<fun>)``)."""
    seen = _tls.__dict__
    key = seen.pop("key", None)
    hit = seen.pop("hit", False)
    writing = seen.pop("writing", False)
    fun = module[module.find("(") + 1 : -1] if module.endswith(")") else module
    trace = seen.pop("traces", {}).get(fun)
    lower = seen.pop("lower", None)
    if lower is not None and lower[2] != module:
        lower = None
    _COMPILE_SECONDS.labels("disk" if hit else "compile").inc(end - start)

    sight = seen.get("sight")
    declared = _declared_by(sight, fun)
    entry, identity = "other", fun
    if declared is not None:
        entry = declared[0]
        identity = _identity(entry, fun, declared[1], sight.args)
    path = _entry_file(key) if key else None
    try:
        size = os.path.getsize(path) if path else 0
    except OSError:
        size = 0
    rec = {
        "t": round(end, 3),
        "pid": os.getpid(),
        "fun": fun,
        "entry": entry,
        "identity": identity,
        "key": key,
        "trace_s": round(trace[1] - trace[0], 6) if trace else 0.0,
        "lower_s": round(lower[1] - lower[0], 6) if lower else 0.0,
        "backend_s": round(end - start, 6),
        "bytes": size,
    }
    if hit:
        outcome = "hit"
    elif key is None:
        outcome = "uncached"
    else:
        # skipped: JAX chose not to write (host callbacks, a threshold)
        rec["write"] = ("ok" if size else "failed") if writing else "skipped"
        if rec["write"] == "ok":
            _WRITTEN_BYTES.inc(size)
        elif rec["write"] == "failed":
            _WRITE_FAILURES.inc()
        outcome = _why_miss(rec)
    rec["outcome"] = outcome
    if declared is not None:
        sight.record = rec
    _TRACE_SECONDS.labels(entry).inc(rec["trace_s"])
    _LOWER_SECONDS.labels(entry).inc(rec["lower_s"])
    _FIRST_SIGHT.labels(outcome).inc()
    attrs = {
        "entry": entry,
        "fun": fun,
        "outcome": outcome,
        # the hash's head: every key of one function starts with its name
        "key": key.rsplit("-", 1)[-1][:16] if key else "",
        "bytes": size,
    }
    for name, step in (
        ("compile.trace", trace),
        ("compile.lower", lower),
        ("compile.backend", (start, end)),
    ):
        if step is not None:
            _spans.add_finished(name, step[0], step[1], dict(attrs))
    _journal_append(rec)


# ---------------------------------------------------------------------------
# Compile journal: the records, kept beside the manifest for the next process
# ---------------------------------------------------------------------------

_JOURNAL_NAME = "compile_journal.jsonl"
_JOURNAL_MAX = 1024  # newest lines kept at a trim
_JOURNAL_SLACK = 256  # appended lines between trims

_journal_lock = threading.Lock()
_records: deque = deque(maxlen=256)  # this process's, newest last; guarded by: _journal_lock
_landed: set = set()  # keys the journal has as hit or written; guarded by: _journal_lock
_key_of: Dict[str, str] = {}  # declared identity -> its newest key; guarded by: _journal_lock
_journal_lines = 0  # lines in the file as last counted; guarded by: _journal_lock


def journal_path(root: Optional[str] = None) -> Optional[str]:
    """The journal lives beside the manifest, at the cache ROOT."""
    base = root or _active_root
    if base is None:
        return None
    return os.path.join(base, _JOURNAL_NAME)


def _read_journal(path: Optional[str]) -> List[dict]:
    if path is None or not os.path.isfile(path):
        return []
    out = []
    try:
        with open(path, "rb") as f:
            for line in f:
                try:
                    rec = json.loads(line.decode("utf-8"))
                except ValueError:
                    continue
                if isinstance(rec, dict) and isinstance(rec.get("fun"), str):
                    out.append(rec)
    except OSError:
        return []
    return out


def load_journal(root: Optional[str] = None) -> List[dict]:
    """The journal's newest records, oldest first.  A missing file reads as
    empty and a torn or foreign line is skipped: the journal is advisory, as
    the manifest is."""
    return _read_journal(journal_path(root))[-_JOURNAL_MAX:]


def records() -> List[dict]:
    """This process's first-sight records, oldest first (the newest 256)."""
    with _journal_lock:
        return list(_records)


def _index(rec: dict) -> None:  # kolint: holds[_journal_lock]
    key = rec.get("key")
    if not key:
        return
    if rec.get("outcome") == "hit" or rec.get("write") == "ok":
        _landed.add(key)
    if rec.get("entry") != "other" and rec.get("identity"):
        _key_of[rec["identity"]] = key


def _why_miss(rec: dict) -> str:
    with _journal_lock:
        if rec["key"] in _landed:
            return "miss_entry_lost"
        # a jit nobody declared has its name for an identity, and one name
        # has a key a shape: only a declared identity can tell a key moved
        was = _key_of.get(rec["identity"]) if rec["entry"] != "other" else None
    if was is not None and was != rec["key"]:
        rec["was"] = was
        return "miss_key_moved"
    return "miss_new"


def _write_journal(path: str, recs: List[dict]) -> None:
    from kolibrie_tpu.durability.fsio import atomic_write_bytes

    atomic_write_bytes(
        path, "".join(json.dumps(r, sort_keys=True) + "\n" for r in recs).encode()
    )


def _adopt_journal() -> None:
    """Index what earlier processes on this root left (called when a root
    becomes active) and trim the file to its bound."""
    global _journal_lines
    path = journal_path()
    recs = _read_journal(path)
    with _journal_lock:
        _landed.clear()
        _key_of.clear()
        for rec in recs[-_JOURNAL_MAX:] + list(_records):
            _index(rec)
        if len(recs) > _JOURNAL_MAX:
            recs = recs[-_JOURNAL_MAX:]
            try:
                _write_journal(path, recs)
            except OSError:
                pass
        _journal_lines = len(recs)


def _journal_append(rec: dict) -> None:
    global _journal_lines
    path = journal_path()
    with _journal_lock:
        _records.append(rec)
        _index(rec)
        if path is None:
            return
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            with open(path, "a", encoding="utf-8") as f:
                f.write(json.dumps(rec, sort_keys=True) + "\n")
            _journal_lines += 1
            if _journal_lines >= _JOURNAL_MAX + _JOURNAL_SLACK:
                recs = load_journal()
                _write_journal(path, recs)
                _journal_lines = len(recs)
        except OSError:
            pass  # the journal is advisory: a lost line costs the next miss its reason


def _scan_entries(target: str):
    """``(entries, bytes)`` of the persistent-cache entries in ``target``."""
    entries = size = 0
    try:
        with os.scandir(target) as it:
            for e in it:
                if e.name.endswith(_CACHE_SUFFIX) and e.is_file():
                    entries += 1
                    size += e.stat().st_size
    except OSError:
        pass
    return entries, size


def enable(
    data_dir: Optional[str] = None, explicit_dir: Optional[str] = None
) -> Optional[str]:
    """Idempotently enable the persistent compilation cache.

    Where ``$JAX_COMPILATION_CACHE_DIR`` is set it wins over every
    argument: JAX already reads it, so no directory is set here and no
    sub-directory appended — the call only records it as active, drops
    the two thresholds and installs the listeners.  Otherwise the
    resolution order is ``explicit_dir`` argument →
    ``$KOLIBRIE_COMPILE_CACHE_DIR`` → ``<data_dir>/compile_cache``, each
    namespaced by :func:`cache_namespace`.  Returns the active directory,
    or ``None`` when no location is configured (cache left untouched).
    Must run before the first lowering it should capture; durability
    recovery calls it before WAL replay so even the replay's own
    dispatches hit disk.
    """
    global _active_dir, _active_root
    # first sights are recorded with or without a cache directory
    _install_listener()
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        root = target = os.path.abspath(env_dir)
    else:
        root = explicit_dir or os.environ.get("KOLIBRIE_COMPILE_CACHE_DIR")
        if not root and data_dir:
            root = os.path.join(data_dir, "compile_cache")
        if not root:
            return None
        root = os.path.abspath(root)
        target = os.path.join(root, cache_namespace())
    with _lock:
        if _active_dir == target:
            return _active_dir
        import jax

        if not env_dir:
            os.makedirs(target, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", target)
        # the tail is many SMALL compiles: cache all of them
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_enable_compilation_cache", True)
        _active_dir, _active_root = target, root
        entries, size = _scan_entries(target)
        _FOUND_ENTRIES.set(entries)
        _FOUND_BYTES.set(size)
        _adopt_journal()
    return target


def enabled_dir() -> Optional[str]:
    return _active_dir


def counters() -> Dict[str, int]:
    """Raw (registry-independent) hit/miss event tallies since process
    start — snapshot/delta these around a dispatch to classify its
    source as disk-hit vs real compile."""
    return dict(_event_counts)


def stats() -> dict:
    """Inspection block for /stats: location, entry count, bytes, the
    hit/miss tallies, JAX's size bound, and this process's first-sight
    records with the journal they were appended to."""
    out: dict = {
        "enabled": _active_dir is not None,
        "dir": _active_dir,
        "hits": _event_counts["hits"],
        "misses": _event_counts["misses"],
    }
    if _active_dir and os.path.isdir(_active_dir):
        out["entries"], out["bytes"] = _scan_entries(_active_dir)
        import jax

        out["max_size"] = jax.config.jax_compilation_cache_max_size
    out["journal"] = journal_path()
    out["records"] = records()
    return out


# ---------------------------------------------------------------------------
# Pre-warm manifest: fingerprint -> representative query + hit count
# ---------------------------------------------------------------------------

_MANIFEST_NAME = "prewarm_manifest.json"
_MANIFEST_MAX = 256  # top-N by hits kept on disk

# in-memory accumulation: fp -> {"query": str, "hits": int}
_templates: Dict[str, Dict] = {}
_templates_lock = threading.Lock()
_suppress = threading.local()


class suppress_recording:
    """Context manager: executions inside do not feed the manifest.
    The warmer wraps its replays in this so warming the top-N does not
    inflate the very popularity ranking it replays."""

    def __enter__(self):
        self._prev = getattr(_suppress, "on", False)
        _suppress.on = True
        return self

    def __exit__(self, *exc):
        _suppress.on = self._prev
        return False


def manifest_path(root: Optional[str] = None) -> Optional[str]:
    """The manifest lives at the cache ROOT (not the versioned
    namespace): query texts replay across jax upgrades just fine."""
    base = root or _active_root
    if base is None:
        return None
    return os.path.join(base, _MANIFEST_NAME)


def record_template(fp: str, query: str) -> None:
    """Account one execution of template ``fp``; keeps the first-seen
    query text as the replayable representative.  Called from the
    executor's plan-cache bookkeeping — must stay O(1)."""
    if getattr(_suppress, "on", False):
        return
    with _templates_lock:
        ent = _templates.get(fp)
        if ent is None:
            if len(_templates) >= 4 * _MANIFEST_MAX:
                # bound the accumulator; the save path re-ranks anyway
                drop = min(_templates, key=lambda k: _templates[k]["hits"])
                _templates.pop(drop)
            _templates[fp] = {"query": query, "hits": 1}
        else:
            ent["hits"] += 1


def manifest_snapshot() -> List[dict]:
    """Current top-N, hottest first."""
    with _templates_lock:
        items = [
            {"fp": fp, "query": e["query"], "hits": e["hits"]}
            for fp, e in _templates.items()
        ]
    items.sort(key=lambda e: (-e["hits"], e["fp"]))
    return items[:_MANIFEST_MAX]


def save_manifest(root: Optional[str] = None) -> Optional[str]:
    """Atomically persist the ranked manifest (tmp + rename, same
    discipline as the durability snapshots)."""
    path = manifest_path(root)
    if path is None:
        return None
    merged: Dict[str, dict] = {
        e["fp"]: e for e in load_manifest(root)
    }
    for e in manifest_snapshot():
        old = merged.get(e["fp"])
        if old is None or e["hits"] >= old.get("hits", 0):
            merged[e["fp"]] = e
    ranked = sorted(
        merged.values(), key=lambda e: (-e.get("hits", 0), e["fp"])
    )[:_MANIFEST_MAX]
    from kolibrie_tpu.optimizer.stats_advisor import stats_advisor

    payload = json.dumps(
        {
            "version": 1,
            "templates": ranked,
            # learned per-template cardinalities ride the same manifest:
            # a restarted replica (or a follower bootstrapping from
            # snapshot) starts with tuned routing instead of re-learning
            "stats_advisor": stats_advisor.export_state(),
        }
    ).encode()
    try:
        from kolibrie_tpu.durability.fsio import atomic_write_bytes

        os.makedirs(os.path.dirname(path), exist_ok=True)
        atomic_write_bytes(path, payload)
    # kolint: ignore[KL601] manifest persistence is advisory: a failed save only costs the next boot warmth
    except Exception:
        return None
    return path


def load_manifest(root: Optional[str] = None) -> List[dict]:
    path = manifest_path(root)
    if path is None or not os.path.isfile(path):
        return []
    try:
        with open(path, "rb") as f:
            doc = json.loads(f.read().decode("utf-8"))
    except (OSError, ValueError):
        return []  # torn/corrupt manifest only costs warmth
    out = []
    for e in doc.get("templates", []):
        if isinstance(e, dict) and isinstance(e.get("query"), str):
            out.append(e)
    return out


def load_advisor_state(root: Optional[str] = None) -> int:
    """Import the manifest's ``stats_advisor`` section into the
    process-wide advisor; returns templates imported.  Corruption at any
    level (file, JSON, section, entry) degrades to the static AGM model
    — the section is advisory, exactly like the template list."""
    path = manifest_path(root)
    if path is None or not os.path.isfile(path):
        return 0
    try:
        with open(path, "rb") as f:
            doc = json.loads(f.read().decode("utf-8"))
    except (OSError, ValueError):
        return 0
    if not isinstance(doc, dict):
        return 0
    from kolibrie_tpu.optimizer.stats_advisor import stats_advisor

    return stats_advisor.import_state(doc.get("stats_advisor"))
