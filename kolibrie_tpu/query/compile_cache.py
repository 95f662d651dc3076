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

The module also owns the **pre-warm manifest**: a small JSON file next
to the cache recording, per template fingerprint, one representative
query text and its cumulative hit count.  On startup the warmer
(:mod:`kolibrie_tpu.query.prewarm`) replays the top-N entries so the
first *foreground* query finds both the in-process jit cache and the
disk cache hot — zero compiles, zero disk misses.
"""

from __future__ import annotations

import json
import os
import threading
from typing import Dict, List, Optional

from kolibrie_tpu.obs import metrics as _metrics

__all__ = [
    "enable",
    "enabled_dir",
    "cache_namespace",
    "stats",
    "counters",
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
_tls = threading.local()  # .hit: this thread's compile in flight hit disk

_lock = threading.Lock()
_active_dir: Optional[str] = None
# where the pre-warm manifest lives: the configured root (the cache
# directory itself when the environment placed it)
_active_root: Optional[str] = None
_listener_installed = False
# raw event tallies, independent of the obs registry being enabled —
# the restart regression test asserts on these
_event_counts = {"hits": 0, "misses": 0}


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
    # jax records a hit's retrieval time inside the backend-compile step and
    # that step's whole duration when it ends, both on the compiling thread
    if event == "/jax/compilation_cache/cache_retrieval_time_sec":
        _tls.hit = True
    elif event == "/jax/core/compile/backend_compile_duration":
        source = "disk" if getattr(_tls, "hit", False) else "compile"
        _tls.hit = False
        _COMPILE_SECONDS.labels(source).inc(duration_secs)


def _install_listener() -> None:
    global _listener_installed
    if _listener_installed:
        return
    try:
        from jax._src import monitoring

        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
        _listener_installed = True
    # kolint: ignore[KL601] private-API drift: cache still works, only the counters go dark
    except Exception:
        pass


def enable(
    data_dir: Optional[str] = None, explicit_dir: Optional[str] = None
) -> Optional[str]:
    """Idempotently enable the persistent compilation cache.

    Where ``$JAX_COMPILATION_CACHE_DIR`` is set it wins over every
    argument: JAX already reads it, so no directory is set here and no
    sub-directory appended — the call only records it as active, drops
    the two thresholds and installs the hit/miss listener.  Otherwise the
    resolution order is ``explicit_dir`` argument →
    ``$KOLIBRIE_COMPILE_CACHE_DIR`` → ``<data_dir>/compile_cache``, each
    namespaced by :func:`cache_namespace`.  Returns the active directory,
    or ``None`` when no location is configured (cache left untouched).
    Must run before the first lowering it should capture; durability
    recovery calls it before WAL replay so even the replay's own
    dispatches hit disk.
    """
    global _active_dir, _active_root
    with _lock:
        # compile seconds are counted with or without a cache directory
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
    return target


def enabled_dir() -> Optional[str]:
    return _active_dir


def counters() -> Dict[str, int]:
    """Raw (registry-independent) hit/miss event tallies since process
    start — snapshot/delta these around a dispatch to classify its
    source as disk-hit vs real compile."""
    return dict(_event_counts)


def stats() -> dict:
    """Inspection block for /stats: location, entry count, bytes, and
    the hit/miss tallies."""
    out: dict = {
        "enabled": _active_dir is not None,
        "dir": _active_dir,
        "hits": _event_counts["hits"],
        "misses": _event_counts["misses"],
    }
    if _active_dir and os.path.isdir(_active_dir):
        entries = 0
        size = 0
        try:
            for name in os.listdir(_active_dir):
                p = os.path.join(_active_dir, name)
                if os.path.isfile(p):
                    entries += 1
                    size += os.path.getsize(p)
        except OSError:
            pass
        out["entries"] = entries
        out["bytes"] = size
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
