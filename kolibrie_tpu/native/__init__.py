"""Loader for the C++ native runtime (``native/kolibrie_native.cpp``).

The library is built lazily with the repo's ``native/Makefile`` on first
use and cached.  Everything here degrades gracefully: if the toolchain or
library is unavailable (or ``KOLIBRIE_NATIVE=0``), ``load()`` returns None
and callers keep using the pure-Python implementations.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native",
)
_SO_PATH = os.path.join(_NATIVE_DIR, "libkolibrie_native.so")
_SRC_PATH = os.path.join(_NATIVE_DIR, "kolibrie_native.cpp")
_MAKEFILE_PATH = os.path.join(_NATIVE_DIR, "Makefile")

_lock = threading.Lock()
_lib = None
_load_attempted = False


def _declare(lib: ctypes.CDLL) -> ctypes.CDLL:
    c = ctypes
    i64, f64, ptr = c.c_int64, c.c_double, c.c_void_p
    sigs = {
        "kn_sdd_new": ([], ptr),
        "kn_sdd_free": ([ptr], None),
        "kn_sdd_new_var": ([ptr, f64, f64, c.c_int], i64),
        "kn_sdd_set_weight": ([ptr, i64, f64, f64], None),
        "kn_sdd_literal": ([ptr, i64, c.c_int], i64),
        "kn_sdd_apply": ([ptr, i64, i64, c.c_int], i64),
        "kn_sdd_apply_batch": (
            [ptr, c.POINTER(i64), c.POINTER(i64), i64, c.c_int, c.POINTER(i64)],
            None,
        ),
        "kn_sdd_reduce_groups": (
            [ptr, c.POINTER(i64), c.POINTER(i64), i64, c.c_int, c.POINTER(i64)],
            None,
        ),
        "kn_sdd_negate": ([ptr, i64], i64),
        "kn_sdd_exactly_one": ([ptr, c.POINTER(i64), i64], i64),
        "kn_sdd_wmc": ([ptr, i64], f64),
        "kn_sdd_wmc_gradient": ([ptr, i64, c.POINTER(i64), i64, c.POINTER(f64)], None),
        "kn_sdd_size": ([ptr, i64], i64),
        "kn_sdd_node_count": ([ptr], i64),
        "kn_sdd_enumerate_models": (
            [ptr, i64, i64, c.POINTER(i64), c.POINTER(c.c_int8), i64, c.POINTER(i64)],
            i64,
        ),
        "kn_nt_parse": ([c.c_char_p, i64, c.POINTER(ptr)], i64),
        "kn_nt_parse_mt": ([c.c_char_p, i64, c.c_int, c.POINTER(ptr)], i64),
        "kn_nt_nterms": ([ptr], i64),
        "kn_nt_term_bytes": ([ptr], i64),
        "kn_nt_ids": ([ptr, c.POINTER(c.c_uint32)], None),
        "kn_nt_terms": ([ptr, c.c_char_p, c.POINTER(i64)], None),
        "kn_nt_free": ([ptr], None),
        "kn_rx_parse_mt": ([c.c_char_p, i64, c.c_int, c.POINTER(ptr)], i64),
        "kn_ttl_parse_mt": (
            [c.c_char_p, i64, c.c_int, c.c_char_p, i64, c.POINTER(ptr)],
            i64,
        ),
        "kn_ttl_nterms": ([ptr], i64),
        "kn_ttl_term_bytes": ([ptr], i64),
        "kn_ttl_ids": ([ptr, c.POINTER(c.c_uint32)], None),
        "kn_ttl_terms": ([ptr, c.c_char_p, c.POINTER(i64)], None),
        "kn_ttl_prefixes_len": ([ptr], i64),
        "kn_ttl_prefixes": ([ptr, c.c_char_p], None),
        "kn_ttl_free": ([ptr], None),
    }
    for name, (argtypes, restype) in sigs.items():
        fn = getattr(lib, name)
        fn.argtypes = argtypes
        fn.restype = restype
    return lib


def _build() -> bool:
    try:
        proc = subprocess.run(
            ["make", "-C", _NATIVE_DIR, "-s"],
            capture_output=True,
            timeout=120,
        )
        return proc.returncode == 0 and os.path.exists(_SO_PATH)
    except (OSError, subprocess.TimeoutExpired):
        return False


def load():
    """Return the declared CDLL, or None if native mode is unavailable."""
    global _lib, _load_attempted
    if _lib is not None:
        return _lib
    if _load_attempted:
        return None
    with _lock:
        if _lib is not None or _load_attempted:
            return _lib
        _load_attempted = True
        if os.environ.get("KOLIBRIE_NATIVE", "1") == "0":
            return None
        stale = not os.path.exists(_SO_PATH) or any(
            os.path.exists(dep)
            and os.path.getmtime(dep) > os.path.getmtime(_SO_PATH)
            for dep in (_SRC_PATH, _MAKEFILE_PATH)
        )
        if stale and not _build():
            return None
        try:
            _lib = _declare(ctypes.CDLL(_SO_PATH))
        except AttributeError:
            # a stale prebuilt .so missing newly-required symbols (mtime
            # check fooled by copied artifacts).  Rebuild for FUTURE
            # processes — re-dlopening the same path in THIS process would
            # return the cached stale handle (glibc dedups by pathname), so
            # this process degrades to the pure-Python paths.
            _lib = None
            rebuilt = _build()
            import warnings

            warnings.warn(
                "kolibrie_tpu native library was stale; "
                + (
                    "rebuilt for the next run — "
                    if rebuilt
                    else "rebuild failed — "
                )
                + "this process falls back to pure-Python paths",
                RuntimeWarning,
                stacklevel=2,
            )
        except OSError:
            _lib = None
        return _lib


def available() -> bool:
    return load() is not None
