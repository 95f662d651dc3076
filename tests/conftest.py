"""Test configuration: force JAX onto an 8-device virtual CPU mesh so that
multi-chip sharding paths compile and execute without TPU hardware.

The suite runs on XLA's CPU backend (the driver sets ``JAX_PLATFORMS=cpu``;
the ``jax_platforms`` update below holds it there for a bare ``pytest``
too).  The chip is reached only through ``benchmark/run.py``, one cell a
process; ``tests/test_chip_compile.py`` asks the TPU *compiler* about a
described chip and needs no device.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import resource  # noqa: E402

# XLA's CPU compiler can exhaust the default 8 MiB stack on the suite's
# largest programs (the Pallas chunk-scan joins) once a few hundred tests
# of state have accumulated — a nondeterministic SIGSEGV in
# backend_compile_and_load.  The main thread's stack grows on demand up
# to the SOFT limit, so raising it here (before any big compile) is
# effective.
try:
    _soft, _hard = resource.getrlimit(resource.RLIMIT_STACK)
    resource.setrlimit(resource.RLIMIT_STACK, (_hard, _hard))
except (ValueError, OSError):
    pass

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache: the suite's wall-clock is dominated by
# 8-device shard_map compiles that are identical run-to-run.  Where
# JAX_COMPILATION_CACHE_DIR is set JAX reads it itself and nothing is set
# here; otherwise the cache is the fixed <checkout>/.jax_cache, which
# survives across pytest invocations.
#
# CAUTION: do not run two suites concurrently against this cache — the
# XLA-level caches ("all" below) are not write-atomic, and a torn entry
# SEGFAULTS jax's zstd cache read on the next run.  Symptom: pytest dies
# rc=139 inside compilation_cache.get_executable_and_time; fix:
# ``rm -rf .jax_cache/*`` and rerun (one process).
import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _bound_jit_memory():
    """Drop compiled executables after every test module.

    A full-suite run compiles many hundreds of programs into one
    process; past a threshold the NEXT big XLA CPU compile dies with
    SIGSEGV inside ``backend_compile_and_load`` (reproducibly around the
    Pallas chunk-join programs at ~60% of the suite; independent of
    stack rlimit, map count, and the persistent cache — consistent with
    LLVM-JIT address-space/relocation exhaustion).  Neither half of the
    suite alone reproduces it, so bounding accumulation per module is
    both the fix and the regression guard.  The persistent compile cache
    below absorbs the recompiles this forces."""
    yield
    jax.clear_caches()


if os.environ.get("KOLIBRIE_NO_TEST_CACHE"):
    pass  # cold-compile everything (cache-corruption triage)
else:
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        _cache_dir = os.path.join(
            os.path.dirname(__file__), os.pardir, ".jax_cache"
        )
        jax.config.update(
            "jax_compilation_cache_dir", os.path.abspath(_cache_dir)
        )
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 2)
    jax.config.update("jax_persistent_cache_enable_xla_caches", "all")


@pytest.fixture(scope="module")
def mesh8():
    """The 8-device mesh for sharded-serving tests — the XLA_FLAGS forcing
    above normally guarantees 8 virtual CPU devices; skip cleanly (instead
    of asserting) when the flag arrived too late to take effect (jax
    already initialized by an embedding process) so tier-1 stays green on
    any runner."""
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices (XLA_FLAGS came too late to force them)")
    from kolibrie_tpu.parallel import make_mesh

    return make_mesh(8)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: multi-minute mesh tests, excluded from the tier-1 "
        "`-m 'not slow'` gate (run explicitly with `-m slow`)",
    )
    config.addinivalue_line(
        "markers",
        "chaos: deterministic fault-injection scenarios (seeded "
        "resilience.faultinject plans); CPU-only and fast, so they run "
        "INSIDE the tier-1 `-m 'not slow'` gate",
    )
