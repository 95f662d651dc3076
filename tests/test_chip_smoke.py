"""``chip_smoke.py`` rehearsed off-TPU: every phase runs, every query agrees
with the host engine, and the run still FAILS — nothing makes it pass
without a TPU.  Runs the script as a child (it starts a server and owns
JAX for its process)."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SOLO = {"lubm_q2", "lubm_q9", "join_iri_filter", "group_count"}
VARIANTS = {f"variant{i}" for i in range(8)}
# what only a TPU can satisfy; every other check must hold on the CPU too
TPU_ONLY = {
    "platform_is_tpu",
    "pallas_enabled_not_interpreted",
    "q9_plan_has_tpu_custom_call",
}


def _smoke(*argv, env_extra=None, xla_flags=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("XLA_FLAGS", None)  # conftest's 8 virtual devices
    if xla_flags:
        env["XLA_FLAGS"] = xla_flags
    env.update(env_extra or {})
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *argv],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO,
    )
    lines = [json.loads(ln) for ln in proc.stdout.splitlines() if ln.strip()]
    assert lines, proc.stderr[-3000:]
    return proc, lines


def _assert_rehearsal(proc, lines, queries, tpu_only):
    assert proc.returncode != 0
    last = lines[-1]
    assert set(last) == {"ok", "device"} and last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    got = {ln["query"]: ln for ln in lines if "query" in ln}
    assert set(got) == queries
    assert all(q["rows_equal_host"] and q["rows"] > 0 for q in got.values())
    failed = {ln["check"] for ln in lines if ln.get("ok") is False
              and "check" in ln}
    assert failed == tpu_only, failed


def test_rehearsal_fails_off_tpu_with_rows_equal():
    proc, lines = _smoke("--universities", "1")
    _assert_rehearsal(proc, lines, SOLO | VARIANTS, TPU_ONLY)
    obs = next(ln for ln in lines if ln.get("phase") == "observations")
    assert obs["path_degraded"] == 0
    assert obs["path_device"] + obs["batched"] == obs["sent"] == 24
    # unset, the cache is the fixed <checkout>/.jax_cache
    assert obs["compile_cache_dir"].startswith(
        os.path.join(REPO, ".jax_cache")
    )


def test_cache_placed_by_environment(tmp_path):
    from kolibrie_tpu.query import compile_cache

    placed = tmp_path / "placed"
    # where an UNPLACED chip_smoke run would write (other workers use the
    # .jax_cache root itself, so watch only this namespace directory)
    default = os.path.join(REPO, ".jax_cache", compile_cache.cache_namespace())

    def listing():
        return sorted(os.listdir(default)) if os.path.isdir(default) else []

    before = listing()
    proc, lines = _smoke(
        "--universities", "1",
        env_extra={"JAX_COMPILATION_CACHE_DIR": str(placed)},
    )
    _assert_rehearsal(proc, lines, SOLO | VARIANTS, TPU_ONLY)
    start = next(ln for ln in lines if ln.get("phase") == "start")
    assert start["compile_cache_dir"] == str(placed)
    assert len(os.listdir(placed)) > 0  # entries landed where it was placed
    assert listing() == before  # ... and nowhere else
    obs = next(ln for ln in lines if ln.get("phase") == "observations")
    assert obs["compile_cache"]["misses"] > 0


def test_mesh_rehearsal_on_four_virtual_devices():
    proc, lines = _smoke(
        "--mesh", "--universities", "1",
        xla_flags="--xla_force_host_platform_device_count=4",
    )
    _assert_rehearsal(
        proc, lines, {"lubm_q2", "lubm_q9"} | VARIANTS,
        TPU_ONLY - {"q9_plan_has_tpu_custom_call"},
    )
    assert lines[-1]["device"]["count"] == 4
    checks = {ln["check"]: ln for ln in lines if "check" in ln}
    assert checks["dispatches_recorded_sharded"]["ok"]
    assert checks["shard_arrays_span_four_devices"]["min_devices"] == 4


def test_bench_is_one_process_and_fails_off_tpu():
    """``python bench.py`` has no probe child, retry, replay or CPU
    fallback: off-TPU it says so and exits non-zero at once."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""  # no result line of any kind
    assert "TPU" in proc.stderr and "cpu" in proc.stderr
