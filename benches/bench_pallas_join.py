"""Pallas merge-join kernel vs XLA searchsorted join.

Two workloads:
- the employee-100K shape of the headline bench (``bench.py``'s query:
  join of the workplaceHomepage and annual_salary predicate runs);
- a size sweep of uniform-key joins, covering the kernel's verified range
  and the first size past ``_PALLAS_MAX_LEFT_ROWS`` (where ``merge_join``
  transparently routes to the XLA formulation).

Each size runs in its OWN subprocess, one at a time, so verification
readbacks never share a process with the next size's timing loop.  The
parent never imports JAX: one process holds the chip at a time.

Prints one JSON line per measurement.
"""

import json
import subprocess
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np  # noqa: E402

N_EMPLOYEES = 25_000
N_DISPATCH = 20
GAP_S = 0.1
SWEEP_SIZES = (131072, 262144, 1048576)


def time_fn(fn, *args):
    import jax

    out = fn(*args)
    jax.block_until_ready(out)
    times = []
    for _ in range(N_DISPATCH):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
        time.sleep(GAP_S)
    return min(times), out


def employee_runs():
    """The two sorted (key, payload) predicate runs of the headline query."""
    n = N_EMPLOYEES
    emp = np.arange(n, dtype=np.uint32)
    homepage = (emp % 500).astype(np.uint32)
    salary = (30000 + (emp % 50) * 1000).astype(np.uint32)
    return (emp, homepage), (emp, salary)


def _measure(lk, lv, rk, rv, cap):
    import jax
    import jax.numpy as jnp

    from kolibrie_tpu.ops.pallas_kernels import _xla_merge_join, merge_join

    args = tuple(jnp.asarray(a) for a in (lk, lv, rk, rv))
    xla_jit = jax.jit(_xla_merge_join, static_argnames="cap")
    t_pallas, out_p = time_fn(lambda *a: merge_join(*a, cap), *args)
    t_xla, out_x = time_fn(lambda *a: xla_jit(*a, cap=cap), *args)
    # readback + cross-check after ALL timing
    n_p, n_x = int(out_p[4]), int(out_x[4])
    assert n_p == n_x, (n_p, n_x)
    return t_pallas, t_xla, n_p


def section_employee():
    import jax

    (ls, lo_), (rs, ro_) = employee_runs()
    cap = 131072
    t_pallas, t_xla, n_pairs = _measure(ls, lo_, rs, ro_, cap)
    platform = jax.devices()[0].platform
    n_triples = 4 * N_EMPLOYEES
    for name, t in (("pallas_merge_join", t_pallas), ("xla_merge_join", t_xla)):
        print(
            json.dumps(
                {
                    "metric": f"{name}_employee100k_triples_per_sec_{platform}",
                    "value": round(n_triples / t, 1),
                    "unit": "triples/sec/chip",
                    "vs_baseline": round(t_xla / t, 3),
                }
            )
        )


def section_size(n: int):
    import jax

    from kolibrie_tpu.ops.pallas_kernels import (
        _PALLAS_MAX_LEFT_ROWS,
        pallas_chunked_enabled,
    )

    rng = np.random.default_rng(0)
    lk = np.sort(rng.integers(0, n, n).astype(np.uint32))
    rk = np.sort(rng.integers(0, n, n).astype(np.uint32))
    lv = np.arange(n, dtype=np.uint32)
    rv = np.arange(n, dtype=np.uint32)
    cap = 4 * n
    t_pallas, t_xla, n_pairs = _measure(lk, lv, rk, rv, cap)
    print(
        json.dumps(
            {
                "metric": f"merge_join_uniform_{n}",
                "platform": jax.devices()[0].platform,
                "path": (
                    "pallas"
                    if n <= _PALLAS_MAX_LEFT_ROWS
                    else (
                        "pallas_chunked"
                        if pallas_chunked_enabled()
                        else "xla_fallback"
                    )
                ),
                "pairs": n_pairs,
                "pallas_ms": round(1000 * t_pallas, 3),
                "xla_ms": round(1000 * t_xla, 3),
                "pairs_per_sec": round(n_pairs / t_pallas, 1),
                "speedup_vs_xla": round(t_xla / t_pallas, 3),
            }
        )
    )


def main():
    if len(sys.argv) > 2 and sys.argv[1] == "--section":
        if sys.argv[2] == "employee":
            section_employee()
        else:
            section_size(int(sys.argv[2]))
        return
    here = str(Path(__file__).resolve())
    subprocess.run([sys.executable, here, "--section", "employee"], check=True)
    for n in SWEEP_SIZES:
        subprocess.run([sys.executable, here, "--section", str(n)], check=True)


if __name__ == "__main__":
    main()
