"""Distributed shard-local join: Pallas tile kernel vs XLA, inside shard_map.

Measures the Pallas dist route (``dist_join._local_join_u32_pallas`` —
sort-once + merge-join kernel + permutation map-back) against the default
XLA searchsorted expansion, through the SAME ``dist_equi_join`` entry the
distributed fixpoint/query rounds use.  Routing uses the unified
``KOLIBRIE_PALLAS`` mode (``force`` turns the dist kernels on; the
deprecated ``KOLIBRIE_PALLAS_DIST`` alias still wins when set).  The flag
is read at TRACE time and the compiled-program caches don't key on it, so
each mode runs in its own subprocess; the parent computes the ratio.

On the real chip this is the measurement VERDICT r3 item 3 asks for (flip
the distributed default to Pallas if it wins); on the CPU mesh the kernel
runs in interpret mode and the ratio is meaningless (noted in the output).

Usage: ``python benches/bench_dist_pallas.py``          (parent: both modes)
       ``python benches/bench_dist_pallas.py pallas``   (one timed child)
"""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np  # noqa: E402

ROWS_PER_SHARD = int(os.environ.get("KOLIBRIE_DIST_BENCH_ROWS", 262_144))
N_DISPATCH = 12
GAP_S = 0.1


def _child(mode: str) -> None:
    os.environ.pop("KOLIBRIE_PALLAS_DIST", None)  # deprecated alias
    if mode == "pallas":
        os.environ["KOLIBRIE_PALLAS"] = "force"
    else:
        os.environ["KOLIBRIE_PALLAS"] = "off"
    import jax

    from kolibrie_tpu.parallel import make_mesh
    from kolibrie_tpu.parallel.dist_join import dist_equi_join

    devs = jax.devices()
    n = len(devs)
    mesh = make_mesh(n)
    rng = np.random.default_rng(7)
    L = ROWS_PER_SHARD
    # two 2-column sides: join key + payload; the key space scales with the
    # GLOBAL row count (half-overlapping) so matches stay ~0.5/row and the
    # static caps hold at any size
    lkey = rng.integers(0, 2 * n * L, size=(n, L), dtype=np.uint32)
    lval = rng.integers(0, 1 << 20, size=(n, L), dtype=np.uint32)
    rkey = rng.integers(0, 2 * n * L, size=(n, L), dtype=np.uint32)
    rval = rng.integers(0, 1 << 20, size=(n, L), dtype=np.uint32)
    valid = np.ones((n, L), dtype=bool)

    bucket_cap = 2 * L  # hash-balanced: ~L/n rows per destination bucket
    out_cap = 2 * L

    def run():
        return dist_equi_join(
            mesh,
            (lkey, lval),
            valid,
            (rkey, rval),
            valid,
            0,
            0,
            bucket_cap=bucket_cap,
            out_cap=out_cap,
        )

    lo, ro, v, total, dropped = run()  # compile + calibrate
    assert dropped == 0, f"bucket overflow: {dropped}"
    times = []
    for _ in range(N_DISPATCH):
        t0 = time.perf_counter()
        lo, ro, v, total, dropped = run()
        times.append(time.perf_counter() - t0)
        time.sleep(GAP_S)
    print(
        json.dumps(
            {
                "mode": mode,
                "platform": devs[0].platform,
                "n_devices": n,
                "rows_per_shard": L,
                "total_matches": int(total),
                "best_ms": round(1000 * min(times), 3),
            }
        )
    )


def main() -> int:
    if len(sys.argv) > 1:
        _child(sys.argv[1])
        return 0
    results = {}
    for mode in ("xla", "pallas"):
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), mode],
            capture_output=True,
            text=True,
            timeout=1200,
        )
        if proc.returncode != 0:
            print(
                json.dumps(
                    {"mode": mode, "error": proc.stderr[-1000:]}
                )
            )
            continue
        for line in reversed(proc.stdout.strip().splitlines()):
            if line.startswith("{"):
                results[mode] = json.loads(line)
                break
    if "xla" in results and "pallas" in results:
        plat = results["pallas"]["platform"]
        ratio = results["xla"]["best_ms"] / results["pallas"]["best_ms"]
        print(
            json.dumps(
                {
                    "metric": f"dist_join_xla_over_pallas_{plat}",
                    "value": round(ratio, 3),
                    "unit": "x (>1 means Pallas wins)",
                    "xla_ms": results["xla"]["best_ms"],
                    "pallas_ms": results["pallas"]["best_ms"],
                    "rows_per_shard": ROWS_PER_SHARD,
                    "n_devices": results["pallas"]["n_devices"],
                    "note": (
                        "interpret-mode kernel; ratio not meaningful"
                        if plat != "tpu"
                        else "Mosaic kernel inside shard_map on chip"
                    ),
                }
            )
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
