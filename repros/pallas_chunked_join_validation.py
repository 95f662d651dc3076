"""Hardware validation for the chunk-level Pallas merge-join driver.

Round-4 lift of ``_PALLAS_MAX_LEFT_ROWS``: left sides past the 393,216-row
single-launch gate now run :func:`_pallas_join_core_chunked` — the same
tile kernel launched per 131,072-output chunk over a dynamic-sliced local
row window, so per-launch row-start offsets stay an order of magnitude
under the empirical 2^19 Mosaic fault boundary
(``repros/mosaic_merge_join_rowstart_fault.py``).

For each size this script runs the chunked kernel path AND the pure-XLA
formulation on the same data, checks totals + full row equality, and
prints per-path device times (one warm-up, then timed reruns).

Run on real TPU:  python repros/pallas_chunked_join_validation.py [sizes...]
Default sizes: 1048576 4194304 16777216.  Off-TPU it validates a scaled
-down size in interpret mode (full sizes are impractical interpreted).
"""
import os
import sys
import time

import jax

# KOLIBRIE_REPRO_CPU=1 pins the CPU backend before anything touches devices
# (same effect as JAX_PLATFORMS=cpu).
if os.environ.get("KOLIBRIE_REPRO_CPU") == "1":
    jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp
import numpy as np

sys.path.insert(0, __file__.rsplit("/repros/", 1)[0])


def run_one(n_left: int, chunk_out=None) -> None:
    from kolibrie_tpu.ops.pallas_kernels import _xla_merge_join, merge_join

    rng = np.random.default_rng(0)
    # ~4 distinct left rows per key, ~2 right rows -> fanout ~2, total ~2n.
    lk = rng.integers(0, n_left // 4, n_left).astype(np.uint32)
    lv = rng.integers(0, 1 << 30, n_left).astype(np.uint32)
    rk = np.sort(rng.integers(0, n_left // 4, n_left // 2).astype(np.uint32))
    rv = rng.integers(0, 1 << 30, n_left // 2).astype(np.uint32)
    cap = int(n_left * 2.5)
    args = tuple(map(jnp.asarray, (lk, lv, rk, rv)))

    def timed(fn):
        out = fn()
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(3):
            out = fn()
            jax.block_until_ready(out)
        return (time.perf_counter() - t0) / 3, out

    t_xla, ref = timed(lambda: _xla_merge_join(*args, cap))
    # auto-chunks past the gate; explicit chunk_out for the interpret check
    t_pal, got = timed(lambda: merge_join(*args, cap, chunk_out=chunk_out))
    rt, gt = int(np.asarray(ref[4])), int(np.asarray(got[4]))
    assert rt == gt, (rt, gt)
    eff = min(gt, cap)
    for i in range(3):  # key, lval, rval (valid-masked, order-aligned)
        a = np.asarray(ref[i])[:eff][np.asarray(ref[3])[:eff]]
        b = np.asarray(got[i])[:eff][np.asarray(got[3])[:eff]]
        assert np.array_equal(a, b), f"column {i} mismatch at n={n_left}"
    print(
        f"OK n_left={n_left} total={gt} xla={t_xla*1e3:.2f}ms "
        f"pallas_chunked={t_pal*1e3:.2f}ms ratio={t_xla/t_pal:.2f}x"
    )


def main(sizes) -> None:
    if jax.default_backend() != "tpu":
        print("SKIP full sizes: not on TPU; full sizes are impractical "
              "interpreted — running 8K-row/1K-chunk interpret-mode check")
        run_one(8192, chunk_out=1024)
        return
    for n in sizes:
        run_one(n)


if __name__ == "__main__":
    main(
        [int(a) for a in sys.argv[1:]] or [1_048_576, 4_194_304, 16_777_216]
    )
