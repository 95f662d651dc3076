"""Minimized repro: Mosaic device fault when merge-join row-start offsets
cross 2^19 under a multi-thousand-tile grid.

Gate it documents: ``ops/pallas_kernels._PALLAS_MAX_LEFT_ROWS = 393216`` —
the SINGLE-LAUNCH tiled merge-join kernel is verified stable up to that
left size; past ~2^19 compacted rows the SAME kernel raises a TPU device
fault at dispatch (seen on a v5e, 2026-07).  Block-index,
pipeline-lookahead and SMEM-size causes were ruled out in round-2
elimination runs.  Since round 4, production inputs
past the gate run the chunk-level driver (bounded local windows — see
``repros/pallas_chunked_join_validation.py``), so this repro bypasses the
gate to reach the raw single-launch path and document the fault boundary
itself.

Run on real TPU:  python repros/mosaic_merge_join_rowstart_fault.py [n_left]
Default n_left = 1_048_576 (faults).  n_left = 393_216 passes.
Off-TPU this runs the interpreter and always passes (prints SKIP).
"""
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, __file__.rsplit("/repros/", 1)[0])


def main(n_left: int) -> None:
    from kolibrie_tpu.ops import pallas_kernels as pk

    if jax.default_backend() != "tpu":
        print("SKIP: repro requires real TPU (interpret mode cannot fault)")
    # every left row matches exactly once -> compaction keeps ALL rows, so
    # row_start values reach n_left (the faulting regime is row starts
    # beyond ~2^19 with n_left/128 output tiles)
    lkey = jnp.arange(n_left, dtype=jnp.uint32)
    rkey = jnp.arange(n_left, dtype=jnp.uint32)
    lval = jnp.arange(n_left, dtype=jnp.uint32)
    rval = jnp.arange(n_left, dtype=jnp.uint32)
    # bypass the production gate to reach the kernel
    saved = pk._PALLAS_MAX_LEFT_ROWS
    pk._PALLAS_MAX_LEFT_ROWS = 1 << 30
    try:
        out = pk.merge_join(lkey, lval, rkey, rval, n_left)
        jax.block_until_ready(out)
        total = int(np.asarray(out[4]))
        print(f"OK: n_left={n_left} total={total} (no fault)")
        assert total == n_left
    finally:
        pk._PALLAS_MAX_LEFT_ROWS = saved


if __name__ == "__main__":
    import jaxlib

    # the fault boundary is empirical per toolchain: print the versions
    print(f"jax {jax.__version__} / jaxlib {jaxlib.__version__}", flush=True)
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 1_048_576)
