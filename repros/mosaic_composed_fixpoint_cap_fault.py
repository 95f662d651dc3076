"""Minimized repro: TPU device fault for COMPOSED fixpoint programs whose
join buffers exceed 2^21 rows.

Gate it documents: ``reasoner/device_fixpoint.SAFE_JOIN_CAP = 2_097_152``.
Each constituent op standalone (sorts to 16M rows, join_indices at 4M cap,
gathers) passes; the fault appears only when the semi-naive round body —
scan + join + gather + sort-unique + set-difference + append — compiles as
ONE program with a join capacity past 2^21 (seen on a v5e, 2026-07).

Run on real TPU:  python repros/mosaic_composed_fixpoint_cap_fault.py [cap]
Default cap = 4_194_304 (faults).  cap = 2_097_152 passes.
Off-TPU this runs the XLA CPU backend and always passes (prints SKIP).
"""
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

sys.path.insert(0, __file__.rsplit("/repros/", 1)[0])


def main(cap: int) -> None:
    from kolibrie_tpu.ops.device_join import (
        join_indices,
        set_difference_rows,
        sort_unique_rows,
    )

    if jax.default_backend() != "tpu":
        print("SKIP: repro requires real TPU (CPU backend does not fault)")
    n = cap // 4

    @jax.jit
    def round_body(s, p, o):
        with jax.enable_x64(True):
            li, ri, valid, _tot = join_indices(o, s, cap)  # (x p y)(y p z)
            cs, co = s[li], o[ri]
            cp = jnp.where(valid, p[0], 0)
            (us, up, uo), uv, _n1 = sort_unique_rows((cs, cp, co), valid, cap)
            (ns, np_, no), nv, n_new = set_difference_rows(
                (us, up, uo), uv, (s, p, o), jnp.ones_like(s, bool), cap
            )
            return ns, np_, no, n_new

    rng = np.random.default_rng(0)
    s = jnp.asarray(rng.integers(1, n // 2, n).astype(np.uint32))
    o = jnp.asarray(rng.integers(1, n // 2, n).astype(np.uint32))
    p = jnp.full(n, 7, dtype=jnp.uint32)
    out = round_body(s, p, o)
    jax.block_until_ready(out)
    print(f"OK: cap={cap} derived={int(np.asarray(out[3]))} (no fault)")


if __name__ == "__main__":
    import jaxlib

    # the fault boundary is empirical per toolchain: print the versions
    print(f"jax {jax.__version__} / jaxlib {jaxlib.__version__}", flush=True)
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 4_194_304)
