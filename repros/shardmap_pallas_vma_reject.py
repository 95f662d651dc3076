"""Repro: jax's shard_map varying-mesh-axes checker rejects pallas_call.

Why this file exists: VERDICT r3 item 3 asks to "chase removing the
``check_vma=False`` escape hatch" on the distributed Pallas join route
(``parallel/dist_join.py``).  The kernel's out_shape already propagates the
operand's vma set (``ops/pallas_kernels.py::_pallas_join_core``), but the
checker faults INSIDE pallas_call's own machinery: a ``dynamic_slice``
whose operand varies over the mesh axis while an internal index operand is
replicated.  jax's error message itself prescribes ``check_vma=False`` as
the workaround, i.e. the boundary is upstream, not in this repo.

Observed on jax 0.9.x CPU interpret mode (2026-07): ::

    ValueError: Primitive dynamic_slice requires varying manual axes to
    match, but got [frozenset({'x'}), frozenset()]. Please open an issue
    at https://github.com/jax-ml/jax/issues and as a temporary workaround
    pass the check_vma=False argument to `jax.shard_map`

Run (exits 0 when jax still rejects — the escape hatch must stay; exits 1
the day jax accepts, which is the signal to drop ``check_vma=False``):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python repros/shardmap_pallas_vma_reject.py
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np


def main() -> int:
    import jax
    import jax.numpy as jnp
    import jaxlib
    from jax.sharding import Mesh, PartitionSpec as P

    # This repro is CPU-by-design (the vma checker rejects at TRACE time;
    # no chip involved) — pin the backend so it never claims a chip.
    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass  # backend already initialized by the caller

    # upstream behavior: print the versions it was observed with
    print(f"jax {jax.__version__} / jaxlib {jaxlib.__version__}", flush=True)

    from kolibrie_tpu.ops.pallas_kernels import merge_join_indices

    devs = jax.devices()
    mesh = Mesh(np.array(devs[: min(8, len(devs))]), ("x",))

    def body(lk, rk):
        lk, rk = lk[0], rk[0]
        li, rpos, valid, total = merge_join_indices(lk, jnp.sort(rk), 128)
        return li[None, :128], total[None]

    f = jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            check_vma=True,  # the default we would like to keep
            in_specs=(P("x", None), P("x", None)),
            out_specs=(P("x", None), P("x")),
        )
    )
    n = mesh.devices.size
    lk = np.tile(np.arange(256, dtype=np.uint32), (n, 1))
    rk = np.tile(np.arange(256, dtype=np.uint32), (n, 1))
    try:
        out = f(lk, rk)
    except ValueError as e:
        assert "check_vma=False" in str(e) or "manual axes" in str(e), e
        print("REJECTED (expected): jax still requires check_vma=False")
        print(str(e)[:300])
        return 0
    print(
        "ACCEPTED: jax now takes pallas_call under vma checking — drop the"
        " check_vma=False escape hatch in parallel/dist_join.py"
        f" (total[0]={int(out[1][0])})"
    )
    return 1


if __name__ == "__main__":
    sys.exit(main())
