"""Vectorized compute kernels for the query engine and reasoner.

This is the rebuild's replacement for the reference's hand-written SSE2/NEON
SIMD joins/filters (``kolibrie/src/sparql_database.rs:1497-1785,2168-2967``)
and rayon parallel join kernels (``shared/src/join_algorithm.rs``): everything
operates on dense u32/u64/f64 ID columns, expressed as numpy (host) and
jax.numpy (device) array programs.  The device path is what runs on the TPU's
VPU/MXU; the host path mirrors its semantics exactly for small inputs and for
environments without a device.
"""

from kolibrie_tpu.ops.join import equi_join_tables, multi_key_pack
from kolibrie_tpu.ops.unique import unique_rows

_LAZY_KERNELS = ("merge_join", "filter_mask", "tag_combine")

__all__ = [
    "equi_join_tables",
    "multi_key_pack",
    "round_cap",
    "slot_class",
    "unique_rows",
    *_LAZY_KERNELS,
]


def round_cap(n: int, lo: int = 128) -> int:
    """Round a buffer size up to a power of two (>= ``lo``) — the shared
    capacity-rounding rule for every static-shape buffer, so jit executable
    shapes stay stable across nearby sizes."""
    c = lo
    while c < n:
        c <<= 1
    return c


MIN_SLOTS = 8


def slot_class(members: int) -> int:
    """Rows of the parameter matrix a template group of ``members`` is
    dispatched in, on the mesh and on one chip alike: a power of two, not
    below :data:`MIN_SLOTS`.  A class costs memory (the ``[slots, ...]``
    output buffers), not time: the member loop runs the live members only,
    so one executable serves every group size up to its class."""
    return round_cap(members, MIN_SLOTS)


def __getattr__(name):
    # Pallas kernels import jax.experimental.pallas; load lazily so the
    # numpy-only host paths stay importable in minimal environments.
    if name in _LAZY_KERNELS:
        from kolibrie_tpu.ops import pallas_kernels

        return getattr(pallas_kernels, name)
    raise AttributeError(name)


def __dir__():
    return sorted(set(globals()) | set(_LAZY_KERNELS))
