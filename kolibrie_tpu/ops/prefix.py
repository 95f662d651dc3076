"""A prefix sum the chip's compiler takes quickly.

The mesh programs' seed scans (``parallel/dist_join.py``) and a WCOJ
level's slot map (:func:`kolibrie_tpu.ops.wcoj.slot_rows`) sum at widths of
a million and more, where a flat ``cumsum`` costs the TPU compiler seconds.
"""

from __future__ import annotations

import jax.numpy as jnp

__all__ = ["prefix_count"]

_PREFIX_BLOCK = 1024


def prefix_count(x: jnp.ndarray) -> jnp.ndarray:
    """``cumsum`` of a mask, or of counts, as int32.  A wide one is summed
    in blocks of ``_PREFIX_BLOCK`` with the blocks' totals beneath them: the
    same numbers, but the chip's compiler takes 0.3 s over it where a flat
    ``cumsum`` of 2 M rows takes 6-7 s (compiled for a described v5e, PR
    50), which every mesh program paid on its seed scan."""
    n = x.shape[0]
    x = x.astype(jnp.int32)
    if n < 4 * _PREFIX_BLOCK:
        return jnp.cumsum(x)
    pad = -n % _PREFIX_BLOCK
    inner = jnp.cumsum(jnp.pad(x, (0, pad)).reshape(-1, _PREFIX_BLOCK), axis=1)
    totals = inner[:, -1]
    before = jnp.cumsum(totals) - totals
    return (inner + before[:, None]).reshape(-1)[:n]
