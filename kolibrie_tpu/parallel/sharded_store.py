"""Hash-partitioned triple columns over a device mesh.

Layout: global arrays of shape ``[n_shards, cap]`` for s/p/o (+ validity
mask), sharded ``PartitionSpec("shards", None)`` so each chip holds one row
block in its HBM.  Shard ownership is ``hash(subject) % n`` ("by_subj") —
joins probing by subject are local — and a mirrored copy partitioned by
object hash ("by_obj") makes object-keyed probes local too.  This pair of
copies is the distributed analogue of the reference's SPO/OPS permutation
indexes (``shared/src/index_manager.rs:18-26``): replication in *partitioning
key* instead of sort order.
"""

from __future__ import annotations

import weakref
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _mix32(x: np.ndarray) -> np.ndarray:
    """Cheap integer mix (finalizer-style) so consecutive dictionary IDs
    spread across shards instead of clumping.  All arithmetic is wrapping
    u32 — bit-identical to the device twin ``dist_join.mix32``."""
    x = x.astype(np.uint32)
    c = np.uint32(0x45D9F3B)
    with np.errstate(over="ignore"):
        x = (x ^ (x >> np.uint32(16))) * c
        x = (x ^ (x >> np.uint32(16))) * c
    return x ^ (x >> np.uint32(16))


def shard_of(key: np.ndarray, n_shards: int) -> np.ndarray:
    return (_mix32(key) % np.uint32(n_shards)).astype(np.int32)


def partition_rows(
    cols: Tuple[np.ndarray, ...],
    key: np.ndarray,
    n_shards: int,
    cap: Optional[int] = None,
) -> Tuple[Tuple[np.ndarray, ...], np.ndarray]:
    """Host-side partition: rows → ``[n_shards, cap]`` blocks + valid mask."""
    dest = shard_of(key, n_shards)
    counts = np.bincount(dest, minlength=n_shards)
    need = int(counts.max()) if len(key) else 0
    if cap is None:
        cap = max(8, 1 << (need - 1).bit_length() if need else 3)
    if need > cap:
        raise ValueError(f"shard capacity {cap} < max shard load {need}")
    # dtype-preserving: payload columns (e.g. f64 provenance tags) ride the
    # same placement as the u32 id columns
    out_cols = [np.zeros((n_shards, cap), dtype=c.dtype) for c in cols]
    valid = np.zeros((n_shards, cap), dtype=bool)
    order = np.argsort(dest, kind="stable")
    offs = np.concatenate([[0], np.cumsum(counts)])
    for sh in range(n_shards):
        rows = order[offs[sh] : offs[sh + 1]]
        for c_out, c_in in zip(out_cols, cols):
            c_out[sh, : len(rows)] = c_in[rows]
        valid[sh, : len(rows)] = True
    return tuple(out_cols), valid


class ShardedTripleStore:
    """Device-sharded (s, p, o) columns with subject- and object-hash copies."""

    def __init__(self, mesh: Mesh, cap_per_shard: int):
        self.mesh = mesh
        self.axis = mesh.axis_names[0]
        self.n_shards = mesh.devices.size
        self.cap = cap_per_shard
        self.sharding = NamedSharding(mesh, P(self.axis, None))
        z = np.zeros((self.n_shards, cap_per_shard), dtype=np.uint32)
        f = np.zeros((self.n_shards, cap_per_shard), dtype=bool)
        self.by_subj = tuple(jax.device_put(z, self.sharding) for _ in range(3))
        self.by_subj_valid = jax.device_put(f, self.sharding)
        self.by_obj = tuple(jax.device_put(z, self.sharding) for _ in range(3))
        self.by_obj_valid = jax.device_put(f, self.sharding)
        # subj_packed_sorted is built lazily by ensure_subj_index on first
        # probe (and eagerly by from_columns).
        self.subj_packed_sorted = None
        self._subj_index_src = None
        # two-tier probe index (see refresh_subj_index): (base, tombs, delta)
        # sorted u64 packs; the full-rebuild path fills tombs/delta with
        # tiny all-sentinel arrays so consumers probe uniformly
        self.subj_index_parts = None
        self._subj_base_packed = None
        self._subj_base_end = None
        self.subj_index_base_builds = 0
        self.subj_index_delta_builds = 0

    @classmethod
    def from_columns(
        cls,
        mesh: Mesh,
        s: np.ndarray,
        p: np.ndarray,
        o: np.ndarray,
        cap_per_shard: Optional[int] = None,
    ) -> "ShardedTripleStore":
        n = mesh.devices.size
        dest = shard_of(s, n)
        counts = np.bincount(dest, minlength=n)
        dest_o = shard_of(o, n)
        counts_o = np.bincount(dest_o, minlength=n)
        need = int(max(counts.max() if len(s) else 0, counts_o.max() if len(s) else 0))
        if cap_per_shard is None:
            cap_per_shard = max(8, 1 << max(need - 1, 1).bit_length())
        st = cls(mesh, cap_per_shard)
        (ss, sp, so), sv = partition_rows((s, p, o), s, n, cap_per_shard)
        (os_, op, oo), ov = partition_rows((s, p, o), o, n, cap_per_shard)
        put = lambda a: jax.device_put(a, st.sharding)  # noqa: E731
        st.by_subj = (put(ss), put(sp), put(so))
        st.by_subj_valid = put(sv)
        st.by_obj = (put(os_), put(op), put(oo))
        st.by_obj_valid = put(ov)
        st.refresh_subj_index()
        return st

    def refresh_subj_index(
        self,
        *,
        base_end: Optional[int] = None,
        base_valid=None,
        del_pos=None,
        base_unchanged: bool = False,
    ) -> None:
        """(Re)build the pre-sorted (predicate<<32 | subject) probe index
        from the CURRENT subject-hashed shards, fully ON DEVICE — a host
        round-trip here would cost a transfer.  u64 arrays require the x64
        scope;
        consumers (dist_join) run their jitted bodies under it too.

        With no arguments this is the monolithic full repack (every row
        packed and re-sorted).  Two-tier callers — the serving layer's
        delta-segment mirrors, whose ``by_subj`` is ``concat(base, delta)``
        along the row axis — pass the segment geometry instead, and the
        expensive base sort runs only when the base actually changed:

        - ``base_end``: column index splitting the frozen base region
          ``[:, :base_end]`` from the delta region ``[:, base_end:]``.
        - ``base_valid``: validity of the base region BEFORE tombstones
          (padding only) — the cached base pack must keep tombstoned rows
          so it survives delete batches; deletions are carried by the
          tombstone pack and SUBTRACTED at probe time.
        - ``del_pos``: ``[n, dcap]`` int32 intra-base tombstone positions
          (sentinel >= base_end for padding).
        - ``base_unchanged``: the caller vouches the base region is
          byte-identical to the previous refresh — the cached base pack is
          reused and only the O(delta) packs rebuild.

        Consumers probe :attr:`subj_index_parts` ``(base, tombs, delta)``
        — three sorted packs; a key's multiplicity is
        ``count(base) - count(tombs) + count(delta)``.  The monolithic
        path presents the same shape with empty tomb/delta packs.

        Consumers call :meth:`ensure_subj_index`, which detects stale
        derived state structurally (array identity), so forgetting an
        explicit refresh after a ``by_subj`` write-back cannot produce
        wrong results — only a lazy (full) rebuild.
        """
        with jax.enable_x64(True):
            if base_end is None:
                self.subj_packed_sorted = _pack_sort_device(
                    self.by_subj[0], self.by_subj[1], self.by_subj_valid
                )
                empty = _empty_packs(self.n_shards, self.sharding)
                self.subj_index_parts = (self.subj_packed_sorted,) + empty
                self._subj_base_packed = None
                self._subj_base_end = None
                self.subj_index_base_builds += 1
            else:
                reuse = (
                    base_unchanged
                    and self._subj_base_packed is not None
                    and self._subj_base_end == base_end
                )
                if not reuse:
                    bv = (
                        base_valid
                        if base_valid is not None
                        else self.by_subj_valid[:, :base_end]
                    )
                    self._subj_base_packed = _pack_sort_device(
                        self.by_subj[0][:, :base_end],
                        self.by_subj[1][:, :base_end],
                        bv,
                    )
                    self._subj_base_end = base_end
                    self.subj_index_base_builds += 1
                if del_pos is not None:
                    tombs = _tomb_pack_device(
                        self.by_subj[0][:, :base_end],
                        self.by_subj[1][:, :base_end],
                        del_pos,
                    )
                else:
                    tombs = _empty_packs(self.n_shards, self.sharding)[0]
                delta = _pack_sort_device(
                    self.by_subj[0][:, base_end:],
                    self.by_subj[1][:, base_end:],
                    self.by_subj_valid[:, base_end:],
                )
                self.subj_index_parts = (self._subj_base_packed, tombs, delta)
                self.subj_packed_sorted = self._subj_base_packed
                self.subj_index_delta_builds += 1
        # weakrefs keep the identity check sound: if a source array was
        # collected and its address reused, the dead ref can never compare
        # identical to the new object (a bare id() tuple could).
        self._subj_index_src = (
            weakref.ref(self.by_subj[0]),
            weakref.ref(self.by_subj[1]),
            weakref.ref(self.by_subj_valid),
        )

    def ensure_subj_index(self) -> None:
        """Rebuild the probe index iff ``by_subj`` was reassigned since the
        last build (structural staleness detection — callers need not
        remember to refresh after a write-back).  The lazy rebuild is the
        monolithic one; two-tier owners refresh explicitly at write-back
        time, so a current index is never downgraded here."""
        src = self._subj_index_src
        current = (self.by_subj[0], self.by_subj[1], self.by_subj_valid)
        if (
            self.subj_index_parts is None
            or src is None
            or any(r() is not a for r, a in zip(src, current))
        ):
            self.refresh_subj_index()

    @property
    def n_triples(self) -> int:
        return int(jnp.sum(self.by_subj_valid))

    def gather_host(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """All triples back on host (subject-owned copy), unpadded."""
        v = np.asarray(self.by_subj_valid).ravel()
        s, p, o = (np.asarray(c).ravel()[v] for c in self.by_subj)
        return s, p, o


@jax.jit
def _pack_sort_device(ss, sp, sv):
    """Per-shard (pred<<32|subj) pack + row sort, fully on device (sharding
    propagates from the inputs; sort is along the intra-shard axis)."""
    packed = jnp.where(
        sv,
        (sp.astype(jnp.uint64) << jnp.uint64(32)) | ss.astype(jnp.uint64),
        jnp.uint64(0xFFFFFFFFFFFFFFFF),
    )
    return jnp.sort(packed, axis=1)


@jax.jit
def _tomb_pack_device(ss, sp, del_pos):
    """Sorted (pred<<32|subj) keys of the tombstoned base rows: gather the
    base columns at the per-shard intra positions (sentinel positions out
    of range -> all-ones fill) and sort — O(delta) work against the O(base)
    repack it replaces."""
    sent = jnp.uint64(0xFFFFFFFFFFFFFFFF)
    inb = del_pos < ss.shape[1]
    pos = jnp.minimum(del_pos, ss.shape[1] - 1)
    s = jnp.take_along_axis(ss, pos, axis=1)
    p = jnp.take_along_axis(sp, pos, axis=1)
    packed = jnp.where(
        inb, (p.astype(jnp.uint64) << jnp.uint64(32)) | s.astype(jnp.uint64), sent
    )
    return jnp.sort(packed, axis=1)


def _empty_packs(n_shards: int, sharding):
    """A pair of tiny all-sentinel sorted packs (tombs, delta) so monolithic
    indexes present the same three-part probe surface as two-tier ones."""
    e = np.full((n_shards, 8), 0xFFFFFFFFFFFFFFFF, dtype=np.uint64)
    with jax.enable_x64(True):
        arr = jax.device_put(e, sharding)
    return arr, arr
