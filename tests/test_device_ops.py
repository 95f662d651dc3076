"""Device kernel tests: static-shape joins/dedup/scans agree with the host
numpy paths (ops/join.py) on randomized inputs."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kolibrie_tpu.ops import device_join as dj
from kolibrie_tpu.ops.join import join_indices as host_join


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(0)


class TestJoinIndices:
    def test_agrees_with_host(self, rng):
        lk = rng.integers(0, 50, 200).astype(np.uint32)
        rk = rng.integers(0, 50, 150).astype(np.uint32)
        li, ri, valid, total = dj.join_indices(
            jnp.asarray(lk), jnp.asarray(rk), cap=4096
        )
        hli, hri = host_join(lk.astype(np.uint64), rk.astype(np.uint64))
        assert int(total) == len(hli)
        v = np.asarray(valid)
        got = set(zip(np.asarray(li)[v].tolist(), np.asarray(ri)[v].tolist()))
        assert got == set(zip(hli.tolist(), hri.tolist()))

    def test_masked_rows_excluded(self, rng):
        lk = rng.integers(0, 50, 200).astype(np.uint32)
        rk = rng.integers(0, 50, 150).astype(np.uint32)
        lv = rng.random(200) > 0.3
        rv = rng.random(150) > 0.3
        _, _, _, total = dj.join_indices(
            jnp.asarray(lk), jnp.asarray(rk), cap=4096,
            lvalid=jnp.asarray(lv), rvalid=jnp.asarray(rv),
        )
        hli, _ = host_join(lk[lv].astype(np.uint64), rk[rv].astype(np.uint64))
        assert int(total) == len(hli)

    def test_overflow_reports_true_total(self):
        lk = jnp.zeros(32, dtype=jnp.uint32)
        rk = jnp.zeros(32, dtype=jnp.uint32)
        _, _, valid, total = dj.join_indices(lk, rk, cap=16)
        assert int(total) == 32 * 32
        assert int(np.asarray(valid).sum()) == 16

    def test_empty_sides(self):
        e = jnp.zeros(0, dtype=jnp.uint32)
        x = jnp.arange(5, dtype=jnp.uint32)
        for a, b in ((e, x), (x, e), (e, e)):
            _, _, valid, total = dj.join_indices(a, b, cap=8)
            assert int(total) == 0 and not np.asarray(valid).any()


class TestSortUnique:
    def test_dedups_exactly(self, rng):
        s = rng.integers(1, 10, 64).astype(np.uint32)
        p = rng.integers(1, 4, 64).astype(np.uint32)
        o = rng.integers(1, 10, 64).astype(np.uint32)
        v = np.ones(64, bool)
        v[50:] = False
        (us, up, uo), uv, n = dj.sort_unique_rows(
            (jnp.asarray(s), jnp.asarray(p), jnp.asarray(o)),
            jnp.asarray(v), cap=128,
        )
        want = set(zip(s[:50].tolist(), p[:50].tolist(), o[:50].tolist()))
        k = int(n)
        got = set(zip(np.asarray(us)[:k].tolist(), np.asarray(up)[:k].tolist(),
                      np.asarray(uo)[:k].tolist()))
        assert got == want and k == len(want)

    def test_all_invalid(self):
        z = jnp.zeros(8, dtype=jnp.uint32)
        _, uv, n = dj.sort_unique_rows((z, z, z), jnp.zeros(8, bool), cap=8)
        assert int(n) == 0 and not np.asarray(uv).any()


class TestSetDifference:
    def test_difference_exact(self, rng):
        s = rng.integers(1, 10, 64).astype(np.uint32)
        p = rng.integers(1, 4, 64).astype(np.uint32)
        o = rng.integers(1, 10, 64).astype(np.uint32)
        v = np.ones(64, bool)
        v[50:] = False
        (ds, dp_, do_), dv, dn = dj.set_difference_rows(
            (jnp.asarray(s), jnp.asarray(p), jnp.asarray(o)), jnp.asarray(v),
            (jnp.asarray(s[:20]), jnp.asarray(p[:20]), jnp.asarray(o[:20])),
            jnp.asarray(np.ones(20, bool)), cap=128,
        )
        first20 = set(zip(s[:20].tolist(), p[:20].tolist(), o[:20].tolist()))
        want = {r for r in zip(s[:50].tolist(), p[:50].tolist(), o[:50].tolist())
                if r not in first20}
        k = int(dn)
        got = set(zip(np.asarray(ds)[:k].tolist(), np.asarray(dp_)[:k].tolist(),
                      np.asarray(do_)[:k].tolist()))
        assert got == want


class TestScansAndFilters:
    def test_compare_filter_all_ops(self):
        col = jnp.asarray(np.arange(10, dtype=np.uint32))
        ops = {0: np.equal, 1: np.not_equal, 2: np.greater,
               3: np.less, 4: np.greater_equal, 5: np.less_equal}
        for code, fn in ops.items():
            m = dj.compare_filter(col, jnp.int32(code), jnp.uint32(5))
            np.testing.assert_array_equal(
                np.asarray(m), fn(np.arange(10), 5)
            )

    def test_prefix_range_scan(self, rng):
        s = np.sort(rng.integers(1, 20, 64)).astype(np.uint64)
        with jax.enable_x64(True):
            key = jnp.asarray(s << np.uint64(32))
        (out,), valid, n = dj.prefix_range_scan(
            key, (key,), np.uint64(5 << 32), np.uint64(9 << 32), cap=64
        )
        assert int(n) == ((s >= 5) & (s < 9)).sum()


class TestSemiJoin:
    def test_mask(self, rng):
        lk = rng.integers(0, 30, 100).astype(np.uint32)
        rk = rng.integers(0, 30, 50).astype(np.uint32)
        m = dj.semi_join_mask(jnp.asarray(lk), jnp.asarray(rk))
        np.testing.assert_array_equal(np.asarray(m), np.isin(lk, rk))

    def test_empty_right(self):
        lk = jnp.arange(5, dtype=jnp.uint32)
        m = dj.semi_join_mask(lk, jnp.zeros(0, dtype=jnp.uint32))
        assert not np.asarray(m).any()
