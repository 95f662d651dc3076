"""Pallas kernel tests (run on the CPU interpreter via conftest's platform
override; the same code Mosaic-compiles on TPU).

Parity: the kernels replace the reference's hot loops —
``shared/src/join_algorithm.rs:19-131`` (sorted merge join),
``kolibrie/src/sparql_database.rs:1497-1785`` (SIMD filters), and the f64
semiring combines of ``shared/src/provenance.rs``.
"""

import numpy as np
import pytest
import jax.numpy as jnp

from kolibrie_tpu.ops.pallas_kernels import (
    TILE,
    filter_mask,
    merge_join,
    tag_combine,
)


def ref_join(lk, lv, rk, rv):
    return sorted(
        (int(lk[i]), int(lv[i]), int(rv[j]))
        for i in range(len(lk))
        for j in range(len(rk))
        if lk[i] == rk[j]
    )


def run_join(lk, lv, rk, rv, cap):
    out = merge_join(*map(jnp.asarray, (lk, lv, rk, rv)), cap)
    key, lval, rval, valid, total = (np.asarray(x) for x in out)
    got = sorted(
        zip(key[valid].tolist(), lval[valid].tolist(), rval[valid].tolist())
    )
    return got, int(total)


class TestMergeJoin:
    def test_nm_join_with_gaps(self):
        rng = np.random.default_rng(1)
        lk = np.sort(rng.integers(0, 60, 40).astype(np.int32))
        lv = (np.arange(40) + 1000).astype(np.int32)
        rk = np.sort(rng.integers(0, 60, 50).astype(np.int32))
        rv = (np.arange(50) + 5000).astype(np.int32)
        got, total = run_join(lk, lv, rk, rv, 512)
        exp = ref_join(lk, lv, rk, rv)
        assert got == exp and total == len(exp)

    def test_large_random_multi_tile(self):
        # Forces many output tiles and windows crossing tile boundaries.
        rng = np.random.default_rng(7)
        lk = np.sort(rng.integers(0, 400, 700).astype(np.int32))
        lv = rng.integers(0, 1 << 20, 700).astype(np.int32)
        rk = np.sort(rng.integers(0, 400, 600).astype(np.int32))
        rv = rng.integers(0, 1 << 20, 600).astype(np.int32)
        exp = ref_join(lk, lv, rk, rv)
        got, total = run_join(lk, lv, rk, rv, 8192)
        assert total == len(exp)
        assert got == exp

    def test_heavy_fanout_single_key(self):
        # One key with fanout far beyond a tile: 3 left x 300 right = 900.
        lk = np.array([5, 5, 5], np.int32)
        lv = np.array([1, 2, 3], np.int32)
        rk = np.full(300, 5, np.int32)
        rv = np.arange(300, dtype=np.int32)
        got, total = run_join(lk, lv, rk, rv, 1024)
        assert total == 900
        assert got == ref_join(lk, lv, rk, rv)

    def test_no_matches(self):
        lk = np.array([1, 2, 3], np.int32)
        rk = np.array([10, 20], np.int32)
        got, total = run_join(lk, lk, rk, rk, TILE)
        assert total == 0 and got == []

    def test_empty_sides(self):
        e = np.zeros(0, np.int32)
        k = np.array([1], np.int32)
        assert run_join(e, e, k, k, TILE) == ([], 0)
        assert run_join(k, k, e, e, TILE) == ([], 0)

    def test_overflow_reports_true_total(self):
        lk = np.full(20, 9, np.int32)
        rk = np.full(20, 9, np.int32)
        _, total = run_join(lk, lk, rk, rk, TILE)
        assert total == 400  # > cap: caller re-runs with larger capacity

    def test_cap_rounds_up_not_down(self):
        # cap=200 with 150 matches: capacity must not shrink below request.
        lk = np.arange(150, dtype=np.int32)
        rk = np.arange(150, dtype=np.int32)
        got, total = run_join(lk, lk, rk, rk, 200)
        assert total == 150 and len(got) == 150

    def test_u32_keys_above_2_31(self):
        # Dictionary IDs can use the full u32 range (bit 31 = quoted
        # triples); keys must not wrap negative and break sortedness.
        lk = np.array([10, 2**31 + 5, 2**31 + 9], np.uint32)
        lv = np.array([1, 2, 3], np.uint32)
        rk = np.array([2**31 + 5, 2**31 + 9, 2**31 + 9], np.uint32)
        rv = np.array([7, 8, 9], np.uint32)
        got, total = run_join(lk, lv, rk, rv, TILE)
        assert total == 3
        assert got == ref_join(lk, lv, rk, rv)

    def test_xla_fallback_agrees(self):
        from kolibrie_tpu.ops.pallas_kernels import _xla_merge_join

        rng = np.random.default_rng(11)
        lk = np.sort(rng.integers(0, 80, 60).astype(np.uint32))
        lv = rng.integers(0, 1000, 60).astype(np.uint32)
        rk = np.sort(rng.integers(0, 80, 70).astype(np.uint32))
        rv = rng.integers(0, 1000, 70).astype(np.uint32)
        out = _xla_merge_join(*map(jnp.asarray, (lk, lv, rk, rv)), 1024)
        key, lval, rval, valid, total = (np.asarray(x) for x in out)
        got = sorted(
            zip(key[valid].tolist(), lval[valid].tolist(), rval[valid].tolist())
        )
        assert got == ref_join(lk, lv, rk, rv) and total == len(got)

    def test_sparse_matches_zero_count_runs(self):
        # Long stretches of unmatched left rows between matches: exercises
        # the counts>0 compaction that keeps tile windows bounded.
        lk = np.arange(0, 2000, 2, dtype=np.int32)  # evens
        lv = lk + 1
        rk = np.array([100, 1000, 1998], np.int32)  # three evens
        rv = rk + 7
        got, total = run_join(lk, lv, rk, rv, 256)
        assert total == 3
        assert got == ref_join(lk, lv, rk, rv)


class TestChunkedMergeJoin:
    """The chunk-level driver that lifts ``_PALLAS_MAX_LEFT_ROWS``: forces
    small ``chunk_out`` so multi-chunk stitching (global ``cum``/``kbase``
    against local row windows) is exercised at test sizes.  Output must be
    bit-identical to the XLA formulation of the same join."""

    def _check(self, lk, lv, rk, rv, cap, chunk_out):
        from kolibrie_tpu.ops.pallas_kernels import _xla_merge_join

        ref = _xla_merge_join(*map(jnp.asarray, (lk, lv, rk, rv)), cap)
        got = merge_join(
            *map(jnp.asarray, (lk, lv, rk, rv)), cap, chunk_out=chunk_out
        )
        rt, gt = int(ref[4]), int(got[4])
        assert rt == gt

        def rows(o):
            k, l, r, v, _ = (np.asarray(x) for x in o)
            return sorted(
                zip(k[v].tolist(), l[v].tolist(), r[v].tolist())
            )

        assert rows(ref) == rows(got)
        return gt

    def test_multi_chunk_skewed(self):
        rng = np.random.default_rng(42)
        lk = rng.integers(0, 800, 5000).astype(np.uint32)
        lv = rng.integers(0, 1 << 20, 5000).astype(np.uint32)
        rk = np.sort(rng.integers(0, 800, 3000).astype(np.uint32))
        rv = rng.integers(0, 1 << 20, 3000).astype(np.uint32)
        total = self._check(lk, lv, rk, rv, 32768, 1024)
        assert total > 1024  # really spans many chunks

    def test_heavy_fanout_crosses_chunks(self):
        # One key's run spans several whole chunks: the chunk-level window
        # bound (<= chunk_out + 1 rows) with a single straddling left row.
        lk = np.array([3, 5, 9], np.uint32)
        lv = np.array([30, 50, 90], np.uint32)
        rk = np.sort(
            np.concatenate(
                [np.full(2500, 5, np.uint32), np.array([3, 9], np.uint32)]
            )
        )
        rv = np.arange(2502, dtype=np.uint32)
        total = self._check(lk, lv, rk, rv, 4096, 1024)
        assert total == 2502

    def test_no_matches_multi_chunk(self):
        lk = np.arange(100, dtype=np.uint32)
        rk = np.arange(1000, 1100, dtype=np.uint32)
        total = self._check(lk, lk, rk, rk, 4096, 1024)
        assert total == 0

    def test_tail_chunk_past_total(self):
        # cap far beyond total: tail chunks are all-masked (clamped local
        # row starts, zero valid bits).
        lk = np.arange(50, dtype=np.uint32)
        rk = np.arange(50, dtype=np.uint32)
        total = self._check(lk, lk, rk, rk, 8192, 1024)
        assert total == 50

    def test_indices_multi_chunk(self):
        from kolibrie_tpu.ops.pallas_kernels import merge_join_indices

        rng = np.random.default_rng(7)
        lk = rng.integers(0, 300, 4000).astype(np.uint32)
        rk = np.sort(rng.integers(0, 300, 2000).astype(np.uint32))
        li, ri, valid, tot = merge_join_indices(
            jnp.asarray(lk), jnp.asarray(rk), 65536, chunk_out=1024
        )
        li, ri, valid = (np.asarray(x) for x in (li, ri, valid))
        tot = int(tot)
        assert valid.sum() == tot
        assert np.all(lk[li[valid]] == rk[ri[valid]])
        pairs = set(zip(li[valid].tolist(), ri[valid].tolist()))
        assert len(pairs) == tot
        # exact pair set vs brute force over the key runs
        exp = 0
        for k in np.unique(lk):
            exp += (lk == k).sum() * (rk == k).sum()
        assert tot == exp


def _oracle_indices(lk, rk, lvalid, rvalid, cap):
    """numpy twin of ``merge_join_indices``: rows with a match first, in
    left order, each followed by its run of right rows; the exact total."""
    lk = np.where(lvalid, lk, np.uint32(0xFFFFFFFE))
    rk = np.where(rvalid, rk, np.uint32(0xFFFFFFFF))
    low = np.searchsorted(rk, lk, side="left")
    counts = np.searchsorted(rk, lk, side="right") - low
    rows = np.flatnonzero(counts)
    li = np.repeat(rows, counts[rows])
    ri = np.repeat(low[rows] - (np.cumsum(counts[rows]) - counts[rows]), counts[rows])
    ri = ri + np.arange(len(li))
    total = int(counts.sum())
    cap_r = -(-cap // 1024) * 1024
    out = np.zeros((2, cap_r), np.int32)
    n = min(total, cap_r)
    out[0, :n], out[1, :n] = li[:n], ri[:n]
    return out[0], out[1], np.arange(cap_r) < total, total


def _blocked_case(name):
    """``(lk, rk, lvalid, rvalid, cap, chunk_out)`` of one case of
    :func:`test_blocked_prepass_returns_the_unblocked_rows`."""
    from kolibrie_tpu.ops.pallas_kernels import _SEARCH_BLOCK as B

    rng = np.random.default_rng(39)
    P, N = 2 * B + 1808, 3000  # the last block is clamped back into the width
    rk = np.sort(rng.integers(0, 500, N).astype(np.uint32))
    rvalid = np.arange(N) < N - 200
    lk = rng.integers(0, 600, P).astype(np.uint32)
    extents = {"extent_0": 0, "extent_1": 1, "extent_B-1": B - 1,
               "extent_B": B, "extent_B+1": B + 1, "extent_full": P}
    cap, chunk_out = 65536, None
    if name in extents:
        lvalid = np.arange(P) < extents[name]
    elif name == "holes":
        lvalid = (np.arange(P) < B + 900) & (rng.random(P) < 0.5)
    elif name == "every_row_invalid":
        lk, lvalid = lk[:1000], np.zeros(1000, bool)
    elif name == "narrower_than_a_block":
        lk, lvalid = lk[:1000], np.arange(1000) < 37
    elif name == "runs_cross_a_block_edge":
        # one left key over rows B-3 .. B+3, matching a run of 40 right rows
        rk[1000:1040] = rk[1000]
        rk = np.sort(rk)
        lk[B - 3:B + 4] = rk[1000]
        lvalid = np.arange(P) < B + 4
    elif name == "largest_live_right_key":
        lk[5] = lk[B] = rk[N - 201]  # the last live right row's key
        lvalid = np.arange(P) < B + 1
    elif name == "chunked_driver":
        lvalid, cap, chunk_out = np.arange(P) < B + 77, 8192, 1024
    elif name == "no_lvalid":
        lvalid = None
    else:
        raise KeyError(name)
    return lk, rk, lvalid, rvalid, cap, chunk_out


@pytest.mark.parametrize("name", [
    "extent_0", "extent_1", "extent_B-1", "extent_B", "extent_B+1",
    "extent_full", "holes", "every_row_invalid", "narrower_than_a_block",
    "runs_cross_a_block_edge", "largest_live_right_key", "chunked_driver",
    "no_lvalid",
])
def test_blocked_prepass_returns_the_unblocked_rows(name):
    """ISSUE 39: with a validity mask the run-bound searches stop at the
    block of the last live left row; ``(li, ri, valid, total)`` stay what
    the unblocked searches over every slot give, bit for bit, and what a
    numpy twin gives.  Without a mask the call is the unblocked form."""
    import jax
    from kolibrie_tpu.ops.pallas_kernels import merge_join_indices

    lk, rk, lvalid, rvalid, cap, chunk_out = _blocked_case(name)
    dev = lambda x: None if x is None else jnp.asarray(x)
    with jax.enable_x64(True):
        got = merge_join_indices(
            dev(lk), dev(rk), cap, dev(lvalid), dev(rvalid), chunk_out=chunk_out
        )
        everywhere = np.ones(len(lk), bool) if lvalid is None else lvalid
        unblocked = merge_join_indices(
            dev(np.where(everywhere, lk, np.uint32(0xFFFFFFFE))),
            dev(np.where(rvalid, rk, np.uint32(0xFFFFFFFF))),
            cap,
            chunk_out=chunk_out,
        )
    want = _oracle_indices(lk, rk, everywhere, rvalid, cap)
    assert want[3] > 0 or name in ("extent_0", "every_row_invalid")
    for g, u, w in zip(got, unblocked, want):
        assert g.dtype == u.dtype
        assert np.array_equal(np.asarray(g), np.asarray(u))
        assert np.array_equal(np.asarray(g), w)


@pytest.mark.parametrize("name", [
    "none_matched", "one_matched", "every_row_matched", "narrower_than_a_block",
    "a_block_of_matches_exactly", "one_past_a_block", "last_block_clamped_back",
])
def test_matched_first_is_the_stable_argsorts_head(name):
    """ISSUE 40: the compaction of the matched left rows runs block by block
    up to the last matched row (no sort, which the TPU compiler is slowest
    at): its first ``n_matched`` entries are those of ``argsort(counts == 0,
    stable=True)``, every later entry some row's index, for the caller to
    mask."""
    import jax
    from kolibrie_tpu.ops.pallas_kernels import _SEARCH_BLOCK as B, _matched_first

    rng = np.random.default_rng(40)
    n = 2 * B + 1808
    counts = np.zeros(n, np.int32)
    if name == "one_matched":
        counts[n - 1] = 7
    elif name == "every_row_matched":
        counts[:] = rng.integers(1, 5, n)
    elif name == "narrower_than_a_block":
        n = 1000
        counts = (rng.random(n) < 0.3).astype(np.int32) * 3
    elif name == "a_block_of_matches_exactly":
        counts[rng.choice(n, B, replace=False)] = 2
    elif name == "one_past_a_block":
        counts[rng.choice(n, B + 1, replace=False)] = 1
    elif name == "last_block_clamped_back":
        counts[rng.choice(n, 2 * B + 5, replace=False)] = 1
    with jax.enable_x64(True):
        order, n_matched = _matched_first(jnp.asarray(counts))
    assert order.dtype == jnp.int32 and order.shape == (n,)
    want = np.argsort(counts == 0, kind="stable")
    live = int((counts > 0).sum())
    assert int(n_matched) == live
    order = np.asarray(order)
    assert np.array_equal(order[:live], want[:live])
    assert order.min() >= 0 and order.max() < n


def test_searched_keys_counts_the_blocks_the_searches_cover():
    from kolibrie_tpu.ops.pallas_kernels import _SEARCH_BLOCK as B, searched_keys

    assert searched_keys(65536) == 65536  # no extent: every slot
    assert searched_keys(65536, 0) == 0
    assert searched_keys(65536, 6) == B
    assert searched_keys(65536, B) == B
    assert searched_keys(65536, B + 1) == 2 * B
    assert searched_keys(65536, 10 ** 6) == 65536  # rows past the width
    assert searched_keys(B // 4, 3) == B // 4  # a side narrower than a block
    assert searched_keys(0, 0) == 0


class TestFilterMask:
    def test_pattern_and_range(self):
        rng = np.random.default_rng(3)
        s = rng.integers(0, 10, 500).astype(np.int32)
        p = rng.integers(0, 5, 500).astype(np.int32)
        o = rng.integers(0, 100, 500).astype(np.int32)
        m = np.asarray(
            filter_mask(
                jnp.asarray(s), jnp.asarray(p), jnp.asarray(o),
                s_const=3, o_op=4, o_cmp=50,
            )
        )
        assert (m == ((s == 3) & (o > 50))).all()

    @pytest.mark.parametrize(
        "op,fn",
        [
            (0, np.equal), (1, np.not_equal), (2, np.less),
            (3, np.less_equal), (4, np.greater), (5, np.greater_equal),
        ],
    )
    def test_all_comparators(self, op, fn):
        o = np.arange(40, dtype=np.int32)
        m = np.asarray(
            filter_mask(
                jnp.asarray(o), jnp.asarray(o), jnp.asarray(o),
                o_op=op, o_cmp=17,
            )
        )
        assert (m == fn(o, 17)).all()

    def test_wildcards_pass_everything(self):
        o = np.arange(10, dtype=np.int32)
        m = np.asarray(filter_mask(jnp.asarray(o), jnp.asarray(o), jnp.asarray(o)))
        assert m.all()

    def test_full_u32_range(self):
        """IDs >= 2^31 (quoted-triple bit set) must compare as unsigned:
        equality against a high constant and ordered comparisons across the
        sign-bit boundary both stay exact."""
        o = np.array(
            [5, 0x7FFFFFFF, 0x80000000, 0x90000001, 0xFFFFFFFE], dtype=np.uint32
        )
        s = o.copy()
        p = o.copy()
        m = np.asarray(
            filter_mask(
                jnp.asarray(s), jnp.asarray(p), jnp.asarray(o),
                s_const=0x90000001,
            )
        )
        assert (m == (s == 0x90000001)).all()
        m = np.asarray(
            filter_mask(
                jnp.asarray(s), jnp.asarray(p), jnp.asarray(o),
                o_op=4, o_cmp=0x80000000,
            )
        )
        assert (m == (o.astype(np.uint64) > 0x80000000)).all()
        m = np.asarray(
            filter_mask(
                jnp.asarray(s), jnp.asarray(p), jnp.asarray(o),
                o_op=2, o_cmp=0x90000001,
            )
        )
        assert (m == (o.astype(np.uint64) < 0x90000001)).all()


class TestTagCombine:
    def test_ops(self):
        rng = np.random.default_rng(5)
        a = rng.random(333).astype(np.float32)
        b = rng.random(333).astype(np.float32)
        ja, jb = jnp.asarray(a), jnp.asarray(b)
        assert np.allclose(np.asarray(tag_combine(ja, jb, "min")), np.minimum(a, b))
        assert np.allclose(np.asarray(tag_combine(ja, jb, "max")), np.maximum(a, b))
        assert np.allclose(np.asarray(tag_combine(ja, jb, "mul")), a * b)
        assert np.allclose(
            np.asarray(tag_combine(ja, jb, "noisy_or")), 1 - (1 - a) * (1 - b)
        )

    def test_unknown_op_raises(self):
        a = jnp.zeros(4)
        with pytest.raises(ValueError):
            tag_combine(a, a, "xor")


class TestX64TraceSafety:
    """Regression: callers (device engine, fixpoint) trace whole plans under
    ``jax.enable_x64``; with x64 promotion live inside a kernel body,
    ``jnp.sum`` accumulates i32 in i64 and Mosaic's i64→i32 convert lowering
    recurses without terminating (RecursionError at compile time on real
    TPU — invisible to the CPU interpreter, so assert on the jaxpr: no
    64-bit dtype may appear inside any pallas_call sub-jaxpr)."""

    @staticmethod
    def _assert_no_i64_in_pallas(jaxpr):
        def subjaxprs(params):
            def scan(v):
                if hasattr(v, "eqns"):  # Jaxpr
                    yield v
                elif hasattr(v, "jaxpr"):  # ClosedJaxpr
                    yield v.jaxpr
                elif isinstance(v, (tuple, list)):
                    for item in v:
                        yield from scan(item)
                elif hasattr(v, "block_mappings"):  # pallas GridMapping:
                    # index-map jaxprs ride the dataclass, not params
                    for bm in v.block_mappings:
                        yield from scan(bm.index_map_jaxpr)

            for v in params.values():
                yield from scan(v)

        def walk(j, inside_pallas):
            for eqn in j.eqns:
                inside = inside_pallas or eqn.primitive.name == "pallas_call"
                if inside_pallas:
                    for v in [*eqn.invars, *eqn.outvars]:
                        aval = getattr(v, "aval", None)
                        dt = getattr(aval, "dtype", None)
                        if dt is not None:
                            assert dt.itemsize < 8, (
                                f"64-bit {dt} inside pallas kernel: {eqn}"
                            )
                for sub in subjaxprs(eqn.params):
                    walk(sub, inside)

        walk(jaxpr.jaxpr, False)

    @pytest.mark.parametrize("chunk_out", [None, 1024])
    def test_merge_join_traces_x64_clean(self, chunk_out):
        import jax
        from kolibrie_tpu.ops.pallas_kernels import merge_join_indices

        lkey = jnp.arange(256, dtype=jnp.uint32)
        rkey = jnp.arange(256, dtype=jnp.uint32)
        with jax.enable_x64(True):
            jaxpr = jax.make_jaxpr(
                lambda a, b: merge_join_indices(
                    a, b, cap=2048, chunk_out=chunk_out
                )
            )(lkey, rkey)
        self._assert_no_i64_in_pallas(jaxpr)

    def test_filter_and_tag_trace_x64_clean(self):
        import jax

        s = jnp.arange(256, dtype=jnp.uint32)
        t = jnp.ones(256, jnp.float32)
        with jax.enable_x64(True):
            j1 = jax.make_jaxpr(
                lambda a: filter_mask(a, a, a, o_op=2, o_cmp=7)
            )(s)
            j2 = jax.make_jaxpr(lambda a: tag_combine(a, a, "min"))(t)
        self._assert_no_i64_in_pallas(j1)
        self._assert_no_i64_in_pallas(j2)
