"""Core data model tests: dictionary, quoted triples, columnar store, rules.

Parity targets: shared/src unit tests (dictionary roundtrip, quoted-triple
store roundtrip/nesting at quoted_triple_store.rs:82-158, index query dispatch
at index_manager.rs).
"""

import numpy as np
import pytest

from kolibrie_tpu.core.dictionary import Dictionary, is_quoted_triple_id, QUOTED_BIT
from kolibrie_tpu.core.quoted import QuotedTripleStore
from kolibrie_tpu.core.rule import Rule, FilterCondition, check_rule_safety
from kolibrie_tpu.core.rule_index import RuleIndex, WILDCARD
from kolibrie_tpu.core.store import ColumnarTripleStore
from kolibrie_tpu.core.terms import Term, TriplePattern
from kolibrie_tpu.core.triple import Triple
from kolibrie_tpu.ops.join import equi_join_tables, join_indices, anti_join_mask
from kolibrie_tpu.ops.unique import unique_rows, unique_table


class TestDictionary:
    def test_roundtrip(self):
        d = Dictionary()
        a = d.encode("http://example.org/alice")
        b = d.encode("http://example.org/bob")
        assert a != b
        assert d.encode("http://example.org/alice") == a
        assert d.decode(a) == "http://example.org/alice"
        assert d.decode(b) == "http://example.org/bob"
        assert len(d) == 2

    def test_zero_is_null(self):
        d = Dictionary()
        assert d.decode(0) is None
        assert d.encode("x") == 1

    def test_merge_remap(self):
        d1 = Dictionary()
        d1.encode("a")
        d1.encode("b")
        d2 = Dictionary()
        x = d2.encode("b")
        y = d2.encode("c")
        remap = d1.merge(d2)
        assert d1.decode(remap[x]) == "b"
        assert d1.decode(remap[y]) == "c"
        assert len(d1) == 3

    def test_quoted_bit(self):
        assert is_quoted_triple_id(QUOTED_BIT | 5)
        assert not is_quoted_triple_id(5)


class TestQuotedTripleStore:
    def test_intern_dedup(self):
        q = QuotedTripleStore()
        a = q.intern(1, 2, 3)
        b = q.intern(1, 2, 3)
        assert a == b
        assert is_quoted_triple_id(a)
        assert q.get(a) == (1, 2, 3)

    def test_nesting_decode(self):
        d = Dictionary()
        q = QuotedTripleStore()
        s, p, o = d.encode(":s"), d.encode(":p"), d.encode(":o")
        says = d.encode(":says")
        alice = d.encode(":alice")
        inner = q.intern(s, p, o)
        outer = q.intern(alice, says, inner)
        assert d.decode_term(outer, q) == "<< :alice :says << :s :p :o >> >>"

    def test_merge(self):
        d1, q1 = Dictionary(), QuotedTripleStore()
        d2, q2 = Dictionary(), QuotedTripleStore()
        a2 = d2.encode("a")
        b2 = d2.encode("b")
        c2 = d2.encode("c")
        inner2 = q2.intern(a2, b2, c2)
        outer2 = q2.intern(a2, b2, inner2)
        remap = d1.merge(d2)
        qremap = q1.merge(q2, remap)
        ri = q1.get(qremap[outer2])
        assert q1.get(ri[2]) == (remap[a2], remap[b2], remap[c2])


class TestColumnarStore:
    def test_add_contains_dedup(self):
        st = ColumnarTripleStore()
        st.add(1, 2, 3)
        st.add(1, 2, 3)
        st.add(4, 5, 6)
        assert len(st) == 2
        assert st.contains(1, 2, 3)
        assert not st.contains(9, 9, 9)

    def test_remove(self):
        st = ColumnarTripleStore()
        st.add(1, 2, 3)
        st.add(4, 5, 6)
        st.remove(1, 2, 3)
        assert len(st) == 1
        assert not st.contains(1, 2, 3)
        assert st.contains(4, 5, 6)

    def test_match_dispatch_all_combinations(self):
        st = ColumnarTripleStore()
        rows = [(1, 10, 100), (1, 10, 101), (1, 11, 100), (2, 10, 100), (2, 12, 102)]
        for r in rows:
            st.add(*r)

        def got(**kw):
            s, p, o = st.match(**kw)
            return set(zip(s.tolist(), p.tolist(), o.tolist()))

        assert got(s=1) == {(1, 10, 100), (1, 10, 101), (1, 11, 100)}
        assert got(s=1, p=10) == {(1, 10, 100), (1, 10, 101)}
        assert got(s=1, p=10, o=101) == {(1, 10, 101)}
        assert got(p=10) == {(1, 10, 100), (1, 10, 101), (2, 10, 100)}
        assert got(p=10, o=100) == {(1, 10, 100), (2, 10, 100)}
        assert got(o=100) == {(1, 10, 100), (1, 11, 100), (2, 10, 100)}
        assert got(s=2, o=102) == {(2, 12, 102)}
        assert got() == set(rows)
        assert got(s=7) == set()

    @pytest.mark.parametrize(
        "bound",
        [{}, {"s": 1}, {"p": 10}, {"o": 100}, {"s": 1, "p": 10},
         {"s": 2, "o": 102}, {"p": 10, "o": 100},
         {"s": 1, "p": 10, "o": 101}, {"s": 7}],
        ids=lambda b: "".join(sorted(b)) or "none",
    )
    def test_count_is_the_size_of_the_match(self, bound, monkeypatch):
        from kolibrie_tpu.core.store import SortedOrder

        rows = [(1, 10, 100), (1, 10, 101), (1, 11, 100), (2, 10, 100), (2, 12, 102)]
        st = ColumnarTripleStore()
        for r in rows:
            st.add(*r)
        want = sum(
            all(r["spo".index(k)] == v for k, v in bound.items()) for r in rows
        )
        assert len(st.match(**bound)[0]) == want
        # a range count on the sorted order: no row is sliced out
        monkeypatch.setattr(SortedOrder, "slice_rows", None)
        assert st.count(**bound) == want

    def test_bulk_batch(self):
        st = ColumnarTripleStore()
        n = 10_000
        rng = np.random.default_rng(0)
        s = rng.integers(0, 100, n).astype(np.uint32)
        p = rng.integers(0, 10, n).astype(np.uint32)
        o = rng.integers(0, 1000, n).astype(np.uint32)
        st.add_batch(s, p, o)
        expected = len(set(zip(s.tolist(), p.tolist(), o.tolist())))
        assert len(st) == expected
        ms, mp, mo = st.match(p=int(p[0]))
        assert (mp == p[0]).all()

    def test_clone_independent(self):
        st = ColumnarTripleStore()
        st.add(1, 2, 3)
        c = st.clone()
        c.add(4, 5, 6)
        assert len(st) == 1 and len(c) == 2

    def test_clone_cow_both_directions(self):
        """COW clone: mutations on either side never leak to the other, and
        pre-built sort orders survive on the untouched side."""
        st = ColumnarTripleStore()
        for i in range(50):
            st.add(i, i % 5, i % 7)
        st.order("pos")  # pre-build an order, shared by the clone
        c = st.clone()
        assert c.match(p=2)[0].tolist() == st.match(p=2)[0].tolist()
        st.add(100, 100, 100)
        c.remove(0, 0, 0)
        assert st.contains(100, 100, 100) and st.contains(0, 0, 0)
        assert not c.contains(100, 100, 100) and not c.contains(0, 0, 0)

    def test_merge_insert_compaction_equivalence(self):
        """Small-batch merge-insert compaction must equal the full re-sort
        path: duplicates within the batch, duplicates vs existing rows, and
        interleaved deletes."""
        rng = np.random.default_rng(3)
        base_n = 4000
        bs = rng.integers(0, 64, base_n).astype(np.uint32)
        bp = rng.integers(0, 8, base_n).astype(np.uint32)
        bo = rng.integers(0, 64, base_n).astype(np.uint32)
        st = ColumnarTripleStore()
        st.add_batch(bs, bp, bo)
        st.compact()
        ref = set(st.triples_set())
        # a small batch: some fresh rows, some already-present, some dups
        adds = [(1000, 1, 1), (1000, 1, 1), (int(bs[0]), int(bp[0]), int(bo[0])),
                (0, 0, 0), (2**31 + 5, 3, 9)]
        for a in adds:
            st.add(*a)
            ref.add(a)
        st.remove(int(bs[1]), int(bp[1]), int(bo[1]))
        ref.discard((int(bs[1]), int(bp[1]), int(bo[1])))
        assert set(st.triples_set()) == ref
        s, p, o = st.columns()
        # canonical columns stay lexsorted + unique
        packed = [(int(a), int(b), int(c)) for a, b, c in zip(s, p, o)]
        assert packed == sorted(set(packed))

    def test_snapshot_restore(self):
        st = ColumnarTripleStore()
        for i in range(20):
            st.add(i, 1, i)
        snap = st.snapshot()
        v0 = st.version
        st.add(999, 999, 999)
        assert st.contains(999, 999, 999)
        st.restore(snap)
        assert not st.contains(999, 999, 999) and len(st) == 20
        assert st.version == v0
        # a fresh mutation after restore gets a version never seen before
        st.add(5, 5, 5)
        assert st.version != v0

    def test_roundtrip_npz(self, tmp_path):
        st = ColumnarTripleStore()
        st.add(1, 2, 3)
        st.add(7, 8, 9)
        path = str(tmp_path / "store.npz")
        st.save_npz(path)
        st2 = ColumnarTripleStore.load_npz(path)
        assert st2.triples_set() == st.triples_set()


class TestJoinOps:
    def test_join_indices_basic(self):
        l = np.array([1, 2, 2, 3], dtype=np.uint64)
        r = np.array([2, 3, 3], dtype=np.uint64)
        li, ri = join_indices(l, r)
        pairs = sorted(zip(li.tolist(), ri.tolist()))
        assert pairs == [(1, 0), (2, 0), (3, 1), (3, 2)]

    def test_equi_join_shared_var(self):
        left = {"x": np.array([1, 2, 3], dtype=np.uint32), "y": np.array([10, 20, 30], dtype=np.uint32)}
        right = {"x": np.array([2, 3, 3], dtype=np.uint32), "z": np.array([200, 300, 301], dtype=np.uint32)}
        out = equi_join_tables(left, right)
        rows = sorted(zip(out["x"].tolist(), out["y"].tolist(), out["z"].tolist()))
        assert rows == [(2, 20, 200), (3, 30, 300), (3, 30, 301)]

    def test_cartesian_when_no_shared(self):
        left = {"x": np.array([1, 2], dtype=np.uint32)}
        right = {"y": np.array([7, 8, 9], dtype=np.uint32)}
        out = equi_join_tables(left, right)
        assert len(out["x"]) == 6

    def test_three_key_join(self):
        left = {
            "a": np.array([1, 1, 2], dtype=np.uint32),
            "b": np.array([5, 5, 6], dtype=np.uint32),
            "c": np.array([9, 8, 9], dtype=np.uint32),
        }
        right = {
            "a": np.array([1, 2], dtype=np.uint32),
            "b": np.array([5, 6], dtype=np.uint32),
            "c": np.array([9, 9], dtype=np.uint32),
            "d": np.array([111, 222], dtype=np.uint32),
        }
        out = equi_join_tables(left, right)
        rows = sorted(zip(out["a"].tolist(), out["d"].tolist()))
        assert rows == [(1, 111), (2, 222)]

    def test_anti_join(self):
        l = np.array([1, 2, 3], dtype=np.uint64)
        r = np.array([2], dtype=np.uint64)
        assert anti_join_mask(l, r).tolist() == [True, False, True]

    def test_empty_join(self):
        left = {"x": np.empty(0, dtype=np.uint32)}
        right = {"x": np.array([1], dtype=np.uint32), "y": np.array([2], dtype=np.uint32)}
        out = equi_join_tables(left, right)
        assert len(out["x"]) == 0 and len(out["y"]) == 0


class TestUnique:
    def test_unique_rows(self):
        a = np.array([1, 1, 2, 1], dtype=np.uint32)
        b = np.array([5, 5, 6, 5], dtype=np.uint32)
        cols, idx = unique_rows([a, b])
        assert sorted(zip(cols[0].tolist(), cols[1].tolist())) == [(1, 5), (2, 6)]

    def test_unique_table(self):
        t = {"x": np.array([1, 1, 2], dtype=np.uint32), "y": np.array([3, 3, 4], dtype=np.uint32)}
        u = unique_table(t)
        assert len(u["x"]) == 2


class TestRules:
    def _pat(self, s, p, o):
        def term(v):
            return Term.variable(v[1:]) if isinstance(v, str) and v.startswith("?") else Term.constant(v)

        return TriplePattern(term(s), term(p), term(o))

    def test_safety(self):
        safe = Rule(
            premise=[self._pat("?x", 1, "?y")],
            conclusion=[self._pat("?y", 2, "?x")],
        )
        assert check_rule_safety(safe)
        unsafe_head = Rule(
            premise=[self._pat("?x", 1, "?y")],
            conclusion=[self._pat("?z", 2, "?x")],
        )
        assert not check_rule_safety(unsafe_head)
        unsafe_neg = Rule(
            premise=[self._pat("?x", 1, "?y")],
            negative_premise=[self._pat("?x", 3, "?w")],
            conclusion=[self._pat("?x", 2, "?y")],
        )
        assert not check_rule_safety(unsafe_neg)

    def test_rule_index_candidates(self):
        idx = RuleIndex()
        r0 = Rule(premise=[self._pat("?x", 10, "?y")], conclusion=[self._pat("?x", 11, "?y")])
        r1 = Rule(premise=[self._pat("?x", 20, "?y")], conclusion=[self._pat("?x", 21, "?y")])
        r2 = Rule(premise=[self._pat("?x", "?p", "?y")], conclusion=[self._pat("?x", 99, "?y")])
        idx.add_rule(r0)
        idx.add_rule(r1)
        idx.add_rule(r2)
        assert idx.query_candidate_rules(5, 10, 6) == [0, 2]
        assert idx.query_candidate_rules(5, 20, 6) == [1, 2]
        assert idx.query_candidate_rules(5, 30, 6) == [2]

    def test_filter_condition(self):
        f = FilterCondition("age", ">", 30.0)
        decode = {100: '"35"', 101: '"25"'}.get
        assert f.evaluate(100, decode)
        assert not f.evaluate(101, decode)
        eq = FilterCondition("x", "=", 42)
        assert eq.evaluate(42)
        assert not eq.evaluate(41)


@pytest.mark.parametrize("key", [0, 7, 999, 1000, -1, 2**32, np.int64(7), np.uint32(7)])
def test_a_range_search_takes_its_key_in_the_columns_own_dtype(key, monkeypatch):
    """ISSUE 40 (ROADMAP A11): ``range0`` and ``range012`` search a uint32
    column with a uint32 key, so numpy does not promote (copy) the column a
    call; a key no uint32 holds finds what it found before: nothing."""
    from kolibrie_tpu.core import store as store_mod

    rng = np.random.default_rng(11)
    c0 = np.sort(rng.integers(0, 1000, 5000).astype(np.uint32))
    c2 = rng.integers(0, 1000, 5000).astype(np.uint32)
    order = np.lexsort((c2, np.zeros(5000), c0))
    so = store_mod.SortedOrder.from_parts(
        ("s", "p", "o"), c0, np.zeros(5000, np.uint32), c2[order],
        c0.astype(np.uint64) << np.uint64(32))
    wide = c0.astype(np.int64)
    want = (int(np.searchsorted(wide, int(key), "left")),
            int(np.searchsorted(wide, int(key), "right")))
    seen = []
    real = np.searchsorted
    monkeypatch.setattr(
        store_mod.np, "searchsorted",
        lambda a, v, side="left": seen.append(np.asarray(v).dtype) or real(a, v, side=side))
    assert so.range0(key) == want
    if 0 <= int(key) < 2**32:
        assert set(seen) == {np.dtype(np.uint32)}
        lo, hi = so.range012(key, 0, int(so.c2[want[0]]) if want[1] > want[0] else 5)
        rows = np.flatnonzero((c0 == key) & (so.c2 == (so.c2[want[0]] if want[1] > want[0] else 5)))
        assert (hi - lo) == len(rows)
