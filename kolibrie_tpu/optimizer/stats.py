"""Sampled database statistics for cardinality estimation.

Parity: ``streamertail_optimizer/stats/database_stats.rs:18-105`` —
``gather_stats_fast``: ≤100k step-sampled triples, scaled-up per-term
cardinality maps (predicates counted over every row), and a
join-selectivity cache.  Counting is vectorized
(np.unique) rather than rayon-folded.
"""

from __future__ import annotations

import weakref
from typing import Dict

import numpy as np

SAMPLE_CAP = 100_000


class DatabaseStats:
    def __init__(self) -> None:
        self.total_triples = 0
        self.quoted_triple_count = 0
        self.distinct_subjects = 0
        self.distinct_predicates = 0
        self.distinct_objects = 0
        self.predicate_counts: Dict[int, float] = {}
        self.subject_counts: Dict[int, float] = {}
        self.object_counts: Dict[int, float] = {}
        self.join_selectivity_cache: Dict[int, float] = {}
        self._db_ref = None  # weakref to the sampled database

    def database(self):
        """The database these stats were sampled from (None for
        hand-built stats or after the database was collected) — the
        stats-advisor's host-oracle exploration needs a store to count
        against (docs/OPTIMIZER.md)."""
        return self._db_ref() if self._db_ref is not None else None

    @staticmethod
    def gather_stats_fast(db) -> "DatabaseStats":
        st = DatabaseStats()
        st._db_ref = weakref.ref(db)
        s, p, o = db.store.columns()
        n = len(s)
        st.total_triples = n
        st.quoted_triple_count = len(getattr(db, "quoted", ()) or ())
        if n == 0:
            return st
        # Predicates are counted over every row: there are few of them, a
        # join order rests on each count, and a step sample of rows sorted by
        # subject meets a class whose instances all carry the same k triples
        # (k a divisor of the step) at one predicate every time: WatDiv's
        # purchases read 1 row for ``purchaseDate`` in one seed and 300,000
        # for ``purchaseFor``, the other way round in the next, and a
        # template's join order with them (PERF.md section 6, PR 40).
        up, cp = np.unique(p, return_counts=True)
        if n > SAMPLE_CAP:
            step = n // SAMPLE_CAP
            idx = np.arange(0, n, step)
            scale = n / len(idx)
            s, o = s[idx], o[idx]
        else:
            scale = 1.0
        us, cs = np.unique(s, return_counts=True)
        uo, co = np.unique(o, return_counts=True)
        st.distinct_subjects = int(len(us) * scale) if scale > 1 else len(us)
        st.distinct_predicates = len(up)
        st.distinct_objects = int(len(uo) * scale) if scale > 1 else len(uo)
        st.subject_counts = dict(zip(us.tolist(), (cs * scale).tolist()))
        st.predicate_counts = dict(zip(up.tolist(), cp.astype(float).tolist()))
        st.object_counts = dict(zip(uo.tolist(), (co * scale).tolist()))
        return st

    # ------------------------------------------------------------ estimates

    def pattern_cardinality(self, pattern) -> float:
        """Estimated matching rows for a triple pattern (constant positions
        narrow the estimate multiplicatively, mirroring estimator.rs:194+)."""
        n = float(max(self.total_triples, 1))
        est = n
        s, p, o = pattern.subject, pattern.predicate, pattern.object
        if s.kind == "id":
            est = min(est, self.subject_counts.get(s.value, 1.0))
        if p.kind == "id":
            est = min(est, self.predicate_counts.get(p.value, 1.0))
        if o.kind == "id":
            est = min(est, self.object_counts.get(o.value, 1.0))
        return max(est, 0.0)

    def hottest_key_rows(self, pattern) -> "float | None":
        """What a pattern that binds its predicate and one of subject and
        object holds at the key with most rows under that predicate
        (:func:`hottest_key_rows`): a number of the text's shape and the
        store, not of the constant a request happened to carry.  ``None``
        for any other pattern, and for stats with no database behind
        them."""
        s, p, o = pattern.subject, pattern.predicate, pattern.object
        if p.kind != "id" or p.value is None or (s.kind == "id") == (o.kind == "id"):
            return None
        db = self.database()
        if db is None:
            return None
        return float(hottest_key_rows(db, int(p.value), "s" if s.kind == "id" else "o"))

    def join_selectivity(self, card_left: float, card_right: float) -> float:
        """Crude independence assumption over the larger distinct-value side
        (fallback when neither join side has a bound predicate)."""
        denom = max(self.distinct_subjects + self.distinct_objects, 1)
        return 1.0 / denom

    def get_join_selectivity(self, predicate: int) -> float:
        """Cached per-predicate selectivity = |pred| / |db|
        (``database_stats.rs:129-153`` ``get_join_selectivity``)."""
        cached = self.join_selectivity_cache.get(predicate)
        if cached is not None:
            return cached
        if self.total_triples > 0:
            sel = self.predicate_counts.get(predicate, 0.0) / self.total_triples
        else:
            sel = 0.1
        self.join_selectivity_cache[predicate] = sel
        return sel

    # --------------------------------------------- incremental maintenance

    def update_stats(self, s: int, p: int, o: int) -> None:
        """Count one added triple (``database_stats.rs:156-165`` parity
        API).  The engine itself rebuilds stats per store version
        (``SparqlDatabase.get_or_build_stats``); this keeps a LONG-LIVED
        stats object coherent across small mutation batches — including
        the distinct counts the independence-fallback selectivity uses."""
        self.total_triples += 1
        for counts, key, attr in (
            (self.subject_counts, s, "distinct_subjects"),
            (self.predicate_counts, p, "distinct_predicates"),
            (self.object_counts, o, "distinct_objects"),
        ):
            prev = counts.get(key, 0.0)
            if prev <= 0:
                setattr(self, attr, getattr(self, attr) + 1)
            counts[key] = prev + 1.0
        self.join_selectivity_cache.clear()

    def remove_stats(self, s: int, p: int, o: int) -> None:
        """Uncount one removed triple (``database_stats.rs:168-193``)."""
        self.total_triples = max(self.total_triples - 1, 0)
        for counts, key, attr in (
            (self.subject_counts, s, "distinct_subjects"),
            (self.predicate_counts, p, "distinct_predicates"),
            (self.object_counts, o, "distinct_objects"),
        ):
            v = counts.get(key)
            if v is not None and v > 0:
                counts[key] = v - 1.0
                if v - 1.0 <= 0:
                    setattr(self, attr, max(getattr(self, attr) - 1, 0))
        self.join_selectivity_cache.clear()


def hottest_key_rows(db, predicate: int, key: str) -> int:
    """Rows of the subject (``key`` "s") or object ("o") that holds most rows
    under ``predicate`` in the frozen base segment: the largest ``(s, p)``
    group of ``spo``, or ``(p, o)`` group of ``pos``, the orders a scan that
    binds the pair reads; with ``key`` "p", every base row under the
    predicate.  0 for a predicate the base does not hold.  One pass a key,
    kept per ``base_version`` on the database: the planner's ordering cost
    (:meth:`DatabaseStats.hottest_key_rows`) and a scan's compiled width
    (:func:`device_engine.template_scan_cap`) read this one table."""
    store = db.store
    cache = db.__dict__.setdefault("_hottest_key_rows_cache", {})
    bv = store.base_version
    table = cache.get((key, bv))
    if table is None:
        for stale in [k for k in cache if k[1] != bv]:
            del cache[stale]
        base = store.base_order("pos" if key == "o" else "spo")
        rows = base.slice_rows(0, len(base))
        p = rows["p"]
        if key == "p":
            preds, most = np.unique(p, return_counts=True)
        elif len(p):
            k = rows[key]
            starts = np.flatnonzero(
                np.r_[True, (p[1:] != p[:-1]) | (k[1:] != k[:-1])]
            )
            sizes = np.diff(np.r_[starts, len(p)])
            preds, inverse = np.unique(p[starts], return_inverse=True)
            most = np.zeros(len(preds), dtype=np.int64)
            np.maximum.at(most, inverse, sizes)
        else:
            preds = most = p
        table = dict(zip(preds.tolist(), most.tolist()))
        cache[(key, bv)] = table
    return table.get(predicate, 0)
