"""What a template's capacities are, where they are remembered and how they
grow: the one module that knows (docs/COMPILE_CACHE.md "Capacity protocol").

A join, a WCOJ level or a group table is compiled for a static number of
slots, and every constant variant of one text shares them (one executable a
template).  Here are the RULE from counts to capacities
(:func:`fit_join_caps`, :func:`grown_cap`), the MEMORY (one :class:`CapStore`
a database, :func:`of`) and the overflow LOOP (:func:`run_until_fits`).
Nothing here touches the device; callers pass the loop how to run.
"""

from __future__ import annotations

import math
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, Hashable, List, Optional, Sequence, Tuple

from kolibrie_tpu.ops import round_cap

__all__ = [
    "CAP_FLOOR",
    "CAP_HEADROOM",
    "CapStore",
    "Remembered",
    "fit_join_caps",
    "group_cap_ceiling",
    "grown_cap",
    "of",
    "run_until_fits",
]

# ---------------------------------------------------------------------------
# The rule.  A join or WCOJ level is compiled for the rows the template has
# been seen to produce and never for more than the inputs' capacities
# suggest: the search loops, the compaction sorts, the gathers and the
# readback all cost slots, not rows.  The rule has two arms.  A count that is
# one instance's (or the most of the instances some passes saw) gets headroom,
# CAP_HEADROOM x, for the instances not yet seen.  A count that is a CEILING,
# the most rows any instance of the text gives the join on the store as it
# stands (the calibration says which: LoweredPlan._calibration_counts), gets
# none: there is no instance left to leave room for.  Overflow (a variant with
# more than the headroom, a store that grew past a ceiling) is the loop's
# business, not the rule's.
# ---------------------------------------------------------------------------
CAP_HEADROOM = 4
CAP_FLOOR = 1024
# a dispatch that still overflows after this many runs is a fault, not a fit
MAX_ATTEMPTS = 12


def fit_join_caps(
    heuristic: Sequence[int],
    counts: Sequence[int],
    ceilings: Sequence[bool] = (),
) -> List[int]:
    """THE capacity rule, per join and per WCOJ level:
    ``min(heuristic, round_cap(max(H x count, FLOOR)))``, and where
    ``ceilings[i]`` says that no instance of the text can pass ``counts[i]``
    the same without the ``H``.  Every path that sizes a join from counts
    (the calibrated start, the tighten-once fallback, ``calibrate_host``, the
    mesh's counted plan) goes through here; one that holds a single
    instance's counts passes no ``ceilings`` and keeps the headroom."""
    ceilings = tuple(ceilings) or (False,) * len(counts)
    return [
        min(
            int(h),
            round_cap(max((1 if top else CAP_HEADROOM) * int(c), CAP_FLOOR)),
        )
        for h, c, top in zip(heuristic, counts, ceilings)
    ]


def group_cap_ceiling(slots: int) -> int:
    """No table has more groups than slots: the most a group capacity is
    ever compiled for."""
    return round_cap(max(int(slots), 1))


def grown_cap(count: int) -> int:
    """THE overflow step: what a capacity that ``count`` rows overflowed is
    compiled for next.  Twice the count: the ladder of a template that keeps
    growing is logarithmic in what it finally needs."""
    return round_cap(2 * int(count))


# ---------------------------------------------------------------------------
# The memory
# ---------------------------------------------------------------------------


@dataclass
class _Entry:
    caps: Tuple[int, ...]
    # compiled at the inputs' heuristic because the host calibration was too
    # large to run: the first run that fits tightens it from its counts, once
    provisional: bool = False


class Remembered:
    """One table of capacity vectors by key.  Every constant variant of a
    template shares an entry, so the merge is a MONOTONIC elementwise
    maximum: shrinking a capacity for one variant would recompile (and
    possibly overflow) the next."""

    def __init__(self) -> None:
        self._entries: Dict[Hashable, _Entry] = {}

    def __len__(self) -> int:
        return len(self._entries)

    def get(self, key: Hashable, n: Optional[int] = None) -> Optional[Tuple[int, ...]]:
        """The vector held under ``key``; ``None`` where there is none or it
        is not ``n`` long (a replan under the same key: start over)."""
        entry = self._entries.get(key)
        if entry is None or (n is not None and len(entry.caps) != n):
            return None
        return entry.caps

    def provisional(self, key: Hashable) -> bool:
        entry = self._entries.get(key)
        return entry is not None and entry.provisional

    def start(
        self, key: Hashable, caps: Sequence[int], provisional: bool = False
    ) -> None:
        """A template's first sight on this store: what it starts from."""
        self._entries[key] = _Entry(tuple(int(c) for c in caps), provisional)

    def merge(self, key: Hashable, caps: Sequence[int]) -> Tuple[int, ...]:
        """Hold at least ``caps`` under ``key``; returns what is held."""
        caps = tuple(int(c) for c in caps)
        entry = self._entries.setdefault(key, _Entry(caps))
        if len(entry.caps) == len(caps):
            caps = tuple(max(a, b) for a, b in zip(entry.caps, caps))
        entry.caps = caps
        return caps

    def settle(
        self, key: Hashable, ran_with: Sequence[int], counts: Sequence[int]
    ) -> None:
        """A run compiled with ``ran_with`` fitted ``counts``.  Where the
        entry was provisional its counts size it by the rule, once (the next
        dispatch takes the smaller executable); everywhere else the merge
        stays monotonic."""
        entry = self._entries.get(key)
        if entry is not None and entry.provisional:
            entry.caps = tuple(fit_join_caps(ran_with, counts))
            entry.provisional = False
        else:
            self.merge(key, ran_with)

    def items(self):
        """``(key, vector)`` of every entry."""
        return [(key, entry.caps) for key, entry in self._entries.items()]


class CapStore:
    """One database's memory of capacities.  Three tables, apart and under
    keys of their own: a template's join and WCOJ-level capacities by its
    ``cap_key``; its GROUP BY's one capacity by ``(cap_key, stage.key)``; and
    the largest key-group of an order's bound prefix, which holds for one
    ``base_version`` of the store and is dropped whole when that moves."""

    def __init__(self) -> None:
        self.joins = Remembered()
        self.groups = Remembered()
        # cap_key -> the fingerprint its template was first dispatched under
        self.templates: Dict[Hashable, str] = {}
        self._key_groups: Dict[Tuple[str, int], int] = {}
        self._key_groups_version: Optional[int] = None

    def group_cap(self, key: Hashable) -> Optional[int]:
        held = self.groups.get(key)
        return None if held is None else held[0]

    def largest_key_group(
        self, order: str, n_bound: int, base_version: int, count: Callable[[], int]
    ) -> int:
        """Rows of the largest group of ``order``'s first ``n_bound`` columns
        in the frozen base at ``base_version``; ``count()`` (O(base)) where
        this version has not been asked yet."""
        if self._key_groups_version != base_version:
            self._key_groups = {}
            self._key_groups_version = base_version
        key = (order, n_bound)
        if key not in self._key_groups:
            self._key_groups[key] = int(count())
        return self._key_groups[key]

    def stats(self) -> dict:
        """The ``/stats`` block: per template of this store its join
        capacities, whether they still await their tightening, and its group
        capacities (an aggregate template's one capacity more)."""
        group_caps: Dict[Hashable, List[int]] = {}
        for (cap_key, _stage), held in self.groups.items():
            group_caps.setdefault(cap_key, []).append(held[0])
        return {
            "templates": [
                {
                    "template": self.templates.get(cap_key),
                    "caps": list(held),
                    "provisional": self.joins.provisional(cap_key),
                    "group_caps": group_caps.get(cap_key, []),
                }
                for cap_key, held in self.joins.items()
            ]
        }


def of(db) -> CapStore:
    """The capacity store of ``db``, made on first use.  It hangs on the
    database object so that it lives and dies with the store it describes:
    no capacity outlasts its data, none is shared between two stores."""
    store = db.__dict__.get("_device_caps")
    if store is None:
        store = db.__dict__["_device_caps"] = CapStore()
    return store


# ---------------------------------------------------------------------------
# The loop
# ---------------------------------------------------------------------------


def run_until_fits(
    memory: Remembered,
    key: Hashable,
    run: Callable[[int], Tuple[Any, Sequence[int], Any]],
    tally: Callable[[Any, Sequence[int]], Sequence[int]],
    retried: Callable[[], None],
    rerun_seconds: Optional[Callable[[float], None]] = None,
    ceiling: float = math.inf,
):
    """THE overflow loop.  ``run(attempt)`` dispatches and reads back:
    ``(out, caps, read)``, what the program produced, the capacities it was
    compiled with (taken from ``memory``, so a re-run sees what this loop
    stored) and the counts as read.  ``tally(read, caps)`` does the attempt's
    accounting and reduces ``read`` to one count a capacity: itself, the most
    of a group's live members, the groups of an aggregation.  A count above
    its capacity grows it (:func:`grown_cap`, never past ``ceiling``) in
    ``memory``; ``retried()`` counts the re-run, ``rerun_seconds`` its wall
    time up to its counts on the host.  The run that fits settles the entry.
    Returns its ``(out, caps, counts)``."""
    started = None
    for attempt in range(MAX_ATTEMPTS):
        out, caps, read = run(attempt)
        if started is not None and rerun_seconds is not None:
            rerun_seconds(time.perf_counter() - started)
        counts = [int(c) for c in tally(read, caps)]
        if all(c <= cap for c, cap in zip(counts, caps)):
            memory.settle(key, caps, counts)
            return out, caps, counts
        retried()
        memory.merge(
            key,
            [
                min(grown_cap(c), ceiling) if c > cap else cap
                for c, cap in zip(counts, caps)
            ],
        )
        started = time.perf_counter()
    raise RuntimeError("capacities failed to converge")
