"""``optimizer/caps.py`` on its own: the rule's overflow step, the memory's
merge and tighten-once, the loop.  No device and no plan: the loop is handed
a ``run`` that answers from a list (what it does under its three callers is
``tests/test_cap_calibration.py`` section (g))."""

import pytest

from kolibrie_tpu.optimizer import caps


def test_a_capacity_that_overflowed_grows_to_twice_the_count_rounded():
    assert [caps.grown_cap(c) for c in (1, 512, 513, 3000, 6000)] == [
        caps.round_cap(2 * c) for c in (1, 512, 513, 3000, 6000)]
    assert caps.grown_cap(3000) == 8192 and caps.grown_cap(6000) == 16384


def test_merge_is_the_monotonic_elementwise_maximum():
    held = caps.Remembered()
    assert held.get("t") is None and not held.provisional("t") and len(held) == 0
    assert held.merge("t", [2048, 1024]) == (2048, 1024)
    assert held.merge("t", [1024, 4096]) == (2048, 4096)
    assert held.get("t", 2) == (2048, 4096) and held.get("t", 3) is None
    # a replan under the same key: another length starts over
    assert held.merge("t", [1024]) == (1024,)
    assert held.items() == [("t", (1024,))]


def test_a_provisional_entry_is_tightened_by_the_rule_once():
    held = caps.Remembered()
    held.start("t", [65536, 65536], provisional=True)
    assert held.provisional("t") and held.get("t") == (65536, 65536)
    held.settle("t", (65536, 65536), [10, 3000])
    assert not held.provisional("t")
    assert held.get("t") == tuple(caps.fit_join_caps([65536, 65536], [10, 3000]))
    assert held.get("t") == (caps.CAP_FLOOR, 16384)
    held.settle("t", (caps.CAP_FLOOR, 16384), [1, 1])  # settled: only grows
    assert held.get("t") == (caps.CAP_FLOOR, 16384)


def test_a_store_belongs_to_one_database_and_keeps_its_tables_apart():
    class Db:
        pass

    one, other = Db(), Db()
    store = caps.of(one)
    assert caps.of(one) is store and caps.of(other) is not store
    store.joins.start("t", [2048])
    store.groups.start(("t", "stage"), [4096])
    assert store.group_cap(("t", "stage")) == 4096 and store.group_cap("t") is None
    calls = []

    def count():
        calls.append(1)
        return 7

    assert store.largest_key_group("spo", 1, 0, count) == 7
    assert store.largest_key_group("spo", 1, 0, count) == 7 and len(calls) == 1
    assert store.largest_key_group("spo", 2, 0, count) == 7 and len(calls) == 2
    # the base moved: every key-group is counted again, the capacities stay
    assert store.largest_key_group("spo", 1, 1, count) == 7 and len(calls) == 3
    assert store.joins.get("t") == (2048,) and store.group_cap(("t", "stage")) == 4096
    assert caps.of(other).stats() == {"templates": []}
    assert store.stats() == {"templates": [
        {"template": None, "caps": [2048], "provisional": False, "group_caps": [4096]}]}


def _scripted(memory, key, reads, log):
    """A ``run`` that is compiled with what ``memory`` holds and reads back
    the next of ``reads``."""
    def run(attempt):
        caps_now = memory.get(key)
        log.append((attempt, caps_now))
        return f"out{attempt}", caps_now, reads[attempt]
    return run


@pytest.mark.parametrize("reads,ceiling,want_caps,want_retries", [
    ([[10, 20]], None, (1024, 1024), 0),
    ([[10, 3000], [10, 3000]], None, (1024, 8192), 1),
    ([[2000, 3000], [2000, 9000], [2000, 9000]], None, (4096, 32768), 2),
    ([[3000], [3000]], 4096, (4096,), 1),
], ids=["fits", "one_overflow", "a_ladder", "under_a_ceiling"])
def test_the_loop_grows_what_overflowed_and_counts_each_rerun(
        reads, ceiling, want_caps, want_retries):
    memory, log, retried, seconds = caps.Remembered(), [], [], []
    memory.start("t", [1024] * len(reads[0]))
    kwargs = {} if ceiling is None else {"ceiling": ceiling}
    out, ran_with, counts = caps.run_until_fits(
        memory, "t", _scripted(memory, "t", reads, log),
        tally=lambda read, ran: read,
        retried=lambda: retried.append(1),
        rerun_seconds=seconds.append,
        **kwargs,
    )
    assert (out, ran_with, counts) == (f"out{want_retries}", want_caps, reads[-1])
    assert memory.get("t") == want_caps
    assert len(retried) == len(seconds) == want_retries
    assert [attempt for attempt, _caps in log] == list(range(want_retries + 1))
    assert all(s >= 0 for s in seconds)


def test_the_loop_gives_up_on_a_capacity_that_never_fits():
    memory = caps.Remembered()
    memory.start("t", [1024])
    with pytest.raises(RuntimeError, match="converge"):
        caps.run_until_fits(
            memory, "t", lambda attempt: (None, (1024,), [2000]),
            tally=lambda read, ran: read, retried=lambda: None)
