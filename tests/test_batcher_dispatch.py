"""When a group leaves ``TemplateBatcher``: on arrival where the server is
idle, at the holder's release where it is busy.

Everything runs against a stub executor, in milliseconds, and no test sleeps
to synchronise: a held dispatch is an event the test opens, "the followers
have queued" is a semaphore the batcher's own lock releases as each of them
starts to wait for it, and every join has a timeout and is asserted."""

import os
import sys
import threading
import types

import pytest

from kolibrie_tpu.frontends import http_server
from kolibrie_tpu.frontends.http_server import TemplateBatcher
from kolibrie_tpu.resilience.deadline import Deadline, deadline_scope
from kolibrie_tpu.resilience.errors import DeadlineExceeded, Overloaded

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
JOIN_S = 10.0


class StubExecutor:
    """Stands in for ``execute_queries_batched`` / ``execute_query_volcano``.
    The first batched call waits for ``gate`` when ``hold_first`` is set."""

    def __init__(self, hold_first=False, fail_batches=False, bad=()):
        self.calls = []  # the texts of each batched call, in order
        self.solo = []
        self.order = []  # shared with a test's own events
        self.entered = threading.Event()  # the first batched call is inside
        self.gate = threading.Event()
        self.hold_first = hold_first
        self.fail_batches = fail_batches
        self.bad = set(bad)
        self.inside = 0  # dispatches running right now: never above 1
        self.overlapped = False

    def batched(self, db, texts):
        self.inside += 1
        self.overlapped |= self.inside > 1
        try:
            self.calls.append(list(texts))
            self.order.append("dispatch")
            first = len(self.calls) == 1
            if first:
                self.entered.set()
                if self.hold_first:
                    assert self.gate.wait(JOIN_S), "the test never opened the gate"
            if self.fail_batches and not first:
                raise ValueError("one bad member")
            return [("rows of", t) for t in texts]
        finally:
            self.inside -= 1

    def volcano(self, text, db):
        self.solo.append(text)
        if text in self.bad:
            raise ValueError(f"bad query {text}")
        return ("rows of", text)


@pytest.fixture
def stub(monkeypatch):
    def install(**kw):
        s = StubExecutor(**kw)
        monkeypatch.setattr(
            "kolibrie_tpu.query.executor.execute_queries_batched", s.batched
        )
        monkeypatch.setattr(
            "kolibrie_tpu.query.executor.execute_query_volcano", s.volcano
        )
        return s

    return install


def make_batcher(**kw):
    """A batcher over a store that holds nothing, whose lock says when a
    request has queued behind a holder (its request is in ``pending`` by
    then: ``_submit`` appends before it looks at the lock)."""
    b = TemplateBatcher(types.SimpleNamespace(), **kw)
    b.queued = threading.Semaphore(0)
    wait = b.dispatch_lock.acquire_unless

    def acquire_unless(done, timeout):
        b.queued.release()
        return wait(done, timeout)

    b.dispatch_lock.acquire_unless = acquire_unless
    return b


class Client(threading.Thread):
    """One submit on a thread of its own; keeps what came back."""

    def __init__(self, batcher, text, deadline=None):
        super().__init__(daemon=True)
        self.batcher, self.text, self.deadline = batcher, text, deadline
        self.result = self.error = None
        self.start()

    def run(self):
        try:
            with deadline_scope(self.deadline):
                self.result = self.batcher.submit(self.text)
        except Exception as e:
            self.error = e

    def finish(self):
        self.join(JOIN_S)
        assert not self.is_alive(), f"submit({self.text!r}) never returned"
        return self


def starts():
    fam = http_server._BATCH_DISPATCH_START
    return {at: fam.labels(at).value for at in ("arrival", "handoff")}


def grew(before):
    return {at: v - before[at] for at, v in starts().items()}


def hold_a_dispatch(batcher, s):
    """A first request whose dispatch stays open until ``s.gate`` is set."""
    first = Client(batcher, "first")
    assert s.entered.wait(JOIN_S)
    return first


def queue_behind(batcher, texts, **kw):
    clients = [Client(batcher, t, **kw) for t in texts]
    for _ in clients:
        assert batcher.queued.acquire(timeout=JOIN_S), "a request never queued"
    return clients


def test_lone_request_to_an_idle_batcher_leaves_on_arrival(stub):
    s, b, before = stub(), make_batcher(), starts()
    assert b.submit("q") == ("rows of", "q")
    assert s.calls == [["q"]]
    assert grew(before) == {"arrival": 1, "handoff": 0}
    assert (b.requests, b.dispatches, b.pending) == (1, 1, [])
    # it never waited for the lock: nothing queued
    assert not b.queued.acquire(blocking=False)


@pytest.mark.parametrize("k", [1, 3, 7])
def test_what_queues_behind_a_dispatch_in_flight_rides_the_next_one(stub, k):
    s, b, before = stub(hold_first=True), make_batcher(), starts()
    first = hold_a_dispatch(b, s)
    texts = ["a", "b", "a", "c", "b", "a", "d"][:k]
    followers = queue_behind(b, texts)
    assert s.calls == [["first"]]  # nothing left while the lock was held
    s.gate.set()
    assert first.finish().result == ("rows of", "first")
    for f in followers:
        assert f.finish().error is None
        assert f.result == ("rows of", f.text)
    uniq = list(dict.fromkeys(texts))
    # one group, each text once (whoever led it drained in arrival order)
    assert len(s.calls) == 2 and sorted(s.calls[1]) == sorted(uniq)
    assert grew(before) == {"arrival": 1, "handoff": 1}
    assert (b.requests, b.dispatches, b.max_batch) == (k + 1, 2, k)
    assert b.dedup_hits == k - len(uniq)
    assert b.pending == []


def test_a_waiter_past_its_deadline_sheds_and_its_mates_ride_on(stub):
    s, b = stub(hold_first=True), make_batcher()
    first = hold_a_dispatch(b, s)
    (mate,) = queue_behind(b, ["mate"])
    # a clock that stands still: the budget cannot run out before the
    # request has queued, and the wait for the lock is 20 ms of real time
    (late,) = queue_behind(b, ["late"], deadline=Deadline(0.02, clock=lambda: 0.0))
    late.finish()
    assert isinstance(late.error, DeadlineExceeded)
    assert late.error.site == "batcher.queue"
    with b.lock:
        assert [r.text for r in b.pending] == ["mate"]
    assert b.shed_deadline == 1
    assert s.calls == [["first"]]  # the dispatch in flight was not disturbed
    s.gate.set()
    assert first.finish().result == ("rows of", "first")
    assert mate.finish().result == ("rows of", "mate")
    assert s.calls == [["first"], ["mate"]]


def test_a_query_behind_a_load_starts_when_the_load_releases(stub):
    s, b, before = stub(), make_batcher(), starts()
    with b.dispatch_lock:  # what /store/load, /stats, a checkpoint hold
        s.order.append("load holds")
        (q,) = queue_behind(b, ["q"])
        s.order.append("query queued")
        assert s.calls == []
        s.order.append("load releases")
    assert q.finish().result == ("rows of", "q")
    assert s.order == ["load holds", "query queued", "load releases", "dispatch"]
    assert grew(before) == {"arrival": 0, "handoff": 1}


def test_overloaded_at_max_queue_depth(stub):
    s, b = stub(hold_first=True), make_batcher(max_queue_depth=2)
    first = hold_a_dispatch(b, s)
    followers = queue_behind(b, ["a", "b"])
    with pytest.raises(Overloaded) as e:
        b.submit("one too many")
    assert e.value.retry_after_s == 0.05
    assert (b.shed_queue_full, b.requests) == (1, 3)
    s.gate.set()
    for c in [first] + followers:
        assert c.finish().result == ("rows of", c.text)


def test_a_failed_batch_gives_every_member_its_solo_retry(stub):
    s, b = stub(hold_first=True, fail_batches=True, bad={"bad"}), make_batcher()
    fallbacks = http_server._BATCH_FALLBACKS.labels().value
    first = hold_a_dispatch(b, s)
    followers = queue_behind(b, ["a", "bad", "c"])
    s.gate.set()
    first.finish()
    for f in followers:
        f.finish()
    assert sorted(s.solo) == ["a", "bad", "c"]
    by_text = {f.text: f for f in followers}
    assert by_text["a"].result == ("rows of", "a")
    assert by_text["c"].result == ("rows of", "c")
    assert isinstance(by_text["bad"].error, ValueError)
    assert by_text["a"].error is None and by_text["c"].error is None
    assert http_server._BATCH_FALLBACKS.labels().value - fallbacks == 1
    assert b.dispatches == 2


def test_the_benchmark_reads_its_three_counts_off_the_batcher(stub):
    """``benchmark/layer_metrics/batcher_*_in_window.json`` through the
    benchmark's own reader, on what ``/metrics`` renders."""
    sys.path.insert(0, REPO)
    from benchmark.harness import data as files
    from kolibrie_tpu.obs import export

    def counters():
        lines = (ln.rpartition(" ") for ln in export.render_prometheus().splitlines()
                 if ln and not ln.startswith("#"))
        return {"metrics." + key: float(value) for key, _, value in lines}

    def read(name, ctx):
        args = dict(files.read_json("layer_metrics", name + ".json")["reader"])
        return files.load_module("readers", args.pop("kind")).read(ctx, **args)

    s, b = stub(hold_first=True), make_batcher()
    ctx = {"counters0": counters()}
    first = hold_a_dispatch(b, s)
    followers = queue_behind(b, ["a", "b", "c"])
    s.gate.set()
    for c in [first] + followers:
        c.finish()
    ctx["counters1"] = counters()
    assert read("batcher_arrival_starts_in_window", ctx) == 1.0
    assert read("batcher_dispatches_in_window", ctx) == 2.0
    assert read("batcher_requests_in_window", ctx) == 4.0


def test_many_threads_each_get_their_own_answer_and_never_overlap(stub):
    s, b, before = stub(), make_batcher(), starts()
    threads, each = 16, 40
    wrong = []

    def client(i):
        for j in range(each):
            text = f"q{i}.{j % 3}"  # some texts meet their own repeats
            if b.submit(text) != ("rows of", text):
                wrong.append(text)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        workers = [
            threading.Thread(target=client, args=(i,), daemon=True)
            for i in range(threads)
        ]
        for w in workers:
            w.start()
        for w in workers:
            w.join(60.0)
            assert not w.is_alive()
    finally:
        sys.setswitchinterval(interval)
    assert wrong == [] and not s.overlapped
    assert b.requests == threads * each and b.pending == []
    # every request rode exactly one dispatch, every dispatch was counted once
    assert sum(len(c) for c in s.calls) + b.dedup_hits == threads * each
    assert sum(grew(before).values()) == b.dispatches == len(s.calls)
