"""Durability unit tests — ISSUE 7.

WAL frame round trips, torn/CRC/bit-flip truncation semantics, the
fault-injection sites on the disk path, atomic snapshot rotation with
pruning, and DurabilityManager end-to-end recovery against a plain
in-memory oracle.  The process-crash variants (kill -9 a live server)
live in tests/test_chaos_durability.py; these tests exercise the same
machinery in-process where every intermediate state can be inspected.
"""

import json
import os
import struct
import zlib

import pytest

from kolibrie_tpu.durability import fsio, wal
from kolibrie_tpu.durability.manager import DurabilityManager
from kolibrie_tpu.query.sparql_database import SparqlDatabase
from kolibrie_tpu.resilience.errors import DurabilityError
from kolibrie_tpu.resilience.faultinject import (
    FaultPlan,
    InjectedBitFlip,
    InjectedFsyncFault,
    InjectedTornWrite,
)

# ------------------------------------------------------------------ helpers


def wal_dir(tmp_path):
    d = str(tmp_path / "wal")
    os.makedirs(d, exist_ok=True)
    return d


def triples(db):
    """Canonical decoded-triple multiset of a database (oracle compare)."""
    return sorted(db.iter_decoded())


def seed_db(n=20, prefix="e"):
    db = SparqlDatabase()
    for i in range(n):
        db.add_triple_parts(
            f"<http://x/{prefix}{i}>", "<http://x/p>", f"<http://x/v{i % 7}>"
        )
    return db


# ------------------------------------------------------- WAL frame encoding


def test_wal_record_round_trip(tmp_path):
    d = wal_dir(tmp_path)
    w = wal.WalWriter(d, fsync_policy="always")
    metas = [
        {"k": "mut", "st": "s", "i": i, "note": "π ≠ ascii"} for i in range(5)
    ]
    tails = [bytes(range(i + 1)) * 3 for i in range(5)]
    for m, t in zip(metas, tails):
        w.append(m, t)
    w.close()
    records, stats = wal.scan_wal(d)
    assert [m for m, _ in records] == metas
    assert [t for _, t in records] == tails
    assert stats.records == 5
    assert stats.corrupt_reason is None
    assert stats.truncated_records == 0


def test_wal_empty_dir_scans_clean(tmp_path):
    records, stats = wal.scan_wal(wal_dir(tmp_path))
    assert records == []
    assert stats.records == 0 and stats.corrupt_reason is None


def test_wal_unknown_fsync_policy_rejected(tmp_path):
    with pytest.raises(ValueError):
        wal.WalWriter(wal_dir(tmp_path), fsync_policy="sometimes")


def test_wal_segment_rotation(tmp_path):
    d = wal_dir(tmp_path)
    # tiny segment budget: every append rotates
    w = wal.WalWriter(d, fsync_policy="never", segment_bytes=64)
    for i in range(4):
        w.append({"k": "mut", "i": i}, b"x" * 64)
    w.close()
    assert len(wal.list_segments(d)) >= 4
    records, stats = wal.scan_wal(d)
    assert [m["i"] for m, _ in records] == [0, 1, 2, 3]
    assert stats.segments >= 4


def test_a_write_token_names_the_segment_that_holds_the_record(tmp_path):
    """A seal between a write and its read-your-writes token leaves the
    active segment empty: the token names the sealed segment, which a
    follower applies, not the empty one, which seals only with the next
    write (a follower waiting for it would wait for that write)."""
    w = wal.WalWriter(wal_dir(tmp_path), fsync_policy="always")
    assert w.last_record_segment() == w.segment - 1  # nothing written yet
    w.append({"i": 0})
    assert w.last_record_segment() == w.segment
    sealed = w.seal_if_dirty()
    assert w.position() == (sealed + 1, len(wal.SEG_MAGIC))
    assert w.last_record_segment() == sealed
    assert w.seal_if_dirty() is None  # an empty segment never seals
    w.append({"i": 1})
    assert w.last_record_segment() == w.segment == sealed + 1
    w.close()


# ----------------------------------------------- torn / corrupt truncation


def _append_raw(d, segment, raw):
    with open(wal.segment_path(d, segment), "ab") as fh:
        fh.write(raw)


def test_wal_torn_frame_header_truncated(tmp_path):
    d = wal_dir(tmp_path)
    w = wal.WalWriter(d, fsync_policy="always")
    for i in range(3):
        w.append({"i": i})
    seg = w.segment
    w.close()
    _append_raw(d, seg, b"\x07")  # 1 byte of a frame header: torn at crash
    records, stats = wal.scan_wal(d)
    assert len(records) == 3
    assert "torn frame header" in stats.corrupt_reason
    assert stats.truncated_records == 1
    # the file was physically truncated: a re-scan is clean
    records2, stats2 = wal.scan_wal(d)
    assert len(records2) == 3 and stats2.corrupt_reason is None


def test_wal_torn_payload_truncated(tmp_path):
    d = wal_dir(tmp_path)
    w = wal.WalWriter(d, fsync_policy="always")
    w.append({"i": 0})
    seg = w.segment
    w.close()
    frame = wal.encode_record({"i": 1}, b"tail-bytes")
    _append_raw(d, seg, frame[: len(frame) - 4])  # payload cut short
    records, stats = wal.scan_wal(d)
    assert [m["i"] for m, _ in records] == [0]
    assert "torn record payload" in stats.corrupt_reason


def test_wal_crc_mismatch_truncates_and_drops_later_segments(tmp_path):
    d = wal_dir(tmp_path)
    w = wal.WalWriter(d, fsync_policy="always", segment_bytes=1 << 20)
    for i in range(3):
        w.append({"i": i})
    first = w.segment
    w.rotate()
    w.append({"i": 3})
    later = w.segment
    w.close()
    # flip one payload bit in the LAST record of the first segment
    path = wal.segment_path(d, first)
    blob = bytearray(open(path, "rb").read())
    blob[-1] ^= 0x01
    with open(path, "wb") as fh:
        fh.write(blob)
    records, stats = wal.scan_wal(d)
    # replay stops at the corrupt record; nothing after it (including the
    # intact later segment) may be replayed
    assert [m["i"] for m, _ in records] == [0, 1]
    assert "crc mismatch" in stats.corrupt_reason
    assert stats.dropped_segments == 1
    assert not os.path.exists(wal.segment_path(d, later))


def test_wal_implausible_length_rejected(tmp_path):
    d = wal_dir(tmp_path)
    w = wal.WalWriter(d, fsync_policy="always")
    w.append({"i": 0})
    seg = w.segment
    w.close()
    bogus = struct.pack("<II", wal.MAX_RECORD_BYTES + 1, 0)
    _append_raw(d, seg, bogus + b"junk")
    records, stats = wal.scan_wal(d)
    assert len(records) == 1
    assert "implausible record length" in stats.corrupt_reason


def test_wal_bad_magic_is_unreplayable(tmp_path):
    d = wal_dir(tmp_path)
    with open(wal.segment_path(d, 1), "wb") as fh:
        fh.write(b"NOTMAGIC" + wal.encode_record({"i": 0}))
    records, stats = wal.scan_wal(d)
    assert records == []
    assert "bad segment magic" in stats.corrupt_reason


def test_wal_scan_without_truncate_is_read_only(tmp_path):
    d = wal_dir(tmp_path)
    w = wal.WalWriter(d, fsync_policy="always")
    w.append({"i": 0})
    seg = w.segment
    w.close()
    _append_raw(d, seg, b"\x01\x02")
    size = os.path.getsize(wal.segment_path(d, seg))
    _records, stats = wal.scan_wal(d, truncate=False)
    assert stats.corrupt_reason is not None
    assert os.path.getsize(wal.segment_path(d, seg)) == size


# ----------------------------------------------------- injected disk faults


def test_fault_torn_write_fails_append_and_recovers_prefix(tmp_path):
    d = wal_dir(tmp_path)
    w = wal.WalWriter(d, fsync_policy="always")
    plan = FaultPlan(seed=1).add(
        "wal.append", error=InjectedTornWrite, at_calls=[3]
    )
    with plan.installed():
        w.append({"i": 0})
        w.append({"i": 1})
        with pytest.raises(DurabilityError, match="torn write"):
            w.append({"i": 2}, b"never-lands")
    w.close()
    records, stats = wal.scan_wal(d)
    assert [m["i"] for m, _ in records] == [0, 1]
    assert stats.corrupt_reason is not None  # the half frame WAS on disk
    assert stats.truncated_bytes > 0


def test_fault_bit_flip_lands_silently_scan_catches_it(tmp_path):
    d = wal_dir(tmp_path)
    w = wal.WalWriter(d, fsync_policy="always")
    plan = FaultPlan(seed=1).add(
        "wal.append", error=InjectedBitFlip, at_calls=[2]
    )
    with plan.installed():
        w.append({"i": 0})
        w.append({"i": 1}, b"payload")  # corrupted on disk, no error raised
        w.append({"i": 2})
    w.close()
    records, stats = wal.scan_wal(d)
    assert [m["i"] for m, _ in records] == [0]
    assert "crc mismatch" in stats.corrupt_reason
    # record 2 sat AFTER the corrupt frame: replay must not resurrect it
    assert stats.truncated_records == 1


def test_fault_fsync_failure_surfaces(tmp_path):
    d = wal_dir(tmp_path)
    w = wal.WalWriter(d, fsync_policy="always")
    plan = FaultPlan(seed=1).add(
        "wal.fsync", error=InjectedFsyncFault, at_calls=[1]
    )
    with plan.installed():
        with pytest.raises(InjectedFsyncFault):
            w.append({"i": 0})
        w.append({"i": 1})  # disk recovered: next append fsyncs fine
    w.close()
    records, _stats = wal.scan_wal(d)
    assert [m["i"] for m, _ in records] == [0, 1]


# --------------------------------------------------------- fsio primitives


def test_atomic_write_replaces_whole_file(tmp_path):
    p = str(tmp_path / "manifest.json")
    fsio.atomic_write_bytes(p, b"old-complete")
    with pytest.raises(RuntimeError):
        with fsio.atomic_write(p) as fh:
            fh.write(b"half-new")
            raise RuntimeError("crash mid-write")
    # the failed write left the old content AND no temp debris
    assert open(p, "rb").read() == b"old-complete"
    assert [n for n in os.listdir(tmp_path) if ".tmp." in n] == []
    fsio.atomic_write_bytes(p, b"new-complete")
    assert open(p, "rb").read() == b"new-complete"


def test_atomic_rename_dir_publishes_complete_tree(tmp_path):
    tmp = str(tmp_path / ".tmp-gen-1")
    final = str(tmp_path / "gen-1")
    os.makedirs(tmp)
    fsio.atomic_write_bytes(os.path.join(tmp, "a.bin"), b"abc")
    fsio.atomic_rename_dir(tmp, final)
    assert not os.path.exists(tmp)
    assert open(os.path.join(final, "a.bin"), "rb").read() == b"abc"


# ------------------------------------------------- manager: WAL-only replay


def test_manager_wal_replay_round_trip(tmp_path):
    data = str(tmp_path / "data")
    m = DurabilityManager(data, fsync_policy="always")
    m.start()
    db = SparqlDatabase()
    m.attach("store-1", db)
    for i in range(10):
        db.add_triple_parts(f"<http://x/s{i}>", "<http://x/p>", f'"{i}"')
    db.delete_triple(db.add_triple_parts("<http://x/s0>", "<http://x/p>", '"0"'))
    oracle = triples(db)
    m.close()

    m2 = DurabilityManager(data, fsync_policy="always")
    res = m2.recover()
    assert set(res.stores) == {"store-1"}
    assert triples(res.stores["store-1"]) == oracle
    assert res.stats["replayed_records"] > 0
    assert res.stats["truncated_records"] == 0
    assert res.stats["snapshot_generation"] == 0
    m2.close()


def test_manager_recover_truncates_torn_tail_to_oracle(tmp_path):
    data = str(tmp_path / "data")
    m = DurabilityManager(data, fsync_policy="always")
    m.start()
    db = SparqlDatabase()
    m.attach("store-1", db)
    oracle_db = SparqlDatabase()
    plan = FaultPlan(seed=3).add(
        "wal.append", error=InjectedTornWrite, at_calls=[8]
    )
    applied = 0
    with plan.installed():
        for i in range(12):
            try:
                db.add_triple_parts(
                    f"<http://x/s{i}>", "<http://x/p>", f'"{i}"'
                )
            except DurabilityError:
                break
            oracle_db.add_triple_parts(
                f"<http://x/s{i}>", "<http://x/p>", f'"{i}"'
            )
            applied += 1
    assert 0 < applied < 12
    m.close()

    m2 = DurabilityManager(data, fsync_policy="always")
    res = m2.recover()
    # every ACKNOWLEDGED insert survives; the torn one is gone
    assert triples(res.stores["store-1"]) == triples(oracle_db)
    assert res.stats["corrupt_reason"] is not None
    assert res.stats["truncated_records"] >= 1
    m2.close()


def test_manager_replay_is_idempotent_for_deletes_and_clear(tmp_path):
    data = str(tmp_path / "data")
    m = DurabilityManager(data, fsync_policy="always")
    m.start()
    db = SparqlDatabase()
    m.attach("store-1", db)
    t = db.add_triple_parts("<http://x/a>", "<http://x/p>", "<http://x/b>")
    db.add_triple_parts("<http://x/c>", "<http://x/p>", "<http://x/d>")
    db.delete_triple(t)
    db.store.clear()
    db.add_triple_parts("<http://x/e>", "<http://x/p>", "<http://x/f>")
    oracle = triples(db)
    m.close()
    m2 = DurabilityManager(data, fsync_policy="always")
    res = m2.recover()
    assert triples(res.stores["store-1"]) == oracle == [
        ("http://x/e", "http://x/p", "http://x/f")
    ]
    m2.close()


# -------------------------------------------- manager: snapshots + pruning


def test_manager_snapshot_and_recover(tmp_path):
    data = str(tmp_path / "data")
    m = DurabilityManager(data, fsync_policy="always")
    m.start()
    db = seed_db(30)
    m.attach("store-1", db, log_create=True)
    gen = m.snapshot({"store-1": db})
    assert gen == 1
    # post-snapshot mutations land in the WAL only
    db.add_triple_parts("<http://x/post>", "<http://x/p>", '"after"')
    oracle = triples(db)
    m.close()

    m2 = DurabilityManager(data, fsync_policy="always")
    res = m2.recover()
    assert res.stats["snapshot_generation"] == 1
    assert triples(res.stores["store-1"]) == oracle
    m2.close()


def test_manager_snapshot_prunes_old_generations_and_segments(tmp_path):
    data = str(tmp_path / "data")
    m = DurabilityManager(data, fsync_policy="always")
    m.start()
    db = seed_db(5)
    m.attach("store-1", db)
    g1 = m.snapshot({"store-1": db})
    db.add_triple_parts("<http://x/n1>", "<http://x/p>", '"1"')
    g2 = m.snapshot({"store-1": db})
    assert g2 == g1 + 1
    gens = [
        n
        for n in os.listdir(os.path.join(data, "snapshots"))
        if n.startswith("gen-")
    ]
    assert gens == [f"gen-{g2:08d}"]
    # all WAL segments below the g2 manifest's wal_start were deleted
    manifest = json.load(
        open(os.path.join(data, "snapshots", gens[0], "manifest.json"))
    )
    assert min(
        wal.list_segments(os.path.join(data, "wal")), default=manifest["wal_start"]
    ) >= manifest["wal_start"]
    m.close()


def test_manager_falls_back_past_corrupt_generation(tmp_path):
    data = str(tmp_path / "data")
    m = DurabilityManager(data, fsync_policy="always")
    m.start()
    db = seed_db(8)
    m.attach("store-1", db)
    m.snapshot({"store-1": db})
    oracle = triples(db)
    m.close()
    # corrupt the (only) generation's store file: CRC check must reject it
    gen_dir = os.path.join(data, "snapshots", "gen-00000001")
    store_file = os.path.join(gen_dir, "store-0.npz")
    blob = bytearray(open(store_file, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    with open(store_file, "wb") as fh:
        fh.write(blob)
    m2 = DurabilityManager(data, fsync_policy="always")
    res = m2.recover()
    assert 1 in res.stats["invalid_generations"]
    assert res.stats["snapshot_generation"] == 0
    assert res.stats["gen_1_error"]
    m2.close()


def test_manager_tmp_generation_debris_is_cleaned(tmp_path):
    data = str(tmp_path / "data")
    m = DurabilityManager(data, fsync_policy="always")
    m.start()
    debris = os.path.join(data, "snapshots", ".tmp-gen-00000009")
    os.makedirs(debris)
    with open(os.path.join(debris, "half.npz"), "wb") as fh:
        fh.write(b"partial")
    m.close()
    m2 = DurabilityManager(data, fsync_policy="always")
    m2.recover()
    assert not os.path.exists(debris)
    m2.close()


# ------------------------------------------------- manager: session records


def test_manager_session_lifecycle_round_trip(tmp_path):
    data = str(tmp_path / "data")
    m = DurabilityManager(data, fsync_policy="always")
    m.start()
    cfg = {"query": "REGISTER ...", "window_size": 10}
    m.log_session_register("7", cfg)
    m.log_session_checkpoint("7", b'{"engine":"state-1"}')
    m.log_session_checkpoint("7", b'{"engine":"state-2"}')
    m.log_session_register("8", {"query": "other"})
    m.log_session_close("8")
    m.close()
    m2 = DurabilityManager(data, fsync_policy="always")
    res = m2.recover()
    assert set(res.sessions) == {"7"}  # 8 was closed
    assert res.sessions["7"]["register"] == cfg
    assert res.sessions["7"]["state"] == b'{"engine":"state-2"}'  # last wins
    m2.close()


def test_manager_sessions_survive_via_snapshot(tmp_path):
    data = str(tmp_path / "data")
    m = DurabilityManager(data, fsync_policy="always")
    m.start()
    m.log_session_register("3", {"query": "q"})
    m.snapshot(
        {}, sessions={"3": {"register": {"query": "q"}, "state": b"blob3"}}
    )
    m.close()
    m2 = DurabilityManager(data, fsync_policy="always")
    res = m2.recover()
    assert res.sessions["3"]["register"] == {"query": "q"}
    assert res.sessions["3"]["state"] == b"blob3"
    m2.close()


# ----------------------------------------------------- writer resume + stats


def test_recovery_resumes_on_fresh_segment(tmp_path):
    data = str(tmp_path / "data")
    m = DurabilityManager(data, fsync_policy="always")
    m.start()
    seg0 = m.wal.segment
    m.log_session_register("1", {})
    m.close()
    m2 = DurabilityManager(data, fsync_policy="always")
    m2.recover()
    assert m2.wal.segment > seg0
    m2.log_session_register("2", {})  # appending after recovery works
    m2.close()


def test_manager_stats_shape(tmp_path):
    data = str(tmp_path / "data")
    m = DurabilityManager(data, fsync_policy="always")
    res = m.recover()
    st = m.stats()
    assert st["data_dir"] == data
    assert st["fsync_policy"] == "always"
    assert st["wal"]["appended_records"] == 0
    assert st["last_recovery"]["replayed_records"] == 0
    assert res.stats["duration_s"] >= 0
    m.close()


def test_group_policy_bounds_fsyncs(tmp_path, monkeypatch):
    d = wal_dir(tmp_path)
    w = wal.WalWriter(d, fsync_policy="group", group_interval_s=3600.0)
    real_fsync = os.fsync
    calls = []

    def counting_fsync(fd):
        calls.append(fd)
        return real_fsync(fd)

    monkeypatch.setattr(os, "fsync", counting_fsync)
    for i in range(50):
        w.append({"i": i})
    # a fresh hour-long interval means no append-path fsync fired
    assert calls == []
    w.flush()
    assert len(calls) == 1
    w.close()
    records, stats = wal.scan_wal(d)
    assert stats.records == 50 and stats.corrupt_reason is None
