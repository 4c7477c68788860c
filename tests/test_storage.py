"""Storage engine tests: segmented log, recovery, kvstore, snapshots,
plus an opfuzz-style randomized interleaving test (the reference's
storage/opfuzz pattern)."""

import asyncio
import os

import numpy as np
import pytest

from redpanda_tpu.models import NTP, Record, RecordBatch, RecordBatchType
from redpanda_tpu.storage import (
    DiskLog,
    KeySpace,
    KvStore,
    LogConfig,
    LogManager,
    MemLog,
    SnapshotManager,
    read_snapshot,
    write_snapshot,
)
from redpanda_tpu.storage.recovery import scan_valid_prefix_host


def _batch(n=3, value_size=32, type=RecordBatchType.raft_data, ts=0):
    rng = np.random.default_rng(abs(hash((n, value_size, ts))) % 2**31)
    recs = [
        Record(offset_delta=i, timestamp_delta=i, value=rng.bytes(value_size))
        for i in range(n)
    ]
    return RecordBatch.build(recs, type=type, first_timestamp=ts, max_timestamp=ts + n - 1)


def _run(coro):
    return asyncio.run(coro)


@pytest.fixture()
def ntp():
    return NTP.kafka("t-log", 0)


@pytest.fixture()
def cfg(tmp_path):
    return LogConfig(base_dir=str(tmp_path), fsync_on_append=False)


# ------------------------------------------------------------------ basic log
def test_append_read_roundtrip(ntp, cfg):
    async def main():
        log = await DiskLog.open(ntp, cfg)
        r1 = await log.append([_batch(3), _batch(2)])
        assert (r1.base_offset, r1.last_offset) == (0, 4)
        r2 = await log.append([_batch(4)])
        assert (r2.base_offset, r2.last_offset) == (5, 8)
        batches = await log.read(0)
        assert [b.base_offset for b in batches] == [0, 3, 5]
        assert [b.header.record_count for b in batches] == [3, 2, 4]
        for b in batches:
            assert b.verify_kafka_crc() and b.verify_header_crc()
        # offset-bounded read
        mid = await log.read(3, max_offset=4)
        assert [b.base_offset for b in mid] == [3]
        await log.close()

    _run(main())


def test_reopen_preserves_state(ntp, cfg):
    async def main():
        log = await DiskLog.open(ntp, cfg)
        await log.append([_batch(3), _batch(3)])
        await log.flush()
        await log.close()
        log2 = await DiskLog.open(ntp, cfg)
        off = log2.offsets()
        assert off.dirty_offset == 5
        batches = await log2.read(0)
        assert len(batches) == 2
        r = await log2.append([_batch(1)])
        assert r.base_offset == 6
        await log2.close()

    _run(main())


def test_segment_roll_and_read_across(ntp, cfg):
    cfg.max_segment_size = 400  # force rolls
    async def main():
        log = await DiskLog.open(ntp, cfg)
        for _ in range(10):
            await log.append([_batch(2, value_size=64)])
        assert len(log.segments) > 1
        batches = await log.read(0, max_bytes=1 << 30)
        assert sum(b.header.record_count for b in batches) == 20
        assert [b.base_offset for b in batches] == [2 * i for i in range(10)]
        await log.close()

    _run(main())


def test_truncate_suffix(ntp, cfg):
    async def main():
        log = await DiskLog.open(ntp, cfg)
        for _ in range(5):
            await log.append([_batch(2)])
        await log.truncate(6)  # drop offsets >= 6
        assert log.offsets().dirty_offset == 5
        batches = await log.read(0)
        assert [b.base_offset for b in batches] == [0, 2, 4]
        r = await log.append([_batch(1)])
        assert r.base_offset == 6
        await log.close()

    _run(main())


def test_prefix_truncate_and_retention(ntp, cfg):
    cfg.max_segment_size = 300
    async def main():
        log = await DiskLog.open(ntp, cfg)
        for _ in range(10):
            await log.append([_batch(2, value_size=64)])
        await log.prefix_truncate(8)
        assert log.offsets().start_offset == 8
        batches = await log.read(0)
        assert all(b.last_offset >= 8 for b in batches)
        await log.close()

    _run(main())


def test_timequery(ntp, cfg):
    async def main():
        log = await DiskLog.open(ntp, cfg)
        for i in range(5):
            await log.append([_batch(2, ts=1000 * i)])
        off = await log.timequery(2500)
        assert off == 6  # first batch with max_ts >= 2500 is batch 3 (ts 3000..)
        await log.close()

    _run(main())


# ------------------------------------------------------------------ recovery
def test_recovery_truncates_torn_write(ntp, cfg):
    async def main():
        log = await DiskLog.open(ntp, cfg)
        for _ in range(4):
            await log.append([_batch(2)])
        await log.flush()
        path = log.segments[-1].data_path
        await log.close()
        # tear the last batch: chop 7 bytes off
        size = os.path.getsize(path)
        with open(path, "r+b") as f:
            f.truncate(size - 7)
        log2 = await DiskLog.open(ntp, cfg)
        assert log2.offsets().dirty_offset == 5  # last batch dropped
        batches = await log2.read(0)
        assert len(batches) == 3
        r = await log2.append([_batch(1)])
        assert r.base_offset == 6
        await log2.close()

    _run(main())


def test_recovery_detects_corruption_midfile(ntp, cfg):
    async def main():
        log = await DiskLog.open(ntp, cfg)
        for _ in range(4):
            await log.append([_batch(2)])
        await log.flush()
        path = log.segments[-1].data_path
        await log.close()
        size = os.path.getsize(path)
        with open(path, "r+b") as f:
            f.seek(size // 2)
            f.write(b"\xde\xad")
        log2 = await DiskLog.open(ntp, cfg)
        assert log2.offsets().dirty_offset < 7
        for b in await log2.read(0):
            assert b.verify_kafka_crc()
        await log2.close()

    _run(main())


def test_device_recovery_scan_matches_host(tmp_path):
    blob = b"".join(
        _batch(2, value_size=24).with_base_offset(2 * i).encode_internal() for i in range(6)
    )
    from redpanda_tpu.storage.recovery import scan_valid_prefix_device

    full_host = scan_valid_prefix_host(blob)
    full_dev = scan_valid_prefix_device(blob)
    assert full_host == full_dev == (len(blob), 11)
    # corrupt payload of 4th frame (beyond its header)
    corrupt = bytearray(blob)
    frame = len(blob) // 6
    corrupt[3 * frame + 70] ^= 0xFF
    assert scan_valid_prefix_host(bytes(corrupt)) == scan_valid_prefix_device(bytes(corrupt))
    assert scan_valid_prefix_device(bytes(corrupt))[1] == 5

    _ = tmp_path  # unused


def test_recovery_fully_corrupt_tail_no_offset_hole(ntp, cfg):
    """A wholly-corrupt tail segment must not leave stale offsets behind."""
    cfg.max_segment_size = 250
    async def main():
        log = await DiskLog.open(ntp, cfg)
        for _ in range(4):
            await log.append([_batch(2, value_size=64)])
        await log.flush()
        tail = log.segments[-1]
        tail_base = tail.base_offset
        path = tail.data_path
        await log.close()
        # corrupt the very first header byte of the tail segment
        with open(path, "r+b") as f:
            f.write(b"\xff\xff\xff\xff")
        log2 = await DiskLog.open(ntp, cfg)
        assert log2.offsets().dirty_offset == tail_base - 1
        r = await log2.append([_batch(1)])
        assert r.base_offset == tail_base  # no hole
        got = await log2.read(0)
        offs = [b.base_offset for b in got]
        assert offs == sorted(offs) and offs[-1] == tail_base
        await log2.close()

    _run(main())


def test_term_survives_restart(ntp, cfg):
    async def main():
        log = await DiskLog.open(ntp, cfg)
        await log.append([_batch(2)], term=3)
        await log.append([_batch(2)], term=5)
        assert log.term == 5
        got = await log.read(0)
        assert [b.header.term for b in got] == [3, 5]
        await log.flush()
        await log.close()
        log2 = await DiskLog.open(ntp, cfg)
        assert log2.term == 5
        got = await log2.read(0)
        assert [b.header.term for b in got] == [3, 5]
        await log2.close()

    _run(main())


def test_follower_append_preserves_wire_terms_across_restart(ntp, cfg):
    """Follower-path appends (assign_offsets=False) carry the leader's terms;
    the segment filename is the durable term record, so a fresh (empty) or
    mid-term segment must never absorb batches from another term — including
    terms going DOWN after a divergent-suffix truncation."""

    async def main():
        log = await DiskLog.open(ntp, cfg)
        b1 = _batch(2).with_base_offset(0)
        b1.header.term = 5  # fresh log: empty 0-0 segment must be replaced
        b2 = _batch(2).with_base_offset(2)
        b2.header.term = 7
        b3 = _batch(2).with_base_offset(4)
        b3.header.term = 7
        await log.append([b1, b2, b3], assign_offsets=False)
        assert [b.header.term for b in await log.read(0)] == [5, 7, 7]
        await log.flush()
        await log.close()
        # restart: terms recovered from segment names, not headers
        log2 = await DiskLog.open(ntp, cfg)
        assert [b.header.term for b in await log2.read(0)] == [5, 7, 7]
        # divergence repair: truncate the term-7 suffix, append term-6 history
        await log2.truncate(2)
        b4 = _batch(2).with_base_offset(2)
        b4.header.term = 6
        await log2.append([b4], assign_offsets=False)
        assert [b.header.term for b in await log2.read(0)] == [5, 6]
        await log2.flush()
        await log2.close()
        log3 = await DiskLog.open(ntp, cfg)
        assert [b.header.term for b in await log3.read(0)] == [5, 6]
        await log3.close()

    _run(main())


def test_kvstore_stop_without_start_preserves_state(tmp_path):
    kv = KvStore(str(tmp_path / "kv")).start()
    kv.put(KeySpace.consensus, b"voted_for", b"node-3")
    kv.stop()
    # construct-then-stop without start must not clobber the snapshot
    KvStore(str(tmp_path / "kv")).stop()
    kv2 = KvStore(str(tmp_path / "kv")).start()
    assert kv2.get(KeySpace.consensus, b"voted_for") == b"node-3"
    kv2.stop()


# ------------------------------------------------------------------ kvstore
def test_kvstore_roundtrip_and_recovery(tmp_path):
    kv = KvStore(str(tmp_path / "kv")).start()
    kv.put(KeySpace.consensus, b"voted_for", b"node-3")
    kv.put(KeySpace.storage, b"start_offset", b"42")
    kv.remove(KeySpace.storage, b"missing")
    kv.stop()
    kv2 = KvStore(str(tmp_path / "kv")).start()
    assert kv2.get(KeySpace.consensus, b"voted_for") == b"node-3"
    assert kv2.get(KeySpace.storage, b"start_offset") == b"42"
    assert kv2.get(KeySpace.storage, b"missing") is None
    kv2.put(KeySpace.consensus, b"voted_for", b"node-5")
    kv2.stop()
    kv3 = KvStore(str(tmp_path / "kv")).start()
    assert kv3.get(KeySpace.consensus, b"voted_for") == b"node-5"
    kv3.stop()


def test_kvstore_wal_only_recovery(tmp_path):
    """Kill without stop(): WAL alone must recover state."""
    kv = KvStore(str(tmp_path / "kv")).start()
    kv.put(KeySpace.coproc, b"k1", b"v1")
    kv.put(KeySpace.coproc, b"k2", b"v2")
    kv._wal.close()  # simulate crash (no snapshot)
    kv2 = KvStore(str(tmp_path / "kv")).start()
    assert kv2.get(KeySpace.coproc, b"k1") == b"v1"
    assert kv2.get(KeySpace.coproc, b"k2") == b"v2"
    kv2.stop()


def test_kvstore_torn_wal_tail(tmp_path):
    kv = KvStore(str(tmp_path / "kv")).start()
    kv.put(KeySpace.testing, b"a", b"1")
    kv.put(KeySpace.testing, b"b", b"2")
    kv._wal.close()
    wal = str(tmp_path / "kv" / "kvstore.wal")
    size = os.path.getsize(wal)
    with open(wal, "r+b") as f:
        f.truncate(size - 3)
    kv2 = KvStore(str(tmp_path / "kv")).start()
    assert kv2.get(KeySpace.testing, b"a") == b"1"
    assert kv2.get(KeySpace.testing, b"b") is None  # torn op dropped
    kv2.stop()


# ------------------------------------------------------------------ snapshots
def test_snapshot_roundtrip(tmp_path):
    p = str(tmp_path / "snap")
    write_snapshot(p, b"meta", b"payload-bytes")
    assert read_snapshot(p) == (b"meta", b"payload-bytes")


def test_snapshot_corruption_detected(tmp_path):
    from redpanda_tpu.storage.snapshot import SnapshotError

    p = str(tmp_path / "snap")
    write_snapshot(p, b"meta", b"payload-bytes")
    blob = bytearray(open(p, "rb").read())
    blob[-2] ^= 1
    open(p, "wb").write(blob)
    with pytest.raises(SnapshotError):
        read_snapshot(p)


# ------------------------------------------------------------------ manager
def test_log_manager_manage_and_remove(tmp_path):
    async def main():
        mgr = LogManager(LogConfig(base_dir=str(tmp_path)))
        a = await mgr.manage(NTP.kafka("a", 0))
        b = await mgr.manage(NTP.kafka("b", 1))
        assert a is await mgr.manage(NTP.kafka("a", 0))
        await a.append([_batch(1)])
        await mgr.remove(NTP.kafka("a", 0))
        assert mgr.get(NTP.kafka("a", 0)) is None
        assert not os.path.exists(os.path.join(str(tmp_path), "kafka/a/0"))
        await mgr.stop()
        _ = b

    _run(main())


# ------------------------------------------------------------------ opfuzz
def test_opfuzz_random_interleaving(tmp_path):
    """Randomized append/read/truncate/prefix/roll/reopen against a model."""

    async def main():
        rng = np.random.default_rng(1234)
        ntp = NTP.kafka("fuzz", 0)
        cfg = LogConfig(base_dir=str(tmp_path), max_segment_size=600)
        log = await DiskLog.open(ntp, cfg)
        model: list[RecordBatch] = []  # mirrors expected visible batches
        start_offset = 0

        def dirty():
            return model[-1].last_offset if model else start_offset - 1

        for step in range(120):
            op = rng.choice(["append", "read", "truncate", "prefix", "reopen"], p=[0.5, 0.2, 0.1, 0.1, 0.1])
            if op == "append":
                n = int(rng.integers(1, 4))
                b = _batch(n, value_size=int(rng.integers(8, 80)))
                r = await log.append([b])
                expected_base = dirty() + 1
                assert r.base_offset == expected_base, f"step {step}"
                model.append(b.with_base_offset(expected_base))
            elif op == "read":
                got = await log.read(start_offset, max_bytes=1 << 30)
                want = [b for b in model if b.last_offset >= start_offset]
                assert [g.base_offset for g in got] == [w.base_offset for w in want], f"step {step}"
                assert all(g.verify_kafka_crc() for g in got)
            elif op == "truncate" and model:
                cut = int(rng.integers(start_offset, dirty() + 2))
                await log.truncate(cut)
                model = [b for b in model if b.last_offset < cut]
            elif op == "prefix" and model:
                cut = int(rng.integers(start_offset, dirty() + 2))
                await log.prefix_truncate(cut)
                start_offset = max(start_offset, cut)
            elif op == "reopen":
                await log.flush()
                await log.close()
                log = await DiskLog.open(ntp, cfg)
                assert log.offsets().dirty_offset == dirty(), f"step {step}"
        await log.close()

    _run(main())


def test_opfuzz_with_caches_and_cursors(tmp_path):
    """The same randomized interleaving, but through a LogManager so the
    batch cache AND the readers cache (positioned cursors) front every
    read — plus a chunked sequential-read op that walks the log in small
    continuation reads (the cursor hot path). Any stale-cursor or
    stale-cache bug after truncate/prefix/reopen diverges from the model."""

    async def main():
        rng = np.random.default_rng(987654)
        ntp = NTP.kafka("fuzzc", 0)
        mgr = LogManager(
            LogConfig(base_dir=str(tmp_path), max_segment_size=600),
            batch_cache_bytes=8 << 10,  # tiny: constant eviction pressure
        )
        log = await mgr.manage(ntp)
        model: list[RecordBatch] = []
        start_offset = 0

        def dirty():
            return model[-1].last_offset if model else start_offset - 1

        for step in range(150):
            op = rng.choice(
                ["append", "read", "read_seq", "truncate", "prefix", "reopen"],
                p=[0.4, 0.15, 0.2, 0.1, 0.05, 0.1],
            )
            if op == "append":
                n = int(rng.integers(1, 4))
                b = _batch(n, value_size=int(rng.integers(8, 80)))
                r = await log.append([b])
                model.append(b.with_base_offset(r.base_offset))
            elif op == "read":
                got = await log.read(start_offset, max_bytes=1 << 30)
                want = [b for b in model if b.last_offset >= start_offset]
                assert [g.base_offset for g in got] == [
                    w.base_offset for w in want
                ], f"step {step}"
                assert all(g.verify_kafka_crc() for g in got)
            elif op == "read_seq" and model and dirty() >= start_offset:
                # chunked continuation walk from a random start: every
                # follow-up read adopts the cursor stored by the previous
                lo = int(rng.integers(start_offset, dirty() + 1))
                cur = lo
                seen = []
                while True:
                    got = await log.read(cur, max_bytes=200)
                    if not got:
                        break
                    seen += got
                    cur = got[-1].last_offset + 1
                want = [b for b in model if b.last_offset >= lo]
                assert [g.base_offset for g in seen] == [
                    w.base_offset for w in want
                ], f"step {step} from {lo}"
                assert [g.payload for g in seen] == [w.payload for w in want]
            elif op == "truncate" and model:
                cut = int(rng.integers(start_offset, dirty() + 2))
                await log.truncate(cut)
                model = [b for b in model if b.last_offset < cut]
            elif op == "prefix" and model:
                cut = int(rng.integers(start_offset, dirty() + 2))
                await log.prefix_truncate(cut)
                start_offset = max(start_offset, cut)
            elif op == "reopen":
                await log.flush()
                await mgr.stop()
                mgr = LogManager(
                    LogConfig(base_dir=str(tmp_path), max_segment_size=600),
                    batch_cache_bytes=8 << 10,
                )
                log = await mgr.manage(ntp)
                assert log.offsets().dirty_offset == dirty(), f"step {step}"
        # the cursor path was actually exercised
        assert mgr.readers_cache.hits > 0, mgr.readers_cache.stats()
        await mgr.stop()

    _run(main())


# ------------------------------------------------------------------ read windows
def test_segment_scan_hands_on_the_unread_rest_of_its_window(ntp, cfg):
    """Segment.scan reads the file through ONE kept descriptor and returns,
    with the batches, the cursor for the follow-up read: the position past
    the last kept frame and what the window holds beyond it (PR 25)."""
    from redpanda_tpu.storage import segment as segment_mod

    async def main():
        log = await DiskLog.open(ntp, cfg)
        sizes = []
        for i in range(40):
            b = _batch(2, value_size=4000, ts=i)
            sizes.append(b.size_bytes)
            await log.append([b])
        seg = log.segments[0]
        assert seg._rfile is None  # nothing read yet: no descriptor
        # a cold read takes about what it was asked for, and keeps the rest
        got, cur, file_reads = seg.scan(0, sizes[0])
        assert [b.base_offset for b in got] == [0] and file_reads == 1
        fd = seg._rfile.fileno()
        assert cur.segment_base == 0 and cur.file_pos == sizes[0]
        assert cur.window_pos == 0 and len(cur.window) == 1 << 16
        # a continuation decodes out of the window: no file read
        got, cur, file_reads = seg.scan(2, sizes[1], cursor=cur, read_ahead=True)
        assert [b.base_offset for b in got] == [2] and file_reads == 0
        # ... until the window holds no whole next frame: then one read-ahead
        # window from that frame's boundary on
        seen = []
        preads = 0
        while got:
            at = got[-1].last_offset + 1
            got, cur, file_reads = seg.scan(at, sizes[0], cursor=cur, read_ahead=True)
            seen += [b.base_offset for b in got]
            preads += file_reads
            assert len(cur.window) <= segment_mod.READ_AHEAD_BYTES
        assert seen == list(range(4, 80, 2))
        # 38 frames of ~8 KB from 64 KiB on: one 256 KiB window and the EOF probe
        assert preads == 2 and seg._rfile.fileno() == fd
        # appended after the window ended at EOF: the exhausted cursor reads on
        await log.append([_batch(2, value_size=4000, ts=99)])
        got, cur, file_reads = seg.scan(80, sizes[0], cursor=cur, read_ahead=True)
        assert [b.base_offset for b in got] == [80] and file_reads == 1
        # a request at or over the window size reads as asked and keeps no more
        # than a reader's own window
        got, cur, _ = seg.scan(0, 4 * segment_mod.READ_AHEAD_BYTES, read_ahead=True)
        assert len(got) == 41 and cur.window == b""
        await log.close()
        assert seg._rfile is None

    _run(main())


def test_read_is_a_stage_and_the_cursor_counters_add_up(tmp_path):
    from redpanda_tpu.observability import probes

    async def main():
        mgr = LogManager(LogConfig(base_dir=str(tmp_path)), batch_cache_bytes=0)
        log = await mgr.manage(NTP.kafka("stage", 0))
        for i in range(32):
            await log.append([_batch(2, value_size=2000, ts=i)])
        one = _batch(2, value_size=2000).size_bytes
        rc = mgr.readers_cache
        h0 = probes.storage_read_hist.hist.count
        at, reads = 0, 0
        while True:
            got = await log.read(at, one)
            reads += 1
            if not got:
                break
            at = got[-1].last_offset + 1
        assert at == 64 and probes.storage_read_hist.hist.count == h0 + reads
        st = rc.stats()
        # the empty read at the tail is answered before any cursor is looked at
        assert st["hits"] == reads - 2 and st["misses"] == 1
        # every scanned read either touched no file or made at least one pread
        assert st["window_reads"] + st["file_reads"] >= reads - 1 > st["window_reads"] > 0
        assert st["window_reads"] >= 0.7 * (st["window_reads"] + st["file_reads"])
        assert st["window_bytes"] == sum(len(c.window) for c in rc._lru.values())
        await mgr.stop()
        assert rc.stats()["window_bytes"] == 0 and rc.stats()["entries"] == 0

    _run(main())


# ------------------------------------------------------------------ compaction
def _kv_batch(pairs, ts=0):
    """pairs: [(key, value-or-None)]"""
    recs = [
        Record(offset_delta=i, timestamp_delta=i, key=k, value=v)
        for i, (k, v) in enumerate(pairs)
    ]
    return RecordBatch.build(recs, first_timestamp=ts, max_timestamp=ts)


def _kv_view(batches):
    """Materialize key->value last-write-wins from read batches."""
    out = {}
    for b in batches:
        for r in b.records():
            out[r.key] = r.value
    return out


def test_compaction_last_value_wins(ntp, cfg):
    async def main():
        cfg.cleanup_policy = "compact"
        cfg.max_segment_size = 400  # force frequent rolls
        log = await DiskLog.open(ntp, cfg)
        for round_ in range(6):
            await log.append(
                [_kv_batch([(b"k%d" % i, b"v%d-%d" % (i, round_)) for i in range(4)])]
            )
        before_bytes = sum(s.size_bytes for s in log.segments)
        dirty_before = log.offsets().dirty_offset
        b_before, b_after = await log.compact()
        assert b_after < b_before
        # offsets unchanged, replay sees only the latest values
        assert log.offsets().dirty_offset == dirty_before
        view = _kv_view(await log.read(0, 1 << 30))
        assert view == {b"k%d" % i: b"v%d-5" % i for i in range(4)}
        # surviving records keep their ORIGINAL absolute offsets
        for b in await log.read(0, 1 << 30):
            for r in b.records():
                assert b.base_offset + r.offset_delta <= dirty_before
        await log.close()

    _run(main())


def test_compaction_preserves_offsets_across_restart(ntp, cfg):
    async def main():
        cfg.cleanup_policy = "compact"
        cfg.max_segment_size = 300
        log = await DiskLog.open(ntp, cfg)
        # same single key over and over: closed segments become fully shadowed
        for i in range(8):
            await log.append([_kv_batch([(b"k", b"v%d" % i)])])
        dirty = log.offsets().dirty_offset
        await log.compact()
        assert log.offsets().dirty_offset == dirty  # empty final batches kept
        r = await log.append([_kv_batch([(b"k2", b"x")])])
        assert r.base_offset == dirty + 1  # no offset reuse after compaction
        await log.close()
        # restart: recovery replays the compacted segments cleanly
        log2 = await DiskLog.open(ntp, cfg)
        assert log2.offsets().dirty_offset == dirty + 1
        view = _kv_view(await log2.read(0, 1 << 30))
        assert view == {b"k": b"v7", b"k2": b"x"}
        await log2.close()

    _run(main())


def test_compaction_tombstones(ntp, cfg):
    async def main():
        cfg.cleanup_policy = "compact"
        cfg.max_segment_size = 1  # roll after every batch: all but tail closed
        log = await DiskLog.open(ntp, cfg)
        now_ms = 1_700_000_000_000
        await log.append([_kv_batch([(b"a", b"1"), (b"b", b"2")], ts=now_ms)])
        await log.append([_kv_batch([(b"a", None)], ts=now_ms + 1)])  # tombstone
        await log.append([_kv_batch([(b"c", b"3")], ts=now_ms + 2)])
        # retention window still open: tombstone survives, shadows a=1
        cfg.delete_retention_ms = 10**15
        await log.compact()
        view = _kv_view(await log.read(0, 1 << 30))
        assert view == {b"a": None, b"b": b"2", b"c": b"3"}
        # window closed: tombstone itself is removed
        cfg.delete_retention_ms = 0
        log._compacted_through = None
        await log.compact()
        view = _kv_view(await log.read(0, 1 << 30))
        assert view == {b"b": b"2", b"c": b"3"}
        await log.close()

    _run(main())


def test_compaction_key_index_spills(ntp, cfg):
    async def main():
        from redpanda_tpu.storage.compaction import build_key_index

        cfg.cleanup_policy = "compact"
        cfg.max_segment_size = 4096
        log = await DiskLog.open(ntp, cfg)
        for chunk in range(10):
            pairs = [(b"key-%04d" % (chunk * 50 + i), b"v") for i in range(50)]
            await log.append([_kv_batch(pairs)])
        idx = build_key_index(log.segments, max_keys_in_memory=64)  # force spill
        assert len(idx) == 500
        assert idx[b"key-0000"] == 0 and idx[b"key-0499"] == 499
        await log.close()

    _run(main())


def test_compaction_keeps_non_data_batches(ntp, cfg):
    async def main():
        cfg.cleanup_policy = "compact"
        cfg.max_segment_size = 1  # roll after every batch
        log = await DiskLog.open(ntp, cfg)
        await log.append([_kv_batch([(b"k", b"old")])])
        await log.append([_batch(1, type=RecordBatchType.raft_configuration)])
        await log.append([_kv_batch([(b"k", b"new")])])
        await log.append([_kv_batch([(b"z", b"tail")])])
        await log.compact()
        batches = await log.read(0, 1 << 30)
        types = [b.header.type for b in batches]
        assert RecordBatchType.raft_configuration in types
        view = _kv_view([b for b in batches if b.header.type == RecordBatchType.raft_data])
        assert view[b"k"] == b"new"
        await log.close()

    _run(main())


def test_storage_failure_probes(tmp_path):
    """storage/failure_probes.h analogue: armed honey-badger probes make
    append/truncate fail at the probe site; disarming restores service;
    the probes are listed under the 'storage' module for the admin API."""
    from redpanda_tpu.finjector import ProbeTriggered, honey_badger

    async def body():
        assert {"log_append", "log_roll", "log_truncate"} <= set(
            honey_badger.modules().get("storage", [])
        )
        log = await DiskLog.open(NTP.kafka("probe", 0), LogConfig(base_dir=str(tmp_path)))
        honey_badger.enable()
        try:
            honey_badger.set_exception("storage", "log_append")
            with pytest.raises(ProbeTriggered):
                await log.append([_batch(1)])
            honey_badger.unset("storage", "log_append")
            await log.append([_batch(1)])  # service restored

            honey_badger.set_exception("storage", "log_truncate")
            with pytest.raises(ProbeTriggered):
                await log.truncate(0)
            honey_badger.unset("storage", "log_truncate")
            await log.truncate(0)
        finally:
            honey_badger.disable()
            await log.close()

    _run(body())


def test_storage_delay_probe_actually_delays(tmp_path):
    """A DELAY effect armed on a sync storage probe must stall the op."""
    import time as _time

    from redpanda_tpu.finjector import honey_badger

    async def body():
        log = await DiskLog.open(NTP.kafka("dly", 0), LogConfig(base_dir=str(tmp_path)))
        honey_badger.enable()
        prev_delay = honey_badger.delay_ms
        try:
            honey_badger.delay_ms = 120
            honey_badger.set_delay("storage", "log_append")
            t0 = _time.perf_counter()
            await log.append([_batch(1)])
            assert _time.perf_counter() - t0 >= 0.1, "delay probe did not delay"
        finally:
            honey_badger.disable()
            honey_badger.delay_ms = prev_delay
            await log.close()

    _run(body())


def test_kvstore_opfuzz_vs_model(tmp_path):
    """Randomized put/delete/snapshot/reopen interleaving against a dict
    model (the storage/opfuzz posture applied to the kvstore's WAL +
    snapshot machinery): after every reopen the store must equal the
    model exactly."""
    rng = np.random.default_rng(31337)
    path = str(tmp_path / "kvf")
    kv = KvStore(path).start()
    model: dict[bytes, bytes] = {}
    keys = [b"k%03d" % i for i in range(40)]
    try:
        for step in range(300):
            op = rng.choice(["put", "delete", "snapshot", "reopen"], p=[0.6, 0.2, 0.1, 0.1])
            if op == "put":
                k = keys[int(rng.integers(len(keys)))]
                v = rng.bytes(int(rng.integers(1, 64)))
                kv.put(KeySpace.storage, k, v)
                model[k] = v
            elif op == "delete" and model:
                k = list(model)[int(rng.integers(len(model)))]
                kv.remove(KeySpace.storage, k)
                del model[k]
            elif op == "snapshot":
                kv._do_snapshot()
            elif op == "reopen":
                if rng.random() < 0.5:
                    # CRASH reopen: drop the WAL handle without stop()'s
                    # snapshot+truncate, so recovery must REPLAY the WAL
                    kv._wal.close()
                    kv._wal = None
                else:
                    kv.stop()  # clean reopen: snapshot-only recovery
                kv = KvStore(path).start()
                for k in keys:
                    assert kv.get(KeySpace.storage, k) == model.get(k), (step, k)
        kv.stop()
        kv = KvStore(path).start()
        for k in keys:
            assert kv.get(KeySpace.storage, k) == model.get(k)
    finally:
        kv.stop()
