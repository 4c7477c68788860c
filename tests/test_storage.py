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


# ------------------------------------------------- one framing crossing a list
# An offset-assigning append frames its list in one native crossing
# (DiskLog._frame / _append_framed); the per-batch loop stays for a follower's
# append and for a process with no native library. Both leave the same files.
def _mixed(n, seed=0, ts0=1_700_000_000_000):
    """`n` sealed batches of mixed sizes and record counts, as a reply's."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        recs = [
            Record(offset_delta=j, timestamp_delta=j, value=rng.bytes(int(rng.integers(1, 700))))
            for j in range(int(rng.integers(1, 6)))
        ]
        out.append(RecordBatch.build(
            recs, first_timestamp=ts0 + 10 * i, max_timestamp=ts0 + 10 * i + len(recs) - 1
        ))
    return out


def _flipped(batch):
    """`batch` with one payload byte flipped under its sealed header."""
    from dataclasses import replace

    payload = bytearray(batch.payload)
    payload[len(payload) // 2] ^= 0x40
    return RecordBatch(replace(batch.header), bytes(payload))


def _withhold(monkeypatch, what):
    from redpanda_tpu import native

    if what == "crossing":
        monkeypatch.setattr(native.lib, "has_frame_internal_many", False)
    elif what == "native":
        monkeypatch.setattr(native, "lib", None)


def _crossing_samples():
    from redpanda_tpu.observability import probes

    h = probes.storage_append_crossing_batches_hist.hist
    return h.count, h.sum


async def _write_log(base_dir, calls, *, roll=None, verify=False, manager=False):
    """A log written by one append a (batches, term) of `calls`. Returns what
    the calls returned, what the listeners and the cache saw, and, the log
    closed, every file it left with its bytes."""
    cfg = LogConfig(base_dir=str(base_dir))
    if roll is not None:
        cfg.max_segment_size = roll
    ntp = NTP.kafka("framed", 0)
    mgr = LogManager(cfg) if manager else None
    log = await (mgr.manage(ntp) if manager else DiskLog.open(ntp, cfg))
    seen = []
    log.append_listeners.append(lambda *a: seen.append(a))
    results = []
    for batches, term in calls:
        r = await log.append(batches, term=term, verify_crc=verify)
        results.append((r.base_offset, r.last_offset, r.byte_size))
    cached = []
    if manager:
        cached = [
            (vars(b.header), b.payload)
            for (_, _), b in sorted(mgr.batch_cache._lru.items(), key=lambda kv: kv[0][1])
        ]
    index = [[(e.rel_offset, e.file_pos, e.timestamp) for e in s.index.entries] for s in log.segments]
    await log.close()
    files = {}
    for fn in sorted(os.listdir(log.dir)):
        with open(os.path.join(log.dir, fn), "rb") as f:
            files[fn] = f.read()
    return {"results": results, "seen": seen, "cached": cached, "index": index, "files": files}


@pytest.mark.skipif(
    not getattr(__import__("redpanda_tpu.native").native.lib, "has_frame_internal_many", False),
    reason="no native framing crossing here",
)
@pytest.mark.parametrize("roll", [None, 2500], ids=["one_segment", "roll_in_mid_list"])
@pytest.mark.parametrize("n", [1, 2, 9, 64])
def test_a_framed_append_leaves_the_per_batch_loops_files(tmp_path, monkeypatch, n, roll):
    """Segment files and index entries byte for byte, the results, the
    batches the cache holds (field for field what with_base_offset gave)
    and the listeners' calls: lists of 1, 2, 9 and 64 mixed batches, a
    roll in mid-list, a term change between calls."""
    calls = [(_mixed(n, seed=n), 1), (_mixed(max(1, n // 2), seed=n + 100), 1),
             (_mixed(n, seed=n + 200), 3)]

    async def main():
        before = _crossing_samples()
        framed = await _write_log(tmp_path / "framed", calls, roll=roll, manager=True)
        crossings = _crossing_samples()
        # one sample a crossing, holding the list's batches
        assert crossings[0] - before[0] == len(calls)
        assert crossings[1] - before[1] == sum(len(b) for b, _ in calls)
        _withhold(monkeypatch, "crossing")
        looped = await _write_log(tmp_path / "looped", calls, roll=roll, manager=True)
        per_batch = _crossing_samples()
        # the loop: one sample a batch, each 1
        assert per_batch[0] - crossings[0] == per_batch[1] - crossings[1] == sum(
            len(b) for b, _ in calls
        )
        return framed, looped

    framed, looped = _run(main())
    assert sorted(framed["files"]) == sorted(looped["files"])
    for fn in framed["files"]:
        assert framed["files"][fn] == looped["files"][fn], fn
    if roll is not None and n >= 9:
        assert sum(fn.endswith(".log") for fn in framed["files"]) > 2
    assert any(fn.endswith("-3-v1.log") for fn in framed["files"])  # the term change
    assert framed["index"] == looped["index"]
    assert framed["results"] == looped["results"]
    assert framed["seen"] == looped["seen"]
    assert framed["cached"] == looped["cached"] and framed["cached"]
    assert all(h["term"] in (1, 3) and h["base_offset"] >= 0 for h, _ in framed["cached"])


@pytest.mark.parametrize("how", ["crossing", "loop", "no_native"])
@pytest.mark.parametrize("n", [9, 64])
def test_a_framed_log_recovers_and_reads_back_what_was_appended(tmp_path, monkeypatch, how, n):
    """recover_segment over the reopened tail and a scanned read give the
    appended batches back, offsets assigned, whichever road framed them."""
    _withhold(monkeypatch, {"loop": "crossing", "no_native": "native"}.get(how))
    batches = _mixed(n, seed=7 * n)
    cfg = LogConfig(base_dir=str(tmp_path), max_segment_size=6000)
    ntp = NTP.kafka("framed", 0)

    async def main():
        log = await DiskLog.open(ntp, cfg)
        r = await log.append(batches, term=2)
        span = sum(b.header.last_offset_delta + 1 for b in batches)
        assert (r.base_offset, r.last_offset) == (0, span - 1)
        assert r.byte_size == sum(b.size_bytes for b in batches)
        await log.close()
        log = await DiskLog.open(ntp, cfg)  # recover_segment scans the tail
        assert log.offsets().dirty_offset == span - 1
        got = await log.read(0, max_bytes=1 << 30)
        await log.close()
        return got

    got = _run(main())
    assert len(got) == n
    expect = 0
    for b, src in zip(got, batches):
        assert b.base_offset == expect and b.payload == src.payload
        assert b.verify_header_crc() and b.verify_kafka_crc()
        assert b.header.crc == src.header.crc and b.header.record_count == src.header.record_count
        expect = b.last_offset + 1


@pytest.mark.parametrize("how", ["crossing", "loop", "no_native"])
@pytest.mark.parametrize("corrupt", [(0,), (4,), (8,), (2, 3), tuple(range(9))],
                         ids=["first", "middle", "last", "two", "all"])
def test_a_verifying_append_leaves_a_corrupt_batch_out(tmp_path, monkeypatch, caplog, how, corrupt):
    """One payload byte flipped: the batch is left out and logged, takes no
    offset, and its neighbours land with contiguous offsets; the files are
    those of an append of the sound batches alone."""
    _withhold(monkeypatch, {"loop": "crossing", "no_native": "native"}.get(how))
    sound = _mixed(9, seed=5)
    given = [(_flipped(b) if i in corrupt else b) for i, b in enumerate(sound)]
    kept = [b for i, b in enumerate(sound) if i not in corrupt]

    async def main():
        with caplog.at_level("ERROR", logger="rptpu.storage"):
            checked = await _write_log(
                tmp_path / "checked", [(given, 1), (sound[:2], 1)], verify=True
            )
        plain = await _write_log(tmp_path / "plain", [(kept, 1), (sound[:2], 1)])
        return checked, plain

    checked, plain = _run(main())
    dropped = [r for r in caplog.records if "dropping corrupt batch" in r.getMessage()]
    assert len(dropped) == len(corrupt)
    assert "framed/0" in dropped[0].getMessage() or "framed" in dropped[0].getMessage()
    assert checked["files"] == plain["files"]
    assert checked["results"] == plain["results"]
    assert checked["seen"] == plain["seen"]
    span = sum(b.header.last_offset_delta + 1 for b in kept)
    assert checked["results"][0][:2] == (0, span - 1)  # nothing taken by the dropped
    assert checked["results"][1][0] == span  # the next append follows on


def test_an_append_that_does_not_verify_lands_a_corrupt_batch_as_before(tmp_path):
    """verify_crc is the caller's to ask: a produce's append (the front end
    checked the CRC on the way in) reads no payload twice."""
    given = [_flipped(b) for b in _mixed(3, seed=1)]

    async def main():
        log = await DiskLog.open(NTP.kafka("framed", 0), LogConfig(base_dir=str(tmp_path)))
        r = await log.append(given, term=1)
        got = await log.read(0)
        await log.close()
        return r, got

    r, got = _run(main())
    assert len(got) == 3 and r.base_offset == 0
    assert not any(b.verify_kafka_crc() for b in got)
    assert all(b.verify_header_crc() for b in got)


@pytest.mark.parametrize("payload_type", [bytearray, memoryview])
def test_a_payload_that_is_not_bytes_takes_the_loop_and_the_same_files(tmp_path, payload_type):
    sound = _mixed(4, seed=9)
    other = [RecordBatch(b.header, payload_type(b.payload)) for b in sound]

    async def main():
        before = _crossing_samples()
        a = await _write_log(tmp_path / "a", [(other, 1)])
        assert _crossing_samples()[0] - before[0] == 4  # one a batch: the loop
        return a, await _write_log(tmp_path / "b", [(sound, 1)])

    a, b = _run(main())
    assert a["files"] == b["files"] and a["results"] == b["results"]


@pytest.mark.parametrize("how", ["crossing", "loop"])
def test_a_roll_that_fails_in_mid_list_keeps_what_landed_before_it(tmp_path, monkeypatch, how):
    """The log_roll probe fires at the list's first roll: the batches before
    it are in the log, whole and readable, and the next append follows on."""
    from redpanda_tpu.finjector import ProbeTriggered, honey_badger

    _withhold(monkeypatch, {"loop": "crossing"}.get(how))
    batches = _mixed(9, seed=3)
    cfg = LogConfig(base_dir=str(tmp_path), max_segment_size=2500)

    async def main():
        log = await DiskLog.open(NTP.kafka("framed", 0), cfg)
        honey_badger.enable()
        try:
            honey_badger.set_exception("storage", "log_roll")
            with pytest.raises(ProbeTriggered):
                await log.append(batches, term=1)
        finally:
            honey_badger.unset("storage", "log_roll")
            honey_badger.disable()
        landed = await log.read(0, max_bytes=1 << 30)
        dirty = log.offsets().dirty_offset
        r = await log.append(batches[:1], term=1)
        await log.close()
        return landed, dirty, r

    landed, dirty, r = _run(main())
    assert 0 < len(landed) < 9
    assert [b.payload for b in landed] == [b.payload for b in batches[: len(landed)]]
    assert all(b.verify_header_crc() for b in landed)
    assert dirty == landed[-1].last_offset and r.base_offset == dirty + 1


@pytest.mark.parametrize("fsync", [False, True])
def test_fsync_on_append_commits_a_framed_list(tmp_path, fsync):
    async def main():
        cfg = LogConfig(base_dir=str(tmp_path), fsync_on_append=fsync)
        log = await DiskLog.open(NTP.kafka("framed", 0), cfg)
        r = await log.append(_mixed(9, seed=2), term=1)
        off = log.offsets()
        await log.close()
        return r, off

    r, off = _run(main())
    assert off.dirty_offset == r.last_offset
    assert off.committed_offset == (r.last_offset if fsync else -1)


@pytest.mark.skipif(
    not getattr(__import__("redpanda_tpu.native").native.lib, "has_frame_internal_many", False),
    reason="no native framing crossing here",
)
@pytest.mark.parametrize("n", [1, 9])
@pytest.mark.parametrize("flush", ["flush_after_each", "flush_after_first", "no_flush"])
def test_an_empty_segment_buffer_adopts_the_framed_bytes(tmp_path, monkeypatch, n, flush):
    """A framed list that finds the segment's buffer empty (every acks=all
    produce: the log is flushed after each append) becomes the buffer, not
    a copy of it; one that finds bytes waiting is added to them. The file
    is the per-batch loop's either way."""
    first, calls = _mixed(1, seed=99), [_mixed(n, seed=n + i) for i in range(4)]

    async def write(base_dir, framed):
        log = await DiskLog.open(NTP.kafka("framed", 0), LogConfig(base_dir=str(base_dir)))
        adopted = []
        await log.append(first, term=1)
        await log.flush()
        seg = log.segments[-1]
        for i, batches in enumerate(calls):
            waiting, held = bool(seg._buf), seg._buf
            await log.append(batches, term=1)
            assert seg is log.segments[-1]
            # an empty buffer is replaced by the frames, a filled one is kept and grown
            adopted.append(seg._buf is not held)
            assert adopted[-1] == (framed and not waiting), (i, waiting)
            assert len(seg._buf) == seg.size_bytes - seg._file.tell()
            if flush == "flush_after_each" or (flush == "flush_after_first" and i == 0):
                await log.flush()
                assert not seg._buf
        read_back = await log.read(0)
        await log.close()
        files = {}
        for fn in sorted(os.listdir(log.dir)):
            with open(os.path.join(log.dir, fn), "rb") as fh:
                files[fn] = fh.read()
        return adopted, [b.payload for b in read_back], files

    async def main():
        framed = await write(tmp_path / "framed", True)
        _withhold(monkeypatch, "crossing")
        return framed, await write(tmp_path / "looped", False)

    framed, looped = _run(main())
    assert framed[0].count(True) == {"flush_after_each": 4, "flush_after_first": 2, "no_flush": 1}[flush]
    assert framed[1] == looped[1] == [b.payload for batches in [first, *calls] for b in batches]
    assert framed[2] == looped[2] and framed[2]


def test_mem_log_takes_verify_crc_like_the_disk_log():
    sound = _mixed(3, seed=4)

    async def main():
        log = MemLog(NTP.kafka("framed", 0))
        r = await log.append([sound[0], _flipped(sound[1]), sound[2]], term=1, verify_crc=True)
        return r, await log.read(0)

    r, got = _run(main())
    assert [b.payload for b in got] == [sound[0].payload, sound[2].payload]
    assert got[1].base_offset == got[0].last_offset + 1 == r.last_offset - got[1].header.last_offset_delta
