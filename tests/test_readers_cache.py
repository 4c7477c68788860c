"""Positioned-reader cache (storage/readers_cache.py; reference
storage/readers_cache.h:36): sequential fetch continuation adopts the
cached cursor instead of re-seeking through the sparse index, cursors at
the log tail survive appends (steady-state consumers), and truncation /
compaction / prefix-truncation drop cursors whose positions went stale.

Integration tests run with batch_cache_bytes=0 so reads always reach the
segment scan — the cursor path is what's under test, and every cursor-hit
read is asserted byte-identical to a cold scan of a fresh manager.
"""

import asyncio

import pytest

from redpanda_tpu.models import NTP, Record, RecordBatch
from redpanda_tpu.models.record import RecordBatchType
from redpanda_tpu.storage.log import LogConfig
from redpanda_tpu.storage.log_manager import LogManager
from redpanda_tpu.storage.readers_cache import ReadCursor, ReadersCache


def _batch(base: int, n: int = 4, pad: int = 64, type=RecordBatchType.raft_data):
    recs = [
        Record(offset_delta=i, value=b"v%05d" % (base + i) + b"x" * pad)
        for i in range(n)
    ]
    return RecordBatch.build(recs, base_offset=base, type=type)


class TestUnit:
    def test_lru_and_stats(self):
        c = ReadersCache(max_entries=2)
        c.put(1, 10, ReadCursor(0, 100))
        c.put(1, 20, ReadCursor(0, 200))
        assert c.get(1, 10) == ReadCursor(0, 100)  # refreshes 10
        c.put(1, 30, ReadCursor(0, 300))  # evicts 20 (LRU)
        assert c.get(1, 20) is None
        assert c.get(1, 10) is not None and c.get(1, 30) is not None
        assert c.stats()["entries"] == 2

    def test_invalidate_ranges(self):
        c = ReadersCache()
        for off in (5, 10, 15):
            c.put(1, off, ReadCursor(0, off * 10))
            c.put(2, off, ReadCursor(0, off * 10))
        c.invalidate(1, from_offset=10)  # drops 10 and 15 of log 1
        assert c.get(1, 5) and not c.get(1, 10) and not c.get(1, 15)
        c.invalidate(2, below_offset=10)  # drops 5 of log 2
        assert not c.get(2, 5) and c.get(2, 10)
        c.invalidate(2)
        assert not c.get(2, 10) and not c.get(2, 15)


class TestLogIntegration:
    @pytest.fixture()
    def mgr(self, tmp_path):
        # zero batch cache: force every read through the segment scan
        return LogManager(LogConfig(base_dir=str(tmp_path)), batch_cache_bytes=0)

    def _cold_read(self, base_dir, ntp, start, max_bytes=1 << 20):
        async def body():
            m = LogManager(LogConfig(base_dir=base_dir), batch_cache_bytes=0)
            log = await m.manage(ntp)
            got = await log.read(start, max_bytes)
            await m.stop()
            return [b.encode_internal() for b in got]

        return asyncio.run(body())

    def test_sequential_reads_hit_cursor(self, mgr):
        async def body():
            ntp = NTP.kafka("seq", 0)
            log = await mgr.manage(ntp)
            for base in range(0, 40, 4):
                await log.append([_batch(base)], assign_offsets=False)
            one = _batch(0).size_bytes
            rc = mgr.readers_cache
            chunks = []
            start = 0
            while True:
                got = await log.read(start, one * 2)  # two batches per read
                if not got:
                    break
                chunks += got
                start = got[-1].last_offset + 1
            # every continuation after the first adopted the stored cursor
            assert rc.hits >= 4, rc.stats()
            assert [b.header.base_offset for b in chunks] == list(range(0, 40, 4))
            return [b.encode_internal() for b in chunks]

        served = asyncio.run(body())
        assert served == self._cold_read(mgr.config.base_dir, NTP.kafka("seq", 0), 0)

    def test_tail_cursor_survives_append(self, mgr):
        async def body():
            ntp = NTP.kafka("tail", 0)
            log = await mgr.manage(ntp)
            await log.append([_batch(0)], assign_offsets=False)
            await log.read(0, 1 << 20)  # stores tail cursor at offset 4
            await log.append([_batch(4)], assign_offsets=False)
            rc = mgr.readers_cache
            h0 = rc.hits
            got = await log.read(4, 1 << 20)
            assert rc.hits == h0 + 1, "tail cursor not adopted after append"
            assert [b.header.base_offset for b in got] == [4]
            return [b.encode_internal() for b in got]

        served = asyncio.run(body())
        assert served == self._cold_read(mgr.config.base_dir, NTP.kafka("tail", 0), 4)

    def test_truncate_drops_cursor(self, mgr):
        async def body():
            ntp = NTP.kafka("trunc", 0)
            log = await mgr.manage(ntp)
            for base in (0, 4, 8):
                await log.append([_batch(base)], assign_offsets=False)
            await log.read(0, 1 << 20)  # cursor at offset 12, tail file pos
            await log.truncate(4)  # rewrites the tail: positions went stale
            # re-append different content at the same offsets
            await log.append([_batch(4, n=4, pad=8)], assign_offsets=False)
            got = await log.read(4, 1 << 20)
            assert [b.header.base_offset for b in got] == [4]
            assert got[0].payload == _batch(4, n=4, pad=8).payload
            # the pre-truncate cursor (offset 12) must be gone
            assert mgr.readers_cache.get(id(log), 12) is None

        asyncio.run(body())

    def test_compaction_drops_cursor(self, mgr, tmp_path):
        async def body():
            cfg = LogConfig(
                base_dir=str(tmp_path), cleanup_policy="compact",
                max_segment_size=1024,
            )
            log = await mgr.manage(NTP.kafka("comp", 0), overrides=cfg)
            def kb(base, key):
                recs = [Record(offset_delta=0, key=key, value=b"v%d" % base)]
                return RecordBatch.build(recs, base_offset=base)
            for base in range(0, 12):
                await log.append([kb(base, b"k%d" % (base % 2))], assign_offsets=False)
            await log.read(0, 1 << 20)
            assert any(k[0] == id(log) for k in mgr.readers_cache._lru)
            await log.compact()
            # in-place rewrite: every cursor for this log must be gone
            assert not any(k[0] == id(log) for k in mgr.readers_cache._lru)
            got = await log.read(0, 1 << 20)
            # latest value per key survives
            vals = {r.key: r.value for b in got for r in b.records()}
            assert vals[b"k0"] in (b"v10",) and vals[b"k1"] in (b"v11",)

        asyncio.run(body())

    def test_corrupt_frame_size_raises_not_short_read(self, mgr):
        """A frame whose size field overruns EOF is corruption and must
        raise (the pre-scan read path surfaced it via decode_internal) —
        never a silent short read that strands consumers."""
        async def body():
            from redpanda_tpu.models.record import CorruptBatchError

            ntp = NTP.kafka("corrupt", 0)
            log = await mgr.manage(ntp)
            await log.append([_batch(0), _batch(4)], assign_offsets=False)
            await log.flush()
            seg = log.segments[-1]
            one = _batch(0).size_bytes
            # corrupt the SECOND frame's size_bytes to a huge value
            with open(seg.data_path, "r+b") as f:
                f.seek(one + 4)
                f.write((0x40000000).to_bytes(4, "little"))
            with pytest.raises(CorruptBatchError):
                await log.read(0, 1 << 20)

        asyncio.run(body())

    def test_trailing_filtered_frames_not_skipped_by_cursor(self, mgr):
        async def body():
            ntp = NTP.kafka("filt", 0)
            log = await mgr.manage(ntp)
            await log.append([_batch(0)], assign_offsets=False)
            cfgb = _batch(4, type=RecordBatchType.raft_configuration)
            await log.append([cfgb], assign_offsets=False)
            # filtered read consumes past the config batch but must anchor
            # its cursor BEFORE it, not after
            got = await log.read(0, 1 << 20, type_filter={RecordBatchType.raft_data})
            assert [b.header.base_offset for b in got] == [0]
            # unfiltered continuation at the cursor offset sees the config batch
            got2 = await log.read(4, 1 << 20)
            assert [b.header.base_offset for b in got2] == [4]
            assert got2[0].header.type == RecordBatchType.raft_configuration

        asyncio.run(body())


# ------------------------------------------------------------ read windows
# A cursor carries the unread rest of the window its reader took from the
# file (PR 25). Every read below goes through the windowed path AND through
# a cursor-less, cache-less read of the same range: the two must agree byte
# for byte, whatever was appended, truncated, rolled or compacted between.

from redpanda_tpu.storage import segment as segment_mod  # noqa: E402
from redpanda_tpu.storage.readers_cache import MAX_WINDOW_BYTES  # noqa: E402

DATA = RecordBatchType.raft_data
CONF = RecordBatchType.raft_configuration


def _frames(batches):
    return [(b.encode_internal(), b.header.term) for b in batches]


class _Env:
    """One log behind a LogManager with no batch cache, so every read
    reaches the segment scan; `read` checks it against the plain read."""

    def __init__(self, mgr, log):
        self.mgr, self.log, self.rc = mgr, log, mgr.readers_cache
        self.next = 0  # next offset to append

    async def append(self, n=1, *, pad=200, type=DATA, key=None):
        for _ in range(n):
            if key is None:
                b = _batch(self.next, pad=pad, type=type)
            else:
                recs = [Record(offset_delta=0, key=key, value=b"v%d" % self.next)]
                b = RecordBatch.build(recs, base_offset=self.next)
            await self.log.append([b], assign_offsets=False)
            self.next = b.last_offset + 1

    async def plain(self, start, max_bytes, **kw):
        log = self.log
        rc, log.readers_cache = log.readers_cache, None
        try:
            return await log.read(start, max_bytes, **kw)
        finally:
            log.readers_cache = rc

    async def read(self, start, max_bytes, **kw):
        got = await self.log.read(start, max_bytes, **kw)
        want = await self.plain(start, max_bytes, **kw)
        assert _frames(got) == _frames(want), (start, max_bytes, kw)
        held = sum(len(c.window) for c in self.rc._lru.values())
        assert self.rc.window_bytes == held <= self.rc.max_window_bytes
        return got

    async def walk(self, start, max_bytes, **kw):
        """Continuation reads from `start` to the end; the offsets seen."""
        seen = []
        while True:
            got = await self.read(start, max_bytes, **kw)
            if not got:
                return seen
            seen += [b.header.base_offset for b in got]
            start = got[-1].last_offset + 1


async def _case_append_between_continuations_at_the_tail(env):
    await env.append(6)
    one = _batch(0, pad=200).size_bytes
    got = await env.read(0, 2 * one)
    w0 = env.rc.window_reads
    got = await env.read(got[-1].last_offset + 1, 2 * one)  # out of the window
    assert env.rc.window_reads == w0 + 1
    got = await env.read(got[-1].last_offset + 1, 2 * one)
    tail = got[-1].last_offset + 1
    assert tail == env.next and await env.read(tail, 2 * one) == []
    # the tail cursor's window ended at what was EOF: exhausted, not "end of log"
    await env.append(3)
    h0 = env.rc.hits
    got = await env.read(tail, 2 * one)
    assert env.rc.hits == h0 + 1 and [b.header.base_offset for b in got] == [tail, tail + 4]
    await env.append(1)
    assert await env.walk(got[-1].last_offset + 1, 2 * one) == [tail + 8, tail + 12]


async def _case_max_offset_falls_inside_a_window(env):
    await env.append(10)
    one = _batch(0, pad=200).size_bytes
    got = await env.read(0, one)
    # the window holds frames up to 36; the reader may see up to 13 only
    got = await env.read(4, 8 * one, max_offset=13)
    assert [b.header.base_offset for b in got] == [4, 8, 12]
    assert await env.read(16, 8 * one, max_offset=15) == []
    f0 = env.rc.file_reads
    got = await env.read(16, 2 * one, max_offset=39)  # the cursor kept its window
    assert [b.header.base_offset for b in got] == [16, 20] and env.rc.file_reads == f0
    assert await env.walk(24, 2 * one) == [24, 28, 32, 36]


async def _case_a_frame_straddles_the_windows_end(env):
    # 7 frames of ~4.1 KB in windows of 10,000 B: every third frame is cut
    await env.append(7, pad=1000)
    one = _batch(0, pad=1000).size_bytes
    assert one < segment_mod.READ_AHEAD_BYTES < 3 * one
    f0, w0 = env.rc.file_reads, env.rc.window_reads
    assert await env.walk(0, one) == [0, 4, 8, 12, 16, 20, 24]
    assert env.rc.window_reads > w0 and env.rc.file_reads > f0 + 2
    # a frame larger than the window is read whole, and nothing of it kept
    await env.append(1, pad=4 * segment_mod.READ_AHEAD_BYTES)
    await env.append(2, pad=1000)
    assert await env.walk(24, one) == [24, 28, 32, 36]


async def _case_a_request_larger_than_the_window(env):
    await env.append(12, pad=1000)
    one = _batch(0, pad=1000).size_bytes
    got = await env.read(0, one)
    big = 4 * segment_mod.READ_AHEAD_BYTES
    got = await env.read(4, big)  # a hit, but no reader's read-ahead: as asked
    assert sum(b.size_bytes for b in got) >= big
    nxt = got[-1].last_offset + 1
    cur = env.rc._lru[(id(env.log), nxt)]
    assert len(cur.window) <= 2 * segment_mod.READ_AHEAD_BYTES
    assert await env.walk(nxt, one) == list(range(nxt, 48, 4))


async def _case_continuation_under_another_type_filter(env):
    await env.append(2)
    await env.append(2, type=CONF)
    await env.append(2)
    await env.append(1, type=CONF)
    one = _batch(0, pad=200).size_bytes
    got = await env.read(0, 2 * one, type_filter={DATA})
    assert [b.header.base_offset for b in got] == [0, 4]
    # the data reader consumed no config frame for anyone else
    got = await env.read(8, 8 * one, type_filter={CONF})
    assert [b.header.base_offset for b in got] == [8, 12, 24]
    got = await env.read(8, 8 * one)
    assert [b.header.base_offset for b in got] == [8, 12, 16, 20, 24]
    assert await env.walk(8, one, type_filter={DATA}) == [16, 20]
    assert await env.walk(0, one) == [0, 4, 8, 12, 16, 20, 24]


async def _case_two_readers_at_different_offsets(env):
    await env.append(16)
    one = _batch(0, pad=200).size_bytes
    a_at, b_at = 0, 20
    seen_a, seen_b = [], []
    for step in range(12):
        if step == 5:
            await env.append(4)
        a = await env.read(a_at, 2 * one)
        b = await env.read(b_at, one)
        seen_a += [x.header.base_offset for x in a]
        seen_b += [x.header.base_offset for x in b]
        a_at = a[-1].last_offset + 1 if a else a_at
        b_at = b[-1].last_offset + 1 if b else b_at
    assert seen_a == list(range(0, 80, 4)) and seen_b == list(range(20, 68, 4))
    # a second reader at an offset already consumed: position, no window
    f0 = env.rc.file_reads
    await env.read(8, one)
    assert env.rc.file_reads == f0 + 1


async def _case_the_window_byte_bound_is_reached(env):
    rc = env.rc
    rc.max_window_bytes = 3 * segment_mod.READ_AHEAD_BYTES
    one = _batch(0, pad=1000).size_bytes
    envs = [env]
    for p in range(1, 6):
        envs.append(_Env(env.mgr, await env.mgr.manage(NTP.kafka("win", p))))
    for e in envs:
        await e.append(12, pad=1000)
    at = [0] * len(envs)
    peak = 0
    for _ in range(12):
        for i, e in enumerate(envs):
            got = await e.read(at[i], one)
            at[i] = got[-1].last_offset + 1
            peak = max(peak, rc.window_bytes)
    assert at == [48] * len(envs)
    # six readers' windows do not fit: the bound held, and it was needed
    assert 0 < peak <= rc.max_window_bytes < len(envs) * segment_mod.READ_AHEAD_BYTES
    # the readers that lost their window kept their position (cursor hits)
    assert rc.misses == len(envs)


async def _case_truncate_under_a_window(env):
    await env.append(10)
    one = _batch(0, pad=200).size_bytes
    got = await env.read(0, 2 * one)  # cursor at 8, window reaches to 40
    await env.log.truncate(20)
    env.next = 20
    await env.append(5, pad=150)  # other bytes at the offsets the window held
    assert await env.walk(8, 2 * one) == list(range(8, 40, 4))


def _fuzz(seed):
    async def case(env):
        import random

        rng = random.Random(seed)
        log = env.log
        start = 0
        readers = [0, 0, 0]  # next offsets of three sequential readers
        for step in range(160):
            op = rng.choices(
                ["append", "seq", "cold", "truncate", "prefix", "compact", "filter"],
                [30, 40, 8, 5, 5, 4, 8],
            )[0]
            if op == "append":
                await env.append(
                    rng.randint(1, 4),
                    pad=rng.choice([8, 200, 1500]),
                    type=rng.choice([DATA, DATA, DATA, CONF]),
                    key=rng.choice([None, None, b"k%d" % rng.randint(0, 3)]),
                )
            elif op in ("seq", "filter"):
                r = rng.randrange(len(readers))
                at = max(readers[r], start)
                kw = {}
                if op == "filter":
                    kw["type_filter"] = {DATA}
                if rng.random() < 0.3 and env.next > at:
                    kw["max_offset"] = rng.randint(at, env.next)
                got = await env.read(at, rng.choice([100, 700, 3000, 20000]), **kw)
                readers[r] = got[-1].last_offset + 1 if got else at
            elif op == "cold":
                await env.read(rng.randint(start, max(start, env.next)), 1 << 20)
            elif op == "truncate" and env.next > start:
                cut = rng.randint(start, env.next)
                await log.truncate(cut)
                env.next = max(log.offsets().dirty_offset + 1, start)
                readers = [min(r, env.next) for r in readers]
            elif op == "prefix" and env.next > start:
                start = max(start, rng.randint(start, env.next))
                await log.prefix_truncate(start)
            elif op == "compact":
                await log.compact()
        assert env.rc.hits > 0 and env.rc.window_reads > 0, env.rc.stats()

    return case


WINDOW_CASES = {
    "append_between_continuations_at_the_tail": (
        _case_append_between_continuations_at_the_tail, None),
    "max_offset_falls_inside_a_window": (_case_max_offset_falls_inside_a_window, None),
    "a_frame_straddles_the_windows_end": (_case_a_frame_straddles_the_windows_end, 10_000),
    "a_request_larger_than_the_window": (_case_a_request_larger_than_the_window, 10_000),
    "continuation_under_another_type_filter": (
        _case_continuation_under_another_type_filter, None),
    "two_readers_at_different_offsets": (_case_two_readers_at_different_offsets, 1500),
    "the_window_byte_bound_is_reached": (_case_the_window_byte_bound_is_reached, 16 << 10),
    "truncate_under_a_window": (_case_truncate_under_a_window, None),
    **{f"random_interleaving_{s}": (_fuzz(s), 2000) for s in (1, 2, 3, 4)},
    "random_interleaving_real_window": (_fuzz(5), None),
}


@pytest.mark.parametrize("case", list(WINDOW_CASES))
def test_windowed_reads_equal_cursorless_reads(case, tmp_path, monkeypatch):
    scenario, window = WINDOW_CASES[case]
    if window is not None:
        monkeypatch.setattr(segment_mod, "READ_AHEAD_BYTES", window)

    async def body():
        cfg = LogConfig(
            base_dir=str(tmp_path), max_segment_size=16 << 10, cleanup_policy="compact"
        )
        mgr = LogManager(cfg, batch_cache_bytes=0)
        env = _Env(mgr, await mgr.manage(NTP.kafka("win", 0)))
        try:
            await scenario(env)
        finally:
            await mgr.stop()

    asyncio.run(body())


def test_window_memory_is_bounded_by_the_stated_constant():
    """256 cursors x a 256 KiB window would be 64 MiB: the cache holds
    MAX_WINDOW_BYTES (32 MiB), the least recently used give theirs up and
    keep their positions."""
    c = ReadersCache()
    assert c.max_window_bytes == MAX_WINDOW_BYTES == 32 << 20
    window = bytes(segment_mod.READ_AHEAD_BYTES)
    for log in range(c.max_entries):
        c.put(log, 10, ReadCursor(0, 100, window, 100))
        assert c.window_bytes <= MAX_WINDOW_BYTES
    assert c.stats()["window_bytes"] == MAX_WINDOW_BYTES and c.stats()["entries"] == 256
    assert c.get(0, 10) == ReadCursor(0, 100)  # oldest: position only
    assert c.get(255, 10).window is window
    c.invalidate(255)
    assert c.window_bytes == MAX_WINDOW_BYTES - len(window)


# ------------------------------------------------------------ pacemaker level
def test_script_over_a_multi_segment_backlog_matches_the_reference(tmp_path):
    """The catch-up cell's path at a size a test can afford: a script is
    deployed over a backlog that spans several segments and has left the
    batch cache, so the pacemaker's reads are cursor continuations out of
    read-ahead windows; the materialized topic holds exactly what the plain
    reference makes of every input record, in order."""
    import time
    import types

    from redpanda_tpu.cluster.topic_table import TopicConfig
    from redpanda_tpu.coproc.api import CoprocApi
    from redpanda_tpu.coproc.reference import make_documents, project_error
    from redpanda_tpu.kafka.server.broker import Broker, BrokerConfig
    from redpanda_tpu.kafka.server.protocol import KafkaServer
    from redpanda_tpu.ops.exprs import field
    from redpanda_tpu.ops.transforms import Int, Str, map_project, where
    from redpanda_tpu.storage.log_manager import StorageApi

    parts, per_part, per_batch = 2, 1536, 32
    docs = make_documents(25, parts, per_part)

    async def body():
        storage = await StorageApi(str(tmp_path)).start()
        cfg = BrokerConfig(data_dir=str(tmp_path))
        broker = Broker(cfg, storage)
        server = await KafkaServer(broker, "127.0.0.1", 0).start()
        cfg.advertised_port = server.port
        api = await CoprocApi(broker).start()
        api.poll_interval_s = 0.02
        broker.coproc_api = api
        # as in the cell, no launch of this process has run on the device
        # (the process-wide histogram holds what earlier tests launched)
        no_legs = types.SimpleNamespace(count=0, percentile=lambda q: 0, record=lambda v: None)
        api.engine.governor._stage_hist = lambda domain: no_legs
        try:
            # ~1.5 MB a partition in segments of 512 KiB: 16 batches each
            await broker.create_topic(TopicConfig("src", parts, segment_size=512 << 10))
            for p in range(parts):
                part = broker.get_partition("src", p)
                for i in range(0, per_part, per_batch):
                    recs = [
                        Record(value=v, offset_delta=j)
                        for j, v in enumerate(docs[p][i : i + per_batch])
                    ]
                    await part.replicate([RecordBatch.build(recs)], 0)
            rc, bc = storage.log_mgr.readers_cache, storage.log_mgr.batch_cache
            logs = [storage.log_mgr.get(NTP.kafka("src", p)) for p in range(parts)]
            assert all(len(log.segments) >= 3 for log in logs)
            for log in logs:  # the backlog is older than the batch cache
                bc.invalidate(id(log))
            before = rc.stats()
            spec = (
                where(field("level") == "error") | map_project(Int("code"), Str("msg", 64))
            ).to_json()
            await api.deploy("proj", spec, ["src"])
            want = [[o for o in map(project_error, docs[p]) if o is not None] for p in range(parts)]
            assert all(len(w) > per_part // 5 for w in want)

            def materialized(p):
                part = broker.partition_manager.get(NTP.kafka("src.$proj$", p))
                return part.high_watermark if part else 0

            deadline = time.monotonic() + 60
            while any(materialized(p) < len(want[p]) for p in range(parts)):
                assert time.monotonic() < deadline, [materialized(p) for p in range(parts)]
                await asyncio.sleep(0.05)
            await asyncio.sleep(0.2)  # a repeated record would land now
            for p in range(parts):
                part = broker.partition_manager.get(NTP.kafka("src.$proj$", p))
                got = await part.make_reader(0, 1 << 30)
                assert [r.value for b in got for r in b.records()] == want[p]
            after = rc.stats()
            reads = {k: after[k] - before[k]
                     for k in ("hits", "misses", "window_reads", "file_reads")}
            # every read but a partition's first continued from a cursor,
            # and some touched no file. 48 batches a partition, two a read
            # at first and one more every three launches (the launch knob
            # grows on a backlog where nothing has run on the device:
            # test_pacemaker_read_ahead (j)): 14 reads a partition, not 24
            assert reads["misses"] == parts and 20 <= reads["hits"] < 40, reads
            assert reads["window_reads"] >= 4, reads
        finally:
            await api.stop()
            await server.stop()
            await storage.stop()

    asyncio.run(asyncio.wait_for(body(), 120))
