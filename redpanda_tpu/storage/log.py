"""Per-NTP append-only segmented log.

Capability parity with the reference's storage/disk_log_impl.h behind the
storage/log.h pimpl interface: append / read / flush / truncate /
prefix-truncate (eviction) / timequery / segment roll / retention, with
recovery via a CRC scan of the tail segment (log_replayer.h) that can run
as one batched device kernel.

Design note (TPU-first): the log keeps batches byte-contiguous on disk in
the internal layout so recovery and compaction hashing feed the device CRC
kernel without re-framing; readers return RecordBatch views whose payloads
slice directly out of the read blob.
"""

from __future__ import annotations

import asyncio
import logging
import os
import time
from dataclasses import dataclass, field

from redpanda_tpu import native
from redpanda_tpu.finjector import honey_badger
from redpanda_tpu.models.fundamental import NTP
from redpanda_tpu.models.record import (
    INTERNAL_HEADER_SIZE,
    RecordBatch,
    RecordBatchHeader,
)
from redpanda_tpu.observability import probes, stages
from redpanda_tpu.storage.segment import Segment
from redpanda_tpu.storage.recovery import recover_segment

# storage failure probes (reference storage/failure_probes.h:24
# log_failure_probes {append, roll, truncate}, driven over the admin
# honey-badger API like tests/rptest services/honey_badger.py)
honey_badger.register_probe("storage", "log_append", "log_roll", "log_truncate")

logger = logging.getLogger("rptpu.storage")


@dataclass
class LogConfig:
    base_dir: str = "/tmp/redpanda_tpu_data"
    max_segment_size: int = 128 * 1024 * 1024
    segment_age_s: float = float("inf")
    retention_bytes: int | None = None
    retention_ms: int | None = None
    fsync_on_append: bool = False
    use_device_recovery: bool = False  # batch CRC scan on the TPU
    # cleanup.policy: "delete", "compact", or "compact,delete"
    cleanup_policy: str = "delete"
    # debug file-handle sanitizer (storage::debug_sanitize_files)
    sanitize_files: bool = False
    delete_retention_ms: int | None = 86_400_000  # tombstone retention
    compaction_max_keys_in_memory: int = 128 * 1024  # key-index spill bound


@dataclass
class AppendResult:
    base_offset: int
    last_offset: int
    byte_size: int


@dataclass
class LogOffsets:
    start_offset: int
    dirty_offset: int  # highest appended
    committed_offset: int  # highest fsynced


class DiskLog:
    def __init__(self, ntp: NTP, config: LogConfig):
        self.ntp = ntp
        self.config = config
        self.dir = os.path.join(config.base_dir, ntp.path())
        self.segments: list[Segment] = []
        self._start_offset = 0
        self._committed = -1
        self._active_created_at = 0.0
        self._lock = asyncio.Lock()
        self._term = 0
        # sync callables (type, base_offset, last_offset) fired per appended
        # batch under the log lock; truncation listeners get (offset)
        self.append_listeners: list = []
        self.truncate_listeners: list = []
        # global LRU fronting segment reads (batch_cache.h:99); assigned by
        # the LogManager, None in bare/standalone usage
        self.batch_cache = None
        # positioned-cursor cache for sequential fetch continuation
        # (readers_cache.h:36); assigned by the LogManager like batch_cache
        self.readers_cache = None

    def _cache_put(self, batch: RecordBatch) -> None:
        if self.batch_cache is not None:
            self.batch_cache.put(id(self), batch)

    def _cache_invalidate(self, **kw) -> None:
        if self.batch_cache is not None:
            self.batch_cache.invalidate(id(self), **kw)
        if self.readers_cache is not None:
            self.readers_cache.invalidate(id(self), **kw)

    # ------------------------------------------------------------ lifecycle
    @classmethod
    async def open(cls, ntp: NTP, config: LogConfig) -> "DiskLog":
        if config.sanitize_files:
            from redpanda_tpu.storage import file_sanitizer

            file_sanitizer.enable()
        log = cls(ntp, config)
        # The segment scan + tail CRC recovery is pure disk work on an
        # object nothing else references yet; inline it and a node restart
        # with many partitions would stall every other recovery on the loop.
        await asyncio.to_thread(log._open_sync)
        return log

    def _open_sync(self) -> None:
        os.makedirs(self.dir, exist_ok=True)
        stems = sorted(
            (f for f in os.listdir(self.dir) if f.endswith(".log")),
            key=lambda f: int(f.split("-")[0]),
        )
        for i, fname in enumerate(stems):
            base, term, _ = fname.split("-", 2)
            seg = Segment(self.dir, int(base), int(term))
            last = i == len(stems) - 1
            seg.open_existing(writable=False)
            if last:
                # CRC-scan the tail (crash recovery), truncating at the
                # first corrupt frame, then reopen for append.
                recover_segment(seg, use_device=self.config.use_device_recovery)
                seg._file = open(seg.data_path, "ab")
            self.segments.append(seg)
            self._term = max(self._term, seg.term)
        if self.segments:
            self._start_offset = self.segments[0].base_offset
            self._committed = self.segments[-1].dirty_offset
            self._active_created_at = time.monotonic()

    async def close(self):
        async with self._lock:
            self._cache_invalidate()
            for seg in self.segments:
                seg.close()

    async def remove(self):
        async with self._lock:
            self._cache_invalidate()
            for seg in self.segments:
                seg.remove()
            self.segments.clear()
            try:
                os.removedirs(self.dir)
            except OSError:
                pass

    # ------------------------------------------------------------ offsets
    def offsets(self) -> LogOffsets:
        dirty = self.segments[-1].dirty_offset if self.segments else self._start_offset - 1
        return LogOffsets(self._start_offset, dirty, self._committed)

    @property
    def term(self) -> int:
        return self._term

    # ------------------------------------------------------------ append
    async def append(
        self,
        batches: list[RecordBatch],
        *,
        term: int | None = None,
        assign_offsets: bool = True,
        verify_crc: bool = False,
    ) -> AppendResult:
        """Append sealed batches; assigns monotone base offsets by default.
        ``verify_crc``: a batch whose Kafka CRC does not match its header's
        is logged and left out, takes no offset, and its neighbours land
        with contiguous offsets."""
        if not batches:
            off = self.offsets()
            return AppendResult(off.dirty_offset + 1, off.dirty_offset, 0)
        # storage account (resource_mgmt budget plane): append-buffer bytes
        # inflight through this call. Waiting (not shedding) is correct
        # here — every producer of appends sits behind an admission gate
        # (kafka produce, coproc submit, rpc dispatch), so the wait is
        # bounded backpressure and peak occupancy never breaches the
        # account. Plane-less processes skip both branches.
        from redpanda_tpu.resource_mgmt import budgets as _budgets

        acct = _budgets.account_or_none("storage")
        reserved = 0
        if acct is not None:
            reserved = await acct.acquire(
                sum(b.size_bytes for b in batches)
            )
        t0 = stages.begin("storage.append")
        try:
            return await self._append_locked(batches, term, assign_offsets, verify_crc)
        finally:
            if acct is not None:
                acct.release(reserved)
            stages.close("storage.append", probes.storage_append_hist, t0)

    async def _append_locked(
        self,
        batches: list[RecordBatch],
        term: int | None,
        assign_offsets: bool,
        verify_crc: bool,
    ) -> AppendResult:
        async with self._lock:
            honey_badger.inject_sync("storage", "log_append")
            if term is not None and term > self._term:
                self._term = term
            seg = self._active_segment_for_append()
            framed = self._frame(batches, seg.dirty_offset + 1, verify_crc) if assign_offsets else None
            if framed is not None:
                return self._append_framed(seg, batches, *framed)
            next_offset = seg.dirty_offset + 1
            first = None
            size = 0
            for batch in batches:
                if verify_crc and not batch.verify_kafka_crc():
                    logger.error("dropping corrupt batch for %s", self.ntp)
                    continue
                if assign_offsets:
                    batch = batch.with_base_offset(next_offset)
                    batch.header.term = self._term
                elif batch.header.term < 0:
                    batch.header.term = self._term
                else:
                    # Follower-path append: batches arrive with the leader's
                    # term already stamped; adopt it (terms may also go DOWN
                    # after a divergent suffix was truncated).
                    self._term = batch.header.term
                if first is None:
                    first = batch.base_offset
                # The segment filename is the durable term record (the packed
                # header has no term field), so the active segment's term must
                # match every batch written into it.
                seg = self._segment_for_term(seg, batch.header.term)
                seg = self._maybe_roll(seg)
                seg.append(batch)
                probes.storage_append_crossing_batches_hist.record(1)
                size += batch.size_bytes
                next_offset = batch.last_offset + 1
                self._appended(batch)
            if self.config.fsync_on_append:
                seg.fsync()
                self._committed = seg.dirty_offset
            last = next_offset - 1
            return AppendResult(first if first is not None else last + 1, last, size)

    def _appended(self, batch: RecordBatch) -> None:
        # hot tail into the cache: fetch-after-produce never touches
        # the segment file (batch_cache put-on-append)
        self._cache_put(batch)
        for fn in self.append_listeners:
            fn(batch.header.type, batch.base_offset, batch.last_offset)

    @staticmethod
    def _frame(batches: list[RecordBatch], first_offset: int, verify_crc: bool):
        """The list's internal frames with offsets assigned from
        ``first_offset`` on, in one native crossing
        (``native.frame_internal_many``); None where the per-batch loop has
        to serve: no native library, or a payload that is not the ``bytes``
        its header sizes."""
        lib = native.lib
        if lib is None or not lib.has_frame_internal_many:
            return None
        heads, payloads = [], []
        nbytes = 0
        for batch in batches:
            header, payload = batch.header, batch.payload
            if type(payload) is not bytes or len(payload) + INTERNAL_HEADER_SIZE != header.size_bytes:
                return None
            heads.append(header.encode())
            payloads.append(payload)
            nbytes += header.size_bytes
        return lib.frame_internal_many(
            b"".join(heads), payloads, nbytes, first_offset, verify_crc
        )

    def _append_framed(
        self, seg: Segment, batches: list[RecordBatch], frames: bytearray, header_crcs: list[int]
    ) -> AppendResult:
        """Land a framed list (``_frame``): the bytes go to a segment in one
        piece, a roll in mid-list splits them at the roll; what stays per
        batch is the ``RecordBatch`` the cache and the listeners see, the
        index and the roll check. The files are the per-batch loop's, byte
        for byte."""
        term = self._term
        seg = self._segment_for_term(seg, term)
        first = next_offset = seg.dirty_offset + 1
        landed = pos = 0  # frames[landed:pos] are tracked by seg, not yet its bytes
        try:
            for batch, header_crc in zip(batches, header_crcs):
                if header_crc < 0:
                    logger.error("dropping corrupt batch for %s", self.ntp)
                    continue
                if self._should_roll(seg):
                    seg.write_tracked(memoryview(frames)[landed:pos])
                    landed = pos
                    seg = self._roll(seg)
                h = batch.header
                batch = RecordBatch(
                    RecordBatchHeader(
                        header_crc, h.size_bytes, next_offset, h.type, h.crc,
                        h.attrs, h.last_offset_delta, h.first_timestamp,
                        h.max_timestamp, h.producer_id, h.producer_epoch,
                        h.base_sequence, h.record_count, term,
                    ),
                    batch.payload,
                )
                seg.track(batch)
                pos += h.size_bytes
                next_offset += h.last_offset_delta + 1
                self._appended(batch)
        finally:
            # whatever ended the loop, the segment holds what it tracked:
            # the whole of `frames` handed over where no roll split it
            whole = landed == 0 and pos == len(frames)
            seg.write_tracked(frames if whole else memoryview(frames)[landed:pos])
        probes.storage_append_crossing_batches_hist.record(
            len(header_crcs) - header_crcs.count(-1)
        )
        if self.config.fsync_on_append:
            seg.fsync()
            self._committed = seg.dirty_offset
        return AppendResult(first, next_offset - 1, pos)

    def _active_segment_for_append(self) -> Segment:
        if not self.segments or not self.segments[-1].writable:
            base = self.offsets().dirty_offset + 1
            seg = Segment(self.dir, base, self._term).create()
            self.segments.append(seg)
            self._active_created_at = time.monotonic()
            return seg
        return self.segments[-1]

    def _segment_for_term(self, seg: Segment, term: int) -> Segment:
        """Roll (or, if still empty, replace) the active segment so its
        filename term matches `term`."""
        if seg.term == term:
            return seg
        if seg.size_bytes == 0:
            # Nothing written yet: replace it so no batch is ever mislabeled.
            base = seg.base_offset
            seg.remove()
            self.segments.pop()
        else:
            base = seg.dirty_offset + 1
            seg.release_appender()
        new = Segment(self.dir, base, term).create()
        self.segments.append(new)
        self._active_created_at = time.monotonic()
        return new

    def _should_roll(self, seg: Segment) -> bool:
        return seg.size_bytes >= self.config.max_segment_size or (
            seg.size_bytes > 0
            and (time.monotonic() - self._active_created_at) >= self.config.segment_age_s
        )

    def _roll(self, seg: Segment) -> Segment:
        honey_badger.inject_sync("storage", "log_roll")
        seg.release_appender()
        new = Segment(self.dir, seg.dirty_offset + 1, self._term).create()
        self.segments.append(new)
        self._active_created_at = time.monotonic()
        return new

    def _maybe_roll(self, seg: Segment) -> Segment:
        return self._roll(seg) if self._should_roll(seg) else seg

    async def flush(self):
        async with self._lock:
            if self.segments:
                # the stage is the sync alone, lock wait left out: it runs
                # on the event loop, so its time is what every other
                # coroutine waited while the disk did this
                with stages.stage("storage.flush", probes.storage_flush_hist):
                    self.segments[-1].fsync()
                self._committed = self.segments[-1].dirty_offset

    # ------------------------------------------------------------ read
    async def read(
        self,
        start_offset: int,
        max_bytes: int = 1 << 20,
        *,
        max_offset: int | None = None,
        type_filter=None,
    ) -> list[RecordBatch]:
        with stages.stage("storage.read", probes.storage_read_hist):
            async with self._lock:
                return self._read_locked(start_offset, max_bytes, max_offset, type_filter)

    def _read_locked(self, start_offset, max_bytes, max_offset, type_filter):
        start = max(start_offset, self._start_offset)
        cached = self._read_cached(start, max_bytes, max_offset, type_filter)
        if cached is not None:
            return cached
        out: list[RecordBatch] = []
        taken = 0
        # adopt a cached read cursor for the first touched segment: the
        # scan decodes out of the window the last read left and otherwise
        # reads straight at the frame boundary, instead of going through
        # the sparse index (readers_cache.h continuation)
        rc = self.readers_cache
        first = start
        cursor = rc.get(id(self), start) if rc is not None else None
        # a cursor hit is the evidence of a sequential reader: read ahead
        sequential = cursor is not None
        end = None
        file_reads = 0
        for seg in self.segments:
            if seg.dirty_offset < start:
                continue
            if max_offset is not None and seg.base_offset > max_offset:
                break
            if cursor is not None and cursor.segment_base != seg.base_offset:
                cursor = None
            batches, end, n = seg.scan(
                start,
                max_bytes - taken,
                type_filter=type_filter,
                max_offset=max_offset,
                cursor=cursor,
                read_ahead=sequential,
            )
            cursor = None  # only valid for the first segment touched
            if n and rc is not None:
                rc.reader_opened(seg)
            file_reads += n
            for b in batches:
                out.append(b)
                self._cache_put(b)
                taken += b.size_bytes
            if taken >= max_bytes:
                break
            if out:
                start = out[-1].last_offset + 1
        if rc is not None and end is not None:
            rc.note_read(file_reads)
            if out:
                rc.put(
                    id(self),
                    out[-1].last_offset + 1,
                    end,
                    consumed=first if sequential else None,
                )
        return out

    def _read_cached(self, start, max_bytes, max_offset, type_filter):
        """Serve the read purely from the batch cache, or None.

        Only a COMPLETE answer counts: the cached chain must run unbroken
        from `start` to the dirty offset / max_offset / byte budget —
        a mid-range miss falls back to the segment scan (which re-populates
        the cache), so callers never see a silently shortened read."""
        if self.batch_cache is None or not self.segments:
            return None
        end = self.segments[-1].dirty_offset
        if max_offset is not None:
            end = min(end, max_offset)
        out: list[RecordBatch] = []
        taken = 0
        cur = start
        key = id(self)
        while cur <= end and taken < max_bytes:
            b = self.batch_cache.get(key, cur)
            if b is None:
                return None  # chain broken: not a complete answer
            if type_filter is None or b.header.type in type_filter:
                out.append(b)
                taken += b.size_bytes
            cur = b.last_offset + 1
        return out

    async def timequery(self, ts: int) -> int | None:
        """First offset with max_timestamp >= ts (storage timequery)."""
        async with self._lock:
            for seg in self.segments:
                if seg.max_timestamp >= ts:
                    off = seg.first_offset_with_ts(ts)
                    if off is not None:
                        return off
            return None

    # ------------------------------------------------------------ truncate
    async def truncate(self, offset: int):
        """Drop everything at and after `offset` (suffix truncation)."""
        async with self._lock:
            honey_badger.inject_sync("storage", "log_truncate")
            self._cache_invalidate(from_offset=offset)
            keep: list[Segment] = []
            for seg in self.segments:
                if seg.dirty_offset < offset:
                    keep.append(seg)
                    continue
                if seg.base_offset >= offset:
                    seg.remove()
                    continue
                # partial: find the file position of the first batch >= offset
                blob = seg.read_from(0)
                at = 0
                new_dirty = seg.base_offset - 1
                new_max_ts = -1
                while at + INTERNAL_HEADER_SIZE <= len(blob):
                    batch, consumed = RecordBatch.decode_internal(blob, at)
                    if batch.last_offset >= offset:
                        break
                    new_dirty = batch.last_offset
                    new_max_ts = max(new_max_ts, batch.header.max_timestamp)
                    at += consumed
                seg.truncate_to_file_pos(at, new_dirty, new_max_ts)
                keep.append(seg)
            self.segments = keep
            self._committed = min(self._committed, self.offsets().dirty_offset)
            for fn in self.truncate_listeners:
                fn(offset)

    async def prefix_truncate(self, offset: int):
        """Evict whole segments below `offset` (retention / raft snapshot)."""
        async with self._lock:
            self._cache_invalidate(below_offset=offset)
            while self.segments and self.segments[0].dirty_offset < offset and (
                len(self.segments) > 1 or not self.segments[0].writable
            ):
                self.segments.pop(0).remove()
            self._start_offset = max(self._start_offset, offset)

    # ------------------------------------------------------------ compaction
    @property
    def is_compacted(self) -> bool:
        return "compact" in self.config.cleanup_policy

    def compaction_backlog(self) -> int:
        """Closed-segment bytes accumulated SINCE the last compaction pass —
        the controller's process variable (backlog_controller.h). Measured
        against the post-compaction closed-bytes baseline so steady trickle
        appends into the active segment read as zero backlog (total closed
        bytes would keep the controller pinned at max pressure forever)."""
        if not self.is_compacted:
            return 0
        if getattr(self, "_compacted_through", None) == self.offsets().dirty_offset:
            return 0
        closed = sum(s.size_bytes for s in self.segments if not s.writable)
        return max(0, closed - getattr(self, "_compacted_closed_bytes", 0))

    async def compact(self) -> tuple[int, int]:
        """Self-compact all closed segments (storage/compaction.py); no-op
        until new data has arrived since the previous pass."""
        offs = self.offsets()
        if getattr(self, "_compacted_through", None) == offs.dirty_offset:
            return 0, 0
        from redpanda_tpu.storage.compaction import compact_log

        result = await compact_log(
            self,
            delete_retention_ms=self.config.delete_retention_ms,
            max_keys_in_memory=self.config.compaction_max_keys_in_memory,
        )
        # compaction rewrote segment contents in place: cached batches for
        # dropped keys would resurrect them on a cache-served fetch
        self._cache_invalidate()
        self._compacted_through = offs.dirty_offset
        # baseline for the backlog measure: closed bytes as they stand
        # post-rewrite, so only NEW closed data counts as backlog
        self._compacted_closed_bytes = sum(
            s.size_bytes for s in self.segments if not s.writable
        )
        return result

    # ------------------------------------------------------------ retention
    async def apply_retention(self):
        cfg = self.config
        if cfg.retention_bytes is not None:
            total = sum(s.size_bytes for s in self.segments)
            while len(self.segments) > 1 and total > cfg.retention_bytes:
                seg = self.segments[0]
                total -= seg.size_bytes
                await self.prefix_truncate(seg.dirty_offset + 1)
        if cfg.retention_ms is not None:
            cutoff = int(time.time() * 1000) - cfg.retention_ms
            while len(self.segments) > 1 and self.segments[0].max_timestamp < cutoff and self.segments[0].max_timestamp >= 0:
                await self.prefix_truncate(self.segments[0].dirty_offset + 1)
