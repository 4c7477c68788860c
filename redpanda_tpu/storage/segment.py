"""Log segment: one append-only data file + a sparse offset index.

Capability parity with the reference's storage/segment.h +
segment_appender.h (chunked buffered writes, background flush) +
segment_index.h (sparse index sampled every `index_step` bytes). The
on-disk payload is the internal batch layout (61-byte LE header + payload,
models/record.py), so a recovery scan is a straight walk of
[header][payload] frames whose CRCs can be validated in one batched device
kernel (see recovery.py).

File naming: <base_offset>-<term>-v1.log / .index under the ntp directory.
"""

from __future__ import annotations

import os
import struct
from dataclasses import dataclass

from redpanda_tpu import native
from redpanda_tpu.models.record import (
    INTERNAL_HEADER_SIZE,
    CorruptBatchError,
    RecordBatch,
    RecordBatchHeader,
    RecordBatchType,
)
from redpanda_tpu.observability.probes import storage_read_crossing_batches_hist
from redpanda_tpu.storage import file_sanitizer
from redpanda_tpu.storage.readers_cache import ReadCursor

INDEX_STEP = 32 * 1024
# What a sequential reader (a read that continues from a cursor) takes from
# the file at once; the unread rest travels in its cursor. 256 KiB is four
# catch-up reads of two ~32 KB batches: of 128 / 256 / 512 KiB it cost the
# least per read on the v5e host (PERF.md §6, PR 25).
READ_AHEAD_BYTES = 256 * 1024
_INDEX_ENTRY = struct.Struct("<IQq")  # rel_offset u32, file_pos u64, ts i64
_INDEX_MAGIC = b"RPXI\x02"
_INDEX_FOOTER = struct.Struct("<qq")  # dirty_offset i64, max_timestamp i64
# what the scan crossing is told of batch types: the enum's members by
# number, as a table and as the bits of a mask (a type is one signed byte)
_TYPE_OF = {int(t): t for t in RecordBatchType}
_KNOWN_TYPES = sum(1 << t for t in _TYPE_OF)
_NO_MAX_OFFSET = (1 << 63) - 1


@dataclass
class IndexEntry:
    rel_offset: int
    file_pos: int
    timestamp: int


class SegmentIndex:
    """Sparse offset -> file position index, rebuilt on demand if missing."""

    def __init__(self, path: str, base_offset: int):
        self.path = path
        self.base_offset = base_offset
        self.entries: list[IndexEntry] = []
        self._acc_bytes = 0

    def maybe_track(self, batch_header: RecordBatchHeader, file_pos: int):
        self._acc_bytes += batch_header.size_bytes
        if not self.entries or self._acc_bytes >= INDEX_STEP:
            self.entries.append(
                IndexEntry(
                    batch_header.base_offset - self.base_offset,
                    file_pos,
                    batch_header.first_timestamp,
                )
            )
            self._acc_bytes = 0

    def lookup(self, offset: int) -> int:
        """Largest indexed file position whose batch base_offset <= offset."""
        rel = offset - self.base_offset
        pos = 0
        for e in self.entries:
            if e.rel_offset <= rel:
                pos = e.file_pos
            else:
                break
        return pos

    def lookup_time(self, ts: int) -> int:
        pos = 0
        for e in self.entries:
            if e.timestamp <= ts:
                pos = e.file_pos
            else:
                break
        return pos

    def persist(self, dirty_offset: int = -1, max_timestamp: int = -1):
        with open(self.path, "wb") as f:
            f.write(_INDEX_MAGIC)
            f.write(_INDEX_FOOTER.pack(dirty_offset, max_timestamp))
            for e in self.entries:
                f.write(_INDEX_ENTRY.pack(e.rel_offset, e.file_pos, e.timestamp))

    def load(self) -> tuple[int, int] | None:
        """Returns (dirty_offset, max_timestamp) on success, else None."""
        try:
            with open(self.path, "rb") as f:
                blob = f.read()
        except FileNotFoundError:
            return None
        hdr = len(_INDEX_MAGIC) + _INDEX_FOOTER.size
        if not blob.startswith(_INDEX_MAGIC) or len(blob) < hdr:
            return None
        dirty, max_ts = _INDEX_FOOTER.unpack_from(blob, len(_INDEX_MAGIC))
        self.entries = []
        body = blob[hdr:]
        if len(body) % _INDEX_ENTRY.size:
            return None
        for i in range(0, len(body), _INDEX_ENTRY.size):
            rel, pos, ts = _INDEX_ENTRY.unpack_from(body, i)
            self.entries.append(IndexEntry(rel, pos, ts))
        return dirty, max_ts

    def truncate_at_pos(self, file_pos: int):
        self.entries = [e for e in self.entries if e.file_pos < file_pos]


class Segment:
    """One data file; open for append only when it is the active segment."""

    APPEND_BUF_LIMIT = 1 << 20  # flush the write buffer at 1 MiB

    def __init__(self, dir_path: str, base_offset: int, term: int):
        self.dir = dir_path
        self.base_offset = base_offset
        self.term = term
        stem = f"{base_offset}-{term}-v1"
        self.data_path = os.path.join(dir_path, stem + ".log")
        self.index = SegmentIndex(os.path.join(dir_path, stem + ".index"), base_offset)
        self._file = None
        # the ONE read handle of the data file, opened by the first read
        # and kept: reads are os.pread on it, no open/seek/close each
        self._rfile = None
        self._buf = bytearray()
        self.size_bytes = 0
        self.dirty_offset = base_offset - 1  # highest appended offset
        self.max_timestamp = -1

    # ------------------------------------------------------------ lifecycle
    def create(self):
        self._file = file_sanitizer.maybe_wrap(
            open(self.data_path, "wb"), self.data_path
        )
        return self

    def open_existing(self, writable: bool):
        self.size_bytes = os.path.getsize(self.data_path)
        if writable:
            self._file = file_sanitizer.maybe_wrap(
                open(self.data_path, "ab"), self.data_path
            )
        loaded = self.index.load()
        if loaded is None:
            self.rebuild_index()
        else:
            self.dirty_offset, self.max_timestamp = loaded
            if self.dirty_offset < self.base_offset:
                # stale/pre-footer index: derive state from the data file
                self.rebuild_index()
        return self

    @property
    def writable(self) -> bool:
        return self._file is not None

    # ------------------------------------------------------------ append
    def append(self, batch: RecordBatch) -> None:
        self.track(batch)
        self.write_tracked(batch.encode_internal())

    def track(self, batch: RecordBatch) -> None:
        """Account for `batch` as the next frame of this segment: index,
        size, dirty offset, newest timestamp. Its bytes are owed:
        `write_tracked` takes the frames of one or many tracked batches,
        in their order, before anything reads or closes the segment."""
        assert self._file is not None, "segment not writable"
        header = batch.header
        # this batch's file position == bytes appended so far (incl. buffered)
        self.index.maybe_track(header, self.size_bytes)
        self.size_bytes += header.size_bytes
        self.dirty_offset = header.base_offset + header.last_offset_delta
        if header.max_timestamp > self.max_timestamp:
            self.max_timestamp = header.max_timestamp

    def write_tracked(self, frames) -> None:
        """Take the frames of the batches tracked since the last call. A
        `bytearray` is the caller's to give away: an empty buffer adopts it
        and copies nothing (an acks=all produce, flushed after every
        append, always finds the buffer empty)."""
        if not self._buf and type(frames) is bytearray:
            self._buf = frames
        else:
            self._buf += frames
        if len(self._buf) >= self.APPEND_BUF_LIMIT:
            self.flush_buffer()

    def flush_buffer(self):
        if self._buf and self._file:
            self._file.write(self._buf)
            self._buf.clear()

    def fsync(self):
        self.flush_buffer()
        if self._file:
            self._file.flush()
            os.fsync(self._file.fileno())

    def release_appender(self):
        """Close for writing (segment roll); persists the index."""
        if self._file:
            self.flush_buffer()
            self._file.flush()
            os.fsync(self._file.fileno())
            self._file.close()
            self._file = None
        self.index.persist(self.dirty_offset, self.max_timestamp)

    def release_reader(self):
        """Close the read descriptor; the next read opens a new one. Called
        when the file goes away or is replaced (close, remove, compaction's
        rewrite) and when the readers cache bounds the open descriptors."""
        if self._rfile is not None:
            self._rfile.close()
            self._rfile = None

    def close(self):
        self.release_appender()
        self.release_reader()

    # ------------------------------------------------------------ read
    def _pread(self, n: int, pos: int) -> bytes:
        """Up to `n` bytes at `pos` of the data file, every appended frame
        visible (the append buffer is flushed first)."""
        self.flush_buffer()
        if self._file:
            self._file.flush()
        if self._rfile is None:
            self._rfile = file_sanitizer.maybe_wrap(
                open(self.data_path, "rb", buffering=0), self.data_path
            )
        return os.pread(self._rfile.fileno(), n, pos)

    def read_from(self, file_pos: int, max_len: int | None = None) -> bytes:
        self.flush_buffer()
        if self._file:
            self._file.flush()
        with open(self.data_path, "rb") as f:
            f.seek(file_pos)
            return f.read() if max_len is None else f.read(max_len)

    def scan(
        self,
        start_offset: int,
        max_bytes: int,
        *,
        type_filter=None,
        max_offset: int | None = None,
        cursor: ReadCursor | None = None,
        read_ahead: bool = False,
    ) -> tuple[list[RecordBatch], ReadCursor, int]:
        """Batches overlapping [start_offset, max_offset], bounded by size,
        with cursor support (readers_cache.h continuation).

        `cursor` (a cached read cursor of this segment) holds the exact
        file position of a frame boundary — the sparse-index lookup and the
        decode-and-skip scan up to `start_offset` are bypassed — and the
        window its reader left unread: frames are decoded out of that
        first, and the file is read only when it holds no whole next frame.
        `read_ahead`: the caller continues a sequential read (it adopted a
        cursor); a request under READ_AHEAD_BYTES then takes that much from
        the file at once. A cold read, and a request at or over it, read
        about what they asked for and keep only what they over-read.

        Returns (batches, next_cursor, file_reads). next_cursor's position
        is just past the last KEPT batch (or the scan start when nothing
        was kept) — the cursor for the follow-up read at
        `batches[-1].last_offset + 1` — with the window's unread rest.
        Frames consumed but filtered out AFTER the last kept batch are
        deliberately not covered by the cursor, so a continuation under a
        different type_filter re-scans them instead of silently skipping.

        The frames are walked a window a native crossing where the library
        has the entry (`_walk_windows`), else a frame at a time
        (`_walk_frames`): one result either way.
        """
        if read_ahead and max_bytes < READ_AHEAD_BYTES:
            chunk = READ_AHEAD_BYTES
        else:
            # bounded reads: ~max_bytes per call, never the segment tail
            chunk = max(min(max_bytes * 2, 8 << 20), 1 << 16)
        if cursor is not None:
            pos = cursor.file_pos
            frames = _FrameReader(self, pos, chunk, cursor.window, cursor.window_pos)
        else:
            pos = self.index.lookup(start_offset)
            frames = _FrameReader(self, pos, chunk)
        out: list[RecordBatch] = []
        lib = native.lib
        if lib is not None and lib.has_scan_internal_frames:
            walk = self._walk_windows
        else:
            walk = self._walk_frames
        # file offset just past the last KEPT batch
        kept_end = walk(frames, out, pos, start_offset, max_bytes, type_filter, max_offset)
        # the unread rest travels on, unless the window moved past kept_end
        # (filtered frames) or a large request left more than a reader's own
        window, window_pos = frames.buf, frames.base
        if not (
            len(window) <= 2 * READ_AHEAD_BYTES
            and window_pos <= kept_end < window_pos + len(window)
        ):
            window, window_pos = b"", 0
        return (
            out,
            ReadCursor(self.base_offset, kept_end, window, window_pos),
            frames.file_reads,
        )

    def _walk_frames(
        self, frames, out, kept_end, start_offset, max_bytes, type_filter, max_offset
    ) -> int:
        """`scan`'s walk a frame at a time: the batches it keeps go to
        `out`; returns the file position just past the last of them, or
        `kept_end` as given. Serves where the native library lacks the
        crossing of `_walk_windows`."""
        taken = 0
        for batch, end_pos in frames:
            if max_offset is not None and batch.base_offset > max_offset:
                break  # NOT consumed: cursor stays before this frame
            if batch.last_offset < start_offset:
                continue
            if type_filter is not None and batch.header.type not in type_filter:
                continue
            # Runtime term context comes from the segment (the packed
            # header carries no term; the reference derives it the same
            # way, from the raft configuration tracking / segment naming)
            batch.header.term = self.term
            out.append(batch)
            storage_read_crossing_batches_hist.record(1)
            kept_end = end_pos
            taken += batch.size_bytes
            if taken >= max_bytes:
                break
        return kept_end

    def _walk_windows(
        self, frames, out, kept_end, start_offset, max_bytes, type_filter, max_offset
    ) -> int:
        """`_walk_frames` in ONE native crossing a window
        (`native.scan_internal_frames`) where that takes a decode, a header
        CRC and three rules a frame in Python: the crossing checks every
        whole frame of the window (size, type, header CRC) and holds it to
        the same three rules in the same order, and the batches are made
        here from the rows it filled, the payload one slice a batch as
        `decode_internal` takes it. Whatever is not a whole sound frame is
        the reader's: a window that ends inside a frame is read anew
        (`whole_frame`) or raises as it does there, and a frame that is not
        sound is decoded the loop's way for its error. The same batches,
        cursor, file reads and exceptions; a one-frame read is cheaper this
        way too (PERF.md section 5, step 0 of ISSUE 48), so no frame count
        picks the road."""
        if type_filter is None:
            mask = _KNOWN_TYPES
        else:
            mask = sum(1 << t for t in type_filter if t in _TYPE_OF)
        if max_offset is None:
            max_offset = _NO_MAX_OFFSET
        lib = native.lib
        term = self.term
        taken = 0
        while True:
            buf = frames.buf
            status, frames.at, end, took, rows = lib.scan_internal_frames(
                buf, frames.at, start_offset, max_offset, max_bytes - taken,
                _KNOWN_TYPES, mask,
            )
            if end >= 0:
                n = len(out)
                for (at, header_crc, size, base, btype, crc, attrs, last_delta,
                     first_ts, max_ts, pid, epoch, seq, count) in rows:
                    out.append(
                        RecordBatch(
                            RecordBatchHeader(
                                header_crc, size, base, _TYPE_OF[btype], crc,
                                attrs, last_delta, first_ts, max_ts, pid,
                                epoch, seq, count, term,
                            ),
                            buf[at + INTERNAL_HEADER_SIZE : at + size],
                        )
                    )
                storage_read_crossing_batches_hist.record(len(out) - n)
                kept_end = frames.base + end
                taken += took
            if status == 0:
                return kept_end
            if status == 1:
                if not frames.whole_frame():
                    return kept_end
            elif status == 2:
                RecordBatch.decode_internal(buf, frames.at)
                raise CorruptBatchError(
                    f"unsound batch frame ({self.data_path} pos {frames.base + frames.at})"
                )

    def first_offset_with_ts(self, ts: int) -> int | None:
        """First batch offset whose max_timestamp >= ts (index-accelerated).

        Bounded chunked reads via the shared frame iterator: a timequery
        that resolves near the index point must not slurp the rest of the
        segment file; corruption raises loudly like every read path."""
        pos = self.index.lookup_time(ts)
        for batch, _end in _FrameReader(self, pos, 1 << 20):
            if batch.header.max_timestamp >= ts:
                return batch.base_offset
        return None

    def rebuild_index(self, blob: bytes | None = None):
        """Recreate the sparse index (and dirty/max_ts) by scanning the data."""
        self.index.entries = []
        self.index._acc_bytes = 0
        self.dirty_offset = self.base_offset - 1
        self.max_timestamp = -1
        if blob is None:
            blob = self.read_from(0)
        at = 0
        while at + INTERNAL_HEADER_SIZE <= len(blob):
            try:
                batch, consumed = RecordBatch.decode_internal(blob, at)
            except Exception:
                break
            self.index.maybe_track(batch.header, at)
            self.dirty_offset = batch.last_offset
            self.max_timestamp = max(self.max_timestamp, batch.header.max_timestamp)
            at += consumed

    def truncate_to_file_pos(self, file_pos: int, new_dirty: int, new_max_ts: int = -1):
        self.flush_buffer()
        was_writable = self._file is not None
        if self._file:
            self._file.close()
        with open(self.data_path, "r+b") as f:
            f.truncate(file_pos)
        self.size_bytes = file_pos
        self.dirty_offset = new_dirty
        self.max_timestamp = new_max_ts
        self.index.truncate_at_pos(file_pos)
        if was_writable:
            self._file = file_sanitizer.maybe_wrap(
                open(self.data_path, "ab"), self.data_path
            )

    def remove(self):
        self.release_appender()
        self.release_reader()
        for p in (self.data_path, self.index.path):
            try:
                os.remove(p)
            except FileNotFoundError:
                pass

    def __repr__(self):
        return f"Segment(base={self.base_offset}, term={self.term}, size={self.size_bytes})"


class _FrameReader:
    """Iterator of (batch, end_file_pos) over a segment's frames from file
    position `pos`, decoded out of an immutable window of the file that is
    read anew `chunk` bytes at a time (ONE copy per payload: the slice that
    becomes `RecordBatch.payload`). `window` / `window_pos`: bytes of the
    file already in hand, from a read cursor. After the last `next()`,
    `buf` / `base` are the window as it stands, for the next cursor.

    A frame cut at EOF raises CorruptBatchError: appends are whole-frame
    and recovery truncates torn tails at open, so a partial frame is
    corruption, never a legitimate state."""

    __slots__ = ("seg", "chunk", "buf", "base", "at", "file_reads")

    def __init__(
        self, seg: Segment, pos: int, chunk: int, window: bytes = b"", window_pos: int = 0
    ):
        self.seg = seg
        self.chunk = chunk
        if window_pos <= pos < window_pos + len(window):
            self.buf, self.base, self.at = window, window_pos, pos - window_pos
        else:
            self.buf, self.base, self.at = b"", pos, 0
        self.file_reads = 0

    def __iter__(self):
        return self

    def _refill(self, need: int) -> bool:
        """Read the window anew from the next frame's boundary, at least
        `need` bytes if the file has them; False when it has no more than
        the window already held. The consumed front and the cut frame at
        the old window's end are let go (re-read: no concatenation, so no
        second copy of a window)."""
        seg = self.seg
        pos = self.base + self.at
        # a corrupt size field must not size the read: the file's own length
        # (as the segment accounts it) bounds what one call asks for
        n = min(max(self.chunk, need), max(self.chunk, seg.size_bytes - pos))
        more = seg._pread(n, pos)
        self.file_reads += 1
        if len(more) <= len(self.buf) - self.at:
            return False
        self.buf, self.base, self.at = more, pos, 0
        return True

    def whole_frame(self) -> bool:
        """Have the window hold the next frame whole, read anew where it
        does not; False at the file's end."""
        while True:
            have = len(self.buf) - self.at
            if have < INTERNAL_HEADER_SIZE:
                if self._refill(INTERNAL_HEADER_SIZE):
                    continue
                if have:
                    raise CorruptBatchError(
                        f"partial batch header at EOF ({self.seg.data_path}"
                        f" pos {self.base + self.at})"
                    )
                return False
            frame_len = RecordBatch.peek_size(self.buf, self.at)
            if have < frame_len:
                if self._refill(frame_len):
                    continue
                raise CorruptBatchError(
                    f"batch frame overruns EOF ({self.seg.data_path} pos "
                    f"{self.base + self.at}, size_bytes={frame_len})"
                )
            return True

    def __next__(self) -> tuple[RecordBatch, int]:
        if not self.whole_frame():
            raise StopIteration
        batch, consumed = RecordBatch.decode_internal(self.buf, self.at)
        self.at += consumed
        return batch, self.base + self.at
