"""Positioned-reader cache for sequential fetch continuation.

Reference: storage/readers_cache.h:36 — `disk_log_impl` keeps a per-log
cache of live `log_reader`s keyed by their next read position; a fetch
whose start offset matches a cached reader's position adopts it instead of
re-opening and re-seeking, and truncation/compaction/start-offset moves
evict affected readers.

Our readers are not long-lived objects (each DiskLog.read decodes from a
file position), so the cached thing is the *cursor*: next_offset →
(segment base, exact file position just past the last decoded frame, and
the unread remainder of the last window the reader took from the file).
A continuation read decodes its frames out of that window, skipping the
sparse-index lookup, the decode-and-skip scan from the index point AND the
file: the segment (which keeps its read descriptor open) is touched once
per window, not once per read. Cursors at the log tail stay valid across
appends — the next frame lands exactly at the cached position, and a
window that ended at what was EOF is merely exhausted, so steady-state
sequential consumers never re-scan.

A window is an immutable snapshot of a byte range of one segment file.
The read that continues from a cursor passes its window on to the cursor
it stores; the consumed cursor keeps its position only (a second reader at
the same offset reads the file). Memory: a window is at most twice
`segment.READ_AHEAD_BYTES` (a longer one is not kept), and all windows
together hold at most `MAX_WINDOW_BYTES`: past it the least recently used cursors give
their windows up and keep their positions. Descriptors: at most
`MAX_OPEN_READERS` segments keep theirs open, least recently read first out.

Invalidation (DiskLog mirrors its batch-cache hooks):
- truncate(offset): drop cursors with next_offset >= offset (their position
  may now be past EOF or point into rewritten bytes), and the windows of
  the log's other cursors (read-ahead may reach past the cut)
- prefix_truncate(offset): drop cursors below the new start offset
- compaction (in-place segment rewrite): drop the log's cursors entirely
- close/remove: drop the log's cursors entirely
"""

from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass

# bytes all cursors' windows may hold together (worst case, stated: the
# entry bound alone would allow max_entries x a window)
MAX_WINDOW_BYTES = 32 << 20
# segments that may keep their read descriptor open at once, over all logs
MAX_OPEN_READERS = 256


@dataclass(frozen=True)
class ReadCursor:
    segment_base: int  # base offset of the segment the position lies in
    file_pos: int  # byte position of the next frame within that segment
    # bytes of the segment file from `window_pos` on, read ahead by the
    # read that stored this cursor; `file_pos` lies inside it or at its end
    window: bytes = b""
    window_pos: int = 0  # file position of window[0]


class ReadersCache:
    """Process-wide LRU of read cursors, shared by all managed logs."""

    def __init__(self, max_entries: int = 256, max_window_bytes: int = MAX_WINDOW_BYTES):
        self.max_entries = max_entries
        self.max_window_bytes = max_window_bytes
        # (log_key, next_offset) -> ReadCursor, oldest first
        self._lru: "OrderedDict[tuple[int, int], ReadCursor]" = OrderedDict()
        self.hits = 0
        self.misses = 0
        # scanned reads that decoded every frame out of a cursor's window,
        # and preads of segment files (DiskLog.read counts, note_read)
        self.window_reads = 0
        self.file_reads = 0
        self.window_bytes = 0  # held now, by all windows
        # segments whose read descriptor a read through this cache opened
        # or used, least recently used first
        self._open: "OrderedDict[object, None]" = OrderedDict()

    def get(self, log_key: int, next_offset: int) -> ReadCursor | None:
        cur = self._lru.get((log_key, next_offset))
        if cur is None:
            self.misses += 1
            return None
        self._lru.move_to_end((log_key, next_offset))
        self.hits += 1
        return cur

    def put(
        self,
        log_key: int,
        next_offset: int,
        cursor: ReadCursor,
        *,
        consumed: int | None = None,
    ) -> None:
        """`consumed`: the offset whose cursor this read continued from; its
        window has passed to `cursor`, its position stays."""
        if consumed is not None:
            self._strip((log_key, consumed))
        key = (log_key, next_offset)
        self._drop(key)
        self._lru[key] = cursor
        self.window_bytes += len(cursor.window)
        while len(self._lru) > self.max_entries:
            self._drop(next(iter(self._lru)))
        if self.window_bytes > self.max_window_bytes:
            for k in list(self._lru):
                self._strip(k)
                if self.window_bytes <= self.max_window_bytes:
                    break

    def _drop(self, key: tuple[int, int]) -> None:
        cur = self._lru.pop(key, None)
        if cur is not None:
            self.window_bytes -= len(cur.window)

    def _strip(self, key: tuple[int, int]) -> None:
        cur = self._lru.get(key)
        if cur is not None and cur.window:
            self.window_bytes -= len(cur.window)
            self._lru[key] = ReadCursor(cur.segment_base, cur.file_pos)

    def reader_opened(self, segment) -> None:
        """`segment` read its file: past MAX_OPEN_READERS the segment that
        did so longest ago closes its descriptor (its next read reopens)."""
        self._open[segment] = None
        self._open.move_to_end(segment)
        while len(self._open) > MAX_OPEN_READERS:
            self._open.popitem(last=False)[0].release_reader()

    def note_read(self, file_reads: int) -> None:
        if file_reads:
            self.file_reads += file_reads
        else:
            self.window_reads += 1

    def invalidate(
        self,
        log_key: int,
        *,
        from_offset: int | None = None,
        below_offset: int | None = None,
    ) -> None:
        """No range args = drop every cursor for the log."""
        for key in [k for k in self._lru if k[0] == log_key]:
            off = key[1]
            if from_offset is not None:
                # a cursor at exactly `from_offset` points at the first
                # truncated byte — the position is stale too; drop >= hence.
                # One below it keeps its position, not its read-ahead
                if off >= from_offset:
                    self._drop(key)
                else:
                    self._strip(key)
            elif below_offset is not None:
                if off < below_offset:
                    self._drop(key)
            else:
                self._drop(key)

    def stats(self) -> dict[str, int]:
        return {
            "hits": self.hits,
            "misses": self.misses,
            "entries": len(self._lru),
            "window_reads": self.window_reads,
            "file_reads": self.file_reads,
            "window_bytes": self.window_bytes,
        }
