"""Host-side record-batch explode/rebuild for the engine data path.

The per-record work (varint framing) runs in native code
(native/redpanda_native.cc rp_parse_record_values / rp_frame_records) with a
Python fallback; Python only touches per-batch metadata. This is the
division of labour the whole engine is built around: Python per batch,
C per record, TPU per byte.
"""

from __future__ import annotations

import ctypes
import itertools
import threading
from dataclasses import dataclass

import numpy as np

from redpanda_tpu.compression import active_backend, compress, uncompress, uncompress_many
from redpanda_tpu.compression.codecs import ZSTD_LEVEL, ZSTD_MANY_THREADS
from redpanda_tpu.models.record import (
    INTERNAL_HEADER_SIZE, Compression, Record, RecordBatch, RecordBatchHeader,
)
from redpanda_tpu.observability import stages
from redpanda_tpu.utils.vint import decode_zigzag, encode_zigzag


def _native():
    try:
        from redpanda_tpu.native import lib

        return lib
    except Exception:
        return None


class Arena:
    """Reusable scratch buffers for the harvest path's framing crossings.

    Each launch used to allocate a fresh framing dst buffer (and offset
    arrays) only to throw it away after ``.tobytes()`` sliced the payloads
    out; at a steady tick cadence that is megabytes of allocator churn per
    launch for buffers whose size barely changes. The arena keeps a small
    free list instead: ``acquire`` hands back a previously released buffer
    when one is big enough, ``release`` returns it. Thread-safe — mesh
    harvests frame concurrently on pool workers.

    The engine owns one arena per instance (``TpuEngine.reset_arenas()``
    swaps in a fresh one for tests/bench so reuse accounting is
    deterministic)."""

    # bound the free list so a one-off giant launch cannot pin its buffers
    # forever once traffic returns to normal size
    MAX_FREE = 8

    def __init__(self, max_free: int = MAX_FREE, quantum: int = 1) -> None:
        from redpanda_tpu.coproc import lockwatch

        self._lock = lockwatch.wrap(threading.Lock(), "Arena._lock")
        self._max_free = max_free
        # requests round up to a multiple of this: sizes that wander a
        # little from launch to launch are served by one parked buffer
        self._quantum = quantum
        self._free: list[np.ndarray] = []
        self._allocs = 0
        self._reuses = 0
        self._alloc_bytes = 0
        self._trims = 0

    def acquire(self, nbytes: int) -> np.ndarray:
        """A uint8 1-D buffer of AT LEAST nbytes (callers track their own
        logical lengths; the buffer may be bigger)."""
        return self.take(nbytes)[0]

    def take(self, nbytes: int) -> tuple[np.ndarray, bool]:
        """``acquire`` that also says whether the buffer was a parked one
        (True) or had to be allocated."""
        nbytes = -(-max(nbytes, 1) // self._quantum) * self._quantum
        with self._lock:
            best = None
            for i, b in enumerate(self._free):
                if b.nbytes >= nbytes and (
                    best is None or b.nbytes < self._free[best].nbytes
                ):
                    best = i
            if best is not None:
                self._reuses += 1
                return self._free.pop(best), True
            self._allocs += 1
            self._alloc_bytes += max(nbytes, 1)
        return np.empty(max(nbytes, 1), dtype=np.uint8), False

    def release(self, buf: np.ndarray | None) -> None:
        if buf is None:
            return
        with self._lock:
            if len(self._free) < self._max_free:
                self._free.append(buf)
            # else: drop — the launch that needed it can re-allocate

    def trim(self) -> int:
        """Release every parked free-list buffer back to the allocator
        (memory-pressure hook: under a CRITICAL budget-plane signal the
        engine prefers reclaiming idle scratch over shedding work).
        Returns the number of buffers freed; in-flight buffers are
        untouched and later releases re-park as usual."""
        with self._lock:
            n = len(self._free)
            self._free.clear()
            self._trims += 1
        return n

    def stats(self) -> dict:
        with self._lock:
            return {
                "allocs": self._allocs,
                "reuses": self._reuses,
                "alloc_bytes": self._alloc_bytes,
                "free_buffers": len(self._free),
                "trims": self._trims,
            }


@dataclass
class ExplodedBatches:
    """All record values of a batch list, as offsets into one joined blob."""

    joined: bytes
    offsets: np.ndarray  # int64 [N]
    sizes: np.ndarray  # int32 [N] (null values -> 0)
    ranges: list[tuple[int, int]]  # per input batch: [start, end) in N


class _Unpooled:
    """The pool of a caller that has none: fresh memory, kept by whoever
    holds the table."""

    @staticmethod
    def acquire(nbytes: int) -> np.ndarray:
        return np.empty(max(nbytes, 1), dtype=np.uint8)

    @staticmethod
    def release(buf) -> None:
        pass


class LaunchPayloads:
    """A launch's per-batch record payloads, decompressed, as the native
    crossings take them: one address (``ptrs`` uint64 [B]) and one length
    (``lens`` int64 [B]) a batch. An uncompressed batch's entry points at
    the batch's own ``payload`` bytes (nothing is copied); a compressed
    batch's at its span of a buffer out of ``pool`` that one many-frames
    crossing filled (compression.uncompress_many), or at the ``bytes`` the
    per-batch codec returned where the codec has no such form. Reads like
    the list of payloads it replaces (``len``, ``[i]``, iteration: ``bytes``
    or a uint8 view of the span). ``release()`` gives the pooled buffers
    back: the table is dead from then on."""

    __slots__ = ("ptrs", "lens", "_held", "_bufs", "_pool")

    def __init__(self, held: list, bufs: list, pool):
        self._held = held
        self._bufs = bufs
        self._pool = pool
        n = len(held)
        self.lens = np.fromiter(map(len, held), np.int64, n)
        # a bytes object's address (the ctypes array retains every object
        # it points into; None -> NULL), then the pooled spans' own
        table = (ctypes.c_char_p * n)(
            *(h if type(h) is bytes else None for h in held)
        )
        self.ptrs = np.frombuffer(table, dtype=np.uint64) if n else np.zeros(0, np.uint64)
        for i, h in enumerate(held) if bufs else ():
            if type(h) is not bytes:
                self.ptrs[i] = h.ctypes.data

    def __len__(self) -> int:
        return len(self._held)

    def __getitem__(self, i):
        if self.ptrs is None:
            raise ValueError("LaunchPayloads used after release()")
        return self._held[i]

    def __iter__(self):
        return (self[i] for i in range(len(self)))

    def release(self) -> None:
        bufs, self._bufs, self.ptrs = self._bufs, [], None
        for buf in bufs:
            self._pool.release(buf)


def launch_payloads(
    batches: list[RecordBatch], pool=None, count=None
) -> LaunchPayloads:
    """Decompress a launch's batches and table their payloads: the ONE
    place a scanned batch is decompressed, shared by every explode below.
    All batches of one codec go through ``uncompress_many`` in a number of
    crossings that does not grow with their count, off the interpreter
    lock, into a buffer out of ``pool`` (``Arena``; None = fresh memory);
    a codec without a many-frames form, and a frame that form leaves alone
    (no stated content size, truncated, corrupt), takes ``uncompress`` a
    batch. Uncompressed batches pass through untouched. Where ``uncompress``
    raises, that is what the launch gets, with every buffer back in the
    pool. ``count(n_batches, n_crossings, bytes_in, bytes_out, seconds)``
    is called once when anything was decompressed (the engine's stage and
    counters).

    Per-batch bookkeeping is plain Python over lists, not numpy over
    arrays, on purpose: numpy hands the interpreter lock over in any
    operation on more than ~500 elements, and beside a busy event loop
    each hand-over costs up to the switch interval; the lock is dropped
    here in the codec's crossings and nowhere else."""
    pool = pool or _Unpooled
    held = [b.payload for b in batches]
    codecs = [int(b.header.compression) for b in batches]
    if not any(codecs):
        return LaunchPayloads(held, [], pool)
    t0 = stages.begin("coproc.stage.uncompress")
    bufs: list[np.ndarray] = []
    n_batches = crossings = bytes_in = bytes_out = 0
    try:
        for codec in sorted(set(codecs) - {0}):
            idx = [i for i, c in enumerate(codecs) if c == codec]
            frames = [held[i] for i in idx]
            n_batches += len(idx)
            bytes_in += sum(map(len, frames))
            many = uncompress_many(frames, codec, pool)
            if many is not None:
                buf, off, ln = many
                bufs.append(buf)
                crossings += 2  # the frames' stated sizes, the decompress
                for i, o, m in zip(idx, off.tolist(), ln.tolist()):
                    if m >= 0:
                        held[i] = buf[o : o + m]
                idx = [i for i in idx if type(held[i]) is bytes]
            for i in idx:  # no many-frames form, or a frame it left alone
                held[i] = uncompress(held[i], codec)
                crossings += 1
        bytes_out = sum(len(h) for h, c in zip(held, codecs) if c)
        out = LaunchPayloads(held, bufs, pool)
    except BaseException:
        for buf in bufs:
            pool.release(buf)
        stages.close("coproc.stage.uncompress", None, t0)
        raise
    dt = stages.close("coproc.stage.uncompress", None, t0)
    if count is not None:
        count(n_batches, crossings, bytes_in, bytes_out, dt)
    return out


def _batch_ranges(batches: list[RecordBatch]):
    """(counts int32 [B], ranges [(start, end)] in launch rows, n)."""
    per_batch = [b.header.record_count for b in batches]
    ends = list(itertools.accumulate(per_batch))
    ranges = list(zip([0] + ends[:-1], ends))
    counts = np.fromiter(per_batch, np.int32, len(per_batch))
    return counts, ranges, ends[-1] if ends else 0


def _gather_payloads(batches: list[RecordBatch], count=None):
    """Decompress + concatenate batch payloads; shared by the split and
    fused explode paths."""
    payloads = launch_payloads(batches, count=count)
    counts, ranges, n = _batch_ranges(batches)
    p_len = payloads.lens.astype(np.int32)
    p_off = np.cumsum(payloads.lens) - payloads.lens
    parts = list(payloads)
    return parts, counts, p_off, p_len, ranges, b"".join(parts), n


def explode_and_find(batches: list[RecordBatch], paths: list[str], count=None):
    """FUSED explode + find (rp_explode_find): framing parse and the
    k-path JSON walk in one native crossing and one cache-hot traversal.
    Returns (ExplodedBatches, types, vs, ve) or None when the native
    symbol is unavailable (caller runs the split stages). ``count``:
    launch_payloads'."""
    lib = _native()
    if lib is None or not getattr(lib, "has_explode_find", False) or not paths:
        return None
    _, counts, p_off, p_len, ranges, joined, n = _gather_payloads(batches, count)
    if n == 0:
        ex = ExplodedBatches(
            joined, np.zeros(0, np.int64), np.zeros(0, np.int32), ranges
        )
        k = len(paths)
        return ex, np.zeros((0, k), np.int8), np.zeros((0, k), np.int64), np.zeros((0, k), np.int64)
    off, ln, types, vs, ve = lib.explode_find(joined, p_off, p_len, counts, paths)
    ex = ExplodedBatches(joined, off, np.maximum(ln, 0), ranges)
    return ex, types, vs, ve


class StructuralParse:
    """One launch's structural-index parse: everything the fused
    extraction crossing (and the engine's bookkeeping) needs, with the
    decompressed per-batch payload buffers retained so record bytes stay
    reachable WITHOUT a joined blob. ``joined`` is populated (as a uint8
    ndarray view over the in-crossing copy) only when the caller asked
    for it — passthrough plans gather harvest output from it; projection
    plans never touch raw bytes again and skip the copy entirely."""

    __slots__ = (
        "payloads", "counts", "ranges", "joined", "val_off", "val_len",
        "types", "vs", "ve", "n",
    )

    def __init__(self, payloads, counts, ranges, joined, val_off, val_len,
                 types, vs, ve):
        self.payloads = payloads
        self.counts = counts
        self.ranges = ranges
        self.joined = joined
        self.val_off = val_off
        self.val_len = val_len
        self.types = types
        self.vs = vs
        self.ve = ve
        self.n = len(val_len)

    @property
    def sizes(self) -> np.ndarray:
        return np.maximum(self.val_len, 0)

    def exploded(self) -> ExplodedBatches:
        """The classic exploded table (requires ``joined``)."""
        return ExplodedBatches(
            self.joined, self.val_off, self.sizes, self.ranges
        )


def explode_find_structural(
    batches: list[RecordBatch], paths: list[str], need_joined: bool, count=None
) -> StructuralParse | None:
    """Structural-index fused parse (rp_explode_find2): decompressed
    payloads cross the native boundary ONCE as a pointer table — the
    Python-side b"".join copy of explode_and_find's path only happens
    in-crossing, and only when ``need_joined`` says the harvest will
    gather from the blob. Returns None when the native symbols are
    unavailable (caller runs the staged ladder). The payloads are a
    ``LaunchPayloads`` over fresh memory (no pool: the parse is retained
    for as long as the launch's columns are). ``count``: launch_payloads'."""
    lib = _native()
    if lib is None or not getattr(lib, "has_structural", False) or not paths:
        return None
    payloads = launch_payloads(batches, count=count)
    counts, ranges, n = _batch_ranges(batches)
    if n == 0:
        k = len(paths)
        return StructuralParse(
            payloads, counts, ranges,
            np.zeros(0, np.uint8) if need_joined else None,
            np.zeros(0, np.int64), np.zeros(0, np.int32),
            np.zeros((0, k), np.int8), np.zeros((0, k), np.int64),
            np.zeros((0, k), np.int64),
        )
    joined, off, ln, types, vs, ve = lib.explode_find_structural(
        payloads, counts, paths, need_joined
    )
    return StructuralParse(payloads, counts, ranges, joined, off, ln,
                           types, vs, ve)


@dataclass
class PtrExploded:
    """Pointer-table explode for the payload staging lane (ROADMAP item 1
    follow-on b): the decompressed per-batch payloads stay where they lie
    (``LaunchPayloads``: a batch's own bytes, or its span of a pooled
    buffer) and record (offset, len) stay RELATIVE to their own payload, so
    staging packs straight from each — the joined blob (and its b"".join
    copy, plus _pack_staged's second cache-cold read of it) never exists.
    ``release()`` when the launch has read its last value out of the
    payloads (packed, and framed where the launch frames from them)."""

    payloads: LaunchPayloads
    offsets: np.ndarray  # int64 [N] launch-wide, each relative to its payload
    lens: np.ndarray  # int32 [N] launch-wide (raw; -1 for null values)
    sizes: np.ndarray  # int32 [N] launch-wide, clamped >= 0
    ranges: list[tuple[int, int]]  # per input batch: [start, end) in N

    @property
    def rel_off(self) -> list[np.ndarray]:
        """``offsets`` a batch."""
        return [self.offsets[s:e] for s, e in self.ranges]

    @property
    def rel_len(self) -> list[np.ndarray]:
        """``lens`` a batch."""
        return [self.lens[s:e] for s, e in self.ranges]

    def release(self) -> None:
        self.payloads.release()


def explode_ptrs(
    batches: list[RecordBatch], pool: Arena | None = None, count=None
) -> PtrExploded | None:
    """Explode a batch list WITHOUT building the joined blob: the payloads
    decompressed by ``launch_payloads`` (into ``pool``; ``count`` is its
    hook) and every record's (offset, len) parsed in ONE crossing
    (rp_parse_many_ptrs). Returns None when the native packer or parser is
    unavailable — the classic joined-blob lane is the fallback and the
    parity oracle."""
    lib = _native()
    if (
        lib is None
        or not getattr(lib, "has_pack_rows_ptrs", False)
        or not getattr(lib, "has_parse_many_ptrs", False)
    ):
        # no library, or a stale one without the lane's packer
        # (rp_pack_rows_ptrs, pack_exploded_ptrs below) or its parser
        return None
    payloads = launch_payloads(batches, pool, count)
    counts, ranges, n = _batch_ranges(batches)
    try:
        off, ln, sizes = lib.parse_many_ptrs(payloads, counts, n)
    except BaseException:
        payloads.release()
        raise
    return PtrExploded(payloads, off, ln, sizes, ranges)


def explode_batches(batches: list[RecordBatch], count=None) -> ExplodedBatches:
    lib = _native()
    _, counts, p_off, p_len, ranges, joined, n = _gather_payloads(batches, count)
    if n == 0:
        return ExplodedBatches(
            joined, np.zeros(0, np.int64), np.zeros(0, np.int32), ranges
        )
    if lib is not None and getattr(lib, "has_parse_many", False):
        # ONE native crossing for the whole launch (not one per batch)
        off, ln = lib.parse_many(joined, p_off, p_len, counts)
    elif lib is not None:
        offs, lns = [], []
        for i in range(len(counts)):
            # a batch's payload out of the blob: a pooled span is no bytes
            payload = joined[p_off[i] : p_off[i] + p_len[i]]
            o, l = lib.parse_record_values(payload, int(counts[i]))
            offs.append(o + p_off[i])
            lns.append(l)
        off = np.concatenate(offs) if offs else np.zeros(0, np.int64)
        ln = np.concatenate(lns) if lns else np.zeros(0, np.int32)
    else:
        offs, lns = [], []
        for i in range(len(counts)):
            payload = joined[p_off[i] : p_off[i] + p_len[i]]
            o, l = _parse_record_values_py(payload, int(counts[i]))
            offs.append(o + p_off[i])
            lns.append(l)
        off = np.concatenate(offs)
        ln = np.concatenate(lns)
    return ExplodedBatches(joined, off, np.maximum(ln, 0), ranges)


def _parse_record_values_py(payload: bytes, count: int):
    off = np.empty(count, dtype=np.int64)
    ln = np.empty(count, dtype=np.int32)
    pos = 0
    for i in range(count):
        body_len, k = decode_zigzag(payload, pos)
        pos += k
        body_end = pos + body_len
        p = pos + 1  # attributes
        _, k = decode_zigzag(payload, p)
        p += k
        _, k = decode_zigzag(payload, p)
        p += k
        klen, k = decode_zigzag(payload, p)
        p += k
        if klen > 0:
            p += klen
        vlen, k = decode_zigzag(payload, p)
        p += k
        off[i] = p
        ln[i] = vlen if vlen >= 0 else -1
        pos = body_end
    return off, ln


def frame_records(rows: np.ndarray, lens: np.ndarray, keep: np.ndarray) -> tuple[bytes, int]:
    lib = _native()
    if lib is not None:
        return lib.frame_records(rows, lens, keep)
    out = bytearray()
    seq = 0
    for i in range(len(keep)):
        if not keep[i]:
            continue
        vlen = max(int(lens[i]), 0)
        body = bytearray()
        body += b"\x00"
        body += encode_zigzag(0)
        body += encode_zigzag(seq)
        body += encode_zigzag(-1)
        body += encode_zigzag(vlen)
        body += rows[i, :vlen].tobytes()
        body += encode_zigzag(0)
        out += encode_zigzag(len(body))
        out += body
        seq += 1
    return bytes(out), seq


def _range_cols(ranges: list[tuple[int, int]]):
    """(starts, ends) int64 columns of a launch's record ranges."""
    starts = np.fromiter((s for s, _ in ranges), np.int64, len(ranges))
    ends = np.fromiter((e for _, e in ranges), np.int64, len(ranges))
    return starts, ends


def _slice_framed(dst, off, ln, kept, scratch, arena: Arena | None):
    """[(payload, kept)] per range out of a native framer's dst, which
    goes back to the arena (with the undersized scratch the binding
    replaced, if it did: that can still serve a smaller launch)."""
    parts = [
        (dst[off[i] : off[i] + ln[i]].tobytes(), int(kept[i]))
        for i in range(len(off))
    ]
    if arena is not None:
        arena.release(dst)
        if dst is not scratch:
            arena.release(scratch)
    return parts


def frame_ranges(
    rows: np.ndarray,
    lens: np.ndarray,
    keep: np.ndarray,
    ranges: list[tuple[int, int]],
    arena: Arena | None = None,
) -> list[tuple[bytes, int]]:
    """Frame every [start, end) record range of a LAUNCH in one native
    crossing (rp_frame_many): [(payload, kept)] per range. The per-batch
    ctypes call overhead dominated rebuild at 32-record batches; this is
    the same loop, moved below the language boundary. ``arena`` (when
    given) supplies the reusable framing dst buffer."""
    if not ranges:
        # explicit on BOTH paths: the native branch previously fell through
        # to the Python list comprehension when ranges was empty, silently
        # taking the fallback path despite has_frame_many being true
        return []
    lib = _native()
    if lib is not None and getattr(lib, "has_frame_many", False):
        starts, ends = _range_cols(ranges)
        n, stride = rows.shape
        scratch = arena.acquire(n * (stride + 16) + 16) if arena else None
        dst, off, ln, kept = lib.frame_many(
            rows, lens, keep, starts, ends, out=scratch
        )
        return _slice_framed(dst, off, ln, kept, scratch, arena)
    return [frame_records(rows[s:e], lens[s:e], keep[s:e]) for s, e in ranges]


def _frame_gather_py(
    src, offsets, lens, keep, start: int, end: int
) -> tuple[bytes, int]:
    """Python gather framing for one range — bit-identical to
    rp_frame_gather (and to frame_records over packed rows, which the
    parity tests assert)."""
    out = bytearray()
    seq = 0
    for i in range(start, end):
        if not keep[i]:
            continue
        o = int(offsets[i])
        vlen = max(int(lens[i]), 0)
        body = bytearray()
        body += b"\x00"
        body += encode_zigzag(0)
        body += encode_zigzag(seq)
        body += encode_zigzag(-1)
        body += encode_zigzag(vlen)
        body += src[o : o + vlen]
        body += encode_zigzag(0)
        out += encode_zigzag(len(body))
        out += body
        seq += 1
    return bytes(out), seq


def frame_ranges_gather(
    src,
    offsets: np.ndarray,
    lens: np.ndarray,
    keep: np.ndarray,
    ranges: list[tuple[int, int]],
    arena: Arena | None = None,
) -> list[tuple[bytes, int]]:
    """ZERO-COPY launch framing (rp_frame_many_gather): kept records frame
    straight from ``src`` (the launch's joined blob) via per-record
    (offset, len) columns — the padded row matrix the padded path builds
    just to copy from never exists. Output is byte-identical to
    ``frame_ranges`` over rows packed from the same (offset, len) table;
    the engine picks this path only for byte-identity transforms
    (columnar passthrough, host identity)."""
    if not ranges:
        return []
    lib = _native()
    if lib is not None and getattr(lib, "has_frame_many_gather", False):
        starts, ends = _range_cols(ranges)
        n = len(offsets)
        scratch = (
            arena.acquire(int(np.maximum(lens, 0).sum()) + 16 * n + 16)
            if arena
            else None
        )
        dst, off, ln, kept = lib.frame_many_gather(
            src, offsets, lens, keep, starts, ends, out=scratch
        )
        return _slice_framed(dst, off, ln, kept, scratch, arena)
    return [
        _frame_gather_py(src, offsets, lens, keep, s, e) for s, e in ranges
    ]


def frame_ranges_gather_ptrs(
    payloads,
    offsets: np.ndarray,
    lens: np.ndarray,
    keep: np.ndarray,
    ranges: list[tuple[int, int]],
    arena: Arena | None = None,
) -> list[tuple[bytes, int]]:
    """frame_ranges_gather over a pointer table
    (rp_frame_many_gather_ptrs): range r is one input batch, and its
    records' (offset, len) are relative to that batch's own retained
    payload ``payloads[r]`` (PtrExploded's LaunchPayloads, or a list of
    ``bytes``) — a filter-only payload
    launch frames its kept values from the bytes the pack stage just read,
    with no joined blob and no result matrix. Byte-identical to
    ``frame_ranges_gather`` over the joined payloads."""
    if not ranges:
        return []
    lib = _native()
    if lib is not None and getattr(lib, "has_frame_many_gather_ptrs", False):
        starts, ends = _range_cols(ranges)
        n = len(offsets)
        scratch = (
            arena.acquire(int(np.maximum(lens, 0).sum()) + 16 * n + 16)
            if arena
            else None
        )
        dst, off, ln, kept = lib.frame_many_gather_ptrs(
            payloads, offsets, lens, keep, starts, ends, out=scratch
        )
        return _slice_framed(dst, off, ln, kept, scratch, arena)
    return [
        _frame_gather_py(payloads[r], offsets, lens, keep, s, e)
        for r, (s, e) in enumerate(ranges)
    ]


def pack_exploded_ptrs(
    pe: PtrExploded, dst: np.ndarray, row_stride: int,
    rows: np.ndarray | None = None,
) -> None:
    """Fill a payload launch's staging matrix ``dst`` [n_pad, row_stride +
    8] from a pointer table in ONE native crossing (rp_pack_rows_ptrs):
    values, zeroed tails, LE32 lengths (0 for a null value and for one
    wider than ``row_stride``), zero meta bytes, cleared pad rows. ``dst``
    may be a reused matrix holding anything. ``rows``: the table's rows
    this matrix holds (row numbers, ascending: one part of a launch staged
    by width class), None for all of them. explode_ptrs hands out a table
    only when the library has the symbol."""
    starts, ends = _range_cols(pe.ranges)
    _native().pack_rows_ptrs(
        pe.payloads, pe.offsets, pe.sizes, starts, ends, dst, row_stride, rows
    )


def frame_exploded_gather(
    ex, keep: np.ndarray, ranges: list[tuple[int, int]],
    arena: Arena | None = None,
) -> list[tuple[bytes, int]]:
    """Gather-frame a launch's kept records from whichever exploded table
    it holds: per-batch payload buffers (PtrExploded) or the joined blob
    (ExplodedBatches). Same bytes either way."""
    if isinstance(ex, PtrExploded):
        return frame_ranges_gather_ptrs(
            ex.payloads, ex.offsets, ex.sizes, keep, ranges, arena=arena
        )
    return frame_ranges_gather(
        ex.joined, ex.offsets, ex.sizes, keep, ranges, arena=arena
    )


def build_output_batch(
    source: RecordBatch,
    payload: bytes,
    kept: int,
    *,
    compress_threshold: int = 512,
    codec: Compression = Compression.zstd,
) -> RecordBatch | None:
    """Seal a framed payload into a materialized output batch.

    Mirrors the reference's write side (script_context_backend.cc:40-68):
    term reset, zstd recompression above a size threshold, fresh CRCs.
    Returns None when no record survives the transform.
    """
    if kept == 0:
        return None
    attrs = 0
    if len(payload) >= compress_threshold and codec != Compression.none:
        payload = compress(payload, codec)
        attrs = int(codec)
    hdr = RecordBatchHeader(
        base_offset=0,  # assigned by the materialized log appender
        type=source.header.type,
        attrs=attrs,
        last_offset_delta=kept - 1,
        first_timestamp=source.header.first_timestamp,
        max_timestamp=source.header.max_timestamp,
        record_count=kept,
        term=0,
    )
    batch = RecordBatch(hdr, payload)
    batch.reseal()
    return batch


# A slot of build_output_batches' result that build_output_batch has to fill.
UNSEALED = object()
_NO_DST = np.empty(1, dtype=np.uint8)


def build_output_batches(
    jobs: list[tuple],
    *,
    compress_threshold: int = 512,
    codec: Compression = Compression.zstd,
    pool=_Unpooled,
) -> list | None:
    """``build_output_batch`` over a launch's ``(source, payload, kept)``
    jobs in ONE native crossing that holds no interpreter lock
    (rp_seal_many: the compression and both header CRCs of every batch, on
    up to four threads by the job count), its frames written into one
    buffer out of ``pool`` (``acquire(nbytes)`` / ``release(buf)``) that
    goes back before this returns. One slot a job: the sealed batch, None
    where ``kept == 0``, ``UNSEALED`` for a job the crossing left alone,
    which ``build_output_batch`` seals or refuses as it always did. The
    batches are what ``build_output_batch`` makes, down to both CRCs,
    except that a Zstd frame's bytes are the host libzstd's (same level,
    content size stated). ``None`` when there is no such crossing here: no
    native library or no libzstd under it, a codec other than zstd / none,
    a compression backend other than the host's."""
    lib = _native()
    if (
        lib is None
        or not getattr(lib, "has_seal_many", False)
        or codec not in (Compression.zstd, Compression.none)
        or active_backend() != "host"
    ):
        return None
    # plain Python up to the crossing: numpy hands the interpreter lock
    # over for any operation on more than ~500 elements, and beside a busy
    # event loop every hand-over costs up to the switch interval (PERF.md
    # section 6, PR 33); the range checks are the crossing's own
    payloads, kepts, types, first_ts, max_ts = [], [], [], [], []
    to_compress = n_frames = 0
    zstd = codec == Compression.zstd
    for source, payload, kept in jobs:
        h = source.header
        payloads.append(payload)
        kepts.append(kept)
        types.append(h.type)
        first_ts.append(h.first_timestamp)
        max_ts.append(h.max_timestamp)
        if zstd and len(payload) >= compress_threshold:
            to_compress += len(payload)
            n_frames += 1
    # room for ZSTD_compressBound of every frame: len + len / 256 + <= 64
    dst = (
        pool.acquire(to_compress + (to_compress >> 8) + 64 * n_frames + 64)
        if n_frames else _NO_DST
    )
    try:
        sealed = lib.seal_many(
            payloads, np.array(kepts, np.int32), np.array(types, np.int8),
            np.array(first_ts, np.int64), np.array(max_ts, np.int64), dst,
            threshold=compress_threshold, codec=int(codec), level=ZSTD_LEVEL,
            n_threads=ZSTD_MANY_THREADS,
        )
        if sealed is None:
            return None
        frames = memoryview(dst)
        out = []
        for (_, payload, kept), off, ln, attrs, crc, header_crc, btype, ts0, ts1 in zip(
            jobs, *(a.tolist() for a in sealed), types, first_ts, max_ts
        ):
            if kept == 0:
                out.append(None)
            elif ln < 0:
                out.append(UNSEALED)
            else:
                if attrs:
                    payload = frames[off : off + ln].tobytes()
                out.append(RecordBatch(RecordBatchHeader(
                    header_crc, INTERNAL_HEADER_SIZE + ln, 0, btype, crc, attrs,
                    kept - 1, ts0, ts1, -1, -1, -1, kept, 0,
                ), payload))
        return out
    finally:
        if n_frames:
            pool.release(dst)


def rebuild_batch(
    source: RecordBatch,
    rows: np.ndarray,
    lens: np.ndarray,
    keep: np.ndarray,
    *,
    compress_threshold: int = 512,
    codec: Compression = Compression.zstd,
) -> RecordBatch | None:
    """Single-batch rebuild (frame + seal); the engine's launch path uses
    frame_ranges + build_output_batch to amortize the native crossing."""
    payload, kept = frame_records(rows, lens, keep)
    return build_output_batch(
        source, payload, kept,
        compress_threshold=compress_threshold, codec=codec,
    )
