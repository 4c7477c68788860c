"""ctypes loader for the native runtime helpers (native/redpanda_native.cc).

Builds on demand with `make` the first time it is imported. Callers still
tolerate `lib is None` (pure numpy twins exist for every entry point and
tests exercise them), but a failed build is never silent: see ``status()``.
"""

from __future__ import annotations

import contextlib
import ctypes
import fcntl
import logging
import os
import struct
import subprocess
import typing

import numpy as np

logger = logging.getLogger("rptpu.native")

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), "native")
_SO = os.path.join(_NATIVE_DIR, "libredpanda_native.so")


def _pack_paths(paths: list[str]):
    """Paths -> (blob, offsets, lens, k) — the ONE place that defines the
    path-table layout both rp_find_multi and rp_explode_find consume."""
    k = len(paths)
    encoded = [p.encode() for p in paths]
    blob = b"".join(encoded)
    path_off = np.zeros(k, dtype=np.int32)
    path_len = np.fromiter((len(e) for e in encoded), np.int32, k)
    if k:
        np.cumsum(path_len[:-1], out=path_off[1:])
    return blob, path_off, path_len, k


def _gather_dst_cap(lens: np.ndarray, n: int) -> int:
    """Worst-case framed payload size for n records with value lengths
    `lens`: value bytes plus ≤16 bytes of varint framing per record (the
    same margin rp_frame_records has always used)."""
    return int(np.maximum(lens, 0).sum()) + 16 * n + 16


def _take_scratch(out: np.ndarray | None, cap: int) -> np.ndarray:
    """Use the caller's scratch buffer when it fits, else allocate. The
    arena path hands the SAME buffer back launch after launch; a launch
    bigger than everything before it simply allocates fresh."""
    if (
        out is not None
        and out.dtype == np.uint8
        and out.ndim == 1
        and out.nbytes >= cap
        and out.flags["C_CONTIGUOUS"]
    ):
        return out
    return np.empty(max(cap, 1), dtype=np.uint8)


def _check_ranges(starts, ends, n: int, what: str) -> None:
    """The framers' C walks are unchecked: out-of-bounds or overlapping
    [start, end) ranges must be a ValueError here, not a heap write."""
    if len(ends) != len(starts):
        raise ValueError("starts/ends length mismatch")
    if len(starts) and (
        (starts > ends).any()
        or starts.min() < 0
        or ends.max() > n
        or int((ends - starts).sum()) > n
    ):
        raise ValueError(f"{what} ranges out of bounds or overlapping")


def _check_gather_cols(src_arr, offsets, lens, n: int) -> None:
    """Every (offset, len) span must lie inside src — the C gather memcpys
    unchecked."""
    if n and (
        offsets.min() < 0
        or int((offsets + np.maximum(lens, 0)).max()) > src_arr.nbytes
    ):
        raise ValueError("gather (offset, len) span outside the source blob")


# rows a scan crossing's table holds (a 256 KiB window is 8-13 frames),
# and a row: a frame's position, then its thirteen header fields
_SCAN_ROWS = 64
_SCAN_ROW = struct.Struct("<14q")


class SrcTable(typing.NamedTuple):
    """A launch's per-batch source buffers as the ``*_ptrs`` crossings take
    them: one address and one length a buffer."""

    ptrs: np.ndarray  # uint64 [B]
    lens: np.ndarray  # int64 [B]


def src_table(srcs) -> SrcTable:
    """The pointer table of ``srcs``: anything that already is one
    (``.ptrs`` / ``.lens``: a ``SrcTable``, ``batch_codec.LaunchPayloads``,
    alive as long as its owner) is taken as it lies; a list of ``bytes``
    becomes one here (borrowed char*: the address array's base is the
    ctypes array, which retains the objects)."""
    ptrs = getattr(srcs, "ptrs", None)
    if ptrs is not None:
        return SrcTable(ptrs, srcs.lens)
    n = len(srcs)
    if not n:
        return SrcTable(np.zeros(0, np.uint64), np.zeros(0, np.int64))
    ptrs = np.frombuffer((ctypes.c_char_p * n)(*srcs), dtype=np.uint64)
    return SrcTable(ptrs, np.fromiter((len(b) for b in srcs), np.int64, n))


class _NativeLib:
    def __init__(self, dll: ctypes.CDLL):
        self._dll = dll
        dll.rp_crc32c_update.restype = ctypes.c_uint32
        dll.rp_crc32c_update.argtypes = [ctypes.c_uint32, ctypes.c_char_p, ctypes.c_size_t]
        dll.rp_crc32c.restype = ctypes.c_uint32
        dll.rp_crc32c.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
        dll.rp_crc32c_many.restype = None
        dll.rp_crc32c_many.argtypes = [
            ctypes.c_void_p, ctypes.c_size_t, ctypes.c_size_t,
            ctypes.c_void_p, ctypes.c_void_p,
        ]
        dll.rp_pack_rows.restype = ctypes.c_int32
        dll.rp_pack_rows.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_size_t, ctypes.c_void_p, ctypes.c_size_t,
        ]
        dll.rp_unpack_rows.restype = ctypes.c_int64
        dll.rp_unpack_rows.argtypes = [
            ctypes.c_void_p, ctypes.c_size_t, ctypes.c_void_p,
            ctypes.c_size_t, ctypes.c_void_p,
        ]
        dll.rp_parse_record_values.restype = ctypes.c_int32
        dll.rp_parse_record_values.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_int32,
            ctypes.c_void_p, ctypes.c_void_p,
        ]
        dll.rp_frame_records.restype = ctypes.c_int64
        dll.rp_frame_records.argtypes = [
            ctypes.c_void_p, ctypes.c_size_t, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int32, ctypes.c_void_p, ctypes.c_void_p,
        ]
        # Newer symbols bind conditionally: a stale .so (make unavailable,
        # read-only checkout) must degrade to the features it HAS, not
        # disable the whole native layer.
        self.has_parse_many = hasattr(dll, "rp_parse_many")
        if self.has_parse_many:
            dll.rp_parse_many.restype = ctypes.c_int64
            dll.rp_parse_many.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_int32, ctypes.c_void_p,
                ctypes.c_void_p,
            ]
        self.has_explode_find = hasattr(dll, "rp_explode_find")
        if self.has_explode_find:
            dll.rp_explode_find.restype = ctypes.c_int64
            dll.rp_explode_find.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_int32, ctypes.c_char_p,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int32,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_void_p,
            ]
        # Structural-index fused parse + extraction (the pointer-table
        # crossings: payload bytes reach native code without a Python-side
        # b"".join; the joined blob is built in-crossing only when the
        # caller needs it for the zero-copy harvest). The two symbols ship
        # together; the scalar rp_explode_find stays bound as the parity
        # oracle and fallback.
        self.has_structural = hasattr(dll, "rp_explode_find2") and hasattr(
            dll, "rp_extract_cols2"
        )
        if self.has_structural:
            dll.rp_explode_find2.restype = ctypes.c_int64
            dll.rp_explode_find2.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_int32, ctypes.c_void_p,
                ctypes.c_char_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_int32, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ]
            dll.rp_extract_cols2.restype = None
            dll.rp_extract_cols2.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_int32, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_int32, ctypes.c_void_p,
                ctypes.c_int32, ctypes.POINTER(ctypes.c_void_p),
                ctypes.c_int64, ctypes.c_void_p, ctypes.c_int32,
                ctypes.c_int32, ctypes.c_void_p, ctypes.c_void_p,
            ]
        self.has_project_rows = hasattr(dll, "rp_project_rows")
        if self.has_project_rows:
            dll.rp_project_rows.restype = ctypes.c_int64
            dll.rp_project_rows.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_int32, ctypes.c_void_p, ctypes.c_int32,
                ctypes.c_int32, ctypes.c_void_p, ctypes.c_void_p,
            ]
        self.has_find_multi = hasattr(dll, "rp_find_multi")
        if self.has_find_multi:
            dll.rp_find_multi.restype = ctypes.c_int64
            dll.rp_find_multi.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_int64, ctypes.c_char_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_int32, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_void_p,
            ]
            dll.rp_gather_str.restype = None
            dll.rp_gather_str.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_int32, ctypes.c_void_p, ctypes.c_void_p,
            ]
            dll.rp_gather_num.restype = None
            dll.rp_gather_num.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ]
        self.has_frame_many = hasattr(dll, "rp_frame_many")
        if self.has_frame_many:
            dll.rp_frame_many.restype = ctypes.c_int64
            dll.rp_frame_many.argtypes = [
                ctypes.c_void_p, ctypes.c_size_t, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_void_p,
            ]
        self.has_frame_many_gather = hasattr(dll, "rp_frame_many_gather")
        if self.has_frame_many_gather:
            dll.rp_frame_gather.restype = ctypes.c_int64
            dll.rp_frame_gather.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
                ctypes.c_void_p,
            ]
            dll.rp_frame_many_gather.restype = ctypes.c_int64
            dll.rp_frame_many_gather.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_void_p,
            ]
        self.has_frame_many_gather_ptrs = hasattr(
            dll, "rp_frame_many_gather_ptrs"
        )
        if self.has_frame_many_gather_ptrs:
            dll.rp_frame_many_gather_ptrs.restype = ctypes.c_int64
            dll.rp_frame_many_gather_ptrs.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ]
        self.has_pack_rows_ptrs = hasattr(dll, "rp_pack_rows_ptrs")
        if self.has_pack_rows_ptrs:
            dll.rp_pack_rows_ptrs.restype = ctypes.c_int64
            dll.rp_pack_rows_ptrs.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_int64, ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_size_t,
            ]
        self.has_parse_many_ptrs = hasattr(dll, "rp_parse_many_ptrs")
        if self.has_parse_many_ptrs:
            dll.rp_parse_many_ptrs.restype = ctypes.c_int64
            dll.rp_parse_many_ptrs.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p,
            ]
        # many zstd frames a crossing; libzstd is resolved at run time, so
        # the symbol being there does not say the codec is
        self.has_zstd_many = False
        if hasattr(dll, "rp_zstd_uncompress_many"):
            dll.rp_zstd_available.restype = ctypes.c_int32
            dll.rp_zstd_available.argtypes = []
            dll.rp_zstd_frame_sizes.restype = ctypes.c_int64
            dll.rp_zstd_frame_sizes.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_void_p, ctypes.c_void_p,
            ]
            dll.rp_zstd_uncompress_many.restype = ctypes.c_int64
            dll.rp_zstd_uncompress_many.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_int32,
            ]
            self.has_zstd_many = bool(dll.rp_zstd_available())
        # the seal of many output batches a crossing; it compresses through
        # the same run-time libzstd, so the same holds
        self.has_seal_many = False
        if hasattr(dll, "rp_seal_many"):
            dll.rp_seal_available.restype = ctypes.c_int32
            dll.rp_seal_available.argtypes = []
            dll.rp_seal_many.restype = ctypes.c_int64
            dll.rp_seal_many.argtypes = (
                [ctypes.c_void_p] * 6
                + [ctypes.c_int64, ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
                   ctypes.c_void_p, ctypes.c_int64]
                + [ctypes.c_void_p] * 5
                + [ctypes.c_int32]
            )
            self.has_seal_many = bool(dll.rp_seal_available())
        # the log's offset-assigning append, a list of batches a crossing.
        # Bound through PyDLL: the call KEEPS the interpreter lock. It is
        # microseconds of memcpy and CRC on the event loop's thread, and a
        # CDLL call, which drops the lock, waits up to a switch interval to
        # take it back beside a busy thread (PERF.md section 6, PR 42: a
        # one-batch append 26 us alone, 150-170 beside a spinning thread,
        # 30-47 with the lock kept).
        self.has_frame_internal_many = hasattr(dll, "rp_frame_internal_many")
        if self.has_frame_internal_many:
            fn = ctypes.PyDLL(dll._name).rp_frame_internal_many
            fn.restype = ctypes.c_int64
            fn.argtypes = [
                ctypes.c_char_p, ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_int64, ctypes.c_int32, ctypes.c_void_p,
                ctypes.c_int64, ctypes.c_void_p,
            ]
            self._frame_internal_many = fn
        # a scanning read's walk over a window's frames, a window a
        # crossing; PyDLL for the same reason: ~2 us of header CRCs on the
        # loop's thread (PERF.md section 5, step 0 of ISSUE 48)
        self.has_scan_internal_frames = hasattr(dll, "rp_scan_internal_frames")
        if self.has_scan_internal_frames:
            fn = ctypes.PyDLL(dll._name).rp_scan_internal_frames
            fn.restype = ctypes.c_int32
            fn.argtypes = [
                ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_uint64, ctypes.c_uint64, ctypes.c_void_p,
                ctypes.c_int64,
            ]
            self._scan_internal_frames = fn
        dll.rp_json_find.restype = ctypes.c_int32
        dll.rp_json_find.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_char_p, ctypes.c_int32,
            ctypes.c_void_p, ctypes.c_void_p,
        ]
        dll.rp_extract_str.restype = ctypes.c_int64
        dll.rp_extract_str.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_char_p, ctypes.c_int32, ctypes.c_int32,
            ctypes.c_void_p, ctypes.c_void_p,
        ]
        dll.rp_extract_num.restype = ctypes.c_int64
        dll.rp_extract_num.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_char_p, ctypes.c_int32,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ]
        dll.rp_extract_exists.restype = ctypes.c_int64
        dll.rp_extract_exists.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_char_p, ctypes.c_int32, ctypes.c_void_p,
        ]

    def crc32c_update(self, state: int, data: bytes) -> int:
        return self._dll.rp_crc32c_update(state & 0xFFFFFFFF, data, len(data))

    def crc32c(self, data: bytes) -> int:
        return self._dll.rp_crc32c(data, len(data))

    def crc32c_many(self, rows: np.ndarray, lengths: np.ndarray) -> np.ndarray:
        rows = np.ascontiguousarray(rows, dtype=np.uint8)
        lengths = np.ascontiguousarray(lengths, dtype=np.int32)
        n, stride = rows.shape
        out = np.empty(n, dtype=np.uint32)
        self._dll.rp_crc32c_many(
            rows.ctypes.data, stride, n, lengths.ctypes.data, out.ctypes.data
        )
        return out

    def pack_rows(self, src: bytes, offsets: np.ndarray, sizes: np.ndarray, row_stride: int) -> tuple[np.ndarray, int]:
        offsets = np.ascontiguousarray(offsets, dtype=np.int64)
        sizes = np.ascontiguousarray(sizes, dtype=np.int32)
        n = len(sizes)
        dst = np.empty((n, row_stride), dtype=np.uint8)
        src_arr = np.frombuffer(src, dtype=np.uint8)
        truncated = self._dll.rp_pack_rows(
            src_arr.ctypes.data, offsets.ctypes.data, sizes.ctypes.data,
            n, dst.ctypes.data, row_stride,
        )
        return dst, truncated

    def pack_rows_into(
        self, src: bytes, offsets: np.ndarray, sizes: np.ndarray,
        dst: np.ndarray,
    ) -> int:
        """rp_pack_rows into a CALLER-provided [n, stride] row block — a
        contiguous slice of a larger staging matrix: the classic
        joined-blob staging road packs a whole launch into the head of its
        pooled matrix this way (the pointer-table lane has
        pack_rows_ptrs). The C loop clamps sizes to the stride and
        zero-fills every row tail (byte parity with pack_rows)."""
        offsets = np.ascontiguousarray(offsets, dtype=np.int64)
        sizes = np.ascontiguousarray(sizes, dtype=np.int32)
        n, stride = dst.shape
        if len(offsets) != n or len(sizes) != n:
            raise ValueError("pack_rows_into offsets/sizes/dst mismatch")
        if dst.dtype != np.uint8 or not dst.flags["C_CONTIGUOUS"]:
            raise ValueError("pack_rows_into dst must be contiguous uint8")
        src_arr = np.frombuffer(src, dtype=np.uint8)
        # bounds: the C memcpy is unchecked (sizes clamp to the stride
        # in-crossing, so the effective span is min(max(size,0), stride))
        eff = np.minimum(np.maximum(sizes, 0), stride)
        if n and (
            offsets.min() < 0
            or int((offsets + eff).max()) > src_arr.nbytes
        ):
            raise ValueError("pack span outside the source buffer")
        return self._dll.rp_pack_rows(
            src_arr.ctypes.data, offsets.ctypes.data, sizes.ctypes.data,
            n, dst.ctypes.data, stride,
        )

    def pack_rows_ptrs(
        self,
        srcs,
        offsets: np.ndarray,
        lens: np.ndarray,
        starts: np.ndarray,
        ends: np.ndarray,
        dst: np.ndarray,
        row_stride: int,
        rows: np.ndarray | None = None,
    ) -> None:
        """Fill a payload launch's whole staging matrix in ONE crossing
        (rp_pack_rows_ptrs): batch r's records, their (offset, len)
        relative to their own buffer ``srcs[r]`` (a list of ``bytes`` or
        a pointer table, ``src_table``), are rows [starts[r], ends[r]) of
        the table. Row j of ``dst`` [n_pad, row_stride + 8] is the table's
        row ``rows[j]`` (row numbers, ascending: one part of a launch
        staged by width class), or row j itself with ``rows`` None: value,
        zeroed tail, LE32 length (0 for a null value and for one wider
        than ``row_stride``), four zero bytes; the rows past the last one
        are cleared. ``dst`` may be a reused matrix holding anything. The
        ranges must tile [0, n) in order; a span outside its buffer, a row
        outside the table or rows that do not ascend are a ValueError and
        nothing has been written."""
        offsets = np.ascontiguousarray(offsets, dtype=np.int64)
        lens = np.ascontiguousarray(lens, dtype=np.int32)
        starts = np.ascontiguousarray(starts, dtype=np.int64)
        ends = np.ascontiguousarray(ends, dtype=np.int64)
        if rows is not None:
            rows = np.ascontiguousarray(rows, dtype=np.int64)
        n = len(offsets)
        k = n if rows is None else len(rows)
        n_batches = len(starts)
        ptrs, src_lens = src_table(srcs)
        if len(ptrs) != n_batches or len(ends) != n_batches:
            raise ValueError("srcs/ranges length mismatch")
        if len(lens) != n:
            raise ValueError("offsets/lens length mismatch")
        if dst.dtype != np.uint8 or not dst.flags["C_CONTIGUOUS"]:
            raise ValueError("pack_rows_ptrs dst must be contiguous uint8")
        n_pad, stride = dst.shape
        if stride != row_stride + 8 or n_pad < k:
            raise ValueError("pack_rows_ptrs dst shape does not fit the rows")
        # every row of the table belongs to exactly one batch: the ranges
        # tile [0, n) (the C walk reads offsets[i] / lens[i] for i < n)
        edges = np.concatenate(([0], ends))
        if (
            edges[-1] != n
            or (starts > ends).any()
            or not np.array_equal(edges[:-1], starts)
        ):
            raise ValueError("pack_rows_ptrs ranges do not tile the rows")
        rc = self._dll.rp_pack_rows_ptrs(
            ptrs.ctypes.data, src_lens.ctypes.data, offsets.ctypes.data,
            lens.ctypes.data, starts.ctypes.data, ends.ctypes.data,
            n_batches, None if rows is None else rows.ctypes.data, k,
            dst.ctypes.data, n_pad, row_stride,
        )
        if rc < 0:
            raise ValueError(
                "pack span outside its source buffer, or rows outside the table"
            )

    def parse_record_values(self, payload: bytes, count: int) -> tuple[np.ndarray, np.ndarray]:
        """Offsets/lengths of each record's value within a batch payload."""
        val_off = np.empty(count, dtype=np.int64)
        val_len = np.empty(count, dtype=np.int32)
        parsed = self._dll.rp_parse_record_values(
            payload, len(payload), count, val_off.ctypes.data, val_len.ctypes.data
        )
        if parsed != count:
            raise ValueError(f"record framing parse failed at record {parsed}/{count}")
        return val_off, val_len

    def frame_records(self, rows: np.ndarray, lens: np.ndarray, keep: np.ndarray) -> tuple[bytes, int]:
        """Frame kept rows as a records payload; returns (payload, kept_count)."""
        rows = np.ascontiguousarray(rows, dtype=np.uint8)
        lens = np.ascontiguousarray(lens, dtype=np.int32)
        keep = np.ascontiguousarray(keep, dtype=np.uint8)
        n, stride = rows.shape
        dst = np.empty(n * (stride + 16) + 16, dtype=np.uint8)
        kept = ctypes.c_int32()
        length = self._dll.rp_frame_records(
            rows.ctypes.data, stride, lens.ctypes.data, keep.ctypes.data,
            n, dst.ctypes.data, ctypes.byref(kept),
        )
        return dst[:length].tobytes(), kept.value

    def find_multi(
        self, joined, offsets: np.ndarray, sizes: np.ndarray, paths: list[str]
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """One top-level JSON walk per record locating ALL `paths`
        (single-segment keys). Returns (types[n,k] i8, vs[n,k] i64,
        ve[n,k] i64); type 0 = missing."""
        offsets = np.ascontiguousarray(offsets, dtype=np.int64)
        sizes = np.ascontiguousarray(sizes, dtype=np.int32)
        n = len(sizes)
        blob, path_off, path_len, k = _pack_paths(paths)
        joined_arr = np.frombuffer(joined, dtype=np.uint8)
        types = np.empty((n, k), dtype=np.int8)
        vs = np.empty((n, k), dtype=np.int64)
        ve = np.empty((n, k), dtype=np.int64)
        self._dll.rp_find_multi(
            joined_arr.ctypes.data, offsets.ctypes.data, sizes.ctypes.data, n,
            blob, path_off.ctypes.data, path_len.ctypes.data, k,
            types.ctypes.data, vs.ctypes.data, ve.ctypes.data,
        )
        return types, vs, ve

    def gather_str(
        self, joined, offsets, types_col, vs_col, ve_col, w: int
    ) -> tuple[np.ndarray, np.ndarray]:
        offsets = np.ascontiguousarray(offsets, dtype=np.int64)
        types_col = np.ascontiguousarray(types_col, dtype=np.int8)
        vs_col = np.ascontiguousarray(vs_col, dtype=np.int64)
        ve_col = np.ascontiguousarray(ve_col, dtype=np.int64)
        n = len(offsets)
        joined_arr = np.frombuffer(joined, dtype=np.uint8)
        out = np.empty((n, w), dtype=np.uint8)
        vlen = np.empty(n, dtype=np.int32)
        self._dll.rp_gather_str(
            joined_arr.ctypes.data, offsets.ctypes.data, n,
            types_col.ctypes.data, vs_col.ctypes.data, ve_col.ctypes.data,
            w, out.ctypes.data, vlen.ctypes.data,
        )
        return out, vlen

    def gather_num(
        self, joined, offsets, types_col, vs_col, ve_col
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        offsets = np.ascontiguousarray(offsets, dtype=np.int64)
        types_col = np.ascontiguousarray(types_col, dtype=np.int8)
        vs_col = np.ascontiguousarray(vs_col, dtype=np.int64)
        ve_col = np.ascontiguousarray(ve_col, dtype=np.int64)
        n = len(offsets)
        joined_arr = np.frombuffer(joined, dtype=np.uint8)
        f32 = np.empty(n, dtype=np.float32)
        i32 = np.empty(n, dtype=np.int32)
        flags = np.empty(n, dtype=np.uint8)
        self._dll.rp_gather_num(
            joined_arr.ctypes.data, offsets.ctypes.data, n,
            types_col.ctypes.data, vs_col.ctypes.data, ve_col.ctypes.data,
            f32.ctypes.data, i32.ctypes.data, flags.ctypes.data,
        )
        return f32, i32, flags

    def frame_many(
        self,
        rows: np.ndarray,
        lens: np.ndarray,
        keep: np.ndarray,
        starts: np.ndarray,
        ends: np.ndarray,
        out: np.ndarray | None = None,
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """Frame many [start, end) record ranges in ONE crossing.

        `out` (optional, uint8 1-D) is reusable caller scratch — see
        frame_many_gather. Returns (dst, payload_off[r], payload_len[r],
        kept[r]); a range's payload is dst[off : off + len].tobytes()."""
        rows = np.ascontiguousarray(rows, dtype=np.uint8)
        lens = np.ascontiguousarray(lens, dtype=np.int32)
        keep = np.ascontiguousarray(keep, dtype=np.uint8)
        starts = np.ascontiguousarray(starts, dtype=np.int64)
        ends = np.ascontiguousarray(ends, dtype=np.int64)
        n, stride = rows.shape
        n_ranges = len(starts)
        _check_ranges(starts, ends, n, "frame_many")
        dst = _take_scratch(out, n * (stride + 16) + 16)
        out_off = np.empty(n_ranges, dtype=np.int64)
        out_len = np.empty(n_ranges, dtype=np.int64)
        out_kept = np.empty(n_ranges, dtype=np.int32)
        self._dll.rp_frame_many(
            rows.ctypes.data, stride, lens.ctypes.data, keep.ctypes.data,
            starts.ctypes.data, ends.ctypes.data, n_ranges, dst.ctypes.data,
            out_off.ctypes.data, out_len.ctypes.data, out_kept.ctypes.data,
        )
        return dst, out_off, out_len, out_kept

    def frame_gather(
        self,
        src,
        offsets: np.ndarray,
        lens: np.ndarray,
        keep: np.ndarray,
        out: np.ndarray | None = None,
    ) -> tuple[bytes, int]:
        """ZERO-COPY framing of one record range: kept records frame
        straight from `src` via (offset, len) columns — no padded row
        matrix. Returns (payload, kept_count)."""
        offsets = np.ascontiguousarray(offsets, dtype=np.int64)
        lens = np.ascontiguousarray(lens, dtype=np.int32)
        keep = np.ascontiguousarray(keep, dtype=np.uint8)
        n = len(offsets)
        src_arr = np.frombuffer(src, dtype=np.uint8)
        _check_gather_cols(src_arr, offsets, lens, n)
        cap = _gather_dst_cap(lens, n)
        dst = _take_scratch(out, cap)
        kept = ctypes.c_int32()
        length = self._dll.rp_frame_gather(
            src_arr.ctypes.data, offsets.ctypes.data, lens.ctypes.data,
            keep.ctypes.data, n, dst.ctypes.data, ctypes.byref(kept),
        )
        return dst[:length].tobytes(), kept.value

    def frame_many_gather(
        self,
        src,
        offsets: np.ndarray,
        lens: np.ndarray,
        keep: np.ndarray,
        starts: np.ndarray,
        ends: np.ndarray,
        out: np.ndarray | None = None,
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """Gather-frame many [start, end) record ranges in ONE crossing —
        the zero-copy twin of frame_many: records frame straight from
        `src` via per-record (offset, len) columns instead of a padded
        row matrix. `out` (optional, uint8 1-D) is a caller-owned scratch
        buffer (arena reuse across launches); it is grown-by-replacement
        when too small, never written past the returned lengths.

        Returns (dst, payload_off[r], payload_len[r], kept[r]); a range's
        payload is dst[off : off + len].tobytes()."""
        offsets = np.ascontiguousarray(offsets, dtype=np.int64)
        lens = np.ascontiguousarray(lens, dtype=np.int32)
        keep = np.ascontiguousarray(keep, dtype=np.uint8)
        starts = np.ascontiguousarray(starts, dtype=np.int64)
        ends = np.ascontiguousarray(ends, dtype=np.int64)
        n = len(offsets)
        n_ranges = len(starts)
        # malformed ranges or out-of-blob (offset, len) spans must be a
        # ValueError here, not a heap read/write
        _check_ranges(starts, ends, n, "frame_many_gather")
        src_arr = np.frombuffer(src, dtype=np.uint8)
        _check_gather_cols(src_arr, offsets, lens, n)
        cap = _gather_dst_cap(lens, n)
        dst = _take_scratch(out, cap)
        out_off = np.empty(n_ranges, dtype=np.int64)
        out_len = np.empty(n_ranges, dtype=np.int64)
        out_kept = np.empty(n_ranges, dtype=np.int32)
        self._dll.rp_frame_many_gather(
            src_arr.ctypes.data, offsets.ctypes.data, lens.ctypes.data,
            keep.ctypes.data, starts.ctypes.data, ends.ctypes.data,
            n_ranges, dst.ctypes.data,
            out_off.ctypes.data, out_len.ctypes.data, out_kept.ctypes.data,
        )
        return dst, out_off, out_len, out_kept

    def frame_many_gather_ptrs(
        self,
        srcs,
        offsets: np.ndarray,
        lens: np.ndarray,
        keep: np.ndarray,
        starts: np.ndarray,
        ends: np.ndarray,
        out: np.ndarray | None = None,
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """frame_many_gather over a POINTER TABLE: range r's records frame
        from their own buffer ``srcs[r]``, their (offset, len) relative to
        it (the payload staging lane's per-batch payload buffers), so no
        joined blob is needed. Same returns and the same posture: malformed
        ranges or a span outside its buffer are a ValueError here."""
        offsets = np.ascontiguousarray(offsets, dtype=np.int64)
        lens = np.ascontiguousarray(lens, dtype=np.int32)
        keep = np.ascontiguousarray(keep, dtype=np.uint8)
        starts = np.ascontiguousarray(starts, dtype=np.int64)
        ends = np.ascontiguousarray(ends, dtype=np.int64)
        n = len(offsets)
        n_ranges = len(starts)
        ptrs, src_lens = src_table(srcs)
        if len(ptrs) != n_ranges:
            raise ValueError("srcs/ranges length mismatch")
        if len(lens) != n or len(keep) != n:
            raise ValueError("offsets/lens/keep length mismatch")
        _check_ranges(starts, ends, n, "frame_many_gather_ptrs")
        dst = _take_scratch(out, _gather_dst_cap(lens, n))
        out_off = np.empty(n_ranges, dtype=np.int64)
        out_len = np.empty(n_ranges, dtype=np.int64)
        out_kept = np.empty(n_ranges, dtype=np.int32)
        total = self._dll.rp_frame_many_gather_ptrs(
            ptrs.ctypes.data, src_lens.ctypes.data, offsets.ctypes.data,
            lens.ctypes.data, keep.ctypes.data, starts.ctypes.data,
            ends.ctypes.data, n_ranges, dst.ctypes.data,
            out_off.ctypes.data, out_len.ctypes.data, out_kept.ctypes.data,
        )
        if total < 0:
            raise ValueError("gather (offset, len) span outside its buffer")
        return dst, out_off, out_len, out_kept

    def parse_many(
        self,
        joined,
        payload_off: np.ndarray,
        payload_len: np.ndarray,
        counts: np.ndarray,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Record value offsets/lengths for MANY batch payloads in one
        crossing; offsets are absolute into `joined`."""
        payload_off = np.ascontiguousarray(payload_off, dtype=np.int64)
        payload_len = np.ascontiguousarray(payload_len, dtype=np.int32)
        counts = np.ascontiguousarray(counts, dtype=np.int32)
        total = int(counts.sum())
        joined_arr = np.frombuffer(joined, dtype=np.uint8)
        val_off = np.empty(total, dtype=np.int64)
        val_len = np.empty(total, dtype=np.int32)
        parsed = self._dll.rp_parse_many(
            joined_arr.ctypes.data, payload_off.ctypes.data,
            payload_len.ctypes.data, counts.ctypes.data, len(counts),
            val_off.ctypes.data, val_len.ctypes.data,
        )
        if parsed != total:
            raise ValueError(f"record framing parse failed at record {parsed}/{total}")
        return val_off, val_len

    def parse_many_ptrs(
        self, srcs, counts: np.ndarray, total: int | None = None
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """parse_many over a pointer table (rp_parse_many_ptrs): the value
        (offset, length) of every record of a launch in ONE crossing, each
        offset relative to its own batch's buffer ``srcs[b]``, and the
        lengths clamped at 0 (``sizes``). ``total``: ``counts.sum()``, from
        a caller that has it."""
        counts = np.ascontiguousarray(counts, dtype=np.int32)
        ptrs, src_lens = src_table(srcs)
        if len(ptrs) != len(counts):
            raise ValueError("srcs/counts length mismatch")
        if total is None:
            total = int(counts.sum())
        val_off = np.empty(total, dtype=np.int64)
        val_len = np.empty(total, dtype=np.int32)
        sizes = np.empty(total, dtype=np.int32)
        parsed = self._dll.rp_parse_many_ptrs(
            ptrs.ctypes.data, src_lens.ctypes.data, counts.ctypes.data,
            len(counts), val_off.ctypes.data, val_len.ctypes.data,
            sizes.ctypes.data,
        )
        if parsed != total:
            raise ValueError(f"record framing parse failed at record {parsed}/{total}")
        return val_off, val_len, sizes

    def zstd_frame_sizes(self, frames) -> tuple[np.ndarray, np.ndarray, int]:
        """(off int64 [n], ln int64 [n], total): the content size each
        frame's header states (``ln``; -1 where it states none or does not
        parse), and where each sized frame goes in one buffer of ``total``
        bytes that holds them back to back (rp_zstd_frame_sizes).
        ``frames``: a list of ``bytes`` or a pointer table (``src_table``)."""
        ptrs, src_lens = src_table(frames)
        off = np.empty(len(ptrs), dtype=np.int64)
        ln = np.empty(len(ptrs), dtype=np.int64)
        total = self._dll.rp_zstd_frame_sizes(
            ptrs.ctypes.data, src_lens.ctypes.data, len(ptrs),
            off.ctypes.data, ln.ctypes.data,
        )
        if total < 0:
            raise RuntimeError("libzstd is not available")
        return off, ln, int(total)

    def zstd_uncompress_many(
        self, frames, dst: np.ndarray, dst_off: np.ndarray,
        dst_len: np.ndarray, n_threads: int = 1,
    ) -> int:
        """Decompress frame b into ``dst[dst_off[b] : dst_off[b] +
        dst_len[b]]`` for every b with ``dst_len[b] >= 0``, in ONE crossing
        that holds no interpreter lock (rp_zstd_uncompress_many), on
        ``n_threads`` threads. A frame that fails or does not fill its span
        exactly gets ``dst_len[b] = -1`` IN PLACE; returns how many did.
        ``frames`` as for ``zstd_frame_sizes``; ``dst_off`` / ``dst_len``
        contiguous int64; a span outside ``dst`` is a ValueError with
        nothing written (checked inside the crossing)."""
        ptrs, src_lens = src_table(frames)
        n = len(ptrs)
        for a in (dst_off, dst_len):
            if a.dtype != np.int64 or not a.flags["C_CONTIGUOUS"] or len(a) != n:
                raise ValueError("dst spans must be contiguous int64, one a frame")
        if dst.dtype != np.uint8 or dst.ndim != 1 or not dst.flags["C_CONTIGUOUS"]:
            raise ValueError("zstd_uncompress_many dst must be contiguous uint8")
        failed = self._dll.rp_zstd_uncompress_many(
            ptrs.ctypes.data, src_lens.ctypes.data, n, dst.ctypes.data,
            dst.nbytes, dst_off.ctypes.data, dst_len.ctypes.data, n_threads,
        )
        if failed == -2:
            raise ValueError("decompress span outside dst")
        if failed < 0:
            raise RuntimeError("libzstd is not available")
        return int(failed)

    def seal_many(
        self, payloads, kept: np.ndarray, types: np.ndarray,
        first_ts: np.ndarray, max_ts: np.ndarray, dst: np.ndarray, *,
        threshold: int, codec: int, level: int, n_threads: int = 1,
    ):
        """Seal job b (framed payload ``payloads[b]``, ``kept[b]`` records,
        its source's batch type and timestamps) for every b in ONE crossing
        that holds no interpreter lock (rp_seal_many), on up to
        ``n_threads`` threads: Zstd where ``len(payloads[b]) >= threshold``
        and ``codec`` is 4, stored as it is otherwise (``codec`` 0 stores
        all). Returns ``(out_off, out_len, out_attrs, crc, header_crc)``,
        one a job: ``out_len[b] == -1`` for a job that makes no batch
        (``kept[b] <= 0``) or that the crossing could not seal (its frame's
        bound did not fit what was left of ``dst``, the codec failed); else
        the stored payload is ``dst[out_off[b] : out_off[b] + out_len[b]]``
        where ``out_attrs[b] != 0`` and ``payloads[b]`` itself where it is
        0, and the two CRCs are the header's as ``RecordBatch.reseal``
        computes them. ``None`` where the crossing serves no job at all
        (another codec, no libzstd compress side, no context).
        ``payloads``: a list of ``bytes`` or a pointer table
        (``src_table``); the columns contiguous, ``kept`` int32, ``types``
        int8, the timestamps int64."""
        ptrs, src_lens = src_table(payloads)
        n = len(ptrs)
        for a, dt in ((kept, np.int32), (types, np.int8), (first_ts, np.int64),
                      (max_ts, np.int64)):
            if a.dtype != dt or not a.flags["C_CONTIGUOUS"] or len(a) != n:
                raise ValueError("seal_many columns must be contiguous, one entry a job")
        if dst.dtype != np.uint8 or dst.ndim != 1 or not dst.flags["C_CONTIGUOUS"]:
            raise ValueError("seal_many dst must be contiguous uint8")
        out_off = np.empty(n, dtype=np.int64)
        out_len = np.empty(n, dtype=np.int64)
        out_attrs = np.empty(n, dtype=np.int32)
        crc = np.empty(n, dtype=np.uint32)
        header_crc = np.empty(n, dtype=np.uint32)
        failed = self._dll.rp_seal_many(
            ptrs.ctypes.data, src_lens.ctypes.data, kept.ctypes.data,
            types.ctypes.data, first_ts.ctypes.data, max_ts.ctypes.data,
            n, threshold, codec, level, dst.ctypes.data, dst.nbytes,
            out_off.ctypes.data, out_len.ctypes.data, out_attrs.ctypes.data,
            crc.ctypes.data, header_crc.ctypes.data, n_threads,
        )
        if failed < 0:
            return None
        return out_off, out_len, out_attrs, crc, header_crc

    def frame_internal_many(
        self, heads: bytes, payloads: list[bytes], nbytes: int,
        first_base_offset: int, verify: bool,
    ) -> tuple[bytearray, list[int]]:
        """The internal frames of a list of batches, back to back, with
        base offsets assigned from ``first_base_offset`` on, in ONE
        crossing (rp_frame_internal_many): what
        ``RecordBatch.with_base_offset(..).encode_internal()`` writes a
        batch. ``heads``: the batches' 61-byte headers as they state them
        (``RecordBatchHeader.encode``), joined; ``payloads``: their
        ``bytes``, each as long as its header's ``size_bytes`` less 61 (the
        crossing reads that many: the CALLER checks); ``nbytes``: the sum
        of the ``size_bytes``. Returns ``(frames, header_crcs)``: one
        ``header_crc`` a batch, or -1 for a batch that ``verify`` left out
        (its Kafka CRC does not match: no frame, no offset)."""
        n = len(payloads)
        if len(heads) != 61 * n:
            raise ValueError("one 61-byte header a payload")
        frames = bytearray(nbytes)
        header_crcs = (ctypes.c_int64 * n)()
        written = self._frame_internal_many(
            heads, (ctypes.c_char_p * n)(*payloads), n, first_base_offset,
            verify, ctypes.byref(ctypes.c_char.from_buffer(frames)), nbytes,
            header_crcs,
        )
        if written < 0:
            raise ValueError("frames do not fit nbytes, or a size_bytes under 61")
        del frames[written:]
        return frames, header_crcs[:]

    def scan_internal_frames(
        self, window: bytes, at: int, start_offset: int, max_offset: int,
        budget: int, known_types: int, type_mask: int,
    ):
        """A scanning read's walk over the internal frames of ``window``
        from position ``at`` on, in ONE crossing (rp_scan_internal_frames):
        every whole, sound frame (``size_bytes`` >= 61, a type whose bit
        ``known_types`` has, the header CRC its header's) is held to
        ``Segment.scan``'s rules in their order: a base offset over
        ``max_offset`` ends the walk, the frame not consumed; a last offset
        under ``start_offset`` or a type whose bit ``type_mask`` lacks is
        passed over; a kept frame counts its ``size_bytes`` against
        ``budget``, which ends the walk once taken. Returns ``(status,
        stopped_at, kept_end, taken, rows)``: ``status`` 0 done, 1 the
        window ends inside the frame at ``stopped_at`` (or holds no more),
        2 that frame is not sound, 3 the table is full (call again from
        ``stopped_at``); ``kept_end`` the position just past the last kept
        frame, -1 if none; ``rows`` one tuple a kept frame: its position,
        then ``RecordBatchHeader``'s thirteen packed fields in their order
        (``type`` the plain number)."""
        table = (ctypes.c_int64 * (4 + 14 * _SCAN_ROWS))()
        status = self._scan_internal_frames(
            window, len(window), at, start_offset, max_offset, budget,
            known_types, type_mask, table, _SCAN_ROWS,
        )
        rows = _SCAN_ROW.iter_unpack(
            memoryview(table).cast("B")[32 : 32 + _SCAN_ROW.size * table[0]]
        )
        return status, table[1], table[2], table[3], rows

    def explode_find(
        self,
        joined,
        payload_off: np.ndarray,
        payload_len: np.ndarray,
        counts: np.ndarray,
        paths: list[str],
    ):
        """FUSED explode + find: record framing parse AND the k-path JSON
        walk in one crossing and one cache-hot traversal (the engine's two
        hottest stages). Returns (val_off, val_len, types, vs, ve) with
        the same semantics as parse_many + find_multi."""
        payload_off = np.ascontiguousarray(payload_off, dtype=np.int64)
        payload_len = np.ascontiguousarray(payload_len, dtype=np.int32)
        counts = np.ascontiguousarray(counts, dtype=np.int32)
        total = int(counts.sum())
        blob, path_off, path_len, k = _pack_paths(paths)
        joined_arr = np.frombuffer(joined, dtype=np.uint8)
        val_off = np.empty(total, dtype=np.int64)
        val_len = np.empty(total, dtype=np.int32)
        types = np.empty((total, k), dtype=np.int8)
        vs = np.empty((total, k), dtype=np.int64)
        ve = np.empty((total, k), dtype=np.int64)
        parsed = self._dll.rp_explode_find(
            joined_arr.ctypes.data, payload_off.ctypes.data,
            payload_len.ctypes.data, counts.ctypes.data, len(counts),
            blob, path_off.ctypes.data, path_len.ctypes.data, k,
            val_off.ctypes.data, val_len.ctypes.data,
            types.ctypes.data, vs.ctypes.data, ve.ctypes.data,
        )
        if parsed != total:
            raise ValueError(f"record framing parse failed at record {parsed}/{total}")
        return val_off, val_len, types, vs, ve

    def explode_find_structural(
        self,
        payloads: list[bytes],
        counts: np.ndarray,
        paths: list[str],
        build_joined: bool,
    ):
        """Structural-index fused parse (rp_explode_find2): the payload
        bytes cross the boundary ONCE as a per-batch pointer table — no
        Python-side b"".join. ``build_joined=True`` additionally emits the
        concatenated blob (built in-crossing, parsed cache-hot from the
        copy) for plans whose zero-copy harvest gathers from it; False
        skips the blob entirely (projection plans never read the raw bytes
        again). Returns (joined | None, val_off, val_len, types, vs, ve);
        val_off is absolute into the (possibly virtual) concatenation,
        identical to explode_find's tables."""
        counts = np.ascontiguousarray(counts, dtype=np.int32)
        ptrs, src_lens = src_table(payloads)
        p_len = src_lens.astype(np.int32)
        total = int(counts.sum())
        blob, path_off, path_len, k = _pack_paths(paths)
        joined = (
            np.empty(max(int(p_len.sum()), 1), dtype=np.uint8)
            if build_joined
            else None
        )
        val_off = np.empty(total, dtype=np.int64)
        val_len = np.empty(total, dtype=np.int32)
        types = np.empty((total, k), dtype=np.int8)
        vs = np.empty((total, k), dtype=np.int64)
        ve = np.empty((total, k), dtype=np.int64)
        parsed = self._dll.rp_explode_find2(
            ptrs.ctypes.data, p_len.ctypes.data, counts.ctypes.data, len(ptrs),
            joined.ctypes.data if joined is not None else None,
            blob, path_off.ctypes.data, path_len.ctypes.data, k,
            val_off.ctypes.data, val_len.ctypes.data,
            types.ctypes.data, vs.ctypes.data, ve.ctypes.data,
        )
        if parsed != total:
            # includes rp_explode_find2's -1 scratch-allocation sentinel
            raise ValueError(f"record framing parse failed at record {parsed}/{total}")
        if joined is not None and int(p_len.sum()) == 0:
            joined = joined[:0]
        return joined, val_off, val_len, types, vs, ve

    def extract_cols2(
        self,
        payloads: list[bytes],
        counts: np.ndarray,
        val_off: np.ndarray,
        val_len: np.ndarray,
        types: np.ndarray,
        vs: np.ndarray,
        ve: np.ndarray,
        pred_descs: np.ndarray,
        n_pad: int,
        proj_descs: np.ndarray | None = None,
        r_out: int = 0,
    ):
        """FUSED extraction (rp_extract_cols2): every predicate column and
        (optionally) the packed projection rows gathered from the span
        tables in ONE record-major crossing, straight from the per-batch
        source buffers — replaces the per-column gather crossings, the
        separate project_rows crossing AND the numpy pad concatenations.
        pred_descs is [n, 4] int32 {kind: 0 num, 1 str, 2 exists; span
        col; w; 0}; proj_descs follows project_rows' desc layout. Returns
        (pred_arrays, proj_rows | None, proj_ok | None); pred_arrays is
        the flat list in desc order (num -> f32, i32, flags; str -> bytes
        [n_pad, w], vlen; exists -> u8) — the _bind_slots input shape."""
        counts = np.ascontiguousarray(counts, dtype=np.int32)
        ptrs, src_lens = src_table(payloads)
        p_len = src_lens.astype(np.int32)
        val_off = np.ascontiguousarray(val_off, dtype=np.int64)
        val_len = np.ascontiguousarray(val_len, dtype=np.int32)
        types = np.ascontiguousarray(types, dtype=np.int8)
        vs = np.ascontiguousarray(vs, dtype=np.int64)
        ve = np.ascontiguousarray(ve, dtype=np.int64)
        pred_descs = np.ascontiguousarray(pred_descs, dtype=np.int32)
        n, _k = types.shape
        arrays: list[np.ndarray] = []
        for kind, _col, w, _ in pred_descs:
            if kind == 0:
                arrays += [
                    np.empty(n_pad, np.float32),
                    np.empty(n_pad, np.int32),
                    np.empty(n_pad, np.uint8),
                ]
            elif kind == 1:
                arrays += [
                    np.empty((n_pad, int(w)), np.uint8),
                    np.empty(n_pad, np.int32),
                ]
            else:
                arrays.append(np.empty(n_pad, np.uint8))
        pred_ptrs = (ctypes.c_void_p * max(len(arrays), 1))(
            *[a.ctypes.data for a in arrays]
        )
        if proj_descs is not None and len(proj_descs):
            proj_descs = np.ascontiguousarray(proj_descs, dtype=np.int32)
            rows = np.empty((n, r_out), dtype=np.uint8)
            ok = np.empty(n, dtype=np.bool_)
            n_proj, rows_ptr, ok_ptr = (
                len(proj_descs), rows.ctypes.data, ok.ctypes.data
            )
            proj_ptr = proj_descs.ctypes.data
        else:
            rows = ok = None
            n_proj, rows_ptr, ok_ptr, proj_ptr = 0, None, None, None
        self._dll.rp_extract_cols2(
            ptrs.ctypes.data, p_len.ctypes.data, counts.ctypes.data, len(ptrs),
            val_off.ctypes.data, val_len.ctypes.data,
            types.ctypes.data, vs.ctypes.data, ve.ctypes.data, types.shape[1],
            pred_descs.ctypes.data, len(pred_descs), pred_ptrs, n_pad,
            proj_ptr, n_proj, r_out, rows_ptr, ok_ptr,
        )
        return arrays, rows, ok

    def project_rows(
        self,
        joined,
        offsets: np.ndarray,
        types: np.ndarray,
        vs: np.ndarray,
        ve: np.ndarray,
        descs: np.ndarray,
        r_out: int,
    ) -> tuple[np.ndarray, np.ndarray]:
        """FUSED projection: every Int/Float/Str field gathered from the
        span tables straight into packed output rows, one pass per record
        (layout parity with ColumnarPlan.assemble_rows). descs is
        [n_fields, 4] int32 {kind, span col, w, out off}. Returns
        (rows [n, r_out] u8, ok [n] bool)."""
        offsets = np.ascontiguousarray(offsets, dtype=np.int64)
        types = np.ascontiguousarray(types, dtype=np.int8)
        vs = np.ascontiguousarray(vs, dtype=np.int64)
        ve = np.ascontiguousarray(ve, dtype=np.int64)
        descs = np.ascontiguousarray(descs, dtype=np.int32)
        n, k = types.shape
        joined_arr = np.frombuffer(joined, dtype=np.uint8)
        rows = np.empty((n, r_out), dtype=np.uint8)
        # the C side writes 0/1 bytes — valid numpy bool storage, no copy
        ok = np.empty(n, dtype=np.bool_)
        self._dll.rp_project_rows(
            joined_arr.ctypes.data, offsets.ctypes.data, n,
            types.ctypes.data, vs.ctypes.data, ve.ctypes.data, k,
            descs.ctypes.data, len(descs), r_out,
            rows.ctypes.data, ok.ctypes.data,
        )
        return rows, ok

    def json_find(self, value: bytes, path: str) -> tuple[int, int, int]:
        """(type, value_start, value_end) of `path` in one JSON value.

        Mirrors ops.exprs.json_find; types: 0 missing, 1 str, 2 num,
        3 true, 4 false, 5 null, 6 object, 7 array."""
        vs = ctypes.c_int64()
        ve = ctypes.c_int64()
        p = path.encode()
        t = self._dll.rp_json_find(
            value, len(value), p, len(p), ctypes.byref(vs), ctypes.byref(ve)
        )
        return t, vs.value, ve.value

    def extract_str(
        self, joined, offsets: np.ndarray, sizes: np.ndarray, path: str, w: int
    ) -> tuple[np.ndarray, np.ndarray]:
        """String field column: ([n, w] raw bytes, [n] true value length).

        vlen -1 = missing or not a string; bytes are zero-padded/truncated."""
        offsets = np.ascontiguousarray(offsets, dtype=np.int64)
        sizes = np.ascontiguousarray(sizes, dtype=np.int32)
        n = len(sizes)
        joined_arr = np.frombuffer(joined, dtype=np.uint8)
        out = np.empty((n, w), dtype=np.uint8)
        vlen = np.empty(n, dtype=np.int32)
        p = path.encode()
        self._dll.rp_extract_str(
            joined_arr.ctypes.data, offsets.ctypes.data, sizes.ctypes.data, n,
            p, len(p), w, out.ctypes.data, vlen.ctypes.data,
        )
        return out, vlen

    def extract_num(
        self, joined, offsets: np.ndarray, sizes: np.ndarray, path: str
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Numeric field column: ([n] f32, [n] i32, [n] lattice flags u8)."""
        offsets = np.ascontiguousarray(offsets, dtype=np.int64)
        sizes = np.ascontiguousarray(sizes, dtype=np.int32)
        n = len(sizes)
        joined_arr = np.frombuffer(joined, dtype=np.uint8)
        f32 = np.empty(n, dtype=np.float32)
        i32 = np.empty(n, dtype=np.int32)
        flags = np.empty(n, dtype=np.uint8)
        p = path.encode()
        self._dll.rp_extract_num(
            joined_arr.ctypes.data, offsets.ctypes.data, sizes.ctypes.data, n,
            p, len(p), f32.ctypes.data, i32.ctypes.data, flags.ctypes.data,
        )
        return f32, i32, flags

    def extract_exists(
        self, joined, offsets: np.ndarray, sizes: np.ndarray, path: str
    ) -> np.ndarray:
        """Presence column: [n] u8, 1 when the path resolves."""
        offsets = np.ascontiguousarray(offsets, dtype=np.int64)
        sizes = np.ascontiguousarray(sizes, dtype=np.int32)
        n = len(sizes)
        joined_arr = np.frombuffer(joined, dtype=np.uint8)
        out = np.empty(n, dtype=np.uint8)
        p = path.encode()
        self._dll.rp_extract_exists(
            joined_arr.ctypes.data, offsets.ctypes.data, sizes.ctypes.data, n,
            p, len(p), out.ctypes.data,
        )
        return out

    def unpack_rows(self, rows: np.ndarray, sizes: np.ndarray) -> bytes:
        rows = np.ascontiguousarray(rows, dtype=np.uint8)
        sizes = np.ascontiguousarray(sizes, dtype=np.int32)
        n, stride = rows.shape
        total = int(np.minimum(sizes, stride).clip(0).sum())
        dst = np.empty(total, dtype=np.uint8)
        self._dll.rp_unpack_rows(rows.ctypes.data, stride, sizes.ctypes.data, n, dst.ctypes.data)
        return dst.tobytes()


@contextlib.contextmanager
def _build_lock(native_dir: str):
    """One on-demand build at a time per checkout: several interpreters that
    import this module at once (pytest -n 6 on a fresh checkout) queue here,
    the first builds, the rest find ``make`` a no-op. The Makefile links to a
    name of its own and renames, so even an importer outside this lock never
    loads a half-written file. A checkout that cannot be written to takes no
    lock (its ``make`` has nothing to write either)."""
    try:
        fd = os.open(
            os.path.join(native_dir, ".build.lock"), os.O_CREAT | os.O_RDWR, 0o644
        )
    except OSError:
        yield
        return
    try:
        fcntl.flock(fd, fcntl.LOCK_EX)
        yield
    finally:
        os.close(fd)  # closing drops the lock


def _stale(so: str, src: str) -> bool:
    """``make``'s own rule, asked without starting it: there is a source, and
    no library or an older one. A current library is whole (it was renamed
    into place), so its importers take no lock and start no process: a
    cluster of brokers and six test workers starting together would
    otherwise queue on the lock for one no-op ``make`` each."""
    try:
        return not os.path.exists(so) or os.path.getmtime(so) < os.path.getmtime(src)
    except OSError:
        return False  # no source: nothing to build from


def _build_and_load(native_dir: str = _NATIVE_DIR):
    """(lib or None, build error or None). A library older than its source
    (or none) is built with ``make``, under the build lock; inside it
    ``make`` decides again, so of several importers one builds. A failed build is logged at
    ERROR and kept in ``build_error`` whether or not an older .so could
    still be loaded — ``status()`` carries it into the broker's
    /v1/coproc/status, and chip_smoke.py fails on it."""
    error = None
    so = os.path.join(native_dir, os.path.basename(_SO))
    src = os.path.join(native_dir, "redpanda_native.cc")
    if _stale(so, src):
        try:
            with _build_lock(native_dir):
                subprocess.run(
                    ["make", "-C", native_dir],
                    check=True,
                    capture_output=True,
                    timeout=120,
                )
        except subprocess.CalledProcessError as exc:
            error = f"make failed ({exc.returncode}): " + exc.stderr.decode(
                errors="replace"
            )[-2000:]
        except (OSError, subprocess.TimeoutExpired) as exc:
            error = f"make did not run: {exc!r}"
    loaded = None
    if os.path.exists(so):
        try:
            loaded = _NativeLib(ctypes.CDLL(so))
        except (OSError, AttributeError) as exc:
            # AttributeError = a stale .so missing a required symbol; a
            # raising module-level import would evict the module and
            # re-run `make` on every later _native() call
            error = (error + "; " if error else "") + f"load failed: {exc!r}"
    elif error is None:
        error = f"{so} does not exist and there is no source to build it"
    if error is not None:
        logger.error(
            "native library %s: %s",
            "is STALE (older build loaded)" if loaded else "unavailable "
            "(numpy twins in use)",
            error,
        )
    return loaded, error


lib, build_error = _build_and_load()


def status() -> dict:
    """Whether the native library loaded, from what build, and which
    optional entry points (``has_*``) it exports."""
    return {
        "loaded": lib is not None,
        "build_error": build_error,
        "symbols": {
            k: bool(v) for k, v in sorted(vars(lib).items())
            if k.startswith("has_")
        } if lib is not None else {},
    }
