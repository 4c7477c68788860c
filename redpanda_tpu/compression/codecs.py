"""Individual codec implementations.

- gzip: stdlib zlib (parity with compression/internal/gzip_compressor).
- zstd: `zstandard` package with a per-process reusable compressor
  (parity with the per-core stream_zstd workspace, compression/stream_zstd.h).
- lz4: LZ4 *frame* format via ctypes on the system liblz4
  (parity with compression/internal/lz4_frame_compressor).
- snappy: xerial/java-framed snappy via ctypes on the system libsnappy
  (parity with compression/internal/snappy_java_compressor — Kafka's snappy
  framing is the xerial stream format).
"""

from __future__ import annotations

import ctypes
import ctypes.util
import struct
import threading
import zlib

import zstandard

# ------------------------------------------------------------------ gzip

def gzip_compress(data: bytes) -> bytes:
    co = zlib.compressobj(wbits=31)  # gzip container
    return co.compress(data) + co.flush()


def gzip_uncompress(data: bytes) -> bytes:
    return zlib.decompress(data, wbits=47)  # auto gzip/zlib


# ------------------------------------------------------------------ zstd
# One reusable (compressor, decompressor) pair per THREAD (parity with the
# per-core stream_zstd workspaces): a zstandard object wraps one native
# context and releases the GIL inside it, so two threads in the same
# object crash the process — and the coproc engine seals output batches
# from several tick and pool threads at once.
_zstd_local = threading.local()
# The level every Zstd frame of this package is written at: the per-batch
# codec's below, and the many-batches seal's (batch_codec.build_output_batches).
ZSTD_LEVEL = 3


def _zstd_ctx():
    ctx = getattr(_zstd_local, "ctx", None)
    if ctx is None:
        ctx = _zstd_local.ctx = (
            zstandard.ZstdCompressor(level=ZSTD_LEVEL),
            zstandard.ZstdDecompressor(),
        )
    return ctx


def zstd_compress(data: bytes) -> bytes:
    zc, _ = _zstd_ctx()
    return zc.compress(data)


def zstd_uncompress(data: bytes) -> bytes:
    # Streaming loop: handles frames without a content-size header (the
    # form streaming producers emit) with no fixed output cap.
    _, zd = _zstd_ctx()
    dobj = zd.decompressobj()
    out = dobj.decompress(data)
    return out


# Threads one many-frames decompress (and one many-batches seal) runs on at
# most, the caller's among them; the crossing itself takes fewer for fewer
# than 32 frames a thread. Fixed in code: a launch's ~1,500 frames of ~33 KB
# split evenly, the crossing holds no interpreter lock, and the broker's own
# threads (event loop, harvester) leave cores idle on any host that holds a
# chip (PERF.md section 6, PR 33).
ZSTD_MANY_THREADS = 4


def zstd_uncompress_many(frames: list[bytes], pool):
    """Many Zstd frames in ONE crossing that holds no interpreter lock
    (native rp_zstd_uncompress_many), into one buffer taken from ``pool``
    (``acquire(nbytes)`` / ``release(buf)``, e.g. batch_codec.Arena) and
    the caller's to give back. Returns ``(buf, off, ln)``: frame i is
    ``buf[off[i] : off[i] + ln[i]]``, and ``ln[i] == -1`` for a frame that
    is ``zstd_uncompress``'s to decode or to refuse, as it always was: one
    whose header states no content size (a streaming producer's; that
    codec has no fixed output cap), and one that is truncated, corrupt or
    not of its stated size. ``None`` when the native library or libzstd is
    not there."""
    try:
        from redpanda_tpu.native import lib, src_table
    except Exception:
        lib = None
    if lib is None or not getattr(lib, "has_zstd_many", False):
        return None
    # two crossings and nothing between them that hands the interpreter
    # lock over: numpy drops it for any operation on more than ~500
    # elements, and beside a busy event loop every hand-over costs up to
    # the switch interval (PERF.md section 6, PR 33)
    table = src_table(frames)
    off, ln, total = lib.zstd_frame_sizes(table)
    buf = pool.acquire(total)
    try:
        lib.zstd_uncompress_many(table, buf, off, ln, ZSTD_MANY_THREADS)
    except BaseException:
        pool.release(buf)
        raise
    return buf, off, ln


# ------------------------------------------------------------------ lz4 frame
_LZ4F_VERSION = 100


def _load_lz4():
    path = ctypes.util.find_library("lz4") or "liblz4.so.1"
    lib = ctypes.CDLL(path)
    lib.LZ4F_compressFrameBound.restype = ctypes.c_size_t
    lib.LZ4F_compressFrameBound.argtypes = [ctypes.c_size_t, ctypes.c_void_p]
    lib.LZ4F_compressFrame.restype = ctypes.c_size_t
    lib.LZ4F_compressFrame.argtypes = [
        ctypes.c_void_p, ctypes.c_size_t, ctypes.c_void_p, ctypes.c_size_t, ctypes.c_void_p,
    ]
    lib.LZ4F_isError.restype = ctypes.c_uint
    lib.LZ4F_isError.argtypes = [ctypes.c_size_t]
    lib.LZ4F_createDecompressionContext.restype = ctypes.c_size_t
    lib.LZ4F_createDecompressionContext.argtypes = [ctypes.POINTER(ctypes.c_void_p), ctypes.c_uint]
    lib.LZ4F_freeDecompressionContext.restype = ctypes.c_size_t
    lib.LZ4F_freeDecompressionContext.argtypes = [ctypes.c_void_p]
    lib.LZ4F_decompress.restype = ctypes.c_size_t
    lib.LZ4F_decompress.argtypes = [
        ctypes.c_void_p,
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_size_t),
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_size_t),
        ctypes.c_void_p,
    ]
    return lib


_lz4_lib = None


def _lz4_handle():
    global _lz4_lib
    if _lz4_lib is None:
        _lz4_lib = _load_lz4()
    return _lz4_lib


class _Lz4Proxy:
    def __getattr__(self, name):
        return getattr(_lz4_handle(), name)


_lz4 = _Lz4Proxy()


def lz4_compress(data: bytes) -> bytes:
    bound = _lz4.LZ4F_compressFrameBound(len(data), None)
    dst = ctypes.create_string_buffer(bound)
    n = _lz4.LZ4F_compressFrame(dst, bound, data, len(data), None)
    if _lz4.LZ4F_isError(n):
        raise RuntimeError("LZ4F_compressFrame failed")
    return dst.raw[:n]


def lz4_uncompress(data: bytes) -> bytes:
    ctx = ctypes.c_void_p()
    err = _lz4.LZ4F_createDecompressionContext(ctypes.byref(ctx), _LZ4F_VERSION)
    if _lz4.LZ4F_isError(err):
        raise RuntimeError("LZ4F context creation failed")
    try:
        out = bytearray()
        src = ctypes.create_string_buffer(bytes(data), len(data))
        src_off = 0
        chunk = ctypes.create_string_buffer(256 * 1024)
        while src_off < len(data):
            dst_size = ctypes.c_size_t(len(chunk))
            src_size = ctypes.c_size_t(len(data) - src_off)
            rc = _lz4.LZ4F_decompress(
                ctx,
                chunk, ctypes.byref(dst_size),
                ctypes.byref(src, src_off), ctypes.byref(src_size),
                None,
            )
            if _lz4.LZ4F_isError(rc):
                raise RuntimeError("LZ4F_decompress failed")
            out += chunk.raw[: dst_size.value]
            src_off += src_size.value
            if rc == 0 and src_size.value == 0:
                break
        return bytes(out)
    finally:
        _lz4.LZ4F_freeDecompressionContext(ctx)


# ------------------------------------------------------------------ snappy (xerial-framed)
def _load_snappy():
    path = ctypes.util.find_library("snappy") or "libsnappy.so.1"
    lib = ctypes.CDLL(path)
    lib.snappy_compress.restype = ctypes.c_int
    lib.snappy_compress.argtypes = [
        ctypes.c_char_p, ctypes.c_size_t, ctypes.c_void_p, ctypes.POINTER(ctypes.c_size_t),
    ]
    lib.snappy_uncompress.restype = ctypes.c_int
    lib.snappy_uncompress.argtypes = [
        ctypes.c_void_p, ctypes.c_size_t, ctypes.c_void_p, ctypes.POINTER(ctypes.c_size_t),
    ]
    lib.snappy_max_compressed_length.restype = ctypes.c_size_t
    lib.snappy_max_compressed_length.argtypes = [ctypes.c_size_t]
    lib.snappy_uncompressed_length.restype = ctypes.c_int
    lib.snappy_uncompressed_length.argtypes = [
        ctypes.c_void_p, ctypes.c_size_t, ctypes.POINTER(ctypes.c_size_t),
    ]
    return lib


_snappy_lib = None


def _snappy_handle():
    global _snappy_lib
    if _snappy_lib is None:
        _snappy_lib = _load_snappy()
    return _snappy_lib


class _SnappyProxy:
    def __getattr__(self, name):
        return getattr(_snappy_handle(), name)


_snappy = _SnappyProxy()

_XERIAL_MAGIC = b"\x82SNAPPY\x00"
_XERIAL_HEADER = _XERIAL_MAGIC + struct.pack(">ii", 1, 1)
_XERIAL_BLOCK = 32 * 1024


def _snappy_raw_compress(data: bytes) -> bytes:
    bound = _snappy.snappy_max_compressed_length(len(data))
    dst = ctypes.create_string_buffer(bound)
    n = ctypes.c_size_t(bound)
    rc = _snappy.snappy_compress(data, len(data), dst, ctypes.byref(n))
    if rc != 0:
        raise RuntimeError("snappy_compress failed")
    return dst.raw[: n.value]


def _snappy_raw_uncompress(data: bytes) -> bytes:
    buf = ctypes.create_string_buffer(bytes(data), len(data))
    n = ctypes.c_size_t()
    rc = _snappy.snappy_uncompressed_length(buf, len(data), ctypes.byref(n))
    if rc != 0:
        raise RuntimeError("snappy_uncompressed_length failed")
    dst = ctypes.create_string_buffer(n.value)
    out_n = ctypes.c_size_t(n.value)
    rc = _snappy.snappy_uncompress(buf, len(data), dst, ctypes.byref(out_n))
    if rc != 0:
        raise RuntimeError("snappy_uncompress failed")
    return dst.raw[: out_n.value]


def snappy_compress(data: bytes) -> bytes:
    out = bytearray(_XERIAL_HEADER)
    for i in range(0, max(len(data), 1), _XERIAL_BLOCK):
        block = data[i : i + _XERIAL_BLOCK]
        comp = _snappy_raw_compress(block)
        out += struct.pack(">i", len(comp)) + comp
    return bytes(out)


def snappy_uncompress(data: bytes) -> bytes:
    if data[: len(_XERIAL_MAGIC)] != _XERIAL_MAGIC:
        # raw snappy block (non-java producers)
        return _snappy_raw_uncompress(data)
    pos = len(_XERIAL_HEADER)
    out = bytearray()
    while pos < len(data):
        (blen,) = struct.unpack_from(">i", data, pos)
        pos += 4
        out += _snappy_raw_uncompress(data[pos : pos + blen])
        pos += blen
    return bytes(out)
