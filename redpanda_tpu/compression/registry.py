"""Codec registry (parity with compression/compression.h:21, compression.cc:18-54).

Static dispatch over ``models.record.Compression`` with a pluggable backend
boundary: the default ``host`` backend runs native codecs (zlib, zstd via the
zstandard package, lz4-frame and snappy via ctypes on the system libraries);
a ``tpu`` backend can be registered to route batch payload (de)compression
through the device bridge (the plugin seam the north star requires — the CPU
path stays intact).
"""

from __future__ import annotations

from typing import Callable

from redpanda_tpu.models.record import Compression
from redpanda_tpu.compression import codecs as _codecs


class CompressionError(Exception):
    pass


class _Backend:
    """``table[codec]`` is ``(compress, uncompress)`` and, where the codec
    has a many-frames form, a third entry ``uncompress_many(frames, pool)``
    (see ``uncompress_many`` below)."""

    def __init__(self, name: str, table: dict[Compression, tuple[Callable, ...]]):
        self.name = name
        self.table = table

    def compress(self, data: bytes, codec: Compression) -> bytes:
        if codec == Compression.none:
            return data
        try:
            fn = self.table[codec][0]
        except KeyError:
            raise CompressionError(f"codec {codec.name} unsupported by backend {self.name}")
        return fn(data)

    def uncompress(self, data: bytes, codec: Compression) -> bytes:
        if codec == Compression.none:
            return data
        try:
            fn = self.table[codec][1]
        except KeyError:
            raise CompressionError(f"codec {codec.name} unsupported by backend {self.name}")
        return fn(data)


_HOST = _Backend(
    "host",
    {
        Compression.gzip: (_codecs.gzip_compress, _codecs.gzip_uncompress),
        Compression.zstd: (
            _codecs.zstd_compress,
            _codecs.zstd_uncompress,
            _codecs.zstd_uncompress_many,
        ),
        Compression.lz4: (_codecs.lz4_compress, _codecs.lz4_uncompress),
        Compression.snappy: (_codecs.snappy_compress, _codecs.snappy_uncompress),
    },
)

_backends: dict[str, _Backend] = {"host": _HOST}
_active = _HOST


def register_backend(name: str, table: dict[Compression, tuple[Callable, ...]], *, activate: bool = False):
    global _active
    backend = _Backend(name, table)
    _backends[name] = backend
    if activate:
        _active = backend
    return backend


def active_backend() -> str:
    return _active.name


def compress(data: bytes, codec: Compression | int) -> bytes:
    return _active.compress(bytes(data), Compression(codec))


def uncompress(data: bytes, codec: Compression | int) -> bytes:
    return _active.uncompress(bytes(data), Compression(codec))


def uncompress_many(frames: list[bytes], codec: Compression | int, pool):
    """Many frames of ONE codec decompressed in one crossing, off the
    interpreter lock, into a buffer out of ``pool`` (``acquire(nbytes)`` /
    ``release(buf)``): ``(buf, off, ln)`` with frame i at ``buf[off[i] :
    off[i] + ln[i]]`` and ``ln[i] == -1`` for a frame this form leaves to
    ``uncompress`` (no stated content size; truncated or corrupt: whatever
    ``uncompress`` makes of such a frame stays what the caller gets). The
    buffer is the caller's to release. ``None`` when the active backend has
    no many-frames form for ``codec`` (gzip, snappy, lz4; Zstd without the
    native library): the caller calls ``uncompress`` a frame."""
    entry = _active.table.get(Compression(codec))
    if entry is None or len(entry) < 3:
        return None
    return entry[2](frames, pool)


def is_available(codec: Compression | int) -> bool:
    """Can the active backend actually run this codec in THIS process?

    gzip (stdlib zlib) and zstd (the `zstandard` package, a hard
    dependency) always are; lz4/snappy need the system libraries.
    """
    codec = Compression(codec)
    if codec == Compression.none:
        return True
    if codec not in _active.table:
        return False  # the active backend's table is authoritative
    if _active is not _HOST:
        return True  # plugin backends declare support via their table
    if codec in (Compression.gzip, Compression.zstd):
        return True
    try:
        if codec == Compression.lz4:
            _codecs._lz4_handle()
        elif codec == Compression.snappy:
            _codecs._snappy_handle()
    except OSError:
        return False
    return True
