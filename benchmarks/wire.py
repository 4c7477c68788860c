"""The benchmark's own Kafka wire code: what the load generator and the
consumer put on the socket and read back, and nothing of the program's
client (a later PR may change ``redpanda_tpu.kafka.client``; it may not
change how the yardstick's clients behave).

Fixed, non-flexible API versions, hand-packed: Produce v7 and Fetch v4.
RecordBatch v2 built and parsed here. The one borrowed function is the
CRC-32C of a produced batch (``crc32c`` argument of ``build_batch``): the
broker verifies it on every produce, so a wrong one fails the run.

A producer seals its batches with the codec its configuration names
(``producer.compression``, ``CODECS``): none, or Zstd through the
``zstandard`` library. The benchmark's processes have no LZ4 library and
may not borrow the program's ``compression/codecs.py``, whose output they
are there to check.
"""

from __future__ import annotations

import asyncio
import struct
from collections import deque

import zstandard

PRODUCE, FETCH = 0, 1
PRODUCE_V, FETCH_V = 7, 4
CLIENT_ID = b"perfbench"
_HDR = struct.Struct(">hhih")  # api_key, api_version, correlation_id, len(client_id)
_BATCH_HDR = struct.Struct(">qiibIhiqqqhii")  # see kafka RecordBatch v2
BATCH_HDR_SIZE = _BATCH_HDR.size  # 61
_CRC_COVER_START = 21
ERR_UNKNOWN_TOPIC = 3
ERR_NOT_LEADER = 6
# producer.compression -> the codec id in a batch's attribute bits 0-2
NONE, ZSTD = 0, 4
CODECS = {"none": NONE, "zstd": ZSTD}
ZSTD_LEVEL = 3  # the library's default, and Kafka's for compression.type=zstd


def _uvarint(n: int) -> bytes:
    out = bytearray()
    while n > 0x7F:
        out.append((n & 0x7F) | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def _varint(n: int) -> bytes:
    return _uvarint((n << 1) ^ (n >> 63))


_SMALL = [_varint(i) for i in range(4096)]


def encode_records(values: list[bytes]) -> bytes:
    """Records of one batch: no key, no headers, offset and timestamp
    deltas = the index."""
    small = _SMALL
    parts = []
    for i, v in enumerate(values):
        d = small[i]
        n = len(v)
        body_len = 1 + len(d) + len(d) + 1 + n + 1
        ln = small[n] if n < 4096 else _varint(n)
        body_len += len(ln)
        parts.append(small[body_len] if body_len < 4096 else _varint(body_len))
        parts.append(b"\x00" + d + d + b"\x01" + ln)
        parts.append(v)
        parts.append(b"\x00")
    return b"".join(parts)


class InputShapeError(ValueError):
    """A configuration names a producer codec or a document generator that
    the benchmark does not have, or a generator broke its contract."""


def codec_id(name: str) -> int:
    """The codec a configuration names; an unknown one is an error that
    names the key."""
    if name not in CODECS:
        raise InputShapeError(
            f"producer.compression {name!r}: the benchmark's producers seal "
            f"batches with one of {sorted(CODECS)}"
        )
    return CODECS[name]


def zstd_compressor() -> zstandard.ZstdCompressor:
    """One a producer process, used from its one thread. The frame header
    carries the content size."""
    return zstandard.ZstdCompressor(level=ZSTD_LEVEL, write_content_size=True)


def build_batch(values: list[bytes], crc32c, first_timestamp: int = 1_000_000,
                codec: int = NONE, compressor: zstandard.ZstdCompressor | None = None) -> bytes:
    """One wire RecordBatch v2 holding ``values``. With ``codec`` ``ZSTD`` the
    records section is one Zstd frame; length and CRC are of the tail as it
    goes on the wire."""
    n = len(values)
    records = encode_records(values)
    if codec == ZSTD:
        records = (compressor or zstd_compressor()).compress(records)
    elif codec != NONE:
        raise ValueError(f"codec {codec}: build_batch seals with {NONE} (none) or {ZSTD} (Zstd)")
    tail = struct.pack(
        ">hiqqqhii", codec, n - 1, first_timestamp, first_timestamp + n - 1,
        -1, -1, -1, n,
    ) + records
    head = struct.pack(">qiibI", 0, len(tail) + 9, -1, 2, crc32c(tail) & 0xFFFFFFFF)
    return head + tail


def produce_frame(topic: str, partition: int, batch: bytes, corr: int,
                  acks: int = -1, timeout_ms: int = 30000) -> bytes:
    """A whole Produce v7 request frame (size prefix included) carrying
    ``batch`` (one or more wire batches back to back) for one partition."""
    t = topic.encode()
    body = b"".join((
        _HDR.pack(PRODUCE, PRODUCE_V, corr, len(CLIENT_ID)), CLIENT_ID,
        struct.pack(">hhii", -1, acks, timeout_ms, 1),
        struct.pack(">h", len(t)), t,
        struct.pack(">iii", 1, partition, len(batch)), batch,
    ))
    return struct.pack(">i", len(body)) + body


def parse_produce_response(frame: bytes) -> tuple[int, int]:
    """(error_code, base_offset) of the single partition answered."""
    pos = 4  # correlation id
    (n_topics,) = struct.unpack_from(">i", frame, pos)
    pos += 4
    if n_topics != 1:
        raise ValueError(f"produce response names {n_topics} topics")
    (tlen,) = struct.unpack_from(">h", frame, pos)
    pos += 2 + tlen
    _n_parts, _index, err, base = struct.unpack_from(">iihq", frame, pos)
    return err, base


def fetch_frame(topic: str, offsets: dict[int, int], corr: int, max_wait_ms: int,
                min_bytes: int, partition_max_bytes: int) -> bytes:
    """A Fetch v4 request frame for some partitions of one topic."""
    t = topic.encode()
    parts = b"".join(
        struct.pack(">iqi", p, off, partition_max_bytes) for p, off in offsets.items()
    )
    body = b"".join((
        _HDR.pack(FETCH, FETCH_V, corr, len(CLIENT_ID)), CLIENT_ID,
        struct.pack(">iiiibi", -1, max_wait_ms, min_bytes, 0x7FFFFFFF, 0, 1),
        struct.pack(">h", len(t)), t,
        struct.pack(">i", len(offsets)), parts,
    ))
    return struct.pack(">i", len(body)) + body


def parse_fetch_response(frame: bytes) -> list[tuple[int, int, int, memoryview]]:
    """[(partition, error_code, high_watermark, records blob)] of a Fetch v4
    response for one topic."""
    mv = memoryview(frame)
    pos = 8  # correlation id, throttle_time_ms
    (n_topics,) = struct.unpack_from(">i", frame, pos)
    pos += 4
    out = []
    for _ in range(n_topics):
        (tlen,) = struct.unpack_from(">h", frame, pos)
        pos += 2 + tlen
        (n_parts,) = struct.unpack_from(">i", frame, pos)
        pos += 4
        for _ in range(n_parts):
            index, err, hwm, _lso, n_aborted = struct.unpack_from(">ihqqi", frame, pos)
            pos += 26 + 16 * max(n_aborted, 0)
            (rlen,) = struct.unpack_from(">i", frame, pos)
            pos += 4
            rlen = max(rlen, 0)
            out.append((index, err, hwm, mv[pos : pos + rlen]))
            pos += rlen
    return out


def walk_batches(blob) -> list[tuple[int, int, int, int]]:
    """Whole batches in a fetched records blob (a trailing partial batch is
    left out, as Kafka allows one): [(start, end, last_offset, n_records)]."""
    out = []
    pos, n = 0, len(blob)
    while pos + BATCH_HDR_SIZE <= n:
        base, length = struct.unpack_from(">qi", blob, pos)
        end = pos + 12 + length
        if length < BATCH_HDR_SIZE - 12 or end > n:
            break
        last_delta, = struct.unpack_from(">i", blob, pos + 23)
        count, = struct.unpack_from(">i", blob, pos + 57)
        out.append((pos, end, base + last_delta, count))
        pos = end
    return out


def decode_batch(raw: bytes, crc32c=None) -> tuple[int, list[bytes | None]]:
    """(base_offset, record values) of one wire batch; Zstd or
    uncompressed. With ``crc32c`` the batch CRC is verified too."""
    (base, _length, _epoch, magic, crc, attrs, _lod, _ft, _mt, _pid, _pe, _bs,
     count) = _BATCH_HDR.unpack_from(raw, 0)
    if magic != 2:
        raise ValueError(f"record batch magic {magic}")
    if crc32c is not None and crc32c(raw[_CRC_COVER_START:]) & 0xFFFFFFFF != crc:
        raise ValueError(f"batch at offset {base}: CRC mismatch")
    payload = raw[BATCH_HDR_SIZE:]
    codec = attrs & 0x07
    if codec == 4:
        payload = zstandard.ZstdDecompressor().decompressobj().decompress(payload)
    elif codec != 0:
        raise ValueError(f"batch at offset {base}: codec {codec} not expected here")
    values: list[bytes | None] = []
    pos = 0
    for _ in range(count):
        ln, pos = _read_varint(payload, pos)
        end = pos + ln
        pos += 1  # attributes
        _, pos = _read_varint(payload, pos)  # timestamp delta
        _, pos = _read_varint(payload, pos)  # offset delta
        klen, pos = _read_varint(payload, pos)
        if klen > 0:
            pos += klen
        vlen, pos = _read_varint(payload, pos)
        values.append(None if vlen < 0 else bytes(payload[pos : pos + vlen]))
        pos = end
    return base, values


def _read_varint(buf, pos: int) -> tuple[int, int]:
    shift = result = 0
    while True:
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            return (result >> 1) ^ -(result & 1), pos
        shift += 7


class Conn:
    """One TCP connection; requests are answered in order, so a deque of
    futures matches responses to requests."""

    def __init__(self, host: str, port: int):
        self.host, self.port = host, port
        self._waiting: deque[asyncio.Future] = deque()
        self._reader = self._writer = self._task = None

    async def open(self) -> "Conn":
        self._reader, self._writer = await asyncio.open_connection(self.host, self.port)
        self._task = asyncio.create_task(self._recv())
        return self

    async def _recv(self) -> None:
        try:
            while True:
                (size,) = struct.unpack(">i", await self._reader.readexactly(4))
                frame = await self._reader.readexactly(size)
                fut = self._waiting.popleft()
                if not fut.done():
                    fut.set_result(frame)
        except (asyncio.IncompleteReadError, ConnectionError, OSError) as exc:
            while self._waiting:
                fut = self._waiting.popleft()
                if not fut.done():
                    fut.set_exception(ConnectionError(f"connection lost: {exc!r}"))

    def request(self, frame: bytes) -> asyncio.Future:
        fut = asyncio.get_running_loop().create_future()
        self._waiting.append(fut)
        self._writer.write(frame)
        return fut

    async def close(self) -> None:
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
        if self._writer is not None:
            self._writer.close()
            try:
                await self._writer.wait_closed()
            except (ConnectionError, OSError):
                pass
