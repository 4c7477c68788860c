"""Foundation layer tests: vint, iobuf, hashing, codecs, record model."""

import numpy as np
import pytest

from redpanda_tpu.utils import (
    IOBuf,
    decode_uvarint,
    decode_zigzag,
    encode_uvarint,
    encode_zigzag,
)
from redpanda_tpu.hashing import crc32c, crc32c_many, jump_consistent_hash, xxhash64
from redpanda_tpu.models import (
    Compression,
    Record,
    RecordBatch,
    RecordBatchType,
    RecordHeader,
    NTP,
    MaterializedNTP,
)
from redpanda_tpu.compression import compress, uncompress


# ------------------------------------------------------------------ vint
def test_uvarint_roundtrip():
    for v in [0, 1, 127, 128, 300, 2**14, 2**21 - 1, 2**32, 2**63 - 1]:
        buf = encode_uvarint(v)
        got, n = decode_uvarint(buf)
        assert got == v and n == len(buf)


def test_zigzag_roundtrip():
    for v in [0, -1, 1, -2, 2, 127, -128, 2**31, -(2**31), 2**62, -(2**62)]:
        buf = encode_zigzag(v)
        got, n = decode_zigzag(buf)
        assert got == v and n == len(buf)


def test_zigzag_golden():
    # protobuf zigzag: 0->0, -1->1, 1->2, -2->3
    assert encode_zigzag(0) == b"\x00"
    assert encode_zigzag(-1) == b"\x01"
    assert encode_zigzag(1) == b"\x02"
    assert encode_zigzag(-2) == b"\x03"


# ------------------------------------------------------------------ iobuf
def test_iobuf_share_append():
    buf = IOBuf(b"hello ")
    buf.append(b"world")
    assert bytes(buf) == b"hello world"
    assert len(buf) == 11
    sub = buf.share(4, 4)
    assert bytes(sub) == b"o wo"
    buf2 = IOBuf()
    buf2.append(buf)
    assert buf2 == b"hello world"


# ------------------------------------------------------------------ hashing
def test_crc32c_golden_vectors():
    # RFC 3720 / google/crc32c test vectors
    assert crc32c(b"123456789") == 0xE3069283
    assert crc32c(b"") == 0x00000000
    assert crc32c(b"\x00" * 32) == 0x8A9136AA
    assert crc32c(bytes(range(32))) == 0x46DD794E


def test_crc32c_incremental():
    data = bytes(range(256)) * 7
    whole = crc32c(data)
    part = crc32c(data[100:], crc32c(data[:100]))
    assert whole == part


def test_crc32c_many_matches_scalar():
    rng = np.random.default_rng(0)
    msgs = [rng.integers(0, 256, size=n, dtype=np.uint8).tobytes() for n in [0, 1, 7, 8, 9, 63, 64, 65, 200]]
    r = max(len(m) for m in msgs)
    rows = np.zeros((len(msgs), r), np.uint8)
    for i, m in enumerate(msgs):
        rows[i, : len(m)] = np.frombuffer(m, np.uint8)
    lens = np.array([len(m) for m in msgs], np.int32)
    got = crc32c_many(rows, lens)
    assert [int(x) for x in got] == [crc32c(m) for m in msgs]


def test_native_crc_matches_numpy():
    from redpanda_tpu.native import lib

    if lib is None:
        pytest.skip("native lib not built")
    rng = np.random.default_rng(1)
    data = rng.integers(0, 256, size=4097, dtype=np.uint8).tobytes()
    assert lib.crc32c(data) == crc32c(data)


def test_jump_hash_properties():
    # stability: bucket only moves forward as bucket count grows
    for key in [12345, 2**63 - 1, 7]:
        prev = jump_consistent_hash(key, 1)
        assert prev == 0
        for n in range(2, 50):
            b = jump_consistent_hash(key, n)
            assert 0 <= b < n


def test_xxhash64():
    assert xxhash64(b"") == 0xEF46DB3751D8E999


# ------------------------------------------------------------------ codecs

def _require_codec(codec):
    from redpanda_tpu.compression import is_available

    if not is_available(codec):
        pytest.skip(f"codec {codec.name} library not installed in this environment")

@pytest.mark.parametrize("codec", [Compression.gzip, Compression.zstd, Compression.lz4, Compression.snappy])
def test_codec_roundtrip(codec):
    _require_codec(codec)
    data = b"the quick brown fox " * 500
    comp = compress(data, codec)
    assert comp != data
    assert uncompress(comp, codec) == data


@pytest.mark.parametrize("codec", [Compression.gzip, Compression.zstd, Compression.lz4, Compression.snappy])
def test_codec_empty(codec):
    _require_codec(codec)
    assert uncompress(compress(b"", codec), codec) == b""


def test_codec_none_passthrough():
    assert compress(b"abc", Compression.none) == b"abc"


def test_zstd_is_safe_from_many_threads():
    """The coproc engine seals output batches from several tick and pool
    threads at once. A zstandard context shared between threads releases
    the GIL inside native code and segfaulted the broker under two
    concurrent scripts; the codec keeps one context per thread."""
    import os
    import sys
    import threading
    import time

    rng = np.random.default_rng(5)
    blobs = [
        bytes(rng.integers(0, 8, 64 * 1024, dtype=np.uint8)) for _ in range(4)
    ]
    stop = time.monotonic() + 1.5
    errors: list[BaseException] = []
    rounds = [0]

    def worker(k: int) -> None:
        try:
            while time.monotonic() < stop:
                blob = blobs[k % len(blobs)]
                assert uncompress(compress(blob, Compression.zstd), Compression.zstd) == blob
                rounds[0] += 1
        except BaseException as exc:  # noqa: BLE001 — reported by the asserting thread
            errors.append(exc)

    threads = [
        threading.Thread(target=worker, args=(k,))
        for k in range(2 * (os.cpu_count() or 4))
    ]
    saved = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
    finally:
        sys.setswitchinterval(saved)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors
    assert rounds[0] > 0


# ------------------------------------------------------------------ record model
def _mk_records(n=5):
    return [
        Record(
            timestamp_delta=i,
            offset_delta=i,
            key=f"key-{i}".encode(),
            value=f"value-{i}-{'x' * i}".encode(),
            headers=(RecordHeader(b"h1", b"v1"),) if i % 2 else (),
        )
        for i in range(n)
    ]


def test_record_roundtrip():
    for rec in _mk_records():
        buf = rec.encode()
        got, n = Record.decode(buf)
        assert n == len(buf)
        assert got == rec


def test_record_null_key_value():
    rec = Record(key=None, value=None)
    got, _ = Record.decode(rec.encode())
    assert got.key is None and got.value is None


def test_batch_build_and_crcs():
    batch = RecordBatch.build(_mk_records(), base_offset=100)
    assert batch.header.record_count == 5
    assert batch.header.last_offset_delta == 4
    assert batch.last_offset == 104
    assert batch.verify_kafka_crc()
    assert batch.verify_header_crc()


def test_batch_internal_roundtrip():
    batch = RecordBatch.build(_mk_records(), base_offset=7, type=RecordBatchType.raft_data)
    buf = batch.encode_internal()
    assert len(buf) == batch.header.size_bytes
    got, n = RecordBatch.decode_internal(buf)
    assert n == len(buf)
    assert got.header == batch.header
    assert got.payload == batch.payload
    assert [r for r in got.records()] == _mk_records()


def test_batch_corruption_detected():
    from redpanda_tpu.models.record import CorruptBatchError

    batch = RecordBatch.build(_mk_records(), base_offset=0)
    buf = bytearray(batch.encode_internal())
    buf[10] ^= 0xFF
    with pytest.raises(CorruptBatchError):
        RecordBatch.decode_internal(buf)


@pytest.mark.parametrize("codec", [Compression.gzip, Compression.zstd, Compression.lz4, Compression.snappy])
def test_batch_compressed_roundtrip(codec):
    _require_codec(codec)
    records = _mk_records(20)
    batch = RecordBatch.build(records, compression=codec)
    assert batch.header.compression == codec
    assert batch.verify_kafka_crc()
    got, _ = RecordBatch.decode_internal(batch.encode_internal())
    assert got.records() == records


def test_batch_reseal_after_transform():
    batch = RecordBatch.build(_mk_records())
    batch.payload = b"".join(r.encode() for r in _mk_records(3))
    assert not batch.verify_kafka_crc()
    batch.header.record_count = 3
    batch.header.last_offset_delta = 2
    batch.reseal()
    assert batch.verify_kafka_crc() and batch.verify_header_crc()


def test_materialized_ntp():
    src = NTP.kafka("orders", 3)
    m = MaterializedNTP(src, "filter1")
    assert m.ntp.topic == "orders.$filter1$"
    parsed = MaterializedNTP.parse(m.ntp)
    assert parsed is not None and parsed.source == src and parsed.script == "filter1"
    assert MaterializedNTP.parse(src) is None
