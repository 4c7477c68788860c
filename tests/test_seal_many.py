"""The seal in one crossing a launch (PR 38).

``TpuEngine._seal_jobs`` hands a reply's whole list of framed payloads to
``batch_codec.build_output_batches``: one native call (``rp_seal_many``)
that compresses every payload over the threshold and computes both header
CRCs with no interpreter lock held, on up to four threads by the job
count. Held here, on the CPU, against the per-batch seal it replaces
(``batch_codec.build_output_batch``: the single-batch callers' and the
fallback's), which a job the crossing leaves alone still goes through.
"""

import sys
import threading

import numpy as np
import pytest
import zstandard

from redpanda_tpu import compression
from redpanda_tpu.coproc import ProcessBatchRequest, TpuEngine, batch_codec
from redpanda_tpu.coproc.engine import ProcessBatchItem
from redpanda_tpu.models import NTP, Record, RecordBatch
from redpanda_tpu.models.record import Compression, RecordBatchType
from redpanda_tpu.ops.exprs import field
from redpanda_tpu.ops.transforms import where

needs_crossing = pytest.mark.skipif(
    batch_codec.build_output_batches([]) is None,
    reason="the native library has no many-batches seal here (no libzstd?)",
)


def _source(i: int = 0) -> RecordBatch:
    return RecordBatch.build(
        [Record(value=b"x")], base_offset=100 * i, first_timestamp=1_700_000_000_000 + i,
        max_timestamp=1_700_000_000_500 + 3 * i,
        type=RecordBatchType.raft_data if i % 5 else RecordBatchType.checkpoint,
    )


def _one_record_payload(size: int) -> bytes:
    """A records section of exactly ``size`` bytes holding one record."""
    for pad in range(max(size - 12, 0), size + 1):
        payload = Record(value=bytes(97 + (k * 7 + pad) % 26 for k in range(pad))).encode()
        if len(payload) == size:
            return payload
    raise AssertionError(size)


def _records_payload(n: int, width: int, salt: int = 0) -> bytes:
    return b"".join(
        Record(offset_delta=k, value=(b'{"level":"error","code":%d,"msg":"' % (k * 31 + salt))
               .ljust(width, b"m") + b'"}').encode()
        for k in range(n)
    )


def _job(i: int, payload: bytes, kept: int):
    return (_source(i), payload, kept)


SHAPES = {
    "empty_kept_0": lambda: [_job(0, b"", 0)],
    "one_empty_value": lambda: [_job(0, Record(value=b"").encode(), 1)],
    "511B": lambda: [_job(0, _one_record_payload(511), 1)],
    "512B": lambda: [_job(0, _one_record_payload(512), 1)],
    "513B": lambda: [_job(0, _one_record_payload(513), 1)],
    "600B": lambda: [_job(i, _records_payload(9, 50, i), 9) for i in range(5)],
    "9KB": lambda: [_job(i, _records_payload(9, 980, i), 9) for i in range(5)],
    "kept_0_among_kept": lambda: [
        _job(i, b"" if i % 3 == 1 else _records_payload(4, 200, i), 0 if i % 3 == 1 else 4)
        for i in range(7)
    ],
    # a payload launch's count: the crossing splits it over its threads
    "mixed_300": lambda: [
        _job(i, *[(b"", 0), (_one_record_payload(511 + i % 3), 1), (_records_payload(9, 50, i), 9),
                  (_records_payload(9, 980, i), 9), (_records_payload(30, 70, i), 30)][i % 5])
        for i in range(300)
    ],
}


def _assert_same_batch(got: RecordBatch | None, want: RecordBatch | None, raw: bytes):
    if want is None:
        assert got is None
        return
    assert got.verify_kafka_crc() and got.verify_header_crc()
    assert got.records() == want.records()
    assert compression.uncompress(got.payload, got.header.compression) == raw
    gh, wh = got.header, want.header
    same_frame = got.payload == want.payload
    for name in ("base_offset", "type", "attrs", "last_offset_delta", "first_timestamp",
                 "max_timestamp", "producer_id", "producer_epoch", "base_sequence",
                 "record_count", "term"):
        assert getattr(gh, name) == getattr(wh, name), name
    assert gh.size_bytes == 61 + len(got.payload)
    if same_frame:  # then the batch is the per-batch seal's to the bit
        assert (gh.crc, gh.header_crc, gh.size_bytes) == (wh.crc, wh.header_crc, wh.size_bytes)
        assert got.encode_internal() == want.encode_internal()
    if gh.compression == Compression.zstd:
        # one frame that states its content size: what the wheel writes, and
        # what the many-frames decompress needs of a frame it is to take
        assert zstandard.frame_content_size(got.payload) == len(raw)
        assert isinstance(got.payload, bytes)
    elif not gh.attrs:
        assert got.payload is raw  # stored as it came: no copy


@needs_crossing
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_many_form_seals_what_the_per_batch_seal_does(shape):
    jobs = SHAPES[shape]()
    want = [batch_codec.build_output_batch(*j) for j in jobs]
    pool = batch_codec.Arena()
    got = batch_codec.build_output_batches(jobs, pool=pool)
    assert len(got) == len(jobs) and batch_codec.UNSEALED not in got
    for g, w, (_, raw, _) in zip(got, want, jobs):
        _assert_same_batch(g, w, raw)
    # the threshold is the per-batch seal's: stored under it, a frame from it on
    assert [g.header.attrs for g in got if g is not None] == [
        4 if len(raw) >= 512 else 0 for _, raw, kept in jobs if kept]
    frames = [g.payload for g in got if g is not None and g.header.attrs]
    if frames:
        buf, off, ln = compression.uncompress_many(frames, Compression.zstd, pool)
        assert (ln >= 0).all()
        pool.release(buf)
    # its one buffer went back to the pool; a list with no frame took none
    assert pool.stats()["free_buffers"] == (1 if frames else 0)


@needs_crossing
@pytest.mark.parametrize("threshold", [1, 512, 10**9])
def test_the_threshold_and_codec_none_store_the_payload_as_it_came(threshold):
    jobs = SHAPES["mixed_300"]()
    got = batch_codec.build_output_batches(jobs, compress_threshold=threshold)
    none = batch_codec.build_output_batches(jobs, compress_threshold=threshold,
                                            codec=Compression.none)
    for g, n, (src, raw, kept) in zip(got, none, jobs):
        want = batch_codec.build_output_batch(src, raw, kept, compress_threshold=threshold)
        _assert_same_batch(g, want, raw)
        _assert_same_batch(n, batch_codec.build_output_batch(
            src, raw, kept, compress_threshold=threshold, codec=Compression.none), raw)
        if kept:
            assert g.header.attrs == (4 if len(raw) >= threshold else 0) and n.header.attrs == 0


@pytest.mark.parametrize("codec", [Compression.gzip, Compression.lz4, Compression.snappy],
                         ids=lambda c: c.name)
def test_another_codec_takes_the_per_batch_road(codec):
    if not compression.is_available(codec):
        pytest.skip(f"no {codec.name} here")
    jobs = SHAPES["600B"]() + SHAPES["kept_0_among_kept"]()
    assert batch_codec.build_output_batches(jobs, codec=codec) is None
    engine = TpuEngine(row_stride=256, host_workers=0, output_codec=codec)
    try:
        got = engine._seal_jobs(jobs)
        stats = engine.stats()
    finally:
        engine.shutdown()
    for g, (src, raw, kept) in zip(got, jobs):
        _assert_same_batch(g, batch_codec.build_output_batch(src, raw, kept, codec=codec), raw)
        assert g is None or g.header.compression == codec
    n = sum(g is not None for g in got)
    assert stats["n_sealed_batches"] == stats["n_seal_crossings"] == n == 10
    assert stats["seal_arena"]["allocs"] == 0


def _absent_library(monkeypatch):
    monkeypatch.setattr(batch_codec, "_native", lambda: None)


def _absent_libzstd(monkeypatch):
    from redpanda_tpu.native import lib

    monkeypatch.setattr(lib, "has_seal_many", False)


def _another_backend(monkeypatch):
    monkeypatch.setattr(batch_codec, "active_backend", lambda: "tpu")


@pytest.mark.parametrize("absent", [_absent_library, _absent_libzstd, _another_backend],
                         ids=lambda f: f.__name__.lstrip("_"))
def test_without_the_crossing_the_result_is_the_per_batch_seals(absent, monkeypatch):
    if absent is _absent_libzstd and batch_codec._native() is None:
        pytest.skip("no native library here")
    absent(monkeypatch)
    jobs = SHAPES["mixed_300"]()
    assert batch_codec.build_output_batches(jobs) is None
    engine = TpuEngine(row_stride=256, host_workers=0)
    try:
        got = engine._seal_jobs(jobs)
        stats = engine.stats()
    finally:
        engine.shutdown()
    want = [batch_codec.build_output_batch(*j) for j in jobs]
    assert [g and g.encode_internal() for g in got] == [w and w.encode_internal() for w in want]
    assert stats["n_sealed_batches"] == stats["n_seal_crossings"] == 240
    assert stats["t_seal"] > 0


def _bound(nbytes: int) -> int:
    """ZSTD_compressBound of a payload under 128 KB."""
    return nbytes + (nbytes >> 8) + ((131072 - nbytes) >> 11)


class _ShortPool:
    """A pool whose buffers hold ``nbytes`` whatever was asked for."""

    def __init__(self, nbytes: int):
        self.nbytes, self.out = nbytes, 0

    def acquire(self, _asked: int) -> np.ndarray:
        self.out += 1
        return np.empty(self.nbytes, dtype=np.uint8)

    def release(self, _buf) -> None:
        self.out -= 1

    def stats(self) -> dict:
        return {"out": self.out}


@needs_crossing
def test_a_job_the_crossing_cannot_seal_is_left_to_the_per_batch_seal():
    jobs = SHAPES["9KB"]()  # five frames of ~9.3 KB at their bound
    pool = _ShortPool(2 * _bound(len(jobs[0][1])))
    got = batch_codec.build_output_batches(jobs, pool=pool)
    assert pool.out == 0
    assert [g is batch_codec.UNSEALED for g in got] == [False, False, True, True, True]
    for g, j in zip(got[:2], jobs):
        _assert_same_batch(g, batch_codec.build_output_batch(*j), j[1])
    # a stored job needs no room: it is sealed beside the ones that found none
    mixed = jobs + SHAPES["511B"]()
    got = batch_codec.build_output_batches(mixed, pool=_ShortPool(16))
    assert [g is batch_codec.UNSEALED for g in got] == [True] * 5 + [False]
    _assert_same_batch(got[5], batch_codec.build_output_batch(*mixed[5]), mixed[5][1])


@needs_crossing
def test_a_failed_job_comes_back_as_its_exception_with_its_neighbours_sealed(monkeypatch):
    jobs = SHAPES["9KB"]() + SHAPES["600B"]()
    want = [batch_codec.build_output_batch(*j) for j in jobs]
    engine = TpuEngine(row_stride=256, host_workers=0)
    real = batch_codec.build_output_batch
    poisoned = jobs[3][1]

    def refuses_one(src, payload, kept, **kw):
        if payload is poisoned:
            raise compression.registry.CompressionError("poisoned frame")
        return real(src, payload, kept, **kw)

    monkeypatch.setattr(batch_codec, "build_output_batch", refuses_one)
    try:
        # the crossing has room for the first two frames; the rest go one by one
        engine._seal_pool = _ShortPool(2 * _bound(len(jobs[0][1])))
        got = engine._seal_jobs(jobs)
        stats = engine.stats()
        assert isinstance(got[3], compression.registry.CompressionError)
        for i, (g, w) in enumerate(zip(got, want)):
            if i != 3:
                _assert_same_batch(g, w, jobs[i][1])
        # 9 batches: one crossing for 2, one each for the other 7
        assert (stats["n_sealed_batches"], stats["n_seal_crossings"]) == (9, 8)
        # a job the table cannot even hold: every job goes one by one, and the
        # one at fault is the exception build_output_batch raises for it
        engine.reset_stats()
        engine.reset_arenas()
        broken = jobs[:2] + [(_source(), None, 3)] + jobs[5:7]
        got = engine._seal_jobs(broken)
        assert isinstance(got[2], TypeError)
        for g, j in zip(got[:2] + got[3:], broken[:2] + broken[3:]):
            _assert_same_batch(g, real(*j), j[1])
        stats = engine.stats()
        assert (stats["n_sealed_batches"], stats["n_seal_crossings"]) == (4, 4)
    finally:
        engine.shutdown()


@needs_crossing
def test_two_threads_sealing_at_once_stay_correct():
    engine = TpuEngine(row_stride=256, host_workers=0)
    lists = [SHAPES["mixed_300"](), list(reversed(SHAPES["mixed_300"]()))[:257]]
    want = [[batch_codec.build_output_batch(*j) for j in jobs] for jobs in lists]
    errors = []

    def seal(jobs, want):
        try:
            for _ in range(6):
                got = engine._seal_jobs(jobs)
                for g, w, j in zip(got, want, jobs):
                    _assert_same_batch(g, w, j[1])
        except BaseException as exc:  # surfaced below, on the test's thread
            errors.append(exc)

    threads = [threading.Thread(target=seal, args=a) for a in zip(lists, want)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)  # the lock changes hands inside every Python stretch
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        alive = [t.is_alive() for t in threads]
        stats = engine.stats()
    finally:
        sys.setswitchinterval(interval)
        engine.shutdown()
    assert alive == [False, False] and not errors, errors
    assert stats["n_seal_crossings"] == 12
    assert stats["n_sealed_batches"] == 6 * sum(w is not None for ws in want for w in ws)
    # each thread held a buffer of its own while it sealed; both are parked now
    arena = stats["seal_arena"]
    assert 1 <= arena["allocs"] <= 2 and arena["allocs"] + arena["reuses"] == 12
    assert arena["free_buffers"] == arena["allocs"]


def _request(n_batches: int, per_batch: int = 12) -> ProcessBatchRequest:
    def batch(p, k):
        return RecordBatch.build(
            [Record(offset_delta=i, timestamp_delta=i,
                    value=(b'{"level":"%s","code":%d,"msg":"' % ([b"error", b"info"][i % 2],
                           1000 * p + 10 * k + i)).ljust(90, b"m") + b'"}')
             for i in range(per_batch)],
            base_offset=1000 * p + 100 * k, first_timestamp=1000 + k,
        )

    return ProcessBatchRequest([
        ProcessBatchItem(1, NTP.kafka("orders", p), [batch(p, k) for k in range(n_batches // 4)])
        for p in range(4)
    ])


@needs_crossing
def test_the_counters_say_how_often_the_crossing_engages():
    from redpanda_tpu.metrics import registry
    from redpanda_tpu.observability import probes

    before = {k: c.value for k, c in probes.coproc_seal.items()}
    engine = TpuEngine(row_stride=256, host_workers=0, force_mode="columnar_host")
    try:
        engine.enable_coprocessors([(1, where(field("level") == "error").to_json(), ("orders",))])
        for _ in range(3):  # three replies of 40 output batches, each sealed in one crossing
            reply = engine.process_batch(_request(40))
            out = [b for it in reply.items for b in it.batches]
            assert len(out) == 40
            for b in out:
                assert b.verify_kafka_crc() and b.verify_header_crc()
                assert b.header.compression == Compression.zstd  # 6 x ~110 B: over the threshold
                assert [r.value[:16] for r in b.records()] == [b'{"level":"error"'] * 6
        stats = engine.stats()
    finally:
        engine.shutdown()
    assert (stats["n_sealed_batches"], stats["n_seal_crossings"]) == (120, 3)
    assert stats["seal_arena"] == {**stats["seal_arena"], "allocs": 1, "reuses": 2,
                                   "free_buffers": 1}
    after = {k: c.value for k, c in probes.coproc_seal.items()}
    assert {k: after[k] - before[k] for k in after} == {
        "n_sealed_batches": 120, "n_seal_crossings": 3}
    text = registry.render_prometheus()
    for name in ("coproc_sealed_batches_total", "coproc_seal_crossings_total",
                 'coproc_stage_latency_us_count{stage="seal"}'):
        assert name in text


@needs_crossing
def test_a_reply_with_nothing_kept_counts_no_crossing():
    engine = TpuEngine(row_stride=256, host_workers=0)
    try:
        assert engine._seal_jobs([]) == []
        assert engine._seal_jobs(SHAPES["empty_kept_0"]() * 3) == [None] * 3
        stats = engine.stats()
    finally:
        engine.shutdown()
    assert stats.get("n_sealed_batches", 0) == 0 and stats.get("n_seal_crossings", 0) == 0
    assert stats["seal_arena"]["allocs"] == 0


@needs_crossing
def test_the_binding_refuses_columns_that_are_not_one_a_job():
    from redpanda_tpu.native import lib

    payloads = [b"a" * 600, b"b" * 600]
    cols = dict(kept=np.ones(2, np.int32), types=np.ones(2, np.int8),
                first_ts=np.zeros(2, np.int64), max_ts=np.zeros(2, np.int64))
    dst = np.empty(4096, np.uint8)
    kw = dict(threshold=512, codec=4, level=3, n_threads=4)
    off, ln, attrs, crc, header_crc = lib.seal_many(payloads, **cols, dst=dst, **kw)
    assert attrs.tolist() == [4, 4] and (ln > 0).all() and off.tolist()[0] == 0
    for bad in ({"kept": np.ones(3, np.int32)}, {"types": np.ones(2, np.int32)},
                {"first_ts": np.zeros(4, np.int64)[::2]}):
        with pytest.raises(ValueError, match="one entry a job"):
            lib.seal_many(payloads, **{**cols, **bad}, dst=dst, **kw)
    with pytest.raises(ValueError, match="contiguous uint8"):
        lib.seal_many(payloads, **cols, dst=np.empty(4096, np.int8), **kw)
    # a codec the crossing does not have serves no job
    assert lib.seal_many(payloads, **cols, dst=dst, **{**kw, "codec": 3}) is None
