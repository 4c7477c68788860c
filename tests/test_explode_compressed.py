"""The payload lane's explode over compressed input (PR 33).

``batch_codec.launch_payloads`` decompresses a launch's batches in a number
of crossings that does not grow with their count (one many-frames crossing
a codec that has the form, into a buffer out of the engine's pool) and
``explode_ptrs`` parses every record's (offset, length) in one more. Held
here, on the CPU, against the per-batch oracle the lane ran until PR 33
(``uncompress`` a batch, then the Python framing walk), and through
``TpuEngine`` on configuration ``json64p-v1map-zstd``'s script against the
benchmark's plain reference.
"""

import ctypes
import importlib.util
import json
import os

import numpy as np
import pytest
import zstandard

from redpanda_tpu import compression
from redpanda_tpu.compression import codecs
from redpanda_tpu.coproc import EnableResponseCode, ProcessBatchRequest, TpuEngine, batch_codec
from redpanda_tpu.coproc.engine import ProcessBatchItem
from redpanda_tpu.models import NTP, Record, RecordBatch
from redpanda_tpu.models.record import Compression

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
CODECS = [Compression.none, Compression.gzip, Compression.snappy, Compression.lz4,
          Compression.zstd]


def _load(relpath: str):
    path = os.path.join(BENCH, relpath)
    spec = importlib.util.spec_from_file_location("bench_" + relpath[:-3].replace("/", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


docs_text = _load("docs_text.py")


def _values(seed: int, n: int) -> list:
    """Seeded text documents with the framing's edges among them: a null
    value, an empty one, one wider than any staging row."""
    out = list(docs_text.make_documents(seed, 1, n)[0])
    out[1:1] = [None, b"", b"w" * 3000]
    return out


def _batch(values, codec, base: int = 0) -> RecordBatch:
    return RecordBatch.build(
        [Record(offset_delta=i, timestamp_delta=i, value=v) for i, v in enumerate(values)],
        base_offset=base, first_timestamp=1000, compression=codec,
    )


def _launch(codec_of_batch, seed: int = 7, per_batch: int = 8) -> list[RecordBatch]:
    values = _values(seed, per_batch * len(codec_of_batch) - 3)
    return [_batch(values[i * per_batch : (i + 1) * per_batch], codec, 100 * i)
            for i, codec in enumerate(codec_of_batch)]


def _oracle(batches):
    """The lane's explode as it ran until PR 33: one ``uncompress`` a batch,
    and the Python framing walk for the offsets."""
    payloads, offs, lens, ranges, n = [], [], [], [], 0
    for b in batches:
        payload = compression.uncompress(b.payload, b.header.compression)
        off, ln = batch_codec._parse_record_values_py(payload, b.header.record_count)
        payloads.append(payload)
        offs.append(off)
        lens.append(ln)
        ranges.append((n, n + b.header.record_count))
        n += b.header.record_count
    return payloads, offs, lens, ranges


def _explode(batches, pool=None, count=None):
    pe = batch_codec.explode_ptrs(batches, pool, count)
    if pe is None:
        pytest.skip("the native library has no pointer-table explode here")
    return pe


def _assert_same(pe, batches):
    payloads, offs, lens, ranges = _oracle(batches)
    assert [bytes(memoryview(p)) for p in pe.payloads] == payloads
    assert len(pe.payloads) == len(batches)
    assert pe.ranges == ranges
    for got, want in zip(pe.rel_off, offs):
        assert got.dtype == np.int64 and np.array_equal(got, want)
    for got, want in zip(pe.rel_len, lens):
        assert got.dtype == np.int32 and np.array_equal(got, want)
    flat = np.concatenate(lens) if lens else np.zeros(0, np.int32)
    assert pe.sizes.dtype == np.int32 and np.array_equal(pe.sizes, np.maximum(flat, 0))
    assert np.array_equal(pe.offsets, np.concatenate(offs) if offs else np.zeros(0, np.int64))
    # the table the native crossings read says the same as the payloads
    assert pe.payloads.lens.tolist() == [len(p) for p in payloads]
    for ptr, p in zip(pe.payloads.ptrs.tolist(), payloads):
        assert ctypes.string_at(ptr, len(p)) == p


# ------------------------------------------------------------------ against the oracle
@pytest.mark.parametrize("codec", CODECS, ids=lambda c: c.name)
def test_one_crossing_explode_matches_the_per_batch_oracle(codec):
    if not compression.is_available(codec):
        pytest.skip(f"no {codec.name} library here")
    batches = _launch([codec] * 5)
    pool = batch_codec.Arena()
    pe = _explode(batches, pool)
    _assert_same(pe, batches)
    pe.release()
    pooled = codec == Compression.zstd
    assert pool.stats()["allocs"] == (1 if pooled else 0)
    assert pool.stats()["free_buffers"] == (1 if pooled else 0)


def test_mixed_codecs_in_one_launch():
    mix = [c for c in CODECS if compression.is_available(c)]
    batches = _launch([mix[i % len(mix)] for i in range(11)] + [Compression.zstd])
    counted = []
    pe = _explode(batches, batch_codec.Arena(), lambda *a: counted.append(a))
    _assert_same(pe, batches)
    n_zstd = sum(b.header.compression == Compression.zstd for b in batches)
    n_other = sum(b.header.compression not in (Compression.none, Compression.zstd)
                  for b in batches)
    (n_batches, n_crossings, bytes_in, bytes_out, dt), = counted
    assert n_batches == n_zstd + n_other
    # Zstd's batches share two crossings; the others take one each
    assert n_crossings == 2 + n_other and dt > 0
    assert bytes_in == sum(len(b.payload) for b in batches
                           if b.header.compression != Compression.none)
    assert bytes_out == sum(len(p) for p, b in zip(_oracle(batches)[0], batches)
                            if b.header.compression != Compression.none)


@pytest.mark.parametrize("codec", [Compression.none, Compression.zstd], ids=lambda c: c.name)
def test_an_empty_batch_and_an_empty_launch(codec):
    empty = _batch([], codec, 50)
    assert empty.header.record_count == 0
    batches = _launch([codec] * 2)
    batches.insert(1, empty)
    pe = _explode(batches, batch_codec.Arena())
    _assert_same(pe, batches)
    assert pe.ranges[1] == (8, 8)
    none = _explode([], batch_codec.Arena())
    assert len(none.payloads) == 0 and none.ranges == [] and len(none.sizes) == 0


def _streamed(batch: RecordBatch) -> RecordBatch:
    """The batch with its payload as a streaming producer seals it: a Zstd
    frame whose header states no content size."""
    plain = compression.uncompress(batch.payload, Compression.zstd)
    co = zstandard.ZstdCompressor(level=3).compressobj()
    frame = co.compress(plain) + co.flush()
    assert zstandard.frame_content_size(frame) == -1
    out = RecordBatch(batch.header, frame)
    return out


def test_a_frame_without_content_size_decodes_beside_sized_ones():
    batches = _launch([Compression.zstd] * 4)
    batches[2] = _streamed(batches[2])
    counted = []
    pool = batch_codec.Arena()
    pe = _explode(batches, pool, lambda *a: counted.append(a))
    _assert_same(pe, batches)
    assert type(pe.payloads[2]) is bytes and type(pe.payloads[1]) is np.ndarray
    assert counted[0][0] == 4 and counted[0][1] == 3  # two for three frames, one for the fourth
    pe.release()
    assert pool.stats()["free_buffers"] == 1


def _garbled(payload: bytes) -> bytes:
    mid = len(payload) // 2
    return payload[:mid] + bytes(b ^ 0x5A for b in payload[mid : mid + 24]) + payload[mid + 24 :]


@pytest.mark.parametrize("damage", ["truncated", "truncated_streamed", "bad_magic",
                                    "garbled_block", "short_records"])
def test_a_corrupt_frame_fails_as_today_and_leaves_the_pool_whole(damage):
    """Whatever the per-batch explode made of a damaged batch until PR 33
    (the codec's ZstdError, or the parser's ValueError over what a
    truncated frame yields), the one-crossing explode makes of it: the
    frame the many-frames crossing cannot fill exactly is handed to the
    per-batch codec. Every buffer taken is back in the pool."""
    if batch_codec.explode_ptrs(_launch([Compression.none])) is None:
        pytest.skip("the native library has no pointer-table explode here")
    batches = _launch([Compression.zstd] * 6)
    victim = batches[3]
    if damage == "truncated":
        payload = victim.payload[:-7]
    elif damage == "truncated_streamed":
        payload = _streamed(victim).payload[:-7]
    elif damage == "bad_magic":
        payload = b"\x00\x01\x02\x03" + victim.payload[4:]
    elif damage == "garbled_block":
        payload = _garbled(victim.payload)
    else:
        plain = compression.uncompress(victim.payload, Compression.zstd)
        payload = compression.compress(plain[:-9], Compression.zstd)
    batches[3] = RecordBatch(victim.header, payload)
    from redpanda_tpu.native import lib

    def as_today():
        out = []
        for b in batches:
            plain = compression.uncompress(b.payload, b.header.compression)
            off, ln = lib.parse_record_values(plain, b.header.record_count)
            out.append((plain, off.tolist(), ln.tolist()))
        return out

    try:
        today = as_today()
    except (zstandard.ZstdError, ValueError) as exc:
        today = type(exc)
    pool = batch_codec.Arena()
    try:
        pe = batch_codec.explode_ptrs(batches, pool)
        got = [(bytes(memoryview(p)), o.tolist(), n.tolist())
               for p, o, n in zip(pe.payloads, pe.rel_off, pe.rel_len)]
        pe.release()
    except (zstandard.ZstdError, ValueError) as exc:
        got = type(exc)
    assert got == today
    assert (damage == "garbled_block") == isinstance(today, list)  # the others raise
    s = pool.stats()
    assert s["allocs"] + s["reuses"] == s["free_buffers"] == 1
    # and the pool serves the next launch
    good = _launch([Compression.zstd] * 6)
    pe = _explode(good, pool)
    _assert_same(pe, good)
    pe.release()
    s = pool.stats()
    assert s["allocs"] + s["reuses"] == 2 and s["free_buffers"] == s["allocs"]


def test_buffers_are_reused_across_launches_and_never_shared_while_held():
    pool = batch_codec.Arena()
    first = _launch([Compression.zstd] * 6, seed=3)
    second = _launch([Compression.zstd] * 6, seed=4)
    a = _explode(first, pool)
    b = _explode(second, pool)  # a still holds its buffer: b gets another
    assert a.payloads._bufs[0] is not b.payloads._bufs[0]
    assert pool.stats()["allocs"] == 2 and pool.stats()["reuses"] == 0
    _assert_same(a, first)
    _assert_same(b, second)
    held = a.payloads._bufs[0]
    a.release()
    a.release()  # idempotent: the buffer is parked once
    assert pool.stats()["free_buffers"] == 1
    with pytest.raises(ValueError, match="release"):
        a.payloads[0]
    c = _explode(first, pool)  # the parked buffer, not a third
    assert c.payloads._bufs[0] is held
    assert pool.stats() == {**pool.stats(), "allocs": 2, "reuses": 1, "free_buffers": 0}
    _assert_same(c, first)
    _assert_same(b, second)  # untouched by c's decompress


def test_uncompressed_batches_pass_through_untouched():
    batches = _launch([Compression.none] * 4)
    counted = []
    pool = batch_codec.Arena()
    pe = _explode(batches, pool, lambda *a: counted.append(a))
    assert counted == [] and pool.stats()["allocs"] == 0
    for got, b in zip(pe.payloads, batches):
        assert got is b.payload  # the batch's own bytes: no copy
    mixed = _launch([Compression.none, Compression.zstd, Compression.none])
    pe = _explode(mixed, pool)
    assert pe.payloads[0] is mixed[0].payload and pe.payloads[2] is mixed[2].payload


@pytest.mark.parametrize("n_batches", [3, 40, 400])
def test_crossings_do_not_grow_with_the_batches(n_batches, monkeypatch):
    from redpanda_tpu.native import lib

    calls = []
    for name in ("zstd_frame_sizes", "zstd_uncompress_many", "parse_many_ptrs",
                 "parse_record_values"):
        real = getattr(lib, name)
        monkeypatch.setattr(
            lib, name, lambda *a, _n=name, _f=real, **k: (calls.append(_n), _f(*a, **k))[1])
    per_batch = []
    real_uncompress = batch_codec.uncompress
    monkeypatch.setattr(batch_codec, "uncompress",
                        lambda *a: (per_batch.append(1), real_uncompress(*a))[1])
    batches = _launch([Compression.zstd] * n_batches, per_batch=4)
    counted = []
    pe = _explode(batches, batch_codec.Arena(), lambda *a: counted.append(a))
    assert calls == ["zstd_frame_sizes", "zstd_uncompress_many", "parse_many_ptrs"]
    assert per_batch == [] and counted[0][:2] == (n_batches, 2)
    assert len(pe.sizes) == 4 * n_batches


def test_the_many_frames_entry_point_of_the_registry():
    frames = [codecs.zstd_compress(bytes([65 + i]) * (1000 + i)) for i in range(5)]
    pool = batch_codec.Arena()
    many = compression.uncompress_many(frames, Compression.zstd, pool)
    if many is None:
        pytest.skip("the native library has no many-frames Zstd here")
    buf, off, ln = many
    assert [bytes(buf[o : o + n]) for o, n in zip(off, ln)] == [
        compression.uncompress(f, Compression.zstd) for f in frames]
    assert ln.tolist() == [1000, 1001, 1002, 1003, 1004] and off.tolist() == [0, 1000, 2001, 3003, 4006]
    # a codec with no many-frames form says so; the caller goes a frame at a time
    for codec in (Compression.gzip, Compression.snappy, Compression.lz4):
        assert compression.uncompress_many([b"x"], codec, pool) is None
    # only the first frame of a payload is read, as the per-batch codec reads it
    two = frames[0] + frames[1]
    buf2, off2, ln2 = compression.uncompress_many([two], Compression.zstd, pool)
    assert bytes(buf2[: ln2[0]]) == compression.uncompress(two, Compression.zstd) == b"A" * 1000


def test_the_joined_blob_explode_shares_the_helper():
    """``explode_batches`` (the classic lane, and the oracle of the staging
    parity tests) and the structural parse decompress through the same
    helper: same tables as before, and the same count."""
    batches = _launch([Compression.zstd, Compression.none, Compression.gzip, Compression.zstd])
    payloads, offs, lens, ranges = _oracle(batches)
    counted = []
    ex = batch_codec.explode_batches(batches, count=lambda *a: counted.append(a))
    assert ex.joined == b"".join(payloads) and ex.ranges == ranges
    starts = np.cumsum([0] + [len(p) for p in payloads[:-1]])
    assert np.array_equal(ex.offsets, np.concatenate([o + s for o, s in zip(offs, starts)]))
    assert np.array_equal(ex.sizes, np.maximum(np.concatenate(lens), 0))
    assert counted[0][0] == 3
    sp = batch_codec.explode_find_structural(
        [b for b in batches], ["level"], need_joined=True, count=lambda *a: counted.append(a))
    if sp is not None:
        assert bytes(sp.joined) == ex.joined and np.array_equal(sp.val_off, ex.offsets)
        assert counted[1][:2] == counted[0][:2]


# ------------------------------------------------------------------ through the engine
def _config() -> dict:
    with open(os.path.join(BENCH, "configs", "json64p-v1map-zstd.json")) as f:
        return json.load(f)


def _run_engine(config: dict, parts, codec) -> tuple[list, dict]:
    engine = TpuEngine(row_stride=config["reference"]["params"]["row_stride"], host_workers=0)
    try:
        assert engine.enable_coprocessors(
            [(1, json.dumps(config["script"]["spec"]), ("bench",))]
        ) == [EnableResponseCode.success]
        assert engine._plans[1].mode == "payload" and not engine._plans[1].byte_identity
        outs = []
        for _ in range(2):  # two launches: the second reuses the first's buffer
            req = ProcessBatchRequest([
                ProcessBatchItem(1, NTP.kafka("bench", p), [
                    _batch(values[s : s + 32], codec, 1000 * p + s)
                    for s in range(0, len(values), 32)])
                for p, values in enumerate(parts)
            ])
            reply = engine.submit(req).result()
            outs.append([[r.value for b in item.batches for r in b.records()]
                         for item in reply.items])
        assert outs[0] == outs[1]
        return outs[0], engine.stats()
    finally:
        engine.shutdown()


@pytest.mark.parametrize("seed", [7, 2**31 + 11, 3000003301])
def test_engine_on_zstd_input_matches_the_plain_reference(seed):
    """Configuration ``json64p-v1map-zstd``: Zstd-sealed batches of 32 seeded
    ``docs_text`` documents through ``TpuEngine`` give ``project_error_v1``'s
    output byte for byte, and the same bytes as the same documents sent
    uncompressed; the counters say what was decompressed."""
    config = _config()
    assert config["producer"]["compression"] == "zstd"
    assert config["documents"]["generator"] == "docs_text.make_documents"
    ref = _load("references/" + config["reference"]["name"] + ".py")
    params = config["reference"]["params"]
    parts = list(docs_text.make_documents(seed, 4, 96, **config["documents"]["params"]).values())
    sealed, stats = _run_engine(config, parts, Compression.zstd)
    plain, plain_stats = _run_engine(config, parts, Compression.none)
    want = [[o for o in (ref.reference(v, **params) for v in values) if o is not None]
            for values in parts]
    assert sealed == want and plain == want
    assert 0 < sum(map(len, want)) < 4 * 96
    if "t_explode_ptrs" not in stats:
        pytest.skip("the native library has no pointer-table explode here")
    # the counters' arithmetic: 12 batches a launch, two launches, two crossings each
    wire = sum(len(_batch(v[s : s + 32], Compression.zstd).payload)
               for v in parts for s in range(0, 96, 32))
    raw = sum(len(_batch(v[s : s + 32], Compression.none).payload)
              for v in parts for s in range(0, 96, 32))
    assert stats["n_uncompressed_batches"] == 24 and stats["n_uncompress_crossings"] == 4
    assert stats["bytes_uncompress_in"] == 2 * wire and stats["bytes_uncompress_out"] == 2 * raw
    assert 2.5 < raw / wire < 4.0
    assert 0 < stats["t_uncompress"] < stats["t_explode_ptrs"]
    assert stats["uncompress_arena"]["allocs"] == 1 and stats["uncompress_arena"]["reuses"] == 1
    assert stats["uncompress_arena"]["free_buffers"] == 1  # given back after the pack
    for key in ("t_uncompress", "n_uncompressed_batches", "n_uncompress_crossings",
                "bytes_uncompress_in", "bytes_uncompress_out"):
        assert key not in plain_stats
    assert plain_stats["uncompress_arena"]["allocs"] == 0
    assert stats["n_kept_rows"] == plain_stats["n_kept_rows"] == 2 * sum(map(len, want))


def test_a_mask_launch_keeps_its_buffer_until_its_values_are_framed():
    """A filter-only script frames kept values from the decompressed
    payloads, so its launch holds the pooled buffer until that framing is
    done; then it goes back."""
    with open(os.path.join(BENCH, "configs", "json64p-v1.json")) as f:
        config = json.load(f)
    ref = _load("references/" + config["reference"]["name"] + ".py")
    params = config["reference"]["params"]
    values = docs_text.make_documents(5, 1, 96)[0]
    engine = TpuEngine(row_stride=params["row_stride"], host_workers=0)
    try:
        engine.enable_coprocessors([(1, json.dumps(config["script"]["spec"]), ("bench",))])
        req = ProcessBatchRequest([ProcessBatchItem(1, NTP.kafka("bench", 0), [
            _batch(values[s : s + 32], Compression.zstd, s) for s in range(0, 96, 32)])])
        ticket = engine.submit(req)
        if "t_explode_ptrs" not in engine.stats():
            pytest.skip("the native library has no pointer-table explode here")
        held = engine.stats()["uncompress_arena"]
        assert held["allocs"] == 1 and held["free_buffers"] == 0
        reply = ticket.result()
        got = [r.value for b in reply.items[0].batches for r in b.records()]
        assert got == [v for v in values if ref.reference(v, **params) is not None]
        assert engine.stats()["uncompress_arena"]["free_buffers"] == 1
        assert engine.stats()["n_frame_gather"] == 1
    finally:
        engine.shutdown()


def test_the_metric_twins_and_the_stage_are_on_metrics():
    from redpanda_tpu.metrics import registry
    from redpanda_tpu.observability import probes

    before = {k: c.value for k, c in probes.coproc_uncompress.items()}
    engine = TpuEngine(row_stride=1024, host_workers=0)
    try:
        engine._count_uncompress(7, 2, 300, 900, 0.004)
        stats = engine.stats()
    finally:
        engine.shutdown()
    assert (stats["n_uncompressed_batches"], stats["n_uncompress_crossings"],
            stats["bytes_uncompress_in"], stats["bytes_uncompress_out"]) == (7, 2, 300, 900)
    assert stats["t_uncompress"] == pytest.approx(0.004)
    after = {k: c.value for k, c in probes.coproc_uncompress.items()}
    assert {k: after[k] - before[k] for k in after} == {
        "n_uncompressed_batches": 7, "n_uncompress_crossings": 2,
        "bytes_uncompress_in": 300, "bytes_uncompress_out": 900}
    text = registry.render_prometheus()
    for name in ("coproc_uncompressed_batches_total", "coproc_uncompress_crossings_total",
                 "coproc_uncompress_in_bytes_total", "coproc_uncompress_out_bytes_total",
                 'coproc_stage_latency_us_count{stage="uncompress"}'):
        assert name in text
