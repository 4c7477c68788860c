"""Coproc TPU engine tests (hermetic, in-process — the reference's
supervisor_test_fixture pattern with the real engine instead of a fake)."""

import json

import numpy as np
import pytest

from redpanda_tpu.coproc import (
    TpuEngine,
    ProcessBatchRequest,
    EnableResponseCode,
    DisableResponseCode,
    ErrorPolicy,
)
from redpanda_tpu.coproc.engine import ProcessBatchItem
from redpanda_tpu.models import Compression, NTP, Record, RecordBatch
from redpanda_tpu.ops.transforms import Int, Str, filter_field_eq, identity, map_project


def _json_batch(n, base_offset=0, level_of=lambda i: ["error", "info"][i % 2], codec=Compression.none):
    recs = [
        Record(
            offset_delta=i,
            timestamp_delta=i,
            value=json.dumps(
                {"level": level_of(i), "code": i, "msg": f"m{i}"}, separators=(",", ":")
            ).encode(),
        )
        for i in range(n)
    ]
    return RecordBatch.build(recs, base_offset=base_offset, compression=codec, first_timestamp=1000)


def _deploy(engine, script_id=1, spec=None, topics=("orders",)):
    spec = spec or (filter_field_eq("level", "error") | map_project(Int("code"), Str("msg", 16)))
    codes = engine.enable_coprocessors([(script_id, spec.to_json(), topics)])
    assert codes == [EnableResponseCode.success]
    return spec


def test_enable_disable_lifecycle():
    engine = TpuEngine(row_stride=256)
    _deploy(engine, 7)
    assert engine.heartbeat() == 1
    # duplicate id rejected
    codes = engine.enable_coprocessors([(7, identity().to_json(), ("t",))])
    assert codes == [EnableResponseCode.script_id_already_exists]
    # invalid topics
    codes = engine.enable_coprocessors(
        [(8, identity().to_json(), ()), (9, identity().to_json(), ("x.$mat$",))]
    )
    assert codes == [
        EnableResponseCode.script_contains_no_topics,
        EnableResponseCode.script_contains_invalid_topic,
    ]
    assert engine.disable_coprocessors([7, 99]) == [
        DisableResponseCode.success,
        DisableResponseCode.script_id_does_not_exist,
    ]
    assert engine.heartbeat() == 0


def test_process_batch_filter_project():
    engine = TpuEngine(row_stride=256, compress_threshold=10**9)
    _deploy(engine, 1)
    batch = _json_batch(10)
    req = ProcessBatchRequest([ProcessBatchItem(1, NTP.kafka("orders", 0), [batch])])
    reply = engine.process_batch(req)
    assert len(reply.items) == 1
    out = reply.items[0].batches
    assert len(out) == 1
    ob = out[0]
    assert ob.header.record_count == 5  # evens are "error"
    assert ob.verify_kafka_crc() and ob.verify_header_crc()
    recs = ob.records()
    import struct

    for j, r in enumerate(recs):
        code = struct.unpack_from("<i", r.value, 0)[0]
        slen = struct.unpack_from("<H", r.value, 4)[0]
        assert code == 2 * j
        assert r.value[6 : 6 + slen] == f"m{2 * j}".encode()
        assert r.offset_delta == j


def test_process_batch_compressed_input_and_output():
    engine = TpuEngine(row_stride=256, compress_threshold=1)
    _deploy(engine, 1, spec=filter_field_eq("level", "error") | map_project(Str("msg", 32)))
    batch = _json_batch(20, codec=Compression.lz4)
    reply = engine.process_batch(
        ProcessBatchRequest([ProcessBatchItem(1, NTP.kafka("orders", 3), [batch])])
    )
    ob = reply.items[0].batches[0]
    assert ob.header.compression == Compression.zstd
    assert ob.header.record_count == 10
    assert ob.verify_kafka_crc()
    import struct

    for j, r in enumerate(ob.records()):
        slen = struct.unpack_from("<H", r.value, 0)[0]
        assert r.value[2 : 2 + slen] == f"m{2 * j}".encode()


def test_process_batch_no_survivors():
    engine = TpuEngine(row_stride=256)
    _deploy(engine, 1)
    batch = _json_batch(4, level_of=lambda i: "info")
    reply = engine.process_batch(
        ProcessBatchRequest([ProcessBatchItem(1, NTP.kafka("orders", 0), [batch])])
    )
    assert reply.items[0].batches == []


def test_unknown_script_gets_empty_reply():
    engine = TpuEngine()
    reply = engine.process_batch(
        ProcessBatchRequest([ProcessBatchItem(42, NTP.kafka("t", 0), [_json_batch(2)])])
    )
    assert reply.items[0].batches == [] and reply.items[0].script_id == 42
    engine.shutdown()


def test_error_policy_deregister():
    engine = TpuEngine(row_stride=256)
    _deploy(engine, 1)
    engine.scripts[1]  # exists
    engine._handles[1].policy = ErrorPolicy.deregister
    # Force a failure: corrupt batch (record_count lies about payload)
    batch = _json_batch(3)
    batch.header.record_count = 50
    reply = engine.process_batch(
        ProcessBatchRequest([ProcessBatchItem(1, NTP.kafka("orders", 0), [batch])])
    )
    assert reply.deregistered == [1]
    assert engine.heartbeat() == 0


def test_error_policy_skip_on_failure():
    engine = TpuEngine(row_stride=256)
    _deploy(engine, 1)
    batch = _json_batch(3)
    batch.header.record_count = 50
    reply = engine.process_batch(
        ProcessBatchRequest([ProcessBatchItem(1, NTP.kafka("orders", 0), [batch])])
    )
    assert reply.items[0].batches == [] and not reply.deregistered
    assert engine.heartbeat() == 1


def test_multi_batch_multi_partition():
    engine = TpuEngine(row_stride=256, compress_threshold=10**9)
    _deploy(engine, 1, spec=filter_field_eq("level", "error"))
    items = [
        ProcessBatchItem(
            1, NTP.kafka("orders", p), [_json_batch(8, base_offset=100 * p), _json_batch(6, base_offset=100 * p + 8)]
        )
        for p in range(4)
    ]
    reply = engine.process_batch(ProcessBatchRequest(items))
    assert len(reply.items) == 4
    for it in reply.items:
        assert len(it.batches) == 2
        assert it.batches[0].header.record_count == 4
        assert it.batches[1].header.record_count == 3
        for ob in it.batches:
            for r in ob.records():
                assert b'"level":"error"' in r.value


# ------------------------------------------------------------ async pipeline
def test_submit_group_fuses_and_matches_sync():
    """submit_group must produce byte-identical replies to per-request
    process_batch, with one launch per script across the whole group."""
    engine = TpuEngine(row_stride=256, compress_threshold=10**9)
    _deploy(engine, 1)
    reqs = [
        ProcessBatchRequest(
            [
                ProcessBatchItem(1, NTP.kafka("orders", p), [_json_batch(6, base_offset=10 * g)])
                for p in range(3)
            ]
        )
        for g in range(4)
    ]
    tickets = engine.submit_group(reqs)
    group_replies = [t.result() for t in tickets]
    for req, reply in zip(reqs, group_replies):
        solo = engine.process_batch(req)
        assert len(reply.items) == len(solo.items)
        for a, b in zip(reply.items, solo.items):
            assert a.source == b.source
            assert [x.payload for x in a.batches] == [y.payload for y in b.batches]
            assert [x.header.crc for x in a.batches] == [y.header.crc for y in b.batches]


def test_submit_overlapping_tickets_harvest_out_of_order():
    engine = TpuEngine(row_stride=256, compress_threshold=10**9)
    _deploy(engine, 1)
    t1 = engine.submit(
        ProcessBatchRequest([ProcessBatchItem(1, NTP.kafka("orders", 0), [_json_batch(4)])])
    )
    t2 = engine.submit(
        ProcessBatchRequest([ProcessBatchItem(1, NTP.kafka("orders", 1), [_json_batch(8)])])
    )
    r2 = t2.result()
    r1 = t1.result()
    assert r1.items[0].batches[0].header.record_count == 2  # 4 records, half "error"
    assert r2.items[0].batches[0].header.record_count == 4


def test_submit_group_unknown_script_gets_empty_reply():
    engine = TpuEngine(row_stride=256)
    _deploy(engine, 1)
    req = ProcessBatchRequest(
        [
            ProcessBatchItem(99, NTP.kafka("orders", 0), [_json_batch(2)]),
            ProcessBatchItem(1, NTP.kafka("orders", 1), [_json_batch(2)]),
        ]
    )
    reply = engine.submit(req).result()
    assert len(reply.items) == 2
    by_script = {ri.script_id: ri for ri in reply.items}
    assert by_script[99].batches == []
    assert len(by_script[1].batches) == 1


def test_frame_ranges_matches_per_batch_framing():
    """The launch-wide native frame_many crossing must produce byte-
    identical payloads and kept counts to per-range frame_records (the
    single-batch path it replaced on the rebuild hot path)."""
    import numpy as np

    from redpanda_tpu.coproc import batch_codec

    rng = np.random.default_rng(42)
    n, stride = 200, 48
    rows = rng.integers(0, 256, size=(n, stride), dtype=np.uint8)
    lens = rng.integers(-1, stride + 1, size=n).astype(np.int32)
    keep = (rng.random(n) < 0.6)
    ranges = [(0, 32), (32, 32), (32, 100), (100, 200)]  # incl. empty range
    got = batch_codec.frame_ranges(rows, lens, keep, ranges)
    want = [
        batch_codec.frame_records(rows[s:e], lens[s:e], keep[s:e])
        for s, e in ranges
    ]
    assert got == want
    # pure-python framing agrees too (three-way parity)
    py = []
    for s, e in ranges:
        out = bytearray()
        seq = 0
        from redpanda_tpu.utils.vint import encode_zigzag

        for i in range(s, e):
            if not keep[i]:
                continue
            vlen = max(int(lens[i]), 0)
            body = bytearray(b"\x00")
            body += encode_zigzag(0) + encode_zigzag(seq) + encode_zigzag(-1)
            body += encode_zigzag(vlen) + rows[i, :vlen].tobytes()
            body += encode_zigzag(0)
            out += encode_zigzag(len(body)) + body
            seq += 1
        py.append((bytes(out), seq))
    assert got == py


def test_columnar_host_ablation_matches_device_mode():
    """force_mode='columnar_host' (the bench ablation: same columnar plan,
    predicate evaluated in numpy) must produce byte-identical replies to
    the device-mode engine on every expression kind."""
    from redpanda_tpu.ops.exprs import field
    from redpanda_tpu.ops.transforms import where

    specs = [
        filter_field_eq("level", "error") | map_project(Int("code"), Str("msg", 16)),
        where((field("code") > 3) & ~(field("level") == "info")),
        where(field("msg").contains("m1", window=16)),
        where(field("missing").exists() | (field("code") <= 2)),
    ]
    for spec in specs:
        dev = TpuEngine(
            row_stride=256, compress_threshold=10**9, force_mode="columnar_device"
        )
        host = TpuEngine(
            row_stride=256, compress_threshold=10**9, force_mode="columnar_host"
        )
        for e in (dev, host):
            codes = e.enable_coprocessors([(1, spec.to_json(), ("orders",))])
            assert codes == [EnableResponseCode.success]
        req = ProcessBatchRequest([
            ProcessBatchItem(1, NTP.kafka("orders", p), [_json_batch(8, base_offset=p)])
            for p in range(3)
        ])
        r_dev = [t.result() for t in dev.submit_group([req, req])]
        r_host = [t.result() for t in host.submit_group([req, req])]
        for a, b in zip(r_dev, r_host):
            assert len(a.items) == len(b.items)
            for ia, ib in zip(a.items, b.items):
                assert ia.source == ib.source
                va = [bytes(v) for bt in ia.batches for v in bt.record_values()]
                vb = [bytes(v) for bt in ib.batches for v in bt.record_values()]
                assert va == vb, (spec.to_json(), va, vb)
        dev.shutdown()
        host.shutdown()


def _staging_batches(n_batches=5):
    from redpanda_tpu.models.record import Record as R, RecordBatch as RB

    def mk(n, codec=Compression.none, wide=False):
        recs = [
            R(
                offset_delta=i,
                value=(b"v%03d-" % i) * (40 if wide else (i % 7) + 1),
            )
            for i in range(n)
        ]
        return RB.build(recs, base_offset=0, compression=codec)

    shapes = [mk(12), mk(0), mk(5, Compression.gzip), mk(9, wide=True), mk(3)]
    return [shapes[i % len(shapes)] for i in range(n_batches)]


@pytest.mark.parametrize("part", ["whole", "narrow_rows_at_64", "wide_rows", "every_third_row"])
@pytest.mark.parametrize("pool", ["fresh", "reused_dirty", "smaller_after_larger"])
def test_pack_staged_ptr_lane_bit_parity(pool, part, monkeypatch):
    """The pointer-table payload staging (_pack_staged_ptrs over
    batch_codec.explode_ptrs — no joined blob, one native crossing)
    produces byte-identical staging matrices to the classic joined-blob
    _pack_staged, across compression, empty batches, varied sizes and
    records wider than the row stride — into a fresh matrix, into a parked
    one that a larger launch left all 0xFF, and into the front of a larger
    launch's matrix when the bucket shrinks: pad rows and meta bytes
    included. ``part`` (PR 47): the whole launch at the lane's stride, or
    one part of a launch staged by width class: a selection of its rows,
    at a stride under the lane's own; the selection's matrix is the
    selected rows of the whole launch's at that stride."""
    import numpy as np

    from redpanda_tpu.coproc import batch_codec
    from redpanda_tpu.coproc.engine import _bucket_rows

    batches = _staging_batches()
    pe = batch_codec.explode_ptrs(batches)
    if pe is None:
        pytest.skip("native packer unavailable")
    ex = batch_codec.explode_batches(batches)
    assert pe.ranges == ex.ranges
    assert np.array_equal(pe.sizes, ex.sizes)
    n = len(ex.sizes)
    stride, rows = {
        "whole": (128, None),
        "narrow_rows_at_64": (64, np.flatnonzero(ex.sizes <= 64)),
        "wide_rows": (128, np.flatnonzero(ex.sizes > 64)),
        "every_third_row": (128, np.arange(0, n, 3)),
    }[part]
    k = n if rows is None else len(rows)
    assert 0 < k and (rows is None or k < n)
    oracle = TpuEngine(row_stride=128)
    n_pad = _bucket_rows(k)
    classic = oracle._pack_staged(ex, n_pad, stride, rows, [])
    assert classic.shape == (n_pad, stride + 8) and n_pad > k
    if rows is not None:
        whole = oracle._pack_staged(ex, _bucket_rows(n), stride, None, [])
        assert np.array_equal(classic[:k], whole[rows]) and not classic[k:].any()
    oracle.shutdown()

    engine = TpuEngine(row_stride=128)
    if pool == "reused_dirty":
        big = engine._take_staging(4 * n_pad, 128, [])
        big[:] = 0xFF
        engine._staging.release(big.base)
    elif pool == "smaller_after_larger":
        larger = batch_codec.explode_ptrs(_staging_batches(40))
        assert _bucket_rows(len(larger.sizes)) > n_pad
        big = engine._pack_staged_ptrs(
            larger, _bucket_rows(len(larger.sizes)), 128, None, []
        )
        engine._staging.release(big.base)
    parked: list = []
    ptr = engine._pack_staged_ptrs(pe, n_pad, stride, rows, parked)
    assert np.array_equal(classic, ptr)
    st = engine.stats()
    if pool == "fresh":
        assert st["staging_arena"]["reuses"] == 0 and parked == [False]
    else:
        # the parked matrix of the larger launch served this one
        assert ptr.base is big.base
        assert st["staging_arena"]["reuses"] == 1 and parked == [True]
    # the classic road draws from the same pool, and packs the same bytes
    # over whatever the matrix held, with the native library and without
    import redpanda_tpu.native as native_mod

    for lib in (native_mod.lib, None):
        monkeypatch.setattr(native_mod, "lib", lib)
        ptr[:] = 0xEE
        engine._staging.release(ptr.base)
        again = engine._pack_staged(ex, n_pad, stride, rows, [])
        assert again.base is ptr.base and np.array_equal(classic, again)
    engine.shutdown()


def test_pack_rows_ptrs_bad_span_raises_and_writes_nothing():
    """Every span is bounds-checked against ITS buffer before the first
    byte is written: a table whose record runs past its batch's payload is
    a ValueError and the (reused) matrix still holds what it held."""
    import numpy as np

    from redpanda_tpu.coproc import batch_codec

    pe = batch_codec.explode_ptrs(_staging_batches())
    if pe is None:
        pytest.skip("native packer unavailable")
    from redpanda_tpu.native import lib

    starts, ends = batch_codec._range_cols(pe.ranges)
    n = len(pe.sizes)
    dst = np.full((128, 136), 0xAB, np.uint8)
    for bad_row, bad_off in (
        (n - 1, len(pe.payloads[-1]) - int(pe.sizes[-1]) + 1),  # ends 1 past
        (0, -1),
    ):
        offsets = pe.offsets.copy()
        offsets[bad_row] = bad_off
        with pytest.raises(ValueError):
            lib.pack_rows_ptrs(
                pe.payloads, offsets, pe.sizes, starts, ends, dst, 128
            )
        assert (dst == 0xAB).all()
    # a span inside ANOTHER batch's buffer length but outside its own is
    # still outside: the check is per buffer
    assert len(pe.payloads[3]) > len(pe.payloads[4])
    offsets = pe.offsets.copy()
    offsets[n - 1] = len(pe.payloads[4])
    with pytest.raises(ValueError):
        lib.pack_rows_ptrs(pe.payloads, offsets, pe.sizes, starts, ends, dst, 128)
    assert (dst == 0xAB).all()
    # ranges that do not tile the rows, or a matrix of the wrong shape
    with pytest.raises(ValueError):
        lib.pack_rows_ptrs(
            pe.payloads, pe.offsets, pe.sizes, starts, ends - 1, dst, 128
        )
    with pytest.raises(ValueError):
        lib.pack_rows_ptrs(
            pe.payloads, pe.offsets, pe.sizes, starts, ends, dst[:, :130], 128
        )
    with pytest.raises(ValueError):
        lib.pack_rows_ptrs(
            pe.payloads, pe.offsets, pe.sizes, starts, ends, dst[: n - 1], 128
        )
    assert (dst == 0xAB).all()
    lib.pack_rows_ptrs(pe.payloads, pe.offsets, pe.sizes, starts, ends, dst, 128)
    assert not (dst[n:] != 0).any()


def test_pack_rows_ptrs_refuses_a_bad_row_selection_and_writes_nothing():
    """The row selection's binding (PR 47): rows outside the table or out
    of order, a selected span outside its buffer, a matrix of the wrong
    shape are a ValueError with nothing written; an unselected bad span is
    nobody's business."""
    import numpy as np

    from redpanda_tpu.coproc import batch_codec

    pe = batch_codec.explode_ptrs(_staging_batches())
    if pe is None:
        pytest.skip("native packer unavailable")
    from redpanda_tpu.native import lib

    starts, ends = batch_codec._range_cols(pe.ranges)
    n = len(pe.sizes)
    dst = np.full((128, 72), 0xAB, np.uint8)
    good = np.arange(1, n, 2)

    def pack(rows, offsets=pe.offsets, into=dst, stride=64):
        lib.pack_rows_ptrs(pe.payloads, offsets, pe.sizes, starts, ends, into, stride, rows)

    for rows in ([0, n], [-1, 0], [3, 2], [2, 2]):
        with pytest.raises(ValueError):
            pack(np.array(rows))
        assert (dst == 0xAB).all()
    bad = pe.offsets.copy()
    bad[1] = len(pe.payloads[0])
    with pytest.raises(ValueError):
        pack(good, offsets=bad)
    with pytest.raises(ValueError):
        pack(good, into=dst[:, :70])
    with pytest.raises(ValueError):
        pack(np.arange(n), into=dst[: n - 1])
    assert (dst == 0xAB).all()
    pack(np.arange(0, n, 2), offsets=bad)  # row 1 is not selected
    k = len(range(0, n, 2))
    assert not (dst[k:] != 0).any() and dst[:k].any()


def test_pack_staged_null_empty_and_oversize_values_stage_length_zero():
    """A null value, an empty value and a value wider than the staging row
    all stage length 0 on both roads (the device's keep drops length 0,
    launch.fits drops the oversize one): staged, never truncated."""
    import numpy as np

    from redpanda_tpu.coproc import batch_codec
    from redpanda_tpu.models.record import Record as R, RecordBatch as RB

    values = [b"abc", None, b"", b"x" * 64, b"y" * 65, b"z" * 200, b"tail"]
    batch = RB.build(
        [R(offset_delta=i, value=v) for i, v in enumerate(values)], base_offset=0
    )
    engine = TpuEngine(row_stride=64)
    ex = batch_codec.explode_batches([batch])
    mats = [engine._pack_staged(ex, 128, 64, None, [])]
    pe = batch_codec.explode_ptrs([batch])
    if pe is not None:
        assert [int(x) for x in pe.rel_len[0]] == [3, -1, 0, 64, 65, 200, 4]
        dirty = engine._take_staging(128, 64, [])
        dirty[:] = 0xFF
        engine._staging.release(dirty.base)
        mats.append(engine._pack_staged_ptrs(pe, 128, 64, None, []))
        assert np.array_equal(mats[0], mats[1])
    for staged in mats:
        lens = staged[:, 64:68].copy().view("<i4")[:, 0]
        assert lens[: len(values)].tolist() == [3, 0, 0, 64, 0, 0, 4]
        assert not lens[len(values):].any() and not staged[:, 68:].any()
        assert bytes(staged[0, :4]) == b"abc\0" and not staged[1, :64].any()
        # an oversize value's first row_stride bytes ride along, length 0
        assert bytes(staged[4, :64]) == b"y" * 64
    engine.shutdown()


def test_payload_reply_parity_ptr_vs_classic(monkeypatch):
    """End to end: a payload-plan reply through the pointer-table lane is
    byte-identical to the classic lane (forced by disabling explode_ptrs)."""
    from redpanda_tpu.coproc import batch_codec
    from redpanda_tpu.ops.transforms import filter_contains

    spec = filter_contains(b"m1")

    def run():
        engine = TpuEngine(row_stride=256, compress_threshold=10**9)
        codes = engine.enable_coprocessors([(1, spec.to_json(), ("orders",))])
        assert codes == [EnableResponseCode.success]
        req = ProcessBatchRequest([
            ProcessBatchItem(
                1, NTP.kafka("orders", p),
                [_json_batch(10, base_offset=p), _json_batch(4)],
            )
            for p in range(3)
        ])
        reply = engine.process_batch(req)
        stats = engine.stats()
        engine.shutdown()
        return [
            (it.script_id, [b.payload for b in it.batches])
            for it in reply.items
        ], stats

    got_ptr, st_ptr = run()
    monkeypatch.setattr(batch_codec, "explode_ptrs", lambda batches, *a, **k: None)
    got_classic, st_classic = run()
    assert got_ptr == got_classic
    if "t_explode_ptrs" in st_ptr:  # native present: the lane engaged
        assert "t_explode_ptrs" not in st_classic
        assert "t_explode" in st_classic


def test_stats_name_the_device_and_count_device_launches():
    """n_launches counts every launch; n_device_launches only those whose
    program ran on the device, per script too; the first run of a program
    is its compile, counted apart; and stats() names the platform once
    the engine has touched JAX (never before)."""
    from redpanda_tpu.ops.exprs import field
    from redpanda_tpu.ops.transforms import where

    spec = where(field("level") == "error")
    req = ProcessBatchRequest(
        [ProcessBatchItem(7, NTP.kafka("orders", 0), [_json_batch(20)])]
    )
    stats = {}
    for mode in ("columnar_device", "columnar_host"):
        engine = TpuEngine(row_stride=256, force_mode=mode, host_workers=0)
        _deploy(engine, 7, spec=spec)
        assert engine.stats()["device"] is None
        engine.process_batch(req)
        engine.process_batch(req)
        stats[mode] = engine.stats()
        engine.shutdown()
    dev, host = stats["columnar_device"], stats["columnar_host"]
    assert dev["device"] == {
        "platform": "cpu", "device_kind": "cpu", "count": 8, "cpu_pinned": True,
    }
    assert dev["n_launches"] == dev["n_device_launches"] == 2
    assert dev["device_launches_by_script"] == {7: 2}
    assert dev["n_compiles"] == 1 and dev["t_compile"] > 0
    assert host["n_launches"] == 2
    assert host.get("n_device_launches", 0) == 0
    assert host["device_launches_by_script"] == {}
    assert host["device"] is None


# ------------------------------------------- payload mask launches (ISSUE 27)
def _mask_engine(**kw):
    from redpanda_tpu.ops.transforms import filter_contains

    engine = TpuEngine(
        row_stride=256, compress_threshold=10**9, host_workers=0,
        retry_backoff_ms=1, **kw,
    )
    _deploy(engine, spec=filter_contains(b'"level":"error"'))
    return engine


def _mask_req():
    return ProcessBatchRequest([
        ProcessBatchItem(
            1, NTP.kafka("orders", p),
            [_json_batch(11, base_offset=20 * p), _json_batch(0), _json_batch(6)],
        )
        for p in range(3)
    ])


def _bits(reply):
    return [
        (it.source, [(b.payload, b.header.crc, b.header.record_count) for b in it.batches])
        for it in reply.items
    ]


_MASK_FAULTS = {
    # name -> (engine kwargs, armed probe | None, trip the HARVEST breaker,
    #          the harvester thread never runs)
    "harvest_fault": (dict(launch_retries=1, breaker_threshold=100), "harvest", False, False),
    "harvest_breaker_open": (
        dict(breaker_threshold=1, breaker_cooldown_ms=3_600_000), None, True, False,
    ),
    "dispatch_fault": (
        dict(launch_retries=0, breaker_threshold=100), "device_dispatch", False, False,
    ),
    "starved_harvester_dead_fetch": (
        dict(launch_retries=0, device_deadline_ms=100, breaker_threshold=100,
             adaptive_deadline=False),
        "mask_fetch", False, True,
    ),
}


@pytest.mark.parametrize("name", sorted(_MASK_FAULTS))
def test_payload_mask_launch_faults_end_in_the_exact_host_fallback(name, monkeypatch):
    """A filter-only payload launch rides the ONE mask D2H discipline
    (harvester, _resolve_keep, breaker, claim): a dead harvest, an open
    harvest breaker, a dead dispatch, or a starved harvester with a dead
    caller fetch all end in the numpy twin over the retained staged rows,
    fallback rows counted, output unchanged."""
    from redpanda_tpu.coproc import faults
    from redpanda_tpu.finjector import honey_badger

    kw, probe, trip, starve = _MASK_FAULTS[name]
    clean = _mask_engine()
    baseline = _bits(clean.process_batch(_mask_req()))
    assert clean.stats()["n_frame_gather"] == 1
    clean.shutdown()
    assert any(batches for _, batches in baseline)

    engine = _mask_engine(**kw)
    if trip:
        engine.governor.breaker_for(faults.HARVEST).record_failure()
    if starve:
        monkeypatch.setattr(engine, "_ensure_harvester", lambda: None)
    honey_badger.enable()
    if probe:
        honey_badger.set_exception(faults.MODULE, probe)
    try:
        faulted = _bits(engine.process_batch(_mask_req()))
        stats = engine.stats()
    finally:
        if probe:
            honey_badger.unset(faults.MODULE, probe)
        honey_badger.disable()
        engine.shutdown()
    assert faulted == baseline
    assert stats["n_fallback_rows"] == 51  # every record of the one launch
    assert stats["n_frame_gather"] == 1 and "n_frame_padded" not in stats
    if name == "harvest_fault":
        # one failed mask is ONE verdict and one envelope: the harvester's
        assert stats["breakers"]["harvest"]["consecutive_failures"] == 1
        assert stats["n_retries"] == 1
    if name == "harvest_breaker_open":
        assert stats.get("n_retries", 0) == 0
        assert stats["breakers"]["device_dispatch"]["state"] == faults.STATE_CLOSED
    if name == "dispatch_fault":
        assert stats.get("n_device_launches", 0) == 0


@pytest.mark.parametrize("probe", [None, "device_dispatch", "harvest"])
@pytest.mark.parametrize("gather", [True, False], ids=["mask", "matrix"])
def test_staging_matrix_parks_only_after_a_landed_result(gather, probe):
    """A launch's staging matrix re-enters the pool only once its device
    result has landed; a second launch of the same bucket then takes it
    (n_staging_reuses). A launch whose device leg failed (dispatch or
    harvest, host fallback taken) DROPS its matrix — a transfer may still
    be reading it — and its reply is still exact."""
    from redpanda_tpu.coproc import faults
    from redpanda_tpu.finjector import honey_badger

    clean = _mask_engine(gather_frame=gather)
    baseline = _bits(clean.process_batch(_mask_req()))
    clean.shutdown()
    assert any(batches for _, batches in baseline)

    engine = _mask_engine(
        gather_frame=gather, launch_retries=0, breaker_threshold=100
    )
    honey_badger.enable()
    if probe:
        honey_badger.set_exception(faults.MODULE, probe)
    try:
        first = _bits(engine.process_batch(_mask_req()))
        st1 = engine.stats()
    finally:
        if probe:
            honey_badger.unset(faults.MODULE, probe)
        honey_badger.disable()
    try:
        second = _bits(engine.process_batch(_mask_req()))
        st2 = engine.stats()
    finally:
        engine.shutdown()
    assert first == baseline and second == baseline
    assert st1["staging_arena"]["allocs"] == 1
    if probe is None:
        assert st1["staging_arena"]["free_buffers"] == 1
        assert "n_fallback_rows" not in st1
        assert st2["n_staging_reuses"] == 1
        assert st2["staging_arena"]["allocs"] == 1
        assert st2["staging_arena"]["reuses"] == 1
    else:
        assert st1["n_fallback_rows"] == 51
        assert st1["staging_arena"]["free_buffers"] == 0  # dropped, not parked
        # nothing was parked, so the next launch allocates; it lands and parks
        assert "n_staging_reuses" not in st2
        assert st2["staging_arena"]["allocs"] == 2
        assert st2["staging_arena"]["reuses"] == 0
    assert st2["staging_arena"]["free_buffers"] == 1
    assert st2["n_device_launches"] == (2 if probe != "device_dispatch" else 1)


def test_staging_pool_is_reset_and_trimmed_with_the_arena():
    """reset_arenas() swaps the staging pool with the framing arena, the
    memory-pressure hook trims both, and the pool parks at most
    _STAGING_MAX_PARKED matrices."""
    from redpanda_tpu.coproc import engine as engine_mod
    from redpanda_tpu.resource_mgmt import budgets

    engine = _mask_engine()
    engine.process_batch(_mask_req())
    engine.process_batch(_mask_req())
    st = engine.stats()
    assert st["staging_arena"]["free_buffers"] == 1
    assert st["staging_arena"]["reuses"] == 1
    engine._on_memory_pressure(budgets.PRESSURE_CRITICAL, {})
    st = engine.stats()
    assert st["staging_arena"]["free_buffers"] == 0
    assert st["staging_arena"]["trims"] == 1 and st["arena"]["trims"] == 1
    engine.process_batch(_mask_req())  # allocates again, parks again
    assert engine.stats()["staging_arena"]["allocs"] == 2
    assert engine.stats()["staging_arena"]["free_buffers"] == 1
    engine.reset_arenas()
    st = engine.stats()
    assert st["staging_arena"] == {
        "allocs": 0, "reuses": 0, "alloc_bytes": 0, "free_buffers": 0, "trims": 0,
    }
    assert st["arena"]["allocs"] == 0
    held = [engine._take_staging(128, 256, []) for _ in range(6)]
    for staged in held:
        engine._staging.release(staged.base)
    assert (
        engine.stats()["staging_arena"]["free_buffers"]
        == engine_mod._STAGING_MAX_PARKED
    )
    engine.shutdown()


def test_payload_mask_reentry_after_framing_failure_keeps_the_mask(monkeypatch):
    """_resolve_keep consumes the launch's mask slot; a second framed()
    after a framing failure must reuse the resolved keep, not read the
    emptied slot as keep-all."""
    from redpanda_tpu.coproc import batch_codec

    engine = _mask_engine()
    expected = _bits(engine.process_batch(_mask_req()))
    name = (
        "frame_ranges_gather_ptrs"
        if batch_codec.explode_ptrs([_json_batch(1)]) is not None
        else "frame_ranges_gather"
    )
    real = getattr(batch_codec, name)
    calls = {"n": 0}

    def flaky(*a, **kw):
        calls["n"] += 1
        if calls["n"] == 1:
            raise MemoryError("simulated framing allocation failure")
        return real(*a, **kw)

    monkeypatch.setattr(batch_codec, name, flaky)
    ticket = engine.submit(_mask_req())
    first = ticket.result()  # framing fails -> skip_on_failure empties items
    assert all(not it.batches for it in first.items)
    second = ticket.result()
    engine.shutdown()
    assert calls["n"] == 2
    assert _bits(second) == expected
    assert any(len(b) < 11 for _, b in expected)


@pytest.mark.parametrize("gather", [True, False], ids=["mask", "matrix"])
def test_payload_launch_accounting_follows_what_crosses(gather):
    """bytes_d2h is the mask's size on a mask launch and the matrix's on
    the matrix road; the launch, staging and compile counters count the
    same on both; the governor journals the script's harvest path."""
    from redpanda_tpu.coproc import governor

    engine = _mask_engine(gather_frame=gather)
    engine.process_batch(_mask_req())
    engine.process_batch(_mask_req())
    stats = engine.stats()
    posture = stats["governor"]["posture"]["harvest_path"]
    engine.shutdown()
    assert stats["n_launches"] == stats["n_device_launches"] == 2
    assert stats["n_compiles"] == 1
    assert stats["n_staged_rows"] == 2 * 128 and stats["n_records"] == 2 * 51
    # the values are under 128 B: the matrix is fitted to them, a 136 B row
    # in place of the lane's 264 B one, and on the matrix road a filter's
    # result row follows it
    assert stats["bytes_h2d"] == stats["bytes_staged"] == 2 * 128 * (128 + 8)
    assert "n_split_launches" not in stats
    assert stats["t_fetch"] > 0
    if gather:
        assert stats["bytes_d2h"] == 2 * 128 // 8
        assert stats["n_frame_gather"] == 2 and stats["t_frame_gather"] > 0
        assert "t_rebuild" not in stats and posture == "gather"
    else:
        assert stats["bytes_d2h"] == 2 * 128 * (128 + 8)
        assert stats["n_frame_padded"] == 2 and "t_frame_gather" not in stats
        assert posture == "padded"
    mine = [
        e for e in governor.journal.entries(domain=governor.HARVEST_PATH)
        if e["engine"] == engine.governor.engine_tag
    ]
    # journalled once a script (on change), not once a launch
    assert [e["verdict"] for e in mine] == [posture]
    assert mine[0]["inputs"] == {"script_id": 1, "mode": "payload"}
