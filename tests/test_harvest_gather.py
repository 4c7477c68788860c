"""Zero-copy harvest parity (ISSUE 5).

The gather path's correctness argument is "framing kept records straight
from the joined blob via (offset, len) is byte-identical to packing a
padded row matrix and framing from that" — pinned down from four sides:

- codec level: frame_ranges_gather (native AND python fallback) vs
  frame_ranges over rows packed from the same (offset, len) table, across
  compressed/null-value/empty-value/zero-record batch scenarios;
- engine level: gather-on vs gather-off engines produce bit-identical
  replies for every plan kind (passthrough filter, identity, projection,
  uppercase, payload) × native on/off on the single-device road, and for
  the columnar plans on the mesh lane's per-shard harvest at 2, 4 and 8
  devices, with the byte-mutating plans proving they stay on the padded
  path;
- the reply-wide recompress+seal at the catch-up tick's shape (up to 64
  output batches, each codec): every sealed batch decodes, carries valid
  CRCs and holds the framed records; sealed batches survive a CRC round
  trip through a real storage append;
- arena reuse accounting and reset_arenas().
"""

import asyncio
import json

import numpy as np
import pytest

from redpanda_tpu.coproc import (
    EnableResponseCode,
    ProcessBatchRequest,
    TpuEngine,
)
from redpanda_tpu.coproc import batch_codec
from redpanda_tpu.coproc import engine as engine_mod
from redpanda_tpu.coproc.column_plan import plan_spec
from redpanda_tpu.coproc.engine import ProcessBatchItem
from redpanda_tpu.models import Compression, NTP, Record, RecordBatch
from redpanda_tpu.ops.exprs import field
from redpanda_tpu.ops.transforms import (
    Int,
    Str,
    filter_contains,
    filter_field_eq,
    identity,
    map_project,
    map_uppercase,
    where,
)


def _filter_spec():
    return where(field("level") == "error")  # passthrough: byte-identity


def _project_spec():
    return where(field("level") == "error") | map_project(Int("code"), Str("msg", 16))


def _json_batch(n, base_offset=0, codec=Compression.none, empty_every=0, null_every=0):
    recs = []
    for i in range(n):
        if null_every and i % null_every == 0:
            value = None
        elif empty_every and i % empty_every == 0:
            value = b""
        else:
            value = json.dumps(
                {"level": ["error", "info"][i % 2], "code": i, "msg": f"m{i}"},
                separators=(",", ":"),
            ).encode()
        recs.append(Record(offset_delta=i, timestamp_delta=i, value=value))
    return RecordBatch.build(
        recs, base_offset=base_offset, compression=codec, first_timestamp=1000
    )


def _scenarios():
    return {
        "plain": [_json_batch(8), _json_batch(6, base_offset=8)],
        "compressed": [
            _json_batch(8, codec=Compression.lz4),
            _json_batch(6, base_offset=8, codec=Compression.gzip),
        ],
        "empty_values": [_json_batch(9, empty_every=3), _json_batch(5)],
        "null_values": [_json_batch(9, null_every=3), _json_batch(5)],
        "zero_record": [_json_batch(0), _json_batch(7), _json_batch(0)],
        "all_zero": [_json_batch(0), _json_batch(0)],
    }


# ------------------------------------------------------------ codec parity
def _gather_vs_padded(batches, use_native: bool, monkeypatch):
    ex = batch_codec.explode_batches(batches)
    keep = (np.arange(len(ex.sizes)) % 3) != 1  # arbitrary non-trivial mask
    n = len(ex.sizes)
    stride = max(int(ex.sizes.max()) if n else 1, 1)
    if not use_native:
        monkeypatch.setattr(batch_codec, "_native", lambda: None)
    rows, lens = engine_mod._pack_values(ex, stride)
    padded = batch_codec.frame_ranges(rows, lens, keep, ex.ranges)
    gathered = batch_codec.frame_ranges_gather(
        ex.joined, ex.offsets, ex.sizes, keep, ex.ranges
    )
    return padded, gathered


@pytest.mark.parametrize("name", sorted(_scenarios()))
def test_frame_gather_matches_padded_native(name, monkeypatch):
    padded, gathered = _gather_vs_padded(_scenarios()[name], True, monkeypatch)
    assert gathered == padded


@pytest.mark.parametrize("name", sorted(_scenarios()))
def test_frame_gather_matches_padded_python(name, monkeypatch):
    """The python fallback (_frame_gather_py) must emit the exact same
    varint framing as the native symbol and the padded python path."""
    padded, gathered = _gather_vs_padded(_scenarios()[name], False, monkeypatch)
    assert gathered == padded


def _gather_ptrs_vs_padded(batches, use_native: bool, monkeypatch):
    """frame_ranges_gather_ptrs over the per-batch payload buffers against
    frame_ranges over rows packed from the same table, and against
    frame_ranges_gather over the joined blob."""
    pe = batch_codec.explode_ptrs(batches)
    if pe is None:
        pytest.skip("native packer unavailable")
    ex = batch_codec.explode_batches(batches)
    n = len(ex.sizes)
    keep = (np.arange(n) % 3) != 1
    stride = max(int(ex.sizes.max()) if n else 1, 1)
    rows, lens = engine_mod._pack_values(ex, stride)
    if not use_native:
        monkeypatch.setattr(batch_codec, "_native", lambda: None)
    padded = batch_codec.frame_ranges(rows, lens, keep, ex.ranges)
    joined = batch_codec.frame_ranges_gather(
        ex.joined, ex.offsets, ex.sizes, keep, ex.ranges
    )
    ptrs = batch_codec.frame_ranges_gather_ptrs(
        pe.payloads, pe.offsets, pe.sizes, keep, pe.ranges
    )
    return padded, joined, ptrs


@pytest.mark.parametrize("use_native", [True, False], ids=["native", "python"])
@pytest.mark.parametrize("name", sorted(_scenarios()))
def test_frame_gather_ptrs_matches_padded_and_joined(name, use_native, monkeypatch):
    """The pointer-table gather (native symbol AND its Python twin) emits
    what the padded road and the joined-blob gather emit, over compressed,
    null-value, empty-value and zero-record batches."""
    padded, joined, ptrs = _gather_ptrs_vs_padded(
        _scenarios()[name], use_native, monkeypatch
    )
    assert ptrs == padded
    assert ptrs == joined


def test_frame_gather_ptrs_arena_reuse_is_bit_identical():
    pe = batch_codec.explode_ptrs(_scenarios()["plain"])
    if pe is None:
        pytest.skip("native packer unavailable")
    keep = np.ones(len(pe.sizes), bool)
    arena = batch_codec.Arena()
    runs = [
        batch_codec.frame_ranges_gather_ptrs(
            pe.payloads, pe.offsets, pe.sizes, keep, pe.ranges, arena=arena
        )
        for _ in range(2)
    ]
    assert runs[0] == runs[1]
    assert arena.stats()["reuses"] >= 1


_BAD_PTR_SPANS = {
    # (offsets, lens, starts, ends, n_srcs)
    "span_past_its_buffer": ([0, 4], [3, 10], [0], [2], 1),
    "negative_offset": ([-1, 0], [1, 1], [0], [2], 1),
    "span_inside_the_joined_bytes_only": ([0, 7], [6, 1], [0, 1], [1, 2], 2),
    "overlapping_ranges": ([0, 1], [1, 1], [0, 0], [2, 2], 2),
    "range_past_the_table": ([0, 1], [1, 1], [0], [3], 1),
    "start_after_end": ([0, 1], [1, 1], [2], [0], 1),
    "one_source_for_two_ranges": ([0, 1], [1, 1], [0, 1], [1, 2], 1),
}


@pytest.mark.parametrize("name", sorted(_BAD_PTR_SPANS))
def test_frame_many_gather_ptrs_rejects_bad_spans(name):
    """Malformed ranges and a span outside ITS OWN buffer are a ValueError
    in the binding, never a heap read — the same posture as the joined
    gather's."""
    from redpanda_tpu.native import lib

    if lib is None or not getattr(lib, "has_frame_many_gather_ptrs", False):
        pytest.skip("native ptr-table gather unavailable")
    offsets, lens, starts, ends, n_srcs = _BAD_PTR_SPANS[name]
    with pytest.raises(ValueError):
        lib.frame_many_gather_ptrs(
            [b"abcdef"] * n_srcs,
            np.array(offsets, np.int64), np.array(lens, np.int32),
            np.ones(len(offsets), np.uint8),
            np.array(starts, np.int64), np.array(ends, np.int64),
        )


def test_frame_gather_empty_ranges_both_paths(monkeypatch):
    src = b"abcdef"
    offs = np.zeros(0, np.int64)
    lens = np.zeros(0, np.int32)
    keep = np.zeros(0, bool)
    assert batch_codec.frame_ranges_gather(src, offs, lens, keep, []) == []
    assert batch_codec.frame_ranges_gather_ptrs([], offs, lens, keep, []) == []
    monkeypatch.setattr(batch_codec, "_native", lambda: None)
    assert batch_codec.frame_ranges_gather(src, offs, lens, keep, []) == []
    assert batch_codec.frame_ranges_gather_ptrs([], offs, lens, keep, []) == []


def test_frame_gather_single_range_matches_frame_records():
    """The single-range binding (rp_frame_gather) must emit exactly what
    frame_records emits from rows packed off the same (offset, len)
    table — rp_frame_many_gather routes through it per range, so this
    parity covers the shared C body directly."""
    from redpanda_tpu.native import lib

    if lib is None or not getattr(lib, "has_frame_many_gather", False):
        pytest.skip("native gather unavailable")
    ex = batch_codec.explode_batches(_scenarios()["plain"])
    n = len(ex.sizes)
    keep = (np.arange(n) % 2) == 0
    stride = max(int(ex.sizes.max()), 1)
    rows, lens = engine_mod._pack_values(ex, stride)
    want = batch_codec.frame_records(rows, lens, keep)
    got = lib.frame_gather(ex.joined, ex.offsets, ex.sizes, keep)
    assert got == want


def test_gather_framing_failure_retries_with_cached_keep(monkeypatch):
    """A framing failure after the mask was resolved must NOT lose the
    keep mask: _resolve_keep consumes the slot, so the retry relies on
    the cached _gather_mat — an uncached retry would read the empty slot
    as 'no predicate' and silently emit keep-all output."""
    req = _matrix_request(n_items=2)
    engine = TpuEngine(
        row_stride=256, compress_threshold=10**9,
        force_mode="columnar_host", host_workers=0,
    )
    engine.enable_coprocessors([(1, _filter_spec().to_json(), ("orders",))])
    expected = _reply_bits(engine.process_batch(req))

    real = batch_codec.frame_ranges_gather
    calls = {"n": 0}

    def flaky(*a, **kw):
        calls["n"] += 1
        if calls["n"] == 1:
            raise MemoryError("simulated framing allocation failure")
        return real(*a, **kw)

    monkeypatch.setattr(batch_codec, "frame_ranges_gather", flaky)
    ticket = engine.submit(req)
    first = ticket.result()  # framing fails -> skip_on_failure empties items
    assert all(not it.batches for it in first.items)
    # harvesting the SAME launch again retries framing (the launch's mask
    # slot is already consumed) and must produce the exact filtered
    # output, not unfiltered keep-all
    second = ticket.result()
    engine.shutdown()
    assert calls["n"] == 2
    assert _reply_bits(second) == expected


def test_frame_many_gather_rejects_bad_spans():
    from redpanda_tpu.native import lib

    if lib is None or not getattr(lib, "has_frame_many_gather", False):
        pytest.skip("native gather unavailable")
    src = b"abcdef"
    keep = np.ones(2, np.uint8)
    starts = np.array([0], np.int64)
    ends = np.array([2], np.int64)
    with pytest.raises(ValueError):
        # span past the end of src: must be a ValueError, not a heap read
        lib.frame_many_gather(
            src, np.array([0, 4], np.int64), np.array([3, 10], np.int32),
            keep, starts, ends,
        )
    with pytest.raises(ValueError):
        lib.frame_many_gather(
            src, np.array([-1, 0], np.int64), np.array([1, 1], np.int32),
            keep, starts, ends,
        )
    with pytest.raises(ValueError):  # overlapping ranges
        lib.frame_many_gather(
            src, np.array([0, 1], np.int64), np.array([1, 1], np.int32),
            keep, np.array([0, 0], np.int64), np.array([2, 2], np.int64),
        )


# ------------------------------------------------------------ arena
def test_arena_reuses_and_caps():
    arena = batch_codec.Arena()
    a = arena.acquire(100)
    arena.release(a)
    b = arena.acquire(50)  # smaller request reuses the bigger buffer
    assert b is a
    st = arena.stats()
    assert st["allocs"] == 1 and st["reuses"] == 1
    arena.release(b)
    # the free list is bounded
    bufs = [arena.acquire(10) for _ in range(batch_codec.Arena.MAX_FREE + 4)]
    for buf in bufs:
        arena.release(buf)
    assert arena.stats()["free_buffers"] <= batch_codec.Arena.MAX_FREE


def test_frame_gather_arena_reuse_is_bit_identical():
    batches = _scenarios()["plain"]
    ex = batch_codec.explode_batches(batches)
    keep = np.ones(len(ex.sizes), bool)
    arena = batch_codec.Arena()
    first = batch_codec.frame_ranges_gather(
        ex.joined, ex.offsets, ex.sizes, keep, ex.ranges, arena=arena
    )
    second = batch_codec.frame_ranges_gather(
        ex.joined, ex.offsets, ex.sizes, keep, ex.ranges, arena=arena
    )
    assert first == second
    st = arena.stats()
    if batch_codec._native() is not None:
        assert st["reuses"] >= 1, st


# ------------------------------------------------------ engine parity matrix
def _reply_bits(reply):
    return [
        (it.script_id, str(it.source),
         [(b.payload, b.header.crc, b.header.header_crc, b.header.record_count)
          for b in it.batches])
        for it in reply.items
    ]


def _run_engine(spec, force_mode, mesh_devices, gather, req):
    """One engine, one launch. ``mesh_devices``: 0 is the single-device
    road; N >= 2 pins the mesh lane (its per-device ladders and per-shard
    harvest on a 2-worker pool) for the columnar plans."""
    engine = TpuEngine(
        row_stride=256,
        compress_threshold=10**9,
        force_mode=force_mode,
        host_workers=2 if mesh_devices else 0,
        mesh_devices=mesh_devices or None,
        mesh_backend="cpu" if mesh_devices else None,
        mesh_probe=False,  # parity needs the lane deterministically
        gather_frame=gather,
    )
    codes = engine.enable_coprocessors([(1, spec.to_json(), ("orders",))])
    assert codes == [EnableResponseCode.success]
    reply = engine.process_batch(req)
    stats = engine.stats()
    engine.shutdown()
    return reply, stats


def _matrix_request(n_items=6, n_recs=40):
    return ProcessBatchRequest(
        [
            ProcessBatchItem(
                1,
                NTP.kafka("orders", p),
                [
                    _json_batch(n_recs, base_offset=100 * p),
                    _json_batch(
                        n_recs - 7, base_offset=100 * p + 50,
                        empty_every=5, null_every=7,
                    ),
                ]
                # zero-record batches must survive the launch-wide framing
                # (an empty payload, kept=0) in every mode
                + ([_json_batch(0, base_offset=100 * p + 90)] if p == 0 else []),
            )
            for p in range(n_items)
        ]
    )


_MATRIX = [
    ("passthrough_host", _filter_spec(), "columnar_host", True),
    ("passthrough_device", _filter_spec(), "columnar_device", True),
    ("identity", identity(), None, True),
    ("projection", _project_spec(), "columnar_host", False),
    ("uppercase", map_uppercase(), None, False),
    # a filter-only payload plan maps nothing: its launch fetches the keep
    # mask and frames from the bytes the host holds (ISSUE 27) ...
    ("payload", filter_contains(b"error"), None, True),
    # ... and one that builds new bytes keeps the result matrix
    (
        "payload_project",
        filter_contains(b"error") | map_project(Int("code"), Str("msg", 16)),
        None, False,
    ),
    ("payload_uppercase", filter_contains(b"error") | map_uppercase(), None, False),
]


# (spec name, mesh devices): every plan kind on the single-device road;
# the columnar plans (the only ones with a mesh stage) on the mesh lane at
# each mesh size. The mesh runs its own SPMD predicate whatever the
# backend pick says, so its cases leave force_mode unset (a
# ``columnar_host`` pin declines the lane).
_MESH_SPECS = ("passthrough_device", "projection")
_LANES = [(m[0], 0) for m in _MATRIX] + [
    (name, n_dev) for name in _MESH_SPECS for n_dev in (2, 4, 8)
]


def _lane_id(n_dev: int) -> str:
    return "inline" if not n_dev else "mesh" if n_dev == 2 else f"mesh{n_dev}"


@pytest.mark.parametrize("use_native", [True, False], ids=["native", "no_native"])
@pytest.mark.parametrize(
    "name,n_dev", _LANES, ids=[f"{n}-{_lane_id(d)}" for n, d in _LANES]
)
def test_gather_bit_identical_to_padded(
    name, n_dev, use_native, monkeypatch, eight_devices
):
    """Gather-on vs gather-off engines must agree byte-for-byte in every
    plan kind × lane × native combination — and only byte-identity plans
    may actually take the gather path (launch-wide on the single road,
    per shard in _frame_shard on the mesh lane)."""
    _, spec, force_mode, expect_gather = next(m for m in _MATRIX if m[0] == name)
    if n_dev:
        force_mode = None
    if not use_native:
        monkeypatch.setattr(batch_codec, "_native", lambda: None)
    req = _matrix_request()
    on, stats_on = _run_engine(spec, force_mode, n_dev, True, req)
    off, stats_off = _run_engine(spec, force_mode, n_dev, False, req)
    assert _reply_bits(on) == _reply_bits(off)
    if n_dev:
        assert stats_on["n_mesh_launches"] == stats_off["n_mesh_launches"] == 1
        assert stats_on["mesh"]["devices"] == n_dev
    if expect_gather:
        assert stats_on.get("n_frame_gather", 0.0) >= 1.0, stats_on
        assert "n_frame_padded" not in stats_on
    else:
        # byte-mutating transforms must stay on the padded path even with
        # gather enabled
        assert "n_frame_gather" not in stats_on, stats_on
    assert "n_frame_gather" not in stats_off


@pytest.mark.parametrize("n_dev", [2, 4, 8])
def test_mesh_gather_matches_inline_gather(n_dev, eight_devices):
    """Mesh launches gather-frame per shard (_frame_shard); concatenated
    output must be bit-identical to the launch-wide gather of the single
    road."""
    req = _matrix_request()
    inline, stats0 = _run_engine(_filter_spec(), None, 0, True, req)
    mesh, stats = _run_engine(_filter_spec(), None, n_dev, True, req)
    assert stats0["n_frame_gather"] == 1 and "n_mesh_launches" not in stats0
    assert stats["n_mesh_launches"] == 1
    assert stats["n_frame_gather"] == n_dev  # one crossing per shard
    assert "t_shard_frame_gather" in stats and "t_frame_gather" not in stats
    assert _reply_bits(inline) == _reply_bits(mesh)


# ------------------------------------------- payload mask harvest (ISSUE 27)
_STRIDE = 256


def _edge_value(size: int, level: str = "error") -> bytes:
    head = b'{"level":"%s","pad":"' % level.encode()
    return head + b"x" * (size - len(head) - 2) + b'"}'


def _value_batches(values, per_batch=5, base=0):
    return [
        RecordBatch.build(
            [
                Record(offset_delta=i, timestamp_delta=i, value=v)
                for i, v in enumerate(values[s : s + per_batch])
            ],
            base_offset=base + s, first_timestamp=1000,
        )
        for s in range(0, len(values), per_batch)
    ]


def _payload_shapes():
    """name -> list of requests (submitted as ONE submit_group)."""
    edge = [
        _edge_value(_STRIDE),            # exactly the staging row: kept
        _edge_value(_STRIDE + 1),        # one byte over: dropped, never cut
        _edge_value(_STRIDE, "info"),    # fits, no match
        b"", None,                       # empty and null: dropped
        _edge_value(_STRIDE - 1),
        _edge_value(3 * _STRIDE),
        b'{"level":"error"}',
        _edge_value(40, "warn"),
        _edge_value(_STRIDE),
        _edge_value(64),                 # 11 values: n % 8 == 3, bucket 128
    ]
    assert len(edge) % 8 and len(edge[0]) == _STRIDE
    return {
        "mixed": [_matrix_request()],
        "stride_edges": [ProcessBatchRequest([
            ProcessBatchItem(1, NTP.kafka("orders", 0), _value_batches(edge)),
        ])],
        "zero_record_launch": [ProcessBatchRequest([
            ProcessBatchItem(
                1, NTP.kafka("orders", 0), [_json_batch(0), _json_batch(0)]
            ),
        ])],
        "submit_group": [
            _matrix_request(n_items=2, n_recs=19),
            ProcessBatchRequest([
                ProcessBatchItem(1, NTP.kafka("orders", 7), _value_batches(edge)),
            ]),
            _matrix_request(n_items=1, n_recs=9),
        ],
    }


_PAYLOAD_SPECS = {
    # name -> (spec, the launch fetches a mask)
    "contains": (filter_contains(b'"level":"error"'), True),
    "contains_negate": (filter_contains(b'"level":"error"', negate=True), True),
    "field_eq": (filter_field_eq("level", "error"), True),
    "two_filters": (
        filter_contains(b'"level":"error"') | filter_contains(b"m1", negate=True),
        True,
    ),
    "filter_project": (
        filter_contains(b'"level":"error"') | map_project(Int("code"), Str("msg", 16)),
        False,
    ),
    "filter_uppercase": (filter_contains(b'"level":"error"') | map_uppercase(), False),
    "project_only": (map_project(Int("code"), Str("msg", 16)), False),
}


def _run_group(spec, gather, reqs):
    engine = TpuEngine(
        row_stride=_STRIDE, compress_threshold=10**9, host_workers=0,
        gather_frame=gather,
    )
    try:
        codes = engine.enable_coprocessors([(1, spec.to_json(), ("orders",))])
        assert codes == [EnableResponseCode.success]
        assert engine._plans[1].mode == "payload"
        replies = [t.result() for t in engine.submit_group(reqs)]
        return [_reply_bits(r) for r in replies], engine.stats()
    finally:
        engine.shutdown()


@pytest.mark.parametrize("use_native", [True, False], ids=["native", "no_native"])
@pytest.mark.parametrize("shape", sorted(_payload_shapes()))
@pytest.mark.parametrize("name", sorted(_PAYLOAD_SPECS))
def test_payload_mask_harvest_matches_matrix_road(name, shape, use_native, monkeypatch):
    """A filter-only payload launch (mask fetched, kept values framed from
    the pointer table, or from the joined blob without the native library)
    gives byte for byte what the matrix road (``gather_frame=False``)
    gives; a payload plan that builds new bytes still takes the matrix
    road and its output does not move."""
    spec, mask = _PAYLOAD_SPECS[name]
    if not use_native:
        monkeypatch.setattr(batch_codec, "_native", lambda: None)
        monkeypatch.setattr(batch_codec, "explode_ptrs", lambda batches, *a, **k: None)
    reqs = _payload_shapes()[shape]
    on, stats_on = _run_group(spec, True, reqs)
    off, stats_off = _run_group(spec, False, reqs)
    assert on == off
    assert "n_frame_gather" not in stats_off
    assert stats_on["n_launches"] == stats_off["n_launches"] == 1
    assert stats_on.get("n_fallback_rows", 0) == 0
    n_pad = stats_off.get("n_staged_rows", 0)
    assert stats_on.get("n_staged_rows", 0) == n_pad
    if mask:
        assert stats_on["n_frame_gather"] == 1 and "n_frame_padded" not in stats_on
        assert "t_rebuild" not in stats_on
        # what crosses back is one bit a staged row
        assert stats_on.get("bytes_d2h", 0) == n_pad // 8
        # ... and on the matrix road a filter's result row, as wide as the
        # staged one: the smallest multiple of 128 B that holds the
        # launch's widest fitting value (PR 47), plus the meta column
        sizes = [
            len(r.value or b"")
            for req in reqs for item in req.items
            for batch in item.batches for r in batch.records()
        ]
        fitted = 128 * max(-(-max((s for s in sizes if s <= _STRIDE), default=1) // 128), 1)
        assert stats_off.get("bytes_d2h", 0) == (n_pad and n_pad * (fitted + 8))
        assert stats_off.get("bytes_h2d", 0) == stats_off.get("bytes_d2h", 0)
        if use_native and n_pad:
            assert "t_explode_ptrs" in stats_on  # framed from the pointer table
    else:
        assert "n_frame_gather" not in stats_on
        assert stats_on.get("bytes_d2h", 0) == stats_off.get("bytes_d2h", 0)
        assert stats_on["n_frame_padded"] == 1


def test_payload_mask_harvest_drops_what_the_lane_drops():
    """Not only parity with the matrix road: the kept values themselves.
    Empty, null and over-stride values are dropped, a value of exactly the
    stride is kept whole."""
    spec, _ = _PAYLOAD_SPECS["contains"]
    req = _payload_shapes()["stride_edges"][0]
    engine = TpuEngine(row_stride=_STRIDE, compress_threshold=10**9, host_workers=0)
    try:
        engine.enable_coprocessors([(1, spec.to_json(), ("orders",))])
        reply = engine.process_batch(req)
    finally:
        engine.shutdown()
    got = [bytes(v) for b in reply.items[0].batches for v in b.record_values()]
    values = [r.value for b in req.items[0].batches for r in b.records()]
    want = [
        v for v in values
        if v and len(v) <= _STRIDE and b'"level":"error"' in v
    ]
    assert got == want and _edge_value(_STRIDE) in got and len(got) == 5


# ------------------------------------------------------ reply-wide seal
def _seal_request(n_batches, per_batch=24):
    """One request of ``n_batches`` input batches over 8 partitions: one
    reply seals that many output batches in one _seal_jobs pass (a
    catch-up tick seals 64)."""
    per_item = n_batches // 8
    return ProcessBatchRequest([
        ProcessBatchItem(
            1, NTP.kafka("orders", p),
            [
                _json_batch(
                    per_batch + k, base_offset=1000 * p + 100 * k,
                    empty_every=5 if k % 2 else 0,
                )
                for k in range(per_item)
            ],
        )
        for p in range(8)
    ])


@pytest.mark.parametrize("n_batches", [8, 64])
@pytest.mark.parametrize(
    "codec",
    [Compression.none, Compression.zstd, Compression.lz4, Compression.gzip],
    ids=lambda c: c.name,
)
def test_seal_round_trip_at_the_ticks_shape(codec, n_batches):
    """Every batch the reply-wide seal emits decodes from its wire form,
    carries valid kafka + header CRCs, is compressed with the engine's
    codec, and holds exactly the records its input batch's filter kept —
    in input order, one output batch per input batch, sealed in one pass
    on the caller's thread."""
    req = _seal_request(n_batches)
    engine = TpuEngine(
        row_stride=256,
        compress_threshold=64,  # small: every batch recompresses
        output_codec=codec,
        force_mode="columnar_host",
        host_workers=0,
    )
    engine.enable_coprocessors([(1, _filter_spec().to_json(), ("orders",))])
    reply = engine.process_batch(req)
    stats = engine.stats()
    engine.shutdown()
    assert stats["n_launches"] == 1 and "t_seal" in stats
    n_sealed = 0
    for item_in, item_out in zip(req.items, reply.items):
        assert item_out.source == item_in.ntp
        assert len(item_out.batches) == len(item_in.batches)
        for src, out in zip(item_in.batches, item_out.batches):
            want = [
                r.value for r in src.records()
                if r.value and b'"level":"error"' in r.value
            ]
            back, _ = RecordBatch.decode_internal(out.encode_internal())
            assert back.verify_kafka_crc() and back.verify_header_crc()
            assert back.header.compression == codec
            assert back.header.record_count == len(want)
            assert back.header.first_timestamp == src.header.first_timestamp
            assert [bytes(v) for v in back.record_values()] == want
            n_sealed += 1
    assert n_sealed == n_batches


# ------------------------------------------------------ storage round trip
def test_sealed_batches_survive_storage_append(tmp_path):
    """Engine output (gather path, recompressed) appended to a real DiskLog
    must read back byte-identical with valid kafka + header CRCs."""
    from redpanda_tpu.storage import DiskLog, LogConfig

    req = _matrix_request(n_items=4)
    engine = TpuEngine(
        row_stride=256,
        compress_threshold=64,
        force_mode="columnar_host",
        host_workers=0,
        gather_frame=True,
    )
    engine.enable_coprocessors([(1, _filter_spec().to_json(), ("orders",))])
    reply = engine.process_batch(req)
    engine.shutdown()
    out_batches = [b for it in reply.items for b in it.batches]
    assert out_batches

    async def roundtrip():
        log = await DiskLog.open(
            NTP.kafka("orders_mat", 0),
            LogConfig(base_dir=str(tmp_path), fsync_on_append=False),
        )
        await log.append(out_batches)
        got = await log.read(0, max_bytes=1 << 30)
        await log.close()
        return got

    got = asyncio.run(roundtrip())
    assert len(got) == len(out_batches)
    for orig, back in zip(out_batches, got):
        assert back.payload == orig.payload
        assert back.header.crc == orig.header.crc
        assert back.verify_kafka_crc() and back.verify_header_crc()


# ------------------------------------------------------ arena on the engine
def test_engine_arena_reuse_and_reset():
    req = _matrix_request(n_items=4)
    engine = TpuEngine(
        row_stride=256, compress_threshold=10**9,
        force_mode="columnar_host", host_workers=0,
    )
    engine.enable_coprocessors([(1, _filter_spec().to_json(), ("orders",))])
    engine.process_batch(req)
    engine.process_batch(req)
    st = engine.stats()["arena"]
    if batch_codec._native() is not None:
        assert st["reuses"] >= 1, st
    engine.reset_arenas()
    st2 = engine.stats()["arena"]
    assert st2["allocs"] == 0 and st2["reuses"] == 0
    engine.shutdown()
