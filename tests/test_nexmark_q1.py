"""NEXmark Q1 on the payload lane (PR 37): ``map_project``'s two exact
64-bit kinds, ``Long`` and ``Scaled``, and configuration ``nexmark64p-q1``.

JAX's x64 is off in this tree, so the device program carries a 64-bit
integer as two uint32 limbs (``ops/transforms.py``: ``_parse_long_at``,
``_scale_exact``). Here, on the CPU at small sizes: the limb arithmetic
against Python integers over the edges, the device program against its
numpy twin bit for bit, the spec's serde and routing, the served lane
against ``benchmarks/references/nexmark_q1.py`` (loaded by path; it imports
nothing of the program) on ``benchmarks/docs_nexmark.py``'s events, every
departure that reference lists, the ``jax.named_scope``s in the compiled
text, the two staging counters, and the pin that the scripts of the
benchmark's other cells still lower to the program they had.
"""

import hashlib
import importlib.util
import json
import os
import struct

import numpy as np
import pytest

from redpanda_tpu.coproc import EnableResponseCode, ProcessBatchRequest, TpuEngine
from redpanda_tpu.coproc.column_plan import PayloadPlan, plan_spec
from redpanda_tpu.coproc.engine import ProcessBatchItem, _bucket_rows
from redpanda_tpu.models import NTP, Record, RecordBatch
from redpanda_tpu.ops import transforms as T
from redpanda_tpu.ops.exprs import field
from redpanda_tpu.ops.pipeline import (
    IN_META, make_packed_pipeline, make_packed_pipeline_host, unpack_result)

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
STRIDE = 1024


def _load(relpath: str):
    path = os.path.join(BENCH, relpath)
    spec = importlib.util.spec_from_file_location(
        "perfbench_" + relpath[:-3].replace("/", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _config(name: str = "nexmark64p-q1") -> dict:
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


CONFIG = _config()
REF = _load("references/nexmark_q1.py")
PARAMS = CONFIG["reference"]["params"]
Q1 = T.TransformSpec.from_json(json.dumps(CONFIG["script"]["spec"]))


def _rows(values: list[bytes], n_pad: int, stride: int = STRIDE):
    data = np.zeros((n_pad, stride), np.uint8)
    lens = np.zeros(n_pad, np.int32)
    for i, v in enumerate(values):
        data[i, : len(v)] = np.frombuffer(v, np.uint8)
        lens[i] = len(v)
    return data, lens


def _both(spec, values: list[bytes], stride: int = 256):
    """(out, keep) of the device program (jit, CPU backend), after holding
    its numpy twin to the same bits, dropped rows included."""
    n_pad = max(8, -(-len(values) // 8) * 8)
    data, lens = _rows(values, n_pad, stride)
    out_d, len_d, keep_d = (np.asarray(x) for x in T.compile_transform(spec, stride)(data, lens))
    out_h, len_h, keep_h = T.compile_transform_host(spec, stride)(data, lens)
    assert out_d.dtype == out_h.dtype == np.uint8
    assert np.array_equal(out_d, out_h) and np.array_equal(keep_d, keep_h)
    assert np.array_equal(len_d, len_h)
    return out_d, keep_d


# ------------------------------------------------------------------ Scaled
SCALED_VALUES = [0, 1, 999, 999_999_999, -1, -999_999_999, 99_999_999, 500, -500, 1_000]
RATIOS = [(908, 1000), (89, 100), (1, 3), (-7, 2), (2**31 - 1, 1), (-(2**31 - 1), 2**31 - 1),
          (1, 2**31 - 1)]


@pytest.mark.parametrize("num, den", RATIOS)
@pytest.mark.parametrize("value", SCALED_VALUES)
def test_scaled_is_the_exact_floor_of_python_integers(value, num, den):
    spec = T.map_project(T.Scaled("v", num, den))
    out, keep = _both(spec, [b'{"v":%d}' % value, b'{"w":1,"v":%d,"x":2}' % value])
    assert keep[:2].all() and not keep[2:].any()
    want = value * num // den  # Python floors toward minus infinity
    assert -(2**63) <= want < 2**63
    for row in (0, 1):
        assert struct.unpack("<q", out[row].tobytes())[0] == want


def test_a_float32_product_would_be_wrong_by_whole_cents():
    """0.908 x 99,999,999 cents: the device's answer is the integers', and
    a float32 multiply (what a program without the limbs could do with x64
    off) is off by more than a cent, so it is a wrong answer and not a
    tolerance."""
    price = 99_999_999
    out, keep = _both(T.TransformSpec(mapper=Q1.mapper), [
        b'{"auction":1,"bidder":2,"price":%d,"dateTime":3}' % price])
    got = struct.unpack("<iiqq", out[0].tobytes())
    assert keep[0] and got == (1, 2, 90_799_999, 3) and got[2] == price * 908 // 1000
    as_float32 = int(np.floor(np.float32(price) * np.float32(0.908)))
    assert abs(as_float32 - got[2]) >= 1


@pytest.mark.parametrize("text", [b"1000000000", b"-1000000000", b"12345678901", b"", b"-", b"x",
                                  b'"5"'])
def test_scaled_refuses_what_a_v1_int_refuses(text):
    out, keep = _both(T.map_project(T.Scaled("v", 908, 1000)), [b'{"v":' + text + b"}"])
    assert not keep.any()


@pytest.mark.parametrize("num, den", [(908, 0), (908, -1000), (2**31, 1), (-(2**31), 1),
                                      (1, 2**31), (0.908, 1), (908, 1000.0), (True, 1)])
def test_scaled_constants_outside_their_limits_are_refused_at_compile(num, den):
    with pytest.raises(ValueError, match="Scaled"):
        T.compile_transform_host(T.map_project(T.Scaled("v", num, den)), 64)
    engine = TpuEngine(row_stride=STRIDE, host_workers=0)
    try:
        spec = T.map_project(T.Scaled("v", 908, 1000)).to_json().replace(
            '"num": 908, "den": 1000', f'"num": {json.dumps(num)}, "den": {json.dumps(den)}')
        assert engine.enable_coprocessors([(1, spec, ("t",))]) == [
            EnableResponseCode.internal_error]
    finally:
        engine.shutdown()


# ------------------------------------------------------------------ Long
LONGS = [0, 1, -1, 9, 999_999_999, 1_000_000_000, 2**32 - 1, 2**32, 2**32 + 1, -(2**32),
         1_700_000_000_123, 999_999_999_999_999_999, -999_999_999_999_999_999,
         100_000_000_000_000_000, -100_000_000_000_000_000, 123_456_789_012_345_678,
         2**59 + 12345, -(2**59) - 1, 4_294_967_295_999, 65_535, 65_536, 655_359_999]


@pytest.mark.parametrize("value", LONGS)
def test_long_is_the_integer_as_int64(value):
    out, keep = _both(T.map_project(T.Long("t")), [
        b'{"t":%d}' % value, b'{"a":"x","t":%d,"b":1}' % value, b'{"t":%d' % value])
    assert keep[:3].all() and not keep[3:].any()
    for row in range(3):
        assert struct.unpack("<q", out[row].tobytes())[0] == value


@pytest.mark.parametrize("text, want", [
    (b"1000000000000000000", None),       # 19 digits
    (b"-1000000000000000000", None),
    (b"12345678901234567890", None),      # 20: no terminator inside the window
    (b"123456789012345678901234", None),
    (b"-", None), (b"", None), (b'"7"', None), (b" 5", None), (b"+5", None),
    (b"-0", 0), (b"007", 7), (b"000000000000000001", 1),
    (b"12.5", 12), (b"5e3", 5),           # the lane's byte semantics: the digits, then a non-digit
])
def test_long_on_its_edges(text, want):
    out, keep = _both(T.map_project(T.Long("t")), [b'{"t":' + text + b',"u":1}'])
    assert bool(keep[0]) == (want is not None) and not keep[1:].any()
    if want is not None:
        assert struct.unpack("<q", out[0].tobytes())[0] == want


@pytest.mark.parametrize("seed", [7, 2**31 + 11])
def test_random_integers_through_both_kinds(seed):
    rng = np.random.default_rng(seed)
    longs = [int(x) for x in rng.integers(-(10**18) + 1, 10**18, size=200)]
    longs += [int(x) for x in rng.integers(-(10**6), 10**6, size=56)]
    ints = [int(x) for x in rng.integers(-999_999_999, 10**9, size=256)]
    num, den = int(rng.integers(-(2**31) + 1, 2**31)), int(rng.integers(1, 2**31))
    spec = T.map_project(T.Long("t"), T.Scaled("v", num, den), T.Scaled("v", 908, 1000))
    out, keep = _both(spec, [b'{"v":%d,"t":%d}' % (v, t) for v, t in zip(ints, longs)])
    assert keep.all()
    for row, (v, t) in enumerate(zip(ints, longs)):
        assert struct.unpack("<qqq", out[row].tobytes()) == (t, v * num // den, v * 908 // 1000)


# ------------------------------------------------------------------ the spec
def test_spec_round_trips_and_states_its_width():
    spec = T.filter_field_eq("event_type", 2) | T.map_project(
        T.Int("auction"), T.Int("bidder"), T.Scaled("price", 908, 1000), T.Long("dateTime"))
    assert T.TransformSpec.from_json(spec.to_json()) == spec
    assert json.loads(spec.to_json()) == CONFIG["script"]["spec"]
    assert T.project_out_width(spec.mapper.fields) == 24 == T.transform_out_width(spec, STRIDE)
    again = T.TransformSpec.from_json(spec.to_json())
    assert again.mapper.fields[2] == T.Scaled("price", 908, 1000)
    assert again.mapper.fields[3] == T.Long("dateTime")


@pytest.mark.parametrize("kind", ["double", "Long", "", "decimal"])
def test_an_unknown_field_kind_is_an_error_that_names_it(kind):
    """Until PR 37 ``from_json`` read any unknown kind as ``Str`` and died on
    a missing ``max_len`` (a ``KeyError``); an older broker handed this
    configuration's script still refuses the deploy, and this one says why."""
    blob = json.dumps({"name": "x", "ops": [
        {"op": "map_project", "fields": [{"kind": kind, "key": "price", "max_len": 8}]}]})
    with pytest.raises(ValueError, match="unknown map_project field kind"):
        T.TransformSpec.from_json(blob)
    engine = TpuEngine(row_stride=STRIDE, host_workers=0)
    try:
        assert engine.enable_coprocessors([(1, blob, ("t",))]) == [
            EnableResponseCode.internal_error]
    finally:
        engine.shutdown()


def test_the_new_kinds_stay_on_the_payload_lane():
    plan = plan_spec(Q1)
    assert isinstance(plan, PayloadPlan) and plan.mode == "payload" and not plan.byte_identity
    assert isinstance(plan_spec(T.map_project(T.Long("t"))), PayloadPlan)
    # the columnar projector has no column for them: said at the deploy
    with pytest.raises(ValueError, match="payload lane"):
        plan_spec(T.where(field("event_type") == 2) | T.map_project(T.Long("t")))
    with pytest.raises(ValueError, match="payload lane"):
        plan_spec(T.where(field("event_type") == 2) | T.map_project(T.Scaled("p", 1, 2)))
    # what the lane does not compile is still refused by name
    with pytest.raises(ValueError, match="columnar path"):
        T.compile_transform_host(T.map_project(T.Float("x")), 64)


# ------------------------------------------------------------------ the reference's statements
def _bid(auction=b"1007", bidder=b"1093", price=b"5000", stamp=b"1700000000123",
         kind=b"2", extra=b"abc") -> bytes:
    return (b'{"event_type":%s,"auction":%s,"bidder":%s,"price":%s,"dateTime":%s,"extra":"%s"}'
            % (kind, auction, bidder, price, stamp, extra))


def _q1(auction: int, bidder: int, price: int, stamp: int) -> bytes:
    return struct.pack("<iiqq", auction, bidder, price, stamp)


# label -> (value, what the lane's byte semantics give)
EDGES = {
    "a_bid": (_bid(), _q1(1007, 1093, 4540, 1700000000123)),
    "empty": (b"", None),
    "a_person": (_bid(kind=b"0"), None),
    "an_auction": (_bid(kind=b"1"), None),
    "event_type_20": (_bid(kind=b"20"), None),
    "event_type_2_point_5": (_bid(kind=b"2.5"), None),
    "event_type_a_string": (_bid(kind=b'"2"'), None),
    "event_type_ends_the_value": (b'{"auction":1,"bidder":2,"price":3,"dateTime":4,"event_type":2',
                                  _q1(1, 2, 2, 4)),
    "fits_to_the_byte": (_bid(extra=b"x" * (1024 - len(_bid(extra=b"")))),
                         _q1(1007, 1093, 4540, 1700000000123)),
    "one_byte_over_the_row": (_bid(extra=b"x" * (1025 - len(_bid(extra=b"")))), None),
    "price_of_a_cent": (_bid(price=b"1"), _q1(1007, 1093, 0, 1700000000123)),
    "price_largest": (_bid(price=b"999999999"), _q1(1007, 1093, 907999999, 1700000000123)),
    "price_10_digits": (_bid(price=b"1000000000"), None),
    "price_negative_floors_down": (_bid(price=b"-1"), _q1(1007, 1093, -1, 1700000000123)),
    "price_missing": (b'{"event_type":2,"auction":1,"bidder":2,"dateTime":4}', None),
    "auction_10_digits": (_bid(auction=b"4294967296"), None),
    "bidder_a_string": (_bid(bidder=b'"1093"'), None),
    "stamp_18_digits": (_bid(stamp=b"999999999999999999"),
                        _q1(1007, 1093, 4540, 999999999999999999)),
    "stamp_19_digits": (_bid(stamp=b"1000000000000000000"), None),
    "stamp_negative": (_bid(stamp=b"-1700000000123"), _q1(1007, 1093, 4540, -1700000000123)),
    "stamp_missing": (b'{"event_type":2,"auction":1,"bidder":2,"price":3}', None),
    # departures from JSON semantics, each stated in the reference's docstring
    "price_decimal_reads_as_12": (_bid(price=b"12.5"), _q1(1007, 1093, 10, 1700000000123)),
    "stamp_after_a_space": (_bid(stamp=b" 5"), None),
    "first_price_wins": (b'{"event_type":2,"auction":1,"bidder":2,"price":1000,"price":2000,'
                         b'"dateTime":4}', _q1(1, 2, 908, 4)),
    "key_inside_another_fields_text": (
        b'{"event_type":2,"extra":"\\"price\\":7 ","auction":1,"bidder":2,"price":1000,'
        b'"dateTime":4}'.replace(b'\\"', b'"'), _q1(1, 2, 6, 4)),
    "bid_in_a_nested_object": (b'{"event_type":1,"was":{"event_type":2},"auction":1,"bidder":2,'
                               b'"price":1000,"dateTime":4}', _q1(1, 2, 908, 4)),
    "not_json_at_all": (b'"dateTime":9;"price":50 "bidder":3 "auction":4 "event_type":2',
                        _q1(4, 3, 45, 9)),
}


@pytest.mark.parametrize("label", sorted(EDGES))
def test_q1_byte_semantics_on_the_edges(label):
    """One value on an edge: the plain reference gives what the table states,
    and the packed device program and its numpy twin give the reference's
    bytes (the host's part of a drop is ``fits``: a value over the row)."""
    value, want = EDGES[label]
    assert REF.reference(value, **PARAMS) == want
    staged = np.zeros((8, STRIDE + IN_META), np.uint8)
    for row in (0, 2):
        if len(value) <= STRIDE:
            staged[row, : len(value)] = np.frombuffer(value, np.uint8)
            staged[row, STRIDE : STRIDE + 4] = np.frombuffer(struct.pack("<i", len(value)), np.uint8)
    dev_fn, r_out = make_packed_pipeline(Q1, STRIDE)
    packed = np.asarray(dev_fn(staged))
    assert r_out == 24 and packed.shape == (8, 32)
    assert np.array_equal(packed, make_packed_pipeline_host(Q1, STRIDE)(staged))
    out, out_len, keep = unpack_result(packed, r_out)
    for row in (0, 2):
        got = bytes(out[row]) if keep[row] and len(value) <= STRIDE else None
        assert got == want and out_len[row] == (24 if keep[row] else 0)
    assert not keep[[1, 3, 4, 5, 6, 7]].any()


def test_the_reference_recovers_the_event_number():
    out = REF.reference(_bid(stamp=b"%d" % (REF.BASE_MS + 4_194_303)), **PARAMS)
    assert REF.sequence(out) == 4_194_303
    assert REF.BASE_MS == CONFIG["documents"]["params"]["base_ms"]
    assert REF.reference(None, **PARAMS) is None


# ------------------------------------------------------------------ the served lane
def _batches(values: list[bytes], per_batch: int, base: int) -> list[RecordBatch]:
    return [
        RecordBatch.build(
            [Record(offset_delta=i, timestamp_delta=i, value=v)
             for i, v in enumerate(values[s : s + per_batch])],
            base_offset=base + s, first_timestamp=1000)
        for s in range(0, len(values), per_batch)
    ]


@pytest.mark.parametrize("seed", [7, 2**31 + 11])
def test_the_engine_gives_the_reference_bytes_on_nexmark_events(seed):
    """Configuration ``nexmark64p-q1`` through ``TpuEngine``: 64 partitions
    of seeded events (the configuration's own generator and params) plus
    every edge value, byte-equal and in order to the plain reference, with
    the counters the cell's per-layer metrics read."""
    events = _load("docs_nexmark.py").make_events(seed, 64, 64, **CONFIG["documents"]["params"])
    parts = [events[p] for p in range(64)]
    edges = [v for v, _ in EDGES.values()]
    parts[1] = parts[1][:40] + edges + parts[1][40:]
    parts[63] = edges[::-1] + parts[63]
    engine = TpuEngine(row_stride=STRIDE, host_workers=0)
    try:
        assert engine.enable_coprocessors(
            [(1, json.dumps(CONFIG["script"]["spec"]), ("bench",))]
        ) == [EnableResponseCode.success]
        reply = engine.submit(ProcessBatchRequest([
            ProcessBatchItem(1, NTP.kafka("bench", p), _batches(values, 32, 10_000 * p))
            for p, values in enumerate(parts)
        ])).result()
        stats = engine.stats()
    finally:
        engine.shutdown()
    kept = 0
    for item, values in zip(reply.items, parts):
        got = [r.value for b in item.batches for r in b.records()]
        want = [o for o in (REF.reference(v, **PARAMS) for v in values) if o is not None]
        assert got == want, f"partition {item.source.partition}"
        kept += len(want)
    n_in = sum(map(len, parts))
    assert n_in == 64 * 64 + 2 * len(edges) and 0.88 < kept / n_in < 0.94
    assert stats["n_kept_rows"] == kept and stats["bytes_out"] == 24 * kept
    assert stats["n_device_launches"] == stats["n_launches"] == 1
    assert stats.get("n_fallback_rows", 0) == 0 and stats["n_frame_padded"] == 1
    # the matrices themselves against what they hold (PR 47: the Bids, and
    # what is staged empty, in 136 B rows; the Auctions, the Persons and the
    # edge values beside them at the stride of the widest that fits), and
    # the bytes of the values that fit the lane's row
    sizes = [len(v) for values in parts for v in values]
    narrow = sum(s <= 128 or s > STRIDE for s in sizes)
    wide = -(-max(s for s in sizes if s <= STRIDE) // 128) * 128
    rows = (_bucket_rows(narrow), _bucket_rows(n_in - narrow))
    assert rows == (4096, 512) and wide == STRIDE and stats["n_split_launches"] == 1
    assert stats["n_staged_rows"] == sum(rows)
    assert stats["bytes_staged"] == stats["bytes_h2d"] == (
        rows[0] * (128 + IN_META) + rows[1] * (wide + IN_META))
    assert stats["bytes_staged_values"] == sum(s for s in sizes if s <= STRIDE)
    assert 0.4 < stats["bytes_staged_values"] / stats["bytes_staged"] < 0.7


def test_the_staging_counters_reach_the_metrics_page():
    from redpanda_tpu.metrics import registry
    from redpanda_tpu.observability import probes

    before = (probes.coproc_staged_bytes.value, probes.coproc_staged_value_bytes.value)
    values = [_bid(), _bid(extra=b"y" * 2000), b"", _bid(kind=b"1")]
    engine = TpuEngine(row_stride=STRIDE, host_workers=0)
    try:
        assert engine.enable_coprocessors(
            [(1, Q1.to_json(), ("t",))]) == [EnableResponseCode.success]
        engine.submit(ProcessBatchRequest(
            [ProcessBatchItem(1, NTP.kafka("t", 0), _batches(values, 4, 0))])).result()
        stats = engine.stats()
    finally:
        engine.shutdown()
    assert stats["bytes_staged"] == 128 * (128 + IN_META)  # a row fitted to the Bids
    assert stats["bytes_staged_values"] == len(values[0]) + len(values[3])  # the ones that fit
    assert probes.coproc_staged_bytes.value - before[0] == stats["bytes_staged"]
    assert probes.coproc_staged_value_bytes.value - before[1] == stats["bytes_staged_values"]
    page = registry.render_prometheus()
    assert "coproc_staged_bytes_total" in page and "coproc_staged_value_bytes_total" in page


# ------------------------------------------------------------------ the program's text
def _lowered(spec, mask_only: bool, rows: int):
    import jax

    fn, _ = make_packed_pipeline(spec, STRIDE, mask_only)
    return fn.lower(jax.ShapeDtypeStruct((rows, STRIDE + IN_META), np.uint8))


def test_the_programs_stages_are_named_in_its_compiled_text():
    lowered = _lowered(Q1, False, 256)
    text = lowered.compile().as_text()
    for scope in ("rp_transform)/filter", "rp_transform)/project/project.scaled/",
                  "rp_transform)/project/project.long/", "rp_payload_transform)/parse/",
                  "rp_payload_transform)/frame/"):
        assert scope in text, scope
    # no float enters the program, and nothing wider than 32 bits
    hlo = lowered.as_text()
    for dtype in ("f16", "bf16", "f32", "f64", "i64", "ui64"):
        assert f"x{dtype}>" not in hlo and f"<{dtype}>" not in hlo, dtype


# sha256 of the lowered StableHLO text (no locations, so no scope names) of
# the other cells' scripts at the 32,768-row bucket, taken from the parent's
# tree (807f58e) before PR 37 touched ops/transforms.py
PARENT_PROGRAMS = {
    "json64p-v1map": (False, 97756, "ff06b41115b356cfe20bbdac57d16aba559dfa109d9862456e56968e412cf6ea"),
    "json64p-v1": (True, 12833, "ad46b7d9bb44aa236e3b084f002a15e6fd9d5bb5587f5d976a68e89af409074a"),
}


@pytest.mark.parametrize("config", sorted(PARENT_PROGRAMS))
def test_the_other_cells_scripts_lower_to_the_program_they_had(config):
    mask_only, size, digest = PARENT_PROGRAMS[config]
    spec = T.TransformSpec.from_json(json.dumps(_config(config)["script"]["spec"]))
    text = _lowered(spec, mask_only, 32768).as_text()
    assert (len(text), hashlib.sha256(text.encode()).hexdigest()) == (size, digest)
