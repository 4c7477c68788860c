"""The served payload lane against the benchmark's plain reference.

Configuration ``json64p-v1`` (benchmarks/configs/json64p-v1.json) is the
north star's JSON filter, ``filter_contains('"level":"warn"')``: a v1
script, so a PayloadPlan that stages whole rows to the device. Here, at a
small size on the CPU: ``TpuEngine`` submit -> sealed reply over the seeded
document stream plus the values on the lane's edges, byte-equal and in
order to ``benchmarks/references/filter_contains.py`` (loaded by path, the
way ``benchmarks/loadgen.load_reference`` loads it; the reference imports
nothing of the program). The same runs hold the two counters the lane's
per-layer metrics read.

Configuration ``json64p-v1map`` is config 4's filter + projection as a v1
script, ``filter_contains('"level":"error"') | map_project(Int("code"),
Str("msg", 64))``: the same lane, but the device builds new bytes, so the
launch takes the matrix road (a result matrix fetched and rebuilt). Engine,
device program and numpy twin against
``benchmarks/references/project_error_v1.py``, the v1 byte semantics, and
that reference against the JSON one where the two must agree.

And every configuration's script (``json64p-where``: the columnar lane)
at the launch shapes the ledger judges the benchmark's cells at, on the
one host road a single-device launch has.
"""

import functools
import hashlib
import importlib.util
import json
import os
import re
import struct

import numpy as np
import pytest

from redpanda_tpu.coproc import EnableResponseCode, ProcessBatchRequest, TpuEngine
from redpanda_tpu.coproc.engine import ProcessBatchItem, _bucket_rows
from redpanda_tpu.coproc.reference import make_documents
from redpanda_tpu.models import NTP, Record, RecordBatch

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
NEEDLE = b'"level":"warn"'


def _config(name: str = "json64p-v1") -> dict:
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _load(relpath: str):
    """A file of the benchmark, loaded by path (nothing under benchmarks/
    is a package of the program)."""
    path = os.path.join(BENCH, relpath)
    name = "perfbench_" + relpath[:-3].replace("/", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reference(name: str):
    return _load("references/" + name + ".py")


def _doc(size: int, needle_at: int | None) -> bytes:
    """A document of exactly ``size`` bytes holding the needle at byte
    ``needle_at`` (nowhere when None)."""
    body = bytearray(b"y" * size)
    if needle_at is not None:
        body[needle_at : needle_at + len(NEEDLE)] = NEEDLE
    assert len(body) == size
    return bytes(body)


# the lane's edges: empty, exactly the stride, one over it, and a value
# whose only needle lies beyond the staging row
EDGES = [
    b"",
    _doc(1024, 8),            # fits to the byte and matches: kept
    _doc(1024, 1024 - 14),    # the needle ends on the row's last byte: kept
    _doc(1024, None),         # fits, no needle: dropped
    _doc(1025, 8),            # one byte too wide: dropped though it matches
    _doc(1060, 1030),         # the needle only beyond byte 1,024: dropped
    b"",
    NEEDLE,                   # the needle alone
]


def _batches(values: list[bytes], per_batch: int, base: int) -> list[RecordBatch]:
    out = []
    for s in range(0, len(values), per_batch):
        chunk = values[s : s + per_batch]
        out.append(RecordBatch.build(
            [Record(offset_delta=i, timestamp_delta=i, value=v) for i, v in enumerate(chunk)],
            base_offset=base + s, first_timestamp=1000,
        ))
    return out


@pytest.mark.parametrize("road", ["mask", "mask_joined_blob", "matrix"])
@pytest.mark.parametrize("seed", [7, 2**31 + 11])
def test_payload_lane_matches_the_plain_reference(seed, road, monkeypatch):
    """``mask``: the configuration's script maps nothing, so the launch
    fetches one keep bit a row and frames kept values from the pointer
    table; ``mask_joined_blob``: the same without the pointer-table
    explode (the classic staging road); ``matrix``: the result-matrix road
    (``gather_frame=False``). One reference holds all three."""
    from redpanda_tpu.coproc import batch_codec

    if road == "mask_joined_blob":
        monkeypatch.setattr(batch_codec, "explode_ptrs", lambda batches, *a, **k: None)
    config = _config()
    ref = _reference(config["reference"]["name"])
    params = config["reference"]["params"]
    stride = params["row_stride"]
    parts = make_documents(seed, 4, 96)
    parts[1] = parts[1][:40] + EDGES + parts[1][40:]
    parts[3] = EDGES[::-1] + parts[3]

    engine = TpuEngine(
        row_stride=stride, host_workers=0, gather_frame=road != "matrix"
    )
    try:
        codes = engine.enable_coprocessors(
            [(1, json.dumps(config["script"]["spec"]), ("bench",))]
        )
        assert codes == [EnableResponseCode.success]
        assert engine._plans[1].mode == "payload"
        assert engine._plans[1].byte_identity
        req = ProcessBatchRequest([
            ProcessBatchItem(1, NTP.kafka("bench", p), _batches(values, 32, 1000 * p))
            for p, values in enumerate(parts)
        ])
        reply = engine.submit(req).result()
        stats = engine.stats()
    finally:
        engine.shutdown()

    n_in = sum(map(len, parts))
    kept_total = 0
    for item, values in zip(reply.items, parts):
        got = [r.value for b in item.batches for r in b.records()]
        want = [o for o in (ref.reference(v, **params) for v in values) if o is not None]
        assert got == want, f"partition {item.source.partition}"
        kept_total += len(want)
    # the comparison bites on both sides of the stride
    assert ref.reference(EDGES[1], **params) == EDGES[1]
    assert ref.reference(EDGES[4], **params) is None
    assert 0 < kept_total < n_in
    # what the harvest kept and framed, counted on either road
    assert stats["n_kept_rows"] == kept_total
    assert stats["bytes_out"] == sum(
        len(v) for vs in parts for v in vs if ref.reference(v, **params) is not None
    )
    # one launch: the bucket it was padded to, and the values it dropped
    # for exceeding the staging row
    assert stats["n_launches"] == 1 and stats["n_records"] == n_in
    assert stats["n_staged_rows"] == 512 >= n_in
    assert stats["n_oversize_rows"] == sum(len(v) > stride for vs in parts for v in vs) >= 4
    assert stats["t_h2d"] > 0 and stats["bytes_h2d"] == 512 * (stride + 8)
    assert stats.get("n_fallback_rows", 0) == 0
    # what comes back: one bit a staged row, or the whole result matrix
    if road == "matrix":
        assert stats["bytes_d2h"] == 512 * (stride + 8) and "n_frame_gather" not in stats
    else:
        assert stats["bytes_d2h"] == 512 // 8 and stats["n_frame_gather"] == 1
        assert "t_rebuild" not in stats


def test_a_script_inherits_the_programs_its_spec_already_ran():
    """Scripts of one spec share one jitted pipeline (ops/pipeline.py caches
    it by spec), so a second script's launch at a row bucket the function
    has seen is no first run: ``n_compiles`` counts traces + compiles, not
    scripts (a re-deployed filter starts past them, as the benchmark's
    catch-up window does after its warm-up scripts)."""
    config = _config()
    spec = json.dumps(config["script"]["spec"])
    engine = TpuEngine(row_stride=1024, host_workers=0)
    try:
        assert engine.enable_coprocessors(
            [(1, spec, ("bench",)), (2, spec, ("bench",))]
        ) == [EnableResponseCode.success] * 2
        small = make_documents(5, 1, 64)[0]
        large = make_documents(5, 1, 200)[0]

        def launch(script_id, values):
            req = ProcessBatchRequest(
                [ProcessBatchItem(script_id, NTP.kafka("bench", 0), _batches(values, 32, 0))]
            )
            engine.submit(req).result()
            return engine.stats()["n_compiles"]

        assert launch(1, small) == 1  # bucket 128: the function's first run
        assert launch(2, small) == 1  # script 2, same function, same bucket
        assert launch(2, large) == 2  # bucket 256: a new shape compiles
        assert launch(1, large) == 2
        programs = engine.stats()["compiled_programs"]
        assert {(c["script_id"], c["n_pad"]) for c in programs} == {
            (1, 128), (2, 128), (1, 256), (2, 256)
        }
        assert engine.stats()["n_device_launches"] == 4
    finally:
        engine.shutdown()


# ------------------------------------------- json64p-v1map: the device-side map
def _map_doc(level=b"error", code=b"7", msg=b"hello", size: int | None = None,
             pad: bytes | None = None) -> bytes:
    """A document of the benchmark's shape with the given raw field bytes;
    ``size`` pads it to exactly that many bytes."""
    head = b'{"level":"%s","code":%s,"msg":"%s","pad":"' % (level, code, msg)
    if pad is None:
        pad = b"x" * (0 if size is None else size - len(head) - 2)
    doc = head + pad + b'"}'
    assert size is None or len(doc) == size
    return doc


def _out(code: int, msg: bytes) -> bytes:
    return struct.pack("<iH", code, len(msg)) + msg.ljust(64, b"\x00")


_M64, _M65 = b"m" * 64, b"m" * 65
_NEEDLE_ERROR = b'\\"level\\":\\"error\\"'  # as JSON escapes it inside a string
# label -> (value, what the v1 byte semantics give, what JSON semantics give)
MAP_EDGES = {
    "empty": (b"", None, None),
    "fits_to_the_byte": (_map_doc(size=1024), _out(7, b"hello"), _out(7, b"hello")),
    # JSON keeps it: the columnar lane has no staging row
    "one_byte_over_the_row": (_map_doc(size=1025), None, _out(7, b"hello")),
    "msg_64": (_map_doc(msg=_M64), _out(7, _M64), _out(7, _M64)),
    "msg_65": (_map_doc(msg=_M65), None, None),
    "msg_unterminated": (b'{"level":"error","code":7,"msg":"abc', None, None),
    "msg_not_a_string": (b'{"level":"error","code":7,"msg":12}', None, None),
    "msg_empty": (_map_doc(msg=b""), _out(7, b""), _out(7, b"")),
    "code_9_digits": (_map_doc(code=b"999999999"), _out(999999999, b"hello"),
                      _out(999999999, b"hello")),
    "code_10_digits": (_map_doc(code=b"1000000000"), None, None),
    "code_12_digits": (_map_doc(code=b"123456789012"), None, None),
    "code_negative": (_map_doc(code=b"-42"), _out(-42, b"hello"), _out(-42, b"hello")),
    "code_negative_9_digits": (_map_doc(code=b"-999999999"), _out(-999999999, b"hello"),
                               _out(-999999999, b"hello")),
    "code_minus_alone": (_map_doc(code=b"-"), None, None),
    "code_missing": (b'{"level":"error","msg":"hello"}', None, None),
    "code_a_string": (_map_doc(code=b'"7"'), None, None),
    "code_ends_the_value": (b'{"level":"error","msg":"hello","code":7', _out(7, b"hello"), None),
    "level_info": (_map_doc(level=b"info"), None, None),
    # departures from JSON semantics, each stated in the reference's docstring
    "code_decimal_reads_as_3": (_map_doc(code=b"3.5"), _out(3, b"hello"), None),
    "escaped_quote_ends_msg": (_map_doc(msg=b'a\\"b'), _out(7, b"a\\"), _out(7, b'a"b')),
    "needle_only_in_pad": (_map_doc(level=b"info", pad=b'y"level":"error"y'),
                           _out(7, b"hello"), None),
    "needle_escaped_in_pad": (_map_doc(level=b"info", pad=_NEEDLE_ERROR), None, None),
    "needle_in_a_nested_object": (
        b'{"level":"info","ctx":{"level":"error"},"code":7,"msg":"hello"}',
        _out(7, b"hello"), None),
    "first_code_wins": (b'{"level":"error","code":1,"code":2,"msg":"hello"}',
                        _out(1, b"hello"), _out(2, b"hello")),
    "code_after_a_space": (b'{"level":"error","code": 7,"msg":"hello"}', None,
                           _out(7, b"hello")),
    "not_json_at_all": (b'"msg":"late" "code":5; "level":"error"', _out(5, b"late"), None),
}


def _map_config():
    config = _config("json64p-v1map")
    return config, _reference(config["reference"]["name"]), config["reference"]["params"]


def _staged(values: list[bytes], n_pad: int, stride: int) -> np.ndarray:
    """The staging matrix the lane builds: a value a row, zero-filled, its
    length little-endian in the first 4 of the 8 trailing columns (values
    over the row are staged empty: the host drops them by ``fits``)."""
    staged = np.zeros((n_pad, stride + 8), np.uint8)
    for i, v in enumerate(values):
        if len(v) <= stride:
            staged[i, : len(v)] = np.frombuffer(v, np.uint8)
            staged[i, stride : stride + 4] = np.frombuffer(struct.pack("<i", len(v)), np.uint8)
    return staged


@pytest.mark.parametrize("label", sorted(MAP_EDGES))
def test_v1_byte_semantics_on_the_edges(label):
    """One value on an edge of the v1 byte semantics: the plain reference
    gives what the table states, the device program (jit, CPU backend) and
    its numpy twin give the reference's bytes, and the JSON reference gives
    what the table states for it, equal or not."""
    from redpanda_tpu.ops.pipeline import (
        make_packed_pipeline, make_packed_pipeline_host, unpack_result)
    from redpanda_tpu.ops.transforms import TransformSpec

    config, ref, params = _map_config()
    value, want, want_json = MAP_EDGES[label]
    assert ref.reference(value, **params) == want
    assert _reference("project_error").reference(value, msg_width=64) == want_json
    spec = TransformSpec.from_json(json.dumps(config["script"]["spec"]))
    stride = params["row_stride"]
    staged = _staged([value, _map_doc(), value], 8, stride)
    dev_fn, r_out = make_packed_pipeline(spec, stride)
    packed = np.asarray(dev_fn(staged))
    assert r_out == 70 and packed.shape == (8, 78)
    assert np.array_equal(packed, make_packed_pipeline_host(spec, stride)(staged))
    out, out_len, keep = unpack_result(packed, r_out)
    fits = len(value) <= stride  # the host's part of the drop
    for row in (0, 2):
        got = bytes(out[row]) if keep[row] and fits else None
        assert got == want and out_len[row] == (70 if keep[row] else 0)
    assert bytes(out[1]) == _out(7, b"hello") and not keep[3:].any()


@pytest.mark.parametrize("seed", [7, 2**31 + 11])
def test_map_program_and_its_numpy_twin_give_the_reference_bytes(seed):
    """The device program of ``json64p-v1map`` (jit on the CPU backend) and
    its numpy twin over seeded documents and every edge value: the same
    bytes from both, and the reference's for every row that fits."""
    from redpanda_tpu.ops.pipeline import (
        make_packed_pipeline, make_packed_pipeline_host, unpack_result)
    from redpanda_tpu.ops.transforms import TransformSpec

    config, ref, params = _map_config()
    stride = params["row_stride"]
    values = make_documents(seed, 2, 200)
    values = values[0] + [v for v, _, _ in MAP_EDGES.values()] + values[1]
    spec = TransformSpec.from_json(json.dumps(config["script"]["spec"]))
    staged = _staged(values, 512, stride)
    dev_fn, r_out = make_packed_pipeline(spec, stride)
    packed = np.asarray(dev_fn(staged))
    assert np.array_equal(packed, make_packed_pipeline_host(spec, stride)(staged))
    out, out_len, keep = unpack_result(packed, r_out)
    want = [ref.reference(v, **params) for v in values]
    got = [bytes(out[i]) if keep[i] and len(v) <= stride else None
           for i, v in enumerate(values)]
    assert got == want and 0 < sum(o is not None for o in want) < len(values)
    assert not keep[len(values):].any() and not out_len[~keep].any()
    # the program's operations carry the stage that owns them
    hlo = dev_fn.lower(staged).compile().as_text()
    stages = {m.split("/")[0] for m in re.findall(r"/jit\(rp_transform\)/([^\"]+)", hlo)}
    assert {"filter", "project"} <= stages


@pytest.mark.parametrize("staging", ["pointer_table", "joined_blob"])
@pytest.mark.parametrize("seed", [7, 2**31 + 11])
def test_map_lane_matches_the_plain_reference(seed, staging, monkeypatch):
    """``TpuEngine`` ``enable_coprocessors`` -> ``submit`` -> sealed reply
    for ``json64p-v1map``'s own script over seeded documents plus the edge
    values, byte-equal and in order to the reference, on both staging
    roads. The script builds new bytes, so each launch takes the matrix
    road, and the harvest's counters add up. A second launch of shorter
    values into the parked (dirty) staging matrix reads the same."""
    from redpanda_tpu.coproc import batch_codec
    from redpanda_tpu.observability import probes

    if staging == "joined_blob":
        monkeypatch.setattr(batch_codec, "explode_ptrs", lambda batches, *a, **k: None)
    config, ref, params = _map_config()
    stride = params["row_stride"]
    edges = [v for v, _, _ in MAP_EDGES.values()]
    parts = make_documents(seed, 4, 256)
    parts[1] = parts[1][:40] + edges + parts[1][40:]
    parts[3] = edges[::-1] + parts[3]
    short = [[_map_doc(code=b"%d" % i, msg=b"s%d" % i) for i in range(p, 300, 4)]
             for p in range(4)]

    def submit(engine, parts):
        req = ProcessBatchRequest([
            ProcessBatchItem(1, NTP.kafka("bench", p), _batches(values, 32, 1000 * p))
            for p, values in enumerate(parts)
        ])
        reply = engine.submit(req).result()
        kept = 0
        for item, values in zip(reply.items, parts):
            got = [r.value for b in item.batches for r in b.records()]
            want = [o for o in (ref.reference(v, **params) for v in values) if o is not None]
            assert got == want, f"partition {item.source.partition}"
            kept += len(want)
        return kept

    twins = (probes.coproc_kept_rows.value, probes.coproc_output_bytes.value)
    engine = TpuEngine(row_stride=stride, host_workers=0)
    try:
        codes = engine.enable_coprocessors(
            [(1, json.dumps(config["script"]["spec"]), ("bench",))]
        )
        assert codes == [EnableResponseCode.success]
        plan = engine._plans[1]
        assert plan.mode == config["lane"] == "payload" and not plan.byte_identity
        kept = submit(engine, parts)
        stats = engine.stats()
        kept_short = submit(engine, short)
        stats_short = engine.stats()
    finally:
        engine.shutdown()

    n_in = sum(map(len, parts))
    n_pad = _bucket_rows(n_in)
    assert 0 < kept < n_in and kept_short == 300
    assert stats["n_launches"] == stats["n_device_launches"] == 1
    assert stats["n_records"] == n_in and stats["n_staged_rows"] == n_pad == 2048
    assert stats["n_oversize_rows"] == sum(len(v) > stride for vs in parts for v in vs) >= 2
    assert stats.get("n_fallback_rows", 0) == 0
    # the matrix road: a [n_pad, 70 + 8] result fetched and rebuilt
    assert stats["bytes_h2d"] == n_pad * (stride + 8)
    assert stats["bytes_d2h"] == n_pad * 78
    assert stats["n_frame_padded"] == 1 and stats["t_rebuild"] > 0
    assert "n_frame_gather" not in stats and "t_frame_gather" not in stats
    # what the map let through, and the 70 B it wrote for each
    assert stats["n_kept_rows"] == kept and stats["bytes_out"] == 70 * kept
    assert stats_short["n_kept_rows"] == kept + 300
    assert stats_short["bytes_out"] == 70 * (kept + 300)
    assert stats_short["n_staging_reuses"] == 1
    # their /metrics twins moved with them (process-wide counters)
    assert probes.coproc_kept_rows.value - twins[0] == kept + 300
    assert probes.coproc_output_bytes.value - twins[1] == 70 * (kept + 300)


def test_v1_and_json_semantics_agree_on_the_benchmarks_documents():
    """On ``docs.py``'s documents the byte semantics of the payload lane
    and the JSON semantics of the columnar lane give the same output for
    every value that fits the staging row; the values over it (about one
    in seven) only the columnar lane transforms. So the two catch-up cells
    run one transform but for that share."""
    _, v1, params = _map_config()
    by_json = _reference("project_error")
    docs = _load("docs.py").make_documents(2**31 + 5, 4, 1024)
    fit = over = kept_fit = kept_over = 0
    for part in docs.values():
        for v in part:
            want = by_json.reference(v, msg_width=params["msg_width"])
            if len(v) <= params["row_stride"]:
                assert v1.reference(v, **params) == want
                fit += 1
                kept_fit += want is not None
            else:
                assert v1.reference(v, **params) is None
                over += 1
                kept_over += want is not None
    assert 0.12 < over / (fit + over) < 0.17 and kept_over > 0
    assert 0.2 < kept_fit / (fit + over) < 0.3


# ------------------------------------------- the cells' launch shapes
# rows a launch: what json64p-where.paced launches (3 batches of 32), what
# json64p-where.catchup launches (64 partitions x 2 batches), and
# the governor's fixed point of json64p-v1.catchup and json64p-v1map.catchup
# in its 32,768-row bucket (64 x 9)
_SHAPES = {96: (3, 1), 4096: (64, 2), 18432: (64, 9)}


@functools.lru_cache(maxsize=1)  # the cases of one shape run back to back
def _shape_inputs(rows: int):
    """(values a partition, request) of one launch of ``rows`` records:
    the benchmark's own seeded documents (benchmarks/docs.py), batches of
    32 as the configurations' producers send them."""
    partitions, batches = _SHAPES[rows]
    docs = _load("docs.py").make_documents(
        11, 64, 32 * batches, only=range(partitions)
    )
    parts = [docs[p] for p in range(partitions)]
    assert sum(map(len, parts)) == rows
    req = ProcessBatchRequest([
        ProcessBatchItem(1, NTP.kafka("bench", p), _batches(values, 32, 1000 * p))
        for p, values in enumerate(parts)
    ])
    return parts, req


@pytest.mark.parametrize("use_native", [True, False], ids=["native", "no_native"])
@pytest.mark.parametrize("rows", sorted(_SHAPES))
@pytest.mark.parametrize("config_name", ["json64p-where", "json64p-v1", "json64p-v1map"])
def test_single_road_at_the_cells_launch_shapes(
    config_name, rows, use_native, monkeypatch
):
    """One launch of each cell's shape through an engine built as the
    broker builds it (default ``host_workers``, Zstd-sealed output): its
    records equal the configuration's plain reference one for one and in
    order, it ran on the dispatching thread's one road, and ``stats()``
    holds nothing of the fork that used to choose another."""
    from redpanda_tpu.coproc import batch_codec
    from redpanda_tpu.coproc import column_plan

    if not use_native:
        monkeypatch.setattr(batch_codec, "_native", lambda: None)
        monkeypatch.setattr(column_plan, "_native", lambda: None)
    config = _config(config_name)
    ref = _reference(config["reference"]["name"])
    params = config["reference"]["params"]
    parts, req = _shape_inputs(rows)
    TpuEngine.reset_columnar_probe()
    engine = TpuEngine(row_stride=params.get("row_stride", 1024))
    try:
        codes = engine.enable_coprocessors(
            [(1, json.dumps(config["script"]["spec"]), ("bench",))]
        )
        assert codes == [EnableResponseCode.success]
        assert engine._plans[1].mode == config["lane"]
        reply = engine.submit(req).result()
        stats = engine.stats()
        assert engine._host_pool is None
    finally:
        engine.shutdown()
        TpuEngine.reset_columnar_probe()

    kept = 0
    for item, values in zip(reply.items, parts):
        assert len(item.batches) <= len(values) // 32
        got = [r.value for b in item.batches for r in b.records()]
        want = [o for o in (ref.reference(v, **params) for v in values) if o is not None]
        assert got == want, f"partition {item.source.partition}"
        kept += len(want)
    assert 0 < kept < rows and stats["n_kept_rows"] == kept
    assert stats["n_launches"] == 1 and stats["n_records"] == rows
    assert stats.get("n_fallback_rows", 0) == 0 and "t_seal" in stats
    assert "host_pool_probe" not in stats and "host_pool_recal" not in stats
    assert not [k for k in stats if k.startswith(("t_shard", "n_shard"))]
    if config["lane"] == "payload":
        n_pad = _bucket_rows(rows)
        assert stats["n_staged_rows"] == n_pad and stats["n_device_launches"] == 1
        if config["script"]["spec"]["ops"][-1]["op"] == "map_project":
            # the script builds new bytes: a result matrix back, rebuilt
            assert stats["bytes_d2h"] == n_pad * 78 and stats["n_frame_padded"] == 1
            assert stats["bytes_out"] == 70 * kept
        else:  # a filter: one keep bit a row back
            assert stats["bytes_d2h"] == n_pad // 8 and stats["n_frame_gather"] == 1
        n_over = sum(len(v) > params["row_stride"] for vs in parts for v in vs)
        assert stats.get("n_oversize_rows", 0) == n_over
        if rows >= 4096:  # about one document in seven is wider than the row
            assert 0.12 < n_over / rows < 0.17
    else:
        assert stats["n_frame_padded"] == 1 and "n_frame_gather" not in stats


# ------------------------------------ the projection's windows: no gather (PR 31)
@pytest.mark.parametrize("namespace", ["numpy", "jax.numpy"])
@pytest.mark.parametrize("width", [1, 12, 65, "r+3"])
@pytest.mark.parametrize("r", [1024, 256, 100])
def test_gather_window_is_each_rows_plain_slice(r, width, namespace):
    """``_gather_window`` (a log-step row shift, no gather) against a plain
    Python slice of every row: zeros where ``pos`` is negative or past the
    row, zero fill past the row's end, a width that may exceed the row; the
    edge positions and seeded ones, numpy and jit on the CPU backend."""
    from redpanda_tpu.ops.transforms import _gather_window

    w = r + 3 if width == "r+3" else width
    edges = [-1, 0, r - w - 1, r - w, r - w + 1, r - 1, r, r + 4]
    rng = np.random.default_rng(r * 131 + w)
    pos = np.array(edges + list(rng.integers(-2, r + 2, 64)), np.int32)
    data = rng.integers(1, 256, (len(pos), r), dtype=np.uint8)
    if namespace == "numpy":
        got = _gather_window(np, data, pos, w)
    else:
        import jax
        import jax.numpy as jnp

        got = np.asarray(jax.jit(lambda d, p: _gather_window(jnp, d, p, w))(data, pos))
    assert got.dtype == np.uint8 and got.shape == (len(pos), w)
    for row, p, window in zip(data, pos.tolist(), got):
        want = bytes(row[p : p + w]).ljust(w, b"\0") if p >= 0 else bytes(w)
        assert bytes(window) == want, (r, w, p)


# sha256, first 16 hex digits, of the StableHLO text (``lower().as_text()``: no
# source locations) of ``json64p-v1``'s mask program at [8, 1032], taken at the parent of PR 31
# with this container's JAX. A change to ``_find_pattern``, ``packbits`` or
# the parse moves it, and so does another JAX: print the text at both commits
# and pin what a reading of the difference allows.
_MASK_PROGRAM_DIGEST = "3f3175d599a3a6cb"


def test_map_program_holds_no_gather_and_the_mask_program_is_what_it_was():
    """``json64p-v1map``'s device program, lowered and compiled (CPU
    backend), holds no ``gather`` operation: its two windows are static
    slices and selects. ``json64p-v1``'s mask program never reaches
    ``_gather_window``: its lowered text is the parent's, byte for byte."""
    import jax

    from redpanda_tpu.ops.pipeline import make_packed_pipeline
    from redpanda_tpu.ops.transforms import TransformSpec

    def lowered(name: str, mask_only: bool):
        config = _config(name)
        spec = TransformSpec.from_json(json.dumps(config["script"]["spec"]))
        stride = config["reference"]["params"]["row_stride"]
        fn, _ = make_packed_pipeline(spec, stride, mask_only=mask_only)
        return fn.lower(jax.ShapeDtypeStruct((8, stride + 8), np.uint8))

    map_program = lowered("json64p-v1map", False)
    for text in (map_program.as_text(), map_program.compile().as_text()):
        assert "select" in text and not re.search(r"\bgather\b", text)
    mask_text = lowered("json64p-v1", True).as_text()
    assert hashlib.sha256(mask_text.encode()).hexdigest()[:16] == _MASK_PROGRAM_DIGEST
