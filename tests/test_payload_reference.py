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

And both configurations' scripts (``json64p-where``: the columnar lane)
at the launch shapes the ledger judges the benchmark's cells at, on the
one host road a single-device launch has.
"""

import functools
import importlib.util
import json
import os

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
        monkeypatch.setattr(batch_codec, "explode_ptrs", lambda batches: None)
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


# ------------------------------------------- the cells' launch shapes
# rows a launch: what json64p-where.paced launches (3 batches of 32), what
# json64p-where.catchup launches (64 partitions x 2 batches), and
# json64p-v1.catchup's governor fixed point in its 32,768-row bucket (64 x 9)
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
@pytest.mark.parametrize("config_name", ["json64p-where", "json64p-v1"])
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
    assert 0 < kept < rows
    assert stats["n_launches"] == 1 and stats["n_records"] == rows
    assert stats.get("n_fallback_rows", 0) == 0 and "t_seal" in stats
    assert "host_pool_probe" not in stats and "host_pool_recal" not in stats
    assert not [k for k in stats if k.startswith(("t_shard", "n_shard"))]
    if config["lane"] == "payload":
        n_pad = _bucket_rows(rows)
        assert stats["n_staged_rows"] == n_pad and stats["n_device_launches"] == 1
        assert stats["bytes_d2h"] == n_pad // 8 and stats["n_frame_gather"] == 1
        n_over = sum(len(v) > params["row_stride"] for vs in parts for v in vs)
        assert stats.get("n_oversize_rows", 0) == n_over
        if rows >= 4096:  # about one document in seven is wider than the row
            assert 0.12 < n_over / rows < 0.17
    else:
        assert stats["n_frame_padded"] == 1 and "n_frame_gather" not in stats
