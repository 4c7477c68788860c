"""Device programs are built before a script serves (PR 45, ROADMAP B-I.1).

A payload script's programs, one a row bucket, are lowered and compiled at
deploy on the engine's builder thread (``TpuEngine._precompile_loop``), up to the
largest bucket the governor's read budget can hand one launch. A launch then
runs a ready program: at every bucket of the ladder no first run is left on
the serving path, a launch over the top (or one that arrives while the ladder
is building) is cut to the largest ready bucket and gives the uncut launch's
bytes, a second script of one spec builds nothing, and a ladder whose build
failed leaves the script on the first-run path it had before. Held here on
the CPU at small sizes, against ``compile_transform_host``'s numpy twin and
the benchmark's plain references. Also: the mask road over Zstd input at the
paced cell's launch sizes (configuration ``json64p-v1-zstd``), where the
pooled decompress buffers have to outlive the gather framing.
"""

import importlib.util
import json
import os
import threading
import time

import numpy as np
import pytest

from redpanda_tpu.coproc import EnableResponseCode, ProcessBatchRequest, TpuEngine, batch_codec
from redpanda_tpu.coproc import engine as engine_mod
from redpanda_tpu.coproc import faults
from redpanda_tpu.coproc.engine import ProcessBatchItem, _bucket_rows
from redpanda_tpu.coproc.governor import Governor
from redpanda_tpu.models import NTP, Record, RecordBatch
from redpanda_tpu.models.record import Compression
from redpanda_tpu.ops.pipeline import IN_META, make_packed_pipeline_host, unpack_result
from redpanda_tpu.ops.transforms import TransformSpec

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
STRIDE = 1024
TICK_READ = 32 * 1024


def _load(relpath: str):
    path = os.path.join(BENCH, relpath)
    name = "precompile_" + relpath[:-3].replace("/", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _config(name: str) -> dict:
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


docs_text = _load("docs_text.py")
MASK = _config("json64p-v1-zstd")     # filter_contains: a keep bit a row back
MATRIX = _config("json64p-v1map")     # filter | map_project: a result matrix back
CONFIGS = {"mask": MASK, "matrix": MATRIX}


def _spec(config: dict) -> str:
    return json.dumps(config["script"]["spec"])


def _reference(config: dict):
    ref = _load("references/" + config["reference"]["name"] + ".py")
    params = config["reference"]["params"]
    return lambda v: ref.reference(v, **params)


def _engine(top_rows: int = 512, **kw) -> TpuEngine:
    """An engine whose governor knows a read budget that makes the ladder's
    top ``top_rows``: one partition, ``group_ticks`` capped at 1, a tick's
    read of top_rows x 128 B (a staging row of 1,024 B is sized for records
    an eighth of it wide)."""
    engine = TpuEngine(row_stride=STRIDE, host_workers=0, **kw)
    engine.governor.configure_autotune(
        group_ticks_cap=1, tick_read_bytes=top_rows * STRIDE // 8
    )
    return engine


def _wait_ladder(engine: TpuEngine, script_id: int = 1, timeout_s: float = 120.0) -> dict:
    t_end = time.monotonic() + timeout_s
    while time.monotonic() < t_end:
        ready = engine.stats()["programs_ready"].get(script_id)
        if ready is not None and ready["state"] != "building":
            return ready
        time.sleep(0.02)
    raise AssertionError("the ladder did not finish")


def _batches(values, per_batch: int = 32, codec=Compression.none) -> list[RecordBatch]:
    return [
        RecordBatch.build(
            [Record(offset_delta=i, timestamp_delta=i, value=v)
             for i, v in enumerate(values[s : s + per_batch])],
            base_offset=s, first_timestamp=1000, compression=codec,
        )
        for s in range(0, len(values), per_batch)
    ]


def _launch(engine: TpuEngine, values, script_id: int = 1, codec=Compression.none,
            per_batch: int = 32):
    req = ProcessBatchRequest(
        [ProcessBatchItem(script_id, NTP.kafka("bench", 0), _batches(values, per_batch, codec))]
    )
    reply = engine.submit(req).result()
    return [r.value for item in reply.items for b in item.batches for r in b.records()], reply


def _documents(seed: int, n: int) -> list:
    return list(docs_text.make_documents(seed, 1, n)[0])


def _twin(config: dict, values) -> list:
    """What ``compile_transform_host``'s numpy twin of the packed pipeline
    keeps of ``values``, as the lane frames it: the value itself for a
    filter, the projected row for a map."""
    spec = TransformSpec.from_json(_spec(config))
    mask_only = spec.mapper is None
    n_pad = _bucket_rows(len(values))
    staged = np.zeros((n_pad, STRIDE + IN_META), np.uint8)
    for i, v in enumerate(values):
        if v and len(v) <= STRIDE:
            staged[i, : len(v)] = np.frombuffer(v, np.uint8)
            staged[i, STRIDE : STRIDE + 4] = np.frombuffer(
                np.int32(len(v)).tobytes(), np.uint8
            )
    packed = make_packed_pipeline_host(spec, STRIDE, mask_only)(staged)
    if mask_only:
        keep = np.unpackbits(packed)[: len(values)].astype(bool)
        return [v for v, k in zip(values, keep) if k]
    from redpanda_tpu.ops.transforms import transform_out_width

    out, out_len, keep = unpack_result(packed, transform_out_width(spec, STRIDE))
    return [bytes(out[i, : out_len[i]]) for i in range(len(values)) if keep[i]]


# ------------------------------------------------------------------ the ladder's size
@pytest.mark.parametrize("partitions, cap, tick, stride, top", [
    (64, 8, 32 * 1024, 1024, 131072),   # the benchmark's cells: NEXmark's launch exactly
    (64, 8, 32 * 1024, 1152, 131072),
    (1, 8, 32 * 1024, 1024, 2048),
    (4, 1, 32 * 1024, 1024, 1024),
    (1, 1, 4096, 1024, 128),            # never under the smallest bucket
])
def test_the_top_follows_the_read_budget_and_the_row_stride(partitions, cap, tick, stride, top):
    engine = TpuEngine(row_stride=stride, host_workers=0)
    try:
        assert engine._ladder_top(partitions) is None  # a bare engine: no budget known
        engine.governor.configure_autotune(group_ticks_cap=cap, tick_read_bytes=tick)
        assert engine.governor.launch_read_bytes(partitions) == partitions * cap * tick
        assert engine._ladder_top(partitions) == top
    finally:
        engine.shutdown()


@pytest.mark.parametrize("enabled, base, cap, want", [
    (True, 1, 8, 8), (False, 1, 8, 1), (False, 3, 8, 3), (True, 4, 2, 4),
])
def test_the_read_budget_is_the_furthest_the_launch_knob_can_go(enabled, base, cap, want):
    gov = Governor(fault_policy=faults.FaultPolicy())
    assert gov.launch_read_bytes(4) is None
    gov.configure_autotune(enabled=enabled, group_ticks=base, group_ticks_cap=cap)
    assert gov.launch_read_bytes(4) is None  # armed, but no pacemaker's budget told
    gov.configure_autotune(enabled=enabled, group_ticks=base, group_ticks_cap=cap,
                           tick_read_bytes=1000)
    assert gov.launch_read_bytes(4) == 4 * 1000 * want


def test_a_bare_engine_builds_no_ladder_and_serves_through_first_runs():
    engine = TpuEngine(row_stride=STRIDE, host_workers=0)
    try:
        assert engine.enable_coprocessors([(1, _spec(MASK), ("bench",))]) == [
            EnableResponseCode.success]
        assert engine.await_programs(1) is True and engine.programs_ready(1) == []
        values = _documents(3, 64)
        got, _ = _launch(engine, values)
        stats = engine.stats()
        assert got == _twin(MASK, values)
        assert stats["n_compiles"] == 1 and "n_precompiles" not in stats
        assert stats["programs_ready"] == {}
    finally:
        engine.shutdown()


# ------------------------------------------------------------------ every bucket, no first run
@pytest.mark.parametrize("road", sorted(CONFIGS))
@pytest.mark.parametrize("rows", [1, 128, 129, 256, 300, 512])
def test_a_launch_at_every_bucket_of_the_ladder_is_no_first_run(rows, road):
    config = CONFIGS[road]
    reference = _reference(config)
    engine = _engine(512)
    try:
        assert engine.enable_coprocessors(
            [(1, _spec(config), ("bench",))], partitions={"bench": 1}
        ) == [EnableResponseCode.success]
        assert engine.await_programs(1) is True
        ready = _wait_ladder(engine)
        assert ready == {"buckets": [128, 256, 512], "top": 512, "state": "ready"}
        before = engine.stats()
        assert before["n_precompiles"] == 3 and before["t_precompile"] > 0
        assert "n_compiles" not in before and "n_device_launches" not in before
        # the built programs are listed before any launch has met them
        assert {(c["lane"], c["n_pad"]) for c in before["compiled_programs"]} == {
            ("payload", 128), ("payload", 256), ("payload", 512)}
        values = _documents(2**31 + rows, rows)
        got, _ = _launch(engine, values)
        after = engine.stats()
        want = [o for o in map(reference, values) if o is not None]
        assert got == want == _twin(config, values)
        assert after["n_device_launches"] == 1
        assert after["n_staged_rows"] == _bucket_rows(rows)
        assert "n_compiles" not in after and "t_compile" not in after
        assert "n_launch_cuts" not in after and after["n_precompiles"] == 3
        assert after.get("n_fallback_rows", 0) == 0
    finally:
        engine.shutdown()


# ------------------------------------------------------------------ over the top: cut
@pytest.mark.parametrize("road", sorted(CONFIGS))
@pytest.mark.parametrize("rows", [513, 1100])
def test_a_launch_over_the_top_is_cut_and_gives_the_uncut_launchs_bytes(rows, road):
    config = CONFIGS[road]
    reference = _reference(config)
    values = _documents(2**31 + 45, rows)
    values[5:5] = [b"", None, b"w" * 3000]  # the lane's edges ride along

    def run(engine):
        try:
            assert engine.enable_coprocessors(
                [(1, _spec(config), ("bench",))], partitions={"bench": 1}
            ) == [EnableResponseCode.success]
            if engine._ladders:
                _wait_ladder(engine)
            _got, reply = _launch(engine, values)
            return reply, engine.stats()
        finally:
            engine.shutdown()

    cut, cut_stats = run(_engine(512))
    uncut, uncut_stats = run(TpuEngine(row_stride=STRIDE, host_workers=0))
    parts = -(-len(values) // 512)
    assert cut_stats["n_launch_cuts"] == 1 and "n_compiles" not in cut_stats
    assert cut_stats["n_device_launches"] == 1 and cut_stats["n_launches"] == 1
    assert cut_stats["n_staged_rows"] == parts * 512
    assert cut_stats["bytes_h2d"] == parts * 512 * (STRIDE + IN_META)
    assert "n_launch_cuts" not in uncut_stats and uncut_stats["n_compiles"] == 1
    assert uncut_stats["n_staged_rows"] == _bucket_rows(len(values))
    # byte for byte: every output batch, sealed, as the uncut launch wrote it
    a = [b.encode_internal() for item in cut.items for b in item.batches]
    b = [b.encode_internal() for item in uncut.items for b in item.batches]
    assert a == b and len(a) == -(-len(values) // 32)
    got = [r.value for item in cut.items for bt in item.batches for r in bt.records()]
    assert got == [o for o in map(reference, values) if o is not None]
    assert cut_stats["n_kept_rows"] == uncut_stats["n_kept_rows"] == len(got) > 0
    assert cut_stats.get("n_fallback_rows", 0) == 0


def test_a_launch_that_arrives_while_the_ladder_builds_is_cut_to_what_is_ready(monkeypatch):
    """The first launches of a deploy: bucket 128 is built, the builder is
    held inside 256's compile, and a launch of 300 rows neither waits for
    it nor compiles inline: three parts of 128."""
    gate, entered = threading.Event(), threading.Event()
    real = engine_mod.lower_packed_pipeline

    def slow(fn, shape):
        if shape[0] > 128:
            entered.set()
            assert gate.wait(60.0)
        return real(fn, shape)

    monkeypatch.setattr(engine_mod, "lower_packed_pipeline", slow)
    engine = _engine(512)
    try:
        engine.enable_coprocessors([(1, _spec(MASK), ("bench",))], partitions={"bench": 1})
        assert entered.wait(60.0)
        assert engine.await_programs(1, n=1) is True
        assert engine.programs_ready(1) == [128]
        assert engine.stats()["programs_ready"][1]["state"] == "building"
        values = _documents(11, 300)
        got, _ = _launch(engine, values)
        stats = engine.stats()
        assert got == _twin(MASK, values)
        assert stats["n_launch_cuts"] == 1 and stats["n_staged_rows"] == 3 * 128
        assert "n_compiles" not in stats
        gate.set()
        assert _wait_ladder(engine)["buckets"] == [128, 256, 512]
        got, _ = _launch(engine, values)
        stats = engine.stats()
        assert got == _twin(MASK, values)
        assert stats["n_launch_cuts"] == 1 and stats["n_staged_rows"] == 3 * 128 + 512
    finally:
        gate.set()
        engine.shutdown()


# ------------------------------------------------------------------ one spec, one ladder
def test_a_second_script_of_one_spec_builds_nothing():
    engine = _engine(256)
    try:
        assert engine.enable_coprocessors(
            [(1, _spec(MASK), ("bench",))], partitions={"bench": 1}
        ) == [EnableResponseCode.success]
        _wait_ladder(engine, 1)
        built = engine.stats()["n_precompiles"]
        assert built == 2
        assert engine.enable_coprocessors(
            [(2, _spec(MASK), ("bench",))], partitions={"bench": 1}
        ) == [EnableResponseCode.success]
        assert _wait_ladder(engine, 2)["buckets"] == [128, 256]
        values = _documents(5, 200)
        assert _launch(engine, values, script_id=2)[0] == _twin(MASK, values)
        stats = engine.stats()
        assert stats["n_precompiles"] == built and "n_compiles" not in stats
        assert {(c["script_id"], c["n_pad"]) for c in stats["compiled_programs"]} == {
            (1, 128), (1, 256), (2, 128), (2, 256)}
        # a script of the spec over more partitions raises the top: the
        # builder goes on from where the ladder stands
        assert engine.enable_coprocessors(
            [(3, _spec(MASK), ("wide",))], partitions={"wide": 4}
        ) == [EnableResponseCode.success]
        assert _wait_ladder(engine, 3)["buckets"] == [128, 256, 512, 1024]
        assert engine.stats()["n_precompiles"] == built + 2
        assert engine.programs_ready(1) == [128, 256, 512, 1024]
        # another spec is another ladder
        assert engine.enable_coprocessors(
            [(4, _spec(MATRIX), ("bench",))], partitions={"bench": 1}
        ) == [EnableResponseCode.success]
        _wait_ladder(engine, 4)
        assert engine.stats()["n_precompiles"] == built + 4
    finally:
        engine.shutdown()


# ------------------------------------------------------------------ strides a launch shows (PR 47)
def _narrow_documents(n: int) -> list:
    """JSON documents under 128 B (the configurations' own are ~1 KB):
    a launch of them shows the 128-byte stride."""
    return [b'{"level":"%s","code":%d,"msg":"narrow %d"}'
            % ((b"error", b"warn", b"info")[i % 3], i, i) for i in range(n)]


def _wait_strides(engine: TpuEngine, script_id: int, strides: set, timeout_s: float = 120.0) -> dict:
    t_end = time.monotonic() + timeout_s
    while time.monotonic() < t_end:
        shown = engine.stats()["programs_ready"][script_id].get("strides", {})
        if strides <= set(shown) and all(shown[s]["state"] != "building" for s in strides):
            return shown
        time.sleep(0.02)
    raise AssertionError(f"the ladders of {strides} did not finish: {shown}")


@pytest.mark.parametrize("road", sorted(CONFIGS))
def test_a_stride_first_seen_in_serving_builds_off_the_serving_path(road, monkeypatch):
    """The first launch of narrow values finds no program 128 B wide: it
    goes at the lane's own stride, which is built, and the bucket it went
    without is built 128 B wide on ``rptpu-precompile`` (that bucket and no
    other: a narrower stride's ladder holds what launches asked for); the
    next launch runs it. No launch is a first run: every program's record
    reads ``t_first_run_s`` 0.0."""
    config = CONFIGS[road]
    reference = _reference(config)
    built = []  # (staged shape, the thread that built its program), in order
    real = engine_mod.lower_packed_pipeline

    def spy(fn, shape):
        built.append((shape, threading.current_thread().name))
        return real(fn, shape)

    monkeypatch.setattr(engine_mod, "lower_packed_pipeline", spy)
    engine = _engine(256)
    try:
        assert engine.enable_coprocessors(
            [(1, _spec(config), ("bench",))], partitions={"bench": 1}
        ) == [EnableResponseCode.success]
        assert _wait_ladder(engine) == {"buckets": [128, 256], "top": 256, "state": "ready"}
        values = _narrow_documents(200)
        want = [o for o in map(reference, values) if o is not None]
        assert 0 < len(want) < 200
        got, _ = _launch(engine, values)
        first = engine.stats()
        assert got == want
        assert first["bytes_h2d"] == 256 * (STRIDE + IN_META)  # served at a ready stride
        assert "n_compiles" not in first and "n_launch_cuts" not in first
        lane = engine._lanes[1]
        assert sorted(lane.fns) == [128, STRIDE]
        assert engine._ladders[lane.fns[128][0]].top == 256
        shown = _wait_strides(engine, 1, {128})
        assert shown == {128: {"buckets": [256], "state": "ready"}}
        # all on the builder thread
        assert built == [
            ((128, STRIDE + IN_META), "rptpu-precompile"),
            ((256, STRIDE + IN_META), "rptpu-precompile"),
            ((256, 128 + IN_META), "rptpu-precompile"),
        ]
        assert engine.stats()["n_precompiles"] == 3
        got, _ = _launch(engine, values)
        second = engine.stats()
        assert got == want
        assert second["bytes_h2d"] - first["bytes_h2d"] == 256 * (128 + IN_META)
        assert "n_compiles" not in second and "t_compile" not in second
        assert second["n_device_launches"] == 2 and second.get("n_fallback_rows", 0) == 0
        met = {(c["stride"], c["n_pad"]): c for c in second["compiled_programs"]
               if "t_precompile_s" not in c}
        assert set(met) == {(STRIDE, 256), (128, 256)}
        assert all(c["t_first_run_s"] == 0.0 for c in second["compiled_programs"])
        # smaller launches later ask for another bucket of the stride. One
        # launch is no reason to build it (a step of the launch knob's
        # ramp); launches that go on asking are: the builder, which had
        # ended, takes it up, and until then they go wider
        fewer = values[:90]
        want = [o for o in map(reference, fewer) if o is not None]
        monkeypatch.setattr(engine_mod, "_WANT_HOLD_S", 0.4)
        assert _launch(engine, fewer)[0] == want
        time.sleep(0.1)
        assert engine.stats()["programs_ready"][1]["strides"] == {
            128: {"buckets": [256], "state": "ready"}}
        time.sleep(0.2)
        assert _launch(engine, fewer)[0] == want  # asked for 0.3 s: not yet
        time.sleep(0.2)
        assert _launch(engine, fewer)[0] == want  # 0.5 s: wanted now
        third = engine.stats()
        assert third["bytes_h2d"] - second["bytes_h2d"] == 3 * 128 * (STRIDE + IN_META)
        assert _wait_strides(engine, 1, {128}) == {128: {"buckets": [128, 256], "state": "ready"}}
        assert _launch(engine, fewer)[0] == want
        fourth = engine.stats()
        assert fourth["bytes_h2d"] - third["bytes_h2d"] == 128 * (128 + IN_META)
        assert "n_compiles" not in fourth and fourth["n_precompiles"] == 4
    finally:
        engine.shutdown()


def test_a_second_deploy_of_the_spec_starts_the_remembered_strides_ladders(monkeypatch):
    """The strides a spec's launches have shown outlive its scripts: a
    later deploy of the spec finds their ladders at deploy (and raises
    them with its own top), so its first narrow launch is staged narrow."""
    reference = _reference(MATRIX)
    monkeypatch.setattr(engine_mod, "_WANT_HOLD_S", 0.0)  # the first asking is enough
    engine = _engine(256)
    try:
        assert engine.enable_coprocessors(
            [(1, _spec(MATRIX), ("bench",))], partitions={"bench": 1}
        ) == [EnableResponseCode.success]
        _wait_ladder(engine)
        values = _narrow_documents(100)
        want = [o for o in map(reference, values) if o is not None]
        assert _launch(engine, values)[0] == want
        # the launch's own bucket, and the one its mix fills at the top
        assert _wait_strides(engine, 1, {128}) == {
            128: {"buckets": [128, 256], "state": "ready"}}
        built = engine.stats()["n_precompiles"]
        assert built == 4
        engine.disable_coprocessors([1])
        assert engine.stats()["programs_ready"] == {}
        # the same spec again: both ladders stand, nothing is built
        assert engine.enable_coprocessors(
            [(2, _spec(MATRIX), ("bench",))], partitions={"bench": 1}
        ) == [EnableResponseCode.success]
        ready = engine.stats()["programs_ready"][2]
        assert ready == {"buckets": [128, 256], "top": 256, "state": "ready",
                         "strides": {128: {"buckets": [128, 256], "state": "ready"}}}
        before = engine.stats()
        assert _launch(engine, values, script_id=2)[0] == want
        after = engine.stats()
        assert after["bytes_h2d"] - before["bytes_h2d"] == 128 * (128 + IN_META)
        assert after["n_precompiles"] == built and "n_compiles" not in after
        # over more partitions: the lane's own ladder is raised at the
        # deploy; the remembered stride's follows it in rows and goes on
        # holding what launches have asked for, no more
        assert engine.enable_coprocessors(
            [(3, _spec(MATRIX), ("wide",))], partitions={"wide": 4}
        ) == [EnableResponseCode.success]
        assert _wait_ladder(engine, 3)["buckets"] == [128, 256, 512, 1024]
        assert _wait_strides(engine, 3, {128})[128]["buckets"] == [128, 256]
        assert engine.stats()["n_precompiles"] == built + 2
        assert engine._ladders[engine._lanes[3].fns[128][0]].top == 1024
        # another engine has shown nothing yet
        other = _engine(256)
        try:
            other.enable_coprocessors([(1, _spec(MATRIX), ("bench",))], partitions={"bench": 1})
            assert "strides" not in _wait_ladder(other)
        finally:
            other.shutdown()
    finally:
        engine.shutdown()


def _padded_documents(n: int, size: int) -> list:
    """JSON documents of exactly ``size`` bytes."""
    out = []
    for i in range(n):
        head = b'{"level":"%s","code":%d,"msg":"m%d","pad":"' % (
            (b"error", b"warn", b"info")[i % 3], i, i)
        out.append(head + b"x" * (size - len(head) - 2) + b'"}')
    return out


@pytest.mark.parametrize("size,stride,ahead", [
    (100, 128, 1024),   # as dense as the top is sized for: the top itself
    (600, 640, 256),    # 4.7 times wider: the read budget holds 218 of them
    (1000, 1024, None),  # the lane's own stride: its ladder stands, nothing to ask
])
def test_the_bucket_built_ahead_is_the_one_the_read_budget_fills(size, stride, ahead):
    """A launch of 100 values, a step of the launch knob's ramp, asks at
    once for the program its stream will run when the read budget hands a
    launch all it can: the ladders' top in rows where the values are an
    eighth of the lane's stride wide, as many fewer rows as they are wider.
    Its own bucket (128) is not built for one asking."""
    reference = _reference(MATRIX)
    engine = _engine(1024)
    try:
        assert engine.enable_coprocessors(
            [(1, _spec(MATRIX), ("bench",))], partitions={"bench": 1}
        ) == [EnableResponseCode.success]
        assert _wait_ladder(engine)["buckets"] == [128, 256, 512, 1024]
        values = _padded_documents(100, size)
        assert {len(v) for v in values} == {size}
        want = [o for o in map(reference, values) if o is not None]
        assert _launch(engine, values)[0] == want and want
        assert engine.stats()["bytes_h2d"] == 128 * (STRIDE + IN_META)
        if ahead is None:
            assert "strides" not in _wait_ladder(engine)
            assert sorted(engine._lanes[1].fns) == [STRIDE]
        else:
            assert _wait_strides(engine, 1, {stride}) == {
                stride: {"buckets": [ahead], "state": "ready"}}
        assert engine.stats()["n_precompiles"] == 4 + (ahead is not None)
    finally:
        engine.shutdown()


def test_what_a_narrower_strides_launches_wanted_is_built_before_the_lanes_ladder_goes_on(
    monkeypatch,
):
    """A stream shows its stride while the lane's own ladder is still
    building: the one program it wants comes next, and the lane's ladder
    goes on after it."""
    built = []
    real = engine_mod.lower_packed_pipeline

    def slow(fn, shape):
        built.append(shape)
        time.sleep(0.5 if shape[0] >= 512 else 0.0)
        return real(fn, shape)

    monkeypatch.setattr(engine_mod, "lower_packed_pipeline", slow)
    engine = _engine(2048)
    try:
        assert engine.enable_coprocessors(
            [(1, _spec(MATRIX), ("bench",))], partitions={"bench": 1}
        ) == [EnableResponseCode.success]
        assert engine.await_programs(1, n=2)
        _launch(engine, _narrow_documents(100))  # while (512, 1032) builds
        assert _wait_ladder(engine)["buckets"] == [128, 256, 512, 1024, 2048]
        _wait_strides(engine, 1, {128})
        own = STRIDE + IN_META
        assert built == [(128, own), (256, own), (512, own), (2048, 128 + IN_META),
                         (1024, own), (2048, own)]
    finally:
        engine.shutdown()


# ------------------------------------------------------------------ a failed build
def test_a_failed_precompile_leaves_the_first_run_path_and_is_counted(monkeypatch):
    def broken(fn, shape):
        raise RuntimeError("no compiler today")

    monkeypatch.setattr(engine_mod, "lower_packed_pipeline", broken)
    engine = _engine(512)
    try:
        assert engine.enable_coprocessors(
            [(1, _spec(MASK), ("bench",))], partitions={"bench": 1}
        ) == [EnableResponseCode.success]
        ready = _wait_ladder(engine)
        assert ready["buckets"] == [] and ready["state"].startswith("RuntimeError")
        assert engine.await_programs(1) is True  # it does not hold the fiber
        values = _documents(9, 200)
        got, _ = _launch(engine, values)
        stats = engine.stats()
        assert got == _twin(MASK, values)
        assert stats["n_precompile_failures"] == 1 and "n_precompiles" not in stats
        assert stats["n_compiles"] == 1 and stats["t_compile"] > 0  # the old first run
        assert "n_launch_cuts" not in stats and stats.get("n_fallback_rows", 0) == 0
    finally:
        engine.shutdown()


# ------------------------------------------------------------------ the mask road over Zstd input
@pytest.mark.parametrize("n_batches", [1, 4, 8])
def test_mask_road_over_zstd_keeps_its_decompress_buffers_until_the_gather(n_batches, monkeypatch):
    """Configuration ``json64p-v1-zstd`` at the paced cell's launch sizes:
    a launch's kept values are framed out of the pooled decompress buffers,
    so they go back to the pool after the gather framing and not after the
    pack; one buffer serves a hundred launches."""
    if batch_codec.explode_ptrs(_batches([b"x"], 1, Compression.zstd), batch_codec.Arena()) is None:
        pytest.skip("the native library has no pointer-table explode here")
    reference = _reference(MASK)
    engine = _engine(256)
    seen = []
    real_gather = batch_codec.frame_exploded_gather

    def gather(ex, keep, ranges, arena=None):
        # inside the framing the launch still holds its buffer
        seen.append(engine._uncompress_pool.stats()["free_buffers"])
        return real_gather(ex, keep, ranges, arena=arena)

    monkeypatch.setattr(batch_codec, "frame_exploded_gather", gather)
    try:
        assert engine.enable_coprocessors(
            [(1, _spec(MASK), ("bench",))], partitions={"bench": 1}
        ) == [EnableResponseCode.success]
        _wait_ladder(engine)
        for k in range(100):
            values = _documents(2**31 + 1000 * n_batches + k, 32 * n_batches)
            got, _ = _launch(engine, values, codec=Compression.zstd)
            assert got == [o for o in map(reference, values) if o is not None]
            pool = engine._uncompress_pool.stats()
            assert pool["free_buffers"] == 1, k  # back after the framing
        stats = engine.stats()
        assert seen == [0] * 100  # and never before it
        assert pool["allocs"] == 1 and pool["reuses"] == 99
        assert stats["n_uncompressed_batches"] == 100 * n_batches
        assert stats["n_frame_gather"] == 100 and "n_compiles" not in stats
        assert stats["n_staged_rows"] == 100 * _bucket_rows(32 * n_batches)
        assert stats.get("n_fallback_rows", 0) == 0 and "n_launch_cuts" not in stats
    finally:
        engine.shutdown()


# ------------------------------------------------------------------ surfaces
def test_the_new_counters_are_on_metrics_and_the_span_is_on_the_ring():
    from redpanda_tpu.metrics import registry
    from redpanda_tpu.observability.trace import tracer

    def total(name: str) -> float:
        return sum(float(line.rsplit(" ", 1)[1])
                   for line in registry.render_prometheus().splitlines()
                   if line.startswith("redpanda_tpu_" + name))

    before = {k: total(k) for k in ("coproc_precompiles_total", "coproc_launch_cuts_total")}
    was = tracer.enabled
    tracer.configure(enabled=True)
    engine = _engine(256)
    try:
        engine.enable_coprocessors([(1, _spec(MASK), ("bench",))], partitions={"bench": 1})
        _wait_ladder(engine)
        _launch(engine, _documents(1, 300))
        assert total("coproc_precompiles_total") == before["coproc_precompiles_total"] + 2
        assert total("coproc_launch_cuts_total") == before["coproc_launch_cuts_total"] + 1
        assert 'coproc_stage_latency_us_count{stage="precompile"}' in registry.render_prometheus()
        spans = [s for t in tracer.recent(0) for s in t["spans"]
                 if s["name"] == "coproc.precompile"]
        assert {s["n_pad"] for s in spans} >= {128, 256}
        assert all(s["seconds"] >= 0 and s["thread"] == "rptpu-precompile" for s in spans)
    finally:
        tracer.configure(enabled=was)
        engine.shutdown()


def test_rpk_debug_coproc_shows_a_scripts_ready_buckets(capsys):
    from redpanda_tpu.cli import rpk

    engine = _engine(256)
    try:
        engine.enable_coprocessors([(7, _spec(MASK), ("bench",))], partitions={"bench": 1})
        _wait_ladder(engine, 7)
        _launch(engine, _documents(1, 300), script_id=7)
        stats = json.loads(json.dumps(engine.stats(), default=str))  # as the admin API ships it
    finally:
        engine.shutdown()
    assert stats["programs_ready"] == {
        "7": {"buckets": [128, 256], "top": 256, "state": "ready"}}

    async def status(_args, _method, path, **_kw):
        assert path == "/v1/coproc/status"
        return 200, {"enabled": True, "native": {"loaded": True}, "stats": stats}

    saved, rpk._admin_request = rpk._admin_request, status
    try:
        assert rpk.main(["debug", "coproc"]) == 0
    finally:
        rpk._admin_request = saved
    printed = capsys.readouterr().out
    (line,) = [ln for ln in printed.splitlines() if ln.startswith("programs:")]
    assert "script 7: 2 row buckets ready (128-256 rows)" in line
    assert "ladder to 256 rows, ready" in line and "1 launches cut to a ready bucket" in line
    for key in ("n_precompiles", "t_precompile", "n_launch_cuts"):
        assert any(ln.split()[:1] == [key] for ln in printed.splitlines()), key


def test_cut_launches_park_staging_buffers_of_their_bucket_size():
    """A cut launch stages k parts of a smaller bucket, fewer rows than its
    own bucket: it still takes (and parks) a buffer of the bucket's size,
    so the staging pool's few slots are not filled with sizes that no
    later launch can use (on the chip a cold ladder's cut launches left
    four such buffers parked and every later launch of a catch-up paid the
    first touch of a fresh 33.8 MB matrix: PERF.md section 6, PR 45)."""
    engine = _engine(128)
    try:
        engine.enable_coprocessors([(1, _spec(MASK), ("bench",))], partitions={"bench": 1})
        _wait_ladder(engine)
        for rows in (600, 700, 800, 900, 1000, 520):  # 5, 6, 7, 8, 8, 5 parts of 128
            values = _documents(rows, rows)
            assert _launch(engine, values)[0] == _twin(MASK, values)
        stats = engine.stats()
        assert stats["n_launch_cuts"] == 6
        assert stats["n_staged_rows"] == (5 + 6 + 7 + 8 + 8 + 5) * 128
        assert stats["staging_arena"]["allocs"] == 1 and stats["n_staging_reuses"] == 5
        assert stats["staging_arena"]["alloc_bytes"] == 1024 * (STRIDE + IN_META)
    finally:
        engine.shutdown()


def test_a_profile_holds_one_precompile_annotation_a_program_on_the_builders_line(tmp_path):
    """``rp:coproc.precompile`` on the ``rptpu-precompile`` thread's line of
    ``/host:CPU``, one a bucket, the bucket's rows as its argument."""
    import glob

    import jax
    from jax.profiler import ProfileData

    engine = _engine(256)
    jax.profiler.start_trace(str(tmp_path))
    try:
        engine.enable_coprocessors([(1, _spec(MATRIX), ("bench",))], partitions={"bench": 1})
        _wait_ladder(engine)
    finally:
        jax.profiler.stop_trace()
        engine.shutdown()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    found = [
        (line.name, dict(ev.stats))
        for plane in ProfileData.from_file(path).planes if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events if ev.name == "rp:coproc.precompile"
    ]
    assert sorted(int(stats["n_pad"]) for _line, stats in found) == [128, 256]
    assert len({line for line, _stats in found}) == 1  # one thread's line
