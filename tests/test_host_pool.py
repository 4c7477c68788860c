"""Host-stage pool tests.

The pool serves the mesh lane's per-device ladders (tests/test_meshrunner.py
holds that lane's parity matrix); what lives here is the machinery and what
it leans on:

- partition_counts invariants (contiguity, coverage, no empty shards);
- fused explode_and_find vs split explode_batches+build_find_cache span
  parity, with and without the native lib;
- the pool's exception and shutdown contracts;
- the single-device engine has one host road: no pool, no pool thread, and
  none of the surface that used to choose between two.

Plus the frame_ranges empty-ranges regression and the columnar-probe
reset hook.
"""

import json

import numpy as np
import pytest

from redpanda_tpu.coproc import (
    TpuEngine,
    ProcessBatchRequest,
    EnableResponseCode,
)
from redpanda_tpu.coproc import batch_codec, governor, host_pool
from redpanda_tpu.coproc.column_plan import plan_spec
from redpanda_tpu.coproc.engine import ProcessBatchItem
from redpanda_tpu.models import Compression, NTP, Record, RecordBatch
from redpanda_tpu.ops.exprs import field
from redpanda_tpu.ops.transforms import (
    Int,
    Str,
    filter_contains,
    identity,
    map_project,
    where,
)

def _columnar_spec():
    return where(field("level") == "error") | map_project(Int("code"), Str("msg", 16))


def _json_batch(n, base_offset=0, codec=Compression.none, empty_every=0):
    recs = []
    for i in range(n):
        if empty_every and i % empty_every == 0:
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


# ------------------------------------------------------------ partitioner
def test_partition_counts_invariants():
    rng = np.random.default_rng(7)
    for _ in range(200):
        n = int(rng.integers(0, 40))
        counts = [int(c) for c in rng.integers(0, 5000, size=n)]
        for shards in (1, 2, 3, 4, 8):
            parts = host_pool.partition_counts(counts, shards)
            if n == 0:
                assert parts == []
                continue
            # contiguous, in order, covering [0, n), no empty slices
            assert parts[0][0] == 0 and parts[-1][1] == n
            for (s0, e0), (s1, e1) in zip(parts, parts[1:]):
                assert e0 == s1
            assert all(e > s for s, e in parts)
            assert len(parts) <= min(shards, n)


def test_partition_counts_balances_records():
    # one fat batch should not drag its neighbours into the same shard
    counts = [10_000, 10, 10, 10_000]
    parts = host_pool.partition_counts(counts, 2)
    totals = [sum(counts[s:e]) for s, e in parts]
    assert len(parts) == 2
    assert max(totals) <= 2 * min(totals)


def test_pool_propagates_first_exception_in_order():
    pool = host_pool.HostStagePool(2)
    try:
        def boom_a():
            raise ValueError("a")

        def boom_b():
            raise KeyError("b")

        with pytest.raises(ValueError):
            pool.run([boom_a, boom_b, lambda: 3])
        assert pool.run([lambda: 1, lambda: 2]) == [1, 2]
    finally:
        pool.shutdown()


# ------------------------------------------------------ frame_ranges empty
def test_pool_shut_down_under_a_fan_out_serves_it_and_the_next():
    """shutdown() may land while a launch is fanning out: work the
    executor already holds still runs, and the next fan-out gets a new
    executor (never 'cannot schedule new futures')."""
    import threading

    pool = host_pool.HostStagePool(2)
    started, release = threading.Event(), threading.Event()

    def held():
        started.set()
        release.wait(5.0)
        return "held"

    got = []
    t = threading.Thread(target=lambda: got.append(pool.run([held, lambda: "b"])))
    t.start()
    assert started.wait(5.0)
    pool.shutdown()  # mid fan-out
    assert pool.run([lambda: 1, lambda: 2]) == [1, 2]  # a new executor
    release.set()
    t.join(5.0)
    assert got == [["held", "b"]]
    pool.shutdown()


def test_frame_ranges_empty_ranges_native_and_python(monkeypatch):
    rows = np.zeros((4, 8), np.uint8)
    lens = np.full(4, 8, np.int32)
    keep = np.ones(4, bool)
    # native path (when the lib is present) and the python fallback must
    # BOTH return [] — the native branch used to silently fall through to
    # the per-range list comprehension on empty ranges
    assert batch_codec.frame_ranges(rows, lens, keep, []) == []
    monkeypatch.setattr(batch_codec, "_native", lambda: None)
    assert batch_codec.frame_ranges(rows, lens, keep, []) == []


# ------------------------------------------------------ fused vs split
def _batch_scenarios():
    return {
        "plain": [_json_batch(8), _json_batch(6, base_offset=8)],
        "compressed": [
            _json_batch(8, codec=Compression.lz4),
            _json_batch(6, base_offset=8, codec=Compression.gzip),
        ],
        "empty_values": [_json_batch(9, empty_every=3), _json_batch(5)],
        "zero_record": [_json_batch(0), _json_batch(7), _json_batch(0)],
        "all_zero": [_json_batch(0), _json_batch(0)],
    }


@pytest.mark.parametrize("name", sorted(_batch_scenarios()))
def test_fused_vs_split_parity_native(name):
    lib = batch_codec._native()
    if lib is None or not getattr(lib, "has_explode_find", False):
        pytest.skip("native explode_find unavailable")
    batches = _batch_scenarios()[name]
    plan = plan_spec(_columnar_spec())
    paths = plan.flat_paths()

    fused = batch_codec.explode_and_find(batches, paths)
    assert fused is not None
    ex_f, types_f, vs_f, ve_f = fused

    ex_s = batch_codec.explode_batches(batches)
    np.testing.assert_array_equal(ex_f.offsets, ex_s.offsets)
    np.testing.assert_array_equal(ex_f.sizes, ex_s.sizes)
    assert ex_f.ranges == ex_s.ranges
    assert ex_f.joined == ex_s.joined

    cache = plan.build_find_cache(ex_s.joined, ex_s.offsets, ex_s.sizes)
    if len(ex_s.sizes):
        assert cache is not None
        np.testing.assert_array_equal(types_f, cache.types)
        np.testing.assert_array_equal(vs_f, cache.vs)
        np.testing.assert_array_equal(ve_f, cache.ve)


@pytest.mark.parametrize("name", sorted(_batch_scenarios()))
def test_explode_python_fallback_parity(name, monkeypatch):
    """explode_batches without the native lib must yield the exact same
    offset/size/range tables (same joined blob, same varint layout)."""
    batches = _batch_scenarios()[name]
    native = batch_codec.explode_batches(batches)
    monkeypatch.setattr(batch_codec, "_native", lambda: None)
    py = batch_codec.explode_batches(batches)
    np.testing.assert_array_equal(native.offsets, py.offsets)
    np.testing.assert_array_equal(native.sizes, py.sizes)
    assert native.ranges == py.ranges
    assert native.joined == py.joined


# ------------------------------------------------------ one host road
_RETIRED = (
    "coproc_host_pool_probe", "coproc_host_pool_recal_launches",
    "host_pool_probe", "host_pool_recal", "sharded_seal", "n_sharded_launches",
)


def _launch_request(parts=64, batches=2, records=32):
    return ProcessBatchRequest([
        ProcessBatchItem(
            1, NTP.kafka("orders", p),
            [_json_batch(records, base_offset=1000 * p + 100 * k) for k in range(batches)],
        )
        for p in range(parts)
    ])


def test_retired_surface_is_gone(tmp_path, capsys):
    """Nothing names the fork any more: not the config registry, not
    ``stats()``, not the governor's domains or posture, not what
    ``/v1/coproc/status`` and ``/v1/governor`` serve, not what ``rpk debug
    coproc`` / ``rpk debug governor`` print of them."""
    import asyncio

    from redpanda_tpu.admin import AdminServer
    from redpanda_tpu.cli import rpk
    from redpanda_tpu.config.properties import PROPERTIES
    from redpanda_tpu.kafka.server.broker import Broker, BrokerConfig
    from redpanda_tpu.storage.log_manager import StorageApi

    names = {p.name for p in PROPERTIES}
    assert not names & set(_RETIRED)
    assert "coproc_host_workers" in names
    assert not {"host_pool", "sharded_seal"} & set(governor.DOMAINS)
    assert not hasattr(governor, "HOST_POOL") and not hasattr(governor, "SHARDED_SEAL")
    for gone in ("LaunchTrial", "TrialSample", "TRIAL_LAUNCHES", "PROBE_MARGIN"):
        assert not hasattr(host_pool, gone)
    assert not hasattr(batch_codec, "merge_exploded")
    assert governor.PROBE_MARGIN == 1.25

    engine = TpuEngine(row_stride=256, compress_threshold=10**9)
    engine.enable_coprocessors([(1, _columnar_spec().to_json(), ("orders",))])
    engine.process_batch(_launch_request())  # 4,096 rows: every probe fires
    stats = engine.stats()
    assert not set(stats) & set(_RETIRED)
    posture = stats["governor"]["posture"]
    assert not {"host_pool", "sharded_seal"} & set(posture)

    class _FakeApi:
        @staticmethod
        def active_scripts():
            return ["demo"]

    _FakeApi.engine = engine

    async def main():
        storage = await StorageApi(str(tmp_path)).start()
        broker = Broker(BrokerConfig(data_dir=str(tmp_path)), storage)
        broker.coproc_api = _FakeApi()
        admin = await AdminServer(broker, port=0).start()
        try:
            for cmd in ("coproc", "governor"):
                argv = ["--admin-api", f"127.0.0.1:{admin.port}", "debug", cmd]
                assert await asyncio.to_thread(rpk.main, argv) == 0
                assert await asyncio.to_thread(rpk.main, argv + ["--json"]) == 0
        finally:
            await admin.stop()
            await storage.stop()

    try:
        asyncio.run(main())
    finally:
        engine.shutdown()
    printed = capsys.readouterr().out
    assert "harvest_path" in printed and "host_workers" in printed
    for gone in _RETIRED + ("host_pool",):
        assert gone not in printed, gone


@pytest.mark.parametrize(
    "spec",
    [_columnar_spec(), filter_contains(b"error"), identity()],
    ids=["columnar", "payload", "host"],
)
def test_single_device_engine_starts_no_pool_thread(spec):
    """Without a mesh runner the engine builds no pool, whatever
    ``host_workers`` says, and a 4,096-row launch of each lane runs its
    host stages on the dispatching thread: no ``rptpu-host-stage`` thread
    ever exists."""
    import threading

    def pool_threads():
        return [
            t.name for t in threading.enumerate()
            if t.name.startswith("rptpu-host-stage")
        ]

    assert not pool_threads()
    engine = TpuEngine(row_stride=256, compress_threshold=10**9, host_workers=4)
    try:
        assert engine._host_pool is None
        assert engine.enable_coprocessors(
            [(1, spec.to_json(), ("orders",))]
        ) == [EnableResponseCode.success]
        reply = engine.process_batch(_launch_request())
        stats = engine.stats()
        assert stats["n_records"] == 4096 and stats["host_workers"] == 4.0
        assert sum(len(it.batches) for it in reply.items) == 128
        assert "t_seal" in stats
        assert not [k for k in stats if k.startswith("t_shard")]
        assert not pool_threads()
    finally:
        engine.shutdown()


def test_measure_parallel_capacity_shape():
    got = host_pool.measure_parallel_capacity(2)
    assert set(got) == {"speedup", "workers"}
    assert got["workers"] == 2 and got["speedup"] > 0


# ------------------------------------------------------ probe reset hook
def test_reset_columnar_probe():
    saved = (TpuEngine._columnar_backend, TpuEngine._columnar_probe)
    try:
        TpuEngine._columnar_backend = "host"
        TpuEngine._columnar_probe = {"chosen": "host"}
        engine = TpuEngine(host_workers=0)
        stats = engine.stats()
        assert stats["columnar_backend"] == "host"
        assert stats["columnar_probe"] == {"chosen": "host"}
        TpuEngine.reset_columnar_probe()
        assert TpuEngine._columnar_backend is None
        assert TpuEngine._columnar_probe is None
        assert "columnar_backend" not in engine.stats()
        engine.shutdown()
    finally:
        TpuEngine._columnar_backend, TpuEngine._columnar_probe = saved
