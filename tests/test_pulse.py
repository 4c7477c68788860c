"""pandapulse (ISSUE 14): flight recorder, wall profiler, Chrome timelines.

Covers the tentpole acceptance surface: the recorder ring is bounded; a
real launch's timeline slices sum per stage to the engine's ``stats()``
``t_*`` splits (inline, sharded AND mesh lanes); governor verdicts and
admission episodes inject as instant events on the span clock; a real
broker drive exports Chrome-trace JSON that validates against the
trace-event schema; the disabled profiler runs NO sampler thread (the
zero-hot-path pin — the <1% recorder bar lives in tools/microbench.py
--assert-pulse-overhead).
"""

from __future__ import annotations

import asyncio
import json
import threading
import time

import aiohttp
import pytest

from redpanda_tpu.coproc import ProcessBatchRequest, TpuEngine
from redpanda_tpu.coproc import governor as gov_mod
from redpanda_tpu.coproc.engine import ProcessBatchItem
from redpanda_tpu.models import NTP
from redpanda_tpu.models.record import Record, RecordBatch
from redpanda_tpu.observability.pulse import (
    FlightRecorder,
    WallProfiler,
    pulse,
    thread_affinity,
)
from redpanda_tpu.observability.trace import tracer
from redpanda_tpu.ops.exprs import field
from redpanda_tpu.ops.transforms import Int, Str, map_project, where


def run(coro):
    return asyncio.new_event_loop().run_until_complete(coro)


@pytest.fixture(autouse=True)
def _pulse_state():
    """Arm tracer + pulse for the test, restore the disabled defaults
    after (the process-wide singletons must not leak into other suites —
    tests/test_rpc.py pins the disabled default posture)."""
    TpuEngine.reset_columnar_probe()
    tracer.configure(enabled=True, slow_threshold_ms=10_000)
    pulse.configure(enabled=True)
    pulse.reset()
    yield
    pulse.configure(enabled=False, profile_hz=0)
    pulse.reset()
    tracer.configure(enabled=False)
    tracer.reset()


PROJ_SPEC = where(field("level") == "error") | map_project(
    Int("code"), Str("msg", 64)
)


def _request(n_items=8, records=256, topic="pulse") -> ProcessBatchRequest:
    items = []
    for p in range(n_items):
        recs = [
            Record(
                offset_delta=i,
                value=json.dumps({
                    "level": ["error", "info", "warn"][(p + i) % 3],
                    "code": p * 1000 + i,
                    "msg": "x" * (40 + (i % 50)),
                }).encode(),
            )
            for i in range(records)
        ]
        items.append(
            ProcessBatchItem(
                1, NTP.kafka(topic, p),
                [RecordBatch.build(recs, base_offset=0)],
            )
        )
    return ProcessBatchRequest(items, trace_id=tracer.new_trace_id())


def _launch(**engine_kw):
    engine_kw.setdefault("row_stride", 256)
    engine_kw.setdefault("force_mode", "columnar_host")
    engine_kw.setdefault("host_workers", 0)
    eng = TpuEngine(**engine_kw)
    try:
        assert eng.enable_coprocessors(
            [(1, PROJ_SPEC.to_json(), ("pulse",))]
        ) == [0]
        eng.process_batch(_request())
        return eng.stats()
    finally:
        eng.shutdown()


def _assert_stage_parity(stats: dict, prefix: str = "coproc.stage.") -> int:
    """Every stage slice family in the recorder must sum to the engine's
    matching ``t_*`` stat within per-slice integer-microsecond truncation
    (tracer slices store int(dur_us))."""
    totals = pulse.recorder.stage_totals()
    counts: dict[str, int] = {}
    for s in pulse.recorder.spans():
        counts[s["name"]] = counts.get(s["name"], 0) + 1
    checked = 0
    for name, total_s in totals.items():
        if not name.startswith(prefix):
            continue
        key = "t_" + name[len(prefix):]
        assert key in stats, f"{name} has no stats twin {key}"
        tol = (counts[name] + 1) * 2e-6  # 1us truncation + float rounding
        assert abs(total_s - stats[key]) <= tol, (
            name, total_s, stats[key], counts[name]
        )
        checked += 1
    return checked


# ---------------------------------------------------------------- recorder
def test_recorder_ring_is_bounded():
    rec = FlightRecorder(capacity=32)
    for i in range(100):
        rec.record({"trace_id": i, "name": "coproc.tick", "start_us": i,
                    "dur_us": 1, "thread": "t"})
    assert len(rec.spans()) == 32
    assert rec.spans_recorded == 100
    assert rec.spans()[0]["trace_id"] == 68  # oldest fell off
    rec.configure(capacity=16)
    assert len(rec.spans()) == 16
    assert rec.capacity == 16


def test_disabled_pulse_uninstalls_the_sink():
    pulse.configure(enabled=False)
    assert tracer._sink is None
    before = pulse.recorder.spans_recorded
    with tracer.span("coproc.tick", root=True):
        pass
    assert pulse.recorder.spans_recorded == before
    pulse.configure(enabled=True)
    assert tracer._sink is not None
    with tracer.span("coproc.tick", root=True):
        pass
    assert pulse.recorder.spans_recorded == before + 1


def test_launch_groups_and_queue_wait_slices():
    rec = FlightRecorder()
    # a non-launch trace (plain produce) must not appear as a launch
    rec.record({"trace_id": 1, "name": "kafka.produce", "start_us": 0,
                "dur_us": 10, "thread": "t"})
    rec.record({"trace_id": 2, "name": "coproc.tick", "start_us": 100,
                "dur_us": 500, "thread": "t"})
    rec.record({"trace_id": 2, "name": "coproc.device_harvest",
                "start_us": 400, "dur_us": 50, "thread": "h",
                "queue_us": 120, "device_us": 50})
    launches = rec.launches()
    assert len(launches) == 1
    g = launches[0]
    assert g["trace_id"] == 2
    waits = [s for s in g["slices"] if s.get("derived")]
    assert len(waits) == 1
    w = waits[0]
    assert w["name"] == "coproc.device_harvest.queue_wait"
    assert w["start_us"] == 400 - 120 and w["dur_us"] == 120
    # stage totals skip derived slices (they would double-count wall time)
    assert "coproc.device_harvest.queue_wait" not in rec.stage_totals()


# ---------------------------------------------------------------- parity
def test_stage_slice_parity_inline():
    stats = _launch()
    assert len(pulse.recorder.launches()) == 1
    checked = _assert_stage_parity(stats)
    # the inline columnar ladder must have produced real stage slices
    assert checked >= 4, pulse.recorder.stage_totals()


@pytest.mark.parametrize(
    "host_workers", [0, 2], ids=["ladders_inline", "ladders_on_pool"]
)
def test_stage_slice_parity_mesh(host_workers, eight_devices):
    """The per-shard slices (``shard_*``) close on whatever thread ran the
    shard — the dispatching one, or a pool worker with no ambient trace,
    where the launch's trace id rides explicitly — and still sum to the
    engine's ``t_shard_*`` stats."""
    stats = _launch(
        force_mode=None, mesh_devices=4, mesh_backend="cpu",
        mesh_probe=False, host_workers=host_workers,
    )
    assert stats.get("n_mesh_launches", 0) >= 1
    totals = pulse.recorder.stage_totals()
    assert "coproc.stage.mesh_ladder" in totals, totals
    assert any(k.startswith("coproc.stage.shard_") for k in totals), totals
    assert "coproc.stage.sharded_frame" in totals, totals
    _assert_stage_parity(stats)
    # the per-device mesh shard spans carry their shard index
    mesh_spans = [
        s for s in pulse.recorder.spans() if s["name"] == "coproc.mesh_shard"
    ]
    assert len(mesh_spans) >= 2
    assert {s.get("shard") for s in mesh_spans} >= {0, 1}


def test_device_path_queue_wait_is_explicit():
    _launch(force_mode="columnar_device")
    launches = pulse.recorder.launches()
    assert launches
    names = [s["name"] for g in launches for s in g["slices"]]
    assert "coproc.device_harvest" in names
    assert "coproc.device_harvest.queue_wait" in names


# ---------------------------------------------------------------- timeline
def test_timeline_injects_governor_and_admission_instants():
    stats = _launch()
    # a breaker-style governor verdict + an admission shed episode, both
    # stamped NOW so they land inside the launch window
    gov_mod.journal_record(
        gov_mod.BREAKER, "closed -> open",
        "test transition", {"domain": "device_dispatch"},
    )
    gov_mod.journal_record(
        gov_mod.ADMISSION, "shed",
        "coproc admission refused 1 bytes", {"retry_ms": 5},
    )
    tl = pulse.timeline()
    assert tl["launches"] >= 1
    instants = [e for e in tl["traceEvents"] if e["ph"] == "i"]
    names = {e["name"] for e in instants}
    assert "breaker:closed -> open" in names, names
    assert "admission:shed" in names, names
    # same clock: each instant sits inside/near the launch window
    xs = [e for e in tl["traceEvents"] if e["ph"] == "X"]
    lo = min(e["ts"] for e in xs)
    hi = max(e["ts"] + e["dur"] for e in xs)
    for e in instants:
        assert lo - 2.1e6 <= e["ts"] <= hi + 2.1e6
    # the stats twin is present so the two views describe one launch
    assert stats["n_launches"] == 1


def _validate_chrome_trace(doc: dict) -> None:
    """Chrome trace-event schema: what Perfetto's JSON importer requires.
    https://docs.google.com/document/d/1CvAClvFfyA5R-PhYUmn5OOQtYMH4h6I0nSsKchNAySU
    """
    assert isinstance(doc["traceEvents"], list) and doc["traceEvents"]
    assert doc.get("displayTimeUnit") in ("ms", "ns")
    for ev in doc["traceEvents"]:
        assert isinstance(ev.get("name"), str) and ev["name"]
        assert ev.get("ph") in ("X", "i", "I", "M"), ev
        assert isinstance(ev.get("pid"), int)
        assert isinstance(ev.get("tid"), int)
        if ev["ph"] == "X":
            assert isinstance(ev["ts"], (int, float))
            assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 1
        elif ev["ph"] in ("i", "I"):
            assert isinstance(ev["ts"], (int, float))
            assert ev.get("s") in ("g", "p", "t", None)
        else:
            assert ev["name"] in ("process_name", "thread_name")
            assert "name" in (ev.get("args") or {})
    # round-trips as JSON (the --perfetto artifact is json.dump'd)
    json.loads(json.dumps(doc))


def test_timeline_chrome_schema_unit():
    _launch()
    _validate_chrome_trace(pulse.timeline())


def test_timeline_launch_limit():
    for _ in range(3):
        _launch()
    assert len(pulse.recorder.launches()) == 3
    tl = pulse.timeline(launches=1)
    assert tl["launches"] == 1
    tids = {
        e["args"].get("trace_id")
        for e in tl["traceEvents"]
        if e["ph"] == "X"
    }
    assert len(tids) == 1


# ---------------------------------------------------------------- profiler
def test_profiler_folds_stacks_with_affinity_tags():
    prof = WallProfiler()
    stop = threading.Event()

    def busy():
        while not stop.is_set():
            sum(i * i for i in range(2000))

    t = threading.Thread(target=busy, name="rptpu-host-stage_0_test")
    t.start()
    try:
        prof.configure(200.0)
        # wait for BOTH enough samples and the busy thread to show up: on
        # a crushed shared box the freshly-started thread can sit
        # unscheduled (no Python frame yet -> absent from
        # sys._current_frames) for the first tens of milliseconds
        deadline = time.time() + 15.0
        while time.time() < deadline:
            if prof.samples >= 10 and any(
                s["thread"].startswith("rptpu-host-stage")
                for s in prof.stacks()
            ):
                break
            time.sleep(0.01)
    finally:
        prof.stop()
        stop.set()
        t.join()
    assert prof.samples >= 10
    stacks = prof.stacks()
    assert stacks
    threads = {s["thread"] for s in stacks}
    assert any(n.startswith("rptpu-host-stage") for n in threads), threads
    pooled = next(
        s for s in stacks if s["thread"].startswith("rptpu-host-stage")
    )
    assert pooled["affinity"] == "pool_worker"
    assert any(":busy" in fr for fr in pooled["stack"]), pooled["stack"]
    # folded lines are flamegraph.pl-shaped: "thread;f0;...;leaf N"
    line = prof.folded()[0]
    head, count = line.rsplit(" ", 1)
    assert int(count) >= 1 and ";" in head
    top = prof.top(5)
    assert top and top[0]["samples"] >= top[-1]["samples"]


def test_profiler_off_means_no_sampler_thread():
    """The zero-hot-path pin: profile_hz=0 runs NO thread (and the engine
    never calls into pulse — the recorder rides the tracer sink only)."""
    assert not any(
        t.name == "rptpu-pulse-profiler" for t in threading.enumerate()
    )
    prof = pulse.profiler
    assert not prof.running and prof.hz == 0.0
    pulse.configure(profile_hz=50.0)
    assert any(
        t.name == "rptpu-pulse-profiler" for t in threading.enumerate()
    )
    pulse.configure(profile_hz=0)
    deadline = time.time() + 3.0
    while time.time() < deadline and any(
        t.name == "rptpu-pulse-profiler" for t in threading.enumerate()
    ):
        time.sleep(0.01)
    assert not any(
        t.name == "rptpu-pulse-profiler" for t in threading.enumerate()
    )


def test_thread_affinity_vocabulary():
    assert thread_affinity("MainThread") == "loop"
    assert thread_affinity("rptpu-coproc-tick_3") == "executor"
    assert thread_affinity("rptpu-mask-harvester") == "daemon"
    assert thread_affinity("rptpu-host-stage_1") == "pool_worker"
    assert thread_affinity("something-else") == "thread"


# ---------------------------------------------------------------- broker e2e
def test_broker_drive_exports_perfetto_timeline(tmp_path):
    """Acceptance: a live broker drive (deploy → produce → materialize)
    exports a Perfetto-loadable timeline via GET /v1/profile/timeline
    whose launch slices sum per stage to the engine's stats() t_* splits,
    and GET /v1/profile reports recorder + profiler state."""
    from redpanda_tpu.admin import AdminServer
    from redpanda_tpu.cluster.topic_table import TopicConfig
    from redpanda_tpu.coproc.api import CoprocApi
    from redpanda_tpu.kafka.client import KafkaClient
    from redpanda_tpu.kafka.server.broker import Broker, BrokerConfig
    from redpanda_tpu.kafka.server.protocol import KafkaServer
    from redpanda_tpu.storage.log_manager import StorageApi

    async def wait_until(pred, timeout=15.0, msg=""):
        deadline = asyncio.get_event_loop().time() + timeout
        while not pred():
            if asyncio.get_event_loop().time() > deadline:
                raise AssertionError(f"timeout: {msg}")
            await asyncio.sleep(0.03)

    async def main():
        storage = await StorageApi(str(tmp_path)).start()
        cfg = BrokerConfig(data_dir=str(tmp_path))
        broker = Broker(cfg, storage)
        server = await KafkaServer(broker, "127.0.0.1", 0).start()
        cfg.advertised_port = server.port
        api = await CoprocApi(broker).start()
        api.poll_interval_s = 0.02
        broker.coproc_api = api
        admin = await AdminServer(broker, port=0).start()
        client = await KafkaClient([("127.0.0.1", server.port)]).connect()
        try:
            await broker.create_topic(TopicConfig("pulse_e2e", 1))
            await api.deploy("errs", PROJ_SPEC.to_json(), ["pulse_e2e"])
            await wait_until(
                lambda: "errs" in api.active_scripts(), msg="deployed"
            )
            values = [
                json.dumps({
                    "level": ["error", "info"][i % 2],
                    "code": i, "msg": "v" * 32,
                }).encode()
                for i in range(64)
            ]
            await client.produce("pulse_e2e", 0, values)
            mat = "pulse_e2e.$errs$"
            await wait_until(
                lambda: (
                    (p := broker.get_partition(mat, 0)) is not None
                    and p.high_watermark >= 1
                ),
                msg="materialized",
            )
            # a journaled admission episode on the same clock
            gov_mod.journal_record(
                gov_mod.ADMISSION, "shed", "drive test episode", {}
            )
            async with aiohttp.ClientSession() as s:
                async with s.get(
                    f"http://127.0.0.1:{admin.port}/v1/profile"
                ) as resp:
                    assert resp.status == 200
                    prof_doc = await resp.json()
                async with s.get(
                    f"http://127.0.0.1:{admin.port}/v1/profile/timeline"
                ) as resp:
                    assert resp.status == 200
                    tl = await resp.json()
            return prof_doc, tl, api.engine.stats()
        finally:
            await client.close()
            await admin.stop()
            await api.stop()
            await server.stop()
            await storage.stop()

    prof_doc, tl, stats = run(main())
    assert prof_doc["enabled"] and prof_doc["tracing"]
    assert prof_doc["recorder"]["launches"] >= 1
    assert prof_doc["profiler"]["running"] is False
    _validate_chrome_trace(tl)
    assert tl["launches"] >= 1
    names = {e["name"] for e in tl["traceEvents"]}
    assert "coproc.tick" in names
    assert any(n.startswith("coproc.stage.") for n in names), names
    assert "admission:shed" in names
    # slices sum to stats (the launch window is the whole drive here)
    _assert_stage_parity(stats)
