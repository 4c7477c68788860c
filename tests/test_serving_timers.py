"""The serving loop times itself (always on, tracing off or on): pacemaker
phases, the queue waits between layers, the event loop's lag and stalls,
and the benchmark's per-layer metric files that read them."""

from __future__ import annotations

import asyncio
import gc
import json
import os
import sys
import time

import pytest

from redpanda_tpu.cluster.partition import APPEND_STAMPS
from redpanda_tpu.cluster.topic_table import TopicConfig
from redpanda_tpu.coproc.api import CoprocApi
from redpanda_tpu.kafka.client.client import KafkaClient
from redpanda_tpu.kafka.server.broker import Broker, BrokerConfig
from redpanda_tpu.kafka.server.protocol import KafkaServer
from redpanda_tpu.models.fundamental import NTP
from redpanda_tpu.models.record import Record, RecordBatch
from redpanda_tpu.observability import probes
from redpanda_tpu.observability.loopwatch import (
    LoopWatch,
    gc_pause_hists,
    loop_lag_hist,
    loopwatch,
)
from redpanda_tpu.observability.trace import tracer
from redpanda_tpu.ops.exprs import field
from redpanda_tpu.ops.transforms import Int, Str, map_project, where
from redpanda_tpu.storage.log_manager import StorageApi

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))
import readers  # noqa: E402  (the benchmark's own reader, as run.py imports it)

PHASES = ("tick", "read", "gate", "engine", "write")
HANDOFFS = probes.COPROC_HANDOFF_PHASES
LEGS = probes.COPROC_ENGINE_PHASES


def run(coro, limit_s=60.0):
    asyncio.run(asyncio.wait_for(coro, limit_s))


async def wait_until(pred, timeout=15.0, msg=""):
    deadline = time.monotonic() + timeout
    while not pred():
        assert time.monotonic() < deadline, f"timeout: {msg}"
        await asyncio.sleep(0.02)


async def _start(tmp_path):
    storage = await StorageApi(str(tmp_path)).start()
    cfg = BrokerConfig(data_dir=str(tmp_path))
    broker = Broker(cfg, storage)
    server = await KafkaServer(broker, "127.0.0.1", 0).start()
    cfg.advertised_port = server.port
    api = await CoprocApi(broker).start()
    api.poll_interval_s = 0.02
    broker.coproc_api = api
    return storage, broker, server, api


async def _stop(storage, server, api):
    await api.stop()
    await server.stop()
    await storage.stop()


def _docs(n, base=0):
    return [
        json.dumps(
            {"level": "error" if i % 2 == 0 else "info", "code": base + i, "msg": f"m{i}"},
            separators=(",", ":"),
        ).encode()
        for i in range(n)
    ]


async def _append(broker, topic, partition, values):
    p = broker.get_partition(topic, partition)
    batch = RecordBatch.build(
        [Record(value=v, offset_delta=i) for i, v in enumerate(values)]
    )
    await p.replicate([batch], 0)


def _spec():
    return (where(field("level") == "error") | map_project(Int("code"), Str("msg", 16))).to_json()


def _counts(hists):
    return {k: (h.hist.count, h.hist.sum) for k, h in hists.items()}


async def _deployed_but_parked(api, broker, name="proj"):
    """Deploy the script, then park its fiber: the test drives ``tick``."""
    await api.deploy(name, _spec(), ["src"])
    await wait_until(lambda: name in api.active_scripts(), msg="deployed")
    ctx = api.pacemaker.scripts()[name]
    await ctx.stop()
    return ctx


# ------------------------------------------------------------------ pacemaker
@pytest.mark.parametrize("tracing", [False, True])
def test_a_productive_tick_records_every_phase_once(tmp_path, tracing):
    async def main():
        storage, broker, server, api = await _start(tmp_path)
        tracer.reset()
        tracer.configure(enabled=tracing)
        try:
            await broker.create_topic(TopicConfig("src", 2))
            ctx = await _deployed_but_parked(api, broker)
            before = _counts(probes.coproc_tick_hist)
            assert await ctx.tick() is False  # nothing to read: no sample
            assert _counts(probes.coproc_tick_hist) == before
            assert tracer.spans_recorded == 0

            for part in (0, 1):
                await _append(broker, "src", part, _docs(1024))
            assert await ctx.tick() is True
            first = _counts(probes.coproc_tick_hist)
            for ph in PHASES:
                assert first[ph][0] == before[ph][0] + 1, ph
            assert first["gap"][0] == before["gap"][0]  # no tick before it

            def took(ph):
                return first[ph][1] - before[ph][1]

            parts = sum(took(ph) for ph in PHASES[1:])
            assert parts <= took("tick") + len(PHASES)  # each sample truncates to a us
            # between the phases: bookkeeping and one turn of the loop (the
            # gate's release), which no phase owns; an absolute allowance
            assert took("tick") - parts < 50_000, (parts, took("tick"))
            # the engine phase is the sum of its legs, which share their
            # clock reads: the request's construction, the two executor
            # calls clocked on both threads, the wait for the read-ahead
            # between them (nothing to wait for here: exactly 0)
            for ph in LEGS:
                assert first[ph][0] == before[ph][0] + 1, ph
            assert took("read_ahead_wait") == 0
            rest = took("engine") - sum(took(ph) for ph in LEGS)
            assert -1 <= rest <= len(LEGS), (rest, took("engine"))

            await _append(broker, "src", 0, _docs(64, base=1000))
            await asyncio.sleep(0.03)
            assert await ctx.tick() is True
            second = _counts(probes.coproc_tick_hist)
            for ph in PHASES + LEGS:
                assert second[ph][0] == first[ph][0] + 1, ph
            assert second["gap"][0] == first["gap"][0] + 1
            assert second["gap"][1] - first["gap"][1] >= 30_000  # the sleep above

            if not tracing:
                assert tracer.spans_recorded == 0
                return
            ticks = [t for t in tracer.recent(0)
                     if any(s["name"] == "coproc.tick" for s in t["spans"])]
            assert len(ticks) == 2
            for t in ticks:
                (tick,) = [s for s in t["spans"] if s["name"] == "coproc.tick"]
                kids = [s for s in t["spans"] if s.get("parent") == tick["span_id"]]
                assert {"coproc.read", "coproc.gate", "coproc.engine",
                        "coproc.write"} == {s["name"] for s in kids}
                assert sum(s["dur_us"] for s in kids) <= tick["dur_us"]
                assert all(tick["start_us"] <= s["start_us"] for s in kids)
                (engine,) = [s for s in kids if s["name"] == "coproc.engine"]
                waits = [s for s in t["spans"] if s.get("parent") == engine["span_id"]]
                assert {"coproc.submit.wait", "coproc.harvest.wait"} == {
                    s["name"] for s in waits}
                for w in waits:  # the legs ride the loop-side span
                    assert 0 <= w["out_us"] and 0 <= w["back_us"]
                    assert w["out_us"] + w["back_us"] <= engine["dur_us"]
                # the worker-side intervals keep their ring names
                assert {"coproc.dispatch", "coproc.harvest"} <= {
                    s["name"] for s in t["spans"]}
        finally:
            tracer.configure(enabled=False)
            tracer.reset()
            await _stop(storage, server, api)

    run(main())


def test_a_shed_tick_records_no_handoff(tmp_path):
    from redpanda_tpu.resource_mgmt.admission import ShedError

    async def main():
        storage, broker, server, api = await _start(tmp_path)
        try:
            await broker.create_topic(TopicConfig("src", 1))
            ctx = await _deployed_but_parked(api, broker)
            await _append(broker, "src", 0, _docs(64))
            engine = api.pacemaker.engine
            real = engine.submit

            def shed(req):
                raise ShedError("coproc", 1, "test")

            engine.submit = shed
            before = _counts(probes.coproc_tick_hist)
            try:
                assert await ctx.tick() is False
            finally:
                engine.submit = real
            after = _counts(probes.coproc_tick_hist)
            assert after["engine"][0] == before["engine"][0] + 1
            for ph in LEGS:
                assert after[ph] == before[ph], ph
            assert await ctx.tick() is True  # the same records, next tick
        finally:
            await _stop(storage, server, api)

    run(main())


# ------------------------------------------------------------------ queue waits
def test_append_stamps_are_a_bounded_ring(tmp_path):
    async def main():
        storage, broker, server, api = await _start(tmp_path)
        try:
            await broker.create_topic(TopicConfig("src", 1))
            p = broker.get_partition("src", 0)
            t_before = time.perf_counter()
            for i in range(APPEND_STAMPS + 6):
                await _append(broker, "src", 0, _docs(2, base=i))
            t_after = time.perf_counter()
            last = lambda i: 2 * i + 1  # noqa: E731  (batch i ends at this offset)
            assert p.append_stamp(last(0)) is None  # left the ring
            assert p.append_stamp(last(5)) is None
            oldest, newest = p.append_stamp(last(6)), p.append_stamp(last(APPEND_STAMPS + 5))
            assert t_before <= oldest <= newest <= t_after
            assert p.append_stamp(last(APPEND_STAMPS + 5) - 1) is None  # not a batch's end
            assert p.append_stamp(last(APPEND_STAMPS + 6)) is None  # not appended yet
            assert len(p._append_stamps) == APPEND_STAMPS
        finally:
            await _stop(storage, server, api)

    run(main())


def test_input_wait_is_skipped_for_a_backlog_older_than_the_ring(tmp_path):
    async def main():
        storage, broker, server, api = await _start(tmp_path)
        try:
            await broker.create_topic(TopicConfig("src", 1))
            ctx = await _deployed_but_parked(api, broker)
            for i in range(APPEND_STAMPS + 8):
                await _append(broker, "src", 0, _docs(2, base=i))
            before = probes.coproc_input_wait_hist.hist.count
            assert await ctx.tick() is True  # reads from offset 0: no stamp
            assert probes.coproc_input_wait_hist.hist.count == before
            await _append(broker, "src", 0, _docs(2, base=9000))
            await asyncio.sleep(0.02)
            assert await ctx.tick() is True
            h = probes.coproc_input_wait_hist.hist
            assert h.count == before + 1 and h.max >= 20_000
        finally:
            await _stop(storage, server, api)

    run(main())


def test_produce_transform_long_poll_round_leaves_wait_samples(tmp_path):
    async def main():
        storage, broker, server, api = await _start(tmp_path)
        client = await KafkaClient([("127.0.0.1", server.port)]).connect()
        try:
            await broker.create_topic(TopicConfig("src", 1))
            await api.deploy("proj", _spec(), ["src"])
            await wait_until(lambda: "proj" in api.active_scripts(), msg="deployed")
            await client.produce("src", 0, _docs(8))
            mntp = NTP.kafka("src.$proj$", 0)

            def hwm():
                p = broker.partition_manager.get(mntp)
                return p.high_watermark if p else 0

            await wait_until(lambda: hwm() >= 4, msg="first round materialized")
            await client.refresh_metadata()
            before = {
                "input": probes.coproc_input_wait_hist.hist.count,
                "wake": probes.kafka_fetch_wake_hist.hist.count,
                "wake_sum": probes.kafka_fetch_wake_hist.hist.sum,
                "serve": probes.kafka_fetch_serve_hist.hist.count,
                "fetch": probes.kafka_fetch_hist.hist.count,
            }
            # a fetch that finds data at once never enters the gate: no wake
            got, _ = await client.fetch("src.$proj$", 0, 0, max_wait_ms=2000)
            assert got and probes.kafka_fetch_wake_hist.hist.count == before["wake"]
            start = hwm()
            poll = asyncio.create_task(
                client.fetch("src.$proj$", 0, start, max_wait_ms=5000, min_bytes=1)
            )
            await asyncio.sleep(0.1)  # the poll is parked in the gate
            await client.produce("src", 0, _docs(8, base=100))
            got, _ = await poll
            assert sum(len(b.records()) for b in got) == 4
            assert probes.coproc_input_wait_hist.hist.count > before["input"]
            wake, serve = probes.kafka_fetch_wake_hist.hist, probes.kafka_fetch_serve_hist.hist
            assert wake.count == before["wake"] + 1
            # append -> the response: the append's notify wakes the parked
            # fetch, so a few loop turns and the pass that serves it, well
            # under the 20 ms re-check the gate used to sleep
            assert wake.sum - before["wake_sum"] < 10_000  # this poll's one sample
            assert serve.count == before["serve"] + 2  # one sample a request
            # the long poll's handler time holds its wait; its serve time does not
            assert serve.max < 90_000 <= probes.kafka_fetch_hist.hist.max
        finally:
            await client.close()
            await _stop(storage, server, api)

    run(main())


def test_an_acks_all_produce_is_one_flush_sample_and_a_materialized_append_none(tmp_path):
    async def main():
        storage, broker, server, api = await _start(tmp_path)
        client = await KafkaClient([("127.0.0.1", server.port)]).connect()
        try:
            await broker.create_topic(TopicConfig("src", 1))
            ctx = await _deployed_but_parked(api, broker)
            flush = probes.storage_flush_hist.hist
            before = flush.count
            await client.produce("src", 0, _docs(8), acks=1)
            assert flush.count == before  # the leader's append alone: no sync
            await client.produce("src", 0, _docs(8, base=100))  # acks=all
            assert flush.count == before + 1
            appends = probes.storage_append_hist.hist.count
            assert await ctx.tick() is True  # writes the materialized log, no_ack
            assert probes.storage_append_hist.hist.count > appends
            assert flush.count == before + 1
        finally:
            await client.close()
            await _stop(storage, server, api)

    run(main())


def test_a_profile_of_the_served_path_holds_the_waits_and_no_parked_poll(tmp_path):
    """Sink (b) of the three waits: the flush, the worker-side intervals of
    the tick's executor calls beside the loop-side waits. And a long poll is
    not what the host was doing: ``kafka.fetch`` keeps off the profile, its
    serve passes stay."""
    import glob

    import jax
    from jax.profiler import ProfileData

    async def main():
        storage, broker, server, api = await _start(tmp_path / "data")
        client = await KafkaClient([("127.0.0.1", server.port)]).connect()
        try:
            await broker.create_topic(TopicConfig("src", 1))
            ctx = await _deployed_but_parked(api, broker)
            fetches = probes.kafka_fetch_hist.hist.count
            jax.profiler.start_trace(str(tmp_path / "profile"))
            try:
                await client.produce("src", 0, _docs(8))  # acks=all: one flush
                assert await ctx.tick() is True
                got, _ = await client.fetch("src", 0, 0, max_wait_ms=100)
                assert got
            finally:
                jax.profiler.stop_trace()
            assert probes.kafka_fetch_hist.hist.count == fetches + 1  # sink (a) stays
        finally:
            await client.close()
            await _stop(storage, server, api)

    run(main(), 120.0)
    (path,) = glob.glob(str(tmp_path / "profile" / "**" / "*.xplane.pb"), recursive=True)
    names = {
        ev.name
        for plane in ProfileData.from_file(path).planes if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events
    }
    assert {"rp:storage.flush", "rp:kafka.produce", "rp:kafka.fetch.serve",
            "rp:coproc.submit.wait", "rp:coproc.harvest.wait",
            "rp:coproc.dispatch", "rp:coproc.harvest"} <= names
    assert "rp:kafka.fetch" not in names


# ------------------------------------------------------------------ the loop
def _block_the_loop_for_200_ms():
    time.sleep(0.2)


def test_a_blocking_call_is_one_stall_with_a_stack_that_names_it():
    async def main():
        watch = LoopWatch()
        lag_before = loop_lag_hist.hist.count
        watch.start()
        watch.start()  # a second broker in the process: the same ticker
        try:
            await asyncio.sleep(0.25)
            assert watch.stalls() == []
            assert loop_lag_hist.hist.count >= lag_before + 3
            wall = time.time()
            _block_the_loop_for_200_ms()
            await asyncio.sleep(0.1)
            (stall,) = watch.stalls()
            assert 150_000 <= stall["dur_us"] <= 400_000
            assert abs(stall["start"] - wall) < 0.1
            assert any("_block_the_loop_for_200_ms" in f for f in stall["stack"]), stall
            assert stall["phase"] is None  # no frame of the package held it
            assert loop_lag_hist.hist.max >= 150_000
            await watch.stop()
            assert watch._task is not None  # one user left
        finally:
            await watch.stop()
        assert watch._task is None and watch._watchdog is None
        assert watch._on_gc not in gc.callbacks

    run(main(), 20.0)


def test_collections_are_pause_samples_by_generation():
    async def main():
        watch = LoopWatch()
        watch.start()
        try:
            before = [h.hist.count for h in gc_pause_hists]
            gc.collect()
            gc.collect(0)
            after = [h.hist.count for h in gc_pause_hists]
            assert after[2] == before[2] + 1 and after[0] >= before[0] + 1
        finally:
            await watch.stop()
        n = gc_pause_hists[2].hist.count
        gc.collect()
        assert gc_pause_hists[2].hist.count == n  # callbacks gone with the last user

    run(main(), 20.0)


def test_profile_endpoint_serves_the_stall_ring(tmp_path):
    from redpanda_tpu.admin.server import AdminServer

    async def main():
        storage = await StorageApi(str(tmp_path)).start()
        broker = Broker(BrokerConfig(data_dir=str(tmp_path)), storage)
        admin = await AdminServer(broker, host="127.0.0.1", port=0).start()
        loopwatch.start()
        try:
            await asyncio.sleep(0.05)
            _block_the_loop_for_200_ms()
            await asyncio.sleep(0.1)
            import aiohttp

            async with aiohttp.ClientSession() as s:
                async with s.get(f"http://127.0.0.1:{admin.port}/v1/profile") as r:
                    body = await r.json()
            assert body["loop_stalls"] and body["loop_stalls"][0]["dur_us"] >= 150_000
            assert "self_totals_s" in body
        finally:
            await loopwatch.stop()
            await admin.stop()
            await storage.stop()

    run(main(), 30.0)


# ------------------------------------------------------------------ metric files
NEW_METRICS = {
    "tick_ms": ('coproc_tick_latency_us', 'phase="tick"', "catchup"),
    "tick_read_ms": ('coproc_tick_latency_us', 'phase="read"', "catchup"),
    "tick_engine_ms": ('coproc_tick_latency_us', 'phase="engine"', "catchup"),
    "tick_write_ms": ('coproc_tick_latency_us', 'phase="write"', "catchup"),
    "fetch_serve_ms": ("kafka_fetch_serve_latency_us", "", "catchup"),
    "loop_lag_ms": ("broker_loop_lag_us", "", "catchup"),
    "paced.tick_ms": ('coproc_tick_latency_us', 'phase="tick"', "paced"),
    "paced.tick_read_ms": ('coproc_tick_latency_us', 'phase="read"', "paced"),
    "paced.tick_engine_ms": ('coproc_tick_latency_us', 'phase="engine"', "paced"),
    "paced.tick_write_ms": ('coproc_tick_latency_us', 'phase="write"', "paced"),
    "paced.fetch_serve_ms": ("kafka_fetch_serve_latency_us", "", "paced"),
    "paced.loop_lag_ms": ("broker_loop_lag_us", "", "paced"),
    "paced.input_wait_ms": ("coproc_input_wait_latency_us", "", "paced"),
    "paced.output_wait_ms": ("kafka_fetch_wake_latency_us", "", "paced"),
    "tick_handoff_out_ms": ('coproc_tick_latency_us', 'phase="handoff_out"', "catchup"),
    "tick_engine_run_ms": ('coproc_tick_latency_us', 'phase="engine_run"', "catchup"),
    "tick_handoff_back_ms": ('coproc_tick_latency_us', 'phase="handoff_back"', "catchup"),
    "paced.tick_handoff_out_ms": ('coproc_tick_latency_us', 'phase="handoff_out"', "paced"),
    "paced.tick_engine_run_ms": ('coproc_tick_latency_us', 'phase="engine_run"', "paced"),
    "paced.tick_handoff_back_ms": ('coproc_tick_latency_us', 'phase="handoff_back"', "paced"),
    "paced.storage_flush_ms": ("storage_flush_latency_us", "", "paced"),
    "tick_read_hidden_ms": ('coproc_tick_latency_us', 'phase="read_hidden"', "catchup"),
    "paced.tick_read_hidden_ms": ('coproc_tick_latency_us', 'phase="read_hidden"', "paced"),
    # PR 52: the engine phase's two new legs, the produce handler's inside
    "tick_read_ahead_wait_ms": ('coproc_tick_latency_us', 'phase="read_ahead_wait"', "catchup"),
    "tick_engine_prepare_ms": ('coproc_tick_latency_us', 'phase="engine_prepare"', "catchup"),
    "paced.tick_engine_prepare_ms": ('coproc_tick_latency_us', 'phase="engine_prepare"', "paced"),
    **{f"paced.produce_{stage}_ms": ("kafka_produce_stage_latency_us", f'stage="{stage}"', "paced")
       for stage in probes.KAFKA_PRODUCE_STAGES},
}
LINK_WAIT_LEGS = ("h2d", "program", "d2h")


def _scrape():
    """The broker's own /metrics text, parsed by the benchmark's parser."""
    from redpanda_tpu.metrics import registry

    return {"metrics": readers.parse_prometheus(registry.render_prometheus()), "stats": {}}


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_layer_metric_file_reads_its_series_from_a_scrape(name):
    series, label, kind = NEW_METRICS[name]
    with open(os.path.join(REPO, "benchmarks", "layer_metrics", name + ".json")) as f:
        d = json.load(f)
    assert d["traffic"] == [kind] and d["read"]["metric"] == series
    # the series is one the program exports, under the label the file names
    hist = next(
        h for h in _registry_hists(series)
        if not label or dict(h.labels) == dict([label.replace('"', "").split("=")])
    )
    before = _scrape()
    for us in (1000, 2000, 6000):
        hist.record(us)
    after = _scrape()
    other = "paced" if kind == "catchup" else "catchup"
    read = lambda k, b, a: readers.read_all(  # noqa: E731
        os.path.join(REPO, "benchmarks", "layer_metrics"), kind=k, before=b, after=a,
        client={}, trace=None, window_s=1.0,
    )
    got = read(kind, before, after)
    assert got[name] == {"value": pytest.approx(3.0), "unit": "ms"}
    assert name not in read(other, before, after)
    # a program without the series (the parent commit): left out, no error
    assert name not in read(kind, {"metrics": {}, "stats": {}}, {"metrics": {}, "stats": {}})
    assert name not in read(kind, before, before)  # no sample in the window


def _registry_hists(series):
    from redpanda_tpu.metrics import registry

    return [h for h in registry._hists.values() if h.name == series]


@pytest.mark.parametrize("leg", LINK_WAIT_LEGS)
def test_link_wait_file_reads_its_stage_over_the_windows_device_launches(leg):
    """The ``stats_ratio`` twin of the case above: ``link_wait_<leg>_ms_per_launch``
    is ``t_wait_<leg>`` of ``TpuEngine.stats()`` over the device launches."""
    name = f"link_wait_{leg}_ms_per_launch"
    with open(os.path.join(REPO, "benchmarks", "layer_metrics", name + ".json")) as f:
        d = json.load(f)
    assert d["traffic"] == ["catchup"] and d["read"]["kind"] == "stats_ratio"
    assert d["read"]["num"] == [f"t_wait_{leg}"] and d["read"]["den"] == ["n_device_launches"]
    others = {f"t_wait_{o}": 9.0 for o in LINK_WAIT_LEGS if o != leg}
    before = {"metrics": {}, "stats": {f"t_wait_{leg}": 1.0, "n_device_launches": 10.0, "t_fetch": 2.0}}
    after = {"metrics": {}, "stats": {f"t_wait_{leg}": 1.3, "n_device_launches": 20.0, "t_fetch": 9.0, **others}}
    read = lambda k, b, a: readers.read_all(  # noqa: E731
        os.path.join(REPO, "benchmarks", "layer_metrics"), kind=k, before=b, after=a,
        client={}, trace=None, window_s=1.0,
    )
    assert read("catchup", before, after)[name] == {"value": pytest.approx(30.0), "unit": "ms"}
    assert name not in read("paced", before, after)
    # no device launch in the window (the columnar lane): left out, no error
    assert name not in read("catchup", before, before)
    assert name not in read("catchup", {"metrics": {}, "stats": {}}, {"metrics": {}, "stats": {}})
