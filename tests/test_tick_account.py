"""The account of a tick closes (PR 52): on the loop, the engine phase is the
sum of its legs by shared clock reads (``probes.COPROC_ENGINE_PHASES``); on
the worker, ``TpuEngine.submit`` and ``Ticket.result`` each record their time
and their self time (what no top-level ``t_*`` stage of the calling thread
covers); at the front end, a produce request's inside is four stages whose
ring spans hang under the request's ``kafka.produce``."""

from __future__ import annotations

import asyncio
import threading
import time

import pytest

import test_pacemaker_read_ahead as ra
from redpanda_tpu.cluster.topic_table import TopicConfig
from redpanda_tpu.coproc import engine as engine_mod
from redpanda_tpu.coproc.engine import (
    ProcessBatchItem,
    ProcessBatchRequest,
    Ticket,
    TpuEngine,
)
from redpanda_tpu.kafka.client.client import KafkaClient
from redpanda_tpu.kafka.protocol import messages as m
from redpanda_tpu.kafka.protocol.batch import encode_wire_batches
from redpanda_tpu.models.fundamental import NTP
from redpanda_tpu.models.record import Record, RecordBatch
from redpanda_tpu.observability import probes
from redpanda_tpu.observability.trace import tracer

LEGS = probes.COPROC_ENGINE_PHASES
DOCS = 200  # records a batch: 3 partitions x 6 batches = 3,600 a lane


def _sums(phases=("engine", *LEGS)):
    return {ph: (probes.coproc_tick_hist[ph].hist.count, probes.coproc_tick_hist[ph].hist.sum)
            for ph in phases}


def _delta(before, after):
    return {ph: (after[ph][0] - before[ph][0], after[ph][1] - before[ph][1]) for ph in before}


def _assert_the_phase_is_the_sum(d):
    """One productive tick's samples: one of each, and ``engine`` is the sum
    of the five to what truncating six samples to a microsecond leaves (the
    legs share their clock reads, so there is nothing else between them)."""
    assert all(d[ph][0] == 1 for ph in ("engine", *LEGS)), d
    rest = d["engine"][1] - sum(d[ph][1] for ph in LEGS)
    assert -1 <= rest <= len(LEGS), d


async def _backlog(broker):
    for part in range(ra.PARTITIONS):
        for k in range(ra.BATCHES):
            await ra._append(broker, "src", part, ra._docs(DOCS, base=10_000 * part + DOCS * k))


def _drained(ctx):
    return all(ctx.offsets.get(NTP.kafka("src", p)) == DOCS * ra.BATCHES - 1
               for p in range(ra.PARTITIONS))


async def _parked(api, broker, spec):
    await broker.create_topic(TopicConfig("src", ra.PARTITIONS))
    ctx = await ra._deployed(api, "acct", spec)
    await ctx.stop()
    return ctx


def _slow_read_ahead(ctx, seconds):
    """The read-ahead's reads (the ones given a ``start``) take ``seconds``
    each, so the submit is back first and the fiber has to wait."""
    real = ctx._read_ntp

    async def slow(ntp, max_bytes=None, start=None):
        if start is not None:
            await asyncio.sleep(seconds)
        return await real(ntp, max_bytes, start)

    ctx._read_ntp = slow


# ------------------------------------------------------------------ (i) the loop's side
@pytest.mark.parametrize("spec", ["columnar", "payload"])
def test_every_productive_ticks_engine_phase_is_the_sum_of_its_legs(tmp_path, spec):
    async def main():
        storage, broker, server, api = await ra._start(tmp_path)
        try:
            ctx = await _parked(api, broker, spec)
            await _backlog(broker)
            engine = api.pacemaker.engine
            waited = []
            for n in range(ra.BATCHES + 1):
                if _drained(ctx):
                    break
                before = _sums()
                if n == 1:
                    # a tick whose read-ahead ended under the (held) submit
                    assert await ra._tick_under_a_held_engine(ctx, engine) is True
                else:
                    assert await ctx.tick() is True
                d = _delta(before, _sums())
                _assert_the_phase_is_the_sum(d)
                waited.append(d["read_ahead_wait"][1])
                if n == 1:
                    assert d["read_ahead_wait"][1] == 0  # nothing left to wait for
            assert _drained(ctx) and len(waited) == ra.BATCHES
            # the last tick's reads ended at the log's end: nothing was read
            # ahead under it, and its sample is 0 (a tick's mean, like read_hidden)
            assert ctx._ahead is None and waited[-1] == 0
            assert engine.stats()["n_launches"] == ra.BATCHES
        finally:
            await ra._stop(storage, server, api)

    ra.run(main())


def test_a_tick_that_waits_for_its_read_ahead_records_the_wait_and_a_cancelled_one_nothing(tmp_path):
    async def main():
        storage, broker, server, api = await ra._start(tmp_path)
        try:
            ctx = await _parked(api, broker, "columnar")
            await _backlog(broker)
            engine = api.pacemaker.engine
            assert await ctx.tick() is True  # the first launch compiles; not this test's
            _slow_read_ahead(ctx, 0.03)  # three partitions: ~90 ms of reading
            before = _sums()
            assert await ctx.tick() is True
            d = _delta(before, _sums())
            _assert_the_phase_is_the_sum(d)
            assert d["read_ahead_wait"][1] >= 30_000, d  # the submit was back long before
            # cancelled inside the wait (script removal): the tick records its
            # engine phase and none of the legs, and hands the ticket back
            before = _sums()
            released = len(engine.released)
            taken = ctx._ahead  # the tick takes this one and begins the next
            t = asyncio.create_task(ctx.tick())
            await ra.wait_until(
                lambda: ctx._ahead not in (None, taken) and not ctx._ahead.yields,
                msg="in the wait")
            t.cancel()
            with pytest.raises(asyncio.CancelledError):
                await t
            d = _delta(before, _sums())
            assert d["engine"][0] == 1
            assert all(d[ph] == (0, 0) for ph in LEGS), d
            assert len(engine.released) == released + 1
        finally:
            await ra._stop(storage, server, api)

    ra.run(main())


# ------------------------------------------------------------------ (ii) the worker's side
def _request(n=64):
    batch = RecordBatch.build(
        [Record(value=v, offset_delta=i) for i, v in enumerate(ra._docs(n))])
    return ProcessBatchRequest([ProcessBatchItem(1, NTP.kafka("src", 0), [batch])])


def test_a_calls_self_time_is_its_time_less_the_top_level_stages_of_its_own_thread():
    eng = TpuEngine()
    try:
        def on_another_thread():
            t0 = engine_mod._stage_t0("t_stub_elsewhere")
            time.sleep(0.02)
            eng._stat_stage("t_stub_elsewhere", t0, trace_id=None)

        def stub_group(reqs):
            outer = engine_mod._stage_t0("t_stub_outer")
            time.sleep(0.01)
            inner = engine_mod._stage_t0("t_stub_inner")  # nested: counts once, in the outer
            time.sleep(0.01)
            eng._stat_stage("t_stub_inner", inner, trace_id=None)
            eng._stat_stage("t_stub_outer", outer, trace_id=None)
            time.sleep(0.015)  # the call's own Python
            other = threading.Thread(target=on_another_thread)
            other.start()
            other.join()  # a wait of the caller's: its self time, not a stage of its own
            again = engine_mod._stage_t0("t_stub_again")
            time.sleep(0.005)
            eng._stat_stage("t_stub_again", again, trace_id=None)
            return [Ticket(eng)]

        eng.submit_group = stub_group
        ticket = eng.submit(_request())
        s = eng.stats()
        top_level = s["t_stub_outer"] + s["t_stub_again"]
        assert s["t_submit_self"] == pytest.approx(s["t_submit"] - top_level, abs=1e-9)
        assert s["t_stub_inner"] >= 0.01 and s["t_stub_elsewhere"] >= 0.02
        assert s["t_submit_self"] >= 0.015 + 0.02  # the sleep and the join
        assert s["t_submit"] >= 0.06
        # the same two clock reads are the pacemaker's worker clock
        t_run, t_done = ticket.worker_clock
        assert t_done - t_run == pytest.approx(s["t_submit"], abs=1e-9)

        # a submit that raises has its time and its self time too; a stage it
        # left open does not nest the next call's
        def failing(reqs):
            engine_mod._stage_t0("t_stub_left_open")
            time.sleep(0.005)
            raise RuntimeError("no launch")

        eng.submit_group = failing
        with pytest.raises(RuntimeError):
            eng.submit(_request())
        s2 = eng.stats()
        assert s2["t_submit"] - s["t_submit"] >= 0.005
        assert s2["t_submit_self"] - s["t_submit_self"] == pytest.approx(
            s2["t_submit"] - s["t_submit"], abs=1e-9)
        eng.submit_group = stub_group
        eng.submit(_request())
        s3 = eng.stats()
        assert s3["t_submit_self"] - s2["t_submit_self"] == pytest.approx(
            (s3["t_submit"] - s2["t_submit"])
            - (s3["t_stub_outer"] - s2["t_stub_outer"])
            - (s3["t_stub_again"] - s2["t_stub_again"]), abs=1e-9)
        hist = probes.coproc_stage_hist
        assert hist("submit").hist.count >= 3 and hist("submit_self").hist.count >= 3
    finally:
        eng.shutdown()


@pytest.mark.parametrize("lane", ["columnar", "payload", "columnar_host"])
def test_both_calls_record_their_time_and_a_self_time_within_it_on_every_lane(lane):
    spec = ra.SPECS["columnar" if lane != "payload" else "payload"]()
    eng = TpuEngine(force_mode="columnar_host" if lane == "columnar_host" else None)
    try:
        assert eng.enable_coprocessors([(1, spec, ("src",))]) == [0]
        for _ in range(3):
            reply = eng.process_batch(_request(256))
            assert reply.items and reply.items[0].batches
        s = eng.stats()
        assert s["n_launches"] == 3
        for call in ("submit", "harvest"):
            assert 0.0 <= s[f"t_{call}_self"] <= s[f"t_{call}"], (call, s)
            assert probes.coproc_stage_hist(call).hist.count >= 3
        # the stages a call closes at its top level are inside it
        staged = sum(v for k, v in s.items() if k.startswith("t_explode"))
        assert staged <= s["t_submit"] - s["t_submit_self"] + 1e-9
        assert s["t_seal"] <= s["t_harvest"] - s["t_harvest_self"] + 1e-9
    finally:
        eng.shutdown()


# ------------------------------------------------------------------ (iii) the front end
STAGES = probes.KAFKA_PRODUCE_STAGES


def _produce_counts():
    return {st: probes.kafka_produce_stage_hist[st].hist.count for st in STAGES}


def _wire(n, base=0):
    return encode_wire_batches([RecordBatch.build(
        [Record(value=v, offset_delta=i) for i, v in enumerate(ra._docs(n, base))])])


def _body(partitions, acks=-1):
    return {"transactional_id": None, "acks": acks, "timeout_ms": 30000,
            "topics": [{"name": "src", "partitions": [
                {"partition_index": p, "records": _wire(8, base=100 * p)} for p in partitions]}]}


def test_a_produce_request_is_one_queue_sample_and_one_of_each_stage_a_partition(tmp_path):
    async def main():
        storage, broker, server, api = await ra._start(tmp_path)
        client = await KafkaClient([("127.0.0.1", server.port)]).connect()
        tracer.reset()
        tracer.configure(enabled=True)
        try:
            await broker.create_topic(TopicConfig("src", 2))
            conn = await client.leader_connection("src", 0)

            async def took(body, oneway=False):
                before = _produce_counts()
                if oneway:
                    await conn.oneway(m.PRODUCE, body)
                    await ra.wait_until(
                        lambda: _produce_counts()["queue"] > before["queue"], msg="acks=0")
                    await asyncio.sleep(0.05)
                    resp = None
                else:
                    resp = await conn.request(m.PRODUCE, body)
                after = _produce_counts()
                return {st: after[st] - before[st] for st in STAGES}, resp

            got, resp = await took(_body([0]))
            assert got == {"queue": 1, "decode": 1, "crc": 1, "replicate": 1}
            assert resp["responses"][0]["partitions"][0]["error_code"] == 0
            # the ring: each stage's span hangs under this request's kafka.produce
            (trace,) = [t for t in tracer.recent(0)
                        if any(s["name"] == "kafka.produce" for s in t["spans"])]
            (root,) = [s for s in trace["spans"] if s["name"] == "kafka.produce"]
            kids = {s["name"]: s for s in trace["spans"] if s.get("parent") == root["span_id"]}
            assert {"kafka.produce." + st for st in STAGES} <= set(kids)
            assert sum(kids["kafka.produce." + st]["dur_us"] for st in STAGES[1:]) <= root["dur_us"]

            got, _ = await took(_body([0, 1]))  # one request, two partitions
            assert got == {"queue": 1, "decode": 2, "crc": 2, "replicate": 2}
            got, resp = await took(_body([0], acks=0), oneway=True)
            assert resp is None and got == {"queue": 1, "decode": 1, "crc": 1, "replicate": 1}
            # a refused partition records what ran before the refusal: nothing
            broker.get_partition("src", 1).is_leader = lambda: False
            got, resp = await took(_body([0, 1]))
            codes = [p["error_code"] for p in resp["responses"][0]["partitions"]]
            assert codes[0] == 0 and codes[1] != 0
            assert got == {"queue": 1, "decode": 1, "crc": 1, "replicate": 1}
            got, resp = await took(_body([1]))
            assert got == {"queue": 1, "decode": 0, "crc": 0, "replicate": 0}
            # a batch whose CRC does not match: decoded, checked, refused
            bad = _body([0])
            wire = bytearray(_wire(8))
            wire[-1] ^= 0xFF
            bad["topics"][0]["partitions"][0]["records"] = bytes(wire)
            got, resp = await took(bad)
            assert resp["responses"][0]["partitions"][0]["error_code"] != 0
            assert got == {"queue": 1, "decode": 1, "crc": 1, "replicate": 0}
        finally:
            tracer.configure(enabled=False)
            tracer.reset()
            await client.close()
            await ra._stop(storage, server, api)

    ra.run(main())


# ------------------------------------------------------------------ the operator's view
def test_rpk_debug_coproc_prints_the_ticks_account_from_the_same_series(tmp_path, capsys):
    import json

    from redpanda_tpu.admin import AdminServer
    from redpanda_tpu.cli import rpk

    async def main():
        storage, broker, server, api = await ra._start(tmp_path)
        admin = await AdminServer(broker, port=0).start()
        try:
            ctx = await _parked(api, broker, "payload")
            await _backlog(broker)
            while await ctx.tick():
                pass
            argv = ["--admin-api", f"127.0.0.1:{admin.port}", "debug", "coproc"]
            await asyncio.to_thread(rpk.main, argv)
            await asyncio.to_thread(rpk.main, argv + ["--json"])
        finally:
            await admin.stop()
            await ra._stop(storage, server, api)

    ra.run(main())
    printed = capsys.readouterr().out
    (line,) = [ln for ln in printed.splitlines() if ln.startswith("tick:")]
    for word in ("prepare", "out", "run", "back", "read-ahead wait", "submit", "harvest", "self"):
        assert word in line, (word, line)
    body = json.loads(printed[printed.rindex("\n{\n"):])  # the --json call's
    ta = body["tick_account"]
    assert ta["ticks"] >= ra.BATCHES
    legs = sum(ta[ph + "_us"] for ph in LEGS)
    # process-wide sums: engine also holds what other tests' failed ticks left
    assert legs <= ta["engine_us"] + ta["ticks"]
    stats = body["stats"]
    assert stats["t_submit"] >= stats["t_submit_self"] >= 0
    assert stats["t_harvest"] >= stats["t_harvest_self"] >= 0


# ------------------------------------------------------------------ the stop is timed
@pytest.mark.parametrize("tracing", [False, True])
def test_the_stop_says_what_each_service_took_and_which_threads_live(tmp_path, caplog, tracing):
    import logging

    from redpanda_tpu.app import Application
    from redpanda_tpu.config import Configuration

    async def main():
        cfg = Configuration()
        cfg.set("data_directory", str(tmp_path))
        cfg.set("kafka_api_port", 0)
        cfg.set("admin_api_port", 0)
        cfg.set("coproc_enable", "true")
        app = await Application(cfg).start()
        services = [type(svc).__name__ for svc in app._stop_order]
        tracer.reset()
        tracer.configure(enabled=tracing)
        try:
            with caplog.at_level(logging.INFO, logger="rptpu.app"):
                await app.stop()
            return services, tracer.recent(0)
        finally:
            tracer.configure(enabled=False)
            tracer.reset()

    services, traces = asyncio.run(asyncio.wait_for(main(), 60))
    (line,) = [r.getMessage() for r in caplog.records if r.getMessage().startswith("stopped ")]
    assert f"stopped {len(services)} services" in line and len(services) >= 3
    took = line[line.index("(s each: ") + 9:line.index("); threads alive: ")]
    assert [part.rsplit(" ", 1)[0] for part in took.split(", ")] == services[::-1]  # reverse order
    assert all(float(part.rsplit(" ", 1)[1]) >= 0.0 for part in took.split(", "))
    assert "MainThread" in line[line.index("threads alive: "):]
    # ring only (no histogram): one app.stop root, a child a service
    spans = [s for t in traces for s in t["spans"] if s["name"].startswith("app.stop")]
    if not tracing:
        assert spans == []
        return
    (root,) = [s for s in spans if s["name"] == "app.stop"]
    kids = [s for s in spans if s.get("parent") == root["span_id"]]
    assert sorted(s["name"] for s in kids) == sorted("app.stop." + n for n in services)
    from redpanda_tpu.metrics import registry

    assert "app_stop" not in registry.render_prometheus()
