"""The pacemaker's read-ahead of depth one (coproc/pacemaker.py): while the
engine holds tick N's input the script's fiber reads tick N+1's, for the
partitions whose read the byte budget cut short of the LSO. Offsets still
move only after the materialized write, so every failure drops what was
read ahead and the next tick re-reads from ``self.offsets``.

The engine here is the real one behind a door (``HeldEngine``): a submit can
be held until the test lets it through, and failed at will."""

from __future__ import annotations

import asyncio
import json
import threading
import time

import pytest

from redpanda_tpu.cluster.topic_table import TopicConfig
from redpanda_tpu.coproc import governor, leakwatch
from redpanda_tpu.coproc.api import CoprocApi
from redpanda_tpu.kafka.server.broker import Broker, BrokerConfig
from redpanda_tpu.kafka.server.protocol import KafkaServer
from redpanda_tpu.models.fundamental import NTP
from redpanda_tpu.models.record import Record, RecordBatch
from redpanda_tpu.observability import probes
from redpanda_tpu.observability.trace import tracer
from redpanda_tpu.ops.exprs import field
from redpanda_tpu.ops.transforms import Int, Str, filter_contains, map_project, where
from redpanda_tpu.resource_mgmt.admission import ShedError
from redpanda_tpu.storage.log_manager import StorageApi

PARTITIONS = 3
BATCHES = 6  # a partition's backlog; one batch a read at the budget below
DOCS = 8  # records a batch


def run(coro, limit_s=90.0):
    asyncio.run(asyncio.wait_for(coro, limit_s))


async def wait_until(pred, timeout=20.0, msg=""):
    deadline = time.monotonic() + timeout
    while not pred():
        assert time.monotonic() < deadline, f"timeout: {msg}"
        await asyncio.sleep(0.01)


async def _start(tmp_path):
    storage = await StorageApi(str(tmp_path)).start()
    cfg = BrokerConfig(data_dir=str(tmp_path))
    broker = Broker(cfg, storage)
    server = await KafkaServer(broker, "127.0.0.1", 0).start()
    cfg.advertised_port = server.port
    api = await CoprocApi(broker).start()
    api.poll_interval_s = 0.02
    broker.coproc_api = api
    # one batch a partition a read: every read of a backlog stops short of
    # the LSO, which is what engages the read-ahead
    api.pacemaker.max_batch_size = 1
    api.pacemaker.engine = HeldEngine(api.pacemaker.engine)
    return storage, broker, server, api


async def _stop(storage, server, api):
    api.pacemaker.engine.door.set()
    await api.stop()
    await server.stop()
    await storage.stop()


class HeldEngine:
    """The real engine, with a door in front of ``submit``: closed, a
    submit waits on its executor thread until the test opens it; ``fail``
    is raised by the next submit in place of a launch. ``launches`` keeps
    what every submit was handed: [(partition, first offset, last offset)]."""

    def __init__(self, real) -> None:
        self._real = real
        self.door = threading.Event()
        self.door.set()
        self.waiting = threading.Event()
        self.fail: Exception | None = None
        self.launches: list[list[tuple]] = []
        self.released: list = []  # tickets whose admission the pacemaker gave back

    def __getattr__(self, name):
        return getattr(self._real, name)

    def _release_admission(self, ticket):
        self.released.append(ticket)
        self._real._release_admission(ticket)

    def submit(self, req):
        self.launches.append([
            (it.ntp.partition, it.batches[0].base_offset, it.batches[-1].last_offset)
            for it in req.items
        ])
        self.waiting.set()
        self.door.wait(60)
        exc, self.fail = self.fail, None
        if exc is not None:
            raise exc
        return self._real.submit(req)


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


async def _backlog(broker, batches=BATCHES, topic="src", partitions=PARTITIONS):
    for part in range(partitions):
        for k in range(batches):
            await _append(broker, topic, part, _docs(DOCS, base=1000 * part + DOCS * k))


SPECS = {
    "columnar": lambda: (
        where(field("level") == "error") | map_project(Int("code"), Str("msg", 16))
    ).to_json(),
    "payload": lambda: filter_contains(b'"level":"error"').to_json(),
}


async def _deployed(api, name, spec="columnar", topic="src"):
    await api.deploy(name, SPECS[spec](), [topic])
    await wait_until(lambda: name in api.pacemaker.scripts(), msg="deployed")
    return api.pacemaker.scripts()[name]


async def _parked(api, broker, name="proj", topic="src"):
    """An empty topic, the script deployed over it and its fiber parked: the
    test fills the topic and drives ``tick``."""
    await broker.create_topic(TopicConfig(topic, PARTITIONS))
    ctx = await _deployed(api, name, topic=topic)
    await ctx.stop()
    return ctx


def _drained(ctx, batches=BATCHES, topic="src"):
    return all(
        ctx.offsets.get(NTP.kafka(topic, p)) == DOCS * batches - 1
        for p in range(PARTITIONS)
    )


async def _tick_under_a_held_engine(ctx, engine):
    """One tick whose engine phase lasts until the read-ahead (if any)
    under it has finished: what it read, it read hidden."""
    engine.waiting.clear()
    engine.door.clear()
    t = asyncio.create_task(ctx.tick())
    try:
        await wait_until(lambda: engine.waiting.is_set() or t.done(), msg="submit")
        await wait_until(
            lambda: t.done() or ctx._ahead is None or ctx._ahead.task.done(),
            msg="read-ahead",
        )
    finally:
        engine.door.set()
    return await t


async def _materialized(broker, script, topic="src", partitions=PARTITIONS):
    """Every partition of the script's materialized log, batch by batch, as
    the bytes the log holds."""
    out = []
    for part in range(partitions):
        p = broker.partition_manager.get(NTP.kafka(f"{topic}.${script}$", part))
        batches = await p.make_reader(0, 1 << 30) if p is not None else []
        out.append([b.encode_internal() for b in batches])
    return out


async def _drain_one_tick_at_a_time(api, name="serial", spec="columnar"):
    """The same backlog through a script that may not read ahead: what the
    log has to hold."""
    pm = api.pacemaker
    pm.read_ahead_allowed = lambda: False
    try:
        ctx = await _deployed(api, name, spec)
        await wait_until(lambda: _drained(ctx), msg="drained one tick at a time")
        assert ctx._ahead is None
    finally:
        del pm.read_ahead_allowed


def _hist(phase):
    h = probes.coproc_tick_hist[phase].hist
    return h.count, h.sum


# ------------------------------------------------------------------ (a)
@pytest.mark.parametrize("spec", ["columnar", "payload"])
def test_a_backlog_drained_with_read_ahead_is_the_same_log_byte_for_byte(tmp_path, spec):
    async def main():
        storage, broker, server, api = await _start(tmp_path)
        try:
            await broker.create_topic(TopicConfig("src", PARTITIONS))
            await _backlog(broker)
            engine = api.pacemaker.engine
            hidden0 = _hist("read_hidden")
            # the fiber runs free: deploy and wait for the drain
            ctx = await _deployed(api, "ahead", spec)
            await wait_until(lambda: _drained(ctx), msg="drained")
            with_ahead = list(engine.launches)
            hidden1 = _hist("read_hidden")
            assert hidden1[0] - hidden0[0] == len(with_ahead) == BATCHES
            # the first launch compiles for far longer than a read takes
            assert hidden1[1] > hidden0[1]
            del engine.launches[:]
            await _drain_one_tick_at_a_time(api, spec=spec)
            assert _hist("read_hidden") == (hidden1[0] + BATCHES, hidden1[1])
            # the same launches over the same records, and the same log
            assert engine.launches == with_ahead
            got = await _materialized(broker, "ahead")
            assert got == await _materialized(broker, "serial")
            assert all(len(part) == BATCHES for part in got)
        finally:
            await _stop(storage, server, api)

    run(main())


# ------------------------------------------------------------------ (b)
def _fail(ctx, engine):
    engine.fail = RuntimeError("engine down")
    return RuntimeError


def _shed(ctx, engine):
    engine.fail = ShedError("coproc", 1, "test")
    return None  # a shed tick returns False, it does not raise


def _time_out(ctx, engine):
    ctx.pacemaker.tick_deadline_for = lambda _engine: 0.05
    return asyncio.TimeoutError


@pytest.mark.parametrize("how", [_fail, _shed, _time_out])
def test_a_tick_that_does_not_land_drops_what_was_read_ahead(tmp_path, how):
    async def main():
        storage, broker, server, api = await _start(tmp_path)
        try:
            pm = api.pacemaker
            engine = pm.engine
            ctx = await _parked(api, broker)
            await _backlog(broker)
            assert await _tick_under_a_held_engine(ctx, engine) is True
            after_first = dict(ctx.offsets)
            assert ctx._ahead is not None and len(ctx._ahead.reads) == PARTITIONS

            # tick 2 takes what tick 1 read ahead, reads tick 3's under its
            # own engine phase, and then does not land
            raises = how(ctx, engine)
            engine.waiting.clear()
            engine.door.clear()
            t = asyncio.create_task(ctx.tick())
            await wait_until(engine.waiting.is_set, msg="submit")
            await wait_until(
                lambda: t.done() or (ctx._ahead is not None and ctx._ahead.task.done()),
                msg="read-ahead",
            )
            dropped = ctx._ahead
            if raises is not asyncio.TimeoutError:
                engine.door.set()
            if raises is None:
                assert await t is False
            else:
                with pytest.raises(raises):
                    await t
            engine.door.set()
            vars(pm).pop("tick_deadline_for", None)
            assert dropped is not None and ctx._ahead is None
            assert ctx.offsets == after_first  # nothing moved

            # tick 3 re-reads from self.offsets: tick 2's records again
            assert await _tick_under_a_held_engine(ctx, engine) is True
            assert engine.launches[2] == engine.launches[1]
            assert engine.launches[1] == [
                (p, DOCS, 2 * DOCS - 1) for p in range(PARTITIONS)
            ]
            while await ctx.tick():
                pass
            await _drain_one_tick_at_a_time(api)
            assert await _materialized(broker, "proj") == await _materialized(broker, "serial")
            assert pm.read_budget.held == 0
        finally:
            await _stop(storage, server, api)

    run(main())


# ------------------------------------------------------------------ (c)
def test_a_write_that_does_not_land_drops_that_partitions_read_ahead_alone(tmp_path):
    async def main():
        storage, broker, server, api = await _start(tmp_path)
        try:
            engine = api.pacemaker.engine
            ctx = await _parked(api, broker)
            await _backlog(broker)
            real = ctx._write_materialized
            refused = []

            async def refuse_partition_0_once(source, batches):
                if source.partition == 0 and not refused:
                    refused.append(source)
                    return False
                return await real(source, batches)

            ctx._write_materialized = refuse_partition_0_once
            assert await _tick_under_a_held_engine(ctx, engine) is True
            assert refused and NTP.kafka("src", 0) not in ctx.offsets
            assert len(ctx._ahead.reads) == PARTITIONS  # read before the write

            hidden = _hist("read_hidden")
            assert await _tick_under_a_held_engine(ctx, engine) is True
            # partition 0 was read again from where the offsets stand, the
            # others came from the read-ahead
            assert engine.launches[1] == [
                (0, 0, DOCS - 1), (1, DOCS, 2 * DOCS - 1), (2, DOCS, 2 * DOCS - 1),
            ]
            count, total = _hist("read_hidden")
            assert count == hidden[0] + 1 and total > hidden[1]
            while await ctx.tick():
                pass
            await _drain_one_tick_at_a_time(api)
            assert await _materialized(broker, "proj") == await _materialized(broker, "serial")
        finally:
            await _stop(storage, server, api)

    run(main())


# ------------------------------------------------------------------ (d)
@pytest.mark.parametrize("submit", ["held", "back"])
def test_script_removal_during_a_read_ahead_leaves_the_read_budget_balanced(tmp_path, submit):
    """Removal finds the fiber waiting for the submit's reply (``held``) or,
    with the ticket in hand, seeing its read-ahead out before the harvest
    (``back``): either way both tasks go and every reservation comes back."""

    async def main():
        leakwatch.reset()
        leakwatch.enable()
        try:
            storage, broker, server, api = await _start(tmp_path)
        finally:
            leakwatch.disable()  # the pacemaker's account is wrapped by now
        try:
            await broker.create_topic(TopicConfig("src", PARTITIONS))
            await _backlog(broker)
            pm = api.pacemaker
            engine = pm.engine
            # a read ahead of the offsets (start > 0) of partition 1 stays
            # inside storage, holding its reservation, until let go
            p1 = broker.get_partition("src", 1)
            real, let_go = p1.make_reader, asyncio.Event()

            async def held_reader(start, *a, **kw):
                if start > 0:
                    await let_go.wait()
                return await real(start, *a, **kw)

            p1.make_reader = held_reader
            if submit == "held":
                engine.door.clear()
            ctx = await _deployed(api, "proj")
            await wait_until(lambda: pm.read_budget.held > 0, msg="a read-ahead inside a read")
            if submit == "back":
                await asyncio.sleep(0.2)  # the ticket is in hand, unharvested
            ahead = ctx._ahead
            assert not ahead.task.done() and len(ahead.reads) == 1  # partition 0
            assert leakwatch.balances()["pacemaker.read_budget"] > 0

            await pm.remove_script("proj")
            assert ahead.task.cancelled() and ctx._ahead is None and ctx._task is None
            assert leakwatch.balances()["pacemaker.read_budget"] == 0
            assert pm.read_budget.held == 0
            assert not leakwatch.snapshot()["outstanding"]
            # a ticket in hand that will never be harvested gives its
            # admission back
            assert len(engine.released) == (1 if submit == "back" else 0)
            assert leakwatch.snapshot()["imbalances"] == 0
            assert not [t for t in asyncio.all_tasks()
                        if "ScriptContext" in repr(t.get_coro())]
        finally:
            await _stop(storage, server, api)

    run(main())


def test_the_harvest_goes_out_when_the_read_ahead_has_finished(tmp_path, monkeypatch):
    """The harvest's framing and seal take the interpreter lock back once a
    batch: the fiber sees its read-ahead out between the two executor calls,
    so that they never run beside a loop thread that reads."""
    from redpanda_tpu.coproc.engine import Ticket

    harvests = []
    real_result = Ticket.result

    def result(self):
        harvests.append(self)
        return real_result(self)

    monkeypatch.setattr(Ticket, "result", result)

    async def main():
        storage, broker, server, api = await _start(tmp_path)
        try:
            pm = api.pacemaker
            ctx = await _parked(api, broker)
            await _backlog(broker)
            p1 = broker.get_partition("src", 1)
            real, let_go = p1.make_reader, asyncio.Event()

            async def held_reader(start, *a, **kw):
                if start > 0:
                    await let_go.wait()
                return await real(start, *a, **kw)

            p1.make_reader = held_reader
            t = asyncio.create_task(ctx.tick())  # the door is open
            await wait_until(lambda: pm.read_budget.held > 0, msg="a read-ahead inside a read")
            await asyncio.sleep(0.2)  # the submit has long come back
            assert pm.engine.launches and not harvests and not t.done()
            let_go.set()
            assert await t is True
            assert len(harvests) == 1 and len(ctx._ahead.reads) == PARTITIONS
            assert pm.read_budget.held == 0
        finally:
            await _stop(storage, server, api)

    run(main())


# ------------------------------------------------------------------ (e), (f)
def _trickle(api):
    # the reads take whole partitions again: nothing is left behind
    api.pacemaker.max_batch_size = 32 * 1024


def _pressure_warn(api):
    gov = api.pacemaker.engine.governor
    gov.configure_autotune(pressure_fn=lambda: ("warn", 0.9))
    assert gov.pressure_level() == "warn"


@pytest.mark.parametrize("why", [_trickle, _pressure_warn])
def test_no_read_ahead(tmp_path, why):
    """(e) a stream whose every read empties its partitions, (f) a backlog
    under memory pressure: the fiber reads for itself."""

    async def main():
        storage, broker, server, api = await _start(tmp_path)
        try:
            engine = api.pacemaker.engine
            ctx = await _parked(api, broker)
            await _backlog(broker, batches=2)
            why(api)
            hidden = _hist("read_hidden")
            ticks = 0
            while await _tick_under_a_held_engine(ctx, engine):
                assert ctx._ahead is None
                ticks += 1
                for part in range(PARTITIONS):  # and more arrives meanwhile
                    if ticks < 3:
                        await _append(broker, "src", part, _docs(DOCS, base=5000 + ticks))
            assert ticks >= 3
            assert _hist("read_hidden") == (hidden[0] + ticks, hidden[1])
            if why is _pressure_warn:
                # the same backlog with the pressure gone reads ahead
                api.pacemaker.engine.governor.configure_autotune(pressure_fn=None)
                for part in range(PARTITIONS):
                    for k in range(2):
                        await _append(broker, "src", part, _docs(DOCS, base=7000 + k))
                assert await _tick_under_a_held_engine(ctx, engine) is True
                assert ctx._ahead is not None and len(ctx._ahead.reads) == PARTITIONS
        finally:
            await _stop(storage, server, api)

    run(main())


# ------------------------------------------------------------------ (g)
@pytest.mark.parametrize("tracing", [False, True])
def test_one_read_tick_and_read_hidden_sample_a_productive_tick(tmp_path, tracing):
    phases = ("tick", "read", "read_hidden", "gate", "engine", "write")

    def counts():
        return {ph: _hist(ph) for ph in phases}

    async def main():
        storage, broker, server, api = await _start(tmp_path)
        tracer.reset()
        tracer.configure(enabled=tracing)
        try:
            engine = api.pacemaker.engine
            ctx = await _parked(api, broker)
            await _backlog(broker, batches=3)
            waits = probes.coproc_input_wait_hist.hist
            c0, w0 = counts(), waits.count
            assert await _tick_under_a_held_engine(ctx, engine) is True
            c1, w1 = counts(), waits.count
            for ph in phases:
                assert c1[ph][0] == c0[ph][0] + 1, ph
            # it read for itself: nothing hidden
            assert c1["read_hidden"][1] == c0["read_hidden"][1]
            # what was read ahead is no tick's input yet: the wait is
            # recorded by the tick that takes it
            assert len(ctx._ahead.reads) == PARTITIONS and w1 == w0 + PARTITIONS

            assert await _tick_under_a_held_engine(ctx, engine) is True
            c2 = counts()
            for ph in phases:
                assert c2[ph][0] == c1[ph][0] + 1, ph
            assert waits.count == w1 + PARTITIONS

            def took(ph):
                return c2[ph][1] - c1[ph][1]

            # every partition came out of the read-ahead, which ran under
            # the held engine phase: the whole read was hidden
            assert took("read") >= took("read_hidden") > 0
            parts = (took("read") - took("read_hidden") + took("gate")
                     + took("engine") + took("write"))
            assert parts <= took("tick") + len(phases)
            assert parts >= 0.95 * took("tick"), (parts, took("tick"))

            # the last tick of a drain reads ahead of nothing
            assert await _tick_under_a_held_engine(ctx, engine) is True
            assert ctx._ahead is None
            assert await ctx.tick() is False
            assert counts()["read_hidden"][0] == c2["read_hidden"][0] + 1

            if not tracing:
                assert tracer.spans_recorded == 0
                return
            ticks = [t for t in tracer.recent(0)
                     if any(s["name"] == "coproc.tick" for s in t["spans"])]
            assert len(ticks) == 3
            aheads = [
                (t, s) for t in ticks for s in t["spans"]
                if s["name"] == "coproc.read" and "ahead_partitions" in s
            ]
            assert len(aheads) == 2
            for t, s in aheads:
                # the read-ahead lies under the engine span of the tick it
                # ran beneath, beside that span's two waits
                (eng,) = [x for x in t["spans"] if x["name"] == "coproc.engine"]
                assert s["parent"] == eng["span_id"]
                assert s["ahead_partitions"] == PARTITIONS
                assert eng["start_us"] <= s["start_us"]
            for t in ticks:
                (tick,) = [s for s in t["spans"] if s["name"] == "coproc.tick"]
                kids = {s["name"] for s in t["spans"] if s.get("parent") == tick["span_id"]}
                assert kids == {"coproc.read", "coproc.gate", "coproc.engine", "coproc.write"}
        finally:
            tracer.configure(enabled=False)
            tracer.reset()
            await _stop(storage, server, api)

    run(main())


def test_rpk_debug_coproc_prints_the_read_ahead_share(tmp_path, capsys):
    from redpanda_tpu.admin import AdminServer
    from redpanda_tpu.cli import rpk

    async def main():
        storage, broker, server, api = await _start(tmp_path)
        admin = await AdminServer(broker, port=0).start()
        try:
            engine = api.pacemaker.engine
            ctx = await _parked(api, broker)
            await _backlog(broker, batches=2)
            while await _tick_under_a_held_engine(ctx, engine):
                pass
            argv = ["--admin-api", f"127.0.0.1:{admin.port}", "debug", "coproc"]
            await asyncio.to_thread(rpk.main, argv)
            await asyncio.to_thread(rpk.main, argv + ["--json"])
        finally:
            await admin.stop()
            await _stop(storage, server, api)

    run(main())
    printed = capsys.readouterr().out
    (line,) = [ln for ln in printed.splitlines() if ln.startswith("read-ahead:")]
    assert "% of the pacemaker's read ran inside the previous tick's engine phase" in line
    body = json.loads(printed[printed.rindex("\n{\n"):])  # the --json call's
    ra = body["read_ahead"]
    assert 0 < ra["read_hidden_us"] <= ra["read_us"] and ra["ticks"] >= 2
    # the log's appends: storage_append_crossing_batches, _sum over _count
    assert "append:  " in printed and "batches a framing call" in printed
    assert body["append"]["batches"] >= body["append"]["framings"] > 0


# ------------------------------------------------------------------ (i)
@pytest.mark.parametrize("corrupt", [(1,), (0, 1, 2)], ids=["one_of_three", "the_whole_reply"])
def test_a_corrupt_batch_in_the_reply_is_left_out_and_the_offset_moves(tmp_path, caplog, corrupt):
    """The materialized write's CRC re-check rides the log's append
    (``verify_crc``): a batch of the reply whose payload no longer matches
    its seal is left out and logged, its neighbours land with contiguous
    offsets, and the source offset advances as it always has (the input was
    read and transformed; the next tick must not read it again)."""
    from dataclasses import replace

    async def main():
        storage, broker, server, api = await _start(tmp_path)
        try:
            engine = api.pacemaker.engine
            ctx = await _parked(api, broker)
            await _backlog(broker, batches=3)
            api.pacemaker.max_batch_size = 1 << 20  # the whole backlog a read
            real = ctx._write_materialized

            async def tear_partition_1(source, batches):
                if source.partition == 1:
                    assert len(batches) == 3
                    batches = [
                        RecordBatch(
                            replace(b.header),
                            b.payload[:-1] + bytes([b.payload[-1] ^ 0x01]),
                        ) if i in corrupt else b
                        for i, b in enumerate(batches)
                    ]
                return await real(source, batches)

            ctx._write_materialized = tear_partition_1
            with caplog.at_level("ERROR", logger="rptpu.storage"):
                assert await _tick_under_a_held_engine(ctx, engine) is True
            # every partition's offset stands at the end of what was read
            assert _drained(ctx, batches=3)
            dropped = [r for r in caplog.records if "dropping corrupt batch" in r.getMessage()]
            assert len(dropped) == len(corrupt)
            assert "src.$proj$" in dropped[0].getMessage()
            assert await ctx.tick() is False  # nothing is read again
            got = await _materialized(broker, "proj")
            assert [len(part) for part in got] == [3, 3 - len(corrupt), 3]
            p1 = broker.partition_manager.get(NTP.kafka("src.$proj$", 1))
            landed = await p1.make_reader(0, 1 << 30) if p1 is not None else []
            expect = 0
            for b in landed:
                assert b.base_offset == expect and b.verify_kafka_crc()
                expect = b.last_offset + 1
            # the sound batches are the ones partition 0 and 2 hold in
            # their place: same script, same documents but for the code
            assert [b.header.record_count for b in landed] == [DOCS // 2] * len(landed)
        finally:
            await _stop(storage, server, api)

    run(main())


# ------------------------------------------------------------------ (j)
class _NoDeviceLeg:
    """The ``device_dispatch`` histogram of a lane that launches nothing on
    the device: never a sample. (The process-wide one holds whatever the
    payload tests before this one launched.)"""

    count = 0

    def percentile(self, q):
        return 0

    def record(self, v):
        self.count += 1


CAP = 4


async def _on_the_host_lane(api, broker):
    """The columnar script (evaluated on the host at this size) parked over
    an empty topic, under a governor that has never seen a device leg and
    keeps a journal of its own; one batch is a little under the read budget
    at the knob's start, as in the catch-up cells (31.9 KB under 32 KiB: two
    batches a read, and ``knob + 1`` a read from there)."""
    gov = api.pacemaker.engine.governor
    legs = _NoDeviceLeg()
    gov._stage_hist = lambda domain: legs
    gov._journal = governor.DecisionJournal(64)
    gov.configure_autotune(group_ticks=1, group_ticks_cap=CAP, launch_depth=4)
    ctx = await _parked(api, broker)
    for part in range(PARTITIONS):
        await _append(broker, "src", part, _docs(DOCS))
    (batch,) = await broker.get_partition("src", 0).make_reader(0, 1 << 20)
    api.pacemaker.max_batch_size = batch.size_bytes + 4
    return ctx, gov, legs


def _batches_a_partition(launch):
    sizes = {(last - first + 1) // DOCS for _part, first, last in launch}
    assert len(sizes) == 1, launch
    return sizes.pop()


def test_a_backlog_on_the_host_lane_grows_the_read_to_the_cap_by_the_launch(tmp_path):
    K = governor._AUTOTUNE_BACKLOG_LAUNCHES

    async def main():
        storage, broker, server, api = await _start(tmp_path)
        try:
            pm, engine = api.pacemaker, api.pacemaker.engine
            ctx, gov, legs = await _on_the_host_lane(api, broker)
            for part in range(PARTITIONS):
                for _ in range(40):  # every batch the size of the first
                    await _append(broker, "src", part, _docs(DOCS))
            t_start = time.monotonic()
            knobs = []
            real = pm.launch_knobs
            pm.launch_knobs = lambda: knobs.append(real()) or knobs[-1]
            while await ctx.tick():
                pass
            del pm.launch_knobs
            assert _drained(ctx, batches=41) and legs.count == 0
            # one step a K launches, whatever the clock says (the 5 s hold
            # of the device rule would have allowed one move in this time)
            assert time.monotonic() - t_start < 5.0
            ticks = [k["group_ticks"] for k in knobs]
            assert ticks[:3 * K + 1] == [1] * K + [2] * K + [3] * K + [CAP]
            assert set(ticks[3 * K:]) == {CAP}
            assert {k["launch_depth"] for k in knobs} == {4}
            # what a partition gave a launch: knob + 1 batches, the launch
            # after a move still at the size it was read ahead at. At the
            # cap a read is CAP x max_batch_size, rounded up to whole batches
            reads = [_batches_a_partition(launch) for launch in engine.launches]
            assert reads[:3 * K + 2] == [2] * (K + 1) + [3] * K + [4] * K + [CAP + 1]
            assert set(reads[3 * K + 1:-1]) == {CAP + 1} and sum(reads) == 41
            batch = pm.max_batch_size - 4
            assert CAP * pm.max_batch_size <= (CAP + 1) * batch < (CAP + 1) * pm.max_batch_size
            moves = gov._journal.entries(domain="admission")[::-1]
            assert [(e["verdict"], e["inputs"]["evidence"], e["inputs"]["group_ticks"])
                    for e in moves] == [("grow", "backlog", g) for g in (2, 3, CAP)]
            assert gov.autotune_snapshot()["evidence"] == "backlog"

            # a live stream after it: a knob left at the cap only raises a
            # budget that no read reaches, and a read that ends at the LSO
            # is no evidence
            del engine.launches[:]
            for k in range(2 * K):
                for part in range(PARTITIONS):
                    await _append(broker, "src", part, _docs(DOCS))
                assert await ctx.tick() is True and ctx._ahead is None
            assert [_batches_a_partition(launch) for launch in engine.launches] == [1] * 2 * K
            assert len(gov._journal.entries(domain="admission")) == 3
        finally:
            await _stop(storage, server, api)

    run(main())


def test_a_live_trickle_on_the_host_lane_never_moves_the_knob(tmp_path):
    async def main():
        storage, broker, server, api = await _start(tmp_path)
        try:
            engine = api.pacemaker.engine
            ctx, gov, legs = await _on_the_host_lane(api, broker)
            for k in range(12):
                # now one batch a partition, now two: each read ends at the LSO
                for part in range(PARTITIONS):
                    for j in range(1 + k % 2):
                        await _append(broker, "src", part, _docs(DOCS))
                assert await ctx.tick() is True and ctx._ahead is None
            assert _drained(ctx, batches=1 + 6 * 1 + 6 * 2)
            assert len(engine.launches) == 12 and legs.count == 0
            snap = gov.autotune_snapshot()
            assert (snap["group_ticks"], snap["launch_depth"], snap["evidence"]) == (1, 4, None)
            assert gov._journal.entries(domain="admission") == []
        finally:
            await _stop(storage, server, api)

    run(main())


# ------------------------------------------------------------ the live linger
def test_a_live_tick_lingers_as_long_as_it_took_and_a_backlog_does_not(tmp_path):
    """A tick whose reads all ended at the log's end leaves its own
    duration in ``_linger_s``; one that the byte budget cut short of the
    LSO (a backlog) leaves 0.0; a tick that found nothing leaves what the
    fiber set before it."""

    async def main():
        storage, broker, server, api = await _start(tmp_path)
        try:
            ctx = await _parked(api, broker)
            for part in range(PARTITIONS):
                await _append(broker, "src", part, _docs(DOCS))
            h0 = _hist("tick")
            assert await ctx.tick()
            h1 = _hist("tick")
            # the tick's own sample, to the microsecond the histogram keeps
            assert ctx._linger_s > 0.0
            assert int(ctx._linger_s * 1e6) == h1[1] - h0[1]
            # a backlog: two batches a partition, one a read
            ctx._linger_s = 0.0
            for part in range(PARTITIONS):
                await _append(broker, "src", part, _docs(DOCS, base=100))
                await _append(broker, "src", part, _docs(DOCS, base=200))
            assert await ctx.tick()
            assert ctx._linger_s == 0.0
            # the rest of it: the read ends at the LSO again
            assert await ctx.tick()
            assert ctx._linger_s > 0.0
            # nothing to read: no launch, nothing set
            ctx._linger_s = 0.0
            assert not await ctx.tick()
            assert ctx._linger_s == 0.0
        finally:
            await _stop(storage, server, api)

    run(main())


@pytest.mark.parametrize("stream", ["live", "backlog"])
def test_the_fiber_waits_out_a_live_ticks_linger_before_it_reads_again(tmp_path, stream):
    """The fiber's loop: after a live tick that took ~0.3 s (a held engine)
    the next launch's input is read no sooner than that long after the tick
    ended (``idle_sleep_s`` permitting), though it lay in the log all the
    while; over a backlog the next tick follows at once. Read from the
    ``tick`` and ``gap`` samples the fiber itself records."""

    async def main():
        storage, broker, server, api = await _start(tmp_path)
        try:
            pm, engine = api.pacemaker, api.pacemaker.engine
            await broker.create_topic(TopicConfig("src", PARTITIONS))
            await _deployed(api, "proj")
            pm.idle_sleep_s = 5.0  # the cap is not what this test reads
            await asyncio.sleep(0.2)  # the fiber has found nothing and sleeps
            engine.waiting.clear()
            engine.door.clear()
            tick0, gap0 = _hist("tick"), _hist("gap")
            for part in range(PARTITIONS):
                for k in range(1 if stream == "live" else 2):
                    await _append(broker, "src", part, _docs(DOCS, base=100 * k))
            await wait_until(engine.waiting.is_set, timeout=30.0, msg="first submit")
            if stream == "live":
                # the second launch's input arrives while the first is held
                for part in range(PARTITIONS):
                    await _append(broker, "src", part, _docs(DOCS, base=500))
            await asyncio.sleep(0.3)
            engine.door.set()
            await wait_until(lambda: _hist("tick")[0] > tick0[0], msg="first tick")
            tick1 = _hist("tick")
            await wait_until(lambda: _hist("gap")[0] > gap0[0], msg="second tick")
            gap1 = _hist("gap")
            first_tick_s = (tick1[1] - tick0[1]) / (tick1[0] - tick0[0]) / 1e6
            gap_s = (gap1[1] - gap0[1]) / (gap1[0] - gap0[0]) / 1e6
            if stream == "live":
                assert tick1[0] - tick0[0] == 1 and first_tick_s >= 0.3
                assert gap_s >= 0.9 * first_tick_s, (gap_s, first_tick_s)
            else:
                assert gap_s < 0.1, gap_s
        finally:
            await _stop(storage, server, api)

    run(main())
