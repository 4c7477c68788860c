"""The fetch handler's long-poll gate: a fetch whose ``min_bytes`` is unmet
parks on the requested partitions' commit advance, with the request's own
``max_wait_ms`` as its only timer. Driven through ``handle_fetch`` itself
(no socket), on a direct log and on a one-voter raft group."""

from __future__ import annotations

import asyncio
import statistics
import time
import types

import pytest

from redpanda_tpu import rpc
from redpanda_tpu.cluster.partition import ConsistencyLevel, Partition
from redpanda_tpu.cluster.topic_table import TopicConfig
from redpanda_tpu.config.properties import Configuration
from redpanda_tpu.kafka.protocol import messages as m
from redpanda_tpu.kafka.protocol.batch import decode_wire_batches
from redpanda_tpu.kafka.protocol.errors import ErrorCode as E
from redpanda_tpu.kafka.protocol.schema import RequestHeader
from redpanda_tpu.kafka.server.broker import Broker, BrokerConfig
from redpanda_tpu.kafka.server.handlers import handle_fetch
from redpanda_tpu.kafka.server.protocol import RequestContext
from redpanda_tpu.metrics import registry
from redpanda_tpu.models.fundamental import NTP
from redpanda_tpu.models.record import Record, RecordBatch
from redpanda_tpu.observability import probes
from redpanda_tpu.raft import GroupManager, OffsetMonitor, RaftError, RaftTimings, VNode
from redpanda_tpu.storage.log_manager import StorageApi

TOPIC = "lp"
PARTS = 8


def run(coro, limit_s=60.0):
    asyncio.run(asyncio.wait_for(coro, limit_s))


class Fixture:
    """One broker leading ``PARTS`` partitions of one topic: direct logs
    (what a materialized topic is), or one-voter raft groups."""

    def __init__(self, tmp_path, kind: str):
        self.dir, self.kind = str(tmp_path), kind
        self.gm = None

    async def __aenter__(self) -> "Fixture":
        self.storage = await StorageApi(self.dir).start()
        self.broker = Broker(BrokerConfig(data_dir=self.dir), self.storage)
        if self.kind == "direct":
            await self.broker.create_topic(TopicConfig(TOPIC, PARTS))
            return self
        me = VNode(0, 0)
        self.connections = rpc.ConnectionCache()
        self.gm = GroupManager(
            me, self.storage, self.connections,
            timings=RaftTimings(election_timeout_ms=100.0, heartbeat_interval_ms=25.0),
        )
        await self.gm.start()
        for p in range(PARTS):
            ntp = NTP.kafka(TOPIC, p)
            c = await self.gm.create_group(p + 1, ntp, [me])
            part = await Partition(ntp, c, c.log, kvs=self.storage.kvs).start()
            self.broker.partition_manager.attach(ntp, part)
        for p in range(PARTS):
            await self.settled(p)
        return self

    async def __aexit__(self, *exc) -> None:
        if self.gm is not None:
            await self.gm.stop()
            await self.connections.close()
        await self.storage.stop()

    def part(self, p: int) -> Partition:
        return self.broker.get_partition(TOPIC, p)

    async def settled(self, p: int) -> None:
        deadline = time.monotonic() + 10.0
        while not self.part(p).ready_for_reads():
            assert time.monotonic() < deadline, f"partition {p} has no settled leader"
            await asyncio.sleep(0.01)

    def waiters(self) -> int:
        return sum(
            len(part.consensus._commit_monitor._waiters)
            for part in map(self.part, range(PARTS)) if part is not None
        )

    async def append(self, p: int, n_bytes: int = 64, level=ConsistencyLevel.quorum_ack):
        batch = RecordBatch.build([Record(value=b"x" * n_bytes)])
        return await self.part(p).replicate([batch], level)

    def fetch(self, offsets: dict[int, int], *, max_wait_ms: int, min_bytes: int = 1,
              isolation_level: int = 0) -> asyncio.Task:
        request = {
            "replica_id": -1, "max_wait_ms": max_wait_ms, "min_bytes": min_bytes,
            "max_bytes": 1 << 20, "isolation_level": isolation_level,
            "session_id": 0, "session_epoch": -1, "forgotten_topics_data": [],
            "topics": [{"name": TOPIC, "partitions": [
                {"partition_index": p, "fetch_offset": off, "partition_max_bytes": 1 << 20}
                for p, off in offsets.items()
            ]}],
        }
        conn = types.SimpleNamespace(authenticated_principal=None, client_host="127.0.0.1")
        ctx = RequestContext(self.broker, RequestHeader(m.FETCH, 11, 1, "test"), request, conn)
        return asyncio.create_task(handle_fetch(ctx))

    def tails(self) -> dict[int, int]:
        return {p: self.part(p).high_watermark for p in range(PARTS)}

    async def parked(self, n: int = 1) -> None:
        for _ in range(200):
            if self.waiters() >= n:
                return
            await asyncio.sleep(0)
        raise AssertionError(f"{self.waiters()} waiters, expected {n}")


def _partitions(resp: dict) -> dict[int, dict]:
    return {p["partition_index"]: p for p in resp["responses"][0]["partitions"]}


def _batches(presp: dict) -> list[RecordBatch]:
    return [a.batch for a in decode_wire_batches(presp["records"] or b"")]


def _parks() -> dict[str, float]:
    return {end: c.value for end, c in probes.kafka_fetch_parks.items()}


KINDS = pytest.mark.parametrize("kind", ["direct", "raft"])


# (a)
@KINDS
def test_a_commit_on_any_of_eight_partitions_answers_the_parked_fetch(tmp_path, kind):
    async def main():
        async with Fixture(tmp_path, kind) as fx:
            parks = _parks()
            waits = []
            for p in range(PARTS):  # one round a partition: each is the one that commits
                tails = fx.tails()
                poll = fx.fetch(tails, max_wait_ms=5000)
                await fx.parked(PARTS)
                await fx.append(p)
                committed = time.perf_counter()
                got = _partitions(await poll)
                waits.append(time.perf_counter() - committed)
                assert [q for q in got if got[q]["records"]] == [p]
                assert _batches(got[p])[0].base_offset == tails[p]
                assert fx.waiters() == 0
            # the old gate re-checked every 20 ms: a median near 10 ms
            assert statistics.median(waits) < 0.005, waits
            now = _parks()
            assert now["woken_by_commit"] == parks["woken_by_commit"] + PARTS
            assert now["deadline"] == parks["deadline"]

    run(main())


# (b)
@KINDS
def test_an_idle_partition_answers_empty_at_max_wait_and_counts_a_deadline(tmp_path, kind):
    async def main():
        async with Fixture(tmp_path, kind) as fx:
            parks = _parks()
            wake = probes.kafka_fetch_wake_hist.hist.count
            t0 = time.perf_counter()
            got = _partitions(await fx.fetch(fx.tails(), max_wait_ms=150))
            took = time.perf_counter() - t0
            assert 0.150 <= took < 0.150 + 0.1, took
            assert all(p["error_code"] == 0 and not p["records"] for p in got.values())
            now = _parks()
            assert now["deadline"] == parks["deadline"] + 1
            assert now["woken_by_commit"] == parks["woken_by_commit"]
            assert probes.kafka_fetch_wake_hist.hist.count == wake  # no data: no sample
            assert fx.waiters() == 0
            # max_wait_ms 0 never parks, and so counts nothing
            await fx.fetch(fx.tails(), max_wait_ms=0)
            assert _parks() == now

    run(main())


# (c)
@KINDS
def test_no_waiter_is_left_however_the_long_polls_end(tmp_path, kind):
    async def main():
        async with Fixture(tmp_path, kind) as fx:
            tails = fx.tails()
            for _ in range(1000):
                await fx.fetch(tails, max_wait_ms=1)
            assert fx.waiters() == 0
            poll = fx.fetch(tails, max_wait_ms=5000)
            await fx.parked(PARTS)
            poll.cancel()  # what a closing server does to a connection's handlers
            with pytest.raises(asyncio.CancelledError):
                await poll
            assert fx.waiters() == 0
            # and the partitions still wake the next one
            poll = fx.fetch(tails, max_wait_ms=5000)
            await fx.parked(PARTS)
            await fx.append(5)
            assert _partitions(await poll)[5]["records"]
            assert fx.waiters() == 0

    run(main(), 120.0)


# (d)
@pytest.mark.parametrize("second_commit", [True, False])
def test_min_bytes_keeps_the_fetch_parked_across_a_first_commit(tmp_path, second_commit):
    async def main():
        async with Fixture(tmp_path, "direct") as fx:
            parks = _parks()
            t0 = time.perf_counter()
            poll = fx.fetch({0: 0, 1: 0}, max_wait_ms=300, min_bytes=300)
            await fx.parked(2)
            await fx.append(0, 100)  # one batch: ~170 B on the wire
            await asyncio.sleep(0.05)
            assert not poll.done()  # woken, read, still short: parked again
            if second_commit:
                await fx.append(1, 200)
                got = _partitions(await poll)
                assert time.perf_counter() - t0 < 0.25
                assert got[0]["records"] and got[1]["records"]
                assert _parks()["woken_by_commit"] == parks["woken_by_commit"] + 1
            else:
                got = _partitions(await poll)
                assert 0.300 <= time.perf_counter() - t0 < 0.40
                # at the deadline: what there is
                assert len(_batches(got[0])) == 1 and not got[1]["records"]
                assert _parks()["deadline"] == parks["deadline"] + 1
            assert fx.waiters() == 0

    run(main())


# (e)
def test_a_step_down_while_parked_answers_not_leader_before_the_deadline(tmp_path):
    async def main():
        async with Fixture(tmp_path, "raft") as fx:
            t0 = time.perf_counter()
            poll = fx.fetch(fx.tails(), max_wait_ms=5000)
            await fx.parked(PARTS)
            c = fx.part(3).consensus
            await c._step_down(c.term + 1)
            got = _partitions(await poll)
            assert time.perf_counter() - t0 < 1.0
            assert got[3]["error_code"] == int(E.not_leader_for_partition)
            assert all(got[p]["error_code"] == 0 for p in got if p != 3)
            assert fx.waiters() == 0

    run(main())


def test_a_partition_removed_while_parked_answers_before_the_deadline(tmp_path):
    async def main():
        async with Fixture(tmp_path, "raft") as fx:
            t0 = time.perf_counter()
            poll = fx.fetch({2: fx.part(2).high_watermark}, max_wait_ms=5000)
            await fx.parked(1)
            # as the controller backend removes a replica: detach, stop the group
            fx.broker.partition_manager.detach(NTP.kafka(TOPIC, 2))
            await fx.gm.remove_group(3)
            got = await poll
            assert time.perf_counter() - t0 < 1.0
            assert _partitions(got)[2]["error_code"] == int(E.unknown_topic_or_partition)

    run(main())


# (f)
def test_nothing_above_the_high_watermark_is_returned_with_appends_in_flight(tmp_path):
    async def main():
        async with Fixture(tmp_path, "raft") as fx:
            part = fx.part(0)
            # leader_ack appends sit in the log, dirty, ahead of the commit
            # index: a one-voter group commits at its next quorum flush
            for _ in range(3):
                await fx.append(0, level=ConsistencyLevel.leader_ack)
            assert part.high_watermark == 0
            assert part.otl.to_kafka(part.consensus.dirty_offset) >= 2
            poll = fx.fetch({0: 0}, max_wait_ms=100)
            assert not _partitions(await poll)[0]["records"]  # parked to its deadline
            poll = fx.fetch({0: 0}, max_wait_ms=5000)
            await fx.parked(1)

            async def writer():
                for i in range(40):
                    level = ConsistencyLevel.quorum_ack if i % 4 == 3 else ConsistencyLevel.leader_ack
                    await fx.append(0, level=level)
                    await asyncio.sleep(0)

            w = asyncio.create_task(writer())
            offset = 0
            while offset < 43:
                got = _partitions(await poll)[0]
                assert got["error_code"] == 0
                for b in _batches(got):
                    assert b.base_offset == offset
                    assert b.last_offset < got["high_watermark"] <= part.high_watermark
                    offset = b.last_offset + 1
                poll = fx.fetch({0: offset}, max_wait_ms=5000)
            await w
            poll.cancel()

    run(main())


# read_committed: the LSO moves turns after the commit that wakes the fetch
def test_a_parked_read_committed_fetch_sees_a_transaction_only_once_it_has_ended(tmp_path):
    async def main():
        async with Fixture(tmp_path, "direct") as fx:
            part = fx.part(0)
            stm = await fx.broker.recovered_rm_stm(part)
            assert stm.begin_tx(7, 0) == E.none
            poll = fx.fetch({0: 0}, max_wait_ms=5000, isolation_level=1)
            await fx.parked(1)
            batch = RecordBatch.build(
                [Record(value=b"tx")], producer_id=7, producer_epoch=0,
                base_sequence=0, transactional=True,
            )
            code, _ = await stm.replicate([batch], ConsistencyLevel.quorum_ack)
            assert code == E.none
            await asyncio.sleep(0.05)
            # its commit woke the fetch; the open transaction clamps the LSO
            assert not poll.done() and part.high_watermark == 1
            t0 = time.perf_counter()
            assert await stm.end_tx(7, 0, commit=True) == E.none
            got = _partitions(await poll)[0]
            assert time.perf_counter() - t0 < 0.5  # the end of the tx wakes it, not the 5 s deadline
            assert [b.base_offset for b in _batches(got)] == [0, 1]  # data + marker
            assert got["last_stable_offset"] == 2
            assert fx.waiters() == 0 and not stm._lso_monitor._waiters

    run(main())


# the monitor itself
def test_offset_monitor_drops_a_waiter_that_timed_out_or_was_taken_back():
    async def main():
        a, b = OffsetMonitor(), OffsetMonitor()
        with pytest.raises(RaftError):
            await a.wait_for(5, current=0, timeout=0.01)
        assert a._waiters == []
        fut = asyncio.get_running_loop().create_future()
        wa, wb = a.watch(1, fut), b.watch(1, fut)  # one future, two monitors
        b.notify(3)
        assert await fut == 3 and b._waiters == [] and a._waiters == [wa]
        a.notify(9)  # a resolved future is left alone
        assert a._waiters == []
        a.unwatch(wa), b.unwatch(wb)  # already dropped: nothing to do
        wa = a.watch(20, asyncio.get_running_loop().create_future())
        a.unwatch(wa)
        assert a._waiters == []

    run(main())


def test_the_park_counter_is_exported_and_the_interval_is_gone():
    text = registry.render_prometheus()
    for end in ("woken_by_commit", "deadline"):
        assert f'redpanda_tpu_kafka_fetch_parks_total{{end="{end}"}}' in text
    assert not hasattr(BrokerConfig(), "fetch_poll_interval_s")
    assert "fetch_poll_interval_ms" not in {p.name for p in Configuration().properties()}
