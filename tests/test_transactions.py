"""Idempotence + transaction tests.

Mirrors cluster/tests rm_stm/tm_stm unit tests and the ducktape
tx_verifier_test.py acceptance shape: idempotent dedup, epoch fencing,
transactional produce gating, commit/abort visibility under
read_committed, EOS consume-transform-produce offsets, coordinator
restart recovery.
"""

from __future__ import annotations

import asyncio

import pytest

from redpanda_tpu.kafka.client.client import KafkaClient
from redpanda_tpu.kafka.client.producer import TransactionalProducer
from redpanda_tpu.kafka.protocol import messages as m
from redpanda_tpu.kafka.protocol.errors import ErrorCode, KafkaError
from redpanda_tpu.kafka.server.broker import Broker, BrokerConfig
from redpanda_tpu.kafka.server.protocol import KafkaServer
from redpanda_tpu.storage.log_manager import StorageApi


def run(coro):
    asyncio.run(coro)


async def _start_broker(tmp_path, **kw):
    storage = await StorageApi(str(tmp_path)).start()
    cfg = BrokerConfig(data_dir=str(tmp_path), **kw)
    broker = Broker(cfg, storage)
    server = await KafkaServer(broker, "127.0.0.1", 0).start()
    cfg.advertised_port = server.port
    return broker, server


async def _stop(server, broker, *clients):
    for c in clients:
        await c.close()
    await server.stop()
    await broker.storage.stop()


def _values(batches):
    return [r.value for b in batches for r in b.records()]


def test_idempotent_dedup_and_sequencing(tmp_path):
    async def main():
        broker, server = await _start_broker(tmp_path)
        client = await KafkaClient([("127.0.0.1", server.port)]).connect()
        await client.create_topic("idem", partitions=1)
        prod = await TransactionalProducer(client).init()
        assert prod.producer_id >= 0 and prod.epoch == 0
        await prod.send("idem", 0, [b"a", b"b"])
        # duplicate batch (same sequence): broker acks without re-append
        prod._seqs[("idem", 0)] = 0
        await prod.send("idem", 0, [b"a", b"b"])
        batches, hwm = await client.fetch("idem", 0, 0)
        assert _values(batches) == [b"a", b"b"]
        assert hwm == 2
        # sequence gap rejected
        prod._seqs[("idem", 0)] = 10
        with pytest.raises(KafkaError) as ei:
            await prod.send("idem", 0, [b"x"])
        assert ei.value.code == ErrorCode.out_of_order_sequence_number
        await _stop(server, broker, client)

    run(main())


def test_tx_commit_and_abort_visibility(tmp_path):
    async def main():
        broker, server = await _start_broker(tmp_path)
        client = await KafkaClient([("127.0.0.1", server.port)]).connect()
        await client.create_topic("txv", partitions=1)
        prod = await TransactionalProducer(client, "tx-1").init()
        # committed tx
        prod.begin()
        await prod.send("txv", 0, [b"c1", b"c2"])
        await prod.commit()
        # aborted tx
        prod.begin()
        await prod.send("txv", 0, [b"a1", b"a2"])
        await prod.abort()
        # read_uncommitted sees data batches incl. aborted (not markers)
        ru, _ = await client.fetch("txv", 0, 0)
        ru_vals = [r.value for b in ru if not b.header.is_control for r in b.records()]
        assert ru_vals == [b"c1", b"c2", b"a1", b"a2"]
        # read_committed sees only the committed tx
        rc, _ = await client.fetch("txv", 0, 0, isolation_level=1)
        assert _values(rc) == [b"c1", b"c2"]
        await _stop(server, broker, client)

    run(main())


def test_transactional_produce_requires_add_partitions(tmp_path):
    async def main():
        broker, server = await _start_broker(tmp_path)
        client = await KafkaClient([("127.0.0.1", server.port)]).connect()
        await client.create_topic("gate", partitions=1)
        prod = await TransactionalProducer(client, "tx-gate").init()
        # bypass begin(): craft a transactional batch without AddPartitions
        from redpanda_tpu.models.record import Record, RecordBatch

        batch = RecordBatch.build(
            [Record(value=b"sneak")],
            producer_id=prod.producer_id,
            producer_epoch=prod.epoch,
            base_sequence=0,
            transactional=True,
        )
        with pytest.raises(KafkaError) as ei:
            await client.produce_batches("gate", 0, [batch])
        assert ei.value.code == ErrorCode.invalid_txn_state
        await _stop(server, broker, client)

    run(main())


def test_epoch_fencing(tmp_path):
    async def main():
        broker, server = await _start_broker(tmp_path)
        client = await KafkaClient([("127.0.0.1", server.port)]).connect()
        await client.create_topic("fence", partitions=1)
        old = await TransactionalProducer(client, "tx-f").init()
        old.begin()
        await old.send("fence", 0, [b"zombie-open"])
        # a new incarnation bumps the epoch and aborts the old open tx
        new = await TransactionalProducer(client, "tx-f").init()
        assert new.producer_id == old.producer_id
        assert new.epoch == old.epoch + 1
        # zombie's ops now fail with invalid_producer_epoch
        with pytest.raises(KafkaError) as ei:
            await old.commit()
        assert ei.value.code == ErrorCode.invalid_producer_epoch
        # the old tx was aborted: read_committed sees nothing
        rc, _ = await client.fetch("fence", 0, 0, isolation_level=1)
        assert _values(rc) == []
        # new incarnation can run a clean tx
        new.begin()
        await new.send("fence", 0, [b"fresh"])
        await new.commit()
        rc, _ = await client.fetch("fence", 0, 0, isolation_level=1)
        assert _values(rc) == [b"fresh"]
        await _stop(server, broker, client)

    run(main())


def test_eos_send_offsets(tmp_path):
    async def main():
        broker, server = await _start_broker(tmp_path)
        client = await KafkaClient([("127.0.0.1", server.port)]).connect()
        await client.create_topic("src", partitions=1)
        await client.create_topic("dst", partitions=1)
        await client.produce("src", 0, [b"in1", b"in2"])
        prod = await TransactionalProducer(client, "tx-eos").init()
        prod.begin()
        await prod.send("dst", 0, [b"out1", b"out2"])
        await prod.send_offsets("cg-eos", {("src", 0): 2})
        # offsets are NOT visible before commit
        conn = await client.any_connection()
        resp = await conn.request(m.OFFSET_FETCH, {
            "group_id": "cg-eos",
            "topics": [{"name": "src", "partition_indexes": [0]}],
        })
        assert resp["topics"][0]["partitions"][0]["committed_offset"] == -1
        await prod.commit()
        resp = await conn.request(m.OFFSET_FETCH, {
            "group_id": "cg-eos",
            "topics": [{"name": "src", "partition_indexes": [0]}],
        })
        assert resp["topics"][0]["partitions"][0]["committed_offset"] == 2
        rc, _ = await client.fetch("dst", 0, 0, isolation_level=1)
        assert _values(rc) == [b"out1", b"out2"]
        await _stop(server, broker, client)

    run(main())


def test_end_txn_retry_after_the_redrive_finished_it(tmp_path):
    """EndTxn's first attempt comes back retriable (the group-offset fold
    failed; the tx stays prepare_commit), the coordinator's own re-drive
    then completes the commit, and the client's retry arrives at a
    complete_commit tx: that retry is the success it asked for, not
    invalid_txn_state (tests/chaos/test_tx_chaos.py met this under load)."""

    async def main():
        broker, server = await _start_broker(tmp_path)
        client = await KafkaClient([("127.0.0.1", server.port)]).connect()
        await client.create_topic("src", partitions=1)
        await client.create_topic("dst", partitions=1)
        await client.produce("src", 0, [b"in1", b"in2"])
        prod = await TransactionalProducer(client, "tx-retry").init()
        prod.begin()
        await prod.send("dst", 0, [b"out1", b"out2"])
        await prod.send_offsets("cg-retry", {("src", 0): 2})

        co = broker.tx_coordinator
        fold, folds = co.router.commit_group_offsets, []

        async def flaky(group_id, commits):
            folds.append(group_id)
            if len(folds) == 1:
                return int(ErrorCode.coordinator_not_available)
            return await fold(group_id, commits)

        co.router.commit_group_offsets = flaky
        ident = ("tx-retry", prod.producer_id, prod.epoch)
        assert await co.end_txn(*ident, True) == ErrorCode.coordinator_not_available
        md = co._txs["tx-retry"]
        assert md.state.value == "PrepareCommit"
        await co._finish(md, True, redrive=True)  # what expire_stale drives
        assert md.state.value == "CompleteCommit" and len(folds) == 2
        # the client's retry, and one in the other direction
        assert await co.end_txn(*ident, True) == ErrorCode.none
        assert await co.end_txn(*ident, False) == ErrorCode.invalid_txn_state
        assert len(folds) == 2  # nothing was committed twice
        rc, _ = await client.fetch("dst", 0, 0, isolation_level=1)
        assert _values(rc) == [b"out1", b"out2"]
        conn = await client.any_connection()
        resp = await conn.request(m.OFFSET_FETCH, {
            "group_id": "cg-retry",
            "topics": [{"name": "src", "partition_indexes": [0]}],
        })
        assert resp["topics"][0]["partitions"][0]["committed_offset"] == 2
        await _stop(server, broker, client)

    run(main())


def test_lso_blocks_read_committed_until_end(tmp_path):
    async def main():
        broker, server = await _start_broker(tmp_path)
        client = await KafkaClient([("127.0.0.1", server.port)]).connect()
        await client.create_topic("lso", partitions=1)
        await client.produce("lso", 0, [b"plain"])
        prod = await TransactionalProducer(client, "tx-lso").init()
        prod.begin()
        await prod.send("lso", 0, [b"pending"])
        # open tx: read_committed stops at the tx's first offset
        rc, _ = await client.fetch("lso", 0, 0, isolation_level=1)
        assert _values(rc) == [b"plain"]
        await prod.commit()
        rc, _ = await client.fetch("lso", 0, 0, isolation_level=1)
        assert _values(rc) == [b"plain", b"pending"]
        await _stop(server, broker, client)

    run(main())


def test_multi_batch_request_and_partial_duplicate(tmp_path):
    async def main():
        from redpanda_tpu.models.record import Record, RecordBatch

        broker, server = await _start_broker(tmp_path)
        client = await KafkaClient([("127.0.0.1", server.port)]).connect()
        await client.create_topic("mb", partitions=1)
        prod = await TransactionalProducer(client).init()

        def batch(vals, seq):
            return RecordBatch.build(
                [Record(value=v, offset_delta=i) for i, v in enumerate(vals)],
                producer_id=prod.producer_id, producer_epoch=prod.epoch,
                base_sequence=seq,
            )

        # two consecutive-sequence batches in ONE request must both land
        await client.produce_batches("mb", 0, [batch([b"a", b"b"], 0), batch([b"c"], 2)])
        batches, hwm = await client.fetch("mb", 0, 0)
        assert _values(batches) == [b"a", b"b", b"c"] and hwm == 3
        # retry carrying one already-appended batch + one new one: the
        # duplicate is skipped, the new batch still lands (no silent drop)
        await client.produce_batches("mb", 0, [batch([b"c"], 2), batch([b"d"], 3)])
        batches, hwm = await client.fetch("mb", 0, 0)
        assert _values(batches) == [b"a", b"b", b"c", b"d"] and hwm == 4
        await _stop(server, broker, client)

    run(main())


def test_new_producer_in_request_duplicate(tmp_path):
    """A brand-new pid's first request carrying a retried copy of its own
    batch must still dedup (the sim map applies even with no stored state)."""

    async def main():
        from redpanda_tpu.models.record import Record, RecordBatch

        broker, server = await _start_broker(tmp_path)
        client = await KafkaClient([("127.0.0.1", server.port)]).connect()
        await client.create_topic("nd", partitions=1)
        prod = await TransactionalProducer(client).init()

        def batch(vals, seq):
            return RecordBatch.build(
                [Record(value=v, offset_delta=i) for i, v in enumerate(vals)],
                producer_id=prod.producer_id, producer_epoch=prod.epoch,
                base_sequence=seq,
            )

        await client.produce_batches("nd", 0, [batch([b"a", b"b"], 0), batch([b"a", b"b"], 0)])
        batches, hwm = await client.fetch("nd", 0, 0)
        assert _values(batches) == [b"a", b"b"] and hwm == 2
        await _stop(server, broker, client)

    run(main())


def test_tx_timeout_auto_aborts(tmp_path):
    async def main():
        broker, server = await _start_broker(tmp_path)
        broker.tx_coordinator.expire_interval_s = 0.05
        client = await KafkaClient([("127.0.0.1", server.port)]).connect()
        await client.create_topic("to", partitions=1)
        prod = await TransactionalProducer(client, "tx-to", timeout_ms=150).init()
        prod.begin()
        await prod.send("to", 0, [b"will-abort"])
        # producer goes silent; the coordinator's expiry fiber aborts the tx
        deadline = asyncio.get_event_loop().time() + 5.0
        while asyncio.get_event_loop().time() < deadline:
            rc, _ = await client.fetch("to", 0, 0, isolation_level=1)
            md = broker.tx_coordinator._txs.get("tx-to")
            if md is not None and md.state.value == "CompleteAbort":
                break
            await asyncio.sleep(0.05)
        assert broker.tx_coordinator._txs["tx-to"].state.value == "CompleteAbort"
        rc, _ = await client.fetch("to", 0, 0, isolation_level=1)
        assert _values(rc) == []
        await _stop(server, broker, client)

    run(main())


def test_tx_state_survives_restart(tmp_path):
    async def main():
        broker, server = await _start_broker(tmp_path)
        client = await KafkaClient([("127.0.0.1", server.port)]).connect()
        await client.create_topic("dur", partitions=1)
        prod = await TransactionalProducer(client, "tx-dur").init()
        prod.begin()
        await prod.send("dur", 0, [b"uncommitted"])
        await _stop(server, broker, client)  # crash with tx open

        broker2, server2 = await _start_broker(tmp_path)
        client2 = await KafkaClient([("127.0.0.1", server2.port)]).connect()
        # rm_stm recovery: the tx is still open, LSO still clamps
        rc, _ = await client2.fetch("dur", 0, 0, isolation_level=1)
        assert _values(rc) == []
        # new incarnation fences + aborts it, then commits fresh data
        prod2 = await TransactionalProducer(client2, "tx-dur").init()
        assert prod2.epoch >= 1
        prod2.begin()
        await prod2.send("dur", 0, [b"fresh"])
        await prod2.commit()
        rc, _ = await client2.fetch("dur", 0, 0, isolation_level=1)
        assert _values(rc) == [b"fresh"]
        await _stop(server2, broker2, client2)

    run(main())
