"""Consumer-group tests.

Mirrors kafka/server/tests group tests + ducktape group_membership_test.py:
join/sync rebalance barrier, generation bumps, heartbeat-driven rebalance
signaling, session-timeout eviction, offset commit/fetch + persistence
across broker restart, describe/list/delete, and the group-aware client
consumer with range assignment.
"""

from __future__ import annotations

import asyncio

import pytest

from redpanda_tpu.kafka.client.client import KafkaClient
from redpanda_tpu.kafka.client.consumer import (
    GroupConsumer,
    decode_assignment,
    encode_assignment,
    encode_subscription,
    range_assign,
)
from redpanda_tpu.kafka.protocol import messages as m
from redpanda_tpu.kafka.protocol.errors import ErrorCode
from redpanda_tpu.kafka.server.broker import Broker, BrokerConfig
from redpanda_tpu.kafka.server.group import Group, GroupState
from redpanda_tpu.kafka.server.protocol import KafkaServer
from redpanda_tpu.storage.log_manager import StorageApi


def run(coro):
    asyncio.run(coro)


async def wait_until(pred, timeout=8.0, interval=0.02, msg=""):
    deadline = asyncio.get_event_loop().time() + timeout
    while not pred():
        if asyncio.get_event_loop().time() > deadline:
            raise AssertionError(f"timeout: {msg}")
        await asyncio.sleep(interval)


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


# ------------------------------------------------------------------ unit: state machine
def test_group_join_sync_rebalance_cycle():
    async def main():
        g = Group("g1")
        j1_task = asyncio.create_task(g.join("", None, "c1", "h1", 30000, 30000, "consumer", [("range", b"m1")]))
        await asyncio.sleep(0.05)
        assert g.state == GroupState.preparing_rebalance
        j2_task = asyncio.create_task(g.join("", None, "c2", "h2", 30000, 30000, "consumer", [("range", b"m2")]))
        j1, j2 = await asyncio.gather(j1_task, j2_task)
        assert j1["error_code"] == 0 and j2["error_code"] == 0
        assert j1["generation_id"] == j2["generation_id"] == 1
        leader_resp = j1 if j1["leader"] == j1["member_id"] else j2
        follower_resp = j2 if leader_resp is j1 else j1
        assert len(leader_resp["members"]) == 2
        assert follower_resp["members"] == []
        # sync: follower parks until the leader distributes
        f_sync = asyncio.create_task(
            g.sync(follower_resp["member_id"], 1, [])
        )
        await asyncio.sleep(0.02)
        assert not f_sync.done()
        assignments = [
            {"member_id": leader_resp["member_id"], "assignment": b"A-lead"},
            {"member_id": follower_resp["member_id"], "assignment": b"A-follow"},
        ]
        l_sync = await g.sync(leader_resp["member_id"], 1, assignments)
        assert l_sync == {"error_code": 0, "assignment": b"A-lead"}
        assert (await f_sync)["assignment"] == b"A-follow"
        assert g.state == GroupState.stable
        # heartbeat ok at current generation; stale generation rejected
        assert g.heartbeat(leader_resp["member_id"], 1) == ErrorCode.none
        assert g.heartbeat(leader_resp["member_id"], 0) == ErrorCode.illegal_generation
        # a new join triggers rebalance; heartbeats start signaling it
        j3_task = asyncio.create_task(g.join("", None, "c3", "h3", 30000, 30000, "consumer", [("range", b"m3")]))
        await asyncio.sleep(0.02)
        assert g.heartbeat(leader_resp["member_id"], 1) == ErrorCode.rebalance_in_progress
        # others rejoin -> generation 2 completes with 3 members
        j1b = asyncio.create_task(g.join(leader_resp["member_id"], None, "c1", "h1", 30000, 30000, "consumer", [("range", b"m1")]))
        j2b = asyncio.create_task(g.join(follower_resp["member_id"], None, "c2", "h2", 30000, 30000, "consumer", [("range", b"m2")]))
        r3, r1b, r2b = await asyncio.gather(j3_task, j1b, j2b)
        assert {r["generation_id"] for r in (r3, r1b, r2b)} == {2}
        assert len(g.members) == 3
        g.shutdown()

    run(main())


def test_group_session_timeout_eviction():
    async def main():
        g = Group("g2")
        j = asyncio.create_task(g.join("", None, "c1", "h", 50, 100, "consumer", [("range", b"")]))
        r = await j
        mid = r["member_id"]
        await g.sync(mid, r["generation_id"], [{"member_id": mid, "assignment": b"x"}])
        assert g.state == GroupState.stable
        await asyncio.sleep(0.12)  # session_timeout=50ms
        assert g.expire_members()
        assert g.state == GroupState.empty and not g.members
        g.shutdown()

    run(main())


def test_rebalance_timeout_evicts_stragglers():
    async def main():
        g = Group("g3")
        j1 = asyncio.create_task(g.join("", None, "c1", "h", 30000, 200, "consumer", [("range", b"")]))
        j2 = asyncio.create_task(g.join("", None, "c2", "h", 30000, 200, "consumer", [("range", b"")]))
        r1, r2 = await asyncio.gather(j1, j2)
        gen = r1["generation_id"]
        # member 2 triggers rebalance by rejoining; member 1 never rejoins
        j2b = asyncio.create_task(g.join(r2["member_id"], None, "c2", "h", 30000, 200, "consumer", [("range", b"")]))
        r2b = await j2b  # resolves after rebalance timeout evicts member 1
        assert r2b["error_code"] == 0
        assert r2b["generation_id"] == gen + 1
        assert len(g.members) == 1
        g.shutdown()

    run(main())


# ------------------------------------------------------------------ assignment plan
def test_range_assignment_plan():
    members = [("m1", ["t"]), ("m2", ["t"]), ("m3", ["u"])]
    plan = range_assign(members, {"t": 5, "u": 2})
    assert plan["m1"]["t"] == [0, 1, 2]
    assert plan["m2"]["t"] == [3, 4]
    assert plan["m3"]["u"] == [0, 1]
    blob = encode_assignment(plan["m1"])
    assert decode_assignment(blob) == {"t": [0, 1, 2]}


# ------------------------------------------------------------------ wire e2e
def test_e2e_group_consume_rebalance(tmp_path):
    async def main():
        broker, server = await _start_broker(tmp_path, default_partitions=4)
        client = await KafkaClient([("127.0.0.1", server.port)]).connect()
        await client.create_topic("gt", partitions=4)
        for p in range(4):
            await client.produce("gt", p, [b"p%d-%d" % (p, i) for i in range(3)])
        c1 = await GroupConsumer(client, "workers", ["gt"], session_timeout_ms=2000, heartbeat_interval_s=0.1).join()
        # single member owns all partitions
        assert sorted(c1.assignment["gt"]) == [0, 1, 2, 3]
        got = await c1.poll()
        assert sum(len(v) for v in got.values()) == 12
        await c1.commit()
        # second member joins; first notices via heartbeat and rejoins
        client2 = await KafkaClient([("127.0.0.1", server.port)]).connect()
        c2_join = asyncio.create_task(
            GroupConsumer(client2, "workers", ["gt"], session_timeout_ms=2000, heartbeat_interval_s=0.1).join()
        )
        await wait_until(lambda: c1.rejoin_needed, msg="rebalance signal via heartbeat")
        await c1.join()
        c2 = await c2_join
        owned = sorted(c1.assignment.get("gt", []) + c2.assignment.get("gt", []))
        assert owned == [0, 1, 2, 3]
        assert c1.assignment["gt"] and c2.assignment["gt"]
        # committed offsets survived the rebalance: no duplicates on poll
        got1 = await c1.poll()
        got2 = await c2.poll()
        assert sum(len(v) for v in got1.values()) + sum(len(v) for v in got2.values()) == 0
        await c1.leave()
        await c2.leave()
        await _stop(server, broker, client, client2)

    run(main())


def test_offsets_persist_across_restart(tmp_path):
    async def main():
        broker, server = await _start_broker(tmp_path)
        client = await KafkaClient([("127.0.0.1", server.port)]).connect()
        await client.create_topic("pt", partitions=1)
        await client.produce("pt", 0, [b"a", b"b", b"c"])
        conn = await client.any_connection()
        # simple offset storage (no membership)
        resp = await conn.request(m.OFFSET_COMMIT, {
            "group_id": "standalone", "generation_id": -1, "member_id": "",
            "group_instance_id": None, "retention_time_ms": -1,
            "topics": [{"name": "pt", "partitions": [
                {"partition_index": 0, "committed_offset": 2,
                 "committed_leader_epoch": -1, "committed_metadata": "meta"}]}],
        })
        assert resp["topics"][0]["partitions"][0]["error_code"] == 0
        await _stop(server, broker, client)

        # restart on the same data dir: offsets recovered from group topic
        broker2, server2 = await _start_broker(tmp_path)
        client2 = await KafkaClient([("127.0.0.1", server2.port)]).connect()
        conn2 = await client2.any_connection()
        resp = await conn2.request(m.OFFSET_FETCH, {
            "group_id": "standalone",
            "topics": [{"name": "pt", "partition_indexes": [0]}],
        })
        p0 = resp["topics"][0]["partitions"][0]
        assert p0["committed_offset"] == 2
        assert p0["metadata"] == "meta"
        await _stop(server2, broker2, client2)

    run(main())


def test_topic_config_survives_restart(tmp_path):
    async def main():
        broker, server = await _start_broker(tmp_path)
        client = await KafkaClient([("127.0.0.1", server.port)]).connect()
        await client.create_topic(
            "cfged", partitions=2,
            configs={"cleanup.policy": "compact", "retention.ms": "60000"},
        )
        await _stop(server, broker, client)
        broker2, server2 = await _start_broker(tmp_path)
        md = broker2.topic_table.get("cfged")
        assert md is not None and md.config.partition_count == 2
        assert md.config.cleanup_policy == "compact"
        assert md.config.retention_ms == 60000
        await _stop(server2, broker2)

    run(main())


def test_internal_topic_name_rejected(tmp_path):
    async def main():
        broker, server = await _start_broker(tmp_path)
        client = await KafkaClient([("127.0.0.1", server.port)]).connect()
        with pytest.raises(Exception):
            await client.create_topic("__consumer_offsets", partitions=1)
        await _stop(server, broker, client)

    run(main())


def test_group_admin_apis(tmp_path):
    async def main():
        broker, server = await _start_broker(tmp_path, default_partitions=1)
        client = await KafkaClient([("127.0.0.1", server.port)]).connect()
        await client.create_topic("at", partitions=1)
        c1 = await GroupConsumer(client, "admin-g", ["at"], heartbeat_interval_s=5).join()
        conn = await client.any_connection()
        # describe
        resp = await conn.request(m.DESCRIBE_GROUPS, {"groups": ["admin-g"]})
        gd = resp["groups"][0]
        assert gd["error_code"] == 0
        assert gd["group_state"] == "Stable"
        assert gd["protocol_type"] == "consumer"
        assert gd["protocol_data"] == "range"
        assert len(gd["members"]) == 1
        # list
        resp = await conn.request(m.LIST_GROUPS, {})
        assert any(g["group_id"] == "admin-g" for g in resp["groups"])
        # delete fails while non-empty, works after leave
        resp = await conn.request(m.DELETE_GROUPS, {"groups_names": ["admin-g"]})
        assert resp["results"][0]["error_code"] == int(ErrorCode.non_empty_group)
        await c1.leave()
        resp = await conn.request(m.DELETE_GROUPS, {"groups_names": ["admin-g"]})
        assert resp["results"][0]["error_code"] == 0
        await _stop(server, broker, client)

    run(main())


def test_find_coordinator_and_group_topic(tmp_path):
    async def main():
        broker, server = await _start_broker(tmp_path)
        client = await KafkaClient([("127.0.0.1", server.port)]).connect()
        conn = await client.any_connection()
        resp = await conn.request(m.FIND_COORDINATOR, {"key": "some-group", "key_type": 0})
        assert resp["error_code"] == 0
        assert resp["node_id"] == broker.config.node_id
        assert resp["port"] == server.port
        # the group metadata topic was created on demand
        assert broker.topic_table.contains("__consumer_offsets")
        md = broker.topic_table.get("__consumer_offsets")
        assert md.config.cleanup_policy == "compact"
        await _stop(server, broker, client)

    run(main())


def test_simple_commit_rejected_on_live_group():
    """ADVICE round 1: generation<0 commits (simple clients) are only legal
    while the group is Empty (group.cc:1920); a live group's offsets must
    not be overwritable by non-members. The tx coordinator's staged-offset
    apply uses the internal trusted flag instead."""
    async def main():
        from redpanda_tpu.kafka.server.group import Group, GroupState, OffsetCommit
        from redpanda_tpu.kafka.protocol.errors import ErrorCode as E

        g = Group("g1", initial_rebalance_delay_s=0)
        commits = {("t", 0): OffsetCommit(5)}
        # Empty: accepted
        assert g.commit_offsets("", -1, commits) == E.none
        # Fake a live group
        g.state = GroupState.stable
        g.generation = 3
        bad = {("t", 0): OffsetCommit(999)}
        assert g.commit_offsets("", -1, bad) == E.illegal_generation
        assert g.offsets[("t", 0)].offset == 5
        # trusted path (tx coordinator) still lands
        assert g.commit_offsets("", -1, bad, trusted=True) == E.none
        assert g.offsets[("t", 0)].offset == 999

    run(main())


def test_group_topic_compaction_shrinks_and_replays(tmp_path):
    """Round-1 acceptance: a group topic with many commits for the
    same key compacts down to live keys only, and a restart replays the
    compacted log to the correct offsets."""
    async def main():
        from redpanda_tpu.models.fundamental import NTP
        from redpanda_tpu.kafka.server.group_manager import GROUP_TOPIC

        broker, server = await _start_broker(tmp_path)
        client = await KafkaClient([("127.0.0.1", server.port)]).connect()
        await client.create_topic("pt", partitions=1)
        await client.produce("pt", 0, [b"x"])
        conn = await client.any_connection()
        for committed in range(1, 201):  # 200 commits, same (group, tp) key
            resp = await conn.request(m.OFFSET_COMMIT, {
                "group_id": "g-compact", "generation_id": -1, "member_id": "",
                "group_instance_id": None, "retention_time_ms": -1,
                "topics": [{"name": "pt", "partitions": [
                    {"partition_index": 0, "committed_offset": committed,
                     "committed_leader_epoch": -1, "committed_metadata": None}]}],
            })
            assert resp["topics"][0]["partitions"][0]["error_code"] == 0

        # find the group-topic partition holding this group and compact it
        logs = [
            log for ntp, log in broker.storage.log_mgr.logs().items()
            if ntp.topic == GROUP_TOPIC
        ]
        glogs = [log for log in logs if log.offsets().dirty_offset >= 0]
        assert glogs, "group topic has no data"
        glog = max(glogs, key=lambda l: l.offsets().dirty_offset)
        # roll the active segment so commits become compactible, then compact
        async with glog._lock:
            glog.segments[-1].release_appender()
        before, after = await glog.compact()
        assert after < before, (before, after)
        # only the live key survives in the closed segments
        n_records = sum(
            b.header.record_count for b in await glog.read(0, 1 << 30)
        )
        assert n_records <= 2  # latest commit (+ maybe group metadata)
        await _stop(server, broker, client)

        # restart: replay of the compacted log yields the last commit
        broker2, server2 = await _start_broker(tmp_path)
        client2 = await KafkaClient([("127.0.0.1", server2.port)]).connect()
        conn2 = await client2.any_connection()
        resp = await conn2.request(m.OFFSET_FETCH, {
            "group_id": "g-compact",
            "topics": [{"name": "pt", "partition_indexes": [0]}],
        })
        p0 = resp["topics"][0]["partitions"][0]
        assert p0["committed_offset"] == 200
        await _stop(server2, broker2, client2)

    run(main())
