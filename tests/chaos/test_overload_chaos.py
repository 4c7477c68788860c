"""Overload + leader-kill combined (ISSUE 13 admission chaos test).

A 3-node REAL-process cluster with a deliberately tiny budget plane takes
an open-loop produce flood past its capacity; mid-flood the partition
leader is SIGKILLed. The combined-failure contract: admission keeps
shedding with the retriable backpressure code (never silent queueing),
the flood rides through the failover, and at the end EVERY acked write is
present exactly once on the survivors while NO shed write is readable —
overload and elections may slow the cluster, they may never corrupt it.
"""

from __future__ import annotations

import asyncio
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chaos.harness import ProcCluster  # noqa: E402
from redpanda_tpu.kafka.client import KafkaClient  # noqa: E402
from redpanda_tpu.kafka.protocol.errors import ErrorCode, KafkaError  # noqa: E402

TOPIC = "overload-chaos"


def _run(coro):
    # the test's own limit: ~15-25 s on a busy box (cluster start, 5 s of
    # flood, read-back)
    return asyncio.run(asyncio.wait_for(coro, 120))


async def _flood(clients, stop, acked, shed, errors, partitions):
    """Open-loop flood: one task per arrival, never waiting on completions."""
    outstanding: set = set()
    seq = 0

    async def one(c, part, key, values):
        try:
            await c.produce(TOPIC, part, values, acks=-1)
            acked.add(key)
        except KafkaError as e:
            if e.code == ErrorCode.throttling_quota_exceeded:
                shed.add(key)
            else:
                errors.append(key)
        except Exception:
            errors.append(key)

    while not stop.is_set():
        for _ in range(24):  # a burst per 10ms tick: well past capacity
            key = f"k-{seq}"
            # one 32 KiB record (and three small ones) per op: a single
            # 24-op burst is 0.8 MB in flight against the shrunken 256 KiB
            # kafka_produce account, so admission MUST shed however few
            # bursts a client starved by the rest of the suite gets to
            # issue (at 4 KiB an op it took 64 ops in flight at once, which
            # six xdist workers' worth of brokers never let it reach)
            values = [
                b'{"k":"' + key.encode() + b'","pad":"' + b"x" * 32768 + b'"}'
            ] + [b'{"k":"%s-f%d","pad":""}' % (key.encode(), j) for j in range(3)]
            t = asyncio.create_task(
                one(clients[seq % len(clients)], seq % partitions, key, values)
            )
            outstanding.add(t)
            t.add_done_callback(outstanding.discard)
            seq += 1
            if len(outstanding) > 768:
                break
        await asyncio.sleep(0.01)
    if outstanding:
        await asyncio.gather(*outstanding, return_exceptions=True)


async def _read_keys(c, partitions) -> dict[str, int]:
    seen: dict[str, int] = {}
    loop = asyncio.get_event_loop()
    for p in range(partitions):
        off = 0
        deadline = loop.time() + 60.0
        while True:
            try:
                batches, hwm = await c.fetch(TOPIC, p, off, max_wait_ms=20)
            except Exception:
                # stale leadership pointing at the killed broker: refresh
                # and retry until the new leader serves the partition
                if loop.time() > deadline:
                    raise
                try:
                    await c.refresh_metadata([TOPIC])
                except Exception:
                    pass
                await asyncio.sleep(0.5)
                continue
            if not batches:
                if off >= hwm:
                    break
                off = hwm
                continue
            for b in batches:
                for r in b.records():
                    v = r.value or b""
                    if v.startswith(b'{"k":"'):
                        key = v[6:v.find(b'"', 6)].decode()
                        seen[key] = seen.get(key, 0) + 1
            off = batches[-1].last_offset + 1
    return seen


def test_overload_flood_survives_leader_kill(tmp_path):
    async def body():
        cluster = await ProcCluster(
            str(tmp_path), n=3,
            extra_config={
                "default_topic_replication": 3,
                # tiny plane (256KiB produce account): the connection-
                # pipeline-bounded concurrent inflight bytes (~0.8MB on
                # this harness) must overrun it, so the flood MUST shed
                "resource_memory_total_mb": 1,
                "raft_election_timeout_ms": 2000,
                "raft_heartbeat_interval_ms": 200,
            },
        ).start()
        partitions = 2
        clients = []
        try:
            c = await KafkaClient(cluster.bootstrap()).connect()
            clients.append(c)
            await c.create_topic(TOPIC, partitions=partitions, replication=3)
            await c.produce(TOPIC, 0, [b'{"k":"warm","pad":""}'], acks=-1)
            for _ in range(2):
                clients.append(await KafkaClient(cluster.bootstrap()).connect())

            acked: set[str] = set()
            shed: set[str] = set()
            errors: list[str] = []
            stop = asyncio.Event()
            flood = asyncio.create_task(
                _flood(clients, stop, acked, shed, errors, partitions)
            )
            await asyncio.sleep(1.5)  # overload established
            # kill the CURRENT leader of partition 0 mid-flood
            await c.refresh_metadata([TOPIC])
            leader = c._leaders[(TOPIC, 0)]
            killed = cluster.nodes[leader]
            killed.kill()
            await asyncio.sleep(3.5)  # flood rides through the election
            stop.set()
            await flood
            # the flood did shed (overload was real) and did land writes
            assert acked, "no write was ever acked under the flood"
            assert shed, "the tiny budget plane never shed — not overloaded"

            reader = await KafkaClient(cluster.bootstrap()).connect()
            clients.append(reader)
            seen = await _read_keys(reader, partitions)
            # EXACT: every acked write present exactly once on survivors
            missing = [k for k in acked if seen.get(k, 0) == 0]
            dups = [k for k in acked if seen.get(k, 0) > 1]
            assert not missing, f"ACKED LOST under overload+kill: {missing[:5]}"
            assert not dups, f"ACKED DUPLICATED: {dups[:5]}"
            # shed-before-ack holds through the failover too
            shed_visible = [k for k in shed if seen.get(k, 0) > 0]
            assert not shed_visible, f"SHED READABLE: {shed_visible[:5]}"
        finally:
            for cl in clients:
                try:
                    await cl.close()
                except Exception:
                    pass
            await cluster.stop()

    _run(body())
