"""Gobekli-style linearizability campaigns against a real 3-node cluster.

Four campaigns prove the checker works end to end (round-3 review #4;
reference src/consistency-testing/gobekli/gobekli/consensus.py:65 +
chaostest):

1. CLEAN: concurrent writers + a reader run through a leader SIGKILL; the
   history must check out — raft must not lose acked writes, reorder real
   time, or serve stale/rolled-back reads.
2. SLOW NETWORK: delay probes on a follower's append_entries (the io-delay
   campaign shape, on the shared package cluster) slow replication without
   breaking it; the history must still linearize.
3. BROKEN: the broker is deliberately mis-configured
   (unsafe_relaxed_acks: acks=-1 served at leader level) with
   append_entries failure probes armed on both followers via the admin
   honey-badger API, then the leader is killed. The checker MUST report
   lost acked writes — a checker that cannot catch a planted violation
   proves nothing.
4. WRITE OUTAGE: exception probes on both followers cut the leader off
   from quorum mid-workload (asymmetric partition), producing a window of
   indeterminate timed-out writes; after recovery the whole history must
   still linearize.
"""

from __future__ import annotations

import asyncio
import time

import aiohttp
import pytest

from redpanda_tpu.consistency import LogWorkload, check_history
from redpanda_tpu.kafka.client import KafkaClient

from .harness import ProcCluster

pytestmark = pytest.mark.chaos


async def _admin(node, method: str, path: str):
    url = f"http://127.0.0.1:{node.ports['admin']}{path}"
    async with aiohttp.ClientSession() as s:
        async with s.request(
            method, url, timeout=aiohttp.ClientTimeout(total=5)
        ) as r:
            return r.status


async def _find_leader(cluster, topic: str) -> int:
    deadline = time.monotonic() + 45
    while time.monotonic() < deadline:
        try:
            c = await KafkaClient(cluster.bootstrap()).connect()
            await c.refresh_metadata([topic])
            leader = c._leaders.get((topic, 0))
            await c.close()
            if leader is not None:
                return leader
        except Exception:
            pass
        await asyncio.sleep(0.5)
    raise TimeoutError(f"no leader for {topic}")


def test_clean_cluster_history_linearizes(tmp_path):
    async def body():
        cluster = ProcCluster(
            str(tmp_path), 3, extra_config={"default_topic_replication": 3}
        )
        await cluster.start()
        try:
            c = await KafkaClient(cluster.bootstrap()).connect()
            await c.create_topic("lin", partitions=1, replication=3)
            await c.close()
            wl = LogWorkload(cluster.bootstrap, "lin")

            async def killer():
                await asyncio.sleep(2.0)  # mid-workload
                leader = await _find_leader(cluster, "lin")
                cluster.nodes[leader].kill()
                await asyncio.sleep(4.0)
                await cluster.restart(cluster.nodes[leader])

            await asyncio.wait_for(
                asyncio.gather(
                    wl.writer(1, 30),
                    wl.writer(2, 30),
                    wl.reader(40),
                    killer(),
                ),
                240,
            )
            final = await wl.final_log()
            res = check_history(wl.history, final)
            acked = res.n_acked_writes
            assert acked >= 20, f"too few acked ops to be meaningful: {acked}"
            assert res.ok, "linearizability violated on a HEALTHY cluster:\n" + \
                "\n".join(res.violations[:10])
        finally:
            await cluster.stop()

    asyncio.run(body())


def test_slow_network_still_linearizes(proc_cluster):
    """Latency faults instead of kills: delay probes on a follower's
    append_entries (chaostest's io-delay campaign shape) slow replication
    without breaking it — acked writes must still linearize."""

    async def body():
        cluster = proc_cluster
        c = await KafkaClient(cluster.bootstrap()).connect()
        await c.create_topic("lin-slow", partitions=1, replication=3)
        await c.close()
        leader = await _find_leader(cluster, "lin-slow")
        slow = cluster.nodes[(leader + 1) % 3]
        try:
            # arm INSIDE the try: if the PUT arms server-side but the
            # response times out client-side, the finally must still
            # disarm — the cluster is shared by the whole chaos package
            st = await _admin(
                slow, "PUT", "/v1/failure-probes/raftgen/append_entries/delay"
            )
            assert st == 200, st
            wl = LogWorkload(cluster.bootstrap, "lin-slow")
            await asyncio.wait_for(
                asyncio.gather(wl.writer(1, 20), wl.reader(20)), 180
            )
        finally:
            await _admin(
                slow, "DELETE", "/v1/failure-probes/raftgen/append_entries"
            )
        final = await wl.final_log()
        res = check_history(wl.history, final)
        assert res.n_acked_writes >= 15
        assert res.ok, "\n".join(res.violations[:10])

    asyncio.run(body())


def test_checker_catches_planted_violation(tmp_path):
    async def body():
        cluster = ProcCluster(
            str(tmp_path),
            3,
            extra_config={
                "default_topic_replication": 3,
                # deliberately broken: quorum acks served at leader level
                "unsafe_relaxed_acks": 1,
            },
        )
        await cluster.start()
        try:
            c = await KafkaClient(cluster.bootstrap()).connect()
            await c.create_topic("lin", partitions=1, replication=3)
            await c.close()
            wl = LogWorkload(cluster.bootstrap, "lin")
            # phase 1: healthy writes (replicate normally)
            await asyncio.wait_for(wl.writer(1, 10), 60)

            leader = await _find_leader(cluster, "lin")
            followers = [n for n in cluster.nodes if n.node_id != leader]
            # block replication: append_entries raises on both followers
            # (honey-badger probes over the admin API; heartbeats still
            # flow so the leader keeps its lease and keeps acking)
            for f in followers:
                st = await _admin(
                    f, "PUT", "/v1/failure-probes/raftgen/append_entries/exception"
                )
                assert st == 200, st
            # phase 2: these get acked (relaxed) but never replicate
            await asyncio.wait_for(wl.writer(2, 8), 60)
            lost_candidates = [
                op.value for op in wl.history
                if op.kind == "write" and op.ok and op.value.startswith(b"w2-")
            ]
            assert lost_candidates, "planted phase produced no acked writes"
            # kill the only holder of the acked suffix; heal the followers
            cluster.nodes[leader].kill()
            for f in followers:
                await _admin(f, "DELETE", "/v1/failure-probes/raftgen/append_entries")

            final = await wl.final_log()
            res = check_history(wl.history, final)
            assert not res.ok, (
                "checker FAILED to catch deliberately lost acked writes "
                f"(final log {len(final)} records, "
                f"{res.n_acked_writes} acked)"
            )
            assert any("LOST ACKED WRITE" in v for v in res.violations), (
                res.violations
            )
        finally:
            await cluster.stop()

    asyncio.run(body())


def test_quorum_outage_and_recovery_linearizes(proc_cluster):
    """Campaign 4 — WRITE OUTAGE: exception probes on BOTH followers'
    append_entries cut the leader off from quorum (an asymmetric
    partition: the leader is up but cannot commit), so acks=-1 produces
    stall into indeterminate timeouts. After the probes are lifted the
    cluster must recover, and the full history — including the ops that
    were in flight across the outage window — must still linearize: an
    op that timed out may legally land or vanish, but nothing ACKED
    during or after the outage may be lost or reordered."""

    async def body():
        cluster = proc_cluster
        c = await KafkaClient(cluster.bootstrap()).connect()
        await c.create_topic("lin-outage", partitions=1, replication=3)
        await c.close()
        leader = await _find_leader(cluster, "lin-outage")
        followers = [n for n in cluster.nodes if n.node_id != leader]
        wl = LogWorkload(cluster.bootstrap, "lin-outage")

        try:
            reader_task = asyncio.ensure_future(wl.reader(80))
            # phase A: healthy baseline
            await asyncio.wait_for(wl.writer(1, 10), 60)
            # phase B: arm the outage, THEN write into it — the probes are
            # provably up before these ops start, so they must time out
            for f in followers:
                st = await _admin(
                    f, "PUT", "/v1/failure-probes/raftgen/append_entries/exception"
                )
                assert st == 200, st
            await asyncio.wait_for(wl.writer(2, 3, op_timeout=3.0), 60)
            # phase C: lift the outage, write through recovery
            for f in followers:
                await _admin(f, "DELETE", "/v1/failure-probes/raftgen/append_entries")
            await asyncio.wait_for(wl.writer(3, 10), 120)
            await asyncio.wait_for(reader_task, 60)
        finally:
            # belt-and-braces: never leave probes armed on the shared cluster
            for f in followers:
                try:
                    await _admin(
                        f, "DELETE", "/v1/failure-probes/raftgen/append_entries"
                    )
                except Exception:
                    pass
        final = await wl.final_log()
        res = check_history(wl.history, final)
        acked = res.n_acked_writes
        # only phase-B writes (writer id 2) prove the outage bit: an
        # incidental phase-A/C timeout must not satisfy the guard
        timed_out = sum(
            1
            for op in wl.history
            if op.kind == "write"
            and op.response_t is None
            and op.value.startswith(b"w2-")
        )
        assert timed_out >= 1, "outage never bit: no phase-B write timed out"
        assert acked >= 10, f"too few acked ops to be meaningful: {acked}"
        assert res.ok, "violation across quorum outage:\n" + "\n".join(
            res.violations[:10]
        )

    asyncio.run(body())
