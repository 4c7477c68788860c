"""Cluster control-plane tests over an in-process multi-broker fixture.

Mirrors cluster/tests/cluster_test_fixture.h: N brokers (storage + rpc
server + raft group manager + controller + backend) in one process, real
RPC over loopback. Covers: controller command replication, topic
create/delete reconciliation on every replica, leader forwarding, node
join, decommission-driven replica moves, leadership gossip.
"""

from __future__ import annotations

import asyncio

import pytest

from redpanda_tpu import rpc
from redpanda_tpu.cluster import (
    Broker,
    ClusterService,
    Controller,
    ControllerBackend,
    ControllerDispatcher,
    MetadataCache,
    MetadataDisseminationService,
    PartitionLeadersTable,
    PartitionManager,
    ShardTable,
    TopicConfig,
)
from redpanda_tpu.cluster import commands as ccmds
from redpanda_tpu.cluster.metadata_dissemination import md_dissemination_service
from redpanda_tpu.models.fundamental import NTP
from redpanda_tpu.models.record import Record, RecordBatch
from redpanda_tpu.raft.consensus import RaftTimings
from redpanda_tpu.raft.group_manager import GroupManager
from redpanda_tpu.raft.types import ConsistencyLevel, VNode
from redpanda_tpu.storage.log_manager import StorageApi

from raft_stability import flaky_election_retry, wait_for_stable_leader

FAST = dict(election_timeout_ms=150, heartbeat_interval_ms=40)


def run(coro):
    asyncio.run(coro)


async def wait_until(pred, timeout: float = 8.0, interval: float = 0.02, msg: str = ""):
    deadline = asyncio.get_event_loop().time() + timeout
    while not pred():
        if asyncio.get_event_loop().time() > deadline:
            raise AssertionError(f"timeout: {msg}")
        await asyncio.sleep(interval)


class ClusterNode:
    """One broker: storage + rpc + raft + controller + backend."""

    def __init__(self, node_id: int, base_dir: str):
        self.node_id = node_id
        self.base_dir = base_dir
        self.vnode = VNode(node_id, 0)
        self.connections = rpc.ConnectionCache()
        self.storage = None
        self.server = None
        self.gm = None
        self.controller = None
        self.backend = None
        self.pm = None
        self.leaders = PartitionLeadersTable()
        self.shards = ShardTable(n_shards=4)
        self.md = None
        self.dispatcher = None
        self.port = None

    async def start(self) -> "ClusterNode":
        self.storage = await StorageApi(self.base_dir).start()
        self.gm = GroupManager(
            self.vnode, self.storage, self.connections, timings=RaftTimings(**FAST)
        )
        self.pm = PartitionManager(self.storage, self.node_id)
        self.controller = Controller(self.vnode, self.gm, self.connections)
        self.dispatcher = ControllerDispatcher(self.controller, self.connections)
        self.backend = ControllerBackend(
            self.vnode,
            self.controller.topic_table,
            self.gm,
            self.pm,
            leaders_table=self.leaders,
            shard_table=self.shards,
            finish_move=lambda ntp, reps: self.dispatcher.replicate(
                ccmds.finish_moving_cmd(ntp, reps)
            ),
        )
        self.md = MetadataDisseminationService(
            self.node_id, self.leaders, self.controller.members, self.connections,
            interval_s=0.05,
        )
        self.gm.register_leadership_notification(
            lambda c: self.md.notify_leadership(c.ntp, c.leader_id, c.term)
        )
        proto = rpc.SimpleProtocol()
        self.gm.register_service(proto)
        ClusterService(self.controller, self.dispatcher).register(proto)
        proto.register_service(rpc.ServiceHandler(md_dissemination_service, self.md))
        self.server = rpc.Server(port=0)
        self.server.set_protocol(proto)
        await self.server.start()
        self.port = self.server.port
        await self.gm.start()
        return self

    async def start_control_plane(self, seeds: list[VNode]) -> None:
        await self.controller.start(seeds)
        await self.backend.start()
        await self.md.start()

    async def stop(self) -> None:
        if self.md:
            await self.md.stop()
        if self.backend:
            await self.backend.stop()
        if self.controller:
            await self.controller.stop()
        if self.gm:
            await self.gm.stop()
        if self.server:
            await self.server.stop()
        if self.storage:
            await self.storage.stop()
        await self.connections.close()
        self.gm = None


class ClusterFixture:
    def __init__(self, tmp_path, n: int):
        self.nodes = [ClusterNode(i, str(tmp_path / f"n{i}")) for i in range(n)]

    async def start(self) -> "ClusterFixture":
        for n in self.nodes:
            await n.start()
        self.wire()
        seeds = [n.vnode for n in self.nodes]
        for n in self.nodes:
            await n.start_control_plane(seeds)
        leader = await self.wait_for_stable_leader()
        # seed brokers register themselves (application start does this on join)
        for n in self.nodes:
            await n.dispatcher.replicate(
                ccmds.register_node_cmd(
                    n.node_id, "127.0.0.1", n.port, "127.0.0.1", 9092 + n.node_id
                )
            )
        return self

    def wire(self) -> None:
        for a in self.nodes:
            for b in self.nodes:
                if a is not b and b.port is not None:
                    a.connections.register(b.node_id, "127.0.0.1", b.port)

    async def stop(self) -> None:
        for n in self.nodes:
            await n.stop()

    def controller_leader(self):
        for n in self.nodes:
            if n.controller and n.controller.is_leader():
                return n
        return None

    async def wait_for_stable_leader(self, timeout: float = 16.0, margin: float = 1.0):
        """Deflake: see raft_stability.wait_for_stable_leader (margin =
        how many election timeouts the leader must survive in-term)."""
        return await wait_for_stable_leader(
            self.controller_leader,
            lambda n: n.controller.consensus if n.controller else None,
            FAST["election_timeout_ms"] / 1000.0,
            timeout,
            what="controller leader",
            margin=margin,
        )

    async def wait_converged(self, pred_per_node, timeout: float = 8.0, msg: str = ""):
        await wait_until(
            lambda: all(pred_per_node(n) for n in self.nodes), timeout, msg=msg
        )


def data_batch(*values: bytes) -> RecordBatch:
    return RecordBatch.build([Record(value=v, offset_delta=i) for i, v in enumerate(values)])


# ===================================================================== tests

def test_create_topic_reconciles_on_all_replicas(tmp_path):
    async def main():
        fx = await ClusterFixture(tmp_path, 3).start()
        try:
            leader = await fx.wait_for_stable_leader()
            await leader.controller.create_topic(
                TopicConfig("events", partition_count=2, replication_factor=3)
            )
            # every node applied the command
            await fx.wait_converged(
                lambda n: n.controller.topic_table.contains("events"),
                msg="topic table convergence",
            )
            # every node hosts both partitions (rf=3 on 3 nodes)
            await fx.wait_converged(
                lambda n: all(
                    n.pm.get(NTP.kafka("events", p)) is not None for p in range(2)
                ),
                msg="partitions materialized",
            )
            # raft leaders elected for the data partitions; replicate works
            ntp = NTP.kafka("events", 0)

            def part_leader():
                for n in fx.nodes:
                    p = n.pm.get(ntp)
                    if p is not None and p.is_leader():
                        return n
                return None

            await wait_until(lambda: part_leader() is not None, msg="partition leader")
            ln = part_leader()
            res = await ln.pm.get(ntp).replicate(
                [data_batch(b"hello")], ConsistencyLevel.quorum_ack
            )
            assert res.last_offset >= 0
        finally:
            await fx.stop()

    run(main())


def test_forwarding_from_non_leader(tmp_path):
    async def main():
        fx = await ClusterFixture(tmp_path, 3).start()
        try:
            leader = await fx.wait_for_stable_leader()
            follower = next(n for n in fx.nodes if n is not leader)
            # create through a NON-leader broker: dispatcher forwards
            ntp = NTP.kafka("fwd", 0)
            cmd = ccmds.create_topic_cmd(
                {"name": "fwd", "ns": "kafka", "replication_factor": 3, "overrides": {}},
                [ccmds.assignment_payload(ntp, 1000, [0, 1, 2])],
            )
            await follower.dispatcher.replicate(cmd)
            await fx.wait_converged(
                lambda n: n.controller.topic_table.contains("fwd"),
                msg="forwarded create applied",
            )
        finally:
            await fx.stop()

    run(main())


def test_delete_topic_removes_partitions(tmp_path):
    async def main():
        fx = await ClusterFixture(tmp_path, 3).start()
        try:
            leader = await fx.wait_for_stable_leader()
            await leader.controller.create_topic(
                TopicConfig("gone", partition_count=1, replication_factor=3)
            )
            ntp = NTP.kafka("gone", 0)
            await fx.wait_converged(
                lambda n: n.pm.get(ntp) is not None, msg="created"
            )
            await leader.controller.delete_topic("gone")
            await fx.wait_converged(
                lambda n: n.pm.get(ntp) is None
                and not n.controller.topic_table.contains("gone"),
                msg="deleted everywhere",
            )
        finally:
            await fx.stop()

    run(main())


def test_metadata_cache_and_leader_gossip(tmp_path):
    async def main():
        fx = await ClusterFixture(tmp_path, 3).start()
        try:
            leader = await fx.wait_for_stable_leader()
            await leader.controller.create_topic(
                TopicConfig("md", partition_count=1, replication_factor=3)
            )
            ntp = NTP.kafka("md", 0)
            # leadership for the data partition is gossiped to EVERY node,
            # including ones that would know it only via dissemination
            await fx.wait_converged(
                lambda n: n.leaders.get_leader(ntp) is not None,
                msg="leader known cluster-wide",
            )
            cache = MetadataCache(
                fx.nodes[0].controller.topic_table,
                fx.nodes[0].controller.members,
                fx.nodes[0].leaders,
            )
            assert cache.get_leader(ntp) is not None
            assert len(cache.all_brokers()) == 3
            assert cache.contains("md")
        finally:
            await fx.stop()

    run(main())


@flaky_election_retry(
    "4-node membership churn on top of a fresh controller: heartbeats "
    "delayed by CI load can depose the settled leader mid-move"
)
def test_replica_move(tmp_path):
    async def main():
        fx = await ClusterFixture(tmp_path, 4).start()
        try:
            leader = await fx.wait_for_stable_leader(margin=1.5)
            await leader.controller.create_topic(
                TopicConfig("mv", partition_count=1, replication_factor=3)
            )
            ntp = NTP.kafka("mv", 0)
            await fx.wait_converged(
                lambda n: n.controller.topic_table.contains("mv"), msg="created"
            )
            md = leader.controller.topic_table.get("mv")
            old = list(md.assignments[0].replicas)
            outsider = next(i for i in range(4) if i not in old)
            victim = old[0]
            target = [r for r in old if r != victim] + [outsider]
            await leader.controller.move_partition_replicas(ntp, target)
            # move completes: new node hosts it, victim dropped it
            await wait_until(
                lambda: fx.nodes[outsider].pm.get(ntp) is not None,
                timeout=12.0,
                msg="new replica created",
            )
            await wait_until(
                lambda: fx.nodes[victim].pm.get(ntp) is None,
                timeout=12.0,
                msg="old replica dropped",
            )
            md2 = leader.controller.topic_table.get("mv")
            assert sorted(md2.assignments[0].replicas) == sorted(target)
            assert md2.assignments[0].moving_to is None
        finally:
            await fx.stop()

    run(main())


@flaky_election_retry(
    "decommission drains replicas through the controller while startup "
    "elections can still thrash under CI load"
)
def test_decommission_drains_node(tmp_path):
    async def main():
        fx = await ClusterFixture(tmp_path, 4).start()
        try:
            leader = await fx.wait_for_stable_leader(margin=1.5)
            await leader.controller.create_topic(
                TopicConfig("dr", partition_count=2, replication_factor=3)
            )
            await fx.wait_converged(
                lambda n: n.controller.topic_table.contains("dr"), msg="created"
            )
            # decommission a node that is NOT the controller leader
            victim = next(
                n.node_id
                for n in fx.nodes
                if n is not leader
                and any(
                    n.node_id in pa.replicas
                    for pa in leader.controller.topic_table.get("dr").assignments.values()
                )
            )
            await leader.controller.decommission_node(victim)

            def drained():
                md = leader.controller.topic_table.get("dr")
                return all(
                    victim not in pa.replicas and pa.moving_to is None
                    for pa in md.assignments.values()
                )

            await wait_until(drained, timeout=15.0, msg="node drained")
            from redpanda_tpu.cluster import MembershipState

            # the drain watcher seals it with finish_reallocations:
            # draining -> removed, and the broker leaves the metadata view
            await wait_until(
                lambda: leader.controller.members.get(victim).state
                == MembershipState.removed,
                timeout=10.0,
                msg="finish_reallocations applied",
            )
            assert victim not in leader.controller.members.node_ids()
        finally:
            await fx.stop()

    run(main())


def test_allocator_constraints():
    from redpanda_tpu.cluster import AllocationError, PartitionAllocator

    a = PartitionAllocator()
    for i in range(3):
        a.register_node(i)
    sets = a.allocate(6, 3, commit=True)
    assert all(len(set(s)) == 3 for s in sets)
    # balanced: every node got 6 replicas
    assert all(n.allocated == 6 for n in a.nodes())
    # frontend path (commit=False) must not mutate bookkeeping
    a.allocate(4, 2)
    assert all(n.allocated == 6 for n in a.nodes())
    a.decommission_node(2)
    with pytest.raises(AllocationError):
        a.allocate(1, 3)
    sets = a.allocate(2, 2)
    assert all(2 not in s for s in sets)


def test_duplicate_create_applies_as_first_wins_noop(tmp_path):
    """Two brokers can race the same create past the leader's pre-check,
    committing BOTH commands; the duplicate must apply as a no-op keeping
    the first winner's assignments — raising would also fail every restart
    replay of the log (the duplicate sits there forever)."""

    async def main():
        fx = await ClusterFixture(tmp_path, 3).start()
        try:
            leader = await fx.wait_for_stable_leader()
            ntp = NTP.kafka("dup", 0)
            cmd1 = ccmds.create_topic_cmd(
                {"name": "dup", "ns": "kafka", "replication_factor": 3, "overrides": {}},
                [ccmds.assignment_payload(ntp, 2000, [0, 1, 2])],
            )
            cmd2 = ccmds.create_topic_cmd(
                {"name": "dup", "ns": "kafka", "replication_factor": 3, "overrides": {}},
                [ccmds.assignment_payload(ntp, 2001, [2, 1, 0])],  # the loser
            )
            await leader.controller.replicate_and_wait(cmd1)
            await leader.controller.replicate_and_wait(cmd2)  # no raise
            for node in fx.nodes:
                md = node.controller.topic_table.get("dup")
                assert md is not None
                assert md.assignments[0].group == 2000  # first wins
        finally:
            await fx.stop()

    run(main())


def test_join_via_non_leader_seed(tmp_path):
    async def main():
        fx = await ClusterFixture(tmp_path, 3).start()
        try:
            leader = await fx.wait_for_stable_leader()
            seed = next(n for n in fx.nodes if n is not leader)  # NON-leader seed
            from redpanda_tpu.cluster import Broker, join_cluster

            joiner_conns = rpc.ConnectionCache()
            try:
                await join_cluster(
                    Broker(9, "127.0.0.1", 5999, "127.0.0.1", 9099),
                    ("127.0.0.1", seed.port),
                    joiner_conns,
                    seed_node_hint=seed.node_id,
                )
                await fx.wait_converged(
                    lambda n: n.controller.members.contains(9),
                    msg="joined broker visible cluster-wide",
                )
            finally:
                await joiner_conns.close()
        finally:
            await fx.stop()

    run(main())


def test_shard_table_stable_and_grouped():
    st = ShardTable(n_shards=8)
    ntps = [NTP.kafka("t", p) for p in range(64)]
    first = [st.shard_for(n) for n in ntps]
    assert first == [st.shard_for(n) for n in ntps]  # deterministic
    groups = st.group_by_shard(ntps)
    assert sum(len(v) for v in groups.values()) == 64
    assert len(groups) > 1  # spreads
    st.update(ntps[0], 3)
    assert st.shard_for(ntps[0]) == 3


@flaky_election_retry(
    "forced leadership transfers mid-produce: a transfer can race a "
    "load-delayed election and leave no leader within the wait budget"
)
def test_offsets_gap_free_across_leadership_transfers(tmp_path):
    """Round-1 acceptance for offset translation: force leadership
    changes mid-produce (each election/config change appends non-data
    batches to the raft log) and assert the Kafka-visible offsets stay
    contiguous from 0 with no client-visible gaps."""
    async def main():
        fx = await ClusterFixture(tmp_path, 3).start()
        try:
            leader = await fx.wait_for_stable_leader(margin=1.5)
            await leader.controller.create_topic(
                TopicConfig("gapless", partition_count=1, replication_factor=3)
            )
            ntp = NTP.kafka("gapless", 0)
            await fx.wait_converged(
                lambda n: n.pm.get(ntp) is not None, msg="partition everywhere"
            )

            def part_leader():
                for n in fx.nodes:
                    p = n.pm.get(ntp)
                    if p is not None and p.is_leader():
                        return n
                return None

            total = 0
            for round_ in range(3):
                await wait_until(lambda: part_leader() is not None, msg="leader")
                ln = part_leader()
                p = ln.pm.get(ntp)
                for i in range(4):
                    res = await p.replicate(
                        [data_batch(b"r%d-%d" % (round_, i))],
                        ConsistencyLevel.quorum_ack,
                    )
                    # produce responses are kafka offsets: strictly contiguous
                    assert res.base_offset == total, (res, total)
                    total += 1
                if round_ < 2:  # transfer leadership -> config/election churn
                    ok = await p.consensus.do_transfer_leadership()
                    assert ok
                    # settled successor, not an ad-hoc sleep: the next
                    # round's replicate must land on a leader that §8
                    # committed an entry of its own term
                    # single part_leader() call per probe: leadership is in
                    # flux right after the transfer, so a second call can
                    # return None and AttributeError out of wait_until
                    await wait_until(
                        lambda: (
                            (n := part_leader()) is not None
                            and n.pm.get(ntp).consensus.leadership_settled()
                        ),
                        timeout=8.0,
                        msg="settled post-transfer leader",
                    )

            await wait_until(lambda: part_leader() is not None, msg="final leader")
            p = part_leader().pm.get(ntp)
            # the raft log genuinely contains non-data batches...
            assert p.otl.total_delta() > 0, "test exercised no config batches"
            # ...but consumers see contiguous offsets 0..total-1
            await wait_until(lambda: p.high_watermark >= total, msg="hwm catchup")
            batches = await p.make_reader(0, 1 << 30)
            offsets = [b.base_offset + r.offset_delta for b in batches for r in b.records()]
            assert offsets == list(range(total)), offsets
            assert p.high_watermark == total
        finally:
            await fx.stop()

    run(main())
