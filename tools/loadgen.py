"""Closed-loop mixed-workload load generator gated on pandaprobe SLOs.

The ducktape/consistency-suite analogue (SURVEY §4.2-4.3) for the
"heavy traffic from millions of users" leg of the north star: simulated
clients drive produce → coproc-transform → fetch, consumer groups with
live rebalances, EOS consume-transform-produce transactions, and
tiered-storage reads against a real in-process broker (or an in-process
multi-node cluster over loopback RPC), then the run is *judged*: the
pandaprobe registry is snapshotted before and after, and the delta is
evaluated against the scenario's declarative SLO objectives
(observability/slo.py). The verdict — per-objective quantiles,
pass/fail, throughput, and breach exemplars that resolve to
/v1/trace/slow entries — lands in an ``SLO_r0N.json`` report alongside
the BENCH trajectory.

Arrival model: **open-loop arrival, closed-loop completion**. Each
producer client schedules arrivals on the wall clock (a slow broker does
not slow the offered load down — no coordinated omission) but awaits
every operation to completion, so the broker-side histograms see true
end-to-end latencies under the configured concurrency.

Chaos: ``--chaos`` arms the scenario's honey-badger probe (PR 4) through
the REAL admin API before the measured window — ``rpc.send`` delay
between the in-process cluster's nodes is the canonical one: every
replicate leg pays the injected delay, the rpc/produce objectives
breach, and each breach carries trace exemplars. The cluster-level
partition-tolerance suite over real broker *processes* lives in
tests/chaos/test_partition_tolerance.py; this tool is the load half.

Backends: ``--backend inproc`` (default) boots Applications inside this
process and judges the shared registry directly; ``--backend proc`` boots
REAL broker processes (the chaos harness's ProcCluster) and judges the
scenario from the FEDERATED /metrics scrape
(observability/federation.py) — the merged multi-node HdrHists, node
labels preserved — which removes the one-loop ceiling on offered load.

Usage:
    python tools/loadgen.py --scenario mixed_64p --report SLO_r06.json
    python tools/loadgen.py --scenario mixed_64p --chaos --report SLO_r06_chaos.json
    python tools/loadgen.py --scenario mixed_64p --backend proc --report SLO_r10.json
    python tools/loadgen.py --list

Scale: client counts multiply with ``--clients-scale`` (the default
sizes target a 2-core CI box; ``--clients-scale 8`` simulates thousands
of clients on real hardware).
"""

from __future__ import annotations

import argparse
import asyncio
import copy
import json
import os
import socket
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
# the S3 imposter (tiered-storage scenarios) lives with the tests
sys.path.insert(0, os.path.join(REPO, "tests"))


# ================================================================ scenarios
# Objective threshold notes: clean runs must PASS on a busy shared box, so
# thresholds are generous against in-process latencies; the chaos delay is
# sized (see "chaos") to push the rpc/replicate tails well past them.
def _objectives(produce_ms, fetch_ms, append_ms, replicate_ms, rpc_ms,
                explode_ms, min_samples):
    return [
        {"name": "produce_p99", "metric": "kafka_produce_latency_us",
         "quantile": 99, "threshold_ms": produce_ms, "min_samples": min_samples},
        # fetch includes deliberate long-poll waits; judge on the error
        # budget (5% may ride the poll) instead of the raw quantile
        {"name": "fetch_p99", "metric": "kafka_fetch_latency_us",
         "quantile": 99, "threshold_ms": fetch_ms,
         "min_samples": min_samples, "budget_pct": 5.0},
        {"name": "append_p99", "metric": "storage_append_latency_us",
         "quantile": 99, "threshold_ms": append_ms, "min_samples": min_samples},
        {"name": "replicate_p99", "metric": "raft_replicate_latency_us",
         "quantile": 99, "threshold_ms": replicate_ms, "min_samples": 1},
        {"name": "rpc_p99", "metric": "rpc_request_latency_us",
         "quantile": 99, "threshold_ms": rpc_ms, "min_samples": 1},
        # the payload-plan parse stage: since PR 12 the filter transform
        # stages its rows off the per-batch pointer table
        # (t_explode_ptrs), so judging stage="explode" read NO_DATA on a
        # lane that no longer runs (caught by the PR 14 slodiff of
        # SLO_r14 vs SLO_r10 — the diff names idle objectives)
        {"name": "coproc_explode_p95", "metric": "coproc_stage_latency_us",
         "labels": {"stage": "explode_ptrs"}, "quantile": 95,
         "threshold_ms": explode_ms, "min_samples": 1},
    ]


SCENARIOS: dict[str, dict] = {
    # Tier-1 smoke: one broker, seconds long, deterministic PASS under
    # deliberately loose objectives (tests/slo/test_slo_smoke.py).
    "smoke": {
        "nodes": 1,
        "partitions": 4,
        "replication": 1,
        "duration_s": 2.0,
        "producers": 4,
        "produce_rate": 25.0,      # arrivals/s per producer client
        "records_per_op": 4,
        "record_bytes": 128,
        "group_members": 2,
        "rebalance_every_s": 0.0,  # off: the smoke run must be quiet
        "eos_pairs": 1,
        "eos_abort_every": 3,
        "transform_readers": 1,
        "tiered_readers": 0,
        "coproc": True,
        "objectives": _objectives(10_000, 20_000, 5_000, 10_000, 5_000,
                                  5_000, 20),
        "chaos": {"module": "rpc", "probe": "send", "effect": "delay",
                  "delay_ms": 800},
    },
    # The acceptance scenario: an in-process 3-node cluster, 64-partition
    # replicated topic, all four workload families at once. Clean run
    # passes; --chaos delays every inter-node rpc.send 800ms, breaching
    # the rpc (and usually replicate) objectives with trace exemplars.
    "mixed_64p": {
        "nodes": 3,
        "partitions": 64,
        "replication": 3,
        "duration_s": 12.0,
        "producers": 24,
        "produce_rate": 6.0,
        "records_per_op": 8,
        "record_bytes": 256,
        "group_members": 6,
        "rebalance_every_s": 3.0,
        "eos_pairs": 3,
        "eos_abort_every": 4,
        "transform_readers": 2,
        "tiered_readers": 2,
        "coproc": True,
        # thresholds sit in the clean/chaos separation band: the clean run
        # measures produce/replicate p99 ≈ 100ms and rpc p99 ≈ 40ms on a
        # 2-core box, while an 800ms rpc.send delay pushes rpc past 800ms
        # and produce/replicate into seconds — so clean PASSes with ~20x
        # margin and chaos breaches with exemplars, deterministically
        "objectives": _objectives(2_000, 30_000, 5_000, 2_000, 500,
                                  5_000, 100),
        "chaos": {"module": "rpc", "probe": "send", "effect": "delay",
                  "delay_ms": 800},
    },
    # Single-node heavy-partition variant: no replication rpc, coproc and
    # host-stage machinery under the full partition fan-out.
    "standalone_64p": {
        "nodes": 1,
        "partitions": 64,
        "replication": 1,
        "duration_s": 8.0,
        "producers": 16,
        "produce_rate": 10.0,
        "records_per_op": 8,
        "record_bytes": 256,
        "group_members": 4,
        "rebalance_every_s": 2.5,
        "eos_pairs": 2,
        "eos_abort_every": 4,
        "transform_readers": 2,
        "tiered_readers": 2,
        "coproc": True,
        "objectives": _objectives(15_000, 30_000, 8_000, 10_000, 5_000,
                                  8_000, 50),
        "chaos": {"module": "coproc", "probe": "device_dispatch",
                  "effect": "delay", "delay_ms": 800},
    },
    # Device-plane CRC chaos (ROADMAP item 2 follow-on c): a 3-node proc
    # cluster with follower batched-CRC validation ON; --chaos arms the
    # finjector CORRUPT probe so received append blobs arrive torn on
    # every node for its first N appends. The device plane must REJECT
    # them (raft_crc_rejected_batches_total moves in the federated
    # scrape), the leader's resend repairs each one, and acked writes
    # ride the healthy quorum meanwhile (workloads_ok requires both).
    "crc_chaos": {
        "nodes": 3,
        "partitions": 16,
        "replication": 3,
        "duration_s": 10.0,
        "producers": 8,
        "produce_rate": 6.0,
        "records_per_op": 8,
        "record_bytes": 256,
        "group_members": 0,
        "rebalance_every_s": 0.0,
        "eos_pairs": 1,
        "eos_abort_every": 4,
        "transform_readers": 0,
        "tiered_readers": 0,
        "coproc": False,
        "extra_config": {"raft_device_crc_validate": True},
        "objectives": _objectives(15_000, 30_000, 8_000, 15_000, 8_000,
                                  8_000, 20),
        "chaos": {"module": "raft", "probe": "append_blob",
                  "effect": "corrupt", "count": 30},
        "chaos_assert_metric": "raft_crc_rejected_batches_total",
    },
}

# Open-loop overload family (ROADMAP item 4 acceptance): arrivals are
# scheduled at overload_factor x the MEASURED closed-loop capacity and
# never wait for completions (coordinated-omission-safe: each acked op's
# latency is measured from its SCHEDULED arrival). The broker memory
# total is shrunk so the produce admission gate actually bites — the gate
# is that throughput plateaus at the knee, admitted p99 stays governed,
# sheds are counted (never lost: acked-write verification is EXACT), no
# account breaches its budget, and the decision journal reconstructs the
# shed episodes. Run via --scenario overload_* (run_overload_async).
OVERLOAD_SCENARIOS: dict[str, dict] = {
    # seconds-long single-broker smoke (tier-1: tests/slo/test_overload_smoke.py)
    "overload_smoke": {
        "nodes": 1,
        "partitions": 4,
        "replication": 1,
        "calibrate_s": 2.0,
        "duration_s": 4.0,
        "producers": 4,
        "records_per_op": 8,
        "record_bytes": 1024,
        "overload_factor": 2.0,
        "coproc": False,
        "admitted_p99_ms": 10_000,
        "plateau_floor": 0.5,
        "extra_config": {
            # small plane so the flood actually exhausts kafka_produce
            "resource_memory_total_mb": 4,
        },
    },
    # the acceptance scenario: a REAL broker process (proc backend),
    # 64-partition topic, >= 2x measured capacity — SLO_r13_overload.json
    "overload_64p": {
        "nodes": 1,
        "partitions": 64,
        "replication": 1,
        "calibrate_s": 6.0,
        "duration_s": 15.0,
        "producers": 8,
        "records_per_op": 8,
        "record_bytes": 1024,
        "overload_factor": 2.0,
        "coproc": False,
        "admitted_p99_ms": 10_000,
        "plateau_floor": 0.8,
        "extra_config": {
            "resource_memory_total_mb": 8,
        },
    },
}

TOPIC = "loadgen"
EOS_SRC_GROUP = "loadgen-eos"
EOS_DST = "loadgen-eos-out"
TIERED_TOPIC = "loadgen-tiered"
SCRIPT_NAME = "loadgen-filter"


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ================================================================ the stack
class Stack:
    """1..N in-process Applications sharing this process's registry,
    tracer, SLO engine and honey badger — which is exactly what lets the
    scenario snapshot/judge them directly while chaos arming still goes
    through the real admin API."""

    backend = "inproc"

    def __init__(self, scenario: dict, base_dir: str, imposter=None):
        self.scenario = scenario
        self.base_dir = base_dir
        self.imposter = imposter
        self.apps = []
        self.kafka_ports: list[int] = []
        self.admin_ports: list[int] = []

    def _configs(self):
        from redpanda_tpu.config import Configuration

        s = self.scenario
        n = s["nodes"]
        thresholds = [o["threshold_ms"] for o in s["objectives"]]
        slow_ms = max(1, int(min(thresholds)))
        rpc_ports = [_free_port() for _ in range(n)]
        # kafka ports are pre-allocated, not ephemeral: in clustered mode
        # the advertised port replicates through the controller's
        # register_node command, which reads the configured value
        kafka_ports = [_free_port() for _ in range(n)]
        seed_str = (
            ",".join(f"{i}@127.0.0.1:{p}" for i, p in enumerate(rpc_ports))
            if n > 1 else ""
        )
        configs = []
        for i in range(n):
            c = Configuration()
            sets = {
                "node_id": i,
                "data_directory": os.path.join(self.base_dir, f"n{i}"),
                "kafka_api_port": kafka_ports[i],
                "advertised_kafka_api_port": kafka_ports[i],
                "admin_api_port": 0,
                "rpc_server_port": rpc_ports[i],
                "seed_servers": seed_str,
                "default_topic_replication": s["replication"],
                # tolerate the injected rpc delay without election storms:
                # a heartbeat delayed by the chaos effect must still land
                # inside the election timeout
                "raft_election_timeout_ms": 2500,
                "raft_heartbeat_interval_ms": 250,
                "coproc_enable": bool(s.get("coproc")),
                # exemplars + /v1/trace/slow resolution need the tracer;
                # the slow ring threshold tracks the tightest objective so
                # every breach-sized span is resolvable afterwards
                "trace_enabled": True,
                "trace_slow_threshold_ms": slow_ms,
            }
            if self.imposter is not None:
                sets.update({
                    "cloud_storage_enabled": True,
                    "cloud_storage_bucket": "loadgen",
                    "cloud_storage_api_endpoint":
                        f"http://127.0.0.1:{self.imposter.port}",
                    "cloud_storage_access_key": "k",
                    "cloud_storage_secret_key": "s",
                    "cloud_storage_segment_max_upload_interval_sec": 1,
                })
            # per-scenario broker knobs (the overload family shrinks
            # resource_memory_total_mb so admission actually bites)
            sets.update(s.get("extra_config") or {})
            for k, v in sets.items():
                c.set(k, v)
            configs.append(c)
        return configs

    async def archival_run_once(self) -> int:
        """One reconcile+upload pass on every node; returns total uploads."""
        total = 0
        for a in self.apps:
            arch = getattr(a, "archival", None)
            if arch is not None:
                total += await arch.run_once()
        return total

    async def start(self) -> "Stack":
        from redpanda_tpu.app import Application

        configs = self._configs()
        # return_exceptions + assign-before-raise: if one node fails to
        # start (port bind race), the ones that DID start are recorded so
        # the caller's stack.stop() tears them down instead of leaking
        # live brokers into the process
        results = await asyncio.gather(
            *(Application(c).start() for c in configs),
            return_exceptions=True,
        )
        self.apps = [a for a in results if not isinstance(a, BaseException)]
        errors = [e for e in results if isinstance(e, BaseException)]
        if errors:
            raise errors[0]
        # the config property is integer milliseconds; re-apply the exact
        # float so every breach-sized span (possibly sub-ms in tests) is
        # guaranteed to land in the slow ring its exemplar points at
        from redpanda_tpu.observability import tracer

        tracer.configure(
            slow_threshold_ms=min(
                o["threshold_ms"] for o in self.scenario["objectives"]
            )
        )
        self.kafka_ports = [a.kafka_server.port for a in self.apps]
        self.admin_ports = [a.admin.port for a in self.apps]
        if len(self.apps) > 1:
            await self._wait_settled()
        return self

    async def _wait_settled(self, timeout: float = 60.0) -> None:
        """Same contract as the chaos harness's wait_for_settled_writes:
        two acks=-1 canary writes across an election-timeout margin."""
        from redpanda_tpu.kafka.client import KafkaClient

        deadline = time.monotonic() + timeout
        last = None
        while time.monotonic() < deadline:
            c = None
            try:
                c = await KafkaClient(self.bootstrap()).connect()
                try:
                    await c.create_topic(
                        "loadgen-canary", partitions=1,
                        replication=self.scenario["replication"],
                    )
                except Exception:
                    await c.refresh_metadata(["loadgen-canary"], auto_create=False)
                await c.produce("loadgen-canary", 0, [b"settle-1"], acks=-1)
                await asyncio.sleep(0.6)
                await c.produce("loadgen-canary", 0, [b"settle-2"], acks=-1)
                await c.close()
                return
            except Exception as e:  # noqa: BLE001 — retried until deadline
                last = e
                if c is not None:
                    try:
                        await c.close()
                    except Exception:
                        pass
                await asyncio.sleep(0.5)
        raise TimeoutError(f"cluster writes never settled: {last!r}")

    def bootstrap(self) -> list[tuple[str, int]]:
        return [("127.0.0.1", p) for p in self.kafka_ports]

    async def transforms_active(self, script: str) -> bool:
        return all(
            a.coproc is not None and script in a.coproc.active_scripts()
            for a in self.apps
        )

    async def stop(self) -> None:
        for a in self.apps:
            try:
                await a.stop()
            except Exception:
                pass


class ProcStack:
    """REAL broker processes (the chaos harness's ProcCluster): nothing is
    shared with this process, so scenario SLOs are judged from the
    FEDERATED /metrics scrape (observability/federation.py) instead of the
    in-process registry — removing the one-loop ceiling on offered load:
    the brokers burn their own cores, and the judged histograms live where
    the latency happened. Chaos arming and transform-activation polling go
    through each node's real admin API. Tiered-storage scenarios drive
    archival through the admin surface (POST /v1/archival/run_once), so
    ``tiered_readers`` work in this mode too — the S3 imposter runs in
    THIS process and the broker processes reach it over loopback."""

    backend = "proc"

    def __init__(self, scenario: dict, base_dir: str, imposter=None):
        self.scenario = scenario
        self.base_dir = base_dir
        self.imposter = imposter
        self.cluster = None
        self.kafka_ports: list[int] = []
        self.admin_ports: list[int] = []

    async def start(self) -> "ProcStack":
        from chaos.harness import ProcCluster

        s = self.scenario
        thresholds = [o["threshold_ms"] for o in s["objectives"]]
        extra = {
            "default_topic_replication": s["replication"],
            # same chaos posture as the in-process stack: an injected
            # rpc delay must not trigger election storms
            "raft_election_timeout_ms": 2500,
            "raft_heartbeat_interval_ms": 250,
            "coproc_enable": bool(s.get("coproc")),
            "trace_enabled": True,
            "trace_slow_threshold_ms": max(1, int(min(thresholds))),
        }
        if self.imposter is not None:
            extra.update({
                "cloud_storage_enabled": True,
                "cloud_storage_bucket": "loadgen",
                "cloud_storage_api_endpoint":
                    f"http://127.0.0.1:{self.imposter.port}",
                "cloud_storage_access_key": "k",
                "cloud_storage_secret_key": "s",
                "cloud_storage_segment_max_upload_interval_sec": 1,
            })
        extra.update(s.get("extra_config") or {})
        self.cluster = await ProcCluster(
            self.base_dir, n=s["nodes"], extra_config=extra
        ).start()
        self.kafka_ports = [n.ports["kafka"] for n in self.cluster.nodes]
        self.admin_ports = [n.ports["admin"] for n in self.cluster.nodes]
        return self

    def bootstrap(self) -> list[tuple[str, int]]:
        return [("127.0.0.1", p) for p in self.kafka_ports]

    def federation_targets(self) -> list[tuple[int, str]]:
        return [
            (i, f"http://127.0.0.1:{p}")
            for i, p in enumerate(self.admin_ports)
        ]

    async def transforms_active(self, script: str) -> bool:
        import aiohttp

        async with aiohttp.ClientSession() as sess:
            for port in self.admin_ports:
                try:
                    async with sess.get(
                        f"http://127.0.0.1:{port}/v1/coproc/status",
                        timeout=aiohttp.ClientTimeout(total=5),
                    ) as r:
                        doc = await r.json()
                except Exception:
                    return False
                if (
                    not doc.get("enabled")
                    or script not in (doc.get("scripts") or [])
                ):
                    return False
        return True

    async def archival_run_once(self) -> int:
        """Drive one archival pass per node through the admin surface."""
        import aiohttp

        total = 0
        async with aiohttp.ClientSession() as sess:
            for port in self.admin_ports:
                async with sess.post(
                    f"http://127.0.0.1:{port}/v1/archival/run_once",
                    timeout=aiohttp.ClientTimeout(total=60),
                ) as r:
                    if r.status == 200:
                        total += (await r.json()).get("uploads", 0)
        return total

    async def stop(self) -> None:
        if self.cluster is not None:
            await self.cluster.stop()


# ================================================================ workloads
def _payload(client_id: int, seq: int, j: int, size: int) -> bytes:
    level = ("error", "info", "warn")[(client_id + seq + j) % 3]
    doc = '{"level":"%s","code":%d,"msg":"c%d-%d-%d-' % (
        level, j, client_id, seq, j
    )
    pad = max(0, size - len(doc) - 2)
    return (doc + "x" * pad + '"}').encode()


async def _sleep_or_stop(stop: asyncio.Event, delay: float) -> bool:
    """True when the stop event fired during the wait. No shield: wait_for
    cancels the Event.wait() on timeout, which is harmless and leak-free
    (a shielded waiter would survive until stop.set(), thousands of them
    over a long scenario)."""
    if delay <= 0:
        return stop.is_set()
    try:
        await asyncio.wait_for(stop.wait(), delay)
        return True
    except asyncio.TimeoutError:
        return False


async def _producer(i, client, partitions, rate, k, size, stop, stats):
    loop = asyncio.get_event_loop()
    interval = 1.0 / rate
    # stagger client phases so arrivals spread over the interval
    next_t = loop.time() + (i % 16) / 16.0 * interval
    part = i % partitions
    seq = 0
    while not stop.is_set():
        now = loop.time()
        if next_t > now:
            if await _sleep_or_stop(stop, next_t - now):
                break
        # open loop: the schedule advances regardless of completion time
        next_t += interval
        part = (part + 1) % partitions
        values = [_payload(i, seq, j, size) for j in range(k)]
        seq += 1
        try:
            await client.produce(TOPIC, part, values, acks=-1)
            stats["produce_ops"] += 1
            stats["produced_records"] += k
        except Exception:
            stats["produce_errors"] += 1


async def _group_member(i, client, topics, stop, stats):
    from redpanda_tpu.kafka.client.consumer import GroupConsumer

    c = GroupConsumer(
        client, "loadgen-group", topics,
        session_timeout_ms=8000, heartbeat_interval_s=0.5,
    )
    try:
        await c.join()
        stats["group_joins"] += 1
        while not stop.is_set():
            try:
                out = await c.poll(max_records=500)
                n = sum(len(v) for v in out.values())
                stats["consumed_records"] += n
                await c.commit()
                if c.rejoin_needed:
                    stats["rebalances_seen"] += 1
                if not out:
                    await _sleep_or_stop(stop, 0.05)
            except Exception:
                stats["consume_errors"] += 1
                if await _sleep_or_stop(stop, 0.2):
                    break
    finally:
        try:
            await c.leave()
        except Exception:
            pass


async def _rebalancer(client, topics, every_s, stop, stats):
    """Forces group rebalances by cycling a transient member in and out —
    every join and leave bumps the generation for the whole group."""
    from redpanda_tpu.kafka.client.consumer import GroupConsumer

    while not stop.is_set():
        if await _sleep_or_stop(stop, every_s):
            break
        t = GroupConsumer(
            client, "loadgen-group", topics,
            session_timeout_ms=8000, heartbeat_interval_s=0.5,
        )
        try:
            await t.join()
            await _sleep_or_stop(stop, 0.3)
            await t.leave()
            stats["rebalances_forced"] += 1
        except Exception:
            stats["rebalance_errors"] += 1


async def _eos_pair(i, client, partitions, abort_every, stop, stats):
    """Consume-transform-produce with EOS: read the main topic, write the
    transform to EOS_DST inside a transaction with staged group offsets;
    every ``abort_every``-th transaction aborts. The end-of-run
    read_committed count over EOS_DST must equal exactly the committed
    records — the closed-loop exactly-once check."""
    from redpanda_tpu.kafka.client.producer import TransactionalProducer

    p = TransactionalProducer(client, f"loadgen-eos-{i}")
    await p.init()
    src_part = i % partitions
    pos = 0
    n_tx = 0
    while not stop.is_set():
        try:
            batches, hwm = await client.fetch(
                TOPIC, src_part, pos, max_wait_ms=100, max_bytes=64 * 1024
            )
        except Exception:
            stats["eos_errors"] += 1
            if await _sleep_or_stop(stop, 0.2):
                break
            continue
        values = []
        new_pos = pos
        for b in batches:
            for r in b.records():
                off = b.header.base_offset + r.offset_delta
                if off >= pos and r.value:
                    values.append(b"eos:" + r.value[:64])
                    new_pos = off + 1
        if not values:
            if await _sleep_or_stop(stop, 0.05):
                break
            continue
        values = values[:64]
        try:
            p.begin()
            await p.send(EOS_DST, i, values)
            await p.send_offsets(
                f"{EOS_SRC_GROUP}-{i}", {(TOPIC, src_part): new_pos}
            )
            if abort_every and n_tx % abort_every == abort_every - 1:
                await p.abort()
                stats["eos_aborted_tx"] += 1
            else:
                await p.commit()
                stats["eos_committed_tx"] += 1
                stats["eos_committed_records"] += len(values)
                pos = new_pos
            n_tx += 1
        except Exception:
            stats["eos_errors"] += 1
            try:
                await p.abort()
            except Exception:
                # a dead transaction epoch needs a fresh producer session
                try:
                    await p.init()
                except Exception:
                    pass
            if await _sleep_or_stop(stop, 0.2):
                break


async def _transform_reader(i, client, mat_topic, partitions, stop, stats):
    """Closes the produce → coproc → fetch loop: tails the materialized
    topic the deployed transform writes."""
    positions = {p: 0 for p in range(partitions)}
    part = i
    while not stop.is_set():
        part = (part + 1) % partitions
        try:
            batches, _ = await client.fetch(
                mat_topic, part, positions[part], max_wait_ms=20
            )
            n = sum(len(b.records()) for b in batches)
            if batches:
                positions[part] = batches[-1].last_offset + 1
            stats["transform_records_read"] += n
        except Exception:
            stats["transform_read_errors"] += 1
            if await _sleep_or_stop(stop, 0.25):
                break
        if await _sleep_or_stop(stop, 0.05):
            break


async def _tiered_reader(i, client, hi_offset, stop, stats):
    """Re-reads the archived-and-locally-evicted prefix: every fetch below
    the local log start falls through to the cloud read path."""
    off = 0
    while not stop.is_set():
        try:
            batches, _ = await client.fetch(
                TIERED_TOPIC, 0, off, max_wait_ms=10, max_bytes=32 * 1024
            )
            stats["tiered_reads"] += 1
            stats["tiered_records_read"] += sum(
                len(b.records()) for b in batches
            )
            off = batches[-1].last_offset + 1 if batches else 0
            if off >= hi_offset:
                off = 0
        except Exception:
            stats["tiered_read_errors"] += 1
            if await _sleep_or_stop(stop, 0.25):
                break
        if await _sleep_or_stop(stop, 0.05):
            break


# ================================================================ setup
async def _deploy_transform(stack, client) -> str:
    """Deploy the JSON-filter transform through the real wasm-event path
    (what `rpk wasm deploy` produces) and wait until every node's engine
    activated it."""
    from redpanda_tpu.coproc import wasm_event
    from redpanda_tpu.models.fundamental import COPROC_INTERNAL_TOPIC
    from redpanda_tpu.ops.transforms import filter_field_eq

    spec = filter_field_eq("level", "error")
    rec = wasm_event.make_deploy_record(
        SCRIPT_NAME, spec.to_json(), [TOPIC]
    )
    batch = wasm_event.deploy_batch([rec])
    deadline = time.monotonic() + 30.0
    while True:
        try:
            await client.produce_batches(COPROC_INTERNAL_TOPIC, 0, [batch])
            break
        except Exception:
            if time.monotonic() > deadline:
                raise
            await asyncio.sleep(0.5)
    while not await stack.transforms_active(SCRIPT_NAME):
        if time.monotonic() > deadline:
            raise TimeoutError("transform never activated on every node")
        await asyncio.sleep(0.1)
    return f"{TOPIC}.${SCRIPT_NAME}$"


async def _setup_tiered(stack: Stack, client) -> int:
    """Build a topic whose prefix lives ONLY in the bucket: produce across
    several small segments, archive the closed ones, then DeleteRecords
    the local prefix. Returns the high watermark readers cycle over."""
    from redpanda_tpu.kafka.protocol import messages as m

    await client.create_topic(
        TIERED_TOPIC, partitions=1, replication=1,
        configs={"segment.bytes": "8192"},
    )
    for seq in range(24):
        await client.produce(
            TIERED_TOPIC, 0,
            [_payload(999, seq, j, 512) for j in range(4)],
            acks=-1,
        )
    # archive the closed segments now (deterministic, no interval wait):
    # in-proc stacks call the scheduler directly, the proc backend goes
    # through POST /v1/archival/run_once on every node
    uploaded = await stack.archival_run_once()
    if uploaded == 0:
        raise RuntimeError("tiered setup: nothing archived")
    hwm = await client.latest_offset(TIERED_TOPIC, 0)
    evict_to = hwm // 2
    conn = await client.leader_connection(TIERED_TOPIC, 0)
    resp = await conn.request(m.DELETE_RECORDS, {
        "topics": [{
            "name": TIERED_TOPIC,
            "partitions": [{"partition_index": 0, "offset": evict_to}],
        }],
        "timeout_ms": 30_000,
    })
    pr = resp["topics"][0]["partitions"][0]
    if pr["error_code"] != 0:
        raise RuntimeError(f"tiered setup: delete_records error {pr}")
    if pr["low_watermark"] > 0:
        raise RuntimeError(
            "tiered setup: local eviction lost the archived prefix "
            f"(low_watermark {pr['low_watermark']})"
        )
    return hwm


async def _arm_chaos(stack, chaos: dict) -> dict:
    """Arm the scenario's failure probe through the real admin API (and
    size the injected delay), exactly like an operator with rpk. The probe
    is armed on EVERY node: in-process brokers share one honey badger so
    repeats are idempotent, while real broker processes each own theirs —
    one PUT per process is the only way the fault exists cluster-wide."""
    import aiohttp

    delay_ms = int(chaos.get("delay_ms", 50))
    params = []
    if chaos["effect"] == "delay":
        params.append(f"delay_ms={delay_ms}")
    if chaos.get("count"):
        params.append(f"count={int(chaos['count'])}")
    qs = ("?" + "&".join(params)) if params else ""
    body = None
    async with aiohttp.ClientSession() as s:
        for port in stack.admin_ports:
            url = (
                f"http://127.0.0.1:{port}/v1/failure-probes/"
                f"{chaos['module']}/{chaos['probe']}/{chaos['effect']}{qs}"
            )
            async with s.put(url) as resp:
                body = await resp.json()
                if resp.status != 200:
                    raise RuntimeError(
                        f"chaos arm failed on :{port}: {resp.status} {body}"
                    )
    return {**chaos, "armed": body.get("armed")}


async def _disarm_chaos(stack, chaos: dict) -> None:
    """Disarm on every node (the proc backend has one badger per broker
    process; honey_badger.disable() in this process reaches none of them)."""
    import aiohttp

    async with aiohttp.ClientSession() as s:
        for port in stack.admin_ports:
            url = (
                f"http://127.0.0.1:{port}/v1/failure-probes/"
                f"{chaos['module']}/{chaos['probe']}"
            )
            try:
                async with s.delete(url):
                    pass
            except Exception:
                pass  # a node lost mid-chaos: nothing to disarm there


async def _engine_devices(stack) -> list:
    """Per node, the platform its coproc engine runs on as the broker
    reports it (/v1/coproc/status "device"), or None where coproc is off —
    so a report says where its numbers were taken, not where the
    environment suggested. The in-process stack is one JAX process and
    runs on whatever platform the environment selects; the process stack's
    brokers are CPU-pinned by the test harness."""
    import aiohttp

    out = []
    async with aiohttp.ClientSession() as sess:
        for port in stack.admin_ports:
            try:
                async with sess.get(
                    f"http://127.0.0.1:{port}/v1/coproc/status",
                    timeout=aiohttp.ClientTimeout(total=5),
                ) as r:
                    out.append((await r.json()).get("device"))
            except (aiohttp.ClientError, asyncio.TimeoutError, ValueError) as exc:
                out.append({"error": repr(exc)})
    return out


async def _scrape_counter_total(stack, name: str) -> float:
    """Sum one counter series across every node's /metrics (uniform for
    both backends: in-process stacks expose admin /metrics too)."""
    import re

    import aiohttp

    # the registry renders with its exposition prefix (redpanda_tpu_...)
    pat = re.compile(
        rf"^(?:redpanda_tpu_)?{re.escape(name)}(?:\{{[^}}]*\}})? "
        rf"([0-9.eE+-]+)$",
        re.MULTILINE,
    )
    # in-process stacks share ONE registry: scraping every admin port
    # would multiply the same counter by the node count
    ports = (
        stack.admin_ports[:1]
        if stack.backend == "inproc"
        else stack.admin_ports
    )
    total = 0.0
    async with aiohttp.ClientSession() as sess:
        for port in ports:
            try:
                async with sess.get(
                    f"http://127.0.0.1:{port}/metrics",
                    timeout=aiohttp.ClientTimeout(total=10),
                ) as r:
                    text = await r.text()
            except Exception:
                continue
            total += sum(float(m) for m in pat.findall(text))
    return total


async def _resolve_exemplars(stack: Stack, report: dict) -> None:
    """Every breach exemplar must resolve to a /v1/trace/slow entry; the
    report says how many did, so a broken link is visible on its face."""
    import aiohttp

    trace_ids = {
        ex["trace_id"]
        for o in report["objectives"]
        for ex in o.get("exemplars") or []
    }
    report["exemplars_total"] = len(trace_ids)
    if not trace_ids:
        report["exemplars_resolved"] = 0
        return
    url = f"http://127.0.0.1:{stack.admin_ports[0]}/v1/trace/slow?limit=500"
    async with aiohttp.ClientSession() as s:
        async with s.get(url) as resp:
            doc = await resp.json()
    slow_ids = {sp["trace_id"] for sp in doc.get("spans", [])}
    report["exemplars_resolved"] = len(trace_ids & slow_ids)


async def _verify_eos(client, eos_pairs: int, stats: dict) -> dict:
    """read_committed count over EOS_DST must equal the committed records
    exactly: nothing aborted leaked, nothing committed lost."""
    visible = 0
    for p in range(eos_pairs):
        off = 0
        while True:
            batches, hwm = await client.fetch(
                EOS_DST, p, off, max_wait_ms=10, isolation_level=1
            )
            if not batches:
                if off >= hwm:
                    break
                off = hwm  # aborted-range hole: skip to the watermark
                continue
            visible += sum(len(b.records()) for b in batches)
            off = batches[-1].last_offset + 1
    return {
        "committed_records": stats["eos_committed_records"],
        "visible_read_committed": visible,
        "exact": visible == stats["eos_committed_records"],
    }


# ================================================================ scenario run
def _spec_for(scenario_name: str, s: dict):
    from redpanda_tpu.observability.slo import SloSpec

    return SloSpec.from_dict(
        {"name": scenario_name, "objectives": s["objectives"]}
    )


async def run_scenario_async(
    name: str,
    *,
    chaos: bool = False,
    duration_s: float | None = None,
    clients_scale: float = 1.0,
    overrides: dict | None = None,
    base_dir: str | None = None,
    backend: str = "inproc",
) -> dict:
    from redpanda_tpu.kafka.client import KafkaClient
    from redpanda_tpu.observability.slo import slo

    if backend not in ("inproc", "proc"):
        raise ValueError(f"unknown backend {backend!r}")
    s = copy.deepcopy(SCENARIOS[name])
    s.update(overrides or {})
    if duration_s is not None:
        s["duration_s"] = float(duration_s)
    for key in ("producers", "group_members", "eos_pairs",
                "transform_readers", "tiered_readers"):
        s[key] = max(0 if s[key] == 0 else 1, int(s[key] * clients_scale))

    tmp = None
    if base_dir is None:
        tmp = tempfile.TemporaryDirectory(prefix="loadgen-")
        base_dir = tmp.name

    from redpanda_tpu.finjector import honey_badger
    from redpanda_tpu.observability import tracer

    # A scenario reconfigures process-wide singletons (the injected delay,
    # the active SLO spec, the tracer slow threshold); in-process callers
    # (the pytest suite) must get every one of them back afterwards —
    # disable() clears probes but deliberately not the delay knob, and
    # nothing else restores itself
    saved_delay_ms = honey_badger.delay_ms
    saved_spec = slo.spec
    saved_slow_us = tracer.slow_threshold_us
    saved_trace_enabled = tracer.enabled
    spec = None

    imposter = None
    if s["tiered_readers"]:
        from s3_imposter import S3Imposter

        imposter = await S3Imposter().start()

    stack_cls = ProcStack if backend == "proc" else Stack
    stack = stack_cls(s, base_dir, imposter=imposter)
    stats: dict[str, int] = {
        k: 0 for k in (
            "produce_ops", "produced_records", "produce_errors",
            "consumed_records", "consume_errors", "group_joins",
            "rebalances_forced", "rebalances_seen", "rebalance_errors",
            "eos_committed_tx", "eos_aborted_tx", "eos_committed_records",
            "eos_errors", "transform_records_read", "transform_read_errors",
            "tiered_reads", "tiered_records_read", "tiered_read_errors",
        )
    }
    clients: list = []
    t_setup0 = time.monotonic()
    try:
        await stack.start()
        n_clients = max(
            2, min(8, s["producers"] + s["group_members"] + s["eos_pairs"])
        )
        clients = await asyncio.gather(*(
            KafkaClient(stack.bootstrap()).connect() for _ in range(n_clients)
        ))

        def client_for(i: int):
            return clients[i % len(clients)]

        admin = clients[0]
        await admin.create_topic(
            TOPIC, partitions=s["partitions"], replication=s["replication"]
        )
        await admin.create_topic(
            EOS_DST, partitions=max(1, s["eos_pairs"]),
            replication=s["replication"],
        )
        mat_topic = None
        if s.get("coproc"):
            mat_topic = await _deploy_transform(stack, admin)
        tiered_hwm = 0
        if s["tiered_readers"]:
            tiered_hwm = await _setup_tiered(stack, admin)

        # ---- warmup: touch every path once so the measured window holds
        # steady-state latencies, not first-op compiles and cache fills
        for p in range(s["partitions"]):
            await admin.produce(
                TOPIC, p, [_payload(0, 0, j, s["record_bytes"])
                            for j in range(2)], acks=-1
            )
        if mat_topic is not None:
            deadline = time.monotonic() + 20.0
            while time.monotonic() < deadline:
                try:
                    hv = await admin.latest_offset(mat_topic, 0)
                    if hv > 0:
                        break
                except Exception:
                    pass
                await asyncio.sleep(0.2)

        chaos_info = None
        if chaos:
            if not s.get("chaos"):
                raise ValueError(f"scenario {name} defines no chaos probe")
            chaos_info = await _arm_chaos(stack, s["chaos"])

        # ---- the measured window
        spec = _spec_for(name, s)
        fed = None
        if backend == "proc":
            # nothing broker-side lives in this process: judge the window
            # from the FEDERATED scrape of every broker's /metrics (the
            # merged HdrHists carry node labels for drill-down)
            from redpanda_tpu.observability.federation import FederatedSlo

            targets = stack.federation_targets()
            fed = FederatedSlo(lambda: targets)
            baseline = await fed.snapshot()
        else:
            slo.configure(spec)      # arms per-metric exemplar thresholds
            baseline = slo.snapshot()
        stop = asyncio.Event()
        tasks = []
        for i in range(s["producers"]):
            tasks.append(asyncio.create_task(_producer(
                i, client_for(i), s["partitions"], s["produce_rate"],
                s["records_per_op"], s["record_bytes"], stop, stats,
            )))
        group_topics = [TOPIC]
        for i in range(s["group_members"]):
            tasks.append(asyncio.create_task(_group_member(
                i, client_for(100 + i), group_topics, stop, stats
            )))
        if s["group_members"] and s["rebalance_every_s"] > 0:
            tasks.append(asyncio.create_task(_rebalancer(
                client_for(200), group_topics, s["rebalance_every_s"],
                stop, stats,
            )))
        for i in range(s["eos_pairs"]):
            tasks.append(asyncio.create_task(_eos_pair(
                i, client_for(300 + i), s["partitions"],
                s["eos_abort_every"], stop, stats,
            )))
        if mat_topic is not None:
            for i in range(s["transform_readers"]):
                tasks.append(asyncio.create_task(_transform_reader(
                    i, client_for(400 + i), mat_topic, s["partitions"],
                    stop, stats,
                )))
        for i in range(s["tiered_readers"]):
            tasks.append(asyncio.create_task(_tiered_reader(
                i, client_for(500 + i), tiered_hwm, stop, stats
            )))

        t0 = time.monotonic()
        await asyncio.sleep(s["duration_s"])
        stop.set()
        if tasks:
            done, pending = await asyncio.wait(tasks, timeout=20.0)
            for t in pending:
                t.cancel()
            if pending:
                await asyncio.gather(*pending, return_exceptions=True)
            for t in done:
                t.exception()  # consume; stats carry the error counts
        elapsed = time.monotonic() - t0

        if chaos_info is not None:
            # disarm before the closed-loop verification reads — through
            # the admin API on every node (real broker processes own their
            # badgers; the local disable only reaches the in-process one)
            await _disarm_chaos(stack, s["chaos"])
            honey_badger.disable()

        eos_check = (
            await _verify_eos(admin, s["eos_pairs"], stats)
            if s["eos_pairs"] else None
        )

        if fed is not None:
            report = await fed.evaluate(spec, baseline=baseline)
        else:
            report = slo.evaluate(spec, baseline=baseline)
        await _resolve_exemplars(stack, report)
        # scenario-declared fault-path proof: the chaos run must show its
        # counter moved (e.g. crc_chaos: corrupted appends REJECTED by the
        # follower CRC plane, visible in the federated scrape)
        chaos_metric = None
        if chaos_info is not None and s.get("chaos_assert_metric"):
            mname = s["chaos_assert_metric"]
            chaos_metric = {
                "name": mname,
                "total": await _scrape_counter_total(stack, mname),
            }
        report.update({
            "backend": stack.backend,
            "engine_devices": await _engine_devices(stack),
            "chaos": chaos_info,
            "duration_s": round(elapsed, 3),
            "setup_s": round(t0 - t_setup0, 3),
            "nodes": s["nodes"],
            "partitions": s["partitions"],
            "replication": s["replication"],
            "clients": {
                "producers": s["producers"],
                "group_members": s["group_members"],
                "eos_pairs": s["eos_pairs"],
                "transform_readers": s["transform_readers"],
                "tiered_readers": s["tiered_readers"],
            },
            "throughput": {
                **stats,
                "produce_ops_per_s": round(stats["produce_ops"] / elapsed, 1),
                "produced_records_per_s": round(
                    stats["produced_records"] / elapsed, 1
                ),
            },
            "eos_check": eos_check,
            "chaos_metric": chaos_metric,
            # the lossless-workload bar: EOS stays exactly-once always;
            # client-visible produce ERRORS (unacked, retriable) are
            # expected bounded degradation under chaos, but a CLEAN run
            # must not see any; a declared chaos metric must have MOVED
            # (the fault actually exercised its detection path)
            "workloads_ok": (
                (eos_check is None or eos_check["exact"])
                and (chaos_info is not None or stats["produce_errors"] == 0)
                and (chaos_metric is None or chaos_metric["total"] > 0)
            ),
        })
        return report
    finally:
        for c in clients:
            try:
                await c.close()
            except Exception:
                pass
        honey_badger.disable()
        honey_badger.delay_ms = saved_delay_ms
        # disarm the scenario's per-histogram exemplar thresholds before
        # restoring the spec: configure(arm_exemplars=False) restores the
        # OBJECT but would leave e.g. a 2000ms produce threshold silently
        # recording exemplars for the rest of the process (a later
        # in-process /v1/slo re-arms its own spec lazily)
        if spec is not None:
            from redpanda_tpu.observability import probes as _probes

            hists = slo.registry.histograms()
            for o in spec.objectives:
                h = hists.get(o.series)
                if h is not None:
                    _probes.disarm_exemplar_threshold(h)
        slo.configure(saved_spec, arm_exemplars=False)
        tracer.configure(
            enabled=saved_trace_enabled,
            slow_threshold_ms=saved_slow_us / 1000.0,
        )
        await stack.stop()
        if imposter is not None:
            await imposter.stop()
        if tmp is not None:
            tmp.cleanup()


def run_scenario(name: str, **kw) -> dict:
    return asyncio.run(run_scenario_async(name, **kw))


# ================================================================ overload
def _overload_payload(i: int, seq: int, j: int, size: int) -> bytes:
    """Unique, parseable key first so the verification sweep can extract
    it with a prefix scan instead of a JSON parse per record."""
    doc = '{"k":"%d-%d-%d","pad":"' % (i, seq, j)
    pad = max(0, size - len(doc) - 2)
    return (doc + "x" * pad + '"}').encode()


def _overload_keys(value: bytes) -> str | None:
    if not value.startswith(b'{"k":"'):
        return None
    end = value.find(b'"', 6)
    return value[6:end].decode() if end > 0 else None


async def _closed_loop_producer(i, client, partitions, k, size, stop, counter):
    """Calibration phase: back-to-back acked produces, no schedule — the
    aggregate acked rate IS the closed-loop knee the open-loop phase
    overloads against. Calibration keys use an id offset so the
    verification sweep never confuses them with measured-phase records."""
    part = i % partitions
    seq = 0
    while not stop.is_set():
        part = (part + 1) % partitions
        values = [
            _overload_payload(100_000 + i, seq, j, size) for j in range(k)
        ]
        seq += 1
        try:
            await client.produce(TOPIC, part, values, acks=-1)
            counter["records"] += k
        except Exception:
            counter["errors"] += 1


async def _open_loop_producer(
    i, client, partitions, op_rate, k, size, stop, ostats, lats,
    acked_keys, shed_keys, max_outstanding=256,
):
    """Open-loop overload: arrivals fire on a fixed schedule and NEVER
    wait for completions — each send runs as its own task, and an acked
    op's latency is measured from its SCHEDULED arrival time, so slow
    responses cannot suppress the arrivals that would have observed them
    (coordinated-omission-safe). A full outstanding window drops the
    arrival AT THE CLIENT and counts it (bounded client memory, no silent
    deferral of the schedule)."""
    from redpanda_tpu.kafka.protocol.errors import ErrorCode, KafkaError

    loop = asyncio.get_event_loop()
    interval = 1.0 / max(op_rate, 0.001)
    next_t = loop.time() + (i % 64) / 64.0 * interval
    outstanding: set = set()
    seq = 0

    async def one(part, values, keys, sched_t):
        try:
            await client.produce(TOPIC, part, values, acks=-1)
        except KafkaError as e:
            if e.code == ErrorCode.throttling_quota_exceeded:
                ostats["shed_ops"] += 1
                shed_keys.update(keys)
            else:
                ostats["error_ops"] += 1
            return
        except Exception:
            ostats["error_ops"] += 1
            return
        lats.append(loop.time() - sched_t)
        ostats["acked_ops"] += 1
        ostats["acked_records"] += len(values)
        acked_keys.update(keys)

    while not stop.is_set():
        now = loop.time()
        if next_t > now:
            if await _sleep_or_stop(stop, next_t - now):
                break
        sched_t = next_t
        next_t += interval
        if next_t < loop.time() - 2.0:
            # the event loop itself fell behind the schedule (client-side
            # saturation): re-anchor rather than emitting a burst that
            # would measure the CLIENT, not the broker
            skipped = int((loop.time() - next_t) / interval) + 1
            ostats["client_dropped"] += skipped
            next_t += skipped * interval
        if len(outstanding) >= max_outstanding:
            ostats["client_dropped"] += 1
            continue
        part = (i + seq) % partitions
        keys = [f"{i}-{seq}-{j}" for j in range(k)]
        values = [_overload_payload(i, seq, j, size) for j in range(k)]
        seq += 1
        t = asyncio.create_task(one(part, values, keys, sched_t))
        outstanding.add(t)
        t.add_done_callback(outstanding.discard)
    if outstanding:
        await asyncio.gather(*outstanding, return_exceptions=True)


def _quantile_ms(lats: list[float], q: float) -> float:
    if not lats:
        return 0.0
    xs = sorted(lats)
    idx = min(len(xs) - 1, int(q / 100.0 * len(xs)))
    return round(xs[idx] * 1e3, 3)


async def _overload_verify(client, partitions, acked_keys, shed_keys) -> dict:
    """End-of-run EXACT acked-write verification: every acked key appears
    exactly once (zero loss, zero duplicates), and no shed key is readable
    anywhere (shed-before-ack). Calibration/warmup records are ignored."""
    from collections import Counter as _Counter

    seen: _Counter = _Counter()
    for p in range(partitions):
        off = 0
        while True:
            batches, hwm = await client.fetch(
                TOPIC, p, off, max_wait_ms=10, max_bytes=1 << 20
            )
            if not batches:
                if off >= hwm:
                    break
                off = hwm
                continue
            for b in batches:
                for r in b.records():
                    key = _overload_keys(r.value or b"")
                    if key is not None:
                        seen[key] += 1
            off = batches[-1].last_offset + 1
    missing = sum(1 for k in acked_keys if seen[k] == 0)
    duplicated = sum(1 for k in acked_keys if seen[k] > 1)
    shed_visible = sum(1 for k in shed_keys if seen[k] > 0)
    return {
        "acked_keys": len(acked_keys),
        "missing": missing,
        "duplicated": duplicated,
        "shed_keys": len(shed_keys),
        "shed_visible": shed_visible,
        "exact": missing == 0 and duplicated == 0 and shed_visible == 0,
    }


async def _scrape_resources(stack) -> list[dict]:
    import aiohttp

    out = []
    async with aiohttp.ClientSession() as sess:
        for port in stack.admin_ports:
            try:
                async with sess.get(
                    f"http://127.0.0.1:{port}/v1/resources",
                    timeout=aiohttp.ClientTimeout(total=10),
                ) as r:
                    out.append(await r.json())
            except Exception as e:  # noqa: BLE001 — reported, judged below
                out.append({"error": repr(e)})
    return out


async def _scrape_admission_journal(stack) -> list[dict]:
    import aiohttp

    url = (
        f"http://127.0.0.1:{stack.admin_ports[0]}"
        f"/v1/governor?domain=admission&limit=256"
    )
    try:
        async with aiohttp.ClientSession() as sess:
            async with sess.get(
                url, timeout=aiohttp.ClientTimeout(total=10)
            ) as r:
                doc = await r.json()
        return doc.get("journal") or []
    except Exception:
        return []


async def run_overload_async(
    name: str,
    *,
    backend: str = "inproc",
    duration_s: float | None = None,
    base_dir: str | None = None,
    overrides: dict | None = None,
) -> dict:
    """The open-loop overload gate (ROADMAP item 4): calibrate the
    closed-loop knee, then schedule arrivals at overload_factor x that
    rate and judge survival — plateau (no collapse), governed admitted
    p99, counted sheds, EXACT acked-write verification, per-account peaks
    within budget, and an admission journal that reconstructs the run."""
    from redpanda_tpu.kafka.client import KafkaClient

    s = copy.deepcopy(OVERLOAD_SCENARIOS[name])
    s.update(overrides or {})
    if duration_s is not None:
        s["duration_s"] = float(duration_s)
    # the stack plumbing (configs, slow-ring threshold) reads these
    s.setdefault("objectives", _objectives(
        s["admitted_p99_ms"], 30_000, 8_000, 15_000, 8_000, 8_000, 20
    ))
    for key in ("group_members", "eos_pairs", "transform_readers",
                "tiered_readers"):
        s.setdefault(key, 0)

    tmp = None
    if base_dir is None:
        tmp = tempfile.TemporaryDirectory(prefix="loadgen-overload-")
        base_dir = tmp.name
    stack_cls = ProcStack if backend == "proc" else Stack
    stack = stack_cls(s, base_dir)
    clients: list = []
    k = s["records_per_op"]
    try:
        await stack.start()
        n_clients = max(2, min(8, s["producers"]))
        clients = await asyncio.gather(*(
            KafkaClient(stack.bootstrap()).connect() for _ in range(n_clients)
        ))
        admin = clients[0]
        await admin.create_topic(
            TOPIC, partitions=s["partitions"], replication=s["replication"]
        )
        for p in range(s["partitions"]):  # warmup: no first-op costs inside
            await admin.produce(
                TOPIC, p,
                [_overload_payload(200_000, 0, j, 64) for j in range(2)],
                acks=-1,
            )

        # ---- phase 1: the closed-loop knee
        counter = {"records": 0, "errors": 0}
        stop1 = asyncio.Event()
        tasks = [
            asyncio.create_task(_closed_loop_producer(
                i, clients[i % n_clients], s["partitions"], k,
                s["record_bytes"], stop1, counter,
            ))
            for i in range(s["producers"])
        ]
        t0 = time.monotonic()
        await asyncio.sleep(s["calibrate_s"])
        stop1.set()
        await asyncio.gather(*tasks, return_exceptions=True)
        calib_elapsed = time.monotonic() - t0
        capacity_rps = counter["records"] / calib_elapsed

        # ---- phase 2: open loop past the knee
        target_rps = capacity_rps * s["overload_factor"]
        op_rate = target_rps / k / s["producers"]
        ostats: dict[str, int] = {
            key: 0 for key in (
                "acked_ops", "acked_records", "shed_ops", "error_ops",
                "client_dropped",
            )
        }
        lats: list[float] = []
        acked_keys: set[str] = set()
        shed_keys: set[str] = set()
        stop2 = asyncio.Event()
        tasks = [
            asyncio.create_task(_open_loop_producer(
                i, clients[i % n_clients], s["partitions"], op_rate, k,
                s["record_bytes"], stop2, ostats, lats, acked_keys,
                shed_keys,
            ))
            for i in range(s["producers"])
        ]
        t0 = time.monotonic()
        await asyncio.sleep(s["duration_s"])
        stop2.set()
        await asyncio.gather(*tasks, return_exceptions=True)
        elapsed = time.monotonic() - t0
        admitted_rps = ostats["acked_records"] / elapsed

        # ---- verification + control-plane sweeps
        verify = await _overload_verify(
            admin, s["partitions"], acked_keys, shed_keys
        )
        resources = await _scrape_resources(stack)
        budgets_ok = True
        for node in resources:
            accounts = node.get("accounts")
            if not accounts:
                # an unreachable admin API or a plane-less broker is NOT
                # evidence the peaks stayed within budget — fail the gate
                # rather than pass it on missing data
                budgets_ok = False
                continue
            for acct in accounts.values():
                if acct["peak_bytes"] > acct["limit_bytes"]:
                    budgets_ok = False
        journal = await _scrape_admission_journal(stack)
        shed_total = await _scrape_counter_total(
            stack, "kafka_produce_admission_shed_total"
        )
        p99_ms = _quantile_ms(lats, 99.0)
        gates = {
            # the knee held: admitted throughput plateaus, never collapses
            "throughput_plateau": admitted_rps
            >= s["plateau_floor"] * capacity_rps,
            # ADMITTED requests stay governed (CO-safe client clock)
            "admitted_p99": p99_ms <= s["admitted_p99_ms"],
            # every client-observed shed is a counted server-side shed,
            # and the journal carries the episode(s)
            "shed_counted": ostats["shed_ops"] == 0 or (
                shed_total >= ostats["shed_ops"]
                and any(e["verdict"] == "shed" for e in journal)
            ),
            "verification_exact": verify["exact"],
            "budgets_respected": budgets_ok,
        }
        return {
            "scenario": name,
            "kind": "overload",
            "backend": stack.backend,
            "engine_devices": await _engine_devices(stack),
            "nodes": s["nodes"],
            "partitions": s["partitions"],
            "overload_factor": s["overload_factor"],
            "calibration": {
                "duration_s": round(calib_elapsed, 3),
                "capacity_records_per_s": round(capacity_rps, 1),
                "errors": counter["errors"],
            },
            "open_loop": {
                "duration_s": round(elapsed, 3),
                "offered_records_per_s": round(target_rps, 1),
                "admitted_records_per_s": round(admitted_rps, 1),
                "admitted_p50_ms": _quantile_ms(lats, 50.0),
                "admitted_p99_ms": p99_ms,
                "admitted_max_ms": _quantile_ms(lats, 100.0),
                **ostats,
            },
            "shed_total_server": shed_total,
            "verification": verify,
            "resources": resources,
            "admission_journal": journal,
            "gates": gates,
            "pass": all(gates.values()),
        }
    finally:
        for c in clients:
            try:
                await c.close()
            except Exception:
                pass
        await stack.stop()
        if tmp is not None:
            tmp.cleanup()


def run_overload(name: str, **kw) -> dict:
    return asyncio.run(run_overload_async(name, **kw))


# ================================================================ cli
def _diff_block(against_path: str, report: dict, band_pct) -> dict:
    """The release-flow judgment (ROADMAP item 6): this run's report
    diffed against a prior SLO artifact, objective-by-objective, with
    noise-band verdicts. Embedded in the written artifact so the verdict
    travels WITH the evidence; a broken baseline degrades to an error
    block, never a sunk run. Routed through tools/pulsediff.py (which
    delegates SLO/BENCH shapes to slodiff) so a timeline baseline judges
    too — one judge entry point for whatever the release flow hands it."""
    from tools import pulsediff

    try:
        baseline = pulsediff._load(against_path)
        d = pulsediff.diff_artifacts(baseline, report, band_pct)
        d["against"] = against_path
        return d
    except Exception as exc:  # noqa: BLE001 - the run itself succeeded
        return {"against": against_path, "error": repr(exc),
                "verdict": "NO_BASELINE"}


# latency objectives are noisier than the throughput skew the A/A bracket
# measures; a same-box band below this floor would misfire REGRESS on
# ordinary jitter, so the embedded judgments never judge tighter than this
AA_BAND_FLOOR_PCT = 5.0


def _aa_bracket(scenario: str, rounds: int, **run_kw) -> dict:
    """ROADMAP 7d same-session A/A bracket: run the scenario ``rounds``
    times back-to-back on the same code BEFORE the measured run, so the
    artifact carries its OWN noise band (max pairwise throughput skew,
    ``aa_band_pct``) instead of borrowing one measured on a different box
    on a different day — the exact aa_skew discipline BENCH artifacts
    already follow. The bracket also judges ITSELF (first vs last round
    through slodiff at the measured band): a bracket that cannot read
    PASS/WEATHER on its own same-code rounds has no business judging a
    release, and the embedded judgment says so on the artifact's face."""
    from tools import slodiff

    reports = [run_scenario(scenario, **run_kw) for _ in range(rounds)]
    rates = [
        r["throughput"]["produced_records_per_s"] for r in reports
    ]
    lo = min(rates)
    thr_skew = (max(rates) - lo) / lo * 100.0 if lo > 0 else 0.0
    # latency skew measured the same way, per objective across rounds:
    # same-code p99s on short windows jitter far more than throughput, and
    # a band that only priced throughput would misfire REGRESS on every
    # latency objective (observed live: 0.55% rate skew vs >5% p99 moves)
    by_name: dict[str, list[float]] = {}
    for r in reports:
        for o in r.get("objectives", []):
            v = o.get("observed_ms")
            if isinstance(v, (int, float)):
                by_name.setdefault(o["name"], []).append(float(v))
    lat_skews = [
        (max(vals) - min(vals)) / min(vals) * 100.0
        for vals in by_name.values()
        if len(vals) >= 2 and min(vals) > 0
    ]
    lat_skew = max(lat_skews) if lat_skews else 0.0
    band = max(thr_skew, lat_skew)
    block = {
        "rounds": rounds,
        "round_rates": [round(r, 1) for r in rates],
        "throughput_skew_pct": round(thr_skew, 2),
        "latency_skew_pct": round(lat_skew, 2),
        "aa_band_pct": round(band, 2),
        "band_floor_pct": AA_BAND_FLOOR_PCT,
    }
    if rounds >= 2:
        try:
            block["judgment"] = slodiff.diff_artifacts(
                reports[0], reports[-1], max(band, AA_BAND_FLOOR_PCT)
            )
        except Exception as exc:  # noqa: BLE001 - bracket stays advisory
            block["judgment"] = {"error": repr(exc), "verdict": "NO_DATA"}
    return block


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--scenario", default="smoke", help="see --list")
    p.add_argument("--report", default=None, metavar="SLO_r0N.json",
                   help="report path (default SLO_<scenario>.json)")
    p.add_argument("--chaos", action="store_true",
                   help="arm the scenario's honey-badger probe for the "
                        "measured window")
    p.add_argument("--backend", choices=("inproc", "proc"),
                   default="inproc",
                   help="inproc = 1..N Applications in this process "
                        "(judged off the shared registry); proc = REAL "
                        "broker processes judged from the federated "
                        "/metrics scrape — no one-loop ceiling on offered "
                        "load (tiered readers are inproc-only)")
    p.add_argument("--duration", type=float, default=None,
                   help="override the scenario's measured window (s)")
    p.add_argument("--clients-scale", type=float, default=1.0,
                   help="multiply every client count (8 ≈ thousands of "
                        "clients on real hardware)")
    p.add_argument("--list", action="store_true", help="list scenarios")
    p.add_argument(
        "--diff-against", default=None, metavar="SLO_r0N.json",
        help="ROADMAP item 6 release flow: after the run, judge this "
             "report against a prior artifact with tools/slodiff.py "
             "noise-band verdicts (PASS/WEATHER/REGRESS); the diff is "
             "embedded in the written report under 'slodiff'",
    )
    p.add_argument(
        "--diff-band-pct", type=float, default=None, metavar="PCT",
        help="noise band for --diff-against (default: the --ab-rounds "
             "measured band when bracketed, else slodiff's)",
    )
    p.add_argument(
        "--ab-rounds", type=int, default=0, metavar="K",
        help="same-session A/A bracket (ROADMAP 7d): run the scenario K "
             "extra times back-to-back BEFORE the measured run; the "
             "artifact then carries its OWN noise band (max pairwise "
             "throughput skew, 'aa_band_pct') plus the bracket's slodiff "
             "self-judgment, and --diff-against judges at that measured "
             "band instead of a borrowed default",
    )
    args = p.parse_args(argv)
    if args.list:
        for name, s in SCENARIOS.items():
            print(f"{name:<16} nodes={s['nodes']} partitions={s['partitions']} "
                  f"duration={s['duration_s']}s producers={s['producers']} "
                  f"chaos={s['chaos']['module']}.{s['chaos']['probe']}")
        for name, s in OVERLOAD_SCENARIOS.items():
            print(f"{name:<16} nodes={s['nodes']} partitions={s['partitions']} "
                  f"duration={s['duration_s']}s producers={s['producers']} "
                  f"open-loop x{s['overload_factor']} (overload gate)")
        return 0
    if args.ab_rounds and args.scenario in OVERLOAD_SCENARIOS:
        p.error("--ab-rounds brackets closed-loop scenarios only (the "
                "overload gate is judged against its own calibration run)")
    if args.scenario in OVERLOAD_SCENARIOS:
        report = run_overload(
            args.scenario, backend=args.backend, duration_s=args.duration,
        )
        out = args.report or f"SLO_{args.scenario}.json"
        if args.diff_against:
            report["slodiff"] = _diff_block(
                args.diff_against, report, args.diff_band_pct
            )
        with open(out, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
        print(json.dumps({
            "scenario": report["scenario"],
            "verdict": "PASS" if report["pass"] else "FAIL",
            "gates": report["gates"],
            "capacity_records_per_s":
                report["calibration"]["capacity_records_per_s"],
            "admitted_records_per_s":
                report["open_loop"]["admitted_records_per_s"],
            "admitted_p99_ms": report["open_loop"]["admitted_p99_ms"],
            "shed_ops": report["open_loop"]["shed_ops"],
            "report": out,
        }))
        return 0 if report["pass"] else 1
    if args.scenario not in SCENARIOS:
        p.error(f"unknown scenario {args.scenario!r}; --list shows them")
    aa_block = None
    if args.ab_rounds:
        # A/A rounds run WITHOUT chaos even when the measured run arms it:
        # the band prices same-code weather, not the probe's damage
        aa_block = _aa_bracket(
            args.scenario, args.ab_rounds, chaos=False,
            duration_s=args.duration, clients_scale=args.clients_scale,
            backend=args.backend,
        )
    report = run_scenario(
        args.scenario, chaos=args.chaos, duration_s=args.duration,
        clients_scale=args.clients_scale, backend=args.backend,
    )
    out = args.report or f"SLO_{args.scenario}.json"
    if aa_block is not None:
        report["aa"] = aa_block
        # top-level so pulsediff/slodiff sniff it as the artifact's band
        report["aa_band_pct"] = aa_block["aa_band_pct"]
    if args.diff_against:
        band = args.diff_band_pct
        if band is None and aa_block is not None:
            band = max(aa_block["aa_band_pct"], AA_BAND_FLOOR_PCT)
        report["slodiff"] = _diff_block(args.diff_against, report, band)
    with open(out, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
    verdict = "PASS" if report["pass"] else "FAIL"
    print(json.dumps({
        "scenario": report["scenario"],
        "verdict": verdict,
        **(
            {"slodiff": report["slodiff"]["verdict"],
             "slodiff_against": args.diff_against}
            if args.diff_against else {}
        ),
        **(
            {"aa_band_pct": aa_block["aa_band_pct"],
             "aa_judgment": (aa_block.get("judgment") or {}).get("verdict")}
            if aa_block is not None else {}
        ),
        "failed_objectives": report["failed"],
        "chaos": bool(report.get("chaos")),
        "exemplars": f"{report.get('exemplars_resolved', 0)}"
                     f"/{report.get('exemplars_total', 0)} resolved",
        "produced_records_per_s":
            report["throughput"]["produced_records_per_s"],
        "workloads_ok": report["workloads_ok"],
        "report": out,
    }))
    # a chaos run is EXPECTED to breach; its exit code reflects only that
    # the harness itself worked and the workloads stayed lossless
    if args.chaos:
        return 0 if report["workloads_ok"] else 1
    return 0 if (report["pass"] and report["workloads_ok"]) else 1


if __name__ == "__main__":
    sys.exit(main())
