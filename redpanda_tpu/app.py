"""Application assembly: wire and start every service.

Parity with redpanda/application.cc (wire_up_services :492-882, start
:884-1060): construct the service graph in dependency order, start it, and
stop in reverse on shutdown. Two modes, like the reference's single-broker
vs clustered deployments:

- single-node: storage → broker (direct-consensus partitions) → kafka
  server → admin server.
- clustered: + internal rpc server, raft group manager, controller (raft0),
  controller backend, metadata dissemination; the broker routes mutations
  through the controller dispatcher.
"""

from __future__ import annotations

import asyncio
import logging
import os
import threading

from redpanda_tpu import rpc
from redpanda_tpu.admin import AdminServer
from redpanda_tpu.config import Configuration
from redpanda_tpu.kafka.server.broker import Broker, BrokerConfig
from redpanda_tpu.kafka.server.protocol import KafkaServer
from redpanda_tpu.metrics import registry
from redpanda_tpu.observability import stages
from redpanda_tpu.storage.log_manager import StorageApi

logger = logging.getLogger("rptpu.app")


class Application:
    def __init__(self, config: Configuration) -> None:
        self.config = config
        self.storage: StorageApi | None = None
        self.broker: Broker | None = None
        self.kafka_server: KafkaServer | None = None
        self.admin: AdminServer | None = None
        # clustered-mode services
        self.rpc_server = None
        self.group_manager = None
        self.controller = None
        self.backend = None
        self.md_dissemination = None
        self.connections = None
        self.coproc = None
        self._stop_order: list = []

    # ------------------------------------------------------------ wiring
    def _broker_config(self) -> BrokerConfig:
        c = self.config
        return BrokerConfig(
            node_id=c.node_id,
            cluster_id=c.cluster_id,
            advertised_host=c.advertised_kafka_api_host,
            advertised_port=c.advertised_kafka_api_port,
            data_dir=c.data_directory,
            auto_create_topics=c.auto_create_topics_enabled,
            default_partitions=c.default_topic_partitions,
            default_replication=c.default_topic_replication,
            sasl_enabled=c.enable_sasl,
            superusers=[u for u in c.superusers.split(",") if u],
            unsafe_relaxed_acks=c.unsafe_relaxed_acks,
            target_quota_byte_rate=c.target_quota_byte_rate or None,
            kafka_qdc_enable=c.kafka_qdc_enable,
            kafka_qdc_max_latency_ms=float(c.kafka_qdc_max_latency_ms),
        )

    def _tls_for(self, prefix: str):
        """Build the hot-reloadable TLS context for a listener group, or
        None when that listener is plaintext."""
        from redpanda_tpu.security.tls import ReloadableTlsContext, TlsConfig

        c = self.config
        if not getattr(c, f"{prefix}_tls_enabled"):
            return None
        return ReloadableTlsContext(
            TlsConfig(
                enabled=True,
                cert_file=getattr(c, f"{prefix}_tls_cert_file"),
                key_file=getattr(c, f"{prefix}_tls_key_file"),
                truststore_file=getattr(c, f"{prefix}_tls_truststore_file", ""),
                require_client_auth=getattr(
                    c, f"{prefix}_tls_require_client_auth", False
                ),
            )
        )

    async def start(self) -> "Application":
        c = self.config
        # refuse unsuitable environments up front with actionable messages
        # (application.cc:364-373 check_environment -> syschecks)
        from redpanda_tpu.syschecks import check_environment

        check_environment(c)
        # pandaprobe: the probe histograms are always on; the span tracer
        # only spends clock reads + ring slots when the operator asks
        from redpanda_tpu.observability import tracer

        tracer.configure(
            enabled=c.trace_enabled,
            capacity=c.trace_ring_capacity,
            slow_threshold_ms=float(c.trace_slow_threshold_ms),
            # namespace trace/span ids by node: cluster-assembled traces
            # merge by trace id across brokers, so ids must never collide
            node_id=c.node_id,
        )
        # pandapulse: the flight recorder rides the tracer's commit path
        # (span sink — one bounded-deque append per committed span), so it
        # installs whenever enabled and simply sees nothing until
        # trace_enabled flips the plane on (the pandascope rollout-gate
        # posture). The wall profiler is its own low-frequency thread,
        # profile_hz=0 keeps it entirely absent.
        from redpanda_tpu.observability.pulse import pulse

        pulse.configure(
            enabled=c.pulse_enabled,
            ring_capacity=c.pulse_ring_capacity,
            profile_hz=float(c.profile_hz),
        )
        # pandatrend: the bounded metrics-history ring (delta windows over
        # the whole registry, EWMA breach journaling into the governor's
        # trend domain, Perfetto counter tracks). interval 0 = off and NO
        # recorder thread — the profile_hz=0 contract.
        from redpanda_tpu.observability.history import history

        history.configure(
            interval_s=float(c.history_interval_s),
            windows=c.history_windows,
            max_bytes=c.history_max_bytes,
        )
        # loopwatch: the event loop's lag histogram and stall ring, the
        # interpreter's collection pauses (always on; one ticker task, one
        # watchdog thread, gc callbacks; shared by in-process brokers)
        from redpanda_tpu.observability.loopwatch import loopwatch

        loopwatch.start()
        self._stop_order.append(loopwatch)
        # SLO engine: operator objectives (or the lenient broker defaults)
        # judged at GET /v1/slo; loading arms per-metric breach thresholds
        # so over-threshold observations record trace exemplars
        from redpanda_tpu.observability.slo import slo

        if c.slo_objectives_file:
            slo.configure_from_file(c.slo_objectives_file)
        else:
            slo.arm_exemplars()
        # rpk iotune's characterization, when present (io-config.json in the
        # data dir): published below as metrics for operators/dashboards
        from redpanda_tpu.config.io_config import load_io_config

        self.io_config = load_io_config(c.data_directory)
        self.rpc_tls = self._tls_for("rpc_server")
        log_config = None
        if c.debug_sanitize_files:
            from redpanda_tpu.storage import file_sanitizer
            from redpanda_tpu.storage.log import LogConfig

            # arm BEFORE any storage handle opens (the kvstore WAL opens
            # during StorageApi.start, ahead of the first DiskLog.open)
            file_sanitizer.enable()
            log_config = LogConfig(
                base_dir=os.path.join(c.data_directory, "data"),
                sanitize_files=True,
            )
        # Budget plane (resource_mgmt): installed BEFORE storage so the
        # first kvstore/log appends already charge the storage account;
        # the split + thresholds come from config (memory_groups posture).
        from redpanda_tpu.resource_mgmt import admission as rm_admission
        from redpanda_tpu.resource_mgmt import budgets as rm_budgets

        if getattr(c, "coproc_leakwatch", False):
            # must flip BEFORE the plane is built: accounts bind their
            # balance recorder (or lack of one) at construction
            from redpanda_tpu.coproc import leakwatch

            leakwatch.enable()
        self.budget_plane = rm_budgets.BudgetPlane(
            total_bytes=c.resource_memory_total_mb << 20,
            warn_pct=c.resource_pressure_warn_pct,
            critical_pct=c.resource_pressure_critical_pct,
            register_gauges=True,
        )
        rm_budgets.install(self.budget_plane)
        self.storage = await StorageApi(c.data_directory, log_config).start()
        self._stop_order.append(self.storage)
        self.broker = Broker(self._broker_config(), self.storage)
        self.broker.budget_plane = self.budget_plane
        self.broker.produce_admission = rm_admission.AdmissionController(
            self.budget_plane.account("kafka_produce"), "kafka_produce",
            warn_pct=self.budget_plane.warn_pct,
            on_episode=self._journal_admission_episode,
        )

        is_clustered = bool(c.seed_servers)
        if is_clustered:
            await self._start_cluster_services()

        self.kafka_tls = self._tls_for("kafka_api")
        self.kafka_server = await KafkaServer(
            self.broker, c.kafka_api_host, c.kafka_api_port, tls=self.kafka_tls
        ).start()
        # ephemeral bind (port 0, tests) must advertise the real port or
        # metadata sends clients to a dead address
        adv = c.advertised_kafka_api_port
        if c.kafka_api_port == 0 or adv == 0:
            adv = self.kafka_server.port
        self.broker.config.advertised_port = adv
        self._stop_order.append(self.kafka_server)

        self.admin_tls = self._tls_for("admin_api")
        self.admin = await AdminServer(
            self.broker,
            config=c,
            group_manager=self.group_manager,
            controller=self.controller,
            host=c.admin_api_host,
            port=c.admin_api_port,
            require_auth=c.admin_api_require_auth,
            auth_token=c.admin_api_auth_token or None,
            tls=self.admin_tls,
        ).start()
        self.admin.tls_contexts = {
            "kafka": self.kafka_tls,
            "rpc": self.rpc_tls,
            "admin": self.admin_tls,
        }
        self._stop_order.append(self.admin)

        if is_clustered:
            # announce ourselves AFTER the admin server is up so the
            # register_node command can advertise the real (possibly
            # ephemeral) admin port — the cluster observability plane
            # (trace fan-out, /metrics federation) dials peers by it.
            # In a real multi-process cluster the first election only
            # completes after a MAJORITY of seed brokers finish interpreter
            # startup (~10s each), so registration must outwait peers, not
            # give up in the default few retries (tests/chaos drives this
            # path with SIGKILLed real processes).
            from redpanda_tpu.cluster import commands as ccmds

            await self._dispatcher.replicate(
                ccmds.register_node_cmd(
                    c.node_id, c.rpc_server_host, self.rpc_server.port,
                    c.advertised_kafka_api_host, c.advertised_kafka_api_port,
                    admin_port=self.admin.port,
                ),
                retries=300,
            )

        if c.coproc_enable:
            await self._start_coproc()

        if c.cloud_storage_enabled:
            await self._start_archival()
            # admin surface (POST /v1/archival/run_once, GET .../status):
            # the admin server started earlier, so hand it the scheduler
            self.admin.archival = self.archival

        self._register_metrics()
        await self.storage.log_mgr.start_housekeeping(
            c.log_compaction_interval_ms / 1000.0
        )
        logger.info("application started (node %d)", c.node_id)
        return self

    async def _start_cluster_services(self) -> None:
        """Internal RPC + raft + controller (application.cc :521-610)."""
        from redpanda_tpu.cluster import (
            Controller,
            ControllerBackend,
            ControllerDispatcher,
            ClusterService,
            MetadataCache,
            MetadataDisseminationService,
            PartitionLeadersTable,
            ShardTable,
        )
        from redpanda_tpu.cluster import commands as ccmds
        from redpanda_tpu.cluster.metadata_dissemination import md_dissemination_service
        from redpanda_tpu.raft.consensus import RaftTimings
        from redpanda_tpu.raft.group_manager import GroupManager
        from redpanda_tpu.raft.types import VNode

        c = self.config
        rpc_client_ssl = (
            self.rpc_tls.client_context() if self.rpc_tls is not None else None
        )
        self.connections = rpc.ConnectionCache(ssl_context=rpc_client_ssl)
        self_vnode = VNode(c.node_id, 0)
        self.group_manager = GroupManager(
            self_vnode, self.storage, self.connections,
            timings=RaftTimings(
                election_timeout_ms=c.raft_election_timeout_ms,
                heartbeat_interval_ms=c.raft_heartbeat_interval_ms,
            ),
            recovery_concurrency=c.raft_recovery_concurrency,
        )
        # raft device plane (BASELINE config 5): batched follower CRC
        # validation + per-tick cross-group ack tally, both behind their
        # own measured host-vs-device probe (raft/device_plane.py)
        from redpanda_tpu.raft import device_plane as raft_device_plane

        raft_device_plane.configure(
            crc_validate=getattr(c, "raft_device_crc_validate", False),
            vote_tally=getattr(c, "raft_device_vote_tally", False),
            # the plane shares the coproc engine's multi-chip topology:
            # >= 2 devices gives the sharded crc+vote step the psum lane
            mesh_devices=getattr(c, "coproc_mesh_devices", 0),
            mesh_backend=getattr(c, "coproc_mesh_backend", "") or None,
        )
        self.controller = Controller(self_vnode, self.group_manager, self.connections)
        # One topic table per node: the controller STM's replicated view IS
        # the broker's view (topic_table.h — metadata_cache aggregates the
        # same table). The broker's standalone-mode private table is only
        # for controller-less single-node runs.
        self.broker.topic_table = self.controller.topic_table
        dispatcher = ControllerDispatcher(self.controller, self.connections)
        leaders = PartitionLeadersTable()
        self.md_dissemination = MetadataDisseminationService(
            c.node_id, leaders, self.controller.members, self.connections
        )
        self.backend = ControllerBackend(
            self_vnode, self.controller.topic_table, self.group_manager,
            self.broker.partition_manager, leaders_table=leaders,
            shard_table=ShardTable(),
            finish_move=lambda ntp, reps: dispatcher.replicate(
                ccmds.finish_moving_cmd(ntp, reps)
            ),
        )
        def _on_leadership(cons):
            self.md_dissemination.notify_leadership(
                cons.ntp, cons.leader_id, cons.term
            )
            # Coordinator failover: gaining a group-topic partition means
            # replaying its log into group state (group_manager.cc
            # handle_leader_change), or committed offsets vanish for every
            # group hashed onto the partition.
            if (
                cons.ntp.topic == "__consumer_offsets"
                and cons.leader_id == c.node_id
            ):
                self.broker.group_coordinator.on_leadership_gained(
                    cons.ntp.partition
                )

        self.group_manager.register_leadership_notification(_on_leadership)
        from redpanda_tpu.resource_mgmt import admission as rm_admission

        proto = rpc.SimpleProtocol(
            node_id=c.node_id,
            # dispatch-time shed (STATUS_BACKPRESSURE) once inflight
            # requests or their body bytes exceed the rpc account — peers
            # resend; nothing ran, nothing is lost
            inflight_gate=rm_admission.InflightGate(
                self.budget_plane.account("rpc"),
                max_requests=c.rpc_server_max_inflight_requests,
                on_episode=self._journal_admission_episode,
            ),
        )
        self.group_manager.register_service(proto)
        ClusterService(self.controller, dispatcher).register(proto)
        # tx gateway: cross-node marker fan-out + staged-offset routing
        from redpanda_tpu.cluster.tx_gateway import TxGatewayService

        TxGatewayService(self.broker).register(proto)
        proto.register_service(
            rpc.ServiceHandler(md_dissemination_service, self.md_dissemination)
        )
        self.rpc_server = rpc.Server(
            c.rpc_server_host, c.rpc_server_port, tls=self.rpc_tls
        )
        self.rpc_server.set_protocol(proto)
        await self.rpc_server.start()
        await self.group_manager.start()
        self._stop_order += [self.rpc_server, self.group_manager]

        seeds = []
        for hp in c.seed_servers.split(","):
            if not hp:
                continue
            node_str, _, addr = hp.partition("@")
            host, _, port = addr.partition(":")
            seeds.append((int(node_str), host, int(port)))
        for node_id, host, port in seeds:
            if node_id != c.node_id:
                self.connections.register(node_id, host, port)
        seed_vnodes = [VNode(nid, 0) for nid, _, _ in seeds]
        await self.controller.start(seed_vnodes)
        await self.backend.start()
        await self.md_dissemination.start()
        # A (re)starting broker only hears about FUTURE elections from the
        # gossip loop; leaders elected while it was down must be pulled from
        # a peer (metadata_dissemination get_leadership_request semantics).
        for node_id, _h, _p in seeds:
            if node_id == c.node_id:
                continue
            try:
                await self.md_dissemination.pull_initial(node_id)
                break
            except Exception:
                continue  # peer down/fresh cluster: gossip will catch us up
        self._stop_order += [self.md_dissemination, self.backend, self.controller]

        self.broker.controller_dispatcher = dispatcher
        self.broker.controller_leader_fn = lambda: self.controller.leader_id
        self.broker.security.attach(self.controller)
        self.broker.data_policies.attach(self.controller)
        self.broker.metadata_cache = MetadataCache(
            self.controller.topic_table, self.controller.members, leaders
        )
        from redpanda_tpu.cluster.tx_gateway import TxRouter

        self.broker.tx_coordinator.router = TxRouter(
            self.broker, self.broker.metadata_cache, self.connections
        )
        # node registration happens in start() once the admin server is up
        # (its port rides the register_node command for pandascope fan-out)
        self._dispatcher = dispatcher

    @staticmethod
    def _journal_admission_episode(kind: str, info: dict) -> None:
        """Shed episodes land in the process decision journal (ADMISSION
        domain) so /v1/governor reconstructs every shed — one entry per
        episode boundary, never per request (the ring is bounded)."""
        from redpanda_tpu.coproc import governor as _governor

        _governor.journal_record(
            _governor.ADMISSION, kind,
            f"{info.get('subsystem', '?')} admission {kind}", info,
        )

    async def _start_coproc(self) -> None:
        from redpanda_tpu.utils.platform import enable_compile_cache

        # before the engine's first jit: the cache directory is part of
        # JAX's config, read when the first program compiles
        enable_compile_cache()
        from redpanda_tpu.coproc.api import CoprocApi

        self.coproc = await CoprocApi(self.broker, self.config).start()
        self.broker.coproc_api = self.coproc
        self._stop_order.append(self.coproc)

    async def _start_archival(self) -> None:
        """Tiered storage, wired only when enabled (application.cc:630-649)."""
        from redpanda_tpu.archival import ArchivalScheduler
        from redpanda_tpu.cloud_storage import Remote
        from redpanda_tpu.s3 import S3Client

        c = self.config
        client = S3Client(
            c.cloud_storage_bucket,
            region=c.cloud_storage_region,
            endpoint=c.cloud_storage_api_endpoint or None,
            access_key=c.cloud_storage_access_key,
            secret_key=c.cloud_storage_secret_key,
        )
        import os

        from redpanda_tpu.cloud_storage.cache import CacheService

        cache = CacheService(
            os.path.join(c.data_directory, "cloud_storage_cache"),
            max_bytes=c.cloud_storage_cache_size,
        )
        self.archival = await ArchivalScheduler(
            self.broker, Remote(client),
            interval_s=c.cloud_storage_segment_max_upload_interval_sec,
            cache=cache,
        ).start()
        self._stop_order.append(self.archival)
        self._s3_client = client

    def _register_metrics(self) -> None:
        b = self.broker
        registry.gauge(
            "partitions_total", lambda: len(b.partition_manager.partitions()),
            "Local partition replicas",
        )
        registry.gauge(
            "topics_total", lambda: len(b.topic_table.topics()), "Known topics"
        )
        bc = self.storage.log_mgr.batch_cache
        registry.gauge("batch_cache_hits", lambda: bc.hits, "Batch cache hits")
        registry.gauge(
            "batch_cache_misses", lambda: bc.misses, "Batch cache misses"
        )
        registry.gauge(
            "batch_cache_bytes", lambda: bc.bytes_used, "Batch cache bytes"
        )
        lm = self.storage.log_mgr
        registry.gauge(
            "compaction_backlog_bytes",
            lambda: lm.compaction_backlog(),
            "Closed un-compacted bytes (backlog controller input)",
        )
        registry.gauge(
            "compaction_interval_s",
            lambda: lm.backlog_controller.last_interval,
            "Backlog-controlled compaction pass interval",
        )
        rc = self.storage.log_mgr.readers_cache
        registry.gauge("readers_cache_hits", lambda: rc.hits, "Read cursor hits")
        registry.gauge(
            "readers_cache_misses", lambda: rc.misses, "Read cursor misses"
        )
        registry.gauge(
            "readers_cache_window_reads",
            lambda: rc.window_reads,
            "Scanned reads served from a cursor's window (no file read)",
        )
        registry.gauge(
            "readers_cache_file_reads", lambda: rc.file_reads, "Segment file preads"
        )
        registry.gauge(
            "readers_cache_window_bytes",
            lambda: rc.window_bytes,
            "Bytes held by read cursors' windows",
        )
        if self.coproc is not None:
            eng = self.coproc.engine
            # pool size is static per process; the busy-worker gauge
            # (coproc_host_pool_busy_workers) lives in observability.probes
            registry.gauge(
                "coproc_host_workers",
                lambda: float(eng._host_workers),
                "Configured width of the mesh lane's per-device host ladder",
            )
        from redpanda_tpu.observability import tracer

        registry.gauge(
            "trace_enabled", lambda: 1.0 if tracer.enabled else 0.0,
            "pandaprobe span tracer armed",
        )
        if self.io_config:
            io = self.io_config
            registry.gauge(
                "iotune_seq_write_mb_s",
                lambda: io["seq_write_mb_s"],
                "iotune: sequential write MB/s",
            )
            registry.gauge(
                "iotune_fsync_p99_ms",
                lambda: io["fsync_4k"]["p99_ms"],
                "iotune: 4k fsync p99 latency",
            )

    # ------------------------------------------------------------ shutdown
    async def stop(self) -> None:
        """Reverse-order stop (application.cc:179-185). Each service's stop
        is a stage (``app.stop.<service>``: ring only, no histogram, off the
        profile), and one log line at the end says what each took and which
        threads still live: where a stop is slow, which service held it."""
        took = []
        with stages.stage("app.stop", annotate=False, root=True):
            for svc in reversed(self._stop_order):
                name = type(svc).__name__
                st = stages.stage("app.stop." + name, annotate=False)
                try:
                    with st:
                        await svc.stop()
                except Exception:
                    logger.exception("stopping %s failed", name)
                took.append(f"{name} {st.t1 - st.t0:.3f}")
        self._stop_order.clear()
        logger.info(
            "stopped %d services (s each: %s); threads alive: %s",
            len(took), ", ".join(took) or "-",
            ", ".join(sorted(t.name for t in threading.enumerate())),
        )
        # uninstall OUR plane (if still current): a stopped app's module-
        # level plane would otherwise keep gating later brokers/tests in
        # this interpreter and pin its gauges' weakref alive forever
        from redpanda_tpu.resource_mgmt import budgets as rm_budgets

        if (
            getattr(self, "budget_plane", None) is not None
            and rm_budgets.current() is self.budget_plane
        ):
            rm_budgets.install(None)
        if getattr(self, "_s3_client", None) is not None:
            await self._s3_client.close()
            self._s3_client = None
        if self.connections is not None:
            await self.connections.close()

    async def run_forever(self) -> None:
        stop_event = asyncio.Event()
        try:
            await stop_event.wait()
        finally:
            await self.stop()
