"""Property tables + the broker configuration.

See package docstring. Reference: config/base_property.h:30 (metadata +
validation), config/property.h:25 (typed), config/configuration.cc (the
property set), application.cc:312-362 (YAML hydration to every shard).
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import Any, Callable


class ValidationError(ValueError):
    pass


@dataclass
class Property:
    name: str
    description: str
    default: Any
    type: type = str
    validator: Callable[[Any], str | None] | None = None  # returns error or None
    needs_restart: bool = True

    def coerce(self, value: Any) -> Any:
        if value is None:
            return None
        if self.type is bool and isinstance(value, str):
            return value.lower() in ("1", "true", "yes", "on")
        try:
            return self.type(value)
        except (TypeError, ValueError) as e:
            raise ValidationError(f"{self.name}: {e}") from e

    def validate(self, value: Any) -> None:
        if self.validator is not None:
            err = self.validator(value)
            if err:
                raise ValidationError(f"{self.name}: {err}")


def _positive(v) -> str | None:
    return None if v is None or v > 0 else "must be positive"


def _non_negative(v) -> str | None:
    return None if v is None or v >= 0 else "must be >= 0"


def _port(v) -> str | None:
    if v is None:
        return "port may not be empty"
    return None if 0 <= v <= 65535 else "not a port"


# The reference's property groups (configuration.cc), trimmed to the knobs
# this build actually consumes plus the well-known ones operators expect.
PROPERTIES: list[Property] = [
    # --- identity / listeners
    Property("node_id", "Unique broker id", 0, int, _non_negative),
    Property("cluster_id", "Cluster identity string", "redpanda_tpu"),
    Property("data_directory", "Data directory", "/var/lib/redpanda_tpu"),
    Property("kafka_api_host", "Kafka API bind host", "127.0.0.1"),
    Property("kafka_api_port", "Kafka API port", 9092, int, _port),
    Property("advertised_kafka_api_host", "Advertised kafka host", "127.0.0.1"),
    Property("advertised_kafka_api_port", "Advertised kafka port", 9092, int, _port),
    Property("rpc_server_host", "Internal RPC bind host", "127.0.0.1"),
    Property("rpc_server_port", "Internal RPC port", 33145, int, _port),
    Property("admin_api_host", "Admin API bind host", "127.0.0.1"),
    Property("admin_api_port", "Admin API port", 9644, int, _port),
    Property("admin_api_require_auth", "Require auth on the admin API", False, bool),
    Property("admin_api_auth_token", "Static bearer token for the admin API", ""),
    # --- TLS (per listener, hot-reloadable: application.cc:704-719)
    Property("kafka_api_tls_enabled", "TLS on the kafka listener", False, bool),
    Property("kafka_api_tls_cert_file", "Kafka listener cert (PEM)", ""),
    Property("kafka_api_tls_key_file", "Kafka listener key (PEM)", ""),
    Property("kafka_api_tls_truststore_file", "Kafka listener CA bundle", ""),
    Property("kafka_api_tls_require_client_auth", "Kafka mTLS", False, bool),
    Property("rpc_server_tls_enabled", "TLS on the internal RPC mesh", False, bool),
    Property("rpc_server_tls_cert_file", "RPC cert (PEM)", ""),
    Property("rpc_server_tls_key_file", "RPC key (PEM)", ""),
    Property("rpc_server_tls_truststore_file", "RPC CA bundle", ""),
    Property("rpc_server_tls_require_client_auth", "RPC mTLS", False, bool),
    Property("admin_api_tls_enabled", "TLS on the admin API", False, bool),
    Property("admin_api_tls_cert_file", "Admin cert (PEM)", ""),
    Property("admin_api_tls_key_file", "Admin key (PEM)", ""),
    Property("admin_api_tls_truststore_file", "Admin CA bundle", ""),
    Property("admin_api_tls_require_client_auth", "Admin mTLS", False, bool),
    Property("seed_servers", "Seed broker list host:port,...", ""),
    # --- raft timings (configuration.cc raft group)
    Property("raft_election_timeout_ms", "Election timeout", 1500, int, _positive, needs_restart=False),
    Property("raft_heartbeat_interval_ms", "Leader heartbeat interval", 150, int, _positive, needs_restart=False),
    Property("raft_recovery_concurrency", "Parallel follower recoveries", 4, int, _positive),
    # --- storage (log_config application.cc:421-443)
    Property("log_segment_size", "Segment roll size bytes", 128 * 1024 * 1024, int, _positive),
    Property("log_retention_bytes", "Default retention bytes (-1 none)", -1, int),
    Property("log_retention_ms", "Default retention ms (-1 none)", 7 * 24 * 3600 * 1000, int),
    Property("log_compaction_interval_ms", "Housekeeping cadence", 10_000, int, _positive),
    Property("fsync_on_append", "Flush to disk on quorum writes", True, bool),
    # --- kafka server
    Property("auto_create_topics_enabled", "Auto-create topics on metadata", True, bool),
    Property("default_topic_partitions", "Default partition count", 1, int, _positive),
    Property("default_topic_replication", "Default replication factor", 1, int, _positive),
    Property("group_topic_partitions", "__consumer_offsets partitions", 16, int, _positive),
    Property("unsafe_relaxed_acks", "CONSISTENCY-TESTING ONLY: ack acks=-1 at leader level (deliberately unsafe)", False, bool),
    Property("target_quota_byte_rate", "Per-client produce quota B/s (0 off)", 0, int, _non_negative, needs_restart=False),
    Property("kafka_qdc_enable", "Queue-depth latency control on the kafka path", False, bool),
    Property("kafka_qdc_max_latency_ms", "qdc target handler latency", 80, int, _positive),
    Property("debug_sanitize_files", "Debug file-handle sanitizer on storage I/O", False, bool),
    # --- observability (pandaprobe; probes at /metrics are always on).
    # All three snapshot into the tracer once at app start: needs_restart
    # stays True until a runtime config-set path actually re-applies them
    # (tracer.configure() itself is hot-safe when that path arrives).
    Property("trace_enabled", "Record pandaprobe spans (GET /v1/trace/recent)", False, bool),
    Property("trace_ring_capacity", "Bounded span ring size", 2048, int, _positive),
    Property("trace_slow_threshold_ms", "Spans over this land in the slow-request log", 500, int, _positive),
    # pandapulse (observability/pulse.py): the flight recorder installs a
    # span sink on the tracer commit path; it records whenever tracing is
    # on (trace_enabled is the whole plane's rollout gate). profile_hz
    # runs the wall-sampling profiler thread; 0 = no thread at all.
    Property(
        "pulse_enabled",
        "Install the pandapulse flight recorder (per-launch lifecycle "
        "timelines at GET /v1/profile/timeline; records while tracing is on)",
        True, bool,
    ),
    Property(
        "pulse_ring_capacity",
        "Bounded flight-recorder span ring size",
        8192, int, _positive,
    ),
    Property(
        "profile_hz",
        "Wall-profile sampling rate for the pandapulse profiler thread "
        "(0 = off, no thread; ~19 Hz recommended when on — prime, aliases "
        "with nothing periodic)",
        0.0, float, _non_negative,
    ),
    # pandatrend (observability/history.py): the bounded metrics-history
    # ring behind GET /v1/history, `rpk debug trend` and the Perfetto
    # counter tracks. interval 0 = off AND no recorder thread (the
    # profile_hz=0 contract); the ring is bounded both by window count
    # and by history_max_bytes, evicting oldest-first.
    Property(
        "history_interval_s",
        "Metrics-history sampling cadence in seconds (pandatrend delta "
        "windows; 0 = off, no recorder thread)",
        5.0, float, _non_negative,
    ),
    Property(
        "history_windows",
        "Maximum retained metrics-history delta windows (oldest evicted "
        "first; the byte budget below also bounds the ring)",
        240, int, _positive,
    ),
    Property(
        "history_max_bytes",
        "Estimated byte budget for the metrics-history ring (label-"
        "cardinality growth evicts history, never grows the process)",
        4 * 1024 * 1024, int, _positive,
    ),
    Property(
        "slo_objectives_file",
        "YAML/JSON SLO objective spec judged at GET /v1/slo (empty = the "
        "lenient broker defaults in observability/slo.py); loading a spec "
        "arms per-metric breach thresholds for trace exemplars",
        "",
    ),
    # --- security
    Property("enable_sasl", "Require SASL on the kafka listener", False, bool),
    Property("superusers", "Comma-separated superuser principals", ""),
    # --- tx / idempotence
    Property("enable_idempotence", "Accept idempotent producers", True, bool),
    Property("enable_transactions", "Accept transactional producers", True, bool),
    Property("transactional_id_expiration_ms", "Idle tx expiry", 15 * 60 * 1000, int, _positive),
    # --- resource management / budget plane (resource_mgmt/budgets.py;
    # memory_groups.h posture: one total split into per-subsystem accounts,
    # admission sheds with retriable backpressure on exhaustion)
    Property(
        "resource_memory_total_mb",
        "Total byte budget the plane carves into per-subsystem accounts "
        "(kafka_produce 25%, rpc 12.5%, coproc 25%, storage 25%, raft "
        "12.5% — see resource_mgmt/budgets.py DEFAULT_SPLIT)",
        512, int, _positive,
    ),
    Property(
        "resource_pressure_warn_pct",
        "Worst-account occupancy fraction at which MemoryPressure reads "
        "warn (autotune shrinks launch knobs)",
        0.75, float, _positive,
    ),
    Property(
        "resource_pressure_critical_pct",
        "Occupancy fraction at which MemoryPressure reads critical (arena "
        "free-list trims, column cache halves, launch knobs floor)",
        0.90, float, _positive,
    ),
    Property(
        "rpc_server_max_inflight_requests",
        "Concurrent dispatched requests the internal rpc server admits "
        "before shedding with STATUS_BACKPRESSURE (body bytes are bounded "
        "separately by the rpc memory account)",
        1024, int, _positive,
    ),
    # --- coproc (configuration.h:57-61)
    Property("coproc_enable", "Enable the TPU transform engine", False, bool),
    Property("coproc_max_batch_size", "Max read per ntp per tick", 32 * 1024, int, _positive),
    Property("coproc_max_inflight_bytes", "Read semaphore budget", 10 * 1024 * 1024, int, _positive),
    Property(
        "coproc_max_value_bytes",
        "The widest value the device (payload) lane transforms: a wider one is dropped, never truncated, and counted (coproc_oversize_rows_total). Values over 1,024 B are staged in width classes of their own (2,048, 4,096, ... up to this limit) as further parts of the same launch",
        1024, int, _positive,
    ),
    Property("coproc_offset_flush_interval_ms", "Offset snapshot cadence", 300_000, int, _positive),
    Property(
        "coproc_host_workers",
        "Width of the mesh lane's per-device host ladder: worker threads that run the devices' parse/extract and framing concurrently (0 or 1 = one after another; unused without coproc_mesh_devices)",
        min(4, os.cpu_count() or 1), int, _non_negative,
    ),
    Property(
        "coproc_gather_frame",
        "Zero-copy harvest: frame byte-identity transform output straight from the joined blob's (offset, len) columns instead of packing a padded row matrix",
        True, bool,
    ),
    Property(
        "coproc_device_column_cache_mb",
        "LRU byte budget for the device-resident column cache (repeat scripts over unchanged batch windows skip the host parse/extract ladder and the H2D replay); 0 disables it",
        32, int, _non_negative,
    ),
    # --- coproc launch knobs / autotune (governor ADMISSION domain)
    Property(
        "coproc_group_ticks_per_launch",
        "How many ticks' worth of input one coproc launch fuses (the "
        "per-ntp read budget multiplier); the autotune starting point",
        1, int, _positive,
    ),
    Property(
        "coproc_group_ticks_max",
        "Autotune cap on group_ticks_per_launch",
        8, int, _positive,
    ),
    Property(
        "coproc_launch_depth",
        "Concurrent submit+harvest regions across all script fibers; the "
        "autotune starting point",
        4, int, _positive,
    ),
    Property(
        "coproc_launch_depth_max",
        "Autotune cap on launch_depth",
        8, int, _positive,
    ),
    Property(
        "coproc_autotune_launch",
        "Let the governor move group_ticks_per_launch/launch_depth "
        "dynamically (hysteresis-bounded, journaled under the admission "
        "domain) off the success-only dispatch-leg p99.9 and the budget "
        "plane's occupancy, and, where a script launches nothing on the "
        "device, grow group_ticks_per_launch on a backlog (a run of "
        "launches that the read budget cut short; counted in launches, "
        "not seconds); false pins the static knobs against both rules",
        True, bool,
    ),
    # --- coproc multi-chip mesh (coproc/meshrunner.py)
    Property(
        "coproc_mesh_devices",
        "Shard the coproc partition axis over an N-device mesh (pjit/shard_map; per-device sub-launches, one SPMD predicate program). 0/1 keeps the single-device engine; clamped to the devices actually present",
        0, int, _non_negative,
    ),
    Property(
        "coproc_mesh_backend",
        "jax backend whose devices the mesh spans ('' = default backend; 'cpu' = the virtual host-platform mesh, for forced-multi-device runs)",
        "",
    ),
    Property(
        "coproc_mesh_probe",
        "Measure mesh-vs-single-device on the first representative launch and pin the winner (PROBE_MARGIN posture, journaled in the governor 'mesh' domain); false pins 'mesh' unmeasured",
        True, bool,
    ),
    # --- raft device plane (raft/device_plane.py, BASELINE config 5);
    # the plane spans the coproc mesh topology (coproc_mesh_devices /
    # coproc_mesh_backend >= 2 devices = the sharded crc+vote psum step)
    Property(
        "raft_device_crc_validate",
        "Follower-side batched CRC validation of every append_entries blob in one kernel call (the device plane's measured probe picks host or device; both bit-exact). Off = appends are not CRC-checked on the follower (the historical posture)",
        False, bool,
    ),
    Property(
        "raft_device_vote_tally",
        "Per-tick cross-group heartbeat ack tally as one batched reduction (mesh psum on multi-chip, np.sum on host) feeding HeartbeatManager.last_tick_acks; off = no tally",
        False, bool,
    ),
    # --- coproc fault domains (coproc/faults.py)
    Property(
        "coproc_device_deadline_ms",
        "Per-attempt deadline on every device interaction (dispatch, mask fetch, harvest); a wedged fetch is abandoned after this",
        30_000, int, _positive,
    ),
    Property(
        "coproc_launch_retries",
        "Bounded retries per device interaction before the launch fails closed onto the pure-host path",
        2, int, _non_negative,
    ),
    Property(
        "coproc_retry_backoff_ms",
        "Base exponential backoff between device retries (jittered 50-100%)",
        50, int, _positive,
    ),
    Property(
        "coproc_breaker_threshold",
        "Consecutive device failures that trip the engine's circuit breaker to open (host execution)",
        5, int, _positive,
    ),
    Property(
        "coproc_breaker_cooldown_ms",
        "Open-breaker cooldown before one half-open probe launch may re-admit the device",
        30_000, int, _positive,
    ),
    # --- coproc governor / decision plane (coproc/governor.py)
    Property(
        "coproc_adaptive_deadline",
        "Derive per-domain device deadlines from the observed coproc_stage_latency_us p99.9 (coproc_device_deadline_ms stays the floor and is never undercut); false pins every domain to the static knob",
        True, bool,
    ),
    Property(
        "coproc_adaptive_deadline_margin",
        "Multiplier over the observed stage p99.9 when deriving an adaptive deadline (clamped to [floor, 8x floor])",
        4.0, float, _positive,
    ),
    Property(
        "coproc_governor_journal_capacity",
        "Bounded in-memory governor decision journal size (GET /v1/governor, rpk debug governor)",
        256, int, _positive,
    ),
    Property(
        "coproc_lockwatch",
        "Debug: wrap the engine's named locks in a lock-order recorder that journals acquisition edges into the governor 'lockwatch' domain (validates the pandalint static acquisition graph); off = no wrapper installed, zero overhead",
        False, bool,
    ),
    Property(
        "coproc_leakwatch",
        "Debug: wrap the broker's budget accounts/gates/arenas in an acquire-release balance recorder that journals per-site deltas into the governor 'leakwatch' domain (validates the pandalint RSL16xx lifecycle model); off = no proxy installed, zero overhead",
        False, bool,
    ),
    # --- tiered storage (cloud_storage_* group)
    Property("cloud_storage_enabled", "Enable tiered storage", False, bool),
    Property("cloud_storage_bucket", "S3 bucket", ""),
    Property("cloud_storage_region", "S3 region", "us-east-1"),
    Property("cloud_storage_api_endpoint", "S3 endpoint override", ""),
    Property("cloud_storage_access_key", "S3 access key", ""),
    Property("cloud_storage_secret_key", "S3 secret key", ""),
    Property("cloud_storage_segment_max_upload_interval_sec", "Upload cadence", 30, int, _positive),
    Property("cloud_storage_cache_size", "Local read-cache bytes", 1 << 30, int, _positive),
]


class Configuration:
    """Runtime store over the property table (config_store semantics)."""

    def __init__(self) -> None:
        self._props: dict[str, Property] = {p.name: p for p in PROPERTIES}
        self._values: dict[str, Any] = {p.name: p.default for p in PROPERTIES}
        self._extra: dict[str, Any] = {}  # unknown keys, preserved

    # ------------------------------------------------------------ access
    def get(self, name: str) -> Any:
        if name in self._values:
            return self._values[name]
        if name in self._extra:
            return self._extra[name]
        raise KeyError(name)

    def __getattr__(self, name: str) -> Any:
        try:
            return self.get(name)
        except KeyError:
            raise AttributeError(name) from None

    def set(self, name: str, value: Any) -> None:
        prop = self._props.get(name)
        if prop is None:
            self._extra[name] = value
            return
        value = prop.coerce(value)
        prop.validate(value)
        self._values[name] = value

    def property(self, name: str) -> Property | None:
        return self._props.get(name)

    def properties(self) -> list[Property]:
        return list(self._props.values())

    # ------------------------------------------------------------ io
    def to_dict(self, redact: bool = True) -> dict:
        out = dict(self._values)
        out.update(self._extra)
        if redact:
            for k in list(out):
                if "secret" in k or "password" in k:
                    if out[k]:
                        out[k] = "[secret]"
        return out

    def load_dict(self, data: dict) -> None:
        # the reference nests under a `redpanda:` section in redpanda.yaml
        section = data.get("redpanda", data)
        for k, v in section.items():
            self.set(k, v)

    def load_yaml(self, path: str) -> "Configuration":
        import yaml

        with open(path) as f:
            self.load_dict(yaml.safe_load(f) or {})
        return self

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), indent=2, sort_keys=True)


_cfg: Configuration | None = None


def shard_local_cfg() -> Configuration:
    """Process-wide configuration (configuration.cc shard_local_cfg())."""
    global _cfg
    if _cfg is None:
        _cfg = Configuration()
    return _cfg
