"""Per-subsystem latency probes over the metrics registry.

Parity with the reference's probe-per-subsystem pattern (storage/probe.h,
raft/probe.cc, kafka/latency_probe.h): each hot path owns a histogram in
the process-wide registry, exported at /metrics. Unlike the tracer
(trace.py) these are ALWAYS on — a histogram record is a dict lookup plus
integer bucket math, the price the reference pays on every request too.

Naming convention (README "Observability"): ``<subsystem>_<stage>_latency_us``
for latency histograms, ``coproc_stage_latency_us{stage=...}`` for the
engine's per-stage breakdown, ``*_bytes_total`` for transfer counters.
"""

from __future__ import annotations

import collections
import threading
import time
import weakref

from redpanda_tpu.metrics import Counter, Histogram, registry, series_key
from redpanda_tpu.observability.trace import tracer

# ------------------------------------------------------------ broker path
storage_append_hist = registry.histogram(
    "storage_append_latency_us", "Storage log append latency (us)"
)
# One sample per framing of an append: the batches one native crossing
# framed (DiskLog._append_framed), 1 for a batch the per-batch loop
# encoded (no native library, a follower's append). Batches, not us.
storage_append_crossing_batches_hist = registry.histogram(
    "storage_append_crossing_batches",
    "Batches framed into the log by one framing call of an append",
)
# The read side's twin: one sample per native crossing of a scanning read
# (Segment.scan) that kept batches, holding how many it decoded; 1 for each
# batch the per-frame loop decoded (a library without the crossing). A read
# the batch cache serves makes no scan and no sample. Batches, not us.
storage_read_crossing_batches_hist = registry.histogram(
    "storage_read_crossing_batches",
    "Batches decoded by one native crossing of a scanning read",
)
storage_read_hist = registry.histogram(
    "storage_read_latency_us",
    "Storage log read latency, lock wait included (us)",
)
# One sample per DiskLog.flush that had a segment to sync: the buffered
# write and the fsync inside the log's lock, whoever asked (an acks=all
# produce, raft's append path). It runs on the event loop.
storage_flush_hist = registry.histogram(
    "storage_flush_latency_us",
    "Storage log flush (write + fsync of the active segment) latency (us)",
)
storage_housekeeping_hist = registry.histogram(
    "storage_housekeeping_latency_us",
    "One compaction/retention housekeeping pass over a log (us)",
)
raft_replicate_hist = registry.histogram(
    "raft_replicate_latency_us",
    "Raft replicate() to the requested consistency level (us)",
)
# Recorded at the kafka dispatch layer (server/protocol.py _dispatch), so
# one request is one sample — handler wrappers must NOT record these too.
kafka_produce_hist = registry.histogram(
    "kafka_produce_latency_us", "Produce handler latency (microseconds)"
)
# The produce handler's inside, for what kafka_produce_latency_us times
# from outside. ``queue``: one sample a request, the qdc gate's acquire in
# protocol._dispatch (0 with the gate off). ``decode`` (the wire batches
# parsed), ``crc`` (the backend awaited and the batched validate) and
# ``replicate`` (the await of partition.replicate / rm_stm.replicate: raft,
# the log's append and flush, and the awaits between them on a loop that may
# run late): one sample a PARTITION of a request, each only where the
# handler got that far (a refused partition records what ran before the
# refusal). A produce v0-2 MessageSet is converted in one call, under
# ``decode``.
KAFKA_PRODUCE_STAGES = ("queue", "decode", "crc", "replicate")
kafka_produce_stage_hist = {
    stage: registry.histogram(
        "kafka_produce_stage_latency_us",
        "Produce handler time by stage (us)",
        stage=stage,
    )
    for stage in KAFKA_PRODUCE_STAGES
}
kafka_fetch_hist = registry.histogram(
    "kafka_fetch_latency_us",
    "Fetch handler latency incl. long-poll wait (microseconds)",
)
# One sample per fetch request: the time inside the read + encode passes
# only, without the long-poll sleep that kafka_fetch_latency_us includes.
kafka_fetch_serve_hist = registry.histogram(
    "kafka_fetch_serve_latency_us",
    "Fetch handler time reading and encoding, long-poll wait left out (us)",
)
# One sample per fetch that went through the long-poll gate and returned
# data: append of the oldest batch it returns -> the response is built
# (the commit wakes the parked fetch, so what a tailing consumer pays is
# the commit itself on a raft group, a late loop turn and the serve pass).
kafka_fetch_wake_hist = registry.histogram(
    "kafka_fetch_wake_latency_us",
    "Append of the oldest batch a long-polling fetch returns to its return (us)",
)
# One count per fetch that parked in the long-poll gate, by what ended its
# last park: a commit on a requested partition (or its group failing its
# waiters: stepped down, stopping), or the request's own max_wait_ms.
# woken_by_commit / (woken_by_commit + deadline) is the gate's engage share.
kafka_fetch_parks = {
    end: registry.counter(
        "kafka_fetch_parks_total",
        "Fetches that parked in the long-poll gate, by what ended the park",
        end=end,
    )
    for end in ("woken_by_commit", "deadline")
}
rpc_request_hist = registry.histogram(
    "rpc_request_latency_us", "Internal RPC round-trip latency (us)"
)

# ------------------------------------------------------------ coproc pacemaker
# One sample per PRODUCTIVE tick of a script's fiber (coproc/pacemaker.py):
# read - read_hidden + gate + engine + write = tick, and tick + gap tiles
# the fiber's time (gap: end of one productive tick to the start of the
# next one's read: loop hand-off, idle sleeps, the unproductive ticks
# between). read is the read's own time wherever it ran; read_hidden is the
# part of it that ran inside the PREVIOUS tick's engine phase (the
# read-ahead a backlog engages: under the submit call and, between the two
# calls, under the launch's transfer; 0 for a tick that read for itself),
# so sum(read_hidden) / sum(read) is the share of the read that left the
# fiber's own read stretch.
# The engine phase is a sum, by construction (each leg begins on the clock
# read that ended the one before it; each sample truncates to a us):
#   engine = engine_prepare + handoff_out + engine_run + handoff_back
#            + read_ahead_wait
# engine_prepare: the phase's entry -> the submit is handed to the executor
# (the request's construction, the tick deadline's derivation).
# The phase's two executor calls (submit, harvest), summed over both:
# handoff_out, call handed to the executor -> the worker runs it (queue,
# thread wake, interpreter lock); engine_run, the worker's own time in the
# call; handoff_back, worker done -> the fiber runs again (the wake-up and a
# late loop), and after the harvest on to the phase's end.
# read_ahead_wait: between the two calls, the fiber seeing the read-ahead out
# before it sends the harvest (what the read cost the engine phase it ran
# inside); 0 for a tick with nothing read ahead, or whose read-ahead had
# ended under the submit.
# A timed-out, cancelled or shed tick records ``engine`` and none of the five.
COPROC_HANDOFF_PHASES = ("handoff_out", "engine_run", "handoff_back")
COPROC_ENGINE_PHASES = ("engine_prepare", *COPROC_HANDOFF_PHASES, "read_ahead_wait")
COPROC_TICK_PHASES = (
    "tick", "read", "read_hidden", "gate", "engine", "write", "gap",
    *COPROC_ENGINE_PHASES,
)
coproc_tick_hist = {
    phase: registry.histogram(
        "coproc_tick_latency_us",
        "Pacemaker wall time per productive tick, by phase (us)",
        phase=phase,
    )
    for phase in COPROC_TICK_PHASES
}
# One sample per partition read of a tick: append of the oldest batch read
# -> the pacemaker has it (how long input waits for a tick). Skipped when
# that batch has left the partition's append-stamp ring (an old backlog).
coproc_input_wait_hist = registry.histogram(
    "coproc_input_wait_latency_us",
    "Append of the oldest batch a tick reads from a partition to that read (us)",
)

# ------------------------------------------------------------ coproc engine
coproc_h2d_bytes = registry.counter(
    "coproc_device_transfer_bytes_total",
    "Bytes staged to / fetched from the device",
    direction="h2d",
)
coproc_d2h_bytes = registry.counter(
    "coproc_device_transfer_bytes_total",
    "Bytes staged to / fetched from the device",
    direction="d2h",
)
# What the payload lane adds to a launch: the rows of the bucket it stages
# (padding included, so records / staged rows is the staging's fill) and
# the values it drops because they are wider than the staging row.
coproc_staged_rows = registry.counter(
    "coproc_staged_rows_total",
    "Rows the payload lane staged to the device, bucket padding included",
)
coproc_oversize_rows = registry.counter(
    "coproc_oversize_rows_total",
    "Values wider than the staging row, which the payload lane drops",
)
# Payload launches staged in more than one part by width class (their narrow
# rows in a narrow matrix, their few wide ones beside it, and one more a
# class above 1,024 B: TpuEngine._plan_parts; coproc_launch_parts_total
# counts the parts).
coproc_split_launches = registry.counter(
    "coproc_split_launches_total",
    "Payload launches staged, shipped and run as two or more parts by width class",
)
# A launch's parts by width class, keyed by the engine's stats() name: the
# matrices a launch is staged as (one, PR 47's two, or one a class above
# 1,024 B where the lane's limit, coproc_max_value_bytes, is wider), and of
# those above 1,024 B the rows, the matrices' bytes and the value bytes in
# them (the last two's ratio is what the wider classes' padding costs).
coproc_width_classes = {
    "n_parts": registry.counter(
        "coproc_launch_parts_total",
        "Staging matrices (parts by width class) of the payload lane's launches",
    ),
    "n_wide_rows": registry.counter(
        "coproc_wide_rows_total",
        "Values staged in width classes above 1,024 B",
    ),
    "bytes_staged_wide": registry.counter(
        "coproc_staged_wide_bytes_total",
        "Bytes of the staging matrices wider than 1,024 B (rows x stride)",
    ),
    "bytes_staged_values_wide": registry.counter(
        "coproc_staged_wide_value_bytes_total",
        "Record value bytes packed into staging matrices wider than 1,024 B",
    ),
}
# The staging matrices themselves, in bytes: rows x stride of every matrix
# the lane packed, and the record bytes put into them (their ratio is what
# of a launch's H2D is data; ~0.13 for 130 B events in 1,032 B rows).
coproc_staged_bytes = registry.counter(
    "coproc_staged_bytes_total",
    "Bytes of the staging matrices the payload lane packed (rows x stride)",
)
coproc_staged_value_bytes = registry.counter(
    "coproc_staged_value_bytes_total",
    "Record value bytes the payload lane packed into staging matrices",
)
# What a launch's harvest lets through, on either framing road: the rows it
# keeps and the value bytes it frames into output batches (the map's
# shrink: 70 B a kept row of config 4's projection against ~1 KB in).
coproc_kept_rows = registry.counter(
    "coproc_kept_rows_total",
    "Rows the harvest kept and framed into output batches",
)
coproc_output_bytes = registry.counter(
    "coproc_output_bytes_total",
    "Value bytes the harvest framed into output batches",
)
# The decompress leg of the explode (batch_codec.launch_payloads): scanned
# batches that arrived compressed, the crossings into a codec that served
# them (two a launch through a many-frames form, one a batch otherwise) and
# the bytes in and out; keyed by the engine's stats() name.
coproc_uncompress = {
    "n_uncompressed_batches": registry.counter(
        "coproc_uncompressed_batches_total",
        "Scanned batches the explode decompressed",
    ),
    "n_uncompress_crossings": registry.counter(
        "coproc_uncompress_crossings_total",
        "Crossings into a codec that decompressed scanned batches",
    ),
    "bytes_uncompress_in": registry.counter(
        "coproc_uncompress_in_bytes_total",
        "Compressed payload bytes the explode decompressed",
    ),
    "bytes_uncompress_out": registry.counter(
        "coproc_uncompress_out_bytes_total",
        "Payload bytes the explode's decompress produced",
    ),
}
# Rows a structural program (map_project_json) read as JSON, and of those
# the ones it dropped, by reason: counted by the harvest from the reason
# byte of the result row; keyed by the engine's stats() name.
coproc_json_rows = {
    key: registry.counter(
        "coproc_json_rows_total",
        "Rows a launch ran through a structural (JSON read as JSON) program, and "
        "those it dropped as not one sound JSON object or for a path it could not read",
        outcome=outcome,
    )
    for key, outcome in (
        ("n_json_rows", "read"),
        ("n_json_malformed_rows", "malformed"),
        ("n_json_path_miss_rows", "path_miss"),
    )
}
# The seal (TpuEngine._seal_jobs): output batches sealed and the crossings
# that served them (one a native call that sealed a launch's batches, one a
# batch on the per-batch road); keyed by the engine's stats() name.
coproc_seal = {
    "n_sealed_batches": registry.counter(
        "coproc_sealed_batches_total",
        "Output batches the harvest sealed (compressed where over the threshold, both CRCs)",
    ),
    "n_seal_crossings": registry.counter(
        "coproc_seal_crossings_total",
        "Crossings that sealed output batches: one a many-batches native call, one a batch otherwise",
    ),
}
# Device programs built ahead of need (TpuEngine._build_ladder: one a row
# bucket of a payload script's ladder, off the serving path) and launches
# the engine cut to the largest ready bucket; keyed by the engine's stats()
# name. A first run ON the serving path is n_compiles, and these are not.
coproc_precompile = {
    "n_precompiles": registry.counter(
        "coproc_precompiles_total",
        "Device programs lowered and compiled ahead of need, off the serving path",
    ),
    "n_precompile_failures": registry.counter(
        "coproc_precompile_failures_total",
        "Ladders whose build failed (their scripts serve through first runs)",
    ),
    "n_launch_cuts": registry.counter(
        "coproc_launch_cuts_total",
        "Launches cut to the largest row bucket whose program was ready",
    ),
}
coproc_launch_rows_hist = registry.histogram(
    "coproc_launch_rows",
    "Records fused into one device launch (bucket size after shape rounding)",
)
coproc_shard_rows_hist = registry.histogram(
    "coproc_shard_rows",
    "Records per device shard of a mesh launch",
)
# Harvest framing path, per framing crossing (launch- or shard-level):
# gather = zero-copy framing straight from the joined blob's (offset, len)
# columns; padded = the row-matrix path (byte-mutating transforms).
coproc_harvest_gather = registry.counter(
    "coproc_harvest_path_total",
    "Harvest framing crossings by path",
    mode="gather",
)
coproc_harvest_padded = registry.counter(
    "coproc_harvest_path_total",
    "Harvest framing crossings by path",
    mode="padded",
)
# Device-resident column cache (coproc/colcache.py): a hit means a launch
# skipped the whole host parse/extract ladder (and, on the device backend,
# the H2D replay of its predicate columns).
coproc_colcache_hits = registry.counter(
    "coproc_colcache_total",
    "Column-cache lookups by outcome",
    outcome="hit",
)
coproc_colcache_misses = registry.counter(
    "coproc_colcache_total",
    "Column-cache lookups by outcome",
    outcome="miss",
)

# ------------------------------------------------------ multi-chip meshrunner
# One sharded launch = one SPMD predicate program over the partition-axis
# device mesh (coproc/meshrunner.py). Demotions are launches the breaker or
# a failed mesh leg sent down the bit-identical single-device path.
coproc_mesh_launches = registry.counter(
    "coproc_mesh_launches_total",
    "Columnar launches dispatched SPMD over the device mesh",
)
coproc_mesh_demotions = registry.counter(
    "coproc_mesh_demotions_total",
    "Mesh-eligible launches demoted to the single-device path",
)
# per-device record counters, created lazily per mesh device index so the
# series set matches the mesh actually built (locked check-then-create,
# same rationale as coproc_failure_counter)
_mesh_device_rows: dict[int, Counter] = {}
_mesh_device_lock = threading.Lock()


def coproc_mesh_device_rows(device: int) -> Counter:
    c = _mesh_device_rows.get(device)
    if c is None:
        with _mesh_device_lock:
            c = _mesh_device_rows.get(device)
            if c is None:
                c = registry.counter(
                    "coproc_mesh_device_rows_total",
                    "Records dispatched to each mesh device shard",
                    device=str(device),
                )
                _mesh_device_rows[device] = c
    return c

# -------------------------------------------------------- coproc fault domains
# Classified failure counter, one series per (fault domain, exception kind):
# every formerly-silent except block in the engine reports here, so no
# degradation path is invisible on /metrics. Locked check-then-create for
# the same reason as coproc_stage_hist.
_failure_counters: dict[tuple[str, str], Counter] = {}
_failure_lock = threading.Lock()


def coproc_failure_counter(domain: str, kind: str) -> Counter:
    key = (domain, kind)
    c = _failure_counters.get(key)
    if c is None:
        with _failure_lock:
            c = _failure_counters.get(key)
            if c is None:
                c = registry.counter(
                    "coproc_failures_total",
                    "Classified coproc failures by fault domain",
                    domain=domain,
                    kind=kind,
                )
                _failure_counters[key] = c
    return c


coproc_breaker_trips = registry.counter(
    "coproc_breaker_trips_total",
    "Device circuit breaker transitions to open",
)
coproc_retries_total = registry.counter(
    "coproc_device_retries_total",
    "Device interaction retry attempts (deadline/launch failures)",
)
coproc_fallback_rows = registry.counter(
    "coproc_fallback_rows_total",
    "Records whose transform stages re-executed on the pure-host fallback",
)
coproc_lockwatch_edges = registry.counter(
    "coproc_lockwatch_edges_total",
    "Distinct lock-order edges observed by the coproc_lockwatch recorder",
)
coproc_leakwatch_imbalance = registry.counter(
    "coproc_leakwatch_imbalance_total",
    "Resource balances driven negative under the coproc_leakwatch recorder",
)

# Breaker-state gauges moved to the governor (coproc/governor.py): they
# are per-DOMAIN labeled series (coproc_breaker_state{domain=...}) owned by
# the engine's Governor via weakref — the old single weakref-to-latest-
# engine gauge reported a stale engine's breaker after restarts and in
# multi-engine tests.

# ------------------------------------------------------ host-stage pool
# Busy-worker gauge for the mesh lane's host-stage pool (coproc/host_pool.py).
# The counter lives HERE, not on the pool: the gauge must be registered
# exactly once per process while pools are per-engine, and probes already
# owns the process-wide registry. inc/dec under a lock — += on an int is
# a read-modify-write and worker threads race it.
_host_pool_busy = 0
_host_pool_lock = threading.Lock()


def host_pool_task_started() -> None:
    global _host_pool_busy
    with _host_pool_lock:
        _host_pool_busy += 1


def host_pool_task_finished() -> None:
    global _host_pool_busy
    with _host_pool_lock:
        _host_pool_busy -= 1


coproc_host_pool_busy = registry.gauge(
    "coproc_host_pool_busy_workers",
    lambda: float(_host_pool_busy),
    "Host-stage pool workers currently running a shard task",
)

# Success-only device-leg latency per fault domain — THE adaptive-deadline
# source (governor.observe_leg records a sample only when a leg COMPLETES;
# abandoned/timed-out attempts contribute nothing, so timeout bursts can't
# inflate the tail the next deadline derives from the way the fetch-stage
# histogram could).
_device_leg: dict[str, Histogram] = {}
_device_leg_lock = threading.Lock()


def coproc_device_leg_hist(domain: str) -> Histogram:
    """Histogram for one fault domain's successful device legs. Locked
    check-then-create (same rationale as coproc_stage_hist); callers
    serialize record() themselves (the governor records under its own
    lock)."""
    h = _device_leg.get(domain)
    if h is None:
        with _device_leg_lock:
            h = _device_leg.get(domain)
            if h is None:
                h = registry.histogram(
                    "coproc_device_leg_latency_us",
                    "Successful device-leg wall time per fault domain "
                    "(adaptive-deadline source; success-only)",
                    domain=domain,
                )
                _device_leg[domain] = h
    return h


_coproc_stage: dict[str, Histogram] = {}
_coproc_stage_lock = threading.Lock()


def coproc_stage_hist(stage: str) -> Histogram:
    """Histogram for one engine stage (explode/pack/dispatch/fetch/...).

    Locked creation: harvests run on executor threads, and an unlocked
    check-then-create could register one Histogram in the registry while
    caching a twin here — the exported series would then stay frozen.
    Callers serialize record() themselves (the engine records under its
    _stats_lock; HdrHist's read-modify-write is not thread-safe)."""
    h = _coproc_stage.get(stage)
    if h is None:
        with _coproc_stage_lock:
            h = _coproc_stage.get(stage)
            if h is None:
                h = registry.histogram(
                    "coproc_stage_latency_us",
                    "TPU engine per-stage wall time (us)",
                    stage=stage,
                )
                _coproc_stage[stage] = h
    return h


# ------------------------------------------------------------ trace exemplars
# When a histogram observation lands over its breach threshold, the ambient
# trace id is recorded alongside the bucket it fell into, so an SLO breach
# on /v1/slo (and `rpk debug slo`) links straight to the matching
# /v1/trace/slow entry instead of leaving the operator to correlate by
# timestamp. Thresholds come from the armed SLO objectives
# (observability/slo.py arms threshold_ms per metric); a histogram with no
# armed objective falls back to the tracer's slow threshold. Exemplars
# only exist where a trace id does: with the tracer disabled the whole
# layer is one dict lookup + compare per observation (the
# slo_eval_overhead microbench gates that at <1% of a produce op).
_EXEMPLAR_CAP = 16  # newest-first ring per series

_exemplar_lock = threading.Lock()
# id(hist) -> threshold_us armed by an SLO objective (None = tracer default)
_exemplar_thresholds: dict[int, float] = {}
# series key -> deque of {"trace_id", "value_us", "bucket_us"}
_exemplars: dict[str, collections.deque] = {}


# ids that already have a deallocation finalizer registered: tracked
# SEPARATELY from the thresholds dict, because disarm/reset clear the
# thresholds while the finalizer lives as long as the histogram — keying
# "already registered" off the thresholds dict would register a fresh
# finalizer on every disarm/re-arm cycle of an immortal registry
# histogram (loadgen does one such cycle per scenario run).
_exemplar_finalized: set[int] = set()


def _drop_exemplar_threshold(key: int) -> None:
    with _exemplar_lock:
        _exemplar_thresholds.pop(key, None)
        # the object is being deallocated: a future histogram at this
        # address is a different object and deserves its own finalizer
        _exemplar_finalized.discard(key)


def arm_exemplar_threshold(hist: Histogram, threshold_us: float) -> None:
    """Arm a per-histogram breach threshold (an SLO objective's
    threshold_ms). Observations at or over it record the ambient trace id.

    The store is keyed by id(hist) for the hot-path lookup; the finalizer
    drops the entry when the histogram is collected (CPython runs it at
    deallocation, before the address can be reused), so a scratch
    histogram armed and dropped without a disarm can never bequeath its
    threshold to an unrelated histogram allocated at the same address."""
    with _exemplar_lock:
        # one finalizer per object LIFETIME, not per (re-)arm call:
        # evaluate() re-arms on every /v1/slo poll, and disarm/re-arm
        # cycles must not register duplicates either
        first = id(hist) not in _exemplar_finalized
        if first:
            _exemplar_finalized.add(id(hist))
        _exemplar_thresholds[id(hist)] = float(threshold_us)
    if first:
        weakref.finalize(hist, _drop_exemplar_threshold, id(hist))


def disarm_exemplar_threshold(hist: Histogram) -> None:
    with _exemplar_lock:
        _exemplar_thresholds.pop(id(hist), None)


def reset_exemplars() -> None:
    with _exemplar_lock:
        _exemplar_thresholds.clear()
        _exemplars.clear()


def exemplars_for(key: str) -> list[dict]:
    """Newest-first exemplars for one series key (metrics.series_key)."""
    with _exemplar_lock:
        ring = _exemplars.get(key)
        return list(ring)[::-1] if ring else []


def exemplars_snapshot() -> dict[str, list[dict]]:
    with _exemplar_lock:
        return {k: list(ring)[::-1] for k, ring in _exemplars.items() if ring}


def _note_exemplar(hist: Histogram, value_us: int, trace_id) -> None:
    """Slow path — only runs for an over-threshold observation."""
    if trace_id is None:
        trace_id = tracer.current_trace()
        if trace_id is None:
            return  # no trace to link: an exemplar would dangle
    from redpanda_tpu.utils.hdr import _bucket_of, _bucket_upper

    entry = {
        "trace_id": trace_id,
        "value_us": int(value_us),
        "bucket_us": _bucket_upper(_bucket_of(int(value_us))),
        # wall-clock stamp so a windowed SLO report can drop exemplars
        # recorded before its baseline mark (the ring outlives incidents)
        "ts": time.time(),
    }
    key = series_key(hist.name, hist.labels)
    with _exemplar_lock:
        ring = _exemplars.get(key)
        if ring is None:
            ring = _exemplars[key] = collections.deque(maxlen=_EXEMPLAR_CAP)
        ring.append(entry)


def record_us(hist: Histogram, value_us: int, trace_id=None) -> None:
    """Record a latency observation with exemplar capture. The always-on
    cost beyond hist.record is one dict lookup + compare; everything else
    only runs once the value crossed the breach threshold."""
    value_us = int(value_us)
    hist.record(value_us)
    thr = _exemplar_thresholds.get(id(hist))
    if thr is None:
        if not tracer.enabled:
            return
        thr = tracer.slow_threshold_us
    if value_us >= thr:
        _note_exemplar(hist, value_us, trace_id)


def observe_us(hist: Histogram, t0: float) -> None:
    """Record elapsed-since-t0 (a perf_counter timestamp) in microseconds."""
    record_us(hist, int((time.perf_counter() - t0) * 1e6))


__all__ = [
    "Counter",
    "Histogram",
    "arm_exemplar_threshold",
    "exemplars_for",
    "exemplars_snapshot",
    "record_us",
    "reset_exemplars",
    "coproc_breaker_trips",
    "coproc_d2h_bytes",
    "coproc_device_leg_hist",
    "coproc_failure_counter",
    "coproc_fallback_rows",
    "coproc_h2d_bytes",
    "coproc_harvest_gather",
    "coproc_harvest_padded",
    "coproc_host_pool_busy",
    "coproc_input_wait_hist",
    "coproc_json_rows",
    "coproc_kept_rows",
    "coproc_launch_rows_hist",
    "coproc_leakwatch_imbalance",
    "coproc_lockwatch_edges",
    "coproc_output_bytes",
    "coproc_oversize_rows",
    "coproc_precompile",
    "coproc_retries_total",
    "coproc_seal",
    "coproc_shard_rows_hist",
    "coproc_stage_hist",
    "coproc_staged_bytes",
    "coproc_staged_rows",
    "coproc_staged_value_bytes",
    "coproc_tick_hist",
    "coproc_uncompress",
    "coproc_width_classes",
    "host_pool_task_finished",
    "host_pool_task_started",
    "kafka_fetch_hist",
    "kafka_fetch_serve_hist",
    "kafka_fetch_wake_hist",
    "kafka_produce_hist",
    "observe_us",
    "raft_replicate_hist",
    "rpc_request_hist",
    "storage_append_crossing_batches_hist",
    "storage_append_hist",
    "storage_flush_hist",
    "storage_housekeeping_hist",
    "storage_read_crossing_batches_hist",
    "storage_read_hist",
]
