"""North-star benchmark: coproc JSON-filter transform at 64 partitions.

Measures record_batches/sec through the TPU engine (BASELINE.md config 4
shape: JSON filter + project to a fixed struct, 64 partitions, zstd output)
against a single-core host baseline that mirrors what the reference's
Node.js sidecar does per record (decode framing, JSON parse, predicate,
re-encode, re-CRC — src/js/modules/rpc/server.ts:244-266).

The engine is measured the way a broker drives it: a steady stream of ticks
with GROUP ticks fused per launch and DEPTH launches in flight
(submit_group / Ticket.result — coproc/engine.py). The spec is a v2
where-expression, so the engine runs its columnar pushdown path: the native
columnarizer ships per-field columns up, the device evaluates the predicate
tree, one bit per record comes back, and outputs are assembled, framed,
recompressed, and resealed host-side — the clock runs from first submit to
the last fully-rebuilt reply.

Secondary metrics ride in the same JSON line:
- config 1 = produce-path batch CRC validation through the measured adapter
  boundary (ops/crc_backend.py): BOTH host and device rates plus the
  backend pick() chose.
- config 2 = 16-partition LZ4 produce codec path.
- config 3 = identity transform through the engine at 16 partitions (the
  engine routes identity to its host stage — no device work exists for it),
  plus config3_payload_bridge_16p = the same identity FORCED through the
  full-row device staging path.
- "stages" = the engine's per-stage wall/bytes breakdown for the headline
  run; "link" = a quick device-link profile (RTT + H2D MB/s).

This is a measurement of the accelerator path: JAX is initialized once, in
this process, and without an accelerator the run fails (exit 1, naming the
platform) instead of writing the same metric from JAX's CPU backend. A
failed phase fails the run. The mesh round (``python bench.py mesh``) is a
CPU-pinned child on 8 virtual host devices and says so in its block.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
"""

from __future__ import annotations

import json
import os
import struct
import subprocess
import sys
import time

import numpy as np

P = 64  # partitions
RECORDS_PER_BATCH = 32
ROW_STRIDE = 1152
GROUP = int(os.environ.get("BENCH_GROUP", "16"))  # ticks fused per launch
DEPTH = int(os.environ.get("BENCH_DEPTH", "3"))  # launch groups in flight
# long enough that DEPTH-deep pipelining reaches steady state (the
# pipeline's fill and drain are not the sustained rate)
MEASURE_TICKS = int(os.environ.get("BENCH_TICKS", "160"))
BASELINE_TICKS = int(os.environ.get("BENCH_BASELINE_TICKS", "4"))
# Host-stage pool size for the headline runs (coproc/host_pool.py). The
# workers=1 ablation rides in the same JSON so every BENCH artifact proves
# the pool-off path did not regress.
HOST_WORKERS = int(os.environ.get("BENCH_HOST_WORKERS", "4"))


def _build_workload(n_partitions=P, topic="bench"):
    """One tick: one 32-record batch of ~1 KB JSON documents per partition
    (coproc/reference.make_documents — about a third are level "error",
    and about seven in eight of those have a ``msg`` that Str("msg", 64)
    can project, so the transform keeps records and the harvest side
    does real framing, compression and sealing)."""
    from redpanda_tpu.coproc import reference
    from redpanda_tpu.coproc.engine import ProcessBatchItem, ProcessBatchRequest
    from redpanda_tpu.models import NTP, Record, RecordBatch

    items = []
    for p, part in enumerate(
        reference.make_documents(0, n_partitions, RECORDS_PER_BATCH)
    ):
        recs = [
            Record(offset_delta=i, timestamp_delta=i, value=v)
            for i, v in enumerate(part)
        ]
        batch = RecordBatch.build(recs, base_offset=0, first_timestamp=1_000_000)
        items.append(ProcessBatchItem(1, NTP.kafka(topic, p), [batch]))
    return ProcessBatchRequest(items)


def _spec():
    from redpanda_tpu.ops.exprs import field
    from redpanda_tpu.ops.transforms import Int, Str, map_project, where

    return where(field("level") == "error") | map_project(Int("code"), Str("msg", 64))


def _run_engine_stream(engine, req, n_ticks, group, depth) -> float:
    """Steady-state record_batches/sec: GROUP ticks per launch, DEPTH
    launches in flight, replies fully rebuilt on the critical path."""
    n_groups = (n_ticks + group - 1) // group
    pending = []
    replies = []
    t0 = time.perf_counter()
    for g in range(n_groups):
        k = min(group, n_ticks - g * group)
        pending.append(engine.submit_group([req] * k))
        while len(pending) > depth:
            replies.extend(t.result() for t in pending.pop(0))
    while pending:
        replies.extend(t.result() for t in pending.pop(0))
    elapsed = time.perf_counter() - t0
    assert len(replies) == n_ticks
    assert all(len(r.items) == len(req.items) for r in replies)
    # every tick is the same request: the same records must come back
    kept = {
        sum(b.header.record_count for it in r.items for b in it.batches)
        for r in replies
    }
    assert len(kept) == 1, f"replies of one request kept {sorted(kept)} records"
    n_batches = sum(len(it.batches) for it in req.items)
    return n_ticks * n_batches / elapsed


def _fmt_stages(stats: dict) -> dict:
    """Stage keys only (the t_/n_/bytes_ prefixes stats() documents):
    probe records and numeric metadata like host_workers are reported at
    the top level instead, so the per-stage tables stay diffable across
    BENCH artifacts."""
    out = {}
    for k, v in sorted(stats.items()):
        if k.startswith(("t_", "n_", "bytes_")):
            out[k] = round(v, 4) if k.startswith("t_") else int(v)
    return out


def _harvest_mode(stats: dict) -> str:
    """Which framing path a run took (gather = zero-copy from the joined
    blob; padded = row-matrix). One helper so the detection rule can't
    drift between the headline and the ablation blocks."""
    return "gather" if stats.get("n_frame_gather", 0.0) else "padded"


def _run_engine_mode(
    req, force_mode: str | None, host_workers: int = HOST_WORKERS,
    colcache_mb: int = 0, **engine_kw,
) -> tuple[float, dict, dict]:
    """One measured engine run. force_mode None = the PRODUCT path (the
    engine's own measured device-vs-host probe picks where the predicate
    runs); "columnar_device"/"columnar_host" pin each half so every BENCH
    carries the full ablation regardless of what the probe chose.
    host_workers sizes the host-stage shard pool (1 = inline ablation).
    colcache_mb enables the device-resident column cache (the broker
    default posture) — the HEADLINE runs with it because the bench's
    steady state IS a repeat script over unchanged partitions; the
    machinery ablations run cache-off so they still measure the machinery
    they are named for. Returns (rate, stage dict, probe record)."""
    from redpanda_tpu.coproc import TpuEngine

    engine = TpuEngine(
        row_stride=ROW_STRIDE, force_mode=force_mode,
        host_workers=host_workers, device_column_cache_mb=colcache_mb,
        **engine_kw,
    )
    codes = engine.enable_coprocessors([(1, _spec().to_json(), ("bench",))])
    assert codes[0] == 0
    # warmup: compile the GROUP-sized shape and, when MEASURE_TICKS is not a
    # multiple of GROUP, the tail-group shape too (one full group followed
    # by one tail-sized group), so no XLA compile lands in the timed run.
    tail = MEASURE_TICKS % GROUP
    _run_engine_stream(engine, req, GROUP + (tail or min(GROUP, MEASURE_TICKS)), GROUP, DEPTH)
    engine.reset_stats()
    rate = _run_engine_stream(engine, req, MEASURE_TICKS, GROUP, DEPTH)
    stats = engine.stats()
    probe = {
        "columnar_backend": stats.get("columnar_backend"),
        "columnar_probe": stats.get("columnar_probe"),
        # zero-copy harvest: which framing path the run took (the
        # projection headline mutates bytes, so it reports padded
        # honestly) and the scratch arena's reuse accounting
        "harvest_mode": _harvest_mode(stats),
        "arena": stats.get("arena"),
        # structural-index parse: the engine's measured fused-vs-staged
        # pick for this run (None = never probed: every launch was a
        # cache hit or below the probe floor) + the probe timings
        "parse_path": stats.get("parse_path"),
        "parse_probe": stats.get("parse_probe"),
        # device-resident column cache accounting (absent = cache off)
        "colcache": stats.get("colcache"),
        # fault-domain health of the run: a BENCH number produced while the
        # breaker was open (or launches fell back to host) is an artifact
        # of a degraded link, and must say so on its face
        "breaker": stats.get("breaker"),
        # per-domain decision plane: breaker split + posture at run end
        # (coproc/governor.py; the process-wide journal summary + tail ride
        # at the top level of the BENCH json, collected after all runs)
        "breakers": stats.get("breakers"),
        "governor_posture": (stats.get("governor") or {}).get("posture"),
        "fallback_rows": stats.get("n_fallback_rows", 0.0),
        "device_retries": stats.get("n_retries", 0.0),
        # multi-chip meshrunner block (absent on single-device engines)
        "mesh": stats.get("mesh"),
    }
    # a live harvester pins the engine (jit executables, staged arrays)
    # for the rest of the multi-mode bench process
    engine.shutdown()
    return rate, _fmt_stages(stats), probe


def _measure_aa_skew(req) -> dict:
    """A/A box-skew self-check (ROADMAP item 4's "diagnose first"): two
    IDENTICAL host-columnar passthrough rounds timed back to back before
    any measured run. Their rate difference is the box's short-horizon
    capacity noise — a cross-round delta inside this band is weather, not
    a code regression, and the artifact says so on its face."""
    from redpanda_tpu.coproc import TpuEngine
    from redpanda_tpu.ops.exprs import field
    from redpanda_tpu.ops.transforms import where

    spec = where(field("level") == "error")
    engine = TpuEngine(
        row_stride=ROW_STRIDE, force_mode="columnar_host", host_workers=0
    )
    codes = engine.enable_coprocessors([(1, spec.to_json(), ("bench",))])
    assert codes[0] == 0
    _run_engine_stream(engine, req, GROUP, GROUP, DEPTH)  # warmup
    rates = [
        _run_engine_stream(engine, req, 2 * GROUP, GROUP, DEPTH)
        for _ in range(2)
    ]
    engine.shutdown()
    skew = abs(rates[0] - rates[1]) / max(rates) * 100.0 if max(rates) else 0.0
    return {
        "aa_rates_rb_s": [round(r, 1) for r in rates],
        "aa_skew_pct": round(skew, 1),
    }


def run_cpu_baseline(req) -> float:
    """Single-core host engine: per-record decode + the plain reference
    (json.loads, predicate, pack — coproc/reference.project_error) +
    rebuild + recompress + re-CRC: the work profile of the reference's JS
    supervisor, and the same semantics chip_smoke.py holds the engine to."""
    from redpanda_tpu.coproc import reference
    from redpanda_tpu.models import Record, RecordBatch
    from redpanda_tpu.models.record import Compression

    def tick():
        n_batches = 0
        for item in req.items:
            for batch in item.batches:
                kept = [
                    out
                    for out in (
                        reference.project_error(rec.value)
                        for rec in batch.records()
                    )
                    if out is not None
                ]
                if kept:
                    recs = [
                        Record(offset_delta=i, value=v) for i, v in enumerate(kept)
                    ]
                    out = RecordBatch.build(
                        recs,
                        base_offset=0,
                        compression=Compression.zstd,
                        first_timestamp=batch.header.first_timestamp,
                    )
                    assert out.header.crc
                n_batches += 1
        return n_batches

    tick()  # warmup
    # best-of-N per tick: the baseline must be the host's BEST case, so a
    # noisy-slow run can't inflate vs_baseline (min-time convention)
    best = None
    for _ in range(BASELINE_TICKS):
        t0 = time.perf_counter()
        n = tick()
        rate = n / (time.perf_counter() - t0)
        best = rate if best is None else max(best, rate)
    return best


def run_config1_crc_validate() -> dict:
    """Config 1: produce-path batch CRC validation, 1KB records, through
    the measured adapter boundary (ops/crc_backend.py — the call site the
    reference hard-codes at kafka_batch_adapter.cc:93-121).

    Reports both measured rates and the backend the probe chose; the chosen
    path is what the produce handler runs, so vs_host_single_core reflects
    the DECISION, not a forced device run."""
    from redpanda_tpu.models import Record, RecordBatch
    from redpanda_tpu.ops.crc_backend import CrcBackend

    batches = [
        RecordBatch.build(
            [Record(offset_delta=i, value=bytes([i % 251]) * 1024) for i in range(1)],
            base_offset=b,
        )
        for b in range(64)
    ]
    regions = [b.crc_region() for b in batches] * 16  # 1024 batches
    backend = CrcBackend.pick(regions, reps=8)
    d = backend.decision
    chosen_rate = (
        d.device_batches_per_sec if backend.backend == "device" else d.host_batches_per_sec
    )
    return {
        "batches_per_sec": round(chosen_rate, 1),
        "vs_host_single_core": round(chosen_rate / d.host_batches_per_sec, 2),
        "host_batches_per_sec": round(d.host_batches_per_sec, 1),
        "device_batches_per_sec": round(d.device_batches_per_sec, 1),
        "device_error": d.device_error,
        "chosen_backend": backend.backend,
    }


def run_config2_lz4_produce() -> dict:
    """Config 2: 16-partition produce with LZ4 — codec-registry throughput
    (wire batch -> verify CRC -> LZ4 recompress), MB/s."""
    from redpanda_tpu.compression import compress, uncompress
    from redpanda_tpu.models import Record, RecordBatch
    from redpanda_tpu.models.record import Compression

    batches = []
    rng = np.random.default_rng(1)
    for p in range(16):
        recs = [
            Record(offset_delta=i, value=rng.bytes(512) + b"x" * 512)
            for i in range(RECORDS_PER_BATCH)
        ]
        batches.append(RecordBatch.build(recs, base_offset=0))
    total_bytes = sum(len(b.payload) for b in batches)
    reps = 6
    t0 = time.perf_counter()
    for _ in range(reps):
        for b in batches:
            assert b.verify_kafka_crc()
            c = compress(b.payload, Compression.lz4)
            assert uncompress(c, Compression.lz4) == b.payload
    elapsed = time.perf_counter() - t0
    return {"mb_per_sec": round(reps * total_bytes / 1e6 / elapsed, 1)}


def run_config3_identity(engine_cls, force_mode=None, **engine_kw) -> dict:
    """Config 3: identity transform at 16 partitions.

    Default: the engine's real identity path (routed to the host stage —
    identity has no device work; coproc/column_plan.py plan_spec).
    force_mode="payload": the full-row device staging path, isolating raw
    bridge overhead. engine_kw rides through to the engine."""
    from redpanda_tpu.ops.transforms import identity

    req16 = _build_workload(16, topic="bench3")
    engine = engine_cls(row_stride=ROW_STRIDE, force_mode=force_mode, **engine_kw)
    codes = engine.enable_coprocessors([(1, identity().to_json(), ("bench3",))])
    assert codes[0] == 0
    _run_engine_stream(engine, req16, GROUP, GROUP, DEPTH)
    rate = _run_engine_stream(engine, req16, 4 * GROUP, GROUP, DEPTH)
    engine.shutdown()
    return {"record_batches_per_sec": round(rate, 1)}


def run_pulse_block() -> dict:
    """ISSUE 14: the pandapulse block every BENCH artifact carries — one
    instrumented columnar round with the flight recorder on, so the
    artifact holds the same per-stage timeline totals `rpk debug profile`
    would show for the bench's launch shape (plus the recorder/profiler
    summary). Tracer + pulse state restore after; the measured headline
    runs above stay uninstrumented."""
    from redpanda_tpu.coproc import TpuEngine
    from redpanda_tpu.observability.pulse import pulse
    from redpanda_tpu.observability.trace import tracer

    was_tracing = tracer.enabled
    was_pulse = pulse.enabled
    tracer.configure(enabled=True)
    pulse.configure(enabled=True)
    pulse.recorder.reset()
    try:
        req = _build_workload(8, topic="bench_pulse")
        engine = TpuEngine(row_stride=ROW_STRIDE)
        codes = engine.enable_coprocessors(
            [(1, _spec().to_json(), ("bench_pulse",))]
        )
        assert codes[0] == 0
        req.trace_id = tracer.new_trace_id()
        engine.submit(req).result()
        engine.shutdown()
        tl = pulse.timeline()
        global _LAST_PULSE_TIMELINE
        _LAST_PULSE_TIMELINE = tl
        return {
            "recorder": pulse.recorder.summary(),
            "stage_totals_s": {
                k: round(v, 6)
                for k, v in sorted(pulse.recorder.stage_totals().items())
            },
            "timeline_events": len(tl["traceEvents"]),
            "journal_events": tl["journal_events"],
        }
    finally:
        pulse.configure(enabled=was_pulse)
        tracer.configure(enabled=was_tracing)


# the pulse block's raw timeline, kept for --diff-against: a timeline
# baseline diffs against THIS run's timeline through tools/pulsediff.py
_LAST_PULSE_TIMELINE: dict | None = None


def run_trend_block() -> dict:
    """ISSUE 17: the pandatrend block every BENCH artifact carries — the
    metrics-history recorder sampled around one columnar round, so the
    artifact holds the same derived counter tracks `/v1/history` and
    `rpk debug trend` serve on a live broker (occupancy, shed rate,
    colcache, per-histogram p99.9) for the bench's launch shape. No
    recorder thread runs here: two explicit ``sample_once()`` calls
    bracket the round, exactly the delta one 5s window would carry."""
    from redpanda_tpu.coproc import TpuEngine
    from redpanda_tpu.observability.history import history

    history.reset()
    history.sample_once()  # anchors the delta baseline
    req = _build_workload(8, topic="bench_trend")
    engine = TpuEngine(row_stride=ROW_STRIDE)
    codes = engine.enable_coprocessors(
        [(1, _spec().to_json(), ("bench_trend",))]
    )
    assert codes[0] == 0
    engine.submit(req).result()
    engine.shutdown()
    win = history.sample_once() or {}
    snap = history.snapshot(limit=1)
    return {
        "tracks": win.get("tracks", {}),
        "counter_deltas": {
            k: v["delta"]
            for k, v in sorted(win.get("counters", {}).items())
        },
        "hist_p999_us": {
            k: v["p999"] for k, v in sorted(win.get("hists", {}).items())
        },
        "breaches_total": snap["breaches_total"],
        "recorder_running": snap["recorder_running"],
        "counter_events": len(history.counter_tracks(pid=0)),
    }


def run_harvest_passthrough(req) -> dict:
    """Zero-copy harvest ablation: the same 64-partition workload through a
    PURE filter (passthrough plan — output bytes are the input values, the
    shape the gather path exists for), gather on vs off. Stage keys carry
    the per-path split; the microbench harvest_path gate asserts the
    stage-time cut, this block puts both end-to-end rates on record."""
    from redpanda_tpu.coproc import TpuEngine
    from redpanda_tpu.ops.exprs import field
    from redpanda_tpu.ops.transforms import where

    spec = where(field("level") == "error")
    out = {}
    for key, gather in (("gather", True), ("padded_ablation", False)):
        engine = TpuEngine(
            row_stride=ROW_STRIDE,
            force_mode="columnar_host",
            host_workers=HOST_WORKERS,
            gather_frame=gather,
        )
        codes = engine.enable_coprocessors([(1, spec.to_json(), ("bench",))])
        assert codes[0] == 0
        _run_engine_stream(engine, req, GROUP, GROUP, DEPTH)  # warmup
        engine.reset_stats()
        rate = _run_engine_stream(engine, req, 4 * GROUP, GROUP, DEPTH)
        stats = engine.stats()
        out[key] = {
            "record_batches_per_sec": round(rate, 1),
            "harvest_mode": _harvest_mode(stats),
            "stages": _fmt_stages(stats),
            "arena": stats.get("arena"),
        }
        engine.shutdown()
    return out


def run_mesh_64p() -> dict:
    """Config-5 promotion, MEASURED (the MULTICHIP_r06 artifact): the
    64-partition JSON-filter workload through the mesh-sharded engine
    (coproc/meshrunner.py — per-device sub-launches, one SPMD predicate
    program over the partition axis) against the 1-device ablation, with
    the A/A skew band applied to the delta. Bit-parity between the two
    engines is ASSERTED on a live request here (the same contract the
    test_meshrunner matrix pins), and the governor's mesh-domain journal
    rides in the artifact so the mesh-vs-single decision is
    reconstructible.

    Caller must provide >= 2 devices on the cpu backend (``bench.py
    mesh`` spawns this in a child with the host-platform device flag;
    on real multi-chip hardware the mesh spans the actual chips)."""
    from redpanda_tpu.coproc import TpuEngine
    from redpanda_tpu.coproc import governor as gov_mod
    from redpanda_tpu.coproc.meshrunner import available_devices

    n_dev = len(available_devices("cpu"))
    if n_dev < 2:
        return {"skipped": True, "reason": f"need >= 2 devices, have {n_dev}"}
    n_dev = min(8, n_dev)
    req = _build_workload()
    aa = _measure_aa_skew(req)
    gov_mod.reset_journal()
    # mesh lane pinned (mesh_probe=False): the 1-device run IS the
    # ablation, so the config must measure the lane, not the probe's
    # verdict about it — the probe's own measured verdict is reported
    # separately by the headline bench's product path
    TpuEngine.reset_columnar_probe()
    mesh_rate, mesh_stages, mesh_probe = _run_engine_mode(
        req, None, colcache_mb=32,
        mesh_devices=n_dev, mesh_backend="cpu", mesh_probe=False,
    )
    TpuEngine.reset_columnar_probe()
    one_rate, one_stages, _ = _run_engine_mode(req, None, colcache_mb=32)
    # live bit-parity assertion between the two paths
    TpuEngine.reset_columnar_probe()
    em = TpuEngine(
        row_stride=ROW_STRIDE, host_workers=HOST_WORKERS,
        mesh_devices=n_dev, mesh_backend="cpu", mesh_probe=False,
    )
    e1 = TpuEngine(row_stride=ROW_STRIDE, host_workers=0)
    for e in (em, e1):
        assert e.enable_coprocessors([(1, _spec().to_json(), ("bench",))]) == [0]
    pm = [
        (it.script_id, [b.payload for b in it.batches])
        for it in em.process_batch(req).items
    ]
    p1 = [
        (it.script_id, [b.payload for b in it.batches])
        for it in e1.process_batch(req).items
    ]
    em.shutdown()
    e1.shutdown()
    assert pm == p1, "mesh output diverged from the single-device path"
    delta_pct = (mesh_rate - one_rate) / one_rate * 100.0 if one_rate else 0.0
    verdict = (
        "within-band"
        if abs(delta_pct) <= aa["aa_skew_pct"]
        else ("mesh-win" if delta_pct > 0 else "mesh-loss")
    )
    return {
        "measured": True,
        "dryrun": False,
        "config": "mesh_64p",
        "n_devices": n_dev,
        "mesh_rb_s": round(mesh_rate, 1),
        "ablation_1dev_rb_s": round(one_rate, 1),
        "delta_pct": round(delta_pct, 1),
        "aa_skew_pct": aa["aa_skew_pct"],
        "aa_rates_rb_s": aa["aa_rates_rb_s"],
        "verdict": verdict,
        "parity": "bit-identical (asserted live; matrix in tests/test_meshrunner.py)",
        "mesh": mesh_probe.get("mesh"),
        "stages_mesh": mesh_stages,
        "stages_1dev": one_stages,
        "governor_journal_mesh": gov_mod.journal.entries(
            domain=gov_mod.MESH
        ),
    }


def main_mesh() -> None:
    """``python bench.py mesh``: the multichip round on 8 VIRTUAL host
    devices — a CPU-pinned process (it never asks for the chip its parent
    holds), and a CPU measurement: the block carries its platform."""
    from redpanda_tpu.utils.platform import device_info, force_cpu_platform

    force_cpu_platform(8)
    out = {"device": device_info(), **run_mesh_64p()}
    # the microbench gate on the same mesh: sharded CRC+vote step at
    # 1/2/4/8 devices with the no-regression floor (see
    # tools/microbench.py bench_mesh_scaling threshold guidance)
    from tools.microbench import bench_mesh_scaling

    scaling = bench_mesh_scaling(1.0)
    floor = float(os.environ.get("BENCH_MESH_SPEEDUP_FLOOR", "0.9"))
    scaling["assert_mesh_speedup"] = {
        "threshold": floor,
        "speedup": scaling.get("mesh_speedup_best", 0.0),
        "pass": scaling.get("mesh_speedup_best", 0.0) >= floor,
    }
    out["mesh_scaling"] = scaling
    print(json.dumps(out))


def run_link_profile() -> dict:
    """Quick device-link physics: sync RTT and H2D bandwidth (the numbers
    that justify columnar pushdown; full probe in tools/link_probe.py)."""
    import jax

    tiny = np.zeros(8, np.uint8)
    np.asarray(jax.device_put(tiny))  # warm
    t0 = time.perf_counter()
    for _ in range(3):
        np.asarray(jax.device_put(tiny))
    rtt_ms = (time.perf_counter() - t0) / 3 * 1e3
    arr = np.random.default_rng(0).integers(0, 255, 8 << 20, np.uint8)
    f = jax.jit(lambda x: x.astype(np.int32).sum())
    jax.block_until_ready(f(arr))  # warm + compile
    t0 = time.perf_counter()
    jax.block_until_ready(f(arr))
    h2d = 8 / (time.perf_counter() - t0)
    return {"rtt_ms": round(rtt_ms, 1), "h2d_mb_s_consumed": round(h2d, 1)}


def _bench_diff_block(against_path: str, artifact: dict) -> dict:
    """ISSUE 17 release-flow judgment on the BENCH side: diff this run
    against a prior artifact through tools/pulsediff.py. A timeline
    baseline (a saved ``rpk debug profile --perfetto`` / pulse block
    export) judges against THIS run's pulse-round timeline stage by
    stage; a BENCH/SLO baseline delegates to slodiff as before. The
    bench's own measured A/A band rides as the noise band either way."""
    from tools import pulsediff

    try:
        baseline = pulsediff._load(against_path)
        if pulsediff.is_timeline(baseline):
            tl = _LAST_PULSE_TIMELINE
            if tl is None:
                raise ValueError(
                    "no pulse timeline captured this run to diff against"
                )
            tl = dict(tl)
            tl.setdefault("aa_band_pct", artifact.get("aa_skew_pct"))
            d = pulsediff.diff_artifacts(baseline, tl, None)
        else:
            d = pulsediff.diff_artifacts(baseline, artifact, None)
        d["against"] = against_path
        return d
    except Exception as exc:  # the measured run must never sink on a diff
        return {"against": against_path, "error": repr(exc),
                "verdict": "NO_BASELINE"}


def main(diff_against: str | None = None):
    from redpanda_tpu.utils.platform import device_info, enable_compile_cache

    # JAX is initialized here, once: this process measures and holds the chip
    enable_compile_cache()
    device = device_info()
    if device["platform"] == "cpu":
        sys.exit(
            f"bench.py measures the accelerator path, and JAX found platform "
            f"{device['platform']!r} (device_kind {device['device_kind']!r}): "
            "no chip, no number. Tests and dry runs are "
            "`JAX_PLATFORMS=cpu python -m pytest tests/`."
        )
    req = _build_workload()
    from redpanda_tpu.coproc import TpuEngine

    # A/A control FIRST: whatever the measured runs report, the artifact
    # carries the box's own same-code noise band to judge deltas against
    aa = _measure_aa_skew(req)
    TpuEngine.reset_columnar_probe()  # the headline measures its own pick
    # PRODUCT path: broker posture — column cache on (the bench's steady
    # state is a repeat script over unchanged partitions, exactly the
    # workload the cache exists for; its hit rate rides in the artifact)
    value, stages, probe = _run_engine_mode(
        req, None, colcache_mb=32
    )
    # cache-off ablation of the SAME product path: attributes the headline
    # delta between the parse/extract machinery and the cache
    TpuEngine.reset_columnar_probe()
    nc_rate, nc_stages, nc_probe = _run_engine_mode(req, None)
    TpuEngine.reset_columnar_probe()
    dev_rate, dev_stages, _ = _run_engine_mode(req, "columnar_device")
    host_col_rate, host_col_stages, _ = _run_engine_mode(req, "columnar_host")
    # pool-off ablation: the acceptance bar is "no regression when the pool
    # is off", so the same product path runs again with ONE worker (inline).
    # Reset the sticky backend probe first — the ablation engine must
    # re-measure device-vs-host itself, not inherit the headline's pick.
    TpuEngine.reset_columnar_probe()
    w1_rate, w1_stages, w1_probe = _run_engine_mode(req, None, host_workers=1)
    baseline = run_cpu_baseline(req)

    columnar_probe = probe["columnar_probe"]
    columnar_backend = probe["columnar_backend"]

    extras = {}
    # mesh_64p runs in a CPU-PINNED child on 8 virtual host devices
    # (they can only be requested before a backend initializes, and
    # this process's is the chip). The child never asks for the chip,
    # so one process holds it throughout; its block names its platform.
    child = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "mesh"],
        stdout=subprocess.PIPE, timeout=1800, check=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        cwd=os.path.dirname(os.path.abspath(__file__)),
    )
    extras["mesh_64p"] = json.loads(
        child.stdout.decode().strip().splitlines()[-1]
    )
    extras["harvest_passthrough_64p"] = run_harvest_passthrough(req)
    extras["config1_crc_validate"] = run_config1_crc_validate()
    extras["config2_lz4_produce"] = run_config2_lz4_produce()
    extras["config3_identity_16p"] = run_config3_identity(TpuEngine)
    extras["config3_payload_bridge_16p"] = run_config3_identity(
        TpuEngine, force_mode="payload"
    )
    extras["link"] = run_link_profile()
    from redpanda_tpu.ops.lz4_device import measure_probe

    # the SURVEY §7 "measure first" item: device LZ4 block decode vs
    # host liblz4, keep-or-kill on the recorded ratio (ops/lz4_device.py)
    extras["device_lz4_probe"] = measure_probe(
        n_records=32, record_size=256, reps=1
    )
    # decision-plane record for the whole bench process: every adaptive
    # decision any of the runs made (calibrations, backend probes,
    # breaker transitions, harvest/seal modes, lz4 keep-or-kill,
    # deadline moves) is reconstructible from this block alone — the
    # same view /v1/governor serves on a live broker
    from redpanda_tpu.coproc import governor as gov_mod

    extras["governor"] = {
        "posture": probe["governor_posture"],
        "journal": gov_mod.journal.summary(),
        "journal_tail": gov_mod.journal.entries(limit=16),
    }
    # ISSUE 17: the pandatrend block — history-recorder counter tracks
    # for one columnar round, sampled FIRST so the pulse block's
    # timeline below carries them as ph:"C" lanes on the span clock
    extras["trend"] = run_trend_block()
    # ISSUE 14: the pandapulse block — flight-recorder stage totals +
    # timeline/journal event counts for one instrumented round
    extras["pulse"] = run_pulse_block()

    artifact = (
            {
                "metric": "coproc_json_filter_record_batches_per_sec_64p",
                "value": round(value, 1),
                "unit": "record_batches/s",
                "vs_baseline": round(value / baseline, 2),
                "baseline_cpu_single_core": round(baseline, 1),
                # platform / device_kind / count as JAX reports them
                "device": device,
                # same-code A/A control measured before everything else:
                # deltas inside this band are box noise, not regressions
                "aa_skew_pct": aa["aa_skew_pct"],
                "aa_rates_rb_s": aa["aa_rates_rb_s"],
                "partitions": P,
                "records_per_batch": RECORDS_PER_BATCH,
                "group_ticks_per_launch": GROUP,
                "launch_depth": DEPTH,
                "engine_mode": "columnar",
                "host_workers": HOST_WORKERS,
                # zero-copy harvest bookkeeping for the headline run (the
                # projection headline assembles new bytes, so this is
                # honestly "padded"; harvest_passthrough_64p carries the
                # gather-vs-padded ablation)
                "harvest_mode": probe["harvest_mode"],
                "arena": probe["arena"],
                # structural-index parse + device column cache (PR 11):
                # which parse ladder the engine's measured probe picked,
                # its timings, and the headline's cache hit rate
                "parse_path": probe["parse_path"],
                "parse_probe": probe["parse_probe"],
                "colcache": probe["colcache"],
                # the SAME product path with the column cache off: the
                # honest split of the headline between parse/extract
                # machinery and cache hits
                "colcache_off_ablation": {
                    "record_batches_per_sec": round(nc_rate, 1),
                    "parse_path": nc_probe["parse_path"],
                    "parse_probe": nc_probe["parse_probe"],
                    "stages": nc_stages,
                },
                "host_workers1_ablation": {
                    "record_batches_per_sec": round(w1_rate, 1),
                    "stages": w1_stages,
                    # re-probed after reset_columnar_probe(): proves the
                    # ablation measured its own backend pick
                    "columnar_backend": w1_probe["columnar_backend"],
                },
                # where the predicate ran in the headline: the engine's own
                # measured probe decides (device vs numpy over the SAME
                # extracted columns) — probe timings on record
                "columnar_backend": columnar_backend,
                "columnar_probe": columnar_probe,
                "stages": stages,
                # both halves of the decision, every run: vs_host_columnar
                # is what the DEVICE contributes over the identical plan
                # with a numpy predicate; <=1.0 means the device does not
                # pay for its link on this hardware for this workload.
                "engine_device_columnar": {
                    "record_batches_per_sec": round(dev_rate, 1),
                    "stages": dev_stages,
                },
                "engine_host_columnar": {
                    "record_batches_per_sec": round(host_col_rate, 1),
                    "stages": host_col_stages,
                },
                "vs_host_columnar": round(dev_rate / host_col_rate, 2),
                **extras,
            }
    )
    if diff_against:
        artifact["diff"] = _bench_diff_block(diff_against, artifact)
    print(json.dumps(artifact))


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "mesh":
        main_mesh()
    else:
        _da = None
        if "--diff-against" in sys.argv:
            _i = sys.argv.index("--diff-against")
            if _i + 1 >= len(sys.argv):
                sys.exit("--diff-against requires a path")
            _da = sys.argv[_i + 1]
        main(diff_against=_da)
