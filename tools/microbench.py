"""Component microbenchmarks.

Parity with the reference's seastar perf tests (SURVEY §4.1: hashing
hash_bench, compression zstd_stream_bench, storage compaction_idx_bench,
rpc rpc_bench, cluster allocation_bench): each bench exercises one hot
component in isolation and reports ops/s or MB/s as one JSON object on
stdout. Run-it-yourself, like the reference's: `python tools/microbench.py
[--secs 0.5] [--only crc32c,rpc_echo,...]`.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def _rate(fn, secs: float, unit_per_call: float) -> float:
    """Calls fn in a timed loop; returns units/sec."""
    # warmup
    fn()
    n = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < secs:
        fn()
        n += 1
    dt = time.perf_counter() - t0
    return n * unit_per_call / dt


def bench_crc32c(secs: float) -> dict:
    from redpanda_tpu.hashing.crc32c import crc32c

    blob = os.urandom(1 << 20)
    mb_s = _rate(lambda: crc32c(blob), secs, 1.0)  # 1 MB per call
    return {"crc32c_mb_s": round(mb_s, 1)}


def bench_xxhash(secs: float) -> dict:
    from redpanda_tpu.hashing.xx import xxhash64

    blob = os.urandom(1 << 20)
    return {"xxhash64_mb_s": round(_rate(lambda: xxhash64(blob), secs, 1.0), 1)}


def bench_zstd_stream(secs: float) -> dict:
    from redpanda_tpu.compression import compress, uncompress
    from redpanda_tpu.models.record import Compression

    rng = np.random.default_rng(7)
    # compressible-ish payload (zstd_stream_bench uses realistic frames)
    blob = bytes(rng.integers(0, 16, 1 << 20, dtype=np.uint8))
    packed = compress(blob, Compression.zstd)
    c = _rate(lambda: compress(blob, Compression.zstd), secs, 1.0)
    d = _rate(lambda: uncompress(packed, Compression.zstd), secs, 1.0)
    return {"zstd_compress_mb_s": round(c, 1), "zstd_uncompress_mb_s": round(d, 1)}


def bench_batch_codec(secs: float) -> dict:
    from redpanda_tpu.models.record import Record, RecordBatch

    recs = [Record(offset_delta=i, value=b"x" * 256) for i in range(32)]
    batch = RecordBatch.build(recs, base_offset=0)
    wire = batch.encode_internal()
    enc = _rate(lambda: RecordBatch.build(recs, base_offset=0).encode_internal(), secs, 1.0)
    dec = _rate(lambda: RecordBatch.decode_internal(wire), secs, 1.0)
    return {
        "batch_encode_per_s": round(enc, 1),
        "batch_decode_per_s": round(dec, 1),
    }


def bench_explode_find(secs: float) -> dict:
    """Staged-vs-structural parse+extract ladders (min-of-blocks) over
    three record shapes, plus the old per-component rates.

    staged = the scalar rp_explode_find ladder exactly as the engine runs
    it (Python payload join, scalar fused parse, per-column span gathers +
    pads, project_rows crossing); structural = the fused ladder
    (rp_explode_find2 pointer-table parse — no join for projection plans —
    + ONE rp_extract_cols2 extraction crossing). The parse-only split is
    also reported so the kernel and the fusion are attributable
    separately.

    Shapes: ``flat`` is the 64-partition catch-up shape (~1KB records, one
    long string value — the scalar walker's memchr best case, where the
    two ladders are closest); ``nested`` buries an unselected nested
    container the scalar walker must skip byte-at-a-time; ``stringified``
    carries a stringified-JSON msg (escaped quotes everywhere — the
    memchr-restart pathology, and THE log-analytics shape the structural
    escape mask exists for). --assert-explode-speedup gates
    ``explode_find_speedup`` = staged/structural on the stringified shape.
    The engine takes the structural ladder for every plan it can serve
    (``ColumnarPlan.structural_eligible``)."""
    from redpanda_tpu.coproc import batch_codec
    from redpanda_tpu.coproc.column_plan import plan_spec
    from redpanda_tpu.models.record import Record, RecordBatch
    from redpanda_tpu.ops.exprs import field
    from redpanda_tpu.ops.transforms import Int, Str, map_project, where

    rng = np.random.default_rng(0)

    def flat(p, i):
        return json.dumps({
            "level": ["error", "info", "warn"][(p + i) % 3], "code": i,
            "msg": "x" * (900 + int(rng.integers(0, 100))),
        }).encode()

    def nested(p, i):
        inner = {
            "user": {"id": i, "tags": ["a", "b", "c"],
                     "attrs": {f"k{j}": j for j in range(20)}},
            "ctx": [{"s": "x", "n": j} for j in range(10)],
        }
        return json.dumps({
            "level": ["error", "info"][i % 2], "payload": inner,
            "code": i, "msg": "x" * 120,
        }).encode()

    def stringified(p, i):
        inner = json.dumps({
            "trace": "abc", "fields": {f"f{j}": "v" * 8 for j in range(24)},
        })
        return json.dumps({
            "level": ["error", "info"][i % 2], "code": i, "msg": inner,
        }).encode()

    spec = where(field("level") == "error") | map_project(Int("code"), Str("msg", 64))
    plan = plan_spec(spec)
    paths = plan.flat_paths()
    lib = batch_codec._native()
    out = {}

    def min_of_blocks(fn) -> float:
        fn()  # warmup
        best = float("inf")
        t_end = time.perf_counter() + secs
        while time.perf_counter() < t_end:
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    def bucket(n: int) -> int:
        b = 128
        while b < n:
            b *= 2
        return b

    for shape, value_fn in (("flat", flat), ("nested", nested),
                            ("stringified", stringified)):
        batches = [
            RecordBatch.build(
                [Record(offset_delta=i, value=value_fn(p, i)) for i in range(32)],
                base_offset=0,
            )
            for p in range(64)
        ]
        n = 64 * 32
        n_pad = bucket(n)

        def staged():
            got = batch_codec.explode_and_find(batches, paths)
            if got is None:
                raise RuntimeError("staged native ladder unavailable")
            ex, types, vs, ve = got
            cache = plan.make_cache_from_tables(ex, paths, types, vs, ve)
            plan.extract_device_inputs(ex.joined, ex.offsets, ex.sizes, n_pad, cache)
            plan.extract_projection(ex.joined, ex.offsets, ex.sizes, cache)

        def structural():
            sp = batch_codec.explode_find_structural(batches, paths, False)
            if sp is None:
                raise RuntimeError("structural native ladder unavailable")
            plan.extract_fused(sp, n_pad)

        try:
            s = min_of_blocks(staged)
        except RuntimeError:
            out["explode_find_skipped"] = "native lib unavailable"
            return out
        out[f"explode_find_{shape}_staged_ms"] = round(s * 1e3, 3)
        out[f"explode_find_{shape}_staged_recs_per_s"] = round(n / s, 1)
        if lib is not None and getattr(lib, "has_structural", False):
            f = min_of_blocks(structural)
            out[f"explode_find_{shape}_structural_ms"] = round(f * 1e3, 3)
            out[f"explode_find_{shape}_structural_recs_per_s"] = round(n / f, 1)
            out[f"explode_find_{shape}_speedup"] = round(s / f, 3)
            # parse-only split: the kernels alone, identical inputs
            payloads, counts, p_off, p_len, _r, joined, _n = (
                batch_codec._gather_payloads(batches)
            )
            ps = min_of_blocks(
                lambda: lib.explode_find(joined, p_off, p_len, counts, paths)
            )
            pf = min_of_blocks(
                lambda: lib.explode_find_structural(payloads, counts, paths, False)
            )
            out[f"explode_find_{shape}_parse_scalar_ms"] = round(ps * 1e3, 3)
            out[f"explode_find_{shape}_parse_structural_ms"] = round(pf * 1e3, 3)
    if "explode_find_stringified_speedup" in out:
        # the gated number: the structural-index target shape
        out["explode_find_speedup"] = out["explode_find_stringified_speedup"]
    return out


def bench_mesh_scaling(secs: float) -> dict:
    """Multi-chip mesh scaling: the config-5 sharded CRC+vote step
    (parallel.collectives.make_crc_vote_step — the device half of the
    meshrunner's launch) over the SAME total work at 1/2/4/8 devices on
    the host-platform mesh. Pure device compute, no host ladder: the
    ratio is what the mesh buys the kernel, not parse noise. Rates are
    best-of-rounds (min-of-blocks posture). Reports rows/s per device
    count plus ``mesh_speedup_best`` = best multi-device rate over the
    1-device mesh — the ``--assert-mesh-speedup`` gate's input.

    Requires the virtual host-platform mesh
    (XLA_FLAGS=--xla_force_host_platform_device_count=8, set by
    force_cpu_platform before jax initializes); device counts beyond
    what the backend offers are skipped and reported as absent.

    Threshold guidance for the gate: virtual host-platform devices share
    the box's real cores, so the achievable ratio is bounded by the
    MEASURED parallel capacity reported alongside
    (``mesh_parallel_capacity``) — on a quota-limited 1-core box the
    honest floor is ~1.0
    (the sharded program must cost nothing over the 1-device mesh: a
    no-regression gate), while co-located multi-chip ICI justifies 1.5+.
    The engine itself never trusts this bench: the meshrunner's own
    PROBE_MARGIN calibration decides mesh-vs-single per process."""
    from redpanda_tpu.utils.platform import force_cpu_platform

    force_cpu_platform(8)
    import jax

    from redpanda_tpu.hashing.crc32c import crc32c
    from redpanda_tpu.parallel import make_crc_vote_step, partition_mesh, shard_to_mesh

    devs = jax.devices()
    rng = np.random.default_rng(7)
    n_batches, r, groups = 512, 1024, 64
    payloads = [rng.bytes(r - (i % 129)) for i in range(n_batches)]
    rows = np.zeros((n_batches, r), np.uint8)
    lens = np.empty(n_batches, np.int32)
    claimed = np.empty(n_batches, np.uint32)
    for i, p in enumerate(payloads):
        rows[i, : len(p)] = np.frombuffer(p, np.uint8)
        lens[i] = len(p)
        claimed[i] = crc32c(p)
    out: dict = {"mesh_available_devices": len(devs)}
    rates: dict[int, float] = {}
    for d in (1, 2, 4, 8):
        if d > len(devs) or n_batches % d:
            continue
        mesh = partition_mesh(devices=devs[:d])
        step = make_crc_vote_step(mesh, r)
        votes = rng.integers(0, 2, (d, groups)).astype(np.uint8)
        args = shard_to_mesh(
            mesh,
            rows.reshape(d, n_batches // d, r),
            lens.reshape(d, n_batches // d),
            claimed.reshape(d, n_batches // d),
            votes,
        )
        ok, _bad, tally = step(*args)  # compile + warm off the clock
        assert bool(np.asarray(ok).all()), "CRC kernel mismatch on probe rows"
        assert np.array_equal(
            np.asarray(tally), votes.astype(np.int32).sum(axis=0)
        ), "vote psum mismatch vs host oracle"
        best = 0.0
        t_end = time.perf_counter() + secs
        while time.perf_counter() < t_end:
            t0 = time.perf_counter()
            jax.block_until_ready(step(*args))
            best = max(best, n_batches / (time.perf_counter() - t0))
        rates[d] = best
        out[f"mesh_d{d}_batches_per_s"] = round(best, 1)
    if 1 in rates and len(rates) > 1:
        out["mesh_speedup_best"] = round(
            max(v for d, v in rates.items() if d > 1) / rates[1], 3
        )
    # context for ~1.0x results: what thread-level parallelism this box
    # actually has (virtual devices share the real cores)
    from redpanda_tpu.coproc import host_pool

    out["mesh_parallel_capacity"] = host_pool.measure_parallel_capacity()[
        "speedup"
    ]
    return out


def bench_harvest_path(secs: float) -> dict:
    """Zero-copy harvest: gather vs padded framing on the 64-partition
    JSON-filter workload (a pure where-filter -> passthrough plan, ~1KB
    records — the shape where the padded path's [N, maxlen] row matrix
    is pure overhead).

    STAGE-TIME criterion, min-of-blocks: wall-clock A/B on a shared box
    has ±30% A/A skew, so each block runs the same tick count and sums the
    engine's own harvest-side stage seconds (extract_proj + assemble +
    frame + seal); the per-mode result is the BEST block. The output
    recompression cost is mode-independent (identical bytes compress on
    both paths), so compress_threshold is maxed to keep the codec's
    throughput — measured by zstd_stream on its own — from diluting the
    copy physics the gather path removes."""
    from redpanda_tpu.coproc import TpuEngine, ProcessBatchRequest
    from redpanda_tpu.coproc.engine import ProcessBatchItem
    from redpanda_tpu.models import NTP
    from redpanda_tpu.models.record import Record, RecordBatch
    from redpanda_tpu.ops.exprs import field
    from redpanda_tpu.ops.transforms import where

    rng = np.random.default_rng(11)
    spec = where(field("level") == "error")
    items = []
    for p in range(64):
        recs = [
            Record(
                offset_delta=i,
                value=json.dumps({
                    "level": ["error", "info", "warn"][(p + i) % 3],
                    "code": i,
                    "msg": "x" * (900 + int(rng.integers(0, 100))),
                }).encode(),
            )
            for i in range(32)
        ]
        items.append(
            ProcessBatchItem(1, NTP.kafka("bench", p), [RecordBatch.build(recs, base_offset=0)])
        )
    req = ProcessBatchRequest(items)
    n_recs = 64 * 32
    stage_keys = (
        "t_extract_proj", "t_assemble", "t_rebuild",
        "t_frame_gather", "t_seal",
    )
    ticks_per_block = 4
    out = {}
    for mode, gather in (("gather", True), ("padded", False)):
        engine = TpuEngine(
            row_stride=1152,
            compress_threshold=10**9,
            force_mode="columnar_host",
            host_workers=0,
            gather_frame=gather,
        )
        codes = engine.enable_coprocessors([(1, spec.to_json(), ("bench",))])
        assert codes == [0]
        engine.process_batch(req)  # warmup
        best_stage = float("inf")
        best_rate = 0.0
        t_end = time.perf_counter() + secs
        while time.perf_counter() < t_end:
            engine.reset_stats()
            t0 = time.perf_counter()
            for _ in range(ticks_per_block):
                engine.process_batch(req)
            dt = time.perf_counter() - t0
            stats = engine.stats()
            block = sum(stats.get(k, 0.0) for k in stage_keys)
            best_stage = min(best_stage, block)
            best_rate = max(best_rate, ticks_per_block * n_recs / dt)
        out[f"harvest_{mode}_stage_s"] = round(best_stage, 6)
        out[f"harvest_{mode}_recs_per_s"] = round(best_rate, 1)
        engine.shutdown()
    gather_s = out["harvest_gather_stage_s"]
    padded_s = out["harvest_padded_stage_s"]
    out["harvest_speedup"] = round(padded_s / gather_s, 3) if gather_s > 0 else 0.0
    out["harvest_stage_cut_pct"] = (
        round((1.0 - gather_s / padded_s) * 100.0, 1) if padded_s > 0 else 0.0
    )
    return out


def bench_compaction_index(secs: float) -> dict:
    """Key-index build rate (compaction_idx_bench shape)."""
    from redpanda_tpu.storage.compaction import KeyLatestIndex

    keys = [b"key-%06d" % (i % 4096) for i in range(10_000)]

    def build():
        idx = KeyLatestIndex(max_keys_in_memory=1 << 20)
        for off, k in enumerate(keys):
            idx.put(k, off)

    return {"compaction_keyindex_keys_per_s": round(_rate(build, secs, len(keys)), 1)}


def bench_allocation(secs: float) -> dict:
    """Partition allocator throughput (allocation_bench shape)."""
    from redpanda_tpu.cluster.allocator import PartitionAllocator

    def alloc():
        pa = PartitionAllocator()
        for nid in range(5):
            pa.register_node(nid)
        for _ in range(16):
            pa.allocate(6, 3)

    return {"allocator_assignments_per_s": round(_rate(alloc, secs, 16 * 6), 1)}


def bench_tracer_overhead(secs: float) -> dict:
    """Disabled-tracer cost on a produce-hot-path-shaped op.

    Baseline = batch build+encode (the codec work every produce pays);
    traced = the same op under a DISABLED ``tracer.span(...)`` — the
    exact no-op the instrumented produce path executes when tracing is
    off. The always-on probe layer (a perf_counter pair + histogram
    record, the reference's probe.h cost) is measured and reported
    SEPARATELY (``probe_cost_ns``): it is a deliberate steady cost, not
    part of the disabled-tracer budget.

    The headline ``tracer_disabled_overhead_pct`` is DERIVED: (min-based
    per-call cost of the disabled span alone) / (min-based per-op cost of
    the payload). The span is strictly additive straight-line code, so
    the quotient IS its share of the hot path — and both measurements use
    timeit's min-of-many-blocks posture, which resolves nanoseconds
    reliably. The direct A/B wall-clock ratio is reported too
    (``tracer_ab_overhead_pct``) but is informational only: its
    shared-machine noise floor (~5-10%) sits far above the sub-1% signal,
    as an A/A control run demonstrates. The acceptance bar (<2%) is
    asserted by --assert-tracer-overhead, not here."""
    from redpanda_tpu.observability import tracer

    was_enabled = tracer.enabled
    tracer.configure(enabled=False)
    try:
        return _bench_tracer_overhead_disabled(secs)
    finally:
        # the process-wide tracer must come back even if the bench raises
        tracer.configure(enabled=was_enabled)


def _bench_tracer_overhead_disabled(secs: float) -> dict:
    from redpanda_tpu.models.record import Record, RecordBatch
    from redpanda_tpu.observability import probes, tracer

    recs = [Record(offset_delta=i, value=b"x" * 256) for i in range(32)]

    def op():
        RecordBatch.build(recs, base_offset=0).encode_internal()

    # scratch histogram, NOT a registered series: the probe-cost loop below
    # records thousands of synthetic samples, which must never leak into
    # the live registry a --metrics-snapshot run is diffing
    from redpanda_tpu.metrics import Histogram

    hist = Histogram("bench_scratch_us", "unregistered bench scratch")

    def traced_op():
        with tracer.span("bench.produce"):
            op()

    def timed_block(fn, k: int) -> float:
        t0 = time.perf_counter()
        for _ in range(k):
            fn()
        return time.perf_counter() - t0

    # warmup + block sizing: many short rounds inside the time budget
    op()
    traced_op()
    per_op = min(timed_block(op, 4) / 4 for _ in range(3))
    # ~3 ms blocks: short enough that plenty of rounds dodge load spikes
    # entirely, long enough to amortize the timer reads
    k = max(4, int(0.003 / per_op))
    rounds = max(24, int(secs * 2 / (2 * k * per_op)))
    best_base = float("inf")
    best_traced = float("inf")
    n_done = 0
    for r in range(rounds):
        if r % 2 == 0:
            tb, tt = timed_block(op, k), timed_block(traced_op, k)
        else:
            tt, tb = timed_block(traced_op, k), timed_block(op, k)
        best_base = min(best_base, tb / k)
        best_traced = min(best_traced, tt / k)
        n_done += 2 * k
    # per-call cost of the disabled span alone, then of one probe
    # histogram observation — same min-of-blocks discipline
    span_ns = float("inf")
    probe_ns = float("inf")
    for _ in range(10):
        n_raw = 2000
        t0 = time.perf_counter()
        for _ in range(n_raw):
            with tracer.span("bench.noop"):
                pass
        span_ns = min(span_ns, (time.perf_counter() - t0) / n_raw * 1e9)
        t0 = time.perf_counter()
        for _ in range(n_raw):
            probes.observe_us(hist, t0)
        probe_ns = min(probe_ns, (time.perf_counter() - t0) / n_raw * 1e9)
    ab_pct = (best_traced / best_base - 1.0) * 100.0 if best_base else 0.0
    overhead_pct = span_ns / (best_base * 1e9) * 100.0 if best_base else 0.0
    return {
        **_stage_helper_costs(hist),
        "tracer_block_ops": n_done,
        "tracer_span_cost_ns": round(span_ns, 1),
        "probe_cost_ns": round(probe_ns, 1),
        "tracer_op_cost_ns": round(best_base * 1e9, 1),
        "tracer_ab_overhead_pct": round(max(ab_pct, 0.0), 2),
        "tracer_disabled_overhead_pct": round(overhead_pct, 2),
    }


def _stage_helper_costs(hist) -> dict:
    """Per-call cost of the stage helper (observability/stages.py), the
    always-on timer of the serving path, in both forms: tracing off
    (histogram record + the profiler annotation's ``is_enabled`` check, jax
    imported, no profile running) and tracing on (the ring span on top).
    ``stage_tick_cost_us``: what one productive pacemaker tick pays for its
    own stages (2 begin/close, 5 ``with``, the gap sample), tracing off."""
    import jax  # noqa: F401  (binds the annotation: the broker's posture with coproc on)

    from redpanda_tpu.observability import stages, tracer

    def closed():
        stages.close("bench.stage", hist, stages.begin("bench.stage"))

    def with_form():
        with stages.stage("bench.stage", hist):
            pass

    def best_ns(fn) -> float:
        best = float("inf")
        for _ in range(10):
            t0 = time.perf_counter()
            for _ in range(2000):
                fn()
            best = min(best, (time.perf_counter() - t0) / 2000 * 1e9)
        return best

    hist_ns = best_ns(lambda: hist.record(1234))
    off = {"close": best_ns(closed), "with": best_ns(with_form)}
    tracer.configure(enabled=True)
    try:
        with tracer.span("bench.root", root=True):
            on = {"close": best_ns(closed), "with": best_ns(with_form)}
    finally:
        tracer.configure(enabled=False)
        tracer.reset()
    return {
        "histogram_record_ns": round(hist_ns, 1),
        "stage_close_off_ns": round(off["close"], 1),
        "stage_with_off_ns": round(off["with"], 1),
        "stage_close_on_ns": round(on["close"], 1),
        "stage_with_on_ns": round(on["with"], 1),
        "stage_tick_cost_us": round(
            (2 * off["close"] + 5 * off["with"] + hist_ns) / 1e3, 2
        ),
        "stage_tick_cost_on_us": round(
            (2 * on["close"] + 5 * on["with"] + hist_ns) / 1e3, 2
        ),
    }


def bench_slo_eval_overhead(secs: float) -> dict:
    """Cost of the always-on SLO layer on a produce-shaped op.

    What a produce pays for the SLO harness is ONE exemplar-aware
    histogram record (probes.record_us: the raw bucket record plus a
    threshold lookup + compare — the breach slow path never runs in
    steady state). Same derived min-of-blocks discipline as the tracer
    and breaker benches: wall-clock A/B cannot resolve sub-1% on a
    shared box, but the hook is strictly additive straight-line code, so
    (per-call hook delta) / (per-op cost) IS its share of the hot path.
    ``slo_evaluate_ms`` — one full spec evaluation, the operator-triggered
    GET /v1/slo cost — is reported informationally; it is never on a
    request path."""
    from redpanda_tpu.metrics import Histogram
    from redpanda_tpu.models.record import Record, RecordBatch
    from redpanda_tpu.observability import probes, tracer
    from redpanda_tpu.observability.slo import DEFAULT_SPEC, SloEngine

    was_enabled = tracer.enabled
    tracer.configure(enabled=False)
    recs = [Record(offset_delta=i, value=b"x" * 256) for i in range(32)]

    def op():
        RecordBatch.build(recs, base_offset=0).encode_internal()

    # scratch histograms, NOT registered series: thousands of synthetic
    # samples must never leak into the live registry
    raw = Histogram("bench_slo_raw_us", "unregistered bench scratch")
    hooked = Histogram("bench_slo_hooked_us", "unregistered bench scratch")
    # armed with a threshold the samples never cross: the steady-state
    # shape (the breach path is per-incident, not per-op)
    probes.arm_exemplar_threshold(hooked, 1e12)
    try:

        def timed_block(fn, k: int) -> float:
            t0 = time.perf_counter()
            for _ in range(k):
                fn()
            return time.perf_counter() - t0

        op()
        per_op = min(timed_block(op, 4) / 4 for _ in range(3))
        k = max(4, int(0.003 / per_op))
        rounds = max(16, int(secs / (k * per_op)))
        best_op = min(timed_block(op, k) / k for _ in range(rounds))

        record_ns = float("inf")
        hooked_ns = float("inf")
        n_raw = 2000
        for _ in range(10):
            t0 = time.perf_counter()
            for _ in range(n_raw):
                raw.record(500)
            record_ns = min(record_ns, (time.perf_counter() - t0) / n_raw * 1e9)
            t0 = time.perf_counter()
            for _ in range(n_raw):
                probes.record_us(hooked, 500)
            hooked_ns = min(hooked_ns, (time.perf_counter() - t0) / n_raw * 1e9)
        hook_ns = max(0.0, hooked_ns - record_ns)
        pct = hook_ns / (best_op * 1e9) * 100.0 if best_op else 0.0

        # informational: one operator-triggered evaluation of the default
        # spec over the live registry
        # arm=False: a read-only judgment — the bench must not overwrite
        # exemplar thresholds an in-process caller armed on the LIVE
        # registry with DEFAULT_SPEC's lenient ones
        eng = SloEngine()
        eng.evaluate(DEFAULT_SPEC, arm=False)  # warm lazy imports
        t0 = time.perf_counter()
        eng.evaluate(DEFAULT_SPEC, arm=False)
        eval_ms = (time.perf_counter() - t0) * 1e3
        return {
            "slo_record_raw_ns": round(record_ns, 1),
            "slo_record_hooked_ns": round(hooked_ns, 1),
            "slo_hook_cost_ns": round(hook_ns, 1),
            "slo_op_cost_ns": round(best_op * 1e9, 1),
            "slo_evaluate_ms": round(eval_ms, 3),
            "slo_eval_overhead_pct": round(pct, 3),
        }
    finally:
        # surgical: an in-process caller's armed objectives must survive
        probes.disarm_exemplar_threshold(hooked)
        tracer.configure(enabled=was_enabled)


def bench_breaker_overhead(secs: float) -> dict:
    """Cost of the fault machinery on the UNFAULTED coproc launch path.

    A healthy launch pays, per device leg: one closed-breaker
    ``allow_device()`` (a lock + two compares), one disabled honey-badger
    ``inject()`` (an attribute check), one ``record_success()``, and the
    ``retry_call`` envelope around the leg. The headline
    ``breaker_overhead_pct`` is DERIVED the same way as the tracer bench
    (wall-clock A/B cannot resolve sub-1% on a shared box): min-of-blocks
    per-call cost of the checks alone, times a deliberately conservative
    per-launch check count, over the min-of-blocks cost of a real
    columnar launch. The checks are strictly additive straight-line code,
    so the quotient IS their share of the hot path.

    The abandonable-fetch envelope (``fetch_envelope_us``) is reported
    separately and informationally: it prices the thread handoff a
    DEADLINE-BEARING device leg pays, which is per-launch, bounded, and a
    deliberate trade for wedge immunity — not part of the closed-breaker
    + disabled-badger budget the <1% gate covers."""
    import json as _json

    from redpanda_tpu.coproc import TpuEngine, ProcessBatchRequest, faults
    from redpanda_tpu.coproc.engine import ProcessBatchItem
    from redpanda_tpu.finjector import honey_badger
    from redpanda_tpu.models import NTP, Record, RecordBatch
    from redpanda_tpu.ops.exprs import field
    from redpanda_tpu.ops.transforms import Int, Str, map_project, where

    # disable() also CLEARS every armed probe, so snapshot the armed map
    # and re-arm on the way out — an in-process caller mid-fault-campaign
    # must get its badger back exactly as it was
    was_enabled = honey_badger.enabled
    was_armed = honey_badger.armed()
    honey_badger.disable()
    try:
        # a real launch as the denominator: columnar host predicate over
        # 512 records — device-free, so the op is deterministic on any box
        engine = TpuEngine(
            row_stride=256, compress_threshold=10**9,
            force_mode="columnar_host", host_workers=0,
        )
        spec = where(field("level") == "error") | map_project(
            Int("code"), Str("msg", 16)
        )
        engine.enable_coprocessors([(1, spec.to_json(), ("orders",))])
        recs = [
            Record(
                offset_delta=i, timestamp_delta=i,
                value=_json.dumps(
                    {"level": ["error", "info"][i % 2], "code": i,
                     "msg": f"m{i}"},
                    separators=(",", ":"),
                ).encode(),
            )
            for i in range(512)
        ]
        batch = RecordBatch.build(recs, base_offset=0, first_timestamp=1000)
        req = ProcessBatchRequest(
            [ProcessBatchItem(1, NTP.kafka("orders", 0), [batch])]
        )

        def op():
            engine.process_batch(req)

        def timed_block(fn, k: int) -> float:
            t0 = time.perf_counter()
            for _ in range(k):
                fn()
            return time.perf_counter() - t0

        op()  # warmup (plan compile, caches)
        per_op = min(timed_block(op, 2) / 2 for _ in range(3))
        k = max(2, int(0.01 / per_op))
        rounds = max(12, int(secs / (k * per_op)))
        best_op = min(timed_block(op, k) / k for _ in range(rounds))

        breaker = engine._breaker
        assert breaker.state == faults.STATE_CLOSED
        check_ns = float("inf")
        inject_ns = float("inf")
        success_ns = float("inf")
        n_raw = 5000
        for _ in range(10):
            t0 = time.perf_counter()
            for _ in range(n_raw):
                breaker.allow_device()
            check_ns = min(check_ns, (time.perf_counter() - t0) / n_raw * 1e9)
            t0 = time.perf_counter()
            for _ in range(n_raw):
                faults.inject(faults.DEVICE_DISPATCH)
            inject_ns = min(inject_ns, (time.perf_counter() - t0) / n_raw * 1e9)
            t0 = time.perf_counter()
            for _ in range(n_raw):
                breaker.record_success()
            success_ns = min(
                success_ns, (time.perf_counter() - t0) / n_raw * 1e9
            )
        # informational: the deadline envelope's thread handoff per leg
        envelope_s = float("inf")
        for _ in range(30):
            t0 = time.perf_counter()
            faults.fetch_with_deadline(lambda: None, 30.0)
            envelope_s = min(envelope_s, time.perf_counter() - t0)
        # conservative per-launch budget: dispatch + mask fetch + harvest
        # each pay one inject; one allow_device; two breaker verdicts
        checks_per_launch = 3 * inject_ns + check_ns + 2 * success_ns
        pct = checks_per_launch / (best_op * 1e9) * 100.0 if best_op else 0.0
        return {
            "breaker_check_ns": round(check_ns, 1),
            "badger_disabled_check_ns": round(inject_ns, 1),
            "breaker_record_success_ns": round(success_ns, 1),
            "breaker_launch_cost_us": round(best_op * 1e6, 1),
            "fetch_envelope_us": round(envelope_s * 1e6, 1),
            "breaker_overhead_pct": round(pct, 3),
        }
    finally:
        if was_enabled:
            honey_badger.enable()
            arm = {
                "exception": honey_badger.set_exception,
                "delay": honey_badger.set_delay,
                "wedge": honey_badger.set_wedge,
                "terminate": honey_badger.set_termination,
            }
            for module, probes_armed in was_armed.items():
                for probe, effect in probes_armed.items():
                    arm[effect](module, probe)


def bench_admission_overhead(secs: float) -> dict:
    """Cost of the budget-plane admission gate on the UNCONTENDED produce
    path (resource_mgmt): what every admitted produce pays is exactly ONE
    ``try_admit`` (an account lock + two compares + a counter) and ONE
    ``release`` — the shed path is the degraded case and allowed to cost
    more. Derived like breaker_overhead: min-of-blocks per-pair cost over
    the min-of-blocks cost of a REAL acked produce op (a full client →
    broker → storage round trip on an in-process single-node broker),
    because wall-clock A/B cannot resolve sub-1% on a shared box.
    ``--assert-admission-overhead 1`` gates the quotient."""
    import asyncio

    from redpanda_tpu.resource_mgmt import AdmissionController, BudgetPlane

    plane = BudgetPlane(256 << 20)
    ctrl = AdmissionController(plane.account("kafka_produce"), "bench_adm")
    n_raw = 20000
    pair_ns = float("inf")
    for _ in range(10):
        t0 = time.perf_counter()
        for _ in range(n_raw):
            reserved, _r = ctrl.try_admit(4096)
            ctrl.release(reserved)
        pair_ns = min(pair_ns, (time.perf_counter() - t0) / n_raw * 1e9)

    async def produce_op_us() -> float:
        import tempfile

        from redpanda_tpu.kafka.client import KafkaClient
        from redpanda_tpu.kafka.server.broker import Broker, BrokerConfig
        from redpanda_tpu.kafka.server.protocol import KafkaServer
        from redpanda_tpu.storage.log_manager import StorageApi

        with tempfile.TemporaryDirectory(prefix="mb-adm-") as d:
            storage = await StorageApi(d).start()
            broker = Broker(BrokerConfig(data_dir=d), storage)
            server = await KafkaServer(broker, "127.0.0.1", 0).start()
            broker.config.advertised_port = server.port
            client = await KafkaClient(
                [("127.0.0.1", server.port)]
            ).connect()
            try:
                payload = [b"x" * 512] * 4
                for _ in range(8):  # warmup: topic create, first appends
                    await client.produce("bench", 0, payload, acks=-1)
                best = float("inf")
                k = 32
                rounds = max(6, int(secs / 0.05))
                for _ in range(rounds):
                    t0 = time.perf_counter()
                    for _ in range(k):
                        await client.produce("bench", 0, payload, acks=-1)
                    best = min(best, (time.perf_counter() - t0) / k)
                return best * 1e6
            finally:
                await client.close()
                await server.stop()
                await storage.stop()

    op_us = asyncio.run(produce_op_us())
    pct = pair_ns / (op_us * 1e3) * 100.0 if op_us else 0.0
    return {
        "admission_pair_ns": round(pair_ns, 1),
        "admission_produce_op_us": round(op_us, 1),
        "admission_overhead_pct": round(pct, 4),
    }


def bench_governor_overhead(secs: float) -> dict:
    """Cost of the governor's decision-plane hooks on the UNFAULTED coproc
    launch path.

    What a healthy launch pays the governor, per launch: two
    ``record_mode`` calls on their CLOSED path (harvest-path + seal
    verdicts unchanged -> one lock + one compare each) and a few
    ``policy_for`` lookups (cached adaptive deadline -> two dict lookups +
    an int compare). The journal append itself runs only when a verdict
    CHANGES — per-incident, not per-launch — but its cost is priced too
    (``governor_journal_append_ns``) on a throwaway DecisionJournal so the
    live process journal and the decision counters stay untouched.

    Same derived min-of-blocks discipline as the tracer/breaker/slo
    benches: wall-clock A/B cannot resolve sub-1% on a shared box, but the
    hooks are strictly additive straight-line code, so (per-call hook
    cost x conservative per-launch count) / (per-launch cost) IS their
    share of the hot path. --assert-governor-overhead gates it."""
    import json as _json

    from redpanda_tpu.coproc import TpuEngine, ProcessBatchRequest, faults
    from redpanda_tpu.coproc import governor as gov
    from redpanda_tpu.coproc.engine import ProcessBatchItem
    from redpanda_tpu.models import NTP, Record, RecordBatch
    from redpanda_tpu.ops.exprs import field
    from redpanda_tpu.ops.transforms import Int, Str, map_project, where

    # the denominator: a real columnar host launch over 512 records (the
    # same deterministic device-free shape as the breaker bench)
    engine = TpuEngine(
        row_stride=256, compress_threshold=10**9,
        force_mode="columnar_host", host_workers=0,
    )
    spec = where(field("level") == "error") | map_project(
        Int("code"), Str("msg", 16)
    )
    engine.enable_coprocessors([(1, spec.to_json(), ("orders",))])
    recs = [
        Record(
            offset_delta=i, timestamp_delta=i,
            value=_json.dumps(
                {"level": ["error", "info"][i % 2], "code": i, "msg": f"m{i}"},
                separators=(",", ":"),
            ).encode(),
        )
        for i in range(512)
    ]
    batch = RecordBatch.build(recs, base_offset=0, first_timestamp=1000)
    req = ProcessBatchRequest(
        [ProcessBatchItem(1, NTP.kafka("orders", 0), [batch])]
    )

    def op():
        engine.process_batch(req)

    def timed_block(fn, k: int) -> float:
        t0 = time.perf_counter()
        for _ in range(k):
            fn()
        return time.perf_counter() - t0

    op()  # warmup (plan compile, caches, first record_mode entries)
    per_op = min(timed_block(op, 2) / 2 for _ in range(3))
    k = max(2, int(0.01 / per_op))
    rounds = max(12, int(secs / (k * per_op)))
    best_op = min(timed_block(op, k) / k for _ in range(rounds))

    # per-call hook costs on PRIVATE instances: the scratch governor gets
    # its OWN journal (journal_override: its priming entries and any
    # deadline derivation must not land in the live process journal or
    # move coproc_governor_decisions_total), its own histogram source
    # (the live stage histograms must not drive a scratch DEADLINE entry),
    # and no gauges (register_gauges=False: it must not steal the live
    # engine's labeled series)
    from redpanda_tpu.utils.hdr import HdrHist

    journal = gov.DecisionJournal(capacity=256)
    scratch_hists: dict = {}
    scratch = gov.Governor(
        fault_policy=faults.FaultPolicy(),
        register_gauges=False,
        journal_override=gov.DecisionJournal(capacity=256),
        stage_hist=lambda s: scratch_hists.setdefault(s, HdrHist()),
    )
    scratch.record_mode("harvest_path", "gather", "bench prime")
    scratch.policy_for(faults.DEVICE_DISPATCH)
    append_ns = mode_ns = policy_ns = float("inf")
    n_raw = 5000
    for _ in range(10):
        t0 = time.perf_counter()
        for _ in range(n_raw):
            journal.append(
                "harvest_path", "gather", "bench append", {"rows": 512}
            )
        append_ns = min(append_ns, (time.perf_counter() - t0) / n_raw * 1e9)
        t0 = time.perf_counter()
        for _ in range(n_raw):
            scratch.record_mode("harvest_path", "gather", "bench prime")
        mode_ns = min(mode_ns, (time.perf_counter() - t0) / n_raw * 1e9)
        t0 = time.perf_counter()
        for _ in range(n_raw):
            scratch.policy_for(faults.DEVICE_DISPATCH)
        policy_ns = min(policy_ns, (time.perf_counter() - t0) / n_raw * 1e9)
    # conservative per-launch budget: harvest-path + seal record_mode on
    # the closed path, plus a policy_for per device leg (dispatch, mask
    # fetch, harvest)
    hooks_per_launch = 2 * mode_ns + 3 * policy_ns
    pct = hooks_per_launch / (best_op * 1e9) * 100.0 if best_op else 0.0
    engine.shutdown()
    return {
        "governor_journal_append_ns": round(append_ns, 1),
        "governor_record_mode_closed_ns": round(mode_ns, 1),
        "governor_policy_for_ns": round(policy_ns, 1),
        "governor_launch_cost_us": round(best_op * 1e6, 1),
        "governor_overhead_pct": round(pct, 3),
    }


def bench_pulse_overhead(secs: float) -> dict:
    """Cost of the pandapulse flight recorder on a real columnar launch.

    The recorder rides the tracer's commit path: with pulse OFF the
    marginal cost is one attribute check inside ``Tracer._commit``; with
    pulse ON it is one bounded-deque append (+ a counter lock) per
    committed span. The tracer itself is priced and gated separately
    (``tracer_overhead`` / ``trace_propagation_overhead``) — this bench
    answers the ISSUE 14 acceptance question: recorder-on vs recorder-off
    on the SAME traced launch.

    Derived min-of-blocks discipline (wall A/B can't resolve sub-1% on a
    shared box): (per-span sink cost x spans-per-launch, both measured) /
    (per-launch cost). ``pulse_overhead_with_tracer_pct`` reports the
    tracer-inclusive number for context — what a fully dark launch pays
    to become a timeline. Also pins the profiler-off posture: profile_hz=0
    must run NO sampler thread."""
    import json as _json
    import threading as _threading

    from redpanda_tpu.coproc import TpuEngine, ProcessBatchRequest
    from redpanda_tpu.coproc.engine import ProcessBatchItem
    from redpanda_tpu.models import NTP, Record, RecordBatch
    from redpanda_tpu.observability.pulse import FlightRecorder
    from redpanda_tpu.observability.trace import Tracer, tracer
    from redpanda_tpu.ops.exprs import field
    from redpanda_tpu.ops.transforms import Int, Str, map_project, where

    engine = TpuEngine(
        row_stride=256, compress_threshold=10**9,
        force_mode="columnar_host", host_workers=0,
    )
    spec = where(field("level") == "error") | map_project(
        Int("code"), Str("msg", 16)
    )
    engine.enable_coprocessors([(1, spec.to_json(), ("orders",))])
    recs = [
        Record(
            offset_delta=i, timestamp_delta=i,
            value=_json.dumps(
                {"level": ["error", "info"][i % 2], "code": i, "msg": f"m{i}"},
                separators=(",", ":"),
            ).encode(),
        )
        for i in range(512)
    ]
    batch = RecordBatch.build(recs, base_offset=0, first_timestamp=1000)
    req = ProcessBatchRequest(
        [ProcessBatchItem(1, NTP.kafka("orders", 0), [batch])]
    )

    def op():
        engine.process_batch(req)

    def timed_block(fn, k: int) -> float:
        t0 = time.perf_counter()
        for _ in range(k):
            fn()
        return time.perf_counter() - t0

    op()  # warmup
    per_op = min(timed_block(op, 2) / 2 for _ in range(3))
    k = max(2, int(0.01 / per_op))
    rounds = max(12, int(secs / (k * per_op)))
    best_op = min(timed_block(op, k) / k for _ in range(rounds))

    # spans one traced launch commits (recorder installed, fresh ring):
    # the multiplier in the derived overhead
    was_enabled = tracer.enabled
    was_sink = tracer._sink
    probe_rec = FlightRecorder()
    tracer.configure(enabled=True)
    tracer.set_sink(probe_rec.record)
    try:
        req.trace_id = tracer.new_trace_id()
        op()
    finally:
        tracer.set_sink(was_sink)
        tracer.configure(enabled=was_enabled)
        req.trace_id = None
    spans_per_launch = len(probe_rec.spans())

    # per-call costs on PRIVATE instances (the live tracer/recorder rings
    # must not absorb bench spam): the sink append alone (the recorder-on
    # delta) and the full enabled commit+sink (tracer-inclusive context)
    scratch_rec = FlightRecorder(capacity=4096)
    scratch_tr = Tracer(enabled=True, capacity=2048)
    span_dict = {
        "trace_id": 1, "name": "coproc.stage.bench", "start_us": 0,
        "dur_us": 5, "thread": "bench", "span_id": 1,
    }
    sink_ns = commit_ns = commit_dark_ns = float("inf")
    n_raw = 5000
    for _ in range(10):
        t0 = time.perf_counter()
        for _ in range(n_raw):
            scratch_rec.record(span_dict)
        sink_ns = min(sink_ns, (time.perf_counter() - t0) / n_raw * 1e9)
        scratch_tr._sink = scratch_rec.record
        t0 = time.perf_counter()
        for _ in range(n_raw):
            scratch_tr.record("coproc.stage.bench", 5.0, 1)
        commit_ns = min(commit_ns, (time.perf_counter() - t0) / n_raw * 1e9)
        scratch_tr._sink = None
        t0 = time.perf_counter()
        for _ in range(n_raw):
            scratch_tr.record("coproc.stage.bench", 5.0, 1)
        commit_dark_ns = min(
            commit_dark_ns, (time.perf_counter() - t0) / n_raw * 1e9
        )
    engine.shutdown()
    launch_ns = best_op * 1e9
    pct = spans_per_launch * sink_ns / launch_ns * 100.0 if launch_ns else 0.0
    with_tracer_pct = (
        spans_per_launch * commit_ns / launch_ns * 100.0 if launch_ns else 0.0
    )
    profiler_threads = sum(
        1 for t in _threading.enumerate()
        if t.name == "rptpu-pulse-profiler"
    )
    out = {
        "pulse_sink_append_ns": round(sink_ns, 1),
        "pulse_span_commit_sink_ns": round(commit_ns, 1),
        "pulse_span_commit_dark_ns": round(commit_dark_ns, 1),
        "pulse_spans_per_launch": spans_per_launch,
        "pulse_launch_cost_us": round(best_op * 1e6, 1),
        "pulse_overhead_pct": round(pct, 3),
        "pulse_overhead_with_tracer_pct": round(with_tracer_pct, 3),
    }
    if profiler_threads:
        # profiler-off steady state: NO sampler thread may exist. The key
        # only appears on violation (the assert flag reads .get(..., 0),
        # and the all-benches positivity smoke would trip on a good 0).
        out["pulse_profiler_off_threads"] = profiler_threads
    return out


def bench_history_overhead(secs: float) -> dict:
    """Cost of the pandatrend metrics-history recorder vs a real launch.

    The recorder never rides the launch path: it is one background thread
    calling ``sample_once()`` every ``history_interval_s``. Its steady-
    state tax on a running broker is therefore a duty cycle — per-sample
    cost over the sampling interval — and that is what the gate judges:
    during a launch of any length the recorder is expected to steal
    ``sample_ns / interval_ns`` of it. The per-sample cost is dominated
    by ``_cumulative()`` (one full registry scan + ``_hist_window`` per
    histogram), which is paid whether or not any series moved, so a quiet
    registry prices the scan honestly; the registry is first warmed by a
    real columnar launch so the scan walks the series a live broker has.

    Also pins the ISSUE 17 off posture: ``interval_s=0`` must run NO
    recorder thread (the violation-only ``history_recorder_off_threads``
    key, same contract as ``pulse_profiler_off_threads``)."""
    import json as _json
    import threading as _threading

    from redpanda_tpu.coproc import TpuEngine, ProcessBatchRequest
    from redpanda_tpu.coproc.engine import ProcessBatchItem
    from redpanda_tpu.models import NTP, Record, RecordBatch
    from redpanda_tpu.observability.history import (
        DEFAULT_INTERVAL_S, HistoryRecorder,
    )
    from redpanda_tpu.ops.exprs import field
    from redpanda_tpu.ops.transforms import Int, Str, map_project, where

    engine = TpuEngine(
        row_stride=256, compress_threshold=10**9,
        force_mode="columnar_host", host_workers=0,
    )
    spec = where(field("level") == "error") | map_project(
        Int("code"), Str("msg", 16)
    )
    engine.enable_coprocessors([(1, spec.to_json(), ("orders",))])
    recs = [
        Record(
            offset_delta=i, timestamp_delta=i,
            value=_json.dumps(
                {"level": ["error", "info"][i % 2], "code": i, "msg": f"m{i}"},
                separators=(",", ":"),
            ).encode(),
        )
        for i in range(512)
    ]
    batch = RecordBatch.build(recs, base_offset=0, first_timestamp=1000)
    req = ProcessBatchRequest(
        [ProcessBatchItem(1, NTP.kafka("orders", 0), [batch])]
    )

    def op():
        engine.process_batch(req)

    def timed_block(fn, k: int) -> float:
        t0 = time.perf_counter()
        for _ in range(k):
            fn()
        return time.perf_counter() - t0

    op()  # warmup (and: populates the live registry the recorder scans)
    per_op = min(timed_block(op, 2) / 2 for _ in range(3))
    k = max(2, int(0.01 / per_op))
    rounds = max(12, int(secs / (k * per_op)))
    best_op = min(timed_block(op, k) / k for _ in range(rounds))
    engine.shutdown()

    # per-sample cost on a PRIVATE recorder against the PROCESS registry
    # (reads only — sample_once never mutates the registry; a private ring
    # keeps bench windows out of any live /v1/history)
    rec = HistoryRecorder()
    rec.configure(windows=64)
    rec.sample_once()  # anchors the delta baseline; first call is free
    sample_ns = float("inf")
    n_raw = 200
    for _ in range(8):
        t0 = time.perf_counter()
        for _ in range(n_raw):
            rec.sample_once()
        sample_ns = min(sample_ns, (time.perf_counter() - t0) / n_raw * 1e9)
    series = len(rec.windows()[-1]["gauges"]) if rec.windows() else 0

    launch_ns = best_op * 1e9
    interval_ns = DEFAULT_INTERVAL_S * 1e9
    pct = sample_ns / interval_ns * 100.0
    # interval=0 posture: configure() with 0 must leave NO recorder thread
    rec.configure(interval_s=0.0)
    off_threads = sum(
        1 for t in _threading.enumerate()
        if t.name == "rptpu-history-recorder"
    )
    out = {
        "history_sample_ns": round(sample_ns, 1),
        "history_sample_cost_us": round(sample_ns / 1e3, 2),
        "history_gauge_series_scanned": series,
        "history_launch_cost_us": round(best_op * 1e6, 1),
        "history_sample_vs_launch_pct": round(
            sample_ns / launch_ns * 100.0, 3
        ) if launch_ns else 0.0,
        "history_overhead_pct": round(pct, 4),
    }
    if off_threads:
        # violation-only key, same contract as pulse_profiler_off_threads
        out["history_recorder_off_threads"] = off_threads
    return out


def bench_trace_propagation_overhead(secs: float) -> dict:
    """Cost of pandascope trace propagation on an rpc round trip.

    What a SAMPLED request pays beyond the pre-propagation wire: encoding
    the 17-byte TraceContext on the sender, decoding it on the receiver,
    and the receiver's JOINed rpc.handle span. Same derived min-of-blocks
    discipline as tracer_overhead: each piece is strictly additive
    straight-line code, so (per-call cost sum) / (per-RTT cost of a real
    loopback rpc) IS its share — wall-clock A/B on a shared box cannot
    resolve sub-1%.

    The denominator round trip carries a REPLICATE-REPRESENTATIVE payload
    (128 KiB, a quarter of the default 512 KiB recovery chunk): the only
    rpcs that are ever sampled are the coalesced-produce append_entries
    sends that join the submitter's trace — data-carrying by construction
    — while empty heartbeats and chatter never carry context and pay
    zero. Pricing the ctx against an empty echo would gate a cost against
    a request shape that never bears it; the one-process loopback echo
    already UNDERSTATES a real inter-broker round trip besides (no
    process switch, no NIC — the SLO harness measures real cross-process
    rpc means in the milliseconds). The acceptance bar (<1%) is asserted
    by --assert-propagation-overhead, which also FAILS if a disabled
    tracer adds even one byte to the wire
    (``propagation_disabled_extra_bytes`` must be 0 — the header is
    feature-flagged on trace_enabled)."""
    from redpanda_tpu.observability.trace import Tracer
    from redpanda_tpu.rpc import wire

    # real loopback RTT (tracer state untouched: whatever the process has)
    rtt_s = _rpc_echo_rtt_s(min(secs, 2.0), payload_bytes=128 * 1024)

    ctx = wire.TraceContext(0x1234_5678_9ABC, 0x42, True)
    blob = ctx.encode()
    encode_ns = float("inf")
    decode_ns = float("inf")
    join_ns = float("inf")
    scratch = Tracer(enabled=True, capacity=64)
    for _ in range(10):
        n = 2000
        t0 = time.perf_counter()
        for _ in range(n):
            ctx.encode()
        encode_ns = min(encode_ns, (time.perf_counter() - t0) / n * 1e9)
        t0 = time.perf_counter()
        for _ in range(n):
            wire.TraceContext.decode(blob)
        decode_ns = min(decode_ns, (time.perf_counter() - t0) / n * 1e9)
        t0 = time.perf_counter()
        for _ in range(n):
            with scratch.span("bench.join", trace_id=7):
                pass
        join_ns = min(join_ns, (time.perf_counter() - t0) / n * 1e9)
    per_rpc_ns = encode_ns + decode_ns + join_ns
    rtt_ns = rtt_s * 1e9
    pct = per_rpc_ns / rtt_ns * 100.0 if rtt_ns else 0.0
    # zero-wire-bytes invariant: no ctx -> byte-identical version-0 frame
    payload = b"x" * 128
    extra = len(wire.frame(payload, 1, 1)) - (wire.HEADER_SIZE + len(payload))
    return {
        "propagation_ctx_encode_ns": round(encode_ns, 1),
        "propagation_ctx_decode_ns": round(decode_ns, 1),
        "propagation_join_span_ns": round(join_ns, 1),
        "propagation_rpc_rtt_us": round(rtt_s * 1e6, 2),
        "propagation_overhead_pct": round(pct, 3),
        "propagation_disabled_extra_bytes": extra,
        "propagation_ctx_wire_bytes": wire.TRACE_CTX_SIZE,
    }


def _rpc_echo_rtt_s(secs: float, payload_bytes: int = 0) -> float:
    """Per-round-trip seconds of a real loopback rpc echo; the request
    carries ``payload_bytes`` of text (0 = the minimal chatter shape)."""
    from redpanda_tpu import rpc
    from redpanda_tpu.rpc.transport import Transport

    async def run() -> float:
        from redpanda_tpu.rpc import serde

        msg = serde.S(("text", serde.STRING))
        svc = rpc.ServiceDef(
            "bench", "echo_prop", [rpc.MethodDef("echo", msg, msg)]
        )

        class Impl:
            async def echo(self, req):
                return {"text": req["text"]}

        server = rpc.Server()
        proto = rpc.SimpleProtocol()
        proto.register_service(rpc.ServiceHandler(svc, Impl()))
        server.set_protocol(proto)
        await server.start()
        t = Transport("127.0.0.1", server.port)
        await t.connect()
        client = rpc.Client(svc, t)
        body = "r" * max(1, payload_bytes)
        await client.echo({"text": body})
        n = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < secs:
            await client.echo({"text": body})
            n += 1
        dt = time.perf_counter() - t0
        await t.close()
        await server.stop()
        return dt / max(1, n)

    return asyncio.run(run())


def bench_rpc_echo(secs: float) -> dict:
    """Loopback RPC round trips (rpc_bench shape) over the real stack."""
    from redpanda_tpu import rpc
    from redpanda_tpu.rpc.transport import Transport

    async def run() -> float:
        from redpanda_tpu.rpc import serde

        msg = serde.S(("text", serde.STRING))
        svc = rpc.ServiceDef("bench", "echo", [rpc.MethodDef("echo", msg, msg)])

        class Impl:
            async def echo(self, req):
                return {"text": req["text"]}

        server = rpc.Server()
        proto = rpc.SimpleProtocol()
        proto.register_service(rpc.ServiceHandler(svc, Impl()))
        server.set_protocol(proto)
        await server.start()
        t = Transport("127.0.0.1", server.port)
        await t.connect()
        client = rpc.Client(svc, t)
        await client.echo({"text": "warm"})
        n = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < secs:
            await client.echo({"text": "ping"})
            n += 1
        dt = time.perf_counter() - t0
        await t.close()
        await server.stop()
        return n / dt

    return {"rpc_echo_rtt_per_s": round(asyncio.run(run()), 1)}


BENCHES = {
    "crc32c": bench_crc32c,
    "xxhash": bench_xxhash,
    "zstd_stream": bench_zstd_stream,
    "batch_codec": bench_batch_codec,
    "explode_find": bench_explode_find,
    "mesh_scaling": bench_mesh_scaling,
    "harvest_path": bench_harvest_path,
    "compaction_index": bench_compaction_index,
    "allocation": bench_allocation,
    "rpc_echo": bench_rpc_echo,
    "tracer_overhead": bench_tracer_overhead,
    "trace_propagation_overhead": bench_trace_propagation_overhead,
    "breaker_overhead": bench_breaker_overhead,
    "slo_eval_overhead": bench_slo_eval_overhead,
    "governor_overhead": bench_governor_overhead,
    "admission_overhead": bench_admission_overhead,
    "pulse_overhead": bench_pulse_overhead,
    "history_overhead": bench_history_overhead,
}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument(
        "benches", nargs="*", metavar="BENCH",
        help="bench names to run (default: all; same set as --only)",
    )
    p.add_argument("--secs", type=float, default=0.5, help="time budget per bench")
    p.add_argument("--only", help="comma-separated bench names")
    p.add_argument(
        "--metrics-snapshot",
        help="write {before, after} registry snapshots to this JSON file, so "
        "a bench run can be diffed against the probe counters it moved",
    )
    p.add_argument(
        "--assert-tracer-overhead",
        type=float,
        metavar="PCT",
        help="fail (exit 1) if the disabled-tracer overhead exceeds PCT "
        "percent; implies the tracer_overhead bench",
    )
    p.add_argument(
        "--assert-propagation-overhead",
        type=float,
        metavar="PCT",
        help="fail (exit 1) if the trace-context encode/decode + join-span "
        "share of an rpc round trip exceeds PCT percent, OR if a disabled "
        "tracer adds ANY bytes to the wire; implies the "
        "trace_propagation_overhead bench",
    )
    p.add_argument(
        "--assert-breaker-overhead",
        type=float,
        metavar="PCT",
        help="fail (exit 1) if the closed-breaker + disabled-honey-badger "
        "share of the launch path exceeds PCT percent; implies the "
        "breaker_overhead bench",
    )
    p.add_argument(
        "--assert-slo-overhead",
        type=float,
        metavar="PCT",
        help="fail (exit 1) if the always-on SLO/exemplar hook's share of "
        "a produce-shaped op exceeds PCT percent; implies the "
        "slo_eval_overhead bench",
    )
    p.add_argument(
        "--assert-governor-overhead",
        type=float,
        metavar="PCT",
        help="fail (exit 1) if the governor's closed-path decision hooks' "
        "share of a columnar launch exceeds PCT percent; implies the "
        "governor_overhead bench",
    )
    p.add_argument(
        "--assert-admission-overhead",
        type=float,
        metavar="PCT",
        help="fail (exit 1) if the uncontended budget-admission pair "
        "(try_admit + release) exceeds PCT percent of a real acked "
        "produce op; implies the admission_overhead bench",
    )
    p.add_argument(
        "--assert-pulse-overhead",
        type=float,
        metavar="PCT",
        help="fail (exit 1) if the pandapulse flight recorder's derived "
        "share of a real columnar launch exceeds PCT (e.g. 1 = 1%%), or "
        "if a profiler thread exists with profile_hz=0; implies the "
        "pulse_overhead bench",
    )
    p.add_argument(
        "--assert-history-overhead",
        type=float,
        metavar="PCT",
        help="fail (exit 1) if the pandatrend history recorder's steady-"
        "state duty cycle (per-sample cost over history_interval_s) "
        "exceeds PCT (e.g. 1 = 1%%), or if a recorder thread exists with "
        "history_interval_s=0; implies the history_overhead bench",
    )
    p.add_argument(
        "--assert-harvest-speedup",
        type=float,
        metavar="RATIO",
        help="fail (exit 1) if the gather harvest path's stage-time "
        "speedup over the padded path falls below RATIO (e.g. 1.33 = a "
        "25%% cut); implies the harvest_path bench",
    )
    p.add_argument(
        "--assert-mesh-speedup",
        type=float,
        metavar="RATIO",
        help="fail (exit 1) if the sharded CRC+vote step's best "
        "multi-device speedup over the 1-device mesh falls below RATIO "
        "(e.g. 1.2) on a >=2-device host-platform mesh; implies the "
        "mesh_scaling bench",
    )
    p.add_argument(
        "--assert-explode-speedup",
        type=float,
        metavar="RATIO",
        help="fail (exit 1) if the structural fused ladder's speedup over "
        "the staged rp_explode_find ladder on the stringified-JSON shape "
        "(the structural-index target shape) falls below RATIO (e.g. 2.0);"
        " implies the explode_find bench",
    )
    args = p.parse_args(argv)
    from redpanda_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()  # before any bench's first jit
    names = list(args.benches)
    if args.only:
        names.extend(n.strip() for n in args.only.split(","))
    if not names:
        names = list(BENCHES)
    unknown = [n for n in names if n not in BENCHES]
    if unknown:
        p.error(f"unknown bench(es) {unknown}; choose from {sorted(BENCHES)}")
    if args.assert_tracer_overhead is not None and "tracer_overhead" not in names:
        names.append("tracer_overhead")
    if (
        args.assert_propagation_overhead is not None
        and "trace_propagation_overhead" not in names
    ):
        names.append("trace_propagation_overhead")
    if args.assert_mesh_speedup is not None and "mesh_scaling" not in names:
        names.append("mesh_scaling")
    if args.assert_breaker_overhead is not None and "breaker_overhead" not in names:
        names.append("breaker_overhead")
    if args.assert_harvest_speedup is not None and "harvest_path" not in names:
        names.append("harvest_path")
    if args.assert_explode_speedup is not None and "explode_find" not in names:
        names.append("explode_find")
    if args.assert_slo_overhead is not None and "slo_eval_overhead" not in names:
        names.append("slo_eval_overhead")
    if args.assert_pulse_overhead is not None and "pulse_overhead" not in names:
        names.append("pulse_overhead")
    if args.assert_history_overhead is not None and "history_overhead" not in names:
        names.append("history_overhead")
    if args.assert_governor_overhead is not None and "governor_overhead" not in names:
        names.append("governor_overhead")
    if args.assert_admission_overhead is not None and "admission_overhead" not in names:
        names.append("admission_overhead")
    snap_before = None
    if args.metrics_snapshot:
        from redpanda_tpu.metrics import registry

        snap_before = registry.snapshot()
    out: dict[str, float] = {}
    for name in names:
        out.update(BENCHES[name](args.secs))
    if args.metrics_snapshot:
        from redpanda_tpu.metrics import registry

        with open(args.metrics_snapshot, "w") as f:
            json.dump(
                {"before": snap_before, "after": registry.snapshot()},
                f, indent=2, sort_keys=True,
            )
    print(json.dumps(out))
    if args.assert_tracer_overhead is not None:
        pct = out.get("tracer_disabled_overhead_pct", 0.0)
        if pct > args.assert_tracer_overhead:
            print(
                f"tracer overhead {pct}% exceeds budget "
                f"{args.assert_tracer_overhead}%",
                file=sys.stderr,
            )
            return 1
    if args.assert_propagation_overhead is not None:
        pct = out.get("propagation_overhead_pct", 0.0)
        extra = out.get("propagation_disabled_extra_bytes", 0)
        if pct > args.assert_propagation_overhead:
            print(
                f"trace propagation overhead {pct}% exceeds budget "
                f"{args.assert_propagation_overhead}%",
                file=sys.stderr,
            )
            return 1
        if extra != 0:
            print(
                f"disabled tracer added {extra} byte(s) to the wire "
                f"(must be ZERO — header is feature-flagged on "
                f"trace_enabled)",
                file=sys.stderr,
            )
            return 1
    if args.assert_mesh_speedup is not None:
        ratio = out.get("mesh_speedup_best", 0.0)
        if ratio < args.assert_mesh_speedup:
            print(
                f"mesh CRC+vote speedup {ratio}x below floor "
                f"{args.assert_mesh_speedup}x "
                f"({out.get('mesh_available_devices', 0)} devices)",
                file=sys.stderr,
            )
            return 1
    if args.assert_breaker_overhead is not None:
        pct = out.get("breaker_overhead_pct", 0.0)
        if pct > args.assert_breaker_overhead:
            print(
                f"breaker overhead {pct}% exceeds budget "
                f"{args.assert_breaker_overhead}%",
                file=sys.stderr,
            )
            return 1
    if args.assert_slo_overhead is not None:
        pct = out.get("slo_eval_overhead_pct", 0.0)
        if pct > args.assert_slo_overhead:
            print(
                f"slo hook overhead {pct}% exceeds budget "
                f"{args.assert_slo_overhead}%",
                file=sys.stderr,
            )
            return 1
    if args.assert_governor_overhead is not None:
        pct = out.get("governor_overhead_pct", 0.0)
        if pct > args.assert_governor_overhead:
            print(
                f"governor hook overhead {pct}% exceeds budget "
                f"{args.assert_governor_overhead}%",
                file=sys.stderr,
            )
            return 1
    if args.assert_pulse_overhead is not None:
        pct = out.get("pulse_overhead_pct", 0.0)
        if pct > args.assert_pulse_overhead:
            print(
                f"pulse recorder overhead {pct}% exceeds budget "
                f"{args.assert_pulse_overhead}%",
                file=sys.stderr,
            )
            return 1
        if out.get("pulse_profiler_off_threads", 0) != 0:
            print(
                "pulse profiler thread running with profile_hz=0 "
                "(disabled profiler must add ZERO hot-path work)",
                file=sys.stderr,
            )
            return 1
    if args.assert_history_overhead is not None:
        pct = out.get("history_overhead_pct", 0.0)
        if pct > args.assert_history_overhead:
            print(
                f"history recorder duty cycle {pct}% exceeds budget "
                f"{args.assert_history_overhead}%",
                file=sys.stderr,
            )
            return 1
        if out.get("history_recorder_off_threads", 0) != 0:
            print(
                "history recorder thread running with history_interval_s=0 "
                "(0 = off must mean NO thread)",
                file=sys.stderr,
            )
            return 1
    if args.assert_admission_overhead is not None:
        pct = out.get("admission_overhead_pct", 0.0)
        if pct > args.assert_admission_overhead:
            print(
                f"admission pair overhead {pct}% exceeds budget "
                f"{args.assert_admission_overhead}%",
                file=sys.stderr,
            )
            return 1
    if args.assert_harvest_speedup is not None:
        ratio = out.get("harvest_speedup", 0.0)
        if ratio < args.assert_harvest_speedup:
            print(
                f"harvest gather speedup {ratio}x below floor "
                f"{args.assert_harvest_speedup}x",
                file=sys.stderr,
            )
            return 1
    if args.assert_explode_speedup is not None:
        ratio = out.get("explode_find_speedup", 0.0)
        if ratio < args.assert_explode_speedup:
            print(
                f"structural explode+find+extract speedup {ratio}x below "
                f"floor {args.assert_explode_speedup}x",
                file=sys.stderr,
            )
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
