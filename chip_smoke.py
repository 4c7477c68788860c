#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served coproc path still
starts, and is right, on the accelerator.

    python3 chip_smoke.py --seed 0

The parent process never initializes a JAX backend: it runs its stages as
child processes one after another, so exactly one process holds the chip at
any time.

- build: ``make -B -C native`` — the .so is git-ignored, so it is rebuilt
  from the committed source here; a failed build fails the smoke.
- stage A, the served path: a broker child (``python -m redpanda_tpu start
  --set coproc_enable=true``) while this JAX-free parent, through
  ``redpanda_tpu.kafka.client``, creates one 64-partition topic, deploys
  BASELINE config 4's columnar script and a raw-byte payload script through
  ``coprocessor_internal_topic``, produces a seeded backlog of 65,536 ~1 KB
  JSON records, fetches both materialized topics to the end and compares
  them record for record with the plain reference
  (``redpanda_tpu/coproc/reference.py``). Then ``/v1/coproc/status`` and
  ``/metrics`` must show the work ran on the device with nothing hidden.
- stage B, the engine at the bench's launch width: a child drives
  ``TpuEngine.submit_group`` at 64 partitions x 32 records x 16 ticks =
  32,768 rows per launch with the columnar lane forced to the device and
  with the payload lane, then runs each remaining device program once
  against its host oracle.
- stage C, the mesh lane, only where JAX reports >= 4 devices.

A stage fails on a wrong record, a host fallback, a retry, a classified
failure, an open breaker, or a platform that is not an accelerator. The
last line of stdout is one JSON object, and only when every stage passed:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without an accelerator the exit code is 1 and no such line is printed.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

TOPIC = "smoke"
COLUMNAR_SCRIPT = "project_error"
PAYLOAD_SCRIPT = "contains_warn"
NEEDLE = b'"level":"warn"'
PARTITIONS = 64
RECORDS_PER_PARTITION = 1024
RECORDS_PER_BATCH = 32
# stage B's launch geometry: ticks fused a launch, launches in flight, row stride
TICKS_PER_LAUNCH = 16
LAUNCH_DEPTH = 3
WARM_LAUNCHES = 8
ENGINE_ROW_STRIDE = 1152
# the broker's engine stages payload rows at TpuEngine's default stride
BROKER_ROW_STRIDE = 1024
STAGE_MARK = "STAGE_RESULT "


class SmokeFailure(Exception):
    """A stage could not run to its checks (the checks themselves collect
    their failures into the stage's ``failures`` list instead)."""


# ------------------------------------------------------------------ data
def specs() -> dict[str, str]:
    """script name -> TransformSpec JSON (imports jax; initializes nothing)."""
    from redpanda_tpu.ops.exprs import field
    from redpanda_tpu.ops.transforms import (
        Int, Str, filter_contains, map_project, where,
    )

    return {
        COLUMNAR_SCRIPT: (
            where(field("level") == "error")
            | map_project(Int("code"), Str("msg", 64))
        ).to_json(),
        PAYLOAD_SCRIPT: filter_contains(NEEDLE).to_json(),
    }


def reference_fns(row_stride: int) -> dict:
    """script name -> the plain per-record reference of its semantics."""
    from redpanda_tpu.coproc import reference

    return {
        COLUMNAR_SCRIPT: reference.project_error,
        PAYLOAD_SCRIPT: lambda v: reference.filter_contains(v, NEEDLE, row_stride),
    }


def reference_outputs(values, fn) -> list[list[bytes]]:
    """Per-partition expected output values, in order."""
    return [[o for o in map(fn, part) if o is not None] for part in values]


def compare(expected: list[list[bytes]], got: list[list[bytes]]) -> dict:
    """Record-for-record comparison of per-partition output values."""
    n_exp = sum(map(len, expected))
    n_got = sum(map(len, got))
    first = None
    for p, (e, g) in enumerate(zip(expected, got)):
        if e != g:
            i = next(
                (k for k, (a, b) in enumerate(zip(e, g)) if a != b),
                min(len(e), len(g)),
            )
            first = {"partition": p, "index": i, "expected": len(e), "got": len(g)}
            break
    return {
        "records_expected": n_exp,
        "records_materialised": n_got,
        "reference_match": first is None and len(expected) == len(got),
        "first_mismatch": first,
    }


# --------------------------------------------------------------- checks
def engine_health_failures(stats: dict) -> list[str]:
    """The zero-fallback assertions, from ``TpuEngine.stats()``."""
    fails = []
    for key in ("n_fallback_rows", "n_retries"):
        if stats.get(key, 0):
            fails.append(f"{key} = {stats[key]} (must be 0)")
    for name, b in (stats.get("breakers") or {}).items():
        if b.get("state") != "closed" or b.get("trips"):
            fails.append(f"breaker {name}: {b.get('state')} trips={b.get('trips')}")
    for key in ("n_device_launches", "bytes_h2d", "bytes_d2h"):
        if not stats.get(key, 0) > 0:
            fails.append(f"{key} = {stats.get(key, 0)} (must be > 0)")
    return fails


def platform_failures(device: dict | None) -> list[str]:
    if not device:
        return ["the engine never resolved its device"]
    if device.get("platform") == "cpu":
        return [
            f"JAX platform is {device['platform']!r} "
            f"(device_kind {device.get('device_kind')!r}): no accelerator"
        ]
    return []


def _stage_timing(stats: dict) -> dict:
    """compile = the first-call legs of every device program (trace +
    compile + first run); warm = the dispatch legs after them."""
    t_dispatch = stats.get("t_dispatch", 0.0) + stats.get("t_shard_dispatch", 0.0)
    n_warm = stats.get("n_device_launches", 0) - stats.get("n_compiles", 0)
    warm = max(t_dispatch - stats.get("t_compile", 0.0), 0.0)
    return {
        "n_compiles": int(stats.get("n_compiles", 0)),
        "t_compile_s": round(stats.get("t_compile", 0.0), 3),
        "compiled_programs": stats.get("compiled_programs"),
        "n_warm_dispatch_legs": int(n_warm),
        "t_warm_dispatch_leg_s": round(warm / n_warm, 6) if n_warm > 0 else None,
    }


# -------------------------------------------------------------- stage A
def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _admin_get(port: int, path: str, timeout: float = 10.0) -> bytes:
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=timeout
    ) as r:
        return r.read()


def _metric_total(metrics_text: str, name: str) -> float:
    total = 0.0
    for line in metrics_text.splitlines():
        if line.startswith("#"):
            continue
        series, _, value = line.rpartition(" ")
        if series.split("{", 1)[0].endswith(name):
            total += float(value)
    return total


def _stop(proc: subprocess.Popen) -> None:
    """SIGTERM the child's whole process group, SIGKILL what is left."""
    for sig, wait_s in ((signal.SIGTERM, 20.0), (signal.SIGKILL, 10.0)):
        if proc.poll() is not None:
            break
        try:
            os.killpg(proc.pid, sig)
        except ProcessLookupError:
            break
        try:
            proc.wait(timeout=wait_s)
        except subprocess.TimeoutExpired:
            continue
    if proc.stdout is not None:
        proc.stdout.close()


async def _drive_served_path(kafka_port, values, expected, deadline):
    """Create the topic, deploy both scripts, produce the backlog, fetch
    both materialized topics to the end. Returns (got, timings)."""
    from redpanda_tpu.coproc import wasm_event
    from redpanda_tpu.kafka.client import KafkaClient
    from redpanda_tpu.models.fundamental import COPROC_INTERNAL_TOPIC
    from redpanda_tpu.models.record import Record, RecordBatch

    partitions = len(values)
    client = await KafkaClient([("127.0.0.1", kafka_port)]).connect()
    try:
        await client.create_topic(TOPIC, partitions=partitions, replication=1)
        deploys = wasm_event.deploy_batch([
            wasm_event.make_deploy_record(name, spec_json, [TOPIC])
            for name, spec_json in specs().items()
        ])
        t_end = time.monotonic() + 60.0
        while True:  # the internal topic appears once the listener made it
            try:
                await client.produce_batches(COPROC_INTERNAL_TOPIC, 0, [deploys])
                break
            except Exception:
                if time.monotonic() > t_end:
                    raise
                await asyncio.sleep(0.5)

        async def produce_partition(p: int) -> None:
            part = values[p]
            for s in range(0, len(part), RECORDS_PER_BATCH):
                batch = RecordBatch.build([
                    Record(offset_delta=i, timestamp_delta=i, value=v)
                    for i, v in enumerate(part[s : s + RECORDS_PER_BATCH])
                ], first_timestamp=1_000_000)
                await client.produce_batches(TOPIC, p, [batch], acks=-1)

        t0 = time.perf_counter()
        await asyncio.gather(*(produce_partition(p) for p in range(partitions)))
        t_produce = time.perf_counter() - t0

        async def fetch_partition(mtopic: str, p: int, want: int) -> list[bytes]:
            out: list[bytes] = []
            offset = 0
            idle_after_end = 0
            while time.monotonic() < deadline:
                try:
                    batches, _hwm = await client.fetch(
                        mtopic, p, offset, max_bytes=4 << 20
                    )
                except Exception:
                    # the materialized topic is created by its first write
                    await asyncio.sleep(0.5)
                    await client.refresh_metadata([mtopic])
                    batches = []
                for b in batches:
                    out.extend(r.value for r in b.records())
                    offset = b.last_offset + 1
                if len(out) >= want:
                    # at the expected end: one more empty poll shows
                    # nothing was materialized twice
                    if not batches:
                        idle_after_end += 1
                        if idle_after_end >= 2:
                            break
                elif not batches:
                    await asyncio.sleep(0.2)
            return out

        got = {}
        for name in expected:
            mtopic = f"{TOPIC}.${name}$"
            got[name] = list(await asyncio.gather(*(
                fetch_partition(mtopic, p, len(expected[name][p]))
                for p in range(partitions)
            )))
        t_drain = time.perf_counter() - t0
        return got, {
            "t_produce_s": round(t_produce, 3),
            "t_produce_to_materialised_s": round(t_drain, 3),
        }
    finally:
        await client.close()


async def _drive_unmet_bucket(kafka_port, values, already: int, deadline):
    """One batch of ``values`` to partition 0, and what the payload script
    materialized of it (its output follows the ``already`` records the
    backlog left in that partition)."""
    from redpanda_tpu.kafka.client import KafkaClient
    from redpanda_tpu.models.record import Record, RecordBatch

    client = await KafkaClient([("127.0.0.1", kafka_port)]).connect()
    try:
        batch = RecordBatch.build([
            Record(offset_delta=i, timestamp_delta=i, value=v)
            for i, v in enumerate(values)
        ], first_timestamp=2_000_000)
        await client.produce_batches(TOPIC, 0, [batch], acks=-1)
        mtopic, out, offset, idle = f"{TOPIC}.${PAYLOAD_SCRIPT}$", [], 0, 0
        while time.monotonic() < deadline and idle < 3:
            batches, _hwm = await client.fetch(mtopic, 0, offset, max_bytes=4 << 20)
            for b in batches:
                out.extend(r.value for r in b.records())
                offset = b.last_offset + 1
            if len(out) > already and not batches:
                idle += 1
            elif not batches:
                await asyncio.sleep(0.2)
        return out[already:]
    finally:
        await client.close()


def precompile_check(ports: dict, seed: int, already: int, deadline: float) -> dict:
    """Stage A's last step (PR 45): the payload script's ladder of device
    programs was built at the deploy, off the serving path. Wait for
    ``coproc_programs_ready`` to read the whole ladder, then launch at a
    row bucket NO launch has met: no ``t_compile`` sample may appear, and
    the records come back as the reference has them."""
    from redpanda_tpu.coproc import reference, wasm_event

    out: dict = {"failures": []}
    fails = out["failures"]
    sid = str(wasm_event.WasmEvent(PAYLOAD_SCRIPT, wasm_event.DEPLOY).script_id)
    while True:
        status = json.loads(_admin_get(ports["admin"], "/v1/coproc/status"))
        ladder = (status["stats"].get("programs_ready") or {}).get(sid) or {}
        if ladder.get("state") not in (None, "building") or time.monotonic() > deadline:
            break
        time.sleep(0.2)
    metrics = _admin_get(ports["admin"], "/metrics").decode()
    gauge = _metric_total(metrics, "coproc_programs_ready")
    out.update(ladder=ladder, coproc_programs_ready=gauge)
    if ladder.get("state") != "ready" or gauge < len(ladder.get("buckets") or [None]):
        fails.append(f"the payload script's ladder is not ready: {ladder}, gauge {gauge}")
        return out
    stats = status["stats"]
    met = {c["n_pad"] for c in stats["compiled_programs"]
           if c["lane"] == "payload" and "t_precompile_s" not in c}
    unmet = [b for b in ladder["buckets"] if b not in met and b <= 1024]
    if not unmet:
        fails.append(f"no small bucket is left that no launch has met: {sorted(met)}")
        return out
    bucket = unmet[0]
    values = reference.make_documents(seed + 1, 1, bucket // 2 + 1)[0]
    want = [o for o in map(reference_fns(BROKER_ROW_STRIDE)[PAYLOAD_SCRIPT], values)
            if o is not None]
    got = asyncio.run(_drive_unmet_bucket(ports["kafka"], values, already, deadline))
    after = json.loads(_admin_get(ports["admin"], "/v1/coproc/status"))["stats"]
    metrics_after = _admin_get(ports["admin"], "/metrics").decode()
    compile_samples = [
        sum(float(line.rsplit(" ", 1)[1]) for line in text.splitlines()
            if "coproc_stage_latency_us_count" in line and 'stage="compile"' in line)
        for text in (metrics, metrics_after)
    ]
    now_met = {c["n_pad"] for c in after["compiled_programs"]
               if c["lane"] == "payload" and "t_precompile_s" not in c}
    out.update(
        bucket=bucket, rows=len(values), records_expected=len(want),
        records_materialised=len(got), reference_match=got == want,
        n_compiles=[stats.get("n_compiles", 0), after.get("n_compiles", 0)],
        compile_samples=compile_samples, n_precompiles=after.get("n_precompiles", 0),
        n_launch_cuts=after.get("n_launch_cuts", 0),
    )
    if got != want:
        fails.append(f"bucket {bucket}: the materialized records differ from the reference")
    if bucket not in now_met:
        fails.append(f"no launch met bucket {bucket}: launches met {sorted(now_met)}")
    if after.get("n_compiles", 0) != stats.get("n_compiles", 0) or (
        compile_samples[0] != compile_samples[1]
    ):
        fails.append(f"a first run on the serving path: n_compiles {out['n_compiles']}, "
                     f"t_compile samples {compile_samples}")
    return out


def stage_a(
    seed: int,
    partitions: int = PARTITIONS,
    records_per_partition: int = RECORDS_PER_PARTITION,
    timeout_s: float = 600.0,
    require_accelerator: bool = True,
) -> dict:
    """The served path through the normal entry point. Imports jax (through
    the deploy-record helpers) but never initializes a backend. With
    ``require_accelerator`` a broker on JAX's CPU backend fails the stage
    before any data moves; without it (the tier-1 test) the stage runs on
    and only records the platform failure."""
    from redpanda_tpu.coproc import reference, wasm_event

    result: dict = {
        "stage": "A",
        "entry": "python -m redpanda_tpu start --set coproc_enable=true",
        "partitions": partitions,
        "records_in": partitions * records_per_partition,
        "reduced": ["one broker", "replication 1"],
        "failures": [],
    }
    fails = result["failures"]
    data_dir = tempfile.mkdtemp(prefix="chip_smoke_")
    ports = {k: _free_port() for k in ("kafka", "rpc", "admin")}
    cmd = [sys.executable, "-m", "redpanda_tpu", "start"]
    for k, v in {
        "coproc_enable": "true",
        "node_id": 0,
        "data_directory": data_dir,
        "kafka_api_port": ports["kafka"],
        "advertised_kafka_api_port": ports["kafka"],
        "rpc_server_port": ports["rpc"],
        "admin_api_port": ports["admin"],
    }.items():
        cmd += ["--set", f"{k}={v}"]
    log_path = os.path.join(data_dir, "broker.log")
    with open(log_path, "wb") as log:
        broker = subprocess.Popen(
            cmd, cwd=REPO, stdout=log, stderr=subprocess.STDOUT,
            start_new_session=True,
            env={**os.environ, "PYTHONPATH": REPO},
        )
    try:
        t_start = time.monotonic()
        while True:
            if broker.poll() is not None:
                raise SmokeFailure(
                    f"broker exited {broker.returncode} during start-up:\n"
                    + _tail(log_path)
                )
            # ready = the engine exists and has named its device (the
            # admin API answers before the accelerator backend is up)
            try:
                status = json.loads(
                    _admin_get(ports["admin"], "/v1/coproc/status", 2.0)
                )
                if status.get("device"):
                    break
            except (OSError, ValueError):
                pass
            if time.monotonic() - t_start > 180.0:
                raise SmokeFailure("broker not ready after 180 s:\n" + _tail(log_path))
            time.sleep(0.3)
        result["t_broker_ready_s"] = round(time.monotonic() - t_start, 1)
        device = status.get("device")
        result.update(
            platform=(device or {}).get("platform"),
            device_kind=(device or {}).get("device_kind"),
            device_count=(device or {}).get("count"),
        )
        fails += platform_failures(device)
        if fails and require_accelerator:
            return result  # no accelerator: not worth a 64 MiB backlog

        values = reference.make_documents(seed, partitions, records_per_partition)
        result["backlog_bytes"] = sum(len(v) for part in values for v in part)
        expected = {
            name: reference_outputs(values, fn)
            for name, fn in reference_fns(BROKER_ROW_STRIDE).items()
        }
        got, timings = asyncio.run(_drive_served_path(
            ports["kafka"], values, expected, time.monotonic() + timeout_s,
        ))
        result.update(timings)
        for name in expected:
            result[name] = compare(expected[name], got[name])
            if not result[name]["reference_match"]:
                fails.append(f"{name}: materialized topic differs from the reference")

        status = json.loads(_admin_get(ports["admin"], "/v1/coproc/status"))
        metrics = _admin_get(ports["admin"], "/metrics").decode()
        stats = status["stats"]
        by_script = stats.get("device_launches_by_script") or {}
        launches = {
            name: by_script.get(
                str(wasm_event.WasmEvent(name, wasm_event.DEPLOY).script_id), 0
            )
            for name in expected
        }
        probe = stats.get("columnar_probe") or {}
        result.update(
            device_launches=launches,
            n_launches=stats.get("n_launches", 0),
            n_device_launches=stats.get("n_device_launches", 0),
            n_fallback_rows=stats.get("n_fallback_rows", 0),
            n_retries=stats.get("n_retries", 0),
            coproc_failures_total=_metric_total(metrics, "coproc_failures_total"),
            bytes_h2d=stats.get("bytes_h2d", 0),
            bytes_d2h=stats.get("bytes_d2h", 0),
            breakers={
                k: v.get("state") for k, v in (stats.get("breakers") or {}).items()
            },
            # which backend the probe CHOSE is printed, not judged: stage B
            # forces the columnar lane onto the device
            columnar_backend=stats.get("columnar_backend"),
            columnar_probe=probe,
            native=status.get("native"),
            **_stage_timing(stats),
        )
        fails += engine_health_failures(stats)
        if not launches[PAYLOAD_SCRIPT] > 0:
            fails.append("the payload script never launched on the device")
        if result["coproc_failures_total"]:
            fails.append(f"coproc_failures_total = {result['coproc_failures_total']}")
        if probe.get("t_device_s") is None:
            fails.append(
                "columnar probe has no device timing: "
                f"{probe.get('device_error') or 'the probe never ran'}"
            )
        native = status.get("native") or {}
        if not native.get("loaded") or native.get("build_error") or not all(
            (native.get("symbols") or {"": False}).values()
        ):
            fails.append(f"native library incomplete in the broker: {native}")
        # programs built before serving: a launch at a bucket no launch has
        # met is no first run
        result["precompile"] = pre = precompile_check(
            ports, seed, len(got[PAYLOAD_SCRIPT][0]), time.monotonic() + 120.0
        )
        fails += [f"precompile: {f}" for f in pre.pop("failures")]
        return result
    except Exception:
        sys.stderr.write("---- broker log tail ----\n" + _tail(log_path) + "\n")
        raise
    finally:
        _stop(broker)
        shutil.rmtree(data_dir, ignore_errors=True)


def _tail(path: str, n: int = 4000) -> str:
    try:
        with open(path, "rb") as f:
            return f.read()[-n:].decode(errors="replace")
    except OSError as exc:
        return f"(no log: {exc})"


# -------------------------------------------------------------- stage B
def _requests(values, script_id: int):
    """Per tick t, one ProcessBatchRequest holding batch t of every
    partition (what the pacemaker submits per tick)."""
    from redpanda_tpu.coproc.engine import ProcessBatchItem, ProcessBatchRequest
    from redpanda_tpu.models import NTP
    from redpanda_tpu.models.record import Record, RecordBatch

    n_ticks = len(values[0]) // RECORDS_PER_BATCH
    reqs = []
    for t in range(n_ticks):
        s = t * RECORDS_PER_BATCH
        reqs.append(ProcessBatchRequest([
            ProcessBatchItem(script_id, NTP.kafka(TOPIC, p), [RecordBatch.build([
                Record(offset_delta=i, timestamp_delta=i, value=v)
                for i, v in enumerate(part[s : s + RECORDS_PER_BATCH])
            ], base_offset=s, first_timestamp=1_000_000)])
            for p, part in enumerate(values)
        ]))
    return reqs


def _reply_values(reply, partitions: int) -> list[list[bytes]]:
    out = [[] for _ in range(partitions)]
    for item in reply.items:
        for b in item.batches:
            out[item.source.partition].extend(r.value for r in b.records())
    return out


def mixed_width(values: list[list[bytes]]) -> list[list[bytes]]:
    """The backlog with fifteen documents in sixteen cut to their first
    three fields (~50-120 B; the sixteenth keeps its ~1 KB): values in two
    far-apart width classes, which the payload lane stages as two parts
    (PR 47) once a launch is large enough for that to halve its bytes."""
    return [
        [v if i % 16 == 0 else v[: v.index(b',"pad":"')] + b"}" for i, v in enumerate(part)]
        for part in values
    ]


def run_lane(name, spec_json, values, ref_fn, ticks_per_launch, **engine_kw) -> dict:
    """One engine, one script: a first launch on its own (compile + run),
    then WARM_LAUNCHES pipelined LAUNCH_DEPTH deep, cycling through the
    backlog's launch windows; every reply compared with the reference.
    Touches JAX."""
    from redpanda_tpu.coproc import TpuEngine

    partitions = len(values)
    per_window = ticks_per_launch * RECORDS_PER_BATCH
    reqs = _requests(values, 1)
    windows = [
        reqs[s : s + ticks_per_launch]
        for s in range(0, len(reqs) - ticks_per_launch + 1, ticks_per_launch)
    ]
    expected = [
        reference_outputs(
            [part[w * per_window : (w + 1) * per_window] for part in values], ref_fn
        )
        for w in range(len(windows))
    ]
    totals = {"records_expected": 0, "records_materialised": 0}
    mismatches = []

    def harvest(w: int, tickets) -> None:
        got = [[] for _ in range(partitions)]
        for t in tickets:
            for p, vals in enumerate(_reply_values(t.result(), partitions)):
                got[p].extend(vals)
        cmp = compare(expected[w], got)
        for k in totals:
            totals[k] += cmp[k]
        if not cmp["reference_match"]:
            mismatches.append({"window": w, **cmp["first_mismatch"]})

    engine = TpuEngine(row_stride=ENGINE_ROW_STRIDE, **engine_kw)
    try:
        codes = engine.enable_coprocessors([(1, spec_json, (TOPIC,))])
        if codes != [0]:
            raise SmokeFailure(f"{name}: enable_coprocessors returned {codes}")
        t0 = time.perf_counter()
        harvest(0, engine.submit_group(windows[0]))
        t_first = time.perf_counter() - t0
        pending = []
        t0 = time.perf_counter()
        for k in range(WARM_LAUNCHES):
            w = (k + 1) % len(windows)
            pending.append((w, engine.submit_group(windows[w])))
            while len(pending) > LAUNCH_DEPTH:
                harvest(*pending.pop(0))
        while pending:
            harvest(*pending.pop(0))
        t_warm = time.perf_counter() - t0
        stats = engine.stats()
    finally:
        engine.shutdown()
    out = {
        "lane": name,
        "rows_per_launch": per_window * partitions,
        "launches": 1 + WARM_LAUNCHES,
        "records_in": int(stats.get("n_records", 0)),
        **totals,
        "reference_match": not mismatches,
        "mismatches": mismatches[:3],
        "n_launches": int(stats.get("n_launches", 0)),
        "n_device_launches": int(stats.get("n_device_launches", 0)),
        "n_mesh_launches": int(stats.get("n_mesh_launches", 0)),
        "n_split_launches": int(stats.get("n_split_launches", 0)),
        "n_fallback_rows": stats.get("n_fallback_rows", 0),
        "n_retries": stats.get("n_retries", 0),
        "bytes_h2d": int(stats.get("bytes_h2d", 0)),
        "bytes_d2h": int(stats.get("bytes_d2h", 0)),
        "t_first_launch_s": round(t_first, 3),
        "t_warm_launch_s": round(t_warm / WARM_LAUNCHES, 4),
        **_stage_timing(stats),
        "mesh": stats.get("mesh"),
        "failures": engine_health_failures(stats),
    }
    if mismatches:
        out["failures"].append(f"{name}: replies differ from the reference")
    return out


def device_programs(seed: int) -> list[dict]:
    """Each remaining device program once, at a launch-sized shape,
    against its host oracle. Touches JAX."""
    import jax

    from redpanda_tpu.hashing.crc32c import crc32c
    from redpanda_tpu.models.record import Record, RecordBatch
    from redpanda_tpu.ops.crc_backend import CrcBackend
    from redpanda_tpu.ops.lz4_device import measure_probe
    from redpanda_tpu.parallel import (
        make_sharded_coproc_step, partition_mesh, shard_to_mesh,
    )

    out = []
    rng = np.random.default_rng(seed)

    def timed(fn):
        t0 = time.perf_counter()
        r = fn()
        return r, round(time.perf_counter() - t0, 4)

    # config 1: produce-path batch CRC validation, 1,024 batches of 1 KB
    regions = [
        RecordBatch.build(
            [Record(offset_delta=0, value=rng.bytes(1024))], base_offset=b
        ).crc_region()
        for b in range(1024)
    ]
    claimed = np.array([crc32c(r) for r in regions], dtype=np.uint32)
    claimed[::7] ^= 1  # the oracle must reject what was corrupted
    dev = CrcBackend("device")
    ok, t_first = timed(lambda: dev.validate(regions, claimed))
    _, t_warm = timed(lambda: dev.validate(regions, claimed))
    want = np.arange(len(regions)) % 7 != 0
    out.append({
        "program": "make_batch_validator", "shape": [1024, 2048],
        "t_first_call_s": t_first, "t_warm_call_s": t_warm,
        "oracle_match": bool(np.array_equal(ok, want)),
    })

    # the LZ4 block decoder (asserts bit-exactness against liblz4 itself)
    probe, t_total = timed(
        lambda: measure_probe(n_records=32, record_size=256, reps=1)
    )
    out.append({
        "program": "lz4_block_decoder", "shape": [32, 256],
        "t_first_and_warm_s": t_total, "oracle_match": True, **probe,
    })

    # the full sharded per-tick step over every device JAX reports
    d = len(jax.devices())
    mesh = partition_mesh()
    b, n, groups, r_batch = 512 // d, 2048 // d, 64, 2048
    from redpanda_tpu.coproc import reference
    from redpanda_tpu.ops.packing import pack_rows
    from redpanda_tpu.ops.transforms import Int, Str, filter_field_eq, map_project

    spec = filter_field_eq("level", "error") | map_project(Int("code"), Str("msg", 64))
    rows, lens = pack_rows(regions[: d * b], r_batch)
    recs = [v for part in reference.make_documents(seed, d, n) for v in part]
    rec_rows, rec_lens = pack_rows(recs, ENGINE_ROW_STRIDE)
    votes = rng.integers(0, 2, (d, groups)).astype(np.uint8)
    args = shard_to_mesh(
        mesh,
        rows.reshape(d, b, r_batch), lens.reshape(d, b),
        claimed[: d * b].reshape(d, b),
        rec_rows.reshape(d, n, ENGINE_ROW_STRIDE), rec_lens.reshape(d, n), votes,
    )
    step = make_sharded_coproc_step(mesh, spec.to_json(), r_batch, ENGINE_ROW_STRIDE)
    res, t_first = timed(lambda: jax.block_until_ready(step(*args)))
    _, t_warm = timed(lambda: jax.block_until_ready(step(*args)))
    ok, rec_out, rec_out_len, keep, tally = (np.asarray(x) for x in res)
    ref = [reference.project_error(v) for v in recs]
    keep = keep.reshape(-1)
    rec_out = rec_out.reshape(len(recs), -1)
    match = (
        np.array_equal(ok.reshape(-1), want[: d * b])
        and keep.tolist() == [r is not None for r in ref]
        and all(
            rec_out[i].tobytes() == r for i, r in enumerate(ref) if r is not None
        )
        and np.array_equal(tally, votes.astype(np.int32).sum(axis=0))
    )
    out.append({
        "program": "make_sharded_coproc_step", "devices": d,
        "shape": {"batches": [d, b, r_batch], "records": [d, n, ENGINE_ROW_STRIDE]},
        "t_first_call_s": t_first, "t_warm_call_s": t_warm,
        "oracle_match": bool(match),
    })
    return out


def _engine_stage(stage: str, seed: int, partitions: int, records_per_partition: int):
    """What stages B and C share: the compile cache on, the device named
    (this initializes the backend), the seeded backlog."""
    from redpanda_tpu.coproc import reference
    from redpanda_tpu.utils.platform import device_info, enable_compile_cache

    cache_dir = enable_compile_cache()
    device = device_info()
    return {
        "stage": stage, "platform": device["platform"],
        "device_kind": device["device_kind"], "device_count": device["count"],
        "compile_cache_dir": cache_dir, "failures": [],
    }, reference.make_documents(seed, partitions, records_per_partition)


def stage_b(
    seed: int,
    partitions: int = PARTITIONS,
    records_per_partition: int = RECORDS_PER_PARTITION,
    ticks_per_launch: int = TICKS_PER_LAUNCH,
    programs: bool = True,
) -> dict:
    """The engine at the bench's launch width, both lanes forced onto the
    device, then the remaining device programs. Touches JAX."""
    result, values = _engine_stage("B", seed, partitions, records_per_partition)
    result["failures"] += platform_failures(result)
    refs = reference_fns(ENGINE_ROW_STRIDE)
    sp = specs()
    result["lanes"] = [
        run_lane(
            "columnar_device", sp[COLUMNAR_SCRIPT], values,
            refs[COLUMNAR_SCRIPT], ticks_per_launch,
            force_mode="columnar_device",
        ),
        run_lane(
            "payload", sp[PAYLOAD_SCRIPT], values,
            refs[PAYLOAD_SCRIPT], ticks_per_launch, force_mode="payload",
        ),
        # the same script over narrow and wide values mixed: each launch
        # goes as a narrow matrix and a wide one, merged back in row order
        run_lane(
            "payload_mixed_width", sp[PAYLOAD_SCRIPT], mixed_width(values),
            refs[PAYLOAD_SCRIPT], ticks_per_launch, force_mode="payload",
        ),
    ]
    for lane in result["lanes"]:
        result["failures"] += lane["failures"]
    mixed = result["lanes"][-1]
    if mixed["rows_per_launch"] >= 1024 and mixed["n_split_launches"] != mixed["n_launches"]:
        result["failures"].append(
            f"payload_mixed_width: {mixed['n_split_launches']} of "
            f"{mixed['n_launches']} launches were staged in two parts"
        )
    if programs:
        result["programs"] = device_programs(seed)
        result["failures"] += [
            f"{p['program']}: differs from its host oracle"
            for p in result["programs"] if not p["oracle_match"]
        ]
    return result


# -------------------------------------------------------------- stage C
def stage_c(
    seed: int,
    partitions: int = PARTITIONS,
    records_per_partition: int = RECORDS_PER_PARTITION,
    ticks_per_launch: int = TICKS_PER_LAUNCH,
    mesh_devices: int = 4,
    mesh_backend: str | None = None,
) -> dict:
    """The mesh lane on the same stream. Touches JAX."""
    result, values = _engine_stage("C", seed, partitions, records_per_partition)
    lane = run_lane(
        "mesh", specs()[COLUMNAR_SCRIPT], values,
        reference_fns(ENGINE_ROW_STRIDE)[COLUMNAR_SCRIPT], ticks_per_launch,
        mesh_devices=mesh_devices, mesh_backend=mesh_backend, mesh_probe=False,
    )
    result["lanes"] = [lane]
    fails = list(lane["failures"])
    rows = (lane.get("mesh") or {}).get("rows_per_device") or []
    if not lane["n_mesh_launches"] > 0:
        fails.append("n_mesh_launches = 0")
    if len(rows) != mesh_devices or not all(r > 0 for r in rows):
        fails.append(f"rows_per_device = {rows}: not every device took rows")
    result["failures"] = fails
    return result


# ----------------------------------------------------------------- main
def build_native() -> dict:
    """Rebuild native/libredpanda_native.so from the committed source."""
    t0 = time.perf_counter()
    proc = subprocess.run(
        ["make", "-B", "-C", os.path.join(REPO, "native")],
        capture_output=True, text=True, timeout=300,
    )
    if proc.returncode != 0:
        raise SmokeFailure(f"native build failed:\n{proc.stdout}\n{proc.stderr}")
    return {"stage": "build", "t_build_s": round(time.perf_counter() - t0, 1)}


def _run_child(stage: str, seed: int, timeout_s: float) -> dict:
    """Run one JAX-touching stage in its own process; what it prints is
    passed through, its result is the STAGE_RESULT line."""
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--child", stage,
         "--seed", str(seed)],
        cwd=REPO, stdout=subprocess.PIPE, text=True, start_new_session=True,
        env={**os.environ, "PYTHONPATH": REPO},
    )
    try:
        out, _ = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        raise SmokeFailure(f"stage {stage} exceeded {timeout_s:.0f} s") from None
    finally:
        _stop(proc)
    result = None
    for line in out.splitlines():
        if line.startswith(STAGE_MARK):
            result = json.loads(line[len(STAGE_MARK):])
        else:
            print(line)
    if proc.returncode != 0 or result is None:
        raise SmokeFailure(f"stage {stage} child exited {proc.returncode}")
    return result


def _holds_accelerator_runtime() -> bool:
    """True if this process mapped libtpu, i.e. initialized the TPU
    backend and would hold the chip against its own children."""
    try:
        with open("/proc/self/maps") as f:
            return "libtpu" in f.read()
    except OSError:
        return False


def _report(result: dict) -> list[str]:
    print(json.dumps(result, indent=1, sort_keys=True), flush=True)
    return [f"stage {result['stage']}: {f}" for f in result.get("failures", [])]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--child", choices=("b", "c"), help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child:
        result = (stage_b if args.child == "b" else stage_c)(args.seed)
        print(STAGE_MARK + json.dumps(result), flush=True)
        return 0

    t0 = time.perf_counter()
    failures: list[str] = []
    device = None
    try:
        print(json.dumps(build_native()), flush=True)
        a = stage_a(args.seed)
        failures += _report(a)
        if not failures:
            b = _run_child("b", args.seed, 900.0)
            failures += _report(b)
            device = {
                "platform": b["platform"], "kind": b["device_kind"],
                "count": b["device_count"],
            }
            if b["device_count"] >= 4:
                failures += _report(_run_child("c", args.seed, 600.0))
            else:
                print(json.dumps(
                    {"stage": "C", "mesh": f"skipped: {b['device_count']} device"}
                ), flush=True)
    except SmokeFailure as exc:
        failures.append(str(exc))
    print(f"chip_smoke: {time.perf_counter() - t0:.1f} s", flush=True)
    if _holds_accelerator_runtime():
        failures.append("the parent process loaded the accelerator runtime")
    if failures or device is None:
        for f in failures:
            print(f"chip_smoke FAILED: {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
