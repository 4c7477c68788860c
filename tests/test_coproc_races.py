"""Regression tests for the concurrency findings pandaraces surfaced.

Each test pins the FIXED behavior of a true positive the RAC11xx lockset
checker found in-tree (ISSUE 9): the duplicate columnar-backend probe
(check-then-act on the class attribute from concurrent tick-executor
threads — the PR-3 duplicate-jit-trace shape) and Counter.inc lost
updates (an unlocked read-modify-write shared by the harvester daemon,
fetch workers and host-pool shards).
"""

from __future__ import annotations

import json
import sys
import threading
import time

import pytest

from redpanda_tpu.coproc import EnableResponseCode, ProcessBatchRequest, TpuEngine
from redpanda_tpu.coproc.engine import ProcessBatchItem
from redpanda_tpu.models import NTP, Record, RecordBatch
from redpanda_tpu.ops.exprs import field
from redpanda_tpu.ops.transforms import Int, Str, map_project, where


def _columnar_request(n_records: int) -> ProcessBatchRequest:
    recs = [
        Record(
            offset_delta=i,
            timestamp_delta=i,
            value=json.dumps(
                {"level": ["error", "info"][i % 2], "code": i, "msg": f"m{i}"},
                separators=(",", ":"),
            ).encode(),
        )
        for i in range(n_records)
    ]
    batch = RecordBatch.build(recs, base_offset=0, first_timestamp=1000)
    return ProcessBatchRequest(
        [ProcessBatchItem(1, NTP.kafka("orders", 0), [batch])]
    )


def test_columnar_probe_runs_once_under_concurrent_first_launches(monkeypatch):
    """Two concurrent first columnar launches race the process-wide
    backend probe: the double-checked _columnar_probe_lock must admit
    exactly ONE probe — the loser waits and adopts the winner's pick
    instead of re-paying the device leg and tearing the two-field write."""
    TpuEngine.reset_columnar_probe()
    calls: list[int] = []

    def slow_probe(self, plan, cols):
        calls.append(1)
        time.sleep(0.05)  # wide window: an unlocked loser would re-enter
        TpuEngine._columnar_backend = "host"
        TpuEngine._columnar_probe = {"chosen": "host", "fake": True}

    monkeypatch.setattr(TpuEngine, "_probe_columnar_backend", slow_probe)
    spec = where(field("level") == "error") | map_project(
        Int("code"), Str("msg", 8)
    )
    engine = TpuEngine(row_stride=128, host_workers=0)
    try:
        codes = engine.enable_coprocessors([(1, spec.to_json(), ("orders",))])
        assert codes == [EnableResponseCode.success]
        req = _columnar_request(600)  # n_pad = 1024 >= _PROBE_MIN_ROWS
        errors: list[BaseException] = []

        def run():
            try:
                engine.process_batch(req)
            except BaseException as e:  # noqa: BLE001 - surfaced below
                errors.append(e)

        threads = [threading.Thread(target=run) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not errors, errors
        assert len(calls) == 1, "both launches ran the probe (lost race)"
        assert TpuEngine.sticky_columnar_backend() == "host"
    finally:
        engine.shutdown()
        TpuEngine.reset_columnar_probe()


def test_counter_inc_is_thread_exact():
    """Counter.inc is a read-modify-write shared across the engine's
    thread zoo; concurrent incs must not lose updates."""
    from redpanda_tpu.metrics import Counter

    c = Counter("race_test_total", "exactness under contention")
    per_thread, n_threads = 10_000, 8
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)  # force aggressive interleaving
    try:
        threads = [
            threading.Thread(
                target=lambda: [c.inc() for _ in range(per_thread)]
            )
            for _ in range(n_threads)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        sys.setswitchinterval(old)
    assert c.value == per_thread * n_threads
