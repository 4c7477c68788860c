"""Host-stage worker pool: the mesh lane's per-device host ladders.

A mesh launch (engine._dispatch_mesh) splits its batches into one
contiguous, record-count-balanced range per device; each device's parse /
extract ladder, and at harvest its assembly + framing
(_Launch._framed_sharded), is a ctypes crossing (GIL released) or a bulk
numpy pass over a **disjoint record range**, so the ranges run concurrently
on a small thread pool and their tables merge by rebasing. A single-device
launch has one host road, on its dispatching thread, and the engine builds
no pool for it.

This module owns only the generic machinery — the pool itself and the
contiguous batch partitioner. What runs per shard is the engine's business.

Sizing: ``coproc_host_workers`` (config/properties.py), default
``min(4, os.cpu_count())``; the pool only exists at >= 2 workers and with
a mesh runner. Observability: every task ticks the
``coproc_host_pool_busy_workers`` gauge (observability/probes.py) and the
engine records ``coproc_shard_rows`` per device shard.
"""

from __future__ import annotations

import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from redpanda_tpu.observability import probes


def default_host_workers() -> int:
    """The config default: one worker per core, capped at 4 (beyond that
    the merge/serial residue dominates before memory bandwidth does)."""
    return min(4, os.cpu_count() or 1)


def measure_parallel_capacity(workers: int = 2) -> dict:
    """Diagnostic: do GIL-releasing numpy tasks actually run concurrently
    here? ``os.cpu_count()`` lies on quota-limited boxes, so
    tools/microbench.py reports this next to the mesh-scaling numbers.
    NOTE this synthetic answer is context only — the mesh calibration
    times its REAL launches (burstable hosts can pass a millisecond-scale
    synthetic probe and still thrash on sustained work).
    Returns {'speedup', 'workers'}; best-of-3 on both sides."""
    workers = max(2, int(workers))

    def task() -> None:
        x = np.arange(200_000, dtype=np.float64)
        for _ in range(4):
            x = np.sqrt(x * 1.0001 + 1.0)

    ex = ThreadPoolExecutor(max_workers=workers)
    try:
        task()  # warm numpy + the allocator
        serial = parallel = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(workers):
                task()
            serial = min(serial, time.perf_counter() - t0)
            t0 = time.perf_counter()
            futs = [ex.submit(task) for _ in range(workers)]
            for f in futs:
                f.result()
            parallel = min(parallel, time.perf_counter() - t0)
    finally:
        ex.shutdown(wait=False)
    speedup = serial / parallel if parallel > 0 else 1.0
    return {"speedup": round(speedup, 3), "workers": workers}


def partition_counts(counts: list[int], n_shards: int) -> list[tuple[int, int]]:
    """Contiguous [start, end) slices over ``counts`` (per-batch record
    counts), balanced by total records per shard.

    Contiguity is the invariant everything downstream leans on: shard i's
    records form one contiguous record range, so merged offset/size/span
    tables are plain concatenations with rebased indices and the framed
    per-batch outputs concatenate back in input order byte-identically.
    Never returns empty slices; may return fewer than ``n_shards`` when
    there are fewer batches than shards.
    """
    n = len(counts)
    if n == 0 or n_shards <= 1:
        return [(0, n)] if n else []
    n_shards = min(n_shards, n)
    total = sum(counts)
    target = total / n_shards
    cuts = [0]
    acc = 0
    for i, c in enumerate(counts):
        acc += c
        # cut when this shard reached its share, leaving enough batches
        # for the remaining shards to be non-empty
        remaining_shards = n_shards - len(cuts)
        if (
            remaining_shards > 0
            and acc >= target * len(cuts)
            and (n - (i + 1)) >= remaining_shards
            and i + 1 > cuts[-1]
        ):
            cuts.append(i + 1)
            if len(cuts) == n_shards:
                break
    cuts.append(n)
    return [(cuts[i], cuts[i + 1]) for i in range(len(cuts) - 1) if cuts[i + 1] > cuts[i]]


class HostStagePool:
    """A named thread pool for the mesh lane's per-shard host stages.

    Threads, not processes: the per-shard stages spend their time inside
    ctypes calls (GIL dropped for the whole crossing), zlib/lz4
    decompression, or wide numpy kernels — real parallelism without
    pickling record payloads across a process boundary.

    The executor is created lazily (a mesh engine that never launches on
    the mesh costs nothing) and torn down by
    interpreter exit like any ThreadPoolExecutor; engines are long-lived
    process singletons in the broker (one per CoprocApi).
    """

    def __init__(self, workers: int):
        from redpanda_tpu.coproc import lockwatch

        self.workers = int(workers)
        self._executor: ThreadPoolExecutor | None = None
        self._lock = lockwatch.wrap(threading.Lock(), "HostStagePool._lock")

    def _submit_all(self, fns: list) -> list:
        # locked check-then-create: concurrent first launches must not
        # each build (and leak) an executor. The submits stay under the
        # lock too: shutdown() may land while a launch is still fanning
        # out, and an executor that is shut down refuses new work (work
        # it already holds still runs)
        with self._lock:
            if self._executor is None:
                self._executor = ThreadPoolExecutor(
                    max_workers=self.workers,
                    thread_name_prefix="rptpu-host-stage",
                )
            return [self._executor.submit(self._tracked, fn) for fn in fns]

    def run(self, fns: list) -> list:
        """Run thunks concurrently; returns results in input order.

        The first exception (in input order) propagates to the caller —
        the engine's per-script error policy handles it exactly as it
        handles an inline-stage failure. Remaining tasks still run to
        completion (they share no mutable state by construction; the
        SHD6xx pandalint rules keep it that way).
        """
        if len(fns) == 1:
            return [self._tracked(fns[0])]
        futures = self._submit_all(fns)
        results = []
        first_exc: BaseException | None = None
        for f in futures:
            try:
                results.append(f.result())
            except BaseException as e:  # noqa: BLE001  # pandalint: disable=EXC901 -- collected, not swallowed: the first failure re-raises after every task completes
                results.append(None)
                if first_exc is None:
                    first_exc = e
        if first_exc is not None:
            raise first_exc
        return results

    @staticmethod
    def _tracked(fn):
        probes.host_pool_task_started()
        try:
            return fn()
        finally:
            probes.host_pool_task_finished()

    def shutdown(self) -> None:
        with self._lock:
            if self._executor is not None:
                self._executor.shutdown(wait=False)
                self._executor = None
