"""Host-stage worker pool: per-core sharding of the engine's record stages.

A CPU-sandbox bench capture made the bottleneck explicit: with the device
predicate leg down to ~2% of stage wall time, the engine is bound by
SINGLE-THREADED host
stages — ``t_explode_find`` alone is ~57% and projection extraction another
~26%. Every one of those stages is a ctypes crossing (GIL released) or a
bulk numpy pass over **disjoint record ranges**, which is the classic
vectorized-execution sharding setup (MonetDB/X100 style) and the per-core
analogue of the reference's per-shard pacemaker fibers
(coproc/pacemaker.h:41-145): partition a launch's batches into contiguous
shards, run every per-record stage per shard on a small thread pool, and
merge index tables by rebasing.

This module owns only the generic machinery — the pool itself and the
contiguous, record-count-balanced batch partitioner. What runs per shard
(explode/find, column extraction, projection, framing) is the engine's
business (engine._dispatch_sharded / _Launch._framed_sharded).

Sizing: ``coproc_host_workers`` (config/properties.py), default
``min(4, os.cpu_count())``; ``0`` (or 1) keeps today's inline path — the
pool only exists at >= 2 workers. Observability: every task ticks the
``coproc_host_pool_busy_workers`` gauge (observability/probes.py) and the
engine records ``coproc_shard_rows`` per shard, so traceview and /metrics
show the fan-out.
"""

from __future__ import annotations

import os
import statistics
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from redpanda_tpu.observability import probes


def default_host_workers() -> int:
    """The config default: one worker per core, capped at 4 (beyond that
    the merge/serial residue dominates before memory bandwidth does)."""
    return min(4, os.cpu_count() or 1)


# The measured inline/sharded ratio must clear this margin before the
# engine pins the pool on (see LaunchTrial): a real 2-core box shards the
# explode stage ~1.8x faster; a quota-limited box advertising CPUs it
# doesn't have measures <= 1.0 with scheduler-thrash tails. Requiring a
# real win also keeps borderline boxes (whose burst capacity comes and
# goes) on the predictable inline path.
PROBE_MARGIN = 1.25

# Whole launches a trial times on each arm before it decides, and the
# launches it may spend in all (samples that met a one-shot cost, a
# fallback or a fused ticket are dropped) before it gives up and keeps
# the inline path.
TRIAL_LAUNCHES = 5
TRIAL_MAX_LAUNCHES = 40


class TrialSample:
    """One launch of a trial, from its dispatch to its harvest: which
    trial, which road, and the seconds its dispatch took."""

    __slots__ = ("trial", "arm", "dispatch_s")

    def __init__(self, trial: "LaunchTrial", arm: str):
        self.trial = trial
        self.arm = arm
        self.dispatch_s = 0.0


class LaunchTrial:
    """The pool's on/off decision, taken on what it governs.

    The decision switches a whole launch between two roads: inline, or
    per-shard stages on the pool, the merge of their tables and the sharded
    seal. An explode timed alone (the old probe) says little about that: on
    the chip's host it read speedups of 0.46-2.2 while every run that chose
    ``sharded`` was slower end to end. So the first shardable launches of a
    process run alternately inline and sharded (the two are bit-identical,
    no work is wasted), each timed whole on the engine's clock, dispatch
    plus harvest up to the sealed reply, per row; the medians are compared
    and sharded must win by PROBE_MARGIN. The periodic re-calibration is a
    new trial.

    Bookkeeping only: the engine owns the clock, the lock and the journal.
    """

    ARMS = ("inline", "sharded")

    def __init__(self):
        self.samples: dict[str, list[float]] = {arm: [] for arm in self.ARMS}
        self.issued = 0
        self.dropped = 0
        self.per_arm = TRIAL_LAUNCHES

    def next_arm(self) -> str:
        """The road the next shardable launch takes: the arm with fewer
        samples, alternating from inline while they are level (launches in
        flight have not sampled yet)."""
        self.issued += 1
        n_inline, n_sharded = (len(self.samples[arm]) for arm in self.ARMS)
        if n_inline != n_sharded:
            return self.ARMS[n_inline > n_sharded]
        return self.ARMS[(self.issued - 1) % 2]

    def add(self, arm: str, seconds: float, rows: int) -> None:
        self.samples[arm].append(seconds * 1e6 / max(rows, 1))

    def drop(self) -> None:
        self.dropped += 1

    @property
    def complete(self) -> bool:
        return all(len(v) >= self.per_arm for v in self.samples.values())

    @property
    def exhausted(self) -> bool:
        return self.issued >= TRIAL_MAX_LAUNCHES

    def verdict(self) -> dict:
        """What was measured and what it chose; an incomplete trial keeps
        the inline path and says so."""
        out = {
            "measured": "whole launches, dispatch to sealed reply, us a row",
            "launches": {arm: len(v) for arm, v in self.samples.items()},
            "dropped": self.dropped,
        }
        if not self.complete:
            return dict(out, incomplete=True, chosen="inline")
        inline, sharded = (
            statistics.median(self.samples[arm]) for arm in self.ARMS
        )
        ratio = inline / sharded if sharded > 0 else 0.0
        return dict(
            out,
            inline_us_per_row=round(inline, 4),
            sharded_us_per_row=round(sharded, 4),
            speedup=round(ratio, 3),
            chosen="sharded" if ratio >= PROBE_MARGIN else "inline",
        )


def measure_parallel_capacity(workers: int = 2) -> dict:
    """Diagnostic: do GIL-releasing numpy tasks actually run concurrently
    here? ``os.cpu_count()`` lies on quota-limited boxes, so
    tools/microbench.py reports this next to the pool-scaling numbers.
    NOTE this synthetic answer is context only — the engine calibrates on
    its REAL launches (LaunchTrial; burstable hosts can pass a
    millisecond-scale synthetic probe and still thrash on sustained work).
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
    """A named thread pool for the engine's per-shard host stages.

    Threads, not processes: the sharded stages spend their time inside
    ctypes calls (GIL dropped for the whole crossing), zlib/lz4
    decompression, or wide numpy kernels — real parallelism without
    pickling record payloads across a process boundary.

    The executor is created lazily (an engine configured with workers but
    never fed a shardable launch costs nothing) and torn down by
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
        # lock too: a trial's verdict may shut the pool down (shutdown())
        # while another launch of the trial is still fanning out, and an
        # executor that is shut down refuses new work (work it already
        # holds still runs)
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
