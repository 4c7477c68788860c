"""pandapulse: the always-on flight recorder + continuous wall profiler.

Every perf PR since BENCH_r06 has been steered by coarse ``t_*`` stage
sums and one-off microbenches — nobody could *see* a launch's lifecycle
(queue wait vs H2D vs device vs harvest vs seal, across pool shards and
mesh devices) on a time axis. This module turns the instrumentation the
repo already has into timelines, at near-zero marginal cost:

* **Flight recorder** (``FlightRecorder``) — a bounded ring of committed
  span dicts fed straight off the tracer's commit path
  (``Tracer.set_sink``). NO new clocks anywhere: the engine's stage
  timers (``TpuEngine._stat_stage``, ``_Launch._stat``), the pacemaker's
  tick spans and the harvester's queue/device extras are the only time
  sources; the recorder just retains and *assembles* them into per-launch
  lifecycle groups, with queue-wait gaps made explicit from the
  ``queue_us`` extras the harvester already records.
* **Wall profiler** (``WallProfiler``) — a low-frequency sampling thread
  (``sys._current_frames``, config ``profile_hz``, default off; ~19 Hz is
  the recommended on-value: prime, aliases with nothing periodic). Samples
  fold into per-thread flamegraph stacks tagged with the executor-affinity
  names pandalint's concurrency analysis already knows (loop / executor /
  pool_worker / daemon). Profiler off = NO sampler thread and zero code on
  any hot path — the engine never calls into this module.
* **Chrome trace export** (``Pulse.timeline``) — Perfetto-loadable
  trace-event JSON: launch slices as complete ("X") events on per-thread
  tracks, governor journal verdicts and admission-shed episodes injected
  as instant ("i") events on the same clock, so a breaker trip or an
  autotune move is visible in the timeline right next to the launches it
  affected. ``GET /v1/profile/timeline`` serves it; the federated variant
  (observability/federation.py ``assemble_cluster_timeline``) merges every
  node's events into one cluster timeline like ``/v1/trace/cluster``.

Clock contract: span ``start_us`` is perf-counter-relative to the
tracer's epoch (``tracer.epoch_perf``), whose wall anchor is
``tracer.epoch_wall``; journal entries carry wall ``ts``, so instant
events land on the span clock via ``(ts - epoch_wall) * 1e6``. Cross-node
assembly re-anchors on each node's epoch exactly like cluster traces.

Cost discipline: the recorder rides spans that are already being paid for
(``trace_enabled`` gates the whole plane — the pandascope rollout-flag
posture); with the sink installed the extra cost per committed span is one
bounded-deque append, and with pulse disabled it is one attribute check
inside ``Tracer._commit``. ``tools/microbench.py pulse_overhead`` prices
the recorder against a real columnar launch (``--assert-pulse-overhead``).
"""

from __future__ import annotations

import collections
import copy
import itertools
import os
import sys
import threading
import time

from redpanda_tpu.observability.trace import self_times, tracer

# Span names that mark a trace as a LAUNCH lifecycle group (a coproc tick
# or a bare-engine submit both qualify; produce/fetch traces with no
# coproc leg are not launches and stay out of the launch timeline).
_LAUNCH_MARKERS = ("coproc.tick", "coproc.dispatch", "coproc.harvest")

# thread-name prefix -> pandalint executor-affinity context name
# (tools/pandalint/affinity.py seeds: loop / executor / pool_worker /
# daemon / device_mesh / finalizer). The profiler and the timeline tag
# every thread track with these so a flamegraph reads in the same
# vocabulary the static race analysis uses.
_AFFINITY_PREFIXES = (
    ("MainThread", "loop"),
    ("rptpu-coproc-tick", "executor"),
    ("rptpu-host-stage", "pool_worker"),
    ("rptpu-mask-harvester", "daemon"),
    ("rptpu-fault-fetch", "daemon"),
    ("rptpu-pulse-profiler", "daemon"),
    ("asyncio_", "executor"),
    ("ThreadPoolExecutor", "executor"),
)


def thread_affinity(thread_name: str) -> str:
    """Executor-affinity context for a thread name (pandalint vocabulary);
    unknown threads read as plain ``thread``."""
    for prefix, ctx in _AFFINITY_PREFIXES:
        if thread_name.startswith(prefix):
            return ctx
    return "thread"


# ================================================================ recorder
class FlightRecorder:
    """Bounded ring of committed spans + launch-lifecycle assembly.

    ``record`` is the tracer sink: it must stay allocation-light and can
    never raise (deque.append on a bounded deque is atomic under the GIL,
    so no lock on the write path; readers take a snapshot copy)."""

    def __init__(self, capacity: int = 8192) -> None:
        self._ring: collections.deque = collections.deque(
            maxlen=max(16, int(capacity))
        )
        # GIL-atomic C-level counter: += on an int is a read-modify-write
        # racing across commit threads (the lost-update class PR 9 fixed
        # in metrics.Counter), and a lock here would double the per-span
        # sink cost the pulse_overhead gate prices. itertools.count is
        # consumed to count and copy-peeked to read.
        self._ids = itertools.count(1)

    # ------------------------------------------------------------ feed
    def record(self, span: dict) -> None:
        # the span dict is the tracer's own committed object; the recorder
        # treats it as immutable and shares it (no copy per span)
        self._ring.append(span)
        next(self._ids)

    # ------------------------------------------------------------ config
    @property
    def capacity(self) -> int:
        return self._ring.maxlen or 0

    def configure(self, capacity: int) -> None:
        capacity = max(16, int(capacity))
        if capacity != self._ring.maxlen:
            self._ring = collections.deque(self._ring, maxlen=capacity)

    def reset(self) -> None:
        self._ring.clear()
        self._ids = itertools.count(1)

    @property
    def spans_recorded(self) -> int:
        # non-consuming read: a copy of the counter yields the next value
        return next(copy.copy(self._ids)) - 1

    def spans(self) -> list[dict]:
        return list(self._ring)

    # ------------------------------------------------------------ assembly
    def launches(self, limit: int = 0) -> list[dict]:
        """Newest-first launch lifecycle groups assembled from the ring.

        A group is every surviving span of one trace that contains at
        least one launch marker (a coproc tick / dispatch / harvest leg),
        with derived ``*.queue_wait`` slices made explicit from the
        ``queue_us`` extras the harvester records — the gap between a mask
        being enqueued and the harvester picking it up becomes a visible
        slice instead of dead air."""
        spans = self.spans()
        by_trace: dict[int, list[dict]] = {}
        order: list[int] = []
        launchy: set[int] = set()
        for s in spans:
            tid = s["trace_id"]
            if tid not in by_trace:
                by_trace[tid] = []
                order.append(tid)
            by_trace[tid].append(s)
            if s["name"].startswith(_LAUNCH_MARKERS):
                launchy.add(tid)
        out: list[dict] = []
        for tid in reversed(order):
            if tid not in launchy:
                continue
            group = sorted(by_trace[tid], key=lambda s: s["start_us"])
            slices = []
            for s in group:
                slices.append(s)
                q_us = s.get("queue_us")
                if q_us:
                    # derived, not measured twice: the harvester computed
                    # queue_us off timestamps it already took
                    slices.append({
                        "trace_id": tid,
                        "name": s["name"] + ".queue_wait",
                        "start_us": s["start_us"] - int(q_us),
                        "dur_us": int(q_us),
                        "thread": s.get("thread", "?"),
                        "node": s.get("node"),
                        "derived": True,
                    })
            first = min(s["start_us"] for s in group)
            last = max(s["start_us"] + s["dur_us"] for s in group)
            out.append({
                "trace_id": tid,
                "start_us": first,
                "wall_us": last - first,
                "slices": slices,
            })
            if limit and len(out) >= limit:
                break
        return out

    def stage_totals(self) -> dict[str, float]:
        """Per-span-name summed seconds over every span in the ring — the
        recorder-side twin of the engine's ``stats()`` ``t_*`` splits
        (``coproc.stage.explode_find2`` sums against ``t_explode_find2``;
        the parity test pins them within integer-microsecond truncation
        per slice)."""
        totals: dict[str, float] = {}
        for s in self.spans():
            if s.get("derived"):
                continue
            totals[s["name"]] = totals.get(s["name"], 0.0) + s["dur_us"] / 1e6
        return totals

    def self_totals(self) -> dict[str, float]:
        """Per-span-name summed SELF seconds: each span less the part its
        children (spans whose ``parent`` it is) cover. ``coproc.tick``'s
        self time is what a tick spends in none of its phases."""
        spans = [s for s in self.spans() if not s.get("derived")]
        own = self_times(spans)
        totals: dict[str, float] = {}
        for s in spans:
            totals[s["name"]] = totals.get(s["name"], 0.0) + own[s["span_id"]] / 1e6
        return totals

    def summary(self) -> dict:
        spans = self.spans()
        return {
            "capacity": self.capacity,
            "spans": len(spans),
            "spans_recorded": self.spans_recorded,
            "launches": len(self.launches()),
        }


# ================================================================ profiler
def fold_frame(frame, max_depth: int) -> list[str]:
    """A thread's innermost ``max_depth`` frames as ``file.py:function``,
    root first (the folded-stack convention). The wall profiler's samples
    and the loop watchdog's stall stacks (loopwatch.py) are both this."""
    stack: list[str] = []
    while frame is not None and len(stack) < max_depth:
        co = frame.f_code
        stack.append(f"{os.path.basename(co.co_filename)}:{co.co_name}")
        frame = frame.f_back
    stack.reverse()
    return stack


class WallProfiler:
    """Low-frequency wall-clock sampling profiler over every live thread.

    ``sys._current_frames()`` is a point-in-time snapshot of each thread's
    Python frame; at ~19 Hz the sampler costs microseconds per tick and
    nothing at all on the sampled threads (no tracing hooks, no
    sys.setprofile — the threads never know). Stacks fold into
    ``(thread_name, frame-tuple) -> count``, the flamegraph form."""

    MAX_DEPTH = 64
    MAX_STACKS = 4096  # distinct (thread, stack) keys retained

    def __init__(self) -> None:
        self.hz = 0.0
        self._thread: threading.Thread | None = None
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self._stacks: dict[tuple, int] = {}
        self._samples = 0
        self._dropped = 0
        self._started_ts: float | None = None

    # ------------------------------------------------------------ control
    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def configure(self, hz: float | None) -> None:
        """``hz > 0`` starts (or retunes) the sampler; ``hz <= 0`` stops
        it. Idempotent either way."""
        if hz is None:
            return
        hz = float(hz)
        if hz <= 0:
            self.stop()
            return
        self.hz = hz
        if not self.running:
            self._stop.clear()
            self._started_ts = time.time()
            self._thread = threading.Thread(
                target=self._loop, name="rptpu-pulse-profiler", daemon=True
            )
            self._thread.start()

    def stop(self) -> None:
        t, self._thread = self._thread, None
        self.hz = 0.0
        if t is not None and t.is_alive():
            self._stop.set()
            t.join(timeout=2.0)

    def reset(self) -> None:
        with self._lock:
            self._stacks.clear()
            self._samples = 0
            self._dropped = 0

    # ------------------------------------------------------------ sampling
    def _loop(self) -> None:
        while True:
            hz = self.hz
            if hz <= 0 or self._stop.wait(1.0 / hz):
                return
            try:
                self._sample()
            except Exception:  # noqa: BLE001 - the sampler must never die
                self._dropped += 1

    def _sample(self) -> None:
        names = {t.ident: t.name for t in threading.enumerate()}
        frames = sys._current_frames()
        me = threading.get_ident()
        folded: list[tuple[tuple, int]] = []
        for ident, frame in frames.items():
            if ident == me:
                continue  # the sampler observing itself is pure noise
            stack = fold_frame(frame, self.MAX_DEPTH)
            folded.append(((names.get(ident, f"tid-{ident}"), tuple(stack)), 1))
        with self._lock:
            self._samples += 1
            for key, n in folded:
                if key not in self._stacks and len(self._stacks) >= self.MAX_STACKS:
                    self._dropped += 1
                    continue
                self._stacks[key] = self._stacks.get(key, 0) + n

    # ------------------------------------------------------------ queries
    @property
    def samples(self) -> int:
        return self._samples

    def stacks(self, limit: int = 0) -> list[dict]:
        """Folded stacks, heaviest-first: [{thread, affinity, stack,
        count}]."""
        with self._lock:
            items = sorted(
                self._stacks.items(), key=lambda kv: kv[1], reverse=True
            )
        out = [
            {
                "thread": thread,
                "affinity": thread_affinity(thread),
                "stack": list(stack),
                "count": count,
            }
            for (thread, stack), count in items
        ]
        return out[:limit] if limit else out

    def top(self, limit: int = 20) -> list[dict]:
        """Leaf-frame self-time attribution per thread: where the samples
        actually landed — the ``rpk debug profile --top`` table."""
        agg: dict[tuple[str, str], int] = {}
        with self._lock:
            for (thread, stack), count in self._stacks.items():
                leaf = stack[-1] if stack else "<no python frame>"
                k = (thread, leaf)
                agg[k] = agg.get(k, 0) + count
        rows = [
            {
                "thread": thread,
                "affinity": thread_affinity(thread),
                "frame": leaf,
                "samples": count,
            }
            for (thread, leaf), count in agg.items()
        ]
        rows.sort(key=lambda r: r["samples"], reverse=True)
        return rows[:limit] if limit else rows

    def folded(self) -> list[str]:
        """flamegraph.pl folded-stack lines: ``thread;root;...;leaf N``."""
        return [
            ";".join([s["thread"], *s["stack"]]) + f" {s['count']}"
            for s in self.stacks()
        ]

    def summary(self) -> dict:
        with self._lock:
            n_stacks = len(self._stacks)
        return {
            "running": self.running,
            "hz": self.hz,
            "samples": self._samples,
            "distinct_stacks": n_stacks,
            "dropped": self._dropped,
            "started_ts": self._started_ts,
        }


# ================================================================ pulse
class Pulse:
    """The process-wide pandapulse facade: flight recorder + wall
    profiler + Chrome trace export. One instance (``pulse`` below),
    configured from broker config at app start."""

    def __init__(self) -> None:
        self.recorder = FlightRecorder()
        self.profiler = WallProfiler()
        self._installed = False

    # ------------------------------------------------------------ config
    @property
    def enabled(self) -> bool:
        return self._installed

    def configure(
        self,
        *,
        enabled: bool | None = None,
        ring_capacity: int | None = None,
        profile_hz: float | None = None,
    ) -> None:
        if ring_capacity is not None:
            self.recorder.configure(ring_capacity)
        if enabled is not None:
            if enabled and not self._installed:
                tracer.set_sink(self.recorder.record)
                self._installed = True
            elif not enabled and self._installed:
                tracer.set_sink(None)
                self._installed = False
        self.profiler.configure(profile_hz)

    def reset(self) -> None:
        self.recorder.reset()
        self.profiler.reset()

    # ------------------------------------------------------------ surfaces
    def snapshot(self, top: int = 20) -> dict:
        """The ``GET /v1/profile`` body."""
        return {
            "enabled": self._installed,
            "tracing": tracer.enabled,
            "recorder": self.recorder.summary(),
            "profiler": self.profiler.summary(),
            "stage_totals_s": {
                k: round(v, 6)
                for k, v in sorted(self.recorder.stage_totals().items())
            },
            "self_totals_s": {
                k: round(v, 6)
                for k, v in sorted(self.recorder.self_totals().items())
            },
            "top": self.profiler.top(top),
        }

    def timeline(
        self,
        launches: int = 0,
        journal_entries: list[dict] | None = None,
        journal_margin_s: float = 2.0,
    ) -> dict:
        """Chrome trace-event JSON (Perfetto-loadable) for the newest
        ``launches`` launch groups (0 = every launch in the ring), with
        governor verdicts and admission-shed episodes as instant events on
        the same clock. ``journal_entries=None`` pulls the live process
        decision journal."""
        groups = self.recorder.launches(limit=launches)
        if journal_entries is None:
            # lazy: observability must stay importable without coproc
            from redpanda_tpu.coproc.governor import journal

            journal_entries = journal.entries()
        node = tracer.node_id
        pid_default = node if node is not None else 0
        events: list[dict] = []
        tids: dict[tuple[int, str], int] = {}
        pids_seen: set[int] = set()

        def tid_of(pid: int, thread: str) -> int:
            key = (pid, thread)
            t = tids.get(key)
            if t is None:
                t = tids[key] = len(tids) + 1
                events.append({
                    "ph": "M", "name": "thread_name", "pid": pid, "tid": t,
                    "args": {
                        "name": f"{thread} [{thread_affinity(thread)}]"
                    },
                })
            if pid not in pids_seen:
                pids_seen.add(pid)
                events.append({
                    "ph": "M", "name": "process_name", "pid": pid,
                    "tid": 0, "args": {"name": f"broker node {pid}"},
                })
            return t

        t_min = None
        t_max = None
        for g in groups:
            for s in g["slices"]:
                pid = s.get("node")
                pid = pid_default if pid is None else pid
                ev = {
                    "name": s["name"],
                    "ph": "X",
                    "ts": s["start_us"],
                    "dur": max(int(s["dur_us"]), 1),
                    "pid": pid,
                    "tid": tid_of(pid, s.get("thread", "?")),
                    "cat": "derived" if s.get("derived") else "span",
                    "args": {
                        "trace_id": s["trace_id"],
                        # span_id stays: the cluster-timeline assembler
                        # dedupes by it (in-process stacks share one
                        # recorder, so every node's fetch returns the
                        # same spans)
                        **{
                            k: v for k, v in s.items()
                            if k not in (
                                "trace_id", "name", "start_us", "dur_us",
                                "thread", "node", "derived",
                            )
                        },
                    },
                }
                events.append(ev)
                t_min = ev["ts"] if t_min is None else min(t_min, ev["ts"])
                end = ev["ts"] + ev["dur"]
                t_max = end if t_max is None else max(t_max, end)
        # journal entries ride the same clock: wall ts re-anchored on the
        # tracer's (epoch_wall, epoch_perf) pair. With launches in view,
        # only entries inside the window (+/- margin) inject — a 256-deep
        # journal must not bury a 10-launch timeline; with none, the
        # newest entries still render so `rpk debug profile --perfetto` on
        # an idle broker shows the decision history.
        margin_us = journal_margin_s * 1e6
        injected = 0
        for e in journal_entries:
            ts_us = (e["ts"] - tracer.epoch_wall) * 1e6
            if t_min is not None and not (
                t_min - margin_us <= ts_us <= t_max + margin_us
            ):
                continue
            pid = pid_default
            ev = {
                "name": f"{e['domain']}:{e['verdict']}",
                "ph": "i",
                "s": "p",  # process-scoped instant: a governor decision
                "ts": max(ts_us, 0.0),
                "pid": pid,
                "tid": tid_of(pid, "governor"),
                "cat": "governor",
                "args": {
                    "seq": e.get("seq"),
                    "reason": e.get("reason"),
                    "inputs": e.get("inputs"),
                },
            }
            events.append(ev)
            injected += 1
        # pandatrend counter tracks (ROADMAP 7c): the metrics-history
        # ring's derived series as ph:"C" events on the SAME span clock —
        # occupancy, pressure, shed rate, launch knobs, colcache, inflight
        # gate render as counter lanes under the launch slices. Window
        # filtering matches the journal instants: with launches in view
        # only in-window samples (± margin) emit; an idle broker's
        # timeline still shows its whole retained trend.
        from redpanda_tpu.observability.history import history

        counter_events = history.counter_tracks(
            pid=pid_default,
            t_min_us=t_min,
            t_max_us=t_max,
            margin_us=margin_us,
        )
        events.extend(counter_events)
        return {
            "displayTimeUnit": "ms",
            "traceEvents": events,
            "node": node,
            "epoch": tracer.epoch_wall,
            "launches": len(groups),
            "journal_events": injected,
            "counter_events": len(counter_events),
        }


# Process-wide instance, like tracer/registry/slo: subsystems import this;
# app startup configures it from broker config.
pulse = Pulse()

__all__ = [
    "FlightRecorder",
    "Pulse",
    "WallProfiler",
    "pulse",
    "thread_affinity",
]
