"""The broker's event loop and interpreter, timed from the inside.

One interpreter serves reads, appends, fetches and the coproc ticks, so a
coroutine that holds the loop, or a full collection, is paid by every
request in flight. Three always-on instruments, started by ``app.py``:

* a ticker task on the loop (20 wake-ups a second) records how late each
  wake-up came into ``broker_loop_lag_us``: the time a ready coroutine
  waits for the loop;
* a stall of 100 ms or more leaves ``{start, dur_us, phase, stack}`` in a
  ring of the newest 32, served as ``loop_stalls`` in ``GET /v1/profile``.
  The stack is taken by a watchdog thread (10 wake-ups a second;
  ``sys._current_frames()`` of the loop's thread, top frames, the wall
  profiler's frame folding) once, while the ticker's heartbeat is half a
  stall overdue: it names what held the loop.
  ``phase`` is the innermost frame of this package that the loop was
  running (``storage/segment.py:fsync``), None for the loop's own code;
* ``gc.callbacks``: every collection is a sample in
  ``interpreter_gc_pause_us{generation=}``, and a full one is bracketed by
  an ``rp:gc.gen2`` annotation (``stages.begin``), so a profile shows it
  among the stages it interrupted.
"""

from __future__ import annotations

import asyncio
import collections
import gc
import os
import sys
import threading
import time

from redpanda_tpu.metrics import registry
from redpanda_tpu.observability import stages
from redpanda_tpu.observability.pulse import fold_frame

TICK_S = 0.05        # 20 wake-ups a second
STALL_S = 0.1        # a wake-up this late is a stall
STALL_RING = 32
STACK_DEPTH = 12     # top frames kept of a stalled loop

_PACKAGE_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__))) + os.sep
_GC_STAGES = ("gc.gen0", "gc.gen1", "gc.gen2")

loop_lag_hist = registry.histogram(
    "broker_loop_lag_us",
    "How late the event loop ran a timer that was due (us)",
)
gc_pause_hists = tuple(
    registry.histogram(
        "interpreter_gc_pause_us",
        "Time the interpreter stopped for one collection (us)",
        generation=str(g),
    )
    for g in range(3)
)


class LoopWatch:
    """Process-wide (``loopwatch`` below). ``start`` / ``stop`` count their
    users: in-process stacks of several brokers share one loop and one
    interpreter, and get one ticker and one set of callbacks."""

    def __init__(self) -> None:
        self._users = 0
        self._task: asyncio.Task | None = None
        self._watchdog: threading.Thread | None = None
        self._stop = threading.Event()
        self._stalls: collections.deque = collections.deque(maxlen=STALL_RING)
        # shared by the ticker (loop) and the watchdog (thread), under _lock
        self._lock = threading.Lock()
        self._due = 0.0          # perf_counter() at which the ticker is due
        self._sample = None      # (due, stack, phase) the watchdog took for this stall
        self._gc_t0 = 0.0

    # ------------------------------------------------------------ lifecycle
    def start(self) -> None:
        """From a coroutine of the loop to watch."""
        self._users += 1
        if self._users > 1:
            return
        self._stop.clear()
        with self._lock:
            self._due = time.perf_counter() + TICK_S
        self._task = asyncio.get_running_loop().create_task(self._tick())
        self._watchdog = threading.Thread(
            target=self._watch, args=(threading.get_ident(),),
            name="rptpu-loopwatch", daemon=True,
        )
        self._watchdog.start()
        gc.callbacks.append(self._on_gc)

    async def stop(self) -> None:
        self._users = max(0, self._users - 1)
        if self._users or self._task is None:
            return
        gc.callbacks.remove(self._on_gc)
        self._stop.set()
        task, self._task = self._task, None
        task.cancel()
        try:
            await task
        except asyncio.CancelledError:
            pass
        thread, self._watchdog = self._watchdog, None
        thread.join(timeout=2.0)

    # ------------------------------------------------------------ the loop
    async def _tick(self) -> None:
        while True:
            due = time.perf_counter() + TICK_S
            with self._lock:
                self._due = due
            await asyncio.sleep(TICK_S)
            late = time.perf_counter() - due
            loop_lag_hist.record(max(0, int(late * 1e6)))
            if late >= STALL_S:
                self._note_stall(due, late)

    def _note_stall(self, due: float, late: float) -> None:
        with self._lock:
            sample, self._sample = self._sample, None
        if sample is None or sample[0] != due:
            sample = (None, [], None)  # over before the watchdog looked
        self._stalls.append({
            # wall clock: a stall is looked up beside logs and client tails
            "start": time.time() - late,
            "dur_us": int(late * 1e6),
            "phase": sample[2],
            "stack": sample[1],
        })

    def _watch(self, loop_thread: int) -> None:
        """Watchdog thread: once the heartbeat is half a stall overdue, take
        the loop thread's stack, once per heartbeat (a wake-up that then
        comes in under a stall's length drops it)."""
        while not self._stop.wait(STALL_S):
            with self._lock:
                due, taken = self._due, self._sample
            if time.perf_counter() - due < STALL_S / 2:
                continue
            if taken is not None and taken[0] == due:
                continue
            frame = sys._current_frames().get(loop_thread)
            if frame is not None:
                sample = (due, fold_frame(frame, STACK_DEPTH), _phase_of(frame))
                with self._lock:
                    self._sample = sample

    # ------------------------------------------------------------ collections
    def _on_gc(self, phase: str, info: dict) -> None:
        # one collection at a time, under the interpreter lock: start and
        # stop pair up, and nothing else records into these histograms
        if phase == "start":
            self._gc_t0 = (
                stages.begin(_GC_STAGES[2]) if info["generation"] == 2
                else time.perf_counter()
            )
        else:
            g = info["generation"]
            stages.close(_GC_STAGES[g], gc_pause_hists[g], self._gc_t0, trace_id=None)

    # ------------------------------------------------------------ queries
    def stalls(self) -> list[dict]:
        """Newest first."""
        return list(self._stalls)[::-1]


def _phase_of(frame) -> str | None:
    """The innermost frame of this package that the loop is running,
    ``<path in package>:<function>``; None when the loop is in none (its
    own machinery, ``select``, or waiting for the interpreter lock there)."""
    while frame is not None:
        fn = frame.f_code.co_filename
        if frame.f_code.co_name == "_run_once" and fn.endswith("base_events.py"):
            return None  # below this: whoever started the loop, not a phase
        if fn.startswith(_PACKAGE_DIR):
            return f"{fn[len(_PACKAGE_DIR):]}:{frame.f_code.co_name}"
        frame = frame.f_back
    return None


loopwatch = LoopWatch()
