"""Coproc pacemaker + script contexts: the steady-state transform loop.

Parity with coproc/pacemaker.h:41-145 and script_context.cc:47-135:
one ``ScriptContext`` fiber per deployed script runs
  read_from_inputs (per-ntp, from last_acked+1 up to the LSO, bounded by
  coproc_max_batch_size and the shared inflight-bytes semaphore,
  script_context_frontend.cc:80-117)
  → engine.process_batch (the TPU engine replaces the Node.js RPC hop)
  → write_materialized (CRC-checked, recompressed batches appended
  DIRECTLY to the materialized storage log, bypassing raft —
  script_context_backend.cc:40-68)
  → advance last_acked.
While the engine's submit (explode, pack, the H2D's start) holds one tick's
input the fiber reads the next tick's (``_ReadAhead``, depth one), for the
partitions whose read the byte budget cut short of the LSO: a backlog; it
sees that read out before it sends the harvest after, so that the read
overlaps the worker's long crossings and the launch's transfer and never
the harvest's framing and seal, which drop and retake the interpreter lock
once a batch. Offsets still move only after the write.
Offsets are snapshotted per flush interval into the kvstore's coproc
keyspace and recovered on startup (offset_storage_utils.cc:36-104).
"""

from __future__ import annotations

import asyncio
import json
import logging
import os
import time
from concurrent.futures import ThreadPoolExecutor

from redpanda_tpu.coproc import faults, leakwatch
from redpanda_tpu.coproc.engine import (
    ProcessBatchItem,
    ProcessBatchRequest,
    TpuEngine,
)
from redpanda_tpu.models.fundamental import NTP, MaterializedNTP
from redpanda_tpu.observability import stages
from redpanda_tpu.observability.probes import (
    COPROC_ENGINE_PHASES,
    coproc_input_wait_hist,
    coproc_tick_hist,
    record_us,
)
from redpanda_tpu.observability.trace import tracer
from redpanda_tpu.resource_mgmt.admission import ShedError
from redpanda_tpu.resource_mgmt.budgets import MemoryAccount
from redpanda_tpu.storage.kvstore import KeySpace

logger = logging.getLogger("rptpu.coproc.pacemaker")


class _StopScript(Exception):
    """Raised inside a script's own fiber to end it (deregistration from
    within tick — the fiber cannot await its own cancellation)."""


def _release_abandoned(engine):
    """Done-callback for a submit future whose tick gave up waiting: the
    orphan ticket will never be harvested, so its admission reservation
    releases here (a failed submit released its own in submit_group)."""

    def cb(fut):
        try:
            ticket = fut.result()
        except BaseException:  # pandalint: disable=EXC901 -- not a swallow: a raising submit released its own reservation and already classified the failure inside submit_group; this callback only exists for the SUCCESS-after-abandon path
            return
        engine._release_admission(ticket)

    return cb


def _note_handoff(legs: list, wait_span, t_out: float, ticket) -> float:
    """One executor call's three legs from its four clock reads: ``t_out``
    on the loop just before ``run_in_executor``, the worker's two around
    the engine call (``Ticket.worker_clock``), the fiber's resume, read
    here and returned (what comes next in the engine phase begins on it).
    Handed to the executor -> the worker runs it, the worker's own
    time, worker done -> the fiber runs again: added to the tick's sums,
    and the loop-side wait span carries the two hand-offs for ``rpk debug
    trace``."""
    t_back = time.perf_counter()
    t_run, t_done = ticket.worker_clock
    legs[0] += t_run - t_out
    legs[1] += t_done - t_run
    legs[2] += t_back - t_done
    wait_span.set("out_us", int((t_run - t_out) * 1e6))
    wait_span.set("back_us", int((t_back - t_done) * 1e6))
    return t_back


class _ReadAhead:
    """Tick N+1's input, read inside tick N's engine phase (depth one: a
    script holds at most this and the launch in flight). Nothing here moves
    an offset; a partition's read is used by the next tick only if the
    script's offset has by then reached exactly where it began."""

    __slots__ = ("task", "reads", "yields", "error")

    def __init__(self) -> None:
        self.task: asyncio.Task | None = None
        # one entry a partition that had records, in read order:
        # (ntp, start offset, batches, the LSO read against, seconds)
        self.reads: list[tuple] = []
        # while the submit is out it hands the loop back between partitions
        # (the reply, the consumer's fetches)
        self.yields = True
        # a read that raised: the next tick's failed read, raised there
        self.error: Exception | None = None


class ScriptContext:
    def __init__(
        self,
        pacemaker: "Pacemaker",
        script_id: int,
        name: str,
        input_topics: tuple[str, ...],
    ) -> None:
        self.pacemaker = pacemaker
        self.script_id = script_id
        self.name = name
        self.input_topics = input_topics
        # per input ntp: offsets {last_read, last_acked}
        # (ntp_context.h:54-60 offset_tracker)
        self.offsets: dict[NTP, int] = {}
        self._task: asyncio.Task | None = None
        # perf_counter() at the end of the newest productive tick (the
        # ``gap`` phase runs from there to the next productive tick's read)
        self._t_tick_end: float | None = None
        # the next tick's input, while (and after) the engine holds this one's
        self._ahead: _ReadAhead | None = None
        # what the newest productive tick took, if it read a live stream
        # (every read ended at the log's end); 0.0 over a backlog. The
        # fiber lingers that long before it reads again (``_loop``)
        self._linger_s = 0.0

    def start(self) -> None:
        self._task = asyncio.create_task(self._loop())

    async def stop(self) -> None:
        # the read-ahead goes with the fiber: cancelled inside a read, its
        # read_budget reservation is handed back by _read_ntp's finally
        # (taken before the fiber's own clean-up drops the reference)
        ahead = self._ahead
        tasks = [t for t in (self._task, ahead and ahead.task) if t is not None]
        for t in tasks:
            t.cancel()
        for t in tasks:
            try:
                await t
            except asyncio.CancelledError:
                pass
        self._task = self._ahead = None

    async def _loop(self) -> None:
        """do_execute (script_context.cc:66): run ticks until cancelled;
        jittered idle sleep when no input advanced, exponential backoff on
        consecutive tick failures (a dead engine must not busy-spin reads).

        On a live stream (the tick's reads all ended at the log's end) the
        fiber lingers as long as that tick took before it reads again, at
        most ``idle_sleep_s``: ticks run back to back launch whatever one
        tick's time gathered, ~100 launches a second of ~150 records at
        16,384 records/s, and a launch's fixed costs (64 reads that mostly
        find nothing, the two executor hand-offs, the worker's Python under
        the interpreter lock the loop needs, a wake of every parked fetch)
        then fill the loop: its lag, and with it the tail of every produce
        and fetch, swings with whatever slows the machine by a tenth. With
        the linger launches take half the fiber's time at most, each
        carries what two tick-times gathered, and a record waits no longer
        (half of twice a shorter tick). A backlog (any read cut by its byte
        budget) runs back to back as before. The linger is part of the
        ``gap`` phase.

        The retry posture IS the loop: a failed/timed-out tick advanced no
        offsets and wrote nothing, so the next tick re-reads the same
        records — bounded only by backoff, never by a give-up that would
        strand input."""
        pm = self.pacemaker
        failures = 0
        # a payload script's device programs are built ahead of need, off
        # this loop and off the executor's serving worker (the engine's
        # ladder): the fiber takes its first input once the smallest
        # buckets are ready, so no launch waits on a build inside a tick
        await_programs = getattr(pm.engine, "await_programs", None)
        if await_programs is not None:
            await asyncio.to_thread(await_programs, self.script_id)
        while True:
            try:
                self._linger_s = 0.0
                moved = await self.tick()
                failures = 0
                if moved and self._linger_s > 0.0:
                    await asyncio.sleep(min(self._linger_s, pm.idle_sleep_s))
            except asyncio.CancelledError:
                raise
            except _StopScript:
                return
            except Exception as exc:
                failures += 1
                faults.note_failure("pacemaker_tick", exc)
                if failures == 1:
                    logger.exception("script %s tick failed", self.name)
                else:
                    logger.debug(
                        "script %s tick failed again (%d consecutive): %r",
                        self.name, failures, exc,
                    )
                moved = False
            if not moved:
                delay = pm.idle_sleep_s
                if failures:
                    delay = min(
                        pm.idle_sleep_s * (2 ** min(failures, 7)), 5.0
                    )
                await asyncio.sleep(delay)

    async def tick(self) -> bool:
        """One read → transform → write round; True if any offset moved.

        Offsets advance ONLY after the materialized write lands
        (script_context.cc's read → process → write → last_acked order) —
        advancing at read time would drop records on any write failure.

        The read budget is ``max_batch_size`` times the governor's
        ``group_ticks`` knob, which moves on two kinds of evidence
        (``Governor.launch_knobs``): the device leg's tail, on a clock of
        seconds, and, for a lane with no device leg, what this tick tells
        it when it completes (``Governor.note_launch``): whether every
        partition that gave records is in ``behind``, on a clock of
        launches. A live stream's reads end at the LSO, ``behind`` is empty
        and that rule never fires.

        Every phase is a stage (observability/stages.py): always a sample
        in ``coproc_tick_latency_us{phase=}`` and, in a profile, an
        ``rp:coproc.*`` annotation; with tracing on, a span under the tick.
        ``tick`` is what the fiber spent on this launch: what it read here
        for itself, then gate + engine + write. ``read`` is the read's own
        time wherever it ran and ``read_hidden`` the part of it that ran
        inside the previous tick's engine phase (0 for a tick that read for
        itself), so read - read_hidden + gate + engine + write = tick;
        tick + gap tiles the fiber.
        """
        pm = self.pacemaker
        knobs = pm.launch_knobs()
        items = []
        read_high: dict[NTP, int] = {}
        # partitions whose read stopped short of the LSO it read against
        # (the byte budget ended it, not the log): ntp -> where the next
        # read starts. A backlog, and the one thing that is read ahead
        behind: dict[NTP, int] = {}
        hidden_s = 0.0
        t_tick = stages.begin("coproc.tick")
        t_read = stages.begin("coproc.read")
        # group_ticks_per_launch fuses N ticks' worth of input into one
        # launch (deeper batching amortizes the device round trip; the
        # governor shrinks it back to 1 under memory pressure)
        read_budget = pm.max_batch_size * knobs["group_ticks"]
        try:
            ahead = self._take_ahead()
            for ntp in self._input_ntps():
                got = ahead.get(ntp)
                if got is None:
                    batches, lso = await self._read_ntp(ntp, read_budget)
                else:
                    batches, lso, read_s = got
                    hidden_s += read_s
                if batches:
                    # recorded where the records are taken for a launch,
                    # not where they were read: append -> the tick that
                    # takes it
                    self._note_input_wait(ntp, batches)
                    items.append(ProcessBatchItem(self.script_id, ntp, batches))
                    read_high[ntp] = high = batches[-1].last_offset
                    if high < lso - 1:
                        behind[ntp] = high + 1
        finally:
            if not items:
                # an idle tick is no sample and no trace (it would drown
                # the ring): only the annotations end
                stages.close("coproc.read", None, t_read, trace_id=None)
                stages.close("coproc.tick", None, t_tick, trace_id=None)
        if not items:
            return False
        # One trace per productive tick, begun at the start of its read:
        # the tick span and the ``tick`` sample cover the read phase.
        tick_span = tracer.span(
            "coproc.tick", root=True,
            node=self.pacemaker.broker.config.node_id,
        )
        tick_span.enter_at(t_tick)
        if self._t_tick_end is not None:
            coproc_tick_hist["gap"].record(int((t_tick - self._t_tick_end) * 1e6))
        launched = live = False
        try:
            # the stage is what this tick read for itself; what it took
            # out of the read-ahead is the rest
            own_s = stages.close("coproc.read", None, t_read)
            record_us(coproc_tick_hist["read"], int((own_s + hidden_s) * 1e6))
            coproc_tick_hist["read_hidden"].record(int(hidden_s * 1e6))
            live = not behind
            if behind and not pm.read_ahead_allowed():
                behind = {}
            moved, shed_retry_s = await self._launch_and_write(
                items, read_high, behind, read_budget, knobs, tick_span.trace_id
            )
            launched = shed_retry_s is None
        finally:
            if not launched:
                # failed, timed out or shed: no offset moved, so what was
                # read ahead of them is no tick's input; the next tick
                # re-reads from self.offsets
                self._drop_ahead()
            # the gap starts on the clock read that ended the tick
            tick_s = stages.close(
                "coproc.tick", coproc_tick_hist["tick"], t_tick, span=tick_span
            )
            self._t_tick_end = t_tick + tick_s
            if launched and live:
                self._linger_s = tick_s
        if shed_retry_s is not None:
            # backoff OUTSIDE the depth gate: under a floored depth a
            # shed script sleeping inside the slot would head-of-line
            # block every other script's admissible launch
            await asyncio.sleep(shed_retry_s)
            return False
        if moved:
            # append-invalidation hook for the device column cache:
            # this script's input window just advanced, so its cached
            # columns can never be re-read (the cache key is
            # content-addressed — this reclaims memory, it is not
            # what keeps hits correct)
            pm.engine.invalidate_columns(self.script_id)
        return moved

    # ------------------------------------------------------------ read-ahead
    def _begin_read_ahead(self, behind: dict, budget: int) -> None:
        """Start reading the next tick's input (called with this tick's
        submit handed to the executor): one task, the partitions in
        ``behind``, at this tick's read budget. Holds no gate slot."""
        if behind:
            self._ahead = ahead = _ReadAhead()
            ahead.task = asyncio.create_task(self._read_ahead(ahead, behind, budget))

    async def _read_ahead(self, ahead: _ReadAhead, behind: dict, budget: int) -> None:
        t_start = time.perf_counter()
        try:
            for ntp, start in behind.items():
                # one annotation a partition, on the loop thread's line
                # under the fiber's rp:coproc.engine; the ``read`` sample
                # is the tick's that takes these records
                t0 = stages.begin("coproc.read")
                try:
                    batches, lso = await self._read_ntp(ntp, budget, start)
                finally:
                    dt = stages.close("coproc.read", None, t0, trace_id=None)
                if batches:
                    ahead.reads.append((ntp, start, batches, lso, dt))
                if ahead.yields:
                    await asyncio.sleep(0)
        except Exception as exc:  # pandalint: disable=EXC901 -- not a swallow: held for the next tick, which raises it as its own failed read (_take_ahead) so that _loop classifies it once
            ahead.error = exc
        if tracer.enabled and ahead.reads:
            # one ring span a read-ahead, under the engine span of the tick
            # it ran beneath (this task's context was copied inside it)
            tracer.record(
                "coproc.read", sum(r[4] for r in ahead.reads) * 1e6,
                tracer.current_trace(), start_perf=t_start,
                ahead_partitions=len(ahead.reads),
            )

    async def _see_read_ahead_out(self, ticket, t_back: float) -> float:
        """Between a tick's two executor calls: wait for what is being read
        ahead before the harvest goes out; returns the clock read the
        harvest goes out on, which is ``t_back`` (the fiber's resume after
        the submit) itself where there was nothing to wait for, so that the
        tick's ``read_ahead_wait`` sample, the difference of the two, is 0
        there and the engine phase stays the sum of its legs. The stage is
        the annotation and the ring span; the sample is the caller's, from
        the shared reads. The submit's crossings (explode,
        pack) and the launch's transfer, which is in flight from the
        dispatch on, are what the read overlaps; the rest of it runs here
        in one stretch, with the worker idle. Measured on the chip with
        this wait taken out (PR 38, two pairs a cell, over the
        one-crossing seal, which no longer pays beside a reading loop):
        the read then runs beside the harvest's Python and is the slower
        for it (28 -> 34.5 ms a tick, 10 ms of it no longer hidden), the
        hand-off back grows (3.7 -> 5.7 ms), and json64p-v1.catchup
        drained 8.2% and 2.2% slower, json64p-where.catchup 4.8% and
        6.4% slower (its short engine phase ends before the read has had
        its turns of the loop), nexmark64p-q1.catchup the same to 0.2%
        (ROADMAP.md A1 (6))."""
        ahead = self._ahead
        if ahead is None or ahead.task.done():
            return t_back
        ahead.yields = False
        try:
            with stages.stage("coproc.read_ahead.wait"):
                # wait(), not await: cancelled here (script removal), the
                # fiber leaves the task to stop()
                await asyncio.wait([ahead.task])
        except asyncio.CancelledError:
            # this ticket will never be harvested
            self.pacemaker.engine._release_admission(ticket)
            raise
        return time.perf_counter()

    def _drop_ahead(self) -> None:
        """The tick it ran under failed, timed out or was shed, or the
        script is going: cancelled, it is gone within a turn of the loop."""
        ahead, self._ahead = self._ahead, None
        if ahead is not None:
            ahead.task.cancel()

    def _take_ahead(self) -> dict:
        """What was read ahead for this tick (it ended inside the engine
        phase it ran under), partition by partition: ntp -> (batches, lso,
        seconds the read took). A partition's read is used only if the
        script's offset now stands exactly where the read began and the
        partition is still led here; so after a tick whose write of that
        partition did not land it is left out and the caller re-reads from
        ``self.offsets``, as it always has (a tick that failed, timed out
        or was shed dropped all of it)."""
        ahead, self._ahead = self._ahead, None
        if ahead is None:
            return {}
        if ahead.error is not None:
            raise ahead.error
        pm = self.pacemaker
        out = {}
        for ntp, start, batches, lso, dt in ahead.reads:
            p = pm.broker.partition_manager.get(ntp)
            if p is None or not p.is_leader():
                continue
            if self.offsets.get(ntp, p.start_offset - 1) + 1 != start:
                continue
            out[ntp] = (batches, lso, dt)
        return out

    async def _launch_and_write(
        self, items: list, read_high: dict, behind: dict, read_budget: int,
        knobs: dict, trace_id,
    ) -> tuple[bool, float | None]:
        """The gate, engine and write phases of a productive tick:
        (any offset moved, seconds to back off after an admission shed).
        Inside the engine phase the partitions in ``behind`` are read ahead
        for the next tick, ``read_budget`` bytes each."""
        pm = self.pacemaker
        # launch_depth bounds concurrent submit+harvest regions across
        # every script fiber: the staged bytes of at most depth
        # launches are in flight, which is what keeps the coproc
        # account's occupancy (and so the pressure signal) meaningful
        with stages.stage("coproc.gate", coproc_tick_hist["gate"]):
            async with pm._launch_cond:
                while pm._launch_inflight >= knobs["launch_depth"]:
                    await pm._launch_cond.wait()
                pm._launch_inflight += 1
        try:
            # engine: request built and submit dispatched to reply in hand,
            # executor queueing included; the waits are its children in
            # the ring. The phase is the sum of COPROC_ENGINE_PHASES by
            # construction: each leg begins on the clock read that ended
            # the one before it, the first on the stage's own t0 and the
            # last on its t1. Its two executor calls are clocked on both
            # threads: one sample a tick in each of COPROC_HANDOFF_PHASES,
            # the sum over both calls. All five are recorded once both
            # calls have come back (a timed-out, cancelled or shed tick
            # records none)
            legs = [0.0, 0.0, 0.0]
            eng = stages.stage("coproc.engine", coproc_tick_hist["engine"])
            with eng:
                # Submit AND harvest run in worker threads: the first
                # dispatch of a spec jit-compiles for seconds, and anything
                # that blocks the broker's event loop that long stops raft
                # heartbeats and forces cluster-wide re-elections (measured:
                # every group re-elected ~10s after the first deploy when
                # submit ran on-loop).
                loop = asyncio.get_running_loop()
                req = ProcessBatchRequest(items, trace_id=trace_id)
                ex = pm.engine_executor
                # tick deadline: the engine's internal deadlines bound every
                # device leg, so these only fire when that machinery is
                # itself wedged. A timed-out executor call is ABANDONED, not
                # retried in place: its ticket is never harvested, so
                # nothing is written (no duplicates), and the un-advanced
                # offsets make the next tick re-read the same records (no
                # loss). The governor may have adaptively RAISED per-domain
                # deadlines since the static backstop was sized at startup,
                # so re-derive per tick: the backstop must always sit above
                # the engine's own envelope or it would abandon legitimately
                # mid-envelope ticks.
                deadline_s = pm.tick_deadline_for(pm.engine)
                t_out = time.perf_counter()
                prepare_s = t_out - eng.t0
                sub_fut = loop.run_in_executor(ex, pm.engine.submit, req)
                self._begin_read_ahead(behind, read_budget)
                try:
                    with stages.stage("coproc.submit.wait") as wait:
                        ticket = await asyncio.wait_for(
                            asyncio.shield(sub_fut), timeout=deadline_s
                        )
                        t_back = _note_handoff(legs, wait, t_out, ticket)
                except (asyncio.TimeoutError, asyncio.CancelledError):
                    # timeout OR fiber cancellation (script removal): the
                    # executor thread cannot be cancelled, and the shielded
                    # submit's eventual ticket will never be harvested —
                    # hand its reservation back or the account ratchets
                    # shut one abandoned tick at a time
                    sub_fut.add_done_callback(_release_abandoned(pm.engine))
                    raise
                t_out = await self._see_read_ahead_out(ticket, t_back)
                ahead_wait_s = t_out - t_back
                res_fut = loop.run_in_executor(ex, ticket.result)
                try:
                    with stages.stage("coproc.harvest.wait") as wait:
                        reply = await asyncio.wait_for(
                            asyncio.shield(res_fut), timeout=deadline_s
                        )
                        t_back = _note_handoff(legs, wait, t_out, ticket)
                except (asyncio.TimeoutError, asyncio.CancelledError):
                    # shield the work item too: an un-started queued
                    # result() would otherwise be CANCELLED outright and
                    # its finally (the release) never run. Release here
                    # for promptness — _release_admission is atomic and
                    # idempotent, so the racing executor-side finally is
                    # harmless either way.
                    pm.engine._release_admission(ticket)
                    raise
            # the fiber's resume to the phase's end (two stage exits) is the
            # hand-off back's
            legs[2] += eng.t1 - t_back
            for phase, dt in zip(
                COPROC_ENGINE_PHASES, (prepare_s, *legs, ahead_wait_s)
            ):
                coproc_tick_hist[phase].record(int(dt * 1e6))
        except ShedError as exc:
            # admission refused the staged bytes BEFORE any dispatch:
            # no offsets moved, nothing was written — back off the
            # throttle hint and re-read the same records (counted via
            # coproc_admission_shed_total, journaled as an ADMISSION
            # shed episode; not a fault, so no note_failure here)
            logger.debug(
                "script %s submit shed: %s", self.name, exc
            )
            return False, min(exc.retry_after_ms / 1000.0, 5.0)
        finally:
            async with pm._launch_cond:
                pm._launch_inflight -= 1
                pm._launch_cond.notify_all()
        if self.script_id in reply.deregistered:
            logger.warning("script %s deregistered by engine policy", self.name)
            pm.detach_script(self.name)
            self._task = None
            self._drop_ahead()
            raise _StopScript()
        moved = False
        with stages.stage("coproc.write", coproc_tick_hist["write"]):
            for item in reply.items:
                if await self._write_materialized(item.source, item.batches):
                    self.offsets[item.source] = read_high[item.source]
                    moved = True
        gov = getattr(pm.engine, "governor", None)
        if gov is not None:
            # the launch knob's evidence where no device leg will ever give
            # it any: was this launch cut by the read budget in every
            # partition that gave records (a backlog), and how long did the
            # engine's two calls hold it
            gov.note_launch(len(behind) == len(items), sum(legs))
        return moved, None

    def _input_ntps(self) -> list[NTP]:
        out = []
        for topic in self.input_topics:
            md = self.pacemaker.broker.topic_table.get(topic)
            if md is None:
                continue
            out.extend(pa.ntp for pa in md.assignments.values())
        return out

    async def _read_ntp(
        self, ntp: NTP, max_bytes: int | None = None, start: int | None = None
    ) -> tuple[list, int]:
        """read_ntp (script_context_frontend.cc:80-98): from last_acked+1
        (or ``start``, for a read ahead of the offsets) up to the LSO,
        bounded by the read budget (max batch size scaled by the
        group_ticks launch knob) + the read semaphore. Returns the batches
        and the LSO (exclusive) they were read against: a last offset short
        of it says the budget ended the read, not the log."""
        pm = self.pacemaker
        p = pm.broker.partition_manager.get(ntp)
        if p is None or not p.is_leader():
            return [], 0
        if start is None:
            start = self.offsets.get(ntp, p.start_offset - 1) + 1
        lso = p.last_stable_offset  # exclusive
        if start >= lso:
            return [], lso
        budget = max_bytes if max_bytes is not None else pm.max_batch_size
        reserved = await pm.read_budget.acquire(budget)
        try:
            # read what was RESERVED, not what was asked: an oversized
            # budget clamps to the whole account and must read that much,
            # or the bytes in flight exceed the bound they reserved against
            batches = await p.make_reader(start, reserved, max_offset=lso - 1)
        finally:
            pm.read_budget.release(reserved)
        return batches, lso

    def _note_input_wait(self, ntp: NTP, batches: list) -> None:
        """How long the oldest batch a tick takes from a partition waited
        for that tick."""
        p = self.pacemaker.broker.partition_manager.get(ntp)
        t_append = p.append_stamp(batches[0].last_offset) if p is not None else None
        if t_append is not None:
            coproc_input_wait_hist.record(  # pandalint: disable=HST1001 -- every script fiber runs on the broker's event loop, and nothing off it records this histogram
                int((time.perf_counter() - t_append) * 1e6)
            )

    async def _write_materialized(self, source: NTP, batches: list) -> bool:
        """do_write_materialized_partition (script_context_backend.cc:40-68):
        CRC check + append directly to the materialized log, no raft. The
        check rides the append (``verify_crc``): the log leaves a batch
        whose Kafka CRC does not match out, and says so.
        Returns True when the source's offset may advance."""
        if not batches:
            return True  # everything filtered out: the read is still acked
        pm = self.pacemaker
        mntp = MaterializedNTP(source, self.name).ntp
        partition = await pm.ensure_materialized(source, mntp)
        if partition is None:
            return False  # create raced/failed: retry this read next tick
        await partition.replicate(batches, 2, verify_crc=True)  # no_ack: direct log write
        return True


class Pacemaker:
    def __init__(
        self,
        broker,
        engine: TpuEngine,
        *,
        max_batch_size: int = 32 * 1024,
        max_inflight_reads: int = 8,
        offset_flush_interval_s: float = 5.0,
        idle_sleep_s: float = 0.05,
        tick_deadline_s: float = 120.0,
        group_ticks_per_launch: int = 1,
        launch_depth: int = 4,
    ) -> None:
        self.broker = broker
        self.engine = engine
        self.max_batch_size = max_batch_size
        self.tick_deadline_s = tick_deadline_s
        # The read bound is BYTE-denominated (a FIFO-waiting account of
        # max_inflight_reads * max_batch_size bytes), not a read-count
        # semaphore: the group_ticks launch knob scales each read's byte
        # budget, and a count-based gate sized for one-tick reads would
        # let concurrent buffers reach group_ticks_cap x the configured
        # coproc_max_inflight_bytes. An oversized single read clamps to
        # the whole account and proceeds alone (MemoryAccount semantics).
        self.read_budget = leakwatch.wrap(
            MemoryAccount(
                "coproc_read",
                max(1, int(max_inflight_reads)) * max(1, int(max_batch_size)),
            ),
            "pacemaker.read_budget",
        )
        # launch knobs (resource_mgmt / governor ADMISSION domain):
        # group_ticks_per_launch scales how many ticks' worth of input one
        # launch fuses (the read budget per ntp), launch_depth bounds
        # concurrent submit+harvest regions across ALL scripts. Static
        # here; when the engine's governor has autotune configured
        # (CoprocApi does), launch_knobs() returns ITS hysteresis-bounded
        # dynamic verdicts instead — the engine trades launch depth for
        # latency as memory pressure rises.
        self.group_ticks_per_launch = max(1, int(group_ticks_per_launch))
        self.launch_depth = max(1, int(launch_depth))
        self._launch_inflight = 0
        self._launch_cond = asyncio.Condition()
        self.offset_flush_interval_s = offset_flush_interval_s
        self.idle_sleep_s = idle_sleep_s
        self._scripts: dict[str, ScriptContext] = {}
        self._flush_task: asyncio.Task | None = None
        self._materialized_locks: dict[NTP, asyncio.Lock] = {}
        # Dedicated executor for engine submit/harvest: these block for a
        # whole launch (host stages + a device round trip), and on
        # the loop's DEFAULT executor they would starve every
        # asyncio.to_thread user in the broker (storage/archival blocking
        # I/O shares that pool). Lazily created; sized like the default
        # executor it replaced — a harvest can block up to the 30s mask
        # timeout, so a small fixed cap would head-of-line block every
        # other script's tick behind a few wedged fetches.
        self._engine_executor: ThreadPoolExecutor | None = None

    def launch_knobs(self) -> dict:
        """Effective {"group_ticks", "launch_depth"} for the next tick:
        the governor's dynamic verdict when its autotune is configured
        (journaled, hysteresis-bounded), the static constructor knobs for
        bare engines/test doubles."""
        gov = getattr(self.engine, "governor", None)
        if gov is not None and gov.autotune_snapshot() is not None:
            return gov.launch_knobs()
        return {
            "group_ticks": self.group_ticks_per_launch,
            "launch_depth": self.launch_depth,
        }

    def read_ahead_allowed(self) -> bool:
        """A script may hold a second read's input only while the budget
        plane's pressure reads ``ok`` (engines without a governor: always)."""
        gov = getattr(self.engine, "governor", None)
        return gov is None or gov.pressure_level() == "ok"

    def tick_deadline_for(self, engine) -> float:
        """Effective tick backstop: the configured static deadline, never
        below 4x the engine's worst-case per-domain retry envelope (the
        governor can raise per-domain deadlines adaptively at runtime; a
        backstop sized once at startup would then fire on healthy-but-slow
        ticks). Engines without a governor (bare test doubles) keep the
        static value."""
        gov = getattr(engine, "governor", None)
        if gov is None:
            return self.tick_deadline_s
        return max(self.tick_deadline_s, 4.0 * gov.max_envelope_s())

    @property
    def engine_executor(self) -> ThreadPoolExecutor:
        if self._engine_executor is None:
            self._engine_executor = ThreadPoolExecutor(
                max_workers=min(32, (os.cpu_count() or 1) + 4),
                thread_name_prefix="rptpu-coproc-tick",
            )
        return self._engine_executor

    # ------------------------------------------------------------ lifecycle
    async def start(self) -> "Pacemaker":
        self._recover_offsets()
        self._flush_task = asyncio.create_task(self._flush_loop())
        return self

    async def stop(self) -> None:
        if self._flush_task is not None:
            self._flush_task.cancel()
            try:
                await self._flush_task
            except asyncio.CancelledError:
                pass
            self._flush_task = None
        for ctx in list(self._scripts.values()):
            await ctx.stop()
        self._save_offsets()
        self._scripts.clear()
        if self._engine_executor is not None:
            # fibers are stopped, nothing new can be submitted; don't block
            # broker shutdown on a straggling harvest
            self._engine_executor.shutdown(wait=False)
            self._engine_executor = None

    # ------------------------------------------------------------ scripts
    async def add_source(self, name: str, script_id: int, input_topics: tuple[str, ...]) -> None:
        """pacemaker.h:75 add_source: one fiber per script."""
        if name in self._scripts:
            return
        ctx = ScriptContext(self, script_id, name, input_topics)
        for key, off in self._saved_offsets().get(name, {}).items():
            ns, topic, part = key.rsplit("/", 2)
            ctx.offsets[NTP(ns, topic, int(part))] = off
        self._scripts[name] = ctx
        ctx.start()

    async def remove_script(self, name: str) -> None:
        ctx = self._scripts.pop(name, None)
        if ctx is not None:
            await ctx.stop()

    def detach_script(self, name: str) -> None:
        """Unregister without awaiting the fiber (used from INSIDE the
        fiber, which then exits via _StopScript)."""
        self._scripts.pop(name, None)

    def scripts(self) -> dict[str, ScriptContext]:
        return dict(self._scripts)

    # ------------------------------------------------------------ materialized logs
    async def ensure_materialized(self, source: NTP, mntp: NTP):
        """Create the materialized topic/partition on demand under a
        per-ntp mutex (script_context_backend.cc:70-78)."""
        lock = self._materialized_locks.setdefault(mntp, asyncio.Lock())
        async with lock:
            p = self.broker.partition_manager.get(mntp)
            if p is not None:
                return p
            if not self.broker.topic_table.contains(mntp.topic):
                from redpanda_tpu.cluster.topic_table import TopicConfig

                src_md = self.broker.topic_table.get(source.topic)
                n_parts = src_md.config.partition_count if src_md else 1
                try:
                    dispatcher = getattr(self.broker, "controller_dispatcher", None)
                    if dispatcher is not None:
                        # Clustered: replicate create_non_replicable_topic
                        # so every broker's metadata agrees; assignments
                        # mirror the source (group -1, coproc writes bypass
                        # raft — commands.h:112 non_replicable semantics)
                        from redpanda_tpu.cluster.service import (
                            OP_CREATE_NON_REPLICABLE,
                        )

                        await dispatcher.topic_op(  # pandalint: disable=LCK702 -- create-once-per-mntp mutex: a serialized tick beats duplicate create ops racing the controller
                            OP_CREATE_NON_REPLICABLE,
                            {"source": source.topic, "name": mntp.topic,
                             "ns": mntp.ns},
                        )
                        await self.broker._await_topic_table(
                            lambda: self.broker.topic_table.contains(mntp.topic),
                            f"materialize {mntp.topic}",
                        )
                    else:
                        # Standalone: the materialized log lives NEXT TO its
                        # source partition (script_context_backend.cc:70-78
                        # direct storage append, no raft)
                        await self.broker.create_topic(
                            TopicConfig(mntp.topic, n_parts, 1, ns=mntp.ns),
                            local_only=True,
                        )
                except ValueError:
                    pass
            # the local log: reconciled by the backend (clustered) or
            # created by the local path above
            p = self.broker.partition_manager.get(mntp)
            if p is None:
                for _ in range(100):
                    await asyncio.sleep(0.05)
                    p = self.broker.partition_manager.get(mntp)
                    if p is not None:
                        break
            return p

    # ------------------------------------------------------------ offsets
    def _kvs(self):
        return self.broker.storage.kvs

    def _saved_offsets(self) -> dict[str, dict[str, int]]:
        raw = self._kvs().get(KeySpace.coproc, b"offsets")
        return json.loads(raw.decode()) if raw else {}

    def _save_offsets(self) -> None:
        data = {
            name: {
                f"{ntp.ns}/{ntp.topic}/{ntp.partition}": off
                for ntp, off in ctx.offsets.items()
            }
            for name, ctx in self._scripts.items()
        }
        self._kvs().put(KeySpace.coproc, b"offsets", json.dumps(data).encode())

    def _recover_offsets(self) -> None:
        # contexts pick their saved offsets up in add_source
        pass

    async def _flush_loop(self) -> None:
        while True:
            await asyncio.sleep(self.offset_flush_interval_s)
            try:
                self._save_offsets()
            except Exception as exc:
                # classified: losing offset snapshots silently would turn a
                # later restart into a giant re-read with no warning
                faults.note_failure("offset_flush", exc)
                logger.exception("coproc offset flush failed")
