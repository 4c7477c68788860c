"""Budget plane + admission control tests (resource_mgmt).

Covers the ISSUE-13 admission semantics: account acquire/release and
leak-on-exception, shed-before-ack (a shed produce/submit is never
readable), breaker-vs-admission isolation (an open breaker doesn't
double-shed, a shed doesn't move breaker state), hysteresis bounds on the
autotune verdicts, and the arena/colcache pressure hooks (release under
critical, no-op at ok).
"""

from __future__ import annotations

import asyncio
import json

import pytest

from redpanda_tpu.coproc import (
    EnableResponseCode,
    ProcessBatchRequest,
    TpuEngine,
)
from redpanda_tpu.coproc import faults, governor
from redpanda_tpu.coproc.engine import ProcessBatchItem
from redpanda_tpu.models import Compression, NTP, Record, RecordBatch
from redpanda_tpu.ops.transforms import Int, Str, filter_field_eq, map_project
from redpanda_tpu.resource_mgmt import (
    AdmissionController,
    BudgetPlane,
    InflightGate,
    MemoryAccount,
    ShedError,
)
from redpanda_tpu.resource_mgmt import budgets


def run(coro):
    return asyncio.run(coro)


def _json_batch(n, base_offset=0):
    recs = [
        Record(
            offset_delta=i,
            timestamp_delta=i,
            value=json.dumps(
                {"level": ["error", "info"][i % 2], "code": i, "msg": f"m{i}"},
                separators=(",", ":"),
            ).encode(),
        )
        for i in range(n)
    ]
    return RecordBatch.build(recs, base_offset=base_offset, first_timestamp=1000)


def _deploy(engine, script_id=1):
    spec = filter_field_eq("level", "error") | map_project(Int("code"), Str("msg", 16))
    codes = engine.enable_coprocessors([(script_id, spec.to_json(), ("orders",))])
    assert codes == [EnableResponseCode.success]


def _req(n=64):
    return ProcessBatchRequest(
        [ProcessBatchItem(1, NTP("kafka", "orders", 0), [_json_batch(n)])]
    )


# ------------------------------------------------------------------ accounts
def test_account_acquire_release_clamp_peak():
    a = MemoryAccount("t", 1000)
    assert a.try_acquire(400) == 400
    assert a.held == 400 and a.peak == 400
    # refusal leaves state untouched
    assert a.try_acquire(700) == 0
    assert a.held == 400
    # oversized single request clamps to the limit once there's room
    a.release(400)
    assert a.try_acquire(10**9) == 1000
    assert a.held == 1000 and a.peak == 1000
    a.release(1000)
    assert a.held == 0 and a.peak == 1000  # peak survives
    a.reset_peak()
    assert a.peak == 0
    # zero/negative admit reserving nothing
    assert a.try_acquire(0) == 0 and a.held == 0


def test_account_async_acquire_fifo_wait():
    async def main():
        a = MemoryAccount("t", 100)
        assert await a.acquire(80) == 80
        got = []

        async def waiter(tag, n):
            await a.acquire(n)
            got.append(tag)

        w1 = asyncio.create_task(waiter("big", 60))
        await asyncio.sleep(0.01)
        w2 = asyncio.create_task(waiter("small", 10))
        await asyncio.sleep(0.01)
        # FIFO: the small request must NOT starve the parked big one —
        # nothing is granted until the release, then both in order
        assert got == []
        a.release(80)
        await asyncio.gather(w1, w2)
        assert got == ["big", "small"]

    run(main())


def test_plane_pressure_levels_listener_and_hysteresis():
    plane = BudgetPlane(1000, {"x": 1.0}, warn_pct=0.75, critical_pct=0.90)
    events = []
    plane.add_pressure_listener(lambda lvl, snap: events.append(lvl))
    acct = plane.account("x")
    assert plane.pressure() == budgets.PRESSURE_OK
    acct.try_acquire(800)  # 0.8 -> warn
    assert plane.pressure() == budgets.PRESSURE_WARN
    acct.try_acquire(150)  # 0.95 -> critical
    assert plane.pressure() == budgets.PRESSURE_CRITICAL
    # exit hysteresis: dropping just under the critical line holds critical
    acct.release(80)  # 0.87 >= 0.90 - 0.05
    assert plane.pressure() == budgets.PRESSURE_CRITICAL
    acct.release(100)  # 0.77 -> warn
    assert plane.pressure() == budgets.PRESSURE_WARN
    # and just under the warn line holds warn
    acct.release(50)  # 0.72 >= 0.75 - 0.05
    assert plane.pressure() == budgets.PRESSURE_WARN
    acct.release(720)
    assert plane.pressure() == budgets.PRESSURE_OK
    assert events == ["warn", "critical", "warn", "ok"]


def test_admission_controller_throttle_ramp_and_counters():
    a = MemoryAccount("t", 1000)
    c = AdmissionController(a, "unit_test_sub", base_throttle_ms=50,
                            max_throttle_ms=1000, warn_pct=0.75)
    assert c.throttle_ms() == 50  # empty account: base
    a.try_acquire(1000)
    assert c.throttle_ms() == 1000  # full account: max
    reserved, retry = c.try_admit(10)
    assert reserved == 0 and retry == 1000
    a.release(1000)
    reserved, retry = c.try_admit(10)
    assert reserved == 10 and retry == 0
    snap = c.snapshot()
    assert snap["sheds"] == 1 and snap["admitted"] == 1
    c.release(reserved)
    assert a.held == 0


def test_inflight_gate_request_and_byte_caps():
    a = MemoryAccount("rpc", 100)
    g = InflightGate(a, max_requests=2, subsystem="unit_test_rpc")
    r1 = g.try_enter(40)
    r2 = g.try_enter(40)
    assert r1 and r2
    assert g.try_enter(1) is None  # request cap
    g.leave(r1)
    assert g.try_enter(90) is None  # byte cap (40 held + 90 > 100)
    r3 = g.try_enter(30)
    assert r3
    g.leave(r2)
    g.leave(r3)
    assert a.held == 0
    assert g.snapshot()["sheds"] == 2


# ------------------------------------------------------------------ engine
def _tiny_plane(coproc_bytes=256):
    # a plane whose coproc account is too small for a 64-record launch
    return BudgetPlane(coproc_bytes * 8, {
        "kafka_produce": 0.125, "rpc": 0.125, "coproc": 0.125,
        "storage": 0.5, "raft": 0.125,
    })


def test_engine_shed_before_ack_and_no_leak():
    plane = _tiny_plane()
    acct = plane.account("coproc")
    # fill the account so the submit MUST shed
    filler = acct.try_acquire(acct.limit)
    assert filler
    engine = TpuEngine(row_stride=256, budget_plane=plane)
    try:
        _deploy(engine)
        with pytest.raises(ShedError) as ei:
            engine.submit(_req(64))
        assert ei.value.retry_after_ms > 0
        # shed-before-ack: nothing dispatched, nothing held beyond filler
        assert acct.held == filler
        assert engine.stats().get("n_shed_submits") == 1.0
        # the shed episode is journaled under the admission domain
        entries = governor.journal.entries(domain=governor.ADMISSION)
        assert any(e["verdict"] == "shed" for e in entries)
        # release the pressure: the SAME submit now succeeds bit-exactly
        acct.release(filler)
        reply = engine.submit(_req(64)).result()
        assert sum(len(b.records()) for b in reply.items[0].batches) == 32
        assert acct.held == 0  # released at harvest
        entries = governor.journal.entries(domain=governor.ADMISSION)
        assert any(e["verdict"] == "resumed" for e in entries)
    finally:
        engine.shutdown()


def test_engine_admission_releases_on_result_exception():
    plane = BudgetPlane(1 << 20)
    acct = plane.account("coproc")
    engine = TpuEngine(row_stride=256, budget_plane=plane)
    try:
        _deploy(engine)
        ticket = engine.submit(_req(32))
        assert acct.held > 0

        def boom():
            raise RuntimeError("synthetic harvest failure")

        ticket._result_impl = boom
        with pytest.raises(RuntimeError):
            ticket.result()
        # leak-on-exception: the reservation still came back
        assert acct.held == 0
        # and release is idempotent
        engine._release_admission(ticket)
        assert acct.held == 0
    finally:
        engine.shutdown()


def test_breaker_vs_admission_isolation():
    plane = BudgetPlane(1 << 20)
    acct = plane.account("coproc")
    engine = TpuEngine(row_stride=256, budget_plane=plane)
    try:
        _deploy(engine)
        breaker = engine.governor.breaker_for(faults.DEVICE_DISPATCH)
        # force the dispatch breaker open: admission must still ADMIT
        # (the breaker demotes execution to host, it does not shed)
        for _ in range(100):
            breaker.record_failure()
        assert breaker.state == faults.STATE_OPEN
        reply = engine.submit(_req(32)).result()
        assert sum(len(b.records()) for b in reply.items[0].batches) == 16
        assert acct.held == 0
        # now exhaust the budget: the shed must NOT touch breaker state
        trips_before = breaker.snapshot()["trips"]
        filler = acct.try_acquire(acct.limit)
        with pytest.raises(ShedError):
            engine.submit(_req(32))
        assert breaker.snapshot()["trips"] == trips_before
        acct.release(filler)
    finally:
        engine.shutdown()


# ------------------------------------------------------------------ autotune
class _FakeHist:
    def __init__(self):
        self.count = 0
        self._p = 0.0

    def percentile(self, q):
        return self._p

    def record(self, v):
        self.count += 1


def _autotune_gov(clock, hist, pressure):
    pol = faults.FaultPolicy(deadline_s=1.0, retries=0, backoff_s=0.01)
    g = governor.Governor(
        fault_policy=pol, clock=clock, register_gauges=False,
        stage_hist=lambda domain: hist,
        journal_override=governor.DecisionJournal(64),
    )
    g.configure_autotune(
        enabled=True, group_ticks=2, group_ticks_cap=4,
        launch_depth=2, launch_depth_cap=4, hold_s=10.0,
        pressure_fn=lambda: pressure[0],
    )
    return g


def test_autotune_grow_hold_and_caps():
    t = [0.0]
    hist = _FakeHist()  # count < min_samples: p99.9 unknown -> HOLD
    pressure = [("ok", 0.1)]
    g = _autotune_gov(lambda: t[0], hist, pressure)
    # no device-leg evidence: the configured knobs hold, never ratchet
    assert g.launch_knobs() == {"group_ticks": 2, "launch_depth": 2}
    # cheap measured legs: now it grows one step per window
    hist.count = 1000
    hist._p = 0.1 * 1e6  # p99.9 = 0.1s vs 1.0s floor: < 50% -> grow
    k = g.launch_knobs()
    assert k == {"group_ticks": 3, "launch_depth": 3}  # grew by one step
    # hysteresis: inside the hold window NOTHING moves, whatever the inputs
    pressure[0] = ("critical", 0.99)
    t[0] = 5.0
    assert g.launch_knobs() == k
    # window over: critical floors both knobs in one verdict
    t[0] = 11.0
    assert g.launch_knobs() == {"group_ticks": 1, "launch_depth": 1}
    # grow back toward the caps, one step per window, never beyond
    pressure[0] = ("ok", 0.1)
    for i in range(6):
        t[0] = 22.0 + 11.0 * i
        k = g.launch_knobs()
    assert k == {"group_ticks": 4, "launch_depth": 4}  # capped
    entries = g._journal.entries(domain=governor.ADMISSION)
    verdicts = [e["verdict"] for e in entries]
    assert "grow" in verdicts and "floor" in verdicts
    # every resize carries its measured inputs
    assert all(
        "pressure" in e["inputs"] and "group_ticks" in e["inputs"]
        for e in entries
    )


def test_autotune_latency_guard_shrinks():
    t = [0.0]
    hist = _FakeHist()
    hist.count = 1000
    hist._p = 0.9 * 1e6  # p99.9 = 0.9s vs 1.0s floor: > 80% -> shrink
    pressure = [("ok", 0.1)]
    g = _autotune_gov(lambda: t[0], hist, pressure)
    assert g.launch_knobs() == {"group_ticks": 1, "launch_depth": 1}
    # healthy tail again: grows back
    hist._p = 0.1 * 1e6
    t[0] = 11.0
    assert g.launch_knobs() == {"group_ticks": 2, "launch_depth": 2}


# The second kind of evidence (no device leg has ever completed): a backlog,
# told by the pacemaker launch by launch, on a clock of launches.
K = governor._AUTOTUNE_BACKLOG_LAUNCHES


def _backlog_launches(g, n, *, cut=True, engine_s=0.01):
    """``n`` ticks as the pacemaker makes them: poll the knobs, then tell
    the governor how the launch went. The knobs as the last poll gave them."""
    for _ in range(n):
        k = g.launch_knobs()
        g.note_launch(cut, engine_s)
    return k


def _admission(g):
    """The knobs' moves, oldest first."""
    return g._journal.entries(domain=governor.ADMISSION)[::-1]


def test_autotune_backlog_grows_by_the_launch_not_by_the_clock():
    t = [0.0]
    hist = _FakeHist()  # never a device-leg sample
    g = _autotune_gov(lambda: t[0], hist, [("ok", 0.1)])
    seen = []
    for _ in range(4 * K):
        seen.append(g.launch_knobs()["group_ticks"])
        g.note_launch(True, 0.01)
    # one step a K launches with the clock standing still, to the cap (4)
    # and not beyond
    assert seen == [2] * K + [3] * K + [4] * (2 * K)
    assert g.launch_knobs() == {"group_ticks": 4, "launch_depth": 2}
    entries = _admission(g)
    assert [e["verdict"] for e in entries] == ["grow", "grow"]
    for e, (prev, new) in zip(entries, [(2, 3), (3, 4)]):
        i = e["inputs"]
        assert i["evidence"] == "backlog" and i["backlog_launches"] == K
        assert (i["prev_group_ticks"], i["group_ticks"]) == (prev, new)
        assert i["prev_launch_depth"] == i["launch_depth"] == 2  # unmoved
        assert i["p999_us"] is None
    assert g.autotune_snapshot()["evidence"] == "backlog"
    # the device rule says so too, once there is a device leg to go by
    hist.count, hist._p = 1000, 0.9 * 1e6
    assert g.launch_knobs() == {"group_ticks": 3, "launch_depth": 1}
    assert _admission(g)[-1]["inputs"]["evidence"] == "device_leg"
    assert g.autotune_snapshot()["evidence"] == "device_leg"


def _launches_that_reach_the_lso(g, hist):
    _backlog_launches(g, 4 * K, cut=False)


def _a_cut_launch_now_and_then(g, hist):
    for _ in range(4):
        _backlog_launches(g, K - 1)
        _backlog_launches(g, 1, cut=False)


def _a_device_sample_under_min_samples(g, hist):
    hist.record(1)  # a payload lane: the device rule's HOLD, not this rule
    _backlog_launches(g, 4 * K)


def _launches_not_cheap_enough(g, hist):
    # over the grow fraction of the 1 s deadline, under the shrink fraction
    _backlog_launches(g, 4 * K, engine_s=0.6)


def _autotune_off(g, hist):
    g.configure_autotune(enabled=False, group_ticks=2, launch_depth=2)
    _backlog_launches(g, 4 * K)


@pytest.mark.parametrize("how", [
    _launches_that_reach_the_lso, _a_cut_launch_now_and_then,
    _a_device_sample_under_min_samples, _launches_not_cheap_enough,
    _autotune_off,
])
def test_autotune_backlog_rule_holds_without_its_evidence(how):
    t = [0.0]
    hist = _FakeHist()
    g = _autotune_gov(lambda: t[0], hist, [("ok", 0.1)])
    how(g, hist)
    t[0] = 100.0  # and the clock does not stand in for the evidence
    assert g.launch_knobs() == {"group_ticks": 2, "launch_depth": 2}
    assert _admission(g) == []
    assert g.autotune_snapshot()["evidence"] is None


@pytest.mark.parametrize("level, after", [
    ("warn", {"group_ticks": 2, "launch_depth": 1}),
    ("critical", {"group_ticks": 1, "launch_depth": 1}),
])
def test_autotune_backlog_grow_waits_out_the_hold_after_a_shrink(level, after):
    t = [0.0]
    pressure = [("ok", 0.1)]
    g = _autotune_gov(lambda: t[0], _FakeHist(), pressure)
    assert _backlog_launches(g, K + 1) == {"group_ticks": 3, "launch_depth": 2}
    # pressure shrinks as it always has, at once after a launch-counted grow
    # (which armed no hold), and its own move arms the hold
    pressure[0] = (level, 0.95)
    assert g.launch_knobs() == after
    assert _admission(g)[-1]["inputs"]["evidence"] == "pressure"
    # pressure gone, the backlog still there: nothing grows inside hold_s,
    # however many launches are cut
    pressure[0] = ("ok", 0.1)
    t[0] = 9.0
    assert _backlog_launches(g, 3 * K) == after
    t[0] = 10.5
    grown = dict(after, group_ticks=after["group_ticks"] + 1)
    assert g.launch_knobs() == grown
    assert _backlog_launches(g, K + 1)["group_ticks"] == grown["group_ticks"] + 1
    assert [e["verdict"] for e in _admission(g)] == [
        "grow", "floor" if level == "critical" else "shrink", "grow", "grow",
    ]
    # while it lasts, pressure keeps shrinking one step a hold_s
    pressure[0] = ("warn", 0.95)
    held = g.launch_knobs()
    assert held["group_ticks"] == grown["group_ticks"]
    assert _backlog_launches(g, K + 1) == held
    t[0] = 21.0
    assert g.launch_knobs()["group_ticks"] == grown["group_ticks"] - 1


def test_autotune_backlog_slow_engine_phase_steps_down():
    t = [0.0]
    g = _autotune_gov(lambda: t[0], _FakeHist(), [("ok", 0.1)])
    assert _backlog_launches(g, K + 1) == {"group_ticks": 3, "launch_depth": 2}
    # one launch whose engine phase is over 80% of the 1 s deadline: a step
    # down of group_ticks (launch_depth is not this rule's), on the hold clock
    g.note_launch(True, 0.9)
    assert g.launch_knobs() == {"group_ticks": 2, "launch_depth": 2}
    e = _admission(g)[-1]
    assert e["verdict"] == "shrink" and e["inputs"]["evidence"] == "backlog"
    assert e["inputs"]["engine_us"] == 900000
    # spent with the move: the same reading does not step down twice
    t[0] = 11.0
    assert g.launch_knobs() == {"group_ticks": 2, "launch_depth": 2}
    # and cheap budget-cut launches grow it again
    assert _backlog_launches(g, K + 1) == {"group_ticks": 3, "launch_depth": 2}


# ------------------------------------------------------------------ pressure hooks
def test_arena_trim_and_colcache_pressure_hooks():
    plane = BudgetPlane(1 << 20)
    engine = TpuEngine(
        row_stride=256, budget_plane=plane, device_column_cache_mb=1
    )
    try:
        # v2 where-expression spec: a COLUMNAR plan, so the launch
        # populates the device column cache (payload plans don't touch it)
        from redpanda_tpu.ops.exprs import field
        from redpanda_tpu.ops.transforms import where

        spec = where(field("level") == "error")
        codes = engine.enable_coprocessors([(1, spec.to_json(), ("orders",))])
        assert codes == [EnableResponseCode.success]
        # drive a real launch so the arena has parked buffers and the
        # cache has an entry
        engine.submit(_req(64)).result()
        engine.submit(_req(64)).result()  # repeat window -> cache hit path
        cache_before = engine._colcache.stats()
        assert cache_before["entries"] >= 1
        # ok -> ok is a no-op (nothing trims, nothing evicts)
        free_before = engine._arena.stats()["free_buffers"]
        engine._on_memory_pressure(budgets.PRESSURE_OK, plane.snapshot())
        assert engine._arena.stats()["trims"] == 0
        assert engine._arena.stats()["free_buffers"] == free_before
        assert engine._colcache.stats()["pressure_evictions"] == 0
        # critical: arena free-list trimmed, cache budget halves
        engine._on_memory_pressure(
            budgets.PRESSURE_CRITICAL, plane.snapshot()
        )
        st = engine._arena.stats()
        assert st["trims"] == 1 and st["free_buffers"] == 0
        cst = engine._colcache.stats()
        assert cst["pressure"] is True
        assert cst["effective_budget_bytes"] == cst["budget_bytes"] // 2
        assert cst["bytes"] <= cst["effective_budget_bytes"]
        # back to ok: full budget restored
        engine._on_memory_pressure(budgets.PRESSURE_OK, plane.snapshot())
        cst = engine._colcache.stats()
        assert cst["pressure"] is False
        assert cst["effective_budget_bytes"] == cst["budget_bytes"]
        # the transitions are journaled
        entries = governor.journal.entries(domain=governor.ADMISSION)
        assert any(e["verdict"] == "critical" for e in entries)
    finally:
        engine.shutdown()


def test_colcache_pressure_eviction_counts():
    from redpanda_tpu.coproc.colcache import DeviceColumnCache, Entry
    import numpy as np

    cache = DeviceColumnCache(1000)
    for i in range(4):
        cache.put((1, i), Entry(
            n=1, n_pad=1, ranges=[], cols=[np.zeros(200, np.uint8)]
        ))
    st = cache.stats()
    assert st["entries"] == 4 and st["bytes"] == 800
    evicted = cache.set_pressure(True)
    # halved budget (500): two LRU entries must go
    assert evicted == 2
    st = cache.stats()
    assert st["bytes"] <= 500 and st["pressure_evictions"] == 2
    # under pressure, an over-half-budget entry is refused
    assert not cache.put((1, 9), Entry(
        n=1, n_pad=1, ranges=[], cols=[np.zeros(600, np.uint8)]
    ))
    assert cache.set_pressure(False) == 0
    assert cache.put((1, 9), Entry(
        n=1, n_pad=1, ranges=[], cols=[np.zeros(600, np.uint8)]
    ))


# ------------------------------------------------------------------ kafka produce
def test_kafka_produce_shed_before_ack(tmp_path):
    from redpanda_tpu.kafka.client import KafkaClient
    from redpanda_tpu.kafka.protocol.errors import ErrorCode, KafkaError
    from redpanda_tpu.kafka.server.broker import Broker, BrokerConfig
    from redpanda_tpu.kafka.server.protocol import KafkaServer
    from redpanda_tpu.storage.log_manager import StorageApi

    async def main():
        storage = await StorageApi(str(tmp_path)).start()
        broker = Broker(BrokerConfig(data_dir=str(tmp_path)), storage)
        plane = BudgetPlane(8 << 20)
        broker.budget_plane = plane
        broker.produce_admission = AdmissionController(
            plane.account("kafka_produce"), "kafka_produce_test"
        )
        server = await KafkaServer(broker, "127.0.0.1", 0).start()
        broker.config.advertised_port = server.port
        client = await KafkaClient([("127.0.0.1", server.port)]).connect()
        try:
            acct = plane.account("kafka_produce")
            filler = acct.try_acquire(acct.limit)  # pandalint: disable=RSL1602 -- deliberate budget-fill to force the shed; released right after the raises block
            with pytest.raises(KafkaError) as ei:
                await client.produce("t", 0, [(b"k", b"shed-me")], acks=-1)
            assert ei.value.code == ErrorCode.throttling_quota_exceeded
            acct.release(filler)
            # shed-before-ack: the shed record must never be readable
            off = await client.produce("t", 0, [(b"k", b"kept")], acks=-1)
            assert off == 0
            batches, hwm = await client.fetch("t", 0, 0)
            values = [v for b in batches for v in b.record_values()]
            assert values == [b"kept"] and hwm == 1
            assert acct.held == 0  # released after the replicate round
            snap = broker.produce_admission.snapshot()
            assert snap["sheds"] == 1 and snap["admitted"] >= 1
        finally:
            await client.close()
            await server.stop()
            await storage.stop()

    run(main())


# ------------------------------------------------------------------ rpc gate
def test_rpc_server_sheds_with_backpressure_status():
    from redpanda_tpu import rpc
    from redpanda_tpu.rpc import wire

    async def main():
        proto = rpc.SimpleProtocol(
            inflight_gate=InflightGate(
                MemoryAccount("rpc", 1 << 20), max_requests=1,
                subsystem="unit_test_rpc2",
            )
        )

        release = asyncio.Event()

        class Svc:
            def method_ids(self):
                return [0x77]

            async def dispatch(self, mid, body):
                await release.wait()
                return b"pong:" + body

        proto.register_service(Svc())
        server = rpc.Server("127.0.0.1", 0)
        server.set_protocol(proto)
        await server.start()
        t = rpc.Transport("127.0.0.1", server.port)
        await t.connect()
        try:
            # first request parks in the handler and HOLDS the one slot
            first = asyncio.create_task(t.send(0x77, b"a", timeout=5.0))
            await asyncio.sleep(0.05)
            # second is shed at dispatch: the handler never runs
            with pytest.raises(rpc.RpcBackpressure):
                await t.send(0x77, b"b", timeout=5.0)
            release.set()
            assert await first == b"pong:a"
            # slot released: a resend now succeeds (retriable contract)
            assert await t.send(0x77, b"c", timeout=5.0) == b"pong:c"
            assert proto.inflight_gate.snapshot()["sheds"] == 1
            assert proto.inflight_gate.snapshot()["inflight"] == 0
        finally:
            await t.close()
            await server.stop()

    run(main())


# ------------------------------------------------------------------ admin
def test_admin_resources_endpoint(tmp_path):
    import aiohttp

    from redpanda_tpu.admin import AdminServer
    from redpanda_tpu.kafka.server.broker import Broker, BrokerConfig
    from redpanda_tpu.storage.log_manager import StorageApi

    async def main():
        storage = await StorageApi(str(tmp_path)).start()
        broker = Broker(BrokerConfig(data_dir=str(tmp_path)), storage)
        plane = BudgetPlane(16 << 20)
        broker.budget_plane = plane
        broker.produce_admission = AdmissionController(
            plane.account("kafka_produce"), "kafka_produce_admin_test"
        )
        admin = await AdminServer(broker, host="127.0.0.1", port=0).start()
        try:
            plane.account("coproc").try_acquire(1234)
            async with aiohttp.ClientSession() as s:
                async with s.get(
                    f"http://127.0.0.1:{admin.port}/v1/resources"
                ) as r:
                    assert r.status == 200
                    body = await r.json()
            assert body["enabled"] is True
            assert body["accounts"]["coproc"]["held_bytes"] == 1234
            assert body["accounts"]["coproc"]["peak_bytes"] == 1234
            assert body["pressure"] == "ok"
            assert body["produce_admission"]["sheds"] == 0
            # ISSUE 14 satellite: ?federated=1 merges the budget plane
            # over the admin fan-out (single node here: self only) —
            # `rpk debug resources --federated`
            async with aiohttp.ClientSession() as s:
                async with s.get(
                    f"http://127.0.0.1:{admin.port}/v1/resources?federated=1"
                ) as r:
                    assert r.status == 200
                    fed_body = await r.json()
            assert fed_body["federated"] is True
            assert fed_body["enabled"] is True
            assert fed_body["unreachable"] == []
            cop = fed_body["accounts"]["coproc"]
            assert cop["held_bytes"] == 1234
            assert cop["max_occupancy_node"] == "0"
            assert "0" in fed_body["nodes"]
            # archival surface answers 409 when tiered storage is off
            async with aiohttp.ClientSession() as s:
                async with s.post(
                    f"http://127.0.0.1:{admin.port}/v1/archival/run_once"
                ) as r:
                    assert r.status == 409
                async with s.get(
                    f"http://127.0.0.1:{admin.port}/v1/archival/status"
                ) as r:
                    assert r.status == 200
                    assert (await r.json())["enabled"] is False
        finally:
            await admin.stop()
            await storage.stop()

    run(main())


# ------------------------------------------------------------------ gauges
def test_plane_gauges_registered_and_live():
    from redpanda_tpu.metrics import registry

    plane = BudgetPlane(1 << 20, register_gauges=True)
    plane.account("coproc").try_acquire(4096)
    text = registry.render_prometheus()
    assert 'resource_account_held_bytes{account="coproc"} 4096' in text
    assert "resource_pressure_state 0" in text
    plane.account("coproc").release(4096)
