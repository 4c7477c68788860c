"""Coproc governor tests (ISSUE 8): the unified decision plane.

Four sides of coproc/governor.py:

- the decision journal: entries for every decision domain under real
  launches (columnar backend probe, device_lz4 probe, breaker
  transitions, harvest-path mode),
  bounded capacity, monotonic seq, per-entry inputs/verdict/reason/config;
- adaptive deadlines: provably track the observed stage p99.9 against an
  injected histogram source, never undercut the configured static floor,
  and respect the cap — with the derivation journaled;
- per-domain breakers: a tripped mask-fetch domain demotes fetches to the
  exact fallback while the dispatch domain stays on-device;
- the surfaces: stats()["governor"]/["breakers"], GET /v1/governor, and
  the replicate-path owner-trace sampling (ROADMAP item 3 follow-on).
"""

import json
import threading
import time

import numpy as np
import pytest

from redpanda_tpu.coproc import (
    TpuEngine,
    ProcessBatchRequest,
    EnableResponseCode,
)
from redpanda_tpu.coproc import faults
from redpanda_tpu.coproc import governor
from redpanda_tpu.coproc.engine import ProcessBatchItem
from redpanda_tpu.finjector import honey_badger
from redpanda_tpu.models import NTP, Record, RecordBatch
from redpanda_tpu.ops.exprs import field
from redpanda_tpu.ops.transforms import Int, Str, map_project, where
from redpanda_tpu.utils.hdr import HdrHist


_live_engines: list[TpuEngine] = []


@pytest.fixture(autouse=True)
def _clean_slate():
    """Each test starts with a fresh journal and ends with every engine it
    created shut down and the badger disarmed (both are process-global)."""
    governor.reset_journal()
    yield
    for module, armed in list(honey_badger.armed().items()):
        for probe in armed:
            honey_badger.unset(module, probe)
    honey_badger.disable()
    while _live_engines:
        _live_engines.pop().shutdown()


def _engine(**kw) -> TpuEngine:
    kw.setdefault("row_stride", 256)
    kw.setdefault("compress_threshold", 10**9)
    kw.setdefault("host_workers", 0)
    kw.setdefault("retry_backoff_ms", 1)
    engine = TpuEngine(**kw)
    _live_engines.append(engine)
    spec = where(field("level") == "error") | map_project(Int("code"), Str("msg", 16))
    codes = engine.enable_coprocessors([(1, spec.to_json(), ("orders",))])
    assert codes == [EnableResponseCode.success]
    return engine


def _req(parts: int = 1, n: int = 24) -> ProcessBatchRequest:
    items = []
    for p in range(parts):
        recs = [
            Record(
                offset_delta=i,
                timestamp_delta=i,
                value=json.dumps(
                    {"level": ["error", "info"][i % 2], "code": 100 * p + i,
                     "msg": f"p{p}m{i}"},
                    separators=(",", ":"),
                ).encode(),
            )
            for i in range(n)
        ]
        items.append(
            ProcessBatchItem(
                1, NTP.kafka("orders", p),
                [RecordBatch.build(recs, base_offset=1000 * p, first_timestamp=1000)],
            )
        )
    return ProcessBatchRequest(items)


def _payloads(reply):
    return [
        (item.source, [(b.payload, b.header.crc, b.header.record_count) for b in item.batches])
        for item in reply.items
    ]


def _domains():
    return {e["domain"] for e in governor.journal.entries()}


# ------------------------------------------------------------ decision journal
def test_journal_covers_the_engine_domains_under_real_launches(monkeypatch):
    """Every decision the engine takes lands in the journal from REAL code
    paths: a big columnar launch drives the backend probe and the
    harvest-path verdict; an armed mask-fetch fault drives a
    breaker transition; the lz4 probe drives device_lz4."""
    TpuEngine.reset_columnar_probe()
    # pure filter => passthrough plan => gather framing; 64 batches x 32
    # records = 2048 rows clears _PROBE_MIN_ROWS
    spec = where(field("level") == "error")
    engine = TpuEngine(
        row_stride=256, compress_threshold=10**9, retry_backoff_ms=1,
    )
    _live_engines.append(engine)
    assert engine.enable_coprocessors([(1, spec.to_json(), ("orders",))]) == [
        EnableResponseCode.success
    ]
    big = _req(parts=64, n=32)
    engine.process_batch(big)  # first columnar launch: the backend probe
    got = _domains()
    assert governor.COLUMNAR_BACKEND in got
    assert governor.HARVEST_PATH in got

    # breaker transition through the real data path: a starved harvester
    # forces the caller's MASK_FETCH leg, whose armed fault trips that
    # domain's breaker (threshold 1)
    fault_engine = _engine(
        force_mode="columnar_device", launch_retries=0, breaker_threshold=1,
        device_deadline_ms=200, adaptive_deadline=False,
    )
    monkeypatch.setattr(fault_engine, "_ensure_harvester", lambda: None)
    honey_badger.enable()
    honey_badger.set_exception(faults.MODULE, faults.MASK_FETCH)
    try:
        fault_engine.process_batch(_req())
    finally:
        honey_badger.unset(faults.MODULE, faults.MASK_FETCH)
        honey_badger.disable()
    assert governor.BREAKER in _domains()

    from redpanda_tpu.ops.lz4_device import measure_probe

    measure_probe(n_records=4, record_size=64, reps=1)
    got = _domains()
    assert governor.DEVICE_LZ4 in got
    for domain in (
        governor.COLUMNAR_BACKEND, governor.DEVICE_LZ4,
        governor.BREAKER, governor.HARVEST_PATH,
    ):
        assert domain in got, f"missing journal domain {domain}"

    # every entry is reconstructible: monotonic seq + the full shape
    entries = governor.journal.entries()  # newest first
    seqs = [e["seq"] for e in entries]
    assert seqs == sorted(seqs, reverse=True)
    for e in entries:
        assert e["domain"] and e["verdict"] and e["reason"]
        assert isinstance(e["inputs"], dict)
        assert isinstance(e["config"], dict)
        assert e["ts"] > 0
    # engine-made decisions carry the active-config snapshot
    cal = [e for e in entries if e["domain"] == governor.COLUMNAR_BACKEND][0]
    assert "device_deadline_ms" in cal["config"]
    assert cal["inputs"].get("chosen") in ("host", "device")


def test_journal_bounded_capacity_and_summary():
    j = governor.DecisionJournal(capacity=4)
    for i in range(10):
        j.append("harvest_path", "gather", f"r{i}")
    assert len(j.entries()) == 4
    assert [e["seq"] for e in j.entries()] == [10, 9, 8, 7]
    s = j.summary()
    assert s["entries"] == 4 and s["seq"] == 10 and s["dropped"] == 6
    assert s["by_domain"] == {"harvest_path": {"gather": 4}}
    assert s["capacity"] == 4


def test_record_mode_journals_only_on_change():
    gov = governor.Governor(
        fault_policy=faults.FaultPolicy(), register_gauges=False
    )
    assert gov.record_mode("harvest_path", "gather", "first") is True
    assert gov.record_mode("harvest_path", "gather", "same") is False
    assert gov.record_mode("harvest_path", "padded", "flip") is True
    entries = governor.journal.entries(domain="harvest_path")
    assert [e["verdict"] for e in entries] == ["padded", "gather"]


def test_record_mode_dedupes_per_key_not_per_domain():
    """The harvest-path verdict is per SCRIPT: a mixed gather+padded
    workload (two scripts, alternating launches) journals once per script
    instead of flip-flopping an entry into the ring every launch."""
    gov = governor.Governor(
        fault_policy=faults.FaultPolicy(), register_gauges=False
    )
    for _ in range(5):  # alternating launches of two scripts
        gov.record_mode("harvest_path", "gather", "script 1", key=1)
        gov.record_mode("harvest_path", "padded", "script 2", key=2)
    entries = governor.journal.entries(domain="harvest_path")
    assert len(entries) == 2
    assert {e["verdict"] for e in entries} == {"gather", "padded"}
    # posture reflects the most recent launch
    assert gov.posture()["harvest_path"] == "padded"


def test_scratch_governor_with_journal_override_stays_private():
    """A bench/test governor with an injected journal must not write the
    live process journal or move the decision counters."""
    from redpanda_tpu.metrics import registry

    key = 'coproc_governor_decisions_total{domain="harvest_path",verdict="gather"}'
    before = registry.snapshot().get(key, 0.0)
    private = governor.DecisionJournal(capacity=8)
    gov = governor.Governor(
        fault_policy=faults.FaultPolicy(),
        register_gauges=False,
        journal_override=private,
    )
    gov.record_mode("harvest_path", "gather", "scratch")
    assert governor.journal.entries() == []
    assert len(private.entries()) == 1
    assert registry.snapshot().get(key, 0.0) == before
    assert gov.snapshot()["journal"]["seq"] == 1


def test_breaker_transitions_journal_consistent_pairs():
    """Every journaled breaker transition must be a consistent old->new
    pair captured inside the breaker's critical section — including the
    open->half_open tick that fires inside a snapshot() poll."""
    clock = FakeClock()
    gov = governor.Governor(
        fault_policy=faults.FaultPolicy(),
        breaker_threshold=1,
        breaker_cooldown_s=5.0,
        clock=clock,
        register_gauges=False,
    )
    b = gov.breaker_for(faults.DEVICE_DISPATCH)
    b.record_failure()          # closed -> open
    clock.t += 6.0
    b.snapshot()                # tick inside snapshot: open -> half_open
    assert b.allow_device() is True  # the admitted probe
    b.record_success()          # half_open -> closed
    entries = governor.journal.entries(domain=governor.BREAKER)
    pairs = [(e["inputs"]["from"], e["verdict"]) for e in reversed(entries)]
    assert pairs == [
        ("closed", "open"), ("open", "half_open"), ("half_open", "closed"),
    ]


def test_decision_counters_by_domain_and_verdict():
    from redpanda_tpu.metrics import registry

    gov = governor.Governor(
        fault_policy=faults.FaultPolicy(), register_gauges=False
    )
    key = 'coproc_governor_decisions_total{domain="harvest_path",verdict="gather"}'
    before = registry.snapshot().get(key, 0.0)
    gov.record("harvest_path", "gather", "test")
    gov.record("harvest_path", "gather", "test again")
    assert registry.snapshot()[key] == before + 2


# ------------------------------------------------------------ adaptive deadlines
class FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self) -> float:
        return self.t


def _gov(floor_s=0.05, **kw):
    # the injected source is keyed by FAULT DOMAIN since the deadline
    # moved to the success-only device-leg histograms (one per domain)
    hists = {d: HdrHist() for d in governor.BREAKER_DOMAINS}
    kw.setdefault("deadline_min_samples", 64)
    kw.setdefault("deadline_margin", 4.0)
    gov = governor.Governor(
        fault_policy=faults.FaultPolicy(deadline_s=floor_s, retries=1),
        stage_hist=lambda d: hists[d],
        register_gauges=False,
        clock=FakeClock(),
        **kw,
    )
    return gov, hists


def test_adaptive_deadline_falls_back_to_floor_below_min_samples():
    gov, hists = _gov()
    for _ in range(20):  # < min_samples
        hists[faults.DEVICE_DISPATCH].record(5_000_000)
    assert gov.deadline_s(faults.DEVICE_DISPATCH) == 0.05
    assert gov.policy_for(faults.DEVICE_DISPATCH).deadline_s == 0.05


def test_adaptive_deadline_tracks_observed_p999():
    gov, hists = _gov()
    for _ in range(1000):
        hists[faults.DEVICE_DISPATCH].record(30_000)  # 30ms tail
    d = gov.deadline_s(faults.DEVICE_DISPATCH)
    # margin 4x over a ~30ms p99.9 (log-bucket upper bound <= 19% error):
    # well above the 50ms floor, nowhere near the 8x cap
    assert 0.1 <= d <= 0.2
    assert gov.policy_for(faults.DEVICE_DISPATCH).deadline_s == d
    # the envelope every waiter uses grows with it
    assert gov.policy_for(faults.DEVICE_DISPATCH).envelope_s() > \
        faults.FaultPolicy(deadline_s=0.05, retries=1).envelope_s()
    # and the derivation is journaled with its measured inputs
    (entry,) = governor.journal.entries(domain=governor.DEADLINE)
    assert entry["verdict"] == "raised"
    assert entry["inputs"]["samples"] == 1000
    assert entry["inputs"]["floor_ms"] == 50.0
    assert entry["inputs"]["deadline_ms"] == round(d * 1e3, 3)


def test_adaptive_deadline_never_undercuts_static_floor():
    gov, hists = _gov()
    for _ in range(5000):
        hists[faults.HARVEST].record(10)  # 10us tail: margin * p99.9 << floor
    assert gov.deadline_s(faults.MASK_FETCH) == 0.05
    assert gov.deadline_s(faults.HARVEST) == 0.05
    assert governor.journal.entries(domain=governor.DEADLINE) == []


def test_adaptive_deadline_caps_at_multiple_of_floor():
    gov, hists = _gov()
    for _ in range(1000):
        hists[faults.DEVICE_DISPATCH].record(60_000_000)  # 60s tail (wedge-polluted)
    d = gov.deadline_s(faults.DEVICE_DISPATCH)
    assert d == pytest.approx(8.0 * 0.05)  # deadline_cap_x * floor
    (entry,) = governor.journal.entries(domain=governor.DEADLINE)
    assert entry["verdict"] == "capped"


def test_adaptive_deadline_disabled_pins_static_knob():
    gov, hists = _gov(adaptive_deadline=False)
    for _ in range(1000):
        hists[faults.DEVICE_DISPATCH].record(30_000_000)
    assert gov.deadline_s(faults.DEVICE_DISPATCH) == 0.05


def test_envelope_bound_tracks_max_issued_deadline():
    """Waiters (_resolve_keep) size off the envelope bound — the max
    deadline ever ISSUED, not the 8x cap: with no adaptive raise it is
    exactly the static envelope (no order-of-magnitude wait inflation),
    and after a raise it monotonically covers every deadline the
    harvester could be running under."""
    static_env = faults.FaultPolicy(deadline_s=0.05, retries=1).envelope_s()
    gov, hists = _gov()
    assert gov.envelope_bound_s(faults.HARVEST) == pytest.approx(static_env)
    for _ in range(1000):
        hists[faults.HARVEST].record(60_000_000)  # raise to the cap
    raised_env = gov.policy_for(faults.HARVEST).envelope_s()
    assert raised_env > static_env
    bound = gov.envelope_bound_s(faults.HARVEST)
    assert bound >= raised_env
    # monotonic: a later derivation dropping back toward the floor never
    # shrinks the bound below a deadline that was already handed out
    for _ in range(5000):
        hists[faults.HARVEST].record(10)
    gov.policy_for(faults.HARVEST)
    assert gov.envelope_bound_s(faults.HARVEST) == bound
    # the pacemaker backstop derives from the same bounds
    assert gov.max_envelope_s() >= bound
    # adaptive off: bound is the static envelope, always
    gov2, _ = _gov(adaptive_deadline=False)
    assert gov2.envelope_bound_s(faults.HARVEST) == pytest.approx(static_env)


def test_adaptive_raise_grows_breaker_probe_timeout():
    """A half-open probe runs under the raised adaptive envelope; the
    stale-probe release must keep outwaiting it or a slow probe gets a
    second probe stacked onto the same struggling device."""
    gov, hists = _gov()
    b = gov.breaker_for(faults.HARVEST)
    before = b.probe_timeout_s
    for _ in range(1000):
        hists[faults.HARVEST].record(60_000_000)  # raise toward the cap
    assert gov.policy_for(faults.HARVEST).envelope_s() > 0
    assert b.probe_timeout_s >= 2.0 * gov.policy_for(faults.HARVEST).envelope_s()
    assert b.probe_timeout_s >= before


def test_deadline_source_ignores_timeout_inflated_stage_histogram():
    """ISSUE 9 satellite (ROADMAP item 5 follow-on): the adaptive
    deadline derives from the SUCCESS-ONLY device-leg histogram, not the
    fetch-stage coproc_stage_latency_us — whose clock keeps running
    through abandoned attempts and envelope waits, so a burst of
    timeouts used to inflate the very tail the next deadline derived
    from. Injected timeout-inflated stage samples must leave the
    deadline at the floor; successful legs raise it; the 8x cap stays."""
    from redpanda_tpu.observability import probes

    # wiring: the DEFAULT source is the per-domain device-leg histogram,
    # not the fetch/dispatch stage histograms (asserted on the resolved
    # objects so the claim survives whatever other tests recorded into
    # the process-global series)
    gov = governor.Governor(
        fault_policy=faults.FaultPolicy(deadline_s=0.05, retries=1),
        register_gauges=False,
        journal_override=governor.DecisionJournal(),
    )
    for domain in governor.BREAKER_DOMAINS:
        src = gov._stage_hist(domain)
        assert src is probes.coproc_device_leg_hist(domain).hist
        assert src is not probes.coproc_stage_hist("fetch").hist
        assert src is not probes.coproc_stage_hist("dispatch").hist

    # behavior, on an injected source: timeout-scale samples landing in
    # the STAGE histograms move nothing (they are simply not consulted)...
    gov2, hists = _gov()
    stage_fetch = probes.coproc_stage_hist("fetch").hist
    stage_dispatch = probes.coproc_stage_hist("dispatch").hist
    for _ in range(1000):
        stage_fetch.record(60_000_000)     # 60s abandoned-wait artifacts
        stage_dispatch.record(60_000_000)
    assert gov2.deadline_s(faults.MASK_FETCH) == 0.05
    assert gov2.deadline_s(faults.HARVEST) == 0.05
    assert gov2.deadline_s(faults.DEVICE_DISPATCH) == 0.05

    # ...while successful legs ARE the source: observe_leg records into
    # the same histogram the derivation reads (closed loop)
    for _ in range(1000):
        gov2.observe_leg(faults.MASK_FETCH, 0.030)  # healthy 30ms legs
    assert hists[faults.MASK_FETCH].count == 1000
    d = gov2.deadline_s(faults.MASK_FETCH)
    assert 0.1 <= d <= 0.2  # margin 4x over ~30ms, above the 50ms floor
    # the 8x-of-floor cap survives the source change
    for _ in range(2000):
        gov2.observe_leg(faults.MASK_FETCH, 60.0)
    assert gov2.deadline_s(faults.MASK_FETCH) == pytest.approx(8.0 * 0.05)


def test_engine_device_legs_feed_success_only_histogram():
    """A real device-leg success records exactly one sample into the
    domain's device-leg histogram; an injected failure records none, and
    neither does a program's first run (trace + compile is not a
    steady-state leg: it is stats()["t_compile"])."""
    from redpanda_tpu.observability import probes

    engine = _engine(
        force_mode="columnar_device", launch_retries=0,
        device_deadline_ms=10_000, adaptive_deadline=False,
    )
    hist = probes.coproc_device_leg_hist(faults.DEVICE_DISPATCH).hist
    before = hist.count
    engine.process_batch(_req())
    assert hist.count == before
    assert engine.stats()["n_compiles"] == 1.0
    engine.process_batch(_req())
    after_success = hist.count
    assert after_success > before
    honey_badger.enable()
    honey_badger.set_exception(faults.MODULE, faults.DEVICE_DISPATCH)
    try:
        engine.process_batch(_req())
    finally:
        honey_badger.unset(faults.MODULE, faults.DEVICE_DISPATCH)
        honey_badger.disable()
    # the faulted leg raised before completing: no new success sample
    assert hist.count == after_success


def test_adaptive_deadline_recomputes_after_new_samples():
    gov, hists = _gov()
    for _ in range(1000):
        hists[faults.DEVICE_DISPATCH].record(30_000)
    d1 = gov.deadline_s(faults.DEVICE_DISPATCH)
    # fewer than DEADLINE_RECOMPUTE_SAMPLES new observations: cached
    for _ in range(governor.DEADLINE_RECOMPUTE_SAMPLES - 1):
        hists[faults.DEVICE_DISPATCH].record(300_000)
    assert gov.deadline_s(faults.DEVICE_DISPATCH) == d1
    # enough new tail mass shifts p99.9 up and the deadline follows
    for _ in range(1000):
        hists[faults.DEVICE_DISPATCH].record(80_000)
    d2 = gov.deadline_s(faults.DEVICE_DISPATCH)
    assert d2 > d1


# ------------------------------------------------------------ per-domain breakers
def test_mask_fetch_breaker_isolates_dispatch_domain(monkeypatch):
    """A flaky D2H mask-fetch path trips ONLY the mask_fetch breaker:
    fetches demote to the exact numpy fallback while dispatch keeps
    landing on the device — the split the one-breaker engine couldn't do."""
    TpuEngine.reset_columnar_probe()
    baseline = _engine(force_mode="columnar_device").process_batch(_req())
    engine = _engine(
        force_mode="columnar_device", launch_retries=0, breaker_threshold=1,
        device_deadline_ms=200, adaptive_deadline=False,
        breaker_cooldown_ms=3_600_000,
    )
    # harvester never runs: the caller claims its queued mask and pays the
    # MASK_FETCH leg itself (the domain under test)
    monkeypatch.setattr(engine, "_ensure_harvester", lambda: None)
    honey_badger.enable()
    honey_badger.set_exception(faults.MODULE, faults.MASK_FETCH)
    try:
        faulted = engine.process_batch(_req())
    finally:
        honey_badger.unset(faults.MODULE, faults.MASK_FETCH)
        honey_badger.disable()
    assert _payloads(faulted) == _payloads(baseline), "fallback must be exact"
    gov = engine.governor
    assert gov.breaker_for(faults.MASK_FETCH).state == faults.STATE_OPEN
    assert gov.breaker_for(faults.DEVICE_DISPATCH).state == faults.STATE_CLOSED
    assert gov.breaker_for(faults.HARVEST).state == faults.STATE_CLOSED
    # engine-level rollup reports the worst domain
    assert engine.stats()["breaker"]["state"] == faults.STATE_OPEN

    # fault long gone, fetch domain still open: dispatch KEEPS using the
    # device (h2d bytes grow) while the open fetch domain goes straight to
    # the exact fallback (fallback rows grow) — no retry envelope burned
    h2d0 = engine.stats().get("bytes_h2d", 0.0)
    fb0 = engine.stats().get("n_fallback_rows", 0.0)
    retries0 = engine.stats().get("n_retries", 0.0)
    demoted = engine.process_batch(_req())
    assert _payloads(demoted) == _payloads(baseline)
    stats = engine.stats()
    assert stats.get("bytes_h2d", 0.0) > h2d0, "dispatch must stay on-device"
    assert stats.get("n_fallback_rows", 0.0) > fb0
    assert stats.get("n_retries", 0.0) == retries0, (
        "an open fetch breaker skips the doomed retry envelope"
    )
    # the trip is in the journal with the transition spelled out
    trips = [
        e for e in governor.journal.entries(domain=governor.BREAKER)
        if e["verdict"] == faults.STATE_OPEN
    ]
    assert trips and trips[0]["inputs"]["breaker"] == faults.MASK_FETCH


def test_open_harvest_breaker_skips_fetch_and_falls_back():
    """With the HARVEST domain open, the harvester must not burn an
    envelope per mask: it skips the fetch and callers take the exact
    fallback over the retained columns."""
    TpuEngine.reset_columnar_probe()
    baseline = _engine(force_mode="columnar_device").process_batch(_req())
    engine = _engine(
        force_mode="columnar_device", breaker_threshold=1,
        breaker_cooldown_ms=3_600_000, adaptive_deadline=False,
    )
    engine.governor.breaker_for(faults.HARVEST).record_failure()  # trip
    retries0 = engine.stats().get("n_retries", 0.0)
    reply = engine.process_batch(_req())
    assert _payloads(reply) == _payloads(baseline)
    stats = engine.stats()
    assert stats.get("n_fallback_rows", 0.0) > 0
    assert stats.get("n_retries", 0.0) == retries0
    assert engine.governor.breaker_for(faults.DEVICE_DISPATCH).state == \
        faults.STATE_CLOSED


def test_stats_carries_governor_and_per_domain_breakers():
    engine = _engine(force_mode="columnar_host")
    engine.process_batch(_req())
    stats = engine.stats()
    assert set(stats["breakers"]) == set(governor.BREAKER_DOMAINS)
    snap = stats["governor"]
    assert snap["posture"]["harvest_path"] in ("gather", "padded")
    assert set(snap["posture"]["deadlines_ms"]) == set(governor.BREAKER_DOMAINS)
    assert snap["journal"]["seq"] >= 1
    # aggregate keeps the historical shape
    assert set(stats["breaker"]) == {
        "state", "consecutive_failures", "trips", "threshold", "cooldown_ms",
    }


def test_governor_deadline_gauges_registered():
    from redpanda_tpu.metrics import registry

    engine = _engine(adaptive_deadline=False, device_deadline_ms=1234)
    snap = registry.snapshot()
    for domain in governor.BREAKER_DOMAINS:
        assert snap[f'coproc_governor_deadline_ms{{domain="{domain}"}}'] == 1234.0
    # posture gauges exist per mode-domain, -1 while undecided
    assert f'coproc_governor_state{{domain="harvest_path"}}' in snap


# ------------------------------------------------------------ admin surface
def test_admin_governor_endpoint(tmp_path):
    import asyncio

    import aiohttp

    from redpanda_tpu.admin import AdminServer
    from redpanda_tpu.kafka.server.broker import Broker, BrokerConfig
    from redpanda_tpu.storage.log_manager import StorageApi

    async def main():
        storage = await StorageApi(str(tmp_path)).start()
        broker = Broker(BrokerConfig(data_dir=str(tmp_path)), storage)
        admin = await AdminServer(broker, port=0).start()
        base = f"http://127.0.0.1:{admin.port}"
        try:
            async with aiohttp.ClientSession() as s:
                # journal is process-wide even without a live engine
                governor.journal_record(
                    governor.DEVICE_LZ4, "host", "test probe", {"x": 1}
                )
                body = await (await s.get(f"{base}/v1/governor")).json()
                assert body["enabled"] is False
                assert body["summary"]["seq"] >= 1
                assert any(
                    e["domain"] == "device_lz4" for e in body["journal"]
                )

                engine = _engine(force_mode="columnar_host")
                engine.process_batch(_req())

                class _FakeApi:
                    @staticmethod
                    def active_scripts():
                        return ["demo"]

                _FakeApi.engine = engine
                broker.coproc_api = _FakeApi()
                body = await (await s.get(f"{base}/v1/governor")).json()
                assert body["enabled"] is True
                # the projection spec mutates bytes: honest padded verdict
                assert body["posture"]["harvest_path"] == "padded"
                assert set(body["posture"]["breakers"]) == set(
                    governor.BREAKER_DOMAINS
                )
                assert body["breaker"]["state"] == "closed"
                # domain filter + limit + unknown-domain 404
                body = await (
                    await s.get(f"{base}/v1/governor?domain=harvest_path&limit=1")
                ).json()
                assert len(body["journal"]) == 1
                assert body["journal"][0]["domain"] == "harvest_path"
                r = await s.get(f"{base}/v1/governor?domain=nope")
                assert r.status == 404
                r = await s.get(f"{base}/v1/governor?limit=bogus")
                assert r.status == 400
        finally:
            await admin.stop()
            await storage.stop()

    asyncio.run(main())


# ------------------------------------------------------------ owner trace
def test_replicate_batcher_samples_owner_trace(tmp_path):
    """The replicate batcher's rpc sends run detached by span-hygiene
    design; ONE submitter's trace per flush round is sampled as the owner
    trace and consumed by the next append_entries send, so an rpc.send SLO
    breach on the replicate path resolves to a real trace."""
    from test_raft import RaftGroupFixture, data_batch, run
    from redpanda_tpu.raft import ConsistencyLevel
    from redpanda_tpu.observability import tracer

    async def main():
        fx = await RaftGroupFixture(tmp_path, 3).start()
        try:
            leader = (await fx.wait_for_stable_leader()).consensus()
            was = tracer.enabled
            tracer.configure(enabled=True)
            tracer.reset()
            try:
                with tracer.span("test.produce", root=True) as root:
                    await leader.replicate(
                        [data_batch(b"own")], ConsistencyLevel.quorum_ack
                    )
                spans = [
                    s for t in tracer.recent(0) for s in t["spans"]
                ]
                sends = [
                    s for s in spans if s["name"] == "raft.append_entries.send"
                ]
                assert sends, "owner-trace send span must exist"
                assert any(s["trace_id"] == root.trace_id for s in sends), (
                    "one send of the flush round must join the submitter's "
                    "trace"
                )
            finally:
                tracer.configure(enabled=was)
        finally:
            await fx.stop()

    run(main())
