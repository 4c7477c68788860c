"""Coproc fault-domain unit tests (ISSUE 4).

Covers the policy layer in coproc/faults.py from four sides:

- CircuitBreaker state machine: trip threshold, half-open single-probe
  admission, cooldown timing — all against an injected fake clock, so no
  test ever sleeps through a cooldown;
- deadline/retry envelope: fetch_with_deadline abandonment, the
  no-thread-growth regression for late-completing fetches (the wedge-probe
  leak fix), retry_call bounds and programming-error passthrough;
- classified failure accounting: warn-once logging + the
  coproc_failures_total counter;
- engine integration: exhausted device retries fail closed per-launch onto
  the exact host path, an open breaker demotes the engine, a half-open
  probe re-admits it — plus the admin failure-probe round trip and
  /v1/coproc/status.
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
from redpanda_tpu.coproc.engine import ProcessBatchItem
from redpanda_tpu.finjector import ProbeTriggered, honey_badger
from redpanda_tpu.models import NTP, Record, RecordBatch
from redpanda_tpu.observability import probes
from redpanda_tpu.ops.exprs import field
from redpanda_tpu.ops.transforms import Int, Str, filter_contains, map_project, where


_live_engines: list[TpuEngine] = []


@pytest.fixture(autouse=True)
def _quiet_badger():
    """Every test starts and ends with a disarmed, disabled honey badger
    (it is process-global) and a fast wedge cap; engines the test created
    are shut down so their harvester threads don't pin them for the rest
    of the suite."""
    saved_wedge = honey_badger.wedge_max_s
    saved_delay = honey_badger.delay_ms
    yield
    for module, armed in list(honey_badger.armed().items()):
        for probe in armed:
            honey_badger.unset(module, probe)
    honey_badger.disable()
    honey_badger.wedge_max_s = saved_wedge
    honey_badger.delay_ms = saved_delay
    while _live_engines:
        _live_engines.pop().shutdown()


# ------------------------------------------------------------ circuit breaker
class FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self) -> float:
        return self.t


def test_breaker_trips_at_threshold_not_before():
    clk = FakeClock()
    b = faults.CircuitBreaker(threshold=3, cooldown_s=30.0, clock=clk)
    assert b.state == faults.STATE_CLOSED
    b.record_failure()
    b.record_failure()
    assert b.state == faults.STATE_CLOSED and b.allow_device()
    b.record_failure()
    assert b.state == faults.STATE_OPEN
    assert not b.allow_device()
    assert b.trips == 1


def test_breaker_success_resets_consecutive_count():
    clk = FakeClock()
    b = faults.CircuitBreaker(threshold=2, cooldown_s=30.0, clock=clk)
    # failures interleaved with successes never accumulate to the threshold
    for _ in range(5):
        b.record_failure()
        b.record_success()
    assert b.state == faults.STATE_CLOSED and b.trips == 0


def test_breaker_half_open_admits_exactly_one_probe():
    clk = FakeClock()
    b = faults.CircuitBreaker(threshold=1, cooldown_s=10.0, clock=clk)
    b.record_failure()
    assert b.state == faults.STATE_OPEN
    clk.t += 9.9
    assert not b.allow_device(), "cooldown not elapsed yet"
    clk.t += 0.2
    assert b.state == faults.STATE_HALF_OPEN
    assert b.allow_device(), "first caller is the probe"
    assert not b.allow_device(), "second caller must wait for the verdict"
    b.record_success()
    assert b.state == faults.STATE_CLOSED
    assert b.allow_device() and b.allow_device(), "closed admits everyone"


def test_breaker_failed_probe_reopens_and_recools():
    clk = FakeClock()
    b = faults.CircuitBreaker(threshold=1, cooldown_s=10.0, clock=clk)
    b.record_failure()
    clk.t += 10.1
    assert b.allow_device()  # the half-open probe
    b.record_failure()
    assert b.state == faults.STATE_OPEN and b.trips == 2
    assert not b.allow_device(), "a failed probe restarts the cooldown"
    clk.t += 10.1
    assert b.allow_device(), "and a fresh cooldown re-admits one probe"


def test_breaker_stale_probe_releases_after_cooldown():
    """A launch admitted as the half-open probe can exit without ever
    touching the device (e.g. a host-side shard fault degrades it) — no
    verdict is a valid outcome. The probe slot must free itself after a
    cooldown or the breaker wedges in half_open and the engine stays
    demoted until restart."""
    clk = FakeClock()
    b = faults.CircuitBreaker(
        threshold=1, cooldown_s=10.0, clock=clk, probe_timeout_s=25.0
    )
    b.record_failure()
    clk.t += 10.1
    assert b.allow_device(), "probe admitted"
    assert not b.allow_device(), "slot taken"
    # a probe legitimately mid-envelope must NOT be declared stale: the
    # timeout is sized ABOVE the retry envelope, not the cooldown
    clk.t += 24.9
    assert not b.allow_device()
    # ...past the probe timeout the stale slot frees and the NEXT launch
    # becomes the probe
    clk.t += 0.2
    assert b.state == faults.STATE_HALF_OPEN
    assert b.allow_device(), "stale probe released, new probe admitted"
    b.record_success()
    assert b.state == faults.STATE_CLOSED


def test_policy_envelope_bounds_every_waiter():
    p = faults.FaultPolicy(deadline_s=2.0, retries=2, backoff_s=0.1, backoff_cap_s=0.15)
    # 3 attempts x 2s + backoffs (0.1 then capped 0.15)
    assert p.envelope_s() == pytest.approx(6.25)
    # the engine sizes the stale-probe release above the envelope
    engine = _engine(device_deadline_ms=2000, launch_retries=2)
    assert engine._breaker.probe_timeout_s >= 2 * engine._fault_policy.envelope_s()


def test_breaker_snapshot_shape():
    b = faults.CircuitBreaker(threshold=4, cooldown_s=1.5)
    b.record_failure()
    snap = b.snapshot()
    assert snap == {
        "state": "closed",
        "consecutive_failures": 1,
        "trips": 0,
        "threshold": 4,
        "cooldown_ms": 1500,
    }


# ------------------------------------------------------------ fault policy
def test_backoff_is_bounded_and_jittered():
    p = faults.FaultPolicy(deadline_s=1.0, retries=5, backoff_s=0.1, backoff_cap_s=0.5)
    for attempt in range(6):
        step = min(0.5, 0.1 * (2 ** attempt))
        for _ in range(20):
            d = p.backoff(attempt)
            assert step * 0.5 <= d <= step
    # jitter actually varies (not a constant)
    assert len({round(p.backoff(0), 6) for _ in range(20)}) > 1


def test_retry_call_retries_then_returns():
    calls = []
    counted = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise RuntimeError("blip")
        return "ok"

    policy = faults.FaultPolicy(deadline_s=1.0, retries=2, backoff_s=0.001)
    out = faults.retry_call(
        flaky, policy, "test", count=lambda k, v: counted.append((k, v))
    )
    assert out == "ok" and len(calls) == 3
    assert counted == [("n_retries", 1.0), ("n_retries", 1.0)]


def test_retry_call_exhaustion_raises_last_error():
    policy = faults.FaultPolicy(deadline_s=1.0, retries=1, backoff_s=0.001)
    with pytest.raises(KeyError):
        faults.retry_call(
            lambda: (_ for _ in ()).throw(KeyError("gone")), policy, "test"
        )


def test_retry_call_programming_errors_never_retry():
    calls = []

    def buggy():
        calls.append(1)
        raise AssertionError("engine bug")

    policy = faults.FaultPolicy(deadline_s=1.0, retries=3, backoff_s=0.001)
    with pytest.raises(AssertionError):
        faults.retry_call(buggy, policy, "test")
    assert len(calls) == 1, "a bug in our code must not be retried away"


# ------------------------------------------------- abandonable fetch workers
def test_fetch_with_deadline_result_and_exception():
    assert faults.fetch_with_deadline(lambda: 41 + 1, 5.0) == 42
    with pytest.raises(ValueError):
        faults.fetch_with_deadline(
            lambda: (_ for _ in ()).throw(ValueError("x")), 5.0
        )
    # None deadline runs inline on the caller thread
    tid = faults.fetch_with_deadline(threading.get_ident, None)
    assert tid == threading.get_ident()


def test_fetch_deadline_abandons_wedged_fn():
    release = threading.Event()
    t0 = time.perf_counter()
    with pytest.raises(faults.DeadlineExceeded):
        faults.fetch_with_deadline(lambda: release.wait(10.0), 0.05)
    assert time.perf_counter() - t0 < 5.0, "caller must not wait out the wedge"
    release.set()  # unwedge so the worker rejoins the pool


def test_late_completion_reclaims_worker_no_thread_growth():
    """The wedge-probe leak regression (ISSUE 4 satellite): a fetch that
    completes AFTER its caller timed out must discard the stale result and
    return its worker to the free pool — repeated timeouts may not grow
    the thread count."""
    before = faults.fetch_pool_stats()["created"]
    for i in range(5):
        done = threading.Event()

        def late(i=i, done=done):
            time.sleep(0.08)  # completes late, but completes
            done.set()
            return f"stale-{i}"

        with pytest.raises(faults.DeadlineExceeded):
            faults.fetch_with_deadline(late, 0.01)
        assert done.wait(5.0), "late fn must still have run to completion"
        # give the worker a beat to re-enter the free list
        deadline = time.time() + 5.0
        while time.time() < deadline:
            if faults.fetch_pool_stats()["free"] > 0:
                break
            time.sleep(0.005)
        # a fresh fetch REUSES the reclaimed worker and sees no stale result
        assert faults.fetch_with_deadline(lambda: "fresh", 5.0) == "fresh"
    grown = faults.fetch_pool_stats()["created"] - before
    assert grown <= 1, f"late completions grew the pool by {grown} threads"


# ------------------------------------------------------ failure accounting
def test_note_failure_counts_and_warns_once(caplog):
    faults.reset_warned()
    ctr = probes.coproc_failure_counter("test_domain", "RuntimeError")
    v0 = ctr.value
    with caplog.at_level("WARNING", logger="rptpu.coproc.faults"):
        faults.note_failure("test_domain", RuntimeError("a"))
        faults.note_failure("test_domain", RuntimeError("b"))
    warnings = [r for r in caplog.records if r.levelname == "WARNING"]
    assert len(warnings) == 1, "repeats must log at DEBUG, not WARNING"
    assert ctr.value == v0 + 2, "but the counter must see every failure"


def test_note_failure_classifies_kinds():
    assert faults.kind_of(faults.DeadlineExceeded("x")) == "deadline"
    assert faults.kind_of(ProbeTriggered("m.p")) == "injected"
    assert faults.kind_of(ValueError("x")) == "ValueError"


def test_note_failure_reraises_programming_errors():
    faults.reset_warned()
    with pytest.raises(AssertionError):
        faults.note_failure(
            "test_domain", AssertionError("bug"), reraise_programming=True
        )
    # counted anyway: the counter must not lose re-raised bugs
    assert probes.coproc_failure_counter("test_domain", "AssertionError").value >= 1
    # default posture (user-code boundary): swallowed
    faults.note_failure("test_domain", AssertionError("user bug"))


# ------------------------------------------------------ engine integration
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


def _req(parts=4, n=12):
    return ProcessBatchRequest(
        [
            ProcessBatchItem(1, NTP.kafka("orders", p), [_json_batch(n, 100 * p)])
            for p in range(parts)
        ]
    )


def _engine(**kw):
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


def _payloads(reply):
    return [
        (item.source, [(b.payload, b.header.crc, b.header.record_count) for b in item.batches])
        for item in reply.items
    ]


def test_exhausted_dispatch_retries_fail_closed_onto_host_path():
    baseline = _engine(force_mode="columnar_device").process_batch(_req())
    engine = _engine(
        force_mode="columnar_device", launch_retries=1, breaker_threshold=100
    )
    honey_badger.enable()
    honey_badger.set_exception(faults.MODULE, faults.DEVICE_DISPATCH)
    try:
        faulted = engine.process_batch(_req())
    finally:
        honey_badger.unset(faults.MODULE, faults.DEVICE_DISPATCH)
        honey_badger.disable()
    assert _payloads(faulted) == _payloads(baseline), "fallback must be exact"
    stats = engine.stats()
    assert stats["n_fallback_rows"] > 0
    assert stats["n_retries"] >= 1
    assert stats["breaker"]["consecutive_failures"] >= 1


def test_open_breaker_demotes_engine_and_half_open_recloses():
    baseline = _engine(force_mode="columnar_device").process_batch(_req())
    engine = _engine(
        force_mode="columnar_device",
        launch_retries=0,
        breaker_threshold=1,
        # must outlast the tripped run's tail (host re-eval + framing), or
        # the "demoted" run below races into a surprise half-open probe
        breaker_cooldown_ms=400,
    )
    honey_badger.enable()
    honey_badger.set_exception(faults.MODULE, faults.DEVICE_DISPATCH)
    try:
        tripped = engine.process_batch(_req())
    finally:
        honey_badger.unset(faults.MODULE, faults.DEVICE_DISPATCH)
        honey_badger.disable()
    assert engine.stats()["breaker"]["state"] == faults.STATE_OPEN
    assert engine.stats()["breaker"]["trips"] >= 1
    assert _payloads(tripped) == _payloads(baseline)
    # while open (fault long gone), launches stay on the exact host path
    fb0 = engine.stats()["n_fallback_rows"]
    demoted = engine.process_batch(_req())
    assert _payloads(demoted) == _payloads(baseline)
    assert engine.stats()["n_fallback_rows"] > fb0
    # after the cooldown one half-open probe re-admits the device
    time.sleep(0.45)
    reprobed = engine.process_batch(_req())
    assert _payloads(reprobed) == _payloads(baseline)
    assert engine.stats()["breaker"]["state"] == faults.STATE_CLOSED


def test_harvester_failure_counts_once_not_twice():
    """When the harvester has ALREADY run the full retry envelope and
    failed, _resolve_keep must go straight to the exact host fallback —
    re-fetching the same dead mask would double the breaker failures
    (tripping at half the configured threshold) and double the retries."""
    baseline = _engine(force_mode="columnar_device").process_batch(_req())
    engine = _engine(
        force_mode="columnar_device", launch_retries=1, breaker_threshold=100
    )
    honey_badger.enable()
    honey_badger.set_exception(faults.MODULE, faults.HARVEST)
    try:
        faulted = engine.process_batch(_req())  # one fused launch
    finally:
        honey_badger.unset(faults.MODULE, faults.HARVEST)
        honey_badger.disable()
    assert _payloads(faulted) == _payloads(baseline)
    snap = engine.stats()
    assert snap["breaker"]["consecutive_failures"] == 1, (
        "one failed mask must be ONE breaker failure (harvester's), not "
        "harvester + caller re-fetch"
    )
    assert snap["n_retries"] == 1, "only the harvester's envelope retries"
    assert snap["n_fallback_rows"] > 0


def test_starved_harvester_caller_pays_fetch_with_exact_fallback(monkeypatch):
    """If the harvester THREAD never answers (starved / queued behind a
    wedged harvest — beyond even its own retry envelope), the caller pays
    the D2H itself; with that fetch also dead (armed MASK_FETCH), the
    exact numpy fallback over the retained columns produces the bits."""
    baseline = _engine(force_mode="columnar_device").process_batch(_req())
    engine = _engine(
        force_mode="columnar_device", launch_retries=0,
        device_deadline_ms=100, breaker_threshold=100,
    )
    # the harvester never runs: dispatch enqueues, nothing ever harvests
    monkeypatch.setattr(engine, "_ensure_harvester", lambda: None)
    honey_badger.enable()
    honey_badger.set_exception(faults.MODULE, faults.MASK_FETCH)
    try:
        faulted = engine.process_batch(_req())
    finally:
        honey_badger.unset(faults.MODULE, faults.MASK_FETCH)
        honey_badger.disable()
    assert _payloads(faulted) == _payloads(baseline)
    snap = engine.stats()
    assert snap["n_fallback_rows"] > 0
    assert snap["breaker"]["consecutive_failures"] >= 1, "caller's verdict"


def _mesh_shard_fault_under_open_breaker(engine):
    # the mesh ladder faults -> the launch degrades to the single-device
    # road, whose open dispatch breaker demotes it to the numpy predicate
    engine._breaker.record_failure()
    return faults.SHARD_WORKER


def _mesh_leg_exhausted(engine):
    # the SPMD leg fails -> this launch's shards evaluate in numpy
    return faults.MESH_DISPATCH


@pytest.mark.parametrize(
    "arm", [_mesh_shard_fault_under_open_breaker, _mesh_leg_exhausted],
    ids=["shard_fault_under_open_breaker", "mesh_leg_exhausted"],
)
def test_mesh_demotion_counts_fallback_once(arm, eight_devices):
    """A mesh launch that loses its device stage, by whichever hop, emits
    the reference bytes and counts its fallback rows ONCE — not once per
    degradation hop."""
    baseline = _engine(force_mode="columnar_device").process_batch(
        _req(parts=4, n=12)
    )
    engine = _engine(
        force_mode="columnar_device", host_workers=2, mesh_devices=2,
        mesh_backend="cpu", mesh_probe=False, launch_retries=0,
        breaker_threshold=1, breaker_cooldown_ms=3_600_000,
    )
    domain = arm(engine)
    honey_badger.enable()
    honey_badger.set_exception(faults.MODULE, domain)
    try:
        reply = engine.process_batch(_req(parts=4, n=12))  # 48 rows, 1 launch
    finally:
        honey_badger.unset(faults.MODULE, domain)
        honey_badger.disable()
    assert _payloads(reply) == _payloads(baseline)
    stats = engine.stats()
    assert stats["n_launches"] == 1 and stats.get("n_mesh_launches", 0) == 0
    assert stats["n_fallback_rows"] == 48.0, (
        "same records counted once, not per degradation hop"
    )


def test_queued_mask_claim_single_fetch_single_verdict():
    """A caller whose mask is still QUEUED when its wait expires (single
    harvester busy on an earlier wedged mask) claims the slot and fetches
    itself; the harvester must then skip the claimed slot — one envelope,
    one verdict, at any harvest-queue depth."""
    import time as _t

    from redpanda_tpu.coproc.engine import _Launch

    engine = _engine(force_mode="columnar_device", device_deadline_ms=100,
                     launch_retries=0)
    expected = np.array([1, 0, 1, 1, 0, 0, 1, 0], dtype=bool)
    slot = _Launch(1, None)
    slot._mask_dev = np.packbits(expected)
    slot._mask_event = threading.Event()  # never set: harvester never ran
    slot._mask_state = "queued"
    launch = _Launch(1, None)
    launch.engine = engine
    v0 = engine._breaker.snapshot()["consecutive_failures"]
    keep = launch._resolve_keep(slot, 8)
    np.testing.assert_array_equal(keep, expected)
    assert slot._mask_state == "claimed"
    assert engine._breaker.snapshot()["consecutive_failures"] == v0, (
        "a successful claimed fetch is a success verdict, not a failure"
    )
    # the harvester skips a claimed slot entirely: no fetch, no verdict
    class Bomb:
        def __array__(self, *a, **k):
            raise RuntimeError("orphan mask must never be fetched")

    skipped = _Launch(1, None)
    skipped._mask_dev = Bomb()
    skipped._mask_event = threading.Event()
    skipped._mask_state = "claimed"
    probe = _Launch(1, None)
    probe._mask_dev = np.packbits(expected)
    probe._mask_event = threading.Event()
    probe._mask_state = "queued"
    engine._ensure_harvester()
    engine._harvest_q.put(skipped)
    engine._harvest_q.put(probe)
    assert probe._mask_event.wait(10.0), "harvester must reach the probe"
    assert not skipped._mask_event.is_set(), "claimed slot skipped untouched"
    assert engine._breaker.snapshot()["consecutive_failures"] == v0


def test_harvester_programming_error_counted_but_no_breaker_verdict():
    """A bug in our own harvest code (AssertionError et al.) must be
    visible in coproc_failures_total but must NOT demote the engine:
    tripping the breaker on a programming error would silently mask the
    bug as 'device degraded' until process restart."""
    import time as _t

    from redpanda_tpu.coproc.engine import _Launch

    engine = _engine(force_mode="columnar_device", breaker_threshold=1)
    engine._ensure_harvester()

    class Bomb:
        def __array__(self, *a, **k):
            raise AssertionError("engine bug, not a device fault")

    slot = _Launch(1, None)
    slot._mask_dev = Bomb()
    slot._mask_event = threading.Event()
    slot._enq_t = _t.perf_counter()
    ctr = probes.coproc_failure_counter(faults.HARVEST, "AssertionError")
    v0 = ctr.value
    engine._harvest_q.put(slot)
    assert slot._mask_event.wait(10.0), "harvester must survive the bug"
    assert slot._mask_np is None
    assert ctr.value == v0 + 1, "the bug must be counted"
    assert engine._breaker.snapshot()["state"] == faults.STATE_CLOSED, (
        "a programming error is not a device verdict"
    )
    assert engine._harvester.is_alive()


def test_engine_shutdown_stops_harvester_and_is_idempotent():
    engine = _engine(force_mode="columnar_device")
    engine.process_batch(_req())  # spawns the harvester
    t = engine._harvester
    assert t is not None and t.is_alive()
    engine.shutdown()
    t.join(timeout=5.0)
    assert not t.is_alive(), "sentinel must stop the harvester thread"
    assert engine._harvester is None
    engine.shutdown()  # idempotent


def test_breaker_state_gauge_is_per_domain_labeled():
    """The governor owns per-domain labeled breaker gauges (the old single
    weakref-to-latest-engine gauge reported a stale engine's state after
    restarts): a dispatch trip must move ONLY the dispatch series."""
    from redpanda_tpu.metrics import registry

    def gauge(domain):
        return registry.snapshot()[f'coproc_breaker_state{{domain="{domain}"}}']

    engine = _engine(breaker_threshold=1)
    assert gauge("device_dispatch") == faults.STATE_NUM[faults.STATE_CLOSED]
    engine._breaker.record_failure()
    assert gauge("device_dispatch") == faults.STATE_NUM[faults.STATE_OPEN]
    # per-domain isolation: fetch/harvest domains stay closed
    assert gauge("mask_fetch") == faults.STATE_NUM[faults.STATE_CLOSED]
    assert gauge("harvest") == faults.STATE_NUM[faults.STATE_CLOSED]


def test_payload_mode_dispatch_fault_exact_fallback(monkeypatch):
    """The payload lane's fallback is the same integer program in numpy:
    exact, and with no JAX backend under it (a process started with
    JAX_PLATFORMS=tpu has no CPU backend to fall back to)."""
    import jax

    spec = filter_contains(b"error")

    def mk(**kw):
        engine = TpuEngine(
            row_stride=256, compress_threshold=10**9, host_workers=0,
            retry_backoff_ms=1, **kw
        )
        _live_engines.append(engine)
        codes = engine.enable_coprocessors([(1, spec.to_json(), ("orders",))])
        assert codes == [EnableResponseCode.success]
        return engine

    baseline = mk().process_batch(_req())
    engine = mk(launch_retries=0, breaker_threshold=100)

    def no_backend(*a, **k):
        raise RuntimeError("Unknown backend cpu")

    monkeypatch.setattr(jax, "local_devices", no_backend)
    monkeypatch.setattr(jax, "device_put", no_backend)
    honey_badger.enable()
    honey_badger.set_exception(faults.MODULE, faults.DEVICE_DISPATCH)
    try:
        faulted = engine.process_batch(_req())
    finally:
        honey_badger.unset(faults.MODULE, faults.DEVICE_DISPATCH)
        honey_badger.disable()
    assert _payloads(faulted) == _payloads(baseline)
    stats = engine.stats()
    assert stats["n_fallback_rows"] > 0
    assert stats.get("n_device_launches", 0) == 0, "nothing ran on the device"


def test_sandbox_compile_fault_refuses_registration():
    engine = TpuEngine(row_stride=256)
    _live_engines.append(engine)
    honey_badger.enable()
    honey_badger.set_exception(faults.MODULE, faults.SANDBOX_COMPILE)
    try:
        code = engine.enable_py_sandboxed(
            9, "def transform(value):\n    return value\n", ("t",)
        )
    finally:
        honey_badger.unset(faults.MODULE, faults.SANDBOX_COMPILE)
        honey_badger.disable()
    assert code == EnableResponseCode.internal_error
    assert engine.heartbeat() == 0, "a poisoned compile must not register"


# ------------------------------------------------------------ arm-once probes
def test_one_shot_probe_auto_disarms_after_first_injection():
    honey_badger.enable()
    honey_badger.set_exception(faults.MODULE, faults.DEVICE_DISPATCH, count=1)
    assert honey_badger.remaining(faults.MODULE, faults.DEVICE_DISPATCH) == 1
    with pytest.raises(ProbeTriggered):
        faults.inject(faults.DEVICE_DISPATCH)
    # auto-disarmed: the second injection is a no-op, nothing stays armed
    faults.inject(faults.DEVICE_DISPATCH)
    assert honey_badger.armed() == {}
    assert honey_badger.remaining(faults.MODULE, faults.DEVICE_DISPATCH) is None
    # the REGISTRY stays enabled — other probes may be armed; the admin
    # DELETE handler owns the last-probe-disables-registry rule
    assert honey_badger.enabled


def test_count_n_probe_fires_exactly_n_times():
    honey_badger.enable()
    honey_badger.set_exception(faults.MODULE, faults.MASK_FETCH, count=3)
    for i in range(3):
        assert honey_badger.remaining(faults.MODULE, faults.MASK_FETCH) == 3 - i
        with pytest.raises(ProbeTriggered):
            faults.inject(faults.MASK_FETCH)
    faults.inject(faults.MASK_FETCH)  # budget spent: no raise


def test_one_shot_wedge_blocks_once_then_disarms():
    honey_badger.enable()
    honey_badger.wedge_max_s = 0.05
    honey_badger.set_wedge(faults.MODULE, faults.HARVEST, count=1)
    t0 = time.perf_counter()
    faults.inject(faults.HARVEST)  # wedges for the full cap, ONCE
    assert time.perf_counter() - t0 >= 0.04
    t0 = time.perf_counter()
    faults.inject(faults.HARVEST)  # disarmed: immediate
    assert time.perf_counter() - t0 < 0.04
    assert honey_badger.armed() == {}


def test_one_shot_async_probe_consumes():
    import asyncio

    honey_badger.enable()
    honey_badger.set_exception("rpc", "send", count=1)

    async def main():
        with pytest.raises(ProbeTriggered):
            await honey_badger.maybe_inject("rpc", "send")
        await honey_badger.maybe_inject("rpc", "send")  # spent: no raise

    asyncio.run(main())
    assert honey_badger.armed() == {}


def test_one_shot_claim_is_atomic_under_concurrency():
    """Probe sites fire concurrently (pool workers, harvester): a count=N
    budget must yield EXACTLY N injections no matter how many threads
    race the claim."""
    honey_badger.enable()
    honey_badger.set_exception(faults.MODULE, faults.SHARD_WORKER, count=3)
    fired = []
    start = threading.Barrier(8)

    def site():
        start.wait()
        for _ in range(4):
            try:
                faults.inject(faults.SHARD_WORKER)
            except ProbeTriggered:
                fired.append(1)

    threads = [threading.Thread(target=site) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(fired) == 3, f"count=3 probe fired {len(fired)} times"
    assert honey_badger.armed() == {}


def test_rearm_without_count_clears_one_shot_budget():
    honey_badger.enable()
    honey_badger.set_exception(faults.MODULE, faults.HARVEST, count=1)
    honey_badger.set_exception(faults.MODULE, faults.HARVEST)  # now unlimited
    assert honey_badger.remaining(faults.MODULE, faults.HARVEST) is None
    for _ in range(3):
        with pytest.raises(ProbeTriggered):
            faults.inject(faults.HARVEST)


def test_one_shot_dispatch_fault_is_a_deterministic_single_retry():
    """The arm-once use case end to end: ONE injected dispatch fault means
    the engine retries exactly once, the retry hits a healthy device, and
    output is exact — no disarm race deciding how many launches fault."""
    baseline = _engine(force_mode="columnar_device").process_batch(_req())
    engine = _engine(force_mode="columnar_device")
    honey_badger.enable()
    honey_badger.set_exception(faults.MODULE, faults.DEVICE_DISPATCH, count=1)
    reply = engine.process_batch(_req())
    assert _payloads(reply) == _payloads(baseline)
    stats = engine.stats()
    assert stats.get("n_retries", 0.0) == 1.0, stats
    assert stats.get("n_fallback_rows", 0.0) == 0.0, stats
    assert honey_badger.armed() == {}


# ------------------------------------------------------------ admin round trip
def test_admin_failure_probe_round_trip(tmp_path):
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
                # the coproc fault domains register on module import
                body = await (await s.get(f"{base}/v1/failure-probes")).json()
                assert set(body["modules"]["coproc"]) >= {
                    "device_dispatch", "mask_fetch", "harvest",
                    "shard_worker", "sandbox_compile",
                }
                assert "send" in body["modules"]["rpc"]
                # arm exception + delay + wedge, visible in the armed view
                for probe, typ in [
                    ("device_dispatch", "exception"),
                    ("mask_fetch", "delay"),
                    ("harvest", "wedge"),
                ]:
                    r = await s.put(
                        f"{base}/v1/failure-probes/coproc/{probe}/{typ}"
                    )
                    assert r.status == 200
                body = await (await s.get(f"{base}/v1/failure-probes")).json()
                assert body["enabled"] is True
                assert body["armed"]["coproc"] == {
                    "device_dispatch": "exception",
                    "mask_fetch": "delay",
                    "harvest": "wedge",
                }
                with pytest.raises(ProbeTriggered):
                    faults.inject(faults.DEVICE_DISPATCH)
                # count-limited arm: ?count=N rides the PUT, shows in the
                # counts view, and auto-disarms after N injections
                r = await s.put(
                    f"{base}/v1/failure-probes/coproc/shard_worker/"
                    f"exception?count=2"
                )
                assert r.status == 200
                assert (await r.json())["count"] == 2
                body = await (await s.get(f"{base}/v1/failure-probes")).json()
                assert body["counts"]["coproc"]["shard_worker"] == 2
                with pytest.raises(ProbeTriggered):
                    faults.inject(faults.SHARD_WORKER)
                body = await (await s.get(f"{base}/v1/failure-probes")).json()
                assert body["counts"]["coproc"]["shard_worker"] == 1
                with pytest.raises(ProbeTriggered):
                    faults.inject(faults.SHARD_WORKER)
                body = await (await s.get(f"{base}/v1/failure-probes")).json()
                assert "shard_worker" not in body["armed"].get("coproc", {})
                assert "shard_worker" not in body["counts"].get("coproc", {})
                # malformed counts are a 400, not a silently-unlimited arm
                for bad in ("0", "-1", "bogus"):
                    r = await s.put(
                        f"{base}/v1/failure-probes/coproc/shard_worker/"
                        f"exception?count={bad}"
                    )
                    assert r.status == 400, bad
                # unknown probe names 404 loudly (a typo'd campaign is dead)
                r = await s.put(
                    f"{base}/v1/failure-probes/coproc/tpyo/exception"
                )
                assert r.status == 404
                r = await s.put(
                    f"{base}/v1/failure-probes/coproc/harvest/frobnicate"
                )
                assert r.status == 400
                # a typo'd DISARM must fail loudly too (a 200 would leave
                # the real probe silently armed) and must not conjure a
                # phantom module into the registry listing
                r = await s.delete(f"{base}/v1/failure-probes/coproc/tpyo")
                assert r.status == 404
                r = await s.delete(f"{base}/v1/failure-probes/nosuch/probe")
                assert r.status == 404
                body = await (await s.get(f"{base}/v1/failure-probes")).json()
                assert "nosuch" not in body["modules"]
                # disarm everything
                for probe in ("device_dispatch", "mask_fetch", "harvest"):
                    r = await s.delete(
                        f"{base}/v1/failure-probes/coproc/{probe}"
                    )
                    assert r.status == 200
                body = await (await s.get(f"{base}/v1/failure-probes")).json()
                assert body["armed"] == {}
                # last disarm drops the registry back to disabled: probe
                # sites stop paying even the enabled check's coroutine
                assert body["enabled"] is False
                faults.inject(faults.DEVICE_DISPATCH)  # no raise
                # a DISABLED registry is a no-op even with a probe armed
                honey_badger.set_exception(faults.MODULE, faults.DEVICE_DISPATCH)
                honey_badger.disable()
                faults.inject(faults.DEVICE_DISPATCH)  # no raise
                honey_badger.unset(faults.MODULE, faults.DEVICE_DISPATCH)
        finally:
            await admin.stop()
            await storage.stop()

    asyncio.run(main())


def test_admin_coproc_status(tmp_path):
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
                # no coproc api on the broker: disabled, not a 500
                body = await (await s.get(f"{base}/v1/coproc/status")).json()
                assert body["enabled"] is False

                class _FakeApi:
                    engine = _engine()

                    @staticmethod
                    def active_scripts():
                        return ["demo"]

                broker.coproc_api = _FakeApi()
                body = await (await s.get(f"{base}/v1/coproc/status")).json()
                assert body["enabled"] is True
                assert body["scripts"] == ["demo"]
                assert body["breaker"]["state"] == "closed"
                assert body["breaker"]["threshold"] == 5
        finally:
            await admin.stop()
            await storage.stop()

    asyncio.run(main())
