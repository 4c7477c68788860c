"""Coproc fault-injection chaos parity suite (ISSUE 4, hermetic).

The tentpole's whole correctness claim is: a fault anywhere on the device
path changes WHERE a stage executes, never WHAT it produces. This suite
arms the honey badger with every effect (exception, delay, wedge) at every
coproc probe point (device dispatch, mask fetch, harvest, shard worker)
and drives a 64-partition JSON-filter workload through the real engine,
asserting the reply is bit-identical to the fault-free run — same payload
bytes, same CRCs, same record counts, zero records lost or duplicated —
in all three engine modes (columnar, payload, host plan) plus the
columnar-device leg, with the host-stage pool both off and on.

Unlike the rest of tests/chaos/ this file is hermetic (no proc_cluster):
fault injection needs per-run probe arming and fresh breakers, which a
shared 3-node cluster cannot give without cross-test contamination. The
live-broker breaker lifecycle is driven separately (verify skill).
"""

import json

import pytest

from redpanda_tpu.coproc import (
    TpuEngine,
    ProcessBatchRequest,
    EnableResponseCode,
)
from redpanda_tpu.coproc import faults
from redpanda_tpu.coproc.engine import ProcessBatchItem
from redpanda_tpu.finjector import honey_badger
from redpanda_tpu.models import NTP, Record, RecordBatch
from redpanda_tpu.ops.exprs import field
from redpanda_tpu.ops.transforms import (
    Int,
    Str,
    filter_contains,
    identity,
    map_project,
    where,
)

PARTITIONS = 64
RECORDS_PER_PARTITION = 16

PROBE_POINTS = (
    faults.DEVICE_DISPATCH,
    faults.MASK_FETCH,
    faults.HARVEST,
    faults.SHARD_WORKER,
)
EFFECTS = ("exception", "delay", "wedge")

MODES = [
    # (name, spec factory, force_mode) — the three engine modes, plus the
    # async device-predicate leg (per-launch _MaskSlot harvest) explicitly
    ("columnar", lambda: where(field("level") == "error")
     | map_project(Int("code"), Str("msg", 16)), "columnar_host"),
    ("columnar_device", lambda: where(field("level") == "error")
     | map_project(Int("code"), Str("msg", 16)), "columnar_device"),
    ("payload", lambda: filter_contains(b"error"), None),
    ("host", lambda: identity(), None),
]


_live_engines: list[TpuEngine] = []


@pytest.fixture(autouse=True)
def _fast_faults():
    """Chaos must finish inside CI budgets: short wedges and delays, and
    a guaranteed-clean badger.
    Teardown also SHUTS DOWN every engine the test created: this file runs
    early in the suite (inside the chaos package, before the in-process
    cluster tests), and leaked daemon harvesters pin engines — plans, jit
    executables, pool threads — for the rest of the run."""
    saved_wedge = honey_badger.wedge_max_s
    saved_delay = honey_badger.delay_ms
    honey_badger.wedge_max_s = 0.12
    honey_badger.delay_ms = 5
    yield
    for module, armed in list(honey_badger.armed().items()):
        for probe in armed:
            honey_badger.unset(module, probe)
    honey_badger.disable()
    honey_badger.wedge_max_s = saved_wedge
    honey_badger.delay_ms = saved_delay
    while _live_engines:
        _live_engines.pop().shutdown()


def _workload():
    """64-partition JSON-filter workload: one batch per partition, mixed
    error/info levels — the north-star request shape at test size."""
    items = []
    for p in range(PARTITIONS):
        recs = [
            Record(
                offset_delta=i,
                timestamp_delta=i,
                value=json.dumps(
                    {"level": ["error", "info"][(p + i) % 2],
                     "code": 100 * p + i, "msg": f"p{p}m{i}"},
                    separators=(",", ":"),
                ).encode(),
            )
            for i in range(RECORDS_PER_PARTITION)
        ]
        items.append(
            ProcessBatchItem(
                1,
                NTP.kafka("orders", p),
                [RecordBatch.build(recs, base_offset=1000 * p, first_timestamp=1000)],
            )
        )
    return ProcessBatchRequest(items)


def _engine(spec, force_mode, mesh_devices=0):
    """``mesh_devices`` >= 2 pins the mesh lane (per-device ladders on a
    4-worker pool: the one place the shard_worker probe point fires) for
    the launches that can take it; every other launch of that engine runs
    the single-device road."""
    engine = TpuEngine(
        row_stride=256,
        compress_threshold=10**9,
        force_mode=force_mode,
        host_workers=4 if mesh_devices else 0,
        mesh_devices=mesh_devices or None,
        mesh_backend="cpu" if mesh_devices else None,
        mesh_probe=False,
        # Tight fault envelope so wedge runs stay fast: the per-attempt
        # deadline (60ms) sits BELOW wedge_max_s (120ms), which is what
        # forces the deadline-abandonment path a real wedged link takes.
        # The adaptive derivation is pinned OFF for the same reason the
        # deadline itself is pinned: earlier fault runs in this process
        # inflate the fetch-stage p99.9, and a governor-raised deadline
        # above the wedge cap would let the wedged fetch "succeed" late
        # instead of exercising the abandonment path under test.
        device_deadline_ms=60,
        adaptive_deadline=False,
        launch_retries=1,
        retry_backoff_ms=1,
        # parity runs must observe every probe point on the device path,
        # so the breaker may not demote the engine mid-matrix
        breaker_threshold=10_000,
    )
    codes = engine.enable_coprocessors([(1, spec.to_json(), ("orders",))])
    assert codes == [EnableResponseCode.success]
    _live_engines.append(engine)
    return engine


def _fingerprint(reply):
    """Everything that must survive a fault bit-for-bit: per-partition
    output payload bytes, CRCs, record counts, and offsets."""
    out = []
    for item in reply.items:
        out.append((
            item.script_id,
            str(item.source),
            [
                (
                    b.payload,
                    b.header.crc,
                    b.header.record_count,
                    b.header.base_offset,
                )
                for b in item.batches
            ],
        ))
    return out


def _total_records(reply):
    return sum(
        b.header.record_count for item in reply.items for b in item.batches
    )


@pytest.mark.parametrize("mesh_devices", [0, 2], ids=["single", "mesh"])
@pytest.mark.parametrize(
    "mode_name,spec_fn,force_mode", MODES, ids=[m[0] for m in MODES]
)
def test_chaos_parity_every_probe_point(
    mode_name, spec_fn, force_mode, mesh_devices, eight_devices
):
    req = _workload()
    # ONE engine serves the whole probe x effect matrix (its breaker
    # threshold is unreachable, so no run demotes the next): in the full
    # suite this file shares the box with the package's live 3-node
    # cluster, and an engine-per-combination matrix of jit compiles
    # starves the brokers' elections
    engine = _engine(spec_fn(), force_mode, mesh_devices)
    baseline = _fingerprint(engine.process_batch(req))
    base_records = sum(
        bc[2] for _sid, _src, batches in baseline for bc in batches
    )
    assert base_records > 0, "workload must actually produce output"
    if mesh_devices and force_mode == "columnar_device":
        assert engine.stats()["n_mesh_launches"] == 1, "mesh lane must engage"

    honey_badger.enable()
    try:
        for probe in PROBE_POINTS:
            for effect in EFFECTS:
                getattr(honey_badger, {
                    "exception": "set_exception",
                    "delay": "set_delay",
                    "wedge": "set_wedge",
                }[effect])(faults.MODULE, probe)
                try:
                    reply = engine.process_batch(req)
                finally:
                    honey_badger.unset(faults.MODULE, probe)
                got = _fingerprint(reply)
                assert got == baseline, (
                    f"{mode_name}/mesh={mesh_devices}: output diverged under "
                    f"{effect} at {probe}"
                )
                assert _total_records(reply) == base_records, (
                    f"records lost/duplicated under {effect} at {probe}"
                )
    finally:
        honey_badger.disable()


def test_chaos_parity_wedged_harvest_deadline_abandonment():
    """A WEDGED mask harvest (blocks instead of raising) exercises the
    deadline-abandonment machinery end to end: each harvester attempt is
    abandoned at its deadline, the envelope exhausts, the caller — which
    waits out the harvester's WHOLE envelope, never racing a duplicate
    fetch against it — takes the exact numpy fallback directly."""
    req = _workload()
    spec = where(field("level") == "error") | map_project(Int("code"), Str("msg", 16))
    baseline = _fingerprint(
        _engine(spec, "columnar_device", 0).process_batch(req)
    )
    engine = _engine(spec, "columnar_device", 0)
    honey_badger.enable()
    honey_badger.set_wedge(faults.MODULE, faults.HARVEST)
    try:
        reply = engine.process_batch(req)
    finally:
        honey_badger.unset(faults.MODULE, faults.HARVEST)
        honey_badger.disable()
    assert _fingerprint(reply) == baseline
    stats = engine.stats()
    assert stats["n_fallback_rows"] > 0, "the numpy fallback must have run"
    assert stats["n_retries"] >= 1
    assert stats["breaker"]["consecutive_failures"] == 1, (
        "one wedged mask = one breaker failure (no duplicate caller fetch)"
    )


def test_chaos_parity_harvester_failure_single_verdict():
    """Harvester fails its WHOLE envelope (exception armed, event set with
    no bits): the caller must take the exact fallback directly — one
    breaker failure per launch, not harvester + a doomed re-fetch."""
    req = _workload()
    spec = where(field("level") == "error") | map_project(Int("code"), Str("msg", 16))
    baseline = _fingerprint(
        _engine(spec, "columnar_device", 0).process_batch(req)
    )
    engine = _engine(spec, "columnar_device", 0)
    honey_badger.enable()
    honey_badger.set_exception(faults.MODULE, faults.HARVEST)
    try:
        reply = engine.process_batch(req)
    finally:
        honey_badger.unset(faults.MODULE, faults.HARVEST)
        honey_badger.disable()
    assert _fingerprint(reply) == baseline
    snap = engine.stats()
    assert snap["breaker"]["consecutive_failures"] == 1
    assert snap["n_fallback_rows"] > 0


def test_chaos_breaker_lifecycle_under_sustained_faults():
    """Sustained injected dispatch failures trip the breaker; traffic
    continues on the host fallback with exact output; after the cooldown a
    half-open probe re-closes it — the in-process twin of the live-broker
    acceptance drive."""
    import time

    req = _workload()
    spec = where(field("level") == "error") | map_project(Int("code"), Str("msg", 16))
    baseline = _fingerprint(
        _engine(spec, "columnar_device", 0).process_batch(req)
    )
    engine = TpuEngine(
        row_stride=256, compress_threshold=10**9,
        force_mode="columnar_device", host_workers=0,
        # generous deadline: the half-open probe pays this engine's FIRST
        # real device compile, which must not be mistaken for a wedge
        device_deadline_ms=10_000, launch_retries=0, retry_backoff_ms=1,
        # cooldown well above one run's tail so the run right after the
        # trip is deterministically host-demoted, not a surprise probe
        breaker_threshold=2, breaker_cooldown_ms=400,
    )
    _live_engines.append(engine)
    engine.enable_coprocessors([(1, spec.to_json(), ("orders",))])

    honey_badger.enable()
    honey_badger.set_exception(faults.MODULE, faults.DEVICE_DISPATCH)
    try:
        for _ in range(3):  # threshold=2: trips during this loop
            assert _fingerprint(engine.process_batch(req)) == baseline
    finally:
        honey_badger.unset(faults.MODULE, faults.DEVICE_DISPATCH)
        honey_badger.disable()
    snap = engine.stats()["breaker"]
    assert snap["state"] == "open" and snap["trips"] >= 1

    # open breaker, fault long gone: output exact, still host-executed
    fb0 = engine.stats()["n_fallback_rows"]
    assert _fingerprint(engine.process_batch(req)) == baseline
    assert engine.stats()["n_fallback_rows"] > fb0

    # cooldown elapses -> ONE half-open probe launch re-admits the device
    time.sleep(0.45)
    assert _fingerprint(engine.process_batch(req)) == baseline
    assert engine.stats()["breaker"]["state"] == "closed"
