"""The stage helper (observability/stages.py): one clock pair, three sinks;
``parent`` on ring spans and the self time that follows from it."""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

from redpanda_tpu.metrics import Histogram
from redpanda_tpu.observability import stages
from redpanda_tpu.observability.trace import _NOOP, Tracer, self_times, tracer


@pytest.fixture
def tracing():
    tracer.reset()
    tracer.configure(enabled=True)
    yield tracer
    tracer.configure(enabled=False)
    tracer.reset()


def _spans():
    return {s["name"]: s for t in tracer.recent(0) for s in t["spans"]}


# ------------------------------------------------------------------ sinks
@pytest.mark.parametrize("form", ["with", "begin_close"])
def test_histogram_always_ring_only_when_enabled(form):
    tracer.configure(enabled=False)
    tracer.reset()
    hist = Histogram("t_us", "")
    if form == "with":
        with stages.stage("unit.stage", hist, root=True) as sp:
            assert sp is _NOOP  # no span object while tracing is off
    else:
        t0 = stages.begin("unit.stage")
        assert type(t0) is float  # no profile: a bare clock read
        assert stages.close("unit.stage", hist, t0) >= 0.0
    assert hist.hist.count == 1
    assert tracer.spans_recorded == 0


def test_ring_spans_carry_the_parent_that_was_ambient(tracing):
    hist = Histogram("t_us", "")
    with stages.stage("root", hist, root=True) as root:
        t0 = stages.begin("kid.closed")
        stages.close("kid.closed", hist, t0)
        with stages.stage("kid.with") as kid:
            t0 = stages.begin("grandkid")
            stages.close("grandkid", None, t0)
        # an explicit other trace takes no ambient parent
        t0 = stages.begin("elsewhere")
        stages.close("elsewhere", None, t0, trace_id=root.trace_id + 1000)
    spans = _spans()
    assert "parent" not in spans["root"]
    assert spans["kid.closed"]["parent"] == root.span_id
    assert spans["kid.with"]["parent"] == root.span_id
    assert spans["grandkid"]["parent"] == kid.span_id
    assert "parent" not in spans["elsewhere"]
    assert {s["trace_id"] for n, s in spans.items() if n != "elsewhere"} == {root.trace_id}
    assert hist.hist.count == 2  # root and kid.closed; the others took none
    # one clock pair: the ring's duration is the histogram's sample
    assert spans["kid.closed"]["dur_us"] <= hist.hist.max


def test_a_stage_outside_any_trace_mints_no_orphan(tracing):
    hist = Histogram("t_us", "")
    with stages.stage("mid.path", hist):
        pass
    t0 = stages.begin("mid.path")
    stages.close("mid.path", hist, t0)
    assert hist.hist.count == 2 and tracer.spans_recorded == 0


def test_record_takes_an_explicit_parent():
    t = Tracer(enabled=True)
    t.record("a", 10.0, 5, start_perf=t.epoch_perf, parent=42)
    t.record("b", 10.0, 5, start_perf=t.epoch_perf)
    a, b = t.spans_for(5)
    assert a["parent"] == 42 and "parent" not in b


def test_detached_drops_the_ambient_span(tracing):
    with stages.stage("root", root=True):
        with tracer.detached():
            with stages.stage("inside"):
                pass
    assert "inside" not in _spans()


# ------------------------------------------------------------------ self time
def test_self_time_is_span_less_what_children_cover():
    def span(i, start, dur, parent=None):
        s = {"span_id": i, "name": f"s{i}", "start_us": start, "dur_us": dur}
        if parent is not None:
            s["parent"] = parent
        return s

    nest = [
        span(1, 0, 1000),
        span(2, 100, 300, parent=1),
        span(3, 300, 300, parent=1),   # overlaps 2 by 100: not taken off twice
        span(4, 900, 400, parent=1),   # outlives 1: clipped to it
        span(5, 150, 100, parent=2),
        span(6, 5000, 50, parent=99),  # parent fell off the ring
    ]
    assert self_times(nest) == {1: 1000 - 500 - 100, 2: 200, 3: 300, 4: 400, 5: 100, 6: 50}


def test_pulse_self_totals_sum_self_time_by_name(tracing):
    from redpanda_tpu.observability.pulse import FlightRecorder

    rec = FlightRecorder()
    tracer.set_sink(rec.record)
    try:
        with stages.stage("coproc.tick", root=True):
            with stages.stage("coproc.read"):
                pass
    finally:
        tracer.set_sink(None)
    total, own = rec.stage_totals(), rec.self_totals()
    assert own["coproc.read"] == pytest.approx(total["coproc.read"])
    assert own["coproc.tick"] == pytest.approx(
        total["coproc.tick"] - total["coproc.read"], abs=2e-6
    )


# ------------------------------------------------------------------ annotation
_NO_JAX = """
import sys
from redpanda_tpu.metrics import Histogram
from redpanda_tpu.observability import stages
h = Histogram("t_us", "")
with stages.stage("a", h):
    pass
t0 = stages.begin("b"); stages.close("b", h, t0)
assert type(t0) is float and h.hist.count == 2
assert "jax" not in sys.modules, "the stage helper imported jax"
assert stages._annotation is None
print("ok")
"""


def test_annotation_is_a_noop_without_jax_and_never_imports_it():
    out = subprocess.run(
        [sys.executable, "-c", _NO_JAX], capture_output=True, text=True, timeout=60,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


def test_annotation_binds_once_jax_is_there_and_is_idle_without_a_profile():
    import jax

    t0 = stages.begin("unit.stage")
    assert stages._annotation is jax.profiler.TraceAnnotation
    assert type(t0) is float  # no profile running: no annotation object
    stages.close("unit.stage", None, t0)


def test_annotation_lands_on_the_profiles_host_plane(tmp_path):
    import glob

    import jax
    from jax.profiler import ProfileData

    hist = Histogram("t_us", "")
    jax.profiler.start_trace(str(tmp_path))
    try:
        t0 = stages.begin("unit.closed")
        assert type(t0) is not float and float(t0) > 0
        stages.close("unit.closed", hist, t0)
        with stages.stage("unit.with", hist):
            pass
        # a stage that is mostly somebody else's wait keeps off the profile
        with stages.stage("unit.quiet.with", hist, annotate=False):
            pass
        t0 = stages.begin("unit.quiet.closed", annotate=False)
        assert type(t0) is float
        stages.close("unit.quiet.closed", hist, t0)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {
        ev.name
        for plane in ProfileData.from_file(path).planes if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events
    }
    assert {"rp:unit.closed", "rp:unit.with"} <= names
    assert not {n for n in names if n.startswith("rp:unit.quiet")}
    assert hist.hist.count == 4  # sink (a) takes all four


@pytest.mark.parametrize("result", ["mask", "matrix"])
def test_the_payload_lanes_h2d_stage_feeds_all_its_sinks(result, tmp_path):
    """``t_h2d`` (PR 26): the ``jax.device_put`` of a payload launch's
    staged matrix is a stage of its own inside ``t_dispatch``: the stat,
    the ``coproc_stage_latency_us{stage="h2d"}`` histogram, and the
    ``rp:coproc.stage.h2d`` annotation beside pack, fetch and the framing
    stage: ``frame_gather`` for a filter-only script (its launch fetches a
    keep mask, PR 27), ``rebuild`` for one that builds new bytes."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from redpanda_tpu.observability import probes

    engine, req = _payload_engine_and_request(result)
    hist = probes.coproc_stage_hist("h2d").hist
    before = hist.count
    jax.profiler.start_trace(str(tmp_path))
    try:
        reply = engine.process_batch(req)
    finally:
        jax.profiler.stop_trace()
        stats = engine.stats()
        engine.shutdown()
    assert reply.items[0].batches[0].header.record_count == 8
    assert 0 < stats["t_h2d"] <= stats["t_dispatch"] + stats.get("t_compile", 0.0)
    assert hist.count == before + 1
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {
        ev.name
        for plane in ProfileData.from_file(path).planes if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events
    }
    frame = "frame_gather" if result == "mask" else "rebuild"
    assert {"rp:coproc.stage." + s for s in ("pack", "h2d", "dispatch", "fetch", frame)} <= names


def _payload_engine_and_request(result, trace_id=None):
    from redpanda_tpu.coproc import ProcessBatchRequest, TpuEngine
    from redpanda_tpu.coproc.engine import ProcessBatchItem
    from redpanda_tpu.models import NTP, Record, RecordBatch
    from redpanda_tpu.ops.transforms import filter_contains, map_uppercase

    spec = filter_contains(b"warn")
    if result == "matrix":
        spec = spec | map_uppercase()
    engine = TpuEngine(row_stride=64, host_workers=0)
    engine.enable_coprocessors([(1, spec.to_json(), ("t",))])
    batch = RecordBatch.build(
        [Record(offset_delta=i, timestamp_delta=i, value=b"warn %d" % i) for i in range(8)],
        base_offset=0, first_timestamp=1000,
    )
    req = ProcessBatchRequest(
        [ProcessBatchItem(1, NTP.kafka("t", 0), [batch])], trace_id=trace_id
    )
    return engine, req


LINK_LEGS = ("wait_h2d", "wait_program", "wait_d2h")


@pytest.mark.parametrize("result", ["mask", "matrix"])
def test_the_link_waits_three_legs_feed_all_their_sinks(result, tmp_path):
    """The fetch of a payload launch's result waits in dependency order
    with a clock read between: the staged matrix on the device, the result
    defined, the result on the host. Each leg is a stage: the ``stats()``
    twin ``t_wait_*``, ``coproc_stage_latency_us{stage="wait_*"}`` and the
    ``rp:coproc.stage.wait_*`` annotation. On the matrix road the fetching
    thread is the waiting one, so ``t_fetch`` is their sum and the fault
    envelope's thread hop; on the mask road they are the harvester's."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from redpanda_tpu.observability import probes

    engine, req = _payload_engine_and_request(result)
    hists = {leg: probes.coproc_stage_hist(leg).hist for leg in LINK_LEGS}
    before = {leg: h.count for leg, h in hists.items()}
    jax.profiler.start_trace(str(tmp_path))
    try:
        reply = engine.process_batch(req)
    finally:
        jax.profiler.stop_trace()
        stats = engine.stats()
        engine.shutdown()
    assert reply.items[0].batches[0].header.record_count == 8
    assert not stats.get("n_fallback_rows")
    for leg in LINK_LEGS:
        assert hists[leg].count == before[leg] + 1, leg
        assert stats["t_" + leg] > 0, leg
    legs = sum(stats["t_" + leg] for leg in LINK_LEGS)
    if result == "matrix":
        assert legs <= stats["t_fetch"] <= legs + 0.05
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {
        ev.name
        for plane in ProfileData.from_file(path).planes if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events
    }
    assert {"rp:coproc.stage." + leg for leg in LINK_LEGS} <= names
    assert {"rp:coproc.dispatch", "rp:coproc.harvest", "rp:coproc.stage.fetch"} <= names


@pytest.mark.parametrize("result", ["mask", "matrix"])
def test_the_link_waits_legs_are_the_fetchs_children_in_the_ring(result, tracing):
    engine, req = _payload_engine_and_request(result, trace_id=tracer.new_trace_id())
    try:
        engine.process_batch(req)
    finally:
        engine.shutdown()
    spans = _spans()
    fetch = spans["coproc.stage.fetch"]
    for leg in LINK_LEGS:
        assert spans["coproc.stage." + leg]["parent"] == fetch["span_id"], leg
    if result == "matrix":
        # the legs lie inside the fetch, so its self time is what they leave
        # (the envelope's thread hop): a profile counts the wait once
        own = self_times([s for t in tracer.recent(0) for s in t["spans"]])
        kids = sum(spans["coproc.stage." + leg]["dur_us"] for leg in LINK_LEGS)
        assert own[fetch["span_id"]] <= fetch["dur_us"] - kids + len(LINK_LEGS)


@pytest.mark.parametrize("domain", ["device_dispatch", "harvest"])
def test_a_host_fallback_records_no_link_wait_leg(domain):
    """A launch whose dispatch or whose fetch fell back to the host never
    waited on the link: no ``t_wait_*`` key appears in ``stats()``."""
    from redpanda_tpu.coproc import faults
    from redpanda_tpu.finjector import honey_badger

    engine, req = _payload_engine_and_request("matrix")
    honey_badger.enable()
    honey_badger.set_exception(faults.MODULE, domain)
    try:
        reply = engine.process_batch(req)
    finally:
        honey_badger.unset(faults.MODULE, domain)
        honey_badger.disable()
        stats = engine.stats()
        engine.shutdown()
    assert reply.items[0].batches[0].header.record_count == 8
    assert stats["n_fallback_rows"] == 8
    assert not [k for k in stats if k.startswith("t_wait_")]


# ------------------------------------------------------------------ PR 52: shared clock reads
@pytest.mark.parametrize("on", [False, True], ids=["tracing_off", "tracing_on"])
def test_close_takes_a_back_dated_start_for_a_duration_the_caller_already_has(on):
    """The produce handler's ``queue`` stage: the caller holds seconds, not
    a start, and closes the stage inside the span it belongs under."""
    import time

    tracer.reset()
    tracer.configure(enabled=on)
    try:
        hist = Histogram("t_us", "")
        with stages.stage("root", root=True) as root:
            dt = stages.close("kid.back_dated", hist, time.perf_counter() - 0.002)
        assert 0.002 <= dt < 0.0021 and hist.hist.count == 1
        assert 2000 <= hist.hist.sum < 2100
        spans = _spans()
        if not on:
            assert spans == {}
            return
        kid = spans["kid.back_dated"]
        assert kid["parent"] == root.span_id and 2000 <= kid["dur_us"] < 2100
        assert kid["start_us"] < spans["root"]["start_us"]  # it began before its parent did
    finally:
        tracer.configure(enabled=False)
        tracer.reset()


def test_a_with_stage_hands_out_its_own_two_clock_reads():
    """What lets a caller's neighbouring intervals begin and end on the
    stage's reads (the pacemaker's engine phase as the sum of its legs)."""
    import time

    hist = Histogram("t_us", "")
    before = time.perf_counter()
    st = stages.stage("unit.stage", hist)
    with st:
        inside = time.perf_counter()
    after = time.perf_counter()
    assert before <= st.t0 <= inside <= st.t1 <= after
    assert hist.hist.sum == int((st.t1 - st.t0) * 1e6)
    # a stage that raised has its end too
    st = stages.stage("unit.raises")
    with pytest.raises(ValueError):
        with st:
            raise ValueError("inside")
    assert st.t1 >= st.t0
