"""Rows staged by width class (PR 47).

A payload launch's staged row is as wide as its own values need (a multiple
of 128 B, never over the lane's ``row_stride``), and a launch whose values
fall in two far-apart width classes is staged, shipped and run as two parts
(``TpuEngine._plan_parts``), whose results are put back in the launch's row
order where the fetch lands them (``engine._merge_parts``). Held here on the
CPU against the same launches at ONE stride of the lane's own 1,024 B (the
engine as it was: ``_STRIDE_CLASS`` patched to the lane's limit): ``out``,
``out_len``, ``keep`` and the sealed batches byte for byte, over both
exploded tables, both result formats, the host fallback and a cut part.
"""

import contextlib
import importlib.util
import json
import os

import numpy as np
import pytest

from redpanda_tpu.coproc import EnableResponseCode, ProcessBatchRequest, TpuEngine, batch_codec
from redpanda_tpu.coproc import engine as engine_mod
from redpanda_tpu.coproc import faults
from redpanda_tpu.coproc.engine import ProcessBatchItem, _bucket_rows, _merge_parts
from redpanda_tpu.models import NTP, Record, RecordBatch
from redpanda_tpu.ops.pipeline import IN_META, OUT_META
from redpanda_tpu.ops.transforms import Int, Str, filter_contains, map_project

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
ROW = 1024


def _load(relpath: str):
    path = os.path.join(BENCH, relpath)
    spec = importlib.util.spec_from_file_location("width_" + relpath[:-3].replace("/", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


with open(os.path.join(BENCH, "configs", "nexmark64p-q1.json")) as _f:
    _Q1 = json.load(_f)
Q1 = json.dumps(_Q1["script"]["spec"])  # filter | map_project: 24 B rows out
V1MAP = (filter_contains(b'"level":"error"') | map_project(Int("code"), Str("msg", 64))).to_json()
FILTER = filter_contains(b'"level":"warn"').to_json()

# road -> (spec, gather_frame): what the device hands back
ROADS = {
    "matrix": (V1MAP, True),        # a projection: a result matrix of its own width
    "mask": (FILTER, True),         # a pure filter: a keep bit a row
    "filter_matrix": (FILTER, False),  # the same filter with the gather harvest off:
                                       # a result row as wide as the staged one
}


def _doc(i: int, width: int) -> bytes:
    """A JSON document exactly ``width`` bytes long; every third one is an
    error, every third a warn."""
    level = ("error", "warn", "info")[i % 3]
    head = b'{"level":"%s","code":%d,"msg":"m%d","pad":"' % (level.encode(), i % 1000, i)
    tail = b'"}'
    if width < len(head) + len(tail):
        return (b'{"level":"%s"}' % level.encode()).ljust(width, b" ")[:width]
    return head + b"x" * (width - len(head) - len(tail)) + tail


def _mix(name: str) -> list:
    rng = np.random.default_rng(47)
    if name == "narrow_wide":    # 92 / 8 by class, as NEXmark's Bids and Auctions
        widths = [int(w) for w in rng.integers(60, 129, 940)] + [
            int(w) for w in rng.integers(520, 641, 60)]
    elif name == "edges":        # every class edge, the lane's own limit among them
        widths = [1, 64, 127, 128, 129, 255, 256, 257, 639, 640, 641, 1023, 1024, 1025, 1500] * 8
        widths += [100] * 700
    elif name == "all_narrow":
        widths = [int(w) for w in rng.integers(40, 129, 500)]
    elif name == "all_wide":
        widths = [int(w) for w in rng.integers(900, 1025, 300)]
    elif name == "half_half":    # two classes far apart, but not far enough in bytes
        widths = [100] * 256 + [1000] * 256
    elif name == "empty":
        return []
    else:
        raise KeyError(name)
    order = rng.permutation(len(widths))
    values = [_doc(int(i), widths[i]) for i in order]
    if name == "edges":
        values[3:3] = [None, b""]
    return values


def _batches(values, per_batch: int = 40) -> list[RecordBatch]:
    if not values:
        return [RecordBatch.build([], base_offset=0, first_timestamp=1000)]
    return [
        RecordBatch.build(
            [Record(offset_delta=i, timestamp_delta=i, value=v)
             for i, v in enumerate(values[s : s + per_batch])],
            base_offset=s, first_timestamp=1000,
        )
        for s in range(0, len(values), per_batch)
    ]


@contextlib.contextmanager
def _one_stride():
    """The engine as it was: every matrix at the lane's own row_stride."""
    saved = engine_mod._STRIDE_CLASS
    engine_mod._STRIDE_CLASS = ROW
    try:
        yield
    finally:
        engine_mod._STRIDE_CLASS = saved


def _run(values, spec: str, *, gather: bool = True, engine: TpuEngine | None = None, **kw):
    """One launch of ``values`` through the engine: what the fetch landed
    (``out``, ``out_len``, ``keep``), the sealed batches and the stats."""
    own = engine is None
    if own:
        engine = TpuEngine(row_stride=ROW, host_workers=0, gather_frame=gather, **kw)
        assert engine.enable_coprocessors([(1, spec, ("t",))]) == [EnableResponseCode.success]
    try:
        ticket = engine.submit(ProcessBatchRequest(
            [ProcessBatchItem(1, NTP.kafka("t", 0), _batches(values))]))
        launch = ticket._slots[0][2]
        with launch._lock:
            view = launch._gather_view()
            if view is not None:  # the mask road: kept values frame from the host's bytes
                out, out_len, keep = None, None, view[1].copy()
            else:
                out, out_len, keep = launch._materialize_locked()
                out = np.where(keep[:, None], out, 0)  # a dropped row's bytes are nobody's
        reply = ticket.result()
        sealed = [b.encode_internal() for item in reply.items for b in item.batches]
        return dict(out=out, out_len=out_len, keep=keep, sealed=sealed, stats=engine.stats())
    finally:
        if own:
            engine.shutdown()


def _same(a: dict, b: dict) -> None:
    assert np.array_equal(a["keep"], b["keep"])
    if a["out"] is not None:
        assert np.array_equal(a["out_len"], b["out_len"])
        w = min(a["out"].shape[1], b["out"].shape[1])  # a filter's row follows the stride
        assert np.array_equal(a["out"][:, :w], b["out"][:, :w])
        assert not a["out"][:, w:].any() and not b["out"][:, w:].any()
    assert a["sealed"] == b["sealed"]


def _force_joined(monkeypatch, table: str) -> None:
    if table == "joined":
        monkeypatch.setattr(batch_codec, "explode_ptrs", lambda batches, *a, **k: None)
    elif batch_codec.explode_ptrs(_batches([b"x"])) is None:
        pytest.skip("native packer unavailable")


# what each mix must do: (parts, the stride of each)
EXPECT = {
    "narrow_wide": [128, 640],
    "edges": None,        # whatever the histogram says: the bytes are the test
    "all_narrow": [128],
    "all_wide": [1024],
    "half_half": [1024],
    "empty": [],
}


# ------------------------------------------------------------------ the split against one stride
@pytest.mark.parametrize("table", ["ptr", "joined"])
@pytest.mark.parametrize("road", sorted(ROADS))
@pytest.mark.parametrize("mix", sorted(EXPECT))
def test_a_launch_staged_by_width_class_gives_the_one_stride_launchs_bytes(
        mix, road, table, monkeypatch):
    _force_joined(monkeypatch, table)
    spec, gather = ROADS[road]
    values = _mix(mix)
    got = _run(values, spec, gather=gather)
    with _one_stride():
        want = _run(values, spec, gather=gather)
    _same(got, want)
    n = len(values)
    stats, base = got["stats"], want["stats"]
    assert int(np.count_nonzero(got["keep"])) == stats.get("n_kept_rows", 0)
    if mix != "empty":
        assert stats["n_kept_rows"] > 0 and stats["n_device_launches"] == 1
    assert "n_split_launches" not in base
    oversize = sum(1 for v in values if v is not None and len(v) > ROW)
    assert stats.get("n_oversize_rows", 0) == base.get("n_oversize_rows", 0) == oversize
    assert stats.get("n_fallback_rows", 0) == 0
    assert stats.get("bytes_staged_values", 0) == base.get("bytes_staged_values", 0)
    strides = EXPECT[mix]
    if road == "filter_matrix" and strides and len(strides) > 1:
        strides = [strides[-1]]  # a result row that follows the staged one: fitted, never split
    if strides is None:
        assert stats["bytes_staged"] <= base["bytes_staged"]
        return
    assert stats.get("n_split_launches", 0) == (1 if len(strides) > 1 else 0)
    assert sorted(c["stride"] for c in stats["compiled_programs"]) == strides
    if not strides:
        return
    if len(strides) == 1:
        assert stats["bytes_staged"] == _bucket_rows(n) * (strides[0] + IN_META)
        assert stats["n_staged_rows"] == _bucket_rows(n)
    else:
        narrow = sum(1 for v in values if len(v) <= strides[0])
        rows = [_bucket_rows(narrow), _bucket_rows(n - narrow)]
        assert stats["bytes_staged"] == stats["bytes_h2d"] == sum(
            r * (s + IN_META) for r, s in zip(rows, strides))
        assert stats["n_staged_rows"] == sum(rows)
        assert stats["bytes_staged"] * 2 <= _bucket_rows(n) * (strides[-1] + IN_META)
        if road == "mask":
            assert stats["bytes_d2h"] == sum(rows) // 8
        else:
            assert stats["bytes_d2h"] == sum(rows) * (70 + OUT_META)


@pytest.mark.parametrize("table", ["ptr", "joined"])
def test_nexmark_q1_over_its_own_events_is_split_and_exact(table, monkeypatch):
    """The claimed cell's launch in small: ``docs_nexmark`` events (Bids of
    ~100 B, Auctions of ~500, Persons of ~200) under Q1's spec go as a
    [n, 136] and a [n, 648] matrix, and every record is the reference's."""
    _force_joined(monkeypatch, table)
    events = _load("docs_nexmark.py").make_events(3000004111, 1, 2048)[0]
    ref = _load("references/" + _Q1["reference"]["name"] + ".py")
    got = _run(events, Q1)
    with _one_stride():
        want = _run(events, Q1)
    _same(got, want)
    stats = got["stats"]
    assert stats["n_split_launches"] == 1 and stats["n_launches"] == 1
    assert sorted(c["stride"] for c in stats["compiled_programs"]) == [128, 640]
    assert stats["bytes_staged"] * 3 < want["stats"]["bytes_staged"]
    assert stats["bytes_staged_values"] / stats["bytes_staged"] > 0.5
    records = [bytes(got["out"][i, : got["out_len"][i]]) for i in np.flatnonzero(got["keep"])]
    params = _Q1["reference"]["params"]
    assert records == [o for o in (ref.reference(v, **params) for v in events) if o is not None]
    assert len(records) > 1800


# ------------------------------------------------------------------ the host fallback of a split launch
@pytest.mark.parametrize("fault", ["dispatch_breaker_open", "harvest_fault"])
@pytest.mark.parametrize("road", ["matrix", "mask"])
def test_a_split_launchs_host_fallback_is_the_device_result_and_parks_nothing(road, fault):
    """Breaker open at dispatch, or the harvest dead: each part re-runs in
    numpy at its own stride and the parts merge as the device's do; neither
    staging matrix re-enters the pool (a tried transfer may still read it)."""
    from redpanda_tpu.finjector import honey_badger

    spec, gather = ROADS[road]
    values = _mix("narrow_wide")
    device = _run(values, spec, gather=gather)
    assert device["stats"]["n_split_launches"] == 1
    assert device["stats"]["staging_arena"]["free_buffers"] == 2  # both back, the result landed
    engine = TpuEngine(
        row_stride=ROW, host_workers=0, gather_frame=gather, launch_retries=0,
        breaker_threshold=1, breaker_cooldown_ms=3_600_000, retry_backoff_ms=1,
    )
    assert engine.enable_coprocessors([(1, spec, ("t",))]) == [EnableResponseCode.success]
    probe = None
    if fault == "dispatch_breaker_open":
        engine.governor.breaker_for(faults.DEVICE_DISPATCH).record_failure()
    else:
        probe = faults.HARVEST  # the matrix's fetch, and the harvester's fetch of a mask
    honey_badger.enable()
    if probe:
        honey_badger.set_exception(faults.MODULE, probe)
    try:
        host = _run(values, spec, engine=engine)
    finally:
        if probe:
            honey_badger.unset(faults.MODULE, probe)
        honey_badger.disable()
        engine.shutdown()
    _same(host, device)
    stats = host["stats"]
    assert stats["n_fallback_rows"] == len(values) and stats["n_split_launches"] == 1
    assert stats["staging_arena"]["allocs"] == 2
    assert stats["staging_arena"]["free_buffers"] == 0
    assert stats.get("n_device_launches", 0) == (0 if fault == "dispatch_breaker_open" else 1)


# ------------------------------------------------------------------ a cut part
@pytest.mark.parametrize("road", ["matrix", "mask"])
def test_a_part_over_its_ladders_top_is_cut_as_any_launch_is(road):
    """With a read budget behind the engine the strides a launch shows get
    ladders of their own; the narrow part of a later launch is over their
    top (256 rows) and goes in runs of 256, the wide part in one: the bytes
    are the uncut, unsplit launch's."""
    import time

    spec, gather = ROADS[road]
    values = _mix("narrow_wide")
    with _one_stride():
        want = _run(values, spec, gather=gather)
    engine = TpuEngine(row_stride=ROW, host_workers=0, gather_frame=gather)
    engine.governor.configure_autotune(group_ticks_cap=1, tick_read_bytes=256 * ROW // 8)
    try:
        assert engine.enable_coprocessors(
            [(1, spec, ("t",))], partitions={"t": 1}) == [EnableResponseCode.success]
        first = _run(values, spec, engine=engine)  # shows 128 and 640: their ladders start
        _same(first, want)
        assert "n_split_launches" not in first["stats"]  # no ready stride but the lane's own
        t_end = time.monotonic() + 120
        while time.monotonic() < t_end:
            ready = engine.stats()["programs_ready"][1]
            if {128, 640} <= set(ready.get("strides", {})) and all(
                    s["state"] == "ready" for s in ready["strides"].values()):
                break
            time.sleep(0.02)
        assert ready["strides"] == {128: {"buckets": [256], "state": "ready"},
                                    640: {"buckets": [128], "state": "ready"}}
        got = _run(values, spec, engine=engine)
        _same(got, want)
        stats = got["stats"]
        narrow = sum(1 for v in values if len(v) <= 128)
        assert stats["n_split_launches"] == 1 and stats["n_launch_cuts"] == 2
        assert "n_compiles" not in stats
        assert stats["n_staged_rows"] - first["stats"]["n_staged_rows"] == (
            -(-narrow // 256) * 256 + 128)
    finally:
        engine.shutdown()


# ------------------------------------------------------------------ the rule, and the merge
@pytest.mark.parametrize("classes, want", [
    ([(120584, 100), (2621, 200), (7865, 558)], [128, 640]),  # NEXmark's launch: 28.4 MB against 84.9
    ([(18396, 1000), (40, 923)], [1024]),       # config 4: one class, the lane's own
    ([(18396, 774), (100, 502)], [896]),        # NoBench: one fitted stride
    ([(9000, 100), (9000, 774)], [896]),        # a split would save 42%, not half
    ([(260, 1000)], [1024]),                    # a paced launch
    ([(100, 100), (8, 600)], [640]),            # too small: both buckets are the smallest
    ([(16000, 100), (300, 1024)], [128, 1024]),
    ([(17999, 100), (1, 1000)], [128, 1024]),   # one wide value does not widen the rest
    ([(500, 0), (500, 5000)], [128]),           # nothing to stage: empty and oversize values
])
def test_the_split_rule_is_read_off_the_sizes(classes, want):
    engine = TpuEngine(row_stride=ROW, host_workers=0)
    try:
        assert engine.enable_coprocessors([(1, V1MAP, ("t",))]) == [EnableResponseCode.success]
        sizes = np.concatenate([np.full(k, size, np.int32) for k, size in classes])
        np.random.default_rng(1).shuffle(sizes)
        parts = engine._plan_parts(
            engine._lanes[1], sizes, sizes <= ROW, len(sizes), int(sizes[sizes <= ROW].sum())
        )
        assert [p.stride for p in parts] == want
        if len(parts) == 2:
            rows = np.sort(np.concatenate([p.rows for p in parts]))
            assert np.array_equal(rows, np.arange(len(sizes)))
            assert (np.diff(parts[0].rows) > 0).all() and (np.diff(parts[1].rows) > 0).all()
            assert (sizes[parts[0].rows] <= want[0]).all() and (sizes[parts[1].rows] > want[0]).all()
    finally:
        engine.shutdown()


def test_merge_parts_puts_rows_and_bits_back_in_the_launchs_order():
    rng = np.random.default_rng(5)
    n = 1000
    whole = rng.integers(0, 256, (n, 32), dtype=np.uint8)
    narrow = np.sort(rng.choice(n, 900, replace=False))
    wide = np.setdiff1d(np.arange(n), narrow)
    parts = [np.concatenate([whole[r], np.zeros((24, 32), np.uint8)]) for r in (narrow, wide)]
    assert np.array_equal(_merge_parts(parts, [narrow, wide]), whole)
    assert _merge_parts(parts[:1], None) is parts[0]
    keep = rng.integers(0, 2, n).astype(bool)
    bits = [np.packbits(np.concatenate([keep[r], np.zeros(24, bool)])) for r in (narrow, wide)]
    merged = _merge_parts(bits, [narrow, wide])
    assert np.array_equal(np.unpackbits(merged)[:n].astype(bool), keep)
