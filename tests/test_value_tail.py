"""Values of any size on the device lane (PR 49).

The payload lane's limit is the deployment's to state (broker property
``coproc_max_value_bytes`` -> ``TpuEngine(row_stride=...)``, default 1,024).
Where it is wider, a launch's values over 1,024 B are staged in width
classes of their own (2,048, 4,096, ... up to the limit) as further parts of
the same launch (``engine._plan_cuts`` / ``TpuEngine._plan_parts``), none
dropped for its size. Held here at a small size on the CPU: the engine
against the benchmark's plain references on ``benchmarks/docs_tail.py``'s
documents and on the size edges, on both splittable roads, on the device
leg, the host fallback and a cut part; the plan read off the sizes, with
the bytes it stages; a launch of k parts merged back in launch order; and
the default limit, which leaves a config-4 launch what it was.
"""

import importlib.util
import json
import os
import time

import numpy as np
import pytest

from redpanda_tpu.config.properties import PROPERTIES
from redpanda_tpu.coproc import EnableResponseCode, ProcessBatchRequest, TpuEngine
from redpanda_tpu.coproc import engine as engine_mod
from redpanda_tpu.coproc import faults
from redpanda_tpu.coproc.engine import (
    ProcessBatchItem, _bucket_rows, _class_strides, _merge_parts, _plan_cuts,
)
from redpanda_tpu.models import NTP, Record, RecordBatch
from redpanda_tpu.observability import probes
from redpanda_tpu.ops.pipeline import IN_META
from redpanda_tpu.ops.transforms import Int, Str, filter_contains, map_project

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
LIMIT = 16384


def _load(relpath: str):
    path = os.path.join(BENCH, relpath)
    spec = importlib.util.spec_from_file_location("tail_" + relpath[:-3].replace("/", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


with open(os.path.join(BENCH, "configs", "json64p-v1-tail.json")) as _f:
    CONFIG = json.load(_f)

# road -> (spec, the plain reference's file, its parameters)
ROADS = {
    # the cell's own script: a keep bit a row, kept values framed from the host's bytes
    "mask": (json.dumps(CONFIG["script"]["spec"]), CONFIG["reference"]["name"],
             CONFIG["reference"]["params"]),
    # a fixed-width projection: a result matrix of its own width
    "matrix": ((filter_contains(b'"level":"error"')
                | map_project(Int("code"), Str("msg", 64))).to_json(),
               "project_error_v1", {"msg_width": 64, "row_stride": LIMIT}),
}


def _doc(i: int, width: int, needle_last: bool = False) -> bytes:
    """A config-4 document exactly ``width`` bytes long, every third one an
    error, every third a warn; ``needle_last``: its level is its LAST field,
    so the filter's needle lies in the value's last bytes."""
    level = (b"error", b"warn", b"info")[i % 3]
    if needle_last:
        head, tail = b'{"code":%d,"msg":"m%d","pad":"' % (i, i), b'","level":"%s"}' % level
    else:
        head, tail = b'{"level":"%s","code":%d,"msg":"m%d","pad":"' % (level, i, i), b'"}'
    return head + b"x" * (width - len(head) - len(tail)) + tail


def _edges() -> list:
    widths = [1024, 1025, 2048, 2049, 4096, 4097, 8192, 8193, 16384, 16385, 20000]
    out = [_doc(3 * k + lv, w) for k, w in enumerate(widths) for lv in range(3)]
    out += [_doc(3 * k + lv, w, needle_last=True)
            for k, w in enumerate([1025, 2049, 16384, 16385]) for lv in range(2)]
    return out + [None, b""]


def _values(seed: int = 2**31 + 49, n: int = 700) -> list:
    """The size law's own documents with the edges among them."""
    docs = _load("docs_tail.py").make_documents(
        seed, 1, n, **CONFIG["documents"]["params"])[0]
    edges = _edges()
    # ... and enough large ones that every class overflows the row bucket
    # of the part above it (at this size the law's own few would ride there)
    large = [_doc(7 * k, w) for k in range(110) for w in (4000, 8000, 16000)]
    return docs[:100] + edges + large[:150] + docs[100:] + edges[::-1] + large[150:]


def _batches(values, per_batch: int = 32) -> list[RecordBatch]:
    return [
        RecordBatch.build(
            [Record(offset_delta=i, timestamp_delta=i, value=v)
             for i, v in enumerate(values[s : s + per_batch])],
            base_offset=s, first_timestamp=1000,
        )
        for s in range(0, len(values), per_batch)
    ]


def _launch(engine: TpuEngine, values) -> list[bytes]:
    reply = engine.submit(ProcessBatchRequest(
        [ProcessBatchItem(1, NTP.kafka("t", 0), _batches(values))])).result()
    return [r.value for item in reply.items for b in item.batches for r in b.records()]


def _engine(spec: str, *, limit: int = LIMIT, budget_rows: int | None = None, **kw) -> TpuEngine:
    engine = TpuEngine(row_stride=limit, host_workers=0, **kw)
    if budget_rows:  # a read budget: ladders are built, their tops follow it
        engine.governor.configure_autotune(
            group_ticks_cap=1, tick_read_bytes=budget_rows * min(limit, 1024) // 8)
    assert engine.enable_coprocessors(
        [(1, spec, ("t",))], partitions={"t": 1}) == [EnableResponseCode.success]
    return engine


def _want(road: str, values) -> list[bytes]:
    _spec, name, params = ROADS[road]
    ref = _load("references/" + name + ".py")
    return [o for o in (ref.reference(v, **params) for v in values) if o is not None]


# ------------------------------------------------------------------ the engine against the plain reference
@pytest.mark.parametrize("leg", ["device", "host_fallback"])
@pytest.mark.parametrize("road", sorted(ROADS))
def test_the_engine_is_the_plain_reference_on_the_size_law_and_the_edges(road, leg, monkeypatch):
    # a launch of 1,100 rows in as many parts as one of 20,000: matrices of
    # 128-256 rows save tenths of the rule's own megabyte
    monkeypatch.setattr(engine_mod, "_PART_MIN_SAVING_BYTES", 1 << 16)
    values = _values()
    want = _want(road, values)
    wide = [v for v in values if v and 1024 < len(v) <= LIMIT]
    assert len(wide) > 150 and 0 < len(_want(road, wide)) < len(wide)  # the classes carry kept values
    engine = _engine(
        ROADS[road][0],
        **({"launch_retries": 0, "breaker_threshold": 1, "breaker_cooldown_ms": 3_600_000}
           if leg == "host_fallback" else {}),
    )
    if leg == "host_fallback":  # breaker open at dispatch: each part re-runs in numpy
        engine.governor.breaker_for(faults.DEVICE_DISPATCH).record_failure()
    before = {k: c.value for k, c in probes.coproc_width_classes.items()}
    try:
        got = _launch(engine, values)
        stats = engine.stats()
    finally:
        engine.shutdown()
    assert got == want
    n = len(values)
    assert stats["n_records"] == n and stats["n_launches"] == 1
    # over the stated limit: dropped, never truncated, and counted; nothing else is
    assert stats["n_oversize_rows"] == sum(1 for v in values if v and len(v) > LIMIT) == 16
    assert stats["n_kept_rows"] == len(want)
    if leg == "host_fallback":
        assert stats["n_fallback_rows"] == n and "n_device_launches" not in stats
    else:
        assert stats.get("n_fallback_rows", 0) == 0 and stats["n_device_launches"] == 1
    # one part for the rows up to 1,024 B and one a class above it
    assert stats["n_parts"] == 5 and stats["n_split_launches"] == 1
    if leg == "device":
        assert sorted(c["stride"] for c in stats["compiled_programs"]) == [
            1024, 2048, 4096, 8192, 16384]
    assert stats["n_wide_rows"] == len(wide)
    assert stats["bytes_staged_values_wide"] == sum(map(len, wide))
    assert stats["bytes_staged_values"] == sum(len(v) for v in values if v and len(v) <= LIMIT)
    by_class = np.bincount(np.searchsorted([1024, 2048, 4096, 8192, 16384],
                                           [len(v) for v in wide]), minlength=5)
    assert stats["bytes_staged_wide"] == sum(
        _bucket_rows(int(k)) * (s + IN_META)
        for k, s in zip(by_class[1:], [2048, 4096, 8192, 16384]))
    assert stats["bytes_staged"] == stats["bytes_staged_wide"] + _bucket_rows(
        n - len(wide)) * (1024 + IN_META)
    # ... mirrored as probes
    moved = {k: c.value - before[k] for k, c in probes.coproc_width_classes.items()}
    assert moved == {k: stats[k] for k in moved}


@pytest.mark.parametrize("road", sorted(ROADS))
def test_parts_over_their_ladders_tops_are_cut_and_the_launch_is_the_references(road, monkeypatch):
    """With a read budget behind the engine every class has a ladder (the
    lane's own, 16,384 B, built at deploy; the others at the first sight of
    their stride), each with the top its stride is sized for. A launch
    larger than the budget is cut at every stride and answers as before."""
    monkeypatch.setattr(engine_mod, "_WANT_HOLD_S", 0.0)
    monkeypatch.setattr(engine_mod, "_PART_MIN_SAVING_BYTES", 1 << 16)  # five parts of 1,100 rows
    values = _values()
    want = _want(road, values)
    engine = _engine(ROADS[road][0], budget_rows=256)
    try:
        lane = engine._lanes[1]
        assert [engine._ladder_top(1, s) for s in (128, 1024, 2048, 4096, 8192, LIMIT)] == [
            256, 256, 128, 128, 128, 128]
        assert _launch(engine, values) == want  # shows the strides: their ladders start
        t_end = time.monotonic() + 120
        while time.monotonic() < t_end:
            ready = engine.stats()["programs_ready"][1]
            if ready["state"] == "ready" and len(ready.get("strides", {})) == 4 and all(
                    s["state"] == "ready" for s in ready["strides"].values()):
                break
            time.sleep(0.02)
        assert ready["top"] == 128 and {s: v["buckets"] for s, v in ready["strides"].items()} == {
            1024: [256], 2048: [128], 4096: [128], 8192: [128]}
        first = engine.stats()
        assert _launch(engine, values) == want
        stats = engine.stats()
        assert sorted(lane.fns) == [1024, 2048, 4096, 8192, LIMIT]
        assert stats["n_parts"] - first["n_parts"] == 5
        assert stats["n_launch_cuts"] - first.get("n_launch_cuts", 0) == 1
        assert "n_compiles" not in stats and stats.get("n_fallback_rows", 0) == 0
        body = sum(1 for v in values if not v or len(v) <= 1024 or len(v) > LIMIT)
        assert body > 256  # the body part: runs of the 256-row program
        assert stats["n_staged_rows"] - first["n_staged_rows"] >= -(-body // 256) * 256 + 4 * 128
    finally:
        engine.shutdown()


def test_a_bucket_first_seen_rides_padded_in_a_ready_one_and_is_built_meanwhile(monkeypatch):
    """On a lane with classes above 1,024 B a part whose own row bucket is
    not built yet runs the smallest ready bucket of its stride of up to
    four times its rows, padded to it, and its own is left for the builder
    (which had ended, and is woken); a part that would need more than that
    rides in the next part up. Nothing compiles on the serving path."""
    monkeypatch.setattr(engine_mod, "_WANT_HOLD_S", 0.0)
    engine = _engine(ROADS["mask"][0], budget_rows=4096)
    try:
        lane = engine._lanes[1]

        def built(stride):
            t_end = time.monotonic() + 120
            while time.monotonic() < t_end:
                strides = engine.stats()["programs_ready"][1].get("strides", {})
                if strides.get(stride, {}).get("state") == "ready":
                    return strides[stride]["buckets"]
                time.sleep(0.02)
            raise AssertionError(strides)

        # 800 rows of ~1,500 B: the 2,048 B ladder starts and builds their bucket
        assert not engine._stride_ready(lane, 2048, 800, 800, 800 * 1500)
        assert built(2048) == [1024]
        ladder = engine._ladders[lane.fns[2048][0]]
        # 400 rows: their own bucket, 512, is wanted; meanwhile the 1,024-row program
        assert engine._stride_ready(lane, 2048, 400, 400, 400 * 1500)
        assert ladder.program_for(512, 1.0)[1] in (512, 1024)
        assert built(2048) == [512, 1024]
        assert ladder.program_for(512, 1.0)[1] == 512
        # 100 rows would be padded four times over and more: they go wider instead
        assert ladder.program_for(128, 0.0)[1] == 512
        ladder.programs.pop(512)
        assert not engine._stride_ready(lane, 2048, 100, 100, 100 * 1500, start=False)
        assert engine.stats().get("n_compiles", 0) == 0
    finally:
        engine.shutdown()


def test_the_default_limit_drops_what_it_dropped_and_stages_what_it_staged():
    """``coproc_max_value_bytes`` at its default: a launch of config 4's
    documents (and the edges) is what it was, one part at 1,024 B, every
    value over it dropped and counted; the kept values are the reference's
    at 1,024."""
    (prop,) = [p for p in PROPERTIES if p.name == "coproc_max_value_bytes"]
    assert prop.default == 1024
    values = _load("docs.py").make_documents(2**31 + 49, 1, 600)[0]  # config 4's own, 923-1,060 B
    values = values[:300] + _edges() + values[300:]
    spec, name, params = ROADS["mask"]
    ref = _load("references/" + name + ".py")
    want = [o for o in (ref.reference(v, **{**params, "row_stride": 1024}) for v in values)
            if o is not None]
    assert 150 < len(want) < 250
    assert list(_class_strides(1024)) == list(range(128, 1025, 128))
    engine = _engine(spec, limit=prop.default)
    try:
        assert _launch(engine, values) == want
        stats = engine.stats()
    finally:
        engine.shutdown()
    n = len(values)
    assert stats["n_oversize_rows"] == sum(1 for v in values if v and len(v) > 1024) > 100
    assert stats["n_parts"] == 1 and "n_split_launches" not in stats and "n_wide_rows" not in stats
    assert stats["bytes_staged"] == stats["bytes_h2d"] == _bucket_rows(n) * (1024 + IN_META)
    assert [c["stride"] for c in stats["compiled_programs"]] == [1024]


def test_a_spec_that_cannot_be_split_stays_fitted_at_one_stride():
    """A filter on the matrix road (the gather harvest off) hands back a
    row as wide as the staged one: parts of several strides could not be
    merged, so the launch is one part at the class of its widest value."""
    values = _values(n=200)
    engine = _engine(ROADS["mask"][0], gather_frame=False)
    try:
        assert _launch(engine, values) == _want("mask", values)
        stats = engine.stats()
    finally:
        engine.shutdown()
    assert stats["n_parts"] == 1 and "n_split_launches" not in stats
    assert [c["stride"] for c in stats["compiled_programs"]] == [LIMIT]
    assert stats["n_wide_rows"] == stats["n_records"] == len(values)


# ------------------------------------------------------------------ the plan, read off the sizes
def _law_hist(n: int) -> list:
    """(rows, size) by width class for ``n`` rows of the cell's size law."""
    edges = [384, 512, 640, 768, 896, 1024, 2048, 4096, 8192, 16384]
    over = [n * (384 / e) ** 1.2 for e in edges]  # rows wider than each edge
    return [(round(a - b), hi) for a, b, hi in zip(over, over[1:] + [0.0], edges[1:] + [LIMIT])]


@pytest.mark.parametrize("classes, want, staged_mb", [
    # a body alone: PR 47's rule, one fitted stride (a split would not halve it)
    (_law_hist(16384)[:5], [1024], 16.9),
    ([(18396, 1000), (40, 923)], [1024], 33.8),           # config 4's launch
    ([(120584, 100), (2621, 200), (7865, 558)], [128, 640], 28.4),  # NEXmark's, under a wider limit
    # a body and one wide class
    ([(16000, 700), (300, 3000)], [768, 4096], 14.8),
    ([(16000, 700), (3, 16384)], [768, 16384], 14.8),     # three values at the cap do not widen the rest
    ([(16000, 100), (300, 900), (50, 3000)], [128, 1024, 4096], 3.3),  # two parts below, one above
    ([(100, 700), (5000, 1500)], [2048], 16.8),           # a body too small for a part of its own
    # the full tail, a steady launch of the cell: five parts
    (_law_hist(20000), [1024, 2048, 4096, 8192, 16384], 50.5),
    (_law_hist(2600), [1024, 2048, 4096, 8192, 16384], 7.4),  # the first step of the ramp
    # ... or three, where a class count sits just over a row bucket's edge
    (_law_hist(15600), [1024, 4096, 16384], 50.5),
    # a class too thin to pay for a matrix rides in the next one up
    ([(16000, 700), (3, 1500), (40, 3000)], [768, 4096], 13.2),
    ([(16000, 700), (100, 1500), (100, 3000), (5, 7000), (100, 16000)], [768, 4096, 16384], 15.9),
    # every row at the cap
    ([(4000, 16384)], [16384], 67.1),
    ([(500, 0), (500, 20000)], [128], 0.1),               # nothing to stage: empty and oversize values
])
def test_the_plan_is_read_off_the_sizes(classes, want, staged_mb):
    engine = _engine(ROADS["matrix"][0])
    try:
        sizes = np.concatenate([np.full(k, size, np.int32) for k, size in classes])
        np.random.default_rng(1).shuffle(sizes)
        fits = sizes <= LIMIT
        parts = engine._plan_parts(
            engine._lanes[1], sizes, fits, len(sizes), int(sizes[fits].sum()))
    finally:
        engine.shutdown()
    assert [p.stride for p in parts] == want
    staged = sum(
        _bucket_rows(len(sizes) if p.rows is None else len(p.rows)) * (p.stride + IN_META)
        for p in parts)
    assert round(staged / 1e6, 1) == staged_mb
    if len(parts) > 1:
        rows = np.concatenate([p.rows for p in parts])
        assert np.array_equal(np.sort(rows), np.arange(len(sizes)))  # every row in one part
        below = 0
        for p in parts:
            assert (np.diff(p.rows) > 0).all()  # in launch order inside its part
            held = np.where(fits[p.rows], sizes[p.rows], 0)
            assert (held <= p.stride).all() and (held > below).sum() > 0
            below = p.stride


def test_a_further_part_is_taken_only_where_it_pays():
    """Above 1,024 B a class is a part of its own where that leaves
    ``_PART_MIN_SAVING_BYTES`` fewer staged bytes than riding in the next
    part up; a spec that cannot be split is one part whatever the sizes."""
    strides = _class_strides(LIMIT)
    assert list(strides) == [128, 256, 384, 512, 640, 768, 896, 1024, 2048, 4096, 8192, 16384]
    assert list(_class_strides(3000)) == [128, 256, 384, 512, 640, 768, 896, 1024, 2048, 3000]
    assert list(_class_strides(600)) == [128, 256, 384, 512, 600]

    def cuts(**by_stride):
        hist = np.zeros(len(strides), np.int64)
        for stride, rows in by_stride.items():
            hist[list(strides).index(int(stride[1:]))] = rows
        return [int(strides[c]) for c in _plan_cuts(hist, strides, True)], hist

    # 128 rows at 4,096 B are one bucket either way: alone they cost a
    # 0.5 MB matrix and spare the part above nothing
    assert cuts(s1024=1000, s4096=100, s16384=28)[0] == [1024, 16384]
    # 129 of them push the part above into its next bucket (2.1 MB more)
    assert cuts(s1024=1000, s4096=100, s16384=29)[0] == [1024, 4096, 16384]
    got, hist = cuts(s1024=14000, s2048=3000, s4096=1500, s8192=700, s16384=500)
    assert got == [1024, 2048, 4096, 8192, 16384]
    # 600 + 300 rows fill one 1,024-row bucket at 16,384 B: as many bytes
    # as their two matrices, and one part fewer
    assert cuts(s1024=14000, s2048=3000, s4096=1500, s8192=600, s16384=300)[0] == [
        1024, 2048, 4096, 16384]
    assert [int(strides[c]) for c in _plan_cuts(hist, strides, False)] == [16384]
    assert engine_mod._PART_MIN_SAVING_BYTES == 1 << 20 and engine_mod._BODY_STRIDE == 1024


def test_merge_parts_puts_k_parts_back_in_the_launchs_order():
    rng = np.random.default_rng(49)
    n, k = 3000, 5
    whole = rng.integers(0, 256, (n, 78), dtype=np.uint8)
    owner = rng.choice(k, n, p=[0.69, 0.17, 0.08, 0.04, 0.02])
    rows = [np.flatnonzero(owner == j) for j in range(k)]
    pad = [_bucket_rows(len(r)) - len(r) for r in rows]
    parts = [np.concatenate([whole[r], np.zeros((p, 78), np.uint8)]) for r, p in zip(rows, pad)]
    assert np.array_equal(_merge_parts(parts, rows), whole)
    keep = rng.integers(0, 2, n).astype(bool)
    bits = [np.packbits(np.concatenate([keep[r], np.zeros(p, bool)])) for r, p in zip(rows, pad)]
    merged = _merge_parts(bits, rows)
    assert np.array_equal(np.unpackbits(merged)[:n].astype(bool), keep)
