"""Host-stage pool parity tests (ISSUE 3).

The sharded pipeline's whole correctness argument is "contiguous record
ranges + index rebasing == byte-identical to the inline path"; these tests
pin that argument down from three sides:

- partition_counts invariants (contiguity, coverage, no empty shards);
- fused explode_and_find vs split explode_batches+build_find_cache span
  parity, with and without the native lib;
- end-to-end: a sharded engine (workers=4, threshold lowered) produces
  bit-identical replies to workers=0 for all three engine modes.

Plus the frame_ranges empty-ranges regression and the columnar-probe
reset hook.
"""

import json

import numpy as np
import pytest

from redpanda_tpu.coproc import (
    TpuEngine,
    ProcessBatchRequest,
    EnableResponseCode,
)
from redpanda_tpu.coproc import batch_codec, governor, host_pool
from redpanda_tpu.coproc import engine as engine_mod
from redpanda_tpu.coproc.column_plan import plan_spec
from redpanda_tpu.coproc.engine import ProcessBatchItem
from redpanda_tpu.models import Compression, NTP, Record, RecordBatch
from redpanda_tpu.ops.exprs import field
from redpanda_tpu.ops.transforms import (
    Int,
    Str,
    filter_contains,
    identity,
    map_project,
    where,
)

def _columnar_spec():
    return where(field("level") == "error") | map_project(Int("code"), Str("msg", 16))


def _json_batch(n, base_offset=0, codec=Compression.none, empty_every=0):
    recs = []
    for i in range(n):
        if empty_every and i % empty_every == 0:
            value = b""
        else:
            value = json.dumps(
                {"level": ["error", "info"][i % 2], "code": i, "msg": f"m{i}"},
                separators=(",", ":"),
            ).encode()
        recs.append(Record(offset_delta=i, timestamp_delta=i, value=value))
    return RecordBatch.build(
        recs, base_offset=base_offset, compression=codec, first_timestamp=1000
    )


# ------------------------------------------------------------ partitioner
def test_partition_counts_invariants():
    rng = np.random.default_rng(7)
    for _ in range(200):
        n = int(rng.integers(0, 40))
        counts = [int(c) for c in rng.integers(0, 5000, size=n)]
        for shards in (1, 2, 3, 4, 8):
            parts = host_pool.partition_counts(counts, shards)
            if n == 0:
                assert parts == []
                continue
            # contiguous, in order, covering [0, n), no empty slices
            assert parts[0][0] == 0 and parts[-1][1] == n
            for (s0, e0), (s1, e1) in zip(parts, parts[1:]):
                assert e0 == s1
            assert all(e > s for s, e in parts)
            assert len(parts) <= min(shards, n)


def test_partition_counts_balances_records():
    # one fat batch should not drag its neighbours into the same shard
    counts = [10_000, 10, 10, 10_000]
    parts = host_pool.partition_counts(counts, 2)
    totals = [sum(counts[s:e]) for s, e in parts]
    assert len(parts) == 2
    assert max(totals) <= 2 * min(totals)


def test_pool_propagates_first_exception_in_order():
    pool = host_pool.HostStagePool(2)
    try:
        def boom_a():
            raise ValueError("a")

        def boom_b():
            raise KeyError("b")

        with pytest.raises(ValueError):
            pool.run([boom_a, boom_b, lambda: 3])
        assert pool.run([lambda: 1, lambda: 2]) == [1, 2]
    finally:
        pool.shutdown()


# ------------------------------------------------------ frame_ranges empty
def test_pool_shut_down_under_a_fan_out_serves_it_and_the_next():
    """A trial's verdict shuts the pool down while another launch may be
    fanning out: work the executor already holds still runs, and the next
    fan-out gets a new executor (never 'cannot schedule new futures')."""
    import threading

    pool = host_pool.HostStagePool(2)
    started, release = threading.Event(), threading.Event()

    def held():
        started.set()
        release.wait(5.0)
        return "held"

    got = []
    t = threading.Thread(target=lambda: got.append(pool.run([held, lambda: "b"])))
    t.start()
    assert started.wait(5.0)
    pool.shutdown()  # mid fan-out
    assert pool.run([lambda: 1, lambda: 2]) == [1, 2]  # a new executor
    release.set()
    t.join(5.0)
    assert got == [["held", "b"]]
    pool.shutdown()


def test_frame_ranges_empty_ranges_native_and_python(monkeypatch):
    rows = np.zeros((4, 8), np.uint8)
    lens = np.full(4, 8, np.int32)
    keep = np.ones(4, bool)
    # native path (when the lib is present) and the python fallback must
    # BOTH return [] — the native branch used to silently fall through to
    # the per-range list comprehension on empty ranges
    assert batch_codec.frame_ranges(rows, lens, keep, []) == []
    monkeypatch.setattr(batch_codec, "_native", lambda: None)
    assert batch_codec.frame_ranges(rows, lens, keep, []) == []


# ------------------------------------------------------ fused vs split
def _batch_scenarios():
    return {
        "plain": [_json_batch(8), _json_batch(6, base_offset=8)],
        "compressed": [
            _json_batch(8, codec=Compression.lz4),
            _json_batch(6, base_offset=8, codec=Compression.gzip),
        ],
        "empty_values": [_json_batch(9, empty_every=3), _json_batch(5)],
        "zero_record": [_json_batch(0), _json_batch(7), _json_batch(0)],
        "all_zero": [_json_batch(0), _json_batch(0)],
    }


@pytest.mark.parametrize("name", sorted(_batch_scenarios()))
def test_fused_vs_split_parity_native(name):
    lib = batch_codec._native()
    if lib is None or not getattr(lib, "has_explode_find", False):
        pytest.skip("native explode_find unavailable")
    batches = _batch_scenarios()[name]
    plan = plan_spec(_columnar_spec())
    paths = plan.flat_paths()

    fused = batch_codec.explode_and_find(batches, paths)
    assert fused is not None
    ex_f, types_f, vs_f, ve_f = fused

    ex_s = batch_codec.explode_batches(batches)
    np.testing.assert_array_equal(ex_f.offsets, ex_s.offsets)
    np.testing.assert_array_equal(ex_f.sizes, ex_s.sizes)
    assert ex_f.ranges == ex_s.ranges
    assert ex_f.joined == ex_s.joined

    cache = plan.build_find_cache(ex_s.joined, ex_s.offsets, ex_s.sizes)
    if len(ex_s.sizes):
        assert cache is not None
        np.testing.assert_array_equal(types_f, cache.types)
        np.testing.assert_array_equal(vs_f, cache.vs)
        np.testing.assert_array_equal(ve_f, cache.ve)


@pytest.mark.parametrize("name", sorted(_batch_scenarios()))
def test_explode_python_fallback_parity(name, monkeypatch):
    """explode_batches without the native lib must yield the exact same
    offset/size/range tables (same joined blob, same varint layout)."""
    batches = _batch_scenarios()[name]
    native = batch_codec.explode_batches(batches)
    monkeypatch.setattr(batch_codec, "_native", lambda: None)
    py = batch_codec.explode_batches(batches)
    np.testing.assert_array_equal(native.offsets, py.offsets)
    np.testing.assert_array_equal(native.sizes, py.sizes)
    assert native.ranges == py.ranges
    assert native.joined == py.joined


@pytest.mark.parametrize("name", sorted(_batch_scenarios()))
def test_merge_exploded_matches_whole_list(name):
    batches = _batch_scenarios()[name]
    whole = batch_codec.explode_batches(batches)
    parts = host_pool.partition_counts(
        [b.header.record_count for b in batches], 2
    )
    merged = batch_codec.merge_exploded(
        [batch_codec.explode_batches(batches[s:e]) for s, e in parts]
    )
    np.testing.assert_array_equal(whole.offsets, merged.offsets)
    np.testing.assert_array_equal(whole.sizes, merged.sizes)
    assert whole.ranges == merged.ranges
    assert whole.joined == merged.joined


# ------------------------------------------------------ sharded == inline
def _engine_pair_replies(spec, force_mode, monkeypatch, n_batches=6, n_recs=40):
    """Run the same request through workers=0 and workers=4 engines (shard
    threshold lowered so the pool actually engages) and return both reply
    lists plus the sharded engine's stats."""
    monkeypatch.setattr(engine_mod, "_SHARD_MIN_ROWS", 32)
    req = ProcessBatchRequest(
        [
            ProcessBatchItem(
                1,
                NTP.kafka("orders", p),
                [
                    _json_batch(n_recs, base_offset=100 * p),
                    _json_batch(n_recs - 7, base_offset=100 * p + 50, empty_every=5),
                ],
            )
            for p in range(n_batches // 2)
        ]
    )
    replies = []
    stats = None
    for workers in (0, 4):
        engine = TpuEngine(
            row_stride=256,
            compress_threshold=10**9,
            force_mode=force_mode,
            host_workers=workers,
            host_pool_probe=False,  # parity must exercise the fan-out even
            # on boxes whose capacity probe would demote the pool
        )
        codes = engine.enable_coprocessors([(1, spec.to_json(), ("orders",))])
        assert codes == [EnableResponseCode.success]
        replies.append(engine.process_batch(req))
        if workers:
            stats = engine.stats()
    return replies[0], replies[1], stats


@pytest.mark.parametrize(
    "mode_name,spec,force_mode",
    [
        ("columnar", _columnar_spec(), "columnar_host"),
        ("payload", filter_contains(b"error"), None),
        ("host", identity(), None),
    ],
)
def test_sharded_bit_identical_to_inline(mode_name, spec, force_mode, monkeypatch):
    inline, sharded, stats = _engine_pair_replies(spec, force_mode, monkeypatch)
    assert stats["n_sharded_launches"] >= 1, "pool path did not engage"
    assert stats["host_workers"] == 4.0
    assert len(inline.items) == len(sharded.items)
    for a, b in zip(inline.items, sharded.items):
        assert a.source == b.source
        assert len(a.batches) == len(b.batches)
        for ba, bb in zip(a.batches, b.batches):
            assert ba.payload == bb.payload
            assert ba.header.crc == bb.header.crc
            assert ba.header.record_count == bb.header.record_count


def test_sharded_bit_identical_columnar_device(monkeypatch):
    """The device-predicate leg of the sharded path (per-shard launches +
    async mask harvest through _MaskSlot) against the inline device path."""
    inline, sharded, stats = _engine_pair_replies(
        _columnar_spec(), "columnar_device", monkeypatch
    )
    assert stats["n_sharded_launches"] >= 1
    for a, b in zip(inline.items, sharded.items):
        assert [x.payload for x in a.batches] == [y.payload for y in b.batches]


# ------------------------------------------------------ pool calibration
# The decision is taken on what it governs (host_pool.LaunchTrial): the
# first shardable launches run alternately inline and sharded, each timed
# whole, dispatch to sealed reply; medians, sharded must win by PROBE_MARGIN.
def _slowed(monkeypatch, name, seconds):
    """Make ``batch_codec.<name>`` take ``seconds`` longer (a real sleep:
    the trial times real launches)."""
    import time

    real = getattr(batch_codec, name)

    def slow(*a, **kw):
        time.sleep(seconds)
        return real(*a, **kw)

    monkeypatch.setattr(batch_codec, name, slow)


def _fixed_costs(monkeypatch, per_trial):
    """Trials whose samples are the given (inline, sharded) seconds a row,
    one pair per trial in order: the rule is under test, the launches still
    run both roads for real."""
    costs = list(per_trial)
    real_init = host_pool.LaunchTrial.__init__

    def init(self):
        real_init(self)
        self.costs = dict(zip(self.ARMS, costs.pop(0)))

    def add(self, arm, seconds, rows):
        assert seconds > 0 and rows > 0
        self.samples[arm].append(self.costs[arm] * 1e6)

    monkeypatch.setattr(host_pool.LaunchTrial, "__init__", init)
    monkeypatch.setattr(host_pool.LaunchTrial, "add", add)


def _trial_run(monkeypatch, spec, launches, per_arm=2, **engine_kw):
    """Drive ``launches`` shardable launches through an engine whose pool
    decision is still to be measured; returns (engine, stats)."""
    monkeypatch.setattr(engine_mod, "_SHARD_MIN_ROWS", 32)
    monkeypatch.setattr(host_pool, "TRIAL_LAUNCHES", per_arm)
    governor.reset_journal()
    engine = TpuEngine(
        row_stride=256, compress_threshold=10**9, host_workers=4, **engine_kw
    )
    engine.enable_coprocessors([(1, spec.to_json(), ("orders",))])
    req = ProcessBatchRequest(
        [ProcessBatchItem(1, NTP.kafka("orders", 0), [_json_batch(40), _json_batch(40)])]
    )
    want = None
    for _ in range(launches):
        reply = engine.process_batch(req)
        got = [b.payload for b in reply.items[0].batches]
        assert got and (want is None or got == want)  # both roads, same bits
        want = got
    return engine, engine.stats()


def _case_explode_faster_but_whole_launch_slower(monkeypatch):
    # what the old explode-only probe got wrong: the pool explodes faster
    # (the inline explode is slowed), yet the sharded launch as a whole is
    # slower (its merge is slowed more) -> inline
    _slowed(monkeypatch, "explode_ptrs", 0.02)
    _slowed(monkeypatch, "merge_exploded", 0.08)
    engine, stats = _trial_run(monkeypatch, filter_contains(b"error"), 7)
    probe = stats["host_pool_probe"]
    assert probe["chosen"] == "inline" and probe["speedup"] < 1.0
    assert probe["launches"] == {"inline": 2, "sharded": 2}
    assert probe["dropped"] >= 1  # the launch that compiled the program
    assert stats["n_sharded_launches"] == 2  # the trial's own, none after
    return engine


def _case_whole_launch_win_pins_sharded(monkeypatch):
    _slowed(monkeypatch, "explode_ptrs", 0.06)  # the inline road alone
    engine, stats = _trial_run(monkeypatch, filter_contains(b"error"), 8)
    probe = stats["host_pool_probe"]
    assert probe["chosen"] == "sharded"
    assert probe["speedup"] >= host_pool.PROBE_MARGIN
    assert stats["n_sharded_launches"] >= 3  # the trial's two, then every one
    return engine


def _case_win_below_the_margin_keeps_inline(monkeypatch):
    _fixed_costs(monkeypatch, [(0.010, 0.009)])
    engine, stats = _trial_run(
        monkeypatch, _columnar_spec(), 6, force_mode="columnar_host"
    )
    probe = stats["host_pool_probe"]
    assert probe["chosen"] == "inline"
    assert probe["speedup"] == round(10 / 9, 3)
    assert stats["n_sharded_launches"] == 2
    (entry,) = governor.journal.entries(domain="host_pool")
    assert entry["verdict"] == "inline" and "whole launches" in entry["reason"]
    assert entry["inputs"]["samples"] == {
        "inline": [10000.0] * 2, "sharded": [9000.0] * 2
    }
    return engine


def _case_failing_calibration_keeps_inline(monkeypatch):
    def boom(self):
        raise RuntimeError("measurement exploded")

    monkeypatch.setattr(host_pool.LaunchTrial, "verdict", boom)
    engine, stats = _trial_run(
        monkeypatch, _columnar_spec(), 6, force_mode="columnar_host"
    )
    assert engine._pool_decision == "inline" and "host_pool_probe" not in stats
    (entry,) = governor.journal.entries(domain="host_pool")
    assert entry["verdict"] == "inline" and "FAILED" in entry["reason"]
    return engine


def _case_no_clean_sample_keeps_inline(monkeypatch):
    # every launch meets a spoiler (a first run, a probe, a fallback): the
    # trial spends its launches, says so, and the inline path stays
    monkeypatch.setattr(host_pool, "TRIAL_MAX_LAUNCHES", 6)
    monkeypatch.setattr(TpuEngine, "_trial_elapsed", lambda self, mark: None)
    engine, stats = _trial_run(
        monkeypatch, _columnar_spec(), 8, force_mode="columnar_host"
    )
    probe = stats["host_pool_probe"]
    assert probe["chosen"] == "inline" and probe["incomplete"] is True
    assert probe["launches"] == {"inline": 0, "sharded": 0}
    assert probe["dropped"] == 6
    return engine


def _case_recalibration_follows_the_same_rule(monkeypatch):
    _fixed_costs(monkeypatch, [(0.010, 0.009), (0.010, 0.005)])
    engine, stats = _trial_run(
        monkeypatch, _columnar_spec(), 6, per_arm=1,
        force_mode="columnar_host", host_pool_recal_launches=2,
    )
    # launches 1-2: the first trial (inline); 3-4 count to the interval;
    # 4-5: the second trial, which the pool now wins
    assert stats["host_pool_probe_prev"]["chosen"] == "inline"
    assert stats["host_pool_probe"]["chosen"] == "sharded"
    second, first = governor.journal.entries(domain="host_pool")
    assert first["inputs"]["recalibration"] is False
    assert second["inputs"]["recalibration"] is True
    return engine


def _case_unmocked_trial_times_both_roads(monkeypatch):
    engine, stats = _trial_run(
        monkeypatch, _columnar_spec(), 6, force_mode="columnar_host"
    )
    probe = stats["host_pool_probe"]
    assert probe["inline_us_per_row"] > 0 and probe["sharded_us_per_row"] > 0
    assert probe["chosen"] in ("inline", "sharded")
    assert probe["workers"] == 4
    return engine


@pytest.mark.parametrize(
    "case",
    [
        _case_explode_faster_but_whole_launch_slower,
        _case_whole_launch_win_pins_sharded,
        _case_win_below_the_margin_keeps_inline,
        _case_failing_calibration_keeps_inline,
        _case_no_clean_sample_keeps_inline,
        _case_recalibration_follows_the_same_rule,
        _case_unmocked_trial_times_both_roads,
    ],
    ids=lambda f: f.__name__[len("_case_"):],
)
def test_host_pool_trial(case, monkeypatch):
    case(monkeypatch).shutdown()


def test_launch_trial_bookkeeping(monkeypatch):
    monkeypatch.setattr(host_pool, "TRIAL_LAUNCHES", 3)
    monkeypatch.setattr(host_pool, "TRIAL_MAX_LAUNCHES", 9)
    trial = host_pool.LaunchTrial()
    # level: alternate from inline (launches in flight have not sampled)
    assert [trial.next_arm() for _ in range(3)] == ["inline", "sharded", "inline"]
    trial.add("inline", 0.004, 1000)
    assert trial.next_arm() == "sharded"  # the arm that is behind
    for us in (9.0, 1.0, 2.0):
        trial.add("sharded", us * 1e-6 * 500, 500)
    assert trial.next_arm() == "inline" and not trial.complete
    trial.add("inline", 0.006, 1000)
    trial.add("inline", 0.050, 1000)  # one far-off launch moves no median
    assert trial.complete and not trial.exhausted
    got = trial.verdict()
    assert (got["inline_us_per_row"], got["sharded_us_per_row"]) == (6.0, 2.0)
    assert got["speedup"] == 3.0 and got["chosen"] == "sharded"
    assert got["launches"] == {"inline": 3, "sharded": 3} and got["dropped"] == 0
    for _ in range(4):
        trial.next_arm()
    assert trial.exhausted


def test_measure_parallel_capacity_shape():
    got = host_pool.measure_parallel_capacity(2)
    assert set(got) == {"speedup", "workers"}
    assert got["workers"] == 2 and got["speedup"] > 0


# ------------------------------------------------------ probe reset hook
def test_reset_columnar_probe():
    saved = (TpuEngine._columnar_backend, TpuEngine._columnar_probe)
    try:
        TpuEngine._columnar_backend = "host"
        TpuEngine._columnar_probe = {"chosen": "host"}
        engine = TpuEngine(host_workers=0)
        stats = engine.stats()
        assert stats["columnar_backend"] == "host"
        assert stats["columnar_probe"] == {"chosen": "host"}
        TpuEngine.reset_columnar_probe()
        assert TpuEngine._columnar_backend is None
        assert TpuEngine._columnar_probe is None
        assert "columnar_backend" not in engine.stats()
        engine.shutdown()
    finally:
        TpuEngine._columnar_backend, TpuEngine._columnar_probe = saved
