"""Structural-index parse + device column cache: the parity matrix.

The structural ladder (rp_explode_find2 + rp_extract_cols2) exists ONLY as
a faster executor of exactly what the scalar staged ladder computes — every
cell of the matrix below must be byte-equal: structural vs scalar span
tables, fused vs staged extraction, fused vs staged engine replies (native
and no-native, pool on and off, compressed and zero-record inputs), and
cache hit vs cold launch. The adversarial corpus leans on the places the
two walks could plausibly diverge: escaped quotes, backslash runs, UTF-8
multibyte, nested containers, null/empty values, truncated records.
"""

from __future__ import annotations

import contextlib
import json

import numpy as np
import pytest

from redpanda_tpu.coproc import ProcessBatchRequest, TpuEngine, batch_codec
from redpanda_tpu.coproc import colcache
from redpanda_tpu.coproc import column_plan as cp
from redpanda_tpu.coproc import governor as gov_mod
from redpanda_tpu.coproc.engine import ProcessBatchItem
from redpanda_tpu.models import NTP
from redpanda_tpu.models.record import Compression, Record, RecordBatch
from redpanda_tpu.ops.exprs import field
from redpanda_tpu.ops.transforms import Int, Str, map_project, where


def _native_available() -> bool:
    lib = batch_codec._native()
    return lib is not None and getattr(lib, "has_structural", False)


ADVERSARIAL_VALUES = [
    b'{"level":"error","code":5,"msg":"hello"}',
    b'{"a":"esc\\"aped","level":"in\\\\fo","code":-3.5e2,"msg":""}',
    b'{"level":"\\\\\\"x","nested":{"level":"inner","arr":[1,{"q":"}"}]},'
    b'"code":true,"msg":null}',
    '{"level":"ünïcødé → 日本語","code":42,"msg":"πλ"}'.encode(),
    b'{"code":1e308,"level":"error","msg":"' + b"\\\\" * 31 + b'"}',
    b'  { "level" : "warn" , "code" : 007 , "msg" : [ "a" , "b" ] } ',
    b'{"dup":"first","dup":"second","level":"error","code":0,"msg":"x"}',
    b'["not","an","object"]',
    b"42",
    b'{"truncated":"unterminated string',
    b'{"level":"error","code":',
    b"{}",
    b"",
    b'{"msg":"' + b"x" * 3000 + b'","level":"error","code":9}',
    b'{"level":"a,b}c{","code":"not a number","msg":"{\\"inner\\":1}"}',
    # stringified-JSON payloads: every quote escaped (the memchr-restart
    # pathology the structural escape mask exists for)
    json.dumps({"level": "error", "code": 1,
                "msg": json.dumps({"k": ["v", {"x": 1}]})}).encode(),
    b'{"deep":' + b'[' * 40 + b'1' + b']' * 40 + b',"level":"error",'
    b'"code":3,"msg":"d"}',
]

PATHS = ["level", "code", "msg", "dup", "nested"]


def _adversarial_batches() -> list[RecordBatch]:
    recs = [
        Record(offset_delta=i, value=v)
        for i, v in enumerate(ADVERSARIAL_VALUES)
    ]
    recs.append(Record(offset_delta=len(recs), value=None))  # null value
    batches = [RecordBatch.build(recs, base_offset=0)]
    # a compressed batch of the same corpus (decompress path), and a
    # zero-record batch in the middle of the list
    batches.append(
        RecordBatch.build(recs, base_offset=100, compression=Compression.gzip)
    )
    batches.append(RecordBatch.build([], base_offset=200))
    rng = np.random.default_rng(7)
    for p in range(4):
        more = [
            Record(
                offset_delta=i,
                value=json.dumps({
                    "level": ["error", "info"][i % 2],
                    "code": int(rng.integers(-(10**9), 10**9)),
                    "msg": "y" * int(rng.integers(0, 300)),
                }).encode(),
            )
            for i in range(32)
        ]
        batches.append(RecordBatch.build(more, base_offset=300 + 32 * p))
    return batches


def _assert_tables_equal(a, b):
    """(types, vs, ve) equality with vs/ve compared only where a path was
    found — both kernels leave missing-path spans unwritten (np.empty)."""
    ta, va, ea = a
    tb, vb, eb = b
    assert np.array_equal(ta, tb)
    m = ta != 0
    assert np.array_equal(va[m], vb[m])
    assert np.array_equal(ea[m], eb[m])


@pytest.mark.skipif(not _native_available(), reason="native structural symbols unavailable")
class TestSymbolParity:
    def test_span_tables_bit_identical(self):
        batches = _adversarial_batches()
        scalar = batch_codec.explode_and_find(batches, PATHS)
        sp = batch_codec.explode_find_structural(batches, PATHS, True)
        assert scalar is not None and sp is not None
        ex = scalar[0]
        _assert_tables_equal(scalar[1:], (sp.types, sp.vs, sp.ve))
        assert np.array_equal(ex.offsets, sp.val_off)
        assert np.array_equal(ex.sizes, sp.sizes)
        # the in-crossing joined blob is byte-equal to the Python join
        assert sp.joined.tobytes() == ex.joined

    def test_no_joined_tables_identical(self):
        batches = _adversarial_batches()
        with_blob = batch_codec.explode_find_structural(batches, PATHS, True)
        without = batch_codec.explode_find_structural(batches, PATHS, False)
        assert without.joined is None
        _assert_tables_equal(
            (with_blob.types, with_blob.vs, with_blob.ve),
            (without.types, without.vs, without.ve),
        )
        assert np.array_equal(with_blob.val_off, without.val_off)

    def test_zero_record_launch(self):
        batches = [RecordBatch.build([], base_offset=0)]
        sp = batch_codec.explode_find_structural(batches, PATHS, True)
        assert sp.n == 0 and sp.ranges == [(0, 0)]
        sp2 = batch_codec.explode_find_structural(batches, PATHS, False)
        assert sp2.n == 0 and sp2.joined is None

    def test_fused_extract_matches_staged_gathers(self):
        batches = _adversarial_batches()
        spec = (
            where(field("level") == "error")
            | map_project(Int("code"), Str("msg", 64))
        )
        plan = cp.plan_spec(spec)
        assert plan.structural_eligible()
        paths = plan.flat_paths()
        ex, types, vs, ve = batch_codec.explode_and_find(batches, paths)
        cache = plan.make_cache_from_tables(ex, paths, types, vs, ve)
        n = len(ex.sizes)
        n_pad = 1
        while n_pad < n:
            n_pad *= 2
        staged_cols = plan.extract_device_inputs(
            ex.joined, ex.offsets, ex.sizes, n_pad, cache
        )
        staged_data, staged_ok = plan.extract_projection(
            ex.joined, ex.offsets, ex.sizes, cache
        )
        sp = batch_codec.explode_find_structural(batches, paths, False)
        fused_cols, fused_data, fused_ok = plan.extract_fused(sp, n_pad)
        assert len(staged_cols) == len(fused_cols)
        for a, b in zip(staged_cols, fused_cols):
            assert np.array_equal(a, b)
        assert np.array_equal(staged_ok, fused_ok)
        assert np.array_equal(staged_data[0][1], fused_data[0][1])
        # the predicate over fused columns packs identical bits
        pred_plan = cp.plan_spec(where(field("level") == "error"))
        p_paths = pred_plan.flat_paths()
        s_ex, s_t, s_v, s_e = batch_codec.explode_and_find(batches, p_paths)
        s_cache = pred_plan.make_cache_from_tables(s_ex, p_paths, s_t, s_v, s_e)
        s_cols = pred_plan.extract_device_inputs(
            s_ex.joined, s_ex.offsets, s_ex.sizes, n_pad, s_cache
        )
        f_sp = batch_codec.explode_find_structural(batches, p_paths, True)
        f_cols, _, _ = pred_plan.extract_fused(f_sp, n_pad)
        assert np.array_equal(
            pred_plan.eval_host_mask(s_cols), pred_plan.eval_host_mask(f_cols)
        )

    def test_ineligible_plans_stay_staged(self):
        from redpanda_tpu.ops.transforms import Substr, map_project as mp

        nested = cp.plan_spec(where(field("a.b") == 1))
        assert not nested.structural_eligible()
        general = cp.plan_spec(
            where(field("level") == "error") | mp(Substr("msg", 1, 4))
        )
        assert not general.structural_eligible()


# ---------------------------------------------------------------- engine
def _request(n_items=8, records=32, topic="bench", pad=200) -> ProcessBatchRequest:
    rng = np.random.default_rng(3)
    items = []
    for p in range(n_items):
        recs = [
            Record(
                offset_delta=i,
                value=json.dumps({
                    "level": ["error", "info", "warn"][(p + i) % 3],
                    "code": i,
                    "msg": "x" * (pad + int(rng.integers(0, 50))),
                }).encode(),
            )
            for i in range(records)
        ]
        items.append(
            ProcessBatchItem(
                1, NTP.kafka(topic, p), [RecordBatch.build(recs, base_offset=0)]
            )
        )
    return ProcessBatchRequest(items)


def _adversarial_request() -> ProcessBatchRequest:
    batches = _adversarial_batches()
    return ProcessBatchRequest(
        [ProcessBatchItem(1, NTP.kafka("bench", 0), batches)]
    )


def _payloads(reply):
    return [
        (b.header.crc, b.header.record_count, b.payload)
        for item in reply.items
        for b in item.batches
    ]


PROJ_SPEC = where(field("level") == "error") | map_project(
    Int("code"), Str("msg", 64)
)
PASS_SPEC = where(field("level") == "error")


def _engine(**kw) -> TpuEngine:
    kw.setdefault("row_stride", 512)
    kw.setdefault("force_mode", "columnar_host")
    kw.setdefault("host_workers", 0)
    return TpuEngine(**kw)


@pytest.fixture(autouse=True)
def _fresh_probe():
    TpuEngine.reset_columnar_probe()
    yield


@contextlib.contextmanager
def _ladder(monkeypatch, mode: str):
    """Run the body on the ``structural`` ladder (the rule's pick for an
    eligible plan) or on the ``staged`` one as its oracle: a library
    without the structural entry answers None, and every launch (and mesh
    shard) takes the fall-through the engine keeps for it."""
    with monkeypatch.context() as m:
        if mode == "staged":
            m.setattr(
                batch_codec, "explode_find_structural", lambda *a, **k: None
            )
        yield


def _count_calls(monkeypatch, name: str, calls: dict) -> None:
    real = getattr(batch_codec, name)

    def counted(*a, **k):
        calls[name] += 1
        return real(*a, **k)

    monkeypatch.setattr(batch_codec, name, counted)


class TestEngineParity:
    @pytest.mark.parametrize("spec", [PROJ_SPEC, PASS_SPEC], ids=["proj", "pass"])
    @pytest.mark.parametrize("mesh", [0, 4], ids=["inline", "mesh"])
    def test_structural_vs_staged_bit_identical(
        self, spec, mesh, eight_devices, monkeypatch
    ):
        # the mesh cell runs the same ladders per device shard
        # (_shard_ladder) on a 2-worker pool; force_mode unset there (a
        # columnar_host pin declines the lane)
        req = _request(n_items=32, records=64, pad=60) if mesh else _request()
        adv = _adversarial_request()
        replies = {}
        lane = (
            dict(
                host_workers=2, mesh_devices=mesh, mesh_backend="cpu",
                mesh_probe=False, force_mode=None,
            )
            if mesh
            else {}
        )
        for mode in ("staged", "structural"):
            engine = _engine(**lane)
            try:
                codes = engine.enable_coprocessors(
                    [(1, spec.to_json(), ("bench",))]
                )
                assert codes == [0]
                with _ladder(monkeypatch, mode):
                    replies[mode] = (
                        _payloads(engine.process_batch(req)),
                        _payloads(engine.process_batch(adv)),
                    )
                stats = engine.stats()
            finally:
                engine.shutdown()
            if mesh:
                assert stats["n_mesh_launches"] == 2
            if mode == "staged":
                assert stats.get("t_explode_find2", 0.0) == 0.0
                assert stats.get("t_shard_explode_find2", 0.0) == 0.0
            elif _native_available():
                if mesh:
                    # both launches fanned out: the structural lane ran
                    # per shard (per-shard CPU-seconds under t_shard_*)
                    assert stats.get("t_shard_explode_find2", 0.0) > 0.0
                    assert stats.get("t_shard_fused_extract", 0.0) > 0.0
                else:
                    assert stats.get("t_explode_find2", 0.0) > 0.0
                assert stats.get("t_extract_pred", 0.0) == 0.0
                assert stats.get("t_shard_extract_pred", 0.0) == 0.0
        assert replies["staged"] == replies["structural"]

    def test_structural_pinned_without_native_falls_back(self, monkeypatch):
        # a .so without the structural symbols (or no native at all) must
        # degrade to the staged/python ladder with identical output
        req = _request(n_items=2, records=16)
        engine = _engine()
        try:
            engine.enable_coprocessors([(1, PROJ_SPEC.to_json(), ("bench",))])
            with _ladder(monkeypatch, "staged"):
                baseline = _payloads(engine.process_batch(req))
            assert engine.stats()["parse_path"] == "staged"
        finally:
            engine.shutdown()
        monkeypatch.setattr(batch_codec, "_native", lambda: None)
        monkeypatch.setattr(cp, "_native", lambda: None)
        engine = _engine()
        try:
            engine.enable_coprocessors([(1, PROJ_SPEC.to_json(), ("bench",))])
            assert _payloads(engine.process_batch(req)) == baseline
        finally:
            engine.shutdown()

    def test_zero_record_and_compressed_batches(self, monkeypatch):
        recs = [
            Record(offset_delta=i, value=v)
            for i, v in enumerate(ADVERSARIAL_VALUES[:6])
        ]
        batches = [
            RecordBatch.build([], base_offset=0),
            RecordBatch.build(
                recs, base_offset=10, compression=Compression.gzip
            ),
        ]
        req = ProcessBatchRequest(
            [ProcessBatchItem(1, NTP.kafka("bench", 0), batches)]
        )
        out = {}
        for mode in ("staged", "structural"):
            engine = _engine()
            try:
                engine.enable_coprocessors([(1, PASS_SPEC.to_json(), ("bench",))])
                with _ladder(monkeypatch, mode):
                    out[mode] = _payloads(engine.process_batch(req))
            finally:
                engine.shutdown()
        assert out["staged"] == out["structural"]


@pytest.mark.skipif(not _native_available(), reason="native structural symbols unavailable")
class TestParsePathRule:
    """The ladder is the plan's (``structural_eligible``), at every launch
    size, from the first launch: nothing is measured and nothing pinned."""

    @pytest.mark.parametrize(
        "n_items,records", [(1, 1), (2, 32), (64, 64)],
        ids=["1row", "64rows", "4096rows"],
    )
    def test_eligible_plan_runs_structural_from_first_launch(
        self, n_items, records, monkeypatch
    ):
        calls = {"explode_find_structural": 0, "explode_and_find": 0}
        for name in calls:
            _count_calls(monkeypatch, name, calls)
        seq0 = gov_mod.journal.summary()["seq"]
        engine = _engine()
        try:
            engine.enable_coprocessors([(1, PROJ_SPEC.to_json(), ("bench",))])
            assert engine.stats()["parse_path"] is None
            engine.process_batch(_request(n_items=n_items, records=records))
            stats = engine.stats()
        finally:
            engine.shutdown()
        assert stats["n_launches"] == 1
        assert stats["n_records"] == n_items * records
        assert stats["parse_path"] == "structural"
        assert stats["t_explode_find2"] > 0.0
        assert stats.get("t_explode_find", 0.0) == 0.0
        # the launch's own pass and no other: no calibration
        assert calls == {"explode_find_structural": 1, "explode_and_find": 0}
        assert "parse_probe" not in stats
        assert not [
            e for e in gov_mod.journal.entries(domain="parse_path")
            if e["seq"] > seq0
        ]

    def test_ineligible_plan_runs_staged(self):
        engine = _engine()
        try:
            engine.enable_coprocessors(
                [(1, where(field("a.b") == 1).to_json(), ("bench",))]
            )
            engine.process_batch(_request(n_items=2, records=16))
            stats = engine.stats()
        finally:
            engine.shutdown()
        assert stats["parse_path"] == "staged"
        assert stats.get("t_explode_find2", 0.0) == 0.0
        # a nested path leaves the one-crossing find too: explode, then find
        assert stats.get("t_explode_find", 0.0) + stats.get("t_explode", 0.0) > 0.0


class TestColumnCache:
    def test_fingerprint_changes_on_append(self):
        recs = [
            Record(offset_delta=i, value=b'{"level":"error"}') for i in range(4)
        ]
        b1 = RecordBatch.build(recs, base_offset=0)
        fp1 = colcache.fingerprint([b1])
        appended = recs + [Record(offset_delta=4, value=b'{"level":"info"}')]
        b2 = RecordBatch.build(appended, base_offset=0)
        assert colcache.fingerprint([b2]) != fp1
        # order matters too
        b3 = RecordBatch.build(recs, base_offset=0)
        assert colcache.fingerprint([b1, b3]) != colcache.fingerprint([b1])

    @pytest.mark.parametrize("spec", [PROJ_SPEC, PASS_SPEC], ids=["proj", "pass"])
    def test_hit_is_bit_identical_and_counted(self, spec):
        req = _request()
        engine = _engine(device_column_cache_mb=16)
        try:
            engine.enable_coprocessors([(1, spec.to_json(), ("bench",))])
            cold = _payloads(engine.process_batch(req))
            warm = _payloads(engine.process_batch(req))
            third = _payloads(engine.process_batch(req))
            assert cold == warm == third
            st = engine.stats()["colcache"]
            assert st["misses"] == 1 and st["hits"] == 2
            assert st["entries"] == 1 and st["bytes"] > 0
        finally:
            engine.shutdown()

    def test_device_hit_skips_h2d(self):
        req = _request()
        engine = _engine(
            force_mode="columnar_device", device_column_cache_mb=16
        )
        try:
            engine.enable_coprocessors([(1, PASS_SPEC.to_json(), ("bench",))])
            cold = _payloads(engine.process_batch(req))
            h2d_cold = engine.stats().get("bytes_h2d", 0.0)
            assert h2d_cold > 0
            warm = _payloads(engine.process_batch(req))
            assert warm == cold
            assert engine.stats().get("bytes_h2d", 0.0) == h2d_cold
            assert engine.stats()["colcache"]["hits"] == 1
        finally:
            engine.shutdown()

    def test_append_misses_then_invalidate_hook(self):
        req = _request()
        engine = _engine(device_column_cache_mb=16)
        try:
            engine.enable_coprocessors([(1, PASS_SPEC.to_json(), ("bench",))])
            engine.process_batch(req)
            engine.process_batch(req)
            assert engine.stats()["colcache"]["hits"] == 1
            # "append": a changed batch window must miss (no stale read)
            req2 = _request(pad=201)
            r_new = _payloads(engine.process_batch(req2))
            st = engine.stats()["colcache"]
            assert st["misses"] == 2
            # explicit hook drops the entries; outputs stay identical
            dropped = engine.invalidate_columns(1)
            assert dropped == st["entries"]
            again = _payloads(engine.process_batch(req2))
            assert again == r_new
            assert engine.stats()["colcache"]["invalidations"] >= dropped
        finally:
            engine.shutdown()

    def test_script_disable_drops_entries(self):
        req = _request()
        engine = _engine(device_column_cache_mb=16)
        try:
            engine.enable_coprocessors([(1, PASS_SPEC.to_json(), ("bench",))])
            engine.process_batch(req)
            assert engine.stats()["colcache"]["entries"] == 1
            engine.disable_coprocessors([1])
            assert engine.stats()["colcache"]["entries"] == 0
        finally:
            engine.shutdown()

    def test_lru_eviction_under_budget(self):
        cache = colcache.DeviceColumnCache(3000)

        def entry(nbytes):
            e = colcache.Entry(
                n=1, n_pad=1, ranges=[(0, 1)],
                cols=[np.zeros(nbytes, np.uint8)],
            )
            return e

        assert cache.put((1, 1), entry(1000))
        assert cache.put((1, 2), entry(1000))
        assert cache.put((1, 3), entry(1000))
        # refresh (1,1) so (1,2) is LRU, then push it out
        assert cache.lookup((1, 1)) is not None
        assert cache.put((1, 4), entry(1000))
        assert cache.lookup((1, 2)) is None
        assert cache.lookup((1, 1)) is not None
        st = cache.stats()
        assert st["evictions"] >= 1 and st["bytes"] <= 3000
        # an entry bigger than the whole budget is refused outright
        assert cache.lookup((1, 9)) is None
        assert not cache.put((1, 9), entry(5000))
        assert cache.lookup((1, 9)) is None

    def test_reset_hook_and_stats_shape(self):
        engine = _engine(device_column_cache_mb=8)
        try:
            engine.enable_coprocessors([(1, PASS_SPEC.to_json(), ("bench",))])
            engine.process_batch(_request(n_items=2, records=8))
            engine.reset_column_cache()
            st = engine.stats()["colcache"]
            assert st == {
                "hits": 0, "misses": 0, "entries": 0, "bytes": 0,
                "budget_bytes": 8 << 20, "evictions": 0, "invalidations": 0,
                # memory-pressure posture (resource_mgmt): reset clears it
                "effective_budget_bytes": 8 << 20, "pressure": False,
                "pressure_evictions": 0,
            }
        finally:
            engine.shutdown()

    def test_disabled_cache_reports_nothing(self):
        engine = _engine()
        try:
            engine.enable_coprocessors([(1, PASS_SPEC.to_json(), ("bench",))])
            engine.process_batch(_request(n_items=2, records=8))
            stats = engine.stats()
            assert "colcache" not in stats
            assert engine.invalidate_columns() == 0
        finally:
            engine.shutdown()
