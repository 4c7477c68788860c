"""Parity tests for the v2 expression DSL and the columnar engine path.

Three layers, each checked against the one below:
1. `host_eval` (ops/exprs.py) is the normative semantics.
2. The native columnarizer + device predicate program must agree with
   host_eval on every record (device parity, the core guarantee).
3. The engine's columnar mode must produce byte-identical output batches to
   a straight host reimplementation of the same transform.

Reference bar: arbitrary JS apply() per record
(/root/reference/src/js/modules/public/SimpleTransform.ts:18); the DSL's
coverage is the op set exercised here.
"""

from __future__ import annotations

import json

import numpy as np
import pytest

from redpanda_tpu.coproc.column_plan import ColumnarPlan, plan_spec
from redpanda_tpu.coproc.engine import (
    ProcessBatchItem,
    ProcessBatchRequest,
    TpuEngine,
)
from redpanda_tpu.models import NTP, Record, RecordBatch
from redpanda_tpu.ops import exprs as E
from redpanda_tpu.ops.exprs import field, host_eval
from redpanda_tpu.ops.transforms import (
    Concat,
    Float,
    Int,
    Str,
    Substr,
    TransformSpec,
    map_project,
    where,
)

DOCS = [
    {"level": "error", "code": 500, "msg": "boom"},
    {"level": "info", "code": 200, "msg": "fine"},
    {"level": "error", "code": 42, "msg": "xx"},
    {"level": "warn", "meta": {"retriable": True, "n": 3}, "code": 503},
    {"code": 1.5, "msg": "nolevel"},
    {"level": "error", "msg": "nocode"},
    {"level": "errorx", "code": 500},
    {"level": "", "code": 0},
    {"level": None, "code": -7},
    {"level": True, "code": 2**31 - 1},
    {"level": "error", "code": 2**31},  # int32 overflow -> f32 lattice
    {"level": "error", "code": 499.5},
    {"level": "error", "code": "500"},  # string-typed number
    {"meta": {"retriable": False}},
    {"meta": "flat"},
    {"msg": "needle in a haystack", "code": 1},
    {"msg": "no ndl here", "code": 2},
    {"deep": {"a": {"b": 9}}},
    {},
]


def _vals():
    return [json.dumps(d, separators=(",", ":")).encode() for d in DOCS]


EXPRS = [
    field("level") == "error",
    field("level") != "error",
    field("code") == 500,
    field("code") != 500,
    field("code") < 100,
    field("code") <= 42,
    field("code") > 499,
    field("code") >= 500,
    field("code") >= 499.6,
    field("level") == True,  # noqa: E712 — DSL overload, not a py comparison
    field("level") == None,  # noqa: E711
    field("level") != None,  # noqa: E711
    field("level").exists(),
    ~field("level").exists(),
    field("meta.retriable") == True,  # noqa: E712
    field("meta.n") >= 3,
    field("deep.a.b") == 9,
    field("msg").contains(b"needle"),
    field("msg").contains(b"ndl", window=6),
    (field("level") == "error") & (field("code") >= 100),
    (field("level") == "error") | (field("code") < 2),
    ~((field("level") == "error") & (field("code") >= 100)),
    (field("level") == "error")
    & ((field("code") >= 500) | ~field("msg").exists()),
]


def _device_eval(expr, vals) -> np.ndarray:
    """Run the columnar device program the way the engine does."""
    spec = where(expr)
    plan = plan_spec(spec)
    assert isinstance(plan, ColumnarPlan)
    joined = b"".join(vals)
    offsets = np.cumsum([0] + [len(v) for v in vals[:-1]]).astype(np.int64)
    sizes = np.array([len(v) for v in vals], np.int32)
    n = len(vals)
    n_pad = ((n + 7) // 8) * 8
    cols = plan.extract_device_inputs(joined, offsets, sizes, n_pad)
    fn = plan.compile_device()
    bits = np.asarray(fn(*cols))
    return np.unpackbits(bits)[:n].astype(bool)


class TestOracleVsDevice:
    @pytest.mark.parametrize("idx", range(len(EXPRS)))
    def test_parity(self, idx):
        expr = EXPRS[idx]
        vals = _vals()
        want = np.array([host_eval(expr, v) for v in vals])
        got = _device_eval(expr, vals)
        assert (want == got).all(), (
            f"expr #{idx} mismatch: want {want.tolist()} got {got.tolist()}"
        )

    def test_padding_rows_never_match(self):
        # Bucket padding rows (vlen -1 / flags 0) must stay False even for
        # negated trees that would match an empty record.
        expr = ~field("level").exists()
        vals = _vals()
        got = _device_eval(expr, vals)
        want = np.array([host_eval(expr, v) for v in vals])
        # host_eval on real records is the contract; padding is sliced off.
        assert (want == got).all()


class TestNativeWalkerParity:
    def test_json_find_matches_python(self):
        from redpanda_tpu.native import lib

        if lib is None:
            pytest.skip("native lib unavailable")
        paths = ["level", "code", "msg", "meta.retriable", "meta.n", "deep.a.b", "nope.x"]
        for v in _vals():
            for p in paths:
                assert lib.json_find(v, p) == E.json_find(v, p), (v, p)

    def test_tricky_json(self):
        from redpanda_tpu.native import lib

        if lib is None:
            pytest.skip("native lib unavailable")
        tricky = [
            b'{"a":"has \\"quote\\"","b":1}',
            b'{"a":{"b":"}"},"b":2}',
            b'{"a":[1,2,{"b":3}],"b":4}',
            b'{ "a" : 1 , "b" : { "c" : "x" } }',
            b'{"a":1',  # truncated
            b"[1,2,3]",  # not an object
            b"",
            b'{"b":1,"a":2,"b":3}',  # duplicate key: first wins
        ]
        for v in tricky:
            for p in ["a", "b", "a.b", "b.c"]:
                assert lib.json_find(v, p) == E.json_find(v, p), (v, p)

    def test_num_lattice_parity(self):
        from redpanda_tpu.native import lib

        if lib is None:
            pytest.skip("native lib unavailable")
        toks = [
            "0", "-0", "1", "-1", "42", "1.5", "-2.75", "1e3", "1e-3",
            "999999999", "2147483647", "2147483648", "-2147483648",
            "-2147483649", "3.0", "0.1", "1e40", "-1e40", "12345678901234567890",
            "1." + "0" * 50 + "1",  # >= 48 chars: PRESENT-only on both paths
        ]
        docs = [f'{{"x":{t}}}'.encode() for t in toks]
        joined = b"".join(docs)
        offsets = np.cumsum([0] + [len(d) for d in docs[:-1]]).astype(np.int64)
        sizes = np.array([len(d) for d in docs], np.int32)
        f32, i32, fl = lib.extract_num(joined, offsets, sizes, "x")
        for i, d in enumerate(docs):
            h = E.host_field(d, "x")
            assert fl[i] == h["flags"], (toks[i], fl[i], h["flags"])
            assert i32[i] == h["i32"], toks[i]
            assert np.float32(f32[i]) == np.float32(h["f32"]) or (
                np.isnan(f32[i]) and np.isnan(h["f32"])
            ), toks[i]


class TestSerde:
    @pytest.mark.parametrize("idx", range(len(EXPRS)))
    def test_roundtrip(self, idx):
        expr = EXPRS[idx]
        spec = where(expr) | map_project(Int("code"), Str("msg", 16))
        back = TransformSpec.from_json(spec.to_json())
        assert back.to_json() == spec.to_json()
        # and the roundtripped tree evaluates identically
        for v in _vals():
            assert host_eval(back.where, v) == host_eval(expr, v)

    def test_projection_fields_roundtrip(self):
        spec = where(field("code") >= 0) | map_project(
            Int("code"), Float("ratio"), Str("msg", 32),
            Substr("msg", 2, 8), Concat("level", "msg", 24),
        )
        back = TransformSpec.from_json(spec.to_json())
        assert back.to_json() == spec.to_json()


class TestEngineColumnar:
    def _run(self, spec, docs, n_batches=1, stats=None, **engine_kw):
        """The kept values of one launch over ``docs`` in ``n_batches``
        batches; ``stats``, when given, takes the engine's afterwards."""
        vals = [json.dumps(d, separators=(",", ":")).encode() for d in docs]
        per = -(-len(vals) // n_batches)
        batches = [
            RecordBatch.build(
                [
                    Record(offset_delta=i, timestamp_delta=i, value=v)
                    for i, v in enumerate(vals[s : s + per])
                ],
                base_offset=s, first_timestamp=5,
            )
            for s in range(0, len(vals), per)
        ]
        eng = TpuEngine(row_stride=256, **engine_kw)
        try:
            codes = eng.enable_coprocessors([(1, spec.to_json(), ("t",))])
            assert codes[0] == 0
            req = ProcessBatchRequest([ProcessBatchItem(1, NTP.kafka("t", 0), batches)])
            reply = eng.process_batch(req)
            assert len(reply.items) == 1
            out = []
            for b in reply.items[0].batches:
                assert b.verify_kafka_crc()
                out.extend(r.value for r in b.records())
            if stats is not None:
                stats.update(eng.stats())
            return out
        finally:
            eng.shutdown()

    def test_filter_project(self):
        spec = where(
            (field("level") == "error") & (field("code") >= 100)
        ) | map_project(Int("code"), Str("msg", 16))
        out = self._run(spec, DOCS)
        want = []
        for d in DOCS:
            v = json.dumps(d, separators=(",", ":")).encode()
            if not host_eval((field("level") == "error") & (field("code") >= 100), v):
                continue
            if not isinstance(d.get("code"), int) or abs(d["code"]) > 999_999_999:
                continue
            m = d.get("msg")
            if not isinstance(m, str) or len(m) > 16:
                continue
            enc = m.encode()
            want.append(
                int(d["code"]).to_bytes(4, "little", signed=True)
                + len(enc).to_bytes(2, "little")
                + enc.ljust(16, b"\x00")
            )
        assert out == want

    def test_passthrough_filter(self):
        spec = where(field("level") == "error")
        out = self._run(spec, DOCS)
        want = [
            json.dumps(d, separators=(",", ":")).encode()
            for d in DOCS
            if d.get("level") == "error"
        ]
        assert out == want

    def test_projection_with_trivial_where(self):
        # Columnar projection semantics (exact ints only) opt in via where().
        spec = where(field("code").exists()) | map_project(Int("code"))
        out = self._run(spec, DOCS)
        want = [
            int(d["code"]).to_bytes(4, "little", signed=True)
            for d in DOCS
            if isinstance(d.get("code"), int)
            and not isinstance(d.get("code"), bool)
            and abs(d["code"]) <= 999_999_999
        ]
        assert out == want

    def test_projection_only_keeps_v1_payload_semantics(self):
        # A v1 map_project-only spec must keep v1 outputs across the
        # upgrade: _parse_int_at truncates "3.5" -> 3 instead of dropping.
        from redpanda_tpu.coproc.column_plan import plan_spec

        spec = map_project(Int("code"))
        assert plan_spec(spec).mode == "payload"
        out = self._run(spec, [{"code": 3.5}, {"code": 7}])
        assert out == [
            (3).to_bytes(4, "little", signed=True),
            (7).to_bytes(4, "little", signed=True),
        ]

    def test_substr_concat_float(self):
        docs = [
            {"a": "hello", "b": "world", "r": 2.5},
            {"a": "x", "b": "yz", "r": -1.25},
            {"a": "toolongforslot", "b": "", "r": 0.0},
        ]
        spec = where(field("r").exists()) | map_project(
            Float("r"), Substr("a", 1, 3), Concat("a", "b", 8)
        )
        out = self._run(spec, docs)
        assert len(out) == 3
        for d, v in zip(docs, out):
            r = np.frombuffer(v[:4], np.float32)[0]
            assert r == np.float32(d["r"])
            slen = int.from_bytes(v[4:6], "little")
            sub = d["a"][1:4].encode()
            assert slen == len(sub) and v[6 : 6 + slen] == sub
            clen = int.from_bytes(v[9:11], "little")
            cat = (d["a"] + d["b"]).encode()[:8]
            assert clen == len(cat) and v[11 : 11 + clen] == cat

    def test_py_escape_hatch(self):
        def fn(value: bytes):
            d = json.loads(value)
            if d.get("code", 0) % 2:
                return None
            return json.dumps({"c": d.get("code", 0) * 2}).encode()

        vals = [json.dumps({"code": i}).encode() for i in range(6)]
        recs = [Record(offset_delta=i, value=v) for i, v in enumerate(vals)]
        batch = RecordBatch.build(recs, base_offset=0)
        eng = TpuEngine()
        assert eng.enable_py_transform(7, fn, ("t",)) == 0
        req = ProcessBatchRequest([ProcessBatchItem(7, NTP.kafka("t", 0), [batch])])
        reply = eng.process_batch(req)
        out = [r.value for b in reply.items[0].batches for r in b.records()]
        assert out == [json.dumps({"c": i * 2}).encode() for i in range(6) if i % 2 == 0]
        eng.shutdown()

    def test_mesh_columnar(self, eight_devices):
        # the chips the way a broker reaches them: the mesh lane, which
        # declines a launch of one batch
        spec = where(
            (field("level") == "error") & (field("code") >= 100)
        ) | map_project(Int("code"), Str("msg", 16))
        stats: dict = {}
        out_mesh = self._run(
            spec, DOCS * 6, n_batches=8, stats=stats,
            mesh_devices=8, mesh_backend="cpu", mesh_probe=False,
        )
        assert stats["n_mesh_launches"] == 1
        out_single = self._run(spec, DOCS * 6, n_batches=8)
        assert out_mesh == out_single

    def test_contains_window_with_merged_width(self):
        # Another predicate widens msg's column; contains must still honor
        # its own (narrower) window.
        expr = field("msg").contains(b"x", window=4) & (
            field("msg") != "zzzzzzzzzzz"
        )
        docs = [{"msg": "aaaaaaaaaax"}, {"msg": "axaa"}, {"msg": "x"}]
        vals = [json.dumps(d, separators=(",", ":")).encode() for d in docs]
        want = np.array([host_eval(expr, v) for v in vals])
        got = _device_eval(expr, vals)
        assert (want == got).all()

    def test_force_mode_keeps_where_specs_columnar(self):
        spec = where(field("code") >= 500) | map_project(Int("code"))
        eng = TpuEngine(force_mode="payload")
        codes = eng.enable_coprocessors([(1, spec.to_json(), ("t",))])
        assert codes[0] == 0  # v2 specs have no payload compilation
        assert eng._plans[1].mode == "columnar"
        eng.shutdown()

    def test_bad_constant_fails_enable(self):
        bad = json.dumps(
            {"name": "bad", "ops": [],
             "where": {"k": "cmp", "p": "x", "op": "eq", "v": [1, 2]}}
        )
        eng = TpuEngine()
        codes = eng.enable_coprocessors([(1, bad, ("t",))])
        assert codes[0] == 1  # internal_error at enable, not at first batch
        eng.shutdown()

    def test_int_min_projection_dropped(self):
        docs = [{"code": -(2**31)}, {"code": -999_999_999}]
        spec = where(field("code").exists()) | map_project(Int("code"))
        out = self._run(spec, docs)
        assert out == [(-999_999_999).to_bytes(4, "little", signed=True)]

    def test_hex_and_inf_tokens_present_only(self):
        from redpanda_tpu.native import lib

        if lib is None:
            pytest.skip("native lib unavailable")
        docs = [b'{"a":0x10}', b'{"a":inf}', b'{"a":nan}', b'{"a":1e5}']
        joined = b"".join(docs)
        offsets = np.cumsum([0] + [len(d) for d in docs[:-1]]).astype(np.int64)
        sizes = np.array([len(d) for d in docs], np.int32)
        _, _, fl = lib.extract_num(joined, offsets, sizes, "a")
        for d, f in zip(docs, fl):
            h = E.host_field(d, "a")
            assert f == h["flags"], (d, f, h["flags"])
        assert list(fl) == [E.F_PRESENT, E.F_PRESENT, E.F_PRESENT,
                            E.F_PRESENT | E.F_NUMBER | E.F_INT_EXACT]

    def test_stats_populated(self):
        spec = where(field("level") == "error") | map_project(Int("code"))
        vals = [json.dumps(d, separators=(",", ":")).encode() for d in DOCS]
        recs = [Record(offset_delta=i, value=v) for i, v in enumerate(vals)]
        batch = RecordBatch.build(recs, base_offset=0)
        # pinned to the device path: this test asserts device-only stats
        # keys (t_fetch, bytes_h2d) that the probed default may not take
        eng = TpuEngine(force_mode="columnar_device")
        eng.enable_coprocessors([(1, spec.to_json(), ("t",))])
        req = ProcessBatchRequest([ProcessBatchItem(1, NTP.kafka("t", 0), [batch])])
        eng.process_batch(req)
        st = eng.stats()
        for k in ("t_dispatch", "t_fetch",
                  "t_rebuild", "bytes_h2d", "bytes_d2h", "n_records"):
            assert k in st, k
        # an eligible plan's launch runs the structural ladder when the
        # native entry exists, the staged one (fused explode+find, or the
        # split stages without the library) otherwise
        if st["parse_path"] == "structural":
            assert "t_explode_find2" in st and "t_fused_extract" in st
        else:
            assert "t_extract_pred" in st
            assert "t_explode_find" in st or ("t_explode" in st and "t_find" in st)
        assert st["bytes_d2h"] < st["bytes_h2d"]
        assert st["n_records"] == len(DOCS)
        eng.shutdown()


class TestFindMultiParity:
    """rp_find_multi + gathers (ONE JSON walk for all fields) must agree
    with the per-path extractors on every corpus doc, including malformed
    JSON, duplicate keys, escapes, and zero-size records."""

    def _joined(self):
        vals = _vals() + [
            b'{"level":"error","level":"info","code":1}',  # dup keys
            b'{"msg":"a\\"b\\\\","code":-3.5e2}',  # escapes + float
            b'{"code":}',  # malformed value
            b"not json at all",
            b"",
            b'{"other":{"level":"nested-not-top"},"level":"top"}',
        ]
        joined = b"".join(vals)
        offsets = np.cumsum([0] + [len(v) for v in vals[:-1]]).astype(np.int64)
        sizes = np.array([len(v) for v in vals], np.int32)
        return joined, offsets, sizes

    def test_gathers_match_per_path_extract(self):
        from redpanda_tpu.native import lib

        if lib is None or not getattr(lib, "has_find_multi", False):
            pytest.skip("native find_multi unavailable")
        joined, offsets, sizes = self._joined()
        paths = ["level", "code", "msg", "other", "absent"]
        types, vs, ve = lib.find_multi(joined, offsets, sizes, paths)
        for i, p in enumerate(paths):
            # string gather vs extract_str at two widths
            for w in (8, 64):
                gb, gv = lib.gather_str(joined, offsets, types[:, i], vs[:, i], ve[:, i], w)
                eb, ev = lib.extract_str(joined, offsets, sizes, p, w)
                assert (gv == ev).all(), (p, w)
                assert (gb == eb).all(), (p, w)
            # numeric gather vs extract_num
            gf, gi, gfl = lib.gather_num(joined, offsets, types[:, i], vs[:, i], ve[:, i])
            ef, ei, efl = lib.extract_num(joined, offsets, sizes, p)
            assert (gfl == efl).all(), p
            assert (gi == ei).all(), p
            assert (gf == ef).all(), p
            # exists
            ge = (types[:, i] != 0).astype(np.uint8)
            ee = lib.extract_exists(joined, offsets, sizes, p)
            assert (ge == ee).all(), p

    def test_plan_cache_end_to_end_parity(self):
        """The full columnar plan produces identical device inputs and
        projection columns with and without the find cache."""
        from redpanda_tpu.coproc.column_plan import plan_spec
        from redpanda_tpu.ops.transforms import Int, Str, map_project, where

        spec = where(
            (field("level") == "error") & (field("code") >= 0)
        ) | map_project(Int("code"), Str("msg", 32))
        plan = plan_spec(spec)
        joined, offsets, sizes = self._joined()
        cache = plan.build_find_cache(joined, offsets, sizes)
        if cache is None:
            pytest.skip("native find_multi unavailable")
        n_pad = len(sizes)
        with_c = plan.extract_device_inputs(joined, offsets, sizes, n_pad, cache)
        without = plan.extract_device_inputs(joined, offsets, sizes, n_pad, None)
        for a, b in zip(with_c, without):
            assert (np.asarray(a) == np.asarray(b)).all()
        dc, okc = plan.extract_projection(joined, offsets, sizes, cache)
        dn, okn = plan.extract_projection(joined, offsets, sizes, None)
        assert (okc == okn).all()
        # the cached path may take the fused native projector (data comes
        # back pre-packed); the CONTRACT is the assembled output, so
        # compare rows/lens byte-exactly instead of intermediate shapes
        n = len(sizes)
        rows_c, lens_c = plan.assemble_rows(dc, n)
        rows_n, lens_n = plan.assemble_rows(dn, n)
        assert (lens_c == lens_n).all()
        assert (rows_c == rows_n).all(), "fused projector diverged from numpy path"


def test_truncated_string_value_does_not_corrupt():
    """A record cut inside an unterminated string (b'{"a":"') used to make
    the native extractors memcpy (size_t)-1 bytes — heap corruption. It
    must read as an empty-but-present string everywhere."""
    from redpanda_tpu.native import lib

    vals = [b'{"a":"', b'{"a":"ok"}', b'{"a":']
    joined = b"".join(vals)
    offsets = np.cumsum([0] + [len(v) for v in vals[:-1]]).astype(np.int64)
    sizes = np.array([len(v) for v in vals], np.int32)
    if lib is not None:
        b, v = lib.extract_str(joined, offsets, sizes, "a", 8)
        assert v[0] == 0 and not b[0].any()  # empty-but-present
        assert v[1] == 2 and bytes(b[1][:2]) == b"ok"
        if getattr(lib, "has_find_multi", False):
            types, vs, ve = lib.find_multi(joined, offsets, sizes, ["a"])
            gb, gv = lib.gather_str(joined, offsets, types[:, 0], vs[:, 0], ve[:, 0], 8)
            assert (gv == v).all() and (gb == b).all()
    # python fallback path agrees
    from redpanda_tpu.coproc.column_plan import _extract_str

    class _NoLib:
        pass

    import redpanda_tpu.coproc.column_plan as cp

    orig = cp._native
    cp._native = lambda: None
    try:
        pb, pv = _extract_str(joined, offsets, sizes, "a", 8, len(sizes))
    finally:
        cp._native = orig
    assert pv[0] == 0 and pv[1] == 2
