"""JSON read as JSON on the payload lane (PR 40): ``map_project_json``, the
structural program of ``ops/transforms.py`` (string state with escapes,
nesting depth, dotted paths looked up by structure) and configuration
``nobench64p-q2``.

Here, on the CPU at small sizes: the device program against its numpy twin
bit for bit, both against ``benchmarks/references/nobench_q2.py`` (loaded by
path; it is ``json.loads`` and plain Python, and imports nothing of the
program) on hand-written values that pin each rule, on mutated objects and
on ``benchmarks/docs_nobench.py``'s own; the reason each dropped row is
dropped for and the three counters that carry it (``n_json_rows``,
``n_json_malformed_rows``, ``n_json_path_miss_rows``) through ``TpuEngine``,
``/metrics`` and ``rpk debug coproc``; the spec's serde and routing; the
program's text (no float, nothing of 64 bits, the two scopes).
"""

import importlib.util
import json
import os
import struct

import numpy as np
import pytest

from redpanda_tpu.coproc import EnableResponseCode, ProcessBatchRequest, TpuEngine
from redpanda_tpu.coproc.column_plan import PayloadPlan, plan_spec
from redpanda_tpu.coproc.engine import ProcessBatchItem
from redpanda_tpu.models import NTP, Record, RecordBatch
from redpanda_tpu.ops import transforms as T
from redpanda_tpu.ops.exprs import field
from redpanda_tpu.ops.pipeline import (
    IN_META, OUT_META, make_packed_pipeline, make_packed_pipeline_host, unpack_reason,
    unpack_result)

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
STRIDE = 1024
KEPT, MALFORMED, PATH_MISS = 0, T.JSON_MALFORMED, T.JSON_PATH_MISS


def _load(relpath: str):
    path = os.path.join(BENCH, relpath)
    spec = importlib.util.spec_from_file_location(
        "perfbench_" + relpath[:-3].replace("/", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


with open(os.path.join(BENCH, "configs", "nobench64p-q2.json")) as _f:
    CONFIG = json.load(_f)
REF = _load("references/nobench_q2.py")
PARAMS = CONFIG["reference"]["params"]
Q2 = T.TransformSpec.from_json(json.dumps(CONFIG["script"]["spec"]))


def _q2(string: bytes, number: int) -> bytes:
    return struct.pack("<H64si", len(string), string, number)


def _rows(values: list[bytes], stride: int = STRIDE):
    n_pad = max(8, -(-len(values) // 8) * 8)
    data = np.zeros((n_pad, stride), np.uint8)
    lens = np.zeros(n_pad, np.int32)
    for i, v in enumerate(values):
        data[i, : len(v)] = np.frombuffer(v, np.uint8)
        lens[i] = len(v)
    return data, lens


def _both(spec, values: list[bytes], stride: int = STRIDE):
    """(outputs or None, reasons) of the device program (jit, CPU backend),
    after holding its numpy twin to the same bits, dropped rows included."""
    data, lens = _rows(values, stride)
    dev = [np.asarray(x) for x in T.compile_transform(spec, stride, True)(data, lens)]
    host = T.compile_transform_host(spec, stride, True)(data, lens)
    for d, h in zip(dev, host):
        assert d.dtype == h.dtype and np.array_equal(d, h)
    out, out_len, keep, reason = dev
    # a reason drops the row; a v1 filter before the map may drop one that has none
    assert not (keep & (reason != 0)).any() and (spec.filters or (keep | (reason != 0)).all())
    assert np.array_equal(out_len, np.where(keep, out.shape[1], 0))
    n = len(values)
    return [out[i].tobytes() if keep[i] else None for i in range(n)], reason[:n].tolist()


def _obj(inner: bytes = b'"str": "GBRDCMJR", "num": 41', before: bytes = b'"num": 7, ',
         after: bytes = b', "thousandth": 7') -> bytes:
    return b'{' + before + b'"nested_obj": {' + inner + b'}' + after + b'}'


GOOD = _q2(b"GBRDCMJR", 41)
FILL = b'"pad": "' + b"x" * 900 + b'", '
# label -> (value, what a JSON parser reads and the limits admit, the reason a dropped row carries)
RULES = {
    # the path is the nested object's, whatever else bears the name
    "top_level_num_before": (_obj(), GOOD, KEPT),
    "top_level_num_after": (_obj(before=b"", after=b', "num": 7, "str": "top"'), GOOD, KEPT),
    "num_inside_a_string": (_obj(before=b'"a": "x\\"num\\": 5, \\"nested_obj\\": {", '), GOOD, KEPT),
    "num_inside_nested_arr": (_obj(before=b'"nested_arr": ["num", "str", "nested_obj"], '), GOOD, KEPT),
    "num_inside_a_deeper_object": (
        _obj(b'"deep": {"num": 9, "str": "no"}, "str": "GBRDCMJR", "num": 41, "arr": [{"num": 1}]'),
        GOOD, KEPT),
    "nested_obj_inside_another": (b'{"x": {"nested_obj": {"str": "a", "num": 1}}}', None, PATH_MISS),
    "a_value_that_spells_a_key": (_obj(b'"str": "num", "num": 5, "x": "str"'), _q2(b"num", 5), KEPT),
    "members_in_the_other_order": (_obj(b'"num": 41,"str": "GBRDCMJR"'), GOOD, KEPT),
    # whitespace between any two tokens
    "colon_space": (b'{"nested_obj": {"str": "s", "num": 12}}', _q2(b"s", 12), KEPT),
    "compact": (b'{"nested_obj":{"str":"s","num":12}}', _q2(b"s", 12), KEPT),
    "tabs_and_newlines": (b' \n{\n\t"nested_obj" :\r\n {"str"\t:\t"s" , "num" : 12 }\n}\r\n',
                          _q2(b"s", 12), KEPT),
    "a_long_run_of_spaces": (b'{"nested_obj"' + b" " * 300 + b':' + b"\n" * 300 + b'{"str": "s", "num":'
                             + b"\t" * 300 + b'12}}', _q2(b"s", 12), KEPT),
    # escapes in a string before the path
    "escaped_quote_before": (_obj(before=b'"a": "q\\"", '), GOOD, KEPT),
    "escaped_backslash_then_quote": (_obj(before=b'"a": "q\\\\", '), GOOD, KEPT),
    "three_backslashes_then_quote": (_obj(before=b'"a": "q\\\\\\"x{[", '), GOOD, KEPT),
    "brackets_and_colons_in_a_string": (_obj(before=b'"a": "}]{[:,", '), GOOD, KEPT),
    "an_escape_in_another_value": (_obj(b'"str": "GBRDCMJR", "num": 41, "note": "a\\nb"'), GOOD, KEPT),
    # a repeated key: the last wins, as json.loads has it
    "num_twice": (_obj(b'"str": "a", "num": 1, "num": 2'), _q2(b"a", 2), KEPT),
    "nested_obj_twice": (b'{"nested_obj": {"str": "a", "num": 1}, "nested_obj": {"str": "b", "num": 2}}',
                         _q2(b"b", 2), KEPT),
    "nested_obj_then_a_number": (b'{"nested_obj": {"str": "a", "num": 1}, "nested_obj": 5}',
                                 None, PATH_MISS),
    # Int: an integer of 1-9 digits, never a number's prefix
    "num_3_point_5": (_obj(b'"str": "a", "num": 3.5'), None, PATH_MISS),
    "num_3_point_0": (_obj(b'"str": "a", "num": 3.0'), None, PATH_MISS),
    "num_1e3": (_obj(b'"str": "a", "num": 1e3'), None, PATH_MISS),
    "num_1E3": (_obj(b'"str": "a", "num": 1E3'), None, PATH_MISS),
    "num_ten_digits": (_obj(b'"str": "a", "num": 1234567890'), None, PATH_MISS),
    "num_nine_digits": (_obj(b'"str": "a", "num": 999999999'), _q2(b"a", 999999999), KEPT),
    "num_negative": (_obj(b'"str": "a", "num": -999999999 '), _q2(b"a", -999999999), KEPT),
    "num_zero": (_obj(b'"str": "a", "num": 0'), _q2(b"a", 0), KEPT),
    "num_minus_zero": (_obj(b'"str": "a", "num": -0'), _q2(b"a", 0), KEPT),
    "num_a_string": (_obj(b'"str": "a", "num": "7"'), None, PATH_MISS),
    "num_null": (_obj(b'"str": "a", "num": null'), None, PATH_MISS),
    "num_true": (_obj(b'"str": "a", "num": true'), None, PATH_MISS),
    "num_an_object": (_obj(b'"str": "a", "num": {}'), None, PATH_MISS),
    "num_an_array": (_obj(b'"str": "a", "num": [7]'), None, PATH_MISS),
    "num_missing": (_obj(b'"str": "a"'), None, PATH_MISS),
    "num_ends_at_the_brace": (b'{"nested_obj":{"str":"a","num":7}}', _q2(b"a", 7), KEPT),
    "num_ends_at_a_newline": (b'{"nested_obj":{"str":"a","num":7\n}}', _q2(b"a", 7), KEPT),
    # Str: a string of at most 64 bytes; one that holds a backslash is dropped, not unescaped
    "str_64_bytes": (_obj(b'"str": "' + b"x" * 64 + b'", "num": 1'), _q2(b"x" * 64, 1), KEPT),
    "str_65_bytes": (_obj(b'"str": "' + b"x" * 65 + b'", "num": 1'), None, PATH_MISS),
    "str_empty": (_obj(b'"str": "", "num": 1'), _q2(b"", 1), KEPT),
    "str_utf8": (_obj(b'"str": "caf\xc3\xa9", "num": 1'), _q2(b"caf\xc3\xa9", 1), KEPT),
    "str_escaped_newline": (_obj(b'"str": "a\\nb", "num": 1'), None, PATH_MISS),
    "str_escaped_quote": (_obj(b'"str": "a\\"b", "num": 1'), None, PATH_MISS),
    "str_unicode_escape": (_obj(b'"str": "caf\\u00e9", "num": 1'), None, PATH_MISS),
    "str_a_number": (_obj(b'"str": 5, "num": 1'), None, PATH_MISS),
    "str_null": (_obj(b'"str": null, "num": 1'), None, PATH_MISS),
    "str_missing": (_obj(b'"num": 1'), None, PATH_MISS),
    # a key written with an escape cannot be matched by its bytes: not resolved, never resolved wrongly
    "an_escaped_key_after_the_plain_one": (_obj(b'"str": "a", "num": 1, "n\\u0075m": 2'), None, PATH_MISS),
    "an_escaped_key_in_a_deeper_object": (_obj(b'"str": "a", "num": 1, "d": {"n\\u0075m": 2}'),
                                          _q2(b"a", 1), KEPT),
    "an_escaped_top_level_key": (_obj(before=b'"t\\u006fp": 1, '), None, PATH_MISS),
    # the wrong type where an object is read
    "nested_obj_an_array": (b'{"nested_obj": ["str", "num"]}', None, PATH_MISS),
    "nested_obj_a_string": (b'{"nested_obj": "{\\"str\\": \\"a\\", \\"num\\": 1}"}', None, PATH_MISS),
    "nested_obj_missing": (b'{"str": "a", "num": 1}', None, PATH_MISS),
    "an_empty_object": (b"{}", None, PATH_MISS),
    # not one sound JSON object
    "unbalanced": (b'{"nested_obj": {"str": "a", "num": 1}', None, MALFORMED),
    "one_bracket_too_many": (b'{"nested_obj": {"str": "a", "num": 1}}}', None, MALFORMED),
    "unterminated_string": (b'{"nested_obj": {"str": "a, "num": 1}}', None, MALFORMED),
    "an_array_of_objects": (b'[{"nested_obj": {"str": "a", "num": 1}}]', None, MALFORMED),
    "a_string": (b'"nested_obj"', None, MALFORMED),
    "a_number": (b"41", None, MALFORMED),
    "two_objects": (_obj() + b" " + _obj(), None, MALFORMED),
    "text_after_the_object": (_obj() + b" x", None, MALFORMED),
    "text_before_the_object": (b"x " + _obj(), None, MALFORMED),
    "empty": (b"", None, MALFORMED),
    "whitespace_alone": (b" \n ", None, MALFORMED),
    "nesting_of_101": (_obj(after=b', "z": ' + b"[" * 100 + b"]" * 100), None, MALFORMED),
    "nesting_of_100": (_obj(after=b', "z": ' + b"[" * 99 + b"]" * 99), GOOD, KEPT),
    # the staging row
    "fits_to_the_byte": (_obj(before=FILL + b'"q": "' + b"y" * (1024 - len(_obj(before=FILL + b'"q": "", ')))
                              + b'", '), GOOD, KEPT),
}
# where the program departs from the reference, each stated in its docstring:
# the program is no validator
NOT_A_VALIDATOR = {
    "a_bad_literal_elsewhere": (_obj(before=b'"a": tru, '), GOOD),
    "a_trailing_comma": (_obj(after=b', "z": 1, '), GOOD),
    "a_bracket_closed_by_a_brace": (_obj(before=b'"a": [1}, '), GOOD),
    "a_raw_control_character_in_a_string": (_obj(before=b'"a": "x\ty", '), GOOD),
    "bytes_that_are_not_utf8": (_obj(before=b'"a": "\xff\xfe", '), GOOD),
    "a_leading_zero": (_obj(b'"str": "a", "num": 007'), None),
}


def test_the_rule_table_is_what_the_reference_says():
    assert len(RULES["fits_to_the_byte"][0]) == STRIDE
    for label, (value, want, reason) in RULES.items():
        assert REF.reference(value, **PARAMS) == want, label
        assert (want is None) == (reason != KEPT), label
    assert REF.reference(RULES["fits_to_the_byte"][0] + b" ", **PARAMS) is None  # 1,025 bytes
    assert REF.reference(None, **PARAMS) is None
    for label, (value, _want) in NOT_A_VALIDATOR.items():
        assert REF.reference(value, **PARAMS) is None, label


@pytest.mark.parametrize("label", sorted(RULES))
def test_each_rule_on_the_device_program_and_its_twin(label):
    value, want, reason = RULES[label]
    got, why = _both(Q2, [value, RULES["top_level_num_before"][0]])
    assert got == [want, GOOD] and why == [reason, KEPT]


@pytest.mark.parametrize("label", sorted(NOT_A_VALIDATOR))
def test_the_program_is_no_validator(label):
    """Strings that close, brackets that balance and one top-level object
    are what the program checks; a value that is still not JSON is
    projected if its paths resolve (and dropped where they do not)."""
    value, gives = NOT_A_VALIDATOR[label]
    got, why = _both(Q2, [value])
    assert got == [gives] and why == [KEPT if gives else PATH_MISS]


def test_every_rule_in_one_launch_and_on_a_narrow_row():
    values = [v for v, _, _ in RULES.values()]
    got, why = _both(Q2, values)
    assert got == [w for _, w, _ in RULES.values()] and why == [r for _, _, r in RULES.values()]
    # a 256-byte row: what fits reads the same, what does not is the engine's to drop
    short = [v for v in values if len(v) <= 256]
    got, _ = _both(Q2, short, 256)
    assert got == [REF.reference(v, **PARAMS) for v in short] and len(short) > 50


def test_long_and_deeper_paths():
    spec = T.map_project_json(T.Long("a.b.c"), T.Int("a.n"), T.Str("s", 4), T.Long("a.b.d"))
    assert T.TransformSpec.from_json(spec.to_json()) == spec
    assert T.transform_out_width(spec, 256) == 8 + 4 + 6 + 8

    def packed(c, n, s, d):
        return struct.pack("<qiH4sq", c, n, len(s), s, d)

    values = [
        b'{"s": "ab", "a": {"n": -5, "b": {"c": 999999999999999999, "d": -1700000000123}}, "c": 1}',
        b'{"a": {"b": {"d": 0, "c": 1234567890123}, "n": 1, "c": 7}, "s": "abcd", "b": {"c": 2}}',
        b'{"s": "ab", "a": {"n": 1, "b": {"c": 1000000000000000000, "d": 1}}}',  # 19 digits
        b'{"s": "abcde", "a": {"n": 1, "b": {"c": 1, "d": 1}}}',  # 5 bytes
        b'{"s": "ab", "a": {"n": 1, "b": [{"c": 1, "d": 1}]}}',  # b is an array
        b'{"s": "ab", "a": {"n": 1, "b": {"c": 1}}, "d": 1}',  # a.b.d missing
    ]
    got, why = _both(spec, values, 256)
    assert got == [packed(999999999999999999, -5, b"ab", -1700000000123),
                   packed(1234567890123, 1, b"abcd", 0), None, None, None, None]
    assert why == [KEPT, KEPT, PATH_MISS, PATH_MISS, PATH_MISS, PATH_MISS]


def test_a_v1_filter_before_the_structural_map():
    """The v1 filter keeps its byte semantics; a row it drops is not
    counted against the JSON read."""
    spec = T.filter_contains(b'"bool": true') | T.map_project_json(T.Int("nested_obj.num"))
    assert isinstance(plan_spec(spec), PayloadPlan)
    values = [b'{"bool": true, "nested_obj": {"num": 5}}', b'{"bool": false, "nested_obj": {"num": 5}}',
              b'{"bool": false, "nested_obj": {"num": 5.5}}', b'{"bool": true, "nested_obj": {"num": 5.5}}',
              b'{"bool": true, "nested_obj": {"num": 5}', b""]
    got, why = _both(spec, values)
    assert got == [struct.pack("<i", 5), None, None, None, None, None]
    assert why == [KEPT, KEPT, KEPT, PATH_MISS, MALFORMED, MALFORMED]


# ------------------------------------------------------------------ mutated objects
def _mutants(seed: int, count: int) -> list[bytes]:
    rng = np.random.default_rng(seed)
    base = _load("docs_nobench.py").make_objects(seed, 1, 64)[0]
    edits = [b'"', b"\\", b"{", b"}", b"[", b"]", b":", b",", b" ", b"\n", b'\\"', b"\\\\", b'"num": 3.5',
             b'"nested_obj": 1, ', b"7", b"e", b"."]
    out = []
    for k in range(count):
        v = bytearray(base[k % len(base)])
        for _ in range(int(rng.integers(1, 4))):
            at = int(rng.integers(0, len(v)))
            edit = edits[int(rng.integers(0, len(edits)))]
            if rng.random() < 0.5:
                v[at:at] = edit
            else:
                v[at : at + len(edit)] = edit
        out.append(bytes(v[:STRIDE]))
    return out


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_mutated_objects_read_as_json_loads_reads_them_or_are_dropped(seed):
    """What ``json.loads`` reads and the limits admit, the program reads
    equal; what the reference drops, the program drops, but for the values
    that are not JSON and still sound in their quotes and brackets (the
    stated departure), which it may project."""
    values = _mutants(seed, 256)
    got, why = _both(Q2, values)
    readable = kept = 0
    for v, g, w in zip(values, got, why):
        want = REF.reference(v, **PARAMS)
        try:
            json.loads(v)
        except ValueError:
            assert want is None
            continue
        readable += 1
        kept += want is not None
        assert g == want, v
        assert (w == KEPT) == (want is not None), v
    assert readable >= 20 and kept >= 5


def _scan(value: bytes):
    """(unescaped quote, inside a string, bracket depth) of every byte, by
    a plain loop under the program's stated rule: a quote is escaped iff an
    odd run of backslashes precedes it."""
    quote, instr, depth = [], [], []
    inside, run, d = False, 0, 0
    for c in value:
        q = c == 0x22 and run % 2 == 0
        inside ^= q
        run = run + 1 if c == 0x5C else 0
        if not inside:
            d += (c in b"{[") - (c in b"}]")
        quote.append(q)
        instr.append(inside)
        depth.append(d)
    return quote, instr, depth


@pytest.mark.parametrize("seed", [11, 12])
def test_the_structural_pass_against_a_plain_state_machine(seed):
    """Rows dense with backslashes, quotes and brackets: the string state
    (a quote is escaped iff an odd run of backslashes precedes it) and the
    depth of every byte, device program and numpy twin alike; and the same
    rows with no backslash at all, which take the conditional's other
    branch."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    alphabet = np.frombuffer(b'\\\\\\""{}[]:, ab', np.uint8)
    rows = [bytes(alphabet[rng.integers(0, len(alphabet), size=int(n))])
            for n in rng.integers(1, 200, size=64)]
    for values in (rows, [v.replace(b"\\", b"c") for v in rows]):
        data, lens = _rows(values, 256)
        for xp in (np, jnp):
            st, _sound = T._json_structure(xp, xp.asarray(data), xp.asarray(lens))
            for i, v in enumerate(values):
                quote, instr, depth = _scan(v)
                n = len(v)
                assert np.asarray(st["quote"])[i, :n].tolist() == quote, (xp.__name__, v)
                assert np.asarray(st["instr"])[i, :n].tolist() == instr, (xp.__name__, v)
                assert np.asarray(st["depth"])[i, :n].tolist() == depth, (xp.__name__, v)


# ------------------------------------------------------------------ spec, serde, routing
def test_spec_round_trips_and_states_its_width():
    spec = T.map_project_json(T.Str("nested_obj.str", 64), T.Int("nested_obj.num"))
    assert T.TransformSpec.from_json(spec.to_json()) == spec == Q2
    assert json.loads(spec.to_json()) == CONFIG["script"]["spec"]
    assert T.transform_out_width(spec, STRIDE) == 70 == T.project_out_width(spec.mapper.fields)
    assert T.reports_reason(spec) and not T.reports_reason(T.map_project(T.Int("num")))


@pytest.mark.parametrize("kind", ["float", "scaled", "substr", "concat", "double", ""])
def test_a_field_kind_the_form_does_not_read_is_refused_by_name(kind):
    blob = json.dumps({"name": "x", "ops": [
        {"op": "map_project_json", "fields": [{"kind": kind, "key": "a.b", "max_len": 8}]}]})
    with pytest.raises(ValueError, match="unknown map_project_json field kind"):
        T.TransformSpec.from_json(blob)
    engine = TpuEngine(row_stride=STRIDE, host_workers=0)
    try:
        assert engine.enable_coprocessors([(1, blob, ("t",))]) == [
            EnableResponseCode.internal_error]
    finally:
        engine.shutdown()


def test_the_builder_refuses_the_other_kinds_and_bad_paths():
    with pytest.raises(ValueError, match="Int, Long and Str fields, not Float"):
        T.map_project_json(T.Float("a"))
    with pytest.raises(ValueError, match="not Scaled"):
        T.map_project_json(T.Scaled("a", 1, 2))
    for key in ("", "a..b", ".a", 'a"b', "a\\b", "caf\u00e9", "\u65e5", "a\tb",
                ".".join("abcdefghijklmnopq")):
        with pytest.raises(ValueError, match="map_project_json path"):
            T.compile_transform_host(T.map_project_json(T.Int(key)), 64)
    with pytest.raises(ValueError, match="a width of 1 or more"):
        T.compile_transform_host(T.map_project_json(T.Str("a", 0)), 64)
    with pytest.raises(ValueError, match="a row under 32,768 bytes"):
        T.compile_transform_host(T.map_project_json(T.Int("a")), 2**15)
    with pytest.raises(ValueError, match="no field"):
        T.compile_transform_host(T.map_project_json(), 64)
    with pytest.raises(ValueError, match="projected width exceeds input width"):
        T.compile_transform_host(T.map_project_json(T.Str("a", 64)), 64)


def test_the_new_map_is_a_payload_plan_and_says_where_it_runs():
    plan = plan_spec(Q2)
    assert isinstance(plan, PayloadPlan) and plan.mode == "payload"
    assert plan.structural and not plan.byte_identity
    assert not plan_spec(T.map_project(T.Int("num"))).structural
    # after a where(...) the deploy is refused with the sentence that says where it runs
    with pytest.raises(ValueError, match="map_project_json runs on the payload lane"):
        plan_spec(T.where(field("bool") == True) | T.map_project_json(T.Int("num")))  # noqa: E712
    engine = TpuEngine(row_stride=STRIDE, host_workers=0)
    try:
        spec = T.where(field("num") >= 1) | T.map_project_json(T.Int("num"))
        assert engine.enable_coprocessors([(1, spec.to_json(), ("t",))]) == [
            EnableResponseCode.internal_error]
    finally:
        engine.shutdown()


def test_one_map_stage_a_transform():
    with pytest.raises(ValueError, match="only one map stage"):
        T.map_project(T.Int("num")) | T.map_project_json(T.Int("num"))
    blob = json.dumps({"name": "x", "ops": [
        {"op": "map_project", "fields": [{"kind": "int", "key": "num"}]},
        {"op": "map_project_json", "fields": [{"kind": "int", "key": "num"}]}]})
    with pytest.raises(ValueError, match="only one map stage"):
        T.TransformSpec.from_json(blob)


def test_the_v1_forms_keep_their_byte_semantics():
    """The same script means what it meant: v1 ``map_project`` still takes
    the first ``"num":`` and drops a spaced colon (the departures
    ``references/project_error_v1.py`` lists), beside the structural read."""
    v1 = T.map_project(T.Int("num"))
    data, lens = _rows([b'{"num":7,"nested_obj":{"num":41}}', b'{"nested_obj": {"num": 41}}'], 64)
    out, _, keep = T.compile_transform_host(v1, 64)(data, lens)
    assert keep[:2].tolist() == [True, False] and out[0].tobytes() == struct.pack("<i", 7)


# ------------------------------------------------------------------ the packed result row
def test_the_reason_rides_in_a_trailing_byte_of_the_result_row():
    values = [RULES[k][0] for k in ("top_level_num_before", "num_3_point_5", "unbalanced")]
    data, lens = _rows(values)
    staged = np.zeros((8, STRIDE + IN_META), np.uint8)
    staged[:, :STRIDE] = data
    staged[:, STRIDE : STRIDE + 4] = lens.astype("<i4").view(np.uint8).reshape(8, 4)
    fn, r_out = make_packed_pipeline(Q2, STRIDE)
    packed = np.asarray(fn(staged))
    assert r_out == 70 and packed.shape == (8, 70 + OUT_META)
    assert np.array_equal(packed, make_packed_pipeline_host(Q2, STRIDE)(staged))
    _out, out_len, keep = unpack_result(packed, r_out)
    assert keep.tolist() == [True] + [False] * 7 and out_len.tolist() == [70] + [0] * 7
    assert unpack_reason(packed, r_out).tolist() == [KEPT, PATH_MISS] + [MALFORMED] * 6
    assert not packed[:, r_out + 6 :].any()
    # a v1 script's row keeps its three zero bytes
    v1 = T.map_project(T.Int("num"))
    fn1, r1 = make_packed_pipeline(v1, STRIDE)
    assert not np.asarray(fn1(staged))[:, r1 + 5 :].any()


# ------------------------------------------------------------------ the served lane
def _batches(values: list[bytes], per_batch: int, base: int) -> list[RecordBatch]:
    return [
        RecordBatch.build(
            [Record(offset_delta=i, timestamp_delta=i, value=v)
             for i, v in enumerate(values[s : s + per_batch])],
            base_offset=base + s, first_timestamp=1000)
        for s in range(0, len(values), per_batch)
    ]


@pytest.mark.parametrize("seed", [7, 2**31 + 11])
def test_the_engine_gives_the_reference_bytes_on_nobench_objects(seed):
    """Configuration ``nobench64p-q2`` through ``TpuEngine``: 64 partitions
    of seeded objects (the configuration's own generator) plus every rule's
    value and one over the staging row, byte-equal and in order to the plain
    reference, with the counters the cell's per-layer metrics read."""
    objects = _load("docs_nobench.py").make_objects(seed, 64, 64)
    parts = [objects[p] for p in range(64)]
    rules = [v for v, _, _ in RULES.values()] + [RULES["fits_to_the_byte"][0] + b" "]
    parts[1] = parts[1][:40] + rules + parts[1][40:]
    parts[63] = rules[::-1] + parts[63]
    engine = TpuEngine(row_stride=STRIDE, host_workers=0)
    try:
        assert engine.enable_coprocessors(
            [(1, json.dumps(CONFIG["script"]["spec"]), ("bench",))]
        ) == [EnableResponseCode.success]
        reply = engine.submit(ProcessBatchRequest([
            ProcessBatchItem(1, NTP.kafka("bench", p), _batches(values, 32, 10_000 * p))
            for p, values in enumerate(parts)
        ])).result()
        stats = engine.stats()
    finally:
        engine.shutdown()
    kept = 0
    for p, (item, values) in enumerate(zip(reply.items, parts)):
        got = [r.value for b in item.batches for r in b.records()]
        want = [o for o in (REF.reference(v, **PARAMS) for v in values) if o is not None]
        assert got == want, f"partition {p}"
        kept += len(want)
        if p not in (1, 63):  # every generated object is kept, and names its input
            assert [REF.sequence(o) for o in got] == [p * 64 + i for i in range(64)]
    n_in = sum(map(len, parts))
    reasons = [r for _, _, r in RULES.values()]
    assert n_in == 64 * 64 + 2 * len(rules)
    assert kept == 64 * 64 + 2 * reasons.count(KEPT) == stats["n_kept_rows"]
    assert stats["bytes_out"] == 70 * kept
    assert stats["n_device_launches"] == stats["n_launches"] == 1
    assert stats.get("n_fallback_rows", 0) == 0 and stats["n_frame_padded"] == 1
    assert stats["n_json_rows"] == n_in == stats["n_records"]
    assert stats["n_json_malformed_rows"] == 2 * reasons.count(MALFORMED)
    assert stats["n_json_path_miss_rows"] == 2 * reasons.count(PATH_MISS)
    assert stats["n_oversize_rows"] == 2  # the 1,025-byte value: its own counter, not malformed
    assert stats["bytes_d2h"] == stats["n_staged_rows"] * (70 + OUT_META)  # one matrix, one D2H


def test_the_host_fallback_counts_the_same_reasons():
    """A launch demoted to the numpy twin gives the same matrix, reason
    column included, so the harvest counts the same rows."""
    from redpanda_tpu.coproc import faults
    from redpanda_tpu.finjector import honey_badger

    values = [v for v, _, _ in RULES.values()]
    reasons = [r for _, _, r in RULES.values()]
    engine = TpuEngine(row_stride=STRIDE, host_workers=0, launch_retries=0)
    try:
        assert engine.enable_coprocessors([(1, Q2.to_json(), ("t",))]) == [EnableResponseCode.success]
        honey_badger.enable()
        honey_badger.set_exception(faults.MODULE, faults.DEVICE_DISPATCH)
        reply = engine.submit(ProcessBatchRequest(
            [ProcessBatchItem(1, NTP.kafka("t", 0), _batches(values, 32, 0))])).result()
        stats = engine.stats()
    finally:
        honey_badger.unset(faults.MODULE, faults.DEVICE_DISPATCH)
        honey_badger.disable()
        engine.shutdown()
    got = [r.value for b in reply.items[0].batches for r in b.records()]
    assert got == [w for _, w, _ in RULES.values() if w is not None]
    assert stats["n_fallback_rows"] == len(values) and stats.get("n_device_launches", 0) == 0
    assert stats["n_json_rows"] == len(values)
    assert stats["n_json_malformed_rows"] == reasons.count(MALFORMED)
    assert stats["n_json_path_miss_rows"] == reasons.count(PATH_MISS)


def test_the_counters_reach_the_metrics_page_and_rpk(capsys):
    from redpanda_tpu.metrics import registry
    from redpanda_tpu.observability import probes

    before = {k: c.value for k, c in probes.coproc_json_rows.items()}
    values = [RULES[k][0] for k in ("top_level_num_before", "num_3_point_5", "unbalanced", "empty")]
    engine = TpuEngine(row_stride=STRIDE, host_workers=0)
    try:
        assert engine.enable_coprocessors([(1, Q2.to_json(), ("t",))]) == [EnableResponseCode.success]
        engine.submit(ProcessBatchRequest(
            [ProcessBatchItem(1, NTP.kafka("t", 0), _batches(values, 4, 0))])).result()
        stats = engine.stats()
    finally:
        engine.shutdown()
    grew = {k: c.value - before[k] for k, c in probes.coproc_json_rows.items()}
    assert grew == {"n_json_rows": 4, "n_json_malformed_rows": 2, "n_json_path_miss_rows": 1}
    assert {k: stats[k] for k in grew} == grew
    page = registry.render_prometheus()
    for outcome in ("read", "malformed", "path_miss"):
        assert f'coproc_json_rows_total{{outcome="{outcome}"}}' in page

    # rpk debug coproc: the three counters among the stats, and the json: line
    from redpanda_tpu.cli import rpk

    async def status(_args, _method, path, **_kw):
        assert path == "/v1/coproc/status"
        numbers = {k: v for k, v in stats.items() if isinstance(v, (int, float))}
        return 200, {"enabled": True, "native": {"loaded": True}, "stats": numbers}

    saved, rpk._admin_request = rpk._admin_request, status
    try:
        assert rpk.main(["debug", "coproc"]) == 0
    finally:
        rpk._admin_request = saved
    printed = capsys.readouterr().out
    (line,) = [ln for ln in printed.splitlines() if ln.startswith("json:")]
    assert "4 rows read as JSON" in line and "dropped 2 as not one sound object, 1 for a path" in line
    for key in grew:
        assert any(ln.split()[:1] == [key] for ln in printed.splitlines()), key


# ------------------------------------------------------------------ the program's text
def test_the_programs_text_has_its_scopes_and_no_wide_type():
    import jax

    fn, _ = make_packed_pipeline(Q2, STRIDE)
    lowered = fn.lower(jax.ShapeDtypeStruct((256, STRIDE + IN_META), np.uint8))
    text = lowered.compile().as_text()
    for scope in ("rp_transform)/project/json.structure/", "rp_transform)/project/json.path/",
                  "rp_payload_transform)/parse/", "rp_payload_transform)/frame/"):
        assert scope in text, scope
    # no float enters the program, and no value wider than 32 bits: the one
    # 64-bit type in the text is the window padding ATTRIBUTE of the two
    # prefix scans' reduce_window (``padding = dense<...> : tensor<2x2xi64>``)
    hlo = lowered.as_text()
    assert hlo.count("tensor<2x2xi64>") == hlo.count("stablehlo.reduce_window") > 0
    hlo = hlo.replace("tensor<2x2xi64>", "")
    for dtype in ("f16", "bf16", "f32", "f64", "i64", "ui64"):
        assert f"x{dtype}>" not in hlo and f"<{dtype}>" not in hlo, dtype
