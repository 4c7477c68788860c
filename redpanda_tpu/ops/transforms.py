"""User map/filter transform DSL, compiled to jitted device functions.

The reference's coproc engine runs arbitrary user JS per record in a Node.js
sidecar (src/js/modules/public/Coprocessor.ts apply()); a TPU cannot run
arbitrary JS, and the TPU-first answer is not an interpreter but a
*declarative transform spec* compiled once into a fused XLA program that
processes every record of every partition in one launch:

    spec = filter_field_eq("level", "error") | map_project(
        Int("ts"), Str("msg", 64))
    fn = compile_transform(spec, r_in=1024)
    out, out_len, keep = fn(data, lengths)     # data: uint8 [N, r_in]

Semantics notes (documented limits of v1, see tests):
- JSON matching is canonical-form (no whitespace around ':'): field
  predicates compile to substring scans for '"key":'. Records are assumed
  to hold one JSON object per record value, as the reference's example
  transforms do. So ``"code": 7`` (a space after the colon) is dropped, a
  key inside another field's text or a nested object matches (the first
  occurrence wins), and an escaped quote ends a string. The v1 forms
  (``filter_contains``, ``filter_field_eq``, ``map_project``) KEEP these
  byte semantics: a deployed script means what it meant.
- ``map_project`` emits a fixed-width binary struct per record ("flatbuffer"
  layout of the north-star config 4): int fields as little-endian int32,
  string fields as uint16 length + fixed-width padded bytes. Records missing
  a projected field are dropped (keep=False).

JSON read as JSON (PR 40): ``map_project_json(Str("nested_obj.str", 64),
Int("nested_obj.num"))`` emits ``map_project``'s record with every field
read as a JSON parser reads it. A structural pass over the staged byte
matrix finds, for every byte of every row, whether it is inside a string (a
quote is escaped iff an odd run of ``\\`` precedes it) and at what bracket
depth; a field's ``key`` is a dotted path of object keys, and a segment
matches a key token (``"seg"``, RFC 8259 whitespace, ``:``) at its depth
inside its parent's object span, outside every string; of a repeated key
the last wins, as ``json.loads`` has it. ``Int`` / ``Long`` read an optional
``-`` and 1-9 / 1-18 digits ended by whitespace, ``,`` or ``}`` (``3.5`` is
never read as 3); ``Str(path, w)`` a string of at most ``w`` bytes. A row the
program cannot read faithfully is dropped with a reason, never emitted
approximate: ``JSON_MALFORMED`` (empty, not one object, a string left open,
brackets unbalanced or deeper than 100) or ``JSON_PATH_MISS`` (a path
absent, an intermediate value that is no object, a value its field cannot
hold, a string written with a backslash (dropped, not unescaped), or a key
of a searched object written with an escape, which a byte match cannot
read). It is no validator: a value sound in its quotes and brackets whose
paths resolve is projected whatever else is wrong with it
(``benchmarks/references/nobench_q2.py`` lists the departures,
``tests/test_json_structural.py`` pins them). The form is chosen by the
script's own text, runs on the payload lane, and is refused by name after a
``where(...)``.

Every primitive is static-shape, branch-free, and vmap/shard_map friendly:
partitions ride the leading axis and shard over the mesh 'p' axis
(redpanda_tpu.parallel).
"""

from __future__ import annotations

import contextlib
import functools
import json
from dataclasses import dataclass
from typing import Sequence


# ----------------------------------------------------------------- spec types
@dataclass(frozen=True)
class Int:
    key: str


@dataclass(frozen=True)
class Str:
    key: str
    max_len: int = 64


@dataclass(frozen=True)
class Long:
    """Project an integer of 1-18 digits (an optional ``-``) as
    little-endian int64 (8 bytes): an epoch-millisecond timestamp, an id."""

    key: str


@dataclass(frozen=True)
class Scaled:
    """Project ``floor(v * num / den)`` of a v1 ``Int`` (1-9 digits) as
    little-endian int64 (8 bytes), exactly: a rational constant times an
    integer (NEXmark Q1's ``0.908 * price`` is ``Scaled("price", 908,
    1000)``), rounded toward minus infinity, never through a float."""

    key: str
    num: int
    den: int


@dataclass(frozen=True)
class Float:
    """Project a JSON number as little-endian float32 (4 bytes)."""

    key: str


@dataclass(frozen=True)
class Substr:
    """Project value[start : start+length] of a string field, padded."""

    key: str
    start: int
    length: int


@dataclass(frozen=True)
class Concat:
    """Project two string fields joined (a + b), truncated to max_len."""

    a: str
    b: str
    max_len: int = 64


@dataclass(frozen=True)
class _FilterContains:
    pattern: bytes
    negate: bool = False
    # Numeric-equality support: the byte following the match must not extend
    # the number (digit, '.', exponent char, sign), so '"code":42' does not
    # match {"code":420}.
    require_nonnum_suffix: bool = False


@dataclass(frozen=True)
class _MapProject:
    fields: tuple


@dataclass(frozen=True)
class _MapProjectJson:
    """``map_project_json``: ``_MapProject``'s record, its fields read as
    JSON (dotted paths of object keys) by the structural program."""

    fields: tuple


@dataclass(frozen=True)
class _MapUppercase:
    pass


@dataclass(frozen=True)
class TransformSpec:
    """Filters (legacy raw-byte) and/or a predicate tree, plus one map.

    ``filters`` are v1 raw-payload substring ops (compiled to the payload
    device pipeline); ``where`` is a v2 field-anchored expression tree
    (redpanda_tpu.ops.exprs) compiled to the columnar pushdown path. The
    engine picks the execution mode per spec (coproc/column_plan.py).
    """

    filters: tuple = ()
    mapper: object = None
    name: str = "identity"
    where: object = None  # exprs.Expr | None

    def __or__(self, other: "TransformSpec") -> "TransformSpec":
        if self.mapper is not None and other.mapper is not None:
            raise ValueError("only one map stage per transform")
        w = self.where
        if other.where is not None:
            from redpanda_tpu.ops.exprs import And

            w = And(w, other.where) if w is not None else other.where
        return TransformSpec(
            filters=self.filters + other.filters,
            mapper=self.mapper or other.mapper,
            name=f"{self.name}|{other.name}",
            where=w,
        )

    # ------------------------------------------------------------- serde
    def to_json(self) -> str:
        """Wire form for deploy events (coproc internal topic)."""
        ops = []
        for f in self.filters:
            ops.append(
                {
                    "op": "filter_contains",
                    "pattern": f.pattern.decode("latin1"),
                    "negate": f.negate,
                    "nonnum_suffix": f.require_nonnum_suffix,
                }
            )
        if isinstance(self.mapper, _MapProject):
            fields = []
            for f in self.mapper.fields:
                if isinstance(f, Int):
                    fields.append({"kind": "int", "key": f.key})
                elif isinstance(f, Long):
                    fields.append({"kind": "long", "key": f.key})
                elif isinstance(f, Scaled):
                    fields.append(
                        {"kind": "scaled", "key": f.key, "num": f.num, "den": f.den}
                    )
                elif isinstance(f, Float):
                    fields.append({"kind": "float", "key": f.key})
                elif isinstance(f, Substr):
                    fields.append(
                        {"kind": "substr", "key": f.key, "start": f.start, "length": f.length}
                    )
                elif isinstance(f, Concat):
                    fields.append(
                        {"kind": "concat", "a": f.a, "b": f.b, "max_len": f.max_len}
                    )
                else:
                    fields.append({"kind": "str", "key": f.key, "max_len": f.max_len})
            ops.append({"op": "map_project", "fields": fields})
        elif isinstance(self.mapper, _MapProjectJson):
            ops.append(
                {
                    "op": "map_project_json",
                    "fields": [_json_field_doc(f) for f in self.mapper.fields],
                }
            )
        elif isinstance(self.mapper, _MapUppercase):
            ops.append({"op": "map_uppercase"})
        doc = {"name": self.name, "ops": ops}
        if self.where is not None:
            doc["where"] = self.where.to_dict()
        return json.dumps(doc)

    @staticmethod
    def from_json(blob: str | bytes) -> "TransformSpec":
        doc = json.loads(blob)
        spec = TransformSpec(name=doc.get("name", "anon"))
        for op in doc.get("ops", []):
            kind = op["op"]
            if kind == "filter_contains":
                spec = spec | TransformSpec(
                    filters=(
                        _FilterContains(
                            op["pattern"].encode("latin1"),
                            op.get("negate", False),
                            op.get("nonnum_suffix", False),
                        ),
                    ),
                    name="",
                )
            elif kind == "map_project":
                fields = []
                for f in op["fields"]:
                    fk = f["kind"]
                    if fk == "int":
                        fields.append(Int(f["key"]))
                    elif fk == "long":
                        fields.append(Long(f["key"]))
                    elif fk == "scaled":
                        fields.append(Scaled(f["key"], f["num"], f["den"]))
                    elif fk == "float":
                        fields.append(Float(f["key"]))
                    elif fk == "substr":
                        fields.append(Substr(f["key"], f["start"], f["length"]))
                    elif fk == "concat":
                        fields.append(Concat(f["a"], f["b"], f["max_len"]))
                    elif fk == "str":
                        fields.append(Str(f["key"], f["max_len"]))
                    else:
                        raise ValueError(f"unknown map_project field kind {fk!r}")
                spec = spec | TransformSpec(mapper=_MapProject(tuple(fields)), name="")
            elif kind == "map_project_json":
                fields = tuple(_json_field_from_doc(f) for f in op["fields"])
                spec = spec | TransformSpec(mapper=_MapProjectJson(fields), name="")
            elif kind == "map_uppercase":
                spec = spec | TransformSpec(mapper=_MapUppercase(), name="")
            else:
                raise ValueError(f"unknown transform op {kind!r}")
        w = None
        if "where" in doc:
            from redpanda_tpu.ops.exprs import Expr

            w = Expr.from_dict(doc["where"])
        return TransformSpec(spec.filters, spec.mapper, doc.get("name", "anon"), w)


def _json_field_doc(f) -> dict:
    """Wire form of one ``map_project_json`` field."""
    if isinstance(f, Int):
        return {"kind": "int", "key": f.key}
    if isinstance(f, Long):
        return {"kind": "long", "key": f.key}
    if isinstance(f, Str):
        return {"kind": "str", "key": f.key, "max_len": f.max_len}
    raise ValueError(
        f"map_project_json reads Int, Long and Str fields, not {type(f).__name__}"
    )


def _json_field_from_doc(f: dict):
    fk = f["kind"]
    if fk == "int":
        return Int(f["key"])
    if fk == "long":
        return Long(f["key"])
    if fk == "str":
        return Str(f["key"], f["max_len"])
    raise ValueError(f"unknown map_project_json field kind {fk!r}")


# ----------------------------------------------------------------- public DSL
def identity() -> TransformSpec:
    return TransformSpec(name="identity")


def filter_contains(pattern: bytes, negate: bool = False) -> TransformSpec:
    return TransformSpec(filters=(_FilterContains(bytes(pattern), negate),), name="contains")


def filter_field_eq(key: str, value) -> TransformSpec:
    """Canonical-JSON field equality: substring match of '"key":<value>'."""
    nonnum = False
    if isinstance(value, str):
        pat = f'"{key}":"{value}"'
    elif isinstance(value, bool):
        pat = f'"{key}":{"true" if value else "false"}'
    else:
        pat = f'"{key}":{value}'
        nonnum = True  # prevent prefix matches like 42 matching 420
    return TransformSpec(
        filters=(_FilterContains(pat.encode(), require_nonnum_suffix=nonnum),),
        name=f"eq:{key}",
    )


def map_project(*fields) -> TransformSpec:
    return TransformSpec(mapper=_MapProject(tuple(fields)), name="project")


def map_project_json(*fields) -> TransformSpec:
    """``map_project``'s fixed-width record with every field read as JSON:
    ``key`` is a dotted path of object keys from the value's top-level
    object (``Int("nested_obj.num")``), found by structure and not by a
    byte pattern (module docs, "JSON read as JSON"). Fields: ``Int``,
    ``Long``, ``Str``."""
    for f in fields:
        _json_field_doc(f)  # refuses the other kinds by name
    return TransformSpec(mapper=_MapProjectJson(tuple(fields)), name="project_json")


def map_uppercase() -> TransformSpec:
    return TransformSpec(mapper=_MapUppercase(), name="upper")


def where(expr) -> TransformSpec:
    """v2 predicate: a field-anchored expression tree (ops.exprs).

    Compiled to the columnar pushdown path: only referenced fields cross
    the device link, the device evaluates the tree, one bit returns per
    record. Combine with ``|`` like any other stage::

        where((field("level") == "error") & (field("code") >= 500))
            | map_project(Int("code"), Str("msg", 64))
    """
    from redpanda_tpu.ops.exprs import _as_expr

    return TransformSpec(where=_as_expr(expr), name="where")


def project_out_width(fields: Sequence) -> int:
    w = 0
    for f in fields:
        if isinstance(f, (Int, Float)):
            w += 4
        elif isinstance(f, (Long, Scaled)):
            w += 8
        elif isinstance(f, Substr):
            w += 2 + f.length
        elif isinstance(f, Concat):
            w += 2 + f.max_len
        else:
            w += 2 + f.max_len
    return w


# ------------------------------------------------------------ device primitives
def _find_pattern(jnp, data, lengths, pat: bytes, require_nonnum_suffix: bool = False):
    """First start index of `pat` within each row's valid prefix, else -1.

    With require_nonnum_suffix, a match is only valid when the byte after it
    is not a number-continuation character (digit, '.', 'e', 'E', '+', '-')
    or the match ends exactly at the record's length.
    """
    n, r = data.shape
    l = len(pat)
    if l == 0 or l > r:
        return jnp.full((n,), -1, dtype=jnp.int32)
    w = r - l + 1
    match = jnp.ones((n, w), dtype=bool)
    for i, byte in enumerate(pat):
        match = match & (data[:, i : i + w] == jnp.uint8(byte))
    starts = jnp.arange(w, dtype=jnp.int32)
    match = match & (starts[None, :] <= (lengths - l)[:, None])
    if require_nonnum_suffix:
        # Byte at start+l for each start (0 for the final start, which is
        # past the row end).
        nxt = jnp.concatenate(
            [data[:, l:], jnp.zeros((n, 1), dtype=data.dtype)], axis=1
        )  # [N, w]
        is_num = (
            ((nxt >= ord("0")) & (nxt <= ord("9")))
            | (nxt == ord("."))
            | (nxt == ord("e"))
            | (nxt == ord("E"))
            | (nxt == ord("+"))
            | (nxt == ord("-"))
        )
        at_end = (starts[None, :] + l) >= lengths[:, None]
        match = match & (at_end | ~is_num)
    idx = jnp.argmax(match, axis=1).astype(jnp.int32)
    return jnp.where(match.any(axis=1), idx, jnp.int32(-1))


def _columns(xp, row, start: int, count: int):
    """row[:, start : start+count], zero-filled past the last column."""
    part = row[:, start : start + count]
    short = count - part.shape[1]
    return xp.pad(part, ((0, 0), (0, short))) if short else part


def _gather_window(xp, data, pos, width: int):
    """data[i, pos[i] : pos[i]+width], zero-filled out of range. pos<0 -> zeros.

    A log-step row shift (a barrel shifter), not a gather: for each bit k
    of ``pos`` from high to low, a row whose bit is set moves left by 2^k,
    and only the ``2^k - 1 + width`` columns that the lower bits can still
    reach are kept, so the ten steps of a 1,024-byte row write ~1.6 passes
    over it. Static slices and selects are what ``_find_pattern`` is made
    of and the VPU runs (the v5e's compiler lays these byte matrices out
    rows-minor, so a column slice shifts no lanes); a per-byte
    ``take_along_axis`` is a scalar loop there, 10.7 ns a gathered byte
    (PERF.md section 6, PRs 30-31). The numpy twin runs the same code.
    """
    r = data.shape[1]
    row = data
    for k in reversed(range((r - 1).bit_length())):
        keep = (1 << k) - 1 + width
        moved = ((pos >> k) & 1).astype(bool)[:, None]
        row = xp.where(
            moved, _columns(xp, row, 1 << k, keep), _columns(xp, row, 0, keep)
        )
    hit = ((pos >= 0) & (pos < r))[:, None]
    return xp.where(hit, _columns(xp, row, 0, width), xp.uint8(0))


_INT_WINDOW = 12  # sign + 9 digits + terminator fits comfortably


def _parse_int_at(jnp, data, pos):
    """Parse a decimal integer starting at pos[i]; returns (val int32, ok).

    v1 limits (documented): at most 9 digits (|val| <= 999,999,999 — always
    int32-safe); a non-digit terminator must appear within the window, so
    longer numbers are rejected (ok=False) rather than silently truncated.
    """
    win = _gather_window(jnp, data, pos, _INT_WINDOW).astype(jnp.int32)
    neg = win[:, 0] == ord("-")
    val = jnp.zeros(win.shape[0], dtype=jnp.int32)
    ndigits = jnp.zeros(win.shape[0], dtype=jnp.int32)
    seen = jnp.zeros(win.shape[0], dtype=bool)
    stopped = jnp.zeros(win.shape[0], dtype=bool)
    for i in range(_INT_WINDOW):
        d = win[:, i] - ord("0")
        isdig = (d >= 0) & (d <= 9)
        skip_sign = (i == 0) & neg
        stopped = stopped | (~isdig & ~skip_sign)
        active = ~stopped & isdig
        val = jnp.where(active, val * 10 + d, val)
        ndigits = ndigits + active.astype(jnp.int32)
        seen = seen | active
    val = jnp.where(neg, -val, val)
    ok = seen & stopped & (ndigits <= 9) & (pos >= 0)
    return val, ok


# ---- exact 64-bit integers on 32-bit lanes
# JAX's x64 is off in this tree, and a float is a wrong answer here, not a
# tolerance (0.908 x 99,999,999 cents is off by whole cents in float32). A
# 64-bit value is a pair of uint32 arrays (lo, hi); every step below is a
# uint32 operation that wraps, or a select, so the device program and the
# numpy twin agree bit for bit.
_LONG_WINDOW = 20  # sign + 18 digits + terminator
_SCALED_VALUE_BITS = 30  # a v1 Int's magnitude: 999,999,999 < 2**30


def _u64_times10_plus(lo, hi, d):
    """(hi:lo) * 10 + d for a digit ``d`` (uint32 < 10), by 16-bit halves
    of ``lo`` so that no partial product passes 2**32."""
    p0 = (lo & 0xFFFF) * 10 + d
    p1 = (lo >> 16) * 10 + (p0 >> 16)
    return (p1 << 16) | (p0 & 0xFFFF), hi * 10 + (p1 >> 16)


def _u64_negate_where(xp, neg, lo, hi):
    """Two's complement of (hi:lo) in the rows where ``neg``."""
    nlo = ~lo + 1
    nhi = ~hi + (nlo == 0).astype(xp.uint32)
    return xp.where(neg, nlo, lo), xp.where(neg, nhi, hi)


def _u64_le_bytes(xp, lo, hi):
    """(hi:lo) as uint8 [N, 8], little-endian."""
    return xp.stack(
        [((w >> (8 * k)) & 0xFF).astype(xp.uint8) for w in (lo, hi) for k in range(4)],
        axis=1,
    )


def _parse_long_at(xp, data, pos):
    """Parse a decimal integer of 1-18 digits starting at pos[i]; returns
    (lo, hi uint32: its int64 in two's complement; ok).

    ``_parse_int_at``'s rules at a wider window: an optional ``-``, then
    digits ended by a non-digit inside ``_LONG_WINDOW`` bytes; 19 digits or
    more, or none, is ok=False, never a truncated number."""
    win = _gather_window(xp, data, pos, _LONG_WINDOW)
    n = win.shape[0]
    neg = win[:, 0] == ord("-")
    lo = xp.zeros(n, dtype=xp.uint32)
    hi = xp.zeros(n, dtype=xp.uint32)
    ndigits = xp.zeros(n, dtype=xp.int32)
    seen = xp.zeros(n, dtype=bool)
    stopped = xp.zeros(n, dtype=bool)
    for i in range(_LONG_WINDOW):
        c = win[:, i]
        isdig = (c >= ord("0")) & (c <= ord("9"))
        skip_sign = (i == 0) & neg
        stopped = stopped | (~isdig & ~skip_sign)
        active = ~stopped & isdig
        nlo, nhi = _u64_times10_plus(lo, hi, (c & 0x0F).astype(xp.uint32))
        lo = xp.where(active, nlo, lo)
        hi = xp.where(active, nhi, hi)
        ndigits = ndigits + active.astype(xp.int32)
        seen = seen | active
    ok = seen & stopped & (ndigits <= 18) & (pos >= 0)
    lo, hi = _u64_negate_where(xp, neg, lo, hi)
    return lo, hi, ok


def _scale_exact(xp, val, num: int, den: int):
    """floor(val * num / den) as (lo, hi uint32), for an int32 ``val`` of
    magnitude under 2**30 (a v1 Int), ``|num|`` < 2**31 and 0 < ``den`` <
    2**31: the 32 x 32 product by 16-bit halves, then a restoring long
    division a bit at a time (the remainder stays under ``den``, so its
    doubling fits 32 bits). A negative quotient rounds toward minus
    infinity: -(q + (rem != 0))."""
    a = xp.abs(val).astype(xp.uint32)
    b = abs(num)
    a0, a1 = a & 0xFFFF, a >> 16
    b0, b1 = b & 0xFFFF, b >> 16
    p00 = a0 * b0
    mid = a0 * b1 + a1 * b0
    plo = p00 + (mid << 16)
    phi = a1 * b1 + (mid >> 16) + (plo < p00).astype(xp.uint32)
    rem = xp.zeros(a.shape[0], dtype=xp.uint32)
    qlo = xp.zeros(a.shape[0], dtype=xp.uint32)
    qhi = xp.zeros(a.shape[0], dtype=xp.uint32)
    for i in reversed(range(_SCALED_VALUE_BITS + b.bit_length())):
        word, at = (phi, i - 32) if i >= 32 else (plo, i)
        rem = (rem << 1) | ((word >> at) & 1)
        ge = rem >= den
        rem = xp.where(ge, rem - den, rem)
        if i >= 32:
            qhi = qhi | (ge.astype(xp.uint32) << at)
        else:
            qlo = qlo | (ge.astype(xp.uint32) << at)
    neg = (val < 0) != (num < 0)
    up = (neg & (rem != 0)).astype(xp.uint32)
    rlo = qlo + up
    rhi = qhi + (rlo < qlo).astype(xp.uint32)
    return _u64_negate_where(xp, neg, rlo, rhi)


def _find_byte_from(jnp, window, byte: int):
    """First index of `byte` in each row of window, else width (=miss)."""
    n, w = window.shape
    hit = window == jnp.uint8(byte)
    idx = jnp.argmax(hit, axis=1).astype(jnp.int32)
    return jnp.where(hit.any(axis=1), idx, jnp.int32(w))


# ------------------------------------------------------------ compiler
def _validated(spec_json: str, r_in: int):
    """(spec, r_out) of a payload-pipeline spec, or ValueError."""
    spec = TransformSpec.from_json(spec_json)
    if spec.where is not None:
        raise ValueError(
            "where-expression specs compile to the columnar path "
            "(coproc/column_plan.py), not the raw-payload pipeline"
        )
    mapper = spec.mapper
    if isinstance(mapper, _MapProject):
        if any(not isinstance(f, (Int, Str, Long, Scaled)) for f in mapper.fields):
            raise ValueError(
                "Float/Substr/Concat projections require the columnar path"
            )
        for f in mapper.fields:
            if isinstance(f, Scaled) and not (
                type(f.num) is int and type(f.den) is int
                and abs(f.num) < 2**31 and 0 < f.den < 2**31
            ):
                raise ValueError(
                    f"Scaled({f.key!r}, {f.num!r}, {f.den!r}): num and den are "
                    "integers, |num| < 2**31 and 0 < den < 2**31"
                )
        r_out = project_out_width(mapper.fields)
        if r_out > r_in:
            raise ValueError("projected width exceeds input width")
    elif isinstance(mapper, _MapProjectJson):
        if not mapper.fields:
            raise ValueError("map_project_json: no field")
        if r_in >= 2**15:
            raise ValueError("map_project_json: a row under 32,768 bytes (its column scans are int16)")
        for f in mapper.fields:
            _json_field_doc(f)
            _json_path(f.key)
            if isinstance(f, Str) and not (type(f.max_len) is int and f.max_len >= 1):
                raise ValueError(f"Str({f.key!r}, {f.max_len!r}): a width of 1 or more")
        r_out = project_out_width(mapper.fields)
        if r_out > r_in:
            raise ValueError("projected width exceeds input width")
    else:
        r_out = r_in
    return spec, r_out


def packbits(xp, keep):
    """bool [n] -> uint8 [n/8], big-endian bit order (numpy unpackbits):
    the one bit packing of every keep mask that crosses the link."""
    n = keep.shape[0]
    assert n % 8 == 0, "row buckets are multiples of 8"
    b = keep.astype(xp.uint8).reshape(n // 8, 8)
    weights = xp.array([128, 64, 32, 16, 8, 4, 2, 1], dtype=xp.uint8)
    return (b * weights[None, :]).sum(axis=1).astype(xp.uint8)


def _value_pos(xp, data, lengths, key: str):
    """Where the value after the first ``"key":`` starts in each row, else -1."""
    pat = f'"{key}":'.encode()
    pos = _find_pattern(xp, data, lengths, pat)
    return xp.where(pos >= 0, pos + len(pat), xp.int32(-1))


def _str_record(xp, win, slen, max_len: int):
    """A ``Str`` field's bytes as ``_project`` lays them out (whose own
    lines stay as they are: the v1 programs' lowered text is pinned):
    uint16 LE length (at most ``max_len``), then the first ``slen`` bytes
    of ``win`` zero-padded to ``max_len``."""
    slen = xp.minimum(slen, max_len)
    body = win[:, :max_len]
    mask = xp.arange(max_len, dtype=xp.int32)[None, :] < slen[:, None]
    body = xp.where(mask, body, xp.uint8(0))
    lenhdr = xp.stack(
        [
            (slen & 0xFF).astype(xp.uint8),
            ((slen >> 8) & 0xFF).astype(xp.uint8),
        ],
        axis=1,
    )
    return xp.concatenate([lenhdr, body], axis=1)


def _project(xp, mapper: _MapProject, data, lengths, scope=contextlib.nullcontext):
    """(out uint8 [N, r_out], ok bool [N]) of ``map_project``: the
    fixed-width struct of every row, and whether the row's projection
    could be made faithfully (a row it could not is dropped)."""
    parts = []
    ok_all = xp.ones(data.shape[0], dtype=bool)
    for f in mapper.fields:
        if isinstance(f, (Long, Scaled)):
            kind = "long" if isinstance(f, Long) else "scaled"
            with scope("project." + kind):
                vpos = _value_pos(xp, data, lengths, f.key)
                if isinstance(f, Long):
                    lo, hi, ok = _parse_long_at(xp, data, vpos)
                else:
                    val, ok = _parse_int_at(xp, data, vpos)
                    lo, hi = _scale_exact(xp, val, f.num, f.den)
                ok_all = ok_all & ok
                parts.append(_u64_le_bytes(xp, lo, hi))
        elif isinstance(f, Int):
            val, ok = _parse_int_at(xp, data, _value_pos(xp, data, lengths, f.key))
            ok_all = ok_all & ok
            le = val.astype(xp.uint32)
            parts.append(
                xp.stack(
                    [(le >> (8 * k)).astype(xp.uint8) for k in range(4)], axis=1
                )
            )
        else:
            pat = f'"{f.key}":"'.encode()
            pos = _find_pattern(xp, data, lengths, pat)
            spos = xp.where(pos >= 0, pos + len(pat), xp.int32(-1))
            win = _gather_window(xp, data, spos, f.max_len + 1)
            slen = _find_byte_from(xp, win, ord('"'))
            found_quote = slen <= f.max_len
            slen = xp.minimum(slen, f.max_len)
            ok_all = ok_all & (pos >= 0) & found_quote
            body = win[:, : f.max_len]
            mask = xp.arange(f.max_len, dtype=xp.int32)[None, :] < slen[:, None]
            body = xp.where(mask, body, xp.uint8(0))
            lenhdr = xp.stack(
                [
                    (slen & 0xFF).astype(xp.uint8),
                    ((slen >> 8) & 0xFF).astype(xp.uint8),
                ],
                axis=1,
            )
            parts.append(xp.concatenate([lenhdr, body], axis=1))
    return xp.concatenate(parts, axis=1), ok_all


# ---- JSON read as JSON: the structural program of ``map_project_json``
# Why a row was dropped, left in a trailing metadata column of the result
# row (ops/pipeline.py) and counted by the engine's harvest.
JSON_MALFORMED = 1  # not one sound JSON object (see _json_structure)
JSON_PATH_MISS = 2  # a path is absent, or holds no value its field can hold
_JSON_MAX_DEPTH = 100  # deeper nesting drops the row: the depth is an int8
_JSON_MAX_PATH = 16
_WS = (0x20, 0x09, 0x0A, 0x0D)  # RFC 8259 whitespace


def _json_path(key: str) -> tuple:
    """The byte segments of a dotted path, or ValueError. A segment is
    matched against a key's bytes as the document writes them, so it holds
    printable ASCII without ``"`` and ``\\`` (what needs no escape)."""
    segs = key.split(".")
    if not 1 <= len(segs) <= _JSON_MAX_PATH or any(
        not seg or any(not " " <= c <= "~" or c in '"\\' for c in seg) for seg in segs
    ):
        raise ValueError(
            f"map_project_json path {key!r}: 1-{_JSON_MAX_PATH} non-empty segments "
            'of printable ASCII without " and \\, joined by "."'
        )
    return tuple(seg.encode("ascii") for seg in segs)


def _either(xp, pred, then, otherwise):
    """``then()`` if the scalar ``pred`` else ``otherwise()``: a device
    conditional (both sides compiled, one run) or numpy's plain branch."""
    if xp.__name__ == "numpy":
        return then() if pred else otherwise()
    from jax import lax

    return lax.cond(pred, then, otherwise)


def _log_steps(r: int):
    return [1 << k for k in range((r - 1).bit_length())]


def _shift_right(xp, a, s: int):
    """a[:, i - s], zeros (False) entering at the row's start."""
    return xp.pad(a[:, : a.shape[1] - s], ((0, 0), (s, 0)))


def _prefix_xor(xp, q):
    """Inclusive XOR-prefix of a bool matrix along its rows: the parity of
    a wrapping uint8 count. Of the forms step 0 timed on the v5e at
    [32768, 1024] (PERF.md section 6, PR 40: ``cumsum`` in the byte's own
    width 0.60 ms, ten log-steps of a shifted slice 0.85, ``cumsum`` in
    int32 1.95, ``lax.associative_scan`` 2.10, a column-by-column
    ``lax.scan`` 3.78) this is the fastest, and numpy has it too."""
    return (xp.cumsum(q, axis=1, dtype=xp.uint8) & 1).astype(bool)


def _prefix_sum(xp, d):
    """Inclusive prefix sum of an int8 matrix along its rows, wrapping."""
    return xp.cumsum(d, axis=1, dtype=xp.int8)


def _prefix_max(xp, a):
    """Inclusive running maximum along the rows."""
    if xp.__name__ == "numpy":
        return xp.maximum.accumulate(a, axis=1)
    from jax import lax

    return lax.cummax(a, axis=1)


def _first_col(xp, mask, idx):
    """First column where ``mask`` holds in each row, else the row width."""
    return xp.min(xp.where(mask, idx, xp.int32(mask.shape[1])), axis=1)


def _last_col(xp, mask, idx):
    """Last column where ``mask`` holds in each row, else -1."""
    return xp.max(xp.where(mask, idx, xp.int32(-1)), axis=1)


def _byte_at(xp, b, pos, idx):
    """b[i, pos[i]] as int32, 0 when pos[i] is outside the row."""
    return xp.sum(
        xp.where(idx == pos[:, None], b, xp.uint8(0)), axis=1, dtype=xp.int32
    )


def _json_structure(xp, data, lengths):
    """The structural pass over a staged byte matrix: every row read as one
    JSON text. Returns a dict of [N, R] arrays and the row verdict:

    - ``b``: the row's bytes, 0 past its length;
    - ``quote``: an unescaped ``"`` (a quote is escaped iff an odd run of
      ``\\`` precedes it);
    - ``instr``: inside a string, from its opening quote through its last
      byte (the closing quote is outside);
    - ``depth`` (int8): ``{`` ``[`` up and ``}`` ``]`` down outside
      strings, inclusive: a bracket counts at its own column, so an
      object's members sit at the depth of its ``{`` and its ``}`` is one
      below;
    - ``after``: the first byte at or after a column that is no whitespace;
    - ``esc_key``: at a closing quote, the string held a ``\\`` and a
      colon follows: a key the byte match cannot read;
    - ``sound`` [N]: the row is one object and nothing else: non-empty,
      every string closed, brackets balanced, never below zero nor above
      ``_JSON_MAX_DEPTH``, and the only byte outside all brackets that is
      no whitespace is the object's ``{``. Bracket KINDS are not matched
      and literals are not checked: this is not a validator (module docs).

    A row without a ``\\`` needs none of the three backslash scans (two
    running maxima and a count, in int16), so they run under one
    conditional on the launch holding any: what they find is all False
    there, the same bytes out either way."""
    r = data.shape[1]
    idx = xp.arange(r, dtype=xp.int32)[None, :]
    valid = idx < lengths[:, None]
    b = xp.where(valid, data, xp.uint8(0))
    backslash = b == ord("\\")
    anyquote = b == ord('"')

    def backslash_scans():
        # a backslash run's length is the distance to the last column
        # before it that holds none: escaped = an odd run ends just before
        cols = idx.astype(xp.int16)
        plain_at = _prefix_max(xp, xp.where(backslash, xp.int16(-1), cols))
        odd_run = ((cols - plain_at) & 1).astype(bool)
        quote = anyquote & ~_shift_right(xp, odd_run, 1)
        instr = _prefix_xor(xp, quote)
        # backslashes counted inside strings: a string has held one when
        # the count has grown since its opening quote (the count never
        # falls, so the running maximum over opening quotes is the last's)
        held = xp.cumsum(backslash & instr, axis=1, dtype=xp.int16)
        at_open = _prefix_max(xp, xp.where(quote & instr, held, xp.int16(0)))
        return quote, instr, _shift_right(xp, held > at_open, 1)

    def plain():
        return anyquote, _prefix_xor(xp, anyquote), xp.zeros(b.shape, dtype=bool)

    quote, instr, bs_before = _either(xp, backslash.any(), backslash_scans, plain)
    outside = ~instr
    is_open = outside & ((b == ord("{")) | (b == ord("[")))
    is_close = outside & ((b == ord("}")) | (b == ord("]")))
    delta = is_open.astype(xp.int8) - is_close.astype(xp.int8)
    depth = _prefix_sum(xp, delta)
    ws = b == _WS[0]
    for c in _WS[1:]:
        ws = ws | (b == c)
    after = xp.where(ws, xp.uint8(_WS[0]), b)
    for s in _log_steps(r):
        after = xp.where(after == _WS[0], _columns(xp, after, s, r), after)
    top = valid & ~ws & (depth == delta)  # depth before it: 0

    def count(mask):
        return xp.sum(mask, axis=1, dtype=xp.int32)

    sound = (
        (lengths > 0)
        & (count(top) == 1)
        & (count(top & is_open & (b == ord("{"))) == 1)
        & (count(quote) & 1 == 0)
        & (count(is_open) == count(is_close))
        & ~(depth.astype(xp.uint8) > _JSON_MAX_DEPTH).any(axis=1)
    )
    esc_key = quote & outside & bs_before & (_columns(xp, after, 1, r) == ord(":"))
    return {
        "b": b, "idx": idx, "quote": quote, "instr": instr, "depth": depth,
        "after": after, "nonws": ~ws, "esc_key": esc_key,
    }, sound


def _json_walk(xp, st, segs: tuple, memo: dict):
    """(pos int32 [N], found bool [N]): where the value at a path of object
    keys starts in each row, resolved as a JSON parser does: a segment
    matches a key token (``"seg"``, whitespace, ``:``) at its depth inside
    its parent's object span, outside every string; of a repeated key the
    last wins. ``memo``: prefixes shared between fields walk once."""
    if segs in memo:
        return memo[segs]
    b, idx, r = st["b"], st["idx"], st["b"].shape[1]
    level = len(segs)
    inside = st["depth"] == level
    if level > 1:
        # the parent's value is an object, and the key lies inside it
        at, found = _json_walk(xp, st, segs[:-1], memo)
        found = found & (_byte_at(xp, b, at, idx) == ord("{"))
        # the object's own bracket: the first column after its "{" where
        # the depth is back to its parent's
        close = _first_col(
            xp, (st["depth"] == level - 1) & (idx > at[:, None]), idx
        )
        inside = inside & (idx > at[:, None]) & (idx < close[:, None])
    else:
        found = xp.ones(b.shape[0], dtype=bool)
    pat = b'"' + segs[-1] + b'"'
    key = st["quote"] & st["instr"] & inside  # a string opens here, at this depth
    for k in range(1, len(pat)):
        key = key & (_columns(xp, b, k, r) == pat[k])
    key = key & (_columns(xp, st["after"], len(pat), r) == ord(":"))
    # a key of this object written with an escape cannot be matched by its
    # bytes, and may be the one a parser would read: the row is not
    # resolved rather than resolved wrongly
    unread = (st["esc_key"] & inside).any(axis=1)
    at = _last_col(xp, key, idx)
    nonws = st["nonws"]
    colon = _first_col(xp, nonws & (idx >= (at + len(pat))[:, None]), idx)
    pos = _first_col(xp, nonws & (idx > colon[:, None]), idx)
    memo[segs] = (pos, found & (at >= 0) & ~unread)
    return memo[segs]


def _json_integer_at(xp, b, pos, most_digits: int):
    """A JSON integer of 1-``most_digits`` digits at pos[i]: (lo, hi uint32:
    its int64 in two's complement; ok). An optional ``-``, digits with no
    leading zero, ended by whitespace, ``,`` or ``}``: a fraction, an
    exponent, more digits or anything else there is ok=False, never the
    number's prefix."""
    width = most_digits + 2  # sign, digits, the byte that ends them
    win = _gather_window(xp, b, pos, width)
    n = win.shape[0]
    neg = win[:, 0] == ord("-")
    lo = xp.zeros(n, dtype=xp.uint32)
    hi = xp.zeros(n, dtype=xp.uint32)
    ndigits = xp.zeros(n, dtype=xp.int32)
    stopped = xp.zeros(n, dtype=bool)
    end = xp.zeros(n, dtype=xp.uint8)
    for i in range(width):
        c = win[:, i]
        isdig = (c >= ord("0")) & (c <= ord("9"))
        ends = ~stopped & ~isdig & ~((i == 0) & neg)
        end = xp.where(ends, c, end)
        stopped = stopped | ends
        active = ~stopped & isdig
        nlo, nhi = _u64_times10_plus(lo, hi, (c & 0x0F).astype(xp.uint32))
        lo = xp.where(active, nlo, lo)
        hi = xp.where(active, nhi, hi)
        ndigits = ndigits + active.astype(xp.int32)
    lead = xp.where(neg, win[:, 1], win[:, 0])
    ended = stopped & ((end == ord(",")) | (end == ord("}")))
    for c in _WS:
        ended = ended | (stopped & (end == c))
    ok = (
        (ndigits >= 1) & (ndigits <= most_digits) & ended
        & ~((lead == ord("0")) & (ndigits > 1))
    )
    lo, hi = _u64_negate_where(xp, neg, lo, hi)
    return lo, hi, ok


def _project_json(xp, mapper: _MapProjectJson, data, lengths, scope=contextlib.nullcontext):
    """(out uint8 [N, r_out], reason uint8 [N]) of ``map_project_json``:
    ``_project``'s record of every row, and 0 where it holds what a JSON
    parser reads at each path, else why the row is dropped."""
    with scope("json.structure"):
        st, sound = _json_structure(xp, data, lengths)
    parts = []
    hit = xp.ones(data.shape[0], dtype=bool)
    with scope("json.path"):
        memo = {}
        for f in mapper.fields:
            pos, found = _json_walk(xp, st, _json_path(f.key), memo)
            pos = xp.where(found, pos, xp.int32(-1))
            if isinstance(f, Str):
                # the opening quote, max_len bytes, the closing quote
                win = _gather_window(xp, st["b"], pos, f.max_len + 2)
                body = win[:, 1:]
                slen = _find_byte_from(xp, body, ord('"'))
                cols = xp.arange(f.max_len + 1, dtype=xp.int32)[None, :]
                text = cols < slen[:, None]
                # a string that holds a backslash is dropped, not unescaped
                ok = (
                    (win[:, 0] == ord('"')) & (slen <= f.max_len)
                    & ~((body == ord("\\")) & text).any(axis=1)
                )
                parts.append(_str_record(xp, body, slen, f.max_len))
            else:
                wide = isinstance(f, Long)
                lo, hi, ok = _json_integer_at(xp, st["b"], pos, 18 if wide else 9)
                le = _u64_le_bytes(xp, lo, hi)
                parts.append(le if wide else le[:, :4])
            hit = hit & found & ok
    reason = xp.where(
        sound, xp.where(hit, 0, JSON_PATH_MISS), JSON_MALFORMED
    ).astype(xp.uint8)
    return xp.concatenate(parts, axis=1), reason


def _transform_body(
    xp,
    spec: TransformSpec,
    r_out: int,
    scope=contextlib.nullcontext,
    with_reason: bool = False,
):
    """The transform as array code over namespace ``xp``: jax.numpy for the
    device program, numpy for the engine's host fallback. Every operation
    is an integer or boolean one, so the two evaluate bit-identically.
    ``scope``: ``jax.named_scope`` for the device program, so that a
    profile's operations carry the stage that owns them (``filter``,
    ``project``); the numpy twin takes the null scope."""
    mapper = spec.mapper

    def rp_transform(data, lengths):
        data = data.astype(xp.uint8)
        lengths = lengths.astype(xp.int32)
        with scope("filter"):
            keep = lengths > 0
            for f in spec.filters:
                idx = _find_pattern(
                    xp, data, lengths, f.pattern, f.require_nonnum_suffix
                )
                hit = idx >= 0
                keep = keep & (~hit if f.negate else hit)

        if isinstance(mapper, _MapUppercase):
            is_lower = (data >= ord("a")) & (data <= ord("z"))
            out = xp.where(is_lower, data - 32, data)
            return out, lengths, keep
        if isinstance(mapper, _MapProject):
            with scope("project"):
                out, ok_all = _project(xp, mapper, data, lengths, scope)
                keep2 = keep & ok_all
                out_len = xp.where(keep2, xp.int32(r_out), 0)
            return out, out_len, keep2
        if isinstance(mapper, _MapProjectJson):
            with scope("project"):
                out, reason = _project_json(xp, mapper, data, lengths, scope)
                keep2 = keep & (reason == 0)
                out_len = xp.where(keep2, xp.int32(r_out), 0)
            if with_reason:
                if spec.filters:
                    # a row a filter dropped was not dropped by the JSON read
                    reason = xp.where(keep | (lengths <= 0), reason, xp.uint8(0))
                return out, out_len, keep2, reason
            return out, out_len, keep2
        # identity map
        return data, lengths, keep

    return rp_transform


@functools.lru_cache(maxsize=64)
def _compile_cached(spec_json: str, r_in: int, with_reason: bool):
    import jax
    import jax.numpy as jnp

    spec, r_out = _validated(spec_json, r_in)
    body = _transform_body(jnp, spec, r_out, jax.named_scope, with_reason)
    return jax.jit(body), r_out


def compile_transform(spec: TransformSpec, r_in: int, with_reason: bool = False):
    """Compile to fn(data uint8 [N, r_in], lengths [N]) -> (out, out_len, keep).

    The compiled callable is cached per (spec, r_in); output rows for dropped
    records are undefined (mask with `keep`). ``with_reason`` (a spec that
    ``reports_reason``): a fourth result, uint8 [N], why each dropped row
    was dropped (``JSON_MALFORMED`` / ``JSON_PATH_MISS``, 0 for a kept row
    and for one a filter dropped).
    """
    fn, _ = _compile_cached(spec.to_json(), int(r_in), bool(with_reason))
    return fn


def compile_transform_host(spec: TransformSpec, r_in: int, with_reason: bool = False):
    """compile_transform's numpy twin: the same array code with no JAX
    backend under it (the engine's exact payload fallback)."""
    import numpy as np

    spec, r_out = _validated(spec.to_json(), int(r_in))
    return _transform_body(np, spec, r_out, with_reason=with_reason)


def reports_reason(spec: TransformSpec) -> bool:
    """Whether the spec's program says why it dropped a row (``JSON_*``):
    the structural map does, and the packed pipeline carries the code in a
    trailing metadata column of the result row."""
    return isinstance(spec.mapper, _MapProjectJson)


def transform_out_width(spec: TransformSpec, r_in: int) -> int:
    if isinstance(spec.mapper, (_MapProject, _MapProjectJson)):
        return project_out_width(spec.mapper.fields)
    return r_in
