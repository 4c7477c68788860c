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
  transforms do.
- ``map_project`` emits a fixed-width binary struct per record ("flatbuffer"
  layout of the north-star config 4): int fields as little-endian int32,
  string fields as uint16 length + fixed-width padded bytes. Records missing
  a projected field are dropped (keep=False).

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
            elif kind == "map_uppercase":
                spec = spec | TransformSpec(mapper=_MapUppercase(), name="")
            else:
                raise ValueError(f"unknown transform op {kind!r}")
        w = None
        if "where" in doc:
            from redpanda_tpu.ops.exprs import Expr

            w = Expr.from_dict(doc["where"])
        return TransformSpec(spec.filters, spec.mapper, doc.get("name", "anon"), w)


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


def _transform_body(
    xp, spec: TransformSpec, r_out: int, scope=contextlib.nullcontext
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
        # identity map
        return data, lengths, keep

    return rp_transform


@functools.lru_cache(maxsize=64)
def _compile_cached(spec_json: str, r_in: int):
    import jax
    import jax.numpy as jnp

    spec, r_out = _validated(spec_json, r_in)
    return jax.jit(_transform_body(jnp, spec, r_out, jax.named_scope)), r_out


def compile_transform(spec: TransformSpec, r_in: int):
    """Compile to fn(data uint8 [N, r_in], lengths [N]) -> (out, out_len, keep).

    The compiled callable is cached per (spec, r_in); output rows for dropped
    records are undefined (mask with `keep`).
    """
    fn, _ = _compile_cached(spec.to_json(), int(r_in))
    return fn


def compile_transform_host(spec: TransformSpec, r_in: int):
    """compile_transform's numpy twin: the same array code with no JAX
    backend under it (the engine's exact payload fallback)."""
    import numpy as np

    spec, r_out = _validated(spec.to_json(), int(r_in))
    return _transform_body(np, spec, r_out)


def transform_out_width(spec: TransformSpec, r_in: int) -> int:
    if isinstance(spec.mapper, _MapProject):
        return project_out_width(spec.mapper.fields)
    return r_in
