"""Execution planning for transform specs: columnar / payload / host.

Shipping record payloads to the device moves every padded row across the
link both ways (a 64-partition tick is ~2.4 MB each way) for microseconds
of compute; whether a local chip's link makes that a loss is not measured
yet (tools/link_probe.py; ROADMAP A2). The reference met the same cost in
miniature — its supervisor RPC ships batches to a sidecar process
(coproc/script_context.cc send_request) — and answered with batching; the
columnar lane answers with *pushdown*:

- **columnar** (v2 ``where`` expression specs): the native columnarizer
  (native/redpanda_native.cc rp_extract_*) turns each referenced field into
  a fixed-width column — a few bytes per record. The device evaluates the
  whole predicate tree over the columns and returns ONE BIT per record
  (bit-packed, so D2H is n/8 bytes). Projections are assembled host-side
  from columns the host already extracted; output framing/compression/CRC
  were always host work (ops/pipeline.py module docs).
- **payload** (v1 raw-byte specs: filter_contains, map_uppercase with
  filters): the original full-row staging pipeline — whole rows cross the
  link both ways. The v1 forms keep their byte semantics (substring
  scans for ``"key":``); ``map_project_json`` rides the same lane and
  geometry with a program that reads each row as JSON (a structural pass,
  then dotted paths looked up by structure: ops/transforms.py), and says
  in a trailing byte of the result row why it dropped a row.
- **host** (identity, pure uppercase, py_transform escape hatch): no device
  stage exists or none is warranted; runs in the engine's host stage with
  the same interface and semantics.

`plan_spec` is the single decision point; `ColumnarPlan.compile_device`
builds the jitted predicate program (optionally SPMD over a mesh partition
axis), and `assemble_rows` materializes projection outputs.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field as dc_field

import numpy as np

from redpanda_tpu.ops import exprs as E
from redpanda_tpu.ops.transforms import (
    Concat,
    Float,
    Int,
    Long,
    Scaled,
    Str,
    Substr,
    TransformSpec,
    _MapProject,
    _MapProjectJson,
    _MapUppercase,
    packbits as _packbits,
    reports_reason,
    project_out_width,
)

_INT9 = 999_999_999  # v1 projection rule: ints limited to 9 digits


# ------------------------------------------------------------------ columns
@dataclass(frozen=True)
class DevCol:
    """One device input column; kind in {str, num, exists}."""

    kind: str
    path: str
    w: int = 0  # str byte width (merged across uses)


# input arrays contributed per DevCol kind: str -> (bytes, vlen),
# num -> (f32, i32, flags), exists -> (present,)
_COL_ARITY = {"str": 2, "num": 3, "exists": 1}

# DevCol kind -> rp_extract_cols2 desc kind code
_PRED_KIND = {"num": 0, "str": 1, "exists": 2}


class FindCache:
    """Span tables from ONE native JSON walk per record for every
    single-segment path a plan references (rp_find_multi) — the extractors
    gather from these tables instead of re-walking the record per field."""

    def __init__(self, lib, joined, offsets, sizes, paths: list[str]):
        self._lib = lib
        self._joined = joined
        self._offsets = np.ascontiguousarray(offsets, dtype=np.int64)
        self.col = {p: i for i, p in enumerate(paths)}
        self.types, self.vs, self.ve = lib.find_multi(joined, offsets, sizes, paths)

    @classmethod
    def from_tables(cls, lib, joined, offsets, paths, types, vs, ve) -> "FindCache":
        """Wrap span tables the fused explode_find pass already produced
        (same layout as find_multi's) without re-walking anything."""
        self = cls.__new__(cls)
        self._lib = lib
        self._joined = joined
        self._offsets = np.ascontiguousarray(offsets, dtype=np.int64)
        self.col = {p: i for i, p in enumerate(paths)}
        self.types, self.vs, self.ve = types, vs, ve
        return self

    def gather_str(self, path: str, w: int):
        i = self.col[path]
        return self._lib.gather_str(
            self._joined, self._offsets,
            self.types[:, i], self.vs[:, i], self.ve[:, i], w,
        )

    def gather_num(self, path: str):
        i = self.col[path]
        return self._lib.gather_num(
            self._joined, self._offsets,
            self.types[:, i], self.vs[:, i], self.ve[:, i],
        )

    def gather_exists(self, path: str):
        i = self.col[path]
        return (self.types[:, i] != 0).astype(np.uint8)


@dataclass
class ColumnarPlan:
    spec: TransformSpec
    dev_cols: list[DevCol]
    proj: tuple  # projection fields (may be empty -> passthrough)
    r_out: int
    passthrough: bool  # no projection: output = input value bytes
    _fn_cache: dict = dc_field(default_factory=dict)
    # compile_device may be reached from host-pool shard workers and
    # concurrent submitters; first-touch jit is seconds, so a racy
    # check-then-compile would trace the same predicate N times
    _fn_lock: threading.Lock = dc_field(default_factory=threading.Lock)

    mode = "columnar"

    @property
    def byte_identity(self) -> bool:
        """True when the transform's output bytes ARE the input value bytes
        (a pure filter: no projection mutates anything). The engine's
        zero-copy harvest gathers framed output straight from the launch's
        joined blob via (offset, len) — legal exactly when this holds; any
        projection assembles new bytes and must keep the padded path."""
        return self.passthrough

    def flat_paths(self) -> list[str]:
        """Distinct TOP-LEVEL (single-segment) paths the plan references;
        nested paths keep the per-path walker."""
        seen: dict[str, None] = {}
        for c in self.dev_cols:
            seen.setdefault(c.path)
        for f in self.proj:
            if isinstance(f, Concat):
                seen.setdefault(f.a)
                seen.setdefault(f.b)
            else:
                seen.setdefault(f.key)
        return [p for p in seen if "." not in p]

    def build_find_cache(self, joined, offsets, sizes) -> FindCache | None:
        lib = _native()
        if lib is None or not getattr(lib, "has_find_multi", False):
            return None
        paths = self.flat_paths()
        if not paths:
            return None
        return FindCache(lib, joined, offsets, sizes, paths)

    def make_cache_from_tables(self, exploded, paths, types, vs, ve) -> FindCache:
        """Adopt the span tables the fused explode_find pass produced.
        `paths` MUST be the exact list the fused call used — the table
        columns are ordered by it."""
        return FindCache.from_tables(
            _native(), exploded.joined, exploded.offsets, paths,
            types, vs, ve,
        )

    def _bind_slots(self, arrays) -> dict:
        """Ordered input arrays -> {(kind, path): arrays} slot map — the ONE
        place that knows the per-kind arity (str=2, num=3, exists=1); the
        device predicate, the host ablation, and extract_device_inputs all
        stay aligned through it."""
        slots = {}
        k = 0
        for c in self.dev_cols:
            arity = _COL_ARITY[c.kind]
            slots[(c.kind, c.path)] = (
                arrays[k] if arity == 1 else tuple(arrays[k : k + arity])
            )
            k += arity
        return slots

    # ------------------------------------------------------------ device
    def compile_device(self):
        """jit fn(*cols) -> packed keep bits (uint8 [n/8]), one
        single-device program.

        Each DevCol contributes inputs in order: str -> (bytes [n, w] u8,
        vlen [n] i32); num -> (f32 [n], i32 [n], flags [n] u8);
        exists -> (u8 [n]).
        """
        fn = self._fn_cache.get("single")
        if fn is not None:
            return fn
        with self._fn_lock:
            fn = self._fn_cache.get("single")
            if fn is not None:
                return fn
            return self._compile_device_locked()

    def _compile_device_locked(self):
        import jax
        import jax.numpy as jnp

        expr = self.spec.where
        # comparison constants are converted HOST-side, once, before the
        # traced function exists: float()/int()/np.* inside the predicate is
        # exactly the hot-path impurity pandalint HPS201/HPN211 flags
        consts = _prepare_cmp_consts(expr)

        # the function's name is the program's (``jit_rp_columnar_predicate``
        # on the profile's module line and in compile logs); the scopes name
        # its operations
        def rp_columnar_predicate(*arrays):
            with jax.named_scope("predicate"):
                keep = _build_expr(jnp, expr, self._bind_slots(arrays), consts)
            with jax.named_scope("frame"):
                return _packbits(jnp, keep)

        fn = jax.jit(rp_columnar_predicate)
        self._fn_cache["single"] = fn
        return fn

    def compile_device_stacked(self, mesh):
        """shard_map'd twin of compile_device for the meshrunner: every
        input is a per-device STACK [D, n_pad, ...] sharded over the
        mesh's 'p' axis, output is packed keep bits [D, n_pad//8] with
        the same sharding. Each device evaluates its own [n_pad] block of
        the SAME predicate tree, so bit (d, i) is identical to what
        compile_device over device d's rows alone would produce — the
        mesh-vs-single parity contract."""
        key = ("stacked", id(mesh))
        fn = self._fn_cache.get(key)
        if fn is not None:
            return fn
        with self._fn_lock:
            fn = self._fn_cache.get(key)
            if fn is not None:
                return fn
            return self._compile_stacked_locked(key, mesh)

    def _compile_stacked_locked(self, key, mesh):
        import jax
        import jax.numpy as jnp

        from jax import shard_map
        from jax.sharding import PartitionSpec

        from redpanda_tpu.parallel.mesh import PARTITION_AXIS

        expr = self.spec.where
        consts = _prepare_cmp_consts(expr)
        plan = self

        def rp_columnar_predicate_mesh(*arrays):
            # per-device block: [1, n_pad, ...] -> strip the device dim,
            # evaluate the shared predicate tree, re-add it for out_specs
            flat = [a[0] for a in arrays]
            with jax.named_scope("predicate"):
                keep = _build_expr(jnp, expr, plan._bind_slots(flat), consts)
            with jax.named_scope("frame"):
                return _packbits(jnp, keep)[None, :]

        in_specs = []
        for c in self.dev_cols:
            in_specs += [PartitionSpec(PARTITION_AXIS)] * _COL_ARITY[c.kind]
        fn = jax.jit(
            shard_map(
                rp_columnar_predicate_mesh,
                mesh=mesh,
                in_specs=tuple(in_specs),
                out_specs=PartitionSpec(PARTITION_AXIS),
            )
        )
        self._fn_cache[key] = fn
        return fn

    def eval_host_mask(self, cols) -> np.ndarray:
        """ABLATION twin of compile_device: the SAME predicate tree over the
        SAME extracted columns, evaluated in numpy on the host — packed keep
        bits (uint8 [n/8]). _build_expr is namespace-generic and the slot
        binding is shared (_bind_slots), so device and host evaluation
        cannot drift; the bench runs both to measure what the device link
        actually buys."""
        keep = _build_expr(
            np,
            self.spec.where,
            self._bind_slots(cols),
            _prepare_cmp_consts(self.spec.where),
        )
        return _packbits(np, np.asarray(keep, dtype=bool))

    # ------------------------------------------------------------ host side
    def extract_device_inputs(self, joined, offsets, sizes, n_pad: int, cache=None):
        """Native pass over the records -> ordered device input arrays."""
        out = []
        for c in self.dev_cols:
            if c.kind == "str":
                b, v = _extract_str(joined, offsets, sizes, c.path, c.w, n_pad, cache)
                out += [b, v]
            elif c.kind == "num":
                f32, i32, fl = _extract_num(joined, offsets, sizes, c.path, n_pad, cache)
                out += [f32, i32, fl]
            else:
                out.append(_extract_exists(joined, offsets, sizes, c.path, n_pad, cache))
        return out

    def zero_device_inputs(self, n_pad: int) -> list:
        """All-padding device inputs — the dtypes/shapes/arity of
        extract_device_inputs with zero records (str validity -1 =
        absent). Keeps the per-kind array layout in ONE place: an empty
        mesh device shard stacks these so the SPMD input keeps one shape
        regardless of shard occupancy."""
        out = []
        for c in self.dev_cols:
            if c.kind == "str":
                out += [
                    np.zeros((n_pad, c.w), np.uint8),
                    np.full(n_pad, -1, np.int32),
                ]
            elif c.kind == "num":
                out += [
                    np.zeros(n_pad, np.float32),
                    np.zeros(n_pad, np.int32),
                    np.zeros(n_pad, np.uint8),
                ]
            else:
                out.append(np.zeros(n_pad, np.uint8))
        return out

    def extract_projection(self, joined, offsets, sizes, cache=None):
        """Host-side projection columns -> (per-field data, ok mask [n]).

        Fast path: when every projection field is Int/Float/Str over a
        cached span column, ONE native pass (rp_project_rows) gathers all
        fields straight into the packed output rows — no per-field
        [n, w] temporaries, no numpy masking; assemble_rows then just
        unwraps them. Substr/Concat/nested paths keep the general path."""
        n = len(sizes)
        fused = self._project_descs(cache)
        if fused is not None and n:
            descs, lib = fused
            rows, ok = lib.project_rows(
                joined, offsets, cache.types, cache.vs, cache.ve,
                descs, self.r_out,
            )
            return [("rows", rows)], ok
        ok = np.ones(n, dtype=bool)
        data = []
        for f in self.proj:
            if isinstance(f, Int):
                _, i32, fl = _extract_num(joined, offsets, sizes, f.key, n, cache)
                fok = (
                    (fl & (E.F_PRESENT | E.F_NUMBER | E.F_INT_EXACT))
                    == (E.F_PRESENT | E.F_NUMBER | E.F_INT_EXACT)
                ) & (np.abs(i32.astype(np.int64)) <= _INT9)
                ok &= fok
                data.append(("int", i32))
            elif isinstance(f, Float):
                f32, _, fl = _extract_num(joined, offsets, sizes, f.key, n, cache)
                ok &= (fl & (E.F_PRESENT | E.F_NUMBER)) == (
                    E.F_PRESENT | E.F_NUMBER
                )
                data.append(("float", f32))
            elif isinstance(f, Substr):
                b, v = _extract_str(
                    joined, offsets, sizes, f.key, f.start + f.length, n, cache
                )
                ok &= v >= 0
                body = b[:, f.start : f.start + f.length]
                slen = np.clip(v - f.start, 0, f.length).astype(np.int32)
                data.append(("str", body, slen, f.length))
            elif isinstance(f, Concat):
                ba, va = _extract_str(joined, offsets, sizes, f.a, f.max_len, n, cache)
                bb, vb = _extract_str(joined, offsets, sizes, f.b, f.max_len, n, cache)
                ok &= (va >= 0) & (vb >= 0)
                data.append(("concat", ba, va, bb, vb, f.max_len))
            else:  # Str
                b, v = _extract_str(joined, offsets, sizes, f.key, f.max_len, n, cache)
                ok &= (v >= 0) & (v <= f.max_len)
                data.append(("str", b, np.clip(v, 0, f.max_len), f.max_len))
        return data, ok

    def _proj_desc_rows(self, col_of: dict) -> list | None:
        """[{kind, span col, w, out off}] rows for the fused projector, or
        None when any field needs the general path (Substr/Concat/nested).
        Field order and widths MUST mirror assemble_rows' layout walk —
        shared by the staged (rp_project_rows) and structural
        (rp_extract_cols2) fused projectors."""
        descs = []
        off = 0
        for f in self.proj:
            if isinstance(f, Int) and f.key in col_of:
                descs.append((0, col_of[f.key], 0, off))
                off += 4
            elif isinstance(f, Float) and f.key in col_of:
                descs.append((1, col_of[f.key], 0, off))
                off += 4
            elif type(f) is Str and f.key in col_of:
                descs.append((2, col_of[f.key], f.max_len, off))
                off += 2 + f.max_len
            else:  # Substr/Concat/nested: general path
                return None
        return descs

    def _project_descs(self, cache):
        """[n_fields, 4] int32 {kind, span col, w, out off} when the fused
        projector applies to this plan, else None."""
        if cache is None:
            return None
        lib = _native()
        if lib is None or not getattr(lib, "has_project_rows", False):
            return None
        descs = self._proj_desc_rows(cache.col)
        if descs is None:
            return None
        return np.asarray(descs, dtype=np.int32), lib

    # ------------------------------------------------------ structural fused
    def structural_eligible(self) -> bool:
        """Whether the structural-index fused ladder can serve this plan:
        the native structural symbols exist, every DevCol path is a
        top-level single segment, and the projection (when any) is
        expressible as fused Int/Float/Str descs. Anything else keeps the
        staged ladder — the parity contract is 'same outputs, different
        machinery', never 'almost'."""
        lib = _native()
        if lib is None or not getattr(lib, "has_structural", False):
            return False
        col_of = {p: i for i, p in enumerate(self.flat_paths())}
        if not col_of or any(c.path not in col_of for c in self.dev_cols):
            return False
        if self.passthrough:
            return True
        return self._proj_desc_rows(col_of) is not None

    def extract_fused(self, sp, n_pad: int):
        """ONE record-major native crossing off the structural parse's
        span tables: every predicate column and (for projection plans) the
        packed output rows — replaces extract_device_inputs' per-column
        gathers + pads AND extract_projection's separate crossing.
        Returns (cols, proj_data | None, proj_ok | None): cols in
        _bind_slots order, proj_data in assemble_rows' fused shape."""
        lib = _native()
        col_of = {p: i for i, p in enumerate(self.flat_paths())}
        pred = np.asarray(
            [(_PRED_KIND[c.kind], col_of[c.path], c.w, 0)
             for c in self.dev_cols],
            dtype=np.int32,
        ).reshape(-1, 4)
        proj_descs = None
        if not self.passthrough:
            proj_descs = np.asarray(
                self._proj_desc_rows(col_of), dtype=np.int32
            )
        cols, rows, ok = lib.extract_cols2(
            sp.payloads, sp.counts, sp.val_off, sp.val_len,
            sp.types, sp.vs, sp.ve, pred, n_pad, proj_descs, self.r_out,
        )
        if self.passthrough:
            return cols, None, None
        return cols, [("rows", rows)], ok

    def assemble_rows(self, data, n: int) -> tuple[np.ndarray, np.ndarray]:
        """Projection columns -> ([n, r_out] u8 rows, [n] i32 lens)."""
        if len(data) == 1 and data[0][0] == "rows":
            # fused projector already packed the rows at extract time
            return data[0][1], np.full(n, self.r_out, dtype=np.int32)
        rows = np.zeros((n, self.r_out), dtype=np.uint8)
        off = 0
        for item in data:
            kind = item[0]
            if kind in ("int", "float"):
                arr = item[1]
                rows[:, off : off + 4] = (
                    np.ascontiguousarray(arr).view(np.uint8).reshape(n, 4)
                )
                off += 4
            elif kind == "str":
                _, body, slen, w = item
                rows[:, off] = slen & 0xFF
                rows[:, off + 1] = (slen >> 8) & 0xFF
                mask = np.arange(w, dtype=np.int32)[None, :] < slen[:, None]
                rows[:, off + 2 : off + 2 + w] = np.where(mask, body, 0)
                off += 2 + w
            else:  # concat
                _, ba, va, bb, vb, w = item
                alen = np.clip(va, 0, w).astype(np.int32)
                blen = np.clip(vb, 0, np.maximum(w - alen, 0)).astype(np.int32)
                total = alen + blen
                rows[:, off] = total & 0xFF
                rows[:, off + 1] = (total >> 8) & 0xFF
                idx = np.arange(w, dtype=np.int32)[None, :]
                in_a = idx < alen[:, None]
                from_b = idx - alen[:, None]
                in_b = ~in_a & (from_b < blen[:, None])
                a_part = np.where(in_a, ba[:, :w], 0)
                b_idx = np.clip(from_b, 0, w - 1)
                b_part = np.where(in_b, np.take_along_axis(bb[:, :w], b_idx, axis=1), 0)
                rows[:, off + 2 : off + 2 + w] = a_part | b_part
                off += 2 + w
        lens = np.full(n, self.r_out, dtype=np.int32)
        return rows, lens


@dataclass
class PayloadPlan:
    spec: TransformSpec
    mode = "payload"

    @property
    def byte_identity(self) -> bool:
        """True for a pure raw-byte filter: the mapper is the identity, so
        a kept record's output IS its input value and the device has only
        one keep bit a row to send back (the engine then frames kept values
        from the bytes the host still holds). A projection or uppercase
        builds new bytes on the device and keeps the result matrix."""
        return bool(self.spec.filters) and self.spec.mapper is None

    @property
    def structural(self) -> bool:
        """True when the program reads its rows as JSON
        (``map_project_json``): its result rows carry a reason code, which
        the harvest counts (``n_json_*``)."""
        return reports_reason(self.spec)


@dataclass
class HostPlan:
    """No device stage: identity / pure uppercase / py_transform."""

    spec: TransformSpec
    kind: str  # identity | uppercase | python
    fn: object = None  # python escape hatch: callable(bytes) -> bytes | None
    mode = "host"

    @property
    def byte_identity(self) -> bool:
        # identity emits the input value bytes untouched (its keep rule —
        # drop empty values — needs only the sizes column); uppercase and
        # python transforms mutate bytes
        return self.kind == "identity"


def plan_spec(spec: TransformSpec, py_fn=None):
    """Pick the execution mode for a spec (see module docs)."""
    if py_fn is not None:
        return HostPlan(spec, "python", py_fn)
    if spec.where is not None:
        if spec.filters:
            raise ValueError("where-exprs cannot combine with raw filters")
        if isinstance(spec.mapper, _MapUppercase):
            raise ValueError("uppercase is a raw-byte map; use payload specs")
        if isinstance(spec.mapper, _MapProjectJson):
            # the structural pass is the payload lane's device program; the
            # columnar lane's columns come from the native host parser
            raise ValueError(
                "map_project_json runs on the payload lane: write the "
                "filter in the v1 form (filter_contains / filter_field_eq)"
            )
        proj = spec.mapper.fields if isinstance(spec.mapper, _MapProject) else ()
        if any(isinstance(f, (Long, Scaled)) for f in proj):
            # the exact 64-bit kinds live in the payload lane's device
            # program (ops/transforms.py); the columnar projector has no
            # column for them
            raise ValueError(
                "Long/Scaled projections run on the payload lane: write the "
                "filter in the v1 form (filter_contains / filter_field_eq)"
            )
        cols = _collect_dev_cols(spec.where)
        r_out = project_out_width(proj) if proj else 0
        return ColumnarPlan(
            spec, cols, tuple(proj), r_out, passthrough=not proj
        )
    if spec.filters:
        return PayloadPlan(spec)
    if isinstance(spec.mapper, _MapUppercase):
        return HostPlan(spec, "uppercase")
    if isinstance(spec.mapper, _MapProject):
        # A v1 projection-only spec keeps the v1 payload pipeline: its Int
        # semantics differ from columnar (v1 _parse_int_at truncates "3.5"
        # to 3; columnar requires an exact integer) and deployed spec JSON
        # must not change outputs across an upgrade. v2 columnar projection
        # is opted into by writing a where() stage.
        return PayloadPlan(spec)
    if isinstance(spec.mapper, _MapProjectJson):
        return PayloadPlan(spec)
    return HostPlan(spec, "identity")


# ------------------------------------------------------------------ internals
def _collect_dev_cols(expr) -> list[DevCol]:
    cols: dict[tuple, DevCol] = {}

    def need(kind: str, path: str, w: int = 0):
        k = (kind, path)
        if k in cols:
            if kind == "str" and w > cols[k].w:
                cols[k] = DevCol(kind, path, w)
        else:
            cols[k] = DevCol(kind, path, w)

    def walk(e):
        if isinstance(e, (E.And, E.Or)):
            walk(e.a)
            walk(e.b)
        elif isinstance(e, E.Not):
            walk(e.a)
        elif isinstance(e, E.Exists):
            need("exists", e.path)
        elif isinstance(e, E.StrContains):
            need("str", e.path, e.window)
        elif isinstance(e, E.Cmp):
            v = e.value
            if isinstance(v, (str, bytes)):
                raw = v.encode() if isinstance(v, str) else bytes(v)
                need("str", e.path, len(raw))
            elif isinstance(v, (bool, int, float, np.integer, np.floating)) or v is None:
                need("num", e.path)
            else:
                raise TypeError(
                    f"unsupported comparison constant {v!r} for {e.path!r}"
                )
        else:
            raise TypeError(f"not an expr: {e!r}")

    if expr is not None:
        walk(expr)
    return list(cols.values())


def _prepare_cmp_consts(expr) -> dict[int, tuple]:
    """id(Cmp node) -> (f32 const, i32 const | None), prepared host-side.

    Every numeric comparison constant in the tree is classified and
    converted ONCE, before tracing: conversions inside the traced predicate
    would run per trace on host (pandalint HPS201/HPN211 hot-path purity).
    The i32 constant exists only when the spec value is int32-exact, which
    is what gates the exact-integer comparison path on device.
    """
    out: dict[int, tuple] = {}

    def walk(e):
        if isinstance(e, (E.And, E.Or)):
            walk(e.a)
            walk(e.b)
        elif isinstance(e, E.Not):
            walk(e.a)
        elif isinstance(e, E.Cmp):
            v = e.value
            if v is None or isinstance(v, (bool, str, bytes)):
                return
            const_int = (
                isinstance(v, (int, np.integer))
                and not isinstance(v, bool)
                and -(2**31) <= int(v) <= 2**31 - 1
            ) or (
                isinstance(v, (float, np.floating))
                and float(v) == int(v)
                and -(2**31) <= int(v) <= 2**31 - 1
            )
            out[id(e)] = (
                np.float32(float(v)),
                np.int32(int(v)) if const_int else None,
            )

    if expr is not None:
        walk(expr)
    return out


def _build_expr(jnp, expr, slots, consts):
    if isinstance(expr, E.And):
        return _build_expr(jnp, expr.a, slots, consts) & _build_expr(
            jnp, expr.b, slots, consts
        )
    if isinstance(expr, E.Or):
        return _build_expr(jnp, expr.a, slots, consts) | _build_expr(
            jnp, expr.b, slots, consts
        )
    if isinstance(expr, E.Not):
        return ~_build_expr(jnp, expr.a, slots, consts)
    if isinstance(expr, E.Exists):
        col = slots[("exists", expr.path)]
        return col != 0
    if isinstance(expr, E.StrContains):
        bytes_col, vlen = slots[("str", expr.path)]
        return _contains(jnp, bytes_col, vlen, expr.needle, expr.window)
    assert isinstance(expr, E.Cmp)
    v = expr.value
    if isinstance(v, (str, bytes)):
        raw = v.encode() if isinstance(v, str) else bytes(v)
        bytes_col, vlen = slots[("str", expr.path)]
        present = vlen >= 0
        eq = present & (vlen == len(raw))
        for i, ch in enumerate(raw):
            eq = eq & (bytes_col[:, i] == jnp.uint8(ch))
        return eq if expr.op == "eq" else present & ~eq
    f32, i32, flags = slots[("num", expr.path)]
    present = (flags & E.F_PRESENT) != 0
    if isinstance(v, bool):
        isbool = (flags & E.F_BOOL) != 0
        eq = isbool & (i32 == (1 if v else 0))
        return eq if expr.op == "eq" else isbool & ~eq
    if v is None:
        isnull = (flags & E.F_NULL) != 0
        return isnull if expr.op == "eq" else present & ~isnull
    # numeric constant: prepared host-side by _prepare_cmp_consts — no
    # conversions may run inside the traced predicate.
    # E._cmp_num is dtype-generic; sharing it keeps host-oracle and device
    # comparison semantics in one place.
    isnum = (flags & E.F_NUMBER) != 0
    f32c, i32c = consts[id(expr)]
    fcmp = E._cmp_num(expr.op, f32, f32c)
    if i32c is not None:
        int_exact = (flags & E.F_INT_EXACT) != 0
        icmp = E._cmp_num(expr.op, i32, i32c)
        return isnum & jnp.where(int_exact, icmp, fcmp)
    return isnum & fcmp


def _contains(jnp, bytes_col, vlen, needle: bytes, window: int):
    """needle in raw[:window]; scan limited to min(vlen, window).

    The column may be wider than this predicate's window when another
    expression on the same path merged a larger width — the scan must still
    honor THIS predicate's window (host_eval parity)."""
    n, w = bytes_col.shape
    weff = min(window, w)
    l = len(needle)
    present = vlen >= 0
    if l == 0:
        return present
    if l > weff:
        return present & False
    span = jnp.minimum(vlen, weff)  # valid scan length per row
    nwin = weff - l + 1
    match = jnp.ones((n, nwin), dtype=bool)
    for i, ch in enumerate(needle):
        match = match & (bytes_col[:, i : i + nwin] == jnp.uint8(ch))
    starts = jnp.arange(nwin, dtype=jnp.int32)
    match = match & (starts[None, :] <= (span - l)[:, None])
    return present & match.any(axis=1)


# ---------------------------------------------------------------- extractors
def _native():
    try:
        from redpanda_tpu.native import lib

        return lib
    except Exception:
        return None


def _extract_str(joined, offsets, sizes, path, w, n_pad, cache=None):
    lib = _native()
    n = len(sizes)
    if cache is not None and path in cache.col:
        b, v = cache.gather_str(path, w)
    elif lib is not None:
        b, v = lib.extract_str(joined, offsets, sizes, path, w)
    else:
        b = np.zeros((n, w), dtype=np.uint8)
        v = np.full(n, -1, dtype=np.int32)
        for i in range(n):
            rec = joined[offsets[i] : offsets[i] + sizes[i]]
            t, vs, ve = E.json_find(rec, path)
            if t == 1:
                # ve < vs when the record is truncated inside an
                # unterminated string: empty-but-present (native clamp)
                v[i] = max(ve - vs, 0)
                cp = min(v[i], w)
                b[i, :cp] = np.frombuffer(rec[vs : vs + cp], np.uint8)
    if n_pad > n:
        b = np.concatenate([b, np.zeros((n_pad - n, w), np.uint8)])
        v = np.concatenate([v, np.full(n_pad - n, -1, np.int32)])
    return b, v


def _extract_num(joined, offsets, sizes, path, n_pad, cache=None):
    lib = _native()
    n = len(sizes)
    if cache is not None and path in cache.col:
        f32, i32, fl = cache.gather_num(path)
    elif lib is not None:
        f32, i32, fl = lib.extract_num(joined, offsets, sizes, path)
    else:
        f32 = np.zeros(n, np.float32)
        i32 = np.zeros(n, np.int32)
        fl = np.zeros(n, np.uint8)
        for i in range(n):
            rec = joined[offsets[i] : offsets[i] + sizes[i]]
            f = E.host_field(rec, path)
            f32[i], i32[i], fl[i] = f["f32"], f["i32"], f["flags"]
    if n_pad > n:
        f32 = np.concatenate([f32, np.zeros(n_pad - n, np.float32)])
        i32 = np.concatenate([i32, np.zeros(n_pad - n, np.int32)])
        fl = np.concatenate([fl, np.zeros(n_pad - n, np.uint8)])
    return f32, i32, fl


def _extract_exists(joined, offsets, sizes, path, n_pad, cache=None):
    lib = _native()
    n = len(sizes)
    if cache is not None and path in cache.col:
        ex = cache.gather_exists(path)
    elif lib is not None:
        ex = lib.extract_exists(joined, offsets, sizes, path)
    else:
        ex = np.zeros(n, np.uint8)
        for i in range(n):
            rec = joined[offsets[i] : offsets[i] + sizes[i]]
            ex[i] = 1 if E.json_find(rec, path)[0] else 0
    if n_pad > n:
        ex = np.concatenate([ex, np.zeros(n_pad - n, np.uint8)])
    return ex
