"""The TPU transform engine — replacement for the reference's Node.js sidecar.

The reference ships record batches over RPC to a Node.js process that runs
user JS per record (ProcessBatchServer, src/js/modules/rpc/server.ts:79,
applyCoprocessor :244-266). Here the "supervisor" is a JAX engine: deploys
carry a declarative TransformSpec (redpanda_tpu.ops.transforms) compiled once
per script into an execution plan (coproc/column_plan.py).

Data-path architecture: the link between the broker runtime and the device
charges per round trip AND per byte (tools/link_probe.py measures both; not
measured on a local chip yet — ROADMAP A2 decides "ship columns or ship
bytes" from that measurement). The engine as built ships as little as
possible and never blocks per call:

  * **columnar plans** (v2 ``where`` specs) ship per-field columns — a few
    bytes per record — and fetch ONE BIT per record back (packed); the
    device evaluates the whole predicate tree. Projections are assembled
    host-side from columns the native columnarizer already extracted.
  * **payload plans** (v1 raw-byte specs) stage full records: the whole
    row crosses the link both ways.
  * **host plans** (identity / uppercase / py_transform escape hatch) have
    no device stage; they run in the engine's host stage with the same
    interface.
  * ``submit_group()`` fuses MANY requests into one launch per script;
    ``Ticket.result()`` materializes replies after the async D2H lands.
  * ``process_batch()`` is the synchronous compatibility wrapper
    (submit + result), matching the supervisor RPC schema (coproc/gen.json):
    enable_coprocessors / disable_coprocessors / disable_all /
    process_batch / heartbeat.

Per-stage wall time and link bytes accumulate in ``stats()`` so the bench
(and the engine's own mode decisions) argue from data.

Error policies mirror the public SDK (Coprocessor.ts:21-24):
SkipOnFailure drops the failing batch but keeps the script; Deregister
removes the script on first failure.
"""

from __future__ import annotations

import dataclasses
import enum
import itertools
import logging
import queue
import threading
import time
import weakref
from collections import defaultdict
from dataclasses import dataclass, field

import numpy as np

from redpanda_tpu.hashing.xx import xxhash64
from redpanda_tpu.models.fundamental import NTP
from redpanda_tpu.models.record import Compression, RecordBatch
from redpanda_tpu.observability import probes, stages
from redpanda_tpu.observability.trace import tracer
from redpanda_tpu.ops.pipeline import (
    IN_META,
    lower_packed_pipeline,
    make_packed_pipeline,
    make_packed_pipeline_host,
    unpack_reason,
    unpack_result,
)

logger = logging.getLogger("rptpu.coproc.engine")
from redpanda_tpu.ops.transforms import (
    JSON_MALFORMED,
    JSON_PATH_MISS,
    TransformSpec,
    transform_out_width,
)
from redpanda_tpu.coproc import (
    batch_codec,
    colcache,
    faults,
    governor,
    host_pool,
    leakwatch,
    lockwatch,
    meshrunner,
)
from redpanda_tpu.coproc.column_plan import ColumnarPlan, HostPlan, PayloadPlan, plan_spec
from redpanda_tpu.resource_mgmt import admission as rm_admission
from redpanda_tpu.resource_mgmt import budgets as rm_budgets
from redpanda_tpu.utils import platform


class EnableResponseCode(enum.IntEnum):
    success = 0
    internal_error = 1
    script_id_already_exists = 2
    script_contains_invalid_topic = 3
    script_contains_no_topics = 4


class DisableResponseCode(enum.IntEnum):
    success = 0
    internal_error = 1
    script_id_does_not_exist = 2


class ErrorPolicy(enum.IntEnum):
    skip_on_failure = 0
    deregister = 1


@dataclass
class ScriptHandle:
    script_id: int
    spec: TransformSpec
    input_topics: tuple[str, ...]
    policy: ErrorPolicy = ErrorPolicy.skip_on_failure
    checksum: int = 0


@dataclass
class ProcessBatchItem:
    script_id: int
    ntp: NTP
    batches: list[RecordBatch]


@dataclass
class ProcessBatchRequest:
    items: list[ProcessBatchItem] = field(default_factory=list)
    # pandaprobe trace id: executor threads don't inherit the caller's task
    # context, so the ambient id rides the request object across the hop
    # (pacemaker tick → engine submit → harvester thread).
    trace_id: int | None = None


@dataclass
class ProcessBatchReplyItem:
    script_id: int
    source: NTP
    batches: list[RecordBatch]  # transformed output (may be empty)


@dataclass
class ProcessBatchReply:
    items: list[ProcessBatchReplyItem] = field(default_factory=list)
    deregistered: list[int] = field(default_factory=list)


# Parked staging matrices of the payload lane (TpuEngine._staging), parked
# decompress buffers of its explode (TpuEngine._uncompress_pool) and parked
# frame buffers of the seal (TpuEngine._seal_pool). A
# launch in flight holds its own, so this bounds only the idle ones: one
# serves a script's launches one after another, a few more a burst of
# launch_depth launches that land together. NOT leakwatch resources: a
# launch whose device leg failed drops its matrix on purpose
# (_launch_payload), and an abandoned launch's buffers go with the launch.
# The staging pool parks this many a part a launch can have (_staging_slots:
# a launch staged in five parts gives five matrices back at once).
_STAGING_MAX_PARKED = 4
# A decompress buffer (and a seal's frame buffer) is asked for in steps of
# this many bytes, so that a script's launches (45-50 MB of decompressed
# payloads each on the Zstd cell, 5-15 MB of kept payload to compress on
# the uncompressed ones) are served by one parked buffer and not by a ladder
# of ever larger ones, each paying the first touch of its pages.
_UNCOMPRESS_QUANTUM = 8 << 20


def _release_exploded(ex) -> None:
    """A launch has read its last byte out of its exploded table: a pointer
    table's pooled decompress buffers go back to the engine's pool (the
    joined-blob table owns nothing pooled)."""
    if isinstance(ex, batch_codec.PtrExploded):
        ex.release()


def _bucket_rows(n: int) -> int:
    """Round the row count up so jit sees few distinct shapes."""
    b = 128
    while b < n:
        b *= 2
    return b


# The densest rows a launch's read is sized for: records an eighth of the
# staging row wide. The ladder's top is the bucket of (the bytes the read
# budget can hand one launch) / (row_stride / this); narrower records than
# that make a launch the engine cuts (_Ladder).
_LADDER_ROWS_PER_STRIDE = 8


# A staged row's width comes in classes: up to _BODY_STRIDE bytes a class is
# this many bytes and a part's stride is the smallest multiple of it that
# holds the part's widest value; above, a class is twice the one below it
# (2,048, 4,096, ... the lane's limit, ``row_stride``, the widest): a tail of
# large values is few rows, and classes 128 B apart would give each a
# matrix and a program of its own (TpuEngine._plan_parts).
_STRIDE_CLASS = 128
_BODY_STRIDE = 1024
# A narrower stride's program is built at once for the row bucket a launch's
# mix fills when the read budget hands it all it can (_stride_ready), and
# for a launch's own bucket only once launches have gone on asking for that
# one bucket this long: twice the governor's hold between launch-knob moves,
# so a step of the knob's ramp is not worth a trace beside the serving path
# and a live stream's steady launches are.
_WANT_HOLD_S = 2 * governor.AUTOTUNE_HOLD_S
# Until then a part rides in the next part up whose program is built, the
# lane's own stride at worst. Where that stride is a class above
# _BODY_STRIDE the way up ends at rows many times wider than the part's, so
# there a part first rides in a ready bucket of its OWN stride of up to this
# many times its rows, padded to it: the steps of the ramp from a quarter
# of its end on run the programs built for its end.
_PAD_MAX = 4
# A launch's first part (the one that holds its rows up to _BODY_STRIDE
# wide: the whole launch on a lane whose limit is no wider) is staged as two
# (its narrow rows, its wide rows) only where the one matrix fitted to its
# widest value is at least this many times the two parts' bytes, row buckets
# and meta columns counted as they are staged.
_SPLIT_MIN_SAVING = 2
# Above _BODY_STRIDE every class is a part of its own where that leaves at
# least this many staged bytes fewer than riding in the next part up: what
# a part's matrix, transfer, program and merge are worth. (Classes that
# double leave a ratio like _SPLIT_MIN_SAVING's at its edge launch after
# launch: a power-law tail puts about the same bytes in each.)
_PART_MIN_SAVING_BYTES = 1 << 20


def _class_strides(limit: int) -> np.ndarray:
    """The staged widths a lane whose values may be ``limit`` bytes wide
    chooses from, ascending, the last one ``limit`` itself."""
    body = min(limit, _BODY_STRIDE)
    out = [min(c * _STRIDE_CLASS, body) for c in range(1, -(-body // _STRIDE_CLASS) + 1)]
    while out[-1] < limit:
        out.append(min(out[-1] * 2, limit))
    return np.array(out, dtype=np.int64)


def _staged_bytes(rows: int, stride: int) -> int:
    return _bucket_rows(rows) * (stride + IN_META)


def _plan_cuts(hist: np.ndarray, strides: np.ndarray, split_ok: bool) -> list[int]:
    """The parts of a launch whose rows fall in the width classes as
    ``hist`` counts them: the class each part ends at (its stride is that
    class's), ascending, the last one the widest class that holds a row.

    Above _BODY_STRIDE: the cheapest grouping of the classes into parts,
    each part charged _PART_MIN_SAVING_BYTES over its matrix (the rows up to
    _BODY_STRIDE wide enter as one class at their own widest stride, and
    ride in the first part above where a part of their own would not
    pay). The first part, which holds those rows, is then one part at its
    stride, or two where that at least halves its matrix
    (_SPLIT_MIN_SAVING): the narrow stride up to _BODY_STRIDE that leaves
    the fewest staged bytes, and the rest at the part's own. Where no row
    is wider than _BODY_STRIDE that is all there is: PR 47's rule.
    ``split_ok`` False: one part, fitted."""
    widest = int(np.flatnonzero(hist)[-1])
    if not split_ok:
        return [widest]
    body = min(int(np.searchsorted(strides, _BODY_STRIDE)), len(strides) - 1)
    below = np.flatnonzero(hist[: body + 1])
    cuts = [widest]
    if widest > body:
        # the classes above the body, and the body's rows as one item at
        # their own widest class: cheapest[j] is what items[:j] cost staged
        # as parts, starts[j] where the last of those parts begins
        items = [(int(below[-1]), int(hist[: body + 1].sum()))] if len(below) else []
        items += [(c, int(hist[c])) for c in range(body + 1, widest + 1) if hist[c]]
        cheapest, starts = [0], [0]
        for j in range(1, len(items) + 1):
            stride = int(strides[items[j - 1][0]])
            cost, rows = None, 0
            for i in range(j - 1, -1, -1):
                rows += items[i][1]
                c = cheapest[i] + _staged_bytes(rows, stride) + _PART_MIN_SAVING_BYTES
                if cost is None or c < cost:
                    cost, start = c, i
            cheapest.append(cost)
            starts.append(start)
        cuts, j = [], len(items)
        while j:
            cuts.append(items[j - 1][0])
            j = starts[j]
        cuts.reverse()
    # the first part (it holds the rows up to _BODY_STRIDE, where the launch
    # has any): one stride, or two
    top = cuts[0]
    n, wide = int(hist[: top + 1].sum()), int(strides[top])
    best, narrow = None, 0
    for c in range(min(top, body + 1)):
        narrow += int(hist[c])
        if not narrow:
            continue
        staged = _staged_bytes(narrow, int(strides[c])) + _staged_bytes(n - narrow, wide)
        if best is None or staged < best[0]:
            best = (staged, c)
    if best is not None and best[0] * _SPLIT_MIN_SAVING <= _staged_bytes(n, wide):
        cuts.insert(0, best[1])
    return cuts


def _merge_parts(results: list[np.ndarray], rows: list[np.ndarray] | None):
    """A launch's result out of its parts' results (each on the host: a
    packed result matrix, or the bit-packed keep mask): part i holds the
    launch's rows ``rows[i]`` in its first rows. One indexed store a part
    puts them back in the launch's row order; from there on nothing knows
    of the split. ``rows`` None: one part, its result is the launch's."""
    if rows is None:
        return results[0]
    n = sum(len(r) for r in rows)
    if results[0].ndim == 1:
        keep = np.empty(n, dtype=np.uint8)
        for bits, r in zip(results, rows):
            keep[r] = np.unpackbits(bits)[: len(r)]
        return np.packbits(keep)
    out = np.empty((n, results[0].shape[1]), dtype=np.uint8)
    for packed, r in zip(results, rows):
        out[r] = packed[: len(r)]
    return out


class _Part:
    """One staging matrix of a payload launch and the program it runs over
    it: the whole launch at one stride, or the rows of one width class."""

    __slots__ = ("stride", "rows", "staged", "n_pad", "fn", "program", "bucket")

    def __init__(self, stride: int, rows: np.ndarray | None, fn):
        self.stride = stride  # the value part of a staged row, without IN_META
        self.rows = rows  # the launch's rows it holds, ascending; None: all
        self.fn = fn
        self.staged: np.ndarray | None = None
        self.n_pad = 0
        self.program = None
        self.bucket = 0


class _PartsResult:
    """The device results of a launch that is more than one program run:
    staged in several parts by width class, or cut to a ready row bucket, or
    both. ``parts[i]`` are part i's results in row order (one, or one a
    cut); fetched and merged as one (_Launch._fetch_legs)."""

    __slots__ = ("parts", "rows")

    def __init__(self, parts: list[list], rows: list[np.ndarray] | None):
        self.parts = parts
        self.rows = rows

    def arrays(self) -> list:
        return [dev for cuts in self.parts for dev in cuts]

    def landed(self) -> np.ndarray:
        return _merge_parts(
            [
                np.asarray(cuts[0]) if len(cuts) == 1
                else np.concatenate([np.asarray(dev) for dev in cuts])
                for cuts in self.parts
            ],
            self.rows,
        )


class _SpecPrograms:
    """One payload spec's pipelines by staged width (ops/pipeline.py caches
    them by ``r_in``): the lane's full ``row_stride`` from the deploy on,
    and every narrower stride a launch of the spec has shown. The engine
    keeps it by spec for its own life, so a later deploy of the spec finds
    the strides and starts their ladders beside the full-width one."""

    __slots__ = ("spec", "mask_only", "fns", "min_stride", "split_ok", "partitions", "_lock")

    def __init__(self, spec: TransformSpec, mask_only: bool, row_stride: int):
        self.spec = spec
        self.mask_only = mask_only
        self._lock = lockwatch.wrap(threading.Lock(), "_SpecPrograms._lock")
        fn, r_out = make_packed_pipeline(spec, row_stride, mask_only)
        self.fns: dict[int, tuple] = {row_stride: (fn, r_out)}
        # a projection's result row has a width of its own, which a staged
        # row must hold (ops/transforms._validated); any other mapper's
        # follows the staged row, so parts of two strides give rows of two
        # widths and cannot be merged: such a spec is fitted, never split
        # (the keep mask has no width)
        fixed = transform_out_width(spec, row_stride + _STRIDE_CLASS) == r_out
        self.min_stride = r_out if fixed else 1
        self.split_ok = mask_only or fixed
        # the most partitions a script of the spec reads (_ladder_top)
        self.partitions = 1

    def read_from(self, partitions: int) -> None:
        """A deploy of the spec over ``partitions`` partitions: the ladders
        are sized for the script that reads the most."""
        with self._lock:
            self.partitions = max(self.partitions, partitions)

    def at(self, stride: int) -> tuple:
        """(fn, r_out) at ``stride``."""
        with self._lock:
            got = self.fns.get(stride)
            if got is None:
                got = make_packed_pipeline(self.spec, stride, self.mask_only)
                # a new dict, not a new key: whoever walks the old one
                # (stats(), another script's launch) walks it whole
                self.fns = {**self.fns, stride: got}
        return got


class _Ladder:
    """One payload pipeline's device programs by row bucket, lowered and
    compiled ahead of need on the engine's builder thread
    (``rptpu-precompile``: one program at a time, what the lazy ladders'
    launches wanted before the lanes' own ladders, smallest first), through
    JAX's ahead-of-time path
    (``jit(f).lower(shape).compile()``: the persistent compilation cache
    serves it like any jit call). Keyed by the jitted function, which
    ops/pipeline.py caches by spec: K scripts of one spec share one ladder.

    A launch asks ``program_for(n_pad)``: the bucket's program when it is
    ready; while the ladder is still building, or for a launch over its
    top, the LARGEST ready bucket (the launch is cut to it, never compiled
    inline); with nothing ready yet it waits for the first bucket. A
    ladder whose build failed answers None and the script serves through
    the first-run path (_try_device_leg), as every script did before.

    ``lazy``: the ladder of a stride narrower than the lane's own, which a
    launch's values showed (TpuEngine._plan_parts). It holds the buckets
    launches have asked for and no other: a launch is staged that narrow
    only where ``has`` its bucket, and goes wider until then, so nothing
    waits on it, and a stream of two widths costs two programs, not two
    ladders of eleven (each a trace under the interpreter lock, beside the
    serving path: PR 47's cold runs)."""

    def __init__(self, fn, stride: int, top: int, lazy: bool = False, pad_max: int = 1):
        self.fn = fn
        self.stride = stride  # a staged row's bytes, IN_META included
        self.top = top
        self.lazy = lazy
        self.pad_max = pad_max  # _padded
        self.programs: dict[int, object] = {}  # n_pad -> jax.stages.Compiled
        self.seconds: dict[int, float] = {}  # n_pad -> its build seconds
        self.failed: str | None = None
        self.stopped = False
        # the buckets launches went without (staged wider meanwhile): what
        # a lazy ladder builds; and the ONE bucket the newest launches
        # asked for, one after another, with the time of the first of them
        self.wanted: set[int] = set()
        self.asked: dict[int, float] = {}
        self.cond = threading.Condition()

    def buckets(self) -> list[int]:
        out, b = [], 128
        while b <= self.top:
            out.append(b)
            b *= 2
        return out

    def ready(self) -> list[int]:
        with self.cond:
            return sorted(self.programs)

    def _padded(self, n_pad: int) -> int | None:
        """The smallest ready bucket a launch padded to ``n_pad`` rows runs
        whole: its own, or one up to ``pad_max`` times it (a step of the
        launch knob's ramp rides in the program built ahead for the ramp's
        end, and pays that many pad rows for it). Caller holds ``cond``."""
        return min(
            (b for b in self.programs if n_pad <= b <= n_pad * self.pad_max), default=None
        )

    def program_for(self, n_pad: int, wait_s: float):
        """(program, its row bucket) for a launch padded to ``n_pad`` rows:
        a bucket above ``n_pad`` means the launch is to be padded to it, a
        bucket below that it is to be cut to it. (None, n_pad): no ladder
        to serve from, take the first-run path."""
        deadline = time.monotonic() + wait_s
        with self.cond:
            while True:
                b = self._padded(n_pad)
                if b is not None:
                    return self.programs[b], b
                if self.failed is not None or self.stopped:
                    return None, n_pad
                below = [b for b in self.programs if b < n_pad]
                if below:
                    b = max(below)
                    return self.programs[b], b
                left = deadline - time.monotonic()
                if left <= 0:
                    return None, n_pad
                self.cond.wait(timeout=left)

    def has(self, n_pad: int, want: bool = True, hold_s: float = 0.0) -> bool:
        """Whether a launch padded to ``n_pad`` rows runs a built program
        whole (over the top: cut to the top's; in a ready bucket of up to
        ``pad_max`` times its own: padded to it). ``want``: its own bucket,
        where it is not there, is left for the builder, once launches have
        asked for it and no other over ``hold_s`` seconds."""
        with self.cond:
            n_pad = min(n_pad, self.top)
            if n_pad in self.programs:
                return True
            if want and n_pad not in self.wanted:
                now = time.monotonic()
                if hold_s and n_pad not in self.asked:
                    self.asked.clear()
                    self.asked[n_pad] = now
                if not hold_s or now - self.asked[n_pad] >= hold_s:
                    self.wanted.add(n_pad)
            return self._padded(n_pad) is not None

    def missing(self) -> list[int]:
        """The buckets still to build, smallest first: every bucket up to
        the top, or, ``lazy``, the wanted ones. Caller holds ``cond``."""
        want = self.wanted if self.lazy else self.buckets()
        return sorted(b for b in want if b not in self.programs)

    def next_bucket(self) -> int | None:
        """The bucket to build next; None when the ladder stands (or will
        never)."""
        with self.cond:
            if self.failed is not None or self.stopped:
                return None
            return next(iter(self.missing()), None)

    def wait_first(self, n: int, wait_s: float) -> bool:
        """Block until the ``n`` smallest buckets are ready (or the build
        has ended one way or the other)."""
        want = self.buckets()[:n]
        deadline = time.monotonic() + wait_s
        with self.cond:
            while not (self.failed or self.stopped or all(b in self.programs for b in want)):
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self.cond.wait(timeout=left)
        return True


# serializes _mask_state transitions between the harvester and timed-out
# callers claiming their still-queued mask (transitions are rare and O(1);
# one process-wide lock is plenty)
_mask_claim_lock = threading.Lock()


class _MaskSlot:
    """One mesh shard's predicate mask (host-evaluated, or its device's
    block of the SPMD result, fetched at harvest).

    Field names deliberately mirror _Launch's mask fields
    (``_mask_dev``/``_mask_np``/``_mask_event``/``trace_id``):
    _resolve_keep serves either shape without caring which it got. A
    slot never rides the harvester, so its ``_mask_event`` stays None.
    """

    __slots__ = ("n", "_mask_dev", "_mask_np", "_mask_event",
                 "trace_id", "_cols")

    def __init__(self, n: int):
        self.n = n
        self._mask_dev = None
        self._mask_np = None
        self._mask_event = None
        self.trace_id: int | None = None
        # extracted predicate columns, retained while a device mask is in
        # flight: the exact numpy fallback re-evaluates over these if the
        # D2H fetch dies (faults.MASK_FETCH domain)
        self._cols = None


class _HostShard:
    """One contiguous record-range shard of a launch's host stages.

    Everything here is produced by exactly one pool worker and read only
    after the fan-in barrier (pool.run returns) — shard workers never
    touch each other's state (pandalint SHD6xx enforces the discipline).
    """

    __slots__ = ("n", "ranges", "exploded", "proj_data", "proj_ok",
                 "mask", "stages")

    def __init__(self):
        self.n = 0
        self.ranges: list[tuple[int, int]] = []
        self.exploded = None
        self.proj_data = None
        self.proj_ok = None
        self.mask: _MaskSlot | None = None
        self.stages: dict[str, float] = {}


class _Launch:
    """One device launch for one script, possibly spanning many requests.

    ``materialize()`` yields (out_rows, out_len, keep) host arrays with one
    row per input record; mode decides where they come from:

    - payload: the fetched packed device result (full transformed rows);
      a filter-only plan fetches only the keep mask and never materializes
      (``framed()`` gathers kept values from the retained exploded table).
    - columnar: keep = device mask bits & host projection-ok; rows are
      host-assembled projection columns (or packed input values for
      passthrough specs).
    - host: computed synchronously from the exploded inputs at harvest.

    When the mesh lane ran the launch (``_shards`` set, one per device),
    the columnar harvest side assembles and frames per shard instead of
    launch-wide; the framed list is the in-order concatenation of the
    shards' framed lists, byte-identical to the single-device path.
    """

    __slots__ = ("script_id", "policy", "mode", "r_out", "ranges", "fits",
                 "engine", "n", "_packed_dev", "_mask_dev", "_mask_np",
                 "_mask_event", "_proj_data", "_proj_ok", "_plan",
                 "_exploded", "_mat", "_gather_mat", "_framed", "_lock",
                 "_shards", "trace_id", "_enq_t", "_cols", "_staged_parts",
                 "_staged_dev", "_fetch_span", "_mask_state")

    def __init__(self, script_id: int, policy: ErrorPolicy):
        self.script_id = script_id
        self.policy = policy
        self.trace_id: int | None = None
        self._enq_t = 0.0
        self.mode = "payload"
        self.r_out = 0
        self.ranges: list[tuple[int, int]] = []
        self.fits: np.ndarray | None = None
        self.engine = None
        self.n = 0
        self._packed_dev = None
        self._mask_dev = None
        self._mask_np = None
        self._mask_event: threading.Event | None = None
        self._proj_data = None
        self._proj_ok = None
        self._plan = None
        self._exploded = None
        self._mat = None
        self._gather_mat = None
        self._framed = None
        self._lock = lockwatch.wrap(threading.Lock(), "_Launch._lock")
        self._shards: list[_HostShard] | None = None
        # fault-domain fallbacks: predicate columns / staged payload rows
        # retained until their device result lands, so an exhausted device
        # retry can re-execute the stage host-side with exact output
        self._cols = None
        # claim protocol (guarded by _mask_claim_lock): "idle" -> "queued"
        # on enqueue; the harvester CASes queued -> "harvesting" on
        # dequeue; a caller that timed out while its mask was still QUEUED
        # (harvester busy on an earlier wedged mask) CASes queued ->
        # "claimed" and fetches itself. The harvester skips a claimed mask
        # without a fetch or a breaker verdict — one mask, one envelope,
        # one verdict, no matter how deep the harvest queue is.
        self._mask_state = "idle"
        # a payload launch's staging matrices (_Part: one, or one a width
        # class the launch is staged in), retained until the device result lands
        self._staged_parts: list[_Part] | None = None
        # the staged matrices ON the device, kept beside the result only so
        # that the fetch can time the H2D apart (_fetch_legs drops them)
        self._staged_dev = None
        # ring id of this launch's coproc.stage.fetch span, minted ahead of
        # it: the link-wait legs name it as their parent, and on the mask
        # road they begin (on the harvester's worker) before the fetch does
        self._fetch_span = tracer.new_span_id() if tracer.enabled else None

    def _fetch_legs(self, dev, own: bool = True) -> np.ndarray:
        """The link wait for one device result, in dependency order with a
        clock read between: the staged input is on the device (H2D), the
        result is defined (the program has run), the result is on the host
        (D2H: its copy was issued at dispatch). Three stages, ``t_wait_h2d``
        / ``t_wait_program`` / ``t_wait_d2h``, children of the launch's
        ``coproc.stage.fetch`` in the ring. Each call returns at once when
        its array is already there, so together they wait what
        ``np.asarray(dev)`` alone would. Runs inside the fault envelope's
        leg, on its worker: on the matrix road that is the thread
        ``t_fetch`` waits on (``t_fetch`` = the three + the envelope's own
        hop); on the mask road it is the harvester's fetch worker, which
        starts at the enqueue, so the caller's ``t_fetch`` is at most
        their sum. A host fallback runs none of this and records no leg.
        ``own``: the result is the launch's own (a mesh shard's mask has a
        fetch span of its own, which no id was minted for)."""
        import jax

        parent = self._fetch_span if own else None
        staged_dev, self._staged_dev = self._staged_dev, None
        if staged_dev is not None:
            t0 = _stage_t0("t_wait_h2d")
            jax.block_until_ready(staged_dev)
            # dropped before the next wait: the device's copy of the input
            # lives no longer than the program needs it, as before
            staged_dev = None
            self._stat("t_wait_h2d", t0, parent=parent)
        t0 = _stage_t0("t_wait_program")
        several = isinstance(dev, _PartsResult)
        # host bits (a bare launch in tests) pass through
        jax.block_until_ready(dev.arrays() if several else dev)
        self._stat("t_wait_program", t0, parent=parent)
        t0 = _stage_t0("t_wait_d2h")
        # a launch staged in parts or cut: its parts' results, back in the
        # launch's row order, are the launch's result
        out = dev.landed() if several else np.asarray(dev)
        self._stat("t_wait_d2h", t0, parent=parent)
        return out

    def _mat_payload(self):
        if self._packed_dev is None:  # zero-record launch
            return (
                np.zeros((0, self.r_out), np.uint8),
                np.zeros(0, np.int32),
                np.zeros(0, bool),
            )
        t0 = _stage_t0("t_fetch")
        dev = self._packed_dev
        eng = self.engine
        if isinstance(dev, np.ndarray) or eng is None:
            # host-fallback result (already materialized) / bare test launch
            packed = np.asarray(dev)
        elif not eng.governor.breaker_for(faults.HARVEST).allow_device():
            # open harvest breaker: fetches are demoted straight to the
            # exact host fallback without spending a retry envelope
            packed = self._payload_host_fallback()
        else:
            def leg():
                faults.inject(faults.HARVEST)
                return self._fetch_legs(dev)

            packed = eng._try_device_leg(faults.HARVEST, leg)
            if packed is None:
                packed = self._payload_host_fallback()
            else:
                eng.governor.breaker_for(faults.HARVEST).record_success()
        self._stat("t_fetch", t0, span_id=self._fetch_span)
        self._packed_dev = None
        # unpack: the fetched matrix split into rows, lengths and keep bits
        # (strided column copies over every row: 1.5 ms of a NEXmark launch
        # on the chip's host, PR 52), the staging matrices parked
        t0 = _stage_t0("t_unpack")
        self._park_staged()
        out, out_len, keep = unpack_result(packed, self.r_out)
        n = len(self.fits)
        if eng is not None and getattr(self._plan, "structural", False):
            # a structural program says why it dropped a row, beside the
            # keep column; an oversize row (staged empty) is n_oversize_rows'
            why = np.bincount(unpack_reason(packed, self.r_out)[:n][self.fits], minlength=3)
            eng._stat_add("n_json_rows", float(n))
            eng._stat_add("n_json_malformed_rows", float(why[JSON_MALFORMED]))
            eng._stat_add("n_json_path_miss_rows", float(why[JSON_PATH_MISS]))
        kept = keep[:n] & self.fits
        self._stat("t_unpack", t0)
        return out[:n], out_len[:n], kept

    def _payload_host_fallback(self) -> np.ndarray:
        """Fail closed per-launch: re-run the packed pipeline in numpy over
        the retained staged rows — the same integer program over the same
        bytes (ops/pipeline.make_packed_pipeline_host), in the launch's own
        result format (matrix, or mask bits), so output is exact
        and no JAX backend is needed: a process started with
        JAX_PLATFORMS=tpu has no CPU backend to fall back to. Raises when
        nothing was retained (the launch then follows ErrorPolicy, exactly
        like any unrecoverable script failure)."""
        parts = self._staged_parts
        eng = self.engine
        if parts is None or eng is None:
            raise RuntimeError(
                "payload host fallback impossible: staged rows not retained"
            )
        plan = self._plan
        mask_result = eng._mask_result(plan)
        # each part at its own stride, merged as the device's parts are
        packed = _merge_parts(
            [
                make_packed_pipeline_host(plan.spec, part.stride, mask_result)(
                    part.staged
                )
                for part in parts
            ],
            [part.rows for part in parts] if len(parts) > 1 else None,
        )
        # the device leg did not end in a landed result, so a transfer may
        # still be reading the matrices: dropped here, never parked
        # (_launch_payload has the rule)
        self._staged_parts = None  # pandalint: disable=RAC1101 -- the unlocked caller is _dispatch_payload, which runs BEFORE the launch is published to tickets (thread-local construction phase); every harvest-time caller reaches here under _Launch._lock via _materialize_locked / _gather_view
        eng._count_fallback(self.n)
        return packed

    def _park_staged(self) -> None:
        """The launch's device result has landed, so the programs have
        consumed their input: the staging matrices go back to the engine's
        pool. After a host fallback there is none left to give
        (_launch_payload has the rule)."""
        parts, self._staged_parts = self._staged_parts, None
        if parts is not None and self.engine is not None:
            for part in parts:
                self.engine._staging.release(part.staged.base)

    def _resolve_keep(self, slot, n: int) -> np.ndarray:
        """Resolve a keep mask from a mask holder — the launch itself or a
        per-shard _MaskSlot (same field shape by design): no predicate,
        host-evaluated bits, or device fetch via the async-harvest event.
        The D2H discipline is subtle, so exactly ONE copy of it exists."""
        if slot._mask_dev is None and slot._mask_np is None:
            return np.ones(n, dtype=bool)  # no predicate: keep all present
        if slot._mask_dev is None:
            # host-evaluated mask (columnar_host ablation): already on host
            keep = np.unpackbits(slot._mask_np)[:n].astype(bool)
            slot._mask_np = None
            return keep
        t0 = _stage_t0("t_fetch")
        eng = self.engine
        # wait out the harvester's WHOLE retry envelope, not one attempt's
        # deadline: timing out mid-envelope would start a duplicate
        # concurrent fetch of the same array and double-count the failure.
        # Sized off the governor's envelope BOUND (the max deadline ever
        # issued, = the static envelope until an adaptive raise happens),
        # and RE-READ before the second wait below: the harvester derives
        # its own deadline concurrently, and it publishes any raise into
        # the bound before fetching, so the re-reading waiter can never
        # end up shorter than the fetch it is waiting on
        wait_s = (
            eng.governor.envelope_bound_s(faults.HARVEST) + 1.0
            if eng is not None
            else 30.0
        )
        if slot._mask_event is not None:
            # harvester thread pays the link round trip concurrently
            # with the caller's host work; worst case we fetch ourselves.
            # Keep OUR fetch in a local — the harvester may still write
            # _mask_np (even None, on its own failure) after a timeout.
            finished = slot._mask_event.wait(timeout=wait_s)
            bits = slot._mask_np
            if bits is None:
                if finished:
                    # the harvester ran the FULL retry envelope on this
                    # mask and definitively failed (its breaker verdict is
                    # already recorded): re-running the same doomed fetch
                    # here would double-count the failure and double the
                    # dead-link wait — go straight to the exact fallback
                    bits = self._mask_host_fallback(slot)
                else:
                    # mask still QUEUED? The single harvester is busy on
                    # earlier (wedged) masks. Claim it — the harvester
                    # will skip the claimed slot, so this stays ONE fetch
                    # envelope and ONE breaker verdict per mask at any
                    # queue depth.
                    with _mask_claim_lock:
                        claimed = slot._mask_state == "queued"
                        if claimed:
                            slot._mask_state = "claimed"
                    if claimed:
                        bits = self._fetch_mask_bits(slot)
                    else:
                        # the harvester is ACTIVELY harvesting this mask:
                        # one more envelope bounds its verdict. Re-read
                        # the bound — the harvester published any adaptive
                        # raise into it before starting its fetch
                        if eng is not None:
                            wait_s = (
                                eng.governor.envelope_bound_s(faults.HARVEST)
                                + 1.0
                            )
                        finished = slot._mask_event.wait(timeout=wait_s)
                        bits = slot._mask_np
                        if bits is None:
                            # verdict recorded -> exact fallback; still
                            # nothing -> the thread itself is stuck, pay
                            # the fetch ourselves (genuinely exceptional)
                            bits = (
                                self._mask_host_fallback(slot)
                                if finished
                                else self._fetch_mask_bits(slot)
                            )
        else:
            bits = self._fetch_mask_bits(slot)
        self._stat("t_fetch", t0, span_id=self._fetch_span if slot is self else None)
        slot._mask_dev = None
        slot._mask_np = None
        slot._cols = None
        return np.unpackbits(bits)[:n].astype(bool)

    def _fetch_mask_bits(self, slot) -> np.ndarray:
        """Deadline-bounded, retried D2H mask fetch with the EXACT numpy
        fallback: on exhausted retries the predicate re-evaluates over the
        retained extracted columns (faults.MASK_FETCH domain), so a dead
        link changes where the bits come from, never what they are."""
        eng = self.engine
        dev = slot._mask_dev
        if eng is None:  # bare launch in tests: old synchronous behavior
            return np.asarray(dev)
        fetch_breaker = eng.governor.breaker_for(faults.MASK_FETCH)
        if not fetch_breaker.allow_device():
            # open mask-fetch breaker: this domain is demoted — go straight
            # to the exact numpy fallback over the retained columns instead
            # of burning a full retry envelope on a known-dead D2H path.
            # Dispatch keeps its own breaker; launches stay on-device.
            return self._mask_host_fallback(slot)

        def leg():
            faults.inject(faults.MASK_FETCH)
            return self._fetch_legs(dev, own=slot is self)

        bits = eng._try_device_leg(faults.MASK_FETCH, leg)
        if bits is None:
            bits = self._mask_host_fallback(slot)
        else:
            fetch_breaker.record_success()
        return bits

    def _mask_host_fallback(self, slot) -> np.ndarray:
        """Exact numpy re-evaluation of the predicate over the retained
        extracted columns (same expression tree, same column bytes) — or,
        for a payload launch's mask, over its retained staged rows.
        Raises when nothing was retained — the launch then follows the
        script's ErrorPolicy like any unrecoverable failure."""
        if self.mode == "payload":
            return self._payload_host_fallback()
        cols = slot._cols
        if cols is None:
            raise RuntimeError(
                "mask host fallback impossible: predicate columns not retained"
            )
        bits = self._plan.eval_host_mask(cols)
        self.engine._count_fallback(slot.n)
        return bits

    def _mat_columnar(self):
        n = self.n
        if n == 0:
            return (
                np.zeros((0, max(self.r_out, 1)), np.uint8),
                np.zeros(0, np.int32),
                np.zeros(0, bool),
            )
        keep = self._resolve_keep(self, n)
        keep &= self._proj_ok
        t0 = _stage_t0("t_assemble")
        plan: ColumnarPlan = self._plan
        if plan.passthrough:
            # Output = input value bytes of kept records (empty values are
            # legal and kept when the predicate says so — host_eval is the
            # normative semantics, unlike v1's drop-empty payload rule).
            ex = self._exploded
            stride = max(int(ex.sizes.max()) if n else 1, 1)
            rows, lens = _pack_values(ex, stride)
        else:
            rows, lens = plan.assemble_rows(self._proj_data, n)
        self._stat("t_assemble", t0)
        self._proj_data = None
        self._exploded = None
        return rows, lens, keep

    def _mat_host(self):
        plan: HostPlan = self._plan
        ex = self._exploded
        n = self.n
        if n == 0:
            return (
                np.zeros((0, 1), np.uint8),
                np.zeros(0, np.int32),
                np.zeros(0, bool),
            )
        t0 = _stage_t0("t_assemble")
        if plan.kind == "python":
            outs = []
            for i in range(n):
                o = int(ex.offsets[i])
                val = ex.joined[o : o + int(ex.sizes[i])]
                try:
                    outs.append(plan.fn(val))
                except Exception as exc:
                    if self.policy == ErrorPolicy.deregister:
                        # propagate: Ticket._result_impl applies the policy
                        # and unloads the script (wasm_event.h Deregister)
                        raise
                    # user-code boundary: a script TypeError is a script
                    # failure, not an engine bug — never re-raise, but
                    # count it (skip_on_failure drops silently otherwise)
                    faults.note_failure("host_plan", exc)
                    outs.append(None)
            keep = np.array([o is not None for o in outs], dtype=bool)
            stride = max((len(o) for o in outs if o is not None), default=1)
            stride = max(stride, 1)
            rows = np.zeros((n, stride), dtype=np.uint8)
            lens = np.zeros(n, dtype=np.int32)
            for i, o in enumerate(outs):
                if o is not None:
                    rows[i, : len(o)] = np.frombuffer(o, np.uint8)
                    lens[i] = len(o)
        else:
            stride = max(int(ex.sizes.max()), 1)
            rows, lens = _pack_values(ex, stride)
            keep = ex.sizes > 0
            if plan.kind == "uppercase":
                is_lower = (rows >= ord("a")) & (rows <= ord("z"))
                rows = np.where(is_lower, rows - 32, rows)
        self._stat("t_assemble", t0)
        self._exploded = None
        return rows, lens, keep

    def framed(self) -> list[tuple[bytes, int]]:
        """Per-range (payload, kept), framed launch-wide in ONE native
        crossing the first time any ticket rebuilds. Locked: tickets of one
        submit_group share this launch and may harvest from different
        threads (the pacemaker harvests via run_in_executor).

        Byte-identity transforms take the ZERO-COPY gather path: kept
        records frame straight from the joined blob (or, on the payload
        lane's pointer-table road, from the per-batch payload buffers) via
        the (offset, len) columns the explode stage already produced — the
        padded row matrix the padded path packs (or fetches) just to copy
        from never exists. Output is bit-identical either way (the gather
        parity suite pins it)."""
        with self._lock:
            if self._framed is None:
                if self._shards is not None:
                    self._framed = self._framed_sharded()
                else:
                    gv = self._gather_view()
                    arena = self.engine._arena if self.engine is not None else None
                    if gv is not None:
                        ex, keep = gv
                        t0 = _stage_t0("t_frame_gather")
                        self._framed = batch_codec.frame_exploded_gather(
                            ex, keep, self.ranges, arena=arena
                        )
                        self._stat("t_frame_gather", t0)
                        self._count_frame("n_frame_gather", ex.sizes, keep)
                        _release_exploded(ex)
                        self._exploded = None
                        self._gather_mat = None
                    else:
                        out, out_len, keep = self._materialize_locked()
                        t0 = _stage_t0("t_rebuild")
                        self._framed = batch_codec.frame_ranges(
                            out, out_len, keep, self.ranges, arena=arena
                        )
                        self._stat("t_rebuild", t0)
                        self._count_frame("n_frame_padded", out_len, keep)
            return self._framed

    def _gather_view(self):
        """(exploded, keep) when this launch's output bytes are an
        (offset, len) view into bytes the host holds — byte-identity plans
        (columnar passthrough, host identity, filter-only payload) with
        the exploded table still in hand, which a payload launch retains
        only when its device result is the keep mask; None sends the
        launch down the padded path.

        The resolved view is CACHED (like _materialize_locked's _mat):
        _resolve_keep consumes the mask slot, so an uncached re-entry
        after a framing failure would read an empty slot as "no
        predicate" and silently emit keep-all output on retry."""
        if self._gather_mat is not None:
            return self._gather_mat
        eng = self.engine
        if eng is None or not eng._gather_frame:
            return None
        plan = self._plan
        if plan is None or not getattr(plan, "byte_identity", False):
            return None
        ex = self._exploded
        if ex is None:
            return None
        if self.mode == "columnar":
            keep = self._resolve_keep(self, self.n) & self._proj_ok
        elif self.mode == "host":
            # identity's normative keep rule: drop empty values (matches
            # _mat_host's `ex.sizes > 0`)
            keep = ex.sizes > 0
        else:
            # payload mask launch: empty and null values are dropped by
            # the device's keep (lengths > 0), oversize ones by fits
            keep = self._resolve_keep(self, self.n) & self.fits
            self._park_staged()
        self._gather_mat = (ex, keep)
        return self._gather_mat

    def _count_frame(self, key: str, lens: np.ndarray, keep: np.ndarray) -> None:
        """One framing crossing (launch- or shard-level) on either road:
        which road, the rows it kept and the value bytes it framed
        (``lens`` of the kept rows: 70 B a row of config 4's projection,
        the value itself for a filter). Three adds a crossing, nothing
        per record."""
        eng = self.engine
        if eng is None:
            return
        eng._stat_add(key, 1.0)
        eng._stat_add("n_kept_rows", float(np.count_nonzero(keep)))
        eng._stat_add("bytes_out", float(lens[keep].sum()))
        # decision-plane bookkeeping: which framing path this launch took.
        # record_mode journals only on CHANGE (first engagement or a mode
        # flip); the steady-state cost is one lock + one compare per launch
        mode = "gather" if key == "n_frame_gather" else "padded"
        eng.governor.record_mode(
            governor.HARVEST_PATH,
            mode,
            "byte-identity plan framed zero-copy from the bytes the host holds"
            if mode == "gather"
            else "byte-mutating plan framed via the padded row matrix",
            {"script_id": self.script_id, "mode": self.mode},
            # dedupe per SCRIPT: the framing path is a property of the
            # script's plan, and a mixed gather+padded workload must not
            # flip-flop the journal on every alternating launch
            key=self.script_id,
        )

    def _shard_keep(self, shard: _HostShard) -> np.ndarray:
        """Resolve one shard's keep mask via the shared _resolve_keep."""
        if shard.n == 0:
            return np.zeros(0, dtype=bool)
        if shard.mask is None:
            return np.ones(shard.n, dtype=bool) & shard.proj_ok
        return self._resolve_keep(shard.mask, shard.n) & shard.proj_ok

    def _frame_shard(self, shard: _HostShard, keep: np.ndarray):
        """Assemble + frame ONE shard's record range (pool worker body —
        touches only its own shard, see SHD6xx). Byte-identity plans
        gather-frame straight from the shard's exploded table (same
        zero-copy rule as the inline path)."""
        plan: ColumnarPlan = self._plan
        eng = self.engine
        arena = eng._arena if eng is not None else None
        ex = shard.exploded
        if (
            eng is not None
            and eng._gather_frame
            and getattr(plan, "byte_identity", False)
            and ex is not None
            and shard.n > 0
        ):
            t0 = _stage_t0("t_shard_frame_gather")
            framed = batch_codec.frame_ranges_gather(
                ex.joined, ex.offsets, ex.sizes, keep, shard.ranges,
                arena=arena,
            )
            self._stat("t_shard_frame_gather", t0)
            self._count_frame("n_frame_gather", ex.sizes, keep)
            return framed
        t0 = _stage_t0("t_shard_assemble")
        if shard.n == 0:
            rows = np.zeros((0, max(self.r_out, 1)), np.uint8)
            lens = np.zeros(0, np.int32)
        elif plan.passthrough:
            stride = max(int(ex.sizes.max()), 1)
            rows, lens = _pack_values(ex, stride)
        else:
            rows, lens = plan.assemble_rows(shard.proj_data, shard.n)
        # t_shard_* keys: concurrent per-shard CPU-seconds, kept apart from
        # the launch-wall t_assemble/t_rebuild of the inline path (the
        # fan-out's wall time is t_sharded_frame)
        self._stat("t_shard_assemble", t0)
        t0 = _stage_t0("t_shard_rebuild")
        framed = batch_codec.frame_ranges(
            rows, lens, keep, shard.ranges, arena=arena
        )
        self._stat("t_shard_rebuild", t0)
        self._count_frame("n_frame_padded", lens, keep)
        return framed

    def _framed_sharded(self) -> list[tuple[bytes, int]]:
        """Per-shard harvest of a mesh launch: masks resolved in shard
        order, then assembly + framing fan out over the host pool; the
        concatenated framed lists are byte-identical to the launch-wide
        path because shards are contiguous record ranges in input order."""
        shards = self._shards
        keeps = [self._shard_keep(shard) for shard in shards]
        thunks = [
            (lambda s=shard, k=keep: self._frame_shard(s, k))
            for shard, keep in zip(shards, keeps)
        ]
        pool = self.engine._host_pool if self.engine is not None else None
        t0 = _stage_t0("t_sharded_frame")
        parts = pool.run(thunks) if pool is not None else [t() for t in thunks]
        self._stat("t_sharded_frame", t0)
        for shard in shards:
            shard.proj_data = None
            shard.exploded = None
        return [item for part in parts for item in part]

    def _materialize_locked(self):
        """(out, out_len, keep) host arrays; fetch happens at most once.
        Caller holds self._lock."""
        if self._mat is None:
            if self.mode == "payload":
                self._mat = self._mat_payload()
            elif self.mode == "columnar":
                self._mat = self._mat_columnar()
            else:
                self._mat = self._mat_host()
        return self._mat

    def _stat(self, key: str, t0: float, **ring):
        # harvest-side stage (fetch/assemble/frame/seal): runs on whatever
        # thread materializes, so the launch's explicit trace id carries
        # the pulse slice (no ambient there); _stat_stage owns the single
        # clock read + stat/probe/timeline fan-out. ``ring``: the span's
        # own id or its parent's, where the ambient span cannot say
        # (stages.close)
        if self.engine is not None:
            self.engine._stat_stage(key, t0, trace_id=self.trace_id, **ring)
        else:
            _stage_closed(stages.close(
                "coproc.stage." + key[2:], None, t0, trace_id=self.trace_id, **ring
            ))


def _pack_values(ex, stride: int):
    """Pack exploded record values into [n, stride] rows + lens."""
    try:
        from redpanda_tpu.native import lib
    except Exception as exc:
        # expected degradation: no native build on this box — the Python
        # packer is exact, only slower; counted so the demotion is visible
        faults.note_failure("native_lib", exc)
        lib = None
    sizes = np.minimum(ex.sizes, stride).astype(np.int32)
    if lib is not None:
        rows, _ = lib.pack_rows(ex.joined, ex.offsets, sizes, stride)
    else:
        from redpanda_tpu.ops.packing import pack_rows

        vals = [
            ex.joined[o : o + s] for o, s in zip(ex.offsets, sizes)
        ]
        rows, _ = pack_rows(vals, stride)
    return rows, sizes


def _fit_cols(cols, n_pad: int) -> list:
    """Pad/trim host predicate columns to a row bucket. Rows beyond the
    shard's real record count are padding whose predicate bits are
    discarded ([:n] at unpack), so zero-fill is always safe."""
    out = []
    for a in cols:
        if len(a) == n_pad:
            out.append(a)
        elif len(a) > n_pad:
            out.append(a[:n_pad])
        else:
            pad = np.zeros((n_pad - len(a),) + a.shape[1:], dtype=a.dtype)
            out.append(np.concatenate([a, pad]))
    return out


# Per-slot dispositions inside a Ticket.
_UNKNOWN, _EMPTY, _DEREGISTERED, _LAUNCHED = range(4)

# "resolve the trace id from the ambient contextvar" sentinel for
# _stat_stage (None is a real value there: "caller had no trace").
_AMBIENT = stages.AMBIENT


class _SelfTime(threading.local):
    """One thread's books for the self time of the engine call it runs
    (``TpuEngine.submit``, ``Ticket.result``): how deep the open ``t_*``
    stages nest on it, and the seconds of those closed at the top level
    since the call began. A call's self time is its duration less
    ``covered``: a stage closed inside another counts once (``t_h2d`` inside
    ``t_dispatch``), and one that ran on another thread (the fault
    envelope's worker, the harvester, a mesh shard) is in that thread's
    books, which nobody reads. Two additions a stage; no ring."""

    depth = 0
    covered = 0.0


_self_time = _SelfTime()


def _call_t0() -> float:
    """Begin an engine call on this thread: its clock read, with the
    thread's self-time books opened afresh (a stage an earlier call left
    open on an exception must not nest this one's)."""
    _self_time.depth = 0
    _self_time.covered = 0.0
    return time.perf_counter()


def _stage_t0(key: str) -> float:
    """Begin the engine stage whose stat key is ``t_<stage>``: the ``t0``
    that ``_stat_stage`` / ``_Launch._stat`` closes."""
    _self_time.depth += 1
    return stages.begin("coproc.stage." + key[2:])


def _stage_closed(dt: float) -> None:
    """The other half of ``_stage_t0``'s bookkeeping, on the closing thread."""
    acct = _self_time
    acct.depth -= 1
    if acct.depth <= 0:
        acct.depth = 0
        acct.covered += dt


# Columnar backend probe: don't pin the process-wide device-vs-host choice
# on a batch too small to represent steady state, and bound the device leg
# (it covers the predicate's first compile; a wedged device hangs forever).
_PROBE_MIN_ROWS = 1024
_PROBE_DEVICE_TIMEOUT_S = 120.0
# Per-attempt deadline of a dispatch leg whose program this engine has not
# run yet: the first call traces and compiles (seconds on an accelerator),
# which the steady-state deadline was never sized for — under it a cold
# compile reads as a wedged device, retries, and falls back to the host.
# Kept below the pacemaker's tick backstop (4x the default envelope).
_COMPILE_DEADLINE_S = 300.0
# The probe times only the synchronous predicate leg; the device path
# additionally pays per-launch costs the probe cannot see (async harvester
# handoff + GIL contention between the fetch thread and host assembly,
# dispatch bookkeeping). Bench measurement: with the probe leg favoring
# the device 3.2x, END-TO-END host columnar still won 1.5x — an unmeasured
# overhead factor of ~5. The device must therefore beat the host leg by
# this margin to be picked (one observation on XLA's CPU backend; not
# re-measured on a local chip — ROADMAP C3).
_PROBE_DEVICE_MARGIN = 4.0


class Ticket:
    """Handle for an in-flight engine request; ``result()`` materializes it."""

    def __init__(self, engine: "TpuEngine"):
        self._engine = engine
        self.trace_id: int | None = None
        # (disposition, item, launch, [batch range indices])
        self._slots: list[tuple] = []
        # bytes reserved from the coproc memory account at submit (0 when
        # admission is off); released exactly once when result() returns
        # OR raises — leaking them would starve every later submit
        self._admitted: int = 0
        # the calling thread's clock as the engine call that last ran on
        # this ticket (``TpuEngine.submit``, then ``result``) started and as
        # it ended: the pacemaker hands both calls to an executor thread
        # and splits its hand-off legs on these
        self.worker_clock = (0.0, 0.0)

    def result(self) -> ProcessBatchReply:
        t_run = _call_t0()
        try:
            # a stage: on a profile the executor thread's line shows it
            # beside the loop's rp:coproc.harvest.wait
            with stages.stage("coproc.harvest", trace_id=self.trace_id):
                return self._result_impl()
        finally:
            self._engine._release_admission(self)
            self.worker_clock = (t_run, self._engine._call_done("t_harvest", t_run))  # pandalint: disable=RAC1101 -- a ticket is harvested by one call; its reader is the fiber that awaited that call's executor future (the future's completion is the hand-off)

    def _result_impl(self) -> ProcessBatchReply:
        reply = ProcessBatchReply()
        dereg: set[int] = set()
        failed_scripts: set[int] = set()
        # Phase 1: frame every launch and collect the recompress+seal jobs
        # REPLY-WIDE, sealed in one pass below — the harvest-side analogue
        # of submit_group's launch fusion. Jobs are independent
        # (build_output_batch is pure per batch) and land in input order.
        seal_jobs: list[tuple] = []  # (source batch, payload, kept)
        slot_plans: list = []  # per slot: list[int] | Exception | None
        framing_failed: set[int] = set()
        for disp, item, launch, rng in self._slots:
            if disp != _LAUNCHED or launch.script_id in framing_failed:
                # a later slot of a script whose framing already failed is
                # resolved by phase 2's failed_scripts bookkeeping (the
                # failing slot precedes it in slot order)
                slot_plans.append(None)
                continue
            try:
                framed = launch.framed()  # one crossing per launch
                idxs = []
                for batch, ridx in zip(item.batches, rng):
                    payload, kept = framed[ridx]
                    idxs.append(len(seal_jobs))
                    seal_jobs.append((batch, payload, kept))
                slot_plans.append(idxs)
            except Exception as exc:  # pandalint: disable=EXC901 -- held for phase 2: delivered as a value to the ErrorPolicy boundary, which classifies it via note_failure("rebuild")
                # held for phase 2: the script error policy is applied in
                # slot order there, exactly like the old per-slot loop
                slot_plans.append(exc)
                framing_failed.add(launch.script_id)
        sealed = self._engine._seal_jobs(seal_jobs)
        # Phase 2: assemble the reply in slot order under the script's
        # ErrorPolicy — this is the policy boundary (deregister failures
        # ride through here), so programming errors must not bypass it.
        for (disp, item, launch, rng), plan in zip(self._slots, slot_plans):
            if disp == _UNKNOWN or disp == _EMPTY:
                reply.items.append(ProcessBatchReplyItem(item.script_id, item.ntp, []))
            elif disp == _DEREGISTERED:
                dereg.add(item.script_id)
            else:
                if launch.script_id in failed_scripts:
                    if launch.policy != ErrorPolicy.deregister:
                        reply.items.append(
                            ProcessBatchReplyItem(item.script_id, item.ntp, [])
                        )
                    continue
                exc = plan if isinstance(plan, Exception) else next(
                    (
                        sealed[i]
                        for i in plan
                        if isinstance(sealed[i], BaseException)
                    ),
                    None,
                )
                if exc is None:
                    out_batches = [
                        sealed[i] for i in plan if sealed[i] is not None
                    ]
                    reply.items.append(
                        ProcessBatchReplyItem(item.script_id, item.ntp, out_batches)
                    )
                    continue
                faults.note_failure("rebuild", exc)
                failed_scripts.add(launch.script_id)
                if launch.policy == ErrorPolicy.deregister:
                    self._engine.disable_coprocessors([launch.script_id])
                    dereg.add(launch.script_id)
                    reply.items = [
                        ri for ri in reply.items if ri.script_id != launch.script_id
                    ]
                else:
                    reply.items.append(
                        ProcessBatchReplyItem(item.script_id, item.ntp, [])
                    )
        reply.deregistered = sorted(dereg)
        return reply


class TpuEngine:
    """HandleTable + batched async device execution.

    ``force_mode`` pins every script to one execution mode
    ("payload" forces the full-row staging path, "columnar_host" pins the
    numpy predicate, "columnar_device" pins the device predicate; used by
    the bench to measure each half).

    Where the columnar predicate runs is a MEASURED decision (same policy
    as ops/crc_backend.pick and the LZ4 keep-or-kill): the first columnar
    launch probes device vs numpy over the same extracted columns and the
    process keeps the winner; the probe record in ``stats()`` carries both
    timings, or the reason the device leg was unavailable.

    ``stats()["device"]`` names the platform the engine's programs run on
    (resolved when the engine first touches JAX, or by ``resolve_device``),
    and ``n_device_launches`` counts the launches whose program ran on it —
    ``n_launches`` also counts launches the host evaluated.
    """

    # process-wide probed decision: the link physics don't change per
    # engine instance ("device" | "host" | None = not yet probed).
    # Two locks with distinct jobs (pandaraces RAC1101 fix): the RUN lock
    # serializes probe EXECUTION — two concurrent first columnar launches
    # used to BOTH run the expensive device probe (the PR-3 duplicate-
    # jit-trace shape); the loser blocks here and adopts the winner's
    # pick. The short field lock guards the two-field backend/record
    # write and every read — it is never held across the probe itself,
    # so stats()/status readers cannot hang behind a wedged 120s device
    # leg.
    _columnar_backend: str | None = None
    _columnar_probe: dict | None = None
    _columnar_probe_run_lock = threading.Lock()
    _columnar_probe_lock = threading.Lock()

    def __init__(
        self,
        *,
        row_stride: int = 1024,
        compress_threshold: int = 512,
        output_codec: Compression = Compression.zstd,
        force_mode: str | None = None,
        host_workers: int | None = None,
        gather_frame: bool = True,
        device_column_cache_mb: int | None = None,
        mesh_devices: int | None = None,
        mesh_backend: str | None = None,
        mesh_probe: bool = True,
        device_deadline_ms: int | None = None,
        launch_retries: int | None = None,
        retry_backoff_ms: int | None = None,
        breaker_threshold: int | None = None,
        breaker_cooldown_ms: int | None = None,
        adaptive_deadline: bool | None = None,
        adaptive_deadline_margin: float | None = None,
        governor_journal_capacity: int | None = None,
        budget_plane=None,
    ):
        self._handles: dict[int, ScriptHandle] = {}
        # fault domains: every device interaction runs under this envelope
        # (per-attempt deadline, bounded retry + backoff). The static
        # deadline is the FLOOR: the governor derives per-domain effective
        # deadlines from the observed stage p99.9 and may only raise them
        # (coproc/governor.py; config coproc_device_deadline_ms etc.)
        self._fault_policy = faults.FaultPolicy(
            deadline_s=(
                device_deadline_ms if device_deadline_ms is not None else 30_000
            ) / 1000.0,
            retries=launch_retries if launch_retries is not None else 2,
            backoff_s=(
                retry_backoff_ms if retry_backoff_ms is not None else 50
            ) / 1000.0,
        )
        _threshold = breaker_threshold if breaker_threshold is not None else 5
        _cooldown_s = (
            breaker_cooldown_ms if breaker_cooldown_ms is not None else 30_000
        ) / 1000.0
        # The governor owns the decision plane: ONE per-domain breaker per
        # device fault domain (a flaky mask-fetch path demotes fetches
        # while dispatch stays on-device), adaptive per-domain deadlines,
        # and the decision journal every adaptive choice appends to.
        if governor_journal_capacity is not None:
            governor.journal.configure(governor_journal_capacity)
        self.governor = governor.Governor(
            fault_policy=self._fault_policy,
            breaker_threshold=_threshold,
            breaker_cooldown_s=_cooldown_s,
            # a legitimate half-open probe runs a full retry envelope; the
            # stale-probe release must outwait it or a slow probe gets a
            # second probe stacked onto the same struggling device. The
            # envelope here uses the static floor; adaptive growth is
            # bounded by the governor's cap, and the max() keeps the
            # cooldown as the operator-visible lower bound either way.
            breaker_probe_timeout_s=max(
                _cooldown_s, 2.0 * self._fault_policy.envelope_s()
            ),
            adaptive_deadline=(
                adaptive_deadline if adaptive_deadline is not None else True
            ),
            deadline_margin=(
                adaptive_deadline_margin
                if adaptive_deadline_margin is not None
                else 4.0
            ),
        )
        self.governor.set_config_snapshot({
            "device_deadline_ms": round(self._fault_policy.deadline_s * 1e3),
            "launch_retries": self._fault_policy.retries,
            "retry_backoff_ms": round(self._fault_policy.backoff_s * 1e3),
            "breaker_threshold": _threshold,
            "breaker_cooldown_ms": round(_cooldown_s * 1e3),
            "force_mode": force_mode,
            "gather_frame": bool(gather_frame),
            "adaptive_deadline": (
                adaptive_deadline if adaptive_deadline is not None else True
            ),
        })
        # the dispatch-domain breaker doubles as the engine-level handle
        # (dispatch is the domain every launch crosses first)
        self._breaker = self.governor.breaker_for(faults.DEVICE_DISPATCH)
        self._row_stride = row_stride
        self._compress_threshold = compress_threshold
        self._output_codec = output_codec
        self._force_mode = force_mode
        # width of the mesh lane's per-device host ladder
        # (coproc/host_pool.py; the pool is built with the mesh runner
        # below): None = config default min(4, cores); 0 or 1 runs the
        # per-device ladders one after another on the dispatching thread
        if host_workers is None:
            host_workers = host_pool.default_host_workers()
        self._host_workers = max(0, int(host_workers))
        self._host_pool: host_pool.HostStagePool | None = None
        self.governor.update_config_snapshot(host_workers=self._host_workers)
        # Zero-copy harvest: byte-identity transforms gather-frame straight
        # from the joined blob (gather_frame=False is the bench ablation /
        # operator escape hatch), and framing scratch reuses across
        # launches through the arena (reset_arenas() for tests).
        self._gather_frame = bool(gather_frame)
        self._arena = leakwatch.wrap(batch_codec.Arena(), "engine.arena")
        self._staging = batch_codec.Arena(max_free=self._staging_slots())
        self._uncompress_pool = batch_codec.Arena(
            max_free=_STAGING_MAX_PARKED, quantum=_UNCOMPRESS_QUANTUM
        )
        self._seal_pool = batch_codec.Arena(
            max_free=_STAGING_MAX_PARKED, quantum=_UNCOMPRESS_QUANTUM
        )
        # Device-resident column cache (coproc/colcache.py): repeat
        # scripts over unchanged batch windows skip the whole host ladder
        # and the H2D replay. 0/None disables it — the BROKER default is
        # 32 MB via config coproc_device_column_cache_mb (CoprocApi), but
        # a bare-constructed engine keeps the uncached semantics so fault/
        # parity harnesses that replay one request still exercise the
        # machinery they are pointed at.
        _cache_mb = (
            0 if device_column_cache_mb is None
            else max(0, int(device_column_cache_mb))
        )
        self._colcache = (
            colcache.DeviceColumnCache(_cache_mb << 20) if _cache_mb else None
        )
        self.governor.update_config_snapshot(
            device_column_cache_mb=_cache_mb
        )
        # Multi-chip sharded engine (coproc/meshrunner.py): the partition
        # axis pjit/shard_map-sharded over an N-device mesh, per-device
        # sub-launches over the host-pool range shard (the one user of the
        # pool: a single-device engine has none). None/0/1 keeps the
        # single-device engine (config coproc_mesh_devices wires the
        # broker knob). mesh_probe=False pins "mesh" unmeasured — parity
        # tests and bench ablations need the mesh lane deterministically;
        # True runs the measured mesh-vs-single calibration on the first
        # representative launch (PROBE_MARGIN posture, journaled).
        self._meshrunner: meshrunner.MeshRunner | None = None
        self._mesh_error: str | None = None
        if mesh_devices is not None and int(mesh_devices) >= 2:
            try:
                self._meshrunner = meshrunner.MeshRunner(
                    n_devices=int(mesh_devices), backend=mesh_backend,
                    probe=mesh_probe,
                )
            except Exception as exc:
                # fewer devices than asked for (or no jax backend): the
                # engine runs single-device, and says so — on /metrics,
                # in the log and as stats()["mesh_error"]
                faults.note_failure("mesh_init", exc)
                logger.warning("meshrunner unavailable: %s", exc)
                self._mesh_error = f"{faults.kind_of(exc)}: {exc}"
        if self._meshrunner is not None and self._host_workers >= 2:
            self._host_pool = host_pool.HostStagePool(self._host_workers)
        self.governor.update_config_snapshot(
            mesh_devices=(
                self._meshrunner.n_devices if self._meshrunner else 0
            )
        )
        # Budget plane (resource_mgmt): staged rows acquire from the
        # 'coproc' account BEFORE any dispatch — exhaustion sheds the
        # whole submit with a retriable ShedError (the pacemaker backs
        # off and re-reads the same offsets: nothing lost, nothing
        # duplicated, never silent queue growth). Bytes release when the
        # ticket harvests (Ticket.result's finally — the
        # leak-on-exception tests pin it). Plane-less engines (bare
        # test/bench constructions) admit everything, the historical
        # semantics. The pressure listener is weakref-bound: the
        # process-wide plane must not pin dead engines.
        self._budget_plane = budget_plane
        self._admission: rm_admission.AdmissionController | None = None
        self._pressure_listener = None
        if budget_plane is not None:
            acct = budget_plane.accounts.get("coproc")
            if acct is not None:
                self._admission = leakwatch.wrap(
                    rm_admission.AdmissionController(
                        acct, "coproc", warn_pct=budget_plane.warn_pct
                    ),
                    "engine.admission",
                )
            _ref = weakref.ref(self)

            def _pressure_listener(level, snap, _ref=_ref):
                eng = _ref()
                if eng is not None:
                    eng._on_memory_pressure(level, snap)

            self._pressure_listener = _pressure_listener
            budget_plane.add_pressure_listener(_pressure_listener)
        self.governor.update_config_snapshot(
            admission=self._admission is not None
        )
        # per-shard stage splits of the most recent mesh launch (bench
        # artifact + debugging aid; overwritten per launch under the lock)
        self.last_launch_shards: list[dict] | None = None
        # the platform this engine's programs run on ({"platform",
        # "device_kind", "count", "cpu_pinned"}); None until resolved
        self._device: dict | None = None
        # (script_id, lane, n_pad) of every device program that has run
        # once, with its first-run seconds: its next launch is past trace
        # + compile (_try_device_leg)
        self._compiled: dict[tuple, float] = {}
        # (jitted function, n_pad) of every payload pipeline that has run:
        # scripts of one spec share the function (ops/pipeline.py caches it
        # by spec), so a new script's launch at a bucket its function has
        # seen is past trace + compile too
        self._ran_fns: set[tuple] = set()
        self._compile_lock = lockwatch.wrap(
            threading.Lock(), "TpuEngine._compile_lock"
        )
        # jitted payload pipeline -> its ladder of programs built ahead of
        # need (_Ladder); guarded by _stats_lock. Only an engine whose
        # governor knows the launch's read budget has any (_ladder_top)
        self._ladders: dict[object, _Ladder] = {}
        # the one thread that builds them (rptpu-precompile), alive while
        # any ladder has a bucket to build; guarded by _stats_lock
        self._precompiler: threading.Thread | None = None
        self._device_launches: dict[int, int] = defaultdict(int)
        # the parse ladder the last columnar launch ran ("structural" |
        # "staged"); None before one
        self._parse_path: str | None = None
        self._pipelines: dict[int, tuple] = {}  # payload: script_id -> (fn, r_out)
        # payload: script_id -> its spec's pipelines by staged width, and
        # those by (spec json, result format) for the engine's life: the
        # strides a spec has shown outlive its scripts (_SpecPrograms)
        self._lanes: dict[int, _SpecPrograms] = {}
        self._spec_programs: dict[tuple, _SpecPrograms] = {}
        self._plans: dict[int, object] = {}  # script_id -> execution plan
        self._stats: dict[str, float] = defaultdict(float)
        self._stats_lock = lockwatch.wrap(
            threading.Lock(), "TpuEngine._stats_lock"
        )
        # mask harvester: one daemon thread pays the D2H confirmation round
        # trip per launch while the caller keeps doing host work
        self._harvest_q: "queue.Queue[_Launch]" = queue.Queue()  # pandalint: disable=BPR1401 -- bounded upstream: at most launch_depth launches are in flight (pacemaker gate) and each holds coproc-account bytes admitted at submit_group
        self._harvester: threading.Thread | None = None

    def _ensure_harvester(self) -> threading.Thread:
        # locked: concurrent dispatchers must not each spawn a permanent
        # thread (check-then-create race)
        with self._stats_lock:
            if self._harvester is None or not self._harvester.is_alive():
                self._harvester = threading.Thread(
                    target=self._harvest_loop, name="rptpu-mask-harvester",
                    daemon=True,
                )
                self._harvester.start()
            return self._harvester

    def shutdown(self) -> None:
        """Stop the engine's background machinery: the mask-harvester
        thread (sentinel + join) and the host-stage pool. In-flight
        launches drain first (the sentinel queues behind them). A daemon
        harvester pins the whole engine — plans, jit executables, staged
        arrays — for the life of the process otherwise, which long-lived
        embedders (and test suites creating many engines) cannot afford.
        The engine must not process batches after shutdown."""
        with self._stats_lock:
            t, self._harvester = self._harvester, None
            ladders = list(self._ladders.values())
            builder = self._precompiler
        if t is not None and t.is_alive():
            self._harvest_q.put(None)
            t.join(timeout=60.0)
        for ladder in ladders:
            with ladder.cond:
                ladder.stopped = True
                ladder.cond.notify_all()
        if builder is not None:
            # a compile under way cannot be interrupted: the builder ends
            # after it (a daemon thread; nothing waits on it past this)
            builder.join(timeout=5.0)
        if self._host_pool is not None:
            self._host_pool.shutdown()
        with self._stats_lock:  # concurrent shutdowns: swap-then-remove once
            listener, self._pressure_listener = self._pressure_listener, None
        if self._budget_plane is not None and listener is not None:
            # the plane is process-wide and outlives this engine: leave
            # the dead closure behind and every later pressure transition
            # still walks it (the weakref makes it a no-op, not free)
            self._budget_plane.remove_pressure_listener(listener)

    def _harvest_loop(self) -> None:
        while True:
            launch = self._harvest_q.get()
            if launch is None:  # shutdown sentinel
                return
            with _mask_claim_lock:
                if launch._mask_state == "claimed":
                    # its caller gave up waiting and is fetching the mask
                    # itself: a fetch here would be a duplicate envelope
                    # and a stale breaker verdict
                    continue
                launch._mask_state = "harvesting"
            t_get = time.perf_counter()
            dev = launch._mask_dev
            harvest_breaker = self.governor.breaker_for(faults.HARVEST)
            try:
                if dev is not None and not harvest_breaker.allow_device():
                    # open harvest breaker: skip the doomed fetch without
                    # spending an envelope or a verdict — the woken caller
                    # takes the exact host fallback (demoted fetches, while
                    # dispatch's own breaker decides dispatch separately)
                    launch._mask_np = None
                elif dev is not None:
                    def leg(dev=dev):
                        t0 = time.perf_counter()
                        faults.inject(faults.HARVEST)
                        # the fetch worker pays the link wait (timed
                        # leg by leg); this thread only coordinates, so a
                        # wedged link can no longer freeze every later
                        # launch's mask behind it
                        out = launch._fetch_legs(dev)
                        # success-only adaptive-deadline sample (a raise
                        # or abandonment never reaches this line)
                        self.governor.observe_leg(
                            faults.HARVEST, time.perf_counter() - t0
                        )
                        return out

                    launch._mask_np = faults.retry_call(
                        leg, self.governor.policy_for(faults.HARVEST),
                        faults.HARVEST, count=self._stat_add,
                    )
                    harvest_breaker.record_success()
            except Exception as exc:
                launch._mask_np = None  # materialize() falls back
                # classified, never fatal: this daemon serves every launch
                # and _resolve_keep owns the per-launch fallback decision.
                # The verdict lands BEFORE the event below: a caller woken
                # by the event must observe the breaker state this failure
                # produced, not a stale snapshot. A PROGRAMMING error is
                # counted but gives no breaker verdict — a bug in our code
                # must not quietly demote the engine to host forever (and
                # re-raising would kill the daemon every launch depends on).
                faults.note_failure(faults.HARVEST, exc)
                if not isinstance(exc, faults.PROGRAMMING_ERRORS):
                    harvest_breaker.record_failure()
            finally:
                t_done = time.perf_counter()
                # device-time span: the fetch completes the async D2H, so
                # its wall time is the post-block_until_ready device leg;
                # queue_us is how long the launch waited for this thread.
                tracer.record(
                    "coproc.device_harvest",
                    (t_done - t_get) * 1e6,
                    launch.trace_id,
                    start_perf=t_get,
                    queue_us=int((t_get - launch._enq_t) * 1e6),
                    device_us=int((t_done - t_get) * 1e6),
                )
                launch._mask_event.set()

    # ------------------------------------------------------------ control
    def enable_coprocessors(
        self, scripts: list[tuple[int, str, tuple[str, ...]]],
        partitions: dict[str, int] | None = None,
    ) -> list[EnableResponseCode]:
        """scripts: [(script_id, spec_json, input_topics)]. ``partitions``:
        input topic -> its partition count, where the caller knows it (the
        broker does): with the governor's read budget it sizes the ladder
        of device programs a payload script's launches can reach, which
        starts building here, off this thread (_start_ladder)."""
        out = []
        for script_id, spec_json, topics in scripts:
            if script_id in self._handles:
                out.append(EnableResponseCode.script_id_already_exists)
                continue
            if not topics:
                out.append(EnableResponseCode.script_contains_no_topics)
                continue
            if any(t.startswith("__") or ".$" in t for t in topics):
                out.append(EnableResponseCode.script_contains_invalid_topic)
                continue
            try:
                spec = TransformSpec.from_json(spec_json)
                plan = plan_spec(spec)  # validates the expr tree + constants
                if (
                    self._force_mode == "payload"
                    and plan.mode != "payload"
                    and spec.where is None
                ):
                    # v1-expressible specs only: where-specs have no payload
                    # compilation and keep their columnar plan.
                    plan = PayloadPlan(spec)
                if plan.mode == "payload":
                    mask_only = self._mask_result(plan)
                    with self._stats_lock:
                        lane = self._spec_programs.get((spec.to_json(), mask_only))
                    if lane is None:
                        lane = _SpecPrograms(spec, mask_only, self._row_stride)
                        with self._stats_lock:
                            lane = self._spec_programs.setdefault(
                                (spec.to_json(), mask_only), lane
                            )
                    self._lanes[script_id] = lane
                    self._pipelines[script_id] = lane.fns[self._row_stride]
                    # the full-width ladder, and one for every narrower
                    # stride an earlier script of the spec has shown
                    lane.read_from(sum((partitions or {}).get(t, 1) for t in topics))
                    for stride, (fn, _r) in sorted(lane.fns.items(), reverse=True):
                        self._start_ladder(
                            fn, stride, self._ladder_top(lane.partitions, stride)
                        )
                self._plans[script_id] = plan
            except Exception as exc:
                # bad spec from the wire, not a broker fault: refuse the
                # registration and account the rejection
                faults.note_failure("enable", exc)
                out.append(EnableResponseCode.internal_error)
                continue
            self._handles[script_id] = ScriptHandle(
                script_id, spec, tuple(topics), checksum=xxhash64(spec_json)
            )
            out.append(EnableResponseCode.success)
        return out

    # ------------------------------------------------------------ ladders
    def _ladder_top(self, partitions: int, stride: int | None = None) -> int | None:
        """The largest row bucket a part ``stride`` wide (the lane's own by
        default) can reach in a launch of a script over ``partitions``
        partitions: the bytes the governor's read budget hands one launch
        (Governor.launch_read_bytes: partitions x a tick's read x the
        launch knob's cap) at the densest rows the stride is sized for. Up
        to _BODY_STRIDE the strides share one top (the lane's own, where
        its limit is no wider: a launch's rows come with its values, not
        with the limit); a class above it has as many fewer rows as it is
        wider. None: nothing here sizes a launch (a bare engine with no
        pacemaker's budget behind it), so no ladder is built and every
        program is a first run on the serving path, as before."""
        read_bytes = self.governor.launch_read_bytes(partitions)
        if read_bytes is None:
            return None
        own = self._row_stride
        dense = max(own if stride is None else stride, min(own, _BODY_STRIDE))
        return _bucket_rows(read_bytes * _LADDER_ROWS_PER_STRIDE // dense)

    def _start_ladder(self, fn, stride: int, top: int | None) -> None:
        """Have ``fn``'s programs (rows ``stride`` wide) built on the
        engine's builder thread: every row bucket up to ``top`` at the
        lane's own stride, the buckets launches ask for at a narrower one
        (a lazy ladder). A second script of one spec finds the ladder there
        (or taller than it needs) and builds nothing; one over more
        partitions raises the top and the builder goes on from where the
        ladder stands. A ladder's top is in rows, the launch's, whatever
        the stride."""
        if top is None:
            return
        with self._stats_lock:
            ladder = self._ladders.get(fn)
            if ladder is None:
                ladder = self._ladders[fn] = _Ladder(
                    fn, stride + IN_META, top, lazy=stride != self._row_stride,
                    pad_max=_PAD_MAX if self._row_stride > _BODY_STRIDE else 1,
                )
            with ladder.cond:
                ladder.top = max(ladder.top, top)
            self._wake_precompiler()

    def _wake_precompiler(self) -> None:
        """The builder thread runs while any ladder lacks a bucket. Caller
        holds _stats_lock, under which the builder also decides to end: a
        ladder raised or a bucket wanted from then on starts a new one."""
        if self._precompiler is None and any(
            ladder.next_bucket() is not None for ladder in self._ladders.values()
        ):
            self._precompiler = threading.Thread(
                target=self._precompile_loop, name="rptpu-precompile", daemon=True
            )
            self._precompiler.start()

    def _precompile_loop(self) -> None:
        """The builder thread: ONE program at a time, whatever the number
        of ladders (a trace holds the interpreter lock and a compile takes
        every core it finds: two builders at once halved the serving
        path's host stages on the chip's host, PR 47). First what the
        narrower strides' launches wanted: few programs (_stride_ready
        asks for the ones a stream runs in its steady state, not for a
        ramp step's), and the ones that take the stream's launches off the
        lane's own, widest programs. Then the lanes' own ladders, smallest
        bucket first (a launch is cut to the largest built one)."""
        while True:
            with self._stats_lock:
                todo = [
                    (not ladder.lazy, n_pad * ladder.stride, n_pad, ladder)
                    for ladder in self._ladders.values()
                    for n_pad in [ladder.next_bucket()]
                    if n_pad is not None
                ]
                if not todo:
                    self._precompiler = None
                    return
            _own, _bytes, n_pad, ladder = min(todo, key=lambda job: job[:3])
            self._build_program(ladder, n_pad)

    def _build_program(self, ladder: _Ladder, n_pad: int) -> None:
        """One ``lower().compile()``: a stage ``coproc.precompile`` (an
        ``rp:`` annotation on the builder's line of a profile,
        ``t_precompile`` and ``n_precompiles`` in stats()). A failure ends
        that ladder's build: its scripts keep serving, through the
        first-run path."""
        try:
            self.resolve_device()
            t0 = stages.begin("coproc.precompile", n_pad=n_pad)
            program = lower_packed_pipeline(ladder.fn, (n_pad, ladder.stride))
            dt = stages.close(
                "coproc.precompile", None, t0, trace_id=None
            )
            if tracer.enabled:
                tracer.record(
                    "coproc.precompile", dt * 1e6, tracer.new_trace_id(),
                    start_perf=float(t0), parent=None,
                    n_pad=n_pad, seconds=round(dt, 4),
                )
            self._stat_add("t_precompile", dt)
            self._stat_add("n_precompiles", 1.0)
            with self._stats_lock:
                # a launch at this bucket is past trace + compile
                self._ran_fns.add((ladder.fn, n_pad))
            with ladder.cond:
                ladder.programs[n_pad] = program
                ladder.seconds[n_pad] = dt
                ladder.cond.notify_all()
        except Exception as exc:
            faults.note_failure("precompile", exc)
            logger.exception("precompile of a payload program failed")
            self._stat_add("n_precompile_failures", 1.0)
            with ladder.cond:
                ladder.failed = f"{faults.kind_of(exc)}: {exc}"
                ladder.cond.notify_all()

    def _ladder_of(self, script_id: int) -> _Ladder | None:
        pipeline = self._pipelines.get(script_id)
        with self._stats_lock:
            return self._ladders.get(pipeline[0]) if pipeline else None

    def programs_ready(self, script_id: int) -> list[int]:
        """The row buckets whose device program is built for a script's
        launches (``coproc_programs_ready{script=}`` counts them)."""
        ladder = self._ladder_of(script_id)
        return ladder.ready() if ladder is not None else []

    def await_programs(self, script_id: int, n: int = 2, wait_s: float = 120.0) -> bool:
        """Block until a script's ``n`` smallest buckets are built (the
        pacemaker's fiber, off the loop, before it takes its first input):
        the first launches of a deploy are then cut to a ready program and
        none waits inside a tick. True at once for a script with no ladder."""
        ladder = self._ladder_of(script_id)
        return ladder is None or ladder.wait_first(n, wait_s)

    def enable_py_transform(
        self,
        script_id: int,
        fn,
        topics: tuple[str, ...],
        policy: ErrorPolicy = ErrorPolicy.skip_on_failure,
    ) -> EnableResponseCode:
        """Escape hatch: an arbitrary python callable(value) -> value | None
        run in the engine's host stage with the standard engine interface —
        for transforms the declarative DSL cannot express (the analogue of
        the reference's arbitrary Coprocessor.apply(), SimpleTransform.ts:18).
        In-process trust only; the WIRE-deployable form is
        enable_py_sandboxed.
        """
        if script_id in self._handles:
            return EnableResponseCode.script_id_already_exists
        if not topics:
            return EnableResponseCode.script_contains_no_topics
        spec = TransformSpec(name=f"py:{getattr(fn, '__name__', 'fn')}")
        self._plans[script_id] = plan_spec(spec, py_fn=fn)
        self._handles[script_id] = ScriptHandle(
            script_id, spec, tuple(topics), policy=policy
        )
        return EnableResponseCode.success

    def enable_py_sandboxed(
        self,
        script_id: int,
        source: str,
        topics: tuple[str, ...],
        policy: ErrorPolicy = ErrorPolicy.skip_on_failure,
    ) -> EnableResponseCode:
        """Wire-deployable arbitrary transform: restricted-AST python
        validated HERE (on every consuming broker) before registration —
        a malicious blob never reaches execution (coproc/sandbox.py; the
        reference's analogue is the V8 supervisor boundary)."""
        from redpanda_tpu.coproc.sandbox import SandboxViolation, compile_transform

        if script_id in self._handles:
            return EnableResponseCode.script_id_already_exists
        if not topics:
            return EnableResponseCode.script_contains_no_topics
        try:
            fn = compile_transform(source, script_id=script_id)
        except SandboxViolation as exc:
            faults.note_failure(faults.SANDBOX_COMPILE, exc)
            return EnableResponseCode.internal_error
        except Exception as exc:
            # any other compile-time blowup is a bad script, not a broker
            # fault — refuse registration rather than poison the caller
            faults.note_failure(faults.SANDBOX_COMPILE, exc)
            logger.exception("sandboxed script %d failed to compile", script_id)
            return EnableResponseCode.internal_error
        return self.enable_py_transform(script_id, fn, topics, policy)

    def disable_coprocessors(self, script_ids: list[int]) -> list[DisableResponseCode]:
        out = []
        for sid in script_ids:
            if sid in self._handles:
                del self._handles[sid]
                self._pipelines.pop(sid, None)
                self._lanes.pop(sid, None)
                self._plans.pop(sid, None)
                self._forget_programs(sid)
                self.invalidate_columns(sid)
                out.append(DisableResponseCode.success)
            else:
                out.append(DisableResponseCode.script_id_does_not_exist)
        return out

    def disable_all_coprocessors(self) -> int:
        n = len(self._handles)
        self._handles.clear()
        self._pipelines.clear()
        self._lanes.clear()
        self._plans.clear()
        self._forget_programs(None)
        self.invalidate_columns()
        return n

    def _forget_programs(self, script_id: int | None) -> None:
        """A re-registered script id gets a new plan and new programs."""
        with self._stats_lock:
            self._compiled = {} if script_id is None else {
                k: v for k, v in self._compiled.items() if k[0] != script_id
            }

    # ------------------------------------------------------------ colcache
    def invalidate_columns(self, script_id: int | None = None) -> int:
        """Drop cached device/host columns (every script when script_id is
        None); returns entries dropped. The cache key is content-addressed
        (a changed batch window misses by construction), so this hook is a
        MEMORY contract, not a correctness one: the pacemaker calls it
        when a script's input offsets advance (streaming never re-reads,
        the bytes are dead weight) and script unload drops its entries."""
        if self._colcache is None:
            return 0
        return self._colcache.invalidate(script_id)

    def reset_column_cache(self) -> None:
        """Test/bench hook: drop all cached columns AND zero the cache
        counters so hit-rate accounting is deterministic per run."""
        if self._colcache is not None:
            self._colcache.reset()

    # ------------------------------------------------------------ metrics
    def stats(self) -> dict:
        """Accumulated per-stage wall seconds and link bytes, plus the
        pool size and (once probed) the columnar-backend probe record.
        Numeric stage keys are floats; ``columnar_backend``/``columnar_probe``
        are a string and a dict — consumers formatting stages should key on
        the ``t_``/``n_``/``bytes_`` prefixes."""
        with self._stats_lock:
            out = dict(self._stats)
            out["device"] = dict(self._device) if self._device else None
            out["device_launches_by_script"] = dict(self._device_launches)
            out["parse_path"] = self._parse_path
            # every device program this engine compiled: a new row bucket
            # is a new program, and this is where that cost shows
            out["compiled_programs"] = [
                {"script_id": k[0], "lane": k[1], "n_pad": k[2],
                 "t_first_run_s": round(v, 4),
                 **({"stride": k[3]} if len(k) > 3 else {})}
                for k, v in self._compiled.items()
            ]
            met = set(self._compiled)
            # a payload script's ladders by staged width, the lane's full
            # row_stride first
            ladders = {
                sid: [
                    (stride, self._ladders[fn])
                    for stride, (fn, _r) in sorted(lane.fns.items(), reverse=True)
                    if fn in self._ladders
                ]
                for sid, lane in list(self._lanes.items())
            }
        # ... and every program built ahead of need, whether a launch has
        # met it yet or not: a script's ready buckets and the seconds each
        # took to build (what the benchmark's warm-up waits to stand still)
        out["programs_ready"] = {}
        for sid, by_stride in ladders.items():
            for stride, ladder in by_stride:
                with ladder.cond:
                    built = dict(ladder.seconds)
                    state = ladder.failed or (
                        "building" if ladder.missing() else "ready"
                    )
                if stride == self._row_stride:
                    out["programs_ready"][sid] = {
                        "buckets": sorted(built), "top": ladder.top, "state": state,
                    }
                elif sid in out["programs_ready"]:
                    # the narrower strides the spec's launches have shown
                    out["programs_ready"][sid].setdefault("strides", {})[stride] = {
                        "buckets": sorted(built), "state": state,
                    }
                out["compiled_programs"] += [
                    {"script_id": sid, "lane": "payload", "n_pad": n_pad,
                     "t_first_run_s": 0.0, "t_precompile_s": round(secs, 4),
                     "stride": stride}
                    for n_pad, secs in sorted(built.items())
                    if (sid, "payload", n_pad, stride) not in met
                ]
        out["host_workers"] = float(self._host_workers)
        # "breaker" keeps its historical engine-level shape (worst state,
        # summed counts); "breakers" is the per-domain split and
        # "governor" the decision-plane snapshot (posture + journal summary)
        out["breaker"] = self.governor.aggregate_breaker_snapshot()
        out["breakers"] = self.governor.breakers_snapshot()
        out["governor"] = self.governor.snapshot()
        out["arena"] = self._arena.stats()
        out["staging_arena"] = self._staging.stats()
        out["uncompress_arena"] = self._uncompress_pool.stats()
        out["seal_arena"] = self._seal_pool.stats()
        if lockwatch.enabled():
            # debug mode only: the observed lock-order edge count rides
            # stats() into /v1/coproc/status, rpk debug coproc and BENCH
            out["lockwatch"] = lockwatch.snapshot()
        if leakwatch.enabled():
            # same posture: outstanding balances + imbalance count ride
            # stats() into the status/debug surfaces
            out["leakwatch"] = leakwatch.snapshot()
        if self._colcache is not None:
            out["colcache"] = self._colcache.stats()
        if self._admission is not None:
            out["admission"] = self._admission.snapshot()
        if self._meshrunner is not None:
            out["mesh"] = self._meshrunner.stats()
        if self._mesh_error is not None:
            out["mesh_error"] = self._mesh_error
        with TpuEngine._columnar_probe_lock:  # coherent two-field snapshot
            backend = TpuEngine._columnar_backend
            probe = TpuEngine._columnar_probe
        if probe is not None:
            out["columnar_backend"] = backend
            out["columnar_probe"] = dict(probe)
        return out

    def resolve_device(self) -> dict:
        """Name the platform this engine's programs run on, once. Touches
        JAX: on an accelerator this process holds the chip from here on.
        A process that was not pinned to the CPU backend and still found
        no accelerator says so, rather than run JAX's CPU backend under
        the engine's name unremarked."""
        with self._stats_lock:
            dev = self._device
        if dev is not None:
            return dev
        dev = {**platform.device_info(), "cpu_pinned": platform.cpu_pinned()}
        if dev["platform"] == "cpu" and not dev["cpu_pinned"]:
            dev["warning"] = (
                "no accelerator found: the engine's device programs run on "
                "JAX's CPU backend (set JAX_PLATFORMS=cpu to ask for that)"
            )
            logger.warning("coproc engine: %s", dev["warning"])
        else:
            logger.info(
                "coproc engine device: platform=%s device_kind=%s count=%d",
                dev["platform"], dev["device_kind"], dev["count"],
            )
        with self._stats_lock:
            self._device = dev
        return dev

    @classmethod
    def sticky_columnar_backend(cls) -> str | None:
        """The process-wide probed backend, read under the probe lock —
        call sites take ONE coherent snapshot instead of re-reading the
        class attribute around a concurrent probe's two-field write."""
        with cls._columnar_probe_lock:
            return cls._columnar_backend

    @classmethod
    def reset_columnar_probe(cls) -> None:
        """Forget the process-wide columnar backend probe so the next
        columnar launch re-probes. The probed pick is deliberately sticky
        (link physics don't change per engine), but bench ablations and
        tests that construct engines under a different ``force_mode`` or a
        different link must be able to re-measure instead of inheriting a
        stale decision."""
        with cls._columnar_probe_lock:
            cls._columnar_backend = None
            cls._columnar_probe = None

    def _release_admission(self, ticket: "Ticket") -> None:
        """Return a ticket's reserved coproc-account bytes. Idempotent AND
        atomic: the zero-swap runs under the stats lock because an
        abandonment path may release from the loop while the executor
        thread's ``result()`` finally races the same ticket — an unlocked
        double-read would free the bytes twice and overcommit the
        account."""
        with self._stats_lock:
            n, ticket._admitted = ticket._admitted, 0
        if n and self._admission is not None:
            self._admission.release(n)

    def _on_memory_pressure(self, level: str, snap: dict) -> None:
        """Budget-plane pressure transition (fired by BudgetPlane on level
        CHANGE, from whatever thread moved the occupancy). CRITICAL sheds
        reclaimable memory: both arenas' free lists (framing scratch,
        parked staging matrices) are trimmed and the column
        cache evicts down to half its budget; OK restores the full cache
        budget. WARN only journals — the admission and autotune layers own
        the load response. Each transition is one ADMISSION-domain journal
        entry (level changes are rare by the plane's hysteresis)."""
        trims = evicted = 0
        if level == rm_budgets.PRESSURE_CRITICAL:
            trims = (
                self._arena.trim()
                + self._staging.trim()
                + self._uncompress_pool.trim()
                + self._seal_pool.trim()
            )
            if self._colcache is not None:
                evicted = self._colcache.set_pressure(True)
            self._stat_add("n_pressure_trims", 1.0)
            if evicted:
                self._stat_add("n_pressure_evictions", float(evicted))
        elif level == rm_budgets.PRESSURE_OK and self._colcache is not None:
            self._colcache.set_pressure(False)
        self.governor.record(
            governor.ADMISSION, level,
            f"memory pressure {level}: arena buffers freed {trims}, "
            f"colcache entries evicted {evicted}",
            {
                "arena_freed": trims,
                "colcache_evicted": evicted,
                "max_occupancy": snap.get("max_occupancy"),
                "account": snap.get("max_occupancy_account"),
            },
        )

    def reset_arenas(self) -> None:
        """Swap in a fresh harvest scratch arena and a fresh staging pool.
        Both are deliberately long-lived (buffer reuse across launches is
        the point), but tests and bench ablations need deterministic
        alloc/reuse accounting — and an engine parked after a giant launch
        can use this to return the held buffers to the allocator."""
        self._arena = leakwatch.wrap(batch_codec.Arena(), "engine.arena")
        self._staging = batch_codec.Arena(max_free=self._staging_slots())
        self._uncompress_pool = batch_codec.Arena(
            max_free=_STAGING_MAX_PARKED, quantum=_UNCOMPRESS_QUANTUM
        )
        self._seal_pool = batch_codec.Arena(
            max_free=_STAGING_MAX_PARKED, quantum=_UNCOMPRESS_QUANTUM
        )

    def _staging_slots(self) -> int:
        """The staging pool's slots: _STAGING_MAX_PARKED for the matrices of
        the strides up to _BODY_STRIDE, as every lane had, and as many again
        a width class above it (a launch parks a matrix a part)."""
        wide = int(np.count_nonzero(_class_strides(self._row_stride) > _BODY_STRIDE))
        return _STAGING_MAX_PARKED * (1 + wide)

    def reset_stats(self) -> None:
        with self._stats_lock:
            self._stats.clear()

    def _stat_add(self, key: str, v: float) -> None:
        # Harvests may run on executor threads concurrently with dispatch.
        # The probe mirror records UNDER the same lock: HdrHist.record is a
        # read-modify-write, and concurrent harvest threads would lose
        # samples recorded outside it. Per-launch cadence, so the lock is
        # off the per-record path. Stage wall times become
        # coproc_stage_latency_us{stage=...}; link traffic becomes the
        # device-transfer counters.
        with self._stats_lock:
            self._stats[key] += v
            if key.startswith("t_"):
                probes.coproc_stage_hist(key[2:]).record(int(v * 1e6))
            elif key == "bytes_h2d":
                probes.coproc_h2d_bytes.inc(v)
            elif key == "bytes_d2h":
                probes.coproc_d2h_bytes.inc(v)
            elif key == "n_staged_rows":
                probes.coproc_staged_rows.inc(v)
            elif key == "n_oversize_rows":
                probes.coproc_oversize_rows.inc(v)
            elif key == "n_split_launches":
                probes.coproc_split_launches.inc(v)
            elif key in probes.coproc_width_classes:
                probes.coproc_width_classes[key].inc(v)
            elif key == "bytes_staged":
                probes.coproc_staged_bytes.inc(v)
            elif key == "bytes_staged_values":
                probes.coproc_staged_value_bytes.inc(v)
            elif key == "n_frame_gather":
                probes.coproc_harvest_gather.inc(v)
            elif key == "n_frame_padded":
                probes.coproc_harvest_padded.inc(v)
            elif key == "n_kept_rows":
                probes.coproc_kept_rows.inc(v)
            elif key == "bytes_out":
                probes.coproc_output_bytes.inc(v)
            elif key in probes.coproc_json_rows:
                probes.coproc_json_rows[key].inc(v)
            elif key in probes.coproc_uncompress:
                probes.coproc_uncompress[key].inc(v)
            elif key in probes.coproc_seal:
                probes.coproc_seal[key].inc(v)
            elif key in probes.coproc_precompile:
                probes.coproc_precompile[key].inc(v)

    def _stat_stage(self, key: str, t0: float, trace_id=_AMBIENT, **ring) -> float:
        """Close one stage timer (``t0 = _stage_t0(key)``) through the stage
        helper: ONE clock read, the ``rp:coproc.stage.*`` annotation ended,
        the duration mirrored as a pandapulse lifecycle span, and the same
        ``dt`` into stat + probe via ``_stat_add`` (under the stats lock, so
        the helper takes no histogram here): timeline slices sum to the
        ``t_*`` splits by construction. Submit-side call sites run inside
        the ``coproc.dispatch`` span, so the ambient trace id resolves on
        the dispatching thread; pool/mesh workers pass the launch's trace id
        explicitly (no ambient there)."""
        # "coproc.stage." namespace: stage slices must not collide with the
        # wrapper spans (t_dispatch vs the coproc.dispatch span around the
        # whole submit fan-out)
        dt = stages.close(
            "coproc.stage." + key[2:], None, t0, trace_id=trace_id, **ring
        )
        _stage_closed(dt)
        self._stat_add(key, dt)
        return dt

    def _call_done(self, key: str, t_run: float) -> float:
        """End the engine call begun at ``t_run = _call_t0()`` on this
        thread: its clock read (the caller's ``worker_clock`` takes the same
        one), the call's seconds into ``t_<call>`` and what no top-level
        stage of this thread covered into ``t_<call>_self``."""
        t_done = time.perf_counter()
        dt = t_done - t_run
        self._stat_add(key, dt)
        self._stat_add(key + "_self", max(dt - _self_time.covered, 0.0))
        return t_done

    def _count_fallback(self, n: int) -> None:
        """Account records whose stages re-executed on the pure-host
        fallback (exhausted device retries or an open breaker)."""
        self._stat_add("n_fallback_rows", float(n))
        probes.coproc_fallback_rows.inc(n)

    def _count_uncompress(
        self, n_batches: int, n_crossings: int, bytes_in: int, bytes_out: int,
        dt: float,
    ) -> None:
        """batch_codec.launch_payloads' hook, the ONE place the decompress
        leg is counted: the compressed batches of a launch, the crossings
        into a codec that served them (two a launch through the
        many-frames form, one a batch otherwise), the bytes in and out,
        and the ``uncompress`` stage's seconds (the helper timed it, with
        its ``rp:`` annotation and span; it lies inside the launch's
        explode stage)."""
        self._stat_add("t_uncompress", dt)
        self._stat_add("n_uncompressed_batches", float(n_batches))
        self._stat_add("n_uncompress_crossings", float(n_crossings))
        self._stat_add("bytes_uncompress_in", float(bytes_in))
        self._stat_add("bytes_uncompress_out", float(bytes_out))

    def _seal_jobs(self, jobs: list[tuple]) -> list:
        """Recompress + seal framed payloads into output batches, in input
        order: the whole list in ONE native crossing that holds no
        interpreter lock (batch_codec.build_output_batches: compression and
        both CRCs, on up to four threads by the job count, its frames in a
        reused buffer out of the engine's seal pool), and one by one
        (batch_codec.build_output_batch) what that crossing left alone, or
        everything where there is no such crossing (no native library, no
        libzstd, an output codec other than zstd / none). A per-job failure
        comes back AS the exception instance (the caller owns the script
        error policy)."""
        if not jobs:
            return []

        def seal_one(src, payload, kept):
            try:
                return batch_codec.build_output_batch(
                    src, payload, kept,
                    compress_threshold=self._compress_threshold,
                    codec=self._output_codec,
                )
            except Exception as exc:  # pandalint: disable=EXC901 -- delivered as a value to the ErrorPolicy boundary (note_failure("rebuild") classifies it there)
                return exc

        t0 = _stage_t0("t_seal")
        try:
            many = batch_codec.build_output_batches(
                jobs, compress_threshold=self._compress_threshold,
                codec=self._output_codec, pool=self._seal_pool,
            )
        except Exception:  # pandalint: disable=EXC901 -- not swallowed: every job goes through build_output_batch below, and the one at fault comes back as its own exception instance for the ErrorPolicy boundary
            many = None
        if many is None:
            many = itertools.repeat(batch_codec.UNSEALED)
        out = []
        n_batches = n_one_by_one = 0
        for job, b in zip(jobs, many):
            if b is batch_codec.UNSEALED:
                b = seal_one(*job)
                n_one_by_one += isinstance(b, RecordBatch)
            n_batches += isinstance(b, RecordBatch)
            out.append(b)
        self._stat_stage("t_seal", t0)
        # batches over crossings: a launch's batch count where the many
        # form served it, 1.0 on the per-batch road
        self._stat_add("n_sealed_batches", float(n_batches))
        self._stat_add(
            "n_seal_crossings", float(n_one_by_one + (n_batches > n_one_by_one))
        )
        return out

    def _try_device_leg(
        self, domain: str, leg, programs: list[tuple] | None = None
    ):
        """One device leg under the engine's fault envelope: the DOMAIN's
        per-attempt deadline (adaptive, governor-derived) + bounded retry
        (faults.retry_call), classified failure accounting, and a failure
        verdict on the DOMAIN's breaker at exhaustion. Returns the leg's
        value, or None after exhausted retries — the call site supplies
        its exact host fallback and, where the leg's success IS the device
        verdict (harvest/fetch legs), records the success. Every leg
        returns an array, so None is an unambiguous sentinel. This is THE
        shape of a fault-tolerant device interaction; keeping it in one
        place keeps the breaker verdicts exhaustive.

        ``programs``: dispatch legs name the device programs they launch,
        each as ``((script_id, lane, n_pad[, stride]), fn)``. The first
        leg of a program traces and compiles it, so it runs under
        _COMPILE_DEADLINE_S, alone (a concurrent launch reaching the same
        program waits for it rather than compile it again), and its wall
        time is ``t_compile``, not a deadline sample; one unmet program
        makes the leg a first run. Every successful dispatch leg is one
        ``n_device_launches``, however many programs it runs (a payload
        launch staged in k parts runs k). ``fn``: the jitted function
        the leg calls, where scripts of one spec share it (the payload
        lane's pipelines are cached by spec and stride; None elsewhere): a
        script whose function another script already ran at this row
        bucket, or the ladder built, inherits that program, and its launch
        is no first run.
        """
        if programs:
            with self._stats_lock:
                for key, fn in programs:
                    if key not in self._compiled and (fn, key[2]) in self._ran_fns:
                        self._compiled[key] = 0.0
                known = all(key in self._compiled for key, _fn in programs)
                unresolved = self._device is None
            if unresolved:
                self.resolve_device()
            if not known:
                with self._compile_lock:
                    with self._stats_lock:
                        known = all(key in self._compiled for key, _fn in programs)
                    if not known:
                        return self._device_leg(domain, leg, programs, True)
        return self._device_leg(domain, leg, programs, False)

    def _device_leg(self, domain: str, leg, programs, first_run: bool):
        """_try_device_leg's envelope. Each SUCCESSFUL steady-state
        attempt's wall time feeds the governor's success-only device-leg
        histogram — the adaptive-deadline source. The timing wraps the leg
        itself, so a failed or abandoned attempt records nothing (a wedge
        that completes late on its abandoned worker still records its true
        wall time — an honest, rare completion, not a timeout artifact)."""
        gov = self.governor
        policy = gov.policy_for(domain)
        if first_run:
            policy = dataclasses.replace(
                policy, deadline_s=max(policy.deadline_s, _COMPILE_DEADLINE_S)
            )

        first_run_s = 0.0

        def timed_leg():
            nonlocal first_run_s
            t0 = time.perf_counter()
            out = leg()
            dt = time.perf_counter() - t0
            if first_run:
                first_run_s = dt
                self._stat_add("t_compile", dt)
                self._stat_add("n_compiles", 1.0)
            else:
                gov.observe_leg(domain, dt)
            return out

        try:
            out = faults.retry_call(
                timed_leg, policy, domain, count=self._stat_add,
            )
        except Exception as exc:
            faults.note_failure(domain, exc, reraise_programming=True)
            gov.breaker_for(domain).record_failure()
            return None
        if programs:
            self._stat_add("n_device_launches", 1.0)
            with self._stats_lock:
                for key, fn in programs:
                    # the launch's first-run seconds go to the programs it
                    # was the first to meet
                    self._compiled.setdefault(key, first_run_s)
                    if fn is not None:
                        self._ran_fns.add((fn, key[2]))
                self._device_launches[programs[0][0][0]] += 1
        return out

    def heartbeat(self) -> int:
        """Returns the number of registered scripts (liveness probe)."""
        return len(self._handles)

    @property
    def scripts(self) -> dict[int, ScriptHandle]:
        return dict(self._handles)

    # ------------------------------------------------------------ data path
    def process_batch(self, req: ProcessBatchRequest) -> ProcessBatchReply:
        """Synchronous wrapper: one submit, one harvest."""
        return self.submit(req).result()

    def submit(self, req: ProcessBatchRequest) -> Ticket:
        t_run = _call_t0()
        try:
            ticket = self.submit_group([req])[0]
        finally:
            # a shed or failed submit has its time too (and no ticket)
            t_done = self._call_done("t_submit", t_run)
        ticket.worker_clock = (t_run, t_done)
        return ticket

    def submit_group(self, reqs: list[ProcessBatchRequest]) -> list[Ticket]:
        """Fuse many requests into ONE launch per script.

        All records of all requests targeting a script are packed into a
        single staging array: one H2D transfer, one device program, one
        async D2H — the round-trip cost of the device link is paid once per
        group instead of once per request.

        Admission (resource_mgmt budget plane): every request's payload
        bytes reserve from the 'coproc' account BEFORE anything dispatches,
        all-or-nothing per group — a shed submit raises ``ShedError``
        having dispatched NOTHING (shed-before-ack: no offsets move, no
        materialized write can exist). Reserved bytes release when each
        ticket harvests, or here on any submit-path exception.
        """
        admitted: list[int] = []
        if self._admission is not None:
            ctrl = self._admission
            for req in reqs:
                nbytes = sum(
                    len(b.payload) for item in req.items for b in item.batches
                )
                reserved, retry_ms = ctrl.try_admit(nbytes)
                if nbytes > 0 and reserved == 0:
                    for r in admitted:
                        ctrl.release(r)
                    acct = ctrl.account
                    self.governor.note_shed(
                        "coproc", retry_ms,
                        {"requested_bytes": nbytes, "held_bytes": acct.held,
                         "limit_bytes": acct.limit},
                    )
                    self._stat_add("n_shed_submits", 1.0)
                    raise rm_admission.ShedError(
                        "coproc", retry_ms, f"{nbytes} staged bytes"
                    )
                admitted.append(reserved)
            if any(admitted):
                # a zero-byte submit is not evidence the account recovered
                self.governor.note_admitted("coproc")
        try:
            return self._submit_group_admitted(reqs, admitted)
        except BaseException:
            # nothing was handed back: the caller cannot harvest, so the
            # reservations must not outlive the failed submit
            if self._admission is not None:
                for r in admitted:
                    self._admission.release(r)
            raise

    def _submit_group_admitted(
        self, reqs: list[ProcessBatchRequest], admitted: list[int]
    ) -> list[Ticket]:
        tickets = [Ticket(self) for _ in reqs]
        for t, r in zip(tickets, admitted):
            t._admitted = r
        # script_id -> list of (ticket, slot_idx, item)
        by_script: dict[int, list[tuple]] = {}
        for ticket, req in zip(tickets, reqs):
            ticket.trace_id = req.trace_id
            for item in req.items:
                if item.script_id not in self._handles:
                    ticket._slots.append((_UNKNOWN, item, None, None))
                else:
                    slot_idx = len(ticket._slots)
                    ticket._slots.append(None)  # placeholder, filled below
                    by_script.setdefault(item.script_id, []).append(
                        (ticket, slot_idx, item)
                    )
        for script_id, entries in by_script.items():
            handle = self._handles[script_id]
            launch = _Launch(script_id, handle.policy)
            # a fused launch serves many requests; the first requester's
            # trace adopts it (the pacemaker submits one request per tick)
            launch.trace_id = entries[0][0].trace_id
            try:
                with stages.stage("coproc.dispatch", trace_id=launch.trace_id):
                    self._dispatch(script_id, launch, entries)
                ridx = 0
                for ticket, slot_idx, item in entries:
                    rng = list(range(ridx, ridx + len(item.batches)))
                    ridx += len(item.batches)
                    ticket._slots[slot_idx] = (_LAUNCHED, item, launch, rng)
            except Exception as exc:
                # classified: a dispatch blow-up emptying a launch's output
                # must never be invisible (a swallowed AttributeError here
                # once surfaced only as empty replies); programming errors
                # re-raise — the tick fails loudly and retries, instead of
                # the script silently dropping every record
                faults.note_failure("dispatch", exc, reraise_programming=True)
                if handle.policy == ErrorPolicy.deregister:
                    self.disable_coprocessors([script_id])
                    for ticket, slot_idx, item in entries:
                        ticket._slots[slot_idx] = (_DEREGISTERED, item, None, None)
                else:
                    for ticket, slot_idx, item in entries:
                        ticket._slots[slot_idx] = (_EMPTY, item, None, None)
        return tickets

    def _dispatch(self, script_id: int, launch: _Launch, entries: list[tuple]) -> None:
        """Explode all entries' records and issue the (async) device launch."""
        plan = self._plans[script_id]
        launch.engine = self
        launch.mode = plan.mode
        launch._plan = plan
        all_batches = [b for _, _, item in entries for b in item.batches]
        # Multi-chip lane (coproc/meshrunner.py): partition axis sharded
        # over the device mesh, per-device sub-launches, ONE SPMD
        # predicate program. Declines (single-device decision, open mesh
        # breaker, small launch) fall through to the standard path —
        # output is bit-identical either way, which is what the
        # test_meshrunner parity matrix pins.
        if plan.mode == "columnar" and self._meshrunner is not None:
            if self._dispatch_mesh(launch, plan, all_batches):
                return
        # Device-resident column cache: a repeat launch over an unchanged
        # batch window skips the WHOLE host ladder (decompress, parse,
        # find, extract) and — when the predicate ran on-device — the H2D
        # replay (the cached cols are device-resident). The key is
        # content-addressed (colcache.fingerprint), so an append produces
        # a clean miss by construction. (Mesh launches consult and
        # populate the cache PER SHARD inside their own lane, above.)
        store_key = None
        if (
            plan.mode == "columnar"
            and self._colcache is not None
            and all_batches
        ):
            key = (script_id, colcache.fingerprint(all_batches))
            entry = self._colcache.lookup(key)
            if entry is not None:
                self._count_colcache(True)
                self._dispatch_columnar_cached(launch, plan, entry)
                return
            self._count_colcache(False)
            store_key = key
        # the parse ladder is the plan's: nested paths and general
        # projections keep the staged one (ColumnarPlan.structural_eligible)
        structural = plan.mode == "columnar" and plan.structural_eligible()
        # the annotation takes the lane's first-choice name; a lane that
        # falls back closes under the stage that ran (histogram and ring)
        if plan.mode == "columnar":
            t0 = _stage_t0(
                "t_explode_find2" if structural else "t_explode_find"
            )
        else:
            t0 = _stage_t0(
                "t_explode_ptrs" if plan.mode == "payload" else "t_explode"
            )
        cache = None
        if plan.mode == "columnar":
            paths = plan.flat_paths()
            sp = None
            if structural:
                # STRUCTURAL fused lane: payload bytes cross the native
                # boundary once as a pointer table (no Python-side join;
                # the blob is built in-crossing only for passthrough
                # plans, whose zero-copy harvest gathers from it), parsed
                # by the two-stage structural-index kernel
                sp = batch_codec.explode_find_structural(
                    all_batches, paths, need_joined=plan.byte_identity,
                    count=self._count_uncompress,
                )
            if sp is not None:
                self._stat_stage("t_explode_find2", t0)
                self._note_parse_path("structural")
                launch.ranges = sp.ranges
                n = sp.n
                launch.n = n
                self._stat_add("n_records", n)
                self._stat_add("n_launches", 1)
                with self._stats_lock:
                    probes.coproc_launch_rows_hist.record(n)
                self._dispatch_columnar_fused(launch, plan, sp, store_key)
                return
            # STAGED lane: framing parse + k-path JSON walk in one scalar
            # native crossing (rp_explode_find) — the parity oracle, the
            # ladder of the plans the structural one cannot serve, and
            # where a library without the structural entry lands
            self._note_parse_path("staged")
            fused = batch_codec.explode_and_find(
                all_batches, paths, count=self._count_uncompress
            )
            if fused is not None:
                exploded, types, vs, ve = fused
                cache = plan.make_cache_from_tables(exploded, paths, types, vs, ve)
                self._stat_stage("t_explode_find", t0)
            else:
                exploded = batch_codec.explode_batches(
                    all_batches, count=self._count_uncompress
                )
                self._stat_stage("t_explode", t0)
        else:
            if plan.mode == "payload":
                # POINTER-TABLE staging lane (ROADMAP item 1 follow-on b):
                # record (offset, len) parse straight off the decompressed
                # per-batch payloads (compressed batches decompress into a
                # pooled buffer in one crossing; uncompressed ones are read
                # where they lie) and staging packs from the same bytes —
                # the joined blob (and its b"".join copy, plus
                # _pack_staged's second cache-cold pass over it) never
                # exists. Bit-identical to the classic lane (the
                # _pack_staged parity test pins it).
                pe = batch_codec.explode_ptrs(
                    all_batches, self._uncompress_pool, self._count_uncompress
                )
                if pe is not None:
                    self._stat_stage("t_explode_ptrs", t0)
                    launch.ranges = pe.ranges
                    n = len(pe.sizes)
                    launch.n = n
                    self._stat_add("n_records", n)
                    self._stat_add("n_launches", 1)
                    with self._stats_lock:
                        probes.coproc_launch_rows_hist.record(n)
                    self._dispatch_payload(launch, pe, n)
                    return
            exploded = batch_codec.explode_batches(
                all_batches, count=self._count_uncompress
            )
            self._stat_stage("t_explode", t0)
        launch.ranges = exploded.ranges
        n = len(exploded.sizes)
        launch.n = n
        self._stat_add("n_records", n)
        self._stat_add("n_launches", 1)
        with self._stats_lock:  # concurrent submits: HdrHist isn't thread-safe
            probes.coproc_launch_rows_hist.record(n)
        if plan.mode == "payload":
            self._dispatch_payload(launch, exploded, n)
        elif plan.mode == "columnar":
            self._dispatch_columnar(launch, plan, exploded, n, cache, store_key)
        else:  # host: materialized lazily at harvest
            launch._exploded = exploded

    def _note_parse_path(self, ladder: str) -> None:
        """``stats()["parse_path"]``: the parse ladder the last columnar
        launch ran."""
        with self._stats_lock:
            self._parse_path = ladder

    def _count_colcache(self, hit: bool) -> None:
        if hit:
            self._stat_add("n_colcache_hit", 1.0)
            probes.coproc_colcache_hits.inc()
        else:
            self._stat_add("n_colcache_miss", 1.0)
            probes.coproc_colcache_misses.inc()

    def _shard_cache_key(self, script_id: int, batches) -> tuple | None:
        """Per-shard column-cache key (cross-launch cache for the mesh
        lane, ROADMAP item 1 follow-on c): the SAME content fingerprint as
        the launch-wide key, over the shard's batch slice. Contiguous
        range shards of a repeating launch produce identical slices, so
        every shard of the second identical launch hits."""
        if self._colcache is None or not batches:
            return None
        return (script_id, colcache.fingerprint(batches))

    def _shard_cache_entry(
        self, shard: _HostShard, plan: ColumnarPlan, cols, n_pad: int,
        structural: bool,
    ) -> "colcache.Entry":
        """The per-shard cache entry for a just-run ladder."""
        return colcache.Entry(
            n=shard.n, n_pad=n_pad, ranges=shard.ranges, cols=cols,
            proj_data=shard.proj_data, proj_ok=shard.proj_ok,
            exploded=shard.exploded if plan.passthrough else None,
            parse_mode="structural" if structural else "staged",
        )

    def _shard_from_entry(
        self, shard: _HostShard, plan: ColumnarPlan, entry, n_pad: int
    ):
        """Fill a _HostShard from a cached per-shard entry (skips the
        whole host ladder) and return host predicate columns fitted to
        ``n_pad`` (entries cached under a different launch's row bucket
        pad/trim to this launch's — padding rows' bits are discarded, so
        the fit never changes output)."""
        shard.n = entry.n
        shard.ranges = list(entry.ranges)
        if plan.passthrough:
            shard.exploded = entry.exploded
            shard.proj_ok = np.ones(entry.n, dtype=bool)
        else:
            shard.proj_data = entry.proj_data
            shard.proj_ok = entry.proj_ok
        return _fit_cols(entry.cols, n_pad)

    def _shard_ladder(
        self, shard: _HostShard, plan: ColumnarPlan, batches, paths,
        structural: bool, n_pad: int | None = None,
        trace_id: int | None = None,
    ):
        """One shard's host parse/extract ladder (no predicate dispatch):
        explode + find (structural fused or staged), predicate column
        extraction, projection extraction. Fills ``shard`` and returns
        (cols, n_pad). ``n_pad`` pins the row bucket (the mesh path needs
        one COMMON bucket across every device shard so the stacked SPMD
        input has one shape); None buckets per shard. ``trace_id`` is the
        launch's, carried EXPLICITLY because shard ladders run on pool
        workers where no ambient trace is set."""

        def stage(key: str, t0: float) -> None:
            # shards run concurrently: summing their durations into the
            # launch-wall t_* keys would inflate those ~workers-fold, so
            # per-shard time lands under t_shard_* (CPU-seconds across
            # workers); the fan-out's wall time is t_mesh_ladder.
            # _stat_stage mirrors the slice into the pandapulse timeline
            # under the same t_shard_* name (one clock read, shared dt).
            dt = self._stat_stage(
                "t_shard_" + key[2:], t0, trace_id=trace_id
            )
            shard.stages[key] = round(shard.stages.get(key, 0.0) + dt, 6)

        t0 = _stage_t0(
            "t_shard_explode_find2" if structural and paths
            else "t_shard_explode_find"
        )
        cache = None
        cols = None
        fused_proj = None  # (proj_data, proj_ok) from the fused lane
        sp = (
            batch_codec.explode_find_structural(
                batches, paths, need_joined=plan.byte_identity
            )
            if structural and paths
            else None
        )
        if sp is not None:
            stage("t_explode_find2", t0)
            shard.ranges = sp.ranges
            n = sp.n
            shard.n = n
            if n == 0:
                shard.proj_ok = np.zeros(0, dtype=bool)
                return None, n_pad or 0
            # passthrough framing gathers from the joined blob the fused
            # crossing built; projection shards never need raw bytes again
            shard.exploded = sp.exploded() if plan.byte_identity else None
            t0 = _stage_t0("t_shard_fused_extract")
            if n_pad is None:
                n_pad = _bucket_rows(n)
            cols, proj_data, proj_ok = plan.extract_fused(sp, n_pad)
            stage("t_fused_extract", t0)
            fused_proj = (proj_data, proj_ok)
        else:
            fused = (
                batch_codec.explode_and_find(batches, paths) if paths else None
            )
            if fused is not None:
                ex, types, vs, ve = fused
                cache = plan.make_cache_from_tables(ex, paths, types, vs, ve)
                stage("t_explode_find", t0)
            else:
                ex = batch_codec.explode_batches(batches)
                stage("t_explode", t0)
            shard.exploded = ex
            shard.ranges = ex.ranges
            n = len(ex.sizes)
            shard.n = n
            if n == 0:
                shard.proj_ok = np.zeros(0, dtype=bool)
                return None, n_pad or 0
            if cache is None:
                t0 = _stage_t0("t_shard_find")
                cache = plan.build_find_cache(ex.joined, ex.offsets, ex.sizes)
                stage("t_find", t0)
            if plan.dev_cols:
                t0 = _stage_t0("t_shard_extract_pred")
                if n_pad is None:
                    n_pad = _bucket_rows(n)
                cols = plan.extract_device_inputs(
                    ex.joined, ex.offsets, ex.sizes, n_pad, cache
                )
                stage("t_extract_pred", t0)
        if plan.passthrough:
            shard.proj_ok = np.ones(n, dtype=bool)
        elif fused_proj is not None:
            # projection rows came out of the fused extraction crossing
            shard.proj_data, shard.proj_ok = fused_proj
        else:
            t0 = _stage_t0("t_shard_extract_proj")
            data, ok = plan.extract_projection(
                ex.joined, ex.offsets, ex.sizes, cache
            )
            shard.proj_data = data
            shard.proj_ok = ok
            shard.exploded = None  # framing reads proj_data, not raw records
            stage("t_extract_proj", t0)
        return cols, (n_pad or 0)

    # ------------------------------------------------------ mesh dispatch
    def _dispatch_mesh(self, launch: _Launch, plan, all_batches) -> bool:
        """The multi-chip lane (coproc/meshrunner.py): per-device
        sub-launches over the host-pool range shard, the predicate as ONE
        SPMD program over stacked [D, n_pad, ...] columns sharded on the
        mesh's partition axis, per-shard column-cache consult/populate.

        Returns False to send the launch down the standard single-device
        path: not mesh-eligible, sticky "single" decision, open
        mesh_dispatch breaker, or a launch too small to be worth an SPMD
        program. A mesh device-leg failure demotes THIS launch to the
        exact numpy predicate per shard — bit-identical output, and the
        breaker verdict routes later launches to the single-device path
        until the half-open probe re-admits the mesh."""
        runner = self._meshrunner
        if (
            runner is None
            or plan.mode != "columnar"
            or not plan.dev_cols
            or self._force_mode == "columnar_host"
        ):
            return False
        decision = runner.decision
        if decision == "single":
            return False
        counts = [b.header.record_count for b in all_batches]
        n = sum(counts)
        if n == 0 or len(all_batches) < 2:
            return False
        if decision is None and n < meshrunner.PROBE_MIN_ROWS:
            return False  # too small to probe on; single, without pinning
        if decision is None and runner.probe_lock_busy:
            # a sibling launch is mid-calibration (seconds of jit): its
            # maybe_calibrate would route this launch single anyway, so
            # bail BEFORE paying the whole per-shard mesh ladder only to
            # re-run it launch-wide down the standard path
            return False
        if (
            decision == "mesh"
            and runner.probe_enabled
            and n < meshrunner.PROBE_MIN_ROWS
        ):
            # steady-state floor for the MEASURED pin: a trickle launch
            # (flush tail after a calibrated win) isn't worth the stack/
            # device_put/SPMD overhead — the single path is strictly
            # cheaper below the probe's own representativeness floor. A
            # config-forced pin (probe=False) stays unconditional: the
            # operator asked for the mesh lane, full stop.
            return False
        mesh_breaker = self.governor.breaker_for(faults.MESH_DISPATCH)
        if not mesh_breaker.allow_device():
            runner.note_demotion()
            self.governor.record_mode(
                governor.MESH,
                "single",
                "mesh_dispatch breaker open: mesh launches demoted to the "
                "bit-identical single-device path",
                {"devices": runner.n_devices},
                key="path",
            )
            return False
        parts = runner.shard_ranges(counts)
        structural = plan.structural_eligible()
        self._note_parse_path("structural" if structural else "staged")
        paths = plan.flat_paths()
        # one COMMON row bucket across every device shard: the stacked
        # SPMD input is one [D, n_pad, ...] array per column
        n_pad = _bucket_rows(max(sum(counts[s:e]) for s, e in parts))
        t0 = _stage_t0("t_mesh_ladder")
        thunks = [
            (
                lambda d=d, s=s, e=e: self._run_mesh_shard(
                    d, launch, plan, all_batches[s:e], paths, structural,
                    n_pad,
                )
            )
            for d, (s, e) in enumerate(parts)
        ]
        pool = self._host_pool
        try:
            results = (
                pool.run(thunks)
                if pool is not None and len(thunks) >= 2
                else [t() for t in thunks]
            )
        except Exception as exc:
            # fail closed per-launch: a faulted shard worker degrades this
            # launch to the standard path, which re-executes every stage
            # launch-wide from the original batches (exact output)
            faults.note_failure(
                faults.SHARD_WORKER, exc, reraise_programming=True
            )
            return False
        self._stat_stage("t_mesh_ladder", t0)
        shards = [shard for shard, _ in results]
        shard_cols = [cols for _, cols in results]
        zeros = plan.zero_device_inputs(n_pad)
        n_arrays = len(zeros)
        stacked = []
        for i in range(n_arrays):
            blocks = [
                shard_cols[d][i]
                if d < len(shard_cols) and shard_cols[d] is not None
                else zeros[i]
                for d in range(runner.n_devices)
            ]
            stacked.append(np.stack(blocks))
        if decision is None:
            # the single-device baseline must see the rows the REAL single
            # path would launch — each shard trimmed to its true record
            # count, concatenated, padded to _bucket_rows(n) — not the
            # D * n_pad padded stack (which inflates t_single up to ~2x on
            # unbalanced shards and could pin "mesh" on a box where the
            # single path actually wins)
            n_flat = _bucket_rows(n)
            flat = []
            for i in range(n_arrays):
                parts_i = [
                    shard_cols[d][i][: shards[d].n]
                    for d in range(len(shards))
                    if shard_cols[d] is not None
                ]
                flat.append(
                    _fit_cols([np.concatenate(parts_i)], n_flat)[0]
                )
            decision = runner.maybe_calibrate(
                self.governor, plan, stacked, flat, n
            )
            if decision != "mesh":
                # the measured pin says single-device: this launch's ladder
                # re-runs down the standard path (a one-time cost per
                # engine — the sticky decision skips the mesh lane outright
                # from the next launch on)
                return False
        launch.r_out = plan.r_out
        t0 = _stage_t0("t_dispatch")

        def leg():
            faults.inject(faults.MESH_DISPATCH)
            fn = runner.predicate_fn(plan)
            args = runner.stack_and_put(stacked)
            mask = fn(*args)
            mask.copy_to_host_async()
            return mask

        mask = self._try_device_leg(
            faults.MESH_DISPATCH, leg,
            programs=[((launch.script_id, "mesh", n_pad), None)],
        )
        self._stat_stage("t_dispatch", t0)
        if mask is None:
            # exhausted mesh envelope: demote THIS launch to the exact
            # numpy predicate per shard (same columns, identical bits);
            # the breaker verdict (recorded by _try_device_leg) decides
            # whether the NEXT launch even tries the mesh
            runner.note_demotion()
            self._count_fallback(n)
            for shard, cols in zip(shards, shard_cols):
                if shard.n and cols is not None:
                    slot = _MaskSlot(shard.n)
                    slot.trace_id = launch.trace_id
                    slot._mask_np = plan.eval_host_mask(cols)
                    shard.mask = slot
        else:
            mesh_breaker.record_success()
            self._stat_add("bytes_h2d", sum(a.nbytes for a in stacked))
            self._stat_add("bytes_d2h", runner.n_devices * (n_pad // 8))
            for d, (shard, cols) in enumerate(zip(shards, shard_cols)):
                if shard.n == 0 or cols is None:
                    continue
                slot = _MaskSlot(shard.n)
                slot.trace_id = launch.trace_id
                # per-device block of the ONE sharded result; fetched
                # synchronously at harvest under the MASK_FETCH envelope
                # (no _mask_event -> _resolve_keep fetches directly), with
                # the exact numpy fallback over the retained columns
                slot._mask_dev = mask[d]
                slot._cols = cols
                shard.mask = slot
        launch._shards = shards
        ranges: list[tuple[int, int]] = []
        rec_base = 0
        for shard in shards:
            ranges.extend((a + rec_base, b + rec_base) for a, b in shard.ranges)
            rec_base += shard.n
        launch.ranges = ranges
        launch.n = rec_base
        if mask is not None:
            # mesh accounting only when the SPMD program actually ran:
            # a demoted launch (numpy per shard) must not journal a
            # healthy "mesh" posture or grow the mesh launch counters —
            # note_demotion above is its whole story
            runner.note_launch([shard.n for shard in shards])
            self.governor.record_mode(
                governor.MESH,
                "mesh",
                f"SPMD launch over the {runner.n_devices}-device mesh: "
                f"per-device sub-launches via the host-pool range shard, "
                f"one shard_map predicate program",
                {"devices": runner.n_devices, "rows": rec_base},
                key="path",
            )
            self._stat_add("n_mesh_launches", 1)
        self._stat_add("n_records", rec_base)
        self._stat_add("n_launches", 1)
        with self._stats_lock:
            probes.coproc_launch_rows_hist.record(rec_base)
            for shard in shards:
                probes.coproc_shard_rows_hist.record(shard.n)
            self.last_launch_shards = [
                {"rows": shard.n, **shard.stages} for shard in shards
            ]
        return True

    def _run_mesh_shard(
        self, d: int, launch: _Launch, plan: ColumnarPlan, batches, paths,
        structural: bool, n_pad: int,
    ) -> tuple[_HostShard, list | None]:
        """One mesh device's dispatch-side host ladder (pool worker or
        inline): per-shard column-cache consult, parse/extract with the
        LAUNCH-COMMON row bucket, projection extraction, cache populate.
        NO predicate dispatch — the predicate is one SPMD program over
        all shards, issued by _dispatch_mesh after the stack assembles.
        Touches only its own shard (SHD6xx)."""
        shard = _HostShard()
        t_shard0 = time.perf_counter()
        faults.inject(faults.SHARD_WORKER)
        key = self._shard_cache_key(launch.script_id, batches)
        entry = self._colcache.lookup(key) if key is not None else None
        if key is not None:
            self._count_colcache(entry is not None)
        if entry is not None:
            cols = self._shard_from_entry(shard, plan, entry, n_pad)
        else:
            cols, _ = self._shard_ladder(
                shard, plan, batches, paths, structural, n_pad=n_pad,
                trace_id=launch.trace_id,
            )
            if key is not None and shard.n and cols is not None:
                self._colcache.put(
                    key,
                    self._shard_cache_entry(
                        shard, plan, cols, n_pad, structural
                    ),
                )
        tracer.record(
            "coproc.mesh_shard",
            (time.perf_counter() - t_shard0) * 1e6,
            launch.trace_id,
            start_perf=t_shard0,
            shard=d,
            rows=shard.n,
        )
        return shard, cols

    def _dispatch_payload(self, launch: _Launch, exploded, n: int) -> None:
        """Stage and launch one payload plan over an exploded table: the
        classic joined-blob table, or the pointer table
        (batch_codec.PtrExploded), whose staging fills the matrix straight
        from the batches' retained decompressed payload buffers in one
        native crossing — byte-identical staged rows, one fewer full copy
        of the launch's record bytes. Either way the matrix comes from the
        staging pool. A launch whose result is the keep mask retains the
        table: its kept values are framed from it, and the pointer table's
        pooled decompress buffers go back when that framing is done
        (_Launch.framed); any other launch has read its last payload byte
        once the matrix is packed, and gives them back here."""
        # plan: which values fit, and the launch's parts by width class with
        # their row selections (passes over the sizes of every record: 5 ms
        # of a 186,000-row NEXmark launch on the chip's host, PR 52)
        t_plan = _stage_t0("t_plan")
        lane = self._lanes[launch.script_id]
        launch.r_out = lane.fns[self._row_stride][1]
        launch.fits = exploded.sizes <= self._row_stride
        retained = lane.mask_only
        if retained:
            launch._exploded = exploded
        if n == 0:
            if not retained:
                _release_exploded(exploded)
            self._stat_stage("t_plan", t_plan)
            return
        value_bytes = float((exploded.sizes * launch.fits).sum(dtype=np.int64))
        parts = self._plan_parts(lane, exploded.sizes, launch.fits, n, int(value_bytes))
        launch.r_out = lane.fns[parts[-1].stride][1]
        self._stat_stage("t_plan", t_plan, parts=len(parts))
        pack = (
            self._pack_staged_ptrs
            if isinstance(exploded, batch_codec.PtrExploded)
            else self._pack_staged
        )
        t0 = _stage_t0("t_pack")
        cut = False
        held = [n if part.rows is None else len(part.rows) for part in parts]
        for part, k in zip(parts, held):
            part.n_pad = _bucket_rows(k)
            part.program, part.bucket = self._program_for(part.fn, part.n_pad)
            if part.bucket < part.n_pad:
                # no program is ready at this part's bucket (it is over the
                # ladder's top, or the ladder is still building): cut to
                # the largest ready one, as many runs as hold its rows
                part.n_pad = -(-k // part.bucket) * part.bucket
                cut = True
            # ... or padded to a larger ready one (_Ladder._padded)
            part.n_pad = max(part.n_pad, part.bucket)
        # whether each part's matrix was a parked one. The larger matrix is
        # taken first: the pool hands out its smallest parked buffer that
        # is big enough, and the smaller part must not take the larger's
        parked: list[bool] = []
        for part in sorted(parts, key=lambda p: -p.n_pad * (p.stride + IN_META)):
            part.staged = pack(exploded, part.n_pad, part.stride, part.rows, parked)
        if all(parked):
            # a launch that paid no first touch of a fresh matrix
            self._stat_add("n_staging_reuses", 1.0)
        # the parts above _BODY_STRIDE: their rows, and what of their
        # matrices is value bytes (read before the table may go back)
        wide = [part for part in parts if part.stride > _BODY_STRIDE]
        wide_rows = sum(k for part, k in zip(parts, held) if part.stride > _BODY_STRIDE)
        wide_values = sum(
            int((exploded.sizes * launch.fits if part.rows is None
                 else exploded.sizes[part.rows]).sum(dtype=np.int64))
            for part in wide
        )
        if not retained:
            _release_exploded(exploded)
        self._stat_stage(
            "t_pack", t0, parts=len(parts),
            strides=[part.stride for part in parts], rows=held,
        )
        if cut:
            self._stat_add("n_launch_cuts", 1.0)
        self._stat_add("n_parts", float(len(parts)))
        if len(parts) > 1:
            self._stat_add("n_split_launches", 1.0)
        # what the staging matrices hold against what they are: record
        # bytes over rows x stride (a 100 B event in a 1,032 B row is
        # mostly zeros that still cross the link; in a 136 B row it is not)
        self._stat_add("bytes_staged", float(sum(p.staged.nbytes for p in parts)))
        self._stat_add("bytes_staged_values", value_bytes)
        if wide:
            self._stat_add("n_wide_rows", float(wide_rows))
            self._stat_add("bytes_staged_wide", float(sum(p.staged.nbytes for p in wide)))
            self._stat_add("bytes_staged_values_wide", float(wide_values))
        self._launch_payload(launch, parts)

    def _plan_parts(
        self, lane: _SpecPrograms, sizes: np.ndarray, fits: np.ndarray, n: int,
        nbytes: int,
    ) -> list[_Part]:
        """How a payload launch is staged, read off its own values: the
        histogram of the values over the width classes (_class_strides:
        128 B apart up to _BODY_STRIDE, doubling from there to the lane's
        ``row_stride``, which stays the limit a value is held to) and the
        parts _plan_cuts makes of it: ONE part at the stride that fits
        where one stride can, TWO (PR 47) where the rows up to
        _BODY_STRIDE fall in two far-apart classes, and a part a class
        above it where a tail of wider values pays for them. A value that
        is staged empty (null, empty, oversize) rides in the narrowest
        class. A (stride, row bucket) first seen here is left for
        ``rptpu-precompile`` to build and its rows ride in the next part up
        whose program is built (the widest in the narrowest READY stride
        the spec has shown, the lane's own at worst): nothing compiles on
        the serving path for it."""
        strides = _class_strides(self._row_stride)
        lo = int(np.searchsorted(strides, lane.min_stride))
        cls = np.where(fits, np.searchsorted(strides, sizes), 0)
        np.clip(cls, lo, None, out=cls)
        hist = np.bincount(cls, minlength=len(strides))
        cuts = _plan_cuts(hist, strides, lane.split_ok)
        rows = np.diff(np.cumsum(hist)[cuts], prepend=0).tolist()
        plan = [(c, int(strides[c]), k) for c, k in zip(cuts, rows)]
        # every part asked, so that every ladder starts at the first sight
        ready = [self._stride_ready(lane, stride, k, n, nbytes) for _c, stride, k in plan]
        if not all(ready):
            held, plan, waiting = plan, [], 0
            for (c, stride, k), ok in zip(held, ready):
                if waiting:
                    k += waiting
                    ok = self._stride_ready(lane, stride, k, n, nbytes, start=False)
                if ok:
                    plan.append((c, stride, k))
                waiting = 0 if ok else k
            if waiting:
                c, wide, _k = held[-1]
                stride = next(
                    st for st in sorted(s for s in lane.fns if s > wide)
                    if self._stride_ready(lane, st, waiting, n, nbytes, start=False)
                )
                plan.append((c, stride, waiting))
        if len(plan) == 1:
            stride = plan[0][1]
            return [_Part(stride, None, lane.at(stride)[0])]
        parts, below = [], -1
        for c, stride, _k in plan:
            parts.append(_Part(
                stride, np.flatnonzero((cls > below) & (cls <= c)), lane.at(stride)[0]
            ))
            below = c
        return parts

    def _stride_ready(
        self, lane: _SpecPrograms, stride: int, k: int, n: int, nbytes: int,
        start: bool = True,
    ) -> bool:
        """Whether a part ``stride`` wide that holds ``k`` of a launch's
        ``n`` rows (``nbytes`` of values in all) runs a built program. The
        lane's own stride always does (its ladder is built at deploy and a
        launch waits for it or is cut to it), and on an engine that builds
        no ladder every stride is a first run on the serving path, as the
        lane's own is. Otherwise the stride's (lazy) ladder answers,
        started the first time a launch shows the stride, and (``start``)
        a bucket it lacks is left for the builder: at once the one the
        same mix fills in the largest launch the read budget gives (so the
        launch knob's ramp finds its last step built), and the part's own
        where launches keep asking for it (_WANT_HOLD_S)."""
        own = self._row_stride
        if stride == own:
            return True
        fn = lane.at(stride)[0]
        with self._stats_lock:
            base = self._ladders.get(lane.fns[own][0])
            ladder = self._ladders.get(fn)
        top = self._ladder_top(lane.partitions, stride)
        if base is None or top is None:
            return True
        if ladder is None or ladder.top < top:
            if not start:
                return False
            self._start_ladder(fn, stride, top)
            with self._stats_lock:
                ladder = self._ladders[fn]
        ready = ladder.has(_bucket_rows(k), want=start, hold_s=_WANT_HOLD_S)
        if start:
            # the largest launch: the most rows a ladder is sized for where
            # the values are as dense as that (_LADDER_ROWS_PER_STRIDE), as
            # many fewer rows as these are wider (at least this launch's)
            rows_top = self._ladder_top(lane.partitions, _STRIDE_CLASS)
            budget = rows_top * min(own, _BODY_STRIDE) // _LADDER_ROWS_PER_STRIDE
            most = max(n, min(rows_top, budget * n // max(nbytes, 1)))
            ahead = ladder.has(_bucket_rows(k * most // n))
            # ... or a wanted bucket whose rows ride padded meanwhile
            if not (ready and ahead) or ladder.next_bucket() is not None:
                with self._stats_lock:
                    self._wake_precompiler()
        return ready

    def _program_for(self, fn, n_pad: int):
        """(program, row bucket) a payload part padded to ``n_pad`` rows
        runs: its ladder's program at that bucket; the largest ready one
        below it, which the part is cut to; or (None, n_pad) where no
        ladder serves ``fn`` (none was built, or its build failed): the
        jitted function itself, whose first call at a bucket is a first run
        on the serving path."""
        with self._stats_lock:
            ladder = self._ladders.get(fn)
        if ladder is None:
            return None, n_pad
        return ladder.program_for(n_pad, _COMPILE_DEADLINE_S)

    def _launch_payload(self, launch: _Launch, parts: list[_Part]) -> None:
        """Issue one payload-plan device launch over its built staging
        matrices (breaker gate, fault envelope, exact host fallback) —
        shared by the classic joined-blob and pointer-table staging
        lanes. One part, or several by width class: each its own H2D and its
        own program, all inside ONE leg, so a launch is one device launch
        and one verdict however it was staged. The result format follows
        the plan (_mask_result): the packed result matrix, fetched at
        harvest by _mat_payload, or the bit-packed keep mask, which rides
        the mask harvester and _resolve_keep like a columnar predicate's.

        When a staging matrix goes back to the pool (the ONE rule):
        ``jax.device_put`` returns long before the matrix has crossed the
        link, and JAX reads the numpy memory until it has. So the launch
        keeps its matrices until its device result has LANDED (the keep
        mask resolved in _gather_view, or the result matrix fetched in
        _mat_payload): the programs have then consumed their input, and
        _Launch._park_staged gives the matrices back. Whenever the host
        fallback runs instead (breaker open, retries exhausted, envelope
        timed out, harvest demoted), _payload_host_fallback drops them
        after reading them: a tried device leg may still be reading one,
        so none re-enters the pool. An abandoned launch's matrices go with
        the launch. Nothing reads the staged device arrays after the
        result has landed (on a backend whose device_put aliases numpy
        memory that is what makes reuse safe)."""
        import jax

        mask_result = self._mask_result(launch._plan)
        r_out = launch.r_out
        # retained until the result lands: the host fallback re-runs the
        # pipeline in numpy over exactly these rows
        launch._staged_parts = parts
        # what the lane adds to a launch: the rows it pads the buckets
        # with, and the values it drops for exceeding the staging row
        n_pad = sum(part.n_pad for part in parts)
        self._stat_add("n_staged_rows", float(n_pad))
        n_oversize = launch.n - int(np.count_nonzero(launch.fits))
        if n_oversize:
            self._stat_add("n_oversize_rows", float(n_oversize))
        t0 = _stage_t0("t_dispatch")

        def leg():
            faults.inject(faults.DEVICE_DISPATCH)
            # the leg runs on the fault envelope's worker: no ambient trace
            t_h2d = _stage_t0("t_h2d")
            dev = [
                [
                    jax.device_put(part.staged[i : i + part.bucket])
                    for i in range(0, part.n_pad, part.bucket)
                ]
                for part in parts
            ]
            self._stat_stage(
                "t_h2d", t_h2d, trace_id=launch.trace_id,
                strides=[part.stride for part in parts],
                rows=[part.n_pad for part in parts],
            )
            # a program built ahead of need, or the jitted function (whose
            # first call at a bucket traces and compiles); over the part's
            # rows in one run, or, a cut part, in runs of ``bucket`` rows
            results = [
                [(part.program or part.fn)(cut) for cut in cuts]
                for part, cuts in zip(parts, dev)
            ]
            for cuts in results:
                for result in cuts:
                    result.copy_to_host_async()
            # the staged device arrays ride on the launch until the fetch
            # has timed their H2D (_Launch._fetch_legs drops them). Set here
            # and not handed back beside the result: the envelope's worker
            # keeps what a leg returned until its next job ends, and a
            # 17.8 MB device array must not wait on that
            launch._staged_dev = dev
            if len(results) == 1 and len(results[0]) == 1:
                return results[0][0]
            return _PartsResult(
                results, [part.rows for part in parts] if len(parts) > 1 else None
            )

        packed = None
        if self._breaker.allow_device():
            packed = self._try_device_leg(
                faults.DEVICE_DISPATCH, leg,
                programs=[
                    ((launch.script_id, "payload", part.bucket, part.stride), part.fn)
                    for part in parts
                ],
            )
        if packed is None:
            # open breaker or exhausted retries: the exact host result, in
            # the slot its harvest reads as already on the host
            packed = launch._payload_host_fallback()
            if mask_result:
                launch._mask_np = packed
            else:
                launch._packed_dev = packed
            self._stat_stage("t_dispatch", t0)
            return
        # dispatch success IS the dispatch-domain verdict (the device
        # accepted the program); whether the RESULT comes back alive is
        # the harvest domain's verdict, recorded at fetch time
        self._breaker.record_success()
        self._stat_stage("t_dispatch", t0)
        self._stat_add("bytes_h2d", sum(part.staged.nbytes for part in parts))
        if mask_result:
            self._stat_add("bytes_d2h", n_pad // 8)
            self._enqueue_mask(launch, packed)
        else:
            self._stat_add("bytes_d2h", n_pad * (r_out + 8))
            launch._packed_dev = packed

    def _mask_result(self, plan) -> bool:
        """Whether a payload plan's device result is the keep mask alone:
        its mapper is the identity (read from the spec), and the gather
        harvest that frames from host bytes is on (``gather_frame=False``
        keeps the matrix road for everything)."""
        return self._gather_frame and plan.byte_identity

    def _enqueue_mask(self, slot, mask) -> None:
        """Hand a launch's dispatched device mask to the harvester thread,
        which pays its D2H round trip while the caller keeps doing host
        work."""
        slot._mask_dev = mask
        slot._mask_event = threading.Event()
        slot._mask_state = "queued"
        self._ensure_harvester()
        slot._enq_t = time.perf_counter()
        self._harvest_q.put(slot)

    def _dispatch_predicate(
        self, launch: _Launch, plan: ColumnarPlan, cols, n: int, n_pad: int,
        entry=None, dev_cols=None,
    ) -> None:
        """The columnar predicate leg over extracted columns — backend
        pick (measured probe), breaker gate, device dispatch or numpy
        eval, harvester enqueue. ONE copy shared by the staged, fused and
        cache-hit dispatch paths. ``entry``: a column-cache entry under
        construction — the device leg records its device-put arrays into
        it so later hits launch with zero H2D. ``dev_cols``: already
        device-resident arrays from a cache hit (no H2D accounting).
        ``cols`` are always the HOST arrays (probe + exact fallback)."""
        if not plan.dev_cols:
            return
        use_host = self._force_mode == "columnar_host"
        backend = TpuEngine.sticky_columnar_backend()
        if self._force_mode is None:
            if backend is None:
                if n_pad >= _PROBE_MIN_ROWS:
                    # double-checked under the probe RUN lock:
                    # concurrent first launches must not each pay the
                    # device probe (or tear the backend/probe-record
                    # pair) — the loser waits here and adopts the
                    # winner's pick. Readers never take this lock.
                    with TpuEngine._columnar_probe_run_lock:
                        if TpuEngine.sticky_columnar_backend() is None:
                            self._probe_columnar_backend(plan, cols)
                    backend = TpuEngine.sticky_columnar_backend()
                    use_host = backend == "host"
                else:
                    # too small to be representative of steady state:
                    # don't pin the process-wide choice on a trickle
                    # batch — numpy is the cheap safe pick at this size
                    use_host = True
            else:
                use_host = backend == "host"
        if backend is not None:
            # this engine runs the sticky process-wide pick (probed by
            # us just above, or inherited): posture only — the probe
            # that made the decision already journaled it
            self.governor.note_posture(
                governor.COLUMNAR_BACKEND, backend
            )
        breaker_demoted = False
        if not use_host and not self._breaker.allow_device():
            # open breaker: the whole launch stays on the exact numpy
            # predicate over the same columns — identical bits, no
            # device touch until the half-open probe re-admits it
            use_host = breaker_demoted = True
        t0 = _stage_t0("t_dispatch")
        if use_host:
            # measured-host predicate: SAME extracted columns, numpy —
            # what the probe (or the bench ablation) picked on this link
            launch._mask_np = plan.eval_host_mask(cols)
            self._stat_stage("t_dispatch", t0)
            if breaker_demoted:
                self._count_fallback(n)
        else:
            def leg():
                faults.inject(faults.DEVICE_DISPATCH)
                fn = plan.compile_device()
                args = dev_cols
                if args is None:
                    if entry is not None:
                        # explicit device_put so the cache entry owns
                        # committed device arrays: later hits pass them
                        # straight back to the jitted predicate and no
                        # byte re-crosses the link
                        import jax

                        args = [jax.device_put(c) for c in cols]
                        entry.cols_dev = args
                    else:
                        args = cols
                mask = fn(*args)
                mask.copy_to_host_async()
                return mask

            mask = self._try_device_leg(
                faults.DEVICE_DISPATCH, leg,
                programs=[((launch.script_id, "predicate", n_pad), None)],
            )
            if mask is None:
                launch._mask_np = plan.eval_host_mask(cols)
                self._stat_stage("t_dispatch", t0)
                self._count_fallback(n)
            else:
                self._breaker.record_success()  # dispatch-domain verdict
                self._stat_stage("t_dispatch", t0)
                if dev_cols is None:
                    self._stat_add("bytes_h2d", sum(c.nbytes for c in cols))
                self._stat_add("bytes_d2h", n_pad // 8)
                launch._cols = cols
                self._enqueue_mask(launch, mask)

    def _dispatch_columnar(
        self, launch: _Launch, plan: ColumnarPlan, exploded, n: int,
        cache=None, store_key=None,
    ) -> None:
        launch.r_out = plan.r_out
        if n == 0:
            launch._proj_ok = np.zeros(0, bool)
            return
        if cache is None:
            # split path (fused explode_find unavailable): ONE JSON walk
            # per record locates every referenced top-level field
            # (rp_find_multi); extraction gathers from the span tables
            t0 = _stage_t0("t_find")
            cache = plan.build_find_cache(
                exploded.joined, exploded.offsets, exploded.sizes
            )
            self._stat_stage("t_find", t0)
        entry = None
        cols = None
        n_pad = _bucket_rows(n)
        if plan.dev_cols:
            t0 = _stage_t0("t_extract_pred")
            cols = plan.extract_device_inputs(
                exploded.joined, exploded.offsets, exploded.sizes, n_pad, cache
            )
            self._stat_stage("t_extract_pred", t0)
            if store_key is not None and self._colcache is not None:
                entry = colcache.Entry(
                    n=n, n_pad=n_pad, ranges=launch.ranges, cols=cols,
                    exploded=exploded if plan.passthrough else None,
                    parse_mode="staged",
                )
            self._dispatch_predicate(launch, plan, cols, n, n_pad, entry=entry)
        # Projection extraction overlaps the device launch.
        t0 = _stage_t0("t_extract_proj")
        if plan.passthrough:
            launch._proj_ok = np.ones(n, bool)
            launch._exploded = exploded
        else:
            data, ok = plan.extract_projection(
                exploded.joined, exploded.offsets, exploded.sizes, cache
            )
            launch._proj_data = data
            launch._proj_ok = ok
            if entry is not None:
                entry.proj_data = data
                entry.proj_ok = ok
                entry.nbytes = entry._measure()
        self._stat_stage("t_extract_proj", t0)
        if entry is not None:
            self._colcache.put(store_key, entry)

    def _dispatch_columnar_fused(
        self, launch: _Launch, plan: ColumnarPlan, sp, store_key=None
    ) -> None:
        """Structural fused lane: ONE record-major extraction crossing off
        the span tables the structural parse produced — predicate columns
        and packed projection rows together; the separate
        t_extract_pred/t_extract_proj passes don't exist on this path."""
        n = sp.n
        launch.r_out = plan.r_out
        if n == 0:
            launch._proj_ok = np.zeros(0, bool)
            return
        t0 = _stage_t0("t_fused_extract")
        n_pad = _bucket_rows(n)
        cols, proj_data, proj_ok = plan.extract_fused(sp, n_pad)
        self._stat_stage("t_fused_extract", t0)
        ex = sp.exploded() if plan.passthrough else None
        if plan.passthrough:
            launch._proj_ok = np.ones(n, bool)
            launch._exploded = ex
        else:
            launch._proj_data = proj_data
            launch._proj_ok = proj_ok
        entry = None
        if store_key is not None and self._colcache is not None:
            entry = colcache.Entry(
                n=n, n_pad=n_pad, ranges=launch.ranges, cols=cols,
                proj_data=proj_data, proj_ok=launch._proj_ok, exploded=ex,
                parse_mode="structural",
            )
        self._dispatch_predicate(launch, plan, cols, n, n_pad, entry=entry)
        if entry is not None:
            self._colcache.put(store_key, entry)

    def _dispatch_columnar_cached(
        self, launch: _Launch, plan: ColumnarPlan, entry
    ) -> None:
        """Column-cache hit: every host dispatch stage (decompress, parse,
        find, extract) is skipped, and a device-backed predicate launches
        over the cached DEVICE-RESIDENT columns — zero H2D. Output is
        bit-identical to a cold run because the predicate and projection
        consume the exact arrays the cold launch produced (entries are
        read-only after put)."""
        n = entry.n
        launch.ranges = list(entry.ranges)
        launch.n = n
        launch.r_out = plan.r_out
        self._stat_add("n_records", n)
        self._stat_add("n_launches", 1)
        with self._stats_lock:
            probes.coproc_launch_rows_hist.record(n)
        if n == 0:
            launch._proj_ok = np.zeros(0, bool)
            return
        if plan.passthrough:
            launch._proj_ok = np.ones(n, bool)
            launch._exploded = entry.exploded
        else:
            launch._proj_data = entry.proj_data
            launch._proj_ok = entry.proj_ok
        self._dispatch_predicate(
            launch, plan, entry.cols, n, entry.n_pad,
            dev_cols=entry.cols_dev,
        )

    def _probe_columnar_backend(self, plan, cols) -> None:
        """One-time process-wide probe: run the SAME predicate over the SAME
        columns on the device (compile + fetch warmup, then a timed
        launch+fetch) and in numpy; keep the faster. The device leg runs on
        the shared abandonable fetch pool (coproc/faults.py) with a deadline
        because a wedged device link HANGS inside the fetch rather than
        raising — on timeout (or no device / compile error) the probe falls
        back to host. A wedged worker is abandoned; one that merely finishes
        LATE discards its stale timing and rejoins the pool, so repeated
        probes cannot grow threads."""
        import time as _t

        t0 = _t.perf_counter()
        plan.eval_host_mask(cols)
        t_host = _t.perf_counter() - t0

        def _device_leg() -> float:
            fn = plan.compile_device()
            np.asarray(fn(*cols))  # compile + first-launch warmup
            t1 = _t.perf_counter()
            np.asarray(fn(*cols))  # steady-state launch + fetch
            return _t.perf_counter() - t1

        device_error = None
        try:
            t_dev = faults.fetch_with_deadline(
                _device_leg, _PROBE_DEVICE_TIMEOUT_S
            )
        except Exception as exc:
            # wedged (deadline) / no device / compile error: host wins the
            # probe, and the reason lands in coproc_failures_total and in
            # the probe record
            faults.note_failure("columnar_probe", exc)
            device_error = f"{faults.kind_of(exc)}: {exc}"
            t_dev = float("inf")
        chosen = "device" if t_dev * _PROBE_DEVICE_MARGIN < t_host else "host"
        # the two-field publish is the only region under the SHORT field
        # lock — readers (stats, dispatch snapshots) contend with a dict
        # assignment, never with the 120s probe envelope above
        with TpuEngine._columnar_probe_lock:
            TpuEngine._columnar_backend = chosen
            TpuEngine._columnar_probe = {
                "t_host_s": round(t_host, 6),
                "t_device_s": round(t_dev, 6) if t_dev != float("inf") else None,
                "device_error": device_error,
                "margin": _PROBE_DEVICE_MARGIN,
                "chosen": chosen,
            }
        self.governor.record(
            governor.COLUMNAR_BACKEND,
            chosen,
            "measured predicate leg: host "
            f"{t_host * 1e3:.3f} ms vs device "
            + (f"unavailable ({device_error})" if device_error
               else f"{t_dev * 1e3:.3f} ms")
            + f" (device must win {_PROBE_DEVICE_MARGIN}x; process-sticky)",
            dict(TpuEngine._columnar_probe),
        )

    def _take_staging(self, n_pad: int, stride: int, parked: list) -> np.ndarray:
        """A [n_pad, stride + IN_META] staging matrix out of the pool,
        holding anything: a parked one when one is big enough, else a new
        one. Which it was is appended to ``parked`` (_dispatch_payload
        counts a launch whose every matrix was a parked one in
        ``n_staging_reuses``). _Launch._park_staged gives it back (its
        ``.base`` is the pool's buffer)."""
        stride += IN_META
        # asked for by the row bucket, whatever the rows staged: a cut
        # launch (k parts of a smaller bucket) takes and parks a buffer of
        # its uncut size, so the pool's few slots hold the buckets a ramp
        # walks and never fill up with sizes no later launch can use
        buf, reused = self._staging.take(_bucket_rows(n_pad) * stride)
        parked.append(reused)
        return buf[: n_pad * stride].reshape(n_pad, stride)

    def _pack_staged(
        self, exploded, n_pad: int, stride: int, rows: np.ndarray | None,
        parked: list,
    ) -> np.ndarray:
        """[n_pad, stride + IN_META] uint8: record bytes then LE32 length,
        of the launch's rows (``rows`` None) or of ``rows`` (one part of a
        launch staged by width class).

        Records wider than the staging row cannot be transformed faithfully:
        their length is staged as 0 here and their keep bit is cleared after
        the launch via ``launch.fits`` (the reference bounds record size
        upstream via coproc_max_batch_size; truncating would corrupt data
        silently). A part narrower than the lane's row_stride holds no
        fitting value wider than itself (_plan_parts), so what it stages
        as 0 is what the full-width matrix would.
        """
        r = stride
        sizes, offsets = exploded.sizes, exploded.offsets
        if rows is not None:
            sizes, offsets = sizes[rows], offsets[rows]
        n = len(sizes)
        staged = self._take_staging(n_pad, r, parked)
        try:
            from redpanda_tpu.native import lib
        except Exception:
            lib = None
        if lib is not None:
            lib.pack_rows_into(exploded.joined, offsets, sizes, staged[:n])
        else:
            from redpanda_tpu.ops.packing import pack_rows

            vals = [
                exploded.joined[o : o + s]
                for o, s in zip(offsets, np.minimum(sizes, r))
            ]
            staged[:n, :r] = pack_rows(vals, r)[0]
        staged[n:] = 0
        lens = np.where(sizes <= r, sizes, 0).astype("<i4")
        staged[:n, r : r + 4] = lens.view(np.uint8).reshape(n, 4)
        staged[:n, r + 4 :] = 0
        return staged

    def _pack_staged_ptrs(
        self, pe, n_pad: int, stride: int, rows: np.ndarray | None,
        parked: list,
    ) -> np.ndarray:
        """_pack_staged's pointer-table twin: the staging matrix fills
        straight from each batch's retained decompressed payload buffer
        (batch_codec.PtrExploded) in one native crossing — no joined blob
        is ever built or re-read. Byte-identical output to _pack_staged
        over the merged exploded table, into a fresh matrix or a reused
        one, at any stride and over any row selection (the staging parity
        test pins it)."""
        staged = self._take_staging(n_pad, stride, parked)
        batch_codec.pack_exploded_ptrs(pe, staged, stride, rows)
        return staged
