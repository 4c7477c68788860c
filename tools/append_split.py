"""Where an offset-assigning append's time goes, on the host it runs on.

A materialized write as the pacemaker makes it (the Kafka CRC re-check and
``DiskLog.append`` of a partition's output batches) and a produce's append
(one batch, no re-check), through a ``LogManager`` with its 64 MB batch
cache, over 64 logs: per call alone, beside a thread spinning in Python
(the interpreter lock contended, as beside the engine's worker), and with
the framing crossing bound to drop the lock (``ctypes.CDLL``) beside the
tree's own binding, which keeps it, where the tree has the crossing. Then the pieces of one batch in loops of their own.

    python3 tools/append_split.py [--tree CHECKOUT] [--rounds 60]

``--tree`` imports ``redpanda_tpu`` from another checkout (the parent's),
so one command line reads both sides of a change on one machine. Host
clock only: nothing here touches a device.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import statistics
import sys
import tempfile
import threading
import time

PARTITIONS = 64


def _batches(n: int, payload_bytes: int, seed: int):
    import random

    from redpanda_tpu.models.record import RecordBatch, RecordBatchHeader

    rng = random.Random(seed)
    out = []
    for i in range(n):
        payload = rng.randbytes(payload_bytes + 17 * i)
        hdr = RecordBatchHeader(
            attrs=4, last_offset_delta=31, first_timestamp=1_700_000_000_000 + i,
            max_timestamp=1_700_000_000_031 + i, record_count=32,
        )
        out.append(RecordBatch(hdr, payload).reseal())
    return out


def _summary(ns: list[int]) -> dict:
    ns = sorted(ns)
    return {
        "mean_us": statistics.fmean(ns) / 1e3,
        "p50_us": ns[len(ns) // 2] / 1e3,
        "p95_us": ns[int(len(ns) * 0.95)] / 1e3,
        "calls": len(ns),
    }


class _Spinner:
    """A thread that holds the interpreter lock whenever it can."""

    def __enter__(self):
        self._stop = False
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()
        return self

    def _run(self):
        x = 0
        while not self._stop:
            x += 1

    def __exit__(self, *exc):
        self._stop = True
        self._thread.join()


async def _appends(logs, batches, rounds: int, verify: bool, has_verify_arg: bool) -> list[int]:
    out = []
    for _ in range(rounds):
        for log in logs:
            t0 = time.perf_counter_ns()
            if verify and not has_verify_arg:
                good = [b for b in batches if b.verify_kafka_crc()]
                await log.append(good, term=1)
            elif verify:
                await log.append(batches, term=1, verify_crc=True)
            else:
                await log.append(batches, term=1)
            out.append(time.perf_counter_ns() - t0)
    return out


def _loop_us(fn, n: int, reps: int = 2000) -> float:
    """Microseconds one of `n` batches costs in `fn`, which walks them all."""
    fn()
    t0 = time.perf_counter_ns()
    for _ in range(reps):
        fn()
    return (time.perf_counter_ns() - t0) / reps / n / 1e3


def _pieces(batches, base_dir: str) -> dict:
    """One batch's pieces, each in a loop of its own (a 9-batch list)."""
    from redpanda_tpu import native
    from redpanda_tpu.models.record import RecordBatch, RecordBatchHeader
    from redpanda_tpu.storage.batch_cache import BatchCache
    from redpanda_tpu.storage.log import DiskLog
    from redpanda_tpu.storage.segment import Segment

    n = len(batches)
    out = {
        "verify_kafka_crc": _loop_us(lambda: [b.verify_kafka_crc() for b in batches], n),
        "with_base_offset": _loop_us(lambda: [b.with_base_offset(7) for b in batches], n),
        "header_encode": _loop_us(lambda: [b.header.encode() for b in batches], n),
        "encode_internal": _loop_us(lambda: [b.encode_internal() for b in batches], n),
    }

    def build():
        for b in batches:
            h = b.header
            RecordBatch(
                RecordBatchHeader(
                    1, h.size_bytes, 7, h.type, h.crc, h.attrs, h.last_offset_delta,
                    h.first_timestamp, h.max_timestamp, h.producer_id,
                    h.producer_epoch, h.base_sequence, h.record_count, 1,
                ),
                b.payload,
            )

    out["record_batch_from_columns"] = _loop_us(build, n)
    os.makedirs(base_dir, exist_ok=True)
    seg = Segment(base_dir, 0, 1).create()

    def seg_append():
        for b in batches:
            seg.append(b)
        seg._buf.clear()  # keep the loop off the disk: the buffer's own cost

    out["segment_append"] = _loop_us(seg_append, n)
    if hasattr(seg, "track"):
        out["segment_track"] = _loop_us(lambda: [seg.track(b) for b in batches], n)
    cache = BatchCache(64 << 20)
    state = {"k": 0}

    def cache_put():
        state["k"] += 1
        for b in batches:
            cache.put(state["k"] & 1023, b)

    out["cache_put"] = _loop_us(cache_put, n)
    frame = getattr(DiskLog, "_frame", None)
    if frame is not None and native.lib is not None:
        out["frame_list"] = _loop_us(lambda: frame(batches, 7, True), n)
        heads = b"".join(b.header.encode() for b in batches)
        payloads = [b.payload for b in batches]
        nbytes = sum(b.size_bytes for b in batches)
        out["frame_crossing_alone"] = _loop_us(
            lambda: native.lib.frame_internal_many(heads, payloads, nbytes, 7, True), n
        )
    return out


async def _main(args) -> dict:
    if args.tree:
        sys.path.insert(0, os.path.abspath(args.tree))
    else:
        sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    import inspect

    from redpanda_tpu import native
    from redpanda_tpu.models.fundamental import NTP
    from redpanda_tpu.storage.log import DiskLog, LogConfig
    from redpanda_tpu.storage.log_manager import LogManager

    has_verify_arg = "verify_crc" in inspect.signature(DiskLog.append).parameters
    lib = native.lib
    has_crossing = lib is not None and getattr(lib, "has_frame_internal_many", False)
    result = {
        "tree": os.path.abspath(args.tree or "."),
        "native": lib is not None,
        "crossing": bool(has_crossing),
        "cpus": os.cpu_count(),
        "switch_interval_s": sys.getswitchinterval(),
        "appends": {},
    }
    # the crossing as the tree binds it (PyDLL: the lock kept), then bound
    # to drop the lock, as every other crossing of the library is
    bindings = ["as_bound", "CDLL"] if has_crossing else ["as_bound"]
    with tempfile.TemporaryDirectory(prefix="append_split_") as tmp:
        mgr = LogManager(LogConfig(base_dir=tmp))
        logs = [await mgr.manage(NTP("kafka", "t", p)) for p in range(PARTITIONS)]
        shapes = {
            "write_9x3KB": (_batches(9, 3072, 1), True),
            "write_13x1KB": (_batches(13, 1000, 2), True),
            "write_2x1KB": (_batches(2, 1000, 5), True),
            "produce_1x32KB": (_batches(1, 32768, 3), False),
            "produce_1x3KB": (_batches(1, 3072, 4), False),
        }
        for binding in bindings:
            if binding == "CDLL":
                fn = lib._dll.rp_frame_internal_many
                fn.restype = lib._frame_internal_many.restype
                fn.argtypes = lib._frame_internal_many.argtypes
                lib._frame_internal_many = fn
            for name, (batches, verify) in shapes.items():
                await _appends(logs, batches, 3, verify, has_verify_arg)  # warm
                alone = await _appends(logs, batches, args.rounds, verify, has_verify_arg)
                with _Spinner():
                    beside = await _appends(
                        logs, batches, max(2, args.rounds // 6), verify, has_verify_arg
                    )
                result["appends"][f"{name}.{binding}"] = {
                    "alone": _summary(alone),
                    "beside_spinner": _summary(beside),
                }
        result["pieces_us_per_batch_of_9x3KB"] = _pieces(
            shapes["write_9x3KB"][0], os.path.join(tmp, "pieces")
        )
        for log in logs:
            await log.close()
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--tree", help="checkout to import redpanda_tpu from (default: this one)")
    p.add_argument("--rounds", type=int, default=60, help="rounds over the 64 logs, alone")
    args = p.parse_args(argv)
    print(json.dumps(asyncio.run(_main(args)), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
