"""Where a scanning read's time goes, on the host it runs on.

A catch-up read as the pacemaker makes it (``Partition.make_reader`` over a
``DirectConsensus`` log, 256 KiB a read, a cursor from the read before)
through a ``LogManager`` with its 64 MB batch cache, over 64 logs whose
backlog is larger than the cache, so that every read scans: per read alone
and beside a thread spinning in Python (the interpreter lock contended, as
beside the engine's worker), against ``DiskLog.read`` on the same path.
Two shapes: 9 x ~31 KB batches a read (the 1 KB-record cells) and
8 x ~35 KB (NEXmark's). Then the pieces of one read in loops of their own:
the window's ``os.pread`` (the lock dropped, fresh memory), a ``pread``
that keeps the lock into a buffer that is reused, and what the scan does a
batch.

    python3 tools/read_split.py [--tree CHECKOUT] [--rounds 3]

``--tree`` imports ``redpanda_tpu`` from another checkout (the parent's),
so one command line reads both sides of a change on one machine. Host
clock only: nothing here touches a device.
"""

from __future__ import annotations

import argparse
import asyncio
import ctypes
import json
import os
import statistics
import sys
import tempfile
import time

from append_split import _Spinner, _summary  # the sibling tool's, one shape

PARTITIONS = 64
READ_BYTES = 256 * 1024
# batches a partition: 16 reads of either shape, 140-147 MB over the 64 logs
SHAPES = {"9x31KB": (31_000, 144), "8x35KB": (35_500, 128)}


def _batches(n: int, payload_bytes: int, seed: int):
    import random

    from redpanda_tpu.models.record import RecordBatch, RecordBatchHeader

    rng = random.Random(seed)
    out = []
    for i in range(n):
        payload = rng.randbytes(payload_bytes + 7 * (i % 9))
        hdr = RecordBatchHeader(
            attrs=0, last_offset_delta=31, first_timestamp=1_700_000_000_000 + i,
            max_timestamp=1_700_000_000_031 + i, record_count=32,
        )
        out.append(RecordBatch(hdr, payload).reseal())
    return out


async def _drain(readers, ends) -> tuple[list[int], list[int]]:
    """One pass over the backlog, a read a partition a round as the
    pacemaker's tick makes them: the reads' times and their batch counts."""
    times, counts = [], []
    nexts = [0] * len(readers)
    live = True
    while live:
        live = False
        for i, read in enumerate(readers):
            if nexts[i] > ends[i]:
                continue
            t0 = time.perf_counter_ns()
            got = await read(nexts[i], READ_BYTES, max_offset=ends[i])
            times.append(time.perf_counter_ns() - t0)
            counts.append(len(got))
            nexts[i] = got[-1].last_offset + 1
            live = True
    return times, counts


def _libc_pread(buffer_type):
    """libc's `pread` bound to keep the interpreter lock (`ctypes.PyDLL`),
    its buffer argument of `buffer_type`."""
    fn = ctypes.PyDLL(None).pread
    fn.restype = ctypes.c_ssize_t
    fn.argtypes = [ctypes.c_int, buffer_type, ctypes.c_size_t, ctypes.c_int64]
    return fn


def _pread_keep(cap: int = 1 << 20):
    """That `pread`, a buffer of `cap` bytes that is reused, and its address."""
    held = bytearray(cap)
    return _libc_pread(ctypes.c_void_p), held, ctypes.addressof(ctypes.c_char.from_buffer(held))


def _pread_lock_kept():
    """`os.pread`'s twin that never drops the interpreter lock: an
    uninitialized `bytes` (what `os.pread` itself allocates) filled by
    libc's `pread`. Candidate (ii) of ISSUE 48, for a window the page cache
    holds; a measuring stand-in, not program code."""
    new = ctypes.pythonapi.PyBytes_FromStringAndSize
    new.restype = ctypes.py_object
    new.argtypes = [ctypes.c_char_p, ctypes.c_ssize_t]
    fill = _libc_pread(ctypes.c_char_p)

    def pread(fd, n, pos):
        buf = new(None, n)
        got = fill(fd, buf, n, pos)
        if got < 0:
            raise OSError(ctypes.get_errno(), "pread")
        return buf if got == n else buf[:got]

    return pread


class _Os:
    """The `os` module with another `pread`, for the segment module."""

    def __init__(self, pread):
        self._pread = pread

    def __getattr__(self, name):
        return self._pread if name == "pread" else getattr(os, name)


async def _in_situ(readers, ends) -> dict:
    """One more pass with a clock around three calls of the read path, as
    the reads meet them (64 files in turn, the cache evicting): a read's
    mean share in the window's `os.pread` (fresh memory, the lock dropped),
    in `Segment.scan` (the `pread` included) and in the batch cache's puts."""
    from redpanda_tpu.storage import segment as segment_mod
    from redpanda_tpu.storage.log import DiskLog

    spent = {"pread": 0, "preads": 0, "pread_bytes": 0, "scan": 0, "cache_put": 0,
             "fresh_first": 0, "fresh_second": 0, "reused_first": 0, "reused_second": 0}
    real_pread, real_scan, real_put = os.pread, segment_mod.Segment.scan, DiskLog._cache_put
    # the same bytes once more into memory that is reused, the lock kept,
    # before the read's own `pread` on every other call and after it on the
    # rest: whichever comes first meets the page cache as the read does
    keep, held, addr = _pread_keep()

    def pread(fd, n, pos):
        first = spent["preads"] & 1
        if first:
            t0 = time.perf_counter_ns()
            keep(fd, addr, min(n, len(held)), pos)
            spent["reused_first"] += time.perf_counter_ns() - t0
        t0 = time.perf_counter_ns()
        got = real_pread(fd, n, pos)
        dt = time.perf_counter_ns() - t0
        spent["fresh_second" if first else "fresh_first"] += dt
        if not first:
            t0 = time.perf_counter_ns()
            keep(fd, addr, min(n, len(held)), pos)
            spent["reused_second"] += time.perf_counter_ns() - t0
        spent["pread"] += dt
        spent["preads"] += 1
        spent["pread_bytes"] += len(got)
        return got

    def scan(self, *args, **kwargs):
        t0 = time.perf_counter_ns()
        try:
            return real_scan(self, *args, **kwargs)
        finally:
            spent["scan"] += time.perf_counter_ns() - t0

    def cache_put(self, batch):
        t0 = time.perf_counter_ns()
        real_put(self, batch)
        spent["cache_put"] += time.perf_counter_ns() - t0

    segment_mod.os, segment_mod.Segment.scan, DiskLog._cache_put = _Os(pread), scan, cache_put
    try:
        times, _ = await _drain(readers, ends)
    finally:
        segment_mod.os, segment_mod.Segment.scan, DiskLog._cache_put = os, real_scan, real_put
    n = len(times)
    return {
        "read": sum(times) / n / 1e3,
        "scan": spent["scan"] / n / 1e3,
        "pread": spent["pread"] / n / 1e3,
        "cache_put": spent["cache_put"] / n / 1e3,
        "preads_per_read": spent["preads"] / n,
        "us_per_pread": spent["pread"] / max(spent["preads"], 1) / 1e3,
        "bytes_per_pread": spent["pread_bytes"] / max(spent["preads"], 1),
        # a `pread`, by what came first (half the calls each)
        "us_per_pread_fresh_memory_first": spent["fresh_first"] / max(spent["preads"] / 2, 1) / 1e3,
        "us_per_pread_reused_memory_first": spent["reused_first"] / max(spent["preads"] / 2, 1) / 1e3,
        "us_per_pread_fresh_memory_second": spent["fresh_second"] / max(spent["preads"] / 2, 1) / 1e3,
        "us_per_pread_reused_memory_second": spent["reused_second"] / max(spent["preads"] / 2, 1) / 1e3,
    }


def _loop_us(fn, reps: int = 2000) -> float:
    """Microseconds a call of `fn` costs."""
    fn()
    t0 = time.perf_counter_ns()
    for _ in range(reps):
        fn()
    return (time.perf_counter_ns() - t0) / reps / 1e3


def _both(fn, reps: int = 2000) -> dict:
    alone = _loop_us(fn, reps)
    with _Spinner():
        beside = _loop_us(fn, max(50, reps // 10))
    return {"alone_us": alone, "beside_spinner_us": beside}


def _pieces(log, n: int) -> dict:
    """One read's pieces over a window of `n` whole frames, each in a loop
    of its own; per window, not per batch."""
    from redpanda_tpu import native
    from redpanda_tpu.models.record import RecordBatch, RecordBatchHeader
    from redpanda_tpu.storage.batch_cache import BatchCache
    from redpanda_tpu.storage.readers_cache import ReadCursor, ReadersCache

    seg = log.segments[0]
    window = seg._pread(READ_BYTES + 64 * 1024, 0)
    fd = seg._rfile.fileno()
    size = seg.size_bytes
    state = {"pos": 0}

    def pos() -> int:
        state["pos"] = (state["pos"] + READ_BYTES) % max(size - 2 * READ_BYTES, 1)
        return state["pos"]

    # a 256 KiB request reads the file 512 KiB at a time (`scan`'s chunk rule)
    chunk = 2 * READ_BYTES
    out = {"pread_fresh_lock_dropped": _both(lambda: os.pread(fd, chunk, pos()))}
    keep, held, addr = _pread_keep(chunk)
    out["preadv_reused_lock_dropped"] = _both(lambda: os.preadv(fd, [held], pos()))
    out["pread_reused_lock_kept"] = _both(lambda: keep(fd, addr, chunk, pos()))
    out["bytes_of_window_copy"] = _both(lambda: bytes(held))

    starts, at = [], 0
    for _ in range(n):
        starts.append(at)
        at += RecordBatch.peek_size(window, at)
    headers = [RecordBatchHeader.decode(window, s) for s in starts]
    out["peek_size"] = _both(lambda: [RecordBatch.peek_size(window, s) for s in starts])
    out["header_decode"] = _both(lambda: [RecordBatchHeader.decode(window, s) for s in starts])
    out["header_only_crc"] = _both(lambda: [h.internal_header_only_crc() for h in headers])
    out["payload_slice"] = _both(
        lambda: [window[s + 61 : s + h.size_bytes] for s, h in zip(starts, headers)]
    )
    out["decode_internal"] = _both(
        lambda: [RecordBatch.decode_internal(window, s) for s in starts]
    )
    batches = [RecordBatch.decode_internal(window, s)[0] for s in starts]
    cache = BatchCache(64 << 20)
    filler = _batches(1, 31_000, 9)[0]
    for k in range(2200):  # a full cache: every put evicts
        cache.put(-1, RecordBatch(RecordBatchHeader(
            0, filler.size_bytes, 32 * k, filler.header.type, 0, 0, 31), filler.payload))
    state["k"] = 0

    def cache_put():
        state["k"] += 1
        for b in batches:
            cache.put(state["k"], b)

    out["cache_put"] = _both(cache_put)
    rc = ReadersCache()
    cur = ReadCursor(0, 0, window, 0)

    def readers_cache():
        state["k"] += 1
        k = state["k"] & 63
        rc.get(k, 7)
        rc.put(k, 7, cur, consumed=7)

    out["readers_cache_get_put"] = _both(readers_cache)
    # the scan itself out of a window in hand (no file read): through the
    # crossing where the tree has it, and through the per-batch loop
    cursor = ReadCursor(seg.base_offset, 0, window, 0)
    last = batches[-1].last_offset

    def scan():
        return seg.scan(0, READ_BYTES, max_offset=last, cursor=cursor, read_ahead=True)

    def scan_one():  # a one-frame read: is a crossing worth making for it
        return seg.scan(0, 1, cursor=cursor, read_ahead=True)

    lib = native.lib
    if lib is not None and getattr(lib, "has_scan_internal_frames", False):
        out["scan_crossing_from_window"] = _both(scan)
        out["scan_crossing_one_frame"] = _both(scan_one)
        lib.has_scan_internal_frames = False
        try:
            out["scan_loop_from_window"] = _both(scan)
            out["scan_loop_one_frame"] = _both(scan_one)
        finally:
            lib.has_scan_internal_frames = True
    else:
        out["scan_loop_from_window"] = _both(scan)
        out["scan_loop_one_frame"] = _both(scan_one)
    return out


async def _shape(name: str, payload_bytes: int, per_partition: int, rounds: int, tmp: str) -> dict:
    from redpanda_tpu.cluster.partition import DirectConsensus, Partition
    from redpanda_tpu.models.fundamental import NTP
    from redpanda_tpu.storage.log import LogConfig
    from redpanda_tpu.storage.log_manager import LogManager

    mgr = LogManager(LogConfig(base_dir=os.path.join(tmp, name)))
    logs = [await mgr.manage(NTP("kafka", "t", p)) for p in range(PARTITIONS)]
    parts = [await Partition(log.ntp, DirectConsensus(log, 0), log).start() for log in logs]
    batches = _batches(9, payload_bytes, 1)
    for k in range(0, per_partition, 9):
        for log in logs:
            await log.append(batches[: min(9, per_partition - k)], term=0)
    for log in logs:
        await log.flush()
    ends = [log.offsets().dirty_offset for log in logs]
    roads = {
        "make_reader": [p.make_reader for p in parts],
        "disk_log_read": [log.read for log in logs],
    }
    out: dict = {"backlog_bytes": sum(s.size_bytes for log in logs for s in log.segments)}
    await _drain(roads["make_reader"], ends)  # warm: page cache, descriptors
    for road, readers in roads.items():
        alone: list[int] = []
        counts: list[int] = []
        rc0 = mgr.readers_cache.stats()
        for _ in range(rounds):
            t, c = await _drain(readers, ends)
            alone += t
            counts += c
        rc1 = mgr.readers_cache.stats()
        with _Spinner():
            beside, _ = await _drain(readers, ends)
        out[road] = {
            "alone": _summary(alone),
            "beside_spinner": _summary(beside),
            "batches_per_read": statistics.fmean(counts),
            # reads the batch cache did not serve (a scanning read asks the
            # readers cache for a cursor once), and the file reads they
            # made, over the reads made alone
            "scanned_share": (
                rc1["hits"] + rc1["misses"] - rc0["hits"] - rc0["misses"]
            ) / len(alone),
            "file_reads_per_read": (rc1["file_reads"] - rc0["file_reads"]) / len(alone),
        }
    # candidate (ii): the same reads with a `pread` that keeps the lock
    from redpanda_tpu.storage import segment as segment_mod

    segment_mod.os = _Os(_pread_lock_kept())
    try:
        alone = []
        for _ in range(rounds):
            alone += (await _drain(roads["make_reader"], ends))[0]
        with _Spinner():
            beside, _ = await _drain(roads["make_reader"], ends)
    finally:
        segment_mod.os = os
    out["make_reader.pread_lock_kept"] = {
        "alone": _summary(alone), "beside_spinner": _summary(beside),
    }
    out["in_situ_us_per_read"] = await _in_situ(roads["make_reader"], ends)
    out["pieces_us_per_window"] = _pieces(logs[0], round(out["make_reader"]["batches_per_read"]))
    for log in logs:
        await log.close()
    return out


async def _main(args) -> dict:
    if args.tree:
        sys.path.insert(0, os.path.abspath(args.tree))
    else:
        sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from redpanda_tpu import native

    lib = native.lib
    result = {
        "tree": os.path.abspath(args.tree or "."),
        "native": lib is not None,
        "crossing": bool(lib is not None and getattr(lib, "has_scan_internal_frames", False)),
        "cpus": os.cpu_count(),
        "switch_interval_s": sys.getswitchinterval(),
        "reads": {},
    }
    with tempfile.TemporaryDirectory(prefix="read_split_") as tmp:
        for name, (payload_bytes, per_partition) in SHAPES.items():
            result["reads"][name] = await _shape(
                name, payload_bytes, per_partition, args.rounds, tmp
            )
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--tree", help="checkout to import redpanda_tpu from (default: this one)")
    p.add_argument("--rounds", type=int, default=3, help="passes over the backlog, alone")
    args = p.parse_args(argv)
    print(json.dumps(asyncio.run(_main(args)), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
