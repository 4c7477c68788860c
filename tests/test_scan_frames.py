"""A scanning read decodes its window in one native crossing
(storage/segment.py `Segment._walk_windows` over
`native.scan_internal_frames`), and a library without the entry takes the
per-frame loop (`_walk_frames`). Every case runs on both roads and is held
to a model of `Segment.scan`'s rules spelt out over the batches that were
appended: the same batches field for field, the same cursor, the same file
reads and the same exceptions.
"""

import random

import pytest

from redpanda_tpu import native
from redpanda_tpu.models.record import (
    INTERNAL_HEADER_SIZE,
    CorruptBatchError,
    RecordBatch,
    RecordBatchHeader,
    RecordBatchType,
)
from redpanda_tpu.observability import probes
from redpanda_tpu.storage import segment as segment_mod
from redpanda_tpu.storage.log import LogConfig
from redpanda_tpu.storage.log_manager import LogManager
from redpanda_tpu.storage.readers_cache import ReadCursor
from redpanda_tpu.storage.segment import Segment

HAS_CROSSING = native.lib is not None and getattr(
    native.lib, "has_scan_internal_frames", False
)
TERM = 7
TYPES = (
    RecordBatchType.raft_data,
    RecordBatchType.raft_configuration,
    RecordBatchType.checkpoint,
    RecordBatchType.archival_metadata,
)


@pytest.fixture(params=["crossing", "loop"])
def road(request, monkeypatch):
    """Both roads of `Segment.scan`; `loop` is a library without the entry."""
    if request.param == "crossing":
        if not HAS_CROSSING:
            pytest.skip("the native library has no rp_scan_internal_frames")
    elif native.lib is not None:
        monkeypatch.setattr(native.lib, "has_scan_internal_frames", False)
    return request.param


def _batch(rng, base, btype=RecordBatchType.raft_data, payload_len=None):
    n = rng.randrange(1, 40)
    payload = rng.randbytes(rng.randrange(0, 3000) if payload_len is None else payload_len)
    hdr = RecordBatchHeader(
        base_offset=base,
        type=btype,
        attrs=rng.choice((0, 4, 0x14, 0x7FFF)),
        last_offset_delta=n - 1,
        first_timestamp=rng.randrange(-1, 1 << 62),
        max_timestamp=rng.randrange(-1, 1 << 62),
        producer_id=rng.choice((-1, 1 << 40)),
        producer_epoch=rng.choice((-1, 3, -2)),
        base_sequence=rng.choice((-1, (1 << 31) - 1)),
        record_count=n,
    )
    return RecordBatch(hdr, payload).reseal()


def _segment(tmp_path, rng, n, *, types=TYPES, base=1000, payload_len=None):
    """A closed segment of `n` batches: (segment, batches, their file
    positions, the file's bytes)."""
    seg = Segment(str(tmp_path), base, TERM).create()
    batches, positions = [], []
    at, off = 0, base
    for _ in range(n):
        b = _batch(rng, off, rng.choice(types), payload_len)
        seg.append(b)
        batches.append(b)
        positions.append(at)
        at += b.size_bytes
        off = b.last_offset + 1
    seg.release_appender()
    with open(seg.data_path, "rb") as f:
        blob = f.read()
    return seg, batches, positions, blob


def _model(batches, positions, pos, start_offset, max_bytes, type_filter, max_offset):
    """`Segment.scan`'s rules over the appended batches, from file position
    `pos` on: the kept batches' indices and the position past the last."""
    kept, kept_end, taken = [], pos, 0
    for i, (b, at) in enumerate(zip(batches, positions)):
        if at < pos:
            continue
        if max_offset is not None and b.base_offset > max_offset:
            break
        if b.last_offset < start_offset:
            continue
        if type_filter is not None and b.header.type not in type_filter:
            continue
        kept.append(i)
        kept_end = at + b.size_bytes
        taken += b.size_bytes
        if taken >= max_bytes:
            break
    return kept, kept_end


def _same(got, want):
    """Field for field, the runtime term included, and the payload's type."""
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert vars(g.header) == {**vars(w.header), "term": TERM}
        assert type(g.header.type) is RecordBatchType
        assert type(g.payload) is bytes and g.payload == w.payload


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_random_runs_match_the_model(road, seed, tmp_path):
    rng = random.Random(seed)
    seg, batches, positions, blob = _segment(tmp_path, rng, 60)
    last = batches[-1].last_offset
    for _ in range(40):
        start = rng.randrange(batches[0].base_offset - 5, last + 5)
        max_bytes = rng.choice((1, 500, 4000, 20_000, 1 << 20))
        max_offset = rng.choice((None, rng.randrange(start - 3, last + 3)))
        type_filter = rng.choice((None, (TYPES[0],), TYPES[1:3], (TYPES[3], 40)))
        pos = seg.index.lookup(start)
        kept, kept_end = _model(batches, positions, pos, start, max_bytes, type_filter, max_offset)
        out, cursor, file_reads = seg.scan(
            start, max_bytes, type_filter=type_filter, max_offset=max_offset
        )
        _same(out, [batches[i] for i in kept])
        assert (cursor.segment_base, cursor.file_pos) == (seg.base_offset, kept_end)
        assert file_reads >= 1
        # what the cursor carries is the file's bytes where it says they lie
        assert cursor.window == blob[cursor.window_pos : cursor.window_pos + len(cursor.window)]
        # and a continuation from it reads on as a cold read would
        if kept and kept[-1] + 1 < len(batches):
            nxt = batches[kept[-1]].last_offset + 1
            more, c2, _ = seg.scan(nxt, 6000, cursor=cursor, read_ahead=True)
            k2, e2 = _model(batches, positions, kept_end, nxt, 6000, None, None)
            _same(more, [batches[i] for i in k2])
            assert c2.file_pos == e2


def test_start_offset_inside_a_batch_keeps_the_batch(road, tmp_path):
    rng = random.Random(11)
    seg, batches, positions, _ = _segment(tmp_path, rng, 8)
    b = next(x for x in batches[2:] if x.header.last_offset_delta >= 2)
    out, cursor, _ = seg.scan(b.base_offset + 1, 1)
    _same(out, [b])
    assert cursor.file_pos == positions[batches.index(b)] + b.size_bytes


def test_max_offset_leaves_the_frame_past_it_unconsumed(road, tmp_path):
    rng = random.Random(12)
    seg, batches, positions, _ = _segment(tmp_path, rng, 8)
    out, cursor, _ = seg.scan(batches[0].base_offset, 1 << 20, max_offset=batches[3].base_offset - 1)
    _same(out, batches[:3])
    assert cursor.file_pos == positions[3]
    # nothing in range at all: the cursor stays at the scan's start
    out, cursor, _ = seg.scan(batches[0].base_offset, 1 << 20, max_offset=batches[0].base_offset - 1)
    assert out == [] and cursor.file_pos == 0


def test_filtered_frames_after_the_last_kept_one_are_not_covered(road, tmp_path):
    rng = random.Random(13)
    seg = Segment(str(tmp_path), 0, TERM).create()
    kinds = [TYPES[0], TYPES[0], TYPES[1], TYPES[1], TYPES[0], TYPES[1], TYPES[1]]
    batches, positions, at, off = [], [], 0, 0
    for t in kinds:
        b = _batch(rng, off, t)
        seg.append(b)
        batches.append(b)
        positions.append(at)
        at += b.size_bytes
        off = b.last_offset + 1
    seg.release_appender()
    out, cursor, _ = seg.scan(0, 1 << 20, type_filter=(TYPES[0],))
    _same(out, [batches[0], batches[1], batches[4]])
    # the two filtered frames at the end were walked, and the cursor is not
    # past them: a continuation under another filter sees them
    assert cursor.file_pos == positions[5]
    more, c2, _ = seg.scan(batches[5].base_offset, 1 << 20, type_filter=(TYPES[1],), cursor=cursor)
    _same(more, batches[5:])
    assert c2.file_pos == at
    # every frame filtered: nothing kept, the cursor at the scan's start
    out, cursor, _ = seg.scan(0, 1 << 20, type_filter=(TYPES[2],))
    assert out == [] and cursor.file_pos == 0


def test_the_budget_ends_the_read_once_taken(road, tmp_path):
    rng = random.Random(14)
    seg, batches, positions, _ = _segment(tmp_path, rng, 12, payload_len=1000)
    size = batches[0].size_bytes
    for max_bytes, n in ((1, 1), (size, 1), (size + 1, 2), (3 * size, 3), (0, 1), (-5, 1)):
        out, cursor, _ = seg.scan(batches[0].base_offset, max_bytes)
        _same(out, batches[:n])
        assert cursor.file_pos == positions[n]


@pytest.mark.parametrize("cut", ["inside_a_header", "inside_a_payload", "at_a_boundary"])
def test_a_window_that_ends_short_is_read_anew(road, cut, tmp_path):
    rng = random.Random(15)
    seg, batches, positions, blob = _segment(tmp_path, rng, 10, payload_len=700)
    end = {
        "inside_a_header": positions[4] + 30,
        "inside_a_payload": positions[4] + INTERNAL_HEADER_SIZE + 100,
        "at_a_boundary": positions[4],
    }[cut]
    held = ReadCursor(seg.base_offset, positions[1], blob[positions[1] : end], positions[1])
    out, cursor, file_reads = seg.scan(batches[1].base_offset, 1 << 20, cursor=held, read_ahead=True)
    _same(out, batches[1:])
    assert cursor.file_pos == len(blob)
    # the window held three whole frames; the rest took one read of the
    # file from the cut frame's boundary and one that found its end
    assert file_reads == 2
    # a budget the window's whole frames fill: no file read at all
    out, cursor, file_reads = seg.scan(
        batches[1].base_offset, 3 * batches[1].size_bytes, cursor=held
    )
    _same(out, batches[1:4])
    assert file_reads == 0 and cursor.file_pos == positions[4]
    # a max_offset needs the next frame whole to know it lies past it
    out, cursor, file_reads = seg.scan(
        batches[1].base_offset, 1 << 20, cursor=held, max_offset=batches[3].last_offset
    )
    _same(out, batches[1:4])
    assert file_reads == 1 and cursor.file_pos == positions[4]


def _torn(seg, blob, mutate):
    data = bytearray(blob)
    mutate(data)
    with open(seg.data_path, "wb") as f:
        f.write(data)
    seg.size_bytes = len(data)
    seg.release_reader()


def test_a_flipped_header_byte_raises_header_crc_mismatch(road, tmp_path):
    rng = random.Random(16)
    seg, batches, positions, blob = _segment(tmp_path, rng, 6, types=TYPES[:1])

    def flip(data):
        data[positions[3] + 30] ^= 1

    _torn(seg, blob, flip)
    with pytest.raises(CorruptBatchError, match=rf"header_crc mismatch at offset {positions[3]}: "):
        seg.scan(batches[0].base_offset, 1 << 20)
    # a read that ends before the frame never meets it
    out, _, _ = seg.scan(batches[0].base_offset, sum(b.size_bytes for b in batches[:3]))
    _same(out, batches[:3])


def test_a_header_cut_at_eof_raises_partial_batch_header(road, tmp_path):
    rng = random.Random(17)
    seg, batches, positions, blob = _segment(tmp_path, rng, 5)
    _torn(seg, blob, lambda data: data.__delitem__(slice(positions[4] + 40, None)))
    with pytest.raises(
        CorruptBatchError, match=rf"partial batch header at EOF \(.* pos {positions[4]}\)"
    ):
        seg.scan(batches[0].base_offset, 1 << 20)


def test_a_payload_cut_at_eof_raises_frame_overruns(road, tmp_path):
    rng = random.Random(18)
    seg, batches, positions, blob = _segment(tmp_path, rng, 5, payload_len=400)
    _torn(seg, blob, lambda data: data.__delitem__(slice(len(data) - 7, None)))
    with pytest.raises(
        CorruptBatchError,
        match=rf"batch frame overruns EOF \(.* pos {positions[4]}, size_bytes={batches[4].size_bytes}\)",
    ):
        seg.scan(batches[0].base_offset, 1 << 20)


def test_an_unknown_type_and_a_short_size_raise_as_the_decoder_does(road, tmp_path):
    rng = random.Random(19)
    seg, batches, positions, blob = _segment(tmp_path, rng, 4, types=TYPES[:1])

    def retype(data):
        h = RecordBatchHeader.decode(data, positions[2])
        h.type = 77
        h.header_crc = h.internal_header_only_crc()
        data[positions[2] : positions[2] + INTERNAL_HEADER_SIZE] = h.encode()

    _torn(seg, blob, retype)
    with pytest.raises(ValueError, match="77 is not a valid RecordBatchType"):
        seg.scan(batches[0].base_offset, 1 << 20)

    def shrink(data):
        data[positions[2] + 4 : positions[2] + 8] = (60).to_bytes(4, "little")

    _torn(seg, blob, shrink)
    with pytest.raises(CorruptBatchError, match="header_crc mismatch"):
        seg.scan(batches[0].base_offset, 1 << 20)


def _samples(hist):
    return hist.hist.count, hist.hist.sum


def test_the_histogram_says_which_road_ran(road, tmp_path):
    rng = random.Random(20)
    seg, batches, _, _ = _segment(tmp_path, rng, 9, types=TYPES[:1])
    hist = probes.storage_read_crossing_batches_hist
    count0, sum0 = _samples(hist)
    out, _, _ = seg.scan(batches[0].base_offset, 1 << 20)
    assert len(out) == 9
    count1, sum1 = _samples(hist)
    assert sum1 - sum0 == 9
    # one crossing decoded the nine (the second found the file's end and
    # kept nothing: no sample); the loop samples 1 a batch
    assert count1 - count0 == (1 if road == "crossing" else 9)


def test_a_library_without_the_entry_takes_the_loop(tmp_path, monkeypatch):
    rng = random.Random(21)
    seg, batches, _, _ = _segment(tmp_path, rng, 6)
    calls = []
    if native.lib is None:
        pytest.skip("no native library: the loop is the only road")
    if HAS_CROSSING:
        real = native.lib.scan_internal_frames
        monkeypatch.setattr(
            native.lib, "scan_internal_frames", lambda *a: calls.append(a) or real(*a)
        )
        with_entry, _, _ = seg.scan(batches[0].base_offset, 1 << 20)
        assert calls
        del calls[:]
    monkeypatch.setattr(native.lib, "has_scan_internal_frames", False)
    out, cursor, _ = seg.scan(batches[0].base_offset, 1 << 20)
    assert not calls
    _same(out, batches)
    if HAS_CROSSING:
        _same(with_entry, batches)
    # and with no library at all
    monkeypatch.setattr(native, "lib", None)
    monkeypatch.setattr(segment_mod.native, "lib", None)
    out, _, _ = seg.scan(batches[0].base_offset, 1 << 20)
    _same(out, batches)


def test_more_frames_than_the_table_holds(road, tmp_path):
    rng = random.Random(22)
    seg, batches, positions, _ = _segment(tmp_path, rng, 150, payload_len=0, types=TYPES[:2])
    out, cursor, file_reads = seg.scan(batches[0].base_offset, 1 << 20, type_filter=(TYPES[0],))
    _same(out, [b for b in batches if b.header.type == TYPES[0]])
    last_kept = max(i for i, b in enumerate(batches) if b.header.type == TYPES[0])
    assert cursor.file_pos == positions[last_kept] + batches[last_kept].size_bytes


@pytest.mark.skipif(not HAS_CROSSING, reason="the native library has no rp_scan_internal_frames")
@pytest.mark.parametrize("seed", [31, 32, 33])
def test_sequential_reads_of_a_log_are_the_same_on_both_roads(seed, tmp_path, monkeypatch):
    """`DiskLog.read` from cursor to cursor over several segments: batches,
    cursors with their windows, and the readers cache's counters."""
    import asyncio

    from redpanda_tpu.models.fundamental import NTP

    async def drain(tag, crossing):
        monkeypatch.setattr(native.lib, "has_scan_internal_frames", crossing)
        rng = random.Random(seed)
        mgr = LogManager(
            LogConfig(base_dir=str(tmp_path / tag), max_segment_size=200_000),
            batch_cache_bytes=0,
        )
        log = await mgr.manage(NTP("kafka", "t", 0))
        for _ in range(12):
            await log.append(
                [_batch(rng, 0, rng.choice(TYPES[:2]), rng.randrange(100, 30_000)) for _ in range(9)],
                term=3,
            )
        await log.flush()
        seen, trail = [], []
        nxt, end = 0, log.offsets().dirty_offset
        while nxt <= end:
            got = await log.read(nxt, rng.choice((4096, 65_536, 262_144)), type_filter=(TYPES[0],))
            if not got:
                break
            seen += got
            nxt = got[-1].last_offset + 1
            cur = mgr.readers_cache.get(id(log), nxt)
            trail.append((nxt, cur and (cur.segment_base, cur.file_pos, cur.window_pos, cur.window)))
        stats = mgr.readers_cache.stats()
        await log.close()
        return seen, trail, stats

    async def both():
        return await drain("a", True), await drain("b", False)

    (seen_a, trail_a, stats_a), (seen_b, trail_b, stats_b) = asyncio.run(both())
    assert len(seen_a) > 20 and len(trail_a) > 5
    assert [(vars(b.header), b.payload) for b in seen_a] == [
        (vars(b.header), b.payload) for b in seen_b
    ]
    assert trail_a == trail_b
    assert stats_a == stats_b


@pytest.mark.skipif(not HAS_CROSSING, reason="the native library has no rp_scan_internal_frames")
@pytest.mark.parametrize("seed", range(40, 48))
def test_both_roads_agree_on_sound_and_torn_files(seed, tmp_path, monkeypatch):
    """Random segments, half of them torn (a flipped bit, a cut file, a
    rewritten size or type), scanned from random cursors whose windows end
    anywhere: the two roads give one outcome, batches, cursor with its
    window and file reads, or the exception's type and message."""
    rng = random.Random(seed)
    seg, batches, positions, blob = _segment(
        tmp_path, rng, rng.randrange(1, 40), payload_len=rng.choice((None, 0, 50, 5000))
    )
    if seed % 2:
        def tear(data):
            mode = rng.choice(("flip", "cut", "size", "type"))
            at = rng.choice(positions)
            if mode == "flip":
                data[rng.randrange(len(data))] ^= 1 << rng.randrange(8)
            elif mode == "cut":
                del data[rng.randrange(len(data)):]
            elif mode == "size":
                data[at + 4 : at + 8] = rng.choice((0, 60, 61, 10**6, -5)).to_bytes(4, "little", signed=True)
            else:
                data[at + 16] = rng.choice((0, 20, 77, 200))

        _torn(seg, blob, tear)
        with open(seg.data_path, "rb") as f:
            blob = f.read()

    def outcome(crossing, args, kwargs):
        monkeypatch.setattr(native.lib, "has_scan_internal_frames", crossing)
        try:
            out, cursor, file_reads = seg.scan(*args, **kwargs)
        except (CorruptBatchError, ValueError) as exc:
            return type(exc), str(exc)
        return [(vars(b.header), b.payload) for b in out], cursor, file_reads

    last = batches[-1].last_offset
    raised = 0
    for _ in range(60):
        start = rng.randrange(batches[0].base_offset - 3, last + 3)
        args = (start, rng.choice((0, 1, 700, 9000, 1 << 20)))
        kwargs = {
            "type_filter": rng.choice((None, (TYPES[0],), TYPES[1:3])),
            "max_offset": rng.choice((None, rng.randrange(start - 2, last + 2))),
            "read_ahead": rng.random() < 0.5,
        }
        if rng.random() < 0.6 and blob:
            at = rng.choice([p for p in positions if p <= len(blob)])
            lo, hi = rng.randrange(0, at + 1), rng.randrange(at, len(blob) + 1)
            kwargs["cursor"] = ReadCursor(seg.base_offset, at, blob[lo:hi], lo)
        got = outcome(True, args, kwargs)
        assert got == outcome(False, args, kwargs), (args, kwargs)
        raised += isinstance(got[0], type)
    assert seed % 2 or not raised

