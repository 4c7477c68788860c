"""Debug file-handle sanitizer (storage/file_sanitizer.py; reference
utils/file_sanitizer.h:51 + the storage::debug_sanitize_files knob):
armed runs catch write-after-close, double close, and handle leaks at the
misuse site; disarmed runs pay nothing and behave identically.
"""

import asyncio
import os

import pytest

from redpanda_tpu.models import NTP, Record, RecordBatch
from redpanda_tpu.storage import file_sanitizer
from redpanda_tpu.storage.file_sanitizer import FileSanitizerError
from redpanda_tpu.storage.log import DiskLog, LogConfig


@pytest.fixture(autouse=True)
def _disarm():
    yield
    file_sanitizer.disable()


def _batch(base: int) -> RecordBatch:
    return RecordBatch.build(
        [Record(offset_delta=0, value=b"v%d" % base)], base_offset=base
    )


def test_write_after_close_raises(tmp_path):
    file_sanitizer.enable()
    f = file_sanitizer.maybe_wrap(open(tmp_path / "x", "wb"), "x")
    f.write(b"ok")
    f.close()
    with pytest.raises(FileSanitizerError, match="write on closed"):
        f.write(b"boom")


def test_double_close_raises(tmp_path):
    file_sanitizer.enable()
    f = file_sanitizer.maybe_wrap(open(tmp_path / "x", "wb"), "x")
    f.close()
    with pytest.raises(FileSanitizerError, match="double close"):
        f.close()


def test_leak_detection(tmp_path):
    file_sanitizer.enable()
    file_sanitizer.maybe_wrap(open(tmp_path / "leaky", "wb"), "leaky")
    assert file_sanitizer.verify_all_closed() == ["leaky"]
    assert file_sanitizer.verify_all_closed() == []  # registry cleared


def test_scoped_leak_check_spares_other_instances(tmp_path):
    """Two storage instances in one process: one instance's shutdown check
    must not report or clear the other's live handles."""
    file_sanitizer.enable()
    a = file_sanitizer.maybe_wrap(open(tmp_path / "a.wal", "wb"), str(tmp_path / "a.wal"))
    b_dir = tmp_path / "other"
    b_dir.mkdir()
    file_sanitizer.maybe_wrap(open(b_dir / "b.wal", "wb"), str(b_dir / "b.wal"))
    # instance B shuts down: only its (leaked) handle is reported
    leaked = file_sanitizer.verify_all_closed(prefix=str(b_dir))
    assert leaked == [str(b_dir / "b.wal")]
    # instance A's handle survived the scoped sweep and still works
    a.write(b"still live")
    a.close()
    assert file_sanitizer.verify_all_closed() == []


def test_disarmed_is_passthrough(tmp_path):
    assert not file_sanitizer.enabled()
    f = file_sanitizer.maybe_wrap(open(tmp_path / "x", "wb"), "x")
    assert not isinstance(f, file_sanitizer.SanitizedFile)
    f.close()


def test_truncate_keeps_sanitizer_coverage(tmp_path):
    """truncate_to_file_pos reopens the appender handle; the new handle
    must stay wrapped so post-truncation misuse is still caught."""
    async def body():
        cfg = LogConfig(base_dir=str(tmp_path), sanitize_files=True)
        log = await DiskLog.open(NTP.kafka("tr", 0), cfg)
        for i in range(4):
            await log.append([_batch(i)], assign_offsets=False)
        await log.truncate(2)
        seg = log.segments[-1]
        assert isinstance(seg._file, file_sanitizer.SanitizedFile)
        await log.append([_batch(2)], assign_offsets=False)  # still usable
        await log.close()
        assert file_sanitizer.verify_all_closed() == []

    asyncio.run(body())


def test_sanitized_log_lifecycle_is_clean(tmp_path):
    """A normal append/read/roll/close cycle under the armed sanitizer
    must neither raise nor leak — proving storage closes what it opens."""
    async def body():
        cfg = LogConfig(
            base_dir=str(tmp_path), sanitize_files=True, max_segment_size=256
        )
        log = await DiskLog.open(NTP.kafka("san", 0), cfg)
        for i in range(12):  # rolls several segments
            await log.append([_batch(i)], assign_offsets=False)
        got = await log.read(0, 1 << 20)
        assert len(got) == 12
        await log.close()
        assert file_sanitizer.verify_all_closed() == []

    asyncio.run(body())


def _open_paths():
    return sorted(sf._path for sf in file_sanitizer._open_files.values())


def _kb(base, key):
    from redpanda_tpu.models import Record

    return RecordBatch.build(
        [Record(offset_delta=0, key=key, value=b"v%d" % base)], base_offset=base
    )


@pytest.mark.parametrize(
    "how", ["close", "remove", "retention", "compaction", "truncate", "descriptor_bound"]
)
def test_segment_read_descriptor_lifecycle(tmp_path, how, monkeypatch):
    """A segment keeps ONE read descriptor once it has been read (PR 25):
    it is a sanitized handle, closed when the file goes away or is replaced
    (close, remove, retention's roll-out, compaction's rewrite, a truncation
    that drops the segment) and when the readers cache bounds the open ones."""
    async def body():
        from redpanda_tpu.storage import readers_cache
        from redpanda_tpu.storage.log_manager import LogManager

        cfg = LogConfig(
            base_dir=str(tmp_path), sanitize_files=True, max_segment_size=160,
            cleanup_policy="compact,delete",
        )
        mgr = LogManager(cfg, batch_cache_bytes=0)
        log = await mgr.manage(NTP.kafka("rd", 0))
        for i in range(12):  # rolls several segments
            await log.append([_kb(i, b"k%d" % (i % 2))], assign_offsets=False)
        n_seg = len(log.segments)
        assert n_seg >= 4 and _open_paths() == [log.segments[-1].data_path]
        if how == "descriptor_bound":
            monkeypatch.setattr(readers_cache, "MAX_OPEN_READERS", 2)
        assert len(await log.read(0, 1 << 20)) == 12
        readers = [s for s in log.segments if s._rfile is not None]
        if how == "descriptor_bound":
            assert readers == log.segments[-2:]  # the least recently read closed theirs
            assert len(await log.read(0, 1 << 20)) == 12  # and reopen when read again
            assert [s for s in log.segments if s._rfile is not None] == log.segments[-2:]
            await mgr.stop()
            assert file_sanitizer.verify_all_closed() == []
            return
        assert readers == log.segments
        assert all(isinstance(s._rfile, file_sanitizer.SanitizedFile) for s in readers)
        # one appender + one reader per segment, and a second read opens none
        assert len(_open_paths()) == n_seg + 1
        await log.read(0, 1 << 20)
        assert len(_open_paths()) == n_seg + 1
        if how == "close":
            pass
        elif how == "remove":
            await mgr.remove(log.ntp)
            assert _open_paths() == []
        elif how == "retention":
            log.config.retention_bytes = 1
            await log.apply_retention()
            assert len(log.segments) == 1 and len(_open_paths()) == 2
        elif how == "compaction":
            before = {s.base_offset: os.fstat(s._rfile.fileno()).st_ino for s in log.segments[:-1]}
            await log.compact()
            # the rewritten files are new inodes: their descriptors were let go
            assert all(s._rfile is None for s in log.segments[:-1])
            got = await log.read(0, 1 << 20)
            assert {r.key: r.value for b in got for r in b.records()} == {
                b"k0": b"v10", b"k1": b"v11"}
            assert all(
                os.fstat(s._rfile.fileno()).st_ino != before[s.base_offset] for s in log.segments[:-1]
            )
        elif how == "truncate":
            await log.truncate(3)
            # the dropped segments' descriptors went with their files
            assert _open_paths() == [s.data_path for s in log.segments if s._rfile]
            assert len(_open_paths()) == len(log.segments) == 1
            await log.append([_kb(3, b"k9")], assign_offsets=False)
            assert [b.header.base_offset for b in await log.read(0, 1 << 20)] == [0, 1, 2, 3]
        await mgr.stop()
        assert file_sanitizer.verify_all_closed() == []

    asyncio.run(body())


def test_storage_api_stop_reports_no_leaked_read_handle(tmp_path, caplog):
    async def body():
        from redpanda_tpu.storage.log_manager import StorageApi

        cfg = LogConfig(
            base_dir=str(tmp_path / "data"), sanitize_files=True, max_segment_size=256
        )
        storage = await StorageApi(str(tmp_path), cfg).start()
        log = await storage.log_mgr.manage(NTP.kafka("api", 0))
        for i in range(8):
            await log.append([_batch(i)], assign_offsets=False)
        storage.log_mgr.batch_cache.invalidate(id(log))  # read the files
        assert len(await log.read(0, 1 << 20)) == 8
        assert sum(s._rfile is not None for s in log.segments) == len(log.segments)
        await storage.stop()
        assert file_sanitizer.verify_all_closed() == []

    with caplog.at_level("WARNING", logger="rptpu.storage"):
        asyncio.run(body())
    assert "leaked" not in caplog.text
