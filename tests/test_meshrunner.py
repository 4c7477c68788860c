"""meshrunner: the multi-chip sharded engine's parity matrix + config 5.

The contract under test is bit-identity: a TpuEngine sharded over an
N-device mesh (N in {2, 4, 8}, the virtual host-platform mesh from
tests/conftest) must produce byte-for-byte the replies of the 1-device
engine and the inline path, across plan modes, pool on/off and native
on/off. Plus: the config-5 CRC/vote reduction against the host crc32c
oracle, and the governor's mesh-domain journal/breaker-demotion story.
"""

from __future__ import annotations

import json

import numpy as np
import pytest

from redpanda_tpu.coproc import TpuEngine, ProcessBatchRequest
from redpanda_tpu.coproc import batch_codec, faults
from redpanda_tpu.coproc import column_plan as cp
from redpanda_tpu.coproc import governor as gov_mod
from redpanda_tpu.coproc.engine import ProcessBatchItem
from redpanda_tpu.models import NTP, Record, RecordBatch
from redpanda_tpu.ops.exprs import field
from redpanda_tpu.ops.transforms import Int, Str, filter_contains, map_project, where

PASS_SPEC = where(field("level") == "error")
PROJ_SPEC = where(field("level") == "error") | map_project(
    Int("code"), Str("msg", 24)
)
PAYLOAD_SPEC = filter_contains(b"error")

SPECS = {
    "passthrough": PASS_SPEC,
    "projection": PROJ_SPEC,
    "payload": PAYLOAD_SPEC,
}


def _request(n_items=8, records=48, topic="mesh") -> ProcessBatchRequest:
    rng = np.random.default_rng(11)
    items = []
    for p in range(n_items):
        recs = [
            Record(
                offset_delta=i,
                value=json.dumps({
                    "level": ["error", "info", "warn"][(p + i) % 3],
                    "code": p * 1000 + i,
                    "msg": "m%d-%s" % (p, "x" * int(rng.integers(4, 20))),
                }).encode(),
            )
            for i in range(records)
        ]
        items.append(
            ProcessBatchItem(
                1, NTP.kafka(topic, p),
                [RecordBatch.build(recs, base_offset=0)],
            )
        )
    return ProcessBatchRequest(items)


def _payloads(reply):
    return [
        (it.script_id, [(b.payload, b.header.record_count) for b in it.batches])
        for it in reply.items
    ]


def _run(spec, *, mesh_devices=None, host_workers=0, **kw):
    TpuEngine.reset_columnar_probe()
    engine = TpuEngine(
        row_stride=256,
        host_workers=host_workers,
        mesh_devices=mesh_devices,
        mesh_backend="cpu" if mesh_devices else None,
        mesh_probe=False,  # pin "mesh": parity needs the lane deterministically
        **kw,
    )
    try:
        assert engine.enable_coprocessors([(1, spec.to_json(), ("mesh",))]) == [0]
        req = _request()
        out = _payloads(engine.process_batch(req))
        stats = engine.stats()
    finally:
        engine.shutdown()
    return out, stats


# ------------------------------------------------------------ parity matrix
@pytest.mark.parametrize("plan", sorted(SPECS))
@pytest.mark.parametrize("n_dev", [2, 4, 8])
def test_mesh_parity_pool_on(n_dev, plan, eight_devices):
    """mesh(N) with the host pool on == 1-device inline, byte for byte."""
    ref, _ = _run(SPECS[plan])  # inline single-device reference
    got, stats = _run(SPECS[plan], mesh_devices=n_dev, host_workers=2)
    assert got == ref
    if plan != "payload":
        # columnar plans actually took the mesh lane
        assert stats.get("n_mesh_launches", 0) >= 1
        assert stats["mesh"]["devices"] == n_dev
        assert stats["mesh"]["launches"] >= 1
        assert sum(stats["mesh"]["rows_per_device"]) == 8 * 48
    else:
        # payload plans have no mesh stage; the engine must not pretend
        assert stats.get("n_mesh_launches", 0) == 0


@pytest.mark.parametrize("plan", sorted(SPECS))
def test_mesh_parity_pool_off(plan, eight_devices):
    ref, _ = _run(SPECS[plan])
    got, stats = _run(SPECS[plan], mesh_devices=4, host_workers=0)
    assert got == ref
    if plan != "payload":
        assert stats.get("n_mesh_launches", 0) >= 1


@pytest.mark.parametrize("plan", sorted(SPECS))
def test_mesh_parity_native_off(plan, monkeypatch, eight_devices):
    """The numpy fallback ladders under the mesh produce the same bytes
    as the native ladders under the mesh (and as the inline reference)."""
    ref, _ = _run(SPECS[plan])  # native reference
    monkeypatch.setattr(batch_codec, "_native", lambda: None)
    monkeypatch.setattr(cp, "_native", lambda: None)
    got, stats = _run(SPECS[plan], mesh_devices=4, host_workers=0)
    assert got == ref
    if plan != "payload":
        assert stats.get("n_mesh_launches", 0) >= 1


def test_mesh_engine_vs_one_device_engine_stats_shape(eight_devices):
    """A 1-ish mesh request (mesh_devices below 2) keeps the plain
    engine: no mesh block in stats, no meshrunner built."""
    out, stats = _run(PASS_SPEC, mesh_devices=None)
    assert "mesh" not in stats
    out1, stats1 = _run(PASS_SPEC, mesh_devices=1)
    assert "mesh" not in stats1
    assert out == out1


# ------------------------------------------------------ per-shard colcache
def test_mesh_launches_consult_cache_per_shard(eight_devices):
    """Repeat mesh launches hit the per-shard column cache: first launch
    populates one entry per device shard, later identical launches skip
    every shard's ladder (hit/miss counters pinned)."""
    TpuEngine.reset_columnar_probe()
    engine = TpuEngine(
        row_stride=256, host_workers=0, mesh_devices=4, mesh_backend="cpu",
        mesh_probe=False, device_column_cache_mb=32,
    )
    try:
        assert engine.enable_coprocessors(
            [(1, PROJ_SPEC.to_json(), ("mesh",))]
        ) == [0]
        req = _request()
        outs = [_payloads(engine.process_batch(req)) for _ in range(3)]
        assert outs[0] == outs[1] == outs[2]
        cc = engine.stats()["colcache"]
        # 4 shard lookups per launch; launch 1 misses and populates,
        # launches 2-3 hit (the mesh lane bypasses the launch-wide
        # pre-shard lookup entirely, so counters are purely per-shard)
        assert cc["misses"] == 4 and cc["hits"] == 8
        assert cc["entries"] == 4
        assert engine.stats()["mesh"]["launches"] == 3
    finally:
        engine.shutdown()


# ------------------------------------------------------ CRC/vote reduction
def test_crc_vote_step_matches_host_oracle(eight_devices):
    from redpanda_tpu.hashing.crc32c import crc32c, crc32c_many
    from redpanda_tpu.parallel import (
        make_crc_vote_step,
        partition_mesh,
        shard_to_mesh,
    )

    mesh = partition_mesh(devices=eight_devices[:4])
    rng = np.random.default_rng(3)
    d, b, r, g = 4, 6, 192, 16
    rows = np.zeros((d, b, r), np.uint8)
    lens = np.zeros((d, b), np.int32)
    claimed = np.zeros((d, b), np.uint32)
    for i in range(d):
        for j in range(b):
            ln = int(rng.integers(0, r + 1))
            payload = rng.bytes(ln)
            rows[i, j, :ln] = np.frombuffer(payload, np.uint8)
            lens[i, j] = ln
            claimed[i, j] = crc32c(payload)
    # corrupt two claimed CRCs; zero-length batches are invalid by rule
    claimed[1, 2] ^= 0xDEAD
    claimed[3, 0] ^= 1
    votes = rng.integers(0, 2, (d, g)).astype(np.uint8)
    step = make_crc_vote_step(mesh, r)
    ok, bad, tally = step(*shard_to_mesh(mesh, rows, lens, claimed, votes))
    ok, bad, tally = np.asarray(ok), np.asarray(bad), np.asarray(tally)
    oracle = (
        crc32c_many(rows.reshape(d * b, r), lens.reshape(d * b))
        == claimed.reshape(d * b)
    ) & (lens.reshape(d * b) > 0)
    assert np.array_equal(ok.reshape(d * b), oracle)
    assert not ok[1, 2] and not ok[3, 0]
    want_bad = ((~ok) & (lens > 0)).sum(axis=1).astype(np.int32)
    assert np.array_equal(bad, want_bad)
    assert np.array_equal(tally, votes.astype(np.int32).sum(axis=0))


def test_raft_device_plane_validate_and_tally(eight_devices):
    from redpanda_tpu.hashing.crc32c import crc32c
    from redpanda_tpu.parallel import partition_mesh
    from redpanda_tpu.raft.device_plane import RaftDevicePlane

    rng = np.random.default_rng(5)
    regions = [rng.bytes(64 + 13 * i) for i in range(96)]
    claimed = np.array([crc32c(x) for x in regions], np.uint32)
    claimed[7] ^= 0x10
    mesh = partition_mesh(devices=eight_devices[:4])
    dev = RaftDevicePlane(mesh=mesh, probe=False)  # pin device
    host = RaftDevicePlane(probe=True)
    ok_dev = dev.validate(regions, claimed)
    ok_host = host.validate(regions, claimed)
    assert np.array_equal(ok_dev, ok_host)
    assert ok_dev.sum() == 95 and not ok_dev[7]
    votes = rng.integers(0, 2, (4, 32)).astype(np.uint8)
    assert np.array_equal(
        dev.tally_votes(votes), votes.astype(np.int32).sum(axis=0)
    )
    st = dev.stats()
    assert st["devices"] == 4 and st["validations"] == 1


def test_default_plane_builds_configured_mesh(eight_devices):
    # app.py hands the coproc mesh topology to the raft plane: with the
    # knobs set the process-wide default plane runs the SHARDED step
    # (the config-5 psum lane is reachable in product, not just tests)
    from redpanda_tpu.raft import device_plane

    device_plane.reset_default_plane()
    device_plane.configure(mesh_devices=4, mesh_backend="cpu")
    try:
        plane = device_plane.default_plane()
        assert plane.n_devices == 4 and plane.mesh is not None
    finally:
        device_plane.configure(mesh_devices=0, mesh_backend="")
        device_plane.reset_default_plane()
    # knobs cleared: back to the single-device plane
    assert device_plane.default_plane().n_devices == 1
    device_plane.reset_default_plane()


def test_heartbeat_manager_batched_ack_tally():
    from redpanda_tpu.raft import device_plane
    from redpanda_tpu.raft.heartbeat_manager import HeartbeatManager

    hm = HeartbeatManager(client_for=None)
    hm._groups = {3: object(), 5: object(), 9: object()}
    device_plane.configure(vote_tally=True)
    try:
        hm._tally_acks([
            {3: True, 5: False, 9: True},
            {3: True, 9: False},
            {5: False},
        ])
        assert hm.last_tick_acks == {3: 2, 5: 0, 9: 1}
    finally:
        device_plane.configure(vote_tally=False)
    # disabled: no tally view is produced
    hm2 = HeartbeatManager(client_for=None)
    hm2._groups = {1: object()}
    hm2._tally_acks([{1: True}])
    assert hm2.last_tick_acks == {}


# ------------------------------------------------------ governor / breaker
def test_mesh_engagement_journaled(eight_devices):
    gov_mod.reset_journal()
    _run(PASS_SPEC, mesh_devices=4, host_workers=0)
    entries = gov_mod.journal.entries(domain=gov_mod.MESH)
    assert entries, "mesh engagement must journal"
    assert entries[0]["verdict"] == "mesh"
    assert entries[0]["inputs"]["devices"] == 4


def test_mesh_breaker_demotes_to_single_device_bit_identical(eight_devices):
    """An open mesh_dispatch breaker sends mesh-eligible launches down
    the single-device path with byte-identical output, counts the
    demotion, and journals the flip — then the posture reads 'single'."""
    ref, _ = _run(PASS_SPEC)
    gov_mod.reset_journal()
    TpuEngine.reset_columnar_probe()
    engine = TpuEngine(
        row_stride=256, host_workers=0, mesh_devices=4, mesh_backend="cpu",
        mesh_probe=False,
    )
    try:
        assert engine.enable_coprocessors(
            [(1, PASS_SPEC.to_json(), ("mesh",))]
        ) == [0]
        breaker = engine.governor.breaker_for(faults.MESH_DISPATCH)
        for _ in range(10):
            breaker.record_failure()
        assert not breaker.allow_device()
        got = _payloads(engine.process_batch(_request()))
        assert got == ref
        stats = engine.stats()
        assert stats["mesh"]["demotions"] >= 1
        assert stats["mesh"]["launches"] == 0
        assert stats.get("n_mesh_launches", 0) == 0
        posture = stats["governor"]["posture"]
        assert posture[gov_mod.MESH] == "single"
        entries = gov_mod.journal.entries(domain=gov_mod.MESH)
        assert any(e["verdict"] == "single" for e in entries)
    finally:
        engine.shutdown()


def test_mesh_probe_small_launch_stays_single_without_pinning(eight_devices):
    TpuEngine.reset_columnar_probe()
    engine = TpuEngine(
        row_stride=256, host_workers=0, mesh_devices=4, mesh_backend="cpu",
        mesh_probe=True,
    )
    try:
        assert engine.enable_coprocessors(
            [(1, PASS_SPEC.to_json(), ("mesh",))]
        ) == [0]
        engine.process_batch(_request(n_items=4, records=8))  # << probe floor
        stats = engine.stats()
        assert stats["mesh"]["decision"] is None  # nothing pinned
        assert stats.get("n_mesh_launches", 0) == 0
    finally:
        engine.shutdown()


def test_mesh_probe_measures_and_journals(eight_devices):
    """A representative launch runs the measured mesh-vs-single
    calibration: the verdict is whatever the box measures (a 1-core host
    honestly self-demotes), but it must pin, journal with both timings,
    and the engine must still produce reference bytes."""
    ref, _ = _run(PASS_SPEC, mesh_devices=None)
    gov_mod.reset_journal()
    TpuEngine.reset_columnar_probe()
    engine = TpuEngine(
        row_stride=256, host_workers=0, mesh_devices=2, mesh_backend="cpu",
        mesh_probe=True,
    )
    try:
        assert engine.enable_coprocessors(
            [(1, PASS_SPEC.to_json(), ("mesh",))]
        ) == [0]
        req = _request(n_items=8, records=160)  # 1280 rows >= probe floor
        engine.process_batch(req)
        stats = engine.stats()
        decision = stats["mesh"]["decision"]
        assert decision in ("mesh", "single")
        probe = stats["mesh"].get("probe")
        if probe is not None:
            assert probe["chosen"] == decision
            assert probe["t_mesh_ms"] > 0 and probe["t_single_ms"] > 0
        entries = gov_mod.journal.entries(domain=gov_mod.MESH)
        assert any(e["verdict"] == decision for e in entries)
        # parity holds regardless of the verdict
        got = _payloads(engine.process_batch(_request()))
        assert got == ref
    finally:
        engine.shutdown()
