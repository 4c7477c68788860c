"""coproc_lockwatch: the pandaraces dynamic cross-check (ISSUE 9).

The acceptance contract has two halves:

1. **Off = free.** With lockwatch disabled (the default), ``wrap`` is an
   identity function and the engine's locks are plain ``threading.Lock``
   objects — no wrapper installed, zero steady-state overhead.
2. **On = the analyzer is verified.** The chaos-parity workload (all
   engine modes, pool on/off, fault injection at every coproc probe
   point) runs under lockwatch, and the OBSERVED lock-order edge set
   must be a subgraph of the static acquisition graph pandalint builds
   (tools/pandalint/lockgraph.py). A missing edge means the static
   analysis has a call-resolution blind spot — the failure surfaces
   here instead of silently weakening the DLK gate.
"""

from __future__ import annotations

import ast
import json
import os
import threading

from redpanda_tpu.coproc import (
    EnableResponseCode,
    ProcessBatchRequest,
    TpuEngine,
    lockwatch,
)
from redpanda_tpu.coproc import engine as engine_mod
from redpanda_tpu.coproc import faults, governor
from redpanda_tpu.coproc.engine import ProcessBatchItem
from redpanda_tpu.finjector import honey_badger
from redpanda_tpu.models import NTP, Record, RecordBatch
from redpanda_tpu.ops.exprs import field
from redpanda_tpu.ops.transforms import (
    Int,
    Str,
    filter_contains,
    identity,
    map_project,
)
from redpanda_tpu.ops.transforms import where

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PARTITIONS = 16
RECORDS_PER_PARTITION = 16


def _workload() -> ProcessBatchRequest:
    items = []
    for p in range(PARTITIONS):
        recs = [
            Record(
                offset_delta=i,
                timestamp_delta=i,
                value=json.dumps(
                    {
                        "level": ["error", "info"][(p + i) % 2],
                        "code": 100 * p + i,
                        "msg": f"p{p}m{i}",
                    },
                    separators=(",", ":"),
                ).encode(),
            )
            for i in range(RECORDS_PER_PARTITION)
        ]
        items.append(
            ProcessBatchItem(
                1,
                NTP.kafka("orders", p),
                [RecordBatch.build(recs, base_offset=1000 * p, first_timestamp=1000)],
            )
        )
    return ProcessBatchRequest(items)


def _engine(spec, force_mode, mesh_devices=0) -> TpuEngine:
    engine = TpuEngine(
        row_stride=256,
        compress_threshold=10**9,
        force_mode=force_mode,
        # a mesh engine runs its per-device ladders and per-shard harvest
        # on a 4-worker pool; a single-device engine has no pool
        host_workers=4 if mesh_devices else 0,
        mesh_devices=mesh_devices or None,
        mesh_backend="cpu" if mesh_devices else None,
        mesh_probe=False,
        device_deadline_ms=60,
        adaptive_deadline=False,
        launch_retries=1,
        retry_backoff_ms=1,
        breaker_threshold=10_000,
    )
    codes = engine.enable_coprocessors([(1, spec.to_json(), ("orders",))])
    assert codes == [EnableResponseCode.success]
    return engine


def _static_edge_set() -> set[tuple[str, str]]:
    from tools.pandalint.affinity import Program
    from tools.pandalint.engine import iter_python_files
    from tools.pandalint.lockgraph import LockGraph

    mods = []
    for p in iter_python_files([os.path.join(REPO, "redpanda_tpu")]):
        rel = os.path.relpath(p, REPO).replace(os.sep, "/")
        try:
            with open(p, encoding="utf-8", errors="replace") as fh:
                mods.append((rel, ast.parse(fh.read())))
        except SyntaxError:
            pass
    return LockGraph(Program(mods)).edge_set()


# --------------------------------------------------------------- off = free
def test_lockwatch_off_installs_no_wrapper():
    """The acceptance bullet: lockwatch-off overhead is ZERO — wrap() is
    identity and a freshly built engine carries raw locks."""
    assert not lockwatch.enabled()
    raw = threading.Lock()
    assert lockwatch.wrap(raw, "x") is raw
    engine = TpuEngine(host_workers=2)
    try:
        assert not isinstance(engine._stats_lock, lockwatch.WatchedLock)
        assert not isinstance(engine._compile_lock, lockwatch.WatchedLock)
        assert not isinstance(
            engine_mod._mask_claim_lock, lockwatch.WatchedLock
        )
        assert type(engine._stats_lock) is type(raw)
    finally:
        engine.shutdown()


def test_disable_restores_module_locks():
    lockwatch.enable()
    try:
        assert isinstance(engine_mod._mask_claim_lock, lockwatch.WatchedLock)
        assert isinstance(faults._pool_lock, lockwatch.WatchedLock)
    finally:
        lockwatch.disable()
    assert not isinstance(engine_mod._mask_claim_lock, lockwatch.WatchedLock)
    assert not isinstance(faults._pool_lock, lockwatch.WatchedLock)


# ------------------------------------------------- on = analyzer verified
def test_chaos_parity_lock_edges_are_subgraph_of_static_graph(eight_devices):
    """Run the parity workload matrix (every engine mode, the mesh lane
    and its pool, every probe point faulted) under lockwatch; assert (a) the
    parity invariant still holds, (b) edges were actually observed,
    journaled and counted, (c) observed edges ⊆ static graph."""
    lockwatch.reset_edges()
    lockwatch.enable()
    engines: list[TpuEngine] = []
    saved_wedge, saved_delay = honey_badger.wedge_max_s, honey_badger.delay_ms
    honey_badger.wedge_max_s = 0.12
    honey_badger.delay_ms = 5
    try:
        req = _workload()
        matrix = [
            (
                where(field("level") == "error")
                | map_project(Int("code"), Str("msg", 16)),
                "columnar_device",
                0,
            ),
            (
                where(field("level") == "error")
                | map_project(Int("code"), Str("msg", 16)),
                "columnar_device",
                2,
            ),
            (
                where(field("level") == "error")
                | map_project(Int("code"), Str("msg", 16)),
                "columnar_host",
                0,
            ),
            (filter_contains(b"error"), None, 0),
            (identity(), None, 0),
        ]
        for spec, force_mode, mesh_devices in matrix:
            engine = _engine(spec, force_mode, mesh_devices)
            engines.append(engine)
            baseline = engine.process_batch(req)
            n_base = sum(
                b.header.record_count
                for item in baseline.items
                for b in item.batches
            )
            assert n_base > 0
        # fault round on the async-mask and the mesh engine: every coproc
        # probe point,
        # so breaker/fallback/abandonment lock paths are exercised too
        honey_badger.enable()
        try:
            for engine, probe in [
                (engines[0], faults.DEVICE_DISPATCH),
                (engines[0], faults.MASK_FETCH),
                (engines[0], faults.HARVEST),
                (engines[1], faults.MESH_DISPATCH),
                (engines[1], faults.MASK_FETCH),
                (engines[1], faults.SHARD_WORKER),
            ]:
                honey_badger.set_exception(faults.MODULE, probe)
                try:
                    reply = engine.process_batch(req)
                finally:
                    honey_badger.unset(faults.MODULE, probe)
                assert sum(
                    b.header.record_count
                    for item in reply.items
                    for b in item.batches
                ) > 0
        finally:
            honey_badger.disable()

        observed = lockwatch.edges()
        assert observed, "the workload must traverse nested lock paths"
        # the launch lock is held across harvest-side calls — the chain
        # the static entry-lockset propagation exists to see through
        assert any(src == "_Launch._lock" for src, _dst in observed)

        # observability surfaces: stats() block, governor journal domain
        # (reset_edges() at test start means every observed edge was
        # re-discovered — and so journaled — during THIS test)
        snap = engines[0].stats()
        assert snap["lockwatch"]["enabled"] is True
        assert snap["lockwatch"]["edges"] == len(observed)
        entries = governor.journal.entries(domain=governor.LOCKWATCH)
        journaled = {
            (e["inputs"]["from"], e["inputs"]["to"]) for e in entries
        }
        assert set(observed) <= journaled

        static = _static_edge_set()
        missing = [e for e in observed if e not in static]
        assert not missing, (
            f"runtime observed lock-order edges the static acquisition "
            f"graph does not contain (analyzer blind spot): {missing}"
        )
    finally:
        for engine in engines:
            engine.shutdown()
        honey_badger.wedge_max_s = saved_wedge
        honey_badger.delay_ms = saved_delay
        lockwatch.disable()
