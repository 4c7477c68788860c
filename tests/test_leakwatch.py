"""coproc_leakwatch: the pandaleak dynamic cross-check (ISSUE 16).

The acceptance contract has two halves, same posture as lockwatch:

1. **Off = free.** With leakwatch disabled (the default), ``wrap`` is an
   identity function — a freshly built budget plane / engine carries raw
   accounts, admission controllers, and arenas; no proxy is installed
   and the steady-state broker pays nothing per acquisition.
2. **On = the analyzer is verified.** The chaos-parity workload (all
   engine modes, pool on/off, fault injection at every coproc probe
   point, cancellation injection on the async choreography) runs under
   leakwatch, and at end of test (a) every resource balance nets to
   ZERO and (b) every OBSERVED acquire site is a statement pandalint's
   lifecycle model knows about (tools/pandalint/lifecycle.model_sites).
   A nonzero balance is a leak the static gate should have caught; an
   unmodeled site is a vocabulary blind spot — either failure surfaces
   here instead of silently weakening the RSL gate.
"""

from __future__ import annotations

import ast
import asyncio
import json
import os

from redpanda_tpu.coproc import (
    EnableResponseCode,
    ProcessBatchRequest,
    TpuEngine,
    leakwatch,
)
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
from redpanda_tpu.resource_mgmt.admission import InflightGate
from redpanda_tpu.resource_mgmt.budgets import BudgetPlane, MemoryAccount

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


def _engine(spec, force_mode, mesh_devices=0, budget_plane=None) -> TpuEngine:
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
        budget_plane=budget_plane,
    )
    codes = engine.enable_coprocessors([(1, spec.to_json(), ("orders",))])
    assert codes == [EnableResponseCode.success]
    return engine


def _static_model() -> dict[str, set[int]]:
    """pandalint's acquire-site model over the package AND this test
    file — every wrapped acquisition the chaos run performs (including
    the cancellation choreography below) must land on one of these
    statements or the analyzer has a vocabulary blind spot."""
    from tools.pandalint.engine import iter_python_files
    from tools.pandalint.lifecycle import model_sites

    mods = []
    paths = list(iter_python_files([os.path.join(REPO, "redpanda_tpu")]))
    paths.append(os.path.abspath(__file__))
    for p in paths:
        rel = os.path.relpath(p, REPO).replace(os.sep, "/")
        try:
            with open(p, encoding="utf-8", errors="replace") as fh:
                mods.append((rel, ast.parse(fh.read())))
        except SyntaxError:
            pass
    return model_sites(mods)


async def _cancellation_round(plane: BudgetPlane) -> None:
    """Cancellation injection against the wrapped async vocabulary: the
    three PR-13 shapes, each with its FIX discipline, so leakwatch sees
    cancelled tasks and still nets to zero."""
    acct = plane.account("rpc")
    gate = leakwatch.wrap(
        InflightGate(acct, max_requests=8), "test.inflight_gate"
    )

    async def held_with_finally(n: int) -> None:
        reserved = await acct.acquire(n)
        try:
            await asyncio.sleep(30)  # cancelled mid-hold
        finally:
            acct.release(reserved)

    # shape: cancelled while suspended mid-hold — finally releases
    t = asyncio.create_task(held_with_finally(4096))
    await asyncio.sleep(0.01)
    t.cancel()
    try:
        await t
    except asyncio.CancelledError:
        pass

    # shape: cancelled BEFORE the first step — the coroutine body (and
    # any finally inside it) never runs, so the slot must ride the task
    # object via a done-callback, not the body
    async def handler(reserved: int) -> None:  # pragma: no cover - cancelled
        await asyncio.sleep(30)

    reserved = gate.try_enter(1024)
    assert reserved is not None
    t2 = asyncio.create_task(handler(reserved))
    t2.add_done_callback(lambda _t, g=gate, r=reserved: g.leave(r))
    t2.cancel()
    try:
        await t2
    except asyncio.CancelledError:
        pass
    await asyncio.sleep(0)  # let the done-callback run

    # shape: abandonment — the waiter gives up on a parked acquire; the
    # account's own CancelledError handling must not strand grants
    filler = acct.try_acquire(acct.limit)  # pandalint: disable=RSL1602 -- deliberate budget-fill so the next acquire parks; released right below
    waiter = asyncio.create_task(held_with_finally(1))
    await asyncio.sleep(0.01)
    waiter.cancel()
    try:
        await waiter
    except asyncio.CancelledError:
        pass
    acct.release(filler)


# --------------------------------------------------------------- off = free
def test_leakwatch_off_installs_no_proxy():
    """The acceptance bullet: leakwatch-off overhead is ZERO — wrap() is
    identity and freshly built planes/engines carry raw objects."""
    assert not leakwatch.enabled()
    raw = MemoryAccount("probe", 1024)
    assert leakwatch.wrap(raw, "x") is raw
    plane = BudgetPlane(total_bytes=1 << 20)
    for name, acct in plane.accounts.items():
        assert type(acct) is MemoryAccount, name
    engine = TpuEngine(host_workers=2)
    try:
        assert not isinstance(engine._arena, leakwatch.WatchedArena)
    finally:
        engine.shutdown()


# ------------------------------------------------- on = analyzer verified
def test_chaos_parity_balances_zero_and_sites_in_static_model(eight_devices):
    """Run the parity workload matrix (every engine mode, the mesh lane
    and its pool, every probe point faulted, cancellation injected) under
    leakwatch; assert (a) the parity invariant still holds, (b) every
    balance nets to zero and zero imbalances fired, (c) every observed
    acquire site is in the static lifecycle model."""
    leakwatch.reset()
    leakwatch.enable()
    engines: list[TpuEngine] = []
    saved_wedge, saved_delay = honey_badger.wedge_max_s, honey_badger.delay_ms
    honey_badger.wedge_max_s = 0.12
    honey_badger.delay_ms = 5
    try:
        plane = BudgetPlane(total_bytes=256 * 1024 * 1024)
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
            engine = _engine(spec, force_mode, mesh_devices, budget_plane=plane)
            engines.append(engine)
            assert isinstance(engine._arena, leakwatch.WatchedArena)
            baseline = engine.process_batch(req)
            n_base = sum(
                b.header.record_count
                for item in baseline.items
                for b in item.batches
            )
            assert n_base > 0
        # fault round on the async-mask and the mesh engine: every coproc
        # probe point,
        # so breaker/fallback/abandonment release paths are exercised too
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

        # cancellation injection: the async vocabulary under cancel fire
        asyncio.run(_cancellation_round(plane))

        observed = leakwatch.acquire_sites()
        assert observed, "the workload must drive wrapped acquisitions"
        # the engine's own admission path must be among them — proof the
        # chaos run exercised in-package sites, not just test helpers
        assert any(
            rel == "redpanda_tpu/coproc/engine.py" for rel, _ln in observed
        )

        # (a) every balance nets to zero; no imbalance ever fired
        bal = leakwatch.balances()
        leaked = {k: v for k, v in bal.items() if v != 0}
        assert not leaked, f"end-of-test resource balances nonzero: {leaked}"
        snap = leakwatch.snapshot()
        assert snap["enabled"] is True
        assert snap["imbalances"] == 0
        assert snap["outstanding"] == {}

        # observability surfaces: stats() block + governor journal domain
        # (reset() at test start means every observed site was
        # re-discovered — and so journaled — during THIS test)
        stats = engines[0].stats()
        assert stats["leakwatch"]["enabled"] is True
        assert stats["leakwatch"]["imbalances"] == 0
        entries = governor.journal.entries(domain=governor.LEAKWATCH)
        journaled = {
            e["inputs"]["site"] for e in entries if "site" in e["inputs"]
        }
        assert journaled, "first-acquire-per-site must journal"

        # (b) observed ⊆ static model: every runtime acquire site is a
        # statement the lifecycle analyzer classified as an acquisition
        model = _static_model()
        missing = [
            (rel, ln)
            for rel, ln in sorted(observed)
            if ln not in model.get(rel, set())
        ]
        assert not missing, (
            f"runtime observed acquire sites the static lifecycle model "
            f"does not contain (analyzer blind spot): {missing}"
        )
    finally:
        for engine in engines:
            engine.shutdown()
        honey_badger.wedge_max_s = saved_wedge
        honey_badger.delay_ms = saved_delay
        leakwatch.disable()
