"""meshrunner: the multi-chip sharded coproc engine (BASELINE config 5).

The reference scales by spreading partitions over cores and nodes
(shard-per-core SMP + the cluster partition allocator — SURVEY §2.3); the
TPU-native analogue maps the ``[partition, batch, record]`` axis onto a
1-D device mesh (parallel/mesh.py) and runs ONE SPMD predicate program
per launch instead of one program per chip. MULTICHIP_r01–r05 dry-ran
that shape end to end; this module promotes it into the product path:

- a launch's batches partition into **per-device sub-launches** with the
  same contiguous range-shard machinery the host pool uses
  (``host_pool.partition_counts``), so the concatenated outputs are
  byte-identical to the single-device path by construction;
- the predicate pipeline is compiled ONCE under the mesh
  (``ColumnarPlan.compile_device_stacked``: shard_map over the 'p' axis,
  per-device blocks of stacked ``[D, n_pad, ...]`` columns);
- the config-5 stretch rides the same mesh: raft batched-CRC validation
  vmapped over the sharded record axis plus the vote-tally psum
  (``parallel.collectives.make_crc_vote_step``), consumed by
  ``raft/device_plane.py`` behind its own measured probe.

Mesh-vs-single-device is a MEASURED, journaled governor decision (domain
``mesh``, ``governor.PROBE_MARGIN`` posture: the mesh must show a real
win over the known single-device path before it pins). The
``mesh_dispatch`` fault domain gives the mesh its own circuit breaker —
a flaky mesh path demotes mesh launches to the bit-identical
single-device path while plain dispatch keeps its own breaker.
Observability: ``TpuEngine.stats()["mesh"]``, per-device
``coproc_mesh_device_rows_total`` counters, ``/v1/coproc/status`` and
``rpk debug coproc``.
"""

from __future__ import annotations

import logging
import threading
import time

import numpy as np

from redpanda_tpu.coproc import host_pool, lockwatch
from redpanda_tpu.coproc.governor import MESH, PROBE_MARGIN
from redpanda_tpu.observability import probes

logger = logging.getLogger("rptpu.coproc.meshrunner")

# don't pin the engine-sticky mesh-vs-single decision on a launch too
# small to represent steady state (same floor as the columnar backend
# probe's _PROBE_MIN_ROWS posture)
PROBE_MIN_ROWS = 1024


def available_devices(backend: str | None = None) -> list:
    """Devices a mesh could span: the default backend's, or — with
    ``backend`` — that backend's (``'cpu'`` reaches the virtual host
    devices of a process whose default backend is an accelerator)."""
    import jax

    try:
        return jax.local_devices(backend=backend) if backend else jax.devices()
    except Exception as exc:
        # a missing backend means "no mesh possible", not a fault in the
        # engine — classified so the demotion shows on /metrics
        from redpanda_tpu.coproc import faults

        faults.note_failure("mesh_init", exc)
        return []


class MeshRunner:
    """Owns the partition-axis mesh and the mesh-vs-single decision.

    The engine keeps the launch machinery (ladders, column cache, host
    pool, fault envelopes); this class keeps everything mesh-shaped: the
    device list, the per-plan stacked predicate programs, the measured
    calibration, and the per-device accounting behind ``stats()``.
    """

    def __init__(
        self,
        n_devices: int | None = None,
        backend: str | None = None,
        devices=None,
        probe: bool = True,
    ):
        from redpanda_tpu.parallel.mesh import partition_mesh

        if devices is None:
            devices = available_devices(backend)
            if n_devices is not None:
                if len(devices) < int(n_devices):
                    # never a smaller mesh than was asked for: the engine
                    # reports this as stats()["mesh_error"]
                    raise ValueError(
                        f"meshrunner asked for {n_devices} devices, have "
                        f"{len(devices)} (backend={backend!r})"
                    )
                devices = devices[: int(n_devices)]
        if len(devices) < 2:
            raise ValueError(
                f"meshrunner needs >= 2 devices, have {len(devices)} "
                f"(backend={backend!r})"
            )
        self.mesh = partition_mesh(devices=devices)
        self.n_devices = len(devices)
        self._probe_enabled = bool(probe)
        # two-lock discipline (the columnar-backend probe's shape):
        # the RUN lock serializes calibration EXECUTION; the short
        # decision lock guards the fields so stats() readers never wait
        # behind a calibration's timed passes
        self._decision: str | None = None if probe else "mesh"
        self._probe: dict | None = None
        self._decision_lock = lockwatch.wrap(
            threading.Lock(), "MeshRunner._decision_lock"
        )
        self._probe_run_lock = lockwatch.wrap(
            threading.Lock(), "MeshRunner._probe_run_lock"
        )
        # accounting (guarded by the decision lock; per-launch cadence)
        self._n_launches = 0
        self._n_demotions = 0
        self._rows_per_device = [0] * self.n_devices

    # ------------------------------------------------------------ decision
    @property
    def decision(self) -> str | None:
        with self._decision_lock:
            return self._decision

    @property
    def probe_enabled(self) -> bool:
        return self._probe_enabled

    @property
    def probe_lock_busy(self) -> bool:
        """True while a calibration is executing — the engine checks
        this BEFORE paying the mesh per-shard ladder, since an undecided
        launch that loses the probe race runs single-device anyway."""
        return self._probe_run_lock.locked()

    def shard_ranges(self, counts: list[int]) -> list[tuple[int, int]]:
        """Per-device contiguous batch slices — the host pool's balanced
        range shard, one shard per mesh device (may return fewer when
        there are fewer batches than devices; the stack pads with empty
        shards)."""
        return host_pool.partition_counts(counts, self.n_devices)

    def predicate_fn(self, plan):
        return plan.compile_device_stacked(self.mesh)

    def stack_and_put(self, stacked: list[np.ndarray]):
        """device_put each [D, ...] stack with its partition sharding."""
        from redpanda_tpu.parallel.mesh import shard_to_mesh

        out = shard_to_mesh(self.mesh, *stacked)
        return out if isinstance(out, tuple) else (out,)

    # ------------------------------------------------------------ accounting
    def note_launch(self, shard_rows: list[int]) -> None:
        with self._decision_lock:
            self._n_launches += 1
            for d, n in enumerate(shard_rows):
                self._rows_per_device[d] += int(n)
        probes.coproc_mesh_launches.inc()
        for d, n in enumerate(shard_rows):
            if n:
                probes.coproc_mesh_device_rows(d).inc(n)

    def note_demotion(self) -> None:
        with self._decision_lock:
            self._n_demotions += 1
        probes.coproc_mesh_demotions.inc()

    # ------------------------------------------------------------ calibration
    def maybe_calibrate(self, governor, plan, stacked: list[np.ndarray],
                        flat: list[np.ndarray], n_rows: int) -> str:
        """The engine-sticky mesh-vs-single pin, measured on the FIRST
        representative launch's own columns: the SAME predicate over the
        SAME bytes, once as the stacked SPMD program over the mesh and
        once as the single-device program over the concatenated columns.
        The mesh must win by ``governor.PROBE_MARGIN`` — on co-located
        multi-chip ICI it does by construction, on a 1-core host-platform
        mesh it honestly self-demotes. Returns the decision."""
        with self._decision_lock:
            decision = self._decision
        if decision is not None:
            return decision
        if n_rows < PROBE_MIN_ROWS:
            # too small to be representative: run single WITHOUT pinning
            return "single"
        if not self._probe_run_lock.acquire(blocking=False):
            # a sibling launch is mid-calibration (seconds of jit): run
            # THIS launch single-device — bit-identical output — instead
            # of queueing behind the probe
            return "single"
        try:
            with self._decision_lock:
                decision = self._decision
            if decision is None:
                decision = self._calibrate(governor, plan, stacked, flat)
        finally:
            self._probe_run_lock.release()
        return decision

    def _calibrate(self, governor, plan, stacked, flat) -> str:
        from redpanda_tpu.coproc import faults

        try:
            t_mesh = t_single = float("inf")
            mesh_fn = self.predicate_fn(plan)
            args = self.stack_and_put(stacked)
            np.asarray(mesh_fn(*args))  # compile + warmup off the clock
            single_fn = plan.compile_device()
            np.asarray(single_fn(*flat))
            for _ in range(2):
                t0 = time.perf_counter()
                np.asarray(mesh_fn(*args))
                t_mesh = min(t_mesh, time.perf_counter() - t0)
                t0 = time.perf_counter()
                np.asarray(single_fn(*flat))
                t_single = min(t_single, time.perf_counter() - t0)
                # the single-device path's OTHER backend: on boxes where
                # the measured columnar pick is the numpy predicate, the
                # mesh must beat THAT, not a device leg nothing would run
                t0 = time.perf_counter()
                plan.eval_host_mask(flat)
                t_single = min(t_single, time.perf_counter() - t0)
        except Exception as exc:
            # a mesh whose probe blows up runs single-device forever —
            # classified so the demotion is visible on /metrics
            faults.note_failure("mesh_calibration", exc)
            logger.exception("mesh calibration failed; keeping single-device")
            with self._decision_lock:
                self._decision = "single"
            governor.record(
                MESH,
                "single",
                f"calibration FAILED ({faults.kind_of(exc)}); keeping the "
                "single-device path",
                {"error": faults.kind_of(exc), "devices": self.n_devices},
            )
            return "single"
        ratio = t_single / t_mesh if t_mesh > 0 else 0.0
        decision = "mesh" if ratio >= PROBE_MARGIN else "single"
        probe = {
            "t_single_ms": round(t_single * 1e3, 3),
            "t_mesh_ms": round(t_mesh * 1e3, 3),
            "speedup": round(ratio, 3),
            "devices": self.n_devices,
            "chosen": decision,
        }
        with self._decision_lock:
            self._decision = decision
            self._probe = probe
        logger.info("mesh calibration: %s", probe)
        governor.record(
            MESH,
            decision,
            f"measured predicate leg: single-device {t_single * 1e3:.3f} ms"
            f" vs {self.n_devices}-device mesh {t_mesh * 1e3:.3f} ms (mesh "
            f"must win {PROBE_MARGIN}x; engine-sticky)",
            dict(probe),
        )
        return decision

    # ------------------------------------------------------------ views
    def stats(self) -> dict:
        with self._decision_lock:
            out = {
                "devices": self.n_devices,
                "decision": self._decision,
                "launches": self._n_launches,
                "demotions": self._n_demotions,
                "rows_per_device": list(self._rows_per_device),
            }
            if self._probe is not None:
                out["probe"] = dict(self._probe)
        return out
