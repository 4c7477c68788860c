"""Device-side raft batched CRC + vote aggregation (BASELINE config 5).

The reference validates batch CRCs one at a time in host code
(kafka_batch_adapter.cc:93, record_utils.cc:82) and counts votes and
heartbeat acks one message at a time (heartbeat_manager.cc:155-204).
The batched analogues run as ONE device program over the
``[partition, batch, record]`` axis (parallel/collectives.py
``make_crc_vote_step``): every batch of every partition CRC-validated by
the vmapped table-driven CRC kernel (ops/crc32c_device.py), ack/vote
bits tallied per group by a single mesh psum.

Where that program runs is a MEASURED decision, exactly like the coproc
engine's probes: the first representative validation times the device
step against the host ``crc32c_many`` oracle on the same rows and the
process keeps the winner (``governor.PROBE_MARGIN`` posture, journaled
in the governor's ``mesh`` domain). Which side wins on a local chip is not
measured yet (ROADMAP B8). Either backend is bit-exact — ``validate`` and ``tally_votes`` return
identical arrays, only the executor changes.

Consumers: ``Consensus._do_handle_append`` (follower-side batched CRC
reject, config ``raft_device_crc_validate``) and
``HeartbeatManager.send_heartbeats`` (per-tick cross-group ack tally,
config ``raft_device_vote_tally``).
"""

from __future__ import annotations

import logging
import threading
import time

import numpy as np

from redpanda_tpu.coproc import governor as gov_mod
from redpanda_tpu.hashing.crc32c import crc32c, crc32c_many

logger = logging.getLogger("rptpu.raft.device_plane")

# probe floor: fewer rows than this stay on the host oracle without
# pinning the process-wide decision (a 3-batch heartbeat tick is not
# representative of a recovery-scan burst)
PROBE_MIN_ROWS = 64

# ceiling on the padded [n, bucket(max_len)] device matrix: the pack
# amplifies a width-skewed blob (512 x 1KB + one 8MB region = a ~4GB
# matrix) — past this, validate unpadded on the host instead
_PACK_BUDGET_BYTES = 64 << 20


def _bucket(r: int) -> int:
    b = 64
    while b < r:
        b *= 2
    return b


def _host_validate(regions: list[bytes], claimed: np.ndarray) -> np.ndarray:
    """The unpadded host oracle: crc each region where it lies."""
    n = len(regions)
    got = np.fromiter((crc32c(x) for x in regions), np.uint32, n)
    lens = np.fromiter((len(x) for x in regions), np.int64, n)
    return (got == claimed) & (lens > 0)


class RaftDevicePlane:
    """Process-scoped batched CRC/vote executor with a measured backend.

    ``mesh`` (optional): a ``jax.sharding.Mesh`` over the partition axis
    — when given (>= 2 devices) the device leg runs the sharded
    ``make_crc_vote_step`` with the vote psum; without one it runs the
    single-device vmapped kernel. The host leg is ``crc32c_many`` +
    ``np.sum`` — the oracle the device legs are tested against.
    """

    def __init__(self, mesh=None, probe: bool = True):
        self.mesh = mesh
        self.n_devices = int(mesh.devices.size) if mesh is not None else 1
        self._probe_enabled = bool(probe)
        self._decision: str | None = None if probe else "device"
        self._probe: dict | None = None
        self._lock = threading.Lock()
        # serializes the one multi-second calibration; siblings that
        # lose the race serve their call on the host oracle instead of
        # queueing a duplicate jit compile (MeshRunner._probe_run_lock
        # posture)
        self._probe_run_lock = threading.Lock()
        self._steps: dict[object, object] = {}
        self._n_validations = 0
        self._n_tallies = 0
        self._rows_validated = 0

    # ------------------------------------------------------------ decision
    @property
    def decision(self) -> str | None:
        with self._lock:
            return self._decision

    def _device_step(self, r: int):
        with self._lock:
            fn = self._steps.get(r)
        if fn is None:
            if self.mesh is not None:
                from redpanda_tpu.parallel.collectives import make_crc_vote_step

                fn = make_crc_vote_step(self.mesh, r)
            else:
                from redpanda_tpu.ops.crc32c_device import make_crc_fn

                fn = make_crc_fn(r)
            with self._lock:
                fn = self._steps.setdefault(r, fn)
        return fn

    def _run_device(self, rows, lens, claimed, votes):
        """(ok, tally) on the device backend; rows is [N, r] host-packed."""
        n, r = rows.shape
        if self.mesh is not None:
            d = self.n_devices
            n_pad = -(-n // d) * d  # round N up to a multiple of D
            if n_pad != n:
                rows = np.concatenate(
                    [rows, np.zeros((n_pad - n, r), np.uint8)]
                )
                lens = np.concatenate([lens, np.zeros(n_pad - n, np.int32)])
                claimed = np.concatenate(
                    [claimed, np.zeros(n_pad - n, np.uint32)]
                )
            g = votes.shape[1] if votes is not None and votes.ndim == 2 else 1
            v = (
                votes
                if votes is not None
                else np.zeros((d, g), np.uint8)
            )
            step = self._device_step(r)
            ok, _bad, tally = step(
                rows.reshape(d, n_pad // d, r),
                lens.reshape(d, n_pad // d),
                claimed.reshape(d, n_pad // d),
                v,
            )
            return np.asarray(ok).reshape(n_pad)[:n], np.asarray(tally)
        crc = self._device_step(r)
        got = np.asarray(crc(rows, lens))
        ok = (got == claimed) & (lens > 0)
        tally = (
            votes.astype(np.int32).sum(axis=0)
            if votes is not None
            else np.zeros(0, np.int32)
        )
        return ok, tally

    def _calibrate(self, regions, rows, lens, claimed) -> str:
        """Host-vs-device pin on representative rows; journaled (mesh
        domain) so ``rpk debug governor`` reconstructs the choice."""
        try:
            # time the host leg that actually SERVES a "host" pin
            # (_host_validate, unpadded per-region crcs) — measuring
            # crc32c_many over the padded matrix would journal a verdict
            # about a code path the pin never runs
            t0 = time.perf_counter()
            host_ok = _host_validate(regions, claimed)
            t_host = time.perf_counter() - t0
            self._run_device(rows, lens, claimed, None)  # compile + warm
            t0 = time.perf_counter()
            dev_ok, _ = self._run_device(rows, lens, claimed, None)
            t_dev = time.perf_counter() - t0
            if not np.array_equal(host_ok, dev_ok):
                raise RuntimeError("device CRC mismatch vs host oracle")
            if self.mesh is not None:
                # warm the vote aggregator HERE, off the event loop
                # (calibration runs under asyncio.to_thread): the
                # heartbeat tick calls tally_votes on the loop and must
                # never pay a first-use compile there
                from redpanda_tpu.parallel.collectives import (
                    make_vote_aggregator,
                )

                fn = make_vote_aggregator(self.mesh)
                np.asarray(
                    fn(np.zeros((self.n_devices, 1), np.uint8))
                )
                with self._lock:
                    self._steps.setdefault("vote", fn)
        except Exception as exc:
            logger.exception("raft device-plane probe failed; keeping host")
            with self._lock:
                self._decision = "host"
            gov_mod.journal_record(
                gov_mod.MESH,
                "host",
                f"raft CRC/vote probe FAILED ({type(exc).__name__}); "
                "keeping the host oracle",
                {"devices": self.n_devices},
            )
            return "host"
        ratio = t_host / t_dev if t_dev > 0 else 0.0
        decision = "device" if ratio >= gov_mod.PROBE_MARGIN else "host"
        probe = {
            "t_host_ms": round(t_host * 1e3, 3),
            "t_device_ms": round(t_dev * 1e3, 3),
            "speedup": round(ratio, 3),
            "devices": self.n_devices,
            "rows": int(len(lens)),
            "chosen": decision,
        }
        with self._lock:
            self._decision = decision
            self._probe = probe
        gov_mod.journal_record(
            gov_mod.MESH,
            decision,
            f"raft batched CRC/vote probe: host {t_host * 1e3:.3f} ms vs "
            f"device ({self.n_devices} dev) {t_dev * 1e3:.3f} ms (device "
            f"must win {gov_mod.PROBE_MARGIN}x; process-sticky)",
            dict(probe),
        )
        return decision

    # ------------------------------------------------------------ API
    def validate(self, regions: list[bytes], claimed) -> np.ndarray:
        """ok[i] = crc32c(regions[i]) == claimed[i] & non-empty — batched
        over all regions, on the measured backend (bit-exact on both)."""
        n = len(regions)
        claimed = np.asarray(claimed, dtype=np.uint32)
        if n == 0:
            return np.zeros(0, dtype=bool)
        with self._lock:
            decision = self._decision
            self._n_validations += 1
            self._rows_validated += n
        if decision == "host" or (decision is None and n < PROBE_MIN_ROWS):
            # host-pinned (or too small to probe on): crc each region in
            # place — no reason to pay the O(n * max_len) padded-matrix
            # pack the device leg needs
            return _host_validate(regions, claimed)
        r = _bucket(max(len(x) for x in regions))
        if n * r > _PACK_BUDGET_BYTES:
            # pathological width skew (one outsized region buckets EVERY
            # row to its width): the padded device matrix would amplify
            # the blob by orders of magnitude — validate unpadded on the
            # host, without pinning anything
            return _host_validate(regions, claimed)
        from redpanda_tpu.ops.packing import pack_rows

        rows = lens = None
        if decision is None:
            if not self._probe_run_lock.acquire(blocking=False):
                # a sibling thread is mid-calibration: answer on the
                # host oracle (bit-exact) rather than stacking another
                # seconds-long jit compile behind it — checked BEFORE
                # the pack so the lock-busy path never builds the
                # padded matrix it would throw away
                return _host_validate(regions, claimed)
            try:
                with self._lock:
                    decision = self._decision
                if decision is None:
                    rows, lens = pack_rows(regions, r)
                    lens = np.asarray(lens, dtype=np.int32)
                    decision = self._calibrate(regions, rows, lens, claimed)
            finally:
                self._probe_run_lock.release()
        if decision == "device":
            try:
                if rows is None:
                    rows, lens = pack_rows(regions, r)
                    lens = np.asarray(lens, dtype=np.int32)
                ok, _ = self._run_device(rows, lens, claimed, None)
                return ok
            except Exception:
                # a dying device leg degrades to the oracle, exactly
                logger.exception("device CRC leg failed; host fallback")
        return _host_validate(regions, claimed)

    def tally_votes(self, votes: np.ndarray) -> np.ndarray:
        """Per-group vote/ack tally over a [voters, groups] bit matrix —
        the batched analogue of counting one reply at a time. The mesh
        backend lays voters over the 'p' axis and psums; the host oracle
        is ``np.sum(axis=0)``. Identical int32 counts either way."""
        votes = np.ascontiguousarray(votes, dtype=np.uint8)
        with self._lock:
            self._n_tallies += 1
            decision = self._decision
        if (
            decision == "device"
            and self.mesh is not None
            and votes.shape[0] == self.n_devices
        ):
            try:
                from redpanda_tpu.parallel.collectives import (
                    make_vote_aggregator,
                )

                with self._lock:
                    fn = self._steps.get("vote")
                if fn is None:
                    fn = make_vote_aggregator(self.mesh)
                    with self._lock:
                        fn = self._steps.setdefault("vote", fn)
                return np.asarray(fn(votes))
            except Exception:
                logger.exception("device vote tally failed; host fallback")
        return votes.astype(np.int32).sum(axis=0)

    def stats(self) -> dict:
        with self._lock:
            out = {
                "decision": self._decision,
                "devices": self.n_devices,
                "validations": self._n_validations,
                "rows_validated": self._rows_validated,
                "tallies": self._n_tallies,
            }
            if self._probe is not None:
                out["probe"] = dict(self._probe)
        return out


# broker wiring (app.py _start_cluster_services reads the config knobs):
# both consumers are off by default — the measured probe decides WHERE a
# validation runs, these flags decide WHETHER the call sites run at all.
# The mesh knobs mirror the coproc engine's multi-chip topology: with
# >= 2 devices the default plane's device leg is the sharded
# make_crc_vote_step (vote psum), built lazily on first use.
_crc_validate = False
_vote_tally = False
_mesh_devices = 0
_mesh_backend: str | None = None


def configure(
    crc_validate: bool | None = None,
    vote_tally: bool | None = None,
    mesh_devices: int | None = None,
    mesh_backend: str | None = None,
) -> None:
    global _crc_validate, _vote_tally, _mesh_devices, _mesh_backend
    if crc_validate is not None:
        _crc_validate = bool(crc_validate)
    if vote_tally is not None:
        _vote_tally = bool(vote_tally)
    if mesh_devices is not None:
        _mesh_devices = int(mesh_devices)
    if mesh_backend is not None:
        _mesh_backend = mesh_backend or None


def crc_validate_enabled() -> bool:
    return _crc_validate


def vote_tally_enabled() -> bool:
    return _vote_tally


_default: RaftDevicePlane | None = None
_default_lock = threading.Lock()


def default_plane() -> RaftDevicePlane:
    """Process-wide plane, built lazily on first use. With configured
    mesh knobs (>= 2 devices available) the device leg is the sharded
    crc+vote step; otherwise the single-device vmapped kernel. A mesh
    that fails to build degrades to single-device, never to a crash."""
    global _default
    if _default is None:
        with _default_lock:
            if _default is None:
                mesh = None
                if _mesh_devices >= 2:
                    try:
                        from redpanda_tpu.parallel.mesh import partition_mesh

                        mesh = partition_mesh(
                            n_devices=_mesh_devices, backend=_mesh_backend
                        )
                        if mesh.devices.size < 2:
                            mesh = None
                    except Exception:
                        logger.exception(
                            "raft device-plane mesh init failed; "
                            "single-device leg"
                        )
                        mesh = None
                _default = RaftDevicePlane(mesh=mesh)
    return _default


def reset_default_plane() -> None:
    """Test hook: forget the process plane (and its sticky decision)."""
    global _default
    with _default_lock:
        _default = None
