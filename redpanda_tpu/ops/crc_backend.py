"""Produce-path CRC validation backend: a measured adapter-boundary choice.

The reference verifies the Kafka CRC-32C of every produced batch inline in
its wire adapter (kafka_batch_adapter.cc:93-121, castagnoli over
attributes..records). SURVEY §7 phase 3 planned to swap that call site for a
TPU kernel; this module is where the swap would happen:

- The MXU CRC kernel (ops/crc32c_device.py) is bit-exact but needs the wire
  bytes ON DEVICE; the produce path's bytes arrive on the host NIC, so the
  kernel's cost includes shipping every region across the device link.
- The host side is ONE core running the native SSE4.2 loop
  (native/redpanda_native.cc rp_crc32c). Device vs host on a local chip:
  not measured yet (ROADMAP A4).

So the adapter boundary *chooses per process*: `CrcBackend.pick()` probes
both paths once on representative rows and selects the faster one, and its
decision record names why the device side was unavailable when it was. The
produce handler (kafka/server/handlers.py) and the bench (config 1) consume
this decision instead of hard-coding either side.
"""

from __future__ import annotations

import logging
import time
from dataclasses import dataclass

import numpy as np

from redpanda_tpu.hashing.crc32c import crc32c

logger = logging.getLogger("rptpu.ops.crc_backend")


@dataclass
class CrcDecision:
    backend: str  # "host" | "device"
    host_batches_per_sec: float
    device_batches_per_sec: float
    # why the device side has no rate: not probed, or the probe's failure
    device_error: str | None = None

    @property
    def ratio_device_vs_host(self) -> float:
        return self.device_batches_per_sec / max(self.host_batches_per_sec, 1e-9)


class CrcBackend:
    """Validate claimed batch CRCs over many batches, host or device."""

    def __init__(self, backend: str = "host", decision: CrcDecision | None = None):
        assert backend in ("host", "device")
        self.backend = backend
        self.decision = decision
        self._validators: dict[int, object] = {}

    # ------------------------------------------------------------ validate
    def validate(self, regions: list[bytes], claimed) -> np.ndarray:
        """ok[i] = crc32c(regions[i]) == claimed[i]."""
        claimed = np.asarray(claimed, dtype=np.uint32)
        if self.backend == "host":
            return np.fromiter(
                (crc32c(r) == int(c) for r, c in zip(regions, claimed)),
                dtype=bool,
                count=len(regions),
            )
        return self._validate_device(regions, claimed)

    def _validate_device(self, regions: list[bytes], claimed) -> np.ndarray:
        from redpanda_tpu.ops.packing import pack_rows
        from redpanda_tpu.ops.pipeline import make_batch_validator

        n = len(regions)
        r = max((len(x) for x in regions), default=1)
        r = 1 << (r - 1).bit_length()  # shape-bucketed stride
        rows, lens = pack_rows(regions, r)
        validate = self._validators.setdefault(r, make_batch_validator(r))
        return np.asarray(validate(rows, lens, claimed))[:n]

    # ------------------------------------------------------------ probing
    @classmethod
    def pick(
        cls,
        sample_regions: list[bytes] | None = None,
        reps: int = 3,
        probe_device: bool = True,
    ) -> "CrcBackend":
        """Measure both paths on sample rows; return the faster backend.

        A device probe failure (no device, compile refusal, mismatch)
        falls back to host — correctness never depends on the device —
        and is logged and kept in ``decision.device_error``. With
        ``probe_device=False`` only the host rate is measured (a device
        probe costs a jit compile, too much for broker startup; the bench
        records the full measurement instead).
        """
        if sample_regions is None:
            rng = np.random.default_rng(0)
            sample_regions = [rng.bytes(1536) for _ in range(64)]
        claimed = np.array([crc32c(r) for r in sample_regions], dtype=np.uint32)

        host = cls("host")
        t0 = time.perf_counter()
        for _ in range(reps):
            ok = host.validate(sample_regions, claimed)
        host_rate = reps * len(sample_regions) / (time.perf_counter() - t0)
        assert ok.all()

        dev = None
        dev_rate = 0.0
        device_error = None if probe_device else "not probed"
        if probe_device:
            try:
                dev = cls("device")
                dev.validate(sample_regions, claimed)  # compile off the clock
                t0 = time.perf_counter()
                for _ in range(reps):
                    ok = dev.validate(sample_regions, claimed)
                dev_rate = reps * len(sample_regions) / (time.perf_counter() - t0)
                if not ok.all():
                    raise RuntimeError("device CRC mismatch on probe rows")
            except Exception as exc:
                logger.warning("device CRC probe failed: %r", exc)
                device_error = repr(exc)
                dev = None
                dev_rate = 0.0

        decision = CrcDecision(
            "device" if dev_rate > host_rate else "host", host_rate, dev_rate,
            device_error,
        )
        chosen = dev if (decision.backend == "device" and dev is not None) else cls("host")
        chosen.decision = decision
        return chosen


_default: CrcBackend | None = None
_default_lock = __import__("threading").Lock()


def default_backend() -> CrcBackend:
    """Process-wide backend for the produce path, probed lazily on first use.

    Device probing is opt-in via RP_CRC_PROBE_DEVICE=1 (see pick()); the
    bench's config 1 records the measured comparison.
    """
    global _default
    if _default is None:
        with _default_lock:
            if _default is None:
                import os

                _default = CrcBackend.pick(
                    probe_device=os.environ.get("RP_CRC_PROBE_DEVICE") == "1"
                )
    return _default


async def default_backend_async() -> CrcBackend:
    """Async-safe accessor: the first call's probe (and, with
    RP_CRC_PROBE_DEVICE=1, a device jit compile) runs in a worker
    thread so the event loop keeps serving raft heartbeats; later calls
    return the cached instance without a thread hop."""
    if _default is not None:
        return _default
    import asyncio

    return await asyncio.to_thread(default_backend)
