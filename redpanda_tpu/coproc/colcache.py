"""Device-resident column cache: exploded columns that survive launches.

A repeat script over the same partitions (bench re-runs, replayed reads,
any workload that re-submits an unchanged batch window) used to pay the
whole host ladder again — decompress, parse, find, extract — plus the H2D
replay of the very same predicate columns. The cache keys one launch's
columnar products by ``(script_id, content fingerprint of the batch
list)`` and hands them back whole: a hit skips every host dispatch stage,
and when the predicate ran on-device the stored ``cols_dev`` arrays are
already device-resident, so not a byte re-crosses the link.

Staleness is impossible by key construction, not by discipline: the
fingerprint covers each batch's payload CRC, base offset, record count,
payload length and compression, so an append, rewrite or reorder produces
a different key and a clean miss. The explicit invalidation hooks exist
for MEMORY, not correctness — the pacemaker drops a script's entries when
its input offsets advance (streaming never re-reads, so the bytes are
dead weight), and script unload drops them with the script.

Eviction is LRU under a byte budget (``coproc_device_column_cache_mb``;
0 disables the cache). ``stats()`` feeds ``TpuEngine.stats()["colcache"]``
→ ``/v1/coproc/status`` / ``rpk debug coproc`` / every BENCH json.
"""

from __future__ import annotations

import struct
import threading
from collections import OrderedDict

from redpanda_tpu.hashing.xx import xxhash64

def fingerprint(batches) -> int:
    """Content fingerprint of a batch list. The per-batch tuple (payload
    CRC, base offset, record count, payload length, attrs) pins both the
    bytes and their order; any append or rewrite changes it."""
    buf = bytearray()
    pack = struct.pack
    for b in batches:
        hdr = b.header
        buf += pack(
            "<qIiiI",
            hdr.base_offset,
            hdr.crc & 0xFFFFFFFF,
            hdr.record_count,
            len(b.payload),
            hdr.attrs & 0xFFFFFFFF,
        )
    return xxhash64(buf)


class Entry:
    """One launch's cached columnar products.

    ``cols`` are the HOST predicate column arrays (always present — the
    exact-fallback path and the backend probe need host arrays);
    ``cols_dev`` the device-put twins, recorded by the first device
    dispatch so later hits launch without an H2D. ``exploded`` is kept
    only for passthrough plans (their harvest gathers output bytes from
    the joined blob); projection plans store the packed rows + ok mask
    instead. Entries are immutable after ``put`` — every consumer is
    read-only, which is what makes a hit bit-identical to a cold run.
    """

    __slots__ = (
        "n", "n_pad", "ranges", "cols", "cols_dev", "proj_data", "proj_ok",
        "exploded", "parse_mode", "nbytes",
    )

    def __init__(self, *, n, n_pad, ranges, cols, proj_data=None,
                 proj_ok=None, exploded=None, parse_mode="staged"):
        self.n = n
        self.n_pad = n_pad
        self.ranges = list(ranges)
        self.cols = cols
        self.cols_dev = None
        self.proj_data = proj_data
        self.proj_ok = proj_ok
        self.exploded = exploded
        self.parse_mode = parse_mode
        self.nbytes = self._measure()

    def _measure(self) -> int:
        total = 0
        for c in self.cols or ():
            total += getattr(c, "nbytes", 0)
        if self.proj_ok is not None:
            total += self.proj_ok.nbytes
        for item in self.proj_data or ():
            for part in item[1:]:
                total += getattr(part, "nbytes", 0)
        if self.exploded is not None:
            j = self.exploded.joined
            total += getattr(j, "nbytes", len(j))
            total += self.exploded.offsets.nbytes + self.exploded.sizes.nbytes
        return total


class DeviceColumnCache:
    """Keyed LRU over Entry objects with a byte budget."""

    def __init__(self, budget_bytes: int):
        from redpanda_tpu.coproc import lockwatch

        self._lock = lockwatch.wrap(
            threading.Lock(), "DeviceColumnCache._lock"
        )
        self._budget = max(0, int(budget_bytes))
        self._entries: "OrderedDict[tuple, Entry]" = OrderedDict()
        self._bytes = 0
        self._hits = 0
        self._misses = 0
        self._evictions = 0
        self._invalidations = 0
        # memory pressure (resource_mgmt budget plane): while CRITICAL the
        # effective budget halves — LRU entries beyond it evict immediately
        # and stay out until the pressure clears
        self._pressure = False
        self._pressure_evictions = 0

    def _effective_budget(self) -> int:
        return self._budget // 2 if self._pressure else self._budget

    def set_pressure(self, critical: bool) -> int:
        """Enter/leave the reduced-budget posture. Entering evicts LRU
        entries beyond the halved budget and counts them as pressure
        evictions; leaving restores the configured budget (repopulation
        happens naturally on later misses). Idempotent per level."""
        evicted = 0
        with self._lock:
            self._pressure = bool(critical)
            budget = self._effective_budget()
            while self._bytes > budget and self._entries:
                _, entry = self._entries.popitem(last=False)
                self._bytes -= entry.nbytes
                self._evictions += 1
                self._pressure_evictions += 1
                evicted += 1
        return evicted

    def lookup(self, key: tuple) -> Entry | None:
        """The cached entry (refreshing LRU order) or None. Misses carry
        no side state: every miss — launch-wide, or per shard on the mesh
        lane — populates on the SAME launch, so nothing needs to recognize
        a repeating workload."""
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                self._entries.move_to_end(key)
                self._hits += 1
                return entry
            self._misses += 1
            return None

    def put(self, key: tuple, entry: Entry) -> bool:
        """Insert + evict LRU down to the budget. An entry bigger than
        the whole budget is refused outright (storing it would evict
        everything for a guaranteed-evicted tenant)."""
        with self._lock:
            budget = self._effective_budget()
            if entry.nbytes > budget:
                return False
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= old.nbytes
            self._entries[key] = entry
            self._bytes += entry.nbytes
            while self._bytes > budget and len(self._entries) > 1:
                _, evicted = self._entries.popitem(last=False)
                self._bytes -= evicted.nbytes
                self._evictions += 1
            if self._bytes > budget:
                # the just-inserted entry is the only one and still over
                # budget (budget shrank below it): drop it too
                self._entries.popitem(last=False)
                self._bytes -= entry.nbytes
                self._evictions += 1
                return False
        return True

    def invalidate(self, script_id: int | None = None) -> int:
        """Drop entries (all scripts when script_id is None). Returns the
        number dropped. Correctness never depends on this — the key is
        content-addressed — it reclaims memory for inputs that moved on."""
        with self._lock:
            if script_id is None:
                dropped = len(self._entries)
                self._entries.clear()
                self._bytes = 0
            else:
                keys = [k for k in self._entries if k[0] == script_id]
                for k in keys:
                    self._bytes -= self._entries.pop(k).nbytes
                dropped = len(keys)
            self._invalidations += dropped
        return dropped

    def reset(self) -> None:
        """Test hook: drop entries AND zero the counters."""
        with self._lock:
            self._entries.clear()
            self._bytes = 0
            self._hits = self._misses = 0
            self._evictions = self._invalidations = 0
            self._pressure = False
            self._pressure_evictions = 0

    def stats(self) -> dict:
        with self._lock:
            return {
                "hits": self._hits,
                "misses": self._misses,
                "entries": len(self._entries),
                "bytes": self._bytes,
                "budget_bytes": self._budget,
                "effective_budget_bytes": self._effective_budget(),
                "evictions": self._evictions,
                "invalidations": self._invalidations,
                "pressure": self._pressure,
                "pressure_evictions": self._pressure_evictions,
            }
