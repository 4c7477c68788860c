"""From a ``jax.profiler`` trace (``.xplane.pb``) to the device numbers:
busy and idle time, time per device operation, and idle gaps laid at what
the host was doing. A trace in which nothing ran on the device reads 100%
idle, the whole of it one gap.

Only JAX's own reader is used (``jax.profiler.ProfileData``); nothing of
the program. Checked on the recorded trace in ``testdata/`` by
``test_benchmark.py``.
"""

from __future__ import annotations

import glob
import json
import os
import re
from collections import defaultdict

DEVICE_PLANE = "/device:TPU:"
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)


_HLO = re.compile(r"^%?([\w.\-]+) = \(?(\w+\[[\d,]*\])")


def short_op(name: str) -> str:
    """``%fusion.1 = u8[4096,1032]{...} fusion(...)`` -> ``fusion.1 u8[4096,1032]``:
    an operation's name and the first shape it writes, which tells the row
    buckets apart."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:64]


def merge(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Union of [start, end) intervals, ascending and disjoint."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def _events(line) -> list[tuple[int, int, str]]:
    return [
        (int(ev.start_ns), int(ev.start_ns + ev.duration_ns), ev.name) for ev in line.events
    ]


def reduce_profile(profile, span_s: float | None = None, top: int = 10) -> dict:
    """``span_s``: how long the capture ran by the harness's clock, which is
    the window of a trace that holds no event at all (nothing called into
    the runtime while it ran)."""
    device_ops: dict[str, list[tuple[int, int, str]]] = {}
    device_modules: dict[str, list[tuple[int, int, str]]] = {}
    host: list[tuple[int, int, str]] = []
    lo, hi = None, None
    for plane in profile.planes:
        for line in plane.lines:
            evs = _events(line)
            if not evs:
                continue
            lo = min(e[0] for e in evs) if lo is None else min(lo, min(e[0] for e in evs))
            hi = max(e[1] for e in evs) if hi is None else max(hi, max(e[1] for e in evs))
            if plane.name.startswith(DEVICE_PLANE):
                if line.name in OP_LINES:
                    device_ops.setdefault(plane.name, []).extend(evs)
                elif line.name in MODULE_LINES:
                    device_modules.setdefault(plane.name, []).extend(evs)
            elif plane.name.startswith("/host:"):
                host.extend(evs)
    if lo is None:
        if span_s is None:
            raise ValueError("the trace holds no event")
        lo, hi = 0, int(span_s * 1e9)
    window_ns = hi - lo
    chips = sorted(set(device_ops) | set(device_modules))
    busy_ns = []
    merged_by_chip = {}
    for chip in chips:
        # an operation ran = an op event, or, where the op line is not
        # there, its module
        evs = device_ops.get(chip) or device_modules.get(chip, [])
        merged = merge([(s, e) for s, e, _ in evs])
        merged_by_chip[chip] = merged
        busy_ns.append(sum(e - s for s, e in merged))
    busy_s = (sum(busy_ns) / len(busy_ns) / 1e9) if busy_ns else 0.0

    per_op: dict[str, float] = defaultdict(float)
    for chip in chips:
        for s, e, name in device_ops.get(chip) or device_modules.get(chip, []):
            per_op[short_op(name)] += (e - s) / 1e9

    # idle gaps of the first chip, each laid at the host span that covers
    # most of it
    gaps: dict[str, float] = defaultdict(float)
    merged = merged_by_chip[chips[0]] if chips else []
    edges = [lo] + [x for s, e in merged for x in (s, e)] + [hi]
    spans = sorted(host)
    for gs, ge in zip(edges[0::2], edges[1::2]):
        if ge - gs > 0:
            gaps[_host_over(spans, gs, ge)] += (ge - gs) / 1e9

    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_s,
        "idle_share_pct": 100.0 * (1.0 - busy_s / (window_ns / 1e9)),
        "chips": len(chips),
        "op_s": dict(per_op),
        "breakdown": {
            "device_ops": _top(per_op, top),
            "idle_gaps": _top(gaps, top),
        },
    }


def _top(seconds_by_name: dict[str, float], top: int) -> list[list]:
    return [[n, t] for n, t in sorted(seconds_by_name.items(), key=lambda x: -x[1])[:top]]


def totals(reductions: list[dict]) -> dict:
    """``busy_s`` and ``window_s`` over every capture of one run."""
    return {key: sum(r[key] for r in reductions) for key in ("busy_s", "window_s")}


def top_ops(reductions: list[dict], top: int = 10) -> list[list]:
    """The device operations that took most time over every capture of one run."""
    per_op: dict[str, float] = defaultdict(float)
    for r in reductions:
        for name, t in r["op_s"].items():
            per_op[name] += t
    return _top(per_op, top)


def _host_over(spans: list[tuple[int, int, str]], gs: int, ge: int) -> str:
    """Name of the host span that overlaps [gs, ge) most, if it covers half
    of the gap or more; the innermost (shortest) of those that cover it
    whole. A gap no span covers by half is the host outside the runtime."""
    best_name, best_cover, best_len = None, 0, None
    for s, e, name in spans:
        if s >= ge:
            break
        cover = min(e, ge) - max(s, gs)
        if cover <= 0:
            continue
        length = e - s
        if cover > best_cover or (cover == best_cover and length < best_len):
            best_name, best_cover, best_len = name, cover, length
    if best_name is None or 2 * best_cover < ge - gs:
        return "no_host_span"
    return "host_in:" + best_name.split("(")[0].strip()[:48].replace(" ", "_")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def describe(profile, limit: int = 12) -> str:
    """What a trace holds, for a look by hand."""
    lines = []
    for plane in profile.planes:
        lines.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            names = sorted({e.name for e in evs[:2000]})[:limit]
            lines.append(f"  line {line.name!r}: {len(evs)} events, e.g. {names}")
            for e in evs[:2]:
                lines.append(f"    {e.name!r} start {e.start_ns} dur {e.duration_ns} "
                             f"stats {dict(list(e.stats)[:12])}")
    return "\n".join(lines)


if __name__ == "__main__":
    import sys

    prof = load(sys.argv[1])
    print(describe(prof))
    print(json.dumps(reduce_profile(prof), indent=1))
