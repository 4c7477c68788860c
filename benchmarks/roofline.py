#!/usr/bin/env python3
"""The payload lane's device program against the chip's HBM roofline: the
bytes and operations a launch needs, computed from its shapes, and the
share of the least time those take that the program's measured time is.

    python3 benchmarks/roofline.py <window.xplane.pb> --r-out 70
    python3 benchmarks/roofline.py <window.xplane.pb> --mask-only

reads a kept trace (``run.py --trace 1 --keep-trace <path>``), sums the
``jit_rp_payload_transform`` module's runs on the device plane and prints
one JSON object. Not a ledger metric yet: ``readers.py`` has no kind that
divides by a trace time (PERF.md section 7). Only JAX's own reader is used
(through ``trace_reduce.py``); nothing of the program. Checked on the
recorded trace in ``testdata/`` by ``test_roofline.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import trace_reduce  # noqa: E402

# Published peaks of one chip, keyed by ``device_kind`` (Google Cloud
# documentation, "TPU v5e": 819 GB/s of HBM, 393 TOP/s in int8). A device
# that is not here is an error, not a default.
PEAKS = {"TPU v5 lite": {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12}}

# trailing metadata columns of a staged input row and of a result row
# (``ops/pipeline.py`` IN_META / OUT_META)
IN_META = OUT_META = 8
MODULE = "jit_rp_payload_transform"

_ROWS = re.compile(r"\S+ \w+\[(\d+),")  # "<op> <dtype>[rows, ...": a matrix an operation writes


def payload_program_bytes(n_pad: int, stride_in: int, r_out: int, mask_only: bool) -> int:
    """Bytes one launch has to move through HBM: the staged matrix in,
    ``n_pad x (stride_in + 8)``, and its result out, ``n_pad x (r_out + 8)``
    for a result matrix or ``n_pad / 8`` for a bit-packed keep mask."""
    out = n_pad // 8 if mask_only else n_pad * (r_out + OUT_META)
    return n_pad * (stride_in + IN_META) + out


def payload_program_ops(
    n_pad: int, stride_in: int, pattern_lens: list[int], window_widths: list[int]
) -> int:
    """Byte operations one launch needs: one compare for every pattern byte
    at every start of every row, and one gathered byte for every position of
    every projection window."""
    scans = sum(length * (stride_in - length + 1) for length in pattern_lens)
    return n_pad * (scans + sum(window_widths))


def module_runs(profile, module: str = MODULE) -> list[tuple[float, int]]:
    """(seconds, rows) of every run of ``module`` on the first device plane:
    rows is the largest leading dimension of the matrices that operations
    inside the run write, which is the run's row bucket (a flattened
    operand such as a gather's ``u8[2129920]`` is no matrix)."""
    for plane in profile.planes:
        if not plane.name.startswith(trace_reduce.DEVICE_PLANE):
            continue
        lines = {line.name: line for line in plane.lines}
        ops = sorted(
            (int(ev.start_ns), ev.name)
            for name in trace_reduce.OP_LINES if name in lines
            for ev in lines[name].events
        )
        runs = []
        for name in trace_reduce.MODULE_LINES:
            for ev in lines[name].events if name in lines else ():
                if ev.name.startswith(module + "("):
                    start, end = int(ev.start_ns), int(ev.start_ns + ev.duration_ns)
                    rows = [int(m.group(1)) for at, op in ops if start <= at < end
                            for m in [_ROWS.match(trace_reduce.short_op(op))] if m]
                    runs.append((ev.duration_ns / 1e9, max(rows, default=0)))
        return runs
    return []


def roofline(
    profile, *, stride_in: int, r_out: int, mask_only: bool,
    device_kind: str = "TPU v5 lite", module: str = MODULE,
) -> dict | None:
    """The module's runs in the trace: their count, seconds, the bytes
    their shapes need and the share of the HBM roofline that is
    (``least_s / seconds``, in percent); None when the module never ran.
    The bytes bound it: the byte operations of ``payload_program_ops`` take
    a twentieth of the bytes' time at the chip's int8 peak."""
    peak = PEAKS[device_kind]["hbm_bytes_per_s"]
    runs = module_runs(profile, module)
    if not runs:
        return None
    seconds = sum(s for s, _ in runs)
    nbytes = sum(payload_program_bytes(rows, stride_in, r_out, mask_only) for _, rows in runs)
    return {
        "module": module,
        "runs": len(runs),
        "rows": sorted({rows for _, rows in runs}),
        "seconds": seconds,
        "ms_per_run": 1e3 * seconds / len(runs),
        "bytes": nbytes,
        "least_s": nbytes / peak,
        "bytes_per_s": nbytes / seconds,
        "roofline_share_pct": 100.0 * nbytes / peak / seconds,
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("xplane")
    ap.add_argument("--stride-in", type=int, default=1024)
    ap.add_argument("--r-out", type=int, default=70)
    ap.add_argument("--mask-only", action="store_true")
    ap.add_argument("--device-kind", default="TPU v5 lite")
    ap.add_argument("--module", default=MODULE)
    args = ap.parse_args()
    got = roofline(
        trace_reduce.load(args.xplane), stride_in=args.stride_in, r_out=args.r_out,
        mask_only=args.mask_only, device_kind=args.device_kind, module=args.module,
    )
    if got is None:
        print(f"no run of {args.module} in {args.xplane}", file=sys.stderr)
        return 1
    print(json.dumps(got))
    return 0


if __name__ == "__main__":
    sys.exit(main())
