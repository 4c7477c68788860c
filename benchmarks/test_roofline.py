"""Tests of ``roofline.py``. Not collected by the repo's tier-1 command;
run by hand beside ``test_benchmark.py``:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/test_roofline.py -q
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import roofline  # noqa: E402
import trace_reduce  # noqa: E402

# PR 23's capture of json64p-v1.catchup, when a filter-only launch still
# took the matrix road at r_out 1,024; JAX named the module ``jit_run`` then
RECORDED = os.path.join(HERE, "testdata", "v1_catchup.xplane.pb")


def test_payload_program_bytes_by_result_format():
    # the map of json64p-v1map: 1,032 B a staged row in, 78 B a result row out
    assert roofline.payload_program_bytes(32768, 1024, 70, False) == 32768 * 1110 == 36_372_480
    # a filter's keep mask: one bit a row
    assert roofline.payload_program_bytes(32768, 1024, 1024, True) == 32768 * 1032 + 4096
    # the matrix road of a filter, as PR 26 measured it: the row back whole
    assert roofline.payload_program_bytes(32768, 1024, 1024, False) == 32768 * 2064
    assert roofline.payload_program_bytes(0, 1024, 70, False) == 0


def test_payload_program_ops_count_scans_and_windows():
    # json64p-v1map: scans of 15, 7 and 7 bytes, windows of 12 and 65
    per_row = 15 * 1010 + 2 * 7 * 1018 + 12 + 65
    assert roofline.payload_program_ops(32768, 1024, [15, 7, 7], [12, 65]) == 32768 * per_row
    # far under the bytes' time at the chip's peaks: the program is HBM-bound
    peak = roofline.PEAKS["TPU v5 lite"]
    assert 32768 * per_row / peak["int8_ops_per_s"] < 36_372_480 / peak["hbm_bytes_per_s"]


def test_roofline_share_of_the_recorded_matrix_road():
    """The share PERF.md's section 5 worked out by hand for that road, 9.2%
    (82.6 us least over ~894 us measured), as this code reads it."""
    got = roofline.roofline(
        trace_reduce.load(RECORDED), stride_in=1024, r_out=1024, mask_only=False,
        module="jit_run",
    )
    assert got["runs"] == 12 and got["rows"] == [32768]
    assert got["bytes"] == 12 * 32768 * 2064
    assert got["seconds"] == pytest.approx(0.010722992, rel=1e-9)
    assert got["ms_per_run"] == pytest.approx(0.8935827, rel=1e-6)
    assert got["least_s"] / got["runs"] == pytest.approx(82.58e-6, rel=1e-3)
    assert got["roofline_share_pct"] == pytest.approx(9.2414686, rel=1e-6)
    assert 0 < got["roofline_share_pct"] < 100


class _Event:
    def __init__(self, name, start_ns, duration_ns):
        self.name, self.start_ns, self.duration_ns = name, start_ns, duration_ns


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class _Profile:
    def __init__(self, planes):
        self.planes = planes


def test_a_runs_row_bucket_is_read_off_its_matrices_not_its_flattened_gathers():
    """The map program's largest operations are gathers over flattened
    operands (``u8[2129920]`` = 32,768 x 65): the bucket is the leading
    dimension of a matrix the run writes. Two buckets in one window are
    counted each at its own bytes (my chip run, PR 30: 28.97 ms a 32,768-row
    run)."""
    module = "jit_rp_payload_transform(7999884249351290431)"
    ops = [
        _Event("%fusion.1 = u8[2129920]{0:T(1024)} fusion(...)", 1_000, 22_714_000),
        _Event("%concatenate.3 = u8[32768,78]{1,0} concatenate(...)", 28_000_000, 215_000),
        _Event("%fusion.1 = u8[1064960]{0:T(1024)} fusion(...)", 40_001_000, 11_000_000),
        _Event("%concatenate.3 = u8[16384,78]{1,0} concatenate(...)", 54_000_000, 100_000),
    ]
    modules = [_Event(module, 0, 28_968_000), _Event(module, 40_000_000, 14_500_000),
               _Event("jit_other(1)", 60_000_000, 5)]
    profile = _Profile([
        _Plane("/host:CPU", [_Line("XLA Ops", ops)]),  # not a device plane
        _Plane("/device:TPU:0", [_Line("XLA Ops", ops), _Line("XLA Modules", modules)]),
    ])
    assert roofline.module_runs(profile) == [(0.028968, 32768), (0.0145, 16384)]
    got = roofline.roofline(profile, stride_in=1024, r_out=70, mask_only=False)
    assert got["rows"] == [16384, 32768] and got["bytes"] == (32768 + 16384) * 1110
    assert got["roofline_share_pct"] == pytest.approx(
        100 * (32768 + 16384) * 1110 / 819e9 / 0.043468, rel=1e-9)
    assert 0.1 < got["roofline_share_pct"] < 0.2


def test_a_module_that_never_ran_reads_nothing_and_an_unknown_device_raises():
    profile = trace_reduce.load(RECORDED)
    assert roofline.module_runs(profile) == []  # no jit_rp_payload_transform then
    assert roofline.roofline(profile, stride_in=1024, r_out=70, mask_only=False) is None
    with pytest.raises(KeyError):
        roofline.roofline(profile, stride_in=1024, r_out=70, mask_only=False,
                          device_kind="TPU v9", module="jit_run")


def test_the_entry_prints_one_json_object():
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "roofline.py"), RECORDED,
         "--r-out", "1024", "--module", "jit_run"],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["runs"] == 12 and round(got["roofline_share_pct"], 2) == 9.24
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "roofline.py"), RECORDED],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 1 and "no run of jit_rp_payload_transform" in proc.stderr
