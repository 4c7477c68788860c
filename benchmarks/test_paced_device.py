"""The whole-run rehearsal of ``json64p-v1-zstd.paced`` (PR 45): the payload
lane's mask road over Zstd producer batches, under running producers.
``test_configs.py`` rehearses the catch-up cells; this file holds what the
live device cell reads instead. Not collected by the repo's tier-1 command;
run by hand:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/test_paced_device.py -q
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from test_benchmark import BROKEN_LAUNCHER  # noqa: E402
from test_configs import _rehearse  # noqa: E402

CELL = "json64p-v1-zstd.paced"
NEW_FILES = [
    "paced.link_wait_h2d_ms_per_launch", "paced.link_wait_program_ms_per_launch",
    "paced.link_wait_d2h_ms_per_launch", "paced.d2h_wait_ms_per_launch",
    "paced.h2d_ms_per_launch", "paced.pack_ms_per_launch", "paced.gather_ms_per_launch",
    "paced.mask_harvest_share", "paced.staging_fill_share", "paced.explode_us_per_rec",
    "paced.uncompress_us_per_batch", "paced.uncompress_batches_per_crossing",
    "paced.launch_cuts",
]


def test_rehearsal_launches_device_programs_beside_producers_and_broken_is_caught(tmp_path):
    rc, last, out = _rehearse(CELL, "--rehearse", "1", "--manifest", "BENCHMARK.json")
    assert rc == 0 and last["correct"] is True and "metrics" not in last, out[-3000:]
    assert {"e2e_p50_ms", "e2e_p95_ms", "setup_s"} <= set(last["not_metrics"]["end_to_end"])
    layer = {k: v["value"] for k, v in last["not_metrics"]["per_layer"].items()}
    assert set(NEW_FILES) <= set(layer), sorted(set(NEW_FILES) - set(layer))
    # every launch a device program whose ladder was built at the deploy:
    # no first run and no cut inside the window, a keep mask back
    assert layer["paced.compiles_in_window"] == 0 and layer["paced.launch_cuts"] == 0
    assert layer["paced.device_launch_share"] == 100.0
    assert layer["paced.mask_harvest_share"] == 1.0 and layer["paced.gather_ms_per_launch"] > 0
    assert 0 < layer["paced.staging_fill_share"] <= 1.0
    # its input arrives compressed: every scanned batch is decompressed
    assert layer["paced.uncompress_us_per_batch"] > 0
    assert layer["paced.uncompress_batches_per_crossing"] >= 0.5
    # the served path itself broken: one acknowledged batch never written
    broken = tmp_path / "broken_launcher.py"
    broken.write_text(BROKEN_LAUNCHER)
    rc, last, out = _rehearse(CELL, "--rehearse", "1", launcher=str(broken))
    assert rc == 1 and last["correct"] is False, out[-3000:]
    assert "check records_missing = 0" not in out
