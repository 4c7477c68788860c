"""The whole-run rehearsal of ``json64p-v1map.catchup`` (PR 30), the payload
lane's matrix road. ``test_configs.py`` runs every catch-up cell the same
way, but tells the payload lane by the cell's name (``json64p-v1.``), so its
case for this cell asserts the columnar lane's absences and fails; this
file holds what the cell reads instead. Not collected by the repo's tier-1
command; run by hand:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/test_v1map.py -q
"""

from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from test_benchmark import BROKEN_LAUNCHER  # noqa: E402
from test_configs import _rehearse  # noqa: E402

CELL = "json64p-v1map.catchup"


def test_rehearsal_takes_the_matrix_road_and_each_broken_guarantee_is_caught(tmp_path):
    rc, last, out = _rehearse(CELL, "--rehearse", "1", "--control", "1")
    assert rc == 0 and last["correct"] is True and "metrics" not in last, out[-3000:]
    control = json.loads(out.split("control (each broken guarantee caught): ")[1].splitlines()[0])
    assert control == {"one_missing": True, "one_duplicated": True,
                       "one_reordered": True, "one_flipped_byte": True}
    assert "transform_rate" in last["not_metrics"]["end_to_end"]
    layer = {k: v["value"] for k, v in last["not_metrics"]["per_layer"].items()}
    # every launch a device program that ships a result matrix back
    assert layer["device_launch_share"] == 100.0 and layer["compiles_in_window"] == 0
    assert layer["mask_harvest_share"] == 0.0 and layer["gather_ms_per_launch"] == 0.0
    assert layer["rebuild_ms_per_launch"] > 0 and layer["oversize_rows_per_launch"] > 0
    fill = layer["staging_fill_share"]
    assert 0 < fill <= 1.0
    assert layer["result_bytes_per_rec"] == pytest.approx(78 / fill)
    assert layer["link_bytes_per_rec"] == pytest.approx((1032 + 78) / fill)
    # what the map let through, and the 70 B it wrote for each
    assert 0.2 < layer["kept_share"] < 0.3
    assert layer["out_bytes_per_rec"] == pytest.approx(70 * layer["kept_share"])
    # the served path itself broken: one acknowledged batch never written
    broken = tmp_path / "broken_launcher.py"
    broken.write_text(BROKEN_LAUNCHER)
    rc, last, out = _rehearse(CELL, "--rehearse", "1", launcher=str(broken))
    assert rc == 1 and last["correct"] is False, out[-3000:]
    assert "check records_missing = 0" not in out
