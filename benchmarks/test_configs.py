"""Tests of the benchmark over every configuration it holds (PR 26: the
second, ``json64p-v1``). Not collected by the repo's tier-1 command; run by
hand beside ``test_benchmark.py``:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/test_configs.py -q

The whole-run cases start a broker on JAX's CPU backend at the traffic
file's ``rehearsal`` size (about 15 s each).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path[:0] = [HERE, REPO]

import checks  # noqa: E402
import readers  # noqa: E402
from loadgen import document_source, load_reference  # noqa: E402
from test_benchmark import BROKEN_LAUNCHER, RUN_WITH_LAUNCHER, manifest  # noqa: E402

CONFIGS = [c["name"] for c in manifest()["configs"]]
CATCHUP_CELLS = [w["name"] for w in manifest()["workloads"] if w["traffic"] == "catchup"]


def config_file(name: str) -> dict:
    entry = next(c for c in manifest()["configs"] if c["name"] == name)
    with open(os.path.join(REPO, entry["file"])) as f:
        return json.load(f)


# ------------------------------------------------------------------ manifest
def test_every_metrics_workloads_are_cells_that_exist_and_report_what_it_moves():
    m = manifest()
    cells = {w["name"]: w for w in m["workloads"]}
    e2e = {e["name"]: set(e.get("workloads", cells)) for e in m["end_to_end"]}
    defs = {d["name"]: d for d in readers.load_definitions(os.path.join(HERE, "layer_metrics"))}
    for e in m["end_to_end"] + m["per_layer"]:
        for cell in e.get("workloads", []):
            assert cell in cells, (e["name"], cell)
    for e in m["per_layer"]:
        assert e["workloads"], e["name"]  # a metric no cell reports is none
        for cell in e["workloads"]:
            assert cell in e2e[e["moves"]], (e["name"], cell)
            assert cells[cell]["traffic"] in defs[e["name"]]["traffic"], (e["name"], cell)
    for cell, w in cells.items():  # setup_s, one more, and a layer metric
        assert any(cell in s for n, s in e2e.items() if n != "setup_s"), cell
        assert any(cell in e["workloads"] for e in m["per_layer"]), cell


@pytest.mark.parametrize("name", CONFIGS)
def test_configuration_files_state_their_cut_and_their_guarantees(name):
    entry = next(c for c in manifest()["configs"] if c["name"] == name)
    c = config_file(name)
    assert c["name"] == name and c["source"] == entry["source"]
    assert c["reduced"] == entry["reduced"] == list(c["reduced_from"])
    assert c["guarantees"] and c["assumed"]
    # the shapes of BASELINE config 4 are no configuration's to change
    assert (c["topic"]["partitions"], c["records_per_batch"], c["acks"]) == (64, 32, -1)
    assert (c["documents"]["bytes_min"], c["documents"]["bytes_max"]) == (923, 1060)
    assert os.path.isfile(os.path.join(HERE, "references", c["reference"]["name"] + ".py"))


def test_the_payload_lanes_metrics_read_nothing_on_the_columnar_lane():
    """``readers.py`` picks metric files by traffic kind, so the columnar
    cell reads the payload lane's files too: each divides by a counter that
    stays 0 there, and is left out."""
    lane = ["d2h_wait_ms_per_launch", "h2d_ms_per_launch", "staging_fill_share",
            "oversize_rows_per_launch", "pack_ms_per_launch", "rebuild_ms_per_launch"]
    columnar = {"n_records": 4096.0, "n_launches": 1.0, "t_seal": 0.004, "t_rebuild": 0.001}
    payload = {**columnar, "n_device_launches": 2.0, "n_staged_rows": 8192.0, "t_fetch": 0.1,
               "t_h2d": 0.01, "t_pack": 0.06, "n_oversize_rows": 500.0}
    parent = {k: v for k, v in payload.items()
              if k not in ("n_staged_rows", "t_h2d", "n_oversize_rows")}

    def read(after):
        return readers.read_all(
            os.path.join(HERE, "layer_metrics"), kind="catchup",
            before={"metrics": {}, "stats": {}}, after={"metrics": {}, "stats": after},
            client={}, trace=None, window_s=1.0)

    assert not set(lane) & set(read(columnar))
    got = read(payload)
    assert {k: round(got[k]["value"], 6) for k in lane} == {
        "d2h_wait_ms_per_launch": 50.0, "h2d_ms_per_launch": 5.0, "staging_fill_share": 0.5,
        "oversize_rows_per_launch": 250.0, "pack_ms_per_launch": 30.0,
        "rebuild_ms_per_launch": 0.5}
    # the parent's program (no such stage, no such counters): 0 or left out
    got = read(parent)
    assert "staging_fill_share" not in got
    assert got["h2d_ms_per_launch"]["value"] == got["oversize_rows_per_launch"]["value"] == 0.0


# ------------------------------------------------------------------ references
@pytest.mark.parametrize("name", CONFIGS)
def test_each_reference_keeps_a_share_and_carries_its_input_sequence(name):
    c = config_file(name)
    ref, params = load_reference(c["reference"]["name"]), c["reference"]["params"]
    stream = {"seed": 2**31 + 5, "partitions": 4, "records_per_partition": 256}
    values = document_source(c["documents"])(stream)  # the configuration's own generator
    kept = 0
    for p, part in values.items():
        outs = [(i, ref.reference(v, **params)) for i, v in enumerate(part)]
        assert [ref.sequence(o) for i, o in outs if o is not None] == [
            p * 256 + i for i, o in outs if o is not None]
        kept += sum(o is not None for _, o in outs)
    assert 0.2 < kept / (4 * 256) < 0.4
    assert ref.reference(b"", **params) is None and ref.reference(None, **params) is None


def test_filter_contains_holds_the_lanes_stated_semantics():
    ref = load_reference("filter_contains")
    needle = '"level":"warn"'
    fits = b'{"level":"warn",' + b"x" * (1024 - 16)
    assert len(fits) == 1024 and ref.reference(fits, needle, 1024) == fits
    assert ref.reference(fits + b"x", needle, 1024) is None  # never truncated
    assert ref.reference(b"x" * 1010 + needle.encode(), needle, 1024) is not None
    assert ref.reference(b"x" * 1011 + needle.encode(), needle, 1024) is None
    assert ref.reference(b'{"level":"warning"}', needle, 1024) is None
    assert ref.sequence(b'{"level":"warn","code":-17,"msg":"code"}') == -17


# ------------------------------------------------------------------ whole runs
def _rehearse(cell: str, *extra: str, launcher: str | None = None) -> tuple[int, dict, str]:
    head = ([sys.executable, os.path.join(HERE, "run.py")] if launcher is None
            else [sys.executable, "-c", RUN_WITH_LAUNCHER, launcher])
    proc = subprocess.run(
        [*head, "--workload", cell, "--seed", str(2**31 + 5), "--seconds", "20",
         "--trace", "0", *extra],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    lines = proc.stdout.strip().splitlines()
    last = json.loads(lines[-1]) if lines and lines[-1].startswith("{") else {}
    return proc.returncode, last, proc.stdout + proc.stderr


@pytest.mark.parametrize("cell", CATCHUP_CELLS)
def test_rehearsal_is_correct_and_each_broken_guarantee_is_caught(cell, tmp_path):
    rc, last, out = _rehearse(cell, "--rehearse", "1", "--control", "1")
    assert rc == 0 and last["correct"] is True and "metrics" not in last, out[-3000:]
    control = json.loads(out.split("control (each broken guarantee caught): ")[1].splitlines()[0])
    assert control == {"one_missing": True, "one_duplicated": True,
                       "one_reordered": True, "one_flipped_byte": True}
    assert "transform_rate" in last["not_metrics"]["end_to_end"]
    layer = last["not_metrics"]["per_layer"]
    config = next(w["config"] for w in manifest()["workloads"] if w["name"] == cell)
    if config_file(config)["lane"] == "payload":  # every launch a device program
        assert layer["device_launch_share"]["value"] == 100.0
        assert 0 < layer["staging_fill_share"]["value"] <= 1.0
        assert layer["oversize_rows_per_launch"]["value"] > 0
    else:
        assert "staging_fill_share" not in layer and "d2h_wait_ms_per_launch" not in layer
    # the served path itself broken: one acknowledged batch never written
    broken = tmp_path / "broken_launcher.py"
    broken.write_text(BROKEN_LAUNCHER)
    rc, last, out = _rehearse(cell, "--rehearse", "1", launcher=str(broken))
    assert rc == 1 and last["correct"] is False, out[-3000:]
    assert "check records_missing = 0" not in out
