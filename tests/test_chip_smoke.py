"""chip_smoke.py on the CPU: its stages at a tiny size for the comparison
logic, its refusal to report without an accelerator, and the compile-cache
placement every entry point shares (utils/platform.py).

The chip run itself is ``python3 chip_smoke.py --seed 0`` on the TPU
machine; nothing here can say anything about the device.
"""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

from redpanda_tpu.utils import platform  # noqa: E402

CPU_REFUSAL = "JAX platform is 'cpu' (device_kind 'cpu'): no accelerator"


def test_compare_reports_the_first_wrong_record():
    from redpanda_tpu.coproc import reference

    values = reference.make_documents(3, 2, 64)
    fn = chip_smoke.reference_fns(1024)[chip_smoke.COLUMNAR_SCRIPT]
    expected = chip_smoke.reference_outputs(values, fn)
    assert all(expected), "every partition keeps some records"
    assert chip_smoke.compare(expected, [list(p) for p in expected]) == {
        "records_expected": sum(map(len, expected)),
        "records_materialised": sum(map(len, expected)),
        "reference_match": True,
        "first_mismatch": None,
    }
    wrong = [list(p) for p in expected]
    wrong[1][2] = wrong[1][2][:-1] + b"\x01"
    got = chip_smoke.compare(expected, wrong)
    assert not got["reference_match"]
    assert got["first_mismatch"]["partition"] == 1
    assert got["first_mismatch"]["index"] == 2
    short = [list(p) for p in expected]
    short[0].pop()  # a lost tail record
    got = chip_smoke.compare(expected, short)
    assert not got["reference_match"]
    assert got["first_mismatch"] == {
        "partition": 0, "index": len(expected[0]) - 1,
        "expected": len(expected[0]), "got": len(expected[0]) - 1,
    }
    dup = [list(p) for p in expected]
    dup[0].append(dup[0][-1])  # a record materialized twice
    assert not chip_smoke.compare(expected, dup)["reference_match"]


def test_reference_drops_what_the_engine_cannot_project_or_stage():
    from redpanda_tpu.coproc import reference

    doc = b'{"level":"error","code":7,"msg":"%s","pad":"x"}'
    assert reference.project_error(doc % (b"m" * 64)) is not None
    assert reference.project_error(doc % (b"m" * 65)) is None
    assert reference.project_error(b'{"level":"info","code":7,"msg":"m"}') is None
    assert reference.project_error(b'{"level":"error","msg":"m"}') is None
    assert reference.project_error(b"not json") is None
    assert reference.filter_contains(b"a warn b", b"warn", 8) == b"a warn b"
    assert reference.filter_contains(b"a warn bc", b"warn", 8) is None
    assert reference.filter_contains(b"", b"", 8) is None


def test_stage_a_served_path_at_tiny_size():
    """4 partitions x 64 records through a real broker child and the
    Kafka client: both materialized topics equal the plain reference. On
    the CPU backend the stage records the platform as a failure (and, at
    this size, that the columnar probe never ran) — nothing else."""
    r = chip_smoke.stage_a(
        1, partitions=4, records_per_partition=64, timeout_s=120.0,
        require_accelerator=False,
    )
    assert r["platform"] == "cpu" and r["records_in"] == 256
    for name in (chip_smoke.COLUMNAR_SCRIPT, chip_smoke.PAYLOAD_SCRIPT):
        assert r[name]["reference_match"], r[name]
        assert r[name]["records_materialised"] > 0
    assert r["device_launches"][chip_smoke.PAYLOAD_SCRIPT] > 0
    assert r["n_fallback_rows"] == 0 and r["n_retries"] == 0
    assert r["coproc_failures_total"] == 0
    assert set(r["breakers"].values()) == {"closed"}
    assert r["native"]["loaded"] and all(r["native"]["symbols"].values())
    assert r["failures"] == [
        CPU_REFUSAL,
        "columnar probe has no device timing: the probe never ran",
    ]
    # PR 45: the payload script's ladder was built at the deploy, and a
    # launch at a bucket no launch had met was no first run
    pre = r["precompile"]
    assert pre["ladder"]["state"] == "ready" and pre["ladder"]["top"] == 8192
    assert pre["coproc_programs_ready"] == len(pre["ladder"]["buckets"]) == 7
    assert pre["reference_match"] and pre["records_materialised"] > 0
    assert pre["n_compiles"] == [0, 0] and pre["compile_samples"][0] == pre["compile_samples"][1]
    assert pre["n_precompiles"] == 7 and pre["rows"] == pre["bucket"] // 2 + 1


def test_stage_b_engine_lanes_at_tiny_size():
    r = chip_smoke.stage_b(
        2, partitions=4, records_per_partition=64, ticks_per_launch=1,
        programs=False,
    )
    assert [lane["lane"] for lane in r["lanes"]] == [
        "columnar_device", "payload", "payload_mixed_width"]
    for lane in r["lanes"]:
        assert lane["reference_match"], lane
        assert lane["records_materialised"] == lane["records_expected"] > 0
        assert lane["n_device_launches"] >= lane["launches"]
        assert lane["failures"] == []
    # 128 rows a launch are too few for two parts to halve the bytes, and
    # eight wide documents a launch may or may not pass 1,024 B: the mixed
    # lane runs one program a fitted stride (1,024 and 1,152)
    assert [lane["n_compiles"] for lane in r["lanes"][:2]] == [1, 1]
    assert r["lanes"][2]["n_compiles"] in (1, 2) and r["lanes"][2]["n_split_launches"] == 0
    assert r["failures"] == [CPU_REFUSAL]


def test_the_mixed_width_lane_is_staged_in_two_parts_and_matches_the_reference():
    """Stage B's third lane at the smallest size where the split halves a
    launch's bytes (1,024 rows): every launch goes as a narrow matrix and a
    wide one, and every reply is the plain reference's."""
    from redpanda_tpu.coproc import reference

    values = chip_smoke.mixed_width(reference.make_documents(2, 4, 512))
    sizes = [len(v) for part in values for v in part]
    assert sum(s <= 128 for s in sizes) == len(sizes) * 15 // 16 and max(sizes) > 900
    lane = chip_smoke.run_lane(
        "payload_mixed_width", chip_smoke.specs()[chip_smoke.PAYLOAD_SCRIPT], values,
        chip_smoke.reference_fns(chip_smoke.ENGINE_ROW_STRIDE)[chip_smoke.PAYLOAD_SCRIPT],
        8, force_mode="payload",
    )
    assert lane["rows_per_launch"] == 1024 and lane["reference_match"], lane
    assert lane["records_materialised"] == lane["records_expected"] > 0
    assert lane["n_split_launches"] == lane["n_launches"] == lane["launches"]
    assert lane["n_compiles"] == 1 and lane["failures"] == []
    # a narrow matrix of 136 B rows and a wide one of 1,160 B rows a launch
    assert lane["bytes_h2d"] == lane["launches"] * (1024 * 136 + 128 * 1160)


def test_stage_c_mesh_lane_on_virtual_devices():
    """The four-chip stage's logic, on the CPU backend's virtual devices."""
    r = chip_smoke.stage_c(
        2, partitions=8, records_per_partition=64, ticks_per_launch=1,
        mesh_backend="cpu",
    )
    (lane,) = r["lanes"]
    assert lane["reference_match"], lane
    assert lane["n_mesh_launches"] == lane["launches"]
    assert all(rows > 0 for rows in lane["mesh"]["rows_per_device"])
    assert r["failures"] == []


def test_main_refuses_to_report_without_an_accelerator(monkeypatch, capsys):
    """Under JAX_PLATFORMS=cpu the smoke exits non-zero, names the
    platform, and prints no result line."""
    assert os.environ["JAX_PLATFORMS"] == "cpu"
    # the suite's own process has the .so mapped: do not rebuild under it
    monkeypatch.setattr(chip_smoke, "build_native", lambda: {"stage": "build"})
    assert chip_smoke.main(["--seed", "0"]) == 1
    out, err = capsys.readouterr()
    assert CPU_REFUSAL in err
    last = out.strip().splitlines()[-1]
    with pytest.raises(ValueError):
        json.loads(last)


def test_compile_cache_follows_the_environment(monkeypatch):
    import jax

    knobs = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
    )
    saved = {k: getattr(jax.config, k) for k in knobs}
    before = saved["jax_compilation_cache_dir"]
    # a CPU-pinned process keeps the cache off
    assert platform.cpu_pinned()
    assert platform.enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before

    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")  # read by the helper only
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    try:
        # placed from outside: the directory is not set in code
        assert platform.enable_compile_cache() == "/some/dir"
        assert jax.config.jax_compilation_cache_dir == before

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = os.path.join(REPO, ".jax_cache")
        assert platform.enable_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)


def test_container_hands_each_chip_to_one_broker():
    from redpanda_tpu.cli.container import chip_assignment

    cpu = {"JAX_PLATFORMS": "cpu"}
    assert platform.visible_chips() == 0  # this process is CPU-pinned
    assert chip_assignment(3, 0) == [cpu, cpu, cpu]
    # one chip: the first broker inherits the environment, the rest are pinned
    assert chip_assignment(3, 1) == [{}, cpu, cpu]
    four = chip_assignment(5, 4)
    assert [e.get("TPU_VISIBLE_CHIPS") for e in four] == ["0", "1", "2", "3", None]
    assert four[4] == cpu
