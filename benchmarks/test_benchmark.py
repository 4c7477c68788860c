"""Tests of the benchmark's own code. Not collected by the repo's tier-1
command (which runs ``tests/``); run by hand:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/test_benchmark.py -q

The last two start a broker on JAX's CPU backend at the traffic files'
``rehearsal`` size (about 15 s each).
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path[:0] = [HERE, REPO]

import checks  # noqa: E402
import docs  # noqa: E402
import readers  # noqa: E402
import trace_reduce  # noqa: E402
import wire  # noqa: E402


# ------------------------------------------------------------------ manifest
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def manifest() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_manifest_names_units_and_files():
    m = manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    names = [e["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for e in m[k]]
    for n in names + [w["traffic"] for w in m["workloads"]]:
        assert NAME.match(n), n
    for k in ("configs", "workloads"):
        assert len({e["name"] for e in m[k]}) == len(m[k])
    metrics = m["end_to_end"] + m["per_layer"]
    assert len({e["name"] for e in metrics}) == len(metrics)
    for e in metrics:
        assert UNIT.match(e["unit"]), e
        assert e["better"] in ("lower", "higher")
    cells = {w["name"] for w in m["workloads"]}
    for c in m["configs"]:
        assert os.path.isfile(os.path.join(REPO, c["file"]))
        assert any(w["config"] == c["name"] for w in m["workloads"])
        assert len(c["source"]) <= 200
    assert len({c["source"] for c in m["configs"]}) == len(m["configs"])
    for w in m["workloads"]:
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert os.path.isfile(os.path.join(HERE, "traffic", w["traffic"] + ".json"))
        assert len(w["why"]) <= 200 and w["chips"] == 1
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert "setup_s" in e2e and all(0.01 <= e["bound"] <= 0.25 for e in e2e.values())
    for e in metrics:
        assert set(e.get("workloads", cells)) <= cells


def test_manifest_agrees_with_the_layer_metric_files():
    m = manifest()
    cells = {w["name"]: w for w in m["workloads"]}
    defs = {d["name"]: d for d in readers.load_definitions(os.path.join(HERE, "layer_metrics"))}
    listed = {e["name"]: e for e in m["per_layer"]}
    assert set(listed) == set(defs)  # no metric file that no cell reports
    e2e_cells = {
        e["name"]: set(e.get("workloads", cells)) for e in m["end_to_end"]
    }
    for name, e in listed.items():
        d = defs[name]
        for key in ("layer", "unit", "better", "source", "moves"):
            assert e[key] == d[key], (name, key)
        for cell in e["workloads"]:
            assert cells[cell]["traffic"] in d["traffic"], (name, cell)
            assert cell in e2e_cells[e["moves"]], (name, cell)


# ------------------------------------------------------------------ wire
@pytest.mark.parametrize("codec", sorted(wire.CODECS))
def test_batches_round_trip_and_match_the_programs_decoder(codec):
    from redpanda_tpu.hashing.crc32c import crc32c
    from redpanda_tpu.kafka.protocol.batch import decode_wire_batches

    values = docs.make_documents(7, 4, 32)[2]
    raw = wire.build_batch(values, crc32c, codec=wire.codec_id(codec))
    assert raw[22] & 0x07 == wire.CODECS[codec] and (len(raw) < 4096) == (codec == "zstd")
    base, got = wire.decode_batch(raw, crc32c)
    assert (base, got) == (0, values)
    (res,) = decode_wire_batches(raw)
    assert res.valid_crc and [r.value for r in res.batch.records()] == values
    assert wire.walk_batches(raw + raw[:40]) == [(0, len(raw), 31, 32)]
    broken = bytearray(raw)
    broken[100] ^= 1
    with pytest.raises(ValueError):
        wire.decode_batch(bytes(broken), crc32c)


def test_documents_depend_on_the_seed_alone():
    a = docs.make_documents(2**31 + 11, 8, 64)
    assert a == docs.make_documents(2**31 + 11, 8, 64)
    assert a[3] == docs.make_documents(2**31 + 11, 8, 64, range(3, 4))[3]
    assert a != docs.make_documents(2**31 + 12, 8, 64)
    assert all(923 <= len(v) <= 1060 for part in a.values() for v in part)


# ------------------------------------------------------------------ comparison
def _outputs():
    from loadgen import load_reference

    ref = load_reference("project_error")
    values = docs.make_documents(5, 4, 256)
    expected = {p: [o for o in (ref.reference(v, msg_width=64) for v in part) if o is not None]
                for p, part in values.items()}
    kept = {p: [i for i, v in enumerate(part) if ref.reference(v, msg_width=64) is not None]
            for p, part in values.items()}
    return ref, values, expected, kept


def test_comparison_passes_the_reference_and_fails_each_broken_guarantee():
    _, _, expected, _ = _outputs()
    got = {p: list(v) for p, v in expected.items()}
    assert checks.compare_ok(checks.compare(expected, got))
    verdicts = checks.control_verdicts(expected, got)
    assert verdicts == {"one_missing": True, "one_duplicated": True,
                        "one_reordered": True, "one_flipped_byte": True}
    # a record moved to another partition, and an empty partition
    moved = {**got, 0: got[0][:-1], 1: got[1] + [got[0][-1]]}
    r = checks.compare(expected, moved)
    assert r["records_missing"] == 1 and r["records_extra"] == 1
    assert not checks.compare_ok(checks.compare(expected, {**got, 2: []}))


def test_reference_outputs_carry_their_input_sequence():
    ref, values, expected, kept = _outputs()
    for p in values:
        assert [ref.sequence(o) for o in expected[p]] == [p * 256 + i for i in kept[p]]
    assert 0 < sum(map(len, kept.values())) < 4 * 256


def test_transform_rate_bookkeeping():
    kept = {0: [2, 5, 6], 1: [0, 9]}
    n_inputs = {0: 10, 1: 10}
    # partition 0 fetched its first output at t=1 (one record), two more at t=3
    arrivals = {0: [(1.0, 1), (3.0, 3)], 1: [(2.0, 1)]}
    # inputs 0 and 1 are dropped by the reference: passed with input 2
    assert checks.passed_at(kept[0], arrivals[0], 0.5) == 0
    assert checks.passed_at(kept[0], arrivals[0], 1.0) == 3
    assert checks.passed_at(kept[0], arrivals[0], 2.9) == 3
    assert checks.passed_at(kept[0], arrivals[0], 3.0) == 7
    # every kept output fetched: the dropped tail is passed too, once told N
    assert checks.passed_at(kept[0], arrivals[0], 3.0, n_inputs=10) == 10
    assert checks.passed_at(kept[1], arrivals[1], 5.0, n_inputs=10) == 1
    # fixed work, not drained when the window closes: passed / window
    r = checks.transform_rate(kept, arrivals, n_inputs, 0.0, 4.0, True, None)
    assert (r["records"], r["value"], r["drained"]) == (11, 11 / 4.0, False)
    # fixed work, drained at t=3.5 inside the window: N / T
    r = checks.transform_rate(kept, arrivals, n_inputs, 0.0, 4.0, True, 3.5)
    assert (r["records"], r["value"], r["drained"]) == (20, 20 / 3.5, True)
    # drained only after the window closed: the window formula holds
    r = checks.transform_rate(kept, arrivals, n_inputs, 0.0, 4.0, True, 4.5)
    assert r["drained"] is False and r["value"] == 11 / 4.0
    # a live stream: what passed between t0 and t1, no tail rule
    r = checks.transform_rate(kept, arrivals, n_inputs, 1.5, 4.0, False, None)
    assert r["records"] == (7 - 3) + (1 - 0) and r["value"] == 5 / 2.5


def test_latencies_are_timed_from_the_due_time(tmp_path):
    kept = {0: [1, 33]}  # one kept record in batch 0, one in batch 1
    log = [["main", 0, 0, 10.0, 10.004, 10.020, 0, 32000], ["main", 0, 1, 10.5, 10.5, 10.530, 0, 31000],
           ["main", 0, 2, 12.0, 12.0, 12.1, 0, 30000]]  # batch 2 is due after the window
    path = tmp_path / "p.json"
    path.write_text(json.dumps(log))
    red = checks.reduce_window(
        kept=kept, acked={0: 96}, arrivals={0: [(10.2, 1), (10.9, 2)]},
        producer_logs=[str(path)], stream="main", records_per_batch=32,
        t0=10.0, t1=11.0, fixed_work=False, t_complete=None,
    )
    assert red["batches_offered"] == 2 and red["kept_in_window"] == 2
    assert red["e2e_ms"]["50"] == pytest.approx(200.0) and red["e2e_ms"]["95"] == pytest.approx(400.0)
    assert red["ack_ms"]["95"] == pytest.approx(30.0)
    assert red["generator_lag_ms"]["99"] == pytest.approx(4.0)
    assert red["produce_rate"] == 64.0
    # what was fed: the batches due in the window; all that was seeded for fixed work
    assert red["input_wire_bytes_per_rec"] == 63000 / 64
    fixed = checks.reduce_window(
        kept=kept, acked={0: 96}, arrivals={0: [(10.2, 1), (10.9, 2)]},
        producer_logs=[str(path)], stream="main", records_per_batch=32,
        t0=20.0, t1=21.0, fixed_work=True, t_complete=20.5,
    )
    assert fixed["input_wire_bytes_per_rec"] == 93000 / 96 and fixed["batches_offered"] == 0
    assert checks.percentile([1.0, 2.0, 3.0, 4.0], 50) == 2.0
    assert checks.percentile(list(range(1, 101)), 95) == 95


# ------------------------------------------------------------------ readers
def test_layer_metric_readers():
    text = ("# HELP x\nredpanda_tpu_kafka_produce_latency_us_sum 3000\n"
            "redpanda_tpu_kafka_produce_latency_us_count 3\n"
            'redpanda_tpu_coproc_failures_total{domain="a",kind="b"} 2\n'
            'redpanda_tpu_coproc_failures_total{domain="c",kind="b"} 1\n')
    m0 = readers.parse_prometheus(text)
    assert readers.metric_total(m0, "coproc_failures_total") == 3
    assert readers.metric_total(m0, "coproc_failures_total", {"domain": "c"}) == 1
    m1 = dict(m0, kafka_produce_latency_us_sum=9000.0, kafka_produce_latency_us_count=5.0)
    out = readers.read_all(
        os.path.join(HERE, "layer_metrics"), kind="paced",
        before={"metrics": m0, "stats": {"n_records": 0, "n_launches": 0}},
        after={"metrics": m1, "stats": {"n_records": 4096, "n_launches": 2, "t_pack": 0.5}},
        client={"generator_lag_ms": {"99": 1.5}}, trace=None, window_s=2.0,
    )
    assert out["paced.produce_handler_ms"] == {"value": 3.0, "unit": "ms"}
    assert out["paced.rows_per_launch"]["value"] == 2048
    assert out["paced.engine_host_ms_per_krec"]["value"] == pytest.approx(0.5 / 4096 * 1e6)
    assert out["paced.compiles_in_window"]["value"] == 0
    assert out["generator_lag_p99_ms"]["value"] == 1.5
    # nothing to read: no trace, no acknowledgement seen, another traffic kind
    for absent in ("paced.device_idle_share", "ack_p95_obs_ms", "rows_per_launch"):
        assert absent not in out


# ------------------------------------------------------------------ trace
def test_trace_reduction_arithmetic():
    assert trace_reduce.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    spans = sorted([(0, 100, "outer(x)"), (10, 30, "inner one"), (60, 90, "other")])
    assert trace_reduce._host_over(spans, 12, 28) == "host_in:inner_one"
    assert trace_reduce._host_over(spans, 40, 50) == "host_in:outer"
    assert trace_reduce._host_over(spans, 95, 300) == "no_host_span"  # covered by 5 of 205
    assert trace_reduce._host_over([], 0, 5) == "no_host_span"
    a = {"busy_s": 1e-6, "window_s": 3.0, "op_s": {"x": 1e-6}}
    b = {"busy_s": 0.5, "window_s": 4.0, "op_s": {"x": 0.1, "y": 0.4}}
    assert trace_reduce.totals([a, b]) == {"busy_s": 0.5 + 1e-6, "window_s": 7.0}
    assert trace_reduce.top_ops([a, b], top=1) == [["y", 0.4]]
    assert trace_reduce.top_ops([a, b])[1] == ["x", pytest.approx(0.1 + 1e-6)]


def test_a_trace_with_nothing_on_the_device_is_all_idle():
    class Empty:
        planes = ()

    with pytest.raises(ValueError):
        trace_reduce.reduce_profile(Empty())
    got = trace_reduce.reduce_profile(Empty(), span_s=4.0)
    assert (got["busy_s"], got["window_s"], got["idle_share_pct"]) == (0.0, 4.0, 100.0)
    assert got["breakdown"] == {"device_ops": [], "idle_gaps": [["no_host_span", 4.0]]}


def test_trace_reduction_on_the_recorded_trace():
    path = os.path.join(HERE, "testdata", "v1_catchup.xplane.pb")
    with open(os.path.join(HERE, "testdata", "v1_catchup.expected.json")) as f:
        want = json.load(f)
    got = trace_reduce.reduce_profile(trace_reduce.load(path))
    assert got["chips"] == 1 and 0 < got["busy_s"] < got["window_s"]
    for key in ("window_s", "busy_s", "idle_share_pct"):
        assert got[key] == pytest.approx(want[key], rel=1e-9), key
    assert got["breakdown"]["device_ops"][0][0] == want["top_op"]
    assert len(got["breakdown"]["device_ops"]) <= 10 and len(got["breakdown"]["idle_gaps"]) <= 10
    gap_s = sum(t for _, t in got["breakdown"]["idle_gaps"])
    assert gap_s <= got["window_s"] - got["busy_s"] + 1e-6


# ------------------------------------------------------------------ whole runs
# A launcher with the served path broken underneath: the third batch the
# measured script materializes is acknowledged as written and dropped.
BROKEN_LAUNCHER = f'''
import sys
sys.path.insert(0, {HERE!r})
from redpanda_tpu.coproc import pacemaker
real = pacemaker.ScriptContext._write_materialized
seen = [0]
async def lossy(self, source, batches):
    if batches and "_warm" not in self.name:
        seen[0] += 1
        if seen[0] == 3:
            return True
    return await real(self, source, batches)
pacemaker.ScriptContext._write_materialized = lossy
import launcher
launcher.main()
'''
# run.py as the driver starts it, but for where the broker's launcher is
RUN_WITH_LAUNCHER = f'''
import sys
sys.path.insert(0, {HERE!r})
import broker
broker.LAUNCHER = sys.argv.pop(1)
import run
sys.exit(run.main())
'''


def _rehearse(*extra: str, launcher: str | None = None) -> tuple[int, dict, str]:
    head = ([sys.executable, os.path.join(HERE, "run.py")] if launcher is None
            else [sys.executable, "-c", RUN_WITH_LAUNCHER, launcher])
    proc = subprocess.run(
        [*head, "--workload", "json64p-where.catchup",
         "--seed", str(2**31 + 5), "--seconds", "20", "--trace", "0", *extra],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    lines = proc.stdout.strip().splitlines()
    last = json.loads(lines[-1]) if lines and lines[-1].startswith("{") else {}
    return proc.returncode, last, proc.stdout + proc.stderr


def test_a_run_without_a_chip_fails_and_prints_no_result():
    rc, last, out = _rehearse()
    assert rc == 1 and last == {} and "not a TPU" in out


def test_rehearsal_is_correct_and_the_broken_timed_path_is_not(tmp_path):
    rc, last, out = _rehearse("--rehearse", "1", "--control", "1")
    assert rc == 0 and last["correct"] is True and "metrics" not in last, out[-3000:]
    assert '"one_duplicated": true' in out
    broken = tmp_path / "broken_launcher.py"
    broken.write_text(BROKEN_LAUNCHER)
    rc, last, out = _rehearse("--rehearse", "1", launcher=str(broken))
    assert rc == 1 and last["correct"] is False, out[-3000:]
    assert "check records_missing = 0" not in out
