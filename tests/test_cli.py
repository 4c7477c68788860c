"""CLI tests: the rpk-style operator tool driven against a live broker.

Mirrors the rpk portions of the ducktape suite (clients/rpk.py usage):
start a broker as a real subprocess via `python -m redpanda_tpu start`,
then run topic/user/cluster/debug/wasm commands as subprocesses against it.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import tarfile
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _rpk(*argv: str, timeout: int = 30) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "-m", "redpanda_tpu", *argv],
        capture_output=True, text=True, timeout=timeout,
        cwd=REPO,
    )


@pytest.fixture()
def live_broker(tmp_path):
    kafka_port, admin_port = _free_port(), _free_port()
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "redpanda_tpu", "start",
            "--set", f"data_directory={tmp_path}",
            "--set", f"kafka_api_port={kafka_port}",
            "--set", f"advertised_kafka_api_port={kafka_port}",
            "--set", f"admin_api_port={admin_port}",
        ],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, cwd=REPO,
    )
    # wait for readiness via the admin api
    deadline = time.time() + 30
    import urllib.request

    while time.time() < deadline:
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{admin_port}/v1/status/ready", timeout=1
            ) as r:
                if r.status == 200:
                    break
        except Exception:
            if proc.poll() is not None:
                raise RuntimeError(f"broker died:\n{proc.stdout.read()}")
            time.sleep(0.2)
    else:
        proc.kill()
        raise RuntimeError("broker did not become ready")
    yield {"kafka": f"127.0.0.1:{kafka_port}", "admin": f"127.0.0.1:{admin_port}"}
    proc.terminate()
    try:
        proc.wait(timeout=10)
    except subprocess.TimeoutExpired:
        proc.kill()


def test_cli_topic_lifecycle_and_produce_consume(live_broker):
    b = ["--brokers", live_broker["kafka"]]
    r = _rpk(*b, "topic", "create", "clitest", "-p", "2", "-c", "retention.ms=60000")
    assert r.returncode == 0, r.stderr
    r = _rpk(*b, "topic", "list")
    assert "clitest\t2 partitions" in r.stdout
    r = _rpk(*b, "topic", "describe", "clitest")
    desc = json.loads(r.stdout)
    assert len(desc["partitions"]) == 2
    r = _rpk(*b, "topic", "produce", "clitest", "hello-cli", "-p", "1", "-k", "k1")
    assert "offset 0" in r.stdout
    r = _rpk(*b, "topic", "consume", "clitest", "-p", "1", "-n", "1")
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert rec == {"offset": 0, "key": "k1", "value": "hello-cli"}
    r = _rpk(*b, "topic", "delete", "clitest")
    assert r.returncode == 0
    r = _rpk(*b, "topic", "describe", "clitest")
    assert r.returncode == 1


def test_cli_users_cluster_debug(live_broker, tmp_path):
    a = ["--admin-api", live_broker["admin"]]
    r = _rpk(*a, "user", "create", "cliuser", "--new-password", "pw")
    assert r.returncode == 0, r.stdout + r.stderr
    r = _rpk(*a, "user", "list")
    assert "cliuser" in r.stdout
    r = _rpk(*a, "cluster", "info")
    assert "active" in r.stdout
    r = _rpk(*a, "config", "get", "node_id")
    assert r.stdout.strip() == "0"
    out = str(tmp_path / "bundle.tar.gz")
    r = _rpk(*a, "debug", "bundle", "-o", out)
    assert r.returncode == 0
    with tarfile.open(out) as tar:
        names = tar.getnames()
    assert {"config.json", "brokers.json", "partitions.json", "metrics.txt"} <= set(names)


def test_metadata_viewer_decodes_offline_state(live_broker, tmp_path):
    """tools/metadata_viewer parity: decode segments + kvstore offline."""
    b = ["--brokers", live_broker["kafka"]]
    _rpk(*b, "topic", "create", "mdv")
    _rpk(*b, "topic", "produce", "mdv", "payload-1")
    _rpk(*b, "topic", "produce", "mdv", "payload-2")
    # the broker's data dir is the fixture tmp dir of the live_broker fixture;
    # find it via admin config
    import urllib.request

    with urllib.request.urlopen(f"http://{live_broker['admin']}/v1/config") as r:
        data_dir = json.loads(r.read())["data_directory"]
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "metadata_viewer.py"),
         "log", data_dir, "kafka/mdv/0", "--records"],
        capture_output=True, text=True, timeout=30,
    )
    assert out.returncode == 0, out.stderr
    assert "payload-1" in out.stdout and "payload-2" in out.stdout
    assert "crc=ok" in out.stdout
    kv = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "metadata_viewer.py"),
         "kvstore", data_dir],
        capture_output=True, text=True, timeout=30,
    )
    assert kv.returncode == 0, kv.stderr
    assert "topic_cfg/kafka/mdv" in kv.stdout


def test_cli_wasm_and_generate(live_broker, tmp_path):
    r = _rpk("wasm", "generate")
    template = json.loads(r.stdout)
    assert template["input_topics"]
    b = ["--brokers", live_broker["kafka"]]
    _rpk(*b, "topic", "create", "wsrc")
    template["input_topics"] = ["wsrc"]
    template["name"] = "cli-transform"
    f = tmp_path / "transform.json"
    f.write_text(json.dumps(template))
    r = _rpk(*b, "wasm", "deploy", str(f))
    assert r.returncode == 0, r.stdout + r.stderr
    r = _rpk(*b, "wasm", "remove", "cli-transform")
    assert r.returncode == 0
    # events actually landed on the internal topic
    r = _rpk(*b, "topic", "consume", "coprocessor_internal_topic", "-n", "2")
    lines = [json.loads(line) for line in r.stdout.strip().splitlines()]
    assert len(lines) == 2
    r = _rpk("--admin-api", live_broker["admin"], "generate", "prometheus-config")
    assert json.loads(r.stdout)["scrape_configs"][0]["metrics_path"] == "/metrics"
    # real tuner framework: dry-run against the real root only READS state
    r = _rpk("tune", "all", "--dry-run")
    assert any(
        tok in r.stdout for tok in ("ok", "would-tune", "unsupported")
    ), r.stdout
    assert "aio_events" in r.stdout


def test_iotune_measures_and_broker_publishes(tmp_path):
    """rpk iotune writes io-config.json; a broker started on that data dir
    publishes the measured numbers at /metrics (iotune.go io-properties
    flow, re-read at startup)."""
    data_dir = tmp_path / "data"
    r = _rpk("iotune", "--directory", str(data_dir), "--probe-mb", "4",
             "--fsync-iters", "5", timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "seq write" in r.stdout and "written" in r.stdout
    cfg = json.loads((data_dir / "io-config.json").read_text())
    assert cfg["version"] == 1
    assert cfg["seq_write_mb_s"] > 0 and cfg["seq_read_mb_s"] > 0
    assert cfg["fsync_4k"]["p99_ms"] >= cfg["fsync_4k"]["p50_ms"] >= 0
    assert not (data_dir / ".iotune.probe").exists()  # probe cleaned up

    kafka_port, admin_port = _free_port(), _free_port()
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "redpanda_tpu", "start",
            "--set", f"data_directory={data_dir}",
            "--set", f"kafka_api_port={kafka_port}",
            "--set", f"advertised_kafka_api_port={kafka_port}",
            "--set", f"admin_api_port={admin_port}",
        ],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, cwd=REPO,
    )
    try:
        import urllib.request

        deadline = time.time() + 30
        while time.time() < deadline:
            try:
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{admin_port}/metrics", timeout=1
                ) as resp:
                    metrics = resp.read().decode()
                if "iotune_seq_write_mb_s" in metrics:
                    break
            except Exception:
                if proc.poll() is not None:
                    raise RuntimeError(f"broker died:\n{proc.stdout.read()}")
                time.sleep(0.2)
        else:
            raise AssertionError("iotune metrics never appeared")
        assert "iotune_fsync_p99_ms" in metrics
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()


def test_iotune_unwritable_directory_fails_cleanly():
    r = _rpk("iotune", "--directory", "/proc/definitely-not-writable")
    assert r.returncode == 1
    assert "cannot characterize" in r.stderr
    assert "Traceback" not in r.stderr


def test_microbench_runs_and_reports(tmp_path):
    """tools/microbench.py (seastar perf-test analogue) emits one JSON
    object of positive rates for every bench."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "microbench.py"),
         "--secs", "0.05"],
        capture_output=True, text=True, timeout=120, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode == 0, r.stdout + r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    expected = {
        "crc32c_mb_s", "xxhash64_mb_s", "batch_encode_per_s",
        "batch_decode_per_s", "compaction_keyindex_keys_per_s",
        "allocator_assignments_per_s", "rpc_echo_rtt_per_s",
    }
    expected |= {"zstd_compress_mb_s", "zstd_uncompress_mb_s"}
    assert expected <= set(out), out
    # rates/costs must be positive; the tracer-overhead percentages and
    # the propagation bench's disabled-tracer wire delta are MEANT to sit
    # at 0 (a 0.0 reading is the bench's best outcome)
    assert all(
        v > 0 for k, v in out.items()
        if not k.endswith("_skipped") and not k.endswith("_pct")
        and not k.endswith("_extra_bytes")
    ), out
    assert out["propagation_disabled_extra_bytes"] == 0
    assert all(v >= 0 for k, v in out.items() if k.endswith("_pct")), out
