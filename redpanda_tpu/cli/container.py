"""`rpk container` — a local multi-broker cluster for development.

The reference's `rpk container` manages a throwaway local cluster in
docker (src/go/rpk/pkg/cli/cmd/container, one container per broker). On
TPU hosts the natural unit is a PROCESS, not a container: each broker is a
detached `python -m redpanda_tpu start`, the cluster state (ports, pids,
data dirs) lives in one JSON file, and teardown is signal + rm. Same
lifecycle surface: start / status / stop / purge.

An accelerator chip belongs to one process at a time, so ``start`` hands
each visible chip to one broker and pins every other broker to JAX's CPU
backend explicitly (``chip_assignment``); a broker that merely inherited
the environment would fail at its first device touch, or hang.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request

DEFAULT_DIR = os.path.join(
    os.environ.get("XDG_STATE_HOME", os.path.expanduser("~/.local/state")),
    "rptpu-container",
)


def chip_assignment(n_brokers: int, n_chips: int) -> list[dict]:
    """Per broker, the environment that decides its JAX platform.

    Broker i < n_chips gets chip i: with one chip the inherited environment
    already means "the chip"; with several, libtpu's per-process variables
    narrow the broker to its own. Every other broker is pinned to the CPU
    backend."""
    envs = []
    for i in range(n_brokers):
        if i >= n_chips:
            envs.append({"JAX_PLATFORMS": "cpu"})
        elif n_chips == 1:
            envs.append({})
        else:
            envs.append({
                "TPU_VISIBLE_CHIPS": str(i),
                "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
                "TPU_PROCESS_BOUNDS": "1,1,1",
            })
    return envs


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except (ProcessLookupError, PermissionError):
        return False
    # reap if it's our own child (start+stop in one process leaves a
    # zombie otherwise; detached use reparents to init, which reaps)
    try:
        done, _ = os.waitpid(pid, os.WNOHANG)
        if done == pid:
            return False
    except ChildProcessError:
        pass
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().split(") ", 1)[1][0] != "Z"
    except OSError:
        return False


def _admin_ready(port: int, timeout: float = 1.0) -> bool:
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/v1/status/ready", timeout=timeout
        ) as r:
            return r.status == 200
    except Exception:
        return False


class LocalCluster:
    def __init__(self, base_dir: str = DEFAULT_DIR):
        self.base_dir = base_dir
        self.state_path = os.path.join(base_dir, "state.json")

    # ------------------------------------------------------------ state
    def load(self) -> dict | None:
        try:
            with open(self.state_path) as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    def _save(self, state: dict) -> None:
        os.makedirs(self.base_dir, exist_ok=True)
        with open(self.state_path, "w") as f:
            json.dump(state, f, indent=2)

    # ------------------------------------------------------------ lifecycle
    def start(self, n: int = 1, wait_s: float = 120.0, extra_sets=None) -> dict:
        if self.load() is not None:
            raise RuntimeError(
                f"cluster already exists in {self.base_dir} "
                "(rpk container stop/purge first)"
            )
        ports = [
            {"kafka": _free_port(), "rpc": _free_port(), "admin": _free_port()}
            for _ in range(n)
        ]
        seeds = ",".join(f"{i}@127.0.0.1:{p['rpc']}" for i, p in enumerate(ports))
        from redpanda_tpu.utils.platform import visible_chips

        n_chips = visible_chips()
        envs = chip_assignment(n, n_chips)
        nodes = []
        for i, p in enumerate(ports):
            data_dir = os.path.join(self.base_dir, f"n{i}")
            os.makedirs(data_dir, exist_ok=True)
            sets = {
                "node_id": i,
                "data_directory": data_dir,
                "kafka_api_port": p["kafka"],
                "advertised_kafka_api_port": p["kafka"],
                "rpc_server_port": p["rpc"],
                "admin_api_port": p["admin"],
            }
            if n > 1:
                sets["seed_servers"] = seeds
            sets.update(extra_sets or {})
            cmd = [sys.executable, "-m", "redpanda_tpu", "start"]
            for k, v in sets.items():
                cmd += ["--set", f"{k}={v}"]
            log = open(os.path.join(data_dir, "broker.log"), "ab")
            proc = subprocess.Popen(
                cmd, stdout=log, stderr=subprocess.STDOUT,
                start_new_session=True,  # survives the rpk process exiting
                env={**os.environ, **envs[i]},
            )
            nodes.append({
                "node_id": i, "pid": proc.pid, **p, "data_dir": data_dir,
                "device": f"chip {i}" if i < n_chips else "cpu",
            })
        state = {"nodes": nodes, "started_at": time.time()}
        self._save(state)
        deadline = time.monotonic() + wait_s
        pending = {nd["node_id"] for nd in nodes}
        while pending and time.monotonic() < deadline:
            for nd in nodes:
                if nd["node_id"] in pending:
                    if not _pid_alive(nd["pid"]):
                        raise RuntimeError(
                            f"node {nd['node_id']} died during startup; see "
                            f"{nd['data_dir']}/broker.log"
                        )
                    if _admin_ready(nd["admin"]):
                        pending.discard(nd["node_id"])
            time.sleep(0.3)
        if pending:
            raise TimeoutError(f"nodes not ready after {wait_s}s: {sorted(pending)}")
        return state

    def status(self) -> list[dict]:
        state = self.load()
        if state is None:
            return []
        out = []
        for nd in state["nodes"]:
            out.append({
                **nd,
                "alive": _pid_alive(nd["pid"]),
                "ready": _admin_ready(nd["admin"]),
            })
        return out

    def stop(self) -> int:
        state = self.load()
        if state is None:
            return 0
        stopped = 0
        for nd in state["nodes"]:
            if _pid_alive(nd["pid"]):
                try:
                    os.kill(nd["pid"], signal.SIGTERM)
                    stopped += 1
                except OSError:
                    pass
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline and any(
            _pid_alive(nd["pid"]) for nd in state["nodes"]
        ):
            time.sleep(0.2)
        for nd in state["nodes"]:
            if _pid_alive(nd["pid"]):
                try:
                    os.kill(nd["pid"], signal.SIGKILL)
                except OSError:
                    pass
        return stopped

    def purge(self) -> None:
        import shutil

        self.stop()
        shutil.rmtree(self.base_dir, ignore_errors=True)

    def brokers(self) -> str:
        state = self.load()
        if state is None:
            return ""
        return ",".join(f"127.0.0.1:{nd['kafka']}" for nd in state["nodes"])


def cmd_container(args) -> int:
    cluster = LocalCluster(args.dir or DEFAULT_DIR)
    if args.container_cmd == "start":
        extra = {}
        for kv in getattr(args, "set", None) or []:
            k, _, v = kv.partition("=")
            extra[k] = v
        state = cluster.start(args.nodes, extra_sets=extra)
        print(f"started {len(state['nodes'])} broker(s) in {cluster.base_dir}")
        print(f"brokers: {cluster.brokers()}")
        for nd in state["nodes"]:
            print(
                f"  node {nd['node_id']}: kafka 127.0.0.1:{nd['kafka']} "
                f"admin 127.0.0.1:{nd['admin']} pid {nd['pid']} "
                f"device {nd['device']}"
            )
        return 0
    if args.container_cmd == "status":
        rows = cluster.status()
        if not rows:
            print("no local cluster")
            return 1
        for nd in rows:
            state = "ready" if nd["ready"] else ("up" if nd["alive"] else "DOWN")
            print(
                f"node {nd['node_id']}: {state} kafka 127.0.0.1:{nd['kafka']} "
                f"admin 127.0.0.1:{nd['admin']} pid {nd['pid']}"
            )
        return 0
    if args.container_cmd == "stop":
        print(f"stopped {cluster.stop()} broker(s)")
        return 0
    if args.container_cmd == "purge":
        cluster.purge()
        print(f"purged {cluster.base_dir}")
        return 0
    print("usage: rpk container {start|status|stop|purge}", file=sys.stderr)
    return 2
