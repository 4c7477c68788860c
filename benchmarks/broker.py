"""Start, watch and stop the system under test: one broker process through
the program's normal entry point (copied from ``chip_smoke.py`` stage A:
child in its own session, ready = ``/v1/coproc/status`` names its device,
SIGTERM then SIGKILL to the whole group)."""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
LAUNCHER = os.path.join(HERE, "launcher.py")  # a test puts a broken one here


class BrokerFailure(Exception):
    pass


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Broker:
    def __init__(self, repo: str, run_dir: str, properties: dict, cores: list[int]):
        self.repo = repo
        self.run_dir = run_dir
        self.ports = {k: free_port() for k in ("kafka", "rpc", "admin")}
        self.control_dir = os.path.join(run_dir, "control")
        os.makedirs(self.control_dir)
        self.log_path = os.path.join(run_dir, "broker.log")
        self._n_commands = 0
        cmd = [sys.executable, LAUNCHER, "--control-dir", self.control_dir,
               "--cores", ",".join(map(str, cores)), "--", "start"]
        for k, v in {
            **properties,
            "node_id": 0,
            "data_directory": os.path.join(run_dir, "data"),
            "kafka_api_port": self.ports["kafka"],
            "advertised_kafka_api_port": self.ports["kafka"],
            "rpc_server_port": self.ports["rpc"],
            "admin_api_port": self.ports["admin"],
        }.items():
            cmd += ["--set", f"{k}={v}"]
        self.t_start = time.monotonic()
        with open(self.log_path, "wb") as log:
            self.proc = subprocess.Popen(
                cmd, cwd=repo, stdin=subprocess.PIPE, stdout=log,
                stderr=subprocess.STDOUT, start_new_session=True,
                env={**os.environ, "PYTHONPATH": repo},
            )

    def admin(self, path: str, timeout: float = 10.0) -> bytes:
        url = f"http://127.0.0.1:{self.ports['admin']}{path}"
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.read()

    def status(self) -> dict:
        return json.loads(self.admin("/v1/coproc/status"))

    def wait_ready(self, timeout_s: float = 180.0) -> dict:
        """The engine's device block, once the engine has named it (the
        admin API answers before the accelerator backend is up)."""
        while True:
            if self.proc.poll() is not None:
                raise BrokerFailure(
                    f"broker exited {self.proc.returncode} during start-up:\n" + self.tail()
                )
            try:
                status = json.loads(self.admin("/v1/coproc/status", 2.0))
                if status.get("device"):
                    self.ready_s = time.monotonic() - self.t_start
                    return status["device"]
            except (OSError, ValueError):
                pass
            if time.monotonic() - self.t_start > timeout_s:
                raise BrokerFailure(f"broker not ready after {timeout_s} s:\n" + self.tail())
            time.sleep(0.2)

    def control(self, cmd: dict, timeout_s: float = 60.0) -> dict:
        """One command to the launcher's control thread, and its reply."""
        self._n_commands += 1
        self.proc.stdin.write((json.dumps(cmd) + "\n").encode())
        self.proc.stdin.flush()
        path = os.path.join(self.control_dir, f"reply.{self._n_commands}.json")
        deadline = time.monotonic() + timeout_s
        while not os.path.exists(path):
            if self.proc.poll() is not None or time.monotonic() > deadline:
                raise BrokerFailure(f"no reply to {cmd['cmd']}:\n" + self.tail())
            time.sleep(0.02)
        with open(path) as f:
            out = json.load(f)
        if "error" in out:
            raise BrokerFailure(f"{cmd['cmd']}: {out['error']}")
        return out

    def tail(self, n: int = 4000) -> str:
        try:
            with open(self.log_path, "rb") as f:
                return f.read()[-n:].decode(errors="replace")
        except OSError as exc:
            return f"(no log: {exc})"

    def stop(self) -> None:
        stop_group(self.proc)


def stop_group(proc: subprocess.Popen) -> None:
    """SIGTERM the child's whole process group, SIGKILL what is left, and
    wait until it has ended."""
    for sig, wait_s in ((signal.SIGTERM, 20.0), (signal.SIGKILL, 10.0)):
        if proc.poll() is not None:
            break
        try:
            os.killpg(proc.pid, sig)
        except ProcessLookupError:
            break
        try:
            proc.wait(timeout=wait_s)
        except subprocess.TimeoutExpired:
            continue
    for pipe in (proc.stdin, proc.stdout):
        if pipe is not None:
            try:
                pipe.close()
            except OSError:
                pass
