"""Runs the program's normal entry point (``python -m redpanda_tpu start
...``) in this process, with one extra thread that serves the harness:
only the process that holds the chip can trace it or read its memory.

    python3 launcher.py --control-dir D [--cores 0,1,2] -- start --set k=v ...

Commands arrive as JSON lines on stdin; the reply to command ``n`` is the
file ``D/reply.<n>.json``. ``trace_start`` / ``trace_stop`` bracket a
``jax.profiler`` trace; ``memory`` reads the devices' peak bytes in use,
``gc`` the interpreter's collection counts (a full collection is a stall
the tail metrics feel). Nothing here runs unless the harness asks: a
``--trace 0`` run asks for ``gc`` at its window's edges and for ``memory``
once the window has closed.
"""

from __future__ import annotations

import json
import os
import runpy
import sys
import threading


def _serve(control_dir: str) -> None:
    n = 0
    for line in sys.stdin:
        n += 1
        try:
            out = _do(json.loads(line))
        except Exception as exc:  # the harness reports it; the broker keeps running
            out = {"error": repr(exc)}
        tmp = os.path.join(control_dir, f"reply.{n}.tmp")
        with open(tmp, "w") as f:
            json.dump(out, f)
        os.replace(tmp, os.path.join(control_dir, f"reply.{n}.json"))


def _do(cmd: dict) -> dict:
    import jax

    op = cmd["cmd"]
    if op == "trace_start":
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(cmd["dir"], profiler_options=opts)
        return {"ok": True}
    if op == "trace_stop":
        jax.profiler.stop_trace()
        return {"ok": True}
    if op == "gc":
        import gc

        return {"collections": [g["collections"] for g in gc.get_stats()]}
    if op == "memory":
        devs = jax.local_devices()
        stats = [d.memory_stats() or {} for d in devs]
        return {
            "platform": devs[0].platform,
            "kind": devs[0].device_kind,
            "count": len(devs),
            "peak_bytes_in_use": [s.get("peak_bytes_in_use") for s in stats],
            "bytes_limit": [s.get("bytes_limit") for s in stats],
        }
    raise ValueError(f"unknown command {op!r}")


def main() -> None:
    argv = sys.argv[1:]
    split = argv.index("--")
    own, entry = argv[:split], argv[split + 1 :]
    opts = dict(zip(own[::2], own[1::2]))
    if opts.get("--cores"):
        os.sched_setaffinity(0, [int(c) for c in opts["--cores"].split(",")])
    threading.Thread(
        target=_serve, args=(opts["--control-dir"],), daemon=True,
        name="perfbench-control",
    ).start()
    sys.argv = ["redpanda_tpu", *entry]
    runpy.run_module("redpanda_tpu", run_name="__main__", alter_sys=True)


if __name__ == "__main__":
    main()
