#!/usr/bin/env python3
"""Run a list of benchmark runs one after another on this machine and keep
every line they print: the tool the A/A sets in PERF.md were made with.

    python3 benchmarks/sets.py <tag> <seconds> <workload>:<seed>[:<trace>[:extra args]] ...

Each run is ``run.py`` as the driver starts it. Full output goes to
``chiprun_out/<tag>/<n>_<workload>_<seed>.log``, the result lines to
``chiprun_out/<tag>/results.jsonl``; a one-line summary per run is printed.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def main() -> int:
    tag, seconds, runs = sys.argv[1], sys.argv[2], sys.argv[3:]
    out_dir = os.path.join(REPO, "chiprun_out", tag)
    os.makedirs(out_dir, exist_ok=True)
    failures = 0
    with open(os.path.join(out_dir, "results.jsonl"), "a") as results:
        for n, spec in enumerate(runs):
            workload, seed, *rest = spec.split(":", 3)
            trace = rest[0] if rest else "0"
            extra = rest[1].split() if len(rest) > 1 else []
            cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload", workload,
                   "--seed", seed, "--seconds", seconds, "--trace", trace, *extra]
            t0 = time.monotonic()
            proc = subprocess.run(cmd, cwd=REPO, capture_output=True, text=True)
            wall = time.monotonic() - t0
            with open(os.path.join(out_dir, f"{n}_{workload}_{seed}_t{trace}.log"), "w") as f:
                f.write(proc.stdout + "\n---- stderr ----\n" + proc.stderr)
            lines = proc.stdout.strip().splitlines()
            try:
                last = json.loads(lines[-1])
            except (IndexError, ValueError):
                last = None
            rec = {"workload": workload, "seed": int(seed), "trace": int(trace),
                   "seconds": float(seconds), "rc": proc.returncode, "wall_s": wall,
                   "result": last,
                   "selections": next((ln.split("selections: ", 1)[1] for ln in lines
                                       if "selections: " in ln), None)}
            results.write(json.dumps(rec) + "\n")
            results.flush()
            if proc.returncode or not last or not last.get("correct"):
                failures += 1
                print(f"FAILED rc={proc.returncode}: {' '.join(cmd)}\n"
                      + "\n".join(lines[-15:]) + "\n" + proc.stderr[-2000:])
                continue
            vals = {k: round(v["value"], 4) for k, v in last["metrics"].items()}
            print(f"{workload} seed={seed} trace={trace} wall={wall:.0f}s "
                  f"correct={last['correct']} {json.dumps(vals)}", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
