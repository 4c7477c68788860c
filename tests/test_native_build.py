"""The native library's on-demand build under several importers at once.

``pytest -n 6`` on a fresh checkout starts six interpreters that import
``redpanda_tpu.native`` together; each runs ``make`` in ``native/``. The
Makefile links to a name of its own and renames onto the .so, and the
loader builds under a file lock, so no importer loads a half-written file
and takes the numpy twins for its whole run (the take-up run of PR 33's
parent tree counted 1,147 of 1,179 for exactly that).
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHILD = """
import json, sys
from redpanda_tpu.native import _build_and_load
lib, error = _build_and_load(sys.argv[1])
print(json.dumps({"error": error, "symbols": sorted(
    k for k, v in vars(lib).items() if k.startswith("has_") and v) if lib else None}))
"""


@pytest.mark.skipif(shutil.which("make") is None or shutil.which("g++") is None,
                    reason="no toolchain to build the native library with")
def test_six_importers_of_a_fresh_checkout_all_load_the_whole_library(tmp_path):
    native = tmp_path / "native"
    native.mkdir()
    for name in ("Makefile", "redpanda_native.cc"):
        shutil.copy(os.path.join(REPO, "native", name), native / name)
    assert not (native / "libredpanda_native.so").exists()
    env = {**os.environ, "PYTHONPATH": REPO}
    procs = [
        subprocess.Popen([sys.executable, "-c", CHILD, str(native)], env=env, cwd=str(tmp_path),
                         stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for _ in range(6)
    ]
    outs = [p.communicate(timeout=300) for p in procs]
    assert [p.returncode for p in procs] == [0] * 6, outs
    seen = [json.loads(out.strip().splitlines()[-1]) for out, _ in outs]
    assert all(s["error"] is None for s in seen), seen
    from redpanda_tpu.native import status

    # every importer binds the whole set, the one this process has
    want = sorted(k for k, v in status()["symbols"].items() if v and k != "has_zstd_many")
    for s in seen:
        assert [k for k in s["symbols"] if k != "has_zstd_many"] == want
    assert len({tuple(s["symbols"]) for s in seen}) == 1
    # nothing of the build is left beside the library but the lock
    assert sorted(os.listdir(native)) == [
        ".build.lock", "Makefile", "libredpanda_native.so", "redpanda_native.cc"]
