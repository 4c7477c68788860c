"""Engine-loop purity: no device→host syncs on the coproc tick/harvest path.

The engine's data path is asynchronous by design: dispatch issues the
launch and ``copy_to_host_async``, and the ONE sanctioned place to pay the
D2H round trip is the dedicated harvester thread (engine._harvest_loop runs
on its own daemon thread, off the event loop). A ``np.asarray(device_arr)``
/ ``.tobytes()`` / ``block_until_ready()`` inside an ``async def`` — or
inside a tick/harvest-named loop body — blocks the broker's event loop for
a full link round trip plus whatever device work is still queued: raft
heartbeats stall and the launch pipeline serializes.

Heuristic scope (no type inference): any call of these shapes inside an
``async def``, or inside a function whose name mentions tick/harvest, in
the checker's scope (defaults to ``redpanda_tpu/coproc``). A sanctioned
sync — e.g. the harvester thread's own fetch — carries a reasoned
``# pandalint: disable=ENG502 -- ...`` pragma, which doubles as
documentation of WHY that sync is allowed to exist.
"""

from __future__ import annotations

import ast
from typing import Iterator

from tools.pandalint.checkers.base import (
    Checker,
    FileContext,
    RawFinding,
    dotted,
    walk_in_function,
)

_NUMPY_ALIASES = {"np", "numpy"}
_SYNC_ATTRS = {"block_until_ready"}
_LOOPY_NAMES = ("tick", "harvest")


def _is_engine_loop(fn: ast.AST) -> bool:
    if isinstance(fn, ast.AsyncFunctionDef):
        return True
    if isinstance(fn, ast.FunctionDef):
        name = fn.name.lower()
        return any(part in name for part in _LOOPY_NAMES)
    return False


class EngineSyncChecker(Checker):
    name = "engine-sync"
    rules = {
        "ENG501": ".tobytes() host materialization on the engine tick/harvest path",
        "ENG502": "np.asarray() device fetch on the engine tick/harvest path",
        "ENG503": "block_until_ready()/jax.device_get() on the engine tick/harvest path",
    }

    def check(self, ctx: FileContext) -> Iterator[RawFinding]:
        for fn in ast.walk(ctx.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if not _is_engine_loop(fn):
                continue
            where = (
                "async" if isinstance(fn, ast.AsyncFunctionDef) else "loop"
            )
            for node in walk_in_function(fn):
                if not isinstance(node, ast.Call):
                    continue
                f = node.func
                # any .tobytes() form: ndarray.tobytes accepts a positional
                # order argument, so arg count must not gate the rule
                if isinstance(f, ast.Attribute) and f.attr == "tobytes":
                    yield RawFinding(
                        "ENG501",
                        node.lineno,
                        node.col_offset,
                        f".tobytes() in {where} {fn.name}() forces a host "
                        f"sync on the engine loop; materialize on the "
                        f"harvester thread",
                    )
                    continue
                name = dotted(f)
                root, _, tail = name.partition(".")
                if root in _NUMPY_ALIASES and tail == "asarray":
                    yield RawFinding(
                        "ENG502",
                        node.lineno,
                        node.col_offset,
                        f"{name}() in {where} {fn.name}() pays the D2H round "
                        f"trip on the engine loop; use copy_to_host_async + "
                        f"the harvester thread",
                    )
                elif (
                    isinstance(f, ast.Attribute) and f.attr in _SYNC_ATTRS
                ) or name == "jax.device_get":
                    yield RawFinding(
                        "ENG503",
                        node.lineno,
                        node.col_offset,
                        f"{name or f.attr}() in {where} {fn.name}() blocks "
                        f"on the device from the engine loop",
                    )
