"""Cross-shard isolation: a shard worker touches ONLY its own shard.

The host-stage pool (coproc/host_pool.py) gets its correctness from a
single discipline: every per-shard worker body produces exactly one
``_HostShard`` and never writes anybody else's — no sibling shard slots,
no launch/engine attributes, no partition-map entries. Fan-in back to
shared state happens after ``pool.run()`` returns, on the submitter
thread (or under the owner's lock). The reference enforces the same
contract structurally — Seastar shards mutate another shard's partition
map only via ``submit_to`` onto its owning reactor — but Python threads
share everything, so the contract here is convention, and this checker
is what keeps the convention honest.

Naming convention the checker leans on (engine.py follows it): per-shard
worker bodies carry a ``shard`` name token (``_run_mesh_shard``,
``_frame_shard``); launch-wide coordinators use ``sharded``
(``_framed_sharded``) and are exempt — they run on the submitter thread
after the fan-in barrier and own the merge.

Rules:

- SHD601 — a worker writes through a shards table (``launch._shards[i]``,
  ``shards[j].field``): reaching a sibling shard by index is exactly the
  cross-shard mutation the pool forbids.
- SHD602 — a worker writes an attribute/element of a SHARED parameter
  (``self``, ``launch``, ``plan``, …) outside a ``with <lock>:`` block.
  Workers write their own shard (a shard-named parameter or an object
  they constructed) and plain locals; results travel via return values.
- SHD603 — any function in scope mutates a queue's internal buffer
  (``q.queue.append(...)``, ``q.queue[i] = ...``): bypassing the Queue
  mutex corrupts the submit/harvest handoff. Use ``put()``/``get()``.
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

_QUEUE_MUTATORS = {
    "append", "appendleft", "extend", "extendleft", "insert",
    "pop", "popleft", "remove", "clear",
}


def _name_tokens(name: str) -> set[str]:
    return set(name.lower().split("_"))


def _is_shard_worker(fn: ast.AST) -> bool:
    """Per-shard worker bodies carry a 'shard' token; 'sharded' names the
    launch-wide coordinators (submitter-thread fan-out/fan-in) instead."""
    if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
        return False
    return "shard" in _name_tokens(fn.name)


def _params(fn) -> set[str]:
    a = fn.args
    names = [p.arg for p in (*a.posonlyargs, *a.args, *a.kwonlyargs)]
    if a.vararg:
        names.append(a.vararg.arg)
    if a.kwarg:
        names.append(a.kwarg.arg)
    return set(names)


def _root_name(node: ast.expr) -> str | None:
    """Leftmost Name of an Attribute/Subscript chain, None otherwise."""
    while isinstance(node, (ast.Attribute, ast.Subscript, ast.Starred)):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


def _shards_subscript(node: ast.expr) -> bool:
    """Does the chain index into a shards table (``*._shards[...]`` /
    ``shards[...]``) anywhere along the way?"""
    while isinstance(node, (ast.Attribute, ast.Subscript, ast.Starred)):
        if isinstance(node, ast.Subscript):
            v = node.value
            coll = v.attr if isinstance(v, ast.Attribute) else (
                v.id if isinstance(v, ast.Name) else ""
            )
            if "shards" in coll.lower():
                return True
        node = node.value
    return False


def _queue_internal(node: ast.expr) -> bool:
    """`<something>.queue` where the owner looks like a queue object —
    the stdlib Queue's internal deque (``q.queue``), not ``put``/``get``."""
    if not (isinstance(node, ast.Attribute) and node.attr == "queue"):
        return False
    owner = node.value
    tail = owner.attr if isinstance(owner, ast.Attribute) else (
        owner.id if isinstance(owner, ast.Name) else ""
    )
    tail = tail.lower()
    return tail.endswith("_q") or tail.endswith("_queue") or "queue" in tail or tail == "q"


def _write_targets(node: ast.AST) -> list[ast.expr]:
    if isinstance(node, ast.Assign):
        return list(node.targets)
    if isinstance(node, (ast.AugAssign, ast.AnnAssign)):
        return [node.target]
    return []


def _flatten(targets: list[ast.expr]) -> Iterator[ast.expr]:
    for t in targets:
        if isinstance(t, (ast.Tuple, ast.List)):
            yield from _flatten(list(t.elts))
        else:
            yield t


def _is_lock_with(node: ast.AST) -> bool:
    if not isinstance(node, (ast.With, ast.AsyncWith)):
        return False
    for item in node.items:
        ctx = item.context_expr
        if isinstance(ctx, ast.Call):
            ctx = ctx.func
        if "lock" in dotted(ctx).lower():
            return True
    return False


class CrossShardChecker(Checker):
    name = "cross-shard"
    rules = {
        "SHD601": "shard worker writes through a shards table (sibling shard mutation)",
        "SHD602": "shard worker writes shared owner state outside a lock",
        "SHD603": "direct mutation of a Queue's internal buffer (bypasses its mutex)",
    }

    def check(self, ctx: FileContext) -> Iterator[RawFinding]:
        for fn in ast.walk(ctx.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            yield from self._check_queue_internals(fn)
            if _is_shard_worker(fn):
                shared = {
                    p for p in _params(fn) if "shard" not in _name_tokens(p)
                }
                yield from self._check_worker(fn, fn.name, shared, locked=False)

    # ---------------------------------------------------------- SHD601/602
    def _check_worker(
        self, node: ast.AST, fn_name: str, shared: set[str], locked: bool
    ) -> Iterator[RawFinding]:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue  # nested defs have their own execution context
            child_locked = locked or _is_lock_with(child)
            for target in _flatten(_write_targets(child)):
                if isinstance(target, ast.Name):
                    continue  # plain locals are the worker's own business
                if _shards_subscript(target):
                    yield RawFinding(
                        "SHD601",
                        target.lineno,
                        target.col_offset,
                        f"{fn_name}() writes a sibling shard's slot through "
                        f"a shards table; a worker owns exactly one shard",
                    )
                    continue
                root = _root_name(target)
                if root in shared and not child_locked:
                    yield RawFinding(
                        "SHD602",
                        target.lineno,
                        target.col_offset,
                        f"{fn_name}() mutates shared '{root}' from a shard "
                        f"worker without a lock; return the result and merge "
                        f"after pool.run(), or take the owner's lock",
                    )
            yield from self._check_worker(child, fn_name, shared, child_locked)

    # -------------------------------------------------------------- SHD603
    def _check_queue_internals(self, fn) -> Iterator[RawFinding]:
        for node in walk_in_function(fn):
            hit: ast.expr | None = None
            if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute):
                if node.func.attr in _QUEUE_MUTATORS and _queue_internal(
                    node.func.value
                ):
                    hit = node.func.value
            else:
                for target in _flatten(_write_targets(node)):
                    probe = target
                    if isinstance(probe, ast.Subscript):
                        probe = probe.value
                    if isinstance(probe, ast.Attribute) and _queue_internal(probe):
                        hit = probe
                    elif _queue_internal(target):
                        hit = target
            if hit is not None:
                yield RawFinding(
                    "SHD603",
                    node.lineno,
                    node.col_offset,
                    f"{fn.name}() reaches into a Queue's internal buffer; "
                    f"only put()/get() hold the mutex",
                )
