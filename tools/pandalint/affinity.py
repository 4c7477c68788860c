"""Whole-program executor-affinity inference (the pandaraces foundation).

The reference is thread-per-core with no shared-state locking; this build
deliberately trades that for a small zoo of execution contexts — the
asyncio loop, the ``rptpu-coproc-tick`` executor pool, daemon threads
(mask harvester, fetch workers), the host-stage pool workers, and
weakref/atexit finalizers. Every past review-round concurrency bug lived
on a boundary between two of those contexts, so the race and lock-order
checkers need one ground truth: *which contexts can execute each
function*.

This module builds that ground truth for a whole parsed program:

1. **Collection** — every function/method/lambda across all files becomes
   a :class:`ProgFunc`, indexed for name-based call resolution (same
   philosophy as jitgraph.py: a false edge is worse than a missed one for
   a gate people must keep green, so resolution is conservative).
2. **Seeding** at spawn sites:

   - ``async def`` → ``loop`` (the function body runs on the event loop);
   - ``loop.run_in_executor(ex, fn, ...)`` / ``asyncio.to_thread(fn)`` →
     ``executor`` (the coproc-tick pool / default executor);
   - ``Thread(target=fn)`` / a ``threading.Thread`` subclass's ``run`` →
     ``daemon`` (harvester, fetch workers, loadgen fleets);
   - callables handed to a ``*pool*.run([...])`` fan-out or
     ``ex.submit(fn)`` → ``pool_worker`` (HostStagePool shard workers);
     lambdas defined in a function that performs such a fan-out count —
     the engine builds its thunk lists before the ``pool.run`` call;
   - ``weakref.finalize(obj, fn)`` / ``atexit.register(fn)`` →
     ``finalizer``;
   - ``loop.call_soon[_threadsafe]/call_later(fn)`` → ``loop``.

3. **Propagation** over resolved calls: a callee inherits every context
   of every caller (monotone fixpoint). Calls resolve through module
   aliases (``from pkg import mod; mod.fn()``), ``from``-imported
   symbols, ``self.``/``cls.`` methods, bare local names, and — for
   plain ``obj.method()`` — by method name only when exactly ONE class
   in the program defines it (ambiguous names would smear contexts
   across unrelated classes).

Contexts are deliberately coarse: ``loop`` is single-threaded, so two
``loop`` sites never race each other, while ``executor`` and
``pool_worker`` are multi-threaded pools that race *themselves*
(`SELF_RACING`) — the duplicate-jit-trace bug class. ``daemon`` models
one dedicated thread per spawn, racing every *other* context but not
itself.
"""

from __future__ import annotations

import ast
import re
from dataclasses import dataclass, field

# ------------------------------------------------------------ context labels
LOOP = "loop"
EXECUTOR = "executor"
DAEMON = "daemon"
POOL_WORKER = "pool_worker"
FINALIZER = "finalizer"

ALL_CONTEXTS = (LOOP, EXECUTOR, DAEMON, POOL_WORKER, FINALIZER)

# The device-mesh execution context is tracked SEPARATELY from the
# concurrency contexts above (ProgFunc.mesh, not ProgFunc.contexts): a
# function handed to ``shard_map(fn, ...)`` is a trace-time SPMD program
# replicated onto every mesh device — it does not RACE host code (tracing
# happens once, on the caller's thread), it must not TOUCH host state at
# all (host calls run at trace time, not per launch, and host effects
# don't shard). Folding it into the race contexts would smear phantom
# RAC11xx findings across every helper a predicate shares with host
# paths; the meshctx checker (MSH13xx) consumes the separate flag.
DEVICE_MESH = "device_mesh"

# call names that seed the device-mesh context at their first argument
_MESH_SPAWNS = {"shard_map"}

# contexts backed by a multi-threaded pool: two activations of the SAME
# context can run concurrently (the PR-3 duplicate-jit-trace shape)
SELF_RACING = frozenset({EXECUTOR, POOL_WORKER})

# name-based obj.method resolution: give up beyond this many candidate
# classes (lock-graph superset edges only; contexts require uniqueness)
AMBIG_LIMIT = 4

# Lifecycle-phase functions (open / recovery / startup): they execute in
# their spawn context (DiskLog._open_sync runs on the to_thread executor)
# but the object is not yet serving concurrent traffic, so their contexts
# do not PROPAGATE to the steady-state helpers they call — otherwise every
# helper shared between recovery and the serve path reads as cross-context
# and the race checker buries real findings under startup noise. The race
# checker also exempts these functions' own accesses (same rationale as
# __init__). Documented limitation: a genuine open-vs-serve overlap is
# invisible to the analysis.
LIFECYCLE = re.compile(r"(^|_)(start|open|load|recover|rebuild|restore|bootstrap)")

_EXECUTOR_SPAWNS = {"run_in_executor", "to_thread"}
_LOOP_CALLBACKS = {"call_soon", "call_soon_threadsafe", "call_later", "call_at"}
_THREAD_CTORS = {"Thread", "Timer"}


def dotted(node: ast.expr) -> str:
    """'a.b.c' for Name/Attribute chains, '' otherwise."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def modkey_of(relpath: str) -> str:
    """'redpanda_tpu/coproc/engine.py' -> 'redpanda_tpu.coproc.engine'."""
    p = relpath.replace("\\", "/")
    if p.endswith(".py"):
        p = p[:-3]
    if p.endswith("/__init__"):
        p = p[: -len("/__init__")]
    return p.replace("/", ".")


def modbase(modkey: str) -> str:
    return modkey.rsplit(".", 1)[-1]


@dataclass
class ProgFunc:
    """One function/method/lambda in the analyzed program."""

    node: ast.AST                 # FunctionDef | AsyncFunctionDef | Lambda
    relpath: str
    modkey: str
    cls: str | None               # enclosing class name (methods + lambdas)
    name: str                     # "<lambda>" for lambdas
    lineno: int
    is_method: bool = False       # a DIRECT class member (not nested)
    contexts: set[str] = field(default_factory=set)
    # device-mesh (shard_map-traced) membership — separate from contexts,
    # see DEVICE_MESH above
    mesh: bool = False

    @property
    def qualname(self) -> str:
        if self.cls:
            return f"{self.cls}.{self.name}"
        return self.name


class Program:
    """Collected functions + call resolution + affinity fixpoint for a
    set of parsed modules ``[(relpath, ast.Module), ...]``."""

    def __init__(self, modules: list[tuple[str, ast.Module]]):
        self.modules = list(modules)
        self.funcs: dict[int, ProgFunc] = {}          # id(node) -> info
        # (modkey, name) -> funcs defined anywhere in that module
        self._local: dict[tuple[str, str], list[ProgFunc]] = {}
        # (modkey, name) -> module-LEVEL functions only
        self._module_level: dict[tuple[str, str], list[ProgFunc]] = {}
        # (class name, method name) -> direct methods, program-wide
        self._methods: dict[tuple[str, str], list[ProgFunc]] = {}
        # method name -> direct methods, program-wide (obj.method fallback)
        self._by_method: dict[str, list[ProgFunc]] = {}
        # class name -> [(modkey, ClassDef)]
        self.classes: dict[str, list[tuple[str, ast.ClassDef]]] = {}
        # modkey -> import alias table:
        #   name -> ("module", target_modkey) | ("symbol", modkey, symbol)
        self._aliases: dict[str, dict[str, tuple]] = {}
        self._known_modkeys: set[str] = {modkey_of(rp) for rp, _ in modules}
        for relpath, tree in self.modules:
            self._collect_module(relpath, tree)
        self._seed()
        self._propagate()

    # ------------------------------------------------------------ collection
    def _collect_module(self, relpath: str, tree: ast.Module) -> None:
        modkey = modkey_of(relpath)
        aliases: dict[str, tuple] = {}
        self._aliases[modkey] = aliases
        program = self

        for node in tree.body:
            if isinstance(node, ast.Import):
                for a in node.names:
                    tgt = a.name
                    if tgt in self._known_modkeys:
                        aliases[a.asname or tgt.rsplit(".", 1)[-1]] = (
                            "module", tgt,
                        )
            elif isinstance(node, ast.ImportFrom) and node.module:
                base = node.module
                for a in node.names:
                    full = f"{base}.{a.name}"
                    if full in self._known_modkeys:
                        aliases[a.asname or a.name] = ("module", full)
                    elif base in self._known_modkeys:
                        aliases[a.asname or a.name] = ("symbol", base, a.name)

        class V(ast.NodeVisitor):
            def __init__(self) -> None:
                # stack entries: ("class", name) | ("func", name)
                self.stack: list[tuple[str, str]] = []

            def _cur_class(self) -> str | None:
                for kind, name in reversed(self.stack):
                    if kind == "class":
                        return name
                return None

            def visit_ClassDef(self, node: ast.ClassDef) -> None:
                program.classes.setdefault(node.name, []).append(
                    (modkey, node)
                )
                self.stack.append(("class", node.name))
                self.generic_visit(node)
                self.stack.pop()

            def _func(self, node) -> None:
                is_method = bool(self.stack) and self.stack[-1][0] == "class"
                info = ProgFunc(
                    node=node,
                    relpath=relpath,
                    modkey=modkey,
                    cls=self._cur_class(),
                    name=getattr(node, "name", "<lambda>"),
                    lineno=node.lineno,
                    is_method=is_method,
                )
                program.funcs[id(node)] = info
                program._local.setdefault((modkey, info.name), []).append(info)
                if is_method:
                    program._methods.setdefault(
                        (info.cls, info.name), []
                    ).append(info)
                    program._by_method.setdefault(info.name, []).append(info)
                elif not any(k == "func" for k, _ in self.stack):
                    program._module_level.setdefault(
                        (modkey, info.name), []
                    ).append(info)
                self.stack.append(("func", info.name))
                self.generic_visit(node)
                self.stack.pop()

            visit_FunctionDef = _func
            visit_AsyncFunctionDef = _func

            def visit_Lambda(self, node: ast.Lambda) -> None:
                info = ProgFunc(
                    node=node,
                    relpath=relpath,
                    modkey=modkey,
                    cls=self._cur_class(),
                    name="<lambda>",
                    lineno=node.lineno,
                )
                program.funcs[id(node)] = info
                self.stack.append(("func", "<lambda>"))
                self.generic_visit(node)
                self.stack.pop()

        V().visit(tree)

    # ------------------------------------------------------------ resolution
    def info_for(self, node: ast.AST) -> ProgFunc | None:
        return self.funcs.get(id(node))

    def _class_init(self, cls_name: str) -> list[ProgFunc]:
        return self._methods.get((cls_name, "__init__"), [])

    def resolve_name(self, fn: ProgFunc, name: str) -> list[ProgFunc]:
        """A bare-name call inside ``fn``: local/module functions, then
        ``from``-imported symbols (functions or a class's __init__)."""
        local = [
            f
            for f in self._local.get((fn.modkey, name), [])
            if not f.is_method
        ]
        if local:
            return local
        alias = self._aliases.get(fn.modkey, {}).get(name)
        if alias is not None:
            if alias[0] == "symbol":
                _, mk, sym = alias
                hit = self._module_level.get((mk, sym), [])
                if hit:
                    return hit
                if sym in self.classes:
                    return self._class_init(sym)
        if name in self.classes:
            # class defined in this module (instantiation runs __init__)
            if any(mk == fn.modkey for mk, _ in self.classes[name]):
                return self._class_init(name)
        return []

    def resolve_call(
        self, fn: ProgFunc, call: ast.Call, *, unique_methods: bool = True
    ) -> tuple[list[ProgFunc], bool]:
        """Candidate callees for one call; second element = ambiguous
        (name-based obj.method with more than one candidate class).

        ``unique_methods=True`` (context propagation) drops ambiguous
        matches entirely; False (lock-graph may-acquire) keeps up to
        AMBIG_LIMIT candidates and reports the ambiguity."""
        f = call.func
        if isinstance(f, ast.Name):
            return self.resolve_name(fn, f.id), False
        if not isinstance(f, ast.Attribute):
            return [], False
        chain = dotted(f)
        if not chain:
            return [], False
        parts = chain.split(".")
        base, attr = parts[0], parts[-1]
        if base in ("self", "cls") and fn.cls is not None and len(parts) == 2:
            return self._methods.get((fn.cls, attr), []), False
        alias = self._aliases.get(fn.modkey, {}).get(base)
        if alias is not None and alias[0] == "module" and len(parts) == 2:
            mk = alias[1]
            hit = self._module_level.get((mk, attr), [])
            if hit:
                return hit, False
            if any(m == mk for m, _ in self.classes.get(attr, [])):
                return self._class_init(attr), False
        # plain obj.method: name-based, bounded
        cands = self._by_method.get(attr, [])
        classes = {c.cls for c in cands}
        if len(classes) == 1:
            return cands, False
        if unique_methods:
            return [], len(classes) > 1
        # a receiver named after its class (``arena.acquire``,
        # ``self._host_pool.run``) narrows a common method name to the
        # classes whose name ends with the receiver's last word: without
        # it ``acquire`` / ``run`` exceed the bound and the lock graph
        # loses the edges into Arena._lock and HostStagePool._lock
        word = parts[-2].rsplit("_", 1)[-1].lower()
        named = [c for c in cands if c.cls.lower().endswith(word)] if word else []
        if named:
            return named, len({c.cls for c in named}) > 1
        if len(classes) > AMBIG_LIMIT:
            return [], True
        return cands, True

    def calls_in(self, fn: ProgFunc) -> list[ast.Call]:
        """Call nodes in fn's body, NOT descending into nested defs or
        lambdas (those are their own ProgFuncs with their own contexts)."""
        out: list[ast.Call] = []
        stack = list(ast.iter_child_nodes(fn.node))
        while stack:
            node = stack.pop()
            if isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
            ):
                continue
            if isinstance(node, ast.Call):
                out.append(node)
            stack.extend(ast.iter_child_nodes(node))
        return out

    # ------------------------------------------------------------ seeding
    def _import_neighborhood(self, modkey: str) -> set[str]:
        """The module itself plus every analyzed module it imports —
        the resolution horizon for liberal seed matching."""
        out = {modkey}
        for alias in self._aliases.get(modkey, {}).values():
            out.add(alias[1])
        return out

    def _seed_ref(self, fn: ProgFunc, expr: ast.expr, ctx: str) -> None:
        """Mark the function a callable REFERENCE points at. Seeds are
        liberal on purpose (a missed spawn seed silently blesses a racy
        function as single-context) but bounded by the spawner's import
        neighborhood: ``run_in_executor(ex, pm.engine.submit)`` must seed
        TpuEngine.submit without also smearing ``executor`` onto every
        ``submit`` method in the program — an over-wide seed propagates
        phantom contexts through whole subsystems. ``ctx=DEVICE_MESH``
        sets the separate mesh flag instead of a concurrency context."""

        def mark(h: ProgFunc) -> None:
            if ctx == DEVICE_MESH:
                h.mesh = True
            else:
                h.contexts.add(ctx)

        if isinstance(expr, ast.Lambda):
            info = self.info_for(expr)
            if info is not None:
                mark(info)
            return
        if isinstance(expr, ast.Name):
            hits = self.resolve_name(fn, expr.id)
            if not hits:
                near = self._import_neighborhood(fn.modkey)
                hits = [
                    f
                    for (mk, nm), fs in self._local.items()
                    if nm == expr.id and mk in near
                    for f in fs
                ]
            for h in hits:
                mark(h)
            return
        if isinstance(expr, ast.Attribute):
            chain = dotted(expr)
            parts = chain.split(".") if chain else []
            if (
                len(parts) == 2
                and parts[0] in ("self", "cls")
                and fn.cls is not None
            ):
                for h in self._methods.get((fn.cls, parts[1]), []):
                    mark(h)
                return
            near = self._import_neighborhood(fn.modkey)
            for h in self._by_method.get(expr.attr, []):
                if h.modkey in near:
                    mark(h)

    def _seed(self) -> None:
        for info in self.funcs.values():
            if isinstance(info.node, ast.AsyncFunctionDef):
                info.contexts.add(LOOP)
        # Thread subclasses: run() executes on the spawned thread
        for cls_name, defs in self.classes.items():
            for _mk, node in defs:
                if any("Thread" in dotted(b) for b in node.bases):
                    for m in self._methods.get((cls_name, "run"), []):
                        m.contexts.add(DAEMON)
        for info in list(self.funcs.values()):
            pool_fanout = False
            for call in self.calls_in(info):
                f = call.func
                name = f.attr if isinstance(f, ast.Attribute) else (
                    f.id if isinstance(f, ast.Name) else ""
                )
                recv = dotted(f.value).lower() if isinstance(
                    f, ast.Attribute
                ) else ""
                if name in _EXECUTOR_SPAWNS:
                    # run_in_executor(ex, fn, ...) / to_thread(fn, ...)
                    idx = 1 if name == "run_in_executor" else 0
                    if len(call.args) > idx:
                        self._seed_ref(info, call.args[idx], EXECUTOR)
                elif name in _LOOP_CALLBACKS:
                    for a in call.args:
                        self._seed_ref(info, a, LOOP)
                elif name in _THREAD_CTORS:
                    for kw in call.keywords:
                        if kw.arg == "target":
                            self._seed_ref(info, kw.value, DAEMON)
                elif name == "finalize" or (
                    name == "register" and recv == "atexit"
                ):
                    if name == "finalize" and len(call.args) > 1:
                        self._seed_ref(info, call.args[1], FINALIZER)
                    elif name == "register" and call.args:
                        self._seed_ref(info, call.args[0], FINALIZER)
                elif name == "submit" and (
                    "pool" in recv or "ex" in recv.split(".")[-1]
                ):
                    if call.args:
                        self._seed_ref(info, call.args[0], POOL_WORKER)
                elif name == "run" and "pool" in recv:
                    pool_fanout = True
                    for a in call.args:
                        if isinstance(a, (ast.List, ast.Tuple)):
                            for el in a.elts:
                                self._seed_ref(info, el, POOL_WORKER)
                elif name in _MESH_SPAWNS:
                    # shard_map(fn, mesh=..., ...): fn (and everything it
                    # calls) is an SPMD device program over the mesh
                    if call.args:
                        self._seed_ref(info, call.args[0], DEVICE_MESH)
            if pool_fanout:
                # the engine builds its thunk lists (lambdas calling the
                # real shard bodies) before the pool.run call; every
                # lambda in a fan-out function runs on a pool worker
                for sub in ast.walk(info.node):
                    if isinstance(sub, ast.Lambda):
                        li = self.info_for(sub)
                        if li is not None:
                            li.contexts.add(POOL_WORKER)

    # ------------------------------------------------------------ fixpoint
    def _propagate(self) -> None:
        work = [f for f in self.funcs.values() if f.contexts]
        # monotone: a function re-enters the worklist only when its
        # context set grew
        while work:
            fn = work.pop()
            if LIFECYCLE.search(fn.name):
                continue  # lifecycle contexts don't flow to callees
            for call in self.calls_in(fn):
                callees, _amb = self.resolve_call(fn, call)
                for callee in callees:
                    if not fn.contexts <= callee.contexts:
                        callee.contexts |= fn.contexts
                        work.append(callee)
        self._propagate_mesh()

    def _propagate_mesh(self) -> None:
        """Separate monotone fixpoint for the device-mesh flag: a callee
        of a mesh-traced function is itself traced into the SPMD program
        (no lifecycle exemption — tracing has no startup phase)."""
        work = [f for f in self.funcs.values() if f.mesh]
        while work:
            fn = work.pop()
            for call in self.calls_in(fn):
                callees, _amb = self.resolve_call(fn, call)
                for callee in callees:
                    if not callee.mesh:
                        callee.mesh = True
                        work.append(callee)

    # ------------------------------------------------------------ queries
    def contexts_of(self, node: ast.AST) -> frozenset[str]:
        info = self.funcs.get(id(node))
        return frozenset(info.contexts) if info is not None else frozenset()

    def is_mesh(self, node: ast.AST) -> bool:
        info = self.funcs.get(id(node))
        return bool(info is not None and info.mesh)


def contexts_race(a: frozenset, b: frozenset) -> bool:
    """Can code in context set ``a`` run concurrently with code in ``b``?
    Distinct contexts always race; a shared context races itself only
    when it is pool-backed (executor / pool_worker)."""
    if not a or not b:
        return False
    if (a | b) - (a & b):
        # at least one context on one side the other doesn't share —
        # two distinct contexts are concurrent by construction
        if len(a | b) > 1:
            return True
    return bool((a & b) & SELF_RACING)
