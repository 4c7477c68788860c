"""Sandboxed wire-deployable transforms (coproc/sandbox.py).

Containment tests: every classic python-sandbox escape route must be
rejected at VALIDATION time (the deploy path), runaway execution must be
cut by the line budget, and the happy path must transform records through
the real engine with both error policies. The reference gets this
isolation from its out-of-process V8 supervisor
(src/js/modules/supervisors/); here the boundary is the restricted AST +
execution budget, so these tests are the security contract.
"""

from __future__ import annotations

import json
import time

import pytest

from redpanda_tpu.coproc.sandbox import (
    SandboxRuntimeError,
    SandboxViolation,
    compile_transform,
    validate_source,
)

GOOD = """
def transform(value):
    doc = json_loads(value.decode())
    if doc.get("level") != "error":
        return None
    out = {"code": int(doc["code"]) * 2, "msg": doc["msg"].upper()}
    return json_dumps(out)
"""


def test_happy_path_transform():
    fn = compile_transform(GOOD)
    rec = json.dumps({"level": "error", "code": 21, "msg": "boom"}).encode()
    assert json.loads(fn(rec)) == {"code": 42, "msg": "BOOM"}
    assert fn(json.dumps({"level": "info"}).encode()) is None


MALICIOUS = [
    # imports
    "import os\ndef transform(value):\n    return value\n",
    "def transform(value):\n    import os\n    return value\n",
    "def transform(value):\n    __import__('os')\n    return value\n",
    # dunder / attribute escapes (the __class__.__mro__ ladder)
    "def transform(value):\n    return ().__class__.__mro__\n",
    "def transform(value):\n    return value.__class__\n",
    "def transform(value):\n    x = getattr(value, 'decode')\n    return x()\n",
    "def transform(value):\n    return open('/etc/passwd').read()\n",
    "def transform(value):\n    exec('x=1')\n    return value\n",
    "def transform(value):\n    eval('1')\n    return value\n",
    # attribute not in safe set / assignment
    "def transform(value):\n    return value.format()\n",
    "def transform(value):\n    value.x = 1\n    return value\n",
    # state/scoping escapes
    "x = 1\ndef transform(value):\n    return value\n",
    "def transform(value):\n    global leak\n    leak = value\n    return value\n",
    "def transform(value):\n    def inner():\n        return 1\n    return value\n",
    "def transform(value):\n    f = lambda: 1\n    return value\n",
    # wrong shape
    "def other(value):\n    return value\n",
    "def transform(a, b):\n    return a\n",
    "def transform(value, *rest):\n    return value\n",
    # generators-as-coroutines
    "def transform(value):\n    yield value\n",
    # await/async
    "async def transform(value):\n    return value\n",
    # walrus into comprehension leak is fine to refuse outright
    "def transform(value):\n    return [y := 1 for _ in range(1)]\n",
]


@pytest.mark.parametrize("src", MALICIOUS, ids=range(len(MALICIOUS)))
def test_malicious_sources_rejected(src):
    with pytest.raises(SandboxViolation):
        validate_source(src)


def test_runaway_loop_hits_budget():
    fn = compile_transform(
        "def transform(value):\n"
        "    n = 0\n"
        "    while True:\n"
        "        n = n + 1\n"
        "    return value\n"
    )
    with pytest.raises(SandboxRuntimeError):
        fn(b"x")


def test_runaway_recursion_contained():
    fn = compile_transform(
        "def transform(value):\n    return transform(value)\n"
    )
    with pytest.raises((SandboxRuntimeError, RecursionError)):
        fn(b"x")


def test_budget_kill_not_swallowable_by_user_except():
    """The documented escape: catch the budget exception with
    `except Exception` (legal syntax), then keep looping with tracing
    unset. The BaseException design + finally/bare-except bans must make
    this terminate with the budget error instead of hanging."""
    fn = compile_transform(
        "def transform(value):\n"
        "    hits = 0\n"
        "    while hits < 3:\n"
        "        try:\n"
        "            n = 0\n"
        "            while True:\n"
        "                n = n + 1\n"
        "        except Exception:\n"
        "            hits = hits + 1\n"
        "    return value\n"
    )
    with pytest.raises(SandboxRuntimeError):
        fn(b"x")


def test_finally_and_broad_except_rejected():
    with pytest.raises(SandboxViolation, match="finally"):
        validate_source(
            "def transform(value):\n"
            "    try:\n        x = 1\n    finally:\n        x = 2\n"
            "    return value\n"
        )
    with pytest.raises(SandboxViolation, match="bare except"):
        validate_source(
            "def transform(value):\n"
            "    try:\n        x = 1\n    except:\n        x = 2\n"
            "    return value\n"
        )
    with pytest.raises(SandboxViolation, match="BaseException"):
        validate_source(
            "def transform(value):\n"
            "    try:\n        x = 1\n    except BaseException:\n        x = 2\n"
            "    return value\n"
        )


def test_pathological_source_is_violation_not_crash():
    # a sub-cap source that blows up the PARSER itself (MemoryError on
    # long operator chains in CPython 3.12) must be a validation failure
    src = "def transform(value):\n    return " + "-" * 60000 + "1\n"
    with pytest.raises(SandboxViolation):
        validate_source(src)


def test_builtins_are_empty_in_sandbox():
    # the compiled function's globals must not expose real builtins
    fn = compile_transform(GOOD)
    # reach the inner transform through the wrapper's closure (the
    # watchdog's _kill helper shares the closure; select by name)
    inner = [
        c.cell_contents
        for c in fn.__closure__
        if callable(c.cell_contents)
        and getattr(c.cell_contents, "__name__", "") == "transform"
    ][0]
    assert inner.__globals__["__builtins__"] == {}
    assert "open" not in inner.__globals__
    assert "getattr" not in inner.__globals__


def test_wrong_return_type_is_an_error():
    fn = compile_transform("def transform(value):\n    return 42\n")
    with pytest.raises(TypeError):
        fn(b"x")


# ------------------------------------------------------------- engine wiring
def test_engine_enable_sandboxed_and_policies():
    from redpanda_tpu.coproc import (
        EnableResponseCode,
        ProcessBatchRequest,
        TpuEngine,
    )
    from redpanda_tpu.coproc.engine import ErrorPolicy, ProcessBatchItem
    from redpanda_tpu.models import NTP, Record, RecordBatch

    def batch(vals):
        return RecordBatch.build(
            [Record(offset_delta=i, value=v) for i, v in enumerate(vals)]
        )

    # malicious source refused at enable (never registered)
    engine = TpuEngine()
    code = engine.enable_py_sandboxed(1, MALICIOUS[0], ("t",))
    assert code == EnableResponseCode.internal_error
    assert engine.heartbeat() == 0

    # skip_on_failure: the crashing record is dropped, others transform
    crashy = (
        "def transform(value):\n"
        "    if value == b'bad':\n"
        "        raise ValueError('nope')\n"
        "    return value.upper()\n"
    )
    assert engine.enable_py_sandboxed(2, crashy, ("t",)) == EnableResponseCode.success
    req = ProcessBatchRequest(
        [ProcessBatchItem(2, NTP.kafka("t", 0), [batch([b"aa", b"bad", b"bb"])])]
    )
    reply = engine.process_batch(req)
    vals = [bytes(v) for b in reply.items[0].batches for v in b.record_values()]
    assert vals == [b"AA", b"BB"]
    assert engine.heartbeat() == 1

    # deregister: one crash unloads the script
    engine2 = TpuEngine()
    assert (
        engine2.enable_py_sandboxed(3, crashy, ("t",), ErrorPolicy.deregister)
        == EnableResponseCode.success
    )
    req2 = ProcessBatchRequest(
        [ProcessBatchItem(3, NTP.kafka("t", 0), [batch([b"aa", b"bad"])])]
    )
    reply2 = engine2.process_batch(req2)
    assert reply2.deregistered == [3]
    assert engine2.heartbeat() == 0
    engine.shutdown()
    engine2.shutdown()


# ---------------------------------------------------- wall-clock watchdog
def _trend_kills():
    from redpanda_tpu.coproc.governor import TREND, journal

    return [
        e for e in journal.entries(domain=TREND)
        if e["verdict"] == "watchdog_kill"
    ]


def test_guard_kills_single_opcode_bigint_before_entry():
    """The canonical uninterruptible burn: ``10**10**8`` is ONE opcode
    holding the GIL for minutes — no tracer line event can interrupt it.
    The compile-time operand guard must refuse it BEFORE entry, fast,
    and journal exactly one governor TREND entry for the incident."""
    from redpanda_tpu.coproc.governor import reset_journal

    reset_journal()
    fn = compile_transform(
        "def transform(value):\n    x = 10 ** 10 ** 8\n    return value\n",
        script_id=901,
    )
    t0 = time.monotonic()
    with pytest.raises(SandboxRuntimeError, match="bits"):
        fn(b"x")
    assert time.monotonic() - t0 < 0.5  # refused pre-entry, not after a burn
    kills = _trend_kills()
    assert len(kills) == 1
    assert kills[0]["inputs"]["script_id"] == 901
    assert kills[0]["inputs"]["layer"] == "guard"
    # the incident journals once per compiled transform, not per record
    with pytest.raises(SandboxRuntimeError):
        fn(b"x")
    assert len(_trend_kills()) == 1


@pytest.mark.parametrize(
    "src",
    [
        "def transform(value):\n    x = 1 << (1 << 30)\n    return value\n",
        "def transform(value):\n    x = 'ab' * (1 << 30)\n    return value\n",
        "def transform(value):\n    x = (1 << 30) * [0]\n    return value\n",
        "def transform(value):\n    x = 2\n    x **= 10 ** 7\n    return value\n",
        "def transform(value):\n"
        "    for i in range(1 << 40):\n        pass\n    return value\n",
    ],
    ids=["lshift", "str-repeat", "list-repeat", "augassign-pow", "range"],
)
def test_guards_refuse_oversized_operands(src):
    fn = compile_transform(src)
    with pytest.raises(SandboxRuntimeError, match="watchdog"):
        fn(b"x")


def test_guards_transparent_for_legit_arithmetic():
    fn = compile_transform(
        "def transform(value):\n"
        "    n = int(value.decode())\n"
        "    out = {'n': n * 3 ** 2, 'pad': 'x' * 4, 'r': [i for i in range(3)]}\n"
        "    return json_dumps(out)\n"
    )
    assert json.loads(fn(b"5")) == {"n": 45, "pad": "xxxx", "r": [0, 1, 2]}


def test_deadline_layer_kills_slow_loop(monkeypatch):
    """Layer 1: a loop that stays under the line budget but over the wall
    deadline is cut by the tracer's deadline check (layer='deadline')."""
    from redpanda_tpu.coproc import sandbox
    from redpanda_tpu.coproc.governor import reset_journal

    reset_journal()
    # 5 ms: a quiet machine runs this loop's 100,000 traced lines in under
    # 50 ms, and then the line budget tripped first (the test failed at the
    # parent commit too); no machine traces a line in 50 ns
    monkeypatch.setattr(sandbox, "EXEC_WALL_DEADLINE_S", 0.005)
    fn = compile_transform(
        # each iteration sleeps via a modest str*int (guard-permitted) so
        # few line events burn real time: deadline trips before budget
        "def transform(value):\n"
        "    n = 0\n"
        "    while n < 50000:\n"
        "        s = 'x' * 65536\n"
        "        n = n + 1\n"
        "    return value\n"
    )
    with pytest.raises(SandboxRuntimeError, match="wall-clock deadline"):
        fn(b"x")
    kills = _trend_kills()
    assert len(kills) == 1
    assert kills[0]["inputs"]["layer"] == "deadline"


def test_post_hoc_layer_catches_residual_overrun(monkeypatch):
    """Layer 3: a single guard-permitted call that overruns the (shrunk)
    deadline finishes — no line event lands mid-call — and the
    post-completion elapsed check still fails the record."""
    from redpanda_tpu.coproc import sandbox
    from redpanda_tpu.coproc.governor import reset_journal

    reset_journal()
    monkeypatch.setattr(sandbox, "EXEC_WALL_DEADLINE_S", 0.01)
    # the slow guard-permitted call sits ON the return line: the tracer's
    # only line event fires before it starts (under deadline), and after
    # it only a "return" event follows — no line event lands to kill it
    fn = compile_transform(
        "def transform(value):\n    return str(sum(range(10000000)))\n"
    )
    with pytest.raises(SandboxRuntimeError, match="deadline"):
        fn(b"x")
    kills = _trend_kills()
    assert len(kills) == 1
    assert kills[0]["inputs"]["layer"] == "post_hoc"


def test_engine_deregisters_on_watchdog_kill():
    """End-to-end policy wiring: a deployed transform that trips the
    operand guard surfaces as a script failure, and deregister policy
    unloads it like any other crash."""
    from redpanda_tpu.coproc import (
        EnableResponseCode,
        ProcessBatchRequest,
        TpuEngine,
    )
    from redpanda_tpu.coproc.engine import ErrorPolicy, ProcessBatchItem
    from redpanda_tpu.coproc.governor import reset_journal
    from redpanda_tpu.models import NTP, Record, RecordBatch

    reset_journal()
    engine = TpuEngine()
    burn = "def transform(value):\n    x = 10 ** 10 ** 8\n    return value\n"
    assert (
        engine.enable_py_sandboxed(7, burn, ("t",), ErrorPolicy.deregister)
        == EnableResponseCode.success
    )
    req = ProcessBatchRequest(
        [ProcessBatchItem(
            7, NTP.kafka("t", 0),
            [RecordBatch.build([Record(offset_delta=0, value=b"x")])],
        )]
    )
    reply = engine.process_batch(req)
    assert reply.deregistered == [7]
    assert engine.heartbeat() == 0
    kills = _trend_kills()
    assert len(kills) == 1
    assert kills[0]["inputs"]["script_id"] == 7
    engine.shutdown()
