"""Coproc: deploy events, the pacemaker and the TPU engine.

The engine's names are re-exported lazily: ``redpanda_tpu.coproc.engine``
imports JAX (seconds), and a client that only builds a deploy record
(``from redpanda_tpu.coproc import wasm_event``: rpk, the benchmark's
harness, whose first deploy runs inside a 3 s profiler capture) must not
pay for it.
"""

__all__ = [
    "TpuEngine",
    "ProcessBatchRequest",
    "ProcessBatchReply",
    "EnableResponseCode",
    "DisableResponseCode",
    "ErrorPolicy",
]


def __getattr__(name: str):
    if name in __all__:
        from redpanda_tpu.coproc import engine

        return getattr(engine, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
