"""Fused coproc data-plane pipelines.

Two device programs cover the engine's steady-state loop (SURVEY §3.4):

1. ``make_batch_validator(r)`` — batch-level Kafka-CRC validation over
   ``[N, r]`` prefixed batch rows (replaces the reference's per-batch
   record_batch_crc_checker, record.h:699-721). This is where the device
   CRC kernel earns its keep: the produce path ships claimed wire CRCs up
   with the payload and gets one ok-bit back per batch.
2. ``make_packed_pipeline(spec, r_in)`` — the engine's record transform as a
   single-buffer program: one uint8 staging array in, one uint8 packed
   result out. The link between the broker runtime and the device charges
   per *transfer* as well as per byte, so lengths ride in trailing
   metadata columns of the input array and (out_len, keep) ride in trailing
   columns of the output — exactly one H2D and one D2H per launch. A
   pure filter (``mask_only``) maps nothing, so its result is one keep bit
   a row and the host frames kept values from the bytes it already holds.

The transform output is deliberately CRC-free: output batches are sealed
host-side after framing + optional compression (the Kafka CRC covers the
compressed payload, which only exists after the host codec runs —
script_context_backend.cc:40-68 re-compresses before the CRC for the same
reason). A per-record value CRC computed on device cannot become the batch
CRC, so we don't compute one.

Both programs are shape-specialized and cached; the bridge calls them with
``[P*B, R]`` staging arrays and overlaps H2D/compute/D2H via JAX async
dispatch (see coproc/engine.py).
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp

from redpanda_tpu.ops.crc32c_device import make_crc_fn
from redpanda_tpu.ops.transforms import (
    TransformSpec,
    compile_transform,
    compile_transform_host,
    packbits,
    reports_reason,
    transform_out_width,
)

# Trailing metadata columns of the staged input row: int32 LE record length,
# then 4 pad bytes (keeps the row 8-byte aligned for the host packer).
IN_META = 8
# Trailing metadata columns of the packed output row: int32 LE out_len,
# uint8 keep flag, uint8 reason (why the row was dropped, of a spec that
# ``reports_reason``: transforms.JSON_*; 0 from every other), 2 pad bytes.
OUT_META = 8


@functools.lru_cache(maxsize=16)
def make_batch_validator(r: int):
    """fn(rows uint8 [N, r], lens int32 [N], claimed uint32 [N]) -> ok bool [N]."""
    crc = make_crc_fn(r)

    @jax.jit
    def rp_batch_validate(rows, lens, claimed):
        got = crc(rows, lens)
        return (got == claimed) & (lens > 0)

    return rp_batch_validate


def _packed_body(
    xp, tfn, r_in: int, scope=contextlib.nullcontext, mask_only: bool = False
):
    """staged -> packed around a compiled transform, over namespace ``xp``
    (jax.numpy on the device, numpy for the engine's host fallback).
    ``scope``: ``jax.named_scope`` for the device program, whose name
    (``jit_rp_payload_transform``) is this function's. ``mask_only``: the
    result is the keep mask alone, bit-packed (uint8 [N/8]). A ``tfn``
    compiled ``with_reason`` gives a fourth result, which rides in the
    first of the three trailing bytes that were padding."""

    def rp_payload_transform(staged):
        with scope("parse"):
            data = staged[:, :r_in]
            c = staged[:, r_in : r_in + 4].astype(xp.int32)
            lens = c[:, 0] | (c[:, 1] << 8) | (c[:, 2] << 16) | (c[:, 3] << 24)
        with scope("transform"):
            out, out_len, keep, *reason = tfn(data, lens)
        with scope("frame"):
            if mask_only:
                return packbits(xp, keep)
            masked = xp.where(keep, out_len, 0).astype(xp.int32)
            lenb = xp.stack(
                [((masked >> (8 * k)) & 0xFF).astype(xp.uint8) for k in range(4)],
                axis=1,
            )
            keepb = keep.astype(xp.uint8)[:, None]
            meta = [lenb, keepb] + [why[:, None] for why in reason]
            pad = xp.zeros((out.shape[0], OUT_META - 5 - len(reason)), dtype=xp.uint8)
            return xp.concatenate([out, *meta, pad], axis=1)

    return rp_payload_transform


@functools.lru_cache(maxsize=64)
def _packed_pipeline_cached(spec_json: str, r_in: int, mask_only: bool):
    spec = TransformSpec.from_json(spec_json)
    tfn = compile_transform(spec, r_in, reports_reason(spec) and not mask_only)
    r_out = transform_out_width(spec, r_in)
    body = _packed_body(jnp, tfn, r_in, jax.named_scope, mask_only)
    return jax.jit(body), r_out


def make_packed_pipeline(spec: TransformSpec, r_in: int, mask_only: bool = False):
    """fn(staged uint8 [N, r_in+IN_META]) -> packed uint8 [N, r_out+OUT_META],
    or the bit-packed keep mask uint8 [N/8] with ``mask_only``."""
    return _packed_pipeline_cached(spec.to_json(), int(r_in), bool(mask_only))


def lower_packed_pipeline(fn, shape: tuple[int, int]):
    """The program of a packed pipeline (``make_packed_pipeline``'s ``fn``)
    for one staged shape ``(n_pad, r_in + IN_META)``, lowered and compiled
    without running it: a ``jax.stages.Compiled`` that takes the staged
    device array. The persistent compilation cache serves it as it serves
    a jit call, and the module keeps the function's name
    (``jit_rp_payload_transform``)."""
    return fn.lower(jax.ShapeDtypeStruct(shape, jnp.uint8)).compile()


def make_packed_pipeline_host(
    spec: TransformSpec, r_in: int, mask_only: bool = False
):
    """make_packed_pipeline's numpy twin (same bytes out, no JAX backend):
    the engine's payload-lane host fallback."""
    import numpy as np

    tfn = compile_transform_host(
        spec, int(r_in), reports_reason(spec) and not mask_only
    )
    return _packed_body(np, tfn, int(r_in), mask_only=mask_only)


@functools.lru_cache(maxsize=64)
def _record_pipeline_cached(spec_json: str, r_in: int):
    spec = TransformSpec.from_json(spec_json)
    tfn = compile_transform(spec, r_in)
    r_out = transform_out_width(spec, r_in)

    @jax.jit
    def rp_record_transform(data, lengths):
        out, out_len, keep = tfn(data, lengths)
        masked_len = jnp.where(keep, out_len, 0)
        return out, masked_len, keep

    return rp_record_transform, r_out


def make_record_pipeline(spec: TransformSpec, r_in: int):
    """fn(data uint8 [N, r_in], lens [N]) -> (out [N, r_out], out_len, keep).

    Unpacked variant for tests and the multichip dryrun; the engine's hot
    path uses make_packed_pipeline.
    """
    return _record_pipeline_cached(spec.to_json(), int(r_in))


def unpack_result(packed, r_out: int):
    """Split a fetched packed result (numpy uint8 [N, r_out+OUT_META]) into
    (out [N, r_out], out_len int32 [N], keep bool [N])."""
    import numpy as np

    out = packed[:, :r_out]
    out_len = packed[:, r_out : r_out + 4].copy().view(np.int32).reshape(-1)
    keep = packed[:, r_out + 4].astype(bool)
    return out, out_len, keep


def unpack_reason(packed, r_out: int):
    """The reason column of a fetched packed result (uint8 [N]): why each
    row of a spec that ``reports_reason`` was dropped, 0 for a kept one."""
    return packed[:, r_out + 5]
