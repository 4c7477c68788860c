"""CRC-32C on TPU: one MXU matmul + a per-record unwind.

CRC is linear over GF(2): after processing R bytes, the state is

    s_R = A^R(s_0)  XOR  Lin(message)

where A is the one-byte shift matrix and ``Lin`` is a fixed linear map of the
message bits — i.e. a 0/1 matrix W of shape [R*8, 32]. Zero bytes contribute
nothing to Lin, so right-padding rows to R leaves Lin untouched, and the true
state at each record's actual length n is recovered by multiplying with
A^-(R-n) (gathered from a precomputed table).

So CRC-32C of N padded records = bit-unpack -> [N, R*8] @ W (MXU, bf16 in /
f32 accumulate, exact for 0/1 data) -> mod 2 -> XOR constant -> unwind ->
final xor. Everything is static-shaped and fuses under jit; this is the
batched kernel the produce path, recovery scan, and coproc engine share
(reference call sites: kafka_batch_adapter.cc:93, parser.cc:159,
record_utils.cc:82 — each a scalar per-batch CRC there, one [P*B] kernel
here).
"""

from __future__ import annotations

import functools

import numpy as np

from redpanda_tpu.hashing.crc32c import TABLE
from redpanda_tpu.ops import gf2


# ------------------------------------------------------------ host precompute
@functools.lru_cache(maxsize=16)
def _plan(r: int):
    """Precompute (W bits [r*8, 32], K_R const, unwind table [r+1, 32])."""
    a = gf2.byte_matrix()
    # Column images of T for each bit of a byte.
    tcols = np.array([TABLE[1 << m] for m in range(8)], dtype=np.uint32)  # [8]
    # W rows: byte position p (0-based), bit m -> A^(r-1-p)(T[2^m]).
    # Build by iterating p from r-1 down to 0, applying A as we go up.
    w_vals = np.zeros((r, 8), dtype=np.uint32)
    cur = tcols.copy()  # A^0 applied
    for p in range(r - 1, -1, -1):
        w_vals[p] = cur
        cur = _apply_many(a, cur)
    w_bits = ((w_vals.reshape(r * 8, 1) >> np.arange(32, dtype=np.uint32)) & 1).astype(np.uint8)
    # K_R = A^r(0xFFFFFFFF)
    k_r = int(0xFFFFFFFF)
    a_r = gf2.mat_pow(a, r)
    k_r = gf2.mat_apply(a_r, k_r)
    # Unwind: A^-k for k = 0..r, stored as column sets.
    ainv = gf2.mat_inv(a)
    unwind = np.zeros((r + 1, 32), dtype=np.uint32)
    cur_m = gf2.identity_mat()
    for k in range(r + 1):
        unwind[k] = cur_m
        cur_m = _mul(ainv, cur_m)
    return w_bits, np.uint32(k_r), unwind


def _apply_many(m: np.ndarray, xs: np.ndarray) -> np.ndarray:
    """Apply columns-matrix m to a batch of uint32 values."""
    bits = ((xs[:, None] >> np.arange(32, dtype=np.uint32)) & 1).astype(bool)  # [K, 32]
    return np.bitwise_xor.reduce(np.where(bits, m[None, :], np.uint32(0)), axis=1)


def _mul(m2: np.ndarray, m1: np.ndarray) -> np.ndarray:
    return _apply_many(m2, m1)


# ------------------------------------------------------------ device kernel
@functools.lru_cache(maxsize=16)
def make_crc_fn(r: int):
    """Build a jitted fn(data uint8 [N, r], lengths int32 [N]) -> uint32 [N]."""
    import jax
    import jax.numpy as jnp

    w_bits, k_r, unwind = _plan(r)
    w_dev = jnp.asarray(w_bits, dtype=jnp.bfloat16)  # [r*8, 32]
    unwind_dev = jnp.asarray(unwind)  # [r+1, 32] uint32
    k_r_dev = jnp.uint32(k_r)

    @jax.jit
    def rp_crc32c(data, lengths):
        n = data.shape[0]
        # Zero out bytes beyond each record's length: the GF(2) linear part
        # only ignores padding if the padding is zero.
        valid = jnp.arange(r, dtype=jnp.int32)[None, :] < lengths[:, None]
        data = jnp.where(valid, data, jnp.uint8(0))
        # bit-unpack: [N, r] uint8 -> [N, r*8] (bit m of byte p at p*8+m)
        bits = (data[:, :, None] >> jnp.arange(8, dtype=jnp.uint8)) & 1
        bits = bits.reshape(n, r * 8).astype(jnp.bfloat16)
        # MXU: exact 0/1 matmul with f32 accumulation.
        counts = jax.lax.dot_general(
            bits,
            w_dev,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        lin_bits = counts.astype(jnp.int32) & 1  # [N, 32]
        lin = jnp.sum(
            lin_bits.astype(jnp.uint32) << jnp.arange(32, dtype=jnp.uint32), axis=1
        ).astype(jnp.uint32)
        s_r = lin ^ k_r_dev
        # Unwind trailing zeros: s_n = A^-(r - len)(s_R)
        k = jnp.clip(r - jnp.clip(lengths, 0, r), 0, r)
        cols = unwind_dev[k]  # [N, 32]
        sbits = ((s_r[:, None] >> jnp.arange(32, dtype=jnp.uint32)) & 1).astype(bool)
        picked = jnp.where(sbits, cols, jnp.uint32(0))
        # XOR-reduce the 32 picked columns in 5 halving rounds.
        v = picked
        for _ in range(5):
            v = v[:, 0::2] ^ v[:, 1::2]
        s_n = v[:, 0]
        return s_n ^ jnp.uint32(0xFFFFFFFF)

    return rp_crc32c


def crc32c_device(data, lengths):
    """CRC-32C of N zero-padded records on the default backend.

    data: uint8 [N, R] (or any leading shape collapsible to N), lengths int32.
    """
    import jax.numpy as jnp

    data = jnp.asarray(data, dtype=jnp.uint8)
    lengths = jnp.asarray(lengths, dtype=jnp.int32)
    lead = data.shape[:-1]
    r = data.shape[-1]
    fn = make_crc_fn(r)
    flat = fn(data.reshape(-1, r), lengths.reshape(-1))
    return flat.reshape(lead)
