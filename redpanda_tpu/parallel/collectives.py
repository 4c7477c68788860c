"""Cross-partition collectives (ICI) for the host consensus plane.

The reference aggregates per-group raft votes and heartbeat responses in
host code, one message at a time (heartbeat_manager.cc:155-204 batches them
per destination node). Here the batched analogues run as mesh collectives:

- ``make_vote_aggregator``: each device holds vote bits for the raft groups
  whose partitions it owns, laid out [n_dev, groups_per_dev] over the 'p'
  axis; one ``psum``-style all-gather yields the per-group tally on every
  device so the host reads a single array instead of n messages (BASELINE
  config 5's vote-aggregation kernel).
- ``make_sharded_crc_check``: the per-shard batched CRC over all partitions
  (config 5's first half): CRC every batch of every partition in one
  sharded launch and reduce per-partition validity counts.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from jax import shard_map
from jax.sharding import PartitionSpec as P

from redpanda_tpu.parallel.mesh import PARTITION_AXIS
from redpanda_tpu.ops.crc32c_device import make_crc_fn


def make_vote_aggregator(mesh):
    """Returns fn(votes uint8 [D, G]) -> int32 [G]: total votes per group.

    votes is sharded over 'p' on the leading device axis; the reduction is a
    psum over the mesh so every shard (and the host) sees the full tally.
    """

    def _local(votes):
        # votes block: [1, G] on each device -> psum over 'p'
        return jax.lax.psum(votes.astype(jnp.int32).sum(axis=0), PARTITION_AXIS)

    fn = shard_map(
        _local,
        mesh=mesh,
        in_specs=P(PARTITION_AXIS, None),
        out_specs=P(),
    )
    return jax.jit(fn)


def make_sharded_coproc_step(mesh, spec_json: str, r_batch: int, r_rec: int):
    """The full per-tick device program, sharded over the partition axis.

    One launch covers what the reference spreads across three host loops
    (SURVEY §3.2/§3.4): produce-path batch CRC validation, the coproc
    record transform, and the cross-partition vote aggregation collective.

    fn(batch_rows [P,B,r_batch] u8, batch_lens [P,B] i32, claimed [P,B] u32,
       rec_rows [P,N,r_rec] u8, rec_lens [P,N] i32, votes [P,G] u8)
      -> (ok [P,B] bool, out [P,N,r_out] u8, out_len [P,N] i32,
          keep [P,N] bool, tally [G] i32)
    """
    import jax.numpy as jnp
    from redpanda_tpu.ops.transforms import TransformSpec, compile_transform, transform_out_width

    spec = TransformSpec.from_json(spec_json)
    batch_crc = make_crc_fn(r_batch)
    tfn = compile_transform(spec, r_rec)

    def _local(b_rows, b_lens, claimed, rec_rows, rec_lens, votes):
        p, b, _ = b_rows.shape
        got = batch_crc(b_rows.reshape(p * b, r_batch), b_lens.reshape(p * b)).reshape(p, b)
        ok = (got == claimed) & (b_lens > 0)
        n = rec_rows.shape[1]
        out, out_len, keep = tfn(rec_rows.reshape(p * n, r_rec), rec_lens.reshape(p * n))
        r_out = out.shape[-1]
        tally = jax.lax.psum(votes.astype(jnp.int32).sum(axis=0), PARTITION_AXIS)
        return (
            ok,
            out.reshape(p, n, r_out),
            out_len.reshape(p, n),
            keep.reshape(p, n),
            tally,
        )

    fn = shard_map(
        _local,
        mesh=mesh,
        in_specs=(
            P(PARTITION_AXIS, None, None),
            P(PARTITION_AXIS, None),
            P(PARTITION_AXIS, None),
            P(PARTITION_AXIS, None, None),
            P(PARTITION_AXIS, None),
            P(PARTITION_AXIS, None),
        ),
        out_specs=(
            P(PARTITION_AXIS, None),
            P(PARTITION_AXIS, None, None),
            P(PARTITION_AXIS, None),
            P(PARTITION_AXIS, None),
            P(),
        ),
    )
    return jax.jit(fn)


def make_crc_vote_step(mesh, r: int):
    """The config-5 raft step in ONE sharded launch: batched CRC
    validation of every partition's batches AND the cross-partition vote
    tally (BASELINE config 5; SURVEY §2.4).

    Returns fn(rows u8 [D, B, r], lens i32 [D, B], claimed u32 [D, B],
    votes u8 [D, G]) -> (ok bool [D, B], bad i32 [D], tally i32 [G]).

    The CRC kernel is vmapped over the sharded device axis (each chip
    CRCs only the batches of the partitions it owns); the tally is the
    one collective — a psum over 'p' — so every shard (and the host)
    reads the full per-group count without n_dev separate messages.
    """
    crc = make_crc_fn(r)

    def _local(rows, lens, claimed, votes):
        # block shapes: rows [1, B, r], votes [1, G]
        got = jax.vmap(crc)(rows, lens)
        ok = (got == claimed) & (lens > 0)
        bad = jnp.sum((~ok) & (lens > 0), axis=1).astype(jnp.int32)
        tally = jax.lax.psum(votes.astype(jnp.int32).sum(axis=0), PARTITION_AXIS)
        return ok, bad, tally

    fn = shard_map(
        _local,
        mesh=mesh,
        in_specs=(
            P(PARTITION_AXIS, None, None),
            P(PARTITION_AXIS, None),
            P(PARTITION_AXIS, None),
            P(PARTITION_AXIS, None),
        ),
        out_specs=(P(PARTITION_AXIS, None), P(PARTITION_AXIS), P()),
    )
    return jax.jit(fn)


def make_sharded_crc_check(mesh, r: int):
    """Returns fn(rows uint8 [P, B, r], lens int32 [P, B], claimed uint32
    [P, B]) -> (ok bool [P, B], bad_per_partition int32 [P]).

    Rows shard over 'p'; the CRC matmul runs per shard with no cross-device
    traffic; only the scalar summary is replicated.
    """
    crc = make_crc_fn(r)

    def _local(rows, lens, claimed):
        p, b, _ = rows.shape
        got = crc(rows.reshape(p * b, r), lens.reshape(p * b)).reshape(p, b)
        ok = (got == claimed) & (lens > 0)
        bad = jnp.sum((~ok) & (lens > 0), axis=1).astype(jnp.int32)
        return ok, bad

    fn = shard_map(
        _local,
        mesh=mesh,
        in_specs=(P(PARTITION_AXIS, None, None), P(PARTITION_AXIS, None), P(PARTITION_AXIS, None)),
        out_specs=(P(PARTITION_AXIS, None), P(PARTITION_AXIS)),
    )
    return jax.jit(fn)
