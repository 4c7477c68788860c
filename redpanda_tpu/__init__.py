"""redpanda_tpu — a TPU-native streaming framework.

A brand-new implementation of the capabilities of the reference streaming
platform (Kafka-compatible partitioned logs, Raft replication, consumer
groups, inline record transforms, tiered storage, REST proxy / schema
registry), re-designed TPU-first:

- The host runtime (storage, raft, RPC, Kafka protocol, control plane) is an
  asyncio-based broker with a native extension for the hot byte paths.
- The per-batch data plane — CRC32c validation, (de)compression staging, and
  user map/filter transforms — executes as batched XLA programs over a
  ``[partition, batch, record]`` axis on TPU (``redpanda_tpu.ops``, driven
  by ``redpanda_tpu.coproc.engine``), with shardings laid over a
  ``jax.sharding.Mesh`` for multi-chip scale-out (``redpanda_tpu.parallel``).

Layer map (mirrors SURVEY.md §1 of the reference analysis):

    utils/ hashing/ compression/ models/   foundation (bytes, CRC, codecs,
                                           record-batch domain model)
    ops/ parallel/                         device data plane (XLA programs,
                                           mesh shardings)
    storage/                               segmented log + kvstore + snapshots
    rpc/ raft/                             internal RPC + consensus
    cluster/                               controller, topic table, allocator
    kafka/                                 wire protocol server + client
    coproc/                                inline transform engine (TPU-backed)
    security/ config/ admin/ proxy/        SASL/ACL, config store, admin API,
    archival/ cli/                         REST proxy + schema registry,
                                           tiered storage, operator CLI
"""

__version__ = "0.1.0"
