"""The seeded document stream (copied from
``redpanda_tpu/coproc/reference.py:make_documents`` so that a later PR
cannot change the inputs): ~1 KB of compact ASCII JSON without escapes,
``{"level", "code", "msg", "pad"}``. ``level`` is drawn from the seed
(about a third each of error / info / warn); ``code`` is the record's
global sequence number ``partition * records_per_partition + index``, so
a misplaced or repeated record cannot compare equal and a consumer can
tell which input an output came from; ``msg`` is 8-72 seeded bytes (about
one in eight longer than the 64 bytes ``Str("msg", 64)`` projects, which
drops the record); ``pad`` fills the document to 923-1,060 bytes, so about
one in seven is wider than the broker's 1,024-byte staging row.
"""

from __future__ import annotations

import numpy as np

LEVELS = (b"error", b"info", b"warn")


def make_documents(
    seed: int, partitions: int, records_per_partition: int,
    only: range | None = None,
) -> dict[int, list[bytes]]:
    """values[p][i] for the partitions in ``only`` (all by default). The
    stream of a partition does not depend on which others are asked for."""
    rng = np.random.default_rng(seed)
    shape = (partitions, records_per_partition)
    levels = rng.integers(0, 3, size=shape)
    msg_lens = rng.integers(8, 73, size=shape)
    pads = rng.integers(870, 941, size=shape)
    letters = rng.integers(97, 123, size=shape + (72,), dtype=np.uint8)
    pad = b"x" * 941
    out = {}
    for p in only if only is not None else range(partitions):
        lv = levels[p].tolist()
        ml = msg_lens[p].tolist()
        pd = pads[p].tolist()
        raw = letters[p].tobytes()
        base = p * records_per_partition
        out[p] = [
            b'{"level":"%s","code":%d,"msg":"%s","pad":"%s"}'
            % (LEVELS[lv[i]], base + i, raw[72 * i : 72 * i + ml[i]], pad[: pd[i]])
            for i in range(records_per_partition)
        ]
    return out
