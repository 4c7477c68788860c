"""Config 4's document with a heavy-tailed size: the seeded stream of
``docs.py`` (compact ASCII JSON without escapes, ``{"level", "code", "msg",
"pad"}``, fields in that order: ``level`` about a third each of error /
info / warn, ``code`` the record's global sequence number ``partition *
records_per_partition + index``, ``msg`` 8-72 seeded bytes) whose ``pad``
fills each document to a length drawn from a capped power law,

    min(cap_bytes, floor(floor_bytes * U ** (-1 / tail_index))),  U uniform in (0, 1]

a body of sub-kilobyte log events and a tail of large ones (stack traces,
embedded documents). With the configuration's 384 B / 1.2 / 16,384 B the
median is 684 B and the mean about 1,400 B; 30.8% of the documents are over
1,024 B, 13.4% over 2,048, 5.8% over 4,096, 2.5% over 8,192 and 1.1% at the
cap, and the documents over 1,024 B hold about 71% of the bytes.
"""

from __future__ import annotations

import numpy as np

LEVELS = (b"error", b"info", b"warn")


def make_documents(
    seed: int, partitions: int, records_per_partition: int,
    only: range | None = None, *,
    floor_bytes: int = 384, tail_index: float = 1.2, cap_bytes: int = 16384,
) -> dict[int, list[bytes]]:
    """values[p][i] for the partitions in ``only`` (all by default). The
    stream of a partition does not depend on which others are asked for."""
    rng = np.random.default_rng(seed)
    shape = (partitions, records_per_partition)
    levels = rng.integers(0, 3, size=shape)
    msg_lens = rng.integers(8, 73, size=shape)
    u = 1.0 - rng.random(size=shape)
    lengths = np.minimum(
        np.floor(floor_bytes * u ** (-1.0 / tail_index)), cap_bytes
    ).astype(np.int64)
    letters = rng.integers(97, 123, size=shape + (72,), dtype=np.uint8)
    pad = b"x" * cap_bytes
    out = {}
    for p in only if only is not None else range(partitions):
        lv = levels[p].tolist()
        ml = msg_lens[p].tolist()
        ln = lengths[p].tolist()
        raw = letters[p].tobytes()
        base = p * records_per_partition
        docs = []
        for i in range(records_per_partition):
            head = b'{"level":"%s","code":%d,"msg":"%s","pad":"' % (
                LEVELS[lv[i]], base + i, raw[72 * i : 72 * i + ml[i]])
            docs.append(head + pad[: ln[i] - len(head) - 2] + b'"}')
        out[p] = docs
    return out
