"""Plain per-record reference for the transforms the benchmark and
chip_smoke.py hold the engine to — ``json.loads``, predicate, pack — and
the seeded document stream both run it on.

Independent of the code under test (no column plan, no native walker, no
device program): the same records through the engine and through these
functions must give the same output values. The work profile is what the
reference system's Node.js supervisor does per record
(src/js/modules/rpc/server.ts:244-266).
"""

from __future__ import annotations

import json
import struct

import numpy as np

_LEVELS = (b"error", b"info", b"warn")
_INT9 = 999_999_999  # Int projections carry at most 9 digits


def make_documents(
    seed: int, partitions: int, records_per_partition: int
) -> list[list[bytes]]:
    """values[p][i]: ~1 KB of compact ASCII JSON without escapes,
    ``{"level", "code", "msg", "pad"}``. ``level`` is drawn from ``seed``
    (about a third each of error / info / warn); ``code`` is the record's
    global sequence number, so a misplaced or repeated record cannot
    compare equal; ``msg`` is 8-72 seeded bytes — about one in eight
    longer than the 64 bytes ``Str("msg", 64)`` projects, which drops the
    record; ``pad`` fills the document to 923-1,060 bytes, so about one
    in seven is wider than a 1,024-byte staging row and none is wider
    than the bench's 1,152."""
    rng = np.random.default_rng(seed)
    shape = (partitions, records_per_partition)
    levels = rng.integers(0, 3, size=shape)
    msg_lens = rng.integers(8, 73, size=shape)
    pads = rng.integers(870, 941, size=shape)
    letters = rng.integers(97, 123, size=shape + (72,), dtype=np.uint8)
    return [
        [
            b'{"level":"%s","code":%d,"msg":"%s","pad":"%s"}'
            % (
                _LEVELS[levels[p, i]],
                p * records_per_partition + i,
                letters[p, i, : msg_lens[p, i]].tobytes(),
                b"x" * int(pads[p, i]),
            )
            for i in range(records_per_partition)
        ]
        for p in range(partitions)
    ]


def project_error(value: bytes | None, msg_width: int = 64) -> bytes | None:
    """BASELINE config 4's script,
    ``where(field("level") == "error") | map_project(Int("code"), Str("msg", msg_width))``:
    the packed output value, or None when the record is dropped — by the
    predicate, or by a projection that cannot be made faithfully (``code``
    not an integer of at most 9 digits, ``msg`` not a string or longer than
    ``msg_width`` bytes)."""
    try:
        doc = json.loads(value)
    except (TypeError, ValueError):
        return None
    if not isinstance(doc, dict) or doc.get("level") != "error":
        return None
    code, msg = doc.get("code"), doc.get("msg")
    if type(code) is not int or abs(code) > _INT9 or not isinstance(msg, str):
        return None
    raw = msg.encode()
    if len(raw) > msg_width:
        return None
    return struct.pack("<iH", code, len(raw)) + raw.ljust(msg_width, b"\x00")


def filter_contains(
    value: bytes | None, needle: bytes, row_stride: int
) -> bytes | None:
    """The raw-byte ``filter_contains(needle)`` script (payload lane): the
    value itself when it holds ``needle``. The payload lane stages whole
    records in rows of ``row_stride`` bytes and drops what it cannot stage
    faithfully — empty values and values wider than the row."""
    if not value or len(value) > row_stride or needle not in value:
        return None
    return value
