"""Plain reference of BASELINE config 4's script,
``where(field("level") == "error") | map_project(Int("code"), Str("msg", msg_width))``
(copied from ``redpanda_tpu/coproc/reference.py:project_error``): the packed
output value, or None when the record is dropped by the predicate or by a
projection that cannot be made faithfully (``code`` not an integer of at
most 9 digits, ``msg`` not a string or longer than ``msg_width`` bytes).
Imports nothing of the program."""

import json
import struct

_INT9 = 999_999_999


def reference(value: bytes | None, msg_width: int = 64) -> bytes | None:
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


def sequence(output: bytes) -> int:
    """The input's global sequence number carried by an output value."""
    return struct.unpack_from("<i", output)[0]
