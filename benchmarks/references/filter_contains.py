"""Plain reference of the north star's JSON filter,
``filter_contains(needle)`` on the payload lane: the value itself when it
is non-empty, at most ``row_stride`` bytes (the lane's staging row: a wider
value is dropped, never truncated) and holds ``needle`` as a substring;
else None. Imports nothing of the program."""

import re

_CODE = re.compile(rb'"code":(-?\d+)')


def reference(value: bytes | None, needle: str, row_stride: int = 1024) -> bytes | None:
    if not value or len(value) > row_stride:
        return None
    return value if needle.encode() in value else None


def sequence(output: bytes) -> int:
    """The input's global sequence number carried by an output value (the
    document's ``code``; the output is the input document itself)."""
    return int(_CODE.search(output).group(1))
