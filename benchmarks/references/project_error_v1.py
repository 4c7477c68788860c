"""Plain reference of BASELINE config 4's script written in the v1 form,
``filter_contains('"level":"error"') | map_project(Int("code"), Str("msg", msg_width))``
on the payload lane: the packed output value (``<iH`` code and msg length,
then msg zero-padded to ``msg_width``), or None when the record is dropped.
Imports nothing of the program.

It follows the lane's *stated byte semantics* (``ops/transforms.py`` module
docs: canonical-form JSON, no whitespace around ``:``, substring scans), in
plain ``bytes`` operations over the raw value, not ``json.loads``:

- an empty value, and a value wider than ``row_stride`` (the lane's staging
  row), is dropped, never truncated;
- the filter keeps a value that holds ``"level":"error"`` as a substring,
  anywhere;
- ``code`` is read after the FIRST ``"code":``: an optional ``-``, then 1-9
  digits ended by a non-digit (or the value's end) inside a 12-byte window;
  anything else (no key, no digit, 10 or more digits) drops the record;
- ``msg`` is read after the FIRST ``"msg":"``: the bytes up to the next
  ``"``, which must come within ``msg_width`` bytes; a longer or
  unterminated string drops the record, never a truncated one.

Where that departs from JSON semantics (``references/project_error.py``,
the same script on the columnar lane), each pinned by a case of
``tests/test_payload_reference.py``:

- a decimal ``"code":3.5`` reads as 3 (the ``.`` ends the digits); JSON
  drops it as not an integer;
- no escapes: the ``"`` of an escaped ``\\"`` inside ``msg`` ends it, and
  the backslash before it is kept as a msg byte;
- the needle, ``"code":`` and ``"msg":"`` match inside another field's
  text or a nested object, and the first occurrence wins (JSON reads the
  top level's, the last of a repeated key);
- a value that is not valid JSON at all is projected if the three scans
  succeed;
- canonical form only: ``"code": 7`` (whitespace after the colon) is
  dropped here and kept by JSON; a ``msg`` that is not a string is dropped
  by both.

On ``docs.py``'s documents (compact ASCII, no escapes, one key each, ``code``
a sequence number) the two semantics agree on every value that fits the
staging row; the values over it (about one in seven) the columnar lane
keeps and this lane drops.
"""

import struct

_NEEDLE = b'"level":"error"'
_CODE_KEY = b'"code":'
_MSG_KEY = b'"msg":"'
_INT_WINDOW = 12
_DIGITS = b"0123456789"


def _code(value: bytes) -> int | None:
    at = value.find(_CODE_KEY)
    if at < 0:
        return None
    window = value[at + len(_CODE_KEY):][:_INT_WINDOW]
    negative = window[:1] == b"-"
    body = window[1:] if negative else window
    digits = len(body) - len(body.lstrip(_DIGITS))
    if not 1 <= digits <= 9:
        return None
    number = int(body[:digits])
    return -number if negative else number


def _msg(value: bytes, msg_width: int) -> bytes | None:
    at = value.find(_MSG_KEY)
    if at < 0:
        return None
    window = value[at + len(_MSG_KEY):][: msg_width + 1]
    end = window.find(b'"')
    return None if end < 0 else window[:end]


def reference(
    value: bytes | None, msg_width: int = 64, row_stride: int = 1024
) -> bytes | None:
    if not value or len(value) > row_stride or _NEEDLE not in value:
        return None
    code, msg = _code(value), _msg(value, msg_width)
    if code is None or msg is None:
        return None
    return struct.pack("<iH", code, len(msg)) + msg.ljust(msg_width, b"\x00")


def sequence(output: bytes) -> int:
    """The input's global sequence number carried by an output value."""
    return struct.unpack_from("<i", output)[0]
