"""Plain reference of NEXmark Q1 ("currency conversion") as the v1 script
``filter_field_eq("event_type", 2) | map_project(Int("auction"),
Int("bidder"), Scaled("price", num, den), Long("dateTime"))`` on the
payload lane: the packed 24 B output value ``<iiqq`` (auction, bidder,
``floor(price * num / den)``, dateTime), or None when the record is dropped.
Plain ``bytes`` operations and Python integers; imports nothing of the
program.

It follows the lane's *stated byte semantics* (``ops/transforms.py`` module
docs: canonical-form JSON, no whitespace around ``:``, substring scans):

- an empty value, and a value wider than ``row_stride`` (the lane's staging
  row), is dropped, never truncated;
- the filter keeps a value that holds ``"event_type":2`` anywhere, followed
  by a byte that cannot continue a number (not a digit, ``.``, ``e``, ``E``,
  ``+``, ``-``) or by the value's end; any occurrence will do;
- ``auction``, ``bidder`` and ``price`` are read after the FIRST ``"<key>":``
  as a v1 ``Int``: an optional ``-``, then 1-9 digits ended by a non-digit
  (or the value's end) inside a 12-byte window; anything else (no key, no
  digit, 10 or more digits) drops the record;
- ``dateTime`` is read after the FIRST ``"dateTime":`` as a ``Long``: an
  optional ``-``, then 1-18 digits ended by a non-digit (or the value's
  end) inside a 20-byte window; 19 digits or more drops the record, never a
  truncated number;
- the price is the exact ``floor(price * num / den)`` of integers, rounded
  toward minus infinity; price and dateTime are never a float's rounding.

Where that departs from JSON semantics, each pinned by a case of
``tests/test_nexmark_q1.py``:

- a decimal ``"price":12.5`` reads as 12 (the ``.`` ends the digits); JSON
  would read 12.5;
- canonical form only: ``"dateTime": 5`` (whitespace after the colon) is
  dropped here and kept by JSON;
- the keys match inside another field's text or a nested object, and the
  first occurrence wins (JSON reads the top level's, the last of a repeated
  key);
- no escapes, and a value that is not valid JSON at all is projected if the
  five scans succeed.

Where it departs from the source (the NEXmark paper's ``SELECT auction,
DOLTOEUR(price), bidder, dateTime FROM bid``; the Flink suite's ``q1.sql``,
``0.908 * price``; Beam's ``Query1``, ``price * 908 / 1000`` on longs):

- the stream is one topic of flat JSON events of three types, and the
  query's ``FROM bid`` is the filter ``event_type == 2`` (Beam and Flink
  read a typed stream);
- the price is integer cents and the conversion floors, as Beam's integer
  division does for the non-negative prices the generator makes; Flink's
  ``0.908 * price`` is a decimal;
- the output is the lane's packed little-endian record in the Beam
  model's field order (auction, bidder, price, dateTime), not a row, and
  Flink's trailing ``extra`` column is not carried;
- auction and bidder are 32-bit (the lane's ``Int``: at most 9 digits),
  where the suites carry longs; the generator's ids stay under 10 ** 6 at
  this scale.

``sequence`` recovers the event number from ``dateTime``, which
``docs_nexmark.py`` sets to ``base_ms + n``.
"""

import struct

BASE_MS = 1_700_000_000_000  # docs_nexmark.make_events' default, the configuration's
_NEEDLE = b'"event_type":2'
_NUMBER_BYTES = b"0123456789.eE+-"
_DIGITS = b"0123456789"
_INT_WINDOW, _INT_DIGITS = 12, 9
_LONG_WINDOW, _LONG_DIGITS = 20, 18


def _is_bid(value: bytes) -> bool:
    at = value.find(_NEEDLE)
    while at >= 0:
        after = value[at + len(_NEEDLE): at + len(_NEEDLE) + 1]
        if not after or after not in _NUMBER_BYTES:
            return True
        at = value.find(_NEEDLE, at + 1)
    return False


def _integer(value: bytes, key: bytes, window: int, most_digits: int) -> int | None:
    at = value.find(b'"' + key + b'":')
    if at < 0:
        return None
    text = value[at + len(key) + 3:][:window]
    negative = text[:1] == b"-"
    body = text[1:] if negative else text
    digits = len(body) - len(body.lstrip(_DIGITS))
    if not 1 <= digits <= most_digits:
        return None
    number = int(body[:digits])
    return -number if negative else number


def reference(
    value: bytes | None, num: int = 908, den: int = 1000, row_stride: int = 1024
) -> bytes | None:
    if not value or len(value) > row_stride or not _is_bid(value):
        return None
    auction = _integer(value, b"auction", _INT_WINDOW, _INT_DIGITS)
    bidder = _integer(value, b"bidder", _INT_WINDOW, _INT_DIGITS)
    price = _integer(value, b"price", _INT_WINDOW, _INT_DIGITS)
    stamp = _integer(value, b"dateTime", _LONG_WINDOW, _LONG_DIGITS)
    if auction is None or bidder is None or price is None or stamp is None:
        return None
    return struct.pack("<iiqq", auction, bidder, price * num // den, stamp)


def sequence(output: bytes) -> int:
    """The input's event number carried by an output value."""
    return struct.unpack_from("<q", output, 16)[0] - BASE_MS
