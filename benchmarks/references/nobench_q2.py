"""Plain reference of NoBench Q2 (Chasseur, Li, Patel: "Enabling JSON
Document Stores in Relational Systems", WebDB 2013: ``SELECT nested_obj.str,
nested_obj.num FROM nobench_main``) as the script
``map_project_json(Str("nested_obj.str", str_width), Int("nested_obj.num"))``
on the payload lane: the packed 70 B output value ``<H64si`` (the string's
length, its bytes zero-padded to ``str_width``, the number), or None when
the record is dropped. ``json.loads`` reads the value; plain Python checks
the field limits; imports nothing of the program.

What is kept holds what a JSON parser reads at the two paths: the value is
one JSON object (RFC 8259 whitespace between any two tokens), the keys are
looked up by structure (``nested_obj`` among the top-level object's
members, ``str`` and ``num`` among its own: never a same-named key
elsewhere, never a key inside a string), and of a repeated key the last
wins, as ``json.loads`` has it.

What is dropped, every class (the lane drops what it cannot do faithfully,
never emits it approximate):

- an empty value, and a value wider than ``row_stride`` (the lane's staging
  row): never truncated;
- a value that is not JSON, or whose top level is not an object (an
  array, a string, a number);
- nesting deeper than 100 brackets;
- ``nested_obj`` absent or not an object; ``str`` or ``num`` absent from it;
- ``num`` not an integer of 1-9 digits (an optional ``-``): a fraction
  (``3.5``, ``3.0``), an exponent (``1e3``), ten digits or more, a string
  (``"7"``), ``true``, ``null``, an object or an array. Never ``3.5`` read
  as 3;
- ``str`` not a string, or longer than ``str_width`` bytes of UTF-8;
- a ``str`` whose JSON text holds a backslash (any escape: ``\\"``,
  ``\\n``, ``\\u00e9``): the lane does not unescape, and drops the row
  rather than emit the escape's text. NoBench's strings hold none;
- a key of the top-level object or of ``nested_obj`` written with an escape
  (``"n\\u0075m"``): the lane matches a key by its bytes and cannot say
  whether that one is the path's, so it does not resolve the row.

Where the program departs from this reference (each pinned by a case of
``tests/test_json_structural.py``): the program is no validator. It checks
that strings close, that brackets balance and that the top level is one
object; a value that passes those and is still not JSON (``{"a": tru,
...}``, a trailing comma, ``[`` closed by ``}``, a raw control character in
a string, bytes that are not UTF-8) is dropped here and projected there if
its paths resolve. Every value ``json.loads`` reads, both give the same
answer.

Where it departs from the source:

- NoBench is a table (``nobench_main``) loaded into a document store; here
  it is a stream: one topic of 64 partitions, one object a record, and Q2
  is a transform deployed over it;
- the output is the lane's packed little-endian record, not a row of two
  columns;
- ``nested_obj.num`` is a 32-bit ``Int`` (at most 9 digits), where a
  document store carries a JSON number; the generator's stay under 2**21;
- ``nested_obj.str`` is at most ``str_width`` (64) bytes; the generator's
  are 8-32;
- the generator (``docs_nobench.py``) gives object ``n`` the ``nested_obj``
  of object ``n XOR 1`` and sets that object's ``num`` to its number (NoBench
  draws the partner at random, and ``num`` too): ``sequence`` recovers the
  input's number as ``num XOR 1``.
"""

import json
import struct

_DECODER = json.JSONDecoder()
_WS = " \t\n\r"
_INT_DIGITS = 9
_MAX_DEPTH = 100


def _skip(text: str, at: int) -> int:
    while at < len(text) and text[at] in _WS:
        at += 1
    return at


def _members(text: str, at: int):
    """(key text, value, value text) of each member of the object whose
    ``{`` is ``text[at]``, in the document's order, by ``raw_decode``. The
    text was read whole by ``json.loads`` before, so nothing here fails."""
    at = _skip(text, at + 1)
    while text[at] != "}":
        _key, end = _DECODER.raw_decode(text, at)
        key_text = text[at:end]
        at = _skip(text, _skip(text, end) + 1)  # past the colon
        value, end = _DECODER.raw_decode(text, at)
        yield key_text, value, text[at:end]
        at = _skip(text, end)
        if text[at] == ",":
            at = _skip(text, at + 1)


def _member_text(text: str, name: str) -> str | None:
    """The text of the value of the object's member ``name``, the last of
    a repeated key; None when a key of the object is written with an
    escape."""
    found = None
    for key_text, _value, value_text in _members(text, _skip(text, 0)):
        if "\\" in key_text:
            return None
        if key_text == '"' + name + '"':
            found = value_text
    return found


def _no_escape_in_the_way(text: str) -> bool:
    """The two backslash rules, on the text of a value that has a backslash
    somewhere and that ``json.loads`` has read down to ``nested_obj.str``:
    no key of the top-level object or of ``nested_obj`` is written with an
    escape, and ``str``'s own text holds none."""
    nested = _member_text(text, "nested_obj")
    string = None if nested is None else _member_text(nested, "str")
    return string is not None and "\\" not in string


def _deepest(text: str) -> int:
    deepest = depth = 0
    in_string = escaped = False
    for c in text:
        if in_string:
            if escaped:
                escaped = False
            elif c == "\\":
                escaped = True
            elif c == '"':
                in_string = False
        elif c == '"':
            in_string = True
        elif c in "{[":
            depth += 1
            deepest = max(deepest, depth)
        elif c in "}]":
            depth -= 1
    return deepest


def reference(
    value: bytes | None, str_width: int = 64, row_stride: int = 1024
) -> bytes | None:
    if not value or len(value) > row_stride:
        return None
    try:
        text = value.decode("utf-8")
        doc = json.loads(text)
    except (ValueError, RecursionError):
        return None
    if not isinstance(doc, dict):
        return None
    if text.count("{") + text.count("[") > _MAX_DEPTH and _deepest(text) > _MAX_DEPTH:
        return None
    nested = doc.get("nested_obj")
    if not isinstance(nested, dict):
        return None
    string, number = nested.get("str"), nested.get("num")
    if not isinstance(string, str) or type(number) is not int:
        return None
    if "\\" in text and not _no_escape_in_the_way(text):
        return None
    encoded = string.encode("utf-8")
    if len(encoded) > str_width or abs(number) >= 10**_INT_DIGITS:
        return None
    return struct.pack(f"<H{str_width}si", len(encoded), encoded, number)


def sequence(output: bytes) -> int:
    """The input's number carried by an output value: object ``n`` holds
    the ``nested_obj`` of object ``n XOR 1``, whose ``num`` is its number."""
    return struct.unpack_from("<i", output, len(output) - 4)[0] ^ 1
