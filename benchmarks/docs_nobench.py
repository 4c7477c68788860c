"""NoBench's objects (Chasseur, Li, Patel: "Enabling JSON Document Stores in
Relational Systems", WebDB 2013, the Argo paper's benchmark), one a record:

    {"str1": s, "str2": s, "num": n, "bool": b, "dyn1": int | s,
     "dyn2": int | s | bool, "nested_arr": [0-7 words],
     "nested_obj": {"str": s, "num": n}, "sparse_XX0": s, ... "sparse_XX9": s,
     "thousandth": n mod 1000}

Object number ``n = p * records_per_partition + i`` is its ``num``. Its ten
sparse attributes are one cluster of the thousand ``sparse_000`` ..
``sparse_999``: ``sparse_(c*10)`` .. ``sparse_(c*10+9)`` with ``c = n mod
100``, so any one attribute is in 1% of the objects. ``nested_obj`` holds
another object's ``str1`` and ``num``; ``nested_arr`` holds words drawn
with repeats from one list; ``dyn1`` and ``dyn2`` change type from object
to object. A value is written as Python's ``json.dumps`` writes it by
default (``", "`` and ``": "`` separators, ASCII, keys in the order above),
so a top-level ``"num": `` precedes the one inside ``nested_obj``, with a
space after every colon.

``assumed`` (nothing can be fetched here, so what is not recalled exactly is
set here and listed in the configuration's ``assumed``): the strings are
8, 16, 24 or 32 characters of the base32 alphabet (the paper's look like
base32 text), cut from one seeded pool a partition, the sparse values
among them; ``dyn1`` is an integer in 95% of the objects and a string in
the rest; ``dyn2`` an integer, a string or a boolean, a third each;
``nested_arr``'s length is uniform over 0-7 and its words uniform over
``WORDS``; the partner: NoBench draws the object whose ``str1`` and ``num``
``nested_obj`` holds at random, here object ``n`` holds those of object ``n
XOR 1`` (its neighbour in the same partition), so an output names its
input and none compares equal out of place; one generator state a
partition, ``[seed, p]``. Nothing is padded to a size.

Imports nothing of the program and nothing of the other generators.
"""

from __future__ import annotations

import numpy as np

ALPHABET = b"ABCDEFGHIJKLMNOPQRSTUVWXYZ234567"
STRING_LENGTHS = (8, 16, 24, 32)
SPARSE, CLUSTER = 1000, 10
DYN1_INTEGER_SHARE = 0.95
MOST_WORDS = 7
WORDS = (b"the", b"of", b"and", b"to", b"in", b"is", b"that", b"for", b"it", b"as",
         b"was", b"with", b"be", b"by", b"on", b"not", b"he", b"this", b"are", b"or",
         b"his", b"from", b"at", b"which", b"but", b"have", b"an", b"had", b"they", b"you",
         b"were", b"their", b"one", b"all", b"we", b"can", b"her", b"has", b"there", b"been",
         b"if", b"more", b"when", b"will", b"would", b"who", b"so", b"no", b"out", b"up")
_POOL = 1 << 16  # the characters of one partition
_STARTS = _POOL - max(STRING_LENGTHS)

_HEAD = (b'{"str1": "%s", "str2": "%s", "num": %d, "bool": %s, "dyn1": %s, "dyn2": %s, '
         b'"nested_arr": [%s], "nested_obj": {"str": "%s", "num": %d}, ')
_SPARSE = b", ".join(b'"sparse_%03d": "%%s"' for _ in range(CLUSTER))  # keys, then values
_TAIL = b', "thousandth": %d}'
_BOOL = (b"false", b"true")


def make_objects(
    seed: int, partitions: int, records_per_partition: int,
    only: range | None = None,
) -> dict[int, list[bytes]]:
    """values[p][i] for the partitions in ``only`` (all by default). The
    stream of a partition does not depend on which others are asked for;
    ``records_per_partition`` is even (a partner is a neighbour)."""
    rpp = records_per_partition
    if rpp % 2:
        raise ValueError("records_per_partition is even: object n pairs with n XOR 1")
    out = {}
    for p in only if only is not None else range(partitions):
        rng = np.random.default_rng([seed, p])
        pool = bytes(np.frombuffer(ALPHABET, np.uint8)[rng.integers(0, 32, size=_POOL)])
        # per object: where each of its 14 strings starts and how long it is
        # (str1, str2, dyn1, dyn2, the ten sparse values), the dynamic
        # types, the array's length and words
        at = rng.integers(0, _STARTS, size=(rpp, 14)).tolist()
        size = rng.integers(0, len(STRING_LENGTHS), size=(rpp, 14)).tolist()
        dyn1_int = (rng.random(rpp) < DYN1_INTEGER_SHARE).tolist()
        dyn2_kind = rng.integers(0, 3, size=rpp).tolist()
        numbers = rng.integers(0, 1 << 31, size=(rpp, 3)).tolist()
        words = rng.integers(0, len(WORDS), size=(rpp, MOST_WORDS)).tolist()
        n_words = rng.integers(0, MOST_WORDS + 1, size=rpp).tolist()

        def text(i: int, k: int) -> bytes:
            return pool[at[i][k]: at[i][k] + STRING_LENGTHS[size[i][k]]]

        str1 = [text(i, 0) for i in range(rpp)]
        values = []
        for i in range(rpp):
            n = p * rpp + i
            x = numbers[i]
            dyn1 = b"%d" % x[0] if dyn1_int[i] else b'"%s"' % text(i, 2)
            kind = dyn2_kind[i]
            dyn2 = (b"%d" % x[1] if kind == 0 else b'"%s"' % text(i, 3) if kind == 1
                    else _BOOL[x[1] & 1])
            arr = b", ".join(b'"%s"' % WORDS[w] for w in words[i][: n_words[i]])
            first = n % (SPARSE // CLUSTER) * CLUSTER
            sparse = _SPARSE % tuple(first + k for k in range(CLUSTER))
            values.append(
                _HEAD % (str1[i], text(i, 1), n, _BOOL[x[2] & 1], dyn1, dyn2, arr,
                         str1[i ^ 1], n ^ 1)
                + sparse % tuple(text(i, 4 + k) for k in range(CLUSTER))
                + _TAIL % (n % 1000)
            )
        out[p] = values
    return out
