"""A second seeded document stream, for configurations whose input is
compressed on the way in: ``docs.py``'s documents field for field and in
the same order, ``{"level", "code", "msg", "pad"}`` as compact ASCII JSON
with no ``"`` and no ``\\`` anywhere, but with a ``pad`` of seeded words in
place of ``"x" * n``.

Why it exists: under Zstd level 3 a 32-record batch of ``docs.py`` shrinks
about 20x (31,980 -> 1,612 B of records, seed 7), since ``pad`` is run-length
filler. The 1.04 GB backlog of ``traffic/catchup.json`` would be ~50 MB in
the log, under the broker's 64 MB batch cache, and a decompressor would be
timed on filler. This stream shrinks about 3x (31,980 -> 10,507 B on the
same seed; ``test_inputs.py`` pins the ratio inside 2.5-4.0x).

``level``, ``code``, ``msg`` and the length of ``pad`` are drawn exactly as
``docs.py`` draws them, from the same generator state, so for one seed the
two streams agree in every field but the content of ``pad``: a reference
keeps the same records of both, and documents are the same 923-1,060 B
(about one in seven over the 1,024-byte staging row). ``pad`` is words of
3-10 lowercase letters from a vocabulary of ``vocabulary`` words drawn from
the seed, each picked with probability ~ rank ** -``zipf_s`` (Zipf,
quantised to 2 ** -20), joined by single spaces, starting at a word and cut
to the pad's length.

``assumed`` (no public corpus can be fetched here): ``vocabulary`` 4,096 and
``zipf_s`` 1.1, chosen so that the Zstd level-3 ratio of a 32-record batch
lands near 3x, the low end of the 3-6x that public accounts of JSON logs
under Zstd-3 give (a log line shares its keys with its neighbours; these
documents share four short keys and nothing else).

Imports nothing of the program and nothing of ``docs.py``.
"""

from __future__ import annotations

import numpy as np

LEVELS = (b"error", b"info", b"warn")
WORD_MIN, WORD_MAX = 3, 10
PAD_MAX = 940
# a document's window of its partition's text: its pad starts at the first
# word at or after the window's start (at most WORD_MAX bytes in), so the
# windows of two documents never overlap
STRIDE = PAD_MAX + WORD_MAX + 1
_ZIPF_BITS = 20


class _Vocabulary:
    """The words of one seed: ``table[w]`` is word ``w``, its space, then
    zeros; ``step[w]`` its length with the space; ``pick`` maps a uniform
    ``_ZIPF_BITS``-bit number to a word by the Zipf distribution."""

    def __init__(self, seed: int, vocabulary: int, zipf_s: float):
        if not 2 <= vocabulary <= 65536:
            raise ValueError(f"vocabulary {vocabulary}: 2 to 65,536 words")
        rng = np.random.default_rng([seed, 0])
        lens = rng.integers(WORD_MIN, WORD_MAX + 1, size=vocabulary)
        self.table = rng.integers(97, 123, size=(vocabulary, WORD_MAX + 1), dtype=np.uint8)
        cols = np.arange(WORD_MAX + 1)
        self.table[cols == lens[:, None]] = 32
        self.table[cols > lens[:, None]] = 0
        self.step = (lens + 1).astype(np.int32)
        p = np.arange(1, vocabulary + 1, dtype=np.float64) ** -float(zipf_s)
        p /= p.sum()
        mids = (np.arange(2**_ZIPF_BITS) + 0.5) / 2**_ZIPF_BITS
        self.pick = np.minimum(np.searchsorted(np.cumsum(p), mids), vocabulary - 1).astype(np.uint16)
        self.mean_step = float((self.step * p).sum())


class _Text:
    """One partition's text at a time, into buffers kept between
    partitions (fresh memory is dear on the chip's host, PERF.md section 7)."""

    def __init__(self, voc: _Vocabulary, n_bytes: int):
        self.voc, self.n_bytes = voc, n_bytes
        self.n_words = int(n_bytes / voc.mean_step * 1.03) + 1024
        self._alloc()

    def _alloc(self) -> None:
        shape = (self.n_words, WORD_MAX + 1)
        self.words = np.empty(shape, dtype=np.uint8)
        self.used = np.empty(shape, dtype=bool)

    def draw(self, rng: np.random.Generator) -> tuple[bytes, np.ndarray]:
        """(text, the offset each of its words starts at); ``n_bytes`` or
        more, a function of ``rng``'s state alone."""
        voc = self.voc
        while True:
            idx = voc.pick[rng.integers(0, 2**_ZIPF_BITS, size=self.n_words, dtype=np.uint32)]
            step = voc.step[idx]
            ends = np.cumsum(step)
            if ends[-1] >= self.n_bytes:
                break
            self.n_words *= 2  # never seen: 3% over the mean is ~100 sigma
            self._alloc()
        np.take(voc.table, idx, axis=0, out=self.words, mode="clip")
        np.not_equal(self.words, 0, out=self.used)
        return self.words[self.used].tobytes(), ends - step


def make_documents(
    seed: int, partitions: int, records_per_partition: int,
    only: range | None = None, *, vocabulary: int = 4096, zipf_s: float = 1.1,
) -> dict[int, list[bytes]]:
    """values[p][i] for the partitions in ``only`` (all by default). The
    stream of a partition does not depend on which others are asked for."""
    rng = np.random.default_rng(seed)
    shape = (partitions, records_per_partition)
    levels = rng.integers(0, 3, size=shape)
    msg_lens = rng.integers(8, 73, size=shape)
    pads = rng.integers(870, PAD_MAX + 1, size=shape)
    letters = rng.integers(97, 123, size=shape + (72,), dtype=np.uint8)
    source = _Text(_Vocabulary(seed, vocabulary, zipf_s),
                   records_per_partition * STRIDE)
    windows = np.arange(records_per_partition) * STRIDE
    out = {}
    for p in only if only is not None else range(partitions):
        text, word_starts = source.draw(np.random.default_rng([seed, 1 + p]))
        at = word_starts[np.searchsorted(word_starts, windows)].tolist()
        lv = levels[p].tolist()
        ml = msg_lens[p].tolist()
        pd = pads[p].tolist()
        raw = letters[p].tobytes()
        base = p * records_per_partition
        out[p] = [
            b'{"level":"%s","code":%d,"msg":"%s","pad":"%s"}'
            % (LEVELS[lv[i]], base + i, raw[72 * i : 72 * i + ml[i]],
               text[at[i] : at[i] + pd[i]])
            for i in range(records_per_partition)
        ]
    return out
