"""The plain reference the scans are checked against.

``find_all(buf, patterns)`` gives, for each literal, the sorted start of
every occurrence in ``buf``, overlapping ones included; a count is the
number of them.  Exact byte equality and nothing else (the guarantees of
the configuration file).  It shares no code with the engine and uses
none of its tables.

How: a hash join over the text's 8-byte words.  ``W(p)`` is bytes p..p+7
read as a little-endian integer.  A literal of length ``m >= 8`` is fixed by
its words at offsets 0, 8, 16, ... and ``m - 8``; a shorter one by the low
``m`` bytes of ``W(0)``.  For each length, every text position gets a key
mixed from its words at those offsets; positions whose key is in a table
of the literals' keys are candidates, and a candidate counts only if each
of its words equals the literal's.  The words of positions ``8k + r`` are a
plain numpy view of the text at offset ``r``, so the keys are a few vector
passes with no gather; the text is cut into blocks that several threads
scan at once.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import List, Sequence

import numpy as np

_MULT = np.uint64(0x9E3779B97F4A7C15)
BLOCK_WORDS = 1 << 21  # positions of one residue per task
THREADS = 8


def _offsets(m: int) -> tuple:
    if m <= 8:
        return (0,)
    return tuple(range(0, m - 7, 8)) + ((m - 8,) if m % 8 else ())


def _mix(words) -> np.ndarray:
    key = words[0] * _MULT
    for w in words[1:]:
        key = (key ^ w) * _MULT
    return key


class _Length:
    """The literals of one length: their words, keys and lookup table."""

    def __init__(self, m: int, pats: Sequence[bytes]):
        self.m = m
        self.offs = _offsets(m)
        self.mask = np.uint64((1 << (8 * min(m, 8))) - 1)
        words = np.asarray(
            [[int.from_bytes(p[o : o + 8], "little") for o in self.offs]
             for p in pats], np.uint64,
        ) & self.mask
        keys = _mix(list(words.T))
        order = np.argsort(keys, kind="stable")
        self.keys, self.words = keys[order], words[order]
        self.ids = order  # index into ``pats`` of each sorted key
        if np.unique(self.keys).size != len(pats):
            raise ValueError(f"two literals of length {m} share a key")
        # about 1 false candidate per 256 positions, in a table that
        # stays in cache
        self.bits = max(10, int(np.ceil(np.log2(len(pats)))) + 8)
        self.table = np.zeros(1 << self.bits, bool)
        self.table[self._slot(self.keys)] = True

    def _slot(self, keys: np.ndarray) -> np.ndarray:
        return keys >> np.uint64(64 - self.bits)

    def scan(self, views, n: int, r: int, k0: int, k1: int):
        """(positions, literal ids) of the occurrences that start at
        8k + r for k in [k0, k1)."""
        ws = []
        for o in self.offs:
            v, s = views[(r + o) % 8], (r + o) // 8
            ws.append(v[k0 + s : k1 + s])
        ws[0] = ws[0] & self.mask
        key = _mix(ws)
        cand = np.flatnonzero(self.table[self._slot(key)])
        kc = key[cand]
        at = np.minimum(np.searchsorted(self.keys, kc), len(self.keys) - 1)
        ok = self.keys[at] == kc
        for j, w in enumerate(ws):  # exact: every word equal
            ok &= w[cand] == self.words[at, j]
        pos = (cand[ok] + k0) * 8 + r
        lit = at[ok]
        inside = pos <= n - self.m
        return pos[inside], self.ids[lit[inside]]


def _scan(buf, patterns: Sequence[bytes], threads: int):
    text = np.frombuffer(buf, np.uint8) if isinstance(buf, bytes) else buf
    n = int(text.size)
    pats = list(dict.fromkeys(bytes(p) for p in patterns))
    if any(len(p) == 0 for p in pats):
        raise ValueError("empty literal")
    max_m = max(len(p) for p in pats)
    padded = np.zeros(n + 8 * (max_m // 8 + 3), np.uint8)
    padded[:n] = text
    views = [
        np.frombuffer(padded, "<u8", count=(padded.size - q) // 8, offset=q)
        for q in range(8)
    ]
    by_len: dict = {}
    for i, p in enumerate(pats):
        by_len.setdefault(len(p), []).append(i)
    tasks = []
    n_words = (n + 7) // 8
    for m, ids in by_len.items():
        group = _Length(m, [pats[i] for i in ids])
        ids = np.asarray(ids)
        for r in range(8):
            for k0 in range(0, n_words, BLOCK_WORDS):
                tasks.append((group, ids, r, k0, min(k0 + BLOCK_WORDS, n_words)))

    def run(task):
        group, ids, r, k0, k1 = task
        pos, lit = group.scan(views, n, r, k0, k1)
        return pos, ids[lit]

    with ThreadPoolExecutor(max_workers=max(1, threads)) as ex:
        parts = list(ex.map(run, tasks))
    return pats, parts


def find_all(buf, patterns: Sequence[bytes], *, threads: int = THREADS
             ) -> List[np.ndarray]:
    """Sorted int64 start positions of every occurrence of each literal in
    ``buf`` (bytes or a uint8 array), overlapping ones included."""
    pats, parts = _scan(buf, patterns, threads)
    pos = np.concatenate([p for p, _ in parts]).astype(np.int64)
    lit = np.concatenate([i for _, i in parts])
    order = np.lexsort((pos, lit))
    pos, lit = pos[order], lit[order]
    bounds = np.searchsorted(lit, np.arange(len(pats) + 1))
    found = {p: pos[bounds[i] : bounds[i + 1]] for i, p in enumerate(pats)}
    return [found[bytes(p)] for p in patterns]

