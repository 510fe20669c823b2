"""Baseline exact string-matching algorithms the paper compares against.

The paper's experimental section (Tables 1-3) pits EPSM against the best
algorithms of the Faro-Lecroq survey.  We implement the representative set
that transfers to a JAX/TPU word-RAM model:

  * ``naive_np``      — scalar numpy oracle (tests only).
  * ``find_all``      — ``bytes.find`` loop: the host reference that the
                        service and stream checks compare against at
                        corpus sizes where ``naive_np`` is too slow.
  * ``packed_naive``  — vectorized shifted-AND over the full pattern (what
                        "naive" becomes once you have wide vector compares).
  * ``shift_or``      — SO [Baeza-Yates & Gonnet 1992]: bit-parallel NFA,
                        O(n ceil(m/w)); sequential scan ==> lax.scan.
  * ``kmp_dfa``       — KMP as a DFA table + lax.scan (the O(n) classic).
  * ``rabin_karp``    — rolling-hash filter + verification (the closest
                        classical relative of EPSMc).
  * ``hash3``         — Lecroq's HASHq (q=3) skip-loop [Lecroq 2007];
                        data-dependent skips ==> lax.while_loop (kept faithful:
                        this is *exactly* the control flow TPUs dislike, and
                        the benchmark quantifies that).
  * ``bndm``          — Backward Nondeterministic DAWG Matching [Navarro &
                        Raffinot 1998], bit-parallel suffix automaton with
                        skips; nested lax.while_loop.  m <= 31 (one word).

Skip-based algorithms (hash3, bndm) take concrete (host) patterns because
their tables are built with data-dependent python loops, mirroring real
implementations where preprocessing is scalar code.  Scan/vector algorithms
accept traced patterns.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.packing import as_u8, shift_left, valid_start_mask


def _concrete_u8(pattern) -> np.ndarray:
    """Host-side pattern bytes (table-building preprocessing is scalar code).

    Must run on a CONCRETE pattern even when the search is jit-traced — so
    convert via numpy BEFORE any jnp op (jnp constants become tracers
    inside a trace)."""
    if isinstance(pattern, str):
        pattern = pattern.encode()
    if isinstance(pattern, (bytes, bytearray, memoryview)):
        return np.frombuffer(bytes(pattern), np.uint8)
    if isinstance(pattern, np.ndarray):
        return pattern.astype(np.uint8)
    return np.asarray(jax.device_get(pattern)).astype(np.uint8)


# ---------------------------------------------------------------------------
# Scalar oracle (numpy; used by tests and to define ground truth)
# ---------------------------------------------------------------------------

def naive_np(text, pattern) -> np.ndarray:
    t = np.asarray(jax.device_get(as_u8(text)))
    p = np.asarray(jax.device_get(as_u8(pattern)))
    n, m = len(t), len(p)
    mask = np.zeros(n, dtype=bool)
    for i in range(n - m + 1):
        if np.array_equal(t[i : i + m], p):
            mask[i] = True
    return mask


def find_all(buf: bytes, pattern: bytes) -> np.ndarray:
    """int64 start offsets of every occurrence of ``pattern`` in ``buf``,
    overlapping ones included (``bytes.count`` skips those).  Plain CPython
    string search: it shares no code with the engine."""
    out = []
    i = buf.find(pattern)
    while i >= 0:
        out.append(i)
        i = buf.find(pattern, i + 1)
    return np.asarray(out, np.int64)


# ---------------------------------------------------------------------------
# Vectorized baselines
# ---------------------------------------------------------------------------

def packed_naive(text, pattern) -> jnp.ndarray:
    """Shifted-AND over all m characters (EPSMa generalized to any m)."""
    t, p = as_u8(text), as_u8(pattern)
    n, m = t.shape[0], p.shape[0]
    if n < m:
        return jnp.zeros((n,), dtype=jnp.bool_)
    acc = jnp.ones((n,), dtype=jnp.bool_)
    for j in range(m):
        acc = acc & (shift_left(t, j) == p[j])
    return acc & valid_start_mask(n, m)


def shift_or(text, pattern) -> jnp.ndarray:
    """SO: D' = (D << 1) | B[c]; match-end when bit m-1 of D is clear."""
    t, p = as_u8(text), as_u8(pattern)
    n, m = t.shape[0], p.shape[0]
    if m > 32:
        raise ValueError("shift_or supports m <= 32 (single 32-bit word)")
    if n < m:
        return jnp.zeros((n,), dtype=jnp.bool_)
    cs = jnp.arange(256, dtype=jnp.uint8)
    # B[c] bit j set <=> p[j] != c ; bits are distinct so sum == OR.
    bits = (p[None, :] != cs[:, None]).astype(jnp.uint32) << jnp.arange(m, dtype=jnp.uint32)[None, :]
    B = bits.sum(axis=1).astype(jnp.uint32)  # (256,)

    def step(D, c):
        D = (D << jnp.uint32(1)) | B[c]
        return D, (D >> jnp.uint32(m - 1)) & jnp.uint32(1)

    _, mism = lax.scan(step, jnp.uint32(0xFFFFFFFF), t)
    match_end = mism == 0  # (n,) True where an occurrence ENDS
    # start mask: start i <=> end i+m-1
    return shift_left(match_end, m - 1) & valid_start_mask(n, m)


def _kmp_table(p: np.ndarray) -> np.ndarray:
    m = len(p)
    dfa = np.zeros((m + 1, 256), dtype=np.int32)
    dfa[0, p[0]] = 1
    x = 0
    for j in range(1, m):
        dfa[j, :] = dfa[x, :]
        dfa[j, p[j]] = j + 1
        x = dfa[x, p[j]]
    # after a full match continue from the border state
    dfa[m, :] = dfa[x, :]
    dfa[m, p[x] if x < m else 0] = dfa[x, p[x]] if x < m else dfa[m, 0]
    return dfa


def kmp_dfa(text, pattern) -> jnp.ndarray:
    """KMP compiled to a (m+1) x 256 DFA, searched with one lax.scan."""
    t = as_u8(text)
    p = _concrete_u8(pattern)
    n, m = t.shape[0], len(p)
    if n < m:
        return jnp.zeros((n,), dtype=jnp.bool_)
    dfa = jnp.asarray(_kmp_table(p))

    def step(s, c):
        s = dfa[s, c]
        return s, s == m

    _, match_end = lax.scan(step, jnp.int32(0), t)
    return shift_left(match_end, m - 1) & valid_start_mask(n, m)


def rabin_karp(text, pattern, *, base: int = 1000003) -> jnp.ndarray:
    """Karp-Rabin mod-2^32 rolling hash filter + exact verification."""
    t, p = as_u8(text), as_u8(pattern)
    n, m = t.shape[0], p.shape[0]
    if n < m:
        return jnp.zeros((n,), dtype=jnp.bool_)
    w = jnp.power(jnp.uint32(base), jnp.arange(m - 1, -1, -1, dtype=jnp.uint32))
    h = jnp.zeros((n,), dtype=jnp.uint32)
    for j in range(m):
        h = h + shift_left(t, j).astype(jnp.uint32) * w[j]
    hp = (p.astype(jnp.uint32) * w).sum(dtype=jnp.uint32)
    cand = (h == hp) & valid_start_mask(n, m)
    # exact verification of candidates (dense masked)
    ok = cand
    for j in range(m):
        ok = ok & (shift_left(t, j) == p[j])
    return ok


# ---------------------------------------------------------------------------
# Skip-loop baselines (sequential; lax.while_loop)
# ---------------------------------------------------------------------------

def _hash3_tables(p: np.ndarray, hs: int = 4096):
    m = len(p)
    q = 3

    def h(c0, c1, c2):
        return (int(c0) + (int(c1) << 3) + (int(c2) << 6)) & (hs - 1)

    shift = np.full(hs, m - q + 1, dtype=np.int32)
    # q-gram ending at pattern position j+q-1 allows shift m-1-(j+q-1)
    for j in range(m - q + 1):
        v = h(p[j], p[j + 1], p[j + 2])
        shift[v] = min(shift[v], m - 1 - (j + q - 1))
    return shift


def hash3(text, pattern) -> jnp.ndarray:
    """Lecroq HASHq (q=3): Wu-Manber style q-gram shift table + skip loop."""
    t = as_u8(text)
    p_np = _concrete_u8(pattern)
    n, m = t.shape[0], len(p_np)
    if m < 3:
        return packed_naive(t, p_np)
    if n < m:
        return jnp.zeros((n,), dtype=jnp.bool_)
    shift = jnp.asarray(_hash3_tables(p_np))
    p = jnp.asarray(p_np)

    def hv(i):  # hash of q-gram ending at i
        c0 = t[i - 2].astype(jnp.int32)
        c1 = t[i - 1].astype(jnp.int32)
        c2 = t[i].astype(jnp.int32)
        return (c0 + (c1 << 3) + (c2 << 6)) & (4096 - 1)

    def cond(state):
        i, _ = state
        return i < n

    def body(state):
        i, mask = state
        s = shift[hv(i)]
        at_cand = s == 0
        start = i - m + 1
        window = lax.dynamic_slice(t, (jnp.maximum(start, 0),), (m,))
        hit = at_cand & (start >= 0) & jnp.all(window == p)
        mask = mask.at[jnp.where(hit, start, n)].set(True, mode="drop")
        i = i + jnp.where(at_cand, 1, s)
        return i, mask

    i0 = jnp.int32(m - 1)
    mask0 = jnp.zeros((n,), dtype=jnp.bool_)
    _, mask = lax.while_loop(cond, body, (i0, mask0))
    return mask


def bndm(text, pattern) -> jnp.ndarray:
    """BNDM: bit-parallel suffix automaton with window skips (m <= 31)."""
    t = as_u8(text)
    p_np = _concrete_u8(pattern)
    n, m = t.shape[0], len(p_np)
    if m > 31:
        raise ValueError("bndm supports m <= 31 (single 32-bit word)")
    if n < m:
        return jnp.zeros((n,), dtype=jnp.bool_)
    B_np = np.zeros(256, dtype=np.uint32)
    for j in range(m):
        B_np[p_np[j]] |= np.uint32(1) << np.uint32(m - 1 - j)
    B = jnp.asarray(B_np)
    top = jnp.uint32(1) << jnp.uint32(m - 1)

    def outer_cond(state):
        pos, _ = state
        return pos <= n - m

    def outer_body(state):
        pos, mask = state

        def inner_cond(s):
            _, D, _, _ = s
            return D != 0

        def inner_body(s):
            j, D, last, mask = s
            D = D & B[t[pos + j - 1]]
            j = j - 1
            hit = (D & top) != 0
            is_match = hit & (j == 0)
            mask = mask.at[jnp.where(is_match, pos, n)].set(True, mode="drop")
            last = jnp.where(hit & (j > 0), j, last)
            D = jnp.where(j > 0, D << jnp.uint32(1), jnp.uint32(0))
            return j, D, last, mask

        j0 = jnp.int32(m)
        D0 = jnp.uint32(0xFFFFFFFF) >> jnp.uint32(32 - m)
        _, _, last, mask = lax.while_loop(
            inner_cond, inner_body, (j0, D0, jnp.int32(m), mask)
        )
        return pos + last, mask

    mask0 = jnp.zeros((n,), dtype=jnp.bool_)
    _, mask = lax.while_loop(outer_cond, outer_body, (jnp.int32(0), mask0))
    return mask


BASELINES = {
    "packed_naive": packed_naive,
    "shift_or": shift_or,
    "kmp_dfa": kmp_dfa,
    "rabin_karp": rabin_karp,
    "hash3": hash3,
    "bndm": bndm,
}
