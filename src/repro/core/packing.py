"""Byte-packing utilities for packed string matching.

The paper packs alpha = w/log(sigma) characters into one machine word and
compares them in bulk.  On TPU the analogous trick is packing 4 consecutive
uint8 characters into one int32 *lane* so that a single 32-bit vector compare
tests a 4-gram at every position (the TPU-native analogue of SSE's
``_mm_mpsadbw_epu8`` 4-byte anchor used by EPSMb).
"""

from __future__ import annotations

import math

import numpy as np

import jax.numpy as jnp
from jax import lax

# Number of bytes packed into one 32-bit lane.  This mirrors the paper's
# 4-byte mpsadbw anchor (wsmatch matches the length-4 prefix of the pattern).
PACK = 4
# TPU vector lanes: hash_text_blocks contracts rows of _LANES blocks
_LANES = 128


def as_u8(x) -> jnp.ndarray:
    """Coerce bytes / str / ndarray to a uint8 jnp array."""
    if isinstance(x, str):
        x = x.encode("utf-8", errors="surrogateescape")
    if isinstance(x, (bytes, bytearray, memoryview)):
        x = np.frombuffer(bytes(x), dtype=np.uint8)
    arr = jnp.asarray(x)
    if arr.dtype != jnp.uint8:
        arr = arr.astype(jnp.uint8)
    return arr


def as_u8_np(x) -> np.ndarray:
    """Host-side sibling of :func:`as_u8`: coerce to a NUMPY uint8 array
    without ever touching a device.  Plan compilation is a host loop over
    up to ~10^5 patterns — one jnp round-trip per pattern is ~16s of pure
    device_put at dictionary scale, vs milliseconds staying on host."""
    if isinstance(x, str):
        x = x.encode("utf-8", errors="surrogateescape")
    if isinstance(x, (bytes, bytearray, memoryview)):
        return np.frombuffer(bytes(x), dtype=np.uint8)
    if isinstance(x, np.ndarray):
        return x if x.dtype == np.uint8 else x.astype(np.uint8)
    import jax

    arr = np.asarray(jax.device_get(x))
    return arr if arr.dtype == np.uint8 else arr.astype(np.uint8)


def shift_left(x: jnp.ndarray, j: int) -> jnp.ndarray:
    """Return y with y[i] = x[i + j] (zero padded at the tail).

    This is the vector analogue of the paper's ``s_j << j`` used by EPSMa to
    align per-character equality masks.  Implemented as a pad+slice so it
    lowers to a cheap static slice rather than a gather.
    """
    if j == 0:
        return x
    n = x.shape[-1]
    pad = [(0, 0)] * (x.ndim - 1) + [(0, j)]
    return jnp.pad(x, pad)[..., j : j + n]


def pack_u32(text_u8: jnp.ndarray) -> jnp.ndarray:
    """w[i] = t[i] | t[i+1]<<8 | t[i+2]<<16 | t[i+3]<<24  (little endian).

    One uint32 lane now holds the 4-gram starting at position i.  Tail lanes
    (i > n-4) contain zero-padded garbage; callers mask starts > n-m anyway.
    """
    t = text_u8.astype(jnp.uint32)
    w = t
    for j in range(1, PACK):
        w = w | (shift_left(t, j) << (8 * j))
    return w


def count_zero_bytes_u32(x: jnp.ndarray) -> jnp.ndarray:
    """Number of zero bytes (0..4) in each uint32 lane.

    This is the packed agreement counter of the k-mismatch path
    (repro.approx): XOR a packed text word against a packed pattern word and
    the agreeing byte lanes are exactly the zero bytes of the result — a
    vectorized popcount-style sum, four byte compares folded into one 32-bit
    lane op per position (cf. Giaquinta, Grabowski & Fredriksson,
    arXiv:1211.5433, where k-mismatch search in packed text reduces to
    per-position symbol-agreement counting over words).
    """
    x = x.astype(jnp.uint32)
    acc = jnp.zeros(x.shape, jnp.int32)
    for s in (0, 8, 16, 24):
        acc = acc + (((x >> jnp.uint32(s)) & jnp.uint32(0xFF)) == 0).astype(
            jnp.int32
        )
    return acc


def pack_word_u32(four_bytes: jnp.ndarray) -> jnp.ndarray:
    """Pack exactly 4 uint8 values into a scalar uint32 (little endian)."""
    b = four_bytes.astype(jnp.uint32)
    return b[0] | (b[1] << 8) | (b[2] << 16) | (b[3] << 24)


def valid_start_mask(n: int, m: int) -> jnp.ndarray:
    """Boolean mask of positions where a length-m occurrence can start."""
    return jnp.arange(n) <= (n - m)


FP_MULT = np.uint32(2654435761)  # Knuth's multiplicative-hash constant
# fixed odd salts mixing the packed words of one window into one fingerprint
WORD_SALTS = np.uint32(
    np.random.RandomState(0xE95).randint(1, 2**30, size=8) * 2 + 1
)


def fp_accum_word(v: jnp.ndarray, word: jnp.ndarray, salt_index: int) -> jnp.ndarray:
    """Add one salted packed-word term to a running window-fingerprint sum.

    The ONE definition of how a packed word enters the window fingerprint —
    shared by the engine's matchers, the FingerprintBank prefix accumulation
    (engine.py), and the Pallas multipattern kernel, so every consumer stays
    keyed to the same LUTs.  uint32 adds wrap mod 2^32, making the sum
    associative/commutative — the property the bank's prefix sharing needs."""
    return v + word * jnp.uint32(int(WORD_SALTS[salt_index]))


def fp_finalize(v: jnp.ndarray, kbits: int) -> jnp.ndarray:
    """Final multiplicative mix + top-bits truncation of a salted sum."""
    return ((v * jnp.uint32(int(FP_MULT))) >> jnp.uint32(32 - kbits)).astype(
        jnp.int32
    )


def fingerprint_weights(beta: int, seed: int = 12345) -> jnp.ndarray:
    """Fixed pseudo-random odd int32 weights for the multiplicative hash.

    The paper fingerprints 8-byte blocks with the crc32 instruction; TPU has
    no CRC unit, so we use h(block) = (block . r) mod 2^32 masked to k bits,
    with fixed odd weights r.  The dot product maps onto the MXU.
    """
    rng = np.random.RandomState(seed)
    w = rng.randint(1, 2**31 - 1, size=(beta,)).astype(np.int64) * 2 + 1
    return jnp.asarray(w & 0x7FFFFFFF, dtype=jnp.int32)


def hash_blocks(blocks_u8: jnp.ndarray, weights: jnp.ndarray, kbits: int) -> jnp.ndarray:
    """k-bit fingerprints of (..., beta) uint8 blocks via int32 dot.

    int32 overflow wraps (two's complement) under XLA, which is exactly the
    mod-2^32 arithmetic the multiplicative hash wants.
    """
    h = jnp.einsum(
        "...b,b->...",
        blocks_u8.astype(jnp.int32),
        weights.astype(jnp.int32),
        preferred_element_type=jnp.int32,
    )
    return (h & ((1 << kbits) - 1)).astype(jnp.int32)


def hash_text_blocks(
    text_u8: jnp.ndarray, weights: jnp.ndarray, kbits: int
) -> jnp.ndarray:
    """:func:`hash_blocks` of the aligned beta-byte blocks of (..., n) text,
    (..., n // beta) int32, as one MXU contraction over lane-dense rows.

    The text is viewed as rows of ROW = 128 * beta bytes (each text's tail
    zero-padded to whole rows), so a block never straddles a row, and each
    row is contracted with a block-diagonal (ROW, 4 * 128) matrix holding
    the four byte planes of the weights: column k * 128 + c sums block c's
    bytes times the weights' k-th bytes.  The planes recombine as
    sum_k p_k << 8k in uint32, which wraps mod 2^32 like the int32 dot of
    :func:`hash_blocks`, so the result equals it bit for bit.  The products
    are exact: a byte and a weight byte (0-255) are exact in bf16, and one
    output sums beta = 8 products, at most 520,200 < 2^24, exactly in f32.

    Neither obvious form fits the TPU.  A reshape to (..., n // beta, beta)
    pads its minor axis of 8 to a 128-lane tile, 16x the text (64 GiB for a
    1 GiB corpus); beta strided slices of the text compiled for v5e to 8
    gathers, 11.4 GB accessed per 10 MiB window.
    """
    beta = int(weights.shape[0])
    row = _LANES * beta
    lead, n = text_u8.shape[:-1], text_u8.shape[-1]
    b, nrows = math.prod(lead), -(-n // row)
    rows = jnp.pad(
        text_u8.reshape(b, n), ((0, 0), (0, nrows * row - n))
    ).reshape(b * nrows, row)
    # wmat[c * beta + j, k * 128 + c] = byte k of weights[j], 0 off the
    # diagonal blocks: built from iotas and the weights' scalars, so that it
    # fuses into the contraction and takes no device buffer of its own
    r = lax.broadcasted_iota(jnp.int32, (row, 4 * _LANES), 0)
    col = lax.broadcasted_iota(jnp.int32, (row, 4 * _LANES), 1)
    w = weights.astype(jnp.uint32)
    wj = jnp.zeros(r.shape, jnp.uint32)
    for j in range(beta):
        wj = jnp.where(r % beta == j, w[j], wj)
    byte = (wj >> (8 * (col // _LANES)).astype(jnp.uint32)) & 0xFF
    wmat = jnp.where(r // beta == col % _LANES, byte, 0).astype(jnp.bfloat16)
    p = jnp.dot(
        rows.astype(jnp.bfloat16), wmat, preferred_element_type=jnp.float32
    ).astype(jnp.int32).astype(jnp.uint32)
    h = p[:, :_LANES]
    for k in range(1, 4):
        h = h + (p[:, k * _LANES : (k + 1) * _LANES] << (8 * k))
    h = h.reshape(lead + (nrows * _LANES,))[..., : n // beta]
    return (h & jnp.uint32((1 << kbits) - 1)).astype(jnp.int32)
