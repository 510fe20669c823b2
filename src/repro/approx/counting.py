"""Packed k-mismatch counting filter + the engine's approximate matchers.

Per-position mismatch counting reuses the exact path's packed substrate
(DESIGN.md §8): the TextIndex's u32 4-gram view XOR'd against a pattern's
packed anchor words yields agreeing bytes as zero bytes of the result, so
one 32-bit lane op counts 4 byte agreements (count_zero_bytes_u32 — the
vectorized popcount-style sum of Giaquinta/Grabowski/Fredriksson's
symbol-agreement reduction, arXiv:1211.5433).  Only the strided words are
used (the overlapping final anchor word would double-count its bytes); the
m % 4 tail is counted byte-wise.

Two count paths, mirroring the exact engine:

  * dense — (B, P, n) mismatch accumulation, always exact for any k; the
    fallback and the small-input / saturated-gate path;
  * sparse — the relaxed fingerprint LUT (repro.approx.relaxed) gates
    candidate blocks before verification, exactly the exact engine's
    compact-then-verify shape but at APPROX_CAND_BLOCK granularity: the
    relaxed LUT is ~2 orders of magnitude denser than the exact union LUT,
    so the exact path's 32-wide blocks would light up ~40% of the text
    while 8-wide blocks stay ~12% at k=1 density.

Soundness of the gate never depends on the density heuristics: a true
<= k-mismatch occurrence's window fingerprint is in the relaxed set by
construction, and candidate overflow falls back to the dense branch via
lax.cond, exactly like the exact engine.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from typing import Optional

from repro.core.engine import (
    FingerprintBank,
    PatternPlan,
    TextIndex,
    _gather_candidate_rows,
    _valid_starts,
)
from repro.core.packing import PACK, count_zero_bytes_u32, shift_left

# Candidate-block granularity of the sparse k-mismatch path (see module
# docstring for why it is narrower than the exact engine's CAND_BLOCK).
APPROX_CAND_BLOCK = 8
# Sparse path only when the expected candidate-block fraction stays below
# this; above it the gather + fixed-budget nonzero can't beat dense.
BLOCK_FRAC_MAX = 0.25


def _n_strided(m: int) -> int:
    """Packed words usable for counting: full non-overlapping 4-grams."""
    return m // PACK


def mismatch_counts(index: TextIndex, plan: PatternPlan) -> jnp.ndarray:
    """int32 (B, P, n) — Hamming distance between the m-byte window at every
    text position and every pattern (garbage in the <m tail; callers mask
    with _valid_starts).  Packed: m // 4 lane ops + m % 4 byte ops."""
    t, w = index.text, index.packed
    P, m = plan.patterns.shape
    B, n = t.shape
    mm = jnp.zeros((B, P, n), jnp.int32)
    nw = _n_strided(m)
    for i in range(nw):
        x = shift_left(w, PACK * i)[:, None, :] ^ plan.anchors[None, :, i, None]
        mm = mm + (PACK - count_zero_bytes_u32(x))
    for j in range(nw * PACK, m):
        mm = mm + (
            shift_left(t, j)[:, None, :] != plan.patterns[None, :, j, None]
        ).astype(jnp.int32)
    return mm


def match_group_approx(
    index: TextIndex, plan: PatternPlan, k: int, end_min=None
) -> jnp.ndarray:
    """bool (B, P, n) k-mismatch match-start mask.  Dense by design: for full
    masks the output write dominates (same argument as the exact engine's
    _match_group_b), so the counting filter runs at every position.
    ``end_min`` is the streaming seam gate (engine.match_many)."""
    ok = mismatch_counts(index, plan) <= k
    return ok & _valid_starts(index, plan.m, end_min)[:, None, :]


def _dense_count_approx(
    index: TextIndex, plan: PatternPlan, k: int, end_min=None
) -> jnp.ndarray:
    return match_group_approx(index, plan, k, end_min).sum(-1, dtype=jnp.int32)


def _approx_candidates(
    index: TextIndex,
    plan: PatternPlan,
    bank: Optional[FingerprintBank] = None,
    end_min=None,
):
    """Relaxed-LUT candidate blocks: one O(n) window fingerprint + probe
    (independent of P and k), compacted to APPROX_CAND_BLOCK granularity.
    The fingerprint itself is a shared-prefix read from the FingerprintBank
    — exact and approx plans of any length split one pass over `packed`."""
    B, n = index.packed.shape
    if bank is None:
        bank = FingerprintBank(index.packed)
    h = bank.window_fp(plan.m, plan.kbits)
    cand = plan.relaxed_lut[h] & _valid_starts(index, plan.m, end_min)
    C = APPROX_CAND_BLOCK
    nblk = -(-n // C)
    pad = nblk * C - n
    blk_any = jnp.pad(cand, ((0, 0), (0, pad))).reshape(B, nblk, C).any(-1)
    # 2x the random-text expectation plus per-row slack covers fingerprint
    # collisions and true fuzzy matches; overflow falls back to dense.
    exp_blocks = int(B * nblk * _block_frac(plan))
    budget = int(min(B * nblk, max(1024, 2 * exp_blocks + 8 * B)))
    return blk_any, budget, nblk


def _block_frac(plan: PatternPlan) -> float:
    """Expected candidate-block fraction on random text (host-side)."""
    density = plan.relaxed_bits / (1 << plan.kbits)
    return 1.0 - (1.0 - density) ** APPROX_CAND_BLOCK


def _approx_verify_counts(
    index: TextIndex, plan: PatternPlan, k: int, blk_any, budget, nblk,
    end_min=None,
) -> jnp.ndarray:
    """Gather candidate blocks, count mismatches at all C positions x P
    patterns on the packed gathered rows, scatter-add per-text counts."""
    B = index.batch
    P, m = plan.patterns.shape
    C = APPROX_CAND_BLOCK
    rows_packed, bvec, bstart, live = _gather_candidate_rows(
        index, m, blk_any, budget, nblk, cblock=C
    )
    nb = rows_packed.shape[0]
    mm = jnp.zeros((nb, C, P), jnp.int32)
    nw = _n_strided(m)
    for i in range(nw):
        o = PACK * i
        x = rows_packed[:, o : o + C, None] ^ plan.anchors[None, None, :, i]
        mm = mm + (PACK - count_zero_bytes_u32(x))
    for j in range(nw * PACK, m):
        # byte at gathered position q is the low byte of its packed word
        byte = rows_packed[:, j : j + C] & jnp.uint32(0xFF)
        mm = mm + (byte[:, :, None] != plan.patterns[None, None, :, j]).astype(
            jnp.int32
        )
    starts = bstart[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    in_row = starts <= (index.lengths[bvec][:, None] - m)
    if end_min is not None:
        in_row = in_row & (
            starts + (m - 1) >= jnp.asarray(end_min, jnp.int32)
        )
    ok = (mm <= k) & (in_row & live[:, None])[:, :, None]
    sums = ok.sum(axis=1, dtype=jnp.int32)  # (nb, P)
    counts = jnp.zeros((B, P), jnp.int32)
    return counts.at[bvec].add(sums, mode="drop")


def count_group_approx(
    index: TextIndex,
    plan: PatternPlan,
    k: int,
    bank: Optional[FingerprintBank] = None,
    end_min=None,
) -> jnp.ndarray:
    """int32 (B, P) k-mismatch occurrence counts: relaxed-LUT sparse path
    when the plan carries a usable gate, dense counting otherwise."""
    B, n = index.packed.shape
    C = APPROX_CAND_BLOCK
    # Same shape as the exact engine's count heuristic, re-measured for the
    # k-mismatch costs: dense packed counting is ~1 lane-op per window word
    # (m=8, k=1, 1 MB: 2.0ms vs 9.2ms for the gated path at P=1 — the fixed
    # nonzero over n/C blocks is the sparse floor), so the gate only pays
    # once the dense O(B*n*P) counting dwarfs that floor AND the union
    # relaxed LUT is still sparse enough to prune blocks.
    gated = (
        plan.relaxed_lut is not None
        and k <= plan.k  # reachable set for plan.k covers any smaller budget
        and n >= 4 * C
        and plan.n_patterns >= 4
        and B * n * plan.n_patterns >= 8_000_000
        and _block_frac(plan) <= BLOCK_FRAC_MAX
    )
    if not gated:
        return _dense_count_approx(index, plan, k, end_min)
    blk_any, budget, nblk = _approx_candidates(index, plan, bank, end_min)
    return lax.cond(
        blk_any.sum(dtype=jnp.int32) <= budget,
        lambda _: _approx_verify_counts(
            index, plan, k, blk_any, budget, nblk, end_min
        ),
        lambda _: _dense_count_approx(index, plan, k, end_min),
        None,
    )
