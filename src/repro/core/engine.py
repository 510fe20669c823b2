"""Shared-text batched multi-pattern matching engine (the repo's hot path).

The paper's packed matcher amortizes one SSE word op over 16 positions; its
sequel (Faro & Kulekci, SPIRE 2012 — paper ref [10]) amortizes one pass over
the text across many patterns.  This module is that second amortization done
TPU-style, as an explicit two-phase design (DESIGN.md §7):

  * :class:`TextIndex` — everything that depends only on the text, computed
    ONCE per batch of texts: the packed u32 4-gram view (EPSMb's anchor
    registers) and the aligned beta-block fingerprints (EPSMc's wscrc
    stream).  Batchable over a leading (B, n) dimension with per-row true
    lengths, so ragged documents ride in one padded matrix.

  * :class:`PatternPlan` — everything that depends only on the patterns,
    compiled once per equal-length group: the stacked packed anchor words
    (EPSMb) and a union 2^k lookup table over all patterns' block
    fingerprints.  Payloads scale with the group: pattern-id / bitmask LUTs
    at flat P, fingerprint-sorted CSR slot tables (plus an optional packed
    Aho-Corasick fallback) at dictionary scale (DESIGN.md §14).  The plan
    for a group of P patterns answers all P in one probe of the shared text
    work; ``compile_patterns(..., canonical=True)`` additionally quantizes
    the plan statics so the serving query plane can coalesce arbitrary
    unions onto one jitted executable (DESIGN.md §15).

  * :func:`match_many` joins them: ``bool[B, P, n]`` match-start masks for
    P patterns x B texts in ONE device dispatch (one jit call, no host loop
    over patterns, groups, or batch elements).  :func:`count_many` /
    :func:`any_many` are the reduced variants the data pipeline and serving
    engine actually consume — they never materialize the (B, P, n) mask.

Why this beats the vmapped per-pattern scan (the previous multipattern path):
XLA already shares the text packing across a vmap, but the per-position
compare work still scales as O(P * n).  The engine's union LUT makes the
per-position filter O(n) *independent of P* — one fingerprint probe answers
"could ANY pattern start near here?" — and only the rare candidate blocks
pay the O(P) verification.  Measured on this backend: >= 3x on
counts/containment for P=32 m=8 over 1 MB (benchmarks/run.py writes the
trajectory to BENCH_multipattern.json).

Exactness never depends on the fingerprint heuristics: candidate overflow
beyond the compaction budget falls back to a dense verification branch via
lax.cond, exactly like core/epsm.py's single-pattern EPSMc.
"""

from __future__ import annotations

import dataclasses
import functools
import weakref
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.epsm import EPSMA_MAX, EPSMB_MAX, EPSMC_BETA, _epsmc_stride
from repro.core.packing import (
    PACK,
    as_u8,
    as_u8_np,
    fingerprint_weights,
    fp_accum_word,
    fp_finalize,
    hash_text_blocks,
    pack_u32,
    shift_left,
)

# Engine-wide fingerprint width.  Wider than the single-pattern EPSMc table
# (k=11): the union LUT is shared by up to ~hundreds of patterns, and false
# positives cost a whole block verification, so we buy 2^17 * 1 byte of table
# to keep the candidate stream sparse.
ENGINE_KBITS = 17

# --- dictionary scale (bucketed CSR plans, DESIGN.md §14) -------------------
# fp_finalize keeps the TOP kbits of the mixed sum, so the 17-bit engine
# fingerprint is exactly the prefix of any wider one: "bucketing" the union
# LUT into per-prefix sub-tables IS widening kbits by `bbits` — one flat
# probe, sub-LUT semantics.  bbits targets DICT_SLOTS_PER_PATTERN slots per
# pattern so per-slot occupancy (and with it the bounded verify cost and the
# candidate-block density) stays roughly constant as P grows 32 -> 50k.
DICT_BUCKET_MIN_P = 128    # bucket="auto": CSR plans from this group size
DICT_BBITS_MAX = 5         # kbits + bbits <= 22: slot_off tops out at 16 MB
DICT_SLOTS_PER_PATTERN = 64
# Static occupancy cliff: a pattern set whose max slot occupancy exceeds
# this makes even the bounded verify pay slot_max deep per position — route
# straight to the automaton when one was compiled (pattern-set-adversarial
# guard; text-adversarial floods are the lax.cond overflow below).
SLOT_VERIFY_CAP = 64
AUTOMATON_MIN_P = 1024     # automaton="auto": build from this total P
# Expected candidate-BLOCK density (from the static LUT popcount) above
# which the sparse compaction cannot pay: skip it statically and run the
# bounded slot-dense verify (no lax.cond, no wasted union pass).
DENSE_ROUTE_RHO = 0.5
# Block width for compacting per-position EPSMb candidates before the
# fixed-size nonzero: nonzero over n positions is the O(n) floor of the
# sparse path (measured ~40ms/MB on this backend), nonzero over n/32 blocks
# is noise.  32 keeps the verified-position inflation (block granularity vs
# true candidates) small; 128 measured ~1.6x slower end to end.
CAND_BLOCK = 32
# count_many / match_many over a text longer than this scan it as a loop
# over windows of this many bytes (plus the max_m - 1 overlap), so
# their device temporaries are those of one window, not of the whole text:
# a 1 GiB resident corpus otherwise asks ~33 GB of temporaries on a 16 GB
# chip, and the TPU compile time of the sparse routes' gathers grows with
# the window (~80 s at 16 MiB, seconds at 1 MiB).  A multiple of
# EPSMC_BETA, so every window keeps the global block phase.
SEGMENT_BYTES = 1 << 20


def _stage(name: str):
    """Trace the decorated function under ``jax.named_scope(name)``: its
    device operations then carry the engine stage (``index``, ``filter``,
    ``compact``, ``verify``, ``dense``, ``automaton``, ``segment``) in the
    ``op_name`` metadata of the compiled HLO.  One scope per stage, on the
    function that does the stage's work.  A fresh scope per call: one
    shared scope object is not safe across tracing threads."""

    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)

        return scoped

    return wrap


# Fingerprint constants live in packing.py next to the mixing primitives;
# the private aliases keep existing importers (approx.relaxed, the Pallas
# kernels) working unchanged.
from repro.core.packing import FP_MULT as _FP_MULT  # noqa: E402
from repro.core.packing import WORD_SALTS as _WORD_SALTS  # noqa: E402

# Plan compilation emits spans/gauges through an optional repro.obs recorder
# (compile-time cost, LUT occupancy, automaton builds, route decisions) —
# same default-disabled pattern as core/stream.py.
import logging  # noqa: E402

from repro.obs.recorder import Recorder, logging_sink  # noqa: E402

_LOG = logging.getLogger("repro.engine")
_DEFAULT_REC = Recorder(enabled=False, fence=False, sinks=(logging_sink(_LOG),))


# ---------------------------------------------------------------------------
# Phase 1: TextIndex — pack & fingerprint the text once
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class TextIndex:
    """Pattern-independent view of a (B, n) batch of padded texts.

    The text bytes are not stored: byte i is the low byte of ``packed[i]``,
    and ``text`` derives them where a matcher reads bytes (a fused
    elementwise op under jit).  On the TPU a (1, n) uint8 array is laid out
    four rows to a 32-bit sublane word, so a stored text row would cost
    4 bytes per corpus byte, as much as ``packed`` itself."""

    packed: jnp.ndarray    # (B, n) uint32 — LE-packed 4-gram per position
    block_fp: jnp.ndarray  # (B, n // beta) int32 — aligned beta-block k-bit fps
    lengths: jnp.ndarray   # (B,) int32 — true byte length of each row

    def tree_flatten(self):
        return (self.packed, self.block_fp, self.lengths), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)

    @property
    def text(self) -> jnp.ndarray:
        """(B, n) uint8 text bytes, derived from ``packed``."""
        return (self.packed & jnp.uint32(0xFF)).astype(jnp.uint8)

    @property
    def batch(self) -> int:
        return self.packed.shape[0]

    @property
    def n(self) -> int:
        return self.packed.shape[1]


def build_index(
    texts,
    lengths=None,
    *,
    beta: int = EPSMC_BETA,
    kbits: int = ENGINE_KBITS,
) -> TextIndex:
    """Pack + fingerprint once.  `texts` is (n,) or (B, n) uint8 (or a list
    of byte strings, padded to the longest).  jit-compatible for array input.
    """
    if isinstance(texts, (list, tuple)):
        rows = [np.asarray(jax.device_get(as_u8(t))) for t in texts]
        n = max((len(r) for r in rows), default=0)
        mat = np.zeros((len(rows), n), np.uint8)
        for i, r in enumerate(rows):
            mat[i, : len(r)] = r
        texts = mat
        if lengths is None:
            lengths = np.asarray([len(r) for r in rows], np.int32)
    t = as_u8(texts)
    if t.ndim == 1:
        t = t[None, :]
    if t.ndim != 2:
        raise ValueError("texts must be (n,) or (B, n)")
    B, n = t.shape
    if lengths is None:
        lengths = jnp.full((B,), n, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    with jax.named_scope("index"):
        packed = pack_u32(t)
        block_fp = hash_text_blocks(t, fingerprint_weights(beta), kbits)
    return TextIndex(packed=packed, block_fp=block_fp, lengths=lengths)


# ---------------------------------------------------------------------------
# Phase 2: PatternPlan — compile a same-length pattern group once
# ---------------------------------------------------------------------------

def _word_offsets(m: int) -> Tuple[int, ...]:
    """Static offsets of the packed u32 words covering bytes [0, m): strided
    4-gram words plus one overlapping final word when m % 4 != 0."""
    offs = list(range(0, m - PACK + 1, PACK))
    if m % PACK and m >= PACK:
        offs.append(m - PACK)
    return tuple(offs)


def _np_pack_words(pats: np.ndarray, offsets) -> np.ndarray:
    """(P, m) uint8 -> (P, nw) uint32 LE-packed anchor words."""
    p32 = pats.astype(np.uint32)
    cols = []
    for o in offsets:
        cols.append(
            p32[:, o]
            | (p32[:, o + 1] << 8)
            | (p32[:, o + 2] << 16)
            | (p32[:, o + 3] << 24)
        )
    return np.stack(cols, axis=1) if cols else np.zeros((pats.shape[0], 0), np.uint32)


def _np_window_fingerprint(words: np.ndarray, kbits: int) -> np.ndarray:
    """Fingerprint of a full window from its packed words (numpy side)."""
    v = np.zeros(words.shape[:-1], np.uint32)
    for i in range(words.shape[-1]):
        v = v + words[..., i] * _WORD_SALTS[i]
    return ((v * _FP_MULT) >> np.uint32(32 - kbits)).astype(np.int32)


def _window_fingerprint(packed: jnp.ndarray, offsets, kbits: int) -> jnp.ndarray:
    """Same fingerprint on the text side: (B, n) packed view -> (B, n) int32
    fingerprint of the m-byte window starting at every position.  O(n) work
    independent of the number of patterns — this is the engine's whole win."""
    v = jnp.zeros(packed.shape, jnp.uint32)
    for i, o in enumerate(offsets):
        v = fp_accum_word(v, shift_left(packed, o), i)
    return fp_finalize(v, kbits)


def _n_strided_words(m: int) -> int:
    """Number of strided (4-aligned, non-overlapping-start) anchor words in
    _word_offsets(m) — the prefix-chain part shared across pattern lengths."""
    return len(range(0, m - PACK + 1, PACK))


class FingerprintBank:
    """Shared incremental window-fingerprint substrate (DESIGN.md §9).

    ``_window_fingerprint`` is a salted sum over the packed words at a
    length's word offsets.  The strided offsets (0, 4, 8, ...) of every
    pattern length form a prefix chain with FIXED salts (salt i belongs to
    offset 4i), so the salted terms can be accumulated ONCE in one traversal
    of ``packed`` and every length group's fingerprint read off as a prefix
    of the running sum — plus, for m % 4 != 0, the group's single
    overlapping tail word.  G length groups thus cost max_nw + G_tail term
    passes over ``packed`` instead of sum_g nw(m_g): one shared fingerprint
    pass for the whole plan set, on the resident path, the streaming path,
    and the approx path alike.

    uint32 addition is commutative and associative mod 2^32, so the derived
    fingerprints are bit-identical to the direct computation.
    """

    def __init__(self, packed: jnp.ndarray):
        self.packed = packed
        # nterms -> accumulated salted sum over strided words [0, nterms)
        self._prefix = {0: jnp.zeros(packed.shape, jnp.uint32)}
        self._fps: dict = {}  # (m, kbits) -> finalized fingerprint map

    def _strided_sum(self, nterms: int) -> jnp.ndarray:
        done = max(t for t in self._prefix if t <= nterms)
        acc = self._prefix[done]
        for i in range(done, nterms):
            acc = fp_accum_word(acc, shift_left(self.packed, PACK * i), i)
            self._prefix[i + 1] = acc
        return self._prefix[nterms]

    @_stage("filter")
    def window_fp(self, m: int, kbits: int) -> jnp.ndarray:
        """(B, n) int32 fingerprint of the m-byte window at every position —
        bit-identical to _window_fingerprint(packed, _word_offsets(m), kbits)."""
        key = (m, kbits)
        fp = self._fps.get(key)
        if fp is None:
            ns = _n_strided_words(m)
            v = self._strided_sum(ns)
            if m % PACK and m >= PACK:
                # the one overlapping tail word is group-specific: offset
                # m - 4, salted with the next free salt index (list order)
                v = fp_accum_word(v, shift_left(self.packed, m - PACK), ns)
            fp = fp_finalize(v, kbits)
            self._fps[key] = fp
        return fp


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class PatternPlan:
    """Compiled matcher state for one equal-length pattern group."""

    m: int                   # static: pattern length
    kbits: int               # static: fingerprint width
    ids: Tuple[int, ...]     # static: original indices of the group's patterns
    distinct: bool           # static: all P window fingerprints unique (EPSMb)
    patterns: jnp.ndarray    # (P, m) uint8
    anchors: jnp.ndarray     # (P, nw) uint32 stacked packed anchor words
    lut_any: jnp.ndarray     # (2^kbits,) bool union fingerprint table
    lut_pid: Optional[jnp.ndarray]   # (2^kbits,) int32 pattern-id payload (EPSMb)
    lut_bits: Optional[jnp.ndarray]  # (2^kbits, ceil(P/32)) uint32 payloads (EPSMc)
    hp: Optional[jnp.ndarray]        # (P, stride) int32 block fps (EPSMc)
    # --- approximate matching (repro.approx, DESIGN.md §8) -----------------
    k: int = 0               # static: mismatch budget the plan was compiled for
    relaxed_lut: Optional[jnp.ndarray] = None  # (2^kbits,) bool <=k-reachable fps
    relaxed_bits: int = 0    # static: set-bit count of relaxed_lut (budgeting)
    # --- dictionary scale: bucketed CSR payloads (DESIGN.md §14) -----------
    # `kbits` above is the WIDENED width (ENGINE_KBITS + bbits) for bucketed
    # plans; the payload bitmask/pid LUTs are replaced by a CSR keyed by the
    # wide fingerprint: slot_off[f] .. slot_off[f+1] index id lists sorted by
    # fingerprint, so a slot's verify gather reads CONSECUTIVE rows of the
    # fp-sorted anchor/pattern tables (grouped gathers), and the per-slot id
    # lists are width-packed (uint16 when P <= 65536).
    bbits: int = 0           # static: widening over ENGINE_KBITS (0 = flat)
    lut_pop: int = 0         # static: union-LUT popcount (budget heuristics)
    slot_max: int = 0        # static: max slot occupancy (verify bound)
    slot_off: Optional[jnp.ndarray] = None       # (2^kbits + 1,) int32 (EPSMb)
    slot_ids: Optional[jnp.ndarray] = None       # (P,) uint16|int32 fp-sorted ids
    anchors_sorted: Optional[jnp.ndarray] = None  # (P, nw) u32 fp-sorted anchors
    c_slot_off: Optional[jnp.ndarray] = None     # (2^kbits + 1,) int32 (EPSMc)
    c_entry_pid: Optional[jnp.ndarray] = None    # (P*stride,) int32 fp-sorted
    c_entry_off: Optional[jnp.ndarray] = None    # (P*stride,) int32 block offset
    c_entry_pat: Optional[jnp.ndarray] = None    # (P*stride, m) u8 grouped rows
    automaton: Optional[Any] = None  # core.automaton.AutomatonPlan fallback

    def tree_flatten(self):
        return (
            (self.patterns, self.anchors, self.lut_any, self.lut_pid,
             self.lut_bits, self.hp, self.relaxed_lut, self.slot_off,
             self.slot_ids, self.anchors_sorted, self.c_slot_off,
             self.c_entry_pid, self.c_entry_off, self.c_entry_pat,
             self.automaton),
            (self.m, self.kbits, self.ids, self.distinct, self.k,
             self.relaxed_bits, self.bbits, self.lut_pop, self.slot_max),
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        m, kbits, ids, distinct, k, relaxed_bits, bbits, lut_pop, slot_max = aux
        (patterns, anchors, lut_any, lut_pid, lut_bits, hp, relaxed,
         slot_off, slot_ids, anchors_sorted, c_slot_off, c_entry_pid,
         c_entry_off, c_entry_pat, automaton) = children
        return cls(
            m, kbits, ids, distinct, patterns, anchors, lut_any, lut_pid,
            lut_bits, hp, k=k, relaxed_lut=relaxed, relaxed_bits=relaxed_bits,
            bbits=bbits, lut_pop=lut_pop, slot_max=slot_max,
            slot_off=slot_off, slot_ids=slot_ids,
            anchors_sorted=anchors_sorted, c_slot_off=c_slot_off,
            c_entry_pid=c_entry_pid, c_entry_off=c_entry_off,
            c_entry_pat=c_entry_pat, automaton=automaton,
        )

    @property
    def n_patterns(self) -> int:
        return self.patterns.shape[0]

    @property
    def regime(self) -> str:
        if self.m < EPSMA_MAX:
            return "a"
        if self.m < EPSMB_MAX:
            return "b"
        return "c"


def _dict_bbits(P: int, kbits: int) -> int:
    """Widening that targets DICT_SLOTS_PER_PATTERN slots per pattern."""
    need = int(np.ceil(np.log2(max(2, DICT_SLOTS_PER_PATTERN * P)))) - kbits
    return int(min(DICT_BBITS_MAX, max(0, need)))


def _pow2_ceil(n: int) -> int:
    """Smallest power of two >= n (n >= 1)."""
    return 1 << max(0, int(n - 1).bit_length())


def compile_patterns(
    patterns: Sequence,
    *,
    kbits: int = ENGINE_KBITS,
    beta: int = EPSMC_BETA,
    k: int = 0,
    bucket="auto",
    automaton="auto",
    canonical: bool = False,
    recorder: Optional[Recorder] = None,
) -> Tuple[PatternPlan, ...]:
    """Group patterns by length and compile one PatternPlan per group.

    Returned plans are sorted by m; each plan's ``ids`` maps its rows back to
    positions in the input sequence (match_many output is plan-concatenated;
    ``plan_order(plans)`` gives the row -> input-position permutation).

    ``k`` is the mismatch budget the plans are compiled for (repro.approx,
    DESIGN.md §8): plans additionally carry a host-expanded relaxed
    fingerprint LUT covering every window fingerprint reachable under <= k
    byte substitutions, so ``match_many(..., k=k)`` can keep the candidate
    gate before verification.  k=0 plans are bit-identical to before.

    ``bucket`` controls the dictionary-scale CSR compilation (DESIGN.md
    §14): True forces bucketed plans (widened fingerprint + CSR payloads +
    bounded verify), False forces the flat payload LUTs, and "auto" buckets
    any group with >= DICT_BUCKET_MIN_P patterns.  Bucketed and flat plans
    produce bit-identical match/count results at every P — only the route
    (and its worst-case bound) differs.  ``automaton`` gates the packed
    Aho-Corasick fallback (core/automaton.py) attached to bucketed EPSMb
    plans: True forces a build over the WHOLE input dictionary, "auto"
    builds it when the total pattern count reaches AUTOMATON_MIN_P and the
    automaton's size caps hold, False skips it.

    ``canonical`` quantizes every content-dependent static in the plan aux
    data so that jit caching keys on the pattern set's SHAPE signature, not
    its content (DESIGN.md §15).  Concretely: ``lut_pop``, ``slot_max`` and
    ``relaxed_bits`` are rounded up to powers of two (they only feed budget
    heuristics and verify bounds, so rounding is exactness-preserving).
    ``distinct`` stays content-dependent — it is a single bool, so a shape
    signature compiles at most TWO executables, and for the deduplicated
    unions the serving plane builds, fingerprint collisions are rare enough
    (~P^2 / 2^18) that in practice every same-shape union shares one: the
    O(candidates) pid fast path instead of the O(candidates * P) all-
    pattern verify, which is what keeps a coalesced union dispatch near
    flat in P.  Two canonical compiles whose groups agree on (m, P, k,
    bucketing, distinct) hit the same jitted executable — the property the
    serving query plane (repro.serve.query_plane) relies on to coalesce
    arbitrary pattern unions without per-union XLA recompiles.  Default
    False: offline callers keep the content-tuned statics.

    ``recorder`` (repro.obs) captures the compile-time span, per-group LUT
    occupancy/bucket gauges, and automaton build/skip events — the plan-
    build cost BENCH_dictionary reports next to per-dispatch throughput.
    """
    if k < 0:
        raise ValueError("mismatch budget k must be >= 0")
    if bucket not in (True, False, "auto"):
        raise ValueError("bucket must be True, False, or 'auto'")
    if automaton not in (True, False, "auto"):
        raise ValueError("automaton must be True, False, or 'auto'")
    rec = _DEFAULT_REC if recorder is None else recorder
    groups: dict = {}
    arrs: List[np.ndarray] = []
    for i, p in enumerate(patterns):
        arr = as_u8_np(p)
        if arr.ndim != 1 or arr.size == 0:
            raise ValueError("patterns must be non-empty 1-D byte strings")
        groups.setdefault(arr.size, []).append((i, arr))
        arrs.append(arr)

    plans: List[PatternPlan] = []
    with rec.span("plan_compile", groups=len(groups), p_total=len(arrs)):
        for m in sorted(groups):
            ids = tuple(i for i, _ in groups[m])
            pats = np.stack([a for _, a in groups[m]])
            P = pats.shape[0]
            offsets = _word_offsets(m)
            anchors = _np_pack_words(pats, offsets)
            bucketed = m >= EPSMA_MAX and (
                bucket is True or (bucket == "auto" and P >= DICT_BUCKET_MIN_P)
            )
            bbits = _dict_bbits(P, kbits) if bucketed else 0
            kb = kbits + bbits
            lut_any = np.zeros((1 << kb,), np.bool_)
            lut_pid = lut_bits = hp = None
            slot_off = slot_ids = anchors_sorted = None
            c_slot_off = c_entry_pid = c_entry_off = c_entry_pat = None
            slot_max = 0
            slot_cap = P  # total CSR entries; EPSMc registers P * stride
            distinct = False
            if m < EPSMA_MAX:
                pass  # dense byte compares; no fingerprint machinery
            elif m < EPSMB_MAX:
                hw = _np_window_fingerprint(anchors, kb)  # (P,)
                lut_any[hw] = True
                # pattern-id payload: when every pattern owns a unique slot,
                # a candidate position names its ONE claimed pattern and
                # verification compares one gathered anchor instead of all P
                distinct = len(set(hw.tolist())) == P
                if bucketed:
                    # CSR payload: ids sorted by fingerprint; a slot's list
                    # is a CONTIGUOUS run, so the bounded verify's j-th probe
                    # gathers consecutive rows of the fp-sorted anchors
                    order = np.argsort(hw, kind="stable")
                    occ = np.bincount(hw, minlength=1 << kb)
                    slot_off = np.zeros((1 << kb) + 1, np.int32)
                    slot_off[1:] = np.cumsum(occ).astype(np.int32)
                    slot_ids = order.astype(
                        np.uint16 if P <= (1 << 16) else np.int32
                    )
                    anchors_sorted = anchors[order]
                    slot_max = int(occ.max())
                elif distinct:
                    lut_pid = np.zeros((1 << kb,), np.int32)
                    lut_pid[hw] = np.arange(P, dtype=np.int32)
            else:
                # EPSMc: union LUT over the aligned-block fingerprints a true
                # occurrence can present.  Only offsets j < stride are ever
                # probed (the occurrence's unique "dedup" block — see
                # _match_group_c), so only those are registered: fewer
                # entries, fewer false positives.
                stride = _epsmc_stride(m, beta)
                w = np.asarray(
                    jax.device_get(fingerprint_weights(beta))
                ).astype(np.int64)
                offs = np.arange(stride)
                blocks = pats[:, offs[:, None] + np.arange(beta)[None, :]]
                h = (blocks.astype(np.int64) * w[None, None, :]).sum(-1)
                hp = (h & ((1 << kb) - 1)).astype(np.int32)  # (P, stride)
                lut_any[hp.reshape(-1)] = True
                if bucketed:
                    # CSR replaces the (2^k, ceil(P/32)) payload bitmask —
                    # at P=50k that bitmask is ~800 MB; the CSR is
                    # O(P * stride) entries with fp-grouped pattern rows
                    keys = hp.reshape(-1)  # entry e = pid * stride + off
                    order = np.argsort(keys, kind="stable")
                    occ = np.bincount(keys, minlength=1 << kb)
                    c_slot_off = np.zeros((1 << kb) + 1, np.int32)
                    c_slot_off[1:] = np.cumsum(occ).astype(np.int32)
                    c_entry_pid = (order // stride).astype(np.int32)
                    c_entry_off = (order % stride).astype(np.int32)
                    c_entry_pat = pats[c_entry_pid]
                    slot_max = int(occ.max())
                    slot_cap = P * stride
                else:
                    nwords = -(-P // 32)
                    lut_bits = np.zeros((1 << kb, nwords), np.uint32)
                    for p_i in range(P):
                        bit = np.uint32(1 << (p_i % 32))
                        lut_bits[hp[p_i], p_i // 32] |= bit
            lut_pop = int(lut_any.sum())
            relaxed = None
            relaxed_bits = 0
            if k > 0:
                from repro.approx.relaxed import relaxed_window_lut

                relaxed = relaxed_window_lut(pats, kbits=kb, k=k)
                if relaxed is not None:
                    relaxed_bits = int(relaxed.sum())
            if canonical:
                # quantize the budget/bound statics to powers of two: they
                # enter the plan aux data (jit cache key) and trace-time
                # candidate budgets, and rounding UP only loosens exact-by-
                # construction bounds — see the compile_patterns docstring
                lut_pop = min(1 << kb, _pow2_ceil(max(1, lut_pop)))
                if slot_max:
                    # clamp against the plan's TOTAL CSR entry count, not P:
                    # an EPSMc slot can exceed P (patterns sharing a repeated
                    # or common block register the same fingerprint at
                    # several offsets), and rounding slot_max down would make
                    # _c_verify_csr skip live entries and drop matches
                    slot_max = min(slot_cap, _pow2_ceil(slot_max))
                if relaxed_bits:
                    relaxed_bits = min(1 << kb, _pow2_ceil(relaxed_bits))
            rec.event(
                "plan_group", m=m, n_patterns=P, bucketed=int(bucketed),
                bbits=bbits, kbits=kb, lut_pop=lut_pop, slot_max=slot_max,
                occupancy=lut_pop / float(1 << kb),
            )
            rec.gauge(f"plan.lut_occupancy.m{m}", lut_pop / float(1 << kb))
            rec.gauge(f"plan.buckets.m{m}", float(1 << bbits))
            plans.append(
                PatternPlan(
                    m=m,
                    kbits=kb,
                    ids=ids,
                    distinct=distinct,
                    patterns=jnp.asarray(pats),
                    anchors=jnp.asarray(anchors),
                    lut_any=jnp.asarray(lut_any),
                    lut_pid=None if lut_pid is None else jnp.asarray(lut_pid),
                    lut_bits=None if lut_bits is None else jnp.asarray(lut_bits),
                    hp=None if hp is None else jnp.asarray(hp),
                    k=k,
                    relaxed_lut=None if relaxed is None else jnp.asarray(relaxed),
                    relaxed_bits=relaxed_bits,
                    bbits=bbits,
                    lut_pop=lut_pop,
                    slot_max=slot_max,
                    slot_off=None if slot_off is None else jnp.asarray(slot_off),
                    slot_ids=None if slot_ids is None else jnp.asarray(slot_ids),
                    anchors_sorted=(
                        None if anchors_sorted is None
                        else jnp.asarray(anchors_sorted)
                    ),
                    c_slot_off=(
                        None if c_slot_off is None else jnp.asarray(c_slot_off)
                    ),
                    c_entry_pid=(
                        None if c_entry_pid is None else jnp.asarray(c_entry_pid)
                    ),
                    c_entry_off=(
                        None if c_entry_off is None else jnp.asarray(c_entry_off)
                    ),
                    c_entry_pat=(
                        None if c_entry_pat is None else jnp.asarray(c_entry_pat)
                    ),
                )
            )
        # --- packed automaton fallback (core/automaton.py, DESIGN.md §14) --
        # Built over the WHOLE input dictionary in INPUT order, so any plan
        # subset can column-select its counts via plan.ids; attached to every
        # bucketed EPSMb plan (the shared-path fallback consumers).
        want_auto = automaton is True or (
            automaton == "auto"
            and len(arrs) >= AUTOMATON_MIN_P
            and any(p.slot_off is not None for p in plans)
        )
        if want_auto and any(p.slot_off is not None for p in plans):
            from repro.core.automaton import compile_automaton

            with rec.span("automaton_compile", p_total=len(arrs)):
                auto = compile_automaton(arrs)
            if auto is None:
                rec.event("automaton_skipped", p_total=len(arrs))
            else:
                rec.event(
                    "automaton_built", states=auto.n_states,
                    classes=auto.n_classes, entries=auto.n_entries,
                    out_max=auto.out_max,
                )
                plans = [
                    dataclasses.replace(p, automaton=auto)
                    if p.slot_off is not None else p
                    for p in plans
                ]
    return tuple(plans)


def plan_order(plans: Sequence[PatternPlan]) -> np.ndarray:
    """inverse permutation: row i of the concatenated engine output is
    pattern ``order[i]`` of the original input sequence."""
    return np.asarray([i for plan in plans for i in plan.ids], np.int64)


def replicate_plans(
    plans: Sequence[PatternPlan], device
) -> Tuple[PatternPlan, ...]:
    """Copies of compiled plans committed to ``device`` (None = leave as is).

    jit requires colocated inputs, so a scanner dispatching on a non-default
    device needs the plan LUTs/anchors resident there.  The sharded stream
    scanner calls this once per device and reuses the replicas for every
    shard it places on that device — the FingerprintBank each dispatch builds
    then reads the same device-local plan state, with no per-chunk transfer
    (device_put of an already-resident array is a no-op)."""
    if device is None:
        return tuple(plans)
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, device), tuple(plans)
    )


_PLAN_CACHE: dict = {}
_PLAN_CACHE_MAX = 64
_PLAN_CACHE_STATS = {"hits": 0, "misses": 0}
# id(array) -> (weakref, canonical-u8 bytes): per-object digest memo so a
# device-resident pattern pays its device_get round-trip ONCE, not on every
# cache probe.  The weakref guards against id() reuse after GC: a recycled
# id maps to a dead (or different) referent and falls through to recompute.
_DIGEST_MEMO: dict = {}
_DIGEST_MEMO_MAX = 256


def _pattern_cache_token(p) -> bytes:
    """Canonical uint8 bytes of one pattern WITHOUT a device round-trip on
    the hot path: host types are serialized directly; device arrays hit a
    per-object digest memo (keyed by id + weakref identity) so only the
    first sighting of an array object pays jax.device_get."""
    if isinstance(p, (bytes, bytearray, memoryview)):
        return bytes(p)
    if isinstance(p, str):
        return p.encode("utf-8", errors="surrogateescape")
    if isinstance(p, np.ndarray):
        a = p if p.dtype == np.uint8 else p.astype(np.uint8)
        return a.tobytes()
    if isinstance(p, (list, tuple)):
        return np.asarray(p).astype(np.uint8).tobytes()
    ent = _DIGEST_MEMO.get(id(p))
    if ent is not None:
        ref, tok = ent
        if ref() is p:
            return tok
    tok = bytes(as_u8_np(p))
    try:
        if len(_DIGEST_MEMO) >= _DIGEST_MEMO_MAX:
            # drop dead entries first; fall back to clearing (rare)
            for i in [i for i, (r, _) in _DIGEST_MEMO.items() if r() is None]:
                del _DIGEST_MEMO[i]
            if len(_DIGEST_MEMO) >= _DIGEST_MEMO_MAX:
                _DIGEST_MEMO.clear()
        _DIGEST_MEMO[id(p)] = (weakref.ref(p), tok)
    except TypeError:
        pass  # not weakref-able: stay correct, just uncached
    return tok


def compile_patterns_cached(
    patterns: Sequence, *, k: int = 0, bucket="auto", automaton="auto",
    canonical: bool = False, recorder: Optional[Recorder] = None,
) -> Tuple[PatternPlan, ...]:
    """compile_patterns with a small host-side LRU memo keyed by pattern
    bytes (and the compile knobs: mismatch budget k, bucket/automaton
    routing, canonical quantization).

    The convenience wrappers (find_multi & co., the batched kernels) receive
    raw pattern stacks per call; without this, every call would pay the
    host-side plan build (2^17 LUT allocation + upload) that PatternSet
    amortizes by construction.  Key construction is transfer-free on cache
    hits: a repeat call with the same (live) device arrays costs dict probes
    only, no jax.device_get (see _pattern_cache_token).  Eviction is
    least-recently-USED (hits refresh recency), so a serving workload's hot
    pattern unions stay resident under tail-churn; hit/miss totals are
    exposed via plan_cache_stats() and, when ``recorder`` is passed, the
    plan_cache.hit / plan_cache.miss counters (DESIGN.md §15)."""
    rec = _DEFAULT_REC if recorder is None else recorder
    key = (k, bucket, automaton, canonical) + tuple(
        _pattern_cache_token(p) for p in patterns
    )
    plans = _PLAN_CACHE.pop(key, None)
    if plans is None:
        _PLAN_CACHE_STATS["misses"] += 1
        rec.count("plan_cache.miss")
        plans = compile_patterns(patterns, k=k, bucket=bucket,
                                 automaton=automaton, canonical=canonical,
                                 recorder=recorder)
        if len(_PLAN_CACHE) >= _PLAN_CACHE_MAX:
            _PLAN_CACHE.pop(next(iter(_PLAN_CACHE)))
    else:
        _PLAN_CACHE_STATS["hits"] += 1
        rec.count("plan_cache.hit")
    _PLAN_CACHE[key] = plans  # (re)insert at the recent end
    return plans


def plan_cache_stats() -> dict:
    """Lifetime hit/miss totals and current size of the plan memo — the
    query plane surfaces these in its stats() snapshot (DESIGN.md §15)."""
    return dict(_PLAN_CACHE_STATS, entries=len(_PLAN_CACHE))


# ---------------------------------------------------------------------------
# Matchers (one per regime).  Each returns mask (B, P, n) or counts (B, P).
# ---------------------------------------------------------------------------

def _valid_starts(
    index: TextIndex, m: int, end_min=None
) -> jnp.ndarray:
    """(B, n) — True where a length-m occurrence may start.  Encodes the
    ragged-padding contract: windows never cross a row's true end, so
    patterns cannot match across document boundaries or inside padding.

    ``end_min`` (traced scalar or None) is the streaming seam bound
    (DESIGN.md §11): when given, a start additionally survives only if its
    occurrence ENDS at or past ``end_min`` (start + m - 1 >= end_min).  This
    is the fused form of the StreamScanner overlap-prefix subtraction — the
    occurrences the two-pass path subtracts via the prefix sub-index are
    exactly the ones this bound excludes — so the seam correction costs one
    compare inside the same gate instead of a second index + count pass.
    None compiles to the exact pre-fusion jaxpr (resident callers pay
    nothing)."""
    n = index.n
    pos = jnp.arange(n, dtype=jnp.int32)[None, :]
    ok = pos <= (index.lengths[:, None] - m)
    if end_min is not None:
        ok = ok & (pos + (m - 1) >= jnp.asarray(end_min, jnp.int32))
    return ok


@_stage("dense")
def _match_group_a(
    index: TextIndex,
    plan: PatternPlan,
    bank: Optional[FingerprintBank] = None,
    end_min=None,
) -> jnp.ndarray:
    """m < 4: dense shifted byte compares (EPSMa, batched over B and P)."""
    del bank  # no fingerprint machinery in this regime
    t = index.text
    acc = _valid_starts(index, plan.m, end_min)[:, None, :]
    for j in range(plan.m):
        acc = acc & (shift_left(t, j)[:, None, :] == plan.patterns[None, :, j, None])
    return acc


@_stage("dense")
def _dense_b(index: TextIndex, plan: PatternPlan, end_min=None) -> jnp.ndarray:
    """Stacked-anchor dense compare: AND over packed word compares.  This is
    the exact EPSMb filter+verify fused — also the overflow fallback."""
    acc = _valid_starts(index, plan.m, end_min)[:, None, :]
    for i, o in enumerate(_word_offsets(plan.m)):
        w = shift_left(index.packed, o)
        acc = acc & (w[:, None, :] == plan.anchors[None, :, i, None])
    return acc


def _expected_union_blocks(
    B: int, n: int, plans: Sequence[PatternPlan], cblock: int = CAND_BLOCK
) -> Tuple[int, float]:
    """(expected candidate blocks, expected block density) from the STATIC
    per-plan LUT popcounts — the satellite fix for the expansion budget.

    The old heuristic ``(B*n*P) >> kbits`` modeled per-POSITION collisions
    against one flat 2^17 table; it ignores (a) slot sharing (P patterns
    occupy lut_pop <= P slots), (b) the widened per-bucket tables of
    dictionary plans (kbits varies per plan), and (c) the block-of-C
    aggregation that actually feeds the nonzero — at high P it both
    over- and under-shoots by orders of magnitude, tripping the dense
    lax.cond fallback on benign text.  The block-level expectation under a
    uniform-fingerprint model is exact: a block of C positions survives when
    ANY of its positions hits ANY plan's occupied slots, so the miss
    probability is prod_g (1 - occ_g)^C with occ_g = lut_pop_g / 2^kbits_g.
    """
    nblk = -(-n // cblock)
    miss = 1.0
    for p in plans:
        occ = min(1.0, p.lut_pop / float(1 << p.kbits))
        miss *= (1.0 - occ) ** cblock
    rho = 1.0 - miss
    return int(B * nblk * rho), rho


@_stage("filter")
def _b_candidates(
    index: TextIndex,
    plan: PatternPlan,
    bank: Optional[FingerprintBank] = None,
    end_min=None,
):
    """Shared-text candidate generation for EPSMb: one O(n) fingerprint +
    union-LUT probe (independent of P), compacted to CAND_BLOCK granularity.
    With a FingerprintBank the fingerprint is a shared-prefix read instead
    of a full per-group recomputation."""
    B, n = index.packed.shape
    if bank is None:
        bank = FingerprintBank(index.packed)
    h = bank.window_fp(plan.m, plan.kbits)  # (B, n)
    cand = plan.lut_any[h] & _valid_starts(index, plan.m, end_min)
    C = CAND_BLOCK
    nblk = -(-n // C)
    pad = nblk * C - n
    blk_any = jnp.pad(cand, ((0, 0), (0, pad))).reshape(B, nblk, C).any(-1)
    # budget covers expected fingerprint collisions AND heavy-tailed true-match
    # densities (patterns sampled from the text itself light up ~1/3 of the
    # blocks before the sparse path stops paying); beyond it, dense fallback.
    exp, _ = _expected_union_blocks(B, n, (plan,))
    budget = int(min(B * nblk, max(1024, 4 * exp + 8 * B, (B * nblk) // 3)))
    return blk_any, budget, nblk


@_stage("compact")
def _gather_candidate_rows(
    index: TextIndex, m: int, blk_any, budget, nblk, cblock: int = CAND_BLOCK
):
    """Shared sparse-path prelude: fixed-budget nonzero over candidate
    blocks, gather each block's C+m-1 bytes, re-pack them once.

    ``cblock`` is the candidate-block granularity C (the exact paths use
    CAND_BLOCK; the k-mismatch path uses a smaller block because its relaxed
    LUT is denser — see repro.approx.counting).

    Returns (rows_packed (nb, C+m-1) u32, bvec (nb,), bstart (nb,), live)."""
    B, n = index.packed.shape
    C = cblock
    (flat,) = jnp.nonzero(blk_any.reshape(-1), size=budget, fill_value=B * nblk)
    live = flat < B * nblk
    flat = jnp.where(live, flat, 0)
    bvec = flat // nblk
    bstart = (flat % nblk) * C
    t_pad = jnp.pad(index.text, ((0, 0), (0, nblk * C - n + m)))
    rows = t_pad[bvec[:, None], bstart[:, None] + jnp.arange(C + m - 1)]
    return pack_u32(rows), bvec, bstart, live


def _start_gate(index: TextIndex, m: int, starts, bvec, end_min):
    """Per-gathered-start validity: inside the row's true length, plus the
    streaming seam bound when one is given (see _valid_starts)."""
    ok = starts <= (index.lengths[bvec][:, None] - m)
    if end_min is not None:
        ok = ok & (starts + (m - 1) >= jnp.asarray(end_min, jnp.int32))
    return ok


@_stage("verify")
def _b_verify(
    index: TextIndex, plan: PatternPlan, blk_any, budget, nblk, end_min=None
):
    """Gather candidate blocks, re-pack them, verify all positions x patterns.

    Returns (ok (nb, C, P), bvec (nb,), starts (nb, C) with n as the
    out-of-range sentinel)."""
    n = index.packed.shape[1]
    m, C = plan.m, CAND_BLOCK
    rows_packed, bvec, bstart, live = _gather_candidate_rows(
        index, m, blk_any, budget, nblk
    )
    ok = None
    for i, o in enumerate(_word_offsets(m)):
        w = rows_packed[:, o : o + C]
        eq = w[:, :, None] == plan.anchors[None, None, :, i]
        ok = eq if ok is None else ok & eq
    starts = bstart[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    in_row = _start_gate(index, m, starts, bvec, end_min)
    ok = ok & (in_row & live[:, None])[:, :, None]
    starts = jnp.where(in_row & live[:, None], starts, n)
    return ok, bvec, starts


@_stage("dense")
def _dense_count(
    index: TextIndex, plan: PatternPlan, dense_fn, end_min=None
) -> jnp.ndarray:
    """Counts via the dense mask (overflow fallback only — the sparse paths
    never materialize (B, P, n))."""
    return dense_fn(index, plan, end_min).sum(-1, dtype=jnp.int32)


def _match_group_b(
    index: TextIndex,
    plan: PatternPlan,
    bank: Optional[FingerprintBank] = None,
    end_min=None,
) -> jnp.ndarray:
    del bank  # dense path — no text-side fingerprint
    # For full (B, P, n) masks the stacked-anchor dense compare is already
    # memory-bound optimal on this backend (the output write dominates), and
    # a candidate scatter of the same size measured ~70x slower.  The union
    # LUT earns its keep on the reduced outputs (_count_group_b), where the
    # (B, P, n) intermediate can be skipped entirely.
    return _dense_b(index, plan, end_min)


@_stage("verify")
def _b_verify_pid(
    index: TextIndex, plan: PatternPlan, blk_any, budget, nblk, end_min=None
):
    """Distinct-fingerprint fast verify: each candidate position names its one
    claimed pattern through the pid payload LUT, so verification gathers and
    compares a SINGLE anchor row per position — O(nb * C) work instead of
    O(nb * C * P).  Returns (ok (nb, C) int32, bvec (nb,), pid (nb, C))."""
    m, C = plan.m, CAND_BLOCK
    rows_packed, bvec, bstart, live = _gather_candidate_rows(
        index, m, blk_any, budget, nblk
    )
    # re-derive the window fingerprint from the gathered rows (cheaper than a
    # second big gather out of the full (B, n) fingerprint map)
    h = _window_fingerprint(rows_packed, _word_offsets(m), plan.kbits)[:, :C]
    candp = plan.lut_any[h]
    pid = plan.lut_pid[h]  # (nb, C) the one pattern that could start here
    sel = plan.anchors[pid]  # (nb, C, nw)
    ok = candp
    for i, o in enumerate(_word_offsets(m)):
        ok = ok & (rows_packed[:, o : o + C] == sel[:, :, i])
    starts = bstart[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    ok = ok & _start_gate(index, m, starts, bvec, end_min) & live[:, None]
    return ok.astype(jnp.int32), bvec, pid


@_stage("automaton")
def _automaton_counts(index: TextIndex, auto, end_min=None) -> jnp.ndarray:
    """(B, N_input) exact counts via the packed Aho-Corasick fallback —
    linear in n regardless of candidate density (DESIGN.md §14)."""
    from repro.core.automaton import count_automaton

    return count_automaton(index.text, index.lengths, auto, end_min=end_min)


@_stage("verify")
def _b_count_rows_csr(
    index: TextIndex,
    plan: PatternPlan,
    rows_packed,
    bvec,
    starts,
    live,
    row_bank: FingerprintBank,
    end_min=None,
) -> jnp.ndarray:
    """Bounded CSR verify on gathered candidate rows (bucketed EPSMb).

    Each candidate position probes its wide-fingerprint slot's id list
    (slot_off CSR) and walks at most ``slot_max`` entries; the j-th probe
    gathers CONSECUTIVE rows of the fp-sorted anchor table (the grouped
    gather the CSR sort buys).  O(nb * C * slot_max * nw) — independent of
    P, unlike the flat all-patterns verify's O(nb * C * P * nw)."""
    B = index.packed.shape[0]
    C = starts.shape[1]
    P = plan.n_patterns
    h = row_bank.window_fp(plan.m, plan.kbits)[:, :C]
    base = plan.slot_off[h]
    cnt = plan.slot_off[h + 1] - base
    ok_pos = _start_gate(index, plan.m, starts, bvec, end_min) & live[:, None]
    words = [
        rows_packed[:, o : o + C] for o in _word_offsets(plan.m)
    ]
    counts = jnp.zeros((B, P), jnp.int32)
    for j in range(plan.slot_max):
        idx = jnp.minimum(base + j, P - 1)
        sel = plan.anchors_sorted[idx]  # (nb, C, nw) — contiguous per slot
        ok = (j < cnt) & ok_pos
        for i in range(len(words)):
            ok = ok & (words[i] == sel[..., i])
        pid = plan.slot_ids[idx].astype(jnp.int32)
        counts = counts.at[bvec[:, None], pid].add(
            ok.astype(jnp.int32), mode="drop"
        )
    return counts


@_stage("dense")
def _count_b_slot_dense(
    index: TextIndex,
    plan: PatternPlan,
    bank: Optional[FingerprintBank] = None,
    end_min=None,
) -> jnp.ndarray:
    """Slot-dense bounded verify: EVERY position checks its slot's id list.

    This replaces the flat path's O(n * P) dense fallback for bucketed
    plans: cost is O(n * slot_max * nw) with slot_max a COMPILE-TIME
    constant of the pattern set — adversarial text can flood the candidate
    stream but cannot change the per-position bound, so collision floods
    degrade to a linear scan instead of the quadratic verify."""
    B, n = index.packed.shape
    P = plan.n_patterns
    if bank is None:
        bank = FingerprintBank(index.packed)
    h = bank.window_fp(plan.m, plan.kbits)  # (B, n)
    base = plan.slot_off[h]
    cnt = plan.slot_off[h + 1] - base
    valid = _valid_starts(index, plan.m, end_min)
    words = [shift_left(index.packed, o) for o in _word_offsets(plan.m)]
    counts = jnp.zeros((B, P), jnp.int32)
    bix = jnp.arange(B, dtype=jnp.int32)[:, None]
    for j in range(plan.slot_max):
        idx = jnp.minimum(base + j, P - 1)
        sel = plan.anchors_sorted[idx]  # (B, n, nw)
        ok = (j < cnt) & valid
        for i in range(len(words)):
            ok = ok & (words[i] == sel[..., i])
        pid = plan.slot_ids[idx].astype(jnp.int32)
        counts = counts.at[bix, pid].add(ok.astype(jnp.int32), mode="drop")
    return counts


# Sparse-vs-dense cliff for the EPSMb count path: the sparse machinery pays
# once the dense (B, P, n) mask would fall out of cache during the reduce
# (measured ~8 MB of mask on this backend); below it, or for tiny pattern
# sets, dense wins.  Shared by the per-group and multi-group count paths.
SPARSE_B_MIN_ELEMS = 8_000_000


def _sparse_b_eligible(index: TextIndex, plan: PatternPlan) -> bool:
    B, n = index.packed.shape
    return (
        n >= 4 * CAND_BLOCK
        and plan.n_patterns >= 4
        and B * n * plan.n_patterns >= SPARSE_B_MIN_ELEMS
    )


def _count_group_b(
    index: TextIndex,
    plan: PatternPlan,
    bank: Optional[FingerprintBank] = None,
    end_min=None,
) -> jnp.ndarray:
    B, n = index.packed.shape
    P = plan.n_patterns
    if plan.slot_off is not None:
        # bucketed (dictionary-scale) plan: sparse CSR verify with the
        # bounded slot-dense scan as BOTH the static dense-density route and
        # the lax.cond overflow fallback — never the O(n * P) dense compare.
        if bank is None:
            bank = FingerprintBank(index.packed)
        _, rho = _expected_union_blocks(B, n, (plan,))
        if (
            not _sparse_b_eligible(index, plan)
            or rho > DENSE_ROUTE_RHO
            or plan.slot_max > SLOT_VERIFY_CAP
        ):
            return _count_b_slot_dense(index, plan, bank, end_min)
        blk_any, budget, nblk = _b_candidates(index, plan, bank, end_min)

        def sparse_csr(_):
            rows_packed, bvec, bstart, live = _gather_candidate_rows(
                index, plan.m, blk_any, budget, nblk
            )
            starts = (
                bstart[:, None] + jnp.arange(CAND_BLOCK, dtype=jnp.int32)[None, :]
            )
            return _b_count_rows_csr(
                index, plan, rows_packed, bvec, starts, live,
                FingerprintBank(rows_packed), end_min,
            )

        return lax.cond(
            blk_any.sum(dtype=jnp.int32) <= budget,
            sparse_csr,
            lambda _: _count_b_slot_dense(index, plan, bank, end_min),
            None,
        )
    if not _sparse_b_eligible(index, plan):
        return _dense_count(index, plan, _dense_b, end_min)
    blk_any, budget, nblk = _b_candidates(index, plan, bank, end_min)

    def sparse_pid(_):
        ok, bvec, pid = _b_verify_pid(
            index, plan, blk_any, budget, nblk, end_min
        )
        counts = jnp.zeros((B, P), jnp.int32)
        return counts.at[bvec[:, None], pid].add(ok, mode="drop")

    def sparse_all(_):
        ok, bvec, _ = _b_verify(index, plan, blk_any, budget, nblk, end_min)
        # reduce the block axis with a batched matvec: XLA-CPU's plain
        # bool-sum reduce runs at ~5ns/element, the dot lowers to the fast
        # GEMV path (measured 92ms -> 7ms on the budget-sized ok tensor)
        sums = jnp.einsum(
            "bcp,c->bp", ok.astype(jnp.float32),
            jnp.ones((CAND_BLOCK,), jnp.float32),
        )
        counts = jnp.zeros((B, P), jnp.float32)
        return counts.at[bvec].add(sums, mode="drop").astype(jnp.int32)

    sparse = sparse_pid if plan.distinct else sparse_all
    return lax.cond(
        blk_any.sum(dtype=jnp.int32) <= budget,
        sparse,
        lambda _: _dense_count(index, plan, _dense_b, end_min),
        None,
    )


@dataclasses.dataclass(frozen=True)
class _SharedRoute:
    """Static (trace-time) routing decision for one shared EPSMb set."""

    budget: int            # candidate-block budget for the lax.cond gate
    exp_blocks: int        # expected candidate blocks (static model)
    rho: float             # expected candidate-block density
    static_fallback: bool  # skip the sparse machinery entirely
    automaton: Any         # AutomatonPlan to fall back to, or None
    kind: str              # fallback kind: "automaton"|"slot_dense"|"dense"


def _shared_b_route(
    index: TextIndex, plans: Sequence[PatternPlan]
) -> _SharedRoute:
    """One routing decision shared by _count_groups_b_shared and
    route_probe, so the dispatcher and the probe cannot disagree.

    Everything here is host-static (LUT popcounts, slot_max, expected
    density) — the only RUNTIME signal is the measured union block count,
    which the caller compares against ``budget`` inside lax.cond."""
    B, n = index.packed.shape
    nblk = -(-n // CAND_BLOCK)
    exp, rho = _expected_union_blocks(B, n, plans)
    # Tighter budget than the per-group path's (B*nblk)//3 heavy-tail slack:
    # every verification op here is paid G-groups-deep on the shared rows,
    # so over-provisioning is G times as expensive, while the bounded
    # fallback below still guarantees exactness on overflow.  2x the
    # expected-collision mass separates textures at dictionary scale, where
    # rho is pinned near DICT_SLOTS_PER_PATTERN/2^bbits-induced ~0.3:
    # average text measures ~exp blocks (inside budget -> sparse gather),
    # while an adversarial fingerprint flood measures ~all blocks, ~3x exp
    # (overflow -> automaton / bounded slot-dense).  A 16x multiplier here
    # would exceed the total block count whenever rho > 1/16 and the
    # measured-density trigger could never fire.  The 8*B + nblk/16 floor
    # keeps benign low-P workloads (tiny exp, bursty real text) sparse.
    budget = int(
        min(B * nblk, max(4096, 2 * exp + 8 * B + (B * nblk) // 16))
    )
    auto = next(
        (p.automaton for p in plans if p.automaton is not None), None
    )
    slot_cap_hit = any(
        p.slot_off is not None and p.slot_max > SLOT_VERIFY_CAP for p in plans
    )
    static_fallback = (slot_cap_hit and auto is not None) or rho > DENSE_ROUTE_RHO
    if auto is not None:
        kind = "automaton"
    elif any(p.slot_off is not None for p in plans):
        kind = "slot_dense"
    else:
        kind = "dense"
    return _SharedRoute(
        budget=budget, exp_blocks=exp, rho=rho,
        static_fallback=static_fallback, automaton=auto, kind=kind,
    )


def _count_groups_b_shared(
    index: TextIndex,
    plans: Sequence[PatternPlan],
    bank: FingerprintBank,
    end_min=None,
) -> jnp.ndarray:
    """Multi-group EPSMb counting with ONE shared candidate pass.

    The per-group sparse path pays an O(n) fingerprint + LUT probe AND an
    O(n) compaction (block reduce, fixed-budget nonzero, candidate-row
    gather + repack) PER GROUP.  Here the G groups share everything the
    algebra allows (DESIGN.md §9): fingerprints come off the
    FingerprintBank's one prefix accumulation; the candidate block masks are
    OR'd into one union; ONE nonzero + ONE row gather (spanning max_m)
    serves every group, which then only verifies its own patterns on the
    shared gathered rows — on a second, rows-sized FingerprintBank for the
    distinct-fingerprint pid fast path.  G length groups thus cost one pass
    over ``packed`` + one compaction instead of G of each.

    Exactness matches the per-group path: the union mask is a superset of
    every group's candidate blocks, verification is the same anchor-word
    compare, and union-budget overflow falls back to the dense count for
    ALL shared groups via lax.cond.
    """
    B, n = index.packed.shape
    C = CAND_BLOCK
    nblk = -(-n // C)
    max_m = max(p.m for p in plans)
    route = _shared_b_route(index, plans)

    def fallback(_):
        # Route hierarchy (DESIGN.md §14): packed automaton when any shared
        # plan carries one (it covers the WHOLE input dictionary, so every
        # plan column-selects via ids — linear-time, density-independent);
        # else slot-dense bounded verify for bucketed plans and the classic
        # dense compare for flat ones.
        auto = route.automaton
        if auto is not None:
            ca = _automaton_counts(index, auto, end_min)
            return jnp.concatenate(
                [ca[:, np.asarray(p.ids, np.int64)] for p in plans], axis=1
            )
        outs = []
        for p in plans:
            if p.slot_off is not None:
                outs.append(_count_b_slot_dense(index, p, bank, end_min))
            else:
                outs.append(_dense_count(index, p, _dense_b, end_min))
        return jnp.concatenate(outs, axis=1)

    if route.static_fallback:
        return fallback(None)

    union = None
    for p in plans:
        h = bank.window_fp(p.m, p.kbits)
        with jax.named_scope("filter"):
            cand = p.lut_any[h] & _valid_starts(index, p.m, end_min)
            blk = (
                jnp.pad(cand, ((0, 0), (0, nblk * C - n)))
                .reshape(B, nblk, C)
                .any(-1)
            )
        union = blk if union is None else union | blk

    def sparse(_):
        rows_packed, bvec, bstart, live = _gather_candidate_rows(
            index, max_m, union, route.budget, nblk
        )
        row_bank = FingerprintBank(rows_packed)
        starts = bstart[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
        outs = []
        for p in plans:
            in_row = _start_gate(index, p.m, starts, bvec, end_min)
            ok_pos = in_row & live[:, None]
            if p.slot_off is not None:
                # bounded CSR verify on the shared rows (dictionary plans)
                outs.append(
                    _b_count_rows_csr(
                        index, p, rows_packed, bvec, starts, live,
                        row_bank, end_min,
                    )
                )
            elif p.distinct:
                # pid fast path on the shared rows: O(nb * C) per group
                h = row_bank.window_fp(p.m, p.kbits)[:, :C]
                pid = p.lut_pid[h]
                sel = p.anchors[pid]  # (nb, C, nw)
                ok = p.lut_any[h]
                for i, o in enumerate(_word_offsets(p.m)):
                    ok = ok & (rows_packed[:, o : o + C] == sel[:, :, i])
                ok = (ok & ok_pos).astype(jnp.int32)
                counts = jnp.zeros((B, p.n_patterns), jnp.int32)
                outs.append(
                    counts.at[bvec[:, None], pid].add(ok, mode="drop")
                )
            else:
                ok = None
                for i, o in enumerate(_word_offsets(p.m)):
                    eq = (
                        rows_packed[:, o : o + C, None]
                        == p.anchors[None, None, :, i]
                    )
                    ok = eq if ok is None else ok & eq
                ok = ok & ok_pos[:, :, None]
                sums = jnp.einsum(
                    "bcp,c->bp", ok.astype(jnp.float32),
                    jnp.ones((C,), jnp.float32),
                )
                counts = jnp.zeros((B, p.n_patterns), jnp.float32)
                outs.append(
                    counts.at[bvec].add(sums, mode="drop").astype(jnp.int32)
                )
        return jnp.concatenate(outs, axis=1)

    return lax.cond(
        union.sum(dtype=jnp.int32) <= route.budget, sparse, fallback, None
    )


# Fallback for EPSMc overflow: dense shifted byte compares — O(m) passes but
# memory-bounded at (B, P, n).  Same computation as the EPSMa matcher, which
# is exact for every m.  (A wrapper, not an alias: _dense_count passes
# end_min as the 3rd positional, which must not bind to `bank`.)
def _dense_c(index: TextIndex, plan: PatternPlan, end_min=None) -> jnp.ndarray:
    return _match_group_a(index, plan, None, end_min)


@_stage("filter")
def _c_candidates(index: TextIndex, plan: PatternPlan):
    """Probe the union LUT at the strided inspected blocks (paper Fig. 1
    bottom, many patterns at once).  Every occurrence has exactly ONE
    inspected block with offset j < stride inside its window (the dedup
    block), so candidates are found — and counted — exactly once.

    Bucketed plans fingerprint at the WIDENED kbits, which the TextIndex's
    shared block_fp (built at ENGINE_KBITS) cannot serve — those recompute
    the strided blocks' wide fingerprints from the text (O(B * G * beta)
    extra work, bought back many times over by the bounded CSR verify)."""
    beta = EPSMC_BETA
    stride = _epsmc_stride(plan.m, beta)
    step = stride // beta
    if plan.bbits > 0:
        ht = hash_text_blocks(
            index.text, fingerprint_weights(beta), plan.kbits
        )[:, ::step]
    else:
        ht = index.block_fp[:, ::step]  # (B, G) — strided view, no gather
    cand = plan.lut_any[ht]
    B, G = cand.shape
    noff_used = min(stride, plan.m - beta + 1)
    # block-level expectation from the static popcount (see
    # _expected_union_blocks): each inspected block is ONE probe
    exp = int(B * G * min(1.0, plan.lut_pop / float(1 << plan.kbits)))
    budget = int(min(max(B * G, 1), max(64, 4 * exp + 8 * B)))
    return ht, cand, stride, noff_used, budget


@_stage("verify")
def _c_verify(index, plan, ht, cand, stride, noff_used, budget, end_min=None):
    """Verify candidate blocks against all P patterns at the <= stride
    offsets, gated by the LUT's pattern-id payload bitmask."""
    B, n = index.packed.shape
    m = plan.m
    G = cand.shape[1]
    front = noff_used - 1
    span = front + m
    with jax.named_scope("compact"):
        (flat,) = jnp.nonzero(cand.reshape(-1), size=budget, fill_value=B * G)
        live = flat < B * G
        flat = jnp.where(live, flat, 0)
        bvec = flat // G
        bsel = (flat % G) * stride  # inspected block start
        t_pad = jnp.pad(index.text, ((0, 0), (front, span)))
        # (nb, span)
        rows = t_pad[bvec[:, None], bsel[:, None] + jnp.arange(span)]
    # pattern-id payload: which patterns registered this fingerprint?
    P = plan.n_patterns
    bits = plan.lut_bits[ht.reshape(-1)[jnp.where(live, flat, 0)]]  # (nb, W)
    word = jnp.arange(P) // 32
    shift = jnp.arange(P, dtype=jnp.uint32) % 32
    pgate = ((bits[:, word] >> shift[None, :]) & 1).astype(jnp.bool_)  # (nb, P)
    oks, sts = [], []
    for j in range(noff_used):
        win = rows[:, front - j : front - j + m]  # window starting at bsel - j
        st = bsel - j
        in_row = (st >= 0) & (st <= index.lengths[bvec] - m)
        if end_min is not None:
            in_row = in_row & (st + (m - 1) >= jnp.asarray(end_min, jnp.int32))
        ok = (
            pgate
            & (live & in_row)[:, None]
            & jnp.all(win[:, None, :] == plan.patterns[None, :, :], axis=-1)
        )
        oks.append(ok)
        sts.append(jnp.where(live & in_row, st, n))
    ok_all = jnp.concatenate(oks)        # (noff_used * nb, P)
    st_all = jnp.concatenate(sts)        # (noff_used * nb,)
    b_all = jnp.concatenate([bvec] * noff_used)
    return ok_all, b_all, st_all


@_stage("verify")
def _c_verify_csr(
    index, plan, ht, cand, stride, noff_used, budget, end_min=None
):
    """Bounded CSR verify for bucketed EPSMc plans (DESIGN.md §14).

    The flat payload bitmask tests every candidate block against all P
    patterns at all < stride offsets — O(nb * P * stride) compares and an
    O(2^k * P / 32) bitmask that reaches ~800 MB at P = 50k.  Here a
    candidate block's wide fingerprint names a CSR slot whose entries are
    exactly the (pattern, offset) pairs that registered it, so the verify
    is O(nb * slot_max * m) with slot_max a COMPILE-TIME constant:
    adversarial text can flood candidates but cannot change the per-block
    bound.  Each true occurrence is tested at exactly one (block, entry)
    pair — its unique dedup block and its registered offset — so counts
    stay bit-identical to the flat path.

    Returns per-entry (ok, pid, b, start) vectors of length
    slot_max * nb for scatter-add/scatter-max joins.
    """
    B, n = index.packed.shape
    m = plan.m
    G = cand.shape[1]
    front = noff_used - 1
    span = front + m
    with jax.named_scope("compact"):
        (flat,) = jnp.nonzero(cand.reshape(-1), size=budget, fill_value=B * G)
        live = flat < B * G
        flat = jnp.where(live, flat, 0)
        bvec = flat // G
        bsel = (flat % G) * stride  # inspected block start
        t_pad = jnp.pad(index.text, ((0, 0), (front, span)))
        # (nb, span)
        rows = t_pad[bvec[:, None], bsel[:, None] + jnp.arange(span)]
    h = ht.reshape(-1)[flat]
    base = plan.c_slot_off[h]
    cnt = plan.c_slot_off[h + 1] - base
    E = plan.c_entry_pid.shape[0]
    oks, pids, sts = [], [], []
    for j in range(plan.slot_max):
        idx = jnp.minimum(base + j, E - 1)
        e_live = live & (j < cnt)
        pid = plan.c_entry_pid[idx]
        off = plan.c_entry_off[idx]
        pat = plan.c_entry_pat[idx]  # (nb, m)
        win = jnp.take_along_axis(
            rows, (front - off)[:, None] + jnp.arange(m)[None, :], axis=1
        )
        st = bsel - off
        in_row = (st >= 0) & (st <= index.lengths[bvec] - m)
        if end_min is not None:
            in_row = in_row & (
                st + (m - 1) >= jnp.asarray(end_min, jnp.int32)
            )
        ok = e_live & in_row & jnp.all(win == pat, axis=-1)
        oks.append(ok)
        pids.append(pid)
        sts.append(jnp.where(ok, st, n))
    ok_all = jnp.concatenate(oks)        # (slot_max * nb,)
    pid_all = jnp.concatenate(pids)
    st_all = jnp.concatenate(sts)
    b_all = jnp.concatenate([bvec] * plan.slot_max)
    return ok_all, pid_all, b_all, st_all


def _match_group_c(
    index: TextIndex,
    plan: PatternPlan,
    bank: Optional[FingerprintBank] = None,
    end_min=None,
) -> jnp.ndarray:
    del bank  # keyed by aligned block fingerprints, not window fingerprints
    B, n = index.packed.shape
    P = plan.n_patterns
    if index.block_fp.shape[1] == 0:
        return _dense_c(index, plan, end_min)
    ht, cand, stride, noff_used, budget = _c_candidates(index, plan)

    if plan.c_slot_off is not None:
        # bucketed: the bounded CSR verify IS the overflow path too — run
        # on every inspected block (budget B * G) instead of densifying,
        # keeping the adversarial bound O(B * G * slot_max * m)
        def sparse_csr(_):
            ok, pid, b_all, st_all = _c_verify_csr(
                index, plan, ht, cand, stride, noff_used, budget, end_min
            )
            out = jnp.zeros((B, P, n + 1), jnp.bool_)
            out = out.at[b_all, pid, st_all].max(ok, mode="drop")
            return out[:, :, :n]

        def full_csr(_):
            ok, pid, b_all, st_all = _c_verify_csr(
                index, plan, ht, jnp.ones_like(cand), stride, noff_used,
                cand.size, end_min,
            )
            out = jnp.zeros((B, P, n + 1), jnp.bool_)
            out = out.at[b_all, pid, st_all].max(ok, mode="drop")
            return out[:, :, :n]

        return lax.cond(
            cand.sum(dtype=jnp.int32) <= budget, sparse_csr, full_csr, None
        )

    def sparse(_):
        ok, b_all, st_all = _c_verify(
            index, plan, ht, cand, stride, noff_used, budget, end_min
        )
        out = jnp.zeros((B, P, n + 1), jnp.bool_)
        out = out.at[
            b_all[:, None, None], jnp.arange(P)[None, None, :], st_all[:, None, None]
        ].max(ok[:, None, :], mode="drop")
        return out[:, :, :n]

    return lax.cond(
        cand.sum(dtype=jnp.int32) <= budget,
        sparse,
        lambda _: _dense_c(index, plan, end_min),
        None,
    )


def _count_group_c(
    index: TextIndex,
    plan: PatternPlan,
    bank: Optional[FingerprintBank] = None,
    end_min=None,
) -> jnp.ndarray:
    del bank  # keyed by aligned block fingerprints, not window fingerprints
    B = index.batch
    if index.block_fp.shape[1] == 0:
        return _dense_c(index, plan, end_min).sum(-1, dtype=jnp.int32)
    ht, cand, stride, noff_used, budget = _c_candidates(index, plan)

    if plan.c_slot_off is not None:
        def sparse_csr(_):
            ok, pid, b_all, _ = _c_verify_csr(
                index, plan, ht, cand, stride, noff_used, budget, end_min
            )
            counts = jnp.zeros((B, plan.n_patterns), jnp.int32)
            return counts.at[b_all, pid].add(
                ok.astype(jnp.int32), mode="drop"
            )

        def full_csr(_):
            ok, pid, b_all, _ = _c_verify_csr(
                index, plan, ht, jnp.ones_like(cand), stride, noff_used,
                cand.size, end_min,
            )
            counts = jnp.zeros((B, plan.n_patterns), jnp.int32)
            return counts.at[b_all, pid].add(
                ok.astype(jnp.int32), mode="drop"
            )

        return lax.cond(
            cand.sum(dtype=jnp.int32) <= budget, sparse_csr, full_csr, None
        )

    def sparse(_):
        ok, b_all, _ = _c_verify(
            index, plan, ht, cand, stride, noff_used, budget, end_min
        )
        counts = jnp.zeros((B, plan.n_patterns), jnp.int32)
        return counts.at[b_all].add(ok.astype(jnp.int32), mode="drop")

    return lax.cond(
        cand.sum(dtype=jnp.int32) <= budget,
        sparse,
        lambda _: _dense_count(index, plan, _dense_c, end_min),
        None,
    )


_MATCH = {"a": _match_group_a, "b": _match_group_b, "c": _match_group_c}
_COUNT = {
    "a": lambda idx, plan, bank=None, end_min=None: _match_group_a(
        idx, plan, None, end_min
    ).sum(-1, dtype=jnp.int32),
    "b": _count_group_b,
    "c": _count_group_c,
}


# ---------------------------------------------------------------------------
# Public joins: one dispatch for P patterns x B texts
# ---------------------------------------------------------------------------

def _effective_k(plan: PatternPlan, k: Optional[int]) -> int:
    """Per-plan mismatch budget: an explicit k overrides; None means "what
    the plan was compiled for" (0 for exact plans), so fuzzy-compiled plans
    flow through existing call sites (serving, blocklist) unchanged."""
    return plan.k if k is None else int(k)


def match_many(
    index: TextIndex,
    plans: Sequence[PatternPlan],
    *,
    k: Optional[int] = None,
    end_min: Optional[int] = None,
) -> jnp.ndarray:
    """bool[B, P_total, n] match-start masks, rows in plan-concatenated order
    (use :func:`plan_order` to map back to the original pattern order) — the
    engine's one-dispatch join of a TextIndex with compiled plans
    (DESIGN.md §7).

    ``k`` is the mismatch budget (repro.approx): mask[b, p, i] is True iff
    the m-byte window at i differs from pattern p in at most k bytes.  k=0
    (or exact-compiled plans with k=None) runs the exact matchers unchanged —
    bit-identical to the pre-approx engine.

    ``end_min`` keeps only occurrences ENDING at position >= end_min (the
    streaming seam gate — DESIGN.md §11): equivalent to subtracting a
    prefix-window scan, fused into the candidate gates of every regime.

    A text of at least SEGMENT_BYTES + overlap columns (n a multiple of
    the beta block) is matched window by window (:func:`_match_segmented`)."""
    if not plans:
        return jnp.zeros((index.batch, 0, index.n), jnp.bool_)
    if _segmented(index, plans):
        return _match_segmented(index, plans, k=k, end_min=end_min)
    return _match_whole(index, plans, k=k, end_min=end_min)


def _match_whole(
    index: TextIndex,
    plans: Sequence[PatternPlan],
    *,
    k: Optional[int],
    end_min,
) -> jnp.ndarray:
    bank = FingerprintBank(index.packed)
    outs = []
    for p in plans:
        kk = _effective_k(p, k)
        if kk == 0:
            outs.append(_MATCH[p.regime](index, p, bank, end_min))
        else:
            from repro.approx import counting

            outs.append(counting.match_group_approx(index, p, kk, end_min))
    return jnp.concatenate(outs, axis=1)


def count_many(
    index: TextIndex,
    plans: Sequence[PatternPlan],
    *,
    k: Optional[int] = None,
    end_min: Optional[int] = None,
    shared: bool = True,
) -> jnp.ndarray:
    """int32[B, P_total] occurrence counts — the reduced hot path: the
    exact and relaxed-gated paths never materialize the (B, P, n) mask.
    ``k`` as in :func:`match_many`; note the k > 0 DENSE path (small P,
    saturated or absent relaxed LUT, or candidate overflow) does build the
    (B, P, n) mismatch mask before reducing.

    All groups draw their window fingerprints from ONE FingerprintBank
    prefix accumulation, and every sparse-eligible EPSMb group additionally
    shares a single candidate compaction (_count_groups_b_shared) — G length
    groups cost one pass over the packed view, not G (DESIGN.md §9).  The
    shared pass runs even for a single eligible group so mixed plan sets
    never silently fall back to the slower per-group compaction.

    ``end_min`` as in :func:`match_many` (streaming seam gate).

    A text of at least SEGMENT_BYTES + overlap columns (n a multiple of
    the beta block) is counted window by window (:func:`_count_segmented`).

    ``shared=False`` disables the shared-compaction routing and counts every
    group through its own per-group matcher (_COUNT dispatch) — the
    pre-fusion per-group reference path benchmarks and oracle tests pin
    against."""
    if not plans:
        return jnp.zeros((index.batch, 0), jnp.int32)
    if _segmented(index, plans):
        return _count_segmented(
            index, plans, k=k, end_min=end_min, shared=shared
        )
    return _count_whole(index, plans, k=k, end_min=end_min, shared=shared)


def _segmented(index: TextIndex, plans: Sequence[PatternPlan]) -> bool:
    """Whether count_many/match_many scan ``index`` window by window: its
    width is a whole number of beta blocks (so every window starts on a
    block) and holds at least one full window.  This covers the stream
    scanner's chunks as well as a resident corpus."""
    n = index.n
    return n % EPSMC_BETA == 0 and n >= SEGMENT_BYTES + _segment_overlap(plans)


def _segment_overlap(plans: Sequence[PatternPlan]) -> int:
    """Carried bytes between windows: max_m - 1, rounded up to the beta
    block so every window starts on a global block boundary."""
    return -(-(max(p.m for p in plans) - 1) // EPSMC_BETA) * EPSMC_BETA


@_stage("segment")
def _window_index(index: TextIndex, st, width: int, cap) -> TextIndex:
    """Columns [st, st + width) of a resident index (``st`` traced, a
    multiple of EPSMC_BETA), each row's length cut at ``cap`` columns."""
    B = index.batch
    return TextIndex(
        packed=lax.dynamic_slice(index.packed, (0, st), (B, width)),
        block_fp=lax.dynamic_slice(
            index.block_fp, (0, st // EPSMC_BETA), (B, width // EPSMC_BETA)
        ),
        lengths=jnp.clip(index.lengths - st, 0, cap),
    )


def _count_segmented(
    index: TextIndex,
    plans: Sequence[PatternPlan],
    *,
    k: Optional[int],
    end_min,
    shared: bool,
) -> jnp.ndarray:
    """count_many as a lax.scan over windows of SEGMENT_BYTES new
    bytes.  Window i holds [a_i - ov, a_i + S) with a_i = i * S (the first
    is clamped to start at 0, the last to end at n) and counts, through the
    end_min seam gate, the occurrences that END in [a_i, a_i + S): each
    exactly once, as in the streaming scanner (DESIGN.md §11).  The windows
    are slices of the resident packed view and block fingerprints; nothing
    is recomputed."""
    B, n = index.batch, index.n
    S = SEGMENT_BYTES
    ov = _segment_overlap(plans)
    W = S + ov
    P = sum(p.n_patterns for p in plans)

    def window(acc, i):
        a = i * S
        st = jnp.clip(a - ov, 0, n - W)
        # the first window starts at 0, not a_0 - ov: stop it at S too
        seg = _window_index(index, st, W, jnp.minimum(W, a + S - st))
        lo = a if end_min is None else jnp.maximum(a, end_min)
        c = _count_whole(seg, plans, k=k, end_min=lo - st, shared=shared)
        return acc + c, None

    acc, _ = lax.scan(
        window, jnp.zeros((B, P), jnp.int32),
        jnp.arange(-(-n // S), dtype=jnp.int32),
    )
    return acc


def _match_segmented(
    index: TextIndex,
    plans: Sequence[PatternPlan],
    *,
    k: Optional[int],
    end_min,
) -> jnp.ndarray:
    """match_many as a loop over windows of SEGMENT_BYTES starts.
    Window i holds [st_i, st_i + S + ov) with st_i = min(a_i, n - S - ov)
    and a_i = min(i * S, n - S), and writes its mask columns for the
    starts in [a_i, a_i + S); every occurrence starting there fits in the
    window, since ov >= max_m - 1.  Where the last window's columns
    overlap the one before, both write the same values."""
    B, n = index.batch, index.n
    S = SEGMENT_BYTES
    ov = _segment_overlap(plans)
    W = S + ov

    def window(i, out):
        a = jnp.minimum(i * S, n - S)
        st = jnp.minimum(a, n - W)
        seg = _window_index(index, st, W, W)
        lo = None if end_min is None else end_min - st
        mask = _match_whole(seg, plans, k=k, end_min=lo)
        cols = lax.dynamic_slice_in_dim(mask, a - st, S, axis=2)
        return lax.dynamic_update_slice_in_dim(out, cols, a, axis=2)

    P = sum(p.n_patterns for p in plans)
    out = jnp.zeros((B, P, n), jnp.bool_)
    return lax.fori_loop(0, -(-n // S), window, out)


def _count_whole(
    index: TextIndex,
    plans: Sequence[PatternPlan],
    *,
    k: Optional[int],
    end_min,
    shared: bool,
) -> jnp.ndarray:
    bank = FingerprintBank(index.packed)
    outs: List[Any] = [None] * len(plans)
    # Exact EPSMb groups on the sparse path: count them together through
    # the shared candidate pass (one fingerprint traversal + one compaction
    # for all of them — see _count_groups_b_shared).  A single eligible
    # group still routes here: the shared pass degenerates gracefully and
    # keeps the dispatch count flat across mixed plan sets.
    shared_idx = [
        i
        for i, p in enumerate(plans)
        if shared
        and _effective_k(p, k) == 0
        and p.regime == "b"
        and _sparse_b_eligible(index, p)
    ]
    if len(shared_idx) >= 1:
        joint = _count_groups_b_shared(
            index, [plans[i] for i in shared_idx], bank, end_min
        )
        col = 0
        for i in shared_idx:
            P = plans[i].n_patterns
            outs[i] = joint[:, col : col + P]
            col += P
    for i, p in enumerate(plans):
        if outs[i] is not None:
            continue
        kk = _effective_k(p, k)
        if kk == 0:
            outs[i] = _COUNT[p.regime](index, p, bank, end_min)
        else:
            from repro.approx import counting

            outs[i] = counting.count_group_approx(index, p, kk, bank, end_min)
    return jnp.concatenate(outs, axis=1)


def route_probe(
    index: TextIndex,
    plans: Sequence[PatternPlan],
    *,
    k: Optional[int] = None,
    end_min: Optional[int] = None,
    shared: bool = True,
    recorder: Optional[Recorder] = None,
) -> dict:
    """Report WHICH route count_many would take for this (index, plans)
    pair without running the verification — the observability half of the
    dictionary-scale dispatcher (DESIGN.md §14, BENCH_dictionary's "route"
    column).

    Uses the same _shared_b_route decision and the same union-block
    measurement as _count_groups_b_shared, so the probe and the dispatcher
    cannot disagree.  Emits a ``fallback_route`` event on ``recorder``
    (repro.obs) with the chosen route, measured candidate blocks, budget,
    and density.  Host-synchronizing (materializes the union popcount) —
    a diagnostic, not a hot-path call.
    """
    rec = _DEFAULT_REC if recorder is None else recorder
    B, n = index.packed.shape
    C = CAND_BLOCK
    nblk = -(-n // C)
    shared_plans = [
        p
        for p in plans
        if shared
        and _effective_k(p, k) == 0
        and p.regime == "b"
        and _sparse_b_eligible(index, p)
    ]
    if not shared_plans:
        info = {
            "route": "per_group",
            "kind": "none",
            "blocks": 0,
            "budget": 0,
            "total_blocks": B * nblk,
            "density": 0.0,
            "rho": 0.0,
            "static": True,
        }
        rec.event("fallback_route", **info)
        return info
    route = _shared_b_route(index, shared_plans)
    blocks = 0
    if not route.static_fallback:
        bank = FingerprintBank(index.packed)
        union = None
        for p in shared_plans:
            h = bank.window_fp(p.m, p.kbits)
            cand = p.lut_any[h] & _valid_starts(index, p.m, end_min)
            blk = (
                jnp.pad(cand, ((0, 0), (0, nblk * C - n)))
                .reshape(B, nblk, C)
                .any(-1)
            )
            union = blk if union is None else union | blk
        blocks = int(union.sum(dtype=jnp.int32))
    overflow = route.static_fallback or blocks > route.budget
    info = {
        "route": route.kind if overflow else "sparse",
        "kind": route.kind,
        "blocks": blocks,
        "budget": route.budget,
        "exp_blocks": route.exp_blocks,
        "total_blocks": B * nblk,
        "density": blocks / float(max(1, B * nblk)),
        "rho": route.rho,
        "static": bool(route.static_fallback),
    }
    rec.event("fallback_route", **info)
    return info


def any_many(
    index: TextIndex, plans: Sequence[PatternPlan], *, k: Optional[int] = None
) -> jnp.ndarray:
    """bool[B, P_total] — does pattern p occur anywhere in text b?"""
    return count_many(index, plans, k=k) > 0


def any_hit(
    index: TextIndex, plans: Sequence[PatternPlan], *, k: Optional[int] = None
) -> jnp.ndarray:
    """bool[B] — does ANY pattern occur in text b?  (blocklist predicate)"""
    return any_many(index, plans, k=k).any(axis=-1)


@functools.partial(jax.jit, static_argnames=("k",))
def match_many_jit(
    index: TextIndex, plans: Tuple[PatternPlan, ...], *, k: Optional[int] = None
) -> jnp.ndarray:
    """Module-level jitted :func:`match_many`: callers that share this entry
    point share one XLA executable cache keyed on (index shapes, plan aux
    statics, k) — canonical plans make that key content-independent
    (DESIGN.md §15)."""
    return match_many(index, plans, k=k)


@functools.partial(jax.jit, static_argnames=("k",))
def count_many_jit(
    index: TextIndex, plans: Tuple[PatternPlan, ...], *, k: Optional[int] = None
) -> jnp.ndarray:
    """Module-level jitted :func:`count_many` — see :func:`match_many_jit`
    for the executable-cache sharing contract."""
    return count_many(index, plans, k=k)


@jax.jit
def _blocked_jit(texts: jnp.ndarray, lengths: jnp.ndarray, plans) -> jnp.ndarray:
    """One fused dispatch: build the TextIndex AND run the blocklist check."""
    return any_hit(build_index(texts, lengths), plans)


def blocked(texts, lengths, plans) -> jnp.ndarray:
    """bool[B] blocklist predicate over a padded (B, L) batch of documents."""
    return _blocked_jit(jnp.asarray(texts), jnp.asarray(lengths), tuple(plans))
