"""Shared-text batched engine (core/engine.py): cross-checks against the
per-pattern single-text scan, ragged-padding semantics, and the serving
stop-scanner's one-dispatch-per-step contract."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import baselines, engine, epsm
from repro.core.multipattern import PatternSet, count_multi, find_multi

from conftest import make_text


def _mixed_patterns(rng, text, lengths):
    """Half extracted from the text (guaranteed hits), half random."""
    pats = []
    for m in lengths:
        s = rng.randint(0, len(text) - m + 1)
        pats.append(text[s : s + m].copy())
        pats.append(rng.randint(0, 5, size=m).astype(np.uint8))
    return pats


def test_match_many_mixed_lengths_vs_find(rng):
    """All three regimes in one plan set, cross-checked against epsm.find."""
    t = make_text(rng, 2000, 4)
    pats = _mixed_patterns(rng, t, (1, 2, 3, 5, 8, 12, 15, 16, 24, 40))
    plans = engine.compile_patterns(pats)
    order = engine.plan_order(plans)
    assert sorted(order.tolist()) == list(range(len(pats)))
    idx = engine.build_index(t)
    mask = np.asarray(engine.match_many_jit(idx, plans))
    counts = np.asarray(engine.count_many_jit(idx, plans))
    assert mask.shape == (1, len(pats), len(t))
    for row, pid in enumerate(order):
        want = np.asarray(epsm.find(t, pats[pid]))
        np.testing.assert_array_equal(mask[0, row], want, err_msg=f"pattern {pid}")
        assert counts[0, row] == want.sum()


def test_match_many_batched_ragged_padding(rng):
    """Batched texts with ragged true lengths: verdicts must match the
    per-document scan, and padding must never produce a match."""
    docs = [make_text(rng, n, 4) for n in (513, 100, 7, 256, 1)]
    pats = _mixed_patterns(rng, docs[0], (2, 6, 8, 20))
    plans = engine.compile_patterns(pats)
    order = engine.plan_order(plans)
    idx = engine.build_index(docs)  # pads to the longest doc
    assert idx.n == 513
    mask = np.asarray(engine.match_many_jit(idx, plans))
    for bi, doc in enumerate(docs):
        assert not mask[bi, :, len(doc) :].any(), "match inside padding"
        for row, pid in enumerate(order):
            np.testing.assert_array_equal(
                mask[bi, row, : len(doc)],
                baselines.naive_np(doc, pats[pid]),
                err_msg=f"doc {bi} pattern {pid}",
            )


def test_no_match_across_document_boundary(rng):
    """A pattern straddling two adjacent rows of the batch matrix must NOT
    match: each row is an independent document."""
    a = make_text(rng, 64, 4)
    b = make_text(rng, 64, 4)
    straddle = np.concatenate([a[-4:], b[:4]])  # exists only across the seam
    # make sure it doesn't accidentally occur inside either doc
    if baselines.naive_np(a, straddle).any() or baselines.naive_np(b, straddle).any():
        pytest.skip("straddle pattern occurs naturally (rng collision)")
    plans = engine.compile_patterns([straddle])
    idx = engine.build_index([a, b])
    assert not np.asarray(engine.match_many_jit(idx, plans)).any()
    # concatenated as ONE document it must match at the seam
    idx2 = engine.build_index(np.concatenate([a, b]))
    mask = np.asarray(engine.match_many_jit(idx2, plans))[0, 0]
    assert mask[60]


def test_engine_equals_vmap_multipattern(rng):
    """find_multi/count_multi (engine-backed) == the vmap baseline."""
    from repro.core.multipattern import count_multi_vmap, find_multi_vmap

    t = make_text(rng, 4096, 8)
    for m in (4, 8, 13):
        starts = rng.randint(0, len(t) - m + 1, 6)
        ps = np.stack([t[s : s + m] for s in starts])
        np.testing.assert_array_equal(
            np.asarray(find_multi(t, ps)), np.asarray(find_multi_vmap(t, ps))
        )
        np.testing.assert_array_equal(
            np.asarray(count_multi(t, ps)), np.asarray(count_multi_vmap(t, ps))
        )


def test_patternset_blocked_batch(rng):
    docs = [make_text(rng, 300, 4) for _ in range(8)]
    bad = b"\x01\x02\x03\x01\x02\x03\x00"
    planted = {2, 5}
    for i in planted:
        docs[i][100:107] = np.frombuffer(bad, np.uint8)
    ps = PatternSet([bad, b"\x09\x09"])
    idx = ps.index(docs)
    hits = np.asarray(jax.device_get(engine.any_hit(idx, ps.plans)))
    assert set(np.nonzero(hits)[0].tolist()) == planted
    counts = np.asarray(ps.count_each(docs[2]))
    assert counts.shape == (2,)


def test_count_many_shared_b_groups(rng, monkeypatch):
    """>= 2 eligible EPSMb groups count through the shared candidate pass
    (one union compaction for all groups — engine._count_groups_b_shared);
    results must match the per-pattern reference exactly, including a group
    with non-distinct fingerprints (duplicated pattern)."""
    monkeypatch.setattr(engine, "SPARSE_B_MIN_ELEMS", 0)
    t = make_text(rng, 4096, 4)
    pats = []
    for m in (5, 8, 12, 15):
        for _ in range(4):
            s = rng.randint(0, len(t) - m + 1)
            pats.append(t[s : s + m].copy())
    pats.append(pats[4].copy())  # duplicate: m=8 group loses `distinct`
    plans = engine.compile_patterns(pats)
    assert sum(
        1 for p in plans if p.regime == "b" and engine._sparse_b_eligible(
            engine.build_index(t), p
        )
    ) >= 2
    idx = engine.build_index(t)
    counts = np.asarray(engine.count_many(idx, plans))
    for row, pid in enumerate(engine.plan_order(plans)):
        want = int(np.asarray(epsm.find(t, pats[pid])).sum())
        assert counts[0, row] == want, f"pattern {pid}"


def test_count_many_single_eligible_b_group_uses_shared(rng, monkeypatch):
    """Regression (ISSUE 6 satellite): exactly ONE sparse-eligible EPSMb
    group in a mixed set must still route through _count_groups_b_shared —
    previously the `>= 2` routing threshold silently sent mixed sets down
    the slow per-group path.  Counts stay exact, and the dense lax.cond
    fallback inside the shared pass must cover the 1-group case too (checked
    here via the all-same-byte saturating text)."""
    monkeypatch.setattr(engine, "SPARSE_B_MIN_ELEMS", 0)
    calls = []
    orig = engine._count_groups_b_shared

    def spy(index, plans_, bank, end_min=None):
        calls.append(len(plans_))
        return orig(index, plans_, bank, end_min)

    monkeypatch.setattr(engine, "_count_groups_b_shared", spy)
    t = make_text(rng, 4096, 4)
    # a + b + c: the b group needs >= 4 patterns to be sparse-eligible, the
    # a/c groups never are — exactly one eligible group total
    pats = [t[7:9].copy(), t[90:114].copy()]
    for s in (50, 200, 600, 1100):
        pats.append(t[s : s + 8].copy())
    plans = engine.compile_patterns(pats)
    assert sum(
        1 for p in plans
        if p.regime == "b" and engine._sparse_b_eligible(engine.build_index(t), p)
    ) == 1
    idx = engine.build_index(t)
    counts = np.asarray(engine.count_many(idx, plans))
    assert calls == [1]
    for row, pid in enumerate(engine.plan_order(plans)):
        want = int(np.asarray(epsm.find(t, pats[pid])).sum())
        assert counts[0, row] == want, f"pattern {pid}"
    # saturating text: the single group's candidates overflow the budget and
    # the dense lax.cond branch inside the shared pass must stay exact
    calls.clear()
    tz = np.zeros(2048, np.uint8)
    pz = [np.zeros(8, np.uint8)] * 4
    plans_z = engine.compile_patterns(pz)
    idx_z = engine.build_index(tz)
    counts_z = np.asarray(engine.count_many(idx_z, plans_z))
    assert calls == [1]
    for row, pid in enumerate(engine.plan_order(plans_z)):
        want = baselines.naive_np(tz, pz[pid]).sum()
        assert counts_z[0, row] == want, f"pattern {pid}"


def test_count_many_shared_b_groups_overflow_dense(rng, monkeypatch):
    """Adversarial density through the SHARED path: all-same-byte text makes
    every block a union candidate, the budget overflows, and the dense
    fallback must keep every group's counts exact."""
    monkeypatch.setattr(engine, "SPARSE_B_MIN_ELEMS", 0)
    t = np.zeros(2048, np.uint8)
    pats = [np.zeros(8, np.uint8)] * 4 + [np.zeros(12, np.uint8)] * 4
    plans = engine.compile_patterns(pats)
    idx = engine.build_index(t)
    counts = np.asarray(engine.count_many(idx, plans))
    for row, pid in enumerate(engine.plan_order(plans)):
        want = baselines.naive_np(t, pats[pid]).sum()
        assert counts[0, row] == want, f"pattern {pid}"


def test_adversarial_density_falls_back_dense(rng):
    """All-same-byte text x matching pattern: every position is a candidate;
    the budget overflows and the dense branch must keep the result exact."""
    t = np.zeros(8192, np.uint8)
    pats = [np.zeros(8, np.uint8), np.zeros(24, np.uint8)]
    plans = engine.compile_patterns(pats)
    idx = engine.build_index(t)
    mask = np.asarray(engine.match_many_jit(idx, plans))
    counts = np.asarray(engine.count_many_jit(idx, plans))
    order = engine.plan_order(plans)
    for row, pid in enumerate(order):
        want = baselines.naive_np(t, pats[pid])
        np.testing.assert_array_equal(mask[0, row], want)
        assert counts[0, row] == want.sum()


def test_multipattern_kernel_long_patterns(rng):
    """m >= 16: the kernel must disable the window-fingerprint gate (the
    compiled plan's LUT is block-keyed there) and still verify exactly."""
    from repro.kernels.multipattern import multipattern

    t = make_text(rng, 3000, 4)
    for m in (16, 24, 36):
        ps = np.stack([t[50 : 50 + m], t[1000 : 1000 + m]])
        got = np.asarray(multipattern(t, ps))
        for i in range(2):
            np.testing.assert_array_equal(
                got[i], baselines.naive_np(t, ps[i]), err_msg=f"m={m} p={i}"
            )


def test_stop_scanner_one_dispatch_per_step():
    """Serving contract: exactly one jitted stop-scan dispatch per decode
    step, independent of batch size and stop-string count."""
    from repro.serve.engine import StopScanner

    streams = [b"hello stop here", b"xxxxxxxxxxxxxxx", b"stopstopstopsto"]
    stops = [b"stop", b"here", b"xx", b"\x00\x00\x00"]
    B, steps = len(streams), len(streams[0])
    scanner = StopScanner(stops, B, steps)
    first_hit = {}
    for step in range(steps):
        toks = np.asarray([s[step] for s in streams], np.int32)
        hits = scanner.scan(toks, step)
        assert hits.shape == (B, len(stops))
        for b in range(B):
            for si in np.nonzero(hits[b])[0]:
                first_hit.setdefault((b, si), step)
    assert scanner.dispatch_count == steps  # 1 per step, not B*stops per step
    # b"stop" ends at step 9 in stream 0; b"here" at 14; b"xx" at 1 in stream 1
    assert first_hit[(0, 0)] == 9
    assert first_hit[(0, 1)] == 14
    assert first_hit[(1, 2)] == 1
    assert first_hit[(2, 0)] == 3
    # the zero-byte stop must NOT fire from the uninitialized ring apron
    assert (2, 3) not in first_hit and (0, 3) not in first_hit


def _scan_stream(stops, stream, k=0):
    """Drive a 1-stream StopScanner; returns {stop_index: [hit steps]}."""
    from repro.serve.engine import StopScanner

    sc = StopScanner(stops, 1, len(stream), k=k)
    hits = {}
    for step in range(len(stream)):
        row = sc.scan(np.asarray([stream[step]], np.int32), step)[0]
        for si in np.nonzero(row)[0]:
            hits.setdefault(int(si), []).append(step)
    return hits, sc


def test_stop_scanner_ring_wraparound():
    """The tail ring is O(window) and slides at step % W == 0: stop
    occurrences spanning a wrap-around point (bytes written before AND after
    a slide) must still be reported, at every wrap over a long stream."""
    stop = b"abcd"  # W = 4: wraps at steps 4, 8, 12, ...
    # occurrences at starts 2 (spans the step-4 slide), 6 (spans step-8),
    # 11 (spans the step-12 slide at its last byte), and 16 (aligned)
    stream = b"xyabcdabcd_abcd_abcd"
    hits, sc = _scan_stream([stop], stream)
    assert sc.buf.shape == (1, 2 * len(stop) - 1)  # O(W), not O(max_new)
    want = [
        e for e in range(len(stream))
        if stream[e - 3 : e + 1] == stop and e >= 3
    ]
    assert hits.get(0, []) == want == [5, 9, 14, 19]
    assert sc.dispatch_count == len(stream)


def test_stop_scanner_two_stops_same_step():
    """Two stop sequences ending on the same decode step must BOTH be
    reported in that step's hit matrix (ties are not swallowed)."""
    stops = [b"abc", b"xbc", b"bc", b"zzzz"]
    stream = b"__abc__xbc"
    hits, _ = _scan_stream(stops, stream)
    # step 4 completes "abc" and "bc"; step 9 completes "xbc" and "bc"
    assert hits.get(0, []) == [4]
    assert hits.get(1, []) == [9]
    assert hits.get(2, []) == [4, 9]
    assert 3 not in hits


def test_stop_scanner_wraparound_exhaustive(rng):
    """Randomized cross-check: every (stop, stream) hit over a stream many
    times longer than the window agrees with the naive scan, so no boundary
    (apron edge, slide point, buffer end) drops or invents a match."""
    sigma = 3
    stops = [bytes(rng.randint(0, sigma, size=m).astype(np.uint8))
             for m in (2, 3, 5)]
    stream = bytes(rng.randint(0, sigma, size=64).astype(np.uint8))
    hits, _ = _scan_stream(stops, stream)
    for si, stop in enumerate(stops):
        want = [
            e for e in range(len(stream))
            if e >= len(stop) - 1
            and stream[e - len(stop) + 1 : e + 1] == stop
        ]
        assert hits.get(si, []) == want, f"stop {si}"


@pytest.mark.parametrize(
    "segment,n,length,end_min,k",
    [
        (1 << 10, 1 << 14, 1 << 14, None, 0),          # every window full
        (1 << 12, 1 << 14, (1 << 14) - 13, 777, 0),    # ragged length, gate
        (4096 + 8, 1 << 14, 1 << 14, None, 0),         # n % S != 0
        # a stream chunk: prev_ov carried bytes + chunk, n % S != 0, the
        # seam gate at prev_ov (core/stream.py's fused count step)
        (1 << 12, 3 * 4096 + 1000, 3 * 4096 + 1000 - 5, 32, 0),
        (1 << 12, 3 * 4096 + 1000, 3 * 4096 + 1000 - 5, 32, 1),  # k > 0
        (1 << 10, 1 << 14, 1 << 14, None, 1),
    ],
)
def test_windowed_scan_equals_whole_text(
    rng, monkeypatch, segment, n, length, end_min, k
):
    """Texts longer than SEGMENT_BYTES are counted and matched window by
    window over the resident index; every occurrence, including those
    across window seams and at the clamped first and last windows, counts
    once, as in the one-pass scan, exact and with a mismatch budget."""
    t = make_text(rng, n, 4)
    pats = _mixed_patterns(rng, t, (2, 3, 5, 8, 12, 16, 20, 32))
    pats += [t[s : s + 8].copy() for s in range(0, n - 8, 997)]  # P >= 8 group
    plans = engine.compile_patterns(pats, k=k)
    idx = engine.build_index(t[None], np.array([length], np.int32))
    whole_c = np.asarray(engine.count_many(idx, plans, end_min=end_min))
    whole_m = np.asarray(engine.match_many(idx, plans, end_min=end_min))
    assert whole_c.sum() > 0
    monkeypatch.setattr(engine, "SEGMENT_BYTES", segment)
    np.testing.assert_array_equal(
        np.asarray(engine.count_many(idx, plans, end_min=end_min)), whole_c
    )
    np.testing.assert_array_equal(
        np.asarray(engine.match_many(idx, plans, end_min=end_min)), whole_m
    )
    # counts and masks agree with each other, not only with themselves
    np.testing.assert_array_equal(whole_m.sum(axis=2), whole_c)


def test_text_index_derives_text_from_packed(rng):
    """The index keeps no text row: its bytes are the low bytes of the
    packed view, and the block fingerprints equal hash_blocks of the
    reshaped (n // beta, beta) blocks."""
    from repro.core.packing import fingerprint_weights, hash_blocks

    t = make_text(rng, 1003, 256)
    idx = engine.build_index(t)
    assert len(jax.tree_util.tree_leaves(idx)) == 3
    np.testing.assert_array_equal(np.asarray(idx.text)[0], t)
    nb = len(t) // engine.EPSMC_BETA
    blocks = jnp.asarray(t[: nb * engine.EPSMC_BETA].reshape(nb, -1))
    want = hash_blocks(
        blocks, fingerprint_weights(engine.EPSMC_BETA), engine.ENGINE_KBITS
    )
    np.testing.assert_array_equal(
        np.asarray(idx.block_fp)[0], np.asarray(want)
    )
