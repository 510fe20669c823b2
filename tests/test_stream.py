"""Streaming scan engine (core/stream.py): seam-equivalence against the
resident engine, the one-dispatch-per-chunk and bounded-device-memory
contracts, compressed (gzip/zstd) sources, the mid-stream prefix/start
injection the sharded scanner builds on, and the streaming consumers (epsm
stream= hatch, blocklist pipeline oversize documents, plan-cache hot key,
lazy stop-scanner sync)."""

import gzip
import io

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import engine, epsm
from repro.core.stream import Compressed, StreamScanner, find_stream, stream_count

from conftest import make_text

LENGTHS = (2, 4, 8, 13, 16, 32)
# few distinct chunk sizes -> few jit traces; odd values put the seams at
# unaligned, mid-beta-block offsets after the scanner's beta rounding
CHUNKS = (96, 251, 1000)


def _patterns(rng, text, k):
    """One extracted (guaranteed-hit) pattern per length, plus one random."""
    pats = []
    for m in LENGTHS:
        s = rng.randint(0, len(text) - m + 1)
        pats.append(text[s : s + m].copy())
        pats.append(rng.randint(0, 5, size=m).astype(np.uint8))
    return pats


def test_seam_equivalence_random_boundaries(rng):
    """Property suite: for random texts split at random chunk boundaries,
    streaming counts AND positions equal the whole-text resident engine for
    m in {2, 4, 8, 13, 16, 32} and k in {0, 1}."""
    for k in (0, 1):
        for trial in range(3):
            n = int(rng.randint(400, 3000))
            text = make_text(rng, n, 4)
            pats = _patterns(rng, text, k)
            plans = engine.compile_patterns(pats, k=k)
            idx = engine.build_index(text)
            want_counts = np.asarray(engine.count_many_jit(idx, plans, k=k))[0]
            want_mask = np.asarray(engine.match_many_jit(idx, plans, k=k))[0]
            chunk = int(CHUNKS[trial % len(CHUNKS)])
            sc = StreamScanner(plans, chunk, k=k)
            got = sc.count_many(text)
            np.testing.assert_array_equal(
                got, want_counts, err_msg=f"k={k} chunk={chunk} n={n}"
            )
            pos = StreamScanner(plans, chunk, k=k).positions_many(text)
            for p_i in range(len(pos)):
                np.testing.assert_array_equal(
                    pos[p_i], np.nonzero(want_mask[p_i])[0],
                    err_msg=f"k={k} chunk={chunk} pattern row {p_i}",
                )


def test_seam_occurrence_straddles_every_phase():
    """Planted occurrences crossing a chunk seam at EVERY straddle phase
    (first byte in chunk i, last byte in chunk i+1, and everything between)
    are found exactly once — including starts inside a beta block and starts
    inside the final chunk's padding region."""
    for m in (2, 4, 8, 13, 16, 32):
        pat = np.full(m, 9, np.uint8)  # alphabet disjoint from the text
        plans = engine.compile_patterns([pat])
        sc = StreamScanner(plans, 256)
        step = sc.step_bytes
        text = make_text(np.random.RandomState(m), 3 * step + 11, 4)
        # every start that makes the occurrence touch the first seam, plus
        # one deep inside the (short, padded) final chunk
        starts = [step - m + 1 + j for j in range(m + 1) if step - m + 1 + j >= 0]
        starts += [2 * step + 5]
        starts = sorted(
            {s for s in starts if 0 <= s <= len(text) - m}
        )
        # plant with a >= 1 byte gap: abutting all-9 plants would merge into
        # a run with extra (unplanned) occurrences of the all-9 pattern
        planted, last_end = [], -1
        for s in starts:
            if s > last_end:
                text[s : s + m] = pat
                planted.append(s)
                last_end = s + m
        got = StreamScanner(plans, 256).count_many(text)
        assert got.tolist() == [len(planted)], f"m={m}"
        pos = StreamScanner(plans, 256).positions_many(text)
        np.testing.assert_array_equal(pos[0], np.asarray(planted), f"m={m}")


def test_fused_seam_equals_reference_two_pass(rng):
    """The fused chunk step (count_many(..., end_min=prev_ov), one scan, no
    overlap-prefix sub-index) is bit-identical to the reference two-pass
    subtraction across the full seam property grid: m in {2,4,8,13,16,32},
    k in {0,1}, every chunk size — counts AND positions."""
    for k in (0, 1):
        for trial in range(3):
            n = int(rng.randint(400, 3000))
            text = make_text(rng, n, 4)
            pats = _patterns(rng, text, k)
            plans = engine.compile_patterns(pats, k=k)
            chunk = int(CHUNKS[trial % len(CHUNKS)])
            ref = StreamScanner(plans, chunk, k=k, fused=False)
            want = ref.count_many(text)
            got = StreamScanner(plans, chunk, k=k, fused=True).count_many(text)
            np.testing.assert_array_equal(
                got, want, err_msg=f"k={k} chunk={chunk} n={n}"
            )
            pos_ref = StreamScanner(
                plans, chunk, k=k, fused=False
            ).positions_many(text)
            pos_fused = StreamScanner(
                plans, chunk, k=k, fused=True
            ).positions_many(text)
            for r in range(len(pos_ref)):
                np.testing.assert_array_equal(
                    pos_fused[r], pos_ref[r],
                    err_msg=f"k={k} chunk={chunk} row {r}",
                )


def test_mixed_plans_one_dispatch_per_chunk_shared_path(rng, monkeypatch):
    """Regression (ISSUE 6 satellite): a MIXED plan set — one sparse-eligible
    EPSMb group among a/c groups — must still issue exactly ONE jitted
    dispatch per chunk with counts equal to the per-group reference, i.e.
    the single-eligible-group case routes through _count_groups_b_shared
    instead of silently taking the slow per-group path."""
    monkeypatch.setattr(engine, "SPARSE_B_MIN_ELEMS", 0)
    text = make_text(rng, 6_000, 4)
    pats = [
        text[7:9].copy(),        # EPSMa
        text[100:108].copy(),    # the ONE sparse-eligible EPSMb group
        text[200:208].copy(),    # (>= 4 patterns: eligibility floor)
        text[400:408].copy(),
        text[900:908].copy(),
        text[300:324].copy(),    # EPSMc
    ]
    plans = engine.compile_patterns(pats)
    idx = engine.build_index(text)
    assert (
        sum(
            1
            for p in plans
            if p.regime == "b" and engine._sparse_b_eligible(idx, p)
        )
        == 1
    )
    # single eligible group still counts through the shared pass
    calls = []
    orig = engine._count_groups_b_shared

    def spy(index, plans_, bank, end_min=None):
        calls.append(len(plans_))
        return orig(index, plans_, bank, end_min)

    monkeypatch.setattr(engine, "_count_groups_b_shared", spy)
    counts = np.asarray(engine.count_many(idx, plans))
    assert calls == [1]  # routed through the shared candidate pass
    for row, pid in enumerate(engine.plan_order(plans)):
        want = int(np.asarray(epsm.find(text, pats[pid])).sum())
        assert counts[0, row] == want, f"pattern {pid}"
    # and the streaming loop stays at exactly one dispatch per chunk
    sc = StreamScanner(plans, 1024)
    n_windows = sum(1 for _ in sc._windows(text))
    got = sc.count_many(text)
    assert sc.dispatch_count == n_windows
    for row, pid in enumerate(sc.order):
        want = int(np.asarray(epsm.find(text, pats[pid])).sum())
        assert got[row] == want, f"pattern {pid}"


def test_auto_chunk_bytes_resolved_and_exact(rng):
    """chunk_bytes="auto" resolves to a sane, beta-aligned size (memory
    budget + dispatch-overhead probe), is recorded on the scanner, and scans
    exactly."""
    from repro.core.epsm import EPSMC_BETA
    from repro.core.stream import (
        MAX_CHUNK_BYTES,
        MIN_CHUNK_BYTES,
        auto_chunk_bytes,
    )

    auto = auto_chunk_bytes()
    assert MIN_CHUNK_BYTES <= auto <= MAX_CHUNK_BYTES
    assert auto % EPSMC_BETA == 0
    text = make_text(rng, 5_000, 4)
    plans = engine.compile_patterns([text[100:108].copy()])
    sc = StreamScanner(plans)  # default chunk_bytes="auto"
    assert sc.chunk_bytes == auto
    want = StreamScanner(plans, 512).count_many(text)
    np.testing.assert_array_equal(sc.count_many(text), want)


class _FakeDevice:
    def __init__(self, platform, stats):
        self.platform = platform
        self._stats = stats

    def memory_stats(self):
        if isinstance(self._stats, Exception):
            raise self._stats
        return self._stats


@pytest.mark.parametrize(
    "stats", [None, {}, RuntimeError("memory_stats unsupported")]
)
def test_auto_chunk_bytes_needs_memory_stats_off_cpu(stats):
    """Only the CPU backend may size chunks without a device memory limit:
    elsewhere a missing limit or a failing memory_stats() raises instead of
    assuming the 512 MiB budget."""
    from repro.core.stream import auto_chunk_bytes

    with pytest.raises(RuntimeError):
        auto_chunk_bytes(device=_FakeDevice("tpu", stats))
    if not isinstance(stats, Exception):
        assert auto_chunk_bytes(device=_FakeDevice("cpu", stats)) > 0
    limit = {"bytes_limit": 16 << 30, "bytes_in_use": 0}
    assert auto_chunk_bytes(device=_FakeDevice("tpu", limit)) > 0


def test_one_dispatch_per_chunk_and_bounded_window(rng):
    text = make_text(rng, 10_000, 4)
    plans = engine.compile_patterns([text[50:58].copy(), text[300:316].copy()])
    sc = StreamScanner(plans, 1024)
    n_windows = sum(1 for _ in sc._windows(text))
    sc.count_many(text)
    assert sc.dispatch_count == n_windows  # exactly one jitted call per chunk
    # device footprint is O(chunk), independent of the input length
    assert sc.window_bytes < 2 * 1024 + sc.overlap + 8
    assert sc.device_bytes_per_chunk < 64 * (1 << 17) + 32 * sc.window_bytes


def test_sources_bytes_file_iterable_agree(rng):
    text = make_text(rng, 5_000, 4)
    plans = engine.compile_patterns([text[100:108].copy()])
    sc = StreamScanner(plans, 512)
    want = sc.count_many(text)
    as_bytes = sc.count_many(text.tobytes())
    as_file = sc.count_many(io.BytesIO(text.tobytes()))
    ragged = np.array_split(text, [1, 7, 8, 1000, 1001, 4000])
    as_iter = sc.count_many(iter(ragged))
    assert want.tolist() == as_bytes.tolist() == as_file.tolist() == as_iter.tolist()


def test_empty_and_short_sources(rng):
    plans = engine.compile_patterns([np.arange(8, dtype=np.uint8)])
    sc = StreamScanner(plans, 256)
    assert sc.count_many(b"").tolist() == [0]
    assert sc.dispatch_count == 0  # no chunk, no dispatch
    short = np.arange(8, dtype=np.uint8)
    assert StreamScanner(plans, 256).count_many(short).tolist() == [1]
    assert StreamScanner(plans, 256).count_many(short[:5]).tolist() == [0]


def test_gzip_sources_stream_exactly(rng):
    """Compressed sources decompress incrementally into the O(chunk) window:
    bytes, file-like, and an iterator of frames, single- and multi-member,
    all agree with the plain scan — including occurrences planted ACROSS
    gzip member boundaries (the decompressed-chunk seams land mid-window,
    so the overlap carry is exercised by the frame layout itself)."""
    text = make_text(rng, 30_000, 4)
    m = 8
    pat = np.full(m, 9, np.uint8)
    cuts = [5_000, 12_344, 20_008]  # member boundaries
    for cut in cuts:
        text[cut - m // 2 : cut - m // 2 + m] = pat  # straddles the boundary
    plans = engine.compile_patterns([pat, text[100:108].copy()])
    want = StreamScanner(plans, 1024).count_many(text)
    assert want[0] >= len(cuts)  # the straddling plants are really there
    members = np.split(text, cuts)
    blob_one = gzip.compress(text.tobytes())
    blob_multi = b"".join(gzip.compress(c.tobytes()) for c in members)
    frames = [gzip.compress(c.tobytes()) for c in members]
    for src in (
        Compressed(blob_one),
        Compressed(blob_multi),
        Compressed(io.BytesIO(blob_multi)),
        Compressed(iter(frames), codec="gzip"),
    ):
        got = StreamScanner(plans, 1024).count_many(src)
        np.testing.assert_array_equal(got, want)
    # positions agree too (mask path shares the decompression)
    pos = StreamScanner(plans, 1024).positions_many(Compressed(blob_multi))
    want_pos = StreamScanner(plans, 1024).positions_many(text)
    for r in range(len(pos)):
        np.testing.assert_array_equal(pos[r], want_pos[r])
    # truncated stream is an error, not a silent short count
    with pytest.raises(ValueError):
        StreamScanner(plans, 1024).count_many(Compressed(blob_one[:-20]))
    # auto-sniff survives a first read() piece shorter than the magic
    tiny_pieces = [blob_one[:2], blob_one[2:3], blob_one[3:]]
    got = StreamScanner(plans, 1024).count_many(Compressed(iter(tiny_pieces)))
    np.testing.assert_array_equal(got, want)


def test_zstd_sources_stream_exactly(rng):
    zstandard = pytest.importorskip("zstandard")
    text = make_text(rng, 20_000, 4)
    plans = engine.compile_patterns([text[100:108].copy()])
    want = StreamScanner(plans, 1024).count_many(text)
    cctx = zstandard.ZstdCompressor()
    blob = b"".join(
        cctx.compress(c.tobytes()) for c in np.array_split(text, 4)
    )
    got = StreamScanner(plans, 1024).count_many(Compressed(blob))
    np.testing.assert_array_equal(got, want)
    got_auto = StreamScanner(plans, 1024).count_many(
        Compressed(io.BytesIO(blob), codec="auto")
    )
    np.testing.assert_array_equal(got_auto, want)


def test_mid_stream_prefix_start_injection(rng):
    """The factored chunk loop: scanning [0, p) and [p, n) as separate
    ranges (the second with the carried prefix and start offset) composes to
    the whole-text result — counts add, positions are global and disjoint.
    This is the per-shard contract shard_stream.py relies on."""
    text = make_text(rng, 9_000, 4)
    pats = [text[70:78].copy(), text[10:42].copy()]
    plans = engine.compile_patterns(pats)
    sc = StreamScanner(plans, 1024)
    ov = sc.overlap
    whole = sc.count_many(text)
    whole_pos = StreamScanner(plans, 1024).positions_many(text)
    for p in (1024, 2048, 4096):  # beta-aligned split points
        left = StreamScanner(plans, 1024).count_many(text[:p])
        right = StreamScanner(plans, 1024).count_many(
            text[p:], prefix=text[p - ov : p], start=p
        )
        np.testing.assert_array_equal(left + right, whole, err_msg=f"p={p}")
        pos_l = StreamScanner(plans, 1024).positions_many(text[:p])
        pos_r = StreamScanner(plans, 1024).positions_many(
            text[p:], prefix=text[p - ov : p], start=p
        )
        for r in range(len(pos_l)):
            np.testing.assert_array_equal(
                np.concatenate([pos_l[r], pos_r[r]]), whole_pos[r],
                err_msg=f"p={p} row {r}",
            )
    # contract violations are loud
    with pytest.raises(ValueError):  # start - len(prefix) off the beta grid
        StreamScanner(plans, 1024).count_many(text[5:], prefix=text[1:5], start=5)
    with pytest.raises(ValueError):  # prefix longer than the overlap
        StreamScanner(plans, 1024).count_many(
            text[ov + 8 :], prefix=text[: ov + 8], start=ov + 8
        )


def test_stream_count_original_order_and_find_stream(rng):
    text = make_text(rng, 20_000, 4)
    pats = [text[70:102].copy(), text[10:12].copy(), text[500:508].copy()]
    got = stream_count(text, pats, chunk_bytes=777)
    for i, p in enumerate(pats):
        assert got[i] == int(np.asarray(epsm.count(text, p))), i
    mask = find_stream(text, pats[2], chunk_bytes=777)
    np.testing.assert_array_equal(mask, np.asarray(epsm.find(text, pats[2])))


def test_epsm_stream_escape_hatch(rng, monkeypatch):
    """find/count with stream=True (and the auto threshold) are identical to
    the resident scan."""
    text = make_text(rng, 9_000, 4)
    pat = text[123:131].copy()
    want_mask = np.asarray(epsm.find(text, pat))
    want_count = int(np.asarray(epsm.count(text, pat)))
    np.testing.assert_array_equal(epsm.find(text, pat, stream=True), want_mask)
    assert int(epsm.count(text, pat, stream=True)) == want_count
    assert int(epsm.count(text, pat, k=1, stream=True)) == int(
        np.asarray(epsm.count(text, pat, k=1))
    )
    # auto mode: host texts above the threshold stream without being asked
    monkeypatch.setattr(epsm, "STREAM_AUTO_BYTES", 1024)
    auto = epsm.find(text, pat)
    assert isinstance(auto, np.ndarray)  # host mask: the streaming path ran
    np.testing.assert_array_equal(auto, want_mask)
    np.testing.assert_array_equal(
        epsm.positions(text, pat), np.nonzero(want_mask)[0]
    )


def test_pipeline_oversize_docs_stream(rng, monkeypatch):
    """Oversize documents take the bounded-memory streaming path and still
    get exact blocklist verdicts."""
    from repro.data import pipeline as pl

    monkeypatch.setattr(pl, "MAX_FILTER_LEN", 512)
    bad = b"\x07\x01\x07\x02\x07\x03"
    clean_big = make_text(rng, 4_000, 4)
    dirty_big = make_text(rng, 4_000, 4)
    dirty_big[2_345 : 2_345 + len(bad)] = np.frombuffer(bad, np.uint8)
    small = make_text(rng, 100, 4)
    pipe = pl.LMDataPipeline(
        [clean_big, dirty_big, small], seq_len=64, batch_size=1,
        blocklist=[bad, b"\x06\x06\x06\x06\x06\x06\x06\x06"],
    )
    for _ in pipe:
        pass
    assert pipe.stats.docs_in == 3
    assert pipe.stats.docs_blocked == 1  # dirty_big, found by the scanner
    assert pipe.stats.docs_out == 2


def test_plan_cache_hit_no_device_transfer(monkeypatch):
    """compile_patterns_cached: a repeat call with the same live device
    arrays must not touch the device — the memoized digest answers."""
    pats = [
        jnp.asarray(np.frombuffer(b"streaming!", np.uint8)),
        jnp.asarray(np.frombuffer(b"does not sync", np.uint8)),
    ]
    first = engine.compile_patterns_cached(pats)  # warm: digests + plans
    transfers = []
    orig = jax.device_get

    def counting_get(x):
        transfers.append(type(x).__name__)
        return orig(x)

    monkeypatch.setattr(jax, "device_get", counting_get)
    again = engine.compile_patterns_cached(pats)
    assert transfers == []  # zero device transfers on the hot path
    assert again is first  # and it really was a cache hit


def test_stop_scanner_lazy_sync_identical(rng, monkeypatch):
    """StopScanner with the scalar-gated transfer: hit matrices identical to
    the naive scan, and the (B, P) device_get happens ONLY on steps with at
    least one hit."""
    from repro.serve.engine import StopScanner

    stops = [b"\x00\x01", b"\x01\x02\x00"]
    stream = bytes(rng.randint(0, 3, size=60).astype(np.uint8))
    sc = StopScanner(stops, 1, len(stream))
    transfers = []
    orig = jax.device_get

    def counting_get(x):
        transfers.append(1)
        return orig(x)

    monkeypatch.setattr(jax, "device_get", counting_get)
    hit_steps = []
    for step in range(len(stream)):
        row = sc.scan(np.asarray([stream[step]], np.int32), step)[0]
        want = np.asarray(
            [
                step >= len(s) - 1 and stream[step - len(s) + 1 : step + 1] == s
                for s in stops
            ]
        )
        np.testing.assert_array_equal(row, want, err_msg=f"step {step}")
        if want.any():
            hit_steps.append(step)
    assert sc.dispatch_count == len(stream)
    assert len(transfers) == len(hit_steps)  # matrix synced only on hits
    assert len(hit_steps) > 0  # the gate was actually exercised both ways


def test_compressed_sources_under_injected_faults(rng):
    """The fault harness x compression matrix: a truncation that cuts a
    gzip/zstd frame mid-member surfaces as the decompressor's truncated-
    stream ValueError, an injected read error surfaces as-is, and a
    zero-rate plan is a clean pass-through — never a silent short count."""
    from repro.dist.fault_injection import FaultPlan, FaultyChunkSource, InjectedReadError

    text = make_text(rng, 30_000, 4)
    plans = engine.compile_patterns([text[100:108].copy(), text[5:7].copy()])
    want = StreamScanner(plans, 1024).count_many(text)

    blobs = {"gzip": gzip.compress(text.tobytes())}
    try:
        import zstandard

        blobs["zstd"] = zstandard.ZstdCompressor().compress(text.tobytes())
    except ImportError:
        pass

    for codec, blob in blobs.items():
        # single member: every proper prefix is a truncated stream
        pieces = [blob[i : i + 1000] for i in range(0, len(blob), 1000)]

        clean = FaultPlan(0)  # all rates zero: the wrapper is transparent
        got = StreamScanner(plans, 1024).count_many(
            Compressed(FaultyChunkSource(iter(pieces), clean), codec=codec)
        )
        np.testing.assert_array_equal(got, want, err_msg=codec)

        trunc = FaultPlan(1, truncate_rate=1.0, attempts_per_fault=None)
        with pytest.raises(ValueError, match="truncated"):
            StreamScanner(plans, 1024).count_many(
                Compressed(FaultyChunkSource(iter(pieces), trunc), codec=codec)
            )
        assert any(e.action == "truncate" for e in trunc.events)

        # mid-member read error: make the SECOND piece fail so decompression
        # is already underway when the fault lands
        err = FaultPlan(2, read_error_rate=1.0, attempts_per_fault=1)
        with pytest.raises(InjectedReadError):
            err.check("read", ("stream", 0))  # burn piece 0's transient fault
        with pytest.raises(InjectedReadError):
            StreamScanner(plans, 1024).count_many(
                Compressed(FaultyChunkSource(iter(pieces), err), codec=codec)
            )

        # truncated compressed data is NOT retryable: rescanning the same
        # bytes can't help, so the classifier must fail fast
        from repro.dist.fault_tolerance import default_is_retryable

        assert not default_is_retryable(ValueError(f"truncated {codec} stream"))
        assert default_is_retryable(InjectedReadError("flaky socket"))


def test_stream_watchdog_flags_stalled_chunk(rng):
    """StreamScanner(watchdog=...) times each host step; a source that
    stalls mid-stream raises StragglerAbort under policy="raise", and under
    policy="log" the scan completes exactly with the event reported to
    on_straggler."""
    import time as _time

    from repro.dist.fault_tolerance import StepWatchdog, StragglerAbort

    text = make_text(rng, 40_000, 4)
    plans = engine.compile_patterns([text[100:108].copy()])
    want = StreamScanner(plans, 1024).count_many(text)

    def stalling_chunks(stall_s):
        def gen():
            for i in range(0, len(text), 1024):
                if i == 20_480:  # enough history for the rolling median
                    _time.sleep(stall_s)
                yield text[i : i + 1024]

        return gen()

    wd = StepWatchdog(factor=5.0, policy="raise", min_history=3)
    with pytest.raises(StragglerAbort):
        StreamScanner(plans, 1024, watchdog=wd).count_many(stalling_chunks(0.25))

    seen = []
    wd2 = StepWatchdog(factor=5.0, policy="log", min_history=3)
    got = StreamScanner(
        plans, 1024, watchdog=wd2, on_straggler=seen.append
    ).count_many(stalling_chunks(0.25))
    np.testing.assert_array_equal(got, want)  # logging never changes the scan
    assert seen and seen[0].duration_s > seen[0].median_s
    assert wd2.events == seen

    # no watchdog, no timing: the plain path is untouched
    got_plain = StreamScanner(plans, 1024).count_many(stalling_chunks(0.0))
    np.testing.assert_array_equal(got_plain, want)


# Literals of every regime (EPSMa m < 4, EPSMb, EPSMc m >= 16), as a log
# scan job asks for them.
SCOPE_QUERIES = (b"Got", b"blk_", b"timeout!", b"PacketResponder",
                 b"Verification succeeded", b"too many open files (24)",
                 b"BLOCK* NameSystem.addStoredBlock")
ENGINE_STAGES = {"index", "filter", "compact", "verify", "dense"}


def _op_name_stages(hlo_text):
    import re

    return {part for op in re.findall(r'op_name="([^"]*)"', hlo_text)
            for part in op.split("/")}


@pytest.mark.parametrize("step,fused", [
    ("count", True), ("mask", True), ("count", False), ("mask", False),
])
def test_steps_name_their_stages_in_op_name(step, fused):
    """The compiled chunk steps carry the engine's stages in their ops'
    ``op_name`` metadata (what a trace's ops are mapped to stages by), the
    reference forms their own seam stage; and the count step's module is
    still ``jit__unknown``, the name its roofline metric reads."""
    from repro.core import stream

    plans = engine.compile_patterns_cached(list(SCOPE_QUERIES), k=0)
    sc = StreamScanner(plans, 1 << 16, use_kernel=False, fused=fused)
    N = sc.window_bytes
    win = jnp.zeros((N,), jnp.uint8)
    args = (win, np.int32(N), np.int32(sc.overlap), sc.plans)
    if step == "count":
        lowered = stream._jitted_count_step(fused, sc.shared).lower(
            sc._zero_counts(), *args, ov=sc.overlap, k=0
        )
        want = ENGINE_STAGES | {"count", "accumulate"}
    else:
        lowered = stream._mask_step.lower(*args, k=0, fused=fused)
        want = ENGINE_STAGES | {"match"}
    if not fused:
        want |= {"seam_gate"}
    text = lowered.compile().as_text()
    assert want <= _op_name_stages(text)
    if step == "count":
        assert text.startswith("HloModule jit__unknown,")
