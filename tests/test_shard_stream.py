"""Sharded streaming scans (core/shard_stream.py): bit-identity against the
single-host StreamScanner across shard counts, shard-seam phase coverage,
degenerate (narrow/empty) shards, range sources, fault retry, and the
repro.dist collective merge.

This file is the CI `multihost` job's main cargo: it runs both on the plain
single-CPU tier-1 device and under XLA_FLAGS=--xla_force_host_platform_
device_count=8, where the per-shard device placement and the cross-device
count reduction are genuinely multi-device (tests that need >= 2 devices
self-skip on the single-device run)."""

import io
import pathlib

import numpy as np
import pytest

import jax

from repro.core import engine
from repro.core.shard_stream import (
    ShardedStreamScanner,
    ShortRangeRead,
    open_range,
    read_range,
    shard_stream_count,
    source_total_bytes,
)
from repro.core.stream import Compressed, StreamScanner
from repro.dist.compat import sum_across_devices
from repro.dist.fault_tolerance import InjectedFault
from repro.dist.sharding import make_stream_shard_spec, range_partition

from conftest import make_text

LENGTHS = (2, 4, 8, 13, 16, 32)
SHARDS = (1, 2, 3, 4, 8)
CHUNK = 997  # odd: window seams land mid-beta-block after rounding


def _patterns(rng, text):
    """One extracted (guaranteed-hit) pattern per length, plus one random."""
    pats = []
    for m in LENGTHS:
        s = rng.randint(0, len(text) - m + 1)
        pats.append(text[s : s + m].copy())
        pats.append(rng.randint(0, 5, size=m).astype(np.uint8))
    return pats


def test_sharded_bit_identical_to_single_host(rng):
    """The acceptance property: sharded count/positions are bit-identical to
    the single-host StreamScanner for shard counts {1,2,3,4,8} across the
    m x k sweep (all LENGTHS in one plan set per k)."""
    for k in (0, 1):
        n = int(rng.randint(3000, 6000))
        text = make_text(rng, n, 4)
        plans = engine.compile_patterns(_patterns(rng, text), k=k)
        want_counts = StreamScanner(plans, CHUNK, k=k).count_many(text)
        want_pos = StreamScanner(plans, CHUNK, k=k).positions_many(text)
        for S in SHARDS:
            sc = ShardedStreamScanner(plans, S, CHUNK, k=k)
            np.testing.assert_array_equal(
                sc.count_many(text), want_counts, err_msg=f"k={k} S={S}"
            )
            pos = ShardedStreamScanner(plans, S, CHUNK, k=k).positions_many(text)
            for r in range(len(pos)):
                np.testing.assert_array_equal(
                    pos[r], want_pos[r], err_msg=f"k={k} S={S} row {r}"
                )


def test_sharded_megakernel_path_bit_identical(rng):
    """The shard bit-identity sweep through the MEGAKERNEL path: every shard
    scanner consumes fused Pallas kernel outputs (use_kernel=True,
    interpret-mode on CPU) and must match the per-group two-pass reference
    (fused=False) exactly across shard counts."""
    n = int(rng.randint(3000, 6000))
    text = make_text(rng, n, 4)
    plans = engine.compile_patterns(_patterns(rng, text))
    want = ShardedStreamScanner(plans, 2, CHUNK, fused=False).count_many(text)
    for S in (1, 3):
        sc = ShardedStreamScanner(plans, S, CHUNK, use_kernel=True)
        assert sc._scanner(0).spec is not None  # kernel path really engaged
        np.testing.assert_array_equal(
            sc.count_many(text), want, err_msg=f"S={S}"
        )


def test_planted_matches_straddle_every_shard_seam_phase():
    """Occurrences planted across every shard boundary at EVERY straddle
    phase (first byte left of the seam ... last byte right of it) are found
    exactly once, counts and positions."""
    for S in (2, 4, 8):
        for m in LENGTHS:
            pat = np.full(m, 9, np.uint8)  # alphabet disjoint from the text
            plans = engine.compile_patterns([pat])
            sc = ShardedStreamScanner(plans, S, 256)
            text = make_text(np.random.RandomState(100 * S + m), 4096 + 13, 4)
            spec = sc.shard_spec(len(text))
            starts = []
            for s_i, _ in spec.ranges[1:]:  # every interior boundary
                starts += [s_i - m + 1 + j for j in range(m + 1)]
            starts = sorted(
                {s for s in starts if 0 <= s <= len(text) - m}
            )
            # plant with >= 1 byte gaps: abutting all-9 plants would merge
            # into runs with extra (unplanned) occurrences
            planted, last_end = [], -1
            for s in starts:
                if s > last_end:
                    text[s : s + m] = pat
                    planted.append(s)
                    last_end = s + m
            got = ShardedStreamScanner(plans, S, 256).count_many(text)
            assert got.tolist() == [len(planted)], f"S={S} m={m}"
            pos = ShardedStreamScanner(plans, S, 256).positions_many(text)
            np.testing.assert_array_equal(
                pos[0], np.asarray(planted), err_msg=f"S={S} m={m}"
            )


def test_shard_narrower_than_overlap_and_empty_shards():
    """Shards narrower than max_m - 1 (an occurrence can span several whole
    shards) and fully empty shards (more shards than beta blocks) stay
    exact."""
    m = 32
    rng = np.random.RandomState(7)
    text = make_text(rng, 64, 4)
    text[5 : 5 + m] = 9  # spans shards of width 8 entirely
    plans = engine.compile_patterns([np.full(m, 9, np.uint8)])
    want = StreamScanner(plans, 256).count_many(text)
    assert want.tolist() == [1]
    for S in (2, 8, 16, 64):
        got = ShardedStreamScanner(plans, S, 256).count_many(text)
        assert got.tolist() == want.tolist(), f"S={S}"
        pos = ShardedStreamScanner(plans, S, 256).positions_many(text)
        np.testing.assert_array_equal(pos[0], [5], err_msg=f"S={S}")
    # degenerate: stream shorter than one beta block, more shards than bytes
    short = text[:5].copy()
    got = ShardedStreamScanner(plans, 8, 256).count_many(short)
    assert got.tolist() == [0]


def test_range_partition_properties():
    for total, S, align in ((1000, 4, 8), (7, 3, 8), (0, 2, 8), (8192, 8, 8)):
        ranges = range_partition(total, S, align=align)
        assert len(ranges) == S
        assert ranges[0][0] == 0 and ranges[-1][1] == total
        for (a, b), (c, _) in zip(ranges, ranges[1:]):
            assert a <= b == c  # contiguous, monotone; empty shards legal
            assert b % align == 0 or b == total  # interior bounds aligned
    spec = make_stream_shard_spec(1000, 4, overlap=32, align=8)
    assert spec.prefix_range(0) == (0, 0)
    s1 = spec.ranges[1][0]
    assert spec.prefix_range(1) == (s1 - 32, s1)
    with pytest.raises(ValueError):
        make_stream_shard_spec(1000, 4, overlap=33, align=8)  # misaligned ov


def test_sources_path_file_callable_agree(rng, tmp_path):
    text = make_text(rng, 20_000, 4)
    pats = [text[70:78].copy(), text[10:26].copy()]
    plans = engine.compile_patterns(pats)
    want = StreamScanner(plans, 2048).count_many(text)
    p = pathlib.Path(tmp_path) / "corpus.bin"
    p.write_bytes(text.tobytes())
    got_path = ShardedStreamScanner(plans, 4, 2048).count_many(p)
    with open(p, "rb") as f:
        got_file = ShardedStreamScanner(plans, 4, 2048).count_many(f)
    opens = []

    def ranged(start, stop):
        opens.append((start, stop))
        return text[start:stop]

    got_call = ShardedStreamScanner(plans, 4, 2048).count_many(
        ranged, total_bytes=len(text)
    )
    assert (
        want.tolist() == got_path.tolist() == got_file.tolist() == got_call.tolist()
    )
    assert len(opens) == 7  # 4 shard bodies + 3 overlap prefixes
    assert source_total_bytes(p) == len(text)
    # compressed sources have no random access: partitioning must refuse
    with pytest.raises(TypeError):
        source_total_bytes(Compressed(b"xx"))


def test_shard_stream_count_original_order(rng):
    text = make_text(rng, 10_000, 4)
    pats = [text[70:102].copy(), text[10:12].copy(), text[500:508].copy()]
    got = shard_stream_count(text, pats, n_shards=4, chunk_bytes=1024)
    want = shard_stream_count(text, pats, n_shards=1, chunk_bytes=1024)
    assert got.tolist() == want.tolist()


def test_fault_injection_retry_and_exhaustion(rng):
    text = make_text(rng, 16_000, 4)
    plans = engine.compile_patterns([text[70:78].copy()])
    want = StreamScanner(plans, 2048).count_many(text)
    fails = {"n": 0}

    def flaky(start, stop):
        if start >= 8000 and start < 12000 and fails["n"] == 0:
            fails["n"] += 1
            raise InjectedFault("shard node died")
        return text[start:stop]

    sc = ShardedStreamScanner(plans, 4, 2048, max_retries=1)
    got = sc.count_many(flaky, total_bytes=len(text))
    assert got.tolist() == want.tolist()  # retried shard re-counts exactly
    assert [e.shard for e in sc.events] == [2] and sc.events[0].attempt == 0

    def dead(start, stop):
        raise InjectedFault("gone for good")

    sc2 = ShardedStreamScanner(plans, 4, 2048, max_retries=2)
    with pytest.raises(InjectedFault):
        sc2.count_many(dead, total_bytes=len(text))
    assert len(sc2.events) == 3  # every attempt logged, then re-raised


def test_fatal_errors_bypass_the_shard_retry_budget(rng):
    """Non-retryable errors (programming errors, FatalScanError) surface on
    the FIRST attempt — no pointless re-open-and-rescan of a shard that
    fails deterministically.  A custom is_retryable hook overrides."""
    from repro.dist.fault_tolerance import FatalScanError

    text = make_text(rng, 16_000, 4)
    plans = engine.compile_patterns([text[70:78].copy()])
    want = StreamScanner(plans, 2048).count_many(text)

    for exc in (FatalScanError("object gone"), TypeError("bad plan")):
        calls = {"n": 0}

        def fatal(start, stop, _exc=exc):
            calls["n"] += 1
            raise _exc

        sc = ShardedStreamScanner(plans, 2, 2048, max_retries=5)
        with pytest.raises(type(exc)):
            sc.count_many(fatal, total_bytes=len(text))
        assert calls["n"] == 1  # first attempt only
        assert len(sc.events) == 1  # still logged for the postmortem

    heal = {"n": 0}

    def flaky_value(start, stop):
        heal["n"] += 1
        if heal["n"] == 1:
            raise ValueError("transiently malformed")
        return text[start:stop]

    sc = ShardedStreamScanner(
        plans, 2, 2048, max_retries=2,
        is_retryable=lambda e: isinstance(e, ValueError),
    )
    got = sc.count_many(flaky_value, total_bytes=len(text))
    assert got.tolist() == want.tolist()


def test_short_range_read_is_loud_not_an_undercount(rng):
    """A source that delivers fewer bytes than a shard's range (truncated
    file, misbehaving range callable) must raise — transiently short reads
    retry, persistent ones propagate; silent undercounts are impossible."""
    text = make_text(rng, 16_000, 4)
    plans = engine.compile_patterns([text[70:78].copy()])
    want = StreamScanner(plans, 2048).count_many(text)
    flaky = {"n": 0}

    def short_once(start, stop):
        if start >= 8000 and start < 12000 and flaky["n"] == 0:
            flaky["n"] += 1
            return text[start : stop - 100]  # transient truncation
        return text[start:stop]

    sc = ShardedStreamScanner(plans, 4, 2048, max_retries=1)
    got = sc.count_many(short_once, total_bytes=len(text))
    assert got.tolist() == want.tolist()
    assert len(sc.events) == 1 and "ShortRangeRead" in sc.events[0].error

    def always_short(start, stop):
        return text[start : max(start, stop - 7)]

    with pytest.raises(ShortRangeRead):
        ShardedStreamScanner(plans, 4, 2048, max_retries=1).count_many(
            always_short, total_bytes=len(text)
        )
    # a stale total_bytes (file truncated after stat) is equally loud
    with pytest.raises(ShortRangeRead):
        ShardedStreamScanner(plans, 2, 2048).count_many(
            lambda s, e: text[s : min(e, 9000)], total_bytes=len(text)
        )


def test_open_range_views_do_not_copy(rng):
    text = make_text(rng, 1024, 4)
    view = open_range(text, 64, 512)
    assert isinstance(view, np.ndarray) and view.base is not None
    np.testing.assert_array_equal(read_range(text, 8, 16), text[8:16])


# ---------------------------------------------------------------------------
# multi-device paths (real under the CI multihost job's 8 forced devices)
# ---------------------------------------------------------------------------

def test_multi_device_placement_and_collective_merge(rng):
    if len(jax.local_devices()) < 2:
        pytest.skip("needs >= 2 local devices (CI multihost job)")
    text = make_text(rng, 100_000, 4)
    pats = [text[11:19].copy(), text[500:532].copy()]
    plans = engine.compile_patterns(pats)
    want = StreamScanner(plans, 8192).count_many(text)
    sc = ShardedStreamScanner(plans, None, 8192)  # defaults to device count
    assert sc.n_shards == jax.device_count()
    got = sc.count_many(text)
    np.testing.assert_array_equal(got, want)
    # plan state was replicated to every device the shards landed on
    assert len(sc._replicas) == min(sc.n_shards, len(jax.local_devices()))
    pos = ShardedStreamScanner(plans, None, 8192).positions_many(text)
    want_pos = StreamScanner(plans, 8192).positions_many(text)
    for r in range(len(pos)):
        np.testing.assert_array_equal(pos[r], want_pos[r])


def test_sum_across_devices_collective(rng):
    devs = jax.local_devices()
    parts = [
        jax.device_put(np.full(3, i + 1, np.int32), devs[i % len(devs)])
        for i in range(5)
    ]
    np.testing.assert_array_equal(sum_across_devices(parts), np.full(3, 15))


def test_distributed_scan_inprocess_mesh(rng):
    """The repro.dist collective scan on an in-process 8-device mesh — the
    multihost job's every-PR replacement for the weekly subprocess test."""
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices (CI multihost job)")
    from repro.core import baselines, distributed

    mesh = jax.make_mesh((8,), ("data",))
    t = make_text(rng, 8 * 512, 4)
    for m in (2, 9, 17):
        p = t[40 : 40 + m].copy()
        oracle = baselines.naive_np(t, p)
        f = distributed.make_distributed_find(mesh, "data")
        np.testing.assert_array_equal(
            np.asarray(f(jax.numpy.asarray(t), jax.numpy.asarray(p))), oracle
        )
        c = distributed.make_distributed_count(mesh, "data")
        assert int(c(jax.numpy.asarray(t), jax.numpy.asarray(p))) == oracle.sum()


def test_sum_across_devices_device_subsets():
    """sum_across_devices builds its mesh over only the devices that hold
    parts: parts on a subset of the devices (here two of each device in
    use, and one device alone) merge to the host sum."""
    devs = jax.devices()
    parts = [
        jax.device_put(np.full(5, i + 1, np.int32), devs[i % len(devs)])
        for i in range(2 * min(len(devs), 2))
    ]
    want = sum(np.asarray(p) for p in parts)
    np.testing.assert_array_equal(sum_across_devices(parts), want)
    np.testing.assert_array_equal(
        sum_across_devices([jax.device_put(p, devs[0]) for p in parts]), want
    )
    with pytest.raises(ValueError):
        sum_across_devices([])
