"""Baseline algorithms (the paper's competitors) against the scalar oracle."""

import numpy as np
import pytest

from repro.core import baselines

from conftest import extract_pattern, make_text


@pytest.mark.parametrize("name", sorted(baselines.BASELINES))
@pytest.mark.parametrize("sigma", [2, 4, 20, 256])
def test_baseline_matches_oracle(rng, name, sigma):
    fn = baselines.BASELINES[name]
    n = 1500
    t = make_text(rng, n, sigma)
    for m in [1, 2, 3, 4, 8, 16, 24, 31]:
        if name == "hash3" and m < 3:
            continue
        p = extract_pattern(rng, t, m)
        oracle = baselines.naive_np(t, p)
        got = np.asarray(fn(t, p))
        np.testing.assert_array_equal(got, oracle, err_msg=f"{name} m={m}")


def test_shift_or_m32(rng):
    t = make_text(rng, 800, 4)
    p = extract_pattern(rng, t, 32)
    np.testing.assert_array_equal(
        np.asarray(baselines.shift_or(t, p)), baselines.naive_np(t, p)
    )
    with pytest.raises(ValueError):
        baselines.shift_or(t, make_text(rng, 33, 4))


def test_bndm_limit(rng):
    t = make_text(rng, 100, 4)
    with pytest.raises(ValueError):
        baselines.bndm(t, make_text(rng, 32, 4))


def test_periodic_patterns_all_baselines(rng):
    t = np.tile(np.array([7, 7, 9], dtype=np.uint8), 100)
    for name, fn in baselines.BASELINES.items():
        for m in [3, 6, 9]:
            p = t[:m].copy()
            oracle = baselines.naive_np(t, p)
            np.testing.assert_array_equal(
                np.asarray(fn(t, p)), oracle, err_msg=f"{name} m={m}"
            )


@pytest.mark.parametrize("sigma", [2, 4, 26])
def test_find_all_overlapping_equals_naive(rng, sigma):
    """find_all, the host reference of the service and stream checks,
    reports every start, overlapping ones included (bytes.count would
    not), exactly as the scalar oracle."""
    t = make_text(rng, 3000, sigma)
    buf = t.tobytes()
    for m in (1, 2, 3, 7, 16, 33):
        p = t[100 : 100 + m].tobytes()
        want = np.flatnonzero(baselines.naive_np(t, t[100 : 100 + m]))
        np.testing.assert_array_equal(baselines.find_all(buf, p), want)
    assert baselines.find_all(b"aaaa", b"aa").tolist() == [0, 1, 2]
    assert baselines.find_all(b"abc", b"x").size == 0
