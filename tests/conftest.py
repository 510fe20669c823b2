"""Shared test fixtures.

NOTE: tests run with the real single CPU device — the 512-device
XLA_FLAGS override belongs ONLY to launch/dryrun.py (and subprocesses
spawned by the multi-device tests), never here.
"""

import os
from pathlib import Path

import numpy as np
import pytest

# The checkout that holds these tests: subprocesses run from it and import
# its src/, never another copy of the repo.
REPO_ROOT = Path(__file__).resolve().parents[1]


def subprocess_env(**extra):
    """Environment for a test's child python: this checkout's src/ first on
    PYTHONPATH, plus ``extra`` variables."""
    path = [str(REPO_ROOT / "src")]
    if os.environ.get("PYTHONPATH"):
        path.append(os.environ["PYTHONPATH"])
    return {**os.environ, "PYTHONPATH": os.pathsep.join(path), **extra}


@pytest.fixture(scope="session")
def rng():
    return np.random.RandomState(0xC0FFEE)


def make_text(rng, n, sigma):
    return rng.randint(0, sigma, size=n).astype(np.uint8)


def extract_pattern(rng, text, m):
    s = rng.randint(0, len(text) - m + 1)
    return text[s : s + m].copy()


@pytest.fixture
def texts(rng):
    """(name, text) pairs mimicking the paper's corpora at test scale."""
    return {
        "genome": make_text(rng, 4096, 4),
        "protein": make_text(rng, 4096, 20),
        "english": make_text(rng, 4096, 64),
        "binary": make_text(rng, 4096, 2),
    }
