"""Distributed packed scan: 1-device mesh in-process, 8 fake devices via
subprocess (jax device count is locked at first init, so multi-device tests
must run in their own interpreter)."""

import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import baselines, distributed

from conftest import REPO_ROOT, make_text, subprocess_env


def test_single_device_mesh(rng):
    mesh = jax.make_mesh((1,), ("data",))
    t = make_text(rng, 1024, 4)
    p = t[100:108].copy()
    f = distributed.make_distributed_find(mesh, "data")
    got = np.asarray(f(jnp.asarray(t), jnp.asarray(p)))
    np.testing.assert_array_equal(got, baselines.naive_np(t, p))
    c = distributed.make_distributed_count(mesh, "data")
    assert int(c(jnp.asarray(t), jnp.asarray(p))) == baselines.naive_np(t, p).sum()


MULTI_DEV_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp
from repro.core import distributed, baselines

rng = np.random.RandomState(42)
n = 8 * 512
t = rng.randint(0, 4, size=n).astype(np.uint8)

mesh = jax.make_mesh((8,), ("data",))
for m in [1, 2, 9, 17, 32]:
    s = rng.randint(0, n - m)
    p = t[s:s+m].copy()
    oracle = baselines.naive_np(t, p)
    f = distributed.make_distributed_find(mesh, "data")
    got = np.asarray(f(jnp.asarray(t), jnp.asarray(p)))
    assert np.array_equal(got, oracle), ("find", m)
    c = distributed.make_distributed_count(mesh, "data")
    assert int(c(jnp.asarray(t), jnp.asarray(p))) == oracle.sum(), ("count", m)

mesh2 = jax.make_mesh((2, 4), ("pod", "data"))
for m in [3, 9, 20]:
    s = rng.randint(0, n - m)
    p = t[s:s+m].copy()
    oracle = baselines.naive_np(t, p)
    f = distributed.make_distributed_find(mesh2, ("pod", "data"))
    got = np.asarray(f(jnp.asarray(t), jnp.asarray(p)))
    assert np.array_equal(got, oracle), ("2axis", m)
print("DISTRIBUTED_OK")
"""


@pytest.mark.slow
def test_multi_device_subprocess():
    res = subprocess.run(
        [sys.executable, "-c", MULTI_DEV_SCRIPT],
        capture_output=True,
        text=True,
        timeout=600,
        env=subprocess_env(),
        cwd=REPO_ROOT,
    )
    assert "DISTRIBUTED_OK" in res.stdout, res.stdout + res.stderr
