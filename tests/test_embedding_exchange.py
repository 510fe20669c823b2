"""All-to-all embedding exchange: exactness (incl. skew overflow fallback)
on a multi-device subprocess mesh."""

import subprocess
import sys

import pytest

from conftest import REPO_ROOT, subprocess_env

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from repro.dist.embedding_exchange import make_alltoall_lookup

mesh = jax.make_mesh((2, 4), ("data", "model"))
rng = np.random.RandomState(0)
V, d, n = 4096, 16, 512
table = rng.randn(V, d).astype(np.float32)
lk = make_alltoall_lookup(mesh, "model", ("data",))

# uniform ids
ids = rng.randint(0, V, n).astype(np.int32)
got = np.asarray(lk(jnp.asarray(table), jnp.asarray(ids)))
assert np.array_equal(got, table[ids]), "uniform"

# zipf-skewed ids
ids = ((rng.zipf(1.3, n) - 1) % V).astype(np.int32)
got = np.asarray(lk(jnp.asarray(table), jnp.asarray(ids)))
assert np.array_equal(got, table[ids]), "zipf"

# adversarial: every id on one shard (forces the overflow fallback)
ids = np.full(n, 7, np.int32)
got = np.asarray(lk(jnp.asarray(table), jnp.asarray(ids)))
assert np.array_equal(got, table[ids]), "overflow"
print("EXCHANGE_OK")
"""


@pytest.mark.slow
def test_alltoall_exchange_subprocess():
    res = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, timeout=600,
        env=subprocess_env(), cwd=REPO_ROOT,
    )
    assert "EXCHANGE_OK" in res.stdout, res.stdout + res.stderr
