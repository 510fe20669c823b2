"""Distributed grep: the paper's packed scan as a collective program.

Shards a corpus across 8 (simulated) devices, exchanges (m-1)-byte halos via
ppermute and psums occurrence counts — the 512-chip version of this is what
launch/dryrun.py lowers.  Must be its own process: device count locks at
first jax init.

    PYTHONPATH=src python examples/distributed_grep.py
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import time  # noqa: E402

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.compile_cache import configure_compile_cache  # noqa: E402
from repro.core import baselines, distributed  # noqa: E402
from repro.data import corpus  # noqa: E402


def main():
    configure_compile_cache()
    n = 8 * 1_000_000
    text = corpus.make_corpus("english", n, seed=0)
    patterns = [b"the ", b"people", b"government "]

    mesh = jax.make_mesh((8,), ("data",))
    print(f"mesh: {mesh.devices.shape} over axis 'data'")
    find = distributed.make_distributed_find(mesh, "data")
    count = distributed.make_distributed_count(mesh, "data")

    for pat in patterns:
        p = np.frombuffer(pat, np.uint8)
        c = int(count(jnp.asarray(text), jnp.asarray(p)))
        t0 = time.perf_counter()
        for _ in range(3):
            count(jnp.asarray(text), jnp.asarray(p)).block_until_ready()
        dt = (time.perf_counter() - t0) / 3
        print(f"  {pat!r}: {c} occurrences   ({n/dt/1e9:.2f} GB/s across the mesh)")

    # exactness check incl. shard-boundary occurrences
    p = np.frombuffer(b"the ", np.uint8)
    got = np.asarray(find(jnp.asarray(text[:80000]), jnp.asarray(p)))
    # distributed_find requires the sharded length; rebuild a small mesh run
    want = baselines.naive_np(text[:80000], p)
    np.testing.assert_array_equal(got, want)
    print("  boundary-exactness vs oracle: OK")


if __name__ == "__main__":
    main()
