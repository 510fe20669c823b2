"""Elastic restart: a checkpoint written under one device layout restores
onto a DIFFERENT (8 fake device) mesh with re-sharding — the down/up-scale
path after losing or gaining nodes."""

import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from repro.train import checkpoint as ckpt

from conftest import REPO_ROOT, subprocess_env


@pytest.mark.slow
def test_elastic_rescale_subprocess(tmp_path):
    # phase 1 (this process, 1 device): train-ish state, save
    tree = {
        "w": jnp.arange(64.0 * 16).reshape(64, 16),
        "opt": {"m": jnp.ones((64, 16)), "step": jnp.int32(7)},
    }
    ckpt.save(tree, tmp_path, step=7)

    # phase 2 (subprocess, 8 devices): restore sharded over a (4,2) mesh
    script = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.train import checkpoint as ckpt

mesh = jax.make_mesh((4, 2), ("data", "model"))
tree = {{
    "w": jnp.zeros((64, 16)),
    "opt": {{"m": jnp.zeros((64, 16)), "step": jnp.int32(0)}},
}}
sh = {{
    "w": NamedSharding(mesh, P("data", "model")),
    "opt": {{"m": NamedSharding(mesh, P("data", None)),
             "step": NamedSharding(mesh, P())}},
}}
restored, step = ckpt.restore(tree, {str(tmp_path)!r}, shardings=sh)
assert step == 7
assert restored["w"].sharding == sh["w"]
assert len(restored["w"].sharding.device_set) == 8
np.testing.assert_array_equal(
    np.asarray(restored["w"]), np.arange(64.0 * 16).reshape(64, 16))
assert int(restored["opt"]["step"]) == 7
print("ELASTIC_OK")
"""
    res = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, timeout=600,
        env=subprocess_env(), cwd=REPO_ROOT,
    )
    assert "ELASTIC_OK" in res.stdout, res.stdout + res.stderr
