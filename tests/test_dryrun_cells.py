"""Dry-run machinery integration: lower+compile representative cells of all
three families (and both LM sharding strategies) on a small fake-device mesh
in a subprocess (device count locks at first jax init)."""

import subprocess
import sys

import pytest

from conftest import REPO_ROOT, subprocess_env

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from pathlib import Path
from repro.launch.dryrun import run_cell
from jax.sharding import AxisType

mesh = jax.make_mesh((4, 2), ("data", "model"), (AxisType.Auto,) * 2)
out = Path(os.environ["DRYRUN_CELLS_OUT"])
cells = [
    ("smollm-135m", "train_4k", "default"),
    ("smollm-135m", "train_4k", "zero_dp"),
    ("smollm-135m", "decode_32k", "default"),
    ("gatedgcn", "molecule", "default"),
    ("gatedgcn", "full_graph_sm", "nodes_sharded+bf16"),
    ("din", "train_batch", "default"),
    ("dcn-v2", "retrieval_cand", "default"),
]
for arch, shape, strat in cells:
    rec = run_cell(arch, shape, False, out, mesh=mesh, strategy=strat)
    assert rec["hlo_flops_per_chip"] > 0, (arch, shape)
    assert rec["roofline"]["bottleneck"] in ("compute", "memory", "collective")
    assert rec["model_flops_global"] > 0
print("DRYRUN_CELLS_OK")
"""


@pytest.mark.slow
def test_dryrun_cells_subprocess(tmp_path):
    res = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, timeout=600,
        env=subprocess_env(DRYRUN_CELLS_OUT=str(tmp_path)), cwd=REPO_ROOT,
    )
    assert "DRYRUN_CELLS_OK" in res.stdout, res.stdout[-2000:] + res.stderr[-3000:]
