"""Collective helpers the sharded-stream merge rides on: a cross-device
sum of per-shard accumulators, and host-array sum / ragged all-gather across
jax.distributed processes.
"""

from __future__ import annotations

from typing import List, Sequence

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


@jax.jit
def _sum_shard_axis(a):
    return a.sum(0)


def sum_across_devices(parts: Sequence[jax.Array]) -> np.ndarray:
    """psum-style merge of per-shard accumulators (same shape/dtype each).

    Parts sharing one device fold with on-device adds; parts spread over D
    devices are assembled — WITHOUT gathering to host first — into one
    device-sharded (D, ...) global array and reduced by a single jitted sum,
    which XLA lowers to an actual cross-device reduction.  This is the
    count-merge collective of the two-level seam rule (DESIGN.md §10)."""
    if not parts:
        raise ValueError("sum_across_devices needs at least one part")
    per_dev: dict = {}
    for p in parts:
        (d,) = p.devices()
        acc = per_dev.get(d)
        per_dev[d] = p if acc is None else acc + p
    vals: List[jax.Array] = list(per_dev.values())
    if len(vals) == 1:
        return np.asarray(jax.device_get(vals[0]))
    mesh = Mesh(np.asarray(list(per_dev)), ("shard",))
    shape = (len(vals),) + tuple(vals[0].shape)
    stacked = jax.make_array_from_single_device_arrays(
        shape, NamedSharding(mesh, P("shard")), [v[None] for v in vals]
    )
    return np.asarray(jax.device_get(_sum_shard_axis(stacked)))


def process_allsum(x: np.ndarray) -> np.ndarray:
    """Sum a host array across jax.distributed processes (identity for a
    single process, so the sharded scanner needs no mode switch)."""
    if jax.process_count() == 1:
        return np.asarray(x)
    from jax.experimental import multihost_utils

    return np.asarray(multihost_utils.process_allgather(np.asarray(x))).sum(0)


def process_allgather_ragged(x: np.ndarray) -> List[np.ndarray]:
    """All-gather a ragged 1-D int64 array across processes; returns one
    array per process (just [x] single-process).

    int64 payloads (global stream positions) are split into two int32 planes
    for the wire — multihost_utils runs under the default x64-disabled config,
    which would silently truncate a direct int64 gather."""
    x = np.asarray(x, np.int64)
    if jax.process_count() == 1:
        return [x]
    from jax.experimental import multihost_utils

    lens = np.asarray(
        multihost_utils.process_allgather(np.asarray([len(x)], np.int32))
    ).reshape(-1)
    cap = max(int(lens.max()), 1)
    lo = np.zeros(cap, np.int32)
    hi = np.zeros(cap, np.int32)
    lo[: len(x)] = (x & 0x7FFFFFFF).astype(np.int32)
    hi[: len(x)] = (x >> 31).astype(np.int32)
    lo_all = np.asarray(multihost_utils.process_allgather(lo))
    hi_all = np.asarray(multihost_utils.process_allgather(hi))
    out = []
    for i in range(len(lens)):
        n = int(lens[i])
        out.append(
            (hi_all[i, :n].astype(np.int64) << 31) | lo_all[i, :n].astype(np.int64)
        )
    return out
