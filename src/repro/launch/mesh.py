"""Mesh construction and the shard-stream cluster entrypoint.  FUNCTIONS,
not module-level constants — importing this module never touches jax device
state.  Meshes use Auto axes, which the sharding rules'
``with_sharding_constraint`` calls require (``jax.make_mesh`` defaults to
Explicit axes).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 chips per pod; 2 pods when multi_pod (512 chips total)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes))


def make_local_mesh(axis_names=("data", "model")):
    """Mesh over whatever devices this process actually has (tests/examples).
    Puts all devices on the first axis."""
    n = len(jax.devices())
    shape = (n,) + (1,) * (len(axis_names) - 1)
    return jax.make_mesh(shape, axis_names, (AxisType.Auto,) * len(axis_names))


# (the sharded-stream count merge builds its own 1-D device mesh inline in
# dist.compat.sum_across_devices — only the devices that actually hold
# shard partials belong on the axis, which varies per scan)

# jax.process_count() itself initializes the local backend, after which
# jax.distributed.initialize refuses to run — so idempotency is tracked here
# instead of queried from jax.
_CLUSTER_JOINED = False


def init_stream_cluster(
    coordinator: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> Tuple[int, int]:
    """Shard-stream entrypoint: join (or skip) a jax.distributed cluster.

    Returns (process_index, process_count).  With ``num_processes`` None or
    1 this is a no-op single-process run — the same ShardedStreamScanner
    code path then merges locally, so examples and tests need no mode
    switch.  Idempotent: a second call just reports the cluster shape.
    MUST run before any other jax call when joining a real cluster."""
    global _CLUSTER_JOINED
    if num_processes is not None and int(num_processes) > 1 and not _CLUSTER_JOINED:
        try:
            # the CPU backend only speaks cross-process collectives through
            # gloo; a no-op (and absent flag) on TPU/GPU and old jax
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        except AttributeError:
            pass
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=int(num_processes),
            process_id=None if process_id is None else int(process_id),
        )
        _CLUSTER_JOINED = True
    return jax.process_index(), jax.process_count()
