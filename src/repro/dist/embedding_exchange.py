"""DLRM-style all-to-all embedding exchange for row-sharded tables.

The table is row-sharded over one mesh axis; every device holds the ids of
its slice of the batch (replicated over the table axis).  Lookup runs in
three hops:

  1. bucket my ids by owning shard (fixed ``capacity`` slots per shard, so
     shapes are static) and all-to-all the id buckets along the table axis;
  2. every shard answers the requests that landed on it with a local gather;
  3. all-to-all the vectors back and scatter them to the original id order.

All-to-all volume is nnz * dim / k per hop versus nnz * dim all-reduced by
the simpler psum strategy (models/embedding.py) — the classic DLRM win.

Skew safety: with a fixed per-shard capacity a hot shard can overflow (zipf
ids, or adversarially all ids on one shard).  Overflow is detected on device
and the whole lookup falls back to the exact psum path via lax.cond, so the
result is exact for every id distribution; capacity only controls how often
the cheap path runs.
"""

from __future__ import annotations

from typing import Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

AxisNames = Union[str, Tuple[str, ...]]


def make_alltoall_lookup(
    mesh,
    table_axis: str = "model",
    batch_axes: Sequence[str] = ("data",),
    capacity_factor: float = 2.0,
):
    """Build `lookup(table, ids) -> vectors` with table row-sharded over
    ``table_axis`` and ids/outputs sharded over ``batch_axes``."""
    batch_axes = tuple(batch_axes)
    batch_spec = batch_axes[0] if len(batch_axes) == 1 else batch_axes

    def local_lookup(table_shard, ids):
        k = lax.axis_size(table_axis)
        me = lax.axis_index(table_axis)
        rows = table_shard.shape[0]  # rows per shard (V // k)
        n = ids.shape[0]
        cap = max(1, int(-(-n * capacity_factor // k)))

        owner = jnp.clip(ids // rows, 0, k - 1)
        onehot = owner[:, None] == jnp.arange(k)[None, :]  # (n, k)
        counts = onehot.sum(axis=0)  # ids per owning shard
        overflow = (counts > cap).any()

        def a2a_path(_):
            # slot of each id inside its owner's bucket
            pos = jnp.cumsum(onehot, axis=0) - 1  # (n, k)
            pib = jnp.take_along_axis(pos, owner[:, None], axis=1)[:, 0]
            slot = owner * cap + pib  # (n,) in [0, k*cap)
            send = jnp.zeros((k * cap,), ids.dtype).at[slot].set(ids)
            # hop 1: ship id buckets to their owners
            recv = lax.all_to_all(
                send.reshape(k, cap), table_axis, split_axis=0, concat_axis=0,
                tiled=False,
            ).reshape(k, cap)
            # hop 2: answer requests with a local gather
            local = jnp.clip(recv - me * rows, 0, rows - 1)
            vals = table_shard[local]  # (k, cap, d)
            # hop 3: ship vectors back and restore the original id order
            back = lax.all_to_all(
                vals, table_axis, split_axis=0, concat_axis=0, tiled=False
            )
            return back.reshape(k * cap, -1)[slot]

        def psum_path(_):
            mine = owner == me
            local = jnp.where(mine, ids - me * rows, 0)
            v = table_shard[local] * mine[:, None].astype(table_shard.dtype)
            return lax.psum(v, table_axis)

        return lax.cond(~overflow, a2a_path, psum_path, operand=None)

    return jax.shard_map(
        local_lookup,
        mesh=mesh,
        in_specs=(P(table_axis, None), P(batch_spec)),
        out_specs=P(batch_spec, None),
        check_vma=False,
    )
