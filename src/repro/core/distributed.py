"""Distributed packed string matching: the paper's scan as a collective program.

The corpus is sharded along one (or a flattened tuple of) mesh axes; each
device runs the packed scan on its shard; the (m-1)-byte halo needed for
occurrences crossing shard boundaries moves via lax.ppermute (one neighbor
exchange — the cheapest collective there is); counts are psum'd.

This mirrors, at pod scale, exactly what wsblend did at register scale in the
paper: stitching two adjacent blocks so no alignment is lost.
"""

from __future__ import annotations

import functools
from typing import Sequence, Union

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import epsm
from repro.core.packing import as_u8

AxisNames = Union[str, tuple]


def _axis_size(axis_names: AxisNames) -> jnp.ndarray:
    if isinstance(axis_names, str):
        return lax.axis_size(axis_names)
    size = 1
    for a in axis_names:
        size = size * lax.axis_size(a)
    return size


def _flat_index(axis_names: AxisNames) -> jnp.ndarray:
    if isinstance(axis_names, str):
        return lax.axis_index(axis_names)
    idx = jnp.int32(0)
    for a in axis_names:
        idx = idx * lax.axis_size(a) + lax.axis_index(a)
    return idx


def _next_rank_halo(shard: jnp.ndarray, halo: int, axis_names: AxisNames) -> jnp.ndarray:
    """Exact next-flat-rank halo exchange (handles multi-axis sharding)."""
    if isinstance(axis_names, str):
        k = lax.axis_size(axis_names)
        head = lax.ppermute(
            shard[:halo], axis_names, perm=[(i, (i - 1) % k) for i in range(k)]
        )
        return jnp.concatenate([shard, head])
    # flatten (a, b, ...) into one logical ring: permute fastest axis cyclically,
    # and at its boundary carry into the slower axes via a second permute.
    names = tuple(axis_names)
    head = shard[:halo]
    # Build the flattened ring permutation as a composition of per-axis
    # ppermutes is fragile; instead use ppermute over each axis with the
    # boundary-carry trick: receive from (flat+1), i.e. send to (flat-1).
    fast = names[-1]
    kf = lax.axis_size(fast)
    # everyone sends head to previous rank on fast axis
    recv_fast = lax.ppermute(head, fast, perm=[(i, (i - 1) % kf) for i in range(kf)])
    if len(names) == 1:
        return jnp.concatenate([shard, recv_fast])
    # ranks whose fast index == kf-1 must instead receive from the next slow
    # rank's fast index 0. recv_fast at those ranks currently holds the head of
    # fast index 0 of the SAME slow rank; fix by shifting that value along the
    # slow axes for boundary ranks.
    slow = names[:-1]
    carried = recv_fast
    for a in reversed(slow):
        k = lax.axis_size(a)
        carried = lax.ppermute(carried, a, perm=[(i, (i - 1) % k) for i in range(k)])
    at_boundary = lax.axis_index(fast) == kf - 1
    head_next = jnp.where(at_boundary, carried, recv_fast)
    return jnp.concatenate([shard, head_next])


def make_distributed_find(mesh, axis_names: AxisNames = "data", *, algo: str = "auto"):
    """Build a shard_map'ed (text, pattern) -> mask function over `mesh`."""
    spec = P(axis_names)

    def local(text_shard: jnp.ndarray, pattern: jnp.ndarray) -> jnp.ndarray:
        m = pattern.shape[0]
        ln = text_shard.shape[0]
        ext = _next_rank_halo(text_shard, m - 1, axis_names) if m > 1 else text_shard
        mask = epsm.find(ext, pattern, algo=algo)[:ln]
        # the last shard's halo wraps to shard 0: kill starts that would cross
        # the global end of the text.
        k = _axis_size(axis_names)
        is_last = _flat_index(axis_names) == k - 1
        tail_ok = jnp.arange(ln) <= (ln - m)
        return jnp.where(is_last, mask & tail_ok, mask)

    fn = jax.shard_map(
        local, mesh=mesh, in_specs=(spec, P()), out_specs=spec, check_vma=False
    )
    return fn


def make_distributed_count(mesh, axis_names: AxisNames = "data", *, algo: str = "auto"):
    find_fn_local_spec = P(axis_names)

    def local(text_shard: jnp.ndarray, pattern: jnp.ndarray) -> jnp.ndarray:
        m = pattern.shape[0]
        ln = text_shard.shape[0]
        ext = _next_rank_halo(text_shard, m - 1, axis_names) if m > 1 else text_shard
        mask = epsm.find(ext, pattern, algo=algo)[:ln]
        k = _axis_size(axis_names)
        is_last = _flat_index(axis_names) == k - 1
        tail_ok = jnp.arange(ln) <= (ln - m)
        mask = jnp.where(is_last, mask & tail_ok, mask)
        local_count = mask.sum(dtype=jnp.int32)
        return lax.psum(local_count, axis_names)

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(find_fn_local_spec, P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn
