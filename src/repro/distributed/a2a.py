"""Explicit all-to-all schedules for MoE dispatch/combine (shard_map).

The GSPMD baseline reshards the dispatch buffers with two
with_sharding_constraint flips (moe.py) and lets the partitioner choose
the collectives. These helpers make the shuffle EXPLICIT — the device-side
mirror of the paper's §4 data shuffle:

* ``a2a``          — one jax.lax.all_to_all over the model axis.
* ``a2a_chunked``  — the transfer split into ``n_chunks`` pieces issued
  inside a scan so the expert GEMM of chunk i overlaps the all-to-all of
  chunk i+1 (the paper's batching/overlap guideline GL2 applied to ICI).

All functions run INSIDE shard_map (per-shard views).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def a2a(x, axis_name: str, *, split_axis: int, concat_axis: int):
    """Tiled all-to-all: redistributes the ``split_axis`` dim across the
    mesh axis, gathering shards along ``concat_axis``."""
    return jax.lax.all_to_all(x, axis_name, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)


def a2a_chunked(x, axis_name: str, *, split_axis: int, concat_axis: int,
                n_chunks: int, chunk_axis: int):
    """All-to-all in n_chunks pieces along ``chunk_axis`` (a scan): lets
    the compiler overlap chunk i's compute with chunk i+1's transfer."""
    if n_chunks <= 1:
        return a2a(x, axis_name, split_axis=split_axis,
                   concat_axis=concat_axis)
    parts = jnp.split(x, n_chunks, axis=chunk_axis)
    outs = [a2a(p, axis_name, split_axis=split_axis,
                concat_axis=concat_axis) for p in parts]
    return jnp.concatenate(outs, axis=chunk_axis)


def moe_dispatch_combine(mesh: Mesh, batch_axes, *, n_chunks: int = 1):
    """Returns (dispatch, combine) callables operating on GLOBAL arrays
    shaped (B, G, Ee, C, D) with G sharded over 'model' (group-local
    buffers) ↔ (B, G, Ee, C, D) with Ee sharded over 'model'
    (expert-local buffers). Explicit shard_map all-to-all replaces the
    GSPMD constraint-flip resharding."""
    bspec = P(batch_axes) if batch_axes else P()

    g_spec = P(batch_axes or None, "model", None, None, None)
    e_spec = P(batch_axes or None, None, "model", None, None)

    @partial(jax.shard_map, mesh=mesh, in_specs=(g_spec,), out_specs=e_spec,
             check_vma=False)
    def dispatch(x):          # local: (B_l, G/16, Ee, C, D)
        return a2a_chunked(x, "model", split_axis=2, concat_axis=1,
                           n_chunks=n_chunks, chunk_axis=3)

    @partial(jax.shard_map, mesh=mesh, in_specs=(e_spec,), out_specs=g_spec,
             check_vma=False)
    def combine(y):           # local: (B_l, G, Ee/16, C, D)
        return a2a_chunked(y, "model", split_axis=1, concat_axis=2,
                           n_chunks=n_chunks, chunk_axis=3)

    return dispatch, combine
