"""Collective-communication utilities (beyond-paper distributed tricks).

These are the explicit shard_map-level tools used by the §Perf hillclimb and
the multi-pod trainer; the baseline path lets XLA SPMD insert collectives
from sharding annotations alone.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def compressed_psum_grads(grads, mesh: Mesh, axis: str = "pod",
                          dtype=jnp.bfloat16):
    """Cross-pod gradient all-reduce with on-the-wire compression.

    Baseline cross-pod sync moves grads at their native dtype; this halves
    (bf16) the slowest-link traffic by casting inside a shard_map around the
    psum, restoring f32 master precision after. Use when the batch is
    replicated (not sharded) across `axis`.
    """
    other = tuple(a for a in mesh.axis_names if a != axis)

    def one(g):
        spec = P(*((None,) * g.ndim))

        @functools.partial(shard_map, mesh=mesh, in_specs=(spec,),
                           out_specs=spec)
        def reduce_(x):
            return jax.lax.psum(x.astype(dtype), axis).astype(jnp.float32) \
                / mesh.shape[axis]

        return reduce_(g)

    return jax.tree.map(one, grads)


def ep_all_to_all(x: jax.Array, mesh: Mesh, axis: str = "model",
                  split_dim: int = 0, concat_dim: int = 0) -> jax.Array:
    """Expert-parallel dispatch all-to-all along `axis` (hillclimb variant)."""
    n = mesh.shape[axis]
    spec_in = P(axis)

    @functools.partial(shard_map, mesh=mesh, in_specs=(spec_in,),
                       out_specs=spec_in)
    def a2a(t):
        return jax.lax.all_to_all(t, axis, split_dim, concat_dim,
                                  tiled=True)

    return a2a(x)


def estimate_collective_bytes(n_bytes: int, group: int,
                              kind: str) -> float:
    """Ring-algorithm per-device wire bytes for a collective over a group."""
    if group <= 1:
        return 0.0
    if kind == "all-reduce":
        return 2.0 * n_bytes * (group - 1) / group
    if kind in ("all-gather", "reduce-scatter", "all-to-all"):
        return float(n_bytes) * (group - 1) / group
    if kind == "collective-permute":
        return float(n_bytes)
    raise ValueError(kind)
