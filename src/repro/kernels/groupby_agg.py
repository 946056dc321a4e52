"""Group-by aggregation (segment reduce) as a Pallas TPU kernel.

The paper's `usd_by_country` hot spot. GPU implementations hash with atomic
CAS; TPU has no atomics, so each row block builds a one-hot (rows x groups)
tile and reduces it on the MXU/VPU into a per-kernel-instance VMEM
accumulator; the final grid step writes the (groups,) result. Group count is
padded to a lane multiple (128).

Supports sum / count / min / max (mean = sum/count in ops.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_INIT = {"sum": 0.0, "count": 0.0, "min": jnp.inf, "max": -jnp.inf}


def _gb_kernel(vals_ref, codes_ref, o_ref, acc_ref, *,
               bn: int, ng: int, n_blocks: int, fn: str):
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref, _INIT[fn])

    vals = vals_ref[...].astype(jnp.float32)          # (bn,)
    codes = codes_ref[...]                            # (bn,) int32
    groups = jax.lax.broadcasted_iota(jnp.int32, (bn, ng), 1)
    onehot = codes[:, None] == groups                 # (bn, ng)
    if fn == "sum":
        part = jnp.sum(jnp.where(onehot, vals[:, None], 0.0), axis=0)
        acc_ref[...] += part
    elif fn == "count":
        # padded rows carry code == ng (out of range) -> contribute nothing
        part = jnp.sum(onehot.astype(jnp.float32), axis=0)
        acc_ref[...] += part
    elif fn == "min":
        part = jnp.min(jnp.where(onehot, vals[:, None], jnp.inf), axis=0)
        acc_ref[...] = jnp.minimum(acc_ref[...], part)
    elif fn == "max":
        part = jnp.max(jnp.where(onehot, vals[:, None], -jnp.inf), axis=0)
        acc_ref[...] = jnp.maximum(acc_ref[...], part)

    @pl.when(b == n_blocks - 1)
    def _finalize():
        o_ref[...] = acc_ref[...]


def _combine_kernel(parts_ref, o_ref, acc_ref, *, bp: int, ng: int,
                    n_blocks: int, fn: str):
    """Combine accumulator: each grid step folds a (bp, ng) tile of per-shard
    partial aggregates into the (ng,) VMEM accumulator with the agg's merge
    op — sum for sum/count, elementwise min/max otherwise. Padded part rows
    carry the op's neutral element."""
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref, _INIT[fn])

    tile = parts_ref[...].astype(jnp.float32)         # (bp, ng)
    if fn in ("sum", "count"):
        acc_ref[...] += jnp.sum(tile, axis=0)
    elif fn == "min":
        acc_ref[...] = jnp.minimum(acc_ref[...], jnp.min(tile, axis=0))
    elif fn == "max":
        acc_ref[...] = jnp.maximum(acc_ref[...], jnp.max(tile, axis=0))

    @pl.when(b == n_blocks - 1)
    def _finalize():
        o_ref[...] = acc_ref[...]


def combine_pallas(parts: jax.Array, fn: str = "sum", block_p: int = 8,
                   interpret: bool = False) -> jax.Array:
    """parts: (P, G) stacked per-shard partial aggregates, one row per shard,
    G % 128 == 0 and P % block_p == 0 (ops.py pads with the neutral
    element). Returns the (G,) merged aggregate."""
    p, g = parts.shape
    bp = min(block_p, p)
    assert p % bp == 0, (p, bp)
    grid = (p // bp,)
    kernel = functools.partial(_combine_kernel, bp=bp, ng=g,
                               n_blocks=grid[0], fn=fn)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bp, g), lambda b: (b, 0))],
        out_specs=pl.BlockSpec((g,), lambda b: (0,)),
        out_shape=jax.ShapeDtypeStruct((g,), jnp.float32),
        scratch_shapes=[pltpu.VMEM((g,), jnp.float32)],
        interpret=interpret,
        name="groupby_combine",
    )(parts)


def groupby_pallas(values: jax.Array, codes: jax.Array, n_groups: int,
                   fn: str = "sum", block_n: int = 1024,
                   interpret: bool = False) -> jax.Array:
    """values: (N,) float, codes: (N,) int32. N and n_groups pre-padded by
    ops.py (N % block_n == 0, n_groups % 128 == 0; pad codes == n_groups)."""
    n = values.shape[0]
    bn = min(block_n, n)
    assert n % bn == 0, (n, bn)
    grid = (n // bn,)
    kernel = functools.partial(_gb_kernel, bn=bn, ng=n_groups,
                               n_blocks=grid[0], fn=fn)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bn,), lambda b: (b,)),
                  pl.BlockSpec((bn,), lambda b: (b,))],
        out_specs=pl.BlockSpec((n_groups,), lambda b: (0,)),
        out_shape=jax.ShapeDtypeStruct((n_groups,), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n_groups,), jnp.float32)],
        interpret=interpret,
        name="groupby_segment",
    )(values, codes)
