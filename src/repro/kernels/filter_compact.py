"""Predicate filter + compaction as Pallas TPU kernels.

The paper's `euro_selection` hot spot: evaluate a mask, then gather the
surviving row indices contiguously. The GPU idiom (warp ballot + atomic
offset) has no TPU analogue; instead:

  pass 1 (kernel): per-block survivor counts           (grid over row blocks)
  stitch (XLA):    exclusive cumsum -> per-block base offsets
  pass 2 (kernel): per-block local compaction on the MXU, emitting
                   (block, slot) -> row-index tiles
  stitch (XLA):    scatter tiles to base offsets (static shapes end to end).

Layout: the mask travels as a (1, N) int32 row, so every block is a
(1, bn) slice of a row whose sublane dim is the full array dim, and bn is a
multiple of 128 (the (8, 128) tiling rule). Mosaic has no cumsum, so pass 2
computes the in-block prefix count as a matmul with a strictly upper
triangular 0/1 matrix, and the permutation as a matmul with a one-hot
(slot, row) matrix. Every operand is 0/1 or < 32, exact in bf16, and the
f32 accumulation is exact for block sizes below 2**24.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_LANES = 128
# row indices inside a block split as 32*hi + lo so each factor is exact in bf16
_SPLIT = 32


def _count_kernel(mask_ref, o_ref):
    o_ref[...] = jnp.full(o_ref.shape, jnp.sum(mask_ref[...]), jnp.int32)


def block_counts(mask: jax.Array, block_n: int, interpret: bool = False
                 ) -> jax.Array:
    """mask: (1, N) int32 of 0/1, N % block_n == 0. Returns (N // block_n,)
    survivor counts; each block writes its count into a lane-aligned
    (1, 128) output block."""
    n = mask.shape[1]
    assert n % block_n == 0 and block_n % _LANES == 0, (n, block_n)
    nb = n // block_n
    out = pl.pallas_call(
        _count_kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((1, block_n), lambda b: (0, b))],
        out_specs=pl.BlockSpec((1, _LANES), lambda b: (0, b)),
        out_shape=jax.ShapeDtypeStruct((1, nb * _LANES), jnp.int32),
        interpret=interpret,
    )(mask)
    return out.reshape(nb, _LANES)[:, 0]


def _compact_kernel(mask_ref, o_ref, *, bn: int):
    b = pl.program_id(0)
    m = mask_ref[...]                                   # (1, bn) int32
    i = jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 1)
    # exclusive prefix count: pos[r] = #survivors before row r in this block
    upper = (i < j).astype(jnp.bfloat16)
    pos = jnp.dot(m.astype(jnp.bfloat16), upper,
                  preferred_element_type=jnp.float32).astype(jnp.int32)
    pos = jnp.where(m != 0, pos, bn)                    # dead rows -> slot bn
    # one-hot permutation, slots on sublanes and rows on lanes:
    # slot s receives row r iff pos[r] == s
    perm = (pos == i).astype(jnp.bfloat16)              # (bn slots, bn rows)
    r = jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)
    nt = (((1,), (1,)), ((), ()))
    hi = jax.lax.dot_general((r // _SPLIT).astype(jnp.bfloat16), perm, nt,
                             preferred_element_type=jnp.float32)
    lo = jax.lax.dot_general((r % _SPLIT).astype(jnp.bfloat16), perm, nt,
                             preferred_element_type=jnp.float32)
    local = (hi * _SPLIT + lo).astype(jnp.int32)        # (1, bn)
    o_ref[...] = b * bn + local


def block_compact(mask: jax.Array, block_n: int, interpret: bool = False
                  ) -> jax.Array:
    """mask: (1, N) int32 of 0/1. Returns (N // block_n, block_n) tiles:
    tile b's first count[b] slots hold block b's surviving row indices in
    ascending order; the other slots hold b * block_n."""
    n = mask.shape[1]
    assert n % block_n == 0 and block_n % _LANES == 0, (n, block_n)
    out = pl.pallas_call(
        functools.partial(_compact_kernel, bn=block_n),
        grid=(n // block_n,),
        in_specs=[pl.BlockSpec((1, block_n), lambda b: (0, b))],
        out_specs=pl.BlockSpec((1, block_n), lambda b: (0, b)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.int32),
        interpret=interpret,
    )(mask)
    return out.reshape(n // block_n, block_n)
