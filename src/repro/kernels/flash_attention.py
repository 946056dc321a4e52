"""Flash attention (fwd) as a Pallas TPU kernel.

Blocked online-softmax attention with causal / sliding-window masking and
gemma2-style logit softcapping. TPU adaptation of the CUDA flash kernel:

  * block shapes are MXU-aligned (q/k blocks multiples of 128 on real
    shapes; tests use smaller aligned tiles);
  * running max/denominator and the output accumulator live in VMEM
    scratch across the innermost (kv) grid dimension;
  * instead of the GPU's warp-level reductions, whole-block ``jnp`` reduce
    ops run on the VPU; the (bq x bk) score tile feeds the MXU.

Grid: (batch*heads, n_q_blocks, n_kv_blocks), kv innermost so the scratch
accumulator carries across kv steps for a fixed q block.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.3819763e38


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
               bq: int, bk: int, nk: int, scale: float, causal: bool,
               window: int, softcap: Optional[float]):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    i = pl.program_id(1)
    q_start = i * bq
    k_start = j * bk

    # skip fully-masked blocks (causal: kv block entirely in the future;
    # window: kv block entirely before the window)
    live = True
    if causal:
        live = k_start <= q_start + bq - 1
    if window > 0:
        live = jnp.logical_and(live, k_start + bk - 1 > q_start - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)              # (bq, d)
        k = k_ref[0].astype(jnp.float32)              # (bk, d)
        v = v_ref[0].astype(jnp.float32)              # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        qi = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kj = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            ok &= kj <= qi
        if window > 0:
            ok &= kj > qi - window
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[...]                           # (bq,)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(ok, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = (acc_ref[...] * alpha[:, None]
                        + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                              preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention_3d(q: jax.Array, k: jax.Array, v: jax.Array, *,
                       causal: bool = True, window: int = 0,
                       softcap: Optional[float] = None,
                       block_q: int = 128, block_k: int = 128,
                       interpret: bool = False) -> jax.Array:
    """q/k/v: (BH, S, D) — flattened batch*heads. Returns (BH, Sq, D)."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    assert sq % bq == 0 and sk % bk == 0, (sq, sk, bq, bk)
    grid = (bh, sq // bq, sk // bk)
    kernel = functools.partial(
        _fa_kernel, bq=bq, bk=bk, nk=grid[2], scale=1.0 / math.sqrt(d),
        causal=causal, window=window, softcap=softcap)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),   # output accumulator
            pltpu.VMEM((bq,), jnp.float32),     # running max
            pltpu.VMEM((bq,), jnp.float32),     # running denominator
        ],
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
