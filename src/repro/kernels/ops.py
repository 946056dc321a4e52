"""jit'd public wrappers over the Pallas kernels (+ padding & dispatch).

On a TPU the kernels compile through Mosaic. On the CPU platform (the
tests run with `JAX_PLATFORMS=cpu`) they run in Pallas interpret mode. Any
other platform is an error, and so is a CPU fallback in a process that
could not open the host's TPU: the kernels never hide a missing device.
`ref.py` holds the pure-jnp oracles tests compare against.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.spans import span
from repro.kernels import filter_compact as _fc
from repro.kernels import flash_attention as _fa
from repro.kernels import groupby_agg as _gb

# Row block of the filter and group-by kernels. XLA tiles a long rank-1
# 32-bit array T(1024), and Mosaic refuses a block whose tiling differs.
ROW_BLOCK = 1024
# The group-by kernel holds a (ROW_BLOCK, groups) f32 one-hot and a
# (groups,) accumulator in VMEM. On a TPU v5e every aggregate compiles at
# 4096 groups, and count runs out of scoped VMEM at 8192.
MAX_GROUPS = 4096


def _interpret() -> bool:
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform != "cpu":
        raise RuntimeError(f"the Pallas kernels compile for 'tpu' and run in "
                           f"interpret mode on 'cpu'; platform {platform!r} "
                           "has neither")
    try:
        jax.devices("tpu")
    except RuntimeError as e:
        # JAX falls back to the CPU on its own when the TPU cannot be
        # opened, e.g. because another process holds the chip
        if "failed to initialize" in str(e):
            raise RuntimeError(
                "this process runs on the CPU because it could not open the "
                f"TPU ({e}). A chip belongs to one process: run device "
                "operators in the process that holds it, or set "
                "JAX_PLATFORMS=cpu to interpret the kernels on the CPU") from e
    return True


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "block_q", "block_k"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, window: int = 0,
                    softcap: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128) -> jax.Array:
    """q/k/v: (B, S, H, D), heads pre-expanded (GQA repeat). -> (B,S,H,D)."""
    B, S, H, D = q.shape
    to3 = lambda t: t.transpose(0, 2, 1, 3).reshape(B * H, t.shape[1], D)
    out = _fa.flash_attention_3d(to3(q), to3(k), to3(v), causal=causal,
                                 window=window, softcap=softcap,
                                 block_q=block_q, block_k=block_k,
                                 interpret=_interpret())
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# group-by aggregation
# ---------------------------------------------------------------------------


def _pad_to(x: jax.Array, mult: int, fill) -> jax.Array:
    """Pad the leading dim to a positive multiple of `mult`."""
    n = x.shape[0]
    p = max((n + mult - 1) // mult, 1) * mult - n
    if p == 0:
        return x
    return jnp.concatenate([x, jnp.full((p,), fill, x.dtype)])


def _lane_pad(n_groups: int) -> int:
    return max((n_groups + 127) // 128 * 128, 128)


def _check_groups(n_groups: int) -> None:
    if n_groups > MAX_GROUPS:
        raise ValueError(
            f"group-by over {n_groups} groups exceeds the device kernel's "
            f"limit of MAX_GROUPS={MAX_GROUPS}: its ({ROW_BLOCK}, groups) "
            "one-hot must fit VMEM; aggregate on the host (backend='numpy')")


@functools.partial(jax.jit, static_argnames=("n_groups", "fn"))
def groupby_aggregate(values: jax.Array, codes: jax.Array, n_groups: int,
                      fn: str = "sum") -> jax.Array:
    """Segment aggregate via the Pallas kernel. values (N,), codes (N,)."""
    _check_groups(n_groups)
    ng_pad = _lane_pad(n_groups)
    vals = _pad_to(values.astype(jnp.float32), ROW_BLOCK, 0.0)
    # padded rows get code n_groups: a group that is sliced off
    # (n_groups < ng_pad) or outside the kernel's one-hot
    cds = _pad_to(codes.astype(jnp.int32), ROW_BLOCK, n_groups)
    if fn == "mean":
        s = _gb.groupby_pallas(vals, cds, ng_pad, "sum", ROW_BLOCK,
                               _interpret())
        c = _gb.groupby_pallas(vals, cds, ng_pad, "count", ROW_BLOCK,
                               _interpret())
        out = s / jnp.maximum(c, 1.0)
    else:
        out = _gb.groupby_pallas(vals, cds, ng_pad, fn, ROW_BLOCK,
                                 _interpret())
    return out[:n_groups]


@functools.partial(jax.jit, static_argnames=("n_groups", "fn", "block_p"))
def combine_aggregate(parts: jax.Array, n_groups: int, fn: str = "sum",
                      block_p: int = 8) -> jax.Array:
    """Merge stacked per-shard partial aggregates: parts (P, n_groups), one
    row per shard, cells absent from a shard pre-filled with the merge op's
    neutral element. Returns the (n_groups,) combined aggregate. mean never
    reaches this point — it travels as a sum+count pair and is finalized by
    the caller."""
    if fn not in ("sum", "count", "min", "max"):
        raise ValueError(f"{fn!r} is not a distributive combine")
    neutral = {"sum": 0.0, "count": 0.0,
               "min": jnp.inf, "max": -jnp.inf}[fn]
    p, g = parts.shape
    g_pad = _lane_pad(g)
    bp = min(block_p, max(p, 1))
    p_pad = (p + bp - 1) // bp * bp
    padded = jnp.full((p_pad, g_pad), neutral, jnp.float32)
    padded = padded.at[:p, :g].set(parts.astype(jnp.float32))
    out = _gb.combine_pallas(padded, fn, bp, _interpret())
    return out[:n_groups]


# ---------------------------------------------------------------------------
# filter compaction
# ---------------------------------------------------------------------------


@jax.jit
def compact(mask: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Returns (indices (N,), count): indices[:count] = survivors ascending."""
    n = mask.shape[0]
    m = _pad_to(mask.astype(jnp.int32), ROW_BLOCK, 0)[None, :]    # (1, N')
    counts = _fc.block_counts(m, ROW_BLOCK, _interpret())         # (nb,)
    tiles = _fc.block_compact(m, ROW_BLOCK, _interpret())         # (nb, bn)
    offsets = jnp.cumsum(counts) - counts                         # exclusive
    slot = jnp.arange(ROW_BLOCK)[None, :]
    valid = slot < counts[:, None]
    dst = jnp.where(valid, offsets[:, None] + slot, n)            # (nb, bn)
    out = jnp.full((n + 1,), n - 1, jnp.int32)
    out = out.at[dst.reshape(-1)].set(tiles.reshape(-1))
    return out[:n], jnp.sum(counts)


# ---------------------------------------------------------------------------
# host-facing entry points (numpy in, numpy out)
# ---------------------------------------------------------------------------
#
# A streamed scan hands the operators chunks whose lengths all differ, and a
# jitted wrapper compiles once per input shape. These entry points pad the
# rows to a power of two of at least ROW_BLOCK, with rows that change no
# result, so a whole scan compiles a few programs rather than one per chunk.
# Each call is one `op.kernel` span (core/spans.py): the host pad, the copy
# to the device, dispatch, the wait for the result and the copy back.


def _bucket(n: int) -> int:
    return max(ROW_BLOCK, 1 << max(n - 1, 0).bit_length())


def _pad_rows(x: np.ndarray, rows: int, fill) -> np.ndarray:
    out = np.full(rows, fill, x.dtype)
    out[:x.shape[0]] = x
    return out


def compact_indices(mask) -> np.ndarray:
    """The surviving indices of a host boolean mask, ascending."""
    mask = np.asarray(mask, bool)
    rows = _bucket(mask.size)
    with span("op.kernel", kernel="compact", padded_rows=rows,
              bytes_in=4 * rows, bytes_out=4 * rows, compiles=0):
        idx, count = compact(jnp.asarray(_pad_rows(mask, rows, False)))
        return np.asarray(idx)[: int(count)]


def groupby_aggregate_rows(values, codes, n_groups: int,
                           fn: str = "sum") -> np.ndarray:
    """`groupby_aggregate` of host arrays. The group count is padded to the
    kernel's lane multiple as well; padded rows take code `ng_pad`, outside
    the kernel's one-hot, so they reach no group."""
    _check_groups(n_groups)
    ng_pad = _lane_pad(n_groups)
    rows = _bucket(len(values))
    with span("op.kernel", kernel="groupby_aggregate", fn=fn,
              padded_rows=rows, bytes_in=8 * rows, bytes_out=4 * ng_pad,
              compiles=0):
        vals = _pad_rows(np.asarray(values, np.float32), rows, 0.0)
        cds = _pad_rows(np.asarray(codes, np.int32), rows, ng_pad)
        out = groupby_aggregate(jnp.asarray(vals), jnp.asarray(cds), ng_pad,
                                fn)
        return np.asarray(out)[:n_groups]
