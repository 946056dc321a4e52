"""GQA attention: full/sliding-window/prefix-bidirectional, train + decode.

TPU-adaptation notes (DESIGN.md §2): the XLA path below is the
dry-run/roofline implementation (identical FLOPs to the fused kernel); on real
TPU hardware `attention_impl="pallas"` routes the no-cache path through the
flash-attention Pallas kernel in repro.kernels. GQA always expands KV to the
full head count at use — KV *storage* stays at n_kv heads (cache memory), while
the flattened head dim shards cleanly on the `model` mesh axis.

Decode attends over a KV cache that may be sharded along *sequence* (the
long-context path): softmax over a sharded axis lowers to a
logsumexp-combining all-reduce (distributed flash-decode).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.common import ModelConfig
from repro.models.layers import ParamSpec, Specs, apply_rope

NEG_INF = -2.3819763e38   # bf16-safe large negative


def attn_specs(cfg: ModelConfig, path: str = "attn") -> Specs:
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    return {
        f"{path}/wq": ParamSpec((d, H, hd), ("embed", "heads", "head_dim"),
                                fan_in=d),
        f"{path}/wk": ParamSpec((d, KV, hd), ("embed", "kv_heads", "head_dim"),
                                fan_in=d),
        f"{path}/wv": ParamSpec((d, KV, hd), ("embed", "kv_heads", "head_dim"),
                                fan_in=d),
        f"{path}/wo": ParamSpec((H, hd, d), ("heads", "head_dim", "embed"),
                                fan_in=H * hd),
    }


def _mask_bias(sq: int, sk: int, q_offset: jax.Array, kind: str,
               window: int, prefix_len: int, causal: bool) -> jax.Array:
    """(sq, sk) additive f32 bias built from iotas (XLA fuses it)."""
    qi = q_offset + jnp.arange(sq)[:, None]          # absolute q positions
    kj = jnp.arange(sk)[None, :]
    if causal:
        ok = kj <= qi
    else:
        ok = jnp.ones((sq, sk), bool)
    if kind == "attn_local" and window > 0:
        ok &= kj > qi - window
    if prefix_len > 0:   # vlm: bidirectional among the first prefix_len tokens
        both_prefix = (qi < prefix_len) & (kj < prefix_len)
        ok |= both_prefix
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def _sdpa(q: jax.Array, k: jax.Array, v: jax.Array, bias: jax.Array,
          softcap: Optional[float]) -> jax.Array:
    """q: (B,Sq,H,hd), k/v: (B,Sk,H,hd), bias: (Sq,Sk) or (B,1,Sq,Sk)."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        scores = jnp.tanh(scores / softcap) * softcap
    scores = scores + (bias if bias.ndim == 4 else bias[None, None, :, :])
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def _expand_kv(k: jax.Array, q_per_kv: int) -> jax.Array:
    if q_per_kv == 1:
        return k
    return jnp.repeat(k, q_per_kv, axis=2)


def attn_apply(p: Dict, x: jax.Array, cfg: ModelConfig, kind: str,
               positions: jax.Array, constrain,
               cache: Optional[Dict] = None,
               cache_index: Optional[jax.Array] = None,
               prefix_len: int = 0, causal: bool = True,
               impl: str = "xla") -> Tuple[jax.Array, Optional[Dict]]:
    """x: (B,S,D). cache: {"k","v"}: (B,Smax,KV,hd) -> updated cache."""
    B, S, D = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = constrain(q, ("act_batch", "act_seq", "act_heads", None))

    if cache is None:
        kf = _expand_kv(k, cfg.q_per_kv)
        vf = _expand_kv(v, cfg.q_per_kv)
        kf = constrain(kf, ("act_batch", "act_kv_seq", "act_heads", None))
        vf = constrain(vf, ("act_batch", "act_kv_seq", "act_heads", None))
        window = cfg.window if kind == "attn_local" else 0
        if impl == "pallas" and prefix_len == 0:
            from repro.kernels import ops as kops

            out = kops.flash_attention(q, kf, vf, causal=causal,
                                       window=window,
                                       softcap=cfg.attn_softcap)
        elif impl == "blocked" and prefix_len == 0:
            from repro.models.blocked_attention import blocked_attention

            out = blocked_attention(q, kf, vf, causal, window,
                                    cfg.attn_softcap)
        else:
            bias = _mask_bias(S, S, jnp.asarray(0), kind, window,
                              prefix_len, causal)
            out = _sdpa(q, kf, vf, bias, cfg.attn_softcap)
        new_cache = None
    elif cache["pos"].ndim == 1:
        # decode (lockstep): ring-buffer cache insert, then attend over the
        # cache. Slot positions are tracked explicitly ("pos"), so local
        # layers can cap their cache at the window size (the long_500k
        # memory story) — keys are RoPE'd with absolute positions before
        # insertion, so slot order is irrelevant to the scores.
        idx = cache_index if cache_index is not None else jnp.asarray(0)
        W = cache["k"].shape[1]
        slot = jnp.mod(idx, W)
        ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                          (0, slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                          (0, slot, 0, 0))
        pos = jax.lax.dynamic_update_slice(
            cache["pos"], (idx + jnp.arange(S)).astype(cache["pos"].dtype),
            (slot,))
        new_cache = {"k": ck, "v": cv, "pos": pos}
        kf = _expand_kv(ck, cfg.q_per_kv)
        vf = _expand_kv(cv, cfg.q_per_kv)
        kf = constrain(kf, ("act_batch", "cache_seq", "act_heads", None))
        vf = constrain(vf, ("act_batch", "cache_seq", "act_heads", None))
        qi = idx + jnp.arange(S)[:, None]            # S==1 for decode
        kj = pos[None, :]                            # absolute key positions
        ok = (kj <= qi) & (kj >= 0)
        if kind == "attn_local" and cfg.window > 0:
            ok &= kj > qi - cfg.window
        bias = jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)
        out = _sdpa(q, kf, vf, bias, cfg.attn_softcap)
    else:
        # decode (continuous batching): per-slot indices "pos" (B, W).
        # cache_index is (B,); a NEGATIVE index marks an inactive slot —
        # its cache/pos are left untouched and its output is garbage the
        # batcher ignores.
        assert S == 1, "per-slot decode is one token per step"
        idxv = jnp.broadcast_to(cache_index, (B,)).astype(jnp.int32)
        W = cache["k"].shape[1]
        write = idxv >= 0
        slot = jnp.mod(jnp.maximum(idxv, 0), W)
        bidx = jnp.arange(B)
        k_new = jnp.where(write[:, None, None], k[:, 0].astype(cache["k"].dtype),
                          cache["k"][bidx, slot])
        v_new = jnp.where(write[:, None, None], v[:, 0].astype(cache["v"].dtype),
                          cache["v"][bidx, slot])
        ck = cache["k"].at[bidx, slot].set(k_new)
        cv = cache["v"].at[bidx, slot].set(v_new)
        pos_new = jnp.where(write, idxv, cache["pos"][bidx, slot])
        pos = cache["pos"].at[bidx, slot].set(pos_new)
        new_cache = {"k": ck, "v": cv, "pos": pos}
        kf = _expand_kv(ck, cfg.q_per_kv)
        vf = _expand_kv(cv, cfg.q_per_kv)
        qi = idxv[:, None, None, None]               # (B,1,1,1)
        kj = pos[:, None, None, :]                   # (B,1,1,W)
        ok = (kj <= qi) & (kj >= 0)
        if kind == "attn_local" and cfg.window > 0:
            ok &= kj > qi - cfg.window
        bias = jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)
        out = _sdpa(q, kf, vf, bias, cfg.attn_softcap)

    out = constrain(out, ("act_batch", "act_seq", "act_heads", None))
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    return y, new_cache


def init_cache(cfg: ModelConfig, batch: int, max_seq: int,
               dtype=jnp.bfloat16) -> Dict:
    """One attention layer's empty ring cache (pos = -1 means empty slot)."""
    return {"k": jnp.zeros((batch, max_seq, cfg.n_kv_heads, cfg.head_dim), dtype),
            "v": jnp.zeros((batch, max_seq, cfg.n_kv_heads, cfg.head_dim), dtype),
            "pos": jnp.full((max_seq,), -1, jnp.int32)}
