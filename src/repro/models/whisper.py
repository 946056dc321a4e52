"""Whisper-style encoder–decoder (audio backbone; conv frontend stubbed).

Per the assignment, the modality frontend is a STUB: ``input_specs()``
provides precomputed frame embeddings (B, T_enc, d_model) — the log-mel +
conv1d stack is out of scope. The transformer backbone is complete:
bidirectional encoder, causal decoder with cross-attention, ring-buffer
self-attention cache for decode, and precomputed cross-attention K/V cache.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.common import ModelConfig
from repro.models import attention, layers
from repro.models.layers import ParamSpec, Specs

import math


def _cross_specs(cfg: ModelConfig, path: str) -> Specs:
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


def whisper_specs(cfg: ModelConfig) -> Specs:
    specs: Specs = {}
    specs.update(layers.embed_specs(cfg.padded_vocab, cfg.d_model,
                                    cfg.tie_embeddings))
    enc: Specs = {}
    enc.update(layers.rms_norm_specs(cfg.d_model, "pre_norm"))
    enc.update(attention.attn_specs(cfg, "attn"))
    enc.update(layers.rms_norm_specs(cfg.d_model, "pre_ffn_norm"))
    enc.update(layers.ffn_specs(cfg.d_model, cfg.d_ff, cfg.act, "ffn",
                                gated=cfg.ffn_gated))
    specs.update(layers.stacked(enc, cfg.encoder_layers, prefix="blocks/"))
    specs.update(layers.rms_norm_specs(cfg.d_model, "enc_norm"))
    dec: Specs = {}
    dec.update(layers.rms_norm_specs(cfg.d_model, "pre_norm"))
    dec.update(attention.attn_specs(cfg, "attn"))
    dec.update(layers.rms_norm_specs(cfg.d_model, "pre_cross_norm"))
    dec.update(_cross_specs(cfg, "cross"))
    dec.update(layers.rms_norm_specs(cfg.d_model, "pre_ffn_norm"))
    dec.update(layers.ffn_specs(cfg.d_model, cfg.d_ff, cfg.act, "ffn",
                                gated=cfg.ffn_gated))
    specs.update(layers.stacked(dec, cfg.n_layers, prefix="decoder_blocks/"))
    specs.update(layers.rms_norm_specs(cfg.d_model, "final_norm"))
    return specs


def _cross_attend(p: Dict, x: jax.Array, ck: jax.Array, cv: jax.Array,
                  cfg: ModelConfig) -> jax.Array:
    """x: (B,S,D); ck/cv: (B,T,KV,hd) precomputed from encoder output."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    kf = attention._expand_kv(ck, cfg.q_per_kv)
    vf = attention._expand_kv(cv, cfg.q_per_kv)
    scale = 1.0 / math.sqrt(cfg.head_dim)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, kf,
                        preferred_element_type=jnp.float32) * scale
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(vf.dtype), vf,
                     preferred_element_type=jnp.float32).astype(x.dtype)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"],
                      preferred_element_type=jnp.float32).astype(x.dtype)


def _cross_kv(p: Dict, enc_out: jax.Array) -> Tuple[jax.Array, jax.Array]:
    k = jnp.einsum("btd,dhk->bthk", enc_out, p["wk"],
                   preferred_element_type=jnp.float32).astype(enc_out.dtype)
    v = jnp.einsum("btd,dhk->bthk", enc_out, p["wv"],
                   preferred_element_type=jnp.float32).astype(enc_out.dtype)
    return k, v


def encode(params: Dict, frames: jax.Array, cfg: ModelConfig,
           constrain) -> jax.Array:
    x = frames
    B, T, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))

    def body(x, gp):
        h = layers.rms_norm(x, gp["pre_norm"], cfg.norm_eps)
        out, _ = attention.attn_apply(gp["attn"], h, cfg, "attn",
                                      positions, constrain, causal=False)
        x = x + out
        h = layers.rms_norm(x, gp["pre_ffn_norm"], cfg.norm_eps)
        x = x + layers.ffn_apply(gp["ffn"], h, cfg.act)
        x = constrain(x, ("act_batch", "act_seq", "act_embed"))
        return x, None

    x, _ = jax.lax.scan(body, x, params["blocks"],
                        unroll=(cfg.encoder_layers if cfg.scan_unroll else 1))
    return layers.rms_norm(x, params["enc_norm"], cfg.norm_eps)


def decode_full(params: Dict, tokens: jax.Array, enc_out: jax.Array,
                cfg: ModelConfig, constrain,
                caches: Optional[Dict] = None, cache_index=None,
                cross_cache: Optional[Dict] = None
                ) -> Tuple[jax.Array, Optional[Dict]]:
    x = layers.embed_lookup(params, tokens, cfg.d_model)
    B, S, _ = x.shape
    off = cache_index if cache_index is not None else jnp.zeros((), jnp.int32)
    positions = jnp.broadcast_to(off + jnp.arange(S)[None, :], (B, S))

    def body(x, xs):
        gp, gcache, gcross = xs
        h = layers.rms_norm(x, gp["pre_norm"], cfg.norm_eps)
        out, nc = attention.attn_apply(gp["attn"], h, cfg, "attn",
                                       positions, constrain, cache=gcache,
                                       cache_index=cache_index)
        x = x + out
        h = layers.rms_norm(x, gp["pre_cross_norm"], cfg.norm_eps)
        if gcross is None:
            ck, cv = _cross_kv(gp["cross"], enc_out)
        else:
            ck, cv = gcross["k"], gcross["v"]
        x = x + _cross_attend(gp["cross"], h, ck, cv, cfg)
        h = layers.rms_norm(x, gp["pre_ffn_norm"], cfg.norm_eps)
        x = x + layers.ffn_apply(gp["ffn"], h, cfg.act)
        x = constrain(x, ("act_batch", "act_seq", "act_embed"))
        return x, (nc if nc is not None else 0)

    xs = (params["decoder_blocks"], caches, cross_cache)
    x, new_caches = jax.lax.scan(body, x, xs,
                                 unroll=(cfg.n_layers if cfg.scan_unroll else 1))
    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = layers.unembed(params, x, cfg.tie_embeddings, cfg.final_softcap)
    return logits, (new_caches if caches is not None else None)


def build_cross_cache(params: Dict, enc_out: jax.Array) -> Dict:
    """Precompute per-decoder-layer cross K/V once per request (prefill)."""

    def body(_, gp):
        k, v = _cross_kv(gp["cross"], enc_out)
        return None, {"k": k, "v": v}

    _, cross = jax.lax.scan(body, None, params["decoder_blocks"])
    return cross


def cross_cache_shapes(cfg: ModelConfig, batch: int,
                       dtype=jnp.bfloat16) -> Dict:
    G = cfg.n_layers
    return {"k": jax.ShapeDtypeStruct(
        (G, batch, cfg.encoder_seq, cfg.n_kv_heads, cfg.head_dim), dtype),
        "v": jax.ShapeDtypeStruct(
        (G, batch, cfg.encoder_seq, cfg.n_kv_heads, cfg.head_dim), dtype)}


def self_cache_shapes(cfg: ModelConfig, batch: int, max_seq: int,
                      dtype=jnp.bfloat16) -> Dict:
    G = cfg.n_layers
    return {"k": jax.ShapeDtypeStruct(
        (G, batch, max_seq, cfg.n_kv_heads, cfg.head_dim), dtype),
        "v": jax.ShapeDtypeStruct(
        (G, batch, max_seq, cfg.n_kv_heads, cfg.head_dim), dtype),
        "pos": jax.ShapeDtypeStruct((G, max_seq), jnp.int32)}
