"""Shared layers + the ParamSpec system (logical-axis sharding metadata).

Logical axes used across the zoo (resolved to mesh axes by
repro.distributed.sharding.PARAM_RULES / ACT_RULES):

    layers   — scan-stacked super-block dim (never sharded)
    vocab    — embedding rows               (tensor-parallel)
    embed    — d_model                      (FSDP)
    heads    — flattened attention heads    (tensor-parallel when divisible)
    kv_heads — kv heads                     (replicated if < model axis)
    head_dim — per-head width
    mlp      — FFN hidden                   (tensor-parallel)
    expert   — MoE expert dim
    inner    — mamba/xlstm inner width      (tensor-parallel)
    state    — SSM state width
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# ParamSpec system
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"       # normal | zeros | ones | small
    scale: float = 1.0
    fan_in: int = 0            # contracted input size; 0: second-to-last dim

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


Specs = Dict[str, ParamSpec]   # flat, "/"-joined paths


def unflatten(flat: Dict[str, object]) -> Dict:
    out: Dict = {}
    for path, leaf in flat.items():
        node = out
        keys = path.split("/")
        for k in keys[:-1]:
            node = node.setdefault(k, {})
        node[keys[-1]] = leaf
    return out


def init_params(rng: jax.Array, specs: Specs, dtype=jnp.bfloat16) -> Dict:
    flat = {}
    keys = jax.random.split(rng, len(specs))
    for key, (path, spec) in zip(keys, sorted(specs.items())):
        if spec.init == "zeros":
            arr = jnp.zeros(spec.shape, dtype)
        elif spec.init == "ones":
            arr = jnp.ones(spec.shape, dtype)
        else:
            fan_in = spec.fan_in or (spec.shape[-2] if len(spec.shape) >= 2
                                     else spec.shape[-1])
            std = spec.scale / math.sqrt(max(fan_in, 1))
            if spec.init == "small":
                std = 0.02 * spec.scale
            arr = (jax.random.normal(key, spec.shape, jnp.float32) * std).astype(dtype)
        flat[path] = arr
    return unflatten(flat)


def axes_tree(specs: Specs) -> Dict:
    return unflatten({p: s.axes for p, s in specs.items()})


def shapes_tree(specs: Specs, dtype=jnp.bfloat16) -> Dict:
    return unflatten({p: jax.ShapeDtypeStruct(s.shape, dtype)
                      for p, s in specs.items()})


def param_bytes(specs: Specs, bytes_per_el: int = 2) -> int:
    return sum(math.prod(s.shape) * bytes_per_el for s in specs.values())


def stacked(specs: Specs, n: int, prefix: str = "") -> Specs:
    """Add a leading scan ('layers') dim to every spec."""
    return {prefix + p: dataclasses.replace(s, shape=(n,) + s.shape,
                                            axes=("layers",) + s.axes)
            for p, s in specs.items()}


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    # gemma-style (1 + scale): scale params init to zeros
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dt)


def rms_norm_specs(d: int, path: str) -> Specs:
    return {path: ParamSpec((d,), ("embed",), init="zeros")}


def activation(name: str) -> Callable[[jax.Array], jax.Array]:
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu,
            "relu2": lambda x: jnp.square(jax.nn.relu(x))}[name]


# -- rotary embeddings ---------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    freqs = rope_freqs(x.shape[-1], theta)                    # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..,S,hd/2)
    cos = jnp.cos(angles)[..., :, None, :]                    # (..,S,1,hd/2)
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# -- embeddings ---------------------------------------------------------------


def embed_specs(vocab: int, d: int, tie: bool) -> Specs:
    specs = {"embed/table": ParamSpec((vocab, d), ("vocab", "embed"),
                                      init="small")}
    if not tie:
        specs["unembed/table"] = ParamSpec((d, vocab), ("embed", "vocab"),
                                           init="small")
    return specs


def embed_lookup(params: Dict, tokens: jax.Array, d: int) -> jax.Array:
    table = params["embed"]["table"]
    x = table[tokens]                       # gather
    return x * jnp.asarray(math.sqrt(d), x.dtype)


def unembed(params: Dict, x: jax.Array, tie: bool,
            softcap: Optional[float] = None) -> jax.Array:
    if tie:
        logits = jnp.einsum("...d,vd->...v", x, params["embed"]["table"],
                            preferred_element_type=jnp.float32)
    else:
        logits = jnp.einsum("...d,dv->...v", x, params["unembed"]["table"],
                            preferred_element_type=jnp.float32)
    if softcap is not None:
        logits = jnp.tanh(logits / softcap) * softcap
    return logits


# -- dense FFN -----------------------------------------------------------------


def ffn_specs(d: int, d_ff: int, act: str, path: str = "ffn",
              gated: bool = True) -> Specs:
    specs = {f"{path}/wi": ParamSpec((d, d_ff), ("embed", "mlp")),
             f"{path}/wo": ParamSpec((d_ff, d), ("mlp", "embed"))}
    if gated:   # SwiGLU / GeGLU
        specs[f"{path}/wg"] = ParamSpec((d, d_ff), ("embed", "mlp"))
    return specs


def ffn_apply(p: Dict, x: jax.Array, act: str) -> jax.Array:
    h = jnp.einsum("...d,df->...f", x, p["wi"],
                   preferred_element_type=jnp.float32)
    if "wg" in p:
        g = jnp.einsum("...d,df->...f", x, p["wg"],
                       preferred_element_type=jnp.float32)
        h = activation(act)(g) * h
    else:
        h = activation(act)(h)
    h = h.astype(x.dtype)
    return jnp.einsum("...f,fd->...d", h, p["wo"],
                      preferred_element_type=jnp.float32).astype(x.dtype)
