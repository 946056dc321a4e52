"""Mixture-of-Experts FFN: capacity-based sorted dispatch (TPU-native).

GPU MoE stacks lean on dynamic shapes / atomics; on TPU everything must be
static. We sort (token, k) slots by expert id, compute each slot's position
within its expert segment, and scatter into a dense (E, capacity, D) buffer —
dropped tokens (over capacity) fall into a trash row. Expert FFNs are one
batched einsum, fully MXU-friendly. The combine is the exact transpose.

Three execution paths:
  * plan=None                 — single-device (tests/smokes): global dispatch;
  * plan given, plan.ep=False — baseline **TP-MoE**: shard_map over the mesh,
    dispatch is token-local per data shard, every device holds ALL experts
    with the mlp dim sharded on "model" (partial-sum psum after wo);
  * plan given, plan.ep=True  — **EP-MoE** (§Perf hillclimb): expert weights
    sharded over "model" (E/m experts per device), tokens exchanged with
    all-to-all along "model", FFN runs on local experts only, reverse
    all-to-all, combine. Wire bytes scale with tokens, not with experts.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.common import ModelConfig
from repro.models.layers import ParamSpec, Specs, activation


def moe_specs(cfg: ModelConfig, path: str = "moe") -> Specs:
    d, m = cfg.d_model, cfg.moe
    specs = {
        f"{path}/router": ParamSpec((d, m.num_experts), ("embed", "expert"),
                                    init="small"),
        f"{path}/wi": ParamSpec((m.num_experts, d, m.d_ff_expert),
                                ("expert", "embed", "mlp")),
        f"{path}/wg": ParamSpec((m.num_experts, d, m.d_ff_expert),
                                ("expert", "embed", "mlp")),
        f"{path}/wo": ParamSpec((m.num_experts, m.d_ff_expert, d),
                                ("expert", "mlp", "embed")),
    }
    if m.shared_expert:
        specs[f"{path}/shared_wi"] = ParamSpec((d, m.d_ff_expert),
                                               ("embed", "mlp"))
        specs[f"{path}/shared_wg"] = ParamSpec((d, m.d_ff_expert),
                                               ("embed", "mlp"))
        specs[f"{path}/shared_wo"] = ParamSpec((m.d_ff_expert, d),
                                               ("mlp", "embed"))
    return specs


def _capacity(tokens: int, cfg: ModelConfig) -> int:
    m = cfg.moe
    c = int(m.capacity_factor * tokens * m.top_k / m.num_experts)
    return max((c + 7) // 8 * 8, 8)


# ---------------------------------------------------------------------------
# core dispatch/combine on a LOCAL token block (runs per-shard)
# ---------------------------------------------------------------------------


def _route(p, tokens, cfg):
    m = cfg.moe
    logits = jnp.einsum("td,de->te", tokens, p["router"],
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, m.top_k)
    if m.top_k > 1:
        gate_vals = gate_vals / jnp.sum(gate_vals, -1, keepdims=True)
    return logits, probs, gate_vals, expert_idx


def _dispatch(tokens, expert_idx, gate_vals, E: int, C: int):
    """tokens (T,D) -> buf (E,C,D) + combine metadata."""
    T, D = tokens.shape
    K = expert_idx.shape[1]
    flat_e = expert_idx.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T), K)
    flat_g = gate_vals.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    seg_start = jnp.searchsorted(se, jnp.arange(E))
    pos = jnp.arange(T * K) - seg_start[se]
    keep = pos < C
    dst = jnp.where(keep, se * C + pos, E * C)
    buf = jnp.zeros((E * C + 1, D), tokens.dtype).at[dst].set(tokens[st])
    return buf[:E * C].reshape(E, C, D), (dst, st, sg, keep)


def _combine(out_e, meta, T: int, dtype):
    dst, st, sg, keep = meta
    E_C, D = out_e.reshape(-1, out_e.shape[-1]).shape
    rows = out_e.reshape(E_C, D)
    slot_out = rows[jnp.minimum(dst, E_C - 1)]
    slot_out = slot_out * (sg * keep).astype(dtype)[:, None]
    return jnp.zeros((T, D), dtype).at[st].add(slot_out)


def _expert_ffn(p, buf, cfg, psum_axis: Optional[str] = None):
    """(E,C,D) x (E,D,F) batched einsums; psum partial sums when the mlp dim
    is sharded inside shard_map."""
    h = jnp.einsum("ecd,edf->ecf", buf, p["wi"],
                   preferred_element_type=jnp.float32)
    g = jnp.einsum("ecd,edf->ecf", buf, p["wg"],
                   preferred_element_type=jnp.float32)
    h = (activation(cfg.act)(g) * h).astype(buf.dtype)
    out = jnp.einsum("ecf,efd->ecd", h, p["wo"],
                     preferred_element_type=jnp.float32)
    if psum_axis is not None:
        out = jax.lax.psum(out, psum_axis)
    return out.astype(buf.dtype)


def _shared_ffn(p, tokens, cfg, psum_axis: Optional[str] = None):
    hs = jnp.einsum("td,df->tf", tokens, p["shared_wi"],
                    preferred_element_type=jnp.float32)
    gs = jnp.einsum("td,df->tf", tokens, p["shared_wg"],
                    preferred_element_type=jnp.float32)
    hs = (activation(cfg.act)(gs) * hs).astype(tokens.dtype)
    out = jnp.einsum("tf,fd->td", hs, p["shared_wo"],
                     preferred_element_type=jnp.float32)
    if psum_axis is not None:
        out = jax.lax.psum(out, psum_axis)
    return out.astype(tokens.dtype)


def _aux_losses(logits, probs, expert_idx, keep, E: int):
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], E, dtype=jnp.float32),
                  axis=0)
    return {
        "load_balance": jnp.sum(me * ce) * E,
        "router_z": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
        "dropped_frac": 1.0 - jnp.mean(keep.astype(jnp.float32)),
    }


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------


def moe_apply(p: Dict, x: jax.Array, cfg: ModelConfig,
              constrain) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    plan = getattr(constrain, "plan", None)
    if plan is None:
        return _moe_local(p, x, cfg)
    return _moe_sharded(p, x, cfg, plan)


def _moe_local(p, x, cfg) -> Tuple[jax.Array, Dict]:
    m = cfg.moe
    B, S, D = x.shape
    T, E = B * S, m.num_experts
    tokens = x.reshape(T, D)
    logits, probs, gate_vals, expert_idx = _route(p, tokens, cfg)
    C = _capacity(T, cfg)
    buf, meta = _dispatch(tokens, expert_idx, gate_vals, E, C)
    out_e = _expert_ffn(p, buf, cfg)
    y = _combine(out_e, meta, T, x.dtype)
    if m.shared_expert:
        y = y + _shared_ffn(p, tokens, cfg)
    return y.reshape(B, S, D), _aux_losses(logits, probs, expert_idx,
                                           meta[3], E)


def _moe_sharded(p, x, cfg, plan) -> Tuple[jax.Array, Dict]:
    mesh = plan.mesh
    m = cfg.moe
    E = m.num_experts
    batch_axes = plan.rules.get("act_batch") or ()
    model_ax = "model" if "model" in mesh.axis_names else None
    mlp_shardable = model_ax and m.d_ff_expert % mesh.shape[model_ax] == 0
    n_model = mesh.shape.get("model", 1)
    ep = plan.ep and model_ax and E % n_model == 0
    wstat = bool(plan.rules.get("moe_weight_stationary")) \
        and batch_axes and E % _mesh_prod(mesh, batch_axes) == 0
    all_axes = tuple(mesh.axis_names)
    mlp = model_ax if mlp_shardable else None

    x_spec = P(batch_axes if batch_axes else None, None, None)
    if wstat:
        # weight-stationary (serving): experts sharded over the BATCH axes
        # (resident), tokens broadcast to the expert owners -- wire scales
        # with activations (tiny at decode), zero weight gathers.
        w_spec = {"router": P(None, None),
                  "wi": P(batch_axes, None, mlp),
                  "wg": P(batch_axes, None, mlp),
                  "wo": P(batch_axes, mlp, None)}
    elif ep:
        # expert-parallel: experts sharded over "model"; each model rank
        # routes its SLICE of the local tokens, all-to-all moves token
        # slots to their expert's owner and back.
        w_spec = {"router": P(None, None),
                  "wi": P(model_ax, None, None),
                  "wg": P(model_ax, None, None),
                  "wo": P(model_ax, None, None)}
    else:
        # baseline TP: every device holds all experts with the mlp dim
        # sharded on "model"; ONE bf16 all-reduce of the combined output.
        w_spec = {"router": P(None, None),
                  "wi": P(None, None, mlp),
                  "wg": P(None, None, mlp),
                  "wo": P(None, mlp, None)}
    if m.shared_expert:
        w_spec.update({"shared_wi": P(None, mlp), "shared_wg": P(None, mlp),
                       "shared_wo": P(mlp, None)})
    aux_spec = {k: P() for k in ("load_balance", "router_z", "dropped_frac")}

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(w_spec, x_spec),
        out_specs=(x_spec, aux_spec),
        check_vma=False)
    def run(pw, xl):
        B, S, D = xl.shape
        T = B * S
        tokens = xl.reshape(T, D)

        if wstat:
            nb = _mesh_prod(mesh, batch_axes)
            tok_full = jax.lax.all_gather(tokens, batch_axes, axis=0,
                                          tiled=True)          # (T*nb, D)
            Tf = T * nb
            logits, probs, gate_vals, expert_idx = _route(pw, tok_full, cfg)
            C = _capacity(Tf, cfg)
            buf, meta = _dispatch(tok_full, expert_idx, gate_vals, E, C)
            # compute ONLY the local expert rows (resident weights)
            rank = _linear_index(mesh, batch_axes)
            e_loc = E // nb
            buf_loc = jax.lax.dynamic_slice_in_dim(buf, rank * e_loc,
                                                   e_loc, 0)
            out_loc = _expert_ffn(pw, buf_loc, cfg, psum_axis=mlp)
            out_e = jnp.zeros((E, C, D), out_loc.dtype)
            out_e = jax.lax.dynamic_update_slice_in_dim(out_e, out_loc,
                                                        rank * e_loc, 0)
            y_full = _combine(out_e, meta, Tf, xl.dtype)
            if m.shared_expert:
                y_full = y_full + _shared_ffn(pw, tok_full, cfg,
                                              psum_axis=mlp) / nb
            y_full = jax.lax.psum(y_full, batch_axes)          # (Tf, D)
            y = jax.lax.dynamic_slice_in_dim(y_full, rank * T, T, 0)
        elif ep:
            # each model rank handles a 1/n slice of the local tokens
            rank = jax.lax.axis_index(model_ax)
            Ts = -(-T // n_model)
            pad = Ts * n_model - T
            tok_p = jnp.pad(tokens, ((0, pad), (0, 0)))
            tok_s = jax.lax.dynamic_slice_in_dim(tok_p, rank * Ts, Ts, 0)
            logits, probs, gate_vals, expert_idx = _route(pw, tok_s, cfg)
            valid = (rank * Ts + jnp.arange(Ts)) < T
            gate_vals = gate_vals * valid[:, None]
            C = _capacity(Ts, cfg)
            buf, meta = _dispatch(tok_s, expert_idx, gate_vals, E, C)
            bufx = buf.reshape(n_model, E // n_model, C, D)
            bufx = jax.lax.all_to_all(bufx, model_ax, 0, 0)    # by expert
            bufx = bufx.transpose(1, 0, 2, 3).reshape(E // n_model,
                                                      n_model * C, D)
            out_local = _expert_ffn(pw, bufx, cfg)
            out_local = out_local.reshape(E // n_model, n_model, C,
                                          D).transpose(1, 0, 2, 3)
            out_e = jax.lax.all_to_all(out_local, model_ax, 0, 0)
            out_e = out_e.reshape(E, C, D)
            y_s = _combine(out_e, meta, Ts, xl.dtype)          # my slice
            y = jax.lax.all_gather(y_s, model_ax, axis=0,
                                   tiled=True)[:T]             # (T, D)
            if m.shared_expert:
                y = y + _shared_ffn(pw, tokens, cfg, psum_axis=mlp)
        else:
            logits, probs, gate_vals, expert_idx = _route(pw, tokens, cfg)
            C = _capacity(T, cfg)
            buf, meta = _dispatch(tokens, expert_idx, gate_vals, E, C)
            out_e = _expert_ffn(pw, buf, cfg)                  # partial on F
            y = _combine(out_e, meta, T, xl.dtype)
            if m.shared_expert:
                y = y + _shared_ffn(pw, tokens, cfg)
            if mlp is not None:
                # ONE bf16 all-reduce of the combined (T, D) output instead
                # of f32 all-reduces of every (E, C, D) expert buffer
                y = jax.lax.psum(y, mlp).astype(xl.dtype)
        aux = _aux_losses(logits, probs, expert_idx, meta[3], E)
        aux = {k: jax.lax.pmean(v, all_axes) for k, v in aux.items()}
        return y.reshape(B, S, D), aux

    weights = {k: p[k] for k in w_spec}
    return run(weights, x)


def _mesh_prod(mesh, axes) -> int:
    if isinstance(axes, str):
        axes = (axes,)
    out = 1
    for a in axes:
        out *= mesh.shape[a]
    return out


def _linear_index(mesh, axes):
    """Linearized rank over a tuple of mesh axes (row-major)."""
    if isinstance(axes, str):
        axes = (axes,)
    idx = jax.lax.axis_index(axes[0])
    for a in axes[1:]:
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return idx
