"""Mixture-of-Experts FFN with capacity-based token-choice routing.

Dispatch is gather-based (per-group expert top-C by earliest-token
priority) rather than Mesh-TF one-hot-einsum dispatch: the gather /
take_along_axis formulation keeps HLO FLOPs equal to the *active* expert
compute (x capacity factor) and partitions cleanly with the batch (group)
dim on the data axis and the expert dim on the model axis, where pjit
inserts the all-to-all-equivalent collectives.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import Params, _normal, cast, _act
from repro.sharding.policy import constrain


def init_moe(key, cfg: ModelConfig) -> Params:
    e = cfg.moe
    ks = jax.random.split(key, 6)
    gated = cfg.ffn_act in ("swiglu", "geglu")
    p = {
        "router": _normal(ks[0], (cfg.d_model, e.n_experts)),
        "up": _normal(ks[1], (e.n_experts, cfg.d_model, e.d_expert)),
        "down": _normal(ks[2], (e.n_experts, e.d_expert, cfg.d_model)),
    }
    if gated:
        p["gate"] = _normal(ks[3], (e.n_experts, cfg.d_model, e.d_expert))
    if e.n_shared:
        d_sh = e.d_expert * e.n_shared
        p["sh_up"] = _normal(ks[4], (cfg.d_model, d_sh))
        p["sh_down"] = _normal(ks[5], (d_sh, cfg.d_model))
        if gated:
            p["sh_gate"] = _normal(ks[4], (cfg.d_model, d_sh))
    return p


def capacity(cfg: ModelConfig, tokens_per_group: int) -> int:
    e = cfg.moe
    c = int(tokens_per_group * e.top_k * e.capacity_factor / e.n_experts)
    return max(1, min(c, tokens_per_group))


def apply_moe(p: Params, x, *, cfg: ModelConfig):
    """x: (B, T, d) — B is the dispatch group dim. Returns (y, aux_loss)."""
    e = cfg.moe
    b, t, d = x.shape
    cap = capacity(cfg, t)
    act = _act(cfg.ffn_act)
    gated = "gate" in p

    # --- routing ---------------------------------------------------------
    logits = jnp.einsum("btd,de->bte", x, cast(p["router"], cfg),
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                     # (b,t,E) f32
    w, e_idx = jax.lax.top_k(probs, e.top_k)                    # (b,t,k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)

    # load-balance aux loss (Switch-style)
    assign = jax.nn.one_hot(e_idx, e.n_experts, dtype=jnp.float32)  # (b,t,k,E)
    f_e = assign.sum(2).mean(1)                                 # (b,E) fraction
    p_e = probs.mean(1)                                         # (b,E)
    aux = e.n_experts * jnp.mean(jnp.sum(f_e * p_e, -1))

    # --- dispatch: per (group, expert) pick up to `cap` earliest tokens ---
    tok_mask = assign.sum(2)                                    # (b,t,E) 0/1
    prio = tok_mask * (t - jnp.arange(t, dtype=jnp.float32))[None, :, None]
    prio = jnp.swapaxes(prio, 1, 2)                             # (b,E,t)
    top_p, top_i = jax.lax.top_k(prio, cap)                     # (b,E,cap)
    slot_valid = top_p > 0.0                                    # (b,E,cap)

    xg = jnp.take_along_axis(
        x[:, None], top_i[..., None], axis=2)                   # (b,E,cap,d)
    xg = xg * slot_valid[..., None].astype(x.dtype)
    xg = constrain(xg, "dp", "model", None, None)

    # --- expert compute ----------------------------------------------------
    from repro.kernels import interpret_mode, kernels_enabled
    yg = None
    if kernels_enabled() and gated and cfg.ffn_act == "swiglu" \
            and (b * cap) % 8 == 0:
        from repro.kernels.moe_gmm.ops import expert_mlp
        xe = jnp.swapaxes(xg, 0, 1).reshape(e.n_experts, b * cap, d)
        ye = expert_mlp(xe, cast(p["gate"], cfg), cast(p["up"], cfg),
                        cast(p["down"], cfg), interpret=interpret_mode())
        yg = jnp.swapaxes(ye.reshape(e.n_experts, b, cap, d), 0, 1)
    if yg is None:
        up = jnp.einsum("becd,edf->becf", xg, cast(p["up"], cfg),
                        preferred_element_type=jnp.float32).astype(x.dtype)
        if gated:
            g = jnp.einsum("becd,edf->becf", xg, cast(p["gate"], cfg),
                           preferred_element_type=jnp.float32).astype(x.dtype)
            h = act(g) * up
        else:
            h = act(up)
        yg = jnp.einsum("becf,efd->becd", h, cast(p["down"], cfg),
                        preferred_element_type=jnp.float32).astype(x.dtype)

    # --- combine: token slot position == rank among earlier assigned tokens
    # cumulative count of assigned tokens per expert, exclusive
    pos_all = jnp.cumsum(tok_mask, axis=1) - tok_mask           # (b,t,E)
    pos_tk = jnp.take_along_axis(pos_all, e_idx.astype(jnp.int32), axis=2)
    keep = pos_tk < cap                                         # (b,t,k)
    slot = jnp.minimum(pos_tk.astype(jnp.int32), cap - 1)       # clip overflow
    flat_idx = (e_idx * cap + slot).reshape(b, t * e.top_k)
    y_flat = yg.reshape(b, e.n_experts * cap, d)
    y_tok = jnp.take_along_axis(
        y_flat, flat_idx[..., None], axis=1, mode="clip"
    ).reshape(b, t, e.top_k, d)
    wk = (w * keep).astype(x.dtype)
    y = jnp.einsum("btk,btkd->btd", wk, y_tok,
                   preferred_element_type=jnp.float32).astype(x.dtype)

    # --- shared experts (always-on) ---------------------------------------
    if "sh_up" in p:
        su = jnp.einsum("btd,df->btf", x, cast(p["sh_up"], cfg),
                        preferred_element_type=jnp.float32).astype(x.dtype)
        if gated:
            sg = jnp.einsum("btd,df->btf", x, cast(p["sh_gate"], cfg),
                            preferred_element_type=jnp.float32).astype(x.dtype)
            sh = act(sg) * su
        else:
            sh = act(su)
        y = y + jnp.einsum("btf,fd->btd", sh, cast(p["sh_down"], cfg),
                           preferred_element_type=jnp.float32).astype(x.dtype)
    return y, aux
