"""Mamba-2 SSD (state-space duality) mixer — chunked jnp reference path.

The chunked algorithm (intra-chunk quadratic + inter-chunk recurrence via
lax.scan) follows arXiv:2405.21060 §6. The Pallas kernel in
``repro.kernels.ssd_scan`` implements the same math with VMEM tiling.

Projections are kept *separate* (z/x/BC/dt) rather than packed in one
in_proj so each gets a clean partition spec: z/x project to the
head-sharded inner dim ("model" axis), while the small B/C/dt projections
stay replicated — no mid-tensor reshards (DESIGN.md §5).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import Params, _normal, cast, rmsnorm
from repro.sharding.policy import constrain


def _dims(cfg: ModelConfig):
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    n_heads = d_in // s.head_dim
    return d_in, n_heads


def init_mamba(key, cfg: ModelConfig) -> Params:
    s = cfg.ssm
    d_in, n_heads = _dims(cfg)
    ks = jax.random.split(key, 6)
    return {
        "z_proj": _normal(ks[0], (cfg.d_model, d_in)),
        "x_proj": _normal(ks[1], (cfg.d_model, d_in)),
        "bc_proj": _normal(ks[2], (cfg.d_model, 2 * s.d_state)),
        "dt_proj": _normal(ks[3], (cfg.d_model, n_heads)),
        "conv_x_w": _normal(ks[4], (s.d_conv, d_in), scale=0.1),
        "conv_x_b": jnp.zeros((d_in,), jnp.float32),
        "conv_bc_w": _normal(ks[5], (s.d_conv, 2 * s.d_state), scale=0.1),
        "conv_bc_b": jnp.zeros((2 * s.d_state,), jnp.float32),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, n_heads).astype(jnp.float32)),
        "D": jnp.ones((n_heads,), jnp.float32),
        "dt_bias": jnp.zeros((n_heads,), jnp.float32),
        "norm": jnp.ones((d_in,), jnp.float32),
        "out_proj": _normal(ks[0], (d_in, cfg.d_model)),
    }


def init_mamba_cache(cfg: ModelConfig, batch: int, dtype=None):
    s = cfg.ssm
    d_in, n_heads = _dims(cfg)
    dtype = dtype or jnp.dtype(cfg.dtype)
    return {
        "conv_x": jnp.zeros((batch, s.d_conv - 1, d_in), dtype),
        "conv_bc": jnp.zeros((batch, s.d_conv - 1, 2 * s.d_state), dtype),
        "ssm": jnp.zeros((batch, n_heads, s.head_dim, s.d_state), jnp.float32),
    }


# ---------------------------------------------------------------------------
# SSD core (chunked)
# ---------------------------------------------------------------------------


def ssd_chunked(x, dt, A, B, C, *, chunk: int, init_state=None):
    """x: (b,s,h,p) dt: (b,s,h) A: (h,)<0  B,C: (b,s,n). Returns (y, state).

    y[t] = C_t . h_t;  h_t = exp(A dt_t) h_{t-1} + dt_t B_t x_t
    """
    b, s, h, p = x.shape
    n = B.shape[-1]
    s_orig = s
    if s % chunk:
        pad = chunk - s % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))  # dt=0 => identity step
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
        s = s + pad
    c, q = s // chunk, chunk
    xdt = (x * dt[..., None]).astype(jnp.float32)
    xr = xdt.reshape(b, c, q, h, p)
    dA = (dt.astype(jnp.float32) * A).reshape(b, c, q, h)       # (b,c,q,h)
    dA_cs = jnp.cumsum(dA, axis=2)                              # inclusive
    dA_sum = dA_cs[:, :, -1]                                    # (b,c,h)
    Br = B.astype(jnp.float32).reshape(b, c, q, n)
    Cr = C.astype(jnp.float32).reshape(b, c, q, n)

    # intra-chunk (quadratic within chunk); mask the exponent BEFORE exp so
    # the backward pass never sees exp(+large)*0 = nan
    diff = dA_cs[:, :, :, None, :] - dA_cs[:, :, None, :, :]    # (b,c,i,j,h)
    causal = jnp.tril(jnp.ones((q, q), bool))[None, None, :, :, None]
    L = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    scores = jnp.einsum("bcin,bcjn->bcij", Cr, Br)
    # intra-chunk product in bf16: the (b,c,q,q,h) tensors dominate the
    # SSD byte footprint; exp/cumsum stay fp32 (§Perf B3)
    M = (scores[..., None] * L).astype(x.dtype)                 # (b,c,i,j,h)
    y_diag = jnp.einsum("bcijh,bcjhp->bcihp", M, xr.astype(x.dtype),
                        preferred_element_type=jnp.float32)

    # chunk-final states
    decay_end = jnp.exp(dA_sum[:, :, None, :] - dA_cs)          # (b,c,q,h)
    states = jnp.einsum("bcqn,bcqh,bcqhp->bchpn", Br, decay_end, xr)

    # inter-chunk recurrence
    s0 = (jnp.zeros((b, h, p, n), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32))

    def body(carry, inp):
        st_c, dA_sum_c = inp                                    # (b,h,p,n),(b,h)
        new = jnp.exp(dA_sum_c)[:, :, None, None] * carry + st_c
        return new, carry                                       # emit state entering chunk

    states_t = jnp.moveaxis(states, 1, 0)                       # (c,b,h,p,n)
    dA_sum_t = jnp.moveaxis(dA_sum, 1, 0)                       # (c,b,h)
    final, entry_states = jax.lax.scan(body, s0, (states_t, dA_sum_t))
    entry = jnp.moveaxis(entry_states, 0, 1)                    # (b,c,h,p,n)

    # contribution of the entering state within each chunk
    y_off = jnp.einsum("bcqn,bcqh,bchpn->bcqhp", Cr, jnp.exp(dA_cs), entry)
    y = (y_diag + y_off).reshape(b, s, h, p)[:, :s_orig]
    return y.astype(x.dtype), final


def _causal_conv(u, w, bias):
    """u: (b, s, ch); w: (k, ch) depthwise causal conv."""
    k = w.shape[0]
    pad = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    out = jnp.zeros(u.shape, jnp.float32)
    for i in range(k):
        out = out + pad[:, i:i + u.shape[1]].astype(jnp.float32) * w[i]
    return (out + bias).astype(u.dtype)


def _proj(x, w, cfg):
    return jnp.einsum("bsd,de->bse", x, cast(w, cfg),
                      preferred_element_type=jnp.float32).astype(x.dtype)


def apply_mamba(p: Params, xin, *, cfg: ModelConfig, mode: str, cache=None,
                pos=None, use_kernel: bool = False):
    """xin: (B, S, d) (S=1 for decode). Returns (y, new_cache)."""
    s_cfg = cfg.ssm
    d_in, n_heads = _dims(cfg)
    b, s, _ = xin.shape
    N, P = s_cfg.d_state, s_cfg.head_dim

    z = constrain(_proj(xin, p["z_proj"], cfg), "dp", None, "model")
    xc = constrain(_proj(xin, p["x_proj"], cfg), "dp", None, "model")
    bc = _proj(xin, p["bc_proj"], cfg)
    dt_raw = _proj(xin, p["dt_proj"], cfg)
    A = -jnp.exp(p["A_log"])                                    # (h,)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])

    if mode in ("train", "prefill"):
        xcv = jax.nn.silu(_causal_conv(xc, cast(p["conv_x_w"], cfg),
                                       cast(p["conv_x_b"], cfg)))
        bcv = jax.nn.silu(_causal_conv(bc, cast(p["conv_bc_w"], cfg),
                                       cast(p["conv_bc_b"], cfg)))
        x = xcv.reshape(b, s, n_heads, P)
        Bm, Cm = bcv[..., :N], bcv[..., N:]
        from repro.kernels import interpret_mode, kernels_enabled
        chunk = min(s_cfg.chunk, s)
        if (use_kernel or kernels_enabled()) and mode == "train" \
                and s % chunk == 0:
            from repro.kernels.ssd_scan.kernel import ssd_scan
            y = ssd_scan(x, dt.astype(x.dtype), A, Bm, Cm, chunk=chunk,
                         interpret=interpret_mode())
            state = None  # kernel path is train-only (no state output)
        else:
            y, state = ssd_chunked(x, dt, A, Bm, Cm, chunk=chunk)
        y = y + p["D"][:, None] * x
        new_cache = None
        if mode == "prefill":
            k = s_cfg.d_conv - 1
            new_cache = {"conv_x": xc[:, -k:], "conv_bc": bc[:, -k:],
                         "ssm": state}
    else:  # decode
        win_x = jnp.concatenate([cache["conv_x"], xc], axis=1)  # (b, k, d_in)
        win_bc = jnp.concatenate([cache["conv_bc"], bc], axis=1)
        wx, wbc = cast(p["conv_x_w"], cfg), cast(p["conv_bc_w"], cfg)
        xcv = jax.nn.silu(jnp.einsum(
            "bkc,kc->bc", win_x.astype(jnp.float32), wx.astype(jnp.float32))
            + p["conv_x_b"]).astype(xin.dtype)
        bcv = jax.nn.silu(jnp.einsum(
            "bkc,kc->bc", win_bc.astype(jnp.float32), wbc.astype(jnp.float32))
            + p["conv_bc_b"]).astype(xin.dtype)
        x = xcv.reshape(b, n_heads, P)
        Bm, Cm = bcv[..., :N], bcv[..., N:]
        dt1 = dt[:, 0]                                          # (b,h)
        h_prev = cache["ssm"]                                   # (b,h,p,n) f32
        dA = jnp.exp(dt1 * A)                                   # (b,h)
        upd = jnp.einsum("bh,bn,bhp->bhpn", dt1, Bm.astype(jnp.float32),
                         x.astype(jnp.float32))
        h_new = dA[..., None, None] * h_prev + upd
        y = jnp.einsum("bn,bhpn->bhp", Cm.astype(jnp.float32), h_new)
        y = (y + p["D"][:, None] * x.astype(jnp.float32))[:, None]
        y = y.astype(xin.dtype)
        new_cache = {"conv_x": win_x[:, 1:], "conv_bc": win_bc[:, 1:],
                     "ssm": h_new}

    y = y.reshape(b, s, d_in)
    y = rmsnorm(y, p["norm"]) * jax.nn.silu(z)
    out = jnp.einsum("bse,ed->bsd", y, cast(p["out_proj"], cfg),
                     preferred_element_type=jnp.float32)
    return out.astype(xin.dtype), new_cache
