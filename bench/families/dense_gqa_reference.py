"""Plain float32 reference of a dense GQA decoder, from its configuration.

The forward pass of a Mistral-style (RMSNorm, SwiGLU) or StarCoder2-style
(LayerNorm, tanh GELU, sliding window) decoder, written from the
published description in straightforward ``jax.numpy``: no cache, no
batching tricks, no kernels, every matrix product in float32 at
``highest`` precision. It imports nothing of the program under test.

Weights arrive in the layout the benchmark makes them (see
``dense_gqa_program.param_shapes``): per-layer matrices stacked on a
leading layer axis. Each layer's weights are widened to float32 inside
the layer loop, so only one layer is ever held in float32.

``precision="fp8"`` is the control: every matrix product takes its two
operands rounded to float8 (e4m3) with one scale per tensor, the next
precision below the bfloat16 the configuration states.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def _fp8(x):
    """Round to float8 e4m3 with one scale for the whole tensor."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / scale).astype(FP8).astype(jnp.float32) * scale


def _mm(spec, a, b, fp8: bool):
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _eps(cfg: dict) -> float:
    return float(cfg.get("rms_norm_eps", cfg.get("norm_epsilon", 1e-6)))


def _norm(x, p, cfg):
    eps = _eps(cfg)
    if cfg["norm"] == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * p["scale"]


def _rope(x, theta: float):
    """Rotate-half RoPE; x (S, H, hd), positions 0..S-1."""
    s, hd = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(hd // 2, dtype=np.float64) / (hd // 2))
    ang = jnp.asarray(np.arange(s)[:, None] * inv[None, :], jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _act(x, cfg):
    if cfg["hidden_act"] == "silu":
        return x * jax.nn.sigmoid(x)
    # gelu_pytorch_tanh
    return 0.5 * x * (1.0 + jnp.tanh(np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def _layer(x, p, cfg, fp8: bool, q_block: int):
    """One decoder layer over one sequence x (S, d)."""
    s = x.shape[0]
    h, kvh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    g = h // kvh
    window = cfg.get("sliding_window") or 0
    m = p["mixer"]
    a = _norm(x, p["norm1"], cfg)
    q = _rope(_mm("sd,dhk->shk", a, m["wq"], fp8), cfg["rope_theta"])
    k = _rope(_mm("sd,dhk->shk", a, m["wk"], fp8), cfg["rope_theta"])
    v = _mm("sd,dhk->shk", a, m["wv"], fp8)
    # query head i reads key/value head i // g
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    kpos = jnp.arange(s)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * q_block, q_block, 0)
        qpos = i * q_block + jnp.arange(q_block)
        logits = _mm("qhk,shk->hqs", qb, k, fp8) / np.sqrt(hd)
        mask = kpos[None, :] <= qpos[:, None]
        if window:
            mask &= kpos[None, :] > qpos[:, None] - window
        logits = jnp.where(mask[None], logits, -jnp.inf)
        w = jax.nn.softmax(logits, axis=-1)
        return _mm("hqs,shk->qhk", w, v, fp8)

    o = jax.lax.map(block, jnp.arange(s // q_block)).reshape(s, h, hd)
    x = x + _mm("shk,hkd->sd", o, m["wo"], fp8)
    f = p["ffn"]
    a = _norm(x, p["norm2"], cfg)
    up = _mm("sd,df->sf", a, f["up"]["w"], fp8)
    if "gate" in f:
        hid = _act(_mm("sd,df->sf", a, f["gate"]["w"], fp8), cfg) * up
    else:
        hid = _act(up, cfg)
    return x + _mm("sf,fd->sd", hid, f["down"]["w"], fp8)


@functools.partial(jax.jit, static_argnames=("cfg_items", "fp8", "q_block",
                                             "first"))
def _forward(params, tokens, *, cfg_items, fp8, q_block, first):
    cfg = dict(cfg_items)
    f32 = functools.partial(jax.tree.map, lambda w: w.astype(jnp.float32))
    x = params["embed"]["tok"][tokens].astype(jnp.float32)

    def body(x, p):
        return _layer(x, f32(p), cfg, fp8, q_block), None

    x, _ = jax.lax.scan(body, x, params["period"]["sub0"])
    x = _norm(x[first:], f32(params["final_norm"]), cfg)
    return _mm("sd,dv->sv", x, params["embed"]["unembed"].astype(jnp.float32), fp8)


def logits(params, tokens: np.ndarray, cfg: dict, first: int,
           precision: str = "float32", q_block: int = 512) -> np.ndarray:
    """(S - first, V) float32 logits at positions first..S-1 of one
    sequence ``tokens`` (S,)."""
    cfg_items = tuple(sorted((k, v) for k, v in cfg.items()
                             if isinstance(v, (int, float, str, type(None)))))
    s = len(tokens)
    q_block = min(q_block, 1 << (s - 1).bit_length())
    pad = -s % q_block
    # padding after the sequence: under the causal mask it changes
    # nothing before it, and its positions are dropped below
    toks = np.concatenate([np.asarray(tokens, np.int32), np.zeros(pad, np.int32)])
    with jax.default_matmul_precision("highest"):
        out = _forward(params, jnp.asarray(toks), cfg_items=cfg_items,
                       fp8=precision == "fp8", q_block=q_block, first=first)
    return np.asarray(out)[: s - first]
