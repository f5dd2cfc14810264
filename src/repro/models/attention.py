"""Attention mixers: GQA (full / sliding-window / encoder) and DeepSeek MLA.

Which path computes full-sequence GQA attention:

* served prefill on a TPU, in a program on one device, at a length the
  kernel tiles (``flash_prefill``): the blocked flash kernel
  (``repro.kernels.flash_attention``), which visits only the key blocks
  inside each query block's causal/window band;
* everywhere else (train mode, which needs a VJP the kernel lacks; the
  CPU backend, the oracle; lengths that do not tile; programs GSPMD
  partitions over a mesh; MLA): ``_chunked_attention``, which chunks
  queries so the (q, S) score block stays bounded — mathematically
  identical to full softmax. ``enable_kernels(True)`` also routes train
  mode, and prefill off the TPU, through the kernel where S tiles.

Decode attends one token against a KV cache; sliding-window layers keep a
ring-buffer cache.
"""
from __future__ import annotations

import contextlib
import contextvars

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import (Params, _normal, apply_mrope, apply_rope,
                                 cast, rmsnorm)
from repro.sharding.policy import constrain

NEG_INF = -1e30


def _kernel_ok(seq: int, block: int) -> bool:
    return seq % block == 0


#: set while tracing a program that GSPMD partitions over a mesh: Mosaic
#: kernels cannot be partitioned automatically
_partitioned: contextvars.ContextVar[bool] = contextvars.ContextVar(
    "repro_partitioned_trace", default=False)


@contextlib.contextmanager
def partitioned(on: bool = True):
    """Trace the block's programs as partitioned over a mesh (``on``)."""
    token = _partitioned.set(on)
    try:
        yield
    finally:
        _partitioned.reset(token)


def flash_prefill(cfg: ModelConfig, seq: int) -> bool:
    """Whether prefill attention over ``seq`` tokens runs through the
    flash kernel: GQA attention, on a TPU backend, in a program on one
    device, at a length the kernel tiles. The engine counts its kernel
    prefills by this same test."""
    if cfg.mla is not None or jax.default_backend() != "tpu" \
            or _partitioned.get():
        return False
    from repro.kernels.flash_attention.ops import prefill_blocks
    return prefill_blocks(seq) is not None


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_attn(key, cfg: ModelConfig) -> Params:
    if cfg.mla is not None:
        m = cfg.mla
        ks = jax.random.split(key, 8)
        qk_hd = m.qk_nope_head_dim + m.qk_rope_head_dim
        return {
            "wdq": _normal(ks[0], (cfg.d_model, m.q_lora_rank)),
            "q_norm": jnp.ones((m.q_lora_rank,), jnp.float32),
            "wuq": _normal(ks[1], (m.q_lora_rank, cfg.n_heads, qk_hd)),
            "wdkv": _normal(ks[2], (cfg.d_model, m.kv_lora_rank)),
            "kv_norm": jnp.ones((m.kv_lora_rank,), jnp.float32),
            "wkr": _normal(ks[3], (cfg.d_model, m.qk_rope_head_dim)),
            "wuk": _normal(ks[4], (m.kv_lora_rank, cfg.n_heads, m.qk_nope_head_dim)),
            "wuv": _normal(ks[5], (m.kv_lora_rank, cfg.n_heads, m.v_head_dim)),
            "wo": _normal(ks[6], (cfg.n_heads, m.v_head_dim, cfg.d_model)),
        }
    ks = jax.random.split(key, 4)
    return {
        "wq": _normal(ks[0], (cfg.d_model, cfg.n_heads, cfg.head_dim)),
        "wk": _normal(ks[1], (cfg.d_model, cfg.n_kv_heads, cfg.head_dim)),
        "wv": _normal(ks[2], (cfg.d_model, cfg.n_kv_heads, cfg.head_dim)),
        "wo": _normal(ks[3], (cfg.n_heads, cfg.head_dim, cfg.d_model)),
    }


def init_attn_cache(cfg: ModelConfig, sliding: bool, batch: int, seq: int,
                    dtype=None):
    """Zeros KV cache for one attention layer.

    Full attention: (B, seq, KVH, hd) K/V. Sliding: ring buffer of
    ``window`` slots. MLA: compressed latent + rope-key cache.
    """
    dtype = dtype or jnp.dtype(cfg.dtype)
    if cfg.mla is not None:
        m = cfg.mla
        return {
            "ckv": jnp.zeros((batch, seq, m.kv_lora_rank), dtype),
            "kr": jnp.zeros((batch, seq, m.qk_rope_head_dim), dtype),
        }
    s = min(cfg.window, seq) if sliding and cfg.window else seq
    return {
        "k": jnp.zeros((batch, s, cfg.n_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, s, cfg.n_kv_heads, cfg.head_dim), dtype),
    }


# ---------------------------------------------------------------------------
# Core chunked attention (full-sequence modes)
# ---------------------------------------------------------------------------


def _chunked_attention(q, k, v, *, causal: bool, window: int, q_chunk: int = 512):
    """q: (B, S, H, hd); k/v: (B, S, KVH, hd). Returns (B, S, H, vd).

    Queries are processed in chunks; each chunk sees the full key range
    with a causal / sliding mask. GQA handled by head grouping.
    """
    b, s, h, hd = q.shape
    kvh = k.shape[2]
    vd = v.shape[-1]
    g = h // kvh
    scale = 1.0 / jnp.sqrt(jnp.array(hd, jnp.float32))
    qc = min(q_chunk, s)
    n_chunks = s // qc
    assert s % qc == 0, (s, qc)

    qr = q.reshape(b, n_chunks, qc, kvh, g, hd)
    qr = jnp.moveaxis(qr, 1, 0)                       # (n, b, qc, kvh, g, hd)
    kpos = jnp.arange(s)

    def body(carry, inp):
        ci, qch = inp                                 # qch: (b, qc, kvh, g, hd)
        qpos = ci * qc + jnp.arange(qc)
        logits = jnp.einsum("bqkgd,bskd->bkgqs", qch, k,
                            preferred_element_type=jnp.float32) * scale
        mask = jnp.ones((qc, s), bool)
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window:
            mask &= kpos[None, :] > qpos[:, None] - window
        logits = jnp.where(mask[None, None, None], logits, NEG_INF)
        p = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        o = jnp.einsum("bkgqs,bskd->bqkgd", p, v,
                       preferred_element_type=jnp.float32)
        return carry, o.astype(v.dtype)

    _, outs = jax.lax.scan(body, 0, (jnp.arange(n_chunks), qr))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, s, h, vd)
    return out


def _decode_attention(q, k, v, *, valid_mask):
    """q: (B, 1, H, hd); k/v: (B, Sc, KVH, hd); valid_mask: (Sc,) or (B, Sc)."""
    b, _, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    scale = 1.0 / jnp.sqrt(jnp.array(hd, jnp.float32))
    qg = q.reshape(b, kvh, g, hd)
    logits = jnp.einsum("bkgd,bskd->bkgs", qg, k,
                        preferred_element_type=jnp.float32) * scale
    vm = valid_mask if valid_mask.ndim == 2 else valid_mask[None]
    logits = jnp.where(vm[:, None, None, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    o = jnp.einsum("bkgs,bskd->bkgd", p, v, preferred_element_type=jnp.float32)
    return o.reshape(b, 1, h, v.shape[-1]).astype(v.dtype)


def ring_slot_positions(pos, window: int):
    """Absolute position stored in each ring-buffer slot when the *current*
    write position is ``pos`` (i.e. ``pos`` tokens already written)."""
    i = jnp.arange(window)
    # last p <= pos with p % window == i
    p = pos - jnp.mod(pos - i, window)
    return p  # may be negative => never written


# ---------------------------------------------------------------------------
# GQA apply
# ---------------------------------------------------------------------------


def apply_attn(p: Params, x, *, cfg: ModelConfig, sliding: bool, mode: str,
               positions=None, cache=None, pos=None, q_chunk: int = 512,
               max_len: int = 0):
    """mode: 'train' | 'prefill' | 'decode'.

    positions: rope positions — (B, S) int32, or (3, B, S) for mrope.
    decode: x is (B, 1, d), ``pos`` scalar count of tokens already cached.
    Returns (y, new_cache) — new_cache is None in train mode.
    """
    if cfg.mla is not None:
        return _apply_mla(p, x, cfg=cfg, mode=mode, positions=positions,
                          cache=cache, pos=pos, q_chunk=q_chunk,
                          max_len=max_len)

    b, s, _ = x.shape
    wq, wk, wv = cast(p["wq"], cfg), cast(p["wk"], cfg), cast(p["wv"], cfg)
    q = jnp.einsum("bsd,dhk->bshk", x, wq, preferred_element_type=jnp.float32
                   ).astype(x.dtype)
    k = jnp.einsum("bsd,dhk->bshk", x, wk, preferred_element_type=jnp.float32
                   ).astype(x.dtype)
    v = jnp.einsum("bsd,dhk->bshk", x, wv, preferred_element_type=jnp.float32
                   ).astype(x.dtype)

    if cfg.pos == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    elif cfg.pos == "mrope":
        q = apply_mrope(q, positions, cfg.rope_theta)
        k = apply_mrope(k, positions, cfg.rope_theta)

    # pin shardings: batch over dp, q heads over model, KV heads only when
    # divisible (constrain() drops non-divisible axes) — stops GSPMD from
    # partially resharding the KV cache over heads (§Perf C1)
    q = constrain(q, "dp", None, "model", None)
    k = constrain(k, "dp", None, "model", None)
    v = constrain(v, "dp", None, "model", None)

    window = cfg.window if sliding else 0

    if mode in ("train", "prefill"):
        from repro.kernels import interpret_mode, kernels_enabled
        if (mode == "prefill" and flash_prefill(cfg, s)) or (
                kernels_enabled() and _kernel_ok(s, 128)):
            from repro.kernels.flash_attention.ops import mha
            o = mha(q, k, v, causal=cfg.causal, window=window,
                    interpret=interpret_mode())
        else:
            o = _chunked_attention(q, k, v, causal=cfg.causal, window=window,
                                   q_chunk=q_chunk)
        new_cache = None
        if mode == "prefill":
            if window:
                # ring-buffer cache: position p lives at slot p % cache_len
                cache_len = min(window, max_len) if max_len else min(window, s)
                if s >= cache_len:
                    last = jnp.arange(s - cache_len, s)
                    order = jnp.argsort(jnp.mod(last, cache_len))
                    idx = last[order]
                    new_cache = {"k": k[:, idx], "v": v[:, idx]}
                else:
                    pad = ((0, 0), (0, cache_len - s), (0, 0), (0, 0))
                    new_cache = {"k": jnp.pad(k, pad), "v": jnp.pad(v, pad)}
            else:
                grow = max(0, max_len - s) if max_len else 0
                pad = ((0, 0), (0, grow), (0, 0), (0, 0))
                new_cache = {"k": jnp.pad(k, pad), "v": jnp.pad(v, pad)}
    else:  # decode
        ck, cv = cache["k"], cache["v"]
        s_c = ck.shape[1]
        if window and s_c <= window:
            slot = jnp.mod(pos, s_c)
            ck = jax.lax.dynamic_update_slice(ck, k, (0, slot, 0, 0))
            cv = jax.lax.dynamic_update_slice(cv, v, (0, slot, 0, 0))
            slot_pos = ring_slot_positions(pos, s_c)
            valid = (slot_pos >= 0) & (slot_pos <= pos)
        else:
            ck = jax.lax.dynamic_update_slice(ck, k, (0, pos, 0, 0))
            cv = jax.lax.dynamic_update_slice(cv, v, (0, pos, 0, 0))
            valid = jnp.arange(s_c) <= pos
        # cache sharding: heads on "model" when they fill it; otherwise
        # shard the SEQUENCE over "model" (flash-decode style: partial
        # softmax per seq shard + all-reduce) so the per-device cache
        # footprint stays bounded (§Perf C2). batch==1 additionally
        # spreads the sequence over "data".
        seq_axes = ("data", "model") if ck.shape[0] == 1 else ("model",)
        ck = constrain(ck, "dp", seq_axes, "model", None, priority=(0, 2, 1))
        cv = constrain(cv, "dp", seq_axes, "model", None, priority=(0, 2, 1))
        o = None
        if not window and _kernel_ok(s_c, 128):
            from repro.kernels import interpret_mode, kernels_enabled
            if kernels_enabled():
                from repro.kernels.decode_attention.ops import gqa_decode
                o = gqa_decode(q, ck, cv, pos + 1, bk=128,
                               interpret=interpret_mode())
        if o is None:
            o = _decode_attention(q, ck, cv, valid_mask=valid)
        new_cache = {"k": ck, "v": cv}

    wo = cast(p["wo"], cfg)
    y = jnp.einsum("bshk,hkd->bsd", o, wo, preferred_element_type=jnp.float32)
    return y.astype(x.dtype), new_cache


# ---------------------------------------------------------------------------
# MLA (DeepSeek) apply — compressed KV cache; absorbed matmuls for decode
# ---------------------------------------------------------------------------


def _apply_mla(p: Params, x, *, cfg: ModelConfig, mode: str, positions, cache,
               pos, q_chunk: int, max_len: int = 0):
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    nd, rd, vd = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim

    # queries
    q_lat = jnp.einsum("bsd,dr->bsr", x, cast(p["wdq"], cfg),
                       preferred_element_type=jnp.float32).astype(x.dtype)
    q_lat = rmsnorm(q_lat, p["q_norm"])
    q = jnp.einsum("bsr,rhk->bshk", q_lat, cast(p["wuq"], cfg),
                   preferred_element_type=jnp.float32).astype(x.dtype)
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    # compressed kv + shared rope key
    ckv = jnp.einsum("bsd,dr->bsr", x, cast(p["wdkv"], cfg),
                     preferred_element_type=jnp.float32).astype(x.dtype)
    ckv = rmsnorm(ckv, p["kv_norm"])
    kr = jnp.einsum("bsd,dr->bsr", x, cast(p["wkr"], cfg),
                    preferred_element_type=jnp.float32).astype(x.dtype)
    kr = apply_rope(kr[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]

    scale = 1.0 / jnp.sqrt(jnp.array(nd + rd, jnp.float32))

    if mode in ("train", "prefill"):
        # materialize per-head K (nope) and V from the latent
        k_nope = jnp.einsum("bsr,rhk->bshk", ckv, cast(p["wuk"], cfg),
                            preferred_element_type=jnp.float32).astype(x.dtype)
        v = jnp.einsum("bsr,rhk->bshk", ckv, cast(p["wuv"], cfg),
                       preferred_element_type=jnp.float32).astype(x.dtype)
        qfull = jnp.concatenate([q_nope, q_rope], axis=-1)
        kfull = jnp.concatenate(
            [k_nope, jnp.broadcast_to(kr[:, :, None, :], (b, s, h, rd))], axis=-1)
        o = _chunked_attention(qfull, kfull, v, causal=True, window=0,
                               q_chunk=q_chunk)
        new_cache = None
        if mode == "prefill":
            grow = max(0, max_len - s) if max_len else 0
            new_cache = {"ckv": jnp.pad(ckv, ((0, 0), (0, grow), (0, 0))),
                         "kr": jnp.pad(kr, ((0, 0), (0, grow), (0, 0)))}
    else:  # decode: absorbed attention against the compressed cache
        c_ckv = jax.lax.dynamic_update_slice(cache["ckv"], ckv, (0, pos, 0))
        c_kr = jax.lax.dynamic_update_slice(cache["kr"], kr, (0, pos, 0))
        s_c = c_ckv.shape[1]
        valid = jnp.arange(s_c) <= pos
        # absorb W_uk into q: (b,1,h,nd) x (r,h,nd) -> (b,h,r)
        q_abs = jnp.einsum("bshk,rhk->bhr", q_nope, cast(p["wuk"], cfg),
                           preferred_element_type=jnp.float32).astype(x.dtype)
        logits = (jnp.einsum("bhr,bsr->bhs", q_abs, c_ckv,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bshk,btk->bht", q_rope, c_kr,
                               preferred_element_type=jnp.float32)) * scale
        logits = jnp.where(valid[None, None, :], logits, NEG_INF)
        pattn = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
        ctx_lat = jnp.einsum("bhs,bsr->bhr", pattn, c_ckv,
                             preferred_element_type=jnp.float32).astype(x.dtype)
        o = jnp.einsum("bhr,rhk->bhk", ctx_lat, cast(p["wuv"], cfg),
                       preferred_element_type=jnp.float32)[:, None].astype(x.dtype)
        new_cache = {"ckv": c_ckv, "kr": c_kr}

    y = jnp.einsum("bshk,hkd->bsd", o, cast(p["wo"], cfg),
                   preferred_element_type=jnp.float32)
    return y.astype(x.dtype), new_cache
