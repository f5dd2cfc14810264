"""Model assembly: layer-pattern blocks, period-scanned stack, LM heads.

Public API (all pure functions of pytrees):
  init_params(key, cfg)                 -> params
  init_cache(cfg, batch, seq[, dtype])  -> decode cache pytree
  forward_train(params, batch, cfg)     -> (loss, metrics)
  prefill(params, batch, cfg)           -> (last_logits, cache)
  forward_logits(params, batch, cfg)    -> (B, S, V) logits, uncached
  decode_step(params, cache, tokens, pos, cfg [, mrope_pos]) -> (logits, cache)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.models import attention, moe, ssm
from repro.models.layers import (apply_mlp, apply_norm, embed_tokens,
                                 init_embed, init_mlp, init_norm, unembed)
from repro.sharding.policy import constrain

AUX_LOSS_WEIGHT = 0.01
MTP_WEIGHT = 0.3


# ---------------------------------------------------------------------------
# Single block (mixer + FFN)
# ---------------------------------------------------------------------------


def init_block(key, cfg: ModelConfig, spec: LayerSpec):
    ks = jax.random.split(key, 4)
    p = {"norm1": init_norm(cfg)}
    if spec.mixer.startswith("attn"):
        p["mixer"] = attention.init_attn(ks[0], cfg)
    else:
        p["mixer"] = ssm.init_mamba(ks[0], cfg)
    if spec.ffn == "dense":
        p["norm2"] = init_norm(cfg)
        p["ffn"] = init_mlp(ks[1], cfg)
    elif spec.ffn == "moe":
        p["norm2"] = init_norm(cfg)
        p["ffn"] = moe.init_moe(ks[1], cfg)
    return p


def apply_block(p, x, *, cfg: ModelConfig, spec: LayerSpec, mode: str,
                positions, cache, pos, max_len: int = 0):
    # keep the residual stream batch-sharded; without this GSPMD may
    # all-gather activations over the data axis every layer (§Perf B1)
    x = constrain(x, "dp", None, None)
    h = apply_norm(p["norm1"], x, cfg)
    if spec.mixer.startswith("attn"):
        y, new_cache = attention.apply_attn(
            p["mixer"], h, cfg=cfg, sliding=spec.mixer == "attn_sliding",
            mode=mode, positions=positions, cache=cache, pos=pos,
            max_len=max_len)
    else:
        y, new_cache = ssm.apply_mamba(p["mixer"], h, cfg=cfg, mode=mode,
                                       cache=cache, pos=pos)
    x = x + y
    aux = jnp.zeros((), jnp.float32)
    if spec.ffn != "none":
        h = apply_norm(p["norm2"], x, cfg)
        if spec.ffn == "dense":
            y = apply_mlp(p["ffn"], h, cfg)
        else:
            y, aux = moe.apply_moe(p["ffn"], h, cfg=cfg)
        x = x + y
    return x, new_cache, aux


def init_block_cache(cfg: ModelConfig, spec: LayerSpec, batch: int, seq: int,
                     dtype=None):
    if spec.mixer.startswith("attn"):
        return attention.init_attn_cache(cfg, spec.mixer == "attn_sliding",
                                         batch, seq, dtype)
    return ssm.init_mamba_cache(cfg, batch, dtype)


# ---------------------------------------------------------------------------
# Full-stack params / cache
# ---------------------------------------------------------------------------


def init_params(key, cfg: ModelConfig):
    n_pre, n_per, n_suf = len(cfg.prefix), len(cfg.period), len(cfg.suffix)
    keys = jax.random.split(key, 3 + n_pre + n_suf + max(1, cfg.n_periods))
    params = {"embed": init_embed(keys[0], cfg),
              "final_norm": init_norm(cfg)}
    params["prefix"] = [init_block(keys[3 + i], cfg, s)
                        for i, s in enumerate(cfg.prefix)]
    params["suffix"] = [init_block(keys[3 + n_pre + i], cfg, s)
                        for i, s in enumerate(cfg.suffix)]
    if cfg.n_periods:
        per_keys = keys[3 + n_pre + n_suf:3 + n_pre + n_suf + cfg.n_periods]

        def one_period(k):
            sub = jax.random.split(k, n_per)
            return {f"sub{i}": init_block(sub[i], cfg, s)
                    for i, s in enumerate(cfg.period)}

        stacked = [one_period(k) for k in per_keys]
        params["period"] = jax.tree.map(lambda *xs: jnp.stack(xs), *stacked)
    if cfg.mtp:
        mk = jax.random.split(keys[1], 3)
        params["mtp"] = {
            "proj": {"w": 0.02 * jax.random.normal(mk[0], (2 * cfg.d_model,
                                                           cfg.d_model))},
            "block": init_block(mk[1], cfg, LayerSpec("attn", "dense")),
            "norm_h": init_norm(cfg), "norm_e": init_norm(cfg),
        }
    return params


def init_cache(cfg: ModelConfig, batch: int, seq: int, dtype=None):
    cache = {
        "prefix": [init_block_cache(cfg, s, batch, seq, dtype)
                   for s in cfg.prefix],
        "suffix": [init_block_cache(cfg, s, batch, seq, dtype)
                   for s in cfg.suffix],
    }
    if cfg.n_periods:
        one = {f"sub{i}": init_block_cache(cfg, s, batch, seq, dtype)
               for i, s in enumerate(cfg.period)}
        cache["period"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (cfg.n_periods,) + x.shape),
            one)
    return cache


# ---------------------------------------------------------------------------
# Scan-over-layers folding: absorb homogeneous prefix/suffix into the
# scanned period stack (compile count stays O(1) in depth, and the whole
# layer stack becomes ONE stacked pytree leaf per parameter — the unit
# the per-tier mesh sharding in repro.sharding.tier_mesh partitions)
# ---------------------------------------------------------------------------


def _fold_counts(cfg: ModelConfig):
    """(k_pre, k_suf, period): how many whole period-copies the prefix /
    suffix fold into. A homogeneous prefix with no existing period
    becomes its own period of length 1. (0, 0, cfg.period) = nothing to
    fold."""
    period = cfg.period
    if not period:
        if cfg.prefix and len(set(cfg.prefix)) == 1 and not cfg.suffix:
            return len(cfg.prefix), 0, (cfg.prefix[0],)
        return 0, 0, cfg.period
    p = len(period)
    k_pre = (len(cfg.prefix) // p
             if cfg.prefix and cfg.prefix == period * (len(cfg.prefix) // p)
             and len(cfg.prefix) % p == 0 else 0)
    k_suf = (len(cfg.suffix) // p
             if cfg.suffix and cfg.suffix == period * (len(cfg.suffix) // p)
             and len(cfg.suffix) % p == 0 else 0)
    return k_pre, k_suf, period


def fold_config(cfg: ModelConfig) -> ModelConfig:
    """Fold homogeneous prefix/suffix blocks into the scanned stack.

    When the prefix (and/or suffix) is a whole number of copies of the
    period pattern, those blocks are absorbed into ``n_periods`` so the
    entire stack lowers to one ``jax.lax.scan`` — the flattened layer
    sequence (``cfg.layers``) is unchanged, so the computation is
    identical block for block. Returns ``cfg`` itself when nothing
    folds."""
    k_pre, k_suf, period = _fold_counts(cfg)
    if k_pre == 0 and k_suf == 0:
        return cfg
    import dataclasses
    return dataclasses.replace(
        cfg,
        prefix=cfg.prefix if k_pre == 0 else (),
        suffix=cfg.suffix if k_suf == 0 else (),
        period=period,
        n_periods=cfg.n_periods + k_pre + k_suf)


def fold_stack(cfg: ModelConfig, params):
    """(cfg, params) -> (folded_cfg, folded_params).

    The params counterpart of ``fold_config``: prefix/suffix block
    params are restacked onto the leading axis of the ``period`` stack
    (prefix copies in front, suffix copies behind), so every weight of
    the folded stack lives in one stacked leaf. No-op (same objects
    returned) when nothing folds; the flattened layer sequence — and so
    the forward computation — is unchanged either way."""
    k_pre, k_suf, period = _fold_counts(cfg)
    if k_pre == 0 and k_suf == 0:
        return cfg, params
    p = len(period)

    def group_stack(blocks):
        groups = [{f"sub{i}": blocks[g * p + i] for i in range(p)}
                  for g in range(len(blocks) // p)]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *groups)

    parts = []
    if k_pre:
        parts.append(group_stack(params["prefix"]))
    if cfg.n_periods:
        parts.append(params["period"])
    if k_suf:
        parts.append(group_stack(params["suffix"]))
    stacked = (parts[0] if len(parts) == 1 else
               jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *parts))
    folded = {k: v for k, v in params.items()
              if k not in ("prefix", "suffix", "period")}
    folded["prefix"] = [] if k_pre else params["prefix"]
    folded["suffix"] = [] if k_suf else params["suffix"]
    folded["period"] = stacked
    return fold_config(cfg), folded


# ---------------------------------------------------------------------------
# Stack forward
# ---------------------------------------------------------------------------


def _apply_stack(params, x, *, cfg: ModelConfig, mode: str, positions, cache,
                 pos, remat: bool, max_len: int = 0):
    aux_total = jnp.zeros((), jnp.float32)
    new_cache = {"prefix": [], "suffix": []}

    for i, spec in enumerate(cfg.prefix):
        c = cache["prefix"][i] if cache is not None else None
        x, nc, aux = apply_block(params["prefix"][i], x, cfg=cfg, spec=spec,
                                 mode=mode, positions=positions, cache=c,
                                 pos=pos, max_len=max_len)
        new_cache["prefix"].append(nc)
        aux_total += aux

    if cfg.n_periods:
        has_cache = cache is not None

        def body(carry, xs):
            h, aux_acc = carry
            p_slice, c_slice = xs           # c_slice is None when no cache
            ncs = {}
            for i, spec in enumerate(cfg.period):
                c = None if c_slice is None else c_slice[f"sub{i}"]
                h, nc, aux = apply_block(p_slice[f"sub{i}"], h, cfg=cfg,
                                         spec=spec, mode=mode,
                                         positions=positions, cache=c,
                                         pos=pos, max_len=max_len)
                if has_cache:
                    ncs[f"sub{i}"] = nc
                aux_acc = aux_acc + aux
            return (h, aux_acc), ncs

        if remat:
            body = jax.checkpoint(body)
        xs = (params["period"], cache["period"] if has_cache else None)
        (x, aux_total), per_cache = jax.lax.scan(body, (x, aux_total), xs)
        if has_cache:
            new_cache["period"] = per_cache

    for i, spec in enumerate(cfg.suffix):
        c = cache["suffix"][i] if cache is not None else None
        x, nc, aux = apply_block(params["suffix"][i], x, cfg=cfg, spec=spec,
                                 mode=mode, positions=positions, cache=c,
                                 pos=pos, max_len=max_len)
        new_cache["suffix"].append(nc)
        aux_total += aux

    return x, (new_cache if cache is not None else None), aux_total


def _embed_inputs(params, batch, cfg: ModelConfig, mode: str):
    """Returns (x, positions). Handles audio (precomputed embeds), VLM
    (vision patch embeds + M-RoPE position ids) and plain tokens."""
    if not cfg.embed_inputs:                       # audio backbone
        x = batch["embeds"].astype(jnp.dtype(cfg.dtype))
        if cfg.pos == "abs":
            pe = params["embed"]["pos"][:x.shape[1]].astype(x.dtype)
            x = x + pe[None]
        return x, None
    tokens = batch["tokens"]
    x = embed_tokens(params["embed"], tokens, cfg)
    if cfg.vision_tokens and "vision_embeds" in batch:
        v = batch["vision_embeds"].astype(x.dtype)
        nv = v.shape[1]
        x = jnp.concatenate([v, x[:, nv:]], axis=1)
    b, s = tokens.shape
    if cfg.pos == "mrope":
        positions = batch.get("mrope_pos")
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s)[None, None], (3, b, s))
    elif cfg.pos == "rope":
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    else:
        positions = None
    return x, positions


# ---------------------------------------------------------------------------
# Train / prefill / decode entry points
# ---------------------------------------------------------------------------


def _cast_params(params, cfg: ModelConfig):
    """Pre-cast fp32 master weights to the compute dtype ONCE, before the
    stack consumes them — under FSDP the all-gather then moves bf16, not
    fp32, halving param collective/HBM traffic (§Perf A2). Norm scales
    and other 1-d params stay fp32."""
    dt = jnp.dtype(cfg.dtype)
    if dt == jnp.float32:
        return params

    def c(x):
        if hasattr(x, "dtype") and x.dtype == jnp.float32 and x.ndim >= 2:
            return x.astype(dt)
        return x

    return jax.tree.map(c, params)


# materialize full (B,S,V) fp32 logits only below this element count;
# above it the train loss runs in unrolled sequence chunks, bf16 logits
_CHUNKED_LOSS_THRESHOLD = 2 ** 31
_LOSS_CHUNKS = 8


def _lm_loss(params, h, labels, cfg: ModelConfig):
    """LM cross-entropy; seq-chunked with bf16 logits when (B,S,V) is too
    large to materialize in fp32 (never builds the full logits tensor) —
    §Perf A3."""
    b, s = labels.shape
    if b * s * cfg.vocab > _CHUNKED_LOSS_THRESHOLD and s % _LOSS_CHUNKS == 0:
        cs = s // _LOSS_CHUNKS
        total = jnp.zeros((), jnp.float32)
        for i in range(_LOSS_CHUNKS):
            lg = unembed(params["embed"], h[:, i * cs:(i + 1) * cs], cfg)
            lg = lg.astype(jnp.dtype(cfg.dtype))
            total += softmax_xent(lg, labels[:, i * cs:(i + 1) * cs])
        return total / _LOSS_CHUNKS
    logits = unembed(params["embed"], h, cfg)      # (B,S,V) fp32
    return softmax_xent(logits, labels)


def forward_train(params, batch, cfg: ModelConfig, remat: bool = True):
    params = _cast_params(params, cfg)
    x, positions = _embed_inputs(params, batch, cfg, "train")
    x, _, aux = _apply_stack(params, x, cfg=cfg, mode="train",
                             positions=positions, cache=None, pos=None,
                             remat=remat)
    h = apply_norm(params["final_norm"], x, cfg)
    labels = batch["labels"]
    loss = _lm_loss(params, h, labels, cfg)
    metrics = {"xent": loss, "aux": aux}
    if cfg.mtp and cfg.embed_inputs:
        loss_mtp = _mtp_loss(params, h, batch, cfg, positions)
        metrics["mtp"] = loss_mtp
        loss = loss + MTP_WEIGHT * loss_mtp
    loss = loss + AUX_LOSS_WEIGHT * aux
    metrics["loss"] = loss
    return loss, metrics


def _mtp_loss(params, h, batch, cfg: ModelConfig, positions):
    """DeepSeek-V3 MTP depth-1: predict token t+2 from h_t and emb(t+1)."""
    mp = params["mtp"]
    tokens, labels = batch["tokens"], batch["labels"]
    emb_next = embed_tokens(params["embed"], labels, cfg)  # labels = t+1 tokens
    hn = apply_norm(mp["norm_h"], h, cfg)
    en = apply_norm(mp["norm_e"], emb_next, cfg)
    merged = jnp.einsum("bse,ed->bsd", jnp.concatenate([hn, en], -1),
                        mp["proj"]["w"].astype(h.dtype),
                        preferred_element_type=jnp.float32).astype(h.dtype)
    spec = LayerSpec("attn", "dense")
    x, _, _ = apply_block(mp["block"], merged, cfg=cfg, spec=spec, mode="train",
                          positions=positions, cache=None, pos=None)
    hn2 = apply_norm(params["final_norm"], x, cfg)
    # target: token at t+2 == labels shifted by one
    tgt = jnp.concatenate([labels[:, 1:], labels[:, -1:]], axis=1)
    return _lm_loss(params, hn2, tgt, cfg)


def softmax_xent(logits, labels):
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def prefill(params, batch, cfg: ModelConfig, max_len: int = 0,
            last_index=None):
    """Full-sequence forward building the decode cache (or, for encoder-only
    archs, the encoding pass). ``max_len``: decode-cache allocation length
    (>= prompt length); defaults to the prompt length. ``last_index``:
    position whose logits to return (may be a traced scalar; defaults to
    the final position) — lets right-padded prompts read the logits of
    their true last token. Returns (last_logits, cache)."""
    x, positions = _embed_inputs(params, batch, cfg, "prefill")
    b, s = x.shape[0], x.shape[1]
    cache = init_cache(cfg, b, s, jnp.dtype(cfg.dtype)) if cfg.causal else None
    if cfg.causal:
        x, new_cache, _ = _apply_stack(params, x, cfg=cfg, mode="prefill",
                                       positions=positions, cache=cache,
                                       pos=jnp.zeros((), jnp.int32), remat=False,
                                       max_len=max_len or s)
    else:
        x, new_cache, _ = _apply_stack(params, x, cfg=cfg, mode="train",
                                       positions=positions, cache=None,
                                       pos=None, remat=False)
    h = apply_norm(params["final_norm"], x, cfg)
    if cfg.causal:
        if last_index is None:
            h_last = h[:, -1:]
        else:
            h_last = jax.lax.dynamic_slice_in_dim(h, last_index, 1, axis=1)
        logits = unembed(params["embed"], h_last, cfg)
    else:
        logits = unembed(params["embed"], h, cfg)   # per-frame logits
    return logits, new_cache


def prefill_flash(cfg: ModelConfig, seq: int) -> bool:
    """Whether ``prefill`` over ``seq`` tokens computes attention in the
    flash kernel (``attention.flash_prefill``); an encoder's prefill runs
    in train mode and never does."""
    return (cfg.causal and cfg.has_attention
            and attention.flash_prefill(cfg, seq))


def forward_logits(params, batch, cfg: ModelConfig):
    """Uncached full-sequence forward -> (B, S, V) logits of every
    position, at the exact prompt length: no KV cache and no padding.
    The reference a cached ``prefill`` + ``decode_step`` must match."""
    x, positions = _embed_inputs(params, batch, cfg, "train")
    x, _, _ = _apply_stack(params, x, cfg=cfg, mode="train",
                           positions=positions, cache=None, pos=None,
                           remat=False)
    h = apply_norm(params["final_norm"], x, cfg)
    return unembed(params["embed"], h, cfg)


def decode_step(params, cache, tokens, pos, cfg: ModelConfig, mrope_pos=None):
    """One decode step: tokens (B, 1), pos = scalar fill level of the cache.

    Returns (logits (B,1,V), new_cache)."""
    assert cfg.causal, "decode not supported for encoder-only archs"
    x = embed_tokens(params["embed"], tokens, cfg)
    b = tokens.shape[0]
    if cfg.pos == "mrope":
        positions = (mrope_pos if mrope_pos is not None
                     else jnp.broadcast_to(pos, (3, b, 1)))
    elif cfg.pos == "rope":
        positions = jnp.broadcast_to(pos, (b, 1))
    else:
        positions = None
    x, new_cache, _ = _apply_stack(params, x, cfg=cfg, mode="decode",
                                   positions=positions, cache=cache, pos=pos,
                                   remat=False)
    h = apply_norm(params["final_norm"], x, cfg)
    logits = unembed(params["embed"], h, cfg)
    return logits, new_cache
