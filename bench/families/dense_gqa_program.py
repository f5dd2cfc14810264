"""The system under test for a dense GQA configuration.

Maps a configuration file (Hugging Face key names) onto the program's
``ModelConfig`` and makes seeded weights in the program's parameter
layout, on the device, in one jitted call. Only this file and the
harness import the program; the reference beside it does not.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

INIT_STD = 0.02


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import LayerSpec, ModelConfig

    window = cfg.get("sliding_window") or 0
    mixer = "attn_sliding" if window else "attn"
    n = cfg["num_hidden_layers"]
    return ModelConfig(
        name=cfg["name"], arch_type="dense", n_layers=n,
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        period=(LayerSpec(mixer=mixer, ffn="dense"),), n_periods=n,
        pos="rope", rope_theta=float(cfg["rope_theta"]), window=window,
        ffn_act="swiglu" if cfg["hidden_act"] == "silu" else "gelu",
        norm=cfg["norm"], max_seq=cfg["max_position_embeddings"],
        dtype=cfg["dtype"], source=cfg["source"])


def param_shapes(cfg: dict) -> dict:
    """{path: shape} of every weight, in the program's layout: a stacked
    ``period`` of one layer kind, embedding and head untied."""
    d, v, n = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    h, kvh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    ff = cfg["intermediate_size"]
    shapes = {
        ("embed", "tok"): (v, d),
        ("embed", "unembed"): (d, v),
        ("period", "sub0", "mixer", "wq"): (n, d, h, hd),
        ("period", "sub0", "mixer", "wk"): (n, d, kvh, hd),
        ("period", "sub0", "mixer", "wv"): (n, d, kvh, hd),
        ("period", "sub0", "mixer", "wo"): (n, h, hd, d),
        ("period", "sub0", "ffn", "up", "w"): (n, d, ff),
        ("period", "sub0", "ffn", "down", "w"): (n, ff, d),
    }
    if cfg["hidden_act"] == "silu":
        shapes[("period", "sub0", "ffn", "gate", "w")] = (n, d, ff)
    return shapes


def norm_shapes(cfg: dict) -> dict:
    d, n = cfg["hidden_size"], cfg["num_hidden_layers"]
    keys = ("scale", "bias") if cfg["norm"] == "layernorm" else ("scale",)
    out = {}
    for k in keys:
        out[("final_norm", k)] = (d,)
        out[("period", "sub0", "norm1", k)] = (n, d)
        out[("period", "sub0", "norm2", k)] = (n, d)
    return out


def _nest(flat: dict) -> dict:
    out: dict = {"prefix": [], "suffix": []}
    for path, leaf in flat.items():
        node = out
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = leaf
    return out


@functools.lru_cache(maxsize=None)
def _maker(key_items: tuple):
    cfg = dict(key_items)
    shapes, norms = param_shapes(cfg), norm_shapes(cfg)
    dtype = jnp.dtype(cfg["dtype"])

    @jax.jit
    def make(seed):
        keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
        flat = {p: (INIT_STD * jax.random.normal(k, s, jnp.float32)
                    ).astype(dtype)
                for k, (p, s) in zip(keys, sorted(shapes.items()))}
        for p, s in norms.items():
            # norm scales and biases stay float32, as the program keeps them
            flat[p] = (jnp.ones(s, jnp.float32) if p[-1] == "scale"
                       else jnp.zeros(s, jnp.float32))
        return _nest(flat)

    return make


def _hashable(cfg: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, type(None)))))


def make_params(cfg: dict, seed: int) -> dict:
    """Seeded weights in the served type, made on the default device in
    one jitted call. ``seed`` is a 32-bit key seed."""
    return _maker(_hashable(cfg))(jnp.uint32(seed))

