"""Operations and bytes the work of a dense GQA decoder needs.

These count the work, not what an implementation does: rows that pad a
batch bucket, cache slots beyond the tokens held, attention that a mask
throws away and any cast of the weights are not counted. A matrix
product of (m, k) by (k, n) is 2mkn operations; norms, rotations and
activations are left out, as model FLOP counts usually do. Weights and
the key/value cache are bfloat16 (2 bytes), logits float32.
"""
from __future__ import annotations

BF16 = 2
F32 = 4


def _dims(cfg: dict):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["vocab_size"],
            cfg["num_hidden_layers"], cfg.get("sliding_window") or 0)


def layer_params(cfg: dict) -> int:
    """Matrix weights of one layer."""
    d, h, kvh, hd, ff, _, _, _ = _dims(cfg)
    mlp = (3 if cfg["hidden_act"] == "silu" else 2) * d * ff
    return d * (h + 2 * kvh) * hd + h * hd * d + mlp


def matrix_params(cfg: dict) -> int:
    """Matrix weights a step reads: every layer and the output head (the
    embedding is looked up row by row, not read whole)."""
    d, _, _, _, _, v, n, _ = _dims(cfg)
    return n * layer_params(cfg) + d * v


def keys_seen(pos: int, window: int) -> int:
    """Keys a query at position ``pos`` (0-based) attends to."""
    return min(pos + 1, window) if window else pos + 1


def _keys_seen_sum(s: int, window: int) -> int:
    """Sum of keys_seen over positions 0..s-1."""
    if not window or s <= window:
        return s * (s + 1) // 2
    return window * (window + 1) // 2 + (s - window) * window


def prefill_flops(cfg: dict, rows: int, s: int) -> float:
    """A prefill of ``rows`` prompts of ``s`` tokens, logits at the last
    position of each."""
    d, h, _, hd, _, v, n, window = _dims(cfg)
    lin = 2 * rows * s * layer_params(cfg) * n
    attn = 4 * h * hd * rows * _keys_seen_sum(s, window) * n
    return float(lin + attn + 2 * rows * d * v)


def prefill_bytes(cfg: dict, rows: int, s: int) -> float:
    """Weights once, the key/value cache the prompts leave, token ids in
    and last-position logits out."""
    d, _, kvh, hd, _, v, n, window = _dims(cfg)
    kept = min(s, window) if window else s
    cache = 2 * rows * kept * kvh * hd * BF16 * n
    return float(matrix_params(cfg) * BF16 + rows * s * d * BF16 + cache
                 + rows * s * 4 + rows * v * F32)


def decode_flops(cfg: dict, rows: int, pos: int) -> float:
    """One decode step of ``rows`` rows whose new token sits at ``pos``."""
    d, h, _, hd, _, v, n, window = _dims(cfg)
    lin = 2 * rows * layer_params(cfg) * n
    attn = 4 * h * hd * rows * keys_seen(pos, window) * n
    return float(lin + attn + 2 * rows * d * v)


def decode_bytes(cfg: dict, rows: int, pos: int) -> float:
    """Weights once, the keys and values at true positions read and the
    new ones written, embedding rows in and logits out."""
    d, _, kvh, hd, _, v, n, window = _dims(cfg)
    kv = 2 * rows * keys_seen(pos, window) * kvh * hd * BF16 * n
    return float(matrix_params(cfg) * BF16 + kv + rows * d * BF16
                 + rows * v * F32)


def least_seconds(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    tc, tm = flops / peak["flops_per_s"], nbytes / peak["bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")


def call_work(cfg: dict, rows: int, s: int, n_new: int):
    """(prefill flops, prefill bytes, [(decode flops, decode bytes)]) of
    one tier call: a prefill of ``rows`` prompts of ``s`` tokens, then
    ``n_new - 1`` decode steps (the first new token comes from the
    prefill's logits)."""
    steps = [(decode_flops(cfg, rows, s + i), decode_bytes(cfg, rows, s + i))
             for i in range(n_new - 1)]
    return prefill_flops(cfg, rows, s), prefill_bytes(cfg, rows, s), steps
