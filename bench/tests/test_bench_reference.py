"""The plain reference against the program's cached prefill and decode,
at a small size on the CPU: full attention, and a sliding window with a
prompt longer than the window, decoded further past it."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.families import dense_gqa_program as P
from bench.families import dense_gqa_reference as R
from bench.tests.tiny import CONFIGS


def program_logits(cfg, params, seq, prompt_len, dtype):
    """Logits of positions prompt_len-1 .. len(seq)-2: the prefill's last
    position, then one cached decode step per further token."""
    from repro.models import transformer as T

    pcfg = dataclasses.replace(P.program_config(cfg), dtype=dtype)
    toks = jnp.asarray(seq[None, :prompt_len])
    logits, cache = T.prefill(params, {"tokens": toks}, pcfg, max_len=len(seq) + 8)
    out = [logits[0, -1]]
    for pos in range(prompt_len, len(seq) - 1):
        lg, cache = T.decode_step(params, cache, jnp.asarray(seq[None, pos:pos + 1]),
                                  jnp.int32(pos), pcfg)
        out.append(lg[0, -1])
    return np.asarray(jnp.stack(out))


@pytest.mark.parametrize("name,prompt,total", [
    ("tiny-nemo", 12, 20),
    ("tiny-sc2", 12, 20),      # inside the 16-token window
    ("tiny-sc2", 24, 40),      # prompt and decode past the window
])
def test_reference_matches_program_in_float32(name, prompt, total):
    cfg = dict(CONFIGS[name], dtype="float32")
    params = P.make_params(cfg, 7)
    seq = np.random.default_rng(0).integers(1, cfg["vocab_size"], total).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got = program_logits(cfg, params, seq, prompt, "float32")
    want = R.logits(params, seq[:-1], cfg, first=prompt - 1)
    assert got.shape == want.shape == (total - prompt, cfg["vocab_size"])
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 1e-4 * scale


def test_window_matters():
    """Past the window the sliding reference differs from full attention,
    so the comparison above covers the ring cache."""
    cfg = CONFIGS["tiny-sc2"]
    params = P.make_params(cfg, 7)
    seq = np.random.default_rng(1).integers(1, cfg["vocab_size"], 40).astype(np.int32)
    windowed = R.logits(params, seq, cfg, first=20)
    full = R.logits(params, seq, dict(cfg, sliding_window=None), first=20)
    assert np.abs(windowed - full).max() > 1e-3 * np.abs(full).max()


def test_bfloat16_program_near_reference_and_fp8_further():
    """The served precision lands near the reference; the float8 control
    lands further from it on the same tokens."""
    cfg = CONFIGS["tiny-nemo"]
    params = P.make_params(cfg, 3)
    seq = np.random.default_rng(2).integers(1, cfg["vocab_size"], 30).astype(np.int32)
    ref = R.logits(params, seq[:-1], cfg, first=11)
    low = program_logits(cfg, params, seq, 12, "bfloat16")
    fp8 = R.logits(params, seq[:-1], cfg, first=11, precision="fp8")
    err_bf16 = np.abs(low - ref).max()
    err_fp8 = np.abs(fp8 - ref).max()
    assert err_bf16 < 0.05 * np.abs(ref).max()
    assert err_fp8 > 2 * err_bf16
