"""Kernel-integration tests: the model stack with Pallas kernels enabled
(interpret mode) must match the pure-jnp reference path."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import LayerSpec, ModelConfig, MoECfg, SSMCfg
from repro.kernels import enable_kernels
from repro.models import transformer as T

KEY = jax.random.PRNGKey(3)


@pytest.fixture(autouse=True)
def _reset_kernels():
    yield
    enable_kernels(False)


def _cfg_dense():
    return ModelConfig(
        name="ki-dense", arch_type="dense", n_layers=2, d_model=128,
        n_heads=4, n_kv_heads=2, head_dim=32, d_ff=256, vocab=512,
        period=(LayerSpec("attn", "dense"),), n_periods=2, pos="rope",
        ffn_act="swiglu", max_seq=512, dtype="float32")


def _cfg_moe():
    return ModelConfig(
        name="ki-moe", arch_type="moe", n_layers=2, d_model=128,
        n_heads=4, n_kv_heads=4, head_dim=32, d_ff=128, vocab=512,
        period=(LayerSpec("attn", "moe"),), n_periods=2,
        moe=MoECfg(n_experts=4, top_k=2, d_expert=128, capacity_factor=2.0),
        pos="rope", ffn_act="swiglu", max_seq=512, dtype="float32")


def _cfg_ssm():
    return ModelConfig(
        name="ki-ssm", arch_type="ssm", n_layers=2, d_model=128,
        d_ff=0, vocab=512, period=(LayerSpec("mamba", "none"),), n_periods=2,
        ssm=SSMCfg(d_state=16, head_dim=32, expand=2, d_conv=4, chunk=64),
        pos="none", ffn_act="swiglu", tie_embeddings=True, max_seq=512,
        dtype="float32")


@pytest.mark.parametrize("make_cfg", [_cfg_dense, _cfg_moe, _cfg_ssm])
def test_train_forward_matches_reference(make_cfg):
    cfg = make_cfg()
    params = T.init_params(KEY, cfg)
    toks = jax.random.randint(KEY, (2, 128), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": toks}
    loss_ref, _ = T.forward_train(params, batch, cfg, remat=False)
    enable_kernels(True)
    loss_k, _ = T.forward_train(params, batch, cfg, remat=False)
    enable_kernels(False)
    assert jnp.allclose(loss_ref, loss_k, rtol=2e-4, atol=2e-4), \
        (float(loss_ref), float(loss_k))


def test_decode_matches_reference():
    cfg = _cfg_dense()
    params = T.init_params(KEY, cfg)
    toks = jax.random.randint(KEY, (2, 129), 0, cfg.vocab)
    _, cache = T.prefill(params, {"tokens": toks[:, :-1]}, cfg, max_len=256)
    lg_ref, _ = T.decode_step(params, cache, toks[:, -1:], jnp.int32(128), cfg)
    enable_kernels(True)
    lg_k, _ = T.decode_step(params, cache, toks[:, -1:], jnp.int32(128), cfg)
    enable_kernels(False)
    err = float(jnp.abs(lg_ref - lg_k).max() / (jnp.abs(lg_ref).max() + 1e-9))
    assert err < 1e-3, err


# ---------------------------------------------------------------------------
# Served prefill: the flash kernel on a TPU backend, routed by observable
# input (mode, backend, length, partitioning) with no switch
# ---------------------------------------------------------------------------


def _cfg_windowed():
    return dataclasses.replace(
        _cfg_dense(), name="ki-windowed", n_heads=8, n_kv_heads=2,
        period=(LayerSpec("attn_sliding", "dense"),), window=64)


def _as_tpu(monkeypatch):
    """The backend check sees a TPU; kernels still run interpreted."""
    import repro.kernels as K

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(K, "interpret_mode", lambda: True)


ROUTES = {  # case: (backend, mode, seq, partitioned, kernel expected)
    "tpu-prefill": ("tpu", "prefill", 256, False, True),
    "tpu-train": ("tpu", "train", 256, False, False),
    "tpu-untiled-length": ("tpu", "prefill", 96, False, False),
    "cpu-prefill": ("cpu", "prefill", 256, False, False),
    "tpu-partitioned": ("tpu", "prefill", 256, True, False),
}


@pytest.mark.parametrize("case", ROUTES)
def test_prefill_attention_route(monkeypatch, case):
    """Prefill on a TPU takes the kernel where S tiles, and matches
    ``_chunked_attention``; train mode, the CPU, a length that does not
    tile and a partitioned program keep ``_chunked_attention``. The
    engine's test (``prefill_flash``) agrees with the route taken."""
    from repro.models import attention

    backend, mode, seq, sharded, kernel = ROUTES[case]
    cfg = _cfg_windowed()
    p = attention.init_attn(KEY, cfg)
    x = jax.random.normal(KEY, (2, seq, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(seq), (2, seq))

    def run(x):
        return attention.apply_attn(p, x, cfg=cfg, sliding=True, mode=mode,
                                    positions=pos, max_len=seq)[0]

    want = run(x)                       # the CPU: _chunked_attention
    if backend == "tpu":
        _as_tpu(monkeypatch)
    with attention.partitioned(sharded):
        routed = "pallas_call" in str(jax.make_jaxpr(run)(x))
        got = run(x)
        assert T.prefill_flash(cfg, seq) == (kernel or mode == "train")
    assert routed == kernel
    assert jnp.allclose(got, want, atol=1e-5, rtol=1e-4), \
        float(jnp.abs(got - want).max())


def test_prefill_flash_calls_counts_kernel_prefills(monkeypatch):
    """A prefill whose bucket tiles counts as a kernel prefill and its
    program holds the kernel; a short bucket and a mesh-sharded engine
    count none and hold none."""
    import numpy as np
    from jax.sharding import Mesh

    from repro.core import telemetry
    from repro.serving.engine import GenerationEngine

    cfg = _cfg_dense()
    params = T.init_params(KEY, cfg)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    _as_tpu(monkeypatch)
    single = GenerationEngine(cfg, params, max_new_tokens=2)
    sharded = GenerationEngine(cfg, params, max_new_tokens=2, mesh=mesh)
    for eng, want in ((single, (3, 2)), (sharded, (3, 0))):
        rec = telemetry.ChunkCounters()
        with telemetry.counting(rec):
            for width in (128, 20, 128):    # buckets 128, 32, 128
                eng.generate(np.ones((2, width), np.int32), 2)
        assert (rec.prefill_calls, rec.prefill_flash_calls) == want
        for key, flash in eng._prefill_flash.items():
            toks = jax.ShapeDtypeStruct(key[:2], jnp.int32)
            jaxpr = jax.make_jaxpr(eng._prefill_fn(key))(
                eng.params, toks, jnp.int32(0))
            assert ("pallas_call" in str(jaxpr)) == flash == (
                eng is single and key[1] == 128)
