"""Compiles for a described TPU v5e chip: the serving path's Pallas
kernels and one full-width gemma3-1b decode step, at gemma3-1b's
published widths, and the flash prefill kernel at the longctx bucket.
Nothing runs; the chip's compiler (Mosaic for the kernels) refuses here
what it would refuse on the chip.

The topology is described inside a fixture and nowhere else: only one
process at a time may load the TPU library, and pytest's workers each
import this file."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import ARCHS

CFG = ARCHS["gemma3-1b"]
V5E_BYTES = 16 * 2 ** 30                 # one v5e chip's HBM


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep it out of the cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_flash_prefill_kernel_compiles(one_chip):
    from repro.kernels.flash_attention.ops import mha

    b, s, hd = 8, 256, CFG.head_dim
    q = _spec(one_chip, (b, s, CFG.n_heads, hd))
    kv = _spec(one_chip, (b, s, CFG.n_kv_heads, hd))
    c = _compile(lambda q, k, v: mha(q, k, v, causal=True, window=CFG.window,
                                     bq=128, bk=128, interpret=False),
                 q, kv, kv)
    assert "tpu_custom_call" in c.as_text()


def test_flash_prefill_kernel_compiles_at_the_longctx_bucket(one_chip):
    """The served prefill's kernel at StarCoder2's one-chip share and the
    longctx bucket: 8 x 6144 tokens, 12 query heads on one KV head,
    head_dim 128, window 4096, blocks chosen from the shape."""
    from repro.kernels.flash_attention.ops import mha

    b, s, hd = 8, 6144, 128
    q = _spec(one_chip, (b, s, 12, hd))
    kv = _spec(one_chip, (b, s, 1, hd))
    c = _compile(lambda q, k, v: mha(q, k, v, causal=True, window=4096),
                 q, kv, kv)
    assert "tpu_custom_call" in c.as_text()


def test_decode_kernel_compiles_over_a_4096_slot_cache(one_chip):
    from repro.kernels.decode_attention.ops import gqa_decode

    b, hd = 8, CFG.head_dim
    q = _spec(one_chip, (b, 1, CFG.n_heads, hd))
    kv = _spec(one_chip, (b, 4096, CFG.n_kv_heads, hd))
    c = _compile(lambda q, k, v, n: gqa_decode(q, k, v, n, bk=128,
                                               interpret=False),
                 q, kv, kv, _spec(one_chip, (), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


def test_compaction_kernel_compiles_at_4096_rows(one_chip):
    from repro.kernels.cascade_compact.kernel import compact_pallas

    c = _compile(lambda i, k: compact_pallas(i, k, interpret=False),
                 _spec(one_chip, (4096,), jnp.int32),
                 _spec(one_chip, (4096,), jnp.bool_))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("kernels", [False, True])
def test_full_width_decode_step_fits_one_chip(one_chip, monkeypatch,
                                              kernels):
    """One jitted decode step of the whole 26-layer model at a serving
    bucket (64 rows, 512-slot cache), f32 master weights included, fits
    the chip's memory. With kernels on, the decode kernel is compiled
    into the global layers (the interpreter is chosen by the host's CPU
    backend, so the test steers it off)."""
    import repro.kernels as K
    from repro.models import transformer as T

    batch, slots = 64, 512
    params = jax.eval_shape(lambda k: T.init_params(k, CFG),
                            jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: T.init_cache(CFG, batch, slots))
    put = lambda x: _spec(one_chip, x.shape, x.dtype)  # noqa: E731
    monkeypatch.setattr(K, "interpret_mode", lambda: False)
    K.enable_kernels(kernels)
    try:
        c = _compile(lambda p, c, t, s: T.decode_step(p, c, t, s, CFG),
                     jax.tree.map(put, params), jax.tree.map(put, cache),
                     _spec(one_chip, (batch, 1), jnp.int32),
                     _spec(one_chip, (), jnp.int32))
    finally:
        K.enable_kernels(False)
    m = c.memory_analysis()
    need = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert need < V5E_BYTES, need
    assert ("tpu_custom_call" in c.as_text()) == kernels
