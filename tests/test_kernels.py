"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret=True)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.cascade_compact.ops import compact
from repro.kernels.cascade_compact.ref import compact_ref
from repro.kernels.decode_attention.ops import gqa_decode
from repro.kernels.decode_attention.ref import decode_ref
from repro.kernels.flash_attention.kernel import band_steps, kv_band
from repro.kernels.flash_attention.ops import mha
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.moe_gmm.kernel import gmm
from repro.kernels.moe_gmm.ops import expert_mlp
from repro.kernels.moe_gmm.ref import gmm_ref
from repro.kernels.ssd_scan.kernel import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_ref

KEY = jax.random.PRNGKey(7)


def _rand(shape, dtype=jnp.float32, key=KEY):
    return jax.random.normal(key, shape).astype(dtype)


@pytest.mark.parametrize("b,s,h,kvh,d", [
    (1, 128, 4, 4, 64),     # MHA
    (2, 256, 4, 2, 64),     # GQA 2:1
    (1, 256, 8, 1, 32),     # MQA
    (2, 128, 4, 4, 128),    # MXU-width head dim
    (1, 512, 4, 2, 64),     # S > window: whole kv blocks skipped
    (1, 256, 12, 1, 128),   # MQA at StarCoder2's ratio
    (1, 256, 4, 1, 256),    # gemma3's head dim
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64), (False, 0),
                                           (True, 128)])
def test_flash_attention_sweep(b, s, h, kvh, d, dtype, causal, window):
    q = _rand((b, s, h, d), dtype)
    k = _rand((b, s, kvh, d), dtype, jax.random.PRNGKey(1))
    v = _rand((b, s, kvh, d), dtype, jax.random.PRNGKey(2))
    o = mha(q, k, v, causal=causal, window=window, interpret=True,
            bq=64, bk=64)
    g = h // kvh
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), g, 1).reshape(b * h, s, d)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), g, 1).reshape(b * h, s, d)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    r = attention_ref(qf, kf, vf, causal=causal, window=window)
    r = r.reshape(b, h, s, d).transpose(0, 2, 1, 3)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    assert jnp.allclose(o.astype(jnp.float32), r.astype(jnp.float32),
                        atol=tol, rtol=tol), float(jnp.abs(o - r).max())


@pytest.mark.parametrize("seq,bq,bk", [(512, 64, 64), (512, 128, 64),
                                        (512, 64, 128), (384, 128, 128)])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 128),
                                           (True, 100), (False, 0),
                                           (False, 64)])
def test_kv_band_visits_exactly_the_blocks_with_a_visible_key(seq, bq, bk,
                                                              causal,
                                                              window):
    """Every kv block the kernel visits for a query block holds a key one
    of its queries may attend, and every block that holds one is
    visited; the grid's kv length is the widest band."""
    qpos, kpos = np.arange(seq)[:, None], np.arange(seq)[None, :]
    mask = np.ones((seq, seq), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    band = dict(bq=bq, bk=bk, seq=seq, causal=causal, window=window)
    widths = []
    for i in range(seq // bq):
        first, last = kv_band(i, **band)
        blocks = mask[i * bq:(i + 1) * bq].reshape(bq, seq // bk, bk)
        seen = np.flatnonzero(blocks.any(axis=(0, 2)))
        assert list(seen) == list(range(int(first), int(last) + 1)), i
        widths.append(int(last) - int(first) + 1)
    assert band_steps(**band) == max(widths)


@pytest.mark.parametrize("b,s,h,kvh,d,length", [
    (2, 512, 4, 2, 64, 300),
    (1, 256, 8, 8, 32, 256),
    (2, 1024, 8, 2, 128, 1),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(b, s, h, kvh, d, length, dtype):
    q = _rand((b, 1, h, d), dtype)
    k = _rand((b, s, kvh, d), dtype, jax.random.PRNGKey(1))
    v = _rand((b, s, kvh, d), dtype, jax.random.PRNGKey(2))
    o = gqa_decode(q, k, v, jnp.int32(length), bk=128, interpret=True)
    r = decode_ref(q.reshape(b, kvh, h // kvh, d), k, v, length)
    r = r.reshape(b, 1, h, d)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    assert jnp.allclose(o.astype(jnp.float32), r.astype(jnp.float32),
                        atol=tol, rtol=tol)


@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (1, 64, 2, 32, 16, 32),
    (2, 128, 4, 32, 16, 32),
    (1, 256, 2, 64, 32, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_sweep(b, s, h, p, n, chunk, dtype):
    x = _rand((b, s, h, p), dtype)
    dt = jax.nn.softplus(_rand((b, s, h), key=jax.random.PRNGKey(1))
                         ).astype(dtype)
    a = -jnp.exp(0.3 * _rand((h,), key=jax.random.PRNGKey(2)))
    bm = _rand((b, s, n), dtype, jax.random.PRNGKey(3))
    cm = _rand((b, s, n), dtype, jax.random.PRNGKey(4))
    o = ssd_scan(x, dt, a, bm, cm, chunk=chunk, interpret=True)
    r = ssd_ref(x.astype(jnp.float32), dt.astype(jnp.float32), a,
                bm.astype(jnp.float32), cm.astype(jnp.float32))
    scale = float(jnp.abs(r).max()) + 1e-6
    err = float(jnp.abs(o.astype(jnp.float32) - r).max()) / scale
    assert err < (3e-2 if dtype == jnp.bfloat16 else 1e-5), err


@pytest.mark.parametrize("e,c,k,f", [
    (4, 256, 128, 256),
    (2, 128, 256, 128),
    (8, 128, 128, 384),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gmm_sweep(e, c, k, f, dtype):
    x = _rand((e, c, k), dtype)
    w = _rand((e, k, f), dtype, jax.random.PRNGKey(1))
    o = gmm(x, w, interpret=True)
    r = gmm_ref(x, w)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    assert jnp.allclose(o.astype(jnp.float32), r.astype(jnp.float32),
                        atol=tol, rtol=tol)


def test_expert_mlp_against_einsum():
    e, c, d, f = 2, 128, 64, 128
    x = _rand((e, c, d))
    wg = _rand((e, d, f), key=jax.random.PRNGKey(1))
    wu = _rand((e, d, f), key=jax.random.PRNGKey(2))
    wd = _rand((e, f, d), key=jax.random.PRNGKey(3))
    o = expert_mlp(x, wg, wu, wd, interpret=True)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", x, wg)) * \
        jnp.einsum("ecd,edf->ecf", x, wu)
    r = jnp.einsum("ecf,efd->ecd", h, wd)
    assert jnp.allclose(o, r, atol=1e-3, rtol=1e-3)


# -- cascade pending-set compaction (gather + prefix-sum) -------------------
# accept-mask edge cases per the serving cascade: all-accept empties the
# pending set, none-accept keeps it whole, single rows and non-pow2
# batches must survive the fixed-shape padding. Both device backends are
# BIT-identical to the numpy oracle (the serving equivalence suite in
# tests/test_placement.py builds on this).


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("n", [1, 2, 7, 16, 33, 200])   # single row, non-pow2
def test_cascade_compact_sweep(n, backend):
    rng = np.random.default_rng(n)
    idx = rng.permutation(n).astype(np.int64) * 5       # non-trivial values
    for accept in (np.ones(n, bool),                    # all-accept
                   np.zeros(n, bool),                   # none-accept
                   rng.random(n) < 0.4):                # mixed
        keep = ~accept                                  # rejected rows stay
        ro, rc = compact_ref(idx, keep)
        o, c = compact(idx, keep, backend=backend)
        assert int(c) == rc
        assert np.array_equal(np.asarray(o), ro.astype(np.int32))


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_cascade_compact_preserves_order_and_padding(backend):
    idx = np.array([40, 10, 30, 20, 50], np.int64)
    keep = np.array([True, False, True, True, False])
    o, c = compact(idx, keep, backend=backend, fill=-7)
    assert int(c) == 3
    assert np.asarray(o).tolist() == [40, 30, 20, -7, -7]   # original order


def test_cascade_compact_empty_and_validation():
    o, c = compact(np.zeros(0, np.int64), np.zeros(0, bool))
    assert int(c) == 0 and len(np.asarray(o)) == 0
    with pytest.raises(ValueError, match="backend"):
        compact(np.arange(4), np.ones(4, bool), backend="cuda")
    with pytest.raises(ValueError, match="1-D"):
        compact(np.arange(4), np.ones(3, bool))
    with pytest.raises(ValueError, match="1-D"):
        compact(np.arange(4).reshape(2, 2), np.ones((2, 2), bool))


@pytest.mark.parametrize("block", [8, 32])
def test_cascade_compact_pallas_multi_block(block):
    """The block-sequential kernel: survivors spanning many grid steps
    land at the right running offsets, later blocks overwrite earlier
    garbage tails, and non-multiple-of-block sizes pad cleanly."""
    rng = np.random.default_rng(3)
    n = 101                                  # not a multiple of any block
    idx = rng.permutation(n).astype(np.int64)
    for density in (0.0, 0.5, 1.0):
        keep = rng.random(n) < density if density not in (0.0, 1.0) \
            else np.full(n, bool(density))
        ro, rc = compact_ref(idx, keep)
        o, c = compact(idx, keep, backend="pallas", block=block)
        assert int(c) == rc
        assert np.array_equal(np.asarray(o), ro.astype(np.int32))
