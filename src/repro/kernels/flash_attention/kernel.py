"""Flash attention (prefill) Pallas TPU kernel.

Online-softmax blocked attention with causal and sliding-window masking.
GQA without copies: the ``g`` query heads that share one KV head ride in
the query block, ``g * bq`` rows, so each K/V block is read once for all
of them. Grid = (batch*kv_heads, n_q_blocks, band_steps): the kv axis
walks only the kv blocks that meet query block ``i``'s band
(``kv_band``), so a block the causal/window mask hides entirely is never
fetched or multiplied. The kv axis is 'arbitrary' so the running max,
denominator and accumulator persist in VMEM scratch across its steps.

The arithmetic is the served jnp path's (``models.attention``): QK^T on
the operands in their own dtype with f32 accumulation, scaled by
``1/sqrt(d)`` in f32; running max and denominator in f32; ``p`` cast to
``v``'s dtype for the PV product, which accumulates in f32; the final
normalisation in f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def kv_band(qi, *, bq: int, bk: int, seq: int, causal: bool, window: int):
    """(first, last) kv block, inclusive, that holds a key some query of
    query block ``qi`` may attend: the blocks meeting
    ``[q_lo - window + 1, q_hi]`` (``[0, q_hi]`` without a window; up to
    the last key when not causal). ``qi`` may be traced."""
    q_lo = qi * bq
    key_lo = q_lo - window + 1
    clamp = max if isinstance(key_lo, int) else jnp.maximum
    first = clamp(key_lo, 0) // bk if window else 0
    last = (q_lo + bq - 1 if causal else seq - 1) // bk
    return first, last


def band_steps(*, bq: int, bk: int, seq: int, causal: bool,
               window: int) -> int:
    """The widest band over all query blocks: the kv grid's length."""
    return max(last - first + 1 for first, last in (
        kv_band(i, bq=bq, bk=bk, seq=seq, causal=causal, window=window)
        for i in range(seq // bq)))


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, window: int, bq: int, bk: int,
                  seq: int, steps: int):
    qi, j = pl.program_id(1), pl.program_id(2)
    g, _, d = q_ref.shape[1:]
    rows = g * bq
    first, last = kv_band(qi, bq=bq, bk=bk, seq=seq, causal=causal,
                          window=window)
    kb = first + j                       # this step's kv block
    q_lo, k_lo = qi * bq, kb * bk

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def block(masked: bool):
        q = q_ref[0].reshape(rows, d)
        s = jax.lax.dot_general(q, k_ref[0], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if masked:
            # row r of the folded block is query q_lo + r % bq
            qpos = q_lo + jnp.bitwise_and(
                jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 0), bq - 1)
            kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 1)
            mask = jnp.ones((rows, bk), jnp.bool_)
            if causal:
                mask &= kpos <= qpos
            if window:
                mask &= kpos > qpos - window
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                               # (rows, LANES)
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next[:, :1])
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_next
        v = v_ref[0]
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    # a block every query of the q block sees whole needs no mask
    edge = jnp.zeros((), jnp.bool_)
    if causal:
        edge |= k_lo + bk - 1 > q_lo
    if window:
        edge |= k_lo <= q_lo + bq - 1 - window
    live = kb <= last
    pl.when(live & edge)(lambda: block(True))
    pl.when(live & jnp.logical_not(edge))(lambda: block(False))

    @pl.when(j == steps - 1)
    def _done():
        o = acc_ref[...] / l_ref[...][:, :1]
        o_ref[0] = o.reshape(g, bq, -1).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    bq: int = 128, bk: int = 128, interpret: bool = False):
    """q: (BK, g, S, d), the g query heads of each (batch, KV head) row;
    k/v: (BK, S, d). ``bq`` is a power of two. Returns (BK, g, S, dv)."""
    bkv, g, s, d = q.shape
    dv = v.shape[-1]
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)
    assert bq & (bq - 1) == 0, bq
    band = dict(bq=bq, bk=bk, seq=s, causal=causal, window=window)
    steps = band_steps(**band)
    rows = g * bq

    def kv_block(b, i, j):
        first, last = kv_band(i, **band)
        # past the band the index stays on its last block: no new DMA
        return b, jnp.minimum(first + j, last), 0

    kernel = functools.partial(_flash_kernel, scale=1.0 / (d ** 0.5),
                               steps=steps, **band)
    item = q.dtype.itemsize
    # double-buffered q/k/v/o blocks, f32 scratch, the (rows, bk) scores
    # and their exp, with room for the compiler's temporaries
    vmem = (2 * (rows * (d + dv) + bk * (d + dv)) * item
            + rows * (dv + 2 * LANES) * 4 + 4 * rows * bk * 4)
    return pl.pallas_call(
        kernel,
        grid=(bkv, s // bq, steps),
        in_specs=[
            pl.BlockSpec((1, g, bq, d), lambda b, i, j: (b, 0, i, 0)),
            pl.BlockSpec((1, bk, d), kv_block),
            pl.BlockSpec((1, bk, dv), kv_block),
        ],
        out_specs=pl.BlockSpec((1, g, bq, dv), lambda b, i, j: (b, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bkv, g, s, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((rows, dv), jnp.float32),      # running accumulator
            pltpu.VMEM((rows, LANES), jnp.float32),   # running max
            pltpu.VMEM((rows, LANES), jnp.float32),   # running denominator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=max(32 << 20, min(2 * vmem, 100 << 20))),
        interpret=interpret,
    )(q, k, v)
