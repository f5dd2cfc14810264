"""Decode attention Pallas TPU kernel: one query token vs a long KV cache.

GQA layout: each program handles one (batch, kv_head) pair; the q-group
dim (queries per kv head) rides in the block's leading axis so the MXU
sees a (G, d) x (d, bk) matmul per block. Online softmax across kv
blocks, state in VMEM scratch. The cache validity horizon ``length`` is
a scalar-prefetch style operand (here: masked by absolute position).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                   l_ref, *, scale: float, bk: int, nk: int):
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)              # (g, d)
    k = k_ref[0, :, 0].astype(jnp.float32)           # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    kpos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (q.shape[0], bk), 1)
    valid = kpos < len_ref[0]
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    v = v_ref[0, :, 0].astype(jnp.float32)           # (bk, dv)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kj == nk - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def decode_attention(q, k, v, length, *, bk: int = 512,
                     interpret: bool = False):
    """q: (B, KVH, G, d); k/v: (B, S, KVH, d); length: scalar valid-length.

    Returns (B, KVH, G, dv)."""
    b, kvh, g, d = q.shape
    s = k.shape[1]
    dv = v.shape[-1]
    bk = min(bk, s)
    assert s % bk == 0, (s, bk)
    nk = s // bk
    scale = 1.0 / (d ** 0.5)
    length_arr = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (1,))

    kernel = functools.partial(_decode_kernel, scale=scale, bk=bk, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=(b, kvh, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, g, d), lambda bi, hi, kj: (bi, hi, 0, 0)),
            pl.BlockSpec((1, bk, 1, d), lambda bi, hi, kj: (bi, kj, hi, 0)),
            pl.BlockSpec((1, bk, 1, dv), lambda bi, hi, kj: (bi, kj, hi, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, dv), lambda bi, hi, kj: (bi, hi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, dv), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(length_arr, q, k, v)
