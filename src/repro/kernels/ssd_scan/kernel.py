"""Mamba-2 SSD chunked-scan Pallas TPU kernel.

One program per (batch, head); the chunk grid dim is 'arbitrary' and the
SSM state (P, N) persists in VMEM scratch across chunks — the TPU
adaptation of the SSD algorithm: the intra-chunk quadratic part is a
(Q, Q) MXU matmul, the inter-chunk recurrence is the scratch carry, so
no sequential scan ever leaves VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_ref, *,
                q: int):
    cj = pl.program_id(2)

    @pl.when(cj == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0, 0].astype(jnp.float32)           # (q, p)
    dt = dt_ref[0, 0, 0, :, 0].astype(jnp.float32)   # (q,)
    a = a_ref[0]                                     # scalar A (negative)
    bm = b_ref[0, 0, 0].astype(jnp.float32)          # (q, n)
    cm = c_ref[0, 0, 0].astype(jnp.float32)          # (q, n)

    xdt = x * dt[:, None]
    da = dt * a                                      # (q,)
    da_cs = jnp.cumsum(da)                           # inclusive
    da_sum = da_cs[-1]

    # intra-chunk: L[i, j] = exp(da_cs[i] - da_cs[j]) for i >= j
    li = da_cs[:, None] - da_cs[None, :]
    iota_i = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    iota_j = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    l_mat = jnp.exp(jnp.where(iota_i >= iota_j, li, -jnp.inf))
    scores = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    y_diag = jax.lax.dot(scores * l_mat, xdt,
                         preferred_element_type=jnp.float32)

    # off-chunk: contribution of the state entering this chunk
    state = state_ref[...]                           # (p, n)
    y_off = jax.lax.dot_general(cm, state, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_off = y_off * jnp.exp(da_cs)[:, None]          # decay within chunk
    y_ref[0, 0, 0] = (y_diag + y_off).astype(y_ref.dtype)

    # update state: decay old state through the chunk + inject chunk inputs
    decay_end = jnp.exp(da_sum - da_cs)              # (q,)
    upd = jax.lax.dot_general(xdt * decay_end[:, None], bm,
                              (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (p, n)
    state_ref[...] = jnp.exp(da_sum) * state + upd


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, a, bm, cm, *, chunk: int = 256, interpret: bool = False):
    """x: (B, S, H, P); dt: (B, S, H); a: (H,); bm/cm: (B, S, N).

    Returns y: (B, S, H, P) = SSD(x*dt) without the D skip term."""
    b, s, h, p = x.shape
    n = bm.shape[-1]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk

    # pre-chunk the operands: (B, H, NC, Q, ...)
    xr = x.transpose(0, 2, 1, 3).reshape(b, h, nc, chunk, p)
    dtr = dt.transpose(0, 2, 1).reshape(b, h, nc, chunk, 1)
    br = bm.reshape(b, 1, nc, chunk, n)
    cr = cm.reshape(b, 1, nc, chunk, n)

    kernel = functools.partial(_ssd_kernel, q=chunk)
    y = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, chunk, p),
                         lambda bi, hi, cj: (bi, hi, cj, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk, 1),
                         lambda bi, hi, cj: (bi, hi, cj, 0, 0)),
            pl.BlockSpec((1,), lambda bi, hi, cj: (hi,)),
            pl.BlockSpec((1, 1, 1, chunk, n),
                         lambda bi, hi, cj: (bi, 0, cj, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk, n),
                         lambda bi, hi, cj: (bi, 0, cj, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, chunk, p),
                               lambda bi, hi, cj: (bi, hi, cj, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, nc, chunk, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xr, dtr, a, br, cr)
    return y.reshape(b, h, s, p).transpose(0, 2, 1, 3)
