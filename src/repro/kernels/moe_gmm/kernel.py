"""Grouped (per-expert) matmul Pallas TPU kernel for MoE expert compute.

Computes out[e] = x[e] @ w[e] for all experts with one kernel launch:
grid = (E, C_blocks, F_blocks, K_blocks), fp32 accumulation in VMEM
scratch across the contraction grid dim. Block shapes are MXU-aligned
(128x128 tiles by default).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(x_ref, w_ref, o_ref, acc_ref, *, nk: int):
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0].astype(jnp.float32)                 # (bc, bk)
    w = w_ref[0].astype(jnp.float32)                 # (bk, bf)
    acc_ref[...] += jax.lax.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(kj == nk - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bc", "bf", "bk", "interpret"))
def gmm(x, w, *, bc: int = 128, bf: int = 128, bk: int = 128,
        interpret: bool = False):
    """x: (E, C, K); w: (E, K, F) -> (E, C, F)."""
    e, c, k = x.shape
    f = w.shape[-1]
    bc, bf, bk = min(bc, c), min(bf, f), min(bk, k)
    assert c % bc == 0 and f % bf == 0 and k % bk == 0, (c, f, k, bc, bf, bk)
    grid = (e, c // bc, f // bf, k // bk)

    kernel = functools.partial(_gmm_kernel, nk=k // bk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bc, bk), lambda ei, ci, fi, kj: (ei, ci, kj)),
            pl.BlockSpec((1, bk, bf), lambda ei, ci, fi, kj: (ei, kj, fi)),
        ],
        out_specs=pl.BlockSpec((1, bc, bf), lambda ei, ci, fi, kj: (ei, ci, fi)),
        out_shape=jax.ShapeDtypeStruct((e, c, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(x, w)
