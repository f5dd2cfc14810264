"""Pallas TPU kernel: pending-set compaction for the cascade executor.

Output-blocked formulation. The wrapper computes every kept row's
destination slot once, ``pos = cumsum(keep) - 1`` (``-1`` for dropped
rows), as a plain XLA prefix sum. The kernel then fills the output one
block of ``block`` slots at a time (grid dim 0, parallel), scanning the
input in blocks (grid dim 1, arbitrary) and accumulating

    out[k] = sum_i idx[i] * (pos[i] == k)

as a compare, a select and a lane reduction on the VPU. Each slot
receives at most one row, so the int32 sum is exact, and there is no
dynamic store, scatter, sort or integer matmul: the shapes Mosaic (the
TPU kernel compiler) handles worst. A kept row never moves right
(``pos[i] <= i``), so input blocks left of the output block are skipped.
Memory is O(block^2) per step; work is O(n^2 / 2) compares, which is
microseconds at serving batch sizes.

Bit-exact against ``ref.compact_ref`` — the equivalence suite
(tests/test_placement.py) relies on that.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _compact_kernel(idx_ref, pos_ref, out_ref, *, q: int):
    kb = pl.program_id(0)
    ib = pl.program_id(1)

    @pl.when(ib == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(ib >= kb)                               # pos[i] <= i
    def _gather():
        slot = kb * q + jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
        hit = pos_ref[...] == slot                   # (q slots, q rows)
        vals = jnp.where(hit, idx_ref[...], 0)
        out_ref[...] += jnp.sum(vals, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("fill", "interpret", "block"))
def compact_pallas(idx, keep, *, fill: int = -1, interpret: bool = False,
                   block: int = 256):
    """idx (n,) int32, keep (n,) bool -> (padded (n,) int32, count).

    ``padded[:count]`` are the kept indices in original order; the tail
    is ``fill``. ``block`` is the per-grid-step slot and row count (a
    multiple of 128 on the chip, or at least ``n``).
    """
    n = idx.shape[0]
    q = min(block, max(n, 1))
    nb = -(-n // q)                                  # ceil blocks
    n_pad = nb * q
    ki = keep.astype(jnp.int32)
    pos = jnp.where(keep, jnp.cumsum(ki) - 1, -1)
    idx_p = jnp.pad(idx.astype(jnp.int32), (0, n_pad - n))
    pos_p = jnp.pad(pos, (0, n_pad - n), constant_values=-1)
    out = pl.pallas_call(
        functools.partial(_compact_kernel, q=q),
        grid=(nb, nb),
        in_specs=[pl.BlockSpec((1, q), lambda k, i: (0, i)),
                  pl.BlockSpec((1, q), lambda k, i: (0, i))],
        out_specs=pl.BlockSpec((q, 1), lambda k, i: (k, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, 1), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(idx_p[None, :], pos_p[None, :])
    count = jnp.sum(ki)
    lane = jnp.arange(n, dtype=jnp.int32)
    return jnp.where(lane < count, out[:n, 0], fill), count
