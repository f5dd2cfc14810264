"""Dispatch wrapper: on-device pending-set compaction for the cascade.

Two device backends behind one call, both bit-identical to the numpy
oracle (``ref.compact_ref``):

  * ``backend="jnp"``    — a jitted stable-argsort formulation (kept
    rows keep their original relative order; sort keys are distinct so
    the result is deterministic on every XLA backend);
  * ``backend="pallas"`` — the Pallas kernel (``kernel.compact_pallas``,
    interpret mode on the CPU, compiled everywhere else) alongside the
    repo's other kernel families.

Fixed output shape (padded to the input length, ``fill`` in the tail)
keeps both variants jittable; the true length comes back as a scalar
alongside, so callers that can stay on device slice there (callers that
also need the indices on host — the cascade executor's bookkeeping
scatters do — still pull the compacted vector back).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.cascade_compact.kernel import compact_pallas

BACKENDS = ("jnp", "pallas")


@functools.partial(jax.jit, static_argnames=("fill",))
def _compact_jnp(idx, keep, fill: int):
    n = idx.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    # stable partition: kept rows sort by original position, rejected
    # rows sort after every kept one — keys are distinct ints, so the
    # argsort (and therefore the result) is fully deterministic
    order = jnp.argsort(jnp.where(keep, iota, n + iota))
    count = jnp.sum(keep.astype(jnp.int32))
    out = jnp.where(iota < count, idx.astype(jnp.int32)[order], fill)
    return out, count


def compact(idx, keep, *, backend: str = "jnp", fill: int = -1,
            interpret: bool | None = None, block: int = 256):
    """idx (n,), keep (n,) bool -> (padded (n,) int32 device array,
    count int32 scalar). ``padded[:count]`` are the kept indices in
    original order. ``interpret=None`` defers to
    ``repro.kernels.interpret_mode`` (the interpreter on the CPU only);
    ``block`` is the Pallas kernel's per-grid-step row count.
    """
    if backend not in BACKENDS:
        raise ValueError(f"unknown compaction backend {backend!r}; "
                         f"expected one of {BACKENDS}")
    idx = jnp.asarray(idx)
    keep = jnp.asarray(keep, bool)
    if idx.shape != keep.shape or idx.ndim != 1:
        raise ValueError(f"idx/keep must be matching 1-D vectors, got "
                         f"{idx.shape} and {keep.shape}")
    if idx.shape[0] == 0:
        return idx.astype(jnp.int32), jnp.int32(0)
    if backend == "pallas":
        if interpret is None:
            interpret = interpret_mode()
        return compact_pallas(idx, keep, fill=fill, interpret=interpret,
                              block=block)
    return _compact_jnp(idx, keep, fill)
