"""jit'd public wrapper: GQA-aware flash attention entry point."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention


def mha(q, k, v, *, causal: bool = True, window: int = 0,
        interpret: bool = False, bq: int = 128, bk: int = 128):
    """q: (B, S, H, d); k/v: (B, S, KVH, d). Returns (B, S, H, dv).

    KV heads are broadcast to query heads (GQA) before the kernel; the
    TPU kernel then runs one (batch*head) program per grid row.
    """
    b, s, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    dv = v.shape[-1]
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), g, axis=1).reshape(b * h, s, d)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), g, axis=1).reshape(b * h, s, dv)
    o = flash_attention(qf, kf, vf, causal=causal, window=window,
                        interpret=interpret, bq=bq, bk=bk)
    return o.reshape(b, h, s, dv).transpose(0, 2, 1, 3)
