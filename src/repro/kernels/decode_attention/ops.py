"""jit'd wrapper for the decode-attention kernel."""
from __future__ import annotations

from repro.kernels.decode_attention.kernel import decode_attention


def gqa_decode(q, k, v, length, *, bk: int = 512, interpret: bool = False):
    """q: (B, 1, H, d) single-token query; k/v: (B, S, KVH, d).

    Returns (B, 1, H, dv)."""
    b, one, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, kvh, g, d)
    o = decode_attention(qg, k, v, length, bk=bk, interpret=interpret)
    return o.reshape(b, 1, h, v.shape[-1])
