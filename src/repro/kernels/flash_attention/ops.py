"""GQA-aware flash attention entry point and its block choice."""
from __future__ import annotations

from repro.kernels.flash_attention.kernel import flash_attention

BLOCKS = (512, 256, 128)


def prefill_blocks(seq: int) -> tuple[int, int] | None:
    """(bq, bk) for ``seq`` tokens: both the largest of 512/256/128 that
    divides ``seq``, or None where none does. On a v5e the largest
    blocks ran fastest at both served shapes, 12 heads folded on one KV
    head included (PERF.md, section 6)."""
    b = next((b for b in BLOCKS if seq % b == 0), None)
    return None if b is None else (b, b)


def mha(q, k, v, *, causal: bool = True, window: int = 0,
        interpret: bool = False, bq: int | None = None,
        bk: int | None = None):
    """q: (B, S, H, d); k/v: (B, S, KVH, d). Returns (B, S, H, dv).

    The H // KVH query heads of each KV head share the kernel's query
    block; K and V are not copied per head. Blocks default to
    ``prefill_blocks``."""
    b, s, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    dv = v.shape[-1]
    if bq is None or bk is None:
        bq, bk = prefill_blocks(s)
    qf = q.reshape(b, s, kvh, g, d).transpose(0, 2, 3, 1, 4)
    qf = qf.reshape(b * kvh, g, s, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kvh, s, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kvh, s, dv)
    o = flash_attention(qf, kf, vf, causal=causal, window=window,
                        interpret=interpret, bq=bq, bk=bk)
    return o.reshape(b, kvh, g, s, dv).transpose(0, 3, 1, 2, 4).reshape(
        b, s, h, dv)
