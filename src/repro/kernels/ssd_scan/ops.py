"""jit'd wrapper: SSD scan with the D skip-connection term."""
from __future__ import annotations

from repro.kernels.ssd_scan.kernel import ssd_scan


def ssd(x, dt, a, bm, cm, d=None, *, chunk: int = 256,
        interpret: bool = False):
    """Full SSD mixer core: y = SSD(x, dt, A, B, C) [+ D * x]."""
    y = ssd_scan(x, dt, a, bm, cm, chunk=chunk, interpret=interpret)
    if d is not None:
        y = y + d[:, None] * x
    return y
