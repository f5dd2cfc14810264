"""jit'd wrapper: gated-MLP expert compute via grouped matmuls."""
from __future__ import annotations

import jax

from repro.kernels.moe_gmm.kernel import gmm


def expert_mlp(x, w_gate, w_up, w_down, *, interpret: bool = False):
    """x: (E, C, d); w_*: (E, d, f)/(E, f, d). SwiGLU expert FFN."""
    g = gmm(x, w_gate, interpret=interpret)
    u = gmm(x, w_up, interpret=interpret)
    h = (jax.nn.silu(g.astype(jax.numpy.float32)) *
         u.astype(jax.numpy.float32)).astype(x.dtype)
    return gmm(h, w_down, interpret=interpret)
