"""Kernel micro-benchmarks.

On the CPU the Pallas kernels execute in interpret mode
(``repro.kernels.interpret_mode``), so wall-clock numbers there
characterize the interpreter only; the structural numbers (FLOPs, VMEM
working set) are the TPU-relevant derived columns.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode


def _time(fn, *args, iters=3):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.time()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / iters * 1e6


def bench_flash_attention():
    from repro.kernels.flash_attention.ops import mha
    key = jax.random.PRNGKey(0)
    b, s, h, kvh, d = 1, 512, 4, 2, 64
    q = jax.random.normal(key, (b, s, h, d), jnp.float32)
    k = jax.random.normal(key, (b, s, kvh, d), jnp.float32)
    v = jax.random.normal(key, (b, s, kvh, d), jnp.float32)
    us = _time(lambda *a: mha(*a, causal=True, interpret=interpret_mode(),
                              bq=128, bk=128), q, k, v)
    flops = 4 * b * h * s * s * d / 2
    vmem_kib = (128 * d * 4 * 3 + 128 * 128 * 4) / 1024
    return [{"kernel": "flash_attention", "us_per_call": us,
             "flops": flops, "vmem_tile_kib": vmem_kib}]


def bench_decode_attention():
    from repro.kernels.decode_attention.ops import gqa_decode
    key = jax.random.PRNGKey(0)
    b, s, h, kvh, d = 2, 2048, 8, 2, 128
    q = jax.random.normal(key, (b, 1, h, d), jnp.float32)
    k = jax.random.normal(key, (b, s, kvh, d), jnp.float32)
    v = jax.random.normal(key, (b, s, kvh, d), jnp.float32)
    us = _time(lambda *a: gqa_decode(*a, jnp.int32(s), bk=512,
                                     interpret=interpret_mode()), q, k, v)
    bytes_hbm = 2 * b * s * kvh * d * 4
    return [{"kernel": "decode_attention", "us_per_call": us,
             "cache_bytes": bytes_hbm,
             "arithmetic_intensity": (4 * b * h * s * d) / bytes_hbm}]


def bench_ssd_scan():
    from repro.kernels.ssd_scan.kernel import ssd_scan
    key = jax.random.PRNGKey(0)
    b, s, h, p, n = 1, 512, 4, 64, 32
    x = jax.random.normal(key, (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(key, (b, s, h)))
    a = -jnp.ones((h,))
    bm = jax.random.normal(key, (b, s, n))
    cm = jax.random.normal(key, (b, s, n))
    us = _time(lambda *args: ssd_scan(*args, chunk=128,
                                      interpret=interpret_mode()),
               x, dt, a, bm, cm)
    chunk_flops = 2 * 128 * 128 * (n + p)
    return [{"kernel": "ssd_scan", "us_per_call": us,
             "chunk_flops": chunk_flops,
             "state_vmem_kib": p * n * 4 / 1024}]


def bench_moe_gmm():
    from repro.kernels.moe_gmm.kernel import gmm
    key = jax.random.PRNGKey(0)
    e, c, k, f = 8, 256, 256, 512
    x = jax.random.normal(key, (e, c, k))
    w = jax.random.normal(key, (e, k, f))
    us = _time(lambda *a: gmm(*a, interpret=interpret_mode()), x, w)
    return [{"kernel": "moe_gmm", "us_per_call": us,
             "flops": 2 * e * c * k * f,
             "mxu_tile": "128x128x128"}]
