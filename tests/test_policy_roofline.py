"""Sharding policy, roofline analysis, and an end-to-end small-mesh
dry-run smoke (subprocess: the 512-device flag must not leak here)."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.launch.roofline import (HBM_BW, LINK_BW, PEAK_FLOPS, analyze,
                                   model_flops, to_markdown)
from repro.sharding import policy


def test_constrain_noop_without_policy():
    x = jnp.ones((4, 8))
    assert policy.constrain(x, "dp", "model") is x


def test_constrain_under_single_device_mesh():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    with policy.policy(mesh):
        x = policy.constrain(jnp.ones((4, 8)), "dp", "model")
        assert x.shape == (4, 8)


def test_constrain_priority_resolution():
    """Heads claim 'model' when divisible; sequence takes it otherwise."""
    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 4, "model": 4}
        size = 16

    policy._ACTIVE_MESH = FakeMesh()
    try:
        import repro.sharding.policy as P

        # emulate spec computation only (with_sharding_constraint would
        # need real devices; we monkeypatch it to capture the spec)
        captured = {}

        def fake_wsc(x, sharding):
            captured["spec"] = sharding.spec
            return x

        orig = P.jax.lax.with_sharding_constraint
        orig_ns = P.NamedSharding
        P.NamedSharding = lambda mesh, spec: type(
            "NS", (), {"spec": spec})()
        P.jax.lax.with_sharding_constraint = fake_wsc
        try:
            # KVH=4 divisible -> heads get "model", seq gets nothing
            policy.constrain(jnp.ones((8, 16, 4, 8)), "dp", ("model",),
                             "model", None, priority=(0, 2, 1))
            assert captured["spec"][2] == "model"
            assert captured["spec"][1] is None
            # KVH=3 not divisible -> seq takes "model"
            policy.constrain(jnp.ones((8, 16, 3, 8)), "dp", ("model",),
                             "model", None, priority=(0, 2, 1))
            assert captured["spec"][1] == "model"
            assert captured["spec"][2] is None
        finally:
            P.jax.lax.with_sharding_constraint = orig
            P.NamedSharding = orig_ns
    finally:
        policy._ACTIVE_MESH = None


def test_model_flops_train_vs_decode():
    t = model_flops("gemma3-1b", "train_4k")
    d = model_flops("gemma3-1b", "decode_32k")
    assert t > d * 1000          # train step >> one decode token step


def test_roofline_analyze_real_results():
    path = os.path.join(os.path.dirname(__file__), "..",
                        "dryrun_results.json")
    if not os.path.exists(path):
        pytest.skip("dryrun_results.json not generated yet")
    rows = analyze(path)
    if len(rows) < 20:
        pytest.skip("dry-run sweep still in progress")
    for r in rows:
        assert r["dominant"] in ("compute", "memory", "collective")
        assert r["compute_s"] >= 0 and r["memory_s"] >= 0
        assert 0 <= r["useful_ratio"] <= 1.5
    md = to_markdown(rows)
    assert md.count("|") > 100


def test_dryrun_small_mesh_subprocess():
    """Full dryrun machinery on an 8-device host mesh in a subprocess."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import repro.launch.mesh as M
M.make_production_mesh = lambda multi_pod=False: M.auto_mesh(
    (2, 2, 2) if multi_pod else (2, 4),
    ("pod", "data", "model") if multi_pod else ("data", "model"))
import repro.configs.registry as REG
from repro.configs.registry import get_arch
cfg = get_arch("gemma3-1b").reduced()
REG.ARCHS["gemma3-1b"] = cfg
from repro.launch.dryrun import dryrun_one
r = dryrun_one("gemma3-1b", "train_4k", verbose=False)
assert r["status"] == "ok", r
r2 = dryrun_one("gemma3-1b", "decode_32k", verbose=False, multi_pod=True)
assert r2["status"] == "ok", r2
print("SMALL-MESH-DRYRUN-OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("JAX_PLATFORMS", None)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert "SMALL-MESH-DRYRUN-OK" in out.stdout, out.stderr[-3000:]
