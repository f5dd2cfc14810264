"""The launcher's device handling and the compile-cache location:
``--devices``/``--mesh`` force host devices only on the CPU platform and
never shrink a request, and the persistent cache lives at one fixed
path."""
import os
import subprocess
import sys

import jax
import pytest

from repro.launch import compile_cache
from repro.launch.serve import _force_host_devices

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src")


@pytest.mark.parametrize("argv,environ,want", [
    (["--devices", "4"], {"JAX_PLATFORMS": "cpu"}, "4"),
    (["--devices=4"], {"JAX_PLATFORMS": "cpu"}, "4"),
    (["--mesh", "2,2"], {"JAX_PLATFORMS": "cpu"}, "4"),
    (["--devices", "2", "--mesh", "4,2"], {"JAX_PLATFORMS": "cpu"}, "8"),
    (["--devices", "1"], {"JAX_PLATFORMS": "cpu"}, None),
    ([], {"JAX_PLATFORMS": "cpu"}, None),
    (["--devices", "4"], {"JAX_PLATFORMS": "tpu"}, None),
    (["--devices", "4"], {}, None),
    (["--devices", "4"], {"JAX_PLATFORMS": "cpu", "XLA_FLAGS": "--x"},
     "--x"),
])
def test_host_devices_forced_only_on_the_cpu(argv, environ, want):
    env = dict(environ)
    _force_host_devices(["serve", *argv], env)
    got = env.get("XLA_FLAGS")
    if want is None or want.startswith("--"):
        assert got == want
    else:
        assert got == f"--xla_force_host_platform_device_count={want}"


def _launch(code, extra_env):
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.update(extra_env)
    return subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)


def test_cpu_launcher_forces_the_requested_device_count():
    out = _launch("import sys; sys.argv = ['serve', '--devices', '4']; "
                  "import repro.launch.serve, jax; "
                  "print(len(jax.devices()))", {})
    assert out.returncode == 0, out.stderr
    assert out.stdout.split()[-1] == "4"


@pytest.mark.parametrize("request_flags", ["'--mesh', '4,1'",
                                           "'--devices', '4'"])
def test_too_few_devices_is_an_error_not_a_smaller_mesh(request_flags):
    # a preset XLA_FLAGS stops the forcing, so one CPU device exists
    out = _launch(f"import sys; sys.argv = ['serve', {request_flags}]; "
                  "from repro.launch.serve import main; main()",
                  {"XLA_FLAGS": ""})
    assert out.returncode == 2
    assert "4 devices requested" in out.stderr
    assert "cpu has 1" in out.stderr


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_path_is_fixed(monkeypatch, tmp_path, env_dir):
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(tmp_path / ".jax_cache")
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    prev = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable_compile_cache(tmp_path) == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_default_cache_is_in_the_checkout_and_ignored():
    root = os.path.dirname(SRC)
    assert os.path.samefile(compile_cache.REPO_ROOT, root)
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
