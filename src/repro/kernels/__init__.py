"""Pallas TPU kernels (kernel.py + ops.py wrapper + ref.py oracle each).

Served prefill on a TPU takes the flash kernel with no switch
(``models.attention.flash_prefill``: mode "prefill", a TPU backend, a
program on one device, a length the kernel tiles). Everything else is
behind ``enable_kernels(True)``, which routes the model stack's other
hot paths through the kernels (train-mode attention, decode attention,
``ssd_scan``, ``moe_gmm``). Default off: the pure-jnp path is the
oracle. Kernel entry points compile by default (``interpret=False``);
callers that run on whatever backend is present pass
``interpret=interpret_mode()``, so the Pallas interpreter runs only on
the CPU and a kernel on the chip either compiles or fails loudly.
"""
import jax

_ENABLED = False


def enable_kernels(on: bool = True):
    global _ENABLED
    _ENABLED = on


def kernels_enabled() -> bool:
    return _ENABLED


def interpret_mode() -> bool:
    """True only where the default backend is the CPU: Pallas has no CPU
    compiler, so kernels run in its interpreter there and nowhere else."""
    return jax.default_backend() == "cpu"
