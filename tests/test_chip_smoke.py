"""CPU rehearsal of ``chip_smoke.py``: its phase functions at a reduced
gemma3-1b on the CPU (Pallas interpreted), and its refusal to run
anywhere but on a TPU. Phase 1 (the device check) and phase 5 (four
chips) have no CPU rehearsal here."""
import dataclasses
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)

import chip_smoke as cs  # noqa: E402


def _reduced_cfg():
    """gemma3-1b.reduced() with one windowed and one global layer, in
    the published bfloat16 and window 512, as at full width: the global
    layer is where the decode kernel runs, the window admits the padded
    256-token prompt bucket the flash kernel needs, and bfloat16 makes
    the near-tie tolerance non-trivial."""
    from repro.configs.base import LayerSpec
    from repro.configs.registry import ARCHS

    full = ARCHS["gemma3-1b"]
    return dataclasses.replace(
        full.reduced(), period=(LayerSpec("attn_sliding", "dense"),
                                LayerSpec("attn", "dense")),
        dtype=full.dtype, window=full.window)


def test_launcher_phase_serve_equals_stream():
    from repro.core.router import RouterConfig

    cs.phase_launcher(requests=24, train_steps=20, train_queries=120,
                      scorer_steps=60,
                      router=RouterConfig(m=2, top_lists=4, sample=96))


def test_cascade_and_kernel_phases_at_reduced_width():
    cfg = _reduced_cfg()
    out = cs.phase_cascade(cfg)
    chk = out["check"]
    assert chk["positions"] == cs.N_CHECK * cs.N_NEW
    assert chk["match"] + chk["excused"] == chk["positions"]
    k = cs.phase_kernels(cfg, out["params"][0], out["engines"][0],
                         out["prompts"], chk["ref_head"])
    assert 0 < k["gap"] <= k["tol"]
    # phase 5's check of sharded tokens: equal calls pass untouched, and
    # a token that is not the reference's (near-)top fails
    call = (out["prompts"][:2], out["generated"][:2])
    assert cs.held_to_reference(cfg, out["params"][0], call, call) \
        == "0/2 rows differ"
    bad = call[1].copy()
    bad[1, 0] = (bad[1, 0] + 1) % cfg.vocab
    assert cs.held_to_reference(cfg, out["params"][0], (call[0], bad),
                                call).startswith("1/2 rows differ")
    with pytest.raises(AssertionError, match="differ from the uncached"):
        cs.held_to_reference(cfg, out["params"][0], call, (call[0], bad))


def _run_script(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chip_smoke.py", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_script_refuses_the_cpu():
    out = _run_script(ROOT)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "not a TPU" in out.stderr


def test_script_alone_fails_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    out = _run_script(tmp_path)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


@pytest.mark.parametrize("seed", [0, 1])
def test_cascade_prompts_escalate_a_quarter(seed):
    from repro.configs.registry import ARCHS

    cfg = ARCHS["gemma3-1b"]
    toks = cs.cascade_prompts(cfg, seed=seed)
    assert toks.shape == (cs.N_QUERIES, cs.PROMPT_LEN[1])
    assert 0 <= toks.min() and toks.max() < cfg.vocab
    lengths = (toks != cs.PAD).sum(1)
    assert lengths.min() >= cs.PROMPT_LEN[0]
    scores = cs._scorer(toks, None)
    assert (scores < 0.5).sum() == cs.N_QUERIES // 4
