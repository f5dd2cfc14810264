"""A run whose timed path is broken underneath comes out not correct: a
token altered where the engine produces it, an answer, an escalation or a
charged cost altered where the cascade produces them; and the float8
control, put in the program's place, reads above the limit."""
import time

import numpy as np
import pytest

from bench import harness
from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("checkout"))


def _alter_token(monkeypatch):
    from repro.serving.engine import GenerationEngine

    orig = GenerationEngine.generate

    def generate(self, tokens, n_new=None, seed=0):
        out = np.array(orig(self, tokens, n_new, seed))
        out[:, -1] = (out[:, -1] + 1) % self.cfg.vocab
        return out

    monkeypatch.setattr(GenerationEngine, "generate", generate)


def _alter_step(part):
    def patch(monkeypatch):
        from repro.serving.sched import scheduler

        orig = scheduler.tier_step

        def tier_step(*a, **kw):
            ans, cost, scores, accept = orig(*a, **kw)
            ans, cost, accept = ans.copy(), cost.copy(), accept.copy()
            if part == "answer":
                ans[0] = ans[0] + 1
            elif part == "accept":
                accept[0] = not accept[0] if not kw.get("last") else accept[0]
                if kw.get("last"):
                    ans[0] = ans[0] + 1
            else:
                cost[0] = cost[0] * 2
            return ans, cost, scores, accept

        monkeypatch.setattr(scheduler, "tier_step", tier_step)
    return patch


@pytest.mark.parametrize("fault", ["token", "answer", "accept", "cost"])
def test_broken_path_is_not_correct(root, monkeypatch, fault):
    (_alter_token if fault == "token" else _alter_step(fault))(monkeypatch)
    cell = harness.load_cell("tiny.open", root)
    out = harness.run_cell(cell, 11, 1.0, False, time.perf_counter())
    assert not out["correct"], (fault, out["checks"])


def test_float8_control_is_not_correct(root):
    """The float8 control in the program's place, judged by the harness's
    own comparison and limits, comes out not correct where the program's
    run on the same sample is correct."""
    cell = harness.load_cell("small.check", root)
    out = harness.run_cell(cell, 2 ** 31 + 1, 1.0, False, time.perf_counter(),
                           controls=True)
    assert out["correct"], out["checks"]
    assert out["control"]["correct"] is False, out["control"]["checks"]
    assert (out["control"]["checks"]["logit_gap"]["limit"]
            == out["checks"]["logit_gap"]["limit"] == cell.config["logit_gap_limit"])
