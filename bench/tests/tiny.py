"""A checkout in miniature for the tests: tiny configurations of the
benchmark's families, their mixes and a BENCHMARK.json that names them,
with the real families and metric readers beside them."""
from __future__ import annotations

import json
import os
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

TINY = {
    "family": "dense_gqa", "hidden_size": 64, "head_dim": 16,
    "intermediate_size": 96, "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 2, "vocab_size": 256, "rope_theta": 10000.0,
    "max_position_embeddings": 4096, "tie_word_embeddings": False,
    "dtype": "bfloat16", "source": "test",
}
CONFIGS = {
    "tiny-nemo": dict(TINY, name="tiny-nemo", hidden_act="silu", norm="rmsnorm",
                      rms_norm_eps=1e-6, sliding_window=None, logit_gap_limit=0.05),
    "tiny-sc2": dict(TINY, name="tiny-sc2", hidden_act="gelu_pytorch_tanh",
                     norm="layernorm", norm_epsilon=1e-6, sliding_window=16,
                     logit_gap_limit=0.05),
    # wide enough for float8's rounding to move the served tokens
    "small-nemo": dict(TINY, name="small-nemo", hidden_size=512, head_dim=64,
                       intermediate_size=1024, vocab_size=4096, hidden_act="silu",
                       norm="rmsnorm", rms_norm_eps=1e-6, sliding_window=None,
                       # CPU readings over 4 seeds: program at most 0.0040,
                       # float8 control at least 0.116
                       logit_gap_limit=0.03),
}
MIXES = {
    "tiny-open": {"arrivals": "poisson", "rate_per_s": 40.0, "prompt_tokens": 12,
                  "new_tokens": 6, "escalate_share": 0.25, "max_chunk": 4,
                  "holdback_s": 0.01, "check_per_tier": 2},
    # prompts longer than the window of tiny-sc2
    "tiny-long": {"arrivals": "backlog", "backlog_per_s": 10.0, "prompt_tokens": 24,
                  "new_tokens": 5, "escalate_share": 0.25, "max_chunk": 2,
                  "holdback_s": 0.01, "check_per_tier": 2},
    "small-check": {"arrivals": "backlog", "backlog_per_s": 16.0, "prompt_tokens": 16,
                    "new_tokens": 16, "escalate_share": 0.25, "max_chunk": 8,
                    "holdback_s": 0.01, "check_per_tier": 4},
}
CELLS = [("tiny.open", "tiny-nemo", "tiny-open"), ("tiny.long", "tiny-sc2", "tiny-long"),
         ("small.check", "small-nemo", "small-check")]


def make_root(path: Path, metrics=("answer_ms_p95", "answers_per_s", "setup_s",
                                   "chunk_rows_mean")) -> Path:
    """Write the miniature checkout under ``path`` and return it."""
    (path / "bench" / "configs").mkdir(parents=True)
    (path / "bench" / "traffic").mkdir()
    (path / "bench" / "metrics").mkdir()
    os.symlink(BENCH / "families", path / "bench" / "families")
    (path / "bench" / "arrivals").mkdir()
    for f in (BENCH / "arrivals").glob("*.py"):
        os.symlink(f, path / "bench" / "arrivals" / f.name)
    for m in metrics:
        os.symlink(BENCH / "metrics" / f"{m}.py", path / "bench" / "metrics" / f"{m}.py")
    for name, cfg in CONFIGS.items():
        (path / "bench" / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    for name, mix in MIXES.items():
        (path / "bench" / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    spec = {
        "configs": [{"name": n, "file": f"bench/configs/{n}.json"} for n in CONFIGS],
        "workloads": [{"name": c, "config": cfg, "traffic": t, "chips": 1}
                      for c, cfg, t in CELLS],
        "end_to_end": [
            {"name": "answer_ms_p95", "unit": "ms", "workloads": ["tiny.open"]},
            {"name": "answers_per_s", "unit": "answers/s", "workloads": ["tiny.long"]},
            {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": "chunk_rows_mean", "unit": "rows",
                       "moves": "answers_per_s", "workloads": ["tiny.long"]}],
    }
    (path / "BENCHMARK.json").write_text(json.dumps(spec))
    return path
