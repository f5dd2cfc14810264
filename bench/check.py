"""What decides ``correct``: the served tokens against the plain reference,
and the cascade's outputs against what the traffic implies.

Two comparisons, each a number beside its limit:

- ``logit_gap``: over a seeded sample of the requests each tier served,
  the reference runs once over the prompt and the served tokens, and the
  widest gap by which a served token's reference logit lies below the
  reference's best at that position. Greedy decoding puts the top token
  first, so a sound program reads only the rounding of its bfloat16
  arithmetic here. The limit is the configuration's ``logit_gap_limit``.
- ``cascade_mismatches``: requests whose answer, charged cost or
  stopping tier differ from what the traffic implies, plus the
  difference in per-tier counts. The limit is 0.

The control (``precision="fp8"``) runs the reference itself in float8 in
the program's place and reads, at each position, the gap of the token
float8 puts first; ``verdict`` then judges it as it judges the program.
"""
from __future__ import annotations

import zlib

import numpy as np


def answer_id(generated_row: np.ndarray) -> int:
    """One int standing for all the tokens a tier generated for a row."""
    return zlib.crc32(np.asarray(generated_row, np.int32).tobytes())


def tier_cost(prices: dict, n_in: int, n_out: int) -> float:
    """Charged dollars for one request on one tier, in float32 as the
    program's price model computes them. Prices are powers of two times
    small integers, so every product is exact and the order of the
    additions cannot change the sum."""
    c1 = np.float32(prices["per_10m_input"] / 1e7)
    c2 = np.float32(prices["per_10m_output"] / 1e7)
    c0 = np.float32(prices.get("per_request", 0.0))
    return float(c2 * np.float32(n_out) + c1 * np.float32(n_in) + c0)


def cascade_mismatches(served, traffic, generated: list[dict],
                       prices: list[dict]) -> int:
    """Requests whose answer, cost or stopping tier is not what the
    traffic implies, plus the absolute difference in tier counts.

    ``generated[j]`` maps a prompt's bytes to the tokens tier j generated
    for it."""
    stop = np.asarray(traffic.stop)
    n, width = traffic.prompts.shape
    want_counts = [int((stop >= j).sum()) for j in range(len(prices))]
    bad = sum(abs(a - b) for a, b in zip(served.tier_counts, want_counts))
    bad += abs(len(served.tier_counts) - len(want_counts))
    costs = [tier_cost(p, width, traffic.new_tokens) for p in prices]
    for i in range(n):
        s = int(stop[i])
        gen = generated[s].get(traffic.prompts[i].tobytes())
        want_cost = 0.0
        for j in range(s + 1):
            want_cost += costs[j]
        ok = (gen is not None
              and int(served.stopped_at[i]) == s
              and served.answers[i] is not None
              and int(served.answers[i]) == answer_id(gen)
              and float(served.cost[i]) == want_cost)
        bad += not ok
    return int(bad)


def widest_gap(ref_logits: np.ndarray, tokens: np.ndarray) -> float:
    """Widest gap by which ``tokens``' reference logits lie below the
    reference's best, over positions; ``ref_logits`` (n, V)."""
    got = np.take_along_axis(ref_logits, np.asarray(tokens)[:, None], -1)[:, 0]
    return float(np.max(ref_logits.max(-1) - got))


def verdict(numbers: dict, limits: dict) -> bool:
    """Correct where every number compared is within its limit."""
    return all(numbers[k] <= limits[k] for k in limits)


def token_gaps(tiers, traffic, sample, generated: list[dict],
               controls: bool = False) -> dict:
    """Reference logits over each sampled request's prompt and served
    tokens, tier by tier; the widest gap of the served tokens and, with
    ``controls``, of the tokens the float8 control puts first at each
    of the same positions (the control's tokens standing in for the
    served ones).

    ``tiers[j]`` has the tier's ``reference`` module, its configuration
    ``config`` and ``weights()``, which makes its weights again from the
    seed; they are dropped before the next tier's are made."""
    width = traffic.prompts.shape[1]
    gap, ctrl, n_tok = 0.0, 0.0, 0
    for tier, rows in zip(tiers, sample):
        if not len(rows):
            continue
        params = tier.weights()
        for i in rows:
            prompt = traffic.prompts[i]
            served = generated[tier.index].get(prompt.tobytes())
            if served is None:      # the tier never served it: no reading
                gap = float("inf")
                continue
            seq = np.concatenate([prompt, served[:-1]])
            ref = tier.reference.logits(params, seq, tier.config, first=width - 1)
            gap = max(gap, widest_gap(ref, served))
            n_tok += len(served)
            if controls:
                low = tier.reference.logits(params, seq, tier.config,
                                            first=width - 1, precision="fp8")
                ctrl = max(ctrl, widest_gap(ref, low.argmax(-1)))
        del params
    out = {"logit_gap": gap, "tokens_checked": n_tok}
    if controls:
        out["control_logit_gap"] = ctrl
    return out
