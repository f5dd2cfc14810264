"""The one traffic generator: turns a mix's parameter file into requests.

A mix file (``bench/traffic/<name>.json``) holds:

- ``arrivals``: the name of the arrival process, a module
  ``bench/arrivals/<name>.py`` with ``count(mix, seconds)`` (how many
  requests a window of that length gets) and ``times(mix, n, rng)`` (their
  due times, in seconds after the start of the window), reading its own
  parameters from the mix (``poisson``: ``rate_per_s``; ``backlog``:
  ``backlog_per_s``, all due at the start);
- ``prompt_tokens``, ``new_tokens``: one width for every prompt, made
  of real tokens only, and the greedy tokens each request decodes;
- ``escalate_share``: the share of the requests reaching a tier that the
  scorer sends on to the next one; one number for every tier boundary,
  or a list with one per boundary;
- ``max_chunk``, ``holdback_s``: the scheduler's settings for the cell;
- ``check_per_tier``: requests per tier whose tokens are compared with
  the reference after the window.

Every seed gets the same work at the same times: the arrival times and
which of the requests due at them stop at each tier are drawn from one
fixed stream (``SCHEDULE``), not from the seed. Only the tokens of the
prompts (and the weights) depend on the seed. A schedule drawn anew for
each seed would move the answer tails from seed to seed by more than two
runs of one seed differ, since where a burst falls decides which chunks
overflow.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import math
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent

#: the first token's residue mod 4 carries the scorer's decision, so a
#: cascade has at most this many tiers
MAX_TIERS = 4

#: the seed of the stream that draws every mix's schedule: arrival times
#: and the tier each request stops at
SCHEDULE = 20261017


@dataclasses.dataclass
class Traffic:
    prompts: np.ndarray      # (n, prompt_tokens) int32, ids in [1, vocab)
    arrivals: np.ndarray     # (n,) seconds after the start of the window
    stop: np.ndarray         # (n,) int: the tier whose answer stands
    new_tokens: int

    @property
    def escalate(self) -> np.ndarray:
        """(n,) bool: the scorer sends this request past the first tier."""
        return self.stop > 0


_MODULES: dict = {}


def load(path: Path):
    """A module from its file, loaded once per process."""
    path = Path(path)
    if path not in _MODULES:
        spec = importlib.util.spec_from_file_location(
            "bench_" + "_".join(path.with_suffix("").parts[-2:]).replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def arrival_process(mix: dict, root: Path | None = None):
    """The mix's arrival process, ``bench/arrivals/<arrivals>.py``."""
    base = BENCH if root is None else Path(root) / "bench"
    return load(base / "arrivals" / f"{mix['arrivals']}.py")


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one purpose of one run; any whole seed."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *stream]))


def escalate_shares(mix: dict, n_tiers: int) -> list[float]:
    share = mix["escalate_share"]
    shares = list(share) if isinstance(share, list) else [share] * (n_tiers - 1)
    if len(shares) != n_tiers - 1:
        raise ValueError(f"escalate_share has {len(shares)} entries for "
                         f"{n_tiers} tiers")
    return shares


def generate(mix: dict, vocab: int, seed: int, seconds: float,
             n_tiers: int = 2, root: Path | None = None) -> Traffic:
    if not 2 <= n_tiers <= MAX_TIERS:
        raise ValueError(f"a cascade here has 2 to {MAX_TIERS} tiers, not {n_tiers}")
    process = arrival_process(mix, root)
    n = process.count(mix, seconds)
    rng = rng_for(seed, 0)
    schedule = rng_for(SCHEDULE, 0)
    width = mix["prompt_tokens"]
    prompts = rng.integers(1, vocab, size=(n, width), dtype=np.int64)
    # a fixed number of the requests that reach each tier go on past it
    stop = np.zeros(n, np.int64)
    for j, share in enumerate(escalate_shares(mix, n_tiers)):
        pool = np.flatnonzero(stop == j)
        n_on = int(round(share * len(pool)))
        stop[pool[schedule.permutation(len(pool)) < n_on]] = j + 1
    # the first token's residue mod 4 says where the request stops: 0 at
    # the last tier, 1.. at the tiers before it counted from the end, and
    # at the first tier any residue that no other tier uses
    last = n_tiers - 1
    first = 4 * rng.integers(1, (vocab - 1) // 4, size=n)
    lowest = last + rng.integers(0, 4 - last, size=n)
    prompts[:, 0] = first + np.where(stop == last, 0,
                                     np.where(stop == 0, lowest, last - stop))
    arrivals = np.asarray(process.times(mix, n, schedule), np.float64)
    return Traffic(prompts.astype(np.int32), arrivals, stop.astype(np.int32),
                   int(mix["new_tokens"]))


def stop_tier(tokens: np.ndarray, n_tiers: int) -> np.ndarray:
    """The tier each row stops at, read from its first token."""
    r = np.asarray(tokens)[:, 0] % 4
    last = n_tiers - 1
    return np.where(r == 0, last, np.maximum(last - r, 0))


def scores(tokens: np.ndarray, n_tiers: int = 2) -> np.ndarray:
    """The benchmark's deterministic scorer: a row that stops at tier s
    scores between the thresholds of tiers s - 1 and s."""
    return 1.0 - (stop_tier(tokens, n_tiers) + 0.5) / n_tiers


def thresholds(n_tiers: int = 2) -> list[float]:
    """Tier j accepts a row scoring at least ``thresholds[j]``."""
    return [1.0 - (j + 1) / n_tiers for j in range(n_tiers - 1)]


def check_sample(traffic: Traffic, calls: list, per_tier: int, seed: int,
                 n_tiers: int = 2) -> list[np.ndarray]:
    """Request indices whose tokens each tier is checked on: a seeded draw
    of the requests that tier served, spread over its chunks (``calls``,
    each with the request index of every row in ``"requests"``) and over
    the rows within them: the m-th pick comes from the next chunk in a
    seeded order, at a row m/per_tier of the way down. Every request
    decodes the same number of tokens, so any of them is among the
    longest."""
    rng = rng_for(seed, 1)
    out = []
    for j in range(n_tiers):
        chunks = [c["requests"] for c in calls if c["tier"] == j]
        chunks = [chunks[i] for i in rng.permutation(len(chunks))]
        n_served = len({i for c in chunks for i in c})
        chosen: list[int] = []
        m = 0
        while len(chosen) < min(per_tier, n_served):
            rows = [i for i in chunks[m % len(chunks)] if i not in chosen]
            if rows:
                pos = int((len(chosen) + 0.5) / per_tier * len(rows))
                chosen.append(rows[min(pos, len(rows) - 1)])
            m += 1
        out.append(np.sort(np.asarray(chosen, np.int64)))
    return out


def chunk_buckets(max_chunk: int) -> list[int]:
    """Every power-of-two row count up to the one that holds ``max_chunk``
    rows: the shapes a chunk can take."""
    top = 1 << math.ceil(math.log2(max(max_chunk, 1)))
    out, b = [], 1
    while b <= top:
        out.append(b)
        b *= 2
    return out
