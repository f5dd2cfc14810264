"""Closed backlog: ``backlog_per_s`` x seconds requests, all due at the
start of the window, which lasts until the last of them is answered."""
import numpy as np


def count(mix: dict, seconds: float) -> int:
    return max(1, int(round(mix["backlog_per_s"] * seconds)))


def times(mix: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    return np.zeros(n)
