"""Open loop: requests due on the schedule of a Poisson process whose
mean rate is the mix's ``rate_per_s``.

With ``"bursts": {"period_s": P, "on_share": f, "factor": k}`` in the mix
the rate is k times higher in the first f x P seconds of every period
than in the rest (the mean stays ``rate_per_s``): the schedule of the
plain process, stretched in time by the inverse of the cumulative rate.

The gaps are the quantiles of the exponential distribution, in the
order that ``rng`` (the traffic's schedule stream) draws.
"""
import numpy as np


def count(mix: dict, seconds: float) -> int:
    return max(1, int(round(mix["rate_per_s"] * seconds)))


def times(mix: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    q = (np.arange(n) + 0.5) / n
    rate = float(mix["rate_per_s"])
    gaps = -np.log1p(-q) / rate
    t = np.cumsum(rng.permutation(gaps))
    bursts = mix.get("bursts")
    if not bursts:
        return t
    period, on, factor = (float(bursts["period_s"]), float(bursts["on_share"]),
                          float(bursts["factor"]))
    low = 1.0 / (on * factor + 1.0 - on)      # the rate off a burst, over the mean
    # breakpoints of the cumulative rate, in mean-rate seconds, over
    # enough periods to hold every arrival
    k = np.arange(int(np.ceil(t[-1] / period)) + 2)
    starts = k * period
    real = np.sort(np.concatenate([starts, starts + on * period]))
    per = np.where(np.isin(real, starts), on * factor * low, (1.0 - on) * low)
    warped = np.concatenate([[0.0], np.cumsum(per[:-1] * period)])
    return np.interp(t, warped, real)
