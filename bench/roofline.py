"""Roofline shares and model FLOP utilization from the tier-call log and
the trace.

The traced window holds the whole served stream, so every tier call the
benchmark logged ran inside it and every execution of the engine's
programs in it belongs to a logged call. A share is then the least time
of all that work (``counts.least_seconds``, step by step) over the device
time the trace gives the program.
"""
from __future__ import annotations


def _work(run, part: str):
    """(flops, least seconds) of one part ("prefill" or "decode") of every
    logged tier call."""
    peak = run.peak
    flops = least = 0.0
    for c in run.calls:
        tier = run.tiers[c["tier"]]
        counts = tier.counts
        pf, pb, steps = counts.call_work(tier.config, c["rows"], c["prompt"], c["n_new"])
        if part == "prefill":
            flops += pf
            least += counts.least_seconds(pf, pb, peak)[0]
        else:
            for f, b in steps:
                flops += f
                least += counts.least_seconds(f, b, peak)[0]
    return flops, least


def share(run, program: str, part: str):
    """Percent of its roofline ``program`` reaches over the window; None
    where the trace holds no execution of it."""
    if run.trace is None or run.peak is None:
        return None
    seconds = run.trace.program_seconds(program)
    if seconds <= 0:
        return None
    _, least = _work(run, part)
    return 100.0 * least / seconds


def mfu(run):
    """Percent of the chip's peak FLOP/s that the served model work
    reaches over the traced window."""
    if run.trace is None or run.peak is None or run.trace.window_s <= 0:
        return None
    flops = _work(run, "prefill")[0] + _work(run, "decode")[0]
    n_chips = max(1, len(run.trace.devices))
    return 100.0 * flops / (run.trace.window_s * run.peak["flops_per_s"] * n_chips)

