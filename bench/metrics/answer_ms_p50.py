"""Median of due time -> answer over every request due in the window, ms."""
from bench.harness import percentile


def read(run):
    return 1e3 * percentile(run.latencies, 50)
