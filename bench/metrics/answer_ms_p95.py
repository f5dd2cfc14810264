"""95th percentile of due time -> answer over every request due in the
window, in ms; an unanswered request counts as missing (infinite)."""
from bench.harness import percentile


def read(run):
    return 1e3 * percentile(run.latencies, 95)
