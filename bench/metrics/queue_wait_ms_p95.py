"""95th percentile of due time -> admission in ms (the program's
``ServeResult.ingress["queue_wait"]``): how late the ingress loop took
each request in."""
import numpy as np

from bench.harness import percentile


def read(run):
    wait = np.asarray(run.served.ingress["queue_wait"], np.float64)
    return 1e3 * percentile(wait, 95) if len(wait) else None
