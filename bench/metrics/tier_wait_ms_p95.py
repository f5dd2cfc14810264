"""95th percentile (nearest rank) of each answered request's summed wait
in tier queues in ms: entering a tier's queue to its chunk being popped,
over every tier it visited (the program's
``ServeResult.ingress["tier_wait"]``). None where the program publishes
no such key."""
import numpy as np

from bench.harness import percentile


def read(run):
    wait = run.served.ingress.get("tier_wait")
    if wait is None or not len(wait):
        return None
    return 1e3 * percentile(np.asarray(wait, np.float64), 95)
