"""Host microseconds per decode step from a token on the host to the next
step enqueued (the random split, the position scalar and the step's
dispatch), over every tier: the program's
``ServeResult.ingress["tier_counters"]``, summed ``decode_dispatch_s``
over summed ``decode_steps``. None where the program publishes none."""


def read(run):
    tiers = run.served.ingress.get("tier_counters")
    steps = sum(t["decode_steps"] for t in tiers or ())
    if not steps:
        return None
    return 1e6 * sum(t["decode_dispatch_s"] for t in tiers) / steps
