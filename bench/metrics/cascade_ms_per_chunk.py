"""Host milliseconds per tier chunk that the cascade step spends outside
the tier call (scoring, the accept rule, the answer and cost casts), over
every tier: the program's ``ServeResult.ingress["tier_counters"]``,
summed ``cascade_s`` over summed ``chunks``. None where the program
publishes none."""


def read(run):
    tiers = run.served.ingress.get("tier_counters")
    chunks = sum(t["chunks"] for t in tiers or ())
    if not chunks:
        return None
    return 1e3 * sum(t["cascade_s"] for t in tiers) / chunks
