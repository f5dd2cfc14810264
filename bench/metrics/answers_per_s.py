"""Answers completed per second: every answer of the closed backlog over
the window from its start to the last answer (an escalated request's
answer used both tiers)."""


def read(run):
    answered = sum(a is not None for a in run.served.answers)
    return answered / run.window_s if run.window_s > 0 else None
