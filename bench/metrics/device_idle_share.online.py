"""Share of the traced window in which no program ran on the device, %."""


def read(run):
    t = run.trace
    return None if t is None or t.window_s <= 0 else 100.0 * t.idle_share
