"""Model FLOP/s utilization of the whole served window, in %: the model
operations of every prefill and decode token both tiers served in the
traced window over the window's length times the chip's peak."""
from bench import roofline


def read(run):
    return roofline.mfu(run)
