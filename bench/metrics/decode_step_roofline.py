"""Share of its roofline the decode program reaches, in %: the least time
the chip could take for every decode step the tiers ran in the traced
window (true rows, true positions; the larger of operations over peak
FLOP/s and bytes over peak bandwidth, step by step) over the device time
of the decode program's executions in the trace."""
from bench import roofline

PROGRAM = "jit__decode_body"


def read(run):
    return roofline.share(run, PROGRAM, "decode")
