"""Share of its roofline the prefill program reaches, in %: the least time
for every prefill the tiers ran in the traced window (true rows, true
prompt lengths, unmasked attention only) over the device time of the
prefill program's executions in the trace."""
from bench import roofline

PROGRAM = "jit_fn"


def read(run):
    return roofline.share(run, PROGRAM, "prefill")
