"""Trace x the program's communication table: union of the communication
instructions' intervals (an asynchronous pair in flight from its start to
its done) over the traced slice, mean over the chips.  What
``collective_share`` means, with the compiler's fused forms in."""
from chipbench import comm_probe

NAME, UNIT = "comm_share", "%"
LAYER, MOVES = "Collectives", "tokens_per_s"


def read(run):
    return comm_probe.share(run, "comm_s")
