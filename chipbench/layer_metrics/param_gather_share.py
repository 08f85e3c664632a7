"""Trace x the program's scope table: busy time of the instructions placed in
``param_gather`` (the parameters' all-gathers, their casts, and the two
fusions that begin and end an asynchronous gather) over the busy time of
the slice."""
from chipbench import comm_probe

NAME, UNIT = "param_gather_share", "%"
LAYER, MOVES = "Collectives", "tokens_per_s"


def read(run):
    return comm_probe.scope_share(run, "param_gather")
