"""Trace x the program's scope table: busy time of the scope ``mlp`` of every
layer over the busy time of the slice."""
from chipbench import program_probe

NAME, UNIT = "mlp_scope_share", "%"
LAYER, MOVES = "Step on device", "tokens_per_s"


def read(run):
    return program_probe.share(run, "scope", "mlp")
