"""Trace x the program's scope table: busy time of the instructions whose
``op_name`` passes through ``transpose(jvp(`` (the backward pass; the Runner's
own scopes are phase ``update``) over the busy time of the slice."""
from chipbench import program_probe

NAME, UNIT = "backward_share", "%"
LAYER, MOVES = "Step on device", "tokens_per_s"


def read(run):
    return program_probe.share(run, "phase", "backward")
