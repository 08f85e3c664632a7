"""Trace x the program's scope table: busy time of the instructions that carry
no named scope (what the table cannot place) over the busy time of the slice."""
from chipbench import program_probe

NAME, UNIT = "scope_unattributed_share", "%"
LAYER, MOVES = "Step on device", "tokens_per_s"


def read(run):
    return program_probe.share(run, "scope", "(unattributed)")
