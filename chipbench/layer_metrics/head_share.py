"""Trace x the program's scope table: busy time of the scope ``head``
(``logits``, ``lm_head`` / ``mlm_head`` and the loss inside them) over the
busy time of the slice."""
from chipbench import program_probe

NAME, UNIT = "head_share", "%"
LAYER, MOVES = "Step on device", "tokens_per_s"


def read(run):
    return program_probe.share(run, "scope", "head")
