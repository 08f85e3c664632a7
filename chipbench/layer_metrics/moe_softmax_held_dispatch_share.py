"""Trace x the program's scope table: busy time of ``moe/router`` and
``moe/dispatch`` in a program whose expert layers hold a share of their
softmax-routed experts: the router over all the experts, the sort of every
assignment (the held ones first), the gathers of the ``T x k`` rows and the
masks, which serve the one assignment in thirty-two that a held expert
computes; over the busy time of the slice."""
from chipbench.layer_metrics import moe_softmax_held_scope_share

NAME, UNIT = "moe_softmax_held_dispatch_share", "%"
LAYER, MOVES = "Step on device", "tokens_per_s"


def read(run):
    found = moe_softmax_held_scope_share.seconds(
        run, ("moe/router", "moe/dispatch"))
    return None if found is None else 100.0 * found[0] / found[1]
