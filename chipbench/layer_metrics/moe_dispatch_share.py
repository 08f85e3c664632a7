"""Trace x the program's scope table: busy time of ``moe/router`` (logits,
softmax, top-k, the auxiliary terms) and ``moe/dispatch`` (the sort, the
rows' permutation in, the weighted sum back) over the busy time of the
slice: the part of the expert layer that is not matrix arithmetic."""
from chipbench.layer_metrics import moe_scope_share

NAME, UNIT = "moe_dispatch_share", "%"
LAYER, MOVES = "Step on device", "tokens_per_s"


def read(run):
    found = moe_scope_share.seconds(run, ("moe/router", "moe/dispatch"))
    return None if found is None else 100.0 * found[0] / found[1]
