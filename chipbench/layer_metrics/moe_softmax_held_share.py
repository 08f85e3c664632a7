"""The program's own counter, from inside the last step: the assignments that
chose an expert this chip holds over all the step's assignments, in a program
whose router scores by softmax (``moe_held_share``'s reading, which that
metric reports in the sigmoid-routed cell only).  8 of 256 experts held take
3.125% at an even load."""
from chipbench.layer_metrics import moe_held_share, \
    moe_softmax_held_scope_share

NAME, UNIT = "moe_softmax_held_share", "%"
LAYER, MOVES = "Step on device", "tokens_per_s"


def read(run):
    return moe_held_share.read(run) \
        if moe_softmax_held_scope_share.softmax_held() else None
