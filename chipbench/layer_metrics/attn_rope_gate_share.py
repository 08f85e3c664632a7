"""Trace x the program's compiled step: busy time of ``attn/rope`` and
``attn/gate`` (rotary on q and k by the layer kind's tables, and the sigmoid
gate a head on attention's output: what this block has around its kernels
that is not a matrix product) over the busy time of the slice."""
from chipbench.layer_metrics import swa_core_share

NAME, UNIT = "attn_rope_gate_share", "%"
LAYER, MOVES = "Step on device", "tokens_per_s"


def read(run):
    return swa_core_share.share(run, ("attn/rope", "attn/gate"))
