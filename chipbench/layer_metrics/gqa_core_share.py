"""Trace x the program's compiled step: busy time of the full-attention
layers' cores in a program that also runs sliding layers (the named scope
``attn/core`` of ``layers.mha``: the three flash kernels over grouped
key-value heads and the whole causal triangle) over the busy time of the
slice."""
from chipbench.layer_metrics import swa_core_share

NAME, UNIT = "gqa_core_share", "%"
LAYER, MOVES = "Step on device", "tokens_per_s"


def read(run):
    return swa_core_share.share(run, (swa_core_share.CORE,))
