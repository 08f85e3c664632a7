"""Trace x the program's scope table: busy time of the scope ``attn`` of every
layer (the kernels, the projections, the LayerNorm before them and the copies
and casts around them) over the busy time of the slice.  Less
``attn_kernel_share``, it is what surrounds the kernels."""
from chipbench import program_probe

NAME, UNIT = "attn_scope_share", "%"
LAYER, MOVES = "Step on device", "tokens_per_s"


def read(run):
    return program_probe.share(run, "scope", "attn")
