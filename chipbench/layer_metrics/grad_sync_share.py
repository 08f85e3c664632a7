"""Trace x the program's scope table: busy time of the instructions placed in
``grad_sync`` over the busy time of the slice (``optimizer_share``'s
denominator).  The program places a communication instruction that carries
no named scope by what it is, so the compiler's ``all-reduce`` +
``dynamic-slice`` fusions of the gradients' reduce-scatter, which the trace
names ``fusion.N``, are in it."""
from chipbench import comm_probe

NAME, UNIT = "grad_sync_share", "%"
LAYER, MOVES = "Collectives", "tokens_per_s"


def read(run):
    return comm_probe.scope_share(run, "grad_sync")
