"""Trace: the least time the chip could take for the full-attention layers'
three kernels over grouped key-value heads in the traced slice
(``flops_swa.py`` with no window: every score behind the diagonal; k, v, dk
and dv counted once a key-value head), over the time spent in the scope
``attn/core``, in a program that also runs sliding layers."""
from chipbench.layer_metrics import swa_core_share, swa_kernel_roofline

NAME, UNIT = "gqa_kernel_roofline", "%"
LAYER, MOVES = "Kernels", "tokens_per_s"


def read(run):
    return swa_kernel_roofline.roofline(run, "full", swa_core_share.CORE)
