"""Trace: time in the events of the three attention kernels over the time
the chip is busy."""
from chipbench import flops

NAME, UNIT = "attn_kernel_share", "%"
LAYER, MOVES = "Kernels", "tokens_per_s"


def read(run):
    trace = run["trace"]
    if trace is None or not trace["kernel_seconds"]:
        return None
    return (100.0 * sum(trace["kernel_seconds"].get(k, 0.0)
                        for k in flops.KERNELS) / trace["busy_s"])
