"""Trace: the least time the chip could take for the attention kernels'
calls in the traced slice (``flops.py``: the larger of operations / peak
FLOP/s and bytes / peak bytes/s, per call) over the time they took."""
from chipbench import flops

NAME, UNIT = "attn_kernel_roofline", "%"
LAYER, MOVES = "Kernels", "tokens_per_s"


def read(run):
    trace = run["trace"]
    if trace is None or not trace["kernel_seconds"]:
        return None
    least = 0.0
    for kernel, calls in trace["kernel_calls"].items():
        ops, nbytes = flops.attention_kernel_cost(kernel, **run["attention"])
        seconds, bound = flops.roofline_seconds(ops, nbytes, run["peak"])
        print(f"chipbench: {kernel}: {calls:g} calls a chip, at least "
              f"{seconds * 1e6:.1f} us each, bound by {bound}", flush=True)
        least += calls * seconds
    return 100.0 * least / sum(trace["kernel_seconds"].values())
