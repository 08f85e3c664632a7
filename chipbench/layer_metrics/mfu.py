"""Model FLOP/s utilization: the operations forward and backward need per
token (``flops.py``; no recomputation) x tokens per second of the window,
over chips x the chip's peak (``peaks.json``)."""
NAME, UNIT = "mfu", "%"
LAYER, MOVES = "Step on device", "tokens_per_s"


def read(run):
    return (100.0 * run["flops_per_token"] * run["tokens_per_s"]
            / (run["chips"] * run["peak"]["flops_per_s"]))
