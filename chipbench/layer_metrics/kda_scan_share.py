"""Trace x the program's scope table: busy time of ``kda/scan`` (the chunked
delta rule with a decay a channel, forward and backward: the sub-blocks'
products, the chunks' inverses and the walk over the chunks) over the busy
time of the slice: the part of the mixer that is not a plain matrix
product."""
from chipbench.layer_metrics import kda_scope_share

NAME, UNIT = "kda_scan_share", "%"
LAYER, MOVES = "Step on device", "tokens_per_s"


def read(run):
    found = kda_scope_share.seconds(run, "kda/scan")
    return None if found is None else 100.0 * found[0] / found[1]
