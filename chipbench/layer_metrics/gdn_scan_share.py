"""Trace x the program's scope table: busy time of ``gdn/scan`` (the chunked
gated delta rule, forward and backward: the triangular solves, the chunks'
products and the scan over the chunks) over the busy time of the slice: the
part of the mixer that is not a plain matrix product."""
from chipbench.layer_metrics import gdn_scope_share

NAME, UNIT = "gdn_scan_share", "%"
LAYER, MOVES = "Step on device", "tokens_per_s"


def read(run):
    found = gdn_scope_share.seconds(run, "gdn/scan")
    return None if found is None else 100.0 * found[0] / found[1]
