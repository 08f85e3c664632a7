"""Trace x the program's scope table: busy time of the instructions traced
under the Runner's ``optimizer`` scope (``opt.update`` and ``apply_updates``)
over the busy time of the slice."""
from chipbench import program_probe

NAME, UNIT = "optimizer_share", "%"
LAYER, MOVES = "Step on device", "tokens_per_s"


def read(run):
    return program_probe.share(run, "scope", "optimizer")
