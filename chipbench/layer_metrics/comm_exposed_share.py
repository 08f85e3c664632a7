"""Trace x the program's communication table: the part of the communication
instructions' union during which no other instruction runs on the chip (a
synchronous collective or fused collective is exposed whole), over the
traced slice.  What ``collective_exposed_share`` means."""
from chipbench import comm_probe

NAME, UNIT = "comm_exposed_share", "%"
LAYER, MOVES = "Collectives", "tokens_per_s"


def read(run):
    return comm_probe.share(run, "exposed_s")
