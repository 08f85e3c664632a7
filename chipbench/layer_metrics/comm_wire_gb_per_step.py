"""The program's communication table of the compiled step: the bytes a chip
sends a step (``profile.comm_wire_bytes``: every collective's payload by
its shapes, ``(group - 1) / group`` of it in a ring, an all-reduce twice),
summed over the kinds."""
from chipbench import comm_probe

NAME, UNIT = "comm_wire_gb_per_step", "GB"
LAYER, MOVES = "Collectives", "tokens_per_s"


def read(run):
    found = comm_probe.measured(run)
    return sum(found["wire_bytes"].values()) / 1e9 if found else None
