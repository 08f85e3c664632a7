"""The bytes a chip sends a step in the instructions placed in ``grad_sync``
(the program's communication table) over the seconds a step spends in
them (the union of their intervals in the trace): the rate the gradients'
reduce-scatter achieves.  No peak is divided by."""
from chipbench import comm_probe

NAME, UNIT = "grad_sync_gbytes_per_s", "GB/s"
LAYER, MOVES = "Collectives", "tokens_per_s"
SCOPE = "grad_sync"


def read(run):
    found = comm_probe.measured(run)
    seconds = found["by_scope"].get(SCOPE) if found else None
    if not seconds or not found["steps"]:
        return None
    return found["wire_bytes_by_scope"][SCOPE] / 1e9 \
        / (seconds / found["steps"])
