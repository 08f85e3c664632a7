"""Program spans: the program's ``create-state`` span of the cell's session:
the jitted init's call and the copy of the captured values to the host (the
helper prints the ``init`` and ``host-copy`` children beside it)."""
from chipbench import program_probe

NAME, UNIT = "create_state_s", "s"
LAYER, MOVES = "Lowering", "setup_s"


def read(run):
    split = program_probe.setup_split(run)
    return None if split is None else split[NAME]
