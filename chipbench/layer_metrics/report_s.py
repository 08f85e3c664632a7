"""Program spans: the program's ``report`` spans of the cell's session: the
transform report, rendered whenever a step is built."""
from chipbench import program_probe

NAME, UNIT = "report_s", "s"
LAYER, MOVES = "Lowering", "setup_s"


def read(run):
    split = program_probe.setup_split(run)
    return None if split is None else split[NAME]
