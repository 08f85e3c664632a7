"""Program spans: the ``xla-compile`` spans inside the program's ``compile``
span of the cell's session (the last one before the window opens): the
backend's compile, or the compile cache's read."""
from chipbench import program_probe

NAME, UNIT = "xla_compile_s", "s"
LAYER, MOVES = "Lowering", "setup_s"


def read(run):
    split = program_probe.setup_split(run)
    return None if split is None else split[NAME]
