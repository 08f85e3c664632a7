"""Program spans: the outermost ``jax-trace`` and ``jax-lower`` spans inside
the program's ``compile`` span of the cell's session (the last one before
the window opens): what jit spends tracing the step and lowering it to MLIR."""
from chipbench import program_probe

NAME, UNIT = "trace_lower_s", "s"
LAYER, MOVES = "Lowering", "setup_s"


def read(run):
    split = program_probe.setup_split(run)
    return None if split is None else split[NAME]
