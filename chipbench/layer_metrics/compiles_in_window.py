"""Program spans: ``xla-compile`` spans (a backend compile or a cache read,
of any jit in the process) that start after the window opened (the end of
the benchmark's ``warmup`` span) and before it closed.  Expect 0."""
from chipbench import program_probe

NAME, UNIT = "compiles_in_window", "count"
LAYER, MOVES = "Lowering", "step_ms_p90"


def read(run):
    return program_probe.compiles_in_window(run)
