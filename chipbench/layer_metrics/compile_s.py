"""Host clock around the first ``runner.step`` at the cell's shape, to
``block_until_ready``: the compile (or the cache read) plus one step."""
NAME, UNIT = "compile_s", "s"
LAYER, MOVES = "Lowering", "setup_s"


def read(run):
    return run["spans"].seconds("compile")
