"""Host clock around ``ad.capture`` + ``create_distributed_session`` of the
cell's own session."""
NAME, UNIT = "capture_s", "s"
LAYER, MOVES = "Capture and strategy", "setup_s"


def read(run):
    return run["spans"].seconds("capture")
