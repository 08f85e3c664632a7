"""Trace: the part of the collectives' union during which no other
operation runs on the same chip, over the traced slice."""
NAME, UNIT = "collective_exposed_share", "%"
LAYER, MOVES = "Collectives", "tokens_per_s"


def read(run):
    trace = run["trace"]
    if trace is None or run["chips"] < 2:
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["window_s"]
