"""Trace: 1 - (union of the intervals in which an operation runs on the
chip / traced slice), mean over the chips."""
NAME, UNIT = "device_idle_share", "%"
LAYER, MOVES = "Step on device", "tokens_per_s"


def read(run):
    if run["trace"] is None:
        return None
    return 100.0 * run["trace"]["idle_share"]
