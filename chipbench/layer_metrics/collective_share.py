"""Trace: union of the all-gather / reduce-scatter / all-reduce intervals
(an asynchronous one from its start's beginning to its done's end) over the
traced slice, mean over the chips."""
NAME, UNIT = "collective_share", "%"
LAYER, MOVES = "Collectives", "tokens_per_s"


def read(run):
    trace = run["trace"]
    if trace is None or run["chips"] < 2:
        return None
    return 100.0 * trace["collective_s"] / trace["window_s"]
