"""Growth of ``DevicePrefetcher.stats()["data_wait_ms_total"]`` over the
window, as a share of the window: the time the loop stood waiting for a
batch's transfer."""
NAME, UNIT = "data_wait_share", "%"
LAYER, MOVES = "Input", "tokens_per_s"


def read(run):
    return 100.0 * run["counters"]["data_wait_s"] / run["window_s"]
