"""``memory_stats()["bytes_in_use"]`` after the window, on the fullest
chip: the state that stays between steps, not the step's peak."""
NAME, UNIT = "hbm_in_use_gb", "GB"
LAYER, MOVES = "Memory", "tokens_per_s"


def read(run):
    in_use = run["counters"]["bytes_in_use"]
    return in_use / 1e9 if in_use else None
