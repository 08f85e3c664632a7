"""Trace x the program's scope table: busy time of the expert layers' three
scopes (``moe/router``, ``moe/dispatch``, ``moe/experts``, each folded over
the layers) over the busy time of the slice.  Nothing to read where the
program's table has no such scope."""
from chipbench import program_probe

NAME, UNIT = "moe_scope_share", "%"
LAYER, MOVES = "Step on device", "tokens_per_s"
SCOPES = ("moe/router", "moe/dispatch", "moe/experts")


def seconds(run, scopes=SCOPES):
    """``(seconds a chip in ``scopes``, busy seconds a chip)`` of the traced
    slice; None without a slice, a table, or any ``moe/*`` scope in it."""
    joined = program_probe.by_scope() if run["trace"] is not None else None
    if not joined or not joined["busy_s"] \
            or not any(s in joined["scope"] for s in SCOPES):
        return None
    return (sum(joined["scope"].get(s, 0.0) for s in scopes),
            joined["busy_s"])


def read(run):
    found = seconds(run)
    return None if found is None else 100.0 * found[0] / found[1]
