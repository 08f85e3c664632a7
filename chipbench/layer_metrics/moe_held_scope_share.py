"""Trace x the program's scope table: busy time of an expert layer that holds
a share of its experts beside a shared one (``moe/router``,
``moe/dispatch``, ``moe/experts``, ``moe/shared``, each folded over the
layers and the prediction module's block) over the busy time of the slice.
Nothing to read where the program's table has no ``moe/shared`` scope."""
from chipbench import program_probe

NAME, UNIT = "moe_held_scope_share", "%"
LAYER, MOVES = "Step on device", "tokens_per_s"
SCOPES = ("moe/router", "moe/dispatch", "moe/experts", "moe/shared")


def seconds(run, scopes=SCOPES):
    """``(seconds a chip in ``scopes``, busy seconds a chip)`` of the traced
    slice; None without a slice, a table, or a ``moe/shared`` scope in it."""
    joined = program_probe.by_scope() if run["trace"] is not None else None
    if not joined or not joined["busy_s"] \
            or "moe/shared" not in joined["scope"]:
        return None
    return (sum(joined["scope"].get(s, 0.0) for s in scopes),
            joined["busy_s"])


def read(run):
    found = seconds(run)
    return None if found is None else 100.0 * found[0] / found[1]
