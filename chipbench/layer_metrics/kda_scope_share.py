"""Trace x the program's scope table: busy time of the KDA mixers' scopes
(``kda/proj``, ``kda/conv``, ``kda/gates``, ``kda/scan``, ``kda/out`` and
``kda`` itself; each folded over the layers) over the busy time of the
slice.  Nothing to read where the program's table has no such scope."""
from chipbench import program_probe

NAME, UNIT = "kda_scope_share", "%"
LAYER, MOVES = "Step on device", "tokens_per_s"
SCOPE = "kda"


def seconds(run, only=None):
    """``(seconds a chip in the mixers' scopes, or in ``only`` of them, busy
    seconds a chip)`` of the traced slice; None without a slice, a table,
    or any ``kda`` scope in it."""
    joined = program_probe.by_scope() if run["trace"] is not None else None
    if not joined or not joined["busy_s"]:
        return None
    found = {s: t for s, t in joined["scope"].items()
             if s == SCOPE or s.startswith(SCOPE + "/")}
    if not found:
        return None
    return (sum(t for s, t in found.items() if only is None or s == only),
            joined["busy_s"])


def read(run):
    found = seconds(run)
    return None if found is None else 100.0 * found[0] / found[1]
