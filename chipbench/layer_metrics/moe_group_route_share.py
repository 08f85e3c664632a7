"""Trace x the program's scope table: busy time of a group-limited router
(``moe/router`` with ``moe/router/groups``, the step that keeps a token's
choice inside its best groups; each folded over the layers) over the busy
time of the slice.  Nothing to read where the program's table has no
``moe/router/groups`` scope."""
from chipbench import program_probe

NAME, UNIT = "moe_group_route_share", "%"
LAYER, MOVES = "Step on device", "tokens_per_s"
SCOPES = ("moe/router", "moe/router/groups")


def read(run):
    joined = program_probe.by_scope() if run["trace"] is not None else None
    if not joined or not joined["busy_s"] \
            or SCOPES[1] not in joined["scope"]:
        return None
    return 100.0 * sum(joined["scope"].get(s, 0.0) for s in SCOPES) \
        / joined["busy_s"]
