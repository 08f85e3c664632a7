"""Trace x the program's compiled step: busy time of every instruction traced
under the top-level scope ``mtp`` (the multi-token-prediction module: its
projection, its block, its use of the head and its loss) over the busy time
of the slice.  An overlay: the module's block, head and loss are ALSO in
the generic shares (``attn``, ``moe/*``, ``head``), into which the program's
scope table folds them.  Nothing to read where the program cannot say which
instructions a top-level scope holds, or holds none under ``mtp``."""
from chipbench import program_probe

NAME, UNIT = "mtp_scope_share", "%"
LAYER, MOVES = "Step on device", "tokens_per_s"
SCOPE = "mtp"


def read(run):
    path = program_probe.trace_path() if run["trace"] is not None else None
    try:
        from autodist_tpu.autodist import get_default_autodist
        from autodist_tpu.observability import profile
    except ImportError:
        return None
    runner = getattr(get_default_autodist(), "runner", None)
    overlay = getattr(profile, "overlay_table", None)
    if path is None or overlay is None or not hasattr(runner, "step_text"):
        return None
    table = overlay(runner.step_text(), SCOPE)
    if not any(scope == SCOPE for scope, _ in table.values()):
        return None
    joined = program_probe.join(program_probe.load(path), table,
                                profile.device_time_by_scope)
    if not joined["busy_s"]:
        return None
    return 100.0 * joined["scope"].get(SCOPE, 0.0) / joined["busy_s"]
