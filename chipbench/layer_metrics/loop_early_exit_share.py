"""Trace x the program's compiled step: what a looped model's objective
costs over a plain model's one head.  Busy time of the instructions traced
under ``pass<t>`` for every pass but the last (that pass's float32 head and
the cross-entropy of each position, ``pass<t>/lm_head``, and its exit gate,
``pass<t>/exit_gate``) and under ``exit_loss`` (the weighing of the passes'
losses by the exit distribution and the entropy term), forward and backward,
over the busy time of the slice.  Nothing to read where the program has no
loop."""
from chipbench.layer_metrics import loop_body_share

NAME, UNIT = "loop_early_exit_share", "%"
LAYER, MOVES = "Step on device", "tokens_per_s"


def read(run):
    found = loop_body_share.seconds_by_top_scope(run)
    if found is None:
        return None
    scope, busy, passes = found
    rows = [f"pass{t}" for t in range(passes - 1)] \
        + [loop_body_share.OBJECTIVE]
    if not any(row in scope for row in rows):
        return None
    return 100.0 * sum(scope.get(row, 0.0) for row in rows) / busy
