"""Trace x the program's compiled step: busy time of every instruction traced
under the top-level scope ``pass`` (the scan over a looped model's passes:
its layer applications and final norms, forward and backward, and the sums
of the variables' gradients in the backward scan's carry) over the busy time
of the slice.  An overlay, as ``mtp_scope_share`` is: the generic shares
(``attn``, ``mlp``, ``ln_f``) hold the same time.  What is left is what a
step runs once whatever the passes: the embedding, the heads and the
objective, the optimizer.  Nothing to read where the program's gauges name
no loop (``loop.passes``) or the program cannot say which instructions a
top-level scope holds."""
import functools
import json
import os

from chipbench import program_probe

NAME, UNIT = "loop_body_share", "%"
LAYER, MOVES = "Step on device", "tokens_per_s"
BODY, OBJECTIVE = "pass", "exit_loss"


def program():
    """``(runner, profile module, passes)`` of a running looped program;
    None where there is no runner or no loop."""
    try:
        from autodist_tpu.autodist import get_default_autodist
        from autodist_tpu.observability import metrics, profile
    except ImportError:
        return None
    runner = getattr(get_default_autodist(), "runner", None)
    passes = metrics.registry().snapshot().get("gauges", {}).get("loop.passes")
    if not passes or not hasattr(runner, "step_text") \
            or not hasattr(profile, "overlay_table"):
        return None
    return runner, profile, int(passes)


@functools.lru_cache(maxsize=2)
def _joined(path, _mtime, scopes):
    """The traced slice joined with one table of the step's instructions by
    the top-level scope each was traced under, of ``scopes``."""
    runner, profile, _ = program()
    text, table = runner.step_text(), {}
    for scope in scopes:
        for name, (where, phase) in profile.overlay_table(text, scope).items():
            if where == scope or name not in table:
                table[name] = (where, phase)
    return program_probe.join(program_probe.load(path), table,
                              profile.device_time_by_scope)


def seconds_by_top_scope(run):
    """``({top-level scope: seconds a chip}, busy seconds a chip, passes)``
    of the traced slice, the scopes a looped model's: ``pass`` (the scan's
    body), ``pass<t>`` (pass t's head and gate), ``exit_loss``; None where
    there is nothing to read."""
    found = program() if run["trace"] is not None else None
    path = program_probe.trace_path() if found else None
    if path is None:
        return None
    passes = found[2]
    scopes = (BODY, OBJECTIVE) + tuple(f"pass{t}" for t in range(passes))
    joined = _joined(path, os.path.getmtime(path), scopes)
    if not joined["busy_s"]:
        return None
    print("chipbench: busy time by a looped model's top-level scope, % of "
          "the slice's: " + json.dumps({
              scope: round(100.0 * s / joined["busy_s"], 3)
              for scope, s in sorted(joined["scope"].items())}), flush=True)
    return dict(joined["scope"]), joined["busy_s"], passes


def read(run):
    found = seconds_by_top_scope(run)
    if found is None or BODY not in found[0]:
        return None
    return 100.0 * found[0][BODY] / found[1]
