"""Trace x the program's compiled step: busy time of ``attn/rope`` and
``attn/gate`` (the rotation of a part of q's and k's lanes, and the sigmoid
gate a LANE on attention's output with the projection that makes it: what the
full-attention block has around its kernels that ``attn_rope_gate_share``
reads only where the program also ran a window layer) over the busy time of
the slice.  Nothing to read where the program cannot split a block's scope
one level down, or traced neither scope."""
import functools
import os

from chipbench import program_probe

NAME, UNIT = "attn_lane_gate_share", "%"
LAYER, MOVES = "Step on device", "tokens_per_s"
SCOPES = ("attn/rope", "attn/gate")


def split(run):
    """``program_probe.join`` of the traced slice with the program's table of
    attention's inner scopes; None without a slice or such a table."""
    path = program_probe.trace_path() if run["trace"] is not None else None
    return _split(path, os.path.getmtime(path)) if path else None


@functools.lru_cache(maxsize=1)
def _split(path, _mtime):
    try:
        from autodist_tpu.autodist import get_default_autodist
        from autodist_tpu.observability import profile
    except ImportError:
        return None
    runner = getattr(get_default_autodist(), "runner", None)
    table_of = getattr(profile, "subscope_table", None)
    if table_of is None or not hasattr(runner, "step_text"):
        return None
    table = table_of(runner.step_text(), "attn")
    if not any(scope in SCOPES for scope, _ in table.values()):
        return None
    joined = program_probe.join(program_probe.load(path), table,
                                profile.device_time_by_scope)
    if not joined["busy_s"]:
        return None
    print("chipbench: attention's inner scopes, % of "
          f"{joined['busy_s'] * 1e3:.3f} ms a chip: " + ", ".join(
              f"{scope} {100.0 * seconds / joined['busy_s']:.3f}"
              for scope, seconds in joined["scope"].most_common()
              if scope.startswith("attn")), flush=True)
    return joined


def read(run):
    joined = split(run)
    if joined is None:
        return None
    return 100.0 * sum(joined["scope"].get(s, 0.0) for s in SCOPES) \
        / joined["busy_s"]
