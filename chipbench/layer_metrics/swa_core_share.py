"""Trace x the program's compiled step: busy time of the sliding-window
layers' attention cores (the named scope ``attn/window_core`` of
``layers.mha``, folded over the layers: the three flash kernels under a
window and what the backward pass computes beside them) over the busy time of
the slice.  The generic ``attn`` row holds it together with the projections,
rotary, the gate and the full layers' cores.

:func:`split` is what the readers of attention's inner scopes share
(``gqa_core_share``, ``attn_rope_gate_share``, ``swa_kernel_roofline``,
``gqa_kernel_roofline``).  Nothing to read where the program cannot split a
block's scope one level down, or traced no ``attn/window_core``."""
import functools
import os

from chipbench import program_probe

NAME, UNIT = "swa_core_share", "%"
LAYER, MOVES = "Step on device", "tokens_per_s"
WINDOW_CORE, CORE = "attn/window_core", "attn/core"


def split(run):
    """``program_probe.join`` of the traced slice with the program's table
    of attention's inner scopes (seconds a chip by ``attn/<scope>``, busy
    seconds); None without a slice, without such a table, or where the
    program ran no sliding layer."""
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
    if not any(scope == WINDOW_CORE for scope, _ in table.values()):
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


def share(run, scopes):
    joined = split(run)
    if joined is None:
        return None
    return 100.0 * sum(joined["scope"].get(s, 0.0) for s in scopes) \
        / joined["busy_s"]


def read(run):
    return share(run, (WINDOW_CORE,))
