"""Trace: the least time the chip could take for the delta rule with a decay
a channel of the traced slice's steps (``flops_kda.py``: forward and backward
a layer, each the larger of operations / peak FLOP/s and bytes / peak
bytes/s, counted by the recurrence and not by the chunked form) over the
time spent in the scope ``kda/scan``.

The shapes are the program's: the ``layer<i>/kda/{A_log,q/kernel,v/kernel}``
variables give the layers, the heads and both head widths; the positions of
a step a chip are the cell's own (the window's tokens over its steps)."""
from chipbench import flops, flops_kda
from chipbench.layer_metrics import kda_scope_share

NAME, UNIT = "kda_scan_roofline", "%"
LAYER, MOVES = "Kernels", "tokens_per_s"


def program_shapes():
    """``{"layers", "heads", "key_width", "value_width"}`` of the running
    program's KDA mixers; None where it has none."""
    try:
        from autodist_tpu.autodist import get_default_autodist
    except ImportError:
        return None
    runner = getattr(get_default_autodist(), "runner", None)
    if runner is None:
        return None
    shape = {v.name: v.shape for v in runner.program.graph_item.variables}
    heads = [s for name, s in shape.items() if name.endswith("kda/A_log")]
    if not heads:
        return None
    (n_heads,) = heads[0]
    width = {part: next(s[1] for name, s in shape.items()
                        if name.endswith(f"kda/{part}/kernel")) // n_heads
             for part in ("q", "v")}
    return {"layers": len(heads), "heads": n_heads,
            "key_width": width["q"], "value_width": width["v"]}


def read(run):
    found = kda_scope_share.seconds(run, "kda/scan")
    shapes = program_shapes()
    if found is None or shapes is None or not found[0]:
        return None
    layers = shapes.pop("layers")
    positions = round(run["tokens_per_s"] * run["window_s"] / run["steps"]
                      / run["chips"])
    least = 0.0
    for phase in flops_kda.PHASES:
        ops, nbytes = flops_kda.scan_cost(phase, positions=positions,
                                          **shapes)
        seconds, bound = flops.roofline_seconds(ops, nbytes, run["peak"])
        least += seconds
        print(f"chipbench: delta rule with a decay a channel {phase}, "
              f"{positions} positions a layer: at least {seconds * 1e6:.1f} "
              f"us, bound by {bound}", flush=True)
    steps = run["trace"]["programs"]
    print(f"chipbench: kda/scan took {found[0] / steps * 1e3:.3f} ms a step "
          f"over {steps:g} steps; its {layers} layer(s) need at least "
          f"{layers * least * 1e3:.3f} ms", flush=True)
    return 100.0 * layers * least * steps / found[0]
