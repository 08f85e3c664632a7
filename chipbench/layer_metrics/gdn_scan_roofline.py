"""Trace: the least time the chip could take for the gated delta rule of the
traced slice's steps (``flops_gdn.py``: forward and backward a layer, each
the larger of operations / peak FLOP/s and bytes / peak bytes/s, counted by
the recurrence and not by the chunked form) over the time spent in the scope
``gdn/scan``.

The shapes are the program's: the ``layer<i>/gdn/{A_log,q/kernel,v/kernel}``
variables give the layers, the heads and both head widths; the positions of
a step a chip are the cell's own (the window's tokens over its steps)."""
from chipbench import flops, flops_gdn
from chipbench.layer_metrics import gdn_scope_share

NAME, UNIT = "gdn_scan_roofline", "%"
LAYER, MOVES = "Kernels", "tokens_per_s"


def program_shapes():
    """``{"layers", "heads", "key_width", "value_width"}`` of the running
    program's gated-delta mixers; None where it has none."""
    try:
        from autodist_tpu.autodist import get_default_autodist
    except ImportError:
        return None
    runner = getattr(get_default_autodist(), "runner", None)
    if runner is None:
        return None
    shape = {v.name: v.shape for v in runner.program.graph_item.variables}
    heads = [s for name, s in shape.items() if name.endswith("gdn/A_log")]
    if not heads:
        return None
    (n_heads,) = heads[0]
    width = {part: next(s[1] for name, s in shape.items()
                        if name.endswith(f"gdn/{part}/kernel")) // n_heads
             for part in ("q", "v")}
    return {"layers": len(heads), "heads": n_heads,
            "key_width": width["q"], "value_width": width["v"]}


def read(run):
    found = gdn_scope_share.seconds(run, "gdn/scan")
    shapes = program_shapes()
    if found is None or shapes is None or not found[0]:
        return None
    layers = shapes.pop("layers")
    positions = round(run["tokens_per_s"] * run["window_s"] / run["steps"]
                      / run["chips"])
    least = 0.0
    for phase in flops_gdn.PHASES:
        ops, nbytes = flops_gdn.scan_cost(phase, positions=positions,
                                          **shapes)
        seconds, bound = flops.roofline_seconds(ops, nbytes, run["peak"])
        least += seconds
        print(f"chipbench: gated delta rule {phase}, {positions} positions "
              f"a layer: at least {seconds * 1e6:.1f} us, bound by {bound}",
              flush=True)
    steps = run["trace"]["programs"]
    print(f"chipbench: gdn/scan took {found[0] / steps * 1e3:.3f} ms a step "
          f"over {steps:g} steps; its {layers} layer(s) need at least "
          f"{layers * least * 1e3:.3f} ms", flush=True)
    return 100.0 * layers * least * steps / found[0]
