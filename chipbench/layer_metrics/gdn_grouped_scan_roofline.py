"""Trace: the least time the chip could take for a gated delta rule whose
value heads read fewer key heads, over the traced slice's steps
(``flops_gdn_grouped.py``: forward and backward a layer, each the larger of
operations / peak FLOP/s and bytes / peak bytes/s, counted by the recurrence
a value head with q and k's bytes once a KEY head, and with no recomputation)
over the time spent in the scope ``gdn/scan`` (which holds the mixer's forward
pass a second time where the program recomputes it).

The shapes are the program's: the gauges ``gdn.heads`` and ``gdn.key_heads``
give the two head counts, the ``layer<i>/gdn/{A_log,q/kernel,v/kernel}``
variables the layers and both head widths.  ``gdn_scan_roofline`` takes
``q/kernel``'s width over the VALUE heads for the key width, which is right
only where the two counts are equal; this one reads nothing where the program
sets no ``gdn.key_heads``."""
from chipbench import flops, flops_gdn_grouped
from chipbench.layer_metrics import gdn_scope_share

NAME, UNIT = "gdn_grouped_scan_roofline", "%"
LAYER, MOVES = "Kernels", "tokens_per_s"


def program_shapes():
    """``{"layers", "heads", "key_heads", "key_width", "value_width"}`` of the
    running program's gated-delta mixers; None where it has none or does not
    say its key heads."""
    try:
        from autodist_tpu.autodist import get_default_autodist
        from autodist_tpu.observability import metrics
    except ImportError:
        return None
    runner = getattr(get_default_autodist(), "runner", None)
    gauges = metrics.registry().snapshot().get("gauges", {})
    heads, key_heads = gauges.get("gdn.heads"), gauges.get("gdn.key_heads")
    if runner is None or not heads or not key_heads:
        return None
    shape = {v.name: v.shape for v in runner.program.graph_item.variables}
    layers = sum(name.endswith("gdn/A_log") for name in shape)
    if not layers:
        return None
    width = {part: next(s[1] for name, s in shape.items()
                        if name.endswith(f"gdn/{part}/kernel"))
             for part in ("q", "v")}
    return {"layers": layers, "heads": int(heads),
            "key_heads": int(key_heads),
            "key_width": width["q"] // int(key_heads),
            "value_width": width["v"] // int(heads)}


def read(run):
    found = gdn_scope_share.seconds(run, "gdn/scan")
    shapes = program_shapes()
    if found is None or shapes is None or not found[0]:
        return None
    layers = shapes.pop("layers")
    positions = round(run["tokens_per_s"] * run["window_s"] / run["steps"]
                      / run["chips"])
    least = 0.0
    for phase in flops_gdn_grouped.PHASES:
        ops, nbytes = flops_gdn_grouped.scan_cost(phase, positions=positions,
                                                  **shapes)
        seconds, bound = flops.roofline_seconds(ops, nbytes, run["peak"])
        least += seconds
        print(f"chipbench: grouped gated delta rule {phase}, {positions} "
              f"positions a layer, {shapes['heads']} value heads on "
              f"{shapes['key_heads']} key heads: at least "
              f"{seconds * 1e6:.1f} us, bound by {bound}", flush=True)
    steps = run["trace"]["programs"]
    print(f"chipbench: gdn/scan took {found[0] / steps * 1e3:.3f} ms a step "
          f"over {steps:g} steps; its {layers} layer(s) need at least "
          f"{layers * least * 1e3:.3f} ms", flush=True)
    return 100.0 * layers * least * steps / found[0]
