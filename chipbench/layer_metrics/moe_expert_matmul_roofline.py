"""Trace: the least time the chip could take for the expert layers' grouped
matrix products in the traced slice (``flops_moe.py``: nine a layer and
step, each the larger of operations / peak FLOP/s and bytes / peak bytes/s)
over the time spent in the scope ``moe/experts``, which also holds the gate
(``silu(.) * .``) between the products and the casts of the matrices.

The shapes are the program's: the stacked ``layer<i>/moe/up/kernel``
variables give experts, width and expert width, the gauge
``moe.assignments_per_step`` the rows of one layer's products."""
from chipbench import flops, flops_moe
from chipbench.layer_metrics import moe_scope_share

NAME, UNIT = "moe_expert_matmul_roofline", "%"
LAYER, MOVES = "Kernels", "tokens_per_s"


def program_shapes():
    """``{"layers", "experts", "width", "expert_width", "assignments"}`` of
    the running program's expert layers; None where it has none."""
    try:
        from autodist_tpu.autodist import get_default_autodist
        from autodist_tpu.observability import metrics
    except ImportError:
        return None
    runner = getattr(get_default_autodist(), "runner", None)
    if runner is None:
        return None
    stacked = [v.shape for v in runner.program.graph_item.variables
               if v.name.endswith("moe/up/kernel")]
    gauges = metrics.registry().snapshot().get("gauges", {})
    assignments = gauges.get("moe.assignments_per_step")
    if not stacked or not assignments:
        return None
    experts, width, expert_width = stacked[0]
    return {"layers": len(stacked), "experts": experts, "width": width,
            "expert_width": expert_width, "assignments": int(assignments)}


def read(run):
    found = moe_scope_share.seconds(run, ("moe/experts",))
    shapes = program_shapes()
    if found is None or shapes is None or not found[0]:
        return None
    layers = shapes.pop("layers")
    least = 0.0
    for name, ops, nbytes in flops_moe.expert_layer_products(**shapes):
        seconds, bound = flops.roofline_seconds(ops, nbytes, run["peak"])
        least += seconds
        print(f"chipbench: grouped product {name}: at least "
              f"{seconds * 1e6:.1f} us, bound by {bound}", flush=True)
    steps = run["trace"]["programs"]
    print(f"chipbench: moe/experts took {found[0] / steps * 1e3:.3f} ms a "
          f"step over {steps:g} steps; its {layers} layer(s) of nine "
          f"products need at least {layers * least * 1e3:.3f} ms",
          flush=True)
    return 100.0 * layers * least * steps / found[0]
