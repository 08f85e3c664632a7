"""The program's own counter, from inside the last step: the rows one held
expert of one layer saw, ``aux["moe.held_assignments"]`` (summed over the
expert layers; ``Runner.last_aux``) over the expert layers (one router's
``gate`` variable each) and the experts a layer holds (the gauge
``moe.experts_held``).  The grouped products work a tile of rows at a time
(``parallel/moe.py:GMM_TILING``), so this says how full an expert's tiles
are: 8,192 x 10 / 512 = 160 rows at an even load."""
from chipbench.layer_metrics import moe_load_imbalance

NAME, UNIT = "moe_held_rows_per_expert", "rows"
LAYER, MOVES = "Step on device", "tokens_per_s"


def held_layout():
    """``(expert layers, experts a layer holds, assignments a layer and
    step)`` of the running program; None where it holds no share."""
    try:
        from autodist_tpu.autodist import get_default_autodist
        from autodist_tpu.observability import metrics
    except ImportError:
        return None
    runner = getattr(get_default_autodist(), "runner", None)
    if runner is None:
        return None
    gauges = metrics.registry().snapshot().get("gauges", {})
    layers = sum(v.name.endswith("moe/gate/kernel")
                 for v in runner.program.graph_item.variables)
    held, every = gauges.get("moe.experts_held"), gauges.get("moe.experts")
    a_layer = gauges.get("moe.assignments_per_step")
    if not layers or not held or not a_layer or not held < every:
        return None
    return layers, int(held), int(a_layer)


def read(run):
    rows = (moe_load_imbalance.last_aux() or {}).get("moe.held_assignments")
    layout = held_layout()
    if rows is None or layout is None:
        return None
    layers, held, _ = layout
    return float(rows) / (layers * held)
