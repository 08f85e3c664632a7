"""The program's own counter, from inside the last step: the assignments that
chose an expert this chip holds (``aux["moe.held_assignments"]``, summed over
the expert layers; ``Runner.last_aux``) over all the step's assignments (the
gauge ``moe.assignments_per_step`` times the expert layers, one router's
``gate`` variable each).  16 of 256 experts held take 6.25% at an even load;
the rest of the sorted rows no product visits."""
from chipbench.layer_metrics import moe_load_imbalance

NAME, UNIT = "moe_held_share", "%"
LAYER, MOVES = "Step on device", "tokens_per_s"


def read(run):
    held = (moe_load_imbalance.last_aux() or {}).get("moe.held_assignments")
    if held is None:
        return None
    from autodist_tpu.autodist import get_default_autodist
    from autodist_tpu.observability import metrics
    layers = sum(v.name.endswith("moe/gate/kernel") for v in
                 get_default_autodist().runner.program.graph_item.variables)
    a_layer = metrics.registry().snapshot().get("gauges", {}).get(
        "moe.assignments_per_step")
    if not layers or not a_layer:
        return None
    print(f"chipbench: the last step's held assignments {float(held):g} of "
          f"{layers} x {int(a_layer)}", flush=True)
    return 100.0 * float(held) / (layers * a_layer)
