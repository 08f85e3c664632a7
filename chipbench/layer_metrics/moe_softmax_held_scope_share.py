"""Trace x the program's scope table: busy time of an expert layer that holds
a share of its softmax-routed experts beside a shared one (``moe/router``,
``moe/dispatch``, ``moe/experts``, ``moe/shared``, each folded over the
layers) over the busy time of the slice.  ``moe_held_scope_share`` reads the
same scopes in the cell whose router scores by sigmoid; this one reports where
the program's gauges say softmax (``moe.softmax_scoring``) and a share
(``moe.experts_held`` under ``moe.experts``), and nothing elsewhere."""
from chipbench.layer_metrics import moe_held_scope_share

NAME, UNIT = "moe_softmax_held_scope_share", "%"
LAYER, MOVES = "Step on device", "tokens_per_s"


def softmax_held():
    """Whether the expert layer the running program traced last holds a
    share of softmax-routed experts, by its gauges."""
    try:
        from autodist_tpu.observability import metrics
    except ImportError:
        return False
    gauges = metrics.registry().snapshot().get("gauges", {})
    return bool(gauges.get("moe.softmax_scoring")) and \
        0 < gauges.get("moe.experts_held", 0) < gauges.get("moe.experts", 0)


def seconds(run, scopes=moe_held_scope_share.SCOPES):
    return moe_held_scope_share.seconds(run, scopes) if softmax_held() \
        else None


def read(run):
    found = seconds(run)
    return None if found is None else 100.0 * found[0] / found[1]
