"""The program's own counter, from inside the last step: the busiest
expert's assignments over the mean (``aux["moe.load_max_over_mean"]``, the
largest over the expert layers), which the Runner keeps as a device value
(``Runner.last_aux``) and this reads after the window.  1.0 is an even
load; a grouped product's time follows the sum, not the largest, so this
says how far the traffic is from the even case, not what it costs."""
NAME, UNIT = "moe_load_imbalance", "ratio"
LAYER, MOVES = "Step on device", "tokens_per_s"


def last_aux():
    """The last step's ``aux`` as the program kept it; None where the
    program keeps none."""
    try:
        from autodist_tpu.autodist import get_default_autodist
    except ImportError:
        return None
    runner = getattr(get_default_autodist(), "runner", None)
    return getattr(runner, "last_aux", None)


def read(run):
    aux = last_aux() or {}
    value = aux.get("moe.load_max_over_mean")
    if value is None:
        return None
    print("chipbench: the last step's aux " + ", ".join(
        f"{k} {float(v):.6g}" for k, v in sorted(aux.items())), flush=True)
    return float(value)
