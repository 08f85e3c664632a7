"""Trace: the least time the chip could take for the sliding-window layers'
three attention kernels in the traced slice, by the scores inside window and
diagonal and nothing the mask hides (``flops_swa.py``: q, o and dq a query
head, k, v, dk and dv a key-value head; the larger of operations / peak FLOP/s
and bytes / peak bytes/s, per call), over the time spent in the scope
``attn/window_core``.

The shapes are the program's: the gauges ``attn.heads_window``,
``attn.kv_heads`` and ``attn.window`` set where ``layers.mha`` is traced, and
the layers of each kind counted from the widths of its ``attn/query``
variables; nothing to read where the program has none."""
from chipbench import flops, flops_swa
from chipbench.layer_metrics import swa_core_share

NAME, UNIT = "swa_kernel_roofline", "%"
LAYER, MOVES = "Kernels", "tokens_per_s"


def program_shapes():
    """``{"window": (heads, layers, window), "full": (heads, layers, None),
    "kv_heads", "head_dim"}`` of the running program's attention; None where
    it has no such gauges."""
    try:
        from autodist_tpu.autodist import get_default_autodist
        from autodist_tpu.observability import metrics
    except ImportError:
        return None
    gauges = metrics.registry().snapshot().get("gauges", {})
    names = ("attn.heads_window", "attn.heads_full", "attn.kv_heads",
             "attn.window")
    runner = getattr(get_default_autodist(), "runner", None)
    if runner is None or not all(gauges.get(name) for name in names):
        return None
    heads_window, heads_full, kv_heads, window = (int(gauges[n])
                                                  for n in names)
    widths = [v.shape[-1] for v in
              runner.program.graph_item.variables
              if v.name.endswith("attn/query/kernel")]
    if not widths:
        return None
    # Query heads differ by kind; a head's width does not.
    head_dim = min(widths) // min(heads_window, heads_full)
    return {"window": (heads_window, widths.count(heads_window * head_dim),
                       window),
            "full": (heads_full, widths.count(heads_full * head_dim), None),
            "kv_heads": kv_heads, "head_dim": head_dim}


def roofline(run, kind, scope):
    """Least time of the three kernels of the layers of ``kind`` over the
    time in ``scope``, in percent."""
    joined = swa_core_share.split(run)
    shapes = None if joined is None else program_shapes()
    trace = run["trace"]
    if shapes is None or not trace["kernel_calls"]:
        return None
    heads, layers, window = shapes[kind]
    every = shapes["window"][1] + shapes["full"][1]
    seq_len = run["attention"]["seq_len"]
    batch = max(1, round(run["tokens_per_s"] * run["window_s"]
                         / (run["steps"] * seq_len * run["chips"])))
    least = 0.0
    for kernel, calls in trace["kernel_calls"].items():
        ops, nbytes = flops_swa.grouped_window_kernel_cost(
            kernel, batch=batch, heads=heads, kv_heads=shapes["kv_heads"],
            seq_len=seq_len, head_dim=shapes["head_dim"], window=window)
        seconds, bound = flops.roofline_seconds(ops, nbytes, run["peak"])
        mine = calls * layers / every
        print(f"chipbench: {kernel} ({heads} heads over "
              f"{shapes['kv_heads']}, window {window}): {mine:g} calls a "
              f"chip, at least {seconds * 1e6:.1f} us each, bound by {bound}",
              flush=True)
        least += mine * seconds
    spent = joined["scope"].get(scope, 0.0)
    return 100.0 * least / spent if spent else None


def read(run):
    return roofline(run, "window", swa_core_share.WINDOW_CORE)
