"""Trace: the least time the chip could take for the attention kernels' calls
of the traced slice in their two-product form (latent attention), by the
products they really make (``flops_mla.py``: scores over 128 + 64 lanes,
values over 128, the shared rotary key counted once a position; the larger
of operations / peak FLOP/s and bytes / peak bytes/s, per call), over the
time they took.  ``attn_kernel_roofline`` reads the same calls by the
one-width yardstick at the mean width.

The shapes are the program's: the gauges ``mla.heads``, ``mla.nope_width``,
``mla.rope_width`` and ``mla.value_width``, set where ``layers.mla`` is
traced; nothing to read where the program has none."""
from chipbench import flops, flops_mla

NAME, UNIT = "mla_kernel_roofline", "%"
LAYER, MOVES = "Kernels", "tokens_per_s"


def program_widths():
    """``{"heads", "nope", "rope", "value"}`` of the running program's
    latent attention; None where it has none."""
    try:
        from autodist_tpu.observability import metrics
    except ImportError:
        return None
    gauges = metrics.registry().snapshot().get("gauges", {})
    found = {key: gauges.get(f"mla.{name}") for key, name in (
        ("heads", "heads"), ("nope", "nope_width"), ("rope", "rope_width"),
        ("value", "value_width"))}
    return {k: int(v) for k, v in found.items()} if all(found.values()) \
        else None


def read(run):
    trace, widths = run["trace"], program_widths()
    if trace is None or not trace["kernel_seconds"] or widths is None:
        return None
    shape = run["attention"]
    batch = shape["batch_heads"] // widths["heads"]
    least = 0.0
    for kernel, calls in trace["kernel_calls"].items():
        ops, nbytes = flops_mla.two_product_kernel_cost(
            kernel, batch=batch, seq_len=shape["seq_len"],
            causal=shape["causal"], **widths)
        seconds, bound = flops.roofline_seconds(ops, nbytes, run["peak"])
        print(f"chipbench: {kernel} (two-product): {calls:g} calls a chip, "
              f"at least {seconds * 1e6:.1f} us each, bound by {bound}",
              flush=True)
        least += calls * seconds
    return 100.0 * least / sum(trace["kernel_seconds"].values())
