"""The program's own counter, from inside the last step: the chunks a held
expert layer's loop ran, ``aux["moe.held_buffer_rows"]`` (the rows of the
chunks run, a mean over the expert layers; ``Runner.last_aux``) over the rows
of one chunk (the gauge ``moe.held_chunk_rows``; from a program without it
``parallel/moe.py:held_chunk_rows`` of the gauge
``moe.assignments_per_step``).  Each chunk is a pass of the layer's grouped
products, forward and backward: where the held count sits at a chunk's edge
(5,120 held rows a layer at an even load against a chunk of 5,120) the seed
decides between one and two, which is why the Qwen3-Next configuration
names a chunk of 7,168 (``deployment.held_chunks``)."""
from chipbench.layer_metrics import moe_held_rows_per_expert, \
    moe_load_imbalance

NAME, UNIT = "moe_held_chunks_per_layer", "count"
LAYER, MOVES = "Step on device", "tokens_per_s"


def read(run):
    rows = (moe_load_imbalance.last_aux() or {}).get("moe.held_buffer_rows")
    layout = moe_held_rows_per_expert.held_layout()
    if rows is None or layout is None:
        return None
    try:
        from autodist_tpu.observability import metrics
        from autodist_tpu.parallel.moe import held_chunk_rows
    except ImportError:
        return None
    gauges = metrics.registry().snapshot().get("gauges", {})
    chunk = int(gauges.get("moe.held_chunk_rows")
                or held_chunk_rows(layout[2]))
    print(f"chipbench: the last step's held layers ran {float(rows):g} rows "
          f"of buffer a layer in chunks of {chunk}", flush=True)
    return float(rows) / chunk
