"""Stage cutter: balanced pipeline stages from per-scope predicted costs.

The cutter answers "where do the pipeline stages go, and how balanced are
they" from the captured program alone: :meth:`GraphItem.op_provenance`
gives every traced equation's ``jax.named_scope`` path and FLOPs, the
cutter aggregates those per *top-level* scope in trace order, finds the
repeated-layer run (``layer0..layerN`` sibling scopes, or the single
``blocks`` scope of the stacked/``scan_blocks`` layout), and partitions it
into S contiguous stages minimizing the max per-stage cost (exact DP over
cut points, deterministic ``(rounded-cost, boundaries)`` tie-break so
chief and workers agree even when each rebuilds locally).

Robustness contract (ISSUE 14 satellite): equations with no usable scope
land in the ``(unattributed)`` bucket of ``scope_costs()`` — the cutter
charges them to their *nearest enclosing stage* (the most recent top-level
scope in trace order; the prelude before any scope goes to the first
stage), never drops them, so the per-stage costs sum EXACTLY to the
per-equation total ``flops_estimate()`` counts.

Per-scope calibration (``Calibration.scope_scales()``, the PR 9 profiler's
``profile:<scope>`` samples) refines each scope's predicted compute with
its measured-vs-predicted ratio before balancing, so a layer the profiler
measured slow weighs more in the cut and in the cost model's imbalance
term.
"""
import re

from autodist_tpu.utils import logging

#: Scope name of a repeated block: trailing integer index ("layer3",
#: "stage2/block1" top-levels like "stage2" — any prefix + digits).
_INDEXED = re.compile(r"^(?P<prefix>.*?)(?P<idx>\d+)$")

# Last StageCut produced in this process (the report's surface, like
# tuner.last_result / automap.last_result).
_last_cut = None


def last_cut():
    return _last_cut


def set_last_cut(cut):
    global _last_cut
    _last_cut = cut


class StageCut:
    """A balanced assignment of model scopes to S pipeline stages."""

    def __init__(self, stages, total_flops, num_layers, layer_prefix,
                 source="auto"):
        self.stages = stages            # [{"scopes", "flops", "bytes"}]
        self.total_flops = total_flops  # == sum of per-eqn flops, exactly
        self.num_layers = num_layers
        self.layer_prefix = layer_prefix  # "" for the stacked-blocks layout
        self.source = source            # "explicit" | "env" | "hint" | "auto"

    @property
    def num_stages(self):
        return len(self.stages)

    @property
    def imbalance(self):
        """max stage cost / mean stage cost - 1 (0.0 == perfectly even).

        Measured over the *pipelined layer run* only (``layer_flops``):
        the prelude/postlude (embedding, head, loss) run outside the
        schedule on every rank, so they belong in the sum invariant but
        not in the slowest-stage pacing term."""
        costs = [s.get("layer_flops", s["flops"]) for s in self.stages]
        mean = sum(costs) / max(1, len(costs))
        if mean <= 0:
            return 0.0
        return max(costs) / mean - 1.0

    def to_json(self):
        return {
            "num_stages": self.num_stages,
            "num_layers": self.num_layers,
            "layer_prefix": self.layer_prefix,
            "source": self.source,
            "imbalance": round(self.imbalance, 4),
            "total_flops": self.total_flops,
            "stages": [{"scopes": list(s["scopes"]),
                        "flops": s["flops"],
                        "share": (round(s["flops"] / self.total_flops, 4)
                                  if self.total_flops else 0.0)}
                       for s in self.stages],
        }


def top_level_costs(graph_item, calibration=None):
    """Per top-level-scope predicted FLOPs, in trace order.

    Returns ``[(scope, flops, bytes)]``.  Scope-less equations are charged
    to the nearest enclosing group — the most recent top-level scope seen
    in trace order, or the FIRST group for the prelude — never dropped,
    so ``sum(flops) == sum of every traced equation's flops`` exactly
    (the quantity ``flops_estimate()`` counts).  Per-scope calibration
    ratios (``scope_scales``) multiply the matching scope's compute.
    """
    records = graph_item.op_provenance()
    if not records:
        return []
    order, agg = [], {}
    prelude = []  # records before the first scoped equation
    current = None
    for rec in records:
        top = rec["scope"].split("/", 1)[0] if rec["scope"] else ""
        if not top:
            top = current  # nearest enclosing scope, in trace order
        if top is None:
            prelude.append(rec)
            continue
        if top not in agg:
            order.append(top)
            agg[top] = {"flops": 0.0, "bytes": 0.0}
        current = top if rec["scope"] else current
        agg[top]["flops"] += rec["flops"]
        agg[top]["bytes"] += rec["bytes"]
    if not order:
        # A fully scope-less program: one synthetic group holds everything.
        order.append("")
        agg[""] = {"flops": 0.0, "bytes": 0.0}
    for rec in prelude:  # charge the pre-scope prelude to the first stage
        agg[order[0]]["flops"] += rec["flops"]
        agg[order[0]]["bytes"] += rec["bytes"]
    scales = {}
    if calibration is not None:
        try:
            scales = calibration.scope_scales()
        except Exception as e:  # noqa: BLE001 - calibration is best-effort
            logging.debug("scope scales unavailable: %s", e)
    out = []
    for scope in order:
        scale = float(scales.get(scope, {}).get("compute", 1.0))
        out.append((scope, agg[scope]["flops"] * scale,
                    agg[scope]["bytes"]))
    return out


def _layer_run(groups):
    """Longest run of consecutive same-prefix indexed scopes.

    Returns ``(start, end, prefix)`` — the half-open [start, end) range in
    ``groups`` holding the repeated-layer scopes — or ``None`` when the
    model has no indexed run (e.g. the stacked ``blocks`` layout, handled
    separately).
    """
    best = None
    i = 0
    while i < len(groups):
        m = _INDEXED.match(groups[i][0])
        if not m:
            i += 1
            continue
        prefix, idx = m.group("prefix"), int(m.group("idx"))
        j = i + 1
        nxt = idx + 1
        while j < len(groups):
            m2 = _INDEXED.match(groups[j][0])
            if not m2 or m2.group("prefix") != prefix or \
                    int(m2.group("idx")) != nxt:
                break
            nxt += 1
            j += 1
        if j - i >= 2 and (best is None or j - i > best[1] - best[0]):
            best = (i, j, prefix)
        i = j if j > i + 1 else i + 1
    return best


def _balanced_partition(costs, k):
    """Cut ``costs`` into k contiguous groups minimizing the max group
    sum.  Exact DP; ties broken by the lexicographically smallest
    boundary tuple on the ROUNDED cost, so every process computes the
    same cut (the chief/worker determinism contract).  Returns the list
    of boundary indices (length k-1)."""
    n = len(costs)
    k = max(1, min(k, n))
    prefix = [0.0]
    for c in costs:
        prefix.append(prefix[-1] + c)

    def span(i, j):  # cost of [i, j)
        return prefix[j] - prefix[i]

    # best[j][g]: (rounded max cost, boundaries tuple) for the first j
    # items in g groups.
    best = {(0, 0): (0.0, ())}
    for g in range(1, k + 1):
        for j in range(g, n - (k - g) + 1):
            cand = None
            for i in range(g - 1, j):
                prev = best.get((i, g - 1))
                if prev is None:
                    continue
                cost = max(prev[0], round(span(i, j), 6))
                bounds = prev[1] + ((i,) if g > 1 else ())
                key = (cost, bounds)
                if cand is None or key < cand:
                    cand = key
            if cand is not None:
                best[(j, g)] = cand
    return list(best[(n, k)][1])


def cut_stages(graph_item, num_stages, calibration=None, source="auto"):
    """Partition the model's repeated-layer run into ``num_stages``
    balanced stages; returns a :class:`StageCut`.

    Works on any model with scope provenance: the indexed-layer layout
    (``layer0..layerN``) is cut by predicted per-layer FLOPs; the stacked
    ``scan_blocks`` layout (one ``blocks`` scope whose scan body traces
    once) is homogeneous by construction, so the cut is the contiguous
    L/S split ``scan_blocks`` executes and the imbalance reflects only a
    non-divisible layer count.  A program with no provenance (metadata-
    only GraphItem) yields a uniform synthetic cut (imbalance 0) so cost
    ranking still works.
    """
    num_stages = max(1, int(num_stages))
    groups = top_level_costs(graph_item, calibration)
    num_layers = _stacked_layer_count(graph_item)
    if not groups:
        stages = [{"scopes": (f"stage{i}",), "flops": 0.0, "bytes": 0.0}
                  for i in range(num_stages)]
        return StageCut(stages, 0.0, num_layers or num_stages, "",
                        source=source)
    total = sum(f for _, f, _ in groups)

    run = _layer_run(groups)
    if run is None and num_layers:
        # Stacked-blocks layout: the "blocks" scan body traces once, so
        # synthesize L homologous layers from the single blocks group and
        # spread the rest of the model around them.
        bi = next((i for i, (s, _, _) in enumerate(groups)
                   if s == "blocks"), None)
        if bi is not None:
            per_layer = groups[bi][1]
            per_bytes = groups[bi][2]
            synth = [(f"blocks[{i}]", per_layer, per_bytes)
                     for i in range(num_layers)]
            groups = groups[:bi] + synth + groups[bi + 1:]
            total = sum(f for _, f, _ in groups)
            run = (bi, bi + num_layers, "blocks[")
    if run is None:
        # No repeated run: cut the whole top-level sequence.
        run = (0, len(groups), "")

    start, end, prefix = run
    layers = groups[start:end]
    bounds = _balanced_partition([f for _, f, _ in layers], num_stages)
    edges = [0] + bounds + [len(layers)]
    stages = []
    for s in range(min(num_stages, len(layers))):
        chunk = layers[edges[s]:edges[s + 1]]
        flops = sum(f for _, f, _ in chunk)
        stages.append({"scopes": tuple(n for n, _, _ in chunk),
                       "flops": flops, "layer_flops": flops,
                       "bytes": sum(b for _, _, b in chunk)})
    while len(stages) < num_stages:  # fewer layers than stages
        stages.append({"scopes": (), "flops": 0.0, "layer_flops": 0.0,
                       "bytes": 0.0})
    # Prelude (embed, ...) rides with the first stage, the postlude
    # (final norm, head, loss) with the last — where the schedule runs
    # them (outside the pipelined block stack, but the balance ledger
    # must still sum to the program total).
    for g in groups[:start]:
        stages[0]["flops"] += g[1]
        stages[0]["bytes"] += g[2]
        stages[0]["scopes"] = (g[0],) + tuple(stages[0]["scopes"])
    for g in groups[end:]:
        stages[-1]["flops"] += g[1]
        stages[-1]["bytes"] += g[2]
        stages[-1]["scopes"] = tuple(stages[-1]["scopes"]) + (g[0],)
    cut = StageCut(stages, total, end - start, prefix, source=source)
    return cut


def _stacked_layer_count(graph_item):
    """Leading dim of the stacked ``blocks/`` variables (0 when absent)."""
    for v in graph_item.trainable_variables:
        if ("blocks/" in v.name or v.name.startswith("blocks/")) and v.shape:
            return int(v.shape[0])
    return 0


def resolve_microbatches(graph_item, num_stages, explicit=None):
    """Resolve the GPipe microbatch count M for ``num_stages``: an
    explicit count wins untouched; else ``AUTODIST_MICROBATCHES``, else
    ``2 * num_stages`` — and a defaulted count that does not divide the
    captured batch (the schedule reshapes batch -> (M, batch/M)) falls
    back to the largest batch divisor.  Shared by ``Pipeline.build`` and
    automap's pipe-axis proposals so both arms resolve identically."""
    from autodist_tpu import const
    num_microbatches = int(
        explicit or const.ENV.AUTODIST_MICROBATCHES.val or 2 * num_stages)
    batch = int(graph_item.batch_size or 0)
    if not explicit and batch and batch % num_microbatches:
        for m in range(min(num_microbatches, batch), 0, -1):
            if batch % m == 0:
                return m
    return num_microbatches


def resolve_stages(graph_item, resource_spec, explicit=None):
    """Resolve the stage count S: explicit arg > ``AUTODIST_PIPELINE_STAGES``
    > the spec's ``pipeline:`` mesh hint > the cutter's own choice (the
    divisor of the device count with the best predicted step share under
    the default microbatch count).  Returns ``(num_stages, source)``;
    ``(1, ...)`` means "don't pipeline"."""
    from autodist_tpu import const
    if explicit:
        return int(explicit), "explicit"
    env = const.ENV.AUTODIST_PIPELINE_STAGES.val
    if env and int(env) > 1:
        return int(env), "env"
    hint = int(resource_spec.mesh_hints.get(const.MESH_AXIS_PIPELINE, 0) or 0)
    n = max(1, len(resource_spec.accelerator_devices))
    if hint > 1 and n % hint == 0:
        return hint, "hint"
    layers = _stacked_layer_count(graph_item)
    if not layers:
        return 1, "auto"
    best = None
    for k in range(2, min(8, layers, n) + 1):
        if n % k or layers % k:
            continue
        cut = cut_stages(graph_item, k)
        m = 2 * k  # default microbatch count the builder would pick
        # Per-rank step share: bubble-stretched max-stage cost.
        share = (1.0 + cut.imbalance) * (m + k - 1) / (m * k)
        key = (round(share, 6), k)
        if best is None or key < best:
            best = (key[0], k)
    return (best[1], "auto") if best else (1, "auto")
