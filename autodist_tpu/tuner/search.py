"""Candidate enumeration + budgeted search over the strategy zoo.

The searchable space is the existing ``StrategyBuilder`` zoo crossed with
its tunable knobs (fusion chunk sizes, shard thresholds, mesh shapes for
the parallelism overlays), pruned by legality (a candidate whose ``build``
raises is recorded and skipped, not fatal) and ranked by the analytic cost
model.  Only *semantics-preserving* candidates are enumerated by default:
lossy knobs (gradient compressors, bounded staleness) change numerics and
stay opt-in through explicit builder choice.

Determinism contract: chief and workers must agree on the chosen strategy
even when every process rebuilds locally (the no-KV fallback in
``autodist._ship_or_fetch_strategy``), so enumeration order is a fixed
literal sequence, randomized builders get pinned seeds, and the final
ranking sorts with an explicit ``(rounded cost, name)`` tie-break — no
dict-iteration or hash-order dependence anywhere.
"""
import json
import os
import re
from collections import namedtuple

from autodist_tpu import const
from autodist_tpu.automap.builder import Automap
from autodist_tpu.strategy.all_reduce_strategy import AllReduce
from autodist_tpu.strategy.model_parallel_strategy import ModelParallel
from autodist_tpu.strategy.parallax_strategy import Parallax
from autodist_tpu.strategy.partitioned_all_reduce_strategy import PartitionedAR
from autodist_tpu.strategy.partitioned_ps_strategy import PartitionedPS
from autodist_tpu.strategy.pipeline_strategy import (DEFAULT_STAGE_PATTERN,
                                                     Pipeline)
from autodist_tpu.strategy.ps_lb_strategy import PSLoadBalancing
from autodist_tpu.strategy.ps_strategy import PS
from autodist_tpu.strategy.random_axis_partition_all_reduce_strategy import \
    RandomAxisPartitionAR
from autodist_tpu.strategy.sequence_parallel_strategy import SequenceParallel
from autodist_tpu.strategy.uneven_partition_ps_strategy import \
    UnevenPartitionedPS
from autodist_tpu.tuner.calibration import Calibration
from autodist_tpu.tuner.cost_model import CostModel, Topology
from autodist_tpu.utils import logging

DEFAULT_BUDGET = 64

#: Tuning objective -> costing function ``(cost_model, strategy,
#: graph_item, **kwargs) -> CostBreakdown``.  The registry-completeness
#: lint (tests/test_tuner.py) prices every builder family under every
#: objective, so a new builder or a new objective cannot silently drift
#: out of the other's table.
OBJECTIVES = {
    "train_step": lambda model, strategy, item, **kw:
        model.strategy_cost(strategy, item, **kw),
    "serve_latency": lambda model, strategy, item, **kw:
        model.serve_cost(strategy, item, **kw),
}
DEFAULT_OBJECTIVE = "train_step"

#: Execution-knob variants priced per candidate under the ``train_step``
#: objective: the pipeline's microbatch count.  Variants reuse the
#: already-built strategy — they cost one extra model evaluation each,
#: never an extra build — and the per-candidate winner is chosen by
#: ``(rounded cost, label)``, the serialized baseline first on ties, so
#: rankings stay chief/worker-deterministic.
EXEC_VARIANTS = (
    ("", {}),
    # Pipeline exec knob: the GPipe microbatch count trades bubble
    # fraction (S-1)/(S+M-1) against per-microbatch dispatch granularity.
    # A no-op (identical cost, so the baseline label wins the tie) for
    # candidates without a pipe axis.
    ("+microbatches=4", {"microbatches": 4}),
    ("+microbatches=8", {"microbatches": 8}),
    ("+microbatches=16", {"microbatches": 16}),
)

#: Hierarchical two-level collective variants (docs/collectives.md):
#: full-precision ICI reduce-scatter/all-gather with the named codec on
#: the cross-host DCN leg only.  Searched on top of EXEC_VARIANTS for
#: multi-host topologies (see :func:`hier_exec_variants`); the winning
#: codec is baked into the strategy artifact (spec: DCN + compressor),
#: which is what the runner's synchronizers execute.
HIER_VARIANTS = (
    ("+hier=bf16", {"hier": "bf16"}),
    ("+hier=int8", {"hier": "int8"}),
    ("+hier=int8ef", {"hier": "int8ef"}),
)


def hier_exec_variants(topology=None):
    """The hierarchical exec variants active for this search:
    ``AUTODIST_HIER_COLLECTIVES=off`` disables them,
    ``AUTODIST_HIER_DCN_CODEC`` restricts the searched DCN codec, and a
    single-host topology gets none at all — the two-level schedule
    degenerates to the flat path there (zero cost delta), so searching
    it would only burn evaluations on guaranteed ties."""
    mode = str(const.ENV.AUTODIST_HIER_COLLECTIVES.val or "auto").lower()
    if mode in ("off", "0", "false", "no"):
        return ()
    if topology is not None and topology.num_hosts <= 1:
        return ()
    restrict = str(const.ENV.AUTODIST_HIER_DCN_CODEC.val or "").lower()
    if restrict:
        return tuple(v for v in HIER_VARIANTS if v[1]["hier"] == restrict)
    return HIER_VARIANTS


def _apply_hier_codec(strategy, codec, graph_item=None):
    """Bake the winning ``+hier=<codec>`` knob into the strategy artifact:
    every dense all-reduce node gets ``spec: DCN`` plus the codec's
    compressor enum — the selector ``AllReduceSynchronizer`` executes.
    Data-partitioned (FSDP) and PS nodes are untouched (their gradients
    have no dense all-reduce wire), and sparse-access vars keep the flat
    f32 wire the cost model priced them at (outlier-dominated embedding
    gradients don't survive blockwise quantization)."""
    from autodist_tpu.proto import strategy_pb2
    from autodist_tpu.tuner.cost_model import _parse_partitioner
    S = strategy_pb2.AllReduceSynchronizer
    comp = {"f32": S.Compressor.NoneCompressor,
            "bf16": S.Compressor.HorovodCompressor,
            "int8": S.Compressor.Int8Compressor,
            "int8ef": S.Compressor.Int8CompressorEF}[codec]
    sparse = {v.name for v in getattr(graph_item, "variables", []) or []
              if getattr(v, "sparse_access", False)}
    for node in strategy.node_config:
        if node.WhichOneof("synchronizer") != "all_reduce_synchronizer":
            continue
        if node.var_name in sparse:
            continue
        part = _parse_partitioner(node.partitioner)
        if part is not None and part[2] == const.MESH_AXIS_DATA:
            continue
        node.all_reduce_synchronizer.spec = S.Spec.DCN
        node.all_reduce_synchronizer.compressor = comp


#: Unroll factors the online re-tuning controller prices per candidate on
#: top of :data:`EXEC_VARIANTS` (docs/retuning.md).  unroll is a
#: launch-argument for the one-shot search (the runner owns the dispatch
#: shape at launch), but the live controller can re-lower mid-run, so it
#: joins the exec grid there.
RETUNE_UNROLLS = (1, 8, 32)


def reprice(strategy, graph_item, cost_model, unrolls=(1,),
            variants=EXEC_VARIANTS, host_dispatch_ms=None, batch_size=0):
    """Calibrated re-pricing of ONE already-built strategy: every
    exec-knob variant x unroll factor costed under the cost model's
    CURRENT calibration (term scales, ``profile:<scope>`` scales, link
    overrides) — the search re-entry the online re-tuning controller
    runs on the flush cadence (docs/retuning.md).  No builds happen: the
    strategy object is reused, so a full re-pricing pass is pure
    cost-model arithmetic.

    ``host_dispatch_ms`` (the calibration's measured per-dispatch host
    overhead, :attr:`Calibration.host_dispatch_ms`) replaces the
    ``DISPATCH_MS`` seed in every variant's total when given — the
    measured dispatch floor is exactly the term that makes unroll rank.
    ``batch_size`` prunes microbatch knobs that do not divide the batch.

    Returns rows ``[{label, unroll, knobs, predicted_ms, breakdown}]``
    sorted by ``(rounded cost, label)`` — deterministic like the main
    search ranking.
    """
    rows, feasible, refused = [], [], []
    for k in unrolls:
        for label, kw in variants:
            mb = kw.get("microbatches")
            if mb and batch_size and batch_size % mb:
                continue  # knob not executable on this batch
            bd = cost_model.strategy_cost(strategy, graph_item, unroll=k,
                                          **kw)
            total = bd.total_ms
            if host_dispatch_ms:
                total = total - bd["dispatch_ms"] + host_dispatch_ms / k
            row = {
                "label": f"unroll={k}{label}",
                "unroll": k,
                "knobs": {"unroll": k,
                          "bucket_mb": int(bd.get("bucket_mb") or 0),
                          "microbatches": (int(bd["microbatches"])
                                           if bd.get("microbatches")
                                           else 0)},
                "predicted_ms": float(total),
                "breakdown": dict(bd),
            }
            reason = _memory_refusal(
                cost_model, strategy, graph_item, unroll=k,
                bucket_bytes=kw.get("bucket_bytes", 0), microbatches=mb,
                row=row)
            rows.append(row)
            if reason:
                refused.append((row["label"], reason))
            else:
                feasible.append(row)
    # Memory-feasibility pruning (docs/memory.md): knob combos whose
    # predicted peak exceeds capacity x headroom are dropped — named,
    # never silent — unless EVERY combo is over (fail-open: an empty
    # ranking would strand the caller worse than an over-budget one).
    if refused and feasible:
        for label, reason in refused:
            logging.info("reprice: refused %s (%s)", label, reason)
        rows = feasible
    elif refused:
        logging.warning(
            "reprice: every exec variant exceeds the memory budget "
            "(e.g. %s: %s); keeping the ranking anyway", *refused[0])
    rows.sort(key=lambda r: (round(r["predicted_ms"], 6), r["label"]))
    return rows


def _memory_refusal(cost_model, strategy, graph_item, unroll=1,
                    bucket_bytes=0, microbatches=None, batch_rows=None,
                    row=None):
    """Predicted-memory feasibility of one (strategy, knobs) point:
    returns the named refusal reason when the predicted peak exceeds
    ``capacity x AUTODIST_MEM_HEADROOM``, else ``None``.  Attaches
    ``predicted_mem_gb`` to ``row`` when given.  Fail-open: anything the
    memory model cannot price passes."""
    try:
        mem = cost_model.strategy_memory(
            strategy, graph_item, unroll=max(1, int(unroll or 1)),
            bucket_bytes=bucket_bytes, microbatches=microbatches,
            batch_rows=batch_rows)
    except Exception as e:  # noqa: BLE001 - unpriceable: cannot refuse
        logging.debug("memory feasibility not priced: %s", e)
        return None
    if row is not None:
        row["predicted_mem_gb"] = round(mem.peak_gb, 4)
    try:
        from autodist_tpu.observability import memory as memory_mod
        return memory_mod.check_feasible(mem)
    except Exception as e:  # noqa: BLE001 - unpriceable: cannot refuse
        logging.debug("memory feasibility not checked: %s", e)
        return None


def resolve_objective(objective=None):
    """Objective name -> costing fn; unknown names fail loudly."""
    name = objective or DEFAULT_OBJECTIVE
    if name not in OBJECTIVES:
        raise ValueError(f"unknown tuner objective {name!r}; one of "
                         f"{sorted(OBJECTIVES)}")
    return name, OBJECTIVES[name]


#: A point in the search space: ``make()`` returns a fresh builder.
Candidate = namedtuple("Candidate", ["name", "family", "knobs", "make",
                                     "canonical"])


def _cand(name, family, make, canonical=False, **knobs):
    return Candidate(name, family, dict(knobs), make, canonical)


# -- per-family candidate generators ----------------------------------------
# Each takes (graph_item, resource_spec) and yields candidates in a FIXED
# order; the first yielded candidate of a family should be its canonical
# configuration (kept under tight budgets).

def _gen_all_reduce(item, spec):
    yield _cand("all_reduce/chunk=128", "AllReduce",
                lambda: AllReduce(chunk_size=128), canonical=True,
                chunk_size=128)
    for cs in (32, 512):
        yield _cand(f"all_reduce/chunk={cs}", "AllReduce",
                    lambda cs=cs: AllReduce(chunk_size=cs), chunk_size=cs)


def _gen_ps(item, spec):
    yield _cand("ps", "PS", PS, canonical=True)


def _gen_ps_lb(item, spec):
    yield _cand("ps_lb/threshold=256KiB", "PSLoadBalancing",
                lambda: PSLoadBalancing(shard_threshold_bytes=256 << 10),
                canonical=True, shard_threshold_bytes=256 << 10)
    for kib in (64, 1024):
        yield _cand(f"ps_lb/threshold={kib}KiB", "PSLoadBalancing",
                    lambda kib=kib: PSLoadBalancing(
                        shard_threshold_bytes=kib << 10),
                    shard_threshold_bytes=kib << 10)


def _gen_partitioned_ps(item, spec):
    yield _cand("partitioned_ps", "PartitionedPS", PartitionedPS,
                canonical=True)


def _gen_uneven_ps(item, spec):
    yield _cand("uneven_partitioned_ps", "UnevenPartitionedPS",
                UnevenPartitionedPS, canonical=True)


def _gen_partitioned_ar(item, spec):
    yield _cand("partitioned_ar/chunk=128", "PartitionedAR",
                lambda: PartitionedAR(chunk_size=128), canonical=True,
                chunk_size=128)


def _gen_random_axis_ar(item, spec):
    # Pinned seed: the determinism contract forbids per-process randomness.
    yield _cand("random_axis_ar/seed=0", "RandomAxisPartitionAR",
                lambda: RandomAxisPartitionAR(seed=0), canonical=True,
                seed=0)


def _gen_parallax(item, spec):
    yield _cand("parallax/chunk=128", "Parallax",
                lambda: Parallax(chunk_size=128), canonical=True,
                chunk_size=128)


def _axis_sizes(spec, hint_key):
    """Candidate sizes for a carved mesh axis: the spec's hint (when it
    divides the device count), else nothing — overlays are opt-in via
    mesh hints, never silently forced onto a model."""
    n = max(1, len(spec.accelerator_devices))
    k = int(spec.mesh_hints.get(hint_key, 0) or 0)
    if k > 1 and n % k == 0:
        yield k


def _gen_model_parallel(item, spec):
    for i, k in enumerate(_axis_sizes(spec, const.MESH_AXIS_MODEL)):
        yield _cand(f"model_parallel/tp={k}", "ModelParallel",
                    lambda k=k: ModelParallel(AllReduce(), model_axis=k),
                    canonical=(i == 0), model_axis=k)


def _gen_sequence_parallel(item, spec):
    for i, k in enumerate(_axis_sizes(spec, const.MESH_AXIS_SEQ)):
        yield _cand(f"sequence_parallel/sp={k}", "SequenceParallel",
                    lambda k=k: SequenceParallel(seq_axis=k,
                                                 base=AllReduce()),
                    canonical=(i == 0), seq_axis=k)


def _gen_pipeline(item, spec):
    pat = re.compile(DEFAULT_STAGE_PATTERN)
    stacked = any(pat.search(v.name) for v in item.trainable_variables)
    if not stacked:
        return  # Pipeline.build would raise; skip enumerating
    sizes = list(_axis_sizes(spec, const.MESH_AXIS_PIPELINE))
    if not sizes:
        # No pipeline: hint — let the stage cutter propose S from the
        # model's per-scope predicted FLOPs, so pipeline candidates rank
        # under AUTODIST_STRATEGY=auto for any stacked-blocks model (the
        # bubble term keeps them behind pure DP unless the model pays).
        from autodist_tpu.pipeline import cutter
        k, _source = cutter.resolve_stages(item, spec)
        if k > 1:
            sizes = [k]
    for i, k in enumerate(sizes):
        yield _cand(f"pipeline/stages={k}", "Pipeline",
                    lambda k=k: Pipeline(num_stages=k, base=AllReduce()),
                    canonical=(i == 0), num_stages=k)


def _gen_automap(item, spec):
    # The per-op sharding search compiler (docs/tuning.md "Automap"): its
    # build runs the inner data-parallel base search + the chain search,
    # and falls back to the base when sharding doesn't pay — so ONE
    # candidate covers the whole automap space.  No mesh hint gate: the
    # searcher decides axis sizes itself.
    yield _cand("automap", "Automap", lambda: Automap(), canonical=True)


#: builder class -> candidate generator.  The registry-completeness lint
#: (tests/test_tuner.py) pins this against ``strategy.__all__`` in both
#: directions, so new builders cannot silently escape auto-selection.
CANDIDATE_FAMILIES = {
    AllReduce: _gen_all_reduce,
    PS: _gen_ps,
    PSLoadBalancing: _gen_ps_lb,
    PartitionedPS: _gen_partitioned_ps,
    UnevenPartitionedPS: _gen_uneven_ps,
    PartitionedAR: _gen_partitioned_ar,
    RandomAxisPartitionAR: _gen_random_axis_ar,
    Parallax: _gen_parallax,
    ModelParallel: _gen_model_parallel,
    SequenceParallel: _gen_sequence_parallel,
    Pipeline: _gen_pipeline,
    Automap: _gen_automap,
}


def effective_budget(budget=None):
    """Resolve the candidate budget: explicit arg, else the env knob, else
    :data:`DEFAULT_BUDGET` (0 means 'default', i.e. effectively
    exhaustive for the shipped space)."""
    if budget is None:
        budget = const.ENV.AUTODIST_TUNER_BUDGET.val
    return int(budget) if budget and int(budget) > 0 else DEFAULT_BUDGET


def enumerate_candidates(graph_item, resource_spec, budget=None,
                         exclude_families=()):
    """Deterministic candidate list, canonical-per-family first.

    Returns ``(candidates, space_size)``: under a budget smaller than the
    space, each family's canonical configuration survives before any knob
    variant does (a cheap beam over families), so tight budgets still
    compare qualitatively different plans instead of chunk-size variants
    of one plan.  ``exclude_families`` (family name strings) drops whole
    families — the automap builder's inner base search excludes itself
    and the hint-gated overlays this way.
    """
    budget = effective_budget(budget)
    excluded = set(exclude_families or ())
    canonical, variants = [], []
    for cls, gen in CANDIDATE_FAMILIES.items():
        if cls.__name__ in excluded:
            continue
        for cand in gen(graph_item, resource_spec):
            (canonical if cand.canonical else variants).append(cand)
    ordered = canonical + variants
    return ordered[:budget], len(ordered)


class TuningResult:
    """Ranked search outcome; also what the report renders."""

    def __init__(self, ranked, pruned, budget, space_size, topology,
                 calibration, objective=DEFAULT_OBJECTIVE):
        self.ranked = ranked          # list of dicts, best first
        self.pruned = pruned          # [{"name", "reason"}]
        self.budget = budget
        self.space_size = space_size
        self.topology = topology
        self.calibration = calibration
        self.objective = objective
        self.measured_ms = None
        self.prediction_error_pct = None

    @property
    def chosen(self):
        return self.ranked[0]

    @property
    def chosen_strategy(self):
        return self.chosen["strategy"]

    @property
    def predicted_ms(self):
        return self.chosen["predicted_ms"]

    def to_json(self, top=None):
        """JSON-serializable view (strategy objects stripped)."""
        rows = []
        for i, r in enumerate(self.ranked[:top or len(self.ranked)]):
            row = {"rank": i + 1, "name": r["name"],
                   "family": r["family"], "knobs": r["knobs"],
                   "predicted_ms": round(r["predicted_ms"], 4),
                   "breakdown": {k: (round(v, 4)
                                     if isinstance(v, float) else v)
                                 for k, v in r["breakdown"].items()}}
            if r.get("op_specs") is not None:
                row["op_specs"] = r["op_specs"]
            if r.get("predicted_mem_gb") is not None:
                row["predicted_mem_gb"] = r["predicted_mem_gb"]
            if r.get("mem_refusal"):
                row["mem_refusal"] = r["mem_refusal"]
            rows.append(row)
        topo = self.topology
        return {
            "chosen": self.chosen["name"],
            "objective": self.objective,
            "predicted_ms": round(self.predicted_ms, 4),
            "measured_ms": (round(self.measured_ms, 4)
                            if self.measured_ms else None),
            "prediction_error_pct": self.prediction_error_pct,
            "budget": self.budget,
            "space_size": self.space_size,
            "evaluated": len(self.ranked),
            "mode": ("exhaustive" if self.budget >= self.space_size
                     else "beam"),
            "pruned": self.pruned,
            "topology": {"devices": topo.num_devices,
                         "hosts": topo.num_hosts,
                         "devices_per_host": topo.devices_per_host},
            "calibration_scale": round(self.calibration.scale, 4),
            "calibration_path": self.calibration.path,
            "ranking": rows,
        }


def search(graph_item, resource_spec, budget=None, cost_model=None,
           calibration=None, objective=None, exclude_families=(),
           **objective_kwargs):
    """Enumerate, legality-prune, and rank candidates; best first.

    ``objective`` selects the costing (:data:`OBJECTIVES`):
    ``"train_step"`` (default) prices a full training step;
    ``"serve_latency"`` prices a forward-only dispatch at the declared
    bucket (``batch_size=`` in ``objective_kwargs``) — no optimizer-HBM
    term, param gathers charged per request (docs/serving.md).
    """
    cal = calibration or Calibration.load()
    if cost_model is None:
        topo = Topology.from_resource_spec(resource_spec)
        cost_model = CostModel(topo, cal)
    obj_name, obj_fn = resolve_objective(objective)
    budget = effective_budget(budget)
    candidates, space_size = enumerate_candidates(
        graph_item, resource_spec, budget,
        exclude_families=exclude_families)
    exec_variants = (EXEC_VARIANTS + hier_exec_variants(cost_model.topology)
                     if obj_name == DEFAULT_OBJECTIVE else (("", {}),))
    ranked, pruned, mem_refused = [], [], []
    for cand in candidates:
        try:
            strategy = cand.make().build(graph_item, resource_spec)
        except Exception as e:  # noqa: BLE001 - illegal candidate, not fatal
            pruned.append({"name": cand.name, "reason": str(e)[:160]})
            continue
        # Price every exec-knob variant of this plan and keep the best:
        # the knobs join the search space without consuming build budget
        # (the strategy object is shared).
        best_label, best_bd = None, None
        for label, kw in exec_variants:
            bd = obj_fn(cost_model, strategy, graph_item,
                        **{**objective_kwargs, **kw})
            if best_bd is None or (round(bd.total_ms, 4), label) < \
                    (round(best_bd.total_ms, 4), best_label):
                best_label, best_bd = label, bd
        knobs = dict(cand.knobs)
        if obj_name == DEFAULT_OBJECTIVE:
            knobs["ar_bucket_mb"] = best_bd.get("bucket_mb", 0)
            if best_bd.get("microbatches"):
                # The winning microbatch knob becomes the artifact: the
                # Runner reads GraphConfig.pipeline_microbatches at trace
                # time, so the priced schedule is the executed one.
                knobs["microbatches"] = int(best_bd["microbatches"])
                strategy.graph_config.pipeline_microbatches = \
                    knobs["microbatches"]
            if best_label and best_label.startswith("+hier=") and \
                    best_bd.get("hier_codec"):
                # Same artifact-baking for a winning hierarchical knob:
                # spec DCN + codec compressor on every dense AR node, so
                # the synchronizers execute the priced two-level plan.
                knobs["hier_dcn_codec"] = best_bd["hier_codec"]
                _apply_hier_codec(strategy, best_bd["hier_codec"],
                                  graph_item)
        row = {"name": cand.name, "family": cand.family,
               "knobs": knobs,
               "predicted_ms": best_bd.total_ms,
               "breakdown": dict(best_bd),
               "strategy": strategy}
        plan = getattr(strategy, "automap_plan", None)
        if plan is not None:
            # The ranked-candidate sidecar carries the per-op specs, so a
            # plan is inspectable without re-running the search.
            row["op_specs"] = plan.to_json(cost_model.topology)
        # Memory-feasibility gate (docs/memory.md): a candidate whose
        # predicted peak HBM exceeds capacity x AUTODIST_MEM_HEADROOM is
        # refused with a NAMED reason in the pruned list — the ranked
        # sidecar shows exactly why it is absent.  Training objective
        # only: serving footprints are validated by the serve engine's
        # bucket pre-validation against its own batch rows.
        if obj_name == DEFAULT_OBJECTIVE:
            reason = _memory_refusal(
                cost_model, strategy, graph_item,
                unroll=objective_kwargs.get("unroll", 1),
                bucket_bytes=int(best_bd.get("bucket_mb") or 0) << 20,
                microbatches=knobs.get("microbatches") or None, row=row)
            if reason:
                mem_refused.append({"name": cand.name, "reason": reason,
                                    "row": row})
                continue
        ranked.append(row)
    if mem_refused and ranked:
        pruned.extend({"name": r["name"], "reason": r["reason"]}
                      for r in mem_refused)
    elif mem_refused:
        # Fail-open: EVERY legal candidate is over the memory budget.  An
        # empty ranking would strand the run before it even tried, so the
        # least-bad plans stay ranked — loudly, with the refusal carried
        # on each row.
        logging.warning(
            "tuner: every legal candidate exceeds the memory budget "
            "(e.g. %s: %s); keeping the ranking anyway",
            mem_refused[0]["name"], mem_refused[0]["reason"])
        for r in mem_refused:
            r["row"]["mem_refusal"] = r["reason"]
            ranked.append(r["row"])
    if not ranked:
        raise RuntimeError(
            f"tuner: no legal candidate out of {len(candidates)} "
            f"(pruned: {[p['name'] for p in pruned]})")
    # Explicit tie-break on the rounded cost THEN the name: ranking must be
    # bit-identical across processes (SPMD agreement when every process
    # rebuilds) and across repeated runs.
    ranked.sort(key=lambda r: (round(r["predicted_ms"], 4), r["name"]))
    logging.info("tuner: ranked %d/%d candidates (objective %s, budget %d, "
                 "%d pruned); best %s @ %.3fms", len(ranked), space_size,
                 obj_name, budget, len(pruned), ranked[0]["name"],
                 ranked[0]["predicted_ms"])
    return TuningResult(ranked, pruned, budget, space_size,
                        cost_model.topology, cal, objective=obj_name)


def sidecar_path(strategy_id):
    """Ranking sidecar location for a chosen strategy artifact."""
    return os.path.join(const.DEFAULT_SERIALIZATION_DIR,
                        f"{strategy_id}.tuner.json")


def write_sidecar(result, strategy_id):
    """Persist the ranked table next to the strategy artifact (fail-open);
    tests/test_tuner.py reads it back."""
    path = sidecar_path(strategy_id)
    try:
        const.ensure_working_dirs()
        with open(path, "w") as f:
            json.dump(result.to_json(), f, indent=1)
        return path
    except OSError as e:
        logging.debug("tuner sidecar not written: %s", e)
        return None
