"""Automap: the per-op sharding search as a first-class StrategyBuilder.

``build`` composes three stages, all deterministic:

1. **Base**: the existing tuner zoo, restricted to the data-parallel
   families (overlays and automap itself excluded), picks the
   per-variable sync winner — the plan automap falls back to when
   sharding does not pay.
2. **Search**: :mod:`autodist_tpu.automap.search` walks the captured
   program's shard-node chain and solves per-weight assignments per
   candidate axis size.
3. **Rank**: every materialized candidate (the base + each sharded
   plan) is priced through ``CostModel.strategy_cost`` — the SAME
   objective the zoo ranks under — with ``(rounded-cost, name)``
   tie-breaking; a sharded plan must beat the base by
   :data:`~autodist_tpu.automap.search.MIN_GAIN_PCT` to be chosen.

Selected via ``AutoDist(strategy_builder=Automap())``, via
``AUTODIST_STRATEGY=automap``, or ranked against the zoo inside
``AUTODIST_STRATEGY=auto`` (docs/tuning.md).
"""
import json
import os
import time

from autodist_tpu import const, observability
from autodist_tpu.automap import search as automap_search
from autodist_tpu.automap.plan import plan_fingerprint
from autodist_tpu.strategy.base import StrategyBuilder, carve_mesh_axis
from autodist_tpu.utils import logging

#: Families excluded from the base (fallback) search: automap must not
#: recurse into itself, and the hint-gated overlays would double-apply
#: the very axes the per-op search owns.
BASE_EXCLUDED_FAMILIES = ("Automap", "ModelParallel", "SequenceParallel",
                          "Pipeline")

# Last AutomapResult produced in this process: the report's per-op
# proposal table reads it.
_last_result = None


def last_result():
    return _last_result


def set_last_result(result):
    global _last_result
    _last_result = result


class AutomapResult:
    """Search outcome surface: ranked mesh candidates + per-op detail."""

    def __init__(self, chosen_name, base_name, ranked, outcome, topology,
                 fingerprint):
        self.chosen_name = chosen_name    # "automap/dp" or "automap/<axis>=<k>"
        self.base_name = base_name        # the zoo family the base search chose
        self.ranked = ranked              # [{"name", "predicted_ms", ...}]
        self.outcome = outcome            # automap_search.SearchOutcome
        self.topology = topology
        self.fingerprint = fingerprint

    @property
    def chosen_plan(self):
        for row in self.ranked:
            if row["name"] == self.chosen_name:
                return row.get("plan")
        return None

    @property
    def rediscovered(self):
        """{"tp": bool, "ep": bool}: did the search shard anything on a
        model (tensor-parallel) / expert axis — the ROADMAP acceptance
        flags tests/test_automap.py asserts.  A composed plan sets BOTH."""
        plan = self.chosen_plan
        axes = plan.axes if plan is not None else {}
        return {"tp": const.MESH_AXIS_MODEL in axes,
                "ep": const.MESH_AXIS_EXPERT in axes}

    @property
    def composition(self):
        """Multi-axis surface of the chosen plan (the composed-
        rediscovery flags): the carved axes, the mesh name, the
        placement verdict, and whether a pipe axis rode along."""
        plan = self.chosen_plan
        if plan is None:
            return {"composed": False, "mesh": "data", "axes": {},
                    "placement": {}, "pipelined": False}
        return {"composed": plan.composed, "mesh": plan.mesh_name,
                "axes": dict(plan.mesh_axes),
                "placement": dict(plan.placement),
                "pipelined": plan.pipeline is not None}

    def to_json(self):
        rows = []
        for r in self.ranked:
            plan = r.get("plan")
            row = {
                "name": r["name"],
                "predicted_ms": round(r["predicted_ms"], 4),
                "breakdown": {k: (round(v, 4) if isinstance(v, float)
                                  else v)
                              for k, v in r["breakdown"].items()},
                "plan": (plan.to_json(self.topology)
                         if plan is not None else None)}
            if r.get("predicted_mem_gb") is not None:
                row["predicted_mem_gb"] = r["predicted_mem_gb"]
            if r.get("mem_refusal"):
                row["mem_refusal"] = r["mem_refusal"]
            rows.append(row)
        return {
            "chosen": self.chosen_name,
            "base": self.base_name,
            "fingerprint": self.fingerprint,
            "search_ms": round(self.outcome.search_ms, 3),
            "budget": self.outcome.budget,
            "space_size": self.outcome.space_size,
            "min_gain_pct": automap_search.MIN_GAIN_PCT,
            "rediscovered": self.rediscovered,
            "composition": self.composition,
            "ranking": rows,
        }


def sidecar_path(strategy_id):
    """Per-op proposal sidecar location next to the strategy artifact."""
    return os.path.join(const.DEFAULT_SERIALIZATION_DIR,
                        f"{strategy_id}.automap.json")


def write_sidecar(result, strategy_id):
    """Persist the proposal table so a plan is inspectable without
    re-running the search (fail-open, like the tuner sidecar)."""
    path = sidecar_path(strategy_id)
    try:
        const.ensure_working_dirs()
        with open(path, "w") as f:
            json.dump(result.to_json(), f, indent=1)
        return path
    except OSError as e:
        logging.debug("automap sidecar not written: %s", e)
        return None


def materialize(base, resource_spec, plan, graph_item=None):
    """Overlay a searched plan onto a copy of the base strategy: carve
    the plan's axes out of ``data`` (canonical order, ``pipe`` outermost
    and ``model`` innermost — the layout that makes the ICI placement
    physically real), stamp per-variable partitioners (composed kinds
    emit multi-entry strings), and record the per-op activation
    constraints in the artifact.  A pipe-bearing plan additionally
    records the microbatch count and storage-shards the stacked block
    variables over ``pipe`` exactly as ``Pipeline.build`` does."""
    from autodist_tpu.automap.plan import CANONICAL_AXES
    from autodist_tpu.proto import strategy_pb2
    from autodist_tpu.strategy.base import Strategy
    proto = strategy_pb2.Strategy()
    proto.CopyFrom(base.proto)
    proto.id = ""    # a distinct artifact: mint a fresh id
    proto.path = ""
    strategy = Strategy(proto)
    for axis in CANONICAL_AXES:
        if axis in plan.axes:
            carve_mesh_axis(strategy, resource_spec, axis, plan.axes[axis])
    for name, ptext in sorted(plan.partitioners().items()):
        node = strategy.node_by_name(name)
        if node is not None and not node.partitioner:
            node.partitioner = ptext
    if plan.pipeline and graph_item is not None:
        import re
        from autodist_tpu.strategy.pipeline_strategy import \
            DEFAULT_STAGE_PATTERN
        stages = int(plan.pipeline["stages"])
        strategy.graph_config.pipeline_microbatches = \
            int(plan.pipeline["microbatches"])
        pat = re.compile(DEFAULT_STAGE_PATTERN)
        nodes = {n.var_name: n for n in strategy.node_config}
        for var in graph_item.trainable_variables:
            node = nodes.get(var.name)
            if node is None or not pat.search(var.name) or \
                    node.partitioner:
                continue
            if var.shape and var.shape[0] % stages == 0:
                node.partitioner = \
                    f"0:{stages}:{const.MESH_AXIS_PIPELINE}"
    strategy.invalidate_node_cache()
    for scope, spec_text in sorted(plan.op_shardings().items()):
        strategy.graph_config.op_shardings[scope] = spec_text
    strategy.automap_plan = plan
    return strategy


class Automap(StrategyBuilder):
    """Per-op sharding search compiler (docs/tuning.md "Automap").

    Args:
        budget: mesh candidates priced, incl. the DP base (default:
            ``AUTODIST_AUTOMAP_BUDGET``, else 8; 1 forces the base).
        base_budget: candidate budget for the inner data-parallel zoo
            search (default: the zoo default).
        calibration: a Calibration to price with (default: the persisted
            file — per-scope ``profile:<scope>`` samples refine the
            per-op terms).
    """

    def __init__(self, budget=None, base_budget=None, calibration=None):
        self._budget = budget
        self._base_budget = base_budget
        self._calibration = calibration

    def build(self, graph_item, resource_spec):
        # Lazy: tuner.search imports this module for the family registry
        # (and tuner/__init__ shadows the submodule name with the search
        # FUNCTION, so resolve the module through importlib).
        import importlib
        tuner_search = importlib.import_module("autodist_tpu.tuner.search")
        from autodist_tpu.tuner.calibration import Calibration
        from autodist_tpu.tuner.cost_model import CostModel, Topology
        t0 = time.perf_counter()
        cal = self._calibration or Calibration.load()
        topo = Topology.from_resource_spec(resource_spec)
        model = CostModel(topo, cal)
        base_result = tuner_search.search(
            graph_item, resource_spec, budget=self._base_budget,
            cost_model=model, calibration=cal,
            exclude_families=BASE_EXCLUDED_FAMILIES)
        base = base_result.chosen_strategy
        frozen = {n.var_name for n in base.node_config if n.partitioner}
        outcome = automap_search.search_plans(
            graph_item, topo, calibration=cal, budget=self._budget,
            frozen=frozen)

        # Rank materialized candidates on the zoo's exact objective.
        ranked, mem_refused = [], []
        for cand in outcome.candidates or \
                [automap_search.PlanCandidate("automap/dp", None, 0.0, {})]:
            strategy = (base if cand.plan is None
                        else materialize(base, resource_spec, cand.plan,
                                         graph_item))
            bd = model.strategy_cost(strategy, graph_item)
            row = {"name": cand.name, "plan": cand.plan,
                   "strategy": strategy,
                   "predicted_ms": bd.total_ms,
                   "breakdown": dict(bd)}
            # Memory-feasibility gate (docs/memory.md): a searched plan
            # whose predicted peak exceeds capacity x headroom is refused
            # with a NAMED row in the sidecar.  The DP base is never
            # pruned — fail-open: an infeasible base is still the
            # least-bad anchor the MIN_GAIN_PCT fallback needs.
            reason = None
            if cand.plan is not None:
                reason = tuner_search._memory_refusal(
                    model, strategy, graph_item, row=row)
            if reason:
                mem_refused.append(dict(row, mem_refusal=reason))
                logging.info("Automap: refused %s (%s)", cand.name, reason)
                continue
            ranked.append(row)
        ranked.sort(key=lambda r: (round(r["predicted_ms"], 4), r["name"]))
        # Refused plans stay visible at the bottom of the sidecar table,
        # never silently absent.
        ranked.extend(sorted(mem_refused,
                             key=lambda r: (round(r["predicted_ms"], 4),
                                            r["name"])))
        # The fallback contract on the re-priced objective: the winner
        # must clear the DP base by MIN_GAIN_PCT, and a composed winner
        # must additionally clear the best single-axis plan by the same
        # bar (automap_search.select_candidate — refused rows excluded).
        live = [automap_search.PlanCandidate(
                    r["name"], r["plan"], r["predicted_ms"], None)
                for r in ranked if not r.get("mem_refusal")]
        winner = automap_search.select_candidate(live)
        chosen = next(r for r in ranked if r["name"] == winner.name)
        strategy = chosen["strategy"]
        search_ms = (time.perf_counter() - t0) * 1e3
        outcome = outcome._replace(search_ms=search_ms)
        result = AutomapResult(chosen["name"],
                               base_result.chosen["name"], ranked, outcome,
                               topo, plan_fingerprint(strategy))
        set_last_result(result)
        write_sidecar(result, strategy.id)
        observability.record_event(
            "automap", f"{chosen['name']} over base "
            f"{base_result.chosen['name']} "
            f"({chosen['predicted_ms']:.4f}ms predicted, "
            f"{len(ranked)}/{result.outcome.space_size} mesh candidates, "
            f"search {search_ms:.1f}ms)")
        if observability.enabled():
            reg = observability.registry()
            reg.gauge("automap.search_ms").set(round(search_ms, 3))
            reg.gauge("automap.sharded_vars").set(
                len(chosen["plan"].sharded) if chosen["plan"] else 0)
            plan = chosen["plan"]
            reg.gauge("automap.mesh_axes").set(
                len(plan.axes) if plan is not None else 0)
            reg.gauge("automap.composed").set(
                1 if plan is not None and plan.composed else 0)
            reg.gauge("automap.placement_ici").set(
                1 if plan is not None and all(
                    t == "ici" for t in plan.placement.values())
                and plan.placement else 0)
            reg.gauge("automap.pipeline_stages").set(
                int(plan.pipeline["stages"])
                if plan is not None and plan.pipeline else 0)
        logging.info("Automap: %s (base %s, predicted %.4fms/step, "
                     "fingerprint %s)", chosen["name"],
                     base_result.chosen["name"], chosen["predicted_ms"],
                     result.fingerprint)
        return strategy
