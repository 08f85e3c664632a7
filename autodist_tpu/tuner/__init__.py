"""Strategy autotuner: cost-model-driven automatic strategy selection.

Given a captured :class:`~autodist_tpu.graph_item.GraphItem` and a
:class:`~autodist_tpu.resource_spec.ResourceSpec`, the tuner enumerates
candidate strategies from the builder zoo (crossed with their tunable
knobs), ranks them with an analytic cost model over the interconnect
topology, and exposes the argmin as the :class:`AutoStrategy` builder —
``AUTODIST_STRATEGY=auto`` end to end.  See docs/tuning.md.

* :mod:`~autodist_tpu.tuner.cost_model` — hierarchical-ring collective +
  compute + update costs, ICI/DCN tier aware;
* :mod:`~autodist_tpu.tuner.search` — deterministic candidate
  enumeration, legality pruning, budgeted ranking
  (``AUTODIST_TUNER_BUDGET``);
* :mod:`~autodist_tpu.tuner.calibration` — persisted refinement of the
  cost constants from measured step times and opt-in micro-probes.
"""
from autodist_tpu.tuner.auto import (AutoStrategy, builder_from_name,
                                     last_result, record_measurement,
                                     set_last_result)
from autodist_tpu.tuner.calibration import Calibration
from autodist_tpu.tuner.cost_model import CostModel, Topology
from autodist_tpu.tuner.search import (CANDIDATE_FAMILIES, OBJECTIVES,
                                       TuningResult, enumerate_candidates,
                                       resolve_objective, search,
                                       sidecar_path, write_sidecar)

__all__ = [
    "AutoStrategy", "builder_from_name", "last_result",
    "record_measurement", "set_last_result",
    "Calibration",
    "CostModel", "Topology",
    "CANDIDATE_FAMILIES", "OBJECTIVES", "TuningResult",
    "enumerate_candidates", "resolve_objective", "search",
    "sidecar_path", "write_sidecar",
]
