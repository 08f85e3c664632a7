"""Online re-tuning: close the loop from monitor to strategy, mid-run.

Every piece of a control loop exists elsewhere in the stack — the
monitor detects regime changes, per-term/per-scope calibration
continuously re-fits the cost model, and ``reshard_state`` can re-lay-out
a live TrainState value-exact onto a new plan — and before this module
none of them talked: a long run inherited its launch-time plan forever.

The :class:`~autodist_tpu.retune.controller.Controller` is the missing
edge (docs/retuning.md).  Evaluated on the observed step loop's existing
flush cadence, it re-prices the tuner's candidate set **and** the
incumbent's exec-knob grid (unroll, AR bucket MB, pipeline microbatches)
under the *current* :class:`~autodist_tpu.tuner.calibration.Calibration`,
and when a
challenger beats the incumbent's *measured* step time by more than the
hysteresis margin (``controller.MARGIN_PCT``) for
``AUTODIST_RETUNE_PATIENCE`` consecutive windows, switches in place at a
megastep boundary:

* **tier 1 — exec-knob switches** (``AUTODIST_RETUNE=exec``): same
  strategy, same layout, state untouched on device; the step is simply
  re-lowered/re-compiled with the new knobs;
* **tier 2 — strategy switches** (``AUTODIST_RETUNE=1``/``full``): the
  program re-transforms under the challenger strategy and the live state
  routes through the elastic ``reshard_state`` path (host-numpy
  round-trip — no checkpoint, no re-exec), value-exact.

Every switch records a ``retune`` flight event with before/after
attribution ledgers; switch downtime (recompile + reshard) is charged to
the ``retune_switch_ms`` goodput badput class so the controller's own
cost stays visible, and switches whose amortized payoff over the
remaining steps is negative are refused — preferring the run's own
measured priced downtime over static estimates.

Multi-process jobs ship the chief's per-window verdict over the
coordination-service KV channel (retune/shipping.py): workers run a
:class:`~autodist_tpu.retune.controller.FollowerController` that adopts
the shipped decision at the same megastep boundary, fingerprint-checked
— a mismatch refuses the switch loudly instead of splitting the fleet.
A tier-2 challenger on DIFFERENT mesh axes is a *reshape* switch
(offered when an elastic Coordinator is bound): pinned via
``AUTODIST_STRATEGY_ID`` and executed through the emergency-save +
re-exec episode.  retune/selfheal.py closes the remaining loop — a
persistently degraded host (the monitor's skew-decomposed straggler
verdict, held against hysteresis) provokes a priced shrink-and-reshape-
around-it decision optimizing stitched run-level goodput.

Zero-call contract: with ``AUTODIST_RETUNE`` unset/0 (the default) or
``AUTODIST_TELEMETRY=0``, the step loop never constructs a controller —
no re-pricing passes, no events, no gauges (spy-pinned).
"""
from autodist_tpu.retune.controller import (Controller, Decision,
                                            FollowerController,
                                            bind_coordinator,
                                            bound_coordinator,
                                            controller_for, enabled,
                                            last_controller, mode, reset,
                                            status_section)

__all__ = [
    "Controller", "Decision", "FollowerController", "bind_coordinator",
    "bound_coordinator", "controller_for", "enabled", "last_controller",
    "mode", "reset", "status_section",
]
