"""Reshape-on-degrade: evict a persistently degraded host, priced
(docs/retuning.md).

A host that is slow-but-alive is worse than a dead one: it answers every
barrier, so elastic supervision never fires, yet in an SPMD job its drag
is the whole fleet's step time.  This module closes the remaining loop:
the monitor's skew-decomposed straggler *verdict* (observability/skew.py
-> monitor.AnomalyDetector) feeds a :class:`SelfHealer` that

1. holds the verdict against hysteresis — the SAME host must stay the
   significant straggler for ``AUTODIST_SELFHEAL_PATIENCE`` consecutive
   cluster-sync rounds, so a transient blip (GC pause, one slow batch)
   never evicts a host;
2. pokes the re-tuning controller for an out-of-cadence evaluation the
   moment a verdict appears (a knob/strategy switch may absorb a mild
   regime shift without any eviction);
3. prices the eviction with the same amortization discipline the
   controller uses, against run-level goodput: per-step saving =
   ``p50 - (p50 - drag) * w/(w-1)`` (the verdict's skew-wait is the
   drag; shrinking costs ``w/(w-1)`` more compute per device), and the
   payoff over the remaining steps must exceed the re-exec downtime —
   preferring the run's own MEASURED priced downtime
   (:func:`~autodist_tpu.observability.goodput.priced_downtime`) over a
   static estimate;
4. on a paid decision, carves the incumbent strategy down to the
   surviving hosts' devices, serializes it, pins it on the coordinator
   (``AUTODIST_STRATEGY_ID`` through the re-exec env), and requests the
   re-form — the checkpoint loop's existing ``reform_pending`` poll
   drains through emergency-save into ``reform_now`` (docs/elasticity.md),
   and the goodput stitcher bills the whole episode as ``selfheal_ms``.

Zero-call contract: without ``AUTODIST_RETUNE`` + telemetry +
``AUTODIST_SELFHEAL`` (and a bound coordinator), nothing here is ever
constructed and the monitor path makes zero selfheal calls.
"""
import time

from autodist_tpu import const, observability
from autodist_tpu.utils import logging

_healer = None
#: Remaining steps assumed for the shrink payoff while the step loop has
#: not reported progress yet.
HORIZON = 1000


def enabled():
    """Self-healing needs the re-tuning stack on AND its own knob."""
    from autodist_tpu.retune import controller
    return bool(const.ENV.AUTODIST_SELFHEAL.val) and controller.enabled()


def healer():
    """The process-global healer (``None`` when no checkpoint loop with a
    coordinator is live)."""
    return _healer


def reset():
    """Test harness hook."""
    global _healer
    _healer = None


def bind(manager, coordinator):
    """Install a healer for one checkpoint-managed step loop (chief-side;
    called by ``CheckpointManager.run``).  Returns the healer, or ``None``
    when self-healing cannot act (disabled, or no coordinator to reshape
    through)."""
    global _healer
    if coordinator is None or not enabled():
        _healer = None
        return None
    _healer = SelfHealer(manager, coordinator)
    return _healer


def note_progress(step, num_steps, p50_ms):
    """Cheap step-loop hook: the healer's view of where the run is (for
    remaining-steps pricing) and how fast it currently goes."""
    if _healer is not None:
        _healer.note_progress(step, num_steps, p50_ms)


def note_anomalies(detector, now=None):
    """Monitor hook (``observe_cluster``): fold this sync round's active
    anomaly set into the eviction hysteresis.  Fail-open."""
    if _healer is None:
        return
    try:
        _healer.note_anomalies(detector, now=now)
    except Exception as e:  # noqa: BLE001 - healing must never kill a run
        logging.debug("selfheal round skipped: %s", e)


class SelfHealer:
    """Hysteresis + pricing around the degraded-host eviction decision."""

    def __init__(self, manager, coordinator):
        self._manager = manager
        self._coordinator = coordinator
        self.patience = max(1, int(const.ENV.AUTODIST_SELFHEAL_PATIENCE.val))
        self._streak_host = None
        self._streak = 0
        self._first_degraded_ts = None
        self._step = 0
        self._num_steps = 0
        self._p50_ms = None
        self._refused = set()       # hosts whose eviction did not pay
        self.decisions = []         # completed eviction records

    def note_progress(self, step, num_steps, p50_ms):
        self._step = int(step)
        self._num_steps = int(num_steps)
        if p50_ms:
            self._p50_ms = float(p50_ms)

    # -- hysteresis ----------------------------------------------------------

    def note_anomalies(self, detector, now=None):
        now = time.time() if now is None else now
        hosts = sorted(h for (kind, h) in
                       getattr(detector, "_active", {}) if kind == "straggler")
        if not hosts:
            # Verdict cleared: whatever streak existed was a blip.
            self._streak_host, self._streak = None, 0
            self._first_degraded_ts = None
            return
        host = hosts[0]
        if host == self._streak_host:
            self._streak += 1
        else:
            self._streak_host, self._streak = host, 1
            self._first_degraded_ts = now
        # A fresh verdict is a regime change the controller should see
        # NOW, not at the next scheduled window — maybe a knob/strategy
        # switch absorbs it without evicting anyone.
        try:
            from autodist_tpu.retune import controller
            ctl = controller.last_controller()
            if ctl is not None:
                ctl.request_evaluation(f"straggler verdict for host {host}")
        except Exception as e:  # noqa: BLE001
            logging.debug("selfheal: controller poke failed: %s", e)
        if self._streak >= self.patience:
            self._maybe_evict(host, now)

    # -- pricing -------------------------------------------------------------

    def _drag_ms(self):
        """The degraded host's per-step drag: the skew decomposition's
        straggler wait (what everyone else spends waiting on it)."""
        try:
            from autodist_tpu.observability import skew
            verdict = (skew.last_summary() or {}).get("straggler") or {}
            return max(0.0, float(verdict.get("cause_ms") or 0.0)), \
                str(verdict.get("cause") or "unknown")
        except Exception:  # noqa: BLE001
            return 0.0, "unknown"

    def _reexec_cost_ms(self):
        """Estimated eviction downtime: the run's own measured re-exec
        episodes when it has any, else compile-scaled static."""
        try:
            from autodist_tpu.observability import goodput
            measured = goodput.priced_downtime().get("reexec_ms")
            if measured:
                return float(measured)
        except Exception:  # noqa: BLE001
            pass
        compile_ms = 500.0
        try:
            snap = observability.registry().snapshot()
            compile_ms = float((snap.get("gauges") or {}).get("compile.ms")
                               or compile_ms)
        except Exception:  # noqa: BLE001
            pass
        # Relaunch + restore + full recompile: conservatively 3x the
        # in-place switch estimate.
        return 3.0 * (1.5 * compile_ms) + 1000.0

    def _maybe_evict(self, host, now):
        co = self._coordinator
        if co is None or getattr(co, "reform_pending", False):
            return
        w = int(getattr(co, "world_size", 1) or 1)
        if w <= 1:
            return  # nobody left to reshape around
        cur = self._p50_ms
        if not cur or cur <= 0:
            return  # no measured window yet — nothing to price against
        drag, cause = self._drag_ms()
        drag = min(drag, 0.9 * cur)
        new_ms = (cur - drag) * w / (w - 1.0)
        saving = cur - new_ms
        remaining = self._num_steps - self._step
        if remaining <= 0:
            remaining = HORIZON
        payoff_ms = saving * remaining
        cost_ms = self._reexec_cost_ms()
        if saving <= 0 or payoff_ms <= cost_ms:
            if host not in self._refused:
                self._refused.add(host)
                observability.record_event(
                    "selfheal",
                    f"refused evicting degraded host {host}: per-step "
                    f"saving {saving:.3f}ms x {remaining} remaining steps "
                    f"= {max(0.0, payoff_ms):.0f}ms does not cover the "
                    f"estimated {cost_ms:.0f}ms re-exec downtime",
                    decision="refused", host=host,
                    payoff_ms=round(payoff_ms, 1),
                    reexec_cost_ms=round(cost_ms, 1))
            return
        challenger_id = None
        try:
            challenger_id = self._shrink_challenger(w)
            if challenger_id:
                co.pin_strategy(challenger_id)
        except Exception as e:  # noqa: BLE001 - the relaunch can still
            # re-tune from scratch; the eviction itself is the healing.
            logging.warning("selfheal: shrink challenger not pinned "
                            "(relaunch re-plans): %s", e)
        decided_ms = None
        if self._first_degraded_ts is not None:
            decided_ms = round((now - self._first_degraded_ts) * 1e3, 3)
        reg = observability.registry()
        reg.counter("selfheal.decisions").inc()
        if decided_ms is not None:
            reg.gauge("selfheal.degrade_to_decision_ms").set(decided_ms)
        record = {
            "decision": "evict",
            "host": host, "cause": cause, "world": w, "new_world": w - 1,
            "step": self._step,
            "before_p50_ms": round(cur, 5),
            "predicted_p50_ms": round(new_ms, 5),
            "saving_ms_per_step": round(saving, 5),
            "payoff_ms": round(payoff_ms, 1),
            "reexec_cost_ms": round(cost_ms, 1),
            "degrade_to_decision_ms": decided_ms,
            "pinned_strategy_id": challenger_id,
        }
        self.decisions.append(record)
        observability.record_event(
            "selfheal",
            f"evicting degraded host {host} ({cause}): shrink {w} -> "
            f"{w - 1}, predicted {cur:.3f} -> {new_ms:.3f} ms/step; "
            f"payoff {payoff_ms:.0f}ms over {remaining} steps vs "
            f"{cost_ms:.0f}ms re-exec downtime"
            + (f"; decided {decided_ms:.0f}ms after degradation onset"
               if decided_ms is not None else ""),
            **record)
        co.request_reform(w - 1,
                          reason=f"selfheal: degraded host {host} ({cause})")
        self._streak_host, self._streak = None, 0
        self._first_degraded_ts = None

    # -- shrink challenger ---------------------------------------------------

    def _shrink_challenger(self, w):
        """Serialize the incumbent strategy re-carved for the surviving
        ``w - 1`` hosts' devices and return its id (the
        ``AUTODIST_STRATEGY_ID`` pin for the re-exec'd generation)."""
        from autodist_tpu.proto import strategy_pb2
        from autodist_tpu.strategy.base import Strategy
        runner = self._manager._runner
        incumbent = runner.program.strategy
        total = int(runner.program.mesh.devices.size)
        per_host = max(1, total // w)
        new_n = per_host * (w - 1)
        proto = strategy_pb2.Strategy()
        proto.CopyFrom(incumbent.proto)
        proto.id = ""    # fresh id: never overwrite the incumbent artifact
        proto.path = ""
        challenger = Strategy(proto)
        axes = dict(challenger.graph_config.mesh_axes)
        other = 1
        for name, sz in axes.items():
            if name != const.MESH_AXIS_DATA:
                other *= max(1, int(sz))
        if new_n % other != 0:
            # The model/pipeline axes don't survive the shrink — fall
            # back to pure data parallelism over what remains.
            axes = {const.MESH_AXIS_DATA: new_n}
        else:
            axes[const.MESH_AXIS_DATA] = new_n // other
        challenger.graph_config.mesh_axes.clear()
        for name, sz in axes.items():
            challenger.graph_config.mesh_axes[name] = int(sz)
        challenger.serialize()
        logging.info("selfheal: pinned shrink challenger %s (mesh %s over "
                     "%d devices)", challenger.id, axes, new_n)
        return challenger.id

    # -- surfaces ------------------------------------------------------------

    def status(self):
        return {
            "patience": self.patience,
            "streak_host": self._streak_host,
            "streak": self._streak,
            "decisions": list(self.decisions),
        }
