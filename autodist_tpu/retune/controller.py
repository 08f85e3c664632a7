"""Chief-side online re-tuning controller (docs/retuning.md).

The controller closes the monitor -> calibration -> strategy loop
mid-run.  It is created by the observed step loops (telemetry on,
``AUTODIST_RETUNE`` set) and consulted on the existing flush/StepGuard
cadence — on a multi-process job the chief's verdict ships to every
worker over the coordination-service KV channel (retune/shipping.py) so
all processes switch at the same megastep boundary, and each worker runs
a :class:`FollowerController` that adopts rather than evaluates.  Every
evaluation window the chief:

1. re-prices the incumbent program and its exec-knob grid (unroll x
   AR bucket x microbatches, ``tuner.search.reprice``) plus —
   in ``full`` mode — every mesh-compatible candidate strategy from the
   tuner's last ranking, all under the CURRENT persisted
   :class:`~autodist_tpu.tuner.calibration.Calibration` (term scales,
   ``profile:<scope>`` scales, link overrides, a measured
   host-dispatch floor);
2. anchors predictions to reality: a challenger's estimated step time is
   ``measured_p50 * predicted(challenger) / predicted(incumbent)`` — the
   incumbent's measured window p50 is the scale, so only the *ratio* of
   model predictions matters;
3. applies hysteresis: the challenger must beat the measured incumbent
   by more than :data:`MARGIN_PCT` for
   ``AUTODIST_RETUNE_PATIENCE`` consecutive windows (the streak resets
   when the best challenger changes or the measured regime flips), so
   two candidates inside the margin can never ping-pong;
4. refuses switches whose amortized payoff is negative: estimated
   per-step saving x remaining steps must exceed the estimated switch
   downtime (recompile, plus the reshard round-trip for tier 2);
5. on a qualified decision, switches at the megastep boundary — tier 1
   re-lowers with new exec knobs (state untouched on device), tier 2
   re-transforms and routes the live state through the elastic
   ``reshard_state`` path, and a tier-2 challenger on DIFFERENT mesh
   axes (``reshape``, offered only when an elastic Coordinator is bound)
   is pinned via ``AUTODIST_STRATEGY_ID`` and executed through the
   emergency-save + re-exec episode — and records a ``retune`` flight
   event with before/after attribution ledgers once the first
   post-switch window lands.

The monitor's straggler/anomaly verdicts can additionally request an
out-of-cadence evaluation (:meth:`Controller.request_evaluation`) so a
regime change is priced at the very next megastep boundary; the
degraded-host eviction path itself lives in retune/selfheal.py.

Cost discipline: everything here runs on the flush cadence (never per
step); a full evaluation is pure cost-model arithmetic over already-
built strategies.  Fail-open: a controller error degrades to "no
switch", never to a dead run.
"""
import time
from types import SimpleNamespace
from typing import NamedTuple

import numpy as np

from autodist_tpu import const, observability
from autodist_tpu.utils import logging

#: Windows whose measured p50 moves more than this factor x the margin
#: relative to the previous window count as a regime flip (patience
#: resets: pre-flip evidence is stale).  2x the switch margin: window
#: p50s jitter, and a flip threshold at the margin itself would reset
#: patience on noise alone.
_REGIME_FLIP_FACTOR = 2.0
#: Hysteresis: a challenger must beat the incumbent's measured step time by
#: more than this many percent before a switch is considered.
MARGIN_PCT = 10.0


def _search_module():
    """The ``tuner.search`` MODULE (the package re-exports a ``search``
    *function* under the same name, so a plain ``from ... import search``
    would grab the callable)."""
    import importlib
    return importlib.import_module("autodist_tpu.tuner.search")


def enabled():
    """Whether the online re-tuning controller may run at all: an
    ``AUTODIST_RETUNE`` mode is set AND telemetry is on (the controller
    needs measured windows; ``AUTODIST_TELEMETRY=0`` keeps the zero-call
    contract)."""
    raw = str(const.ENV.AUTODIST_RETUNE.val or "").strip().lower()
    if raw in ("", "0", "false", "no", "off"):
        return False
    return observability.enabled()


def mode():
    """``"exec"`` (tier-1 exec-knob switches only) or ``"full"`` (exec
    knobs AND live strategy switches through ``reshard_state``)."""
    raw = str(const.ENV.AUTODIST_RETUNE.val or "").strip().lower()
    return "exec" if raw == "exec" else "full"


_last_controller = None
_coordinator = None
_declined_once = False


def last_controller():
    """The most recent controller in this process (report/monitor
    surface); ``None`` before the first retune-enabled observed loop."""
    return _last_controller


def bind_coordinator(coordinator):
    """Attach the elastic Coordinator (chief-side, set by the
    checkpoint-managed step loop).  With one bound, tier-2 candidates on
    DIFFERENT mesh axes stay in the challenger set as *reshape* switches
    — executed through emergency-save + re-exec with the challenger
    pinned (``AUTODIST_STRATEGY_ID``) instead of an in-place transform.
    Without one, reshape candidates are excluded as before (an in-place
    mesh reshape is impossible)."""
    global _coordinator
    _coordinator = coordinator
    return coordinator


def bound_coordinator():
    return _coordinator


def reset():
    """Test harness hook."""
    global _last_controller, _coordinator, _declined_once
    _last_controller = None
    _coordinator = None
    _declined_once = False


def controller_for(runner, unroll=1, allow_unroll=True):
    """Resolve a fresh controller for one observed step loop, or ``None``
    when this process cannot re-tune.

    Single-process: the full :class:`Controller`.  Multi-process with a
    coordination-service KV byte channel: the chief gets a publishing
    :class:`Controller` and every worker a :class:`FollowerController` —
    the chief's per-window verdict ships over the KV store
    (retune/shipping.py) so all processes switch at the same megastep
    boundary.  Multi-process WITHOUT the channel is declined: the
    warning logs once per process and every declined resolution bumps
    the ``retune.declined`` counter."""
    global _last_controller, _declined_once
    pidx, pcount = 0, 1
    try:
        import jax
        pidx, pcount = jax.process_index(), jax.process_count()
    except Exception:  # noqa: BLE001 - backend not initialized: chief
        pass
    channel = None
    if pcount > 1:
        try:
            from autodist_tpu.retune import shipping
            channel = shipping.channel()
        except Exception as e:  # noqa: BLE001
            logging.debug("retune shipping channel unavailable: %s", e)
        if channel is None:
            try:
                observability.registry().counter("retune.declined").inc()
            except Exception:  # noqa: BLE001 - counter is best-effort
                pass
            if not _declined_once:
                _declined_once = True
                logging.warning(
                    "AUTODIST_RETUNE is set but this %d-process job has no "
                    "coordination-service KV byte channel to ship decisions "
                    "over — controller disabled (SPMD-symmetric switching "
                    "needs it; docs/retuning.md)", pcount)
            return None
    if pidx != 0:
        ctl = FollowerController(runner, unroll=unroll,
                                 allow_unroll=allow_unroll, channel=channel)
    else:
        ctl = Controller(runner, unroll=unroll, allow_unroll=allow_unroll,
                         channel=channel)
    _last_controller = ctl
    return ctl


class Decision(NamedTuple):
    """A qualified switch the step loop applies at the next megastep
    boundary."""
    tier: int            # 1 = exec knobs only, 2 = strategy switch
    label: str           # challenger label (candidate name + knobs)
    knobs: dict          # {"unroll", "bucket_mb", "microbatches"}
    strategy: object     # built Strategy for tier 2, else None
    strategy_name: str   # candidate name for tier 2, else "" (incumbent)
    predicted_ms: float  # challenger predicted step time (calibrated)
    incumbent_predicted_ms: float
    measured_ms: float   # incumbent measured window p50 at decision time
    margin_pct: float    # predicted improvement over the incumbent
    remaining_steps: int
    reshape: bool = False  # challenger lives on DIFFERENT mesh axes: the
                           # switch rides emergency-save + elastic
                           # re-exec with the challenger pinned, not an
                           # in-place transform


class Controller:
    """Evaluates challengers on the flush cadence and applies switches."""

    def __init__(self, runner, unroll=1, allow_unroll=True, channel=None):
        self._runner = runner
        self._channel = channel  # decision-shipping channel (multi-process)
        self._eval_requested = None  # out-of-cadence evaluation reason
        self._allow_unroll = bool(allow_unroll)
        self._mode = mode()
        self.margin_pct = MARGIN_PCT
        self.patience = max(1, int(const.ENV.AUTODIST_RETUNE_PATIENCE.val))
        gc = runner.program.strategy.graph_config
        self._knobs = {
            "unroll": max(1, int(unroll)),
            "bucket_mb": max(0, int(const.ENV.AUTODIST_AR_BUCKET_MB.val)),
            "microbatches": int(gc.pipeline_microbatches or 0),
        }
        self._strategy_name = self._incumbent_name()
        self._candidates = None     # lazy [(name, Strategy)] for tier 2
        self._streak_label = None
        self._streak = 0
        self._last_measured = None
        self._pending = None        # switch record awaiting its "after"
        self._refused = set()       # labels already refused (event spam)
        self.windows = 0
        self.evaluations = 0
        self.ooc_evaluations = 0
        self.regime_flips = 0
        self.refusals = 0
        self.eval_ms = 0.0
        self.last_margin_pct = None
        self.last_best_label = None
        self.switches = []          # completed switch records

    # -- out-of-cadence requests --------------------------------------------

    def request_evaluation(self, reason=""):
        """Ask for an evaluation at the NEXT megastep boundary instead of
        waiting for the flush cadence — the monitor's regime/straggler
        verdicts call this so a degradation is priced within one
        boundary, not one window.  Declined (returns ``False``) on a
        shipped multi-process job: the verdict sequence must stay
        SPMD-symmetric, and the fleet-wide regime response (reshape /
        selfheal re-exec) needs no early window."""
        if self._channel is not None:
            return False
        self._eval_requested = reason or "requested"
        logging.info("retune: out-of-cadence evaluation requested (%s)",
                     self._eval_requested)
        return True

    def eval_requested(self):
        """Whether the step loop should consult at the next boundary even
        off-cadence (cheap: one attribute read)."""
        return self._eval_requested is not None

    # -- incumbent bookkeeping ----------------------------------------------

    def _incumbent_name(self):
        try:
            from autodist_tpu import tuner
            result = tuner.last_result()
            if result is not None and result.chosen_strategy is not None \
                    and result.chosen_strategy.id == \
                    getattr(self._runner.program.strategy, "id", None):
                return result.chosen["name"]
        except Exception:  # noqa: BLE001 - cosmetic
            pass
        return getattr(self._runner.program.strategy, "id", "incumbent")

    def _state_mb(self):
        """Rough live-state footprint (params + grads + optimizer) for the
        tier-2 switch-cost estimate."""
        try:
            return 3.0 * sum(v.size_bytes for v in
                             self._runner.program.graph_item.variables) / 1e6
        except Exception:  # noqa: BLE001
            return 0.0

    def _switch_cost_estimate(self, tier, reshape=False):
        """Estimated switch downtime (ms) — the number the amortization
        refusal compares against payoff x remaining steps.  The run's own
        MEASURED priced downtime (the goodput ledger's per-switch
        ``retune_switch_ms`` / per-episode re-exec cost,
        :func:`~autodist_tpu.observability.goodput.priced_downtime`)
        takes precedence; the static model — re-lower/re-compile scaled
        from this program's measured compile, plus the reshard round-trip
        for tier 2, tripled plus relaunch overhead for a reshape — only
        prices the switches the run has not yet paid for once."""
        priced = {}
        try:
            from autodist_tpu.observability import goodput
            priced = goodput.priced_downtime()
        except Exception:  # noqa: BLE001 - fall through to the static model
            pass
        measured = priced.get("reexec_ms" if reshape else "retune_switch_ms")
        if measured:
            return float(measured)
        compile_ms = 500.0
        try:
            snap = observability.registry().snapshot()
            compile_ms = float((snap.get("gauges") or {}).get("compile.ms")
                               or compile_ms)
        except Exception:  # noqa: BLE001
            pass
        cost = 1.5 * compile_ms
        if tier == 2:
            # Host-numpy round-trip + re-placement: ~10 GB/s effective.
            cost += max(10.0, self._state_mb() * 0.2)
        if reshape:
            # Emergency-save + process relaunch + restore + full
            # recompile: conservatively 3x the in-place estimate plus a
            # fixed relaunch floor.
            cost = 3.0 * cost + 1000.0
        return cost

    # -- candidate set -------------------------------------------------------

    def _tier2_candidates(self):
        """Already-built challenger strategies as ``(name, strategy,
        reshape)`` triples.  Source: the tuner's last ranking when this
        process tuned (the rows carry built Strategy objects); otherwise
        ONE lazy budgeted search on first use (explicitly-built
        incumbents re-enter the search the tuner never ran).  Candidates
        whose mesh axes differ from the live mesh are ``reshape=True``
        when an elastic Coordinator is bound — their switch path is
        emergency-save + re-exec with the challenger pinned
        (docs/elasticity.md) instead of an in-place transform — and
        excluded otherwise (reshaping the device mesh in place is
        impossible)."""
        if self._mode != "full":
            return []
        if self._candidates is not None:
            return self._candidates
        rows = None
        try:
            from autodist_tpu import tuner
            result = tuner.last_result()
            if result is not None:
                rows = [(r["name"], r["strategy"]) for r in result.ranked]
        except Exception as e:  # noqa: BLE001
            logging.debug("retune: tuner ranking unavailable: %s", e)
        if rows is None:
            try:
                from autodist_tpu import tuner
                from autodist_tpu.resource_spec import ResourceSpec
                result = tuner.search(self._runner.program.graph_item,
                                      ResourceSpec(None))
                rows = [(r["name"], r["strategy"]) for r in result.ranked]
                logging.info("retune: search re-entry ranked %d candidates",
                             len(rows))
            except Exception as e:  # noqa: BLE001 - tier 1 still works
                logging.warning("retune: search re-entry failed (exec-knob "
                                "switches only): %s", e)
                rows = []
        live = {str(k): int(v)
                for k, v in self._runner.program.mesh.shape.items()}
        n = max(1, int(np.prod(list(live.values())) if live else 1))
        reshapeable = bound_coordinator() is not None
        out = []
        for name, strategy in rows:
            want = {str(k): int(v)
                    for k, v in dict(strategy.graph_config.mesh_axes).items()}
            if not want:
                want = {const.MESH_AXIS_DATA: n}
            if want == live:
                out.append((name, strategy, False))
            elif reshapeable and \
                    int(np.prod(list(want.values()))) == n:
                # Same device count, different axis carve: reachable
                # through the elastic re-exec path.
                out.append((name, strategy, True))
        self._candidates = out
        return out

    # -- evaluation ----------------------------------------------------------

    def _cost_model(self):
        """A cost model priced under the CURRENT persisted calibration —
        re-loaded every window, so mid-run re-fits (and persisted
        host-dispatch floors) take effect immediately."""
        import jax
        from autodist_tpu.tuner.calibration import Calibration
        from autodist_tpu.tuner.cost_model import CostModel, Topology
        cal = Calibration.load()
        try:
            hosts = max(1, jax.process_count())
        except Exception:  # noqa: BLE001
            hosts = 1
        mesh = self._runner.program.mesh
        n = max(1, int(mesh.devices.size))
        topo = Topology(n, num_hosts=hosts)
        return CostModel(topo, cal), cal

    def _allowed_unrolls(self, remaining_steps):
        search_mod = _search_module()
        cur = self._knobs["unroll"]
        if not self._allow_unroll:
            return (cur,)
        ks = sorted(set(search_mod.RETUNE_UNROLLS) | {cur})
        # No divisibility requirement: the step loop drains a ragged
        # tail as single steps.  A factor larger than what remains can
        # never dispatch, though — keep those out of the grid.
        return tuple(k for k in ks
                     if k == cur or k <= max(1, remaining_steps))

    def _priced_candidates(self, remaining_steps):
        """(incumbent_predicted_ms, challenger rows).  Each row is a
        ``reprice`` row extended with ``tier``/``strategy``/
        ``strategy_name``; deterministic order."""
        search_mod = _search_module()
        model, cal = self._cost_model()
        item = self._runner.program.graph_item
        host_ms = cal.host_dispatch_ms
        batch = int(item.batch_size or 0)
        kn = self._knobs
        inc = search_mod.reprice(
            self._runner.program.strategy, item, model,
            unrolls=(kn["unroll"],),
            variants=(("", {"bucket_bytes": kn["bucket_mb"] << 20,
                            "microbatches": kn["microbatches"] or None}),),
            host_dispatch_ms=host_ms, batch_size=batch)
        incumbent_pred = inc[0]["predicted_ms"]
        incumbent_knobs = inc[0]["knobs"]
        unrolls = self._allowed_unrolls(remaining_steps)
        rows = []
        for row in search_mod.reprice(self._runner.program.strategy, item,
                                      model, unrolls=unrolls,
                                      host_dispatch_ms=host_ms,
                                      batch_size=batch):
            if row["knobs"] == incumbent_knobs:
                continue  # the incumbent itself is not a challenger
            rows.append(dict(row, tier=1, strategy=None, strategy_name="",
                             reshape=False, label=f"exec:{row['label']}"))
        for name, strategy, reshape in self._tier2_candidates():
            if getattr(strategy, "id", None) == \
                    getattr(self._runner.program.strategy, "id", None):
                continue
            for row in search_mod.reprice(strategy, item, model,
                                          unrolls=unrolls,
                                          host_dispatch_ms=host_ms,
                                          batch_size=batch):
                rows.append(dict(row, tier=2, strategy=strategy,
                                 strategy_name=name, reshape=reshape,
                                 label=(f"reshape:{name}|{row['label']}"
                                        if reshape
                                        else f"{name}|{row['label']}")))
        rows.sort(key=lambda r: (round(r["predicted_ms"], 6), r["label"]))
        return incumbent_pred, rows

    def observe_window(self, measured_ms, remaining_steps, step=None,
                       after_attr=None):
        """Fold one evaluation window (the flush-cadence measured step
        p50); returns a :class:`Decision` when a switch qualified, else
        ``None``.  Called by the observed step loop at megastep
        boundaries only — a switch can never land mid-megastep.
        ``after_attr`` (the post-switch attribution summary, priced by
        the runner while a switch is pending) closes the switch record's
        AFTER ledger when the steady window lands.

        On a shipped multi-process job the chief publishes EVERY
        window's verdict over the KV channel — "hold" verdicts included,
        so worker fetches return promptly — and a failed publish holds
        the incumbent everywhere: a chief-only switch is exactly the
        fleet split the channel exists to prevent."""
        if self._eval_requested is not None:
            self.ooc_evaluations += 1
            self._eval_requested = None
        decision = self._evaluate_window(measured_ms, remaining_steps,
                                         step=step, after_attr=after_attr)
        if self._channel is None:
            return decision
        try:
            self._channel.publish(
                decision, boundary=-1 if step is None else int(step))
        except Exception as e:  # noqa: BLE001 - publish failure = no switch
            logging.warning("retune: verdict publish failed — holding the "
                            "incumbent (%s)", e)
            return None
        return decision

    def _evaluate_window(self, measured_ms, remaining_steps, step=None,
                         after_attr=None):
        self.windows += 1
        measured_ms = float(measured_ms)
        self._complete_pending(measured_ms, step=step,
                               after_attr=after_attr)
        # Regime flip: the measured incumbent moved by more than the
        # margin since the last window — whatever evidence a challenger
        # had accumulated belongs to the old regime.
        if self._last_measured:
            flip = self.margin_pct / 100.0 * _REGIME_FLIP_FACTOR
            ratio = measured_ms / max(1e-9, self._last_measured)
            if ratio > 1.0 + flip or ratio < 1.0 / (1.0 + flip):
                if self._streak:
                    logging.info(
                        "retune: regime flip (measured %.3f -> %.3f ms); "
                        "patience resets", self._last_measured, measured_ms)
                self.regime_flips += 1
                self._streak_label, self._streak = None, 0
        self._last_measured = measured_ms

        t0 = time.perf_counter()
        try:
            incumbent_pred, rows = self._priced_candidates(remaining_steps)
        finally:
            self.eval_ms += (time.perf_counter() - t0) * 1e3
        self.evaluations += 1
        if not rows or incumbent_pred <= 0:
            self._streak_label, self._streak = None, 0
            return None
        best = rows[0]
        margin = 100.0 * (1.0 - best["predicted_ms"] / incumbent_pred)
        self.last_margin_pct = round(margin, 3)
        self.last_best_label = best["label"]
        reg = observability.registry()
        reg.counter("retune.evaluations").inc()
        reg.gauge("retune.best_margin_pct").set(round(margin, 3))

        if margin <= self.margin_pct:
            # Hysteresis: nothing beats the incumbent by enough.  Two
            # candidates inside the margin therefore never ping-pong.
            self._streak_label, self._streak = None, 0
            return None
        if best["label"] == self._streak_label:
            self._streak += 1
        else:
            self._streak_label, self._streak = best["label"], 1
        if self._streak < self.patience:
            return None

        decision = Decision(
            tier=int(best["tier"]), label=best["label"],
            knobs=dict(best["knobs"]), strategy=best["strategy"],
            strategy_name=best["strategy_name"],
            predicted_ms=best["predicted_ms"],
            incumbent_predicted_ms=incumbent_pred,
            measured_ms=measured_ms, margin_pct=margin,
            remaining_steps=int(remaining_steps),
            reshape=bool(best.get("reshape", False)))
        # Amortization: estimated saving over the remaining steps must
        # pay for the switch downtime, else the switch refuses — the
        # controller's own cost stays visible AND bounded.
        payoff_ms = measured_ms * margin / 100.0 * max(0, remaining_steps)
        cost_ms = self._switch_cost_estimate(decision.tier,
                                             reshape=decision.reshape)
        if payoff_ms <= cost_ms:
            self.refusals += 1
            reg.counter("retune.refusals").inc()
            if best["label"] not in self._refused:
                self._refused.add(best["label"])
                observability.record_event(
                    "retune",
                    f"refused {best['label']}: amortized payoff "
                    f"{payoff_ms:.0f}ms over {remaining_steps} remaining "
                    f"steps does not cover the estimated "
                    f"{cost_ms:.0f}ms switch downtime",
                    decision="refused", label=best["label"], step=step,
                    payoff_ms=round(payoff_ms, 1),
                    switch_cost_ms=round(cost_ms, 1))
            return None
        return decision

    # -- switching -----------------------------------------------------------

    def apply(self, state, decision, before=None, step=None):
        """Execute a qualified switch at a megastep boundary; returns
        ``(state, new_unroll)``.  Tier 1 re-lowers with the new exec
        knobs (device state untouched); tier 2 re-transforms under the
        challenger strategy and reshards the live state value-exact
        (host-numpy round-trip — no checkpoint, no re-exec).  The
        ``retune`` flight event is emitted once the first post-switch
        window measures the payoff (:meth:`observe_window` /
        :meth:`finalize`).  A ``reshape`` decision takes neither path:
        the challenger is pinned on the bound Coordinator and the switch
        rides the elastic emergency-save + re-exec episode
        (:meth:`_apply_reshape`)."""
        if getattr(decision, "reshape", False):
            return self._apply_reshape(state, decision, step=step)
        runner = self._runner
        frm = {"strategy": self._strategy_name, **self._knobs}
        old_program = runner.program
        t0 = time.perf_counter()
        with observability.span("retune-switch", tier=decision.tier,
                                to=decision.label):
            try:
                if decision.strategy is not None:
                    from autodist_tpu.checkpoint.saver import \
                        reshard_live_state
                    from autodist_tpu.kernel.graph_transformer import \
                        GraphTransformer
                    from autodist_tpu.strategy.base import StrategyCompiler
                    mesh = runner.program.mesh
                    item = runner.program.graph_item
                    compiled = StrategyCompiler(item, mesh).compile(
                        decision.strategy)
                    program = GraphTransformer(
                        compiled, SimpleNamespace(mesh=mesh),
                        item).transform()
                    state = reshard_live_state(runner, state, program)
                    self._strategy_name = decision.strategy_name
                self._apply_exec_knobs(decision.knobs)
            except Exception:
                # A failed switch must leave the incumbent runnable: the
                # live state was never donated (to_logical/device_get are
                # read-only), so re-adopting the old program restores the
                # pre-switch world exactly.
                if runner.program is not old_program:
                    runner._adopt_program(old_program)
                raise
        switch_ms = (time.perf_counter() - t0) * 1e3
        reg = observability.registry()
        reg.counter("retune.switches").inc()
        reg.gauge("retune.last_switch_ms").set(round(switch_ms, 3))
        self._pending = {
            "_warmup": True,  # first post-switch window holds the
                              # recompile dispatch — not steady state
            "step": step,
            "tier": decision.tier,
            "frm": frm,
            "to": {"strategy": self._strategy_name, **self._knobs},
            "label": decision.label,
            "switch_ms": round(switch_ms, 3),
            "predicted_ms": round(decision.predicted_ms, 5),
            "incumbent_predicted_ms": round(
                decision.incumbent_predicted_ms, 5),
            "predicted_margin_pct": round(decision.margin_pct, 3),
            "before_p50_ms": round(decision.measured_ms, 5),
            "before_attribution": before,
            "after_p50_ms": None,
            "after_attribution": None,
            "payoff_pct": None,
        }
        self._streak_label, self._streak = None, 0
        self._refused.clear()
        self._last_measured = None  # post-switch window is a new regime
        logging.info("retune: switched to %s (tier %d) in %.0fms",
                     decision.label, decision.tier, switch_ms)
        return state, self._knobs["unroll"]

    def _apply_reshape(self, state, decision, step=None):
        """Reshape switch: the challenger lives on DIFFERENT mesh axes,
        so the "switch" is an elastic episode — serialize + pin the
        challenger on the bound Coordinator and request a same-world
        re-form; the checkpoint loop's ``reform_pending`` poll drains
        through emergency-save into ``reform_now``, and the re-exec'd
        generation starts under the pinned challenger
        (``AUTODIST_STRATEGY_ID``).  On a worker (no coordinator bound)
        this is a no-op: the chief's coordinator re-execs the whole
        fleet, this process included."""
        co = bound_coordinator()
        if co is None:
            logging.info("retune: reshape switch -> %s rides the chief's "
                         "elastic re-exec; holding until re-formed",
                         decision.label)
            return state, self._knobs["unroll"]
        if getattr(co, "reform_pending", False):
            return state, self._knobs["unroll"]
        sid = None
        if decision.strategy is not None:
            decision.strategy.serialize()
            sid = decision.strategy.id
            co.pin_strategy(sid)
        observability.registry().counter("retune.reshapes").inc()
        observability.record_event(
            "retune",
            f"reshape switch -> {decision.label} at step {step}: challenger "
            f"mesh axes differ from the live mesh; riding emergency-save + "
            f"elastic re-exec with strategy {sid} pinned (predicted "
            f"{decision.predicted_ms:.3f} vs incumbent "
            f"{decision.incumbent_predicted_ms:.3f} ms/step)",
            decision="reshape", label=decision.label, step=step,
            strategy_id=sid, tier=decision.tier,
            predicted_ms=round(decision.predicted_ms, 5),
            incumbent_predicted_ms=round(decision.incumbent_predicted_ms, 5),
            predicted_margin_pct=round(decision.margin_pct, 3))
        co.request_reform(
            int(getattr(co, "world_size", 1) or 1),
            reason=(f"selfheal: retune reshape -> "
                    f"{decision.strategy_name or decision.label}"))
        self._streak_label, self._streak = None, 0
        self._refused.clear()
        return state, self._knobs["unroll"]

    def _apply_exec_knobs(self, knobs):
        """Tier-1 half of every switch: move the runner (and the env
        contract later traces read) onto the new exec knobs and drop the
        compiled-step caches so the next dispatch re-lowers."""
        import os
        runner = self._runner
        bucket = int(knobs.get("bucket_mb") or 0)
        os.environ[const.ENV.AUTODIST_AR_BUCKET_MB.var_name] = str(bucket)
        mb = int(knobs.get("microbatches") or 0)
        if mb:
            runner.program.strategy.graph_config.pipeline_microbatches = mb
        unroll = max(1, int(knobs.get("unroll", self._knobs["unroll"])))
        if not self._allow_unroll:
            unroll = self._knobs["unroll"]
        self._knobs = {"unroll": unroll, "bucket_mb": bucket,
                       "microbatches": mb}
        runner._invalidate_compiled()

    # -- event closure -------------------------------------------------------

    def _complete_pending(self, after_p50_ms, step=None, after_attr=None):
        rec = self._pending
        if rec is None:
            return
        if rec.pop("_warmup", False) and after_p50_ms:
            # Skip the window that billed the switch's own recompile
            # dispatch: the payoff compares steady states, and the
            # downtime is already priced separately (switch_ms + the
            # retune_switch_ms badput class).
            return
        self._pending = None
        if after_p50_ms:
            rec["after_p50_ms"] = round(float(after_p50_ms), 5)
            rec["payoff_pct"] = round(
                100.0 * (rec["before_p50_ms"] - after_p50_ms)
                / max(1e-9, rec["before_p50_ms"]), 3)
            observability.registry().gauge("retune.payoff_pct").set(
                rec["payoff_pct"])
        if after_attr is not None:
            rec["after_attribution"] = after_attr
        self.switches.append(rec)
        payoff = (f"{rec['payoff_pct']:+.1f}% measured payoff"
                  if rec["payoff_pct"] is not None
                  else "payoff unmeasured (run ended)")
        observability.record_event(
            "retune",
            f"tier {rec['tier']} switch -> {rec['label']} at step "
            f"{rec['step']}: {rec['before_p50_ms']:.3f} -> "
            f"{rec['after_p50_ms'] or float('nan'):.3f} ms/step "
            f"({payoff}; {rec['switch_ms']:.0f}ms downtime)",
            **{k: rec[k] for k in
               ("step", "tier", "frm", "to", "label", "switch_ms",
                "predicted_ms", "incumbent_predicted_ms",
                "predicted_margin_pct", "before_p50_ms", "after_p50_ms",
                "payoff_pct", "before_attribution", "after_attribution")})

    def finalize(self, after_attr=None):
        """End-of-loop closure: emit any switch still awaiting its
        post-switch window (payoff stays unmeasured) and refresh the
        attribution attached to the last completed switch."""
        try:
            if self._pending is not None:
                if after_attr is not None:
                    self._pending["after_attribution"] = after_attr
                self._complete_pending(None)
            elif after_attr is not None and self.switches and \
                    self.switches[-1].get("after_attribution") is None:
                self.switches[-1]["after_attribution"] = after_attr
        except Exception as e:  # noqa: BLE001 - closure is best-effort
            logging.debug("retune finalize failed: %s", e)

    # -- surfaces ------------------------------------------------------------

    def status(self):
        """JSON-serializable controller state (monitor /status,
        report)."""
        return {
            "mode": self._mode,
            "role": ("follower" if isinstance(self, FollowerController)
                     else "chief" if self._channel is not None
                     else "single"),
            "shipping": self._channel is not None,
            "margin_pct": self.margin_pct,
            "patience": self.patience,
            "incumbent": {"strategy": self._strategy_name, **self._knobs},
            "windows": self.windows,
            "evaluations": self.evaluations,
            "ooc_evaluations": self.ooc_evaluations,
            "eval_ms": round(self.eval_ms, 3),
            "streak": self._streak,
            "streak_label": self._streak_label,
            "last_best_label": self.last_best_label,
            "last_margin_pct": self.last_margin_pct,
            "regime_flips": self.regime_flips,
            "refusals": self.refusals,
            "switches": list(self.switches),
            "pending_switch": (dict(self._pending)
                               if self._pending else None),
        }


class FollowerController(Controller):
    """Worker-side controller on a shipped multi-process job: never
    evaluates or prices anything — every window it fetches the chief's
    verdict from the KV channel, validates the fingerprint echo and the
    megastep boundary, and materializes the chief's decision against its
    OWN deterministic candidate set (candidate names resolve locally, so
    process-local strategy ids never cross the wire).  Any disagreement
    — fingerprint, boundary, or an unresolvable candidate — raises
    :class:`~autodist_tpu.retune.shipping.ShipMismatch`, which the step
    loop re-raises instead of swallowing: no switch happens anywhere,
    and the fleet never splits."""

    def observe_window(self, measured_ms, remaining_steps, step=None,
                       after_attr=None):
        self.windows += 1
        measured_ms = float(measured_ms)
        self._complete_pending(measured_ms, step=step, after_attr=after_attr)
        payload = self._channel.fetch(
            boundary=-1 if step is None else int(step))
        if not payload.get("switch"):
            return None
        return self._materialize(payload)

    def _materialize(self, payload):
        """Chief verdict payload -> local :class:`Decision`."""
        tier = int(payload.get("tier") or 1)
        name = str(payload.get("strategy_name") or "")
        reshape = bool(payload.get("reshape"))
        strategy = None
        if tier == 2 and not reshape:
            for cname, cstrat, creshape in self._tier2_candidates():
                if cname == name and not creshape:
                    strategy = cstrat
                    break
            if strategy is None:
                from autodist_tpu.retune import shipping
                raise shipping.ShipMismatch(
                    f"autodist_tpu: chief switched to tier-2 candidate "
                    f"{name!r} but this process cannot resolve it from its "
                    f"own candidate set — divergent tuner rankings; "
                    f"refusing the switch")
        return Decision(
            tier=tier, label=str(payload.get("label") or ""),
            knobs=dict(payload.get("knobs") or {}),
            strategy=strategy, strategy_name=name,
            predicted_ms=float(payload.get("predicted_ms") or 0.0),
            incumbent_predicted_ms=float(
                payload.get("incumbent_predicted_ms") or 0.0),
            measured_ms=float(payload.get("measured_ms") or 0.0),
            margin_pct=float(payload.get("margin_pct") or 0.0),
            remaining_steps=int(payload.get("remaining_steps") or 0),
            reshape=reshape)


def status_section():
    """Monitor ``/status`` retune section (``None`` when no controller
    ever ran in this process)."""
    ctl = last_controller()
    if ctl is None:
        return None
    st = ctl.status()
    # The monitor row keeps attribution ledgers out (they are large);
    # the flight event and the report carry the full record.
    st["switches"] = [
        {k: s.get(k) for k in ("step", "tier", "label", "switch_ms",
                               "before_p50_ms", "after_p50_ms",
                               "payoff_pct", "predicted_margin_pct")}
        for s in st["switches"]]
    st.pop("pending_switch", None)
    return st
