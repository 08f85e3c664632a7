"""Calibration: keep the analytic cost model honest against this cluster.

The cost model's bandwidth/latency constants are *seeds*.  Two refinement
paths converge on reality:

* **Measured steps** — the runner records predicted-vs-measured step time
  after every observed run (observability's ``step.latency_ms`` window);
  :meth:`Calibration.observe` folds the ratio into a bounded-history EMA
  ``scale`` that multiplies future predictions, so absolute predictions
  track this cluster even when the seeds are off by a constant factor.
* **Per-term attribution** — the step-time attribution ledger
  (``observability/attribution.py``) reconciles wall time into named
  causes and feeds :meth:`Calibration.observe_term` a measured value
  per *class*: ``compute`` (wall minus the measured/overhead terms vs
  the raw FLOPs+HBM roofline) and ``comms`` (the scheduled-HLO exposed
  collective time vs the raw sync estimate).  The per-term EMAs refine
  the global scale — the model learns WHICH term is wrong, not just a
  single fudge factor — via :attr:`compute_scale` / :attr:`comms_scale`,
  which the cost model applies per class.

A fitted per-dispatch host overhead persists as :attr:`host_dispatch_ms`
(nothing in the tree fits one today: ROADMAP D3) — the attribution ledger's
host-dispatch term reads it instead of the ``DISPATCH_MS`` seed.

State persists as JSON (default ``<working_dir>/tuner_calibration.json``,
override ``AUTODIST_TUNER_CALIBRATION``) so later processes — and later
*runs* — start from the refined constants.  Every filesystem touch is
fail-open: a read-only working dir degrades to in-memory calibration.
"""
import json
import os
import time

from autodist_tpu import const
from autodist_tpu.utils import logging

MAX_SAMPLES = 50
EMA_ALPHA = 0.3
# Clamp the EMA scale: a single wild measurement (cold caches, CI host
# contention) must not invert every future ranking.
SCALE_BOUNDS = (0.02, 50.0)


def default_path():
    return const.ENV.AUTODIST_TUNER_CALIBRATION.val or \
        os.path.join(const.DEFAULT_WORKING_DIR, "tuner_calibration.json")


class Calibration:
    """Persisted refinement state for the cost model."""

    def __init__(self, scale=1.0, samples=None, term_scales=None,
                 host_dispatch_ms=None, last_mfu=None, path=None):
        self.scale = float(scale)
        self.samples = list(samples or [])
        # Per-class refinement on top of the global scale (attribution
        # feedback): {"compute": ..., "comms": ...}.
        self.term_scales = {"compute": 1.0, "comms": 1.0,
                            **(term_scales or {})}
        # Measured per-dispatch host overhead (ms), where a caller
        # fitted one; None => the cost model's DISPATCH_MS seed.
        self.host_dispatch_ms = (float(host_dispatch_ms)
                                 if host_dispatch_ms else None)
        # Last run-level MFU from the goodput ledger (docs/goodput.md) —
        # a sanity anchor for the compute roofline: an MFU above 1 means
        # the peak table or the flops estimate is wrong, so the compute
        # scale the attribution loop is fitting cannot be trusted either.
        self.last_mfu = float(last_mfu) if last_mfu else None
        self.path = path or default_path()

    @property
    def compute_scale(self):
        """Effective multiplier for compute/update terms."""
        return self.scale * self.term_scales.get("compute", 1.0)

    @property
    def comms_scale(self):
        """Effective multiplier for collective/overlay terms."""
        return self.scale * self.term_scales.get("comms", 1.0)

    # -- persistence ---------------------------------------------------------

    @classmethod
    def load(cls, path=None):
        path = path or default_path()
        try:
            with open(path) as f:
                data = json.load(f)
            return cls(scale=data.get("scale", 1.0),
                       samples=data.get("samples", []),
                       term_scales=data.get("term_scales", {}),
                       host_dispatch_ms=data.get("host_dispatch_ms"),
                       last_mfu=data.get("last_mfu"),
                       path=path)
        except (OSError, ValueError):
            return cls(path=path)

    def save(self):
        try:
            os.makedirs(os.path.dirname(self.path), exist_ok=True)
            tmp = f"{self.path}.{os.getpid()}.tmp"
            with open(tmp, "w") as f:
                json.dump({"version": 2, "scale": round(self.scale, 6),
                           "term_scales": {k: round(v, 6) for k, v
                                           in self.term_scales.items()},
                           "host_dispatch_ms": self.host_dispatch_ms,
                           "last_mfu": self.last_mfu,
                           "samples": self.samples[-MAX_SAMPLES:]}, f,
                          indent=1)
            os.replace(tmp, self.path)
            return self.path
        except OSError as e:
            logging.debug("tuner calibration not persisted: %s", e)
            return None

    # -- refinement ----------------------------------------------------------

    def observe(self, predicted_ms, measured_ms, context=""):
        """Fold one predicted-vs-measured pair into the scale EMA."""
        if not predicted_ms or not measured_ms or predicted_ms <= 0 \
                or measured_ms <= 0:
            return self.scale
        ratio = measured_ms / predicted_ms
        lo, hi = SCALE_BOUNDS
        new = self.scale * (1 - EMA_ALPHA) + min(hi, max(lo, ratio)) * \
            EMA_ALPHA
        self.scale = min(hi, max(lo, new))
        self.samples.append({
            "t": int(time.time()),
            "predicted_ms": round(float(predicted_ms), 4),
            "measured_ms": round(float(measured_ms), 4),
            "error_pct": round(100.0 * (predicted_ms - measured_ms)
                               / measured_ms, 2),
            "context": str(context)[:120]})
        self.samples = self.samples[-MAX_SAMPLES:]
        self.save()
        return self.scale

    def observe_term(self, term, predicted_ms, measured_ms, context=""):
        """Fold one per-class predicted-vs-measured pair into that term's
        EMA (attribution feedback; independent of the other terms).

        ``predicted_ms`` is the RAW model term — the global scale is
        factored out of the ratio, so the term scale captures only the
        per-class error on top of the common-mode correction."""
        if not predicted_ms or not measured_ms or predicted_ms <= 0 \
                or measured_ms <= 0:
            return self.term_scales.get(term, 1.0)
        ratio = measured_ms / (predicted_ms * max(1e-9, self.scale))
        lo, hi = SCALE_BOUNDS
        cur = self.term_scales.get(term, 1.0)
        new = cur * (1 - EMA_ALPHA) + min(hi, max(lo, ratio)) * EMA_ALPHA
        self.term_scales[term] = min(hi, max(lo, new))
        self.samples.append({
            "t": int(time.time()),
            "term": str(term),
            "predicted_ms": round(float(predicted_ms), 4),
            "measured_ms": round(float(measured_ms), 4),
            "error_pct": round(100.0 * (predicted_ms - measured_ms)
                               / measured_ms, 2),
            "context": str(context)[:120]})
        self.samples = self.samples[-MAX_SAMPLES:]
        self.save()
        return self.term_scales[term]

    def note_mfu(self, mfu, context=""):
        """Record the goodput ledger's run-level MFU as a calibration
        sanity input (persisted as ``last_mfu``).  MFU > 1 is physically
        impossible — it means the peak-flops table (or the flops
        estimate) is wrong, and the compute roofline every ``compute``
        term observation is fit against shares the same inputs, so the
        warning names both."""
        if mfu is None or mfu <= 0:
            return self.last_mfu
        self.last_mfu = float(mfu)
        if self.last_mfu > 1.0:
            logging.warning(
                "goodput MFU %.3f > 1 (%s): the peak-flops table "
                "(AUTODIST_PEAK_TFLOPS) or GraphItem.flops_estimate is "
                "wrong — per-term compute calibration shares these inputs "
                "and should not be trusted until they are fixed",
                self.last_mfu, context)
        self.save()
        return self.last_mfu

    def scope_scales(self):
        """Per-scope refinement ratios from the per-layer profiler's
        ``profile:<scope>`` samples (``observability/profile.py``
        ``feed_calibration``): ``{scope: {"compute": r, "comms": r}}``.

        Only REAL measured data produces these samples (the profiler
        feeds scheduled-HLO measurements, never model-vs-itself), so a
        scope key here means the automap searcher can price that layer
        with its own measured-vs-predicted ratio.  Ratios are EMA-folded
        in sample order with the same bounds the class scales use, and
        the global scale is factored out (samples record raw-model
        predictions) — scope scales compose ON TOP of
        ``compute_scale``/``comms_scale``, they do not replace them.
        """
        out = {}
        for s in self.samples:
            ctx = str(s.get("context", ""))
            term = s.get("term")
            if not ctx.startswith("profile:") or term not in ("compute",
                                                              "comms"):
                continue
            scope = ctx[len("profile:"):]
            pred, meas = s.get("predicted_ms"), s.get("measured_ms")
            if not pred or not meas or pred <= 0 or meas <= 0:
                continue
            lo, hi = SCALE_BOUNDS
            ratio = min(hi, max(lo, meas / (pred * max(1e-9, self.scale))))
            row = out.setdefault(scope, {})
            cur = row.get(term, 1.0)
            row[term] = min(hi, max(lo, cur * (1 - EMA_ALPHA) +
                                    ratio * EMA_ALPHA))
        return out

    def prediction_error_pct(self):
        """Signed error of the most recent sample (None if no samples)."""
        return self.samples[-1]["error_pct"] if self.samples else None
