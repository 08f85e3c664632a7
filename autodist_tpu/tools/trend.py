"""Bench trend sentinel: the history diagnoses itself.

Every bench round leaves a record — ``BENCH_r<N>.json`` (the driver's
stdout-tail snapshot whose last line is the headline JSON) and
``BENCH_DETAILS.json`` (the latest run's full detail blob).  This module
loads that history, computes per-metric deltas for the latest round
against both the *previous* round and the *best* prior round, flags
regressions beyond a noise floor, and emits a markdown table
(``TREND.md``) plus a JSON blob — so a bench run lands with its own
trend diagnosis attached (ROADMAP item 1: "with causes, not just
ratios") instead of waiting for a human to eyeball five files.

Direction-aware: ``value`` (images/sec) regressing means it went DOWN;
``serve_p99_ms`` regressing means it went UP; ``tuner_prediction_error``
is judged by magnitude.  The noise floor is the ``--threshold`` (default
10%) raised to the headline's own measured spread for metrics that carry
one (a trial spread wider than 10% is common — flagging inside the noise
band would cry wolf every round).

Usage::

    python -m autodist_tpu.tools.trend [--root DIR] [--threshold 0.10]
                                       [--warn-only] [--json PATH]
    python bench.py --trend [--trend-warn-only]

Exit status: 0 = no regression (or ``--warn-only``), 1 = at least one
tracked headline metric regressed beyond its noise floor.

Deliberately dependency-free (stdlib only, no jax) so it runs on any CI
box against a checked-out history.
"""
import argparse
import glob
import json
import os
import re
import sys
import time

#: metric name -> direction ("higher" / "lower" better, "abs" = smaller
#: magnitude better).  Only headline keys: every bench round carries the
#: headline, so the trend is computable over the whole history.
TRACKED = {
    "value": "higher",
    "vs_baseline": "higher",
    "bert_paired": "higher",
    "bf16_vs_f32": "higher",
    "achieved_tflops": "higher",
    "loader_steady_vs_ceiling": "higher",
    "loader_steady_vs_h2d": "higher",
    "unroll_speedup": "higher",
    "overlap_speedup": "higher",
    "compress_speedup": "higher",
    # Hierarchical collectives (docs/collectives.md): hier_speedup is the
    # paired flat-f32 vs best-hierarchical step-time ratio on the forced
    # two-host mesh; hier_wire_dcn_ratio the best hier arm's measured
    # DCN-leg bytes over the flat f32 ring's DCN share — the compression
    # the two-level schedule buys on the slow leg.  A kernel or pricing
    # regression (ratio creeping toward 1.0) fails the round loudly.
    "hier_speedup": "higher",
    "hier_wire_dcn_ratio": "lower",
    "serve_rps_at_p99_slo": "higher",
    "serve_p99_ms": "lower",
    # Autoregressive decode (docs/serving.md): tokens/sec and request
    # p99 at the steady 16-client level of the slot-based KV-cache
    # decode engine; serve_rps_at_p99_slo_through_scale the SLO-gated
    # rps of the level that rode THROUGH a forced shrink->grow fleet
    # reshape — a drop means the zero-drop scale path stopped hiding in
    # the latency budget.
    "decode_tokens_per_sec": "higher",
    "decode_p99_ms": "lower",
    "serve_rps_at_p99_slo_through_scale": "higher",
    "tuner_prediction_error": "abs",
    # Automap search quality (docs/tuning.md): the rediscovery flags are
    # 1.0/0.0 — a flag dropping to 0 is a -100% regression, so a search
    # change that loses TP/EP rediscovery fails the round loudly.
    "automap_search_ms": "lower",
    "automap_prediction_error": "abs",
    "automap_rediscovered_tp": "higher",
    "automap_rediscovered_ep": "higher",
    # Multi-axis composition (docs/tuning.md Multi-axis Automap): 1.0/0.0
    # flags like the rediscovery pair — the MoE winner composing an
    # expert x model mesh, a stacked-blocks model drawing a data x pipe
    # proposal, and the fake-pod placement pass keeping the model axis
    # on the intra-host ici tier.  Any flag dropping to 0 means the
    # searcher stopped composing (or started paying DCN rates for model
    # collectives) and fails the round loudly.
    "automap_tp_ep_composed": "higher",
    "automap_dp_pipe_composed": "higher",
    "automap_placement_model_ici": "higher",
    # Cluster skew (docs/observability.md): barrier wait blamed on a
    # straggler host — a growing value means the fleet is pacing on one
    # slow host, not on the wire.
    "skew_wait_ms_per_step": "lower",
    # Pipeline parallelism (docs/pipelining.md): pipeline_speedup is the
    # paired shifting-vs-sequential schedule ratio on the same mesh;
    # bubble_fraction the measured idle-slot share of the schedule, which
    # must track the cost model's (S-1)/(S+M-1).
    "pipeline_speedup": "higher",
    "bubble_fraction": "lower",
    # Online re-tuning (docs/retuning.md): retune_payoff_pct is the
    # measured post- vs pre-switch p50 improvement when the controller
    # corrects deliberately stale launch knobs; retune_switch_ms the
    # downtime of that switch.  A controller regression (payoff gone,
    # switch cost ballooning) fails the round loudly.
    "retune_payoff_pct": "higher",
    "retune_switch_ms": "lower",
    # Self-healing (docs/retuning.md Reshape-on-degrade):
    # degrade_to_decision_ms is the measured degradation-onset ->
    # eviction-decision latency (hysteresis + pricing included);
    # selfheal_goodput_retained_pct the degraded arm's stitched goodput
    # over the undisturbed control arm's.  A healer regression (slower
    # decisions, recovery losing more of the run) fails the round loudly.
    "degrade_to_decision_ms": "lower",
    "selfheal_goodput_retained_pct": "higher",
    # HBM memory ledger (docs/memory.md): mem_peak_gb is the worst-arm
    # measured per-device peak on the zoo-transformer PS/zero1 x unroll
    # grid — a growing value is a real memory regression;
    # mem_prediction_error_pct the worst-arm measured-vs-predicted-
    # resident reconciliation error — a growing magnitude is cost-model
    # drift, and either fails bench.py --trend loudly.
    "mem_peak_gb": "lower",
    "mem_prediction_error_pct": "abs",
}

DEFAULT_THRESHOLD = 0.10


# ---------------------------------------------------------------------------
# history loading


def _headline_from_tail(tail):
    """The last JSON object line of a driver stdout tail that parses and
    looks like a bench headline (has ``metric`` or ``value``)."""
    for line in reversed(str(tail).splitlines()):
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and ("metric" in obj or "value" in obj):
            return obj
    return None


def _parse_round_file(path):
    """One history file -> (label, headline) or ``None``.

    Three shapes are accepted: the driver's ``{"n": N, "tail": ...}``
    snapshot, a ``{"headline": ..., "details": ...}`` details blob, and
    a bare headline dict (synthetic fixtures / hand-saved rounds).
    """
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(data, dict):
        return None
    base = os.path.basename(path)
    m = re.search(r"r(\d+)", base)
    label = f"r{int(m.group(1)):02d}" if m else base
    if "tail" in data:
        headline = _headline_from_tail(data["tail"])
        if data.get("n") is not None:
            label = f"r{int(data['n']):02d}"
    elif "headline" in data:
        headline = data["headline"]
    elif "metric" in data or "value" in data:
        headline = data
    else:
        headline = None
    if not isinstance(headline, dict):
        return None
    return label, headline


def load_rounds(root):
    """The bench history under ``root``, oldest first:
    ``[{"label", "headline"}]`` from every parseable ``BENCH_r*.json``,
    with ``BENCH_DETAILS.json``'s headline appended as the *current*
    round when it differs from the newest snapshot (a just-finished run
    has written details but no ``BENCH_r`` record yet)."""
    rounds = []
    paths = sorted(
        glob.glob(os.path.join(root, "BENCH_r*.json")),
        key=lambda p: (int(re.search(r"r(\d+)", os.path.basename(p))
                           .group(1))
                       if re.search(r"r(\d+)", os.path.basename(p))
                       else 0, p))
    for path in paths:
        parsed = _parse_round_file(path)
        if parsed:
            rounds.append({"label": parsed[0], "headline": parsed[1]})
    details = os.path.join(root, "BENCH_DETAILS.json")
    parsed = _parse_round_file(details) if os.path.exists(details) else None
    if parsed:
        headline = parsed[1]
        if not rounds or any(
                headline.get(k) != rounds[-1]["headline"].get(k)
                for k in TRACKED):
            rounds.append({"label": "current", "headline": headline})
    return rounds


# ---------------------------------------------------------------------------
# trend computation


def _metric(headline, name):
    v = headline.get(name)
    if isinstance(v, bool) or not isinstance(v, (int, float)):
        return None
    return float(v)


def _improvement_pct(cur, ref, direction):
    """Signed improvement of ``cur`` over ``ref`` in percent: positive =
    better, negative = worse, regardless of the metric's direction."""
    if ref is None or cur is None:
        return None
    if direction == "abs":
        cur, ref = abs(cur), abs(ref)
        direction = "lower"
    if ref == 0:
        return None
    raw = (cur - ref) / abs(ref) * 100.0
    return raw if direction == "higher" else -raw


def _noise_floor_pct(metric, headline, threshold):
    """Per-metric noise floor in percent: the threshold, raised to the
    headline's own measured spread when it reports one (only the
    framework-arm spread applies to ``value``)."""
    floor = threshold * 100.0
    if metric == "value":
        spread = ((headline.get("spread_pct") or {}).get("fw")
                  if isinstance(headline.get("spread_pct"), dict) else None)
        if isinstance(spread, (int, float)):
            floor = max(floor, float(spread))
    return floor


def compute_trend(rounds, threshold=DEFAULT_THRESHOLD):
    """Per-metric trend of the latest round vs the previous and the best
    prior round.

    Returns ``{"rounds", "latest", "rows", "regressions", "missing"}``;
    ``rows`` carry ``status`` in {"regressed", "improved", "flat",
    "missing", "new", "untracked"}.  ``regressions`` is the subset of
    rows whose latest value is worse than the PREVIOUS round's beyond
    the noise floor — the exit-code signal.
    """
    if not rounds:
        return {"rounds": [], "latest": None, "rows": [],
                "regressions": [], "missing": []}
    latest = rounds[-1]
    prior = rounds[:-1]
    rows, regressions, missing = [], [], []
    for metric, direction in TRACKED.items():
        cur = _metric(latest["headline"], metric)
        history = [(r["label"], _metric(r["headline"], metric))
                   for r in prior]
        history = [(lab, v) for lab, v in history if v is not None]
        prev_label, prev = history[-1] if history else (None, None)
        best_label, best = None, None
        for lab, v in history:
            if best is None or (_improvement_pct(v, best, direction)
                                or 0) > 0:
                best_label, best = lab, v
        if cur is None:
            if history:
                row = {"metric": metric, "status": "missing",
                       "latest": None, "prev": prev,
                       "prev_label": prev_label, "best": best,
                       "best_label": best_label,
                       "delta_vs_prev_pct": None, "delta_vs_best_pct": None}
                rows.append(row)
                missing.append(row)
            continue  # never measured anywhere: untracked this history
        if not history:
            rows.append({"metric": metric, "status": "new", "latest": cur,
                         "prev": None, "prev_label": None, "best": None,
                         "best_label": None, "delta_vs_prev_pct": None,
                         "delta_vs_best_pct": None})
            continue
        d_prev = _improvement_pct(cur, prev, direction)
        d_best = _improvement_pct(cur, best, direction)
        floor = _noise_floor_pct(metric, latest["headline"], threshold)
        if d_prev is not None and d_prev < -floor:
            status = "regressed"
        elif d_prev is not None and d_prev > floor:
            status = "improved"
        else:
            status = "flat"
        row = {"metric": metric, "status": status, "latest": cur,
               "prev": prev, "prev_label": prev_label, "best": best,
               "best_label": best_label,
               "delta_vs_prev_pct": (round(d_prev, 2)
                                     if d_prev is not None else None),
               "delta_vs_best_pct": (round(d_best, 2)
                                     if d_best is not None else None),
               "noise_floor_pct": round(floor, 2)}
        rows.append(row)
        if status == "regressed":
            regressions.append(row)
    return {"rounds": [r["label"] for r in rounds],
            "latest": latest["label"], "rows": rows,
            "regressions": regressions, "missing": missing}


# ---------------------------------------------------------------------------
# emission


def _fmt(v):
    if v is None:
        return "—"
    if isinstance(v, float):
        return f"{v:,.4g}" if abs(v) < 1000 else f"{v:,.0f}"
    return str(v)


_STATUS_MARK = {"regressed": "🔴 regressed", "improved": "🟢 improved",
                "flat": "flat", "missing": "⚠ missing", "new": "new"}


def to_markdown(trend):
    """The trend as a markdown section (one table, worst news first)."""
    lines = [
        f"## Bench trend — latest `{trend['latest']}` vs history "
        f"{trend['rounds'][:-1] or '(none)'}",
        "",
        "| metric | best (round) | prev (round) | latest | Δ vs prev "
        "| Δ vs best | status |",
        "|---|---|---|---|---|---|---|",
    ]
    order = {"regressed": 0, "missing": 1, "improved": 2, "flat": 3,
             "new": 4}
    for row in sorted(trend["rows"],
                      key=lambda r: (order.get(r["status"], 9),
                                     r["metric"])):
        lines.append(
            f"| `{row['metric']}` "
            f"| {_fmt(row['best'])} ({row['best_label'] or '—'}) "
            f"| {_fmt(row['prev'])} ({row['prev_label'] or '—'}) "
            f"| {_fmt(row['latest'])} "
            f"| {_fmt(row['delta_vs_prev_pct'])}% "
            f"| {_fmt(row['delta_vs_best_pct'])}% "
            f"| {_STATUS_MARK.get(row['status'], row['status'])} |")
    if trend["regressions"]:
        names = ", ".join(f"`{r['metric']}`" for r in trend["regressions"])
        lines += ["", f"**{len(trend['regressions'])} regression(s) beyond "
                      f"the noise floor:** {names}"]
    else:
        lines += ["", "No tracked headline metric regressed beyond the "
                      "noise floor."]
    if trend["missing"]:
        names = ", ".join(f"`{r['metric']}`" for r in trend["missing"])
        lines.append(f"Previously-tracked metrics missing from the latest "
                     f"round: {names}.")
    return "\n".join(lines) + "\n"


def run(root=None, out_md=None, out_json=None, threshold=DEFAULT_THRESHOLD,
        append=True, stamp=None):
    """Load the history under ``root``, compute the trend, and emit the
    markdown/JSON artifacts.  Returns the trend dict (callers read
    ``trend["regressions"]`` for the exit decision).  File writes are
    fail-open — a read-only checkout still gets the computed trend."""
    root = root or os.getcwd()
    trend = compute_trend(load_rounds(root), threshold=threshold)
    trend["generated_at"] = stamp or time.strftime("%Y-%m-%d %H:%M:%S")
    md = to_markdown(trend)
    if out_md:
        try:
            mode = "a" if append and os.path.exists(out_md) else "w"
            with open(out_md, mode) as f:
                if mode == "w":
                    f.write("# Bench trend sentinel "
                            "(autodist_tpu.tools.trend)\n\n")
                f.write(f"<!-- generated {trend['generated_at']} -->\n")
                f.write(md + "\n")
        except OSError as e:
            sys.stderr.write(f"trend: could not write {out_md}: {e}\n")
    if out_json:
        try:
            with open(out_json, "w") as f:
                json.dump(trend, f, indent=1)
        except OSError as e:
            sys.stderr.write(f"trend: could not write {out_json}: {e}\n")
    return trend


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="python -m autodist_tpu.tools.trend",
        description="Bench trend sentinel over BENCH_r*.json history")
    ap.add_argument("--root", default=None,
                    help="directory holding BENCH_r*.json (default: cwd, "
                         "falling back to the repo root this module "
                         "lives in)")
    ap.add_argument("--out", default=None,
                    help="markdown output path (default <root>/TREND.md)")
    ap.add_argument("--json", dest="json_out", default=None,
                    help="also write the trend as JSON here")
    ap.add_argument("--threshold", type=float, default=DEFAULT_THRESHOLD,
                    help="regression noise floor as a fraction "
                         "(default 0.10)")
    ap.add_argument("--no-append", action="store_true",
                    help="overwrite the markdown instead of appending")
    ap.add_argument("--warn-only", action="store_true",
                    help="report regressions but exit 0")
    args = ap.parse_args(argv)
    root = args.root
    if root is None:
        root = os.getcwd()
        if not glob.glob(os.path.join(root, "BENCH_r*.json")):
            pkg_root = os.path.dirname(os.path.dirname(
                os.path.dirname(os.path.abspath(__file__))))
            if glob.glob(os.path.join(pkg_root, "BENCH_r*.json")):
                root = pkg_root
    out_md = args.out or os.path.join(root, "TREND.md")
    trend = run(root=root, out_md=out_md, out_json=args.json_out,
                threshold=args.threshold, append=not args.no_append)
    sys.stdout.write(to_markdown(trend))
    if trend["regressions"] and not args.warn_only:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
