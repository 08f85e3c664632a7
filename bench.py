"""Benchmark driver: prints ONE JSON line with the headline metric.

Flagship: ResNet-50 (BASELINE.md's headline model), synthetic ImageNet
shapes, trained through the full framework pipeline (capture -> strategy ->
GSPMD step) on the real accelerator.

Methodology:
* Output contract: stdout carries ONE compact headline line; the full trial
  arrays, notes, and HLO verification detail go to ``DETAILS_PATHS``
  (referenced from the headline's ``details_file``).
* The HEADLINE ``vs_baseline`` is the PAIRED estimator: both arms alternate
  in ONE subprocess, so process-level drift cancels pairwise.  Profiled
  residual: the framework's AOT call dispatches ~14us/call slower than the
  hand-written step (TrainState pytree handling).
* INTERLEAVED subprocess trials remain the cross-check: the framework arm
  and the plain-``jax.jit`` baseline arm alternate F,B,F,B,... in fresh
  subprocesses, ``TRIALS`` >= 7 per arm, each reporting its best segment
  (timeit-style); median-ratio, min-vs-min, and both arms' spreads are
  reported so the headline can be judged against the noise floor.
* Chip workers stop with an error when JAX finds no TPU, and name the
  platform and device kind they ran on.  Achieved TFLOP/s comes from XLA
  cost analysis over the measured step time; no number here has been taken
  on today's code (ROADMAP S1 defines the benchmark).
* The loader-fed trial feeds the model through NativeDataLoader (C++
  shuffle, buffer-pool staging + async assembly ring) + the depth-N
  DevicePrefetcher (explicit completion handles, just-in-time settle,
  staging-buffer recycle) over >= 40 steps, next to three same-process
  control windows: the pure-H2D wire ceiling (depth 2 in flight), the
  serialized wire+assembly bound (one in flight), and pure assembly (the
  assemble-vs-transfer breakdown persisted to the details sidecar).
  loader_fed_vs_resident is reported for context only.
* The weak-scaling proxy runs framework AND plain-jax arms on forced-host
  CPU meshes (fixed per-device batch).  All n virtual devices timeshare one
  host core, so ideal total throughput is FLAT; the plain-jax arm separates
  XLA-CPU partitioned-program overhead from framework overhead.  Round 5:
  both arms run in ONE process per trial in alternating segments (the same
  paired estimator as the headline; single-subprocess-per-mode trials
  flipped several points run-to-run), ``SCALING_TRIALS`` >= 5 trials per
  point, medians + spreads reported.  The
  framework claim is paired fw/plainjax >= 0.95 at every n (the
  reference's own claim is "performance per GPU is stable", not absolute
  scaling of a timeshared host).
* ZeRO verification on the REAL TPU COMPILER: the PS program is AOT-compiled
  against a detached v5e-8 topology (``jax.experimental.topologies``) and
  its optimized HLO asserted — reduce-scatter present / no per-variable
  gradient all-reduce on the default explicit path, shard-local-update
  pattern (AR+DynamicSlice+AllGather) on the ``gspmd_update=True`` escape
  hatch.  ``gspmd_zero_verified`` in the output is backed by chip-compiled
  HLO, not the CPU proxy assertions of ``tests/test_hlo_lowering.py``.
* The flagship failing is a hard error (exit 1) — no silent fallback to a
  smaller model under the same headline name.
"""
import argparse
import functools
import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

STEPS = 40  # per timing segment
WARMUP = 6
SEGMENTS = 4
TRIALS = 7
SCALING_TRIALS = 5
BATCH = 64
# Repo-root copy FIRST: the end-of-round commit preserves it, so the
# published headline's details_file pointer must cite that one (the /tmp
# copy is the run-local convenience and dies with the machine).
DETAILS_PATHS = (os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "BENCH_DETAILS.json"),
                 "/tmp/autodist_tpu/bench_details.json")
LOADER_STEPS = 40  # steady-state window
LOADER_WARMUP = 4


# ---------------------------------------------------------------------------
# fixtures


def _resnet50_fixture(batch_size):
    import jax
    from autodist_tpu.models import resnet
    cfg = resnet.resnet50()
    params = resnet.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    batch = (rng.randn(batch_size, 224, 224, 3).astype(np.float32),
             rng.randint(0, 1000, (batch_size,)).astype(np.int32))
    return params, resnet.make_loss_fn(cfg), batch


def _cifar_fixture(batch_size):
    import jax
    from autodist_tpu.models import resnet
    cfg = resnet.cifar_resnet(depth=20)
    params = resnet.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    batch = (rng.randn(batch_size, 32, 32, 3).astype(np.float32),
             rng.randint(0, 10, (batch_size,)).astype(np.int32))
    return params, resnet.make_loss_fn(cfg), batch


def _u8_fixture(batch_size):
    """uint8-fed variant: ship bytes over the (bandwidth-limited) link and
    normalize on-device — the TPU input-pipeline idiom (f32 on the host
    costs ~60ms/batch and 4x the H2D bytes)."""
    params, f32_loss, batch = _resnet50_fixture(batch_size)

    def u8_loss(p, b):
        img_u8, labels = b
        return f32_loss(p, (img_u8.astype(np.float32) / 255.0, labels))
    rng = np.random.RandomState(1)
    u8_batch = ((rng.rand(batch_size, 224, 224, 3) * 255).astype(np.uint8),
                batch[1])
    return params, u8_loss, u8_batch


def _time_loop(fn, state, batch, steps, warmup, get_loss, segments=SEGMENTS):
    """Time `segments` independent segments of `steps` steps; return the
    best segment's per-step time plus all segment times.

    Both arms are measured identically.
    """
    import jax
    for _ in range(warmup):
        state, out = fn(state, batch)
    jax.block_until_ready(get_loss(out))
    seg_dts = []
    for _ in range(segments):
        t0 = time.perf_counter()
        for _ in range(steps):
            state, out = fn(state, batch)
        jax.block_until_ready(get_loss(out))
        seg_dts.append((time.perf_counter() - t0) / steps)
    loss = float(jax.device_get(get_loss(out)))
    assert np.isfinite(loss), f"non-finite loss {loss}"
    return min(seg_dts), loss, seg_dts


def _build_framework_step(params, loss_fn, batch, precision=None):
    import optax
    from autodist_tpu import AutoDist
    from autodist_tpu.strategy import AllReduce
    ad = AutoDist(strategy_builder=AllReduce(chunk_size=128))
    # Small lr keeps the loss finite on random data (BN in train mode +
    # lr 0.1 diverges within ~30 steps).
    item = ad.capture(loss_fn, params, optax.sgd(1e-3), example_batch=batch,
                      precision=precision)
    runner = ad.create_distributed_session(item)
    state = runner.create_state()
    step_fn = runner.make_callable(batch, aot=True)  # Session.make_callable parity
    return runner, state, step_fn


def _build_baseline_step(params, loss_fn, batch, opt=None):
    """Hand-written jax.jit train step — the no-framework baseline."""
    import jax
    import optax
    opt = opt or optax.sgd(1e-3)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(p, o, b):
        loss, grads = jax.value_and_grad(loss_fn)(p, b)
        updates, o = opt.update(grads, o, p)
        return optax.apply_updates(p, updates), o, loss

    p, o = params, opt.init(params)
    db = jax.device_put(batch)
    compiled = step.lower(p, o, db).compile()  # AOT: reused for the loop
    # AOT executables don't auto-transfer args; place state on the chip.
    p, o = jax.device_put((p, o), jax.devices()[0])
    jax.block_until_ready((p, o, db))
    flops = None
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        flops = float(ca.get("flops", 0)) or None
    except Exception:  # noqa: BLE001 - cost analysis is best-effort
        pass

    def fn(st, b):
        pp, oo, loss = compiled(st[0], st[1], b)
        return (pp, oo), loss
    return fn, (p, o), db, flops


# ---------------------------------------------------------------------------
# workers (each runs in its own subprocess; prints one JSON line on stdout)


def _phase_timings_ms():
    """Per-phase framework span totals (observability), for the details
    sidecar: BENCH rounds attribute a regression to capture vs strategy
    build vs transform vs compile without re-profiling."""
    try:
        from autodist_tpu import observability
        return {k: v["total_ms"]
                for k, v in observability.phase_timings().items()}
    except Exception:  # noqa: BLE001 - attribution is best-effort
        return {}


def _attribution_summary():
    """The last finalized step-time attribution breakdown (wall = data
    wait + host dispatch + device compute + exposed comms + residual,
    per-step ms) — persisted into BENCH_DETAILS.json by every step-loop
    worker so a gate regression ships with its causes attached."""
    try:
        from autodist_tpu import observability
        return observability.attribution.last_summary()
    except Exception:  # noqa: BLE001 - attribution is best-effort
        return None


def _profile_summary():
    """The last finalized per-layer profile (per-scope compute/comms ms +
    wire bytes, reconciled to the attribution ledger) — persisted into
    BENCH_DETAILS.json by every step-loop worker so a gate regression
    names the layer, not just the cost class."""
    try:
        from autodist_tpu import observability
        return observability.profile.last_profile()
    except Exception:  # noqa: BLE001 - profiling is best-effort
        return None


def _goodput_summary():
    """The last finalized run-level goodput segment (goodput vs badput
    class totals + MFU, observability/goodput.py) — persisted into
    BENCH_DETAILS.json by every step-loop worker so the bench history
    carries productive-fraction and MFU series the trend sentinel can
    watch run-over-run."""
    try:
        from autodist_tpu import observability
        return observability.goodput.last_summary()
    except Exception:  # noqa: BLE001 - goodput is best-effort
        return None


def _skew_summary():
    """The last skew decomposition (per-host wire vs skew-wait split of
    exposed comms + clock offsets + the straggler verdict,
    observability/skew.py) — persisted into BENCH_DETAILS.json by every
    step-loop worker; ``skew_wait_ms_per_step`` is trend-tracked so a
    fleet that starts pacing on one slow host fails the round loudly."""
    try:
        from autodist_tpu import observability
        return observability.skew.last_summary()
    except Exception:  # noqa: BLE001 - skew is best-effort
        return None


def _memory_summary():
    """The last finalized HBM ledger summary (predicted per-class peak,
    measured boundary peak, reconciliation error,
    observability/memory.py) — persisted into BENCH_DETAILS.json by
    every step-loop worker; ``mem_peak_gb`` and
    ``mem_prediction_error_pct`` are trend-tracked so a memory
    regression (or a cost-model drift) fails the round loudly."""
    try:
        from autodist_tpu import observability
        return observability.memory.last_summary()
    except Exception:  # noqa: BLE001 - memory ledger is best-effort
        return None


def _worker_framework(steps=STEPS, warmup=WARMUP, precision=None):
    import itertools
    import jax
    n_chips = len(jax.devices())
    bs = BATCH * max(1, n_chips)
    params, loss_fn, batch = _resnet50_fixture(bs)
    runner, state, step_fn = _build_framework_step(params, loss_fn, batch,
                                                   precision=precision)
    # A short OBSERVED loop before the bare-callable timing: populates
    # the attribution ledger (and returns the live donated state the
    # timed loop continues from).
    state, _ = runner.run(state, itertools.repeat(batch), 4)
    sharded = runner.remapper.shard_batch(batch)
    spp, loss, segs = _time_loop(step_fn, state, sharded, steps, warmup,
                                 lambda out: out["loss"])
    print(json.dumps({"ips": bs / spp, "ms_per_step": spp * 1e3,
                      "segments_ms": [round(d * 1e3, 3) for d in segs],
                      "loss": loss, "precision": precision or "f32",
                      "phases_ms": _phase_timings_ms(),
                      "attribution": _attribution_summary(),
                      "profile": _profile_summary(),
                      "goodput": _goodput_summary(),
                      "skew": _skew_summary(),
                      "memory": _memory_summary(),
                      "n_chips": n_chips}))


def _worker_baseline(steps=STEPS, warmup=WARMUP):
    import jax
    n_chips = len(jax.devices())
    bs = BATCH * max(1, n_chips)
    params, loss_fn, batch = _resnet50_fixture(bs)
    fn, st, db, flops = _build_baseline_step(params, loss_fn, batch)
    spp, loss, segs = _time_loop(fn, st, db, steps, warmup, lambda out: out)
    print(json.dumps({"ips": bs / spp, "ms_per_step": spp * 1e3,
                      "segments_ms": [round(d * 1e3, 3) for d in segs],
                      "loss": loss, "flops_per_step": flops,
                      "n_chips": n_chips}))


def _run_paired_segments(fseg, fstate, bseg, bstate, steps, segments):
    """Alternate framework/baseline segments and return per-segment ms
    lists plus the median of adjacent-pair ratios (each pair shares the
    same ~seconds-wide window, so slow drift cancels pairwise).
    Both seg functions return (state, last_loss); finiteness of BOTH
    arms' losses is asserted after the LAST timed segment — a run that
    diverges mid-measurement must not publish a throughput."""
    import jax
    fstate, fl = fseg(fstate)   # warmup both
    bstate, bl = bseg(bstate)
    f_ms, b_ms = [], []
    for _ in range(segments):
        t0 = time.perf_counter()
        fstate, fl = fseg(fstate)
        f_ms.append((time.perf_counter() - t0) / steps * 1e3)
        t0 = time.perf_counter()
        bstate, bl = bseg(bstate)
        b_ms.append((time.perf_counter() - t0) / steps * 1e3)
    for name, l in (("framework", fl), ("baseline", bl)):
        l = float(jax.device_get(l))
        assert np.isfinite(l), f"non-finite {name} loss {l} after timing"
    pair_ratios = sorted(b / f for f, b in zip(f_ms, b_ms))
    n = len(pair_ratios)
    # True median for even counts: upper-middle alone would systematically
    # favor the framework (worst at n=2, where it is the max).
    med = (pair_ratios[n // 2] if n % 2
           else (pair_ratios[n // 2 - 1] + pair_ratios[n // 2]) / 2)
    return f_ms, b_ms, med


def _worker_paired(steps=STEPS, segments=16):
    """Both arms, one subprocess, alternating F,B per segment: process-level
    drift hits both arms identically, so per-pair segment ratios
    isolate actual framework overhead.  Segments are nearly free next to
    process setup (~21s vs ~60ms/segment), so a wide pair count tightens
    the median without measurable wall-time cost."""
    import jax
    n_chips = len(jax.devices())
    bs = BATCH * max(1, n_chips)
    params, loss_fn, batch = _resnet50_fixture(bs)
    runner, fstate, fstep = _build_framework_step(params, loss_fn, batch)
    fbatch = runner.remapper.shard_batch(batch)
    bfn, bstate, db, _ = _build_baseline_step(params, loss_fn, batch)

    def fseg(state):
        for _ in range(steps):
            state, out = fstep(state, fbatch)
        jax.block_until_ready(out["loss"])
        return state, out["loss"]

    def bseg(st):
        for _ in range(steps):
            st, loss = bfn(st, db)
        jax.block_until_ready(loss)
        return st, loss

    f_ms, b_ms, ratio = _run_paired_segments(fseg, fstate, bseg, bstate,
                                             steps, segments)
    print(json.dumps({
        "ratio": ratio,
        "ratio_minmin": min(b_ms) / min(f_ms),
        "framework_segments_ms": [round(x, 3) for x in f_ms],
        "baseline_segments_ms": [round(x, 3) for x in b_ms],
        "n_chips": n_chips}))


def _worker_bert(steps=20, segments=10, bs=32, seq=128):
    """BERT-base masked-LM pretraining, paired in one subprocess: the
    framework (Parallax, BASELINE.md's benchmark config for BERT — sparse
    embeddings to sharded PS, dense to AllReduce) against a hand-written
    jax.jit step.  The reference's second headline model
    (``/root/reference/docs/usage/performance.md``)."""
    import jax
    import optax
    from autodist_tpu import AutoDist
    from autodist_tpu.strategy import Parallax
    from autodist_tpu.models import bert

    n_chips = len(jax.devices())
    gbs = bs * max(1, n_chips)
    cfg = bert.bert_base(max_len=seq)
    params = bert.init(jax.random.PRNGKey(0), cfg)
    loss_fn = bert.make_loss_fn(cfg)
    batch = bert.synthetic_batch(cfg, batch_size=gbs, seq_len=seq,
                                 num_masked=20)

    ad = AutoDist(strategy_builder=Parallax())
    item = ad.capture(loss_fn, params, optax.adam(1e-4),
                      example_batch=batch)
    runner = ad.create_distributed_session(item)
    fstate = runner.create_state()
    fstep = runner.make_callable(batch, aot=True)
    fbatch = runner.remapper.shard_batch(batch)

    bfn, bstate, db, _ = _build_baseline_step(params, loss_fn, batch,
                                              opt=optax.adam(1e-4))

    def fseg(state):
        for _ in range(steps):
            state, out = fstep(state, fbatch)
        jax.block_until_ready(out["loss"])
        return state, out["loss"]

    def bseg(st):
        for _ in range(steps):
            st, loss = bfn(st, db)
        jax.block_until_ready(loss)
        return st, loss

    f_ms, b_ms, ratio = _run_paired_segments(fseg, fstate, bseg, bstate,
                                             steps, segments)
    f_best = min(f_ms)
    print(json.dumps({
        "samples_per_sec": gbs / (f_best / 1e3),
        "ms_per_step": f_best,
        "ratio": ratio,
        "framework_segments_ms": [round(x, 3) for x in f_ms],
        "baseline_segments_ms": [round(x, 3) for x in b_ms],
        "n_chips": n_chips}))


def _worker_tuner(steps=40, warmup=6):
    """Strategy autotuner end to end on the chip: AutoStrategy ranks the
    candidate zoo with the analytic cost model, the winner trains a
    CIFAR-ResNet through the full pipeline, and the observed step loop
    records predicted-vs-measured step time (the calibration feedback
    loop, docs/tuning.md).  The JSON carries the ranked table top plus
    ``prediction_error_pct`` so BENCH_DETAILS.json tracks whether the
    cost model is drifting run-over-run."""
    import itertools
    import jax
    import optax
    from autodist_tpu import AutoDist, observability, tuner
    n_chips = len(jax.devices())
    bs = 32 * max(1, n_chips)
    params, loss_fn, batch = _cifar_fixture(bs)
    ad = AutoDist(strategy_builder=tuner.AutoStrategy())
    item = ad.capture(loss_fn, params, optax.sgd(1e-3), example_batch=batch)
    runner = ad.create_distributed_session(item)
    state = runner.create_state()
    state, metrics = runner.run(state, itertools.repeat(batch),
                                warmup + steps)
    loss = float(jax.device_get(metrics["loss"]))
    assert np.isfinite(loss), f"non-finite loss {loss}"
    result = tuner.last_result()
    info = result.to_json(top=8)
    gauges = observability.registry().snapshot()["gauges"]
    print(json.dumps({
        "chosen": info["chosen"],
        "predicted_ms": info["predicted_ms"],
        "measured_ms": info["measured_ms"],
        "prediction_error_pct": info["prediction_error_pct"],
        "calibration_scale": info.get("calibration_scale"),
        "error_gauge": gauges.get("tuner.prediction_error_pct"),
        "mode": info["mode"],
        "evaluated": info["evaluated"],
        "space_size": info["space_size"],
        "ranking": [{"rank": r["rank"], "name": r["name"],
                     "predicted_ms": r["predicted_ms"]}
                    for r in info["ranking"]],
        "attribution": _attribution_summary(),
        "profile": _profile_summary(),
        "goodput": _goodput_summary(),
        "skew": _skew_summary(),
        "loss": loss, "n_chips": n_chips}))


def _worker_automap(steps=24, warmup=4):
    """Automap per-op sharding search quality (ISSUE 12): three searches
    on one 8-way mesh — a wide-FFN transformer where TENSOR parallelism
    must fall out of the search, the zoo MoE where EXPERT parallelism
    must, and a tiny linreg that must fall back to the data-parallel zoo
    winner — plus a measured step loop on the chosen transformer plan so
    predicted-vs-measured drift is tracked.  ``automap_search_ms`` and
    the two rediscovery flags are trend-sentinel metrics (bench.py
    --trend), so a search-quality regression fails the round.  Spawned
    on a forced 8-device CPU mesh (like longcontext-ring): rediscovery
    is a property of the searcher, not the backing chip."""
    import itertools
    import jax
    import jax.numpy as jnp
    import optax
    from autodist_tpu import AutoDist, automap, observability
    from autodist_tpu.autodist import _reset_default
    from autodist_tpu.models import lm as lm_mod
    from autodist_tpu.parallel import moe

    n_chips = len(jax.devices())
    out = {"n_chips": n_chips}

    # -- wide-FFN transformer: TP must fall out of the search ----------------
    cfg = lm_mod.lm_tiny(max_len=32)
    cfg.mlp_dim = 16 * cfg.dim
    params = lm_mod.init(jax.random.PRNGKey(0), cfg)
    loss_fn = lm_mod.make_loss_fn(cfg)
    batch = lm_mod.synthetic_batch(cfg, batch_size=8, seq_len=32)
    ad = AutoDist(strategy_builder=automap.Automap())
    item = ad.capture(loss_fn, params, optax.sgd(1e-2), example_batch=batch)
    runner = ad.create_distributed_session(item)
    res = automap.last_result()
    info = res.to_json()
    out["transformer"] = {
        "chosen": info["chosen"], "base": info["base"],
        "search_ms": info["search_ms"],
        "fingerprint": info["fingerprint"]}
    out["automap_rediscovered_tp"] = bool(info["rediscovered"]["tp"])
    out["automap_search_ms"] = info["search_ms"]
    predicted = next(r["predicted_ms"] for r in info["ranking"]
                     if r["name"] == info["chosen"])

    state = runner.create_state()
    state, metrics = runner.run(state, itertools.repeat(batch),
                                warmup + steps)
    loss = float(jax.device_get(metrics["loss"]))
    assert np.isfinite(loss), f"non-finite loss {loss}"
    hist = observability.registry().histogram("step.latency_ms").summary()
    measured = float(hist.get("p50") or 0.0)
    out["predicted_ms"] = round(predicted, 4)
    out["measured_ms"] = round(measured, 4)
    out["automap_prediction_error"] = (
        round(100.0 * (predicted - measured) / measured, 2)
        if measured > 0 else None)

    # -- zoo MoE: EP must fall out of the search -----------------------------
    _reset_default()
    mcfg = moe.MoEConfig(num_experts=8, top_k=2, d_model=32, d_hidden=512)
    k = jax.random.PRNGKey(0)
    mparams = {"moe": moe.init(k, mcfg),
               "head": {"kernel": jax.random.normal(k, (32, 8)) * 0.1}}

    def moe_loss(p, b):
        x, labels = b
        h, aux = moe.apply(p["moe"], mcfg, x)
        lg = h @ p["head"]["kernel"]
        ce = -jnp.mean(jax.nn.log_softmax(lg)[
            jnp.arange(labels.shape[0]), labels])
        return ce + 0.01 * aux

    rng = np.random.RandomState(0)
    mbatch = (rng.randn(16, 32).astype(np.float32),
              rng.randint(0, 8, (16,)).astype(np.int32))
    ad2 = AutoDist(strategy_builder=automap.Automap())
    item2 = ad2.capture(moe_loss, mparams, optax.adam(1e-2),
                        example_batch=mbatch)
    ad2.build_strategy(item2)
    minfo = automap.last_result().to_json()
    out["moe"] = {"chosen": minfo["chosen"], "base": minfo["base"],
                  "search_ms": minfo["search_ms"],
                  "composition": minfo.get("composition")}
    out["automap_rediscovered_ep"] = bool(minfo["rediscovered"]["ep"])

    # -- multi-axis composition sentinels (search-only, no step loop) --------
    # Three properties of the multi-axis searcher, independent of the
    # backing chip like the rediscovery flags: a narrow-head MoE must
    # compose an expert x model mesh, a stacked-blocks model must draw a
    # data x pipe proposal, and on a fake 4-devices-per-host x 2-host
    # pod the placement pass must keep the model axis on the ici tier
    # while data spans hosts at DCN rates.
    from autodist_tpu.automap import search as automap_search
    from autodist_tpu.graph_item import GraphItem
    from autodist_tpu.models import transformer as T_mod
    from autodist_tpu.tuner.cost_model import Topology

    # 4-class head: at this shape composing model on top of expert pays
    # (a wider head tips the balance to single-axis expert parallelism).
    eparams = {"moe": moe.init(k, mcfg),
               "head": {"kernel": jax.random.normal(k, (32, 4)) * 0.1}}
    ebatch = (rng.randn(16, 32).astype(np.float32),
              rng.randint(0, 4, (16,)).astype(np.int32))
    eitem = GraphItem.capture(moe_loss, eparams, optax.adam(1e-2),
                              example_batch=ebatch)
    eout = automap_search.search_plans(eitem, Topology(n_chips, num_hosts=1))
    out["automap_tp_ep_composed"] = bool(
        eout.chosen is not None and
        {"expert", "model"} <= set(eout.chosen.axes))
    out["moe_composed"] = {
        "chosen": next((c.name for c in eout.candidates
                        if c.plan is eout.chosen), "automap/dp"),
        "placement": (dict(eout.chosen.placement)
                      if eout.chosen is not None else None)}

    scfg = T_mod.TransformerConfig(
        vocab=256, dim=64, num_heads=4, num_layers=4, max_len=16,
        causal=True, scan_layers=True, dtype=jnp.float32)
    sitem = GraphItem.capture(
        lm_mod.make_loss_fn(scfg), T_mod.init(jax.random.PRNGKey(0), scfg),
        optax.sgd(0.1),
        example_batch=lm_mod.synthetic_batch(scfg, batch_size=16,
                                             seq_len=16))
    sout = automap_search.search_plans(sitem, Topology(n_chips, num_hosts=1))

    def _data_fold(axes):
        prod = 1
        for v in axes.values():
            prod *= v
        return n_chips // prod

    out["automap_dp_pipe_composed"] = bool(any(
        c.plan is not None and "pipe" in c.plan.axes
        and _data_fold(c.plan.axes) > 1 for c in sout.candidates))
    out["stacked"] = {
        "chosen": next((c.name for c in sout.candidates
                        if c.plan is sout.chosen), "automap/dp"),
        "pipe_candidates": [c.name for c in sout.candidates
                            if c.plan is not None
                            and "pipe" in c.plan.axes]}

    # -- fake 4x2 pod: placement verdict (model axis on ici) -----------------
    pcfg = lm_mod.lm_tiny(max_len=32)
    pcfg.dim = 512
    pcfg.num_heads = 8
    pcfg.mlp_dim = 4 * pcfg.dim
    pitem = GraphItem.capture(
        lm_mod.make_loss_fn(pcfg), lm_mod.init(jax.random.PRNGKey(0), pcfg),
        optax.sgd(0.1),
        example_batch=lm_mod.synthetic_batch(pcfg, batch_size=8,
                                             seq_len=32))
    pout = automap_search.search_plans(pitem, Topology(8, num_hosts=2))
    pplan = pout.chosen
    out["automap_placement_model_ici"] = bool(
        pplan is not None and pplan.placement.get("model") == "ici")
    out["placement"] = {
        "chosen_axes": dict(pplan.axes) if pplan is not None else None,
        "tiers": dict(pplan.placement) if pplan is not None else None}

    # -- tiny linreg: must fall back to the data-parallel winner -------------
    _reset_default()
    lparams = {"w": jnp.zeros((12, 4)), "b": jnp.zeros((4,))}

    def lr_loss(p, b):
        x, y = b
        return jnp.mean(((x @ p["w"] + p["b"]).sum(-1) - y) ** 2)

    lbatch = (jnp.zeros((8, 12), jnp.float32), jnp.zeros((8,), jnp.float32))
    ad3 = AutoDist(strategy_builder=automap.Automap())
    item3 = ad3.capture(lr_loss, lparams, optax.sgd(0.1),
                        example_batch=lbatch)
    s3 = ad3.build_strategy(item3)
    linfo = automap.last_result().to_json()
    out["linreg"] = {"chosen": linfo["chosen"], "base": linfo["base"]}
    out["automap_fallback_dp"] = (
        linfo["chosen"] == "automap/dp" and
        dict(s3.graph_config.mesh_axes).keys() == {"data"})

    out.update({"attribution": _attribution_summary(),
                "profile": _profile_summary(),
                "goodput": _goodput_summary(),
                "skew": _skew_summary(),
                "loss": loss})
    print(json.dumps(out))


def _worker_pipeline(steps_per_segment=4, segments=3, stages=2, micro=4):
    """Pipeline parallelism point (ISSUE 14, docs/pipelining.md): the zoo
    transformer under ``Pipeline(stages=2, microbatches=4)`` driven in
    TWO paired arms on one forced 8-device mesh, segments interleaved
    round-robin so host drift hits every arm identically:

    * ``shift``      — the pipelined shifting-scan schedule;
    * ``sequential`` — the unpipelined control (one microbatch in
      flight, same stage placement, M*P ticks); every warm-up step's
      loss must be BITWISE equal to shift (asserted — the numerics
      contract pinned in tests/test_pipeline_subsystem.py).

    ``pipeline_speedup`` = t_sequential / t_shift (the schedule-overlap
    win; on a timeshared CPU host both arms execute the same M*P real
    stage slots, so this hovers near 1 and tracks schedule overhead —
    on real stages it approaches S x (1 - bubble)).

    ``bubble_fraction`` is measured STRUCTURALLY: the schedule scan's
    trip count is parsed out of the traced program (the ``length=N`` of
    the largest scan, the same artifact the tier-1 schedule-length test
    pins) and the idle share is 1 - M/N.  A timeshared host cannot
    surface idle slots as wall-clock (the fill/drain skip exists to
    erase them), so the wall pair would measure the emulator, not the
    schedule; the trip count is chip-independent and must match the
    cost model's (S-1)/(S+M-1) (conveyor-adjusted) EXACTLY —
    ``bubble_within_floor`` pins it.  Both headline keys are
    trend-sentinel TRACKED (tools/trend.py)."""
    import itertools
    import re as _re
    import jax
    import optax
    from autodist_tpu import AutoDist, observability
    from autodist_tpu.autodist import _reset_default
    from autodist_tpu.models import lm as lm_mod
    from autodist_tpu.pipeline import observe
    from autodist_tpu.strategy import Pipeline

    n_chips = len(jax.devices())
    cfg = lm_mod.lm_tiny(max_len=64)
    cfg.num_layers = 4
    cfg.scan_layers = True
    cfg.dim = 128
    cfg.mlp_dim = 512
    params = lm_mod.init(jax.random.PRNGKey(0), cfg)
    loss_fn = lm_mod.make_loss_fn(cfg)
    batch = lm_mod.synthetic_batch(cfg, batch_size=16, seq_len=64)

    arms = ("shift", "sequential")
    runners, states, items = {}, {}, {}
    for arm in arms:
        os.environ["AUTODIST_PIPELINE_SCHEDULE"] = arm
        _reset_default()
        ad = AutoDist(strategy_builder=Pipeline(num_stages=stages,
                                                num_microbatches=micro))
        items[arm] = ad.capture(loss_fn, params, optax.adam(1e-3),
                                example_batch=batch)
        runners[arm] = ad.create_distributed_session(items[arm])
        states[arm] = runners[arm].create_state()
        # The ParallelContext reads AUTODIST_PIPELINE_SCHEDULE lazily at
        # first use — materialize it NOW, while this arm's env value is
        # set, so the interleaved warm/timing loops below can't leak the
        # last arm's schedule into every program.
        assert runners[arm].program.parallel_context() \
            .pipeline_schedule == arm

    # Warm (compile) + the bitwise contract: identical init, identical
    # batches => identical per-step losses across both schedules.
    warm_losses = {arm: [] for arm in arms}
    for _ in range(2):
        for arm in arms:
            states[arm], m = runners[arm].step(states[arm], batch)
            warm_losses[arm].append(float(jax.device_get(m["loss"])))
    assert warm_losses["shift"] == warm_losses["sequential"], \
        f"schedule numerics diverged: {warm_losses}"

    # Structural bubble: trace each arm's loss under its own parallel
    # context and read the schedule scan's trip count (its scan is the
    # longest in the program: the stage bodies scan only L/S layers).
    def schedule_ticks(arm):
        from autodist_tpu.parallel import context as pctx
        import jax.numpy as jnp
        prog = runners[arm].program
        item = items[arm]
        structs = jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(jnp.shape(l), jnp.result_type(l)),
            item.params)
        with pctx.use(prog.parallel_context()):
            # Fresh lambda: make_jaxpr rides the jit trace cache, and
            # capture already traced item.loss_fn WITHOUT the context
            # (single-device semantics) — a cached hit would silently
            # show the unpipelined program.
            text = str(jax.make_jaxpr(
                lambda p, b: item.loss_fn(p, b))(structs,
                                                 item.batch_struct))
        return max(int(x) for x in _re.findall(r"length=(\d+)", text))

    ticks = {arm: schedule_ticks(arm) for arm in arms}
    bubble = 1.0 - micro / ticks["shift"]
    predicted = observe.predicted_bubble(stages, micro)
    assert ticks["sequential"] == micro * stages, ticks

    seg_ms = {arm: [] for arm in arms}
    for _ in range(segments):
        for arm in arms:
            t0 = time.perf_counter()
            for _ in range(steps_per_segment):
                states[arm], m = runners[arm].step(states[arm], batch)
            jax.block_until_ready(m["loss"])
            seg_ms[arm].append(
                (time.perf_counter() - t0) / steps_per_segment * 1e3)
    loss = float(jax.device_get(m["loss"]))
    assert np.isfinite(loss), f"non-finite loss {loss}"

    best = {arm: min(v) for arm, v in seg_ms.items()}
    speedup = best["sequential"] / best["shift"]
    # A short observed run on the shift arm populates the pipeline.*
    # gauges + the attribution/goodput ledgers for the details sidecar.
    states["shift"], _ = runners["shift"].run(
        states["shift"], itertools.repeat(batch), 4)
    gauges = observability.registry().snapshot().get("gauges") or {}
    print(json.dumps({
        "pipeline_speedup": round(speedup, 4),
        "bubble_fraction": round(bubble, 4),
        "bubble_predicted": round(predicted, 4),
        "bubble_error": round(bubble - predicted, 4),
        "bubble_within_floor": bool(abs(bubble - predicted) < 1e-9),
        "schedule_ticks": ticks,
        "stages": stages, "microbatches": micro,
        "ms_per_step": {a: round(best[a], 3) for a in arms},
        "segments_ms_per_step": {a: [round(x, 3) for x in v]
                                 for a, v in seg_ms.items()},
        "warm_losses_bitwise": True,
        "pipeline_gauges": {k: v for k, v in gauges.items()
                            if k.startswith("pipeline.")},
        "attribution": _attribution_summary(),
        "profile": _profile_summary(),
        "goodput": _goodput_summary(),
        "skew": _skew_summary(),
        "steps_per_segment": steps_per_segment, "segments": segments,
        "loss": loss, "n_chips": n_chips}))


def _worker_mem(steps=6, unrolls=(1, 8)):
    """HBM memory ledger point (ISSUE 17, docs/memory.md): the zoo
    transformer driven through SHORT observed loops in four arms — PS
    with staleness (stale local-SGD: fully replicated optimizer state)
    vs PS zero1 (state sharded 1/N) at unroll 1 and 8 — each arm
    finalizing its own MemoryLedger, so the predicted per-class split,
    the measured boundary-sample peak, and the reconciliation error are
    all persisted per arm.

    Structural assertions ride along: the predicted classes sum exactly
    to the predicted peak, zero1's optimizer class undercuts stale-PS
    replication on a multi-chip mesh, and unroll=8 grows the staging
    class.  ``mem_peak_gb`` (worst-arm measured peak) and
    ``mem_prediction_error_pct`` (worst-arm |reconciliation error|) are
    trend-sentinel TRACKED (tools/trend.py)."""
    import gc
    import itertools
    import jax
    import optax
    from autodist_tpu import AutoDist, observability
    from autodist_tpu.autodist import _reset_default
    from autodist_tpu.models import lm as lm_mod
    from autodist_tpu.strategy import PS

    n_chips = len(jax.devices())
    cfg = lm_mod.lm_tiny(max_len=64)
    cfg.dim = 128
    cfg.mlp_dim = 512
    params = lm_mod.init(jax.random.PRNGKey(0), cfg)
    loss_fn = lm_mod.make_loss_fn(cfg)
    batch = lm_mod.synthetic_batch(cfg, batch_size=8 * max(1, n_chips),
                                   seq_len=64)

    arms = {}
    for name, staleness in (("ps", 2), ("zero1", 0)):
        for k in unrolls:
            _reset_default()
            observability.reset()
            ad = AutoDist(strategy_builder=PS(staleness=staleness))
            item = ad.capture(loss_fn, params, optax.adam(1e-3),
                              example_batch=batch)
            runner = ad.create_distributed_session(item)
            state = runner.create_state()
            state, _ = runner.run(state, itertools.repeat(batch),
                                  max(steps, 2 * k), unroll=k)
            summ = observability.memory.last_summary() or {}
            pred = summ.get("predicted") or {}
            peak = summ.get("predicted_peak_bytes") or 0.0
            assert not pred or \
                abs(sum(pred.values()) - peak) <= 1e-6 * max(peak, 1.0), \
                f"class-sum broken: {pred} vs {peak}"
            arms[f"{name}/unroll={k}"] = {
                "predicted_peak_gb": summ.get("predicted_peak_gb"),
                "measured_peak_gb": summ.get("measured_peak_gb"),
                "prediction_error_pct": summ.get("prediction_error_pct"),
                "dominant_class": summ.get("dominant_class"),
                "measured_source": summ.get("measured_source"),
                "predicted_gb": {c: round(v / (1 << 30), 6)
                                 for c, v in pred.items()},
            }
            # Free this arm's device state before the next arm measures:
            # live_arrays boundary samples must not see dead arms.
            del runner, state, item, ad
            gc.collect()

    z = (arms.get("zero1/unroll=1") or {}).get("predicted_gb") or {}
    p = (arms.get("ps/unroll=1") or {}).get("predicted_gb") or {}
    if z and p and n_chips > 1:
        assert z["optimizer_bytes"] < p["optimizer_bytes"], \
            f"zero1 state not sharded: {z} vs {p}"
    s1 = (arms.get("zero1/unroll=1") or {}).get("predicted_gb") or {}
    s8 = (arms.get("zero1/unroll=8") or {}).get("predicted_gb") or {}
    if s1 and s8:
        assert s8["staging_bytes"] > s1["staging_bytes"], \
            f"unroll staging not charged: {s1} vs {s8}"

    measured = [a["measured_peak_gb"] for a in arms.values()
                if a.get("measured_peak_gb")]
    errors = [abs(a["prediction_error_pct"]) for a in arms.values()
              if a.get("prediction_error_pct") is not None]
    print(json.dumps({
        "mem_peak_gb": round(max(measured), 6) if measured else None,
        "mem_prediction_error_pct": (round(max(errors), 2)
                                     if errors else None),
        "arms": arms,
        "n_chips": n_chips}))


def _worker_loader(steps=LOADER_STEPS, warmup=LOADER_WARMUP, window=10):
    """Loader-fed steady state NEXT TO its rooflines, all in ONE process:

    1. pure-H2D wire window (pipelined uint8 transfers, depth 2 in
       flight, no host work);
    2. input-pipeline ceiling window (wire + synchronous batch assembly,
       ONE transfer in flight — the fully serialized bound);
    2b. pure-assembly window (loader only, no device): the host-side
       memcpy cost, persisted as the assemble side of the
       assemble-vs-transfer breakdown;
    3. loader-fed train window: C++ loader (buffer-pool staging + native
       async assembly ring) -> depth-N DevicePrefetcher (explicit
       completion handles, settled just-in-time, staging buffers recycled
       on transfer retire) -> AOT step.

    All four windows run in one process so their ratios compare like with
    like; the controls run first.  ``steady_ips`` is the best
    consecutive-``window`` mean.

    With a single transfer in flight every batch pays the transfer's full
    latency (window 2's serialized bound); with depth >= 2 the wire drains
    back-to-back."""
    import jax
    from collections import deque
    n_chips = len(jax.devices())
    bs = BATCH * max(1, n_chips)
    depth = int(os.environ.get("AUTODIST_PREFETCH_DEPTH", "2"))
    params, u8_loss, u8_batch = _u8_fixture(bs)
    runner, state, step_fn = _build_framework_step(params, u8_loss, u8_batch)

    from autodist_tpu.data import (DevicePrefetcher, NativeDataLoader,
                                   write_record_file)
    n_rec = 4 * bs
    images = np.tile(u8_batch[0], (n_rec // bs + 1, 1, 1, 1))[:n_rec]
    labels = u8_batch[1]
    dev = jax.devices()[0]
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "images.rec")
        write_record_file(path, images)

        # -- window 1: pure-H2D wire (depth 2 in flight) --
        img = images[:bs]
        q = deque()
        for _ in range(2):
            q.append(jax.device_put(img, dev))
        for _ in range(5):
            jax.block_until_ready(q.popleft())
            q.append(jax.device_put(img, dev))
        t0 = time.perf_counter()
        for _ in range(30):
            jax.block_until_ready(q.popleft())
            q.append(jax.device_put(img, dev))
        dt_wire = (time.perf_counter() - t0) / 30

        # -- window 2: wire + SYNCHRONOUS assembly (the serialized bound) --
        ceil_loader = NativeDataLoader(path, (224, 224, 3), np.uint8, bs,
                                       num_threads=0, pipeline=False)
        pend = jax.device_put(next(ceil_loader), dev)
        for _ in range(3):
            jax.block_until_ready(pend)
            pend = jax.device_put(next(ceil_loader), dev)
        t0 = time.perf_counter()
        for _ in range(30):
            jax.block_until_ready(pend)
            pend = jax.device_put(next(ceil_loader), dev)
        dt_ceil = (time.perf_counter() - t0) / 30
        ceil_loader.close()

        # -- window 2b: pure assembly (no device): the assemble side of the
        # breakdown; pool-recycled so it measures memcpy, not allocation --
        asm_loader = NativeDataLoader(path, (224, 224, 3), np.uint8, bs,
                                      num_threads=0, pipeline=False)
        for _ in range(3):
            asm_loader.recycle(next(asm_loader))
        t0 = time.perf_counter()
        for _ in range(30):
            asm_loader.recycle(next(asm_loader))
        dt_asm = (time.perf_counter() - t0) / 30
        asm_loader.close()

        # -- window 3: loader-FED training (shipped defaults: buffer-pool
        # staging, async assembly ring, depth-N prefetch with recycle) ----
        loader = NativeDataLoader(path, (224, 224, 3), np.uint8, bs)
        backend = loader.backend
        feed_it = DevicePrefetcher(((img, labels) for img in loader),
                                   runner.remapper, depth=depth,
                                   loader=loader)
        out = None
        for _ in range(warmup):
            state, out = step_fn(state, next(feed_it))
        jax.block_until_ready(out["loss"])
        dts = []
        t_prev = time.perf_counter()
        for i in range(steps):
            state, out = step_fn(state, next(feed_it))
            if i == steps - 1:
                # Drain the device queue INSIDE the timed region so the
                # full-window mean shares _time_loop's timing contract
                # (advisor r4: per-step host gaps alone over-report if the
                # device lags the host).  Interior steps stay gap-timed —
                # the prefetcher's ordering rule (transfers issue only
                # after the previous step dispatched, settled just-in-time)
                # bounds host run-ahead to ~depth steps.
                jax.block_until_ready(out["loss"])
            t_now = time.perf_counter()
            dts.append(t_now - t_prev)
            t_prev = t_now
        loss = float(jax.device_get(out["loss"]))
        assert np.isfinite(loss), f"non-finite loss {loss}"
        feed_stats = feed_it.stats()
        loader_stats = loader.stats()
        loader.close()
        # Short observed loop: the attribution ledger decomposes this
        # worker's step time (data-wait vs compute vs residual) so the
        # 0.784-gate record carries causes, not just a ratio.
        try:
            import itertools
            state, _ = runner.run(
                state, itertools.repeat((images[:bs], labels)), 6)
        except Exception as e:  # noqa: BLE001 - breakdown is best-effort
            sys.stderr.write(f"bench: loader attribution run: {e}\n")
    spp = sum(dts) / len(dts)
    best = min(sum(dts[i:i + window]) / window
               for i in range(len(dts) - window + 1))
    print(json.dumps({"ips": bs / spp, "ms_per_step": spp * 1e3,
                      "steady_ips": bs / best,
                      "steady_ms_per_step": best * 1e3,
                      "steady_window": window,
                      "wire_ips": bs / dt_wire,
                      "assembly_ceiling_ips": bs / dt_ceil,
                      "steady_vs_wire": round(dt_wire / best, 4),
                      "steady_vs_ceiling": round(dt_ceil / best, 4),
                      "breakdown": {
                          "assemble_ms_per_batch": round(dt_asm * 1e3, 3),
                          "transfer_ms_per_batch": round(dt_wire * 1e3, 3),
                          "serialized_ms_per_batch": round(dt_ceil * 1e3, 3),
                          "data_wait_ms_mean": feed_stats[
                              "data_wait_ms_mean"],
                          "pool_fallback_allocs": loader_stats[
                              "pool_fallback_allocs"]},
                      "prefetch_depth": depth,
                      "attribution": _attribution_summary(),
                      "profile": _profile_summary(),
                      "goodput": _goodput_summary(),
                      "skew": _skew_summary(),
                      "steps": steps, "loss": loss,
                      "loader_backend": backend, "n_chips": n_chips}))


def _worker_dispatch(steps_per_segment=256, segments=4):
    """Host-dispatch amortization curve: a TINY model (device compute is
    microseconds, so per-step time is dominated by the per-dispatch host
    cost) driven at ``unroll in {1, 8, 32}`` in ONE process, segments
    interleaved round-robin so drift hits every arm identically —
    the same pairing discipline as the headline.

    Every arm pays the same per-dispatch feeding cost (one
    ``shard_block``/``shard_batch`` per dispatch from a resident host
    block) so the ms-per-step difference isolates what unroll amortizes:
    jit dispatch + placement + clock reads.  ``dispatch_overhead_ms_per_
    step`` fits ``t(K) = compute + host/K`` on the measured points
    (least squares over 1/K) and reports the measured per-step overhead
    above the fitted compute floor per K; ``unroll_speedup`` is the raw
    t(1)/t(K).  Persisted to BENCH_DETAILS.json so the host-overhead
    trajectory is tracked run-over-run like the loader breakdown."""
    import jax
    import jax.numpy as jnp
    import optax
    from autodist_tpu import AutoDist
    from autodist_tpu.strategy import AllReduce
    n_chips = len(jax.devices())
    bs = 32 * max(1, n_chips)
    rng = np.random.RandomState(0)
    params = {"w": jnp.zeros((16, 4)), "b": jnp.zeros((4,))}
    batch = (rng.randn(bs, 16).astype(np.float32),
             rng.randn(bs, 4).astype(np.float32))

    def loss_fn(p, b):
        x, y = b
        return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)

    ad = AutoDist(strategy_builder=AllReduce())
    item = ad.capture(loss_fn, params, optax.sgd(1e-3), example_batch=batch)
    runner = ad.create_distributed_session(item)
    state = runner.create_state()

    unrolls = (1, 8, 32)
    host_blocks = {1: batch}
    for k in unrolls[1:]:
        host_blocks[k] = tuple(np.broadcast_to(a, (k,) + a.shape).copy()
                               for a in batch)

    def run_arm(state, k, n_steps):
        for _ in range(n_steps // k if k > 1 else n_steps):
            if k == 1:
                state, out = runner.step(state, host_blocks[1])
            else:
                state, out = runner.megastep(state, host_blocks[k])
        jax.block_until_ready(out["loss"])
        return state, out

    # Warm every arm (compiles all three programs) before timing.
    for k in unrolls:
        state, out = run_arm(state, k, 2 * k)
    seg_ms = {k: [] for k in unrolls}
    for _ in range(segments):
        for k in unrolls:
            t0 = time.perf_counter()
            state, out = run_arm(state, k, steps_per_segment)
            seg_ms[k].append(
                (time.perf_counter() - t0) / steps_per_segment * 1e3)
    last = np.asarray(jax.device_get(out["loss"]))
    loss = float(last.ravel()[-1])  # scalar at unroll=1, stacked (K,) above
    assert np.isfinite(loss), f"non-finite loss {loss}"

    best = {k: min(v) for k, v in seg_ms.items()}
    # Fit t(K) = compute + host/K over the measured points (x = 1/K).
    xs = np.array([1.0 / k for k in unrolls])
    ts = np.array([best[k] for k in unrolls])
    host_ms, compute_ms = np.polyfit(xs, ts, 1)
    compute_ms = max(0.0, float(compute_ms))
    overhead = {str(k): round(max(0.0, best[k] - compute_ms), 5)
                for k in unrolls}
    # Persist the fitted per-dispatch host overhead into the tuner
    # calibration: the attribution ledger's host-dispatch term reads it
    # instead of the DISPATCH_MS seed on every later run on this host.
    host_dispatch_persisted = None
    try:
        from autodist_tpu.tuner.calibration import Calibration
        cal = Calibration.load()
        cal.host_dispatch_ms = round(max(0.0, float(host_ms)), 5)
        if cal.save():
            host_dispatch_persisted = cal.host_dispatch_ms
    except Exception as e:  # noqa: BLE001 - calibration is best-effort
        sys.stderr.write(f"bench: host-dispatch calibration: {e}\n")
    # A short observed unrolled loop populates the attribution ledger.
    try:
        import itertools
        state, _ = runner.run(state, itertools.repeat(batch), 32, unroll=8)
    except Exception as e:  # noqa: BLE001 - breakdown is best-effort
        sys.stderr.write(f"bench: dispatch attribution run: {e}\n")
    print(json.dumps({
        "ms_per_step": {str(k): round(best[k], 5) for k in unrolls},
        "segments_ms_per_step": {str(k): [round(x, 5) for x in v]
                                 for k, v in seg_ms.items()},
        "dispatch_overhead_ms_per_step": overhead,
        "per_dispatch_host_ms": round(float(host_ms), 5),
        "compute_floor_ms": round(compute_ms, 5),
        "overhead_ratio_32_vs_1": round(
            (best[32] - compute_ms) / max(1e-9, best[1] - compute_ms), 5),
        "unroll_speedup": round(best[1] / best[32], 4),
        "unroll_speedup_8": round(best[1] / best[8], 4),
        "host_dispatch_ms_calibrated": host_dispatch_persisted,
        "attribution": _attribution_summary(),
        "profile": _profile_summary(),
        "goodput": _goodput_summary(),
        "skew": _skew_summary(),
        "steps_per_segment": steps_per_segment, "segments": segments,
        "loss": loss, "n_chips": n_chips}))


def _worker_overlap(steps_per_segment=64, segments=4, unroll=4):
    """Latency-hiding collective scheduler point (ISSUE 7): the SAME
    model/strategy driven with the overlap scheduler on vs off, PAIRED —
    both arms alternate round-robin segments in one process (the headline
    pairing discipline), with the async-collective XLA flags enabled for
    the whole process so the two arms differ only in program structure:
    reverse-layer bucket issue + the megastep weight-AG reorder (on) vs
    the serialized post-backward schedule (off).

    The strategy is PS-LB (small vars fuse into bucketed all-reduce, the
    big one goes ZeRO) at ``unroll=4`` megasteps, so BOTH overlap
    mechanisms are exercised.  ``comms_exposed_ms_per_step`` per arm is
    parsed from each arm's *scheduled* single-step HLO
    (``Runner.dump_scheduled`` -> ``kernel/overlap`` pricing).  Persisted
    to BENCH_DETAILS.json and tracked run-over-run like the dispatch
    curve."""
    os.environ["AUTODIST_OVERLAP"] = "1"   # flags before backend init
    from autodist_tpu.kernel import overlap as overlap_mod
    overlap_mod.apply_overlap_flags()
    import jax
    import jax.numpy as jnp
    import optax
    from autodist_tpu import AutoDist
    from autodist_tpu.autodist import _reset_default
    from autodist_tpu.strategy import PSLoadBalancing
    n_chips = len(jax.devices())
    bs = 16 * max(1, n_chips)
    rng = np.random.RandomState(0)
    dims = (64, 256, 256, 64, 8)
    params = {f"w{i}": jnp.zeros((dims[i], dims[i + 1]))
              for i in range(len(dims) - 1)}
    batch = (rng.randn(bs, dims[0]).astype(np.float32),
             rng.randn(bs, dims[-1]).astype(np.float32))

    def loss_fn(p, b):
        x, y = b
        h = x
        for i in range(len(dims) - 1):
            h = h @ p[f"w{i}"]
            if i < len(dims) - 2:
                h = jax.nn.relu(h)
        return jnp.mean((h - y) ** 2)

    def build(on):
        os.environ["AUTODIST_OVERLAP"] = "1" if on else "0"
        _reset_default()
        ad = AutoDist(strategy_builder=PSLoadBalancing(
            shard_threshold_bytes=128 << 10))
        item = ad.capture(loss_fn, params, optax.adam(1e-3),
                          example_batch=batch)
        return ad.create_distributed_session(item)

    runners = {"off": build(False), "on": build(True)}
    host_block = tuple(np.broadcast_to(a, (unroll,) + a.shape).copy()
                       for a in batch)
    states = {arm: r.create_state() for arm, r in runners.items()}

    def run_arm(arm, n_steps):
        state = states[arm]
        for _ in range(n_steps // unroll):
            state, out = runners[arm].megastep(state, host_block)
        jax.block_until_ready(out["loss"])
        states[arm] = state
        return out

    for arm in runners:  # warm/compile both megastep programs
        run_arm(arm, 2 * unroll)
    seg_ms = {arm: [] for arm in runners}
    for _ in range(segments):
        for arm in runners:
            t0 = time.perf_counter()
            out = run_arm(arm, steps_per_segment)
            seg_ms[arm].append(
                (time.perf_counter() - t0) / steps_per_segment * 1e3)
    loss = float(np.asarray(jax.device_get(out["loss"])).ravel()[-1])
    assert np.isfinite(loss), f"non-finite loss {loss}"

    exposed = {}
    for arm, r in runners.items():
        try:
            path = r.dump_scheduled(batch)
            # dump_scheduled writes the parsed async-window summary as a
            # .windows.json sidecar — read it instead of re-parsing.
            try:
                with open(path.replace(".txt", ".windows.json")) as f:
                    exposed[arm] = round(
                        json.load(f)["exposed_ms_per_step"], 4)
            except (OSError, KeyError, ValueError):
                with open(path) as f:
                    exposed[arm] = round(overlap_mod.exposed_collective_ms(
                        f.read()), 4)
        except Exception as e:  # noqa: BLE001 - structural metric only
            sys.stderr.write(f"bench: exposed-comms parse ({arm}): {e}\n")
            exposed[arm] = None

    best = {arm: min(v) for arm, v in seg_ms.items()}
    # Observed loop on the overlap arm: attribution with the scheduled-
    # HLO exposed-comms gauge in place (the AOT path set it above).
    try:
        import itertools
        states["on"], _ = runners["on"].run(
            states["on"], itertools.repeat(batch), 4 * unroll,
            unroll=unroll)
    except Exception as e:  # noqa: BLE001 - breakdown is best-effort
        sys.stderr.write(f"bench: overlap attribution run: {e}\n")
    print(json.dumps({
        "overlap_ms_per_step": round(best["on"], 5),
        "serial_ms_per_step": round(best["off"], 5),
        "overlap_speedup": round(best["off"] / best["on"], 4),
        "comms_exposed_ms_per_step": exposed,
        "segments_ms_per_step": {a: [round(x, 5) for x in v]
                                 for a, v in seg_ms.items()},
        "xla_overlap_flags": list(overlap_mod.overlap_xla_flags()),
        "attribution": _attribution_summary(),
        "profile": _profile_summary(),
        "goodput": _goodput_summary(),
        "skew": _skew_summary(),
        "unroll": unroll, "steps_per_segment": steps_per_segment,
        "segments": segments, "loss": loss, "n_chips": n_chips}))


def _worker_compress(steps_per_segment=64, segments=4):
    """Compressed-collective point (ROADMAP item 2's bench story): the
    SAME model trained under f32 AllReduce vs each compressed wire —
    bf16 (HorovodCompressor), blockwise-int8+EF, PowerSGD — all arms
    alternating round-robin segments in ONE process (the headline
    pairing discipline), so ``compress_speedup`` per compressor is a
    paired ratio against the f32 arm.

    Wire bytes per step per arm come from the tuner cost model's
    compressor-exact accounting (bf16 0.5x, int8 ~0.254x, PowerSGD
    r*(m+n)/(m*n)) — the number that says how much DCN traffic the
    compressor removes even when this host's compute-bound arms tie.
    Persisted to BENCH_DETAILS.json and tracked run-over-run like the
    overlap curve."""
    import jax
    import jax.numpy as jnp
    import optax
    from autodist_tpu import AutoDist
    from autodist_tpu.autodist import _reset_default
    from autodist_tpu.strategy import AllReduce
    from autodist_tpu.tuner.cost_model import CostModel, Topology
    n_chips = len(jax.devices())
    bs = 16 * max(1, n_chips)
    rng = np.random.RandomState(0)
    dims = (64, 512, 512, 8)
    params = {f"w{i}": jnp.zeros((dims[i], dims[i + 1]))
              for i in range(len(dims) - 1)}
    batch = (rng.randn(bs, dims[0]).astype(np.float32),
             rng.randn(bs, dims[-1]).astype(np.float32))

    def loss_fn(p, b):
        x, y = b
        h = x
        for i in range(len(dims) - 1):
            h = h @ p[f"w{i}"]
            if i < len(dims) - 2:
                h = jax.nn.relu(h)
        return jnp.mean((h - y) ** 2)

    arms = {"f32": None, "bf16": "HorovodCompressor",
            "int8_ef": "Int8CompressorEF", "powersgd": "PowerSGDCompressor"}

    def build(compressor):
        _reset_default()
        ad = AutoDist(strategy_builder=AllReduce(compressor=compressor)
                      if compressor else AllReduce())
        item = ad.capture(loss_fn, params, optax.sgd(1e-3),
                          example_batch=batch)
        return ad.create_distributed_session(item)

    runners = {arm: build(comp) for arm, comp in arms.items()}
    states = {arm: r.create_state() for arm, r in runners.items()}
    losses = {}

    def run_arm(arm, n_steps):
        state = states[arm]
        for _ in range(n_steps):
            state, out = runners[arm].step(state, batch)
        jax.block_until_ready(out["loss"])
        states[arm] = state
        losses[arm] = float(jax.device_get(out["loss"]))

    for arm in runners:  # warm/compile every arm before timing
        run_arm(arm, 2)
    seg_ms = {arm: [] for arm in runners}
    for _ in range(segments):
        for arm in runners:
            t0 = time.perf_counter()
            run_arm(arm, steps_per_segment)
            seg_ms[arm].append(
                (time.perf_counter() - t0) / steps_per_segment * 1e3)
    for arm, loss in losses.items():
        assert np.isfinite(loss), f"non-finite {arm} loss {loss}"

    best = {arm: min(v) for arm, v in seg_ms.items()}
    topo = Topology(max(1, n_chips))
    wire_mb = {}
    for arm, r in runners.items():
        try:
            wire_mb[arm] = round(CostModel(topo).strategy_cost(
                r.program.strategy, r.program.graph_item)["wire_mb"], 4)
        except Exception:  # noqa: BLE001 - structural metric only
            wire_mb[arm] = None
    print(json.dumps({
        "ms_per_step": {arm: round(v, 5) for arm, v in best.items()},
        "compress_speedup": {arm: round(best["f32"] / best[arm], 4)
                             for arm in arms if arm != "f32"},
        "wire_mb_per_step": wire_mb,
        "wire_vs_f32": {arm: round(wire_mb[arm] / wire_mb["f32"], 4)
                        for arm in arms
                        if arm != "f32" and wire_mb.get(arm)
                        and wire_mb.get("f32")},
        "segments_ms_per_step": {a: [round(x, 5) for x in v]
                                 for a, v in seg_ms.items()},
        "losses": {a: round(l, 6) for a, l in losses.items()},
        "steps_per_segment": steps_per_segment, "segments": segments,
        "n_chips": n_chips}))


def _worker_hier(steps_per_segment=48, segments=4):
    """Hierarchical two-level collectives point (docs/collectives.md):
    the SAME model trained under the flat f32 AllReduce vs the
    hierarchical family — full-precision reduce-scatter / all-gather on
    the intra-host (ICI) leg, bf16 or blockwise-int8+EF wire only
    across the cross-host (DCN) leg — on a forced two-host CPU mesh
    (8 devices split d=4 x h=2 via ``AUTODIST_HIER_ICI``).  All arms
    alternate round-robin segments in ONE process; ``hier_speedup`` is
    the paired step-time ratio against the flat arm.

    The wire story is the point on a compute-bound CPU host:
    ``hier_wire_dcn_ratio`` compares each hier arm's DCN-leg bytes —
    MEASURED from the tally the kernels record at trace time — against
    the flat f32 ring's DCN share, and ``wire_match_pred`` checks that
    measured tally against the tuner cost model's ``hier_wire_split``
    prediction: the byte-for-byte equality that lets the tuner trust
    its per-leg pricing.  Persisted to BENCH_DETAILS.json and tracked
    run-over-run."""
    import jax
    import jax.numpy as jnp
    import optax
    from autodist_tpu import AutoDist
    from autodist_tpu.autodist import _reset_default
    from autodist_tpu.kernel.synchronization import hierarchical
    from autodist_tpu.strategy import AllReduce
    from autodist_tpu.tuner.cost_model import CostModel, Topology
    n_chips = len(jax.devices())
    d, n_hosts = hierarchical.resolve_legs(n_chips)
    bs = 16 * max(1, n_chips)
    rng = np.random.RandomState(0)
    dims = (64, 512, 512, 8)
    params = {f"w{i}": jnp.zeros((dims[i], dims[i + 1]))
              for i in range(len(dims) - 1)}
    batch = (rng.randn(bs, dims[0]).astype(np.float32),
             rng.randn(bs, dims[-1]).astype(np.float32))

    def loss_fn(p, b):
        x, y = b
        act = x
        for i in range(len(dims) - 1):
            act = act @ p[f"w{i}"]
            if i < len(dims) - 2:
                act = jax.nn.relu(act)
        return jnp.mean((act - y) ** 2)

    # arm -> (compressor, hier codec the cost model prices it as)
    arms = {"flat_f32": (None, None),
            "hier_bf16": ("HorovodCompressor", "bf16"),
            "hier_int8ef": ("Int8CompressorEF", "int8ef")}

    runners, states, measured, losses = {}, {}, {}, {}

    def run_arm(arm, n_steps):
        state = states[arm]
        for _ in range(n_steps):
            state, out = runners[arm].step(state, batch)
        jax.block_until_ready(out["loss"])
        states[arm] = state
        losses[arm] = float(jax.device_get(out["loss"]))

    for arm, (comp, _codec) in arms.items():
        _reset_default()
        builder = (AllReduce(all_reduce_spec="DCN", compressor=comp)
                   if comp else AllReduce())
        ad = AutoDist(strategy_builder=builder)
        item = ad.capture(loss_fn, params, optax.sgd(1e-3),
                          example_batch=batch)
        hierarchical.reset_wire_tally()
        runners[arm] = ad.create_distributed_session(item)
        states[arm] = runners[arm].create_state()
        run_arm(arm, 2)  # warm/compile; the trace records the tally once
        measured[arm] = hierarchical.wire_tally()

    seg_ms = {arm: [] for arm in runners}
    for _ in range(segments):
        for arm in runners:
            t0 = time.perf_counter()
            run_arm(arm, steps_per_segment)
            seg_ms[arm].append(
                (time.perf_counter() - t0) / steps_per_segment * 1e3)
    for arm, loss in losses.items():
        assert np.isfinite(loss), f"non-finite {arm} loss {loss}"

    best = {arm: min(v) for arm, v in seg_ms.items()}
    payload = sum(float(v.size_bytes) for v in
                  runners["flat_f32"].program.graph_item.trainable_variables)
    topo = Topology(max(1, n_chips), num_hosts=n_hosts)
    flat_split = topo.flat_wire_split(2.0 * payload, n_chips)
    predicted, dcn_ratio, wire_match = {}, {}, {}
    for arm, (_comp, codec) in arms.items():
        if codec is None:
            predicted[arm] = flat_split
            continue
        predicted[arm] = topo.hier_wire_split(payload, n_chips, codec)
        if flat_split["dcn"] > 0:
            dcn_ratio[arm] = round(
                measured[arm]["dcn"] / flat_split["dcn"], 4)
        if predicted[arm]["dcn"] > 0:
            wire_match[arm] = round(
                measured[arm]["dcn"] / predicted[arm]["dcn"], 4)
    hier_best = min(best[a] for a in arms if a != "flat_f32")
    print(json.dumps({
        "ms_per_step": {arm: round(v, 5) for arm, v in best.items()},
        "hier_speedup": round(best["flat_f32"] / hier_best, 4),
        "hier_speedup_per_arm": {
            arm: round(best["flat_f32"] / best[arm], 4)
            for arm in arms if arm != "flat_f32"},
        "hier_wire_dcn_ratio": (min(dcn_ratio.values())
                                if dcn_ratio else None),
        "wire_dcn_ratio_per_arm": dcn_ratio,
        "wire_match_pred": wire_match,
        "wire_bytes_measured": {a: {k: round(v, 1) for k, v in m.items()}
                                for a, m in measured.items()},
        "wire_bytes_predicted": {a: {k: round(v, 1) for k, v in p.items()}
                                 for a, p in predicted.items()},
        "legs": {"ici": d, "dcn": n_hosts},
        "segments_ms_per_step": {a: [round(x, 5) for x in v]
                                 for a, v in seg_ms.items()},
        "losses": {a: round(l, 6) for a, l in losses.items()},
        "steps_per_segment": steps_per_segment, "segments": segments,
        "n_chips": n_chips}))


def _worker_elastic(cycles=3, steps_per_segment=24, warmup=4):
    """Elastic N->M resharding point (docs/elasticity.md): paired
    save -> kill -> reshard-resume cycles in ONE process.  A PS
    (zero1-sharded optimizer state) run on the full mesh saves
    checkpoints + manifests; the "fleet change" rebuilds the session on
    HALF the devices, and every cycle's cross-shape restore is timed —
    ``reshard_restore_ms`` is the price of surviving a shrink.

    The post-resume arm then steps the resharded state against a
    fresh-init state on the SAME shrunk runner (paired within one
    process, same compile): ``post_resume_latency_delta_pct`` near zero
    is the durable signal that a reshard-restored state carries no
    step-time poison (bad layouts would show up as per-step
    re-transfers).  Value-exactness of params across the shape change is
    asserted, not assumed.  Persisted to BENCH_DETAILS.json and tracked
    run-over-run like the overlap curve."""
    import jax
    import jax.numpy as jnp
    import optax
    from autodist_tpu import AutoDist
    from autodist_tpu.autodist import _reset_default
    from autodist_tpu.checkpoint import Saver
    from autodist_tpu.strategy import PS
    n_chips = len(jax.devices())
    if n_chips < 2:
        print(json.dumps({"skipped": "elastic shrink needs >= 2 devices",
                          "n_chips": n_chips}))
        return
    half = n_chips // 2
    bs = 16 * n_chips
    rng = np.random.RandomState(0)
    dims = (64, 256, 256, 8)
    params = {f"w{i}": jnp.zeros((dims[i], dims[i + 1]))
              for i in range(len(dims) - 1)}
    batch = (rng.randn(bs, dims[0]).astype(np.float32),
             rng.randn(bs, dims[-1]).astype(np.float32))

    def loss_fn(p, b):
        x, y = b
        h = x
        for i in range(len(dims) - 1):
            h = h @ p[f"w{i}"]
            if i < len(dims) - 2:
                h = jax.nn.relu(h)
        return jnp.mean((h - y) ** 2)

    def build(devices=None, mesh_axes=None):
        _reset_default()
        ad = AutoDist(strategy_builder=PS(), devices=devices,
                      mesh_axes=mesh_axes)
        item = ad.capture(loss_fn, params, optax.adam(1e-3),
                          example_batch=batch)
        return ad.create_distributed_session(item)

    def time_steps(runner, state):
        for _ in range(warmup):
            state, out = runner.step(state, batch)
        jax.block_until_ready(out["loss"])
        t0 = time.perf_counter()
        for _ in range(steps_per_segment):
            state, out = runner.step(state, batch)
        jax.block_until_ready(out["loss"])
        return state, (time.perf_counter() - t0) / steps_per_segment * 1e3

    tmp = tempfile.mkdtemp(prefix="bench_elastic_")
    # Full-mesh phase: train, save one manifest-carrying checkpoint per
    # cycle (the save side of the paired cycle).
    runner_n = build()
    saver_n = Saver(runner_n)
    state = runner_n.create_state()
    state, pre_kill_ms = time_steps(runner_n, state)
    save_ms, ckpts, expect = [], [], None
    for c in range(cycles):
        for _ in range(2):
            state, _ = runner_n.step(state, batch)
        path = os.path.join(tmp, f"cycle{c}")
        t0 = time.perf_counter()
        saver_n.save(state, path)
        save_ms.append((time.perf_counter() - t0) * 1e3)
        ckpts.append(path)
    expect = jax.device_get(runner_n.logical_params(state))

    # The fleet change: same model, HALF the devices.  One compile,
    # every cycle's restore reshards onto it.
    runner_m = build(devices=jax.devices()[:half],
                     mesh_axes={"data": half})
    saver_m = Saver(runner_m)
    reshard_ms, restored = [], None
    for path in ckpts:
        t0 = time.perf_counter()
        restored = saver_m.restore(path)
        jax.block_until_ready(jax.tree_util.tree_leaves(restored.params))
        reshard_ms.append((time.perf_counter() - t0) * 1e3)
    got = jax.device_get(runner_m.logical_params(restored))
    flat_e = jax.tree_util.tree_flatten_with_path(expect)[0]
    flat_g = jax.tree_util.tree_leaves(got)  # same structure, same order
    for (path, a), b in zip(flat_e, flat_g):
        assert np.array_equal(np.asarray(a), np.asarray(b)), \
            f"reshard restore not value-exact at {jax.tree_util.keystr(path)}"

    # Post-resume vs fresh-init on the SAME shrunk runner (paired).
    _, post_ms = time_steps(runner_m, restored)
    _, fresh_ms = time_steps(runner_m, runner_m.create_state())
    print(json.dumps({
        "reshard_restore_ms": round(float(np.median(reshard_ms)), 3),
        "reshard_restore_ms_cycles": [round(v, 3) for v in reshard_ms],
        "save_ms": round(float(np.median(save_ms)), 3),
        "pre_kill_ms_per_step": round(pre_kill_ms, 5),
        "post_resume_ms_per_step": round(post_ms, 5),
        "fresh_state_ms_per_step": round(fresh_ms, 5),
        "post_resume_latency_delta_pct": round(
            (post_ms - fresh_ms) / fresh_ms * 100, 3),
        "value_exact": True,
        "world": {"from_devices": n_chips, "to_devices": half},
        "cycles": cycles, "steps_per_segment": steps_per_segment,
        "n_chips": n_chips}))


def _worker_retune(num_steps=8192, window=16):
    """Online re-tuning controller point (docs/retuning.md): start a
    TINY model on deliberately stale exec knobs — unroll=1, where the
    calibrated per-dispatch host overhead dominates and the tuner's
    pricing prefers unroll 8+ — and let the controller converge mid-run.
    ONE process, one run: the pre-switch windows ARE the stale arm, the
    post-switch windows the corrected arm, so the payoff is paired by
    construction.

    ``retune_payoff_pct`` is the measured p50 improvement (pre-switch vs
    the first steady post-switch window, the controller's own paired
    record); ``retune_switch_ms`` the switch downtime.  Both persist to
    BENCH_DETAILS.json and are trend-sentinel TRACKED, so a controller
    regression (payoff gone, downtime ballooning) fails
    ``bench.py --trend`` loudly."""
    import jax
    import jax.numpy as jnp
    import optax
    from autodist_tpu import AutoDist, retune
    from autodist_tpu.strategy import AllReduce
    os.environ.update({
        "AUTODIST_RETUNE": "exec",
        "AUTODIST_RETUNE_PATIENCE": "2",
        "AUTODIST_GUARD_CHECK_EVERY": str(window),
    })
    n_chips = len(jax.devices())
    bs = 32 * max(1, n_chips)
    rng = np.random.RandomState(0)
    params = {"w": jnp.zeros((16, 4)), "b": jnp.zeros((4,))}
    batch = (rng.randn(bs, 16).astype(np.float32),
             rng.randn(bs, 4).astype(np.float32))

    def loss_fn(p, b):
        x, y = b
        return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)

    ad = AutoDist(strategy_builder=AllReduce())
    item = ad.capture(loss_fn, params, optax.sgd(1e-3), example_batch=batch)
    runner = ad.create_distributed_session(item)
    state = runner.create_state()
    # Warm the stale arm so the first windows measure steady state, not
    # the initial compile.
    for _ in range(4):
        state, out = runner.step(state, batch)
    jax.block_until_ready(out["loss"])

    import itertools
    t0 = time.perf_counter()
    state, out = runner.run(state, itertools.repeat(batch), num_steps,
                            unroll=1)
    wall_s = time.perf_counter() - t0
    loss = float(np.asarray(jax.device_get(out["loss"])).ravel()[-1])
    assert np.isfinite(loss), f"non-finite loss {loss}"
    ctl = retune.last_controller()
    st = ctl.status() if ctl is not None else {}
    switches = st.get("switches") or []
    sw = switches[0] if switches else None
    print(json.dumps({
        "retune_payoff_pct": (sw or {}).get("payoff_pct"),
        "retune_switch_ms": (sw or {}).get("switch_ms"),
        "retune_switches": len(switches),
        "pre_switch_p50_ms": (sw or {}).get("before_p50_ms"),
        "post_switch_p50_ms": (sw or {}).get("after_p50_ms"),
        "switched_to": (sw or {}).get("label"),
        "switch_step": (sw or {}).get("step"),
        "predicted_margin_pct": (sw or {}).get("predicted_margin_pct"),
        "evaluations": st.get("evaluations"),
        "eval_ms_total": st.get("eval_ms"),
        "refusals": st.get("refusals"),
        "regime_flips": st.get("regime_flips"),
        "windows": st.get("windows"),
        "incumbent_after": st.get("incumbent"),
        "attribution": _attribution_summary(),
        "goodput": _goodput_summary(),
        "wall_s": round(wall_s, 3),
        "num_steps": num_steps, "window": window,
        "loss": loss, "n_chips": n_chips}))


def _worker_selfheal(num_steps=256, window=8, drag_ms=40.0):
    """Self-healing fleet point (docs/retuning.md "Reshape-on-degrade"):
    paired control vs degraded arms of the SAME run.  The degraded arm
    injects the ``slow_host`` chaos fault's deterministic per-step delay
    schedule as host 1's drag — the chief pays it as barrier wait inside
    its measured step latency, exactly what an SPMD fleet pays for a
    slow-but-alive host — and feeds the monitor the matching
    skew-decomposed straggler verdict each sync round.  The healer holds
    the verdict against hysteresis, prices the eviction against
    remaining-steps payoff, pins a shrink challenger, and drains the
    checkpoint loop through emergency-save + (stubbed) re-exec; the run
    resumes on half the devices and finishes clean.

    ``degrade_to_decision_ms`` is the measured degradation-onset ->
    eviction-decision latency (the healer's own record);
    ``selfheal_goodput_retained_pct`` the degraded arm's STITCHED
    cross-generation goodput_pct over the undisturbed control arm's —
    how much of the run's goodput self-healing preserved, with the
    drain + re-exec episode billed under the ``selfheal_ms`` class.
    Both persist to BENCH_DETAILS.json and are trend-sentinel TRACKED."""
    import jax
    import jax.numpy as jnp
    import optax
    from autodist_tpu import AutoDist, observability
    from autodist_tpu.autodist import _reset_default
    from autodist_tpu.checkpoint import CheckpointManager
    from autodist_tpu.coordinator import Coordinator
    from autodist_tpu.observability import goodput, monitor, skew
    from autodist_tpu.resilience import ElasticReform, chaos
    from autodist_tpu.retune import selfheal
    from autodist_tpu.strategy import PS
    n_chips = len(jax.devices())
    if n_chips < 2:
        print(json.dumps({"skipped": "selfheal shrink needs >= 2 devices",
                          "n_chips": n_chips}))
        return
    half = n_chips // 2
    # The whole stack on, knobs tightened for a short run: verdicts every
    # `window` steps, two consecutive rounds of hysteresis.
    os.environ.update({
        "AUTODIST_RETUNE": "exec",
        "AUTODIST_SELFHEAL": "1",
        "AUTODIST_SELFHEAL_PATIENCE": "2",
        "AUTODIST_GUARD_CHECK_EVERY": str(window),
        "AUTODIST_CHAOS": f"slow_host={int(drag_ms)}:bench",
    })
    degrade_at = 2 * window + 1  # first flushed window is fully degraded
    bs = 16 * n_chips
    rng = np.random.RandomState(0)
    dims = (64, 256, 256, 8)
    # Small random init: an all-zeros deep MLP is a saddle (every layer
    # gradient vanishes) and the loss trace would be flat.
    params = {f"w{i}": jnp.asarray(
                  rng.randn(dims[i], dims[i + 1]).astype(np.float32) * 0.05)
              for i in range(len(dims) - 1)}
    batch = (rng.randn(bs, dims[0]).astype(np.float32),
             rng.randn(bs, dims[-1]).astype(np.float32))

    def loss_fn(p, b):
        x, y = b
        h = x
        for i in range(len(dims) - 1):
            h = h @ p[f"w{i}"]
            if i < len(dims) - 2:
                h = jax.nn.relu(h)
        return jnp.mean((h - y) ** 2)

    def build(devices=None, mesh_axes=None):
        _reset_default()
        ad = AutoDist(strategy_builder=PS(), devices=devices,
                      mesh_axes=mesh_axes)
        item = ad.capture(loss_fn, params, optax.adam(1e-3),
                          example_batch=batch)
        return ad.create_distributed_session(item)

    def verdict(cause_ms):
        # The skew decomposition's straggler verdict for host 1
        # (observability/skew.py shape), cause_ms = the injected drag.
        return {"hosts": {0: {}, 1: {}}, "windows": window,
                "significant": True, "max_skew_wait_ms": cause_ms,
                "max_abs_offset_ms": 0.1,
                "straggler": {"host": 1, "share_pct": 100.0,
                              "cause": "device_compute",
                              "cause_ms": cause_ms,
                              "detail": f"host 1 is the straggler in "
                                        f"{window}/{window} windows; "
                                        f"dominant term device_compute "
                                        f"({cause_ms:.3f} ms/step)"}}

    def run_arm(run_id, degraded):
        os.environ["AUTODIST_RUN_ID"] = run_id
        os.environ.pop("AUTODIST_RUN_GENERATION", None)
        observability.refresh()
        observability.reset()
        monitor.reset_detector()
        selfheal.reset()
        from autodist_tpu import retune as retune_mod
        retune_mod.reset()
        tmp = tempfile.mkdtemp(prefix="bench_selfheal_")
        runner = build()
        mgr = CheckpointManager(runner, os.path.join(tmp, "ckpt"),
                                save_interval_steps=10_000)
        state = mgr.restore_or_init()
        co = None
        execs = []
        if degraded:
            co = Coordinator(None, None)
            co._exec = lambda *a: execs.append(a)
            co._world_size = 2

        def feed():
            i = 0
            while True:
                i += 1
                if degraded and i >= degrade_at and not co.reform_pending:
                    # Host 1's chaos-scheduled drag, paid by the chief as
                    # barrier wait (lands inside the measured step
                    # latency); one straggler verdict per sync round.
                    d = chaos.slow_host_delay_ms(i, 1)
                    time.sleep(d / 1e3)
                    if i % window == 0:
                        skew.set_last_summary(verdict(d))
                        monitor.observe_cluster([], now=time.time())
                yield batch

        t0 = time.perf_counter()
        reform_step, record, pinned = None, {}, None
        try:
            state, metrics = mgr.run(state, feed(), num_steps=num_steps,
                                     coordinator=co, unroll=1)
            mgr.close()
        except ElasticReform as e:
            mgr.close()
            reform_step = e.step
            healer = selfheal.healer()
            if healer is not None and healer.decisions:
                record = dict(healer.decisions[0])
            (_exe, _argv, env), = execs
            pinned = env.get("AUTODIST_STRATEGY_ID")
            # Generation 1: the re-exec'd process (simulated in-process),
            # resharded onto the surviving half of the devices.
            time.sleep(0.05)
            os.environ["AUTODIST_RUN_GENERATION"] = "1"
            observability.reset()
            runner2 = build(devices=jax.devices()[:half],
                            mesh_axes={"data": half})
            mgr2 = CheckpointManager(runner2, os.path.join(tmp, "ckpt"),
                                     save_interval_steps=10_000)
            state2 = mgr2.restore_or_init()
            assert int(jax.device_get(state2.step)) == reform_step, \
                "emergency save / resume step mismatch"
            state2, metrics = mgr2.run(state2, iter(lambda: batch, None),
                                       num_steps=num_steps, unroll=1)
            mgr2.close()
        wall_s = time.perf_counter() - t0
        loss = float(np.asarray(jax.device_get(metrics["loss"])).ravel()[-1])
        assert np.isfinite(loss), f"non-finite loss {loss}"
        st = goodput.stitch_run() or {}
        return {"stitched": st, "reform_step": reform_step,
                "record": record, "pinned": pinned,
                "wall_s": round(wall_s, 3), "loss": loss}

    control = run_arm(f"bench-selfheal-ctl-{os.getpid()}", degraded=False)
    healed = run_arm(f"bench-selfheal-{os.getpid()}", degraded=True)
    assert healed["reform_step"], "degraded arm never re-formed"
    ctl_pct = (control["stitched"] or {}).get("goodput_pct")
    heal_pct = (healed["stitched"] or {}).get("goodput_pct")
    retained = (round(heal_pct / ctl_pct * 100.0, 3)
                if ctl_pct and heal_pct else None)
    st = healed["stitched"]
    print(json.dumps({
        "degrade_to_decision_ms": healed["record"].get(
            "degrade_to_decision_ms"),
        "selfheal_goodput_retained_pct": retained,
        "control_goodput_pct": ctl_pct,
        "healed_goodput_pct": heal_pct,
        "selfheal_ms": (st.get("classes") or {}).get("selfheal_ms"),
        "selfheal_episodes": st.get("selfheal_episodes"),
        "selfheal_decision": healed["record"],
        "reform_step": healed["reform_step"],
        "pinned_strategy": healed["pinned"],
        "generations": st.get("generations"),
        "control_wall_s": control["wall_s"],
        "healed_wall_s": healed["wall_s"],
        "loss": healed["loss"],
        "num_steps": num_steps, "window": window,
        "drag_ms": drag_ms, "n_chips": n_chips,
        "world": {"from_devices": n_chips, "to_devices": half}}))


def _worker_serve(requests_per_level=120, warmup=16):
    """Serving runtime point (ISSUE 6): a ``serve.Server`` on the zoo's
    BERT encoder driven closed-loop at increasing client concurrency
    (1 / 4 / 16 outstanding requests, variable row counts), measuring
    per-request p50/p99 latency and achieved requests/sec per level.

    ``serve_rps_at_p99_slo`` is the best achieved rps among levels whose
    p99 stayed under the SLO (``BENCH_SERVE_SLO_MS``, default 50ms) —
    the "how much traffic fits the latency budget" number the roadmap's
    serving item asks for.  Persisted to BENCH_DETAILS.json and tracked
    run-over-run like the loader breakdown."""
    import queue as _queue
    import threading
    import jax
    from autodist_tpu import serve
    from autodist_tpu.models import bert
    from autodist_tpu.models import transformer as T

    slo_ms = float(os.environ.get("BENCH_SERVE_SLO_MS", "50"))
    cfg = bert.bert_tiny()
    params = bert.init(jax.random.PRNGKey(0), cfg)
    seq = 16

    def apply_fn(p, batch):
        ids, seg = batch
        return T.encode(p, cfg, ids, segment_ids=seg)

    rng = np.random.RandomState(0)

    def make_request(rows):
        return (rng.randint(0, cfg.vocab, (rows, seq)).astype(np.int32),
                rng.randint(0, 2, (rows, seq)).astype(np.int32))

    example = make_request(8)
    srv = serve.Server(apply_fn, params, example, buckets=(8, 32),
                       max_wait_ms=2)
    try:
        # Warm every bucket before timing.
        for rows in (3, 8, 20, 32):
            srv.infer(make_request(rows), timeout=120)

        row_choices = (1, 2, 4, 8)
        levels = {}
        for conc in (1, 4, 16):
            lat_ms, lock = [], threading.Lock()
            work = _queue.Queue()
            for i in range(requests_per_level):
                work.put(make_request(row_choices[i % len(row_choices)]))

            def client():
                while True:
                    try:
                        req = work.get_nowait()
                    except _queue.Empty:
                        return
                    t0 = time.perf_counter()
                    srv.infer(req, timeout=120)
                    dt = (time.perf_counter() - t0) * 1e3
                    with lock:
                        lat_ms.append(dt)

            # Closed loop: `conc` clients, each submit->wait->submit.
            for _ in range(warmup):
                srv.infer(make_request(4), timeout=120)
            t0 = time.perf_counter()
            threads = [threading.Thread(target=client) for _ in range(conc)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            lat_ms.sort()
            p50 = lat_ms[len(lat_ms) // 2]
            p99 = lat_ms[min(len(lat_ms) - 1, int(0.99 * len(lat_ms)))]
            levels[str(conc)] = {
                "p50_ms": round(p50, 3), "p99_ms": round(p99, 3),
                "rps": round(len(lat_ms) / wall, 2),
                "requests": len(lat_ms)}

        meeting = [(lv["rps"], lv) for lv in levels.values()
                   if lv["p99_ms"] <= slo_ms]
        best = max(meeting)[1] if meeting else None
        stats = srv.stats()
        print(json.dumps({
            "serve_p50_ms": (best or levels["1"])["p50_ms"],
            "serve_p99_ms": (best or levels["1"])["p99_ms"],
            "serve_rps_at_p99_slo": best["rps"] if best else None,
            "slo_ms": slo_ms,
            "levels": levels,
            "batches": stats["batches"],
            "padded_rows": stats["padded_rows"],
            "replicas": stats["replicas"],
            "buckets": stats["buckets"],
            "model": "bert_tiny_encoder",
            "n_chips": len(jax.devices())}))
    finally:
        srv.close()


def _worker_decode(requests_per_level=32, requests_16=4800, max_new=8):
    """Autoregressive decode runtime point (ISSUE 19): a
    ``serve.DecodeServer`` on the zoo tiny causal LM — slot-based
    KV-cache continuous batching — driven closed-loop at 1 / 4 / 16
    clients with ragged prompts.  The 16-client level runs twice:
    steady, then THROUGH a forced shrink(2->1)->grow(1->2) fleet
    reshape mid-flight (the zero-drop evict/re-queue path); every
    request must complete exactly once, asserted from the server's own
    accounting.  ``decode_tokens_per_sec`` / ``decode_p99_ms`` are the
    steady 16-client level's; ``serve_rps_at_p99_slo_through_scale`` is
    the through-scale level's achieved rps when its p99 held the SLO
    (``BENCH_DECODE_SLO_MS``) — "does the fleet reshape hide in the
    latency budget".  Persisted to BENCH_DETAILS.json; all three
    trend-TRACKED."""
    import queue as _queue
    import threading
    import jax
    from autodist_tpu import serve
    from autodist_tpu.models import lm
    from autodist_tpu.models import transformer as T

    slo_ms = float(os.environ.get("BENCH_DECODE_SLO_MS", "10000"))
    cfg = lm.lm_tiny()
    params = lm.init(jax.random.PRNGKey(0), cfg)

    def apply_fn(p, ids):
        return T.logits(p, cfg, T.encode(p, cfg, ids))

    rng = np.random.RandomState(0)
    prompt_lens = (2, 4, 7, 12)

    srv = serve.DecodeServer(
        apply_fn, lm.make_decode_fn(cfg),
        lambda s, l: lm.init_decode_cache(cfg, s, l),
        params, example_batch=np.zeros((8, 16), np.int32),
        buckets=((8, 32),), replicas=2)
    try:
        # Warm prefill + decode AND both fleet shapes' executables
        # (scale_to recompiles per shape; the persistent XLA cache makes
        # the timed reshape pay re-prefill, not first-compile).
        srv.generate(rng.randint(1, cfg.vocab, (4,)).astype(np.int32),
                     max_new_tokens=2, timeout=300)
        srv.scale_to(1)
        srv.scale_to(2)

        def run_level(conc, n, scale_cycle=False):
            lat_ms, lock = [], threading.Lock()
            tokens = [0]
            work = _queue.Queue()
            for i in range(n):
                work.put(rng.randint(
                    1, cfg.vocab,
                    (prompt_lens[i % len(prompt_lens)],)).astype(np.int32))

            def client():
                while True:
                    try:
                        p = work.get_nowait()
                    except _queue.Empty:
                        return
                    t0 = time.perf_counter()
                    out = srv.generate(p, max_new_tokens=max_new,
                                       timeout=300)
                    dt = (time.perf_counter() - t0) * 1e3
                    with lock:
                        lat_ms.append(dt)
                        tokens[0] += len(out)

            threads = [threading.Thread(target=client) for _ in range(conc)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            if scale_cycle:
                # Forced fleet reshape while clients are mid-request:
                # shrink to one replica, grow back — in-flight
                # generations are evicted to host, re-queued at the
                # front, and continued on the new fleet.  The reshape
                # wall (incl. the recompiles) lands inside this level.
                time.sleep(0.05)
                srv.scale_to(1)
                time.sleep(0.05)
                srv.scale_to(2)
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            if len(lat_ms) != n:
                raise RuntimeError(
                    f"decode bench dropped requests: {len(lat_ms)}/{n} "
                    f"completed at conc={conc} scale_cycle={scale_cycle}")
            lat_ms.sort()
            return {
                "p50_ms": round(lat_ms[len(lat_ms) // 2], 3),
                "p99_ms": round(
                    lat_ms[min(len(lat_ms) - 1,
                               int(0.99 * len(lat_ms)))], 3),
                "rps": round(len(lat_ms) / wall, 2),
                "tokens_per_sec": round(tokens[0] / wall, 1),
                "requests": len(lat_ms),
                "through_scale": bool(scale_cycle)}

        # The 16-client pair (steady, then through the reshape) runs
        # long enough that the reshape wall amortizes — that is the
        # "held through scale" contract, not a reshape-dominated blip.
        levels = {str(c): run_level(c, requests_per_level)
                  for c in (1, 4)}
        levels["16"] = run_level(16, requests_16)
        through = run_level(16, requests_16, scale_cycle=True)
        levels["16_through_scale"] = through

        stats = srv.stats()
        if stats["completed"] != stats["requests"]:
            raise RuntimeError(
                f"decode server accounting off: {stats['completed']} "
                f"completed of {stats['requests']} admitted")
        steady = levels["16"]
        print(json.dumps({
            "decode_tokens_per_sec": steady["tokens_per_sec"],
            "decode_p99_ms": steady["p99_ms"],
            "serve_rps_at_p99_slo_through_scale":
                through["rps"] if through["p99_ms"] <= slo_ms else None,
            "rps_held_through_scale_pct": round(
                100.0 * through["rps"] / steady["rps"], 1)
                if steady["rps"] else None,
            "slo_ms": slo_ms,
            "levels": levels,
            "zero_drops": True,
            "scale_events": stats["scale_events"],
            "requests": stats["requests"],
            "tokens": stats["tokens"],
            "replicas": stats["replicas"],
            "buckets": stats["buckets"],
            "model": "lm_tiny_decoder",
            "n_chips": len(jax.devices())}))
    finally:
        srv.close()


def _worker_h2d(steps=45):
    """Input-pipeline rooflines, no training step:

    * ``ips`` — pure host->device wire ceiling: pipelined uint8 batch
      transfers (depth 2 in flight), no host work.
    * ``pipeline_ceiling_ips`` — wire + the C++ loader's shuffled-batch
      assembly, one transfer in flight: the serialized ceiling for any
      loader-FED number."""
    import jax
    from collections import deque
    n_chips = len(jax.devices())
    bs = BATCH * max(1, n_chips)
    rng = np.random.RandomState(1)
    img = (rng.rand(bs, 224, 224, 3) * 255).astype(np.uint8)
    dev = jax.devices()[0]
    q = deque()
    for _ in range(2):
        q.append(jax.device_put(img, dev))
    for _ in range(5):
        jax.block_until_ready(q.popleft())
        q.append(jax.device_put(img, dev))
    t0 = time.perf_counter()
    for _ in range(steps):
        jax.block_until_ready(q.popleft())
        q.append(jax.device_put(img, dev))
    dt = (time.perf_counter() - t0) / steps

    from autodist_tpu.data import NativeDataLoader, write_record_file
    n_rec = 4 * bs
    images = np.tile(img, (n_rec // bs + 1, 1, 1, 1))[:n_rec]
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "images.rec")
        write_record_file(path, images)
        loader = NativeDataLoader(path, (224, 224, 3), np.uint8, bs)
        pend = jax.device_put(next(loader), dev)
        for _ in range(3):
            jax.block_until_ready(pend)
            pend = jax.device_put(next(loader), dev)
        t0 = time.perf_counter()
        for _ in range(steps):
            jax.block_until_ready(pend)
            pend = jax.device_put(next(loader), dev)
        dt_pipe = (time.perf_counter() - t0) / steps
        loader.close()
    print(json.dumps({"ips": bs / dt, "ms_per_batch": dt * 1e3,
                      "mb_per_s": img.nbytes / 1e6 / dt,
                      "pipeline_ceiling_ips": bs / dt_pipe,
                      "pipeline_ceiling_ms": dt_pipe * 1e3,
                      "n_chips": n_chips}))


def _worker_longcontext(steps=8, segments=3):
    """One long-context point on the chip: a causal transformer block
    (LN -> MHA -> residual -> LN -> MLP -> residual) trained fwd+bwd with
    the fused Pallas flash kernels vs the dense VJP, PAIRED in one process.

    ``LC_SEQ`` picks the sequence length; ``LC_DENSE=0`` skips the dense
    arm (flash-only max-seq probes).  The dense arm materializes the
    (seq x seq) probability matrix in its VJP residuals — the memory wall
    these kernels exist to remove (``ops/flash_attention.py:1-18``); its
    OOM at long seq IS the measurement, reported as ``dense_oom`` with the
    compiler's own HBM numbers (``memory_analysis``) for both arms."""
    import jax
    import jax.numpy as jnp
    import optax
    from autodist_tpu.models import layers as L
    from autodist_tpu.ops.flash_attention import (_dense_reference,
                                                  make_flash_attn_fn)

    seq = int(os.environ.get("LC_SEQ", "4096"))
    try_dense = os.environ.get("LC_DENSE", "1") == "1"
    bs, heads, d_model, d_ff = 1, 8, 512, 2048

    def init_params():
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        return {"ln1": L.layernorm_init(d_model),
                "attn": L.mha_init(ks[0], d_model, heads),
                "ln2": L.layernorm_init(d_model),
                "fc1": L.dense_init(ks[1], d_model, d_ff),
                "fc2": L.dense_init(ks[2], d_ff, d_model)}

    params = init_params()
    rng = np.random.RandomState(0)
    batch = rng.randn(bs, seq, d_model).astype(np.float32)

    def make_loss(attn_fn):
        def loss_fn(p, x):
            h = x + L.mha(p["attn"], L.layernorm(p["ln1"], x), heads,
                          attn_fn=attn_fn)
            g = L.dense(p["fc2"], jax.nn.relu(
                L.dense(p["fc1"], L.layernorm(p["ln2"], h))))
            return jnp.mean((h + g) ** 2)
        return loss_fn

    def build(attn_fn):
        opt = optax.sgd(1e-4)
        loss_fn = make_loss(attn_fn)

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def step(p, o, b):
            loss, grads = jax.value_and_grad(loss_fn)(p, b)
            updates, o = opt.update(grads, o, p)
            return optax.apply_updates(p, updates), o, loss

        p, o = params, opt.init(params)
        db = jax.device_put(batch)
        compiled = step.lower(p, o, db).compile()
        mem = flops = None
        try:
            ma = compiled.memory_analysis()
            mem = {"temp_mb": round(ma.temp_size_in_bytes / 1e6, 1),
                   "arg_mb": round(ma.argument_size_in_bytes / 1e6, 1)}
        except Exception:  # noqa: BLE001 - memory analysis is best-effort
            pass
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            flops = float(ca.get("flops", 0)) or None
        except Exception:  # noqa: BLE001 - cost analysis is best-effort
            pass
        p, o = jax.device_put((p, o), jax.devices()[0])
        jax.block_until_ready((p, o, db))

        def fn(st):
            pp, oo, loss = compiled(st[0], st[1], db)
            return (pp, oo), loss
        return fn, (p, o), mem, flops

    def seg_runner(fn):
        def seg(st):
            for _ in range(steps):
                st, loss = fn(st)
            jax.block_until_ready(loss)
            return st, loss
        return seg

    flash_fn, flash_st, flash_mem, flash_flops = build(
        make_flash_attn_fn(causal=True))

    # Calibrate steps/segment so one segment is >= ~60ms of wall time: an
    # 8-step segment of very short steps would time pure dispatch noise.
    st, l = flash_fn(flash_st)
    st, l = flash_fn(st)
    jax.block_until_ready(l)
    t0 = time.perf_counter()
    for _ in range(4):
        st, l = flash_fn(st)
    jax.block_until_ready(l)
    est = (time.perf_counter() - t0) / 4
    # Cap at 40 (the resident workers' segment length).
    steps = int(min(40, max(steps, 0.06 / max(est, 1e-6))))
    flash_st = st

    dense = dense_err = None
    dense_oom = False
    if try_dense:
        try:
            dense = build(lambda q, k, v, mask: _dense_reference(
                q, k, v, True).astype(q.dtype))
            # OOM may surface at first execution, not compile: warm one
            # step inside the guard before committing to the paired loop.
            _st, _l = dense[0](dense[1])
            jax.block_until_ready(_l)
            dense = (dense[0], _st, dense[2], dense[3])
        except Exception as e:  # noqa: BLE001 - OOM IS the measurement
            import re
            msg = str(e)
            # Strict OOM signatures only (the XLA:TPU compile error and the
            # runtime allocator's): any other failure mentioning "allocate"
            # at a seq where dense fits must re-raise, not be published as
            # the memory-wall boundary.
            dense_oom = ("RESOURCE_EXHAUSTED" in msg
                         or "out of memory" in msg.lower()
                         or "Exceeded hbm capacity" in msg)
            # Keep the compiler's canonical OOM sentence (e.g. "Ran out of
            # memory in memory space hbm. Used 19.07G of 15.75G hbm.").
            m = re.search(r"Ran out of memory[^\n]*", msg)
            dense_err, dense = (m.group(0) if m else msg[:300]), None
            if not dense_oom:
                raise

    out = {"seq": seq, "batch": bs, "heads": heads, "d_model": d_model,
           "steps_per_segment": steps, "flash_mem": flash_mem,
           "dense_oom": dense_oom, "dense_error": dense_err}
    if dense is not None:
        f_ms, b_ms, ratio = _run_paired_segments(
            seg_runner(flash_fn), flash_st, seg_runner(dense[0]), dense[1],
            steps, segments)
        out.update(flash_ms_per_step=min(f_ms), dense_ms_per_step=min(b_ms),
                   flash_over_dense_paired=ratio, dense_mem=dense[2])
    else:
        seg = seg_runner(flash_fn)
        st, _ = seg(flash_st)  # warmup
        f_ms = []
        for _ in range(segments):
            t0 = time.perf_counter()
            st, loss = seg(st)
            f_ms.append((time.perf_counter() - t0) / steps * 1e3)
        l = float(jax.device_get(loss))
        assert np.isfinite(l), f"non-finite flash loss {l}"
        out.update(flash_ms_per_step=min(f_ms))
    if flash_flops:
        out["flash_tflops"] = round(
            flash_flops / (out["flash_ms_per_step"] / 1e3) / 1e12, 2)
    print(json.dumps(out))


def _worker_longcontext_ring(steps=4, segments=2, seq=2048, sp=8):
    """Ring-attention composition point: the same transformer block with
    the sequence axis sharded over an 8-device forced-host CPU mesh (the
    chip is a single device — ring composition cannot run there; the
    single-shard Pallas kernel is what the chip points measure).  Records
    a fwd+bwd step time for the record; the durable claim is that the ring
    VJP trains the block end-to-end at a sequence length where every
    device holds only seq/sp of K/V."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh
    from autodist_tpu.models import layers as L
    from autodist_tpu.parallel import make_ring_attn_fn

    devs = jax.devices()
    assert len(devs) >= sp, f"need {sp} forced-host devices, got {len(devs)}"
    mesh = Mesh(np.array(devs[:sp]).reshape(1, sp), ("data", "seq"))
    bs, heads, d_model, d_ff = 1, 8, 256, 512

    def init_params():
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        return {"ln1": L.layernorm_init(d_model),
                "attn": L.mha_init(ks[0], d_model, heads),
                "ln2": L.layernorm_init(d_model),
                "fc1": L.dense_init(ks[1], d_model, d_ff),
                "fc2": L.dense_init(ks[2], d_ff, d_model)}

    params = init_params()
    rng = np.random.RandomState(0)
    x = rng.randn(bs, seq, d_model).astype(np.float32)
    attn_fn = make_ring_attn_fn(mesh, causal=True)

    def loss_fn(p, xb):
        h = xb + L.mha(p["attn"], L.layernorm(p["ln1"], xb), heads,
                       attn_fn=attn_fn)
        g = L.dense(p["fc2"], jax.nn.relu(
            L.dense(p["fc1"], L.layernorm(p["ln2"], h))))
        return jnp.mean((h + g) ** 2)

    opt = optax.sgd(1e-4)

    @jax.jit
    def step(p, o, xb):
        loss, grads = jax.value_and_grad(loss_fn)(p, xb)
        updates, o = opt.update(grads, o, p)
        return optax.apply_updates(p, updates), o, loss

    p, o = params, opt.init(params)
    for _ in range(2):
        p, o, loss = step(p, o, x)
    jax.block_until_ready(loss)
    seg_ms = []
    for _ in range(segments):
        t0 = time.perf_counter()
        for _ in range(steps):
            p, o, loss = step(p, o, x)
        jax.block_until_ready(loss)
        seg_ms.append((time.perf_counter() - t0) / steps * 1e3)
    l = float(loss)
    assert np.isfinite(l), f"non-finite ring loss {l}"
    print(json.dumps({"seq": seq, "sp": sp, "ms_per_step": min(seg_ms),
                      "kv_per_device": seq // sp, "loss": l}))


def _worker_scaling_paired(steps=6, segments=2):
    """One weak-scaling point: BOTH arms (framework full pipeline and a
    hand-written plain-``jax.jit`` sharded step) built in ONE process on the
    forced-host CPU mesh, timed in alternating segments.

    Round-4's scaling points were one subprocess trial per (mode, n) and
    flipped across runs (fw/plainjax@8 measured 1.02 and 0.93 on the same
    harness — VERDICT r4 weak #2): process-to-process CPU scheduling noise
    swamps a few-percent framework effect.  Pairing inside one process gives
    the scaling proxy the same drift-immune estimator the chip headline
    uses; the orchestrator still runs >= 5 such trials per point and
    reports medians + spreads."""
    import jax
    # A CPU-mesh worker by definition (xla_force_host_platform_device_count).
    jax.config.update("jax_platforms", "cpu")
    import optax
    n = len(jax.devices())
    bs = 16 * n
    params, loss_fn, batch = _cifar_fixture(bs)

    from autodist_tpu import AutoDist
    from autodist_tpu.strategy import AllReduce
    ad = AutoDist(strategy_builder=AllReduce())
    item = ad.capture(loss_fn, params, optax.sgd(1e-3),
                      example_batch=batch)
    runner = ad.create_distributed_session(item)
    fstate = runner.create_state()
    fstep = runner.make_callable(batch)
    fbatch = runner.remapper.shard_batch(batch)

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    opt = optax.sgd(1e-3)
    mesh = Mesh(np.array(jax.devices()), ("data",))
    bsh = NamedSharding(mesh, P("data"))
    repl = NamedSharding(mesh, P())

    @functools.partial(jax.jit, donate_argnums=(0, 1),
                       out_shardings=(repl, repl, repl))
    def step(p, o, b):
        loss, grads = jax.value_and_grad(loss_fn)(p, b)
        updates, o = opt.update(grads, o, p)
        return optax.apply_updates(p, updates), o, loss

    p = jax.device_put(params, repl)
    o = jax.device_put(opt.init(params), repl)
    db = jax.device_put(batch, bsh)

    def fseg(state):
        for _ in range(steps):
            state, out = fstep(state, fbatch)
        jax.block_until_ready(out["loss"])
        return state, out["loss"]

    def bseg(st):
        for _ in range(steps):
            pp, oo, loss = step(st[0], st[1], db)
            st = (pp, oo)
        jax.block_until_ready(loss)
        return st, loss

    f_ms, b_ms, ratio = _run_paired_segments(fseg, fstate, bseg, (p, o),
                                             steps, segments)
    print(json.dumps({
        "n_devices": n,
        "fw_ips": bs / (min(f_ms) / 1e3),
        "pj_ips": bs / (min(b_ms) / 1e3),
        "ratio_fw_over_pj": ratio,
        "framework_segments_ms": [round(x, 3) for x in f_ms],
        "plainjax_segments_ms": [round(x, 3) for x in b_ms]}))


def _compile_on_topology(builder, loss_fn, params, batch, topology_name,
                         num_slices=1, opt=None, precision=None):
    """AOT-compile the framework's full train step for a DETACHED TPU
    topology (no chips attached, no buffers materialized) and return
    (optimized_hlo_text, runner, executable).  Params and batch may be
    ShapeDtypeStructs — pod-scale global batches never exist as arrays.
    The single home of the detached-topology pattern used by the
    zero-verify and pod-compile workers."""
    import jax
    import optax
    from jax.experimental import topologies
    from autodist_tpu import AutoDist
    from autodist_tpu.autodist import _reset_default
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name=topology_name, num_slices=num_slices)
    n_dev = len(topo.devices)
    with tempfile.TemporaryDirectory() as td:
        spec_path = os.path.join(td, "spec.yml")
        with open(spec_path, "w") as f:
            # Single-process spec regardless of slice count: this process
            # only COMPILES for the topology (jax.distributed must not
            # start); the device list carries the true shape.
            f.write("nodes:\n  - address: 127.0.0.1\n    chief: true\n"
                    f"    tpus: [{', '.join(str(i) for i in range(n_dev))}]\n")
        _reset_default()
        ad = AutoDist(spec_path, builder, devices=topo.devices)
        item = ad.capture(loss_fn, params, opt or optax.adam(1e-3),
                          example_batch=batch, precision=precision)
        runner = ad.create_distributed_session(item)
        batch_struct = jax.tree_util.tree_map(
            lambda x: x if isinstance(x, jax.ShapeDtypeStruct)
            else jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype),
            batch)
        compiled = runner._compile(batch_struct)
        exe = compiled.lower(runner.state_struct, batch_struct).compile()
    return exe.as_text(), runner, exe


def _exe_analysis(exe):
    """Per-chip XLA cost + memory analysis of a compiled executable (the
    SPMD module is the per-device program, so these ARE per-chip numbers)."""
    out = {}
    try:
        ca = exe.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        out["per_chip_gflops_per_step"] = round(
            float(ca.get("flops", 0)) / 1e9, 2)
        if ca.get("bytes accessed"):
            out["per_chip_gbytes_accessed"] = round(
                float(ca["bytes accessed"]) / 1e9, 2)
    except Exception:  # noqa: BLE001 - cost analysis is best-effort
        pass
    try:
        ma = exe.memory_analysis()
        out["per_chip_hbm_mb"] = round(
            (ma.temp_size_in_bytes + ma.argument_size_in_bytes) / 1e6, 1)
    except Exception:  # noqa: BLE001 - memory analysis is best-effort
        pass
    return out


def _worker_pod_compile():
    """BASELINE.md's pod-scale configs through the REAL TPU compiler:
    ResNet-50/AllReduce and BERT-base/Parallax AOT-compiled for a detached
    256-chip v5e pod (16x16 over ICI) next to the 8-chip base (2x4) —
    the 8->256-chip scaling targets can never RUN here, but the compiler
    sees exactly the programs a pod would run.  Asserts the collective
    structure survives at pod scale (a 256-way replica group on the wire;
    sharded-PS ReduceScatter for BERT's Parallax) and records XLA per-chip
    cost/memory analysis for both scales."""
    import jax
    import jax.numpy as jnp
    import optax
    from autodist_tpu.strategy import AllReduce, Parallax
    from autodist_tpu.models import bert, resnet
    from autodist_tpu.report import collective_summary, replica_group_sizes

    PER_CHIP_RN, PER_CHIP_BERT, SEQ = BATCH, 32, 128
    scales = (("8", "v5e:2x4", 8), ("256", "v5e:16x16", 256))
    out = {"resnet50_allreduce": {}, "bert_base_parallax": {}}

    cfg = resnet.resnet50()
    rn_params = jax.eval_shape(
        lambda: resnet.init(jax.random.PRNGKey(0), cfg))
    rn_loss = resnet.make_loss_fn(cfg)
    bcfg = bert.bert_base(max_len=SEQ)
    bert_params = jax.eval_shape(
        lambda: bert.init(jax.random.PRNGKey(0), bcfg))
    bert_loss = bert.make_loss_fn(bcfg)

    for label, topology, n in scales:
        gbs = PER_CHIP_RN * n
        batch = (jax.ShapeDtypeStruct((gbs, 224, 224, 3), jnp.float32),
                 jax.ShapeDtypeStruct((gbs,), jnp.int32))
        text, _, exe = _compile_on_topology(
            AllReduce(chunk_size=128), rn_loss, rn_params, batch,
            topology_name=topology, opt=optax.sgd(1e-3))
        counts = collective_summary(text, keep_zeros=True)
        rec = {"collectives": {k: v for k, v in counts.items() if v},
               "replica_group_sizes": sorted(replica_group_sizes(text)),
               "global_batch": gbs, **_exe_analysis(exe)}
        rec["ok"] = (counts.get("all-reduce", 0) >= 1
                     and n in replica_group_sizes(text))
        out["resnet50_allreduce"][label] = rec

        gbs_b = PER_CHIP_BERT * n
        bbatch = bert.synthetic_batch(bcfg, batch_size=8, seq_len=SEQ,
                                      num_masked=20)
        bbatch = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(
                (gbs_b,) + np.shape(a)[1:], np.asarray(a).dtype), bbatch)
        text, _, exe = _compile_on_topology(
            Parallax(), bert_loss, bert_params, bbatch,
            topology_name=topology, opt=optax.adam(1e-4))
        counts = collective_summary(text, keep_zeros=True)
        rec = {"collectives": {k: v for k, v in counts.items() if v},
               "replica_group_sizes": sorted(replica_group_sizes(text)),
               "global_batch": gbs_b, **_exe_analysis(exe)}
        # Parallax = sharded-PS embedding (storage sharded over the pod:
        # AllGather at use) + BUCKETED dense all-reduces (a per-variable
        # AR storm would show ~200 ARs for BERT's 197 vars).  The
        # embedding-gradient ReduceScatter is required at 8 chips; at 256
        # this XLA's TPU pipeline legalizes the same psum_scatter to
        # AR+pad (its choice, recorded via the collectives counts — the
        # sharded-storage memory claim is unaffected).
        rec["ok"] = (counts.get("all-gather", 0) >= 1
                     and 1 <= counts.get("all-reduce", 0) <= 6
                     and n in replica_group_sizes(text)
                     and (counts.get("reduce-scatter", 0) >= 1
                          or n > 8))
        out["bert_base_parallax"][label] = rec

    out["pod_compile_verified"] = all(
        out[m][s]["ok"] for m in ("resnet50_allreduce", "bert_base_parallax")
        for s in ("8", "256"))
    out["compiler"] = ("tpu detached topologies: v5e:2x4 (8 chips) and "
                       "v5e:16x16 (256-chip pod), AOT, no chips attached")
    print(json.dumps(out))


def _worker_zero_verify():
    """Parallelism-mechanism verification with the REAL TPU COMPILER:
    AOT-compile the framework's programs against a detached v5e topology
    (``tests/test_hlo_lowering.py``'s CPU proxies cannot see TPU backend
    rewrites — VERDICT r3 items 4/5/8) and assert the optimized HLO:

    * PS explicit path — structural ReduceScatter, no gradient AllReduce;
    * PS(gspmd_update=True) — shard-local ZeRO update (AR+DS+AllGather);
    * TP (ModelParallel dp4 x tp2) — kernel storage sharded over 'model',
      activation collectives present;
    * MoE (dp2 x ep4) — every expert-FFN dot on an E/ep buffer AND a
      collective whose replica groups span the expert axis;
    * multislice — the same data-parallel program compiled over a
      2-slice (DCN-connected) 16-chip topology."""
    import jax
    import jax.numpy as jnp
    import optax
    from autodist_tpu.strategy import PS, AllReduce, ModelParallel
    from autodist_tpu.report import collective_summary

    def loss_fn(params, batch):
        x, y = batch
        h = jax.nn.relu(x @ params["w1"])
        pred = h @ params["w2"] + params["b"]
        return jnp.mean((pred - y) ** 2)

    rng = np.random.RandomState(0)
    params = {"w1": jnp.zeros((64, 128)), "w2": jnp.zeros((128, 8)),
              "b": jnp.zeros((8,))}
    batch = (rng.randn(32, 64).astype(np.float32),
             rng.randn(32, 8).astype(np.float32))

    def compile_on_topology(builder, lfn, prm, btch, num_slices=1,
                            opt=None):
        text, runner, _ = _compile_on_topology(
            builder, lfn, prm, btch, "v5e:2x4", num_slices=num_slices,
            opt=opt)
        return text, runner

    def counts(text):
        return collective_summary(
            text, ops=("reduce-scatter", "all-reduce", "all-gather",
                       "dynamic-slice"), keep_zeros=True)

    # -- PS paths -------------------------------------------------------------
    explicit = counts(compile_on_topology(PS(), loss_fn, params, batch)[0])
    # Default path: structural ReduceScatter; the only all-reduces allowed
    # are scalar metrics (a per-variable gradient AR regression would show
    # as ar > 2 with 3 trainable vars).
    explicit_ok = (explicit["reduce-scatter"] >= 1
                   and explicit["all-gather"] >= 1
                   and explicit["all-reduce"] <= 2)
    gspmd = counts(compile_on_topology(PS(gspmd_update=True), loss_fn,
                                       params, batch)[0])
    # Escape hatch: this XLA version reshards grads as AR+DynamicSlice (no
    # AR->RS rewrite even on the TPU pipeline — measured, which is WHY the
    # structural explicit path is the default); the verified claim is the
    # shard-local ZeRO update: slice -> update -> AllGather.
    gspmd_ok = (gspmd["all-gather"] >= 1 and gspmd["dynamic-slice"] >= 1)

    from autodist_tpu.report import (einsum_result_lead_dims,
                                     replica_group_sizes)

    # -- TP: dp4 x tp2 --------------------------------------------------------
    TP_AXIS = 2
    tp_counts, tp_ok = {}, False
    try:
        tp_text, tp_runner = compile_on_topology(
            ModelParallel(rules=(("w1", 1), ("w2", 0))), loss_fn, params,
            batch)
        tp_spec = tp_runner.state_shardings.params["w1"].spec
        tp_counts = counts(tp_text)
        # Kernel storage sharded over 'model' AND some collective whose
        # replica groups span the model axis (size 2) — the base strategy's
        # data-axis gradient all-reduces (groups of 4) don't satisfy this,
        # so a lowering that replicates activations fails here.
        tp_ok = ("model" in str(tp_spec)
                 and TP_AXIS in replica_group_sizes(tp_text))
    except Exception as e:  # noqa: BLE001 - keep the PS verdicts on failure
        tp_counts = {"error": str(e)[:200]}

    # -- MoE (dp2 x ep4): mirrors tests/test_moe_hlo.py on the TPU compiler ---
    EP, E = 4, 8
    ffn_lead, group_sizes, moe_ok = [], set(), False
    try:
        from autodist_tpu.parallel import moe as moe_mod
        cfg = moe_mod.MoEConfig(num_experts=E, top_k=2, d_model=32,
                                d_hidden=128)
        moe_params = {"moe": moe_mod.init(jax.random.PRNGKey(1), cfg)}

        def moe_loss(p, b):
            x, _ = b
            h, aux = moe_mod.apply(p["moe"], cfg, x)
            return jnp.mean(h ** 2) + 0.01 * aux

        moe_batch = (rng.randn(256, 32).astype(np.float32),
                     rng.randint(0, 4, (256,)).astype(np.int32))
        moe_text, _ = compile_on_topology(
            ModelParallel(AllReduce(), model_axis=EP,
                          rules=moe_mod.EXPERT_RULES, mesh_axis="expert"),
            moe_loss, moe_params, moe_batch)
        ffn_lead = einsum_result_lead_dims(
            moe_text, ("ecd,edh->ech", "ech,ehd->ecd"))
        group_sizes = replica_group_sizes(moe_text)
        moe_ok = (bool(ffn_lead) and all(d == E // EP for d in ffn_lead)
                  and EP in group_sizes)
    except Exception as e:  # noqa: BLE001 - keep the PS verdicts on failure
        ffn_lead = [f"error: {str(e)[:200]}"]

    # -- multislice (2 x v5e-8 over DCN) --------------------------------------
    try:
        ms = counts(compile_on_topology(AllReduce(), loss_fn, params, batch,
                                        num_slices=2)[0])
        ms_ok = ms["all-reduce"] >= 1
    except Exception as e:  # noqa: BLE001 - topology support may vary
        ms, ms_ok = {"error": str(e)[:200]}, False

    print(json.dumps({
        "gspmd_zero_verified": bool(explicit_ok and gspmd_ok),
        "tp_verified": bool(tp_ok),
        "moe_expert_parallel_verified": bool(moe_ok),
        "multislice_compile_verified": bool(ms_ok),
        "explicit_hlo": explicit, "gspmd_update_hlo": gspmd,
        "tp_hlo": tp_counts,
        "moe_ffn_per_device_expert_dims": sorted(set(ffn_lead)),
        "moe_collective_group_sizes": sorted(group_sizes),
        "multislice_hlo": ms,
        "compiler": "tpu v5e:2x4 detached topology (AOT), 2-slice for DCN",
        "note": "explicit path: structural ReduceScatter, no gradient "
                "all-reduce; gspmd_update path: shard-local update "
                "(AR+DynamicSlice+AllGather; this XLA version emits no "
                "AR->RS rewrite, hence explicit is the default)"}))


# ---------------------------------------------------------------------------
# orchestrator

# Workers whose numbers are the accelerator's (main() spawns them on the
# default backend); the rest are CPU-mesh workers by construction.
_CHIP_WORKERS = frozenset({
    "framework", "framework-bf16", "baseline", "paired", "bert", "tuner",
    "dispatch", "overlap", "compress", "serve", "retune", "elastic",
    "loader", "h2d", "longcontext"})


def _require_tpu(worker):
    """Name the device a chip worker runs on; stop when it is no TPU."""
    import jax
    dev = jax.devices()[0]
    sys.stderr.write(f"bench: worker {worker} on platform={dev.platform} "
                     f"device_kind={dev.device_kind!r} "
                     f"count={len(jax.devices())}\n")
    if dev.platform != "tpu":
        sys.exit(f"bench: worker {worker!r} measures the chip and JAX found "
                 f"platform {dev.platform!r}: no TPU, no number")



def _spawn(worker, env_overrides=None, timeout=560):
    # Persistent compilation cache, shared by the workers through the
    # environment: later trials of one program shape (fresh subprocesses,
    # same HLO) reload what the first compiled.
    from autodist_tpu.utils import compile_cache
    compile_cache.enable()
    env = dict(os.environ)
    env.update(env_overrides or {})
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--worker", worker],
        capture_output=True, text=True, env=env, timeout=timeout,
        cwd=os.path.dirname(os.path.abspath(__file__)))
    sys.stderr.write(f"bench: worker {worker} took "
                     f"{time.perf_counter() - t0:.0f}s\n")
    if proc.returncode != 0:
        sys.stderr.write(proc.stderr[-4000:])
        raise RuntimeError(f"bench worker {worker!r} failed "
                           f"(rc={proc.returncode})")
    lines = [ln for ln in proc.stdout.strip().splitlines() if
             ln.startswith("{")]
    if not lines:
        raise RuntimeError(
            f"bench worker {worker!r} exited 0 without a JSON line; "
            f"stderr tail: {proc.stderr[-2000:]}")
    return json.loads(lines[-1])


# -- compiler-verification workers (zero-verify, pod-compile) ------------
# Their outputs are pure functions of (code, compiler): detached-topology
# AOT executables cannot reload from the XLA compilation cache
# (DeserializeLoadedExecutable unimplemented), so each run would pay the
# full ~20 min of pod compiles.  Cache the RESULTS keyed by the exact
# git commit, clean-tree only; repeat driver runs of the same commit
# reuse them (marked "cached": true).
# Driver-owned volatile artifacts do not invalidate the verification
# results (they are not code); without this filter the tree is dirty on
# essentially every driver run and the cache would never activate.
_VOLATILE = ("PROGRESS.jsonl", "BENCH_DETAILS.json", "BENCH_r",
             "MULTICHIP_r", "COPYCHECK.json", "VERDICT.md", "ADVICE.md")

def _verify_cached(worker, timeout, fallback):
    sha = None
    try:
        repo = os.path.dirname(os.path.abspath(__file__))
        # Key on the CODE tree objects, not HEAD: the driver's snapshot
        # commits touch only record files and must not invalidate the
        # cached verification of unchanged code.
        tree = subprocess.run(
            ["git", "rev-parse", "HEAD:autodist_tpu", "HEAD:bench.py"],
            capture_output=True, text=True, cwd=repo)
        dirty = subprocess.run(["git", "status", "--porcelain"],
                               capture_output=True, text=True, cwd=repo)
        code_dirty = [ln for ln in dirty.stdout.splitlines()
                      if ln.strip() and not any(
                          v in ln for v in _VOLATILE)]
        if tree.returncode == 0 and not code_dirty:
            import jax
            import jaxlib
            key = "_".join(h[:12] for h in tree.stdout.split())
            sha = f"{key}_{jax.__version__}_{jaxlib.__version__}"
    except Exception:  # noqa: BLE001 - caching is best-effort
        pass
    # Per-uid 0700 cache dir: a predictable world-writable /tmp name
    # would let another local user plant forged 'verified' results.
    cache_dir = f"/tmp/autodist_tpu_verify_{os.getuid()}"
    path = os.path.join(cache_dir,
                        f"{worker}_{sha}.json") if sha else None
    if path and os.path.exists(path):
        try:
            st = os.stat(path)
            if st.st_uid != os.getuid():
                raise PermissionError("cache file not owned by us")
            with open(path) as f:
                res = json.load(f)
            res["cached"] = True
            sys.stderr.write(f"bench: {worker} result reused from "
                             f"{path}\n")
            return res
        except Exception:  # noqa: BLE001 - fall through to a live run
            pass
    try:
        res = _spawn(worker, timeout=timeout)
    except Exception as e:  # noqa: BLE001 - must not kill the bench
        sys.stderr.write(f"bench: {worker} failed: {e}\n")
        return dict(fallback, error=str(e)[:200])
    if path:
        try:
            os.makedirs(cache_dir, mode=0o700, exist_ok=True)
            if os.stat(cache_dir).st_uid == os.getuid():
                with open(path, "w") as f:
                    json.dump(res, f)
        except OSError:
            pass
    return res


def _median(xs):
    xs = sorted(xs)
    return xs[len(xs) // 2]


def _spread_pct(xs, med):
    return round(100 * (max(xs) - min(xs)) / med, 1)


def _run_trend(warn_only):
    """Append the trend sentinel's verdict to TREND.md next to the bench
    history and return the exit code the caller should use: 0, or
    nonzero when a tracked headline metric regressed beyond its noise
    floor (warn-only downgrades that to 0).  Fail-open: a broken history
    must never hide a finished bench run's headline."""
    repo = os.path.dirname(os.path.abspath(__file__))
    try:
        from autodist_tpu.tools import trend as trend_mod
        res = trend_mod.run(root=repo,
                            out_md=os.path.join(repo, "TREND.md"),
                            append=True)
        for row in res["regressions"]:
            sys.stderr.write(
                f"bench: TREND REGRESSION {row['metric']}: "
                f"{row['prev']} ({row['prev_label']}) -> {row['latest']} "
                f"({row['delta_vs_prev_pct']}% vs a "
                f"{row['noise_floor_pct']}% noise floor)\n")
        sys.stderr.write(f"bench: trend appended to TREND.md "
                         f"({len(res['regressions'])} regression(s))\n")
        if res["regressions"] and not warn_only:
            return 3
    except Exception as e:  # noqa: BLE001 - sentinel must not eat the run
        sys.stderr.write(f"bench: trend sentinel failed: {e}\n")
    return 0


def main(trend_warn_only=False):
    # -- chip arms: fresh subprocess per trial, interleaved F,B,F,B,... -------
    fw, base = [], []
    for _ in range(TRIALS):
        fw.append(_spawn("framework"))
        base.append(_spawn("baseline"))
    fw_all = sorted(r["ips"] for r in fw)
    base_all = sorted(r["ips"] for r in base)
    fw_ips, base_ips = fw_all, base_all
    fw_med, base_med = _median(fw_ips), _median(base_ips)
    n_chips = fw[0]["n_chips"]

    # -- paired same-process cross-check --------------------------------------
    try:
        paired = _spawn("paired")
    except Exception as e:  # noqa: BLE001 - cross-check; keep headline
        sys.stderr.write(f"bench: paired trial failed: {e}\n")
        paired = None

    # -- BERT-base paired point (the reference's second headline model) -------
    try:
        # Two BERT-base fwd+bwd programs compile cold in minutes; warm
        # cache runs take ~2 min.
        bert = _spawn("bert", timeout=1200)
    except Exception as e:  # noqa: BLE001 - secondary metric; keep headline
        sys.stderr.write(f"bench: bert trial failed: {e}\n")
        bert = None

    # -- mixed-precision (bf16 compute) point ------------------------------
    bf16_med = None
    try:
        bf16_runs = [_spawn("framework-bf16") for _ in range(3)]
        bf16_med = _median(r["ips"] for r in bf16_runs)
    except Exception as e:  # noqa: BLE001 - secondary metric; keep headline
        sys.stderr.write(f"bench: bf16 trial failed: {e}\n")

    flops = next((r["flops_per_step"] for r in base
                  if r.get("flops_per_step")), None)
    bs = BATCH * max(1, n_chips)
    # Step time implied by the same median as the headline.
    tflops = (flops * fw_med / bs / 1e12) if flops else None

    # -- loader-fed + H2D roofline (independent workers, independent fates) ---
    loader = h2d = None
    try:
        loader = _spawn("loader")
    except Exception as e:  # noqa: BLE001 - secondary metric; keep headline
        sys.stderr.write(f"bench: loader trial failed: {e}\n")
    try:
        h2d = _spawn("h2d")
    except Exception as e:  # noqa: BLE001 - secondary metric; keep headline
        sys.stderr.write(f"bench: h2d roofline failed: {e}\n")

    # -- strategy autotuner: auto-selection end to end + cost-model drift -----
    tuner_res = None
    try:
        tuner_res = _spawn("tuner", timeout=900)
    except Exception as e:  # noqa: BLE001 - secondary metric; keep headline
        sys.stderr.write(f"bench: tuner trial failed: {e}\n")

    # -- automap: per-op sharding search rediscovery + search cost ------------
    # Forced 8-device CPU mesh (like longcontext-ring): rediscovery is a
    # property of the searcher and must not depend on the backing chip.
    automap_res = None
    try:
        automap_res = _spawn(
            "automap",
            env_overrides={"JAX_PLATFORMS": "cpu",
                           "XLA_FLAGS":
                           "--xla_force_host_platform_device_count=8"},
            timeout=900)
    except Exception as e:  # noqa: BLE001 - secondary metric; keep headline
        sys.stderr.write(f"bench: automap trial failed: {e}\n")

    # -- pipeline parallelism: paired shift/sequential/noskip schedules -------
    # Forced 8-device CPU mesh (like automap): the schedule structure —
    # tick counts, bubble slots, bitwise numerics — is chip-independent.
    pipeline_res = None
    try:
        pipeline_res = _spawn(
            "pipeline",
            env_overrides={"JAX_PLATFORMS": "cpu",
                           "XLA_FLAGS":
                           "--xla_force_host_platform_device_count=8"},
            timeout=900)
    except Exception as e:  # noqa: BLE001 - secondary metric; keep headline
        sys.stderr.write(f"bench: pipeline trial failed: {e}\n")

    # -- fused multi-step dispatch: host-overhead amortization curve ----------
    dispatch = None
    try:
        dispatch = _spawn("dispatch", timeout=900)
    except Exception as e:  # noqa: BLE001 - secondary metric; keep headline
        sys.stderr.write(f"bench: dispatch trial failed: {e}\n")

    # -- latency-hiding overlap: paired on/off megastep segments --------------
    overlap_res = None
    try:
        overlap_res = _spawn("overlap", timeout=900)
    except Exception as e:  # noqa: BLE001 - secondary metric; keep headline
        sys.stderr.write(f"bench: overlap trial failed: {e}\n")

    # -- compressed collectives: paired compressed-vs-f32 wire formats --------
    compress_res = None
    try:
        compress_res = _spawn("compress", timeout=900)
    except Exception as e:  # noqa: BLE001 - secondary metric; keep headline
        sys.stderr.write(f"bench: compress trial failed: {e}\n")

    # -- hierarchical collectives: per-leg quantized vs flat f32 wire ---------
    hier_res = None
    try:
        hier_res = _spawn(
            "hier",
            env_overrides={"JAX_PLATFORMS": "cpu",
                           "XLA_FLAGS":
                           "--xla_force_host_platform_device_count=8",
                           "AUTODIST_HIER_ICI": "4"},
            timeout=900)
    except Exception as e:  # noqa: BLE001 - secondary metric; keep headline
        sys.stderr.write(f"bench: hier trial failed: {e}\n")

    # -- serving runtime: continuous-batching latency/throughput point --------
    serve_res = None
    try:
        serve_res = _spawn("serve", timeout=900)
    except Exception as e:  # noqa: BLE001 - secondary metric; keep headline
        sys.stderr.write(f"bench: serve trial failed: {e}\n")

    # -- autoregressive decode: continuous batching through a fleet reshape ---
    decode_res = None
    try:
        decode_res = _spawn(
            "decode",
            env_overrides={"JAX_PLATFORMS": "cpu",
                           "XLA_FLAGS":
                           "--xla_force_host_platform_device_count=8"},
            timeout=900)
    except Exception as e:  # noqa: BLE001 - secondary metric; keep headline
        sys.stderr.write(f"bench: decode trial failed: {e}\n")

    # -- online re-tuning: stale-knob launch converging mid-run ---------------
    retune_res = None
    try:
        retune_res = _spawn("retune", timeout=900)
    except Exception as e:  # noqa: BLE001 - secondary metric; keep headline
        sys.stderr.write(f"bench: retune trial failed: {e}\n")

    # -- elastic resharding: paired save->kill->reshard-resume cycles ---------
    elastic_res = None
    try:
        elastic_res = _spawn("elastic", timeout=900)
    except Exception as e:  # noqa: BLE001 - secondary metric; keep headline
        sys.stderr.write(f"bench: elastic trial failed: {e}\n")

    # -- self-healing: degraded-host eviction, priced + stitched -------------
    selfheal_res = None
    try:
        selfheal_res = _spawn(
            "selfheal",
            env_overrides={"JAX_PLATFORMS": "cpu",
                           "XLA_FLAGS":
                           "--xla_force_host_platform_device_count=8"},
            timeout=900)
    except Exception as e:  # noqa: BLE001 - secondary metric; keep headline
        sys.stderr.write(f"bench: selfheal trial failed: {e}\n")

    # -- HBM memory ledger: predicted vs measured on the zoo transformer ------
    mem_res = None
    try:
        mem_res = _spawn(
            "mem",
            env_overrides={"JAX_PLATFORMS": "cpu",
                           "XLA_FLAGS":
                           "--xla_force_host_platform_device_count=8"},
            timeout=900)
    except Exception as e:  # noqa: BLE001 - secondary metric; keep headline
        sys.stderr.write(f"bench: mem trial failed: {e}\n")

    # -- long-context: fused flash vs dense VJP on the chip, seq sweep +
    # flash-only probe past the dense memory wall + ring composition point --
    long_context = {"points": {}}
    lc_dense_max = lc_flash_max = 0
    for s in (2048, 4096, 8192, 16384):
        try:
            r = _spawn("longcontext", env_overrides={"LC_SEQ": str(s)},
                       timeout=900)
            long_context["points"][str(s)] = r
            lc_flash_max = s
            if r.get("dense_ms_per_step") and not r.get("dense_oom"):
                lc_dense_max = s
        except Exception as e:  # noqa: BLE001 - keep partial sweep
            sys.stderr.write(f"bench: longcontext seq={s} failed: {e}\n")
            long_context["points"][str(s)] = {"error": str(e)[:200]}
    try:
        # Flash-only probe past the dense wall: O(s) residents keep going.
        probe = _spawn("longcontext",
                       env_overrides={"LC_SEQ": "32768", "LC_DENSE": "0"},
                       timeout=900)
        long_context["points"]["32768"] = probe
        lc_flash_max = 32768
    except Exception as e:  # noqa: BLE001 - probe is best-effort
        sys.stderr.write(f"bench: longcontext probe failed: {e}\n")
    long_context["dense_max_seq"] = lc_dense_max
    long_context["flash_max_seq"] = lc_flash_max
    try:
        long_context["ring"] = _spawn(
            "longcontext-ring",
            env_overrides={"JAX_PLATFORMS": "cpu",
                           "XLA_FLAGS":
                           "--xla_force_host_platform_device_count=8"},
            timeout=600)
    except Exception as e:  # noqa: BLE001 - composition point is best-effort
        sys.stderr.write(f"bench: longcontext ring failed: {e}\n")
        long_context["ring"] = {"error": str(e)[:200]}

    # -- weak-scaling proxy: >=5 paired (both-arms-in-one-process) trials per
    # point, medians + spreads (single trials flipped fw/plainjax@8 between
    # 1.02 and 0.93) --------------------------------------------------------
    scaling_fw, scaling_base, scaling_ratio, scaling_detail = {}, {}, {}, {}
    try:
        for n in (1, 8):
            env = {"JAX_PLATFORMS": "cpu",
                   "XLA_FLAGS": f"--xla_force_host_platform_device_count={n}"}
            runs = [_spawn("scaling-paired", env_overrides=env)
                    for _ in range(SCALING_TRIALS)]
            fw_kept = sorted(r["fw_ips"] for r in runs)
            pj_kept = sorted(r["pj_ips"] for r in runs)
            ratios = sorted(r["ratio_fw_over_pj"] for r in runs)
            scaling_fw[str(n)] = round(_median(fw_kept), 1)
            scaling_base[str(n)] = round(_median(pj_kept), 1)
            scaling_ratio[str(n)] = round(_median(ratios), 4)
            scaling_detail[str(n)] = {
                "trials": SCALING_TRIALS,
                "fw_ips": [round(r["fw_ips"], 1) for r in runs],
                "pj_ips": [round(r["pj_ips"], 1) for r in runs],
                "paired_ratios": [round(x, 4) for x in ratios],
                "fw_spread_pct": _spread_pct(fw_kept, _median(fw_kept)),
                "pj_spread_pct": _spread_pct(pj_kept, _median(pj_kept)),
            }
    except Exception as e:  # noqa: BLE001 - secondary metric; keep headline
        sys.stderr.write(f"bench: scaling proxy failed: {e}\n")

    def eff(d):
        return round(d["8"] / d["1"], 4) if "8" in d and "1" in d else None

    zero = _verify_cached("zero-verify", 900,
                          {"gspmd_zero_verified": False})
    pod = _verify_cached("pod-compile", 1800,
                         {"pod_compile_verified": False})

    # Reference publishes no numbers (BASELINE.md); the honest baseline is a
    # hand-written jax.jit step on the same model and chip — vs_baseline
    # >= 1.0 means the framework adds no overhead over minimal JAX.  The
    # HEADLINE estimator is the paired same-process alternating measurement
    # (immune to process-level drift);
    # the interleaved fresh-subprocess median ratio and min-vs-min are
    # reported as cross-checks with both arms' spreads.
    details = {
            "trials": TRIALS,
            "framework_ips": [round(x, 1) for x in fw_all],
            "baseline_ips": [round(x, 1) for x in base_all],
            "framework_spread_pct": _spread_pct(fw_ips, fw_med),
            "baseline_spread_pct": _spread_pct(base_ips, base_med),
            "vs_baseline_best": round(max(fw_ips) / max(base_ips), 4),
            "vs_baseline_paired": round(paired["ratio"], 4) if paired else None,
            "paired_segments_ms": {
                "framework": paired["framework_segments_ms"],
                "baseline": paired["baseline_segments_ms"]} if paired else None,
            "bert_base_samples_per_sec": round(bert["samples_per_sec"], 1)
                if bert else None,
            "bert_vs_baseline_paired": round(bert["ratio"], 4)
                if bert else None,
            "framework_bf16_ips": round(bf16_med, 1) if bf16_med else None,
            "bf16_vs_f32": round(bf16_med / fw_med, 4) if bf16_med else None,
            "bf16_note": "capture(precision='bf16') — bf16 compute, f32 "
                         "master state (tests/test_mixed_precision.py)",
            "phase_timings_ms": next(
                (r.get("phases_ms") for r in fw if r.get("phases_ms")),
                None),
            "phase_timings_note": "framework span totals (ms) from the "
                                  "first framework trial's observability "
                                  "layer: capture / strategy-build / "
                                  "transform / compile / aot-compile — "
                                  "step time lives in the segment arrays; "
                                  "multi-host ship shows up as "
                                  "strategy-ship when present",
            "attribution": {
                "framework": next(
                    (r.get("attribution") for r in fw
                     if r.get("attribution")), None),
                "tuner": (tuner_res or {}).get("attribution"),
                "dispatch": (dispatch or {}).get("attribution"),
                "loader": (loader or {}).get("attribution"),
                "overlap": (overlap_res or {}).get("attribution"),
            },
            "attribution_note": "per-step ms ledgers (observability/"
                                "attribution.py): wall = data_wait + "
                                "host_dispatch + device_compute + "
                                "exposed_comms + residual; a gate "
                                "regression reads its cause here before "
                                "anyone re-profiles",
            "skew": {
                "framework": next(
                    (r.get("skew") for r in fw if r.get("skew")), None),
                "tuner": (tuner_res or {}).get("skew"),
                "dispatch": (dispatch or {}).get("skew"),
                "loader": (loader or {}).get("skew"),
                "overlap": (overlap_res or {}).get("skew"),
            },
            "skew_wait_ms_per_step": (
                (next((r.get("skew") for r in fw if r.get("skew")),
                      None) or {}).get("max_skew_wait_ms")),
            "skew_note": "cross-host clock-sync + wire-vs-skew-wait "
                         "split of exposed comms (observability/skew.py); "
                         "single-host bench rounds read 0 — the metric "
                         "exists so a multi-host round that starts "
                         "pacing on one slow host regresses loudly "
                         "(tools/trend.py TRACKED)",
            "flops_per_step": flops,
            "achieved_tflops": round(tflops, 2) if tflops else None,
            "tflops_note": "achieved = XLA cost-analysis FLOPs / median "
                           "step time",
            "loader_fed_ips": round(loader["ips"], 1) if loader else None,
            "loader_fed_steady_ips": round(loader["steady_ips"], 1)
                if loader else None,
            "loader_fed_steps": loader["steps"] if loader else None,
            "loader_backend": loader.get("loader_backend") if loader else None,
            "loader_wire_ips": round(loader["wire_ips"], 1)
                if loader else None,
            "loader_assembly_ceiling_ips": round(
                loader["assembly_ceiling_ips"], 1) if loader else None,
            "loader_steady_vs_pipeline_ceiling": loader["steady_vs_ceiling"]
                if loader else None,
            "loader_steady_vs_h2d_roofline": loader["steady_vs_wire"]
                if loader else None,
            "loader_breakdown": loader.get("breakdown") if loader else None,
            "loader_prefetch_depth": loader.get("prefetch_depth")
                if loader else None,
            "h2d_roofline_ips": round(h2d["ips"], 1) if h2d else None,
            "h2d_roofline_mb_s": round(h2d["mb_per_s"], 1) if h2d else None,
            "input_pipeline_ceiling_ips": round(
                h2d["pipeline_ceiling_ips"], 1) if h2d else None,
            "loader_fed_vs_resident": round(loader["ips"] / fw_med, 4)
                if loader else None,
            "loader_note": "all loader numbers come from ADJACENT WINDOWS "
                           "OF ONE PROCESS: pure "
                           "wire (depth 2 in flight), wire+synchronous "
                           "assembly with ONE transfer in flight (the "
                           "serialized bound), pure assembly (the "
                           "assemble side of loader_breakdown), then the "
                           "loader-fed train loop: buffer-pool staging + "
                           "native async assembly ring + depth-N "
                           "DevicePrefetcher with explicit completion "
                           "handles (settled just-in-time, staging "
                           "buffers recycled on transfer retire).  The "
                           "serialized bound pays the transfer's full "
                           "latency each batch; depth>=2 keeps the wire "
                           "draining back-to-back.  data_wait_ms_mean in "
                           "loader_breakdown is the prefetcher's "
                           "settle-wait — the same quantity the runner "
                           "records as step.data_wait_ms for the "
                           "report's input-bound/compute-bound label",
            "weak_scaling_cpu_ips": scaling_fw,
            "weak_scaling_plainjax_cpu_ips": scaling_base,
            "weak_scaling_efficiency_1to8": eff(scaling_fw),
            "weak_scaling_plainjax_efficiency_1to8": eff(scaling_base),
            "framework_vs_plainjax_paired": scaling_ratio,
            "weak_scaling_trials": scaling_detail,
            "scaling_note": "n virtual devices timeshare ONE host core; "
                            "ideal total ips is flat.  The plainjax arm is "
                            "the same step hand-written with jax.jit, run "
                            "in the SAME process as the framework arm in "
                            "alternating segments; the paired ratio is "
                            "framework overhead, the rest is XLA-CPU "
                            "partitioned-program cost.  Medians over "
                            f"{SCALING_TRIALS} trials",
            "dispatch_overhead_ms_per_step": dispatch.get(
                "dispatch_overhead_ms_per_step") if dispatch else None,
            "unroll_speedup": dispatch.get("unroll_speedup")
                if dispatch else None,
            "dispatch": dispatch,
            "dispatch_note": "tiny-model paired segments at unroll in "
                             "{1, 8, 32} (one process, round-robin "
                             "segments): per-step time is host dispatch "
                             "cost / unroll + a fitted compute floor.  "
                             "dispatch_overhead_ms_per_step is the "
                             "measured per-step overhead above that "
                             "floor per unroll factor; unroll_speedup = "
                             "t(1)/t(32).  Tracks the megastep host-"
                             "overhead trajectory run-over-run",
            "comms_exposed_ms_per_step": overlap_res.get(
                "comms_exposed_ms_per_step") if overlap_res else None,
            "overlap_speedup": overlap_res.get("overlap_speedup")
                if overlap_res else None,
            "overlap": overlap_res,
            "overlap_note": "latency-hiding scheduler on vs off, PAIRED "
                            "round-robin segments in one process (PS-LB "
                            "strategy, unroll=4 megasteps): 'on' issues "
                            "bucketed reductions in reverse-layer order "
                            "and carries ZeRO params sharded so the "
                            "weight all-gather sits adjacent to the next "
                            "forward; 'off' is the serialized "
                            "post-backward schedule.  "
                            "comms_exposed_ms_per_step is priced from "
                            "each arm's scheduled HLO async "
                            "start/done windows (kernel/overlap).  "
                            "Tracks the overlap-efficiency trajectory "
                            "run-over-run",
            "compress_speedup": compress_res.get("compress_speedup")
                if compress_res else None,
            "compress_wire_mb_per_step": compress_res.get("wire_mb_per_step")
                if compress_res else None,
            "compress": compress_res,
            "compress_note": "f32 AllReduce vs bf16 / blockwise-int8+EF / "
                             "PowerSGD wires, paired round-robin segments "
                             "in one process: compress_speedup is each "
                             "arm's paired step-time ratio vs f32, "
                             "wire_mb_per_step the cost model's "
                             "compressor-exact bytes-on-the-wire.  On a "
                             "compute-bound host the arms tie; the wire "
                             "column is the DCN-regime signal.  Tracks "
                             "ROADMAP item 2 run-over-run",
            "hier_speedup": hier_res.get("hier_speedup")
                if hier_res else None,
            "hier_wire_dcn_ratio": hier_res.get("hier_wire_dcn_ratio")
                if hier_res else None,
            "hier": hier_res,
            "hier_note": "flat f32 AllReduce vs the hierarchical "
                         "two-level family (full-precision RS/AG on the "
                         "intra-host leg, bf16 / blockwise-int8+EF wire "
                         "only across DCN) on a forced two-host CPU "
                         "mesh (d=4 x h=2 via AUTODIST_HIER_ICI), "
                         "paired round-robin segments in one process.  "
                         "hier_wire_dcn_ratio is the best hier arm's "
                         "MEASURED DCN-leg bytes (trace-time kernel "
                         "tally) over the flat f32 ring's DCN share; "
                         "wire_match_pred pins the tally to the cost "
                         "model's hier_wire_split.  On a compute-bound "
                         "host the step times tie; the DCN column is "
                         "the multi-host signal.  Tracks "
                         "docs/collectives.md run-over-run",
            "serve_p50_ms": serve_res.get("serve_p50_ms")
                if serve_res else None,
            "serve_p99_ms": serve_res.get("serve_p99_ms")
                if serve_res else None,
            "serve_rps_at_p99_slo": serve_res.get("serve_rps_at_p99_slo")
                if serve_res else None,
            "serve": serve_res,
            "serve_note": "serve.Server (AOT buckets 8/32, 2ms coalesce "
                          "window) on the zoo BERT-tiny encoder, driven "
                          "closed-loop at 1/4/16 concurrent clients with "
                          "variable-row requests.  serve_rps_at_p99_slo is "
                          "the best achieved rps among levels whose p99 "
                          "held the BENCH_SERVE_SLO_MS budget (default "
                          "50ms); p50/p99 are that level's.  Tracks the "
                          "continuous-batching latency/throughput "
                          "trajectory run-over-run",
            "decode_tokens_per_sec": decode_res.get("decode_tokens_per_sec")
                if decode_res else None,
            "decode_p99_ms": decode_res.get("decode_p99_ms")
                if decode_res else None,
            "serve_rps_at_p99_slo_through_scale": decode_res.get(
                "serve_rps_at_p99_slo_through_scale")
                if decode_res else None,
            "decode": decode_res,
            "decode_note": "serve.DecodeServer (slot-based KV-cache "
                           "continuous batching, bucket 8x32, 2 replicas "
                           "on the forced 8-device CPU mesh) on the zoo "
                           "tiny causal LM, closed-loop 1/4/16 clients "
                           "with ragged prompts; the 16-client level "
                           "re-runs THROUGH a forced shrink->grow fleet "
                           "reshape (zero-drop evict/re-queue, "
                           "exactly-once asserted from the server's own "
                           "accounting).  decode_tokens_per_sec / "
                           "decode_p99_ms are the steady 16-client "
                           "level's; serve_rps_at_p99_slo_through_scale "
                           "the through-scale level's rps when its p99 "
                           "held BENCH_DECODE_SLO_MS.  All three "
                           "trend-TRACKED",
            "retune_payoff_pct": retune_res.get("retune_payoff_pct")
                if retune_res else None,
            "retune_switch_ms": retune_res.get("retune_switch_ms")
                if retune_res else None,
            "retune": retune_res,
            "retune_note": "online re-tuning controller "
                           "(docs/retuning.md): one run launched on "
                           "deliberately stale exec knobs (unroll=1 on a "
                           "tiny dispatch-bound model), AUTODIST_RETUNE="
                           "exec; the controller re-prices the exec-knob "
                           "grid under the calibrated host-dispatch "
                           "floor each flush window and switches at a "
                           "megastep boundary.  retune_payoff_pct pairs "
                           "the pre-switch p50 against the first steady "
                           "post-switch window within the SAME process; "
                           "retune_switch_ms is the measured switch "
                           "downtime (the recompile is charged to the "
                           "retune_switch_ms goodput class).  Both "
                           "trend-sentinel TRACKED",
            "reshard_restore_ms": elastic_res.get("reshard_restore_ms")
                if elastic_res else None,
            "post_resume_latency_delta_pct": elastic_res.get(
                "post_resume_latency_delta_pct") if elastic_res else None,
            "elastic": elastic_res,
            "elastic_note": "paired save->kill->reshard-resume cycles in "
                            "one process (docs/elasticity.md): a PS "
                            "(zero1) run saves manifest-carrying "
                            "checkpoints on the full mesh, the session "
                            "rebuilds on half the devices, and each "
                            "cycle's cross-shape restore is timed "
                            "(reshard_restore_ms, value-exactness "
                            "asserted).  post_resume_latency_delta_pct "
                            "pairs the resharded state against a "
                            "fresh-init state on the same shrunk runner "
                            "— near zero means the restored layout "
                            "carries no step-time poison.  Tracks the "
                            "elastic-resume price run-over-run",
            "degrade_to_decision_ms": selfheal_res.get(
                "degrade_to_decision_ms") if selfheal_res else None,
            "selfheal_goodput_retained_pct": selfheal_res.get(
                "selfheal_goodput_retained_pct") if selfheal_res else None,
            "selfheal": selfheal_res,
            "selfheal_note": "self-healing eviction of a degraded host "
                             "(docs/retuning.md Reshape-on-degrade): "
                             "paired control vs degraded arms; the "
                             "degraded arm pays the slow_host chaos "
                             "fault's deterministic drag as barrier wait "
                             "and feeds the monitor the matching "
                             "straggler verdict until the healer's "
                             "hysteresis + pricing evicts the host "
                             "(emergency-save -> stubbed re-exec -> "
                             "resume on half the devices).  "
                             "degrade_to_decision_ms is the measured "
                             "onset->decision latency; "
                             "selfheal_goodput_retained_pct the stitched "
                             "cross-generation goodput_pct over the "
                             "control arm's (episode billed as "
                             "selfheal_ms).  Both trend-sentinel TRACKED",
            "mem_peak_gb": mem_res.get("mem_peak_gb") if mem_res else None,
            "mem_prediction_error_pct": mem_res.get(
                "mem_prediction_error_pct") if mem_res else None,
            "memory": mem_res,
            "memory_note": "HBM memory ledger (docs/memory.md): the zoo "
                           "transformer in four observed arms — PS "
                           "staleness (fully replicated optimizer state) "
                           "vs PS zero1 (state sharded 1/N), each at "
                           "unroll 1 and 8 — with the per-class predicted "
                           "split, measured boundary peak, and "
                           "reconciliation error persisted per arm.  "
                           "mem_peak_gb is the worst-arm measured peak; "
                           "mem_prediction_error_pct the worst-arm "
                           "|measured - predicted-resident| error.  Both "
                           "trend-sentinel TRACKED: a memory regression "
                           "or a cost-model drift fails bench.py --trend",
            "automap_search_ms": automap_res.get("automap_search_ms")
                if automap_res else None,
            "automap_rediscovered_tp": automap_res.get(
                "automap_rediscovered_tp", False) if automap_res else False,
            "automap_rediscovered_ep": automap_res.get(
                "automap_rediscovered_ep", False) if automap_res else False,
            "automap_fallback_dp": automap_res.get(
                "automap_fallback_dp", False) if automap_res else False,
            "automap_prediction_error": automap_res.get(
                "automap_prediction_error") if automap_res else None,
            "automap_tp_ep_composed": automap_res.get(
                "automap_tp_ep_composed", False) if automap_res else False,
            "automap_dp_pipe_composed": automap_res.get(
                "automap_dp_pipe_composed", False) if automap_res else False,
            "automap_placement_model_ici": automap_res.get(
                "automap_placement_model_ici", False)
                if automap_res else False,
            "automap": automap_res,
            "automap_note": "per-op sharding search quality on a forced "
                            "8-device mesh (docs/tuning.md Automap): the "
                            "searcher must REDISCOVER tensor parallelism "
                            "on a wide-FFN transformer and expert "
                            "parallelism on the zoo MoE without mesh or "
                            "builder hints, and fall back to the "
                            "data-parallel zoo winner on a tiny model; "
                            "automap_search_ms is the full build cost "
                            "(inner zoo base search + chain DP) and "
                            "automap_prediction_error the chosen plan's "
                            "predicted-vs-measured step time.  The "
                            "multi-axis flags pin composition: "
                            "automap_tp_ep_composed = the MoE winner is "
                            "a composed expert x model mesh, "
                            "automap_dp_pipe_composed = a stacked-blocks "
                            "model draws a data x pipe proposal, "
                            "automap_placement_model_ici = on a fake "
                            "4x2-host pod the placement pass keeps the "
                            "model axis on the intra-host ici tier.  All "
                            "trend-sentinel tracked: a rediscovery or "
                            "composition flag dropping to 0 or search "
                            "cost regressing fails bench.py --trend",
            "pipeline_speedup": pipeline_res.get("pipeline_speedup")
                if pipeline_res else None,
            "bubble_fraction": pipeline_res.get("bubble_fraction")
                if pipeline_res else None,
            "pipeline": pipeline_res,
            "pipeline_note": "zoo transformer under Pipeline(stages=2, "
                             "microbatches=4) on a forced 8-device mesh, "
                             "paired round-robin shift vs sequential "
                             "arms (docs/pipelining.md): "
                             "pipeline_speedup is the "
                             "sequential-schedule / shifting-schedule "
                             "step-time ratio (~1 on a timeshared host "
                             "where both arms run the same M*P real "
                             "stage slots; approaches S*(1-bubble) on "
                             "real stages), bubble_fraction is measured "
                             "STRUCTURALLY — 1 - M/ticks with the tick "
                             "count parsed from the traced schedule "
                             "scan — and must equal the cost model's "
                             "(S-1)/(S+M-1) conveyor-adjusted "
                             "prediction exactly (bubble_within_floor; "
                             "a timeshared host cannot surface idle "
                             "slots as wall-clock, the fill/drain skip "
                             "exists to erase them).  The warm-up "
                             "losses are asserted BITWISE equal across "
                             "both arms before timing.  Both headline "
                             "keys are trend-sentinel TRACKED",
            "tuner_prediction_error": tuner_res.get("prediction_error_pct")
                if tuner_res else None,
            "tuner": tuner_res,
            "tuner_note": "AutoStrategy's analytic cost model vs the "
                          "measured step loop on a CIFAR-ResNet "
                          "(prediction_error_pct = (predicted - measured) "
                          "/ measured); the ranked candidate table is the "
                          "sidecar AutoStrategy persists next to the "
                          "strategy artifact.  Track run-over-run for "
                          "cost-model drift",
            "long_context": long_context,
            "long_context_note": "causal transformer block fwd+bwd, fused "
                                 "Pallas flash kernels vs the dense VJP, "
                                 "paired in one process per seq point, with "
                                 "the compiler memory_analysis numbers and "
                                 "the dense OOM boundary — "
                                 "flash keeps O(s) residents where the "
                                 "dense VJP's (s x s) residuals hit the "
                                 "HBM wall",
            "gspmd_zero_verified": zero.get("gspmd_zero_verified", False),
            "tp_verified": zero.get("tp_verified", False),
            "moe_expert_parallel_verified": zero.get(
                "moe_expert_parallel_verified", False),
            "multislice_compile_verified": zero.get(
                "multislice_compile_verified", False),
            "zero_verify": zero,
            "pod_compile_verified": pod.get("pod_compile_verified", False),
            "pod_compile": pod,
    }

    # -- output: ONE compact headline line (the driver records only a ~3.6KB
    # stdout tail — round 4's single ~6KB line was truncated into an
    # unparseable record, VERDICT r4 weak #1); the full detail blob goes to
    # DETAILS_PATHS and is referenced by path --------------------------------
    vs_paired = round(paired["ratio"], 4) if paired else None
    headline = {
        "metric": f"resnet50_imagenet_train_images_per_sec_{n_chips}chip",
        "value": round(fw_med, 1),
        "unit": "images/sec",
        "vs_baseline": vs_paired if vs_paired is not None
            else round(fw_med / base_med, 4),
        "estimator": ("paired-16-segment-pairs" if vs_paired is not None
                      else "interleaved-median-FALLBACK"),
        "vs_baseline_interleaved_median": round(fw_med / base_med, 4),
        "vs_baseline_minmin": round(max(fw_ips) / max(base_ips), 4),
        "spread_pct": {"fw": _spread_pct(fw_ips, fw_med),
                       "base": _spread_pct(base_ips, base_med)},
        "bert_paired": round(bert["ratio"], 4) if bert else None,
        "bf16_vs_f32": round(bf16_med / fw_med, 4) if bf16_med else None,
        "achieved_tflops": round(tflops, 2) if tflops else None,
        "loader_steady_vs_ceiling": details["loader_steady_vs_pipeline_ceiling"],
        "loader_steady_vs_h2d": details["loader_steady_vs_h2d_roofline"],
        "tuner_chosen": tuner_res.get("chosen") if tuner_res else None,
        "tuner_prediction_error": details["tuner_prediction_error"],
        "automap_search_ms": details["automap_search_ms"],
        "automap_rediscovered_tp": (
            float(details["automap_rediscovered_tp"])
            if automap_res else None),
        "automap_rediscovered_ep": (
            float(details["automap_rediscovered_ep"])
            if automap_res else None),
        "automap_prediction_error": details["automap_prediction_error"],
        "automap_tp_ep_composed": (
            float(details["automap_tp_ep_composed"])
            if automap_res else None),
        "automap_dp_pipe_composed": (
            float(details["automap_dp_pipe_composed"])
            if automap_res else None),
        "automap_placement_model_ici": (
            float(details["automap_placement_model_ici"])
            if automap_res else None),
        "serve_p99_ms": details["serve_p99_ms"],
        "serve_rps_at_p99_slo": details["serve_rps_at_p99_slo"],
        "decode_tokens_per_sec": details["decode_tokens_per_sec"],
        "decode_p99_ms": details["decode_p99_ms"],
        "serve_rps_at_p99_slo_through_scale":
            details["serve_rps_at_p99_slo_through_scale"],
        "compress_speedup": details["compress_speedup"],
        "hier_speedup": details["hier_speedup"],
        "hier_wire_dcn_ratio": details["hier_wire_dcn_ratio"],
        "unroll_speedup": details["unroll_speedup"],
        "pipeline_speedup": details["pipeline_speedup"],
        "bubble_fraction": details["bubble_fraction"],
        "retune_payoff_pct": details["retune_payoff_pct"],
        "retune_switch_ms": details["retune_switch_ms"],
        "degrade_to_decision_ms": details["degrade_to_decision_ms"],
        "selfheal_goodput_retained_pct":
            details["selfheal_goodput_retained_pct"],
        "skew_wait_ms_per_step": details["skew_wait_ms_per_step"],
        "mem_peak_gb": details["mem_peak_gb"],
        "mem_prediction_error_pct": details["mem_prediction_error_pct"],
        "scaling_fw_vs_pj_paired": scaling_ratio,
        "scaling_eff_1to8": {"fw": eff(scaling_fw),
                             "pj": eff(scaling_base)},
        "long_context": {
            "flash_max_seq": long_context.get("flash_max_seq"),
            "dense_max_seq": long_context.get("dense_max_seq"),
            "flash_over_dense": {
                s: round(p["flash_over_dense_paired"], 3)
                for s, p in long_context["points"].items()
                if isinstance(p, dict)
                and p.get("flash_over_dense_paired") is not None},
        },
        "verified": {
            "zero": details["gspmd_zero_verified"],
            "tp": details["tp_verified"],
            "moe_ep": details["moe_expert_parallel_verified"],
            "multislice": details["multislice_compile_verified"],
            "pod_256chip": details["pod_compile_verified"],
        },
        "details_file": None,
    }
    # The repo-root copy is INTENTIONAL: the driver's end-of-round commit
    # sweeps it in, making the full blob a durable record next to the
    # BENCH_r0N.json stdout-tail snapshots.
    written = []
    for path in DETAILS_PATHS:
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            headline["details_file"] = path  # each copy self-references
            with open(path, "w") as f:
                f.write(json.dumps({"headline": headline,
                                    "details": details}, indent=1))
            written.append(path)
        except OSError as e:
            sys.stderr.write(f"bench: could not write {path}: {e}\n")
    headline["details_file"] = written[0] if written else None
    sys.stderr.write(f"bench: full details -> {', '.join(written) or '(none)'}\n")
    line = json.dumps(headline, separators=(",", ":"))
    if len(line) >= 3000:
        # Never abort a finished run over line length: shed the optional
        # keys (the driver's record keeps ~3.6KB of stdout tail).
        sys.stderr.write(f"bench: headline {len(line)}B too long; trimming\n")
        keep = ("metric", "value", "unit", "vs_baseline", "estimator",
                "verified", "details_file")
        line = json.dumps({k: headline[k] for k in keep if k in headline},
                          separators=(",", ":"))
    print(line)
    # Trend sentinel AFTER the headline prints (the record must survive a
    # regression verdict): every bench run appends its own diagnosis to
    # TREND.md, and a >noise-floor headline regression exits nonzero
    # (--trend-warn-only downgrades to a warning).
    rc = _run_trend(trend_warn_only)
    if rc:
        sys.exit(rc)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", default=None,
                    choices=["framework", "framework-bf16", "baseline",
                             "paired", "bert", "tuner", "automap",
                             "pipeline",
                             "dispatch", "overlap", "compress", "hier",
                             "serve", "decode",
                             "retune", "selfheal", "mem",
                             "elastic", "loader", "h2d", "scaling-paired",
                             "longcontext", "longcontext-ring",
                             "zero-verify", "pod-compile"])
    ap.add_argument("--trend", action="store_true",
                    help="run ONLY the trend sentinel over the BENCH_r*/"
                         "BENCH_DETAILS history (no benchmarks)")
    ap.add_argument("--trend-warn-only", action="store_true",
                    help="report trend regressions without a nonzero exit")
    args = ap.parse_args()
    if args.trend:
        from autodist_tpu.tools import trend as _trend
        argv = ["--root", os.path.dirname(os.path.abspath(__file__))]
        if args.trend_warn_only:
            argv.append("--warn-only")
        sys.exit(_trend.main(argv))
    if args.worker in _CHIP_WORKERS:
        _require_tpu(args.worker)
    if args.worker == "framework":
        _worker_framework()
    elif args.worker == "framework-bf16":
        _worker_framework(precision="bf16")
    elif args.worker == "baseline":
        _worker_baseline()
    elif args.worker == "paired":
        _worker_paired()
    elif args.worker == "bert":
        _worker_bert()
    elif args.worker == "tuner":
        _worker_tuner()
    elif args.worker == "automap":
        _worker_automap()
    elif args.worker == "pipeline":
        _worker_pipeline()
    elif args.worker == "dispatch":
        _worker_dispatch()
    elif args.worker == "overlap":
        _worker_overlap()
    elif args.worker == "compress":
        _worker_compress()
    elif args.worker == "hier":
        _worker_hier()
    elif args.worker == "serve":
        _worker_serve()
    elif args.worker == "decode":
        _worker_decode()
    elif args.worker == "retune":
        _worker_retune()
    elif args.worker == "selfheal":
        _worker_selfheal()
    elif args.worker == "mem":
        _worker_mem()
    elif args.worker == "elastic":
        _worker_elastic()
    elif args.worker == "loader":
        _worker_loader()
    elif args.worker == "h2d":
        _worker_h2d()
    elif args.worker == "scaling-paired":
        _worker_scaling_paired()
    elif args.worker == "longcontext":
        _worker_longcontext()
    elif args.worker == "longcontext-ring":
        _worker_longcontext_ring()
    elif args.worker == "zero-verify":
        _worker_zero_verify()
    elif args.worker == "pod-compile":
        _worker_pod_compile()
    else:
        main(trend_warn_only=args.trend_warn_only)
