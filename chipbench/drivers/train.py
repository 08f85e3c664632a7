"""Driver ``train``: a closed loop over training steps.

One run of one cell: the reference check, the program's session through the
calls a user makes (``AutoDist`` -> ``capture`` ->
``create_distributed_session`` -> ``create_state`` -> ``DevicePrefetcher``
-> ``runner.step``), a warm-up of the cell's one shape, and a window of
``seconds`` in which the host runs at most ``lag_steps`` steps ahead of the
device, as a user's loop does.  With ``trace`` a slice of ``TRACE_STEPS``
more steps is profiled after the window has closed, so that the window's
own numbers carry none of the profiler's start and stop.
"""
import collections
import glob
import itertools
import json
import math
import os
import shutil
import time

import jax
import numpy as np
import optax

from chipbench import flops, measure, reference, trace_reduce

WARMUP_STEPS = 3
TRACE_STEPS = 12
MIN_STEPS_FOR_P90 = 100
# The reference takes a batch this many tokens at a time.
REFERENCE_CHUNK_TOKENS = 1024


def _key(sizes):
    return json.dumps(sizes, sort_keys=True)


def _optimizer(deployment):
    spec = deployment["optimizer"]
    return getattr(optax, spec["name"])(spec["learning_rate"])


class Sessions:
    """The program's session for a set of sizes; one lives at a time.

    The check and the window share a session where the check runs the
    cell's own sizes, and then the program that is checked is the program
    that is measured; where the check runs others (fewer layers, so that
    the reference fits a chip) its session is dropped before the cell's is
    built.
    """

    def __init__(self, kind, example_batch, seed, spans):
        self._kind, self._example, self._seed = kind, example_batch, seed
        self._spans = spans
        self._key = self._values_key = self._values = None
        self.runner = self.state = None

    def initial_values(self, sizes):
        """The model's values from the seed, made on the device in one
        jitted call of the program's own initialiser."""
        key = _key(sizes)
        if key != self._values_key:
            init, _ = self._kind.program(sizes)
            with self._spans.span("init"):
                self._values = jax.block_until_ready(
                    jax.jit(init)(jax.random.PRNGKey(self._seed)))
            self._values_key = key
        return self._values

    def get(self, sizes):
        from autodist_tpu import AutoDist, strategy
        from autodist_tpu.autodist import _reset_default
        key = _key(sizes)
        if key == self._key:
            return self
        self.runner = self.state = None
        # The program allows one AutoDist a process; this is its own hook
        # for a second (PERF.md, open questions).
        _reset_default()
        _, loss_fn = self._kind.program(sizes)
        deployment = sizes["deployment"]
        params = self.initial_values(sizes)
        with self._spans.span("capture"):
            ad = AutoDist(
                strategy_builder=getattr(strategy, deployment["strategy"])())
            item = ad.capture(loss_fn, params, _optimizer(deployment),
                              example_batch=self._example)
            # The program holds the values now; these references would
            # keep a second copy of the model on the first chip.
            del params
            self._values_key = self._values = None
            self.runner = ad.create_distributed_session(item)
        with self._spans.span("create_state"):
            self.state = jax.block_until_ready(self.runner.create_state())
        self._key = key
        return self


class StepLoop:
    """The closed loop: dispatch a step, then wait for the one ``lag``
    before it, so that the host is never more than ``lag`` steps ahead of
    the last completed step and dispatch and the input pull overlap the
    device.  ``step(batch)`` dispatches and returns the step's loss (a
    device value); ``done_at`` holds the host clock at each completion."""

    def __init__(self, step, feed, lag, annotate):
        self._step, self._feed, self._lag = step, feed, lag
        self._annotate = annotate
        self._pending = collections.deque()
        self.dispatched = 0
        self.done_at, self.losses = [], []

    def _dispatch(self):
        with self._annotate("next_batch"):
            batch = next(self._feed)
        with self._annotate("dispatch"):
            self._pending.append(self._step(batch))
        self.dispatched += 1

    def _settle(self):
        loss = self._pending.popleft()
        with self._annotate("block"):
            loss.block_until_ready()
        self.done_at.append(time.perf_counter())
        self.losses.append(loss)

    def run_until(self, stop):
        """Dispatch until ``stop()``; then let the queue drain."""
        while not stop():
            self._dispatch()
            if len(self._pending) > self._lag:
                self._settle()
        while self._pending:
            self._settle()


def step_program_bytes(runner, host_batch, state):
    """Bytes one chip holds while the cell's step runs, by the compiler's
    ``memory_analysis()`` of the program that ran.  Lowering the jitted
    step again with the arguments it has just run on finds the compiled
    program in JAX's own cache; nothing compiles."""
    step = runner.make_callable(host_batch)
    batch = runner.remapper.shard_batch(host_batch)
    t0 = time.perf_counter()
    analysis = step.lower(state, batch).compile().memory_analysis()
    print(f"chipbench: memory_analysis() of the step found in "
          f"{time.perf_counter() - t0:.3f} s", flush=True)
    return (analysis.argument_size_in_bytes + analysis.temp_size_in_bytes
            + analysis.output_size_in_bytes - analysis.alias_size_in_bytes)


def traced_slice(loop, root):
    """Profile ``TRACE_STEPS`` more steps of the loop and reduce the trace
    (``trace_reduce.py``); the trace stays under ``<root>/.chipbench_work``
    until the next traced run."""
    trace_dir = os.path.join(root, ".chipbench_work", "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # the annotations, not every call
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        traced_to = loop.dispatched + TRACE_STEPS
        loop.run_until(lambda: loop.dispatched >= traced_to)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    return trace_reduce.reduce(trace_reduce.load(path), kernels=flops.KERNELS)


def window_summary(done_at, first, tokens_per_step):
    """The window opens on the completion before ``done_at[first]`` and
    closes on the last: its length, the times between completions in ms,
    and the tokens of the steps completed inside it over its length."""
    window_s = done_at[-1] - done_at[first - 1]
    step_ms = np.diff(done_at[first - 1:]) * 1e3
    return {"window_s": window_s, "step_ms": step_ms,
            "tokens_per_s": len(step_ms) * tokens_per_step / window_s}


def reference_check(kind, sizes, mix, rows, seed, sessions, spans):
    """A few Adam steps on batches of the cell's own shape: the program's
    losses against the plain reference's, from the same initial values."""
    check = sizes["check"]
    check_sizes = {**sizes, **check["sizes"]}
    rng = np.random.RandomState([seed, 0])
    batches = [kind.host_batch(check_sizes, mix, rows, rng)
               for _ in range(check["steps"])]
    values = sessions.initial_values(check_sizes)
    with spans.span("reference"):
        want = reference.train_losses(
            kind.reference_loss(check_sizes), values, batches,
            check_sizes["deployment"]["optimizer"]["learning_rate"],
            chunk_rows=math.gcd(rows, max(
                1, REFERENCE_CHUNK_TOKENS // kind.tokens_per_row(mix))))
    del values
    session = sessions.get(check_sizes)
    got = []
    # Where the check runs the cell's own sizes, its program is the cell's
    # and its first step is the cell's compile (or its read from the cache).
    shared = not check["sizes"]
    for i, batch in enumerate(batches):
        with spans.span("compile" if shared and i == 0 else "check"):
            session.state, metrics = session.runner.step(session.state, batch)
            got.append(float(metrics["loss"]))
    worst = max(abs(g - w) / abs(w) for g, w in zip(got, want))
    return {"ok": bool(np.isfinite(got).all() and worst <= check["rtol"]),
            "program": got, "reference": want, "worst_rel": worst,
            "rtol": check["rtol"], "sizes_checked": check["sizes"],
            "shared_session": shared}


def run(cell, *, seed, seconds, trace, catalog, clock0):
    """Run the cell once; returns the record the harness and the per-layer
    readers take their numbers from.  ``clock0`` is ``(perf_counter value,
    process age)`` taken together at the harness's start."""
    from autodist_tpu.data import DevicePrefetcher
    sizes, mix = cell["sizes"], cell["mix"]
    kind = catalog.module("kinds", sizes["kind"])
    devices = jax.devices()
    chips = len(devices)
    peak = catalog.peak(devices[0].device_kind)
    spans = measure.Spans()
    rows = mix["rows_per_chip"] * chips
    tokens_per_step = rows * kind.tokens_per_row(mix)

    with spans.span("traffic"):
        rng = np.random.RandomState([seed, 1])
        pool = [kind.host_batch(sizes, mix, rows, rng)
                for _ in range(mix["pool_batches"])]
    sessions = Sessions(kind, pool[0], seed, spans)
    check = reference_check(kind, sizes, mix, rows, seed, sessions, spans)
    print(f"chipbench: reference check {json.dumps(check)}", flush=True)
    session = sessions.get(sizes)
    runner, state = session.runner, session.state
    session.state = None
    program = runner.program
    print(f"chipbench: mesh {dict(program.mesh.shape)}, lowering "
          f"{'explicit' if program.use_explicit_path else 'gspmd'}",
          flush=True)

    feed = DevicePrefetcher(itertools.cycle(pool), runner.remapper, depth=2)

    held = []   # a step's rows that met a held expert, where it says

    def step(batch):
        nonlocal state
        state, metrics = runner.step(state, batch, shard_inputs=False)
        held.append((metrics.get("aux") or {}).get("moe.held_assignments"))
        return metrics["loss"]

    loop = StepLoop(step, feed, mix["lag_steps"], spans.annotate)
    done_at, losses = loop.done_at, loop.losses
    if not check["shared_session"]:
        with spans.span("compile"):
            loop.run_until(lambda: loop.dispatched >= 1)
    with spans.span("warmup"):
        loop.run_until(lambda: loop.dispatched >= 1 + WARMUP_STEPS)

    # The window opens on a completed step and closes on the last one's.
    opened_at, first = done_at[-1], len(done_at)
    setup_s = clock0[1] + (opened_at - clock0[0])
    wait_ms_before = feed.stats()["data_wait_ms_total"]
    loop.run_until(lambda: time.perf_counter() - opened_at >= seconds)
    window = window_summary(done_at, first, tokens_per_step)
    window_s, step_ms = window["window_s"], window["step_ms"]
    data_wait_s = (feed.stats()["data_wait_ms_total"] - wait_ms_before) / 1e3
    memory = [d.memory_stats() or {} for d in devices]
    step_bytes = step_program_bytes(runner, pool[0], state)
    print("chipbench: set-up spans (s) " + json.dumps(
        {name: round(spans.seconds(name), 3)
         for name, _, _ in spans.records}), flush=True)
    print(f"chipbench: the step's program holds {step_bytes} bytes a chip "
          "(arguments + temporaries + outputs not aliased, by the "
          "compiler); memory_stats " + json.dumps(memory), flush=True)
    window_losses = [float(x) for x in losses[first:]]
    n_steps = len(step_ms)
    if held[first] is not None:
        print("chipbench: moe.held_assignments of the window's first and "
              f"last step {float(held[first]):.0f} {float(held[-1]):.0f}",
              flush=True)
    print(f"chipbench: {n_steps} steps in a window of {window_s:.3f} s; "
          f"step ms median {np.median(step_ms):.3f}, p90 from {n_steps} "
          f"samples" + ("" if n_steps >= MIN_STEPS_FOR_P90 else
                        f" (fewer than the {MIN_STEPS_FOR_P90} it wants)"),
          flush=True)

    reduced = traced_slice(loop, catalog.root) if trace else None

    tokens_per_s = window["tokens_per_s"]
    flops_per_token = kind.flops_per_token(sizes, mix)
    achieved = flops_per_token * tokens_per_s
    losses_fine, losses_detail = measure.losses_ok(window_losses)
    return {
        "end_to_end": {"tokens_per_s": tokens_per_s,
                       "step_ms_p90": measure.percentile(step_ms, 90),
                       "setup_s": setup_s},
        "attempted": n_steps,
        "failed": int(sum(not np.isfinite(x) for x in window_losses)),
        "checks": {
            "reference": (check["ok"],
                          f"worst relative difference {check['worst_rel']:.2e}"
                          f" (rtol {check['rtol']})"),
            "losses": (losses_fine, losses_detail),
            "clock": (achieved <= peak["flops_per_s"] * chips,
                      f"{achieved / 1e12:.1f} model TFLOP/s on {chips} x "
                      f"{peak['flops_per_s'] / 1e12:.0f} peak"),
        },
        # The allocator's peak leaves out a running program's temporaries
        # (PERF.md, section 7), so the step's own figure stands beside it.
        "memory_peak_bytes": max([step_bytes] + [
            m.get("peak_bytes_in_use", 0) for m in memory]),
        # What the per-layer readers take their numbers from.
        "chips": chips, "peak": peak, "spans": spans, "window_s": window_s,
        "steps": n_steps, "tokens_per_s": tokens_per_s,
        "flops_per_token": flops_per_token,
        "attention": kind.attention_calls(sizes, mix),
        "counters": {"data_wait_s": data_wait_s,
                     "bytes_in_use": max(m.get("bytes_in_use", 0)
                                         for m in memory)},
        "trace": reduced,
    }
