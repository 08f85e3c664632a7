"""The train path's own spans and counters (docs/observability.md, Pillar 2):
the ``compile`` span wraps what compiles, JAX's own timings are its
children, ``create-state`` has its two parts, every span is an
``autodist.*`` annotation in the profiler's trace with no knob, the hot
loop's annotations vanish with telemetry off, and the compiled step's
instructions map to named scopes without changing the program.
"""
import collections
import contextlib
import re
import time

import jax
import numpy as np
import optax
import pytest

from autodist_tpu import AutoDist, autodist, observability, strategy
from autodist_tpu.data import DevicePrefetcher
from autodist_tpu.graph_item import scope_path
from autodist_tpu.models import lm
from autodist_tpu.observability import profile, tracing
from autodist_tpu.observability.profile import UNATTRIBUTED

JAX_SPANS = ("jax-trace", "jax-lower", "xla-compile")
# PartitionedPS shards over the 8 CPU devices on the explicit shard_map
# path; AllReduce takes the GSPMD (jax.jit) path.
LOWERINGS = {"explicit": "PartitionedPS", "gspmd": "AllReduce"}


@pytest.fixture(autouse=True)
def _fresh_telemetry(monkeypatch):
    monkeypatch.delenv("AUTODIST_TELEMETRY", raising=False)
    monkeypatch.delenv("AUTODIST_TRACE", raising=False)
    observability.refresh()
    observability.reset()
    yield
    observability.refresh()
    observability.reset()


def _session(lowering="explicit"):
    params, loss_fn, batch = lm.tiny_fixture()
    ad = AutoDist(strategy_builder=getattr(strategy, LOWERINGS[lowering])())
    item = ad.capture(loss_fn, params, optax.adam(1e-4), example_batch=batch)
    runner = ad.create_distributed_session(item)
    assert runner.program.use_explicit_path == (lowering == "explicit")
    return ad, runner, batch


def _spans(name=None):
    return [e for e in tracing.events() if e.get("ph") == "X"
            and (name is None or e["name"] == name)]


def _inside(outer, events):
    lo, hi = outer["ts"], outer["ts"] + outer["dur"]
    return [e for e in events if e is not outer
            and lo <= e["ts"] and e["ts"] + e["dur"] <= hi + 1.0]


def _outermost(events):
    return [e for e in events if not any(
        o is not e and o["name"] == e["name"] and o["ts"] <= e["ts"]
        and e["ts"] + e["dur"] <= o["ts"] + o["dur"] for o in events)]


def _counter(name):
    return observability.registry().snapshot()["counters"].get(name, 0)


# -- set-up spans where the work happens -------------------------------------


def test_compile_span_holds_jax_trace_lower_and_xla_compile():
    _, runner, batch = _session()
    state = runner.create_state()
    assert not _spans("compile")        # building a session compiles no step
    state, _ = runner.step(state, batch)
    (compile_span,) = _spans("compile")
    (build,) = _spans("build-step")
    (report,) = _spans("report")
    assert build["ts"] + build["dur"] <= report["ts"] + 1.0
    assert report["ts"] + report["dur"] <= compile_span["ts"] + 1.0
    children = _inside(compile_span, _spans())
    for name in JAX_SPANS:
        kids = _outermost([e for e in children if e["name"] == name])
        assert kids, f"no {name} span inside the compile span"
        assert all(e["args"]["fun_name"] for e in kids)
    # JAX traces every inner function inside the step's own trace; only the
    # outermost is kept, so the ring is not flooded (788 events for this
    # two-layer model otherwise).
    assert len([e for e in children if e["name"] == "jax-trace"]) <= 4
    # compile.ms is the span's wall time: at least the sum of its children.
    child_ms = sum(e["dur"] for name in JAX_SPANS for e in _outermost(
        [c for c in children if c["name"] == name])) / 1e3
    gauge = observability.registry().snapshot()["gauges"]["compile.ms"]
    assert gauge >= child_ms > 0
    assert gauge == pytest.approx(compile_span["dur"] / 1e3, rel=0.05)
    (event,) = [e for e in observability.recorder.events()
                if e["kind"] == "compile"]
    assert "compiled in" in event["detail"]

    before = {n: len(_spans(n)) for n in JAX_SPANS + ("compile",)}
    compiles = _counter("compile.count")
    runner.step(state, batch)
    assert {n: len(_spans(n)) for n in before} == before
    assert _counter("compile.count") == compiles


def test_second_batch_shape_adds_exactly_one_xla_compile():
    _, runner, batch = _session("gspmd")
    state = runner.create_state()
    state, _ = runner.step(state, batch)
    spans, compiles = len(_spans("xla-compile")), _counter("compile.count")
    assert compiles == spans > 0
    # Placed by hand, so that the transfer itself compiles nothing.
    shorter = runner.remapper.shard_batch(
        jax.tree_util.tree_map(lambda x: x[:, :9], batch))
    jax.block_until_ready(shorter)
    spans, compiles = len(_spans("xla-compile")), _counter("compile.count")
    runner.step(state, shorter, shard_inputs=False)
    assert len(_spans("xla-compile")) == spans + 1
    assert _counter("compile.count") == compiles + 1


def test_create_state_span_has_init_and_host_copy():
    _, runner, _ = _session()
    runner.create_state()
    (outer,) = _spans("create-state")
    names = [e["name"] for e in _inside(outer, _spans())]
    assert names.count("init") == 1 and names.count("host-copy") == 1
    (init,) = _spans("init")
    (copy,) = _spans("host-copy")
    assert init["ts"] + init["dur"] <= copy["ts"] + 1.0
    assert "xla-compile" in [e["name"] for e in _inside(init, _spans())]
    assert all(isinstance(x, np.ndarray) for x in
               jax.tree_util.tree_leaves(runner.program.graph_item.params))


def test_megastep_first_call_runs_under_the_compile_span():
    _, runner, batch = _session("gspmd")
    state = runner.create_state()
    block = jax.tree_util.tree_map(lambda x: np.stack([x, x]), batch)
    state, _ = runner.megastep(state, block)
    (compile_span,) = _spans("compile")
    assert compile_span["args"]["unroll"] == "2"
    assert "xla-compile" in [e["name"] for e in
                             _inside(compile_span, _spans())]
    runner.megastep(state, jax.tree_util.tree_map(
        lambda x: np.stack([x, x]), batch))
    assert len(_spans("compile")) == 1


def test_a_span_is_placed_on_the_perf_counter_axis():
    t0 = time.perf_counter()
    with observability.span("probe"):
        pass
    t1 = time.perf_counter()
    (event,) = _spans("probe")
    assert t0 <= tracing.to_perf_counter(event["ts"]) <= t1


# -- one clock ---------------------------------------------------------------


@pytest.fixture
def annotations(monkeypatch):
    """Names of every ``TraceAnnotation`` made while the fixture is live."""
    made = []
    real = jax.profiler.TraceAnnotation

    def recording(name, **kwargs):
        made.append(name)
        return real(name, **kwargs)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", recording)
    return made


def test_every_span_and_the_hot_loop_are_profiler_annotations(annotations):
    _, runner, batch = _session()
    state = runner.create_state()
    state, _ = runner.step(state, batch)
    for name in ("capture", "create-state", "init", "host-copy",
                 "build-step", "report", "compile"):
        assert "autodist." + name in annotations
    assert "autodist.dispatch" not in annotations   # the first call compiles
    del annotations[:]
    feed = DevicePrefetcher(iter([batch, batch]), runner.remapper, depth=1,
                            pull_in_background=False)
    for host_batch in feed:
        state, _ = runner.step(state, host_batch, shard_inputs=False)
    assert annotations.count("autodist.dispatch") == 2
    assert annotations.count("autodist.data_wait") == 2
    assert annotations.count("autodist.shard_batch") == 2
    # The hot loop's annotations are no spans: nothing lands in the ring.
    assert not [e for e in _spans()
                if e["name"] in ("dispatch", "data_wait", "shard_batch")]


def test_trace_knob_has_no_profiler_mode(monkeypatch):
    monkeypatch.setenv("AUTODIST_TRACE", "profiler")
    observability.refresh()
    assert tracing._mode() == "chrome"
    started = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **k: started.append(a))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    _, runner, batch = _session("gspmd")
    state = runner.create_state()
    state, _ = runner.run(state, iter([batch] * 2), 2)
    assert started == []
    runner.run(state, iter([batch] * 2), 2, trace_dir="/tmp/somewhere")
    assert started == [("/tmp/somewhere",)]


def test_telemetry_off_new_call_sites_make_zero_calls(monkeypatch):
    monkeypatch.setenv("AUTODIST_TELEMETRY", "0")
    observability.refresh()
    calls = []
    monkeypatch.setattr(tracing, "annotate",
                        lambda *a, **k: calls.append("annotate"))
    monkeypatch.setattr(tracing, "watch_jax_compiles",
                        lambda: calls.append("watch"))
    monkeypatch.setattr(tracing, "record_complete",
                        lambda *a, **k: calls.append("record"))
    monkeypatch.setattr(tracing.Span, "__enter__",
                        lambda self: calls.append("span"))
    ad, runner, batch = _session()
    state = runner.create_state()
    feed = DevicePrefetcher(iter([batch] * 3), runner.remapper, depth=1,
                            pull_in_background=False)
    for host_batch in feed:     # the first step compiles: listeners stay mute
        state, _ = runner.step(state, host_batch, shard_inputs=False)
    assert calls == []
    assert observability.annotate("x") is tracing.NULL_SPAN
    assert ad.runner is runner


# -- scope names for the whole step ------------------------------------------


def _opcode_counts(text):
    """Instructions of each kind in a compiled program's text."""
    kinds = collections.Counter()
    for line in text.splitlines():
        if profile._INSTRUCTION_RE.match(line):
            m = re.search(r"\s([a-z][a-z\-]*)\(", line.split(" = ", 1)[1])
            kinds[m.group(1) if m else "?"] += 1
    return kinds


def _compiled_text(runner):
    fn, (state, batch) = runner._first_call_signature
    return fn.lower(state, batch).compile().as_text()


@pytest.mark.parametrize("lowering", sorted(LOWERINGS))
def test_scope_table_names_the_whole_step(lowering, monkeypatch):
    ad, runner, batch = _session(lowering)
    assert autodist.get_default_autodist().runner is runner
    with pytest.raises(RuntimeError, match="a step that has run"):
        runner.scope_table()
    state = runner.create_state()
    state, _ = runner.step(state, batch)
    compiles = _counter("compile.count")
    misses = _counter("compile.cache_misses")
    table = runner.scope_table()
    # Nothing new compiles: the executable is the one JAX already holds.
    assert _counter("compile.count") == compiles
    assert _counter("compile.cache_misses") == misses
    scopes = {scope for scope, _ in table.values()}
    assert {"optimizer", "attn", "mlp", "head", "embed", "ln_f",
            UNATTRIBUTED} <= scopes
    assert not [s for s in scopes if s.startswith("layer")]
    if lowering == "explicit":
        assert {"grad_sync", "param_gather", "loss_sync"} <= scopes
    assert {("attn", "forward"), ("attn", "backward"), ("mlp", "forward"),
            ("mlp", "backward"), ("head", "forward"), ("head", "backward"),
            ("optimizer", "update")} <= set(table.values())
    assert {phase for scope, phase in table.values()
            if scope in profile.UPDATE_SCOPES} == {"update"}
    with_scopes = _opcode_counts(_compiled_text(runner))

    # The same step without the Runner's four scopes: names only, so the
    # program has the same instructions of each kind.
    real = jax.named_scope
    monkeypatch.setattr(
        jax, "named_scope", lambda name: contextlib.nullcontext()
        if name in profile.UPDATE_SCOPES else real(name))
    autodist._reset_default()
    _, bare, batch = _session(lowering)
    bare.step(bare.create_state(), batch)
    bare_table = bare.scope_table()
    # Without its names the step's communication is still placed, by what
    # each instruction is (the table's rule); nothing else is.
    placed = {name: scope for name, (scope, _) in bare_table.items()
              if scope in profile.UPDATE_SCOPES}
    assert set(placed) <= set(bare.comm_table())
    assert set(placed.values()) <= {"grad_sync", "param_gather"}
    assert _opcode_counts(_compiled_text(bare)) == with_scopes


def test_scope_table_reads_op_names():
    text = "\n".join([
        "HloModule jit_step_fn",
        "%fused_computation.1 (p: f32[8]) -> f32[8] {",
        "  %p = f32[8]{0} parameter(0)",
        '  %dot.1 = f32[8]{0} dot(%p, %p), metadata={op_name='
        '"jit(step_fn)/transpose(jvp(layer3))/mlp/dot_general"}',
        '  %mul.2 = f32[8]{0} multiply(%dot.1, %p), metadata={op_name='
        '"jit(step_fn)/optimizer/mul"}',
        '  ROOT %add.9 = f32[8]{0} add(%mul.2, %p), metadata={op_name='
        '"jit(step_fn)/optimizer/add"}',
        "}",
        "%fused_computation.2 (p: f32[8]) -> f32[8] {",
        '  %exp.1 = f32[8]{0} exponential(%p), metadata={op_name='
        '"jit(step_fn)/jvp(layer0)/attn/exp"}',
        '  ROOT %neg.1 = f32[8]{0} negate(%exp.1), metadata={op_name='
        '"jit(step_fn)/jvp(layer0)/mlp/neg"}',
        "}",
        "ENTRY %main {",
        # XLA fuses a weight's Adam update into the matmul that makes its
        # gradient and the fusion keeps the matmul's name: the vote of what
        # it fused places it.  A tie goes to the fusion's own name.
        '  %fusion.20 = f32[8]{0} fusion(%a), kind=kOutput, calls='
        '%fused_computation.1, metadata={op_name='
        '"jit(step_fn)/transpose(jvp(layer3))/mlp/dot_general"}',
        '  %fusion.21 = f32[8]{0} fusion(%a), kind=kLoop, calls='
        '%fused_computation.2, metadata={op_name='
        '"jit(step_fn)/jvp(layer0)/mlp/neg"}',
        '  %fusion.1 = f32[8]{0} fusion(%a), kind=kLoop, calls=%f, metadata='
        '{op_name="jit(step_fn)/jvp(layer11)/attn/bhqd,bhkd->bhqk/dot"}',
        '  %flash_bwd_dq.3 = bf16[8]{0} custom-call(%a), metadata={op_name='
        '"jit(f)/shard_map/transpose(jvp(layer0))/attn/flash_bwd_dq/pallas"}',
        '  %fusion.2 = f32[8]{0} fusion(%a), metadata={op_name='
        '"jit(step_fn)/transpose(jvp(blocks))/mlp/dot_general"}',
        '  %fusion.3 = f32[8]{0} fusion(%a), metadata={op_name='
        '"jit(step_fn)/jvp(mlm_head)/jit(log_softmax)/reduce_max"}',
        '  %fusion.4 = f32[8]{0} fusion(%a), metadata={op_name='
        '"jit(step_fn)/transpose(jvp(logits))/dot_general"}',
        '  %all-gather.5 = f32[8]{0} all-gather(%a), metadata={op_name='
        '"jit(f)/shard_map/jvp(param_gather)/all_gather"}',
        '  %reduce-scatter.6 = f32[1]{0} reduce-scatter(%a), metadata={'
        'op_name="jit(f)/shard_map/transpose(jvp(param_gather))/rs"}',
        '  %fusion.7 = f32[8]{0} fusion(%a), metadata={op_name='
        '"jit(f)/shard_map/grad_sync/div"}',
        '  %fusion.8 = f32[8]{0} fusion(%a), metadata={op_name='
        '"jit(step_fn)/jvp(ln_f)/mul"}',
        '  %copy-start.1 = (f32[8]{0}, f32[8]{0}) copy-start(%a)',
        '  %is-finite.1 = pred[] is-finite(%a), metadata={op_name='
        '"jit(step_fn)/is_finite"}',
        '  ROOT %tuple.1 = (f32[8]{0}) tuple(%fusion.1)',
        "}"])
    assert profile.mixed_fusions(text) == {
        "fusion.20": {"mlp": 1, "optimizer": 2},
        "fusion.21": {"attn": 1, "mlp": 1}}
    assert profile.scope_table(text) == {
        "p": (UNATTRIBUTED, UNATTRIBUTED),
        "dot.1": ("mlp", "backward"), "mul.2": ("optimizer", "update"),
        "add.9": ("optimizer", "update"),
        "exp.1": ("attn", "forward"), "neg.1": ("mlp", "forward"),
        "fusion.20": ("optimizer", "update"),
        "fusion.21": ("mlp", "forward"),
        "fusion.1": ("attn", "forward"),
        "flash_bwd_dq.3": ("attn", "backward"),
        "fusion.2": ("mlp", "backward"),
        "fusion.3": ("head", "forward"),
        "fusion.4": ("head", "backward"),
        "all-gather.5": ("param_gather", "update"),
        "reduce-scatter.6": ("param_gather", "update"),
        "fusion.7": ("grad_sync", "update"),
        "fusion.8": ("ln_f", "forward"),
        "copy-start.1": (UNATTRIBUTED, UNATTRIBUTED),
        "is-finite.1": (UNATTRIBUTED, UNATTRIBUTED),
        "tuple.1": (UNATTRIBUTED, UNATTRIBUTED)}
    assert scope_path("jit(f)/shard_map/jvp(layer0)/attn/dot") == \
        "layer0/attn/dot"


def test_device_time_by_scope_sums_to_the_events_total():
    table = {"fusion.1": ("attn", "forward"),
             "fusion.2": ("attn", "backward"),
             "fusion.3": ("optimizer", "update"),
             "copy-start.1": (UNATTRIBUTED, UNATTRIBUTED)}
    events = [("fusion.1", 0.0, 1.0), ("fusion.2", 1.0, 3.0),
              ("fusion.1", 3.0, 4.0), ("fusion.3", 4.0, 4.5),
              ("copy-start.1", 4.5, 4.75), ("not-in-the-table.7", 5.0, 5.25)]
    out = profile.device_time_by_scope(events, table)
    assert out["scope"] == {"attn": 4.0, "optimizer": 0.5, UNATTRIBUTED: 0.5}
    assert out["phase"] == {"forward": 2.0, "backward": 2.0, "update": 0.5,
                            UNATTRIBUTED: 0.5}
    total = sum(end - start for _, start, end in events)
    assert sum(out["scope"].values()) == total == sum(out["phase"].values())
    assert profile.device_time_by_scope([], table) == {"scope": {},
                                                       "phase": {}}


def test_goodput_sees_a_compile_inside_a_step_loop_that_is_still_open():
    """The compile span now holds the compile, so a ledger persisted from
    inside the loop (an elastic drain) must not bill it as step time too."""
    from autodist_tpu.observability import goodput
    with observability.span("step-loop"):
        assert ("step-loop", pytest.approx(tracing.open_spans()[0][1])) \
            == tracing.open_spans()[0]
        with observability.span("compile"):
            time.sleep(0.02)
        with observability.span("emergency-save"):
            time.sleep(0.01)
        inside = goodput._contained_in_loop_ms(tracing.events(),
                                               tracing.open_spans())
        # The drain's own save is made after the last flush: never billed.
        assert set(inside) == {"compile"} and inside["compile"] >= 20.0
        assert goodput._contained_in_loop_ms(tracing.events()) == {}
    assert tracing.open_spans() == []
    closed = goodput._contained_in_loop_ms(tracing.events(),
                                           tracing.open_spans())
    assert set(closed) == {"compile", "emergency-save"}
    assert closed["compile"] == pytest.approx(inside["compile"])


@pytest.mark.parametrize("instant_between", [False, True])
def test_only_the_outermost_jax_span_is_kept(instant_between, monkeypatch):
    """JAX reports the inner traces first; the outer one drops them from
    the ring's tail, also where an event recorded at trace time (the
    ``grad_sync`` or ``flash`` event) stands among them."""
    monkeypatch.setattr(tracing, "_telemetry_on", lambda: True)
    now = [0.0]                                 # the clock, in us
    monkeypatch.setattr(tracing, "_now_us", lambda: now[0])
    event = next(k for k, v in tracing._JAX_DURATION_SPANS.items()
                 if v == "jax-trace")
    now[0] = 10.0
    tracing._on_jax_duration(event, 5e-6, fun_name="inner_a")
    if instant_between:
        now[0] = 15.0
        tracing.record_instant("grad_sync")
    now[0] = 30.0
    tracing._on_jax_duration(event, 5e-6, fun_name="inner_b")
    now[0] = 100.0
    tracing._on_jax_duration(event, 99e-6, fun_name="outer")
    kept = [e for e in tracing.events() if e["name"] == "jax-trace"]
    assert [e["args"]["fun_name"] for e in kept] == ["outer"]
    assert len([e for e in tracing.events()
                if e["name"] == "grad_sync"]) == int(instant_between)
