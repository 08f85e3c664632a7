"""The readers of the program's own spans, counters and scope table: on
hand-built spans whose answers are known, on a pair recorded on the chip (a
slice of a ``bert-base.mlm-s128`` trace with the host plane, and the scope
table of the step that ran), through the harness at a toy size, and against
a program that has none of it."""
import gzip
import json
import pathlib
import time
import types

import pytest

from autodist_tpu.observability import profile, tracing
from chipbench import measure, program_probe as probe, trace_reduce
from chipbench import run as chipbench_run
from chipbench.catalog import Catalog

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
TRACE = DATA / "bert-base.mlm-s128.pr23.xplane.pb.gz"
TABLE = DATA / "bert-base.mlm-s128.pr23.scope_table.json.gz"

SETUP = ("trace_lower_s", "xla_compile_s", "report_s", "create_state_s")
SHARES = {"optimizer_share": ("scope", "optimizer"),
          "head_share": ("scope", "head"),
          "attn_scope_share": ("scope", "attn"),
          "mlp_scope_share": ("scope", "mlp"),
          "backward_share": ("phase", "backward"),
          "scope_unattributed_share": ("scope", probe.UNATTRIBUTED)}
NEW = SETUP + ("compiles_in_window",) + tuple(SHARES)


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(TABLE, "rt") as f:
        table = {name: tuple(v) for name, v in json.load(f).items()}
    return probe.load(TRACE), table


@pytest.fixture(autouse=True)
def _fresh_caches():
    yield
    probe._setup_split.cache_clear()
    probe._by_scope.cache_clear()


def _readers():
    return {m.NAME: m for m in Catalog(str(ROOT)).layer_metrics()}


def _run(records, window_s=10.0, trace=None):
    spans = measure.Spans()
    spans.records = list(records)
    return {"spans": spans, "window_s": window_s, "trace": trace}


# -- the recorded pair -------------------------------------------------------


def test_the_recorded_pair_is_small_and_whole(recorded):
    trace, table = recorded
    assert TRACE.stat().st_size + TABLE.stat().st_size < 400 * 1024
    assert set(trace["chips"]) == {0}
    chip = trace["chips"][0]
    assert len(chip["modules"]) == 3 and len(chip["ops"]) > 1000
    # Every executed instruction is in the table of the step that ran.
    assert {name for name, _, _ in chip["ops"]} <= set(table)
    assert {"optimizer", "attn", "mlp", "head"} <= {
        scope for scope, _ in table.values()}


def test_the_programs_annotations_are_on_the_benchmarks_clock(recorded):
    host = recorded[0]["host"]
    by_name = {}
    for name, lo, hi in host:
        by_name.setdefault(name, []).append((lo, hi))
    assert {"chipbench.dispatch", "chipbench.next_batch", "chipbench.block",
            "autodist.dispatch", "autodist.data_wait",
            "autodist.shard_batch"} == set(by_name)

    def lies_inside(inner, outer):
        return all(any(a <= lo and hi <= b for a, b in by_name[outer])
                   for lo, hi in by_name[inner])

    # One clock: the program's dispatch is what the benchmark's loop calls
    # under its own annotation, and the data wait is inside its pull.
    assert lies_inside("autodist.dispatch", "chipbench.dispatch")
    assert lies_inside("autodist.data_wait", "chipbench.next_batch")
    assert lies_inside("autodist.shard_batch", "chipbench.next_batch")
    # The host runs two steps ahead: the last three programs see its last
    # pull and dispatch, and then its blocks.
    assert len(by_name["autodist.dispatch"]) == 1
    assert len(by_name["chipbench.block"]) == 4


def test_join_places_the_busy_time_of_the_slice(recorded):
    trace, table = recorded
    joined = probe.join(trace, table, profile.device_time_by_scope)
    reduced = trace_reduce.reduce(trace_reduce.load(TRACE))
    assert joined["chips"] == 1
    assert joined["busy_s"] == pytest.approx(reduced["busy_s"], rel=1e-9)
    # Operations on a chip's line never overlap: the scopes' seconds are
    # the busy seconds, and so are the phases'.
    for kind in ("scope", "phase"):
        assert sum(joined[kind].values()) == pytest.approx(
            joined["busy_s"], rel=1e-6)
    assert sum(joined["unplaced"].values()) == pytest.approx(
        joined["scope"][probe.UNATTRIBUTED], rel=1e-9)
    assert set(joined["phase"]) <= {"forward", "backward", "update",
                                    probe.UNATTRIBUTED}
    assert joined["phase"]["backward"] > joined["phase"]["forward"] > 0
    # An empty table places nothing.
    nothing = probe.join(trace, {}, profile.device_time_by_scope)
    assert nothing["scope"] == {probe.UNATTRIBUTED: pytest.approx(
        joined["busy_s"])}


def test_join_places_a_loops_body_and_not_the_loop_around_it():
    """A ``while`` event spans its body's events: the scopes and phases add
    up to the body's seconds, busy time stays the union of every event,
    and what only the loop's own event covers is kept apart."""
    ops = [("fusion.1", 0.0, 2.0), ("while.5", 2.0, 9.0),
           ("gmm.3", 2.0, 4.0), ("fusion.7", 4.0, 5.0),
           ("gmm.3", 5.5, 7.5), ("fusion.7", 7.5, 8.5), ("fusion.9", 9.0, 10.0)]
    trace = {"chips": {0: {"ops": ops, "modules": [("jit_step", 0.0, 10.0)]}},
             "host": []}
    table = {"fusion.1": ("attn", "forward"), "while.5": ("moe", "backward"),
             "gmm.3": ("moe/experts", "backward"),
             "fusion.7": ("moe/dispatch", "backward"),
             "fusion.9": ("optimizer", "update")}
    joined = probe.join(trace, table, profile.device_time_by_scope,
                        skip_programs=0)
    assert joined["busy_s"] == pytest.approx(10.0)
    assert dict(joined["scope"]) == {
        "attn": pytest.approx(2.0), "moe/experts": pytest.approx(4.0),
        "moe/dispatch": pytest.approx(2.0), "optimizer": pytest.approx(1.0)}
    assert dict(joined["phase"]) == {
        "forward": pytest.approx(2.0), "backward": pytest.approx(6.0),
        "update": pytest.approx(1.0)}
    assert joined["inside_containers_s"] == pytest.approx(1.0)
    assert sum(joined["scope"].values()) + joined["inside_containers_s"] \
        == pytest.approx(joined["busy_s"])
    assert not joined["gaps"] and not joined["unplaced"]


@pytest.mark.parametrize("name", sorted(SHARES))
def test_share_readers_on_the_recorded_pair(name, recorded, monkeypatch,
                                            tmp_path):
    from autodist_tpu import autodist
    trace, table = recorded
    copy = tmp_path / TRACE.name
    copy.write_bytes(TRACE.read_bytes())
    calls = []
    monkeypatch.setattr(probe, "trace_path", lambda: str(copy))
    monkeypatch.setattr(autodist, "_default_autodist", types.SimpleNamespace(
        runner=types.SimpleNamespace(
            step_text=lambda: calls.append(1) or "the step's text")))
    monkeypatch.setattr(profile, "scope_table", lambda text: table)
    reader = _readers()[name]
    run = _run([], trace={"busy_s": 1.0})
    value = reader.read(run)
    kind, key = SHARES[name]
    joined = probe.join(trace, table, profile.device_time_by_scope)
    assert value == pytest.approx(
        100.0 * joined[kind].get(key, 0.0) / joined["busy_s"])
    assert 0.0 <= value <= 100.0
    # Loaded and joined once a process; the table is left beside the trace.
    assert reader.read(run) == value and calls == [1]
    with gzip.open(tmp_path / probe.TABLE_FILE, "rt") as f:
        assert {k: tuple(v) for k, v in json.load(f).items()} == table
    # An untraced run reads nothing.
    assert reader.read(_run([])) is None


def test_the_recorded_shares_add_up(recorded):
    trace, table = recorded
    joined = probe.join(trace, table, profile.device_time_by_scope)
    share = {s: 100.0 * v / joined["busy_s"]
             for s, v in joined["scope"].items()}
    assert sum(share.values()) == pytest.approx(100.0, abs=1e-3)
    # The encoder at 128 positions: attention's scope (kernels, projections,
    # copies and casts) is the largest, the head on a sixth of the positions
    # the smallest of the four named; the update is Adam on 109 M values.
    assert share["attn"] > share["mlp"] > share["optimizer"] > share["head"]
    assert share["attn"] > 40.0 and 5.0 < share["optimizer"] < 25.0
    assert share[probe.UNATTRIBUTED] < 5.0


# -- the spans, hand-built ---------------------------------------------------


def _program_spans():
    """Two sessions: an 8-layer check (capture at 0) and the cell's own
    (capture at 100).  The cell's compile span runs 120-150 with a trace
    (121-126, an inner one inside it), a lowering (126-130), a cache read
    (130-148) and, before it, a report (118-119.5) and a build (117-118)."""
    return [
        ("capture", 0.0, 2.0, {}), ("create-state", 2.0, 3.0, {}),
        ("report", 3.0, 3.5, {}), ("compile", 4.0, 20.0, {}),
        ("xla-compile", 10.0, 19.0, {"fun_name": "jit(local_step)"}),
        ("capture", 100.0, 104.0, {}),
        ("create-state", 104.0, 111.0, {}), ("init", 104.0, 106.0, {}),
        ("xla-compile", 104.5, 105.5, {"fun_name": "jit(init_fn)"}),
        ("host-copy", 106.0, 111.0, {}),
        ("build-step", 117.0, 118.0, {}), ("report", 118.0, 119.5, {}),
        ("compile", 120.0, 150.0, {"path": "explicit"}),
        ("jax-trace", 121.0, 126.0, {"fun_name": "local_step"}),
        ("jax-trace", 122.0, 123.0, {"fun_name": "add"}),
        ("jax-lower", 126.0, 130.0, {"fun_name": "jit(local_step)"}),
        ("xla-compile", 130.0, 148.0, {"fun_name": "jit(local_step)"}),
        # After the window opened at 160:
        ("xla-compile", 165.0, 166.0, {"fun_name": "jit(late)"}),
        ("xla-compile", 171.0, 172.0, {"fun_name": "jit(after)"})]


def test_setup_split_and_compiles_on_hand_built_spans(monkeypatch, capsys):
    monkeypatch.setattr(probe, "program_spans", _program_spans)
    run = _run([("compile", 116.0, 151.0), ("warmup", 151.0, 160.0)])
    assert probe.window(run) == (160.0, 170.0)
    assert probe.outermost(_program_spans()[13:15]) == [_program_spans()[13]]
    split = probe.setup_split(run)
    assert split == {"compile_span_s": 30.0, "trace_lower_s": 9.0,
                     "xla_compile_s": 18.0, "report_s": 1.5,
                     "create_state_s": 7.0}
    assert '"host-copy": 5.0' in capsys.readouterr().out
    readers = _readers()
    assert [readers[n].read(run) for n in SETUP] == [9.0, 18.0, 1.5, 7.0]
    # One compile started inside the window (160-170), one after it.
    assert readers["compiles_in_window"].read(run) == 1
    assert "jit(late)" in capsys.readouterr().out
    assert probe.compiles_in_window(_run([("warmup", 0.0, 200.0)])) == 0
    # Without the benchmark's warmup span there is no window.
    assert probe.setup_split(_run([])) is None
    assert probe.compiles_in_window(_run([])) is None


# -- through the harness, at a toy size --------------------------------------


def test_span_readers_through_the_harness(toy_root, monkeypatch):
    recorded = trace_reduce.load(DATA / "gpt2-medium.train-s1024.xplane.pb.gz")
    monkeypatch.setattr(trace_reduce, "load", lambda path: recorded)
    # The CPU stands in for the machine with the chip: nothing read here is
    # a measurement.
    monkeypatch.setattr(probe, "on_chip", lambda: True)
    catalog = Catalog(str(toy_root))
    line = chipbench_run.run_cell(
        catalog, catalog.cell("tiny-lm.train-s32"), seed=3000000007,
        seconds=0.3, trace=True,
        clock0=(time.perf_counter(), measure.process_age_s()))
    got = {name: m["value"] for name, m in line["metrics"].items()}
    assert set(SETUP) | {"compiles_in_window", "compile_s"} <= set(got)
    assert got["compiles_in_window"] == 0
    assert all(got[name] > 0 for name in SETUP)
    # The benchmark's own span holds the program's and one step.
    assert got["trace_lower_s"] + got["xla_compile_s"] + got["report_s"] \
        <= got["compile_s"]
    # No device plane in a CPU trace, and none is looked for under the
    # toy root: the shares have nothing to read.
    assert not set(SHARES) & set(got)


# -- a program from before the spans -----------------------------------------


@pytest.mark.parametrize("name", NEW)
def test_readers_report_nothing_for_a_program_without_the_spans(
        name, monkeypatch):
    monkeypatch.setattr(probe, "on_chip", lambda: True)
    monkeypatch.delattr(tracing, "to_perf_counter")
    monkeypatch.delattr(profile, "device_time_by_scope")
    monkeypatch.setattr(probe, "trace_path", lambda: str(TRACE))
    run = _run([("warmup", 0.0, 1.0)], trace={"busy_s": 1.0})
    assert _readers()[name].read(run) is None


def test_every_new_metric_is_declared_with_its_source():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    declared = {m["name"]: m for m in bench["per_layer"]}
    source = dict.fromkeys(SETUP, "program_span")
    source["compiles_in_window"] = "program_counter"
    source.update(dict.fromkeys(SHARES, "device_trace"))
    # Later PRs append: PR 23's stand together, in their order, wherever.
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(NEW[0])
    assert names[first:first + len(NEW)] == list(NEW)
    for name in NEW:
        assert declared[name]["source"] == source[name]
        assert declared[name]["better"] == "lower"
        assert "workloads" not in declared[name]
