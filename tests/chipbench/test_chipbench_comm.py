"""The six readers of the Collectives layer that take the step's
communication from the program (``chipbench/comm_probe.py``): declared by
name, reported where a step communicates, on hand-built events whose answers
are known, through the harness at a toy size, and against a run without a
trace or a program without ``comm_table``."""
import gzip
import json
import pathlib
import time
import types

import pytest

from autodist_tpu.observability import profile
from chipbench import comm_probe, measure, program_probe, trace_reduce
from chipbench import run as chipbench_run
from chipbench.catalog import Catalog

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELL = "gpt2-xl.train-s1024-x4"
TOY_CELL = "tiny-lm.train-s32"
# name -> (unit, better, source)
NEW = {"grad_sync_share": ("%", "lower", "device_trace"),
       "param_gather_share": ("%", "lower", "device_trace"),
       "comm_share": ("%", "lower", "device_trace"),
       "comm_exposed_share": ("%", "lower", "device_trace"),
       "comm_wire_gb_per_step": ("GB", "lower", "program_counter"),
       "grad_sync_gbytes_per_s": ("GB/s", "higher", "device_trace")}
OLD = ("collective_share", "collective_exposed_share")
# The cell's compiled step cut to seven stretches, with chip 0's events of
# them in one traced step (my chip run, PR 33; tests/test_comm_table.py).
RECORDED = str(ROOT / "tests" / "chipbench" / "data"
               / "gpt2-xl.train-s1024-x4.pr33")


@pytest.fixture(autouse=True)
def _fresh_caches():
    yield
    comm_probe._measured.cache_clear()
    program_probe._by_scope.cache_clear()


def _readers(root=ROOT):
    return {m.NAME: m for m in Catalog(str(root)).layer_metrics()}


# -- the entries, by name ------------------------------------------------------


def test_the_six_entries_are_declared_with_their_readers():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    declared = {m["name"]: m for m in bench["per_layer"]}
    readers = _readers()
    assert set(NEW) | set(OLD) <= set(declared) and set(NEW) <= set(readers)
    for name, (unit, better, source) in NEW.items():
        assert declared[name] == {
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": "Collectives", "moves": "tokens_per_s",
            "workloads": [CELL]}
        reader = readers[name]
        assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES) == (
            name, unit, "Collectives", "tokens_per_s")
    # The two older metrics of the layer stay as they were.
    for name in OLD:
        assert declared[name]["layer"] == "Collectives"
        assert declared[name]["workloads"] == [CELL]


def test_the_readers_report_in_the_four_chip_cell_only():
    catalog = Catalog(str(ROOT))
    for cell in catalog.benchmark["workloads"]:
        wanted = {m["name"] for m in
                  catalog.metric_specs("per_layer", cell["name"])}
        if cell["name"] == CELL:
            assert cell["chips"] == 4 and set(NEW) | set(OLD) <= wanted
        else:
            assert cell["chips"] == 1 and not (set(NEW) | set(OLD)) & wanted


# -- nothing to read -----------------------------------------------------------


def _stand_in_runner(monkeypatch, text="the step's text"):
    from autodist_tpu import autodist
    monkeypatch.setattr(autodist, "_default_autodist", types.SimpleNamespace(
        runner=types.SimpleNamespace(step_text=lambda: text)))


@pytest.mark.parametrize("name", sorted(NEW))
def test_readers_read_none_without_a_trace_or_a_comm_table(
        name, monkeypatch, tmp_path):
    trace_file = tmp_path / "t.xplane.pb"
    trace_file.write_bytes(b"")
    monkeypatch.setattr(program_probe, "trace_path", lambda: str(trace_file))
    monkeypatch.setattr(trace_reduce, "load", lambda path: pytest.fail(
        "a reader with nothing to read loaded the trace"))
    _stand_in_runner(monkeypatch)
    reader = _readers()[name]
    # An untraced run.
    assert reader.read({"trace": None}) is None
    # A step that holds no communication instruction (one chip).
    monkeypatch.setattr(profile, "comm_table", lambda text: {})
    assert reader.read({"trace": {"busy_s": 1.0}}) is None
    # A program from before the table.
    comm_probe._measured.cache_clear()
    monkeypatch.delattr(profile, "comm_table")
    assert reader.read({"trace": {"busy_s": 1.0}}) is None
    # A traced run that left no trace behind.
    monkeypatch.setattr(program_probe, "trace_path", lambda: None)
    assert reader.read({"trace": {"busy_s": 1.0}}) is None


# -- hand-built events ---------------------------------------------------------


def _row(kind, scope, nbytes=0, pair=False, group=4):
    return {"kind": kind, "bytes": nbytes, "group": group, "async": pair,
            "scope": scope}


def test_reduce_takes_the_slice_and_the_mean_over_the_chips():
    table = {"fusion.9": _row("reduce-scatter", "grad_sync", 4000),
             "ag-start.1": _row("all-gather", "param_gather", 8000,
                                "ag-start.1"),
             "ag-done.1": _row("all-gather", "param_gather", 0, "ag-start.1")}
    # Three programs of 10 s each; the slice is the third (20-30).  The
    # names are a trace's: the instruction's whole text.
    ops = [("%fusion.1 = f32[8]{0} fusion(%p)", 20.0, 22.0),
           ("%ag-start.1 = (f32[2], f32[8]) all-gather-start(%p)", 22.0, 22.5),
           ("%fusion.2 = f32[8]{0} fusion(%p)", 22.5, 24.0),
           ("%ag-done.1 = f32[8] all-gather-done(%ag-start.1)", 25.0, 26.0),
           ("%fusion.9 = f32[2] fusion(%p), calls=%all-reduce-scatter", 26.0,
            29.0),
           # Before the slice: clipped away.
           ("%fusion.9 = f32[2] fusion(%p), calls=%all-reduce-scatter", 16.0,
            19.0)]
    modules = [("jit_step", 0.0, 10.0), ("jit_step", 10.0, 20.0),
               ("jit_step", 20.0, 30.0)]
    first = {"ops": ops, "modules": modules,
             "async": [("%ag-start.1 = (f32[2], f32[8]) all-gather-start(%p)",
                        22.0, 26.0)]}
    # The profiler writes the asynchronous line for the first chip only: the
    # second's pair is whole by its halves on the line of operations.
    second = {"ops": ops, "modules": modules, "async": []}
    out = comm_probe.reduce({"chips": {0: first, 1: second}, "host": []},
                            table, profile.comm_time)
    assert out["chips"] == 2 and out["steps"] == 1
    assert out["window_s"] == pytest.approx(10.0)
    # In flight 22-26, then the fused reduce-scatter 26-29.
    assert out["comm_s"] == pytest.approx(7.0)
    assert out["by_kind"] == {"all-gather": pytest.approx(4.0),
                              "reduce-scatter": pytest.approx(3.0)}
    assert out["by_scope"] == {"param_gather": pytest.approx(4.0),
                               "grad_sync": pytest.approx(3.0)}
    # fusion.2 hides 22.5-24 of the gather; the rest has nothing beside it.
    assert out["exposed_s"] == pytest.approx(7.0 - 1.5)
    # A chip with no operations is no chip.
    third = {"ops": [], "modules": [], "async": []}
    assert comm_probe.reduce({"chips": {0: first, 2: third}, "host": []},
                             table, profile.comm_time)["chips"] == 1


def test_reduce_on_the_recorded_four_chip_step():
    with gzip.open(RECORDED + ".step_text.txt.gz", "rt") as f:
        table = profile.comm_table(f.read())
    with gzip.open(RECORDED + ".events.json.gz", "rt") as f:
        events = json.load(f)
    step_s = events["step_ns"] * 1e-9

    def seconds(found):
        return [(name, lo * 1e-9, hi * 1e-9) for name, lo, hi in found]
    chip = {"ops": seconds(events["ops"]), "async": seconds(events["async"]),
            "modules": [("jit_local_step", 0.0, step_s)]}
    out = comm_probe.reduce({"chips": {0: chip}, "host": []}, table,
                            profile.comm_time, skip_programs=0)
    assert out["chips"] == 1 and out["steps"] == 1
    assert out["window_s"] == pytest.approx(293.248481e-3)
    # Of the stretches that were kept: the fused reduce-scatters, which
    # ``trace_reduce.COLLECTIVE`` does not match, are most of what is exposed.
    assert out["comm_s"] == pytest.approx(31.078101e-3)
    assert out["exposed_s"] == pytest.approx(24.236608e-3)
    named = trace_reduce.total(trace_reduce.union(
        (lo, hi) for _, lo, hi in trace_reduce.collective_intervals(chip)))
    fused = sum(hi - lo for name, lo, hi in chip["ops"]
                if name.startswith("fusion") and name in table)
    assert fused == pytest.approx(13.222228e-3)
    assert out["comm_s"] >= named + fused - 1e-9
    # The rate the readers divide: bytes of the rows placed in grad_sync
    # over the seconds in them.
    sent = profile.comm_wire_bytes(table, by="scope")["grad_sync"]
    assert sent == pytest.approx(0.75 * 1091993600 + 1.5 * 324268800)
    assert sent / 1e9 / out["by_scope"]["grad_sync"] == pytest.approx(
        67.958, abs=0.001)


# -- through the harness, at a toy size ----------------------------------------


@pytest.fixture
def comm_root(toy_root):
    """``toy_root`` with its eight-device toy cell named in the ``workloads``
    of every metric that lists the four-chip cell."""
    path = toy_root / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    for metric in bench["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append(TOY_CELL)
    path.write_text(json.dumps(bench))
    return toy_root


def _stand_in_trace():
    """A trace in ``trace_reduce.load``'s shape, made of the toy step's own
    communication instructions (the CPU's profiler writes no device plane):
    three programs, in each every instruction of the table for 1 ms, one
    after the other, and 1 ms of something else after every fourth."""
    from autodist_tpu.autodist import get_default_autodist
    table = get_default_autodist().runner.comm_table()
    ops, modules, at = [], [], 0.0
    for _ in range(3):
        begin = at
        for i, name in enumerate(table):
            ops.append((f"%{name} = f32[8]{{0}} fusion(%p)", at, at + 1e-3))
            at += 1e-3
            if i % 4 == 3:
                ops.append((f"%other.{i} = f32[8]{{0}} fusion(%p)", at,
                            at + 1e-3))
                at += 1e-3
        modules.append(("jit_local_step", begin, at))
    return {"chips": {0: {"ops": ops, "async": [], "modules": modules}},
            "host": []}, table


def test_the_six_readers_through_the_harness(comm_root, monkeypatch, tmp_path,
                                             capsys):
    made = {}

    def load(path):
        if not made:
            made["trace"], made["table"] = _stand_in_trace()
        return made["trace"]

    def probe_load(path):
        chip = load(path)["chips"][0]
        return {"chips": {0: {"ops": [(trace_reduce.op_name(n), a, b)
                                      for n, a, b in chip["ops"]],
                              "modules": chip["modules"]}}, "host": []}

    trace_file = tmp_path / "stand-in.xplane.pb"
    trace_file.write_bytes(b"")
    monkeypatch.setattr(trace_reduce, "load", load)
    monkeypatch.setattr(program_probe, "load", probe_load)
    monkeypatch.setattr(program_probe, "trace_path", lambda: str(trace_file))
    catalog = Catalog(str(comm_root))
    line = chipbench_run.run_cell(
        catalog, catalog.cell(TOY_CELL), seed=3000000011, seconds=0.3,
        trace=True, clock0=(time.perf_counter(), measure.process_age_s()))
    got = {name: m["value"] for name, m in line["metrics"].items()}
    units = {name: m["unit"] for name, m in line["metrics"].items()}
    assert set(NEW) | set(OLD) <= set(got)
    assert {n: units[n] for n in NEW} == {n: v[0] for n, v in NEW.items()}
    table = made["table"]
    kinds = {row["kind"] for row in table.values()}
    assert {"all-gather", "reduce-scatter", "all-reduce"} <= kinds
    assert "loss_sync" in {row["scope"] for row in table.values()}
    # Every instruction of the stand-in is synchronous: all of it exposed.
    n, others = len(table), len(table) // 4
    assert got["comm_share"] == pytest.approx(100.0 * n / (n + others))
    assert got["comm_exposed_share"] == pytest.approx(got["comm_share"])
    placed = {scope: sum(r["scope"] == scope for r in table.values())
              for scope in ("grad_sync", "param_gather")}
    assert got["param_gather_share"] == pytest.approx(
        100.0 * placed["param_gather"] / (n + others))
    assert got["grad_sync_share"] == pytest.approx(
        100.0 * placed["grad_sync"] / (n + others))
    wire = profile.comm_wire_bytes(table)
    assert got["comm_wire_gb_per_step"] == pytest.approx(
        sum(wire.values()) / 1e9)
    # The toy's variables are all partitioned: gathered and scattered whole,
    # seven eighths of each over the ring of eight.
    item = made_item()
    assert wire["all-gather"] == pytest.approx(
        7 / 8 * sum(v.size_bytes for v in item.variables))
    log = capsys.readouterr().out
    assert "comm_probe: comm_table() of the step took" in log
    assert "bytes a chip sends a step by kind" in log


def made_item():
    from autodist_tpu.autodist import get_default_autodist
    return get_default_autodist().runner.program.graph_item
