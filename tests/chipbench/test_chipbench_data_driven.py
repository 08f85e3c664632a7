"""A later PR adds a configuration, a traffic mix, a cell or a per-layer
metric by adding files and entries, and edits no file that is there; and
``BENCHMARK.json`` keeps to its contract."""
import hashlib
import json
import pathlib
import re
import time

import pytest

from chipbench import measure, trace_reduce
from chipbench import run as chipbench_run
from chipbench.catalog import Catalog

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
FILE = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

NEW_METRIC = '''"""Added by a later PR: steps completed in the window."""
NAME, UNIT = "steps_in_window", "steps"
LAYER, MOVES = "Step on device", "tokens_per_s"


def read(run):
    return run["steps"]
'''


def _digests(root):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_files_and_entries_run_with_no_edit(toy_root, monkeypatch):
    """``toy_root`` has added two configurations, two traffic mixes and two
    cells as files and entries; add a per-layer metric the same way."""
    recorded = trace_reduce.load(DATA / "gpt2-medium.train-s1024.xplane.pb.gz")
    monkeypatch.setattr(trace_reduce, "load", lambda path: recorded)
    (toy_root / "chipbench" / "layer_metrics" / "steps_in_window.py"
     ).write_text(NEW_METRIC)
    path = toy_root / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    bench["per_layer"].append({
        "name": "steps_in_window", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "Step on device",
        "moves": "tokens_per_s", "workloads": ["tiny-lm.train-s32"]})
    path.write_text(json.dumps(bench))

    before = _digests(ROOT / "chipbench")
    after = _digests(toy_root / "chipbench")
    changed = {f for f in before if after[f] != before[f]}
    assert changed == {"peaks.json"}     # the fixture's row for the CPU
    assert set(after) - set(before) == {
        "layer_metrics/steps_in_window.py", "configs/tiny-lm.json",
        "configs/tiny-mlm.json", "traffic/tiny-lm-s32.json",
        "traffic/tiny-mlm-s32.json"}

    catalog = Catalog(str(toy_root))
    assert "steps_in_window" in [m.NAME for m in catalog.layer_metrics()]
    for cell_name, expected in (("tiny-lm.train-s32", True),
                                ("tiny-mlm.mlm-s32", False)):
        line = chipbench_run.run_cell(
            catalog, catalog.cell(cell_name), seed=1, seconds=0.3,
            trace=True, clock0=(time.perf_counter(),
                                measure.process_age_s()))
        assert line["correct"] is True
        assert ("steps_in_window" in line["metrics"]) is expected
    assert line["metrics"].keys() >= {"capture_s", "mfu"}


@pytest.fixture(scope="module")
def bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_benchmark_json_has_exactly_the_contracts_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["chipbench", "tests/chipbench"]
    assert len(bench["command"]) <= 32
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    for entry in bench["configs"]:
        assert set(entry) == {"name", "source", "file", "reduced", "why"}
    for entry in bench["workloads"]:
        assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    for entry in bench["end_to_end"]:
        assert set(entry) == {"name", "unit", "better", "bound", "source"}
    for entry in bench["per_layer"]:
        assert set(entry) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves"}


def test_names_units_and_lines_use_the_allowed_characters(bench):
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = ([m["name"] for m in metrics]
             + [c["name"] for c in bench["configs"]]
             + [w["name"] for w in bench["workloads"]])
    assert len(names) == len(set(names))
    for name in names + [w["traffic"] for w in bench["workloads"]] + [
            key for c in bench["configs"] for key in c["reduced"]]:
        assert NAME.match(name), name
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    lines = ([w["why"] for w in bench["workloads"] + bench["configs"]]
             + [c["source"] for c in bench["configs"]]
             + [m["layer"] for m in bench["per_layer"]] + bench["command"])
    for line in lines:
        assert 1 <= len(line) <= 200 and "\n" not in line \
            and "\t" not in line, line
    for c in bench["configs"]:
        assert len(c["reduced"]) <= 16
        # No width is ever cut.
        for key in c["reduced"]:
            assert not re.search(r"(_dim|_rank|hidden_size|intermediate|"
                                 r"n_embd|n_head|head|n_inner)", key), key


def test_every_cell_names_files_that_exist(bench):
    catalog = Catalog(str(ROOT))
    configs = {c["name"]: c for c in bench["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert 2 <= len(pairs) <= 24
    assert {w["config"] for w in bench["workloads"]} == set(configs)
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in bench["workloads"])
    assert len(four) <= max(1, len(pairs) // 4)
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    for w in bench["workloads"]:
        cell = catalog.cell(w["name"])
        config = configs[w["config"]]
        assert FILE.match(config["file"])
        assert config["file"].startswith("chipbench/")
        sizes = cell["sizes"]
        assert sizes["source"] == config["source"]
        assert sizes["reduced"] == config["reduced"]
        assert sizes["deployment"]["chips"] == w["chips"]
        assert sizes["departures"] and sizes["assumed"]
        assert sizes["check"]["why"] and 0 < sizes["check"]["rtol"] <= 0.0002
        kind = catalog.module("kinds", sizes["kind"])
        catalog.module("drivers", cell["mix"]["driver"])
        assert kind.flops_per_token(sizes, cell["mix"]) > 0
    for path in (ROOT / "chipbench").rglob("*"):
        if "__pycache__" not in path.parts:
            assert FILE.match(str(path.relative_to(ROOT))), path


def test_every_per_layer_metric_has_its_reader_and_its_arrow(bench):
    catalog = Catalog(str(ROOT))
    readers = {m.NAME: m for m in catalog.layer_metrics()}
    # A reader may wait for the cell that gives it something to read.
    assert set(readers) >= {m["name"] for m in bench["per_layer"]}
    cells = [w["name"] for w in bench["workloads"]]
    for entry in bench["per_layer"]:
        reader = readers[entry["name"]]
        assert (reader.UNIT, reader.LAYER, reader.MOVES) == (
            entry["unit"], entry["layer"], entry["moves"])
        for cell in entry.get("workloads", cells):
            assert cell in cells
            assert reader.MOVES in [
                m["name"] for m in catalog.metric_specs("end_to_end", cell)]
    for cell in cells:
        assert "setup_s" in [m["name"] for m in
                             catalog.metric_specs("end_to_end", cell)]
        assert len(catalog.metric_specs("end_to_end", cell)) >= 2
        assert catalog.metric_specs("per_layer", cell)


def test_the_toy_cells_are_not_in_the_benchmark(bench):
    assert not [w for w in bench["workloads"] + bench["configs"]
                if w["name"].startswith("tiny")]
