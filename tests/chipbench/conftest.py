"""Fixtures of the benchmark's own tests.

The tests drive the benchmark's functions on the CPU at a toy size, through
a copy of the benchmark's data directories to which toy configurations are
added as files, the way a later PR adds real ones.  Nothing they produce is
a measurement.
"""
import json
import pathlib
import shutil

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
TOY_CELLS = (("tiny-lm.train-s32", "tiny-lm", "tiny-lm-s32"),
             ("tiny-mlm.mlm-s32", "tiny-mlm", "tiny-mlm-s32"))


def add_cell(root, name, config, traffic, chips):
    """Add one configuration file, one traffic file and one cell entry to
    the benchmark at ``root``; edits no file but ``BENCHMARK.json``."""
    shutil.copy(DATA / f"{config}.json", root / "chipbench" / "configs")
    shutil.copy(DATA / f"{traffic}.json", root / "chipbench" / "traffic")
    path = root / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    bench["configs"].append({
        "name": config, "source": "none", "reduced": [], "why": "toy",
        "file": f"chipbench/configs/{config}.json"})
    bench["workloads"].append({"name": name, "config": config,
                               "traffic": traffic, "chips": chips,
                               "why": "toy"})
    path.write_text(json.dumps(bench))


@pytest.fixture
def toy_root(tmp_path):
    """A copy of the benchmark with the toy cells added and a row for the
    CPU in the copy's table of peaks (a nominal figure: the clock check
    needs a number, and no test reads a utilization)."""
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for name, config, traffic in TOY_CELLS:
        add_cell(tmp_path, name, config, traffic, chips=8)
    peaks_path = tmp_path / "chipbench" / "peaks.json"
    peaks = json.loads(peaks_path.read_text())
    peaks["cpu"] = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
                    "hbm_bytes": 1e10}
    peaks_path.write_text(json.dumps(peaks))
    return tmp_path
