"""The harness on the CPU at a toy size: the loop, the arithmetic, the
``correct`` rule and the last line's keys.  No assertion on wall-clock."""
import json
import math
import os
import pathlib
import subprocess
import sys
import time

import pytest

from chipbench import measure, trace_reduce
from chipbench import run as chipbench_run
from chipbench.catalog import Catalog
from chipbench.drivers import train

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"

END_TO_END = {"tokens_per_s": "tokens/s", "step_ms_p90": "ms", "setup_s": "s"}


def _run(root, cell_name, trace, seconds=0.5):
    catalog = Catalog(str(root))
    return chipbench_run.run_cell(
        catalog, catalog.cell(cell_name), seed=3, seconds=seconds,
        trace=trace, clock0=(time.perf_counter(), measure.process_age_s()))


@pytest.fixture
def recorded_trace(monkeypatch):
    """The CPU has no device plane, so the traced path reads the trace
    recorded on the chip in place of the one it has just taken."""
    recorded = trace_reduce.load(DATA / "gpt2-medium.train-s1024.xplane.pb.gz")
    monkeypatch.setattr(trace_reduce, "load", lambda path: recorded)


@pytest.mark.parametrize("cell_name", ["tiny-lm.train-s32",
                                       "tiny-mlm.mlm-s32"])
def test_untraced_run_prints_the_end_to_end_metrics(toy_root, cell_name):
    line = _run(toy_root, cell_name, trace=False)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert set(line["checks"]) == {"reference", "losses", "clock"}
    assert all(said.startswith("ok: ") for said in line["checks"].values())
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert {k: v["unit"] for k, v in line["metrics"].items()} == END_TO_END
    assert all(math.isfinite(v["value"]) and v["value"] > 0
               for v in line["metrics"].values())
    # The CPU's allocator reports nothing; the figure is the compiler's
    # for the step that ran.
    assert line["device"].pop("memory_peak_bytes") > 0
    assert line["device"] == {"platform": "cpu", "kind": "cpu", "count": 8}
    json.dumps(line)


def test_traced_run_prints_the_per_layer_metrics(toy_root, recorded_trace):
    line = _run(toy_root, "tiny-lm.train-s32", trace=True)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "breakdown", "checks"]
    assert not set(line["metrics"]) & set(END_TO_END)
    # The toy cell is not among the cells the collective metrics list, and
    # the CPU reports no memory: a reader with nothing to read is left out.
    assert set(line["metrics"]) == {
        "capture_s", "compile_s", "data_wait_share", "device_idle_share",
        "mfu", "attn_kernel_share", "attn_kernel_roofline"}
    assert line["device"]["busy_s"] > 0
    assert line["device"]["window_s"] >= line["device"]["busy_s"]
    assert 1 <= len(line["breakdown"]["device_ops"]) <= 10
    assert 1 <= len(line["breakdown"]["idle_gaps"]) <= 10
    json.dumps(line)


def test_a_traced_run_without_a_device_plane_is_refused(toy_root):
    with pytest.raises(ValueError, match="no device plane"):
        _run(toy_root, "tiny-lm.train-s32", trace=True)


def test_the_command_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "gpt2-medium.train-s1024", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    assert proc.returncode != 0
    assert "no TPU was found" in proc.stderr
    assert "JAX found" in proc.stdout and "(cpu)" in proc.stdout
    assert '"correct"' not in proc.stdout
    # Before anything is compiled: not even the cache is set up.
    assert "compile cache" not in proc.stdout


def test_an_unknown_workload_is_named():
    with pytest.raises(KeyError, match="no workload 'nope'"):
        Catalog(str(ROOT)).cell("nope")


class _Loss:
    def __init__(self, log, i):
        self._log, self._i = log, i

    def block_until_ready(self):
        self._log.append(("block", self._i))


def test_step_loop_keeps_the_host_two_steps_ahead():
    import contextlib
    log, count = [], iter(range(100))

    def step(batch):
        log.append(("dispatch", batch))
        return _Loss(log, batch)

    loop = train.StepLoop(step, count, lag=2,
                          annotate=lambda name: contextlib.nullcontext())
    loop.run_until(lambda: loop.dispatched >= 5)
    # Step i is waited for right after step i + 2 is dispatched; the
    # queue drains at the end.
    assert log == [("dispatch", 0), ("dispatch", 1), ("dispatch", 2),
                   ("block", 0), ("dispatch", 3), ("block", 1),
                   ("dispatch", 4), ("block", 2), ("block", 3),
                   ("block", 4)]
    assert loop.dispatched == 5 and len(loop.done_at) == 5
    assert loop.done_at == sorted(loop.done_at)
    loop.run_until(lambda: loop.dispatched >= 6)
    assert log[-2:] == [("dispatch", 5), ("block", 5)]


def test_window_summary_counts_completed_steps_over_the_window():
    # Completions at 1.0 (warm-up's last), then every 0.25 s, one late.
    done_at = [0.5, 1.0, 1.25, 1.5, 1.75, 2.25]
    window = train.window_summary(done_at, first=2, tokens_per_step=1000)
    assert window["window_s"] == 1.25
    assert list(window["step_ms"]) == [250.0, 250.0, 250.0, 500.0]
    assert window["tokens_per_s"] == 4 * 1000 / 1.25


def test_percentile_interpolates():
    assert measure.percentile([10, 20, 30, 40, 50], 90) == 46.0
    assert measure.percentile(list(range(1, 102)), 90) == 91.0
    assert measure.percentile([7.0], 90) == 7.0


@pytest.mark.parametrize("losses, ok", [
    ([10.8 - 0.001 * i for i in range(120)], True),
    ([10.8] * 120, True),
    # Within what noise may do.
    ([10.8] * 60 + [10.84] * 60, True),
    ([10.8] * 60 + [10.9] * 60, False),
    ([10.8] * 60 + [float("nan")] + [10.8] * 60, False),
    ([10.8] * 60 + [float("inf")], False),
    ([], False),
])
def test_the_rule_on_losses(losses, ok):
    assert measure.losses_ok(losses)[0] is ok


def test_a_failed_check_gives_correct_false(toy_root, monkeypatch):
    """A loss that rises inside the window: the line says so."""
    monkeypatch.setattr(measure, "losses_ok",
                        lambda losses: (False, "forced by the test"))
    line = _run(toy_root, "tiny-mlm.mlm-s32", trace=False)
    assert line["correct"] is False
    assert line["metrics"]["tokens_per_s"]["value"] > 0


def test_process_age_counts_from_the_process_start():
    age = measure.process_age_s()
    assert 0 < age < 24 * 3600
    assert measure.process_age_s() >= age


def test_spans_add_up_by_name():
    spans = measure.Spans()
    with spans.span("capture"):
        pass
    with spans.span("capture"):
        pass
    assert spans.seconds("capture") >= 0
    assert len(spans.records) == 2
    assert spans.seconds("compile") is None
