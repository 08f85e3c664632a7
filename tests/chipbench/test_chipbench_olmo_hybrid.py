"""Kind ``hybrid_causal_lm`` on the CPU at a toy size: its cell through the
harness, the scan's and the model's arithmetic by hand, the three ``gdn_*``
readers on a made-up table, the configuration against the published one.
Every entry of ``BENCHMARK.json`` is looked up by its name: no test here
says where in a list an entry stands.  Nothing here is a measurement."""
import json
import pathlib
import shutil
import time

import pytest

from chipbench import flops, flops_gdn, measure, program_probe, trace_reduce
from chipbench import run as chipbench_run
from chipbench.catalog import Catalog
from chipbench.layer_metrics import (gdn_scan_roofline, gdn_scan_share,
                                     gdn_scope_share)

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
CELL = "olmo-hybrid-7b.train-s4096"
CONFIG = "olmo-hybrid-7b"
TOY_CELL = "tiny-olmo-hybrid.train-s32"
GDN_METRICS = {"gdn_scope_share", "gdn_scan_share", "gdn_scan_roofline"}
V5E = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture
def hybrid_root(toy_root):
    """``toy_root`` with the toy hybrid cell added the same way, and named
    in the ``workloads`` of the three metrics that list their cells."""
    shutil.copy(DATA / "tiny-olmo-hybrid.json",
                toy_root / "chipbench" / "configs")
    path = toy_root / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    bench["configs"].append({
        "name": "tiny-olmo-hybrid", "source": "none", "reduced": [],
        "why": "toy", "file": "chipbench/configs/tiny-olmo-hybrid.json"})
    bench["workloads"].append({
        "name": TOY_CELL, "config": "tiny-olmo-hybrid",
        "traffic": "tiny-lm-s32", "chips": 8, "why": "toy"})
    for metric in bench["per_layer"]:
        if metric["name"] in GDN_METRICS:
            metric["workloads"].append(TOY_CELL)
    path.write_text(json.dumps(bench))
    return toy_root


def _run(root, trace):
    catalog = Catalog(str(root))
    return chipbench_run.run_cell(
        catalog, catalog.cell(TOY_CELL), seed=2147483659, seconds=0.5,
        trace=trace, clock0=(time.perf_counter(), measure.process_age_s()))


def test_the_toy_cell_runs_untraced(hybrid_root):
    line = _run(hybrid_root, trace=False)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == {"tokens_per_s", "step_ms_p90", "setup_s"}
    assert line["device"]["memory_peak_bytes"] > 0


def test_the_toy_cell_runs_traced_and_keeps_the_programs_aux(
        hybrid_root, monkeypatch):
    recorded = trace_reduce.load(DATA / "gpt2-medium.train-s1024.xplane.pb.gz")
    monkeypatch.setattr(trace_reduce, "load", lambda path: recorded)
    line = _run(hybrid_root, trace=True)
    assert line["correct"] is True
    # The CPU leaves no trace of its own to join with the program's table:
    # the three readers have nothing to read, and say so by saying nothing.
    assert not set(line["metrics"]) & GDN_METRICS
    from autodist_tpu.autodist import get_default_autodist
    runner = get_default_autodist().runner
    assert 0.0 < float(runner.last_aux["gdn.state_absmax"]) < 1e3
    assert gdn_scan_roofline.program_shapes() == {
        "layers": 3, "heads": 4, "key_width": 8, "value_width": 16}
    scopes = {scope for scope, _ in runner.scope_table().values()}
    assert {"gdn/proj", "gdn/conv", "gdn/gates", "gdn/scan", "gdn/out",
            "attn", "mlp", "head", "optimizer"} <= scopes


# -- the entries, by name ------------------------------------------------------

def _bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_the_new_readers_are_declared_for_the_new_cell_only():
    declared = {m["name"]: m for m in _bench()["per_layer"]}
    assert GDN_METRICS <= set(declared)
    for name in GDN_METRICS:
        metric = declared[name]
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "tokens_per_s"
        assert metric["source"] == "device_trace" and metric["unit"] == "%"
        assert set(metric) == {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
    assert declared["gdn_scope_share"]["layer"] == "Step on device"
    assert declared["gdn_scan_share"]["layer"] == "Step on device"
    assert declared["gdn_scan_roofline"]["layer"] == "Kernels"
    assert declared["gdn_scan_roofline"]["better"] == "higher"
    assert declared["gdn_scope_share"]["better"] == "lower"
    # No metric that lists its cells lists this one but these three.
    assert {m["name"] for m in declared.values()
            if CELL in m.get("workloads", ())} == GDN_METRICS
    catalog = Catalog(str(ROOT))
    wanted = {m["name"] for m in catalog.metric_specs("per_layer", CELL)}
    assert GDN_METRICS <= wanted and "moe_scope_share" not in wanted
    for other in ("gpt2-medium.train-s1024", "olmoe-1b-7b.train-s4096"):
        assert not GDN_METRICS & {
            m["name"] for m in catalog.metric_specs("per_layer", other)}
    for reader in (gdn_scope_share, gdn_scan_share, gdn_scan_roofline):
        assert (reader.NAME in GDN_METRICS and reader.UNIT == "%"
                and reader.MOVES == "tokens_per_s")
        assert reader.LAYER == declared[reader.NAME]["layer"]


def test_the_cell_and_its_configuration_are_declared():
    bench = _bench()
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    assert cells[CELL] == {**cells[CELL], "config": CONFIG,
                           "traffic": "lm-s4096-r1", "chips": 1}
    assert configs[CONFIG]["source"] == (
        "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json")
    assert configs[CONFIG]["file"] == "chipbench/configs/olmo-hybrid-7b.json"
    assert configs[CONFIG]["reduced"] == ["num_hidden_layers", "layer_types",
                                          "vocab_size"]
    for line in (cells[CELL]["why"], configs[CONFIG]["why"],
                 configs[CONFIG]["source"]):
        assert 1 <= len(line) <= 200 and "\n" not in line and "\t" not in line
    # A quarter of the cells, rounded down, and always one, may take four.
    assert len(cells) >= 6
    assert sum(w["chips"] == 4 for w in cells.values()) <= max(
        1, len(cells) // 4)
    assert [w["name"] for w in cells.values() if w["chips"] == 4] == [
        "gpt2-xl.train-s1024-x4"]
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


# -- the configuration file ----------------------------------------------------

PERIOD = ["linear_attention"] * 3 + ["full_attention"]
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "layer_types": PERIOD * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}}


def test_the_configuration_is_the_published_one_less_what_reduced_names():
    catalog = Catalog(str(ROOT))
    cell = catalog.cell(CELL)
    sizes, mix = cell["sizes"], cell["mix"]
    differs = {k for k, v in PUBLISHED.items() if sizes[k] != v}
    assert differs == set(sizes["reduced"]) == {
        "num_hidden_layers", "layer_types", "vocab_size"}
    assert sizes["num_hidden_layers"] == 4 and sizes["layer_types"] == PERIOD
    assert sizes["vocab_size"] == 12544 == 100352 // 8
    assert set(sizes["reduced_why"]) == set(sizes["reduced"])
    assert sizes["source"] == _bench_config()["source"]
    for filled in ("block", "assumed", "departures", "deployment", "check"):
        assert sizes[filled]
    # The check keeps every width and both kinds of layer: it cuts depth,
    # the pattern that goes with it, and rows of the vocabulary.
    assert sizes["check"]["sizes"] == {
        "num_hidden_layers": 2,
        "layer_types": ["linear_attention", "full_attention"],
        "vocab_size": 8192}
    assert sizes["check"]["steps"] == 3
    assert sizes["check"]["rtol"] <= 2e-4
    assert (mix["seq_len"], mix["rows_per_chip"], mix["masked_per_row"],
            mix["pool_batches"], mix["lag_steps"], mix["driver"]) == (
        4096, 1, 0, 64, 2, "train")
    assert cell["chips"] == 1 and sizes["deployment"]["chips"] == 1
    kind = catalog.module("kinds", "hybrid_causal_lm")
    assert kind.attention_calls(sizes, mix) == {
        "batch_heads": 30, "seq_len": 4096, "head_width": 128,
        "causal": True}


def _bench_config():
    return next(c for c in _bench()["configs"] if c["name"] == CONFIG)


def test_flops_per_token_by_hand():
    catalog = Catalog(str(ROOT))
    cell = catalog.cell(CELL)
    kind = catalog.module("kinds", "hybrid_causal_lm")
    d, inner, heads, d_k, d_v = 3840, 11008, 30, 96, 192
    full = 4 * d * d + 3 * d * inner                       # 185,794,560
    linear = d * (2 * heads * d_k + 3 * heads * d_v) + 2 * d * heads \
        + 3 * d * inner                                    # 215,516,160
    assert (full, linear) == (185_794_560, 215_516_160)
    passed = 3 * linear + full + 12544 * d
    assert passed == 880_512_000        # matmul parameters a position passes
    by_hand = 6 * passed + 12 * 4096 * d // 2 + 3 * 18 * heads * d_k * d_v
    assert by_hand == 5_283_072_000 + 94_371_840 + 29_859_840
    assert kind.flops_per_token(cell["sizes"], cell["mix"]) == by_hand


def test_the_kind_refuses_what_it_does_not_implement():
    catalog = Catalog(str(ROOT))
    sizes = catalog.cell(CELL)["sizes"]
    kind = catalog.module("kinds", "hybrid_causal_lm")
    for wrong in ({"attention_bias": True},
                  {"rope_parameters": {"rope_theta": 500000.0}},
                  {"num_key_value_heads": 6},
                  {"linear_num_value_heads": 60},
                  {"tie_word_embeddings": True},
                  {"layer_types": PERIOD[:3]},
                  {"layer_types": ["sliding_attention"] * 4}):
        with pytest.raises(ValueError, match="does not implement"):
            kind.program({**sizes, **wrong})
        with pytest.raises(ValueError, match="does not implement"):
            kind.reference_loss({**sizes, **wrong})


def test_the_reference_imports_nothing_of_the_program():
    text = (ROOT / "chipbench" / "reference_olmo_hybrid.py").read_text()
    assert "import autodist_tpu" not in text
    assert "from autodist_tpu" not in text
    assert "pallas" not in text


# -- the scan's arithmetic ---------------------------------------------------------

def test_scan_cost_by_hand():
    shape = dict(positions=4096, heads=30, key_width=96, value_width=192)
    # Forward: S k, the rank-one write, S q: 3 x 2 x 96 x 192 a head and
    # position = 110,592; x 30 heads x 4,096 positions.
    ops, nbytes = flops_gdn.scan_cost("forward", **shape)
    assert ops == 6 * 4096 * 30 * 96 * 192 == 13_589_544_960
    # q, k (96 each) and v, o (192 each) in bf16, g and beta in f32.
    assert nbytes == 4096 * 30 * (2 * (96 + 96 + 192 + 192) + 8) \
        == 142_540_800
    # Backward: q, k, v, dO and the gates read again; dq, dk (96 each), dv
    # (192) written in bf16, the gates' gradients in f32.
    back_ops, back_bytes = flops_gdn.scan_cost("backward", **shape)
    assert back_ops == 2 * ops
    assert back_bytes == nbytes + 4096 * 30 * (2 * (96 + 96 + 192) + 8) \
        == 237_895_680
    least = [flops.roofline_seconds(*flops_gdn.scan_cost(phase, **shape), V5E)
             for phase in flops_gdn.PHASES]
    assert [bound for _, bound in least] == ["memory", "memory"]
    assert sum(s for s, _ in least) == pytest.approx(0.4645e-3, rel=1e-3)
    # The count is the recurrence's: no chunk size enters it.
    assert "chunk" not in flops_gdn.scan_cost.__code__.co_varnames


# -- the readers on a made-up table ----------------------------------------------

JOINED = {"busy_s": 2.0, "scope": {
    "gdn/proj": 0.2, "gdn/conv": 0.06, "gdn/gates": 0.04, "gdn/scan": 0.4,
    "gdn/out": 0.08, "gdn": 0.02, "attn": 0.2, "mlp": 0.5, "head": 0.1,
    "optimizer": 0.4}}
SHAPES = {"layers": 3, "heads": 30, "key_width": 96, "value_width": 192}


def _traced_run():
    # 10 steps of 4,096 positions in a window of 2.5 s, one chip.
    return {"trace": {"programs": 10.0}, "peak": V5E, "chips": 1,
            "steps": 10, "window_s": 2.5, "tokens_per_s": 16384.0}


def test_the_scope_readers_add_up_the_mixers_scopes(monkeypatch):
    monkeypatch.setattr(program_probe, "by_scope", lambda: JOINED)
    assert gdn_scope_share.read(_traced_run()) == pytest.approx(40.0)
    assert gdn_scan_share.read(_traced_run()) == pytest.approx(20.0)
    assert gdn_scope_share.read({"trace": None}) is None
    assert gdn_scan_share.read({"trace": None}) is None


def test_the_roofline_reader_divides_the_least_time_by_the_scans(
        monkeypatch, capsys):
    monkeypatch.setattr(program_probe, "by_scope", lambda: JOINED)
    monkeypatch.setattr(gdn_scan_roofline, "program_shapes",
                        lambda: dict(SHAPES))
    # Ten steps of three layers of 0.4645 ms at least, in 0.4 s of gdn/scan.
    assert gdn_scan_roofline.read(_traced_run()) == pytest.approx(
        100 * 10 * 3 * 0.4645e-3 / 0.4, rel=1e-3)
    out = capsys.readouterr().out
    assert "4096 positions a layer" in out and "bound by memory" in out
    monkeypatch.setattr(gdn_scan_roofline, "program_shapes",
                        lambda: dict(SHAPES, layers=1))
    assert gdn_scan_roofline.read(_traced_run()) == pytest.approx(
        100 * 10 * 0.4645e-3 / 0.4, rel=1e-3)


@pytest.mark.parametrize("reader", [gdn_scope_share, gdn_scan_share,
                                    gdn_scan_roofline])
def test_a_program_without_the_mixers_scopes_gives_the_readers_nothing(
        reader, monkeypatch):
    """The parent's table, or another cell's: the metric is left out."""
    monkeypatch.setattr(gdn_scan_roofline, "program_shapes",
                        lambda: dict(SHAPES))
    other = {"busy_s": 2.0, "scope": {"attn": 0.9, "mlp": 0.4, "head": 0.2,
                                      "moe/experts": 0.3}}
    monkeypatch.setattr(program_probe, "by_scope", lambda: other)
    assert reader.read(_traced_run()) is None
    monkeypatch.setattr(program_probe, "by_scope", lambda: None)
    assert reader.read(_traced_run()) is None


def test_without_a_runner_there_are_no_shapes():
    assert gdn_scan_roofline.program_shapes() is None
    assert gdn_scan_roofline.read({"trace": None}) is None
