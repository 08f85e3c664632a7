"""Kind ``looped_causal_lm`` on the CPU at a toy size: its cell through the
harness (one traced session, shared), the ``loop_*`` readers on that
session and on made-up tables, the check's controls through the harness's
own comparison, the configuration against the catalog's row,
the yardstick's arithmetic by hand, the entries of ``BENCHMARK.json`` by
name.  Nothing here is a measurement."""
import collections
import json
import pathlib
import shutil
import time

import pytest

from chipbench import measure, trace_reduce
from chipbench import run as chipbench_run
from chipbench.catalog import Catalog
from chipbench import controls_ouro
from chipbench.layer_metrics import loop_body_share, loop_early_exit_share

from conftest import TOY_CELLS, add_cell

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
CONFIG = "ouro-2.6b"
CELL = "ouro-2.6b.train-s2048"
TRAFFIC = "lm-s2048-r1"
KIND = "looped_causal_lm"
TOY_CELL = "tiny-ouro.train-s32"
# reader, unit: what the entry of each in ``BENCHMARK.json`` says.
NEW_METRICS = {"loop_early_exit_share": (loop_early_exit_share, "%"),
               "loop_body_share": (loop_body_share, "%")}
CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")


@pytest.fixture(scope="module")
def toy_catalog(tmp_path_factory):
    """A copy of the benchmark with the toy cells added, the new readers
    declared for the toy looped cell, and a row for the CPU in the copy's
    table of peaks."""
    root = tmp_path_factory.mktemp("ouro")
    shutil.copytree(ROOT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root)
    for name, config, traffic in TOY_CELLS:
        add_cell(root, name, config, traffic, chips=8)
    add_cell(root, TOY_CELL, "tiny-ouro", "tiny-lm-s32", chips=8)
    path = root / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    for metric in bench["per_layer"]:
        if metric["name"] in NEW_METRICS:
            metric["workloads"].append(TOY_CELL)
    path.write_text(json.dumps(bench))
    peaks_path = root / "chipbench" / "peaks.json"
    peaks = json.loads(peaks_path.read_text())
    peaks["cpu"] = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
                    "hbm_bytes": 1e10}
    peaks_path.write_text(json.dumps(peaks))
    return Catalog(str(root))


@pytest.fixture(scope="module")
def session(toy_catalog):
    """The toy cell run once through the harness, traced, on the CPU's eight
    devices (the explicit ``shard_map`` step), and what the tests read of
    it: the result line and the program's own account of the step."""
    catalog = toy_catalog
    recorded = trace_reduce.load(DATA / "gpt2-medium.train-s1024.xplane.pb.gz")
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(trace_reduce, "load", lambda path: recorded)
        line = chipbench_run.run_cell(
            catalog, catalog.cell(TOY_CELL), seed=2147483693, seconds=0.5,
            trace=True, clock0=(time.perf_counter(),
                                measure.process_age_s()))
    from autodist_tpu import observability
    from autodist_tpu.autodist import get_default_autodist
    from autodist_tpu.observability import profile, recorder
    runner = get_default_autodist().runner
    text = runner.step_text()
    return {
        "line": line, "explicit": runner.program.use_explicit_path,
        "aux": {k: [float(x) for x in v.reshape(-1)]
                for k, v in runner.last_aux.items()},
        "gauges": observability.registry().snapshot()["gauges"],
        "events": [e["detail"] for e in recorder.events()
                   if e["kind"] == "loop"],
        "scopes": {scope for scope, _ in profile.scope_table(text).values()},
        "overlay": {top: collections.Counter(
            where for where, _ in profile.overlay_table(text, top).values())
            for top in ("pass", "pass0", "pass3", "exit_loss")},
        "program": loop_body_share.program() is not None}


def test_the_toy_cell_runs_traced_on_the_explicit_step(session):
    """One test for the one session: under ``--dist load`` every test that
    asks for the fixture may build it again in another worker."""
    line = session["line"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1 and session["explicit"]
    # The CPU leaves no trace of its own to join with the program's table:
    # the readers have nothing to read, and say so by saying nothing.
    assert not set(NEW_METRICS) & set(line["metrics"])
    assert {"mfu", "attn_kernel_share"} <= set(line["metrics"])
    assert session["program"]

    # The program reports every pass.
    aux, gauges = session["aux"], session["gauges"]
    assert set(aux) == {"xent", "loop.xent", "loop.exit_pdf",
                        "loop.exit_entropy"}
    assert len(aux["loop.xent"]) == len(aux["loop.exit_pdf"]) == 4
    assert aux["xent"] == aux["loop.xent"][-1:]
    assert sum(aux["loop.exit_pdf"]) == pytest.approx(1.0, abs=1e-5)
    assert all(4.0 < x < 7.0 for x in aux["loop.xent"])     # ln 257 = 5.55
    assert 0.0 < aux["loop.exit_entropy"][0] < 1.3863       # ln 4
    assert {name: gauges[name] for name in gauges
            if name.startswith("loop.")} == {
        "loop.passes": 4, "loop.layers": 3, "loop.applications": 12}
    assert any("3 layers run 4 times" in said for said in session["events"])

    # The scopes fold into the generic rows, and the loop is told.
    assert {"attn", "mlp", "head", "ln_f", "exit_gate", "exit_loss", "embed",
            "optimizer"} <= session["scopes"]
    # ``pass`` is what the scan runs in no scope of its body (its carry's
    # sums and copies); no row keeps a pass's number or a loop's frame.
    assert {s for s in session["scopes"] if s.startswith("pass")} == {"pass"}
    assert not {s for s in session["scopes"] if "closed_call" in s}
    overlay = session["overlay"]
    # The scan's body holds most of the program; a pass's head and gate are
    # under the pass's own scope, the last pass has a head and no gate.
    assert overlay["pass"]["pass"] > 300
    assert overlay["pass0"]["pass0"] > overlay["pass3"]["pass3"] > 10
    assert overlay["exit_loss"]["exit_loss"] > 5
    assert overlay["pass"]["elsewhere"] > overlay["pass0"]["pass0"]


# -- the readers on made-up tables -------------------------------------------

BY_TOP = {"pass": 2.0, "pass0": 0.03, "pass1": 0.03, "pass2": 0.03,
          "pass3": 0.04, "exit_loss": 0.02, "elsewhere": 0.30,
          "(unattributed)": 0.05}


def test_the_readers_arithmetic(monkeypatch):
    monkeypatch.setattr(loop_body_share, "seconds_by_top_scope",
                        lambda run: (dict(BY_TOP), 2.5, 4))
    run = {"trace": {"programs": 10.0}}
    # Heads and gates of passes 0-2 and the weighing: 0.11 of 2.5 s.
    assert loop_early_exit_share.read(run) == pytest.approx(100 * 0.11 / 2.5)
    assert loop_body_share.read(run) == pytest.approx(80.0)
    monkeypatch.setattr(loop_body_share, "seconds_by_top_scope",
                        lambda run: ({"elsewhere": 2.5}, 2.5, 4))
    assert loop_early_exit_share.read(run) is None
    assert loop_body_share.read(run) is None


@pytest.mark.parametrize("name", list(NEW_METRICS))
def test_a_program_without_a_loop_gives_the_readers_nothing(name,
                                                            monkeypatch):
    """The parent's program, or another cell's: no ``loop.passes`` gauge;
    the metric is left out and nothing is raised."""
    from autodist_tpu.observability import metrics
    reader = NEW_METRICS[name][0]
    run = {"trace": {"programs": 10.0}}
    monkeypatch.setattr(metrics.registry(), "snapshot",
                        lambda: {"gauges": {"moe.experts": 64}})
    assert loop_body_share.program() is None
    assert reader.read(run) is None
    assert reader.read({"trace": None}) is None


@pytest.mark.parametrize("control, refused", [
    ("sound", False), ("bfloat16", None), ("last_pass_only", True)])
def test_a_control_goes_through_the_harness_own_comparison(toy_catalog,
                                                           control, refused):
    """``controls_ouro`` at the toy size: the program passes, a planted
    reference in the program's place is refused by ``reference_check``
    itself; the bfloat16 control runs (what it reads at a toy size decides
    nothing)."""
    found = controls_ouro.control(toy_catalog, toy_catalog.cell(TOY_CELL),
                                  2147483693, control, steps=2)
    assert found["control"] == control and len(found["program"]) == 2
    assert found["rtol"] == 0.01 and found["refused"] == (not found["ok"])
    if refused is not None:
        assert found["refused"] is refused, found
    assert set(controls_ouro.CONTROLS) == {"sound", "bfloat16"} | set(
        controls_ouro.reference_ouro.PLANTS)


# -- the entries, by name -----------------------------------------------------

def _bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_the_new_readers_are_declared_for_the_new_cell_only():
    declared = {m["name"]: m for m in _bench()["per_layer"]}
    for name, (reader, unit) in NEW_METRICS.items():
        assert declared[name] == {
            "name": name, "unit": unit, "better": "lower",
            "source": "device_trace", "layer": "Step on device",
            "moves": "tokens_per_s", "workloads": [CELL]}
        assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES) == (
            name, unit, "Step on device", "tokens_per_s")
    # No metric that lists its cells lists this one but these.
    assert {m["name"] for m in declared.values()
            if CELL in m.get("workloads", ())} == set(NEW_METRICS)
    catalog = Catalog(str(ROOT))
    wanted = {m["name"] for m in catalog.metric_specs("per_layer", CELL)}
    assert set(NEW_METRICS) | {"mfu", "attn_kernel_roofline",
                               "attn_kernel_share", "head_share"} <= wanted
    assert not set(NEW_METRICS) & {m["name"] for m in catalog.metric_specs(
        "per_layer", "gpt2-medium.train-s1024")}


def test_the_cell_and_its_configuration_are_declared():
    bench = _bench()
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    assert CELL in cells and CONFIG in configs
    assert cells[CELL] == {**cells[CELL], "config": CONFIG,
                           "traffic": TRAFFIC, "chips": 1}
    assert configs[CONFIG] == {
        **configs[CONFIG],
        "source": "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/"
                  "config.json",
        "file": "chipbench/configs/ouro-2.6b.json",
        "reduced": ["num_hidden_layers", "layer_types", "vocab_size"]}
    for line in (cells[CELL]["why"], configs[CONFIG]["why"],
                 configs[CONFIG]["source"]):
        assert 1 <= len(line) <= 200 and "\n" not in line and "\t" not in line
    assert sum(w["chips"] == 4 for w in cells.values()) <= max(
        1, len(cells) // 4)
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def _catalog_entry():
    if CATALOG.exists():
        for row in map(json.loads, CATALOG.read_text().splitlines()):
            if row["name"] == "Ouro-2.6B":
                return row
    pytest.skip("the catalog has no Ouro-2.6B")


def test_the_configuration_is_the_catalogs_less_what_reduced_names():
    published = _catalog_entry()["config"]
    cell = Catalog(str(ROOT)).cell(CELL)
    sizes, mix = cell["sizes"], cell["mix"]
    assert sizes["kind"] == KIND and set(published) <= set(sizes)
    differs = {k for k, v in published.items() if sizes[k] != v}
    assert differs == set(sizes["reduced"]) == {
        "num_hidden_layers", "layer_types", "vocab_size"}
    assert (sizes["num_hidden_layers"], sizes["vocab_size"],
            sizes["total_ut_steps"]) == (8, 8192, 4)
    assert sizes["layer_types"] == published["layer_types"][:8]
    assert (sizes["published"]["num_hidden_layers"],
            sizes["published"]["vocab_size"]) == (48, 49152)
    # A sixth of the depth and a sixth of the vocabulary; no width differs.
    assert 6 * 8 == 48 and 6 * 8192 == 49152
    assert set(sizes["reduced_why"]) == set(sizes["reduced"])
    assert sizes["source"] == _catalog_entry()["source_url"]
    for filled in ("block", "assumed", "departures", "deployment", "check"):
        assert sizes[filled]
    assert {"published_code", "norm_placement", "pass_restart", "exit_gate",
            "exit_entropy_coef", "no_embedding_scale", "rotary"} <= set(
        sizes["assumed"])
    assert sizes["block"]["exit_entropy_coef"] == 0.05
    deployment = sizes["deployment"]
    assert (deployment["chips"], deployment["pipeline_stages"],
            deployment["vocab_ranks"]) == (1, 6, 6)
    assert deployment["optimizer"] == {"name": "adam",
                                       "learning_rate": 0.0001}
    assert sizes["check"]["sizes"] == {
        "num_hidden_layers": 2,
        "layer_types": ["full_attention", "full_attention"]}
    assert sizes["check"]["steps"] == 16 and sizes["check"]["rtol"] <= 2e-4
    assert (mix["seq_len"], mix["rows_per_chip"], mix["masked_per_row"],
            mix["pool_batches"], mix["lag_steps"], mix["driver"]) == (
        2048, 1, 0, 64, 2, "train")
    kind = Catalog(str(ROOT)).module("kinds", KIND)
    assert kind.attention_calls(sizes, mix) == {
        "batch_heads": 16, "seq_len": 2048, "head_width": 128,
        "causal": True}
    cfg = kind.config(sizes)
    assert (cfg.loops, cfg.norm_position, cfg.exit_entropy_coef,
            cfg.num_layers, cfg.rope_theta) == (4, "sandwich", 0.05, 8, 1e6)


def test_flops_per_token_by_hand():
    catalog = Catalog(str(ROOT))
    cell = catalog.cell(CELL)
    kind = catalog.module("kinds", KIND)
    d, inner = 2048, 5632
    layer = 4 * d * d + 3 * d * inner
    assert layer == 51_380_224      # 51,388,416 parameters less four scales
    passed = 4 * (8 * layer + 8192 * d)
    assert passed == 1_711_276_032  # matmul parameters a position passes
    by_hand = 6 * passed + 4 * 8 * 12 * 2048 * d // 2
    assert by_hand == 10_267_656_192 + 805_306_368
    assert kind.flops_per_token(cell["sizes"], cell["mix"]) == by_hand
    # The four heads' share of the products, here and at the published size.
    assert 4 * 8192 * d / passed == pytest.approx(
        4 * 49152 * d / (4 * (48 * layer + 49152 * d)), rel=1e-9)


def test_the_kind_refuses_what_it_does_not_implement():
    catalog = Catalog(str(ROOT))
    sizes = catalog.cell(CELL)["sizes"]
    kind = catalog.module("kinds", KIND)
    for wrong in ({"tie_word_embeddings": True}, {"num_key_value_heads": 4},
                  {"use_sliding_window": True}, {"sliding_window": 4096},
                  {"rope_scaling": {"type": "yarn"}}, {"head_dim": 64},
                  {"total_ut_steps": 1}, {"hidden_act": "gelu"},
                  {"layer_types": ["full_attention"] * 7},
                  {"layer_types": ["sliding_attention"] * 8}):
        with pytest.raises(ValueError, match="does not implement"):
            kind.program({**sizes, **wrong})
        with pytest.raises(ValueError, match="does not implement"):
            kind.reference_loss({**sizes, **wrong})


@pytest.mark.parametrize("name", ["reference_ouro.py",
                                  "kinds/looped_causal_lm.py"])
def test_the_yardstick_imports_nothing_of_the_program(name):
    text = (ROOT / "chipbench" / name).read_text()
    if name.startswith("reference"):
        assert "autodist_tpu" not in text.replace(
            "It imports nothing from ``autodist_tpu``", "")
        assert "pallas" not in text
    else:   # the kind reaches the program inside ``config`` and ``program``
        top = text.split("\ndef ")[0]
        assert "autodist_tpu" not in top.split('"""')[2]
        assert "from chipbench import reference_ouro" in top
