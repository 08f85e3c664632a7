"""Kind ``kda_mla_moe_causal_lm`` on the CPU at a toy size: its cell through
the harness, the yardstick's arithmetic by hand, the four new readers on a
fixture table, the configuration against the catalog's, the entries of
``BENCHMARK.json`` by name.  Nothing here is a measurement."""
import json
import pathlib
import time

import pytest

from chipbench import flops, flops_kda, measure, program_probe, trace_reduce
from chipbench import run as chipbench_run
from chipbench.catalog import Catalog
from chipbench.layer_metrics import (kda_scan_roofline, kda_scan_share,
                                     kda_scope_share, moe_group_route_share)

from conftest import add_cell

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
CONFIG = "ling-3.0-flash-vl"
CELL = "ling-3.0-flash-vl.train-s2048"
TRAFFIC = "lm-s2048-r1"
KIND = "kda_mla_moe_causal_lm"
TOY_CELL = "tiny-ling.train-s32"
# reader, better: what the entry of each in ``BENCHMARK.json`` says.
NEW_METRICS = {
    "kda_scope_share": (kda_scope_share, "lower"),
    "kda_scan_share": (kda_scan_share, "lower"),
    "kda_scan_roofline": (kda_scan_roofline, "higher"),
    "moe_group_route_share": (moe_group_route_share, "lower")}
V5E = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "num_experts",
           "vocab_size"]


@pytest.fixture
def ling_root(toy_root):
    """``toy_root`` with the toy cell added the same way, and the new
    readers declared for it alone."""
    add_cell(toy_root, TOY_CELL, "tiny-ling", "tiny-lm-s32", chips=8)
    path = toy_root / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    bench["per_layer"] += [
        {"name": name, "unit": reader.UNIT, "better": better,
         "source": "device_trace", "layer": reader.LAYER,
         "moves": reader.MOVES, "workloads": [TOY_CELL]}
        for name, (reader, better) in NEW_METRICS.items()]
    path.write_text(json.dumps(bench))
    return toy_root


def _cell():
    """The configuration and its traffic mix, as the harness reads them."""
    return Catalog(str(ROOT)).cell(CELL)


def test_the_toy_cell_runs_traced_and_the_program_names_its_scopes(
        ling_root, monkeypatch):
    recorded = trace_reduce.load(DATA / "gpt2-medium.train-s1024.xplane.pb.gz")
    monkeypatch.setattr(trace_reduce, "load", lambda path: recorded)
    catalog = Catalog(str(ling_root))
    line = chipbench_run.run_cell(
        catalog, catalog.cell(TOY_CELL), seed=2147483781, seconds=0.5,
        trace=True, clock0=(time.perf_counter(), measure.process_age_s()))
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    # The CPU leaves no trace of its own to join with the program's table:
    # the trace readers have nothing to read and say nothing.
    assert not set(NEW_METRICS) & set(line["metrics"])
    assert not {"gdn_scope_share", "moe_load_imbalance"} \
        & set(line["metrics"])
    from autodist_tpu.autodist import get_default_autodist
    from autodist_tpu.observability import metrics
    runner = get_default_autodist().runner
    aux = runner.last_aux
    assert float(aux["moe.dropped"]) == 0.0
    assert float(aux["kda.state_absmax"]) > 0
    assert -75.0 <= float(aux["kda.gate_min"]) < 0
    assert 1.0 <= float(aux["moe.groups_reached"]) <= 2.0
    gauges = metrics.registry().snapshot()["gauges"]
    assert (gauges["kda.heads"], gauges["kda.key_dim"],
            gauges["kda.sub_block"], gauges["kda.gate_lower_bound"]) == (
                4, 8, 16, -5.0)
    assert (gauges["moe.groups"], gauges["moe.groups_kept"]) == (4, 2)
    assert kda_scan_roofline.program_shapes() == {
        "layers": 2, "heads": 4, "key_width": 8, "value_width": 8}
    table = runner.scope_table()
    scopes = {scope for scope, _ in table.values()}
    assert {"attn", "head", "optimizer", "mlp", "kda/proj", "kda/conv",
            "kda/gates", "kda/scan", "kda/out", "moe/router",
            "moe/router/groups", "moe/dispatch", "moe/experts",
            "moe/shared"} <= scopes
    # The toy recomputes its linear mixers (deployment.recomputation): what
    # the backward pass computes again keeps the mixer's rows.
    assert ("kda/scan", "backward") in set(table.values())
    assert not {scope for scope in scopes if "rematted" in scope}


def _table(**seconds):
    return {"busy_s": 10.0, "scope": seconds}


@pytest.mark.parametrize("table, want", [
    (_table(**{"kda/proj": 1.0, "kda/conv": 0.25, "kda/gates": 0.25,
               "kda/scan": 2.0, "kda/out": 0.5, "kda": 0.0,
               "moe/router": 0.5, "moe/router/groups": 0.25, "attn": 3.0,
               "gdn/scan": 9.0}),
     {"kda_scope_share": 40.0, "kda_scan_share": 20.0,
      "moe_group_route_share": 7.5}),
    # A program with no such scope (the parent's, every other cell's): no
    # reader reports, none raises, none says 0.
    (_table(**{"gdn/scan": 2.0, "moe/router": 0.5, "attn": 3.0}),
     {"kda_scope_share": None, "kda_scan_share": None,
      "moe_group_route_share": None}),
    (None, {"kda_scope_share": None, "kda_scan_share": None,
            "moe_group_route_share": None})],
    ids=["ling", "other-cells", "no-table"])
def test_the_share_readers_on_a_fixture_table(monkeypatch, table, want):
    monkeypatch.setattr(program_probe, "by_scope", lambda: table)
    run = {"trace": {"programs": 12}}
    for name, value in want.items():
        got = NEW_METRICS[name][0].read(run)
        assert got == (None if value is None else pytest.approx(value)), name
    assert kda_scope_share.read({"trace": None}) is None


def test_the_roofline_reader_on_a_fixture_table(monkeypatch):
    """Six layers of 32 heads of 128 / 128 over 2,048 positions, 12 traced
    steps, 0.24 s in ``kda/scan``: the least the rule needs by
    ``flops_kda`` over the time measured; nothing where the program has no
    KDA mixer or the scope took no time."""
    shapes = {"layers": 6, "heads": 32, "key_width": 128, "value_width": 128}
    monkeypatch.setattr(kda_scan_roofline, "program_shapes",
                        lambda: dict(shapes))
    monkeypatch.setattr(program_probe, "by_scope",
                        lambda: _table(**{"kda/scan": 0.24}))
    run = {"trace": {"programs": 12}, "tokens_per_s": 2048.0 * 5,
           "window_s": 30.0, "steps": 150, "chips": 1, "peak": V5E}
    least = sum(flops.roofline_seconds(*flops_kda.scan_cost(
        phase, positions=2048, heads=32, key_width=128, value_width=128),
        V5E)[0] for phase in flops_kda.PHASES)
    assert kda_scan_roofline.read(run) == pytest.approx(
        100.0 * 6 * least * 12 / 0.24)
    assert 0 < kda_scan_roofline.read(run) < 100
    monkeypatch.setattr(kda_scan_roofline, "program_shapes", lambda: None)
    assert kda_scan_roofline.read(run) is None
    monkeypatch.setattr(program_probe, "by_scope",
                        lambda: _table(**{"gdn/scan": 0.24}))
    assert kda_scan_roofline.read(run) is None


def test_the_rules_operations_and_bytes_by_hand():
    """One layer of 32 heads of 128 / 128 over 2,048 positions.  Forward:
    the decay of the state's rows and the recurrence's three products, 7 d_k
    d_v a head and position; q, k, v, o in bf16 and the gates (128 decays
    and one write strength a head) in f32.  Backward: twice the operations;
    q, k, v, do and the gates read again, dq, dk, dv and the gates'
    gradients written.  Both phases are bound by the operations."""
    shape = dict(positions=2048, heads=32, key_width=128, value_width=128)
    ops, nbytes = flops_kda.scan_cost("forward", **shape)
    assert ops == 7 * 2048 * 32 * 128 * 128 == 7_516_192_768
    assert nbytes == 2048 * 32 * (2 * 4 * 128 + 4 * 129) == 100_925_440
    back_ops, back_bytes = flops_kda.scan_cost("backward", **shape)
    assert back_ops == 2 * ops
    assert back_bytes == 2048 * 32 * (
        2 * 4 * 128 + 4 * 129 + 2 * 3 * 128 + 4 * 129) == 185_073_664
    for o, b in ((ops, nbytes), (back_ops, back_bytes)):
        assert flops.roofline_seconds(o, b, V5E)[1] == "memory"
    # The same count whatever implements the rule: no chunk, no sub-block.
    assert flops_kda.PHASES == ("forward", "backward")


def test_operations_a_token_by_hand():
    cell = _cell()
    sizes, mix = cell["sizes"], cell["mix"]
    kind = Catalog(str(ROOT)).module("kinds", KIND)
    d, wide = 2560, 32 * 128
    kda = d * (4 * wide + 64) + wide * d
    latent = d * 32 * 192 + d * 576 + 512 * 32 * 256 + wide * d + d * 32
    expert = 3 * d * 768
    parts = kind.matmul_parameters(sizes)
    assert parts == {
        "kda_mixers": 6 * kda, "latent_mixers": latent,
        "dense_mlp": 3 * d * 6144,
        "expert_layers": 6 * (expert + d * 512 + 8 * 8 / 512 * expert),
        "head": 19648 * d}
    assert kind.flops_per_token(sizes, mix) == 6 * sum(parts.values()) \
        + 6 * 2048 * 32 * (192 + 128) // 2 + 6 * 21 * 32 * 128 * 128
    assert kind.attention_calls(sizes, mix) == {
        "batch_heads": 32, "seq_len": 2048, "head_width": 160,
        "causal": True}
    assert kind.layer_types(sizes) == ["kda_attention"] * 6 \
        + ["latent_attention"]


# -- the entries, by name -----------------------------------------------------

def _bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_the_new_readers_are_declared_for_the_new_cell_only():
    declared = {m["name"]: m for m in _bench()["per_layer"]}
    readers = {m.NAME: m for m in Catalog(str(ROOT)).layer_metrics()}
    for name, (reader, better) in NEW_METRICS.items():
        assert readers[name].NAME == reader.NAME == name
        assert declared[name] == {
            "name": name, "unit": "%", "better": better,
            "source": "device_trace", "layer": reader.LAYER,
            "moves": "tokens_per_s", "workloads": [CELL]}
        assert reader.MOVES == "tokens_per_s" and reader.UNIT == "%"
    assert kda_scan_roofline.LAYER == "Kernels"
    # No older list is appended to (the older cells' tests pin theirs): the
    # cell's only per-layer metrics with a list are the new ones.
    assert {m["name"] for m in declared.values()
            if CELL in m.get("workloads", ())} == set(NEW_METRICS)
    assert [m["name"] for m in _bench()["per_layer"][-4:]] == list(
        NEW_METRICS)
    catalog = Catalog(str(ROOT))
    wanted = {m["name"] for m in catalog.metric_specs("per_layer", CELL)}
    assert set(NEW_METRICS) | {"attn_kernel_roofline", "mfu",
                               "optimizer_share"} <= wanted
    assert not {"gdn_scan_roofline", "mla_kernel_roofline", "moe_held_share",
                "collective_share"} & wanted
    for other in ("olmo-hybrid-7b.train-s4096", "joyai-llm-flash.train-s4096",
                  "qwen3-next-80b-a3b.train-s8192"):
        assert not set(NEW_METRICS) & {
            m["name"] for m in catalog.metric_specs("per_layer", other)}


def test_the_cell_and_its_configuration_are_declared():
    bench = _bench()
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    assert cells[CELL] == {**cells[CELL], "config": CONFIG,
                           "traffic": TRAFFIC, "chips": 1}
    assert configs[CONFIG]["source"] == (
        "https://huggingface.co/inclusionAI/Ling-3.0-flash-VL/blob/main/"
        "config.json")
    assert configs[CONFIG]["file"] == f"chipbench/configs/{CONFIG}.json"
    assert configs[CONFIG]["reduced"] == REDUCED
    for line in (cells[CELL]["why"], configs[CONFIG]["why"],
                 configs[CONFIG]["source"]):
        assert 1 <= len(line) <= 200 and "\n" not in line and "\t" not in line
    assert set(cells[CELL]) == {"name", "config", "traffic", "chips", "why"}
    # A quarter of the cells, rounded down, and always one, may take four.
    assert sum(w["chips"] == 4 for w in cells.values()) <= max(
        1, len(cells) // 4)
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


# -- the configuration file ---------------------------------------------------

def _catalog_entry():
    if not CATALOG.exists():
        pytest.skip(f"no catalog at {CATALOG}")
    for line in CATALOG.read_text().splitlines():
        entry = json.loads(line)
        if entry.get("name") == "Ling-3.0-flash-VL":
            return entry
    pytest.skip("the catalog has no Ling-3.0-flash-VL")


def test_the_configuration_is_the_catalogs_less_what_reduced_names():
    published = _catalog_entry()["config"]
    cell = _cell()
    sizes, mix = cell["sizes"], cell["mix"]
    assert sizes["kind"] == KIND
    assert set(published) <= set(sizes)
    differs = {k for k, v in published.items() if sizes[k] != v}
    assert differs == set(sizes["reduced"]) == set(REDUCED)
    assert sizes["reduced"] == REDUCED
    assert {k: published[k] for k in REDUCED} == {
        k: sizes["published"][k] for k in REDUCED} == {
        "num_hidden_layers": 42, "first_k_dense_replace": 2,
        "num_experts": 512, "vocab_size": 157184}
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        assert sizes["published"][key] == published[key] == sizes[key]
        assert not any(sizes[key][i] for i in sizes["layers_held"])
    # No width among them: depth, dense layers, experts held, vocabulary.
    assert (sizes["num_hidden_layers"], sizes["first_k_dense_replace"],
            sizes["num_experts"], sizes["vocab_size"]) == (
                7, 1, 8, 157184 // 8)
    assert sizes["layers_held"] == [1, 6, 7, 8, 9, 10, 11]
    assert set(sizes["reduced_why"]) == set(REDUCED)
    assert sizes["source"] == _catalog_entry()["source_url"]
    for filled in ("block", "assumed", "not_built", "departures",
                   "deployment", "check"):
        assert sizes[filled]
    assert {"published_code", "kda_heads", "qk_norm", "gate", "group_norm",
            "head_gate", "initialisation", "bias_update_rate", "bias_update",
            "group_limit", "no_balance_term"} <= set(sizes["assumed"])
    assert {"vision_tower", "prediction_module", "swiglu_clamp"} \
        == set(sizes["not_built"])
    deployment = sizes["deployment"]
    assert (deployment["chips"], deployment["expert_ranks"],
            deployment["vocab_ranks"]) == (1, 64, 8)
    assert deployment["expert_ranks"] * sizes["num_experts"] == 512
    assert "64 chips share each layer" in deployment["stands_for"]
    assert deployment["recomputation"] in ("none", "linear_mixer")
    assert deployment["recomputation_why"]
    assert deployment["optimizer"] == {"name": "adam", "learning_rate": 1e-6}
    assert deployment["optimizer_why"]
    assert "held_chunks" not in deployment
    checked = dict(sizes["check"]["sizes"])
    probes = checked.pop("probes")
    assert checked == {"num_hidden_layers": 3, "layers_held": [1, 6, 11]}
    assert set(probes) == {"held_output_rms", "update_mean_square",
                           "attn_output_std", "kda_output_std",
                           "groups_reached", "anchor_samples"}
    assert "probes" not in sizes
    assert sizes["check"]["steps"] == 3 and sizes["check"]["rtol"] <= 2e-4
    for fault in ("averaged over a head's channels", "group limit left out",
                  "without its bound", "head gate left off", "bf16"):
        assert fault in sizes["check"]["why"], fault
    assert (mix["seq_len"], mix["rows_per_chip"], mix["masked_per_row"],
            mix["pool_batches"], mix["lag_steps"], mix["driver"]) == (
                2048, 1, 0, 64, 2, "train")
    kind = Catalog(str(ROOT)).module("kinds", KIND)
    assert kind.tokens_per_row(mix) == 2048
    cfg = kind.config(sizes)
    assert (cfg.moe.num_experts, cfg.moe.held, cfg.moe.top_k,
            cfg.moe.scoring, cfg.moe.route_scale, cfg.moe.shared,
            cfg.moe.groups, cfg.moe.groups_kept, cfg.moe.select_bias) == (
                512, (0, 8), 8, "sigmoid", 2.5, 1, 8, 4, True)
    assert (cfg.linear_heads, cfg.linear_key_dim, cfg.linear_value_dim,
            cfg.conv_width, cfg.linear_gate_bound) == (32, 128, 128, 4, -5.0)
    assert (cfg.q_rank, cfg.kv_rank, cfg.nope_dim, cfg.rope_dim,
            cfg.value_dim, cfg.attn_gate, cfg.rope_theta) == (
                0, 512, 128, 64, 128, True, 6000000.0)
    assert (cfg.first_dense, cfg.mlp_dim, cfg.load_balance_coef,
            cfg.mixer_stats) == (1, 6144, 0.0, False)
    check_cfg = kind.config({**sizes, **sizes["check"]["sizes"]})
    assert check_cfg.layer_types == ("kda_attention", "kda_attention",
                                     "latent_attention")
    assert check_cfg.mixer_stats and check_cfg.first_dense == 1


def test_a_file_the_kind_does_not_implement_is_refused():
    kind = Catalog(str(ROOT)).module("kinds", KIND)
    sizes = _cell()["sizes"]
    for wrong in ({"kda_safe_gate": False}, {"q_lora_rank": 1536},
                  {"layers_held": [0, 1, 2, 3, 4, 5, 6]},
                  {"layers_held": [1, 6, 7, 8, 9, 10, 40]}):
        with pytest.raises(ValueError, match="does not implement"):
            kind.config({**sizes, **wrong})
