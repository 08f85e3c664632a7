"""Kind ``swa_moe_causal_lm`` on the CPU at a toy size: its cell through the
harness, the yardstick's arithmetic by hand, the eight new readers on made-up
tables, the configuration against the catalog's.  Every entry of
``BENCHMARK.json`` is looked up by its name: no test here says where in a
list an entry stands or how many there are.  Nothing here is a measurement."""
import json
import pathlib
import time

import pytest

from chipbench import flops, flops_swa, measure, program_probe, trace_reduce
from chipbench import run as chipbench_run
from chipbench.catalog import Catalog
from chipbench.layer_metrics import (attn_rope_gate_share, gqa_core_share,
                                     gqa_kernel_roofline, moe_held_share,
                                     moe_load_imbalance,
                                     moe_softmax_held_dispatch_share,
                                     moe_softmax_held_scope_share,
                                     moe_softmax_held_share, swa_core_share,
                                     swa_kernel_roofline)

from conftest import add_cell

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
CELL = "laguna-s-2.1.train-s4096"
CONFIG = "laguna-s-2.1"
KIND = "swa_moe_causal_lm"
TOY_CELL = "tiny-laguna.train-s32"
NEW_METRICS = {
    "swa_kernel_roofline": swa_kernel_roofline,
    "gqa_kernel_roofline": gqa_kernel_roofline,
    "swa_core_share": swa_core_share, "gqa_core_share": gqa_core_share,
    "attn_rope_gate_share": attn_rope_gate_share,
    "moe_softmax_held_scope_share": moe_softmax_held_scope_share,
    "moe_softmax_held_dispatch_share": moe_softmax_held_dispatch_share,
    "moe_softmax_held_share": moe_softmax_held_share}
V5E = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")


@pytest.fixture
def laguna_root(toy_root):
    """``toy_root`` with the toy cell added the same way, and named in the
    ``workloads`` of every metric that lists the real cell."""
    add_cell(toy_root, TOY_CELL, "tiny-laguna", "tiny-lm-s32", chips=8)
    path = toy_root / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    for metric in bench["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append(TOY_CELL)
    path.write_text(json.dumps(bench))
    return toy_root


def _run(root, trace):
    catalog = Catalog(str(root))
    return chipbench_run.run_cell(
        catalog, catalog.cell(TOY_CELL), seed=2147483777, seconds=0.5,
        trace=trace, clock0=(time.perf_counter(), measure.process_age_s()))


def test_the_toy_cell_runs_untraced(laguna_root):
    line = _run(laguna_root, trace=False)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == {"tokens_per_s", "step_ms_p90", "setup_s"}


def test_the_toy_cell_runs_traced_and_the_program_says_its_shapes(
        laguna_root, monkeypatch):
    recorded = trace_reduce.load(DATA / "gpt2-medium.train-s1024.xplane.pb.gz")
    monkeypatch.setattr(trace_reduce, "load", lambda path: recorded)
    line = _run(laguna_root, trace=True)
    assert line["correct"] is True
    # The CPU leaves no trace of its own to join with the program's table:
    # the trace readers have nothing to read and say nothing; the counter's
    # reader reads the program's aux.
    assert "moe_softmax_held_share" in line["metrics"]
    assert 0.0 <= line["metrics"]["moe_softmax_held_share"]["value"] <= 100.0
    assert not {"swa_core_share", "gqa_core_share", "attn_rope_gate_share",
                "swa_kernel_roofline", "gqa_kernel_roofline",
                "moe_softmax_held_scope_share", "moe_held_share",
                "moe_softmax_held_dispatch_share"} & set(line["metrics"])
    from autodist_tpu.autodist import get_default_autodist
    runner = get_default_autodist().runner
    aux = runner.last_aux
    assert float(aux["moe.dropped"]) == 0.0 and "state_updates" not in aux
    assert float(aux["moe.held_assignments"]) > 0
    assert moe_softmax_held_scope_share.softmax_held() is True
    assert swa_kernel_roofline.program_shapes() == {
        "window": (6, 1, 8), "full": (4, 2, None), "kv_heads": 2,
        "head_dim": 16}
    from autodist_tpu.observability import profile
    split = profile.subscope_table(runner.step_text(), "attn")
    assert {"attn/qkv", "attn/rope", "attn/core", "attn/window_core",
            "attn/gate", "attn/out", "elsewhere"} <= {
                scope for scope, _ in split.values()}
    scopes = {scope for scope, _ in runner.scope_table().values()}
    assert {"attn", "mlp", "head", "optimizer", "moe/router", "moe/dispatch",
            "moe/experts", "moe/shared"} <= scopes


# -- the check's number ---------------------------------------------------------

def _check(root, monkeypatch, broken=None, probes=True):
    """``drivers/train.py:reference_check`` of the toy cell, one function of
    the reference replaced by ``broken(original)`` where given."""
    import numpy as np
    from chipbench import reference_swa_moe
    from chipbench.drivers import train
    catalog = Catalog(str(root))
    cell = catalog.cell(TOY_CELL)
    sizes, mix = cell["sizes"], cell["mix"]
    if not probes:
        sizes["check"]["sizes"].pop("probes")
    if broken is not None:
        name, make = broken
        monkeypatch.setattr(reference_swa_moe, name,
                            make(getattr(reference_swa_moe, name)))
    kind = catalog.module("kinds", KIND)
    rows, seed, spans = mix["rows_per_chip"] * 8, 2147483779, measure.Spans()
    batch = kind.host_batch(sizes, mix, rows, np.random.RandomState(seed))
    return train.reference_check(
        kind, sizes, mix, rows, seed,
        train.Sessions(kind, batch, seed, spans), spans)


def _no_routed_experts(route):
    def broken(p, x, **kw):
        weights, chosen, probs = route(p, x, **kw)
        return 0.0 * weights, chosen, probs
    return broken


def _no_window(visible):
    return lambda start, block, seq, window: visible(start, block, seq, None)


def _heads_modulo(core):
    def broken(q, k, v, window):
        import jax.numpy as jnp
        group = q.shape[1] // k.shape[1]
        # Query head h reads h % kv_heads: the key-value heads reordered so
        # that the sound ``h // group`` indexing lands on them.
        order = (jnp.arange(q.shape[1]) % k.shape[1])[::group]
        return core(q, k[:, order], v[:, order], window)
    return broken


@pytest.mark.parametrize("broken, probes, ok", [
    (None, True, True),
    (("route", _no_routed_experts), True, False),
    # What the probes are for: the loss alone lets it through.
    (("route", _no_routed_experts), False, True),
    # What one number a step on uniform targets does not see at a toy's
    # tolerance: a fault of the score alone re-draws the logits' mean by the
    # seed (the configuration's check.why).  tests/test_gqa_window.py and
    # tests/test_laguna.py hold these against the same reference, exactly.
    (("visible", _no_window), True, True),
    (("attention_core", _heads_modulo), True, True)],
    ids=["sound", "no-routed-experts", "no-routed-experts-by-the-loss-alone",
         "no-window-is-the-cpu-tests-to-hold",
         "heads-modulo-is-the-cpu-tests-to-hold"])
def test_the_checks_number_sees_the_faults_it_is_held_to(
        laguna_root, monkeypatch, broken, probes, ok):
    check = _check(laguna_root, monkeypatch, broken, probes)
    assert check["ok"] is ok, check
    assert ("probes" in check["sizes_checked"]) is probes


# -- the entries, by name ------------------------------------------------------

def _bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_the_new_readers_are_declared_for_the_new_cell_only():
    declared = {m["name"]: m for m in _bench()["per_layer"]}
    assert set(NEW_METRICS) <= set(declared)
    for name, reader in NEW_METRICS.items():
        metric = declared[name]
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "tokens_per_s" and metric["unit"] == "%"
        assert set(metric) == {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        assert (reader.NAME, reader.UNIT, reader.MOVES, reader.LAYER) == (
            name, "%", "tokens_per_s", metric["layer"])
    for name in ("swa_kernel_roofline", "gqa_kernel_roofline"):
        assert declared[name]["layer"] == "Kernels"
        assert declared[name]["better"] == "higher"
    assert declared["moe_softmax_held_share"]["source"] == "program_counter"
    # No older list is appended to: the cell's only per-layer metrics with a
    # list are the new ones.
    assert {m["name"] for m in declared.values()
            if CELL in m.get("workloads", ())} == set(NEW_METRICS)
    catalog = Catalog(str(ROOT))
    wanted = {m["name"] for m in catalog.metric_specs("per_layer", CELL)}
    assert set(NEW_METRICS) | {"attn_kernel_roofline", "mfu"} <= wanted
    assert not {"moe_scope_share", "gdn_scope_share", "collective_share",
                "moe_held_share", "mla_kernel_roofline"} & wanted
    for other in ("gpt2-medium.train-s1024", "olmoe-1b-7b.train-s4096",
                  "olmo-hybrid-7b.train-s4096",
                  "joyai-llm-flash.train-s4096"):
        assert not set(NEW_METRICS) & {
            m["name"] for m in catalog.metric_specs("per_layer", other)}


def test_the_cell_and_its_configuration_are_declared():
    bench = _bench()
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    assert cells[CELL] == {**cells[CELL], "config": CONFIG,
                           "traffic": "lm-s4096-r1", "chips": 1}
    assert configs[CONFIG]["source"] == (
        "https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json")
    assert configs[CONFIG]["file"] == "chipbench/configs/laguna-s-2.1.json"
    assert configs[CONFIG]["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    for line in (cells[CELL]["why"], configs[CONFIG]["why"],
                 configs[CONFIG]["source"]):
        assert 1 <= len(line) <= 200 and "\n" not in line and "\t" not in line
    assert set(cells[CELL]) == {"name", "config", "traffic", "chips", "why"}
    # A quarter of the cells, rounded down, and always one, may take four.
    assert sum(w["chips"] == 4 for w in cells.values()) <= max(
        1, len(cells) // 4)
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


# -- the configuration file ----------------------------------------------------

def _catalog_entry():
    if not CATALOG.exists():
        pytest.skip(f"no catalog at {CATALOG}")
    for line in CATALOG.read_text().splitlines():
        entry = json.loads(line)
        if entry.get("name") == "Laguna-S-2.1":
            return entry
    pytest.skip("the catalog has no Laguna-S-2.1")


PER_LAYER_LISTS = ("layer_types", "mlp_layer_types", "gating_types",
                   "num_attention_heads_per_layer")


def test_the_configuration_is_the_catalogs_less_what_reduced_names():
    published = _catalog_entry()["config"]
    cell = Catalog(str(ROOT)).cell(CELL)
    sizes, mix = cell["sizes"], cell["mix"]
    assert set(published) <= set(sizes)
    differs = {k for k, v in published.items() if sizes[k] != v}
    # The lists that give each layer its kind follow the depth: their first
    # five entries, nothing else of them changed.
    assert differs == set(sizes["reduced"]) | set(PER_LAYER_LISTS)
    assert set(sizes["reduced"]) == {"num_hidden_layers", "num_experts",
                                     "vocab_size"}
    for key in PER_LAYER_LISTS:
        assert sizes[key] == published[key][:5]
    assert sizes["layer_types"] == ["full_attention"] \
        + ["sliding_attention"] * 3 + ["full_attention"]
    assert sizes["num_attention_heads_per_layer"] == [48, 72, 72, 72, 48]
    assert {k: published[k] for k in sizes["reduced"]} == {
        k: sizes["published"][k] for k in sizes["reduced"]} == {
        "num_hidden_layers": 48, "num_experts": 256, "vocab_size": 100352}
    # No width among them: depth, experts held, rows of the vocabulary.
    assert (sizes["num_hidden_layers"], sizes["num_experts"],
            sizes["vocab_size"]) == (5, 8, 100352 // 8)
    assert sizes["rope_parameters"] == published["rope_parameters"]
    assert set(sizes["reduced_why"]) == set(sizes["reduced"])
    assert sizes["source"] == _catalog_entry()["source_url"]
    for filled in ("block", "assumed", "departures", "deployment", "check"):
        assert sizes[filled]
    assert sizes["assumed"]["load_balance_coef"] == 0.001
    assert {"gate", "scoring", "qk_norm", "shared_expert_gate", "window_edge",
            "load_balance"} <= set(sizes["assumed"])
    deployment = sizes["deployment"]
    assert (deployment["chips"], deployment["expert_ranks"],
            deployment["vocab_ranks"]) == (1, 32, 8)
    assert deployment["expert_ranks"] * sizes["num_experts"] == 256
    assert "32 chips share each layer" in deployment["stands_for"]
    assert deployment["recomputation"] == "none"
    # The check keeps every width, the router's 256 outputs, the 8 held
    # experts, 10 a token, the shared expert, and one sliding and one full
    # expert layer at the cell's own row: it cuts depth and rows.
    checked = dict(sizes["check"]["sizes"])
    probes = checked.pop("probes")
    assert checked == {
        "num_hidden_layers": 2, "vocab_size": 8192, "mlp_only_layers": [],
        "layer_types": ["sliding_attention", "full_attention"],
        "mlp_layer_types": ["sparse", "sparse"],
        "gating_types": ["per_head", "per_head"],
        "num_attention_heads_per_layer": [72, 48]}
    assert set(probes) == {"held_output_rms", "update_mean_square",
                           "anchor_samples"}
    assert "probes" not in sizes
    assert sizes["check"]["steps"] == 3 and sizes["check"]["rtol"] <= 2e-4
    # Since PR 39 Adam's steps are too small to move the router inside a
    # window, so the held load stays even and the step's time with it.
    assert sizes["deployment"]["optimizer"] == {
        "name": "adam", "learning_rate": 1e-6}
    assert (mix["seq_len"], mix["rows_per_chip"], mix["masked_per_row"],
            mix["pool_batches"], mix["lag_steps"], mix["driver"]) == (
                4096, 1, 0, 64, 2, "train")
    kind = Catalog(str(ROOT)).module("kinds", KIND)
    assert kind.tokens_per_row(mix) == 4096
    cfg = kind.config(sizes)
    assert (cfg.moe.num_experts, cfg.moe.held, cfg.moe.top_k,
            cfg.moe.scoring, cfg.moe.route_scale, cfg.moe.shared) == (
                256, (0, 8), 10, "softmax", 2.5, 1)
    assert (cfg.head_dim, cfg.kv_heads, cfg.window, cfg.attn_gate,
            cfg.first_dense) == (128, 8, 512, True, 1)
    assert cfg.rope_by_type["full_attention"]["lanes"] == 64
    assert cfg.rope_by_type["full_attention"]["yarn"]["factor"] == 128.0
    assert cfg.rope_by_type["sliding_attention"] == {
        "theta": 10000.0, "lanes": 128, "yarn": None}
    # The check's sizes build too: no dense layer, sliding then full.
    small = kind.config({**sizes, **sizes["check"]["sizes"]})
    assert (small.num_layers, small.first_dense, small.heads_by_layer) == (
        2, 0, (72, 48))


def test_flops_per_token_and_the_generic_yardsticks_shape_by_hand():
    catalog = Catalog(str(ROOT))
    cell = catalog.cell(CELL)
    kind = catalog.module("kinds", KIND)
    d = 3072
    full = 2 * d * 48 * 128 + 2 * d * 8 * 128 + d * 48
    sliding = 2 * d * 72 * 128 + 2 * d * 8 * 128 + d * 72
    assert (full, sliding) == (44_187_648, 63_135_744)   # ISSUE 36's counts
    expert = 3 * d * 1024
    parts = kind.matmul_parameters(cell["sizes"])
    assert parts == {
        "attention": 2 * full + 3 * sliding, "dense_mlp": 3 * d * 12288,
        "expert_layers": 4 * (expert + d * 256 + 10 * 8 / 256 * expert),
        "head": 12544 * d}
    seen = 512 * 513 // 2 + (4096 - 512) * 512
    assert seen == 1_966_336 == kind.seen_scores(4096, 512) \
        == flops_swa.seen_scores(4096, 512)
    assert kind.seen_scores(4096) == 4096 * 4097 // 2
    assert kind.seen_scores(256, 512) == 256 * 257 // 2
    attention = 12 * 128 * (2 * 48 * 4096 * 4097 // 2
                            + 3 * 72 * seen) / 4096
    by_hand = 6 * sum(parts.values()) + attention
    assert kind.flops_per_token(cell["sizes"], cell["mix"]) \
        == pytest.approx(by_hand, rel=1e-12)
    assert by_hand == pytest.approx(3.3549e9, rel=1e-3)
    # The five attentions (projections, gate, scores) are about 64% of it,
    # the kernels' own scores 14%.
    assert (6 * parts["attention"] + attention) / by_hand \
        == pytest.approx(0.64, abs=0.01)
    assert attention / by_hand == pytest.approx(0.136, abs=0.005)
    # One shape for the generic reader whose operations are the scores seen.
    shape = kind.attention_calls(cell["sizes"], cell["mix"])
    assert shape == {"batch_heads": pytest.approx(29.326, abs=1e-3),
                     "seq_len": 4096, "head_width": 128, "causal": True}
    for kernel in flops.KERNELS:
        generic, _ = flops.attention_kernel_cost(kernel, **shape)
        real = sum(flops_swa.grouped_window_kernel_cost(
            kernel, batch=1, heads=heads, kv_heads=8, seq_len=4096,
            head_dim=128, window=window)[0]
            for heads, window in ((48, None), (72, 512), (72, 512),
                                  (72, 512), (48, None)))
        # ``seq^2 / 2`` against ``seq (seq + 1) / 2`` in the full layers.
        assert 5 * generic == pytest.approx(real, rel=3e-4)
    # A full-causal 72-head shape would say 2.5 times the work.
    assert 72 / shape["batch_heads"] == pytest.approx(2.455, abs=1e-3)


def test_grouped_window_kernel_cost_by_hand():
    shape = dict(batch=1, heads=72, kv_heads=8, seq_len=4096, head_dim=128)
    seen = 1_966_336
    for kernel, products in (("flash_fwd", 2), ("flash_bwd_dq", 3),
                             ("flash_bwd_dkv", 4)):
        ops, _ = flops_swa.grouped_window_kernel_cost(kernel, window=512,
                                                      **shape)
        assert ops == products * 2 * 72 * seen * 128
        ops, _ = flops_swa.grouped_window_kernel_cost(kernel, **shape)
        assert ops == products * 2 * 72 * (4096 * 4097 // 2) * 128
    q, kv, stat = 72 * 4096 * 128, 8 * 4096 * 128, 72 * 4096 * 4
    _, fwd = flops_swa.grouped_window_kernel_cost("flash_fwd", **shape)
    assert fwd == 2 * (q + 2 * kv) + 2 * q + stat
    _, dq = flops_swa.grouped_window_kernel_cost("flash_bwd_dq", **shape)
    assert dq == 2 * (2 * q + 2 * kv) + 2 * stat + 2 * q
    _, dkv = flops_swa.grouped_window_kernel_cost("flash_bwd_dkv", **shape)
    assert dkv == 2 * (2 * q + 2 * kv) + 2 * stat + 4 * kv
    least = [flops.roofline_seconds(*flops_swa.grouped_window_kernel_cost(
        k, window=512, **shape), V5E) for k in flops.KERNELS]
    assert [bound for _, bound in least] == ["compute"] * 3
    # 1.66 ms a sliding layer at the peak, 4.71 a full one of 48 heads.
    assert sum(s for s, _ in least) == pytest.approx(1.656e-3, rel=2e-3)
    full = sum(flops.roofline_seconds(*flops_swa.grouped_window_kernel_cost(
        k, **{**shape, "heads": 48}), V5E)[0] for k in flops.KERNELS)
    assert full == pytest.approx(4.710e-3, rel=2e-3)


def test_the_kind_refuses_what_it_does_not_implement():
    catalog = Catalog(str(ROOT))
    sizes = catalog.cell(CELL)["sizes"]
    kind = catalog.module("kinds", KIND)
    for wrong in ({"model_type": "qwen2_moe"}, {"gating": "per-token"},
                  {"norm_topk_prob": False}, {"attention_bias": True},
                  {"moe_router_logit_softcapping": 30.0},
                  {"shared_expert_intermediate_size": 2048},
                  {"mlp_only_layers": [1]},
                  {"layer_types": ["linear_attention"] * 5},
                  {"num_attention_heads_per_layer": [48, 72]}):
        with pytest.raises(ValueError, match="does not implement"):
            kind.program({**sizes, **wrong})
        with pytest.raises(ValueError, match="does not implement"):
            kind.reference_loss({**sizes, **wrong})


@pytest.mark.parametrize("name", ["reference_swa_moe.py", "flops_swa.py"])
def test_the_yardstick_imports_nothing_of_the_program(name):
    text = (ROOT / "chipbench" / name).read_text()
    assert "import autodist_tpu" not in text
    assert "from autodist_tpu" not in text
    assert "pallas" not in text


# -- the readers on made-up tables -----------------------------------------------

SPLIT = {"busy_s": 2.0, "scope": {
    "attn/window_core": 0.2, "attn/core": 0.12, "attn/rope": 0.13,
    "attn/gate": 0.05, "attn/qkv": 0.05, "attn/out": 0.03, "attn": 0.04,
    "elsewhere": 1.38}}
JOINED = {"busy_s": 2.0, "scope": {
    "attn": 0.62, "moe/router": 0.02, "moe/dispatch": 0.56,
    "moe/experts": 0.16, "moe/shared": 0.03, "mlp": 0.1, "head": 0.04,
    "optimizer": 0.32}}
SHAPES = {"window": (72, 3, 512), "full": (48, 2, None), "kv_heads": 8,
          "head_dim": 128}


def _traced_run(**trace):
    return {"trace": {"programs": 10.0, "kernel_calls": {}, **trace},
            "peak": V5E, "chips": 1, "tokens_per_s": 14650.0,
            "window_s": 35.0, "steps": 125,
            "attention": {"batch_heads": 29.326, "seq_len": 4096,
                          "head_width": 128, "causal": True}}


def test_the_scope_readers_split_attentions_row(monkeypatch):
    monkeypatch.setattr(swa_core_share, "split", lambda run: SPLIT)
    run = _traced_run()
    assert swa_core_share.read(run) == pytest.approx(10.0)
    assert gqa_core_share.read(run) == pytest.approx(6.0)
    assert attn_rope_gate_share.read(run) == pytest.approx(9.0)
    monkeypatch.setattr(swa_core_share, "split", lambda run: None)
    for reader in (swa_core_share, gqa_core_share, attn_rope_gate_share,
                   swa_kernel_roofline, gqa_kernel_roofline):
        assert reader.read(run) is None


def test_the_kernel_rooflines_count_each_kind_over_its_own_scope(
        monkeypatch, capsys):
    monkeypatch.setattr(swa_core_share, "split", lambda run: SPLIT)
    monkeypatch.setattr(swa_kernel_roofline, "program_shapes",
                        lambda: SHAPES)
    calls = {k: 50.0 for k in flops.KERNELS}        # ten steps of five layers
    run = _traced_run(kernel_calls=calls)
    # Thirty sliding calls of 1.656 ms at the peak over 0.2 s in their scope.
    assert swa_kernel_roofline.read(run) == pytest.approx(
        100 * 10 * 3 * 1.656e-3 / 0.2, rel=2e-3)
    assert gqa_kernel_roofline.read(run) == pytest.approx(
        100 * 10 * 2 * 4.710e-3 / 0.12, rel=2e-3)
    said = capsys.readouterr().out
    assert "72 heads over 8, window 512): 30 calls" in said
    assert "48 heads over 8, window None): 20 calls" in said
    # The parent's program, or another cell's, sets no such gauge.
    monkeypatch.setattr(swa_kernel_roofline, "program_shapes", lambda: None)
    assert swa_kernel_roofline.read(run) is None
    assert gqa_kernel_roofline.read(run) is None


def test_the_softmax_held_readers_report_under_their_gauges_only(monkeypatch):
    monkeypatch.setattr(program_probe, "by_scope", lambda: JOINED)
    monkeypatch.setattr(moe_softmax_held_scope_share, "softmax_held",
                        lambda: True)
    run = _traced_run()
    assert moe_softmax_held_scope_share.read(run) == pytest.approx(38.5)
    assert moe_softmax_held_dispatch_share.read(run) == pytest.approx(29.0)
    # The sigmoid-routed cell's program: the same table, other gauges.
    monkeypatch.setattr(moe_softmax_held_scope_share, "softmax_held",
                        lambda: False)
    assert moe_softmax_held_scope_share.read(run) is None
    assert moe_softmax_held_dispatch_share.read(run) is None
    assert moe_softmax_held_share.read(run) is None


def test_without_a_program_the_readers_have_nothing_to_read(monkeypatch):
    from autodist_tpu.observability import metrics
    monkeypatch.setattr(metrics, "registry", lambda: type(
        "Empty", (), {"snapshot": lambda self: {"gauges": {}}})())
    assert swa_kernel_roofline.program_shapes() is None
    assert moe_softmax_held_scope_share.softmax_held() is False
    monkeypatch.setattr(moe_load_imbalance, "last_aux", lambda: None)
    assert moe_softmax_held_share.read({"trace": None}) is None
    for reader in (swa_core_share, gqa_core_share, attn_rope_gate_share,
                   swa_kernel_roofline, gqa_kernel_roofline):
        assert reader.read({"trace": None}) is None
        assert reader.read(_traced_run()) is None       # no trace file
    # A program from before the table existed.
    from autodist_tpu.observability import profile
    monkeypatch.delattr(profile, "subscope_table")
    assert swa_core_share.read(_traced_run()) is None


def test_the_softmax_held_share_is_the_counter_over_all_assignments(
        monkeypatch):
    monkeypatch.setattr(moe_softmax_held_scope_share, "softmax_held",
                        lambda: True)
    monkeypatch.setattr(moe_held_share, "read", lambda run: 5.71)
    assert moe_softmax_held_share.read({}) == 5.71
