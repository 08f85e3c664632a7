"""Kind ``mla_moe_causal_lm`` on the CPU at a toy size: its cell through the
harness, the yardstick's arithmetic by hand, the five new readers on made-up
tables, the configuration against the catalog's.  Every entry of
``BENCHMARK.json`` is looked up by its name: no test here says where in a
list an entry stands or how many there are.  Nothing here is a measurement."""
import json
import pathlib
import time

import pytest

from chipbench import flops, flops_mla, measure, program_probe, trace_reduce
from chipbench import run as chipbench_run
from chipbench.catalog import Catalog
from chipbench.layer_metrics import (mla_kernel_roofline,
                                     moe_held_dispatch_share,
                                     moe_held_scope_share, moe_held_share,
                                     moe_load_imbalance, mtp_scope_share)

from conftest import add_cell

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
CELL = "joyai-llm-flash.train-s4096"
CONFIG = "joyai-llm-flash"
KIND = "mla_moe_causal_lm"
TOY_CELL = "tiny-joyai.train-s32"
NEW_METRICS = {"mla_kernel_roofline": mla_kernel_roofline,
               "moe_held_scope_share": moe_held_scope_share,
               "moe_held_dispatch_share": moe_held_dispatch_share,
               "moe_held_share": moe_held_share,
               "mtp_scope_share": mtp_scope_share}
V5E = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")


@pytest.fixture
def joyai_root(toy_root):
    """``toy_root`` with the toy cell added the same way, and named in the
    ``workloads`` of every metric that lists the real cell."""
    add_cell(toy_root, TOY_CELL, "tiny-joyai", "tiny-lm-s32-mtp1", chips=8)
    path = toy_root / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    for metric in bench["per_layer"]:
        if metric["name"] in NEW_METRICS or CELL in metric.get("workloads",
                                                                ()):
            metric["workloads"].append(TOY_CELL)
    path.write_text(json.dumps(bench))
    return toy_root


def _run(root, trace):
    catalog = Catalog(str(root))
    return chipbench_run.run_cell(
        catalog, catalog.cell(TOY_CELL), seed=2147483777, seconds=0.5,
        trace=trace, clock0=(time.perf_counter(), measure.process_age_s()))


def test_the_toy_cell_runs_untraced(joyai_root):
    line = _run(joyai_root, trace=False)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == {"tokens_per_s", "step_ms_p90", "setup_s"}


def test_the_toy_cell_runs_traced_and_the_step_moves_the_biases(
        joyai_root, monkeypatch):
    recorded = trace_reduce.load(DATA / "gpt2-medium.train-s1024.xplane.pb.gz")
    monkeypatch.setattr(trace_reduce, "load", lambda path: recorded)
    line = _run(joyai_root, trace=True)
    assert line["correct"] is True
    # The CPU leaves no trace of its own to join with the program's table:
    # the trace readers have nothing to read and say nothing; the counter's
    # reader reads the program's aux.
    # (``mla_kernel_roofline`` finds the recorded TPU trace's kernels.)
    assert {"moe_held_share", "moe_load_imbalance"} <= set(line["metrics"])
    assert line["metrics"]["moe_load_imbalance"]["value"] >= 1.0
    assert not {"moe_held_scope_share", "moe_held_dispatch_share",
                "mtp_scope_share"} & set(line["metrics"])
    assert 0.0 <= line["metrics"]["moe_held_share"]["value"] <= 100.0
    from autodist_tpu.autodist import get_default_autodist
    runner = get_default_autodist().runner
    assert not runner.program.use_explicit_path
    aux = runner.last_aux
    assert float(aux["moe.dropped"]) == 0.0 and "state_updates" not in aux
    assert 0.001 <= float(aux["moe.bias_absmax"]) < 0.1
    assert float(aux["mtp.xent"]) > 0.0
    assert mla_kernel_roofline.program_widths() == {
        "heads": 4, "nope": 16, "rope": 8, "value": 24}
    scopes = {scope for scope, _ in runner.scope_table().values()}
    assert {"attn", "mlp", "head", "optimizer", "moe/router", "moe/dispatch",
            "moe/experts", "moe/shared", "mtp/proj"} <= scopes
    from autodist_tpu.observability import profile
    overlay = profile.overlay_table(runner.step_text(), "mtp")
    assert {"mtp", "elsewhere"} <= {scope for scope, _ in overlay.values()}


# -- the check's number ---------------------------------------------------------

def _check(root, monkeypatch, route=None, probes=True):
    """``drivers/train.py:reference_check`` of the toy cell, the reference's
    router replaced by ``route(original)`` where given."""
    import numpy as np
    from chipbench import reference_mla_moe
    from chipbench.drivers import train
    catalog = Catalog(str(root))
    cell = catalog.cell(TOY_CELL)
    sizes, mix = cell["sizes"], cell["mix"]
    if not probes:
        sizes["check"]["sizes"].pop("probes")
    if route is not None:
        monkeypatch.setattr(reference_mla_moe, "route",
                            route(reference_mla_moe.route))
    kind = catalog.module("kinds", KIND)
    rows, seed, spans = mix["rows_per_chip"] * 8, 2147483779, measure.Spans()
    batch = kind.host_batch(sizes, mix, rows, np.random.RandomState(seed))
    return train.reference_check(
        kind, sizes, mix, rows, seed,
        train.Sessions(kind, batch, seed, spans), spans)


def _no_routed_experts(route):
    def broken(p, x, **kw):
        weights, chosen, scores = route(p, x, **kw)
        return 0.0 * weights, chosen, scores
    return broken


def _weights_over_the_held_only(route):
    def broken(p, x, *, top_k, route_scale):
        weights, chosen, scores = route(p, x, top_k=top_k,
                                        route_scale=route_scale)
        held = weights[..., 4:8]          # the toy's rank 1 of 4
        return weights.at[..., 4:8].set(
            route_scale * held / (held.sum(-1, keepdims=True) + 1e-9)), \
            chosen, scores
    return broken


@pytest.mark.parametrize("route, probes, ok", [
    (None, True, True),
    (_no_routed_experts, True, False),
    (_weights_over_the_held_only, True, False),
    # What the probes are for: the loss alone lets both through.
    (_no_routed_experts, False, True),
    (_weights_over_the_held_only, False, True)])
def test_the_checks_number_sees_the_routed_experts(joyai_root, monkeypatch,
                                                   route, probes, ok):
    check = _check(joyai_root, monkeypatch, route, probes)
    assert check["ok"] is ok, check
    assert ("probes" in check["sizes_checked"]) is probes


def _moved(values, by):
    import jax
    return jax.tree_util.tree_map(lambda x: x + by, values)


def test_the_update_probe_reads_steps_of_the_learning_rate():
    """0 before a step, 1 after one Adam step's worth, less where a
    variable stayed, other where the values are kept in bfloat16; the
    selection biases and the anchor itself are not counted."""
    import jax
    import jax.numpy as jnp
    kind = Catalog(str(ROOT)).module("kinds", KIND)
    rate, samples = 1e-4, 64
    values = {"a": {"kernel": 0.027 * jnp.ones((16, 40))},
              "b": {"kernel": -0.02 * jnp.ones((300,)),
                    "bias": jnp.zeros((8,))}}
    anchor = jax.tree_util.tree_map(lambda x: kind._sample(x, samples),
                                    values)
    assert anchor["a"]["kernel"].shape == (64,)     # 640 entries, every 10th
    assert anchor["b"]["kernel"].shape == (75,)     # 300 entries, every 4th

    def read(v):
        return float(kind.update_mean_square({**v, kind.ANCHOR: anchor},
                                             rate, samples))
    assert read(values) == 0.0
    stepped = _moved(values, -rate)
    assert read(stepped) == pytest.approx(1.0, rel=1e-3)
    assert read({**stepped, "a": values["a"]}) == pytest.approx(0.5,
                                                                rel=1e-3)
    moved_bias = {**values, "b": {**values["b"],
                                  "bias": values["b"]["bias"] + 0.001}}
    assert read(moved_bias) == 0.0
    in_bf16 = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16).astype(jnp.float32), stepped)
    assert abs(read(in_bf16) - 1.0) > 0.2


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
    assert declared["mla_kernel_roofline"]["layer"] == "Kernels"
    assert declared["mla_kernel_roofline"]["better"] == "higher"
    assert declared["moe_held_share"]["source"] == "program_counter"
    # Appended to the one older list whose reader finds its counter here
    # (``aux["moe.load_max_over_mean"]``, over all the router's experts).
    assert declared["moe_load_imbalance"]["workloads"][-1] == CELL
    assert {m["name"] for m in declared.values()
            if CELL in m.get("workloads", ())} == set(NEW_METRICS) | {
                "moe_load_imbalance"}
    catalog = Catalog(str(ROOT))
    wanted = {m["name"] for m in catalog.metric_specs("per_layer", CELL)}
    assert set(NEW_METRICS) <= wanted
    assert not {"moe_scope_share", "gdn_scope_share",
                "collective_share"} & wanted
    for other in ("gpt2-medium.train-s1024", "olmoe-1b-7b.train-s4096",
                  "olmo-hybrid-7b.train-s4096"):
        assert not set(NEW_METRICS) & {
            m["name"] for m in catalog.metric_specs("per_layer", other)}


def test_the_cell_and_its_configuration_are_declared():
    bench = _bench()
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    assert cells[CELL] == {**cells[CELL], "config": CONFIG,
                           "traffic": "lm-s4096-r1-mtp1", "chips": 1}
    assert configs[CONFIG]["source"] == (
        "https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/"
        "config.json")
    assert configs[CONFIG]["file"] == "chipbench/configs/joyai-llm-flash.json"
    assert configs[CONFIG]["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
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
        if entry.get("name") == "JoyAI-LLM-Flash":
            return entry
    pytest.skip("the catalog has no JoyAI-LLM-Flash")


def test_the_configuration_is_the_catalogs_less_what_reduced_names():
    published = _catalog_entry()["config"]
    cell = Catalog(str(ROOT)).cell(CELL)
    sizes, mix = cell["sizes"], cell["mix"]
    assert set(published) <= set(sizes)
    differs = {k for k, v in published.items() if sizes[k] != v}
    assert differs == set(sizes["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert {k: published[k] for k in differs} == {
        k: sizes["published"][k] for k in differs} == {
        "num_hidden_layers": 40, "n_routed_experts": 256,
        "vocab_size": 129280}
    # No width among them: depth, experts held, rows of the vocabulary.
    assert (sizes["num_hidden_layers"], sizes["n_routed_experts"],
            sizes["vocab_size"]) == (5, 16, 129280 // 8)
    assert set(sizes["reduced_why"]) == set(sizes["reduced"])
    assert sizes["source"] == _catalog_entry()["source_url"]
    for filled in ("block", "assumed", "departures", "deployment", "check"):
        assert sizes[filled]
    assert (sizes["assumed"]["bias_update_rate"],
            sizes["assumed"]["sequence_balance_coef"],
            sizes["assumed"]["mtp_loss_coef"]) == (0.001, 1e-4, 0.3)
    assert "rope_pairing" in sizes["assumed"]
    deployment = sizes["deployment"]
    assert (deployment["chips"], deployment["expert_ranks"],
            deployment["vocab_ranks"]) == (1, 16, 8)
    assert deployment["expert_ranks"] * sizes["n_routed_experts"] == 256
    assert "16 chips share each layer" in deployment["stands_for"]
    # The check keeps every width, the router's 256 outputs, the 16 held
    # experts, 8 a token and the module: it cuts depth and rows.
    checked = dict(sizes["check"]["sizes"])
    probes = checked.pop("probes")
    assert checked == {"num_hidden_layers": 2, "vocab_size": 8192}
    # Beside the loss, at weights that leave the number the loss's own size.
    assert set(probes) == {"held_output_rms", "update_mean_square",
                           "anchor_samples"}
    assert 0 < probes["held_output_rms"] * 0.004 < 0.1
    assert 0 < probes["update_mean_square"] * 2.25 < 1.0
    assert "probes" not in sizes
    assert sizes["check"]["steps"] == 3 and sizes["check"]["rtol"] <= 2e-4
    # Since PR 39 Adam's steps are too small to move the router inside a
    # window, so the held load stays even and the step's time with it.
    assert sizes["deployment"]["optimizer"] == {
        "name": "adam", "learning_rate": 1e-6}
    assert (mix["seq_len"], mix["rows_per_chip"], mix["targets_ahead"],
            mix["masked_per_row"], mix["pool_batches"], mix["lag_steps"],
            mix["driver"]) == (4096, 1, 2, 0, 64, 2, "train")
    kind = Catalog(str(ROOT)).module("kinds", KIND)
    assert kind.attention_calls(sizes, mix) == {
        "batch_heads": 32, "seq_len": 4096, "head_width": 160,
        "causal": True}
    assert kind.tokens_per_row(mix) == 4096
    cfg = kind.config(sizes)
    assert (cfg.moe.num_experts, cfg.moe.held, cfg.moe.top_k) == (
        256, (0, 16), 8)


def test_flops_per_token_by_hand():
    catalog = Catalog(str(ROOT))
    cell = catalog.cell(CELL)
    kind = catalog.module("kinds", KIND)
    d, heads = 2048, 32
    mla = d * 1536 + 1536 * heads * 192 + d * 576 + 512 * heads * 256 \
        + heads * 128 * d
    assert mla == 26_345_472                 # 26,347,520 less the two norms
    expert = 3 * d * 768
    parts = kind.matmul_parameters(cell["sizes"])
    assert parts == {
        "latent_attention": 6 * mla, "dense_mlp": 3 * d * 7168,
        "expert_layers": 5 * (expert + d * 256 + 8 * 16 / 256 * expert),
        "mtp_projection": 2 * d * d, "head": 2 * 16160 * d}
    attention = 6 * 6 * 4096 * heads * (192 + 128) // 2
    assert attention == 754_974_720
    by_hand = 6 * sum(parts.values()) + attention
    assert kind.flops_per_token(cell["sizes"], cell["mix"]) == by_hand
    assert by_hand == pytest.approx(2.643e9, rel=1e-3)
    # Six MLA blocks are about two thirds of it.
    assert (6 * 6 * mla + attention) / by_hand == pytest.approx(0.644,
                                                                abs=0.005)


def test_two_product_kernel_cost_by_hand():
    shape = dict(batch=1, heads=32, seq_len=4096, nope=128, rope=64,
                 value=128)
    area = 32 * 4096 * 4096       # a head's score tile, halved by the mask
    for kernel, lanes in (("flash_fwd", 192 + 128),
                          ("flash_bwd_dq", 192 + 128 + 192),
                          ("flash_bwd_dkv", 192 + 128 + 128 + 192)):
        ops, _ = flops_mla.two_product_kernel_cost(kernel, **shape)
        assert ops == 2 * area * lanes // 2
    a_head, shared = 32 * 4096, 4096
    read = 2 * (a_head * (192 + 128 + 128) + shared * 64)   # q, k_nope, v; k_r
    _, fwd = flops_mla.two_product_kernel_cost("flash_fwd", **shape)
    assert fwd == read + 2 * a_head * 128 + 4 * a_head
    _, dq = flops_mla.two_product_kernel_cost("flash_bwd_dq", **shape)
    assert dq == read + 2 * a_head * 128 + 8 * a_head + 2 * a_head * 192
    _, dkv = flops_mla.two_product_kernel_cost("flash_bwd_dkv", **shape)
    assert dkv == read + 2 * a_head * 128 + 8 * a_head \
        + 2 * (a_head * 256 + shared * 64)
    least = [flops.roofline_seconds(
        *flops_mla.two_product_kernel_cost(k, **shape), V5E)
        for k in flops.KERNELS]
    assert [bound for _, bound in least] == ["compute"] * 3
    # 4.01 ms a layer at the peak; the generic yardstick at 160 lanes says
    # 3.93 (its dq is 6% low).
    assert sum(s for s, _ in least) == pytest.approx(4.011e-3, rel=2e-3)
    generic = sum(flops.roofline_seconds(*flops.attention_kernel_cost(
        k, batch_heads=32, seq_len=4096, head_width=160, causal=True),
        V5E)[0] for k in flops.KERNELS)
    assert generic == pytest.approx(3.924e-3, rel=2e-3)


def test_the_kind_refuses_what_it_does_not_implement():
    catalog = Catalog(str(ROOT))
    sizes = catalog.cell(CELL)["sizes"]
    kind = catalog.module("kinds", KIND)
    for wrong in ({"scoring_func": "softmax"}, {"rope_interleave": False},
                  {"n_group": 8}, {"n_shared_experts": 2},
                  {"num_nextn_predict_layers": 0}, {"qk_head_dim": 128},
                  {"rope_scaling": {"type": "yarn"}},
                  {"first_k_dense_replace": 0}, {"num_key_value_heads": 8}):
        with pytest.raises(ValueError, match="does not implement"):
            kind.program({**sizes, **wrong})
        with pytest.raises(ValueError, match="does not implement"):
            kind.reference_loss({**sizes, **wrong})


@pytest.mark.parametrize("name", ["reference_mla_moe.py", "flops_mla.py"])
def test_the_yardstick_imports_nothing_of_the_program(name):
    text = (ROOT / "chipbench" / name).read_text()
    assert "import autodist_tpu" not in text
    assert "from autodist_tpu" not in text
    assert "pallas" not in text


# -- the readers on made-up tables -----------------------------------------------

JOINED = {"busy_s": 2.0, "scope": {
    "attn": 0.9, "moe/router": 0.02, "moe/dispatch": 0.18,
    "moe/experts": 0.06, "moe/shared": 0.04, "mlp": 0.1, "head": 0.2,
    "optimizer": 0.4, "mtp/proj": 0.01}}


def _traced_run(**trace):
    return {"trace": {"programs": 10.0, **trace}, "peak": V5E, "chips": 1,
            "attention": {"batch_heads": 32, "seq_len": 4096,
                          "head_width": 160, "causal": True}}


def test_the_scope_readers_add_up_the_held_layers_scopes(monkeypatch):
    monkeypatch.setattr(program_probe, "by_scope", lambda: JOINED)
    assert moe_held_scope_share.read(_traced_run()) == pytest.approx(15.0)
    assert moe_held_dispatch_share.read(_traced_run()) == pytest.approx(10.0)
    for reader in (moe_held_scope_share, moe_held_dispatch_share):
        assert reader.read({"trace": None}) is None
    # OLMoE's table has no shared expert: nothing to read there.
    olmoe = {"busy_s": 2.0, "scope": {"moe/router": 0.1, "moe/experts": 0.5}}
    monkeypatch.setattr(program_probe, "by_scope", lambda: olmoe)
    assert moe_held_scope_share.read(_traced_run()) is None
    assert moe_held_dispatch_share.read(_traced_run()) is None
    monkeypatch.setattr(program_probe, "by_scope", lambda: None)
    assert moe_held_scope_share.read(_traced_run()) is None


def test_the_kernel_roofline_reader_counts_the_products_really_made(
        monkeypatch, capsys):
    monkeypatch.setattr(mla_kernel_roofline, "program_widths", lambda: {
        "heads": 32, "nope": 128, "rope": 64, "value": 128})
    calls = {k: 60.0 for k in flops.KERNELS}        # ten steps of six layers
    seconds = {"flash_fwd": 0.12, "flash_bwd_dq": 0.2, "flash_bwd_dkv": 0.28}
    run = _traced_run(kernel_calls=calls, kernel_seconds=seconds)
    assert mla_kernel_roofline.read(run) == pytest.approx(
        100 * 60 * 4.011e-3 / 0.6, rel=2e-3)
    assert "two-product" in capsys.readouterr().out
    # The parent's program, or another cell's, sets no such gauge.
    monkeypatch.setattr(mla_kernel_roofline, "program_widths", lambda: None)
    assert mla_kernel_roofline.read(run) is None
    assert mla_kernel_roofline.read({"trace": None}) is None


def test_without_a_program_the_readers_have_nothing_to_read(monkeypatch):
    from autodist_tpu.observability import metrics
    monkeypatch.setattr(metrics, "registry", lambda: type(
        "Empty", (), {"snapshot": lambda self: {"gauges": {}}})())
    assert mla_kernel_roofline.program_widths() is None
    monkeypatch.setattr(moe_load_imbalance, "last_aux", lambda: None)
    assert moe_held_share.read({"trace": None}) is None
    assert mtp_scope_share.read({"trace": None}) is None
    assert mtp_scope_share.read(_traced_run()) is None      # no trace file


def test_the_held_share_is_the_counter_over_all_assignments(monkeypatch):
    monkeypatch.setattr(moe_load_imbalance, "last_aux",
                        lambda: {"moe.load_max_over_mean": 2.0})
    assert moe_held_share.read({}) is None      # OLMoE's aux has no such key
