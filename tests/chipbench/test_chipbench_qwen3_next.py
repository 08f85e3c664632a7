"""Kind ``gdn_moe_causal_lm`` on the CPU at a toy size: its cell through the
harness, the yardstick's arithmetic by hand, the new readers on the toy run,
the configuration against the catalog's, the entries of ``BENCHMARK.json`` by
name.  Nothing here is a measurement."""
import json
import pathlib
import time

import pytest

from chipbench import flops, flops_gdn, flops_gdn_grouped, measure, \
    trace_reduce
from chipbench import run as chipbench_run
from chipbench.catalog import Catalog
from chipbench.layer_metrics import (attn_lane_gate_share,
                                     gdn_grouped_scan_roofline,
                                     moe_held_chunks_per_layer,
                                     moe_held_rows_per_expert)

from conftest import add_cell

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
CONFIG = "qwen3-next-80b-a3b"
CELL = "qwen3-next-80b-a3b.train-s8192"
TRAFFIC = "lm-s8192-r1"
KIND = "gdn_moe_causal_lm"
TOY_CELL = "tiny-qwen3-next.train-s32"
# reader, better: what the entry of each in ``BENCHMARK.json`` says.
NEW_METRICS = {
    "gdn_grouped_scan_roofline": (gdn_grouped_scan_roofline, "higher"),
    "moe_held_rows_per_expert": (moe_held_rows_per_expert, "higher"),
    "moe_held_chunks_per_layer": (moe_held_chunks_per_layer, "lower"),
    "attn_lane_gate_share": (attn_lane_gate_share, "lower")}
COUNTERS = {"moe_held_rows_per_expert", "moe_held_chunks_per_layer"}
V5E = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")


@pytest.fixture
def qwen_root(toy_root):
    """``toy_root`` with the toy cell added the same way, and the new
    readers declared for it alone."""
    add_cell(toy_root, TOY_CELL, "tiny-qwen3-next", "tiny-lm-s32", chips=8)
    path = toy_root / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    bench["per_layer"] += [
        {"name": name, "unit": reader.UNIT, "better": better,
         "source": ("program_counter" if name in COUNTERS
                    else "device_trace"),
         "layer": reader.LAYER, "moves": reader.MOVES,
         "workloads": [TOY_CELL]}
        for name, (reader, better) in NEW_METRICS.items()]
    path.write_text(json.dumps(bench))
    return toy_root


def _cell():
    """The configuration and its traffic mix, as the harness reads them."""
    return Catalog(str(ROOT)).cell(CELL)


def _run(root, trace):
    catalog = Catalog(str(root))
    return chipbench_run.run_cell(
        catalog, catalog.cell(TOY_CELL), seed=2147483777, seconds=0.5,
        trace=trace, clock0=(time.perf_counter(), measure.process_age_s()))


def test_the_toy_cell_runs_traced_and_the_program_says_its_shapes(
        qwen_root, monkeypatch):
    recorded = trace_reduce.load(DATA / "gpt2-medium.train-s1024.xplane.pb.gz")
    monkeypatch.setattr(trace_reduce, "load", lambda path: recorded)
    line = _run(qwen_root, trace=True)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    # The CPU leaves no trace of its own to join with the program's table:
    # the trace readers have nothing to read and say nothing; the counters'
    # readers read the program's aux and gauges.
    got = line["metrics"]
    assert COUNTERS <= set(got)
    assert not (set(NEW_METRICS) - COUNTERS) & set(got)
    assert not {"gdn_scope_share", "moe_softmax_held_share",
                "moe_load_imbalance", "attn_rope_gate_share"} & set(got)
    from autodist_tpu.autodist import get_default_autodist
    runner = get_default_autodist().runner
    aux = runner.last_aux
    assert float(aux["moe.dropped"]) == 0.0
    assert float(aux["gdn.state_absmax"]) > 0
    # 16 rows x 32 positions x 5 a token over 16 experts, 4 held, 2 layers.
    assert moe_held_rows_per_expert.held_layout() == (2, 4, 16 * 32 * 5)
    assert got["moe_held_rows_per_expert"]["value"] == pytest.approx(
        float(aux["moe.held_assignments"]) / (2 * 4))
    assert got["moe_held_rows_per_expert"]["unit"] == "rows"
    # The toy names its chunks (deployment.held_chunks 2: the 2,560
    # assignments of the batch as captured in chunks of 1,536 rows, three
    # whole tiles, where the default's is 512; a chip of the toy's eight
    # runs its 320 in one).
    assert got["moe_held_chunks_per_layer"]["value"] == pytest.approx(
        float(aux["moe.held_buffer_rows"]) / 1536)
    assert 0.0 < got["moe_held_chunks_per_layer"]["value"] <= 2.0
    assert gdn_grouped_scan_roofline.program_shapes() == {
        "layers": 1, "heads": 4, "key_heads": 2, "key_width": 8,
        "value_width": 8}
    from autodist_tpu.observability import profile
    split = profile.subscope_table(runner.step_text(), "attn")
    assert {"attn/qkv", "attn/rope", "attn/core", "attn/gate", "attn/out",
            "elsewhere"} <= {scope for scope, _ in split.values()}
    table = runner.scope_table()
    scopes = {scope for scope, _ in table.values()}
    assert {"attn", "head", "optimizer", "gdn/proj", "gdn/conv", "gdn/gates",
            "gdn/scan", "gdn/out", "moe/router", "moe/dispatch",
            "moe/experts", "moe/shared"} <= scopes
    # The toy recomputes its linear mixer (deployment.recomputation): what
    # the backward pass computes again keeps the mixer's rows.
    assert ("gdn/scan", "backward") in set(table.values())
    assert "rematted_computation" in runner.step_text()
    assert not {scope for scope in scopes if "rematted" in scope}


def test_the_readers_say_nothing_where_the_program_lacks_the_mechanism(
        monkeypatch):
    """A program without grouped key heads or a gated shared expert (the
    parent commit's, every other cell's) sets no such gauge: the readers
    return None and never 0, and raise nothing."""
    from autodist_tpu.observability import metrics
    monkeypatch.setattr(metrics.registry(), "snapshot",
                        lambda: {"gauges": {"gdn.heads": 30,
                                            "moe.softmax_scoring": 1,
                                            "moe.experts": 256,
                                            "moe.experts_held": 8}})
    run = {"trace": None, "tokens_per_s": 1.0, "window_s": 1.0, "steps": 1,
           "chips": 1, "peak": V5E}
    for name, (reader, _) in NEW_METRICS.items():
        if name not in ("moe_held_rows_per_expert",
                        "moe_held_chunks_per_layer"):
            assert reader.read(run) is None, name


# -- the check's number ---------------------------------------------------------

def _check(root, monkeypatch, broken=None, probes=True):
    """``drivers/train.py:reference_check`` of the toy cell, one function of
    the reference replaced by ``broken(original)`` where given."""
    import numpy as np
    from chipbench import reference_gdn_moe
    from chipbench.drivers import train
    catalog = Catalog(str(root))
    cell = catalog.cell(TOY_CELL)
    sizes, mix = cell["sizes"], cell["mix"]
    if not probes:
        sizes["check"]["sizes"].pop("probes")
    if broken is not None:
        name, make = broken
        monkeypatch.setattr(reference_gdn_moe, name,
                            make(getattr(reference_gdn_moe, name)))
    kind = catalog.module("kinds", KIND)
    rows, seed, spans = mix["rows_per_chip"] * 8, 2147483779, measure.Spans()
    batch = kind.host_batch(sizes, mix, rows, np.random.RandomState(seed))
    return train.reference_check(
        kind, sizes, mix, rows, seed,
        train.Sessions(kind, batch, seed, spans), spans)


def _no_held_experts(held_experts):
    return lambda p, x, weights: held_experts(p, x, 0.0 * weights)


def _heads_modulo(key_head_of):
    def broken(heads, key_heads):
        import jax.numpy as jnp
        return jnp.arange(heads) % key_heads
    return broken


@pytest.mark.parametrize("broken, probes, ok", [
    (None, True, True),
    (("held_experts", _no_held_experts), True, False),
    # A fault of the rule re-draws what the state holds: at the toy's 32
    # positions the loss reads it (tests/test_qwen3_next.py holds the
    # grouping against the same reference, exactly).
    (("key_head_of", _heads_modulo), True, False)],
    ids=["sound", "no-held-experts", "key-heads-modulo"])
def test_the_checks_number_sees_the_faults_it_is_held_to(
        qwen_root, monkeypatch, broken, probes, ok):
    check = _check(qwen_root, monkeypatch, broken, probes)
    assert check["ok"] is ok, check
    assert ("probes" in check["sizes_checked"]) is probes
    # The probes alone make the check's sizes a session of its own.
    assert check["shared_session"] is (not probes)


# -- the entries, by name ------------------------------------------------------

def _bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_the_new_readers_are_declared_for_the_new_cell_only():
    declared = {m["name"]: m for m in _bench()["per_layer"]}
    readers = {m.NAME: m for m in Catalog(str(ROOT)).layer_metrics()}
    for name, (reader, better) in NEW_METRICS.items():
        metric = declared[name]
        assert readers[name].NAME == reader.NAME == name
        assert metric == {
            "name": name, "unit": reader.UNIT, "better": better,
            "source": ("program_counter" if name in COUNTERS
                       else "device_trace"),
            "layer": reader.LAYER, "moves": "tokens_per_s",
            "workloads": [CELL]}
        assert reader.MOVES == "tokens_per_s"
    assert (gdn_grouped_scan_roofline.LAYER, gdn_grouped_scan_roofline.UNIT,
            NEW_METRICS["gdn_grouped_scan_roofline"][1]) == (
                "Kernels", "%", "higher")
    assert moe_held_rows_per_expert.UNIT == "rows"
    assert moe_held_chunks_per_layer.UNIT == "count"
    # No older list is appended to (the older cells' tests pin theirs): the
    # cell's only per-layer metrics with a list are the new ones.
    assert {m["name"] for m in declared.values()
            if CELL in m.get("workloads", ())} == set(NEW_METRICS)
    catalog = Catalog(str(ROOT))
    wanted = {m["name"] for m in catalog.metric_specs("per_layer", CELL)}
    assert set(NEW_METRICS) | {"attn_kernel_roofline", "mfu"} <= wanted
    assert not {"gdn_scan_roofline", "gqa_kernel_roofline", "moe_held_share",
                "collective_share"} & wanted
    for other in ("gpt2-medium.train-s1024", "olmo-hybrid-7b.train-s4096",
                  "joyai-llm-flash.train-s4096", "laguna-s-2.1.train-s4096"):
        assert not set(NEW_METRICS) & {
            m["name"] for m in catalog.metric_specs("per_layer", other)}


def test_the_cell_and_its_configuration_are_declared():
    bench = _bench()
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    assert cells[CELL] == {**cells[CELL], "config": CONFIG,
                           "traffic": TRAFFIC, "chips": 1}
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == CONFIG
    assert configs[CONFIG]["source"] == (
        "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/"
        "config.json")
    assert configs[CONFIG]["file"] == f"chipbench/configs/{CONFIG}.json"
    assert configs[CONFIG]["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    for line in (cells[CELL]["why"], configs[CONFIG]["why"],
                 configs[CONFIG]["source"]):
        assert 1 <= len(line) <= 200 and "\n" not in line and "\t" not in line
    assert set(cells[CELL]) == {"name", "config", "traffic", "chips", "why"}
    # A quarter of the cells, rounded down, and always one, may take four.
    assert len(cells) == 9
    assert sum(w["chips"] == 4 for w in cells.values()) == 1
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


# -- the configuration file ----------------------------------------------------

def _catalog_entry():
    if not CATALOG.exists():
        pytest.skip(f"no catalog at {CATALOG}")
    for line in CATALOG.read_text().splitlines():
        entry = json.loads(line)
        if entry.get("name") == "Qwen3-Next-80B-A3B-Instruct":
            return entry
    pytest.skip("the catalog has no Qwen3-Next-80B-A3B-Instruct")


def test_the_configuration_is_the_catalogs_less_what_reduced_names():
    published = _catalog_entry()["config"]
    cell = _cell()
    sizes, mix = cell["sizes"], cell["mix"]
    assert sizes["kind"] == KIND
    assert set(published) <= set(sizes)
    differs = {k for k, v in published.items() if sizes[k] != v}
    assert differs == set(sizes["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert {k: published[k] for k in sizes["reduced"]} == {
        k: sizes["published"][k] for k in sizes["reduced"]} == {
        "num_hidden_layers": 48, "num_experts": 512, "vocab_size": 151936}
    # No width among them: depth, experts held, rows of the vocabulary.
    assert (sizes["num_hidden_layers"], sizes["num_experts"],
            sizes["vocab_size"]) == (4, 32, 151936 // 8)
    assert set(sizes["reduced_why"]) == set(sizes["reduced"])
    assert sizes["source"] == _catalog_entry()["source_url"]
    for filled in ("block", "assumed", "departures", "deployment", "check"):
        assert sizes[filled]
    assert sizes["assumed"]["load_balance_coef"] == 0.001
    assert {"published_code", "multi_token_prediction", "load_balance",
            "norm_scale", "initialisation"} <= set(sizes["assumed"])
    deployment = sizes["deployment"]
    assert (deployment["chips"], deployment["expert_ranks"],
            deployment["vocab_ranks"]) == (1, 16, 8)
    assert deployment["expert_ranks"] * sizes["num_experts"] == 512
    assert "16 chips share each layer" in deployment["stands_for"]
    # The linear mixers recomputed: without, the step does not fit the chip.
    assert deployment["recomputation"] == "linear_mixer"
    assert "17,704,496,640" in deployment["recomputation_why"]
    assert "16,061,370,368" in deployment["recomputation_why"]
    assert deployment["steadiness"]
    # A chunk of the held loop is 7,168 rows, not the even share of 5,120.
    assert deployment["held_chunks"] == 12 and deployment["held_chunks_why"]
    assert deployment["optimizer"] == {"name": "adam", "learning_rate": 1e-6}
    # The check keeps every width, the router's 512 outputs, the 32 held
    # experts, 10 a token, the gated shared expert, the whole slice of the
    # vocabulary and one linear and one full layer at the cell's own row.
    checked = dict(sizes["check"]["sizes"])
    probes = checked.pop("probes")
    assert checked == {"num_hidden_layers": 2, "full_attention_interval": 2}
    assert set(probes) == {"held_output_rms", "update_mean_square",
                           "attn_output_std", "gdn_output_std",
                           "anchor_samples"}
    assert "probes" not in sizes
    assert sizes["check"]["steps"] == 3 and sizes["check"]["rtol"] <= 2e-4
    assert (mix["seq_len"], mix["rows_per_chip"], mix["masked_per_row"],
            mix["pool_batches"], mix["lag_steps"], mix["driver"]) == (
                8192, 1, 0, 64, 2, "train")
    kind = Catalog(str(ROOT)).module("kinds", KIND)
    assert kind.tokens_per_row(mix) == 8192
    assert kind.layer_types(sizes) == ["linear_attention"] * 3 \
        + ["full_attention"]
    cfg = kind.config(sizes)
    assert (cfg.moe.num_experts, cfg.moe.held, cfg.moe.top_k,
            cfg.moe.scoring, cfg.moe.route_scale, cfg.moe.shared,
            cfg.moe.shared_gate, cfg.moe.held_chunks) == (
                512, (0, 32), 10, "softmax", 1.0, 1, True, 12)
    assert (cfg.recompute, cfg.mixer_stats) == ("linear_mixer", False)
    assert (cfg.linear_heads, cfg.linear_key_heads, cfg.linear_key_dim,
            cfg.linear_value_dim, cfg.allow_neg_eigval) == (32, 16, 128, 128,
                                                            False)
    assert (cfg.head_dim, cfg.kv_heads, cfg.num_heads, cfg.attn_gate,
            cfg.qk_norm, cfg.first_dense, cfg.norm_position) == (
                256, 2, 16, "lane", "head", 0, "pre")
    assert cfg.rope_by_type == {"full_attention": {
        "theta": 1e7, "lanes": 64, "yarn": None}}
    # The file and the library's builder of the same model say one thing.
    from autodist_tpu.models import lm
    built = lm.qwen3_next_80b_a3b(num_layers=4, vocab=18992,
                                  experts_held=(0, 32),
                                  experts_held_chunks=12)

    def fields(config):
        return {k: vars(v) if k == "moe" else v
                for k, v in vars(config).items()}
    assert fields(cfg) == {**fields(built), "recompute": "linear_mixer"}
    small = kind.config({**sizes, **sizes["check"]["sizes"]})
    assert small.layer_types == ("linear_attention", "full_attention")
    assert small.mixer_stats is True
    import jax
    count = sum(x.size for x in jax.tree_util.tree_leaves(jax.eval_shape(
        kind.program(sizes)[0], jax.random.PRNGKey(0))))
    assert count == 625_667_136
    assert "625,667,136" in sizes["assumed"]["parameters"]


def test_flops_per_token_and_the_generic_yardsticks_shape_by_hand():
    catalog = Catalog(str(ROOT))
    cell = _cell()
    kind = catalog.module("kinds", KIND)
    d = 2048
    linear = d * (2 * 16 * 128 + 2 * 32 * 128 + 2 * 32) + 32 * 128 * d
    full = d * (2 * 16 * 256 + 2 * 2 * 256) + 16 * 256 * d
    # ISSUE 40's counts, less the norms, the taps, A_log and dt_bias.
    assert linear == 33_718_464 - 4 * 8192 - 64 - 128
    assert full == 27_263_488 - 512
    expert = 3 * d * 512
    parts = kind.matmul_parameters(cell["sizes"])
    assert parts == {
        "linear_mixers": 3 * linear, "full_mixers": full,
        "expert_layers": 4 * (expert + d + d * 512 + 10 * 32 / 512 * expert),
        "head": 18992 * d}
    attention = 12 * 8192 * 16 * 256 / 2
    rule = 3 * 18 * 32 * 128 * 128
    by_hand = 6 * sum(parts.values()) + attention + rule
    assert kind.flops_per_token(cell["sizes"], cell["mix"]) \
        == pytest.approx(by_hand, rel=1e-12)
    assert by_hand == 1_380_827_136
    # The scores of the one full layer are 15% of it, the recurrence 2%.
    assert attention / by_hand == pytest.approx(0.146, abs=0.002)
    assert rule / by_hand == pytest.approx(0.021, abs=0.001)
    shape = kind.attention_calls(cell["sizes"], cell["mix"])
    assert shape == {"batch_heads": 16, "seq_len": 8192, "head_width": 256,
                     "causal": True}
    least = [flops.roofline_seconds(*flops.attention_kernel_cost(k, **shape),
                                    V5E) for k in flops.KERNELS]
    assert [bound for _, bound in least] == ["compute"] * 3
    assert sum(s for s, _ in least) == pytest.approx(12.56e-3, rel=2e-3)


def test_the_grouped_scans_cost_by_hand():
    shape = dict(positions=8192, heads=32, key_heads=16, key_width=128,
                 value_width=128)
    ops, nbytes = flops_gdn_grouped.scan_cost("forward", **shape)
    assert ops == 6 * 8192 * 32 * 128 * 128
    # q, k a key head; v, o and the two f32 gates a value head.
    assert nbytes == 8192 * (16 * 2 * 2 * 128 + 32 * (2 * 2 * 128 + 8))
    ops, nbytes = flops_gdn_grouped.scan_cost("backward", **shape)
    assert ops == 12 * 8192 * 32 * 128 * 128
    assert nbytes == 8192 * (16 * 4 * 2 * 128 + 32 * (3 * 2 * 128 + 16))
    # With as many key heads as heads it is the count of equal heads.
    for phase in flops_gdn_grouped.PHASES:
        equal = dict(positions=4096, heads=30, key_width=96, value_width=192)
        assert flops_gdn_grouped.scan_cost(phase, key_heads=30, **equal) \
            == flops_gdn.scan_cost(phase, **equal)
    least = [flops.roofline_seconds(
        *flops_gdn_grouped.scan_cost(phase, **shape), V5E)
        for phase in flops_gdn_grouped.PHASES]
    assert [bound for _, bound in least] == ["memory"] * 2
    assert sum(s for s, _ in least) == pytest.approx(663.2e-6, rel=2e-3)


def test_the_kind_refuses_what_it_does_not_implement():
    catalog = Catalog(str(ROOT))
    sizes = _cell()["sizes"]
    kind = catalog.module("kinds", KIND)
    for wrong in ({"model_type": "qwen2_moe"}, {"norm_topk_prob": False},
                  {"mlp_only_layers": [0]}, {"decoder_sparse_step": 2},
                  {"shared_expert_intermediate_size": 1024},
                  {"linear_num_key_heads": 12},
                  {"full_attention_interval": 3},
                  {"rope_scaling": {"type": "yarn"}}):
        with pytest.raises(ValueError, match="does not implement"):
            kind.program({**sizes, **wrong})
        with pytest.raises(ValueError, match="does not implement"):
            kind.reference_loss({**sizes, **wrong})


@pytest.mark.parametrize("name", ["reference_gdn_moe.py",
                                  "flops_gdn_grouped.py"])
def test_the_yardstick_imports_nothing_of_the_program(name):
    text = (ROOT / "chipbench" / name).read_text()
    assert "import autodist_tpu" not in text
    assert "from autodist_tpu" not in text
    assert "pallas" not in text
