"""Kind ``moe_causal_lm`` on the CPU at a toy size: its cell through the
harness, the grouped products' arithmetic by hand, the four ``moe_*``
readers on a made-up table, and the check telling each of four broken
programs from the right one.  Nothing here is a measurement."""
import json
import pathlib
import re
import shutil
import time

import jax
import numpy as np
import pytest

from chipbench import flops, flops_moe, measure, program_probe, trace_reduce
from chipbench import run as chipbench_run
from chipbench.catalog import Catalog
from chipbench.layer_metrics import (moe_dispatch_share,
                                     moe_expert_matmul_roofline,
                                     moe_load_imbalance, moe_scope_share)

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
CELL = "olmoe-1b-7b.train-s4096"
TOY_CELL = "tiny-olmoe.train-s32"
MOE_METRICS = {"moe_scope_share", "moe_dispatch_share",
               "moe_expert_matmul_roofline", "moe_load_imbalance"}
V5E = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture
def olmoe_root(toy_root):
    """``toy_root`` with the toy OLMoE cell added the same way, and named
    in the ``workloads`` of the four metrics that list their cells."""
    shutil.copy(DATA / "tiny-olmoe.json", toy_root / "chipbench" / "configs")
    path = toy_root / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    bench["configs"].append({
        "name": "tiny-olmoe", "source": "none", "reduced": [], "why": "toy",
        "file": "chipbench/configs/tiny-olmoe.json"})
    bench["workloads"].append({
        "name": TOY_CELL, "config": "tiny-olmoe", "traffic": "tiny-lm-s32",
        "chips": 8, "why": "toy"})
    for metric in bench["per_layer"]:
        if metric["name"] in MOE_METRICS:
            metric["workloads"].append(TOY_CELL)
    path.write_text(json.dumps(bench))
    return toy_root


def _run(root, trace):
    catalog = Catalog(str(root))
    return chipbench_run.run_cell(
        catalog, catalog.cell(TOY_CELL), seed=2147483659, seconds=0.5,
        trace=trace, clock0=(time.perf_counter(), measure.process_age_s()))


def test_the_toy_cell_runs_untraced(olmoe_root):
    line = _run(olmoe_root, trace=False)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == {"tokens_per_s", "step_ms_p90", "setup_s"}
    assert line["device"]["memory_peak_bytes"] > 0


def test_the_toy_cell_runs_traced_and_reads_the_programs_aux(
        olmoe_root, monkeypatch):
    recorded = trace_reduce.load(DATA / "gpt2-medium.train-s1024.xplane.pb.gz")
    monkeypatch.setattr(trace_reduce, "load", lambda path: recorded)
    line = _run(olmoe_root, trace=True)
    assert line["correct"] is True
    # The CPU leaves no trace of its own to join with the program's table:
    # the three trace readers have nothing to read; the counter has.
    assert set(line["metrics"]) & MOE_METRICS == {"moe_load_imbalance"}
    value = line["metrics"]["moe_load_imbalance"]
    assert value["unit"] == "ratio" and 1.0 <= value["value"] <= 8.0
    from autodist_tpu.autodist import get_default_autodist
    aux = get_default_autodist().runner.last_aux
    assert float(aux["moe.dropped"]) == 0.0


def test_the_new_readers_report_in_the_new_cell_only():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    found = {m["name"]: m for m in bench["per_layer"]
             if m["name"] in MOE_METRICS}
    assert set(found) == MOE_METRICS
    cells = [w["name"] for w in bench["workloads"]]
    configs = [c["name"] for c in bench["configs"]]
    before = cells[:cells.index(CELL)]
    for metric in found.values():
        # Its own cell first; a later expert cell may be appended, a cell
        # the benchmark had before (none has an expert layer) never.
        assert metric["workloads"][0] == CELL
        assert not set(metric["workloads"]) & set(before)
        assert set(metric["workloads"]) <= set(cells)
        assert metric["moves"] == "tokens_per_s"
    for name in MOE_METRICS - {"moe_load_imbalance"}:
        assert found[name]["workloads"] == [CELL]
    assert found["moe_expert_matmul_roofline"]["layer"] == "Kernels"
    # Appended: what the benchmark had comes first, unchanged in order.
    names = [m["name"] for m in bench["per_layer"]]
    assert [n for n in names if n in MOE_METRICS] == [
        "moe_scope_share", "moe_dispatch_share",
        "moe_expert_matmul_roofline", "moe_load_imbalance"]
    assert names.index("moe_scope_share") > names.index(
        "scope_unattributed_share")
    assert before == ["gpt2-medium.train-s1024", "bert-base.mlm-s512",
                      "bert-base.mlm-s128", "gpt2-xl.train-s1024-x4"]
    assert configs[:configs.index("olmoe-1b-7b-0125")] == [
        "gpt2-medium", "bert-base-uncased", "gpt2-xl"]


# -- the configuration file ----------------------------------------------------

PUBLISHED = {
    "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 1024,
    "max_position_embeddings": 4096, "model_type": "olmoe",
    "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 16,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 50304}


def test_the_configuration_is_the_published_one_less_depth():
    catalog = Catalog(str(ROOT))
    cell = catalog.cell(CELL)
    sizes, mix = cell["sizes"], cell["mix"]
    differs = {k for k, v in PUBLISHED.items() if sizes[k] != v}
    assert differs == set(sizes["reduced"]) == {"num_hidden_layers"}
    assert sizes["num_hidden_layers"] == 1
    assert set(sizes["reduced_why"]) == set(sizes["reduced"])
    # The check keeps every width, all the experts and their number a
    # token: it cuts rows of the vocabulary and nothing else.
    assert set(sizes["check"]["sizes"]) == {"vocab_size"}
    assert sizes["assumed"]["router_aux_loss_coef"] == 0.01
    assert sizes["assumed"]["router_z_loss_coef"] == 0.001
    assert (mix["seq_len"], mix["rows_per_chip"], mix["masked_per_row"],
            mix["pool_batches"], mix["lag_steps"], mix["driver"]) == (
        4096, 2, 0, 64, 2, "train")
    assert cell["chips"] == 1 and sizes["deployment"]["chips"] == 1
    kind = catalog.module("kinds", "moe_causal_lm")
    assert kind.attention_calls(sizes, mix) == {
        "batch_heads": 32, "seq_len": 4096, "head_width": 128,
        "causal": True}
    # By hand: 6 x (4 d^2 + 3 x 8 x d x 1024 + 64 d + V d) + 6 s d.
    by_hand = 6 * (4 * 2048 ** 2 + 24 * 2048 * 1024 + 64 * 2048
                   + 50304 * 2048) + 6 * 4096 * 2048
    assert kind.flops_per_token(sizes, mix) == by_hand
    with pytest.raises(ValueError, match="does not implement"):
        kind.program({**sizes, "clip_qkv": 8.0})


def test_benchmark_json_keeps_to_the_contract_with_the_new_entries():
    """``test_names_units_and_lines_use_the_allowed_characters`` with the
    rule on ``reduced`` as the contract words it: a key may not name a
    width (a hidden, intermediate, latent, state or projection size, a key
    that ends in ``_dim`` or ``_rank``, a head size, an expansion factor,
    the experts a token); ``num_hidden_layers`` names a depth."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    # ``vocab_size`` ends in ``_size`` and is rows held, not a width.
    width = re.compile(r"(_dim$|_rank$|(?<!vocab)_size$|_width$|head|n_embd|"
                       r"n_inner|expansion|experts_per_tok)")
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = ([m["name"] for m in metrics]
             + [c["name"] for c in bench["configs"]]
             + [w["name"] for w in bench["workloads"]])
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in bench["workloads"]] + [
            key for c in bench["configs"] for key in c["reduced"]]:
        assert name.match(n), n
    for m in metrics:
        assert unit.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    lines = ([w["why"] for w in bench["workloads"] + bench["configs"]]
             + [c["source"] for c in bench["configs"]]
             + [m["layer"] for m in bench["per_layer"]])
    for line in lines:
        assert 1 <= len(line) <= 200 and "\n" not in line and "\t" not in line
    for c in bench["configs"]:
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert not width.search(key), key
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_pr23s_metrics_are_declared_with_their_sources_wherever_they_stand():
    """``test_every_new_metric_is_declared_with_its_source`` by name and
    order, not by distance from the end of the list."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    setup = ("trace_lower_s", "xla_compile_s", "report_s", "create_state_s")
    shares = ("optimizer_share", "head_share", "attn_scope_share",
              "mlp_scope_share", "backward_share",
              "scope_unattributed_share")
    pr23 = setup + ("compiles_in_window",) + shares
    declared = {m["name"]: m for m in bench["per_layer"]}
    source = dict.fromkeys(setup, "program_span")
    source["compiles_in_window"] = "program_counter"
    source.update(dict.fromkeys(shares, "device_trace"))
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(pr23[0])
    assert names[first:first + len(pr23)] == list(pr23)
    assert names[first + len(pr23):][:4] == [
        "moe_scope_share", "moe_dispatch_share",
        "moe_expert_matmul_roofline", "moe_load_imbalance"]
    for name in pr23:
        assert declared[name]["source"] == source[name]
        assert declared[name]["better"] == "lower"
        assert "workloads" not in declared[name]


# -- the grouped products' arithmetic ------------------------------------------

def test_grouped_product_cost_by_hand():
    # 65,536 rows of 2,048 through 64 matrices of 2,048 x 1,024, in bf16:
    # 2 x 65,536 x 2,048 x 1,024 operations whichever expert a row meets;
    # rows in 268,435,456 bytes, matrices the same, results 134,217,728.
    ops, nbytes = flops_moe.grouped_product_cost(
        assignments=65536, k=2048, n=1024, experts=64)
    assert ops == 274_877_906_944
    assert nbytes == 268_435_456 + 268_435_456 + 134_217_728
    # Transposed (the down projection) it is the same count.
    assert flops_moe.grouped_product_cost(
        assignments=65536, k=1024, n=2048, experts=64) == (ops, nbytes)


def test_an_expert_layer_makes_nine_products():
    products = flops_moe.expert_layer_products(
        assignments=65536, width=2048, expert_width=1024, experts=64)
    assert len(products) == 9 and len({p[0] for p in products}) == 9
    assert sum(p[1] for p in products) == 9 * 274_877_906_944   # 2.47 TFLOP
    least = sum(flops.roofline_seconds(ops, nbytes, V5E)[0]
                for _, ops, nbytes in products)
    assert all(flops.roofline_seconds(ops, nbytes, V5E)[1] == "compute"
               for _, ops, nbytes in products)
    assert least == pytest.approx(12.56e-3, rel=1e-3)
    # A small layer, by hand: 6 rows, d = 4, h = 2, 3 experts.
    small = dict(flops_moe.expert_layer_products(
        assignments=6, width=4, expert_width=2, experts=3)[i][:2]
        for i in range(9))
    assert set(small.values()) == {2 * 6 * 4 * 2}


# -- the readers on a made-up table ----------------------------------------------

JOINED = {"busy_s": 2.0, "scope": {
    "moe/router": 0.1, "moe/dispatch": 0.3, "moe/experts": 0.5, "attn": 0.4,
    "head": 0.5, "optimizer": 0.2}}
SHAPES = {"layers": 1, "experts": 64, "width": 2048, "expert_width": 1024,
          "assignments": 65536}


def _traced_run():
    return {"trace": {"programs": 10.0}, "peak": V5E}


def test_the_scope_readers_add_up_the_moe_scopes(monkeypatch):
    monkeypatch.setattr(program_probe, "by_scope", lambda: JOINED)
    assert moe_scope_share.read(_traced_run()) == pytest.approx(45.0)
    assert moe_dispatch_share.read(_traced_run()) == pytest.approx(20.0)
    assert moe_scope_share.read({"trace": None}) is None
    assert moe_dispatch_share.read({"trace": None}) is None


def test_the_roofline_reader_divides_the_least_time_by_the_scopes(
        monkeypatch, capsys):
    monkeypatch.setattr(program_probe, "by_scope", lambda: JOINED)
    monkeypatch.setattr(moe_expert_matmul_roofline, "program_shapes",
                        lambda: dict(SHAPES))
    # Ten steps of 12.56 ms at least, in 0.5 s of moe/experts.
    assert moe_expert_matmul_roofline.read(_traced_run()) == pytest.approx(
        100 * 10 * 12.558e-3 / 0.5, rel=1e-3)
    assert "bound by compute" in capsys.readouterr().out
    monkeypatch.setattr(moe_expert_matmul_roofline, "program_shapes",
                        lambda: dict(SHAPES, layers=2))
    assert moe_expert_matmul_roofline.read(_traced_run()) == pytest.approx(
        100 * 20 * 12.558e-3 / 0.5, rel=1e-3)


@pytest.mark.parametrize("reader", [moe_scope_share, moe_dispatch_share,
                                    moe_expert_matmul_roofline])
def test_a_program_without_expert_scopes_gives_the_readers_nothing(
        reader, monkeypatch):
    """The parent's table, or a dense cell's: the metric is left out."""
    dense = {"busy_s": 2.0, "scope": {"attn": 0.9, "mlp": 0.4, "head": 0.2}}
    monkeypatch.setattr(program_probe, "by_scope", lambda: dense)
    assert reader.read(_traced_run()) is None
    monkeypatch.setattr(program_probe, "by_scope", lambda: None)
    assert reader.read(_traced_run()) is None


def test_without_a_runner_there_are_no_shapes_and_no_aux():
    assert moe_expert_matmul_roofline.program_shapes() is None
    assert moe_load_imbalance.last_aux() is None
    assert moe_load_imbalance.read({}) is None


# -- the check tells a broken program ------------------------------------------

def _toy_program(**changes):
    sizes = json.loads((DATA / "tiny-olmoe.json").read_text())
    sizes["deployment"]["compute_dtype"] = "float32"
    mix = json.loads((DATA / "tiny-lm-s32.json").read_text())
    kind = Catalog(str(ROOT)).module("kinds", "moe_causal_lm")
    init, loss_fn = kind.program({**sizes, **changes})
    params = init(jax.random.PRNGKey(11))
    # Fresh from the initialiser a toy this small is close to linear: the
    # attention is near uniform and the experts add little to the residual
    # stream, so no mechanism moves the loss much.  Sharper attention (the
    # QK-norm's scales at 4) and louder experts bring out what each of the
    # broken programs changes.
    for name, layer in params.items():
        if name.startswith("layer"):
            for norm in ("q_norm", "k_norm"):
                layer["attn"][norm]["scale"] = 4.0 * layer["attn"][norm]["scale"]
            layer["moe"]["down"]["kernel"] = 16.0 * layer["moe"]["down"]["kernel"]
    batch = kind.host_batch(sizes, mix, 4, np.random.RandomState(11))
    with jax.default_matmul_precision("highest"):
        want = float(jax.jit(kind.reference_loss(sizes))(params, batch))
    return params, batch, loss_fn, want


def _renormalised(monkeypatch):
    return _toy_program(norm_topk_prob=True)


def _without_qk_norm(monkeypatch):
    params, batch, loss_fn, want = _toy_program()

    def stripped(params, batch):
        params = {k: ({**v, "attn": {n: p for n, p in v["attn"].items()
                                     if n not in ("q_norm", "k_norm")}}
                      if k.startswith("layer") else v)
                  for k, v in params.items()}
        return loss_fn(params, batch)
    return params, batch, stripped, want


def _interleaved_rotary(monkeypatch):
    """Pairs element 2i with 2i + 1 (the other published form) where the
    model pairs i with i + w / 2."""
    import jax.numpy as jnp
    from autodist_tpu.models import layers as L

    def apply_rope(x, tables):
        cos, sin = (t[..., :x.shape[-1] // 2] for t in tables)
        even, odd = x[..., 0::2], x[..., 1::2]
        return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                         axis=-1).reshape(x.shape)

    monkeypatch.setattr(L, "apply_rope", apply_rope)
    return _toy_program()


def _capacity_drops(monkeypatch):
    """The repo's other dispatch form at a capacity of the mean load: the
    assignments over it are dropped."""
    from autodist_tpu.parallel import moe
    dropless = moe.dropless_apply

    def with_capacity(params, cfg, x):
        capped = moe.MoEConfig(
            num_experts=cfg.num_experts, top_k=cfg.top_k, d_model=cfg.d_model,
            d_hidden=cfg.d_hidden, dtype=cfg.dtype, expert=cfg.expert,
            norm_topk=cfg.norm_topk, capacity_factor=1.0)
        return moe.apply(params, capped, x)[0], dropless(params, cfg, x)[1]

    monkeypatch.setattr(moe, "dropless_apply", with_capacity)
    return _toy_program()


def _rtol():
    sizes = json.loads((ROOT / "chipbench" / "configs"
                        / "olmoe-1b-7b-0125.json").read_text())
    return sizes["check"]["rtol"]


def test_the_right_program_is_inside_the_tolerance():
    params, batch, loss_fn, want = _toy_program()
    got = float(jax.jit(lambda p, b: loss_fn(p, b)[0])(params, batch))
    assert abs(got - want) / want < _rtol() / 10


@pytest.mark.parametrize("broken", [_renormalised, _without_qk_norm,
                                    _interleaved_rotary, _capacity_drops])
def test_the_check_fails_a_broken_program(broken, monkeypatch):
    params, batch, loss_fn, want = broken(monkeypatch)
    got = float(jax.jit(lambda p, b: loss_fn(p, b)[0])(params, batch))
    assert abs(got - want) / want > 2 * _rtol()
