"""The yardstick's arithmetic against values worked by hand."""
import json
import pathlib

import pytest

from chipbench import flops
from chipbench.catalog import Catalog

ROOT = pathlib.Path(__file__).resolve().parents[2]

# (configuration, traffic mix) -> operations a token, worked by hand from
# the published sizes:
# 6 x (layers x 12 d^2 + V d x head share) + 12 L s d (halved when causal).
BY_HAND = {
    # 6 x (24 x 12 x 1024^2 + 50257 x 1024) + 6 x 24 x 1024 x 1024
    ("gpt2-medium", "lm-s1024"):
        6 * (24 * 12 * 1024 ** 2 + 50257 * 1024) + 6 * 24 * 1024 * 1024,
    # 6 x (12 x 12 x 768^2 + 30522 x 768 x 80/512) + 12 x 12 x 512 x 768
    ("bert-base-uncased", "mlm-s512"):
        6 * (12 * 12 * 768 ** 2 + 30522 * 768 * 80 / 512)
        + 12 * 12 * 512 * 768,
    ("bert-base-uncased", "mlm-s128"):
        6 * (12 * 12 * 768 ** 2 + 30522 * 768 * 20 / 128)
        + 12 * 12 * 128 * 768,
    ("gpt2-xl", "lm-s1024-sharded"):
        6 * (48 * 12 * 1600 ** 2 + 50257 * 1600) + 6 * 48 * 1024 * 1600,
}
ROUNDED = {"gpt2-medium": 2.27e9, "mlm-s512": 0.588e9, "mlm-s128": 0.546e9,
           "gpt2-xl": 9.80e9}
PUBLISHED_DEPTH = {"gpt2-medium": ("n_layer", 24), "gpt2-xl": ("n_layer", 48),
                   "bert-base-uncased": ("num_hidden_layers", 12)}


@pytest.mark.parametrize("config, traffic", sorted(BY_HAND))
def test_model_flops_per_token_at_the_published_sizes(config, traffic):
    catalog = Catalog(str(ROOT))
    sizes = json.loads((ROOT / "chipbench" / "configs" / f"{config}.json")
                       .read_text())
    mix = json.loads((ROOT / "chipbench" / "traffic" / f"{traffic}.json")
                     .read_text())
    # Depth as published, whatever a cell had to cut.
    key, depth = PUBLISHED_DEPTH[config]
    sizes[key] = depth
    got = catalog.module("kinds", sizes["kind"]).flops_per_token(sizes, mix)
    assert got == pytest.approx(BY_HAND[config, traffic], rel=1e-12)
    rounded = ROUNDED.get(traffic, ROUNDED.get(config))
    assert got == pytest.approx(rounded, rel=2e-3)


@pytest.mark.parametrize("kernel, matmuls, nbytes", [
    # (16, 1024, 64): one tile is 16 x 1024 x 64 = 1,048,576 elements, one
    # row statistic 16,384.  fwd: q k v o in bf16 + lse in f32.
    ("flash_fwd", 2, 4 * 1048576 * 2 + 16384 * 4),
    # dq: q k v do in bf16, lse and delta in f32, dq out in f32.
    ("flash_bwd_dq", 3, 4 * 1048576 * 2 + 2 * 16384 * 4 + 1048576 * 4),
    # dk/dv: the same reads, two f32 results.
    ("flash_bwd_dkv", 4, 4 * 1048576 * 2 + 2 * 16384 * 4 + 2 * 1048576 * 4),
])
def test_attention_kernel_cost_by_hand(kernel, matmuls, nbytes):
    shape = dict(batch_heads=16, seq_len=1024, head_width=64)
    ops, got_bytes = flops.attention_kernel_cost(kernel, causal=False,
                                                 **shape)
    # one matmul of a head: 2 x 1024 x 1024 x 64 = 134,217,728 operations
    assert ops == matmuls * 16 * 134217728
    assert got_bytes == nbytes
    causal_ops, causal_bytes = flops.attention_kernel_cost(
        kernel, causal=True, **shape)
    assert causal_ops == ops / 2 and causal_bytes == nbytes


def test_attention_kernel_cost_refuses_an_unknown_kernel():
    with pytest.raises(KeyError):
        flops.attention_kernel_cost("flash_mystery", batch_heads=1,
                                    seq_len=128, head_width=64, causal=False)


def test_roofline_names_the_bound():
    peak = json.loads((ROOT / "chipbench" / "peaks.json").read_text())[
        "TPU v5 lite"]
    assert peak == {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9}
    # 197e12 operations and one byte: a second of arithmetic.
    assert flops.roofline_seconds(197e12, 1, peak) == (1.0, "compute")
    # 819e9 bytes and one operation: a second of memory traffic.
    assert flops.roofline_seconds(1, 819e9, peak) == (1.0, "memory")
    # The s = 128 forward of BERT-base (3,072 one-block programs) is bound
    # by memory; at s = 1,024 the causal forward is bound by compute.
    short = flops.attention_kernel_cost("flash_fwd", batch_heads=3072,
                                        seq_len=128, head_width=64,
                                        causal=False)
    assert flops.roofline_seconds(*short, peak)[1] == "memory"
    long = flops.attention_kernel_cost("flash_fwd", batch_heads=128,
                                       seq_len=1024, head_width=64,
                                       causal=True)
    assert flops.roofline_seconds(*long, peak)[1] == "compute"


def test_an_unknown_device_kind_is_an_error():
    catalog = Catalog(str(ROOT))
    assert catalog.peak("TPU v5 lite")["flops_per_s"] == 197e12
    for kind in ("cpu", "TPU v9", "_source"):
        with pytest.raises(KeyError):
            catalog.peak(kind)
