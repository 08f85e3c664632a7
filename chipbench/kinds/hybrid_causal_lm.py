"""Kind ``hybrid_causal_lm``: an Olmo-Hybrid-shaped decoder, gated-delta-rule
(linear-attention) layers and full-attention layers mixed by a pattern,
trained on next-token loss.

A configuration of this kind carries the keys of Olmo-Hybrid's
``config.json`` (``hidden_size``, ``intermediate_size``,
``num_attention_heads``, ``num_hidden_layers``, ``layer_types``,
``linear_num_key_heads``, ``linear_key_head_dim``,
``linear_value_head_dim``, ``linear_conv_kernel_dim``,
``linear_allow_neg_eigval``, ``rms_norm_eps``, ``rope_parameters``,
``vocab_size``, ``max_position_embeddings``, ``attention_bias``,
``tie_word_embeddings``); what the source leaves to the modelling code is
under ``block`` and ``assumed``.  ``program`` is the system under test;
everything else here is the yardstick's.
"""
import jax.numpy as jnp
import numpy as np

from chipbench import reference_olmo_hybrid

LAYER_TYPES = ("linear_attention", "full_attention")


def _supported(sizes):
    """The block the program and the reference implement: anything else in
    the file is an error, not something to run approximately."""
    wanted = {"model_type": "olmo_hybrid", "hidden_act": "silu",
              "attention_bias": False, "tie_word_embeddings": False,
              "rope_parameters": {"rope_theta": None},
              "num_key_value_heads": sizes["num_attention_heads"],
              "linear_num_value_heads": sizes["linear_num_key_heads"]}
    wrong = {k: sizes[k] for k, v in wanted.items() if sizes[k] != v}
    pattern = sizes["layer_types"]
    if len(pattern) != sizes["num_hidden_layers"] \
            or set(pattern) - set(LAYER_TYPES):
        wrong["layer_types"] = pattern
    if wrong:
        raise ValueError(f"kind hybrid_causal_lm does not implement {wrong}; "
                         f"it wants {wanted} and one of {LAYER_TYPES} for "
                         f"each of the num_hidden_layers")


def config(sizes):
    """The program's ``TransformerConfig`` of ``sizes``."""
    from autodist_tpu.models import transformer as T
    _supported(sizes)
    return T.TransformerConfig(
        vocab=sizes["vocab_size"], dim=sizes["hidden_size"],
        num_heads=sizes["num_attention_heads"],
        num_layers=sizes["num_hidden_layers"],
        mlp_dim=sizes["intermediate_size"],
        max_len=sizes["max_position_embeddings"], causal=True,
        dtype=jnp.dtype(sizes["deployment"]["compute_dtype"]),
        norm="rmsnorm", norm_eps=sizes["rms_norm_eps"], positions="none",
        qk_norm=True, bias=False, tied_head=False, ffn="swiglu",
        layer_types=sizes["layer_types"],
        linear_heads=sizes["linear_num_key_heads"],
        linear_key_dim=sizes["linear_key_head_dim"],
        linear_value_dim=sizes["linear_value_head_dim"],
        conv_width=sizes["linear_conv_kernel_dim"],
        allow_neg_eigval=sizes["linear_allow_neg_eigval"],
        norm_position="output")


def program(sizes):
    """``(init(key) -> params, loss_fn(params, batch) -> (loss, aux))`` as
    the program builds them: ``models/lm.py`` over the block of
    ``models/transformer.py`` with its gated-delta mixer (``layers.gdn``
    over ``ops/gated_delta.py``)."""
    from autodist_tpu.models import lm
    cfg = config(sizes)
    return (lambda key: lm.init(key, cfg)), lm.make_loss_fn(cfg)


def reference_loss(sizes):
    """The same loss in plain float32 ``jax.numpy``
    (``reference_olmo_hybrid.py``)."""
    _supported(sizes)

    def loss_fn(params, batch):
        (tokens,) = batch
        return reference_olmo_hybrid.loss(
            params, tokens, layer_types=tuple(sizes["layer_types"]),
            heads=sizes["num_attention_heads"],
            linear_heads=sizes["linear_num_key_heads"],
            eps=sizes["rms_norm_eps"],
            neg_eigval=sizes["linear_allow_neg_eigval"])
    return loss_fn


def host_batch(sizes, traffic, rows, rng):
    """Uniform tokens over the rows of the vocabulary held here,
    ``seq_len`` + 1 a row: inputs and the targets shifted by one; one
    document a row, no packing."""
    return (rng.randint(0, sizes["vocab_size"],
                        (rows, traffic["seq_len"] + 1)).astype(np.int32),)


def tokens_per_row(traffic):
    return traffic["seq_len"]


def flops_per_token(sizes, traffic):
    """Forward + backward operations one input position needs, written out:

    ``6 x (layer matmul parameters + held vocabulary x width)`` (2 forward,
    4 backward), where a full layer's are ``4 d^2 + 3 d I`` (four
    projections, the SwiGLU MLP's three matrices) and a linear layer's
    ``d (2 H d_k + 3 H d_v) + 2 d H + 3 d I`` (q and k; v, the output gate
    and the output projection; the decay's and the write strength's
    projections; the MLP); the head is untied and applied to every
    position.  Plus the mixers themselves: ``12 s d / 2`` for each full
    layer (q.k^T and p.v forward, three times that with the backward, half
    under the causal mask) and ``18 H d_k d_v`` for each linear one (the
    recurrence's ``S k``, rank-one write and ``S q``, 6 H d_k d_v forward,
    three times that with the backward: what the rule needs, not what a
    chunked form spends).  No recomputation, no convolution, no norms, no
    embedding lookup."""
    d, inner = sizes["hidden_size"], sizes["intermediate_size"]
    heads = sizes["linear_num_key_heads"]
    d_k, d_v = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    full = 4 * d * d + 3 * d * inner
    linear = d * (2 * heads * d_k + 3 * heads * d_v) + 2 * d * heads \
        + 3 * d * inner
    n_linear = sizes["layer_types"].count("linear_attention")
    n_full = sizes["layer_types"].count("full_attention")
    matmul = 6 * (n_full * full + n_linear * linear
                  + sizes["vocab_size"] * d)
    mixers = n_full * 12 * traffic["seq_len"] * d // 2 \
        + n_linear * 18 * heads * d_k * d_v
    return matmul + mixers


def attention_calls(sizes, traffic):
    """Operand shape of one attention kernel call on one chip (a full
    layer's; the trace counts the calls)."""
    return {"batch_heads": traffic["rows_per_chip"]
            * sizes["num_attention_heads"],
            "seq_len": traffic["seq_len"],
            "head_width": sizes["hidden_size"] // sizes["num_attention_heads"],
            "causal": True}
