"""Kind ``moe_causal_lm``: an OLMoE-shaped decoder trained on next-token
loss plus the router's two auxiliary terms.

A configuration of this kind carries the keys of OLMoE's ``config.json``
(``hidden_size``, ``num_attention_heads``, ``num_hidden_layers``,
``intermediate_size`` (one expert's width), ``num_experts``,
``num_experts_per_tok``, ``norm_topk_prob``, ``rms_norm_eps``,
``rope_theta``, ``vocab_size``, ``max_position_embeddings``,
``attention_bias``, ``tie_word_embeddings``) and, under ``assumed``, the two
coefficients the source leaves out.  ``program`` is the system under test;
everything else here is the yardstick's.
"""
import jax.numpy as jnp
import numpy as np

from chipbench import flops, reference_olmoe


def _supported(sizes):
    """The block the program and the reference implement: anything else in
    the file is an error, not something to run approximately."""
    wanted = {"model_type": "olmoe", "hidden_act": "silu", "clip_qkv": None,
              "rope_scaling": None, "attention_bias": False,
              "tie_word_embeddings": False,
              "num_key_value_heads": sizes["num_attention_heads"]}
    wrong = {k: sizes[k] for k, v in wanted.items() if sizes[k] != v}
    if wrong:
        raise ValueError(f"kind moe_causal_lm does not implement {wrong}; "
                         f"it wants {wanted}")


def program(sizes):
    """``(init(key) -> params, loss_fn(params, batch) -> (loss, aux))`` as
    the program builds them: ``models/lm.py`` over the block of
    ``models/transformer.py`` with its expert layer (``parallel/moe.py``)."""
    from autodist_tpu.models import lm
    from autodist_tpu.models import transformer as T
    _supported(sizes)
    cfg = T.TransformerConfig(
        vocab=sizes["vocab_size"], dim=sizes["hidden_size"],
        num_heads=sizes["num_attention_heads"],
        num_layers=sizes["num_hidden_layers"],
        max_len=sizes["max_position_embeddings"], causal=True,
        dtype=jnp.dtype(sizes["deployment"]["compute_dtype"]),
        norm="rmsnorm", norm_eps=sizes["rms_norm_eps"], positions="rope",
        rope_theta=float(sizes["rope_theta"]), qk_norm=True, bias=False,
        tied_head=False, ffn="moe", num_experts=sizes["num_experts"],
        experts_per_token=sizes["num_experts_per_tok"],
        expert_dim=sizes["intermediate_size"],
        norm_topk=sizes["norm_topk_prob"],
        load_balance_coef=sizes["assumed"]["router_aux_loss_coef"],
        router_z_coef=sizes["assumed"]["router_z_loss_coef"])
    return (lambda key: lm.init(key, cfg)), lm.make_loss_fn(cfg)


def reference_loss(sizes):
    """The same loss in plain float32 ``jax.numpy`` (``reference_olmoe.py``)."""
    _supported(sizes)

    def loss_fn(params, batch):
        (tokens,) = batch
        return reference_olmoe.loss(
            params, tokens, layers=sizes["num_hidden_layers"],
            heads=sizes["num_attention_heads"],
            top_k=sizes["num_experts_per_tok"],
            norm_topk=sizes["norm_topk_prob"], eps=sizes["rms_norm_eps"],
            theta=float(sizes["rope_theta"]),
            aux_coef=sizes["assumed"]["router_aux_loss_coef"],
            z_coef=sizes["assumed"]["router_z_loss_coef"])
    return loss_fn


def host_batch(sizes, traffic, rows, rng):
    """Uniform tokens over the vocabulary, ``seq_len`` + 1 a row: inputs
    and the targets shifted by one; one document a row, no packing."""
    return (rng.randint(0, sizes["vocab_size"],
                        (rows, traffic["seq_len"] + 1)).astype(np.int32),)


def tokens_per_row(traffic):
    return traffic["seq_len"]


def flops_per_token(sizes, traffic):
    """A position passes through ``num_experts_per_tok`` experts of three
    matrices each and through the router: as an MLP of two matrices, that is
    a width of 3 * k * expert width / 2 + experts / 2."""
    mlp_width = (3 * sizes["num_experts_per_tok"] * sizes["intermediate_size"]
                 + sizes["num_experts"]) // 2
    return flops.transformer_flops_per_token(
        width=sizes["hidden_size"], layers=sizes["num_hidden_layers"],
        mlp_width=mlp_width, vocab=sizes["vocab_size"],
        seq_len=traffic["seq_len"], causal=True, head_share=1.0)


def attention_calls(sizes, traffic):
    """Operand shape of one attention kernel call on one chip."""
    return {"batch_heads": traffic["rows_per_chip"]
            * sizes["num_attention_heads"],
            "seq_len": traffic["seq_len"],
            "head_width": sizes["hidden_size"] // sizes["num_attention_heads"],
            "causal": True}
