"""Kind ``looped_causal_lm``: an Ouro-shaped decoder, a stack of layers run
``total_ut_steps`` times over with one set of variables, a head and a
cross-entropy on every pass, weighed by a learned exit gate's distribution
(arXiv:2510.25741).

A configuration of this kind carries the keys of Ouro's ``config.json``
(``hidden_size``, ``intermediate_size``, ``num_attention_heads``,
``num_key_value_heads``, ``head_dim``, ``num_hidden_layers``,
``layer_types``, ``total_ut_steps``, ``early_exit_threshold``,
``rms_norm_eps``, ``rope_theta``, ``rope_scaling``, ``vocab_size``,
``max_position_embeddings``, ``tie_word_embeddings``, ``hidden_act``, and
the sliding-window keys, which are off); what the source leaves to the
modelling code is under ``block`` and ``assumed``.  ``program`` is the
system under test; everything else here is the yardstick's.
"""
import jax.numpy as jnp
import numpy as np

from chipbench import reference_ouro


def _supported(sizes):
    """The block the program and the reference implement: anything else in
    the file is an error, not something to run approximately."""
    wanted = {"model_type": "ouro", "hidden_act": "silu",
              "tie_word_embeddings": False, "rope_scaling": None,
              "use_sliding_window": False, "sliding_window": None,
              "num_key_value_heads": sizes["num_attention_heads"],
              "head_dim": sizes["hidden_size"] // sizes["num_attention_heads"],
              "layer_types": ["full_attention"] * sizes["num_hidden_layers"]}
    wrong = {k: sizes[k] for k, v in wanted.items() if sizes[k] != v}
    if sizes["total_ut_steps"] < 2:
        wrong["total_ut_steps"] = sizes["total_ut_steps"]
    if wrong:
        raise ValueError(f"kind looped_causal_lm does not implement {wrong}; "
                         f"it wants {wanted} and total_ut_steps of 2 or more")


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
        norm="rmsnorm", norm_eps=sizes["rms_norm_eps"], positions="rope",
        rope_theta=float(sizes["rope_theta"]), bias=False, tied_head=False,
        ffn="swiglu", norm_position="sandwich",
        loops=sizes["total_ut_steps"],
        exit_entropy_coef=sizes["block"]["exit_entropy_coef"],
        recompute=sizes["deployment"].get("recompute"))


def program(sizes):
    """``(init(key) -> params, loss_fn(params, batch) -> (loss, aux))`` as
    the program builds them: ``models/lm.py`` over the looped stack of
    ``models/transformer.py``."""
    from autodist_tpu.models import lm
    cfg = config(sizes)
    return (lambda key: lm.init(key, cfg)), lm.make_loss_fn(cfg)


def reference_loss(sizes, plant=None):
    """The same loss in plain float32 ``jax.numpy`` (``reference_ouro.py``);
    ``plant`` one of its named departures, for the checks that a wrong
    program is refused."""
    _supported(sizes)

    def loss_fn(params, batch):
        (tokens,) = batch
        return reference_ouro.loss(
            params, tokens, layers=sizes["num_hidden_layers"],
            passes=sizes["total_ut_steps"],
            heads=sizes["num_attention_heads"], eps=sizes["rms_norm_eps"],
            theta=float(sizes["rope_theta"]),
            beta=sizes["block"]["exit_entropy_coef"], plant=plant)
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

    ``6 x T x (N x layer matmul parameters + held vocabulary x width)`` (2
    forward, 4 backward), where T = ``total_ut_steps`` passes each run all
    N layers and the head, and a layer's matmul parameters are ``4 d^2 + 3
    d I`` (four projections, the SwiGLU MLP's three matrices); the head is
    untied and applied to every position of every pass.  Plus attention
    itself: ``12 s d / 2`` for each of the ``T x N`` layer applications
    (q.k^T and p.v forward, three times that with the backward, half under
    the causal mask).  Model operations: no recomputation, no norms, no
    rotation, no embedding lookup, and nothing for the gate (``2 d`` a
    pass) or the loss over the passes."""
    d, inner = sizes["hidden_size"], sizes["intermediate_size"]
    passes, layers = sizes["total_ut_steps"], sizes["num_hidden_layers"]
    layer = 4 * d * d + 3 * d * inner
    matmul = 6 * passes * (layers * layer + sizes["vocab_size"] * d)
    return matmul + passes * layers * 12 * traffic["seq_len"] * d // 2


def attention_calls(sizes, traffic):
    """Operand shape of one attention kernel call on one chip; the trace
    counts the calls, ``total_ut_steps x num_hidden_layers`` of each kernel
    a step."""
    return {"batch_heads": traffic["rows_per_chip"]
            * sizes["num_attention_heads"],
            "seq_len": traffic["seq_len"], "head_width": sizes["head_dim"],
            "causal": True}
