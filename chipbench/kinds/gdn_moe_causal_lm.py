"""Kind ``gdn_moe_causal_lm``: a Qwen3-Next-shaped decoder: gated-delta-rule
layers whose value heads read fewer key heads, a full-attention layer every
``full_attention_interval`` layers (grouped key-value heads, a norm a head on
q and k, a part of the lanes rotated, a sigmoid gate a lane on the output),
and in EVERY layer softmax-routed experts of which this chip holds a share
beside one shared expert behind a sigmoid gate; trained on next-token loss
and the load-balancing term.

A configuration of this kind carries the keys of the source's
``config.json`` (``hidden_size``, ``head_dim``, ``num_attention_heads``,
``num_key_value_heads``, ``full_attention_interval``, ``partial_rotary_factor``,
``rope_theta``, ``linear_num_key_heads``, ``linear_num_value_heads``,
``linear_key_head_dim``, ``linear_value_head_dim``, ``linear_conv_kernel_dim``,
``moe_intermediate_size``, ``shared_expert_intermediate_size``,
``num_experts`` (here: the experts HELD), ``num_experts_per_tok``,
``norm_topk_prob``, ``decoder_sparse_step``, ``mlp_only_layers``,
``rms_norm_eps``, ``vocab_size``, ...); ``published`` states the source's
values of what ``reduced`` names, and the router is as wide as
``published.num_experts``; what the source leaves to the family's convention
is under ``assumed``.  ``program`` is the system under test; everything else
here is the yardstick's.
"""
import jax
import numpy as np

from chipbench import reference_gdn_moe
from chipbench.kinds import mla_moe_causal_lm as probes

FULL, LINEAR = "full_attention", "linear_attention"


def _supported(sizes):
    """The model the program and the reference implement: anything else in
    the file is an error, not something to run approximately."""
    wanted = {"model_type": "qwen3_next", "hidden_act": "silu",
              "tie_word_embeddings": False, "norm_topk_prob": True,
              "decoder_sparse_step": 1, "mlp_only_layers": [],
              "rope_scaling": None, "use_sliding_window": False,
              "shared_expert_intermediate_size":
                  sizes["moe_intermediate_size"]}
    wrong = {k: sizes[k] for k, v in wanted.items() if sizes[k] != v}
    if sizes["linear_num_value_heads"] % sizes["linear_num_key_heads"]:
        wrong["linear_num_key_heads"] = sizes["linear_num_key_heads"]
    if sizes["num_hidden_layers"] % sizes["full_attention_interval"]:
        wrong["full_attention_interval"] = sizes["full_attention_interval"]
    if wrong:
        raise ValueError(f"kind gdn_moe_causal_lm does not implement {wrong}; "
                         f"it wants {wanted}, key heads that divide the value "
                         f"heads and whole periods of layers")


def layer_types(sizes):
    """Layer ``i`` is full attention where ``(i + 1) %
    full_attention_interval == 0`` and the gated-delta mixer elsewhere."""
    return [FULL if (i + 1) % sizes["full_attention_interval"] == 0
            else LINEAR for i in range(sizes["num_hidden_layers"])]


def _held(sizes):
    """``(first, count)``: the experts this chip holds of the router's
    ``published.num_experts``, rank ``deployment.expert_rank``'s."""
    count = sizes["num_experts"]
    return sizes["deployment"]["expert_rank"] * count, count


def _recompute(sizes):
    """``deployment.recomputation``: ``"none"``, or the sublayers
    ``TransformerConfig.recompute`` names."""
    named = sizes["deployment"].get("recomputation", "none")
    return None if named == "none" else named


def _rotary_lanes(sizes):
    return int(sizes["head_dim"] * sizes["partial_rotary_factor"])


def config(sizes):
    """The program's ``TransformerConfig`` of ``sizes``.
    ``deployment.held_chunks``, where the file has it, is the chunks the held
    layers take a step's assignments in (the program's default otherwise)."""
    import jax.numpy as jnp
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
        qk_norm="head", bias=False, tied_head=False, ffn="moe",
        num_experts=sizes["published"]["num_experts"],
        experts_per_token=sizes["num_experts_per_tok"],
        expert_dim=sizes["moe_intermediate_size"],
        norm_topk=sizes["norm_topk_prob"],
        load_balance_coef=sizes["assumed"]["load_balance_coef"],
        layer_types=layer_types(sizes),
        linear_heads=sizes["linear_num_value_heads"],
        linear_key_heads=sizes["linear_num_key_heads"],
        linear_key_dim=sizes["linear_key_head_dim"],
        linear_value_dim=sizes["linear_value_head_dim"],
        conv_width=sizes["linear_conv_kernel_dim"], allow_neg_eigval=False,
        expert_scoring="softmax", shared_experts=1, shared_gate=True,
        experts_held=_held(sizes), head_dim=sizes["head_dim"],
        experts_held_chunks=sizes["deployment"].get("held_chunks"),
        recompute=_recompute(sizes),
        mixer_stats="probes" in sizes,
        kv_heads=sizes["num_key_value_heads"], attn_gate="lane",
        rope_by_type={FULL: {"theta": float(sizes["rope_theta"]),
                             "lanes": _rotary_lanes(sizes), "yarn": None}})


def checked_number(sizes, loss, probed, params):
    """The JoyAI kind's ``checked_number`` (the loss, what the held experts
    add, how far Adam moved the values) and, added the same way with no
    gradient and each at its weight in ``sizes["probes"]``, how far what the
    full-attention and the gated-delta mixers add to the residual stream
    stands from its mean over a row's positions, as a root mean square
    (``probed``: the program's ``aux["attn.output_std"]``,
    ``aux["gdn.output_std"]``; the reference's own from its own forward
    pass).  A fault that re-draws what a mixer's output holds and keeps the
    loss's size (the wrong key head, more lanes rotated) moves how sharply
    the next attention layer's positions differ (``check.why`` has the
    readings)."""
    weights = sizes["probes"]
    return probes.checked_number(
        sizes, loss, probed["held_output_rms"], params) \
        + jax.lax.stop_gradient(sum(
            weights[name] * probed[name]
            for name in ("attn_output_std", "gdn_output_std")))


def program(sizes):
    """``(init(key) -> params, loss_fn(params, batch) -> (loss, aux))`` as
    the program builds them: ``models/lm.py`` over the block of
    ``models/transformer.py`` with ``layers.gdn`` (``ops/gated_delta.py``'s
    rule with grouped key heads), ``layers.mha`` (grouped heads, a norm a
    head, a gate a lane; ``ops/flash_attention.py``'s kernels) and the held
    share of ``parallel/moe.py:dropless_apply`` beside its gated shared
    expert.  With ``sizes["probes"]`` (the check's session) the values carry
    ``ANCHOR``, the mixers report their outputs' sizes and the loss reported
    is :func:`checked_number`."""
    from autodist_tpu.models import lm
    cfg = config(sizes)
    init, loss_fn = (lambda key: lm.init(key, cfg)), lm.make_loss_fn(cfg)
    if "probes" not in sizes:
        return init, loss_fn
    samples = sizes["probes"]["anchor_samples"]

    def init_with_anchor(key):
        values = init(key)
        return {**values, probes.ANCHOR: jax.tree_util.tree_map(
            lambda x: probes._sample(x, samples), values)}

    def checked_loss_fn(params, batch):
        loss, aux = loss_fn(
            {k: v for k, v in params.items() if k != probes.ANCHOR}, batch)
        return checked_number(
            sizes, loss, {"held_output_rms": aux["moe.held_output_rms"],
                          "attn_output_std": aux["attn.output_std"],
                          "gdn_output_std": aux["gdn.output_std"]},
            params), aux
    return init_with_anchor, checked_loss_fn


def reference_model(sizes):
    """The keyword arguments ``reference_gdn_moe.loss`` takes for
    ``sizes``."""
    _supported(sizes)
    return dict(
        layer_types=tuple(layer_types(sizes)),
        rotary_lanes=_rotary_lanes(sizes), theta=float(sizes["rope_theta"]),
        eps=sizes["rms_norm_eps"], heads=sizes["linear_num_value_heads"],
        key_heads=sizes["linear_num_key_heads"], head_dim=sizes["head_dim"],
        top_k=sizes["num_experts_per_tok"], held=_held(sizes),
        balance_coef=sizes["assumed"]["load_balance_coef"])


def reference_loss(sizes):
    """The same loss in plain float32 ``jax.numpy``
    (``reference_gdn_moe.py``).  With ``sizes["probes"]`` the number is
    ``checked_number``, from the reference's own forward pass and its own
    values."""
    model = reference_model(sizes)

    def loss_fn(params, batch):
        (tokens,) = batch
        loss, probed = reference_gdn_moe.loss_and_probes(
            params, tokens, **model)
        if "probes" not in sizes:
            return loss
        return checked_number(sizes, loss, probed, params)
    return loss_fn


def host_batch(sizes, traffic, rows, rng):
    """Uniform tokens over the rows of the vocabulary held here, ``seq_len``
    + 1 a row: inputs and the targets one ahead; one document a row, no
    packing."""
    return (rng.randint(0, sizes["vocab_size"],
                        (rows, traffic["seq_len"] + 1)).astype(np.int32),)


def tokens_per_row(traffic):
    return traffic["seq_len"]


def matmul_parameters(sizes):
    """``{part: matrix-multiply parameters one position passes}``: a linear
    layer's six projections in (q and k at the KEY heads, v and the output
    gate at the value heads, the decay's and the write strength's a value
    head) and its output projection; a full layer's q and gate at the query
    heads, k and v at the key-value heads, and out; in every layer the
    router, the shared expert with its one-output gate, and
    ``num_experts_per_tok`` experts of which the share held here is held /
    router outputs at an even load; the held rows of the head."""
    d = sizes["hidden_size"]
    keys = sizes["linear_num_key_heads"] * sizes["linear_key_head_dim"]
    values = sizes["linear_num_value_heads"] * sizes["linear_value_head_dim"]
    linear = d * (2 * keys + 2 * values + 2 * sizes["linear_num_value_heads"]) \
        + values * d
    q = sizes["num_attention_heads"] * sizes["head_dim"]
    kv = sizes["num_key_value_heads"] * sizes["head_dim"]
    full = d * (2 * q + 2 * kv) + q * d
    kinds = layer_types(sizes)
    expert = 3 * d * sizes["moe_intermediate_size"]
    routed = sizes["num_experts_per_tok"] * sizes["num_experts"] \
        / sizes["published"]["num_experts"] * expert
    return {
        "linear_mixers": kinds.count(LINEAR) * linear,
        "full_mixers": kinds.count(FULL) * full,
        "expert_layers": len(kinds) * (
            expert + d + d * sizes["published"]["num_experts"] + routed),
        "head": sizes["vocab_size"] * d}


def flops_per_token(sizes, traffic):
    """Forward + backward operations one input position needs, written out:
    ``6 x`` :func:`matmul_parameters` (2 forward, 4 backward); plus ``12 s H
    head_dim / 2`` for each full layer (q.k^T and p.v forward, three times
    that with the backward, half under the causal mask, ``H`` QUERY heads)
    and ``18 H_v d_k d_v`` for each linear one (the recurrence's ``S k``,
    rank-one write and ``S q`` a VALUE head, 6 d_k d_v forward, three times
    that with the backward: what the rule needs, not what a chunked form
    spends).  No recomputation, no convolution, no norms, no rotary, no
    embedding lookup, no sorting or gathering of the experts' rows."""
    kinds = layer_types(sizes)
    attention = kinds.count(FULL) * 12 * traffic["seq_len"] \
        * sizes["num_attention_heads"] * sizes["head_dim"] / 2
    rule = kinds.count(LINEAR) * 18 * sizes["linear_num_value_heads"] \
        * sizes["linear_key_head_dim"] * sizes["linear_value_head_dim"]
    return 6 * sum(matmul_parameters(sizes).values()) + attention + rule


def attention_calls(sizes, traffic):
    """Operand shape of one attention kernel call on one chip (a full
    layer's; the trace counts the calls): the QUERY heads, which is what the
    operations follow; the generic reader's bytes then count k and v a query
    head where the kernels move them a key-value head, and the kernels are
    bound by operations either way."""
    return {"batch_heads": traffic["rows_per_chip"]
            * sizes["num_attention_heads"],
            "seq_len": traffic["seq_len"], "head_width": sizes["head_dim"],
            "causal": True}
