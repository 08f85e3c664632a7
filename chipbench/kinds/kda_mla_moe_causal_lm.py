"""Kind ``kda_mla_moe_causal_lm``: a decoder shaped as the language model of
Ling-3.0-flash-VL: five KDA layers (the delta rule with a decay a channel of
the key, its gate bounded below) to one latent-attention layer (full-rank
queries, a gate a head), leading dense layers, then layers of sigmoid-routed
experts chosen inside the best groups, of which this chip holds a share
beside one shared expert; trained on next-token loss in a step that also
moves the routers' selection biases.

A configuration of this kind carries the keys of the source's
``config.json`` (``hidden_size``, ``num_attention_heads``, ``head_dim``,
``layer_group_size``, ``first_k_dense_replace``, ``intermediate_size``,
``moe_intermediate_size``, ``num_experts`` (here: the experts HELD),
``num_experts_per_tok``, ``n_group``, ``topk_group``,
``routed_scaling_factor``, ``score_function``, ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``, ``rope_theta``,
``short_conv_kernel_size``, ``kda_lower_bound``, ``rms_norm_eps``,
``vocab_size``, ...); ``published`` states the source's values of what
``reduced`` names, and the router is as wide as ``published.num_experts``;
what the source leaves to the family's convention is under ``assumed``.
``program`` is the system under test; everything else here is the
yardstick's.
"""
import jax
import numpy as np

from chipbench import reference_kda_mla_moe
from chipbench.kinds import mla_moe_causal_lm as probes

KDA, LATENT = "kda_attention", "latent_attention"
PROBES = ("attn_output_std", "kda_output_std", "groups_reached")


def _supported(sizes):
    """The model the program and the reference implement: anything else in
    the file is an error, not something to run approximately."""
    wanted = {"score_function": "sigmoid", "norm_topk_prob": True,
              "moe_router_enable_expert_bias": True, "q_lora_rank": None,
              "use_qk_norm": True, "linear_silu": True, "kda_safe_gate": True,
              "no_kda_lora": True, "use_kda_lora": False,
              "use_mla_nope": False, "use_nGPT": False,
              "scale_router_input": False, "value_norm": False,
              "up_proj_norm": False, "group_norm_size": 1,
              "num_kv_heads_for_linear_attn": 0,
              "gated_attention_proj_granularity_type": "head_wise",
              "num_key_value_heads": sizes["num_attention_heads"],
              "rotary_dim": sizes["qk_rope_head_dim"],
              "moe_shared_expert_intermediate_size":
                  sizes["moe_intermediate_size"]}
    wrong = {k: sizes[k] for k, v in wanted.items() if sizes[k] != v}
    held = sizes["layers_held"]
    dense = [i for i in held
             if i < sizes["published"]["first_k_dense_replace"]]
    if len(held) != sizes["num_hidden_layers"] or held != sorted(held) \
            or not 0 < len(dense) < len(held) \
            or len(dense) != sizes["first_k_dense_replace"]:
        wrong["layers_held"] = held
    if any(sizes[key][i] for key in ("expert_swiglu_limit_list",
                                     "share_expert_swiglu_limit_list")
           for i in held):
        wrong["expert_swiglu_limit_list"] = "a clamp in a held layer"
    if wrong:
        raise ValueError(
            f"kind kda_mla_moe_causal_lm does not implement {wrong}; it "
            f"wants {wanted}, num_hidden_layers published layers in order "
            f"(layers_held) of which the first first_k_dense_replace are "
            f"dense ones, and no SwiGLU clamp in a layer held")


def layer_types(sizes):
    """The mixers of the layers held (``layers_held``: their places in the
    published model): layer ``i`` of the model is latent attention where
    ``(i + 1) % layer_group_size == 0`` and KDA elsewhere."""
    return [LATENT if (i + 1) % sizes["layer_group_size"] == 0 else KDA
            for i in sizes["layers_held"]]


def _held(sizes):
    """``(first, count)``: the experts this chip holds of the router's
    ``published.num_experts``, rank ``deployment.expert_rank``'s."""
    count = sizes["num_experts"]
    return sizes["deployment"]["expert_rank"] * count, count


def _recompute(sizes):
    named = sizes["deployment"].get("recomputation", "none")
    return None if named == "none" else named


def config(sizes):
    """The program's ``TransformerConfig`` of ``sizes``."""
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
        norm="rmsnorm", norm_eps=sizes["rms_norm_eps"], positions="none",
        rope_theta=float(sizes["rope_theta"]), bias=False, tied_head=False,
        ffn="moe", num_experts=sizes["published"]["num_experts"],
        experts_per_token=sizes["num_experts_per_tok"],
        expert_dim=sizes["moe_intermediate_size"],
        norm_topk=sizes["norm_topk_prob"], layer_types=layer_types(sizes),
        linear_heads=sizes["num_attention_heads"],
        linear_key_dim=sizes["head_dim"], linear_value_dim=sizes["head_dim"],
        conv_width=sizes["short_conv_kernel_size"],
        linear_gate_bound=float(sizes["kda_lower_bound"]),
        expert_scoring=sizes["score_function"],
        route_scale=sizes["routed_scaling_factor"], shared_experts=1,
        select_bias=True,
        bias_update_rate=sizes["assumed"]["bias_update_rate"],
        experts_held=_held(sizes), expert_groups=sizes["n_group"],
        expert_groups_kept=sizes["topk_group"],
        first_dense=sizes["first_k_dense_replace"], q_rank=0,
        kv_rank=sizes["kv_lora_rank"], nope_dim=sizes["qk_nope_head_dim"],
        rope_dim=sizes["qk_rope_head_dim"], value_dim=sizes["v_head_dim"],
        attn_gate=True, recompute=_recompute(sizes),
        mixer_stats="probes" in sizes)


def checked_number(sizes, loss, probed, params):
    """The JoyAI kind's ``checked_number`` (the loss, what the held experts
    add, how far Adam moved the values) and, added the same way with no
    gradient and each at its weight in ``sizes["probes"]``, how far what the
    latent-attention and the KDA mixers add to the residual stream stands
    from its mean over a row's positions, as a root mean square, and how
    many of the router's groups a position's choices fall in (``probed``:
    the program's ``aux["attn.output_std"]``, ``aux["kda.output_std"]``,
    ``aux["moe.groups_reached"]``; the reference's own from its own forward
    pass).  The last is what sees the group limit: the held experts' part
    has the same size with and without it (``check.why``)."""
    weights = sizes["probes"]
    return probes.checked_number(
        sizes, loss, probed["held_output_rms"], params) \
        + jax.lax.stop_gradient(sum(weights[name] * probed[name]
                                    for name in PROBES))


def program(sizes):
    """``(init(key) -> params, loss_fn(params, batch) -> (loss, aux))`` as
    the program builds them: ``models/lm.py`` over the block of
    ``models/transformer.py`` with ``layers.kda`` (``ops/gated_delta.py``'s
    rule with a decay a channel), ``layers.mla`` (full-rank queries, a gate
    a head; ``ops/flash_attention.py``'s two-product form) and the held
    share of ``parallel/moe.py:dropless_apply`` behind its group-limited
    router; ``aux`` carries the biases' next values (``state_updates``).
    With ``sizes["probes"]`` (the check's session) the values carry
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
                          "kda_output_std": aux["kda.output_std"],
                          "groups_reached": aux["moe.groups_reached"]},
            params), aux
    return init_with_anchor, checked_loss_fn


def reference_model(sizes):
    """The keyword arguments ``reference_kda_mla_moe.loss`` takes for
    ``sizes``."""
    _supported(sizes)
    return dict(
        layer_types=tuple(layer_types(sizes)),
        heads=sizes["num_attention_heads"], nope=sizes["qk_nope_head_dim"],
        rope=sizes["qk_rope_head_dim"], eps=sizes["rms_norm_eps"],
        theta=float(sizes["rope_theta"]),
        gate_bound=float(sizes["kda_lower_bound"]),
        top_k=sizes["num_experts_per_tok"],
        route_scale=sizes["routed_scaling_factor"],
        groups=sizes["n_group"], groups_kept=sizes["topk_group"],
        held=_held(sizes))


def reference_loss(sizes, plant=None):
    """The same loss in plain float32 ``jax.numpy``
    (``reference_kda_mla_moe.py``).  ``reference.train_losses`` takes Adam
    steps and nothing else, so under the harness's check the reference's
    biases stay where they began while the program's move by
    ``assumed.bias_update_rate`` a step (``check.why`` has what that
    costs).  With ``sizes["probes"]`` the number is :func:`checked_number`,
    from the reference's own forward pass and its own values.  ``plant``
    names one of ``reference_kda_mla_moe.PLANTS`` (``controls_ling.py``)."""
    model = reference_model(sizes)

    def loss_fn(params, batch):
        (tokens,) = batch
        loss, probed = reference_kda_mla_moe.loss_and_probes(
            params, tokens, plant=plant, **model)
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
    """``{part: matrix-multiply parameters one position passes}``: a KDA
    layer's q, k, v and the decay's full-rank projection, the write
    strength's and the head gate's one output a head, and its output
    projection; a latent layer's full-rank query, the latent's two
    matrices, its gate a head and out; the dense layers' SwiGLU; in every
    expert layer the router, the shared expert and ``num_experts_per_tok``
    experts of which the share held here is held / router outputs at an even
    load; the held rows of the head."""
    d = sizes["hidden_size"]
    heads, head = sizes["num_attention_heads"], sizes["head_dim"]
    wide = heads * head
    kda = d * (4 * wide + 2 * heads) + wide * d
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    rank, value = sizes["kv_lora_rank"], sizes["v_head_dim"]
    latent = d * heads * (nope + rope) + d * (rank + rope) \
        + rank * heads * (nope + value) + heads * value * d + d * heads
    kinds = layer_types(sizes)
    dense = sizes["first_k_dense_replace"]
    expert = 3 * d * sizes["moe_intermediate_size"]
    routed = sizes["num_experts_per_tok"] * sizes["num_experts"] \
        / sizes["published"]["num_experts"] * expert
    return {
        "kda_mixers": kinds.count(KDA) * kda,
        "latent_mixers": kinds.count(LATENT) * latent,
        "dense_mlp": dense * 3 * d * sizes["intermediate_size"],
        "expert_layers": (len(kinds) - dense) * (
            expert + d * sizes["published"]["num_experts"] + routed),
        "head": sizes["vocab_size"] * d}


def flops_per_token(sizes, traffic):
    """Forward + backward operations one input position needs, written out:
    ``6 x`` :func:`matmul_parameters` (2 forward, 4 backward); plus ``6 s H
    (score width + value width) / 2`` for each latent layer (q.k^T over 192
    lanes and p.v over 128, three times that with the backward, half under
    the causal mask) and ``21 H d_k d_v`` for each KDA layer (the decay of
    the state's rows, ``S k``, the rank-one write and ``S q`` a head, 7 d_k
    d_v forward, three times that with the backward: what the rule needs,
    ``flops_kda.py``'s count, not what a chunked form spends).  No
    recomputation, no convolution, no norms, no rotary, no embedding lookup,
    no sorting or gathering of the experts' rows, no update of the biases."""
    kinds = layer_types(sizes)
    heads = sizes["num_attention_heads"]
    score = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    attention = kinds.count(LATENT) * 6 * traffic["seq_len"] * heads \
        * (score + sizes["v_head_dim"]) // 2
    rule = kinds.count(KDA) * 21 * heads * sizes["head_dim"] ** 2
    return 6 * sum(matmul_parameters(sizes).values()) + attention + rule


def attention_calls(sizes, traffic):
    """Operand shape of one attention kernel call on one chip (a latent
    layer's; the trace counts the calls), for the generic readers, which
    know one head width: the mean of the score's 192 and the value's 128,
    160, as the JoyAI kind gives it and for its reasons."""
    score = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    return {"batch_heads": traffic["rows_per_chip"]
            * sizes["num_attention_heads"],
            "seq_len": traffic["seq_len"],
            "head_width": (score + sizes["v_head_dim"]) // 2,
            "causal": True}
