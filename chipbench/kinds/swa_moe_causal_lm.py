"""Kind ``swa_moe_causal_lm``: a Laguna-S-2.1-shaped decoder: grouped
key-value heads, sliding-window layers beside full-attention ones with their
own head counts and rotary tables, a sigmoid gate a head on attention's
output, a dense first layer, then layers of softmax-routed experts of which
this chip holds a share beside one shared expert; trained on next-token loss
and the load-balancing term.

A configuration of this kind carries the keys of the source's
``config.json`` (``hidden_size``, ``head_dim``, ``num_key_value_heads``,
``num_attention_heads_per_layer``, ``layer_types``, ``sliding_window``,
``rope_parameters``, ``gating``, ``mlp_only_layers``, ``intermediate_size``,
``moe_intermediate_size``, ``shared_expert_intermediate_size``,
``num_experts`` (here: the experts HELD), ``num_experts_per_tok``,
``moe_routed_scaling_factor``, ``norm_topk_prob``, ``rms_norm_eps``,
``vocab_size``, ...); ``published`` states the source's values of what
``reduced`` names, and the router is as wide as ``published.num_experts``;
what the source leaves to the family's convention is under ``assumed``.
``program`` is the system under test; everything else here is the
yardstick's.
"""
import jax
import numpy as np

from chipbench import flops_swa, reference_swa_moe
from chipbench.kinds import mla_moe_causal_lm as probes

FULL, SLIDING = "full_attention", "sliding_attention"


def _supported(sizes):
    """The model the program and the reference implement: anything else in
    the file is an error, not something to run approximately."""
    layers = sizes["num_hidden_layers"]
    wanted = {"model_type": "laguna", "attention_bias": False,
              "tie_word_embeddings": False, "norm_topk_prob": True,
              "decoder_sparse_step": 1, "gating": "per-head",
              "moe_apply_router_weight_on_input": False,
              "moe_router_logit_softcapping": 0,
              "shared_expert_intermediate_size":
                  sizes["moe_intermediate_size"],
              "gating_types": ["per_head"] * layers,
              "mlp_layer_types": [
                  "dense" if i in sizes["mlp_only_layers"] else "sparse"
                  for i in range(layers)]}
    wrong = {k: sizes[k] for k, v in wanted.items() if sizes[k] != v}
    for key in ("layer_types", "num_attention_heads_per_layer"):
        if len(sizes[key]) != layers:
            wrong[key] = sizes[key]
    if set(sizes["layer_types"]) - {FULL, SLIDING}:
        wrong["layer_types"] = sizes["layer_types"]
    dense = sizes["mlp_only_layers"]
    if dense != list(range(len(dense))) or len(dense) >= layers:
        wrong["mlp_only_layers"] = dense
    if wrong:
        raise ValueError(f"kind swa_moe_causal_lm does not implement {wrong};"
                         f" it wants {wanted}, full and sliding layers, and "
                         f"the dense layers first")


def _held(sizes):
    """``(first, count)``: the experts this chip holds of the router's
    ``published.num_experts``, rank ``deployment.expert_rank``'s."""
    count = sizes["num_experts"]
    return sizes["deployment"]["expert_rank"] * count, count


def _rope(sizes):
    """``{layer type: {"theta", "lanes", "yarn"}}`` of the source's
    ``rope_parameters``: ``lanes`` the lanes of a head that are rotated,
    ``yarn`` None or YaRN's five numbers, under the names that both
    ``layers.yarn_rope_tables`` and the reference take."""
    out = {}
    for kind, given in sizes["rope_parameters"].items():
        yarn = None
        if given["rope_type"] == "yarn":
            yarn = dict(
                factor=float(given["factor"]),
                original_len=given["original_max_position_embeddings"],
                beta_fast=float(given["beta_fast"]),
                beta_slow=float(given["beta_slow"]),
                attention_factor=given["attention_factor"])
        elif given["rope_type"] != "default":
            raise ValueError(f"rope_type {given['rope_type']!r} of {kind}")
        out[kind] = {
            "theta": float(given["rope_theta"]), "yarn": yarn,
            "lanes": int(sizes["head_dim"] * given["partial_rotary_factor"])}
    return out


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
        norm="rmsnorm", norm_eps=sizes["rms_norm_eps"], positions="rope",
        bias=False, tied_head=False, ffn="moe",
        num_experts=sizes["published"]["num_experts"],
        experts_per_token=sizes["num_experts_per_tok"],
        expert_dim=sizes["moe_intermediate_size"],
        norm_topk=sizes["norm_topk_prob"],
        load_balance_coef=sizes["assumed"]["load_balance_coef"],
        layer_types=sizes["layer_types"], expert_scoring="softmax",
        route_scale=sizes["moe_routed_scaling_factor"], shared_experts=1,
        experts_held=_held(sizes), first_dense=len(sizes["mlp_only_layers"]),
        head_dim=sizes["head_dim"], kv_heads=sizes["num_key_value_heads"],
        heads_by_layer=sizes["num_attention_heads_per_layer"],
        window=sizes["sliding_window"]
        if SLIDING in sizes["layer_types"] else None,
        attn_gate=True, rope_by_type=_rope(sizes))


def program(sizes):
    """``(init(key) -> params, loss_fn(params, batch) -> (loss, aux))`` as
    the program builds them: ``models/lm.py`` over the block of
    ``models/transformer.py`` with ``layers.mha`` (grouped heads, a window,
    a gate; ``ops/flash_attention.py``'s kernels) and the held share of
    ``parallel/moe.py:dropless_apply``.  With ``sizes["probes"]`` (the
    check's session) the values carry ``ANCHOR`` and the loss reported is
    the JoyAI kind's ``checked_number``, which this kind shares."""
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
        return probes.checked_number(sizes, loss, aux["moe.held_output_rms"],
                                     params), aux
    return init_with_anchor, checked_loss_fn


def reference_model(sizes):
    """The keyword arguments ``reference_swa_moe.loss`` takes for
    ``sizes``."""
    _supported(sizes)
    return dict(
        layer_types=tuple(sizes["layer_types"]), rope=_rope(sizes),
        head_dim=sizes["head_dim"], eps=sizes["rms_norm_eps"],
        kv_heads=sizes["num_key_value_heads"],
        window=sizes["sliding_window"],
        top_k=sizes["num_experts_per_tok"],
        route_scale=sizes["moe_routed_scaling_factor"], held=_held(sizes),
        balance_coef=sizes["assumed"]["load_balance_coef"])


def reference_loss(sizes):
    """The same loss in plain float32 ``jax.numpy``
    (``reference_swa_moe.py``).  With ``sizes["probes"]`` the number is
    ``checked_number``, from the reference's own forward pass and its own
    values."""
    model = reference_model(sizes)

    def loss_fn(params, batch):
        (tokens,) = batch
        loss, held_output_rms = reference_swa_moe.loss_and_held_output_rms(
            params, tokens, **model)
        if "probes" not in sizes:
            return loss
        return probes.checked_number(sizes, loss, held_output_rms, params)
    return loss_fn


def host_batch(sizes, traffic, rows, rng):
    """Uniform tokens over the rows of the vocabulary held here, ``seq_len``
    + 1 a row: inputs and the targets one ahead; one document a row, no
    packing."""
    return (rng.randint(0, sizes["vocab_size"],
                        (rows, traffic["seq_len"] + 1)).astype(np.int32),)


def tokens_per_row(traffic):
    return traffic["seq_len"]


#: Scores one head computes over a row: ``flops_swa``'s count, which the
#: kernels' own readers use too.
seen_scores = flops_swa.seen_scores


def layer_windows(sizes):
    """Each layer's window: ``sliding_window`` or None."""
    return [sizes["sliding_window"] if kind == SLIDING else None
            for kind in sizes["layer_types"]]


def matmul_parameters(sizes):
    """``{part: matrix-multiply parameters one position passes}``: each
    layer's attention (q and out at its own heads, k and v at the key-value
    heads, the gate); the dense layers' SwiGLU; in every expert layer the
    shared expert, the router and ``num_experts_per_tok`` experts of which
    the share held here is held / router outputs at an even load; the held
    rows of the head."""
    d, head_dim = sizes["hidden_size"], sizes["head_dim"]
    kv = sizes["num_key_value_heads"] * head_dim
    attention = sum(2 * d * heads * head_dim + 2 * d * kv + d * heads
                    for heads in sizes["num_attention_heads_per_layer"])
    dense = len(sizes["mlp_only_layers"])
    expert = 3 * d * sizes["moe_intermediate_size"]
    routed = sizes["num_experts_per_tok"] * sizes["num_experts"] \
        / sizes["published"]["num_experts"] * expert
    return {
        "attention": attention,
        "dense_mlp": dense * 3 * d * sizes["intermediate_size"],
        "expert_layers": (sizes["num_hidden_layers"] - dense) * (
            expert + d * sizes["published"]["num_experts"] + routed),
        "head": sizes["vocab_size"] * d}


def flops_per_token(sizes, traffic):
    """Forward + backward operations one input position needs, written out:
    ``6 x`` :func:`matmul_parameters` (2 forward, 4 backward) plus, for each
    layer's attention, ``12 x heads x head_dim x`` the scores a head really
    sees a position (:func:`seen_scores` over the row's length: all behind
    the diagonal in a full layer, those inside the window in a sliding one):
    q.k^T and p.v, three times that with the backward.  No recomputation,
    no norms, no rotary, no embedding lookup, no sorting or gathering of
    the experts' rows."""
    seq = traffic["seq_len"]
    attention = sum(
        12 * heads * sizes["head_dim"] * seen_scores(seq, window) / seq
        for heads, window in zip(sizes["num_attention_heads_per_layer"],
                                 layer_windows(sizes)))
    return 6 * sum(matmul_parameters(sizes).values()) + attention


def attention_calls(sizes, traffic):
    """Operand shape of one attention kernel call on one chip, for the
    generic readers (``attn_kernel_roofline``), which know one shape a cell
    and count a causal call's scores as ``seq^2 / 2`` a head: the head count
    at which ``layers x`` that shape's operations are the operations of the
    scores the model's kernels really see.  A layer of ``H`` heads under a
    window sees ``H x seen_scores(seq, window)`` scores, a full layer ``H x
    seq^2 / 2`` by the yardstick's own count, so the equivalent head count
    is their mean over the layers: at 4,096 with a window of 512,
    ``seen_scores`` = 1,966,336 = 0.2344 of 8,388,608, and three sliding
    layers of 72 beside two full ones of 48 give ``(3 x 72 x 0.2344 + 2 x
    48) / 5 = 29.33`` heads.  A full-causal 72-head shape would hold the
    sliding layers to four times their work.  The bytes it implies (q, k,
    v, o of 29.33 heads) are near the kernels' own, which move q and o a
    query head and k and v a key-value head; the kernels are bound by
    operations either way.  ``swa_kernel_roofline`` and
    ``gqa_kernel_roofline`` count each kind by ``flops_swa.py``."""
    seq = traffic["seq_len"]
    causal = seq * seq / 2
    heads = [h * (1.0 if window is None
                  else seen_scores(seq, window) / causal)
             for h, window in zip(sizes["num_attention_heads_per_layer"],
                                  layer_windows(sizes))]
    return {"batch_heads": traffic["rows_per_chip"] * sum(heads) / len(heads),
            "seq_len": seq, "head_width": sizes["head_dim"], "causal": True}
