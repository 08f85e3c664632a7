"""Kind ``mla_moe_causal_lm``: a JoyAI-LLM-Flash-shaped decoder (DeepSeek-V3's
layer): latent attention, a dense first layer, then layers of sigmoid-routed
experts of which this chip holds a share beside one shared expert, and one
multi-token-prediction module; trained on next-token loss, the module's loss
two tokens ahead, and the sequence-wise balance term, in a step that also
moves the routers' selection biases.

A configuration of this kind carries the keys of the source's
``config.json`` (``hidden_size``, ``num_attention_heads``,
``num_hidden_layers``, ``first_k_dense_replace``, ``intermediate_size``,
``moe_intermediate_size``, ``n_routed_experts`` (here: the experts HELD),
``n_shared_experts``, ``num_experts_per_tok``, ``routed_scaling_factor``,
``scoring_func``, ``topk_method``, ``norm_topk_prob``, ``q_lora_rank``,
``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
``rope_theta``, ``rope_interleave``, ``rms_norm_eps``,
``num_nextn_predict_layers``, ``vocab_size``, ...); ``published`` states the
source's values of what ``reduced`` names, and the router is as wide as
``published.n_routed_experts``; what the source leaves to the training
recipe is under ``assumed``.  ``program`` is the system under test;
everything else here is the yardstick's.
"""
import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference_mla_moe

#: Where a session carries probes (below), its values hold under this name
#: a sample of every variable as it was initialised.
ANCHOR = "check_anchor"


def _supported(sizes):
    """The model the program and the reference implement: anything else in
    the file is an error, not something to run approximately."""
    wanted = {"model_type": "joyai_llm_flash", "hidden_act": "silu",
              "attention_bias": False, "tie_word_embeddings": False,
              "rope_scaling": None, "rope_interleave": True,
              "scoring_func": "sigmoid", "topk_method": "noaux_tc",
              "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
              "moe_layer_freq": 1, "n_shared_experts": 1,
              "num_nextn_predict_layers": 1,
              "num_key_value_heads": sizes["num_attention_heads"],
              "qk_head_dim": sizes["qk_nope_head_dim"]
              + sizes["qk_rope_head_dim"]}
    wrong = {k: sizes[k] for k, v in wanted.items() if sizes[k] != v}
    if not 0 < sizes["first_k_dense_replace"] < sizes["num_hidden_layers"]:
        wrong["first_k_dense_replace"] = sizes["first_k_dense_replace"]
    if wrong:
        raise ValueError(f"kind mla_moe_causal_lm does not implement {wrong}; "
                         f"it wants {wanted} and a dense layer before the "
                         f"expert layers")


def _held(sizes):
    """``(first, count)``: the experts this chip holds of the router's
    ``published.n_routed_experts``, rank ``deployment.expert_rank``'s."""
    count = sizes["n_routed_experts"]
    return sizes["deployment"]["expert_rank"] * count, count


def config(sizes):
    """The program's ``TransformerConfig`` of ``sizes``."""
    from autodist_tpu.models import transformer as T
    _supported(sizes)
    assumed = sizes["assumed"]
    layers = sizes["num_hidden_layers"]
    return T.TransformerConfig(
        vocab=sizes["vocab_size"], dim=sizes["hidden_size"],
        num_heads=sizes["num_attention_heads"], num_layers=layers,
        mlp_dim=sizes["intermediate_size"],
        max_len=sizes["max_position_embeddings"], causal=True,
        dtype=jnp.dtype(sizes["deployment"]["compute_dtype"]),
        norm="rmsnorm", norm_eps=sizes["rms_norm_eps"], positions="none",
        rope_theta=float(sizes["rope_theta"]), bias=False, tied_head=False,
        ffn="moe", num_experts=sizes["published"]["n_routed_experts"],
        experts_per_token=sizes["num_experts_per_tok"],
        expert_dim=sizes["moe_intermediate_size"],
        norm_topk=sizes["norm_topk_prob"],
        load_balance_coef=assumed["sequence_balance_coef"],
        layer_types=["latent_attention"] * layers,
        expert_scoring=sizes["scoring_func"],
        route_scale=sizes["routed_scaling_factor"],
        shared_experts=sizes["n_shared_experts"], select_bias=True,
        bias_update_rate=assumed["bias_update_rate"],
        experts_held=_held(sizes),
        first_dense=sizes["first_k_dense_replace"],
        q_rank=sizes["q_lora_rank"], kv_rank=sizes["kv_lora_rank"],
        nope_dim=sizes["qk_nope_head_dim"],
        rope_dim=sizes["qk_rope_head_dim"], value_dim=sizes["v_head_dim"],
        mtp_depth=sizes["num_nextn_predict_layers"],
        mtp_coef=assumed["mtp_loss_coef"])


def _sample(leaf, samples):
    flat = leaf.reshape(-1)
    return flat[::max(1, flat.size // samples)]


def update_mean_square(params, learning_rate, samples):
    """How far the optimizer has moved the variables, in steps of the
    learning rate: the mean over the variables of the mean square of
    (value - value at initialisation) / ``learning_rate``, over the sample
    of each that ``params[ANCHOR]`` holds.  Adam moves an entry about one
    learning rate a step whatever its gradient's size, so this reads 0
    before the first step and about 1 after it on either side of the
    check, less where a variable was not updated, and other where the
    values are kept in fewer bits than the 24 of float32 (a bfloat16 value
    near 0.02 moves in steps of 1.2e-4 or not at all).  The selection
    biases are left out: the step writes them, Adam does not, and the
    harness's reference (``reference.train_losses``) cannot move them."""
    moved = [jnp.mean(jnp.square(
                 (_sample(value, samples).astype(jnp.float32) - anchor)
                 / learning_rate))
             for (path, value), anchor in zip(
                 jax.tree_util.tree_flatten_with_path(
                     {k: v for k, v in params.items() if k != ANCHOR})[0],
                 jax.tree_util.tree_leaves(params[ANCHOR]))
             if path[-1].key != "bias"]
    return sum(moved) / len(moved)


def checked_number(sizes, loss, held_output_rms, params):
    """What a step of a session with ``sizes["probes"]`` reports as its
    loss: the loss, and added to it with no gradient of their own (so the
    steps taken are those of the loss alone) two numbers the loss hardly
    feels, each at the probe's weight: what the held experts add to their
    layers' outputs (``aux["moe.held_output_rms"]``; the reference's own
    from its own forward pass), and :func:`update_mean_square`.  The
    harness compares one number a step (``drivers/train.py:
    reference_check``), and behind a pre-norm residual and a final RMSNorm
    the loss alone does not see the routed experts nor the precision of the
    state (the configuration's ``check.why`` has the readings)."""
    probes = sizes["probes"]
    return loss + jax.lax.stop_gradient(
        probes["held_output_rms"] * held_output_rms
        + probes["update_mean_square"] * update_mean_square(
            params, sizes["deployment"]["optimizer"]["learning_rate"],
            probes["anchor_samples"]))


def program(sizes):
    """``(init(key) -> params, loss_fn(params, batch) -> (loss, aux))`` as
    the program builds them: ``models/lm.py`` over the block of
    ``models/transformer.py`` with ``layers.mla``, the held share of
    ``parallel/moe.py:dropless_apply`` and the prediction module; ``aux``
    carries the biases' next values (``state_updates``).  With
    ``sizes["probes"]`` (the check's session) the values carry
    ``ANCHOR`` and the loss reported is :func:`checked_number`."""
    from autodist_tpu.models import lm
    cfg = config(sizes)
    init, loss_fn = (lambda key: lm.init(key, cfg)), lm.make_loss_fn(cfg)
    if "probes" not in sizes:
        return init, loss_fn
    samples = sizes["probes"]["anchor_samples"]

    def init_with_anchor(key):
        values = init(key)
        return {**values, ANCHOR: jax.tree_util.tree_map(
            lambda x: _sample(x, samples), values)}

    def checked_loss_fn(params, batch):
        loss, aux = loss_fn(
            {k: v for k, v in params.items() if k != ANCHOR}, batch)
        return checked_number(sizes, loss, aux["moe.held_output_rms"],
                              params), aux
    return init_with_anchor, checked_loss_fn


def reference_model(sizes):
    """The keyword arguments ``reference_mla_moe.loss`` and its siblings take
    for ``sizes``."""
    _supported(sizes)
    assumed = sizes["assumed"]
    return dict(
        layers=sizes["num_hidden_layers"],
        heads=sizes["num_attention_heads"], nope=sizes["qk_nope_head_dim"],
        rope=sizes["qk_rope_head_dim"], eps=sizes["rms_norm_eps"],
        theta=float(sizes["rope_theta"]),
        top_k=sizes["num_experts_per_tok"],
        route_scale=sizes["routed_scaling_factor"], held=_held(sizes),
        mtp_coef=assumed["mtp_loss_coef"],
        balance_coef=assumed["sequence_balance_coef"])


def reference_loss(sizes):
    """The same loss in plain float32 ``jax.numpy``
    (``reference_mla_moe.py``).  ``reference.train_losses`` takes Adam steps
    and nothing else, so under the harness's check the reference's biases
    stay where they began while the program's move by 0.001 a step (the
    configuration's ``check.why`` has what that costs);
    ``reference_mla_moe.train`` moves them, and is what the tests and the
    builder's chip run compare with.  With ``sizes["probes"]`` the number
    is :func:`checked_number`, from the reference's own forward pass and
    its own values."""
    model = reference_model(sizes)

    def loss_fn(params, batch):
        (tokens,) = batch
        loss, held_output_rms = reference_mla_moe.loss_and_held_output_rms(
            params, tokens, **model)
        if "probes" not in sizes:
            return loss
        return checked_number(sizes, loss, held_output_rms, params)
    return loss_fn


def host_batch(sizes, traffic, rows, rng):
    """Uniform tokens over the rows of the vocabulary held here, ``seq_len``
    + ``targets_ahead`` a row: inputs, the targets one ahead and the
    prediction module's two ahead; one document a row, no packing."""
    return (rng.randint(
        0, sizes["vocab_size"],
        (rows, traffic["seq_len"] + traffic["targets_ahead"]))
        .astype(np.int32),)


def tokens_per_row(traffic):
    return traffic["seq_len"]


def matmul_parameters(sizes):
    """``{part: matrix-multiply parameters one position passes}``: each of
    the ``num_hidden_layers`` + 1 blocks' latent attention (its five
    matrices); the dense layers' SwiGLU; in every expert layer (the
    module's too) the shared expert, the router and ``num_experts_per_tok``
    experts of which the share held here is held / router outputs at an
    even load; the module's projection; the held rows of the head, twice
    (the model's use and the module's)."""
    d = sizes["hidden_size"]
    heads = sizes["num_attention_heads"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    q_rank, kv_rank = sizes["q_lora_rank"], sizes["kv_lora_rank"]
    mla = d * q_rank + q_rank * heads * (nope + rope) + d * (kv_rank + rope) \
        + kv_rank * heads * (nope + sizes["v_head_dim"]) \
        + heads * sizes["v_head_dim"] * d
    dense = sizes["first_k_dense_replace"]
    blocks = sizes["num_hidden_layers"] + sizes["num_nextn_predict_layers"]
    expert = 3 * d * sizes["moe_intermediate_size"]
    routed = sizes["num_experts_per_tok"] * sizes["n_routed_experts"] \
        / sizes["published"]["n_routed_experts"] * expert
    return {
        "latent_attention": blocks * mla,
        "dense_mlp": dense * 3 * d * sizes["intermediate_size"],
        "expert_layers": (blocks - dense) * (
            sizes["n_shared_experts"] * expert
            + d * sizes["published"]["n_routed_experts"] + routed),
        "mtp_projection": sizes["num_nextn_predict_layers"] * 2 * d * d,
        "head": (1 + sizes["num_nextn_predict_layers"])
        * sizes["vocab_size"] * d}


def flops_per_token(sizes, traffic):
    """Forward + backward operations one input position needs, written out:
    ``6 x`` :func:`matmul_parameters` (2 forward, 4 backward) plus, for each
    of the blocks' attentions, ``6 x s x heads x (score width + value
    width) / 2``: q.k^T over 192 lanes and p.v over 128, three times that
    with the backward, half under the causal mask.  No recomputation, no
    norms, no rotary, no embedding lookup, no sorting or gathering of the
    experts' rows, no update of the biases."""
    blocks = sizes["num_hidden_layers"] + sizes["num_nextn_predict_layers"]
    score = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    attention = blocks * 6 * traffic["seq_len"] \
        * sizes["num_attention_heads"] * (score + sizes["v_head_dim"]) // 2
    return 6 * sum(matmul_parameters(sizes).values()) + attention


def attention_calls(sizes, traffic):
    """Operand shape of one attention kernel call on one chip, for the
    generic readers (``attn_kernel_roofline``), which know one head width:
    the mean of the score's 192 and the value's 128, 160, which is exact for
    ``flash_fwd`` (a product over each) and ``flash_bwd_dkv`` (two over
    each) and 6% low for ``flash_bwd_dq`` (two over 192, one over 128: 512
    lanes of products where 3 x 160 says 480); its bytes count q, k, v, o
    at 160 lanes where the kernels move 128-wide q_nope, k_nope, v, o, a
    64-wide q_rope a head and a 64-wide k_rope a position.
    ``mla_kernel_roofline`` counts what the kernels really make
    (``flops_mla.py``)."""
    score = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    return {"batch_heads": traffic["rows_per_chip"]
            * sizes["num_attention_heads"],
            "seq_len": traffic["seq_len"],
            "head_width": (score + sizes["v_head_dim"]) // 2,
            "causal": True}
