"""Decoder-only causal language model (lm1b-class benchmark config).

Benchmark parity: the driver baseline names an lm1b 1B-word LM under sharded
PS, multi-host (BASELINE.md); the reference's closest driver is
``/root/reference/examples/benchmark/bert.py``'s language-model path.
"""
import jax
import jax.numpy as jnp
import numpy as np

from autodist_tpu.models import layers as L
from autodist_tpu.models import transformer as T


def lm1b(vocab=32000, dtype=jnp.bfloat16):
    return T.TransformerConfig(vocab=vocab, dim=1024, num_heads=16,
                               num_layers=16, max_len=1024, causal=True,
                               dtype=dtype)


def lm_tiny(vocab=256, dtype=jnp.float32, max_len=64):
    return T.TransformerConfig(vocab=vocab, dim=64, num_heads=4, num_layers=2,
                               max_len=max_len, causal=True, dtype=dtype)


def olmoe_1b_7b(num_layers=16, dtype=jnp.bfloat16):
    """OLMoE-1B-7B (allenai/OLMoE-1B-7B-0125-Instruct ``config.json``,
    arXiv:2409.02060): pre-RMSNorm, rotary positions, QK-norm, no biases,
    16 heads of 128, 64 SwiGLU experts of 1,024 with 8 a token whose
    weights are not renormalised, an untied head; trained with the
    load-balancing term at 0.01 and the router z-loss at 0.001."""
    return T.TransformerConfig(
        vocab=50304, dim=2048, num_heads=16,
        num_layers=num_layers, max_len=4096, causal=True, dtype=dtype,
        norm="rmsnorm", norm_eps=1e-5, positions="rope", rope_theta=10000.0,
        qk_norm=True, bias=False, tied_head=False, ffn="moe",
        num_experts=64, experts_per_token=8, expert_dim=1024,
        norm_topk=False, load_balance_coef=0.01, router_z_coef=0.001)


def olmo_hybrid_7b(num_layers=32, vocab=100352, dtype=jnp.bfloat16):
    """Olmo-Hybrid-7B (allenai/Olmo-Hybrid-7B ``config.json``): three
    gated-delta-rule layers (30 heads of 96 x 192, a 4-tap convolution,
    eigenvalues down to -1) to every full-attention layer (30 heads of 128,
    QK-norm, no rotary: ``rope_theta`` is null), RMSNorm on each sublayer's
    output, a SwiGLU MLP of 11,008, no biases, an untied head.
    ``num_layers`` keeps the pattern's first layers (a period is four)."""
    period = (T.LINEAR,) * 3 + (T.FULL,)
    return T.TransformerConfig(
        vocab=vocab, dim=3840, num_heads=30, num_layers=num_layers,
        mlp_dim=11008, max_len=65536, causal=True, dtype=dtype,
        norm="rmsnorm", norm_eps=1e-6, positions="none", qk_norm=True,
        bias=False, tied_head=False, ffn="swiglu",
        layer_types=[period[i % 4] for i in range(num_layers)],
        linear_heads=30, linear_key_dim=96, linear_value_dim=192,
        conv_width=4, allow_neg_eigval=True, norm_position="output")


def joyai_llm_flash(num_layers=40, vocab=129280, experts_held=None,
                    dtype=jnp.bfloat16):
    """JoyAI-LLM-Flash (jdopensource/JoyAI-LLM-Flash ``config.json``;
    DeepSeek-V3's layer at another size): width 2,048; latent attention with
    32 heads, queries through a latent of 1,536, keys and values through
    one of 512, scores 128 + 64 wide (adjacent-pair rotary, theta
    32,000,000, one rotary key a position), values 128; the first layer's
    feed-forward a dense SwiGLU of 7,168, every later one 256 routed SwiGLU
    experts of 768, 8 a token by sigmoid scores plus a selection bias,
    weights over their sum times 2.5, beside one shared expert; one
    multi-token-prediction module; RMSNorm eps 1e-6, no bias, an untied
    head.  ``experts_held = (first, count)`` is one rank's share of each
    expert layer (``parallel/moe.py``).  Not in ``config.json``, taken from
    DeepSeek-V3's report (arXiv:2412.19437): the bias moves 0.001 a step,
    the sequence-wise balance term enters at 1e-4, the module's loss at
    0.3."""
    return T.TransformerConfig(
        vocab=vocab, dim=2048, num_heads=32, num_layers=num_layers,
        mlp_dim=7168, max_len=131072, causal=True, dtype=dtype,
        norm="rmsnorm", norm_eps=1e-6, positions="none",
        rope_theta=32000000.0, bias=False, tied_head=False, ffn="moe",
        num_experts=256, experts_per_token=8, expert_dim=768, norm_topk=True,
        load_balance_coef=1e-4, layer_types=[T.LATENT] * num_layers,
        expert_scoring="sigmoid", route_scale=2.5, shared_experts=1,
        select_bias=True, bias_update_rate=0.001, experts_held=experts_held,
        first_dense=1, q_rank=1536, kv_rank=512, nope_dim=128, rope_dim=64,
        value_dim=128, mtp_depth=1, mtp_coef=0.3)


def laguna_s_2_1(num_layers=48, vocab=100352, experts_held=None,
                 dtype=jnp.bfloat16):
    """Laguna-S-2.1 (poolside/Laguna-S-2.1 ``config.json``): width 3,072;
    a period of four layers, full attention then three sliding ones; heads
    of 128 over 8 key-value heads, 48 query heads in a full layer and 72 in
    a sliding one, whose window is 512 keys; sliding layers rotate all 128
    lanes (rotate-half, theta 10,000), full layers the first 64 by YaRN
    (theta 500,000, factor 128 over 8,192, beta 32 / 1, cos and sin times
    1.4852...); a sigmoid gate a head on attention's output; the first
    layer's feed-forward a dense SwiGLU of 12,288, every later one 256
    routed SwiGLU experts of 1,024, 10 a token by softmax scores, weights
    over their sum times 2.5, beside one shared expert; RMSNorm eps 1e-6,
    no bias, an untied head.  ``num_layers`` keeps the pattern's first
    layers; ``experts_held = (first, count)`` is one rank's share of each
    expert layer (``parallel/moe.py``).  Not in ``config.json`` (the
    benchmark's configuration file lists each with its reason): the gate's
    form, softmax scoring, no QK-norm, no gate on the shared expert, the
    load-balancing term at 0.001."""
    period = (T.FULL,) + (T.SLIDING,) * 3
    kinds = [period[i % 4] for i in range(num_layers)]
    return T.TransformerConfig(
        vocab=vocab, dim=3072, num_heads=48, num_layers=num_layers,
        mlp_dim=12288, max_len=1048576, causal=True, dtype=dtype,
        norm="rmsnorm", norm_eps=1e-6, positions="rope", bias=False,
        tied_head=False, ffn="moe", num_experts=256, experts_per_token=10,
        expert_dim=1024, norm_topk=True, load_balance_coef=0.001,
        layer_types=kinds, expert_scoring="softmax", route_scale=2.5,
        shared_experts=1, experts_held=experts_held, first_dense=1,
        head_dim=128, kv_heads=8,
        heads_by_layer=[48 if kind == T.FULL else 72 for kind in kinds],
        window=512, attn_gate=True, rope_by_type={
            T.FULL: {"theta": 500000.0, "lanes": 64, "yarn": dict(
                factor=128.0, original_len=8192, beta_fast=32.0,
                beta_slow=1.0, attention_factor=1.4852030263919618)},
            T.SLIDING: {"theta": 10000.0, "lanes": None, "yarn": None}})


def qwen3_next_80b_a3b(num_layers=48, vocab=151936, experts_held=None,
                       experts_held_chunks=None, dtype=jnp.bfloat16):
    """Qwen3-Next-80B-A3B (Qwen/Qwen3-Next-80B-A3B-Instruct ``config.json``):
    width 2,048; a period of four layers, three gated-delta-rule layers (32
    value heads of 128 reading 16 key heads of 128, a 4-tap convolution,
    ``beta = sigmoid(b)``) then full attention (16 heads of 256 over 2
    key-value heads, an RMSNorm over each head's lanes for q and for k, 64 of
    a head's 256 lanes rotated at theta 10,000,000, a sigmoid gate a lane on
    the output); every layer's feed-forward 512 routed SwiGLU experts of 512,
    10 a token by softmax scores, weights over their sum, beside one shared
    expert times the sigmoid of a scalar a token; pre-RMSNorm eps 1e-6, no
    bias, an untied head.  ``num_layers`` keeps the pattern's first layers;
    ``experts_held = (first, count)`` is one rank's share of each expert
    layer (``parallel/moe.py``) and ``experts_held_chunks`` the chunks it
    takes a step's assignments in (a rank of sixteen names fewer than
    sixteen, so that its even share sits inside a chunk).  Not in the
    catalog's ``config``: the
    load-balancing term at 0.001 (the family's ``router_aux_loss_coef``
    default) and the multi-token-prediction module, which is not built."""
    period = (T.LINEAR,) * 3 + (T.FULL,)
    return T.TransformerConfig(
        vocab=vocab, dim=2048, num_heads=16, num_layers=num_layers,
        mlp_dim=5120, max_len=262144, causal=True, dtype=dtype,
        norm="rmsnorm", norm_eps=1e-6, positions="rope", qk_norm="head",
        bias=False, tied_head=False, ffn="moe", num_experts=512,
        experts_per_token=10, expert_dim=512, norm_topk=True,
        load_balance_coef=0.001,
        layer_types=[period[i % 4] for i in range(num_layers)],
        linear_heads=32, linear_key_heads=16, linear_key_dim=128,
        linear_value_dim=128, conv_width=4, allow_neg_eigval=False,
        expert_scoring="softmax", shared_experts=1, shared_gate=True,
        experts_held=experts_held, experts_held_chunks=experts_held_chunks,
        head_dim=256, kv_heads=2, attn_gate="lane",
        rope_by_type={T.FULL: {"theta": 1e7, "lanes": 64, "yarn": None}})


def ling_3_0_flash(num_layers=42, vocab=157184, experts_held=None,
                   first_dense=2, dtype=jnp.bfloat16):
    """The language model of Ling-3.0-flash-VL (inclusionAI/Ling-3.0-flash-VL
    ``config.json``): width 2,560; a period of six layers
    (``layer_group_size``), five KDA layers (the delta rule with a decay a
    channel: 32 heads of 128 / 128, a 4-tap convolution, the gate bounded
    below by -5, a head-wise output gate) then latent attention (32 heads,
    full-rank queries, keys and values through a latent of 512, scores 128 +
    64 wide with adjacent-pair rotary at theta 6,000,000, values 128, a
    sigmoid gate a head); the first ``first_dense`` layers' feed-forward a
    dense SwiGLU of 6,144, every later one 512 routed SwiGLU experts of 768,
    8 a token by sigmoid scores plus a selection bias, chosen inside the 4
    of 8 groups of 64 whose two best scores sum highest, weights over their
    sum times 2.5, beside one shared expert; RMSNorm eps 1e-6, no bias, an
    untied head.  ``num_layers`` keeps the pattern's first layers;
    ``experts_held = (first, count)`` is one rank's share of each expert
    layer (``parallel/moe.py``).  Not in ``config.json`` (the benchmark's
    configuration file lists each with its reason): the gates' forms, the
    initial decays, the bias's 0.001 a step, no auxiliary balance term.
    Not built: the vision tower, the prediction module, the clamped SwiGLU
    of the layers from 34 up."""
    period = (T.KDA,) * 5 + (T.LATENT,)
    return T.TransformerConfig(
        vocab=vocab, dim=2560, num_heads=32, num_layers=num_layers,
        mlp_dim=6144, max_len=131072, causal=True, dtype=dtype,
        norm="rmsnorm", norm_eps=1e-6, positions="none",
        rope_theta=6000000.0, bias=False, tied_head=False, ffn="moe",
        num_experts=512, experts_per_token=8, expert_dim=768, norm_topk=True,
        layer_types=[period[i % 6] for i in range(num_layers)],
        linear_heads=32, linear_key_dim=128, linear_value_dim=128,
        conv_width=4, linear_gate_bound=-5.0, expert_scoring="sigmoid",
        route_scale=2.5, shared_experts=1, select_bias=True,
        bias_update_rate=0.001, experts_held=experts_held,
        expert_groups=8, expert_groups_kept=4, first_dense=first_dense,
        q_rank=0, kv_rank=512, nope_dim=128, rope_dim=64, value_dim=128,
        attn_gate=True)


def ouro_2_6b(num_layers=48, vocab=49152, dtype=jnp.bfloat16):
    """Ouro-2.6B (ByteDance/Ouro-2.6B ``config.json``; arXiv:2510.25741,
    "Scaling Latent Reasoning via Looped Language Models"): width 2,048; 48
    layers run four times over with the same variables
    (``total_ut_steps``), each 16 heads of 128 over as many key-value heads
    (rotate-half rotary over a head's 128 lanes, theta 1,000,000) and a
    SwiGLU MLP of 5,632, an RMSNorm (eps 1e-6) on each sublayer's input and
    on its output; one final norm after every pass, from which the next
    pass starts; an untied head and a cross-entropy on every pass, weighed
    by an exit gate's distribution.  ``num_layers`` keeps the stack's first
    layers.  Not in ``config.json`` (the benchmark's configuration file
    lists each with its reason): the norms' places, the gate's form and the
    entropy term at 0.05."""
    return T.TransformerConfig(
        vocab=vocab, dim=2048, num_heads=16, num_layers=num_layers,
        mlp_dim=5632, max_len=65536, causal=True, dtype=dtype,
        norm="rmsnorm", norm_eps=1e-6, positions="rope", rope_theta=1e6,
        bias=False, tied_head=False, ffn="swiglu", norm_position="sandwich",
        loops=4, exit_entropy_coef=0.05, recompute="pointwise")


def init(key, cfg):
    return T.init(key, cfg)


def make_loss_fn(cfg, attn_fn=None):
    """Next-token loss. batch = (tokens,) — inputs are tokens[:-1], targets tokens[1:].

    For ``ffn="moe"`` the function returns ``(loss, aux)`` (``capture``
    sees the pair in its trace of the loss): the loss is the
    cross-entropy plus the mean over the expert layers of the
    load-balancing term and of the router z-loss at the configuration's
    coefficients; ``aux`` holds the three terms and the router's
    statistics under the names of docs/observability.md.  With
    ``"linear_attention"`` layers it returns the pair too, ``aux`` holding
    ``gdn.state_absmax``, the largest magnitude of any such layer's final
    state; with ``"kda_attention"`` layers ``kda.state_absmax`` likewise and
    ``kda.gate_min``, the most negative log decay summed inside a sub-block
    of the chunked rule (what stands between it and an overflow).  With
    ``cfg.mixer_stats`` it returns the pair, ``aux`` holding
    ``attn.output_std``, ``gdn.output_std`` and ``kda.output_std``: the mean over the layers of
    each kind of the root mean square of the mixer's output about its mean
    over a row's positions.

    With ``cfg.mtp_depth`` a row holds one token more, the inputs are
    ``tokens[:-2]``, and the prediction module's cross-entropy to the
    token after next (``aux["mtp.xent"]``) enters at ``cfg.mtp_coef``.
    Expert layers with a selection bias put its next value under the
    reserved ``aux["state_updates"]`` (variable name -> value), which the
    Runner's step writes (``GraphItem.capture``).

    With ``cfg.loops`` above 1 the loss is the looped model's
    (:func:`looped_objective`): a head and a cross-entropy on every pass,
    weighed position by position by the exit gate's distribution.
    """
    if cfg.loops > 1:
        return _looped_loss_fn(cfg, attn_fn)
    ahead = 1 + cfg.mtp_depth

    def loss_fn(params, batch):
        (tokens,) = batch if isinstance(batch, (tuple, list)) else (batch,)
        hidden, stats = T.encode_with_stats(params, cfg, tokens[:, :-ahead],
                                            attn_fn=attn_fn)
        with jax.named_scope("lm_head"):
            lg = T.logits(params, cfg, hidden)
            xent = L.softmax_xent(lg, tokens[:, 1:tokens.shape[1] - ahead + 1])
        mtp_xent = None
        if cfg.mtp_depth:
            predicted, mtp_stats = T.mtp_hidden(
                params, cfg, hidden, tokens[:, 1:-1], attn_fn=attn_fn)
            stats = stats + [mtp_stats] if mtp_stats else stats
            with jax.named_scope("mtp"), jax.named_scope("lm_head"):
                mtp_xent = L.softmax_xent(T.logits(params, cfg, predicted),
                                          tokens[:, 2:])
        if not stats and mtp_xent is None:
            return xent

        def over_layers(name, reduce=jnp.mean):
            return reduce(jnp.stack([s[name] for s in stats if name in s]))

        def reported(name):
            return any(name in s for s in stats)

        aux, loss = {"xent": xent}, xent
        if mtp_xent is not None:
            aux["mtp.xent"] = mtp_xent
            loss = loss + cfg.mtp_coef * mtp_xent
        if cfg.ffn == "moe":
            aux["moe.load_balance_loss"] = over_layers("load_balance")
            if reported("z_loss"):
                aux["moe.router_z_loss"] = over_layers("z_loss")
            aux["moe.load_max_over_mean"] = over_layers("load_max_over_mean",
                                                        jnp.max)
            aux["moe.dropped"] = over_layers("dropped", jnp.sum)
            loss = loss + cfg.load_balance_coef * aux["moe.load_balance_loss"]
            if reported("z_loss"):
                loss = loss + cfg.router_z_coef * aux["moe.router_z_loss"]
            if reported("held_assignments"):
                aux["moe.held_assignments"] = over_layers("held_assignments",
                                                          jnp.sum)
            if reported("held_buffer_rows"):
                aux["moe.held_buffer_rows"] = over_layers("held_buffer_rows")
            if reported("held_output_rms"):
                aux["moe.held_output_rms"] = over_layers("held_output_rms")
            if reported("bias_absmax"):
                aux["moe.bias_absmax"] = over_layers("bias_absmax", jnp.max)
                aux["state_updates"] = {
                    name: value for s in stats
                    for name, value in s.get("state_updates", {}).items()}
        if reported("gdn_state_absmax"):
            aux["gdn.state_absmax"] = over_layers("gdn_state_absmax",
                                                  jnp.max)
        if reported("kda_state_absmax"):
            aux["kda.state_absmax"] = over_layers("kda_state_absmax",
                                                  jnp.max)
            aux["kda.gate_min"] = over_layers("kda_gate_min", jnp.min)
        if reported("groups_reached"):
            aux["moe.groups_reached"] = over_layers("groups_reached")
        for mixer in ("attn", "gdn", "kda"):
            if reported(f"{mixer}_output_std"):
                aux[f"{mixer}.output_std"] = over_layers(
                    f"{mixer}_output_std")
        return loss, aux
    return loss_fn


def looped_objective(params, cfg, hidden, labels):
    """A looped model's loss from its passes' states (arXiv:2510.25741's
    entropy-regularised objective with a uniform prior), ``(loss, aux)``.
    With ``h_t = hidden[t]`` the states after pass t's final norm
    (``transformer.encode_passes``), ``l_t(i)`` the cross-entropy of position
    i under ``logits_t = h_t W_head`` and ``p_t(i)`` the exit distribution
    (``transformer.exit_distribution``):

        loss = mean_i [ sum_t p_t(i) l_t(i) - beta H(p(i)) ],
        H(p) = -sum_t p_t log p_t,  beta = cfg.exit_entropy_coef

    Each pass's head and cross-entropy run under ``pass<t>/lm_head`` (float32
    logits), the weighing under ``exit_loss``.  ``aux`` holds ``loop.xent``
    (a value a pass: the mean cross-entropy), ``loop.exit_pdf`` (a value a
    pass: the mean of ``p_t``), ``loop.exit_entropy`` (the mean of ``H``)
    and ``xent``, the last pass's."""
    xent = []
    for t, h in enumerate(hidden):
        with jax.named_scope(f"pass{t}"), jax.named_scope("lm_head"):
            logp = jax.nn.log_softmax(T.logits(params, cfg, h))
            xent.append(-jnp.take_along_axis(logp, labels[..., None],
                                             axis=-1)[..., 0])
    log_p = T.exit_distribution(params, hidden)
    with jax.named_scope("exit_loss"):
        xent, p = jnp.stack(xent), jnp.exp(log_p)
        entropy = -jnp.sum(p * log_p, axis=0)
        loss = jnp.mean(jnp.sum(p * xent, axis=0)
                        - cfg.exit_entropy_coef * entropy)
        by_pass = xent.mean(axis=(1, 2))
        aux = {"xent": by_pass[-1], "loop.xent": by_pass,
               "loop.exit_pdf": p.mean(axis=(1, 2)),
               "loop.exit_entropy": entropy.mean()}
    return loss, aux


def _looped_loss_fn(cfg, attn_fn=None):
    """``loss_fn(params, batch) -> (loss, aux)`` of a looped model: every
    pass's states (``transformer.encode_passes``) under
    :func:`looped_objective`."""
    def loss_fn(params, batch):
        (tokens,) = batch if isinstance(batch, (tuple, list)) else (batch,)
        hidden, _ = T.encode_passes(params, cfg, tokens[:, :-1],
                                    attn_fn=attn_fn)
        return looped_objective(params, cfg, hidden, tokens[:, 1:])
    return loss_fn


def make_decode_fn(cfg):
    """``(params, cache, tokens, pos) -> (logits, new_cache)`` — the
    apply fn the decode engine AOT-compiles per (slots, cache_len)
    bucket (serve/decode.py)."""
    def decode_fn(params, cache, tokens, pos):
        return T.decode_step(params, cfg, cache, tokens, pos)
    return decode_fn


def init_decode_cache(cfg, slots, cache_len):
    return T.init_cache(cfg, slots, cache_len)


def synthetic_batch(cfg, batch_size=8, seq_len=None, seed=0):
    rng = np.random.RandomState(seed)
    s = (seq_len or min(cfg.max_len, 64)) + 1
    return (rng.randint(0, cfg.vocab, (batch_size, s)).astype(np.int32),)


def tiny_fixture(seed=0):
    cfg = lm_tiny()
    params = init(jax.random.PRNGKey(seed), cfg)
    return params, make_loss_fn(cfg), synthetic_batch(cfg, batch_size=8,
                                                      seq_len=16, seed=seed)
