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
    state.
    """
    def loss_fn(params, batch):
        (tokens,) = batch if isinstance(batch, (tuple, list)) else (batch,)
        hidden, stats = T.encode_with_stats(params, cfg, tokens[:, :-1],
                                            attn_fn=attn_fn)
        with jax.named_scope("lm_head"):
            lg = T.logits(params, cfg, hidden)
            xent = L.softmax_xent(lg, tokens[:, 1:])
        if not stats:
            return xent

        def over_layers(name, reduce=jnp.mean):
            return reduce(jnp.stack([s[name] for s in stats if name in s]))

        aux, loss = {"xent": xent}, xent
        if cfg.ffn == "moe":
            aux.update({
                "moe.load_balance_loss": over_layers("load_balance"),
                "moe.router_z_loss": over_layers("z_loss"),
                "moe.load_max_over_mean": over_layers("load_max_over_mean",
                                                      jnp.max),
                "moe.dropped": over_layers("dropped", jnp.sum)})
            loss = xent \
                + cfg.load_balance_coef * aux["moe.load_balance_loss"] \
                + cfg.router_z_coef * aux["moe.router_z_loss"]
        if any("gdn_state_absmax" in s for s in stats):
            aux["gdn.state_absmax"] = over_layers("gdn_state_absmax",
                                                  jnp.max)
        return loss, aux
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
