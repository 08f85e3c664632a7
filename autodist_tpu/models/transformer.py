"""Transformer blocks shared by BERT (encoder) and the causal LM (decoder).

Benchmark parity: the reference benchmarks BERT-large pretraining
(``/root/reference/examples/benchmark/bert.py``, ``docs/usage/performance.md:7-14``);
the driver baseline names BERT-base and an lm1b LM (BASELINE.md).

Param scopes are Megatron-friendly: ``attn/{query,key,value,out}`` and
``mlp/{up,down}`` — tensor-parallel sharding rules key off these names
(column-split q/k/v and up: output dim on the model axis; row-split out and
down: input dim on the model axis).

One block, chosen by the configuration's architectural fields.  The
defaults are the GPT-2 / BERT block (LayerNorm, learned positions, biased
projections, heads of ``dim / num_heads``, a GELU MLP, the embedding reused
as the head); ``norm="rmsnorm"``, ``positions="rope"``, ``qk_norm``,
``bias=False``, ``tied_head=False`` and ``ffn="moe"`` make the
current decoder block whose feed-forward is a layer of routed experts
(``parallel/moe.py:dropless_apply``, parameters under ``layer<i>/moe``).
``layer_types`` chooses each layer's token mixer: ``"full_attention"``
(``layer<i>/attn``) or ``"linear_attention"``, the gated delta rule
(``layers.gdn`` over ``ops/gated_delta.py``, parameters under
``layer<i>/gdn``); ``norm_position="output"`` normalises each sublayer's
output before the residual add (the Olmo 2 order) where the default
normalises its input; ``ffn="swiglu"`` is the dense gated MLP
(``mlp/{gate,up,down}``); ``positions="none"`` gives attention no positions
at all (the convolutions and decays of the linear layers carry order).
"""
import jax
import jax.numpy as jnp

from autodist_tpu.models import layers as L
from autodist_tpu.parallel import moe


class TransformerConfig:
    def __init__(self, vocab=32000, dim=512, num_heads=8, num_layers=6,
                 mlp_dim=None, max_len=512, causal=False, dtype=jnp.bfloat16,
                 num_segments=0, scan_layers=False, norm="layernorm",
                 norm_eps=1e-6, positions="learned", rope_theta=10000.0,
                 qk_norm=False, bias=True, tied_head=True,
                 ffn="mlp", num_experts=0, experts_per_token=0,
                 expert_dim=None, norm_topk=True, load_balance_coef=0.0,
                 router_z_coef=0.0, layer_types=None, linear_heads=0,
                 linear_key_dim=0, linear_value_dim=0, conv_width=4,
                 allow_neg_eigval=True, norm_position="pre"):
        for name, value, known in (("norm", norm, ("layernorm", "rmsnorm")),
                                   ("positions", positions,
                                    ("learned", "rope", "none")),
                                   ("ffn", ffn, ("mlp", "moe", "swiglu")),
                                   ("norm_position", norm_position,
                                    ("pre", "output"))):
            if value not in known:
                raise ValueError(f"{name} must be one of {known}, got "
                                 f"{value!r}")
        self.vocab = vocab
        self.dim = dim
        self.num_heads = num_heads
        self.num_layers = num_layers
        self.mlp_dim = mlp_dim or 4 * dim
        self.max_len = max_len
        self.causal = causal
        self.dtype = dtype
        self.num_segments = num_segments
        # Stacked-blocks layout (the flax nn.scan idiom): one "blocks"
        # subtree with a leading layer dim, applied via ops.scan_blocks —
        # sequential by default, GPipe-pipelined under a Pipeline strategy.
        self.scan_layers = scan_layers
        self.norm, self.norm_eps = norm, norm_eps
        self.positions, self.rope_theta = positions, rope_theta
        self.qk_norm, self.bias, self.tied_head = qk_norm, bias, tied_head
        self.ffn = ffn
        # The expert layer (ffn="moe"): SwiGLU experts of ``expert_dim``,
        # ``experts_per_token`` a token, none dropped; the loss adds the
        # load-balancing term and the router z-loss at these coefficients.
        self.moe = None
        self.load_balance_coef = load_balance_coef
        self.router_z_coef = router_z_coef
        if ffn == "moe":
            if scan_layers:
                raise NotImplementedError(
                    "scan_layers does not carry the expert layers' "
                    "auxiliary terms out of the scan; build an ffn='moe' "
                    "configuration with scan_layers=False")
            self.moe = moe.MoEConfig(
                num_experts=num_experts, top_k=experts_per_token,
                d_model=dim, d_hidden=expert_dim or self.mlp_dim,
                dtype=dtype, expert="swiglu", norm_topk=norm_topk)
        # The token mixer of each layer; None is full attention throughout.
        # A linear layer holds ``linear_heads`` states of ``linear_key_dim``
        # x ``linear_value_dim`` and convolves q, k and v over
        # ``conv_width`` positions first.
        self.norm_position = norm_position
        self.layer_types = None if layer_types is None else tuple(layer_types)
        self.linear_heads = linear_heads
        self.linear_key_dim, self.linear_value_dim = (linear_key_dim,
                                                      linear_value_dim)
        self.conv_width, self.allow_neg_eigval = conv_width, allow_neg_eigval
        if self.layer_types is not None:
            unknown = set(self.layer_types) - set(LAYER_TYPES)
            if unknown or len(self.layer_types) != num_layers:
                raise ValueError(
                    f"layer_types must name one of {LAYER_TYPES} for each "
                    f"of the {num_layers} layers, got {layer_types!r}")
            if scan_layers and LINEAR in self.layer_types:
                raise NotImplementedError(
                    "scan_layers stacks one kind of block and does not "
                    "carry the linear layers' final states out of the scan; "
                    "build a configuration with 'linear_attention' layers "
                    "with scan_layers=False")
            if LINEAR in self.layer_types and not (
                    linear_heads and linear_key_dim and linear_value_dim):
                raise ValueError(
                    "a 'linear_attention' layer needs linear_heads, "
                    "linear_key_dim and linear_value_dim")

    def layer_type(self, i):
        return FULL if self.layer_types is None else self.layer_types[i]


FULL, LINEAR = LAYER_TYPES = ("full_attention", "linear_attention")


def _norm_init(cfg):
    return L.rmsnorm_init(cfg.dim) if cfg.norm == "rmsnorm" \
        else L.layernorm_init(cfg.dim)


def _norm(cfg, p, x):
    return L.rmsnorm(p, x, cfg.norm_eps) if cfg.norm == "rmsnorm" \
        else L.layernorm(p, x, cfg.norm_eps)


def block_init(key, cfg, layer_type=FULL):
    """One block's parameters; ``layer_type`` decides whether it holds
    ``attn`` or ``gdn``.  ``ln1`` and ``ln2`` are the norms of the mixer's
    and the feed-forward's sublayer, wherever ``norm_position`` puts them."""
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"ln1": _norm_init(cfg)}
    if layer_type == LINEAR:
        p["gdn"] = L.gdn_init(k1, cfg.dim, cfg.linear_heads,
                              cfg.linear_key_dim, cfg.linear_value_dim,
                              cfg.conv_width)
    else:
        p["attn"] = L.mha_init(k1, cfg.dim, cfg.num_heads, cfg.bias,
                               cfg.qk_norm)
    p["ln2"] = _norm_init(cfg)
    if cfg.ffn == "moe":
        p["moe"] = moe.init(k2, cfg.moe)
    else:
        p["mlp"] = {"up": L.dense_init(k2, cfg.dim, cfg.mlp_dim, cfg.bias),
                    "down": L.dense_init(k3, cfg.mlp_dim, cfg.dim, cfg.bias)}
        if cfg.ffn == "swiglu":
            p["mlp"]["gate"] = L.dense_init(jax.random.fold_in(k2, 1),
                                            cfg.dim, cfg.mlp_dim, cfg.bias)
    return p


def _residual(cfg, norm_p, x, sublayer):
    """``x`` plus ``sublayer``, which gives ``(y, stats)``: of the
    normalised ``x`` (``norm_position="pre"``), or itself normalised before
    the add (``"output"``)."""
    if cfg.norm_position == "output":
        y, stats = sublayer(x)
        return x + _norm(cfg, norm_p, y), stats
    y, stats = sublayer(_norm(cfg, norm_p, x))
    return x + y, stats


def block_apply(p, x, cfg, mask=None, attn_fn=None, rope=None):
    """One block: ``(x, stats)``, ``stats`` a dict of what its layers
    report from inside the step (the expert layer's
    ``moe.dropless_apply`` statistics, a linear layer's
    ``gdn_state_absmax``) and None where they report nothing.  The
    parameters say which mixer the layer holds."""
    # attn/gdn/mlp scopes nest under the caller's layer scope, mirroring the
    # param paths ("layer<i>/attn/...") for the per-layer profiler.
    if "gdn" in p:
        def mixer(h):
            y, state = L.gdn(p["gdn"], h, cfg.linear_heads, dtype=cfg.dtype,
                             allow_neg_eigval=cfg.allow_neg_eigval,
                             norm_eps=cfg.norm_eps)
            return y, {"gdn_state_absmax": jnp.max(jnp.abs(
                jax.lax.stop_gradient(state)))}
    else:
        def mixer(h):
            return L.mha(p["attn"], h, cfg.num_heads, mask=mask,
                         dtype=cfg.dtype, attn_fn=attn_fn, rope=rope,
                         norm_eps=cfg.norm_eps), None
    with jax.named_scope("gdn" if "gdn" in p else "attn"):
        x, mixed = _residual(cfg, p["ln1"], x, mixer)

    def ffn(h):
        if cfg.ffn == "moe":
            return moe.dropless_apply(p["moe"], cfg.moe, h)
        up = L.dense(p["mlp"]["up"], h, cfg.dtype)
        if cfg.ffn == "swiglu":
            h = jax.nn.silu(L.dense(p["mlp"]["gate"], h, cfg.dtype)) * up
        else:
            h = jax.nn.gelu(up)
        return L.dense(p["mlp"]["down"], h, cfg.dtype), None
    with jax.named_scope("moe" if cfg.ffn == "moe" else "mlp"):
        x, fed = _residual(cfg, p["ln2"], x, ffn)
    return x, {**(mixed or {}), **(fed or {})} or None


def init(key, cfg):
    keys = jax.random.split(key, cfg.num_layers + 3)
    params = {
        "embed": L.embed_init(keys[0], cfg.vocab, cfg.dim),
        "ln_f": _norm_init(cfg),
    }
    if cfg.positions == "learned":
        params["pos_embed"] = L.normal(keys[1], (cfg.max_len, cfg.dim), 0.02)
    if not cfg.tied_head:
        params["lm_head"] = L.dense_init(
            jax.random.fold_in(keys[1], 1), cfg.dim, cfg.vocab,
            use_bias=False)
    if cfg.num_segments:
        params["seg_embed"] = L.normal(keys[2], (cfg.num_segments, cfg.dim), 0.02)
    if cfg.scan_layers:
        params["blocks"] = jax.vmap(
            lambda k: block_init(k, cfg, cfg.layer_type(0)))(
            jnp.stack(keys[3:3 + cfg.num_layers]))
    else:
        for i in range(cfg.num_layers):
            params[f"layer{i}"] = block_init(keys[3 + i], cfg,
                                             cfg.layer_type(i))
    return params


def encode(params, cfg, ids, segment_ids=None, attn_fn=None):
    """Token ids (batch, seq) -> final hidden states (batch, seq, dim).

    With no explicit ``attn_fn``, on TPU the fused Pallas flash-attention
    kernel is used (ops/flash_attention.py); elsewhere the dense reference.
    """
    return encode_with_stats(params, cfg, ids, segment_ids, attn_fn)[0]


def encode_with_stats(params, cfg, ids, segment_ids=None, attn_fn=None):
    """:func:`encode` and what the layers report from inside the step
    (:func:`block_apply`): ``(hidden, [stats of a layer that has any,
    ...])``, the list empty for the default block."""
    s = ids.shape[1]
    with jax.named_scope("embed"):
        x = L.embed(params["embed"], ids)
        if cfg.positions == "learned":
            x = x + params["pos_embed"][:s]
        if cfg.num_segments and segment_ids is not None:
            x = x + params["seg_embed"][segment_ids]
        x = x.astype(cfg.dtype)
    if attn_fn is None:
        # Strategy-provided attention first (SequenceParallel sets ring/
        # ulysses through the parallel context at trace time); otherwise the
        # default encodes causality positionally (no mask tensor).
        from autodist_tpu.parallel.context import resolve_attn
        attn_fn = resolve_attn(causal=cfg.causal)
        if attn_fn is None:
            from autodist_tpu.ops.flash_attention import make_flash_attn_fn
            attn_fn = make_flash_attn_fn(causal=cfg.causal)
        mask = None
    else:
        # Explicit attn_fns keep the documented mha contract: they receive
        # the boolean mask (and may ignore it if causality is positional).
        mask = L.causal_mask(s) if cfg.causal else None
    rope = L.rope_tables(s, cfg.dim // cfg.num_heads, cfg.rope_theta) \
        if cfg.positions == "rope" else None
    stats = []
    if cfg.scan_layers:
        from autodist_tpu.ops import scan_blocks
        with jax.named_scope("blocks"):
            x = scan_blocks(params["blocks"],
                            lambda bp, a: block_apply(
                                bp, a, cfg, mask=mask, attn_fn=attn_fn,
                                rope=rope)[0], x)
    else:
        for i in range(cfg.num_layers):
            with jax.named_scope(f"layer{i}"):
                x, layer_stats = block_apply(
                    params[f"layer{i}"], x, cfg, mask=mask, attn_fn=attn_fn,
                    rope=rope)
            if layer_stats is not None:
                stats.append(layer_stats)
    with jax.named_scope("ln_f"):
        return _norm(cfg, params["ln_f"], x), stats


def logits(params, cfg, hidden):
    """Output projection in float32: the embedding matrix again, or the
    head's own (``tied_head=False``)."""
    with jax.named_scope("logits"):
        hidden = hidden.astype(jnp.float32)
        head = params["embed"]["embedding"].T if cfg.tied_head \
            else params["lm_head"]["kernel"]
        return hidden @ head.astype(jnp.float32)


# -- autoregressive decode (KV cache) ----------------------------------------

def _decodable(cfg):
    block = (cfg.norm, cfg.positions, cfg.ffn, cfg.qk_norm, cfg.bias,
             cfg.tied_head, cfg.norm_position, cfg.layer_types)
    if block != ("layernorm", "learned", "mlp", False, True, True, "pre",
                 None):
        raise NotImplementedError(
            "decoding is implemented for the default block only (LayerNorm, "
            "learned positions, biased projections, an MLP, a tied head); "
            "through rope, QK-norm, moe, output norms or linear-attention "
            "layers (recurrent state beside a KV cache) it waits for "
            "ROADMAP R2")


def init_cache(cfg, slots, cache_len, dtype=None):
    """Preallocated per-layer KV cache: (slots, heads, cache_len,
    head_dim) per k/v per layer, in the compute dtype (what the forward's
    k/v projections produce).  The leading ``slots`` dim is the decode
    engine's batch dimension — it shards over the data axis exactly like
    a request batch.  Zeros are safe initial content: the ``j <= pos``
    mask means unwritten rows are never exposed (layers.mha_decode)."""
    _decodable(cfg)
    if cache_len > cfg.max_len:
        raise ValueError(
            f"cache_len {cache_len} exceeds the model's max_len "
            f"{cfg.max_len} (pos_embed table is the hard ceiling)")
    hd = cfg.dim // cfg.num_heads
    shape = (int(slots), cfg.num_heads, int(cache_len), hd)
    dt = dtype or cfg.dtype
    return {f"layer{i}": {"k": jnp.zeros(shape, dt),
                          "v": jnp.zeros(shape, dt)}
            for i in range(cfg.num_layers)}


def block_decode(p, x, cfg, k_cache, v_cache, pos):
    """One transformer block for a single decode token (mirrors
    block_apply's named scopes so the per-layer profiler attributes
    decode time the same way)."""
    with jax.named_scope("attn"):
        h = L.layernorm(p["ln1"], x)
        a, k_cache, v_cache = L.mha_decode(
            p["attn"], h, cfg.num_heads, k_cache, v_cache, pos,
            dtype=cfg.dtype)
        x = x + a
    with jax.named_scope("mlp"):
        h = L.layernorm(p["ln2"], x)
        h = jax.nn.gelu(L.dense(p["mlp"]["up"], h, cfg.dtype))
        return x + L.dense(p["mlp"]["down"], h, cfg.dtype), k_cache, v_cache


def decode_step(params, cfg, cache, tokens, pos):
    """One autoregressive step: feed ``tokens`` (slots,) at positions
    ``pos`` (slots,), return ``(logits, new_cache)`` with logits
    (slots, vocab) predicting position ``pos + 1``.

    Every per-position op (embed, layernorm, dense, logits) is
    row-independent and the attention is an exact masked select over the
    cache, so the step's output is bitwise-equal to running the full
    prefix through :func:`encode` (padded to the cache length, explicit
    dense attention) and reading row ``pos`` — the KV cache is a pure
    optimization, never an approximation.
    """
    _decodable(cfg)
    if cfg.scan_layers:
        raise NotImplementedError(
            "decode_step does not support scan_layers layouts; build the "
            "serving config with scan_layers=False")
    with jax.named_scope("embed"):
        x = L.embed(params["embed"], tokens[:, None]) + \
            params["pos_embed"][pos][:, None, :]
        x = x.astype(cfg.dtype)
    new_cache = {}
    for i in range(cfg.num_layers):
        with jax.named_scope(f"layer{i}"):
            lc = cache[f"layer{i}"]
            x, kc, vc = block_decode(params[f"layer{i}"], x, cfg,
                                     lc["k"], lc["v"], pos)
            new_cache[f"layer{i}"] = {"k": kc, "v": vc}
    with jax.named_scope("ln_f"):
        x = L.layernorm(params["ln_f"], x)
    return logits(params, cfg, x)[:, 0, :], new_cache
