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
``layer<i>/gdn``) or ``"latent_attention"`` (``layers.mla``: queries, keys
and values through low-rank latents, one rotary key a position shared by the
heads, parameters under ``layer<i>/attn``; ``q_rank=0``: the queries by one
full-rank matrix; ``attn_gate`` gates its heads too) or ``"sliding_attention"``
(full attention's mixer behind a ``window``: position t sees the keys ``t -
window < s <= t``) or ``"kda_attention"`` (``layers.kda``: the delta rule
with a decay a channel of the key, its gate bounded below by
``linear_gate_bound``, at ``linear_heads`` x ``linear_key_dim`` /
``linear_value_dim``, parameters under ``layer<i>/kda``;
``recompute="linear_mixer"`` covers it).  A layer's mixer and its
feed-forward are chosen apart:
a ``"linear_attention"`` layer of an ``ffn="moe"`` model holds ``gdn`` and
``moe`` (``linear_key_heads``: fewer key heads than ``linear_heads``, each
read by a group of value heads).  ``head_dim`` is a head's width where it is
not ``dim / num_heads``, ``kv_heads`` the key-value heads that groups of
query heads share, ``heads_by_layer`` each layer's query heads, ``attn_gate``
a sigmoid gate on attention's output (a scalar a head, or ``"lane"``: one a
lane), ``qk_norm="head"`` a q / k norm over each head's own lanes where True
normalises the whole projected vector, ``shared_gate`` the sigmoid of a
scalar a token on the shared expert's output, ``experts_held_chunks`` the
chunks a layer told its share of the experts splits a step's assignments into
where that is not sixteen (``MoEConfig.held_chunks``), ``recompute="linear_mixer"``
has the backward pass compute the linear layers' mixer sublayer again
(``jax.checkpoint``), ``mixer_stats`` every mixer report its output's
standard deviation over positions, ``rope_by_type`` the rotary tables of
each kind of layer (:func:`_rope_tables`); ``first_dense`` gives the first
layers of an ``ffn="moe"`` model a dense SwiGLU MLP; ``mtp_depth=1`` adds the
multi-token-prediction module (``mtp/...``, :func:`mtp_hidden`), one more
block that predicts the token after next through the same embedding and head;
``norm_position="output"`` normalises each sublayer's
output before the residual add (the Olmo 2 order) where the default
normalises its input, ``"sandwich"`` both (``ln1_out``, ``ln2_out`` beside
``ln1``, ``ln2``); ``loops`` runs the stack of layers that many times over
with the same variables (:func:`encode_passes`: one ``lax.scan`` over the
passes under the scope ``pass``, the final norm after every pass, the next
pass from what it gave), every pass's state goes to the head
(``pass<t>/lm_head``), and :func:`exit_distribution` (``exit_gate``,
a projection to a scalar) gives the weights of the passes' losses
(``lm.looped_objective``, ``exit_entropy_coef``); ``loops=1`` is the plain
stack, instruction for instruction; ``recompute="pointwise"`` makes the
norms and the gated feed-forward's activation again in the backward pass
(what the scan would otherwise keep a pass); ``ffn="swiglu"`` is the dense
gated MLP (``mlp/{gate,up,down}``); ``positions="none"`` gives attention no
positions at all (the convolutions and decays of the linear layers carry
order).
"""
import functools

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
                 allow_neg_eigval=True, norm_position="pre",
                 expert_scoring="softmax", route_scale=1.0, shared_experts=0,
                 select_bias=False, bias_update_rate=0.0, experts_held=None,
                 first_dense=0, q_rank=0, kv_rank=0, nope_dim=0, rope_dim=0,
                 value_dim=0, mtp_depth=0, mtp_coef=0.0, head_dim=None,
                 kv_heads=None, heads_by_layer=None, window=None,
                 attn_gate=False, rope_by_type=None, linear_key_heads=None,
                 shared_gate=False, recompute=None, mixer_stats=False,
                 experts_held_chunks=None, loops=1, exit_entropy_coef=0.0,
                 linear_gate_bound=None, expert_groups=None,
                 expert_groups_kept=None):
        for name, value, known in (("norm", norm, ("layernorm", "rmsnorm")),
                                   ("positions", positions,
                                    ("learned", "rope", "none")),
                                   ("recompute", recompute,
                                    (None, "linear_mixer", "pointwise")),
                                   ("ffn", ffn, ("mlp", "moe", "swiglu")),
                                   ("norm_position", norm_position,
                                    ("pre", "output", "sandwich"))):
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
                dtype=dtype, expert="swiglu", norm_topk=norm_topk,
                scoring=expert_scoring, route_scale=route_scale,
                shared=shared_experts, select_bias=select_bias,
                bias_update_rate=bias_update_rate, held=experts_held,
                shared_gate=shared_gate, held_chunks=experts_held_chunks,
                groups=expert_groups, groups_kept=expert_groups_kept)
        # The first ``first_dense`` layers of an ffn="moe" model keep a dense
        # SwiGLU MLP of ``mlp_dim``.
        self.first_dense = first_dense
        # Latent attention (layer type "latent_attention"): the ranks of the
        # two latents, a head's unrotated and rotary score widths, its
        # value's width; rotary pairs are adjacent, ``rope_theta`` theirs.
        self.q_rank, self.kv_rank = q_rank, kv_rank
        self.nope_dim, self.rope_dim, self.value_dim = (nope_dim, rope_dim,
                                                        value_dim)
        # The multi-token-prediction module: 0 or 1 block deep; its loss
        # enters at ``mtp_coef``.
        if mtp_depth not in (0, 1):
            raise NotImplementedError(
                f"mtp_depth {mtp_depth}: the prediction module is one block "
                f"deep or absent")
        self.mtp_depth, self.mtp_coef = mtp_depth, mtp_coef
        # The token mixer of each layer; None is full attention throughout.
        # A linear layer holds ``linear_heads`` states of ``linear_key_dim``
        # x ``linear_value_dim`` and convolves q, k and v over
        # ``conv_width`` positions first; ``linear_key_heads`` (None: as many)
        # are the heads of its queries and keys, a group of states each.
        self.norm_position = norm_position
        self.layer_types = None if layer_types is None else tuple(layer_types)
        self.linear_heads = linear_heads
        self.linear_key_heads = linear_key_heads
        self.linear_key_dim, self.linear_value_dim = (linear_key_dim,
                                                      linear_value_dim)
        self.conv_width, self.allow_neg_eigval = conv_width, allow_neg_eigval
        # A "kda_attention" layer's gate a position and channel lies in
        # (``linear_gate_bound``, 0).
        self.linear_gate_bound = linear_gate_bound
        # "linear_mixer": the backward pass computes a linear layer's mixer
        # sublayer again from its input (``jax.checkpoint``) and keeps none
        # of what lies inside; "pointwise": it computes every norm and the
        # gated feed-forward's ``silu(gate) * up`` again from their inputs,
        # the matrix products' and kernels' results (a scan over the passes
        # of a looped model keeps what autodiff keeps a pass, the norms'
        # float32 copies with it, where the compiler would keep none in an
        # unrolled program); None keeps what autodiff keeps.
        self.recompute = recompute
        # Every mixer reports how far what it adds to the residual stream
        # stands from its mean over a row's positions, as a root mean square
        # (``aux["attn.output_std"]``, ``aux["gdn.output_std"]``).
        self.mixer_stats = mixer_stats
        # Full and sliding attention (``layers.mha``): a head's width, the
        # key-value heads (None: as many as query heads), each layer's query
        # heads (None: ``num_heads`` throughout), the sliding layers' window,
        # the gate (True: a scalar a head; "lane": one a lane), and ``{layer
        # type: {"theta", "lanes" (None: a head's width), "yarn" (None, or
        # ``layers.yarn_rope_tables``' factor,
        # original_len, beta_fast, beta_slow, attention_factor)}}`` under
        # ``positions="rope"`` (None: ``rope_theta`` over the whole head for
        # every kind).
        self._head_dim = head_dim
        self.kv_heads, self.window, self.attn_gate = kv_heads, window, \
            attn_gate
        self.heads_by_layer = None if heads_by_layer is None \
            else tuple(heads_by_layer)
        self.rope_by_type = rope_by_type
        if self.heads_by_layer is not None and (
                len(self.heads_by_layer) != num_layers):
            raise ValueError(
                f"heads_by_layer must give each of the {num_layers} layers "
                f"its query heads, got {heads_by_layer!r}")
        if kv_heads and any(h % kv_heads for h in
                            self.heads_by_layer or (num_heads,)):
            raise ValueError(
                f"{kv_heads} key-value heads do not group the query heads "
                f"{self.heads_by_layer or num_heads}")
        sliding = self.layer_types is not None and SLIDING in self.layer_types
        if sliding != (window is not None):
            raise ValueError(
                "a 'sliding_attention' layer needs window, and a window "
                f"needs such a layer; got window={window!r} with layer_types "
                f"{layer_types!r}")
        if scan_layers and (sliding or self.heads_by_layer is not None):
            raise NotImplementedError(
                "scan_layers stacks one kind of block: 'sliding_attention' "
                "layers and heads_by_layer need scan_layers=False")
        if self.layer_types is not None:
            unknown = set(self.layer_types) - set(LAYER_TYPES)
            if unknown or len(self.layer_types) != num_layers:
                raise ValueError(
                    f"layer_types must name one of {LAYER_TYPES} for each "
                    f"of the {num_layers} layers, got {layer_types!r}")
            if scan_layers and (LINEAR in self.layer_types
                                or KDA in self.layer_types):
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
            if KDA in self.layer_types and not (
                    linear_heads and linear_key_dim and linear_value_dim
                    and linear_gate_bound):
                raise ValueError(
                    "a 'kda_attention' layer needs linear_heads, "
                    "linear_key_dim, linear_value_dim and linear_gate_bound")
            if LATENT in self.layer_types and not (
                    kv_rank and nope_dim and rope_dim and value_dim):
                raise ValueError(
                    "a 'latent_attention' layer needs kv_rank, nope_dim, "
                    "rope_dim and value_dim (q_rank 0: full-rank queries)")
            if scan_layers and LATENT in self.layer_types:
                raise NotImplementedError(
                    "scan_layers stacks the default block; build a "
                    "configuration with 'latent_attention' layers with "
                    "scan_layers=False")
        if (first_dense or mtp_depth) and scan_layers:
            raise NotImplementedError(
                "scan_layers stacks one kind of block: first_dense and "
                "mtp_depth need scan_layers=False")
        # The stack of layers is run ``loops`` times over with the same
        # variables (a looped language model): a pass ends in the final norm,
        # the next starts from what it gave, and every pass's state goes to
        # the head; ``exit_entropy_coef`` is the entropy term's weight in the
        # loss over the passes (``lm.make_loss_fn``).  1 is the plain stack.
        if loops < 1:
            raise ValueError(f"loops must be at least 1, got {loops!r}")
        if loops > 1 and scan_layers:
            raise NotImplementedError(
                f"loops={loops} with scan_layers: a scan over the layers "
                "inside the loop over the passes is not built (ROADMAP R9); "
                "build a looped configuration with scan_layers=False")
        if loops > 1 and (ffn == "moe" or mtp_depth or (
                self.layer_types is not None
                and {LINEAR, KDA} & set(self.layer_types))):
            raise NotImplementedError(
                f"loops={loops}: a pass has no account of its own of what "
                "expert layers, linear layers or the prediction module "
                "report from inside the step (auxiliary terms, selection "
                "biases, final states); a looped stack holds attention and a "
                "dense feed-forward (ROADMAP R9)")
        self.loops, self.exit_entropy_coef = loops, exit_entropy_coef

    def layer_type(self, i):
        return FULL if self.layer_types is None else self.layer_types[i]

    @property
    def head_dim(self):
        """A head's width: its own where given, else ``dim / num_heads`` of
        the configuration as it stands (callers set ``dim`` and ``num_heads``
        on a built configuration)."""
        return self._head_dim or self.dim // self.num_heads

    def layer_heads(self, i):
        """The query heads of layer ``i``'s attention."""
        return self.num_heads if self.heads_by_layer is None \
            else self.heads_by_layer[i]

    def layer_ffn(self, i):
        """The feed-forward of layer ``i``: ``ffn``, but a dense SwiGLU MLP
        in the ``first_dense`` layers of an expert model."""
        return "swiglu" if self.ffn == "moe" and i < self.first_dense \
            else self.ffn


FULL, LINEAR, LATENT, SLIDING, KDA = LAYER_TYPES = (
    "full_attention", "linear_attention", "latent_attention",
    "sliding_attention", "kda_attention")


def _norm_init(cfg):
    return L.rmsnorm_init(cfg.dim) if cfg.norm == "rmsnorm" \
        else L.layernorm_init(cfg.dim)


def _norm(cfg, p, x):
    norm = L.rmsnorm if cfg.norm == "rmsnorm" else L.layernorm
    if cfg.recompute == "pointwise":
        norm = jax.checkpoint(norm, static_argnums=2)
    return norm(p, x, cfg.norm_eps)


def block_init(key, cfg, layer_type=FULL, ffn=None, heads=None):
    """One block's parameters; ``layer_type`` decides whether it holds
    ``attn`` (full, sliding or latent attention), ``gdn`` or ``kda``, ``ffn``
    (the
    configuration's where None) whether ``moe`` or ``mlp``, ``heads`` (the
    configuration's ``num_heads`` where None) its attention's query heads.
    ``ln1`` and ``ln2`` are the norms of the mixer's and the feed-forward's
    sublayer, wherever ``norm_position`` puts them; under ``"sandwich"``
    they norm the sublayers' inputs and ``ln1_out``, ``ln2_out`` their
    outputs."""
    ffn = ffn or cfg.ffn
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"ln1": _norm_init(cfg)}
    if cfg.norm_position == "sandwich":
        p["ln1_out"], p["ln2_out"] = _norm_init(cfg), _norm_init(cfg)
    if layer_type == LINEAR:
        p["gdn"] = L.gdn_init(k1, cfg.dim, cfg.linear_heads,
                              cfg.linear_key_dim, cfg.linear_value_dim,
                              cfg.conv_width, cfg.linear_key_heads)
    elif layer_type == KDA:
        p["kda"] = L.kda_init(k1, cfg.dim, cfg.linear_heads,
                              cfg.linear_key_dim, cfg.linear_value_dim,
                              cfg.conv_width, cfg.linear_gate_bound)
    elif layer_type == LATENT:
        p["attn"] = L.mla_init(
            k1, cfg.dim, cfg.num_heads, cfg.q_rank, cfg.kv_rank, cfg.nope_dim,
            cfg.rope_dim, cfg.value_dim,
            **({"gate": True} if cfg.attn_gate else {}))
    else:
        p["attn"] = L.mha_init(k1, cfg.dim, heads or cfg.num_heads, cfg.bias,
                               cfg.qk_norm, cfg.head_dim, cfg.kv_heads,
                               cfg.attn_gate)
    p["ln2"] = _norm_init(cfg)
    if ffn == "moe":
        p["moe"] = moe.init(k2, cfg.moe)
    else:
        p["mlp"] = {"up": L.dense_init(k2, cfg.dim, cfg.mlp_dim, cfg.bias),
                    "down": L.dense_init(k3, cfg.mlp_dim, cfg.dim, cfg.bias)}
        if ffn == "swiglu":
            p["mlp"]["gate"] = L.dense_init(jax.random.fold_in(k2, 1),
                                            cfg.dim, cfg.mlp_dim, cfg.bias)
    return p


def _residual(cfg, p, ln, x, sublayer):
    """``x`` plus ``sublayer``, which gives ``(y, stats)``: of the
    normalised ``x`` (``norm_position="pre"``), or itself normalised before
    the add (``"output"``), or both (``"sandwich"``: ``p[ln]`` norms the
    input, ``p[ln + "_out"]`` the output)."""
    if cfg.norm_position == "output":
        y, stats = sublayer(x)
        return x + _norm(cfg, p[ln], y), stats
    y, stats = sublayer(_norm(cfg, p[ln], x))
    if cfg.norm_position == "sandwich":
        y = _norm(cfg, p[ln + "_out"], y)
    return x + y, stats


_MIXER_KEYS = ("ln1", "ln1_out", "attn", "gdn", "kda")


def _halves(p):
    """A block's parameters as ``(the mixer sublayer's, the feed-forward
    sublayer's)``: what :func:`mixer_sublayer` and :func:`ffn_sublayer`
    read of them."""
    return ({k: v for k, v in p.items() if k in _MIXER_KEYS},
            {k: v for k, v in p.items() if k not in _MIXER_KEYS})


def block_apply(p, x, cfg, mask=None, attn_fn=None, rope=None, window=None):
    """One block: ``(x, stats)``, ``stats`` a dict of what its layers
    report from inside the step (the expert layer's
    ``moe.dropless_apply`` statistics, a linear layer's
    ``gdn_state_absmax``) and None where they report nothing.  The
    parameters say which mixer and which feed-forward the layer holds;
    ``rope`` are the tables of the layer's kind (:func:`_rope_tables`),
    ``window`` a sliding layer's.  The block is :func:`mixer_sublayer` then
    :func:`ffn_sublayer`."""
    x, mixed = mixer_sublayer(p, x, cfg, mask=mask, attn_fn=attn_fn,
                              rope=rope, window=window)
    x, fed = ffn_sublayer(p, x, cfg)
    return x, {**(mixed or {}), **(fed or {})} or None


def mixer_sublayer(p, x, cfg, mask=None, attn_fn=None, rope=None,
                   window=None):
    """The first half of a block, ``(x, stats)``: the token mixer the
    parameters hold (``attn``, full or latent, ``gdn`` or ``kda``) with its
    norm
    and residual.  The parameters say how many query heads full attention
    has (``query`` is dim -> heads x ``cfg.head_dim``); ``window`` makes it
    sliding, and an explicit ``mask`` is then narrowed to the window."""
    # attn/gdn/mlp scopes nest under the caller's layer scope, mirroring the
    # param paths ("layer<i>/attn/...") for the per-layer profiler.
    if "gdn" in p:
        def mixer(h):
            y, state = L.gdn(p["gdn"], h, cfg.linear_heads, dtype=cfg.dtype,
                             allow_neg_eigval=cfg.allow_neg_eigval,
                             norm_eps=cfg.norm_eps,
                             key_heads=cfg.linear_key_heads)
            return y, {"gdn_state_absmax": jnp.max(jnp.abs(
                jax.lax.stop_gradient(state)))}
    elif "kda" in p:
        def mixer(h):
            y, state, gate_min = L.kda(
                p["kda"], h, cfg.linear_heads, dtype=cfg.dtype,
                norm_eps=cfg.norm_eps, gate_lower_bound=cfg.linear_gate_bound)
            return y, {"kda_state_absmax": jnp.max(jnp.abs(
                jax.lax.stop_gradient(state))), "kda_gate_min": gate_min}
    elif "kv_down" in p["attn"]:
        def mixer(h):
            return L.mla(p["attn"], h, cfg.num_heads, cfg.nope_dim,
                         cfg.rope_dim, cfg.value_dim, rope, mask=mask,
                         dtype=cfg.dtype, attn_fn=attn_fn,
                         norm_eps=cfg.norm_eps, causal=cfg.causal), None
    else:
        heads = p["attn"]["query"]["kernel"].shape[1] // cfg.head_dim
        if window is not None and mask is not None:
            mask = jnp.logical_and(mask, L.causal_mask(x.shape[1], window))

        def mixer(h):
            return L.mha(p["attn"], h, heads, mask=mask,
                         dtype=cfg.dtype, attn_fn=attn_fn, rope=rope,
                         norm_eps=cfg.norm_eps, kv_heads=cfg.kv_heads,
                         window=window), None
    scope = next((name for name in ("gdn", "kda") if name in p), "attn")
    if cfg.mixer_stats:
        plain = mixer

        def mixer(h):
            y, stats = plain(h)
            out = jax.lax.stop_gradient(y).astype(jnp.float32)
            std = jnp.sqrt(jnp.mean(jnp.square(
                out - out.mean(axis=1, keepdims=True))))
            return y, {**(stats or {}), f"{scope}_output_std": std}
    with jax.named_scope(scope):
        return _residual(cfg, p, "ln1", x, mixer)


def ffn_sublayer(p, x, cfg):
    """The second half of a block, ``(x, stats)``: the feed-forward the
    parameters hold (``mlp``, plain or gated, or ``moe``) with its norm and
    residual."""
    def ffn(h):
        if "moe" in p:
            return moe.dropless_apply(p["moe"], cfg.moe, h)
        up = L.dense(p["mlp"]["up"], h, cfg.dtype)

        def down(gate, up):
            h = jax.nn.silu(gate) * up if gate is not None \
                else jax.nn.gelu(up)
            return L.dense(p["mlp"]["down"], h, cfg.dtype)
        if cfg.recompute == "pointwise":
            down = jax.checkpoint(down)
        return down(L.dense(p["mlp"]["gate"], h, cfg.dtype)
                    if "gate" in p["mlp"] else None, up), None
    with jax.named_scope("moe" if "moe" in p else "mlp"):
        return _residual(cfg, p, "ln2", x, ffn)


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
    if cfg.loops > 1:
        # The exit gate: one projection of the width to a scalar, with a
        # bias; drawn, not zero, so that no two passes weigh alike at the
        # start.
        params["exit_gate"] = {
            "kernel": L.normal(jax.random.fold_in(keys[1], 2),
                               (cfg.dim, 1), 0.02),
            "bias": jnp.zeros((1,))}
    if cfg.scan_layers:
        params["blocks"] = jax.vmap(
            lambda k: block_init(k, cfg, cfg.layer_type(0)))(
            jnp.stack(keys[3:3 + cfg.num_layers]))
    else:
        for i in range(cfg.num_layers):
            params[f"layer{i}"] = block_init(keys[3 + i], cfg,
                                             cfg.layer_type(i),
                                             cfg.layer_ffn(i),
                                             cfg.layer_heads(i))
    if cfg.mtp_depth:
        # Its own projection, norms and block; embedding and head are the
        # model's, used a second time.
        k1, k2 = jax.random.split(jax.random.fold_in(key, cfg.num_layers))
        params["mtp"] = {
            "embed_norm": _norm_init(cfg), "hidden_norm": _norm_init(cfg),
            "proj": L.dense_init(k1, 2 * cfg.dim, cfg.dim, use_bias=False),
            "block": block_init(k2, cfg, cfg.layer_type(cfg.num_layers - 1),
                                cfg.ffn),
            "ln_f": _norm_init(cfg)}
    return params


def _rope_tables(cfg, s):
    """``{layer type: tables}`` for the kinds of attention the model holds:
    rotate-half tables for full and sliding attention under
    ``positions="rope"`` (``rope_theta`` over a head's width, or each kind's
    own by ``cfg.rope_by_type``: its theta, the lanes it rotates, YaRN's
    frequencies), adjacent-pair tables of ``rope_dim`` for latent attention;
    a type with no rotation is absent."""
    tables = {}
    if cfg.positions == "rope" and cfg.rope_by_type is None:
        tables[FULL] = L.rope_tables(s, cfg.head_dim, cfg.rope_theta)
    elif cfg.positions == "rope":
        for kind, rope in cfg.rope_by_type.items():
            lanes = rope.get("lanes") or cfg.head_dim
            tables[kind] = L.rope_tables(s, lanes, rope["theta"]) \
                if rope.get("yarn") is None else L.yarn_rope_tables(
                    s, lanes, rope["theta"], **rope["yarn"])
    if cfg.layer_types is not None and LATENT in cfg.layer_types:
        tables[LATENT] = L.rope_pair_tables(s, cfg.rope_dim, cfg.rope_theta)
    return tables


def _resolve_attn(cfg, s, attn_fn):
    """``(attn_fn, mask)`` for a model's blocks (:func:`encode_with_stats`)."""
    if attn_fn is None:
        # Strategy-provided attention first (SequenceParallel sets ring/
        # ulysses through the parallel context at trace time); otherwise the
        # default encodes causality positionally (no mask tensor).
        from autodist_tpu.parallel.context import resolve_attn
        attn_fn = resolve_attn(causal=cfg.causal)
        if attn_fn is None:
            from autodist_tpu.ops.flash_attention import make_flash_attn_fn
            attn_fn = make_flash_attn_fn(causal=cfg.causal)
        return attn_fn, None
    # Explicit attn_fns keep the documented mha contract: they receive
    # the boolean mask (and may ignore it if causality is positional).
    return attn_fn, L.causal_mask(s) if cfg.causal else None


def _named_updates(stats, prefix):
    """A layer's ``stats`` with its ``state_updates`` keyed by the variables'
    full names: ``bias`` of the expert layer under ``prefix`` becomes
    ``<prefix>/moe/bias``."""
    if stats and "state_updates" in stats:
        stats = dict(stats, state_updates={
            f"{prefix}/moe/{name}": value
            for name, value in stats["state_updates"].items()})
    return stats


def encode(params, cfg, ids, segment_ids=None, attn_fn=None):
    """Token ids (batch, seq) -> final hidden states (batch, seq, dim).

    With no explicit ``attn_fn``, on TPU the fused Pallas flash-attention
    kernel is used (ops/flash_attention.py); elsewhere the dense reference.
    """
    return encode_with_stats(params, cfg, ids, segment_ids, attn_fn)[0]


def encode_with_stats(params, cfg, ids, segment_ids=None, attn_fn=None):
    """:func:`encode` and what the layers report from inside the step
    (:func:`block_apply`): ``(hidden, [stats of a layer that has any,
    ...])``, the list empty for the default block.  Under ``cfg.loops`` the
    hidden states are the last pass's (:func:`encode_passes` gives all)."""
    hidden, stats = encode_passes(params, cfg, ids, segment_ids, attn_fn)
    return hidden[-1], stats


_loops_announced = set()


def _announce_loops(cfg):
    """Gauges ``loop.*`` and one ``loop`` event a distinct shape, at trace
    time: the passes, the layers of the stack and the layer applications a
    step makes."""
    from autodist_tpu import observability
    if not observability.enabled():
        return
    registry = observability.registry()
    registry.gauge("loop.passes").set(cfg.loops)
    registry.gauge("loop.layers").set(cfg.num_layers)
    registry.gauge("loop.applications").set(cfg.loops * cfg.num_layers)
    detail = (f"looped stack: {cfg.num_layers} layers run {cfg.loops} times "
              f"with one set of variables ({cfg.loops * cfg.num_layers} "
              f"layer applications a step, a scan over the passes under the "
              f"scope 'pass'), the final norm, the head and a cross-entropy "
              f"on every pass, an exit gate on the first {cfg.loops - 1}; "
              f"entropy term at {cfg.exit_entropy_coef}")
    if detail not in _loops_announced:
        _loops_announced.add(detail)
        observability.record_event("loop", detail)


def encode_passes(params, cfg, ids, segment_ids=None, attn_fn=None):
    """``([hidden states of pass 0, ..., of pass loops - 1], stats)``: the
    stack of layers run ``cfg.loops`` times over with the same variables,
    the final norm after every pass, the next pass starting from what that
    norm gave.  With ``loops=1`` the list holds :func:`encode`'s one state
    and the scopes are ``layer<i>`` and ``ln_f``.  A looped model's passes
    are one ``lax.scan`` whose body is the unrolled stack and the final norm
    (scopes ``pass/layer<i>``, ``pass/ln_f``, which the profiler folds into
    a plain model's rows): the program holds one pass's instructions
    whatever ``loops`` is, and the variables' gradients meet in the
    backward scan's carry, in the variables' own float32."""
    s = ids.shape[1]
    with jax.named_scope("embed"):
        x = L.embed(params["embed"], ids)
        if cfg.positions == "learned":
            x = x + params["pos_embed"][:s]
        if cfg.num_segments and segment_ids is not None:
            x = x + params["seg_embed"][segment_ids]
        x = x.astype(cfg.dtype)
    attn_fn, mask = _resolve_attn(cfg, s, attn_fn)
    rope = _rope_tables(cfg, s)
    stats = []
    if cfg.scan_layers:
        from autodist_tpu.ops import scan_blocks
        with jax.named_scope("blocks"):
            x = scan_blocks(params["blocks"],
                            lambda bp, a: block_apply(
                                bp, a, cfg, mask=mask, attn_fn=attn_fn,
                                rope=rope.get(FULL))[0], x)
        with jax.named_scope("ln_f"):
            return [_norm(cfg, params["ln_f"], x)], stats
    from autodist_tpu.parallel.context import layer_boundary
    last = cfg.num_layers - 1
    halves = [_halves(params[f"layer{i}"]) for i in range(cfg.num_layers)]

    def one_pass(x):
        mixers, ffns = (list(half) for half in zip(*halves))
        # The parameters of the layer ahead meet this layer's activations,
        # a half a boundary: the mixer's where this layer begins, the
        # feed-forward's where this layer's feed-forward begins (layer 0's,
        # which has no layer below, where it begins itself).  Plain
        # arguments back wherever no strategy shards them
        # (``layer_boundary``); where one does, each half's gradients
        # travel during the backward pass of the half-layers below it.
        (mixers[0], ffns[0]), x = layer_boundary((mixers[0], ffns[0]), x)
        for i in range(cfg.num_layers):
            if i < last:
                mixers[i + 1], x = layer_boundary(mixers[i + 1], x)
            with jax.named_scope(f"layer{i}"):
                kind = cfg.layer_type(i)
                sublayer = functools.partial(
                    mixer_sublayer, cfg=cfg, mask=mask, attn_fn=attn_fn,
                    rope=rope.get(kind),
                    window=cfg.window if kind == SLIDING else None)
                if kind in (LINEAR, KDA) \
                        and cfg.recompute == "linear_mixer":
                    sublayer = jax.checkpoint(sublayer)
                x, mixed = sublayer(mixers[i], x)
            if i < last:
                ffns[i + 1], x = layer_boundary(ffns[i + 1], x)
            with jax.named_scope(f"layer{i}"):
                x, fed = ffn_sublayer(ffns[i], x, cfg)
            layer_stats = {**(mixed or {}), **(fed or {})} or None
            if layer_stats is not None:
                stats.append(_named_updates(layer_stats, f"layer{i}"))
        with jax.named_scope("ln_f"):
            return _norm(cfg, params["ln_f"], x)
    if cfg.loops == 1:
        return [one_pass(x)], stats
    _announce_loops(cfg)
    # A pass reads the variables as the scan's constants, and the boundary
    # op runs inside it: on the explicit step a variable is gathered, and
    # its gradient scattered, once a pass.
    with jax.named_scope("pass"):
        _, hidden = jax.lax.scan(lambda x, _: (one_pass(x),) * 2, x, None,
                                 length=cfg.loops)
    return [hidden[t] for t in range(cfg.loops)], stats


def mtp_hidden(params, cfg, hidden, next_ids, attn_fn=None):
    """The multi-token-prediction module (DeepSeek-V3's, one block deep):
    ``(hidden states that predict the token after next, the block's
    stats)``.  ``hidden`` are the main model's final hidden states of
    positions ``0..s-1`` (after its last norm) and ``next_ids`` the tokens
    ``1..s``: ``z_i = W_p [norm_e(Emb(t_(i+1))) ; norm_h(hidden_i)]``, one
    block of the model's last layer's kind on ``z``, the module's own final
    norm; the caller applies the model's head.  Everything runs under the
    scope ``mtp``, whose inner scopes the profiler folds into the generic
    ones (``mtp/block/attn`` is ``attn``); the gauge ``mtp.depth`` is set
    where it is traced."""
    from autodist_tpu import observability
    if observability.enabled():     # at trace time, as the layers' gauges
        observability.registry().gauge("mtp.depth").set(cfg.mtp_depth)
    p, s = params["mtp"], next_ids.shape[1]
    attn_fn, mask = _resolve_attn(cfg, s, attn_fn)
    kind = cfg.layer_type(cfg.num_layers - 1)
    with jax.named_scope("mtp"):
        with jax.named_scope("embed"):
            e = L.embed(params["embed"], next_ids).astype(cfg.dtype)
        with jax.named_scope("proj"):
            z = L.dense(p["proj"], jnp.concatenate(
                [_norm(cfg, p["embed_norm"], e),
                 _norm(cfg, p["hidden_norm"], hidden)], axis=-1), cfg.dtype)
        with jax.named_scope("block"):
            z, stats = block_apply(
                p["block"], z, cfg, mask=mask, attn_fn=attn_fn,
                rope=_rope_tables(cfg, s).get(kind),
                window=cfg.window if kind == SLIDING else None)
        with jax.named_scope("ln_f"):
            return _norm(cfg, p["ln_f"], z), _named_updates(stats,
                                                            "mtp/block")


def logits(params, cfg, hidden):
    """Output projection in float32: the embedding matrix again, or the
    head's own (``tied_head=False``)."""
    with jax.named_scope("logits"):
        hidden = hidden.astype(jnp.float32)
        head = params["embed"]["embedding"].T if cfg.tied_head \
            else params["lm_head"]["kernel"]
        return hidden @ head.astype(jnp.float32)


def exit_distribution(params, hidden):
    """The looped model's exit distribution, ``(passes, batch, seq)`` in
    float32, as its logarithm: from the states of every pass (after the
    final norm) the gate gives ``lam_t = sigmoid(w . h_t + b)`` on every
    pass but the last; ``p_t = lam_t prod_(j<t) (1 - lam_j)`` is the
    probability of leaving after pass t, and the last pass takes what is
    left, ``prod_(j<T) (1 - lam_j)``, so that a position's ``p`` sums to
    one.  The gate's product is a float32 sum of products, not a matrix
    product: it is one column wide.  Each pass's gate runs under
    ``pass<t>/exit_gate``."""
    w = params["exit_gate"]["kernel"][:, 0].astype(jnp.float32)
    b = params["exit_gate"]["bias"][0].astype(jnp.float32)
    log_p, stay = [], 0.0       # log of the probability of not having left
    for t, h in enumerate(hidden[:-1]):
        with jax.named_scope(f"pass{t}"), jax.named_scope("exit_gate"):
            z = jnp.sum(h.astype(jnp.float32) * w, axis=-1) + b
            log_p.append(stay + jax.nn.log_sigmoid(z))
            stay = stay + jax.nn.log_sigmoid(-z)
    return jnp.stack(log_p + [stay])


# -- autoregressive decode (KV cache) ----------------------------------------

def _decodable(cfg):
    if cfg.loops > 1:
        raise NotImplementedError(
            f"decoding keeps one cache entry a layer, and this configuration "
            f"runs its layers loops={cfg.loops} times over: a cache entry a "
            f"pass and layer, and an exit before the last pass, wait for "
            f"ROADMAP R9")
    attention = {"grouped key-value heads (kv_heads)": cfg.kv_heads,
                 "a window (sliding_attention layers)": cfg.window,
                 "heads by layer (heads_by_layer)": cfg.heads_by_layer,
                 "a gate on attention's output (attn_gate)": cfg.attn_gate,
                 "a head width that is not dim / heads (head_dim)":
                     cfg.head_dim != cfg.dim // cfg.num_heads}
    refused = [name for name, value in attention.items() if value]
    if refused:
        raise NotImplementedError(
            "decoding keeps one full-length cache of dim / heads wide heads "
            "for every query head of every layer, and this configuration "
            "has " + "; ".join(refused) + ": a cache by key-value head, cut "
            "at a window layer's window, waits for ROADMAP R3")
    block = (cfg.norm, cfg.positions, cfg.ffn, cfg.qk_norm, cfg.bias,
             cfg.tied_head, cfg.norm_position, cfg.layer_types,
             cfg.mtp_depth)
    if block != ("layernorm", "learned", "mlp", False, True, True, "pre",
                 None, 0):
        raise NotImplementedError(
            "decoding is implemented for the default block only (LayerNorm, "
            "learned positions, biased projections, an MLP, a tied head); "
            "through rope, QK-norm, moe, output or sandwich norms, "
            "linear-attention "
            "layers (recurrent state beside a KV cache) or latent attention "
            "(a latent cache) it waits for ROADMAP R2")


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
