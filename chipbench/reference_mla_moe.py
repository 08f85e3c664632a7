"""The plain reference of the JoyAI-LLM-Flash block (DeepSeek-V3's layer at
another size): ``jax.numpy``, float32, dense causal attention on assembled
keys, every held expert applied by mask.

It imports nothing from ``autodist_tpu``, uses no kernel and sorts nothing.
It reads the parameter tree by the names the program gives it
(``embed/embedding``, ``layer<i>/{ln1,ln2}/scale``,
``layer<i>/attn/{q_down,q_up,kv_down,kv_up,out}/kernel``,
``layer<i>/attn/{q_norm,kv_norm}/scale``, ``layer<i>/mlp/{gate,up,down}/kernel``
(the dense first layers), ``layer<i>/moe/{gate,glu,up,down}/kernel``,
``layer<i>/moe/shared/{glu,up,down}/kernel``, ``layer<i>/moe/bias``,
``ln_f/scale``, ``lm_head/kernel``, ``mtp/{embed_norm,hidden_norm,ln_f}/scale``,
``mtp/proj/kernel``, ``mtp/block/...`` as a layer) because the two sides of
the check start from the same values.  ``x_t`` is the input of position
``t``; RMSNorm everywhere, no bias, SwiGLU is ``W_down(silu(W_gate u) *
W_up u)``:

* block: ``h = x + MLA(RMSNorm(x))``; ``out = h + FFN(RMSNorm(h))``; the
  FFN of the first ``dense_layers`` layers is a dense SwiGLU, every later
  one the expert layer.
* MLA: ``c_q = RMSNorm(W_dq x)``; a head's query ``[q_nope ; q_rope] = W_uq
  c_q``; ``[c_kv ; k_r] = W_dkv x``, ``c_kv <- RMSNorm(c_kv)``; a head's
  ``[k_nope ; v] = W_ukv c_kv``; rotary on adjacent pairs ``(2i, 2i + 1)``
  of ``q_rope`` and of ``k_r``, which is ONE key a position for every head;
  ``score(t, s) = (q_nope(t) . k_nope(s) + q_rope(t) . k_r(s)) /
  sqrt(nope + rope)``, causal softmax, values, ``W_o``.
* expert layer of input ``u``: ``s = sigmoid(W_r u)``; the chosen are the
  ``top_k`` of ``s + b``; ``g_e = route_scale * s_e / sum_chosen s_e'``;
  ``y = SwiGLU_shared(u) + sum over e chosen AND held of g_e SwiGLU_e(u)``:
  the layer holds the experts ``held = (first, count)`` and leaves out what
  the others would add; routing and weights are over all the experts.
* balance: a layer's term is the mean over rows of ``E * sum_e f_e P_e``,
  ``f_e`` the share of the row's ``seq * top_k`` assignments that chose
  ``e``, ``P_e`` the row's mean of ``s_e / sum_e' s_e'``; after a step each
  layer's bias moves by ``rate * sign(mean_e'(c_e') - c_e)``, ``c_e`` the
  step's count of assignments that chose ``e``.
* prediction module: ``z_i = W_p [RMSNorm_e(Emb(t_(i+1))) ;
  RMSNorm_h(f_i)]``, ``f`` the model's output after its final RMSNorm; one
  more block of the expert kind on ``z``; its own final RMSNorm; the SAME
  embedding and head; cross-entropy to ``t_(i+2)``.
* loss = ``xent + mtp_coef * xent_mtp + balance_coef * mean over the expert
  layers (the module's among them) of the balance term``.
"""
import functools

import jax
import jax.numpy as jnp

from chipbench import reference

#: Queries of the dense attention are taken this many at a time, so that the
#: f32 scores of a long row are (heads, QUERY_BLOCK, seq) and not (heads,
#: seq, seq).
QUERY_BLOCK = 512


def rmsnorm(scale, x, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def swiglu(p, x):
    return (jax.nn.silu(x @ p["gate" if "gate" in p else "glu"]["kernel"])
            * (x @ p["up"]["kernel"])) @ p["down"]["kernel"]


def rotate_pairs(x, theta):
    """Rotary positions on the adjacent pairs ``(2i, 2i + 1)`` of ``x``
    (..., seq, width), in place: pair ``i`` at position ``t`` turns by ``t *
    theta^(-2i / width)``."""
    seq, width = x.shape[-2:]
    inv_freq = theta ** (-jnp.arange(0, width, 2, dtype=jnp.float32) / width)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    first, second = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([first * cos - second * sin,
                        second * cos + first * sin], axis=-1)
    return turned.reshape(x.shape)


def latent_attention(p, x, *, heads, nope, rope, eps, theta):
    rows, seq, _ = x.shape

    def split(t):
        return t.reshape(rows, seq, heads, -1).transpose(0, 2, 1, 3)

    q = split(rmsnorm(p["q_norm"]["scale"], x @ p["q_down"]["kernel"], eps)
              @ p["q_up"]["kernel"])
    down = x @ p["kv_down"]["kernel"]
    c_kv, k_r = down[..., :-rope], down[..., -rope:]
    kv = split(rmsnorm(p["kv_norm"]["scale"], c_kv, eps)
               @ p["kv_up"]["kernel"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q = jnp.concatenate([q[..., :nope], rotate_pairs(q[..., nope:], theta)],
                        axis=-1)
    k_r = jnp.broadcast_to(rotate_pairs(k_r, theta)[:, None],
                           (rows, heads, seq, rope))
    keys = jnp.concatenate([k_nope, k_r], axis=-1)
    block = min(QUERY_BLOCK, seq)

    def some_queries(start):
        qs = jax.lax.dynamic_slice_in_dim(q, start, block, axis=2)
        scores = jnp.einsum("bhqd,bhkd->bhqk", qs, keys) \
            / jnp.sqrt(float(nope + rope))
        visible = (start + jnp.arange(block))[:, None] \
            >= jnp.arange(seq)[None, :]
        scores = jnp.where(visible, scores, -jnp.inf)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1),
                          v)

    out = jax.lax.map(jax.checkpoint(some_queries),
                      jnp.arange(0, seq, block))     # (blocks, b, h, block, d)
    out = jnp.moveaxis(out, 0, 2).reshape(rows, heads, seq, -1)
    return out.transpose(0, 2, 1, 3).reshape(rows, seq, -1) \
        @ p["out"]["kernel"]


def route(p, x, *, top_k, route_scale):
    """``(weights, chosen, scores)``: ``chosen`` (rows, seq, top_k) are the
    experts of each position, the ``top_k`` of score plus bias; ``weights``
    (rows, seq, E) ``route_scale`` times the score over the chosen scores'
    sum where the expert was chosen and 0 elsewhere (the bias is not in
    them)."""
    scores = jax.nn.sigmoid(x @ p["gate"]["kernel"])
    _, chosen = jax.lax.top_k(scores + p["bias"], top_k)
    mask = (chosen[..., None] == jnp.arange(scores.shape[-1])).any(axis=-2)
    picked = jnp.where(mask, scores, 0.0)
    weights = route_scale * picked / picked.sum(axis=-1, keepdims=True)
    return weights, chosen, scores


def experts_layer(p, x, *, top_k, route_scale, held):
    """``(y, balance term, counts (E,), rms of the held experts' part of
    y)`` of one expert layer that holds the experts ``held = (first,
    count)``; the matrices are stacked over the held experts."""
    weights, chosen, scores = route(p, x, top_k=top_k,
                                    route_scale=route_scale)
    rows, seq, experts = scores.shape
    first, count = held

    # One held expert at a time over every position, weighted by 0 where the
    # expert was not chosen; its hidden activations are made again in the
    # backward pass.
    def one_expert(y, expert):
        glu, up, down, weight = expert
        hidden = jax.nn.silu(x @ glu) * (x @ up)
        return y + weight[..., None] * (hidden @ down), None

    stacked = (p["glu"]["kernel"], p["up"]["kernel"], p["down"]["kernel"],
               jnp.moveaxis(weights[..., first:first + count], -1, 0))
    routed, _ = jax.lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(x),
                             stacked)
    y = swiglu(p["shared"], x) + routed

    assigned = (chosen[..., None] == jnp.arange(experts)).sum(axis=(1, 2))
    share = assigned / (seq * top_k)                        # (rows, E)
    probs = scores / scores.sum(axis=-1, keepdims=True)
    balance = jnp.mean(experts * jnp.sum(share * probs.mean(axis=1), axis=-1))
    return (y, balance, assigned.sum(axis=0),
            jnp.sqrt(jnp.mean(jnp.square(routed))))


def block(p, x, *, heads, nope, rope, eps, theta, top_k, route_scale, held):
    """``(out, balance term, counts, rms of the held experts' part)`` of one
    block, the last three None where its feed-forward is dense; the
    parameters say which it is."""
    def attn_half(p, x):
        return x + latent_attention(
            p["attn"], rmsnorm(p["ln1"]["scale"], x, eps), heads=heads,
            nope=nope, rope=rope, eps=eps, theta=theta)

    x = jax.checkpoint(attn_half)(p, x)
    u = rmsnorm(p["ln2"]["scale"], x, eps)
    if "mlp" in p:
        return x + swiglu(p["mlp"], u), None, None, None
    y, balance, counts, routed_rms = experts_layer(
        p["moe"], u, top_k=top_k, route_scale=route_scale, held=held)
    return x + y, balance, counts, routed_rms


def head_xent(params, hidden, labels):
    logits = hidden @ params["lm_head"]["kernel"]
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1,
                                                keepdims=True)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def forward(params, tokens, *, layers, eps, **layer):
    """``(xent, the module's xent, mean balance term, {bias variable's
    name: the step's counts (E,)}, mean over the expert layers of the rms
    of what the held experts add)`` of ``tokens`` (rows, seq + 2): inputs
    are all but the last two of a row."""
    ids, nxt, after = tokens[:, :-2], tokens[:, 1:-1], tokens[:, 2:]
    x = params["embed"]["embedding"][ids]
    terms, counts, routed = [], {}, []

    def through(p, x, name):
        x, balance, count, routed_rms = block(p, x, eps=eps, **layer)
        if balance is not None:
            terms.append(balance)
            counts[f"{name}/moe/bias"] = count
            routed.append(routed_rms)
        return x

    for i in range(layers):
        x = through(params[f"layer{i}"], x, f"layer{i}")
    final = rmsnorm(params["ln_f"]["scale"], x, eps)
    xent = head_xent(params, final, nxt)
    m = params["mtp"]
    z = jnp.concatenate(
        [rmsnorm(m["embed_norm"]["scale"], params["embed"]["embedding"][nxt],
                 eps),
         rmsnorm(m["hidden_norm"]["scale"], final, eps)],
        axis=-1) @ m["proj"]["kernel"]
    z = through(m["block"], z, "mtp/block")
    mtp_xent = head_xent(params, rmsnorm(m["ln_f"]["scale"], z, eps), after)
    return (xent, mtp_xent, sum(terms) / len(terms), counts,
            sum(routed) / len(routed))


def loss(params, tokens, *, mtp_coef, balance_coef, **model):
    return loss_and_held_output_rms(params, tokens, mtp_coef=mtp_coef,
                                    balance_coef=balance_coef, **model)[0]


def loss_and_held_output_rms(params, tokens, *, mtp_coef, balance_coef,
                             **model):
    """The loss, and beside it the mean over the expert layers of the rms of
    what the held experts add to their layer's output (the program's
    ``aux["moe.held_output_rms"]``)."""
    xent, mtp_xent, balance, _, routed = forward(params, tokens, **model)
    return xent + mtp_coef * mtp_xent + balance_coef * balance, routed


def state_updates(params, tokens, *, bias_update_rate, mtp_coef=None,
                  balance_coef=None, **model):
    """``{bias variable's name: its value after this step}``: each entry up
    by ``bias_update_rate`` where the expert got fewer of the step's
    assignments than the mean, down where more, as it is where equal."""
    del mtp_coef, balance_coef
    updates = {}
    for name, counts in forward(params, tokens, **model)[3].items():
        p = params
        for part in name.split("/"):
            p = p[part]
        counts = counts.astype(jnp.float32)
        updates[name] = p + bias_update_rate * jnp.sign(counts.mean()
                                                        - counts)
    return updates


def with_updates(params, updates):
    """``params`` with the variables ``updates`` names replaced."""
    def put(tree, parts, value):
        if not parts:
            return value
        return {**tree, parts[0]: put(tree[parts[0]], parts[1:], value)}
    for name, value in updates.items():
        params = put(params, name.split("/"), value)
    return params


def train(params, batches, learning_rate, *, bias_update_rate, **model):
    """``(losses, params)`` of ``len(batches)`` steps from ``params`` (left
    as they are): each one plain Adam step (``reference.make_step``), under
    which a bias does not move (its gradient is zero), and then the biases'
    own update, computed from the values the step began with.  Float32,
    exact (``highest``) products."""
    loss_fn = lambda p, batch: loss(p, batch[0], **model)      # noqa: E731
    opt, step = reference.make_step(loss_fn, learning_rate, chunk_rows=1)
    update = jax.jit(functools.partial(
        state_updates, bias_update_rate=bias_update_rate, **model))
    losses = []
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(jnp.copy, params)
        opt_state = opt.init(params)
        for batch in batches:
            moved = update(params, batch[0])
            params, opt_state, value = step(params, opt_state, batch)
            params = with_updates(params, moved)
            losses.append(float(value))
    return losses, params
