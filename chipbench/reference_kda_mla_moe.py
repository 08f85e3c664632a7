"""The plain reference of the Ling-3.0-flash language model's block:
``jax.numpy``, float32, the delta rule with a decay a channel one position at
a time, dense causal latent attention with a gate a head, a sigmoid router
limited to groups by sort and mask, every held expert applied by mask.

It imports nothing from ``autodist_tpu`` (the helpers it shares with the
references beside it are theirs), uses no kernel, no chunked form and no
``top_k``.  It reads the parameter tree by the names the program gives it
(``embed/embedding``, ``layer<i>/{ln1,ln2}/scale``,
``layer<i>/kda/{q,k,v,f,b,z,out}/kernel``, ``layer<i>/kda/conv/kernel``,
``layer<i>/kda/{A_log,dt_bias}``, ``layer<i>/kda/norm/scale``,
``layer<i>/attn/{q,kv_down,kv_up,gate,out}/kernel``,
``layer<i>/attn/kv_norm/scale``, ``layer<i>/mlp/{gate,up,down}/kernel``,
``layer<i>/moe/{gate,glu,up,down}/kernel``, ``layer<i>/moe/bias``,
``layer<i>/moe/shared/{glu,up,down}/kernel``, ``ln_f/scale``,
``lm_head/kernel``) because the two sides of the check start from the same
values.  ``x`` is a position's input; ``RMSNorm(x) = x / sqrt(mean(x^2) +
eps) * s``; no bias; SwiGLU is ``W_down(silu(W_gate u) * W_up u)``:

* block ``i``: ``h = x + Mixer_i(RMSNorm(x))``; ``out = h +
  FFN_i(RMSNorm(h))``; the mixer is KDA where ``layer_types[i]`` is ``"kda_attention"`` and latent
  attention where ``"latent_attention"``; the feed-forward is the dense
  SwiGLU where the parameters hold ``mlp`` and the expert layer where
  ``moe``.
* KDA mixer of input ``u`` (``H`` heads of ``d_k`` / ``d_v``): ``q~, k~, v~ =
  W_q u, W_k u, W_v u``; each channel convolved causally with its own taps
  (zeros before the row's start), then SiLU; per head ``q = q' / sqrt(|q'|^2
  + 1e-6) / sqrt(d_k)``, ``k = k' / sqrt(|k'|^2 + 1e-6)``; ``beta =
  sigmoid(W_b u)``, one a head; the gate a channel of a head ``g =
  gate_bound * sigmoid(exp(A_log_h) (W_f u + dt_bias))``, ``alpha = exp g``;
  from ``S_0 = 0``: ``S_t = (I - beta_t k_t k_t^T) diag(alpha_t) S_(t-1) +
  beta_t k_t v_t^T``, ``o_t = S_t^T q_t`` (kept here as its transpose, (d_v,
  d_k)); ``y = W_o (sigmoid(W_z u)_h * RMSNorm(o))``, the norm over all ``H
  d_v`` lanes together, the gate one scalar a head.
* latent attention of input ``u``: ``q_h = W_q u`` (``nope + rope`` a head);
  ``[c ; k_r] = W_dkv u``, ``c <- RMSNorm(c)``, ``[k_h ; v_h] = W_ukv c``;
  the rotary part of ``q_h`` and the one ``k_r`` a position rotated in
  adjacent pairs; causal ``softmax((q_nope . k_nope + q_rope . k_r) /
  sqrt(nope + rope)) v``; ``y = W_o (sigmoid(W_g u)_h * o_h)``.
* expert layer of input ``u``: ``s = sigmoid(W_r u)`` over ALL the experts;
  for the choice only ``s' = s + b``; the experts are ``groups`` groups of
  consecutive ids, a group's score the sum of its two largest ``s'``, the
  ``groups_kept`` best groups stay (ties to the lower group), the chosen are
  the ``top_k`` largest ``s'`` inside them (ties to the lower expert); ``w_e
  = route_scale * s_e / sum_chosen s_e'``; ``y = SwiGLU_shared(u) + sum over
  e chosen AND held of w_e SwiGLU_e(u)``: the layer holds the experts ``held
  = (first, count)`` and leaves out what the others would add.
* loss = next-token cross-entropy; no balance term.

Departures from the published description, none of which changes a number:
norm scales are stored as ``s`` and start at one; the recurrence's gradient
is taken through ``jax.checkpoint`` a segment of positions at a time,
attention takes a block of queries at a time, and each held expert's hidden
activations are made again in the backward pass.

Compile ``loss`` under ``jax.value_and_grad``, as ``reference.make_step``
does (``reference_olmo_hybrid.py`` says why).

``PLANTS`` are faults a caller can plant by name (``plant=``; the check's
controls, ``controls_ling.py``): each is a model that is wrong in one place,
and the configuration's ``check.why`` states how far it reads from the sound
one.
"""
import math

import jax
import jax.numpy as jnp

# The parts this block shares with the references beside it, theirs as they
# stand: RMSNorm, the root mean square, the causal short convolution, the
# held experts one at a time by mask, and the head's cross-entropy
# (``reference_gdn_moe.py``); adjacent-pair rotary in place
# (``reference_mla_moe.py``).
from chipbench.reference_gdn_moe import (head_xent, held_experts, rms,
                                         rmsnorm, short_convolution)
from chipbench.reference_mla_moe import rotate_pairs

#: Positions whose states the recurrence's backward pass holds at once.
SEGMENT = 64
#: Queries whose scores against every key exist at once.
QUERY_BLOCK = 512
KDA, LATENT = "kda_attention", "latent_attention"
#: ``scalar_decay``: a position's decay averaged over a head's channels (the
#: rule with one decay a head); ``unbounded_gate``: the gate without its
#: bound, ``-exp(A_log) softplus(.)``; ``no_head_gate``: the gate a head left
#: off the latent layer; ``no_group_limit``: the router chooses over all
#: groups.
PLANTS = ("scalar_decay", "unbounded_gate", "no_head_gate", "no_group_limit")


# ``1 / (1 + exp(-x))`` as written overflows in its gradient where x is
# under -88 (inf / inf), and the gate's argument ``exp(A_log) (W_f u +
# dt_bias)`` goes there: jax.nn's forms are the same numbers, stable.
silu, sigmoid = jax.nn.silu, jax.nn.sigmoid


def swiglu(p, x):
    return (silu(x @ p["gate" if "gate" in p else "glu"]["kernel"])
            * (x @ p["up"]["kernel"])) @ p["down"]["kernel"]


# -- the KDA mixer ------------------------------------------------------------

def delta_rule(q, k, v, alpha, beta):
    """The recurrence, one position at a time.  ``q``, ``k``, ``alpha``
    (rows, seq, heads, d_k), ``v`` (rows, seq, heads, d_v), ``beta`` (rows,
    seq, heads); gives ``o`` (rows, seq, heads, d_v)."""
    rows, seq, heads, d_k = q.shape
    d_v = v.shape[-1]

    def position(state, x):
        q, k, v, alpha, beta = x                    # (rows, heads, ...)
        state = alpha[..., None, :] * state         # (rows, heads, d_v, d_k)
        written = beta[..., None] * (v - jnp.einsum("rhvk,rhk->rhv",
                                                    state, k))
        state = state + written[..., :, None] * k[..., None, :]
        return state, jnp.einsum("rhvk,rhk->rhv", state, q)

    def segment(state, xs):
        return jax.lax.scan(position, state, xs)

    # Padded positions write nothing (beta 0) and what they decay is read
    # by none.
    pad = -seq % SEGMENT
    xs = tuple(jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
               for t in (q, k, v, alpha, beta))
    xs = tuple(jnp.moveaxis(t, 1, 0).reshape(
        (-1, SEGMENT) + t.shape[:1] + t.shape[2:]) for t in xs)
    _, o = jax.lax.scan(jax.checkpoint(segment),
                        jnp.zeros((rows, heads, d_v, d_k)), xs)
    o = o.reshape((seq + pad, rows, heads, d_v))[:seq]
    return jnp.moveaxis(o, 0, 1)


def decay_gate(p, x, *, heads, gate_bound, plant=None):
    """``g`` (rows, seq, heads, d_k): the bounded gate a channel."""
    rows, seq, _ = x.shape
    f = (x @ p["f"]["kernel"] + p["dt_bias"]).reshape(rows, seq, heads, -1)
    rate = jnp.exp(p["A_log"])[:, None]
    if plant == "unbounded_gate":
        return -rate * jnp.logaddexp(f, 0.0)
    g = gate_bound * sigmoid(rate * f)
    if plant == "scalar_decay":
        g = jnp.broadcast_to(g.mean(axis=-1, keepdims=True), g.shape)
    return g


def kda_mixer(p, x, *, heads, eps, gate_bound, plant=None):
    rows, seq, _ = x.shape
    kernel = p["conv"]["kernel"]
    width = p["q"]["kernel"].shape[1]
    q, k, v = (silu(short_convolution(kernel[:, lo:hi], x @ p[name]["kernel"]))
               .reshape(rows, seq, heads, -1)
               for name, lo, hi in (("q", 0, width), ("k", width, 2 * width),
                                    ("v", 2 * width, kernel.shape[1])))
    q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) \
        / math.sqrt(q.shape[-1])
    k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    beta = sigmoid(x @ p["b"]["kernel"])
    alpha = jnp.exp(decay_gate(p, x, heads=heads, gate_bound=gate_bound,
                               plant=plant))
    o = delta_rule(q, k, v, alpha, beta)
    o = rmsnorm(p["norm"]["scale"], o.reshape(rows, seq, -1), eps)
    o = o.reshape(rows, seq, heads, -1) * sigmoid(
        x @ p["z"]["kernel"])[..., None]
    return o.reshape(rows, seq, -1) @ p["out"]["kernel"]


# -- latent attention ---------------------------------------------------------

def head_gate(p, x):
    """``sigmoid(W_g u)`` (rows, heads, seq, 1): one scalar a head and
    position."""
    return jnp.moveaxis(sigmoid(x @ p["gate"]["kernel"]), -1, 1)[..., None]


def latent_attention(p, x, *, heads, nope, rope, eps, theta, plant=None):
    rows, seq, _ = x.shape

    def split(t):
        return t.reshape(rows, seq, heads, -1).transpose(0, 2, 1, 3)

    q = split(x @ p["q"]["kernel"])
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
    block = math.gcd(seq, QUERY_BLOCK)

    def some_queries(start):
        qs = jax.lax.dynamic_slice_in_dim(q, start, block, axis=2)
        scores = jnp.einsum("bhqd,bhkd->bhqk", qs, keys) \
            / math.sqrt(nope + rope)
        visible = (start + jnp.arange(block))[:, None] \
            >= jnp.arange(seq)[None, :]
        scores = jnp.where(visible, scores, -jnp.inf)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1),
                          v)

    out = jax.lax.map(jax.checkpoint(some_queries),
                      jnp.arange(0, seq, block))     # (blocks, b, h, block, d)
    out = jnp.moveaxis(out, 0, 2).reshape(rows, heads, seq, -1)
    if plant != "no_head_gate":
        out = out * head_gate(p, x)
    return out.transpose(0, 2, 1, 3).reshape(rows, seq, -1) \
        @ p["out"]["kernel"]


# -- the expert layer ---------------------------------------------------------

def ranks(x):
    """The place of each entry of ``x`` along its last axis, the largest
    first, ties to the lower index: by a stable sort."""
    return jnp.argsort(jnp.argsort(-x, axis=-1, stable=True), axis=-1,
                       stable=True)


def kept_groups(choice, *, groups, groups_kept):
    """(..., E) bool: the experts inside the ``groups_kept`` groups whose two
    largest ``choice`` sum highest."""
    size = choice.shape[-1] // groups
    by_group = choice.reshape(choice.shape[:-1] + (groups, size))
    score = jnp.sort(by_group, axis=-1)[..., -2:].sum(axis=-1)
    return jnp.repeat(ranks(score) < groups_kept, size, axis=-1)


def route(p, x, *, top_k, route_scale, groups, groups_kept):
    """``(weights, chosen mask, scores)``: ``chosen`` (rows, seq, E) marks
    the ``top_k`` experts of each position, the largest of score plus bias
    inside the kept groups; ``weights`` (rows, seq, E) ``route_scale`` times
    the score over the chosen scores' sum where the expert was chosen and 0
    elsewhere (the bias is not in them)."""
    scores = sigmoid(x @ p["gate"]["kernel"])
    choice = scores + p["bias"]
    choice = jnp.where(kept_groups(choice, groups=groups,
                                   groups_kept=groups_kept),
                       choice, -jnp.inf)
    chosen = ranks(choice) < top_k
    picked = jnp.where(chosen, scores, 0.0)
    weights = route_scale * picked / picked.sum(axis=-1, keepdims=True)
    return weights, chosen, scores


def experts_layer(p, x, *, held, **router):
    """``(y, counts (E,), rms of the held experts' part of y, the groups a
    position's choices fall in, mean over the positions)`` of one expert
    layer that holds the experts ``held = (first, count)``; the matrices are
    stacked over the held experts."""
    weights, chosen, _ = route(p, x, **router)
    first, count = held
    routed = held_experts(p, x, weights[..., first:first + count])
    reached = chosen.reshape(chosen.shape[:-1] + (router["groups"], -1)) \
        .any(axis=-1).sum(axis=-1).mean()
    return swiglu(p["shared"], x) + routed, chosen.sum(axis=(0, 1)), \
        rms(routed), reached


# -- the model ----------------------------------------------------------------

def block(p, x, *, kind, heads, nope, rope, eps, theta, gate_bound, held,
          plant=None, **router):
    """``(out, the mixer's output's root mean square about its mean over a
    row's positions, counts, rms of the held experts' part, groups reached)``
    of one block of layer type ``kind``; the last three None where its
    feed-forward is dense."""
    def mixer_half(p, x):
        u = rmsnorm(p["ln1"]["scale"], x, eps)
        if kind == KDA:
            y = kda_mixer(p["kda"], u, heads=heads, eps=eps,
                          gate_bound=gate_bound, plant=plant)
        else:
            y = latent_attention(p["attn"], u, heads=heads, nope=nope,
                                 rope=rope, eps=eps, theta=theta, plant=plant)
        return x + y, rms(y - y.mean(axis=1, keepdims=True))

    x, mixed_std = jax.checkpoint(mixer_half)(p, x)
    u = rmsnorm(p["ln2"]["scale"], x, eps)
    if "mlp" in p:
        return x + swiglu(p["mlp"], u), mixed_std, None, None, None
    if plant == "no_group_limit":
        router = {**router, "groups_kept": router["groups"]}
    y, counts, routed_rms, reached = experts_layer(p["moe"], u, held=held,
                                                   **router)
    return x + y, mixed_std, counts, routed_rms, reached


def forward(params, tokens, *, layer_types, eps, **layer):
    """``(xent, probes, {bias variable's name: the step's counts (E,)})`` of
    ``tokens`` (rows, seq + 1): inputs are all but the last of a row.
    ``probes`` are means over layers: ``held_output_rms`` of the root mean
    square of what the held experts add and ``groups_reached`` of the
    groups a position's choices fall in (the expert layers),
    ``attn_output_std`` and ``kda_output_std`` of the root mean square of
    what the mixer adds about its mean over a row's positions (the layers of
    each kind)."""
    ids, nxt = tokens[:, :-1], tokens[:, 1:]
    x = params["embed"]["embedding"][ids]
    counts = {}
    probes = {"held_output_rms": [], "groups_reached": [],
              "attn_output_std": [], "kda_output_std": []}
    for i, kind in enumerate(layer_types):
        x, mixed_std, count, routed_rms, reached = block(
            params[f"layer{i}"], x, kind=kind, eps=eps, **layer)
        probes["kda_output_std" if kind == KDA
               else "attn_output_std"].append(mixed_std)
        if count is not None:
            counts[f"layer{i}/moe/bias"] = count
            probes["held_output_rms"].append(routed_rms)
            probes["groups_reached"].append(reached)
    xent = head_xent(params, rmsnorm(params["ln_f"]["scale"], x, eps), nxt)
    return xent, {name: sum(values) / len(values)
                  for name, values in probes.items() if values}, counts


def loss_and_probes(params, tokens, **model):
    """The loss, and beside it :func:`forward`'s probes (the program's
    ``aux["moe.held_output_rms"]``, ``aux["moe.groups_reached"]``,
    ``aux["attn.output_std"]``, ``aux["kda.output_std"]``)."""
    xent, probes, _ = forward(params, tokens, **model)
    return xent, probes


def loss(params, tokens, **model):
    return loss_and_probes(params, tokens, **model)[0]


def bias_updates(params, tokens, *, bias_update_rate, **model):
    """``{bias variable's name: its value after this step}``: each entry up
    by ``bias_update_rate`` where the expert got fewer of the step's
    assignments than the mean, down where more, as it is where equal."""
    updates = {}
    for name, counts in forward(params, tokens, **model)[2].items():
        layer = name.split("/")[0]
        counts = counts.astype(jnp.float32)
        updates[name] = params[layer]["moe"]["bias"] \
            + bias_update_rate * jnp.sign(counts.mean() - counts)
    return updates
