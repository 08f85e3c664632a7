"""The plain reference of the looped language model (Ouro, arXiv:2510.25741):
``jax.numpy``, float32, dense causal attention.

It imports nothing from ``autodist_tpu`` and uses no kernel.  It reads the
parameter tree by the names the program gives it (``embed/embedding``,
``layer<i>/{ln1,ln1_out,ln2,ln2_out}/scale``,
``layer<i>/attn/{query,key,value,out}/kernel``,
``layer<i>/mlp/{gate,up,down}/kernel``, ``ln_f/scale``, ``lm_head/kernel``,
``exit_gate/{kernel,bias}``) because the two sides of the check start from
the same values.  With ``x`` a row's states, N layers and T passes, no bias
but the gate's:

* ``x <- Emb(ids)``: no scaling, no position table.
* pass t = 1..T, layer i = 0..N-1, the same variables on every pass:
  ``a = x + RMSNorm_(i,2)(Attn_i(RMSNorm_(i,1)(x)))``;
  ``x <- a + RMSNorm_(i,4)(W_down (silu(W_gate n) * W_up n))`` with ``n =
  RMSNorm_(i,3)(a)`` (the "sandwich": a norm on each sublayer's input and
  on its output; ``ln1``, ``ln1_out``, ``ln2``, ``ln2_out`` in that order).
  ``Attn``: heads of ``width / heads``, as many key-value heads,
  rotate-half rotary over a head's whole width, causal ``softmax(q k^T /
  sqrt(head width)) v``, an output projection.
* after pass t: ``h_t = RMSNorm_f(x)``, and the next pass starts from
  ``h_t`` (one final norm, used T times).
* ``logits_t = h_t W_head``; ``l_t(i)`` the cross-entropy of position i.
* exit gate, used on passes 1..T-1: ``lam_t(i) = sigmoid(w . h_t(i) + b)``;
  ``p_1 = lam_1``, ``p_t = lam_t prod_(j<t) (1 - lam_j)``, ``p_T =
  prod_(j<T) (1 - lam_j)``.
* ``loss = mean_i [ sum_t p_t(i) l_t(i) - beta H(p(i)) ]``, ``H = -sum_t
  p_t log p_t``.

``plant`` names one departure from these equations, for the checks that a
wrong program is refused: the reference with ``plant=X`` is the right
reference of a program with fault X.
"""
import math

import jax
import jax.numpy as jnp

PLANTS = ("three_passes", "unnormed_restart", "no_output_norms",
          "last_pass_only", "no_survival_product", "no_entropy",
          "rope_theta_1e4")


def rmsnorm(scale, x, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rotary_tables(seq, head_width, theta):
    """``(cos, sin)`` of (seq, head_width): angle ``t * theta^(-2i / w)``
    for i < w / 2, the same again for the upper half."""
    inv_freq = theta ** (-jnp.arange(0, head_width, 2, dtype=jnp.float32)
                         / head_width)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(angles), jnp.sin(angles)


def rotate(x, cos, sin):
    """Rotate-half: element i of a head pairs with element i + w / 2."""
    half = x.shape[-1] // 2
    swapped = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + swapped * sin


def attention(p, x, *, heads, theta):
    rows, seq, _ = x.shape

    def split(t):
        return t.reshape(rows, seq, heads, -1).transpose(0, 2, 1, 3)

    q, k, v = (split(x @ p[name]["kernel"])
               for name in ("query", "key", "value"))
    head_width = q.shape[-1]
    cos, sin = (t.astype(x.dtype)
                for t in rotary_tables(seq, head_width, theta))
    q, k = rotate(q, cos, sin), rotate(k, cos, sin)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(head_width)
    keep = jnp.tril(jnp.ones((seq, seq), bool))
    scores = jnp.where(keep, scores, -jnp.inf)
    out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v)
    out = out.transpose(0, 2, 1, 3).reshape(rows, seq, -1)
    return out @ p["out"]["kernel"]


def layer(p, x, *, heads, eps, theta, output_norms=True):
    """One layer of one pass.  ``output_norms`` False leaves the two norms
    of the sublayers' outputs out (plain pre-norm: a planted fault)."""
    def out_norm(name, y):
        return rmsnorm(p[name]["scale"], y, eps) if output_norms else y

    a = x + out_norm("ln1_out", attention(
        p["attn"], rmsnorm(p["ln1"]["scale"], x, eps), heads=heads,
        theta=theta))
    n = rmsnorm(p["ln2"]["scale"], a, eps)
    mlp = p["mlp"]
    fed = (jax.nn.silu(n @ mlp["gate"]["kernel"]) * (n @ mlp["up"]["kernel"])
           ) @ mlp["down"]["kernel"]
    return a + out_norm("ln2_out", fed)


def pass_states(params, ids, *, layers, passes, heads, eps, theta,
                plant=None):
    """``[h_1, ..., h_T]``: every pass's states after the final norm."""
    if plant == "rope_theta_1e4":
        theta = 1e4
    if plant == "three_passes":
        passes -= 1
    # The layers are stacked and scanned, and the passes are scanned over
    # them, so that the compiler sees one layer and not ``passes x layers``
    # of them; each layer is made again in the backward pass, or the dense
    # f32 scores of every layer application would be kept at once.  Neither
    # changes a number.
    stacked = jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves),
        *(params[f"layer{i}"] for i in range(layers)))
    one_layer = jax.checkpoint(lambda x, p: (layer(
        p, x, heads=heads, eps=eps, theta=theta,
        output_norms=plant != "no_output_norms").astype(x.dtype), None))

    def one_pass(x, _):
        x, _ = jax.lax.scan(one_layer, x, stacked)
        h = rmsnorm(params["ln_f"]["scale"], x, eps)
        return (x if plant == "unnormed_restart" else h), h

    _, hidden = jax.lax.scan(one_pass, params["embed"]["embedding"][ids],
                             None, length=passes)
    return list(hidden)


def exit_distribution(params, hidden, plant=None):
    """``p`` of (passes, rows, seq): a position's probabilities of leaving
    after each pass; they sum to one."""
    w, b = params["exit_gate"]["kernel"][:, 0], params["exit_gate"]["bias"][0]
    lam = [jax.nn.sigmoid(h @ w + b) for h in hidden[:-1]]
    p, stay = [], jnp.ones_like(lam[0])
    for lam_t in lam:
        p.append(lam_t if plant == "no_survival_product" else lam_t * stay)
        stay = stay * (1.0 - lam_t)
    return jnp.stack(p + [stay])


def pass_logits(params, hidden):
    """Every pass's logits, in float32 (which they are already, unless the
    caller runs the whole reference in a lower precision as a control)."""
    return [(h @ params["lm_head"]["kernel"]).astype(jnp.float32)
            for h in hidden]


def loss(params, tokens, *, layers, passes, heads, eps, theta, beta,
         plant=None):
    """The training loss of ``tokens`` (rows, seq + 1): inputs are all but
    the last of a row, targets all but the first."""
    ids, labels = tokens[:, :-1], tokens[:, 1:]
    hidden = pass_states(params, ids, layers=layers, passes=passes,
                         heads=heads, eps=eps, theta=theta, plant=plant)
    xent = []
    for logits in pass_logits(params, hidden):
        logp = logits - jax.scipy.special.logsumexp(logits, axis=-1,
                                                    keepdims=True)
        xent.append(-jnp.take_along_axis(logp, labels[..., None],
                                         axis=-1)[..., 0])
    xent = jnp.stack(xent)
    if plant == "last_pass_only":
        return jnp.mean(xent[-1])
    p = exit_distribution(params, hidden, plant)
    entropy = -jnp.sum(p * jnp.log(jnp.maximum(p, 1e-30)), axis=0)
    if plant == "no_entropy":
        beta = 0.0
    return jnp.mean(jnp.sum(p * xent, axis=0) - beta * entropy)
