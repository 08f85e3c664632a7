"""The plain reference: the repo's transformer block in straightforward
``jax.numpy``, float32, dense attention.

It imports nothing from ``autodist_tpu``.  It reads the parameter tree by
the names the program gives it (``embed/embedding``, ``pos_embed``,
``seg_embed``, ``layer<i>/{ln1,attn/{query,key,value,out},ln2,mlp/{up,down}}``,
``ln_f``) because the two sides of the check start from the same values.
The block, as ``models/transformer.py`` defines it: learned positions,
pre-LayerNorm, biased multi-head attention scaled by 1/sqrt(head width),
tanh-GELU MLP, a final LayerNorm, and the embedding matrix reused as the
output head.
"""
import functools
import math

import jax
import jax.numpy as jnp
import optax


def _layernorm(p, x, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _dense(p, x):
    return x @ p["kernel"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _attention(p, x, heads, causal):
    rows, seq, width = x.shape
    head_width = width // heads

    def split(t):
        return t.reshape(rows, seq, heads, head_width).transpose(0, 2, 1, 3)

    q, k, v = (split(_dense(p[name], x)) for name in ("query", "key", "value"))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(head_width)
    if causal:
        keep = jnp.tril(jnp.ones((seq, seq), bool))
        scores = jnp.where(keep, scores, -jnp.inf)
    out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v)
    return _dense(p["out"], out.transpose(0, 2, 1, 3).reshape(rows, seq, width))


def hidden_states(params, ids, *, layers, heads, causal, eps,
                  segment_ids=None):
    """Token ids (rows, seq) -> final hidden states (rows, seq, width)."""
    x = params["embed"]["embedding"][ids] + params["pos_embed"][:ids.shape[1]]
    if segment_ids is not None:
        x = x + params["seg_embed"][segment_ids]

    def block(x, p):
        x = x + _attention(p["attn"], _layernorm(p["ln1"], x, eps), heads,
                           causal)
        h = _gelu_tanh(_dense(p["mlp"]["up"], _layernorm(p["ln2"], x, eps)))
        return x + _dense(p["mlp"]["down"], h), None

    # The layers are stacked and scanned, so that the compiler sees one
    # block and not ``layers`` of them (the cold compile of the unrolled
    # f32 reference took longer than everything else in a run's set-up),
    # and each block is recomputed in the backward pass, or the dense f32
    # scores of every layer would be kept at once.  Neither changes a
    # number.
    stacked = jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves),
        *(params[f"layer{i}"] for i in range(layers)))
    x, _ = jax.lax.scan(jax.checkpoint(block), x, stacked)
    return _layernorm(params["ln_f"], x, eps)


def tied_head_xent(params, hidden, labels):
    """Mean cross-entropy of ``hidden @ embedding^T`` against ``labels``."""
    logits = hidden @ params["embed"]["embedding"].T
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def make_step(loss_fn, learning_rate, chunk_rows):
    """``(optimizer, step)``: ``step(params, opt_state, batch)`` is one plain
    Adam step, ``jax.value_and_grad`` + ``optax.adam``, jitted; it gives
    back ``(params, opt_state, loss)`` and its first two arguments are
    donated.

    A batch is taken ``chunk_rows`` rows at a time and the losses and
    gradients of the chunks are averaged: the loss is a mean over rows of
    equal weight, so that is the whole batch's loss and gradient, and the
    dense f32 attention scores of a whole batch never have to exist.
    """
    opt = optax.adam(learning_rate)

    def loss_and_grads(params, batch):
        n = jax.tree_util.tree_leaves(batch)[0].shape[0] // chunk_rows
        chunks = jax.tree_util.tree_map(
            lambda x: x.reshape((n, chunk_rows) + x.shape[1:]), batch)

        def add_chunk(total, chunk):
            one = jax.value_and_grad(loss_fn)(params, chunk)
            return jax.tree_util.tree_map(jnp.add, total, one), None

        zero = (jnp.zeros(()), jax.tree_util.tree_map(jnp.zeros_like, params))
        total, _ = jax.lax.scan(add_chunk, zero, chunks)
        return jax.tree_util.tree_map(lambda x: x / n, total)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, batch):
        loss, grads = loss_and_grads(params, batch)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return opt, step


def train_losses(loss_fn, params, batches, learning_rate, chunk_rows):
    """The losses of ``len(batches)`` Adam steps from ``params`` (which are
    left as they are), in float32 with exact (``highest``) matrix
    multiplications."""
    opt, step = make_step(loss_fn, learning_rate, chunk_rows)
    losses = []
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(jnp.copy, params)
        opt_state = opt.init(params)
        for batch in batches:
            params, opt_state, loss = step(params, opt_state, batch)
            losses.append(float(loss))
    return losses
