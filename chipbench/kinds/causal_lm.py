"""Kind ``causal_lm``: a GPT-2-shaped decoder trained on next-token loss.

A configuration of this kind carries GPT-2's ``config.json`` keys
(``n_embd``, ``n_head``, ``n_layer``, ``n_positions``, ``vocab_size``).
``program`` is the system under test (the repo's own model code);
everything else here is the yardstick's.
"""
import jax.numpy as jnp
import numpy as np

from chipbench import flops, reference


def program(sizes):
    """``(init(key) -> params, loss_fn(params, batch))`` as the program
    builds them: ``models/lm.py`` over ``models/transformer.py``."""
    from autodist_tpu.models import lm
    from autodist_tpu.models import transformer as T
    cfg = T.TransformerConfig(
        vocab=sizes["vocab_size"], dim=sizes["n_embd"],
        num_heads=sizes["n_head"], num_layers=sizes["n_layer"],
        mlp_dim=4 * sizes["n_embd"], max_len=sizes["n_positions"],
        causal=True, dtype=jnp.dtype(sizes["deployment"]["compute_dtype"]))
    return (lambda key: lm.init(key, cfg)), lm.make_loss_fn(cfg)


def reference_loss(sizes):
    """The same loss in plain float32 ``jax.numpy`` (``reference.py``)."""
    def loss_fn(params, batch):
        (tokens,) = batch
        hidden = reference.hidden_states(
            params, tokens[:, :-1], layers=sizes["n_layer"],
            heads=sizes["n_head"], causal=True,
            eps=sizes["block"]["layernorm_eps"])
        return reference.tied_head_xent(params, hidden, tokens[:, 1:])
    return loss_fn


def host_batch(sizes, traffic, rows, rng):
    """Uniform tokens over the vocabulary, ``seq_len`` + 1 a row: inputs
    and the targets shifted by one (as ``lm.synthetic_batch`` draws them)."""
    return (rng.randint(0, sizes["vocab_size"],
                        (rows, traffic["seq_len"] + 1)).astype(np.int32),)


def tokens_per_row(traffic):
    return traffic["seq_len"]


def flops_per_token(sizes, traffic):
    return flops.transformer_flops_per_token(
        width=sizes["n_embd"], layers=sizes["n_layer"],
        mlp_width=4 * sizes["n_embd"], vocab=sizes["vocab_size"],
        seq_len=traffic["seq_len"], causal=True, head_share=1.0)


def attention_calls(sizes, traffic):
    """Operand shape of one attention kernel call on one chip."""
    return {"batch_heads": traffic["rows_per_chip"] * sizes["n_head"],
            "seq_len": traffic["seq_len"],
            "head_width": sizes["n_embd"] // sizes["n_head"],
            "causal": True}
