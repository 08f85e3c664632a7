"""Kind ``masked_lm``: a BERT-shaped encoder trained on the masked-LM loss.

A configuration of this kind carries BERT's ``config.json`` keys
(``hidden_size``, ``num_attention_heads``, ``num_hidden_layers``,
``intermediate_size``, ``vocab_size``, ``max_position_embeddings``,
``type_vocab_size``).  ``program`` is the system under test; everything
else here is the yardstick's.
"""
import jax.numpy as jnp
import numpy as np

from chipbench import flops, reference


def program(sizes):
    """``(init(key) -> params, loss_fn(params, batch))`` as the program
    builds them: ``models/bert.py`` over ``models/transformer.py``."""
    from autodist_tpu.models import bert
    from autodist_tpu.models import transformer as T
    cfg = T.TransformerConfig(
        vocab=sizes["vocab_size"], dim=sizes["hidden_size"],
        num_heads=sizes["num_attention_heads"],
        num_layers=sizes["num_hidden_layers"],
        mlp_dim=sizes["intermediate_size"],
        max_len=sizes["max_position_embeddings"], causal=False,
        dtype=jnp.dtype(sizes["deployment"]["compute_dtype"]),
        num_segments=sizes["type_vocab_size"])
    return (lambda key: bert.init(key, cfg)), bert.make_loss_fn(cfg)


def reference_loss(sizes):
    """The same loss in plain float32 ``jax.numpy`` (``reference.py``)."""
    def loss_fn(params, batch):
        ids, segments, positions, labels = batch
        hidden = reference.hidden_states(
            params, ids, layers=sizes["num_hidden_layers"],
            heads=sizes["num_attention_heads"], causal=False,
            eps=sizes["block"]["layernorm_eps"], segment_ids=segments)
        picked = jnp.take_along_axis(hidden, positions[..., None], axis=1)
        return reference.tied_head_xent(params, picked, labels)
    return loss_fn


def host_batch(sizes, traffic, rows, rng):
    """Uniform ids, segment ids, masked positions and labels, as
    ``bert.synthetic_batch`` draws them."""
    seq, masked = traffic["seq_len"], traffic["masked_per_row"]
    return (rng.randint(0, sizes["vocab_size"], (rows, seq)).astype(np.int32),
            rng.randint(0, sizes["type_vocab_size"],
                        (rows, seq)).astype(np.int32),
            rng.randint(0, seq, (rows, masked)).astype(np.int32),
            rng.randint(0, sizes["vocab_size"],
                        (rows, masked)).astype(np.int32))


def tokens_per_row(traffic):
    return traffic["seq_len"]


def flops_per_token(sizes, traffic):
    return flops.transformer_flops_per_token(
        width=sizes["hidden_size"], layers=sizes["num_hidden_layers"],
        mlp_width=sizes["intermediate_size"], vocab=sizes["vocab_size"],
        seq_len=traffic["seq_len"], causal=False,
        head_share=traffic["masked_per_row"] / traffic["seq_len"])


def attention_calls(sizes, traffic):
    """Operand shape of one attention kernel call on one chip."""
    return {"batch_heads": (traffic["rows_per_chip"]
                            * sizes["num_attention_heads"]),
            "seq_len": traffic["seq_len"],
            "head_width": (sizes["hidden_size"]
                           // sizes["num_attention_heads"]),
            "causal": False}
