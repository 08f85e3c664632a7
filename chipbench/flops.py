"""Operations and bytes, counted from shapes.

The yardstick's arithmetic: what a model's forward and backward passes need
per token, and what one call of each attention kernel needs.  Nothing here
is measured and nothing is read from the program; a later PR that changes a
kernel cannot change what the kernel is held to.
"""

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def transformer_flops_per_token(*, width, layers, mlp_width, vocab, seq_len,
                                causal, head_share):
    """Forward + backward operations one input position needs.

    6 x the matrix-multiply parameters a position passes through (2 forward,
    4 backward): per layer the four attention projections and the two MLP
    matrices; the tied output head, counted at the share of positions it is
    applied to (all for a causal LM, the masked ones for a masked LM).  Plus
    attention itself: q.k^T and p.v are 2 * seq * width each per position
    and layer forward, three times that with the backward; a causal mask
    halves it.  No recomputation, no embedding lookup, no biases, no
    LayerNorm.
    """
    per_layer = 4 * width * width + 2 * width * mlp_width
    matmul = 6 * (layers * per_layer + vocab * width * head_share)
    attention = 12 * layers * seq_len * width * (0.5 if causal else 1.0)
    return matmul + attention


def attention_kernel_cost(kernel, *, batch_heads, seq_len, head_width,
                          causal):
    """``(operations, bytes)`` one call of ``kernel`` needs at operands of
    (batch_heads, seq_len, head_width), as ``ops/flash_attention.py`` splits
    the work: the forward makes q.k^T and p.v; the dq kernel recomputes
    q.k^T, makes do.v^T and ds.k; the dk/dv kernel recomputes q.k^T, makes
    do.v^T, p^T.do and ds^T.q.  Each is 2 * seq^2 * head_width operations a
    head, halved under a causal mask.  Bytes are each operand read once and
    each result written once: q, k, v, o, do in bf16; the row statistics
    (log-sum-exp, delta) and the three gradients in f32.
    """
    matmuls = {"flash_fwd": 2, "flash_bwd_dq": 3, "flash_bwd_dkv": 4}[kernel]
    ops = (matmuls * 2 * batch_heads * seq_len * seq_len * head_width
           * (0.5 if causal else 1.0))
    tile = batch_heads * seq_len * head_width
    row = batch_heads * seq_len
    if kernel == "flash_fwd":
        nbytes = 3 * tile * 2 + tile * 2 + row * 4
    elif kernel == "flash_bwd_dq":
        nbytes = 4 * tile * 2 + 2 * row * 4 + tile * 4
    else:
        nbytes = 4 * tile * 2 + 2 * row * 4 + 2 * tile * 4
    return ops, nbytes


def roofline_seconds(ops, nbytes, peak):
    """Least time the chip could take, and which peak sets it."""
    compute, memory = ops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
