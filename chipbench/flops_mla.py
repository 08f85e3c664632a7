"""Operations and bytes of the attention kernels in their two-product form
(latent attention), counted from shapes.

The yardstick's arithmetic, as ``flops.py`` is for the one-width kernels:
nothing here is measured and nothing is read from the program.  A head's
score is a product over ``nope`` lanes plus one over ``rope`` lanes whose key
is ONE a position, shared by the heads; its values are ``value`` lanes wide.
"""


def two_product_kernel_cost(kernel, *, batch, heads, seq_len, nope, rope,
                            value, causal=True):
    """``(operations, bytes)`` one call of ``kernel`` needs, by the products
    it really makes: the forward q.k^T over ``nope + rope`` lanes and p.v
    over ``value``; the dq kernel q.k^T again, do.v^T over ``value`` and
    ds.k over ``nope + rope``; the dk/dv kernel q.k^T again, do.v^T and
    p^T.do over ``value`` and ds^T.q over ``nope + rope``; each ``2 x seq^2
    x lanes`` operations a head, halved under the causal mask.  Bytes are
    each operand read once and each result written once in bf16: q_nope,
    q_rope, k_nope, v, o, do and their gradients once a head, k_rope and its
    gradient once a POSITION (what the heads share is counted once); the
    row statistics (log-sum-exp, delta) in f32."""
    score = nope + rope
    lanes = {"flash_fwd": score + value,
             "flash_bwd_dq": 2 * score + value,
             "flash_bwd_dkv": 2 * score + 2 * value}[kernel]
    ops = 2 * batch * heads * seq_len * seq_len * lanes \
        * (0.5 if causal else 1.0)
    a_head = batch * heads * seq_len        # elements a lane, once a head
    shared = batch * seq_len                # once a position
    q, k, v = a_head * score, a_head * nope + shared * rope, a_head * value
    stat = a_head * 4
    if kernel == "flash_fwd":
        nbytes = 2 * (q + k + v) + 2 * v + stat
    elif kernel == "flash_bwd_dq":
        nbytes = 2 * (q + k + v) + 2 * v + 2 * stat + 2 * q
    else:
        nbytes = 2 * (q + k + v) + 2 * v + 2 * stat + 2 * (k + v)
    return ops, nbytes
