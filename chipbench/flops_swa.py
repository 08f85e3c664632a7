"""Operations and bytes of the attention kernels with grouped key-value heads
and a window, counted from shapes.

The yardstick's arithmetic, as ``flops.py`` is for the kernels of one head
count and a whole causal triangle: nothing here is measured and nothing is
read from the program.  ``heads`` query heads read ``kv_heads`` key-value
heads of the same width, and position t sees the keys ``s <= t`` that lie
inside its window, ``t - window < s``, or all of them with no window.
"""


def seen_scores(seq_len, window=None):
    """Scores one head computes over a row of ``seq_len`` positions."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def grouped_window_kernel_cost(kernel, *, batch, heads, kv_heads, seq_len,
                               head_dim, window=None):
    """``(operations, bytes)`` one call of ``kernel`` needs, by the products
    it has to make and nothing the mask hides: the forward q.k^T and p.v;
    the dq kernel q.k^T again, do.v^T and ds.k; the dk/dv kernel q.k^T
    again, do.v^T, p^T.do and ds^T.q; each ``2 x head_dim`` operations a
    seen score (:func:`seen_scores`) and query head.  Bytes are each operand
    read once and each result written once in bf16: q, o, do and dq once a
    QUERY head, k, v, dk and dv once a KEY-VALUE head (what a group shares
    is counted once); the row statistics (log-sum-exp, delta) in f32 a query
    head."""
    products = {"flash_fwd": 2, "flash_bwd_dq": 3, "flash_bwd_dkv": 4}[kernel]
    ops = products * 2 * batch * heads * seen_scores(seq_len, window) \
        * head_dim
    q = batch * heads * seq_len * head_dim          # elements a query head
    kv = batch * kv_heads * seq_len * head_dim      # a key-value head
    stat = batch * heads * seq_len * 4
    if kernel == "flash_fwd":
        nbytes = 2 * (q + 2 * kv) + 2 * q + stat
    elif kernel == "flash_bwd_dq":
        nbytes = 2 * (2 * q + 2 * kv) + 2 * stat + 2 * q
    else:
        nbytes = 2 * (2 * q + 2 * kv) + 2 * stat + 2 * 2 * kv
    return ops, nbytes
