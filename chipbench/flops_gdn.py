"""Operations and bytes of the gated delta rule, counted from shapes.

The yardstick's arithmetic, as ``flops.py`` is for the attention kernels:
nothing here is measured and nothing is read from the program.  The count
is the recurrence's, so that no choice of chunk (or of any other form) can
move what the scan is held to: a position of one head multiplies its
(d_v, d_k) state by k, writes a rank-one update and multiplies the state
by q, 2 d_k d_v operations each.
"""

PHASES = ("forward", "backward")


def scan_cost(phase, *, positions, heads, key_width, value_width):
    """``(operations, bytes)`` of one layer's scan over ``positions``
    (rows x sequence length) in one ``phase``.  Forward: 6 H d_k d_v
    operations a position; q, k, v read and o written once in bf16, the log
    decay and the write strength once in f32.  Backward: twice the
    operations; q, k, v and o's gradient read again in bf16 and the two
    gates in f32 (o itself is not needed: it is linear in the state), the
    gradients of q, k and v written in bf16 and those of the two gates in
    f32.  Nothing for states: a perfect kernel keeps them on the chip."""
    gates = 2 * 4
    reads = 2 * (2 * key_width + 2 * value_width) + gates
    if phase == "forward":
        ops_a_position, bytes_a_position = 6, reads
    else:
        ops_a_position = 12
        bytes_a_position = reads + 2 * (2 * key_width + value_width) + gates
    return (ops_a_position * positions * heads * key_width * value_width,
            positions * heads * bytes_a_position)
