"""Operations and bytes of the delta rule with a decay a channel of the key
(Kimi Delta Attention's), counted from shapes.

The yardstick's arithmetic, as ``flops_gdn.py`` is for the rule with one
decay a head: nothing here is measured and nothing is read from the program.
The count is the recurrence's, so that no choice of chunk, of sub-block (or
of any other form) can move what the scan is held to: a position of one head
scales the rows of its (d_k, d_v) state by the position's decays (d_k d_v
operations), multiplies it by k, writes a rank-one update and multiplies it
by q, 2 d_k d_v operations each.
"""

PHASES = ("forward", "backward")


def scan_cost(phase, *, positions, heads, key_width, value_width):
    """``(operations, bytes)`` of one layer's scan over ``positions``
    (rows x sequence length) in one ``phase``.  Forward: 7 H d_k d_v
    operations a position (the decay's d_k d_v and the recurrence's 6 d_k
    d_v); q, k, v read and o written once in bf16, the log decay's d_k
    values and the one write strength a head once in f32.  Backward: twice
    the operations; q, k, v and o's gradient read again in bf16 and the
    gates in f32, the gradients of q, k and v written in bf16 and those of
    the gates in f32.  Nothing for states: a perfect kernel keeps them on
    the chip."""
    gates = 4 * (key_width + 1)
    reads = 2 * (2 * key_width + 2 * value_width) + gates
    if phase == "forward":
        ops_a_position, bytes_a_position = 7, reads
    else:
        ops_a_position = 14
        bytes_a_position = reads + 2 * (2 * key_width + value_width) + gates
    return (ops_a_position * positions * heads * key_width * value_width,
            positions * heads * bytes_a_position)
