"""Operations and bytes of the gated delta rule whose value heads read fewer
key heads, counted from shapes.

The yardstick's arithmetic, as ``flops_gdn.py`` is for equal heads: nothing
here is measured and nothing is read from the program.  The count is the
recurrence's, so that no choice of chunk (or of any other form) can move what
the scan is held to: a position of one VALUE head multiplies its (d_v, d_k)
state by k, writes a rank-one update and multiplies the state by q, ``2 d_k
d_v`` operations each.  The queries and keys belong to a KEY head, which ``heads
/ key_heads`` value heads read: their bytes are counted once a key head.
"""

PHASES = ("forward", "backward")


def scan_cost(phase, *, positions, heads, key_heads, key_width, value_width):
    """``(operations, bytes)`` of one layer's scan over ``positions`` (rows x
    sequence length) in one ``phase``.  Forward: ``6 H d_k d_v`` operations a
    position over the ``H`` value heads; q and k read once a key head in bf16,
    v read and o written once a value head in bf16, the log decay and the
    write strength once a value head in f32.  Backward: twice the operations;
    q and k read again a key head, v and o's gradient a value head, the two
    gates in f32 (o itself is not needed: it is linear in the state); the
    gradients of q and k written a key head and that of v a value head in
    bf16, those of the two gates in f32.  Nothing for states: a perfect
    kernel keeps them on the chip."""
    gates = 2 * 4
    a_key_head = 2 * 2 * key_width          # q and k, bf16
    a_value_head = 2 * 2 * value_width + gates
    ops_a_position = 6 if phase == "forward" else 12
    if phase == "backward":
        # dq and dk; dv beside v and do, and the gates' gradients.
        a_key_head += 2 * 2 * key_width
        a_value_head += 2 * value_width + gates
    return (ops_a_position * positions * heads * key_width * value_width,
            positions * (key_heads * a_key_head + heads * a_value_head))
