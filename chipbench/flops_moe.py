"""Operations and bytes of an expert layer's grouped matrix products,
counted from shapes.

The yardstick's arithmetic, as ``flops.py`` is for the attention kernels:
nothing here is measured and nothing is read from the program.  ``A`` rows
(one per assignment of a token to an expert, sorted by expert) go through
``E`` experts' matrices; a row meets one expert's matrix only, so a product
of (A, k) rows with (E, k, n) matrices is 2 * A * k * n operations however
the rows are spread over the experts.
"""

#: One SwiGLU expert layer: ``(name, rows' width k, result's width n)`` per
#: product, with ``d`` the model's width and ``h`` one expert's.  Forward:
#: gate, up (d -> h) and down (h -> d).  Backward, two for each of those:
#: the gradient of the rows (the same shapes with the matrix transposed)
#: and the gradient of the matrices (rows^T x gradients, summed per expert).
PRODUCTS = (("gate", "d", "h"), ("up", "d", "h"), ("down", "h", "d"),
            ("gate.d_rows", "h", "d"), ("up.d_rows", "h", "d"),
            ("down.d_rows", "d", "h"),
            ("gate.d_matrix", "d", "h"), ("up.d_matrix", "d", "h"),
            ("down.d_matrix", "h", "d"))


def grouped_product_cost(*, assignments, k, n, experts, bytes_per_element=2):
    """``(operations, bytes)`` of one grouped product in any of its three
    forms: (A, k) rows and (E, k, n) matrices give (A, n) rows, or (A, n)
    gradients and the matrices give (A, k) rows, or (A, k) rows and (A, n)
    gradients give (E, k, n) matrices.  Each reads two of the three arrays
    once and writes the third once; all are ``bytes_per_element`` wide
    (bf16 operands and results)."""
    ops = 2 * assignments * k * n
    elements = assignments * k + experts * k * n + assignments * n
    return ops, elements * bytes_per_element


def expert_layer_products(*, assignments, width, expert_width, experts):
    """``[(name, operations, bytes)]`` of the nine grouped products one
    SwiGLU expert layer makes in a training step."""
    size = {"d": width, "h": expert_width}
    return [(name,) + grouped_product_cost(
        assignments=assignments, k=size[k], n=size[n], experts=experts)
        for name, k, n in PRODUCTS]
