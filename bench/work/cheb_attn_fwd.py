"""Operations and bytes of one ``cheb_attn`` forward over one graph
(``repro/kernels/cheb_attn.py``): H heads x N rows x B neighbour slots,
d features, a degree-p power series.

Operations: per (head, row, slot) p+1 Horner multiply-adds, the mask, the
denominator's add and a multiply-add per feature; per (head, row, feature)
one divide. Bytes are the layer's logical inputs and output in float32:
scores (H, N, B), features h (N, d), neighbour ids (N, B) int32, the mask
(N, B) as bytes, and the output (H, N, d). The (N, B, d) gathered copy of
h that the wrapper materialises today is not counted, so a kernel that
gathers in place is held to the same work.
"""


def per_graph(n: int, b: int, d: int, heads: int, degree: int):
    """(flops, bytes) of one graph's forward."""
    per_slot = 2 * (degree + 1) + 1 + 1 + 2 * d
    flops = heads * n * (b * per_slot + d)
    nbytes = 4 * heads * n * b + 4 * n * d + 4 * n * b + n * b + 4 * heads * n * d
    return float(flops), float(nbytes)
