"""``flash_decode``: a batch's score and context GEMMs in one launch;
every row is one request with its own K and V."""


def cost(dims: list[tuple[int, int, int]],
         elem_bytes: int = 4) -> tuple[float, float]:
    """``dims``: ``[(rows, d, s), (rows, s, d)]`` for the score and the
    context GEMM; q, each row's K and V, and the output cross HBM."""
    (r, d, s), (_, _, d_out) = dims
    flops = sum(2.0 * m * k * n for m, k, n in dims)
    nbytes = float(elem_bytes) * r * (d + d * s + s * d_out + d_out)
    return flops, nbytes
