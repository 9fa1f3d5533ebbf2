"""``fused_chain``: a chain of GEMMs in one launch; only the chain's
input, every layer's weight and the last output cross HBM."""


def cost(dims: list[tuple[int, int, int]],
         elem_bytes: int = 4) -> tuple[float, float]:
    """``dims``: each layer's ``(m, k, n)``, in chain order."""
    flops = sum(2.0 * m * k * n for m, k, n in dims)
    m0, k0, _ = dims[0]
    ml, _, nl = dims[-1]
    nbytes = float(elem_bytes) * (m0 * k0 + sum(k * n for _, k, n in dims)
                                  + ml * nl)
    return flops, nbytes
