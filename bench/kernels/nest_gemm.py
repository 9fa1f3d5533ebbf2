"""``nest_gemm``: one GEMM ``[m, k] @ [k, n]`` per launch."""


def cost(dims: list[tuple[int, int, int]],
         elem_bytes: int = 4) -> tuple[float, float]:
    """``dims``: the one ``(m, k, n)`` of the launch."""
    (m, k, n), = dims
    flops = 2.0 * m * k * n
    nbytes = float(elem_bytes) * (m * k + k * n + m * n)
    return flops, nbytes
