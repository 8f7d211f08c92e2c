"""Operations and bytes of one fused low-rank matmul call, ``y = (x @ A) @ B``.

Per stacked slice: x (M, K), A (K, r), B (r, N), y (M, N).  The least the
call must do is both products, ``2 M r (K + N)`` operations, and read x, A
and B and write y once.
"""


def lowrank_call(M: int, K: int, r: int, N: int, *, L: int = 1, itemsize: int = 2):
    """(flops, bytes) of one call over ``L`` stacked slices."""
    flops = 2 * L * M * r * (K + N)
    nbytes = itemsize * L * (M * K + K * r + r * N + M * N)
    return flops, nbytes
