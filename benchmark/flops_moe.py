"""Operations and bytes the three grouped-matmul kernels need
(`paddle_tpu/ops/pallas/grouped_matmul.py`: `moe_gmm_fwd`, `moe_gmm_dx`,
`moe_gmm_dw`), computed from shapes: the counts behind
`moe_gmm_roofline_pct`. `rows` is the rows that belong to a group (what the
step really routed to the held experts; padding is not work), K and N the
matrices' two widths, `groups` the matrices. Every count is the least the
algorithm needs: each row and each matrix moves once.
"""
from benchmark import flops

KERNELS = ("fwd", "dx", "dw")


def gmm_flops(rows, k, n):
    """The same 2 rows K N for each of the three products."""
    return 2 * rows * k * n


def gmm_bytes(kernel, rows, k, n, groups, itemsize):
    """fwd reads x (rows, K) and w (G, K, N) and writes (rows, N); dx reads
    dy (rows, N) and w and writes (rows, K); dw reads x and dy and writes
    (G, K, N)."""
    if kernel not in KERNELS:
        raise ValueError("no grouped-matmul kernel %r (have %r)"
                         % (kernel, KERNELS))
    return (rows * k + rows * n + groups * k * n) * itemsize


def gmm_least_seconds(kernel, rows, k, n, groups, itemsize, peaks):
    """(least seconds, which bound applies) of one call."""
    return flops.roofline_seconds(
        gmm_flops(rows, k, n),
        gmm_bytes(kernel, rows, k, n, groups, itemsize), peaks)
