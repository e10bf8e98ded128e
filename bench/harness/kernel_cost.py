"""Operations and bytes the Pallas Gram kernel's algorithm needs, from
the unpadded shapes (not the padded tiles the kernel computes).

One Gram call between M and N pattern vectors of width D (paper Eq. 6,
exponential kernel) needs the squared norms, (M + N) D multiply-adds;
the cross products, M N D multiply-adds; and per entry the distance
identity, its clamp, a square root, a division, an exponential and the
scale, 8 operations.  It reads both pattern sets and writes the matrix,
in float32.
"""
from __future__ import annotations


def gram(M: int, N: int, D: int) -> tuple[int, int]:
    """(operations, bytes) of one Gram call."""
    ops = 2 * (M + N) * D + 2 * M * N * D + 8 * M * N
    nbytes = 4 * (M * D + N * D + M * N)
    return ops, nbytes


def gp_forecast_grams(gp: dict, horizon: int) -> list[tuple[int, int, int]]:
    """The (M, N, D) of every kernel call one GP forecast row makes: one
    per evidence step (the gradient recomputes the Gram without the
    kernel), one for the posterior, one per forecast step."""
    n, d = int(gp["max_patterns"]), int(gp["history"]) + 1
    return ([(n, n, d)] * (int(gp["opt_steps"]) + 1)
            + [(1, n, d)] * int(horizon))


def gp_row_cost(gp: dict, horizon: int) -> tuple[int, int]:
    """(operations, bytes) of the kernel calls of one forecast row."""
    cost = [gram(*s) for s in gp_forecast_grams(gp, horizon)]
    return sum(c[0] for c in cost), sum(c[1] for c in cost)


def least_seconds(ops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The roofline's least time and which bound sets it."""
    t_ops = ops / peak["flops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")
