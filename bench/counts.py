"""What CP-APR MU has to compute and move, from its shapes alone.

These count the algorithm's work, never an implementation's arrays: Pi,
padding, layouts and recomputed operations are not counted.  So a share of
the least time they give reads the same whichever kernel does the work,
and cannot pass 100%.

One pass of Phi for mode n (paper Eq. 3 for the operations) reads each
nonzero's count and its N coordinates once (4 bytes each), the mode's
factor once and writes it once, and reads each other factor once:

    flops = nnz (4R + 2)
    bytes = 4 nnz (N + 1) + 4 R (2 I_n + sum over m != n of I_m)

A mode update makes one such pass for the scooch (Alg. 1 line 3) and one
per inner iteration, and forms the Khatri-Rao rows once, nnz R (N - 2)
multiplications.
"""
from __future__ import annotations


def phi_pass(nnz: int, dims, rank: int, n: int) -> tuple:
    """``(flops, bytes)`` of one Phi pass of mode ``n``."""
    n_modes = len(dims)
    flops = nnz * (4 * rank + 2)
    factors = 2 * dims[n] + sum(d for m, d in enumerate(dims) if m != n)
    return flops, 4 * nnz * (n_modes + 1) + 4 * rank * factors


def khatri_rao(nnz: int, dims, rank: int) -> int:
    """Multiplications that form the Pi rows of one mode update."""
    return nnz * rank * max(len(dims) - 2, 0)


def solve_work(nnz: int, dims, rank: int, n_outer: int,
               inner_total: int) -> tuple:
    """``(flops, bytes)`` of a solve of ``n_outer`` sweeps.

    ``inner_total`` is the inner iterations of all its mode updates.  The
    solve's counts do not say which mode ran how many, so every pass is
    counted at the smallest per-mode bytes, which keeps it a lower bound.
    """
    n_modes = len(dims)
    passes = inner_total + n_outer * n_modes
    flops, _ = phi_pass(nnz, dims, rank, 0)
    least = min(phi_pass(nnz, dims, rank, n)[1] for n in range(n_modes))
    kr = n_outer * n_modes * khatri_rao(nnz, dims, rank)
    return passes * flops + kr, passes * least


def least_time(flops: float, nbytes: float, peaks: dict) -> tuple:
    """``(seconds, bound)``: the larger of the compute and memory times."""
    t_c = flops / peaks["flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_m, "memory") if t_m >= t_c else (t_c, "compute")
