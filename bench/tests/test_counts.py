"""The least work of ``counts.py`` against what the program's paths stream.

The roofline share divides the least time by measured device time; it can
pass 100% only if the least bytes exceed what an implementation must move.
At chicago's published shapes, the Pallas path streams each inner
iteration's expanded counts, Pi rows and in-block row indices over its
padded grid, and the segment path its sorted rows, counts and Pi rows.
"""
import numpy as np
import pytest

import counts

CHICAGO = {"dims": [6186, 24, 77, 32], "nnz": 5330673, "rank": 16}


@pytest.mark.parametrize("n", range(4))
def test_least_bytes_below_what_the_paths_stream(n):
    from repro.core.layout import build_blocked_layout
    from repro.core.policy import default_policy

    dims, nnz, rank = CHICAGO["dims"], CHICAGO["nnz"], CHICAGO["rank"]
    _, least = counts.phi_pass(nnz, dims, rank, n)
    rows = np.sort(np.random.default_rng(n).integers(0, dims[n], nnz))
    pol = default_policy(rank)
    lay = build_blocked_layout(rows.astype(np.int32), dims[n],
                               pol.block_nnz, pol.block_rows)
    slots = lay.gather.shape[0]
    # vals_e and pi_e (float32) and local_rows (int32), plus B read, Phi out
    pallas = slots * (4 + 4 * rank + 4) + 2 * 4 * rank * lay.n_rows_pad
    # rows (int32), counts and Pi rows (float32), plus B read, Phi out
    segment = nnz * (4 + 4 + 4 * rank) + 2 * 4 * rank * dims[n]
    assert least <= pallas and least <= segment


def test_solve_work_counts_scooch_and_khatri_rao():
    dims, nnz, rank = [10, 20, 30], 1000, 4
    flops, nbytes = counts.solve_work(nnz, dims, rank, n_outer=2,
                                      inner_total=15)
    passes = 15 + 2 * 3
    assert flops == passes * nnz * (4 * rank + 2) + 2 * 3 * nnz * rank * 1
    least = min(counts.phi_pass(nnz, dims, rank, n)[1] for n in range(3))
    assert nbytes == passes * least


def test_least_time_names_its_bound():
    peaks = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.least_time(1000, 10, peaks) == (10.0, "compute")
    assert counts.least_time(10, 1000, peaks) == (100.0, "memory")
