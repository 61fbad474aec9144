"""The benchmark's own data: count tensors and starting points, from a seed.

A copy of the arithmetic of the program's ``random_poisson_tensor`` (a
planted low-rank Poisson model: each draw picks a component by its weight,
then one coordinate per mode from that component's factor column, with a
count of 1 + Poisson(1)), kept here so that a change to the program cannot
change the data it is measured on.  One departure, for steadiness: the
tensor holds exactly the configuration's ``nnz`` distinct coordinates
(draws are topped up and the first ``nnz`` distinct ones kept), so every
seed gives the same number of nonzeros.

Everything here is host NumPy in bulk; the arrays go to the device once.
"""
from __future__ import annotations

import numpy as np

# streams of one seed: the planted model, the draws, starting points and
# the sample that is checked
PLANTED, DRAWS, START, SAMPLE = 0, 1, 2, 4


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one stream of one seed (any non-negative int)."""
    return np.random.default_rng([int(seed), *map(int, stream)])


def ktensor(r: np.random.Generator, dims, rank: int) -> tuple:
    """Random non-negative Kruskal tensor ``(lam, factors)``, float32.

    Factors are uniform in [0.1, 1) with unit column sums and the weights
    uniform in [0.5, 2), as the program's ``random_ktensor`` draws them.
    """
    factors = []
    for i_n in dims:
        f = r.uniform(0.1, 1.0, size=(int(i_n), rank))
        factors.append((f / f.sum(axis=0)).astype(np.float32))
    lam = r.uniform(0.5, 2.0, size=rank).astype(np.float32)
    return lam, factors


def linear_index(idx: np.ndarray, dims) -> np.ndarray:
    lin = np.zeros(idx.shape[0], np.int64)
    for n, d in enumerate(dims):
        lin = lin * int(d) + idx[:, n].astype(np.int64)
    return lin


def _draw(r, lam, factors, dims, m: int) -> tuple:
    """``m`` coordinates and counts from the planted model.

    The draws are grouped by component once, so that each mode takes one
    ``searchsorted`` per component over a contiguous slice.
    """
    cdf_r = np.cumsum(lam.astype(np.float64))
    comp = np.searchsorted(cdf_r / cdf_r[-1], r.random(m), side="right")
    comp = np.minimum(comp, lam.shape[0] - 1).astype(np.int16)
    order = np.argsort(comp, kind="stable")  # a radix sort for int16
    bounds = np.searchsorted(comp[order], np.arange(lam.shape[0] + 1))
    idx = np.empty((m, len(dims)), np.int32)
    for n, f in enumerate(factors):
        cdf = np.cumsum(f.astype(np.float64), axis=0)
        cdf /= cdf[-1]
        u = r.random(m)[order]
        col = np.empty(m, np.int64)
        for c in range(lam.shape[0]):
            lo, hi = bounds[c], bounds[c + 1]
            col[lo:hi] = np.searchsorted(cdf[:, c], u[lo:hi], side="right")
        idx[order, n] = np.minimum(col, int(dims[n]) - 1)
    vals = (r.poisson(1.0, size=m) + 1).astype(np.float32)
    return idx, vals


def _stable_sort(lin: np.ndarray) -> tuple:
    """``(order, lin[order])`` with ties in draw order: the draw index is
    packed below the linear index, so that one unstable sort of distinct
    keys does it, where the two fit in 63 bits."""
    shift = int(lin.size).bit_length()
    if int(lin.max(initial=0)).bit_length() + shift > 63:
        order = np.argsort(lin, kind="stable")
        return order, lin[order]
    key = np.sort((lin << shift) | np.arange(lin.size, dtype=np.int64))
    return key & ((1 << shift) - 1), key >> shift


def poisson_tensor(r: np.random.Generator, dims, nnz: int,
                   planted: tuple) -> tuple:
    """Exactly ``nnz`` distinct nonzeros ``(indices int32, values float32)``.

    Repeated draws of one cell add their counts, and the first ``nnz``
    distinct cells in draw order are kept.  The result is sorted by linear
    index, as the program's generator leaves it.
    """
    lam, factors = planted
    m = int(nnz * 1.05) + 1000
    while True:
        idx, vals = _draw(r, lam, factors, dims, m)
        order, lin_s = _stable_sort(linear_index(idx, dims))
        head = np.flatnonzero(np.r_[True, lin_s[1:] != lin_s[:-1]])
        if head.size >= nnz:
            break
        m *= 2
    first = order[head]  # each distinct cell's first draw, by linear index
    sums = np.add.reduceat(vals[order].astype(np.float64), head)
    # the nnz distinct cells drawn first: those whose first draw is no later
    # than the nnz-th first draw in draw order
    is_first = np.zeros(m, bool)
    is_first[first] = True
    cutoff = np.flatnonzero(is_first)[nnz - 1]
    keep = first <= cutoff
    return idx[first[keep]], sums[keep].astype(np.float32)
