"""The generator's bulk arithmetic against a plain per-draw reading of it."""
import numpy as np
import pytest

import gen

SHAPES = [([40, 8, 12, 9], 3000), ([20, 8, 30, 40], 3000), ([7, 5, 3], 90)]


def _plain_draw(r, lam, factors, dims, m):
    """Each draw's coordinate from its own component's column, component by
    component under a mask."""
    cdf_r = np.cumsum(lam.astype(np.float64))
    comp = np.minimum(np.searchsorted(cdf_r / cdf_r[-1], r.random(m),
                                      side="right"), lam.shape[0] - 1)
    idx = np.empty((m, len(dims)), np.int32)
    for n, f in enumerate(factors):
        cdf = np.cumsum(f.astype(np.float64), axis=0)
        cdf /= cdf[-1]
        u = r.random(m)
        col = np.empty(m, np.int64)
        for c in range(lam.shape[0]):
            sel = comp == c
            col[sel] = np.searchsorted(cdf[:, c], u[sel], side="right")
        idx[:, n] = np.minimum(col, dims[n] - 1)
    return idx, (r.poisson(1.0, size=m) + 1).astype(np.float32)


def _plain_tensor(r, dims, nnz, planted):
    """The first ``nnz`` distinct cells in draw order, counts summed, sorted
    by linear index."""
    m = int(nnz * 1.05) + 1000
    while True:
        idx, vals = _plain_draw(r, *planted, dims, m)
        lin = gen.linear_index(idx, dims)
        uniq, first, inv = np.unique(lin, return_index=True,
                                     return_inverse=True)
        if uniq.size >= nnz:
            break
        m *= 2
    sums = np.bincount(inv.ravel(), weights=vals, minlength=uniq.size)
    chosen = np.sort(np.argsort(first, kind="stable")[:nnz])
    return idx[first[chosen]], sums[chosen].astype(np.float32)


@pytest.mark.parametrize("dims,nnz", SHAPES)
@pytest.mark.parametrize("seed", [0, 12345678901, 2**31 + 7])
def test_bulk_draws_match_the_plain_reading(dims, nnz, seed):
    planted = gen.ktensor(gen.rng(seed, gen.PLANTED), dims, 16)
    got = gen.poisson_tensor(gen.rng(seed, gen.DRAWS), dims, nnz, planted)
    want = _plain_tensor(gen.rng(seed, gen.DRAWS), dims, nnz, planted)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    lin = gen.linear_index(got[0], dims)
    assert got[0].shape == (nnz, len(dims)) and np.all(np.diff(lin) > 0)


def test_stable_sort_without_room_to_pack():
    """Linear indices too wide to pack with the draw index still sort with
    ties in draw order."""
    lin = np.array([2**62, 5, 2**62, 5, 1], np.int64)
    order, lin_s = gen._stable_sort(lin)
    assert order.tolist() == [4, 1, 3, 0, 2]
    assert lin_s.tolist() == [1, 5, 5, 2**62, 2**62]
