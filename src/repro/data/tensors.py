"""Synthetic FROSTT-shaped sparse count tensors (paper Table 2).

The six evaluation tensors, with true FROSTT dimensions and an ``nnz``
scale knob so CPU benchmarks stay tractable (the paper's counts are in
the millions; scale=1.0 reproduces them).  Values are Poisson counts from
a planted low-rank model — the generative assumption of CP-APR — so
decomposition quality is checkable against ground truth.
"""
from __future__ import annotations

import zlib

import jax

from repro.core.sparse_tensor import SparseTensor, random_poisson_tensor

__all__ = ["FROSTT", "make_tensor", "TENSOR_NAMES"]

# name -> (dims, paper nnz)
FROSTT = {
    "chicago": ((6_186, 24, 77, 32), 5_330_673),
    "enron": ((6_066, 5_699, 244_268, 1_176), 54_202_099),
    "lbnl": ((1_605, 4_198, 1_631, 4_209, 868_131), 1_698_825),
    "nell2": ((12_092, 9_184, 28_818), 76_879_419),
    "nips": ((2_482, 2_862, 14_036, 17), 3_101_609),
    "uber": ((183, 24, 1_140, 1_717), 3_309_490),
}

TENSOR_NAMES = tuple(FROSTT)


def make_tensor(name: str, scale: float = 0.01, rank: int = 8,
                seed: int = 0) -> tuple:
    """Synthesize one FROSTT-shaped tensor.

    Returns (SparseTensor, ground-truth KTensor).  ``scale`` multiplies the
    paper's nnz (default 1% for CPU-speed benchmarks).  The draw is a pure
    function of ``(name, scale, rank, seed)``: the key comes from a crc32 of
    the name and seed, which, unlike ``hash``, no process salts.
    """
    dims, nnz = FROSTT[name]
    n = max(int(nnz * scale), 1_000)
    key = jax.random.PRNGKey(zlib.crc32(f"{name}:{seed}".encode()) & 0x7FFFFFFF)
    return random_poisson_tensor(key, dims, nnz=n, rank=rank)
