"""Plain CP-APR MU: the reference that decides ``correct``.

Chi & Kolda's multiplicative update (the paper's Alg. 1), written from the
algorithm and not from the program: no kernels, layouts, policies, sorting
or batching.  For each mode n the other factors' rows are multiplied per
nonzero (Pi), and

    Phi[i] = sum over nonzeros z in row i of  x_z / max(<B[i], Pi_z>, eps) * Pi_z

is summed over the nonzeros in chunks of ``chunk``, so that a tensor of
millions of nonzeros fits next to nothing else.  Pi is recomputed in every
pass.  Inadmissible zeros are lifted by ``kappa`` once per mode update
(the scooch), the inner loop updates ``B <- B * Phi`` until the KKT
violation ``max |min(B, 1 - Phi)|`` is at most ``tol`` (checked before the
update) or ``max_inner`` passes ran, and the columns are renormalised into
``lam``.  A solve stops when every mode's last violation is at most
``tol``, or after ``max_outer`` sweeps.

``dtype`` is the precision every array is held and computed in: float32 is
the configuration's; bfloat16 is the control, which has to fail.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 1 << 18


@partial(jax.jit, static_argnames=("n", "n_rows", "max_inner"))
def _mode_update(idx_c, vals_c, factors, lam, tol, eps, kappa, kappa_tol, *,
                 n: int, n_rows: int, max_inner: int):
    dtype = lam.dtype
    rank = lam.shape[0]

    def phi(b):
        def chunk(acc, xs):
            idx, v = xs
            pi = jnp.ones((idx.shape[0], rank), dtype)
            for m, f in enumerate(factors):
                if m != n:
                    pi = pi * f[idx[:, m]]
            s = jnp.sum(b[idx[:, n]] * pi, axis=1)
            w = v / jnp.maximum(s, eps)
            part = jax.ops.segment_sum(w[:, None] * pi, idx[:, n],
                                       num_segments=n_rows)
            return acc + part, None

        acc, _ = jax.lax.scan(chunk, jnp.zeros((n_rows, rank), dtype),
                              (idx_c, vals_c))
        return acc

    a_n = factors[n]
    phi0 = phi(a_n * lam[None, :])
    s = jnp.where((a_n < kappa_tol) & (phi0 > 1), kappa, 0).astype(dtype)
    b0 = (a_n + s) * lam[None, :]

    def cond(st):
        i, _, viol = st
        return (i < max_inner) & (viol > tol)

    def body(st):
        i, b, _ = st
        p = phi(b)
        viol = jnp.max(jnp.abs(jnp.minimum(b, 1 - p)))
        return i + 1, jnp.where(viol > tol, b * p, b), viol

    _, b, viol = jax.lax.while_loop(
        cond, body, (jnp.int32(0), b0, jnp.asarray(jnp.inf, dtype)))
    lam_new = jnp.sum(b, axis=0)
    return b / jnp.maximum(lam_new, eps), lam_new, viol


def normalize(lam, factors) -> tuple:
    """Unit column sums, the mass folded into ``lam``."""
    lam = np.asarray(lam, np.float64).copy()
    out = []
    for f in factors:
        f = np.asarray(f, np.float64)
        col = f.sum(axis=0)
        out.append(f / np.where(col > 0, col, 1.0))
        lam = lam * np.where(col > 0, col, 0.0)
    return lam, out


def chunks(indices: np.ndarray, values: np.ndarray, chunk: int = CHUNK):
    """Nonzeros padded with zero counts at coordinate 0, in (C, chunk) rows.

    A zero count adds ``0 / max(s, eps) = 0`` to every row of Phi.
    """
    nnz = indices.shape[0]
    chunk = min(chunk, 1 << max(int(math.ceil(math.log2(max(nnz, 2)))), 10))
    c = -(-nnz // chunk)
    pad = c * chunk - nnz
    idx = np.concatenate([indices, np.zeros((pad, indices.shape[1]),
                                            indices.dtype)])
    vals = np.concatenate([values, np.zeros(pad, values.dtype)])
    return idx.reshape(c, chunk, -1), vals.reshape(c, chunk)


def cpapr(indices, values, dims, init, *, max_outer: int, max_inner: int,
          tol: float, eps: float, kappa: float, kappa_tol: float,
          dtype=jnp.float32, data=None) -> dict:
    """Solve from ``init = (lam, factors)``; returns the fitted model.

    ``data`` is ``chunks(indices, values)`` placed on the device, where the
    caller already has it.
    """
    lam, factors = normalize(*init)
    if data is None:
        idx_c, vals_c = chunks(np.asarray(indices, np.int32),
                               np.asarray(values, np.float32))
        data = jnp.asarray(idx_c), jnp.asarray(vals_c)
    idx_c, vals_c = data
    vals_c = vals_c.astype(dtype)
    factors = [jnp.asarray(f, dtype) for f in factors]
    lam = jnp.asarray(lam, dtype)
    consts = [jnp.asarray(c, dtype) for c in (tol, eps, kappa, kappa_tol)]
    kkt = []
    for _ in range(max_outer):
        worst = 0.0
        for n in range(len(dims)):
            a, lam, viol = _mode_update(
                idx_c, vals_c, tuple(factors), lam, *consts,
                n=n, n_rows=int(dims[n]), max_inner=max_inner)
            factors[n] = a
            worst = max(worst, float(viol))
        kkt.append(worst)
        if worst <= tol:
            break
    return {"lam": np.asarray(lam, np.float64),
            "factors": [np.asarray(f, np.float64) for f in factors],
            "kkt": kkt}


def factor_gap(got: tuple, ref: dict) -> float:
    """Widest gap of ``B_n = A_n * lam`` over the modes, relative per mode.

    ``got`` is ``(lam, factors)`` of the program's answer; each mode's
    largest entry-wise gap is taken against that mode's largest entry of the
    reference.
    """
    lam_g = np.asarray(got[0], np.float64)
    gap = 0.0
    for f_g, f_r in zip(got[1], ref["factors"]):
        b_g = np.asarray(f_g, np.float64) * lam_g[None, :]
        b_r = f_r * ref["lam"][None, :]
        if b_g.shape != b_r.shape or not np.all(np.isfinite(b_g)):
            return math.inf
        gap = max(gap, float(np.max(np.abs(b_g - b_r)) / np.max(np.abs(b_r))))
    return gap
