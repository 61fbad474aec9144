"""Pallas TPU kernels for the dense matrix-free MTTKRP/Phi tier.

GenTen-style (PAPERS.md, arXiv 2510.14891): on near-dense bands and
small-mode tensors the (nnz, R) Pi materialization and per-nonzero index
indirection of the sparse layouts cost more than the arithmetic they
skip.  These kernels never build Pi — the tensor is streamed through
VMEM as dense slice tiles and the Khatri-Rao contraction happens
in-kernel per tile.

Layout convention (built once per mode by
``repro.core.dense.build_dense_mode``): the tensor is permuted and
reshaped to ``x (K, I, J)`` where ``I`` is the target mode, ``J`` is the
widest non-target mode (the matmul inner width), and ``K`` flattens the
remaining modes row-major.  The factor-side operands are ``c = A_J``
``(J, R)`` and ``a`` ``(K, R)``, the row-major Khatri-Rao product of the
remaining factors; then

    MTTKRP:  M[i, r]   = sum_k sum_j x[k, i, j] * c[j, r] * a[k, r]
    Phi:     m_k       = B @ (c * a[k]).T                  # model slice
             w_k       = where(x[k] > 0, x[k] / max(m_k, eps), 0)
             Phi[i, r] = sum_k (w_k @ c)[i, r] * a[k, r]

(zero tensor entries contribute w = 0, so dense Phi equals the sparse
strategies' Phi exactly — the dense path changes cost, not semantics).

The grid iterates over K tiles of ``block_k`` slices; every step maps to
the *same* ``(I, R)`` output window ("arbitrary" dimension semantics,
zero-init on step 0) so the accumulator never leaves VMEM.  The fused
``phi_mu`` variant transforms the window into ``B * Phi`` plus a KKT
partial on the final step, mirroring the sparse fused epilogue.

Mixed precision: elements (x, c, a, b) may arrive as bf16 while every
``jnp.dot`` pins ``preferred_element_type`` to the f32 ``acc_dtype`` —
the bf16-compute/f32-accumulate tier.  The Phi kernels unroll a static
Python loop over the ``block_k`` slices so every contraction stays a
plain 2-D MXU dot (no batched dot_general for Mosaic to choke on).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dtypes import contract_precision

__all__ = [
    "dense_mttkrp_pallas_call",
    "dense_phi_pallas_call",
    "dense_phi_mu_pallas_call",
    "KKT_TILE",
]

# Single KKT partial tile (one row-block window), callers jnp.max it away.
KKT_TILE = (8, 128)


def _dense_mttkrp_kernel(x_ref, c_ref, a_ref, out_ref, *, acc_dtype):
    g = pl.program_id(0)

    @pl.when(g == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    bk, i_pad, j_pad = x_ref.shape
    # (bk*I, J) @ (J, R) -> one MXU dot per grid step; then the rank-1
    # Khatri-Rao scale by this tile's a rows and the reduce over slices.
    t = jnp.dot(
        x_ref[...].reshape(bk * i_pad, j_pad),
        c_ref[...],
        preferred_element_type=acc_dtype,
        precision=contract_precision(c_ref.dtype),
    ).reshape(bk, i_pad, -1)
    t = t * a_ref[...].astype(acc_dtype)[:, None, :]
    out_ref[...] += t.sum(axis=0)


def _dense_phi_accum(x_ref, c_ref, a_ref, b_ref, *, eps, acc_dtype):
    """One grid step's Phi contribution over its block_k slices.

    Static unroll keeps every contraction a 2-D dot: per slice k the
    model window ``B @ (c*a_k).T`` (MXU), the elementwise Poisson weight
    (VPU, in acc_dtype), and the weighted back-contraction ``w @ c``
    (MXU) scaled by ``a_k``.
    """
    block_k = x_ref.shape[0]
    x = x_ref[...]
    c = c_ref[...]
    a = a_ref[...]
    b = b_ref[...]
    prec = contract_precision(c.dtype)
    acc = jnp.zeros((x_ref.shape[1], c_ref.shape[1]), acc_dtype)
    for k in range(block_k):
        a_k = a[k][None, :]  # (1, R) element dtype
        ca = c * a_k  # (J, R)
        m = jnp.dot(b, ca.T, preferred_element_type=acc_dtype,
                    precision=prec)  # (I, J)
        x_k = x[k].astype(acc_dtype)
        w = jnp.where(x_k > 0, x_k / jnp.maximum(m, eps), 0.0)  # (I, J)
        acc += (
            jnp.dot(w.astype(c.dtype), c, preferred_element_type=acc_dtype,
                    precision=prec)
            * a_k.astype(acc_dtype)
        )
    return acc


def _dense_phi_kernel(x_ref, c_ref, a_ref, b_ref, phi_ref, *, eps, acc_dtype):
    g = pl.program_id(0)

    @pl.when(g == 0)
    def _init():
        phi_ref[...] = jnp.zeros_like(phi_ref)

    phi_ref[...] += _dense_phi_accum(
        x_ref, c_ref, a_ref, b_ref, eps=eps, acc_dtype=acc_dtype
    )


def _dense_phi_mu_kernel(
    x_ref,
    c_ref,
    a_ref,
    b_ref,
    mu_ref,  # (I, R) acc_dtype: Phi accumulator, becomes B*Phi on last step
    kkt_ref,  # KKT_TILE acc_dtype: partial max |min(B, 1-Phi)|
    *,
    eps,
    n_grid,
    acc_dtype,
):
    g = pl.program_id(0)

    @pl.when(g == 0)
    def _init():
        mu_ref[...] = jnp.zeros_like(mu_ref)
        kkt_ref[...] = jnp.zeros_like(kkt_ref)

    mu_ref[...] += _dense_phi_accum(
        x_ref, c_ref, a_ref, b_ref, eps=eps, acc_dtype=acc_dtype
    )

    # Fused epilogue: the accumulated Phi window never leaves VMEM — it
    # is consumed in place by the KKT partial and the MU product.
    # Padding rows/lanes hold B = Phi = 0 -> |min(0, 1)| = 0.
    @pl.when(g == n_grid - 1)
    def _epilogue():
        phi = mu_ref[...]
        b = b_ref[...].astype(acc_dtype)
        viol = jnp.max(jnp.abs(jnp.minimum(b, 1.0 - phi)))
        kkt_ref[...] = jnp.full(kkt_ref.shape, viol, kkt_ref.dtype)
        mu_ref[...] = b * phi


def _call(kernel, n_grid, block_k, i_pad, j_pad, rank_pad, out_shape,
          out_specs, n_inputs, interpret):
    in_specs = [
        pl.BlockSpec((block_k, i_pad, j_pad), lambda g: (g, 0, 0)),  # x tile
        pl.BlockSpec((j_pad, rank_pad), lambda g: (0, 0)),  # c (whole)
        pl.BlockSpec((block_k, rank_pad), lambda g: (g, 0)),  # a tile
    ]
    if n_inputs == 4:
        in_specs.append(
            pl.BlockSpec((i_pad, rank_pad), lambda g: (0, 0))  # B (whole)
        )
    return pl.pallas_call(
        kernel,
        grid=(n_grid,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),  # sequential: output revisiting
        ),
        interpret=interpret,
    )


def dense_mttkrp_pallas_call(
    n_grid: int,
    block_k: int,
    i_pad: int,
    j_pad: int,
    rank_pad: int,
    acc_dtype=jnp.float32,
    interpret: bool = False,
):
    """Build the dense MTTKRP pallas_call for static padded dims.

    Signature of the returned callable:
      (x (n_grid*block_k, i_pad, j_pad), c (j_pad, R), a (n_grid*block_k, R))
        -> m (i_pad, R) in ``acc_dtype``
    """
    kernel = functools.partial(_dense_mttkrp_kernel, acc_dtype=acc_dtype)
    return _call(
        kernel,
        n_grid,
        block_k,
        i_pad,
        j_pad,
        rank_pad,
        jax.ShapeDtypeStruct((i_pad, rank_pad), acc_dtype),
        pl.BlockSpec((i_pad, rank_pad), lambda g: (0, 0)),
        n_inputs=3,
        interpret=interpret,
    )


def dense_phi_pallas_call(
    n_grid: int,
    block_k: int,
    i_pad: int,
    j_pad: int,
    rank_pad: int,
    eps: float,
    acc_dtype=jnp.float32,
    interpret: bool = False,
):
    """Build the dense Phi pallas_call.

    Signature: (x, c, a, b (i_pad, R)) -> phi (i_pad, R) in ``acc_dtype``.
    """
    kernel = functools.partial(
        _dense_phi_kernel, eps=eps, acc_dtype=acc_dtype
    )
    return _call(
        kernel,
        n_grid,
        block_k,
        i_pad,
        j_pad,
        rank_pad,
        jax.ShapeDtypeStruct((i_pad, rank_pad), acc_dtype),
        pl.BlockSpec((i_pad, rank_pad), lambda g: (0, 0)),
        n_inputs=4,
        interpret=interpret,
    )


def dense_phi_mu_pallas_call(
    n_grid: int,
    block_k: int,
    i_pad: int,
    j_pad: int,
    rank_pad: int,
    eps: float,
    acc_dtype=jnp.float32,
    interpret: bool = False,
):
    """Build the fused dense Phi -> (B*Phi, KKT partial) pallas_call.

    Signature: (x, c, a, b) -> (mu (i_pad, R), kkt KKT_TILE), both in
    ``acc_dtype``; ``max(kkt)`` is the KKT violation over the window.
    """
    kernel = functools.partial(
        _dense_phi_mu_kernel, eps=eps, n_grid=n_grid, acc_dtype=acc_dtype
    )
    return _call(
        kernel,
        n_grid,
        block_k,
        i_pad,
        j_pad,
        rank_pad,
        (
            jax.ShapeDtypeStruct((i_pad, rank_pad), acc_dtype),
            jax.ShapeDtypeStruct(KKT_TILE, acc_dtype),
        ),
        [
            pl.BlockSpec((i_pad, rank_pad), lambda g: (0, 0)),
            pl.BlockSpec(KKT_TILE, lambda g: (0, 0)),
        ],
        n_inputs=4,
        interpret=interpret,
    )
