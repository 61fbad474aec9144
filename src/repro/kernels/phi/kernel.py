"""Pallas TPU kernels for the Phi^(n) blocked segmented reduction.

Schedule (see core/layout.py): grid step g processes ``block_nnz`` sorted
nonzeros that all fall in row block ``grid_rb[g]``.  The B window and the
Phi output window for that row block live in VMEM; consecutive grid steps
with the same row block *revisit* the same Phi VMEM block and accumulate —
the TPU analog of the paper's "atomics only at segment boundaries"
(CPU Alg. 4 cases 1/3).  All irregular work is expressed as one-hot
matmuls so both contractions hit the MXU:

    onehot  = (local_rows == iota)            (bn, br)
    B_rows  = onehot @ B_window                (bn, br) @ (br, R)   MXU
    s       = rowsum(B_rows * Pi_block)        VPU
    w       = x / max(s, eps)                  VPU
    Phi    += onehot^T @ (w * Pi_block)        (br, bn) @ (bn, R)   MXU

Two kernels share that schedule:

  * ``phi_pallas_call``    — plain Phi^(n) (used by the scooch step and
    standalone benchmarks).
  * ``phi_mu_pallas_call`` — the fused MU fast path: on the *last* visit
    to each row block the accumulated Phi window is transformed in place
    into the MU product ``B * Phi`` and a per-block KKT-violation partial
    ``max |min(B, 1 - Phi)|`` is emitted.  One VMEM-resident pass replaces
    the three separate HBM sweeps (Phi, KKT reduce, B*Phi) of the unfused
    inner loop.  Padding rows/lanes hold B = Phi = 0, so they contribute
    ``|min(0, 1)| = 0`` to the partial max and nothing to B*Phi.

Grid must iterate sequentially over nnz blocks ("arbitrary" dimension
semantics) for the revisit accumulation to be legal.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dtypes import contract_precision

__all__ = ["phi_pallas_call", "phi_mu_pallas_call", "KKT_TILE"]

# KKT partials are emitted one (sublane, lane) f32 tile per row block so the
# output block shape satisfies the TPU minimum tile; callers jnp.max it away.
KKT_TILE = (8, 128)


def _phi_kernel(
    # scalar prefetch
    grid_rb_ref,
    # inputs
    vals_ref,  # (bn, 1) f32
    lrow_ref,  # (bn, 1) i32  local row within the row block
    pi_ref,  # (bn, R) f32
    b_ref,  # (br, R) f32  B window for this row block
    # output
    phi_ref,  # (br, R) f32  Phi window (revisited across grid steps)
    *,
    block_rows: int,
    eps: float,
):
    g = pl.program_id(0)
    rb = grid_rb_ref[g]
    rb_prev = grid_rb_ref[jnp.maximum(g - 1, 0)]
    first_visit = jnp.logical_or(g == 0, rb != rb_prev)

    @pl.when(first_visit)
    def _init():
        phi_ref[...] = jnp.zeros_like(phi_ref)

    bn = vals_ref.shape[0]
    lrow = lrow_ref[...]  # (bn, 1)
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (bn, block_rows), 1)
    onehot = (lrow == row_iota).astype(pi_ref.dtype)  # (bn, br)
    prec = contract_precision(pi_ref.dtype)

    pi = pi_ref[...]
    b_rows = jnp.dot(onehot, b_ref[...], preferred_element_type=jnp.float32,
                     precision=prec)
    s = jnp.sum(b_rows * pi, axis=1, keepdims=True)  # (bn, 1)
    vals = vals_ref[...]
    w = jnp.where(vals > 0, vals / jnp.maximum(s, eps), 0.0)  # (bn, 1) f32
    contrib = (w * pi).astype(pi.dtype)  # (bn, R) element dtype
    phi_ref[...] += jnp.dot(onehot.T, contrib,
                            preferred_element_type=jnp.float32, precision=prec)


def _phi_mu_kernel(
    # scalar prefetch
    grid_rb_ref,
    # inputs
    vals_ref,  # (bn, 1) f32
    lrow_ref,  # (bn, 1) i32
    pi_ref,  # (bn, R) f32
    b_ref,  # (br, R) f32
    # outputs
    mu_ref,  # (br, R) f32: Phi accumulator, becomes B*Phi on last visit
    kkt_ref,  # KKT_TILE f32: per-row-block partial max |min(B, 1-Phi)|
    *,
    block_rows: int,
    eps: float,
    n_grid: int,
):
    g = pl.program_id(0)
    rb = grid_rb_ref[g]
    rb_prev = grid_rb_ref[jnp.maximum(g - 1, 0)]
    rb_next = grid_rb_ref[jnp.minimum(g + 1, n_grid - 1)]
    first_visit = jnp.logical_or(g == 0, rb != rb_prev)
    last_visit = jnp.logical_or(g == n_grid - 1, rb != rb_next)

    @pl.when(first_visit)
    def _init():
        mu_ref[...] = jnp.zeros_like(mu_ref)

    bn = vals_ref.shape[0]
    lrow = lrow_ref[...]  # (bn, 1)
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (bn, block_rows), 1)
    onehot = (lrow == row_iota).astype(pi_ref.dtype)  # (bn, br)
    prec = contract_precision(pi_ref.dtype)

    pi = pi_ref[...]
    b = b_ref[...]
    b_rows = jnp.dot(onehot, b, preferred_element_type=jnp.float32,
                     precision=prec)
    s = jnp.sum(b_rows * pi, axis=1, keepdims=True)  # (bn, 1)
    vals = vals_ref[...]
    w = jnp.where(vals > 0, vals / jnp.maximum(s, eps), 0.0)  # (bn, 1) f32
    contrib = (w * pi).astype(pi.dtype)  # (bn, R) element dtype
    mu_ref[...] += jnp.dot(onehot.T, contrib,
                           preferred_element_type=jnp.float32, precision=prec)

    # Fused epilogue: the accumulated Phi window never leaves VMEM — it is
    # consumed in place by the KKT partial reduce and the MU product.
    @pl.when(last_visit)
    def _epilogue():
        phi = mu_ref[...]
        viol = jnp.max(jnp.abs(jnp.minimum(b, 1.0 - phi)))
        kkt_ref[...] = jnp.full(kkt_ref.shape, viol, kkt_ref.dtype)
        mu_ref[...] = b * phi


def phi_pallas_call(
    n_grid: int,
    block_nnz: int,
    block_rows: int,
    n_rows_pad: int,
    rank_pad: int,
    eps: float,
    interpret: bool = False,
):
    """Build the pallas_call for a given static layout.

    Signature of the returned callable:
      (grid_rb (G,), vals (G*bn, 1), local_rows (G*bn, 1), pi (G*bn, R),
       b (n_rows_pad, R)) -> phi (n_rows_pad, R)
    """
    bn, br = block_nnz, block_rows

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_grid,),
        in_specs=[
            pl.BlockSpec((bn, 1), lambda g, rb: (g, 0)),  # vals
            pl.BlockSpec((bn, 1), lambda g, rb: (g, 0)),  # local rows
            pl.BlockSpec((bn, rank_pad), lambda g, rb: (g, 0)),  # pi
            pl.BlockSpec((br, rank_pad), lambda g, rb: (rb[g], 0)),  # B window
        ],
        out_specs=pl.BlockSpec((br, rank_pad), lambda g, rb: (rb[g], 0)),
    )
    kernel = functools.partial(_phi_kernel, block_rows=br, eps=eps)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_rows_pad, rank_pad), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),  # sequential: output revisiting
        ),
        interpret=interpret,
    )


def phi_mu_pallas_call(
    n_grid: int,
    block_nnz: int,
    block_rows: int,
    n_rows_pad: int,
    rank_pad: int,
    eps: float,
    interpret: bool = False,
):
    """Build the fused Phi -> (B*Phi, KKT partials) pallas_call.

    Signature of the returned callable:
      (grid_rb (G,), vals (G*bn, 1), local_rows (G*bn, 1), pi (G*bn, R),
       b (n_rows_pad, R))
        -> (mu (n_rows_pad, R), kkt (n_row_blocks*8, 128))

    ``mu = B * Phi`` and ``max(kkt)`` is the KKT violation over the padded
    window (padding contributes exactly 0; see module docstring).
    """
    bn, br = block_nnz, block_rows
    n_rb = n_rows_pad // br

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_grid,),
        in_specs=[
            pl.BlockSpec((bn, 1), lambda g, rb: (g, 0)),  # vals
            pl.BlockSpec((bn, 1), lambda g, rb: (g, 0)),  # local rows
            pl.BlockSpec((bn, rank_pad), lambda g, rb: (g, 0)),  # pi
            pl.BlockSpec((br, rank_pad), lambda g, rb: (rb[g], 0)),  # B window
        ],
        out_specs=[
            pl.BlockSpec((br, rank_pad), lambda g, rb: (rb[g], 0)),  # mu
            pl.BlockSpec(KKT_TILE, lambda g, rb: (rb[g], 0)),  # kkt partials
        ],
    )
    kernel = functools.partial(
        _phi_mu_kernel, block_rows=br, eps=eps, n_grid=n_grid
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((n_rows_pad, rank_pad), jnp.float32),
            jax.ShapeDtypeStruct((n_rb * KKT_TILE[0], KKT_TILE[1]), jnp.float32),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),  # sequential: output revisiting
        ),
        interpret=interpret,
    )
