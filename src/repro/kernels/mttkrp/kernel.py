"""Pallas TPU kernel for sparse MTTKRP (PASTA-style, paper Exp. 8).

Same blocked segmented schedule as the Phi kernel (sorted nonzeros,
capacity-padded blocks, output-window revisiting) without the model
division:  M[i, :] += x_j * KRrow_j.  Khatri-Rao rows are pre-gathered
(gather_mode='prefetch': XLA streams them; the 'vmem' resident-factor
variant is the data-reuse policy point studied in bench_policy).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dtypes import contract_precision

__all__ = ["mttkrp_pallas_call"]


def _mttkrp_kernel(
    grid_rb_ref,
    vals_ref,  # (bn, 1)
    lrow_ref,  # (bn, 1)
    kr_ref,  # (bn, R)
    out_ref,  # (br, R) revisited
    *,
    block_rows: int,
):
    g = pl.program_id(0)
    rb = grid_rb_ref[g]
    rb_prev = grid_rb_ref[jnp.maximum(g - 1, 0)]
    first_visit = jnp.logical_or(g == 0, rb != rb_prev)

    @pl.when(first_visit)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    bn = vals_ref.shape[0]
    lrow = lrow_ref[...]
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (bn, block_rows), 1)
    onehot = (lrow == row_iota).astype(kr_ref.dtype)
    contrib = vals_ref[...] * kr_ref[...]  # (bn, R)
    out_ref[...] += jnp.dot(onehot.T, contrib, preferred_element_type=jnp.float32,
                            precision=contract_precision(kr_ref.dtype))


def mttkrp_pallas_call(
    n_grid: int,
    block_nnz: int,
    block_rows: int,
    n_rows_pad: int,
    rank_pad: int,
    interpret: bool = False,
):
    bn, br = block_nnz, block_rows
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_grid,),
        in_specs=[
            pl.BlockSpec((bn, 1), lambda g, rb: (g, 0)),
            pl.BlockSpec((bn, 1), lambda g, rb: (g, 0)),
            pl.BlockSpec((bn, rank_pad), lambda g, rb: (g, 0)),
        ],
        out_specs=pl.BlockSpec((br, rank_pad), lambda g, rb: (rb[g], 0)),
    )
    kernel = functools.partial(_mttkrp_kernel, block_rows=br)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_rows_pad, rank_pad), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )
