"""Jitted wrappers for the Phi Pallas kernels, and the kernels' operands.

``phi_blocked`` runs the plain Phi^(n) reduction; ``phi_mu_blocked`` runs
the fused MU fast path (Phi accumulation + ``B*Phi`` + KKT partial max in
one VMEM-resident pass — see kernel.py).  Both read the nonzero stream as
:class:`PhiOperands`, the form the kernels take it in: ``(N, 1)`` values,
``(N, 1)`` int32 local rows, Pi padded to a multiple of 128 lanes and the
int32 row block of each grid step.  :func:`phi_operands` builds them from
layout-expanded ``vals_e``/``pi_e`` (``repro.core.phi.expand_to_layout``)
and the layout's index arrays.  They do not depend on B, so the solver
builds them once per mode update, outside its inner loop
(``cpapr._make_mode_update``), and every kernel call of that mode update
reads the same arrays; per call only B is padded to the kernel's window
and the result sliced back to R lanes.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.layout import BlockedLayout, round_up
from repro.kernels.dtypes import check_kernel_dtype

from .kernel import phi_mu_pallas_call, phi_pallas_call

__all__ = ["PhiOperands", "phi_blocked", "phi_blocked_arrays",
           "phi_mu_blocked", "phi_operands"]


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


class PhiOperands(NamedTuple):
    """The nonzero-stream operands of the Phi kernels, in kernel order."""

    grid_rb: jax.Array  # (n_grid,) int32 row block per grid step
    vals: jax.Array  # (N, 1) values, the caller's element dtype
    local_rows: jax.Array  # (N, 1) int32 row within the row block
    pi: jax.Array  # (N, round_up(R, 128)) Pi rows, zero past lane R


# The (N, 1) reshapes and the 128-lane pad of Pi are named cpapr.layout, as
# are the per-call pad of B and the slices back from the padded window; the
# kernel call is the caller's cpapr.phi.
def phi_operands(vals_e, pi_e, local_rows, grid_rb) -> PhiOperands:
    """The kernels' operands from a layout-expanded stream.

    ``vals_e`` (N,) and ``pi_e`` (N, R) come from ``expand_to_layout`` (or
    one shard's slice of ``expand_to_shards``); ``local_rows`` (N,) and
    ``grid_rb`` (n_grid,) are the layout's.  Every Phi kernel call goes
    through this function; a caller that runs the kernel more than once on
    one stream (the solver's inner loop) builds the operands once.
    """
    r = pi_e.shape[1]
    with jax.named_scope("cpapr.layout"):
        return PhiOperands(
            grid_rb=jnp.asarray(grid_rb, jnp.int32),
            vals=vals_e.reshape(-1, 1),
            local_rows=jnp.asarray(local_rows, jnp.int32).reshape(-1, 1),
            pi=jnp.pad(pi_e, ((0, 0), (0, round_up(r, 128) - r))),
        )


def _pad_b(b, n_rows_pad: int, r_pad: int):
    with jax.named_scope("cpapr.layout"):
        return jnp.pad(b, ((0, n_rows_pad - b.shape[0]),
                           (0, r_pad - b.shape[1])))


def _phi_padded(ops: PhiOperands, b, *, n_rows_pad: int, block_nnz: int,
                block_rows: int, eps: float, interpret: bool):
    """The plain Phi kernel: the (n_rows_pad, R) Phi window of ``b``."""
    dt = check_kernel_dtype("phi_blocked", ops.vals, ops.pi, b)
    r, r_pad = b.shape[1], ops.pi.shape[1]
    call = phi_pallas_call(
        n_grid=ops.grid_rb.shape[0],
        block_nnz=block_nnz,
        block_rows=block_rows,
        n_rows_pad=n_rows_pad,
        rank_pad=r_pad,
        eps=float(eps),
        interpret=bool(interpret),
    )
    phi_pad = call(*ops, _pad_b(b, n_rows_pad, r_pad))
    with jax.named_scope("cpapr.layout"):
        return phi_pad[:, :r].astype(dt)


def phi_blocked_arrays(
    grid_rb: jax.Array,
    vals_e: jax.Array,
    local_rows: jax.Array,
    pi_e: jax.Array,
    b_win: jax.Array,
    *,
    block_nnz: int,
    block_rows: int,
    eps: float,
    interpret: bool | None = None,
) -> jax.Array:
    """Pallas Phi on raw (possibly traced) layout arrays.

    Unlike :func:`phi_blocked`, no host-static :class:`BlockedLayout` is
    needed — grid/row metadata arrive as arrays, so this entry point works
    on per-shard slices inside ``shard_map`` where each device carries its
    own layout data.  ``b_win`` is the (n_rows_pad, R) B window; returns
    the padded (n_rows_pad, R) Phi window in the caller's element dtype
    (f32 or bf16; f64 raises — see ``repro.kernels.dtypes``).
    Accumulation is always f32.  The operands are built on every call.
    """
    if interpret is None:
        interpret = _default_interpret()
    return _phi_padded(
        phi_operands(vals_e, pi_e, local_rows, grid_rb),
        b_win,
        n_rows_pad=b_win.shape[0],
        block_nnz=block_nnz,
        block_rows=block_rows,
        eps=eps,
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("layout", "eps", "interpret"))
def _run(layout: BlockedLayout, ops: PhiOperands, b, eps: float,
         interpret: bool):
    return _phi_padded(
        ops,
        b,
        n_rows_pad=layout.n_rows_pad,
        block_nnz=layout.block_nnz,
        block_rows=layout.block_rows,
        eps=eps,
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("layout", "eps", "interpret"))
def _run_mu(layout: BlockedLayout, ops: PhiOperands, b, eps: float,
            interpret: bool):
    dt = check_kernel_dtype("phi_mu_blocked", ops.vals, ops.pi, b)
    r, r_pad = b.shape[1], ops.pi.shape[1]
    call = phi_mu_pallas_call(
        n_grid=layout.n_grid,
        block_nnz=layout.block_nnz,
        block_rows=layout.block_rows,
        n_rows_pad=layout.n_rows_pad,
        rank_pad=r_pad,
        eps=eps,
        interpret=interpret,
    )
    mu_pad, kkt = call(*ops, _pad_b(b, layout.n_rows_pad, r_pad))
    with jax.named_scope("cpapr.layout"):
        mu = mu_pad[:, :r].astype(dt)
    with jax.named_scope("cpapr.epilogue"):  # the KKT max outside the kernel
        return mu, jnp.max(kkt)


def phi_blocked(
    layout: BlockedLayout,
    ops: PhiOperands,
    b: jax.Array,
    eps: float = 1e-10,
    interpret: bool | None = None,
) -> jax.Array:
    """Phi^(n) via the Pallas kernel on a prebuilt blocked layout.

    ``ops`` comes from :func:`phi_operands` on the layout's expansion
    (``phi.expand_to_layout``) and index arrays; ``b`` is (n_rows, R).
    Returns the padded (n_rows_pad, R) result; callers slice to n_rows.
    """
    if interpret is None:
        interpret = _default_interpret()
    return _run(layout, ops, b, float(eps), bool(interpret))


def phi_mu_blocked(
    layout: BlockedLayout,
    ops: PhiOperands,
    b: jax.Array,
    eps: float = 1e-10,
    interpret: bool | None = None,
) -> tuple:
    """Fused MU fast path via the Pallas kernel.

    ``ops`` as for :func:`phi_blocked`.  Returns ``(mu, viol)`` where
    ``mu`` is the padded (n_rows_pad, R) array ``B * Phi^(n)`` (callers
    slice to n_rows) and ``viol`` is the scalar KKT violation
    ``max |min(B, 1 - Phi)|`` — the padded region of B is zero so it
    contributes exactly 0 to the max.
    """
    if interpret is None:
        interpret = _default_interpret()
    return _run_mu(layout, ops, b, float(eps), bool(interpret))
