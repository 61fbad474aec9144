"""Jitted wrappers for the Phi Pallas kernels: padding + layout plumbing.

``phi_blocked`` runs the plain Phi^(n) reduction; ``phi_mu_blocked`` runs
the fused MU fast path (Phi accumulation + ``B*Phi`` + KKT partial max in
one VMEM-resident pass — see kernel.py).  Both take layout-expanded inputs
(``repro.core.phi.expand_to_layout``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.layout import BlockedLayout, round_up
from repro.kernels.dtypes import check_kernel_dtype

from .kernel import phi_mu_pallas_call, phi_pallas_call

__all__ = ["phi_blocked", "phi_blocked_arrays", "phi_mu_blocked"]


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


# The lane pads, the (N, 1) reshapes and the slices back from the padded
# window are named cpapr.layout; the kernel call is the caller's cpapr.phi.
def _pad_inputs(layout: BlockedLayout, vals_e, pi_e, b):
    dt = check_kernel_dtype("phi_mu_blocked", vals_e, pi_e, b)
    r = pi_e.shape[1]
    r_pad = round_up(r, 128)
    n_rows_pad = layout.n_rows_pad
    with jax.named_scope("cpapr.layout"):
        vals2 = vals_e.reshape(-1, 1)
        lrow2 = jnp.asarray(layout.local_rows, jnp.int32).reshape(-1, 1)
        pi_p = jnp.pad(pi_e, ((0, 0), (0, r_pad - r)))
        b_p = jnp.pad(b, ((0, n_rows_pad - b.shape[0]), (0, r_pad - r)))
        grid_rb = jnp.asarray(layout.grid_rb, jnp.int32)
    return vals2, lrow2, pi_p, b_p, grid_rb, r, r_pad, dt


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
    Accumulation is always f32.
    """
    dt = check_kernel_dtype("phi_blocked", vals_e, pi_e, b_win)
    if interpret is None:
        interpret = _default_interpret()
    r = pi_e.shape[1]
    r_pad = round_up(r, 128)
    with jax.named_scope("cpapr.layout"):
        vals2 = vals_e.reshape(-1, 1)
        lrow2 = local_rows.astype(jnp.int32).reshape(-1, 1)
        pi_p = jnp.pad(pi_e, ((0, 0), (0, r_pad - r)))
        b_p = jnp.pad(b_win, ((0, 0), (0, r_pad - r)))
    call = phi_pallas_call(
        n_grid=grid_rb.shape[0],
        block_nnz=block_nnz,
        block_rows=block_rows,
        n_rows_pad=b_win.shape[0],
        rank_pad=r_pad,
        eps=float(eps),
        interpret=bool(interpret),
    )
    phi_pad = call(grid_rb.astype(jnp.int32), vals2, lrow2, pi_p, b_p)
    with jax.named_scope("cpapr.layout"):
        return phi_pad[:, :r].astype(dt)


@functools.partial(jax.jit, static_argnames=("layout", "eps", "interpret"))
def _run(layout: BlockedLayout, vals_e, pi_e, b, eps: float, interpret: bool):
    with jax.named_scope("cpapr.layout"):
        b_pad = jnp.pad(b, ((0, layout.n_rows_pad - b.shape[0]), (0, 0)))
    return phi_blocked_arrays(
        jnp.asarray(layout.grid_rb, jnp.int32),
        vals_e,
        jnp.asarray(layout.local_rows, jnp.int32),
        pi_e,
        b_pad,
        block_nnz=layout.block_nnz,
        block_rows=layout.block_rows,
        eps=eps,
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("layout", "eps", "interpret"))
def _run_mu(layout: BlockedLayout, vals_e, pi_e, b, eps: float, interpret: bool):
    vals2, lrow2, pi_p, b_p, grid_rb, r, r_pad, dt = _pad_inputs(
        layout, vals_e, pi_e, b
    )

    call = phi_mu_pallas_call(
        n_grid=layout.n_grid,
        block_nnz=layout.block_nnz,
        block_rows=layout.block_rows,
        n_rows_pad=layout.n_rows_pad,
        rank_pad=r_pad,
        eps=eps,
        interpret=interpret,
    )
    mu_pad, kkt = call(grid_rb, vals2, lrow2, pi_p, b_p)
    with jax.named_scope("cpapr.layout"):
        mu = mu_pad[:, :r].astype(dt)
    with jax.named_scope("cpapr.epilogue"):  # the KKT max outside the kernel
        return mu, jnp.max(kkt)


def phi_blocked(
    layout: BlockedLayout,
    vals_e: jax.Array,
    pi_e: jax.Array,
    b: jax.Array,
    eps: float = 1e-10,
    interpret: bool | None = None,
) -> jax.Array:
    """Phi^(n) via the Pallas kernel on a prebuilt blocked layout.

    ``vals_e``/``pi_e`` are layout-expanded (see ``phi.expand_to_layout``).
    Returns the padded (n_rows_pad, R) result; callers slice to n_rows.
    """
    if interpret is None:
        interpret = _default_interpret()
    return _run(layout, vals_e, pi_e, b, float(eps), bool(interpret))


def phi_mu_blocked(
    layout: BlockedLayout,
    vals_e: jax.Array,
    pi_e: jax.Array,
    b: jax.Array,
    eps: float = 1e-10,
    interpret: bool | None = None,
) -> tuple:
    """Fused MU fast path via the Pallas kernel.

    Returns ``(mu, viol)`` where ``mu`` is the padded (n_rows_pad, R)
    array ``B * Phi^(n)`` (callers slice to n_rows) and ``viol`` is the
    scalar KKT violation ``max |min(B, 1 - Phi)|`` — the padded region of
    B is zero so it contributes exactly 0 to the max.
    """
    if interpret is None:
        interpret = _default_interpret()
    return _run_mu(layout, vals_e, pi_e, b, float(eps), bool(interpret))
