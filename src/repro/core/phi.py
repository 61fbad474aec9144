"""Phi^(n) kernel: the CP-APR MU hot spot (81% of runtime, paper Fig. 2).

    Phi^(n) = (X_(n) (/) max(B Pi, eps)) Pi^T        (Alg. 2)

computed one nonzero at a time (never materializing X_(n) or Pi):

    s_j   = <B[i_j, :], pi[j, :]>          # model value at nonzero j
    w_j   = x_j / max(s_j, eps)
    Phi[i_j, :] += w_j * pi[j, :]          # reduction by row -> conflicts

Strategies (the paper's CPU/GPU composite implementation, mapped to TPU):

  * ``scatter``  — XLA scatter-add on unsorted nonzeros.  Functional analog
    of the paper's GPU Alg. 3 (atomic per nonzero).
  * ``segment``  — sorted nonzeros + ``jax.ops.segment_sum``.  Analog of the
    paper's CPU Alg. 4 (sort + atomic mitigation).
  * ``blocked``  — the TPU schedule: blocked segmented reduction with one-hot
    MXU matmuls over a :class:`BlockedLayout` (pure-jnp emulation of the
    Pallas kernel; bitwise-same schedule).
  * ``pallas``   — the actual Pallas TPU kernel (repro.kernels.phi).
  * ``dense``    — the matrix-free tier for near-dense modes: the mode's
    densified (K, I, J) tensor (``repro.core.dense``) is contracted
    against factor tiles in VMEM (repro.kernels.dense), skipping the
    (nnz, R) Pi materialization and the sorted stream entirely.  Exact,
    not approximate: zero entries carry zero Phi weight.

PPA perturbations (paper Sec. 3.3) are exposed uniformly via ``perturb``:

  * ``no_conflict``   — drop the keyed reduction (uniform-segment sum):
    upper bound with zero write contention (paper's "no atomics").
  * ``perfect_reuse`` — clamp every gather index to row 0: upper bound with
    perfect cache/VMEM reuse (paper's "single row access").

Perturbed variants are *wrong on purpose* — benchmarks only.

The CP-APR inner loop's hot sequence — Phi, the KKT check, and the MU
update ``B <- B*Phi`` — is exposed as one fused entry point,
:func:`phi_mu_step`, shared by all strategies.  For ``pallas`` it maps to
the fused-epilogue kernel (one VMEM-resident pass instead of three HBM
sweeps); the jnp strategies mirror the same math in a single traced
expression so XLA fuses the elementwise epilogue into the reduction.
``vals_e``/``pi_e`` accept pre-expanded layout arrays so callers (the
solver) can hoist the Pi gather out of the inner loop; for ``pallas``,
``operands`` hoists the kernel's own operands (``repro.kernels.phi.ops``)
as well.
"""
from __future__ import annotations

import warnings
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .layout import (
    BlockedLayout,
    GridLayout,
    ShardedBlockedLayout,
    build_blocked_layout,
    build_grid_layout,
    choose_grid_shape,
    mode_run_stats,
    round_up,
    shard_blocked_layout,
)
from .pi import pi_rows
from .policy import heuristic_policy
from .resilience import ShardAssignmentError
from .sparse_tensor import ModeView

__all__ = [
    "phi_flops_words",
    "phi_from_rows",
    "phi_mode",
    "phi_mu_step",
    "krao_reduce_rows",
    "expand_to_layout",
    "expand_to_grid",
    "expand_to_shards",
    "expand_vals_to_shards",
    "PHI_STRATEGIES",
    "ALL_PHI_STRATEGIES",
]

PHI_STRATEGIES = ("scatter", "segment", "blocked", "pallas", "dense")
# "sharded" = blocked schedule partitioned over a mesh data axis with a
# psum Phi combine; emulated on one device when no mesh is given.
# "grid" = the same schedule over an (A x B) device grid: A row-block
# shards x B stream cells, column-axis all-gather + reduce-scatter
# combine (wire O(I_n * R / A) per device); also emulated without a mesh.
ALL_PHI_STRATEGIES = PHI_STRATEGIES + ("sharded", "grid")


# ---------------------------------------------------------------------------
# Roofline operation counts (paper Eqs. 3-8)
# ---------------------------------------------------------------------------


def phi_flops_words(nnz: int, rank: int, variant: str = "gpu", v: int = 32) -> tuple:
    """(W FLOPs, Q words) for Phi^(n), per paper Eqs. 3-4 / 6-7.

    ``variant='gpu'``: W = nnz(4R+2), Q = nnz(5R+2)   -> I = 0.125 @ R->inf
    ``variant='cpu'``: W = nnz(4R+R/V+3), Q = nnz(6R+2R/V+3) -> I ~ 0.27
    """
    if variant == "gpu":
        return nnz * (4 * rank + 2), nnz * (5 * rank + 2)
    if variant == "cpu":
        w = nnz * (4 * rank + rank / v + 3)
        q = nnz * (6 * rank + 2 * rank / v + 3)
        return w, q
    raise ValueError(variant)


# ---------------------------------------------------------------------------
# Core strategies, operating on gathered rows
# ---------------------------------------------------------------------------


def _weights(vals, s, eps):
    return vals / jnp.maximum(s, eps)


@partial(jax.jit, static_argnames=("n_rows", "perturb"))
def _phi_scatter(rows, vals, pi, b, n_rows: int, eps, perturb: str | None = None):
    if perturb == "perfect_reuse":
        rows = rows * 0
    s = jnp.sum(b[rows] * pi, axis=1)
    w = _weights(vals, s, eps)
    contrib = w[:, None] * pi
    if perturb == "no_conflict":
        return _uniform_segment_sum(contrib, n_rows)
    return jnp.zeros((n_rows, pi.shape[1]), pi.dtype).at[rows].add(contrib)


@partial(jax.jit, static_argnames=("n_rows", "perturb"))
def _phi_segment(rows, vals, pi, b, n_rows: int, eps, perturb: str | None = None):
    if perturb == "perfect_reuse":
        rows = rows * 0
    s = jnp.sum(b[rows] * pi, axis=1)
    w = _weights(vals, s, eps)
    contrib = w[:, None] * pi
    if perturb == "no_conflict":
        return _uniform_segment_sum(contrib, n_rows)
    return jax.ops.segment_sum(
        contrib, rows, num_segments=n_rows, indices_are_sorted=True
    )


@partial(jax.jit, static_argnames=("n_rows", "strategy", "sorted_rows"))
def _krao_unblocked(rows, vals, kr, n_rows: int, strategy: str,
                    sorted_rows: bool):
    """Plain Khatri-Rao reduction ``out[i] += x_j * kr_j`` (unblocked).

    ``sorted_rows`` is a promise, not a strategy: segment_sum only gets
    ``indices_are_sorted=True`` when the caller really has the sorted
    stream (a ModeView), so unsorted COO callers stay correct.
    """
    contrib = vals[:, None] * kr
    if strategy == "scatter":
        return jnp.zeros((n_rows, kr.shape[1]), kr.dtype).at[rows].add(contrib)
    return jax.ops.segment_sum(
        contrib, rows, num_segments=n_rows, indices_are_sorted=sorted_rows
    )


def _uniform_segment_sum(contrib: jax.Array, n_rows: int) -> jax.Array:
    """PPA 'no_conflict': keep the FLOPs/stream, drop the keyed reduce.

    Pads nnz to a multiple of n_rows and reduces fixed-size groups — the
    same add count with zero possibility of write conflict.
    """
    nnz, r = contrib.shape
    group = max(1, -(-nnz // n_rows))  # ceil
    pad = group * n_rows - nnz
    c = jnp.pad(contrib, ((0, pad), (0, 0)))
    return c.reshape(n_rows, group, r).sum(axis=1)


def _phi_blocked_core(
    vals,
    pi,
    local_rows,
    grid_rb,
    b_win,
    *,
    block_nnz: int,
    block_rows: int,
    n_row_blocks: int,
    eps,
    perturb=None,
):
    """Traced heart of the blocked schedule: arrays in, padded window out.

    All layout data arrives as (traced) arrays so the same expression runs
    on a host-static :class:`BlockedLayout` *and* on per-shard slices
    inside ``shard_map`` (where each device sees its own layout arrays).

      vals:       (n_grid*block_nnz,)   layout-expanded values
      pi:         (n_grid*block_nnz, R) layout-expanded Pi/Khatri-Rao rows
      local_rows: (n_grid*block_nnz,)   row within the step's row block
      grid_rb:    (n_grid,)             row block per grid step
      b_win:      (n_row_blocks*block_rows, R) B window (padded), or None
                  for the *plain* weighting ``out[i] += x_j * pi_j`` — the
                  MTTKRP reduction, which shares this schedule verbatim
                  (no model divide, no B gather).

    Returns the padded (n_row_blocks*block_rows, R) output window.
    """
    bn, br = block_nnz, block_rows
    g = vals.shape[0] // bn
    r = pi.shape[1]
    if perturb == "perfect_reuse":
        local_rows = local_rows * 0
        grid_rb = grid_rb * 0

    onehot = jax.nn.one_hot(
        local_rows.reshape(g, bn), br, dtype=pi.dtype
    )  # (G, bn, br)
    pi_b = pi.reshape(g, bn, r)
    vals_b = vals.reshape(g, bn)

    if b_win is None:
        w = vals_b  # plain weights: padding slots carry vals == 0
    else:
        # Gather B windows per grid step: (G, block_rows, R)
        b_blocks = b_win.reshape(n_row_blocks, br, r)[grid_rb]
        # s = rows of (onehot @ B_window) dotted with pi — both on MXU, at
        # the element dtype's full precision (the TPU's default rounds f32
        # operands to bf16).
        b_rows = jnp.einsum("gvb,gbr->gvr", onehot, b_blocks,
                            precision=jax.lax.Precision.HIGHEST)
        s = jnp.sum(b_rows * pi_b, axis=-1)
        w = jnp.where(vals_b > 0, vals_b / jnp.maximum(s, eps), 0.0)
    contrib = w[..., None] * pi_b  # (G, bn, R)
    if perturb == "no_conflict":
        partial_blocks = contrib[:, :br, :]  # uniform write, no keyed reduce
    else:
        partial_blocks = jnp.einsum("gvb,gvr->gbr", onehot, contrib,
                                    precision=jax.lax.Precision.HIGHEST)
    # Cross-grid-step combine (the "output block revisit" in the kernel):
    phi_blocks = jax.ops.segment_sum(
        partial_blocks, grid_rb, num_segments=n_row_blocks, indices_are_sorted=True
    )
    return phi_blocks.reshape(n_row_blocks * br, r)


def _phi_blocked_padded(layout: BlockedLayout, vals, pi, b, eps, perturb=None):
    """Pure-jnp emulation of the Pallas schedule (same blocking, same math).

    vals/pi here are already expanded to the padded layout order:
      vals: (n_grid*block_nnz,)   pi: (n_grid*block_nnz, R)

    Returns the *padded* (n_rows_pad, R) result, mirroring the kernel's
    output window; :func:`_phi_blocked` slices to n_rows.
    """
    with jax.named_scope("cpapr.layout"):
        b_pad = jnp.pad(b, ((0, layout.n_rows_pad - b.shape[0]), (0, 0)))
    return _phi_blocked_core(
        vals,
        pi,
        jnp.asarray(layout.local_rows),
        jnp.asarray(layout.grid_rb),
        b_pad,
        block_nnz=layout.block_nnz,
        block_rows=layout.block_rows,
        n_row_blocks=layout.n_row_blocks,
        eps=eps,
        perturb=perturb,
    )


def _phi_blocked(layout: BlockedLayout, vals, pi, b, eps, perturb=None):
    phi_pad = _phi_blocked_padded(layout, vals, pi, b, eps, perturb)
    with jax.named_scope("cpapr.layout"):
        return phi_pad[: layout.n_rows]


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------


def _resolve_layout(rows, n_rows, layout, vals, pi, vals_e, pi_e):
    """Default layout + expansion for the blocked/pallas strategies.

    When no layout is given, the block sizes come from the
    distribution-aware heuristic (segment-run stats of ``rows``) instead
    of a fixed 256x256 — a hub-dominated and a uniform mode get different
    default blockings, mirroring the autotuner's v2 keying.  Pre-expanded
    ``vals_e``/``pi_e`` (from a hoisted :func:`expand_to_layout`) are
    passed through untouched so the solver's inner loop never re-gathers.

    The heuristic sees the *real* backend (``jax.default_backend()``), not
    a hardcoded "tpu" — CPU runs get the CPU branch's cache-model block
    sizes.  Strategy choice is already fixed by the caller here; only the
    blocking is taken from the policy.
    """
    if layout is None:
        rows_np = np.asarray(rows)
        stats = mode_run_stats(rows_np, n_rows)
        pol = heuristic_policy(
            int(rows_np.shape[0]), n_rows, int(pi.shape[1]),
            platform=jax.default_backend(), stats=stats,
        )
        layout = build_blocked_layout(
            rows_np, n_rows, block_nnz=pol.block_nnz, block_rows=pol.block_rows
        )
        vals_e = pi_e = None  # any pre-expansion matched a different layout
    if vals_e is None or pi_e is None:
        vals_e, pi_e = expand_to_layout(layout, vals, pi)
    return layout, vals_e, pi_e


def _resolve_pallas(rows, n_rows, layout, vals, pi, vals_e, pi_e, operands):
    """Layout + the Pallas kernels' operands (``phi_ops.PhiOperands``).

    Prepared ``operands`` (the solver builds them once per mode update)
    pass through untouched; otherwise they are built here, once per call,
    from :func:`_resolve_layout`'s expansion.
    """
    from repro.kernels.phi import ops as phi_ops

    if operands is None or layout is None:
        layout, vals_e, pi_e = _resolve_layout(
            rows, n_rows, layout, vals, pi, vals_e, pi_e
        )
        operands = phi_ops.phi_operands(vals_e, pi_e, layout.local_rows,
                                        layout.grid_rb)
    return layout, operands


def _dense_operands(dense, factors, b=None):
    """Kernel operands ``(x, c, a)`` for the dense tier.

    ``dense`` is a :class:`repro.core.dense.DenseModeData`; ``factors``
    the full factor tuple.  The element tier follows ``b`` when given
    (the MU path), else the ``c`` factor — ``x`` is stored f32 and cast
    here, so a bf16 factor set drives the bf16-compute/f32-accumulate
    kernel variant without a second densified copy.
    """
    if dense is None:
        raise ValueError(
            "strategy='dense' needs dense= (a DenseModeData; build one "
            "with repro.core.dense.build_dense_mode)"
        )
    if factors is None:
        raise ValueError("strategy='dense' needs the full factors tuple")
    from .dense import dense_kr_factors  # deferred: keeps import DAG flat

    c, a = dense_kr_factors(dense, factors)
    dt = b.dtype if b is not None else c.dtype
    return dense.x.astype(dt), c.astype(dt), a.astype(dt)


def _default_shard_count(mesh) -> int:
    if mesh is not None:
        from .distributed import mesh_device_count  # deferred: avoids cycle

        return mesh_device_count(mesh)
    return int(jax.device_count())


def _sharded_block_rows(n_rows: int, n_shards: int) -> int:
    """Default block_rows sized so >= ~4 row blocks land on every shard."""
    target = max(8, n_rows // max(1, 4 * n_shards))
    return int(2 ** np.clip(np.floor(np.log2(target)), 3, 8))


def _resolve_sharded(rows, n_rows, layout, mesh, vals, pi, vals_e, pi_e):
    """Sharded layout + expansion, with the single-device fallback.

    Returns ``(layout, vals_e, pi_e, mesh)``.  Normally ``layout`` is the
    :class:`ShardedBlockedLayout`; when the shard count cannot be honoured
    (fewer row blocks than devices) a warning fires and the *base*
    :class:`BlockedLayout` comes back instead (with ``None`` expansions) —
    callers detect that and run the unsharded path on it.  Mesh/layout
    shard-count agreement is validated downstream by
    ``repro.core.distributed``.
    """
    if layout is not None and not isinstance(layout, ShardedBlockedLayout):
        raise TypeError(
            "strategy='sharded' needs a ShardedBlockedLayout "
            f"(got {type(layout).__name__}); use shard_blocked_layout()"
        )
    if layout is None:
        n_shards = _default_shard_count(mesh)
        base = build_blocked_layout(
            np.asarray(rows),
            n_rows,
            block_nnz=256,
            block_rows=_sharded_block_rows(n_rows, n_shards),
        )
        if n_shards > base.n_row_blocks:
            warnings.warn(
                f"sharded Phi: {n_shards} shards requested but layout has "
                f"only {base.n_row_blocks} row blocks; falling back to the "
                "single-device blocked path",
                stacklevel=3,
            )
            return base, None, None, None
        layout = shard_blocked_layout(base, n_shards)
        vals_e = pi_e = None  # any pre-expansion matched a different layout
    if vals_e is None or pi_e is None:
        vals_e, pi_e = expand_to_shards(layout, vals, pi)
    return layout, vals_e, pi_e, mesh


def _check_combine(strategy: str, combine: str) -> None:
    """Validate the combine flavour; only the multi-device strategies
    combine (the grid is always owner-scattered, so it accepts
    ``reduce_scatter`` as a no-op alias of its only combine)."""
    if combine == "psum":
        return
    from .distributed import PHI_COMBINES  # deferred: avoids import cycle

    if combine not in PHI_COMBINES:
        raise ValueError(
            f"unknown combine {combine!r}; expected one of {PHI_COMBINES}"
        )
    if strategy not in ("sharded", "grid"):
        raise ValueError(
            f"combine={combine!r} only applies to strategy='sharded' "
            f"(got strategy={strategy!r})"
        )


def _resolve_grid(rows, n_rows, layout, mesh, vals, pi, vals_e, pi_e,
                  rank: int):
    """Grid layout + expansion, with the single-device fallback.

    Returns ``(layout, vals_e, pi_e, mesh)``.  Normally ``layout`` is the
    :class:`GridLayout`; when the grid cannot be honoured (fewer row
    blocks than the row axis, or fewer grid steps than the column axis)
    a warning fires and the *base* :class:`BlockedLayout` comes back
    instead (with ``None`` expansions) — callers detect that and run
    the unsharded path on it, mirroring :func:`_resolve_sharded`.
    """
    if layout is not None and not isinstance(layout, GridLayout):
        raise TypeError(
            "strategy='grid' needs a GridLayout "
            f"(got {type(layout).__name__}); use build_grid_layout()"
        )
    if layout is None:
        if mesh is not None:
            shape = (int(mesh.shape["row"]), int(mesh.shape["col"]))
        else:
            n_shards = _default_shard_count(None)
            shape = choose_grid_shape(
                n_rows, _sharded_block_rows(n_rows, n_shards), rank,
                n_shards,
            )
        base = build_blocked_layout(
            np.asarray(rows),
            n_rows,
            block_nnz=256,
            block_rows=_sharded_block_rows(n_rows, shape[0]),
        )
        try:
            layout = build_grid_layout(base, shape)
        except ValueError as e:
            warnings.warn(
                f"grid Phi: {e}; falling back to the single-device "
                "blocked path",
                stacklevel=3,
            )
            return base, None, None, None
        vals_e = pi_e = None  # any pre-expansion matched a different layout
    if vals_e is None or pi_e is None:
        vals_e, pi_e = expand_to_grid(layout, vals, pi)
    return layout, vals_e, pi_e, mesh


def _check_grid_args(pi_gather, perturb):
    if perturb is not None:
        raise ValueError("perturb is not supported for strategy='grid'")
    if pi_gather is not None:
        raise ValueError(
            "pi_gather is not supported for strategy='grid'; use "
            "strategy='sharded' for the shard-local Pi path"
        )


def _require_pig_layout(layout, pi_gather, factors) -> ShardedBlockedLayout:
    """Validate the shard-local-Pi argument triple (layout, pig, factors)."""
    if not isinstance(layout, ShardedBlockedLayout):
        raise TypeError(
            "pi_gather needs an explicit ShardedBlockedLayout (the one the "
            f"gather maps were built from); got {type(layout).__name__}"
        )
    if factors is None:
        raise ValueError("pi_gather needs the full factors tuple")
    if pi_gather.n_shards != layout.n_shards:
        raise ValueError(
            f"pi_gather has {pi_gather.n_shards} shards but the layout has "
            f"{layout.n_shards}"
        )
    if pi_gather.rb_start != tuple(int(x) for x in layout.rb_start):
        raise ShardAssignmentError(
            "pi_gather was built from a different shard assignment "
            f"(rb_start {pi_gather.rb_start} vs "
            f"{tuple(int(x) for x in layout.rb_start)}); rebuild it with "
            "build_shard_pi_gather after rebalancing"
        )
    return layout


def phi_from_rows(
    rows: jax.Array,
    vals: jax.Array,
    pi: jax.Array,
    b: jax.Array,
    n_rows: int,
    eps: float = 1e-10,
    strategy: str = "segment",
    layout: "BlockedLayout | ShardedBlockedLayout | None" = None,
    perturb: str | None = None,
    vals_e: jax.Array | None = None,
    pi_e: jax.Array | None = None,
    mesh=None,
    local_strategy: str = "blocked",
    pi_gather=None,
    factors=None,
    combine: str = "psum",
    dense=None,
    operands=None,
) -> jax.Array:
    """Phi^(n) from pre-gathered Pi rows.  ``rows`` sorted unless 'scatter'.

    For ``dense``, ``dense`` (a :class:`repro.core.dense.DenseModeData`)
    plus the full ``factors`` tuple replace the sorted stream entirely —
    ``rows``/``vals``/``pi`` may be ``None``.

    For ``blocked``/``pallas``, optional ``vals_e``/``pi_e`` are the
    layout-expanded arrays (see :func:`expand_to_layout`); pass them to
    skip per-call re-expansion.  For ``pallas``, ``operands`` (a
    ``repro.kernels.phi.ops.PhiOperands`` on ``layout``) go to the kernel
    as they are, and ``vals_e``/``pi_e`` are not read; without them the
    operands are built per call.  For ``sharded``, ``layout`` is a
    :class:`ShardedBlockedLayout`, ``vals_e``/``pi_e`` come from
    :func:`expand_to_shards`, and ``mesh`` (optional) places the shards on
    real devices with a psum combine — without a mesh the same schedule is
    emulated on one device.  With ``pi_gather`` (a
    :class:`repro.core.layout.ShardedPiGather`) plus the full ``factors``
    tuple, ``pi``/``pi_e`` may be ``None``: each shard computes its own Pi
    rows from the factor rows it touches (the shard-local Pi gather), so
    no O(nnz, R) Pi array is ever materialized.  ``combine`` picks the
    sharded combine flavour (``"psum"`` all-reduce or
    ``"reduce_scatter"`` owner-sliced epilogue — bitwise-identical; see
    ``repro.core.distributed.PHI_COMBINES``).
    """
    eps = float(eps)
    _check_combine(strategy, combine)
    if strategy == "scatter":
        return _phi_scatter(rows, vals, pi, b, n_rows, eps, perturb)
    if strategy == "segment":
        return _phi_segment(rows, vals, pi, b, n_rows, eps, perturb)
    if strategy == "blocked":
        layout, vals_e, pi_e = _resolve_layout(
            rows, n_rows, layout, vals, pi, vals_e, pi_e
        )
        return _phi_blocked(layout, vals_e, pi_e, b, eps, perturb)
    if strategy == "pallas":
        from repro.kernels.phi import ops as phi_ops

        layout, operands = _resolve_pallas(
            rows, n_rows, layout, vals, pi, vals_e, pi_e, operands
        )
        phi_pad = phi_ops.phi_blocked(layout, operands, b, float(eps))
        with jax.named_scope("cpapr.layout"):
            return phi_pad[:n_rows]
    if strategy == "dense":
        if perturb is not None:
            raise ValueError("perturb is not supported for strategy='dense'")
        from repro.kernels.dense import ops as dense_ops

        x, c, a = _dense_operands(dense, factors, b)
        return dense_ops.phi_dense(x, c, a, b, eps=eps)
    if strategy == "sharded":
        if perturb is not None:
            raise ValueError("perturb is not supported for strategy='sharded'")
        from .distributed import phi_sharded  # deferred: avoids import cycle

        if pi_gather is not None:
            slayout = _require_pig_layout(layout, pi_gather, factors)
            if vals_e is None:
                vals_e = expand_vals_to_shards(slayout, vals)
            return phi_sharded(slayout, vals_e, None, b, eps, mesh=mesh,
                               local_strategy=local_strategy,
                               pi_gather=pi_gather, factors=factors,
                               combine=combine)
        slayout, vals_e, pi_e, mesh = _resolve_sharded(
            rows, n_rows, layout, mesh, vals, pi, vals_e, pi_e
        )
        if not isinstance(slayout, ShardedBlockedLayout):
            # fewer row blocks than shards: warned fallback on the base
            # layout, keeping the requested local compute flavour
            return phi_from_rows(
                rows, vals, pi, b, n_rows, eps=eps,
                strategy=local_strategy, layout=slayout,
            )
        return phi_sharded(slayout, vals_e, pi_e, b, eps, mesh=mesh,
                           local_strategy=local_strategy, combine=combine)
    if strategy == "grid":
        _check_grid_args(pi_gather, perturb)
        from .distributed import phi_grid  # deferred: avoids import cycle

        glayout, vals_e, pi_e, mesh = _resolve_grid(
            rows, n_rows, layout, mesh, vals, pi, vals_e, pi_e,
            b.shape[-1],
        )
        if not isinstance(glayout, GridLayout):
            # grid infeasible for this mode: warned fallback on the base
            # layout, keeping the requested local compute flavour
            return phi_from_rows(
                rows, vals, pi, b, n_rows, eps=eps,
                strategy=local_strategy, layout=glayout,
            )
        return phi_grid(glayout, vals_e, pi_e, b, eps, mesh=mesh,
                        local_strategy=local_strategy)
    raise ValueError(f"unknown strategy {strategy!r}")


def _mu_epilogue(b: jax.Array, phi: jax.Array, tol) -> tuple:
    """Shared unblocked epilogue: KKT violation + conditional MU update.

    ``B`` is left untouched on the iteration that detects convergence
    (viol <= tol), matching Chi & Kolda's check-before-update semantics.
    """
    with jax.named_scope("cpapr.epilogue"):
        viol = jnp.max(jnp.abs(jnp.minimum(b, 1.0 - phi)))
        return jnp.where(viol > tol, b * phi, b), viol


def phi_mu_step(
    rows: jax.Array,
    vals: jax.Array,
    pi: jax.Array,
    b: jax.Array,
    n_rows: int,
    eps: float = 1e-10,
    tol: float = 1e-4,
    strategy: str = "segment",
    layout: "BlockedLayout | ShardedBlockedLayout | None" = None,
    vals_e: jax.Array | None = None,
    pi_e: jax.Array | None = None,
    mesh=None,
    local_strategy: str = "blocked",
    pi_gather=None,
    factors=None,
    combine: str = "psum",
    dense=None,
    operands=None,
) -> tuple:
    """One fused CP-APR inner MU step: ``(B', viol)`` in a single pass.

    Computes Phi^(n), the KKT violation ``max |min(B, 1 - Phi)|`` and the
    multiplicative update ``B' = B * Phi`` (applied only while
    ``viol > tol``) for any strategy.  For ``pallas`` the epilogue runs
    inside the kernel on the last visit to each row block — the Phi window
    never round-trips through HBM; for the jnp strategies the whole step
    is one traced expression so XLA fuses the epilogue into the reduction.
    For ``sharded`` the per-device Phi partials meet in a single psum over
    the mesh and the epilogue runs on the replicated combined window — the
    fused fast path survives sharding with exactly one collective.  With
    ``combine="reduce_scatter"`` the combine scatters over row-owner
    slots instead and the epilogue runs shard-locally on owned rows
    (bitwise-identical ``(B', viol)``); the solver's inner loop uses the
    owner-stacked carry directly via
    ``repro.core.distributed.phi_mu_sharded_owner``.
    ``operands`` as for :func:`phi_from_rows`.
    This is the entry point ``cpapr_mu``'s inner ``lax.while_loop`` calls.
    """
    eps = float(eps)
    _check_combine(strategy, combine)
    if strategy in ("scatter", "segment"):
        phi = (
            _phi_scatter(rows, vals, pi, b, n_rows, eps)
            if strategy == "scatter"
            else _phi_segment(rows, vals, pi, b, n_rows, eps)
        )
        return _mu_epilogue(b, phi, tol)
    if strategy == "blocked":
        layout, vals_e, pi_e = _resolve_layout(
            rows, n_rows, layout, vals, pi, vals_e, pi_e
        )
        # Mirror of the fused kernel epilogue on the padded windows: the
        # padded region of B/Phi is zero, so it adds |min(0, 1)| = 0 to the
        # violation max and nothing to B*Phi.
        phi_pad = _phi_blocked_padded(layout, vals_e, pi_e, b, eps)
        with jax.named_scope("cpapr.layout"):
            b_pad = jnp.pad(b, ((0, layout.n_rows_pad - b.shape[0]), (0, 0)))
        b_new_pad, viol = _mu_epilogue(b_pad, phi_pad, tol)
        with jax.named_scope("cpapr.layout"):
            return b_new_pad[:n_rows], viol
    if strategy == "pallas":
        from repro.kernels.phi import ops as phi_ops

        layout, operands = _resolve_pallas(
            rows, n_rows, layout, vals, pi, vals_e, pi_e, operands
        )
        mu_pad, viol = phi_ops.phi_mu_blocked(layout, operands, b, eps)
        with jax.named_scope("cpapr.layout"):
            mu = mu_pad[:n_rows]
        with jax.named_scope("cpapr.epilogue"):
            return jnp.where(viol > tol, mu, b), viol
    if strategy == "dense":
        from repro.kernels.dense import ops as dense_ops

        x, c, a = _dense_operands(dense, factors, b)
        mu, viol = dense_ops.phi_mu_dense(x, c, a, b, eps=eps)
        return jnp.where(viol > tol, mu, b), viol
    if strategy == "sharded":
        from .distributed import phi_mu_sharded  # deferred: avoids cycle

        if pi_gather is not None:
            slayout = _require_pig_layout(layout, pi_gather, factors)
            if vals_e is None:
                vals_e = expand_vals_to_shards(slayout, vals)
            return phi_mu_sharded(slayout, vals_e, None, b, eps, tol,
                                  mesh=mesh, local_strategy=local_strategy,
                                  pi_gather=pi_gather, factors=factors,
                                  combine=combine)
        slayout, vals_e, pi_e, mesh = _resolve_sharded(
            rows, n_rows, layout, mesh, vals, pi, vals_e, pi_e
        )
        if not isinstance(slayout, ShardedBlockedLayout):
            # fewer row blocks than shards: warned fallback on the base
            # layout, keeping the requested local compute flavour
            return phi_mu_step(
                rows, vals, pi, b, n_rows, eps=eps, tol=tol,
                strategy=local_strategy, layout=slayout,
            )
        return phi_mu_sharded(slayout, vals_e, pi_e, b, eps, tol, mesh=mesh,
                              local_strategy=local_strategy, combine=combine)
    if strategy == "grid":
        _check_grid_args(pi_gather, None)
        from .distributed import phi_mu_grid  # deferred: avoids cycle

        glayout, vals_e, pi_e, mesh = _resolve_grid(
            rows, n_rows, layout, mesh, vals, pi, vals_e, pi_e,
            b.shape[-1],
        )
        if not isinstance(glayout, GridLayout):
            # grid infeasible for this mode: warned fallback on the base
            # layout, keeping the requested local compute flavour
            return phi_mu_step(
                rows, vals, pi, b, n_rows, eps=eps, tol=tol,
                strategy=local_strategy, layout=glayout,
            )
        return phi_mu_grid(glayout, vals_e, pi_e, b, eps, tol, mesh=mesh,
                           local_strategy=local_strategy)
    raise ValueError(f"unknown strategy {strategy!r}")


def krao_reduce_rows(
    rows: jax.Array,
    vals: jax.Array,
    kr: jax.Array,
    n_rows: int,
    strategy: str = "segment",
    layout: "BlockedLayout | ShardedBlockedLayout | None" = None,
    vals_e: jax.Array | None = None,
    kr_e: jax.Array | None = None,
    mesh=None,
    local_strategy: str = "blocked",
    pi_gather=None,
    factors=None,
    sorted_rows: bool = True,
    combine: str = "psum",
    dense=None,
) -> jax.Array:
    """Shared segmented Khatri-Rao reduction: ``out[i] = sum x_j * kr_j``.

    The MTTKRP kernel family (CP-ALS's bottleneck, paper Exp. 8) is the
    Phi reduction without the model divide — same sorted stream, same
    blocked schedule, same shard combine.  This entry point routes it
    through the identical strategy stack:

      * ``scatter``  — XLA scatter-add (``rows`` may be unsorted);
      * ``segment``  — sorted ``segment_sum``;
      * ``blocked``  — the blocked segmented schedule (jnp emulation),
        via :func:`_phi_blocked_core` with plain weights;
      * ``pallas``   — the MTTKRP Pallas kernel (repro.kernels.mttkrp);
      * ``dense``    — the matrix-free dense kernel on ``dense=`` (a
        :class:`repro.core.dense.DenseModeData`) + ``factors``;
        ``rows``/``vals``/``kr`` may be None;
      * ``sharded``  — row-block shards + one psum combine; with
        ``pi_gather``/``factors``, each shard computes its Khatri-Rao
        rows shard-locally and ``kr``/``kr_e`` may be None.

    ``rows`` must be sorted for every strategy except ``scatter`` and
    ``segment``; for ``segment``, ``sorted_rows=False`` drops the
    ``indices_are_sorted`` promise so unsorted COO order stays correct
    (the :func:`repro.core.cpals.mttkrp` wrapper's default).
    ``vals_e``/``kr_e`` are pre-expanded layout arrays (hoisted by the
    solver), mirroring :func:`phi_from_rows` — as does ``combine`` (the
    sharded psum vs reduce-scatter epilogue flavour).
    """
    _check_combine(strategy, combine)
    if strategy in ("scatter", "segment"):
        return _krao_unblocked(rows, vals, kr, n_rows, strategy,
                               bool(sorted_rows))
    if strategy == "blocked":
        layout, vals_e, kr_e = _resolve_layout(
            rows, n_rows, layout, vals, kr, vals_e, kr_e
        )
        return _phi_blocked_core(
            vals_e,
            kr_e,
            jnp.asarray(layout.local_rows),
            jnp.asarray(layout.grid_rb),
            None,
            block_nnz=layout.block_nnz,
            block_rows=layout.block_rows,
            n_row_blocks=layout.n_row_blocks,
            eps=0.0,
        )[:n_rows]
    if strategy == "pallas":
        from repro.kernels.mttkrp import ops as mttkrp_ops

        layout, vals_e, kr_e = _resolve_layout(
            rows, n_rows, layout, vals, kr, vals_e, kr_e
        )
        return mttkrp_ops.mttkrp_blocked(layout, vals_e, kr_e)[:n_rows]
    if strategy == "dense":
        from repro.kernels.dense import ops as dense_ops

        x, c, a = _dense_operands(dense, factors)
        return dense_ops.mttkrp_dense(x, c, a)
    if strategy == "sharded":
        from .distributed import krao_sharded  # deferred: avoids cycle

        if pi_gather is not None:
            slayout = _require_pig_layout(layout, pi_gather, factors)
            if vals_e is None:
                vals_e = expand_vals_to_shards(slayout, vals)
            return krao_sharded(slayout, vals_e, None, mesh=mesh,
                                local_strategy=local_strategy,
                                pi_gather=pi_gather, factors=factors,
                                combine=combine)
        slayout, vals_e, kr_e, mesh = _resolve_sharded(
            rows, n_rows, layout, mesh, vals, kr, vals_e, kr_e
        )
        if not isinstance(slayout, ShardedBlockedLayout):
            # fewer row blocks than shards: warned fallback on the base
            # layout, keeping the requested local compute flavour
            return krao_reduce_rows(
                rows, vals, kr, n_rows,
                strategy=local_strategy, layout=slayout,
            )
        return krao_sharded(slayout, vals_e, kr_e, mesh=mesh,
                            local_strategy=local_strategy, combine=combine)
    if strategy == "grid":
        _check_grid_args(pi_gather, None)
        from .distributed import krao_grid  # deferred: avoids cycle

        glayout, vals_e, kr_e, mesh = _resolve_grid(
            rows, n_rows, layout, mesh, vals, kr, vals_e, kr_e,
            kr.shape[-1],
        )
        if not isinstance(glayout, GridLayout):
            # grid infeasible for this mode: warned fallback on the base
            # layout, keeping the requested local compute flavour
            return krao_reduce_rows(
                rows, vals, kr, n_rows,
                strategy=local_strategy, layout=glayout,
            )
        return krao_grid(glayout, vals_e, kr_e, mesh=mesh,
                         local_strategy=local_strategy)
    raise ValueError(f"unknown strategy {strategy!r}")


def expand_to_layout(layout: BlockedLayout, vals, pi):
    """Expand sorted per-nonzero arrays into the padded layout order."""
    gather = jnp.asarray(layout.gather)
    valid = jnp.asarray(layout.valid)
    if vals.shape[0] == 0:  # gather on a 0-row operand is ill-formed
        return (jnp.zeros(gather.shape, vals.dtype),
                jnp.zeros(gather.shape + (pi.shape[1],), pi.dtype))
    vals_e = jnp.where(valid, vals[gather], 0.0)
    pi_e = jnp.where(valid[:, None], pi[gather], 0.0)
    return vals_e, pi_e


def expand_to_shards(slayout: ShardedBlockedLayout, vals, pi):
    """Expand sorted per-nonzero arrays into per-shard padded layout order.

    Returns ``vals_e`` of shape (S, n_grid_shard*block_nnz) and ``pi_e`` of
    shape (S, n_grid_shard*block_nnz, R); the leading axis is the shard
    (mesh data) axis.
    """
    gather = jnp.asarray(slayout.gather)
    valid = jnp.asarray(slayout.valid)
    if vals.shape[0] == 0:  # gather on a 0-row operand is ill-formed
        return (jnp.zeros(gather.shape, vals.dtype),
                jnp.zeros(gather.shape + (pi.shape[1],), pi.dtype))
    vals_e = jnp.where(valid, vals[gather], 0.0)
    pi_e = jnp.where(valid[..., None], pi[gather], 0.0)
    return vals_e, pi_e


def expand_to_grid(glayout: GridLayout, vals, pi):
    """Expand sorted per-nonzero arrays into per-cell padded layout order.

    Returns ``vals_e`` of shape (A*B, n_grid_cell*block_nnz) and ``pi_e``
    of shape (A*B, n_grid_cell*block_nnz, R); the leading axis is the
    flat cell axis (cell ``(s, c)`` at ``s*B + c``), split row-major
    over the ``("row", "col")`` mesh.
    """
    gather = jnp.asarray(glayout.gather)
    valid = jnp.asarray(glayout.valid)
    if vals.shape[0] == 0:  # gather on a 0-row operand is ill-formed
        return (jnp.zeros(gather.shape, vals.dtype),
                jnp.zeros(gather.shape + (pi.shape[1],), pi.dtype))
    vals_e = jnp.where(valid, vals[gather], 0.0)
    pi_e = jnp.where(valid[..., None], pi[gather], 0.0)
    return vals_e, pi_e


def expand_vals_to_shards(slayout: ShardedBlockedLayout, vals):
    """Expand sorted per-nonzero values into per-shard padded layout order.

    The values-only half of :func:`expand_to_shards`, for the shard-local
    Pi path where the (S, slot, R) expanded Pi array is never materialized
    — each device builds its own Pi rows from gathered factor rows (see
    ``repro.core.pi.pi_rows_local``).
    """
    gather = jnp.asarray(slayout.gather)
    valid = jnp.asarray(slayout.valid)
    if vals.shape[0] == 0:  # gather on a 0-row operand is ill-formed
        return jnp.zeros(gather.shape, vals.dtype)
    return jnp.where(valid, vals[gather], 0.0)


def phi_mode(
    mv: ModeView,
    factors: Sequence[jax.Array],
    b: jax.Array,
    eps: float = 1e-10,
    strategy: str = "segment",
    layout: BlockedLayout | None = None,
    perturb: str | None = None,
) -> jax.Array:
    """Full Phi^(n) for a mode view: Pi gather-product then reduction.

    For ``strategy="dense"`` the mode is densified on the fly (shape
    taken from the factor row counts) and no Pi is ever built — fine for
    one-shot calls; solvers build the :class:`DenseModeData` once via
    ``repro.core.cpapr.resolve_mode_policies`` instead.
    """
    n = mv.mode
    if strategy == "dense":
        if perturb is not None:
            raise ValueError("perturb is not supported for strategy='dense'")
        from .dense import build_dense_mode

        shape = tuple(int(f.shape[0]) for f in factors)
        dn = build_dense_mode(
            np.asarray(mv.sorted_idx), np.asarray(mv.sorted_vals), shape, n
        )
        return phi_from_rows(
            None, None, None, b, n_rows=mv.n_rows, eps=eps,
            strategy="dense", dense=dn, factors=tuple(factors),
        )
    idx = mv.sorted_idx
    if perturb == "perfect_reuse":
        idx = idx * 0
    pi = pi_rows(idx, factors, n)
    return phi_from_rows(
        mv.rows,
        mv.sorted_vals,
        pi,
        b,
        n_rows=mv.n_rows,
        eps=eps,
        strategy=strategy,
        layout=layout,
        perturb=perturb,
    )
