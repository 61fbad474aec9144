"""Distributed CP-APR MU via shard_map (beyond-paper: SparTen is one node).

Decomposition (DESIGN.md Sec. 3):
  * nonzeros sharded over the data axes — each device owns a contiguous
    slice of the *sorted* stream (the paper's permutation array, built
    once on host);
  * factor matrices sharded over rank R on 'model' — Pi rows are
    elementwise in R, so the Khatri-Rao gather-product needs **no
    communication**;
  * the model value s_j = <B[i_j,:], pi_j> sums over R => one small
    psum over 'model' of an (nnz_local,) vector per inner iteration;
  * Phi is a local segmented reduce to (I_n, R_local) + one psum over
    the data axes per inner iteration.

Two collectives per inner MU iteration, both minimal for this algorithm
family: comm volume is O(nnz/chips) + O(I_n * R / model), independent of
the tensor's dimensionality.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .layout import (
    GridLayout,
    OwnerPartition,
    ShardedBlockedLayout,
    ShardedPiGather,
    owner_partition,
)
from .pi import pi_rows_local
from .resilience import ShardAssignmentError
from .sparse_tensor import KTensor, SparseTensor, random_ktensor, sort_mode

__all__ = [
    "DistCPAPRConfig",
    "PHI_COMBINES",
    "dist_cpapr_mu",
    "shard_mode_views",
    "grid_scatter_wire_bytes",
    "grid_stack",
    "grid_unstack",
    "krao_grid",
    "make_grid_mesh",
    "make_phi_mesh",
    "mesh_device_count",
    "krao_sharded",
    "owner_stack",
    "owner_unstack",
    "owner_scatter_wire_bytes",
    "preferred_combine",
    "phi_grid",
    "phi_grid_owner",
    "phi_mu_grid",
    "phi_mu_grid_owner",
    "phi_sharded",
    "phi_sharded_owner",
    "phi_mu_sharded",
    "phi_mu_sharded_owner",
    "sharded_combine_bytes",
]

# Combine flavours of the sharded Phi/MTTKRP reduction:
#   "psum"           — all-reduce the full (buf_rows, R) window (PR-2);
#                      every device holds the combined window, the MU
#                      epilogue runs replicated.  Bitwise reference.
#   "reduce_scatter" — reduce-scatter over row-owner slots; each device
#                      keeps only its owned O(I_n*R/S) slice and runs
#                      the epilogue owner-locally.  Bitwise-identical
#                      results (owner slots sum disjoint-support
#                      windows, so both combines add exact zeros).
PHI_COMBINES = ("psum", "reduce_scatter")


# ---------------------------------------------------------------------------
# Sharded blocked Phi: contiguous row-block shards + one psum combine
# ---------------------------------------------------------------------------


def mesh_device_count(mesh: Mesh) -> int:
    """Total devices in a mesh (product over every axis)."""
    return int(np.prod([mesh.shape[a] for a in mesh.axis_names] or [1]))


def make_phi_mesh(n_shards: int, devices=None) -> Mesh:
    """1-D ``("data",)`` mesh over the first ``n_shards`` devices."""
    devices = list(devices if devices is not None else jax.devices())
    if n_shards > len(devices):
        raise ValueError(
            f"n_shards={n_shards} exceeds available devices ({len(devices)})"
        )
    return Mesh(np.asarray(devices[:n_shards]), ("data",))


def sharded_combine_bytes(slayout: ShardedBlockedLayout, rank: int,
                          itemsize: int = 4) -> int:
    """Bytes of the per-device psum operand for the sharded Phi combine."""
    return slayout.combine_bytes(rank, itemsize)


def _shard_window(slayout: ShardedBlockedLayout, eps: float,
                  local_strategy: str,
                  vals_e, pi_e, local_rows, grid_rb, b_win):
    """One shard's local output window (``n_rb_shard * block_rows``, R).

    The local blocked reduction over this shard's row-block range
    (``local_strategy``: 'blocked' = jnp emulation, 'pallas' = the real
    kernel).  ``b_win`` is the shard's B window (or None for the *plain*
    Khatri-Rao sum, MTTKRP).  Rows past the shard's real row-block count
    are all-padding (only invalid slots visit them), so they come back
    exactly zero — the invariant both combines rely on.
    """
    from .phi import _phi_blocked_core  # deferred: phi lazily imports us

    br = slayout.block_rows
    if local_strategy == "pallas":
        if b_win is None:
            from repro.kernels.mttkrp import ops as mttkrp_ops

            phi_local = mttkrp_ops.mttkrp_blocked_arrays(
                grid_rb,
                vals_e,
                local_rows,
                pi_e,
                block_nnz=slayout.block_nnz,
                block_rows=br,
                n_rows_pad=slayout.n_rb_shard * br,
            )
        else:
            from repro.kernels.phi import ops as phi_ops

            phi_local = phi_ops.phi_blocked_arrays(
                grid_rb,
                vals_e,
                local_rows,
                pi_e,
                b_win,
                block_nnz=slayout.block_nnz,
                block_rows=br,
                eps=eps,
            )
    else:
        phi_local = _phi_blocked_core(
            vals_e,
            pi_e,
            local_rows,
            grid_rb,
            b_win,
            block_nnz=slayout.block_nnz,
            block_rows=br,
            n_row_blocks=slayout.n_rb_shard,
            eps=eps,
        )
    return phi_local


def _shard_partial(slayout: ShardedBlockedLayout, eps: float,
                   local_strategy: str,
                   vals_e, pi_e, local_rows, grid_rb, rb_start, b_buf):
    """One shard's contribution to the global output window.

    Computes the local window (:func:`_shard_window`) and places it at
    its global row offset inside a zero ``buf_rows``-row buffer — the
    psum combine then sums disjoint windows (plus zeros).
    """
    br = slayout.block_rows
    r = pi_e.shape[-1]
    row0 = rb_start * br
    b_win = None if b_buf is None else jax.lax.dynamic_slice(
        b_buf, (row0, 0), (slayout.n_rb_shard * br, r)
    )
    phi_local = _shard_window(slayout, eps, local_strategy,
                              vals_e, pi_e, local_rows, grid_rb, b_win)
    out = jnp.zeros((slayout.buf_rows, r), phi_local.dtype)
    return jax.lax.dynamic_update_slice(out, phi_local, (row0, 0))


def _pad_b_buf(slayout: ShardedBlockedLayout, b):
    return jnp.pad(b, ((0, slayout.buf_rows - b.shape[0]), (0, 0)))


@functools.partial(
    jax.jit, static_argnames=("slayout", "eps", "mesh", "local_strategy")
)
def _phi_sharded_buf(slayout: ShardedBlockedLayout, vals_es, pi_es, b,
                     eps: float, mesh: Mesh | None,
                     local_strategy: str = "blocked"):
    """Combined (buf_rows, R) Phi window, replicated on every device.

    With a mesh: one shard per device inside ``shard_map`` and a single
    psum over every mesh axis — the only collective of the inner MU
    iteration.  Without a mesh: the identical schedule unrolled on one
    device (shard loop + sum), numerically matching the psum combine.
    """
    lrows = jnp.asarray(slayout.local_rows)
    grbs = jnp.asarray(slayout.grid_rb)
    rb0 = jnp.asarray(slayout.rb_start)
    b_buf = _pad_b_buf(slayout, b)
    part = partial(_shard_partial, slayout, eps, local_strategy)

    if mesh is None:
        partials = [
            part(vals_es[s], pi_es[s], lrows[s], grbs[s], rb0[s], b_buf)
            for s in range(slayout.n_shards)
        ]
        return functools.reduce(jnp.add, partials)

    axes = tuple(mesh.axis_names)

    def local(vals_e, pi_e, lr, grb, r0, bb):
        p = part(vals_e[0], pi_e[0], lr[0], grb[0], r0[0], bb)
        return jax.lax.psum(p, axes)

    fn = jax.shard_map(
        local,
        mesh=mesh,
        check_vma=False,
        in_specs=(P(axes, None), P(axes, None, None), P(axes, None),
                  P(axes, None), P(axes), P(None, None)),
        out_specs=P(None, None),
    )
    return fn(vals_es, pi_es, lrows, grbs, rb0, b_buf)


def _run_sharded(mesh: Mesh | None, shard_fn, sharded_args, bcast_args):
    """Run ``shard_fn`` once per shard and sum the partial buffers.

    ``sharded_args`` carry a leading shard axis (one slice per device);
    ``bcast_args`` are replicated.  With a mesh this is a ``shard_map``
    whose single collective is the psum of the (buf_rows, R) partials;
    without one the identical schedule is unrolled on one device
    (numerically matching the psum combine).
    """
    if mesh is None:
        n_shards = sharded_args[0].shape[0]
        parts = [
            shard_fn(*[a[s] for a in sharded_args], *bcast_args)
            for s in range(n_shards)
        ]
        return functools.reduce(jnp.add, parts)

    axes = tuple(mesh.axis_names)
    n_sharded = len(sharded_args)

    def local(*args):
        sh = [a[0] for a in args[:n_sharded]]
        p = shard_fn(*sh, *args[n_sharded:])
        return jax.lax.psum(p, axes)

    in_specs = tuple(
        P(axes, *([None] * (a.ndim - 1))) for a in sharded_args
    ) + tuple(P(*([None] * a.ndim)) for a in bcast_args)
    fn = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                       out_specs=P(None, None), check_vma=False)
    return fn(*sharded_args, *bcast_args)


@functools.partial(
    jax.jit,
    static_argnames=("slayout", "pig", "eps", "mesh", "local_strategy",
                     "plain"),
)
def _sharded_local_pi_buf(slayout: ShardedBlockedLayout,
                          pig: ShardedPiGather, vals_es, fgs, b,
                          eps: float, mesh: Mesh | None,
                          local_strategy: str, plain: bool):
    """Combined (buf_rows, R) window with *shard-local* Pi computation.

    ``fgs`` are the per-shard gathered factor rows (one (S, U_m, R) array
    per gathered mode, from ``pig.touched``); each device rebuilds its
    own Pi/Khatri-Rao rows with ``pi_rows_local`` — the O(nnz, R)
    expanded Pi array of the replicated path is never materialized, and
    the per-device factor bytes are O(touched_rows * R) instead of the
    replicated O(I * R).  ``plain=True`` drops the model weighting
    (MTTKRP); ``b`` must then be None.
    """
    lrows = jnp.asarray(slayout.local_rows)
    grbs = jnp.asarray(slayout.grid_rb)
    rb0 = jnp.asarray(slayout.rb_start)
    valid = jnp.asarray(slayout.valid)
    lidx = tuple(jnp.asarray(x) for x in pig.local_idx)
    n_modes = len(lidx)
    b_buf = None if plain else _pad_b_buf(slayout, b)

    def shard_fn(vals_e, vmask, lr, grb, r0, *rest):
        li = rest[:n_modes]
        fg = rest[n_modes : 2 * n_modes]
        bb = rest[2 * n_modes] if not plain else None
        pi_e = pi_rows_local(fg, li, vmask)
        return _shard_partial(slayout, eps, local_strategy,
                              vals_e, pi_e, lr, grb, r0, bb)

    sharded_args = (vals_es, valid, lrows, grbs, rb0, *lidx, *fgs)
    bcast_args = () if plain else (b_buf,)
    return _run_sharded(mesh, shard_fn, sharded_args, bcast_args)


@functools.partial(
    jax.jit, static_argnames=("slayout", "mesh", "local_strategy")
)
def _krao_sharded_buf(slayout: ShardedBlockedLayout, vals_es, kr_es,
                      mesh: Mesh | None, local_strategy: str = "blocked"):
    """Combined (buf_rows, R) window of the plain sharded reduction
    (MTTKRP): pre-expanded Khatri-Rao rows, no model weighting."""
    lrows = jnp.asarray(slayout.local_rows)
    grbs = jnp.asarray(slayout.grid_rb)
    rb0 = jnp.asarray(slayout.rb_start)

    def shard_fn(vals_e, kr_e, lr, grb, r0):
        return _shard_partial(slayout, 0.0, local_strategy,
                              vals_e, kr_e, lr, grb, r0, None)

    return _run_sharded(mesh, shard_fn, (vals_es, kr_es, lrows, grbs, rb0),
                        ())


# ---------------------------------------------------------------------------
# Reduce-scatter epilogue over row-owner partitions
# ---------------------------------------------------------------------------


def owner_stack(opart: OwnerPartition, b):
    """Owner-stacked (S, own_rows, R) form of a full factor block.

    Pads ``b`` to the combine window, slices each owner's padded row
    window, and masks rows owned by the *next* owner to zero.  The
    masked tail only ever multiplies invalid layout slots inside the
    shard-local compute, so Phi built from the stacked form is
    bitwise-identical to Phi built from the full window.
    """
    r = b.shape[-1]
    b_buf = jnp.pad(b, ((0, opart.buf_rows - b.shape[0]), (0, 0)))
    slots = jnp.stack([
        jax.lax.dynamic_slice(b_buf, (int(s0), 0), (opart.own_rows, r))
        for s0 in opart.row_start
    ])
    return jnp.where(jnp.asarray(opart.masks())[:, :, None], slots, 0.0)


def owner_unstack(opart: OwnerPartition, stacked):
    """Reassemble the full (n_rows, R) block from owner-stacked slices.

    This is the once-per-mode-update factor-row gather of the
    reduce-scatter epilogue: under a mesh the stacked array is
    device-sharded on its owner axis, so consuming it here gathers the
    O(I_n * R) updated rows **once per mode update** — instead of the
    psum path's all-reduce of the full window once per inner iteration.
    Keep it in its own jitted dispatch (the solver does) so the runtime
    can overlap the gather with the next mode's Phi prologue.

    When every owner slot is really its full padded width (uniform
    splits — the common case), the slots tile the combine window
    exactly, so the reassembly is a single reshape: one traced op
    instead of a chain of S sequential ``dynamic_update_slice``
    dispatches over the same O(I_n * R) buffer.
    """
    r = stacked.shape[-1]
    if np.all(np.asarray(opart.row_count) == opart.own_rows):
        return stacked.reshape(opart.n_shards * opart.own_rows, r)[
            : opart.n_rows
        ]
    out = jnp.zeros((opart.buf_rows, r), stacked.dtype)
    for s in range(opart.n_shards):
        cnt = int(opart.row_count[s])
        out = jax.lax.dynamic_update_slice(
            out, stacked[s, :cnt], (int(opart.row_start[s]), 0)
        )
    return out[: opart.n_rows]


def owner_scatter_wire_bytes(opart: OwnerPartition, rank: int,
                             itemsize: int = 4) -> float:
    """Per-device ring wire bytes of the reduce-scatter combine.

    Input is the (S * own_rows, R) owner-slot operand, output the owned
    (own_rows, R) slice: ring reduce-scatter moves ``(S-1) * output``
    bytes per device — about half the psum path's all-reduce of the full
    window, with an O(I_n * R / S) per-device *result* instead of the
    replicated O(I_n * R) buffer.
    """
    if opart.n_shards <= 1:
        return 0.0
    return float(
        (opart.n_shards - 1) * opart.own_rows * rank * itemsize
    )


def preferred_combine(slayout: ShardedBlockedLayout, rank: int,
                      itemsize: int = 4) -> str:
    """Wire-cheaper combine flavour for this layout's shard split.

    The reduce-scatter operand's owner slots are padded to the *widest*
    owner (``own_rows = n_rb_shard * block_rows``), so its ring wire is
    ``(S-1) * own_rows * R`` against the psum's ``2 (S-1)/S * buf_rows
    * R``.  Balanced splits pay about half the psum wire; a heavily
    block-skewed split (one owner holding most row blocks) can pad the
    slots past the all-reduce.  ``combine="auto"`` consults this per
    mode; ties go to reduce-scatter — its per-device combine *output*
    (the owned O(I_n * R / S) slice) always beats the replicated window,
    and the factor gather amortizes to once per mode update.
    """
    s = slayout.n_shards
    if s <= 1:
        return "reduce_scatter"
    opart = owner_partition(slayout)
    rs_wire = owner_scatter_wire_bytes(opart, rank, itemsize)
    psum_wire = 2.0 * (s - 1) / s * slayout.combine_bytes(rank, itemsize)
    return "reduce_scatter" if rs_wire <= psum_wire else "psum"


def _validate_owner(slayout: ShardedBlockedLayout, opart: OwnerPartition):
    """An owner partition built from one shard assignment must never run
    against another — its slices would silently cover the wrong rows."""
    if opart.n_shards != slayout.n_shards:
        raise ValueError(
            f"owner partition has {opart.n_shards} shards but the layout "
            f"has {slayout.n_shards}"
        )
    if opart.rb_start != tuple(int(x) for x in slayout.rb_start):
        raise ShardAssignmentError(
            "owner partition was built from a different shard assignment "
            f"(rb_start {opart.rb_start} vs "
            f"{tuple(int(x) for x in slayout.rb_start)}); rebuild it with "
            "owner_partition() after rebalancing"
        )


def _linear_axis_index(mesh: Mesh, axes: tuple):
    idx = jax.lax.axis_index(axes[0])
    for a in axes[1:]:
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return idx


@functools.partial(
    jax.jit,
    static_argnames=("slayout", "opart", "pig", "eps", "tol", "mesh",
                     "local_strategy", "fused", "plain"),
)
def _owner_combined(slayout: ShardedBlockedLayout, opart: OwnerPartition,
                    vals_es, pi_es, fgs, b_own, eps: float, tol: float,
                    mesh: Mesh | None, local_strategy: str,
                    fused: bool, plain: bool,
                    pig: ShardedPiGather | None = None):
    """Reduce-scatter combine core: owner-stacked results, no replication.

    Each shard's local window *is* its contribution to its own owner
    slot (windows only overlap on padding rows, which are exactly zero),
    so the combine is one ``psum_scatter`` of the (S * own_rows, R)
    owner-slot operand: device ``s`` writes its masked window at slot
    ``s`` and receives only its owned O(I_n * R / S) slice.

    * ``fused=False`` — returns the owner-stacked combined window
      (S, own_rows, R); ``b_own`` supplies the Phi model weighting
      (None together with ``plain=True`` for the MTTKRP reduction).
    * ``fused=True`` — the full owner-local MU step: KKT violation via a
      scalar ``pmax`` and the multiplicative update on owned rows only;
      returns ``(b_own', viol)`` with the loop-carry kept owner-stacked.

    Without a mesh the same schedule runs unrolled on one device —
    bitwise-matching the scattered combine (each owner slot receives
    exactly one nonzero contribution, so both paths add exact zeros).
    With ``pig`` the Pi rows are computed shard-locally from ``fgs``
    (``pi_es`` unused).
    """
    lrows = jnp.asarray(slayout.local_rows)
    grbs = jnp.asarray(slayout.grid_rb)
    mask = jnp.asarray(opart.masks())
    s_count, own_rows = opart.n_shards, opart.own_rows
    n_pig = len(pig.local_idx) if pig is not None else 0
    valid = jnp.asarray(slayout.valid) if pig is not None else None
    lidx = (tuple(jnp.asarray(x) for x in pig.local_idx)
            if pig is not None else ())

    def window(vals_e, pi_e, lr, grb, b_win, vmask, li, fg):
        if pig is not None:
            pi_e = pi_rows_local(fg, li, vmask)
        return _shard_window(slayout, eps, local_strategy,
                             vals_e, pi_e, lr, grb, b_win)

    if mesh is None:
        wins = []
        for s in range(s_count):
            wins.append(window(
                vals_es[s],
                None if pig is not None else pi_es[s],
                lrows[s], grbs[s],
                None if plain else b_own[s],
                valid[s] if pig is not None else None,
                tuple(x[s] for x in lidx),
                tuple(f[s] for f in fgs) if pig is not None else (),
            ))
        stacked = jnp.where(mask[:, :, None], jnp.stack(wins), 0.0)
        if not fused:
            return stacked
        viol = jnp.max(jnp.abs(jnp.minimum(b_own, 1.0 - stacked)))
        return jnp.where(viol > tol, b_own * stacked, b_own), viol

    axes = tuple(mesh.axis_names)
    name = axes[0] if len(axes) == 1 else axes

    def local(*args):
        i = 0
        vals_e = args[i][0]; i += 1
        lr = args[i][0]; i += 1
        grb = args[i][0]; i += 1
        if pig is not None:
            vmask = args[i][0]; i += 1
            li = tuple(args[i + j][0] for j in range(n_pig)); i += n_pig
            fg = tuple(args[i + j][0] for j in range(n_pig)); i += n_pig
            pi_e = None
        else:
            vmask, li, fg = None, (), ()
            pi_e = args[i][0]; i += 1
        b_w = None if plain else args[i][0]
        i += 0 if plain else 1
        mk = args[i][0]  # this owner's (own_rows,) real-row mask

        win = window(vals_e, pi_e, lr, grb, b_w, vmask, li, fg)
        win = jnp.where(mk[:, None], win, 0.0)
        r = win.shape[-1]
        idx = _linear_axis_index(mesh, axes)
        op = jnp.zeros((s_count * own_rows, r), win.dtype)
        op = jax.lax.dynamic_update_slice(op, win, (idx * own_rows, 0))
        owned = jax.lax.psum_scatter(
            op, name, scatter_dimension=0, tiled=True
        )
        if not fused:
            return owned[None]
        viol = jax.lax.pmax(
            jnp.max(jnp.abs(jnp.minimum(b_w, 1.0 - owned))), name
        )
        return jnp.where(viol > tol, b_w * owned, b_w)[None], viol

    sharded_args = [vals_es, lrows, grbs]
    if pig is not None:
        sharded_args += [valid, *lidx, *fgs]
    else:
        sharded_args += [pi_es]
    if not plain:
        sharded_args += [b_own]
    sharded_args += [mask]
    in_specs = tuple(
        P(axes, *([None] * (a.ndim - 1))) for a in sharded_args
    )
    out_specs = (
        (P(axes, None, None), P()) if fused else P(axes, None, None)
    )
    fn = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn(*sharded_args)


def _gather_factor_shards(pig: ShardedPiGather, factors):
    """(S, U_m, R) gathered factor rows per gathered mode (the only factor
    bytes a shard receives under the local-Pi path)."""
    return tuple(
        jnp.asarray(factors[m])[jnp.asarray(pig.touched[j])]
        for j, m in enumerate(pig.modes)
    )


def _validate_pig(slayout: ShardedBlockedLayout, pig: ShardedPiGather):
    """A gather built from one shard assignment must never run against
    another — its index maps would silently point at the wrong rows."""
    if pig.rb_start != tuple(int(x) for x in slayout.rb_start):
        raise ShardAssignmentError(
            "pi_gather was built from a different shard assignment "
            f"(rb_start {pig.rb_start} vs "
            f"{tuple(int(x) for x in slayout.rb_start)}); rebuild it with "
            "build_shard_pi_gather after rebalancing"
        )


def _resolve_combine(combine: str) -> str:
    if combine not in PHI_COMBINES:
        raise ValueError(
            f"unknown combine {combine!r}; expected one of {PHI_COMBINES}"
        )
    return combine


def _resolve_owner(slayout: ShardedBlockedLayout,
                   owner: OwnerPartition | None) -> OwnerPartition:
    if owner is None:
        return owner_partition(slayout)
    _validate_owner(slayout, owner)
    return owner


def _owner_inputs(slayout: ShardedBlockedLayout,
                  owner: OwnerPartition | None,
                  pi_gather: ShardedPiGather | None, factors, pi_es):
    """Shared reduce-scatter dispatch preamble.

    Resolves (or validates) the owner partition, validates the
    shard-local Pi gather and collects its factor-row shards, and picks
    the pre-expanded-rows operand (``None`` when Pi is shard-local).
    Returns ``(opart, fgs, pi_es)`` — the argument-selection rule every
    reduce-scatter entry point must agree on.
    """
    opart = _resolve_owner(slayout, owner)
    fgs = None
    if pi_gather is not None:
        _validate_pig(slayout, pi_gather)
        fgs = _gather_factor_shards(pi_gather, factors)
        pi_es = None
    return opart, fgs, pi_es


def phi_sharded(slayout: ShardedBlockedLayout, vals_es, pi_es, b,
                eps: float = 1e-10, mesh: Mesh | None = None,
                local_strategy: str = "blocked",
                pi_gather: ShardedPiGather | None = None, factors=None,
                combine: str = "psum",
                owner: OwnerPartition | None = None):
    """Phi^(n) over row-block shards.  Inputs from ``expand_to_shards``,
    or — with ``pi_gather``/``factors`` — shard-locally computed Pi rows
    (``pi_es`` then unused; ``vals_es`` from ``expand_vals_to_shards``).
    ``combine="reduce_scatter"`` scatters the combine over row-owner
    slots (each device holds only its owned O(I_n*R/S) slice; the full
    result is reassembled here) instead of the replicating psum —
    bitwise-identical output.  ``owner`` (optional) pins the owner
    partition; it must match the layout's shard assignment."""
    _validate_phi_mesh(slayout, mesh)
    if _resolve_combine(combine) == "reduce_scatter":
        opart, fgs, pi_es = _owner_inputs(slayout, owner, pi_gather,
                                          factors, pi_es)
        stacked = _owner_combined(
            slayout, opart, vals_es, pi_es, fgs,
            owner_stack(opart, b), float(eps), 0.0, mesh, local_strategy,
            False, False, pig=pi_gather)
        return owner_unstack(opart, stacked)
    if pi_gather is not None:
        _validate_pig(slayout, pi_gather)
        fgs = _gather_factor_shards(pi_gather, factors)
        return _sharded_local_pi_buf(
            slayout, pi_gather, vals_es, fgs, b, float(eps), mesh,
            local_strategy, False)[: slayout.n_rows]
    return _phi_sharded_buf(slayout, vals_es, pi_es, b, float(eps),
                            mesh, local_strategy)[: slayout.n_rows]


def krao_sharded(slayout: ShardedBlockedLayout, vals_es, kr_es,
                 mesh: Mesh | None = None, local_strategy: str = "blocked",
                 pi_gather: ShardedPiGather | None = None, factors=None,
                 combine: str = "psum",
                 owner: OwnerPartition | None = None):
    """Sharded plain Khatri-Rao reduction (MTTKRP) with one combine.

    Same shard machinery as :func:`phi_sharded` without the model
    weighting; with ``pi_gather``/``factors`` the Khatri-Rao rows are
    computed shard-locally and ``kr_es`` is unused.  ``combine`` picks
    the psum (replicating all-reduce) or reduce-scatter (owner-sliced)
    epilogue — bitwise-identical results.
    """
    _validate_phi_mesh(slayout, mesh)
    if _resolve_combine(combine) == "reduce_scatter":
        opart, fgs, kr_arg = _owner_inputs(slayout, owner, pi_gather,
                                           factors, kr_es)
        stacked = _owner_combined(
            slayout, opart, vals_es, kr_arg, fgs,
            None, 0.0, 0.0, mesh, local_strategy,
            False, True, pig=pi_gather)
        return owner_unstack(opart, stacked)
    if pi_gather is not None:
        _validate_pig(slayout, pi_gather)
        fgs = _gather_factor_shards(pi_gather, factors)
        return _sharded_local_pi_buf(
            slayout, pi_gather, vals_es, fgs, None, 0.0, mesh,
            local_strategy, True)[: slayout.n_rows]
    return _krao_sharded_buf(slayout, vals_es, kr_es, mesh,
                             local_strategy)[: slayout.n_rows]


def phi_mu_sharded(slayout: ShardedBlockedLayout, vals_es, pi_es, b,
                   eps: float = 1e-10, tol: float = 1e-4,
                   mesh: Mesh | None = None,
                   local_strategy: str = "blocked",
                   pi_gather: ShardedPiGather | None = None, factors=None,
                   combine: str = "psum",
                   owner: OwnerPartition | None = None):
    """Fused sharded MU step, psum or reduce-scatter combine.

    ``combine="psum"`` (PR-2): all-reduce the full window, replicated
    epilogue.  ``combine="reduce_scatter"``: owner-sliced combine +
    owner-local epilogue (the full updated B is reassembled here; the
    solver's inner loop keeps the owner-stacked carry instead via
    :func:`phi_mu_sharded_owner`).  The combine buffer's padding rows
    hold B = Phi = 0, contributing ``|min(0, 1)| = 0`` to the KKT max
    and nothing to ``B * Phi`` — the same invariant as the
    single-device padded windows.  With ``pi_gather``/``factors`` the
    Pi rows are computed shard-locally (``pi_es`` unused).
    """
    from .phi import _mu_epilogue  # deferred: phi lazily imports us

    _validate_phi_mesh(slayout, mesh)
    if _resolve_combine(combine) == "reduce_scatter":
        opart = _resolve_owner(slayout, owner)
        b_own, viol = phi_mu_sharded_owner(
            slayout, opart, vals_es, pi_es, owner_stack(opart, b),
            eps=eps, tol=tol, mesh=mesh, local_strategy=local_strategy,
            pi_gather=pi_gather, factors=factors)
        return owner_unstack(opart, b_own), viol
    if pi_gather is not None:
        _validate_pig(slayout, pi_gather)
        fgs = _gather_factor_shards(pi_gather, factors)
        phi_buf = _sharded_local_pi_buf(
            slayout, pi_gather, vals_es, fgs, b, float(eps), mesh,
            local_strategy, False)
    else:
        phi_buf = _phi_sharded_buf(slayout, vals_es, pi_es, b, float(eps),
                                   mesh, local_strategy)
    b_buf = _pad_b_buf(slayout, b)
    b_new, viol = _mu_epilogue(b_buf, phi_buf, tol)
    return b_new[: slayout.n_rows], viol


def phi_sharded_owner(slayout: ShardedBlockedLayout, opart: OwnerPartition,
                      vals_es, pi_es, b_own,
                      eps: float = 1e-10, mesh: Mesh | None = None,
                      local_strategy: str = "blocked",
                      pi_gather: ShardedPiGather | None = None,
                      factors=None):
    """Owner-stacked combined Phi (S, own_rows, R) — reduce-scatter
    combine, no reassembly.  ``b_own`` is the owner-stacked B
    (:func:`owner_stack`); the solver's scooch step consumes this form
    directly so the full window is never replicated."""
    _validate_phi_mesh(slayout, mesh)
    opart, fgs, pi_es = _owner_inputs(slayout, opart, pi_gather,
                                      factors, pi_es)
    return _owner_combined(
        slayout, opart, vals_es, pi_es, fgs, b_own,
        float(eps), 0.0, mesh, local_strategy, False, False,
        pig=pi_gather)


def phi_mu_sharded_owner(slayout: ShardedBlockedLayout,
                         opart: OwnerPartition, vals_es, pi_es, b_own,
                         eps: float = 1e-10, tol: float = 1e-4,
                         mesh: Mesh | None = None,
                         local_strategy: str = "blocked",
                         pi_gather: ShardedPiGather | None = None,
                         factors=None):
    """Owner-partitioned fused MU step: ``(b_own', viol)``, no gather.

    The loop-carry form of the reduce-scatter epilogue: ``b_own`` is the
    owner-stacked (S, own_rows, R) B (:func:`owner_stack`), the combine
    is one reduce-scatter over owner slots, and the MU/KKT epilogue runs
    shard-locally on owned rows (the KKT max meets in a scalar pmax).
    The solver's inner ``lax.while_loop`` carries ``b_own`` across
    iterations and reassembles the full factor **once** per mode update
    with :func:`owner_unstack` — per-inner-iteration combine traffic
    drops from the psum path's all-reduce of the full O(I_n * R) window
    to a reduce-scatter whose per-device output is O(I_n * R / S).
    """
    _validate_phi_mesh(slayout, mesh)
    opart, fgs, pi_es = _owner_inputs(slayout, opart, pi_gather,
                                      factors, pi_es)
    return _owner_combined(
        slayout, opart, vals_es, pi_es, fgs, b_own,
        float(eps), float(tol), mesh, local_strategy, True, False,
        pig=pi_gather)


def _validate_phi_mesh(slayout: ShardedBlockedLayout, mesh: Mesh | None):
    if mesh is None:
        return
    n_dev = mesh_device_count(mesh)
    if n_dev != slayout.n_shards:
        raise ValueError(
            f"mesh has {n_dev} devices but the layout has "
            f"{slayout.n_shards} shards"
        )


# ---------------------------------------------------------------------------
# N-D grid combine: all-gather + reduce-scatter over the column axis
# ---------------------------------------------------------------------------


def make_grid_mesh(grid_a: int, grid_b: int, devices=None) -> Mesh:
    """2-D ``("row", "col")`` mesh over the first ``A*B`` devices.

    Device ``(i, j)`` holds grid cell ``i*B + j`` — the row-major flat
    order every ``(A*B, ...)`` cell array uses.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = int(grid_a) * int(grid_b)
    if n > len(devices):
        raise ValueError(
            f"grid {grid_a}x{grid_b} needs {n} devices, "
            f"have {len(devices)}"
        )
    return Mesh(
        np.asarray(devices[:n]).reshape(int(grid_a), int(grid_b)),
        ("row", "col"),
    )


def _validate_grid_mesh(glayout: GridLayout, mesh: Mesh | None):
    if mesh is None:
        return
    if tuple(mesh.axis_names) != ("row", "col"):
        raise ValueError(
            f"grid mesh must have axes ('row', 'col'), got "
            f"{tuple(mesh.axis_names)}"
        )
    shape = (int(mesh.shape["row"]), int(mesh.shape["col"]))
    if shape != (glayout.grid_a, glayout.grid_b):
        raise ValueError(
            f"mesh shape {shape} does not match the layout's grid "
            f"{(glayout.grid_a, glayout.grid_b)}"
        )


def grid_stack(glayout: GridLayout, b):
    """Grid-stacked (A*B, sub_rows, R) form of a full factor block.

    Cell ``(s, c)`` owns rows ``[row_start[s] + c*sub_rows, +sub_rows)``
    of the combine window; rows past the shard's real count are masked
    to zero (they only ever multiply invalid layout slots), exactly like
    :func:`owner_stack`'s tail masking.
    """
    opart = owner_partition(glayout.slayout)
    r = b.shape[-1]
    b_buf = jnp.pad(b, ((0, glayout.stack_rows - b.shape[0]), (0, 0)))
    slots = jnp.stack([
        jax.lax.dynamic_slice(
            b_buf, (int(s0), 0), (glayout.own_rows_pad, r)
        )
        for s0 in opart.row_start
    ])
    cells = slots.reshape(glayout.n_shards, glayout.sub_rows, r)
    return jnp.where(jnp.asarray(glayout.masks())[:, :, None], cells, 0.0)


def grid_unstack(glayout: GridLayout, stacked):
    """Reassemble the full (n_rows, R) block from grid-stacked slices.

    The once-per-mode-update factor gather of the grid epilogue; under a
    mesh ``stacked`` is device-sharded on its cell axis, so consuming it
    here gathers the O(I_n * R) updated rows once per mode update.
    """
    opart = owner_partition(glayout.slayout)
    r = stacked.shape[-1]
    shards = stacked.reshape(glayout.grid_a, glayout.own_rows_pad, r)
    if (
        glayout.own_rows_pad == opart.own_rows
        and np.all(np.asarray(opart.row_count) == opart.own_rows)
    ):
        return shards.reshape(glayout.grid_a * opart.own_rows, r)[
            : opart.n_rows
        ]
    out = jnp.zeros((glayout.stack_rows, r), stacked.dtype)
    for s in range(glayout.grid_a):
        cnt = int(opart.row_count[s])
        out = jax.lax.dynamic_update_slice(
            out, shards[s, :cnt], (int(opart.row_start[s]), 0)
        )
    return out[: opart.n_rows]


def grid_scatter_wire_bytes(glayout: GridLayout, rank: int,
                            itemsize: int = 4) -> float:
    """Per-device ring wire bytes of one grid combine iteration.

    The all-gather of the (own_rows_pad, R) B window plus the
    reduce-scatter of the combined window, both over the size-``B``
    column axis: ``2 (B-1) * sub_rows * R`` — the arXiv 1708.07401
    bound shape O(I_n * R / A) instead of the 1D O(I_n * R).
    """
    if glayout.grid_b <= 1:
        return 0.0
    return float(
        2 * (glayout.grid_b - 1) * glayout.sub_rows * rank * itemsize
    )


@functools.partial(
    jax.jit,
    static_argnames=("glayout", "eps", "tol", "mesh", "local_strategy",
                     "fused", "plain"),
)
def _grid_combined(glayout: GridLayout, vals_cs, pi_cs, b_own,
                   eps: float, tol: float, mesh: Mesh | None,
                   local_strategy: str, fused: bool, plain: bool):
    """Grid combine core: column-axis all-gather + reduce-scatter.

    Each cell computes a *partial* shard window over its slice of the
    shard's nonzero stream; the column reduce-scatter genuinely sums
    the ``B`` partials (unlike the 1D owner scatter, whose slots are
    disjoint) and hands each cell its owned (sub_rows, R) tile.  The
    B window is rebuilt per inner iteration by an all-gather of the
    column's carry tiles — both collectives move O(I_n * R / A) per
    device.  No row-axis collective exists at all: shard windows never
    overlap on real rows, so at ``B=1`` the whole combine is the
    identity and the result is bitwise the 1D reduce-scatter path's.

    * ``fused=False`` — the grid-stacked combined window (A*B,
      sub_rows, R); ``plain=True`` drops the model weighting (MTTKRP,
      ``b_own`` None).
    * ``fused=True`` — the owner-local MU step: scalar KKT ``pmax``
      over both axes and the multiplicative update on owned tiles;
      returns ``(b_own', viol)``.

    Without a mesh the same schedule runs unrolled on one device,
    summing each column's partials in cell order — bitwise-matching
    the ring reduce-scatter at B <= 2 and numerically matching beyond.
    """
    slayout = glayout.slayout
    bdim = glayout.grid_b
    sub_rows, own_rows_pad = glayout.sub_rows, glayout.own_rows_pad
    own_rows = owner_partition(slayout).own_rows
    lrows = jnp.asarray(glayout.local_rows)
    grbs = jnp.asarray(glayout.grid_rb)
    smask = jnp.asarray(glayout.shard_masks())

    if mesh is None:
        parts = []
        for s in range(glayout.grid_a):
            if plain:
                b_win = None
            else:
                b_shard = b_own[s * bdim : (s + 1) * bdim].reshape(
                    own_rows_pad, -1
                )
                b_win = b_shard[:own_rows]
            wins = [
                _shard_window(slayout, eps, local_strategy,
                              vals_cs[s * bdim + c], pi_cs[s * bdim + c],
                              lrows[s * bdim + c], grbs[s * bdim + c],
                              b_win)
                for c in range(bdim)
            ]
            win = functools.reduce(jnp.add, wins)
            win = jnp.where(smask[s * bdim][:, None], win, 0.0)
            win = jnp.pad(win, ((0, own_rows_pad - own_rows), (0, 0)))
            parts.append(win.reshape(bdim, sub_rows, -1))
        stacked = jnp.concatenate(parts, axis=0)
        if not fused:
            return stacked
        viol = jnp.max(jnp.abs(jnp.minimum(b_own, 1.0 - stacked)))
        return jnp.where(viol > tol, b_own * stacked, b_own), viol

    axes = tuple(mesh.axis_names)

    def local(*args):
        i = 0
        vals_e = args[i][0]; i += 1
        pi_e = args[i][0]; i += 1
        lr = args[i][0]; i += 1
        grb = args[i][0]; i += 1
        b_c = None if plain else args[i][0]
        i += 0 if plain else 1
        mk = args[i][0]  # this cell's shard's (own_rows,) real-row mask

        if plain:
            b_win = None
        else:
            b_full = jax.lax.all_gather(b_c, "col", axis=0, tiled=True)
            b_win = b_full[:own_rows]
        win = _shard_window(slayout, eps, local_strategy,
                            vals_e, pi_e, lr, grb, b_win)
        win = jnp.where(mk[:, None], win, 0.0)
        win = jnp.pad(win, ((0, own_rows_pad - own_rows), (0, 0)))
        owned = jax.lax.psum_scatter(
            win, "col", scatter_dimension=0, tiled=True
        )
        if not fused:
            return owned[None]
        viol = jax.lax.pmax(
            jnp.max(jnp.abs(jnp.minimum(b_c, 1.0 - owned))), axes
        )
        return jnp.where(viol > tol, b_c * owned, b_c)[None], viol

    sharded_args = [vals_cs, pi_cs, lrows, grbs]
    if not plain:
        sharded_args += [b_own]
    sharded_args += [smask]
    in_specs = tuple(
        P(axes, *([None] * (a.ndim - 1))) for a in sharded_args
    )
    out_specs = (
        (P(axes, None, None), P()) if fused else P(axes, None, None)
    )
    fn = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn(*sharded_args)


def phi_grid(glayout: GridLayout, vals_cs, pi_cs, b,
             eps: float = 1e-10, mesh: Mesh | None = None,
             local_strategy: str = "blocked"):
    """Phi^(n) over an ``A x B`` nonzero grid.  Inputs from
    ``expand_to_grid``; the combine is the column-axis all-gather +
    reduce-scatter pair (wire O(I_n * R / A) per device), and the full
    (n_rows, R) result is reassembled here."""
    _validate_grid_mesh(glayout, mesh)
    stacked = _grid_combined(
        glayout, vals_cs, pi_cs, grid_stack(glayout, b),
        float(eps), 0.0, mesh, local_strategy, False, False)
    return grid_unstack(glayout, stacked)


def krao_grid(glayout: GridLayout, vals_cs, kr_cs,
              mesh: Mesh | None = None, local_strategy: str = "blocked"):
    """Grid-partitioned plain Khatri-Rao reduction (MTTKRP): same cell
    machinery as :func:`phi_grid` without the model weighting, so the
    per-iteration all-gather disappears and only the column
    reduce-scatter remains."""
    _validate_grid_mesh(glayout, mesh)
    stacked = _grid_combined(
        glayout, vals_cs, kr_cs, None,
        0.0, 0.0, mesh, local_strategy, False, True)
    return grid_unstack(glayout, stacked)


def phi_mu_grid(glayout: GridLayout, vals_cs, pi_cs, b,
                eps: float = 1e-10, tol: float = 1e-4,
                mesh: Mesh | None = None,
                local_strategy: str = "blocked"):
    """Fused grid MU step returning the full updated factor.

    The combine buffer's masked rows hold B = Phi = 0, contributing
    ``|min(0, 1)| = 0`` to the KKT max and nothing to ``B * Phi`` —
    the same invariant as the 1D padded windows.  The solver's inner
    loop keeps the grid-stacked carry instead via
    :func:`phi_mu_grid_owner`.
    """
    _validate_grid_mesh(glayout, mesh)
    b_own, viol = _grid_combined(
        glayout, vals_cs, pi_cs, grid_stack(glayout, b),
        float(eps), float(tol), mesh, local_strategy, True, False)
    return grid_unstack(glayout, b_own), viol


def phi_grid_owner(glayout: GridLayout, vals_cs, pi_cs, b_own,
                   eps: float = 1e-10, mesh: Mesh | None = None,
                   local_strategy: str = "blocked"):
    """Grid-stacked combined Phi (A*B, sub_rows, R) — no reassembly;
    ``b_own`` is the grid-stacked B (:func:`grid_stack`).  The solver's
    scooch step consumes this form directly."""
    _validate_grid_mesh(glayout, mesh)
    return _grid_combined(
        glayout, vals_cs, pi_cs, b_own,
        float(eps), 0.0, mesh, local_strategy, False, False)


def phi_mu_grid_owner(glayout: GridLayout, vals_cs, pi_cs, b_own,
                      eps: float = 1e-10, tol: float = 1e-4,
                      mesh: Mesh | None = None,
                      local_strategy: str = "blocked"):
    """Grid-partitioned fused MU step: ``(b_own', viol)``, no gather.

    The loop-carry form of the grid epilogue: the solver's inner
    ``lax.while_loop`` carries the (A*B, sub_rows, R) tiles across
    iterations and reassembles the full factor **once** per mode
    update with :func:`grid_unstack` — per-inner-iteration combine
    wire is the column pair's O(I_n * R / A) per device.
    """
    _validate_grid_mesh(glayout, mesh)
    return _grid_combined(
        glayout, vals_cs, pi_cs, b_own,
        float(eps), float(tol), mesh, local_strategy, True, False)


@dataclasses.dataclass(frozen=True)
class DistCPAPRConfig:
    rank: int
    max_outer: int = 10
    max_inner: int = 5
    tol: float = 1e-4
    eps: float = 1e-10
    kappa: float = 1e-2
    kappa_tol: float = 1e-10


def _data_axes(mesh: Mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def shard_mode_views(t: SparseTensor, mesh: Mesh):
    """Per-mode sorted views padded to the data-axis size.

    Padding slots have value 0 and row I_n (reduced into a dump row that is
    sliced off), so they contribute nothing.
    """
    axes = _data_axes(mesh)
    n_shards = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
    out = []
    for n in range(t.ndim):
        mv = sort_mode(t, n)
        nnz = mv.nnz
        pad = (-nnz) % n_shards
        rows = np.concatenate([np.asarray(mv.rows),
                               np.full(pad, t.shape[n], np.int32)])
        idx = np.concatenate([np.asarray(mv.sorted_idx),
                              np.zeros((pad, t.ndim), np.int32)])
        vals = np.concatenate([np.asarray(mv.sorted_vals),
                               np.zeros(pad, np.float32)])
        out.append({"rows": jnp.asarray(rows), "idx": jnp.asarray(idx),
                    "vals": jnp.asarray(vals), "n_rows": t.shape[n]})
    return out


def _mode_update_dist(mesh: Mesh, cfg: DistCPAPRConfig, n: int, n_rows: int,
                      n_modes: int):
    """Build the jitted shard_map per-mode MU solve."""
    axes = _data_axes(mesh)
    nnz_spec = P(axes)  # nonzero stream over data
    f_spec = P(None, "model")  # factor matrices: rank columns over model
    lam_spec = P("model")

    def local_update(rows, idx, vals, factors, lam):
        # factors: tuple of (I_m, R_local); rows/idx/vals: local slices
        a_n = factors[n]

        def pi_local():
            out = jnp.ones((idx.shape[0], a_n.shape[1]), a_n.dtype)
            for m in range(n_modes):
                if m == n:
                    continue
                out = out * factors[m][idx[:, m]]
            return out

        pi = pi_local()

        def phi_of(b):
            s_part = jnp.sum(b[jnp.minimum(rows, n_rows - 1)] * pi, axis=1)
            s = jax.lax.psum(s_part, "model")  # full R dot
            w = jnp.where(vals > 0, vals / jnp.maximum(s, cfg.eps), 0.0)
            contrib = w[:, None] * pi
            phi_loc = jax.ops.segment_sum(
                contrib, rows, num_segments=n_rows + 1,  # +1 dump row for pad
                indices_are_sorted=True,
            )[:n_rows]
            return jax.lax.psum(phi_loc, axes) if axes else phi_loc

        phi0 = phi_of(a_n * lam[None, :])
        s_fix = jnp.where((a_n < cfg.kappa_tol) & (phi0 > 1.0), cfg.kappa, 0.0)
        b0 = (a_n + s_fix) * lam[None, :]

        def cond(state):
            i, _, viol = state
            return (i < cfg.max_inner) & (viol > cfg.tol)

        def body(state):
            i, b, _ = state
            phi = phi_of(b)
            viol_loc = jnp.max(jnp.abs(jnp.minimum(b, 1.0 - phi)))
            viol = jax.lax.pmax(viol_loc, "model")
            if axes:
                viol = jax.lax.pmax(viol, axes)
            b_new = jnp.where(viol > cfg.tol, b * phi, b)
            return (i + 1, b_new, viol)

        i, b, viol = jax.lax.while_loop(
            cond, body, (jnp.int32(0), b0, jnp.asarray(jnp.inf, b0.dtype)))

        lam_new = jnp.sum(b, axis=0)  # (R_local,) — local columns
        a_new = b / jnp.maximum(lam_new, cfg.eps)
        return a_new, lam_new, viol, i

    in_specs = (
        nnz_spec, P(axes, None), nnz_spec,
        tuple(f_spec for _ in range(n_modes)),
        lam_spec,
    )
    out_specs = (f_spec, lam_spec, P(), P())
    fn = jax.shard_map(local_update, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return jax.jit(fn)


def _single_device_mesh(mesh: Mesh) -> Mesh:
    """A 1-device mesh with the same axis names (the warned fallback)."""
    first = np.asarray(list(mesh.devices.flat)[:1])
    return Mesh(first.reshape((1,) * len(mesh.axis_names)), mesh.axis_names)


def _validate_dist_mesh(t: SparseTensor, rank: int, mesh: Mesh) -> Mesh:
    """Validate shardability; fall back to one device with a warning.

    Catches the configurations that otherwise die deep inside XLA with a
    cryptic reshape/sharding error: a model axis that does not divide the
    rank, or more data shards than nonzeros to spread over them.
    """
    problems = []
    model = int(mesh.shape.get("model", 1))
    if model > 1 and rank % model:
        problems.append(f"rank={rank} not divisible by model axis ({model})")
    n_data = int(np.prod([mesh.shape[a] for a in _data_axes(mesh)] or [1]))
    if n_data > max(1, t.nnz):
        problems.append(f"{n_data} data shards exceed nnz={t.nnz}")
    if problems:
        warnings.warn(
            "dist_cpapr_mu: " + "; ".join(problems) +
            "; falling back to a single-device mesh",
            stacklevel=3,
        )
        return _single_device_mesh(mesh)
    return mesh


def dist_cpapr_mu(t: SparseTensor, rank: int, mesh: Mesh,
                  key=None, init: KTensor | None = None,
                  config: DistCPAPRConfig | None = None):
    """Distributed CP-APR MU.  Returns (KTensor, kkt_history)."""
    cfg = config or DistCPAPRConfig(rank=rank)
    mesh = _validate_dist_mesh(t, rank, mesh)
    n_modes = t.ndim
    if init is None:
        key = key if key is not None else jax.random.PRNGKey(0)
        init = random_ktensor(key, t.shape, rank)
    kt = init.normalize()

    views = shard_mode_views(t, mesh)
    axes = _data_axes(mesh)
    r_sh = NamedSharding(mesh, P(None, "model"))
    lam_sh = NamedSharding(mesh, P("model"))
    nnz_sh = NamedSharding(mesh, P(axes))
    idx_sh = NamedSharding(mesh, P(axes, None))

    factors = [jax.device_put(f, r_sh) for f in kt.factors]
    lam = jax.device_put(kt.lam, lam_sh)
    for v in views:
        v["rows"] = jax.device_put(v["rows"], nnz_sh)
        v["idx"] = jax.device_put(v["idx"], idx_sh)
        v["vals"] = jax.device_put(v["vals"], nnz_sh)

    updates = [
        _mode_update_dist(mesh, cfg, n, t.shape[n], n_modes)
        for n in range(n_modes)
    ]

    kkt_hist = []
    for _ in range(cfg.max_outer):
        worst = 0.0
        for n in range(n_modes):
            v = views[n]
            a_new, lam, viol, _ = updates[n](
                v["rows"], v["idx"], v["vals"], tuple(factors), lam)
            factors[n] = a_new
            worst = max(worst, float(viol))
        kkt_hist.append(worst)
        if worst <= cfg.tol:
            break
    return KTensor(lam=lam, factors=tuple(factors)), kkt_hist
