"""CP-APR Multiplicative Update (Chi & Kolda 2012; paper Alg. 1).

Faithful reproduction of the SparTen algorithm:

    for k in 1..k_max:                      # outer
      for n in 1..N:                        # modes
        B <- (A^(n) + S) Lambda             # S removes inadmissible zeros
        for l in 1..l_max:                  # inner MU
          Phi <- (X_(n) (/) max(B Pi, eps)) Pi^T
          if KKT(B, Phi) < tol: break
          B <- B * Phi
        lam <- e^T B;  A^(n) <- B Lambda^-1

The per-mode inner solve is a single jitted ``lax.while_loop`` whose body
is the *fused* ``phi_mu_step`` — Phi, the KKT check, and ``B <- B*Phi``
in one pass (for ``pallas``, one VMEM-resident kernel sweep instead of
three HBM round trips).  The layout expansion of the Pi rows (the gather
into the padded blocked order) is hoisted out of the inner loop: it runs
once per mode update, not once per inner iteration.  The outer sweep is a
host loop (k_max is small and convergence is data-dependent, mirroring
SparTen's driver).

Strategy + blocking policy is the paper's "parallel policy".  It can be:

  * implicit — ``CPAPRConfig.strategy`` with default block sizes;
  * explicit — ``CPAPRConfig.policy`` set to a :class:`PhiPolicy` (its
    block sizes are used; ``strategy`` still picks the algorithm);
  * ``policy="auto"`` — the persistent autotuner
    (:mod:`repro.perf.autotune`) picks a policy per mode, keyed on
    ``(nnz, n_rows, rank, platform)`` and cached across processes in a
    JSON store (default ``~/.cache/repro/autotune.json``; override with
    ``CPAPRConfig.autotuner`` or ``$REPRO_AUTOTUNE_CACHE``), so repeat
    decompositions of same-shaped data pay zero search cost.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import resilience, trace
from .layout import (
    BlockedLayout,
    GridLayout,
    ShardedBlockedLayout,
    ShardedPiGather,
    build_blocked_layout,
    build_grid_layout,
    build_shard_pi_gather,
    choose_grid_shape,
    mode_run_stats,
    owner_partition,
    rebalance_shards,
    shard_blocked_layout,
    shard_stream_cuts,
)
from .phi import (
    _sharded_block_rows,
    expand_to_grid,
    expand_to_layout,
    expand_to_shards,
    expand_vals_to_shards,
    phi_from_rows,
    phi_mu_step,
)
from .pi import pi_rows
from .policy import PhiPolicy, default_policy
from .resilience import STRATEGY_DEMOTION, RecoveryEvent
from .sparse_tensor import KTensor, ModeView, SparseTensor, random_ktensor, sort_mode

__all__ = [
    "CPAPRConfig",
    "CPAPRResult",
    "ModeCutout",
    "SweepOutcome",
    "cpapr_mu",
    "extract_mode_cutout",
    "poisson_loglik",
    "kkt_violation",
    "sweep_step",
]


@dataclasses.dataclass(frozen=True)
class CPAPRConfig:
    rank: int
    max_outer: int = 20
    max_inner: int = 10
    tol: float = 1e-4
    eps: float = 1e-10  # minimum divisor (paper Alg. 2)
    kappa: float = 1e-2  # "scooch" offset for inadmissible zeros
    kappa_tol: float = 1e-10
    strategy: str = "segment"
    # PhiPolicy (explicit blocking), "auto" (persistent autotuner), or None.
    policy: "PhiPolicy | str | None" = None
    # Optional repro.perf.autotune.Autotuner for policy="auto"; a default
    # one (persistent user-level cache) is created when absent.
    autotuner: "object | None" = None
    track_loglik: bool = True
    # strategy="sharded": row blocks split over this jax.sharding.Mesh with
    # one psum Phi combine per inner iteration; None emulates on one device.
    mesh: "object | None" = None
    # Shard count for the emulated sharded path (ignored when mesh is set;
    # defaults to jax.device_count()).
    n_shards: "int | None" = None
    # strategy="grid": explicit (A, B) device grid; None picks per mode
    # from the measured row-distribution skew (choose_grid_shape), where
    # (S, 1) keeps the 1D combine and B > 1 trades it for the
    # O(I_n * R / A) column reduce-scatter.  A grid run's mesh must be a
    # ("row", "col") mesh of matching shape (make_grid_mesh).
    grid_shape: "tuple | None" = None
    # strategy="sharded": compute Pi rows shard-locally from the factor
    # rows each shard touches (ShardedPiGather) instead of materializing
    # the replicated (nnz, R) Pi array — per-device factor bytes drop from
    # O(I * R) to O(touched_rows * R).  The Pi product is recomputed per
    # inner iteration inside the shard (O(nnz/S * R) per device), which
    # beats the one-time replicated O(nnz * R) compute once S >= max_inner
    # and removes the expanded-Pi HBM footprint entirely.
    shard_pi: bool = True
    # Rebalance sharded row-block boundaries by measured nnz skew every
    # this many outer sweeps (0 = static PR-2 sharding).  The base blocked
    # schedule (and the tuned block sizes) stay pinned; only the
    # block->shard assignment moves, so every shard remains a valid
    # blocked schedule.  Changed modes re-jit their update.
    rebalance_every: int = 0
    # strategy="sharded" combine flavour: "psum" (PR-2 all-reduce of the
    # full (buf_rows, R) window, the bitwise reference), "reduce_scatter"
    # (owner-partitioned epilogue: each device keeps only its owned
    # O(I_n*R/S) slice through the inner MU loop and the updated factor
    # rows are gathered once per mode update, async-dispatched so the
    # gather overlaps the next mode's Phi prologue), or "auto" (default:
    # reduce_scatter whenever the mode is actually sharded).
    combine: str = "auto"
    # Reject NaN/negative values, out-of-range indices, and rank <= 0 at
    # the solve boundary (one host pass over the nonzeros).
    validate: bool = True
    # Numerical guard: a finite/positivity reduction on (A_n', lam) after
    # each mode update, its own small dispatch; the sweep reads each
    # mode's KKT scalar on the host as the mode completes, and the guard
    # flags at sweep end (host syncs).  On violation the last-good
    # state is restored and the mode retried — once as-is (transient
    # fault), then with the scooch kappa escalated 10x per further retry
    # (the kappa ladder) — before giving up after guard_retries.
    guard: bool = True
    guard_retries: int = 3
    # Degradation ladder: runtime failures classified by
    # repro.core.resilience.classify_failure demote the failing mode
    # (pallas->blocked->segment, combine reduce_scatter->psum, shard
    # halving + rebalance on OOM), each retried after bounded exponential
    # backoff (demote_backoff * 2^attempt, capped), at most max_demotions
    # rungs per mode invocation.
    demote_backoff: float = 0.05
    max_demotions: int = 4
    # Sweep-level checkpointing: every checkpoint_every outer sweeps the
    # solver state (factors, lam, outer index, histories, per-mode
    # policies + rebalanced shard cuts) is written atomically to
    # checkpoint_path; cpapr_mu(resume_from=...) continues bitwise-
    # identically to an uninterrupted solve.  0 / None disables.
    checkpoint_every: int = 0
    checkpoint_path: "str | None" = None


@dataclasses.dataclass
class CPAPRResult:
    ktensor: KTensor
    n_outer: int
    kkt_history: list  # per outer iter: max violation over modes
    loglik_history: list
    inner_iters: list  # per outer iter: total inner iterations
    converged: bool
    policies: list | None = None  # per-mode PhiPolicy when policy="auto"
    # per rebalance event: {"outer", "mode", "rb_start_old", "rb_start_new",
    # "imbalance_old", "imbalance_new"} (nnz max/mean over shards)
    rebalances: list | None = None
    # RecoveryEvents (numerical-guard restores, degradation-ladder
    # demotions, checkpoint quarantine/resume) — every fault the solver
    # absorbed instead of crashing, in order.
    recoveries: list | None = None


@dataclasses.dataclass
class SweepOutcome:
    """One outer sweep's worth of state, produced by :func:`sweep_step`.

    ``worst``/``inner_total`` are left as device values (scalars for the
    driver's per-tensor updates, ``(J,)`` arrays for the service's batched
    bucket updates); callers that need host floats convert once at sweep
    end.  ``bad`` lists the modes the numerical guard blamed for a
    non-finite sweep (empty when the sweep is clean or unguarded).
    """

    factors: list
    lam: jax.Array
    worst: "jax.Array | None"
    inner_total: "jax.Array | int"
    bad: list


def sweep_step(carry, batch, guard: bool = False,
               counters: "trace.Counters | None" = None) -> SweepOutcome:
    """One CP-APR outer sweep as a pure ``(carry, batch) -> carry`` step.

    ``carry`` is ``(factors, lam)``; ``batch`` is the sweep's worth of
    per-mode subproblems: callables ``(factors, lam) -> (A_n', lam',
    viol, n_inner, ok)`` where ``ok`` is the mode's on-device guard
    boolean (or None when unguarded).  The function owns nothing but the
    mode-ordered application and the guard bookkeeping, so every caller
    runs the exact same sweep body: :func:`cpapr_mu` passes its
    resilience-wrapped mode updates (and its checkpoint/resume path
    re-enters the same loop on the restored carry), while the
    decomposition service (``repro.serve``) passes vmapped padded-bucket
    updates whose ``viol`` is a per-job ``(J,)`` array.

    Guard semantics mirror the driver's: a non-finite KKT scalar aborts
    the sweep early (the remaining modes would consume NaN factors) and
    blames the earliest mode whose completed guard flag tripped; a sweep
    that finishes collects every tripped mode into ``bad``.  The input
    ``factors`` list is never mutated — the outcome carries a fresh list,
    so the caller's sweep-start snapshot stays intact for guard restores.
    The guard's host reads (each mode's KKT scalar, then the flags) count
    as ``host_syncs`` on ``counters``.
    """
    counters = counters if counters is not None else trace.Counters()
    factors, lam = list(carry[0]), carry[1]
    n_modes = len(batch)
    worst = None
    inner_total: "jax.Array | int" = 0
    ok_flags: list = [None] * n_modes
    bad: list = []
    for n, mode_fn in enumerate(batch):
        a_new, lam_new, viol, n_inner, ok = mode_fn(factors, lam)
        if guard and not math.isfinite(counters.read(float, jnp.max(viol))):
            # poisoned KKT scalar: no point finishing the sweep, the
            # remaining modes would consume NaN factors.  Blame an
            # earlier mode whose (complete) guard flag tripped — its bad
            # factors poisoned this one.
            bad = [m for m in range(n) if ok_flags[m] is not None
                   and not counters.read(bool, ok_flags[m])] or [n]
            break
        factors[n] = a_new
        lam = lam_new
        ok_flags[n] = ok
        worst = viol if worst is None else jnp.maximum(worst, viol)
        inner_total = inner_total + n_inner
    if guard and not bad:
        bad = [n for n in range(n_modes) if ok_flags[n] is not None
               and not counters.read(bool, ok_flags[n])]
    return SweepOutcome(factors=factors, lam=lam, worst=worst,
                        inner_total=inner_total, bad=bad)


def mode_pi_gather(
    mv: ModeView, layout, shard_pi: bool = True
) -> "ShardedPiGather | None":
    """The shard-local Pi gather maps for one mode, or None when the mode
    is not sharded (or ``shard_pi`` is off).  Shared by CP-APR and CP-ALS
    so both solver families build identical maps."""
    if shard_pi and isinstance(layout, ShardedBlockedLayout):
        return build_shard_pi_gather(layout, np.asarray(mv.sorted_idx),
                                     mv.mode)
    return None


def hoisted_mode_inputs(mv: ModeView, factors, strategy: str, layout, pig):
    """Per-mode-update hoisted inputs ``(pi, vals_e, pi_e)``.

    One Pi/Khatri-Rao gather + layout expansion per mode update — shared
    by ``cpapr._make_mode_update`` and ``cpals._make_als_mode_update`` so
    the hoisting (and the shard-local-Pi bypass, where no (nnz, R) array
    is ever built) cannot diverge between the two solver families.
    """
    if pig is not None:
        # Shard-local Pi: only the values expansion is hoisted (the
        # factor-row gathers happen per call inside the sharded reduce).
        with jax.named_scope("cpapr.layout"):
            return None, expand_vals_to_shards(layout, mv.sorted_vals), None
    if strategy == "dense":
        # The dense tier never builds Pi or a sorted-stream expansion —
        # its hoisted state is the DenseModeData riding the layout slot.
        return None, None, None
    with jax.named_scope("cpapr.pi"):
        pi = pi_rows(mv.sorted_idx, factors, mv.mode)
    with jax.named_scope("cpapr.layout"):
        if strategy == "grid" and isinstance(layout, GridLayout):
            vals_e, pi_e = expand_to_grid(layout, mv.sorted_vals, pi)
        elif strategy == "sharded" and layout is not None:
            vals_e, pi_e = expand_to_shards(layout, mv.sorted_vals, pi)
        elif strategy in ("blocked", "pallas") and layout is not None:
            vals_e, pi_e = expand_to_layout(layout, mv.sorted_vals, pi)
        else:
            vals_e = pi_e = None
    return pi, vals_e, pi_e


@dataclasses.dataclass(frozen=True)
class ModeCutout:
    """One mode's fused-MU burst problem, cut out of the solver.

    The (rows, vals, Pi, B) quadruple that :func:`_make_mode_update`'s
    inner ``while_loop`` consumes, extracted as a standalone problem (the
    DaCe cutout-tuner shape): a tuner or benchmark can lower and measure
    the MU burst on exactly the arrays the solver would feed it — same
    sorted mode view, same hoisted Pi gather, same scaled factor —
    without paying for a whole decomposition per probe.  Policy-dependent
    layout expansion (``vals_e``/``pi_e``) is deliberately NOT part of
    the cutout: it differs per candidate and the autotuner hoists it per
    probe, exactly as the solver hoists it per mode update.
    """

    mode: int
    rows: jax.Array  # (nnz,) sorted row ids
    vals: jax.Array  # (nnz,) values in sorted order
    pi: jax.Array  # (nnz, R) Khatri-Rao rows (hoisted gather)
    b: jax.Array  # (I_n, R) scaled factor  B = A_n * lam
    n_rows: int
    rank: int
    stats: "object"  # layout.ModeStats of the sorted rows

    @property
    def nnz(self) -> int:
        return int(self.rows.shape[0])


def extract_mode_cutout(t: SparseTensor, kt: KTensor, mode: int) -> ModeCutout:
    """Extract :class:`ModeCutout` for ``mode`` of ``(t, kt)``.

    Reuses the solver's own plumbing — :func:`sort_mode` for the mode
    view, :func:`hoisted_mode_inputs` for the Pi gather (strategy
    ``"segment"``: no layout expansion, Pi itself is policy-independent),
    :func:`mode_run_stats` for the segment-run statistics the heuristic
    and the autotune key consume — so the cutout cannot drift from what
    ``cpapr_mu`` actually runs.
    """
    mv = sort_mode(t, mode)
    pi, _, _ = hoisted_mode_inputs(mv, kt.factors, "segment", None, None)
    b = kt.factors[mode] * kt.lam[None, :]
    stats = mode_run_stats(np.asarray(mv.rows), mv.n_rows)
    return ModeCutout(
        mode=mode,
        rows=mv.rows,
        vals=mv.sorted_vals,
        pi=pi,
        b=b,
        n_rows=mv.n_rows,
        rank=int(kt.rank),
        stats=stats,
    )


def kkt_violation(b: jax.Array, phi: jax.Array) -> jax.Array:
    """max |min(B, 1 - Phi)| — zero iff the KKT conditions hold (C&K Sec. 4)."""
    return jnp.max(jnp.abs(jnp.minimum(b, 1.0 - phi)))


def poisson_loglik(t: SparseTensor, kt: KTensor, eps: float = 1e-10) -> jax.Array:
    """sum_z x_z log m_z - sum(model);  model mass = sum(lam) for normalized kt."""
    prod = jnp.ones((t.values.shape[0], kt.rank), kt.lam.dtype)
    for n, f in enumerate(kt.factors):
        prod = prod * f[t.indices[:, n]]
    # an elementwise f32 sum, not ``prod @ lam``: the TPU runs an f32 matmul
    # in bf16 passes by default, which rounds every model value
    m = jnp.sum(prod * kt.lam[None, :], axis=1)
    return jnp.sum(t.values * jnp.log(jnp.maximum(m, eps))) - jnp.sum(kt.lam)


def resolve_combine(combine: str, strategy: str) -> str:
    """Resolve a (possibly ``"auto"``) combine flavour for one mode.

    ``"auto"`` means reduce-scatter whenever the mode actually runs
    sharded (it is never slower and its per-device epilogue footprint is
    O(I_n * R / S)); non-sharded modes always resolve to ``"psum"`` —
    there is nothing to combine.  The grid family has exactly one
    combine (the column-axis all-gather + reduce-scatter pair, itself a
    reduce-scatter epilogue), so ``"grid"`` always resolves to
    ``"reduce_scatter"`` and an explicit ``"psum"`` is rejected.
    """
    from .distributed import PHI_COMBINES  # deferred: avoids cycle

    if strategy == "grid":
        if combine not in ("auto", "reduce_scatter"):
            raise ValueError(
                f"combine {combine!r} is not supported for strategy='grid'"
                " (the grid combine is always the column reduce-scatter)"
            )
        return "reduce_scatter"
    if strategy != "sharded":
        return "psum"
    if combine == "auto":
        return "reduce_scatter"
    if combine not in PHI_COMBINES:
        raise ValueError(
            f"unknown combine {combine!r}; expected 'auto' or one of "
            f"{PHI_COMBINES}"
        )
    return combine


def effective_mode_combine(combine: str, strategy: str, layout,
                           rank: int, *, itemsize: int = 4) -> str:
    """Per-mode combine after the wire-aware ``"auto"`` demotion.

    ``"auto"`` prefers the reduce-scatter epilogue but consults
    :func:`repro.core.distributed.preferred_combine` on the mode's
    actual sharded layout: a heavily block-skewed split pads the owner
    slots past the psum wire, and auto then keeps the psum combine for
    that mode.  An explicit ``combine="reduce_scatter"`` is never
    demoted.  ``itemsize`` is the factor element width in bytes — the
    wire model scales linearly with it, so an f64 run must thread 8 here
    or both sides of the comparison are 2x off (they used to be: the
    model silently assumed 4-byte elements).
    """
    eff = resolve_combine(combine, strategy)
    if isinstance(layout, GridLayout):
        # The 1D-vs-N-D pick already happened at layout resolution
        # (choose_grid_shape, keyed on the measured skew stats); a built
        # GridLayout has exactly one combine flavour.
        return "reduce_scatter"
    if (
        combine == "auto"
        and eff == "reduce_scatter"
        and isinstance(layout, ShardedBlockedLayout)
    ):
        from .distributed import preferred_combine  # deferred: avoids cycle

        eff = preferred_combine(layout, rank, itemsize=itemsize)
    return eff


# The numerical guard runs as its own jitted dispatch, deliberately kept
# out of the per-mode update programs: fusing the guard reductions into
# the update jit measurably perturbed XLA's CPU schedule (~10% on the
# quick tier), while a separate async dispatch whose boolean is only
# read at sweep end is noise-level.
_jit_guard_ok = jax.jit(resilience.guard_ok)


def _make_owner_mode_update(
    mv: ModeView,
    cfg: CPAPRConfig,
    layout: ShardedBlockedLayout,
    local_strategy: str,
    pig: "ShardedPiGather | None",
):
    """Owner-partitioned per-mode solve (the reduce-scatter epilogue).

    Returns ``(update, gather)``: ``update(factors, lam)`` runs the
    scooch and the fused inner MU loop entirely on the owner-stacked
    (S, own_rows, R) carry — each inner iteration's only combine is a
    reduce-scatter whose per-device output is the owned O(I_n * R / S)
    slice — and returns ``(b_own, viol, n_inner)``.  ``gather(b_own)``
    reassembles the full factor and renormalizes; it is a *separate*
    jitted dispatch (one trace per mode) so the solver can fire it
    asynchronously and let the runtime overlap the factor-row gather
    with the next mode's Phi prologue (the schedule expansion and value
    gathers, which depend on no factor).
    """
    from .distributed import (  # deferred: avoids import cycle
        owner_stack,
        owner_unstack,
        phi_mu_sharded_owner,
        phi_sharded_owner,
    )

    n = mv.mode
    mesh = cfg.mesh
    opart = owner_partition(layout)

    @jax.jit
    def update(factors: tuple, lam: jax.Array):
        a_n = factors[n]
        _, vals_e, pi_e = hoisted_mode_inputs(mv, factors, "sharded",
                                              layout, pig)
        with jax.named_scope("cpapr.layout"):
            a_own = owner_stack(opart, a_n)
        lam_b = lam[None, None, :]

        # --- scooch: lift inadmissible zeros (Alg. 1 line 3), owner-local
        with jax.named_scope("cpapr.phi"):
            phi0_own = phi_sharded_owner(
                layout, opart, vals_e, pi_e, a_own * lam_b,
                eps=cfg.eps, mesh=mesh, local_strategy=local_strategy,
                pi_gather=pig,
                factors=factors if pig is not None else None,
            )
        with jax.named_scope("cpapr.epilogue"):
            s = jnp.where((a_own < cfg.kappa_tol) & (phi0_own > 1.0),
                          cfg.kappa, 0.0)
            b0_own = (a_own + s) * lam_b

        # --- fused inner MU loop (Alg. 1 lines 5-8), owner-stacked carry
        def cond(state):
            i, _, viol = state
            return (i < cfg.max_inner) & (viol > cfg.tol)

        def body(state):
            i, b_own, _ = state
            with jax.named_scope("cpapr.phi"):
                b_new, viol = phi_mu_sharded_owner(
                    layout, opart, vals_e, pi_e, b_own,
                    eps=cfg.eps, tol=cfg.tol, mesh=mesh,
                    local_strategy=local_strategy, pi_gather=pig,
                    factors=factors if pig is not None else None,
                )
            return (i + 1, b_new, viol)

        with jax.named_scope("cpapr.epilogue"):  # the loop's own KKT test
            i, b_own, viol = jax.lax.while_loop(
                cond, body, (jnp.int32(0), b0_own,
                             jnp.asarray(jnp.inf, b0_own.dtype))
            )
        return b_own, viol, i

    @jax.jit
    def gather(b_own: jax.Array):
        # --- renormalize (Alg. 1 lines 9-10) on the reassembled factor.
        # Under a mesh the stacked carry is device-sharded, so this is
        # the once-per-mode-update all-gather of the updated rows.
        with jax.named_scope("cpapr.layout"):
            b = owner_unstack(opart, b_own)
        with jax.named_scope("cpapr.epilogue"):
            lam_new = jnp.sum(b, axis=0)
            safe = jnp.maximum(lam_new, cfg.eps)
            a_new = b / safe
        return a_new, lam_new

    return update, gather


def _make_grid_mode_update(
    mv: ModeView,
    cfg: CPAPRConfig,
    glayout: GridLayout,
    local_strategy: str,
):
    """Grid-partitioned per-mode solve (the N-D combine epilogue).

    The grid analog of :func:`_make_owner_mode_update`: the scooch and
    the fused inner MU loop run on the grid-stacked (A*B, sub_rows, R)
    carry, whose only per-iteration combine is the column-axis
    all-gather + reduce-scatter pair — per-device wire
    ``2 (B-1) * sub_rows * R`` = O(I_n * R / A), the arXiv 1708.07401
    bound shape, instead of the 1D O(I_n * R).  ``gather(b_own)``
    reassembles + renormalizes as a separate async dispatch, exactly
    like the owner path's epilogue.
    """
    from .distributed import (  # deferred: avoids import cycle
        grid_stack,
        grid_unstack,
        phi_grid_owner,
        phi_mu_grid_owner,
    )

    n = mv.mode
    mesh = cfg.mesh

    @jax.jit
    def update(factors: tuple, lam: jax.Array):
        a_n = factors[n]
        _, vals_e, pi_e = hoisted_mode_inputs(mv, factors, "grid",
                                              glayout, None)
        with jax.named_scope("cpapr.layout"):
            a_own = grid_stack(glayout, a_n)
        lam_b = lam[None, None, :]

        # --- scooch: lift inadmissible zeros (Alg. 1 line 3), grid-local
        with jax.named_scope("cpapr.phi"):
            phi0_own = phi_grid_owner(
                glayout, vals_e, pi_e, a_own * lam_b,
                eps=cfg.eps, mesh=mesh, local_strategy=local_strategy,
            )
        with jax.named_scope("cpapr.epilogue"):
            s = jnp.where((a_own < cfg.kappa_tol) & (phi0_own > 1.0),
                          cfg.kappa, 0.0)
            b0_own = (a_own + s) * lam_b

        # --- fused inner MU loop (Alg. 1 lines 5-8), grid-stacked carry
        def cond(state):
            i, _, viol = state
            return (i < cfg.max_inner) & (viol > cfg.tol)

        def body(state):
            i, b_own, _ = state
            with jax.named_scope("cpapr.phi"):
                b_new, viol = phi_mu_grid_owner(
                    glayout, vals_e, pi_e, b_own,
                    eps=cfg.eps, tol=cfg.tol, mesh=mesh,
                    local_strategy=local_strategy,
                )
            return (i + 1, b_new, viol)

        with jax.named_scope("cpapr.epilogue"):  # the loop's own KKT test
            i, b_own, viol = jax.lax.while_loop(
                cond, body, (jnp.int32(0), b0_own,
                             jnp.asarray(jnp.inf, b0_own.dtype))
            )
        return b_own, viol, i

    @jax.jit
    def gather(b_own: jax.Array):
        # --- renormalize (Alg. 1 lines 9-10) on the reassembled factor.
        with jax.named_scope("cpapr.layout"):
            b = grid_unstack(glayout, b_own)
        with jax.named_scope("cpapr.epilogue"):
            lam_new = jnp.sum(b, axis=0)
            safe = jnp.maximum(lam_new, cfg.eps)
            a_new = b / safe
        return a_new, lam_new

    return update, gather


def _make_mode_update(
    mv: ModeView,
    cfg: CPAPRConfig,
    strategy: str,
    layout: "BlockedLayout | ShardedBlockedLayout | None",
    local_strategy: str = "blocked",
    pig: "ShardedPiGather | None" = None,
):
    """Jitted per-mode solve.

    Returns ``(update, gather)``.  On the psum/unsharded paths
    ``update(factors, lam)`` returns ``(A_n', lam', kkt, n_inner)`` and
    ``gather`` is ``None``; when the mode runs sharded with the
    reduce-scatter combine the pair comes from
    :func:`_make_owner_mode_update` instead (owner-stacked carry +
    separate async gather).  With ``pig`` (sharded strategy +
    ``cfg.shard_pi``) the Pi rows are never materialized: each shard
    gathers only the factor rows its nonzeros touch and rebuilds its Pi
    product inside the shard, per inner iteration.
    """

    n = mv.mode
    n_rows = mv.n_rows
    mesh = cfg.mesh if strategy in ("sharded", "grid") else None
    if strategy == "grid" and isinstance(layout, GridLayout):
        return _make_grid_mode_update(mv, cfg, layout, local_strategy)
    if (
        strategy == "sharded"
        and isinstance(layout, ShardedBlockedLayout)
        and effective_mode_combine(
            cfg.combine, strategy, layout, cfg.rank,
            itemsize=jnp.dtype(mv.sorted_vals.dtype).itemsize,
        )
        == "reduce_scatter"
    ):
        return _make_owner_mode_update(mv, cfg, layout, local_strategy, pig)

    if strategy == "dense":
        from repro.kernels.dense import ops as dense_ops
        from .phi import _dense_operands

        dense = layout  # DenseModeData rides the layouts slot

        @jax.jit
        def _dense_update(x, factors: tuple, lam: jax.Array):
            # x arrives as a runtime argument (not a closure) so XLA does
            # not embed the densified tensor as a program literal; the
            # factor-side operands (c, a) are hoisted out of the inner
            # loop — they depend only on the non-target factors.
            a_n = factors[n]
            with jax.named_scope("cpapr.pi"):
                xx, c, a = _dense_operands(dense.with_x(x), factors, a_n)

            # --- scooch: lift inadmissible zeros (Alg. 1 line 3) ----------
            with jax.named_scope("cpapr.phi"):
                phi0 = dense_ops.phi_dense(
                    xx, c, a, a_n * lam[None, :], eps=cfg.eps
                )
            with jax.named_scope("cpapr.epilogue"):
                s = jnp.where((a_n < cfg.kappa_tol) & (phi0 > 1.0),
                              cfg.kappa, 0.0)
                b0 = (a_n + s) * lam[None, :]

            # --- fused inner MU loop (Alg. 1 lines 5-8) -------------------
            def cond(state):
                i, _, viol = state
                return (i < cfg.max_inner) & (viol > cfg.tol)

            def body(state):
                i, b, _ = state
                with jax.named_scope("cpapr.phi"):
                    mu, viol = dense_ops.phi_mu_dense(xx, c, a, b,
                                                      eps=cfg.eps)
                with jax.named_scope("cpapr.epilogue"):
                    b = jnp.where(viol > cfg.tol, mu, b)
                return (i + 1, b, viol)

            with jax.named_scope("cpapr.epilogue"):  # the loop's KKT test
                i, b, viol = jax.lax.while_loop(
                    cond, body,
                    (jnp.int32(0), b0, jnp.asarray(jnp.inf, jnp.float32)),
                )

            # --- renormalize (Alg. 1 lines 9-10) --------------------------
            with jax.named_scope("cpapr.epilogue"):
                lam_new = jnp.sum(b, axis=0)
                safe = jnp.maximum(lam_new, cfg.eps)
                return b / safe, lam_new, viol, i

        def update(factors: tuple, lam: jax.Array):
            return _dense_update(dense.x, tuple(factors), lam)

        return update, None

    # The mode view and a blocked layout enter the program as arguments,
    # not closure constants: XLA embeds a closed-over array in the
    # program, and on TPU in the lane-padded device layout (5.5 GB of
    # program constants for one mode of chicago at its published nnz).
    blk = jax.device_put(layout) if isinstance(layout, BlockedLayout) \
        else None

    @jax.jit
    def _update(mv: ModeView, blk, factors: tuple, lam: jax.Array):
        lay = layout if blk is None else blk
        a_n = factors[n]
        # Hoisted gather + layout expansion: once per mode update, shared
        # by the scooch Phi and every fused inner iteration below.
        pi, vals_e, pi_e = hoisted_mode_inputs(mv, factors, strategy,
                                               lay, pig)
        operands = None
        if strategy == "pallas":
            # The kernel's operands too: the (N, 1) reshapes and the
            # 128-lane Pi, which replaces pi_e for the rest of the update.
            from repro.kernels.phi import ops as phi_ops

            operands = phi_ops.phi_operands(vals_e, pi_e, lay.local_rows,
                                            lay.grid_rb)
            vals_e = pi_e = None

        # --- scooch: lift inadmissible zeros (Alg. 1 line 3) --------------
        with jax.named_scope("cpapr.phi"):
            phi0 = phi_from_rows(
                mv.rows,
                mv.sorted_vals,
                pi,
                a_n * lam[None, :],
                n_rows=n_rows,
                eps=cfg.eps,
                strategy=strategy,
                layout=lay,
                vals_e=vals_e,
                pi_e=pi_e,
                mesh=mesh,
                local_strategy=local_strategy,
                pi_gather=pig,
                factors=factors if pig is not None else None,
                operands=operands,
            )
        with jax.named_scope("cpapr.epilogue"):
            s = jnp.where((a_n < cfg.kappa_tol) & (phi0 > 1.0),
                          cfg.kappa, 0.0)
            b0 = (a_n + s) * lam[None, :]

        # --- fused inner MU loop (Alg. 1 lines 5-8) ------------------------
        def cond(state):
            i, _, viol = state
            return (i < cfg.max_inner) & (viol > cfg.tol)

        def body(state):
            i, b, _ = state
            # phi_mu_step names its own layout and epilogue ops
            with jax.named_scope("cpapr.phi"):
                b_new, viol = phi_mu_step(
                    mv.rows,
                    mv.sorted_vals,
                    pi,
                    b,
                    n_rows=n_rows,
                    eps=cfg.eps,
                    tol=cfg.tol,
                    strategy=strategy,
                    layout=lay,
                    vals_e=vals_e,
                    pi_e=pi_e,
                    mesh=mesh,
                    local_strategy=local_strategy,
                    pi_gather=pig,
                    factors=factors if pig is not None else None,
                    operands=operands,
                )
            return (i + 1, b_new, viol)

        with jax.named_scope("cpapr.epilogue"):  # the loop's own KKT test
            i, b, viol = jax.lax.while_loop(
                cond, body,
                (jnp.int32(0), b0, jnp.asarray(jnp.inf, b0.dtype)),
            )

        # --- renormalize (Alg. 1 lines 9-10) -------------------------------
        with jax.named_scope("cpapr.epilogue"):
            lam_new = jnp.sum(b, axis=0)
            safe = jnp.maximum(lam_new, cfg.eps)
            a_new = b / safe
        return a_new, lam_new, viol, i

    # update(factors, lam); ``update.func.lower(*update.args, factors, lam)``
    # lowers the program of one mode update
    return partial(_update, mv, blk), None


def _effective_shard_count(mesh, n_shards) -> int:
    if mesh is not None:
        from .distributed import mesh_device_count  # deferred: avoids cycle

        return mesh_device_count(mesh)
    if n_shards is not None:
        return int(n_shards)
    return int(jax.device_count())


def _shard_mode_layout(mv: ModeView, pol: PhiPolicy, n_shards: int):
    """(strategy, layout) for one sharded mode — warn + unsharded fallback
    (preserving the policy's blocked/pallas flavour) when the blocking
    leaves fewer row blocks than shards."""
    base = build_blocked_layout(
        np.asarray(mv.rows), mv.n_rows, pol.block_nnz, pol.block_rows
    )
    if n_shards > base.n_row_blocks:
        import warnings

        local = pol.strategy if pol.strategy in ("blocked", "pallas") \
            else "blocked"
        warnings.warn(
            f"sharded CP-APR mode {mv.mode}: {n_shards} shards requested but "
            f"the layout has only {base.n_row_blocks} row blocks; falling "
            f"back to the single-device {local} path for this mode",
            stacklevel=4,
        )
        return local, base
    return "sharded", shard_blocked_layout(base, n_shards)


def _grid_mode_layout(mv: ModeView, pol: PhiPolicy, n_shards: int,
                      grid_shape, rank: int, stats=None):
    """(strategy, layout, grid_shape) for one grid mode.

    ``grid_shape=None`` picks the (A, B) split per mode from the
    measured skew (:func:`choose_grid_shape` — hub modes take any wire
    win, uniform modes need a decisive one, else the degenerate (S, 1)
    keeps the 1D combine bitwise).  Falls back to the single-device
    blocked/pallas path — mirroring :func:`_shard_mode_layout` — when
    the blocking cannot honour the grid.
    """
    import warnings

    base = build_blocked_layout(
        np.asarray(mv.rows), mv.n_rows, pol.block_nnz, pol.block_rows
    )
    shape = grid_shape
    if shape is None:
        shape = choose_grid_shape(
            mv.n_rows, pol.block_rows, rank, n_shards, stats=stats,
            itemsize=jnp.dtype(mv.sorted_vals.dtype).itemsize,
        )
    a, b = int(shape[0]), int(shape[1])
    local = pol.strategy if pol.strategy in ("blocked", "pallas") \
        else "blocked"
    if a > base.n_row_blocks:
        warnings.warn(
            f"grid CP-APR mode {mv.mode}: row axis {a} requested but the "
            f"layout has only {base.n_row_blocks} row blocks; falling "
            f"back to the single-device {local} path for this mode",
            stacklevel=4,
        )
        return local, base, None
    try:
        return "grid", build_grid_layout(base, (a, b)), (a, b)
    except ValueError as e:
        warnings.warn(
            f"grid CP-APR mode {mv.mode}: cannot honour grid {a}x{b} "
            f"({e}); falling back to the single-device {local} path for "
            f"this mode",
            stacklevel=4,
        )
        return local, base, None


def _mode_row_width(factors, n: int) -> int:
    """Cells per mode-``n`` row: the product of the other mode sizes.

    This is the denominator of the per-mode fill fraction
    (``nnz / (n_rows * row_width)``) that keys the dense-tier cut.
    """
    w = 1
    for m, f in enumerate(factors):
        if m != n:
            w *= int(f.shape[0])
    return w


def _dense_mode_data(mv: ModeView, factors):
    """Densify one mode into its :class:`repro.core.dense.DenseModeData`
    (the dense tier's analog of a blocked layout); shape comes from the
    factor row counts."""
    from .dense import build_dense_mode  # deferred: keeps import DAG flat

    shape = tuple(int(f.shape[0]) for f in factors)
    return build_dense_mode(
        np.asarray(mv.sorted_idx), np.asarray(mv.sorted_vals), shape, mv.mode
    )


def resolve_mode_policies(
    mvs: Sequence[ModeView],
    factors: Sequence[jax.Array],
    lam: jax.Array,
    *,
    rank: int,
    strategy: str,
    policy: "PhiPolicy | str | None" = None,
    autotuner: "object | None" = None,
    mesh: "object | None" = None,
    n_shards: "int | None" = None,
    combine: str = "auto",
    grid_shape: "tuple | None" = None,
) -> tuple:
    """Per-mode (strategy, layout, policy, local_strategy) lists.

    The shared strategy resolver for every solver over the Phi/MTTKRP
    reduction family: CP-APR (:func:`cpapr_mu`) and CP-ALS
    (``repro.core.cpals.cp_als``) both route through it, so
    ``policy="auto"`` / explicit :class:`PhiPolicy` / sharded layouts
    behave identically across the paper's two algorithm families.
    ``combine`` (the sharded psum / reduce-scatter epilogue choice, or
    ``"auto"``) is folded into the autotuner's sharded cache keys.  The
    keys follow the *requested* resolution (``"auto"`` keys as
    reduce-scatter): the tuned sub-problems are shard-local fused MU
    steps, which no combine flavour changes, so the later per-mode
    wire-aware demotion (:func:`effective_mode_combine`, which needs the
    built layout) deliberately does not re-key — the dimension exists so
    future combine-*sensitive* probes stay separable.
    """
    n_modes = len(mvs)
    strategies = [strategy] * n_modes
    layouts: list = [None] * n_modes
    policies: list = [None] * n_modes
    locals_: list = ["blocked"] * n_modes
    sharded = strategy == "sharded"
    grid = strategy == "grid"
    eff_combine = resolve_combine(combine, strategy)
    eff_shards = (
        _effective_shard_count(mesh, n_shards) if sharded or grid else 1
    )
    # the per-mode (A, B) pick: explicit grid_shape pins it; None defers
    # to choose_grid_shape on the measured mode skew
    grid_shapes: list = [None] * n_modes

    def _pick_grid_shape(mv, stats_n):
        if grid_shape is not None:
            return tuple(int(x) for x in grid_shape)
        return choose_grid_shape(
            mv.n_rows, _sharded_block_rows(mv.n_rows, eff_shards), rank,
            eff_shards, stats=stats_n,
            itemsize=jnp.dtype(mv.sorted_vals.dtype).itemsize,
        )

    if policy == "auto":
        from repro.perf.autotune import Autotuner  # deferred: avoids cycle

        tuner = autotuner if autotuner is not None else Autotuner()
        for n in range(n_modes):
            mv = mvs[n]
            pi_n = pi_rows(mv.sorted_idx, tuple(factors), n)
            b_n = factors[n] * lam[None, :]
            if grid:
                # whole-mode skew stats pick the (A, B) split, which then
                # keys the sharded sub-problem tuning (/grid=AxB)
                stats_n = mode_run_stats(
                    np.asarray(mv.rows), mv.n_rows,
                    row_width=_mode_row_width(factors, n),
                )
                grid_shapes[n] = _pick_grid_shape(mv, stats_n)
                pol, _ = tuner.policy_for_sharded_mode(
                    mv.rows, mv.sorted_vals, pi_n, b_n,
                    n_rows=mv.n_rows, rank=rank,
                    n_shards=int(grid_shapes[n][0]),
                    combine=eff_combine, grid=grid_shapes[n],
                )
            elif sharded:
                # per-shard stats are computed on the shard slices inside
                # policy_for_sharded_mode; no whole-mode pass needed here
                pol, _ = tuner.policy_for_sharded_mode(
                    mv.rows, mv.sorted_vals, pi_n, b_n,
                    n_rows=mv.n_rows, rank=rank, n_shards=eff_shards,
                    combine=eff_combine,
                )
            else:
                # Segment-run stats computed once per mode (host numpy,
                # same cost model as the layout sort) — they key the v2
                # autotune cache so equal-size modes with different
                # distributions stop sharing a winner.  row_width adds
                # the fill fraction (the /fill key dimension), which
                # arms the dense-tier cut in the tuner's heuristic.
                stats_n = mode_run_stats(
                    np.asarray(mv.rows), mv.n_rows,
                    row_width=_mode_row_width(factors, n),
                )
                pol = tuner.policy_for_mode(
                    mv.rows, mv.sorted_vals, pi_n, b_n,
                    n_rows=mv.n_rows, rank=rank, stats=stats_n,
                )
            policies[n] = pol
            if pol.strategy == "dense":
                # Per-mode hybrid: a near-dense mode runs the matrix-free
                # dense tier (always unsharded — its whole densified mode
                # fits one device by construction) while the other modes
                # keep their sparse winners.
                strategies[n] = "dense"
                layouts[n] = _dense_mode_data(mv, factors)
            elif pol.strategy in ("blocked", "pallas"):
                locals_[n] = pol.strategy
                if grid:
                    strategies[n], layouts[n], grid_shapes[n] = \
                        _grid_mode_layout(mv, pol, eff_shards,
                                          grid_shapes[n], rank)
                elif sharded:
                    strategies[n], layouts[n] = _shard_mode_layout(
                        mv, pol, eff_shards
                    )
                else:
                    strategies[n] = pol.strategy
                    layouts[n] = build_blocked_layout(
                        np.asarray(mv.rows), mv.n_rows,
                        pol.block_nnz, pol.block_rows,
                    )
            else:  # an unblocked winner has nothing to shard
                strategies[n] = pol.strategy
        return strategies, layouts, policies, locals_

    if sharded or grid:
        for n in range(n_modes):
            mv = mvs[n]
            if isinstance(policy, PhiPolicy):
                pol = policy
            else:
                pol = PhiPolicy(
                    strategy="blocked",
                    block_nnz=256,
                    block_rows=_sharded_block_rows(mv.n_rows, eff_shards),
                )
            policies[n] = pol
            if pol.strategy in ("blocked", "pallas"):
                locals_[n] = pol.strategy
                if grid:
                    stats_n = mode_run_stats(np.asarray(mv.rows),
                                             mv.n_rows)
                    strategies[n], layouts[n], grid_shapes[n] = \
                        _grid_mode_layout(mv, pol, eff_shards,
                                          _pick_grid_shape(mv, stats_n),
                                          rank)
                else:
                    strategies[n], layouts[n] = _shard_mode_layout(
                        mv, pol, eff_shards
                    )
            else:  # an unblocked user policy has nothing to shard
                strategies[n] = pol.strategy
        return strategies, layouts, policies, locals_

    if strategy == "dense":
        pol = policy if isinstance(policy, PhiPolicy) \
            else PhiPolicy(strategy="dense", block_nnz=8)
        for n in range(n_modes):
            policies[n] = pol
            layouts[n] = _dense_mode_data(mvs[n], factors)
        return strategies, layouts, policies, locals_

    if strategy in ("blocked", "pallas"):
        pol = policy if isinstance(policy, PhiPolicy) else default_policy(rank)
        for n in range(n_modes):
            policies[n] = pol
            layouts[n] = build_blocked_layout(
                np.asarray(mvs[n].rows), mvs[n].n_rows, pol.block_nnz, pol.block_rows
            )
    return strategies, layouts, policies, locals_


def _resolve_mode_policies(
    cfg: CPAPRConfig,
    mvs: Sequence[ModeView],
    factors: Sequence[jax.Array],
    lam: jax.Array,
) -> tuple:
    """Config-object wrapper over :func:`resolve_mode_policies`."""
    return resolve_mode_policies(
        mvs, factors, lam,
        rank=cfg.rank,
        strategy=cfg.strategy,
        policy=cfg.policy,
        autotuner=cfg.autotuner,
        mesh=cfg.mesh,
        n_shards=cfg.n_shards,
        combine=cfg.combine,
        grid_shape=cfg.grid_shape,
    )


def _ckpt_fingerprint(t: SparseTensor, cfg: CPAPRConfig) -> str:
    """Problem/config fingerprint a checkpoint must match to be resumed
    (the fields that change the iteration trajectory)."""
    return resilience.config_fingerprint({
        "shape": [int(s) for s in t.shape],
        "nnz": int(np.asarray(t.values).shape[0]),
        "rank": int(cfg.rank),
        "max_inner": int(cfg.max_inner),
        "tol": float(cfg.tol),
        "eps": float(cfg.eps),
        "kappa": float(cfg.kappa),
        "kappa_tol": float(cfg.kappa_tol),
        "strategy": cfg.strategy,
        "combine": cfg.combine,
        "shard_pi": bool(cfg.shard_pi),
        "grid_shape": [int(x) for x in cfg.grid_shape]
        if cfg.grid_shape is not None else None,
    })


def _restore_mode_layouts(mvs, strategies, policies, mode_shards, rb_bounds,
                          shape=None, mode_grids=None):
    """Rebuild per-mode layouts exactly as checkpointed: tuned block
    sizes from the saved policies, rebalanced shard assignments from the
    saved row-block cuts (``shard_blocked_layout(bounds=...)``) — the
    resumed schedule is identical to the killed run's, so the solve
    continues bitwise.  ``shape`` (the full tensor shape) re-densifies
    any dense-tier modes; ``mode_grids`` (per-mode ``[A, B]`` or None)
    rebuilds any grid modes on their checkpointed device grid."""
    layouts: list = [None] * len(mvs)
    for n, mv in enumerate(mvs):
        pol = policies[n]
        if strategies[n] == "grid":
            g = (mode_grids or [None] * len(mvs))[n]
            if g is None:
                raise resilience.CheckpointError(
                    f"checkpoint names strategy 'grid' for mode {n} but "
                    f"records no grid shape (mode_grids missing)"
                )
            base = build_blocked_layout(
                np.asarray(mv.rows), mv.n_rows, pol.block_nnz, pol.block_rows
            )
            layouts[n] = build_grid_layout(
                base, (int(g[0]), int(g[1])), bounds=rb_bounds.get(n)
            )
        elif strategies[n] == "sharded":
            base = build_blocked_layout(
                np.asarray(mv.rows), mv.n_rows, pol.block_nnz, pol.block_rows
            )
            layouts[n] = shard_blocked_layout(
                base, mode_shards[n], bounds=rb_bounds.get(n)
            )
        elif strategies[n] == "dense":
            from .dense import build_dense_mode  # deferred

            layouts[n] = build_dense_mode(
                np.asarray(mv.sorted_idx), np.asarray(mv.sorted_vals),
                tuple(shape), n,
            )
        elif strategies[n] in ("blocked", "pallas") and pol is not None:
            layouts[n] = build_blocked_layout(
                np.asarray(mv.rows), mv.n_rows, pol.block_nnz, pol.block_rows
            )
    return layouts


def cpapr_mu(
    t: SparseTensor,
    rank: int,
    key: jax.Array | None = None,
    init: KTensor | None = None,
    config: CPAPRConfig | None = None,
    mode_views: Sequence[ModeView] | None = None,
    resume_from: str | None = None,
) -> CPAPRResult:
    """Run CP-APR MU.  Returns the fitted KTensor + convergence stats.

    ``resume_from`` continues a checkpointed solve (see
    ``CPAPRConfig.checkpoint_every`` / ``checkpoint_path``) bitwise-
    identically to the uninterrupted run; a corrupt or mismatched
    checkpoint is quarantined (recorded in ``result.recoveries``) and the
    solve starts fresh instead of dying.

    The call is the host span ``cpapr.solve``; at its end it carries the
    attributes ``modes``, ``sweeps`` and ``inner`` (the sweeps and inner
    iterations this call ran) and ``host_syncs`` (device values the sweep
    loop read on the host).  Inside it: ``cpapr.prepare`` (with
    ``cpapr.validate``, ``cpapr.sort``, ``cpapr.policy``, ``cpapr.build``),
    then per sweep ``cpapr.sweep`` (with ``cpapr.mode_update`` and
    ``cpapr.loglik``), and ``cpapr.rebalance``, ``cpapr.checkpoint`` and
    ``cpapr.recover`` where those paths run.
    """
    counters = trace.Counters()
    with trace.counted("cpapr.solve", counters, modes=t.ndim):
        return _cpapr_mu(t, rank, key, init, config, mode_views,
                         resume_from, counters)


def _cpapr_mu(t, rank, key, init, config, mode_views, resume_from,
              counters: trace.Counters) -> CPAPRResult:
    cfg = config or CPAPRConfig(rank=rank)
    assert cfg.rank == rank
    n_modes = t.ndim
    with trace.span("cpapr.prepare"):
        if cfg.validate:
            with trace.span("cpapr.validate"):
                resilience.validate_decomposition_inputs(t, rank,
                                                         where="cpapr_mu")
        if init is None:
            key = key if key is not None else jax.random.PRNGKey(0)
            init = random_ktensor(key, t.shape, rank)
        kt = init.normalize()
        factors = list(kt.factors)
        lam = kt.lam

        if mode_views is not None:
            mvs = list(mode_views)
        else:
            mvs = []
            for n in range(n_modes):
                with trace.span("cpapr.sort", mode=n):
                    mvs.append(sort_mode(t, n))

        recoveries: list = []
        fp = _ckpt_fingerprint(t, cfg)
        resume_state = None
        if resume_from is not None:
            try:
                with trace.span("cpapr.recover"):
                    resume_state = resilience.load_checkpoint(resume_from)
                if resume_state.get("fingerprint") != fp:
                    raise resilience.CheckpointError(
                        f"{resume_from}: checkpoint fingerprint "
                        f"{resume_state.get('fingerprint')!r} does not "
                        f"match this problem/config ({fp!r})"
                    )
            except resilience.CheckpointError as e:
                qpath = resilience.quarantine_checkpoint(resume_from)
                recoveries.append(RecoveryEvent(
                    "checkpoint_corrupt", outer=0,
                    detail={"error": str(e), "quarantined": qpath},
                ))
                resume_state = None

        start_outer = 0
        kkt_hist: list = []
        ll_hist: list = []
        inner_hist: list = []
        rebalances: list = []
        if resume_state is None:
            with trace.span("cpapr.policy"):
                strategies, layouts, policies, locals_ = \
                    _resolve_mode_policies(cfg, mvs, factors, lam)
            # per-mode effective config: the kappa ladder and the
            # combine demotion mutate these without touching the cfg
            mode_cfgs = [cfg] * n_modes
        else:
            start_outer = int(resume_state["outer"])
            factors = [jnp.asarray(f) for f in resume_state["factors"]]
            lam = jnp.asarray(resume_state["lam"])
            strategies = list(resume_state["strategies"])
            locals_ = list(resume_state["locals"])
            policies = [PhiPolicy(**p) if p else None
                        for p in resume_state["policies"]]
            rb_bounds = {int(k): v for k, v in
                         resume_state.get("rb_bounds", {}).items()}
            with trace.span("cpapr.policy"):
                layouts = _restore_mode_layouts(
                    mvs, strategies, policies,
                    list(resume_state["mode_shards"]), rb_bounds,
                    shape=t.shape, mode_grids=resume_state.get("mode_grids"),
                )
            # restore the per-mode kappa ladder + combine demotions, so
            # the resumed trajectory matches the killed run even
            # mid-recovery
            mode_cfgs = [
                dataclasses.replace(cfg, kappa=kap, combine=comb)
                for kap, comb in zip(resume_state["kappas"],
                                     resume_state["combines"])
            ]
            kkt_hist = list(resume_state["kkt_history"])
            ll_hist = list(resume_state["loglik_history"])
            inner_hist = list(resume_state["inner_iters"])
            rebalances = list(resume_state.get("rebalances") or [])
            recoveries.extend(RecoveryEvent(**r) for r in
                              resume_state.get("recoveries", []))
            recoveries.append(RecoveryEvent(
                "resume", outer=start_outer,
                detail={"path": resume_from},
            ))

        with trace.span("cpapr.build"):
            pigs = [mode_pi_gather(mvs[n], layouts[n], cfg.shard_pi)
                    for n in range(n_modes)]
            updates, gathers = [], []
            for n in range(n_modes):
                upd, gat = _make_mode_update(mvs[n], mode_cfgs[n],
                                             strategies[n], layouts[n],
                                             locals_[n], pig=pigs[n])
                updates.append(upd)
                gathers.append(gat)

    def _rebuild(n: int) -> None:
        """Re-derive mode ``n``'s gather maps + jitted update from its
        current (layout, strategy, per-mode config)."""
        pigs[n] = mode_pi_gather(mvs[n], layouts[n], cfg.shard_pi)
        updates[n], gathers[n] = _make_mode_update(
            mvs[n], mode_cfgs[n], strategies[n], layouts[n], locals_[n],
            pig=pigs[n],
        )

    def _ctx(outer: int, n: int) -> dict:
        sl = layouts[n]
        ctx = {
            "outer": outer,
            "mode": n,
            "strategy": strategies[n],
            "local": locals_[n],
            "combine": mode_cfgs[n].combine,
            "n_shards": int(sl.n_shards)
            if isinstance(sl, (ShardedBlockedLayout, GridLayout)) else 1,
        }
        if isinstance(sl, GridLayout):
            ctx["grid"] = (int(sl.grid_a), int(sl.grid_b))
        return ctx

    def _invoke(outer: int, n: int, factors, lam):
        """One raw mode-update attempt (fault hooks + update + gather)."""
        ctx = _ctx(outer, n)
        if resilience.have_hooks():
            resilience.fire_mode_hooks(ctx)
        if gathers[n] is None:
            a_new, lam_new, viol, n_inner = updates[n](tuple(factors), lam)
        else:
            # Owner-partitioned mode: the inner loop returns the
            # owner-stacked carry; the factor-row gather is its own
            # async dispatch, so it overlaps the host-side dispatch
            # (and factor-independent prologue) of the next mode.
            b_own, viol, n_inner = updates[n](tuple(factors), lam)
            a_new, lam_new = gathers[n](b_own)
        if resilience.have_post_update_hooks():
            a_new, lam_new = resilience.apply_post_update_hooks(
                ctx, a_new, lam_new
            )
        ok = None
        if cfg.guard:
            # The guard is its own tiny async dispatch *outside* the
            # update program (embedding it in the update's jit measurably
            # perturbs XLA's schedule): the compiled update is identical
            # with the guard on or off, the boolean stays on device until
            # the sweep-end read, and — running after the hooks — it also
            # sees injected host-level corruption.
            ok = _jit_guard_ok(jnp.asarray(a_new), jnp.asarray(lam_new))
        return a_new, lam_new, viol, n_inner, ok

    def _demote(n: int, kind: str, exc: BaseException) -> "dict | None":
        """Take one degradation-ladder rung for mode ``n``; returns the
        recovery detail, or None when the ladder is exhausted (the error
        then propagates)."""
        detail = {"error": f"{type(exc).__name__}: {exc}"[:200]}

        def _grid_to_1d(sl: GridLayout) -> str:
            """The grid->1D rung: keep the row-shard split, drop the
            column axis (STRATEGY_DEMOTION['grid']); a degenerate
            single-row-shard grid leaves the distributed family for the
            single-device local kernel instead.  Returns the action
            label for the recovery record."""
            if sl.grid_a > 1:
                strategies[n], layouts[n] = "sharded", sl.slayout
                if mode_cfgs[n].mesh is not None:
                    from .distributed import make_phi_mesh  # deferred

                    mode_cfgs[n] = dataclasses.replace(
                        mode_cfgs[n], mesh=make_phi_mesh(sl.grid_a)
                    )
                return (f"grid {sl.grid_a}x{sl.grid_b}->"
                        f"{STRATEGY_DEMOTION['grid']}@{sl.grid_a}")
            local = locals_[n] if locals_[n] in ("blocked", "pallas") \
                else "blocked"
            strategies[n], layouts[n] = local, sl.slayout.base
            return f"grid 1x{sl.grid_b}->single-device {local}"

        if kind in ("kernel", "policy"):
            if strategies[n] == "grid" and isinstance(layouts[n], GridLayout):
                if locals_[n] == "pallas":
                    locals_[n] = "blocked"
                    detail["action"] = "local pallas->blocked"
                else:
                    detail["action"] = _grid_to_1d(layouts[n])
            elif strategies[n] == "sharded":
                if locals_[n] == "pallas":
                    locals_[n] = "blocked"
                    detail["action"] = "local pallas->blocked"
                else:
                    # the shard-local blocked kernel failed too: leave
                    # the sharded family for the streaming segment path
                    strategies[n], layouts[n] = "segment", None
                    locals_[n] = "blocked"
                    detail["action"] = "sharded->segment"
            elif strategies[n] in STRATEGY_DEMOTION:
                new = STRATEGY_DEMOTION[strategies[n]]
                detail["action"] = f"{strategies[n]}->{new}"
                strategies[n] = new
                if new not in ("blocked", "pallas"):
                    layouts[n] = None
            elif kind == "policy" and strategies[n] != "segment":
                # e.g. a poisoned autotune entry naming a strategy that
                # does not exist: fall to the always-available baseline
                detail["action"] = f"{strategies[n]}->segment"
                strategies[n], layouts[n] = "segment", None
            else:
                return None
        elif kind == "fingerprint":
            if strategies[n] != "sharded" or mode_cfgs[n].combine == "psum":
                return None
            detail["action"] = f"combine {mode_cfgs[n].combine}->psum"
            mode_cfgs[n] = dataclasses.replace(mode_cfgs[n], combine="psum")
        elif kind == "oom":
            sl = layouts[n]
            if isinstance(sl, GridLayout):
                # first OOM rung for grid: drop to the 1D row split (the
                # replicated B window shrinks from own_rows_pad to the
                # owned slice); further OOMs then halve the shard count
                # through the existing sharded rungs
                detail["action"] = _grid_to_1d(sl)
                return detail
            if not isinstance(sl, ShardedBlockedLayout):
                return None
            new_s = sl.n_shards // 2
            if new_s <= 1:
                local = locals_[n] if locals_[n] in ("blocked", "pallas") \
                    else "blocked"
                detail["action"] = (
                    f"sharded@{sl.n_shards}->single-device {local}"
                )
                strategies[n], layouts[n] = local, sl.base
            else:
                detail["action"] = f"shards {sl.n_shards}->{new_s}"
                layouts[n] = rebalance_shards(
                    shard_blocked_layout(sl.base, new_s)
                )
                if mode_cfgs[n].mesh is not None:
                    from .distributed import make_phi_mesh  # deferred

                    mode_cfgs[n] = dataclasses.replace(
                        mode_cfgs[n], mesh=make_phi_mesh(new_s)
                    )
        else:
            return None
        return detail

    def _run_mode(outer: int, n: int, factors, lam):
        """Mode update under the degradation ladder: classified runtime
        failures demote one rung and retry with bounded backoff."""
        for attempt in range(cfg.max_demotions + 1):
            try:
                # the span holds the dispatch, and the tracing and
                # lowering of a mode update's first call
                with trace.span("cpapr.mode_update", mode=n,
                                strategy=strategies[n]):
                    return _invoke(outer, n, factors, lam)
            except Exception as e:
                kind = resilience.classify_failure(e)
                if kind is None or attempt >= cfg.max_demotions:
                    raise
                with trace.span("cpapr.recover"):
                    detail = _demote(n, kind, e)
                    if detail is None:
                        raise
                    recoveries.append(RecoveryEvent(
                        f"demote_{kind}", outer=outer, mode=n,
                        attempt=attempt, detail=detail,
                    ))
                    resilience.backoff_sleep(attempt, cfg.demote_backoff)
                    _rebuild(n)
        raise AssertionError("unreachable")  # pragma: no cover

    def _escalate_kappa(n: int) -> None:
        mode_cfgs[n] = dataclasses.replace(
            mode_cfgs[n], kappa=min(mode_cfgs[n].kappa * 10.0, 1.0)
        )

    def _nnz_imbalance(sl: ShardedBlockedLayout) -> float:
        mean = float(sl.shard_nnz.mean())
        return float(sl.shard_nnz.max()) / max(mean, 1.0)

    def _rebalance_modes(outer: int, events: list) -> None:
        """nnz-weighted boundary re-split of every sharded mode.

        Only the block->shard assignment moves — the base schedule (and
        the tuned block sizes) stay pinned, so every shard remains a
        valid blocked schedule.  Modes whose boundaries changed rebuild
        their Pi gather maps and re-jit their update.

        With a *non-measuring* autotuner configured, the new shard
        sub-problems are also re-keyed under assignment-aware cache keys
        so future cold starts of this assignment hit.  A measuring tuner
        is deliberately skipped: grid-searching timed probes inside the
        solve would stall its sweeps.
        """
        tuner = cfg.autotuner if cfg.policy == "auto" else None
        rekey = tuner is not None and not getattr(tuner, "measure", True)
        for n in range(n_modes):
            sl = layouts[n]
            if not isinstance(sl, ShardedBlockedLayout):
                continue
            new_sl = rebalance_shards(sl)
            if np.array_equal(new_sl.rb_start, sl.rb_start):
                continue
            if rekey:
                # thread the new assignment through the autotune keyspace;
                # a non-measuring tuner never probes, so pi=None — no
                # (nnz, R) array is materialized
                mv = mvs[n]
                cuts = shard_stream_cuts(new_sl, np.asarray(mv.rows))
                tuner.policy_for_sharded_mode(
                    mv.rows, mv.sorted_vals, None,
                    factors[n] * lam[None, :],
                    n_rows=mv.n_rows, rank=cfg.rank,
                    n_shards=new_sl.n_shards, cuts=cuts,
                    combine=resolve_combine(cfg.combine, strategies[n]),
                )
            events.append({
                "outer": outer,
                "mode": n,
                "rb_start_old": [int(x) for x in sl.rb_start],
                "rb_start_new": [int(x) for x in new_sl.rb_start],
                "imbalance_old": round(_nnz_imbalance(sl), 4),
                "imbalance_new": round(_nnz_imbalance(new_sl), 4),
            })
            layouts[n] = new_sl
            _rebuild(n)

    def _write_checkpoint(n_outer: int) -> None:
        rb_bounds: dict = {}
        shards = []
        grids: list = []
        for n in range(n_modes):
            sl = layouts[n]
            if isinstance(sl, GridLayout):
                # persist the 1D row-shard cuts of the wrapped layout plus
                # the (A, B) device grid, so resume rebuilds the exact
                # cell schedule (build_grid_layout is deterministic in
                # (base, shape, bounds))
                rb_bounds[str(n)] = (
                    [int(x) for x in sl.slayout.rb_start]
                    + [int(sl.slayout.base.n_row_blocks)]
                )
                shards.append(int(sl.grid_a))
                grids.append([int(sl.grid_a), int(sl.grid_b)])
                continue
            grids.append(None)
            if isinstance(sl, ShardedBlockedLayout):
                rb_bounds[str(n)] = (
                    [int(x) for x in sl.rb_start]
                    + [int(sl.base.n_row_blocks)]
                )
                shards.append(int(sl.n_shards))
            else:
                shards.append(1)
        resilience.save_checkpoint(cfg.checkpoint_path, {
            "fingerprint": fp,
            "outer": int(n_outer),
            "kkt_history": kkt_hist,
            "loglik_history": ll_hist,
            "inner_iters": inner_hist,
            "rebalances": rebalances,
            "recoveries": [dataclasses.asdict(r) for r in recoveries],
            "policies": [dataclasses.asdict(p) if p is not None else None
                         for p in policies],
            "strategies": list(strategies),
            "locals": list(locals_),
            "combines": [mc.combine for mc in mode_cfgs],
            "kappas": [float(mc.kappa) for mc in mode_cfgs],
            "mode_shards": shards,
            "mode_grids": grids,
            "rb_bounds": rb_bounds,
            "lam": lam,
            "factors": factors,
        })

    converged = False
    n_outer = start_outer
    k = start_outer
    while k < cfg.max_outer:
        n_outer = k + 1
        with trace.span("cpapr.sweep", outer=n_outer):
            # sweep-start snapshot: the guards restore it (and redo the
            # whole sweep) when any mode's state went numerically bad —
            # mode updates are deterministic in (factors, lam), so a
            # redone sweep is bitwise the sweep an uninterrupted run
            # would have produced
            snap_factors, snap_lam = list(factors), lam
            ll = None
            for sweep_attempt in range(cfg.guard_retries + 1):
                # the shared pure sweep body (also the service's entry
                # point).  With the guard on, it reads each mode's KKT
                # scalar on the host as the mode completes, and the
                # modes' guard flags at sweep end; then the sweep's
                # worst KKT, its inner iterations and the log-likelihood
                # are read here.  Every such read is a host sync.
                out = sweep_step(
                    (factors, lam),
                    [partial(_run_mode, n_outer, n) for n in range(n_modes)],
                    guard=cfg.guard, counters=counters,
                )
                factors, lam, bad = out.factors, out.lam, out.bad
                worst = counters.read(float, out.worst) \
                    if out.worst is not None else 0.0
                inner_total = counters.read(int, out.inner_total)
                if not bad:
                    if cfg.track_loglik:
                        with trace.span("cpapr.loglik"):
                            ll = counters.read(float, poisson_loglik(
                                t, KTensor(lam, tuple(factors)), cfg.eps
                            ))
                    if not cfg.guard or ll is None or math.isfinite(ll):
                        break
                    # whole-sweep guard: per-mode states passed but the
                    # joint model mass went non-finite — escalate every
                    # mode
                    recoveries.append(RecoveryEvent(
                        "loglik_guard", outer=n_outer, attempt=sweep_attempt,
                        detail={"loglik": ll},
                    ))
                    bad = list(range(n_modes))
                else:
                    for n in bad:
                        recoveries.append(RecoveryEvent(
                            "nan_guard", outer=n_outer, mode=n,
                            attempt=sweep_attempt,
                            detail={"kappa": float(mode_cfgs[n].kappa)},
                        ))
                # restore last-good state and redo the sweep.  The first
                # retry reruns as-is (transient fault); later retries
                # climb the kappa ladder on the offending modes.
                with trace.span("cpapr.recover"):
                    factors = list(snap_factors)
                    lam = snap_lam
                    if sweep_attempt >= 1:
                        for n in bad:
                            _escalate_kappa(n)
                            _rebuild(n)
            else:
                raise FloatingPointError(
                    f"CP-APR sweep {n_outer}: non-finite or negative state "
                    f"persisted through {cfg.guard_retries} guarded sweep "
                    f"retries (mode(s) {bad})"
                )
            if cfg.guard and sweep_attempt > 0:
                # recovery done: drop any escalated scooch back to the
                # configured kappa so the lift does not keep distorting
                # every subsequent sweep
                with trace.span("cpapr.recover"):
                    for n in range(n_modes):
                        if mode_cfgs[n].kappa != cfg.kappa:
                            mode_cfgs[n] = dataclasses.replace(
                                mode_cfgs[n], kappa=cfg.kappa
                            )
                            _rebuild(n)
            kkt_hist.append(worst)
            inner_hist.append(inner_total)
            counters.add("sweeps")
            counters.add("inner", inner_total)
            if ll is not None:
                ll_hist.append(ll)
            if worst <= cfg.tol:
                converged = True
                break
            if (
                cfg.rebalance_every > 0
                and n_outer % cfg.rebalance_every == 0
                and n_outer < cfg.max_outer
            ):
                with trace.span("cpapr.rebalance"):
                    _rebalance_modes(n_outer, rebalances)
            if (
                cfg.checkpoint_every > 0
                and cfg.checkpoint_path
                and n_outer % cfg.checkpoint_every == 0
            ):
                with trace.span("cpapr.checkpoint"):
                    _write_checkpoint(n_outer)
        k += 1
    return CPAPRResult(
        ktensor=KTensor(lam=lam, factors=tuple(factors)),
        n_outer=n_outer,
        kkt_history=kkt_hist,
        loglik_history=ll_hist,
        inner_iters=inner_hist,
        converged=converged,
        policies=policies if cfg.policy == "auto" else None,
        rebalances=rebalances or None,
        recoveries=recoveries or None,
    )
