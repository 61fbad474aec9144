"""Padded-bucket batching for the decomposition service.

Many-tenant traffic is dominated by *small* tensors; dispatching one
XLA program per job wastes the accelerator on launch overhead.  This
module rounds job shapes up into shared padded **buckets** (the same
padding trick the blocked layout uses for rows: append zero-value
nonzeros at coordinate 0 and zero factor rows past the true extent) and
solves every same-bucket job in ONE dispatch with ``jax.vmap`` over the
job axis.

Padding is exact, not approximate: a zero-valued nonzero contributes
``w_j = 0 / max(s, eps) = 0`` to every Phi row, a zero factor row gets
``Phi = 0`` and stays zero through the multiplicative update, and the
scooch never lifts it (``phi0 = 0 ≯ 1``).  Jobs that converge early are
frozen with a ``where`` mask, so a job's trajectory is independent of
its cohort — solving ``[A, B, C]`` batched yields bitwise the factors of
solving ``[A]`` alone through the same padded path.

The outer sweep runs through :func:`repro.core.cpapr.sweep_step` — the
same pure ``(carry, batch) -> carry`` body the ``cpapr_mu`` driver and
its checkpoint path execute — with vmapped per-mode updates whose KKT
scalar is a per-job ``(J,)`` array.  Only the ``segment`` strategy is
offered here: it is the vmap-friendly one (pure gathers +
``segment_sum``), and bucket-tier tensors are too small for the blocked
schedule to pay off.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cpapr import CPAPRConfig, CPAPRResult, sweep_step
from repro.core.phi import phi_from_rows, phi_mu_step
from repro.core.pi import pi_rows
from repro.core.sparse_tensor import KTensor, SparseTensor, random_ktensor

__all__ = [
    "Bucket",
    "BucketRegistry",
    "batched_cpapr_mu",
    "pad_tensor",
    "padded_init",
]


def _round_up(x: int, m: int) -> int:
    return ((int(x) + m - 1) // m) * m


def _next_pow2(x: int, floor: int) -> int:
    p = floor
    while p < x:
        p *= 2
    return p


@dataclasses.dataclass(frozen=True)
class Bucket:
    """One padded problem class: every job padded to these extents."""

    shape: tuple  # padded (I_1, ..., I_N)
    nnz: int  # padded nonzero count
    rank: int

    @property
    def ndim(self) -> int:
        return len(self.shape)


class BucketRegistry:
    """Rounds job shapes up to shared padded buckets.

    Mode extents round up to a multiple of ``row_multiple`` and the
    nonzero count to the next power of two (floored at
    ``nnz_floor``) — coarse enough that same-ish jobs share a compiled
    program, fine enough that padding waste stays bounded (< 2x nnz,
    < ``row_multiple`` rows per mode).
    """

    def __init__(self, row_multiple: int = 8, nnz_floor: int = 64):
        self.row_multiple = int(row_multiple)
        self.nnz_floor = int(nnz_floor)
        self.seen: dict = {}  # Bucket -> jobs routed through it

    def bucket_of(self, shape, nnz: int, rank: int) -> Bucket:
        b = Bucket(
            shape=tuple(_round_up(s, self.row_multiple) for s in shape),
            nnz=_next_pow2(int(nnz), self.nnz_floor),
            rank=int(rank),
        )
        self.seen[b] = self.seen.get(b, 0) + 1
        return b

    def group(self, specs) -> dict:
        """Group job indices by bucket; ``specs`` is (shape, nnz, rank)."""
        groups: dict = {}
        for j, (shape, nnz, rank) in enumerate(specs):
            groups.setdefault(self.bucket_of(shape, nnz, rank), []).append(j)
        return groups


def pad_tensor(t: SparseTensor, bucket: Bucket) -> SparseTensor:
    """Pad ``t`` into its bucket: zero-valued tail nonzeros at coordinate 0.

    The padded tensor decomposes to exactly the same factors as ``t``
    (over the true rows) when the initial factors are zero past the true
    extents — see :func:`padded_init`.
    """
    if t.ndim != bucket.ndim or any(
        s > bs for s, bs in zip(t.shape, bucket.shape)
    ):
        raise ValueError(
            f"tensor shape {t.shape} does not fit bucket {bucket.shape}"
        )
    if t.nnz > bucket.nnz:
        raise ValueError(
            f"tensor nnz {t.nnz} exceeds bucket nnz {bucket.nnz}"
        )
    pad = bucket.nnz - t.nnz
    idx = jnp.concatenate(
        [jnp.asarray(t.indices, jnp.int32),
         jnp.zeros((pad, t.ndim), jnp.int32)]
    )
    vals = jnp.concatenate(
        [jnp.asarray(t.values, jnp.float32), jnp.zeros((pad,), jnp.float32)]
    )
    return SparseTensor(shape=bucket.shape, indices=idx, values=vals)


def padded_init(key: jax.Array, true_shape, bucket: Bucket) -> KTensor:
    """Random init drawn on the *true* shape, zero-padded to the bucket.

    Zero rows past the true extent keep the padded problem exactly
    equivalent to the unpadded one (their Phi is identically zero, so
    they never acquire mass).
    """
    kt = random_ktensor(key, tuple(true_shape), bucket.rank)
    factors = []
    for f, i_pad in zip(kt.factors, bucket.shape):
        factors.append(jnp.pad(f, ((0, i_pad - f.shape[0]), (0, 0))))
    return KTensor(lam=kt.lam, factors=tuple(factors))


def _mode_arrays(idx_pad: np.ndarray, vals_pad: np.ndarray, n: int):
    """Stable mode-n sort of padded COO arrays (mirrors ``sort_mode``)."""
    perm = np.argsort(idx_pad[:, n], kind="stable")
    return (
        idx_pad[perm, n].astype(np.int32),
        idx_pad[perm].astype(np.int32),
        vals_pad[perm].astype(np.float32),
    )


def _make_mode_update(n: int, bucket: Bucket, cfg: CPAPRConfig):
    """Single-job padded mode update, mirroring the solver's segment path.

    The math is ``cpapr._make_mode_update(strategy="segment")`` verbatim
    — hoisted Pi gather, scooch, fused ``phi_mu_step`` inner while_loop,
    renormalize — expressed over one padded job so ``jax.vmap`` lifts it
    to the whole bucket.  ``phi_mu_step`` leaves B untouched once
    ``viol <= tol``, so the extra iterations a vmapped while_loop runs on
    already-converged lanes are exact no-ops.
    """
    n_rows = bucket.shape[n]

    def update(rows, sidx, svals, factors, lam):
        a_n = factors[n]
        pi = pi_rows(sidx, factors, n)
        phi0 = phi_from_rows(
            rows, svals, pi, a_n * lam[None, :],
            n_rows=n_rows, eps=cfg.eps, strategy="segment",
        )
        s = jnp.where((a_n < cfg.kappa_tol) & (phi0 > 1.0), cfg.kappa, 0.0)
        b0 = (a_n + s) * lam[None, :]

        def cond(state):
            i, _, viol = state
            return (i < cfg.max_inner) & (viol > cfg.tol)

        def body(state):
            i, b, _ = state
            b_new, viol = phi_mu_step(
                rows, svals, pi, b,
                n_rows=n_rows, eps=cfg.eps, tol=cfg.tol, strategy="segment",
            )
            return (i + 1, b_new, viol)

        i, b, viol = jax.lax.while_loop(
            cond, body, (jnp.int32(0), b0, jnp.asarray(jnp.inf, b0.dtype))
        )
        lam_new = jnp.sum(b, axis=0)
        safe = jnp.maximum(lam_new, cfg.eps)
        a_new = b / safe
        return a_new, lam_new, viol, i

    return update


def batched_cpapr_mu(
    tensors,
    rank: int,
    keys=None,
    inits=None,
    config: CPAPRConfig | None = None,
    bucket: Bucket | None = None,
    registry: BucketRegistry | None = None,
):
    """Solve many small tensors in one vmapped dispatch per mode update.

    Args:
      tensors: list of :class:`SparseTensor`, all fitting one bucket.
      rank: decomposition rank (shared across the bucket).
      keys: per-job PRNG keys for the random init (ignored where
        ``inits`` provides one).
      inits: optional per-job :class:`KTensor` inits on the *true* job
        shapes (padded internally).
      config: solver config; ``strategy`` is forced to ``segment`` (the
        vmappable path).  Guards/checkpointing/rebalance do not apply to
        the bucket tier.
      bucket: explicit bucket; default = registry's rounding of the
        largest job.
      registry: :class:`BucketRegistry` used when ``bucket`` is None.

    Returns ``(results, bucket)`` where ``results`` is a list of
    :class:`CPAPRResult` aligned with ``tensors`` (factors sliced back to
    the true shapes).  Inner-iteration counts are cohort-level: a
    vmapped ``while_loop`` runs until every lane converges, so per-job
    splits are upper bounds.
    """
    cfg = config or CPAPRConfig(rank=rank)
    cfg = dataclasses.replace(cfg, rank=rank, strategy="segment",
                              policy=None, track_loglik=False)
    n_jobs = len(tensors)
    if n_jobs == 0:
        raise ValueError("batched_cpapr_mu: no tensors given")
    ndim = tensors[0].ndim
    if any(t.ndim != ndim for t in tensors):
        raise ValueError("batched_cpapr_mu: all tensors must share ndim")
    if bucket is None:
        registry = registry or BucketRegistry()
        shape_max = tuple(
            max(t.shape[n] for t in tensors) for n in range(ndim)
        )
        bucket = registry.bucket_of(
            shape_max, max(t.nnz for t in tensors), rank
        )

    if keys is None:
        keys = [jax.random.PRNGKey(j) for j in range(n_jobs)]

    # --- pad + per-mode stable sorts, stacked over the job axis ----------
    rows_b = [[] for _ in range(ndim)]
    sidx_b = [[] for _ in range(ndim)]
    svals_b = [[] for _ in range(ndim)]
    factors_j = []
    lam_j = []
    for j, t in enumerate(tensors):
        tp = pad_tensor(t, bucket)
        idx_np = np.asarray(tp.indices)
        vals_np = np.asarray(tp.values)
        for n in range(ndim):
            r, si, sv = _mode_arrays(idx_np, vals_np, n)
            rows_b[n].append(r)
            sidx_b[n].append(si)
            svals_b[n].append(sv)
        if inits is not None and inits[j] is not None:
            init = inits[j]
            kt0 = padded_init_from(init, bucket)
        else:
            kt0 = padded_init(keys[j], t.shape, bucket)
        kt0 = kt0.normalize()  # what cpapr_mu does to its init
        factors_j.append(kt0.factors)
        lam_j.append(kt0.lam)
    rows_b = [jnp.asarray(np.stack(r)) for r in rows_b]
    sidx_b = [jnp.asarray(np.stack(s)) for s in sidx_b]
    svals_b = [jnp.asarray(np.stack(v)) for v in svals_b]
    factors = [
        jnp.stack([fj[n] for fj in factors_j]) for n in range(ndim)
    ]  # per mode: (J, I_pad, R)
    lam = jnp.stack(lam_j)  # (J, R)

    updates = [
        jax.jit(jax.vmap(_make_mode_update(n, bucket, cfg),
                         in_axes=(0, 0, 0, 0, 0)))
        for n in range(ndim)
    ]

    def sweep_batch(keep):
        """Per-mode callables for sweep_step, frozen at this sweep's mask."""

        def mode_fn(n):
            def fn(fac, lm):
                a, l, viol, ninner = updates[n](
                    rows_b[n], sidx_b[n], svals_b[n], tuple(fac), lm
                )
                # freeze converged jobs: their state (and reported KKT)
                # must not depend on how long the cohort keeps sweeping
                a = jnp.where(keep[:, None, None], a, fac[n])
                l = jnp.where(keep[:, None], l, lm)
                viol = jnp.where(keep, viol, 0.0)
                return a, l, viol, ninner, None

            return fn

        return [mode_fn(n) for n in range(ndim)]

    # --- outer sweeps through the shared pure sweep body ------------------
    done = np.zeros(n_jobs, bool)
    kkt_hist = [[] for _ in range(n_jobs)]
    inner_hist = [[] for _ in range(n_jobs)]
    n_outer = np.zeros(n_jobs, np.int64)
    k = 0
    while k < cfg.max_outer and not done.all():
        out = sweep_step((factors, lam), sweep_batch(jnp.asarray(~done)))
        factors, lam = out.factors, out.lam
        worst = np.asarray(out.worst)  # (J,)
        inner = np.asarray(out.inner_total)  # (J,) cohort-level counts
        for j in range(n_jobs):
            if not done[j]:
                kkt_hist[j].append(float(worst[j]))
                inner_hist[j].append(int(inner[j]))
                n_outer[j] = k + 1
        done |= worst <= cfg.tol
        k += 1

    results = []
    for j, t in enumerate(tensors):
        facs = tuple(
            factors[n][j, : t.shape[n], :] for n in range(ndim)
        )
        results.append(CPAPRResult(
            ktensor=KTensor(lam=lam[j], factors=facs),
            n_outer=int(n_outer[j]),
            kkt_history=kkt_hist[j],
            loglik_history=[],
            inner_iters=inner_hist[j],
            converged=bool(done[j]),
        ))
    return results, bucket


def padded_init_from(init: KTensor, bucket: Bucket) -> KTensor:
    """Zero-pad an explicit init KTensor up to the bucket extents."""
    factors = []
    for f, i_pad in zip(init.factors, bucket.shape):
        if f.shape[0] > i_pad:
            raise ValueError(
                f"init factor with {f.shape[0]} rows does not fit bucket "
                f"extent {i_pad}"
            )
        factors.append(jnp.pad(f, ((0, i_pad - f.shape[0]), (0, 0))))
    return KTensor(lam=init.lam, factors=tuple(factors))
