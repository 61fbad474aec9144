"""Sparse count-tensor substrate for CP-APR / CP-ALS.

A :class:`SparseTensor` is a COO tensor of non-negative counts, the input
format of the CP-APR MU algorithm (Chi & Kolda 2012).  The paper's CPU
algorithm (Alg. 4) relies on per-mode *permutation arrays* that sort the
nonzeros by their mode-n coordinate so that updates to the same row of
Phi^(n) are contiguous.  On TPU this sorted layout is not merely an atomic
mitigation — it is the *only* way to express the reduction (there are no
atomics), so the sorted views are first-class here.

A :class:`KTensor` is a Kruskal tensor: weights ``lam`` (R,) plus one factor
matrix per mode.  All arrays are JAX arrays; everything is functional.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "SparseTensor",
    "KTensor",
    "ModeView",
    "AppendInfo",
    "append_nonzeros",
    "merge_mode_view",
    "sort_mode",
    "random_ktensor",
    "random_poisson_tensor",
    "dense_from_coo",
    "ktensor_full",
]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class SparseTensor:
    """COO sparse tensor of counts.

    Attributes:
      shape:   static python tuple (I_1, ..., I_N).
      indices: (nnz, N) int32 coordinates.
      values:  (nnz,) float32 counts (CP-APR works on float copies of counts).
    """

    shape: tuple
    indices: jax.Array
    values: jax.Array

    # -- pytree plumbing ----------------------------------------------------
    def tree_flatten(self):
        return (self.indices, self.values), self.shape

    @classmethod
    def tree_unflatten(cls, shape, children):
        indices, values = children
        return cls(shape=shape, indices=indices, values=values)

    # -- basic properties ---------------------------------------------------
    @property
    def ndim(self) -> int:
        return len(self.shape)

    @property
    def nnz(self) -> int:
        return int(self.values.shape[0])

    def density(self) -> float:
        full = float(np.prod([float(s) for s in self.shape]))
        return self.nnz / full

    def mode_view(self, n: int) -> "ModeView":
        return sort_mode(self, n)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class ModeView:
    """Nonzeros of a tensor sorted by their mode-``n`` coordinate.

    This is the paper's per-mode *permutation array* P[n] (Alg. 4 line 6),
    computed once up front and reused by every inner iteration.

    Attributes:
      mode:        static mode index n.
      perm:        (nnz,) int32, sort order into the original COO arrays.
      rows:        (nnz,) int32, sorted mode-n coordinates (ascending).
      sorted_idx:  (nnz, N) int32, all coordinates in sorted order.
      sorted_vals: (nnz,) f32, values in sorted order.
      row_starts:  (I_n + 1,) int32 CSR-style pointers into the sorted run.
    """

    mode: int
    perm: jax.Array
    rows: jax.Array
    sorted_idx: jax.Array
    sorted_vals: jax.Array
    row_starts: jax.Array

    def tree_flatten(self):
        return (
            self.perm,
            self.rows,
            self.sorted_idx,
            self.sorted_vals,
            self.row_starts,
        ), self.mode

    @classmethod
    def tree_unflatten(cls, mode, children):
        perm, rows, sorted_idx, sorted_vals, row_starts = children
        return cls(mode, perm, rows, sorted_idx, sorted_vals, row_starts)

    @property
    def nnz(self) -> int:
        return int(self.rows.shape[0])

    @property
    def n_rows(self) -> int:
        return int(self.row_starts.shape[0]) - 1


def sort_mode(t: SparseTensor, n: int) -> ModeView:
    """Build the sorted mode view (permutation array) for mode ``n``."""
    rows_unsorted = t.indices[:, n]
    perm = jnp.argsort(rows_unsorted, stable=True).astype(jnp.int32)
    rows = rows_unsorted[perm].astype(jnp.int32)
    sorted_idx = t.indices[perm].astype(jnp.int32)
    sorted_vals = t.values[perm]
    i_n = t.shape[n]
    counts = jnp.bincount(rows, length=i_n)
    row_starts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts).astype(jnp.int32)]
    )
    return ModeView(
        mode=n,
        perm=perm,
        rows=rows,
        sorted_idx=sorted_idx,
        sorted_vals=sorted_vals,
        row_starts=row_starts,
    )


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class KTensor:
    """Kruskal tensor: ``sum_r lam[r] * outer(factors[0][:, r], ...)``."""

    lam: jax.Array  # (R,)
    factors: tuple  # tuple of (I_n, R) arrays

    def tree_flatten(self):
        return (self.lam, tuple(self.factors)), None

    @classmethod
    def tree_unflatten(cls, _, children):
        lam, factors = children
        return cls(lam=lam, factors=tuple(factors))

    @property
    def rank(self) -> int:
        return int(self.lam.shape[0])

    @property
    def shape(self) -> tuple:
        return tuple(int(f.shape[0]) for f in self.factors)

    def normalize(self) -> "KTensor":
        """Column-1-normalize all factors, folding mass into ``lam``."""
        lam = self.lam
        factors = []
        for f in self.factors:
            colsum = jnp.sum(f, axis=0)
            safe = jnp.where(colsum > 0, colsum, 1.0)
            factors.append(f / safe)
            lam = lam * jnp.where(colsum > 0, colsum, 0.0)
        return KTensor(lam=lam, factors=tuple(factors))


# ---------------------------------------------------------------------------
# Constructors / oracles
# ---------------------------------------------------------------------------


def random_ktensor(
    key: jax.Array, shape: Sequence[int], rank: int, dtype=jnp.float32
) -> KTensor:
    """Random non-negative Kruskal tensor with unit-sum columns."""
    keys = jax.random.split(key, len(shape) + 1)
    factors = []
    for k, i_n in zip(keys[:-1], shape):
        f = jax.random.uniform(k, (i_n, rank), dtype=dtype, minval=0.1, maxval=1.0)
        factors.append(f / jnp.sum(f, axis=0))
    lam = jax.random.uniform(keys[-1], (rank,), dtype=dtype, minval=0.5, maxval=2.0)
    return KTensor(lam=lam, factors=tuple(factors))


def _linear_index(idx: np.ndarray, shape) -> np.ndarray:
    """Row-major linearization of (nnz, N) coordinates into int64 codes."""
    lin = np.zeros(idx.shape[0], dtype=np.int64)
    mult = 1
    for n in range(len(shape) - 1, -1, -1):
        lin += idx[:, n].astype(np.int64) * mult
        mult *= int(shape[n])
    return lin


def _unique_coo(idx: np.ndarray, vals: np.ndarray, shape) -> tuple:
    """Deduplicate COO coordinates (summing values)."""
    lin = _linear_index(idx, shape)
    uniq, inv = np.unique(lin, return_inverse=True)
    out_vals = np.zeros(uniq.shape[0], dtype=vals.dtype)
    np.add.at(out_vals, inv, vals)
    out_idx = np.zeros((uniq.shape[0], len(shape)), dtype=np.int32)
    rem = uniq.copy()
    for n in range(len(shape) - 1, -1, -1):
        out_idx[:, n] = rem % int(shape[n])
        rem //= int(shape[n])
    return out_idx, out_vals


@dataclasses.dataclass(frozen=True)
class AppendInfo:
    """Bookkeeping for one :func:`append_nonzeros` merge.

    ``n_fresh`` entries landed on previously-empty coordinates (they sit
    at the tail of the merged COO arrays, in mode-sorted-stable order of
    the incoming batch); ``n_merged`` collided with existing coordinates
    and had their counts summed in place.  ``frac_new`` is the fresh
    share of the merged nonzero count — the freshness signal the serving
    layer's warm-start sweep budget consumes.
    """

    n_appended: int
    n_fresh: int
    n_merged: int
    nnz_before: int
    nnz_after: int

    @property
    def frac_new(self) -> float:
        return self.n_fresh / max(self.nnz_after, 1)


def append_nonzeros(
    t: SparseTensor, new_indices, new_values
) -> "tuple[SparseTensor, AppendInfo]":
    """Merge a batch of new nonzeros into ``t`` (streaming append).

    The incoming batch is first deduplicated against itself through the
    :func:`_unique_coo` path (duplicate coordinates sum), then matched
    against the existing coordinates by linearized index: collisions add
    their counts to the existing entries *in place* (COO order
    preserved), genuinely-new coordinates append at the tail.  That
    layout invariant — positions ``[0, t.nnz)`` of the merged arrays are
    ``t``'s nonzeros in their original order — is what lets
    :func:`merge_mode_view` extend the per-mode sorted views by merging
    sorted runs instead of re-sorting.  Runs on host numpy (ingest, not
    a hot path).
    """
    new_idx = np.asarray(new_indices)
    new_vals = np.asarray(new_values, dtype=np.float32)
    if new_idx.ndim != 2 or new_idx.shape[1] != t.ndim:
        raise ValueError(
            f"append_nonzeros: new_indices must be (k, {t.ndim}) for a "
            f"{t.ndim}-mode tensor; got shape {new_idx.shape}"
        )
    if new_vals.shape != (new_idx.shape[0],):
        raise ValueError(
            f"append_nonzeros: new_values must be ({new_idx.shape[0]},) to "
            f"match new_indices; got shape {new_vals.shape}"
        )
    if not np.all(np.isfinite(new_vals)) or np.any(new_vals < 0):
        raise ValueError(
            "append_nonzeros: values must be finite non-negative counts"
        )
    for n, i_n in enumerate(t.shape):
        if new_idx.shape[0] and (
            new_idx[:, n].min() < 0 or new_idx[:, n].max() >= i_n
        ):
            raise ValueError(
                f"append_nonzeros: mode-{n} coordinates out of range for "
                f"shape {t.shape}"
            )
    n_appended = int(new_idx.shape[0])
    new_idx, new_vals = _unique_coo(
        new_idx.astype(np.int64), new_vals, t.shape
    )

    old_idx = np.asarray(t.indices)
    old_vals = np.array(t.values, dtype=np.float32)  # copy: updated in place
    lin_old = _linear_index(old_idx, t.shape)
    order_old = np.argsort(lin_old, kind="stable")
    lin_sorted = lin_old[order_old]
    lin_new = _linear_index(new_idx, t.shape)
    pos = np.searchsorted(lin_sorted, lin_new)
    pos_c = np.minimum(pos, max(len(lin_sorted) - 1, 0))
    matched = (
        (lin_new <= lin_sorted[-1]) & (lin_sorted[pos_c] == lin_new)
        if len(lin_sorted)
        else np.zeros(lin_new.shape, dtype=bool)
    )
    np.add.at(old_vals, order_old[pos_c[matched]], new_vals[matched])

    fresh_idx = new_idx[~matched].astype(np.int32)
    fresh_vals = new_vals[~matched]
    merged = SparseTensor(
        shape=t.shape,
        indices=jnp.concatenate(
            [jnp.asarray(old_idx, jnp.int32), jnp.asarray(fresh_idx)]
        ),
        values=jnp.concatenate(
            [jnp.asarray(old_vals), jnp.asarray(fresh_vals, jnp.float32)]
        ),
    )
    info = AppendInfo(
        n_appended=n_appended,
        n_fresh=int(fresh_idx.shape[0]),
        n_merged=int(matched.sum()),
        nnz_before=t.nnz,
        nnz_after=merged.nnz,
    )
    return merged, info


def merge_mode_view(
    mv: ModeView, merged: SparseTensor, nnz_before: int
) -> ModeView:
    """Extend a mode view over an appended tensor by merging sorted runs.

    ``merged`` must come from :func:`append_nonzeros` on the tensor
    ``mv`` was built from (``nnz_before`` = that tensor's nnz): positions
    ``[0, nnz_before)`` are the old nonzeros in their original order
    (values possibly bumped by collisions) and the tail is the fresh
    batch.  The old sorted run is reused as-is; only the O(k log k) sort
    of the fresh tail plus an O(nnz) merge (``searchsorted`` +
    ``insert``) and a value re-gather are paid — no full re-sort.  The
    result is identical (element-for-element, including stable tie
    order) to ``sort_mode(merged, mv.mode)``.
    """
    n = mv.mode
    i_n = mv.n_rows
    idx_np = np.asarray(merged.indices)
    if idx_np.shape[0] < nnz_before:
        raise ValueError(
            f"merge_mode_view: merged tensor has {idx_np.shape[0]} nonzeros "
            f"< nnz_before={nnz_before}"
        )
    tail_idx = idx_np[nnz_before:]
    tail_rows = tail_idx[:, n]
    order_tail = np.argsort(tail_rows, kind="stable")
    rows_tail = tail_rows[order_tail].astype(np.int32)
    perm_tail = (nnz_before + order_tail).astype(np.int32)

    rows_old = np.asarray(mv.rows)
    # stable merge: new entries land *after* old entries with equal row
    # (they sit at higher COO positions), matching sort_mode's stable sort
    ins = np.searchsorted(rows_old, rows_tail, side="right")
    perm = np.insert(np.asarray(mv.perm), ins, perm_tail).astype(np.int32)
    rows = np.insert(rows_old, ins, rows_tail).astype(np.int32)
    sorted_idx = np.insert(
        np.asarray(mv.sorted_idx), ins, tail_idx[order_tail], axis=0
    ).astype(np.int32)
    # collisions changed old values in place: re-gather, don't re-sort
    sorted_vals = np.asarray(merged.values)[perm]
    counts_tail = np.bincount(rows_tail, minlength=i_n)
    row_starts = np.asarray(mv.row_starts) + np.concatenate(
        [[0], np.cumsum(counts_tail)]
    ).astype(np.int32)
    return ModeView(
        mode=n,
        perm=jnp.asarray(perm),
        rows=jnp.asarray(rows),
        sorted_idx=jnp.asarray(sorted_idx),
        sorted_vals=jnp.asarray(sorted_vals, jnp.float32),
        row_starts=jnp.asarray(row_starts, jnp.int32),
    )


def random_poisson_tensor(
    key: jax.Array,
    shape: Sequence[int],
    nnz: int,
    rank: int = 4,
    seed_ktensor: KTensor | None = None,
) -> tuple:
    """Sample a sparse Poisson count tensor from a low-rank model.

    Draws ``nnz`` candidate multi-indices from the factor-defined categorical
    distribution (the generative model CP-APR assumes), assigns count values
    >=1, and deduplicates.  Returns ``(SparseTensor, ground_truth_KTensor)``.
    Runs on host numpy (data generation, not a hot path).
    """
    shape = tuple(int(s) for s in shape)
    kt = seed_ktensor or random_ktensor(key, shape, rank)
    rng = np.random.default_rng(np.asarray(jax.random.key_data(key)).ravel()[-1])
    lam = np.asarray(kt.lam, dtype=np.float64)
    p_r = lam / lam.sum()
    comp = rng.choice(len(lam), size=nnz, p=p_r)
    idx = np.zeros((nnz, len(shape)), dtype=np.int32)
    for n, f in enumerate(kt.factors):
        fn = np.asarray(f, dtype=np.float64)
        fn = fn / np.clip(fn.sum(axis=0, keepdims=True), 1e-12, None)
        cdf = np.cumsum(fn, axis=0)  # (I_n, R)
        u = rng.random(nnz)
        # per-component inverse-CDF sampling (O(nnz log I_n) memory-safe)
        col = np.zeros(nnz, dtype=np.int64)
        for r in range(len(lam)):
            sel = comp == r
            if sel.any():
                col[sel] = np.searchsorted(cdf[:, r], u[sel])
        idx[:, n] = col.clip(0, shape[n] - 1)
    vals = rng.poisson(1.0, size=nnz).astype(np.float32) + 1.0
    idx, vals = _unique_coo(idx, vals, shape)
    st = SparseTensor(
        shape=shape,
        indices=jnp.asarray(idx, jnp.int32),
        values=jnp.asarray(vals, jnp.float32),
    )
    return st, kt


def dense_from_coo(t: SparseTensor) -> jax.Array:
    """Materialize a small COO tensor densely (test oracle only)."""
    dense = jnp.zeros(t.shape, t.values.dtype)
    return dense.at[tuple(t.indices[:, n] for n in range(t.ndim))].add(t.values)


def ktensor_full(kt: KTensor) -> jax.Array:
    """Materialize a small Kruskal tensor densely (test oracle only)."""
    shape = kt.shape
    out = jnp.zeros(shape, kt.lam.dtype)
    r = kt.rank
    for rr in range(r):
        term = kt.lam[rr]
        vecs = [f[:, rr] for f in kt.factors]
        acc = vecs[0]
        for v in vecs[1:]:
            acc = jnp.tensordot(acc, v, axes=0)
        out = out + term * acc
    return out


def model_values_at(kt: KTensor, indices: jax.Array) -> jax.Array:
    """Model value m_z = sum_r lam_r prod_n A^(n)[i_n, r] at each nonzero."""
    prod = jnp.ones((indices.shape[0], kt.rank), kt.lam.dtype)
    for n, f in enumerate(kt.factors):
        prod = prod * f[indices[:, n]]
    return jnp.sum(prod * kt.lam[None, :], axis=1)  # f32 on TPU, unlike @
