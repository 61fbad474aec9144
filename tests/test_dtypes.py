"""Dtype honesty across the full strategy registry (PR 9 contract).

* jnp strategies (``scatter``/``segment``/``blocked``) compute in the
  caller's dtype: f32, f64 (under ``enable_x64``) and bf16 all come
  back unchanged, and the f64 path really carries f64 precision.
* Pallas-family entry points (``pallas``, ``dense``, the raw kernel
  wrappers and ``stream_op``) support exactly the f32 and
  bf16-element/f32-accumulate tiers and **raise** on f64 or mixed
  operands — never a silent downcast (the historical bug this PR
  fixes: ``.astype(float32)`` unconditionally at every entry point).
* The fused MU variants return ``(mu in caller dtype, f32 scalar)``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import dense_phi_reference

from repro.core.dense import DenseModeData
from repro.core.layout import build_blocked_layout
from repro.core.phi import krao_reduce_rows, phi_from_rows, phi_mu_step

N_ROWS, NNZ, RANK = 12, 64, 4
SPARSE = ("scatter", "segment", "blocked")
KERNEL = ("pallas", "dense")  # the Pallas-tier strategies


def _problem(dt):
    rng = np.random.default_rng(0)
    rows = np.sort(rng.integers(0, N_ROWS, NNZ)).astype(np.int32)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    vals = jax.random.uniform(k1, (NNZ,), minval=0.5, maxval=2.0)
    pi = jax.random.uniform(k2, (NNZ, RANK), minval=0.1, maxval=1.0)
    b = jax.random.uniform(k3, (N_ROWS, RANK), minval=0.1, maxval=1.0)
    return rows, vals.astype(dt), pi.astype(dt), b.astype(dt)


def _dense_data(rows, vals):
    """Map the raw Phi problem onto its exact 2-way dense equivalent:
    one column per nonzero, c = pi, a = ones (empty k_modes)."""
    x = jnp.zeros((1, N_ROWS, NNZ), jnp.float32)
    x = x.at[0, jnp.asarray(rows), jnp.arange(NNZ)].set(
        vals.astype(jnp.float32))
    return DenseModeData(x=x, mode=0, j_mode=1, k_modes=(),
                         shape=(N_ROWS, NNZ))


def _strategy_kwargs(strategy, rows, vals, pi, b):
    if strategy in ("blocked", "pallas"):
        return dict(layout=build_blocked_layout(np.asarray(rows), N_ROWS,
                                                block_nnz=16, block_rows=8))
    if strategy == "dense":
        return dict(dense=_dense_data(rows, vals), factors=(b, pi))
    return {}


TIER_TOL = {"float32": 3e-5, "bfloat16": 3e-2}


@pytest.mark.parametrize("dtype", sorted(TIER_TOL))
@pytest.mark.parametrize("strategy", SPARSE + KERNEL)
def test_phi_preserves_dtype(strategy, dtype):
    """Every strategy returns Phi in the caller's dtype at both kernel
    tiers, within the tier's tolerance of the f64 oracle."""
    dt = jnp.dtype(dtype)
    rows, vals, pi, b = _problem(dt)
    kw = _strategy_kwargs(strategy, rows, vals, pi, b)
    out = phi_from_rows(jnp.asarray(rows), vals, pi, b, N_ROWS,
                        strategy=strategy, **kw)
    assert out.dtype == dt, (strategy, dtype, out.dtype)
    ref = dense_phi_reference(rows, vals, pi, b, N_ROWS)
    tol = TIER_TOL[dtype]
    np.testing.assert_allclose(np.asarray(out, np.float64), ref,
                               rtol=tol, atol=tol, err_msg=strategy)


@pytest.mark.parametrize("dtype", sorted(TIER_TOL))
@pytest.mark.parametrize("strategy", SPARSE + KERNEL)
def test_mttkrp_preserves_dtype(strategy, dtype):
    dt = jnp.dtype(dtype)
    rows, vals, pi, b = _problem(dt)
    kw = _strategy_kwargs(strategy, rows, vals, pi, b)
    out = krao_reduce_rows(jnp.asarray(rows), vals, pi, N_ROWS,
                           strategy=strategy, sorted_rows=True, **kw)
    assert out.dtype == dt, (strategy, dtype, out.dtype)


@pytest.mark.parametrize("dtype", sorted(TIER_TOL))
@pytest.mark.parametrize("strategy", SPARSE + KERNEL)
def test_mu_step_preserves_dtype(strategy, dtype):
    """The fused MU step: B' in the caller's dtype, violation a float
    scalar (f32 accumulator on the kernel tiers)."""
    dt = jnp.dtype(dtype)
    rows, vals, pi, b = _problem(dt)
    kw = _strategy_kwargs(strategy, rows, vals, pi, b)
    b_new, viol = phi_mu_step(jnp.asarray(rows), vals, pi, b, N_ROWS,
                              tol=1e-4, strategy=strategy, **kw)
    assert b_new.dtype == dt, (strategy, dtype, b_new.dtype)
    # the violation is a floating scalar; the Pallas tiers pin it to the
    # f32 accumulator, the jnp strategies keep the element dtype
    assert jnp.issubdtype(viol.dtype, jnp.floating)
    if strategy in KERNEL:
        assert viol.dtype == jnp.dtype(jnp.float32), (strategy, viol.dtype)


@pytest.mark.parametrize("strategy", SPARSE)
def test_sparse_strategies_carry_f64(strategy):
    """f64 in, f64 out — and genuinely double precision, not an upcast
    of an f32 intermediate: the result matches the f64 oracle far
    below f32 resolution."""
    with jax.enable_x64(True):
        rows, vals, pi, b = _problem(jnp.float64)
        kw = _strategy_kwargs(strategy, rows, vals, pi, b)
        out = phi_from_rows(jnp.asarray(rows), vals, pi, b, N_ROWS,
                            strategy=strategy, **kw)
        assert out.dtype == jnp.dtype(jnp.float64), (strategy, out.dtype)
        ref = dense_phi_reference(rows, vals, pi, b, N_ROWS)
        np.testing.assert_allclose(np.asarray(out), ref,
                                   rtol=1e-12, atol=1e-12, err_msg=strategy)


@pytest.mark.parametrize("strategy", KERNEL)
def test_kernel_strategies_raise_on_f64(strategy):
    """No silent downcast: the Pallas tiers refuse f64 with a pointer at
    the jnp strategies instead of handing back f32."""
    with jax.enable_x64(True):
        rows, vals, pi, b = _problem(jnp.float64)
        kw = _strategy_kwargs(strategy, rows, vals, pi, b)
        with pytest.raises(ValueError, match="float64"):
            phi_from_rows(jnp.asarray(rows), vals, pi, b, N_ROWS,
                          strategy=strategy, **kw)


def test_kernel_entry_points_raise_on_f64():
    """The raw kernel wrappers enforce the tier themselves (callers that
    bypass the routing layer get the same contract)."""
    from repro.kernels.dense import mttkrp_dense, phi_dense
    from repro.kernels.stream.ops import stream_op

    with jax.enable_x64(True):
        x = jnp.ones((2, 4, 4), jnp.float64)
        c = jnp.ones((4, 3), jnp.float64)
        a = jnp.ones((2, 3), jnp.float64)
        with pytest.raises(ValueError, match="float64"):
            mttkrp_dense(x, c, a)
        with pytest.raises(ValueError, match="float64"):
            phi_dense(x, c, a, jnp.ones((4, 3), jnp.float64))
        with pytest.raises(ValueError, match="float64"):
            stream_op("scale", jnp.ones((128 * 256,), jnp.float64))


def test_kernel_entry_points_raise_on_mixed_dtypes():
    """Mixed operands must state the tier explicitly, not promote."""
    from repro.kernels.dense import mttkrp_dense

    x = jnp.ones((2, 4, 4), jnp.float32)
    c = jnp.ones((4, 3), jnp.bfloat16)
    a = jnp.ones((2, 3), jnp.float32)
    with pytest.raises(ValueError, match="share one element dtype"):
        mttkrp_dense(x, c, a)


def test_dense_bf16_accumulates_in_f32():
    """The mixed tier really runs an f32 accumulator: summing many
    same-sign bf16 contributions stays within bf16 *rounding* of the
    exact sum, instead of the catastrophic error a bf16 accumulator
    would give (bf16 loses integer resolution past 256)."""
    from repro.kernels.dense import mttkrp_dense

    k, i, j, r = 8, 8, 512, 4
    x = jnp.ones((k, i, j), jnp.bfloat16)
    c = jnp.ones((j, r), jnp.bfloat16)
    a = jnp.ones((k, r), jnp.bfloat16)
    out = np.asarray(mttkrp_dense(x, c, a), np.float64)
    exact = k * j  # 4096 ones per output cell
    # one terminal bf16 rounding (rel 2^-8); a bf16 accumulator would
    # stall at 256 and lose >90% of the sum
    np.testing.assert_allclose(out, np.full((i, r), exact), rtol=2 ** -8)
