"""Compile-only checks of the Pallas kernels for a TPU v5e, with no chip.

The TPU compiler is installed with jax; it compiles for a v5e that is
described, not attached.  This finds what interpret mode cannot: tiling
and VMEM/SMEM limits, and whether the kernel lowers at all.  Nothing runs,
so nothing here says anything about results or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.layout import round_up
from repro.core.policy import heuristic_policy
from repro.data.tensors import FROSTT
from repro.kernels.dense.kernel import dense_phi_mu_pallas_call
from repro.kernels.mttkrp.kernel import mttkrp_pallas_call
from repro.kernels.phi.kernel import phi_mu_pallas_call

R = 16
R_PAD = 128
# chicago mode 0 at its published size: nonzeros after dedupe of
# make_tensor("chicago", scale=1.0, rank=16, seed=0), and its row count
CHICAGO_NNZ = 5_289_205
CHICAGO_ROWS = FROSTT["chicago"][0][0]
# The fused kernel scalar-prefetches one int32 row-block id per grid step
# into SMEM (1 MiB on v5e, in 4 KiB pages, one page taken by the kernel's
# other scalars): 261,120 steps is the most that compiles.
SMEM_MAX_GRID = 261_120


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile here cannot be read back from a persistent cache without
    # a chip; keep it out of any cache a caller has turned on
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _blocked_shapes(n_grid, block_nnz, n_rows_pad, with_b=True):
    n = n_grid * block_nnz
    shapes = [((n_grid,), jnp.int32), ((n, 1), jnp.float32),
              ((n, 1), jnp.int32), ((n, R_PAD), jnp.float32)]
    if with_b:
        shapes.append(((n_rows_pad, R_PAD), jnp.float32))
    return shapes


def _chicago_blocking():
    """Grid of chicago mode 0 under the TPU heuristic's blocking: every
    row block takes ceil(its nnz / block_nnz) steps, at most one more
    than its share of nnz."""
    pol = heuristic_policy(CHICAGO_NNZ, CHICAGO_ROWS, R, platform="tpu")
    n_rows_pad = round_up(CHICAGO_ROWS, pol.block_rows)
    n_grid = CHICAGO_NNZ // pol.block_nnz + n_rows_pad // pol.block_rows
    return pol, n_grid, n_rows_pad


def test_phi_mu_kernel_compiles_at_chicago_mode0(one_chip):
    pol, n_grid, n_rows_pad = _chicago_blocking()
    assert pol.strategy == "blocked"
    call = phi_mu_pallas_call(n_grid=n_grid, block_nnz=pol.block_nnz,
                              block_rows=pol.block_rows,
                              n_rows_pad=n_rows_pad, rank_pad=R_PAD,
                              eps=1e-10)
    c = _compile(call, _blocked_shapes(n_grid, pol.block_nnz, n_rows_pad),
                 one_chip)
    assert "tpu_custom_call" in c.as_text()


def test_mttkrp_kernel_compiles_at_chicago_mode0(one_chip):
    pol, n_grid, n_rows_pad = _chicago_blocking()
    call = mttkrp_pallas_call(n_grid=n_grid, block_nnz=pol.block_nnz,
                              block_rows=pol.block_rows,
                              n_rows_pad=n_rows_pad, rank_pad=R_PAD)
    c = _compile(call, _blocked_shapes(n_grid, pol.block_nnz, n_rows_pad,
                                       with_b=False), one_chip)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_dense_phi_mu_kernel_compiles(one_chip, dt):
    # a slab at the dense tier's size cap: K x I x J = 2048 x 24 x 77
    # cells (<= DENSE_MAX_ELEMS), padded as kernels/dense/ops.py pads it
    block_k = 16 if dt == jnp.bfloat16 else 8
    k, i_pad, j_pad = 2048, round_up(24, block_k), round_up(77, 128)
    call = dense_phi_mu_pallas_call(n_grid=k // block_k, block_k=block_k,
                                    i_pad=i_pad, j_pad=j_pad,
                                    rank_pad=R_PAD, eps=1e-10)
    c = _compile(call, [((k, i_pad, j_pad), dt), ((j_pad, R_PAD), dt),
                        ((k, R_PAD), dt), ((i_pad, R_PAD), dt)], one_chip)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("n_grid,fits", [(SMEM_MAX_GRID, True),
                                         (SMEM_MAX_GRID + 1, False)],
                         ids=["at-limit", "past-limit"])
def test_phi_mu_kernel_smem_grid_limit(one_chip, n_grid, fits):
    call = phi_mu_pallas_call(n_grid=n_grid, block_nnz=8, block_rows=8,
                              n_rows_pad=1024, rank_pad=R_PAD, eps=1e-10)
    shapes = _blocked_shapes(n_grid, 8, 1024)
    if fits:
        assert "tpu_custom_call" in _compile(call, shapes, one_chip).as_text()
    else:
        with pytest.raises(jax.errors.JaxRuntimeError,
                           match="RESOURCE_EXHAUSTED.*smem"):
            _compile(call, shapes, one_chip)
