"""Pallas kernel sweeps: shapes x dtypes vs the pure-jnp ref.py oracles.

All kernels run in interpret mode on CPU (the kernel body is executed in
Python), asserting allclose against the reference implementation.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CPAPRConfig, cpapr, cpapr_mu
from repro.core.layout import build_blocked_layout, round_up
from repro.core.phi import expand_to_layout
from repro.core.pi import pi_rows
from repro.core.sparse_tensor import (random_ktensor, random_poisson_tensor,
                                      sort_mode)
from repro.kernels.mttkrp.ops import mttkrp_blocked
from repro.kernels.mttkrp.ref import mttkrp_blocked_ref, mttkrp_ref
from repro.kernels.phi.kernel import phi_mu_pallas_call, phi_pallas_call
from repro.kernels.phi.ops import phi_blocked, phi_mu_blocked, phi_operands
from repro.kernels.phi.ref import phi_blocked_ref, phi_ref
from repro.kernels.stream.ops import STREAM_OPS, stream_op
from repro.kernels.stream.ref import stream_ref


def _mode_data(shape, nnz, rank, mode, seed=0):
    t, kt = random_poisson_tensor(jax.random.PRNGKey(seed), shape, nnz=nnz,
                                  rank=rank)
    mv = sort_mode(t, mode)
    pi = pi_rows(mv.sorted_idx, kt.factors, mode)
    b = kt.factors[mode] * kt.lam[None, :]
    return t, mv, pi, b


PHI_CASES = [
    # (tensor shape, nnz, rank, block_nnz, block_rows)
    ((40, 30, 25), 1500, 4, 64, 32),
    ((40, 30, 25), 1500, 8, 128, 64),
    ((100, 7, 11), 900, 16, 32, 128),
    ((8, 60, 60), 2500, 4, 256, 8),
    ((64, 64, 64, 8), 3000, 12, 128, 16),
]


@pytest.mark.parametrize("shape,nnz,rank,bn,br", PHI_CASES)
def test_phi_pallas_sweep(shape, nnz, rank, bn, br):
    for mode in range(min(len(shape), 2)):
        t, mv, pi, b = _mode_data(shape, nnz, rank, mode)
        layout = build_blocked_layout(np.asarray(mv.rows), mv.n_rows, bn, br)
        vals_e, pi_e = expand_to_layout(layout, mv.sorted_vals, pi)
        ops = phi_operands(vals_e, pi_e, layout.local_rows, layout.grid_rb)
        out = phi_blocked(layout, ops, b, eps=1e-10)
        b_pad = jnp.pad(b, ((0, layout.n_rows_pad - b.shape[0]), (0, 0)))
        ref = phi_blocked_ref(layout, vals_e, pi_e, b_pad, eps=1e-10)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=3e-5, atol=1e-5)
        # and against the unblocked per-nonzero oracle
        ref2 = phi_ref(mv.rows, mv.sorted_vals, pi, b, mv.n_rows, 1e-10)
        np.testing.assert_allclose(np.asarray(out[: mv.n_rows]),
                                   np.asarray(ref2), rtol=3e-5, atol=1e-5)


def test_phi_pallas_empty_rows():
    """Rows with zero nonzeros must come back exactly zero."""
    t, mv, pi, b = _mode_data((200, 10, 10), 300, 4, 0)  # many empty rows
    layout = build_blocked_layout(np.asarray(mv.rows), mv.n_rows, 64, 32)
    vals_e, pi_e = expand_to_layout(layout, mv.sorted_vals, pi)
    ops = phi_operands(vals_e, pi_e, layout.local_rows, layout.grid_rb)
    out = np.asarray(phi_blocked(layout, ops, b)[: mv.n_rows])
    occupied = np.zeros(mv.n_rows, bool)
    occupied[np.asarray(mv.rows)] = True
    assert np.all(out[~occupied] == 0.0)


def _per_call_kernel(kernel, layout, vals_e, pi_e, b):
    """A Phi kernel with its operands padded and reshaped inside the call,
    as the raw-array path does on every call."""
    r, r_pad = b.shape[1], round_up(b.shape[1], 128)
    make = phi_mu_pallas_call if kernel == "phi_mu" else phi_pallas_call
    call = make(n_grid=layout.n_grid, block_nnz=layout.block_nnz,
                block_rows=layout.block_rows, n_rows_pad=layout.n_rows_pad,
                rank_pad=r_pad, eps=1e-10, interpret=True)
    out = call(
        jnp.asarray(layout.grid_rb, jnp.int32),
        vals_e.reshape(-1, 1),
        jnp.asarray(layout.local_rows, jnp.int32).reshape(-1, 1),
        jnp.pad(pi_e, ((0, 0), (0, r_pad - r))),
        jnp.pad(b, ((0, layout.n_rows_pad - b.shape[0]), (0, r_pad - r))),
    )
    if kernel == "phi_mu":
        mu_pad, kkt = out
        return mu_pad[:, :r].astype(b.dtype), jnp.max(kkt)
    return out[:, :r].astype(b.dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("kernel", ["phi", "phi_mu"])
def test_phi_prepared_operands_bitwise(kernel, dtype):
    """The kernels on operands built once equal the per-call plumbing
    bit for bit: the kernel reads the same arrays."""
    _, mv, pi, b = _mode_data((40, 30, 25), 1500, 8, 0, seed=3)
    layout = build_blocked_layout(np.asarray(mv.rows), mv.n_rows, 64, 32)
    vals_e, pi_e = expand_to_layout(layout, mv.sorted_vals.astype(dtype),
                                    pi.astype(dtype))
    b = b.astype(dtype)
    ops = phi_operands(vals_e, pi_e, layout.local_rows, layout.grid_rb)
    assert ops.pi.shape == (vals_e.shape[0], 128)
    assert ops.vals.shape == ops.local_rows.shape == (vals_e.shape[0], 1)
    assert ops.local_rows.dtype == ops.grid_rb.dtype == jnp.int32
    run = phi_mu_blocked if kernel == "phi_mu" else phi_blocked
    got = jax.tree.leaves(run(layout, ops, b, interpret=True))
    want = jax.tree.leaves(_per_call_kernel(kernel, layout, vals_e, pi_e, b))
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))


@pytest.mark.parametrize("shape,nnz", [((12, 9, 7), 300),
                                       ((10, 6, 5, 4), 400)])
def test_cpapr_pallas_hoisted_operands_bitwise(shape, nnz, monkeypatch):
    """A Pallas solve with the kernel operands built once per mode update
    gives the factors of one that builds them in every kernel call."""
    t, _ = random_poisson_tensor(jax.random.PRNGKey(7), shape, nnz, rank=3)
    cfg = CPAPRConfig(rank=3, strategy="pallas", max_outer=3)
    hoisted = cpapr_mu(t, 3, key=jax.random.PRNGKey(8), config=cfg)

    dropped = []

    def per_call(fn):
        def call(*args, operands=None, **kw):  # drop the hoisted operands
            dropped.append(operands is not None)
            return fn(*args, **kw)
        return call

    monkeypatch.setattr(cpapr, "phi_from_rows", per_call(cpapr.phi_from_rows))
    monkeypatch.setattr(cpapr, "phi_mu_step", per_call(cpapr.phi_mu_step))
    per = cpapr_mu(t, 3, key=jax.random.PRNGKey(8), config=cfg)
    assert dropped and all(dropped)
    for a, c in zip(hoisted.ktensor.factors, per.ktensor.factors):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
    np.testing.assert_array_equal(np.asarray(hoisted.ktensor.lam),
                                  np.asarray(per.ktensor.lam))


def _plumbing(lowered) -> dict:
    """Operand shapes of the ``pad``, ``reshape`` and ``broadcast_in_dim``
    ops of a lowered program, split by whether a ``while`` holds them
    (calls are followed)."""
    from jax._src.lib.mlir import ir

    module = lowered.compiler_ir("stablehlo")
    funcs = {ir.StringAttr(f.attributes["sym_name"]).value: f
             for f in module.body.operations}
    found = {"loop": [], "outside": []}

    def visit(op, in_loop):
        name = op.operation.name
        if name in ("stablehlo.pad", "stablehlo.reshape",
                    "stablehlo.broadcast_in_dim"):
            shape = tuple(ir.RankedTensorType(op.operands[0].type).shape)
            found["loop" if in_loop else "outside"].append((name, shape))
        if name == "func.call":
            callee = ir.FlatSymbolRefAttr(op.attributes["callee"]).value
            walk(funcs[callee], in_loop)
        walk(op, in_loop or name == "stablehlo.while")

    def walk(op, in_loop):
        for region in op.regions:
            for block in region.blocks:
                for inner in block.operations:
                    visit(inner, in_loop)

    walk(funcs["main"], False)
    return found


def test_pallas_mode_update_loop_holds_no_nnz_plumbing():
    """The inner MU loop of a Pallas mode update reads the kernel's
    operands as built before it: no pad, reshape or broadcast of an
    nnz-length array is left in the loop, only B-sized ones."""
    t, _ = random_poisson_tensor(jax.random.PRNGKey(0), (12, 9, 7), 300,
                                 rank=3)
    kt = random_ktensor(jax.random.PRNGKey(1), (12, 9, 7), 3).normalize()
    mv = sort_mode(t, 0)
    layout = build_blocked_layout(np.asarray(mv.rows), mv.n_rows, 64, 8)
    n = layout.n_grid * layout.block_nnz
    assert n not in (layout.n_rows_pad, mv.n_rows, 128)
    cfg = CPAPRConfig(rank=3, strategy="pallas")
    update, _ = cpapr._make_mode_update(mv, cfg, "pallas", layout)
    found = _plumbing(update.func.lower(*update.args, tuple(kt.factors),
                                        kt.lam))
    assert [op for op in found["loop"] if op[1][:1] == (n,)] == []
    # the loop pads only B, to the kernel's window
    assert {s for op, s in found["loop"] if op == "stablehlo.pad"} \
        == {(mv.n_rows, 3)}
    # built once, before the loop: the (N, 1) reshapes and the lane pad
    assert ("stablehlo.pad", (n, 3)) in found["outside"]
    assert ("stablehlo.reshape", (n,)) in found["outside"]


@pytest.mark.parametrize("bn,br", [(32, 32), (128, 16), (64, 128)])
def test_mttkrp_pallas_sweep(bn, br):
    t, mv, kr, _ = _mode_data((50, 30, 40), 2000, 8, 0, seed=4)
    layout = build_blocked_layout(np.asarray(mv.rows), mv.n_rows, bn, br)
    vals_e, kr_e = expand_to_layout(layout, mv.sorted_vals, kr)
    out = mttkrp_blocked(layout, vals_e, kr_e)[: mv.n_rows]
    ref = mttkrp_ref(mv.rows, mv.sorted_vals, kr, mv.n_rows)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=1e-5)


@pytest.mark.parametrize("op", STREAM_OPS)
@pytest.mark.parametrize("n,block_rows", [(128 * 256, 256), (128 * 512, 64)])
def test_stream_pallas_sweep(op, n, block_rows):
    b = jax.random.normal(jax.random.PRNGKey(0), (n,))
    c = jax.random.normal(jax.random.PRNGKey(1), (n,))
    out = stream_op(op, b, c, block_rows=block_rows)
    ref = stream_ref(op, b, c)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_stream_rejects_untiled_lengths():
    """Lengths that are not a multiple of 128*block_rows used to be
    silently truncated (the bandwidth figure quietly covered fewer
    bytes); now they are rejected at the boundary with the tile size
    in the message."""
    b = jnp.ones((128 * 256,), jnp.float32)
    with pytest.raises(ValueError, match="128-lane"):
        stream_op("copy", b[:100])
    with pytest.raises(ValueError, match=r"128\*block_rows=32768"):
        stream_op("copy", b[: 128 * 8], block_rows=256)
    with pytest.raises(ValueError, match="1-D"):
        stream_op("copy", b.reshape(-1, 128))
    with pytest.raises(ValueError, match="unknown STREAM op"):
        stream_op("daxpy", b)
    # exact tile multiple still works with a non-default block_rows
    out = stream_op("scale", jnp.ones((128 * 8,), jnp.float32),
                    block_rows=8, s=2.0)
    np.testing.assert_array_equal(np.asarray(out), np.full(128 * 8, 2.0))


def test_stream_two_array_ops_require_c():
    """add/triad read two distinct arrays; c=None used to alias b and
    silently compute b+b / b+s*b."""
    b = jnp.ones((128 * 256,), jnp.float32)
    with pytest.raises(ValueError, match="aliasing"):
        stream_op("add", b)
    with pytest.raises(ValueError, match="aliasing"):
        stream_op("triad", b)
    with pytest.raises(ValueError, match="does not match"):
        stream_op("add", b, b[:-128])
    # one-array ops never needed c and still accept its absence
    np.testing.assert_array_equal(np.asarray(stream_op("copy", b)),
                                  np.asarray(b))


def test_ssd_chunked_vs_ref():
    from repro.models.mamba2 import ssd_chunked, ssd_ref
    key = jax.random.PRNGKey(2)
    for (B, S, H, P, G, N, chunk) in [(2, 24, 4, 8, 2, 8, 8),
                                      (1, 32, 8, 16, 1, 4, 16),
                                      (3, 16, 2, 4, 2, 8, 4)]:
        ks = jax.random.split(key, 7)
        key = ks[6]
        x = jax.random.normal(ks[0], (B, S, H, P))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
        a_log = jax.random.normal(ks[2], (H,)) * 0.5
        b = jax.random.normal(ks[3], (B, S, G, N))
        c = jax.random.normal(ks[4], (B, S, G, N))
        d = jax.random.normal(ks[5], (H,))
        h0 = jax.random.normal(ks[0], (B, H, P, N)) * 0.1
        y1, hf1 = ssd_chunked(x, dt, a_log, b, c, d, chunk, h0=h0)
        y2, hf2 = ssd_ref(x, dt, a_log, b, c, d, h0=h0)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(hf1), np.asarray(hf2),
                                   rtol=1e-4, atol=1e-4)


def test_rg_lru_vs_ref():
    from repro.models.rglru import rg_lru, rg_lru_ref
    key = jax.random.PRNGKey(5)
    B, S, W = 2, 20, 12
    x = jax.random.normal(key, (B, S, W))
    p = {
        "w_a": jax.random.normal(jax.random.PRNGKey(6), (W, W)) * 0.3,
        "b_a": jnp.zeros(W),
        "w_x": jax.random.normal(jax.random.PRNGKey(7), (W, W)) * 0.3,
        "b_x": jnp.zeros(W),
        "lam": jnp.ones(W),
    }
    h0 = jax.random.normal(jax.random.PRNGKey(8), (B, W))
    for h_init in (None, h0):
        y1, hf1 = rg_lru(x, p, h0=h_init)
        y2, hf2 = rg_lru_ref(x, p, h0=h_init)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(hf1), np.asarray(hf2),
                                   rtol=1e-5, atol=1e-5)
