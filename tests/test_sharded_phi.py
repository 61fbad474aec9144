"""Multi-device sharded Phi: layout invariants, fused-step equivalence,
collective-byte accounting vs the analytic O(I_n * R) bound, and the
warned single-device fallbacks.  (Cross-strategy oracle conformance —
including the reduce-scatter combine — lives in the registry-driven
tests/test_conformance.py.)"""
import os
import subprocess
import sys
import warnings

import jax
import numpy as np
import pytest

from repro.core import (
    cpapr_mu,
    CPAPRConfig,
    phi_from_rows,
    phi_mu_step,
    sort_mode,
)
from repro.core.layout import build_blocked_layout, shard_blocked_layout
from repro.core.phi import expand_to_shards
from repro.core.pi import pi_rows
from repro.core.policy import PhiPolicy

from conftest import dense_phi_reference

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mode_problem(small_tensor, mode=0, bn=64, br=8):
    t, kt = small_tensor
    mv = sort_mode(t, mode)
    pi = pi_rows(mv.sorted_idx, kt.factors, mode)
    b = kt.factors[mode] * kt.lam[None, :]
    base = build_blocked_layout(np.asarray(mv.rows), mv.n_rows, bn, br)
    return mv, pi, b, base


# ---------------------------------------------------------------------------
# Sharded layout invariants
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_shards", [1, 2, 3, 4])
@pytest.mark.parametrize("bn,br", [(64, 8), (32, 4)])
def test_sharded_layout_partition_invariants(small_tensor, n_shards, bn, br):
    """Shards partition the nonzeros; row-block ranges are contiguous and
    disjoint; per-shard arrays are uniform; grid_rb stays non-decreasing."""
    mv, _, _, _ = _mode_problem(small_tensor)
    base = build_blocked_layout(np.asarray(mv.rows), mv.n_rows, bn, br)
    sl = shard_blocked_layout(base, n_shards)
    assert sl.n_shards == n_shards
    # contiguous disjoint row-block cover
    assert int(sl.rb_start[0]) == 0
    np.testing.assert_array_equal(
        sl.rb_start[1:], sl.rb_start[:-1] + sl.rb_count[:-1]
    )
    assert int(sl.rb_start[-1] + sl.rb_count[-1]) == base.n_row_blocks
    assert np.all(sl.rb_count >= 1)
    # every nonzero appears exactly once across all shards' valid slots
    gathered = np.sort(sl.gather[sl.valid])
    np.testing.assert_array_equal(gathered, np.arange(mv.nnz))
    assert int(sl.shard_nnz.sum()) == mv.nnz
    # uniform shapes, local grid_rb in range and non-decreasing
    assert sl.gather.shape == (n_shards, sl.n_grid_shard * bn)
    assert sl.grid_rb.shape == (n_shards, sl.n_grid_shard)
    assert np.all(sl.grid_rb >= 0) and np.all(sl.grid_rb < sl.n_rb_shard)
    assert np.all(np.diff(sl.grid_rb, axis=1) >= 0)
    # every local row block of every shard is visited at least once
    for s in range(n_shards):
        assert set(sl.grid_rb[s].tolist()) == set(range(sl.n_rb_shard))
    # valid slots land in their shard's global row range
    for s in range(n_shards):
        rows_of_slot = (
            (sl.rb_start[s] + np.repeat(sl.grid_rb[s], bn)) * br
            + sl.local_rows[s]
        )
        v = sl.valid[s]
        np.testing.assert_array_equal(
            rows_of_slot[v], np.asarray(mv.rows)[sl.gather[s][v]]
        )
    assert sl.buf_rows >= base.n_rows_pad


def test_shard_layout_rejects_too_many_shards(small_tensor):
    mv, _, _, _ = _mode_problem(small_tensor)
    base = build_blocked_layout(np.asarray(mv.rows), mv.n_rows, 64, 256)
    assert base.n_row_blocks == 1
    with pytest.raises(ValueError, match="n_row_blocks"):
        shard_blocked_layout(base, 2)


# ---------------------------------------------------------------------------
# Fused-step equivalence (cross-strategy oracle conformance now lives in
# tests/test_conformance.py — one registry table instead of per-file loops)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_shards", [1, 2, 3, 4])
@pytest.mark.parametrize("local_strategy", ["blocked", "pallas"])
def test_sharded_phi_mu_step_matches_unfused(small_tensor, n_shards,
                                             local_strategy):
    """Fused sharded (B', viol) == unfused scatter composition, for both
    local compute flavours (jnp emulation and the Pallas kernel)."""
    mv, pi, b, base = _mode_problem(small_tensor)
    sl = shard_blocked_layout(base, n_shards)
    tol = 1e-4
    phi = phi_from_rows(mv.rows, mv.sorted_vals, pi, b, mv.n_rows,
                        strategy="scatter")
    viol_ref = np.max(np.abs(np.minimum(np.asarray(b), 1.0 - np.asarray(phi))))
    b_ref = np.asarray(b) * np.asarray(phi) if viol_ref > tol else np.asarray(b)
    out_b, out_v = phi_mu_step(mv.rows, mv.sorted_vals, pi, b, mv.n_rows,
                               tol=tol, strategy="sharded", layout=sl,
                               local_strategy=local_strategy)
    np.testing.assert_allclose(float(out_v), viol_ref, rtol=3e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out_b), b_ref, rtol=3e-5, atol=1e-5)


def test_sharded_pre_expanded_inputs_match(small_tensor):
    """Hoisted expand_to_shards arrays give the same answer as re-expansion."""
    mv, pi, b, base = _mode_problem(small_tensor)
    sl = shard_blocked_layout(base, 3)
    vals_es, pi_es = expand_to_shards(sl, mv.sorted_vals, pi)
    a = phi_from_rows(mv.rows, mv.sorted_vals, pi, b, mv.n_rows,
                      strategy="sharded", layout=sl)
    h = phi_from_rows(mv.rows, mv.sorted_vals, pi, b, mv.n_rows,
                      strategy="sharded", layout=sl,
                      vals_e=vals_es, pi_e=pi_es)
    np.testing.assert_allclose(np.asarray(a), np.asarray(h),
                               rtol=1e-6, atol=1e-7)


def test_cpapr_sharded_matches_segment(small_tensor):
    """Full solver equivalence: sharded strategy == segment strategy."""
    t, _ = small_tensor
    ref = cpapr_mu(t, rank=4, config=CPAPRConfig(
        rank=4, max_outer=3, strategy="segment", track_loglik=False))
    res = cpapr_mu(t, rank=4, config=CPAPRConfig(
        rank=4, max_outer=3, strategy="sharded", n_shards=3,
        track_loglik=False))
    for a, b in zip(ref.ktensor.factors, res.ktensor.factors):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(ref.kkt_history, res.kkt_history, rtol=1e-4)


# ---------------------------------------------------------------------------
# Warned single-device fallbacks (instead of cryptic reshape errors)
# ---------------------------------------------------------------------------


def test_sharded_phi_falls_back_when_too_few_row_blocks(small_tensor,
                                                        monkeypatch):
    """More shards requested than row blocks exist: warn + single-device
    blocked result, never a cryptic reshape error."""
    mv, pi, b, _ = _mode_problem(small_tensor)
    monkeypatch.setattr("repro.core.phi._default_shard_count",
                        lambda mesh: 4096)
    ref = dense_phi_reference(mv.rows, mv.sorted_vals, pi, b, mv.n_rows)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        out = phi_from_rows(mv.rows, mv.sorted_vals, pi, b, mv.n_rows,
                            strategy="sharded")
        bs, vs = phi_mu_step(mv.rows, mv.sorted_vals, pi, b, mv.n_rows,
                             strategy="sharded")
    assert any("falling back" in str(x.message) for x in w)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=3e-5, atol=1e-5)
    viol = np.max(np.abs(np.minimum(np.asarray(b, np.float64), 1.0 - ref)))
    np.testing.assert_allclose(float(vs), viol, rtol=3e-5, atol=1e-5)
    assert bs.shape == b.shape


def test_cpapr_sharded_falls_back_with_warning(small_tensor):
    t, _ = small_tensor
    cfg = CPAPRConfig(rank=4, max_outer=2, strategy="sharded", n_shards=64,
                      track_loglik=False,
                      policy=PhiPolicy(strategy="blocked", block_nnz=64,
                                       block_rows=256))
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        res = cpapr_mu(t, rank=4, config=cfg)
    assert any("falling back" in str(x.message) for x in w)
    ref = cpapr_mu(t, rank=4, config=CPAPRConfig(
        rank=4, max_outer=2, strategy="segment", track_loglik=False))
    np.testing.assert_allclose(res.kkt_history, ref.kkt_history, rtol=1e-4)


# ---------------------------------------------------------------------------
# Real-mesh equivalence + collective accounting (forced-device subprocesses)
# ---------------------------------------------------------------------------


def _run(script: str, devices: int, timeout: int = 560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


HLO_SCRIPT = """
import jax, numpy as np
from repro.core.sparse_tensor import random_poisson_tensor, sort_mode
from repro.core.pi import pi_rows
from repro.core.layout import build_blocked_layout, shard_blocked_layout
from repro.core.phi import expand_to_shards
from repro.core.distributed import (_phi_sharded_buf, make_phi_mesh,
                                    sharded_combine_bytes)
from repro.perf.hlo import (collective_stats, allreduce_wire_bytes,
                            phi_combine_wire_bound)

S = jax.device_count()
assert S == 4
t, kt = random_poisson_tensor(jax.random.PRNGKey(0), (40, 30, 25),
                              nnz=1500, rank=4)
mv = sort_mode(t, 0)
pi = pi_rows(mv.sorted_idx, kt.factors, 0)
b = kt.factors[0] * kt.lam[None, :]
base = build_blocked_layout(np.asarray(mv.rows), mv.n_rows, 64, 8)
sl = shard_blocked_layout(base, S)
mesh = make_phi_mesh(S)
vals_es, pi_es = expand_to_shards(sl, mv.sorted_vals, pi)
txt = _phi_sharded_buf.lower(sl, vals_es, pi_es, b, 1e-10, mesh,
                             "blocked").compile().as_text()
cs = collective_stats(txt, n_participants=S)
assert cs.by_kind_count.get("all-reduce", 0) >= 1, cs.by_kind_count
expected = allreduce_wire_bytes(sharded_combine_bytes(sl, 4), S)
bound = phi_combine_wire_bound(mv.n_rows, 4, S, block_rows=8)
print("wire", cs.wire_bytes, "expected", expected, "bound", bound)
# the measured combine must match the psum of the combine buffer ...
assert abs(cs.wire_bytes - expected) <= 0.1 * expected, (cs.wire_bytes,
                                                         expected)
# ... and stay under the analytic O(I_n * R) bound
assert 0 < cs.wire_bytes <= bound, (cs.wire_bytes, bound)
print("HLO_OK")
"""


def test_sharded_combine_collective_bytes_within_bound():
    """repro.perf.hlo accounting of the sharded Phi combine: exactly the
    psum of the combine buffer, under the analytic O(I_n * R) bound."""
    assert "HLO_OK" in _run(HLO_SCRIPT, devices=4)


DIST_FALLBACK_SCRIPT = """
import warnings
import jax, numpy as np
from repro.core import cpapr_mu, CPAPRConfig, random_poisson_tensor, \
    random_ktensor
from repro.core.distributed import DistCPAPRConfig, dist_cpapr_mu
t, _ = random_poisson_tensor(jax.random.PRNGKey(0), (24, 18, 15),
                             nnz=900, rank=3)
init = random_ktensor(jax.random.PRNGKey(1), t.shape, 3)
mesh = jax.make_mesh((2, 2), ("data", "model"))
with warnings.catch_warnings(record=True) as w:
    warnings.simplefilter("always")
    # rank 3 is not divisible by the model axis (2): must warn + fall back
    kt_d, hist = dist_cpapr_mu(t, 3, mesh, init=init,
                               config=DistCPAPRConfig(rank=3, max_outer=2,
                                                      max_inner=3))
assert any("falling back" in str(x.message) for x in w), \
    [str(x.message) for x in w]
res = cpapr_mu(t, 3, init=init,
               config=CPAPRConfig(rank=3, max_outer=2, max_inner=3,
                                  track_loglik=False))
for fd, fs in zip(kt_d.factors, res.ktensor.factors):
    np.testing.assert_allclose(np.asarray(fd), np.asarray(fs),
                               rtol=2e-4, atol=2e-5)
print("FALLBACK_OK")
"""


def test_dist_cpapr_invalid_mesh_falls_back_single_device():
    """dist_cpapr_mu with an unshardable mesh (rank % model != 0) warns and
    falls back to one device instead of dying in a reshape."""
    assert "FALLBACK_OK" in _run(DIST_FALLBACK_SCRIPT, devices=4)


# ---------------------------------------------------------------------------
# In-process multi-device coverage (auto-skipped on 1 device)
# ---------------------------------------------------------------------------


@pytest.mark.multidevice
def test_mesh_matches_emulation_in_process(small_tensor):
    """Real shard_map + psum == the one-device emulation, bitwise-close."""
    from repro.core.distributed import make_phi_mesh, phi_sharded

    mv, pi, b, base = _mode_problem(small_tensor)
    n = min(jax.device_count(), base.n_row_blocks)
    sl = shard_blocked_layout(base, n)
    vals_es, pi_es = expand_to_shards(sl, mv.sorted_vals, pi)
    emu = phi_sharded(sl, vals_es, pi_es, b)
    real = phi_sharded(sl, vals_es, pi_es, b, mesh=make_phi_mesh(n))
    np.testing.assert_allclose(np.asarray(real), np.asarray(emu),
                               rtol=1e-6, atol=1e-7)
