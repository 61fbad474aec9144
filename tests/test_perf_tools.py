"""Roofline model, PPA harness, policy search, FROSTT tensors."""
import jax
import numpy as np
import pytest

from repro.core.policy import (
    PhiPolicy,
    default_policy,
    grid_search,
    heuristic_policy,
    policy_grid,
)
from repro.data.tensors import FROSTT, make_tensor
from repro.perf.ppa import PERTURBATIONS, run_ppa
from repro.perf.roofline import (
    HARDWARE,
    attainable_gflops,
    operational_intensity_phi,
    roofline_terms,
)


def test_roofline_paper_bounds():
    """Reproduce the paper's headline bounds: 41.5 GF/s CPU, 60 GF/s GPU
    from the stated intensities (Sec. 3.2)."""
    cpu = HARDWARE["e5_2690v4_dual"]
    gpu = HARDWARE["k80"]
    np.testing.assert_allclose(attainable_gflops(0.27, cpu), 41.472, rtol=1e-3)
    np.testing.assert_allclose(attainable_gflops(0.125, gpu), 60.0, rtol=1e-3)
    # both far below peak => memory-bound (the paper's conclusion)
    assert attainable_gflops(0.27, cpu) < 0.05 * cpu.peak_flops / 1e9
    assert attainable_gflops(0.125, gpu) < 0.05 * gpu.peak_flops / 1e9


def test_operational_intensity_literal_formulas():
    """Eqs. 3-8 evaluated literally (see roofline.py note on the paper's
    stated 0.125/0.27 values)."""
    i_gpu = operational_intensity_phi(16, "gpu")
    i_cpu = operational_intensity_phi(16, "cpu")
    assert 0 < i_gpu < 0.2
    assert 0 < i_cpu < 0.2
    # R -> inf limit of W/Q: 4R/5R = 0.8 flop/word = 0.1 flop/byte
    i_inf = operational_intensity_phi(10_000, "gpu")
    np.testing.assert_allclose(i_inf, 0.8 / 8, rtol=1e-3)


def test_roofline_terms_dominance():
    rt = roofline_terms(hlo_flops=1e15, hlo_bytes=1e12, collective_bytes=1e5,
                        n_chips=256, model_flops=8e14)
    assert rt.dominant == "compute"
    assert rt.bound_s == rt.compute_s
    assert 0.7 < rt.useful_flops_ratio <= 1.0
    rt2 = roofline_terms(hlo_flops=1e12, hlo_bytes=1e12, collective_bytes=1e12,
                         n_chips=256)
    assert rt2.dominant == "collective"


def test_ppa_runs_all_perturbations(small_tensor):
    t, kt = small_tensor
    res = run_ppa(t, kt, mode=0, strategy="segment", iters=2)
    assert set(res.seconds) == {str(p) for p in PERTURBATIONS}
    assert all(v > 0 for v in res.seconds.values())
    assert res.speedup["None"] == 1.0


def test_policy_grid_and_search(small_tensor):
    t, kt = small_tensor
    policies = policy_grid(strategies=("segment", "blocked"),
                           block_nnz=(64, 128), block_rows=(32, 64))
    assert len(policies) == 1 + 4
    import time
    fake = {p.label(): i for i, p in enumerate(policies)}
    ranked = grid_search(lambda p: float(fake[p.label()]), policies)
    assert ranked[0][1] <= ranked[-1][1]


def test_heuristic_policy_tracks_duplication():
    # high duplication (nnz >> rows) => bigger block_nnz than low duplication
    hi = heuristic_policy(nnz=10**6, n_rows=100, rank=16, platform="tpu")
    lo = heuristic_policy(nnz=10**4, n_rows=10**4, rank=16, platform="tpu")
    assert heuristic_policy(10**6, 100, 16, platform="cpu").strategy == "segment"
    assert hi.block_nnz >= lo.block_nnz


def test_frostt_tensors_shapes():
    for name, (dims, nnz) in FROSTT.items():
        assert len(dims) in (3, 4, 5)
    t, kt = make_tensor("uber", scale=0.003)
    assert t.shape == FROSTT["uber"][0]
    assert t.nnz >= 1000
    assert float(t.values.min()) > 0


def test_make_tensor_is_the_same_in_every_process():
    """The draw is a function of (name, scale, rank, seed) alone: Python
    salts ``hash`` per process, so a seed built from it drew a new tensor
    every run."""
    import hashlib
    import os
    import subprocess
    import sys

    script = (
        "import hashlib, numpy as np\n"
        "from repro.data.tensors import make_tensor\n"
        "t, _ = make_tensor('uber', scale=0.001, seed=3)\n"
        "h = hashlib.sha256(np.asarray(t.indices).tobytes()"
        " + np.asarray(t.values).tobytes())\n"
        "print(t.nnz, h.hexdigest())\n"
    )
    src = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")
    outs = []
    for hash_seed in ("1", "2"):
        env = dict(os.environ, PYTHONPATH=src, PYTHONHASHSEED=hash_seed,
                   JAX_PLATFORMS="cpu")
        out = subprocess.run([sys.executable, "-c", script], env=env,
                             capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        outs.append(out.stdout.split()[-2:])
    assert outs[0] == outs[1]
    t, _ = make_tensor("uber", scale=0.001, seed=3)
    h = hashlib.sha256(np.asarray(t.indices).tobytes()
                       + np.asarray(t.values).tobytes())
    assert outs[0] == [str(t.nnz), h.hexdigest()]
    other, _ = make_tensor("uber", scale=0.001, seed=4)
    assert not np.array_equal(np.asarray(other.indices),
                              np.asarray(t.indices))
