"""PR-7 analytic-model stack: spec-aware roofline fixes, the PPA
baseline fallback, nnz-invariant operational intensity, the small/large
instruction split in HLO cost extraction, cutout extraction, and the
model-guided autotuner's prune/serve protocol (including the
model-vs-measured pipeline on fixtures with known winners)."""
import dataclasses

import jax
import numpy as np
import pytest

from conftest import dense_phi_reference
from repro.core.cpapr import ModeCutout, extract_mode_cutout
from repro.core.phi import phi_from_rows
from repro.core.policy import (
    PhiPolicy,
    grid_search,
    model_ambiguous_prefix,
    model_top_k,
)
from repro.core.sparse_tensor import random_poisson_tensor
from repro.perf.autotune import Autotuner, candidate_policies, policy_key
from repro.perf.ppa import run_ppa
from repro.perf.roofline import (
    HARDWARE,
    HardwareSpec,
    RooflineTerms,
    detect_hardware_spec,
    operational_intensity_phi,
    roofline_terms,
)


# ---------------------------------------------------------------------------
# mfu_bound vs the spec that built the terms (satellite 1)
# ---------------------------------------------------------------------------


def test_mfu_bound_uses_spec_peak_not_tpu_constant():
    """A compute-bound host_cpu module that uses every peak FLOP must get
    mfu_bound ~ 1.0 — the old module-level TPU peak made it ~2.5e-4."""
    hw = HARDWARE["host_cpu"]
    terms = roofline_terms(hlo_flops=hw.peak_flops, hlo_bytes=1.0,
                           collective_bytes=0.0, n_chips=1, hw=hw,
                           model_flops=hw.peak_flops)
    assert terms.peak_flops == hw.peak_flops
    assert terms.mfu_bound == pytest.approx(1.0)


def test_mfu_bound_scales_across_specs():
    """Identical flops/bytes: the K80 spec must not be judged against the
    TPU peak (ratio of bounds == ratio of time, peaks held per-spec)."""
    args = dict(hlo_flops=1e12, hlo_bytes=1e6, collective_bytes=0.0,
                n_chips=1, model_flops=1e12)
    t_tpu = roofline_terms(hw=HARDWARE["tpu_v5e"], **args)
    t_k80 = roofline_terms(hw=HARDWARE["k80"], **args)
    assert t_tpu.mfu_bound == pytest.approx(1.0)
    assert t_k80.mfu_bound == pytest.approx(1.0)


def test_roofline_terms_direct_construction_default_peak():
    """Direct RooflineTerms(...) constructions predating the field keep
    the TPU default and stay finite."""
    t = RooflineTerms(compute_s=1.0, memory_s=0.5, collective_s=0.0,
                      hlo_flops=1.0, hlo_bytes=1.0, collective_bytes=0.0,
                      model_flops=1.0, n_chips=1)
    assert t.peak_flops == HARDWARE["tpu_v5e"].peak_flops
    assert t.mfu_bound > 0


# ---------------------------------------------------------------------------
# detect_hardware_spec (tentpole: spec from the actual backend)
# ---------------------------------------------------------------------------


def test_detect_hardware_spec_resolution(monkeypatch):
    monkeypatch.delenv("REPRO_HARDWARE_SPEC", raising=False)
    assert detect_hardware_spec("cpu") is HARDWARE["host_cpu"]
    # accelerators are keyed by device_kind, as jax reports it
    assert detect_hardware_spec("tpu", device_kind="TPU v5 lite") \
        is HARDWARE["tpu_v5e"]
    assert detect_hardware_spec("gpu", device_kind="Tesla K80") \
        is HARDWARE["k80"]
    # no argument: resolves the real backend without raising
    assert detect_hardware_spec() in HARDWARE.values()


@pytest.mark.parametrize("platform,kind", [
    ("tpu", "TPU v4"), ("tpu", "TPU v99"), ("gpu", "NVIDIA H100"),
])
def test_detect_hardware_spec_unknown_kind_raises(monkeypatch, platform,
                                                  kind):
    """An unlisted device has no peaks: detection refuses to guess."""
    monkeypatch.delenv("REPRO_HARDWARE_SPEC", raising=False)
    with pytest.raises(ValueError, match="device kind"):
        detect_hardware_spec(platform, device_kind=kind)


def test_detect_hardware_spec_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_HARDWARE_SPEC", "e5_2690v4_dual")
    assert detect_hardware_spec("tpu") is HARDWARE["e5_2690v4_dual"]
    monkeypatch.setenv("REPRO_HARDWARE_SPEC", "not_a_spec")
    assert detect_hardware_spec("gpu", device_kind="Tesla K80") \
        is HARDWARE["k80"]


# ---------------------------------------------------------------------------
# operational_intensity_phi nnz-invariance (satellite 3)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("variant", ["gpu", "cpu"])
@pytest.mark.parametrize("rank", [8, 32])
def test_operational_intensity_nnz_invariant(variant, rank):
    base = operational_intensity_phi(rank, variant=variant, nnz=10**4)
    assert base > 0
    for nnz in (10**5, 10**6, 10**8):
        oi = operational_intensity_phi(rank, variant=variant, nnz=nnz)
        assert oi == pytest.approx(base, rel=1e-3), (
            f"intensity must not depend on nnz: {oi} vs {base} at nnz={nnz}"
        )


# ---------------------------------------------------------------------------
# run_ppa without the unperturbed baseline (satellite 2)
# ---------------------------------------------------------------------------


def test_run_ppa_without_baseline_measures_denominator(small_tensor):
    t, kt = small_tensor
    res = run_ppa(t, kt, perturbations=("no_conflict",), iters=1)
    # the old code raised KeyError 'None' here
    assert set(res.seconds) == {"no_conflict"}
    assert set(res.speedup) == {"no_conflict"}
    assert np.isfinite(res.speedup["no_conflict"])
    assert res.speedup["no_conflict"] > 0


def test_run_ppa_with_baseline_unchanged(small_tensor):
    t, kt = small_tensor
    res = run_ppa(t, kt, perturbations=(None, "perfect_reuse"), iters=1)
    assert res.speedup["None"] == pytest.approx(1.0)
    assert set(res.seconds) == {"None", "perfect_reuse"}


# ---------------------------------------------------------------------------
# model_top_k / model_ambiguous_prefix (prune/serve protocol units)
# ---------------------------------------------------------------------------


def _p(strategy, bn=256, br=256):
    return PhiPolicy(strategy=strategy, block_nnz=bn, block_rows=br)


def test_model_top_k_family_slots():
    scored = [
        (_p("blocked", 64, 16), 1.0),
        (_p("blocked", 128, 16), 1.1),
        (_p("blocked", 256, 16), 1.2),
        (_p("segment"), 5.0),
        (_p("scatter"), 6.0),
    ]
    top = model_top_k(scored, k=3)
    fams = [p.strategy for p, _ in top]
    # one slot per family before global ranking fills the rest
    assert set(fams) == {"blocked", "segment", "scatter"}
    assert top[0][0] == _p("blocked", 64, 16)
    # without family slots: the 3 fastest predictions win
    flat = model_top_k(scored, k=3, per_family=False)
    assert [p.strategy for p, _ in flat] == ["blocked"] * 3


def test_model_top_k_drops_nonfinite_and_caps():
    scored = [(_p("segment"), float("inf")), (_p("scatter"), 2.0),
              (_p("blocked"), float("nan")), (_p("blocked", 64, 64), 1.0)]
    top = model_top_k(scored, k=10)
    assert len(top) == 2 and top[0][1] == 1.0
    assert model_top_k(scored, k=0) == []
    assert model_top_k([], k=3) == []


def test_model_ambiguous_prefix_margins():
    ranked = [(_p("blocked"), 1.0), (_p("segment"), 1.3), (_p("scatter"), 3.0)]
    # bound covers the runner-up but not the third
    prefix = model_ambiguous_prefix(ranked, bound_factor=1.5)
    assert [p.strategy for p, _ in prefix] == ["blocked", "segment"]
    # overwhelming margin: length-1 prefix => model-only serve
    assert len(model_ambiguous_prefix(ranked, bound_factor=1.2)) == 1
    # bound_factor below 1 is clamped to 1 (never excludes a tie)
    tied = [(_p("blocked"), 1.0), (_p("segment"), 1.0)]
    assert len(model_ambiguous_prefix(tied, bound_factor=0.5)) == 2
    assert model_ambiguous_prefix([], 2.0) == []


# ---------------------------------------------------------------------------
# cutout extraction (tentpole: tune the mode problem, not a whole solve)
# ---------------------------------------------------------------------------


def test_extract_mode_cutout_matches_solver_inputs(small_tensor):
    t, kt = small_tensor
    for mode in range(t.indices.shape[1]):
        cut = extract_mode_cutout(t, kt, mode)
        assert isinstance(cut, ModeCutout)
        assert cut.mode == mode and cut.rank == kt.rank
        assert cut.nnz == t.nnz == cut.rows.shape[0] == cut.vals.shape[0]
        assert cut.pi.shape == (t.nnz, kt.rank)
        assert cut.b.shape == (t.shape[mode], kt.rank)
        assert cut.n_rows == t.shape[mode]
        rows = np.asarray(cut.rows)
        assert (np.diff(rows) >= 0).all(), "cutout rows must be sorted"
        assert cut.stats.nnz == t.nnz
        np.testing.assert_allclose(
            np.asarray(cut.b),
            np.asarray(kt.factors[mode] * kt.lam[None, :]), rtol=1e-6)


def test_cutout_phi_matches_dense_oracle(small_tensor):
    """Phi computed from the cutout arrays is the solver's Phi — the
    cutout really is the mode problem, not an approximation of it."""
    t, kt = small_tensor
    cut = extract_mode_cutout(t, kt, 1)
    phi = phi_from_rows(cut.rows, cut.vals, cut.pi, cut.b,
                        n_rows=cut.n_rows, strategy="segment")
    ref = dense_phi_reference(cut.rows, cut.vals, cut.pi, cut.b, cut.n_rows)
    np.testing.assert_allclose(np.asarray(phi), ref, rtol=2e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# HLO cost extraction: the small/large instruction split
# ---------------------------------------------------------------------------


def test_module_costs_small_instruction_split(small_tensor):
    from repro.perf.autotune import _jit_mu_burst
    from repro.perf.hlo_costs import module_costs

    t, kt = small_tensor
    cut = extract_mode_cutout(t, kt, 0)
    comp = _jit_mu_burst.lower(
        cut.rows, cut.vals, cut.pi, cut.b, None, None,
        n_rows=cut.n_rows, strategy="segment", layout=None, burst=4,
    ).compile()
    mc = module_costs(comp.as_text())
    assert mc.exec_instructions > 0
    assert 0 < mc.exec_small_instructions <= mc.exec_instructions
    # the serial reduction loop dominates the executed-instruction count
    # on XLA:CPU and its per-row body results are small
    assert mc.exec_small_instructions >= 0.5 * mc.exec_instructions


# ---------------------------------------------------------------------------
# model-guided tuner: scoring, pruning, error recording, model-serve
# ---------------------------------------------------------------------------


def _cold_tuner(tmp_path, **kw):
    kw.setdefault("iters", 1)
    return Autotuner(cache_path=str(tmp_path / "cache.json"), warmup=0, **kw)


def test_model_guided_tuner_prunes_and_records_error(small_tensor, tmp_path):
    t, kt = small_tensor
    cut = extract_mode_cutout(t, kt, 0)
    tuner = _cold_tuner(tmp_path, model_guided=True)
    cands = candidate_policies(cut.nnz, cut.n_rows, cut.rank,
                               jax.default_backend(), stats=cut.stats)
    pol = tuner.policy_for_cutout(cut)
    assert pol in cands
    # pruning: at most top-K candidates were measured on a cold key
    assert tuner.n_probes <= tuner.model_top_k < len(cands)
    key = tuner.mode_key(cut.rows, cut.n_rows, cut.rank, stats=cut.stats)[0]
    e = tuner.cache.entries[key]
    assert e["source"] == "grid"
    assert e["probes"] <= tuner.model_top_k
    assert e["n_candidates"] == len(cands)
    assert e["model_pruned"] == len(cands) - e["probes"]
    # per-entry model error: the winner's estimate next to its measurement
    assert e["model_s"] > 0 and e["measured_s"] > 0
    stats = tuner.cache.model_error_stats()
    assert stats["n"] >= 1 and stats["median_ratio"] > 0


def test_model_guided_off_measures_everything(small_tensor, tmp_path):
    t, kt = small_tensor
    cut = extract_mode_cutout(t, kt, 0)
    tuner = _cold_tuner(tmp_path, model_guided=False)
    cands = candidate_policies(cut.nnz, cut.n_rows, cut.rank,
                               jax.default_backend(), stats=cut.stats)
    tuner.policy_for_cutout(cut)
    assert tuner.n_probes >= len(cands)  # >= because of probe retries


def _seed_calibration(cache, ratio=2.0, n=6):
    """Store n entries whose measured/model ratio is exactly ``ratio`` —
    zero dispersion, so the error bound collapses to its floor."""
    for i in range(n):
        cache.store(policy_key(100 + i, 50, 4, "cpu"),
                    PhiPolicy(strategy="segment"), 1e-3, "grid",
                    extra={"model_s": 1e-3 / ratio, "measured_s": 1e-3})


def test_model_serve_on_overwhelming_margin(tmp_path):
    tuner = _cold_tuner(tmp_path, model_guided=True)
    _seed_calibration(tuner.cache)
    stats = tuner.cache.model_error_stats()
    assert stats["n"] >= tuner.model_min_samples
    assert stats["p95_log_err"] == pytest.approx(0.0, abs=1e-12)
    a, b = _p("segment"), _p("blocked", 64, 16)
    # margin 10x >> floored bound (1.25 ** margin_factor): serve model-only
    served = tuner._model_serve_or_prune("k_serve", [(a, 1e-3), (b, 1e-2)],
                                         None, n_cands=8)
    assert served == a
    assert tuner.n_model_served == 1
    e = tuner.cache.entries["k_serve"]
    assert e["source"] == "model" and e["probes"] == 0
    assert e["model_margin"] == pytest.approx(10.0)
    assert e["calibration_n"] == stats["n"]
    # a model-served entry satisfies a later measuring tuner's lookup
    assert tuner.cache.lookup("k_serve", source=("grid", "model")) == a


def test_ambiguous_margin_is_measured_not_served(tmp_path):
    tuner = _cold_tuner(tmp_path, model_guided=True)
    _seed_calibration(tuner.cache)
    a, b = _p("segment"), _p("blocked", 64, 16)
    # margin 1.05 < bound: both candidates come back for measurement
    out = tuner._model_serve_or_prune("k_amb", [(a, 1.0), (b, 1.05)],
                                      None, n_cands=8)
    assert isinstance(out, list) and [p for p, _ in out] == [a, b]
    assert tuner.n_model_served == 0 and "k_amb" not in tuner.cache.entries


def test_no_serve_before_calibration(tmp_path):
    tuner = _cold_tuner(tmp_path, model_guided=True)
    a, b = _p("segment"), _p("blocked", 64, 16)
    out = tuner._model_serve_or_prune("k_cold", [(a, 1e-3), (b, 1.0)],
                                      None, n_cands=8)
    # no calibration data yet: even a 1000x margin must be measured
    assert isinstance(out, list)
    assert tuner.n_model_served == 0


# ---------------------------------------------------------------------------
# model-vs-measured pipeline on two fixtures with known winners
# ---------------------------------------------------------------------------

# (shape, nnz, mode): a hub-ish mode with many rows and short runs
# (scatter/segment territory) and a dense-rows mode with few rows and
# long runs (blocked territory — uber-shaped).
_FIXTURES = [
    ((1500, 40, 30), 3000, 0),
    ((48, 600, 50), 9000, 0),
]


@pytest.mark.parametrize("shape,nnz,mode", _FIXTURES)
def test_model_topk_contains_near_optimal_winner(shape, nnz, mode, tmp_path):
    """The pipeline contract behind the >=5x probe cut: measuring ONLY the
    model's top-K must find the full grid search's winner — the top-K
    spans every strategy family, and its best measured candidate is the
    grid winner (or statistically tied with it)."""
    t, kt = random_poisson_tensor(jax.random.PRNGKey(7), shape, nnz=nnz,
                                  rank=8)
    cut = extract_mode_cutout(t, kt, mode)
    tuner = _cold_tuner(tmp_path, model_guided=True, iters=2)
    cands = candidate_policies(cut.nnz, cut.n_rows, cut.rank,
                               jax.default_backend(), stats=cut.stats)
    scored, runners, errors = tuner._model_rank(
        cands, cut.rows, cut.vals, cut.pi, cut.b, cut.n_rows)
    assert len(scored) == len(cands), f"model scoring failed: {errors}"
    top = model_top_k(scored, k=3)

    # every strategy family is represented in the measured top-K
    assert {p.strategy for p, _ in top} == {p.strategy for p in cands}

    ranked = grid_search(
        lambda p: tuner._time_policy(p, cut.rows, cut.vals, cut.pi, cut.b,
                                     cut.n_rows,
                                     runner=runners.get(p.label())),
        cands,
    )
    meas = {p.label(): s for p, s, _ in ranked if np.isfinite(s)}
    grid_best_s = ranked[0][1]
    topk_best_s = min(meas[p.label()] for p, _ in top)
    # measuring only the top-K lands on the grid winner (to timing noise)
    assert topk_best_s <= 1.35 * grid_best_s, (
        f"model top-K missed the grid winner: best-in-K {topk_best_s:.2e} "
        f"vs grid best {grid_best_s:.2e} "
        f"({[(p.label(), round(s, 6)) for p, s in top]})"
    )
