"""Roofline model (paper Sec. 3.2, Eqs. 1-8) + the 3-term pod roofline.

Two uses:
  1. Paper-faithful: operational intensity of the Phi kernel (Eqs. 3-8)
     against a hardware balance line (Figs. 3-4).
  2. Framework-wide: for every (arch x shape x mesh) dry-run we derive
         compute term    = HLO_FLOPs   / (chips * peak_FLOPs)
         memory term     = HLO_bytes   / (chips * HBM_bw)
         collective term = coll_bytes  / (chips * link_bw)
     from the compiled artifact (cost_analysis + HLO parse).
"""
from __future__ import annotations

import dataclasses

__all__ = [
    "HardwareSpec",
    "HARDWARE",
    "attainable_gflops",
    "detect_hardware_spec",
    "operational_intensity_phi",
    "RooflineTerms",
    "roofline_terms",
]


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    name: str
    peak_flops: float  # FLOP/s per chip (bf16 for TPU; f64-ish for paper CPUs)
    hbm_bw: float  # bytes/s per chip
    link_bw: float = 0.0  # bytes/s per ICI link (0 = single device)
    vmem_bytes: int = 0
    # Small-problem overhead coefficients, used by the model-guided
    # autotuner on top of the 3 roofline terms (zero = pure roofline):
    # ``op_overhead_s`` — seconds per executed large-result HLO
    # instruction (a kernel dispatch); ``serial_instr_s`` — seconds per
    # small-result (<=256-element) instruction, the iteration cost of the
    # serial while loops XLA:CPU lowers scatter/segment reductions into;
    # ``scatter_elem_s`` — seconds per update element of a scatter that
    # survives as an HLO op.  These dominate the ranking of candidate
    # policies on problems too small to stress flops or bandwidth.
    op_overhead_s: float = 0.0
    serial_instr_s: float = 0.0
    scatter_elem_s: float = 0.0

    @property
    def balance(self) -> float:
        """FLOP/byte at the roofline knee."""
        return self.peak_flops / self.hbm_bw


HARDWARE = {
    # Target chip for all TPU-derived numbers in EXPERIMENTS.md:
    "tpu_v5e": HardwareSpec(
        "TPU v5e", peak_flops=197e12, hbm_bw=819e9, link_bw=50e9,
        vmem_bytes=128 * 2**20,
    ),
    # The paper's two systems (Sec. 3.2), for reproducing Figs. 3-4:
    "e5_2690v4_dual": HardwareSpec(
        "dual Intel E5-2690v4", peak_flops=1164.8e9, hbm_bw=153.6e9
    ),
    "k80": HardwareSpec("NVIDIA Tesla K80", peak_flops=2910e9, hbm_bw=480e9),
    # The container host (1 core); bandwidth measured by bench_stream.
    # Overhead coefficients calibrated against measured fused-MU bursts
    # (see tests/test_roofline_model.py): ~1us per dispatched HLO
    # instruction, ~40ns per serial-loop iteration (the while loops
    # XLA:CPU lowers scatter/segment reductions into — e.g. chicago
    # mode-0 segment: 106621 small instrs x 4e-8 = 4.3ms vs 4.4ms
    # measured), ~30ns per scatter update element.
    "host_cpu": HardwareSpec(
        "host XLA:CPU (1 core)", peak_flops=50e9, hbm_bw=20e9,
        op_overhead_s=1e-6, serial_instr_s=4e-8, scatter_elem_s=3e-8,
    ),
}


def attainable_gflops(intensity: float, hw: HardwareSpec) -> float:
    """P = min(pi, beta * I)   (paper Eq. 2), in GFLOP/s."""
    return min(hw.peak_flops, hw.hbm_bw * intensity) / 1e9


# jax ``device_kind`` -> HARDWARE key.  A device that is not listed has no
# peaks to score against, so detection raises instead of guessing.
_DEVICE_KIND_SPECS = {"TPU v5 lite": "tpu_v5e", "Tesla K80": "k80"}


def detect_hardware_spec(platform: str | None = None,
                         device_kind: str | None = None) -> HardwareSpec:
    """HardwareSpec for the *actual* device, keyed by its ``device_kind``.

    Resolution order: ``$REPRO_HARDWARE_SPEC`` (a HARDWARE key), then the
    ``platform`` argument or ``jax.default_backend()``: ``"cpu"`` is the
    host spec; any other platform is looked up by ``device_kind`` (default:
    that of the platform's first device).  An unknown kind raises
    ``ValueError``.
    """
    import os

    override = os.environ.get("REPRO_HARDWARE_SPEC")
    if override and override in HARDWARE:
        return HARDWARE[override]
    import jax

    if platform is None:
        platform = jax.default_backend()
    if platform == "cpu":
        return HARDWARE["host_cpu"]
    if device_kind is None:
        device_kind = jax.devices(platform)[0].device_kind
    if device_kind not in _DEVICE_KIND_SPECS:
        raise ValueError(
            f"no hardware spec for {platform} device kind {device_kind!r}; "
            f"known kinds: {sorted(_DEVICE_KIND_SPECS)}"
        )
    return HARDWARE[_DEVICE_KIND_SPECS[device_kind]]


# The intensities the paper *states* (Eq. 5 / Eq. 8, FLOP/byte).  Note:
# evaluating the paper's own Eqs. 3-4 / 6-7 literally gives W/Q ~ 0.80 / 0.67
# FLOP/word (= 0.10 / 0.084 FLOP/byte with the paper's 8-byte words) — the
# stated 0.125 / 0.27 don't follow from the formulas, but they are what the
# paper's headline bounds derive from (480 GB/s x 0.125 = 60 GFLOP/s K80;
# 153.6 GB/s x 0.27 = 41.5 GFLOP/s Xeon).  We report both.
PAPER_STATED_INTENSITY = {"gpu": 0.125, "cpu": 0.27}  # FLOP/byte


def operational_intensity_phi(
    rank: int,
    variant: str = "gpu",
    v: int = 32,
    word_bytes: int = 8,
    nnz: int = 10**6,
) -> float:
    """Operational intensity of Phi^(n) from the paper's Eqs. 3-4 / 6-7,
    evaluated literally, in FLOP/byte (paper words are 8-byte doubles).

    ``nnz`` only matters through sub-linear terms in Eqs. 3-4/6-7 (there
    are none for the gpu variant; the cpu variant's v-strip remainder is
    O(1)), so the intensity is nnz-invariant — asserted in
    tests/test_roofline_model.py.
    """
    from repro.core.phi import phi_flops_words

    w, q = phi_flops_words(nnz, rank, variant=variant, v=v)
    return (w / q) / word_bytes


@dataclasses.dataclass(frozen=True)
class RooflineTerms:
    """Three-term roofline for one (arch x shape x mesh) cell."""

    compute_s: float
    memory_s: float
    collective_s: float
    hlo_flops: float  # global (all chips)
    hlo_bytes: float
    collective_bytes: float
    model_flops: float  # 6*N*D (dense) or 6*N_active*D (MoE); 0 if n/a
    n_chips: int
    # Peak FLOP/s of the spec these terms were built from.  Defaults to
    # the TPU v5e peak for direct RooflineTerms(...) constructions that
    # predate the field; roofline_terms() always sets it from ``hw``.
    peak_flops: float = 197e12

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        """Roofline step time: max of the three terms (perfect overlap)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs — catches remat/redundancy waste."""
        return self.model_flops / self.hlo_flops if self.hlo_flops else 0.0

    @property
    def mfu_bound(self) -> float:
        """Upper bound on MFU implied by the three terms, against the
        peak of the spec that built these terms (a module-level TPU peak
        here used to make host_cpu bounds ~4000x too small)."""
        if not self.model_flops or not self.bound_s or not self.peak_flops:
            return 0.0
        return self.model_flops / (self.bound_s * self.n_chips) / self.peak_flops


def roofline_terms(
    hlo_flops: float,
    hlo_bytes: float,
    collective_bytes: float,
    n_chips: int,
    hw: HardwareSpec = HARDWARE["tpu_v5e"],
    model_flops: float = 0.0,
) -> RooflineTerms:
    """Build the 3-term roofline.  ``hlo_flops``/``hlo_bytes`` are GLOBAL
    (sum over chips); ``collective_bytes`` is the per-chip wire traffic
    (sum of collective operand bytes in the per-device partitioned module).
    """
    return RooflineTerms(
        compute_s=hlo_flops / (n_chips * hw.peak_flops),
        memory_s=hlo_bytes / (n_chips * hw.hbm_bw),
        collective_s=(collective_bytes / hw.link_bw) if hw.link_bw else 0.0,
        hlo_flops=hlo_flops,
        hlo_bytes=hlo_bytes,
        collective_bytes=collective_bytes,
        model_flops=model_flops,
        n_chips=n_chips,
        peak_flops=hw.peak_flops,
    )
