"""Run every benchmark (one per paper table/figure) and summarize.

  PYTHONPATH=src python -m benchmarks.run           # quick tier
  PYTHONPATH=src python -m benchmarks.run --only ppa,stream
  PYTHONPATH=src python -m benchmarks.run --devices 4 --only sharded

``--devices N`` forces N host CPU devices (via
``--xla_force_host_platform_device_count``) so the sharded Phi benchmark
exercises real shard_map + psum on one machine; it must be processed
before jax initializes, which is why the bench modules are imported
lazily inside :func:`main`.

After the benches finish, the Phi-centric results (runtime breakdown,
policy winners + autotuner regret, fused-vs-unfused and sharded
speedups) are distilled into machine-readable ``BENCH_phi.json`` at the
repo root so the perf trajectory is tracked across PRs.
"""
from __future__ import annotations

import argparse
import json
import os
import time
import traceback

BENCH_PHI_PATH = "BENCH_phi.json"
OUT_DIR = "experiments/bench"  # mirrors benchmarks.common.OUT_DIR (no jax)


def _load_all():
    """Import the bench modules (pulls in jax) after env flags are set."""
    from . import (
        bench_breakdown,
        bench_cutout,
        bench_dense,
        bench_fused,
        bench_grid,
        bench_guard,
        bench_mttkrp,
        bench_modes,
        bench_policy,
        bench_ppa,
        bench_rebalance,
        bench_roofline,
        bench_serve,
        bench_sharded,
        bench_stream,
    )

    return {
        "breakdown": bench_breakdown.run,  # Fig. 2
        "roofline": bench_roofline.run,    # Figs. 3-4 / Eqs. 3-8
        "ppa": bench_ppa.run,              # Exps. 1-2 / Figs. 5-7
        "policy": bench_policy.run,        # Exps. 3-5 / Figs. 8-13
        "fused": bench_fused.run,          # PR 1: fused MU fast path
        "sharded": bench_sharded.run,      # PR 2: multi-device sharded Phi
        "rebalance": bench_rebalance.run,  # PR 4: rebalancing + sharded Pi
        "guard": bench_guard.run,          # PR 6: numerical-guard overhead
        "cutout": bench_cutout.run,        # PR 7: model-guided cold tuning
        "serve": bench_serve.run,          # PR 8: streaming service receipts
        "dense": bench_dense.run,          # PR 9: dense matrix-free tier
        "grid": bench_grid.run,            # PR 10: N-D grid combine
        "modes": bench_modes.run,          # Exp. 6 / Figs. 14-15
        "stream": bench_stream.run,        # Exp. 7 / Figs. 16-17
        "mttkrp": bench_mttkrp.run,        # Exp. 8 / Figs. 18-19
    }


def _load_rows(name: str):
    path = os.path.join(OUT_DIR, f"{name}.json")
    if not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            return json.load(f).get("rows", [])
    except (OSError, ValueError):
        return None


def emit_bench_phi(path: str = BENCH_PHI_PATH) -> dict | None:
    """Distill experiments/bench/*.json -> BENCH_phi.json.

    Schema (all medians in seconds):
      breakdown: {tensor: {kernel: seconds, ..., phi_share: float}}
      policy:    {tensor: {default_s, best, best_s, heuristic, heuristic_regret,
                           autotune, autotune_s, autotune_regret,
                           autotune_key, p95_run, dup_share, empty_frac,
                           autotune_probe_failures, twin_autotune,
                           v2_vs_v1_regret}}
      fused:     {tensor: {strategy: {unfused_s, fused_s, speedup}}}
      sharded:   {tensor: {devices, single_s, sharded_s, reduce_scatter_s,
                           speedup, combine_speedup, combine_bytes,
                           combine_bound_bytes, psum_wire_bytes,
                           rs_wire_bytes, rs_owned_bytes, rs_bound_bytes}}
      rebalance: {tensor: {devices, rebalance_gain, imbalance_static,
                           imbalance_rebalanced, boundaries_moved,
                           sharded_mttkrp_speedup, pi_gather_bytes,
                           pi_replicated_bytes, pi_wire_ratio}}
      summary:   geomeans (policy speedup, autotune regret, v2-vs-v1 regret,
                           fused speedup, sharded speedup, rebalance gain,
                           sharded-MTTKRP speedup, Pi wire ratio) + total
                           autotune probe failures

    ``autotune_key`` is the v2 distribution-aware cache key and
    ``p95_run``/``dup_share``/``empty_frac`` the segment-run stats behind
    it; ``v2_vs_v1_regret`` is the slowdown a v1 (stats-less) keyspace
    would have inflicted on the hub twin of each mode (see
    ``bench_policy``).  ``autotune_probe_failures`` counts probes whose
    failure reasons the tuner recorded in the cache instead of silently
    falling back.  Schema 4 adds the ``rebalance`` section (see
    ``bench_rebalance``): measured-time-weighted shard rebalancing gain,
    the sharded-MTTKRP speedup of the CP-ALS kernel family routed through
    the strategy stack, and the sharded-Pi per-device gather bytes
    against the replicated O(I*R) baseline (``pi_wire_ratio`` < 1 means
    the shard-local gather moves less than replication).  Schema 5 adds
    the reduce-scatter combine columns to the ``sharded`` section (see
    ``bench_sharded``): ``reduce_scatter_s`` / ``combine_speedup`` time
    the owner-partitioned epilogue against the psum combine, and the
    byte columns receipt the communication cut — ``rs_wire_bytes`` vs
    ``psum_wire_bytes`` per device per inner iteration, and
    ``rs_owned_bytes`` (the owned O(I_n*R/S) slice each device keeps) vs
    ``combine_bytes`` (the full window the psum path replicates).
    Schema 6 adds the ``guard`` section (see ``bench_guard``): warm
    CP-APR solve seconds with the PR-6 numerical guard on vs off and the
    per-tensor ``overhead_frac`` (guard_s/no_guard_s - 1), with the
    geomean surfaced as ``summary.guard_overhead_frac`` — the acceptance
    bar is <= 2% on the quick tier.  Schema 7 adds the ``model`` section
    (see ``bench_cutout``): the model-guided tuner's cold-start receipt —
    probes per cold key under the full measured grid vs the
    roofline-pruned tuner, ``probe_reduction`` (the >= 5x acceptance
    bar), per-key winner matches / measured regret vs the full grid,
    fixture x strategy-family cell matches, the count of keys served
    model-only with zero probes, and the calibrated model-vs-measured
    error percentiles that drive the pruning bound.  Schema 8 adds the
    ``serve`` section (see ``bench_serve``): the streaming service's
    warm-start receipt — per-fixture warm vs cold outer sweeps after a
    model-consistent append (``summary.warm_vs_cold_sweeps`` geomean,
    acceptance bar >= 2x) — and the padded-bucket batching receipt
    (one vmapped dispatch for J same-bucket jobs vs the same jobs one
    dispatch each through the identical padded path).  Schema 9 adds the
    ``dense`` section (see ``bench_dense``): the dense matrix-free
    tier's crossover receipt on near-dense fixtures — per-fixture
    sparse-vs-dense Phi seconds and ``dense_vs_segment`` speedup
    (acceptance bar: > 1 on at least one fixture, surfaced as
    ``summary.best_dense_vs_segment``), whether the fill cut's
    heuristic selected the tier (``heuristic_dense``), and the
    bf16-element/f32-accumulate path's timing + max relative error vs
    the f32 dense result (``bf16_within_tier`` = within the 3e-2
    conformance tolerance tier).  Schema 10 adds the ``grid`` section
    (see ``bench_grid``): the N-D grid combine's wire receipt at 4
    devices — per-tensor 1D reduce-scatter vs ``A x B`` grid fused
    Phi->MU sweep seconds (``grid_speedup``), the per-device combine
    wire of each path (``rs_wire_bytes`` = (S-1)*own_rows*R vs
    ``grid_wire_bytes`` = 2(B-1)*sub_rows*R, with ``wire_ratio`` =
    grid/1D — < 1 means the grid moves less), the analytic
    ``grid_bound_bytes`` the measured HLO wire is asserted against in
    conformance, and the Omega(I_n*R/P) ``comm_lower_bound_bytes``
    floor; geomeans surface as ``summary.grid_wire_ratio`` and
    ``summary.grid_speedup``.
    """
    out: dict = {"schema": 10, "generated_unix": time.time(),
                 "breakdown": {}, "policy": {}, "fused": {}, "sharded": {},
                 "rebalance": {}, "guard": {}, "model": {}, "serve": {},
                 "dense": {}, "grid": {}, "summary": {}}
    found = False

    rows = _load_rows("breakdown")
    if rows:
        found = True
        per: dict = {}
        for r in rows:
            if "tensor" in r:
                per.setdefault(r["tensor"], {})[r["kernel"]] = r["seconds"]
        for tensor, kernels in per.items():
            total = sum(kernels.values()) or 1.0
            kernels["phi_share"] = round(kernels.get("phi", 0.0) / total, 4)
        out["breakdown"] = per

    rows = _load_rows("policy")
    if rows:
        found = True
        keep = ("default_s", "best", "best_s", "worst_s", "heuristic",
                "heuristic_s", "heuristic_regret", "autotune", "autotune_s",
                "autotune_regret", "speedup_best_vs_default",
                "autotune_key", "p95_run", "dup_share", "empty_frac",
                "autotune_probe_failures", "twin_autotune", "v2_vs_v1_regret")
        for r in rows:
            if "tensor" in r:
                out["policy"][r["tensor"]] = {k: r[k] for k in keep if k in r}
            elif r.get("summary") == "geomean":
                for k in ("speedup_best_vs_default", "heuristic_regret",
                          "autotune_regret", "v2_vs_v1_regret",
                          "autotune_probe_failures"):
                    if k in r:
                        out["summary"][k] = r[k]
        n_fail = sum(r.get("autotune_probe_failures", 0)
                     for r in rows if "tensor" in r)
        if n_fail:
            # surface what the tuner recorded instead of letting the
            # heuristic fallback hide broken probes
            print(f"[benchmarks] WARNING: {n_fail} autotune probe failure(s) "
                  "recorded in cache entries (see probe_errors in "
                  f"{OUT_DIR}/autotune_cache.json)", flush=True)

    rows = _load_rows("fused")
    if rows:
        found = True
        for r in rows:
            if "tensor" in r:
                out["fused"].setdefault(r["tensor"], {})[r["strategy"]] = {
                    "unfused_s": r["unfused_s"],
                    "fused_s": r["fused_s"],
                    "speedup": r["speedup"],
                }
            elif r.get("summary") == "geomean":
                out["summary"][f"fused_speedup_{r['strategy']}"] = r["speedup"]

    rows = _load_rows("sharded")
    if rows:
        found = True
        keep = ("devices", "real_mesh", "single_s", "sharded_s",
                "reduce_scatter_s", "speedup", "combine_speedup",
                "combine_bytes", "combine_bound_bytes", "psum_wire_bytes",
                "rs_wire_bytes", "rs_owned_bytes", "rs_bound_bytes")
        for r in rows:
            if "tensor" in r:
                out["sharded"][r["tensor"]] = {k: r[k] for k in keep if k in r}
            elif r.get("summary") == "geomean":
                out["summary"]["sharded_speedup"] = r["speedup"]
                out["summary"]["sharded_devices"] = r.get("devices")
                if "combine_speedup" in r:
                    out["summary"]["combine_speedup"] = r["combine_speedup"]

    rows = _load_rows("rebalance")
    if rows:
        found = True
        keep = ("devices", "real_mesh", "static_s", "rebalanced_s",
                "rebalance_gain", "imbalance_static", "imbalance_rebalanced",
                "boundaries_moved", "mttkrp_scatter_s", "mttkrp_sharded_s",
                "sharded_mttkrp_speedup", "pi_gather_bytes",
                "pi_replicated_bytes", "pi_wire_ratio")
        for r in rows:
            if "tensor" in r:
                out["rebalance"][r["tensor"]] = {
                    k: r[k] for k in keep if k in r
                }
            elif r.get("summary") == "geomean":
                for k in ("rebalance_gain", "sharded_mttkrp_speedup",
                          "pi_wire_ratio"):
                    if k in r:
                        out["summary"][k] = r[k]

    rows = _load_rows("guard")
    if rows:
        found = True
        keep = ("sweeps", "guard_s", "no_guard_s", "overhead_frac")
        for r in rows:
            if "tensor" in r:
                out["guard"][r["tensor"]] = {k: r[k] for k in keep if k in r}
            elif r.get("summary") == "geomean":
                out["summary"]["guard_overhead_frac"] = \
                    r["guard_overhead_frac"]

    rows = _load_rows("cutout")
    if rows:
        found = True
        per_key = [r for r in rows if "tensor" in r]
        out["model"]["keys"] = {
            f"{r['tensor']}:{r['mode']}": {
                k: r[k] for k in (
                    "nnz", "n_candidates", "probes_full", "probes_model",
                    "winner_full", "winner_model", "source_model",
                    "model_s", "measured_s", "regret", "match",
                    "family_regrets")
                if k in r
            }
            for r in per_key
        }
        summ = next((r for r in rows if r.get("summary") == "totals"), None)
        if summ:
            keep = ("cold_keys", "probes_full", "probes_model",
                    "probes_per_cold_key_full", "probes_per_cold_key_model",
                    "probe_reduction", "model_served", "winner_match",
                    "family_match", "winner_regret_geomean",
                    "model_error_rel_p50", "model_error_rel_p95",
                    "model_error_p95_log", "calibration_n")
            out["model"].update({k: summ[k] for k in keep if k in summ})
            out["summary"]["probe_reduction"] = summ.get("probe_reduction")
            out["summary"]["model_winner_regret"] = \
                summ.get("winner_regret_geomean")
            if (summ.get("probe_reduction") or 0) < 5.0:
                print("[benchmarks] WARNING: model-guided probe reduction "
                      f"{summ.get('probe_reduction')}x is below the 5x bar",
                      flush=True)

    rows = _load_rows("serve")
    if rows:
        found = True
        keep_f = ("warm_sweeps", "cold_sweeps", "sweep_ratio", "frac_new",
                  "sweep_budget", "warm_s", "cold_s")
        keep_b = ("jobs", "dispatches", "batched_s", "perjob_s",
                  "batched_speedup", "jobs_per_s")
        for r in rows:
            if "tensor" in r:
                out["serve"].setdefault("fixtures", {})[r["tensor"]] = {
                    k: r[k] for k in keep_f if k in r
                }
            elif "batch" in r:
                out["serve"]["batched"] = {k: r[k] for k in keep_b if k in r}
            elif r.get("summary") == "geomean":
                out["summary"]["warm_vs_cold_sweeps"] = \
                    r["warm_vs_cold_sweeps"]
                out["summary"]["serve_batched_speedup"] = \
                    r["batched_speedup"]
                if r["warm_vs_cold_sweeps"] < 2.0:
                    print("[benchmarks] WARNING: warm-vs-cold sweep ratio "
                          f"{r['warm_vs_cold_sweeps']}x is below the 2x bar",
                          flush=True)

    rows = _load_rows("dense")
    if rows:
        found = True
        keep = ("nnz", "fill", "fill_bin", "heuristic_dense", "segment_s",
                "pallas_s", "dense_s", "dense_bf16_s", "dense_vs_segment",
                "dense_vs_pallas", "bf16_vs_f32", "bf16_max_rel_err",
                "bf16_within_tier")
        for r in rows:
            if "tensor" in r:
                out["dense"][r["tensor"]] = {k: r[k] for k in keep if k in r}
            elif r.get("summary") == "geomean":
                out["summary"]["dense_vs_segment"] = r["dense_vs_segment"]
                out["summary"]["best_dense_vs_segment"] = \
                    r["best_dense_vs_segment"]
                if r["best_dense_vs_segment"] <= 1.0:
                    print("[benchmarks] WARNING: dense tier beat segment on "
                          "no fixture (bar: at least one)", flush=True)

    rows = _load_rows("grid")
    if rows:
        found = True
        keep = ("devices", "grid", "real_mesh", "sharded_rs_s", "grid_s",
                "grid_speedup", "rs_wire_bytes", "grid_wire_bytes",
                "wire_ratio", "grid_bound_bytes", "comm_lower_bound_bytes")
        for r in rows:
            if "tensor" in r:
                out["grid"][r["tensor"]] = {k: r[k] for k in keep if k in r}
            elif r.get("summary") == "geomean":
                out["summary"]["grid_wire_ratio"] = r["wire_ratio"]
                out["summary"]["grid_speedup"] = r["grid_speedup"]
                if r["wire_ratio"] >= 1.0 and out["grid"]:
                    print("[benchmarks] WARNING: grid combine wire ratio "
                          f"{r['wire_ratio']} is not below the 1D path",
                          flush=True)

    if not found:
        return None
    with open(path, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
    print(f"[benchmarks] phi summary -> {path}", flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="all")
    ap.add_argument("--devices", type=int, default=0,
                    help="force N host CPU devices (sets XLA_FLAGS before "
                         "jax init; records sharded-vs-single speedup)")
    args = ap.parse_args(argv)
    if args.devices > 1:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" in flags:
            print(f"[benchmarks] XLA_FLAGS already forces a device count; "
                  f"ignoring --devices {args.devices}: {flags}", flush=True)
        else:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{args.devices}"
            ).strip()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    all_benches = _load_all()
    names = list(all_benches) if args.only == "all" else args.only.split(",")
    t0 = time.time()
    failed = []
    for name in names:
        print(f"\n=== bench:{name} ===", flush=True)
        try:
            all_benches[name]()
        except Exception:
            traceback.print_exc()
            failed.append(name)
    try:  # distillation gets the same containment as the benches
        emit_bench_phi()
    except Exception:
        traceback.print_exc()
    print(f"\n[benchmarks] {len(names) - len(failed)}/{len(names)} ok "
          f"in {time.time() - t0:.0f}s; failed: {failed or 'none'}")
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
