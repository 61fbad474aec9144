"""Run CP-APR and the decomposition service once on a TPU, and check them.

    python chip_smoke.py               # one chip: solve, reference, service
    python chip_smoke.py --four-chips  # 2x2 host: grid 2x2 vs sharded 4x1

The one-chip run decomposes the chicago tensor at its published FROSTT size
(5,330,673 draws before dedupe) at rank 16 with the Pallas kernel, checks
one fused Phi/MU step against a float64 NumPy oracle and the solve against a
``strategy="segment"`` solve on the same chip, then has ``DecompService``
answer three tenants and one warm-started append under ``policy="auto"``.

The script refuses to run without a TPU: there is no CPU fallback.  Every
line but the last is a report; the last is one JSON object, printed only
when every phase passed.  Wall times printed here are smoke times,
compilation included, not metrics.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import jax
import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

SEED = 0
RANK = 16
TENSOR = "chicago"
SOLVE = dict(max_outer=3, max_inner=10)
# f32 kernel vs the float64 oracle, max-norm relative error of B*Phi; f32
# accumulation over ~900 nonzeros per row stays far below this.
ORACLE_RTOL = 1e-4
# pallas vs segment after the same sweeps on the same chip: the two sum
# Phi in different orders, so they agree to f32 rounding, not bitwise.
LOGLIK_RTOL = 1e-5
KKT_ATOL = 1e-3
# grid 2x2 vs sharded 4x1: the grid adds column partials in another order.
FOUR_CHIP_LOGLIK_RTOL = 1e-5
FOUR_CHIP_FACTOR_ATOL = 1e-4
# the service's autotune store: a fixed path in the checkout, emptied at the
# start of every run so that its Pallas candidates are compiled and probed
SERVICE_STORE = ROOT / ".autotune" / "chip_smoke.json"


class Failed(Exception):
    """A check of the smoke did not hold."""


def check(ok: bool, what: str) -> None:
    print(f"  [{'ok' if ok else 'FAIL'}] {what}", flush=True)
    if not ok:
        raise Failed(what)


def _factor_arrays(kt):
    return [np.asarray(f) for f in kt.factors] + [np.asarray(kt.lam)]


def check_solution(res) -> None:
    """The checks every solve of the smoke has to pass."""
    check(not res.recoveries,
          f"recovery events: {len(res.recoveries or [])} "
          f"{[r.kind for r in res.recoveries or []]}")
    ll = res.loglik_history
    check(len(ll) == res.n_outer and all(b >= a for a, b in zip(ll, ll[1:])),
          f"log-likelihood non-decreasing over {len(ll)} sweeps: {ll}")
    arrs = _factor_arrays(res.ktensor)
    check(all(np.all(np.isfinite(a)) and np.all(a >= 0) for a in arrs),
          "every factor and lambda finite and non-negative")


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def draw_tensor(name: str, scale: float):
    from repro.data.tensors import FROSTT, make_tensor

    t0 = time.perf_counter()
    t, _ = make_tensor(name, scale=scale, rank=RANK, seed=SEED)
    draws = max(int(FROSTT[name][1] * scale), 1_000)
    print(f"tensor {name}: shape={t.shape} draws={draws} "
          f"nnz_after_dedupe={t.nnz} (smoke time {time.perf_counter() - t0:.1f}s)",
          flush=True)
    return t


def phase_solve(t):
    """cpapr_mu with the Pallas kernel; every mode must run it compiled."""
    from repro.core.cpapr import (
        CPAPRConfig, _make_mode_update, cpapr_mu, resolve_mode_policies,
    )
    from repro.core.sparse_tensor import random_ktensor, sort_mode

    print("phase solve: cpapr_mu strategy=pallas "
          f"R={RANK} max_outer={SOLVE['max_outer']} "
          f"max_inner={SOLVE['max_inner']}", flush=True)
    cfg = CPAPRConfig(rank=RANK, strategy="pallas", **SOLVE)
    key = jax.random.PRNGKey(SEED)
    mvs = [sort_mode(t, n) for n in range(t.ndim)]

    # The mode updates cpapr_mu builds, lowered: the kernel is compiled
    # (a tpu_custom_call), not interpreted, in every mode.
    kt0 = random_ktensor(key, t.shape, RANK).normalize()
    strategies, layouts, _, locals_ = resolve_mode_policies(
        mvs, kt0.factors, kt0.lam, rank=RANK, strategy="pallas")
    for n in range(t.ndim):
        upd, _ = _make_mode_update(mvs[n], cfg, strategies[n], layouts[n],
                                   locals_[n])
        text = upd.func.lower(*upd.args, tuple(kt0.factors), kt0.lam).as_text()
        lay = layouts[n]
        check(strategies[n] == "pallas" and "tpu_custom_call" in text,
              f"mode {n}: strategy={strategies[n]} compiled kernel "
              f"(tpu_custom_call in the lowered update), n_grid={lay.n_grid} "
              f"block={lay.block_nnz}x{lay.block_rows}")

    t0 = time.perf_counter()
    res = cpapr_mu(t, RANK, key=key, config=cfg, mode_views=mvs)
    jax.block_until_ready(res.ktensor.factors)
    print(f"  pallas solve: {res.n_outer} sweeps, kkt={res.kkt_history} "
          f"inner={res.inner_iters} (smoke time "
          f"{time.perf_counter() - t0:.1f}s, compile included)", flush=True)
    check_solution(res)
    return mvs, res


def phase_reference(t, mvs, res_pallas):
    """One fused Phi/MU step against float64 NumPy, and pallas vs segment."""
    from repro.core.cpapr import CPAPRConfig, cpapr_mu
    from repro.core.layout import build_blocked_layout
    from repro.core.phi import phi_mu_step
    from repro.core.pi import pi_rows
    from repro.core.policy import default_policy

    print("phase reference", flush=True)
    kt = res_pallas.ktensor
    n = 0
    mv = mvs[n]
    n_rows = mv.n_rows
    pol = default_policy(RANK)
    layout = jax.device_put(build_blocked_layout(
        np.asarray(mv.rows), n_rows, pol.block_nnz, pol.block_rows))

    @jax.jit
    def fused_step(layout, mv, factors, lam):
        b = factors[n] * lam[None, :]
        pi = pi_rows(mv.sorted_idx, factors, n)
        return phi_mu_step(mv.rows, mv.sorted_vals, pi, b, n_rows=n_rows,
                           tol=-1.0, strategy="pallas", layout=layout)

    mu, viol = fused_step(layout, mv, kt.factors, kt.lam)
    mu, viol = np.asarray(mu, np.float64), float(viol)
    del layout

    # The float64 oracle of tests/conftest.py:dense_phi_reference, from the
    # same factors: Phi[i] += (x / max(<B[i], pi>, eps)) * pi, then B*Phi
    # and the KKT max |min(B, 1 - Phi)|.
    idx = np.asarray(mv.sorted_idx)
    f64 = [np.asarray(f, np.float64) for f in kt.factors]
    b64 = f64[n] * np.asarray(kt.lam, np.float64)[None, :]
    pi64 = np.ones((idx.shape[0], RANK))
    for m, f in enumerate(f64):
        if m != n:
            pi64 *= f[idx[:, m]]
    rows = idx[:, n]
    s = np.sum(b64[rows] * pi64, axis=1)
    w = np.asarray(mv.sorted_vals, np.float64) / np.maximum(s, 1e-10)
    phi = np.stack([np.bincount(rows, weights=w * pi64[:, r],
                                minlength=n_rows) for r in range(RANK)],
                   axis=1)
    mu_ref = b64 * phi
    viol_ref = float(np.max(np.abs(np.minimum(b64, 1.0 - phi))))
    err = float(np.max(np.abs(mu - mu_ref)) / np.max(np.abs(mu_ref)))
    check(err <= ORACLE_RTOL,
          f"mode {n} fused Phi/MU vs float64 oracle: max rel err {err!r} "
          f"(limit {ORACLE_RTOL})")
    kerr = abs(viol - viol_ref) / max(viol_ref, 1.0)
    check(kerr <= ORACLE_RTOL,
          f"mode {n} KKT {viol!r} vs oracle {viol_ref!r}: rel err {kerr!r} "
          f"(limit {ORACLE_RTOL})")

    cfg = CPAPRConfig(rank=RANK, strategy="segment", **SOLVE)
    t0 = time.perf_counter()
    seg = cpapr_mu(t, RANK, key=jax.random.PRNGKey(SEED), config=cfg,
                   mode_views=mvs)
    jax.block_until_ready(seg.ktensor.factors)
    print(f"  segment solve: {seg.n_outer} sweeps, kkt={seg.kkt_history} "
          f"(smoke time {time.perf_counter() - t0:.1f}s, compile included)",
          flush=True)
    check_solution(seg)
    ll_p, ll_s = res_pallas.loglik_history[-1], seg.loglik_history[-1]
    ll_err = abs(ll_p - ll_s) / abs(ll_s)
    check(ll_err <= LOGLIK_RTOL,
          f"final loglik pallas {ll_p!r} vs segment {ll_s!r}: rel err "
          f"{ll_err!r} (limit {LOGLIK_RTOL})")
    k_p, k_s = res_pallas.kkt_history[-1], seg.kkt_history[-1]
    check(abs(k_p - k_s) <= KKT_ATOL,
          f"final KKT pallas {k_p!r} vs segment {k_s!r}: abs err "
          f"{abs(k_p - k_s)!r} (limit {KKT_ATOL})")


def phase_service(name: str, scale: float, n_tenants: int = 3,
                  append_frac: float = 0.1):
    """DecompService: cold tenants through submit_many, one warm append."""
    from repro.core.sparse_tensor import random_poisson_tensor
    from repro.data.tensors import make_tensor
    from repro.serve.decomp import DecompJob, DecompService

    print(f"phase service: {n_tenants} tenants {name}@{scale} R={RANK}, "
          f"policy=auto with measured probes, store {SERVICE_STORE}",
          flush=True)
    SERVICE_STORE.parent.mkdir(parents=True, exist_ok=True)
    SERVICE_STORE.unlink(missing_ok=True)
    svc = DecompService(autotune_path=str(SERVICE_STORE), measure=True,
                        max_outer=10, tol=1e-4)
    jobs, truths = [], {}
    for j in range(n_tenants):
        t, kt = make_tensor(name, scale=scale, rank=RANK, seed=SEED + 1 + j)
        jobs.append(DecompJob(tenant=f"tenant{j}", tensor=t, rank=RANK,
                              key=jax.random.PRNGKey(SEED + j)))
        truths[f"tenant{j}"] = kt
    t0 = time.perf_counter()
    results = svc.submit_many(jobs)
    print(f"  submit_many: {len(results)} jobs in "
          f"{svc.n_batched_dispatches} dispatch(es) (smoke time "
          f"{time.perf_counter() - t0:.1f}s)", flush=True)

    tenant = jobs[0].tenant
    st = svc.tenant(tenant)
    extra, _ = random_poisson_tensor(
        jax.random.PRNGKey(SEED + 1000), st.tensor.shape,
        nnz=max(1, int(append_frac * st.tensor.nnz)), rank=RANK,
        seed_ktensor=truths[tenant])
    t0 = time.perf_counter()
    warm = svc.append(tenant, np.asarray(extra.indices),
                      np.asarray(extra.values))
    print(f"  append {tenant}: frac_new={warm.frac_new:.3f} warm "
          f"{warm.result.n_outer} sweeps (budget {warm.sweep_budget}) "
          f"policies={[p.label() for p in warm.result.policies]} "
          f"(smoke time {time.perf_counter() - t0:.1f}s)", flush=True)

    answered = results + [warm]
    for r in answered:
        arrs = _factor_arrays(r.result.ktensor)
        check(not r.result.recoveries
              and all(np.all(np.isfinite(a)) for a in arrs),
              f"{r.tenant} ({'warm' if r.warm else 'cold'}): "
              f"{r.result.n_outer} sweeps, kkt={r.result.kkt_history[-1]!r}, "
              f"recoveries={len(r.result.recoveries or [])}, factors finite")
    entries = svc.tuner.cache.entries
    errors = [e for entry in entries.values()
              for e in entry.get("probe_errors") or []]
    pallas_errors = [e for e in errors if "pallas" in e]
    print(f"  autotune: {svc.tuner.counters()} entries={len(entries)} "
          f"probe_errors={errors}", flush=True)
    check(svc.tuner.counters()["probes"] > 0 and not pallas_errors,
          f"autotune probed on the chip with no Pallas probe errors "
          f"({len(pallas_errors)})")


def phase_four_chips(t):
    """strategy=grid 2x2 vs strategy=sharded 4x1 (reduce-scatter)."""
    from repro.core.cpapr import (
        CPAPRConfig, _make_mode_update, cpapr_mu, mode_pi_gather,
        resolve_mode_policies,
    )
    from repro.core.distributed import make_grid_mesh, make_phi_mesh
    from repro.core.sparse_tensor import random_ktensor, sort_mode

    print("phase four chips: grid 2x2 vs sharded 4x1 reduce-scatter",
          flush=True)
    mvs = [sort_mode(t, n) for n in range(t.ndim)]
    key = jax.random.PRNGKey(SEED)
    runs = {
        "grid 2x2": CPAPRConfig(rank=RANK, strategy="grid", grid_shape=(2, 2),
                                mesh=make_grid_mesh(2, 2), **SOLVE),
        "sharded 4x1": CPAPRConfig(rank=RANK, strategy="sharded",
                                   combine="reduce_scatter",
                                   mesh=make_phi_mesh(4), **SOLVE),
    }
    kt0 = random_ktensor(key, t.shape, RANK).normalize()
    out = {}
    for label, cfg in runs.items():
        # each mode's carry: sharded over the mesh, one piece per chip
        strategies, layouts, _, locals_ = resolve_mode_policies(
            mvs, kt0.factors, kt0.lam, rank=RANK, strategy=cfg.strategy,
            mesh=cfg.mesh, combine=cfg.combine, grid_shape=cfg.grid_shape)
        for n in range(t.ndim):
            if strategies[n] != cfg.strategy:
                # 1D sharding splits a mode by row blocks of >= 8 rows; a
                # mode with fewer blocks than chips runs on one chip, with
                # the layout's warning.  The grid splits it by nonzeros.
                n_rb = layouts[n].n_row_blocks
                check(cfg.strategy == "sharded" and n_rb < 4,
                      f"{label} mode {n}: {mvs[n].n_rows} rows, {n_rb} row "
                      f"blocks < 4 chips: single-chip {strategies[n]}")
                continue
            upd, gather = _make_mode_update(
                mvs[n], cfg, strategies[n], layouts[n], locals_[n],
                pig=mode_pi_gather(mvs[n], layouts[n], cfg.shard_pi))
            b_own = upd(tuple(kt0.factors), kt0.lam)[0]
            jax.block_until_ready(b_own)
            devs = sorted({s.device for s in b_own.addressable_shards},
                          key=lambda d: d.id)
            check(strategies[n] == cfg.strategy and gather is not None
                  and len(b_own.sharding.device_set) == 4 and len(devs) == 4,
                  f"{label} mode {n}: strategy={strategies[n]} carry "
                  f"{b_own.shape} on devices {[d.id for d in devs]}")
            in_use = [(d.memory_stats() or {}).get("bytes_in_use", 0)
                      for d in devs]
            check(all(b > 0 for b in in_use),
                  f"{label} mode {n}: bytes_in_use per device {in_use}")
        t0 = time.perf_counter()
        res = cpapr_mu(t, RANK, key=key, config=cfg, mode_views=mvs)
        jax.block_until_ready(res.ktensor.factors)
        print(f"  {label}: {res.n_outer} sweeps, kkt={res.kkt_history} "
              f"loglik={res.loglik_history} (smoke time "
              f"{time.perf_counter() - t0:.1f}s, compile included)",
              flush=True)
        check_solution(res)
        out[label] = res
    g, s = out["grid 2x2"], out["sharded 4x1"]
    ll_err = abs(g.loglik_history[-1] - s.loglik_history[-1]) \
        / abs(s.loglik_history[-1])
    check(ll_err <= FOUR_CHIP_LOGLIK_RTOL,
          f"final loglik grid vs sharded: rel err {ll_err!r} "
          f"(limit {FOUR_CHIP_LOGLIK_RTOL})")
    f_err = max(float(np.max(np.abs(a - b))) for a, b in zip(
        _factor_arrays(g.ktensor)[:-1], _factor_arrays(s.ktensor)[:-1]))
    check(f_err <= FOUR_CHIP_FACTOR_ATOL,
          f"factors grid vs sharded: max abs err {f_err!r} "
          f"(limit {FOUR_CHIP_FACTOR_ATOL})")


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


def run_phases(phases) -> list:
    """Run every phase, even after one failed; returns the failed names."""
    failed = []
    state: dict = {}
    for name, fn in phases:
        try:
            fn(state)
        except Exception as e:  # report every phase, then fail the run
            if not isinstance(e, Failed):
                traceback.print_exc()
            print(f"phase {name} FAILED: {type(e).__name__}: {e}", flush=True)
            failed.append(name)
    return failed


def one_chip_phases(name: str = TENSOR, scale: float = 1.0,
                    service: tuple = ("uber", 0.01)) -> list:
    def solve(st):
        st["t"] = draw_tensor(name, scale)
        st["mvs"], st["res"] = phase_solve(st["t"])

    def reference(st):
        phase_reference(st["t"], st["mvs"], st["res"])

    return [("solve", solve), ("reference", reference),
            ("service", lambda st: phase_service(*service))]


def four_chip_phases(name: str = TENSOR, scale: float = 1.0) -> list:
    return [("four-chips",
             lambda st: phase_four_chips(draw_tensor(name, scale)))]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only grid 2x2 vs sharded 4x1 on four chips")
    args = ap.parse_args(argv)

    try:
        from repro.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the program is not next to this script ({e})",
              file=sys.stderr)
        return 2
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        print(f"chip_smoke: no TPU found (jax platform {d.platform!r}); "
              f"this smoke runs only on the chip", file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} TPU devices, found {len(devices)}",
              file=sys.stderr)
        return 1
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devices)} jax={jax.__version__}", flush=True)
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    t0 = time.perf_counter()
    phases = four_chip_phases() if args.four_chips else one_chip_phases()
    failed = run_phases(phases)
    print(f"smoke time total {time.perf_counter() - t0:.1f}s", flush=True)
    if failed:
        print(f"chip_smoke: FAILED phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
