"""End-to-end serving drivers.

LM serving (batched prefill + decode):

  PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b \
      --batch 4 --prompt-len 64 --new-tokens 32

Decomposition service smoke (the ``decomp`` subcommand): submits N
small cold jobs through the padded-bucket batched path, appends a
fresh-nonzero batch to one tenant and warm-starts it, and prints the
warm-vs-cold sweep receipt plus the shared autotune store's counters:

  PYTHONPATH=src python -m repro.launch.serve decomp \
      --jobs 3 --append-frac 0.2
"""
from __future__ import annotations

import argparse
import sys
import time

import jax

from repro.compile_cache import REPO_ROOT, enable_compile_cache
from repro.config import ShapeConfig
from repro.configs import get_arch, reduced
from repro.models.api import build_model
from repro.serve.engine import Engine, ServeConfig

# The decomp launcher's shared autotune store: a fixed path in the checkout,
# so a second run serves the first run's winners.
DEFAULT_AUTOTUNE_STORE = REPO_ROOT / ".autotune" / "serve.json"


def main_decomp(argv=None):
    import numpy as np

    from repro.core.cpapr import CPAPRConfig, cpapr_mu
    from repro.core.sparse_tensor import random_poisson_tensor
    from repro.serve.decomp import DecompJob, DecompService

    ap = argparse.ArgumentParser(prog="repro.launch.serve decomp")
    ap.add_argument("--jobs", type=int, default=3,
                    help="cold jobs to submit (bucketed + batched)")
    ap.add_argument("--shape", type=int, nargs="+", default=[25, 20, 15])
    ap.add_argument("--nnz", type=int, default=3000)
    ap.add_argument("--rank", type=int, default=2)
    ap.add_argument("--append-frac", type=float, default=0.2,
                    help="appended nonzeros as a fraction of the tensor")
    ap.add_argument("--max-outer", type=int, default=40)
    ap.add_argument("--tol", type=float, default=1e-2)
    ap.add_argument("--autotune-cache", default=str(DEFAULT_AUTOTUNE_STORE),
                    help="shared autotune store path")
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    enable_compile_cache()
    print(f"[decomp] devices={jax.device_count()} "
          f"backend={jax.default_backend()}")
    cache = args.autotune_cache
    svc = DecompService(autotune_path=cache, max_outer=args.max_outer,
                        tol=args.tol)

    shape = tuple(args.shape)
    jobs, kts = [], {}
    for j in range(args.jobs):
        t, kt = random_poisson_tensor(
            jax.random.PRNGKey(args.seed + j), shape,
            nnz=args.nnz, rank=args.rank)
        jobs.append(DecompJob(tenant=f"tenant{j}", tensor=t, rank=args.rank))
        kts[f"tenant{j}"] = kt
    t0 = time.perf_counter()
    results = svc.submit_many(jobs)
    dt = time.perf_counter() - t0
    for r in results:
        print(f"[decomp] {r.tenant}: cold {r.result.n_outer} sweeps "
              f"(converged={r.result.converged}, batched={r.batched})")
    print(f"[decomp] {len(jobs)} jobs in {svc.n_batched_dispatches} "
          f"batched dispatch(es), {dt:.2f}s")

    # one streaming append, drawn from tenant0's own generative model
    tenant = jobs[0].tenant
    st = svc.tenant(tenant)
    extra, _ = random_poisson_tensor(
        jax.random.PRNGKey(args.seed + 1000), shape,
        nnz=max(1, int(args.append_frac * st.tensor.nnz)),
        rank=args.rank, seed_ktensor=kts[tenant])
    warm = svc.append(tenant, np.asarray(extra.indices),
                      np.asarray(extra.values))
    cold = cpapr_mu(
        st.tensor, st.rank, key=jax.random.PRNGKey(args.seed + 2000),
        config=CPAPRConfig(rank=st.rank, max_outer=args.max_outer,
                           tol=args.tol, track_loglik=False))
    print(f"[decomp] append frac_new={warm.frac_new:.3f} -> warm "
          f"{warm.result.n_outer} sweeps (budget {warm.sweep_budget}, "
          f"converged={warm.result.converged}) vs cold {cold.n_outer} "
          f"sweeps (converged={cold.converged})")
    if not warm.result.converged and cold.converged:
        raise SystemExit("[decomp] FAIL: warm-started solve did not reach "
                         "tolerance inside its freshness budget")
    if warm.result.n_outer > cold.n_outer:
        raise SystemExit("[decomp] FAIL: warm-start took more sweeps than "
                         "a cold solve")
    stats = svc.stats()
    print(f"[decomp] autotune: {stats['autotune']} "
          f"entries={stats['autotune_cache_entries']} (store: {cache})")
    print("[decomp] OK")
    return 0


def main(argv=None):
    argv = sys.argv[1:] if argv is None else list(argv)
    if argv and argv[0] == "decomp":
        return main_decomp(argv[1:])
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if not args.full:
        cfg = reduced(cfg)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    shape = ShapeConfig("serve", args.prompt_len, args.batch, "prefill")
    batch = model.make_batch(jax.random.PRNGKey(args.seed + 1), shape)

    engine = Engine(model, params, ServeConfig(
        max_new_tokens=args.new_tokens, temperature=args.temperature))
    t0 = time.perf_counter()
    out = engine.generate(batch, key=jax.random.PRNGKey(args.seed + 2))
    out.block_until_ready()
    dt = time.perf_counter() - t0
    toks = args.batch * args.new_tokens
    print(f"[serve] arch={cfg.name} generated {tuple(out.shape)} tokens in "
          f"{dt:.2f}s ({toks/dt:.1f} tok/s, includes compile)")
    print("[serve] first sequence:", out[0, :16].tolist(), "...")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
