"""How a cell drives the program: whole solves back to back.

The driver makes its cell's data from the seed, warms up the shapes the
window will use, runs the window, and then checks a sample of what the
window produced against ``reference.cpapr``.  It returns a record: the
host-clock times and counts the metric readers take their numbers from.

From the program it uses only ``cpapr_mu``, ``CPAPRConfig``,
``SparseTensor`` and ``KTensor``.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

import gen
import reference


class Window:
    """The measured window: its clock, its trace and its compile count.

    Programs compiled or loaded from the persistent cache while the window
    is open are counted through ``jax.monitoring``, and the misses among
    them apart.  Nothing compiled in the window is written to the cache:
    where the program's shapes follow each input's data (a blocked
    layout), a deployment meets new shapes all the time, so every run pays
    those compiles in its window, and a later run of the same seed does not
    find them cached.  With ``trace_dir`` the window is traced by the
    profiler, under a host span ``bench.window``.
    """

    COMPILE = "/jax/core/compile/backend_compile_duration"
    MISS = "/jax/compilation_cache/cache_misses"
    NO_WRITES = 1e9  # seconds a compile must take to be written

    def __init__(self, trace_dir: str | None):
        self.trace_dir = trace_dir
        self.compiles = self.misses = 0
        self.open = False
        self.start = self.end = None
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.monitoring.register_event_listener(self._on_miss)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if self.open and event == self.COMPILE:
            self.compiles += 1

    def _on_miss(self, event: str, **_) -> None:
        if self.open and event == self.MISS:
            self.misses += 1

    def __enter__(self):
        key = "jax_persistent_cache_min_compile_time_secs"
        self._min_compile = getattr(jax.config, key)
        jax.config.update(key, self.NO_WRITES)
        if self.trace_dir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._span = jax.profiler.TraceAnnotation("bench.window")
            self._span.__enter__()
        self.open = True
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.end = time.perf_counter()
        self.open = False
        if self.trace_dir:
            self._span.__exit__(*exc)
            jax.profiler.stop_trace()
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          self._min_compile)
        print(f"window: {self.compiles} programs compiled or loaded from "
              f"the cache, {self.misses} of them compiled", flush=True)
        return False


def span(name: str):
    return jax.profiler.TraceAnnotation(name)


def memory_peak() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def _algorithm(config: dict) -> dict:
    return {k: config["algorithm"][k] for k in ("eps", "kappa", "kappa_tol")}


def _host_model(kt) -> tuple:
    return (np.asarray(kt.lam), [np.asarray(f) for f in kt.factors])


def _ktensor(model):
    from repro.core import KTensor

    lam, factors = model
    return KTensor(lam=jnp.asarray(lam),
                   factors=tuple(jnp.asarray(f) for f in factors))


def _sparse(dims, indices, values):
    from repro.core import SparseTensor

    return SparseTensor(shape=tuple(int(d) for d in dims),
                        indices=jnp.asarray(indices),
                        values=jnp.asarray(values))


def solves(config: dict, mix: dict, seed: int, seconds: float,
           trace_dir: str | None, t_start: float, control: bool) -> dict:
    from repro.core import CPAPRConfig, cpapr_mu

    dims, nnz, rank = config["dims"], config["nnz"], config["rank"]
    planted = gen.ktensor(gen.rng(seed, gen.PLANTED), dims, rank)
    indices, values = gen.poisson_tensor(gen.rng(seed, gen.DRAWS), dims,
                                         nnz, planted)
    tensor = _sparse(dims, indices, values)
    cfg = CPAPRConfig(rank=rank, **config["cpapr"], **mix["request"],
                      **_algorithm(config))
    print(f"solve: {config['tensor']} dims={dims} nnz={nnz} R={rank} "
          f"strategy={cfg.strategy} max_outer={cfg.max_outer} "
          f"max_inner={cfg.max_inner} tol={cfg.tol}", flush=True)

    def one(i: int, cfg=cfg) -> dict:
        start = gen.ktensor(gen.rng(seed, gen.START, i), dims, rank)
        t0 = time.perf_counter()
        with span("bench.solve"):
            res = cpapr_mu(tensor, rank, init=_ktensor(start), config=cfg)
            jax.block_until_ready(res.ktensor.factors)
        return {"i": i, "start": start, "answer": _host_model(res.ktensor),
                "n_outer": res.n_outer, "inner": int(sum(res.inner_iters)),
                "kkt": res.kkt_history, "recoveries": len(res.recoveries or []),
                "seconds": time.perf_counter() - t0}

    # one sweep runs every program of the window: the sweep count is not
    # part of any program, and each solve traces its programs anew
    print(f"data: {time.time() - t_start:.3f} s since start", flush=True)
    warm = one(0, dataclasses.replace(cfg, max_outer=1))
    print(f"warm-up solve: {warm['n_outer']} sweeps, {warm['inner']} inner "
          f"iterations, {warm['seconds']:.3f} s", flush=True)
    done, failed = [], 0
    with Window(trace_dir) as w:
        setup_s = time.time() - t_start
        while not done or time.perf_counter() - w.start < seconds:
            try:
                done.append(one(len(done) + failed + 1))
            except Exception as e:  # a failed solve counts, the run goes on
                print(f"solve failed: {type(e).__name__}: {e}", flush=True)
                failed += 1
                if failed > 3:
                    break
    peak = memory_peak()
    print(f"window: {len(done)} solves, {sum(s['n_outer'] for s in done)} "
          f"sweeps in {w.end - w.start:.3f} s; per solve "
          f"{[round(s['seconds'], 3) for s in done]}; recoveries "
          f"{sum(s['recoveries'] for s in done)}", flush=True)
    del tensor
    gc.collect()

    checks = {}
    t0 = time.perf_counter()
    if done:
        pick = done[int(gen.rng(seed, gen.SAMPLE).integers(len(done)))]
        data = tuple(jnp.asarray(a) for a in
                     reference.chunks(indices, values))
        kw = dict(max_outer=cfg.max_outer, max_inner=cfg.max_inner,
                  tol=cfg.tol, **_algorithm(config))
        ref = reference.cpapr(indices, values, dims, pick["start"],
                              data=data, **kw)
        checks["factor_gap"] = reference.factor_gap(pick["answer"], ref)
        print(f"check: solve {pick['i']}: program kkt {pick['kkt']} "
              f"reference kkt {ref['kkt']}", flush=True)
        if control:
            low = reference.cpapr(indices, values, dims, pick["start"],
                                  data=data, dtype=jnp.bfloat16, **kw)
            checks["control.factor_gap"] = reference.factor_gap(
                (low["lam"], low["factors"]), ref)
    print(f"check: {time.perf_counter() - t0:.3f} s", flush=True)
    return {
        "setup_s": setup_s, "window_s": w.end - w.start,
        "compiles_in_window": w.compiles, "memory_peak_bytes": peak,
        "attempted": len(done) + failed, "failed": failed,
        "solves": [{k: s[k] for k in ("n_outer", "inner", "seconds")}
                   for s in done],
        "sweeps": sum(s["n_outer"] for s in done),
        "work": {"nnz": nnz, "dims": dims, "rank": rank},
        "checks": checks,
    }
