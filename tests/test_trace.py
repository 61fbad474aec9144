"""Host spans, counters and device scopes of ``cpapr_mu``.

Each solve runs on the CPU under ``jax.profiler.trace`` on a tiny tensor,
and the spans are read back from the trace the profiler wrote.  The
device scopes are read from the op metadata of one compiled mode update.
"""
import glob
import re

import jax
import pytest
from jax.profiler import ProfileData

from repro.core import CPAPRConfig, cpapr, cpapr_mu
from repro.core.sparse_tensor import (random_ktensor, random_poisson_tensor,
                                     sort_mode)
from repro.testing import faults

SHAPE, RANK = (12, 9, 7), 3

# each span and the span it nests in
PARENT = {
    "cpapr.prepare": "cpapr.solve",
    "cpapr.validate": "cpapr.prepare",
    "cpapr.sort": "cpapr.prepare",
    "cpapr.policy": "cpapr.prepare",
    "cpapr.build": "cpapr.prepare",
    "cpapr.sweep": "cpapr.solve",
    "cpapr.mode_update": "cpapr.sweep",
    "cpapr.loglik": "cpapr.sweep",
    "cpapr.rebalance": "cpapr.sweep",
    "cpapr.checkpoint": "cpapr.sweep",
}


@pytest.fixture(scope="module")
def tensor():
    t, _ = random_poisson_tensor(jax.random.PRNGKey(0), SHAPE, 300, rank=RANK)
    return t


def traced(tmp_path, fn):
    """``fn()`` under the profiler: its result and the ``cpapr.*`` spans
    as ``(name, start_ns, end_ns, stats)``."""
    with jax.profiler.trace(str(tmp_path / "trace")):
        out = fn()
    (path,) = glob.glob(str(tmp_path / "trace" / "plugins" / "profile" /
                            "*" / "*.xplane.pb"))
    spans = [(e.name, e.start_ns, e.end_ns, dict(e.stats))
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events
             if e.name.startswith("cpapr.")]
    return out, spans


def parent_of(span, spans):
    """The shortest other span that holds ``span``."""
    _, s, e, _ = span
    holders = [x for x in spans if x is not span and x[1] <= s and e <= x[2]]
    return min(holders, key=lambda x: x[2] - x[1])[0] if holders else None


def solve_span(spans):
    (solve,) = [x for x in spans if x[0] == "cpapr.solve"]
    return solve


@pytest.mark.parametrize("strategy", ["segment", "pallas"])
def test_every_span_is_present_and_nested(tensor, tmp_path, strategy):
    cfg = CPAPRConfig(rank=RANK, max_outer=3, max_inner=4, tol=1e-12,
                      strategy=strategy)
    res, spans = traced(tmp_path, lambda: cpapr_mu(tensor, RANK, config=cfg))
    names = [x[0] for x in spans]
    for name in ("cpapr.solve", "cpapr.prepare", "cpapr.validate",
                 "cpapr.policy", "cpapr.build"):
        assert names.count(name) == 1, name
    assert names.count("cpapr.sort") == len(SHAPE)
    assert names.count("cpapr.sweep") == res.n_outer == 3
    assert names.count("cpapr.mode_update") == 3 * len(SHAPE)
    assert names.count("cpapr.loglik") == 3
    assert not {"cpapr.rebalance", "cpapr.checkpoint",
                "cpapr.recover"} & set(names)
    assert parent_of(solve_span(spans), spans) is None
    for span in spans:
        if span[0] != "cpapr.solve":
            assert parent_of(span, spans) == PARENT[span[0]], span
    sorts = [x[3]["mode"] for x in spans if x[0] == "cpapr.sort"]
    assert sorts == list(range(len(SHAPE)))
    updates = [x[3] for x in spans if x[0] == "cpapr.mode_update"]
    assert updates == [{"mode": n, "strategy": strategy}
                       for _ in range(3) for n in range(len(SHAPE))]
    assert [x[3]["outer"] for x in spans if x[0] == "cpapr.sweep"] == \
        [1, 2, 3]


@pytest.mark.parametrize("guard", [True, False])
def test_solve_span_carries_its_counts(tensor, tmp_path, guard):
    cfg = CPAPRConfig(rank=RANK, max_outer=3, max_inner=4, tol=1e-12,
                      guard=guard)
    res, spans = traced(tmp_path, lambda: cpapr_mu(tensor, RANK, config=cfg))
    stats = solve_span(spans)[3]
    assert stats["sweeps"] == res.n_outer == 3
    assert stats["inner"] == sum(res.inner_iters)
    assert stats["modes"] == len(SHAPE)
    # each sweep reads its worst KKT, its inner iterations and the
    # log-likelihood; the guard adds each mode's KKT scalar and flag
    per_sweep = 3 + (2 * len(SHAPE) if guard else 0)
    assert stats["host_syncs"] == per_sweep * res.n_outer


def _checkpointed(tensor, path, **kw):
    return CPAPRConfig(rank=RANK, max_outer=3, max_inner=4, tol=1e-12,
                       checkpoint_every=1, checkpoint_path=str(path), **kw)


def _rebalance_and_checkpoint(tensor, tmp_path):
    cfg = _checkpointed(tensor, tmp_path / "ck.bin", strategy="sharded",
                        n_shards=2, rebalance_every=1)
    return lambda: cpapr_mu(tensor, RANK, config=cfg)


def _resume(tensor, tmp_path):
    cfg = _checkpointed(tensor, tmp_path / "ck.bin")
    with faults.kill_at_sweep(2), pytest.raises(faults.KilledError):
        cpapr_mu(tensor, RANK, config=cfg)
    return lambda: cpapr_mu(tensor, RANK, config=cfg,
                            resume_from=str(tmp_path / "ck.bin"))


def _nan_guard(tensor, tmp_path):
    cfg = CPAPRConfig(rank=RANK, max_outer=3, max_inner=4, tol=1e-12)

    def run():
        with faults.inject_nan(mode=0, outer=1):
            return cpapr_mu(tensor, RANK, config=cfg)
    return run


def _demotion(tensor, tmp_path):
    cfg = CPAPRConfig(rank=RANK, max_outer=3, max_inner=4, tol=1e-12,
                      strategy="blocked", demote_backoff=0.0)

    def run():
        with faults.fail_strategy(strategy="blocked", mode=0):
            return cpapr_mu(tensor, RANK, config=cfg)
    return run


@pytest.mark.parametrize("setup,want", [
    (_rebalance_and_checkpoint, {"cpapr.rebalance": "cpapr.sweep",
                                 "cpapr.checkpoint": "cpapr.sweep"}),
    (_resume, {"cpapr.recover": "cpapr.prepare"}),
    (_nan_guard, {"cpapr.recover": "cpapr.sweep"}),
    (_demotion, {"cpapr.recover": "cpapr.sweep"}),
], ids=["rebalance-checkpoint", "resume", "nan-guard", "demotion"])
def test_rare_paths_have_their_spans(tensor, tmp_path, setup, want):
    res, spans = traced(tmp_path, setup(tensor, tmp_path))
    for name, parent in want.items():
        found = [x for x in spans if x[0] == name]
        assert found, name
        assert {parent_of(x, spans) for x in found} == {parent}
    if "cpapr.recover" in want:
        assert res.recoveries


def _innermost_scope(op_name: str):
    scopes = [c for c in op_name.split("/") if c.startswith("cpapr.")]
    return scopes[-1] if scopes else None


@pytest.mark.parametrize("strategy,scopes", [
    ("pallas", {"cpapr.pi", "cpapr.layout", "cpapr.phi", "cpapr.epilogue"}),
    ("segment", {"cpapr.pi", "cpapr.phi", "cpapr.epilogue"}),
])
def test_mode_update_ops_carry_their_scopes(tensor, strategy, scopes):
    """Every op of the compiled ``_update`` is named by a ``cpapr.*``
    scope; the segment path has no layout."""
    cfg = CPAPRConfig(rank=RANK, strategy=strategy)
    kt = random_ktensor(jax.random.PRNGKey(1), SHAPE, RANK).normalize()
    mvs = [sort_mode(tensor, n) for n in range(len(SHAPE))]
    strategies, layouts, _, locals_ = cpapr._resolve_mode_policies(
        cfg, mvs, kt.factors, kt.lam)
    update, _ = cpapr._make_mode_update(mvs[0], cfg, strategies[0],
                                        layouts[0], locals_[0])
    hlo = update.func.lower(*update.args, tuple(kt.factors),
                            kt.lam).compile().as_text()
    ops = [n for n in re.findall(r'op_name="([^"]*)"', hlo)
           if n.startswith("jit(_update)/")]
    assert ops
    assert {_innermost_scope(n) for n in ops} == scopes
