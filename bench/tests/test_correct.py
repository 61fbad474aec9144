"""``correct`` holds for the program and fails for its control and faults.

Each cell is driven end to end at a tiny size on the CPU, past the chip
check, against the cell's own limits.  The control is the reference in
bfloat16 put in the program's place.  The faults are planted underneath
the entry point the window drives, ``cpapr_mu``: a solve that returns its starting
state unchanged, a solve over half of the nonzeros, and an answer altered
by 5% where it is produced.  (The cells run on one chip, so no exchange
between chips can be left out.)
"""
import dataclasses
import types

import jax.numpy as jnp
import numpy as np
import pytest

import reference
from conftest import run_tiny, tiny_cell

def _unchanged(real):
    def f(*a, config=None, **kw):
        return real(*a, config=dataclasses.replace(config, max_outer=0),
                    **kw)
    return f


def _half(t):
    from repro.core import SparseTensor

    h = max(t.nnz // 2, 1)
    return SparseTensor(shape=t.shape, indices=t.indices[:h],
                        values=t.values[:h])


def _altered(res):
    from repro.core import KTensor

    kt = res.ktensor
    f0 = np.array(kt.factors[0])
    k = np.argmax(f0 * np.asarray(kt.lam)[None, :])
    f0.reshape(-1)[k] *= 1.05
    return dataclasses.replace(res, ktensor=KTensor(
        lam=kt.lam, factors=(jnp.asarray(f0),) + tuple(kt.factors[1:])))


def _bf16_reference(t, rank, init=None, config=None, **_):
    """The control in ``cpapr_mu``'s place: the reference in bfloat16."""
    from repro.core import KTensor

    out = reference.cpapr(
        np.asarray(t.indices), np.asarray(t.values), t.shape,
        (np.asarray(init.lam), [np.asarray(f) for f in init.factors]),
        max_outer=config.max_outer, max_inner=config.max_inner,
        tol=config.tol, eps=config.eps, kappa=config.kappa,
        kappa_tol=config.kappa_tol, dtype=jnp.bfloat16)
    kt = KTensor(lam=jnp.asarray(out["lam"], jnp.float32),
                 factors=tuple(jnp.asarray(f, jnp.float32)
                               for f in out["factors"]))
    return types.SimpleNamespace(
        ktensor=kt, n_outer=len(out["kkt"]), inner_iters=[0],
        kkt_history=out["kkt"], recoveries=[], sweep_budget=0)


def _plant(monkeypatch, fault):
    """Break the timed path underneath its entry point, ``cpapr_mu``."""
    import repro.core

    real = repro.core.cpapr_mu
    wrap = {
        "unchanged": _unchanged(real),
        "half": lambda t, *a, **kw: real(_half(t), *a, **kw),
        "altered": lambda *a, **kw: _altered(real(*a, **kw)),
        "control": _bf16_reference,
    }[fault]
    monkeypatch.setattr(repro.core, "cpapr_mu", wrap)


CELLS = ["chicago-r16.solve", "uber-r16.solve"]


@pytest.mark.parametrize("cell", CELLS)
def test_the_program_is_correct(cell):
    record, line = run_tiny(tiny_cell(cell))
    assert record["attempted"] > 0 and line["failed"] == 0
    assert line["correct"], line["checks"]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["control", "unchanged", "half",
                                   "altered"])
def test_control_and_faults_are_not_correct(cell, fault, monkeypatch):
    _plant(monkeypatch, fault)
    _, line = run_tiny(tiny_cell(cell))
    assert not line["correct"], line["checks"]
