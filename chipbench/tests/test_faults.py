"""The harness drives a whole run (no chip look, tiny sizes on the
CPU) with the timed path broken underneath, and ``correct`` comes out
false for every fault a cell can have.  A sound run of the same cell
comes out true."""
import time

import jax.numpy as jnp
import numpy as np
import pytest

import harness
from repro.core import eigenbasis
from repro.launch.serve import FGFTServeEngine

SERVING = ["sym-bulk", "dir-bulk"]


@pytest.fixture
def run(bench_root):
    def _run(workload, seed=1234567890123):
        result, _ = harness.run(bench_root, workload, seed, 1.5, False,
                                True, time.perf_counter())
        return result
    return _run


def _break_step(monkeypatch, how):
    """Break the engine step that every served answer comes from."""
    real = FGFTServeEngine.step_versioned
    real_bank = FGFTServeEngine.step_bank_versioned

    def alter(y, x):
        if how == "altered":          # one answer row changed
            return y.at[..., 0, :].add(1.0)
        if how == "half":             # half of the rows left out
            return y.at[..., y.shape[-2] // 2:, :].set(0.0)
        return x                      # state returned unchanged

    def step(self, signals, h=None, tier=None):
        y, v = real(self, signals, h, tier)
        return alter(y, signals), v

    def step_bank(self, signals):
        y, v = real_bank(self, signals)
        x = jnp.broadcast_to(signals[:, None], y.shape)
        return alter(y, x), v

    monkeypatch.setattr(FGFTServeEngine, "step_versioned", step)
    monkeypatch.setattr(FGFTServeEngine, "step_bank_versioned", step_bank)


@pytest.mark.parametrize("workload", SERVING + ["sym-onboard"])
def test_sound_run_is_correct(workload, run):
    result = run(workload)
    assert result["correct"], result["checks"]


@pytest.mark.parametrize("how", ["altered", "half", "unchanged"])
@pytest.mark.parametrize("workload", SERVING + ["sym-onboard"])
def test_broken_answers_are_not_correct(workload, how, monkeypatch, run):
    _break_step(monkeypatch, how)
    result = run(workload)
    assert not result["correct"], result["checks"]


def test_fit_that_returns_its_state_unchanged_is_not_correct(monkeypatch,
                                                            run):
    """The fit hands back the identity chain it started from."""
    real = eigenbasis.ApproxEigenbasis.fit.__func__

    def fit(cls, mats, num_transforms, **kwargs):
        basis = real(cls, mats, num_transforms, **kwargs)
        f = basis.factors
        ident = type(f)(i=f.i, j=f.j, c=jnp.ones_like(f.c),
                        s=jnp.zeros_like(f.s), sigma=jnp.ones_like(f.sigma))
        from repro.core.staging import pack_g_batch_pair
        fwd, bwd = pack_g_batch_pair(ident, basis.n)
        diag = jnp.asarray(np.diagonal(np.asarray(mats), axis1=-2,
                                       axis2=-1))
        return type(basis)(kind=basis.kind, n=basis.n, batched=True,
                           factors=ident, spectrum=diag, fwd=fwd, bwd=bwd,
                           objective=basis.objective, info=basis.info,
                           sizes=basis.sizes)

    monkeypatch.setattr(eigenbasis.ApproxEigenbasis, "fit",
                        classmethod(fit))
    result = run("sym-onboard")
    assert not result["correct"], result["checks"]
    assert result["checks"]["fit_objective"]["value"] > \
        result["checks"]["fit_objective"]["limit"]
