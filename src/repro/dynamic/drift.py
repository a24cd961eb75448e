"""Drift scoring: how stale is a fitted basis on an updated Laplacian?

The fitted objective is ``||L - Ubar diag(s) Ubar^T||_F^2`` (equivalently
``||Ubar^T L Ubar - diag(s)||_F^2`` for the orthogonal G family).  After a
stream of edge updates moves ``L`` to ``L'``, the serving question is how
much of that objective the CURRENT basis has lost — WITHOUT a dense
eigendecomposition and without even materializing the reconstruction.

This module estimates the residual stochastically (Hutchinson):

    ||L' - Ubar diag(s) Ubar^T||_F^2  =  E_z ||(L' - Ubar diag(s) Ubar^T) z||^2

for Rademacher probes ``z``.  Each probe costs one dense matvec ``L' z``
(O(n^2)) plus one fused staged operator apply (O(g)) — the probe pass is
batched over the whole fleet in ONE jitted program (``jit`` of the vmapped
operator oracle), cached per (family, shape) so steady-state drift checks
trigger zero recompilation.  The DRIFT SCORE is the estimated relative
residual minus the relative objective the basis achieved when it was
(re)fitted: ~0 means the basis is as good as the day it was fitted,
positive values meter exactly the quality the update stream has eroded
(the quantity Le Magoarou et al. (1711.00386) show governs FGFT error).

Ragged (masked) bases need no special handling: ``L'`` is zero on the pad
block and the padded spectrum is zero, so pad coordinates contribute
nothing to the residual; per-graph normalization uses each graph's own
``||L'||_F^2``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.gtransform import full_f32_matmuls
from repro.core.staging import table_arrays as _tables
from repro.kernels.plan import ApplyPlan

_EPS = 1e-30


@functools.lru_cache(maxsize=None)
def _residual_program(plan, num_probes: int):
    """Cached jitted Hutchinson pass: (fwd tables, bwd tables, spectrum,
    laps, key) -> estimated relative residual, (B,) or scalar.  Keyed on
    the (hashable) ``ApplyPlan`` that names the operator — tables are
    ARGUMENTS (not closure constants) so a hot-swapped basis version
    with unchanged shapes reuses the compiled program; the plan's
    unjitted ``table_op`` embeds in this larger jitted probe pass
    instead of compiling its own program (DESIGN.md §13)."""
    op = plan.table_op()
    n, batched = plan.n, plan.batched

    def program(fwd_t, bwd_t, spectrum, laps, key):
        z = jax.random.rademacher(key, (num_probes, n), jnp.float32)
        if batched:
            z = jnp.broadcast_to(z, (laps.shape[0], num_probes, n))
        # (L' - recon) z, per probe: dense matvec + fused staged operator
        lz = jnp.einsum("...ij,...kj->...ki", laps, z)
        rz = lz - op(fwd_t, bwd_t, spectrum, z)
        est = jnp.mean(jnp.sum(rz * rz, axis=-1), axis=-1)
        den = jnp.maximum(jnp.sum(laps * laps, axis=(-2, -1)), _EPS)
        return est / den

    return jax.jit(full_f32_matmuls(program))


def estimate_rel_residual(basis, laps, *, num_probes: int = 8,
                          seed: int = 0) -> np.ndarray:
    """Hutchinson estimate of ``||L' - recon||_F^2 / ||L'||_F^2`` per
    graph ((B,) array, or a 0-d array unbatched).  Unbiased in the
    probes; relative std ~ sqrt(2 / num_probes).  Never forms a dense
    reconstruction or eigendecomposition."""
    laps = jnp.asarray(laps, jnp.float32)
    plan = ApplyPlan(family=basis.kind, mode="operator", n=basis.n,
                     batched=basis.batched)
    prog = _residual_program(plan, int(num_probes))
    return np.asarray(prog(_tables(basis.fwd), _tables(basis.bwd),
                           basis.spectrum, laps,
                           jax.random.PRNGKey(seed)))


def exact_rel_residual(basis, laps) -> np.ndarray:
    """Dense reference ``||L' - recon||_F^2 / ||L'||_F^2`` (materializes
    the (n, n) reconstruction — small-n tests and maintenance paths
    only)."""
    laps = jnp.asarray(laps, jnp.float32)
    den = np.maximum(np.asarray(jnp.sum(laps * laps, axis=(-2, -1))),
                     _EPS)
    return np.asarray(basis.frobenius_error(laps)) / den


def relative_objective(objective, laps) -> np.ndarray:
    """Per-graph relative objective ``obj / max(||L||_F^2, eps)`` — THE
    baseline normalization of the drift score (one definition shared by
    the serving engine's baselines and ``drift_score``)."""
    laps = jnp.asarray(laps, jnp.float32)
    den = np.maximum(np.asarray(jnp.sum(laps * laps, axis=(-2, -1))),
                     _EPS)
    return np.atleast_1d(np.asarray(objective)) / np.atleast_1d(den)


def drift_score(basis, laps, baseline=None, *, num_probes: int = 8,
                seed: int = 0) -> np.ndarray:
    """Per-graph drift: estimated relative residual on ``laps`` minus the
    ``baseline`` relative residual recorded when the basis was last
    (re)fitted (default: the basis's own fitted objective), floored at 0.

    A freshly fitted basis scores ~0 on its own Laplacians; the score
    grows with every update batch the basis has not absorbed — the
    refit-policy controller (dynamic/refit.py) thresholds exactly this
    number."""
    est = estimate_rel_residual(basis, laps, num_probes=num_probes,
                                seed=seed)
    if baseline is None:
        if basis.objective is None:
            raise ValueError("basis has no recorded objective; pass an "
                             "explicit baseline")
        baseline = relative_objective(basis.objective, laps)
        if not np.ndim(est):
            baseline = baseline.reshape(())
    return np.maximum(est - np.asarray(baseline), 0.0)
