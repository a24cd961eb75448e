"""Unit tests for the general-case T-transform factorization (Thm 3/4,
Lemma 2, Algorithm 1)."""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import (approximate_general, t_init, t_polish, t_objective,
                        t_to_dense, tapply, t_reconstruct, lemma2_spectrum)
from repro.core.polyutil import real_quartic_roots
from repro.core.types import SCALE, SHEAR, TFactors


def random_gen(n, seed=0):
    return np.random.default_rng(seed).standard_normal(
        (n, n)).astype(np.float32)


def random_tfactors(n, m, seed=0):
    rng = np.random.default_rng(seed)
    kind = rng.integers(0, 2, m).astype(np.int32)
    i = rng.integers(0, n, m).astype(np.int32)
    j = rng.integers(0, n, m).astype(np.int32)
    j = np.where((kind == SHEAR) & (j == i), (i + 1) % n, j)
    j = np.where(kind == SCALE, i, j)
    a = rng.uniform(0.5, 2.0, m).astype(np.float32) * rng.choice([-1, 1], m)
    return TFactors(jnp.asarray(kind), jnp.asarray(i), jnp.asarray(j),
                    jnp.asarray(a))


def test_inverse_roundtrip():
    n, m = 12, 30
    f = random_tfactors(n, m, 1)
    t = np.asarray(t_to_dense(f, n))
    tinv = np.asarray(t_to_dense(f, n, inverse=True))
    np.testing.assert_allclose(t @ tinv, np.eye(n), atol=1e-4)


def test_tapply_matches_dense():
    n, m = 10, 20
    f = random_tfactors(n, m, 2)
    t = np.asarray(t_to_dense(f, n))
    x = np.random.default_rng(0).standard_normal((n, 3)).astype(np.float32)
    y = tapply(f, jnp.asarray(x), axis=0)
    np.testing.assert_allclose(np.asarray(y), t @ x, rtol=1e-4, atol=1e-4)
    yi = tapply(f, jnp.asarray(x), inverse=True, axis=0)
    np.testing.assert_allclose(np.asarray(yi), np.linalg.solve(t, x),
                               rtol=1e-3, atol=1e-3)


def test_t_reconstruct_matches_dense():
    n, m = 9, 15
    f = random_tfactors(n, m, 3)
    cbar = jnp.asarray(np.arange(1, n + 1, dtype=np.float32))
    dense = np.asarray(t_to_dense(f, n))
    want = dense @ np.diag(np.arange(1, n + 1)) @ np.linalg.inv(dense)
    got = np.asarray(t_reconstruct(f, cbar))
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)


@pytest.mark.slow
def test_objective_decreases_over_iterations():
    c = jnp.asarray(random_gen(24, 4))
    _, _, info = approximate_general(c, m=48, n_iter=5, eps=0.0)
    hist = np.asarray(info["history"])
    hist = hist[~np.isnan(hist)]
    assert len(hist) >= 2
    assert np.all(np.diff(hist) <= 1e-3 * hist[0] + 1e-3)


def test_greedy_init_beats_diagonal_only():
    c = jnp.asarray(random_gen(16, 5))
    cbar = jnp.diagonal(c)
    base = float(jnp.sum((c - jnp.diag(cbar)) ** 2))
    factors, _ = t_init(c, cbar, 24)
    after = float(t_objective(c, factors, cbar))
    assert after < base


@pytest.mark.slow
def test_polish_never_regresses():
    c = jnp.asarray(random_gen(16, 6))
    cbar = jnp.diagonal(c)
    factors, _ = t_init(c, cbar, 20)
    before = float(t_objective(c, factors, cbar))
    f2 = t_polish(c, factors, cbar)
    after = float(t_objective(c, f2, cbar))
    assert after <= before + 1e-3 * abs(before) + 1e-3


@pytest.mark.slow
def test_lemma2_spectrum_improves_or_matches():
    c = jnp.asarray(random_gen(12, 7))
    cbar0 = jnp.diagonal(c)
    factors, _ = t_init(c, cbar0, 16)
    before = float(t_objective(c, factors, cbar0))
    cb = lemma2_spectrum(c, factors)
    after = float(t_objective(c, factors, cb))
    assert after <= before + 1e-3


def test_diagonalizable_exact_small():
    """A matrix that IS a short T-product times a diagonal reconstructs
    (near-)exactly once m is large enough."""
    n = 6
    f = random_tfactors(n, 4, seed=8)
    cbar = jnp.asarray(np.linspace(1.0, 2.0, n).astype(np.float32))
    c = t_reconstruct(f, cbar)
    _, _, info = approximate_general(c, m=24, n_iter=8)
    rel = float(info["objective"]) / float(jnp.sum(c * c))
    assert rel < 0.05


@pytest.mark.slow
def test_accuracy_improves_with_m():
    c = jnp.asarray(random_gen(24, 9))
    den = float(jnp.sum(c * c))
    errs = []
    for m in (12, 48, 120):
        _, _, info = approximate_general(c, m=m, n_iter=3)
        errs.append(float(info["objective"]) / den)
    assert errs[0] >= errs[1] >= errs[2]
    assert errs[2] < errs[0]


# -- the real-root quartic solver behind the scaling scores -------------


def _quartics(case, count=400, seed=0):
    """Seeded quartic coefficient rows (highest degree first)."""
    rng = np.random.default_rng(seed)
    # real roots at least 0.5 apart (a tighter random cluster is beyond
    # what f32 coefficients can resolve), then one pair merged or nearly
    gaps = rng.uniform(0.5, 2.0, (count, 3))
    roots = rng.uniform(-4.0, -2.0, (count, 1)) + np.concatenate(
        [np.zeros((count, 1)), np.cumsum(gaps, axis=1)], axis=1)
    if case == "random":
        return rng.standard_normal((count, 5))
    if case == "near_double":
        roots[:, 1] = roots[:, 0] + 1e-3
    elif case == "double":
        roots[:, 1] = roots[:, 0]
    if case in ("four_real", "near_double", "double"):
        scale = rng.uniform(0.1, 10.0, (count, 1))
        return scale * np.stack([np.poly(r) for r in roots])
    coeffs = rng.standard_normal((count, 5))
    coeffs[:, 0] *= {"small_lead": 1e-5, "zero_lead": 0.0}[case]
    return coeffs


@pytest.mark.parametrize("case,tol", [
    ("random", 1e-4), ("four_real", 1e-4), ("near_double", 5e-3),
    ("double", 5e-3), ("small_lead", 1e-4), ("zero_lead", 1e-4)])
def test_real_quartic_roots_match_numpy(case, tol):
    """Every real root numpy finds (within |x| <= 1e6) is matched, and
    every root the solver reports is one; close pairs are resolved to
    the f32 square-root limit (tol)."""
    coeffs = _quartics(case)
    roots, ok = real_quartic_roots(*jnp.asarray(coeffs.T, jnp.float32))
    roots, ok = np.asarray(roots), np.asarray(ok)
    for c, r, k in zip(coeffs, roots, ok):
        ref = np.roots(c if c[0] != 0 else c[1:])
        real = ref[(np.abs(ref.imag) <= 1e-7 * (1 + np.abs(ref)))
                   & (np.abs(ref) <= 1e6)].real
        got = r[k]
        for x in real:
            assert got.size, (c, real)
            assert np.min(np.abs(got - x)) <= tol * (1 + abs(x)), (c, x, got)
        for x in got:
            resid = abs(np.polyval(c, x)) / np.polyval(np.abs(c), abs(x))
            assert resid <= 1e-4, (c, x)


def test_directed_fit_n64_improves_objective():
    """A directed-graph fit at n=64 improves on the diagonal and over its
    sweeps as it did with the eigensolver root finder (0.0795 there)."""
    from repro.core import laplacian
    from repro.graphs import community_graph, directed_variant
    c = jnp.asarray(laplacian(directed_variant(community_graph(64, seed=1),
                                               seed=1)), jnp.float32)
    den = float(jnp.sum(c * c))
    diag_only = float(jnp.sum((c - jnp.diag(jnp.diagonal(c))) ** 2)) / den
    _, _, info = approximate_general(c, m=128, n_iter=2)
    rel = float(info["objective"]) / den
    hist = np.asarray(info["history"])
    hist = hist[~np.isnan(hist)]
    assert rel < 0.0796 < diag_only
    assert np.all(np.diff(hist) <= 0.0)
