"""Unit tests for the symmetric-case G-transform factorization (Thm 1/2,
Lemma 1, Algorithm 1)."""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import (approximate_symmetric, g_init, g_polish, g_objective,
                        g_to_dense, gapply, lemma1_spectrum)
from repro.core.gtransform import (_gain_matrix, _procrustes_2x2,
                                  _refresh_row_max)
from repro.core.types import GFactors, gfactors_identity


def random_sym(n, seed=0, psd=False):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, n)).astype(np.float32)
    return (x @ x.T if psd else x + x.T)


def test_g_to_dense_orthonormal():
    s = jnp.asarray(random_sym(24, 1))
    factors, _, _ = approximate_symmetric(s, g=40, n_iter=2)
    u = g_to_dense(factors, 24)
    np.testing.assert_allclose(np.asarray(u @ u.T), np.eye(24), atol=1e-5)


def test_gapply_matches_dense():
    n = 16
    s = jnp.asarray(random_sym(n, 2))
    factors, _, _ = approximate_symmetric(s, g=20, n_iter=1)
    u = np.asarray(g_to_dense(factors, n))
    x = np.random.default_rng(0).standard_normal((n, 5)).astype(np.float32)
    y = gapply(factors, jnp.asarray(x), axis=0)
    np.testing.assert_allclose(np.asarray(y), u @ x, atol=1e-5)
    yt = gapply(factors, jnp.asarray(x), adjoint=True, axis=0)
    np.testing.assert_allclose(np.asarray(yt), u.T @ x, atol=1e-5)


def test_objective_decreases_over_iterations():
    s = jnp.asarray(random_sym(32, 3))
    _, _, info = approximate_symmetric(s, g=64, n_iter=6, eps=0.0)
    hist = np.asarray(info["history"])
    hist = hist[~np.isnan(hist)]
    assert len(hist) >= 2
    assert np.all(np.diff(hist) <= 1e-3 * hist[0])  # monotone (fp slack)


@pytest.mark.slow
def test_update_spectrum_beats_fixed():
    s = jnp.asarray(random_sym(32, 4))
    ev = np.linalg.eigvalsh(np.asarray(s))
    _, _, info_fix = approximate_symmetric(
        s, g=48, n_iter=3, sbar=jnp.asarray(np.sort(ev)[::-1].copy()),
        update_spectrum=False)
    _, _, info_upd = approximate_symmetric(s, g=48, n_iter=3,
                                           update_spectrum=True)
    assert float(info_upd["objective"]) <= float(info_fix["objective"]) * 1.05


@pytest.mark.slow
def test_theorem1_score_matches_bruteforce():
    """The analytic pair gain must equal the brute-force objective drop."""
    n = 8
    s_np = random_sym(n, 5)
    s = jnp.asarray(s_np)
    rng = np.random.default_rng(6)
    sbar = jnp.asarray(np.sort(rng.standard_normal(n))[::-1]
                       .copy().astype(np.float32))
    gains = np.asarray(_gain_matrix(s, sbar))
    base = float(jnp.sum((s - jnp.diag(sbar)) ** 2))
    for i in range(n):
        for j in range(i + 1, n):
            c, sv, sg = _procrustes_2x2(s[i, i], s[j, j], s[i, j],
                                        sbar[i], sbar[j])
            f = gfactors_identity(1)
            f = GFactors(f.i.at[0].set(i), f.j.at[0].set(j),
                         f.c.at[0].set(c), f.s.at[0].set(sv),
                         f.sigma.at[0].set(sg))
            obj = float(g_objective(s, f, sbar))
            # objective drop = 2 * gain
            np.testing.assert_allclose(base - obj, 2 * gains[i, j],
                                       rtol=1e-3, atol=1e-3)


def test_row_maxima_pick_the_whole_matrix_argmax():
    """The greedy's row maxima, refreshed after each step's row/column
    updates, pick the pair a row-major argmax over the whole gain matrix
    picks, ties and all-(-inf) padding rows included."""
    rng = np.random.default_rng(0)
    n, pad = 16, 3
    g = rng.integers(0, 4, (n, n)).astype(np.float32)
    g = np.maximum(g, g.T)
    g[-pad:] = g[:, -pad:] = -np.inf
    np.fill_diagonal(g, -np.inf)
    gains = jnp.asarray(g)
    rmax = jnp.max(gains, axis=1)
    rarg = jnp.argmax(gains, axis=1).astype(jnp.int32)
    for _ in range(60):
        i, j = (jnp.int32(v) for v in sorted(rng.choice(n - pad, 2, False)))
        gi, gj = (np.where(np.arange(n) < n - pad,
                           rng.integers(0, 4, n), -np.inf).astype(np.float32)
                  for _ in range(2))
        gi[i], gj[j] = -np.inf, -np.inf
        gains = (gains.at[i].set(gi).at[:, i].set(gi).at[j].set(gj)
                 .at[:, j].set(gj).at[j, i].set(gj[i]).at[i, j].set(gj[i]))
        rmax, rarg = _refresh_row_max(gains, rmax, rarg, i, j,
                                      jnp.asarray(gi), jnp.asarray(gj))
        np.testing.assert_array_equal(rmax, jnp.max(gains, axis=1))
        np.testing.assert_array_equal(rarg, jnp.argmax(gains, axis=1))
        p = int(jnp.argmax(rmax))
        assert p * n + int(rarg[p]) == int(jnp.argmax(gains))


def test_equal_sbar_entries_give_zero_gain():
    s = jnp.asarray(random_sym(6, 7))
    sbar = jnp.ones(6, jnp.float32)
    gains = np.asarray(_gain_matrix(s, sbar))
    off = gains[~np.eye(6, dtype=bool)]
    np.testing.assert_allclose(off, 0.0, atol=1e-4)


def test_lemma1_spectrum_is_optimal():
    s = jnp.asarray(random_sym(16, 8))
    factors, _, _ = approximate_symmetric(s, g=24, n_iter=1,
                                          update_spectrum=False)
    sb_star = lemma1_spectrum(s, factors)
    obj_star = float(g_objective(s, factors, sb_star))
    rng = np.random.default_rng(9)
    for _ in range(5):
        perturbed = sb_star + jnp.asarray(
            rng.standard_normal(16).astype(np.float32) * 0.1)
        assert obj_star <= float(g_objective(s, factors, perturbed)) + 1e-4


def test_polish_never_regresses():
    s = jnp.asarray(random_sym(24, 10))
    factors, w = g_init(s, jnp.diagonal(s), 32)
    sbar = jnp.diagonal(w)
    before = float(g_objective(s, factors, sbar))
    f2 = g_polish(s, factors, sbar)
    after = float(g_objective(s, f2, sbar))
    assert after <= before + 1e-3 * abs(before)


def test_diagonal_matrix_is_exact():
    d = jnp.asarray(np.diag(np.arange(1, 9)).astype(np.float32))
    factors, sbar, info = approximate_symmetric(d, g=4, n_iter=1)
    assert float(info["objective"]) < 1e-6


@pytest.mark.slow
def test_accuracy_improves_with_g():
    s = jnp.asarray(random_sym(32, 11))
    den = float(jnp.sum(s * s))
    errs = []
    for g in (16, 64, 160):
        _, _, info = approximate_symmetric(s, g=g, n_iter=3)
        errs.append(float(info["objective"]) / den)
    assert errs[0] > errs[1] > errs[2]


def test_psd_better_than_indefinite():
    """Paper Fig. 5: PSD matrices are approximated more accurately."""
    n, g = 32, 80
    e_psd, e_ind = [], []
    for seed in range(3):
        sp = jnp.asarray(random_sym(n, seed, psd=True))
        si = jnp.asarray(random_sym(n, seed + 100, psd=False))
        _, _, ip = approximate_symmetric(sp, g=g, n_iter=3)
        _, _, ii = approximate_symmetric(si, g=g, n_iter=3)
        e_psd.append(float(ip["objective"]) / float(jnp.sum(sp * sp)))
        e_ind.append(float(ii["objective"]) / float(jnp.sum(si * si)))
    assert np.mean(e_psd) < np.mean(e_ind)
