"""General (unsymmetric) case: scaling + shear T-transform factorization.

Implements the paper's unsymmetric pipeline:
  * Theorem 3 — greedy initialization.  For every ordered pair (i, j) the
    shear cost ``||C - T B T^{-1}||_F^2`` is an exact quartic polynomial in
    the shear parameter ``a`` (re-derived from first principles; see
    DESIGN.md — the supplementary's printed formulas contain typos), so the
    full O(n^2) score sweep is elementwise with closed-form cubic
    root-finding.  The n scaling costs are quartics in ``a`` divided by
    ``a^2``; they are fit exactly through 5 samples and minimized through a
    4x4 companion eigensolve.
  * Theorem 4 (polish variant) — per-transform value refit with indices
    fixed, O(n^2) per transform via rank-2 residual algebra (the paper's own
    experimental setting; the full index re-search is O(n^4) and the paper
    itself does not use it in experiments).
  * Lemma 2 — spectrum refit.  We solve the *normal equations* of the
    Khatri-Rao least squares: ``G = (Tinv Tinv^T) ⊙ (T^T T)``,
    ``r = diag(T^T C Tinv^T)``, an O(n^3) exact solve instead of the naive
    O(n^4) stated in the paper.
  * Algorithm 1 driver for the general case.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .types import SCALE, SHEAR, TFactors, tfactors_identity
from .gtransform import (_masked_default_spectrum, _valid_coords,
                         full_f32_matmuls)
from .polyutil import (QUARTIC_POINTS, fit_quartic, minimize_quartic,
                       real_cubic_roots, real_quartic_roots)

# Transform-parameter bounds.  The optimal local 'a' can be enormous when
# the quartic's leading coefficients are tiny; huge shears/scales are
# numerically toxic (kappa(Tbar) explodes, f32 state overflows — observed
# objective blow-ups to 1e31 with a 1e4 clip).  |a| in [1/32, 32] keeps
# every factor well-conditioned; the greedy just spends more factors.
_A_CLIP = 32.0
_A_MIN_SCALE = 1.0 / 32.0


# ---------------------------------------------------------------------------
# Application of T-transform products
# ---------------------------------------------------------------------------

def _tapply_axis0(factors: TFactors, x: jnp.ndarray,
                  inverse: bool) -> jnp.ndarray:
    """Apply Tbar (or Tbar^{-1}) to x with coordinates on axis 0."""

    def body(carry, f):
        kind, i, j, a = f
        xi = carry[i]
        xj = carry[j]

        def do_scale(c):
            return c.at[i].set(a * xi)

        def do_shear(c):
            return c.at[i].set(xi + a * xj)

        carry = lax.cond(kind == SCALE, do_scale, do_shear, carry)
        return carry, None

    if inverse:
        a_inv = jnp.where(factors.kind == SCALE,
                          1.0 / factors.a, -factors.a)
        xs = (factors.kind[::-1], factors.i[::-1], factors.j[::-1],
              a_inv[::-1].astype(x.dtype))
    else:
        xs = (factors.kind, factors.i, factors.j, factors.a.astype(x.dtype))
    out, _ = lax.scan(body, x, xs)
    return out


def tapply(factors: TFactors, x: jnp.ndarray, inverse: bool = False,
           axis: int = -1) -> jnp.ndarray:
    """Compute ``Tbar @ x`` (or ``Tbar^{-1} @ x``) along ``axis``."""
    moved = jnp.moveaxis(x, axis, 0)
    out = _tapply_axis0(factors, moved, inverse)
    return jnp.moveaxis(out, 0, axis)


def t_to_dense(factors: TFactors, n: int, inverse: bool = False,
               dtype=jnp.float32) -> jnp.ndarray:
    return tapply(factors, jnp.eye(n, dtype=dtype), inverse=inverse, axis=0)


def _conjugate_inplace(m, kind, i, j, a):
    """m <- T m T^{-1} via exact sequential row/col ops (O(n))."""

    def do_scale(mm):
        mm = mm.at[i].multiply(a)
        mm = mm.at[:, i].multiply(1.0 / a)
        return mm

    def do_shear(mm):
        mm = mm.at[i].add(a * mm[j])
        mm = mm.at[:, j].add(-a * mm[:, i])
        return mm

    return lax.cond(kind == SCALE, do_scale, do_shear, m)


def t_reconstruct(factors: TFactors, cbar: jnp.ndarray) -> jnp.ndarray:
    """Dense ``Tbar diag(cbar) Tbar^{-1}``."""
    m0 = jnp.diag(cbar)

    def body(k, m):
        return _conjugate_inplace(m, factors.kind[k], factors.i[k],
                                  factors.j[k], factors.a[k])

    return lax.fori_loop(0, factors.m, body, m0)


def t_objective(c_mat: jnp.ndarray, factors: TFactors,
                cbar: jnp.ndarray) -> jnp.ndarray:
    d = c_mat - t_reconstruct(factors, cbar.astype(c_mat.dtype))
    return jnp.sum(d * d)


# ---------------------------------------------------------------------------
# Theorem 3: greedy initialization
# ---------------------------------------------------------------------------
# State: B (current T..T diag(cbar) T^{-1}..T^{-1}), E = C - B,
# V = E B^T, H = E^T B, N = row norms^2 of B, M = col norms^2 of B.

def _shear_scores(b_mat, e_mat, v_mat, h_mat, nrow, mcol):
    """Quartic coefficients of the shear cost at every ordered pair (i, j).

    F(a) - ||E||^2 = c1 a + c2 a^2 + c3 a^3 + c4 a^4 with (derived):
      c1 = -2 (V_ij - H_ji)
      c2 = N_j + M_i - 2 B_ii B_jj + 2 B_ji E_ij
      c3 = 2 B_ji (B_ii - B_jj)
      c4 = B_ji^2
    """
    db = jnp.diagonal(b_mat)
    bt = b_mat.T
    c1 = -2.0 * (v_mat - h_mat.T)
    c2 = (nrow[None, :] + mcol[:, None] - 2.0 * db[:, None] * db[None, :]
          + 2.0 * bt * e_mat)
    c3 = 2.0 * bt * (db[:, None] - db[None, :])
    c4 = bt * bt
    a_star, val = minimize_quartic(c1, c2, c3, c4, clip=_A_CLIP)
    n = b_mat.shape[0]
    val = jnp.where(jnp.eye(n, dtype=bool), jnp.inf, val)
    return a_star, val


def _scale_phi(a, rho, eps_d, nv, mv, v0, h0):
    """phi_i(a) = F(a) - ||E||^2 for the scaling transform at index i."""
    alpha = a - 1.0
    beta = (1.0 - a) / a
    return (-2.0 * alpha * v0 - 2.0 * beta * h0
            - 2.0 * alpha * beta * rho * eps_d
            + alpha * alpha * nv + beta * beta * mv
            + (alpha * beta * rho) ** 2
            + 2.0 * alpha * beta * rho * rho
            + 2.0 * alpha * alpha * beta * rho * rho
            + 2.0 * alpha * beta * beta * rho * rho)


def _scale_scores(b_mat, e_mat, v_mat, h_mat, nrow, mcol):
    """Best scaling parameter and score per index i (vectorized over i)."""
    rho = jnp.diagonal(b_mat)
    eps_d = jnp.diagonal(e_mat)
    v0 = jnp.diagonal(v_mat)
    h0 = jnp.diagonal(h_mat)

    # P(a) = a^2 phi(a) is an exact quartic: fit through 5 samples.
    pts = QUARTIC_POINTS.astype(b_mat.dtype)
    vals = jnp.stack([pts[k] ** 2 * _scale_phi(pts[k], rho, eps_d, nrow,
                                               mcol, v0, h0)
                      for k in range(5)], axis=-1)          # (n, 5)
    p = fit_quartic(vals)                                   # (n, 5)
    # minimize phi = P/a^2:  Q(a) = a P' - 2P = -2 p0 - p1 a + p3 a^3 + 2 p4 a^4
    q0, q1, q3, q4 = -2.0 * p[..., 0], -p[..., 1], p[..., 2] * 0 + p[..., 3], 2.0 * p[..., 4]
    roots, real_ok = real_quartic_roots(q4, q3, jnp.zeros_like(q3), q1, q0)
    cand = jnp.where(real_ok, roots, 1.0).astype(b_mat.dtype)
    # also try cubic fallback roots (q4 ~ 0) and a plain grid refresh
    fb = real_cubic_roots(q3, jnp.zeros_like(q3), q1, q0)
    cand = jnp.concatenate([cand, fb, jnp.ones_like(cand[:, :1])], axis=-1)
    mag = jnp.clip(jnp.abs(cand), _A_MIN_SCALE, _A_CLIP)
    cand = jnp.where(cand < 0, -mag, mag)
    phis = _scale_phi(cand, rho[:, None], eps_d[:, None], nrow[:, None],
                      mcol[:, None], v0[:, None], h0[:, None])
    phis = jnp.where(jnp.isfinite(phis), phis, jnp.inf)
    kbest = jnp.argmin(phis, axis=-1)
    a_star = jnp.take_along_axis(cand, kbest[:, None], axis=-1)[:, 0]
    val = jnp.take_along_axis(phis, kbest[:, None], axis=-1)[:, 0]
    val = jnp.minimum(val, 0.0)  # a=1 is always available (identity)
    a_star = jnp.where(val < 0, a_star, jnp.ones_like(a_star))
    return a_star, val


def _rank2_vectors(b_mat, kind, i, j, a):
    """Delta = B' - B = u1 v1^T + u2 v2^T for the chosen transform."""
    n = b_mat.shape[0]
    ei = jax.nn.one_hot(i, n, dtype=b_mat.dtype)
    ej = jax.nn.one_hot(j, n, dtype=b_mat.dtype)

    def shear(_):
        u1 = ei
        v1 = a * b_mat[j] - (a * a * b_mat[j, i]) * ej
        u2 = -a * b_mat[:, i]
        v2 = ej
        return u1, v1, u2, v2

    def scale(_):
        alpha = a - 1.0
        beta = (1.0 - a) / a
        u1 = ei
        v1 = alpha * b_mat[i] + (alpha * beta * b_mat[i, i]) * ei
        u2 = beta * b_mat[:, i]
        v2 = ei
        return u1, v1, u2, v2

    return lax.cond(kind == SCALE, scale, shear, None)


def _apply_update(state, kind, i, j, a):
    """Apply the transform and refresh (B, E, V, H, N, M) in O(n^2)."""
    b_mat, e_mat, v_mat, h_mat, _, _ = state
    u1, v1, u2, v2 = _rank2_vectors(b_mat, kind, i, j, a)

    ev1 = e_mat @ v1
    ev2 = e_mat @ v2
    bv1 = b_mat @ v1
    bv2 = b_mat @ v2
    etu1 = e_mat.T @ u1
    etu2 = e_mat.T @ u2
    btu1 = b_mat.T @ u1
    btu2 = b_mat.T @ u2
    v11, v12, v22 = v1 @ v1, v1 @ v2, v2 @ v2
    u11, u12, u22 = u1 @ u1, u1 @ u2, u2 @ u2

    v_new = (v_mat
             + jnp.outer(ev1, u1) + jnp.outer(ev2, u2)
             - jnp.outer(u1, bv1) - jnp.outer(u2, bv2)
             - v11 * jnp.outer(u1, u1) - v22 * jnp.outer(u2, u2)
             - v12 * (jnp.outer(u1, u2) + jnp.outer(u2, u1)))
    h_new = (h_mat
             + jnp.outer(etu1, v1) + jnp.outer(etu2, v2)
             - jnp.outer(v1, btu1) - jnp.outer(v2, btu2)
             - u11 * jnp.outer(v1, v1) - u22 * jnp.outer(v2, v2)
             - u12 * (jnp.outer(v1, v2) + jnp.outer(v2, v1)))
    delta = jnp.outer(u1, v1) + jnp.outer(u2, v2)
    b_new = b_mat + delta
    e_new = e_mat - delta
    n_new = jnp.sum(b_new * b_new, axis=1)
    m_new = jnp.sum(b_new * b_new, axis=0)
    return b_new, e_new, v_new, h_new, n_new, m_new


_REFRESH_EVERY = 8


def t_init(c_mat: jnp.ndarray, cbar: jnp.ndarray, m: int, valid=None
           ) -> Tuple[TFactors, jnp.ndarray]:
    """Theorem-3 greedy initialization of m T-transforms.

    The score state (E, V, H, row/col norms) is maintained by O(n^2)
    rank-2 updates but REFRESHED from B every _REFRESH_EVERY steps: f32
    drift across hundreds of incremental updates corrupts the scores
    enough to stall the greedy (observed: objective saturates with m).
    ``valid`` ((n,) bool) restricts the greedy to real coordinates of a
    ragged matrix embedded in a wider bucket (DESIGN.md §10).
    Returns (factors in application order, final dense approximation B).
    """
    b0 = jnp.diag(cbar.astype(c_mat.dtype))
    return _t_greedy(c_mat, b0, m, valid)


def _t_greedy(c_mat: jnp.ndarray, b0: jnp.ndarray, m: int, valid=None
              ) -> Tuple[TFactors, jnp.ndarray]:
    """Greedy Theorem-3 loop from an arbitrary current approximation
    ``b0`` (= diag(cbar) for a fresh fit; = the fitted reconstruction for
    a warm-start extension, DESIGN.md §9).  New transforms CONJUGATE the
    running approximation (B <- T B T^{-1}), i.e. they are appended to the
    application order.  With ``valid``, shear pairs and scaling indices
    that touch a padding coordinate score +inf and are never selected."""
    n = c_mat.shape[0]
    dtype = c_mat.dtype
    e0 = c_mat - b0
    v0 = e0 @ b0.T
    h0 = e0.T @ b0
    n0 = jnp.sum(b0 * b0, axis=1)
    m0 = jnp.sum(b0 * b0, axis=0)
    f0 = tfactors_identity(m, dtype)

    def body(t, carry):
        state, fk, fi, fj, fa = carry
        b_mat, e_mat, v_mat, h_mat, nrow, mcol = state

        def refresh(bm):
            e = c_mat - bm
            return (bm, e, e @ bm.T, e.T @ bm,
                    jnp.sum(bm * bm, axis=1), jnp.sum(bm * bm, axis=0))

        state = lax.cond(t % _REFRESH_EVERY == 0,
                         lambda s: refresh(s[0]), lambda s: s, state)
        b_mat, e_mat, v_mat, h_mat, nrow, mcol = state
        a_sh, val_sh = _shear_scores(b_mat, e_mat, v_mat, h_mat, nrow, mcol)
        a_sc, val_sc = _scale_scores(b_mat, e_mat, v_mat, h_mat, nrow, mcol)
        if valid is not None:
            pair_ok = jnp.logical_and(valid[:, None], valid[None, :])
            val_sh = jnp.where(pair_ok, val_sh, jnp.inf)
            val_sc = jnp.where(valid, val_sc, jnp.inf)
        flat = jnp.argmin(val_sh)
        pi = (flat // n).astype(jnp.int32)
        pj = (flat % n).astype(jnp.int32)
        best_sh = val_sh[pi, pj]
        si = jnp.argmin(val_sc).astype(jnp.int32)
        best_sc = val_sc[si]
        use_scale = best_sc < best_sh
        kind = jnp.where(use_scale, SCALE, SHEAR).astype(jnp.int32)
        i = jnp.where(use_scale, si, pi)
        j = jnp.where(use_scale, si, pj)
        a = jnp.where(use_scale, a_sc[si], a_sh[pi, pj])
        state = _apply_update(state, kind, i, j, a)
        fk = fk.at[t].set(kind)
        fi = fi.at[t].set(i)
        fj = fj.at[t].set(j)
        fa = fa.at[t].set(a)
        return state, fk, fi, fj, fa

    init = ((b0, e0, v0, h0, n0, m0), f0.kind, f0.i, f0.j, f0.a)
    state, fk, fi, fj, fa = lax.fori_loop(0, m, body, init)
    return TFactors(fk, fi, fj, fa), state[0]


# ---------------------------------------------------------------------------
# Theorem 4 (polish): refit each transform value, indices fixed
# ---------------------------------------------------------------------------

def _left_mul(mat, kind, i, j, a):
    """mat <- T mat."""

    def sc(mm):
        return mm.at[i].multiply(a)

    def sh(mm):
        return mm.at[i].add(a * mm[j])

    return lax.cond(kind == SCALE, sc, sh, mat)


def _right_mul_inv(mat, kind, i, j, a):
    """mat <- mat T^{-1}."""

    def sc(mm):
        return mm.at[:, i].multiply(1.0 / a)

    def sh(mm):
        return mm.at[:, j].add(-a * mm[:, i])

    return lax.cond(kind == SCALE, sc, sh, mat)


def _shear_polish_coeffs(chat0, a_col_i, abci, w_r, w_j, kappa):
    """Quartic coefficients of ||Chat0 - (a U1 - a U2 - a^2 kappa U3)||^2.

    U1 = u_i w_r^T, U2 = u_bc w_j^T, U3 = u_i w_j^T with u_i = A[:, i],
    u_bc = A B[:, i], w_r = (B[j, :] A^{-1})^T, w_j = A^{-1}[j, :]^T.
    """
    u_i, u_bc = a_col_i, abci
    c1u = u_i @ (chat0 @ w_r)
    c2u = u_bc @ (chat0 @ w_j)
    c3u = u_i @ (chat0 @ w_j)
    uu11, uu12, uu22 = u_i @ u_i, u_i @ u_bc, u_bc @ u_bc
    ww_rr, ww_rj, ww_jj = w_r @ w_r, w_r @ w_j, w_j @ w_j
    n11 = uu11 * ww_rr
    n22 = uu22 * ww_jj
    n12 = uu12 * ww_rj
    n13 = uu11 * ww_rj
    n23 = uu12 * ww_jj
    n33 = uu11 * ww_jj
    d1 = -2.0 * (c1u - c2u)
    d2 = n11 + n22 - 2.0 * n12 + 2.0 * kappa * c3u
    d3 = -2.0 * kappa * (n13 - n23)
    d4 = kappa * kappa * n33
    return d1, d2, d3, d4


def t_polish(c_mat: jnp.ndarray, factors: TFactors, cbar: jnp.ndarray
             ) -> TFactors:
    """One Gauss-Seidel sweep refitting every transform's parameter."""
    m = factors.m
    n = c_mat.shape[0]
    dtype = c_mat.dtype
    cbar = cbar.astype(dtype)

    # A = T_{m-1} ... T_1 (all but factor 0), A_inv its inverse
    def build_a(t, am):
        return _left_mul(am, factors.kind[t], factors.i[t], factors.j[t],
                         factors.a[t])

    a_mat = lax.fori_loop(1, m, build_a, jnp.eye(n, dtype=dtype))

    def build_ainv(t, am):
        return _right_mul_inv(am, factors.kind[t], factors.i[t],
                              factors.j[t], factors.a[t])

    a_inv = lax.fori_loop(1, m, build_ainv, jnp.eye(n, dtype=dtype))

    b_mat = jnp.diag(cbar)
    chat = c_mat - t_reconstruct(factors, cbar)

    def rank2_conj(a_mat_, a_inv_, b_mat_, kind, i, j, a):
        """A Delta(a) A^{-1} as dense (O(n^2)) for the current factor."""
        u1, v1, u2, v2 = _rank2_vectors(b_mat_, kind, i, j, a)
        left1 = a_mat_ @ u1
        left2 = a_mat_ @ u2
        right1 = v1 @ a_inv_
        right2 = v2 @ a_inv_
        return jnp.outer(left1, right1) + jnp.outer(left2, right2)

    def body(k, carry):
        a_mat_, a_inv_, b_mat_, chat_, fa = carry
        kind = factors.kind[k]
        i, j = factors.i[k], factors.j[k]
        a_old = fa[k]
        # residual with T_k = identity
        chat0 = chat_ + rank2_conj(a_mat_, a_inv_, b_mat_, kind, i, j, a_old)

        def shear_branch(_):
            kappa = b_mat_[j, i]
            u_i = a_mat_[:, i]
            u_bc = a_mat_ @ b_mat_[:, i]
            w_r = b_mat_[j] @ a_inv_
            w_j = a_inv_[j]
            d1, d2, d3, d4 = _shear_polish_coeffs(
                chat0, u_i, u_bc, w_r, w_j, kappa)
            a_new, _ = minimize_quartic(
                d1, d2, d3, d4, extra_candidates=[a_old], clip=_A_CLIP)
            return a_new

        def scale_branch(_):
            # candidates on a fixed multiplicative grid around a_old plus
            # the incumbent — exact enough for a polish refit, always
            # monotone because a_old is included.
            grid = jnp.array([0.25, 0.5, 0.8, 0.9, 1.0, 1.1, 1.25, 2.0, 4.0],
                             dtype) * a_old
            cands = jnp.concatenate([grid, jnp.array([1.0, a_old], dtype)])

            def eval_one(a):
                diff = chat0 - rank2_conj(a_mat_, a_inv_, b_mat_, kind,
                                          i, j, a)
                return jnp.sum(diff * diff)

            vals = jax.vmap(eval_one)(cands)
            vals = jnp.where(jnp.abs(cands) < _A_MIN_SCALE, jnp.inf, vals)
            return cands[jnp.argmin(vals)]

        a_new = lax.cond(kind == SCALE, scale_branch, shear_branch, None)
        fa = fa.at[k].set(a_new)
        chat_ = chat0 - rank2_conj(a_mat_, a_inv_, b_mat_, kind, i, j, a_new)
        # advance: B absorbs T_k(a_new); A drops T_{k+1}
        b_mat_ = _conjugate_inplace(b_mat_, kind, i, j, a_new)
        kn = jnp.minimum(k + 1, m - 1)

        def advance(args):
            am, ai = args
            am = _right_mul_inv(am, factors.kind[kn], factors.i[kn],
                                factors.j[kn], fa_next)
            ai = _left_mul(ai, factors.kind[kn], factors.i[kn],
                           factors.j[kn], fa_next)
            return am, ai

        fa_next = fa[kn]
        a_mat_, a_inv_ = lax.cond(k + 1 < m, advance,
                                  lambda args: args, (a_mat_, a_inv_))
        return a_mat_, a_inv_, b_mat_, chat_, fa

    _, _, _, _, fa = lax.fori_loop(
        0, m, body, (a_mat, a_inv, b_mat, chat, factors.a))
    return TFactors(factors.kind, factors.i, factors.j, fa)


# ---------------------------------------------------------------------------
# Lemma 2 + Algorithm 1 driver
# ---------------------------------------------------------------------------

_LSTSQ_MAX_N = 256


def lemma2_spectrum(c_mat: jnp.ndarray, factors: TFactors) -> jnp.ndarray:
    """cbar* = argmin ||C - Tbar diag(c) Tbar^{-1}||_F^2 (Lemma 2).

    For n <= 256 the Khatri-Rao matrix (n^2 x n) is materialized and solved
    by QR least squares — the normal-equations route squares kappa(Tbar),
    which in f32 can REGRESS the objective (observed on random C).  Larger
    n falls back to ridge-regularized normal equations (O(n^3)); callers
    guard against regression either way."""
    n = c_mat.shape[0]
    t_dense = t_to_dense(factors, n, dtype=c_mat.dtype)
    t_inv = t_to_dense(factors, n, inverse=True, dtype=c_mat.dtype)
    if n <= _LSTSQ_MAX_N:
        # columns: vec(t_col_k outer tinv_row_k); sanitize — non-finite
        # entries (overflowed Tbar^{-1}) would poison LAPACK lstsq, and the
        # caller's regression guard rejects a useless solution anyway
        kr = jnp.einsum("ik,kj->ijk", t_dense, t_inv).reshape(n * n, n)
        kr = jnp.where(jnp.isfinite(kr), kr, 0.0)
        sol, _, _, _ = jnp.linalg.lstsq(kr, c_mat.reshape(n * n))
        return jnp.where(jnp.isfinite(sol), sol, jnp.diagonal(c_mat))
    gram = (t_inv @ t_inv.T) * (t_dense.T @ t_dense)
    rhs = jnp.diagonal(t_dense.T @ c_mat @ t_inv.T)
    ridge = 1e-7 * jnp.trace(gram) / n + 1e-20
    return jnp.linalg.solve(gram + ridge * jnp.eye(n, dtype=c_mat.dtype), rhs)


def _gen_refit_spectrum(c_mat, factors, cbar0, update_spectrum):
    """Lemma-2 refit with the regression guard: the f32 refit may be
    worse than the incumbent spectrum on ill-conditioned Tbar — keep
    whichever reconstructs better."""
    cbar_l2 = lemma2_spectrum(c_mat, factors)
    keep_l2 = (t_objective(c_mat, factors, cbar_l2)
               < t_objective(c_mat, factors, cbar0))
    return jnp.where(jnp.logical_and(update_spectrum, keep_l2),
                     cbar_l2, cbar0)


def _gen_iterate(c_mat, factors, cbar, n_iter, update_spectrum, eps):
    """Algorithm-1 refinement loop for the general case (shared by the
    from-scratch fit and the warm-start extension)."""
    obj0 = t_objective(c_mat, factors, cbar)

    def iter_body(carry):
        it, factors, cbar, obj_prev, obj, hist = carry
        f2 = t_polish(c_mat, factors, cbar)
        cb2 = jnp.where(update_spectrum, lemma2_spectrum(c_mat, f2), cbar)
        obj2 = t_objective(c_mat, f2, cb2)
        # spectrum refit via ridge solve can in rare ill-conditioned cases
        # regress; keep the better of the two spectra
        keep_old = obj2 > obj
        cb2 = jnp.where(keep_old, cbar, cb2)
        obj2 = jnp.where(keep_old, t_objective(c_mat, f2, cbar), obj2)
        hist = hist.at[it + 1].set(obj2)
        return it + 1, f2, cb2, obj, obj2, hist

    def cond(carry):
        it, _, _, obj_prev, obj, _ = carry
        return jnp.logical_and(it < n_iter,
                               jnp.abs(obj_prev - obj) >= eps)

    hist0 = jnp.full((n_iter + 1,), jnp.nan, c_mat.dtype).at[0].set(obj0)
    state = (0, factors, cbar, obj0 + 2 * eps + 1.0, obj0, hist0)
    it, factors, cbar, _, obj, hist = lax.while_loop(cond, iter_body, state)
    return factors, cbar, obj, hist, it


@full_f32_matmuls
def _approx_gen_core(c_mat, cbar0, m, n_iter, update_spectrum, eps,
                     size=None):
    """Traceable Algorithm-1 body for the general case (jit-free so the
    batched engine can run it over a batch in one jit; DESIGN.md §7).
    ``size`` (scalar, may be traced/vmapped) masks the Theorem-3 greedy to
    the leading ``size`` coordinates of a zero-padded ragged matrix; the
    polish and Lemma-2 refits then stay confined to the valid block by
    construction (padding rows/cols of C and B are zero; DESIGN.md §10).
    """
    factors, _ = t_init(c_mat, cbar0, m, _valid_coords(c_mat, size))
    cbar = _gen_refit_spectrum(c_mat, factors, cbar0, update_spectrum)
    return _gen_iterate(c_mat, factors, cbar, n_iter, update_spectrum, eps)


@full_f32_matmuls
def _extend_gen_core(c_mat, factors0, cbar0, m_extra, n_iter,
                     update_spectrum, eps, size=None):
    """Warm-start extension for the general case (DESIGN.md §9): continue
    the Theorem-3 greedy from the fitted reconstruction, so the
    ``m_extra`` new transforms refine the current residual.  New factors
    conjugate the running approximation and are therefore APPENDED in
    application order (extending the discovery order, which for the T
    family coincides with application order).  ``size`` masks the appended
    greedy like ``_approx_gen_core``."""
    b0 = t_reconstruct(factors0, cbar0.astype(c_mat.dtype))
    new, _ = _t_greedy(c_mat, b0, m_extra, _valid_coords(c_mat, size))
    factors = TFactors(*(jnp.concatenate([of, nf])
                         for of, nf in zip(factors0, new)))
    cbar = _gen_refit_spectrum(c_mat, factors, cbar0, update_spectrum)
    return _gen_iterate(c_mat, factors, cbar, n_iter, update_spectrum, eps)


_approx_gen_jit = functools.partial(jax.jit, static_argnames=(
    "m", "n_iter", "update_spectrum"))(_approx_gen_core)


def default_cbar(c_mat: jnp.ndarray, sizes=None) -> jnp.ndarray:
    """Default spectrum estimate diag(C) + deterministic tie-break; accepts
    a single (n, n) matrix or a leading-batched (..., n, n) stack.
    ``sizes`` marks ragged matrices embedded in the n-wide bucket (see
    ``gtransform.default_sbar``): statistics follow each matrix's true
    size and padding coordinates get exactly zero."""
    n = c_mat.shape[-1]
    cbar = jnp.diagonal(c_mat, axis1=-2, axis2=-1)
    if sizes is not None:
        return _masked_default_spectrum(cbar, sizes, c_mat.dtype)
    scale = jnp.maximum(jnp.std(cbar, axis=-1, keepdims=True), 1e-6)
    return cbar + 1e-6 * scale * jnp.arange(n, dtype=c_mat.dtype) / n


def approximate_general(
    c_mat: jnp.ndarray,
    m: int,
    n_iter: int = 10,
    cbar: Optional[jnp.ndarray] = None,
    update_spectrum: bool = True,
    eps: float = 1e-2,
):
    """Algorithm 1, general case. Returns (factors, cbar, info)."""
    if cbar is None:
        cbar = default_cbar(c_mat)
    factors, cbar, obj, hist, iters = _approx_gen_jit(
        c_mat, cbar.astype(c_mat.dtype), m, n_iter, update_spectrum,
        jnp.asarray(eps, c_mat.dtype))
    info = {"objective": obj, "history": hist, "iterations": iters}
    return factors, cbar, info
