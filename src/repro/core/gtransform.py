"""Symmetric-case factorization with extended Givens (G-) transforms.

Implements the paper's symmetric pipeline:
  * Theorem 1 — greedy initialization of each G-transform via the pair score
    (rearrangement-maximized Procrustes gain; eq. 15-16),
  * Theorem 2 — locally-optimal per-transform update; the default is the
    paper's experimental choice, "polishing" (indices fixed, values refit).
    The 2x2 sub-problem is solved exactly as a smooth trig maximization
    (grid + safeguarded Newton), which computes the same minimizer as the
    paper's Gander-Golub-von-Matt constrained LS without a 4x4 eigensolver
    (TPU-friendlier; see DESIGN.md).
  * Lemma 1 — closed-form spectrum refit ``sbar = diag(Ubar^T S Ubar)``.
  * Algorithm 1 — init + iterate(polish, spectrum) until the absolute change
    in the squared Frobenius error falls below ``eps``.

All loops are ``lax``-native so everything jits; matrices stay dense (the
targets of the factorization are n x n with n <= a few thousand — the *point*
of the paper is that the factor APPLICATION is O(g), see kernels/).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .types import GFactors, gfactors_identity

_NEG_INF = -jnp.inf
_GRID_SIZE = 64
_NEWTON_ITERS = 6


# ---------------------------------------------------------------------------
# Application of G-transform products
# ---------------------------------------------------------------------------

def _gapply_axis0(factors: GFactors, x: jnp.ndarray) -> jnp.ndarray:
    """Apply Ubar to x where coordinates live on axis 0. x: (n, ...)."""

    def body(carry, f):
        i, j, c, s, sg = f
        xi = carry[i]
        xj = carry[j]
        carry = carry.at[i].set(c * xi + s * xj)
        carry = carry.at[j].set(sg * (-s * xi + c * xj))
        return carry, None

    xs = (factors.i, factors.j, factors.c.astype(x.dtype),
          factors.s.astype(x.dtype), factors.sigma.astype(x.dtype))
    out, _ = lax.scan(body, x, xs)
    return out


def _adjoint_factors(factors: GFactors) -> GFactors:
    """Ubar^T as a G-factor sequence: reverse order; rotations flip s."""
    s_adj = jnp.where(factors.sigma > 0, -factors.s, factors.s)
    return GFactors(
        i=factors.i[::-1], j=factors.j[::-1],
        c=factors.c[::-1], s=s_adj[::-1], sigma=factors.sigma[::-1],
    )


def gapply(factors: GFactors, x: jnp.ndarray, adjoint: bool = False,
           axis: int = -1) -> jnp.ndarray:
    """Compute ``Ubar @ x`` (or ``Ubar.T @ x``) along ``axis`` of x."""
    if adjoint:
        factors = _adjoint_factors(factors)
    moved = jnp.moveaxis(x, axis, 0)
    out = _gapply_axis0(factors, moved)
    return jnp.moveaxis(out, 0, axis)


def g_to_dense(factors: GFactors, n: int, dtype=jnp.float32) -> jnp.ndarray:
    """Materialize Ubar (for tests / small-n evaluation)."""
    return gapply(factors, jnp.eye(n, dtype=dtype), axis=0)


# ---------------------------------------------------------------------------
# Dense 2x2 row/column mixing helpers (dynamic indices, jit-safe)
# ---------------------------------------------------------------------------

# The rows (columns) a 2x2 mix reads are materialized before it writes:
# fused into the writes instead, they keep the pre-update matrix alive,
# and XLA:TPU then copies the whole matrix in and out of every loop
# iteration that mixes it.


def _mix_rows(m, i, j, w00, w01, w10, w11):
    ri, rj = lax.optimization_barrier((m[i], m[j]))
    m = m.at[i].set(w00 * ri + w01 * rj)
    m = m.at[j].set(w10 * ri + w11 * rj)
    return m


def _mix_cols(m, i, j, w00, w01, w10, w11):
    ci, cj = lax.optimization_barrier((m[:, i], m[:, j]))
    m = m.at[:, i].set(w00 * ci + w01 * cj)
    m = m.at[:, j].set(w10 * ci + w11 * cj)
    return m


def _conjugate_gt(m, i, j, c, s, sigma):
    """m <- G^T m G for the canonical block G = [[c, s], [-sigma*s, sigma*c]].

    G^T = [[c, -sigma*s], [s, sigma*c]]; the same 2x2 acts on rows (left
    G^T @ m) and on columns (right m @ G, i.e. G^T in the column sense).
    """
    w00, w01, w10, w11 = c, -sigma * s, s, sigma * c
    m = _mix_rows(m, i, j, w00, w01, w10, w11)
    m = _mix_cols(m, i, j, w00, w01, w10, w11)
    return m


def _conjugate_g(m, i, j, c, s, sigma):
    """m <- G m G^T."""
    w00, w01, w10, w11 = c, s, -sigma * s, sigma * c
    m = _mix_rows(m, i, j, w00, w01, w10, w11)
    m = _mix_cols(m, i, j, w00, w01, w10, w11)
    return m


# ---------------------------------------------------------------------------
# Theorem 1: greedy initialization
# ---------------------------------------------------------------------------

def _pair_gains_rows(diag_s, s_row, sbar, idx, score: str = "paper",
                     valid=None):
    """Gain of pairing index ``idx`` with every other index (vectorized).

    score="paper": the exact Theorem-1 score in rearrangement-max form —
    gain_pq = max over eigvec assignment of  sbar_p d1 + sbar_q d2  (d1>=d2
    eigvalues of the 2x2 block) minus the current  sbar_p S_pp + sbar_q S_qq
    (valid for unsorted sbar).

    score="gamma": Remark 1's eigenvalue-free regime.  When the reference
    diagonal is refit (Lemma 1), the exact objective drop of annihilating
    the (p, q) off-diagonal is 2 S_pq^2 — the Jacobi selection, but applied
    with the extended (rotation+reflection) blocks.  The right choice when
    the sbar estimate is unreliable (e.g. a Laplacian's diagonal, full of
    repeated degrees, zeroes most eq.-15 gains).

    ``valid`` ((n,) bool, optional) marks real coordinates of a ragged
    matrix embedded in a wider bucket; pairs touching a padding coordinate
    score -inf so the greedy can never select them (DESIGN.md §10).
    """
    a_i = diag_s[idx]
    delta = a_i - diag_s
    r = jnp.sqrt(delta * delta + 4.0 * s_row * s_row)
    tr = a_i + diag_s
    d1 = 0.5 * (tr + r)
    d2 = 0.5 * (tr - r)
    if score == "gamma":
        gain = s_row * s_row
    else:
        si = sbar[idx]
        base = si * a_i + sbar * diag_s
        gain = jnp.maximum(si * d1 + sbar * d2, si * d2 + sbar * d1) - base
    if valid is not None:
        gain = jnp.where(jnp.logical_and(valid, valid[idx]), gain, _NEG_INF)
    return gain.at[idx].set(_NEG_INF)


def _gain_matrix(s_work, sbar, score: str = "paper", valid=None):
    n = s_work.shape[0]
    a = jnp.diag(s_work)
    ai, aj = a[:, None], a[None, :]
    delta = ai - aj
    r = jnp.sqrt(delta * delta + 4.0 * s_work * s_work)
    d1 = 0.5 * (ai + aj + r)
    d2 = 0.5 * (ai + aj - r)
    if score == "gamma":
        gain = s_work * s_work
    else:
        si, sj = sbar[:, None], sbar[None, :]
        base = si * ai + sj * aj
        gain = jnp.maximum(si * d1 + sj * d2, si * d2 + sj * d1) - base
    if valid is not None:
        gain = jnp.where(
            jnp.logical_and(valid[:, None], valid[None, :]), gain, _NEG_INF)
    return jnp.where(jnp.eye(n, dtype=bool), _NEG_INF, gain)


def _procrustes_2x2(s_ii, s_jj, s_ij, sbar_i, sbar_j):
    """Optimal G block for a pair: eigendecomposition of the 2x2 + pairing.

    Returns canonical (c, s, sigma).
    """
    theta = 0.5 * jnp.arctan2(2.0 * s_ij, s_ii - s_jj)
    ct = jnp.cos(theta)
    st = jnp.sin(theta)
    # V = [[ct, -st], [st, ct]] has V^T S_pair V = diag(d1, d2), d1 >= d2.
    # The stored factor is G = V (so the working-matrix update
    # G^T S G annihilates the off-diagonal): canonical (ct, -st, +1).
    # If the rearrangement pairs (d2 -> i, d1 -> j) instead, G = V @ swap =
    # [[-st, ct], [ct, st]]: a reflection with canonical (-st, ct, -1).
    swap = sbar_i < sbar_j
    c = jnp.where(swap, -st, ct)
    s = jnp.where(swap, ct, -st)
    sigma = jnp.where(swap, -1.0, 1.0).astype(ct.dtype)
    return c, s, sigma


def g_init(s_mat: jnp.ndarray, sbar: jnp.ndarray, g: int,
           score: str = "paper", valid=None
           ) -> Tuple[GFactors, jnp.ndarray]:
    """Theorem-1 greedy initialization of ``g`` G-transforms.

    ``score`` selects the pair score: "paper" (eq. 15, uses sbar) or
    "gamma" (Remark 1, eigenvalue-free).  ``valid`` ((n,) bool) restricts
    the greedy to real coordinates of a ragged matrix embedded in a wider
    bucket — no selected pair ever touches a padding coordinate, so the
    fitted chain acts as the identity on coordinates >= the true size.
    Returns factors (application order) and the final working matrix
    ``W = Ubar^T S Ubar`` (whose diagonal is the Lemma-1 spectrum).
    """
    dtype = s_mat.dtype
    sbar = sbar.astype(dtype)
    factors0 = gfactors_identity(g, dtype)
    gains0 = _gain_matrix(s_mat, sbar, score, valid)

    def body(t, carry):
        s_work, diag_s, gains, rmax, rarg, fi, fj, fc, fs, fsg = carry
        # first maximum in row-major order, as argmax over the whole matrix
        p = jnp.argmax(rmax).astype(jnp.int32)
        q = rarg[p]
        i = jnp.minimum(p, q)
        j = jnp.maximum(p, q)
        # gamma mode pairs d1 with the larger current diagonal slot
        # (continuity); paper mode pairs by the sbar rearrangement.
        ki = sbar[i] if score == "paper" else diag_s[i]
        kj = sbar[j] if score == "paper" else diag_s[j]
        c, s, sigma = _procrustes_2x2(
            diag_s[i], diag_s[j], s_work[i, j], ki, kj)
        s_work = _conjugate_gt(s_work, i, j, c, s, sigma)
        diag_s = diag_s.at[i].set(s_work[i, i]).at[j].set(s_work[j, j])
        # refresh the O(n) affected scores (rows/cols i and j)
        gi = _pair_gains_rows(diag_s, s_work[i], sbar, i, score, valid)
        gains = gains.at[i].set(gi).at[:, i].set(gi)
        gj = _pair_gains_rows(diag_s, s_work[j], sbar, j, score, valid)
        gains = gains.at[j].set(gj).at[:, j].set(gj)
        gains = gains.at[j, i].set(gj[i]).at[i, j].set(gj[i])
        rmax, rarg = _refresh_row_max(gains, rmax, rarg, i, j, gi, gj)
        # store in application order: discovery t corresponds to slot g-1-t
        slot = g - 1 - t
        fi = fi.at[slot].set(i)
        fj = fj.at[slot].set(j)
        fc = fc.at[slot].set(c)
        fs = fs.at[slot].set(s)
        fsg = fsg.at[slot].set(sigma)
        return s_work, diag_s, gains, rmax, rarg, fi, fj, fc, fs, fsg

    init = (s_mat, jnp.diagonal(s_mat), gains0, jnp.max(gains0, axis=1),
            jnp.argmax(gains0, axis=1).astype(jnp.int32), factors0.i,
            factors0.j, factors0.c, factors0.s, factors0.sigma)
    s_work, *_, fi, fj, fc, fs, fsg = lax.fori_loop(0, g, body, init)
    return GFactors(fi, fj, fc, fs, fsg), s_work


def _refresh_row_max(gains, rmax, rarg, i, j, gi, gj):
    """Row maxima and their first columns after rows/columns i < j of the
    (symmetric) gain matrix changed, without a pass over the matrix.

    Rows i and j are re-read.  Any other row r changed only in columns i
    and j (to gi[r], gj[r]); its old maximum still stands unless it sat in
    one of those columns and fell, and then the row is re-read too (a few
    rows per step).  Ties keep the first column, so the greedy picks the
    same pair as a row-major argmax over the whole matrix."""
    rows = jnp.arange(rmax.shape[0])
    stale = (((rarg == i) & (gi < rmax)) | ((rarg == j) & (gj < rmax))
             | (rows == i) | (rows == j))

    def better(v, a, best_v, best_a):
        take = (v > best_v) | ((v == best_v) & (a < best_a))
        return jnp.where(take, v, best_v), jnp.where(take, a, best_a)

    rmax, rarg = better(gi, i, rmax, rarg)
    rmax, rarg = better(gj, j, rmax, rarg)

    def reread(carry):
        stale, rmax, rarg = carry
        r = jnp.argmax(stale)
        row = gains[r]
        rmax = rmax.at[r].set(jnp.max(row))
        rarg = rarg.at[r].set(jnp.argmax(row).astype(rarg.dtype))
        return stale.at[r].set(False), rmax, rarg

    _, rmax, rarg = lax.while_loop(lambda c: jnp.any(c[0]), reread,
                                   (stale, rmax, rarg))
    return rmax, rarg


# ---------------------------------------------------------------------------
# Theorem 2 (polish variant): refit each transform's values, indices fixed
# ---------------------------------------------------------------------------

def _theta_candidates(dtype):
    return jnp.linspace(-jnp.pi, jnp.pi, _GRID_SIZE, endpoint=False,
                        dtype=dtype)


def _maximize_trig(k1, k2, k3, k4, theta_extra):
    """Maximize h(t) = k1 cos2t + k2 sin2t + 2 k3 cos t + 2 k4 sin t.

    Grid + safeguarded Newton; ``theta_extra`` (the incumbent) is included in
    the candidate set so the refit can never regress.
    """

    def h(t):
        return (k1 * jnp.cos(2 * t) + k2 * jnp.sin(2 * t)
                + 2 * k3 * jnp.cos(t) + 2 * k4 * jnp.sin(t))

    def dh(t):
        return (-2 * k1 * jnp.sin(2 * t) + 2 * k2 * jnp.cos(2 * t)
                - 2 * k3 * jnp.sin(t) + 2 * k4 * jnp.cos(t))

    def d2h(t):
        return (-4 * k1 * jnp.cos(2 * t) - 4 * k2 * jnp.sin(2 * t)
                - 2 * k3 * jnp.cos(t) - 2 * k4 * jnp.sin(t))

    grid = _theta_candidates(jnp.result_type(k1))
    tbest = grid[jnp.argmax(h(grid))]

    def newton(_, t):
        curv = d2h(t)
        step = jnp.where(curv < -1e-12, dh(t) / curv, 0.0)
        t_new = t - step
        return jnp.where(h(t_new) >= h(t), t_new, t)

    tbest = lax.fori_loop(0, _NEWTON_ITERS, newton, tbest)
    tbest = jnp.where(h(theta_extra) > h(tbest), theta_extra, tbest)
    return tbest, h(tbest)


def _polish_block(a_ii, a_jj, a_ij, b_ii, b_jj, b_ij, m11, m12, m21, m22,
                  c_old, s_old, sigma_old):
    """Exact 2x2 refit: maximize <A_PP, G B_PP G^T> + 2 <A_PR, G B_PR>.

    The cross term enters through M = A_PR @ B_PR^T. Both the rotation
    (f=1) and reflection (f=2) branches of eq. (3) are solved; returns the
    better canonical (c, s, sigma).
    """
    da, db = a_ii - a_jj, b_ii - b_jj
    theta_old = jnp.arctan2(s_old, c_old)

    # f = 1: rotation G = [[c, s], [-s, c]]
    k1r = 0.5 * da * db + 2.0 * a_ij * b_ij
    k2r = da * b_ij - a_ij * db
    k3r = m11 + m22
    k4r = m12 - m21
    t_rot, h_rot = _maximize_trig(
        k1r, k2r, k3r, k4r, jnp.where(sigma_old > 0, theta_old, 0.0))
    # guard: only let the incumbent protect its own branch
    h_rot_inc = jnp.where(sigma_old > 0, h_rot, h_rot)

    # f = 2: reflection G = [[c, s], [s, -c]]
    k1f = 0.5 * da * db - 2.0 * a_ij * b_ij
    k2f = a_ij * db + da * b_ij
    k3f = m11 - m22
    k4f = m12 + m21
    t_ref, h_ref = _maximize_trig(
        k1f, k2f, k3f, k4f, jnp.where(sigma_old < 0, theta_old, 0.0))

    use_rot = h_rot_inc >= h_ref
    theta = jnp.where(use_rot, t_rot, t_ref)
    c = jnp.cos(theta)
    s = jnp.sin(theta)
    sigma = jnp.where(use_rot, 1.0, -1.0).astype(c.dtype)
    return c, s, sigma


def g_polish(s_mat: jnp.ndarray, factors: GFactors, sbar: jnp.ndarray,
             w: Optional[jnp.ndarray] = None) -> GFactors:
    """One Gauss-Seidel polishing sweep over all g transforms (Theorem 2
    restricted to the stored indices — the paper's experimental setting).
    ``w``: W = Ubar^T S Ubar for ``factors``, if the caller has it."""
    g = factors.g
    dtype = s_mat.dtype
    sbar = sbar.astype(dtype)

    # W = Ubar^T S Ubar; A_0 = G_0 W G_0^T
    if w is None:
        w = g_conjugated(s_mat, factors)
    a0 = _conjugate_g(w, factors.i[0], factors.j[0],
                      factors.c[0], factors.s[0], factors.sigma[0])
    b0 = jnp.zeros_like(s_mat) + jnp.diag(sbar)

    def body(k, carry):
        a_mat, b_mat, fc, fs, fsg = carry
        i, j = factors.i[k], factors.j[k]
        ai_row, aj_row = a_mat[i], a_mat[j]
        bi_row, bj_row = b_mat[i], b_mat[j]
        a_ii, a_jj, a_ij = ai_row[i], aj_row[j], ai_row[j]
        b_ii, b_jj, b_ij = bi_row[i], bj_row[j], bi_row[j]
        # M = A_PR B_PR^T with the {i,j} columns excluded
        m11 = ai_row @ bi_row - a_ii * b_ii - a_ij * b_ij
        m12 = ai_row @ bj_row - a_ii * b_ij - a_ij * b_jj
        m21 = aj_row @ bi_row - a_ij * b_ii - a_jj * b_ij
        m22 = aj_row @ bj_row - a_ij * b_ij - a_jj * b_jj
        c, s, sg = _polish_block(a_ii, a_jj, a_ij, b_ii, b_jj, b_ij,
                                 m11, m12, m21, m22,
                                 fc[k], fs[k], fsg[k])
        fc = fc.at[k].set(c)
        fs = fs.at[k].set(s)
        fsg = fsg.at[k].set(sg)
        # advance: B_{k+1} = G_k B_k G_k^T (new values); A_{k+1} = G_{k+1} A_k G_{k+1}^T
        b_mat = _conjugate_g(b_mat, i, j, c, s, sg)
        # (after the last component this conjugates once more, unread)
        kn = jnp.minimum(k + 1, g - 1)
        a_mat = _conjugate_g(a_mat, factors.i[kn], factors.j[kn],
                             factors.c[kn], factors.s[kn], factors.sigma[kn])
        return a_mat, b_mat, fc, fs, fsg

    _, _, fc, fs, fsg = lax.fori_loop(
        0, g, body, (a0, b0, factors.c, factors.s, factors.sigma))
    return GFactors(factors.i, factors.j, fc, fs, fsg)


# ---------------------------------------------------------------------------
# Lemma 1 + objective + Algorithm 1 driver
# ---------------------------------------------------------------------------

def g_conjugated(s_mat: jnp.ndarray, factors: GFactors) -> jnp.ndarray:
    """W = Ubar^T S Ubar (dense)."""
    g = factors.g

    def conj_down(t, m):
        k = g - 1 - t
        return _conjugate_gt(m, factors.i[k], factors.j[k],
                             factors.c[k], factors.s[k], factors.sigma[k])

    return lax.fori_loop(0, g, conj_down, s_mat)


def lemma1_spectrum(s_mat: jnp.ndarray, factors: GFactors) -> jnp.ndarray:
    """sbar* = diag(Ubar^T S Ubar) — Lemma 1."""
    return jnp.diagonal(g_conjugated(s_mat, factors))


def g_objective(s_mat: jnp.ndarray, factors: GFactors, sbar: jnp.ndarray
                ) -> jnp.ndarray:
    """||S - Ubar diag(sbar) Ubar^T||_F^2 (== ||W - diag(sbar)||_F^2)."""
    return _off_spectrum(g_conjugated(s_mat, factors), sbar)


def _off_spectrum(w, sbar):
    """||W - diag(sbar)||_F^2."""
    d = w - jnp.diag(sbar.astype(w.dtype))
    return jnp.sum(d * d)


def _sym_iterate(s_mat, factors, sbar, n_iter, update_spectrum, eps):
    """Algorithm-1 refinement loop: polish + Lemma-1 sweeps until the
    objective change drops below ``eps`` (shared by the from-scratch fit
    and the warm-start extension).  Each sweep conjugates S through the
    chain once: that W gives the Lemma-1 spectrum, the objective and the
    next polish's starting point."""
    w0 = g_conjugated(s_mat, factors)
    obj0 = _off_spectrum(w0, sbar)

    def iter_body(carry):
        it, factors, w, sbar, obj_prev, obj, hist = carry
        f2 = g_polish(s_mat, factors, sbar, w)
        w2 = g_conjugated(s_mat, f2)
        sb2 = jnp.where(update_spectrum, jnp.diagonal(w2), sbar)
        obj2 = _off_spectrum(w2, sb2)
        hist = hist.at[it + 1].set(obj2)
        return it + 1, f2, w2, sb2, obj, obj2, hist

    def cond(carry):
        it, *_, obj_prev, obj, _ = carry
        return jnp.logical_and(it < n_iter,
                               jnp.abs(obj_prev - obj) >= eps)

    hist0 = jnp.full((n_iter + 1,), jnp.nan, s_mat.dtype).at[0].set(obj0)
    state = (0, factors, w0, sbar, obj0 + 2 * eps + 1.0, obj0, hist0)
    it, factors, _, sbar, _, obj, hist = lax.while_loop(cond, iter_body,
                                                        state)
    return factors, sbar, obj, hist, it


def full_f32_matmuls(fn):
    """Trace ``fn`` with f32 matmuls at full precision.  A TPU otherwise
    runs an f32 dot as a single bf16 pass (about three significant
    digits), which the greedy's incremental score updates cannot absorb:
    fits would stall and objectives drift away from the CPU's."""
    @functools.wraps(fn)
    def traced(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return traced


def _valid_coords(s_mat, size):
    """(n,) bool mask of real coordinates for a ragged matrix embedded in
    an n-wide bucket (None when the matrix fills the bucket)."""
    if size is None:
        return None
    return jnp.arange(s_mat.shape[-1]) < size


@full_f32_matmuls
def _approx_sym_core(s_mat, sbar0, g, n_iter, update_spectrum, eps, score,
                     size=None):
    """Traceable Algorithm-1 body (init + polish/spectrum sweeps).

    Kept jit-free so callers can compose it: ``approximate_symmetric`` jits
    it directly; the batched engine (core/eigenbasis.py) runs
    Algorithm 1 for a whole stack of matrices in one program with it
    (DESIGN.md §7).  ``size`` (scalar, may be traced/vmapped)
    masks the greedy to the leading ``size`` coordinates so a ragged
    matrix zero-padded into the bucket fits exactly as its own-size fit
    would: padding rows/cols are zero, so every polish/Lemma-1 sweep is
    automatically confined to the valid block once the init never selects
    a padding pair (DESIGN.md §10).
    """
    factors, w = g_init(s_mat, sbar0, g, score, _valid_coords(s_mat, size))
    sbar = jnp.where(update_spectrum, jnp.diagonal(w), sbar0)
    return _sym_iterate(s_mat, factors, sbar, n_iter, update_spectrum, eps)


@full_f32_matmuls
def _extend_sym_core(s_mat, factors0, sbar0, g_extra, n_iter,
                     update_spectrum, eps, score, size=None):
    """Warm-start extension: append ``g_extra`` Theorem-1 components
    fitted against the current residual (DESIGN.md §9).

    The greedy continues on W = Ubar^T S Ubar — exactly where a
    from-scratch fit's init would stand after the first g components — so
    the g new factors extend the DISCOVERY order.  In application order
    (core/types.py) the new factors are therefore PREPENDED: Ubar_ext =
    Ubar0 · Unew.  ``n_iter`` > 0 re-sweeps the whole extended chain
    (fitted prefix included) with the usual polish/Lemma-1 loop.
    ``size`` masks the appended greedy like ``_approx_sym_core``.
    """
    w = g_conjugated(s_mat, factors0)
    new, w2 = g_init(w, sbar0, g_extra, score, _valid_coords(s_mat, size))
    factors = GFactors(*(jnp.concatenate([nf, of])
                         for nf, of in zip(new, factors0)))
    sbar = jnp.where(update_spectrum, jnp.diagonal(w2), sbar0)
    return _sym_iterate(s_mat, factors, sbar, n_iter, update_spectrum, eps)


_approx_sym_jit = functools.partial(jax.jit, static_argnames=(
    "g", "n_iter", "update_spectrum", "score"))(_approx_sym_core)


def _masked_default_spectrum(diag: jnp.ndarray, sizes,
                             dtype) -> jnp.ndarray:
    """diag + deterministic tie-break for ragged matrices embedded in an
    n-wide bucket: statistics (std) and the perturbation ramp use the TRUE
    size of each matrix, so the estimate matches what the matrix's own-size
    fit would start from; padding coordinates are exactly zero."""
    n = diag.shape[-1]
    size = jnp.asarray(sizes, dtype)[..., None]
    valid = jnp.arange(n) < size
    d = jnp.where(valid, diag, 0.0)
    mean = jnp.sum(d, axis=-1, keepdims=True) / size
    var = jnp.sum(jnp.where(valid, (d - mean) ** 2, 0.0),
                  axis=-1, keepdims=True) / size
    scale = jnp.maximum(jnp.sqrt(var), 1e-6)
    pert = 1e-6 * scale * jnp.arange(n, dtype=dtype) / size
    return jnp.where(valid, d + pert, 0.0)


def default_sbar(s_mat: jnp.ndarray, sizes=None) -> jnp.ndarray:
    """Default spectrum estimate: diag(S) with a deterministic tie-break.

    The paper requires distinct estimated eigenvalues; the tiny monotone
    perturbation keeps pairs with equal diagonal entries selectable.  Works
    on a single (n, n) matrix or on any leading-batched (..., n, n) stack
    (used by the batched engine so batched and single fits see bit-identical
    starting spectra).  ``sizes`` (scalar or (...,) to match the batch)
    marks ragged matrices embedded in the n-wide bucket: statistics follow
    each matrix's true size and padding coordinates get exactly zero."""
    n = s_mat.shape[-1]
    sbar = jnp.diagonal(s_mat, axis1=-2, axis2=-1)
    if sizes is not None:
        return _masked_default_spectrum(sbar, sizes, s_mat.dtype)
    scale = jnp.maximum(jnp.std(sbar, axis=-1, keepdims=True), 1e-6)
    return sbar + 1e-6 * scale * jnp.arange(n, dtype=s_mat.dtype) / n


def approximate_symmetric(
    s_mat: jnp.ndarray,
    g: int,
    n_iter: int = 10,
    sbar: Optional[jnp.ndarray] = None,
    update_spectrum: bool = True,
    eps: float = 1e-2,
    score: Optional[str] = None,
):
    """Algorithm 1, symmetric case. Returns (factors, sbar, info).

    ``score``: "paper" (eq. 15) or "gamma" (Remark 1).  Default: "paper"
    when a spectrum estimate is supplied, "gamma" otherwise — with no
    reliable sbar the eq.-15 score degenerates (e.g. the repeated degrees
    on a Laplacian diagonal zero out most pair gains), which is exactly
    the regime Remark 1 addresses.
    """
    if score is None:
        score = "paper" if sbar is not None else "gamma"
    if sbar is None:
        sbar = default_sbar(s_mat)
    factors, sbar, obj, hist, iters = _approx_sym_jit(
        s_mat, sbar.astype(s_mat.dtype), g, n_iter, update_spectrum,
        jnp.asarray(eps, s_mat.dtype), score)
    info = {"objective": obj, "history": hist, "iterations": iters}
    return factors, sbar, info
