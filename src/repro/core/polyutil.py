"""Closed-form elementwise polynomial minimization utilities.

The T-transform scores (Theorems 3 and 4) are quartic polynomials in the
transform parameter ``a`` (shears) or quartics divided by ``a^2`` (scalings).
Their minimization reduces to root-finding on low-degree derivative
polynomials.  Everything here is branchless elementwise jnp so the score
*sweeps over all n^2 index pairs* vectorize.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

_TINY = 1e-30


def real_cubic_roots(a3, a2, a1, a0):
    """Real roots of a3 x^3 + a2 x^2 + a1 x + a0, elementwise.

    Returns an array stacked on the last axis with 3 candidates; degenerate
    (quadratic/linear) cases fall back gracefully and may duplicate roots.
    """
    a3, a2, a1, a0 = jnp.broadcast_arrays(a3, a2, a1, a0)
    dt = jnp.result_type(a3, jnp.float32)
    scale = jnp.maximum(jnp.maximum(jnp.abs(a3), jnp.abs(a2)),
                        jnp.maximum(jnp.abs(a1), jnp.abs(a0))) + _TINY
    is_cubic = jnp.abs(a3) > 1e-12 * scale
    is_quad = jnp.abs(a2) > 1e-12 * scale

    # --- cubic branch (normalized) ---
    a3s = jnp.where(is_cubic, a3, 1.0)
    A = a2 / a3s
    B = a1 / a3s
    C = a0 / a3s
    p = B - A * A / 3.0
    q = 2.0 * A ** 3 / 27.0 - A * B / 3.0 + C
    disc = (q / 2.0) ** 2 + (p / 3.0) ** 3
    # one real root
    sq = jnp.sqrt(jnp.maximum(disc, 0.0))
    u = jnp.cbrt(-q / 2.0 + sq)
    v = jnp.cbrt(-q / 2.0 - sq)
    r_single = u + v - A / 3.0
    # three real roots (disc <= 0 implies p <= 0):
    # t_k = 2 sqrt(-p/3) cos(arccos(3q/(2p) * sqrt(-3/p))/3 - 2 pi k/3)
    mneg = jnp.sqrt(jnp.maximum(-p / 3.0, 0.0))
    denom = jnp.where(jnp.abs(p * mneg) > _TINY, p * mneg, 1.0)
    cos_arg = jnp.clip(1.5 * q / denom, -1.0, 1.0)
    theta = jnp.arccos(cos_arg) / 3.0
    two_pi_3 = 2.0 * jnp.pi / 3.0
    r0 = 2.0 * mneg * jnp.cos(theta) - A / 3.0
    r1 = 2.0 * mneg * jnp.cos(theta - two_pi_3) - A / 3.0
    r2 = 2.0 * mneg * jnp.cos(theta - 2.0 * two_pi_3) - A / 3.0
    one_real = disc > 0
    c0 = jnp.where(one_real, r_single, r0)
    c1 = jnp.where(one_real, r_single, r1)
    c2 = jnp.where(one_real, r_single, r2)
    # disc ~ 0 (double-root boundary) is unstable in f32: add the exact
    # disc=0 candidates  t1 = 3q/p, t2 = t3 = -3q/(2p)  unconditionally
    # (downstream filters candidates by objective value, extras are free)
    p_safe = jnp.where(jnp.abs(p) > _TINY, p, 1.0)
    c3 = 3.0 * q / p_safe - A / 3.0
    c4 = -1.5 * q / p_safe - A / 3.0

    # --- quadratic fallback: a2 x^2 + a1 x + a0 ---
    a2s = jnp.where(is_quad, a2, 1.0)
    qd = a1 * a1 - 4.0 * a2 * a0
    sqq = jnp.sqrt(jnp.maximum(qd, 0.0))
    q0 = (-a1 + sqq) / (2.0 * a2s)
    q1 = (-a1 - sqq) / (2.0 * a2s)
    # --- linear fallback: a1 x + a0 ---
    a1s = jnp.where(jnp.abs(a1) > 1e-12 * scale, a1, 1.0)
    lin = -a0 / a1s

    f0 = jnp.where(is_quad, q0, lin)
    f1 = jnp.where(is_quad, q1, lin)
    c0 = jnp.where(is_cubic, c0, f0)
    c1 = jnp.where(is_cubic, c1, f1)
    c2 = jnp.where(is_cubic, c2, f0)
    c3 = jnp.where(is_cubic, c3, f0)
    c4 = jnp.where(is_cubic, c4, f1)
    out = jnp.stack([c0, c1, c2, c3, c4], axis=-1).astype(dt)
    return jnp.where(jnp.isfinite(out), out, 0.0)


def minimize_quartic(c1, c2, c3, c4, extra_candidates=None, clip=1e4):
    """Minimize q(a) = c1 a + c2 a^2 + c3 a^3 + c4 a^4 elementwise.

    q(0) = 0, so the returned value is always <= 0 (taking a=0 recovers the
    identity transform).  Returns (a_star, q_star).
    """
    roots = real_cubic_roots(4.0 * c4, 3.0 * c3, 2.0 * c2, c1)
    cands = [roots[..., k] for k in range(roots.shape[-1])]
    cands.append(jnp.zeros_like(roots[..., 0]))
    if extra_candidates is not None:
        cands.extend(extra_candidates)
    best_a = jnp.zeros_like(cands[0])
    best_v = jnp.zeros_like(cands[0])
    for a in cands:
        a = jnp.clip(a, -clip, clip)
        v = a * (c1 + a * (c2 + a * (c3 + a * c4)))
        v = jnp.where(jnp.isfinite(v), v, jnp.inf)
        take = v < best_v
        best_a = jnp.where(take, a, best_a)
        best_v = jnp.where(take, v, best_v)
    return best_a, best_v


# 5-point exact fit of a quartic: P(a) = sum_k p_k a^k through samples at
# fixed abscissae (all nonzero so rational a^-1, a^-2 terms stay finite).
# Host constants: importing this module must not run a device computation.
QUARTIC_POINTS = np.array([-2.0, -1.0, 0.5, 1.0, 2.0], np.float32)
_V = np.stack([QUARTIC_POINTS.astype(np.float64) ** k for k in range(5)],
              axis=-1)                                       # (5, 5)
QUARTIC_VANDER_INV = np.linalg.inv(_V).astype(np.float32)


def fit_quartic(values):
    """values: (..., 5) evaluations at QUARTIC_POINTS -> (..., 5) coeffs."""
    return jnp.einsum("ck,...k->...c", QUARTIC_VANDER_INV.astype(values.dtype),
                      values)


_BISECT_STEPS = 30
_NEWTON_STEPS = 2
#: a candidate that is not a bracketed root counts as a root when
#: |P(x)| <= _ROOT_RTOL * sum_k |a_k| |x|^k (near-double roots sit at a
#: stationary point, where P need not change sign)
_ROOT_RTOL = 1e-5


def _horner(coeffs, x):
    """sum_k coeffs[k] x^(K-k), coefficients highest degree first."""
    acc = jnp.zeros_like(x) + coeffs[0]
    for c in coeffs[1:]:
        acc = acc * x + c
    return acc


def _derivative(coeffs):
    deg = len(coeffs) - 1
    return tuple((deg - k) * c for k, c in enumerate(coeffs[:-1]))


def _monotone_roots(coeffs, ends):
    """The root of the polynomial on each piece [ends_k, ends_k+1] where it
    is monotone: bisection where the ends differ in sign, then Newton
    steps kept inside the final bisection bracket (a Newton step from a
    near-double root may jump across the piece).  Returns (x,
    bracketed), both (..., K-1)."""
    coeffs = tuple(c[..., None] for c in coeffs)
    deriv = _derivative(coeffs)
    lo, hi = ends[..., :-1], ends[..., 1:]
    f_lo, f_hi = _horner(coeffs, lo), _horner(coeffs, hi)
    lo_pos = f_lo > 0
    bracketed = jnp.logical_or(lo_pos != (f_hi > 0),
                               jnp.logical_or(f_lo == 0, f_hi == 0))
    a, b = lo, hi
    for _ in range(_BISECT_STEPS):
        mid = 0.5 * (a + b)
        right = (_horner(coeffs, mid) > 0) == lo_pos
        a = jnp.where(right, mid, a)
        b = jnp.where(right, b, mid)
    x = 0.5 * (a + b)
    for _ in range(_NEWTON_STEPS):
        f, d = _horner(coeffs, x), _horner(deriv, x)
        xn = x - f / jnp.where(d != 0, d, 1.0)
        keep = jnp.logical_and(jnp.logical_and(xn >= a, xn <= b),
                               jnp.abs(_horner(coeffs, xn)) < jnp.abs(f))
        x = jnp.where(keep, xn, x)
    x = jnp.where(f_lo == 0, lo, jnp.where(f_hi == 0, hi, x))
    return x, bracketed


def _polish(coeffs, x):
    """Newton steps that are kept only where they shrink |P(x)|."""
    coeffs = tuple(c[..., None] for c in coeffs)
    deriv = _derivative(coeffs)
    for _ in range(_NEWTON_STEPS + 1):
        f = _horner(coeffs, x)
        d = _horner(deriv, x)
        xn = x - f / jnp.where(d != 0, d, 1.0)
        better = jnp.abs(_horner(coeffs, xn)) < jnp.abs(f)
        x = jnp.where(jnp.logical_and(better, jnp.isfinite(xn)), xn, x)
    return x


def _is_root(coeffs, x):
    full = tuple(c[..., None] for c in coeffs)
    mag = _horner(tuple(jnp.abs(c) for c in full), jnp.abs(x))
    return jnp.abs(_horner(full, x)) <= _ROOT_RTOL * mag + _TINY


def real_quartic_roots(a4, a3, a2, a1, a0):
    """Real roots of a4 x^4 + a3 x^3 + a2 x^2 + a1 x + a0, elementwise.

    Branchless and free of eigensolvers, so it lowers on every backend.
    Roots are isolated by derivative recursion inside the Cauchy interval
    [-R, R]: the (stable closed-form) roots of P'' cut it into pieces on
    which P' is monotone, bisection there finds the stationary points of
    P, and those cut [-R, R] into pieces on which P is monotone — each
    piece whose ends differ in sign holds exactly one root, bisected and
    polished by bracketed Newton steps.  Stationary points where P
    (nearly) vanishes are kept as (near-)double roots, which do not
    change sign.  A negligible leading coefficient falls back to the
    Newton-polished roots of the cubic.

    Returns ``(roots, ok)``, both (..., 12): candidate abscissae and the
    mask of those that are real roots (the others hold 0).
    """
    a4, a3, a2, a1, a0 = jnp.broadcast_arrays(a4, a3, a2, a1, a0)
    dt = jnp.result_type(a4, jnp.float32)
    coeffs = tuple(jnp.asarray(c, dt) for c in (a4, a3, a2, a1, a0))
    a4, a3, a2, a1, a0 = coeffs
    scale = jnp.maximum(jnp.maximum(jnp.abs(a4), jnp.abs(a3)),
                        jnp.maximum(jnp.maximum(jnp.abs(a2), jnp.abs(a1)),
                                    jnp.abs(a0))) + _TINY
    is_quartic = jnp.abs(a4) > 1e-8 * scale

    lead = jnp.where(is_quartic, a4, 1.0)
    monic = (jnp.ones_like(a4), a3 / lead, a2 / lead, a1 / lead, a0 / lead)
    bound = (1.0 + jnp.maximum(
        jnp.maximum(jnp.abs(monic[1]), jnp.abs(monic[2])),
        jnp.maximum(jnp.abs(monic[3]), jnp.abs(monic[4]))))[..., None]
    # roots of P'' = 12 x^2 + 6 b3 x + 2 b2 (citardauq form, no cancellation)
    qa, qb, qc = 12.0, 6.0 * monic[1], 2.0 * monic[2]
    disc = jnp.maximum(qb * qb - 4.0 * qa * qc, 0.0)
    q = -0.5 * (qb + jnp.where(qb >= 0, 1.0, -1.0) * jnp.sqrt(disc))
    inflect = jnp.stack([q / qa, qc / jnp.where(q != 0, q, 1.0)], axis=-1)
    inflect = jnp.clip(inflect, -bound, bound)

    def pieces(inner):
        return jnp.sort(jnp.concatenate([-bound, inner, bound], axis=-1),
                        axis=-1)

    crit, _ = _monotone_roots(_derivative(monic), pieces(inflect))
    roots, bracketed = _monotone_roots(monic, pieces(crit))

    cubic = _polish((a3, a2, a1, a0), real_cubic_roots(a3, a2, a1, a0))
    quartic = is_quartic[..., None]
    cand = jnp.concatenate([roots, crit, cubic], axis=-1)
    ok = jnp.concatenate([
        jnp.logical_and(bracketed, quartic),
        jnp.logical_and(_is_root(coeffs, crit), quartic),
        jnp.logical_and(_is_root(coeffs, cubic), ~quartic)], axis=-1)
    ok = jnp.logical_and(ok, jnp.isfinite(cand))
    return jnp.where(ok, cand, 0.0), ok
