"""The plain reference: fitted bases applied as dense float64 operators on
the host.

A fit's answer is its factor chain and spectrum.  The reference checks
that answer against the graph itself (``sym_objective``: the off-diagonal
energy left in Ubar^T L Ubar, the quantity the fit minimizes) and checks
every served answer against that chain applied as a dense matrix:

    y = Ubar_k diag(h(lam_k)) Ubar_k^T x          (undirected, G chain)
    y = Tbar_k diag(h(c))     Tbar_k^{-1} x       (directed, T chain)

where k is the tier's component count.  It uses numpy and scipy only:
the chains are multiplied out factor by factor from their (i, j, c, s,
sigma) / (kind, i, j, a) entries, the undirected tier spectra are
recomputed here by Lemma 1 (lam_k = diag(Ubar_k^T L Ubar_k)), and the
bank responses are written out below.  The arithmetic follows the dense
checks of ``chip_smoke.py`` (``dense_legs``, ``check_responses``).

Factor conventions (the paper's eq. 3-5 and 8-10): factors are listed in
application order.  A G factor maps (x_i, x_j) to (c x_i + s x_j,
sigma (-s x_i + c x_j)); the greedy found the LAST factor first, so the
k most significant components are the application suffix.  A T factor
is a shear x_i += a x_j (kind 1) or a scaling x_i *= a (kind 0), found
in application order, so the k most significant are the prefix.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp

SCALE, SHEAR = 0, 1


def tier_components(fraction: float, g: int) -> int:
    """Components served at a tier: the nearest cut of the quarters
    ladder {round(g q / 4)} to fraction * g (ties to the larger)."""
    ladder = sorted({round(g * q / 4) for q in range(5)} - {0})
    target = fraction * g
    return min(ladder, key=lambda k: (abs(k - target), -k))


def sym_legs(factors, w: int, ks) -> dict:
    """{k: Ubar_k^T} as (w, w) float64 for each component count k, from
    one G chain (arrays i, j, c, s, sigma of length g)."""
    i, j, c, s, sg = (np.asarray(f) for f in factors)
    c, s, sg = (np.asarray(v, np.float64) for v in (c, s, sg))
    g = len(i)
    want = set(int(k) for k in ks)
    a = np.eye(w)                       # rows of Ubar_k^T, built as
    out = {}                            # (Ubar_k G_t)^T = G_t^T Ubar_k^T
    for step, t in enumerate(range(g - 1, -1, -1), start=1):
        p, q = i[t], j[t]
        rp, rq = a[p].copy(), a[q].copy()
        a[p] = c[t] * rp - sg[t] * s[t] * rq
        a[q] = s[t] * rp + sg[t] * c[t] * rq
        if step in want:
            out[step] = a.copy()
    if 0 in want:
        out[0] = np.eye(w)
    return out


def gen_legs(factors, w: int, ks) -> dict:
    """{k: (Tbar_k, Tbar_k^{-1})} as (w, w) float64, from one T chain
    (arrays kind, i, j, a)."""
    kind, i, j, a = (np.asarray(f) for f in factors)
    a = np.asarray(a, np.float64)
    want = set(int(k) for k in ks)
    fwd = np.eye(w)                     # Tbar_k, grown as T_t Tbar_k
    inv_t = np.eye(w)                   # (Tbar_k^{-1})^T, grown as
    out = {}                            # (Tbar_k^{-1} T_t^{-1})^T
    for step, t in enumerate(range(len(kind)), start=1):
        p, q = i[t], j[t]
        if kind[t] == SHEAR:
            fwd[p] += a[t] * fwd[q]
            inv_t[q] -= a[t] * inv_t[p]
        else:
            fwd[p] *= a[t]
            inv_t[p] /= a[t]
        if step in want:
            out[step] = (fwd.copy(), inv_t.T.copy())
    if 0 in want:
        out[0] = (np.eye(w), np.eye(w))
    return out


def lemma1_spectrum(ana: np.ndarray, lap: np.ndarray) -> np.ndarray:
    """diag(Ubar^T L Ubar) for ana = Ubar^T and L embedded in w."""
    w = ana.shape[0]
    lp = sp.csr_matrix(_embed(lap, w).astype(np.float64))
    return np.einsum("ij,ij->i", (lp @ ana.T).T, ana)


def sym_objective(ana: np.ndarray, lap: np.ndarray) -> float:
    """||L - Ubar diag(lam) Ubar^T||_F^2 / ||L||_F^2 at the Lemma-1
    spectrum: the off-diagonal energy of Ubar^T L Ubar over ||L||_F^2."""
    w = ana.shape[0]
    lp = sp.csr_matrix(_embed(lap, w).astype(np.float64))
    conj = ana @ (lp @ ana.T)
    off = float((conj * conj).sum() - (np.diag(conj) ** 2).sum())
    return off / max(float((np.asarray(lap, np.float64) ** 2).sum()), 1e-30)


def gen_objective(synth: np.ndarray, ana: np.ndarray, spectrum,
                  lap: np.ndarray) -> float:
    """||L - Tbar diag(c) Tbar^{-1}||_F^2 / ||L||_F^2."""
    w = synth.shape[0]
    diff = _embed(np.asarray(lap, np.float64), w) - (synth * spectrum) @ ana
    return float((diff * diff).sum()) / max(
        float((np.asarray(lap, np.float64) ** 2).sum()), 1e-30)


def _embed(mat: np.ndarray, w: int) -> np.ndarray:
    n = mat.shape[0]
    if n == w:
        return np.asarray(mat)
    out = np.zeros((w, w), mat.dtype)
    out[:n, :n] = mat
    return out


# -- responses --------------------------------------------------------------


def tier_response(lam: np.ndarray) -> np.ndarray:
    """The benchmark's tier response h(lam) = 1 / (1 + |lam|)."""
    return 1.0 / (1.0 + np.abs(lam))


def _lmax(lam):
    return max(float(np.max(np.abs(lam))), 1e-12)


BANK_RESPONSES = {
    # the filter bank's default responses, normalized by max |lam|
    "heat": lambda lam, m: np.exp(-5.0 * lam / m),
    "tikhonov": lambda lam, m: 1.0 / (1.0 + lam / m),
    "lowpass": lambda lam, m: 1.0 / (1.0 + (lam / (0.25 * m)) ** 8),
    "highpass": lambda lam, m: 1.0 - 1.0 / (1.0 + (lam / (0.25 * m)) ** 8),
    "bandpass": lambda lam, m: np.exp(-((lam - 0.5 * m) / (0.15 * m)) ** 2),
}


def bank_gains(names, lam: np.ndarray) -> np.ndarray:
    """(F, w) gains of the named responses over one graph's spectrum."""
    m = _lmax(lam)
    return np.stack([BANK_RESPONSES[name](lam, m) for name in names])


# -- served answers ---------------------------------------------------------


def apply_operator(synth: np.ndarray, ana: np.ndarray, gains: np.ndarray,
                   x: np.ndarray, n: int) -> np.ndarray:
    """Rows x (r, n) -> (F, r, n): ((x ana^T) * gains) synth^T, with the
    gains zeroed at the pad coordinates n..w-1."""
    w = synth.shape[0]
    xp = np.zeros((x.shape[0], w))
    xp[:, :n] = x
    gains = np.array(np.atleast_2d(gains), np.float64)
    gains[:, n:] = 0.0
    coeff = xp @ ana.T
    return ((coeff[None] * gains[:, None, :]) @ synth.T)[..., :n]


def relative_gap(y: np.ndarray, ref: np.ndarray) -> float:
    """||y - ref||_F / ||ref||_F (inf when y is missing or misshapen)."""
    y = np.asarray(y, np.float64)
    if y.shape != ref.shape or not np.all(np.isfinite(y)):
        return float("inf")
    return float(np.linalg.norm(y - ref) / max(np.linalg.norm(ref), 1e-30))
