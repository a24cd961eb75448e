"""FastEig layers: the paper's structured operators as LM building blocks.

Two integration modes (DESIGN.md §3):

1. ``ButterflyLinear`` — a *trainable* fast orthonormal mixing layer with a
   fixed FFT-style conflict-free index pattern and learnable rotation angles
   + diagonal: y = Ubar(theta) diag(d) Ubar(theta)^T x, O(n log n) per token.
   This is the paper's "replace the Fourier matrix with a learned matrix with
   similar computational properties" idea turned into a trainable module.

2. ``compress_linear`` — post-hoc compression of a trained square projection
   W via the polar decomposition W = Q H: the orthonormal Q is factorized
   with the greedy Givens method (baselines.factorize_orthonormal) and the
   symmetric PSD H with the paper's Algorithm 1, giving
   W ~= Qbar (Ubar diag(s) Ubar^T) with O((gq + gh) ) apply cost.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.plan import ApplyPlan
from repro.tables import StagedG
from . import gtransform as gt
from .baselines import factorize_orthonormal
from .staging import pack_g, pack_g_adjoint


class ButterflyParams(NamedTuple):
    theta: jnp.ndarray  # (S, P) rotation angles (trainable)
    diag: jnp.ndarray   # (n,) diagonal (trainable)


class ButterflyPattern(NamedTuple):
    idx_i: jnp.ndarray  # (S, P) int32 — static FFT-style disjoint pairs
    idx_j: jnp.ndarray  # (S, P) int32
    n: int


def fft_pattern(n: int, n_stages: int | None = None) -> ButterflyPattern:
    """FFT-butterfly index pattern: stage k pairs (i, i + 2^k mod-block).

    ``n``: even layer width; ``n_stages`` defaults to ceil(log2 n).
    Returns (S, n//2) int32 index tables.  Each stage is a perfect
    matching, so it packs conflict-free by construction — no host greedy
    scheduling needed, unlike fitted chains (DESIGN.md §2-3).
    """
    assert n % 2 == 0, "butterfly mixing needs even width"
    depth = n_stages or max(int(np.ceil(np.log2(n))), 1)
    ii, jj = [], []
    for k in range(depth):
        stride = 2 ** (k % max(int(np.log2(n)) if (n & (n - 1)) == 0
                               else int(np.log2(n)) + 1, 1))
        stride = max(stride % n, 1)
        pairs_i, pairs_j, used = [], [], set()
        for a in range(n):
            b = (a + stride) % n
            if a in used or b in used or a == b:
                continue
            pairs_i.append(a)
            pairs_j.append(b)
            used.add(a)
            used.add(b)
        # pad to n//2 with no-op self pairs on an unused index
        free = [x for x in range(n) if x not in used]
        pad = free[0] if free else 0
        while len(pairs_i) < n // 2:
            pairs_i.append(pad)
            pairs_j.append(pad)
        ii.append(pairs_i)
        jj.append(pairs_j)
    return ButterflyPattern(jnp.asarray(np.array(ii, np.int32)),
                            jnp.asarray(np.array(jj, np.int32)), n)


def butterfly_init(key, pattern: ButterflyPattern,
                   dtype=jnp.float32) -> ButterflyParams:
    """Trainable params for a ButterflyLinear layer: small random angles
    theta (S, n//2) ~ N(0, 0.1^2) (near-identity init) and a unit diagonal
    (n,), both ``dtype``."""
    k1, _ = jax.random.split(key)
    theta = jax.random.normal(k1, pattern.idx_i.shape, dtype) * 0.1
    return ButterflyParams(theta=theta,
                           diag=jnp.ones((pattern.n,), dtype))


def _apply_stages(x, idx_i, idx_j, cos_t, sin_t):
    def stage(xc, arrs):
        ii, jj, cc, ss = arrs
        xi = jnp.take(xc, ii, axis=-1)
        xj = jnp.take(xc, jj, axis=-1)
        # pad pairs have ii == jj; make them exact no-ops regardless of theta
        noop = (ii == jj)
        cc = jnp.where(noop, 1.0, cc).astype(xc.dtype)
        ss = jnp.where(noop, 0.0, ss).astype(xc.dtype)
        yi = cc * xi + ss * xj
        yj = -ss * xi + cc * xj
        xc = xc.at[..., ii].set(yi)
        xc = xc.at[..., jj].set(yj)
        return xc, None

    out, _ = jax.lax.scan(stage, x, (idx_i, idx_j, cos_t, sin_t))
    return out


def butterfly_apply(params: ButterflyParams, pattern: ButterflyPattern,
                    x: jnp.ndarray, mix_only: bool = False) -> jnp.ndarray:
    """y = U(theta) diag(d) U(theta)^T x  (or just U(theta) x).

    The trainable form of the paper's eq. (2) operator with rotation-only
    blocks (DESIGN.md §3 mode 1).  ``x``: (..., n), any float dtype
    (params cast to ``x.dtype``); O(n log n) per vector.  ``mix_only=True``
    applies the orthonormal mixing U(theta) alone."""
    cos_t = jnp.cos(params.theta)
    sin_t = jnp.sin(params.theta)
    if mix_only:
        return _apply_stages(x, pattern.idx_i, pattern.idx_j, cos_t, sin_t)
    # adjoint: reversed stages with -sin
    y = _apply_stages(x, pattern.idx_i[::-1], pattern.idx_j[::-1],
                      cos_t[::-1], -sin_t[::-1])
    y = y * params.diag.astype(y.dtype)
    return _apply_stages(y, pattern.idx_i, pattern.idx_j, cos_t, sin_t)


class CompressedLinear(NamedTuple):
    """W ~= Qbar @ (Ubar diag(s) Ubar^T): all-butterfly square projection."""

    q_fwd: StagedG
    h_fwd: StagedG
    h_adj: StagedG
    diag: jnp.ndarray


def compress_linear(w: jnp.ndarray, g_orth: int, g_sym: int,
                    n_iter: int = 6) -> Tuple[CompressedLinear, dict]:
    """Compress a trained square projection via the paper's factorizations.

    ``w``: (n, n) float.  Polar-decomposes W = Q H (f64 SVD on host), then
    factors the orthonormal Q with ``g_orth`` greedy Givens transforms
    (baselines.factorize_orthonormal) and the symmetric PSD H with
    Algorithm 1 (``g_sym`` transforms, ``n_iter`` sweeps), giving
    W ~= Qbar (Ubar diag(s) Ubar^T) at O(g_orth + g_sym) apply cost
    (DESIGN.md §3 mode 2).  Returns the staged bundle + a report dict
    {"rel_err", "h_obj"} (f32 reconstruction quality)."""
    n = w.shape[0]
    w64 = np.asarray(w, np.float64)
    u, sv, vt = np.linalg.svd(w64)
    q = (u @ vt).astype(np.float32)              # orthonormal polar factor
    h = (vt.T * sv[None, :]) @ vt                # symmetric PSD factor
    qf = factorize_orthonormal(jnp.asarray(q), g_orth)
    hf, sbar, info = gt.approximate_symmetric(
        jnp.asarray(h.astype(np.float32)), g=g_sym, n_iter=n_iter)
    comp = CompressedLinear(q_fwd=pack_g(qf), h_fwd=pack_g(hf),
                            h_adj=pack_g_adjoint(hf), diag=sbar)
    # report reconstruction quality
    qd = gt.g_to_dense(qf, n)
    hd = gt.g_to_dense(hf, n)
    w_hat = qd @ (hd * sbar[None, :]) @ hd.T
    rel = float(jnp.sum((w - w_hat) ** 2) / jnp.sum(w * w))
    return comp, {"rel_err": rel, "h_obj": float(info["objective"])}


def compressed_linear_apply(comp: CompressedLinear, x: jnp.ndarray,
                            backend: str = "xla") -> jnp.ndarray:
    """y ~= W x through the compressed factors: the fused symmetric
    operator (H) followed by the staged Q apply.  ``x``: (..., n);
    ``backend`` as in kernels/plan.py (DESIGN.md §4)."""
    y = ApplyPlan.for_staged(comp.h_fwd, mode="operator",
                             backend=backend).operator(
        comp.h_fwd, comp.h_adj, comp.diag, x)
    return ApplyPlan.for_staged(comp.q_fwd, mode="apply",
                                backend=backend).apply(comp.q_fwd, y)
