"""Fast Graph Fourier Transforms — the paper's application (its §5;
DESIGN.md §1 "Algorithm 2").

Undirected graph -> symmetric Laplacian -> G-transform factorization
(orthonormal fast eigenspace).  Directed graph -> general Laplacian ->
T-transform factorization.  The returned FGFT bundles sequential factors,
staged (TPU) forms (DESIGN.md §2) and the estimated spectrum, and exposes
analysis / synthesis / spectral-filtering operations with O(alpha n log n)
cost.  For fitting/serving MANY graphs at once use the batched engine,
core/eigenbasis.py::ApproxEigenbasis (DESIGN.md §7).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

import jax.numpy as jnp
import numpy as np

from . import gtransform as gt
from . import ttransform as tt
from repro.kernels.plan import ApplyPlan, leg_orientation
from repro.tables import StagedG, StagedT
from .staging import pack_g_pair, pack_t_pair, select_cut
from .types import GFactors, TFactors


def laplacian(adj: np.ndarray, normalized: bool = False) -> np.ndarray:
    """Graph Laplacian L = D - A (out-degree D for directed graphs).

    ``adj``: (n, n) adjacency, any real numpy dtype.  Returns (n, n) f32.
    ``normalized=True`` gives D^{-1/2} L D^{-1/2} (degree-0 rows guarded).
    Symmetric L feeds Algorithm 1's G-transform path, directed L the
    T-transform path (paper §5; DESIGN.md §1)."""
    deg = np.asarray(adj).sum(axis=1)
    lap = np.diag(deg) - np.asarray(adj)
    if normalized:
        d = 1.0 / np.sqrt(np.maximum(deg, 1e-12))
        lap = lap * d[:, None] * d[None, :]
    return lap.astype(np.float32)


@dataclass
class FGFT:
    """A fast approximate graph Fourier transform for ONE graph.

    ``spectrum`` is (n,) f32 (estimated graph frequencies, Lemma 1/2);
    ``fwd``/``bwd`` are the staged (S, P) tables of the synthesis operator
    and its adjoint/inverse (DESIGN.md §2).  All signal arguments put the
    graph coordinate on the LAST axis: x is (..., n), f32 or bf16."""

    n: int
    directed: bool
    spectrum: jnp.ndarray                 # estimated graph frequencies
    g_factors: Optional[GFactors] = None  # undirected
    t_factors: Optional[TFactors] = None  # directed
    fwd: Optional[StagedG | StagedT] = None
    bwd: Optional[StagedG | StagedT] = None  # adjoint (G) or inverse (T)
    objective: float = float("nan")

    # -- ops (plan-backed: one cached program per shape; DESIGN.md §13) ----
    def _plan(self, mode: str, backend: str, num_stages: Optional[int],
              precision: str, keep: str = "head",
              fused: bool = True) -> ApplyPlan:
        return ApplyPlan(family="general" if self.directed else "sym",
                         mode=mode, n=self.n, backend=backend,
                         num_stages=num_stages, keep=keep,
                         precision=precision, fused=fused)

    def analysis(self, x: jnp.ndarray, backend: str = "xla",
                 num_stages: Optional[int] = None,
                 precision: str = "f32") -> jnp.ndarray:
        """Graph Fourier coefficients  x_hat = Ubar^T x  (or Tbar^{-1} x).

        x: (..., n) -> (..., n), same dtype.  Cost 6g (G) or m1+2m2 (T)
        flops per vector — paper Table 1 (vs 2n^2 dense).  ``num_stages``
        runs the anytime prefix transform: only the stages covering the
        leading components (pick a boundary via ``self.stage_cuts``;
        DESIGN.md §9).  ``precision="bf16"`` runs bf16 table storage
        with f32 accumulation (DESIGN.md §13)."""
        keep = leg_orientation("general" if self.directed else "sym")[0]
        plan = self._plan("apply", backend, num_stages, precision, keep)
        return plan.apply(self.bwd, x)

    def synthesis(self, xh: jnp.ndarray, backend: str = "xla",
                  num_stages: Optional[int] = None,
                  precision: str = "f32") -> jnp.ndarray:
        """Inverse transform  x = Ubar x_hat  (or Tbar x_hat): (..., n) ->
        (..., n).  Exact inverse of ``analysis`` for the G case
        (orthonormal); for T it inverts up to f32 conditioning of Tbar."""
        keep = leg_orientation("general" if self.directed else "sym")[1]
        plan = self._plan("apply", backend, num_stages, precision, keep)
        return plan.apply(self.fwd, xh)

    def filter(self, x: jnp.ndarray, h: Callable[[jnp.ndarray], jnp.ndarray],
               backend: str = "xla", num_stages: Optional[int] = None,
               precision: str = "f32", fused: bool = True) -> jnp.ndarray:
        """Spectral filter  y = Ubar diag(h(spectrum)) Ubar^T x  (or the
        Tbar form) — eq. (2)/(7) as an operator.  ``h`` maps (n,) graph
        frequencies to (n,) gains; x: (..., n).  ``backend="pallas"`` runs
        the fused one-round-trip kernel (DESIGN.md §4); ``num_stages``
        truncates both transform legs to the same component prefix;
        ``fused=False`` runs the three-pass staged baseline (parity /
        benchmarking; DESIGN.md §13)."""
        d = h(self.spectrum)
        plan = self._plan("operator", backend, num_stages, precision,
                          fused=fused)
        return plan.operator(self.fwd, self.bwd, d, x)

    @property
    def stage_cuts(self) -> np.ndarray:
        """(C, 2) array of exact (num_stages, num_components) prefix
        boundaries of the staged tables (core/staging.py)."""
        return self.fwd.cuts

    def select_tier(self, fraction: Optional[float] = None,
                    num_transforms: Optional[int] = None
                    ) -> tuple[int, int]:
        """Pick the exact stage cut nearest a component target — API
        parity with ``ApproxEigenbasis.select_tier``.  Returns
        ``(num_stages, num_components)``; the ``num_stages`` feeds
        straight into ``analysis``/``synthesis``/``filter``, which apply
        the family's head/tail cut orientation themselves (callers no
        longer hand-roll ``staging.select_cut`` plus the orientation
        rules)."""
        return select_cut(self.fwd, num_transforms=num_transforms,
                          fraction=fraction)

    def prefix_transforms(self, num_transforms: int):
        """The leading ``num_transforms`` fundamental components as a
        factor container (the paper's greedy/significance order: for the
        G family that is the application-order TAIL of ``g_factors``, for
        the T family the application-order HEAD of ``t_factors``)."""
        if self.directed:
            return TFactors(*(f[:num_transforms] for f in self.t_factors))
        g = self.g_factors.g
        return GFactors(*(f[g - num_transforms:] for f in self.g_factors))

    def flops_per_matvec(self, num_transforms: Optional[int] = None) -> int:
        """Paper Table-1 cost of one matvec with the reconstructed
        operator  Lbar = Ubar diag(sbar) Ubar^T  (or Tbar diag(cbar)
        Tbar^{-1}): each leg costs 6 per G-transform / 1 per scaling and
        2 per shear, both legs are applied, and the diagonal costs n —
        i.e. 12 g + n (G) or 2 (m1 + 2 m2) + n (T).  ``num_transforms``
        prices an anytime prefix instead of the full chain."""
        if self.directed:
            kinds = np.asarray(self.t_factors.kind)
            if num_transforms is not None:
                kinds = kinds[:num_transforms]
            return int(2 * ((kinds == 0).sum() + 2 * (kinds == 1).sum())
                       + self.n)
        g = (self.g_factors.g if num_transforms is None
             else num_transforms)
        return 12 * g + self.n


def build_fgft(lap: jnp.ndarray, num_transforms: int, directed: bool,
               n_iter: int = 8, eps: float = 1e-3,
               update_spectrum: bool = True) -> FGFT:
    """Factorize one (n, n) graph Laplacian into a fast approximate GFT.

    Runs Algorithm 1 (DESIGN.md §1) with ``num_transforms`` components and
    at most ``n_iter`` refinement sweeps (early stop when the objective
    change drops below ``eps``), then host-packs the staged forms
    (DESIGN.md §2).  Input is cast to f32.  For a batch of graphs use
    ``ApproxEigenbasis.fit`` (one jit for all; DESIGN.md §7)."""
    lap = jnp.asarray(lap, jnp.float32)
    n = lap.shape[0]
    if directed:
        factors, cbar, info = tt.approximate_general(
            lap, m=num_transforms, n_iter=n_iter, eps=eps,
            update_spectrum=update_spectrum)
        fwd, bwd = pack_t_pair(factors, n)
        return FGFT(n=n, directed=True, spectrum=cbar, t_factors=factors,
                    fwd=fwd, bwd=bwd, objective=float(info["objective"]))
    factors, sbar, info = gt.approximate_symmetric(
        lap, g=num_transforms, n_iter=n_iter, eps=eps,
        update_spectrum=update_spectrum)
    fwd, bwd = pack_g_pair(factors)
    return FGFT(n=n, directed=False, spectrum=sbar, g_factors=factors,
                fwd=fwd, bwd=bwd, objective=float(info["objective"]))


def _relative(obj: float, denom: float) -> float:
    """obj / denom guarded for the all-zero-Laplacian corner: an empty
    graph (e.g. ``erdos_renyi(n, p=0.0)``) has ||L||_F = 0, and the exact
    approximation of the zero operator has error 0, not NaN."""
    if denom > 0.0:
        return obj / denom
    return 0.0 if obj <= 1e-12 else float("inf")


def relative_error(lap: jnp.ndarray, f: FGFT) -> float:
    """||L - Lbar||_F^2 / ||L||_F^2 — the paper's accuracy metric (its
    Figs. 1-5).  ``lap``: the (n, n) Laplacian ``f`` was fitted to.
    Returns 0.0 (not NaN) for an exactly-represented all-zero Laplacian."""
    lap = jnp.asarray(lap, jnp.float32)
    denom = float(jnp.sum(lap * lap))
    if f.directed:
        obj = float(tt.t_objective(lap, f.t_factors, f.spectrum))
    else:
        obj = float(gt.g_objective(lap, f.g_factors, f.spectrum))
    return _relative(obj, denom)


def prefix_relative_error(lap: jnp.ndarray, f: FGFT,
                          num_transforms: int) -> float:
    """Relative error of the ANYTIME prefix operator with the leading
    ``num_transforms`` components (DESIGN.md §9), with the spectrum refit
    for the prefix (Lemma 1 closed form; Lemma 2 refit guarded against
    f32 regression).  Evaluates the accuracy-vs-FLOPs frontier the tiered
    server trades along (benchmarks/fig9_anytime.py)."""
    lap = jnp.asarray(lap, jnp.float32)
    denom = float(jnp.sum(lap * lap))
    pre = f.prefix_transforms(num_transforms)
    if f.directed:
        cbar = tt.lemma2_spectrum(lap, pre)
        obj = float(jnp.minimum(tt.t_objective(lap, pre, cbar),
                                tt.t_objective(lap, pre, f.spectrum)))
    else:
        sbar = gt.lemma1_spectrum(lap, pre)
        obj = float(gt.g_objective(lap, pre, sbar))
    return _relative(obj, denom)
