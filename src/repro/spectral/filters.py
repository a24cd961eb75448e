"""Spectral filter bank over fast approximate eigenbases (DESIGN.md §8).

A *response* is a scalar gain function of the graph frequencies:
``h(lam) -> gains`` with ``lam`` the estimated spectrum, (n,) or (B, n).
Responses here self-normalize against the per-graph spectral range
(``lam.max`` along the last axis), so one response serves a whole batch of
graphs with different Laplacian scales — the form the batched engine wants
(core/eigenbasis.py).

The factories cover the classic GSP toolbox: heat-kernel smoothing,
Butterworth low/high-pass, Gaussian band-pass, Tikhonov denoising
(``argmin_y ||y - x||^2 + tau y^T L y`` has the closed form
``y = (I + tau L)^{-1} x``, i.e. the gain ``1/(1 + tau lam)``), and
Hammond-style spectral-graph-wavelet scales (arXiv:0912.3848: a band-pass
kernel ``g(x) = x e^{1-x}`` evaluated at log-spaced scales plus a low-pass
scaling function).

``SpectralFilter``/``SpectralFilterBank`` bind responses to a fitted
``ApproxEigenbasis``; ``SpectralFilterBank.apply`` routes a whole bank
through one fused dispatch (kernels/spectral.py via an ApplyPlan) so the
analysis transform is paid once for all F filters.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.kernels.plan import ApplyPlan

Response = Callable[[jnp.ndarray], jnp.ndarray]


def _lmax(lam: jnp.ndarray) -> jnp.ndarray:
    """Per-graph spectral range, guarded against degenerate spectra."""
    return jnp.maximum(jnp.max(jnp.abs(lam), axis=-1, keepdims=True), 1e-12)


def heat(scale: float = 5.0) -> Response:
    """Heat-kernel smoothing  exp(-scale · lam / lam_max)  (diffusion for
    ``scale`` units of normalized time; larger = smoother)."""
    return lambda lam: jnp.exp(-scale * lam / _lmax(lam))


def tikhonov(tau: float = 1.0) -> Response:
    """Tikhonov denoiser  1 / (1 + tau · lam / lam_max)  — the closed-form
    minimizer of ||y - x||^2 + tau~ y^T L y with tau~ = tau/lam_max."""
    return lambda lam: 1.0 / (1.0 + tau * lam / _lmax(lam))


def lowpass(frac: float = 0.25, order: int = 4) -> Response:
    """Butterworth low-pass with cutoff at ``frac`` of the spectral range."""
    return lambda lam: 1.0 / (1.0 + (lam / (frac * _lmax(lam)))
                              ** (2 * order))


def highpass(frac: float = 0.25, order: int = 4) -> Response:
    """Complement of ``lowpass``: passes frequencies above the cutoff."""
    lp = lowpass(frac, order)
    return lambda lam: 1.0 - lp(lam)


def bandpass(center_frac: float = 0.5, width_frac: float = 0.15
             ) -> Response:
    """Gaussian band-pass centered at ``center_frac`` of the range."""

    def resp(lam):
        mx = _lmax(lam)
        z = (lam - center_frac * mx) / (width_frac * mx)
        return jnp.exp(-z * z)

    return resp


def hammond_kernel(x: jnp.ndarray) -> jnp.ndarray:
    """SGWT band-pass kernel  g(x) = x · e^{1-x}  (peak g(1) = 1)."""
    return x * jnp.exp(1.0 - x)


def wavelet_scales(num_scales: int = 4, scale_ratio: float = 20.0
                   ) -> np.ndarray:
    """Log-spaced SGWT scales t_j (coarse -> fine) in normalized frequency
    units: t_j · lam/lam_max sweeps the kernel's pass band across
    [lam_max/scale_ratio, lam_max] (Hammond et al. §8 design rule)."""
    return np.logspace(np.log10(scale_ratio), 0.0, num_scales)


def hammond_bank(num_scales: int = 4, scale_ratio: float = 20.0
                 ) -> "Dict[str, Response]":
    """Scaling function + ``num_scales`` wavelet responses.

    The scaling function covers the lam -> 0 end (where every wavelet
    vanishes, g(0) = 0); together the bank tiles the whole spectrum."""
    scales = wavelet_scales(num_scales, scale_ratio)
    t_coarse = float(scales[0])

    def scaling(lam):
        return jnp.exp(-(t_coarse * lam / _lmax(lam)) ** 4)

    bank: Dict[str, Response] = {"scaling": scaling}
    for j, t in enumerate(scales):
        t = float(t)
        bank[f"wavelet{j}"] = (
            lambda lam, t=t: hammond_kernel(t * lam / _lmax(lam)))
    return bank


def response_lipschitz(response: Response, lmax: float = 1.0,
                       num: int = 512) -> float:
    """Dimensionless Lipschitz constant of a response on [0, lmax]:
    ``max |dh/dlam| · lmax``, estimated on a dense grid.

    Converts a basis approximation error into the filtering error it
    implies — ``||h(Sbar) - h(S)|| <~ Lip(h) ||Sbar - S||`` — which is the
    per-filter accuracy bound asserted by benchmarks/fig8_spectral.py and
    tests/test_spectral.py (narrow responses amplify spectral error)."""
    lam = jnp.linspace(0.0, lmax, num)
    h = response(lam)
    d = jnp.abs(jnp.diff(h) / jnp.diff(lam))
    return float(jnp.max(d) * lmax)


RESPONSES: Dict[str, Callable[..., Response]] = {
    "heat": heat,
    "tikhonov": tikhonov,
    "lowpass": lowpass,
    "highpass": highpass,
    "bandpass": bandpass,
}


def named_responses(spec: str) -> "Dict[str, Response]":
    """Parse a serve-style bank spec: comma-separated names with an
    optional ``:param`` (e.g. ``"heat:3.0,lowpass,wavelets:4"``).

    ``wavelets[:J]`` expands to the Hammond scaling function + J wavelet
    scales; every other name maps through ``RESPONSES`` with the optional
    float as its first parameter."""
    bank: Dict[str, Response] = {}

    def add(key: str, resp: Response):
        if key in bank:
            raise ValueError(f"duplicate filter {key!r} in bank spec "
                             f"{spec!r} — each response would silently "
                             "overwrite the previous one")
        bank[key] = resp

    for item in filter(None, (s.strip() for s in spec.split(","))):
        name, _, param = item.partition(":")
        if name == "wavelets":
            for key, resp in hammond_bank(int(param) if param else 4
                                          ).items():
                add(key, resp)
            continue
        if name not in RESPONSES:
            raise ValueError(f"unknown filter {name!r}; known: "
                             f"{sorted(RESPONSES)} + 'wavelets'")
        add(item, (RESPONSES[name](float(param)) if param
                   else RESPONSES[name]()))
    return bank


def _mask_padded_gains(gains: jnp.ndarray, basis) -> jnp.ndarray:
    """Zero the gains at a ragged basis's padding coordinates.

    A masked (size-bucketed) fit carries zeros in the padded spectrum
    slots, but a response may map 0 to a nonzero gain (heat/tikhonov:
    h(0) = 1).  ``ApproxEigenbasis.project`` masks its own gains at
    depth; this helper covers the FUSED bank path (``SpectralFilterBank
    .apply`` dispatches precomputed (B, F, n) gains straight into the
    bank kernels, bypassing ``project``) and the public ``gains()``
    contract (DESIGN.md §10)."""
    sizes = getattr(basis, "sizes", None)
    if sizes is None:
        return gains
    n = gains.shape[-1]
    # batched: (B,) sizes -> (B, n) mask; unbatched: scalar size -> (n,)
    # mask (a reshape(-1, 1) here would silently grow (n,) gains to
    # (1, n) and break the gains() shape contract)
    valid = np.arange(n) < np.asarray(sizes)[..., None]
    return jnp.where(jnp.asarray(valid), gains, 0.0)


@dataclass(frozen=True)
class SpectralFilter:
    """One response bound to a fitted basis: y = Ubar diag(h(s)) Ubar^T x.

    ``basis`` may be single ((n, n) fit) or batched ((B, n, n) fit); the
    signal layout follows ``ApproxEigenbasis.project``.  For a ragged
    (size-bucketed) basis the gains are zeroed at each graph's padding
    coordinates, so padded signal columns filter to zero."""

    basis: object               # ApproxEigenbasis
    response: Response
    name: str = "filter"

    def gains(self) -> jnp.ndarray:
        """Diagonal gains h(spectrum): (n,) or (B, n)."""
        return _mask_padded_gains(self.response(self.basis.spectrum),
                                  self.basis)

    def apply(self, x: jnp.ndarray, backend: str = "xla") -> jnp.ndarray:
        """Filter signals x (..., n) / (B, ..., n) -> same shape
        (``project`` itself zeroes the gains at a ragged basis's padding
        coordinates; the explicit mask here is only for the fused bank
        path, which bypasses ``project``)."""
        return self.basis.project(x, h=self.response, backend=backend)


class SpectralFilterBank:
    """F responses served through one fused dispatch per signal block.

    ``responses``: dict name -> response (order preserved) or a sequence
    of (name, response) pairs.  ``apply`` returns the filter axis FIRST
    after any matrix batch: (F, ..., n) unbatched, (B, F, ..., n) batched.
    """

    def __init__(self, basis, responses):
        if isinstance(responses, dict):
            items: Sequence[Tuple[str, Response]] = list(responses.items())
        else:
            items = list(responses)
        if not items:
            raise ValueError("empty filter bank")
        self.basis = basis
        self.names = [name for name, _ in items]
        self.filters = [SpectralFilter(basis, resp, name)
                        for name, resp in items]

    def __len__(self) -> int:
        return len(self.filters)

    def gains(self) -> jnp.ndarray:
        """Stacked diagonal gains: (F, n) or (B, F, n) when batched."""
        axis = 1 if self.basis.batched else 0
        return jnp.stack([f.gains() for f in self.filters], axis=axis)

    def apply(self, x: jnp.ndarray, backend: str = "xla",
              fused: bool = True) -> jnp.ndarray:
        """Filter x through every response.

        ``fused=True`` dispatches the whole bank at once (one analysis
        pass shared by all F filters; ``backend="pallas"`` additionally
        runs the one-launch kernel).  ``fused=False`` is the per-filter
        composition — kept as the semantics baseline and the thing
        benchmarks/fig8_spectral.py races against."""
        basis = self.basis
        if not fused:
            axis = 1 if basis.batched else 0
            return jnp.stack([f.apply(x, backend=backend)
                              for f in self.filters], axis=axis)
        plan = ApplyPlan.for_staged(basis.fwd, mode="bank",
                                    backend=backend)
        return plan.bank(basis.fwd, basis.bwd, self.gains(), x)
