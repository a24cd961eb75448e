"""ApproxEigenbasis: one facade over both factorization families, batched.

The paper factors an eigenspace into g fundamental components — extended
orthogonal Givens transforms for symmetric matrices (Algorithm 1 with
Theorems 1-2 + Lemma 1) or scaling/shear transforms for general matrices
(Theorems 3-4 + Lemma 2).  The seed exposed those as two parallel APIs
(core/gtransform.py, core/ttransform.py) that factor ONE matrix at a time.
This module is the single entry point and the batched engine (DESIGN.md §7):

  * ``fit`` runs Algorithm 1 for a whole stack of B matrices inside one
    jitted program — the solver cores are pure ``lax`` control flow, so
    one program runs each device's matrices in turn (``_over_batch``),
    and a device mesh shards the matrix batch across the data axes
    (runtime/sharding.py + launch/mesh.py).
  * ``apply`` / ``project`` route through the batched staged tables
    ((B, S, P); core/staging.py) into the fused Pallas kernels
    (kernels/butterfly.py, kernels/shear.py) with the vmapped ref.py
    oracle as the ``backend="xla"`` fallback.
  * ``save`` / ``load`` persist factors + spectrum through the
    fault-tolerant checkpoint store (checkpoint/store.py; DESIGN.md §6).

Everything also works unbatched ((n, n) input) so single-matrix callers can
migrate from the two legacy APIs without behavior change.
"""
from __future__ import annotations

import functools
import warnings
from dataclasses import dataclass, field, replace
from typing import Any, Callable, Dict, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import gtransform as gt
from . import ttransform as tt
from .staging import (StagedG, StagedT, default_cut_ladder,
                      pack_g_batch_pair, pack_g_pair, pack_t_batch_pair,
                      pack_t_pair, select_cut)
from .types import GFactors, TFactors

SYMMETRIC = "sym"
GENERAL = "general"


# ---------------------------------------------------------------------------
# Cached jitted fit programs (one compile per (kind, g, hyperparam) combo)
# ---------------------------------------------------------------------------

def _over_batch(one, shards: int = 1):
    """``one`` over a leading batch axis of every argument.

    Each device's matrices run one after another (``lax.map``): the
    greedy and polish loops update rows and columns at data-dependent
    indices, which a lockstep ``vmap`` would turn into gathers and
    scatters over the whole stack, and a TPU runs those far slower than
    the in-place slice updates of one matrix.  ``shards`` is the number
    of devices the batch is split over: the batch is viewed as (shards,
    B / shards) and vmapped over its device axis (one matrix slice per
    device, so nothing is gathered), where a plain ``lax.map`` over the
    split axis would gather the whole batch onto every device."""
    def per_device(*args):
        return jax.lax.map(lambda a: one(*a), args)

    def run(*args):
        split = [a.reshape(shards, -1, *a.shape[1:]) for a in args]
        out = jax.vmap(per_device)(*split)
        return jax.tree.map(lambda o: o.reshape(-1, *o.shape[2:]), out)

    # a size-1 device axis would still cost index arithmetic in every
    # loop step
    return per_device if shards == 1 else run


def _batch_shards(mats) -> int:
    """How many devices the leading (batch) axis of ``mats`` is split
    over (1 for host arrays and single-device arrays)."""
    sharding = getattr(mats, "sharding", None)
    if sharding is None:
        return 1
    return mats.shape[0] // sharding.shard_shape(mats.shape)[0]


@functools.lru_cache(maxsize=None)
def _sym_fit_program(g: int, n_iter: int, update_spectrum: bool,
                     eps: float, score: str, batched: bool,
                     masked: bool = False, shards: int = 1):
    if masked:
        def one(s_mat, sbar0, size):
            return gt._approx_sym_core(
                s_mat, sbar0, g, n_iter, update_spectrum,
                jnp.asarray(eps, s_mat.dtype), score, size)
    else:
        def one(s_mat, sbar0):
            return gt._approx_sym_core(
                s_mat, sbar0, g, n_iter, update_spectrum,
                jnp.asarray(eps, s_mat.dtype), score)

    return jax.jit(_over_batch(one, shards) if batched else one)


@functools.lru_cache(maxsize=None)
def _gen_fit_program(m: int, n_iter: int, update_spectrum: bool,
                     eps: float, batched: bool, masked: bool = False,
                     shards: int = 1):
    if masked:
        def one(c_mat, cbar0, size):
            return tt._approx_gen_core(
                c_mat, cbar0, m, n_iter, update_spectrum,
                jnp.asarray(eps, c_mat.dtype), size)
    else:
        def one(c_mat, cbar0):
            return tt._approx_gen_core(
                c_mat, cbar0, m, n_iter, update_spectrum,
                jnp.asarray(eps, c_mat.dtype))

    return jax.jit(_over_batch(one, shards) if batched else one)


@functools.lru_cache(maxsize=None)
def _sym_extend_program(g_extra: int, n_iter: int, update_spectrum: bool,
                        eps: float, score: str, batched: bool,
                        masked: bool = False, shards: int = 1):
    """Warm-start extension program, cached like the fit programs: one
    compile per (g_extra, hyperparam) combo serves every batch."""
    if masked:
        def one(s_mat, fi, fj, fc, fs, fsg, sbar, size):
            return gt._extend_sym_core(
                s_mat, GFactors(fi, fj, fc, fs, fsg), sbar, g_extra,
                n_iter, update_spectrum, jnp.asarray(eps, s_mat.dtype),
                score, size)
    else:
        def one(s_mat, fi, fj, fc, fs, fsg, sbar):
            return gt._extend_sym_core(
                s_mat, GFactors(fi, fj, fc, fs, fsg), sbar, g_extra,
                n_iter, update_spectrum, jnp.asarray(eps, s_mat.dtype),
                score)

    return jax.jit(_over_batch(one, shards) if batched else one)


@functools.lru_cache(maxsize=None)
def _gen_extend_program(m_extra: int, n_iter: int, update_spectrum: bool,
                        eps: float, batched: bool, masked: bool = False,
                        shards: int = 1):
    if masked:
        def one(c_mat, fk, fi, fj, fa, cbar, size):
            return tt._extend_gen_core(
                c_mat, TFactors(fk, fi, fj, fa), cbar, m_extra, n_iter,
                update_spectrum, jnp.asarray(eps, c_mat.dtype), size)
    else:
        def one(c_mat, fk, fi, fj, fa, cbar):
            return tt._extend_gen_core(
                c_mat, TFactors(fk, fi, fj, fa), cbar, m_extra, n_iter,
                update_spectrum, jnp.asarray(eps, c_mat.dtype))

    return jax.jit(_over_batch(one, shards) if batched else one)


def _is_symmetric(mats: jnp.ndarray) -> bool:
    # on-device reduction: only one scalar crosses to the host (the batch
    # may be large and already device-resident)
    return bool(jnp.allclose(mats, jnp.swapaxes(mats, -1, -2), atol=1e-6))


def pad_ragged(mats, width: Optional[int] = None
               ) -> tuple[jnp.ndarray, np.ndarray]:
    """Zero-pad a heterogeneous fleet of square matrices into one bucket.

    ``mats``: a sequence of (n_b, n_b) arrays (sizes may differ).  Returns
    ``(stack, sizes)`` with ``stack`` a (B, n, n) f32 stack (``n`` =
    ``width`` or the largest size) and ``sizes`` the (B,) true sides.
    The zero pad block is exactly representable: a masked fit
    (``ApproxEigenbasis.fit(..., sizes=sizes)``) acts as the identity on
    coordinates >= n_b, so each matrix factors as its own-size fit would
    (DESIGN.md §10)."""
    arrs = [np.asarray(m, np.float32) for m in mats]
    for a in arrs:
        if a.ndim != 2 or a.shape[0] != a.shape[1]:
            raise ValueError(f"ragged fleet entries must be square "
                             f"matrices, got shape {a.shape}")
    if not arrs:
        raise ValueError("empty ragged fleet")
    sizes = np.asarray([a.shape[0] for a in arrs], np.int64)
    n = int(width) if width is not None else int(sizes.max())
    if n < int(sizes.max()):
        raise ValueError(f"bucket width {n} < largest matrix "
                         f"{int(sizes.max())}")
    out = np.zeros((len(arrs), n, n), np.float32)
    for b, a in enumerate(arrs):
        out[b, :a.shape[0], :a.shape[0]] = a
    return jnp.asarray(out), sizes


def _zero_pad_block(mats: jnp.ndarray, sizes) -> jnp.ndarray:
    """Enforce the ragged-embedding precondition: coordinates >= the true
    size are zeroed.  The masked greedy never SELECTS a pad pair either
    way, but the polish/Lemma value refits and the reported objective
    integrate whole rows/cols — a caller-padded stack with garbage in the
    pad block would silently corrupt them, so the contract is enforced
    rather than assumed."""
    if sizes is None:
        return mats
    n = mats.shape[-1]
    valid = jnp.arange(n) < jnp.asarray(np.asarray(sizes))[..., None]
    return jnp.where(
        jnp.logical_and(valid[..., :, None], valid[..., None, :]),
        mats, 0.0)


def _normalize_sizes(sizes, batched: bool, n: int, batch: int):
    """Validate/canonicalize a ``sizes`` argument.

    Returns host metadata: an (B,) int64 array for a batched fit, an int
    for an unbatched one — or None when every matrix fills the bucket
    (the unmasked programs are strictly cheaper)."""
    if sizes is None:
        return None
    sizes = np.asarray(sizes)
    if batched:
        if sizes.shape != (batch,):
            raise ValueError(f"sizes must be ({batch},) to match the "
                             f"matrix batch, got {sizes.shape}")
        sizes = sizes.astype(np.int64)
    else:
        if sizes.ndim != 0:
            raise ValueError(f"unbatched fit takes a scalar size, got "
                             f"shape {sizes.shape}")
        sizes = np.int64(sizes)
    if np.any(sizes < 2) or np.any(sizes > n):
        raise ValueError(f"sizes must lie in [2, {n}], got {sizes}")
    if np.all(sizes == n):
        return None
    return int(sizes) if not batched else sizes


@dataclass
class ApproxEigenbasis:
    """A fitted fast approximate eigenbasis (single matrix or a batch).

    Attributes:
      kind: "sym" (G-transforms) or "general" (T-transforms).
      n: matrix side.
      batched: True when ``factors``/``spectrum`` carry a leading batch dim.
      factors: GFactors (g,)-arrays or TFactors (m,)-arrays; (B, g)/(B, m)
        when batched.
      spectrum: estimated eigenvalues, (n,) or (B, n) f32.
      fwd: staged Ubar / Tbar tables, (S, P) or (B, S, P).
      bwd: staged Ubar^T / Tbar^{-1} tables, same layout.
      objective: final ||M - reconstruction||_F^2, scalar or (B,).
      info: fit diagnostics (objective history, iteration counts).
      sizes: true matrix sides for a ragged (masked) fit — (B,) int64 host
        array / int, or None when every matrix fills the bucket.  A masked
        basis acts as the identity on coordinates >= sizes[b] (DESIGN.md
        §10): ``apply`` passes those signal coordinates through untouched
        and ``project`` zeroes them (the padded spectrum is zero).
    """

    kind: str
    n: int
    batched: bool
    factors: Union[GFactors, TFactors]
    spectrum: jnp.ndarray
    fwd: Union[StagedG, StagedT]
    bwd: Union[StagedG, StagedT]
    objective: Optional[jnp.ndarray] = None
    info: Dict[str, Any] = field(default_factory=dict)
    sizes: Optional[Any] = None

    # -- fitting -----------------------------------------------------------

    @classmethod
    def fit(cls, mats, num_transforms: int, *,
            kind: str = "auto", hint: Optional[str] = None,
            n_iter: int = 8, eps: float = 1e-3,
            update_spectrum: bool = True,
            spectrum: Optional[jnp.ndarray] = None,
            score: Optional[str] = None,
            sizes=None,
            mesh: Optional[Any] = None,
            stage_pad: Optional[tuple] = None) -> "ApproxEigenbasis":
        """Factor one matrix (n, n) or a batch (B, n, n) — Algorithm 1.

        A batch runs inside ONE jit: the B greedy factorizations
        (Theorem-1/3 init + Theorem-2/4 polish sweeps + Lemma-1/2
        spectrum refits) run one after another on each device
        (``_over_batch``).  With ``mesh`` the batch is device_put
        against the mesh's data axes first, so the same program runs SPMD
        across devices (DESIGN.md §7).

        Heterogeneous fleets (DESIGN.md §10): ``mats`` may be a LIST of
        square matrices with different sides — they are zero-padded into
        one (B, n, n) bucket (``pad_ragged``) and fitted with the greedy
        masked to each matrix's true coordinates, so every factor chain
        acts as the identity on its padding block and the per-matrix
        result matches the matrix's own-size fit.  Alternatively pass an
        already-padded stack plus ``sizes`` ((B,) true sides; the pad
        block must be zero).

        ``kind="auto"`` picks "sym" when the input is (numerically)
        symmetric; pass ``kind="sym"``/``"general"`` to force a family, or
        ``hint`` to keep auto-detection but get a warning when it resolves
        against the caller's expectation (e.g. a directed graph whose
        Laplacian happens to be numerically symmetric would silently route
        through the G path).  ``score``/``spectrum`` have the same meaning
        as in ``approximate_symmetric``; ``score`` applies to the
        symmetric family only and is rejected (not silently dropped) for
        a general-family fit.

        ``stage_pad``: optional (depth_quantum, width_quantum) staged-
        table shape quantization for BATCHED fits (core/staging.py;
        DESIGN.md §11): rounding each chunk's depth / the stage width up
        to fixed quanta makes repeated refits of the same (B, n, g)
        problem land on identical table shapes, so every jitted program
        holding the tables as arguments (drift scoring, serving tiers)
        hits its compile cache instead of retracing.  The dynamic serve
        engines fit with ``stage_pad=(4, 8)``.
        """
        if isinstance(mats, (list, tuple)):
            if sizes is not None:
                raise ValueError("pass sizes= only with a pre-padded "
                                 "stack; a ragged list derives its own")
            mats, sizes = pad_ragged(mats)
        mats = jnp.asarray(mats, jnp.float32)
        if mats.ndim not in (2, 3):
            raise ValueError(f"expected (n, n) or (B, n, n), got {mats.shape}")
        batched = mats.ndim == 3
        n = mats.shape[-1]
        if mats.shape[-2] != n:
            raise ValueError(f"matrices must be square, got {mats.shape}")
        sizes = _normalize_sizes(sizes, batched, n,
                                 mats.shape[0] if batched else 0)
        mats = _zero_pad_block(mats, sizes)
        if hint not in (None, SYMMETRIC, GENERAL):
            raise ValueError(f"unknown hint {hint!r}; expected "
                             f"{SYMMETRIC!r} or {GENERAL!r}")
        if kind == "auto":
            kind = SYMMETRIC if _is_symmetric(mats) else GENERAL
            if hint is not None and hint != kind:
                warnings.warn(
                    f"kind='auto' resolved to {kind!r}, overriding the "
                    f"caller hint {hint!r}; pass kind={hint!r} to force "
                    "that factorization family", stacklevel=2)
        if kind == GENERAL and score is not None:
            raise ValueError(
                f"score={score!r} applies to the symmetric (G-transform) "
                "family only; the general (T-transform) greedy has no "
                "score variant — drop the argument or force kind='sym'")
        if spectrum is not None:
            spectrum = jnp.asarray(spectrum, jnp.float32)
            want = mats.shape[:-2] + (n,)
            if spectrum.shape != want:
                raise ValueError(
                    f"spectrum shape {spectrum.shape} does not match the "
                    f"fitted batch: expected {want}")
        if mesh is not None and batched:
            # unbatched (n, n) input has no batch axis to spread — only a
            # (B, n, n) stack shards; awkward B falls back to replication
            from repro.runtime.sharding import matrix_batch_sharding
            mats = jax.device_put(
                mats, matrix_batch_sharding(mesh, mats.ndim,
                                            batch=mats.shape[0]))
        masked = sizes is not None
        size_arg = (jnp.asarray(sizes, jnp.int32),) if masked else ()

        if kind == SYMMETRIC:
            if score is None:
                score = "paper" if spectrum is not None else "gamma"
            sbar0 = (spectrum if spectrum is not None
                     else gt.default_sbar(mats, sizes))
            fit_fn = _sym_fit_program(num_transforms, n_iter,
                                      update_spectrum, float(eps), score,
                                      batched, masked, _batch_shards(mats))
            factors, sbar, obj, hist, iters = fit_fn(mats, sbar0, *size_arg)
            fwd, bwd = (pack_g_batch_pair(factors, n, pad=stage_pad)
                        if batched else pack_g_pair(factors, n=n))
            return cls(kind=SYMMETRIC, n=n, batched=batched,
                       factors=factors, spectrum=sbar, fwd=fwd, bwd=bwd,
                       objective=obj,
                       info={"history": hist, "iterations": iters,
                             "score": score, "stage_pad": stage_pad},
                       sizes=sizes)

        if kind == GENERAL:
            cbar0 = (spectrum if spectrum is not None
                     else tt.default_cbar(mats, sizes))
            fit_fn = _gen_fit_program(num_transforms, n_iter,
                                      update_spectrum, float(eps), batched,
                                      masked, _batch_shards(mats))
            factors, cbar, obj, hist, iters = fit_fn(mats, cbar0, *size_arg)
            fwd, bwd = (pack_t_batch_pair(factors, n, pad=stage_pad)
                        if batched else pack_t_pair(factors, n))
            return cls(kind=GENERAL, n=n, batched=batched,
                       factors=factors, spectrum=cbar, fwd=fwd, bwd=bwd,
                       objective=obj,
                       info={"history": hist, "iterations": iters,
                             "stage_pad": stage_pad},
                       sizes=sizes)

        raise ValueError(f"unknown kind {kind!r}")

    # -- warm-start extension (DESIGN.md §9) -------------------------------

    @property
    def num_transforms(self) -> int:
        """Number of fitted fundamental components g (per matrix)."""
        return int(np.asarray(self.factors[0]).shape[-1])

    @property
    def stage_cuts(self) -> np.ndarray:
        """(C, 2) array of exact (num_stages, num_components) anytime
        boundaries of the staged tables (core/staging.py)."""
        return self.fwd.cuts

    def select_tier(self, fraction: Optional[float] = None,
                    num_transforms: Optional[int] = None) -> tuple:
        """Pick the exact stage cut nearest a component target; returns
        ``(num_stages, num_components)`` for ``apply``/``project``."""
        return select_cut(self.fwd, num_transforms=num_transforms,
                          fraction=fraction)

    def extend(self, mats: jnp.ndarray, num_transforms: int, *,
               n_iter: int = 0, eps: float = 1e-3,
               update_spectrum: bool = True, score: Optional[str] = None,
               mesh: Optional[Any] = None,
               stage_pad: Optional[tuple] = None) -> "ApproxEigenbasis":
        """Grow this fit to ``num_transforms`` total components WITHOUT
        refitting the prefix: Theorem-1/3-initialized components are
        greedily appended against the current residual (the greedy
        continues exactly where a from-scratch init would stand after the
        first g components), so the extended basis's anytime prefix of the
        ORIGINAL g components is the original basis.  ``n_iter`` > 0
        additionally re-sweeps the whole chain (fitted prefix included)
        with the usual polish/Lemma refinement.

        ``mats``: the same (n, n) / (B, n, n) stack this basis was fitted
        to (the basis stores factors, not matrices; a ragged fit extends
        against the same zero-padded bucket stack and keeps its masking).
        Batched fits extend under one jitted program, cached like the
        fit programs.  The extended tables' cut ladder includes the
        ORIGINAL g, so the pre-extension basis remains selectable as a
        serving tier.  ``score`` defaults to the score the fit resolved
        (recorded in ``info`` and restored by ``load``); like ``fit`` it
        is rejected for the general family."""
        mats = jnp.asarray(mats, jnp.float32)
        if mats.ndim != (3 if self.batched else 2):
            raise ValueError(f"expected {'batched ' if self.batched else ''}"
                             f"matrices matching the fit, got {mats.shape}")
        if mats.shape[-1] != self.n or mats.shape[-2] != self.n:
            raise ValueError(f"matrix side {mats.shape[-1]} != fitted "
                             f"n={self.n}")
        if self.kind != SYMMETRIC and score is not None:
            raise ValueError(
                f"score={score!r} applies to the symmetric (G-transform) "
                "family only; this basis is kind='general'")
        g_old = self.num_transforms
        extra = num_transforms - g_old
        if extra <= 0:
            raise ValueError(f"num_transforms must exceed the fitted "
                             f"{g_old}, got {num_transforms}")
        n = self.n
        if mesh is not None and self.batched:
            from repro.runtime.sharding import matrix_batch_sharding
            mats = jax.device_put(
                mats, matrix_batch_sharding(mesh, mats.ndim,
                                            batch=mats.shape[0]))
        masked = self.sizes is not None
        mats = _zero_pad_block(mats, self.sizes)
        size_arg = (jnp.asarray(self.sizes, jnp.int32),) if masked else ()
        # keep the pre-extension basis selectable as a tier: the new
        # ladder carries the original g as an extra exact cut
        cuts = sorted(set(default_cut_ladder(num_transforms).tolist())
                      | {g_old})
        if stage_pad is None:     # keep the fit's shape quantization
            stage_pad = self.info.get("stage_pad")
        info = {"extended_from": g_old, "stage_pad": stage_pad}
        if self.kind == SYMMETRIC:
            if score is None:
                score = self.info.get("score", "gamma")
            info["score"] = score  # chained extends keep the criterion
            fit_fn = _sym_extend_program(extra, n_iter, update_spectrum,
                                         float(eps), score, self.batched,
                                         masked, _batch_shards(mats))
            factors, sbar, obj, hist, iters = fit_fn(
                mats, *self.factors, self.spectrum, *size_arg)
            fwd, bwd = (pack_g_batch_pair(factors, n, cuts=cuts,
                                          pad=stage_pad)
                        if self.batched
                        else pack_g_pair(factors, cuts=cuts, n=n))
        else:
            fit_fn = _gen_extend_program(extra, n_iter, update_spectrum,
                                         float(eps), self.batched, masked,
                                         _batch_shards(mats))
            factors, sbar, obj, hist, iters = fit_fn(
                mats, *self.factors, self.spectrum, *size_arg)
            fwd, bwd = (pack_t_batch_pair(factors, n, cuts=cuts,
                                          pad=stage_pad)
                        if self.batched
                        else pack_t_pair(factors, n, cuts=cuts))
        info.update(history=hist, iterations=iters)
        return type(self)(kind=self.kind, n=n, batched=self.batched,
                          factors=factors, spectrum=sbar, fwd=fwd, bwd=bwd,
                          objective=obj, info=info, sizes=self.sizes)

    # -- application (plan-backed: one cached program per shape; ----------
    # -- DESIGN.md §13) ----------------------------------------------------

    def _plan(self, mode: str, backend: str, num_stages: Optional[int],
              precision: str, keep: str = "head", fused: bool = True):
        from repro.kernels.plan import ApplyPlan
        return ApplyPlan(family=self.kind, mode=mode, n=self.n,
                         batched=self.batched, backend=backend,
                         num_stages=num_stages, keep=keep,
                         precision=precision, fused=fused)

    def apply(self, x: jnp.ndarray, inverse: bool = False,
              backend: str = "xla", num_stages: Optional[int] = None,
              precision: str = "f32") -> jnp.ndarray:
        """y = Ubar x (or Tbar x); ``inverse=True`` applies Ubar^T /
        Tbar^{-1} (graph Fourier ANALYSIS; forward is SYNTHESIS).

        ``x``: (..., n), with a leading (B, ...) batch when ``batched``.
        ``num_stages`` runs the anytime prefix (pick a boundary with
        ``select_tier``; DESIGN.md §9).  ``precision="bf16"`` runs bf16
        table storage with f32 accumulation (DESIGN.md §13).
        """
        from repro.kernels.plan import leg_orientation
        staged = self.bwd if inverse else self.fwd
        keep = leg_orientation(self.kind)[0 if inverse else 1]
        plan = self._plan("apply", backend, num_stages, precision, keep)
        return plan.apply(staged, x)

    def project(self, x: jnp.ndarray,
                h: Optional[Callable[[jnp.ndarray], jnp.ndarray]] = None,
                backend: str = "xla",
                num_stages: Optional[int] = None,
                precision: str = "f32", fused: bool = True) -> jnp.ndarray:
        """Apply the reconstructed operator (a spectral projection/filter):

            y = Ubar diag(h(spectrum)) Ubar^T x      (symmetric)
            y = Tbar diag(h(spectrum)) Tbar^{-1} x   (general)

        ``h`` defaults to the identity (the approximated matrix itself).
        ``backend="pallas"`` runs the fused one-round-trip kernel; batched
        instances use the (B, S, P)-table batched kernels (DESIGN.md §4,
        §7).  ``num_stages`` truncates both transform legs to the same
        anytime component prefix (DESIGN.md §9).  On a ragged basis the
        gains are zeroed at each matrix's padding coordinates — the padded
        spectrum slots are 0 but ``h(0)`` need not be (heat/Tikhonov map
        0 -> 1), and the transforms pass pad coordinates through, so an
        unmasked ``h`` would leak pad columns of ``x`` into the output.
        ``precision="bf16"``/``fused=False`` select the plan layer's
        storage-precision and three-pass baseline paths (DESIGN.md
        §13)."""
        d = self.spectrum if h is None else h(self.spectrum)
        if h is not None and self.sizes is not None:
            valid = (np.arange(self.n)
                     < np.asarray(self.sizes)[..., None])
            d = jnp.where(jnp.asarray(valid), d, 0.0)
        plan = self._plan("operator", backend, num_stages, precision,
                          fused=fused)
        return plan.operator(self.fwd, self.bwd, d, x)

    def to_dense(self, num_stages: Optional[int] = None) -> jnp.ndarray:
        """Materialize the basis: Ubar / Tbar as (n, n) or (B, n, n)
        (``num_stages``: the anytime prefix basis instead of the full
        one)."""
        eye = jnp.eye(self.n, dtype=jnp.float32)
        if self.batched:
            b = self.spectrum.shape[0]
            eye = jnp.broadcast_to(eye, (b, self.n, self.n))
        # staged apply acts on row vectors: row r of the result is
        # (basis e_r), i.e. the transpose of the basis matrix
        return jnp.swapaxes(self.apply(eye, num_stages=num_stages),
                            -1, -2)

    def reconstruct(self) -> jnp.ndarray:
        """Dense approximation  Ubar diag(s) Ubar^T  /  Tbar diag(c)
        Tbar^{-1}  as (n, n) or (B, n, n) (small-n evaluation only)."""
        eye = jnp.eye(self.n, dtype=jnp.float32)
        if self.batched:
            b = self.spectrum.shape[0]
            eye = jnp.broadcast_to(eye, (b, self.n, self.n))
        return jnp.swapaxes(self.project(eye), -1, -2)

    def frobenius_error(self, mats: jnp.ndarray) -> jnp.ndarray:
        """||M - reconstruction||_F^2 per matrix (scalar or (B,))."""
        diff = jnp.asarray(mats, jnp.float32) - self.reconstruct()
        return jnp.sum(diff * diff, axis=(-2, -1))

    def shard(self, mesh) -> "ApproxEigenbasis":
        """Device_put the staged tables + spectrum against ``mesh``: the
        leading matrix-batch axis maps to the mesh's data axes, so
        ``apply``/``project`` on (B, ..., n) signals run SPMD without any
        code change (runtime/sharding.py)."""
        if not self.batched:
            return self
        from repro.runtime.sharding import matrix_batch_sharding
        batch = int(self.spectrum.shape[0])

        def put(leaf):
            if isinstance(leaf, (int, np.integer)) or leaf is None:
                return leaf
            if isinstance(leaf, np.ndarray):
                return leaf  # host metadata (the cuts ladder) stays host
            return jax.device_put(
                leaf, matrix_batch_sharding(mesh, jnp.ndim(leaf),
                                            batch=batch))

        fwd = type(self.fwd)(*(put(l) for l in self.fwd))
        bwd = type(self.bwd)(*(put(l) for l in self.bwd))
        return replace(self, fwd=fwd, bwd=bwd, spectrum=put(self.spectrum))

    # -- persistence (checkpoint/store.py; DESIGN.md §6) --------------------

    def save(self, directory, step: int = 0, *,
             extra_state: Optional[Dict[str, Any]] = None,
             extra_metadata: Optional[Dict[str, Any]] = None,
             shards: int = 1):
        """Persist factors + spectrum via the atomic checkpoint store.

        ``extra_state``: additional leaves saved alongside (``load``
        ignores them; callers restore them with their own ``state_like``
        — the dynamic serve engines persist their tracked Laplacians this
        way).  ``extra_metadata``: JSON-able keys merged into the
        manifest metadata next to the ``eigenbasis`` block.  ``shards``:
        per-shard table files (mesh-placed engines pass their device
        count; ``load`` reassembles on any mesh — DESIGN.md §14)."""
        from repro.checkpoint import save_checkpoint
        state = {"factors": self.factors, "spectrum": self.spectrum}
        for key, leaf in (extra_state or {}).items():
            if key in state:
                raise ValueError(f"extra_state key {key!r} collides with "
                                 "the basis state")
            state[key] = leaf
        meta = dict(extra_metadata or {})
        if "eigenbasis" in meta:
            raise ValueError("extra_metadata must not carry an "
                             "'eigenbasis' key")
        meta.update({
            "eigenbasis": {
                "kind": self.kind, "n": self.n, "batched": self.batched,
                "num_transforms": int(
                    np.asarray(self.factors[0]).shape[-1]),
                "batch": (int(self.spectrum.shape[0]) if self.batched
                          else 0),
                # anytime prefix metadata (DESIGN.md §9): load() repacks
                # the staged tables deterministically, so recording the
                # ladder here both documents the serving tiers a restored
                # basis offers and lets load() verify the repack
                "num_stages": int(self.fwd.num_stages),
                "stage_cuts": (np.asarray(self.fwd.cuts).tolist()
                               if self.fwd.cuts is not None else None),
                # the fit's resolved greedy criterion and final objective:
                # without these a restored basis would EXTEND under the
                # default "gamma" score even when the fit used "paper",
                # silently switching the greedy mid-chain
                "score": self.info.get("score"),
                "objective": (np.asarray(self.objective,
                                         np.float64).tolist()
                              if self.objective is not None else None),
                # ragged-fleet masking (DESIGN.md §10)
                "sizes": (np.asarray(self.sizes).tolist()
                          if self.sizes is not None else None),
                # dynamic-subsystem basis version (DESIGN.md §11): bumped
                # by the serving layer on every hot swap; pre-versioned
                # checkpoints simply lack the key and load() defaults it
                # to 0
                "version": int(self.info.get("version", 0)),
                # staged-table shape quantization (DESIGN.md §11): load()
                # must repack with the same quanta or the cut ladder's
                # stage indices would shift
                "stage_pad": (list(self.info["stage_pad"])
                              if self.info.get("stage_pad") else None),
            }
        })
        return save_checkpoint(directory, step, state, metadata=meta,
                               shards=shards)

    @classmethod
    def load(cls, directory, step: Optional[int] = None
             ) -> "ApproxEigenbasis":
        """Restore a fitted basis and rebuild its staged tables."""
        from repro.checkpoint import (read_metadata, restore_checkpoint,
                                      latest_step)
        if step is None:
            step = latest_step(directory)
            if step is None:
                raise FileNotFoundError(
                    f"no committed checkpoint in {directory}")
        meta = read_metadata(directory, step).get("eigenbasis")
        if meta is None:
            raise ValueError(f"checkpoint at {directory} does not hold an "
                             "ApproxEigenbasis state")
        kind, n = meta["kind"], int(meta["n"])
        batched = bool(meta["batched"])
        g = int(meta["num_transforms"])
        shape = (int(meta["batch"]), g) if batched else (g,)
        nsh = (int(meta["batch"]), n) if batched else (n,)
        zi = jnp.zeros(shape, jnp.int32)
        zf = jnp.zeros(shape, jnp.float32)
        if kind == SYMMETRIC:
            factors_like = GFactors(i=zi, j=zi, c=zf, s=zf, sigma=zf)
        else:
            factors_like = TFactors(kind=zi, i=zi, j=zi, a=zf)
        like = {"factors": factors_like,
                "spectrum": jnp.zeros(nsh, jnp.float32)}
        state, _, _ = restore_checkpoint(directory, like, step=step)
        factors, spectrum = state["factors"], state["spectrum"]
        stage_pad = meta.get("stage_pad")
        if stage_pad is not None:
            stage_pad = tuple(int(q) for q in stage_pad)
        # repack with the checkpoint's COMPONENT ladder: an extended
        # basis carries its pre-extension g as an extra exact cut, which
        # the default quarters ladder would silently drop
        cuts = None
        if meta.get("stage_cuts") is not None:
            cuts = sorted({int(row[1]) for row in meta["stage_cuts"]})
        if kind == SYMMETRIC:
            fwd, bwd = (pack_g_batch_pair(factors, n, cuts=cuts,
                                          pad=stage_pad)
                        if batched else pack_g_pair(factors, cuts=cuts,
                                                    n=n))
        else:
            fwd, bwd = (pack_t_batch_pair(factors, n, cuts=cuts,
                                          pad=stage_pad)
                        if batched else pack_t_pair(factors, n,
                                                    cuts=cuts))
        saved_cuts = meta.get("stage_cuts")
        if (saved_cuts is not None and fwd.cuts is not None
                and np.asarray(fwd.cuts).tolist() != saved_cuts):
            warnings.warn(
                "restored staged tables repacked with a different anytime "
                "cut ladder than the checkpoint recorded (packing defaults "
                "changed?); serving tiers pinned to the old ladder's stage "
                "counts must be re-selected via select_tier", stacklevel=2)
        # restore the fit's resolved scoring criterion + objective so a
        # post-restore extend() keeps the original greedy criterion
        # (pre-fix checkpoints carry neither key -> .get defaults)
        info: Dict[str, Any] = {}
        if meta.get("score") is not None:
            info["score"] = meta["score"]
        # dynamic-subsystem version: pre-versioned checkpoints carry no
        # key and restore as version 0 (DESIGN.md §11)
        info["version"] = int(meta.get("version", 0))
        info["stage_pad"] = stage_pad
        objective = None
        if meta.get("objective") is not None:
            objective = jnp.asarray(meta["objective"], jnp.float32)
        sizes = meta.get("sizes")
        if sizes is not None:
            sizes = (np.asarray(sizes, np.int64) if batched
                     else int(sizes))
        return cls(kind=kind, n=n, batched=batched, factors=factors,
                   spectrum=spectrum, fwd=fwd, bwd=bwd,
                   objective=objective, info=info, sizes=sizes)
