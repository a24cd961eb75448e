"""Pure-jnp oracles for every kernel in this package.

These are the semantics of record: Pallas kernels must match them (see
tests/test_kernels.py shape/dtype sweeps).  They are also the production
``backend="xla"`` path used by the dry-run (Pallas TPU kernels cannot lower
on the CPU backend; DESIGN.md §4).

Padding entries in the staged tables carry the out-of-bounds index ``n``:
reads clip (the value is never used), writes drop — which also gives the
ragged-fleet semantics for free (DESIGN.md §10): a masked bucket fit's
chain leaves each matrix's padding coordinates untouched.

Every oracle takes a static ``num_stages`` prefix argument (DESIGN.md §9):
``None`` applies the full staged chain; an integer cuts the stage tables at
that boundary BEFORE the scan, so a truncated transform costs exactly
``num_stages`` stages.  Plain applies also take ``keep`` ("head"/"tail")
because a staged table set's significant stages sit at its head or tail
depending on family and direction (core/staging.py); the fused operators
know their own orientation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.tables import StagedG, StagedT, truncate_staged


def staged_g_apply(staged: StagedG, x: jnp.ndarray,
                   num_stages: int | None = None,
                   keep: str = "head") -> jnp.ndarray:
    """Apply the staged G-transform product to x (..., n) on the last axis."""
    staged = truncate_staged(staged, num_stages, keep)

    def stage(xc, arrs):
        ii, jj, cc, ss, sg = arrs
        cc = cc.astype(xc.dtype)
        ss = ss.astype(xc.dtype)
        sg = sg.astype(xc.dtype)
        # padding entries carry the out-of-bounds index n: reads clip
        # (value unused), writes drop (structural no-op)
        xi = jnp.take(xc, ii, axis=-1, mode="clip")
        xj = jnp.take(xc, jj, axis=-1, mode="clip")
        yi = cc * xi + ss * xj
        yj = sg * (-ss * xi + cc * xj)
        xc = xc.at[..., ii].set(yi, mode="drop")
        xc = xc.at[..., jj].set(yj, mode="drop")
        return xc, None

    out, _ = lax.scan(stage, x, (staged.idx_i, staged.idx_j, staged.c,
                                 staged.s, staged.sigma))
    return out


def staged_t_apply(staged: StagedT, x: jnp.ndarray,
                   num_stages: int | None = None,
                   keep: str = "head") -> jnp.ndarray:
    """Apply the staged T-transform product to x (..., n) on the last axis."""
    staged = truncate_staged(staged, num_stages, keep)

    def stage(xc, arrs):
        ii, jj, al, be = arrs
        al = al.astype(xc.dtype)
        be = be.astype(xc.dtype)
        xi = jnp.take(xc, ii, axis=-1, mode="clip")
        xj = jnp.take(xc, jj, axis=-1, mode="clip")
        yi = al * xi + be * xj
        xc = xc.at[..., ii].set(yi, mode="drop")
        return xc, None

    out, _ = lax.scan(stage, x, (staged.idx_i, staged.idx_j, staged.alpha,
                                 staged.beta))
    return out


def sym_operator_apply(fwd: StagedG, adj: StagedG, diag: jnp.ndarray,
                       x: jnp.ndarray,
                       num_stages: int | None = None) -> jnp.ndarray:
    """Sbar x = Ubar diag(sbar) Ubar^T x (the symmetric FGFT projection).

    ``num_stages`` cuts BOTH legs to the same component prefix: the
    adjoint's head stages and the forward tables' tail stages
    (core/staging.py orientation)."""
    y = staged_g_apply(adj, x, num_stages, keep="head")
    y = y * diag.astype(y.dtype)
    return staged_g_apply(fwd, y, num_stages, keep="tail")


# ---------------------------------------------------------------------------
# Batched oracles: staged tables carry a leading matrix-batch dim (B, S, P)
# and x is (B, R, n) — one independent factorization per batch row
# (DESIGN.md §7).  vmap over the single-matrix oracle is the semantics of
# record for kernels/butterfly.py::batched_sym_operator_apply.  Prefix cuts
# are uniform across the batch (chunk-uniform padding, core/staging.py), so
# truncation slices the (B, S, P) tables once, before the vmap.
# ---------------------------------------------------------------------------

_G_AXES = StagedG(0, 0, 0, 0, 0, None, None)
_T_AXES = StagedT(0, 0, 0, 0, None, None)


def batched_g_apply(staged: StagedG, x: jnp.ndarray,
                    num_stages: int | None = None,
                    keep: str = "head") -> jnp.ndarray:
    """Per-matrix Ubar_b x_b: tables (B, S, P), x (B, ..., n)."""
    staged = truncate_staged(staged, num_stages, keep)
    return jax.vmap(staged_g_apply, in_axes=(_G_AXES, 0))(staged, x)


def batched_t_apply(staged: StagedT, x: jnp.ndarray,
                    num_stages: int | None = None,
                    keep: str = "head") -> jnp.ndarray:
    """Per-matrix Tbar_b x_b: tables (B, S, P), x (B, ..., n)."""
    staged = truncate_staged(staged, num_stages, keep)
    return jax.vmap(staged_t_apply, in_axes=(_T_AXES, 0))(staged, x)


def batched_sym_operator_apply(fwd: StagedG, adj: StagedG,
                               diag: jnp.ndarray, x: jnp.ndarray,
                               num_stages: int | None = None) -> jnp.ndarray:
    """y_b = Ubar_b diag(d_b) Ubar_b^T x_b for every b: diag (B, n),
    x (B, ..., n)."""
    adj = truncate_staged(adj, num_stages, "head")
    fwd = truncate_staged(fwd, num_stages, "tail")
    return jax.vmap(sym_operator_apply,
                    in_axes=(_G_AXES, _G_AXES, 0, 0))(fwd, adj, diag, x)


def batched_gen_operator_apply(fwd: StagedT, inv: StagedT,
                               diag: jnp.ndarray, x: jnp.ndarray,
                               num_stages: int | None = None) -> jnp.ndarray:
    """y_b = Tbar_b diag(d_b) Tbar_b^{-1} x_b for every b."""
    inv = truncate_staged(inv, num_stages, "tail")
    fwd = truncate_staged(fwd, num_stages, "head")
    return jax.vmap(gen_operator_apply,
                    in_axes=(_T_AXES, _T_AXES, 0, 0))(fwd, inv, diag, x)


def gen_operator_apply(fwd: StagedT, inv: StagedT, diag: jnp.ndarray,
                       x: jnp.ndarray,
                       num_stages: int | None = None) -> jnp.ndarray:
    """Cbar x = Tbar diag(cbar) Tbar^{-1} x (the directed FGFT projection).

    ``num_stages`` cuts both legs: the inverse tables' tail stages and the
    forward tables' head stages."""
    y = staged_t_apply(inv, x, num_stages, keep="tail")
    y = y * diag.astype(y.dtype)
    return staged_t_apply(fwd, y, num_stages, keep="head")


# ---------------------------------------------------------------------------
# Filter-bank oracles: F spectral filters share ONE analysis pass
# (repro/spectral/filters.py; DESIGN.md §8).  Semantics of record for
# kernels/spectral.py.
# ---------------------------------------------------------------------------


def _bank_scale(coeff: jnp.ndarray, gains: jnp.ndarray) -> jnp.ndarray:
    """(..., n) coefficients x (F, n) gains -> (F, ..., n) scaled copies."""
    g = gains.reshape((gains.shape[0],) + (1,) * (coeff.ndim - 1)
                      + (gains.shape[-1],))
    return coeff[None] * g.astype(coeff.dtype)


def sym_filter_bank_apply(fwd: StagedG, adj: StagedG, gains: jnp.ndarray,
                          x: jnp.ndarray,
                          num_stages: int | None = None) -> jnp.ndarray:
    """y[f] = Ubar diag(gains_f) Ubar^T x for a bank of F filters.

    ``gains``: (F, n), ``x``: (..., n) -> (F, ..., n).  The analysis
    transform runs ONCE and is reused by every filter — the three-pass
    composition pays it F times (DESIGN.md §8)."""
    coeff = staged_g_apply(adj, x, num_stages, keep="head")
    return staged_g_apply(fwd, _bank_scale(coeff, gains), num_stages,
                          keep="tail")


def gen_filter_bank_apply(fwd: StagedT, inv: StagedT, gains: jnp.ndarray,
                          x: jnp.ndarray,
                          num_stages: int | None = None) -> jnp.ndarray:
    """y[f] = Tbar diag(gains_f) Tbar^{-1} x — the directed bank."""
    coeff = staged_t_apply(inv, x, num_stages, keep="tail")
    return staged_t_apply(fwd, _bank_scale(coeff, gains), num_stages,
                          keep="head")


def batched_sym_filter_bank_apply(fwd: StagedG, adj: StagedG,
                                  gains: jnp.ndarray, x: jnp.ndarray,
                                  num_stages: int | None = None
                                  ) -> jnp.ndarray:
    """Per-matrix banks: tables (B, S, P), gains (B, F, n), x (B, ..., n)
    -> (B, F, ..., n)."""
    adj = truncate_staged(adj, num_stages, "head")
    fwd = truncate_staged(fwd, num_stages, "tail")
    return jax.vmap(sym_filter_bank_apply,
                    in_axes=(_G_AXES, _G_AXES, 0, 0))(fwd, adj, gains, x)


def batched_gen_filter_bank_apply(fwd: StagedT, inv: StagedT,
                                  gains: jnp.ndarray, x: jnp.ndarray,
                                  num_stages: int | None = None
                                  ) -> jnp.ndarray:
    """Directed per-matrix banks: gains (B, F, n), x (B, ..., n)."""
    inv = truncate_staged(inv, num_stages, "tail")
    fwd = truncate_staged(fwd, num_stages, "head")
    return jax.vmap(gen_filter_bank_apply,
                    in_axes=(_T_AXES, _T_AXES, 0, 0))(fwd, inv, gains, x)
