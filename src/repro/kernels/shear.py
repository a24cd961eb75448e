"""Pallas TPU kernel: staged T-transform (scaling/shear) application.

Same launch as butterfly.py (``chain_call``: coordinates on sublanes,
signals on lanes, tables streamed through SMEM); the per-pair action is
the unified  y_i = alpha x_i + beta x_j  — 2 flops per lane and one row
store, where a G pair needs 6 and two (the paper's efficiency argument
for T- over G-transforms carries straight to the VPU).  The fused
general operator applies  Tbar diag(d) Tbar^{-1}  in one launch
(directed-graph FGFT projection).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.tables import StagedT, table_arrays, truncate_staged
from .butterfly import (DEFAULT_BLOCK_B, _STATIC, batch_of_one,
                        chain_call)

#: identity entry (index slots take the width n): alpha=1, beta=0
T_NOOP = (1.0, 0.0)


def _t_pair(buf, rnd, i, j, al, be):
    buf[pl.ds(i, 1), :] = rnd(rnd(al * buf[pl.ds(i, 1), :])
                              + rnd(be * buf[pl.ds(j, 1), :]))


@functools.partial(jax.jit, static_argnames=_STATIC + ("keep",))
def batched_shear_apply(staged: StagedT, x: jnp.ndarray,
                        block_b: int = DEFAULT_BLOCK_B,
                        interpret: Optional[bool] = None,
                        num_stages: int | None = None,
                        keep: str = "head") -> jnp.ndarray:
    """y[b] = Tbar_b x[b]: tables (B, S, P), x (B, R, n) -> (B, R, n).
    Static ``num_stages`` cuts the tables at a prefix boundary
    (DESIGN.md §9)."""
    staged = truncate_staged(staged, num_stages, keep)
    return chain_call(_t_pair, T_NOOP, None, table_arrays(staged), None,
                      x, block_b, interpret)[:, 0]


@functools.partial(jax.jit, static_argnames=_STATIC + ("keep",))
def shear_apply(staged: StagedT, x: jnp.ndarray,
                block_b: int = DEFAULT_BLOCK_B,
                interpret: Optional[bool] = None,
                num_stages: int | None = None,
                keep: str = "head") -> jnp.ndarray:
    """y = Tbar @ x for x of shape (R, n)."""
    return batched_shear_apply(batch_of_one(staged), x[None], block_b,
                               interpret, num_stages, keep)[0]


@functools.partial(jax.jit, static_argnames=_STATIC)
def batched_gen_operator_apply(fwd: StagedT, inv: StagedT,
                               diag: jnp.ndarray, x: jnp.ndarray,
                               block_b: int = DEFAULT_BLOCK_B,
                               interpret: Optional[bool] = None,
                               num_stages: int | None = None
                               ) -> jnp.ndarray:
    """y[b] = Tbar_b diag(d_b) Tbar_b^{-1} x[b] for a batch of directed
    factorizations: tables (B, S, P), diag (B, n), x (B, R, n).  Static
    ``num_stages`` cuts both legs (inv tail / fwd head)."""
    inv = truncate_staged(inv, num_stages, "tail")
    fwd = truncate_staged(fwd, num_stages, "head")
    return chain_call(_t_pair, T_NOOP, table_arrays(inv), table_arrays(fwd),
                      diag[:, None], x, block_b, interpret)[:, 0]


@functools.partial(jax.jit, static_argnames=_STATIC)
def gen_operator_apply(fwd: StagedT, inv: StagedT, diag: jnp.ndarray,
                       x: jnp.ndarray, block_b: int = DEFAULT_BLOCK_B,
                       interpret: Optional[bool] = None,
                       num_stages: int | None = None) -> jnp.ndarray:
    """y = Tbar diag(d) Tbar^{-1} x, fused."""
    return batched_gen_operator_apply(
        batch_of_one(fwd), batch_of_one(inv), diag[None], x[None], block_b,
        interpret, num_stages)[0]
