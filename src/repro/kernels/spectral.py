"""Pallas TPU kernel: fused spectral filter-bank application.

The spectral subsystem (repro/spectral/; DESIGN.md §8) filters graph
signals through a *bank* of F responses at once.  Composed naively that is
three kernel launches per filter — analysis, diagonal scale, synthesis —
and F redundant analysis passes.  This kernel fuses the whole bank into ONE
launch per tile (butterfly.py::chain_call): the analysis transform runs
once into a VMEM scratch, and each filter seeds its output tile from those
coefficients times its gains and runs the synthesis chain there.  HBM
traffic drops from 2F reads + F writes of the signal tile to 1 read + F
writes, and the analysis flops are paid once instead of F times.  The
filter index is part of the grid's last axis, so VMEM holds one output
tile whatever F is.

Validated in interpret mode against kernels/ref.py::*_filter_bank_apply
(tests/test_spectral.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.staging import StagedG, StagedT, table_arrays, truncate_staged
from .butterfly import (DEFAULT_BLOCK_B, G_NOOP, _STATIC, _g_pair,
                        batch_of_one, chain_call)
from .shear import T_NOOP, _t_pair


def _g_legs(fwd: StagedG, adj: StagedG, num_stages=None):
    """Analysis (adj, head-cut) + synthesis (fwd, tail-cut) tables
    truncated to the same component prefix (DESIGN.md §9)."""
    return (table_arrays(truncate_staged(adj, num_stages, "head")),
            table_arrays(truncate_staged(fwd, num_stages, "tail")))


def _t_legs(fwd: StagedT, inv: StagedT, num_stages=None):
    return (table_arrays(truncate_staged(inv, num_stages, "tail")),
            table_arrays(truncate_staged(fwd, num_stages, "head")))


@functools.partial(jax.jit, static_argnames=_STATIC)
def batched_sym_filter_bank_apply(fwd: StagedG, adj: StagedG,
                                  gains: jnp.ndarray, x: jnp.ndarray,
                                  block_b: int = DEFAULT_BLOCK_B,
                                  interpret: Optional[bool] = None,
                                  num_stages: int | None = None
                                  ) -> jnp.ndarray:
    """Per-matrix banks: tables (B, S, P), gains (B, F, n), x (B, R, n)
    -> (B, F, R, n), all F filters in one launch.  Static ``num_stages``
    cuts both transform legs to the same component prefix."""
    return chain_call(_g_pair, G_NOOP, *_g_legs(fwd, adj, num_stages),
                      gains, x, block_b, interpret)


@functools.partial(jax.jit, static_argnames=_STATIC)
def batched_gen_filter_bank_apply(fwd: StagedT, inv: StagedT,
                                  gains: jnp.ndarray, x: jnp.ndarray,
                                  block_b: int = DEFAULT_BLOCK_B,
                                  interpret: Optional[bool] = None,
                                  num_stages: int | None = None
                                  ) -> jnp.ndarray:
    """Directed per-matrix banks: gains (B, F, n), x (B, R, n)."""
    return chain_call(_t_pair, T_NOOP, *_t_legs(fwd, inv, num_stages),
                      gains, x, block_b, interpret)


@functools.partial(jax.jit, static_argnames=_STATIC)
def sym_filter_bank_apply(fwd: StagedG, adj: StagedG, gains: jnp.ndarray,
                          x: jnp.ndarray, block_b: int = DEFAULT_BLOCK_B,
                          interpret: Optional[bool] = None,
                          num_stages: int | None = None) -> jnp.ndarray:
    """y[f] = Ubar diag(gains_f) Ubar^T x: gains (F, n), x (R, n) ->
    (F, R, n)."""
    return batched_sym_filter_bank_apply(
        batch_of_one(fwd), batch_of_one(adj), gains[None], x[None],
        block_b, interpret, num_stages)[0]


@functools.partial(jax.jit, static_argnames=_STATIC)
def gen_filter_bank_apply(fwd: StagedT, inv: StagedT, gains: jnp.ndarray,
                          x: jnp.ndarray, block_b: int = DEFAULT_BLOCK_B,
                          interpret: Optional[bool] = None,
                          num_stages: int | None = None) -> jnp.ndarray:
    """y[f] = Tbar diag(gains_f) Tbar^{-1} x — the directed bank."""
    return batched_gen_filter_bank_apply(
        batch_of_one(fwd), batch_of_one(inv), gains[None], x[None],
        block_b, interpret, num_stages)[0]
